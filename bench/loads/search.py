"""Back-to-back mapper searches for one DAG at the configuration's rate,
cycling over pool seeds drawn from the seed; every pool seed is warmed
up."""

from __future__ import annotations

from typing import List

import numpy as np

import base
import deploy
import generator


class Search(base.BackToBack):

    def setup(self, seconds: float) -> None:
        from repro.core.search import search_mapping
        self.lib = deploy.library(self.cfg)
        self.dag = deploy.dataflow(self.cfg, self.cfg["dag"])
        t = self.traffic
        self.pool_seeds = generator.draws(self.seed, "pools").integers(
            0, 2 ** 31 - 1, size=int(t["pool_seeds"])).tolist()
        self.opts = dict(allocator=self.cfg["allocator"],
                         vm_sizes=self.cfg["vm_family"],
                         duration=float(t["duration"]), dt=float(t["dt"]),
                         warmup=float(t["warmup"]),
                         latency_sample_every=float(t["sample_every"]),
                         rate_fractions=np.linspace(
                             t["fraction_low"], t["fraction_high"],
                             int(t["rates"])))
        self.rate = float(self.cfg["rate"])
        self.search = search_mapping
        for s in self.pool_seeds:
            search_mapping(self.dag, self.rate, self.lib, seed=s, **self.opts)

    def run(self, seconds: float, between=None) -> None:
        seeds = self.pool_seeds

        def request(n):
            return self.search(self.dag, self.rate, self.lib,
                               seed=seeds[n % len(seeds)], **self.opts)
        self._loop(seconds, request, "bench.search", between)

    def cells_of(self, out) -> float:
        return (len(out.candidates) * int(self.traffic["rates"])
                * int(self.opts["duration"] / self.opts["dt"]))

    def check(self, control: bool = False) -> List[base.Check]:
        """Every candidate of sampled requests against the reference, and
        the winner against the reference's ranking; with ``control`` the
        reference one precision lower stands in for the program."""
        err, flips, wrong_winner = 0.0, 0, 0
        for ranked in self._sampled():
            got, want, ranks = [], [], []
            for c in ranked.candidates:
                facts = [deploy.dag_facts(self.cfg, self.cfg["dag"], c.name,
                                          c.mapping, ranked.omegas)]
                r = self._simulate(facts, np.float64)[0]
                if control:
                    lo = self._simulate(facts, base.LOWER["cosimulation"])[0]
                    got.append({"latency_slope": lo.latency_slope,
                                "stable": lo.stable})
                else:
                    got.append({"latency_slope": np.asarray(c.latency_slope),
                                "stable": np.asarray(c.stable)})
                want.append(r)
                ok = np.asarray(ranked.omegas)[r.stable]
                ranks.append((-(float(ok.max()) if ok.size else 0.0),
                              len({s for g in facts[0].groups.values()
                                   for s in g}), c.name))
            e, f = base.surface_gap(got, want)
            err, flips = max(err, e), flips + f
            if not control:
                wrong_winner += int(min(ranks)[2] != ranked.best.name)
        return [self._check("surface_err", err),
                self._check("verdict_flips", flips),
                self._check("winner_mismatch", wrong_winner)]


LOAD = Search
