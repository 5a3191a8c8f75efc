"""Back-to-back co-simulations of the fleet that the configuration's
script leaves, each over a sweep of rate fractions drawn from the seed."""

from __future__ import annotations

from typing import List

import numpy as np

import base
import deploy
import generator
import roofline


class Cosimulate(base.BackToBack):

    def setup(self, seconds: float) -> None:
        lib = deploy.library(self.cfg)
        self.ctl = deploy.controller(self.cfg, lib)
        for entry in self.cfg["script"]:
            self.ctl.apply(deploy.script_event(self.cfg, self.ctl, entry))
        t = self.traffic
        rng = generator.draws(self.seed, "fractions")
        self.fractions = np.sort(rng.uniform(
            t["fraction_low"], t["fraction_high"],
            size=(int(t["sweeps"]), int(t["rates"]))), axis=1)
        self.opts = dict(duration=float(t["duration"]), dt=float(t["dt"]),
                         warmup=float(t["warmup"]),
                         latency_sample_every=float(t["sample_every"]))
        self.ctl.cosimulate(fractions=self.fractions[0], **self.opts)
        self.types = deploy.dag_types(self.cfg)
        live = [deploy.dag_facts(self.cfg, self.types[n], n,
                                 self.ctl.entry(n).schedule.mapping,
                                 self.fractions[0] * self.ctl.entry(n).omega)
                for n in self.ctl.dag_names
                if self.ctl.entry(n).schedule is not None
                and self.ctl.entry(n).omega > 0]
        self.cells = (len(live) * int(t["rates"])
                      * int(self.opts["duration"] / self.opts["dt"]))
        self.scan_work = roofline.scan_work(
            live, self.cfg["profiles"], duration=self.opts["duration"],
            dt=self.opts["dt"], sample_every=self.opts["latency_sample_every"])

    def run(self, seconds: float, between=None) -> None:
        fr = self.fractions
        self._loop(seconds, lambda n: self.ctl.cosimulate(
            fractions=fr[n % len(fr)], **self.opts), "bench.cosim", between)

    def cells_of(self, out) -> float:
        return self.cells

    def check(self, control: bool = False) -> List[base.Check]:
        """Sampled calls against the reference; with ``control`` the
        reference one precision lower stands in for the program."""
        err, flips = 0.0, 0
        for rep in self._sampled():
            facts = [deploy.dag_facts(self.cfg, self.types[n], n,
                                      self.ctl.entry(n).schedule.mapping,
                                      rep.entries[n].omegas)
                     for n in rep.entries]
            want = self._simulate(facts, np.float64)
            got = ([base.surfaces(r) for r in self._simulate(
                facts, base.LOWER["cosimulation"])] if control else
                [_entry_surfaces(rep.entries[n]) for n in rep.entries])
            e, f = base.surface_gap(got, want)
            err, flips = max(err, e), flips + f
        return [self._check("surface_err", err),
                self._check("verdict_flips", flips)]


def _entry_surfaces(entry) -> dict:
    res = entry.results
    return {
        "latency_samples": np.array([r.latency_samples for r in res]).T,
        "latency_slope": np.array([r.latency_slope for r in res]),
        "stable": np.array([r.stable for r in res]),
        "queue_total": np.array([r.queue_total for r in res]),
        "slot_busy": {(s.vm, s.slot): np.array([r.slot_busy[s] for r in res])
                      for s in res[0].slot_busy}}


LOAD = Cosimulate
