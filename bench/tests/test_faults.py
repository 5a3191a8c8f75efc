"""A run with the timed path broken underneath reads ``correct`` false.

Each test drives a whole run of a cell on the CPU (the harness's look for
a TPU skipped) with one fault planted in the program: an answer altered
where it is produced, half of a batch left out, or a step that returns
its state unchanged.  No cell spans chips, so none has an exchange
between chips to leave out."""

import numpy as np
import pytest

import run as bench_run

SEED = 2 ** 31 + 99


def _correct(workload: str) -> bool:
    result = bench_run.run_cell(workload, SEED, 1.5, False,
                                require_tpu=False)
    assert result["attempted"] > 0
    return result["correct"]


def test_operator_output_altered(monkeypatch):
    from repro.runtime import operators
    pi = operators.OPERATORS["pi"]
    monkeypatch.setitem(operators.OPERATORS, "pi",
                        lambda b: {**pi(b), "pi": pi(b)["pi"] * 1.001})
    assert not _correct("traffic.stream")


def test_half_of_each_frame_left_out(monkeypatch):
    from repro.runtime.executor import StreamExecutor
    process = StreamExecutor.process_frame

    def half(self, frame, interval):
        frame.arrays = {k: v[: len(v) // 2] for k, v in frame.arrays.items()}
        return process(self, frame, interval)
    monkeypatch.setattr(StreamExecutor, "process_frame", half)
    assert not _correct("traffic.stream")


def test_scan_returns_its_state_unchanged(monkeypatch):
    from repro.core.simulator import SweepBatch
    run_scan = SweepBatch._run_scan

    def unchanged(self, caps, src_rate, steps, sample_every, s0, dt):
        out = run_scan(self, caps, src_rate, steps, sample_every, s0, dt)
        return tuple(np.zeros_like(a) for a in out)
    monkeypatch.setattr(SweepBatch, "_run_scan", unchanged)
    assert not _correct("fig7.cosim")


def test_scan_answer_altered(monkeypatch):
    from repro.core.simulator import SweepBatch
    run_scan = SweepBatch._run_scan

    def altered(self, *args):
        q, busy, srv, realized, lat = run_scan(self, *args)
        return q, busy, srv, realized, lat * (1 + 1e-6)
    monkeypatch.setattr(SweepBatch, "_run_scan", altered)
    assert not _correct("fig7.cosim")


def test_search_kernel_answer_altered(monkeypatch):
    from repro.core import search
    get = search.get_scan_kernel

    def altered_kernel(*args, **kwargs):
        fn = get(*args, **kwargs)

        def wrapped(*a, **k):
            q, busy, srv, realized, lat = fn(*a, **k)
            return q, busy, srv, realized, lat * (1 + 1e-6)
        return wrapped
    monkeypatch.setattr(search, "get_scan_kernel", altered_kernel)
    assert not _correct("traffic.search")
