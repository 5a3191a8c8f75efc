"""Unified telemetry layer: tracing, metrics, scoreboard, auto-recal.

Determinism pins mirror ``test_chaos``: everything time-sensitive runs on
a :class:`VirtualClock` through the :mod:`repro.obs.clock` seam, so span
timelines are *bit*-identical across replays of the same chaos seed.
"""

import json
import math
import os
import pathlib
import time
from types import SimpleNamespace

import pytest

from repro import obs
from repro.analysis import verify_autorecal, verify_tracer, verify_trace
from repro.core import (DagArrive, EventTrace, FleetController, ModelLibrary,
                        ModelRefresh, PerfModel, RateChange, diamond_dag,
                        linear_dag, paper_library, rate_error)
from repro.core.calibrate import AutoRecalPolicy
from repro.core.perfmodel import ModelPoint
from repro.core.profiler import LiveTrialRunner
from repro.obs import (MetricsRegistry, Scoreboard, SpanRecord, Tracer,
                       observe_controller_record)
from repro.obs.clock import use_clock
from repro.obs.scoreboard import MEASURED, PLANNED, SIMULATED
from repro.obs.trace import _NULL_SPAN, spans_from_jsonl, spans_to_chrome
from repro.runtime import FaultPlan, LiveFleet, VirtualClock
from repro.runtime.chaos import Fault, FaultKind

BUDGET = 24


@pytest.fixture
def fresh_obs():
    """Swap in a fresh enabled tracer + reset global registry; restore."""
    prev = obs.set_tracer(Tracer(enabled=True))
    obs.REGISTRY.reset()
    obs.REGISTRY.enable()
    yield obs.get_tracer()
    obs.REGISTRY.disable()
    obs.REGISTRY.reset()
    obs.set_tracer(prev)


def _trace():
    return EventTrace([
        (0.0, DagArrive("d1", diamond_dag(), max_rate=80.0)),
        (1.0, DagArrive("d2", diamond_dag(), max_rate=60.0)),
        (2.0, RateChange("d1", 50.0)),
    ])


def _bursty_plan(seed=7):
    return FaultPlan.from_seed(
        seed, dags=["d1", "d2"], tasks=["b", "c"], horizon_frames=20,
        operator_errors=2, slowdowns=2, drops=1)


def _scaled(lib, factor):
    out = ModelLibrary({})
    for kind in lib.kinds():
        m = lib[kind]
        pts = [ModelPoint(p.tau, p.rate * (1.0 if m.static else factor),
                          p.cpu, p.mem) for p in m.points]
        out.add(PerfModel(kind, pts, static=m.static))
    return out


# -- clock seam --------------------------------------------------------------

def test_clock_seam_defaults_to_wall():
    assert not obs.clock.is_virtual()
    a, b = obs.clock.now(), obs.clock.now()
    assert b >= a


def test_clock_seam_install_and_restore():
    vc = VirtualClock()
    with use_clock(vc):
        assert obs.clock.is_virtual()
        t0 = obs.clock.now()
        obs.clock.sleep(2.5)
        assert obs.clock.now() == t0 + 2.5
    assert not obs.clock.is_virtual()


def test_clock_seam_nesting_restores_previous():
    outer, inner = VirtualClock(), VirtualClock()
    inner.sleep(10.0)
    with use_clock(outer):
        with use_clock(inner):
            assert obs.clock.now() == 10.0
        assert obs.clock.now() == 0.0
    assert not obs.clock.is_virtual()


# -- tracer ------------------------------------------------------------------

def test_disabled_span_is_shared_noop():
    tr = Tracer(enabled=False)
    s1, s2 = tr.span("a"), tr.span("b", x=1)
    assert s1 is s2                      # the shared null span: no alloc
    with s1:
        pass
    assert len(tr) == 0


def test_span_nesting_depths_and_attrs(fresh_obs):
    with use_clock(VirtualClock()):
        with obs.span("outer", dag="d1"):
            with obs.span("inner") as s:
                s.set(result=7)
    spans = fresh_obs.spans
    assert [s.name for s in spans] == ["inner", "outer"]  # close order
    by_name = {s.name: s for s in spans}
    assert by_name["outer"].depth == 0
    assert by_name["inner"].depth == 1
    assert by_name["outer"].attr_dict() == {"dag": "d1"}
    assert by_name["inner"].attr_dict() == {"result": 7}
    assert all(s.t1 >= s.t0 for s in spans)
    assert verify_tracer(fresh_obs) == []


def test_trace_decorator_wraps_and_records(fresh_obs):
    @obs.trace("math.double")
    def double(x):
        """doc survives"""
        return 2 * x

    assert double(21) == 42
    assert double.__doc__ == "doc survives"
    assert [s.name for s in fresh_obs.spans] == ["math.double"]


def test_tracer_clear_and_signature(fresh_obs):
    with obs.span("a"):
        pass
    assert len(fresh_obs.signature()) == 1
    fresh_obs.clear()
    assert fresh_obs.signature() == ()


def test_chaos_replay_span_timeline_deterministic(lib):
    """Same chaos seed ⇒ bit-identical span timeline signatures."""
    def run():
        tracer = Tracer(enabled=True)
        prev = obs.set_tracer(tracer)
        try:
            fleet = LiveFleet(FleetController(lib, budget_slots=BUDGET),
                              fault_plan=_bursty_plan(),
                              clock=VirtualClock())
            fleet.replay(_trace())
        finally:
            obs.set_tracer(prev)
        return tracer

    run()                                # warm the global kernel cache
    a, b = run(), run()
    assert len(a.signature()) > 0
    assert a.signature() == b.signature()
    assert verify_tracer(a) == []


# -- metrics -----------------------------------------------------------------

def test_counter_gauge_and_label_identity():
    reg = MetricsRegistry(enabled=True)
    c = reg.counter("events", labels={"kind": "arrive"})
    assert reg.counter("events", labels={"kind": "arrive"}) is c
    assert reg.counter("events", labels={"kind": "depart"}) is not c
    c.inc()
    c.inc(2.0)
    assert c.value == 3.0
    with pytest.raises(ValueError):
        c.inc(-1.0)
    g = reg.gauge("cost")
    g.set(1.5)
    g.add(0.5)
    assert g.value == 2.0
    with pytest.raises(TypeError):
        reg.gauge("events", labels={"kind": "arrive"})  # kind clash


def test_disabled_registry_mutations_are_noops():
    reg = MetricsRegistry(enabled=False)
    c, g = reg.counter("c"), reg.gauge("g")
    h = reg.histogram("h")
    c.inc()
    g.set(9.0)
    h.observe(1.0)
    assert c.value == 0.0 and g.value == 0.0 and h.count == 0


def test_histogram_percentiles_pinned():
    reg = MetricsRegistry(enabled=True)
    h = reg.histogram("lat", buckets=(1.0, 10.0, 100.0))
    for v in range(1, 101):              # 1..100
        h.observe(float(v))
    assert h.count == 100
    assert h.sum == 5050.0
    # closest-rank linear interpolation: pos = q/100 * 99
    assert h.percentile(50) == pytest.approx(50.5)
    assert h.percentile(95) == pytest.approx(95.05)
    assert h.percentile(0) == 1.0
    assert h.percentile(100) == 100.0
    with pytest.raises(ValueError):
        h.percentile(101)


def test_prometheus_text_format():
    reg = MetricsRegistry(enabled=True)
    reg.counter("repro_events_total", help="Events.",
                labels={"kind": "arrive"}).inc(3)
    h = reg.histogram("repro_lat_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.prometheus_text()
    assert "# HELP repro_events_total Events." in text
    assert "# TYPE repro_events_total counter" in text
    assert 'repro_events_total{kind="arrive"} 3.0' in text
    assert "# TYPE repro_lat_seconds histogram" in text
    assert 'repro_lat_seconds_bucket{le="0.1"} 1' in text
    assert 'repro_lat_seconds_bucket{le="1.0"} 2' in text   # cumulative
    assert 'repro_lat_seconds_bucket{le="+Inf"} 3' in text
    assert "repro_lat_seconds_count 3" in text
    assert text.endswith("\n")


def test_collector_runs_before_snapshot():
    reg = MetricsRegistry(enabled=True)
    reg.register_collector(
        lambda r: r.gauge("pulled").set(42.0))
    snap = reg.snapshot()
    assert snap["pulled"]["value"] == 42.0


def test_registry_reset_keeps_registrations():
    reg = MetricsRegistry(enabled=True)
    c = reg.counter("c")
    c.inc(5)
    reg.reset()
    assert c.value == 0.0
    assert reg.counter("c") is c


def test_controller_record_bridge(fresh_obs):
    ctl = FleetController(paper_library(), budget_slots=BUDGET)
    ctl.apply(DagArrive("d1", diamond_dag(), max_rate=80.0))
    ctl.apply(DagArrive("d2", linear_dag(), max_rate=60.0))
    ctl.apply(RateChange("d1", 50.0))
    snap = obs.snapshot()
    assert snap['repro_controller_events_total{kind="DagArrive"}'][
        "value"] == 2.0
    assert snap['repro_controller_events_total{kind="RateChange"}'][
        "value"] == 1.0
    lat = snap["repro_replan_latency_seconds"]
    assert lat["count"] == 3 and lat["sum"] > 0.0
    assert "p50" in lat and "p95" in lat and "p99" in lat
    # re-ingesting the whole log doubles the event counters
    assert obs.bridge_controller_log(ctl.log) == 3
    snap2 = obs.snapshot()
    assert snap2['repro_controller_events_total{kind="DagArrive"}'][
        "value"] == 4.0


def test_scan_kernel_cache_collector(fresh_obs):
    from repro.core.simulator import scan_kernel_cache_stats
    snap = obs.snapshot()
    stats = scan_kernel_cache_stats()
    assert snap["repro_scan_kernel_cache_entries"]["value"] == float(
        stats["entries"])
    assert "repro_scan_kernel_cache_hit_ratio" in snap


def test_disabled_instrumentation_micro_budget():
    """Dormant telemetry must cost < 1% of a median replan latency."""
    obs.disable()
    reg = obs.REGISTRY
    assert not reg.enabled and not obs.tracing_enabled()

    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        with obs.span("x", kind="probe"):
            pass
    span_cost = (time.perf_counter() - t0) / n
    c = reg.counter("budget_probe_total")
    t0 = time.perf_counter()
    for _ in range(n):
        c.inc()
    inc_cost = (time.perf_counter() - t0) / n

    # per-event instrumentation: count real spans+samples on one replay
    tracer = Tracer(enabled=True)
    prev = obs.set_tracer(tracer)
    try:
        ctl = FleetController(paper_library(), budget_slots=BUDGET)
        ctl.apply(DagArrive("d1", diamond_dag(), max_rate=80.0))
        ctl.apply(DagArrive("d2", linear_dag(), max_rate=60.0))
        ctl.apply(RateChange("d1", 50.0))
    finally:
        obs.set_tracer(prev)
    latencies = sorted(r.replan_latency_s for r in ctl.log.records)
    median_latency = latencies[len(latencies) // 2]
    spans_per_event = max(1, len(tracer.spans) / len(ctl.log.records))
    # ~10 metric samples ride along per event (bridge counters/gauges)
    per_event = spans_per_event * span_cost + 10 * inc_cost
    assert per_event < 0.01 * median_latency, (
        f"dormant telemetry {per_event * 1e6:.2f}us/event >= 1% of "
        f"median replan latency {median_latency * 1e3:.3f}ms")


# -- scoreboard --------------------------------------------------------------

def test_scoreboard_residual_math_hand_pinned():
    b = Scoreboard()
    b.record("d", "rate", PLANNED, 100.0, t=0.0)
    b.record("d", "rate", SIMULATED, 90.0, t=1.0)
    b.record("d", "rate", PLANNED, 120.0, t=2.0)   # newer promise
    b.record("d", "rate", SIMULATED, 126.0, t=3.0)
    res = b.residuals("rate", SIMULATED, "d")
    assert [r.residual for r in res] == [-10.0, 6.0]
    assert res[0].relative == pytest.approx(-0.1)
    assert res[1].relative == pytest.approx(0.05)
    stats = b.summary("rate", SIMULATED)["d"]
    assert stats.n == 2
    assert stats.mean_abs == pytest.approx(8.0)
    assert stats.rmse == pytest.approx(math.sqrt((100.0 + 36.0) / 2.0))
    assert stats.max_abs == 10.0
    assert stats.mean_abs_relative == pytest.approx(0.075)
    assert not stats.exact
    assert b.planned_sustained() == {"d": True}    # last residual >= 0


def test_scoreboard_zero_promise_relative_is_nan_safe():
    b = Scoreboard()
    b.record("d", "rate", PLANNED, 0.0, t=0.0)
    b.record("d", "rate", MEASURED, 5.0, t=1.0)
    (r,) = b.residuals("rate", MEASURED, "d")
    assert math.isnan(r.relative)
    stats = b.summary("rate", MEASURED)["d"]
    assert stats.mean_abs_relative == 0.0          # NaNs excluded


def test_scoreboard_observation_without_promise_is_dropped():
    b = Scoreboard()
    b.record("d", "rate", SIMULATED, 50.0, t=0.0)
    assert b.residuals("rate", SIMULATED) == []


def test_fault_free_rail_residuals_exactly_zero(lib):
    ctl = FleetController(lib, budget_slots=BUDGET)
    ctl.apply(DagArrive("d1", diamond_dag(), max_rate=80.0))
    ctl.apply(DagArrive("d2", linear_dag(), max_rate=60.0))
    b = Scoreboard()
    assert b.ingest_controller(ctl, t=0.0) == 2
    assert b.ingest_cosim(ctl.cosimulate(), t=1.0) == 2
    stats = b.summary("rate", SIMULATED)
    assert set(stats) == {"d1", "d2"}
    for s in stats.values():
        assert s.exact                  # bit-clean: max_abs == 0.0 exactly
        assert s.max_abs == 0.0
    assert b.planned_sustained() == {"d1": True, "d2": True}


# -- auto-recalibration ------------------------------------------------------

def _misprofiled_fleet(lib, **policy_kw):
    policy = AutoRecalPolicy(threshold=0.15, cooldown_events=2, **policy_kw)
    return LiveFleet(FleetController(_scaled(lib, 2.0), budget_slots=BUDGET),
                     fault_plan=FaultPlan.none(), clock=VirtualClock(),
                     truth=lib, auto_recal=policy)


def test_misprofiled_tables_trigger_auto_recalibration(lib):
    fleet = _misprofiled_fleet(lib)
    before = dict(fleet.ctl.models.items()) if hasattr(
        fleet.ctl.models, "items") else fleet.ctl.models
    rec = fleet.apply(DagArrive("d1", diamond_dag(), max_rate=4000.0),
                      at=0.0)
    assert rec.drift_magnitude > 0.15
    assert rec.drift_alerts >= 1
    assert rec.recalibration is not None
    assert rec.recalibration.recalibrated
    assert rec.recalibration.kind == "ModelRefresh"
    assert fleet.recal_ticks == [0]
    assert fleet.recalibrations and fleet.recalibrations[0].changed_kinds
    # the controller's tables were actually replaced and are closer to truth
    samples = fleet.measurements()
    assert rate_error(fleet.ctl.models, samples) < 0.15
    assert verify_autorecal(fleet) == []


def test_recalibration_respects_cooldown(lib):
    fleet = _misprofiled_fleet(lib)
    events = [DagArrive("d1", diamond_dag(), max_rate=4000.0),
              RateChange("d1", 1500.0),
              RateChange("d1", 1200.0)]
    for i, ev in enumerate(events):
        fleet.apply(ev, at=float(i))
    ticks = fleet.recal_ticks
    assert ticks                        # at least the first recal fired
    assert all(b - a >= 2 for a, b in zip(ticks, ticks[1:]))
    assert verify_autorecal(fleet) == []


def test_fault_free_rail_never_recalibrates(lib):
    fleet = LiveFleet(FleetController(lib, budget_slots=BUDGET),
                      fault_plan=FaultPlan.none(), clock=VirtualClock(),
                      auto_recal=AutoRecalPolicy(threshold=0.15,
                                                 cooldown_events=2))
    for i, ev in enumerate([DagArrive("d1", diamond_dag(), max_rate=80.0),
                            RateChange("d1", 60.0)]):
        rec = fleet.apply(ev, at=float(i))
        # rate_error is float math: noise-level only, far below threshold
        assert rec.drift_magnitude < 1e-12
        assert rec.recalibration is None
    assert fleet.recal_ticks == []


def test_controller_recalibrate_rebuilds_every_schedule(lib):
    ctl = FleetController(lib, budget_slots=BUDGET)
    ctl.apply(DagArrive("d1", diamond_dag(), max_rate=80.0))
    ctl.apply(DagArrive("d2", linear_dag(), max_rate=60.0))
    rec = ctl.recalibrate(_scaled(lib, 1.1), kinds=("pi",), reason="test")
    assert rec.kind == "ModelRefresh"
    assert rec.recalibrated
    assert set(rec.changed) == {"d1", "d2"}   # nothing untouched
    assert ctl.models["pi"] is not lib["pi"]


# -- verifier mutation tests -------------------------------------------------

def test_verify_tracer_clean_then_unclosed_span():
    tr = Tracer(enabled=True)
    prev = obs.set_tracer(tr)
    try:
        with obs.span("ok"):
            pass
        assert verify_tracer(tr) == []
        leaked = obs.span("leaked")
        leaked.__enter__()              # mutation: never exited
        out = verify_tracer(tr)
        assert [v.code for v in out] == ["OBS_SPAN_UNCLOSED"]
        leaked.__exit__(None, None, None)
        assert verify_tracer(tr) == []
    finally:
        obs.set_tracer(prev)


def test_verify_tracer_flags_clock_swap_mid_span():
    tr = Tracer(enabled=True)
    s = tr.span("swapped")
    s.__enter__()                       # t0 from the wall clock (large)
    with use_clock(VirtualClock()):     # t1 from a fresh virtual clock: 0.0
        s.__exit__(None, None, None)
    out = verify_tracer(tr)
    assert [v.code for v in out] == ["OBS_SPAN_NEGATIVE"]


def test_verify_autorecal_flags_thrash():
    policy = AutoRecalPolicy(threshold=0.1, cooldown_events=3)
    thrashing = SimpleNamespace(auto_recal=policy, recal_ticks=[0, 1])
    out = verify_autorecal(thrashing)
    assert [v.code for v in out] == ["CAL_AUTO_RECAL_LOOP"]
    spaced = SimpleNamespace(auto_recal=policy, recal_ticks=[0, 5])
    assert verify_autorecal(spaced) == []
    assert verify_autorecal(SimpleNamespace(auto_recal=None,
                                            recal_ticks=[0, 1])) == []


def test_verify_trace_accepts_model_refresh():
    ok = EventTrace([(0.0, DagArrive("d", diamond_dag())),
                     (1.0, ModelRefresh(kinds=("pi",), reason="drift"))])
    assert verify_trace(ok) == []
    bad = EventTrace([(0.0, ModelRefresh(kinds=(7,)))])
    assert [v.code for v in verify_trace(bad)] == ["TRC_BAD_EVENT"]


# -- export + CLI ------------------------------------------------------------

def test_jsonl_round_trip(fresh_obs):
    with use_clock(VirtualClock()):
        with obs.span("a", dag="d1"):
            with obs.span("b"):
                pass
    text = fresh_obs.to_jsonl()
    assert len(text.splitlines()) == 2
    assert spans_from_jsonl(text) == fresh_obs.spans


def test_chrome_export_shape(fresh_obs):
    with use_clock(VirtualClock()):
        with obs.span("replan", dag="d1"):
            obs.clock.sleep(0.25)
    doc = fresh_obs.to_chrome()
    assert doc["displayTimeUnit"] == "ms"
    (ev,) = doc["traceEvents"]
    assert ev["ph"] == "X"
    assert ev["name"] == "replan"
    assert ev["ts"] == 0.0
    assert ev["dur"] == 0.25 * 1e6      # microseconds
    assert ev["args"] == {"dag": "d1"}
    assert spans_to_chrome(fresh_obs.spans) == doc


def test_export_files_round_trip(tmp_path, fresh_obs):
    with obs.span("x"):
        pass
    jsonl = tmp_path / "spans.jsonl"
    chrome = tmp_path / "trace.json"
    n = obs.export_tracer(fresh_obs, jsonl=str(jsonl), chrome=str(chrome))
    assert n == 1
    assert obs.read_jsonl(str(jsonl)) == fresh_obs.spans
    doc = json.loads(chrome.read_text())
    assert len(doc["traceEvents"]) == 1


def test_cli_smoke_writes_perfetto_json(tmp_path, capsys):
    from repro.obs.__main__ import main
    out = tmp_path / "obs_trace.json"
    jsonl = tmp_path / "spans.jsonl"
    rc = main(["export", "--smoke", "--out", str(out),
               "--jsonl", str(jsonl)])
    assert rc == 0
    doc = json.loads(out.read_text())
    assert doc["traceEvents"]
    names = {e["name"] for e in doc["traceEvents"]}
    assert "controller.apply" in names
    assert "plan" in names
    # conversion mode reads the jsonl back
    out2 = tmp_path / "converted.json"
    assert main(["export", str(jsonl), "--out", str(out2)]) == 0
    assert (json.loads(out2.read_text())["traceEvents"]
            == doc["traceEvents"])
    captured = capsys.readouterr()
    assert "tracer verified clean" in captured.out


def test_cli_requires_input_without_smoke(tmp_path):
    from repro.obs.__main__ import main
    assert main(["export", "--out", str(tmp_path / "x.json")]) == 2


# -- LiveTrialRunner clock seam ----------------------------------------------

def test_trial_runner_virtual_mode_deterministic():
    def run_once():
        clock = VirtualClock()
        runner = LiveTrialRunner(lambda: (lambda: None), clock=clock,
                                 trial_seconds=0.5, service_time=0.004)
        result = runner(2, 100.0)
        return result, clock.now()

    (a, ta), (b, tb) = run_once(), run_once()
    assert a.latencies == b.latencies
    assert a.cpu == b.cpu and a.mem == b.mem
    assert a.supported_rate == b.supported_rate
    assert ta == tb > 0.0               # the trial advanced virtual time
    # 2 servers x 4ms service vs 10ms arrivals: stable, latency == service
    assert all(l == pytest.approx(0.004) for l in a.latencies)
    assert a.supported_rate == pytest.approx(100.0, rel=0.05)


def test_trial_runner_virtual_mode_through_seam():
    with use_clock(VirtualClock()):
        runner = LiveTrialRunner(lambda: (lambda: None),
                                 trial_seconds=0.5, service_time=0.002)
        result = runner(1, 50.0)
    assert result.supported_rate > 0.0


def test_trial_runner_virtual_requires_service_time():
    runner = LiveTrialRunner(lambda: (lambda: None),
                             clock=VirtualClock())
    with pytest.raises(ValueError, match="service_time"):
        runner(1, 50.0)


def test_trial_runner_live_path_still_works():
    runner = LiveTrialRunner(lambda: (lambda: None), trial_seconds=0.05)
    result = runner(1, 200.0)
    assert result.supported_rate > 0.0
    assert 0.0 <= result.cpu <= 1.0
    assert len(result.latencies) > 0


# -- bench envelope ----------------------------------------------------------

def test_write_bench_json_envelope(tmp_path):
    from benchmarks.common import BENCH_SCHEMA_VERSION, write_bench_json
    path = tmp_path / "BENCH_x.json"
    payload = write_bench_json(str(path), "unit_test",
                               {"speedup": 2.0}, units={"speedup": "x"})
    on_disk = json.loads(path.read_text())
    assert on_disk == payload
    assert on_disk["schema_version"] == BENCH_SCHEMA_VERSION
    assert on_disk["bench"] == "unit_test"
    assert on_disk["metrics"] == {"speedup": 2.0}
    assert on_disk["units"] == {"speedup": "x"}
    assert set(on_disk["host"]) == {"python", "platform", "machine",
                                    "cpu_count"}
    assert isinstance(on_disk["git_sha"], str) and on_disk["git_sha"]
    assert on_disk["created_unix_s"] > 0


# -- spans in the profiler trace, and the layers' spans and counters ---------

def _profiled(tmp_path, fn):
    """Run ``fn`` under the JAX profiler; the host events of every line as
    ``(name, start_ns, end_ns)``, in order of start."""
    import jax
    from jax.profiler import ProfileData
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    events = [(e.name, int(e.start_ns), int(e.end_ns))
              for plane in ProfileData.from_file(str(path)).planes
              if plane.name.startswith("/host")
              for line in plane.lines for e in line.events]
    return sorted(events, key=lambda e: e[1])


def _named(events, name):
    return [e for e in events if e[0] == name]


def test_enabled_span_reaches_the_profiler_trace_nested(fresh_obs, tmp_path):
    def work():
        with obs.span("obs.outer", dag="d"):
            with obs.span("obs.inner"):
                time.sleep(0.001)

    events = _profiled(tmp_path, work)
    (outer,) = _named(events, "obs.outer")
    (inner,) = _named(events, "obs.inner")
    assert outer[1] <= inner[1] and inner[2] <= outer[2]
    # the name alone reaches the trace; the attributes stay in the record
    rec = {s.name: s for s in fresh_obs.spans}
    assert rec["obs.outer"].attr_dict() == {"dag": "d"}
    assert rec["obs.inner"].depth == rec["obs.outer"].depth + 1


def test_disabled_span_never_reaches_the_profiler_trace(tmp_path):
    obs.disable()
    spans = []

    def work():
        with obs.span("obs.dormant") as s:
            spans.append(s)

    events = _profiled(tmp_path, work)
    assert spans == [_NULL_SPAN]
    assert not _named(events, "obs.dormant")


def test_obs_imports_and_traces_without_jax():
    import subprocess
    import sys
    code = ("import sys\n"
            "import repro.obs as o\n"
            "o.enable()\n"
            "with o.span('x'):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules, 'repro.obs imported jax'\n"
            "print(len(o.get_tracer().spans))\n")
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env={**os.environ, "PYTHONPATH": src},
                         timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "1"


def _executor(lib, **kwargs):
    from repro.core import plan
    from repro.runtime import StreamExecutor
    from repro.runtime.stream import SyntheticSource
    # at 120 tuples/s one task's threads span two slots: a gather
    schedule = plan(diamond_dag(), 120, lib, allocator="mba", mapper="sam")
    ex = StreamExecutor(schedule, lib, clock=VirtualClock(), **kwargs)
    source = SyntheticSource(120, batch=16, seed=3, clock=VirtualClock())
    return ex, list(source.frames(n_frames=2))


STAGES = ("executor.route", "executor.place", "executor.launch",
          "executor.service", "executor.gather", "executor.sink_wait")


def test_process_frame_records_the_executor_span_tree(lib, fresh_obs):
    ex, frames = _executor(lib)
    assert ex.process_frame(frames[0], 0.0)[0] == "ok"
    spans = fresh_obs.spans
    (root,) = [s for s in spans if s.name == "executor.frame"]
    assert root.attr_dict() == {"seq": frames[0].seq}
    kids = sorted((s for s in spans if s.depth == root.depth + 1),
                  key=lambda s: s.t0)
    assert all(root.t0 <= s.t0 and s.t1 <= root.t1 for s in kids)
    names = [s.name for s in kids]
    assert set(names) == set(STAGES)
    assert names[0] == "executor.route" and names[-1] == "executor.sink_wait"
    # every part is placed, then launched without a wait; the frame's
    # only waits are at its sinks, which close it
    for i, name in enumerate(names):
        if name == "executor.launch":
            assert names[i - 1] == "executor.place"
    assert names.index("executor.sink_wait") > max(
        i for i, name in enumerate(names) if name != "executor.sink_wait")


def test_process_frame_spans_reach_the_profiler_trace(lib, fresh_obs,
                                                     tmp_path):
    ex, frames = _executor(lib)
    ex.process_frame(frames[0], 0.0)      # compiles every program first
    events = _profiled(tmp_path, lambda: ex.process_frame(frames[1], 0.0))
    (root,) = _named(events, "executor.frame")
    traced = [e[0] for e in events
              if e[0] in STAGES and root[1] <= e[1] and e[2] <= root[2]]
    (rec,) = [s for s in fresh_obs.spans if s.name == "executor.frame"
              and s.attr_dict() == {"seq": frames[1].seq}]
    recorded = [s.name for s in sorted(fresh_obs.spans, key=lambda s: s.t0)
                if s.name in STAGES and rec.t0 <= s.t0 and s.t1 <= rec.t1]
    # every recorded stage, and nothing else, is in the trace, in order
    assert set(traced) == set(STAGES)
    assert traced == recorded


@pytest.mark.parametrize("fault, status, counter", [
    (Fault(FaultKind.OPERATOR_ERROR, frame=0, dag="d", task="p", count=3),
     "failed", "repro_frames_failed_total"),
    (Fault(FaultKind.DROP_FRAME, frame=0, dag="d"),
     "shed", "repro_frames_shed_total"),
])
def test_process_frame_counts_frames_without_run(lib, fresh_obs, fault,
                                                 status, counter):
    from repro.runtime import FaultInjector
    ex, frames = _executor(lib, faults=FaultInjector(
        FaultPlan(faults=(fault,)), "d"))
    frames[0].seq = 0
    assert ex.process_frame(frames[0], 0.0)[0] == status
    snap = obs.snapshot()
    assert snap["repro_frames_total"]["value"] == 1
    assert snap[counter]["value"] == 1
    if status == "failed":
        # every part of the task fails past its retries
        assert snap["repro_frames_retried_total"]["value"] == \
            ex._run_counters["retries"] > 0
        assert snap["repro_tuples_lost_total"]["value"] == \
            ex._run_counters["tuples_lost"] == frames[0].size


def _cosimulate(lib):
    from repro.core import plan_fleet, simulate_fleet
    fp = plan_fleet({"linear": linear_dag(), "diamond": diamond_dag()}, lib,
                    budget_slots=12)
    simulate_fleet(fp, lib, duration=2.0, dt=0.1, engine="scan")


def _search(lib):
    from repro.core.search import search_mapping
    search_mapping(diamond_dag(), 100, lib, n_moves=2, rate_fractions=[1.0],
                   duration=1.0, dt=0.5, seed=5)


@pytest.mark.parametrize("call, root, children", [
    (_cosimulate, "cosim",
     ("cosim.build", "cosim.inputs", "cosim.launch", "cosim.wait",
      "cosim.fetch", "cosim.results", "cosim.resources")),
    (_search, "search_mapping",
     ("search.pool", "search.candidates", "search.index", "search.pack",
      "search.launch", "search.wait", "search.fetch", "search.judge")),
])
def test_cosimulation_and_search_record_every_stage(lib, fresh_obs, call,
                                                     root, children):
    call(lib)
    spans = fresh_obs.spans
    (top,) = [s for s in spans if s.name == root]
    kids = [s for s in spans if s.depth == top.depth + 1
            and top.t0 <= s.t0 and s.t1 <= top.t1]
    assert {s.name for s in kids} == set(children)
