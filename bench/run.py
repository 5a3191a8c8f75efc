"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration file, its traffic file and its metrics are all
found by name from ``BENCHMARK.json`` at the root of the checkout.  The run
builds the system under test from ``src/``, warms up every shape the
traffic uses (set-up), drives the traffic for ``--seconds``, reads device
memory, then compares what the window produced with the plain references
under ``bench/reference/``.  With ``--trace 1`` the JAX profiler records
the first ``trace_seconds`` of the window (the traffic file's, else the
whole window), closed at a request boundary, and the per-layer metrics are
read over that traced window; with ``--trace 0`` the end-to-end metrics
are printed.

It runs only on a TPU: with another platform, or fewer chips than the cell
asks for, it prints why on standard error and exits 2 with no result.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, List, Mapping, Optional, Tuple  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))
# the TPU runtime otherwise writes its logs to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run.  ``window`` is the timed
    window; ``traced`` the part of it that the profiler recorded, over
    which the per-layer metrics are read."""

    load: object
    setup_s: float
    window: Tuple[float, float]
    compiles: object
    traced: Optional[Tuple[float, float]] = None
    trace: object = None
    peaks: Mapping = dataclasses.field(default_factory=dict)

    def items(self, kind: Optional[str] = None) -> list:
        t0, t1 = self.traced or self.window
        return [i for i in self.load.items
                if (kind is None or i.kind == kind)
                and t0 <= i.start and i.end <= t1]

    @property
    def device_trace(self):
        """The trace summary, or None where there is none or it lost
        events."""
        return self.trace if self.trace is not None and \
            self.trace.complete else None


def read_json(path: pathlib.Path):
    return json.loads(path.read_text())


def _module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(name: str):
    return _module(BENCH / "metrics" / f"{name}.py", f"metric_{name}").read


def load_class(name: str):
    """The load a traffic file names, from ``bench/loads/<name>.py``."""
    return _module(BENCH / "loads" / f"{name}.py", f"load_{name}").LOAD


def cell_files(workload: str):
    """``(benchmark, cell, configuration, traffic, limits)`` of a cell,
    each read from its own file by the names in ``BENCHMARK.json``."""
    bench = read_json(ROOT / "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return (bench, cell, read_json(ROOT / conf["file"]),
            read_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
            read_json(BENCH / "limits" / f"{workload}.json")["limits"])


class TracedWindow:
    """The profiler's window: opened with the timed window and closed at
    the first request boundary ``seconds`` after it opened (or when the
    timed window ends, whichever comes first)."""

    def __init__(self, log_dir: str, seconds: float):
        self.log_dir, self.seconds = log_dir, seconds
        self.span: Optional[Tuple[float, float]] = None

    def __enter__(self):
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.log_dir, profiler_options=options)
        import xplane
        self._mark = jax.profiler.TraceAnnotation(xplane.WINDOW)
        self._mark.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __call__(self, now: float) -> None:
        if self.span is None and now - self.t0 >= self.seconds:
            self.close()

    def close(self) -> None:
        if self.span is None:
            import jax
            self._mark.__exit__(None, None, None)
            self.span = (self.t0, time.perf_counter())
            jax.profiler.stop_trace()

    def __exit__(self, *exc) -> None:
        self.close()


def cell_metrics(bench: Mapping, cell: str, traced: bool) -> List[Mapping]:
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def devices_for(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, but JAX found platform "
                     f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)} ({devs[0].platform})")
    return devs[:chips]


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True) -> Dict:
    """One run of one cell; returns the result object."""
    bench, cell, config, traffic, limits = cell_files(workload)
    wanted = cell_metrics(bench, workload, trace)
    readers = {m["name"]: load_reader(m["name"]) for m in wanted}

    import jax
    devices = devices_for(int(cell["chips"]), require_tpu)
    from repro.jaxenv import init_compile_cache
    init_compile_cache()
    # cache every program, however fast it compiled, so that a second run
    # of the cell in this checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from compiles import CompileLog
    compiles = CompileLog()
    load = load_class(traffic["load"])(config, traffic, seed, devices, limits)

    load.setup(seconds)
    setup_s = time.perf_counter() - PROCESS_START

    summary = traced = None
    if trace:
        trace_seconds = float(traffic.get("trace_seconds", seconds))
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            with TracedWindow(log_dir, trace_seconds) as window:
                load.run(seconds, between=window)
            traced = window.span
            import xplane
            summary = xplane.read(log_dir)
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        if not summary.complete:
            print(f"bench/run.py: the trace lost events: "
                  f"{summary.requests_dark} of {summary.requests} requests "
                  f"have no launch, {summary.launches_dark} launches no "
                  f"operation; no device metric is read from it",
                  file=sys.stderr)
    else:
        load.run(seconds)
    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak(devices)}

    load.release()
    checks = load.check()

    import roofline
    run = Run(load=load, setup_s=setup_s, window=load.window,
              compiles=compiles, traced=traced, trace=summary,
              peaks=roofline.peaks(dev0.device_kind) if require_tpu else {})
    metrics = {}
    for m in wanted:
        value = readers[m["name"]](run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": all(c.ok for c in checks),
              "attempted": len(load.items),
              "failed": sum(1 for i in load.items if not i.ok),
              "metrics": metrics, "device": device}
    if run.device_trace is not None:
        import xplane
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = xplane.breakdown(summary)
    result["setup"] = {"compiles": len(compiles.times),
                       "cache_hits": compiles.cache_hits,
                       "cache_misses": compiles.cache_misses,
                       "compiles_in_window": compiles.within(load.window)}
    if summary is not None:
        result["trace"] = {"requests": summary.requests,
                           "requests_dark": summary.requests_dark,
                           "launches": sum(summary.modules_n.values()),
                           "launches_dark": summary.launches_dark}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as err:
        print(f"bench/run.py: {err}; nothing was run", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
