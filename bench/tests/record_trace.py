"""Record the small TPU trace that ``test_xplane.py`` reads.

    python3 bench/tests/record_trace.py <out.xplane.pb>

On one chip: one traffic-app frame and one small co-simulation under the
profiler, inside a ``bench.window`` annotation, each inside its own
benchmark span; prints each plane's lines and the events' names.
"""

from __future__ import annotations

import pathlib
import shutil
import sys
import tempfile
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(out: str) -> int:
    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    import deploy
    import generator
    import run
    import xplane
    from repro.core import plan
    from repro.runtime import StreamExecutor, WallClock
    from repro.runtime.stream import MicroBatch

    cfg = run.cell_files("traffic.stream")[2]
    lib = deploy.library(cfg)
    # the recorded trace was taken at 200 tuples/s (16 slots on 4 VMs)
    sched = plan(deploy.dataflow(cfg, "traffic"), 200.0, lib,
                 allocator="mba", mapper="sam", vm_sizes="azure-d")
    ex = StreamExecutor(sched, lib, clock=WallClock(),
                        devices=jax.devices()[:1])
    arrays = generator.frame_payload(7, 0, 16, 256)
    ex.process_frame(MicroBatch(0, arrays, time.perf_counter()), 0.0)
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    with TraceAnnotation("bench.window"):
        time.sleep(0.002)
        with TraceAnnotation("bench.frame"):
            ex.process_frame(MicroBatch(1, arrays, time.perf_counter()), 0.0)
        time.sleep(0.002)
    jax.profiler.stop_trace()
    path = xplane.find_xplane(log_dir)
    for plane in ProfileData.from_file(str(path)).planes:
        print("plane", plane.name)
        for line in plane.lines:
            names = sorted({e.name for e in line.events})
            print("  line", repr(line.name), len(names), names[:12])
    shutil.copy(path, out)
    shutil.rmtree(log_dir, ignore_errors=True)
    print(xplane.summarize(ProfileData.from_file(out).planes))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
