#!/usr/bin/env python3
"""Record the small chip trace that ``test_yardstick.py`` reduces.

    python3 perfbench/tests/record_trace.py --workload mobilenet_v2.b1.z020

Runs the cell's set-up, then traces a window of a few requests exactly as
a ``--trace 1`` run does, and writes the trace, gzipped, to
``tests/data/<workload>.xplane.pb.gz``. Needs the chip.
"""
import argparse
import gzip
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=0.15)
    args = p.parse_args(argv)
    import jax
    import harness
    import tracing
    harness.enable_compile_cache()
    cell = harness.load_cell(args.workload)
    harness.device_info(cell.chips)
    compiles = harness.CompileCounter()
    system = harness.build_system(cell, args.seed)
    harness.warm_up(system)
    with harness.profiler_trace() as trace_dir:
        window = harness.closed_loop(system, args.seconds, compiles, traced=True)
        jax.profiler.stop_trace()
        src = tracing.find_xplane(trace_dir)
        out = os.path.join(HERE, "data", f"{args.workload}.xplane.pb.gz")
        with open(src, "rb") as f, gzip.open(out, "wb", compresslevel=9) as g:
            shutil.copyfileobj(f, g)
    print(f"{out}: {len(window.latencies_s)} requests, "
          f"{os.path.getsize(out)} bytes; layer paths "
          f"{sorted(set(system.ex.layer_paths.values()))}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
