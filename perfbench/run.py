#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cells, metrics and bounds are in ``BENCHMARK.json`` at the root of the
checkout; ``harness.py`` says how a run goes. The last line of stdout is
one JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``:
each number compared for ``correct`` beside its limit). The last lines
of stderr give the same checks. Without a TPU, or with fewer chips than
the cell asks for, the run exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# JAX's compile cache stays inside the checkout, at a fixed path; set
# before JAX is imported, so the program under test finds the same one.
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        import harness
        result, notes = harness.run(args.workload, args.seed, args.seconds,
                                    bool(args.trace), T_START)
    except Exception as e:  # any failure: non-zero exit, no result line
        import traceback
        traceback.print_exc()
        print(f"perfbench: FAILED: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        return 1
    for line in notes:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
