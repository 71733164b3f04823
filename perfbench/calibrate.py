#!/usr/bin/env python3
"""Readings that the limits in ``checks/<workload>.json`` are set from.

    python3 perfbench/calibrate.py --workload <name> --seeds 1,2,3 [--seconds 3]

In one process, for each seed: the cell's program runs a short window at
the cell's own load, exactly as in a benchmark run, and every request's
logits are compared with the float32 reference (the program's reading,
the lower end of a limit). Then the control, the same reference computed
in bfloat16, is compared with the float32 reference on the same requests
(the upper end). One JSON line per seed. The benchmark's own runs never
run this.
"""
import argparse
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

import jax.numpy as jnp  # noqa: E402

import harness  # noqa: E402


def readings(cell, seeds, seconds: float, require_tpu: bool = True):
    """Yield one dict of readings per seed."""
    harness.enable_compile_cache()
    harness.device_info(cell.chips, require_tpu)
    compiles = harness.CompileCounter()
    prog = harness.build_program(cell)
    for seed in seeds:
        system = harness.build_system(cell, seed, prog=prog)
        harness.warm_up(system)
        window = harness.closed_loop(system, seconds, compiles)
        outputs, ids = window.outputs, window.image_ids
        layers, weights, images = system.layers, system.weights, system.images
        del system, window
        ref = harness.reference_logits(layers, weights, images, ids)
        low = harness.reference_logits(layers, weights, images, ids,
                                       dtype=jnp.bfloat16)
        prog_err = harness.logit_errors(outputs, ids, ref)
        ctrl_err = harness.logit_errors([low[i] for i in ids], ids, ref)
        yield {"seed": seed, "requests": len(ids),
               "program": _summary(prog_err, ids),
               "control": _summary(ctrl_err, ids)}


def _summary(errs, ids) -> dict:
    """Largest error, median, and the share of requests (and of distinct
    images) off by more than 1e-6, 1e-4 and 1e-2 of their largest logit."""
    out = {"max": max(errs), "median": statistics.median(errs)}
    for tol in (1e-6, 1e-4, 1e-2):
        bad = [e > tol for e in errs]
        out[f"share>{tol:g}"] = sum(bad) / len(bad)
        out[f"images>{tol:g}"] = len({i for i, b in zip(ids, bad) if b})
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True,
                   help="comma-separated whole numbers")
    p.add_argument("--seconds", type=float, default=3.0)
    args = p.parse_args(argv)
    cell = harness.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    for row in readings(cell, seeds, args.seconds):
        print(json.dumps(dict(row, workload=args.workload)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
