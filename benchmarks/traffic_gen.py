"""Open-loop traffic generator for the serving fleet.

Drives a :class:`repro.serve.fleet.FleetServer` with Poisson arrivals
(open loop: the arrival process does not wait for completions, so the
fleet sees real queueing pressure) over a configurable prompt /
new-token mix, then emits ``serve.fleet.*`` bench rows in the repo's
CSV row format (requests/sec, p50/p99 latency from
``obs.METRICS``, worker utilization from the per-worker busy-time
series).

Two hard gates ride in the rows (the CI ``serving`` job fails on
either):

* **bit-exactness** — every completed request's tokens must equal the
  single-process ``ExecutorSession`` oracle
  (``engine.greedy_generate_compiled`` on a dedicated batch-1
  session);
* **continuous beats serial** — the continuous-batching policy must
  sustain at least the requests/sec of serial per-request dispatch on
  the same fleet and workload.

  PYTHONPATH=src python benchmarks/traffic_gen.py --smoke \
      --workers golden:thread,pallas:thread | tee serve-fleet.csv

Workers on a TPU host are threads: the chip belongs to the one process
that holds it, so the fleet refuses subprocess workers there.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import csv
import json
import sys
import time

import numpy as np

from repro.obs import METRICS
from repro.serve.engine import greedy_generate_compiled
from repro.serve.fleet import FleetServer, RequestFailed


def _parse_workers(spec: str):
    """``golden:thread,pallas:subprocess`` -> fleet worker triples."""
    out = []
    for i, part in enumerate(x for x in spec.split(",") if x):
        backend, _, mode = part.partition(":")
        out.append((f"w{i}", backend, mode or "thread"))
    return out


def _workload(args):
    """Deterministic request mix + Poisson inter-arrival gaps."""
    rng = np.random.default_rng(args.seed)
    reqs = []
    for _ in range(args.requests):
        s0 = int(rng.integers(1, args.prompt_len + 1))
        prompt = rng.integers(0, 512, s0).astype(np.int32)
        reqs.append((prompt, args.new_tokens))
    gaps = rng.exponential(1.0 / args.rate, args.requests)
    return reqs, gaps


def _oracle_outputs(args, reqs):
    """Single-process batch-1 oracle for every request (the hard
    bit-exactness reference: same program config, same weight seed)."""
    from repro.compiler import compile_decode_network
    from repro.compiler.runtime import ExecutorSession
    prog = compile_decode_network(args.arch, batch=1,
                                  max_seq=args.max_seq, opt_level=1)
    session = ExecutorSession(prog, backend="golden")
    session.bind_synthetic_all(seed=args.seed)
    outs = []
    for prompt, n_new in reqs:
        outs.append(np.asarray(greedy_generate_compiled(
            session, prompt[None, :], n_new))[0])
    return outs


def _drive(fleet: FleetServer, reqs, gaps, timeout_s: float):
    """Submit the workload open-loop; returns (outputs, wall_s,
    completed, failed). ``outputs[i]`` is None for failed requests."""
    # one warm-up request so JIT compile time is paid outside the
    # measured window (both policies pay it identically)
    fleet.submit(reqs[0][0], reqs[0][1]).result(timeout_s)
    METRICS.clear()
    futures = []
    arrivals = np.cumsum(gaps)
    t0 = time.perf_counter()
    for (prompt, n_new), at in zip(reqs, arrivals):
        delay = t0 + at - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        futures.append(fleet.submit(prompt, n_new))
    outputs, failed = [], 0
    for fut in futures:
        try:
            outputs.append(np.asarray(fut.result(timeout_s)))
        except (RequestFailed, concurrent.futures.TimeoutError):
            outputs.append(None)
            failed += 1
    wall = time.perf_counter() - t0
    return outputs, wall, len(futures) - failed, failed


def _utilization_pct(worker_ids, wall_s: float) -> float:
    busy_ms = sum(sum(METRICS.series(f"serve.fleet.worker.{w}.busy_ms"))
                  for w in worker_ids)
    return round(busy_ms / max(wall_s * 1e3 * len(worker_ids), 1e-9)
                 * 100, 1)


def run_policy(args, policy: str, reqs, gaps, oracle):
    workers = _parse_workers(args.workers)
    with FleetServer(args.arch, workers, batch_slots=args.slots,
                     max_seq=args.max_seq, seed=args.seed,
                     policy=policy,
                     step_timeout_s=args.step_timeout) as fleet:
        outputs, wall, completed, failed = _drive(
            fleet, reqs, gaps, args.request_timeout)
    exact = all(out is None or np.array_equal(out, ref)
                for out, ref in zip(outputs, oracle))
    blob = {
        "BENCH": "serve.fleet",
        "arch": args.arch,
        "policy": policy,
        "workers": len(workers),
        "slots": args.slots,
        "requests": len(reqs),
        "completed": completed,
        "failed": failed,
        "req_per_s": round(completed / max(wall, 1e-9), 2),
        "p50_ms": round(METRICS.percentile("serve.fleet.request_ms", 50), 1),
        "p99_ms": round(METRICS.percentile("serve.fleet.request_ms", 99), 1),
        "utilization_pct": _utilization_pct(
            [w[0] for w in workers], wall),
        "steps": METRICS.counter("serve.fleet.steps"),
        "bit_exact": exact,
    }
    row = (f"serve.fleet.{policy}.{args.arch}", wall * 1e6,
           json.dumps(blob, sort_keys=True))
    assert exact, (f"{policy}: fleet outputs diverge from the "
                   f"single-process oracle")
    assert completed == len(reqs), \
        f"{policy}: {failed} of {len(reqs)} requests failed"
    return row, blob


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="open-loop Poisson traffic against the serving fleet")
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--workers", default="golden:thread,golden:thread",
                    metavar="B:M,B:M",
                    help="comma list of backend:mode worker specs")
    ap.add_argument("--slots", type=int, default=4,
                    help="continuous-batching slots per worker")
    ap.add_argument("--max-seq", type=int, default=16)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=20.0,
                    help="Poisson arrival rate, requests/sec")
    ap.add_argument("--prompt-len", type=int, default=4,
                    help="max prompt length (uniform 1..N)")
    ap.add_argument("--new-tokens", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--step-timeout", type=float, default=300.0)
    ap.add_argument("--request-timeout", type=float, default=600.0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized workload (8 requests, short decode)")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="export the final obs.METRICS registry "
                         "(.json or .csv)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.requests = 8
        args.prompt_len = 2
        args.new_tokens = 3
        args.slots = 4
        args.max_seq = 8
        args.rate = 50.0

    reqs, gaps = _workload(args)
    oracle = _oracle_outputs(args, reqs)

    rows = []
    row_c, blob_c = run_policy(args, "continuous", reqs, gaps, oracle)
    rows.append(row_c)
    metrics_continuous = METRICS.snapshot()
    row_s, blob_s = run_policy(args, "serial", reqs, gaps, oracle)
    rows.append(row_s)

    speedup = round(blob_c["req_per_s"] / max(blob_s["req_per_s"], 1e-9), 2)
    beats = blob_c["req_per_s"] >= blob_s["req_per_s"]
    rows.append((f"serve.fleet.compare.{args.arch}", 0.0, json.dumps({
        "BENCH": "serve.fleet.compare",
        "arch": args.arch,
        "continuous_req_per_s": blob_c["req_per_s"],
        "serial_req_per_s": blob_s["req_per_s"],
        "speedup_x": speedup,
        "continuous_beats_serial": beats,
    }, sort_keys=True)))

    if args.metrics:
        # merge the continuous phase back in so the export covers both
        # policies (run_policy clears between phases)
        for name, v in metrics_continuous["counters"].items():
            METRICS.incr(name, v)
        for name, stats in metrics_continuous["observations"].items():
            for v in stats["values"]:
                METRICS.observe(name, v)
        METRICS.save(args.metrics)

    writer = csv.writer(sys.stdout)
    for row in rows:
        writer.writerow(row)
    # the tentpole's hard gate: batching must pay for itself
    assert beats, (
        f"continuous batching ({blob_c['req_per_s']} req/s) does not "
        f"beat serial dispatch ({blob_s['req_per_s']} req/s)")


if __name__ == "__main__":
    main()
