"""Compiler benchmark: compile wall-time, instruction counts, bytes
moved, DDR footprint — plus the optimization-pass pipeline and executor
backends:

  * per network: -O0 vs -O1 instruction counts with per-pass deltas
    (weight-prefetch / sync-elision / dma-fusion) and, for the
    simulation subset, simulated-latency deltas from
    ``simulate_program`` (optimized streams are what gets timed);
  * one registry LM smoke program executed functionally on both
    backends: golden interpreter vs batched Pallas fast path, wall
    clock + speedup + a bit-exactness flag;
  * whole-CNN inference rows: resnet18 and mobilenet_v2 executed end
    to end through the spatial im2col chain (depthwise grouped GEMMs
    included) on the pallas backend, with a golden bit-exactness
    cross-check on the reduced smoke variant;
  * multi-device scaling: the same LM compiled under 1 -> 2 -> 4-device
    pipeline and filter plans, with the cross-device makespan (link
    latency included) and speedup vs one device for a batched input
    stream.

Covers both CNN workloads and a slice of the LM registry, so compile
cost is tracked for every frontend family. Each row's ``derived`` field
carries a ``BENCH`` JSON blob with the program-level metrics the
roadmap cares about. ``--smoke`` restricts to a fast subset for CI.
"""
from __future__ import annotations

import argparse
import csv
import json
import sys
import time

import numpy as np

from repro.compiler import (
    GoldenExecutor,
    PallasExecutor,
    bind_synthetic,
    compile_network,
    optimize_program,
    to_binary,
)
from repro.core.scheduler import simulate_program

NETWORKS = [
    ("resnet18", {}),
    ("mobilenet_v2", {}),
    ("llama3.2-1b", {"seq_len": 64}),
    ("qwen3-moe-235b-a22b", {"seq_len": 64}),
    ("mamba2-780m", {"seq_len": 64}),
]
SMOKE_NETWORKS = [
    ("llama3.2-1b", {"seq_len": 16}),
    ("mamba2-780m", {"seq_len": 16}),
]
#: networks whose -O0/-O1 simulated latency is reported (simulation of
#: the big CNN im2col programs is minutes-long; instruction deltas are
#: still reported for every network)
SIMULATE = {"llama3.2-1b", "qwen3-moe-235b-a22b", "mamba2-780m"}

#: the registry LM smoke program used for the backend-speedup row
EXEC_NETWORK = "llama3.2-1b"


def bench_network(name: str, kw: dict) -> tuple[str, float, str]:
    t0 = time.time()
    prog = compile_network(name, **kw)
    compile_s = time.time() - t0
    t1 = time.time()
    opt = optimize_program(prog, 1, validate=False)
    opt_s = time.time() - t1
    t2 = time.time()
    image = to_binary(opt)
    pack_s = time.time() - t2
    s = prog.stats()
    bench = {
        "BENCH": "compiler",
        "network": name,
        "layers": len(prog.layers),
        "instructions": s.n_instructions,
        "instructions_o1": opt.n_instructions,
        "passes": [{
            "name": ps.name,
            "instrs_before": ps.instrs_before,
            "instrs_after": ps.instrs_after,
            **ps.detail,
        } for ps in opt.opt_stats],
        "by_opcode": s.by_opcode,
        "image_bytes": len(image),
        "ddr_footprint_bytes": s.ddr_footprint,
        "mb_fetched": round(s.bytes_fetched / 1e6, 3),
        "mb_written": round(s.bytes_written / 1e6, 3),
        "compile_s": round(compile_s, 4),
        "opt_s": round(opt_s, 4),
        "pack_s": round(pack_s, 4),
        "instrs_per_s": int(s.n_instructions / max(compile_s, 1e-9)),
    }
    if name in SIMULATE:
        t3 = time.time()
        c0 = simulate_program(prog).total_cycles
        c1 = simulate_program(opt).total_cycles
        bench.update({
            "sim_cycles_o0": c0,
            "sim_cycles_o1": c1,
            "sim_latency_gain_pct": round(100.0 * (c0 - c1) / max(c0, 1), 3),
            "sim_s": round(time.time() - t3, 4),
        })
    return (f"compiler.{name}", 1e6 * compile_s,
            json.dumps(bench, sort_keys=True))


def bench_backends(seq_len: int = 64) -> tuple[str, float, str]:
    """Golden interpreter vs batched Pallas fast path on one registry
    LM smoke program: wall clock per full program execution, bit-exact
    cross-check, speedup."""
    prog = compile_network(EXEC_NETWORK, seq_len=seq_len, opt_level=1)
    golden = GoldenExecutor(prog)
    pallas = PallasExecutor(prog)
    acts = {}
    for lp in prog.layers:
        bind_synthetic(golden, lp)
        bind_synthetic(pallas, lp)
        acts[lp.index] = np.random.default_rng(1000 + lp.index).integers(
            -8, 8, (lp.dims.m, lp.dims.k)).astype(np.int8)

    # warm the fast path once (jit/trace), then time both
    for lp in prog.layers:
        pallas.run_layer(lp.index, acts[lp.index])
    t0 = time.time()
    outs_g = {lp.index: np.asarray(golden.run_layer(lp.index,
                                                    acts[lp.index]))
              for lp in prog.layers}
    golden_s = time.time() - t0
    t1 = time.time()
    outs_p = {lp.index: np.asarray(pallas.run_layer(lp.index,
                                                    acts[lp.index]))
              for lp in prog.layers}
    pallas_s = time.time() - t1
    bit_exact = all((outs_g[i] == outs_p[i]).all() for i in outs_g)
    bench = {
        "BENCH": "compiler.backends",
        "network": EXEC_NETWORK,
        "seq_len": seq_len,
        "layers": len(prog.layers),
        "golden_s": round(golden_s, 4),
        "pallas_s": round(pallas_s, 4),
        "speedup_x": round(golden_s / max(pallas_s, 1e-9), 1),
        "bit_exact": bool(bit_exact),
    }
    return (f"compiler.backends.{EXEC_NETWORK}", 1e6 * pallas_s,
            json.dumps(bench, sort_keys=True))


def bench_cnn_execute(arch: str, smoke: bool = False
                      ) -> tuple[str, float, str]:
    """Whole-CNN inference through the compiled program: a synthetic
    quantized image chained end to end (im2col staging, depthwise
    grouped GEMMs, pool glue, shortcut sources, inter-layer requant).

    Full mode runs the full-size 224 network on the pallas backend;
    ``--smoke`` runs the reduced geometry-consistent variant and also
    cross-checks golden-vs-pallas bit-exactness.
    """
    kw = {"in_hw": 28, "width": 0.25} if smoke else {}
    prog = compile_network(arch, opt_level=1, **kw)
    geo0 = prog.layers[0].geometry
    rng = np.random.default_rng(0)
    x_q = rng.integers(-8, 8, geo0.in_shape).astype(np.int8)

    pallas = PallasExecutor(prog)
    for lp in prog.layers:
        bind_synthetic(pallas, lp, seed=lp.index)
    pallas.run(x_q)                       # warm the jit tables
    t0 = time.time()
    out_p = np.asarray(pallas.run(x_q))
    pallas_s = time.time() - t0

    bench = {
        "BENCH": "compiler.cnn_execute",
        "network": arch,
        "in_hw": geo0.in_hw,
        "layers": len(prog.layers),
        "depthwise_layers": sum(lp.depthwise for lp in prog.layers),
        "logits": list(out_p.shape),
        "abs_sum": float(np.abs(out_p).sum()),
        "pallas_s": round(pallas_s, 4),
    }
    if smoke:
        golden = GoldenExecutor(prog)
        for lp in prog.layers:
            bind_synthetic(golden, lp, seed=lp.index)
        t1 = time.time()
        out_g = np.asarray(golden.run(x_q))
        bench["golden_s"] = round(time.time() - t1, 4)
        bench["bit_exact"] = bool((out_g == out_p).all())
    return (f"compiler.cnn_execute.{arch}", 1e6 * pallas_s,
            json.dumps(bench, sort_keys=True))


def bench_multi_device(seq_len: int = 64,
                       batches: int = 8) -> tuple[str, float, str]:
    """1 -> 2 -> 4-device scaling of one registry LM program: simulated
    cross-device makespan (plan link latency included) for a stream of
    ``batches`` inputs, vs the single-device baseline."""
    t0 = time.time()
    prog = compile_network(EXEC_NETWORK, seq_len=seq_len, opt_level=1)
    base = simulate_program(prog).total_cycles * batches
    bench = {
        "BENCH": "compiler.multi_device",
        "network": EXEC_NETWORK,
        "seq_len": seq_len,
        "batches": batches,
        "makespan_1dev": base,
        "plans": {},
    }
    for kind in ("pipeline", "filter"):
        for n_dev in (2, 4):
            bundle = compile_network(EXEC_NETWORK, seq_len=seq_len,
                                     opt_level=1, devices=n_dev,
                                     partition=kind)
            bs = simulate_program(bundle, batches=batches)
            bench["plans"][f"{kind}_x{n_dev}"] = {
                "makespan": bs.total_cycles,
                "latency": bs.latency_cycles,
                "interval": bs.interval_cycles,
                "speedup_x": round(base / max(bs.total_cycles, 1), 3),
                "instructions": bundle.n_instructions,
                "link_bytes": sum(e.nbytes for e in bundle.edges),
            }
    bench["pipeline_x2_beats_1dev"] = \
        bench["plans"]["pipeline_x2"]["makespan"] < base
    wall = time.time() - t0
    return (f"compiler.multi_device.{EXEC_NETWORK}", 1e6 * wall,
            json.dumps(bench, sort_keys=True))


def bench_obs_overhead(seq_len: int = 16,
                       repeats: int = 7) -> tuple[str, float, str]:
    """``obs.overhead.*`` row: tracer-on vs tracer-off simulation wall
    time on one registry LM program.

    The observability contract says tracing is free when off (the
    ``trace is None`` fast path in ``scheduler.simulate``) and cheap
    when on (lazy replay — nothing per instruction); this row pins
    both — enabled overhead must stay under 15%. Off/on reps are
    interleaved and min-of-N timed, so a load ramp on a shared CI
    runner hits both sides alike instead of flaking the ratio.
    """
    from repro.obs import Tracer
    prog = compile_network(EXEC_NETWORK, seq_len=seq_len, opt_level=1)
    simulate_program(prog)              # warm imports/caches
    simulate_program(prog, tracer=Tracer())

    off_times, on_times, tracers = [], [], []
    for _ in range(repeats):
        t0 = time.perf_counter()
        simulate_program(prog)
        off_times.append(time.perf_counter() - t0)
        tr = Tracer()
        t0 = time.perf_counter()
        simulate_program(prog, tracer=tr)
        on_times.append(time.perf_counter() - t0)
        tracers.append(tr)

    off_s, on_s = min(off_times), min(on_times)
    overhead_pct = 100.0 * (on_s - off_s) / max(off_s, 1e-9)
    n_spans = len(tracers[-1].to_chrome()["traceEvents"])
    closure_ok = not tracers[-1].counters.closure_errors()
    assert overhead_pct < 15.0, \
        f"tracer-on simulation overhead {overhead_pct:.1f}% >= 15%"
    assert closure_ok, "traced simulation failed cycle-accounting closure"
    bench = {
        "BENCH": "obs.overhead",
        "network": EXEC_NETWORK,
        "seq_len": seq_len,
        "sim_off_s": round(off_s, 5),
        "sim_on_s": round(on_s, 5),
        "overhead_pct": round(overhead_pct, 2),
        "trace_events": n_spans,
        "closure_ok": closure_ok,
    }
    return (f"obs.overhead.{EXEC_NETWORK}", 1e6 * on_s,
            json.dumps(bench, sort_keys=True))


def bench_dse_sim_gap(smoke: bool = False) -> list[tuple[str, float, str]]:
    """``dse.sim_gap.*`` rows: the analytical latency model the DSE
    explores with vs ``simulate_program`` on the compiled ``-O1``
    program, for the registry LMs this bench already tracks — the gap
    the two-tier search loop (docs/dse.md) corrects inside the loop,
    with the documented tolerance flagged per row."""
    from repro.dse.evaluator import sim_gap_report
    nets = ["llama3.2-1b"] if smoke else ["llama3.2-1b", "mamba2-780m"]
    rows = []
    for net in nets:
        t0 = time.time()
        rep = sim_gap_report(net, seq_len=16 if smoke else 64)
        rows.append((f"dse.sim_gap.{net}", 1e6 * (time.time() - t0),
                     json.dumps(rep, sort_keys=True)))
    return rows


def bench_gather_overlap(smoke: bool = False) -> tuple[str, float, str]:
    """``compiler.gather_overlap`` row: filter-mode gather DMAs issued
    at the *producing* layer's fetch tail (riding under its compute)
    vs serialized at the consuming layer's head — the simulated
    filter-parallel makespan delta on a registry LM under a 2-device
    plan. Hard guard: the overlapped placement must not be slower."""
    from repro.compiler import derive_plan, lower_partitioned
    from repro.compiler.networks import network_layers
    from repro.core.scheduler import (DspCoreConfig, LutCoreConfig,
                                      XC7Z020)
    t0 = time.time()
    seq = 16 if smoke else 64
    layers = network_layers(EXEC_NETWORK, seq_len=seq)
    lut = LutCoreConfig(m=8, n=16, k=128)
    dsp = DspCoreConfig(
        n_reg_row_a=DspCoreConfig.rows_for_device(XC7Z020))
    plan = derive_plan(layers, 2, kind="filter")
    kw = dict(bits_w_lut=4, bits_a=4, opt_level=1)
    over = lower_partitioned(EXEC_NETWORK, layers, plan, lut, dsp,
                             XC7Z020, **kw)
    serial = lower_partitioned(EXEC_NETWORK, layers, plan, lut, dsp,
                               XC7Z020, gather_overlap=False, **kw)
    c_over = simulate_program(over).latency_cycles
    c_serial = simulate_program(serial).latency_cycles
    assert c_over < c_serial, \
        (f"filter gather overlap regressed: {c_over} cycles vs "
         f"{c_serial} serialized on {EXEC_NETWORK}")
    bench = {
        "BENCH": "compiler.gather_overlap",
        "network": EXEC_NETWORK,
        "seq_len": seq,
        "devices": 2,
        "latency_overlap": c_over,
        "latency_serialized": c_serial,
        "gain_pct": round(100.0 * (c_serial - c_over)
                          / max(c_serial, 1), 3),
    }
    return (f"compiler.gather_overlap.{EXEC_NETWORK}",
            1e6 * (time.time() - t0), json.dumps(bench, sort_keys=True))


def bench_serve_decode(smoke: bool = False) -> list[tuple[str, float, str]]:
    """``serve.decode.*`` rows: decode-resident step programs vs
    naively re-running the whole fixed-sequence program per generated
    token.

    Per network: the simulator's warm-up vs steady-state cycles/token
    (``DecodeSim`` — weight fetches elided after warm-up, KV/state
    segments persistent), the fixed-seq full re-invocation baseline,
    and host tokens/sec through a live ``ExecutorSession`` on the
    pallas backend (bind once, then ``step(token, pos)`` against the
    resident image). Hard regression guards: the resident steady-state
    step must beat both the naive per-token re-run and its own warm-up
    invocation.
    """
    from repro.compiler import compile_decode_network
    from repro.compiler.runtime import ExecutorSession

    max_seq = 8 if smoke else 16
    n_tokens = 4 if smoke else 8
    rows = []
    for net in ("llama3.2-1b", "mamba2-780m"):
        t0 = time.time()
        prog = compile_decode_network(net, batch=1, max_seq=max_seq,
                                      opt_level=1)
        ds = simulate_program(prog)
        fixed = compile_network(net, seq_len=max_seq, opt_level=1)
        naive = simulate_program(fixed).total_cycles
        assert ds.steady_cycles < naive, \
            (f"resident decode step ({ds.steady_cycles} cycles) not "
             f"faster than re-running the fixed-seq program per token "
             f"({naive} cycles) on {net}")
        assert ds.steady_cycles < ds.warmup_cycles, \
            (f"steady-state step ({ds.steady_cycles} cycles) not "
             f"faster than warm-up ({ds.warmup_cycles}) on {net}")

        session = ExecutorSession(prog, backend="pallas")
        session.bind_synthetic_all(seed=0)
        tok = np.array([1], np.int32)
        t1 = time.perf_counter()
        logits = session.step(tok, 0)          # warm-up invocation
        warm_s = time.perf_counter() - t1
        t1 = time.perf_counter()
        for i in range(1, n_tokens):
            tok = np.argmax(np.asarray(logits), axis=-1).astype(np.int32)
            logits = session.step(tok, i)
        steady_s = time.perf_counter() - t1
        bench = {
            "BENCH": "serve.decode",
            "network": net,
            "family": prog.step.family,
            "batch": 1,
            "max_seq": max_seq,
            "tokens": n_tokens,
            "warmup_cycles": ds.warmup_cycles,
            "steady_cycles": ds.steady_cycles,
            "naive_fixed_seq_cycles_per_token": naive,
            "resident_vs_naive_x": round(naive
                                         / max(ds.steady_cycles, 1), 2),
            "warmup_vs_steady_x": round(ds.warmup_cycles
                                        / max(ds.steady_cycles, 1), 3),
            "tokens_cycles": ds.tokens_cycles(n_tokens),
            "warmup_s": round(warm_s, 4),
            "steady_s_per_token": round(steady_s
                                        / max(n_tokens - 1, 1), 4),
            "host_tok_per_s": round((n_tokens - 1)
                                    / max(steady_s, 1e-9), 1),
        }
        rows.append((f"serve.decode.{net}", 1e6 * (time.time() - t0),
                     json.dumps(bench, sort_keys=True)))
    return rows


def run(smoke: bool = False) -> list[tuple[str, float, str]]:
    rows = [bench_network(name, kw)
            for name, kw in (SMOKE_NETWORKS if smoke else NETWORKS)]
    rows.append(bench_backends(seq_len=16 if smoke else 64))
    for arch in ("resnet18", "mobilenet_v2"):
        rows.append(bench_cnn_execute(arch, smoke=smoke))
    rows.append(bench_multi_device(seq_len=16 if smoke else 64))
    rows.append(bench_obs_overhead(seq_len=16 if smoke else 64))
    rows.extend(bench_dse_sim_gap(smoke=smoke))
    rows.append(bench_gather_overlap(smoke=smoke))
    rows.extend(bench_serve_decode(smoke=smoke))
    return rows


def main() -> list[tuple[str, float, str]]:
    return run()


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast subset for CI (small LM programs only)")
    args = ap.parse_args()
    writer = csv.writer(sys.stdout)
    for row in run(smoke=args.smoke):
        writer.writerow(row)
