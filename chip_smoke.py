#!/usr/bin/env python3
"""Smoke run of the execution path on one TPU chip.

    python chip_smoke.py [--seed N]

Everything runs in this one process, because a chip belongs to the one
process that opened it. The phases, each checked against a reference
on the same chip:

  1. device  JAX must report a TPU. There is no CPU fallback, and
             ``JAX_PLATFORMS`` is left as the caller set it.
  2. cnn     resnet18, mobilenet_v2 and resnet50 at 224 and width 1.0, compiled
             by ``compile_network`` and run through
             ``PallasExecutor(mode="auto")`` on one synthetic image.
             The logits must equal ``mode="ref"`` bit for bit, and the
             one-executable chain's must equal the eager chain's
             (``check_timing=True``). Prints where each layer ran and
             one warmed-up image time, which is a smoke timing and not
             a metric.
  3. decode  a registry LM with attention in its step, at its smoke
             config (the only size the decode path supports), through
             ``ExecutorSession(backend="pallas")``: tokens and logits
             must equal the ``mode="ref"`` session's bit for bit.
  4. fleet   a ``FleetServer`` with one in-process pallas thread worker
             answers a few requests; every token must equal the
             single-process oracle, with no failed request.

Weights and inputs are random, made from ``--seed``. Any failure ends
the script with a non-zero exit and without the result line. On
success the last line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

CNNS = ("resnet18", "mobilenet_v2", "resnet50")
DECODE_ARCH = "qwen3-8b"
MAX_SEQ = 16
N_TOKENS = 4
FLEET_REQUESTS = [([5], 4), ([3, 11], 4), ([1, 2, 3], 4), ([9, 8], 4)]


class SmokeError(RuntimeError):
    """A phase produced a wrong or missing result."""


def _bitwise_equal(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype \
        and a.tobytes() == b.tobytes()


def _layer_counters() -> dict[str, int]:
    from repro.obs import METRICS
    snap = METRICS.snapshot()["counters"]
    return {k: v for k, v in snap.items()
            if k.startswith(("pallas.layer.", "pallas.run."))}


def _counter_delta(before: dict, after: dict) -> dict[str, int]:
    return {k: after[k] - before.get(k, 0) for k in sorted(after)
            if after[k] != before.get(k, 0)}


def device_phase() -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    if d.platform != "tpu":
        raise SmokeError(f"JAX reports no TPU (default device platform "
                         f"{d.platform!r})")
    return info


def cnn_phase(name: str, seed: int, *, in_hw: int | None = None,
              width: float | None = None, mode: str = "auto") -> dict:
    """One synthetic image through ``PallasExecutor(mode=mode)``, its
    eager chain and ``mode="ref"``; the logits must agree bit for
    bit."""
    import jax
    from repro.compiler import PallasExecutor, bind_synthetic, compile_network
    from repro.quant.uniform import qrange
    prog = compile_network(name, in_hw=in_hw, width=width)
    lp0 = prog.layers[0]
    lo, hi = qrange(lp0.bits_a)
    x_q = np.random.default_rng(seed).integers(
        lo, hi + 1, lp0.geometry.in_shape).astype(np.int8)
    out = {}
    for m, eager in ((mode, False), ("ref", False), (mode, True)):
        ex = PallasExecutor(prog, mode=m, check_timing=eager)
        for lp in prog.layers:
            bind_synthetic(ex, lp, seed=seed + lp.index)
        out[m, eager] = (ex, np.asarray(jax.block_until_ready(ex.run(x_q))))
    ex, got = out[mode, False]
    want = out["ref", False][1]
    if not _bitwise_equal(got, out[mode, True][1]):
        bad = int((got != out[mode, True][1]).sum())
        raise SmokeError(f"{name}: the chain executable's logits differ "
                         f"from the eager chain's in {bad} of {got.size} "
                         f"entries")

    by_path = collections.defaultdict(list)
    for lp in prog.layers:
        by_path[ex.layer_paths[lp.name]].append(lp.name)
    n = len(prog.layers)
    print(f"{name}: {len(by_path.get('kernel', []))}/{n} layers ran a Pallas "
          f"kernel", flush=True)
    for path in sorted(by_path):
        if path != "kernel":
            print(f"{name}: {len(by_path[path])} layer(s) on {path}: "
                  f"{' '.join(by_path[path])}", flush=True)
    before = _layer_counters()
    t0 = time.perf_counter()
    jax.block_until_ready(ex.run(x_q))
    dt = time.perf_counter() - t0
    print(f"{name}: counters for one image: "
          f"{_counter_delta(before, _layer_counters())}", flush=True)
    print(f"{name}: smoke timing, not a metric: one warmed-up image in "
          f"{dt * 1e3!r} ms (host clock to block_until_ready)", flush=True)

    if got.shape != (1, prog.layers[-1].dims.n):
        raise SmokeError(f"{name}: logits shape {got.shape}")
    if not np.isfinite(got).all():
        raise SmokeError(f"{name}: non-finite logits")
    if not _bitwise_equal(got, want):
        bad = int((got != want).sum())
        raise SmokeError(f"{name}: mode={mode!r} logits differ from "
                         f"mode='ref' in {bad} of {got.size} entries")
    oracle = [p for p in by_path if p in ("ref", "interpret")]
    if mode == "auto" and oracle:
        raise SmokeError(f"{name}: layers left the kernel path for "
                         f"{oracle}")
    print(f"{name}: logits {list(got.shape)} bitwise equal to "
          f"mode='ref' and to the eager chain", flush=True)
    return dict(by_path)


def _decode_program(batch: int = 1):
    from repro.compiler import compile_decode_network
    return compile_decode_network(DECODE_ARCH, batch=batch, max_seq=MAX_SEQ,
                                  opt_level=1)


def decode_phase(seed: int, mode: str = "auto") -> list[int]:
    """Greedy decode through the pallas session (warm-up step, then
    steady steps with the donated KV append) against the ``mode="ref"``
    session: same tokens, bitwise equal logits."""
    from repro.compiler import ExecutorSession
    prog = _decode_program()
    print(f"decode: {DECODE_ARCH} smoke config ({len(prog.layers)} GEMM "
          f"layers), {N_TOKENS} tokens", flush=True)
    runs = {}
    for m in (mode, "ref"):
        sess = ExecutorSession(prog, backend="pallas", mode=m)
        sess.bind_synthetic_all(seed=seed)
        before = _layer_counters()
        token, tokens, logits = 1, [], []
        for pos in range(N_TOKENS):
            lg = np.asarray(sess.step(token, pos))
            token = int(np.argmax(lg[0]))
            tokens.append(token)
            logits.append(lg)
        runs[m] = (tokens, logits, _counter_delta(before, _layer_counters()))
    tokens, logits, counts = runs[mode]
    print(f"decode: mode={mode!r} counters: {counts}", flush=True)
    if mode == "auto" and set(counts) != {"pallas.layer.kernel"}:
        raise SmokeError(f"decode: GEMMs left the kernel path: {counts}")
    if tokens != runs["ref"][0]:
        raise SmokeError(f"decode: tokens {tokens} != ref "
                         f"{runs['ref'][0]}")
    for pos, (a, b) in enumerate(zip(logits, runs["ref"][1])):
        if not _bitwise_equal(a, b) or not np.isfinite(a).all():
            raise SmokeError(f"decode: logits differ from ref at "
                             f"position {pos}")
    print(f"decode: tokens {tokens} and logits bitwise equal to "
          f"mode='ref'", flush=True)
    return tokens


def fleet_phase(seed: int) -> int:
    """A few requests through a one-worker fleet (in-process pallas
    thread) against a single-process batch-1 ``mode="ref"`` session."""
    from repro.compiler import ExecutorSession
    from repro.serve.engine import greedy_generate_compiled
    from repro.serve.fleet import FleetServer, RequestFailed
    server = FleetServer(DECODE_ARCH, [("w0", "pallas", "thread")],
                         batch_slots=2, max_seq=MAX_SEQ, seed=seed)
    got, failed = [], 0
    before = _layer_counters()
    with server:
        futs = [server.submit(p, n) for p, n in FLEET_REQUESTS]
        for fut in futs:
            try:
                got.append(np.asarray(fut.result(600)))
            except RequestFailed as e:
                failed += 1
                got.append(None)
                print(f"fleet: request failed: {e}", flush=True)
    print(f"fleet: worker counters: "
          f"{_counter_delta(before, _layer_counters())}", flush=True)
    oracle = ExecutorSession(_decode_program(), backend="pallas",
                             mode="ref")
    oracle.bind_synthetic_all(seed=seed)
    for (prompt, n_new), row in zip(FLEET_REQUESTS, got):
        want = np.asarray(greedy_generate_compiled(
            oracle, np.asarray(prompt, np.int32)[None, :], n_new))[0]
        if row is not None and not np.array_equal(row, want):
            raise SmokeError(f"fleet: prompt {prompt}: tokens "
                             f"{row.tolist()} != oracle {want.tolist()}")
    print(f"fleet: {len(FLEET_REQUESTS)} requests x "
          f"{FLEET_REQUESTS[0][1]} new tokens, {failed} failed, tokens "
          f"equal to the single-process oracle", flush=True)
    if failed:
        raise SmokeError(f"fleet: {failed} request(s) failed")
    return failed


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0,
                   help="seed of the synthetic weights and inputs")
    args = p.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    try:
        device = device_phase()
        for name in CNNS:
            cnn_phase(name, args.seed)
        decode_phase(args.seed)
        fleet_phase(args.seed)
    except Exception as e:  # every phase failure ends the run non-zero
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
