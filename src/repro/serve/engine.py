"""Inference engine: prefill / decode step factories + generation loop.

``make_prefill_fn`` / ``make_decode_fn`` adapt the per-family model APIs
to one uniform signature so the launcher, the dry-run and the examples
never branch on the architecture family:

    prefill_fn(params, batch, cache)       -> (logits, cache)
    decode_fn(params, token, cache, pos)   -> (logits, cache)

Family notes:
  * lm      — real prefill (scores prompt AND fills the KV cache).
  * ssm     — decode carries the recurrent state; "prefill" scores the
              prompt with the scan forward (state building for
              generation happens token-by-token in greedy_generate).
  * hybrid  — like ssm for the Mamba sublayers + KV for attention.
  * encdec  — prefill = encode(frames) + build the static cross-cache;
              decode = one decoder token.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.registry import ArchConfig
from repro.parallel.sharding import AxisRules, DEFAULT_RULES


@dataclasses.dataclass
class ServeState:
    cache: Any
    pos: int


def make_cache(arch: ArchConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> Any:
    mod = arch.model_module()
    if arch.module == "ssm":
        return mod.init_cache(arch.model, batch, dtype=dtype)
    if arch.module == "encdec":
        return mod.init_cache(arch.model, batch, max_tgt=max_seq,
                              src=max_seq, dtype=dtype)
    return mod.init_cache(arch.model, batch, max_seq, dtype)


def make_prefill_fn(arch: ArchConfig, rules: AxisRules = DEFAULT_RULES
                    ) -> Callable:
    mod = arch.model_module()
    cfg = arch.model

    if arch.module == "lm":
        def prefill_fn(params, batch, cache):
            return mod.prefill(params, batch["tokens"], cache, cfg, rules,
                               extra_embed=batch.get("extra_embed"))
        return prefill_fn

    if arch.module == "encdec":
        def prefill_fn(params, batch, cache):
            memory = mod.encode(params, batch["frames"], cfg, rules)
            cache = mod.build_cross_cache(params, memory, cfg, cache)
            logits, _ = mod.forward(params, batch["frames"],
                                    batch["tokens"], cfg, rules)
            return logits, cache
        return prefill_fn

    # ssm / hybrid: forward scores the prompt; recurrent state accrues
    # during generation (see greedy_generate).
    def prefill_fn(params, batch, cache):
        logits, _ = mod.forward(params, batch["tokens"], cfg, rules,
                                extra_embed=batch.get("extra_embed"))
        return logits, cache
    return prefill_fn


def make_decode_fn(arch: ArchConfig, rules: AxisRules = DEFAULT_RULES
                   ) -> Callable:
    mod = arch.model_module()
    cfg = arch.model

    def decode_fn(params, token, cache, pos):
        return mod.decode_step(params, token, cache, pos, cfg, rules)

    return decode_fn


def greedy_generate(arch: ArchConfig, params: Any, prompts: jax.Array,
                    n_new: int, max_seq: int | None = None,
                    dtype=jnp.float32,
                    rules: AxisRules = DEFAULT_RULES) -> jax.Array:
    """Greedy batched generation (the end-to-end serving path).

    prompts: [B, S0] int32. Returns [B, S0 + n_new]. For the recurrent
    families the prompt is consumed token-by-token to build the state
    (simple and correct; chunked prefill is a recorded follow-up).
    """
    b, s0 = prompts.shape
    max_seq = max_seq or (s0 + n_new)
    cache = make_cache(arch, b, max_seq, dtype)
    decode_fn = jax.jit(make_decode_fn(arch, rules))

    recurrent = arch.module in ("ssm", "hybrid")
    out = [prompts]
    if arch.module == "lm":
        # real prefill: one call scores the whole prompt and fills the
        # KV cache (S0 single-token steps would re-pay the attention
        # window per token for nothing)
        prefill_fn = jax.jit(make_prefill_fn(arch, rules))
        logits, cache = prefill_fn(params, {"tokens": prompts}, cache)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        pos = s0
    elif recurrent:
        # the recurrent families build state token-by-token: their
        # prefill scores the prompt but does not advance the state
        for t in range(s0):
            logits, cache = decode_fn(params, prompts[:, t:t + 1], cache,
                                      jnp.int32(t))
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        pos = s0
    else:  # encdec: encode once, then decode from BOS
        raise NotImplementedError(
            "encdec generation uses examples/serve_encdec.py")

    new = [tok]
    for i in range(n_new - 1):
        logits, cache = decode_fn(params, tok, cache, jnp.int32(pos))
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        new.append(tok)
        pos += 1
    return jnp.concatenate(out + new, axis=1)


# ---------------------------------------------------------------------------
# Compiled (quantized) serving path: decode-resident executor sessions
# ---------------------------------------------------------------------------


def make_compiled_session(arch_id: str, *, backend: str = "golden",
                          batch: int = 1, max_seq: int = 64,
                          bits_w: int = 4, bits_a: int = 4,
                          opt_level: int = 1, device: str = "XC7Z020",
                          seed: int | None = None):
    """Build a decode-resident :class:`~repro.compiler.runtime.session.
    ExecutorSession` for a registry arch: compile the decode step
    program (weights resident, KV/state persistent), bind synthetic
    quantized weights once, and report the simulator's warm-up vs
    steady-state step cycles into ``obs.METRICS``
    (``serve.decode.warmup_cycles`` / ``serve.decode.steady_cycles``).
    """
    from repro.obs import METRICS
    from repro.core.scheduler import simulate_program
    from repro.compiler import compile_decode_network
    from repro.compiler.runtime import ExecutorSession
    prog = compile_decode_network(arch_id, batch=batch, max_seq=max_seq,
                                  bits_w=bits_w, bits_a=bits_a,
                                  opt_level=opt_level, device=device)
    ds = simulate_program(prog)
    METRICS.gauge("serve.decode.warmup_cycles", ds.warmup_cycles)
    METRICS.gauge("serve.decode.steady_cycles", ds.steady_cycles)
    session = ExecutorSession(prog, backend=backend)
    session.bind_synthetic_all(seed=seed)
    return session


def make_compiled_decode_fn(session) -> Callable:
    """Adapt an ``ExecutorSession`` to the uniform decode signature.
    ``params`` and ``cache`` pass through untouched — the session owns
    the resident weights and the live cache buffers."""
    def decode_fn(params, token, cache, pos):
        logits = session.step(jnp.asarray(token, jnp.int32).reshape(-1),
                              int(pos))
        return logits, cache
    return decode_fn


def greedy_generate_compiled(session, prompts: jax.Array,
                             n_new: int) -> jax.Array:
    """Greedy generation through a compiled decode session: the prompt
    is consumed step by step (warm-up program on the first token,
    steady-state program after), then ``n_new`` greedy tokens follow —
    every step against the session's resident weights and live caches.
    """
    prompts = jnp.asarray(prompts, jnp.int32)
    b, s0 = prompts.shape
    if b != session.spec.batch:
        raise ValueError(f"session is compiled for batch="
                         f"{session.spec.batch}, prompts have {b}")
    if s0 + n_new > session.spec.max_seq:
        raise ValueError(f"{s0} prompt + {n_new} new tokens exceed the "
                         f"session's max_seq={session.spec.max_seq}")
    session.reset()
    logits = None
    for t in range(s0):
        logits = session.step(prompts[:, t], t)
    new = []
    for i in range(n_new):
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        new.append(tok[:, None])
        if i + 1 < n_new:
            logits = session.step(tok, s0 + i)
    return jnp.concatenate([prompts] + new, axis=1)
