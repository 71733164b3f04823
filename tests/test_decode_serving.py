"""Decode-resident serving: the ISSUE-8 acceptance surface.

  * per-family (lm / ssm / hybrid) decode-step parity: a compiled
    executor session's ``step()`` is bit-exact against the plain-jax
    ``decode_step`` reference, on the golden interpreter AND the
    batched Pallas fast path;
  * residency classes + the ``.step`` invocation header round-trip
    through text asm and the ``N3HPROG1`` binary (fixed-sequence
    programs stay step-free and all-``io``);
  * steady-state weight elision: after warm-up, no fetch into a
    ``weights``-resident segment is ever re-issued, and the steady
    image moves strictly fewer DDR bytes;
  * ``simulate_program`` reports warm-up vs steady-state decode
    cycles (``DecodeSim``) with the n-token closed form;
  * the serving factories: ``make_compiled_session`` /
    ``greedy_generate_compiled`` and the launcher's decode-mode
    ``ProgramKey``;
  * satellite: the lm branch of ``greedy_generate`` runs one real
    prefill (not S0 single-token decode steps).
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.compiler import (
    ExecutorSession,
    ReferenceSession,
    asm,
    compile_decode_network,
    compile_network,
    steady_program,
)
from repro.configs import registry
from repro.core import isa
from repro.core.scheduler import simulate_program

FAMILIES = [("llama3.2-1b", "lm"), ("mamba2-780m", "ssm"),
            ("jamba-v0.1-52b", "hybrid")]


def _decode_prog(name, **kw):
    kw.setdefault("batch", 1)
    kw.setdefault("max_seq", 8)
    kw.setdefault("opt_level", 1)
    return compile_decode_network(name, **kw)


def _weight_fetches(prog) -> int:
    """Stage-0 fetches that target a ``weights``-resident segment."""
    wbases = {s.base for s in prog.memory.segments
              if s.residency == "weights"}
    n = 0
    for lp in prog.layers:
        for cp in (lp.lut, lp.dsp):
            if cp is None:
                continue
            for op in cp.streams["fetch"]:
                if (isinstance(op.instr, isa.FetchInstr)
                        and op.instr.stage_ctrl == 0
                        and op.instr.ddr_base in wbases):
                    n += 1
    return n


# ---------------------------------------------------------------------------
# session parity vs the plain-jax decode_step reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["golden", "pallas", "pallas-kernel"])
@pytest.mark.parametrize("name,family", FAMILIES)
def test_session_step_matches_reference(name, family, backend):
    """``pallas-kernel`` runs the Pallas kernels (interpreted off the
    TPU) on the weights each executor laid out when they were bound."""
    prog = _decode_prog(name)
    assert prog.step is not None and prog.step.family == family
    ref = ReferenceSession(prog)
    ref.bind_synthetic_all(seed=0)
    backend, _, mode = backend.partition("-")
    sess = ExecutorSession(prog, backend=backend,
                           **({"mode": mode} if mode else {}))
    sess.bind_synthetic_all(seed=0)
    for pos, t in enumerate([3, 5, 1]):
        tok = np.array([t], np.int32)
        want = np.asarray(ref.step(tok, pos))
        got = np.asarray(sess.step(tok, pos))
        assert want.shape == got.shape
        np.testing.assert_array_equal(want, got)


def test_multi_device_decode_session_matches_single():
    # a filter-partitioned decode bundle decodes bit-identically to the
    # single-device reference — residency decoration survives the split
    single = _decode_prog("llama3.2-1b")
    bundle = _decode_prog("llama3.2-1b", devices=2, partition="filter")
    ref = ReferenceSession(single)
    ref.bind_synthetic_all(seed=0)
    sess = ExecutorSession(bundle, backend="golden")
    sess.bind_synthetic_all(seed=0)
    for pos, t in enumerate([2, 7]):
        tok = np.array([t], np.int32)
        np.testing.assert_array_equal(np.asarray(ref.step(tok, pos)),
                                      np.asarray(sess.step(tok, pos)))


# ---------------------------------------------------------------------------
# residency + step header round-trips
# ---------------------------------------------------------------------------


def test_step_header_and_residency_roundtrip_text():
    prog = _decode_prog("llama3.2-1b")
    text = asm.disassemble(prog)
    assert ".step" in text
    assert "residency=weights" in text
    assert "residency=kv" in text
    rt = asm.assemble(text)
    assert rt == prog
    assert rt.step == prog.step


def test_step_header_and_residency_roundtrip_binary():
    prog = _decode_prog("mamba2-780m")
    rt = asm.from_binary(asm.to_binary(prog))
    assert rt == prog
    assert rt.step == prog.step
    kinds = {s.residency for s in rt.memory.segments}
    assert {"io", "weights", "state"} <= kinds


def test_fixed_program_stays_stepless_and_io():
    # the legacy fixed-sequence path is untouched: no step header, all
    # segments io, binary round-trip intact
    prog = compile_network("llama3.2-1b", seq_len=8, opt_level=1)
    assert prog.step is None
    assert {s.residency for s in prog.memory.segments} == {"io"}
    rt = asm.from_binary(asm.to_binary(prog))
    assert rt == prog and rt.step is None


# ---------------------------------------------------------------------------
# steady-state weight elision
# ---------------------------------------------------------------------------


def test_steady_program_elides_weight_fetches():
    warm = _decode_prog("llama3.2-1b")
    steady = steady_program(warm)
    assert _weight_fetches(warm) > 0
    assert _weight_fetches(steady) == 0
    assert steady.stats().bytes_fetched < warm.stats().bytes_fetched


def test_session_multi_step_never_refetches_weights():
    # the session swaps to the steady image after the first invocation:
    # across a 4-token generation only the warm-up program carries
    # weight fetches, so total weight-fetch issues == warm-up's count
    sess = ExecutorSession(_decode_prog("llama3.2-1b"), backend="golden")
    sess.bind_synthetic_all(seed=0)
    assert not sess._warmed
    for pos in range(4):
        sess.step(np.array([1], np.int32), pos)
        assert sess._warmed
    assert _weight_fetches(sess.warm) > 0
    assert _weight_fetches(sess.steady) == 0


def test_decode_sim_reports_warmup_and_steady():
    ds = simulate_program(_decode_prog("mamba2-780m"))
    assert ds.steady_cycles < ds.warmup_cycles
    assert ds.total_cycles == ds.warmup_cycles
    assert ds.tokens_cycles(1) == ds.warmup_cycles
    assert ds.tokens_cycles(4) == ds.warmup_cycles + 3 * ds.steady_cycles


# ---------------------------------------------------------------------------
# serving factories + launcher key
# ---------------------------------------------------------------------------


def test_greedy_generate_compiled_roundtrip():
    from repro.serve.engine import (greedy_generate_compiled,
                                    make_compiled_session)
    sess = make_compiled_session("llama3.2-1b", backend="golden",
                                 max_seq=8, seed=0)
    prompts = np.array([[3, 1, 4]], np.int32)
    out = np.asarray(greedy_generate_compiled(sess, prompts, 3))
    assert out.shape == (1, 6)
    assert (out[:, :3] == prompts).all()
    # deterministic: a fresh generation over the reset caches matches
    out2 = np.asarray(greedy_generate_compiled(sess, prompts, 3))
    np.testing.assert_array_equal(out, out2)
    with pytest.raises(ValueError):
        greedy_generate_compiled(sess, prompts, 64)   # exceeds max_seq


def test_program_cache_decode_mode_key():
    from repro.launch.serve import ProgramCache, ProgramKey
    cache = ProgramCache(maxsize=4)
    key = ProgramKey(arch="llama3.2-1b", mode="decode", batch=1,
                     max_seq=8, opt_level=1)
    image = cache.get(key)
    assert image[:8] == b"N3HPROG1"
    rt = asm.from_binary(image)
    assert rt.step is not None and rt.step.max_seq == 8
    assert cache.get(key) == image          # LRU hit, not a recompile
    assert cache.info()["hits"] == 1
    # decode keys never collide with the fixed-seq image of the same arch
    fixed = cache.get(ProgramKey(arch="llama3.2-1b", seq_len=8,
                                 opt_level=1))
    assert fixed != image and asm.from_binary(fixed).step is None


def test_greedy_generate_lm_uses_prefill(monkeypatch):
    # satellite regression: the lm branch runs ONE real prefill over
    # the whole prompt instead of S0 single-token decode steps
    import repro.serve.engine as eng
    arch = registry.get("llama3.2-1b")
    arch = dataclasses.replace(arch, model=arch.smoke)
    mod = arch.model_module()
    params = mod.init(arch.model, jax.random.key(0))
    prompts = jax.random.randint(jax.random.key(1), (2, 6), 0,
                                 arch.model.vocab)
    calls = {"prefill": 0}
    real = eng.make_prefill_fn

    def spy(*a, **kw):
        calls["prefill"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(eng, "make_prefill_fn", spy)
    out = eng.greedy_generate(arch, params, prompts, n_new=2)
    assert calls["prefill"] == 1
    assert out.shape == (2, 8)
