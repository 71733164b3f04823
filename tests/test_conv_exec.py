"""Functional conv execution: im2col staging, depthwise, CNN chains.

The contract under test (ISSUE 4 acceptance surface):
  * per-layer equivalence vs the ``models/cnn.py`` reference conv: the
    golden executor's im2col-staged GEMM equals ``cnn.conv2d`` (the
    network's ``lax.conv_general_dilated`` primitive) exactly, in the
    integer code domain, for dense and depthwise layers;
  * whole-CNN inference: resnet18, mobilenet_v2 and resnet50 programs (reduced
    geometry-consistent variants) run end to end through the spatial
    chain — shortcut sources, max-pool/GAP glue, inter-layer requant —
    with pallas bit-identical to golden;
  * -O0 vs -O1 invariance on depthwise programs (passes change timing,
    never semantics);
  * programs carry their ConvGeometry bit-exactly through text assembly
    and the ``N3HPROG1`` binary image, and the memory map wires conv
    act fetches straight to the producer's spatial segment (no
    ``L{i}.col`` staging — the fused kernels im2col on chip);
  * multi-device bundles of CNNs (filter shards of depthwise layers,
    pipeline stages) stay bit-exact vs the single-device program.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.compiler import (
    ConvGeometry,
    GemmLayer,
    GoldenExecutor,
    MultiDeviceExecutor,
    PallasExecutor,
    assemble,
    bind_synthetic,
    compile_network,
    derive_plan,
    disassemble,
    from_binary,
    lower_network,
    lower_partitioned,
    optimize_program,
    to_binary,
)
from repro.compiler.cli import execute_report
from repro.compiler.runtime import (
    ExecutionError,
    apply_pool,
    im2col_patches,
    synthetic_weights,
)
from repro.core.scheduler import XC7Z020, DspCoreConfig, LutCoreConfig
from repro.core.workloads import WORKLOADS, ConvSpec
from repro.models import cnn
from repro.models.cnn import CNNConfig, specs_for

LUT = LutCoreConfig(m=8, n=16, k=128)
DSP = DspCoreConfig(n_reg_row_a=13)


def _cnn_layers(arch: str, in_hw: int = 28, width: float = 0.25):
    cfg = CNNConfig(arch=arch, n_classes=10, in_hw=in_hw, width=width)
    return [GemmLayer.from_conv(s) for s in specs_for(cfg)]


def _bound(cls, prog, **kw):
    ex = cls(prog, **kw)
    for lp in prog.layers:
        bind_synthetic(ex, lp, seed=lp.index)
    return ex


def _image(gl: GemmLayer, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        -8, 8, gl.geometry.in_shape).astype(np.int8)


# ---------------------------------------------------------------------------
# Per-layer equivalence vs the models/cnn.py reference conv
# ---------------------------------------------------------------------------

CONV_CASES = [
    ConvSpec("k3s1", 5, 24, 3, 1, 10),
    ConvSpec("k3s2", 7, 20, 3, 2, 9),
    ConvSpec("k7s2", 3, 18, 7, 2, 16),        # the ResNet stem shape
    ConvSpec("k1s1", 12, 30, 1, 1, 6),        # pointwise
    ConvSpec("k1s2", 8, 16, 1, 2, 8),         # downsample shortcut
    ConvSpec("dw3s1", 20, 20, 3, 1, 8, depthwise=True),
    ConvSpec("dw3s2", 24, 24, 3, 2, 9, depthwise=True),
]


@pytest.mark.parametrize("spec", CONV_CASES, ids=lambda s: s.name)
def test_golden_matches_cnn_reference_conv(spec):
    """Im2col staging + (grouped) GEMM == lax.conv on the same codes.

    Both sides stay in exact arithmetic: integer activations/weight
    codes accumulate exactly (int32 GEMM vs fp32 conv of small ints),
    then the same per-filter fp32 scale applies — so equality is ==.
    """
    gl = GemmLayer.from_conv(spec)
    n_lut = gl.dims.n // 3
    prog = lower_network("one", [gl], LUT, DSP, XC7Z020, n_luts=[n_lut])
    ex = _bound(GoldenExecutor, prog)
    x = _image(gl, seed=7)
    got = np.asarray(ex.run_layer(0, x))

    w_lut, s_lut, w_dsp, s_dsp = synthetic_weights(
        0, gl.dims.k, n_lut, gl.dims.n - n_lut, 4, seed=0)
    w = np.concatenate([p for p in (w_lut, w_dsp) if p is not None], axis=1)
    s = np.concatenate([p for p in (s_lut, s_dsp) if p is not None])
    kk, ci = spec.kernel, 1 if spec.depthwise else spec.c_in
    w_hwio = w.reshape(kk, kk, ci, spec.c_out).astype(np.float32)
    ref = cnn.conv2d(jnp.asarray(x, jnp.float32)[None],
                     jnp.asarray(w_hwio), spec)
    ref = np.asarray(ref)[0].reshape(-1, spec.c_out) * s[None, :]
    assert got.shape == (gl.dims.m, gl.dims.n)
    assert (got == ref.astype(np.float32)).all()


def test_im2col_patch_order_matches_hwio_flattening():
    # column order (kh, kw, c) with c fastest == w.reshape(k, n) order
    geom = ConvGeometry(kernel=2, stride=1, pad=1, in_hw=3, out_hw=4,
                        c_in=2, c_out=1)
    x = np.arange(18, dtype=np.int8).reshape(3, 3, 2)
    pat = np.asarray(im2col_patches(jnp.asarray(x), geom))
    assert pat.shape == (16, 4, 2)
    # output position (1, 1) covers input rows/cols 0..1 (pad 1)
    m = 1 * 4 + 1
    want = np.stack([x[0, 0], x[0, 1], x[1, 0], x[1, 1]])
    assert (pat[m] == want).all()


# ---------------------------------------------------------------------------
# Whole-CNN inference: golden vs pallas, end to end
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["resnet18", "mobilenet_v2", "resnet50"])
def test_cnn_end_to_end_pallas_bit_exact_vs_golden(arch):
    # resnet50's 54 golden layers at in-hw 32 (a 1x1 map in layer4)
    layers = _cnn_layers(arch, in_hw=32 if arch == "resnet50" else 28)
    prog = lower_network(arch, layers, LUT, DSP, XC7Z020)
    x = _image(layers[0])
    out_g = np.asarray(_bound(GoldenExecutor, prog).run(x))
    out_p = np.asarray(_bound(PallasExecutor, prog).run(x))
    assert out_g.shape == (1, 10)
    assert np.abs(out_g).sum() > 0
    assert (out_g == out_p).all()


def test_resnet_chain_exercises_shortcut_and_pools():
    layers = _cnn_layers("resnet18")
    by_name = {gl.name: gl for gl in layers}
    assert by_name["conv1"].geometry.pool == "max"
    assert by_name["conv20"].geometry.pool == "gap"
    assert by_name["conv8_ds"].geometry.src_offset == 3
    # the shortcut reads the same spatial input as the block entry
    i = layers.index(by_name["conv8_ds"])
    src = layers[i - 3]
    assert src.geometry.pooled_hw() == by_name["conv8_ds"].geometry.in_hw
    assert src.geometry.c_out == by_name["conv8_ds"].geometry.c_in


def test_chain_rejects_wrong_input_shape():
    layers = _cnn_layers("resnet18")
    prog = lower_network("r", layers, LUT, DSP, XC7Z020)
    ex = _bound(GoldenExecutor, prog)
    with pytest.raises(ExecutionError, match="spatial"):
        ex.run(np.zeros((5, 5, 3), np.int8))


def test_apply_pool_glue():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 8, 4)),
                    jnp.float32)
    assert apply_pool(x, "").shape == (8, 8, 4)
    assert apply_pool(x, "max").shape == (4, 4, 4)
    gap = apply_pool(x, "gap")
    assert gap.shape == (1, 1, 4)
    np.testing.assert_allclose(np.asarray(gap)[0, 0],
                               np.asarray(x).mean(axis=(0, 1)), rtol=1e-6)


# ---------------------------------------------------------------------------
# -O0 vs -O1 invariance on depthwise programs
# ---------------------------------------------------------------------------


def test_depthwise_program_pass_invariant():
    # a mobilenet bottleneck slice: expand -> depthwise -> project
    specs = [ConvSpec("exp", 8, 48, 1, 1, 12),
             ConvSpec("dw", 48, 48, 3, 2, 12, depthwise=True),
             ConvSpec("pw", 48, 16, 1, 1, 6)]
    layers = [GemmLayer.from_conv(s) for s in specs]
    p0 = lower_network("block", layers, LUT, DSP, XC7Z020)
    p1 = optimize_program(p0, 1)
    assert p1.n_instructions < p0.n_instructions
    x = _image(layers[0], seed=3)
    out0 = np.asarray(_bound(GoldenExecutor, p0).run(x))
    out1 = np.asarray(_bound(GoldenExecutor, p1).run(x))
    outp = np.asarray(_bound(PallasExecutor, p1).run(x))
    assert (out0 == out1).all()
    assert (out0 == outp).all()


# ---------------------------------------------------------------------------
# Geometry round-trips + staging memory map
# ---------------------------------------------------------------------------


def test_geometry_round_trips_text_and_binary():
    layers = _cnn_layers("mobilenet_v2")
    prog = lower_network("mb2", layers, LUT, DSP, XC7Z020, opt_level=1)
    assert any(lp.depthwise for lp in prog.layers)
    text = disassemble(prog)
    assert " geom=" in text
    rt = assemble(text)
    assert rt == prog
    assert disassemble(rt) == text
    blob = to_binary(prog)
    rt2 = from_binary(blob)
    assert rt2 == prog
    assert to_binary(rt2) == blob
    for a, b in zip(prog.layers, rt2.layers):
        assert a.geometry == b.geometry


def test_memory_map_has_no_col_staging_segments():
    """Fused-kernel DDR map: conv layers read their producer's spatial
    NHWC segment directly (im2col happens inside the kernel) — no
    ``L{i}.col`` staging copy exists, and the act fetches address the
    ``src_offset`` producer's output (or ``act.in``)."""
    layers = _cnn_layers("resnet18")
    prog = lower_network("r", layers, LUT, DSP, XC7Z020)
    mem = prog.memory
    g0 = layers[0].geometry
    # program input is the spatial image, not its im2col expansion
    assert mem["act.in"].size == \
        (g0.in_hw * g0.in_hw * g0.c_in * 4 + 7) // 8
    assert not any(".col" in seg.name for seg in mem.segments)
    for pos, lp in enumerate(prog.layers):
        src = pos - lp.geometry.src_offset
        seg = mem["act.in"] if src < 0 else mem[f"L{src}.out"]
        # the act fetches address the producer's spatial segment
        for cp in lp.cores():
            from repro.core import isa
            bases = {op.instr.ddr_base for op in cp.streams["fetch"]
                     if isinstance(op.instr, isa.FetchInstr)
                     and op.instr.stage_ctrl == 1}
            assert bases == {seg.base}


def test_full_size_workload_geometry_is_chain_consistent():
    for name, fn in WORKLOADS.items():
        layers = [GemmLayer.from_conv(s) for s in fn()]
        for i, gl in enumerate(layers[1:], start=1):
            src = layers[i - gl.geometry.src_offset].geometry
            assert src.pooled_hw() == gl.geometry.in_hw, (name, gl.name)
            assert src.c_out == gl.geometry.c_in, (name, gl.name)


# ---------------------------------------------------------------------------
# Multi-device CNN bundles stay bit-exact
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["filter", "pipeline"])
def test_cnn_bundle_bit_exact_vs_single(kind):
    layers = _cnn_layers("mobilenet_v2")
    prog = lower_network("mb2", layers, LUT, DSP, XC7Z020)
    x = _image(layers[0])
    ref = np.asarray(_bound(GoldenExecutor, prog).run(x))
    plan = derive_plan(layers, 2, kind)
    mdp = lower_partitioned("mb2", layers, plan, LUT, DSP, XC7Z020)
    mex = MultiDeviceExecutor(mdp)
    for gi in range(mdp.n_layers):
        mex.bind_synthetic(gi, seed=gi)
    assert (np.asarray(mex.run(x)) == ref).all()


def test_filter_shard_of_depthwise_layer_bit_exact():
    # shard a lone depthwise layer: each device computes its channel
    # range from its own input slice; gathered shards == full layer
    spec = ConvSpec("dw", 32, 32, 3, 1, 10, depthwise=True)
    gl = GemmLayer.from_conv(spec)
    prog = lower_network("dw", [gl], LUT, DSP, XC7Z020)
    x = _image(gl, seed=11)
    ex = _bound(GoldenExecutor, prog)
    ref = np.asarray(ex.run_layer(0, x))
    plan = derive_plan([gl], 2, "filter")
    mdp = lower_partitioned("dw", [gl], plan, LUT, DSP, XC7Z020)
    mex = MultiDeviceExecutor(mdp)
    mex.bind_synthetic(0, seed=0)
    got = np.asarray(mex.run_layer(0, x))
    assert (got == ref).all()


# ---------------------------------------------------------------------------
# CLI --execute end-to-end report
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["golden", "pallas"])
def test_execute_report_runs_cnn_end_to_end(backend):
    specs = [ConvSpec("c0", 3, 16, 3, 2, 12, is_first=True),
             ConvSpec("dw", 16, 16, 3, 1, 6, depthwise=True),
             ConvSpec("fc", 16, 10, 1, 1, 6, is_last=True)]
    # fc here is a plain 1x1 conv on the 6x6 map (no GAP glue)
    layers = [GemmLayer.from_conv(s) for s in specs]
    prog = lower_network("tiny", layers, LUT, DSP, XC7Z020)
    report = execute_report(prog, backend=backend)
    assert "executed  3/3 layers end to end" in report
    assert "skipped" not in report


def test_execute_report_checksum_matches_across_backends():
    layers = _cnn_layers("mobilenet_v2", in_hw=14)
    prog = lower_network("mb2", layers, LUT, DSP, XC7Z020)
    r_g = execute_report(prog, backend="golden")
    r_p = execute_report(prog, backend="pallas")
    assert r_g.split("|out| sum")[1] == r_p.split("|out| sum")[1]


def test_compile_network_cnn_carries_geometry():
    prog = compile_network("resnet18")
    assert all(lp.geometry is not None for lp in prog.layers)
    lm = compile_network("llama3.2-1b", seq_len=4)
    assert all(lp.geometry is None for lp in lm.layers)
