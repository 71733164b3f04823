"""Fused split-aware whole-layer kernels (ISSUE 7 acceptance surface).

The contract under test:
  * kernel-level bit-exactness: the fused oracles equal the two-call
    ``hetero_gemm_ref`` path at mixed (bits, split-ratio) corners —
    including one-sided splits — and the actual Pallas kernel bodies
    (interpret mode) equal the oracles for dense and in-kernel-im2col
    conv variants;
  * executor-level bit-exactness: ``PallasExecutor`` equals
    ``GoldenExecutor`` per layer (dense conv, depthwise, 1x1 LM
    GEMMs) and end to end on resnet18 / mobilenet_v2 / llama3.2-1b
    smoke programs, at -O0 and -O1, single- and 2-device
    (filter-parallel bundle);
  * the conv DDR map has no ``L{i}.col`` staging segment (pinned in
    ``test_conv_exec.py``) and the spatial input path feeds the fused
    conv call directly;
  * the per-program JIT cache builds fn tables atomically (threaded
    regression for the old lazy-mutation race), evicts the least
    recently used program past its capacity, and hits/misses land in
    ``obs.metrics.METRICS`` as ``pallas.jit_cache.*``.
"""
import threading

import numpy as np
import jax.numpy as jnp
import pytest

from repro.compiler import (
    GemmLayer,
    GoldenExecutor,
    MultiDeviceExecutor,
    PallasExecutor,
    bind_synthetic,
    compile_network,
    derive_plan,
    lower_network,
    lower_partitioned,
)
from repro.core.scheduler import XC7Z020, DspCoreConfig, LutCoreConfig
from repro.core.workloads import ConvSpec
from repro.kernels import ops, ref
from repro.kernels.fused_hetero_gemm import dsp_blocks, fused_conv_gemm
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


def _split_weights(rng, k, n_lut, n_dsp, bits):
    w_lut = jnp.asarray(rng.integers(-(2 ** (bits - 1)), 2 ** (bits - 1),
                                     (k, n_lut)), jnp.int32) if n_lut else None
    w_dsp = jnp.asarray(rng.integers(-8, 8, (k, n_dsp)),
                        jnp.int32) if n_dsp else None
    s_lut = jnp.asarray(rng.uniform(0.5, 2.0, n_lut),
                        jnp.float32) if n_lut else None
    s_dsp = jnp.asarray(rng.uniform(0.5, 2.0, n_dsp),
                        jnp.float32) if n_dsp else None
    return w_lut, s_lut, w_dsp, s_dsp


# ---------------------------------------------------------------------------
# Kernel-level: fused oracle / fused Pallas kernel vs the two-call path
# ---------------------------------------------------------------------------

SPLIT_CORNERS = [
    # (bits, n_lut, n_dsp): mixed ratios incl. one-sided splits
    (2, 24, 40), (4, 16, 48), (6, 40, 24), (8, 62, 2),
    (4, 0, 64), (4, 64, 0), (3, 2, 62),
]


@pytest.mark.parametrize("bits,n_lut,n_dsp", SPLIT_CORNERS)
def test_fused_ref_equals_two_call_path(bits, n_lut, n_dsp):
    rng = np.random.default_rng(bits * 100 + n_lut)
    m, k = 24, 96
    x = jnp.asarray(rng.integers(-128, 128, (m, k)), jnp.int8)
    w_lut, s_lut, w_dsp, s_dsp = _split_weights(rng, k, n_lut, n_dsp, bits)
    outs = []
    if n_lut:
        outs.append(ref.bitserial_gemm_ref(x, w_lut, s_lut, bits))
    if n_dsp:
        outs.append(ref.int4_gemm_ref(x, ref.pack_int4(w_dsp), s_dsp))
    want = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    got = ref.fused_hetero_gemm_ref(x, w_lut, s_lut, bits, w_dsp, s_dsp)
    assert (np.asarray(want) == np.asarray(got)).all()


@pytest.mark.parametrize("bits,n_lut,n_dsp", SPLIT_CORNERS)
def test_fused_kernel_interpret_equals_ref(bits, n_lut, n_dsp):
    """The actual Pallas kernel body (interpret mode on CPU), via the
    ops wrapper's padding/splicing, on non-block-multiple extents."""
    rng = np.random.default_rng(bits * 100 + n_dsp)
    m, k = 13, 72
    x = jnp.asarray(rng.integers(-128, 128, (m, k)), jnp.int8)
    w_lut, s_lut, w_dsp, s_dsp = _split_weights(rng, k, n_lut, n_dsp, bits)
    want = ref.fused_hetero_gemm_ref(x, w_lut, s_lut, bits, w_dsp, s_dsp)
    got = ops.fused_matmul(x, w_lut, s_lut, bits, w_dsp, s_dsp,
                           mode="kernel", block=(8, 32, 16))
    assert (np.asarray(want) == np.asarray(got)).all()


@pytest.mark.parametrize("kernel,stride,pad", [(3, 1, 1), (3, 2, 0),
                                               (1, 1, 0), (5, 2, 2),
                                               (3, 2, 1), (1, 2, 0)])
def test_fused_conv_kernel_in_kernel_im2col_equals_staged(kernel, stride,
                                                          pad):
    """In-kernel patch generation == out-of-kernel staging + dense
    fused GEMM, through the actual conv kernel body in interpret mode."""
    bits, n_lut, n_dsp, in_hw, c_in, bn = 5, 16, 24, 9, 4, 8
    out_hw = (in_hw + 2 * pad - kernel) // stride + 1
    rng = np.random.default_rng(kernel * 10 + stride)
    x_sp = jnp.asarray(rng.integers(-128, 128, (in_hw, in_hw, c_in)),
                       jnp.int8)
    k = kernel * kernel * c_in
    w_lut, s_lut, w_dsp, s_dsp = _split_weights(rng, k, n_lut, n_dsp, bits)
    col = ref.conv_patches_ref(x_sp, kernel, stride, pad,
                               out_hw).reshape(out_hw * out_hw, k)
    want = ref.fused_hetero_gemm_ref(col, w_lut, s_lut, bits, w_dsp, s_dsp)

    planes = ops._pad_to(ref.bitplane_decompose(w_lut, bits), 2, bn)
    packed = dsp_blocks(ref.pack_int4(ops._pad_to(w_dsp, 1, bn), block=bn),
                        bn)
    sp = jnp.concatenate([ops._pad_to(s_lut, 0, bn),
                          ops._pad_to(s_dsp, 0, bn)])
    xp = jnp.pad(x_sp, ((pad, pad), (pad, pad), (0, 0)))
    out = fused_conv_gemm(xp, planes, packed, sp, bits,
                          planes.shape[2] // bn, packed.shape[0],
                          kernel, stride, out_hw, bn=bn, interpret=True)
    nlp = planes.shape[2]
    got = jnp.concatenate([out[:, :n_lut], out[:, nlp:nlp + n_dsp]], axis=1)
    assert (np.asarray(want) == np.asarray(got)).all()


def test_fused_grouped_ref_equals_per_partition():
    bits, m, kk, n_lut, n_dsp = 6, 18, 9, 7, 13
    rng = np.random.default_rng(3)
    x_col = jnp.asarray(rng.integers(-128, 128, (m, kk, n_lut + n_dsp)),
                        jnp.int8)
    w_lut, s_lut, w_dsp, s_dsp = _split_weights(rng, kk, n_lut, n_dsp, bits)
    want = jnp.concatenate([
        ref.bitserial_grouped_gemm_ref(x_col[:, :, :n_lut], w_lut, s_lut,
                                       bits),
        ref.int4_grouped_gemm_ref(x_col[:, :, n_lut:], w_dsp, s_dsp)],
        axis=1)
    got = ops.fused_grouped_matmul(x_col, w_lut, s_lut, bits, w_dsp, s_dsp)
    assert (np.asarray(want) == np.asarray(got)).all()


def test_fused_conv_vmem_fallback_is_bit_exact():
    """Over-budget spatial inputs fall back to XLA's int8 convolution
    — same bits, still one fused jit call."""
    bits, n_lut, n_dsp, in_hw, c_in = 4, 8, 8, 6, 3
    kernel = stride = 1
    out_hw = in_hw
    rng = np.random.default_rng(9)
    x_sp = jnp.asarray(rng.integers(-128, 128, (in_hw, in_hw, c_in)),
                       jnp.int8)
    w_lut, s_lut, w_dsp, s_dsp = _split_weights(rng, c_in, n_lut, n_dsp,
                                                bits)
    a = ops.fused_conv_matmul(x_sp, kernel, stride, 0, out_hw, w_lut,
                              s_lut, bits, w_dsp, s_dsp, mode="ref")
    b = ops.fused_conv_matmul(x_sp, kernel, stride, 0, out_hw, w_lut,
                              s_lut, bits, w_dsp, s_dsp, mode="kernel",
                              vmem_budget=1)     # force the fallback
    assert (np.asarray(a) == np.asarray(b)).all()


#: (kernel, stride, pad, C, in_hw): the over-budget layers of the
#: networks — resnet's 7x7 s2 stem, mobilenet_v2's 3x3 s2 stem and its
#: 1x1 ``b0_pw`` (the oracle's dot), and a 3x3 s1 window — at CPU-cheap
#: sizes
XLA_VMEM_GEOMETRIES = [(7, 2, 3, 3, 32), (3, 2, 1, 3, 24), (1, 1, 0, 32, 16),
                       (3, 1, 1, 16, 16)]
#: (n_lut, n_dsp): both sides, LUT only, DSP only, and an n_lut that is
#: not a multiple of the 128-column block
XLA_VMEM_SPLITS = [(49, 15), (24, 0), (0, 20), (130, 6)]


@pytest.mark.parametrize("geom", XLA_VMEM_GEOMETRIES,
                         ids=lambda g: "k{}s{}p{}c{}".format(*g[:4]))
@pytest.mark.parametrize("split", XLA_VMEM_SPLITS,
                         ids=lambda s: "lut{}dsp{}".format(*s))
@pytest.mark.parametrize("bits", [2, 4, 8])
def test_xla_vmem_conv_is_bitwise_the_oracle(geom, split, bits):
    """The over-budget route (XLA's int8 convolution from the spatial
    block, no patch tensor, for windows wider than 1x1) gives the bits
    of ``mode="ref"`` (patches staged by ``ref.conv_patches_ref``)."""
    kernel, stride, pad, c_in, in_hw = geom
    n_lut, n_dsp = split
    out_hw = (in_hw + 2 * pad - kernel) // stride + 1
    assert ops.conv_path(in_hw, c_in, kernel, pad, out_hw, bits,
                         mode="kernel", vmem_budget=1) == "xla_vmem"
    rng = np.random.default_rng(bits * 1000 + n_lut + kernel)
    x_sp = jnp.asarray(rng.integers(-128, 128, (in_hw, in_hw, c_in)),
                       jnp.int8)
    w_lut, s_lut, w_dsp, s_dsp = _split_weights(
        rng, kernel * kernel * c_in, n_lut, n_dsp, bits)
    args = (x_sp, kernel, stride, pad, out_hw, w_lut, s_lut, bits,
            w_dsp, s_dsp)
    want = np.asarray(ops.fused_conv_matmul(*args, mode="ref"))
    got = np.asarray(ops.fused_conv_matmul(*args, mode="kernel",
                                           vmem_budget=1))
    assert got.shape == (out_hw * out_hw, n_lut + n_dsp)
    assert got.tobytes() == want.tobytes()


# ---------------------------------------------------------------------------
# Executor-level: fused PallasExecutor vs GoldenExecutor
# ---------------------------------------------------------------------------

LAYER_CASES = [
    # dense conv, depthwise conv, pointwise (the 1x1 LM-GEMM shape)
    ConvSpec("k3s1", 5, 24, 3, 1, 10),
    ConvSpec("k7s2", 3, 18, 7, 2, 16),
    ConvSpec("dw3s1", 20, 20, 3, 1, 8, depthwise=True),
    ConvSpec("k1s1", 12, 30, 1, 1, 6),
]


@pytest.mark.parametrize("spec", LAYER_CASES, ids=lambda s: s.name)
@pytest.mark.parametrize("bits", [2, 4, 8])
def test_fused_layer_bit_exact_vs_golden(spec, bits):
    gl = GemmLayer.from_conv(spec)
    n_lut = gl.dims.n // 3
    prog = lower_network("one", [gl], LUT, DSP, XC7Z020, n_luts=[n_lut],
                         bits_w_lut=bits)
    golden = _bound(GoldenExecutor, prog)
    fused = _bound(PallasExecutor, prog)
    x = np.random.default_rng(7).integers(
        -8, 8, gl.geometry.in_shape).astype(np.int8)
    assert (np.asarray(golden.run_layer(0, x))
            == np.asarray(fused.run_layer(0, x))).all()


@pytest.mark.parametrize("n_lut_frac", [0.0, 0.3, 1.0])
def test_fused_layer_split_ratio_corners(n_lut_frac):
    gl = GemmLayer.from_conv(ConvSpec("c", 6, 20, 3, 1, 12))
    n_lut = int(gl.dims.n * n_lut_frac)
    prog = lower_network("one", [gl], LUT, DSP, XC7Z020, n_luts=[n_lut])
    golden = _bound(GoldenExecutor, prog)
    fused = _bound(PallasExecutor, prog)
    x = np.random.default_rng(1).integers(
        -8, 8, gl.geometry.in_shape).astype(np.int8)
    assert (np.asarray(golden.run_layer(0, x))
            == np.asarray(fused.run_layer(0, x))).all()


@pytest.mark.parametrize("opt_level", [0, 1])
def test_lm_program_fused_bit_exact_mixed_bits(opt_level):
    """1x1 LM GEMMs at per-layer mixed (bits, split) through -O0/-O1:
    fused == golden, layer by layer (the DSP-only layers too)."""
    prog = compile_network("llama3.2-1b", seq_len=8)
    bw = [2 + (lp.index % 4) for lp in prog.layers]
    n_luts = [lp.dims.n * (lp.index % 3) // 4 for lp in prog.layers]
    layers = [GemmLayer(name=lp.name, dims=lp.dims) for lp in prog.layers]
    prog = lower_network("lm-mixed", layers, LUT, DSP, XC7Z020,
                         bits_w_lut=bw, n_luts=n_luts,
                         opt_level=opt_level)
    golden = _bound(GoldenExecutor, prog)
    fused = _bound(PallasExecutor, prog)
    for lp in prog.layers:
        x = np.random.default_rng(100 + lp.index).integers(
            -8, 8, (lp.dims.m, lp.dims.k)).astype(np.int8)
        g = np.asarray(golden.run_layer(lp.index, x))
        assert (g == np.asarray(fused.run_layer(lp.index, x))).all()


@pytest.mark.parametrize("arch", ["resnet18", "mobilenet_v2"])
@pytest.mark.parametrize("opt_level", [0, 1])
def test_cnn_e2e_fused_bit_exact(arch, opt_level):
    layers = _cnn_layers(arch)
    prog = lower_network(arch, layers, LUT, DSP, XC7Z020,
                         opt_level=opt_level)
    golden = _bound(GoldenExecutor, prog)
    fused = _bound(PallasExecutor, prog)
    x = np.random.default_rng(0).integers(
        -8, 8, layers[0].geometry.in_shape).astype(np.int8)
    assert (np.asarray(golden.run(x)) == np.asarray(fused.run(x))).all()


def test_two_device_filter_bundle_fused_bit_exact():
    layers = _cnn_layers("mobilenet_v2")
    prog = lower_network("mb2", layers, LUT, DSP, XC7Z020)
    x = np.random.default_rng(0).integers(
        -8, 8, layers[0].geometry.in_shape).astype(np.int8)
    ref_out = np.asarray(_bound(GoldenExecutor, prog).run(x))
    plan = derive_plan(layers, 2, "filter")
    mdp = lower_partitioned("mb2", layers, plan, LUT, DSP, XC7Z020)
    mex = MultiDeviceExecutor(mdp, backend="pallas")
    for gi in range(mdp.n_layers):
        mex.bind_synthetic(gi, seed=gi)
    assert all(isinstance(e, PallasExecutor) for e in mex.executors)
    assert (np.asarray(mex.run(x)) == ref_out).all()


def test_prestaged_input_still_works_under_fused():
    """A conv layer handed the pre-staged [m, k] matrix (not the
    spatial tensor) takes the dense fused entry, same bits."""
    gl = GemmLayer.from_conv(ConvSpec("c", 5, 24, 3, 1, 10))
    prog = lower_network("one", [gl], LUT, DSP, XC7Z020,
                         n_luts=[gl.dims.n // 2])
    golden = _bound(GoldenExecutor, prog)
    fused = _bound(PallasExecutor, prog)
    x_sp = np.random.default_rng(2).integers(
        -8, 8, gl.geometry.in_shape).astype(np.int8)
    col = ref.conv_patches_ref(jnp.asarray(x_sp, jnp.int8), 3, 1, 1,
                               gl.geometry.out_hw)
    x_col = np.asarray(col).reshape(gl.dims.m, gl.dims.k)
    want = np.asarray(golden.run_layer(0, x_sp))
    assert (want == np.asarray(fused.run_layer(0, x_col))).all()
    assert (want == np.asarray(fused.run_layer(0, x_sp))).all()


@pytest.mark.parametrize("kw,want", [
    (dict(depthwise=True, mode="kernel"), "xla_depthwise"),
    (dict(depthwise=True, mode="ref"), "xla_depthwise"),
    (dict(mode="ref"), "ref"),
    (dict(mode="auto"), "ref"),                 # off the TPU
    (dict(mode="kernel"), "interpret"),         # off the TPU
    (dict(mode="kernel", vmem_budget=1), "xla_vmem"),
])
def test_conv_path_names_where_a_layer_runs(kw, want):
    assert ops.conv_path(28, 16, 3, 1, 28, 4, **kw) == want


def test_executor_reports_each_layer_path():
    """Every executed layer records where it ran and bumps its
    ``pallas.layer.<path>`` counter: no layer leaves the kernel
    silently."""
    from repro.obs import METRICS
    layers = _cnn_layers("mobilenet_v2")
    prog = lower_network("mb2", layers, LUT, DSP, XC7Z020)
    ex = _bound(PallasExecutor, prog, mode="kernel")
    before = {p: METRICS.counter(f"pallas.layer.{p}")
              for p in ("interpret", "xla_depthwise", "ref")}
    ex.run(np.zeros(layers[0].geometry.in_shape, np.int8))
    dw = {lp.name for lp in prog.layers if lp.depthwise}
    assert dw and ex.layer_paths == {
        lp.name: "xla_depthwise" if lp.name in dw else "interpret"
        for lp in prog.layers}
    delta = {p: METRICS.counter(f"pallas.layer.{p}") - n
             for p, n in before.items()}
    assert delta == {"interpret": len(prog.layers) - len(dw),
                     "xla_depthwise": len(dw), "ref": 0}


# ---------------------------------------------------------------------------
# JIT cache: atomic tables, LRU capacity, metrics, spans
# ---------------------------------------------------------------------------


def test_program_fn_tables_built_atomically_threaded():
    """Regression for the lazy per-key mutation race: many threads
    constructing executors and running layers concurrently must agree
    bit for bit and never hit a partially-built table (KeyError)."""
    PallasExecutor.cache_clear()
    prog = lower_network(
        "tiny", [GemmLayer.from_conv(ConvSpec("c", 5, 16, 3, 1, 8))],
        LUT, DSP, XC7Z020, n_luts=[8])
    x = np.random.default_rng(0).integers(
        -8, 8, prog.layers[0].geometry.in_shape).astype(np.int8)
    want = np.asarray(_bound(PallasExecutor, prog).run_layer(0, x))

    errs, outs = [], []
    barrier = threading.Barrier(8)

    def worker():
        try:
            barrier.wait()
            ex = _bound(PallasExecutor, prog)
            outs.append(np.asarray(ex.run_layer(0, x)))
        except Exception as e:          # noqa: BLE001 — collect to assert
            errs.append(e)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs
    assert all((o == want).all() for o in outs)
    # one shared table: every constructor after the first was a hit
    info = PallasExecutor.cache_info()
    assert info["programs"] == 1
    assert info["misses"] >= 1 and info["hits"] + info["misses"] >= 9


def test_jit_cache_evicts_least_recently_used(monkeypatch):
    monkeypatch.setattr(PallasExecutor, "_jit_cache_capacity", 2)
    PallasExecutor.cache_clear()
    progs = [lower_network(
        f"tiny{i}", [GemmLayer.from_conv(ConvSpec("c", 5, 16, 3, 1, 8))],
        LUT, DSP, XC7Z020, n_luts=[8 * i]) for i in range(3)]
    PallasExecutor(progs[0])
    PallasExecutor(progs[1])
    PallasExecutor(progs[0])            # progs[1] is now the least recent
    PallasExecutor(progs[2])
    keys = {key[0] for key in PallasExecutor._jit_cache}
    assert keys == {progs[0].fingerprint(), progs[2].fingerprint()}
    assert PallasExecutor.cache_info()["maxsize"] == 2
    assert PallasExecutor.cache_info()["programs"] == 2


def test_jit_cache_metrics_published():
    from repro.obs.metrics import METRICS
    PallasExecutor.cache_clear()
    prog = lower_network(
        "tiny", [GemmLayer.from_conv(ConvSpec("c", 5, 16, 3, 1, 8))],
        LUT, DSP, XC7Z020, n_luts=[8])
    before = METRICS.snapshot()["counters"]
    PallasExecutor(prog)
    PallasExecutor(prog)
    after = METRICS.snapshot()["counters"]
    assert after.get("pallas.jit_cache.miss", 0) \
        - before.get("pallas.jit_cache.miss", 0) == 1
    assert after.get("pallas.jit_cache.hit", 0) \
        - before.get("pallas.jit_cache.hit", 0) == 1
    assert METRICS.snapshot()["gauges"]["pallas.jit_cache.programs"] >= 1
