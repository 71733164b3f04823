"""Fused split-aware whole-layer Pallas kernels.

The N3H-Core split (Eq. 12) makes a layer's GEMM *one* heterogeneous
computation: the first ``n_lut`` output columns run on the LUT core
(bit-serial, latency ∝ weight bits), the rest on the DSP core
(packed-int4, fixed latency). The batched executor used to mirror that
as two kernel launches plus a host-side concat per layer; these kernels
consume both sides of the split in a *single* launch.

``fused_hetero_gemm`` — one grid whose column-block axis spans the
LUT-region blocks followed by the DSP-region blocks. Per column block
the kernel picks its path with ``pl.when`` on the block index: LUT
blocks accumulate the bitplane decomposition (one int8 MXU matmul per
plane, shifted partial sums, so the cost stays proportional to the
weight bits as the paper's L_LUT is), DSP blocks unpack
two-int4-per-byte weights in-register and issue one int8 matmul (the
packed layout halves the weights' HBM traffic). Both paths share one
int32 VMEM accumulator per output tile and one fp32 per-column dequant
epilogue, so the output lands as a single [M, N] tile in split column
order. A one-sided split is the same launch with zero blocks in the
empty region, which is handed one dummy weight block that is never
read.

``fused_conv_gemm`` — the im2col-free conv variant: the kernel reads
the raw zero-padded NHWC activation block and generates im2col patches
*inside* the launch, contracting tap by tap ((kh, kw) static unroll;
each tap is a [M, C] x [C, bn] matmul against the matching weight
rows). No column matrix is ever materialized — not in DDR (the
``L{i}.col`` staging copy is gone from compiled programs) and not in
VMEM. The whole spatial input must fit on chip; where it does not
(``fused_conv_vmem_bytes``), the ``ops.py`` wrapper runs XLA's int8
convolution from the spatial block instead (a 1x1 window: the oracle's
dot on the block; both bit-identical) and ``ops.conv_path`` reports
"xla_vmem".

Both kernels compile for the TPU (``tests/test_tpu_compile.py``) and
are validated in interpret mode against the pure-jnp oracles
(``ref.fused_hetero_gemm_ref``). Under ``mode="auto"`` off the TPU the
wrappers dispatch the oracles instead, still as one jitted call per
layer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 128


def _plane_weights(bits: int) -> list[int]:
    """Python-int two's-complement plane weights (jnp constants cannot
    be captured in-kernel)."""
    return [2 ** b for b in range(bits - 1)] + [-(2 ** (bits - 1))]


def unpack_int4_block(p: jax.Array) -> jax.Array:
    """[bk, bn//2] packed bytes -> [bk, bn] int8 codes (sign-extended).

    The low nibbles are the block's first ``bn//2`` columns and the high
    nibbles its last ``bn//2`` (``ref.pack_int4`` with ``block=bn``), so
    the unpack is one concat of two halves. The shifts run in int32:
    Mosaic has no int8 vector shifts.
    """
    w = p.astype(jnp.int32)
    lo = (w << 28) >> 28                    # arithmetic shift sign-extends
    hi = w >> 4
    return jnp.concatenate([lo, hi], axis=1).astype(jnp.int8)


def dsp_blocks(packed: jax.Array, bn: int) -> jax.Array:
    """[K, N//2] packed bytes -> [N//bn, K, bn//2]: one contiguous slab
    per DSP column block. A (bk, bn//2) block of the 2-D layout is only
    legal on the TPU while it spans the whole array (its 64 lanes are
    not a multiple of 128); a slab's last dim always does."""
    k, nh = packed.shape
    return packed.reshape(k, nh // (bn // 2), bn // 2).transpose(1, 0, 2)


# ---------------------------------------------------------------------------
# Dense fused kernel: [M, K] x (LUT planes | packed int4) -> [M, N]
# ---------------------------------------------------------------------------


def _fused_kernel(x_ref, planes_ref, packed_ref, scale_ref, out_ref,
                  acc_ref, *, bits: int, nk: int, nn_lut: int):
    """One (m, col, k) grid step. Column blocks j < nn_lut take the
    bitplane path; blocks j >= nn_lut take the packed-int4 path. Both
    land in the same int32 accumulator and fp32 dequant epilogue."""
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                   # [bm, bk] int8

    @pl.when(j < nn_lut)
    def _lut():
        s = _plane_weights(bits)
        acc = acc_ref[...]
        for b in range(bits):                        # static unroll: planes
            part = jax.lax.dot(x, planes_ref[b],
                               preferred_element_type=jnp.int32)
            acc = acc + s[b] * part
        acc_ref[...] = acc

    @pl.when(j >= nn_lut)
    def _dsp():
        w = unpack_int4_block(packed_ref[...])       # [bk, bn] int8
        acc_ref[...] += jax.lax.dot(x, w, preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _done():
        out_ref[...] = acc_ref[...].astype(jnp.float32) * scale_ref[...]


def _check_regions(planes: jax.Array, packed: jax.Array,
                   w_scale: jax.Array, bits: int, nn: int, bn: int) -> None:
    """The checks both kernels make of their split operands."""
    if planes.shape[0] != max(bits, 1):
        raise ValueError(f"planes leading dim {planes.shape[0]} != bits "
                         f"{bits}")
    if nn == 0:
        raise ValueError("grid needs at least one column block")
    if planes.shape[2] < bn or packed.shape[1] < bn // 2:
        raise ValueError("each region needs at least one weight block "
                         "(use a dummy when the region is empty)")
    if w_scale.shape[0] < nn * bn:
        raise ValueError(f"scales {w_scale.shape[0]} < grid columns "
                         f"{nn * bn}")


@functools.partial(jax.jit, static_argnames=(
    "bits", "n_lut_blocks", "n_dsp_blocks", "bm", "bn", "bk", "interpret"))
def fused_hetero_gemm(x: jax.Array, planes: jax.Array, packed: jax.Array,
                      w_scale: jax.Array, bits: int, n_lut_blocks: int,
                      n_dsp_blocks: int, *, bm: int = DEFAULT_BM,
                      bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
                      interpret: bool = False) -> jax.Array:
    """Single-launch split GEMM over pre-padded operands.

    x: [M, K] int8; planes: [max(bits, 1), K, >=bn] int8 {0, 1} plane
    stack of the LUT columns; packed: [K, >=bn//2] int8
    ``ref.pack_int4`` bytes (``block=bn``) of the DSP columns; w_scale:
    [(n_lut_blocks + n_dsp_blocks) * bn] fp32 in split region order,
    handed to the kernel as a [1, N] row in (1, bn) blocks. The grid
    covers ``n_lut_blocks`` LUT column blocks then ``n_dsp_blocks`` DSP
    blocks; a region with zero blocks still needs one (dummy,
    never-consumed) weight block so its BlockSpec stays in-bounds. M
    and K must divide by their blocks (pad at the ops.py layer).
    Returns fp32 [M, N] in split column order.
    """
    m, k = x.shape
    nn = n_lut_blocks + n_dsp_blocks
    _check_regions(planes, packed, w_scale, bits, nn, bn)
    if m % bm or k % bk:
        raise ValueError(f"shape ({m},{k}) not divisible by blocks "
                         f"({bm},{bk}); pad first")
    n = nn * bn
    nk = k // bk

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    nl = n_lut_blocks
    return pl.pallas_call(
        functools.partial(_fused_kernel, bits=bits, nk=nk, nn_lut=nl),
        grid=(m // bm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            # clamp each region's block index so the other region's
            # blocks read a valid (ignored) block instead of OOB
            pl.BlockSpec((max(bits, 1), bk, bn),
                         lambda i, j, kk: (0, kk, jnp.minimum(j, nl - 1)
                                           if nl else 0)),
            pl.BlockSpec((None, bk, bn // 2),
                         lambda i, j, kk: (jnp.maximum(j - nl, 0), kk, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        **kwargs,
    )(x, planes, dsp_blocks(packed, bn), w_scale[:n].reshape(1, n))


# ---------------------------------------------------------------------------
# Conv fused kernel: in-kernel im2col from the NHWC activation block
# ---------------------------------------------------------------------------


def fused_conv_vmem_bytes(in_hw: int, c_in: int, kernel: int, pad: int,
                          m: int, k: int, bits: int,
                          bn: int = DEFAULT_BN) -> int:
    """Rough VMEM working set of one ``fused_conv_gemm`` grid step: the
    padded spatial block, the per-column-block weight stack (planes are
    the worst case), the int32 accumulator and the fp32 output tile.
    The ops.py wrapper falls back to XLA's int8 convolution when this
    exceeds the budget."""
    hp = in_hw + 2 * pad
    x_bytes = hp * hp * c_in
    w_bytes = max(bits, 1) * k * bn
    acc_bytes = 2 * m * bn * 4
    return x_bytes + w_bytes + acc_bytes


def _stride_phases(x: jax.Array, stride: int, extent: int) -> jax.Array:
    """[H, W, C] -> [stride**2, extent, extent, C]: phase ``a*stride+b``
    holds rows ``a::stride`` and columns ``b::stride``, so every tap of
    a strided window is a unit-stride slice of one phase (Mosaic slices
    vectors with unit strides only). Rows past the input are zeros and
    are never read."""
    e = extent * stride
    h, w, c = x.shape
    x = jnp.pad(x[:e, :e], ((0, max(e - h, 0)), (0, max(e - w, 0)), (0, 0)))
    x = x.reshape(extent, stride, extent, stride, c)
    return x.transpose(1, 3, 0, 2, 4).reshape(stride * stride, extent,
                                              extent, c)


def _fused_conv_kernel(x_ref, planes_ref, packed_ref, scale_ref, out_ref, *,
                       bits: int, nn_lut: int, kernel: int, stride: int,
                       out_hw: int, c_in: int):
    """One column-block grid step: generate im2col patches in-kernel
    (tap-by-tap static unroll over the (kh, kw) window) and contract
    them against this block's weight rows — LUT blocks through the
    bitplane path, DSP blocks through packed int4. A 1x1 conv arrives
    as its [M, C] pixel rows and is its own single tap."""
    j = pl.program_id(0)
    x = x_ref[...]
    m = out_hw * out_hw

    def taps():
        if kernel == 1:
            yield 0, x                       # [M, C]
            return
        for t, (dh, dw) in enumerate(
                (a, b) for a in range(kernel) for b in range(kernel)):
            ph = (dh % stride) * stride + dw % stride
            r0, c0 = dh // stride, dw // stride
            xt = x[ph, r0:r0 + out_hw, c0:c0 + out_hw, :]  # [oh, oh, C]
            yield t, xt.reshape(m, c_in)

    @pl.when(j < nn_lut)
    def _lut():
        s = _plane_weights(bits)
        acc = jnp.zeros(out_ref.shape, jnp.int32)
        for t, xt in taps():
            rows = slice(t * c_in, (t + 1) * c_in)
            for b in range(bits):
                part = jax.lax.dot(xt, planes_ref[b, rows],
                                   preferred_element_type=jnp.int32)
                acc = acc + s[b] * part
        out_ref[...] = acc.astype(jnp.float32) * scale_ref[...]

    @pl.when(j >= nn_lut)
    def _dsp():
        acc = jnp.zeros(out_ref.shape, jnp.int32)
        for t, xt in taps():
            w = unpack_int4_block(packed_ref[t * c_in:(t + 1) * c_in, :])
            acc = acc + jax.lax.dot(xt, w,
                                    preferred_element_type=jnp.int32)
        out_ref[...] = acc.astype(jnp.float32) * scale_ref[...]


def _named(f, name: str):
    """``f`` under a jit named ``name``. XLA names what it emits for the
    innermost jit after that jit, so a kernel launched here is the
    instruction ``%<name>`` in the compiled HLO and in the profiler's
    device ops."""
    def call(*args):
        return f(*args)
    call.__name__ = call.__qualname__ = name
    return jax.jit(call)


@functools.partial(jax.jit, static_argnames=(
    "bits", "n_lut_blocks", "n_dsp_blocks", "kernel", "stride", "out_hw",
    "bn", "interpret", "name"))
def fused_conv_gemm(x_sp: jax.Array, planes: jax.Array, packed: jax.Array,
                    w_scale: jax.Array, bits: int, n_lut_blocks: int,
                    n_dsp_blocks: int, kernel: int, stride: int,
                    out_hw: int, *, bn: int = DEFAULT_BN,
                    interpret: bool = False,
                    name: str = "fused_conv_gemm") -> jax.Array:
    """Single-launch im2col-free conv GEMM.

    x_sp: [H+2p, W+2p, C] int8 — the *already zero-padded* spatial
    activation block (code 0 is real 0.0 under the symmetric
    quantizer); planes: [bits, kernel**2*C, >=bn] LUT plane stack in
    (kh, kw, c) row order (the HWIO flattening); packed:
    [kernel**2*C, >=bn//2] int4-pair bytes (``ref.pack_int4``,
    ``block=bn``); w_scale: [(n_lut_blocks + n_dsp_blocks) * bn] fp32
    in split region order. The grid covers ``n_lut_blocks`` LUT column
    blocks then ``n_dsp_blocks`` DSP blocks; a region with zero blocks
    still needs one (dummy, never-consumed) weight block so its
    BlockSpec stays in-bounds. Before the launch, a strided window is
    split into its stride phases and a 1x1 conv is gathered into its
    [out_hw**2, C] pixel rows, so the kernel slices with unit strides.
    ``name`` names the launch (the executor names it by its window
    and its layers, ``fused_conv_gemm_<k>x<k>_L<index>[_<index>...]``).
    Returns fp32 [out_hw**2, N] in split column order.
    """
    c_in = x_sp.shape[2]
    nn = n_lut_blocks + n_dsp_blocks
    _check_regions(planes, packed, w_scale, bits, nn, bn)
    n = nn * bn
    k = kernel * kernel * c_in
    m = out_hw * out_hw
    if kernel == 1:
        span = stride * (out_hw - 1) + 1
        x = x_sp[:span:stride, :span:stride].reshape(m, c_in)
    else:
        x = _stride_phases(x_sp, stride, (kernel - 1) // stride + out_hw)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))

    nl = n_lut_blocks
    zeros = (0,) * x.ndim
    call = pl.pallas_call(
        functools.partial(
            _fused_conv_kernel, bits=bits, nn_lut=nl, kernel=kernel,
            stride=stride, out_hw=out_hw, c_in=c_in),
        grid=(nn,),
        in_specs=[
            pl.BlockSpec(x.shape, lambda j: zeros),
            pl.BlockSpec((max(bits, 1), k, bn),
                         lambda j: (0, 0, jnp.minimum(j, nl - 1)
                                    if nl else 0)),
            pl.BlockSpec((None, k, bn // 2),
                         lambda j: (jnp.maximum(j - nl, 0), 0, 0)),
            pl.BlockSpec((1, bn), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
        name=name,
        **kwargs,
    )
    return _named(call, name)(x, planes, dsp_blocks(packed, bn),
                              w_scale[:n].reshape(1, n))
