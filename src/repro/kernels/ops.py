"""Public jit'd wrappers around the Pallas kernels.

Responsibilities the raw kernels don't take:
  * shape padding to block multiples (and un-padding the result);
  * backend dispatch by ``mode``: "auto" runs the compiled Pallas
    kernel on a TPU and the pure-jnp oracle anywhere else; "kernel"
    runs the kernel (in interpret mode off the TPU, which is how the
    tests execute kernel bodies on the CPU); "ref" runs the oracle;
  * two conv cases that never reach a kernel, whatever the mode
    (:func:`conv_path` names them): depthwise layers are an exact
    int32 einsum under XLA, and a conv whose whole-spatial working set
    is over :data:`FUSED_CONV_VMEM_BUDGET` is XLA's int8 convolution
    from the spatial block (no patch tensor; a 1x1 window, whose patch
    matrix is the block itself, keeps the oracle's dot), bit-identical
    to the oracle;
  * GQA head broadcasting for flash attention.

These wrappers are the only entry points the model zoo uses.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash_kernel
from repro.kernels.fused_hetero_gemm import (
    fused_conv_gemm as _fused_conv_kernel,
    fused_conv_vmem_bytes,
    fused_hetero_gemm as _fused_kernel,
)

#: VMEM working-set ceiling (bytes) above which the fused conv kernel
#: falls back to XLA (whole spatial input must fit on chip for
#: in-kernel im2col).
FUSED_CONV_VMEM_BUDGET = 12 * 1024 * 1024


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_ref(mode: str) -> bool:
    return mode == "ref" or (mode == "auto" and not _on_tpu())


#: every name :func:`kernel_path` and :func:`conv_path` return
PATHS = ("kernel", "interpret", "ref", "xla_vmem", "xla_depthwise")


def kernel_path(mode: str = "auto") -> str:
    """Where a dense GEMM under ``mode`` runs: "kernel" (Pallas,
    compiled for the TPU), "interpret" (the kernel body in interpret
    mode off the TPU) or "ref" (the jnp oracle)."""
    if _use_ref(mode):
        return "ref"
    return "kernel" if _on_tpu() else "interpret"


def conv_path(in_hw: int, c_in: int, kernel: int, pad: int, out_hw: int,
              bits: int, *, depthwise: bool = False, mode: str = "auto",
              vmem_budget: int | None = None) -> str:
    """Where a conv layer's GEMM runs: a :func:`kernel_path` name, or
    "xla_depthwise" (depthwise layers, on every backend) or "xla_vmem"
    (the in-kernel im2col working set is over the VMEM budget, so XLA's
    int8 convolution runs instead of the kernel)."""
    if depthwise:
        return "xla_depthwise"
    path = kernel_path(mode)
    if path == "ref":
        return path
    budget = FUSED_CONV_VMEM_BUDGET if vmem_budget is None else vmem_budget
    k = kernel * kernel * c_in
    if fused_conv_vmem_bytes(in_hw, c_in, kernel, pad, out_hw * out_hw, k,
                             bits) > budget:
        return "xla_vmem"
    return path


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    rem = x.shape[axis] % mult
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, mult - rem)
    return jnp.pad(x, pads)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, kv_offset: int = 0,
              block: tuple[int, int] = (128, 128),
              mode: str = "auto") -> jax.Array:
    """Flash attention with GQA broadcast.

    q: [B, Hq, Sq, D]; k, v: [B, Hkv, Skv, D] with Hq % Hkv == 0.
    """
    hq, hkv = q.shape[1], k.shape[1]
    if hq != hkv:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if _use_ref(mode):
        return ref.flash_attention_ref(q, k, v, causal=causal,
                                       kv_offset=kv_offset)
    bq, bkv = block
    sq, skv = q.shape[2], k.shape[2]
    bq = min(bq, sq) if sq % bq else bq
    qp = _pad_to(q, 2, bq)
    kp = _pad_to(k, 2, bkv)
    vp = _pad_to(v, 2, bkv)
    out = _flash_kernel(qp, kp, vp, causal=causal, kv_offset=kv_offset,
                        bq=bq, bkv=bkv, interpret=not _on_tpu())
    return out[:, :, :sq]


def _norm_side(w_q: jax.Array | None, w_scale: jax.Array | None
               ) -> tuple[jax.Array | None, jax.Array | None]:
    """An absent split side may arrive as None or as a 0-column array."""
    if w_q is None or w_q.shape[-1] == 0:
        return None, None
    return w_q, w_scale


def _split_operands(w_lut: jax.Array | None, s_lut: jax.Array | None,
                    bits: int, w_dsp: jax.Array | None,
                    s_dsp: jax.Array | None, k: int, bn: int, bk: int = 1
                    ) -> tuple:
    """The fused kernels' weight operands from the split's codes:
    ``(planes, packed, scales, n_lut_pad, n_dsp_pad)``. The LUT codes
    become bit-planes padded to ``bk`` rows and ``bn`` columns, the DSP
    codes int4 bytes packed per ``bn`` block (rows padded to ``bk``),
    an empty region one dummy block that the kernel never reads; each
    region's scales are padded to ``bn`` and concatenated.
    ``n_*_pad // bn`` is each region's block count."""
    k_pad = -(-k // bk) * bk
    if w_lut is None:      # dummy never-consumed block keeps specs in-bounds
        planes = jnp.zeros((max(bits, 1), k_pad, bn), jnp.int8)
        n_lut_pad, s_l = 0, None
    else:
        planes = _pad_to(_pad_to(ref.bitplane_decompose(w_lut, bits), 1, bk),
                         2, bn)
        n_lut_pad = planes.shape[2]
        s_l = _pad_to(s_lut, 0, bn)
    if w_dsp is None:
        packed = jnp.zeros((k_pad, bn // 2), jnp.int8)
        n_dsp_pad, s_d = 0, None
    else:
        packed = _pad_to(ref.pack_int4(_pad_to(w_dsp, 1, bn), block=bn),
                         0, bk)
        n_dsp_pad = packed.shape[1] * 2
        s_d = _pad_to(s_dsp, 0, bn)
    sp = jnp.concatenate([s for s in (s_l, s_d) if s is not None])
    return planes, packed, sp, n_lut_pad, n_dsp_pad


def _unpad_columns(out: jax.Array, m: int, n_lut_pad: int,
                   w_lut: jax.Array | None, w_dsp: jax.Array | None
                   ) -> jax.Array:
    """The kernels' first ``m`` rows in split column order, without the
    column padding that lands between the regions."""
    n_lut = 0 if w_lut is None else w_lut.shape[1]
    n_dsp = 0 if w_dsp is None else w_dsp.shape[1]
    if n_lut_pad == n_lut:
        return out[:m, :n_lut + n_dsp]
    return jnp.concatenate(
        [out[:m, :n_lut], out[:m, n_lut_pad:n_lut_pad + n_dsp]], axis=1)


def fused_matmul(x_q: jax.Array, w_lut: jax.Array | None,
                 s_lut: jax.Array | None, bits: int,
                 w_dsp: jax.Array | None, s_dsp: jax.Array | None, *,
                 block: tuple[int, int, int] = (128, 128, 128),
                 mode: str = "auto") -> jax.Array:
    """Fused split GEMM — both sides of the Eq.-12 split in ONE launch.

    x_q: [M, K] int8; w_lut: [K, n_lut] codes within ``bits`` bits (the
    LUT partition; None or 0 columns when absent); w_dsp: [K, n_dsp]
    int32 codes in [-8, 7]; s_*: per-column fp32 scales. Returns fp32
    [M, n_lut + n_dsp] in split column order, bit-identical to
    ``ref.fused_hetero_gemm_ref``. A one-sided split is the same launch
    with no block in its empty region.
    """
    w_lut, s_lut = _norm_side(w_lut, s_lut)
    w_dsp, s_dsp = _norm_side(w_dsp, s_dsp)
    if w_lut is None and w_dsp is None:
        raise ValueError("fused_matmul: both split sides are empty")
    if _use_ref(mode):
        return ref.fused_hetero_gemm_ref(x_q, w_lut, s_lut, bits,
                                         w_dsp, s_dsp)
    bm, bk, bn = block
    m, k = x_q.shape
    planes, packed, sp, n_lut_pad, n_dsp_pad = _split_operands(
        w_lut, s_lut, bits, w_dsp, s_dsp, k, bn, bk)
    xp = _pad_to(_pad_to(x_q, 0, bm), 1, bk)
    out = _fused_kernel(xp, planes, packed, sp, bits, n_lut_pad // bn,
                        n_dsp_pad // bn, bm=bm, bn=bn, bk=bk,
                        interpret=not _on_tpu())
    return _unpad_columns(out, m, n_lut_pad, w_lut, w_dsp)


def _conv_xla(x_sp: jax.Array, kernel: int, stride: int, pad: int,
              out_hw: int, w_lut: jax.Array | None,
              s_lut: jax.Array | None, bits: int,
              w_dsp: jax.Array | None, s_dsp: jax.Array | None
              ) -> jax.Array:
    """The split conv as one XLA int8 convolution from the spatial
    block (NHWC x HWIO -> int32), with no patch tensor: its output
    features are the LUT side's ``bits`` binary planes, plane-major,
    then the DSP side's int4 codes. The planes' sums are weighted in
    int32 as :func:`ref.fused_hetero_gemm_ref` does, so the result is
    bit-identical to the oracle on the staged patches."""
    c = x_sp.shape[2]
    k = kernel * kernel * c
    feats, scales = [], []
    n_lut = 0
    if w_lut is not None:
        n_lut = w_lut.shape[1]
        planes = ref.bitplane_decompose(w_lut, bits)     # [bits, k, n_lut]
        feats.append(planes.transpose(1, 0, 2).reshape(k, bits * n_lut))
        scales.append(s_lut)
    if w_dsp is not None:
        feats.append(jnp.asarray(w_dsp, jnp.int8))
        scales.append(s_dsp)
    w = jnp.concatenate(feats, axis=1).reshape(kernel, kernel, c, -1)
    acc = jax.lax.conv_general_dilated(
        x_sp[None], w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.int32)
    acc = acc[0, :out_hw, :out_hw].reshape(out_hw * out_hw, -1)
    if n_lut:
        s = ref.plane_scales(bits)
        lut = sum(s[b] * acc[:, b * n_lut:(b + 1) * n_lut]
                  for b in range(bits))
        acc = jnp.concatenate([lut, acc[:, bits * n_lut:]], axis=1)
    sc = scales[0] if len(scales) == 1 else jnp.concatenate(scales)
    return acc.astype(jnp.float32) * sc[None, :]


def fused_conv_matmul(x_sp: jax.Array, kernel: int, stride: int, pad: int,
                      out_hw: int, w_lut: jax.Array | None,
                      s_lut: jax.Array | None, bits: int,
                      w_dsp: jax.Array | None, s_dsp: jax.Array | None, *,
                      block: tuple[int, int, int] = (128, 128, 128),
                      mode: str = "auto",
                      vmem_budget: int | None = None,
                      name: str = "fused_conv_gemm") -> jax.Array:
    """Fused im2col-free conv GEMM: one launch from the raw spatial
    activation block — patches are generated inside the kernel, so no
    column matrix is staged in DDR or materialized on host.

    x_sp: [H, W, C] int8 spatial activations (*unpadded*; zero padding
    happens here); weights/scales as :func:`fused_matmul` with K =
    ``kernel**2 * C`` rows in (kh, kw, c) order. Where
    :func:`conv_path` says "ref", or "xla_vmem" for a 1x1 window, the
    oracle stages patches (``ref.conv_patches_ref``); where it says
    "xla_vmem" for a wider window XLA's int8 convolution contracts the
    spatial block (:func:`_conv_xla`); elsewhere ``name`` names the
    kernel's launch (``fused_hetero_gemm.fused_conv_gemm``). All give
    the same bits.
    """
    w_lut, s_lut = _norm_side(w_lut, s_lut)
    w_dsp, s_dsp = _norm_side(w_dsp, s_dsp)
    if w_lut is None and w_dsp is None:
        raise ValueError("fused_conv_matmul: both split sides are empty")
    m = out_hw * out_hw
    k = kernel * kernel * x_sp.shape[2]
    path = conv_path(x_sp.shape[0], x_sp.shape[2], kernel, pad, out_hw,
                     bits, mode=mode, vmem_budget=vmem_budget)
    if path == "xla_vmem" and kernel > 1:
        return _conv_xla(x_sp, kernel, stride, pad, out_hw, w_lut, s_lut,
                         bits, w_dsp, s_dsp)
    if path in ("ref", "xla_vmem"):
        # a 1x1 window's patches are the block itself; on the v5e XLA's
        # convolution of it added relayout copies that its dot does not
        x_col = ref.conv_patches_ref(x_sp, kernel, stride, pad, out_hw)
        return ref.fused_hetero_gemm_ref(x_col.reshape(m, k), w_lut, s_lut,
                                         bits, w_dsp, s_dsp)
    _, _, bn = block
    xp = jnp.pad(x_sp, ((pad, pad), (pad, pad), (0, 0)))
    planes, packed, sp, n_lut_pad, n_dsp_pad = _split_operands(
        w_lut, s_lut, bits, w_dsp, s_dsp, k, bn)
    out = _fused_conv_kernel(xp, planes, packed, sp, bits,
                             n_lut_pad // bn, n_dsp_pad // bn, kernel,
                             stride, out_hw, bn=bn,
                             interpret=not _on_tpu(), name=name)
    return _unpad_columns(out, m, n_lut_pad, w_lut, w_dsp)


def fused_grouped_matmul(x_col: jax.Array, w_lut: jax.Array | None,
                         s_lut: jax.Array | None, bits: int,
                         w_dsp: jax.Array | None, s_dsp: jax.Array | None,
                         *, mode: str = "auto") -> jax.Array:
    """Fused depthwise split GEMM over per-channel im2col slices.

    x_col: [M, K, N] over *all* N output channels in split order; the
    first n_lut channels contract bit-serially, the rest as int4. The
    vectorized jnp contraction is the kernel on every backend (K = kh*kw
    taps is far below the MXU tile).
    """
    del mode
    w_lut, s_lut = _norm_side(w_lut, s_lut)
    w_dsp, s_dsp = _norm_side(w_dsp, s_dsp)
    return ref.fused_hetero_grouped_gemm_ref(x_col, w_lut, s_lut, bits,
                                             w_dsp, s_dsp)


def fused_depthwise_matmul(x_sp: jax.Array, kernel: int, stride: int,
                           pad: int, out_hw: int, w_lut: jax.Array | None,
                           s_lut: jax.Array | None, bits: int,
                           w_dsp: jax.Array | None,
                           s_dsp: jax.Array | None, *,
                           mode: str = "auto") -> jax.Array:
    """Fused depthwise conv from the raw spatial block: in-jit patch
    generation (no staged column matrix) feeding the fused grouped
    contraction."""
    x_col = ref.conv_patches_ref(x_sp, kernel, stride, pad, out_hw)
    return fused_grouped_matmul(x_col, w_lut, s_lut, bits, w_dsp, s_dsp,
                                mode=mode)
