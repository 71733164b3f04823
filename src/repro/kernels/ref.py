"""Pure-jnp oracles for the Pallas kernels.

Every kernel in this package has its reference here; the per-kernel
tests sweep shapes/dtypes and ``assert_allclose`` kernel-vs-oracle
(kernels run in ``interpret=True`` mode on CPU).

Also hosts the representation helpers shared by oracle and kernel:

  * ``bitplane_decompose`` — paper Eq. (1): an ``bits``-bit signed
    integer tensor becomes ``bits`` binary planes with per-plane signed
    weights (two's complement: MSB plane weight is -2^(bits-1)).
  * ``pack_int4`` / ``unpack_int4`` — two int4 codes per int8 byte
    along the last axis, the two nibbles of a byte half a column block
    apart (the DSP-core-analogue packed layout).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Representation helpers
# ---------------------------------------------------------------------------


def plane_scales(bits: int) -> jax.Array:
    """Signed per-plane weights of a two's-complement decomposition."""
    s = [2 ** b for b in range(bits - 1)] + [-(2 ** (bits - 1))]
    return jnp.asarray(s, dtype=jnp.int32)


def bitplane_decompose(q: jax.Array, bits: int) -> jax.Array:
    """Signed integer codes -> ``[bits, ...]`` binary planes (int8 0/1).

    Reconstruction: ``q == sum_b plane_scales(bits)[b] * planes[b]``.
    """
    u = jnp.asarray(q, jnp.int32) & ((1 << bits) - 1)  # two's complement bits
    shifts = jnp.arange(bits, dtype=jnp.int32).reshape((bits,) + (1,) * q.ndim)
    return ((u[None] >> shifts) & 1).astype(jnp.int8)


def bitplane_reconstruct(planes: jax.Array) -> jax.Array:
    bits = planes.shape[0]
    s = plane_scales(bits).reshape((bits,) + (1,) * (planes.ndim - 1))
    return jnp.sum(planes.astype(jnp.int32) * s, axis=0)


def pack_int4(q: jax.Array, block: int | None = None) -> jax.Array:
    """Pack signed int4 codes two per byte along the last axis: [..., N]
    -> [..., N//2] int8.

    The last axis is cut into blocks of ``block`` columns (one block
    when None). Byte ``j`` of a block holds column ``j`` in its low
    nibble and column ``j + block//2`` in its high nibble, so a kernel
    unpacks one ``[.., block//2]`` byte block into ``[.., block]`` codes
    with a single concat of its two nibble halves.
    """
    n = q.shape[-1]
    block = n if block is None else block
    if block % 2 or n % block:
        raise ValueError(f"last axis {n} must split into even blocks of "
                         f"{block} to pack int4 pairs")
    half = block // 2
    b = jnp.asarray(q, jnp.int32).reshape(*q.shape[:-1], n // block, 2, half)
    lo = b[..., 0, :] & 0xF
    hi = b[..., 1, :] & 0xF
    return ((hi << 4) | lo).astype(jnp.int8).reshape(*q.shape[:-1], n // 2)


def unpack_int4(p: jax.Array, block: int | None = None) -> jax.Array:
    """Inverse of ``pack_int4`` (sign-extended), with the same ``block``."""
    nb = p.shape[-1]
    half = nb if block is None else block // 2
    b = jnp.asarray(p, jnp.int8).astype(jnp.int32)
    b = b.reshape(*p.shape[:-1], nb // half, half)
    lo = (b << 28) >> 28                    # arithmetic shift sign-extends
    hi = b >> 4
    out = jnp.concatenate([lo, hi], axis=-1)
    return out.reshape(*p.shape[:-1], nb * 2).astype(jnp.int8)


# ---------------------------------------------------------------------------
# Oracles
# ---------------------------------------------------------------------------


def bitserial_gemm_ref(x: jax.Array, w_q: jax.Array, w_scale: jax.Array,
                       bits: int) -> jax.Array:
    """Bitplane GEMM oracle.

    x: [M, K] int8 activations (already quantized, symmetric).
    w_q: [K, N] signed integer weight codes within ``bits`` bits.
    w_scale: [N] fp32 per-column dequantization scales.
    Returns fp32 [M, N] = (x @ w_q) * w_scale, computed through the
    bitplane decomposition so the oracle exercises the same numerics.
    """
    planes = bitplane_decompose(w_q, bits)                # [B, K, N]
    s = plane_scales(bits)
    acc = jnp.zeros((x.shape[0], w_q.shape[1]), jnp.int32)
    for b in range(bits):
        part = jax.lax.dot(x.astype(jnp.int8), planes[b],
                           preferred_element_type=jnp.int32)
        acc = acc + s[b] * part
    return acc.astype(jnp.float32) * w_scale[None, :]


def bitserial_grouped_gemm_ref(x_col: jax.Array, w_q: jax.Array,
                               w_scale: jax.Array, bits: int) -> jax.Array:
    """Grouped (depthwise) bitplane GEMM oracle.

    x_col: [M, K, N] int8 — one im2col slice per output channel (K is
    the kh*kw tap count; channel c only sees its own slice).
    w_q: [K, N] signed codes within ``bits`` bits; w_scale: [N] fp32.
    Returns fp32 [M, N] with out[m, c] = (sum_k x_col[m,k,c] *
    w_q[k,c]) * w_scale[c], accumulated exactly in int32 through the
    bitplane decomposition (same numerics as the dense oracle).
    """
    planes = bitplane_decompose(w_q, bits)                # [B, K, N]
    s = plane_scales(bits)
    acc = jnp.zeros((x_col.shape[0], w_q.shape[1]), jnp.int32)
    xc = x_col.astype(jnp.int32)
    for b in range(bits):
        part = jnp.einsum("mkc,kc->mc", xc, planes[b].astype(jnp.int32))
        acc = acc + s[b] * part
    return acc.astype(jnp.float32) * w_scale[None, :]


def int4_grouped_gemm_ref(x_col: jax.Array, w_q: jax.Array,
                          w_scale: jax.Array) -> jax.Array:
    """Grouped (depthwise) int4 GEMM oracle.

    x_col: [M, K, N] int8 per-channel im2col slices; w_q: [K, N] int32
    codes in [-8, 7]; w_scale: [N] fp32. Exact int32 accumulation.
    """
    acc = jnp.einsum("mkc,kc->mc", x_col.astype(jnp.int32),
                     jnp.asarray(w_q, jnp.int32))
    return acc.astype(jnp.float32) * w_scale[None, :]


def int4_gemm_ref(x: jax.Array, w_packed: jax.Array, w_scale: jax.Array,
                  block: int | None = None) -> jax.Array:
    """Packed-int4 GEMM oracle.

    x: [M, K] int8; w_packed: [K, N//2] int8 (``pack_int4`` layout with
    the same ``block``); w_scale: [N] fp32. Returns fp32 [M, N].
    """
    w = unpack_int4(w_packed, block)                       # [K, N] int8
    acc = jax.lax.dot(x.astype(jnp.int8), w,
                      preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * w_scale[None, :]


def conv_patches_ref(x_sp: jax.Array, kernel: int, stride: int, pad: int,
                     out_hw: int) -> jax.Array:
    """Im2col patch generation from a spatial [H, W, C] tensor:
    returns [out_hw*out_hw, kernel*kernel, C] (output positions
    row-major, taps in (kh, kw) order). Zero padding — code 0 is real
    0.0 under the symmetric quantizer.

    The single source for the patch layout: the executors' staging
    helper and the fused conv kernels' oracles both delegate here, so
    the (kh, kw, c) column order matches the HWIO weight flattening
    ``w.reshape(k, n)`` everywhere.
    """
    x = jnp.pad(x_sp, ((pad, pad), (pad, pad), (0, 0)))
    span = stride * (out_hw - 1) + 1
    taps = [x[dh:dh + span:stride, dw:dw + span:stride, :]
            for dh in range(kernel) for dw in range(kernel)]
    pat = jnp.stack(taps, axis=2)              # [oh, oh, kk*kk, C]
    return pat.reshape(out_hw * out_hw, kernel * kernel, x_sp.shape[2])


def fused_hetero_gemm_ref(x: jax.Array, w_lut: jax.Array | None,
                          s_lut: jax.Array | None, bits: int,
                          w_dsp: jax.Array | None,
                          s_dsp: jax.Array | None) -> jax.Array:
    """Fused split-GEMM oracle: one int32 accumulation pass over both
    sides of the Eq.-12 split, one per-column dequant.

    x: [M, K] int8; w_lut: [K, n_lut] codes within ``bits`` bits (or
    None); w_dsp: [K, n_dsp] int32 codes in [-8, 7] (or None); s_*:
    per-column fp32 scales. Returns fp32 [M, n_lut + n_dsp] in split
    column order — bit-identical to ``hetero_gemm_ref`` (both paths
    accumulate exactly in int32; the fp32 dequant is per output
    element, so fusing the concat cannot change a single bit).
    """
    accs, scales = [], []
    if w_lut is not None and w_lut.shape[1]:
        planes = bitplane_decompose(w_lut, bits)
        s = plane_scales(bits)
        acc = jnp.zeros((x.shape[0], w_lut.shape[1]), jnp.int32)
        for b in range(bits):
            part = jax.lax.dot(x.astype(jnp.int8), planes[b],
                               preferred_element_type=jnp.int32)
            acc = acc + s[b] * part
        accs.append(acc)
        scales.append(s_lut)
    if w_dsp is not None and w_dsp.shape[1]:
        accs.append(jax.lax.dot(x.astype(jnp.int8),
                                jnp.asarray(w_dsp, jnp.int8),
                                preferred_element_type=jnp.int32))
        scales.append(s_dsp)
    acc = accs[0] if len(accs) == 1 else jnp.concatenate(accs, axis=1)
    sc = scales[0] if len(scales) == 1 else jnp.concatenate(scales)
    return acc.astype(jnp.float32) * sc[None, :]


def fused_hetero_grouped_gemm_ref(x_col: jax.Array,
                                  w_lut: jax.Array | None,
                                  s_lut: jax.Array | None, bits: int,
                                  w_dsp: jax.Array | None,
                                  s_dsp: jax.Array | None) -> jax.Array:
    """Fused grouped (depthwise) split-GEMM oracle.

    x_col: [M, K, N] int8 per-channel im2col slices over *all* N
    channels in split order — the first n_lut channels contract
    bit-serially, the rest through the int4 path. Bit-identical to the
    two grouped oracles run per partition and concatenated.
    """
    outs = []
    n_lut = 0 if w_lut is None else w_lut.shape[1]
    if n_lut:
        outs.append(bitserial_grouped_gemm_ref(
            x_col[:, :, :n_lut], w_lut, s_lut, bits))
    if w_dsp is not None and w_dsp.shape[1]:
        outs.append(int4_grouped_gemm_ref(
            x_col[:, :, n_lut:], w_dsp, s_dsp))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True, scale: float | None = None,
                        kv_offset: int = 0) -> jax.Array:
    """Plain softmax attention oracle.

    q: [B, H, Sq, D]; k, v: [B, H, Skv, D]. ``kv_offset`` positions the
    query block inside the KV sequence (decode: Sq=1, offset=Skv-1).
    """
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        sq, skv = q.shape[2], k.shape[2]
        qpos = jnp.arange(sq)[:, None] + kv_offset
        kpos = jnp.arange(skv)[None, :]
        logits = jnp.where(kpos <= qpos, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)


def hetero_gemm_ref(x: jax.Array, w_q_serial: jax.Array, s_serial: jax.Array,
                    bits_serial: int, w_packed_parallel: jax.Array,
                    s_parallel: jax.Array) -> jax.Array:
    """The paper's heterogeneous split GEMM: first columns via the
    bitplane path, remaining via the packed-int4 path, concatenated."""
    lo = bitserial_gemm_ref(x, w_q_serial, s_serial, bits_serial)
    hi = int4_gemm_ref(x, w_packed_parallel, s_parallel)
    return jnp.concatenate([lo, hi], axis=-1)
