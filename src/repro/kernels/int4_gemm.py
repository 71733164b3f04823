"""Packed-int4 GEMM Pallas kernel — the TPU adaptation of the DSP-core.

The paper's DSP-core is a bit-parallel fixed-precision (int4 weight)
engine: latency is independent of weight bit-width because the DSP48
slices always run full-width MACs. The MXU analogue is an int8 matmul
over weights stored *packed* two-int4-per-byte in HBM (halving weight
bandwidth — the DSP-core's reason to exist was exactly this rigidity/
efficiency trade) and unpacked to int8 in VMEM right before the MXU.

Tiling mirrors ``bitserial_gemm``: grid (nm, nn, nk) with K innermost
and an int32 VMEM accumulator; the weight block is [bk, bn//2] packed
bytes, unpacked in-register to [bk, bn] (``unpack_int4_block``, shared
with the fused kernels). Per-column fp32 scales are
applied in the epilogue on the last K step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_hetero_gemm import dsp_blocks, unpack_int4_block


DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 128


def _int4_kernel(x_ref, w_ref, scale_ref, out_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = unpack_int4_block(w_ref[...])                # [bk, bn] int8
    acc_ref[...] += jax.lax.dot(x_ref[...], w,
                                preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _done():
        out_ref[...] = acc_ref[...].astype(jnp.float32) * scale_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def int4_gemm(x: jax.Array, w_packed: jax.Array, w_scale: jax.Array, *,
              bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
              bk: int = DEFAULT_BK, interpret: bool = False) -> jax.Array:
    """out[M, N] (fp32) = (x int8 @ unpack(w_packed)) * w_scale.

    x: [M, K] int8; w_packed: [K, N//2] int8 (``ref.pack_int4`` layout,
    ``block=bn``); w_scale: [N] fp32, handed to the kernel as a [1, N]
    row in (1, bn) blocks. Shapes must divide by blocks (pad in ops.py).
    """
    m, k = x.shape
    kw, n_half = w_packed.shape
    n = n_half * 2
    if kw != k:
        raise ValueError(f"K mismatch: x has {k}, w_packed has {kw}")
    if m % bm or n % bn or k % bk:
        raise ValueError(f"shape ({m},{k},{n}) not divisible by blocks "
                         f"({bm},{bk},{bn}); pad first")
    nm, nn, nk = m // bm, n // bn, k // bk

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    return pl.pallas_call(
        functools.partial(_int4_kernel, nk=nk),
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((None, bk, bn // 2), lambda i, j, kk: (j, kk, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        **kwargs,
    )(x, dsp_blocks(w_packed, bn), w_scale.reshape(1, -1))
