"""Bitplane GEMM Pallas kernel — the TPU adaptation of the LUT-core.

The paper's LUT-core executes an ``a``-bit x ``w``-bit GEMM as a weighted
sum of binary GEMMs (Eq. 1), one XNOR-popcount pass per plane pair, so
latency scales with the operand bit-width. A literal bit-serial port
would waste the MXU (a 128x128 systolic array with native int8 support),
so we *keep the decomposition but parallelize each plane*: every binary
weight plane is an int8 MXU matmul; shifted partial sums accumulate in
an int32 VMEM scratch accumulator. Compute cost remains proportional to
the number of planes — exactly the cost-model structure the paper's DSE
relies on (L_LUT ∝ B_w) — while each plane runs at full MXU rate.

Tiling: grid (nm, nn, nk), K innermost ("arbitrary" dimension semantics:
the accumulator carries across the K sweep). Block shapes are the VMEM
working set: x-block [bm, bk] int8, one weight block per plane
[bits, bk, bn] int8, accumulator [bm, bn] int32 — choose bm/bn/bk as
multiples of the 128-lane MXU dims (the defaults are).
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


def _bitserial_kernel(x_ref, planes_ref, scale_ref, out_ref, acc_ref, *,
                      bits: int, nk: int):
    """One (m, n, k) grid step: acc += sum_b s_b * (x_blk @ plane_b)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                   # [bm, bk] int8
    # Python-int plane weights (jnp constants cannot be captured in-kernel).
    s = [2 ** b for b in range(bits - 1)] + [-(2 ** (bits - 1))]
    acc = acc_ref[...]
    for b in range(bits):                            # static unroll: planes
        part = jax.lax.dot(x, planes_ref[b],
                           preferred_element_type=jnp.int32)
        acc = acc + s[b] * part
    acc_ref[...] = acc

    @pl.when(k == nk - 1)
    def _done():
        out_ref[...] = acc_ref[...].astype(jnp.float32) * scale_ref[...]


@functools.partial(jax.jit, static_argnames=("bits", "bm", "bn", "bk",
                                             "interpret"))
def bitserial_gemm(x: jax.Array, planes: jax.Array, w_scale: jax.Array,
                   bits: int, *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                   bk: int = DEFAULT_BK, interpret: bool = False) -> jax.Array:
    """out[M, N] (fp32) = (x int8 @ reconstruct(planes)) * w_scale.

    x: [M, K] int8; planes: [bits, K, N] int8 in {0, 1}
    (``ref.bitplane_decompose`` layout); w_scale: [N] fp32, handed to
    the kernel as a [1, N] row in (1, bn) blocks.
    M, K, N must divide by the block shape (pad at the ops.py layer).
    """
    m, k = x.shape
    _, _, n = planes.shape
    if planes.shape[0] != bits:
        raise ValueError(f"planes leading dim {planes.shape[0]} != bits {bits}")
    if m % bm or n % bn or k % bk:
        raise ValueError(f"shape ({m},{k},{n}) not divisible by blocks "
                         f"({bm},{bk},{bn}); pad first")
    nm, nn, nk = m // bm, n // bn, k // bk

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    return pl.pallas_call(
        functools.partial(_bitserial_kernel, bits=bits, nk=nk),
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bits, bk, bn), lambda i, j, kk: (0, kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        **kwargs,
    )(x, planes, w_scale.reshape(1, -1))
