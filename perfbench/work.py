"""What a layer needs, counted from its shapes: operations and bytes.

This counts the work of the layer itself, whatever implements it: a
later change that fuses a tail into a kernel, or moves columns across
the Eq.-12 split, leaves these counts as they are.

A layer is one entry of a reference graph (``qcnn.Layer``) plus the
program's split of its output columns: ``n_lut`` columns with
``bits_w_lut``-bit weight codes, the rest with 4-bit codes.
"""
from __future__ import annotations

import dataclasses

#: weight code width of the DSP (packed int4) side of the split
DSP_BITS = 4
#: bytes of one fp32 per-column dequant scale
SCALE_BYTES = 4
#: code width of a layer output that is not requantized (fp32 logits)
FP32_BITS = 32


@dataclasses.dataclass(frozen=True)
class LayerWork:
    name: str
    macs: int
    ops: int
    bytes: float


def gemm_dims(layer) -> tuple[int, int, int]:
    """(m, k, n) of the layer's im2col GEMM; a depthwise layer's k is
    the taps of one channel."""
    m = layer.out_hw * layer.out_hw
    k = layer.kernel * layer.kernel * (1 if layer.depthwise else layer.c_in)
    return m, k, layer.c_out


def layer_work(layer, n_lut: int, bits_w_lut: int, bits_a: int) -> LayerWork:
    """Operations and bytes one execution of ``layer`` needs.

    Operations are 2*m*k*n (one multiply and one add per MAC). Bytes
    are the layer's own traffic: its input feature map read once as
    ``bits_a``-bit codes, the weights at their code widths (LUT columns
    at ``bits_w_lut``, DSP columns at 4), one fp32 scale per column,
    and the output written once, after its pool, as codes at the
    layer's requant width (fp32 where the layer is not requantized).
    """
    m, k, n = gemm_dims(layer)
    macs = m * k * n
    bits_in = layer.in_hw * layer.in_hw * layer.c_in * bits_a
    bits_w = k * (n_lut * bits_w_lut + (n - n_lut) * DSP_BITS)
    stored = layer.pooled_hw * layer.pooled_hw * n
    bits_out = stored * (layer.out_bits or FP32_BITS)
    nbytes = (bits_in + bits_w + bits_out) / 8 + n * SCALE_BYTES
    return LayerWork(layer.name, macs, 2 * macs, nbytes)


def least_time_s(work: LayerWork, peaks: dict) -> float:
    """The least time the chip could take: the larger of operations
    over the int8 peak and bytes over the HBM bandwidth."""
    return max(work.ops / peaks["int8_ops_per_s"],
               work.bytes / peaks["hbm_bytes_per_s"])
