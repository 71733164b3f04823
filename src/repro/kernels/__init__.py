"""Pallas TPU kernels for the perf-critical compute paths.

  fused_hetero_gemm — both sides of the Eq.-12 split in ONE launch:
                     the LUT-core columns as bitplane GEMMs (latency
                     ∝ bits), the DSP-core columns as packed int4
                     (dense + im2col-free conv variants)
  flash_attention — online-softmax attention for serving hot paths

Each kernel has a pure-jnp oracle in ``ref.py`` and is validated against
it in interpret mode by the test suite. ``ops.py`` holds the public
wrappers (padding, backend dispatch, split-side normalization, the
kernels' weight layout, GQA broadcast).
"""
from repro.kernels.ops import (
    SplitOperands,
    attention,
    fused_conv_matmul,
    fused_conv_matmul_split,
    fused_depthwise_matmul,
    fused_grouped_matmul,
    fused_matmul,
    fused_matmul_split,
    split_operands,
)
from repro.kernels.ref import (
    bitplane_decompose,
    bitplane_reconstruct,
    conv_patches_ref,
    pack_int4,
    plane_scales,
    unpack_int4,
)

__all__ = [
    "SplitOperands", "attention", "fused_conv_matmul",
    "fused_conv_matmul_split", "fused_depthwise_matmul",
    "fused_grouped_matmul", "fused_matmul", "fused_matmul_split",
    "split_operands",
    "bitplane_decompose", "bitplane_reconstruct", "conv_patches_ref",
    "pack_int4", "plane_scales", "unpack_int4",
]
