"""Plain reference of a w4/a4 quantized CNN, in straightforward jax.numpy.

It imports nothing of the system under test. A network is a list of
:class:`Layer` built by a configuration's ``configs/<name>.py`` from the
sizes in ``configs/<name>.json``. One layer is a convolution (dense or
depthwise) over integer activation codes with integer weight codes,
followed by its elementwise tail:

    y = conv(x_codes, w_codes) * col_scale * x_scale     (absolute fp32)
    y = y + dequant(codes of layer ``add``)                (residual)
    y = act(y)                                             (relu / relu6)
    y = pool(y)                                            (max 3x3 s2 SAME, or global mean)
    codes, scale = requant(y, out_bits)                    (per-tensor max-abs)

The last layer is not requantized: its fp32 output is the logits. The
image enters as ``bits_a``-bit codes with scale 1.0.

The convolution contracts integer codes held in ``dtype``. In float32
at ``Precision.HIGHEST`` that is exact: every product of two 4-bit codes
and every partial sum of a layer here stays below 2**24. ``dtype``
bfloat16 is the control: the same arithmetic one precision lower.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Layer:
    name: str
    c_in: int
    c_out: int
    kernel: int
    stride: int
    in_hw: int
    depthwise: bool = False
    src: int = -1          # index of the layer whose codes are the input; -1: the image
    add: int | None = None  # index of the layer whose codes are added back
    act: str = ""          # "", "relu", "relu6"
    pool: str = ""         # "", "max", "gap"
    out_bits: int | None = None  # requant width; None for the logits

    @property
    def out_hw(self) -> int:
        pad = self.kernel // 2
        return (self.in_hw + 2 * pad - self.kernel) // self.stride + 1

    @property
    def pooled_hw(self) -> int:
        if self.pool == "max":
            return (self.out_hw + 1) // 2
        if self.pool == "gap":
            return 1
        return self.out_hw

    @property
    def weight_shape(self) -> tuple[int, int]:
        """[k, n] weight codes, rows in (kh, kw, c_in) order."""
        k = self.kernel * self.kernel * (1 if self.depthwise else self.c_in)
        return k, self.c_out


def qrange(bits: int) -> tuple[int, int]:
    return -(2 ** (bits - 1)), 2 ** (bits - 1) - 1


def requant(y, bits: int):
    """Per-tensor symmetric max-abs requantization: (codes, scale)."""
    lo, hi = qrange(bits)
    scale = jnp.maximum(jnp.max(jnp.abs(y)), 1e-8) * jnp.asarray(1.0 / hi, y.dtype)
    codes = jnp.clip(jnp.round(y / scale), lo, hi)
    return codes, scale


def conv(x, w, layer: Layer, dtype):
    """NHWC x HWIO convolution of codes held in ``dtype`` (batch 1,
    ``kernel // 2`` zero padding, grouped per channel if depthwise)."""
    kk = layer.kernel
    if layer.depthwise:
        w = w.reshape(kk, kk, 1, layer.c_out)
    else:
        w = w.reshape(kk, kk, layer.c_in, layer.c_out)
    pad = kk // 2
    out = jax.lax.conv_general_dilated(
        x[None].astype(dtype), w.astype(dtype),
        window_strides=(layer.stride, layer.stride),
        padding=[(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=layer.c_out if layer.depthwise else 1,
        precision=jax.lax.Precision.HIGHEST)
    return out[0]


def tail(y, layer: Layer, residual):
    if residual is not None:
        y = y + residual
    if layer.act == "relu":
        y = jnp.maximum(y, 0)
    elif layer.act == "relu6":
        y = jnp.clip(y, 0, 6)
    elif layer.act:
        raise ValueError(f"unknown activation {layer.act!r}")
    if layer.pool == "max":
        y = jax.lax.reduce_window(y, jnp.asarray(-jnp.inf, y.dtype), jax.lax.max,
                                  (3, 3, 1), (2, 2, 1), "SAME")
    elif layer.pool == "gap":
        y = jnp.mean(y, axis=(0, 1), keepdims=True)
    elif layer.pool:
        raise ValueError(f"unknown pool {layer.pool!r}")
    return y


@functools.partial(jax.jit, static_argnums=(3, 4))
def _gemm(x, w, col_scale, layer: Layer, dtype):
    return conv(x, w, layer, dtype) * col_scale.astype(dtype)


@functools.partial(jax.jit, static_argnums=(2,))
def _tail(y, residual, layer: Layer):
    y = tail(y, layer, residual)
    if layer.out_bits:
        return (y, *requant(y, layer.out_bits))
    return y, None, None


_scale = jax.jit(jnp.multiply)


def forward(layers, weights, scales, image, dtype=jnp.float32):
    """Logits [1, n_classes] of one image.

    ``weights[i]`` is layer i's [k, n] integer weight codes,
    ``scales[i]`` its [n] per-column scales, ``image`` the
    [in_hw, in_hw, c_in] input codes (scale 1.0).

    Each step of a layer rounds to ``dtype`` before the next, in the
    order the configuration states: the convolution scaled per column,
    then by the input's scale; the residual's codes times their scale;
    then the tail. The network amplifies a single flipped code, so
    steps fused into one expression (a multiply and an add contracted
    into one rounding) would read as a different answer.
    """
    stored = []  # per layer: (codes, scale) of its written-back output
    y = None
    for i, layer in enumerate(layers):
        if layer.src < 0:
            x, s_in = image, jnp.asarray(1.0, dtype)
        else:
            x, s_in = stored[layer.src]
        y = _scale(_gemm(x, weights[i], scales[i], layer, dtype), s_in)
        residual = None
        if layer.add is not None:
            residual = _scale(*stored[layer.add])
        y, codes, scale = _tail(y, residual, layer)
        stored.append((None if codes is None else codes.astype(dtype), scale))
    return y.reshape(1, layers[-1].c_out).astype(jnp.float32)
