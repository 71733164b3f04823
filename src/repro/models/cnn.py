"""ResNet-18 / MobileNet-V2 / ResNet-50 in JAX — the paper's evaluation
workloads and the bottleneck ResNet of FPGA accelerator comparisons.

Every parametric layer maps 1:1 onto a ``ConvSpec`` in
``repro.core.workloads`` (same names, same order), so the DSE framework
can attach per-layer quantization configs and the FPGA latency model
sees exactly the GEMM the network executes (im2col equivalence).

Quantization-aware forward: with ``quant_cfgs`` given (one
``LayerQuantConfig`` per spec), each conv's filters are fake-quantized
with the paper's hybrid filter-wise scheme (§4: DSP-core filters int4,
LUT-core filters 2–8 bit, KL-based allocation) and activations are
quantized layer-wise — first/last layers at 8 bits, as in the paper.

Normalization is a folded (inference-style) per-channel scale+bias —
trainable, which keeps QAT runs on synthetic data simple and matches
what the accelerator would execute (BN folds into the requantization).

``width``/``in_hw``/``reduced`` knobs build small same-family variants
for CPU smoke tests; ``specs_for`` returns the matching ConvSpec list.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core.workloads import WORKLOADS, ConvSpec
from repro.quant.hybrid import LayerQuantConfig, hybrid_fake_quant_weight
from repro.quant.uniform import fake_quant_per_channel, fit_scale, qrange


@dataclasses.dataclass(frozen=True)
class CNNConfig:
    arch: str = "resnet18"              # a core.workloads.WORKLOADS key
    n_classes: int = 1000
    in_hw: int = 224
    width: float = 1.0                  # channel multiplier (reduced smoke)
    param_dtype = jnp.float32


def reduced_config(arch: str, n_classes: int = 10) -> CNNConfig:
    return CNNConfig(arch=arch, n_classes=n_classes, in_hw=32, width=0.25)


def _scale_c(c: int, width: float) -> int:
    if width >= 1.0:
        return c
    return max(8, int(round(c * width / 8)) * 8) if c > 8 else c


def specs_for(cfg: CNNConfig) -> list[ConvSpec]:
    """ConvSpec list matching this config (width/input-size scaled).

    Spatial sizes are *propagated* through the layer graph — each
    layer's ``in_hw`` is its producer's (pooled) ``out_hw``, the
    producer ``in_src`` layers back (the downsample shortcuts read the
    block input) — so the scaled specs chain exactly like the
    full-size network and the compiled program's im2col geometry stays
    executable at any input size. An arch outside
    ``core.workloads.WORKLOADS`` is refused.
    """
    if cfg.arch not in WORKLOADS:
        raise ValueError(f"unknown CNN arch {cfg.arch!r}; known: "
                         f"{', '.join(sorted(WORKLOADS))}")
    base = WORKLOADS[cfg.arch]()
    if cfg.width >= 1.0 and cfg.in_hw == 224 and cfg.n_classes == 1000:
        return base
    out: list[ConvSpec] = []
    for i, s in enumerate(base):
        c_in = 3 if s.is_first else _scale_c(s.c_in, cfg.width)
        c_out = (cfg.n_classes if s.is_last
                 else _scale_c(s.c_out, cfg.width))
        if s.depthwise:
            c_in = c_out = _scale_c(s.c_out, cfg.width)
        if s.is_first:
            in_hw = cfg.in_hw
        else:
            src = out[i - s.in_src]
            in_hw = src.pooled_out_hw
        out.append(dataclasses.replace(s, c_in=c_in, c_out=c_out,
                                       in_hw=in_hw))
    return out


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def init(cfg: CNNConfig, rng: jax.Array) -> dict:
    """Params keyed by ConvSpec name: {w, scale, bias}."""
    specs = specs_for(cfg)
    params = {}
    keys = jax.random.split(rng, len(specs))
    for s, k in zip(specs, keys):
        if s.depthwise:
            shape = (s.kernel, s.kernel, 1, s.c_out)
            fan = s.kernel * s.kernel
        else:
            shape = (s.kernel, s.kernel, s.c_in, s.c_out)
            fan = s.kernel * s.kernel * s.c_in
        std = math.sqrt(2.0 / fan)
        params[s.name] = {
            "w": std * jax.random.normal(k, shape, jnp.float32),
            "scale": jnp.ones((s.c_out,), jnp.float32),
            "bias": jnp.zeros((s.c_out,), jnp.float32),
        }
    return params


# ---------------------------------------------------------------------------
# Quantized conv primitive
# ---------------------------------------------------------------------------


def _quant_activations(x: jax.Array, bits: int) -> jax.Array:
    s = fit_scale(jax.lax.stop_gradient(x), bits)
    lo, hi = qrange(bits)
    xq = jnp.clip(jnp.round(x / s), lo, hi) * s
    return x + jax.lax.stop_gradient(xq - x)            # STE


def conv2d(x: jax.Array, w: jax.Array, s: ConvSpec) -> jax.Array:
    """The network's raw conv primitive: NHWC x HWIO, ``kernel // 2``
    padding, grouped for depthwise. Also the reference numerics the
    compiler executors' im2col staging is validated against
    (``tests/test_conv_exec.py``)."""
    pad = s.kernel // 2
    dn = jax.lax.conv_dimension_numbers(x.shape, w.shape,
                                        ("NHWC", "HWIO", "NHWC"))
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(s.stride, s.stride),
        padding=[(pad, pad), (pad, pad)], dimension_numbers=dn,
        feature_group_count=s.c_out if s.depthwise else 1)


def conv_layer(p: dict, x: jax.Array, s: ConvSpec,
               q: LayerQuantConfig | None, relu: bool = True,
               norm: jax.Array | None = None,
               capture: dict | None = None) -> jax.Array:
    """NHWC conv + folded norm + optional relu, with hybrid quant.

    ``norm`` freezes the layer's RMS statistic to a precomputed value
    (inference mode — the batch statistic is data-dependent, so two
    different batches normalize differently; frozen norms are what the
    accelerator folds into its weights). ``capture`` records the
    statistic actually used under ``s.name`` (see
    :func:`calibrate_norms`).
    """
    w = p["w"]
    if q is not None:
        a_bits = 8 if (s.is_first or s.is_last) else q.a_bits
        x = _quant_activations(x, a_bits)
        if s.is_first or s.is_last:
            w = fake_quant_per_channel(w, 8, axis=3)
        else:
            # filters live on the last axis -> move to front for the
            # filter-wise hybrid scheme, then restore.
            w_f = jnp.moveaxis(w, 3, 0)
            w_f = hybrid_fake_quant_weight(w_f, q)
            w = jnp.moveaxis(w_f, 0, 3)
    out = conv2d(x, w, s)
    # BN-style per-channel RMS normalization (mean-free): stabilizes
    # from-scratch QAT; folds into the requantization scale at inference
    # exactly like BN does on the accelerator.
    if norm is None:
        rms = jnp.sqrt(jnp.mean(jnp.square(out), axis=(0, 1, 2),
                                keepdims=True) + 1e-6)
    else:
        rms = jnp.asarray(norm, jnp.float32).reshape(1, 1, 1, -1)
    if capture is not None:
        capture[s.name] = rms.reshape(-1)
    out = (out / rms) * p["scale"] + p["bias"]
    if relu:
        out = jax.nn.relu6(out) if s.depthwise else jax.nn.relu(out)
    return out


def _qc(quant_cfgs, i):
    return None if quant_cfgs is None else quant_cfgs[i]


# ---------------------------------------------------------------------------
# ResNet-18 forward
# ---------------------------------------------------------------------------


def resnet18_forward(params: dict, x: jax.Array, cfg: CNNConfig,
                     quant_cfgs: Sequence[LayerQuantConfig] | None = None,
                     norms: dict | None = None,
                     capture: dict | None = None) -> jax.Array:
    specs = {s.name: s for s in specs_for(cfg)}
    qi = {s.name: i for i, s in enumerate(specs_for(cfg))}

    def conv(name, x, relu=True):
        return conv_layer(params[name], x, specs[name],
                          _qc(quant_cfgs, qi[name]), relu,
                          norm=None if norms is None else norms[name],
                          capture=capture)

    x = conv("conv1", x)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")

    def basic_block(x, a, b, ds=None):
        h = conv(a, x)
        h = conv(b, h, relu=False)
        sc = x if ds is None else conv(ds, x, relu=False)
        return jax.nn.relu(h + sc)

    x = basic_block(x, "conv2", "conv3")
    x = basic_block(x, "conv4", "conv5")
    x = basic_block(x, "conv6", "conv7", "conv8_ds")
    x = basic_block(x, "conv9", "conv10")
    x = basic_block(x, "conv11", "conv12", "conv13_ds")
    x = basic_block(x, "conv14", "conv15")
    x = basic_block(x, "conv16", "conv17", "conv18_ds")
    x = basic_block(x, "conv19", "conv20")

    x = jnp.mean(x, axis=(1, 2), keepdims=True)          # GAP -> [B,1,1,C]
    x = conv("fc", x, relu=False)
    return x[:, 0, 0, :]


# ---------------------------------------------------------------------------
# MobileNet-V2 forward
# ---------------------------------------------------------------------------


def mobilenet_v2_forward(params: dict, x: jax.Array, cfg: CNNConfig,
                         quant_cfgs: Sequence[LayerQuantConfig] | None = None,
                         norms: dict | None = None,
                         capture: dict | None = None) -> jax.Array:
    all_specs = specs_for(cfg)
    specs = {s.name: s for s in all_specs}
    qi = {s.name: i for i, s in enumerate(all_specs)}

    def conv(name, x, relu=True):
        return conv_layer(params[name], x, specs[name],
                          _qc(quant_cfgs, qi[name]), relu,
                          norm=None if norms is None else norms[name],
                          capture=capture)

    x = conv("conv0", x)
    x = conv("b0_dw", x)
    x = conv("b0_pw", x, relu=False)

    bi = 1
    while f"b{bi}_exp" in specs:
        inp = x
        h = conv(f"b{bi}_exp", x)
        h = conv(f"b{bi}_dw", h)
        h = conv(f"b{bi}_pw", h, relu=False)
        if h.shape == inp.shape:
            h = h + inp                                   # inverted residual
        x = h
        bi += 1

    x = conv("conv_last", x)
    x = jnp.mean(x, axis=(1, 2), keepdims=True)
    x = conv("fc", x, relu=False)
    return x[:, 0, 0, :]


# ---------------------------------------------------------------------------
# ResNet-50 v1.5 forward
# ---------------------------------------------------------------------------


def resnet50_forward(params: dict, x: jax.Array, cfg: CNNConfig,
                     quant_cfgs: Sequence[LayerQuantConfig] | None = None,
                     norms: dict | None = None,
                     capture: dict | None = None) -> jax.Array:
    """Bottleneck blocks in the specs' layer order: conv_a, conv_b,
    conv_c, then the projection (``shortcut``) where the block changes
    shape; relu(conv_c(x) + shortcut(x))."""
    all_specs = specs_for(cfg)
    specs = {s.name: s for s in all_specs}
    qi = {s.name: i for i, s in enumerate(all_specs)}

    def conv(name, x, relu=True):
        return conv_layer(params[name], x, specs[name],
                          _qc(quant_cfgs, qi[name]), relu,
                          norm=None if norms is None else norms[name],
                          capture=capture)

    x = conv(all_specs[0].name, x)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    i = 1
    while not all_specs[i].is_last:
        a, b, c = (s.name for s in all_specs[i:i + 3])
        ds = all_specs[i + 3] if all_specs[i + 3].shortcut else None
        h = conv(b, conv(a, x))
        h = conv(c, h, relu=False)
        sc = x if ds is None else conv(ds.name, x, relu=False)
        x = jax.nn.relu(h + sc)
        i += 3 if ds is None else 4

    x = jnp.mean(x, axis=(1, 2), keepdims=True)
    x = conv(all_specs[i].name, x, relu=False)
    return x[:, 0, 0, :]


def forward(params: dict, x: jax.Array, cfg: CNNConfig,
            quant_cfgs: Sequence[LayerQuantConfig] | None = None,
            norms: dict | None = None,
            capture: dict | None = None) -> jax.Array:
    if cfg.arch == "resnet18":
        return resnet18_forward(params, x, cfg, quant_cfgs, norms, capture)
    if cfg.arch == "mobilenet_v2":
        return mobilenet_v2_forward(params, x, cfg, quant_cfgs, norms,
                                    capture)
    if cfg.arch == "resnet50":
        return resnet50_forward(params, x, cfg, quant_cfgs, norms, capture)
    raise ValueError(f"unknown CNN arch {cfg.arch!r}")


# ---------------------------------------------------------------------------
# Inference-mode norm freezing + weight folding
# ---------------------------------------------------------------------------


def calibrate_norms(params: dict, x: jax.Array, cfg: CNNConfig) -> dict:
    """Freeze every layer's data-dependent RMS statistic on one
    calibration batch: ``{name: rms[c_out]}``.

    The batch statistic makes the forward a function of the *batch*,
    not the sample — two batches normalize differently, so dataset
    evaluation (and the accelerator, whose programs have no norm op)
    needs the statistic pinned. Evaluate with
    ``forward(..., norms=calibrate_norms(...))``.
    """
    capture: dict = {}
    forward(params, x, cfg, capture=capture)
    return capture


def fold_inference_weights(params: dict, cfg: CNNConfig,
                           norms: dict) -> dict:
    """Fold the frozen per-channel norm into effective conv weights:
    ``w_eff[..., c] = w[..., c] * scale[c] / rms[c]`` — exactly the
    BN-fold the accelerator deploys, so a compiled program binding
    quantized ``w_eff`` reproduces the frozen-norm network with no
    norm op in the instruction stream.

    Requires ``bias == 0`` everywhere (the compiled GEMM+elementwise
    pipeline has no bias stage to fold a nonzero bias into).
    """
    folded = {}
    for s in specs_for(cfg):
        p = params[s.name]
        if float(jnp.max(jnp.abs(p["bias"]))) != 0.0:
            raise ValueError(
                f"layer {s.name} has a nonzero norm bias; the compiled "
                f"pipeline has no bias stage to fold it into")
        gain = (p["scale"] / jnp.asarray(norms[s.name], jnp.float32)
                ).reshape(1, 1, 1, -1)
        folded[s.name] = p["w"] * gain
    return folded


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
