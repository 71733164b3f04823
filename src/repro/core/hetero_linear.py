"""HeteroLinear — the paper's heterogeneous-core split GEMM as a module.

A linear layer whose output columns (= the paper's "filters"/neurons)
are split between two execution paths (§3.1 + §5.3):

  * **parallel path** (DSP-core analogue): fixed int4 weights, packed,
    executed on the MXU int8 pipeline — latency rigid w.r.t. precision;
  * **serial path** (LUT-core analogue): flexible 2-8 bit weights,
    executed as shifted bitplane matmuls — latency ∝ bit-width.

Column→path allocation follows the paper's KL-divergence rule (filters
whose weight distribution is most damaged by quantization go to the
higher-bit-width path). The split ratio per layer either comes from the
config or is solved with the TPU cost model (`solve_tpu_split`, the
Eq. 12 analogue).

Three operating modes:
  * ``apply_fp``    — plain fp matmul (quantization off; baseline).
  * ``apply_qat``   — fake-quantized STE forward for training (the
    hybrid scheme of §4: per-column bit-widths by core assignment,
    layer-wise activation quantization).
  * ``apply_deploy``— integer inference through the Pallas kernels on
    a prepared ``DeployedHeteroLinear`` (int codes in HBM).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro import kernels
from repro.core.tpu_cost import TPUChip, V5E, solve_tpu_split
from repro.quant.hybrid import LayerQuantConfig, kl_filter_allocation
from repro.quant.uniform import (
    fake_quant_per_channel,
    fit_scale,
    fit_scale_per_channel,
    qrange,
    quantize,
)


@dataclasses.dataclass(frozen=True)
class HeteroLinearConfig:
    """Per-layer knobs (the DSE action space projected onto one layer)."""
    in_features: int
    out_features: int
    quant: LayerQuantConfig = LayerQuantConfig()
    enabled: bool = True           # False -> plain fp linear
    solve_ratio: bool = False      # override quant.ratio with Eq.-12 solve
    spatial: bool = False          # spatial (max) vs temporal (sum) compose
    chip: TPUChip = V5E

    def resolved_ratio(self, m_tokens: int = 4096) -> float:
        if not self.solve_ratio:
            return self.quant.ratio
        r, _, _ = solve_tpu_split(m_tokens, self.in_features,
                                  self.out_features, self.quant.w_bits_lut,
                                  self.quant.a_bits, self.chip,
                                  spatial=self.spatial)
        return r


def init_hetero_linear(rng: jax.Array, cfg: HeteroLinearConfig,
                       dtype=jnp.float32) -> dict:
    """fp master weights + the (static) column->path permutation."""
    k = 1.0 / (cfg.in_features ** 0.5)
    w = jax.random.uniform(rng, (cfg.in_features, cfg.out_features),
                           dtype, -k, k)
    return {"w": w}


def _split_sizes(cfg: HeteroLinearConfig) -> tuple[int, int]:
    n_serial = int(round(cfg.resolved_ratio() * cfg.out_features))
    return n_serial, cfg.out_features - n_serial


def column_allocation(w: jax.Array, cfg: HeteroLinearConfig) -> jax.Array:
    """Permutation of output columns: first n_serial slots -> serial path.

    Uses the paper's KL rule on the transposed view (filters = columns).
    """
    n_serial, _ = _split_sizes(cfg)
    qcfg = dataclasses.replace(cfg.quant, ratio=n_serial / max(cfg.out_features, 1))
    return kl_filter_allocation(w.T, qcfg)  # [out] filter indices


# ---------------------------------------------------------------------------
# fp + QAT forwards
# ---------------------------------------------------------------------------


def apply_fp(params: dict, x: jax.Array) -> jax.Array:
    return x @ params["w"]


def apply_qat(params: dict, x: jax.Array, cfg: HeteroLinearConfig) -> jax.Array:
    """STE fake-quant forward: per-column weight bits by core assignment,
    layer-wise activation quantization at ``a_bits`` (paper §4)."""
    if not cfg.enabled:
        return apply_fp(params, x)
    w = params["w"]
    perm = column_allocation(jax.lax.stop_gradient(w), cfg)
    n_serial, _ = _split_sizes(cfg)
    is_serial_slot = jnp.arange(cfg.out_features) < n_serial
    is_serial = is_serial_slot[jnp.argsort(perm)]     # original column order

    fq_serial = fake_quant_per_channel(w, cfg.quant.w_bits_lut, axis=1)
    fq_parallel = fake_quant_per_channel(w, cfg.quant.w_bits_dsp, axis=1)
    w_q = jnp.where(is_serial[None, :], fq_serial, fq_parallel)

    # layer-wise activation fake quant (shared by both paths)
    s_a = fit_scale(jax.lax.stop_gradient(x), cfg.quant.a_bits)
    lo, hi = qrange(cfg.quant.a_bits)
    x_q = jnp.clip(jnp.round(x / s_a), lo, hi) * s_a
    x_q = x + jax.lax.stop_gradient(x_q - x)          # STE
    return x_q @ w_q


# ---------------------------------------------------------------------------
# Deployment (integer path through the Pallas kernels)
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DeployedHeteroLinear:
    """Integer codes ready for the kernels; static split boundary."""
    wq_serial: jax.Array       # [in, n_serial] int32 codes
    s_serial: jax.Array        # [n_serial] fp32
    wq_parallel: jax.Array     # [in, n_parallel] int32 codes (int4 range)
    s_parallel: jax.Array      # [n_parallel] fp32
    perm: jax.Array            # [out] column allocation
    inv_perm: jax.Array        # [out]
    bits_serial: int = dataclasses.field(metadata=dict(static=True), default=4)
    a_bits: int = dataclasses.field(metadata=dict(static=True), default=4)


def deploy(params: dict, cfg: HeteroLinearConfig) -> DeployedHeteroLinear:
    """Quantize fp master weights into the two-path integer layout."""
    w = params["w"]
    perm = column_allocation(w, cfg)
    n_serial, _ = _split_sizes(cfg)
    w_sorted = w[:, perm]
    w_ser, w_par = w_sorted[:, :n_serial], w_sorted[:, n_serial:]

    s_ser = fit_scale_per_channel(w_ser, cfg.quant.w_bits_lut, axis=1)
    s_par = fit_scale_per_channel(w_par, cfg.quant.w_bits_dsp, axis=1)
    return DeployedHeteroLinear(
        wq_serial=quantize(w_ser, s_ser, cfg.quant.w_bits_lut),
        s_serial=s_ser.reshape(-1),
        wq_parallel=quantize(w_par, s_par, cfg.quant.w_bits_dsp),
        s_parallel=s_par.reshape(-1),
        perm=perm,
        inv_perm=jnp.argsort(perm),
        bits_serial=cfg.quant.w_bits_lut,
        a_bits=cfg.quant.a_bits,
    )


def apply_deploy(d: DeployedHeteroLinear, x: jax.Array,
                 mode: str = "auto") -> jax.Array:
    """Integer inference: quantize activations, run both paths, restore
    the original column order, dequantize."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    s_a = fit_scale(x2, d.a_bits)
    lo, hi = qrange(d.a_bits)
    x_q = jnp.clip(jnp.round(x2 / s_a), lo, hi).astype(jnp.int8)

    out = kernels.fused_matmul(x_q, d.wq_serial, d.s_serial, d.bits_serial,
                               d.wq_parallel, d.s_parallel, mode=mode)
    out = out[:, d.inv_perm] * s_a
    return out.reshape(*shape[:-1], out.shape[-1]).astype(x.dtype)
