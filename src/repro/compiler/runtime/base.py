"""Executor backend interface for compiled Programs.

A backend executes a :class:`~repro.compiler.program.Program`
*functionally* — integer activations in, fp32 split-order outputs out —
against real weight codes and dequant scales. Two implementations ship:

  * ``runtime/golden.py`` — the reference interpreter: walks the
    instruction streams tile by tile, enforcing the ISA/program
    contract along the way (bit-exact, slow);
  * ``runtime/pallas.py`` — the fused fast path: one
    ``kernels.fused_matmul`` / ``fused_conv_matmul`` call per *layer*
    covering both sides of the split (bit-identical outputs, orders of
    magnitude faster, Pallas kernels on TPU). It overrides
    :meth:`ExecutorBackend.run_layer`; the per-partition
    ``run_layer`` / ``_run_core`` hook here serves the golden
    interpreter.

This module holds everything backends share: weight binding and
validation, activation checks and im2col staging (conv layers accept
spatial NHWC tensors and are staged per their
:class:`~repro.compiler.program.ConvGeometry`; depthwise layers stage
one im2col slice per output channel), layer chaining with inter-layer
requantization (FC chains, and spatial NHWC conv chains that execute
each layer's in-program fused elementwise tail — residual add,
activation, pool glue, write-back requant — in absolute fp32 units),
and the error taxonomy.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.scheduler import simulate
from repro.obs import spans
from repro.obs.spans import span
from repro.quant.uniform import _inv_hi, fit_scale, qrange
from repro.compiler.program import CORE_NAMES, ConvGeometry, CoreProgram, \
    LayerProgram, Program


class ExecutionError(RuntimeError):
    """An instruction stream violated the ISA/program contract."""


# ---------------------------------------------------------------------------
# im2col activation staging (§3.2.1)
# ---------------------------------------------------------------------------


def im2col_patches(x_sp: jnp.ndarray, geom: ConvGeometry) -> jnp.ndarray:
    """Stage a spatial [in_hw, in_hw, C] tensor into im2col patches
    [m, kernel**2, C] (m = out_hw**2, output positions row-major, taps
    in (kh, kw) order). Zero padding — code 0 is real 0.0 under the
    symmetric quantizer.

    Dense convs flatten the last two axes to the [m, k] GEMM activation
    matrix with k = kernel**2 * C in (kh, kw, c) order — exactly the
    HWIO weight flattening ``w.reshape(k, n)`` contracts against.
    Depthwise layers keep the channel axis: slice c is the only input
    channel output channel c sees.

    Delegates to ``kernels.ref.conv_patches_ref`` — the single source
    for the patch layout, shared with the fused conv kernels' in-kernel
    im2col and their oracles.
    """
    from repro.kernels.ref import conv_patches_ref
    return conv_patches_ref(x_sp, geom.kernel, geom.stride, geom.pad,
                            geom.out_hw)


def spatialize(out: jnp.ndarray, geom: ConvGeometry) -> jnp.ndarray:
    """A layer's [m, n] output as the NHWC [out_hw, out_hw, c_out]
    spatial tensor the next layer's staging reads (batch 1)."""
    return jnp.asarray(out).reshape(geom.out_hw, geom.out_hw, geom.c_out)


def settle(x: jnp.ndarray) -> jnp.ndarray:
    """``x`` unchanged, rounded to fp32 before any consumer sees it.

    Compilers contract a multiply into the add that consumes it (one
    fused multiply-add, one rounding), as XLA's CPU backend does once
    the chain is traced into one executable: the residual add of two
    dequantized operands would then round differently from the eager
    chain, which rounds each product on its own. A select on the
    product's own NaN test stands between the two, so no backend can
    fuse them, and is an identity on every value (a NaN stays a NaN).
    The spatial chain settles both operands of its residual add, and
    the global average pool its input."""
    return jnp.where(jnp.isnan(x), jnp.nan, x)


def apply_pool(x_sp: jnp.ndarray, pool: str) -> jnp.ndarray:
    """Spatial pooling glue between conv layers: ``"max"`` is the
    ResNet stem's 3x3 stride-2 SAME max pool, ``"gap"`` the global
    average pool before the classifier. ``""`` is the identity.

    The output spatial extents must agree with the shape rule
    ``core.workloads.pooled_hw`` (the single source the spec scaling
    and ``ConvGeometry.pooled_hw`` both delegate to)."""
    if pool == "max":
        return jax.lax.reduce_window(x_sp, -jnp.inf, jax.lax.max,
                                     (3, 3, 1), (2, 2, 1), "SAME")
    if pool == "gap":
        # A fixed pairwise order of elementwise adds, not a reduction:
        # a compiler keeps the order of the adds it is given, but picks
        # a reduction's order from its operand's shape and layout, which
        # differ once the chain is one executable (the TPU reduced the
        # same [7, 7, 1280] map as [49, 1280] there and rounded apart).
        v = settle(x_sp.reshape(-1, x_sp.shape[-1]))
        n = v.shape[0]
        while v.shape[0] > 1:
            half = v.shape[0] // 2
            pair = v[:half] + v[half:2 * half]
            v = jnp.concatenate([pair, v[2 * half:]]) \
                if v.shape[0] % 2 else pair
        return (v * np.float32(1.0 / n)).reshape(1, 1, -1)
    return x_sp


def stage_activations(lp: LayerProgram, x_q: jnp.ndarray) -> jnp.ndarray:
    """Normalize a layer's input to the staged im2col form: [m, k] for
    dense layers, [m, k, n] per-channel slices for depthwise."""
    m, k, n = lp.dims.m, lp.dims.k, lp.dims.n
    geom = lp.geometry
    if geom is not None and x_q.shape == geom.in_shape:
        pat = im2col_patches(x_q, geom)
        return pat if lp.depthwise else pat.reshape(m, k)
    if lp.depthwise:
        if x_q.shape != (m, k, n):
            want = (f"{geom.in_shape} spatial or " if geom else "")
            raise ExecutionError(
                f"depthwise layer {lp.index} activations must be "
                f"{want}[{m},{k},{n}] staged, got {tuple(x_q.shape)}")
        return x_q
    if x_q.shape != (m, k):
        want = (f"{geom.in_shape} spatial or " if geom else "")
        raise ExecutionError(
            f"layer {lp.index} activations must be {want}"
            f"[{m},{k}], got {tuple(x_q.shape)}")
    return x_q


@dataclasses.dataclass
class LayerWeights:
    """Integer weight codes + per-column dequant scales for one layer,
    already split: LUT (bit-serial) columns first, DSP (int4) columns
    after — the same column order ``hetero_gemm_ref`` concatenates."""
    w_lut: jnp.ndarray | None      # [k, n_lut] int32 codes
    s_lut: jnp.ndarray | None      # [n_lut] fp32
    w_dsp: jnp.ndarray | None      # [k, n_dsp] int32 codes (int4 range)
    s_dsp: jnp.ndarray | None      # [n_dsp] fp32


class ExecutorBackend:
    """Functional executor over a compiled program.

    Subclasses implement :meth:`_run_core` — how one layer partition's
    tiles are actually computed. Everything else (binding, validation,
    chaining) is shared so backends are interchangeable and
    bit-comparable.
    """

    #: registry key; subclasses override ("golden", "pallas", ...)
    name = "base"

    def __init__(self, program: Program, check_timing: bool = True):
        self.program = program
        self.check_timing = check_timing
        self._layer_spans = spans.layer_spans(program.layers)
        self._weights: dict[int, LayerWeights] = {}

    # -- weight binding ----------------------------------------------------

    def bind_layer(self, index: int, w_lut=None, s_lut=None,
                   w_dsp=None, s_dsp=None) -> None:
        lp = self.program.layers[index]
        k, n_lut, n_dsp = lp.dims.k, lp.n_lut, lp.dims.n - lp.n_lut

        def _chk(w, s, n, what, bits):
            if n == 0:
                if w is not None:
                    raise ValueError(f"layer {index} has no {what} partition")
                return None, None
            w = jnp.asarray(w, jnp.int32)
            s = jnp.asarray(s, jnp.float32).reshape(-1)
            if w.shape != (k, n) or s.shape != (n,):
                raise ValueError(
                    f"layer {index} {what} weights must be [{k},{n}] "
                    f"(+[{n}] scales), got {w.shape}/{s.shape}")
            lo, hi = qrange(bits)
            if int(w.min()) < lo or int(w.max()) > hi:
                raise ValueError(f"layer {index} {what} codes exceed "
                                 f"{bits}-bit range [{lo},{hi}]")
            return w, s

        w_lut, s_lut = _chk(w_lut, s_lut, n_lut, "lut", lp.bits_w_lut)
        w_dsp, s_dsp = _chk(w_dsp, s_dsp, n_dsp, "dsp", 4)
        self._weights[index] = LayerWeights(w_lut, s_lut, w_dsp, s_dsp)

    def bind_deployed(self, index: int, deployed) -> None:
        """Bind from a ``hetero_linear.DeployedHeteroLinear`` (its column
        order is already LUT-first, matching the program split)."""
        lp = self.program.layers[index]
        self.bind_layer(
            index,
            w_lut=deployed.wq_serial if lp.n_lut else None,
            s_lut=deployed.s_serial if lp.n_lut else None,
            w_dsp=deployed.wq_parallel if lp.n_dsp else None,
            s_dsp=deployed.s_parallel if lp.n_dsp else None)

    # -- execution ---------------------------------------------------------

    def run_layer(self, index: int, x_q) -> jnp.ndarray:
        """Execute one layer on int8 activations.

        ``x_q`` is the pre-staged GEMM activation matrix [m, k] (plain
        GEMM layers and dense convs), the spatial NHWC tensor
        [in_hw, in_hw, c_in] for conv layers (staged here per the
        layer's geometry), or the pre-staged per-channel im2col stack
        [m, k, n] for depthwise layers.

        Returns fp32 [m, n] in split column order (LUT partition first),
        i.e. exactly ``kernels.ref.hetero_gemm_ref``'s layout — which
        for depthwise layers is the natural channel order (the Eq.-12
        split assigns the *first* ``n_lut`` filters to the LUT core).
        """
        lp = self.program.layers[index]
        if index not in self._weights:
            raise ExecutionError(f"layer {index} has no bound weights")
        x_q = stage_activations(lp, jnp.asarray(x_q, jnp.int8))
        wts = self._weights[index]

        def _slice(lo, hi):
            # depthwise channel c consumes im2col slice c: hand each
            # partition exactly its channels' slices
            return x_q[:, :, lo:hi] if lp.depthwise else x_q

        outs = []
        if lp.lut is not None:
            self._check_stream(lp, lp.lut)
            outs.append(self._run_core(lp, lp.lut, _slice(0, lp.n_lut),
                                       wts.w_lut, wts.s_lut))
        if lp.dsp is not None:
            self._check_stream(lp, lp.dsp)
            outs.append(self._run_core(lp, lp.dsp,
                                       _slice(lp.n_lut, lp.dims.n),
                                       wts.w_dsp, wts.s_dsp))
        return jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]

    def _check_stream(self, lp: LayerProgram, cp: CoreProgram) -> None:
        """Validate the sync-token protocol (when ``check_timing``) by
        running the event-driven scheduler over the core's streams."""
        if not self.check_timing:
            return
        try:
            simulate(cp.streams, cp.sim_tokens())
        except RuntimeError as e:
            raise ExecutionError(
                f"layer {lp.index} {CORE_NAMES[cp.core]} streams "
                f"deadlock: {e}") from e

    def run(self, x_q, x_scale: float = 1.0) -> jnp.ndarray:
        """Chain all layers end to end.

        FC-style networks (GEMMs compose: n_i == k_{i+1}) chain the
        [m, n] outputs directly; conv programs (every layer carries a
        geometry) chain spatially — each layer's fp32 result is scaled
        to absolute units, run through its fused elementwise tail
        (residual add / activation / pool glue / write-back requant,
        see ``LayerProgram.elementwise``) and the stored codes are
        staged through im2col by the consumers its ``src_offset`` /
        add ``src_offset`` name. ``x_q`` is int8: [m, k] for FC
        chains, the spatial [in_hw, in_hw, c_in] input image for conv
        chains; ``x_scale`` is the input's dequant scale (conv chains
        return absolute fp32 logits for the final layer).
        """
        with span(spans.RUN):
            return chain_layers(self.program.layers, self.run_layer, x_q,
                                x_scale=x_scale,
                                tail_factory=self._elementwise_tail,
                                layer_spans=self._layer_spans)

    def _elementwise_tail(self, lp: LayerProgram):
        """Tail callable for one conv layer — overridable: the Pallas
        backend returns a jitted, program-cached fused epilogue; the
        default runs the shared jnp tail eagerly."""
        return elementwise_tail(tuple(lp.elementwise),
                                lp.geometry.pool if lp.geometry else "")

    # -- backend hook ------------------------------------------------------

    def _run_core(self, lp: LayerProgram, cp: CoreProgram, x_q,
                  w_codes, w_scales) -> jnp.ndarray:
        """Compute one layer partition's [m, n_part] fp32 output."""
        raise NotImplementedError


def requantize(x: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Inter-layer write-back requantization: fp32 -> int8 codes at
    ``bits`` with a per-tensor max-abs scale (the chain's single
    bit-exactness-critical quantizer)."""
    s_a = fit_scale(x, bits)
    lo, hi = qrange(bits)
    return jnp.clip(jnp.round(x / s_a), lo, hi).astype(jnp.int8)


def requantize_with_scale(x: jnp.ndarray, bits: int):
    """:func:`requantize` that also returns the per-tensor scale — the
    spatial chain tracks (codes, scale) pairs so residual adds and the
    non-scale-invariant activations (relu6/hswish) run in absolute fp32
    units. Bit-identical codes to :func:`requantize`."""
    s_a = fit_scale(x, bits)
    lo, hi = qrange(bits)
    return jnp.clip(jnp.round(x / s_a), lo, hi).astype(jnp.int8), s_a


def apply_elementwise(y: jnp.ndarray, ops, residual=None) -> jnp.ndarray:
    """Apply the add/activation ops of a fused elementwise tail to a
    layer's absolute fp32 output ``y`` (``requant`` is the chain's job:
    it produces the (codes, scale) pair; pool glue applies between the
    activation and the requant).

    ``residual`` is the dequantized add operand (same shape as ``y``),
    required iff an ``add`` op is present. Shared verbatim by the eager
    golden/multi chains and the jitted Pallas epilogue so every backend
    computes the exact same tail.
    """
    for op in ops:
        if op.kind == "add":
            if residual is None:
                raise ExecutionError("elementwise add without a residual "
                                     "operand")
            y = y + residual
        elif op.kind == "relu":
            y = jnp.maximum(y, 0.0)
        elif op.kind == "relu6":
            y = jnp.clip(y, 0.0, 6.0)
        elif op.kind == "hswish":
            y = y * jnp.clip(y + 3.0, 0.0, 6.0) * (1.0 / 6.0)
        elif op.kind != "requant":
            raise ExecutionError(f"unknown elementwise kind {op.kind!r}")
    return y


def elementwise_tail(ops, pool: str):
    """Build the functional form of one layer's fused elementwise tail:
    ``tail(y_abs, residual=None) -> (y_post, codes, scale)`` — add/act
    ops, the geometry's ``pool`` glue, then the write-back ``requant``
    producing the stored (codes, scale) pair (``(y, None, None)`` when
    the tail carries no requant, i.e. the final layer). Pure jnp, so
    the Pallas backend jits it as the layer's fused epilogue while the
    golden chain runs it eagerly — same function, bit-identical."""
    ops = tuple(ops)
    rq = [op for op in ops if op.kind == "requant"]

    def tail(y, residual=None):
        y = apply_elementwise(y, ops, residual)
        y = apply_pool(y, pool)
        if rq:
            codes, scale = requantize_with_scale(y, rq[0].bits)
            return y, codes, scale
        return y, None, None
    return tail


def requantize_rows(x: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Row-independent twin of :func:`requantize`: one max-abs scale
    per batch row instead of per tensor.

    For a single-row input the scale reduction sees exactly the same
    elements as the per-tensor path, so the two are bit-identical at
    batch 1 — which is what lets slot-batched serving
    (``DecodeSession.step_slots``) mix unrelated requests in one batch
    while each slot stays bit-exact against a dedicated batch-1
    session.
    """
    lo, hi = qrange(bits)
    s_a = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                      1e-8) * _inv_hi(bits)
    return jnp.clip(jnp.round(x / s_a), lo, hi).astype(jnp.int8)


def chain_layers(layers, run_layer, x_q, x_scale: float = 1.0,
                 tail_factory=None, *, layer_spans):
    """Chain ``layers`` through ``run_layer(index, x_q)`` with the
    inter-layer requantization the hardware applies on write-back.

    The single source of truth for the bit-exactness-critical chain:
    ``ExecutorBackend.run`` drives it over one program's layers,
    ``MultiDeviceExecutor.run`` over a bundle's global layers — so the
    multi-device hand-off requantizes exactly like the single-device
    chain. ``layers`` items need ``.index``, ``.name``, ``.dims``,
    ``.bits_a``, ``.geometry`` and ``.elementwise``; when every layer
    carries a geometry the chain is spatial (NHWC reshape + the
    in-program fused elementwise tail + im2col staging, shortcut layers
    reading ``src_offset`` producers), otherwise the FC rule
    n_i == k_{i+1} applies (the LM sessions own that glue).
    ``tail_factory(lp)`` overrides how a layer's elementwise tail
    callable is built (the Pallas backend supplies jitted fused
    epilogues); the default is the eager :func:`elementwise_tail`.

    The chain opens the per-layer ``n3h.layer*`` host spans
    (``repro.obs.spans``) inside the caller's ``n3h.run``;
    ``layer_spans`` are the layers' ``n3h.layer`` names, which
    executors build once, when they are built (``spans.layer_spans``).
    The chain's code is the same whether it runs eagerly or is traced
    into one executable (``PallasExecutor``'s ``n3h_chain``).
    """
    layers = list(layers)
    if layers and all(getattr(lp, "geometry", None) is not None
                      for lp in layers):
        return _chain_spatial(layers, run_layer, x_q, x_scale,
                              tail_factory, layer_spans)
    out = None
    for lp, layer_span in zip(layers, layer_spans):
        with span(layer_span):
            if out is not None:
                if out.shape[1] != lp.dims.k or out.shape[0] != lp.dims.m:
                    raise ExecutionError(
                        f"layer {lp.index} expects "
                        f"[{lp.dims.m},{lp.dims.k}] activations but "
                        f"layer {lp.index - 1} produced "
                        f"{tuple(out.shape)}; run_layer() drives "
                        f"non-chaining programs layer by layer")
                with span(spans.LAYER_GLUE):
                    x_q = requantize(out, lp.bits_a)
            with span(spans.LAYER_RUN):
                out = run_layer(lp.index, x_q)
    return out


def _chain_spatial(layers, run_layer, x_q, x_scale: float,
                   tail_factory, layer_spans) -> jnp.ndarray:
    """Spatial NHWC chain over conv layers (resnet18/mobilenet_v2).

    Layer ``pos`` consumes the stored post-tail codes of layer
    ``pos - src_offset`` (the plain chain or a ResNet downsample
    shortcut reading the block input). The chain tracks a
    (codes, scale) pair per producer: a layer's GEMM result is first
    scaled to absolute fp32 units (``run_layer`` applies the weight
    scales but not the staged input's activation scale), then its
    in-program fused elementwise tail runs — residual add of the
    dequantized ``src_offset`` producer, activation, the geometry's
    ``pool`` glue, and the write-back ``requant`` that produces the
    codes + scale its consumers stage. Residual adds and relu6/hswish
    are not scale invariant, which is why the tail must run in
    absolute units rather than on raw codes. The final layer carries
    no requant: its absolute fp32 output (the logits) is returned.
    """
    if tail_factory is None:
        def tail_factory(lp):
            return elementwise_tail(
                tuple(getattr(lp, "elementwise", ()) or ()),
                lp.geometry.pool)
    # per-position (abs fp32 post-pool output, codes, scale); codes are
    # materialized lazily for programs predating the elementwise stage
    stored: list[list] = []

    def _stage(pos: int, bits: int):
        y_abs, codes, scale = stored[pos]
        if codes is None:
            codes, scale = requantize_with_scale(y_abs, bits)
            stored[pos][1:] = [codes, scale]
        return codes, scale

    for pos, (lp, layer_span) in enumerate(zip(layers, layer_spans)):
        with span(layer_span):
            geom = lp.geometry
            with span(spans.LAYER_GLUE):
                if pos == 0:
                    x_sp = jnp.asarray(x_q, jnp.int8)
                    if x_sp.shape != geom.in_shape:
                        raise ExecutionError(
                            f"conv chain input must be spatial "
                            f"{geom.in_shape}, got {tuple(x_sp.shape)}")
                    s_in = jnp.float32(x_scale)
                else:
                    src = pos - geom.src_offset
                    if src < 0:
                        raise ExecutionError(
                            f"layer {lp.index} reads producer {src}, "
                            f"which precedes the chain")
                    x_sp, s_in = _stage(src, lp.bits_a)
                    if x_sp.shape != geom.in_shape:
                        raise ExecutionError(
                            f"layer {lp.index} expects spatial "
                            f"{geom.in_shape} but producer {src} yields "
                            f"{tuple(x_sp.shape)}")
            with span(spans.LAYER_RUN):
                out = run_layer(lp.index, x_sp)
            with span(spans.LAYER_GLUE):
                y = settle(spatialize(out, geom) * s_in)
                residual = None
                for op in tuple(getattr(lp, "elementwise", ()) or ()):
                    if op.kind != "add":
                        continue
                    r = pos - op.src_offset
                    if r < 0:
                        raise ExecutionError(
                            f"layer {lp.index} adds producer {r}, which "
                            f"precedes the chain")
                    r_codes, r_scale = _stage(r, lp.bits_a)
                    if r_codes.shape != y.shape:
                        raise ExecutionError(
                            f"layer {lp.index} residual add expects "
                            f"{tuple(y.shape)} but producer {r} yields "
                            f"{tuple(r_codes.shape)}")
                    residual = settle(r_codes.astype(jnp.float32) * r_scale)
            with span(spans.LAYER_TAIL):
                y, codes, scale = tail_factory(lp)(y, residual)
            stored.append([y, codes, scale])
    # final layer: absolute fp32 logits in GEMM [rows, c_out] form
    with span(spans.LAYER_GLUE):
        return stored[-1][0].reshape(-1, layers[-1].geometry.c_out)


def synthetic_weights(index: int, k: int, n_lut: int, n_dsp: int,
                      bits_w_lut: int, seed: int | None = None):
    """Deterministic synthetic (w_lut, s_lut, w_dsp, s_dsp) for a layer.

    Codes span each partition's full quantized range, uniformly.
    Scales are a 0.5..1.5 ramp, so column mixups cannot cancel out,
    times the He scale of a layer with ``k`` inputs, sqrt(2 / (k *
    var(code))): activations then keep their size from layer to layer,
    as in a trained network with folded batch norm, so a deep chain
    (resnet50's 54 layers) stays finite. The generation depends only
    on (index-or-seed, k, n_lut, n_dsp, bits), so a multi-device
    executor sharding these full-layer weights sees exactly what a
    single-device executor binds (bit-exactness tests).
    """
    rng = np.random.default_rng(index if seed is None else seed)

    def side(bits: int, n: int):
        if not n:
            return None, None
        lo, hi = qrange(bits)
        he = np.sqrt(2.0 / (k * ((hi - lo + 1) ** 2 - 1) / 12.0))
        return (rng.integers(lo, hi + 1, (k, n)),
                (np.linspace(0.5, 1.5, n) * he).astype(np.float32))
    return side(bits_w_lut, n_lut) + side(4, n_dsp)


def bind_synthetic(ex: ExecutorBackend, lp: LayerProgram,
                   seed: int | None = None) -> None:
    """Bind deterministic synthetic weight codes/scales for one layer.

    Shared by the CLI ``--execute`` path, the executor benchmark and the
    pass-invariance tests, so the bind_layer contract has one call site
    to keep current.
    """
    w_lut, s_lut, w_dsp, s_dsp = synthetic_weights(
        lp.index, lp.dims.k, lp.n_lut, lp.dims.n - lp.n_lut,
        lp.bits_w_lut, seed)
    ex.bind_layer(lp.index, w_lut=w_lut, s_lut=s_lut,
                  w_dsp=w_dsp, s_dsp=s_dsp)
