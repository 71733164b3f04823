"""Batched executor backend — the Pallas fast path.

The golden interpreter loops a Python iteration per tile (plus a
per-core scheduler validation), which makes large registry LM programs
unusably slow to execute. This backend exploits that a layer's tile
grids compute a plain split GEMM, and dispatches ONE fused kernel call
per layer: ``kernels.fused_matmul`` consumes both sides of the Eq.-12
split — the first ``n_lut`` output columns
bit-serially at the layer's LUT bit-width, the rest as packed int4 —
accumulating into a single int32 [m, n] tile with per-column fp32
dequant, so no layer needs a concat or a second launch. Conv
layers go through ``kernels.fused_conv_matmul`` /
``fused_depthwise_matmul``, which generate im2col patches *inside* the
launch from the raw spatial NHWC block (no ``L{i}.col`` staging copy
exists in compiled programs' DDR maps).

Bit-exactness: every path accumulates in exact int32 (bitplane or
packed-int4 arithmetic) and applies per-column fp32 scales
elementwise, so the fused call == the golden interpreter's
tile-by-tile assembly bit for bit — tiling/fusing an exact integer
GEMM is associative, and the dequant scale is per output element. The
pass-invariance suite and ``tests/test_fused_kernels.py`` pin this.

``mode`` is forwarded to the kernel wrappers ("auto" | "kernel" |
"ref"). Under "auto" on a TPU, dense layers and in-budget convs run
the compiled Pallas kernels (``kernels/fused_hetero_gemm.py``);
depthwise convs run an exact int32 einsum under XLA, and convs whose
whole-spatial working set is over the VMEM budget run XLA's int8
convolution from the spatial block (a 1x1 window: the oracle's dot).
Off the TPU, "auto" runs the jnp oracles everywhere. Each
executed layer records where it ran (``kernels.ops.conv_path`` /
``kernel_path``) in :attr:`PallasExecutor.layer_paths` and bumps the
``pallas.layer.<path>`` counter in ``obs.metrics.METRICS``, so no layer
leaves the kernel silently.

Every jitted callable is named by its role, so the profiler's ``XLA
Modules`` line says what ran: ``n3h_conv_<path>`` (spatial fused
calls), ``n3h_gemm_<path>`` (pre-staged fused calls), ``n3h_tail``
(elementwise epilogues), ``n3h_chain`` (the whole chain); the eager
chain's glue keeps jnp's names. Inside them the fused conv kernel is
launched under a name that gives its window and the indices in the
program of the layers that launch it,
``fused_conv_gemm_<k>x<k>_L<i>[_<j>...]`` (the layers of one geometry
share one traced launch): the kernel's instruction name in the
compiled HLO and the device trace. ``run_layer`` opens the
``n3h.layer.launch`` host span (``repro.obs.spans``) around the enqueue
of the layer's call.

One executable per program: the table also holds ``n3h_chain``, the
shared ``chain_layers`` traced once over the program with the table's
callables inlined, the bound weights as one pytree argument and the
input scale as a traced scalar. ``run`` calls it, inside the
``n3h.run`` and ``n3h.run.launch`` spans, when the executor runs
without ``check_timing`` and keeps its own ``run_layer``; otherwise
``run`` drives the eager chain through ``run_layer``. Either way each
run records ``layer_paths``, bumps ``pallas.layer.<path>`` once per
layer, and counts itself as ``pallas.run.chain`` or
``pallas.run.eager``.

Per-program JIT cache: every distinct ``(program fingerprint, mode)``
gets one *complete* table of jitted callables, built atomically under
the cache lock at construction and never mutated afterwards — so
concurrent executors can share a table without races. The table is
shared across executor instances (a class-level LRU of
``_jit_cache_capacity`` programs). Hits/misses are published to
``obs.metrics.METRICS`` as ``pallas.jit_cache.*`` so
``launch/serve.py --metrics`` reports kernel-cache behavior alongside
the program-image cache.

Timing/contract checks are *off* by default here (that is the golden
backend's job); pass ``check_timing=True`` to keep the per-core
scheduler validation (``ExecutorBackend._check_stream``) on the fast
path too.
"""
from __future__ import annotations

import collections
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.obs import spans
from repro.obs.metrics import METRICS
from repro.obs.spans import span
from repro.compiler.program import LayerProgram
from repro.compiler.runtime.base import (
    ExecutionError,
    ExecutorBackend,
    chain_layers,
    elementwise_tail,
    stage_activations,
)


#: the ``n3h.layer.launch`` span of each path a layer can run on
_LAUNCH_SPANS = {p: spans.encode(spans.LAYER_LAUNCH, path=p)
                 for p in kops.PATHS}


def _named_jit(f, name: str):
    """``jax.jit`` of ``f`` under ``name``: the executable is then
    ``jit_<name>`` in the profiler's ``XLA Modules`` line."""
    f.__name__ = f.__qualname__ = name
    return jax.jit(f)


def _make_fused_fn(bits: int, depthwise: bool, mode: str):
    """One launch over the whole split: pre-staged [m, k] (dense) or
    [m, k, n] (depthwise) activations, both weight partitions in.
    Named ``n3h_gemm_<path>`` by where it runs."""
    if depthwise:
        def f(x_col, w_lut, s_lut, w_dsp, s_dsp):
            return kops.fused_grouped_matmul(x_col, w_lut, s_lut, bits,
                                             w_dsp, s_dsp, mode=mode)
        path = "xla_depthwise"
    else:
        def f(x_q, w_lut, s_lut, w_dsp, s_dsp):
            return kops.fused_matmul(x_q, w_lut, s_lut, bits,
                                     w_dsp, s_dsp, mode=mode)
        path = kops.kernel_path(mode)
    return _named_jit(f, f"n3h_gemm_{path}")


def _launch_name(lps: list, mode: str) -> str:
    """The name of the fused conv kernel launch that the layers ``lps``
    (one bit-width and geometry) share: their window and their indices
    in the program, ``fused_conv_gemm_<k>x<k>_L<i>[_<j>...]``, where
    they run the Pallas kernel; the kernel's default name where they
    do not (the name is then never emitted). One name for the group
    keeps one trace and lowering of the kernel for it, as before the
    launches were named."""
    if _layer_path(lps[0], True, mode) not in ("kernel", "interpret"):
        return "fused_conv_gemm"
    k = lps[0].geometry.kernel
    return f"fused_conv_gemm_{k}x{k}_L" + "_".join(str(lp.index)
                                                   for lp in lps)


def _make_fused_sp_fn(bits: int, geom, depthwise: bool, mode: str,
                      name: str):
    """One launch from the raw spatial NHWC block: im2col happens
    inside the call (in-kernel on TPU, in-jit on CPU), the kernel
    launched as ``name``. Named ``n3h_conv_<path>`` by where it runs
    (``kops.conv_path``)."""
    kk, st, p, oh = geom.kernel, geom.stride, geom.pad, geom.out_hw
    if depthwise:
        def f(x_sp, w_lut, s_lut, w_dsp, s_dsp):
            return kops.fused_depthwise_matmul(x_sp, kk, st, p, oh,
                                               w_lut, s_lut, bits,
                                               w_dsp, s_dsp, mode=mode)
    else:
        def f(x_sp, w_lut, s_lut, w_dsp, s_dsp):
            return kops.fused_conv_matmul(x_sp, kk, st, p, oh,
                                          w_lut, s_lut, bits,
                                          w_dsp, s_dsp, mode=mode,
                                          name=name)
    path = kops.conv_path(geom.in_shape[0], geom.in_shape[2], kk, p, oh,
                          bits, depthwise=depthwise, mode=mode)
    return _named_jit(f, f"n3h_conv_{path}")


def _layer_path(lp: LayerProgram, spatial: bool, mode: str) -> str:
    """Where ``lp`` runs: a ``kops.conv_path`` name for a spatial conv
    input, else "xla_depthwise" or a ``kops.kernel_path`` name."""
    geom = lp.geometry
    if spatial:
        return kops.conv_path(geom.in_shape[0], geom.in_shape[2],
                              geom.kernel, geom.pad, geom.out_hw,
                              lp.bits_w_lut, depthwise=lp.depthwise,
                              mode=mode)
    return "xla_depthwise" if lp.depthwise else kops.kernel_path(mode)


def _is_spatial(lp: LayerProgram, x_q) -> bool:
    return lp.geometry is not None and x_q.shape == lp.geometry.in_shape


def _launch(fns: dict, lp: LayerProgram, x_q, wts: tuple):
    """The layer's one fused call on int8 ``x_q`` (spatial NHWC, or
    staged here) with its ``(w_lut, s_lut, w_dsp, s_dsp)``."""
    if _is_spatial(lp, x_q):
        # spatial input: im2col happens inside the fused call
        fn = fns["fused-sp", lp.index]
    else:
        x_q = stage_activations(lp, x_q)
        fn = fns[("fused", lp.bits_w_lut, lp.depthwise)]
    return fn(x_q, *wts)


def _tail(fns: dict, lp: LayerProgram):
    """The layer's jitted elementwise epilogue from the table, or the
    eager shared tail for a layer without one."""
    if lp.geometry is not None and lp.elementwise:
        fn = fns.get(("ew", lp.elementwise, lp.geometry.pool))
        if fn is not None:
            return fn
    return elementwise_tail(tuple(lp.elementwise),
                            lp.geometry.pool if lp.geometry else "")


def _make_chain_fn(program, fns: dict, mode: str):
    """The whole chain (``chain_layers``) as one executable,
    ``n3h_chain(weights, x_q, x_scale)``: ``weights`` holds each
    layer's ``(w_lut, s_lut, w_dsp, s_dsp)`` in layer order, so the
    codes are arguments and never constants of the program, and
    ``x_scale`` is a traced f32 scalar. The per-layer calls are the
    table's role-named jitted callables, inlined by the trace."""
    layers = program.layers
    layer_spans = spans.layer_spans(layers)

    def n3h_chain(weights, x_q, x_scale):
        def run_layer(index, x):
            lp = layers[index]
            x = jnp.asarray(x, jnp.int8)
            path = _layer_path(lp, _is_spatial(lp, x), mode)
            with span(_LAUNCH_SPANS[path]):
                return _launch(fns, lp, x, weights[index])
        return chain_layers(layers, run_layer, x_q, x_scale,
                            tail_factory=lambda lp: _tail(fns, lp),
                            layer_spans=layer_spans)
    return _named_jit(n3h_chain, "n3h_chain")


class PallasExecutor(ExecutorBackend):
    """One fused (jitted, program-cached) kernel call per layer."""

    name = "pallas"

    #: (program fingerprint, mode) -> complete (frozen) fn table; LRU
    #: over programs, shared across instances so re-executing the same
    #: compiled program skips retracing.
    _jit_cache: "collections.OrderedDict[tuple, dict]" = \
        collections.OrderedDict()
    _jit_cache_capacity = 16
    _jit_cache_lock = threading.Lock()
    _cache_hits = 0
    _cache_misses = 0

    def __init__(self, program, check_timing: bool = False,
                 mode: str = "auto"):
        super().__init__(program, check_timing=check_timing)
        self.mode = mode
        #: layer name -> where its last execution ran (see layer_path)
        self.layer_paths: dict[str, str] = {}
        self._fns = self._program_fns(program, mode)
        # what one run of the chain executable records on the host: the
        # path of each layer and the pallas.layer.<path> counts
        spatial = all(lp.geometry is not None for lp in program.layers)
        self._chain_paths = {lp.name: self.layer_path(lp.index, spatial)
                             for lp in program.layers}
        self._chain_counts = tuple(
            (f"pallas.layer.{path}", n) for path, n in
            collections.Counter(self._chain_paths.values()).items())
        self._chain_weights = None

    @classmethod
    def _build_fns(cls, program, mode: str) -> dict:
        """The complete jit table for one program: one fused entry per
        (bits, depthwise) for pre-staged inputs, one per spatial layer
        (under its index; layers sharing bits and geometry share a
        traced executable), the elementwise tails and the chain."""
        fns: dict = {}
        spatial = collections.defaultdict(list)
        for lp in program.layers:
            dw = lp.depthwise
            bits = lp.bits_w_lut
            key = ("fused", bits, dw)
            if key not in fns:
                fns[key] = _make_fused_fn(bits, dw, mode)
            if lp.geometry is not None:
                spatial[bits, dw, lp.geometry].append(lp)
                if lp.elementwise:
                    # fused elementwise epilogue: one jitted call
                    # applying the layer's add/act/pool/requant tail
                    # (the exact jnp tail the golden chain runs eagerly)
                    key = ("ew", lp.elementwise, lp.geometry.pool)
                    if key not in fns:
                        fns[key] = _named_jit(elementwise_tail(
                            lp.elementwise, lp.geometry.pool), "n3h_tail")
        for (bits, dw, geom), lps in spatial.items():
            fn = _make_fused_sp_fn(bits, geom, dw, mode,
                                   _launch_name(lps, mode))
            for lp in lps:
                fns["fused-sp", lp.index] = fn
        fns["chain",] = _make_chain_fn(program, fns, mode)
        return fns

    @classmethod
    def _program_fns(cls, program, mode: str) -> dict:
        """Shared-table lookup. The table is built *complete* before it
        is published (and never mutated after), so readers outside the
        lock can never observe a partially-populated dict — the race
        the old lazy per-key insertion had."""
        key = (program.fingerprint(), mode)
        with cls._jit_cache_lock:
            fns = cls._jit_cache.get(key)
            if fns is not None:
                cls._jit_cache.move_to_end(key)
                cls._cache_hits += 1
                METRICS.incr("pallas.jit_cache.hit")
                return fns
            cls._cache_misses += 1
            METRICS.incr("pallas.jit_cache.miss")
            fns = cls._build_fns(program, mode)
            cls._jit_cache[key] = fns
            while len(cls._jit_cache) > cls._jit_cache_capacity:
                cls._jit_cache.popitem(last=False)
            METRICS.gauge("pallas.jit_cache.programs", len(cls._jit_cache))
            return fns

    @classmethod
    def cache_info(cls) -> dict:
        with cls._jit_cache_lock:
            return {"programs": len(cls._jit_cache),
                    "hits": cls._cache_hits,
                    "misses": cls._cache_misses,
                    "maxsize": cls._jit_cache_capacity}

    @classmethod
    def cache_clear(cls) -> None:
        with cls._jit_cache_lock:
            cls._jit_cache.clear()
            cls._cache_hits = cls._cache_misses = 0

    def layer_path(self, index: int, spatial: bool) -> str:
        """Where layer ``index`` runs under this executor's mode: a
        ``kops.conv_path`` name for a spatial conv input, else
        "xla_depthwise" or a ``kops.kernel_path`` name."""
        return _layer_path(self.program.layers[index], spatial, self.mode)

    def bind_layer(self, index: int, *args, **kwargs) -> None:
        super().bind_layer(index, *args, **kwargs)
        self._chain_weights = None

    def run(self, x_q, x_scale: float = 1.0) -> jnp.ndarray:
        """Chain all layers end to end (``ExecutorBackend.run``). Without
        ``check_timing`` and with this class's own ``run_layer``, the
        whole chain is one call into the program's ``n3h_chain``
        executable; otherwise the chain runs eagerly,
        one call per step of every layer, through ``self.run_layer``
        (so a ``run_layer`` replaced on a subclass or an instance is
        the one that runs)."""
        if self.check_timing or "run_layer" in vars(self) \
                or type(self).run_layer is not PallasExecutor.run_layer:
            METRICS.incr("pallas.run.eager")
            return super().run(x_q, x_scale)
        with span(spans.RUN):
            weights = self._chain_weights or self._bound_weights()
            # an input of another shape is traced anew, and the chain
            # rejects it there, on the host, before anything runs
            if not isinstance(x_scale, jax.Array):
                # strong f32, as a scale array is: one trace for both
                x_scale = np.float32(x_scale)
            with span(spans.RUN_LAUNCH):
                out = self._fns["chain",](weights, x_q, x_scale)
        for name, n in self._chain_counts:
            METRICS.incr(name, n)
        METRICS.incr("pallas.run.chain")
        self.layer_paths.update(self._chain_paths)
        return out

    def _bound_weights(self) -> tuple:
        """Every layer's bound weights as the chain's pytree argument,
        built once per binding."""
        weights = []
        for lp in self.program.layers:
            w = self._weights.get(lp.index)
            if w is None:
                raise ExecutionError(f"layer {lp.index} has no bound weights")
            weights.append((w.w_lut, w.s_lut, w.w_dsp, w.s_dsp))
        self._chain_weights = tuple(weights)
        return self._chain_weights

    def run_layer(self, index: int, x_q) -> jnp.ndarray:
        """One fused kernel call for the whole layer (both split
        sides)."""
        lp = self.program.layers[index]
        if index not in self._weights:
            raise ExecutionError(f"layer {index} has no bound weights")
        x_q = jnp.asarray(x_q, jnp.int8)
        path = self.layer_path(index, _is_spatial(lp, x_q))
        self.layer_paths[lp.name] = path
        METRICS.incr(f"pallas.layer.{path}")
        for cp in (lp.lut, lp.dsp):
            if cp is not None:
                self._check_stream(lp, cp)
        w = self._weights[index]
        with span(_LAUNCH_SPANS[path]):
            return _launch(self._fns, lp, x_q,
                           (w.w_lut, w.s_lut, w.w_dsp, w.s_dsp))

    def _elementwise_tail(self, lp: LayerProgram):
        """The layer's fused (jitted, program-cached) elementwise
        epilogue — falls back to the eager shared tail for layers
        without one in the table."""
        return _tail(self._fns, lp)
