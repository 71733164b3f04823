"""Chip benchmark of batch-1 image latency through ``PallasExecutor``.

Everything a cell needs is found by name from ``BENCHMARK.json``:

  configs/<config>.json   sizes of the network, as it is run
  configs/<config>.py     its plain reference graph (``layers(cfg)``)
  traffic/<traffic>.json  the request mix and the FPGA target of the split
  checks/<workload>.json  the limit of each number that decides ``correct``
  metrics/<metric>.py     the reader of one per-layer metric (``read(ctx)``)

A run builds the program the user builds (``compile_network`` then
``PallasExecutor(mode="auto")``), binds weights made on the device from
``--seed``, warms up on the cell's own image shape, and then sends one
image at a time for ``--seconds``: a closed loop of one client, each
request from the host-side image to ``block_until_ready`` on its logits.
After the window every request's logits are compared with the plain
reference (``qcnn.py``) on the same image and weights.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import gc
import importlib.util
import json
import math
import pathlib
import shutil
import statistics
import sys
import tempfile
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

import peaks as peak_table
import qcnn
import tracing
from work import gemm_dims, layer_work

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
#: fixed in-checkout directory of JAX's persistent compilation cache
CACHE_DIR = ROOT / ".jax_cache"
#: JAX's monitoring event for each executable compiled or loaded
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: longest window a ``--trace 1`` run traces: its metrics are per image,
#: and the trace grows by 0.7-1.5 MB a request
TRACE_WINDOW_S = 5.0


class BenchError(RuntimeError):
    """The run cannot produce a result."""


class NoChip(BenchError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# The cell, from BENCHMARK.json and the files named after its entries
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def _read_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, root: pathlib.Path = ROOT) -> Cell:
    spec = _read_json(root / "BENCHMARK.json")
    entry = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == entry["config"])
    return Cell(
        name=workload, chips=entry["chips"],
        config=_read_json(root / conf["file"]),
        traffic=_read_json(BENCH_DIR / "traffic" / f"{entry['traffic']}.json"),
        limits=_read_json(BENCH_DIR / "checks" / f"{workload}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)])


def _load_module(path: pathlib.Path, name: str):
    if str(BENCH_DIR) not in sys.path:
        sys.path.insert(0, str(BENCH_DIR))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_layers(cfg: dict) -> list:
    mod = _load_module(BENCH_DIR / "configs" / f"{cfg['name']}.py",
                       f"perfbench_config_{cfg['name']}")
    return mod.layers(cfg)


def metric_reader(name: str):
    return _load_module(BENCH_DIR / "metrics" / f"{name}.py",
                        f"perfbench_metric_{name}").read


# ---------------------------------------------------------------------------
# Set-up: the device, the program, weights and images from the seed
# ---------------------------------------------------------------------------


def enable_compile_cache() -> None:
    """JAX's persistent cache in the checkout, for every compile: the
    per-layer programs are small and fast to compile, and JAX's
    defaults would leave them out of the cache."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_info(chips: int, require_tpu: bool = True) -> dict:
    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise NoChip(f"JAX reports no TPU (platform {d.platform!r})")
    if require_tpu and len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX reports {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def build_program(cell: Cell):
    from repro.compiler import compile_network
    cfg = cell.config
    return compile_network(cfg["network"], device=cell.traffic["fpga_target"],
                           bits_w=cfg["bits_w"], bits_a=cfg["bits_a"],
                           opt_level=cfg["opt_level"], in_hw=cfg["in_hw"],
                           width=cfg["width"])


def check_graph(prog, layers) -> None:
    """The program's layers must be the reference graph's, shape for
    shape: weights are made per reference layer and bound by index."""
    if len(prog.layers) != len(layers):
        raise BenchError(f"program has {len(prog.layers)} layers, the "
                         f"reference graph {len(layers)}")
    for lp, ly in zip(prog.layers, layers):
        dims = (lp.dims.m, lp.dims.k, lp.dims.n)
        if dims != gemm_dims(ly) or lp.depthwise != ly.depthwise:
            raise BenchError(f"layer {lp.name}: program GEMM {dims} "
                             f"(depthwise={lp.depthwise}), reference "
                             f"{ly.name} {gemm_dims(ly)}")


def split_seed(seed: int):
    """(jax key seed, numpy Generator) drawn from any whole ``seed``."""
    w, i = np.random.SeedSequence(seed).spawn(2)
    return int(w.generate_state(1)[0] >> 1), np.random.default_rng(i)


@functools.partial(jax.jit, static_argnums=(1, 2))
def make_weights(key, shapes, code_max):
    """All layers' weights in one call on the device: per layer
    ``(k, n, n_lut)`` -> (w_lut, s_lut, w_dsp, s_dsp). Codes are int32,
    uniform in [-code_max, code_max]. Each fp32 column scale is the He
    scale of a layer with ``k`` inputs, sqrt(2 / (k * var(code))), times
    a factor uniform in [0.5, 1.5], so activations keep their size from
    layer to layer as in a trained network with folded batch norm. A
    side with no columns is None."""
    keys = jax.random.split(jax.random.key(key), 2 * len(shapes))
    code_var = ((2 * code_max + 1) ** 2 - 1) / 12
    out = []
    for i, (k, n, n_lut) in enumerate(shapes):
        w = jax.random.randint(keys[2 * i], (k, n), -code_max, code_max + 1,
                               jnp.int32)
        s = jax.random.uniform(keys[2 * i + 1], (n,), jnp.float32, 0.5, 1.5) \
            * (2.0 / (k * code_var)) ** 0.5
        lut = (w[:, :n_lut], s[:n_lut]) if n_lut else (None, None)
        dsp = (w[:, n_lut:], s[n_lut:]) if n_lut < n else (None, None)
        out.append(lut + dsp)
    return tuple(out)


def weight_code_max(cfg: dict) -> int:
    """Largest code magnitude, symmetric so the codes have mean 0."""
    return 2 ** (cfg["bits_w"] - 1) - 1


def make_images(rng, n: int, shape, bits: int):
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    return rng.integers(lo, hi + 1, (n, *shape), dtype=np.int8)


@dataclasses.dataclass
class System:
    """The program under test with its weights and the cell's images."""
    prog: object
    ex: object
    layers: list
    weights: tuple
    images: object
    order: list


def build_system(cell: Cell, seed: int, prog=None) -> System:
    from repro.compiler import PallasExecutor
    prog = build_program(cell) if prog is None else prog
    layers = reference_layers(cell.config)
    check_graph(prog, layers)
    key, rng = split_seed(seed)
    shapes = tuple((lp.dims.k, lp.dims.n, lp.n_lut) for lp in prog.layers)
    weights = make_weights(key, shapes, weight_code_max(cell.config))
    ex = PallasExecutor(prog, mode="auto")
    for lp, (w_lut, s_lut, w_dsp, s_dsp) in zip(prog.layers, weights):
        ex.bind_layer(lp.index, w_lut=w_lut, s_lut=s_lut, w_dsp=w_dsp,
                      s_dsp=s_dsp)
    pool = cell.traffic["image_pool"]
    images = make_images(rng, pool, prog.layers[0].geometry.in_shape,
                         prog.layers[0].bits_a)
    order = [int(i) for i in rng.permutation(pool)]
    jax.block_until_ready(weights)
    return System(prog, ex, layers, weights, images, order)


def warm_up(system: System) -> None:
    """Every program the window runs, on the cell's one image shape."""
    for idx in system.order[:2]:
        jax.block_until_ready(system.ex.run(jax.device_put(system.images[idx])))


# ---------------------------------------------------------------------------
# The measured window
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Window:
    latencies_s: list
    outputs: list
    image_ids: list
    start: float
    end: float
    compiles: int
    counters: dict

    @property
    def seconds(self) -> float:
        return self.end - self.start


def _layer_counters() -> dict:
    from repro.obs import METRICS
    snap = METRICS.snapshot()["counters"]
    return {k: v for k, v in snap.items() if k.startswith("pallas.layer.")}


class CompileCounter:
    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if event == COMPILE_EVENT:
            self.n += 1


def closed_loop(system: System, seconds: float, compiles: CompileCounter,
                traced: bool = False) -> Window:
    """One client, batch 1, no think time: the next image is sent when
    the last one's logits are ready. Requests start until ``seconds``
    have passed; the window ends when the last of them is done."""
    span = jax.profiler.TraceAnnotation if traced \
        else (lambda name: contextlib.nullcontext())
    ex, images, order = system.ex, system.images, system.order
    lat, outs, ids = [], [], []
    before, c0 = _layer_counters(), compiles.n
    with span("bench.window"):
        start = time.perf_counter()
        deadline = start + seconds
        t1 = start
        i = 0
        while t1 < deadline:
            idx = order[i % len(order)]
            t0 = time.perf_counter()
            with span("bench.request"):
                with span("bench.transfer"):
                    x = jax.device_put(images[idx])
                with span("bench.dispatch"):
                    y = ex.run(x)
                with span("bench.wait"):
                    y.block_until_ready()
            t1 = time.perf_counter()
            lat.append(t1 - t0)
            outs.append(y)
            ids.append(idx)
            i += 1
    after = _layer_counters()
    counters = {k: after[k] - before.get(k, 0) for k in after}
    return Window(lat, outs, ids, start, t1, compiles.n - c0, counters)


def end_to_end(window: Window, setup_s: float) -> dict:
    lat_ms = [t * 1e3 for t in window.latencies_s]
    return {
        "image_ms.p50": statistics.median(lat_ms),
        "image_ms.p95": percentile(lat_ms, 95),
        "images_per_s": len(lat_ms) / window.seconds,
        "setup_s": setup_s,
    }


def percentile(values, q: float) -> float:
    """Linear-interpolated ``q``-th percentile (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


# ---------------------------------------------------------------------------
# Correctness: every request's logits against the plain reference
# ---------------------------------------------------------------------------


def full_weights(weights):
    """Per layer the whole [k, n] codes and [n] scales, LUT columns
    first, from the split halves the benchmark made."""
    ws, ss = [], []
    for w_lut, s_lut, w_dsp, s_dsp in weights:
        ws.append(jnp.concatenate([w for w in (w_lut, w_dsp) if w is not None], 1))
        ss.append(jnp.concatenate([s for s in (s_lut, s_dsp) if s is not None]))
    return ws, ss


def reference_logits(layers, weights, images, ids, dtype=None) -> dict:
    """image id -> reference logits [1, n_classes], for each id once."""
    dtype = jnp.float32 if dtype is None else dtype
    ws, ss = jax.jit(full_weights)(weights)
    return {i: np.asarray(qcnn.forward(layers, ws, ss, jnp.asarray(images[i]),
                                       dtype))
            for i in sorted(set(ids))}


def logit_errors(outputs, ids, ref: dict) -> list:
    """Per request: max |logit - reference| over max |reference|
    (inf where the logits are not finite or not the reference's shape)."""
    errs = []
    for out, i in zip(outputs, ids):
        out = np.asarray(out, np.float64)
        want = np.asarray(ref[i], np.float64)
        if out.shape != want.shape or not np.isfinite(out).all():
            errs.append(float("inf"))
            continue
        errs.append(float(np.max(np.abs(out - want)) /
                          max(np.max(np.abs(want)), 1e-30)))
    return errs


def judge(errs: list, limits: dict) -> tuple[bool, int, dict]:
    """(correct, failed requests, checks: each number beside its limit).

    A request fails where it gave no finite logits of the reference's
    shape. The number compared is the share of requests whose logits
    are off the reference by more than ``logit_tol`` of their largest
    (``checks/<workload>.json`` gives both and what they were set from).
    """
    spec = limits["mismatch_share"]
    failed = sum(1 for e in errs if not math.isfinite(e))
    share = sum(1 for e in errs if not e <= spec["logit_tol"]) / max(len(errs), 1)
    checks = {"mismatch_share": {"value": share, "limit": spec["limit"]}}
    return bool(errs) and failed == 0 and share <= spec["limit"], failed, checks


# ---------------------------------------------------------------------------
# Per-layer metrics from the trace
# ---------------------------------------------------------------------------


def layer_context(system: System, window: Window, trace, peaks: dict):
    """What the per-layer readers read (``metrics/<name>.py``)."""
    prog = system.prog
    works = [layer_work(ly, lp.n_lut, lp.bits_w_lut, lp.bits_a)
             for lp, ly in zip(prog.layers, system.layers)]
    paths = [system.ex.layer_paths.get(lp.name, "") for lp in prog.layers]
    return types.SimpleNamespace(
        images=len(window.latencies_s), window_s=window.seconds,
        images_per_s=len(window.latencies_s) / window.seconds,
        layer_paths=paths, layer_work=works, counters=window.counters,
        peaks=peaks, trace=trace)


def per_layer_metrics(cell: Cell, ctx) -> dict:
    out = {}
    for m in cell.per_layer:
        value = metric_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


@contextlib.contextmanager
def profiler_trace():
    """The JAX profiler on, without its Python tracer; yields the
    directory the trace goes to, which is removed afterwards."""
    tmp = tempfile.mkdtemp(prefix="perfbench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        yield tmp
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, require_tpu: bool = True, cell: Cell | None = None,
        system_hook=None) -> tuple[dict, list]:
    """One run of a cell: (result object, lines for stderr).

    ``require_tpu=False``, ``cell`` and ``system_hook`` exist for the
    benchmark's own tests, which drive a run on the CPU at a small size
    and break the system underneath."""
    enable_compile_cache()
    cell = load_cell(workload) if cell is None else cell
    device = device_info(cell.chips, require_tpu)
    peaks = peak_table.peaks_for(device["kind"]) if trace else None
    compiles = CompileCounter()
    system = build_system(cell, seed)
    if system_hook is not None:
        system_hook(system)
    warm_up(system)
    # set-up's objects (the compiled program's instruction streams among
    # them) go to the permanent generation, as a server does after
    # start-up, so a collection in the window does not walk them all
    gc.collect()
    gc.freeze()
    notes = [f"set-up: {compiles.n} executables compiled or loaded"]
    metrics, breakdown = {}, None
    with contextlib.ExitStack() as stack:
        trace_dir = stack.enter_context(profiler_trace()) if trace else None
        setup_s = time.perf_counter() - t_start
        window = closed_loop(system, min(seconds, TRACE_WINDOW_S) if trace
                             else seconds, compiles, traced=trace)
        if trace:
            jax.profiler.stop_trace()
            summary = tracing.reduce_dir(trace_dir)
    notes.append(f"window: {len(window.latencies_s)} requests in "
                 f"{window.seconds!r} s, {window.compiles} executables "
                 f"compiled or loaded in the window")
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    if trace:
        ctx = layer_context(system, window, summary, peaks)
        metrics = per_layer_metrics(cell, ctx)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = {"device_ops": summary.top_ops(10),
                     "idle_gaps": summary.top_gaps(10)}
        notes.append("layer paths: " + json.dumps(
            dict(sorted(collections.Counter(ctx.layer_paths).items()))))
    else:
        e2e = end_to_end(window, setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    # the program's state goes before the reference runs
    outputs = jax.device_get(window.outputs)
    ids = window.image_ids
    layers, weights, images = system.layers, system.weights, system.images
    del system, window
    from repro.compiler import PallasExecutor
    PallasExecutor.cache_clear()
    t_ref = time.perf_counter()
    ref = reference_logits(layers, weights, images, ids)
    notes.append(f"reference: {len(ref)} images in "
                 f"{time.perf_counter() - t_ref!r} s")
    errs = logit_errors(outputs, ids, ref)
    correct, failed, checks = judge(errs, cell.limits)
    notes.append(f"logit error over the reference's largest logit: max "
                 f"{max(errs)!r}, median {statistics.median(errs)!r}, "
                 f"{len(set(ids))} distinct images")
    result = {"correct": correct, "attempted": len(ids), "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    notes += [f"check {k}: {v['value']!r} (limit {v['limit']!r})"
              for k, v in checks.items()]
    return result, notes
