"""Host spans of the executor chain and role names of the jitted calls.

The chain opens ``n3h.*`` spans (``repro.obs.spans``) that land, under
``jax.profiler``, on the ``/host:CPU`` plane of the same trace as the
device's operations; ``PallasExecutor`` names each jitted callable by
its role (``n3h_chain``, ``n3h_conv_<path>``, ``n3h_gemm_<path>``,
``n3h_tail``), so its executable is
``jit_<name>``. A warm ``PallasExecutor.run`` is one launch of
``n3h_chain``; the per-layer spans open on the eager chains. These
tests record a trace on the CPU and read the spans back from the
``.xplane.pb``, as the chip benchmark's reduction does.
"""
import glob
import os

import jax
import numpy as np
import pytest

from repro.compiler import (
    ExecutorSession,
    GemmLayer,
    GoldenExecutor,
    MultiDeviceExecutor,
    PallasExecutor,
    bind_synthetic,
    compile_decode_network,
    derive_plan,
    lower_network,
    lower_partitioned,
)
from repro.core.scheduler import XC7Z020, DspCoreConfig, GemmDims, \
    LutCoreConfig
from repro.core.workloads import ConvSpec
from repro.models.cnn import CNNConfig, specs_for
from repro.obs import spans

LUT = LutCoreConfig(m=8, n=16, k=128)
DSP = DspCoreConfig(n_reg_row_a=13)


def _residual_chain():
    """Three conv layers; the last adds the first one's output."""
    specs = [ConvSpec("c0", 3, 12, 3, 1, 8, act="relu"),
             ConvSpec("c1", 12, 12, 3, 1, 8, act="relu"),
             ConvSpec("c2", 12, 12, 1, 1, 8, act="relu", res_src=2)]
    return [GemmLayer.from_conv(s) for s in specs]


def _bind_all(ex, layers):
    for i in range(len(layers)):
        if isinstance(ex, MultiDeviceExecutor):
            ex.bind_synthetic(i, seed=i)
        else:
            bind_synthetic(ex, ex.program.layers[i], seed=i)
    return ex


def _traced(fn, tmp_path):
    """Run ``fn`` under the JAX profiler (host spans only, as the
    benchmark traces) and return its ``n3h.*`` host events as
    ``(name, start_ns, end_ns, stats)``, in start order."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        jax.block_until_ready(fn())
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    out = [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
           for plane in pd.planes if plane.name == "/host:CPU"
           for line in plane.lines for ev in line.events
           if ev.name.startswith("n3h.")]
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _inside(ev, outer):
    return outer[1] <= ev[1] and ev[2] <= outer[2]


def _no_overlap(events):
    by_name = {}
    for ev in events:
        by_name.setdefault(ev[0], []).append(ev)
    for group in by_name.values():
        for a, b in zip(group, group[1:]):
            assert a[2] <= b[1], f"two {a[0]} spans overlap"


def _executor(backend, layers):
    prog = lower_network("spans", layers, LUT, DSP, XC7Z020)
    if backend == "multi":
        bundle = lower_partitioned("spans", layers,
                                   derive_plan(layers, 2, "filter"),
                                   LUT, DSP, XC7Z020)
        return _bind_all(MultiDeviceExecutor(bundle, backend="pallas"),
                         layers)
    if backend == "pallas-eager":
        return _bind_all(PallasExecutor(prog, check_timing=True), layers)
    cls = PallasExecutor if backend == "pallas" else GoldenExecutor
    return _bind_all(cls(prog), layers)


def _one_launch(events):
    """The warm chain executable's spans: ``n3h.run`` around exactly
    one ``n3h.run.launch``, and no per-layer span."""
    run, = [ev for ev in events if ev[0] == spans.RUN]
    launch, = [ev for ev in events if ev[0] == spans.RUN_LAUNCH]
    assert _inside(launch, run)
    assert [ev[0] for ev in events] == [spans.RUN, spans.RUN_LAUNCH]


@pytest.mark.parametrize("backend", ["pallas", "pallas-eager", "golden",
                                     "multi"])
def test_conv_chain_spans(backend, tmp_path):
    layers = _residual_chain()
    ex = _executor(backend, layers)
    x = np.random.default_rng(0).integers(
        -8, 8, layers[0].geometry.in_shape).astype(np.int8)
    ex.run(x)  # compile outside the trace
    events = _traced(lambda: ex.run(x), tmp_path)
    _no_overlap(events)
    if backend == "pallas":
        _one_launch(events)
        return
    run, = [ev for ev in events if ev[0] == spans.RUN]
    assert all(_inside(ev, run) for ev in events)
    per_layer = [ev for ev in events if ev[0] == spans.LAYER]
    assert [ev[3]["layer"] for ev in per_layer] == [gl.name for gl in layers]
    for lay in per_layer:
        inner = [ev for ev in events if ev is not lay and _inside(ev, lay)]
        names = [ev[0] for ev in inner]
        assert names.count(spans.LAYER_RUN) == 1
        assert names.count(spans.LAYER_GLUE) >= 1
        assert names.count(spans.LAYER_TAIL) == 1
        layer_run, = [ev for ev in inner if ev[0] == spans.LAYER_RUN]
        launches = [ev for ev in inner if ev[0] == spans.LAYER_LAUNCH]
        assert all(_inside(ev, layer_run) for ev in launches)
        if backend == "golden":
            assert not launches
        elif backend == "pallas-eager":
            want = ex.layer_paths[lay[3]["layer"]]
            assert [ev[3]["path"] for ev in launches] == [want]
        else:  # one launch per filter shard
            assert len(launches) >= 1
            assert {ev[3]["path"] for ev in launches} <= set(
                p for e in ex.executors for p in e.layer_paths.values())


def test_fc_chain_glue_is_the_hand_off_requant(tmp_path):
    layers = [GemmLayer("fc1", GemmDims(8, 16, 24)),
              GemmLayer("fc2", GemmDims(8, 24, 16))]
    ex = _executor("pallas", layers)
    x = np.random.default_rng(1).integers(-8, 8, (8, 16)).astype(np.int8)
    ex.run(x)
    _one_launch(_traced(lambda: ex.run(x), tmp_path / "chain"))
    # the eager chain: one launch a layer, the hand-off requant as glue
    ex = _executor("pallas-eager", layers)
    ex.run(x)
    events = _traced(lambda: ex.run(x), tmp_path / "eager")
    names = [ev[0] for ev in events]
    assert names.count(spans.RUN) == 1
    assert names.count(spans.LAYER) == names.count(spans.LAYER_RUN) == 2
    assert names.count(spans.LAYER_LAUNCH) == 2
    # only the second layer requantizes its input; an FC chain has no
    # elementwise tail
    assert names.count(spans.LAYER_GLUE) == 1
    assert spans.LAYER_TAIL not in names


@pytest.mark.parametrize("mode", ["auto", "kernel"])
def test_jitted_callables_named_by_role(mode):
    cfg = CNNConfig(arch="mobilenet_v2", n_classes=10, in_hw=28, width=0.25)
    prog = lower_network("mnv2", [GemmLayer.from_conv(s)
                                  for s in specs_for(cfg)],
                         LUT, DSP, XC7Z020)
    ex = PallasExecutor(prog, mode=mode)
    fns = ex._build_fns(prog, mode)
    assert all(fn.__name__.startswith("n3h_") for fn in fns.values())
    for lp in prog.layers:
        dw, bits = lp.depthwise, lp.bits_w_lut
        assert fns["fused-sp", lp.index].__name__ == \
            f"n3h_conv_{ex.layer_path(lp.index, spatial=True)}"
        assert fns["fused", bits, dw].__name__ == \
            f"n3h_gemm_{ex.layer_path(lp.index, spatial=False)}"
    kinds = {key[0]: fn.__name__ for key, fn in fns.items()}
    assert set(kinds) == {"ew", "fused", "fused-sp", "chain"}
    assert kinds["ew"] == "n3h_tail"
    assert kinds["chain"] == "n3h_chain"


def test_chain_traces_the_layer_spans_once(tmp_path):
    """The first run traces the chain, so the per-layer spans open
    once, inside ``n3h.run.launch``; the warm run opens none."""
    PallasExecutor.cache_clear()
    layers = _residual_chain()
    ex = _executor("pallas", layers)
    x = np.zeros(layers[0].geometry.in_shape, np.int8)
    events = _traced(lambda: ex.run(x), tmp_path)
    launch, = [ev for ev in events if ev[0] == spans.RUN_LAUNCH]
    per_layer = [ev for ev in events if ev[0] == spans.LAYER]
    assert [ev[3]["layer"] for ev in per_layer] == [gl.name for gl in layers]
    assert all(_inside(ev, launch) for ev in events
               if ev[0].startswith(spans.LAYER))
    assert sum(ev[0] == spans.LAYER_LAUNCH for ev in events) == len(layers)


def test_executable_is_jit_of_the_role_name():
    layers = _residual_chain()
    ex = _executor("pallas", layers)
    lp = ex.program.layers[0]
    fn = ex._fns["fused-sp", lp.index]
    w = ex._weights[0]
    x = np.zeros(lp.geometry.in_shape, np.int8)
    text = fn.lower(x, w.w_lut, w.s_lut, w.w_dsp, w.s_dsp).as_text()
    assert f"@jit_n3h_conv_{ex.layer_path(0, spatial=True)}" in text


def test_decode_step_spans_carry_the_phase(tmp_path):
    prog = compile_decode_network("llama3.2-1b", batch=1, max_seq=8,
                                  opt_level=1)
    sess = ExecutorSession(prog, backend="pallas")
    sess.bind_synthetic_all(seed=0)
    tok = np.array([3], np.int32)

    def two_steps():
        sess.step(tok, 0)
        return sess.step(tok, 1)

    events = _traced(two_steps, tmp_path)
    steps = [ev for ev in events if ev[0] == spans.DECODE_STEP]
    assert [ev[3]["phase"] for ev in steps] == ["warmup", "steady"]
    _no_overlap(events)


def test_span_is_free_without_a_profiler():
    assert spans.span(spans.RUN) is spans.span(spans.LAYER)
    with spans.span(spans.encode(spans.LAYER, layer="c0")):
        pass
    assert spans.encode(spans.RUN) == spans.RUN
    assert spans.encode("a", x=1, path="kernel") == "a#x=1,path=kernel#"
