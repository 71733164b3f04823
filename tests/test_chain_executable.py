"""``PallasExecutor.run`` as one executable per program: ``n3h_chain``.

Without ``check_timing`` a run is one call into the program's
``n3h_chain`` executable, which traces the shared ``chain_layers``
with the bound weights as one pytree argument and the input scale as
a traced scalar. The contract under test:

  * its logits equal the golden chain's and the eager Pallas chain's
    bit for bit (conv chains at the shapes where contracting the
    residual add's products into a fused multiply-add had drifted, and
    an FC chain);
  * one trace serves every ``x_scale``; weights are arguments, so
    rebinding a layer changes the output and no weight is a constant
    of the program;
  * each run records on the host what the eager chain records: the
    ``pallas.layer.<path>`` counts, ``layer_paths`` (also for a second
    executor on a cached table) and ``pallas.run.chain`` or
    ``pallas.run.eager``; ``check_timing=True`` and a replaced
    ``run_layer`` keep the eager chain;
  * an unbound layer and a wrong input shape raise ``ExecutionError``.
"""
import re

import numpy as np
import pytest

from repro.compiler import (
    GemmLayer,
    GoldenExecutor,
    PallasExecutor,
    bind_synthetic,
    lower_network,
)
from repro.compiler.runtime import ExecutionError
from repro.core.scheduler import XC7Z020, DspCoreConfig, GemmDims, \
    LutCoreConfig
from repro.kernels import ops
from repro.kernels.fused_hetero_gemm import fused_conv_vmem_bytes
from repro.models.cnn import CNNConfig, specs_for
from repro.obs import METRICS

LUT = LutCoreConfig(m=8, n=16, k=128)
DSP = DspCoreConfig(n_reg_row_a=13)


def _cnn_prog(arch: str, in_hw: int):
    cfg = CNNConfig(arch=arch, n_classes=10, in_hw=in_hw, width=0.25)
    return lower_network(arch, [GemmLayer.from_conv(s)
                                for s in specs_for(cfg)],
                         LUT, DSP, XC7Z020)


def _fc_prog():
    layers = [GemmLayer("fc1", GemmDims(8, 32, 48)),
              GemmLayer("fc2", GemmDims(8, 48, 24)),
              GemmLayer("fc3", GemmDims(8, 24, 16))]
    return lower_network("fc", layers, LUT, DSP, XC7Z020)


def _bound(prog, cls=PallasExecutor, seed_offset: int = 0, **kw):
    ex = cls(prog, **kw)
    for lp in prog.layers:
        bind_synthetic(ex, lp, seed=lp.index + seed_offset)
    return ex


def _input(prog, seed: int = 0) -> np.ndarray:
    lp0 = prog.layers[0]
    shape = lp0.geometry.in_shape if lp0.geometry is not None \
        else (lp0.dims.m, lp0.dims.k)
    return np.random.default_rng(seed).integers(-8, 8, shape).astype(np.int8)


def _layer_counters() -> dict:
    snap = METRICS.snapshot()["counters"]
    return {k: v for k, v in snap.items()
            if k.startswith(("pallas.layer.", "pallas.run."))}


def _delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}


@pytest.mark.parametrize("arch,in_hw", [("resnet18", 28), ("resnet18", 32),
                                        ("mobilenet_v2", 28),
                                        ("mobilenet_v2", 32),
                                        ("resnet50", 32)])
def test_chain_bit_exact_vs_golden_and_eager(arch, in_hw):
    """In-hw 28 is ``test_conv_exec``'s golden comparison, which runs
    the chain executable too; here the chain also meets the eager
    Pallas chain, and the golden chain at in-hw 32."""
    prog = _cnn_prog(arch, in_hw)
    x = _input(prog)
    chain = np.asarray(_bound(prog).run(x))
    eager = np.asarray(_bound(prog, check_timing=True).run(x))
    assert chain.shape == (1, 10) and np.abs(chain).sum() > 0
    assert chain.tobytes() == eager.tobytes()
    if in_hw != 28:
        golden = np.asarray(_bound(prog, GoldenExecutor).run(x))
        assert chain.tobytes() == golden.tobytes()


@pytest.mark.parametrize("arch", ["resnet18", "mobilenet_v2"])
def test_over_budget_layers_run_xla_conv_in_the_chain(arch, monkeypatch):
    """With the VMEM budget just under the stem's working set, the stem
    (and any other layer over it) takes ``xla_vmem``, XLA's int8
    convolution from the spatial block, inside ``n3h_chain``: the
    logits equal the eager chain's and ``mode="ref"``'s bit for bit,
    ``layer_paths`` names exactly the over-budget layers, and
    ``pallas.layer.xla_vmem`` rises by their number per run."""
    prog = _cnn_prog(arch, 32)

    def vmem_bytes(lp):
        g = lp.geometry
        return fused_conv_vmem_bytes(g.in_shape[0], g.in_shape[2], g.kernel,
                                     g.pad, g.out_hw ** 2, lp.dims.k,
                                     lp.bits_w_lut)
    budget = vmem_bytes(prog.layers[0]) - 1
    over = {lp.name for lp in prog.layers
            if not lp.depthwise and vmem_bytes(lp) > budget}
    assert prog.layers[0].name in over and len(over) < len(prog.layers) // 2
    monkeypatch.setattr(ops, "FUSED_CONV_VMEM_BUDGET", budget)
    PallasExecutor.cache_clear()
    try:
        x = _input(prog, seed=4)
        ex = _bound(prog, mode="kernel")
        chain = np.asarray(ex.run(x))
        eager = np.asarray(_bound(prog, mode="kernel",
                                  check_timing=True).run(x))
        want = np.asarray(_bound(prog, mode="ref").run(x))
        assert np.abs(chain).sum() > 0
        assert chain.tobytes() == eager.tobytes() == want.tobytes()
        assert {n for n, p in ex.layer_paths.items()
                if p == "xla_vmem"} == over
        before = METRICS.counter("pallas.layer.xla_vmem")
        ex.run(x)
        assert METRICS.counter("pallas.layer.xla_vmem") - before == len(over)
    finally:
        PallasExecutor.cache_clear()


def test_fc_chain_bit_exact_vs_golden_and_eager():
    prog = _fc_prog()
    x = _input(prog, seed=3)
    chain = np.asarray(_bound(prog).run(x))
    eager = np.asarray(_bound(prog, check_timing=True).run(x))
    golden = np.asarray(_bound(prog, GoldenExecutor).run(x))
    assert chain.shape == (8, 16) and np.abs(chain).sum() > 0
    assert chain.tobytes() == eager.tobytes() == golden.tobytes()


def test_one_trace_across_input_scales():
    PallasExecutor.cache_clear()
    # relu only: the logits scale with the input (relu6 would saturate)
    prog = _cnn_prog("resnet18", 28)
    ex = _bound(prog)
    eager = _bound(prog, check_timing=True)
    x = _input(prog, seed=1)
    outs = []
    for s in (1.0, 0.37, np.float32(2.5)):
        got = np.asarray(ex.run(x, x_scale=s))
        assert got.tobytes() == np.asarray(eager.run(x, x_scale=s)).tobytes()
        outs.append(got)
    assert ex._fns["chain",]._cache_size() == 1
    assert outs[0].tobytes() != outs[1].tobytes()


def test_rebinding_a_layer_changes_the_output():
    prog = _cnn_prog("resnet18", 28)
    ex = _bound(prog)
    x = _input(prog, seed=2)
    before = np.asarray(ex.run(x))
    lp = prog.layers[5]
    bind_synthetic(ex, lp, seed=1000)
    after = np.asarray(ex.run(x))
    want = _bound(prog, check_timing=True)
    bind_synthetic(want, lp, seed=1000)
    assert before.tobytes() != after.tobytes()
    assert after.tobytes() == np.asarray(want.run(x)).tobytes()
    assert ex._fns["chain",]._cache_size() == 1


def test_weights_are_arguments_not_constants():
    prog = _cnn_prog("resnet18", 28)
    ex = _bound(prog)
    weights = ex._bound_weights()
    text = ex._fns["chain",].lower(weights, _input(prog),
                                  np.float32(1.0)).as_text()
    assert "@jit_n3h_chain" in text
    smallest = min(lp.dims.k * n for lp in prog.layers
                   for n in (lp.n_lut, lp.dims.n - lp.n_lut) if n)
    for shape in re.findall(r"stablehlo\.constant dense<[^:]*: "
                            r"tensor<([0-9x]*)[a-z]", text):
        dims = [int(d) for d in shape.split("x") if d]
        assert int(np.prod(dims)) < smallest


@pytest.mark.parametrize("make", ["conv", "fc"])
def test_host_bookkeeping_per_run_matches_the_eager_chain(make):
    prog = _cnn_prog("mobilenet_v2", 28) if make == "conv" else _fc_prog()
    x = _input(prog)
    eager = _bound(prog, check_timing=True)
    eager.run(x)
    before = _layer_counters()
    eager.run(x)
    want = _delta(before, _layer_counters())
    assert want.pop("pallas.run.eager") == 1

    ex = _bound(prog)
    ex.run(x)  # traces: the trace itself records nothing
    for _ in range(2):
        before = _layer_counters()
        ex.run(x)
        got = _delta(before, _layer_counters())
        assert got.pop("pallas.run.chain") == 1
        assert got == want
    assert ex.layer_paths == eager.layer_paths

    # a second executor on the cached table never traces, and still
    # reports its paths and counts
    hits = PallasExecutor.cache_info()["hits"]
    other = _bound(prog)
    assert PallasExecutor.cache_info()["hits"] == hits + 1
    before = _layer_counters()
    other.run(x)
    got = _delta(before, _layer_counters())
    assert got.pop("pallas.run.chain") == 1
    assert got == want
    assert other.layer_paths == eager.layer_paths


def test_unbound_layer_and_wrong_shape_raise():
    prog = _cnn_prog("resnet18", 28)
    ex = PallasExecutor(prog)
    for lp in prog.layers[:-1]:
        bind_synthetic(ex, lp, seed=lp.index)
    with pytest.raises(ExecutionError, match="no bound weights"):
        ex.run(_input(prog))
    bind_synthetic(ex, prog.layers[-1], seed=0)
    with pytest.raises(ExecutionError, match="spatial"):
        ex.run(np.zeros((5, 5, 3), np.int8))
    fc = _bound(_fc_prog())
    with pytest.raises(ExecutionError, match="activations must be"):
        fc.run(np.zeros((8, 31), np.int8))


def _replace_run_layer(ex):
    """A ``run_layer`` replaced on the instance, as a caller does to
    watch or alter each layer's output."""
    seen, run_layer = [], ex.run_layer

    def watched(index, x):
        seen.append(index)
        return run_layer(index, x)
    ex.run_layer = watched
    return seen


@pytest.mark.parametrize("how", ["check_timing", "run_layer"])
def test_eager_chain_where_the_executable_does_not_engage(how):
    prog = _fc_prog()
    kw = {"check_timing": dict(check_timing=True)}.get(how, {})
    ex = _bound(prog, **kw)
    seen = _replace_run_layer(ex) if how == "run_layer" else None
    x = _input(prog)
    before = _layer_counters()
    out = np.asarray(ex.run(x))
    got = _delta(before, _layer_counters())
    assert got.get("pallas.run.eager") == 1
    assert "pallas.run.chain" not in got
    assert out.tobytes() == np.asarray(_bound(prog).run(x)).tobytes()
    if seen is not None:
        assert seen == [lp.index for lp in prog.layers]
