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
  * an unbound layer and a wrong input shape raise ``ExecutionError``;
  * weights are laid out for the kernels once, when they are bound:
    under ``mode="kernel"`` the chain's weights argument holds no int32
    leaf of a kernel-path layer, its jaxpr makes no bit-planes or int4
    bytes from them, ``pallas.bind.packed``/``.codes`` count the two
    kinds of binding, and the outputs stay bitwise the eager chain's
    and ``mode="ref"``'s.
"""
import re

import jax
import jax.extend.core as jcore
import numpy as np
import pytest

from repro.compiler import (
    GemmLayer,
    GoldenExecutor,
    PallasExecutor,
    bind_synthetic,
    compile_network,
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


@pytest.mark.parametrize("arch,in_hw,mode", [
    pytest.param(arch, in_hw, mode, id=f"{arch}-{in_hw}" + (
        f"-{mode}" if mode != "auto" else ""))
    for arch, in_hw, mode in [
        ("resnet18", 28, "auto"), ("resnet18", 32, "auto"),
        ("mobilenet_v2", 28, "auto"), ("mobilenet_v2", 32, "auto"),
        ("resnet50", 32, "auto"),
        ("resnet18", 32, "kernel"), ("mobilenet_v2", 32, "kernel"),
        ("resnet50", 32, "kernel")]])
def test_chain_bit_exact_vs_golden_and_eager(arch, in_hw, mode):
    """In-hw 28 is ``test_conv_exec``'s golden comparison, which runs
    the chain executable too; here the chain also meets the eager
    Pallas chain, and the golden chain at in-hw 32. Under "kernel"
    (the kernels interpreted, on weights laid out at bind) the chain
    and the eager chain meet ``mode="ref"``."""
    prog = _cnn_prog(arch, in_hw)
    x = _input(prog)
    chain = np.asarray(_bound(prog, mode=mode).run(x))
    eager = np.asarray(_bound(prog, mode=mode, check_timing=True).run(x))
    assert chain.shape == (1, 10) and np.abs(chain).sum() > 0
    assert chain.tobytes() == eager.tobytes()
    if mode == "kernel":
        want = np.asarray(_bound(prog, mode="ref").run(x))
        assert chain.tobytes() == want.tobytes()
    elif in_hw != 28:
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


# ---------------------------------------------------------------------------
# weights laid out for the kernels at bind time
# ---------------------------------------------------------------------------

KERNEL_PATHS = ("kernel", "interpret")


def _bind_counters() -> dict:
    return {k: METRICS.counter(f"pallas.bind.{k}")
            for k in ("packed", "codes")}


def _ops_on_tainted(jaxpr, tainted: set) -> tuple[set, set]:
    """The primitives ``jaxpr`` applies to values computed from the
    ``tainted`` variables, outside Pallas kernel bodies (a
    ``pallas_call`` consumes its operands as they are), and the
    outputs so computed. Calls with a sub-jaxpr over the same operands
    (``pjit``, ``closed_call``, custom derivatives) are followed."""
    found, tainted = set(), set(tainted)
    for eqn in jaxpr.eqns:
        hit = [i for i, v in enumerate(eqn.invars)
               if not isinstance(v, jcore.Literal) and v in tainted]
        if not hit or eqn.primitive.name == "pallas_call":
            continue
        subs = [p for p in eqn.params.values()
                if isinstance(p, (jcore.Jaxpr, jcore.ClosedJaxpr))]
        sub = subs[0] if len(subs) == 1 else None
        sub = getattr(sub, "jaxpr", sub)
        if sub is not None and len(sub.invars) == len(eqn.invars):
            got, out = _ops_on_tainted(sub, {sub.invars[i] for i in hit})
            found |= got
            tainted |= {v for v, w in zip(eqn.outvars, sub.outvars)
                        if w in out}
        else:
            found.add(eqn.primitive.name)
            tainted |= set(eqn.outvars)
    return found, {v for v in jaxpr.outvars if v in tainted}


@pytest.mark.parametrize("arch,in_hw", [("resnet18", 224),
                                        ("mobilenet_v2", 32),
                                        ("resnet50", 32)])
def test_chain_takes_kernel_layout_weights(arch, in_hw):
    """Under "kernel" the chain's weights argument holds each
    kernel-path layer's ``SplitOperands``, no leaf of which is int32,
    and no op of the chain's jaxpr makes bit-planes or int4 bytes
    (``shift_right_*``, ``and``) from them: the layout ran at bind.
    The other layers (resnet18's stem on ``xla_vmem`` at 224,
    mobilenet_v2's depthwise layers) keep their int32 codes, and the
    stem's own layout from them stays in the chain."""
    prog = compile_network(arch, in_hw=in_hw) if in_hw == 224 \
        else _cnn_prog(arch, in_hw)
    ex = _bound(prog, mode="kernel")
    weights = ex._bound_weights()
    kernel_leaves, other_leaves = [], []
    for lp, w in zip(prog.layers, weights):
        leaves = jax.tree_util.tree_leaves(w)
        if ex._chain_paths[lp.name] in KERNEL_PATHS:
            assert isinstance(w, ops.SplitOperands)
            assert all(leaf.dtype != np.int32 for leaf in leaves)
            kernel_leaves += [True] * len(leaves)
        else:
            assert not isinstance(w, ops.SplitOperands)
            assert any(leaf.dtype == np.int32 for leaf in leaves)
            kernel_leaves += [False] * len(leaves)
            other_leaves += leaves
    assert any(kernel_leaves)
    if in_hw == 224 or arch == "mobilenet_v2":
        assert other_leaves
    closed = jax.make_jaxpr(ex._fns["chain",])(weights, _input(prog),
                                              np.float32(1.0))
    n_w = len(kernel_leaves)
    invars = closed.jaxpr.invars[:n_w]
    found, _ = _ops_on_tainted(
        closed.jaxpr, {v for v, k in zip(invars, kernel_leaves) if k})
    assert not {p for p in found if p.startswith("shift_right")
                or p == "and"}, found
    # the taint reaches what the codes-keeping layers do with theirs
    if in_hw == 224:
        stem, _ = _ops_on_tainted(
            closed.jaxpr, {v for v, k in zip(invars, kernel_leaves)
                           if not k})
        assert "and" in stem and any(p.startswith("shift_right")
                                     for p in stem)


@pytest.mark.parametrize("arch,in_hw,mode,packed", [
    ("resnet18", 224, "kernel", 20), ("resnet18", 224, "ref", 0),
    ("mobilenet_v2", 32, "kernel", None)])
def test_bind_counts_packed_and_code_layers(arch, in_hw, mode, packed):
    """``pallas.bind.packed`` counts the layers whose chain path runs
    the kernel and ``pallas.bind.codes`` the rest (resnet18 at 224: 20
    and the stem); under ``mode="ref"`` every layer keeps its codes, so
    the oracle shares no layout with the code under test."""
    prog = compile_network(arch, in_hw=in_hw) if in_hw == 224 \
        else _cnn_prog(arch, in_hw)
    before = _bind_counters()
    ex = _bound(prog, mode=mode)
    got = {k: v - before[k] for k, v in _bind_counters().items()}
    on_kernel = sum(p in KERNEL_PATHS for p in ex._chain_paths.values())
    if packed is not None:
        assert on_kernel == packed
    assert got == {"packed": on_kernel,
                   "codes": len(prog.layers) - on_kernel}
    assert set(ex._operands) == {lp.index for lp in prog.layers
                                 if ex._chain_paths[lp.name]
                                 in KERNEL_PATHS}
    if mode == "ref":
        assert not ex._operands
        assert all(not isinstance(w, ops.SplitOperands)
                   for w in ex._bound_weights())


def test_rebinding_lays_out_the_new_codes():
    """Under "kernel" a rebound layer's operands are laid out anew from
    the new codes: the chain gives the new codes' result, bitwise
    ``mode="ref"``'s, on the same trace."""
    prog = _cnn_prog("resnet18", 28)
    ex = _bound(prog, mode="kernel")
    x = _input(prog, seed=2)
    lp = prog.layers[5]
    assert ex._chain_paths[lp.name] in KERNEL_PATHS
    old = ex._operands[lp.index]
    before = np.asarray(ex.run(x))
    bind_synthetic(ex, lp, seed=1000)
    assert ex._operands[lp.index] is not old
    after = np.asarray(ex.run(x))
    want = _bound(prog, mode="ref")
    bind_synthetic(want, lp, seed=1000)
    assert before.tobytes() != after.tobytes()
    assert after.tobytes() == np.asarray(want.run(x)).tobytes()
    assert ex._fns["chain",]._cache_size() == 1


def test_dense_layers_row_pad_matches_ref():
    """Dense, non-spatial layers with k % 128 != 0 under "kernel":
    ``run_layer`` and the FC chain hand the kernel the operands laid
    out at bind, row padded to the 128-row block, bit-identical to
    ``mode="ref"``."""
    prog = _fc_prog()
    ex = _bound(prog, mode="kernel")
    want = _bound(prog, mode="ref")
    x = _input(prog, seed=3)
    assert np.asarray(ex.run(x)).tobytes() == \
        np.asarray(want.run(x)).tobytes()
    for lp in prog.layers:
        assert lp.dims.k % 128 and isinstance(ex._operands[lp.index],
                                              ops.SplitOperands)
        x = np.random.default_rng(lp.index).integers(
            -8, 8, (lp.dims.m, lp.dims.k)).astype(np.int8)
        got = np.asarray(ex.run_layer(lp.index, x))
        assert ex.layer_paths[lp.name] == "interpret"
        assert got.tobytes() == np.asarray(
            want.run_layer(lp.index, x)).tobytes()
