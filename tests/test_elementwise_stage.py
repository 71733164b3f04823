"""Fused elementwise result tail: ISA stage, round-trips, bit-exactness.

The contract under test (ISSUE 10 tentpole surface):
  * residual adds / activations / write-back requant live *in the
    program* — ``LayerProgram.elementwise`` tails lowered as stage-6
    fetch/result records with real cycle closures, not Python-side
    glue;
  * the tail round-trips bit-exactly through text assembly (``ew=``)
    and the ``N3HPROG1`` binary image;
  * every op kind executes bit-identically on golden, pallas (fused
    jitted epilogue) and 2-device filter/pipeline bundles;
  * the tail's (codes, scale) quantizer is jit-stable: the eager and
    ``jax.jit``-ed forms agree bitwise (the reciprocal-multiply scale
    form — XLA's division-by-constant rewrite must not shift scales).
"""
import jax
import numpy as np
import pytest

import tests._hypothesis_compat as _hyp

_hyp.install()
from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402
from hypothesis.extra import numpy as hnp  # noqa: E402

import jax.numpy as jnp  # noqa: E402

from repro.compiler import (  # noqa: E402
    GemmLayer,
    GoldenExecutor,
    MultiDeviceExecutor,
    PallasExecutor,
    assemble,
    bind_synthetic,
    derive_plan,
    disassemble,
    from_binary,
    lower_network,
    lower_partitioned,
    to_binary,
)
from repro.compiler.lower import EW_STAGE  # noqa: E402
from repro.compiler.program import (  # noqa: E402
    ELEMENTWISE_KINDS,
    ElementwiseOp,
)
from repro.compiler.runtime.base import elementwise_tail  # noqa: E402
from repro.core.scheduler import (  # noqa: E402
    XC7Z020,
    DspCoreConfig,
    LutCoreConfig,
)
from repro.core.workloads import ConvSpec  # noqa: E402
from repro.models.cnn import CNNConfig, specs_for  # noqa: E402
from repro.quant.uniform import qrange  # noqa: E402

LUT = LutCoreConfig(m=8, n=16, k=128)
DSP = DspCoreConfig(n_reg_row_a=13)

ACT_KINDS = ("relu", "relu6", "hswish")


def _residual_chain(act: str):
    """Three-layer chain whose last layer adds the first layer's output
    (same 8x8x12 shape) — every tail kind in one program."""
    return [ConvSpec("c0", 3, 12, 3, 1, 8, act=act),
            ConvSpec("c1", 12, 12, 3, 1, 8, act=act),
            ConvSpec("c2", 12, 12, 1, 1, 8, act=act, res_src=2)]


def _lowered(specs, **kw):
    layers = [GemmLayer.from_conv(s) for s in specs]
    return layers, lower_network("ew", layers, LUT, DSP, XC7Z020, **kw)


def _bound(cls, prog):
    ex = cls(prog)
    for lp in prog.layers:
        bind_synthetic(ex, lp, seed=lp.index)
    return ex


def _image(gl: GemmLayer, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        -8, 8, gl.geometry.in_shape).astype(np.int8)


# ---------------------------------------------------------------------------
# The tail is in the program: IR ordering + stage-6 ISA records
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["resnet18", "mobilenet_v2", "resnet50"])
def test_workload_tails_lowered_into_program(arch):
    cfg = CNNConfig(arch=arch, n_classes=10, in_hw=28, width=0.25)
    layers = [GemmLayer.from_conv(s) for s in specs_for(cfg)]
    prog = lower_network(arch, layers, LUT, DSP, XC7Z020)
    assert any(op.kind == "add" for lp in prog.layers
               for op in lp.elementwise)
    for lp in prog.layers[:-1]:
        # every non-final layer's tail ends in the write-back requant
        assert lp.elementwise and lp.elementwise[-1].kind == "requant"
        assert 1 <= lp.elementwise[-1].bits <= 8
        # canonical order: add -> activation -> requant
        ranks = {"add": 0, "relu": 1, "relu6": 1, "hswish": 1,
                 "requant": 2}
        seq = [ranks[op.kind] for op in lp.elementwise]
        assert seq == sorted(seq), lp.name
    # the classifier's tail carries no requant (fp32 logits out)
    assert all(op.kind != "requant"
               for op in prog.layers[-1].elementwise)


def test_tail_emits_stage6_records_with_cycles():
    specs = _residual_chain("relu")
    _, prog = _lowered(specs)
    for lp in prog.layers:
        cp = lp.lut if lp.lut is not None else lp.dsp
        ew_res = [op for op in cp.streams["result"]
                  if getattr(op.instr, "stage_ctrl", None) == EW_STAGE]
        assert len(ew_res) == 1          # one fused write-back per layer
        assert ew_res[0].cycles > 0
        # the encoded record carries the tail length
        assert ew_res[0].instr.ddr_offset == len(lp.elementwise)
        n_adds = sum(op.kind == "add" for op in lp.elementwise)
        ew_fetch = [op for op in cp.streams["fetch"]
                    if getattr(op.instr, "stage_ctrl", None) == EW_STAGE]
        assert len(ew_fetch) == n_adds   # residual operand DMA per add
        assert all(op.cycles > 0 for op in ew_fetch)


def test_elementwise_op_validation():
    assert set(ACT_KINDS) < set(ELEMENTWISE_KINDS)
    with pytest.raises(ValueError, match="unknown elementwise kind"):
        ElementwiseOp("sigmoid")
    with pytest.raises(ValueError, match="src_offset"):
        ElementwiseOp("add", src_offset=0)
    for bad in (0, 9):
        with pytest.raises(ValueError, match="bits"):
            ElementwiseOp("requant", bits=bad)


# ---------------------------------------------------------------------------
# Assembly + binary round-trips
# ---------------------------------------------------------------------------


def test_tail_round_trips_text_and_binary():
    specs = _residual_chain("hswish")
    _, prog = _lowered(specs, opt_level=1)
    text = disassemble(prog)
    assert " ew=" in text
    rt = assemble(text)
    assert rt == prog
    assert [lp.elementwise for lp in rt.layers] == \
        [lp.elementwise for lp in prog.layers]
    blob = to_binary(prog)
    rt2 = from_binary(blob)
    assert rt2 == prog
    assert to_binary(rt2) == blob
    # the tail is part of program identity
    bare = lower_network(
        "ew", [GemmLayer.from_conv(ConvSpec(s.name, s.c_in, s.c_out,
                                            s.kernel, s.stride, s.in_hw))
               for s in specs], LUT, DSP, XC7Z020, opt_level=1)
    assert bare.fingerprint() != prog.fingerprint()


# ---------------------------------------------------------------------------
# Bit-exactness: golden == pallas == multi-device, per op kind
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("act", ACT_KINDS)
def test_each_tail_kind_bit_exact_golden_vs_pallas(act):
    layers, prog = _lowered(_residual_chain(act))
    x = _image(layers[0], seed=5)
    out_g = np.asarray(_bound(GoldenExecutor, prog).run(x))
    out_p = np.asarray(_bound(PallasExecutor, prog).run(x))
    assert np.abs(out_g).sum() > 0
    assert (out_g == out_p).all()


@pytest.mark.parametrize("kind", ["filter", "pipeline"])
def test_tail_chain_bundles_bit_exact(kind):
    layers, prog = _lowered(_residual_chain("relu6"))
    x = _image(layers[0], seed=9)
    ref = np.asarray(_bound(GoldenExecutor, prog).run(x))
    plan = derive_plan(layers, 2, kind)
    mdp = lower_partitioned("ew", layers, plan, LUT, DSP, XC7Z020)
    mex = MultiDeviceExecutor(mdp)
    for gi in range(mdp.n_layers):
        mex.bind_synthetic(gi, seed=gi)
    assert (np.asarray(mex.run(x)) == ref).all()


# ---------------------------------------------------------------------------
# Property: the tail quantizer is jit-stable (eager == jit, bitwise)
# ---------------------------------------------------------------------------


@settings(max_examples=15, deadline=None)
@given(tile=hnp.arrays(np.float32, (12, 16),
                       elements=st.floats(-64.0, 64.0, width=32)),
       act=st.sampled_from(ACT_KINDS),
       bits=st.integers(2, 8),
       with_add=st.booleans())
def test_tail_eager_vs_jit_bitwise(tile, act, bits, with_add):
    """The fused Pallas epilogue jits the exact tail golden runs
    eagerly; they must agree *bitwise* on codes and scale. Guards the
    reciprocal-multiply scale form against XLA's division-by-constant
    rewrite reintroducing a 1-ulp eager/jit drift."""
    ops = ((ElementwiseOp("add", src_offset=1),) if with_add else ()) \
        + (ElementwiseOp(act), ElementwiseOp("requant", bits=bits))
    tail = elementwise_tail(ops, pool="")
    y = jnp.asarray(tile)
    res = jnp.asarray(tile[::-1]) if with_add else None
    post_e, codes_e, scale_e = tail(y, res)
    post_j, codes_j, scale_j = jax.jit(tail)(y, res)
    lo, hi = qrange(bits)
    assert int(jnp.min(codes_e)) >= lo and int(jnp.max(codes_e)) <= hi
    assert (np.asarray(codes_e) == np.asarray(codes_j)).all()
    assert np.float32(scale_e).tobytes() == np.float32(scale_j).tobytes()
    assert (np.asarray(post_e) == np.asarray(post_j)).all()
