"""ResNet-50 v1.5 (bottleneck blocks) on the normal CNN path.

  * topology of ``core.workloads.resnet50_specs``: 54 layers, the MACs
    and weights counted from torchvision's layer shapes, projections
    that read the block input 4 layers back and add conv_c, identity
    blocks whose conv_c adds the block input 3 back;
  * ``specs_for`` propagates shapes through those sources at any input
    size and width, and refuses an arch it does not know;
  * the compiled program's logits on seeded random weights agree with
    the benchmark's plain reference (``perfbench/qcnn.py`` with the
    graph of ``perfbench/configs/resnet50.py``, loaded by path);
  * the producer distance ``ConvSpec.in_src`` leaves resnet18's and
    mobilenet_v2's programs as the old rule (3 back for a shortcut,
    else 1) made them;
  * a two-stage pipeline bundle equals the one-device chain bit for bit;
  * the JAX model (``models/cnn.py``) builds the same network.
"""
import dataclasses
import importlib.util
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compiler import (
    GemmLayer,
    MultiDeviceExecutor,
    PallasExecutor,
    bind_synthetic,
    compile_network,
    derive_plan,
    list_networks,
    lower_network,
    lower_partitioned,
)
from repro.core.scheduler import XC7Z020, DspCoreConfig, LutCoreConfig
from repro.core.workloads import WORKLOADS, resnet50_specs, total_macs
from repro.models import cnn
from repro.models.cnn import CNNConfig, specs_for

BENCH = pathlib.Path(__file__).resolve().parent.parent / "perfbench"
LUT = LutCoreConfig(m=8, n=16, k=128)
DSP = DspCoreConfig(n_reg_row_a=13)


def test_topology_of_resnet50():
    specs = resnet50_specs()
    assert len(specs) == 54
    assert total_macs(specs) == 4_089_184_256
    assert sum(s.n_params for s in specs) == 25_502_912
    assert max(s.c_out for s in specs[:-1]) == 2048
    proj = [i for i, s in enumerate(specs) if s.shortcut]
    assert [specs[i].name for i in proj] == ["conv5_ds", "conv15_ds",
                                             "conv28_ds", "conv47_ds"]
    for i in proj:
        s = specs[i]
        assert (s.kernel, s.in_src, s.res_src, s.act) == (1, 4, 1, "relu")
        block_in, conv_c = specs[i - 4], specs[i - 1]
        assert s.c_in == block_in.c_out and s.c_out == conv_c.c_out
        assert conv_c.act == "" and conv_c.res_src == 0
    ident = [i for i, s in enumerate(specs) if s.res_src == 3]
    assert len(ident) == 12
    for i in ident:
        assert specs[i].kernel == 1 and specs[i].act == "relu"
        assert specs[i].c_out == specs[i - 3].c_out
    # layer1's projection keeps the stride (64 -> 256 at 56x56)
    assert (specs[4].stride, specs[4].in_hw) == (1, 56)
    layers = [GemmLayer.from_conv(s) for s in specs]
    assert [gl.geometry.src_offset for gl in layers] == \
        [s.in_src for s in specs]
    assert "resnet50" in WORKLOADS and "resnet50" in list_networks()


def test_specs_for_chains_at_a_small_size():
    specs = specs_for(CNNConfig(arch="resnet50", n_classes=10, in_hw=32,
                                width=0.25))
    for i, s in enumerate(specs[1:], 1):
        src = specs[i - s.in_src]
        assert (s.in_hw, s.c_in) == (src.pooled_out_hw, src.c_out), s.name
        if s.res_src:
            r = specs[i - s.res_src]
            assert (r.pooled_out_hw, r.c_out) == (s.out_hw, s.c_out), s.name
    assert specs[-1].c_out == 10 and specs[-2].out_hw == 1
    with pytest.raises(ValueError, match="unknown CNN arch"):
        specs_for(CNNConfig(arch="resnet51"))


def _load(monkeypatch, path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, name, mod)
    spec.loader.exec_module(mod)
    return mod


def test_logits_match_the_plain_reference(monkeypatch):
    """The benchmark's configuration at in-hw 32 and a quarter of its
    widths (as ``specs_for`` scales them): the chain executable against
    the float32 reference on the same codes and scales. The limit is
    the benchmark's ``logit_tol``, 1e-3 of the largest logit: the
    global average pool sums its positions in another order than the
    reference's mean."""
    qcnn = _load(monkeypatch, BENCH / "qcnn.py", "qcnn")
    graph = _load(monkeypatch, BENCH / "configs" / "resnet50.py",
                  "resnet50_reference")
    cfg = json.loads((BENCH / "configs" / "resnet50.json").read_text())
    cfg = dict(cfg, in_hw=32, stem_channels=16,
               stage_channels=[c // 4 for c in cfg["stage_channels"]])
    ref_layers = graph.layers(cfg)
    prog = compile_network("resnet50", in_hw=32, width=0.25)
    assert [(lp.dims.m, lp.dims.k, lp.dims.n) for lp in prog.layers] == \
        [(ly.out_hw ** 2, ly.weight_shape[0], ly.c_out) for ly in ref_layers]
    rng = np.random.default_rng(2024)
    ex = PallasExecutor(prog)
    codes, scales = [], []
    for lp in prog.layers:
        k, n = lp.dims.k, lp.dims.n
        w = rng.integers(-7, 8, (k, n))
        s = (rng.uniform(0.5, 1.5, n) * np.sqrt(2 / (k * 56 / 3))
             ).astype(np.float32)
        codes.append(w)
        scales.append(s)
        lut, dsp = slice(0, lp.n_lut), slice(lp.n_lut, n)
        ex.bind_layer(lp.index,
                      **({"w_lut": w[:, lut], "s_lut": s[lut]}
                         if lp.n_lut else {}),
                      **({"w_dsp": w[:, dsp], "s_dsp": s[dsp]}
                         if lp.n_lut < n else {}))
    image = rng.integers(-8, 8, (32, 32, 3)).astype(np.int8)
    got = np.asarray(ex.run(image), np.float64)
    want = np.asarray(qcnn.forward(ref_layers, [jnp.asarray(w) for w in codes],
                                   [jnp.asarray(s) for s in scales],
                                   jnp.asarray(image)), np.float64)
    assert got.shape == want.shape == (1, 1000)
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert err <= 1e-3


def test_pipeline_bundle_hands_the_block_input_across_devices():
    """Two pipeline stages: a stage boundary falls inside the network,
    and the bundle's chain still reads each projection's block input 4
    back and equals the one-device chain bit for bit."""
    cfg = CNNConfig(arch="resnet50", n_classes=10, in_hw=32, width=0.25)
    layers = [GemmLayer.from_conv(s) for s in specs_for(cfg)]
    prog = lower_network("r50", layers, LUT, DSP, XC7Z020)
    ex = PallasExecutor(prog)
    for lp in prog.layers:
        bind_synthetic(ex, lp, seed=lp.index)
    mdp = lower_partitioned("r50", layers, derive_plan(layers, 2, "pipeline"),
                            LUT, DSP, XC7Z020)
    mex = MultiDeviceExecutor(mdp, backend="pallas")
    for gi in range(mdp.n_layers):
        mex.bind_synthetic(gi, seed=gi)
    x = np.random.default_rng(5).integers(-8, 8, (32, 32, 3)).astype(np.int8)
    want = np.asarray(ex.run(x))
    assert np.isfinite(want).all() and np.abs(want).sum() > 0
    assert np.asarray(mex.run(x)).tobytes() == want.tobytes()


@pytest.mark.parametrize("arch", ["resnet18", "mobilenet_v2"])
def test_in_src_leaves_the_older_programs_unchanged(arch):
    for s in WORKLOADS[arch]():
        assert s.in_src == (3 if s.shortcut else 1), s.name
    layers = [GemmLayer.from_conv(s) for s in specs_for(
        CNNConfig(arch=arch, n_classes=10, in_hw=32, width=0.25))]
    old_rule = [dataclasses.replace(gl, geometry=dataclasses.replace(
        gl.geometry, src_offset=3 if gl.name.endswith("_ds") else 1))
        for gl in layers]
    new = lower_network(arch, layers, LUT, DSP, XC7Z020)
    old = lower_network(arch, old_rule, LUT, DSP, XC7Z020)
    assert new == old and new.fingerprint() == old.fingerprint()


def test_jax_model_builds_the_same_network():
    """Parameters per spec, and the forward wired through every block
    (both traced for their shapes, not compiled)."""
    cfg = cnn.reduced_config("resnet50")
    params = jax.eval_shape(lambda key: cnn.init(cfg, key),
                            jax.random.key(0))
    specs = specs_for(cfg)
    assert set(params) == {s.name for s in specs}
    assert [params[s.name]["w"].shape for s in specs] == \
        [(s.kernel, s.kernel, s.c_in, s.c_out) for s in specs]
    x = jax.ShapeDtypeStruct((2, 32, 32, 3), jnp.float32)
    logits = jax.eval_shape(lambda p, x: cnn.forward(p, x, cfg), params, x)
    assert logits.shape == (2, 10)
