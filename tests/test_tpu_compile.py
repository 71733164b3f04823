"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode (every other kernel test) runs a kernel body on the CPU
but never through Mosaic, so it misses tiling, layout and VMEM
refusals. These tests hand the kernels' shapes to the TPU compiler for
a described v5e chip; nothing runs, so they say nothing about results
or times. The ``ops.py`` wrappers ask the backend they run on and take
their CPU branch here, so the tests compile the kernel functions
themselves, at the shapes the wrappers give them.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_hetero_gemm import fused_conv_gemm, fused_hetero_gemm
from repro.kernels.ops import fused_conv_matmul

BN = 128
I8, F32 = jnp.int8, jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described (not attached) v5e:2x2 host. The
    persistent compilation cache is off meanwhile: an executable for a
    described chip cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _dense(bits):
    """Both split sides, two column blocks each (the packed-int4 side
    in per-block slabs), K over several blocks."""
    m, k, n_lut, n_dsp = 256, 1152, 2 * BN, 2 * BN
    fn = functools.partial(fused_hetero_gemm, bits=bits,
                           n_lut_blocks=n_lut // BN,
                           n_dsp_blocks=n_dsp // BN)
    return fn, [((m, k), I8), ((bits, k, n_lut), I8),
                ((n_dsp // BN, k, BN // 2), I8), ((n_lut + n_dsp,), F32)]


def _conv(in_hw, c_in, kernel, stride, pad, out_hw, n_lut_blocks,
          n_dsp_blocks, bits=4):
    """``fused_conv_gemm`` on the zero-padded spatial block, as the
    ``ops.fused_conv_matmul`` wrapper hands it over."""
    k = kernel * kernel * c_in
    hp = in_hw + 2 * pad
    fn = functools.partial(
        fused_conv_gemm, bits=bits, n_lut_blocks=n_lut_blocks,
        n_dsp_blocks=n_dsp_blocks, kernel=kernel, stride=stride,
        out_hw=out_hw)
    return fn, [((hp, hp, c_in), I8), ((bits, k, n_lut_blocks * BN), I8),
                ((max(n_dsp_blocks, 1), k, BN // 2), I8),
                (((n_lut_blocks + n_dsp_blocks) * BN,), F32)]


CASES = {
    "fused_hetero_gemm_bits4": lambda: _dense(4),
    "fused_hetero_gemm_bits8": lambda: _dense(8),
    # one-sided splits: the empty region's dummy block is never read
    "fused_hetero_gemm_dsp_only": lambda: (
        functools.partial(fused_hetero_gemm, bits=4, n_lut_blocks=0,
                          n_dsp_blocks=2),
        [((128, 512), I8), ((4, 512, BN), I8), ((2, 512, BN // 2), I8),
         ((256,), F32)]),
    "fused_hetero_gemm_lut_only": lambda: (
        functools.partial(fused_hetero_gemm, bits=4, n_lut_blocks=2,
                          n_dsp_blocks=0),
        [((128, 512), I8), ((4, 512, 256), I8), ((1, 512, BN // 2), I8),
         ((256,), F32)]),
    # resnet18 conv2: 56x56x64, 3x3 stride 1
    "conv_resnet18_conv2": lambda: _conv(56, 64, 3, 1, 1, 56, 1, 1),
    # resnet18 conv6: 3x3 stride 2, 56 -> 28 (stride phases)
    "conv_resnet18_conv6_s2": lambda: _conv(56, 64, 3, 2, 1, 28, 1, 1),
    # mobilenet_v2 b6_pw: 14x14x192 1x1 (out_hw and C off the tiling)
    "conv_mobilenet_v2_b6_pw": lambda: _conv(14, 192, 1, 1, 0, 14, 1, 1),
    # resnet18 fc as a 1x1 conv on a 1x1 map: several DSP slabs
    "conv_resnet18_fc": lambda: _conv(1, 512, 1, 1, 0, 1, 6, 3),
    # resnet50 layer4 conv_c: 1x1, k 512 -> n 2048 at m 49 (its XC7Z020
    # split: 1696 LUT columns in 14 blocks, 352 DSP columns in 3)
    "conv_resnet50_layer4_conv_c": lambda: _conv(7, 512, 1, 1, 0, 7, 14, 3),
    # resnet50 layer4 projection: 1x1 stride 2, 14 -> 7, k 1024, n 2048
    "conv_resnet50_layer4_proj_s2": lambda: _conv(14, 1024, 1, 2, 0, 7,
                                                  14, 3),
    # resnet50 layer1 conv_c: 1x1 at m 3136, n 256
    "conv_resnet50_layer1_conv_c": lambda: _conv(56, 64, 1, 1, 0, 56, 2, 1),
    "flash_attention": lambda: (
        flash_attention, [((1, 4, 256, 128), F32)] * 3),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_launch_is_named_by_window_and_layers(one_chip):
    """The executor's name for a fused conv launch (its window and the
    indices of the layers that share it) is the kernel's instruction
    name in the compiled HLO, which the device trace carries."""
    from repro.compiler import PallasExecutor, compile_network
    from repro.compiler.runtime.pallas import _launch_name
    prog = compile_network("resnet50", in_hw=32, width=0.25)
    fns = PallasExecutor._build_fns(prog, "kernel")
    groups: dict = {}
    for lp in prog.layers:
        groups.setdefault(fns["fused-sp", lp.index], []).append(lp)
    for lps in groups.values():   # one launch: one bit-width and geometry
        assert len({(lp.bits_w_lut, lp.geometry) for lp in lps}) == 1
    # layer3's identity blocks share their launches; the last conv_c
    # (1x1, the only 512 -> 2048 at this size) has its own
    shared = max(groups.values(), key=len)
    k = shared[0].geometry.kernel
    assert len(shared) >= 5 and _launch_name(shared, "kernel") == \
        f"fused_conv_gemm_{k}x{k}_L" + "_".join(str(lp.index)
                                                for lp in shared)
    name = _launch_name([prog.layers[52]], "kernel")
    assert name == "fused_conv_gemm_1x1_L52"
    assert [lp.index for lp in groups[fns["fused-sp", 52]]] == [52]
    fn, shapes = _conv(7, 512, 1, 1, 0, 7, 14, 3)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    text = jax.jit(functools.partial(fn, name=name)).lower(
        *args).compile().as_text()
    launches = [re.match(r"\s*(?:ROOT )?%(\S+) = ", line).group(1)
                for line in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line]
    assert launches and all(re.fullmatch(rf"{name}(\.\d+)?", instr)
                            for instr in launches), launches


def test_xla_vmem_stem_compiles_as_an_int8_convolution(one_chip):
    """resnet's 7x7 stride-2 stem at 224 (C 3, m 12544) is over the
    VMEM budget: the wrapper hands XLA one int8 convolution from the
    spatial block with int32 sums, which the v5e's compiler takes, with
    no Pallas launch."""
    k, n_lut, n_dsp = 7 * 7 * 3, 49, 15
    fn = functools.partial(fused_conv_matmul, kernel=7, stride=2, pad=3,
                           out_hw=112, bits=4, mode="kernel")
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in
            [((224, 224, 3), I8), ((k, n_lut), jnp.int32), ((n_lut,), F32),
             ((k, n_dsp), jnp.int32), ((n_dsp,), F32)]]
    text = jax.jit(lambda x, wl, sl, wd, sd: fn(
        x, w_lut=wl, s_lut=sl, w_dsp=wd, s_dsp=sd)).lower(
        *args).compile().as_text()
    convs = [line for line in text.splitlines()
             if re.search(r"= s32\[[0-9,]*\]\{[^}]*\} convolution\(", line)]
    assert convs and "tpu_custom_call" not in text
