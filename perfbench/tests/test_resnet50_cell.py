"""The cells ``resnet50.b1.z020`` and ``resnet18.b1.z045`` and the two
readers of the named conv launches.

A run of ``resnet50.b1.z020`` on the CPU at a small size, with the look
for a chip skipped, comes out correct, and with one layer's output
altered underneath it does not. The readers ``pointwise_conv_roofline``
and ``spatial_conv_roofline`` split the launches of a hand-built trace
summary by their window and count each layer's least time once."""
import dataclasses
import json
import time
import types

import pytest

import harness
import tracing
from work import least_time_s

SEED = 2 ** 34 + 11  # wider than 32 bits: seeds are any whole number
IN_HW = 32           # the cell's network at 32x32 instead of 224x224
SECONDS = 0.3


def _run(workload: str, hook=None):
    cell = harness.load_cell(workload)
    cell = dataclasses.replace(cell, config=dict(cell.config, in_hw=IN_HW))
    return harness.run(workload, SEED, SECONDS, False, time.perf_counter(),
                       require_tpu=False, cell=cell, system_hook=hook)


def _break_one_layer(system):
    """Fault: one layer's output (the first projection's, at one pixel)
    altered by one code's worth in every channel, so that no relu
    downstream can hide it."""
    run_layer = system.ex.run_layer

    def altered(index, x):
        y = run_layer(index, x)
        return y.at[0].add(abs(y).max() / 7) if index == 4 else y
    system.ex.run_layer = altered


@pytest.mark.parametrize("hook", [None, _break_one_layer],
                         ids=["sound", "layer_altered"])
def test_resnet50_run_is_correct_only_when_sound(hook):
    result, notes = _run("resnet50.b1.z020", hook)
    check = result["checks"]["mismatch_share"]
    if hook is None:
        assert result["correct"] is True, notes
        assert check["value"] == 0.0 and result["failed"] == 0
    else:
        assert result["correct"] is False, notes
        assert check["value"] > check["limit"]


def test_cells_read_their_files():
    r50 = harness.load_cell("resnet50.b1.z020")
    assert len(harness.reference_layers(r50.config)) == 54
    z045 = harness.load_cell("resnet18.b1.z045")
    assert z045.traffic["fpga_target"] == "XC7Z045"
    z020 = harness.load_cell("resnet18.b1.z020")
    assert dict(z045.traffic, fpga_target="XC7Z020") == z020.traffic
    assert z045.limits == z020.limits == r50.limits
    for cell in (r50, z045):
        assert {"pointwise_conv_roofline", "spatial_conv_roofline"} <= \
            {m["name"] for m in cell.per_layer}


def test_each_configuration_names_a_source_of_its_own():
    """A configuration is new only if its source and its reduced keys
    differ from every other's; its file names the same source."""
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    keys = [(c["source"], tuple(c["reduced"])) for c in spec["configs"]]
    assert len(set(keys)) == len(keys)
    for c in spec["configs"]:
        assert json.loads((harness.ROOT / c["file"]).read_text())["source"] \
            == c["source"]


PEAKS = {"int8_ops_per_s": 4e14, "hbm_bytes_per_s": 8e11}


def _ctx(op_seconds, images=2):
    cfg = harness.load_cell("resnet50.b1.z020").config
    works = [harness.layer_work(ly, ly.c_out // 2, 4, 4)
             for ly in harness.reference_layers(cfg)]
    trace = tracing.TraceSummary(
        window_s=1.0, busy_s=0.5, kernel_s=sum(op_seconds.values()),
        other_s=0.0, kernel_ops=len(op_seconds), op_seconds=op_seconds,
        gap_seconds={})
    return types.SimpleNamespace(images=images, layer_work=works,
                                 peaks=PEAKS, trace=trace)


def test_readers_split_launches_by_window():
    ops = {
        # one launch of layers 2 and 6 (3x3), in two executables: summed
        "jit_n3h_chain/fused_conv_gemm_3x3_L2_6 f32[3136,128]": 2e-4,
        "jit_other/fused_conv_gemm_3x3_L2_6 f32[3136,128]": 1e-4,
        "jit_n3h_chain/fused_conv_gemm_1x1_L4 f32[3136,256]": 3e-4,
        "jit_n3h_chain/fused_conv_gemm_1x1_L53 f32[1,1024]": 1e-4,
        # neither an unnamed launch nor XLA's own work counts
        "jit_n3h_chain/fused_conv_gemm f32[49,512]": 5.0,
        "jit_n3h_chain/fusion s8[12544,3]": 5.0,
    }
    ctx = _ctx(ops)
    point = harness.metric_reader("pointwise_conv_roofline")(ctx)
    spatial = harness.metric_reader("spatial_conv_roofline")(ctx)
    least = {i: least_time_s(ctx.layer_work[i], PEAKS) for i in (2, 4, 6, 53)}
    assert point == pytest.approx(
        100 * (least[4] + least[53]) / (4e-4 / 2))
    assert spatial == pytest.approx(
        100 * (least[2] + least[6]) / (3e-4 / 2))


def test_readers_find_nothing_in_an_unnamed_trace():
    """The program before its launches were named: no reading."""
    ctx = _ctx({"jit_n3h_chain/fused_conv_gemm f32[3136,256]": 1e-3})
    for name in ("pointwise_conv_roofline", "spatial_conv_roofline"):
        assert harness.metric_reader(name)(ctx) is None
