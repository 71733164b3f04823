"""The benchmark's yardstick on the CPU: the trace reduction on a trace
recorded on the chip, the work counts against hand-computed values, and
the table of peaks."""
import gzip
import json
import pathlib

import numpy as np
import pytest

import harness
import peaks
import tracing
import work

DATA = pathlib.Path(__file__).resolve().parent / "data"
TRACE = DATA / "mobilenet_v2.b1.z020.xplane.pb.gz"


def _layers(config: str):
    cfg = json.loads((harness.BENCH_DIR / "configs" / f"{config}.json").read_text())
    return {ly.name: ly for ly in harness.reference_layers(cfg)}


# -- interval arithmetic -----------------------------------------------------


def _union_by_sweep(intervals) -> float:
    """Covered length by counting open intervals at each boundary."""
    edges = sorted([(s, 1) for s, _ in intervals] + [(e, -1) for _, e in intervals])
    covered, depth, last = 0.0, 0, None
    for t, d in edges:
        if depth > 0:
            covered += t - last
        depth += d
        last = t
    return covered


def test_union_overlapping_and_nested():
    iv = [(0, 10), (5, 15), (20, 30), (22, 25), (30, 31)]
    assert tracing.union_ns(iv) == 26
    assert tracing.union_ns([]) == 0


def test_gaps_within_window():
    iv = [(2, 4), (3, 6), (8, 9), (12, 20)]
    assert tracing.gaps(iv, 0, 15) == [(0, 2), (6, 8), (9, 12)]
    assert tracing.gaps([], 1, 3) == [(1, 3)]


def test_gap_goes_to_innermost_open_span():
    spans = [tracing.Interval(0, 100, "bench.window"),
             tracing.Interval(10, 50, "bench.request"),
             tracing.Interval(12, 40, "bench.dispatch"),
             tracing.Interval(40, 50, "bench.wait")]
    got = tracing.attribute([(20, 30), (44, 46), (60, 70), (150, 160)], spans)
    assert got == {"bench.dispatch": [10, 1], "bench.wait": [2, 1],
                   "bench.window": [10, 1], tracing.NO_SPAN: [10, 1]}


def test_kernel_is_told_by_custom_call_target():
    kernel = ('%fused_conv_gemm.1 = f32[3136,256]{1,0} custom-call(s8[1,58,58,64] '
              '%p), custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    other = ('%cc.2 = f32[8]{0} custom-call(f32[8] %p), '
             'custom_call_target="SomethingElse"')
    fusion = ('%fusion.3 = s8[12544,3]{1,0} fusion(s8[230,230,3] %pad.0), '
              'kind=kLoop, calls=%fused_computation.1')
    assert tracing.is_kernel(kernel)
    assert not tracing.is_kernel(other)
    assert not tracing.is_kernel(fusion)
    assert tracing.op_label(kernel) == "fused_conv_gemm f32[3136,256]"
    assert tracing.op_label(fusion) == "fusion s8[12544,3]"
    assert tracing.module_label("jit_f(1881638226656373441)") == "jit_f"


# -- the recorded chip trace ----------------------------------------------------


@pytest.fixture(scope="module")
def profile():
    import jax
    return jax.profiler.ProfileData.from_serialized_xspace(
        gzip.decompress(TRACE.read_bytes()))


def _raw(profile):
    """Window, spans and device ops read straight from the planes."""
    spans = [(ev.start_ns, ev.end_ns, ev.name)
             for plane in profile.planes if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events
             if ev.name.startswith("bench.")]
    (lo, hi), = [(s, e) for s, e, n in spans if n == "bench.window"]
    ops = [(max(ev.start_ns, lo), min(ev.end_ns, hi), ev.name)
           for plane in profile.planes if plane.name == "/device:TPU:0"
           for line in plane.lines if line.name == "XLA Ops"
           for ev in line.events if ev.end_ns > lo and ev.start_ns < hi]
    return lo, hi, spans, ops


def test_trace_summary_matches_the_raw_events(profile):
    lo, hi, spans, ops = _raw(profile)
    s = tracing.summarize(profile)
    kern = [(a, b) for a, b, n in ops if 'custom_call_target="tpu_custom_call"' in n]
    other = [(a, b) for a, b, n in ops if 'custom_call_target="tpu_custom_call"' not in n]
    assert s.window_s == pytest.approx((hi - lo) * 1e-9, rel=1e-12)
    assert s.busy_s == pytest.approx(_union_by_sweep(kern + other) * 1e-9, rel=1e-9)
    assert s.kernel_s == pytest.approx(_union_by_sweep(kern) * 1e-9, rel=1e-9)
    assert s.other_s == pytest.approx(_union_by_sweep(other) * 1e-9, rel=1e-9)
    assert s.kernel_ops == len(kern) > 0
    assert 0 < s.busy_s < s.window_s
    assert s.idle_share == pytest.approx(1 - s.busy_s / s.window_s)
    # every second of the window is busy or in exactly one attributed gap
    idle = sum(v[0] for v in s.gap_seconds.values())
    assert idle + s.busy_s == pytest.approx(s.window_s, rel=1e-9)
    assert set(s.gap_seconds) <= {n for _, _, n in spans} | {tracing.NO_SPAN}
    assert sum(v for _, v in s.top_ops(10)) <= s.busy_s * (1 + 1e-9)
    assert len(s.top_ops(10)) == 10 and len(s.top_gaps(10)) <= 10


def test_each_request_is_traced_once(profile):
    _, _, spans, _ = _raw(profile)
    names = [n for _, _, n in spans]
    n = names.count("bench.request")
    assert n >= 1
    for part in ("bench.transfer", "bench.dispatch", "bench.wait"):
        assert names.count(part) == n


# -- work counts ----------------------------------------------------------------


def test_work_of_a_resnet18_3x3_conv():
    ly = _layers("resnet18")["conv2"]  # 3x3, 64 -> 64, 56x56, stride 1
    w = work.layer_work(ly, n_lut=48, bits_w_lut=4, bits_a=4)
    m, k, n = 56 * 56, 3 * 3 * 64, 64
    assert (m, k, n) == work.gemm_dims(ly)
    assert w.macs == 3136 * 576 * 64 == 115_605_504
    assert w.ops == 231_211_008
    # input 56*56*64 codes at 4 bits + 576*64 weights at 4 bits
    # + 64 fp32 scales + 56*56*64 output codes at 4 bits
    assert w.bytes == 100_352 + 18_432 + 256 + 100_352


def test_work_of_a_mobilenet_v2_depthwise_conv():
    ly = _layers("mobilenet_v2")["b1_dw"]  # 3x3 dw, 96 ch, 112 -> 56
    w = work.layer_work(ly, n_lut=60, bits_w_lut=4, bits_a=4)
    assert work.gemm_dims(ly) == (3136, 9, 96)
    assert w.macs == 3136 * 9 * 96 == 2_709_504
    assert w.ops == 5_419_008
    assert w.bytes == 112 * 112 * 96 / 2 + 9 * 96 / 2 + 96 * 4 + 56 * 56 * 96 / 2


def test_work_of_wider_lut_codes_and_unquantized_logits():
    ly = _layers("resnet18")["fc"]
    w = work.layer_work(ly, n_lut=500, bits_w_lut=8, bits_a=4)
    assert w.bytes == 512 / 2 + 512 * (500 * 8 + 500 * 4) / 8 + 1000 * 4 + 1000 * 4


def test_network_totals():
    macs = {c: sum(work.layer_work(ly, 0, 4, 4).macs for ly in _layers(c).values())
            for c in ("resnet18", "mobilenet_v2")}
    assert macs == {"resnet18": 1_814_073_344, "mobilenet_v2": 300_774_272}


def test_least_time_takes_the_larger_bound():
    p = peaks.peaks_for("TPU v5 lite")
    w = work.LayerWork("x", macs=10, ops=20, bytes=819e9)
    assert work.least_time_s(w, p) == pytest.approx(1.0)
    w = work.LayerWork("y", macs=393e12 / 2, ops=393e12, bytes=1)
    assert work.least_time_s(w, p) == pytest.approx(1.0)


# -- peaks ------------------------------------------------------------------------


def test_peaks_of_tpu_v5e():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p["int8_ops_per_s"], p["bf16_flops_per_s"], p["hbm_bytes_per_s"]) \
        == (393e12, 197e12, 819e9)


def test_unknown_device_kind_is_refused():
    with pytest.raises(peaks.UnknownDeviceError):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(peaks.UnknownDeviceError):
        peaks.peaks_for("cpu")


def test_sweep_helper_agrees_with_numpy():
    rng = np.random.default_rng(0)
    iv = [(float(a), float(a + b)) for a, b in zip(rng.uniform(0, 100, 50),
                                                   rng.uniform(0, 5, 50))]
    grid = np.zeros(106_000, bool)
    for a, b in iv:
        grid[int(round(a * 1000)):int(round(b * 1000))] = True
    assert _union_by_sweep(iv) == pytest.approx(grid.sum() / 1000, abs=0.05)
    assert tracing.union_ns(iv) == pytest.approx(_union_by_sweep(iv), rel=1e-12)
