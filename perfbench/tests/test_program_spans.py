"""The program's host spans and role-named executables in a trace:
``program_spans.py`` (self time, the host's split of ``ex.run`` by step,
idle gaps by the innermost span) and the per-layer readers that rest on
the ``jit_n3h_*`` names, on synthetic intervals and on two traces
recorded on the chip: the mobilenet_v2 one from before the program had
spans or role names, and a resnet18 one with both."""
import collections
import gzip
import pathlib
import types

import pytest

import harness
import program_spans
import tracing

DATA = pathlib.Path(__file__).resolve().parent / "data"
OLD_TRACE = DATA / "mobilenet_v2.b1.z020.xplane.pb.gz"
NEW_TRACE = DATA / "resnet18.b1.z020.xplane.pb.gz"
READERS = ("fallback.device_ms_per_image", "glue.device_ms_per_image")


def _load(path):
    import jax
    return jax.profiler.ProfileData.from_serialized_xspace(
        gzip.decompress(path.read_bytes()))


@pytest.fixture(scope="module")
def old():
    return _load(OLD_TRACE)


@pytest.fixture(scope="module")
def new():
    return _load(NEW_TRACE)


def _ctx(pd, images):
    return types.SimpleNamespace(images=images, trace=tracing.summarize(pd))


# -- self time on synthetic intervals -------------------------------------------


def test_self_time_is_duration_less_nested_spans():
    iv = tracing.Interval
    spans = [iv(0, 100, "n3h.run"),
             iv(10, 60, "n3h.layer"), iv(10, 20, "n3h.layer.glue"),
             iv(25, 55, "n3h.layer.run"), iv(30, 40, "n3h.layer.launch"),
             iv(55, 60, "n3h.layer.tail"),
             iv(60, 95, "n3h.layer"), iv(62, 90, "n3h.layer.run")]
    got = program_spans.span_times(spans)
    assert got == {"n3h.run": [100, 15, 1],
                   "n3h.layer": [85, 5 + 7, 2],
                   "n3h.layer.glue": [10, 10, 1],
                   "n3h.layer.run": [58, 20 + 28, 2],
                   "n3h.layer.launch": [10, 10, 1],
                   "n3h.layer.tail": [5, 5, 1]}
    # the four host steps add up to the outermost span
    steps = sum(got[k][col] for labels, col in program_spans.STEPS.values()
                for k in labels)
    assert steps == got["n3h.run"][0]


def test_self_time_takes_the_union_of_overlapping_children():
    iv = tracing.Interval
    got = program_spans.span_times([iv(0, 10, "p"), iv(1, 5, "a"),
                                    iv(3, 7, "b"), iv(12, 14, "p")])
    assert got == {"p": [12, 4 + 2, 2], "a": [4, 4, 1], "b": [4, 4, 1]}


# -- a trace from before the program had spans or role names --------------------


def test_without_program_spans_the_split_is_the_accepted_reduction(old):
    s = tracing.summarize(old)
    res = program_spans.split(old)
    assert res["window_s"] == s.window_s
    assert res["images"] == 2
    assert res["span_seconds"] == {}
    assert set(res["steps_s"].values()) == {0}
    assert res["idle_seconds"].keys() == s.gap_seconds.keys() \
        == {"bench.dispatch", "bench.transfer"}
    for label, (sec, n) in s.gap_seconds.items():
        assert res["idle_seconds"][label][1] == n
        assert res["idle_seconds"][label][0] == pytest.approx(sec, rel=1e-12)
    assert program_spans.per_image_ms(res)["dispatch_ms"] == \
        pytest.approx(75.314238, rel=1e-9)


@pytest.mark.parametrize("name", READERS)
def test_new_readers_read_nothing_without_role_names(old, name):
    assert harness.metric_reader(name)(_ctx(old, 2)) is None


# -- a trace with both, recorded on the chip ----------------------------------


def _raw_modules(pd):
    """Executable -> device seconds of its ops, straight from the planes
    (an op belongs to the ``XLA Modules`` event it starts in)."""
    spans = program_spans.host_spans(pd)
    (lo, hi), = [(sp.start, sp.end) for sp in spans
                 if sp.label == "bench.window"]
    out = collections.Counter()
    for plane in pd.planes:
        if plane.name != "/device:TPU:0":
            continue
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        mods = [(ev.start_ns, ev.end_ns, ev.name.split("(")[0])
                for ev in lines["XLA Modules"]]
        for ev in lines["XLA Ops"]:
            s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
            if e <= s:
                continue
            owner = [m for m in mods if m[0] <= s <= m[1]]
            out[owner[-1][2] if owner else "?"] += (e - s) * 1e-9
    return out


def test_new_readers_agree_with_the_raw_events(new):
    res = program_spans.split(new)
    mods = _raw_modules(new)
    ctx = _ctx(new, res["images"])
    fallback = sum(v for m, v in mods.items() if m.startswith("jit_n3h_")
                   and m.endswith(("_xla_vmem", "_xla_depthwise")))
    glue = sum(v for m, v in mods.items() if not m.startswith("jit_n3h_"))
    assert fallback > 0 and glue > 0
    assert harness.metric_reader(READERS[0])(ctx) == \
        pytest.approx(1e3 * fallback / res["images"], rel=1e-6)
    assert harness.metric_reader(READERS[1])(ctx) == \
        pytest.approx(1e3 * glue / res["images"], rel=1e-6)
    # resnet18's one fallback is conv1, over the VMEM budget
    assert {m for m in mods if m.endswith("_xla_vmem")} == \
        {"jit_n3h_conv_xla_vmem"}
    assert not any(m == "jit_f" for m in mods)


def test_host_steps_cover_the_dispatch(new):
    res = program_spans.split(new)
    ms = program_spans.per_image_ms(res)
    assert res["images"] >= 2
    assert 0.95 * ms["dispatch_ms"] <= ms["n3h_ms"] <= ms["dispatch_ms"]
    assert ms["n3h_ms"] == pytest.approx(
        1e3 * res["span_seconds"]["n3h.run"][0] / res["images"], rel=1e-9)
    counts = {k: v[2] for k, v in res["span_seconds"].items()}
    assert counts["n3h.run"] == res["images"]
    assert counts["n3h.layer"] == counts["n3h.layer.run"] == \
        counts["n3h.layer.tail"] == counts["n3h.layer.launch"] == \
        21 * res["images"]


def test_idle_gaps_name_the_program_step(new):
    res = program_spans.split(new)
    idle = res["idle_seconds"]
    assert any(k.startswith("n3h.") for k in idle)
    left = sum(v[0] for k, v in idle.items() if k.startswith("bench."))
    assert left < 0.05 * res["window_s"]
    s = tracing.summarize(new)
    assert sum(v[0] for v in idle.values()) == \
        pytest.approx(s.window_s - s.busy_s, rel=1e-9)
