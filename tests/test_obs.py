"""Observability layer: cycle-accounting closure, trace format,
determinism, null-tracer fast path, metrics round-trips.

The load-bearing contract: for every core track of a traced program,
busy + sync + stall + idle cycles sum *exactly* to the makespan
``simulate_program`` reports — the trace decomposes the existing
number, it is not a second opinion. Checked on single-device programs
and on 2-device pipeline/filter bundles, at -O0 and -O1.
"""
import json

import pytest

from repro.compiler import compile_network
from repro.core.scheduler import simulate_program
from repro.obs import (
    METRICS,
    MetricsRegistry,
    NULL_TRACER,
    Tracer,
    profile_report,
    validate_chrome_trace,
)

NET = "llama3.2-1b"
SEQ = 16


@pytest.fixture(scope="module")
def single_prog():
    return compile_network(NET, seq_len=SEQ)


@pytest.fixture(scope="module", params=["pipeline", "filter"])
def bundle(request):
    return compile_network(NET, seq_len=SEQ, devices=2,
                           partition=request.param)


# ---------------------------------------------------------------------------
# cycle-accounting closure
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("opt", [0, 1])
def test_closure_single_device(single_prog, opt):
    tracer = Tracer()
    ps = simulate_program(single_prog, opt_level=opt, tracer=tracer)
    c = tracer.counters
    assert c.makespan == ps.total_cycles
    assert c.closure_errors() == []
    # 2 cores x 3 engines on one device
    assert len(c.tracks) == 6
    for tc in c.tracks.values():
        assert tc.busy + tc.sync + tc.stall + tc.idle == ps.total_cycles


@pytest.mark.parametrize("opt", [0, 1])
def test_closure_bundle(bundle, opt):
    tracer = Tracer()
    bs = simulate_program(bundle, opt_level=opt, batches=1, tracer=tracer)
    c = tracer.counters
    # batches=1: one traversal, latency == total makespan
    assert bs.total_cycles == bs.latency_cycles
    assert c.makespan == bs.total_cycles
    assert c.closure_errors() == []
    assert len(c.tracks) == 12          # 2 devices x 2 cores x 3 engines


def test_tracing_does_not_change_makespan(single_prog, bundle):
    for prog in (single_prog, bundle):
        plain = simulate_program(prog, opt_level=1)
        traced = simulate_program(prog, opt_level=1, tracer=Tracer())
        assert traced.total_cycles == plain.total_cycles


def test_closure_is_a_real_check(single_prog):
    # corrupting any one term must break closure — guards against the
    # decomposition degenerating into makespan-minus-the-rest
    tracer = Tracer()
    simulate_program(single_prog, tracer=tracer)
    tc = next(iter(tracer.counters.tracks.values()))
    tc.idle += 1
    assert tracer.counters.closure_errors() != []


def test_closure_covers_elementwise_stage():
    """Conv chains carry stage-6 fused-tail fetch/result records; their
    cycles must be inside the accounting (closure holds with the tail
    present), and corrupting an elementwise-bearing track's busy span
    must break closure."""
    from repro.compiler.lower import EW_STAGE
    prog = compile_network("resnet18", in_hw=28, width=0.25)
    assert any(lp.elementwise for lp in prog.layers)
    tracer = Tracer()
    ps = simulate_program(prog, tracer=tracer)
    c = tracer.counters
    assert c.makespan == ps.total_cycles
    assert c.closure_errors() == []
    # busy cycles of the stage-6 records are nonzero, so a corrupted
    # tail span cannot hide in the idle remainder
    lp = next(lp for lp in prog.layers if lp.elementwise)
    cp = lp.lut if lp.lut is not None else lp.dsp
    ew_cycles = sum(op.cycles for s in ("fetch", "result")
                    for op in cp.streams[s]
                    if getattr(op.instr, "stage_ctrl", None) == EW_STAGE)
    assert ew_cycles > 0
    track = f"dev0:{'lut' if cp is lp.lut else 'dsp'}/result"
    tc = c.tracks[track] if track in c.tracks else \
        next(iter(c.tracks.values()))
    tc.busy += ew_cycles
    assert c.closure_errors() != []


# ---------------------------------------------------------------------------
# trace JSON: schema + determinism
# ---------------------------------------------------------------------------


def test_trace_schema_valid(single_prog):
    tracer = Tracer()
    simulate_program(single_prog, tracer=tracer)
    obj = json.loads(tracer.to_json())
    assert validate_chrome_trace(obj) == []
    events = obj["traceEvents"]
    # per-instruction complete events on core/engine tracks
    cats = {e.get("cat") for e in events if e["ph"] == "X"}
    assert {"busy", "sync"} <= cats
    names = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert any(n.startswith("dev0:") for n in names)
    assert "lut/execute" in names and "dsp/fetch" in names
    # accounting summary rides in the file
    counters = obj["otherData"]["counters"]
    assert counters["closure_errors"] == []
    assert counters["makespan_cycles"] > 0


def test_bundle_trace_has_link_track(bundle):
    tracer = Tracer()
    simulate_program(bundle, batches=1, tracer=tracer)
    obj = tracer.to_chrome()
    assert validate_chrome_trace(obj) == []
    pids = {e["pid"] for e in obj["traceEvents"]}
    assert {0, 1} <= pids
    if bundle.plan.kind == "pipeline":
        link_events = [e for e in obj["traceEvents"]
                       if e.get("cat") == "link"]
        assert link_events
        assert all(e["args"]["nbytes"] > 0 for e in link_events)


def test_trace_deterministic(single_prog, bundle):
    for prog in (single_prog, bundle):
        blobs = []
        for _ in range(2):
            tracer = Tracer()
            simulate_program(prog, opt_level=1, tracer=tracer)
            blobs.append(tracer.to_json())
        assert blobs[0] == blobs[1]     # byte-identical


def test_validate_rejects_malformed():
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({}) != []
    assert validate_chrome_trace(
        {"traceEvents": [{"ph": "X", "pid": 0, "tid": 0, "name": "x",
                          "ts": -1, "dur": 2}]}) != []
    assert validate_chrome_trace(
        {"traceEvents": [{"ph": "B", "pid": 0, "tid": 0, "name": "x"}]}
    ) != []


# ---------------------------------------------------------------------------
# null tracer / profile report
# ---------------------------------------------------------------------------


def test_null_tracer_is_noop(single_prog):
    assert NULL_TRACER.enabled is False
    # every hook swallows
    NULL_TRACER.record_layer(0, 0, "x", 0, 1, {})
    NULL_TRACER.set_makespan(5)
    NULL_TRACER.finalize()
    # simulate_program treats it as tracing-off (same result object)
    ps = simulate_program(single_prog, tracer=NULL_TRACER)
    assert ps.total_cycles == simulate_program(single_prog).total_cycles


def test_profile_report_renders(single_prog):
    tracer = Tracer()
    simulate_program(single_prog, tracer=tracer)
    text = profile_report(tracer)
    assert "cycle accounting: closed" in text
    assert "dev0 lut/execute" in text
    assert "top stall causes" in text
    assert profile_report(NULL_TRACER).startswith("profile: no trace data")


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_metrics_json_roundtrip():
    reg = MetricsRegistry()
    reg.incr("x.count", 2)
    reg.incr("x.count")
    reg.gauge("x.gauge", 1.25)
    for v in (1.0, 3.0, 2.0):
        reg.observe("x.lat_ms", v)
    back = MetricsRegistry.from_json(reg.to_json())
    assert back.snapshot() == reg.snapshot()
    snap = back.snapshot()
    assert snap["counters"]["x.count"] == 3
    assert snap["observations"]["x.lat_ms"]["count"] == 3
    assert snap["observations"]["x.lat_ms"]["mean"] == 2.0


def test_metrics_csv_export(tmp_path):
    reg = MetricsRegistry()
    reg.incr("a.hits")
    reg.observe("b.ms", 4.0)
    path = tmp_path / "m.csv"
    reg.save(str(path))
    lines = path.read_text().splitlines()
    assert lines[0] == "kind,name,field,value"
    assert "counter,a.hits,value,1" in lines
    assert "observation,b.ms,mean,4.0" in lines


def test_serve_program_cache_metrics():
    from repro.launch.serve import ProgramCache, ProgramKey
    METRICS.clear()
    cache = ProgramCache()
    key = ProgramKey(arch=NET, seq_len=SEQ)
    img1 = cache.get(key)
    img2 = cache.get(key)
    assert img1 == img2
    assert METRICS.counter("serve.program_cache.miss") == 1
    assert METRICS.counter("serve.program_cache.hit") == 1
    assert METRICS.snapshot()["observations"][
        "serve.program_cache.compile_ms"]["count"] == 1


def test_dse_search_metrics():
    from repro.core.workloads import resnet18_specs
    from repro.dse.search import run_search
    res = run_search(specs=resnet18_specs()[:4], episodes=3, seed=0)
    m = res.metrics
    assert m is not None
    assert m["counters"]["dse.episodes"] == 3
    assert m["observations"]["dse.episode.reward"]["count"] == 3
    assert "dse.best_reward" in m["gauges"]
    # the snapshot itself round-trips through the registry export
    back = MetricsRegistry.from_json(json.dumps(m))
    assert back.snapshot() == m
