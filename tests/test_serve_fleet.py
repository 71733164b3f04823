"""Distributed serving fleet: the ISSUE-9 acceptance surface.

  * wire protocol: frame round-trip (every kind), structural-defect
    rejection, deterministic array packing, byte-for-byte
    ``N3HBUND1`` section splitting;
  * per-slot decode: ``step_slots`` is bit-exact vs scalar ``step`` at
    batch 1, and a request admitted mid-flight at a step boundary
    (continuous batching) matches a dedicated batch-1 session;
  * the fleet itself: worker registration + heartbeat, end-to-end
    tokens bit-exact vs the single-process
    ``greedy_generate_compiled`` oracle, overlapped continuous
    admission, per-tenant in-flight and program-cache admission;
  * failure containment: a crashed subprocess worker and a step
    timeout both surface as :class:`RequestFailed` on the request
    futures while the server stays up;
  * 2-worker bundle fleet: the ``*.xdev`` hand-shake over real
    transport is bit-exact vs ``MultiDeviceExecutor.run`` for both
    plan kinds.
"""
import concurrent.futures
import time

import numpy as np
import pytest

from repro.compiler import (
    ExecutionError,
    ExecutorSession,
    GemmLayer,
    MultiDeviceExecutor,
    asm,
    compile_decode_network,
    derive_plan,
    from_bundle_binary,
    lower_partitioned,
    to_bundle_binary,
)
from repro.core.scheduler import (
    XC7Z020,
    DspCoreConfig,
    GemmDims,
    LutCoreConfig,
)
from repro.obs import METRICS
from repro.serve import protocol
from repro.serve.engine import greedy_generate_compiled
from repro.serve.fleet import (
    AdmissionError,
    BundleFleet,
    FleetError,
    FleetServer,
    RequestFailed,
    TenantPolicy,
    _Request,
    _Slot,
)
from repro.serve.protocol import (
    ProtocolError,
    decode_frame,
    encode_frame,
    pack_arrays,
    split_bundle_image,
    unpack_arrays,
)

ARCH = "llama3.2-1b"
MAX_SEQ = 8
SLOTS = 2
SEED = 0

LUT = LutCoreConfig(m=8, n=16, k=128)
DSP = DspCoreConfig(n_reg_row_a=13)
CHAIN = [GemmLayer("fc0", GemmDims(24, 32, 48)),
         GemmLayer("fc1", GemmDims(24, 48, 40)),
         GemmLayer("fc2", GemmDims(24, 40, 36)),
         GemmLayer("fc3", GemmDims(24, 36, 20))]


def _chain_bundle(kind):
    plan = derive_plan(CHAIN, 2, kind)
    return lower_partitioned("toy", CHAIN, plan, LUT, DSP, XC7Z020,
                             bits_w_lut=6, bits_a=4, opt_level=1)


# ---------------------------------------------------------------------------
# Wire protocol
# ---------------------------------------------------------------------------


def test_frame_roundtrip_every_kind():
    for kind in protocol.KINDS:
        hdr = {"seq": 7, "slot": 1, "channel": "L2.xdev"}
        payload = bytes(range(64))
        k, h, p = decode_frame(encode_frame(kind, hdr, payload))
        assert (k, h, p) == (kind, hdr, payload)
    # empty header / payload defaults
    assert decode_frame(encode_frame("ping")) == ("ping", {}, b"")
    # canonical JSON: identical inputs yield identical bytes
    assert (encode_frame("step", {"b": 1, "a": 2})
            == encode_frame("step", {"a": 2, "b": 1}))


def test_frame_rejects_structural_defects():
    with pytest.raises(ProtocolError):
        encode_frame("warp_cores")          # unknown kind
    good = encode_frame("result", {"seq": 1}, b"xyz")
    with pytest.raises(ProtocolError):
        decode_frame(b"NOPE" + good[4:])    # bad magic
    with pytest.raises(ProtocolError):
        decode_frame(good[:8])              # short frame
    with pytest.raises(ProtocolError):
        decode_frame(good + b"\x00")        # trailing bytes
    bad_ver = bytearray(good)
    bad_ver[4] = 99
    with pytest.raises(ProtocolError):
        decode_frame(bytes(bad_ver))        # unsupported version
    bad_kind = bytearray(good)
    bad_kind[5] = 200
    with pytest.raises(ProtocolError):
        decode_frame(bytes(bad_kind))       # unknown kind code


def test_pack_arrays_roundtrip_and_determinism():
    rng = np.random.default_rng(0)
    arrays = {
        "L0.w_lut": rng.integers(-8, 8, (16, 12)).astype(np.int8),
        "L0.s_lut": rng.random(12).astype(np.float32),
        "embed": rng.random((4, 3, 2)),
        "scalar": np.float64(2.5),
        "big_endian": np.arange(5, dtype=">i4"),
    }
    blob = pack_arrays(arrays)
    back = unpack_arrays(blob)
    assert sorted(back) == sorted(arrays)
    for name in arrays:
        np.testing.assert_array_equal(back[name], arrays[name])
    # big-endian inputs are normalized on the wire
    assert back["big_endian"].dtype == np.dtype("<i4")
    # deterministic: dict insertion order never changes the bytes
    reordered = {k: arrays[k] for k in reversed(list(arrays))}
    assert pack_arrays(reordered) == blob


def test_unpack_arrays_rejects_corrupt_payloads():
    blob = pack_arrays({"x": np.arange(4, dtype=np.int32)})
    with pytest.raises(ProtocolError):
        unpack_arrays(blob + b"\x00")       # trailing bytes
    with pytest.raises(ProtocolError):
        unpack_arrays(blob[:-3])            # truncated data
    with pytest.raises(ProtocolError):
        unpack_arrays(b"\xff\xff\xff\xff")  # absurd count, no data


def test_split_bundle_image_sections_byte_exact():
    mdp = _chain_bundle("pipeline")
    image = to_bundle_binary(mdp)
    meta, sections = split_bundle_image(image)
    # sections are the per-device N3HPROG1 images, byte for byte
    assert sections == [asm.to_binary(p) for p in mdp.devices]
    assert meta["bundle"] == mdp.name
    assert len(meta["edges"]) == len(mdp.edges)
    with pytest.raises(ProtocolError):
        split_bundle_image(b"BOGUS123" + image[8:])
    with pytest.raises(ProtocolError):
        split_bundle_image(image + b"\x00")


# ---------------------------------------------------------------------------
# Per-slot decode sessions (the continuous-batching substrate)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def oracle():
    """Single-process batch-1 golden session: the fleet's hard
    bit-exactness reference."""
    prog = compile_decode_network(ARCH, batch=1, max_seq=MAX_SEQ,
                                  opt_level=1)
    session = ExecutorSession(prog, backend="golden")
    session.bind_synthetic_all(seed=SEED)
    return prog, session


def _oracle_tokens(session, prompt, n_new):
    row = greedy_generate_compiled(
        session, np.asarray(prompt, np.int32)[None, :], n_new)
    return np.asarray(row)[0]


def test_step_slots_matches_scalar_step_at_batch1(oracle):
    prog, _ = oracle
    scalar = ExecutorSession(prog, backend="golden")
    scalar.bind_synthetic_all(seed=SEED)
    scalar.reset()
    slots = ExecutorSession(prog, backend="golden")
    slots.bind_synthetic_all(seed=SEED)
    slots.reset(per_slot=True)
    for pos, tok in enumerate([3, 7, 11, 2]):
        ref = np.asarray(scalar.step(tok, pos))
        got = np.asarray(slots.step_slots([tok], [pos]))
        np.testing.assert_array_equal(got, ref)
    # scalar step() is refused on a per-slot session
    with pytest.raises(ExecutionError):
        slots.step(0, 0)
    # and reset_slot is refused outside per-slot mode
    with pytest.raises(ExecutionError):
        scalar.reset_slot(0)


def _mk_slot(prompt, n_new):
    return _Slot(_Request(0, "t", np.asarray(prompt, np.int32), n_new,
                          concurrent.futures.Future(), 0.0))


def test_staggered_admission_is_bit_exact(oracle, fleet):
    """Admit request B into slot 1 at a step boundary while request A
    is mid-flight on slot 0 — both token rows must match dedicated
    batch-1 sessions (the continuous-batching correctness gate)."""
    from repro.launch.serve import compiled_program_image
    prog = asm.from_binary(compiled_program_image(fleet.key))
    sess = ExecutorSession(prog, backend="golden")
    sess.bind_synthetic_all(seed=SEED)
    sess.reset(per_slot=True)
    a = _mk_slot([5, 9], 3)
    b = None
    for step in range(4 + 3):               # a: 4 steps, b: 3, staggered by 2
        if step == 2:
            sess.reset_slot(1)
            b = _mk_slot([7, 3], 2)
        toks = [a.next_token() if not a.done else 0,
                b.next_token() if b and not b.done else 0]
        pos = [a.pos, b.pos if b else 0]
        logits = np.asarray(sess.step_slots(toks, pos))
        if not a.done:
            a.advance(int(np.argmax(logits[0])))
        if b is not None and not b.done:
            b.advance(int(np.argmax(logits[1])))
    _, osess = oracle
    np.testing.assert_array_equal(
        np.asarray(a.out), _oracle_tokens(osess, [5, 9], 3)[2:])
    np.testing.assert_array_equal(
        np.asarray(b.out), _oracle_tokens(osess, [7, 3], 2)[2:])


# ---------------------------------------------------------------------------
# FleetServer end to end
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fleet():
    server = FleetServer(
        ARCH,
        [("w0", "golden", "thread"), ("w1", "golden", "thread")],
        batch_slots=SLOTS, max_seq=MAX_SEQ, seed=SEED,
        tenants={"small": TenantPolicy(max_inflight=1, max_programs=1)})
    with server as f:
        yield f


def test_worker_registration_and_heartbeat(fleet):
    assert fleet.live_workers() == ["w0", "w1"]
    assert fleet.ping("w0") >= 0.0
    assert fleet.ping("w1") >= 0.0
    assert METRICS.counter("serve.fleet.workers.registered") >= 2
    with pytest.raises(RequestFailed):
        fleet.ping("w99")


def test_fleet_tokens_bit_exact_vs_single_process(fleet, oracle):
    _, osess = oracle
    reqs = [([5], 2), ([3, 11], 3), ([1, 2, 3], 4), ([9, 8], 2)]
    futs = [fleet.submit(p, n) for p, n in reqs]
    for (p, n), fut in zip(reqs, futs):
        np.testing.assert_array_equal(
            np.asarray(fut.result(600)), _oracle_tokens(osess, p, n))


def test_continuous_admission_overlaps_requests(fleet):
    steps0 = METRICS.counter("serve.fleet.steps")
    admitted0 = METRICS.counter("serve.fleet.admitted")
    reqs = [([2, 4], 3)] * 4                # 4 steps each served alone
    futs = [fleet.submit(p, n) for p, n in reqs]
    for fut in futs:
        fut.result(600)
    assert METRICS.counter("serve.fleet.admitted") - admitted0 == 4
    # batching: strictly fewer fleet steps than 4 back-to-back solo
    # requests would take (4 requests x 4 steps)
    assert METRICS.counter("serve.fleet.steps") - steps0 < 16


def test_submit_validates_request_shape(fleet):
    with pytest.raises(ValueError):
        fleet.submit([], 2)                 # empty prompt
    with pytest.raises(ValueError):
        fleet.submit([1, 2], 0)             # no new tokens
    with pytest.raises(ValueError):
        fleet.submit([1] * MAX_SEQ, 1)      # exceeds the cache window


def test_tenant_inflight_admission(fleet):
    fut = fleet.submit([1, 2], 5, tenant="small")
    with pytest.raises(AdmissionError):     # budget: 1 in flight
        fleet.submit([1], 1, tenant="small")
    assert np.asarray(fut.result(600)).shape == (7,)
    # completing the request releases the budget
    fleet.submit([1], 1, tenant="small").result(600)


def test_tenant_program_admission(fleet):
    rejected0 = METRICS.counter("serve.fleet.admission.rejected")
    # re-admitting an already-pinned program is free
    fleet.admit_program("small", fleet.key)
    with pytest.raises(AdmissionError):     # budget: 1 distinct program
        fleet.admit_program("small", ("decode", "other-arch", 4, 4))
    assert (METRICS.counter("serve.fleet.admission.rejected")
            > rejected0)


# ---------------------------------------------------------------------------
# Failure containment
# ---------------------------------------------------------------------------


def _wait_until(predicate, timeout_s=10.0):
    deadline = time.perf_counter() + timeout_s
    while time.perf_counter() < deadline:
        if predicate():
            return True
        time.sleep(0.05)
    return predicate()


def test_worker_crash_fails_request_server_stays_up():
    server = FleetServer(ARCH, [("w0", "golden", "subprocess")],
                         batch_slots=SLOTS, max_seq=MAX_SEQ, seed=SEED)
    with server:
        fut = server.submit([1, 2, 3], 4)
        time.sleep(2.0)                     # let the worker admit it
        server.processes["w0"].kill()
        with pytest.raises(RequestFailed):
            fut.result(120)
        # the server survives the crash: loop thread still running,
        # the dead worker dropped from the roster
        assert server._thread.is_alive()
        assert _wait_until(lambda: server.live_workers() == [])
        with pytest.raises(RequestFailed):
            server.submit([1], 1)           # no live workers left


@pytest.mark.parametrize("make", [
    lambda: FleetServer(ARCH, [("w0", "pallas", "subprocess")],
                        batch_slots=SLOTS, max_seq=MAX_SEQ, seed=SEED),
    lambda: BundleFleet(to_bundle_binary(_chain_bundle("filter")),
                        worker_mode="subprocess"),
], ids=["FleetServer", "BundleFleet"])
def test_subprocess_workers_refused_when_parent_holds_tpu(make,
                                                          monkeypatch):
    """One process per chip: with a TPU as the default backend, a
    subprocess worker is refused up front instead of timing out."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(FleetError, match="one process per chip"):
        make()


def test_step_timeout_fails_request_server_stays_up():
    server = FleetServer(ARCH, [("w0", "golden", "thread")],
                         batch_slots=SLOTS, max_seq=MAX_SEQ, seed=SEED,
                         step_timeout_s=0.001)
    with server:
        fut = server.submit([1, 2], 3)
        with pytest.raises(RequestFailed):
            fut.result(120)
        assert server._thread.is_alive()
        assert _wait_until(lambda: server.live_workers() == [])
        with pytest.raises(RequestFailed):
            server.submit([1], 1)


# ---------------------------------------------------------------------------
# Bundle fleet: xdev hand-shake over real transport
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["pipeline", "filter"])
def test_bundle_fleet_bit_exact_vs_in_process(kind):
    mdp = _chain_bundle(kind)
    image = to_bundle_binary(mdp)
    mex = MultiDeviceExecutor(from_bundle_binary(image), backend="golden")
    for gi in range(mdp.n_layers):
        mex.bind_synthetic(gi)
    x = np.random.default_rng(0).integers(-8, 8, (24, 32)).astype(np.int8)
    ref = np.asarray(mex.run(x))
    with BundleFleet(image, seed=None) as bf:
        assert len(bf.sections) == 2
        got = np.asarray(bf.run(x))
    np.testing.assert_array_equal(got, ref)
