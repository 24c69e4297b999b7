"""Network transport: wire framing, error mapping, the PredictionServer /
RemoteReplica pair, and the cross-process acceptance bar — a ReplicaPool
holding one in-process and one RemoteReplica (loopback subprocess) answers
EVERY request through a server kill + restart, with remote predictions
matching in-process results to <=1e-6."""
import json
import socket
import struct
import subprocess
import sys
import threading
import time
import zlib
from pathlib import Path

import numpy as np
import pytest
from _prop import given, settings, st

from repro.cluster import (PROTOCOL_VERSION, ClusterFrontend,
                           DeadlineExceeded, FrontendRejected,
                           PredictionServer, ProtocolError, RemoteError,
                           RemoteReplica, ReplicaPool, TransportError)
from repro.cluster.remote import demo_estimator, spawn_demo_server
from repro.cluster.transport import (decode_error, encode_error, recv_frame,
                                     request_id, send_frame)
from repro.core.scheduler import (PRIORITY_BACKGROUND, DevicePredictor,
                                  schedule, slack_priority)
from repro.serve import ForestEngine
from repro.serve.backend import ServingEngine, supports_deadline

N_F = 6


@pytest.fixture(scope="module")
def fitted():
    # keep every arg at the CLI server's defaults except seed/trees (which
    # _spawn_server forwards): the subprocess must fit the IDENTICAL model
    est = demo_estimator(seed=3, n_features=N_F, n_trees=12)
    rng = np.random.default_rng(7)
    X = rng.lognormal(1.0, 1.5, size=(64, N_F)).astype(np.float32)
    return est, X


class GatedEngine:
    """Engine whose predict blocks until released — deterministic in-flight
    state for drain/kill tests."""

    def __init__(self):
        self.n_features = N_F
        self.gate = threading.Event()
        self.calls = 0

    def predict(self, X):
        self.calls += 1
        if not self.gate.wait(timeout=30):
            raise RuntimeError("gate never released")
        X = np.atleast_2d(np.asarray(X))
        return X[:, 0].astype(np.float64)

    def swap_estimator(self, est):
        return 0

    def close(self):
        self.gate.set()


def _frontend(engine, **kw):
    pool = ReplicaPool({"r0": engine}, check_interval_s=60.0)
    kw.setdefault("max_queue", 64)
    return ClusterFrontend(pool, auto_start=False, **kw)


# ------------------------------------------------------------------ framing

def test_frame_roundtrip_and_clean_eof():
    a, b = socket.socketpair()
    with a, b:
        frame = {"v": PROTOCOL_VERSION, "id": request_id(), "op": "ping",
                 "x": [[1.5, -2.0]], "nested": {"deep": [1, 2, 3]}}
        send_frame(a, frame)
        assert recv_frame(b) == frame
        a.close()
        assert recv_frame(b) is None           # EOF at a frame boundary


def test_torn_length_prefix_raises_retryable():
    a, b = socket.socketpair()
    with a, b:
        a.sendall(b"\x00\x00")                 # 2 of 4 prefix bytes
        a.close()
        with pytest.raises(TransportError, match="length prefix") as ei:
            recv_frame(b)
        assert ei.value.retryable


def test_truncated_body_raises_retryable():
    a, b = socket.socketpair()
    with a, b:
        a.sendall(struct.pack(">I", 100) + b'{"v": 1')   # 8 of 100 bytes
        a.close()
        with pytest.raises(TransportError, match="frame body"):
            recv_frame(b)


def _raw_frame(body: bytes) -> bytes:
    """Hand-rolled frame with a CORRECT header for an arbitrary body —
    lets tests drive invalid JSON through a valid envelope."""
    return struct.pack(">I", len(body)) + struct.pack(
        ">I", zlib.crc32(body)) + body


def test_oversized_and_malformed_frames_are_protocol_errors():
    a, b = socket.socketpair()
    with a, b:
        # the length is validated BEFORE the checksum/body are awaited:
        # no further bytes exist, yet this must not block
        a.sendall(struct.pack(">I", (16 << 20) + 1))
        with pytest.raises(ProtocolError, match="exceeds"):
            recv_frame(b)
    a, b = socket.socketpair()
    with a, b:
        a.sendall(_raw_frame(b"not-json"))
        with pytest.raises(ProtocolError, match="not JSON"):
            recv_frame(b)
    a, b = socket.socketpair()
    with a, b:
        a.sendall(_raw_frame(b"[1,2,3]"))        # array, not object
        with pytest.raises(ProtocolError, match="expected object"):
            recv_frame(b)


def test_checksum_mismatch_is_retryable():
    a, b = socket.socketpair()
    with a, b:
        body = b'{"v": 2, "op": "ping"}'
        a.sendall(struct.pack(">I", len(body))
                  + struct.pack(">I", zlib.crc32(body) ^ 0x1)   # wrong CRC
                  + body)
        with pytest.raises(TransportError, match="checksum") as ei:
            recv_frame(b)
        assert ei.value.retryable


# ------------------------------------------------- codec property tests
#
# The decoder's contract under arbitrary damage: a frame either decodes to
# EXACTLY what was sent, or raises the documented taxonomy (TransportError
# for torn/corrupted streams, ProtocolError for protocol violations) —
# never an unhandled exception, never a silent wrong payload, never a hang
# (every case below closes the writer, so a decoder waiting for bytes that
# cannot arrive would fail the read loop, not block the suite).

def _arbitrary_payload(rng, depth: int = 0):
    """Seed-driven arbitrary JSON value (no NaN/inf: equality must hold)."""
    kinds = ["int", "float", "str", "bool", "null"]
    if depth < 2:
        kinds += ["list", "dict"]
    kind = kinds[int(rng.integers(len(kinds)))]
    if kind == "int":
        return int(rng.integers(-2**53, 2**53))
    if kind == "float":
        return float(np.round(rng.normal() * 10.0**int(rng.integers(-6, 7)),
                              12))
    if kind == "str":
        n = int(rng.integers(0, 12))
        cps = rng.integers(1, 0xD7FF, size=n)    # valid non-surrogate BMP
        return "".join(chr(int(c)) for c in cps)
    if kind == "bool":
        return bool(rng.integers(0, 2))
    if kind == "null":
        return None
    if kind == "list":
        return [_arbitrary_payload(rng, depth + 1)
                for _ in range(int(rng.integers(0, 5)))]
    return {f"k{i}": _arbitrary_payload(rng, depth + 1)
            for i in range(int(rng.integers(0, 5)))}


def _payload_frame(seed: int) -> tuple[dict, bytes]:
    rng = np.random.default_rng(seed)
    obj = {"v": PROTOCOL_VERSION, "id": f"prop-{seed}",
           "payload": _arbitrary_payload(rng)}
    body = json.dumps(obj, separators=(",", ":")).encode("utf-8")
    return obj, _raw_frame(body)


@settings(max_examples=25)
@given(st.integers(0, 2**31 - 1))
def test_prop_frame_roundtrip_is_identity(seed):
    obj, _raw = _payload_frame(seed)
    a, b = socket.socketpair()
    with a, b:
        send_frame(a, obj)
        send_frame(a, obj)                       # frames are self-delimiting
        a.close()
        assert recv_frame(b) == obj
        assert recv_frame(b) == obj
        assert recv_frame(b) is None


@settings(max_examples=25)
@given(st.integers(0, 2**31 - 1))
def test_prop_truncated_stream_raises_never_hangs(seed):
    obj, raw = _payload_frame(seed)
    rng = np.random.default_rng(seed ^ 0x5EED)
    cut = int(rng.integers(0, len(raw)))         # 0 = clean EOF
    a, b = socket.socketpair()
    with a, b:
        a.sendall(raw[:cut])
        a.close()                                # no more bytes will come
        if cut == 0:
            assert recv_frame(b) is None
        else:
            with pytest.raises(TransportError) as ei:
                recv_frame(b)
            assert ei.value.retryable


@settings(max_examples=40)
@given(st.integers(0, 2**31 - 1))
def test_prop_bit_flip_always_detected(seed):
    """Any single flipped bit — header length, checksum, or body — raises
    the documented taxonomy; it can never decode to a DIFFERENT payload
    (CRC32 detects all single-bit errors) and never blocks (the writer is
    closed, so a decoder awaiting phantom bytes sees EOF)."""
    obj, raw = _payload_frame(seed)
    rng = np.random.default_rng(seed ^ 0xF11B)
    pos = int(rng.integers(0, len(raw)))
    bit = int(rng.integers(0, 8))
    fuzzed = bytearray(raw)
    fuzzed[pos] ^= 1 << bit
    a, b = socket.socketpair()
    with a, b:
        a.sendall(bytes(fuzzed))
        a.close()
        with pytest.raises((TransportError, ProtocolError)):
            recv_frame(b)


@settings(max_examples=25)
@given(st.integers(0, 2**31 - 1))
def test_prop_garbage_stream_raises_never_hangs(seed):
    """A peer speaking a different protocol entirely (random bytes, HTTP,
    TLS hellos) must be rejected, not crash the handler thread."""
    rng = np.random.default_rng(seed ^ 0x6A55)
    n = int(rng.integers(1, 64))
    raw = rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
    a, b = socket.socketpair()
    with a, b:
        a.sendall(raw)
        a.close()
        try:
            out = recv_frame(b)
        except (TransportError, ProtocolError):
            return
        # astronomically unlikely: random bytes formed a whole valid frame
        assert out is None or isinstance(out, dict)


def test_error_mapping_roundtrip():
    rej = decode_error(encode_error(FrontendRejected(0.25)))
    assert isinstance(rej, FrontendRejected)
    assert rej.retry_after_s == pytest.approx(0.25)
    assert isinstance(decode_error(encode_error(DeadlineExceeded("late"))),
                      DeadlineExceeded)
    assert isinstance(decode_error({"type": "ProtocolMismatch",
                                    "message": "v9"}), ProtocolError)
    unavailable = decode_error({"type": "Unavailable", "message": "drain"})
    assert isinstance(unavailable, TransportError) and unavailable.retryable
    leftover = decode_error({"type": "SomethingNew", "message": "boom"})
    assert isinstance(leftover, RemoteError) and not leftover.retryable
    internal = encode_error(ValueError("bad"))
    assert internal["type"] == "Internal" and "bad" in internal["message"]


# ----------------------------------------------------------- server + client

def test_remote_predictions_match_in_process(fitted):
    est, X = fitted
    twin = ForestEngine(est, backend="flat-numpy", cache_size=0)
    fe = _frontend(ForestEngine(est, backend="flat-numpy", cache_size=0))
    with PredictionServer(fe, port=0) as server:
        with RemoteReplica(server.address, timeout_s=10.0) as replica:
            got = replica.predict(X)
            np.testing.assert_allclose(got, twin.predict(X), rtol=0,
                                       atol=1e-6)
            assert replica.n_features == N_F   # filled by the hello
            assert replica.stats.connects == 1
            assert replica.stats.rows == X.shape[0]
            info = replica.info()
            assert info["server_version"] == PROTOCOL_VERSION
            assert info["healthy"] == ["r0"]
    twin.close()


def test_version_mismatch_is_rejected_with_both_versions(fitted):
    est, _ = fitted
    fe = _frontend(ForestEngine(est, backend="flat-numpy", cache_size=0))
    with PredictionServer(fe, port=0) as server:
        with socket.create_connection(server.address, timeout=5) as sock:
            send_frame(sock, {"v": 999, "id": "q-1", "op": "ping"})
            resp = recv_frame(sock)
            assert resp["ok"] is False
            assert resp["error"]["type"] == "ProtocolMismatch"
            assert "v999" in resp["error"]["message"]
            assert resp["error"]["server_version"] == PROTOCOL_VERSION
            assert isinstance(decode_error(resp["error"]), ProtocolError)
            # the server hangs up on a mismatched peer
            assert recv_frame(sock) is None


def test_unknown_op_is_bad_request(fitted):
    est, _ = fitted
    fe = _frontend(ForestEngine(est, backend="flat-numpy", cache_size=0))
    with PredictionServer(fe, port=0) as server:
        with socket.create_connection(server.address, timeout=5) as sock:
            send_frame(sock, {"v": PROTOCOL_VERSION, "id": "q-2",
                              "op": "frobnicate"})
            resp = recv_frame(sock)
            assert resp["error"]["type"] == "BadRequest"
            assert resp["id"] == "q-2"


def test_malformed_predict_fields_are_bad_requests(fitted):
    """Peer-controlled frame fields are validated BEFORE touching shared
    frontend state: a non-int priority must never reach the admission heap
    (one poisoned entry would crash every later heap comparison)."""
    est, X = fitted
    fe = _frontend(ForestEngine(est, backend="flat-numpy", cache_size=0))
    with PredictionServer(fe, port=0) as server:
        with socket.create_connection(server.address, timeout=5) as sock:
            for bad in ({"op": "predict", "x": X[0].tolist(),
                         "priority": "0"},
                        {"op": "predict", "x": X[0].tolist(),
                         "priority": 1.5},
                        {"op": "predict", "x": "nope"},
                        {"op": "predict", "x": X[0].tolist(),
                         "deadline_ms": "soon"},
                        {"op": "predict"}):
                send_frame(sock, {"v": PROTOCOL_VERSION,
                                  "id": request_id(), **bad})
                resp = recv_frame(sock)
                assert resp["ok"] is False, bad
                assert resp["error"]["type"] == "BadRequest", bad
        # the dispatcher survived every malformed frame: traffic still flows
        with RemoteReplica(server.address, timeout_s=10.0) as replica:
            got = replica.predict(X[:4])
            assert np.all(np.isfinite(got))


def test_rejected_batch_cancels_queued_siblings(fitted):
    """A mid-batch FrontendRejected fails the frame AND cancels the rows
    already queued — the dispatcher drops them unserved instead of burning
    engine time on answers nobody will read. (v2-pinned: the JSON path
    submits per row, so a too-big batch PARTIALLY queues then fails; a v3
    peer's submit_batch is atomic and would reject before queuing any.)"""
    _, X = fitted
    engine = GatedEngine()
    fe = _frontend(engine, max_queue=3, dispatch_batch=1)
    with PredictionServer(fe, port=0) as server:
        with RemoteReplica(server.address, timeout_s=10.0,
                           protocol=2) as replica:
            with pytest.raises(FrontendRejected):
                replica.predict(X[:6])         # more rows than queue + slot
        engine.gate.set()
        deadline = time.monotonic() + 10
        while fe.queue_len() > 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert fe.stats.cancelled >= 2         # queued siblings were dropped
        assert fe.stats.served <= 2            # only already-claimed rows ran


def test_deadline_expired_on_arrival_fails_fast(fitted):
    est, X = fitted
    engine = GatedEngine()                     # would hang — must not be hit
    fe = _frontend(engine)
    with PredictionServer(fe, port=0) as server:
        with RemoteReplica(server.address, timeout_s=10.0) as replica:
            with pytest.raises(DeadlineExceeded, match="before arrival"):
                replica.predict(X[:2], deadline_s=-0.05)
            with pytest.raises(DeadlineExceeded):
                replica.predict(X[:2], deadline_s=0.0)
            assert engine.calls == 0           # never reached the queue
            assert replica.stats.remote_errors == 2


def test_backpressure_crosses_the_wire(fitted):
    _, X = fitted
    engine = GatedEngine()
    fe = _frontend(engine, max_queue=1, dispatch_batch=1)
    with PredictionServer(fe, port=0) as server:
        # occupy the single dispatch slot, then fill the 1-slot queue
        blocked = fe.submit(X[0])
        deadline = time.monotonic() + 10
        while fe.queue_len() > 0 and time.monotonic() < deadline:
            time.sleep(0.005)                  # row 0 leaves for dispatch
        queued = fe.submit(X[1])
        with RemoteReplica(server.address, timeout_s=10.0) as replica:
            with pytest.raises(FrontendRejected) as ei:
                replica.predict(X[2:3])
            assert ei.value.retry_after_s > 0
        engine.gate.set()
        assert blocked.result(timeout=10) == pytest.approx(float(X[0, 0]))
        assert queued.result(timeout=10) == pytest.approx(float(X[1, 0]))


def test_server_cut_mid_request_is_retryable(fitted):
    _, X = fitted
    engine = GatedEngine()
    fe = _frontend(engine)
    server = PredictionServer(fe, port=0, drain_s=0.05)
    server.start()
    replica = RemoteReplica(server.address, timeout_s=30.0)
    caught = []

    def call():
        try:
            replica.predict(X[:1])
        except Exception as exc:               # noqa: BLE001 - recorded
            caught.append(exc)

    t = threading.Thread(target=call)
    t.start()
    deadline = time.monotonic() + 10
    while engine.calls == 0 and time.monotonic() < deadline:
        time.sleep(0.005)                      # request is now in flight
    closer = threading.Thread(target=server.close)
    closer.start()
    t.join(timeout=10)
    engine.gate.set()                          # let the dispatch finish
    closer.join(timeout=10)
    assert len(caught) == 1
    assert isinstance(caught[0], TransportError)
    assert caught[0].retryable                 # pool would drain + fail over
    assert replica.stats.transport_errors == 1
    replica.close()


def test_graceful_drain_finishes_in_flight_request(fitted):
    _, X = fitted
    engine = GatedEngine()
    fe = _frontend(engine)
    server = PredictionServer(fe, port=0, drain_s=5.0)
    server.start()
    replica = RemoteReplica(server.address, timeout_s=30.0)
    results = []
    t = threading.Thread(target=lambda: results.append(
        replica.predict(X[:1])))
    t.start()
    deadline = time.monotonic() + 10
    while engine.calls == 0 and time.monotonic() < deadline:
        time.sleep(0.005)
    closer = threading.Thread(target=server.close)
    closer.start()
    time.sleep(0.05)                           # close() is now draining
    engine.gate.set()                          # in-flight request completes
    t.join(timeout=10)
    closer.join(timeout=10)
    assert results and results[0][0] == pytest.approx(float(X[0, 0]))
    # after the drain the server is gone: fresh connections fail retryably
    with pytest.raises(TransportError):
        replica.predict(X[:1])
    replica.close()


def test_remote_replica_is_a_serving_engine():
    replica = RemoteReplica("127.0.0.1", 1, n_features=N_F)
    assert isinstance(replica, ServingEngine)
    with pytest.raises(NotImplementedError):
        replica.swap_estimator(None)
    replica.close()


# --------------------------------------------------- slack-derived priority

def test_slack_priority_bands():
    assert slack_priority(0.001) == 0          # inside one prediction budget
    assert slack_priority(0.03) == 1
    assert slack_priority(0.2) == 2
    assert slack_priority(0.9) == 3
    assert slack_priority(60.0) == 4
    assert slack_priority(None) == PRIORITY_BACKGROUND
    slacks = [0.001, 0.03, 0.2, 0.9, 60.0, None]
    prios = [slack_priority(s) for s in slacks]
    assert prios == sorted(prios)              # tighter slack never loses


def test_submit_derives_priority_from_slack(fitted):
    class Recorder(GatedEngine):
        def __init__(self):
            super().__init__()
            self.gate.set()
            self.order = []

        def predict(self, X):
            X = np.atleast_2d(np.asarray(X))
            self.order.extend(int(v) for v in X[:, 0])
            return X[:, 0].astype(np.float64)

    engine = Recorder()
    fe = _frontend(engine, dispatch_batch=1)
    rows = {i: np.full(N_F, float(i), dtype=np.float32) for i in range(3)}
    futs = [fe.submit(rows[0]),                          # background
            fe.submit(rows[1], deadline_s=30.0),         # loose deadline
            fe.submit(rows[2], deadline_s=0.02)]         # tight deadline
    fe.start()
    for f in futs:
        f.result(timeout=10)
    # tightest slack dispatched first, no-deadline last — nobody chose ints
    assert engine.order == [2, 1, 0]
    fe.close()


def test_scheduler_threads_deadline_slack_into_predictors():
    class DeadlineAwareFake:
        def __init__(self):
            self.seen = []

        def predict(self, X, *, deadline_s=None, priority=None):
            self.seen.append(deadline_s)
            return np.asarray(X)[:, 0].astype(np.float64)

    fake = DeadlineAwareFake()
    assert supports_deadline(fake.predict)
    assert not supports_deadline(lambda X: X)
    rng = np.random.default_rng(0)
    X = rng.lognormal(1.0, 1.0, size=(10, N_F)).astype(np.float32)
    sched = schedule(X, [DevicePredictor("d0", fake, log_time=False),
                         DevicePredictor("d1", fake, log_time=False)],
                     deadline_s=5.0)
    assert len(sched.assignments) == 10
    assert len(fake.seen) == 2                 # one call per device
    assert all(s is not None and 0 < s <= 5.0 for s in fake.seen)
    assert fake.seen[1] <= fake.seen[0]        # the budget burns down
    # without a deadline the plain path is used (no kwarg forwarded)
    plain = schedule(X, [DevicePredictor("d0", fake, log_time=False)])
    assert len(plain.assignments) == 10


class DeadlineRecorder:
    """Deadline-aware engine that records the budget each predict saw."""

    def __init__(self):
        self.n_features = N_F
        self.seen: list[float | None] = []

    def predict(self, X, *, deadline_s=None, priority=None):
        self.seen.append(deadline_s)
        return np.atleast_2d(np.asarray(X))[:, 0].astype(np.float64)

    def swap_estimator(self, est):
        return 0

    def close(self):
        pass


def test_dispatch_propagates_tightest_deadline_to_remote_member():
    """ROADMAP gap closed: a dispatched batch no longer drops its requests'
    deadlines. The outer frontend forwards the TIGHTEST member deadline to
    its deadline-aware pool member (a RemoteReplica), the wire carries it as
    ``deadline_ms``, the inner tier re-anchors it — and the engine at the
    BOTTOM of the remote stack observes a positive remaining budget."""
    inner_engine = DeadlineRecorder()
    inner_fe = _frontend(inner_engine)
    with PredictionServer(inner_fe, port=0) as server:
        outer_pool = ReplicaPool(
            {"remote": RemoteReplica(server.address, timeout_s=10.0)},
            probe_X=np.ones((2, N_F), dtype=np.float32),
            check_interval_s=60.0)
        outer = ClusterFrontend(outer_pool, max_queue=16, auto_start=False)
        try:
            x = np.full(N_F, 2.0, dtype=np.float32)
            futs = [outer.submit(x, deadline_s=5.0),
                    outer.submit(x, deadline_s=30.0)]   # batch: 5s tightest
            outer.start()
            for f in futs:
                assert f.result(timeout=10) == pytest.approx(2.0)
            assert outer.stats.deadlines_forwarded >= 1
            # the recording engine sits under the INNER frontend: every hop
            # (outer dispatch -> wire -> inner admission -> inner dispatch)
            # kept the budget alive and below the tightest member's 5 s
            budgets = [s for s in inner_engine.seen if s is not None]
            assert budgets, f"no deadline reached the engine: {inner_engine.seen}"
            assert all(0 < s <= 5.0 for s in budgets)
        finally:
            outer.close()


def test_member_deadline_exceeded_spares_loose_siblings():
    """A member expiring the batch's TIGHTEST deadline must not fail the
    siblings that still have budget: only requests whose own deadline has
    actually passed get DeadlineExceeded; the rest retry and are served."""
    class ExpiringOnce:
        def __init__(self):
            self.n_features = N_F
            self.calls = 0

        def predict(self, X, *, deadline_s=None, priority=None):
            self.calls += 1
            if self.calls == 1:
                time.sleep(0.08)       # burn the tight member's budget
                raise DeadlineExceeded("member expired the tight request")
            return np.atleast_2d(np.asarray(X))[:, 0].astype(np.float64)

        def swap_estimator(self, est):
            return 0

        def close(self):
            pass

    engine = ExpiringOnce()
    fe = _frontend(engine)
    try:
        tight = fe.submit(np.full(N_F, 1.0, dtype=np.float32),
                          deadline_s=0.05)
        loose = fe.submit(np.full(N_F, 2.0, dtype=np.float32),
                          deadline_s=30.0)
        fe.start()
        with pytest.raises(DeadlineExceeded):
            tight.result(timeout=10)
        assert loose.result(timeout=10) == pytest.approx(2.0)
        assert engine.calls >= 2       # survivors were re-dispatched
        assert fe.stats.expired >= 1
    finally:
        fe.close()


def test_dispatch_without_deadlines_stays_on_plain_path():
    """No member carries a deadline -> the member is called WITHOUT the
    kwarg (background probes aside), preserving legacy batches verbatim."""
    engine = DeadlineRecorder()
    fe = _frontend(engine)
    try:
        x = np.full(N_F, 3.0, dtype=np.float32)
        fut = fe.submit(x)
        fe.start()
        assert fut.result(timeout=10) == pytest.approx(3.0)
        assert fe.stats.deadlines_forwarded == 0
        assert engine.seen == [None]
    finally:
        fe.close()


# ------------------------------------------ cross-process acceptance bar

def _spawn_server(port: int, seed: int = 3, trees: int = 12) -> subprocess.Popen:
    proc, _host, _port = spawn_demo_server(port, seed=seed, trees=trees,
                                           n_features=N_F)
    return proc


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_mixed_pool_survives_server_kill_and_restart(fitted):
    """The acceptance criterion: one in-process + one remote (subprocess)
    replica behind one frontend; every request is answered through a server
    KILL and a RESTART; remote answers match in-process to <=1e-6."""
    est, X = fitted
    # the subprocess fits the SAME demo estimator (seed=3, 12 trees): remote
    # and in-process replicas serve one model, so answers must agree
    port = _free_port()
    proc = _spawn_server(port, seed=3, trees=12)
    frontend = None
    try:
        local = ForestEngine(est, backend="flat-numpy", cache_size=0)
        remote = RemoteReplica("127.0.0.1", port, timeout_s=10.0,
                               connect_timeout_s=1.0)
        # remote answers == in-process answers, straight through the wire
        np.testing.assert_allclose(remote.predict(X), local.predict(X),
                                   rtol=0, atol=1e-6)
        pool = ReplicaPool({"local": local, "remote": remote},
                           check_interval_s=0.05, unhealthy_after=2,
                           revive_after=1)
        frontend = ClusterFrontend(pool, max_queue=256, dispatch_batch=8)
        oracle = local.predict(X)

        def stream(n):
            futs = [frontend.submit(X[i % X.shape[0]], deadline_s=30.0)
                    for i in range(n)]
            got = np.array([f.result(timeout=30) for f in futs])
            want = np.array([oracle[i % X.shape[0]] for i in range(n)])
            np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)

        stream(32)                             # both members healthy
        assert set(pool.healthy_names()) == {"local", "remote"}

        proc.kill()                            # ungraceful: SIGKILL mid-run
        proc.wait(timeout=10)
        stream(64)                             # every request still answered
        deadline = time.monotonic() + 20
        while ("remote" in pool.healthy_names()
               and time.monotonic() < deadline):
            time.sleep(0.02)                   # probes notice the corpse
        assert pool.healthy_names() == ["local"]
        assert pool.stats.drains >= 1

        proc = _spawn_server(port, seed=3, trees=12)   # same port, same model
        deadline = time.monotonic() + 30
        while ("remote" not in pool.healthy_names()
               and time.monotonic() < deadline):
            time.sleep(0.05)                   # probes revive the member
        assert "remote" in pool.healthy_names()
        assert pool.stats.revivals >= 1
        stream(32)                             # and traffic flows again
        # the revived remote is genuinely serving — ask it directly
        np.testing.assert_allclose(remote.predict(X[:8]), oracle[:8],
                                   rtol=0, atol=1e-6)
        assert frontend.stats.failed == 0      # not one request was lost
    finally:
        if frontend is not None:
            frontend.close()                   # closes pool + both replicas
        proc.kill()
        proc.wait(timeout=10)


# ------------------------------------------- one process per device

_SMOKE_PARENT = """
import sys
sys.path.insert(0, {src!r})
from jax._src import xla_bridge
from repro.cluster.remote import main
rc = main([{flag!r}])
print("RC", rc, "BACKEND_INITIALIZED", xla_bridge.backends_are_initialized())
"""


@pytest.mark.parametrize("flag", ["--selftest", "--obs-smoke"])
def test_smoke_parent_leaves_the_device_to_its_server(flag):
    """The smoke CLIs spawn a server child that serves from JAX's default
    device. A chip belongs to one process, so the parent that checks the
    child's answers must never initialize a JAX backend itself."""
    src = str(Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run(
        [sys.executable, "-c", _SMOKE_PARENT.format(src=src, flag=flag)],
        capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stderr
    assert "RC 0 BACKEND_INITIALIZED False" in out.stdout, out.stdout
