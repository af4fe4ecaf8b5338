"""Cross-host serving fabric tier-1 suite (cluster/net*.py,
cluster/remote.py, cluster/membership.py).

What is pinned here:

* **the frame codec is typed about every failure** — corrupt,
  truncated, alien, version-skewed, and oversize frames each raise
  FrameError with a distinct reason (never pickle garbage), clean EOF
  at a frame boundary reads as ``None``, and unpickling is restricted
  to containers/scalars/numpy on both transports (an ``os.system``
  payload is a typed refusal, not an import);
* **the handshake refuses bad peers up front** — wrong auth token and
  schema-fingerprint mismatch both answer with a typed reject, and the
  server keeps serving its good clients afterwards;
* **RemoteReplica is robust by construction** — deadlines resolve on a
  silent link (sweeper), transport failures are typed AND reroutable,
  the per-connection breaker opens/half-opens/recloses with PR 4
  semantics, reconnects back off exponentially with jitter, and the
  reader loop fails everything pending however it dies (the
  ProcessReplica audit, regression-tested on both transports);
* **loopback end-to-end** — a ReplicaServer serving a saved-model dir
  answers bit-exact with a lone engine, warms exactly the exporter's
  bucket signatures from the serving manifest, and provisions a fresh
  host over nothing but the socket (``fetch_manifest``/
  ``fetch_artifact``, sha256-verified);
* **partition tolerance** — a partitioned remote degrades to excluded
  (typed errors only, zero lost requests) and rejoins within one
  membership refresh of the partition healing.

All CPU. The sustained-load chaos drill is slow-marked; everything
else is unit-sized or rides one module-scoped loopback fixture.
"""
import io
import os
import pickle
import socket
import struct
import threading
import time
import zlib

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import serving
from paddle_tpu.cluster import (FrameError, HandshakeError, Membership,
                                RemoteReplica, RemoteUnavailableError,
                                ReplicaServer, Router,
                                provision_from_remote, serve_remotes)
from paddle_tpu.cluster import net
from paddle_tpu.cluster.net_worker import dir_manifest
from paddle_tpu.cluster.replica import ProcessReplica
from paddle_tpu.resilience import faultinject
from paddle_tpu.serving import (BucketSpec, QueueFullError,
                                RequestTimeoutError, ServerClosedError,
                                ServingEngine, ServingError,
                                ServiceUnavailableError,
                                WorkerDiedError)
from paddle_tpu.serving.health import (CircuitBreaker, HealthState,
                                       serving_rank)

pytestmark = pytest.mark.cluster


@pytest.fixture(autouse=True)
def _clean_faults():
    faultinject.disarm()
    yield
    faultinject.disarm()


# ---------------------------------------------------------------------------
# frame codec
# ---------------------------------------------------------------------------

def _raw_frame(payload):
    """Hand-built frame around an arbitrary payload (bypasses
    encode_frame so tests can smuggle evil pickles)."""
    return (net.MAGIC + bytes((net.PROTO_VERSION,))
            + struct.pack(">II", len(payload), zlib.crc32(payload))
            + payload)


def test_new_fault_points_registered():
    for point in ("net_conn_refused", "net_frame_drop",
                  "net_frame_delay", "net_partial_write",
                  "net_partition"):
        assert point in faultinject.KNOWN_POINTS


def test_frame_roundtrip_and_clean_eof():
    buf = io.BytesIO()
    first = {"type": "submit", "id": 7,
             "feed": {"x": np.arange(6, dtype=np.float32).reshape(2, 3),
                      "n": np.int64(3)},
             "timeout": 1.5}
    net.write_frame(buf, first)
    net.write_frame(buf, {"type": "stats", "id": 8})
    buf.seek(0)
    got = net.read_frame(buf)
    np.testing.assert_array_equal(got["feed"]["x"], first["feed"]["x"])
    assert got["feed"]["n"] == 3 and got["timeout"] == 1.5
    assert net.read_frame(buf) == {"type": "stats", "id": 8}
    # EOF exactly at a frame boundary is a polite close, not damage
    assert net.read_frame(buf) is None


def test_frame_corrupt_crc_is_typed():
    raw = bytearray(net.encode_frame({"a": 1}))
    raw[-1] ^= 0xFF
    with pytest.raises(FrameError) as exc:
        net.read_frame(io.BytesIO(bytes(raw)))
    assert exc.value.reason == "crc-mismatch"


def test_frame_alien_magic_is_typed():
    with pytest.raises(FrameError) as exc:
        net.read_frame(io.BytesIO(b"GET / HTTP/1.1\r\n\r\n"))
    assert exc.value.reason == "alien-magic"


def test_frame_version_skew_is_typed():
    raw = bytearray(net.encode_frame({"a": 1}))
    raw[len(net.MAGIC)] = net.PROTO_VERSION + 1
    with pytest.raises(FrameError) as exc:
        net.read_frame(io.BytesIO(bytes(raw)))
    assert exc.value.reason == "version-skew"


def test_frame_truncation_is_typed_header_and_payload():
    raw = net.encode_frame({"a": 1})
    with pytest.raises(FrameError) as exc:
        net.read_frame(io.BytesIO(raw[:-3]))        # payload cut
    assert exc.value.reason == "truncated"
    with pytest.raises(FrameError) as exc:
        net.read_frame(io.BytesIO(raw[:5]))         # header cut
    assert exc.value.reason == "truncated"


def test_frame_oversize_length_guard():
    header = (net.MAGIC + bytes((net.PROTO_VERSION,))
              + struct.pack(">II", net.MAX_FRAME_BYTES + 1, 0))
    with pytest.raises(FrameError) as exc:
        net.read_frame(io.BytesIO(header))
    assert exc.value.reason == "oversize"


def test_restricted_unpickle_rejects_code_globals():
    for evil in (os.system, eval, pickle.loads):
        frame = _raw_frame(pickle.dumps(evil))
        with pytest.raises(FrameError) as exc:
            net.read_frame(io.BytesIO(frame))
        assert exc.value.reason == "unpickle"
    # while the actual wire vocabulary stays fully allowed
    ok = net.decode_payload(pickle.dumps(
        {"s": {1, 2}, "t": (b"x", 2.5, None, True),
         "a": np.ones((2,), np.float32), "d": np.dtype("int64")}))
    assert ok["t"][3] is True


def test_wire_error_mapping():
    with pytest.raises(QueueFullError, match="full"):
        net.raise_wire_error(("QueueFullError", "full"))
    # an unknown (future) error name degrades to the ServingError base
    with pytest.raises(ServingError):
        net.raise_wire_error(("ErrorFromTheFuture", "boom"))
    assert net.wire_error(ValueError("x")) == ("ValueError", "x")


def test_check_hello_refusals():
    ok = net.client_hello(token="s3cret")
    assert net.check_hello(ok, token="s3cret") is None
    assert "token" in net.check_hello(
        net.client_hello(token="wrong"), token="s3cret")
    skew = net.client_hello(token="s3cret",
                            fingerprint={"proto": 0, "jax": "alien"})
    assert "fingerprint" in net.check_hello(skew, token="s3cret")
    assert "malformed" in net.check_hello({"type": "submit"})


def test_serving_rank_vocabulary():
    assert serving_rank(HealthState.READY) == 0
    assert serving_rank(HealthState.DEGRADED) == 1
    for state in (HealthState.STARTING, HealthState.DRAINING,
                  HealthState.STOPPED):
        assert serving_rank(state) is None


# ---------------------------------------------------------------------------
# scriptable fake sockets — RemoteReplica units without a server
# ---------------------------------------------------------------------------

class FakeSock:
    """A socket double the RemoteReplica transport can drive: sendall
    parses outgoing frames and (when scripted) pushes reply frames
    into the recv buffer; recv honors settimeout like a real socket."""

    def __init__(self, reply=None):
        self.reply = reply          # fn(msg) -> reply dict | None
        self.sent = []
        self._buf = b""
        self._cond = threading.Condition()
        self._timeout = None
        self.closed = False

    # -- test-side controls ---------------------------------------------
    def push(self, obj):
        with self._cond:
            self._buf += net.encode_frame(obj)
            self._cond.notify_all()

    def push_raw(self, data):
        with self._cond:
            self._buf += data
            self._cond.notify_all()

    # -- socket interface ------------------------------------------------
    def settimeout(self, t):
        self._timeout = t

    def sendall(self, data):
        if self.closed:
            raise BrokenPipeError("fake socket closed")
        stream = io.BytesIO(data)
        while True:
            try:
                msg = net.read_frame(stream)
            except FrameError:
                break
            if msg is None:
                break
            self.sent.append(msg)
            if self.reply is not None:
                out = self.reply(msg)
                if out is not None:
                    self.push(out)

    def recv(self, n):
        deadline = (None if self._timeout is None
                    else time.monotonic() + self._timeout)
        with self._cond:
            while not self._buf:
                if self.closed:
                    return b""
                left = (None if deadline is None
                        else deadline - time.monotonic())
                if left is not None and left <= 0:
                    raise socket.timeout("fake timeout")
                self._cond.wait(0.01 if left is None
                                else min(left, 0.01))
            out, self._buf = self._buf[:n], self._buf[n:]
            return out

    def close(self):
        with self._cond:
            self.closed = True
            self._cond.notify_all()

    def shutdown(self, how):
        self.close()


_WELCOME = {"type": "welcome", "name": "fake-remote",
            "warmup": {"signatures": 2, "compiles": 0},
            "stats": {"health_state": HealthState.READY}}


def _fake_connect(sock_factory):
    """A net.open_conn stand-in handing out scripted sockets."""
    def connect(addr, token=None, deadline=None, connect_timeout=5.0):
        sock = sock_factory()
        if isinstance(sock, Exception):
            raise sock
        return sock, dict(_WELCOME)
    return connect


def _echo_reply(msg):
    if msg.get("type") == "submit":
        return {"type": "result", "id": msg["id"],
                "value": [np.asarray(msg["feed"])]}
    if msg.get("type") == "stats":
        return {"type": "stats", "id": msg["id"],
                "value": {"health_state": HealthState.READY}}
    return None


def test_remote_replica_roundtrip_on_fake_socket():
    rep = RemoteReplica("fake:1", name="r0",
                        connect=_fake_connect(
                            lambda: FakeSock(reply=_echo_reply)))
    try:
        out = rep.submit(np.arange(3), timeout=5.0).result(5.0)
        np.testing.assert_array_equal(out[0], np.arange(3))
        assert rep.alive()
        assert rep.health_state() == HealthState.READY
        assert rep.outstanding() == 0
        assert rep.warmup() == {"signatures": 2, "compiles": 0}
    finally:
        rep.close()
    assert rep.health_state() == HealthState.STOPPED
    with pytest.raises(ServerClosedError):
        rep.submit(np.arange(3))


def test_remote_deadline_resolves_on_silent_link():
    """The server never answers (partitioned link): the sweeper fails
    the request with a typed RequestTimeoutError at deadline+grace —
    never a hang."""
    silent = FakeSock(reply=None)
    rep = RemoteReplica("fake:1", deadline_grace_s=0.1,
                        connect=_fake_connect(lambda: silent))
    try:
        t0 = time.monotonic()
        handle = rep.submit(np.arange(2), timeout=0.2)
        with pytest.raises(RequestTimeoutError,
                           match="unresponsive|no reply"):
            handle.result(5.0)
        assert time.monotonic() - t0 < 2.0
        assert rep.outstanding() == 0       # nothing stranded
    finally:
        rep.close()


def test_remote_wire_timeout_is_tightest_of_caller_and_default():
    sock = FakeSock(reply=None)
    rep = RemoteReplica("fake:1", request_timeout_s=10.0,
                        connect=_fake_connect(lambda: sock))
    try:
        rep.submit(np.arange(2), timeout=3.0)
        rep.submit(np.arange(2), timeout=60.0)
        rep.submit(np.arange(2))
        wire = [m["timeout"] for m in sock.sent
                if m["type"] == "submit"]
        assert wire == [3.0, 10.0, 10.0]
    finally:
        rep.close()


def test_remote_typed_error_reraise():
    def reply(msg):
        if msg.get("type") == "submit":
            return {"type": "error", "id": msg["id"],
                    "error": ("QueueFullError", "remote queue full")}
        return None
    rep = RemoteReplica("fake:1",
                        connect=_fake_connect(lambda: FakeSock(reply)))
    try:
        with pytest.raises(QueueFullError, match="remote queue full"):
            rep.submit(np.arange(2), timeout=5.0).result(5.0)
        # a typed serving error is an ANSWER — the link breaker must
        # not count it as a transport failure
        assert rep.breaker.state == CircuitBreaker.CLOSED
    finally:
        rep.close()


def test_remote_breaker_opens_then_half_open_probe_recovers():
    state = {"refuse": True, "connects": 0}

    def connect(addr, token=None, deadline=None, connect_timeout=5.0):
        state["connects"] += 1
        if state["refuse"]:
            raise RemoteUnavailableError("injected refusal")
        return FakeSock(reply=_echo_reply), dict(_WELCOME)

    rep = RemoteReplica("fake:1", breaker_threshold=2,
                        breaker_cooldown_s=0.05, connect=connect,
                        lazy=True)
    try:
        for _ in range(2):
            with pytest.raises(RemoteUnavailableError):
                rep.submit(np.arange(2), timeout=1.0)
        assert rep.breaker.state == CircuitBreaker.OPEN
        assert rep.health_state() == HealthState.DEGRADED
        connects_when_open = state["connects"]
        # open sheds instantly, without touching the network
        with pytest.raises(ServiceUnavailableError):
            rep.submit(np.arange(2), timeout=1.0)
        assert state["connects"] == connects_when_open
        # cooldown elapses; the network heals; the next submit is the
        # half-open probe and its success closes the (fresh) breaker
        time.sleep(0.08)
        state["refuse"] = False
        out = rep.submit(np.arange(2), timeout=5.0).result(5.0)
        np.testing.assert_array_equal(out[0], np.arange(2))
        assert rep.breaker.state == CircuitBreaker.CLOSED
        assert rep.breaker_opens_total() >= 1
    finally:
        rep.close()


def test_remote_reconnect_backoff_is_jittered_exponential():
    sleeps = []
    attempts = {"n": 0}

    def connect(addr, token=None, deadline=None, connect_timeout=5.0):
        attempts["n"] += 1
        raise RemoteUnavailableError("still down")

    rep = RemoteReplica("fake:1", connect=connect, lazy=True,
                        reconnect_attempts=4,
                        reconnect_backoff_s=0.08,
                        sleep=sleeps.append)
    rep.start()             # swallows the terminal failure by design
    assert attempts["n"] == 4
    assert not rep.alive()
    assert rep.reconnect_failures_total == 1
    # 3 backoffs of 0.08 * 2^k, each jittered into [0.5x, 1.5x)
    assert len(sleeps) == 3
    for base, got in zip((0.08, 0.16, 0.32), sleeps):
        assert 0.5 * base <= got < 1.5 * base
    rep.close()


def test_remote_conn_refused_fault_point():
    faultinject.arm("net_conn_refused", at=0)
    with pytest.raises(RemoteUnavailableError, match="injected"):
        net.open_conn("127.0.0.1:1")


def test_remote_reader_death_fails_pending_typed():
    """The shared reader-loop contract: however the reader exits, every
    pending request is failed typed, promptly."""
    sock = FakeSock(reply=None)
    rep = RemoteReplica("fake:1", connect=_fake_connect(lambda: sock))
    try:
        handle = rep.submit(np.arange(2), timeout=30.0)
        sock.close()            # EOF under the reader
        with pytest.raises((WorkerDiedError, ServerClosedError)):
            handle.result(5.0)
        assert not rep.alive()
        assert rep.outstanding() == 0
    finally:
        rep.close()


def test_remote_reader_protocol_damage_fails_pending_typed():
    sock = FakeSock(reply=None)
    rep = RemoteReplica("fake:1", connect=_fake_connect(lambda: sock))
    try:
        handle = rep.submit(np.arange(2), timeout=30.0)
        sock.push_raw(b"this is not a frame at all!!")
        with pytest.raises(FrameError):
            handle.result(5.0)
        assert rep.outstanding() == 0
    finally:
        rep.close()


def test_process_replica_reader_death_cannot_strand_pending():
    """Regression (the _fail_all_pending audit): a reader thread that
    DIES — e.g. protocol damage mid-drain — must fail every pending
    request typed instead of stranding it past its deadline."""

    class ExplodingStream:
        def __init__(self):
            self.reads = 0

        def read(self, n):
            self.reads += 1
            if self.reads == 1:
                # half a header, then a blocking-forever stream would
                # strand; here: damage
                return b"garbage-that-is-not-magic"[:n]
            return b""

    replica = ProcessReplica.__new__(ProcessReplica)
    replica.name = "audit"
    replica._lock = threading.Lock()
    replica._pending = {}
    replica._stats_waiters = {}
    replica._last_stats = {}
    replica._ready = threading.Event()

    class FakeProc:
        stdout = ExplodingStream()

        def poll(self):
            return None

    replica._proc = FakeProc()
    from paddle_tpu.serving.batching import PendingResult
    req = PendingResult(feed=None, n_rows=1, signature=(),
                        deadline=time.monotonic() + 30.0,
                        enqueued_at=time.monotonic())
    replica._pending[1] = req
    t = threading.Thread(target=replica._reader_loop, daemon=True)
    t.start()
    t.join(5.0)
    assert not t.is_alive()
    with pytest.raises(WorkerDiedError, match="protocol damage"):
        req.result(0.1)
    assert replica._pending == {}


# ---------------------------------------------------------------------------
# membership units
# ---------------------------------------------------------------------------

class FakeMember:
    def __init__(self, name, answering=True):
        self.name = name
        self.answering = answering
        self.stale_after_s = None
        self.refreshes = 0
        self._last_seen = None

    def refresh(self, timeout=2.0):
        self.refreshes += 1
        if self.answering:
            self._last_seen = time.monotonic()
        return self.answering

    def health_state(self):
        return (HealthState.READY if self.answering
                else HealthState.DEGRADED)

    def alive(self):
        return self.answering

    def outstanding(self):
        return 0


def test_membership_eviction_and_rejoin_counters():
    a, b = FakeMember("a"), FakeMember("b")
    m = Membership([a, b], refresh_interval_s=0, stale_after_s=0.5)
    assert m.refresh_once() == 2
    assert m.stats()["evictions_total"] == 0
    b.answering = False         # partition
    assert m.refresh_once() == 1
    assert m.stats()["evictions_total"] == 1
    view = {v["name"]: v for v in m.view()}
    assert view["b"]["answering"] is False
    assert view["b"]["serving_rank"] == 1       # DEGRADED tier
    assert view["a"]["serving_rank"] == 0
    b.answering = True          # heals: ONE refresh rejoins
    m.refresh_once()
    assert m.stats()["rejoins_total"] == 1
    assert {v["name"]: v["answering"] for v in m.view()} \
        == {"a": True, "b": True}
    m.close()


def test_membership_propagates_staleness_bound():
    a = FakeMember("a")
    m = Membership([a], refresh_interval_s=0, stale_after_s=0.7)
    assert a.stale_after_s == 0.7
    m.close()


def test_membership_refresh_thread_runs():
    a = FakeMember("a")
    m = Membership([a], refresh_interval_s=0.02)
    try:
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and a.refreshes < 2:
            time.sleep(0.01)
        assert a.refreshes >= 2
    finally:
        m.close()


# ---------------------------------------------------------------------------
# loopback end-to-end — a real ReplicaServer over a saved model
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def saved_model(tmp_path_factory):
    """A tiny exported classifier with serving buckets, plus a
    lone-engine reference output."""
    fluid.force_cpu()
    tmp = tmp_path_factory.mktemp("netmodel")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        h = fluid.layers.fc(x, size=16, act="relu")
        pred = fluid.layers.fc(h, size=10, act="softmax")
    infer = main.clone(for_test=True)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    model_dir = str(tmp / "model")
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(
            model_dir, ["x"], [pred], exe, main_program=infer,
            serving_buckets=BucketSpec(batch_sizes=(1, 2)))
    eng = ServingEngine.from_saved_model(model_dir,
                                         place=fluid.CPUPlace())
    feed = {"x": np.arange(8, dtype=np.float32).reshape(1, 8)}
    try:
        ref = np.asarray(eng.infer(feed, timeout=30.0)[0])
    finally:
        eng.close()
    return {"dir": model_dir, "feed": feed, "ref": ref}


@pytest.fixture(scope="module")
def loopback_server(saved_model):
    server = ReplicaServer(saved_model["dir"], name="lo-0")
    yield server
    server.close()


def test_server_warms_the_exporters_buckets(loopback_server):
    """Acceptance pin: a fresh ReplicaServer built from only a
    saved-model dir warms exactly the exporter's 2 bucket signatures,
    read from the serving manifest: one executable each."""
    assert loopback_server.engine.buckets.batch_sizes == (1, 2)
    assert loopback_server.warmup_report == {"signatures": 2,
                                             "compiles": 2}


def test_loopback_bit_exact_vs_lone_engine(saved_model,
                                           loopback_server):
    rep = RemoteReplica(loopback_server.addr, name="cli")
    try:
        for _ in range(3):
            out = rep.submit(saved_model["feed"],
                             timeout=30.0).result(30.0)
            np.testing.assert_array_equal(np.asarray(out[0]),
                                          saved_model["ref"])
        assert rep.health_state() == HealthState.READY
        snap = rep.stats()
        assert snap["responses_total"] >= 3
        assert snap["breaker_client"]["state"] == "closed"
    finally:
        rep.close()


def test_handshake_wrong_token_refused_server_survives(
        saved_model, loopback_server):
    with pytest.raises(HandshakeError, match="token"):
        RemoteReplica(loopback_server.addr, token="wrong-secret")
    # the refusal cost the server nothing: a good client still serves
    rep = RemoteReplica(loopback_server.addr)
    try:
        out = rep.submit(saved_model["feed"],
                         timeout=30.0).result(30.0)
        np.testing.assert_array_equal(np.asarray(out[0]),
                                      saved_model["ref"])
    finally:
        rep.close()
    assert loopback_server.stats()["handshake_refused_total"] >= 1


def test_handshake_fingerprint_mismatch_refused(loopback_server):
    sock = socket.create_connection(
        (loopback_server.host, loopback_server.port), timeout=5.0)
    try:
        net.send_frame(sock, {
            "type": "hello", "token": net.default_token(),
            "fingerprint": {"proto": 999, "jax": "not-this-jax"}})
        reply = net.recv_frame(
            sock, deadline=time.monotonic() + 5.0)
        assert reply["type"] == "reject"
        assert "fingerprint" in reply["reason"]
    finally:
        sock.close()


def test_alien_bytes_answered_typed_and_server_survives(
        saved_model, loopback_server):
    """A port scanner / stray writer on the fabric port gets a typed
    protocol_error and ONLY its connection dies."""
    sock = socket.create_connection(
        (loopback_server.host, loopback_server.port), timeout=5.0)
    try:
        sock.sendall(b"GET / HTTP/1.1\r\nHost: nope\r\n\r\n")
        reply = net.recv_frame(sock,
                               deadline=time.monotonic() + 5.0)
        assert reply["type"] == "protocol_error"
        assert reply["error"][0] == "FrameError"
    finally:
        sock.close()
    assert loopback_server.stats()["protocol_errors_total"] >= 1
    rep = RemoteReplica(loopback_server.addr)
    try:
        out = rep.submit(saved_model["feed"],
                         timeout=30.0).result(30.0)
        np.testing.assert_array_equal(np.asarray(out[0]),
                                      saved_model["ref"])
    finally:
        rep.close()


def test_frame_drop_resolves_at_deadline_then_recovers(
        saved_model, loopback_server):
    rep = RemoteReplica(loopback_server.addr, deadline_grace_s=0.15)
    try:
        faultinject.arm("net_frame_drop", at=0)
        handle = rep.submit(saved_model["feed"], timeout=0.3)
        with pytest.raises(RequestTimeoutError):
            handle.result(5.0)
        faultinject.disarm()
        # the connection itself is fine — the next request serves
        out = rep.submit(saved_model["feed"],
                         timeout=30.0).result(30.0)
        np.testing.assert_array_equal(np.asarray(out[0]),
                                      saved_model["ref"])
        assert rep.outstanding() == 0
    finally:
        rep.close()


def test_partial_write_is_typed_and_reconnect_recovers(
        saved_model, loopback_server):
    rep = RemoteReplica(loopback_server.addr)
    try:
        faultinject.arm("net_partial_write", at=0)
        with pytest.raises(RemoteUnavailableError):
            rep.submit(saved_model["feed"], timeout=5.0)
        faultinject.disarm()
        assert not rep.alive()
        rep.start()
        assert rep.alive()
        out = rep.submit(saved_model["feed"],
                         timeout=30.0).result(30.0)
        np.testing.assert_array_equal(np.asarray(out[0]),
                                      saved_model["ref"])
    finally:
        rep.close()


def test_provision_from_remote_over_the_wire(saved_model,
                                             loopback_server,
                                             tmp_path):
    """No shared filesystem: a fresh host materializes the model dir
    over fetch_manifest/fetch_artifact, every file matching the
    server's manifest sha256, then serves bit-exact."""
    dest = str(tmp_path / "provisioned")
    report = provision_from_remote(loopback_server.addr, dest)
    manifest = dir_manifest(saved_model["dir"])
    assert report["files"] == len(manifest) >= 3 and report["bytes"] > 0
    assert dir_manifest(dest) == manifest      # every file, by sha256
    fresh = ReplicaServer(dest, name="provisioned")
    try:
        rep = RemoteReplica(fresh.addr)
        try:
            out = rep.submit(saved_model["feed"],
                             timeout=30.0).result(30.0)
            np.testing.assert_array_equal(np.asarray(out[0]),
                                          saved_model["ref"])
        finally:
            rep.close()
    finally:
        fresh.close()


def test_fetch_artifact_path_confinement(loopback_server, tmp_path):
    rep = RemoteReplica(loopback_server.addr)
    try:
        with pytest.raises(ValueError, match="escapes|relative"):
            rep.fetch_artifact("../../etc/passwd")
        with pytest.raises(ValueError, match="escapes|relative"):
            rep.fetch_artifact("/etc/passwd")
    finally:
        rep.close()


def test_serve_remotes_partition_excluded_then_rejoined(
        saved_model, tmp_path):
    """The quick partition drill: mid-traffic partition on a 2-remote
    pool degrades to typed errors only; the partitioned replicas are
    excluded, then rejoin within one membership refresh of healing."""
    s1 = ReplicaServer(saved_model["dir"], name="p1")
    s2 = ReplicaServer(saved_model["dir"], name="p2")
    router = serve_remotes([s1.addr, s2.addr],
                           refresh_interval_s=0.05,
                           breaker_cooldown_s=0.1,
                           reconnect_backoff_s=0.01)
    feed = saved_model["feed"]
    try:
        assert isinstance(router, Router)
        for _ in range(4):
            out = router.infer(feed, timeout=30.0)
            np.testing.assert_array_equal(np.asarray(out[0]),
                                          saved_model["ref"])
        faultinject.arm("net_partition", at=0, times=12)
        outcomes = {"ok": 0, "typed": 0}
        for _ in range(12):
            try:
                router.infer(feed, timeout=1.0)
                outcomes["ok"] += 1
            except ServingError:
                outcomes["typed"] += 1      # typed, never lost
            time.sleep(0.01)
        faultinject.disarm()
        # heal: every replica rejoins via the membership refresher
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and \
                not all(r.alive() for r in router.pool.replicas()):
            time.sleep(0.02)
        assert all(r.alive() for r in router.pool.replicas())
        for _ in range(4):
            out = router.infer(feed, timeout=30.0)
            np.testing.assert_array_equal(np.asarray(out[0]),
                                          saved_model["ref"])
        assert router.membership.stats()["rejoins_total"] >= 1
    finally:
        router.close()
        s1.close()
        s2.close()


def test_inferencer_serve_remotes_returns_router(saved_model,
                                                 loopback_server):
    from paddle_tpu.inferencer import Inferencer
    inferencer = Inferencer.from_inference_model(
        saved_model["dir"], place=fluid.CPUPlace())
    router = inferencer.serve(remotes=[loopback_server.addr])
    try:
        assert isinstance(router, Router)
        out = router.infer(saved_model["feed"], timeout=30.0)
        np.testing.assert_array_equal(np.asarray(out[0]),
                                      saved_model["ref"])
    finally:
        router.close()


# ---------------------------------------------------------------------------
# the sustained chaos drill — slow lane
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_partition_chaos_zero_loss_breaker_cycle_and_rejoin(
        saved_model):
    """The acceptance chaos gate: net_partition + net_frame_drop
    injected mid-load on a 2-remote pool — zero lost requests (every
    submit resolves to a result or a typed serving error), the breaker
    opens and re-closes, and the partitioned replica rejoins."""
    s1 = ReplicaServer(saved_model["dir"], name="c1")
    s2 = ReplicaServer(saved_model["dir"], name="c2")
    router = serve_remotes([s1.addr, s2.addr],
                           refresh_interval_s=0.05,
                           breaker_threshold=2,
                           breaker_cooldown_s=0.1,
                           reconnect_backoff_s=0.01,
                           reconnect_attempts=2)
    feed = saved_model["feed"]
    outcomes = {"ok": 0, "typed": 0, "lost": 0}
    lock = threading.Lock()
    stop = threading.Event()

    def client():
        while not stop.is_set():
            try:
                router.infer(feed, timeout=5.0)
                key = "ok"
            except ServingError:
                key = "typed"
            except Exception:               # noqa: BLE001 — tallied
                key = "lost"
            with lock:
                outcomes[key] += 1
            time.sleep(0.002)

    try:
        threads = [threading.Thread(target=client, daemon=True)
                   for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.3)
        faultinject.arm("net_partition", at=0, times=60)
        faultinject.arm("net_frame_drop", at=0, times=4)
        time.sleep(1.0)
        faultinject.disarm()
        time.sleep(1.0)
        stop.set()
        for t in threads:
            t.join(30.0)
        replicas = router.pool.replicas()
        # zero lost; traffic flowed on both sides of the partition
        assert outcomes["lost"] == 0, outcomes
        assert outcomes["ok"] > 0, outcomes
        # the breaker cycle happened: at least one open across the
        # drill, and every live link's breaker is closed again
        assert sum(r.breaker_opens_total() for r in replicas) >= 1
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and \
                not all(r.alive() for r in replicas):
            time.sleep(0.02)
        assert all(r.alive() for r in replicas)
        assert all(r.breaker.state == CircuitBreaker.CLOSED
                   for r in replicas)
        assert router.membership.stats()["rejoins_total"] >= 1
        # post-heal traffic is clean and bit-exact
        for _ in range(6):
            out = router.infer(feed, timeout=30.0)
            np.testing.assert_array_equal(np.asarray(out[0]),
                                          saved_model["ref"])
    finally:
        stop.set()
        router.close()
        s1.close()
        s2.close()
