"""Web-portal front end (PR 8) — HTTP/websocket transport over the
serving tier.

Pins the acceptance invariants:
  * 8 concurrent HTTP clients receive responses BIT-IDENTICAL to
    direct `SpikeServer.submit` (engine in-process; all four backends
    in the forced-devices child below);
  * a websocket streaming session equals the in-process session lane
    window for window (including pipelined windows);
  * auth/quota/backpressure negative paths return structured 401/429/
    503 JSON with Retry-After where promised;
  * an `AnalysisError` crossing the portal renders to a 400 whose
    `message` is exactly `report.render()` (E_SCHED_WIDTH worked
    example);
  * serving through the portal compiles NOTHING the in-process path
    had not already compiled (zero extra retraces);
  * satellites: `next_pow2` rejects n <= 0, `submit(timeout=)`
    resolves with a structured `DeadlineError`, `shutdown(drain=)`
    resolves-or-cancels every pending future, `DoubleBuffer(capacity)`
    sheds with `BufferFull`.
"""
import http.client
import json
import os
import struct
import subprocess
import sys
import threading
import time
from concurrent.futures import CancelledError
from pathlib import Path

import numpy as np
import pytest

from repro.analysis import AnalysisError
from repro.analysis.retrace import compile_counts
from repro.core.api import LIF_neuron
from repro.core.compile import compile_spec
from repro.core.deploy import deploy
from repro.core.partition import Hierarchy
from repro.core.spec import NetworkSpec
from repro.portal import Portal, PortalError, TokenQuota, WSClient
from repro.portal.gateway import result_digest
from repro.serve import (BufferClosed, BufferFull, DeadlineError,
                         DoubleBuffer, SpikeServer, next_pow2)

ROOT = Path(__file__).resolve().parents[1]
BACKENDS = ("simulator", "engine", "hiaer", "mesh")


def small_compiled(backend, n_axons=5, n_neurons=12, seed=3):
    rng = np.random.default_rng(seed)
    spec = NetworkSpec()
    ax = spec.add_axons(n_axons)
    nid = spec.add_neurons(n_neurons,
                           LIF_neuron(threshold=5, nu=-32, lam=50))
    pre = np.concatenate([np.repeat(ax, 4), np.repeat(nid, 3)])
    post = rng.integers(0, n_neurons, pre.shape[0])
    w = rng.integers(-3, 7, pre.shape[0])
    spec.connect(pre, post, w)
    spec.set_outputs(list(range(4)))
    kw = {}
    if backend in ("hiaer", "mesh"):
        kw["hierarchy"] = Hierarchy(1, 1, 3, -(-n_neurons // 3))
    return compile_spec(spec, target=backend, **kw)


def http_req(port, method, path, body=None, token=None, timeout=120):
    conn = http.client.HTTPConnection("127.0.0.1", port,
                                      timeout=timeout)
    headers = {"Content-Type": "application/json"}
    if token is not None:
        headers["Authorization"] = f"Bearer {token}"
    conn.request(method, path,
                 json.dumps(body) if body is not None else None,
                 headers)
    resp = conn.getresponse()
    out = (resp.status, {k.lower(): v for k, v in resp.getheaders()},
           json.loads(resp.read().decode("utf-8")))
    conn.close()
    return out


def windows(rng, B, T, A):
    return rng.integers(0, 2, (B, T, A)).astype(np.int32)


# ------------------------------------------------- shared engine portal
@pytest.fixture(scope="module")
def engine_portal():
    """One resident engine model served in-process, shared by the HTTP
    tests (module-scoped: the compile cost is paid once)."""
    c = small_compiled("engine")
    srv = SpikeServer(max_batch=8, max_wait_ms=3.0)
    srv.add_model("m", c, window=4, n_sessions=4, seed=0)
    with srv, Portal(srv, port=0) as portal:
        yield srv, portal, c


# ---------------------------------------------------------- satellites
def test_next_pow2_rejects_nonpositive():
    assert [next_pow2(i) for i in (1, 2, 3, 4, 5, 8, 9)] == \
        [1, 2, 4, 4, 8, 8, 16]
    for bad in (0, -1, -8):
        with pytest.raises(ValueError, match="positive batch size"):
            next_pow2(bad)


def test_double_buffer_capacity_sheds_with_bufferfull():
    buf = DoubleBuffer(capacity=2)
    buf.put("a")
    buf.put("b")
    with pytest.raises(BufferFull) as ei:
        buf.put("c")
    assert ei.value.pending == 2 and ei.value.capacity == 2
    assert buf.take(8) == ["a", "b"]        # drained -> room again
    buf.put("c")
    st = buf.stats()
    assert st["rejected"] == 1 and st["capacity"] == 2


def test_submit_timeout_resolves_structured_deadline_error():
    c = small_compiled("engine")
    srv = SpikeServer(max_batch=4, max_wait_ms=1.0)
    srv.add_model("m", c, window=3, n_sessions=0, seed=0)
    w = windows(np.random.default_rng(0), 1, 3, c.n_axons)[0]
    # enqueue while the dispatcher is NOT running, so the deadline
    # deterministically expires before any batch can admit it
    fut = srv.submit("m", w, timeout=0.01)
    ok = srv.submit("m", w)                  # no timeout -> served
    time.sleep(0.05)
    with srv:
        with pytest.raises(DeadlineError) as ei:
            fut.result(timeout=60)
        assert ok.result(timeout=60).spikes.shape == (3, c.n_neurons)
    e = ei.value
    assert e.model == "m" and e.waited_s >= e.timeout_s
    assert "expired after waiting" in str(e)


def test_shutdown_drains_or_cancels_every_pending_future():
    c = small_compiled("engine")
    w = windows(np.random.default_rng(1), 1, 3, c.n_axons)[0]

    # drain=True: queued work is served before the dispatcher stops
    srv = SpikeServer(max_batch=4, max_wait_ms=1.0)
    srv.add_model("m", c, window=3, n_sessions=0, seed=0)
    srv.start()
    futs = [srv.submit("m", w, seed=i) for i in range(5)]
    srv.shutdown(drain=True)
    for f in futs:
        assert f.result(timeout=1).spikes.shape == (3, c.n_neurons)

    # drain=False (dispatcher never started): everything is cancelled,
    # nobody hangs
    srv2 = SpikeServer(max_batch=4, max_wait_ms=1.0)
    srv2.add_model("m", c, window=3, n_sessions=0, seed=0)
    futs = [srv2.submit("m", w, seed=i) for i in range(3)]
    srv2.shutdown(drain=False)
    for f in futs:
        assert f.done()
        with pytest.raises(CancelledError):
            f.result(timeout=1)
    with pytest.raises(BufferClosed):
        srv2.submit("m", w)
    srv2.shutdown()                           # idempotent


def test_cancelled_future_never_kills_dispatcher_or_peers():
    """A client cancelling its Future (the portal does on timeout /
    disconnect) must not raise InvalidStateError inside the dispatch
    loop: the cancelled request's batch peers still get results, an
    expired-and-cancelled request is dropped silently, and the
    dispatcher thread survives to serve later submissions."""
    c = small_compiled("engine")
    srv = SpikeServer(max_batch=8, max_wait_ms=1.0)
    srv.add_model("m", c, window=3, n_sessions=0, seed=0)
    w = windows(np.random.default_rng(2), 1, 3, c.n_axons)[0]
    # queue a batch while the dispatcher is down, then cancel members:
    # one rides _run_batch cancelled, one hits _expire cancelled
    gone = srv.submit("m", w, seed=1)
    gone_expired = srv.submit("m", w, seed=2, timeout=0.005)
    ok = srv.submit("m", w, seed=3)
    assert gone.cancel() and gone_expired.cancel()
    time.sleep(0.02)                          # let the deadline lapse
    with srv:
        res = ok.result(timeout=60)
        assert res.spikes.shape == (3, c.n_neurons)
        # the dispatcher thread is still alive and serving
        again = srv.submit("m", w, seed=4).result(timeout=60)
        assert again.spikes.shape == (3, c.n_neurons)


# ------------------------------------------------------- HTTP transport
def test_http_eight_concurrent_clients_bit_identical(engine_portal):
    """8 concurrent HTTP clients x 3 requests == direct submit, bit
    for bit (digest AND full arrays)."""
    srv, portal, c = engine_portal
    rng = np.random.default_rng(7)
    n_req = 3
    reqs = {(cl, r): windows(rng, 1, 4, c.n_axons)[0]
            for cl in range(8) for r in range(n_req)}
    results = {}

    def client(cl):
        for r in range(n_req):
            status, _, body = http_req(
                portal.port, "POST", "/v1/m/run",
                {"counts": reqs[(cl, r)].tolist(),
                 "seed": cl * 100 + r})
            results[(cl, r)] = (status, body)

    ts = [threading.Thread(target=client, args=(cl,))
          for cl in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()

    for (cl, r), w in reqs.items():
        status, body = results[(cl, r)]
        assert status == 200, body
        ref = srv.submit("m", w, seed=cl * 100 + r).result(timeout=120)
        assert body["digest"] == result_digest(ref.spikes,
                                               ref.membrane)
        np.testing.assert_array_equal(
            np.asarray(body["spikes"], bool), ref.spikes)
        np.testing.assert_array_equal(
            np.asarray(body["membrane"], np.int32), ref.membrane)
        assert body["batch_size"] >= 1 and body["model"] == "m"


def test_http_session_lifecycle(engine_portal):
    srv, portal, c = engine_portal
    w = windows(np.random.default_rng(3), 1, 4, c.n_axons)[0]
    _, _, opened = http_req(portal.port, "POST", "/v1/m/session")
    sid = opened["session"]
    assert opened["window"] == 4
    s, _, r1 = http_req(portal.port, "POST", "/v1/m/run",
                        {"counts": w.tolist(), "session": sid})
    assert s == 200 and r1["session"] == sid
    s, _, info = http_req(portal.port, "GET", f"/v1/m/session/{sid}")
    assert s == 200 and info["steps"] == 4
    np.testing.assert_array_equal(np.asarray(info["membrane"]),
                                  np.asarray(r1["membrane"]))
    s, _, _ = http_req(portal.port, "POST",
                       f"/v1/m/session/{sid}/reset")
    assert s == 200
    s, _, info = http_req(portal.port, "GET", f"/v1/m/session/{sid}")
    assert not np.asarray(info["membrane"]).any()
    s, _, r2 = http_req(portal.port, "POST", "/v1/m/run",
                        {"counts": w.tolist(), "session": sid})
    # reset -> same construction stream -> same window result
    assert r2["digest"] == r1["digest"]
    s, _, closed = http_req(portal.port, "DELETE",
                            f"/v1/m/session/{sid}")
    assert s == 200 and closed["closed"] == sid
    s, _, body = http_req(portal.port, "GET", f"/v1/m/session/{sid}")
    assert s == 404 and body["error"]["code"] == "E_NO_SESSION"


def test_http_reconfigure_barrier(engine_portal):
    srv, portal, c = engine_portal
    pre, post = -1, int(c.syn_post[0])
    w_old = int(srv.models["m"].dep.read_synapses([pre], [post])[0])
    s, _, body = http_req(portal.port, "POST", "/v1/m/reconfigure",
                          {"pre": [pre], "post": [post],
                           "weight": [w_old + 1]})
    assert s == 200 and body["uploads"] >= 1
    got = int(srv.models["m"].dep.read_synapses([pre], [post])[0])
    assert got == w_old + 1
    # put it back so later module tests see the original weights
    http_req(portal.port, "POST", "/v1/m/reconfigure",
             {"pre": [pre], "post": [post], "weight": [w_old]})


def test_analysis_error_renders_400_with_exact_report(engine_portal):
    """The portal's 400 body carries the analyzer's own code and a
    message that is EXACTLY `report.render()` (== str(AnalysisError))."""
    srv, portal, c = engine_portal
    wide = np.zeros((4, c.n_axons + 7), int)
    with pytest.raises(AnalysisError) as ei:
        srv.submit("m", wide)
    status, _, body = http_req(portal.port, "POST", "/v1/m/run",
                               {"counts": wide.tolist()})
    assert status == 400
    assert body["error"]["code"] == "E_SCHED_WIDTH"
    assert body["error"]["message"] == str(ei.value)
    f = body["error"]["findings"]["findings"][0]
    assert f["code"] == "E_SCHED_WIDTH" and f["severity"] == "error"


def test_http_negative_routes(engine_portal):
    srv, portal, c = engine_portal
    s, _, body = http_req(portal.port, "GET", "/nope")
    assert s == 404 and body["error"]["code"] == "E_NO_ROUTE"
    s, _, body = http_req(portal.port, "POST", "/v1/ghost/run",
                          {"events": [[0]]})
    assert s == 404 and body["error"]["code"] == "E_NO_MODEL"
    s, _, body = http_req(portal.port, "GET", "/v1/m/run")
    assert s == 405 and body["error"]["code"] == "E_METHOD"
    conn = http.client.HTTPConnection("127.0.0.1", portal.port,
                                      timeout=60)
    conn.request("POST", "/v1/m/run", b"{not json",
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    body = json.loads(resp.read().decode())
    conn.close()
    assert resp.status == 400 and body["error"]["code"] == "E_BAD_JSON"
    s, _, body = http_req(portal.port, "POST", "/v1/m/run",
                          {"counts": [[0] * c.n_axons],
                           "events": [[0]]})
    assert s == 400 and "exactly one" in body["error"]["message"]


def test_metrics_exposes_server_stats_and_clients(engine_portal):
    srv, portal, c = engine_portal
    # bare /metrics is Prometheus text now; JSON moved to ?format=json
    s, _, body = http_req(portal.port, "GET", "/metrics?format=json")
    assert s == 200
    assert body["server"]["models"]["m"]["requests"] >= 1
    assert {"p50_ms", "p99_ms", "buffer"} <= set(body["server"])
    assert body["clients"] == {}            # open portal: no tokens


# -------------------------------------------------- websocket transport
def test_ws_streaming_session_equals_inprocess_lane(engine_portal):
    """A websocket stream == the in-process session lane, window for
    window — including pipelined windows (sent before reading)."""
    srv, portal, c = engine_portal
    rng = np.random.default_rng(9)
    wins = windows(rng, 4, 4, c.n_axons)

    ws = WSClient("127.0.0.1", portal.port, "m")
    lane = ws.session
    for w in wins:                           # pipelined: no recv yet
        ws.send_window(counts=w)
    got = [ws.recv() for _ in range(len(wins))]
    ws.close()
    assert [g["window"] for g in got] == [0, 1, 2, 3]

    # in-process reference: same lane id on a fresh deployment of the
    # same artifact + seed (lane streams are construction-derived)
    ref = deploy(c, seed=0)
    ref.alloc_lanes(4)
    for w, g in zip(wins, got):
        spk, V = ref.run_lanes([lane], w[None])
        assert g["digest"] == result_digest(spk[0], V[0])
        np.testing.assert_array_equal(np.asarray(g["spikes"], bool),
                                      spk[0])
    # the lane is released on close: all 4 session slots free again
    assert srv.models["m"].sessions.n_open == 0


def test_ws_lane_exhaustion_is_http_503(engine_portal):
    srv, portal, c = engine_portal
    clients = [WSClient("127.0.0.1", portal.port, "m")
               for _ in range(4)]
    try:
        with pytest.raises(PortalError) as ei:
            WSClient("127.0.0.1", portal.port, "m")
        assert ei.value.status == 503
        assert ei.value.code == "E_NO_LANES"
    finally:
        for ws in clients:
            ws.close()


def _wait_lanes_free(srv, model, deadline_s=30.0):
    t0 = time.monotonic()
    while srv.models[model].sessions.n_open != 0:
        if time.monotonic() - t0 > deadline_s:
            raise AssertionError(
                f"{srv.models[model].sessions.n_open} lane(s) still "
                "open — leaked by a dead connection")
        time.sleep(0.02)


def test_ws_abrupt_disconnect_releases_lane(engine_portal):
    """A client vanishing mid-frame (routine for network servers) must
    not strand the stream handler: the producer's sentinel still fires,
    handle_stream returns, and the resident lane is released — repeated
    abrupt disconnects must not exhaust the SlotPool."""
    srv, portal, c = engine_portal
    for _ in range(6):                        # > the 4 session slots
        ws = WSClient("127.0.0.1", portal.port, "m")
        ws.sock.sendall(b"\x81")              # half a frame header...
        ws.sock.close()                       # ...then vanish
    _wait_lanes_free(srv, "m")
    # every slot is usable again: open the full complement at once
    clients = [WSClient("127.0.0.1", portal.port, "m")
               for _ in range(4)]
    for ws in clients:
        ws.close()
    _wait_lanes_free(srv, "m")


def test_ws_oversized_frame_rejected_with_close_1009(engine_portal):
    """A frame header claiming more than MAX_FRAME_BYTES is refused
    BEFORE any payload is buffered: the server answers a close frame
    with status 1009 (Message Too Big) and releases the lane."""
    from repro.portal.ws import MAX_FRAME_BYTES, OP_CLOSE

    srv, portal, c = engine_portal
    ws = WSClient("127.0.0.1", portal.port, "m")
    claim = 2 * MAX_FRAME_BYTES
    ws.sock.sendall(bytes([0x81, 0x80 | 127])
                    + struct.pack(">Q", claim))
    while True:                               # pongs etc. skipped
        opcode, payload = ws._read_frame()
        if opcode == OP_CLOSE:
            break
    assert struct.unpack(">H", payload[:2])[0] == 1009
    ws.sock.close()
    _wait_lanes_free(srv, "m")


# ------------------------------------------------ auth + quotas + 503s
def test_auth_and_quota_negative_paths():
    c = small_compiled("engine")
    srv = SpikeServer(max_batch=4, max_wait_ms=1.0)
    srv.add_model("m", c, window=3, n_sessions=2, seed=0)
    tokens = {"good": TokenQuota(rate=1000.0, burst=1000,
                                 max_inflight=8, name="alice"),
              "slow": TokenQuota(rate=0.001, burst=1, max_inflight=8,
                                 name="bob"),
              "narrow": TokenQuota(rate=1000.0, burst=1000,
                                   max_inflight=0, name="carol")}
    w = windows(np.random.default_rng(0), 1, 3, c.n_axons)[0]
    body_run = {"counts": w.tolist()}
    with srv, Portal(srv, port=0, tokens=tokens) as portal:
        # 401: missing, malformed, unknown
        s, _, b = http_req(portal.port, "POST", "/v1/m/run", body_run)
        assert s == 401 and b["error"]["code"] == "E_AUTH"
        s, _, b = http_req(portal.port, "POST", "/v1/m/run", body_run,
                           token="wrong")
        assert s == 401 and b["error"]["code"] == "E_AUTH"
        # healthz stays open (load balancers don't hold tokens)
        s, _, b = http_req(portal.port, "GET", "/healthz")
        assert s == 200 and b["ok"]

        # authorized traffic flows
        s, _, b = http_req(portal.port, "POST", "/v1/m/run", body_run,
                           token="good")
        assert s == 200

        # 429 rate: burst of 1 at 0.001 req/s -> second request sheds
        s, _, _ = http_req(portal.port, "POST", "/v1/m/run", body_run,
                           token="slow")
        assert s == 200
        s, h, b = http_req(portal.port, "POST", "/v1/m/run", body_run,
                           token="slow")
        assert s == 429 and b["error"]["code"] == "E_QUOTA_RATE"
        assert int(h["retry-after"]) >= 1
        assert b["error"]["retry_after_s"] > 0

        # 429 in-flight: zero concurrency allowed
        s, h, b = http_req(portal.port, "POST", "/v1/m/run", body_run,
                           token="narrow")
        assert s == 429 and b["error"]["code"] == "E_QUOTA_INFLIGHT"

        # per-token counters in /metrics, keyed by label not secret
        s, _, m = http_req(portal.port, "GET", "/metrics?format=json")
        assert m["clients"]["bob"]["rejected_rate"] == 1
        assert m["clients"]["carol"]["rejected_inflight"] == 1
        assert m["clients"]["alice"]["admitted"] == 1
        assert "good" not in m["clients"]


def test_backpressure_full_buffer_is_503_with_retry_after():
    c = small_compiled("engine")
    srv = SpikeServer(max_batch=4, max_wait_ms=1.0, max_pending=0)
    srv.add_model("m", c, window=3, n_sessions=0, seed=0)
    w = windows(np.random.default_rng(0), 1, 3, c.n_axons)[0]
    with pytest.raises(BufferFull):
        srv.submit("m", w)
    with Portal(srv, port=0) as portal:
        s, h, b = http_req(portal.port, "POST", "/v1/m/run",
                           {"counts": w.tolist()})
        assert s == 503 and b["error"]["code"] == "E_BACKPRESSURE"
        assert int(h["retry-after"]) >= 1
        assert b["error"]["retry_after_s"] > 0
        # shutdown -> structured 503 E_SHUTDOWN with Retry-After,
        # not a hang
        srv.shutdown()
        s, h, b = http_req(portal.port, "POST", "/v1/m/run",
                           {"counts": w.tolist()})
        assert s == 503 and b["error"]["code"] == "E_SHUTDOWN"
        assert int(h["retry-after"]) >= 1
        assert b["error"]["retry_after_s"] > 0


# ----------------------------------------------------- retrace parity
def test_portal_adds_zero_compiles():
    """Serving the same window shapes through the portal compiles
    NOTHING beyond what in-process serving already traced."""
    c = small_compiled("engine")
    srv = SpikeServer(max_batch=8, max_wait_ms=3.0)
    m = srv.add_model("m", c, window=4, n_sessions=0, seed=0)
    rng = np.random.default_rng(5)
    zero = np.zeros((4, c.n_axons), np.int32)
    # warm every pow2 bucket via direct lane dispatches
    for B in (1, 2, 4, 8):
        m.dep.run_lanes([-1] * B, np.stack([zero] * B))
    before = compile_counts(m.dep.impl)
    with srv, Portal(srv, port=0) as portal:
        def client(cl):
            for r in range(2):
                s, _, b = http_req(
                    portal.port, "POST", "/v1/m/run",
                    {"counts": windows(rng, 1, 4,
                                       c.n_axons)[0].tolist(),
                     "seed": cl * 10 + r})
                assert s == 200, b
        ts = [threading.Thread(target=client, args=(cl,))
              for cl in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    after = compile_counts(m.dep.impl)
    assert after == before, (before, after)


# ------------------------------------------------------ bridge workers
def test_bridge_worker_roundtrip(engine_portal):
    """One spawned jax-free front-end worker over the unix-socket
    bridge: same results as in-process, errors cross intact."""
    srv, portal_inproc, c = engine_portal
    rng = np.random.default_rng(13)
    w = windows(rng, 1, 4, c.n_axons)[0]
    with Portal(srv, port=0, workers=1) as portal:
        s, _, health = http_req(portal.port, "GET", "/healthz")
        assert s == 200
        # the answering process is the worker, not the dispatcher
        assert health["worker_pid"] != health["pid"]
        s, _, body = http_req(portal.port, "POST", "/v1/m/run",
                              {"counts": w.tolist(), "seed": 77})
        assert s == 200
        ref = srv.submit("m", w, seed=77).result(timeout=120)
        assert body["digest"] == result_digest(ref.spikes,
                                               ref.membrane)
        # a structured error crosses the bridge intact
        s, _, body = http_req(
            portal.port, "POST", "/v1/m/run",
            {"counts": np.zeros((4, c.n_axons + 3), int).tolist()})
        assert s == 400 and body["error"]["code"] == "E_SCHED_WIDTH"
        # websocket through the worker
        ws = WSClient("127.0.0.1", portal.port, "m")
        ws.send_window(counts=w)
        got = ws.recv()
        ws.close()
        ref_lane = deploy(c, seed=0)
        ref_lane.alloc_lanes(4)
        spk, V = ref_lane.run_lanes([ws.session], w[None])
        assert got["digest"] == result_digest(spk[0], V[0])


# ------------------------------------------------ fault tolerance (PR 10)
def test_healthz_503_down_when_dispatcher_dead():
    """An UNSUPERVISED dispatcher death flips /healthz to a 503 whose
    body says status=down — the tri-state health satellite."""
    from repro import faults

    c = small_compiled("engine")
    srv = SpikeServer(max_batch=4, max_wait_ms=1.0, supervise=False)
    srv.add_model("m", c, window=3, n_sessions=0, seed=0)
    w = windows(np.random.default_rng(0), 1, 3, c.n_axons)[0]
    faults.install(faults.FaultPlan().arm("dispatch_crash", at=(1,)))
    try:
        with srv, Portal(srv, port=0) as portal:
            s, _, hz = http_req(portal.port, "GET", "/healthz")
            assert s == 200 and hz["status"] == "ok"
            s, _, b = http_req(portal.port, "POST", "/v1/m/run",
                               {"counts": w.tolist()})
            assert s == 500
            assert "injected fault" in b["error"]["message"]
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                s, _, hz = http_req(portal.port, "GET", "/healthz")
                if s == 503:
                    break
                time.sleep(0.05)
            assert s == 503, hz
            assert hz["status"] == "down" and hz["ok"] is False
            assert "unsupervised" in hz["reason"]
    finally:
        faults.uninstall()


def test_dispatch_restart_503_with_retry_after_then_recovers():
    """A SUPERVISED dispatcher crash surfaces as one structured 503
    E_DISPATCH_RESTART (with Retry-After), the retried request returns
    the bit-exact fault-free answer, and healthz never leaves 200."""
    from repro import faults

    c = small_compiled("engine")
    srv = SpikeServer(max_batch=4, max_wait_ms=1.0)
    srv.add_model("m", c, window=3, n_sessions=0, seed=0)
    w = windows(np.random.default_rng(2), 1, 3, c.n_axons)[0]
    faults.install(faults.FaultPlan().arm("dispatch_crash", at=(1,)))
    try:
        with srv, Portal(srv, port=0) as portal:
            s, h, b = http_req(portal.port, "POST", "/v1/m/run",
                               {"counts": w.tolist(), "seed": 5})
            assert s == 503, b
            assert b["error"]["code"] == "E_DISPATCH_RESTART"
            assert int(h["retry-after"]) >= 1
            assert b["error"]["retry_after_s"] > 0
            for _ in range(60):               # supervised recovery
                s, _, b = http_req(portal.port, "POST", "/v1/m/run",
                                   {"counts": w.tolist(), "seed": 5})
                if s == 200:
                    break
                time.sleep(0.05)
            assert s == 200, b
            ref = deploy(c, seed=0)
            ref.alloc_lanes(1)
            spk, V = ref.run_lanes([-1], w[None], seeds=[5])
            assert b["digest"] == result_digest(spk[0], V[0])
            s, _, hz = http_req(portal.port, "GET", "/healthz")
            assert s == 200 and hz["status"] in ("ok", "degraded")
            assert hz["restarts"] == 1
    finally:
        faults.uninstall()


def test_bridge_client_auto_reconnect(tmp_path):
    """Severed UDS: the in-flight non-idempotent `run` fails with the
    structured 503 E_BRIDGE_DOWN, an idempotent call across the drop is
    parked + replayed on the redial, and post-reconnect runs are
    bit-exact."""
    import asyncio

    from repro.portal.bridge import BridgeClient, BridgeServer
    from repro.portal.gateway import LocalGateway

    c = small_compiled("engine")
    srv = SpikeServer(max_batch=4, max_wait_ms=100.0)
    srv.add_model("m", c, window=3, n_sessions=0, seed=0)
    w = windows(np.random.default_rng(4), 1, 3, c.n_axons)[0]
    uds = str(Path(tmp_path) / "bridge.sock")

    async def scenario():
        bs = await BridgeServer(LocalGateway(srv), uds).start()
        cl = await BridgeClient.open(uds, backoff_base_s=0.01)
        try:
            hz = await cl.healthz()
            assert hz["ok"]
            # non-idempotent op in flight at drop time (the 100 ms
            # batch deadline holds it) -> structured 503, NOT a replay
            run_t = asyncio.ensure_future(
                cl.run("m", {"counts": w.tolist(), "seed": 1}))
            await asyncio.sleep(0.03)
            cl._writer.transport.abort()      # sever the UDS
            with pytest.raises(PortalError) as ei:
                await run_t
            assert ei.value.status == 503
            assert ei.value.code == "E_BRIDGE_DOWN"
            # idempotent op across the drop: parked + replayed
            hz = await cl.healthz()
            assert hz["ok"]
            assert cl.drops >= 1 and cl.reconnects >= 1
            # non-idempotent traffic works again, bit-exact
            out = await cl.run("m", {"counts": w.tolist(), "seed": 2})
            ref = deploy(c, seed=0)
            ref.alloc_lanes(1)
            spk, V = ref.run_lanes([-1], w[None], seeds=[2])
            assert out["digest"] == result_digest(spk[0], V[0])
        finally:
            await cl.close()
            await bs.stop()

    with srv:
        asyncio.run(scenario())


def test_portal_respawns_killed_worker():
    """SIGKILL one of two bridge front ends: the parent reaper respawns
    it (SO_REUSEPORT keeps the port), traffic keeps flowing bit-exactly
    through survivor and respawn alike, healthz returns to ok."""
    c = small_compiled("engine")
    srv = SpikeServer(max_batch=8, max_wait_ms=2.0)
    srv.add_model("m", c, window=3, n_sessions=0, seed=0)
    w = windows(np.random.default_rng(6), 1, 3, c.n_axons)[0]
    with srv, Portal(srv, port=0, workers=2) as portal:
        direct = srv.submit("m", w, seed=3).result(timeout=120)
        ref = result_digest(direct.spikes, direct.membrane)
        portal._procs[0].kill()
        deadline = time.monotonic() + 30
        while portal.worker_restarts < 1 \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        assert portal.worker_restarts >= 1
        for _ in range(6):
            for attempt in range(8):
                try:
                    s, _, b = http_req(
                        portal.port, "POST", "/v1/m/run",
                        {"counts": w.tolist(), "seed": 3})
                    break
                except OSError:
                    # the struck connection belonged to the dead
                    # worker; the retry lands on a live one
                    time.sleep(0.2)
            assert s == 200 and b["digest"] == ref
        s, _, hz = http_req(portal.port, "GET", "/healthz")
        assert s == 200 and hz["status"] == "ok"


def test_worker_process_imports_no_jax():
    """Bridged front-end workers (`python -m repro.portal --worker`)
    must never load jax: on a TPU host the dispatcher process holds the
    chip, and a worker that touched jax would fail or hang."""
    code = ("import sys; import repro.portal.__main__, "
            "repro.portal.bridge, repro.portal.http, repro.portal.ws; "
            "print(sorted(m for m in ('jax', 'numpy') "
            "if m in sys.modules))")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         env={**os.environ,
                              "PYTHONPATH": str(ROOT / "src")},
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"


# ------------------------------------- all four backends, forced devices
def test_portal_parity_all_backends_forced_devices_subprocess():
    proc = subprocess.run(
        [sys.executable, str(Path(__file__).resolve()), "--child"],
        env={"PYTHONPATH": f"{ROOT / 'src'}:{ROOT / 'tests'}",
             "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
        capture_output=True, text=True, timeout=560, cwd=str(ROOT))
    assert proc.returncode == 0, \
        proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "PORTAL-4BACKEND-OK" in proc.stdout


def _child() -> int:
    """8 concurrent HTTP clients vs direct submit on every backend,
    mesh running on 8 forced host devices."""
    for backend in BACKENDS:
        c = small_compiled(backend)
        srv = SpikeServer(max_batch=8, max_wait_ms=3.0)
        srv.add_model("m", c, window=3, n_sessions=0, seed=0)
        rng = np.random.default_rng(17)
        reqs = {cl: windows(rng, 1, 3, c.n_axons)[0]
                for cl in range(8)}
        results = {}
        with srv, Portal(srv, port=0) as portal:
            def client(cl):
                results[cl] = http_req(
                    portal.port, "POST", "/v1/m/run",
                    {"counts": reqs[cl].tolist(), "seed": cl})
            ts = [threading.Thread(target=client, args=(cl,))
                  for cl in range(8)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            for cl, w in reqs.items():
                status, _, body = results[cl]
                assert status == 200, (backend, body)
                ref = srv.submit("m", w, seed=cl).result(timeout=120)
                assert body["digest"] == result_digest(
                    ref.spikes, ref.membrane), (backend, cl)
        print(f"backend {backend}: 8-client HTTP parity ok")
    print("PORTAL-4BACKEND-OK")
    return 0


if __name__ == "__main__" and "--child" in sys.argv:
    raise SystemExit(_child())
