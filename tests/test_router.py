"""`pio router` — the replica-fleet front door (workflow/router.py).

The contracts under test:

- membership is health-driven: a dead replica is ejected and re-admitted
  when its readiness probe recovers, with journal events on every
  transition;
- a replica dying mid-burst yields ZERO non-503 client errors — the
  idempotent /queries.json failover retries once on another replica;
- load shedding: an empty rotation or a spent deadline answers
  503 + Retry-After / 504 immediately, never an unbounded queue;
- the coordinated /reload barrier: a fleet never serves two model
  generations to one client (per-client responses are generation-
  monotonic) and zero queries drop during the swap;
- injected latency on ONE replica opens its breaker and shifts traffic
  (tier-1 shape via a delegating slow wrapper; the subprocess twin with
  a real PIO_FAULT_SPEC env rides the slow chaos suite);
- the router is a first-class fleet member: doctor line (membership,
  breakers, added-latency, generation skew), /debug/events.json,
  trace pass-through so `pio trace` assembles router→replica trees.

Tests marked ONLY `chaos` are the tier-1 smoke subset; the subprocess
SIGKILL / fault-spec legs carry chaos+slow and run with `-m chaos`.
"""

import datetime as dt
import http.client
import io
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from predictionio_tpu.common import journal
from predictionio_tpu.controller import EngineParams
from predictionio_tpu.data.api.http import make_server, serve_background
from predictionio_tpu.data.datamap import DataMap
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage import App, Storage
from predictionio_tpu.models.recommendation import (
    ALSAlgorithmParams, DataSourceParams, RecommendationEngine,
)
from predictionio_tpu.workflow import WorkflowContext, run_train
from predictionio_tpu.workflow.create_server import QueryAPI, ServerConfig
from predictionio_tpu.workflow.router import (
    RouterAPI, RouterConfig, _parse_backend,
)

UTC = dt.timezone.utc

#: an importable factory so subprocess replicas can deploy without an
#: engine dir (get_engine resolves module:attr)
FACTORY = "predictionio_tpu.models.recommendation:RecommendationEngine"


def _train_seeded(storage, app_name="RouterApp", seed=3, fresh_app=True):
    """Seed ratings (once) + train one small ALS instance with this
    seed; different seeds give byte-distinguishable answers — the
    reload-barrier test's generation marker."""
    apps = storage.get_meta_data_apps()
    if fresh_app:
        app_id = apps.insert(App(0, app_name, None))
        storage.get_events().init(app_id)
        events = []
        for u in range(8):
            for i in range(6):
                events.append(Event(
                    event="rate", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item", target_entity_id=f"i{i}",
                    properties=DataMap(
                        {"rating": 5.0 if (u % 2) == (i % 2) else 1.0}),
                    event_time=dt.datetime(2021, 1, 1, 0,
                                           (u * 6 + i) % 60, tzinfo=UTC)))
        storage.get_events().insert_batch(events, app_id)
    engine = RecommendationEngine()
    ep = EngineParams(
        data_source_params=DataSourceParams(appName=app_name),
        algorithm_params_list=(
            ("als", ALSAlgorithmParams(rank=4, numIterations=3,
                                       lambda_=0.05, seed=seed)),))
    run_train(WorkflowContext(storage=storage), engine, ep,
              engine_factory=FACTORY,
              params_json={
                  "datasource": {"params": {"appName": app_name}},
                  "algorithms": [{"name": "als", "params": {
                      "rank": 4, "numIterations": 3, "lambda": 0.05,
                      "seed": seed}}]})
    return engine


def _replica(storage, engine, port=0):
    """One query-server replica on the async transport (its shutdown
    severs keep-alive connections — the in-process stand-in for a
    killed process). AOT off: router semantics don't depend on it, and
    ~15 prebuilt deploys of compiled-program memos would bloat the
    shared test process (the PR 14 RSS smoke runs in this process)."""
    api = QueryAPI(storage=storage, engine=engine,
                   config=ServerConfig(batching="on", aot="off"))
    server = make_server(api, "127.0.0.1", port, transport="async")
    threading.Thread(target=server.serve_forever, daemon=True).start()
    return api, server, server.server_address[1]


def _router(ports, **kw):
    kw.setdefault("health_ms", 100.0)
    router = RouterAPI(RouterConfig(
        backends=tuple(f"http://127.0.0.1:{p}" for p in ports), **kw))
    server, rport = serve_background(router)
    return router, server, rport


def _post_query(conn, user="u1", num=3, headers=None):
    body = json.dumps({"user": user, "num": num})
    hdrs = {"Content-Type": "application/json"}
    if headers:
        hdrs.update(headers)
    conn.request("POST", "/queries.json", body=body, headers=hdrs)
    resp = conn.getresponse()
    return resp.status, resp.read(), {k.lower(): v
                                      for k, v in resp.getheaders()}


# ---------------------------------------------------------------------------
# construction + shedding + deadline (no fleet needed)
# ---------------------------------------------------------------------------

def test_router_config_validation():
    with pytest.raises(ValueError):
        RouterAPI(RouterConfig(backends=()))
    with pytest.raises(ValueError):
        RouterAPI(RouterConfig(backends=("http://a:1", "http://a:1/")))
    with pytest.raises(ValueError):
        _parse_backend("https://sec.example:1")
    with pytest.raises(ValueError):
        _parse_backend("no-port")
    assert _parse_backend("http://h:8000/") == ("h", 8000)
    assert _parse_backend("h:8000") == ("h", 8000)


def test_router_sheds_with_no_backend_in_rotation():
    """Every backend dead => readyz 503 and /queries.json answers the
    existing 503 + Retry-After contract immediately."""
    # an unused ephemeral port: nothing listens there
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    dead_port = s.getsockname()[1]
    s.close()
    router = RouterAPI(RouterConfig(
        backends=(f"http://127.0.0.1:{dead_port}",), health_ms=50.0))
    try:
        status, payload = router.handle("GET", "/readyz")
        assert status == 503 and payload["backendsInRotation"] == 0
        out = router.handle("POST", "/queries.json",
                            body=b'{"user": "u1", "num": 1}')
        assert out[0] == 503
        assert out[2]["Retry-After"]
        st = router.handle("GET", "/")[1]
        assert st["router"] is True and st["shedCount"] >= 1
    finally:
        router.close()


def test_router_spent_deadline_504s_instead_of_retrying(memory_storage):
    engine = _train_seeded(memory_storage)
    api, server, port = _replica(memory_storage, engine)
    router, rserver, rport = _router([port])
    try:
        conn = http.client.HTTPConnection("127.0.0.1", rport)
        status, payload, _ = _post_query(
            conn, headers={"X-PIO-Deadline-Ms": "0"})
        assert status == 504, payload
        # and an intact budget serves fine through the same router
        status, payload, _ = _post_query(conn)
        assert status == 200, payload
        conn.close()
    finally:
        rserver.shutdown()
        router.close()
        server.shutdown()
        api.close()


def test_router_inflight_admission_bound(memory_storage):
    """max_inflight=0-available => immediate 503 + Retry-After (the
    bound is structural; no queue grows behind it)."""
    engine = _train_seeded(memory_storage)
    api, server, port = _replica(memory_storage, engine)
    router, rserver, rport = _router([port], max_inflight=1)
    try:
        # exhaust the only slot from under the handler (the admission
        # count is a plain lock-guarded counter so the autopilot's shed
        # ladder can shrink the bound under load)
        with router._lock:
            router._inflight_count += 1
        out = router.handle("POST", "/queries.json",
                            body=b'{"user": "u1", "num": 1}')
        assert out[0] == 503 and out[2]["Retry-After"]
        with router._lock:
            router._inflight_count -= 1
        assert router.handle(
            "POST", "/queries.json",
            body=b'{"user": "u1", "num": 1}')[0] == 200
    finally:
        rserver.shutdown()
        router.close()
        server.shutdown()
        api.close()


# ---------------------------------------------------------------------------
# tenant-aware routing (PR 16): key forwarding, learned labels, per-tenant
# shedding, and per-tenant generation skew
# ---------------------------------------------------------------------------

class _MTStubAPI:
    """A minimal multi-tenant replica double: /readyz reports the
    per-tenant ``generations`` dict, /queries.json records the
    accessKey the router forwarded and answers with the backend's
    X-PIO-Tenant resolution header — the two wire surfaces the
    router's tenant awareness is built on."""

    KEYMAP = {"shop-key": "shop", "news-key": "news"}

    def __init__(self, generations):
        self.generations = dict(generations)
        self.seen_keys = []

    def handle(self, method, path, query=None, body=b"", headers=None):
        path = (path or "/").rstrip("/") or "/"
        if method == "GET" and path in ("/", "/healthz", "/readyz"):
            return 200, {"status": "ready",
                         "generation": max(self.generations.values()),
                         "generations": dict(self.generations)}
        if method == "POST" and path == "/queries.json":
            key = (query or {}).get("accessKey")
            self.seen_keys.append(key)
            if key is None:
                return 200, {"legacy": True}
            tenant = self.KEYMAP.get(key)
            if tenant is None:
                return 401, {"message": "Invalid accessKey."}
            return 200, {"tenant": tenant}, {"X-PIO-Tenant": tenant}
        return 404, {"message": "Not Found"}


def _wait_rotation(router, n, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if router.handle("GET", "/")[1]["inRotation"] == n:
            return
        time.sleep(0.02)
    raise AssertionError(f"fleet never reached {n} backends in rotation")


def _post_keyed(rport, key=None):
    conn = http.client.HTTPConnection("127.0.0.1", rport)
    try:
        path = "/queries.json"
        if key:
            path += f"?accessKey={key}"
        conn.request("POST", path, body=b'{"user": "u1", "num": 1}',
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read() or b"{}"), \
            {k.lower(): v for k, v in resp.getheaders()}
    finally:
        conn.close()


def test_router_tenant_forwarding_learning_and_skew():
    """The access key rides the forwarded URL (the backend resolves
    the SAME key the client presented), the router learns key->tenant
    from X-PIO-Tenant, and per-tenant generation skew across the fleet
    is surfaced by name — while key-less queries keep the bare
    legacy path byte for byte."""
    stub0 = _MTStubAPI({"shop": 3, "news": 4})
    stub1 = _MTStubAPI({"shop": 3, "news": 5})   # news lags: skew
    server0, port0 = serve_background(stub0)
    server1, port1 = serve_background(stub1)
    router, rserver, rport = _router([port0, port1])
    try:
        _wait_rotation(router, 2)
        # keyed query: forwarded WITH the key, answered, learned
        status, payload, _ = _post_keyed(rport, "shop-key")
        assert status == 200 and payload["tenant"] == "shop"
        assert (stub0.seen_keys + stub1.seen_keys) == ["shop-key"]
        assert router._tenant_by_key == {"shop-key": "shop"}
        # key-less query: bare legacy path, no tenant involvement
        status, payload, _ = _post_keyed(rport)
        assert status == 200 and payload == {"legacy": True}
        assert None in (stub0.seen_keys + stub1.seen_keys)
        # an unknown key's 401 passes through untouched
        assert _post_keyed(rport, "wrong")[0] == 401
        # fleet status: per-tenant generations + the skewed tenant named
        st = router.handle("GET", "/")[1]
        assert st["tenantGenerations"] == {"news": [4, 5], "shop": [3]}
        assert st["tenantGenerationSkew"] == ["news"]
    finally:
        rserver.shutdown()
        router.close()
        server0.shutdown()
        server1.shutdown()


def test_router_tenant_inflight_cap_sheds_one_tenant_only():
    """PIO_ROUTER_TENANT_MAX_INFLIGHT: a saturated tenant sheds 503 at
    the front door while other tenants and key-less queries ride on —
    and the cap charges the LEARNED tenant name, not the raw key."""
    stub = _MTStubAPI({"shop": 1, "news": 1})
    server, port = serve_background(stub)
    router, rserver, rport = _router([port], tenant_max_inflight=1)
    try:
        _wait_rotation(router, 1)
        # prime the learned mapping
        assert _post_keyed(rport, "shop-key")[0] == 200
        assert router._tenant_by_key["shop-key"] == "shop"
        # saturate tenant shop from under the handler
        with router._lock:
            router._tenant_inflight["shop"] = 1
        status, payload, headers = _post_keyed(rport, "shop-key")
        assert status == 503
        assert "tenant 'shop' is saturated" in payload["message"]
        assert headers["retry-after"]
        # ...while news and key-less traffic are untouched
        assert _post_keyed(rport, "news-key")[0] == 200
        assert _post_keyed(rport)[0] == 200
        # releasing the slot re-admits shop (no sticky state)
        with router._lock:
            router._tenant_inflight.pop("shop", None)
        assert _post_keyed(rport, "shop-key")[0] == 200
    finally:
        rserver.shutdown()
        router.close()
        server.shutdown()


# ---------------------------------------------------------------------------
# failover + membership (tier-1 chaos smoke)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_replica_kill_under_burst_zero_non_503(memory_storage):
    """THE fleet robustness contract, in-process shape: kill one of two
    replicas under a concurrent burst through the router — zero client
    errors that are not 503 (here: zero errors at all, failover covers
    the torn requests), the dead backend is ejected, and a restart on
    the same port re-admits it."""
    journal.clear()
    engine = _train_seeded(memory_storage)
    api0, server0, port0 = _replica(memory_storage, engine)
    api1, server1, port1 = _replica(memory_storage, engine)
    router, rserver, rport = _router([port0, port1])
    n_clients, per_client = 4, 30
    errors, lock = [], threading.Lock()
    statuses = []
    kill_at = threading.Event()

    def client(cx):
        conn = http.client.HTTPConnection("127.0.0.1", rport)
        my = []
        try:
            for q in range(per_client):
                if cx == 0 and q == 5:
                    kill_at.set()
                status, payload, _ = _post_query(conn, user=f"u{q % 8}")
                my.append(status)
                if status not in (200, 503):
                    raise AssertionError(
                        f"non-503 client error {status}: {payload[:200]}")
        except Exception as e:
            with lock:
                errors.append(e)
        finally:
            conn.close()
            with lock:
                statuses.extend(my)

    threads = [threading.Thread(target=client, args=(cx,))
               for cx in range(n_clients)]
    try:
        for t in threads:
            t.start()
        assert kill_at.wait(10)
        server0.shutdown()     # the in-process "kill": connections sever
        server0.server_close()
        api0.close()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors
        assert statuses.count(200) == n_clients * per_client, (
            statuses.count(200), statuses.count(503))
        # ejected...
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            st = router.handle("GET", "/")[1]
            rot = {b["url"]: b["inRotation"] for b in st["backends"]}
            if not rot[f"http://127.0.0.1:{port0}"]:
                break
            time.sleep(0.05)
        assert not rot[f"http://127.0.0.1:{port0}"], rot
        assert rot[f"http://127.0.0.1:{port1}"]
        # ...journaled...
        ev = journal.snapshot(category="router")
        assert any("ejected" in e["message"] for e in ev["events"])
        # ...and re-admitted on restart at the same port
        api2, server2, _ = _replica(memory_storage, engine, port=port0)
        try:
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                st = router.handle("GET", "/")[1]
                if all(b["inRotation"] for b in st["backends"]):
                    break
                time.sleep(0.05)
            assert all(b["inRotation"] for b in st["backends"]), st
            ev = journal.snapshot(category="router")
            assert any("re-admitted" in e["message"]
                       for e in ev["events"])
        finally:
            server2.shutdown()
            api2.close()
    finally:
        rserver.shutdown()
        router.close()
        server1.shutdown()
        api1.close()


@pytest.mark.chaos
def test_latency_on_one_replica_opens_breaker_and_shifts_traffic(
        memory_storage, monkeypatch):
    """One slow replica (the in-process stand-in for PIO_FAULT_SPEC
    latency — the env-spec twin rides the slow suite): first attempts
    against it time out inside the reserved half-budget, its breaker
    opens after min_calls failures, traffic shifts to the healthy
    replica, and tail latency recovers."""
    monkeypatch.setenv("PIO_BREAKER_MIN_CALLS", "3")
    engine = _train_seeded(memory_storage)
    api0, server0, port0 = _replica(memory_storage, engine)

    class SlowAPI:
        """Delegates to a real QueryAPI, adding 0.5 s to every query."""

        def __init__(self, inner):
            self._inner = inner

        def handle(self, method, path, query=None, body=b"",
                   headers=None):
            if path.rstrip("/") == "/queries.json":
                time.sleep(0.5)
            return self._inner.handle(method, path, query, body, headers)

    api1 = QueryAPI(storage=memory_storage, engine=engine,
                    config=ServerConfig(batching="on", aot="off"))
    server1 = make_server(SlowAPI(api1), "127.0.0.1", 0,
                          transport="async")
    threading.Thread(target=server1.serve_forever, daemon=True).start()
    port1 = server1.server_address[1]
    router, rserver, rport = _router([port0, port1], deadline_ms=600.0)
    slow_name = f"127.0.0.1:{port1}"
    try:
        conn = http.client.HTTPConnection("127.0.0.1", rport)
        slow = next(b for b in router.backends if b.name == slow_name)
        # burst until the breaker converges: each time the health
        # poller re-admits the slow replica, the next request pays a
        # half-budget timeout and records another breaker failure
        deadline = time.monotonic() + 30
        q = 0
        while time.monotonic() < deadline \
                and slow.breaker.state == "closed":
            status, payload, _ = _post_query(conn, user=f"u{q % 8}")
            assert status == 200, payload
            q += 1
        assert slow.breaker.state in ("open", "half-open"), \
            slow.breaker.stats()
        # traffic shifted: with the breaker open, requests no longer
        # pay the slow replica's timeout — the tail recovered (an
        # occasional half-open probe may still pay one, so median)
        post = []
        for q in range(10):
            t0 = time.perf_counter()
            status, payload, _ = _post_query(conn, user=f"u{q % 8}")
            post.append(time.perf_counter() - t0)
            assert status == 200, payload
        conn.close()
        assert sorted(post)[len(post) // 2] < 0.25, post
        assert router.failover_count > 0
    finally:
        rserver.shutdown()
        router.close()
        server0.shutdown()
        api0.close()
        server1.shutdown()
        api1.close()


# ---------------------------------------------------------------------------
# the coordinated hot-swap barrier
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_reload_barrier_zero_drops_and_monotone_generations(
        memory_storage, monkeypatch):
    """THE barrier e2e: two replicas serve model A under a live burst;
    a second instance (different seed => byte-distinguishable answers)
    trains; POST /reload on the ROUTER swaps the fleet. Zero queries
    drop, and no client ever observes new-then-old — per-client
    responses are generation-monotonic, so one client never sees two
    model generations interleaved."""
    # On the CPU backend every (re)load times a query and keeps host or
    # device arrays, whichever was faster; the two layouts differ in a
    # score's last bit, so two replicas of one model could answer
    # different bytes. Pin the layout: the test tells generations apart
    # by bytes.
    monkeypatch.setenv("PIO_SERVE_DEVICE_MS", "1e9")
    engine = _train_seeded(memory_storage, seed=3)
    api0, server0, port0 = _replica(memory_storage, engine)
    api1, server1, port1 = _replica(memory_storage, engine)
    router, rserver, rport = _router([port0, port1], health_ms=60.0)
    probe = json.dumps({"user": "u1", "num": 4})

    def answer(port):
        conn = http.client.HTTPConnection("127.0.0.1", port)
        conn.request("POST", "/queries.json", body=probe,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        out = resp.read()
        conn.close()
        assert resp.status == 200, out
        return out

    answer_a = answer(port0)
    assert answer_a == answer(port1)

    stop = threading.Event()
    errors, lock = [], threading.Lock()
    sequences = {}

    def client(cx):
        conn = http.client.HTTPConnection("127.0.0.1", rport)
        seq = []
        try:
            while not stop.is_set():
                conn.request("POST", "/queries.json", body=probe,
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                payload = resp.read()
                if resp.status != 200:
                    raise AssertionError(
                        f"dropped query: {resp.status} {payload[:200]}")
                seq.append(payload)
        except Exception as e:
            with lock:
                errors.append(e)
        finally:
            conn.close()
            with lock:
                sequences[cx] = seq

    threads = [threading.Thread(target=client, args=(cx,))
               for cx in range(4)]
    try:
        for t in threads:
            t.start()
        time.sleep(0.3)
        # model B: same data, different factor seed
        _train_seeded(memory_storage, seed=4, fresh_app=False)
        conn = http.client.HTTPConnection("127.0.0.1", rport)
        conn.request("POST", "/reload?wait=1", body=b"")
        resp = conn.getresponse()
        reload_out = json.loads(resp.read())
        conn.close()
        assert resp.status == 200
        assert reload_out["reload"].get("ok") is True, reload_out
        time.sleep(0.3)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert not errors, errors
        answer_b = answer(port0)
        assert answer_b != answer_a
        assert answer_b == answer(port1)
        swaps = 0
        for cx, seq in sequences.items():
            assert seq, f"client {cx} served nothing"
            kinds = []
            for payload in seq:
                assert payload in (answer_a, answer_b), payload[:200]
                kinds.append("A" if payload == answer_a else "B")
            # generation-monotonic: A...AB...B, never B after A resumed
            assert "BA" not in "".join(kinds), "".join(kinds)
            swaps += "B" in kinds
        assert swaps == len(sequences), "no client observed the swap"
        st = router.handle("GET", "/")[1]
        assert st["generations"] == [2] and not st["generationSkew"]
    finally:
        stop.set()
        rserver.shutdown()
        router.close()
        server0.shutdown()
        api0.close()
        server1.shutdown()
        api1.close()


def test_reload_barrier_single_backend_in_place(memory_storage):
    """N=1 degenerates to the replica's own zero-downtime in-process
    hot-swap: the lone backend never leaves rotation."""
    engine = _train_seeded(memory_storage)
    api, server, port = _replica(memory_storage, engine)
    router, rserver, rport = _router([port])
    try:
        _train_seeded(memory_storage, seed=9, fresh_app=False)
        status, payload = router.handle("POST", "/reload",
                                        query={"wait": "1"})[:2]
        assert status == 200 and payload["reload"]["ok"] is True
        st = router.handle("GET", "/")[1]
        assert st["backends"][0]["generation"] == 2
        assert st["backends"][0]["inRotation"]
    finally:
        rserver.shutdown()
        router.close()
        server.shutdown()
        api.close()


def test_concurrent_reload_barriers_409(memory_storage):
    engine = _train_seeded(memory_storage)
    api, server, port = _replica(memory_storage, engine)
    router, rserver, rport = _router([port])
    try:
        assert router._reload_lock.acquire(blocking=False)
        try:
            status, payload = router.handle("POST", "/reload")
            assert status == 409, payload
        finally:
            router._reload_lock.release()
    finally:
        rserver.shutdown()
        router.close()
        server.shutdown()
        api.close()


# ---------------------------------------------------------------------------
# fleet-member surfaces: doctor, journal, traces
# ---------------------------------------------------------------------------

def test_router_doctor_line_and_fleet_targets(memory_storage):
    from predictionio_tpu.tools.doctor import run_doctor, run_doctor_fleet

    engine = _train_seeded(memory_storage)
    api, server, port = _replica(memory_storage, engine)
    router, rserver, rport = _router([port])
    try:
        buf = io.StringIO()
        rc = run_doctor(f"http://127.0.0.1:{rport}", out=buf)
        text = buf.getvalue()
        assert rc in (0, 1), text   # reachable; other suites may have
        # left process-wide registry alarms (recompiles, failed AOT
        # builds) that redden UNRELATED checks on this shared /metrics
        router_lines = [ln for ln in text.splitlines()
                        if ln.strip().startswith("router")]
        assert router_lines and "1/1 in rotation" in router_lines[0], text
        assert " ok " in router_lines[0], text
        # --targets: router + replica in one sweep, worst code wins
        buf = io.StringIO()
        rc = run_doctor_fleet([f"http://127.0.0.1:{rport}",
                               f"http://127.0.0.1:{port}"], out=buf)
        assert rc in (0, 1), buf.getvalue()
        assert buf.getvalue().count("pio doctor —") == 2
        # a dead member turns the fleet verdict to 2 (unreachable)
        import socket
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        dead = s.getsockname()[1]
        s.close()
        buf = io.StringIO()
        rc = run_doctor_fleet([f"http://127.0.0.1:{rport}",
                               f"http://127.0.0.1:{dead}"], out=buf)
        assert rc == 2
    finally:
        rserver.shutdown()
        router.close()
        server.shutdown()
        api.close()


def test_router_doctor_generation_skew_warns():
    """A constructed scrape with two generations in the fleet WARNs on
    the router line (the aborted-barrier shape, KNOWN_ISSUES #15)."""
    from predictionio_tpu.tools.doctor import diagnose

    ok = {"status": 200, "body": '{"status": "ok"}'}
    scraped = {
        "url": "http://t", "healthz": dict(ok),
        "readyz": {"status": 200, "body": '{"status": "ready"}'},
        "root": {"status": 200, "body": json.dumps({
            "status": "alive", "router": True,
            "backends": [
                {"url": "http://a:1", "inRotation": True,
                 "generation": 1, "breaker": "closed"},
                {"url": "http://b:2", "inRotation": True,
                 "generation": 2, "breaker": "closed"}],
            "generations": [1, 2], "generationSkew": True,
            "shedCount": 0})},
        "metrics": {"status": 200, "body": ""},
        "traces": {"status": 200, "body": '{"spanCount": 0}'},
        "device": {"status": 200, "body": '{"telemetry": false}'},
        "slow": {"status": 200, "body": '{"enabled": false}'},
        "events": {"status": 200, "body":
                   '{"enabled": true, "events": []}'},
    }
    checks = {c: (s, d) for c, s, d in diagnose(scraped)}
    state, detail = checks["router"]
    assert state == "WARN" and "GENERATION SKEW" in detail


def test_router_journal_rides_debug_events(memory_storage):
    """The router's own /debug/events.json serves the `router` journal
    category — `pio events --targets <router>` treats it as one more
    fleet member with zero new plumbing."""
    journal.clear()
    engine = _train_seeded(memory_storage)
    api, server, port = _replica(memory_storage, engine)
    router, rserver, rport = _router([port])
    try:
        conn = http.client.HTTPConnection("127.0.0.1", rport)
        conn.request("GET", "/debug/events.json?category=router")
        resp = conn.getresponse()
        payload = json.loads(resp.read())
        conn.close()
        assert resp.status == 200 and payload["enabled"]
        assert any(e["category"] == "router" for e in payload["events"])
        # and `pio events --targets <router>,<replica>` merge-tails it
        # like any other fleet member
        from predictionio_tpu.common.traceview import run_events
        buf = io.StringIO()
        rc = run_events([f"http://127.0.0.1:{rport}",
                         f"http://127.0.0.1:{port}"],
                        category="router", out=buf)
        assert rc == 0
        assert "router" in buf.getvalue(), buf.getvalue()
    finally:
        rserver.shutdown()
        router.close()
        server.shutdown()
        api.close()


def test_router_trace_passthrough(memory_storage):
    """An incoming X-PIO-Trace is adopted by the router's transport and
    propagated to the chosen replica: both daemons buffer spans under
    the SAME trace id — the raw material `pio trace` assembles."""
    engine = _train_seeded(memory_storage)
    api, server, port = _replica(memory_storage, engine)
    router, rserver, rport = _router([port])
    trace_id = "00000000deadbeef"
    try:
        conn = http.client.HTTPConnection("127.0.0.1", rport)
        status, payload, _ = _post_query(
            conn, headers={"X-PIO-Trace": f"{trace_id}-0000000000000001"})
        assert status == 200, payload
        conn.request("GET", f"/traces.json?trace_id={trace_id}")
        router_spans = json.loads(conn.getresponse().read())
        conn.close()
        conn = http.client.HTTPConnection("127.0.0.1", port)
        conn.request("GET", f"/traces.json?trace_id={trace_id}")
        replica_spans = json.loads(conn.getresponse().read())
        conn.close()
        r_names = {s["name"] for t in router_spans.get("traces", [])
                   for s in t.get("spans", [])}
        b_names = {s["name"] for t in replica_spans.get("traces", [])
                   for s in t.get("spans", [])}
        assert "route" in r_names, router_spans
        assert any(n.startswith("server:") for n in b_names), replica_spans
    finally:
        rserver.shutdown()
        router.close()
        server.shutdown()
        api.close()


# ---------------------------------------------------------------------------
# subprocess fleet: real SIGKILL + real PIO_FAULT_SPEC (chaos + slow)
# ---------------------------------------------------------------------------

_REPLICA_SCRIPT = """\
import sys
port, url = int(sys.argv[1]), sys.argv[2]
from predictionio_tpu.data.storage import Storage
from predictionio_tpu.workflow.create_server import (
    QueryAPI, ServerConfig, serve,
)
storage = Storage(env={
    "PIO_STORAGE_SOURCES_R_TYPE": "remote",
    "PIO_STORAGE_SOURCES_R_URL": url,
    "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "R",
    "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "R",
    "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "R",
})
api = QueryAPI(storage=storage,
               config=ServerConfig(batching="on", aot="off"))
serve(api, host="127.0.0.1", port=port)
"""


def _free_port():
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_replica(tmp_path, port, storage_url, extra_env=None):
    script = tmp_path / "replica.py"
    script.write_text(_REPLICA_SCRIPT)
    # sys.path[0] of a script run is the SCRIPT's directory — the repo
    # root must ride PYTHONPATH for the child to import the package
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pythonpath = repo_root + os.pathsep + os.environ.get("PYTHONPATH", "")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": pythonpath.rstrip(os.pathsep),
           **(extra_env or {})}
    proc = subprocess.Popen(
        [sys.executable, str(script), str(port), storage_url],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    return proc


def _wait_ready(port, timeout=180.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=2.0)
            conn.request("GET", "/readyz")
            ok = conn.getresponse().status == 200
            conn.close()
            if ok:
                return True
        except OSError:
            pass
        time.sleep(0.25)
    return False


@pytest.fixture()
def _fleet_storage(tmp_path):
    """A file/HTTP-backed fleet substrate: the parent trains into a
    local store and serves it over a storage server; subprocess
    replicas deploy through the `remote` driver."""
    from predictionio_tpu.data.storage.remote import serve_storage

    backing = Storage(env={
        "PIO_STORAGE_SOURCES_M_TYPE": "memory",
        "PIO_STORAGE_SOURCES_EL_TYPE": "eventlog",
        "PIO_STORAGE_SOURCES_EL_PATH": str(tmp_path / "el"),
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EL",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
    })
    server = serve_storage(backing, host="127.0.0.1", port=0)
    url = f"http://127.0.0.1:{server.server_address[1]}"
    yield backing, url
    server.shutdown()
    server.server_close()


@pytest.mark.chaos
@pytest.mark.slow
def test_sigkill_replica_under_burst(tmp_path, _fleet_storage):
    """The real thing: two replica PROCESSES behind the router; SIGKILL
    one mid-burst — zero non-503 client errors, ejection, and
    re-admission when a fresh process takes the port back."""
    backing, url = _fleet_storage
    _train_seeded(backing)
    ports = [_free_port(), _free_port()]
    procs = [_spawn_replica(tmp_path, p, url) for p in ports]
    router = rserver = None
    try:
        for p in ports:
            assert _wait_ready(p), f"replica on {p} never became ready"
        router, rserver, rport = _router(ports)
        errors, statuses, lock = [], [], threading.Lock()
        killed = threading.Event()

        def client(cx):
            conn = http.client.HTTPConnection("127.0.0.1", rport)
            try:
                for q in range(25):
                    if cx == 0 and q == 4:
                        procs[0].kill()          # SIGKILL, mid-burst
                        procs[0].wait(timeout=10)
                        killed.set()
                    status, payload, _ = _post_query(conn,
                                                     user=f"u{q % 8}")
                    with lock:
                        statuses.append(status)
                    if status not in (200, 503):
                        raise AssertionError(
                            f"non-503 error {status}: {payload[:200]}")
            except Exception as e:
                with lock:
                    errors.append(e)
            finally:
                conn.close()

        threads = [threading.Thread(target=client, args=(cx,))
                   for cx in range(4)]
        for t in threads:
            t.start()
        assert killed.wait(30)
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors
        assert statuses.count(200) == len(statuses), statuses
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            st = router.handle("GET", "/")[1]
            rot = {b["url"]: b["inRotation"] for b in st["backends"]}
            if not rot[f"http://127.0.0.1:{ports[0]}"]:
                break
            time.sleep(0.1)
        assert not rot[f"http://127.0.0.1:{ports[0]}"], rot
        # a fresh process re-takes the port: re-admission is automatic
        procs[0] = _spawn_replica(tmp_path, ports[0], url)
        assert _wait_ready(ports[0])
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            st = router.handle("GET", "/")[1]
            if all(b["inRotation"] for b in st["backends"]):
                break
            time.sleep(0.1)
        assert all(b["inRotation"] for b in st["backends"]), st
    finally:
        if rserver is not None:
            rserver.shutdown()
        if router is not None:
            router.close()
        for proc in procs:
            proc.kill()


@pytest.mark.chaos
@pytest.mark.slow
def test_fault_spec_latency_shifts_traffic(tmp_path, _fleet_storage,
                                           monkeypatch):
    """PIO_FAULT_SPEC latency injected in ONE replica process: the
    router's reserved half-budget times the slow attempts out, the
    backend's breaker opens, traffic shifts, and the tail recovers."""
    monkeypatch.setenv("PIO_BREAKER_MIN_CALLS", "3")
    backing, url = _fleet_storage
    _train_seeded(backing)
    ports = [_free_port(), _free_port()]
    procs = [
        _spawn_replica(tmp_path, ports[0], url),
        _spawn_replica(
            tmp_path, ports[1], url,
            extra_env={"PIO_FAULT_SPEC": "latency:1:500@/queries.json"}),
    ]
    router = rserver = None
    try:
        for p in ports:
            assert _wait_ready(p), f"replica on {p} never became ready"
        router, rserver, rport = _router(ports, deadline_ms=600.0)
        conn = http.client.HTTPConnection("127.0.0.1", rport)
        slow = next(b for b in router.backends
                    if b.name == f"127.0.0.1:{ports[1]}")
        # burst until the breaker converges (see the in-process twin)
        deadline = time.monotonic() + 30
        q = 0
        while time.monotonic() < deadline \
                and slow.breaker.state == "closed":
            status, payload, _ = _post_query(conn, user=f"u{q % 8}")
            assert status == 200, payload
            q += 1
        assert slow.breaker.state in ("open", "half-open"), \
            slow.breaker.stats()
        post = []
        for q in range(10):
            t0 = time.perf_counter()
            status, payload, _ = _post_query(conn, user=f"u{q % 8}")
            post.append(time.perf_counter() - t0)
            assert status == 200, payload
        conn.close()
        assert sorted(post)[len(post) // 2] < 0.3, post
    finally:
        if rserver is not None:
            rserver.shutdown()
        if router is not None:
            router.close()
        for proc in procs:
            proc.kill()
