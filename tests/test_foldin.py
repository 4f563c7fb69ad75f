"""Realtime fold-in tests (predictionio_tpu/realtime/foldin.py).

THE acceptance demo lives here: a user unseen at train time sends
events against a LIVE deploy and receives non-degraded personalized
top-k within 2 s — no restart, no /reload, 0 post-warmup recompiles,
0 dropped queries during publication — for the replicated path AND the
sharded+quantized path. Around it: the eventlog/memory incremental
cursor surfaces, solve-kernel parity against an independent numpy
half-step, crash-safe cursor resume, the headroom-exhausted /reload
fallback, the drift probe (clean + corrupted), wire parity with
fold-in off, the /reload-under-burst hot-swap contract, the doctor
fold-in line, and the standalone `pio foldin` runner.
"""

import datetime as dt
import json
import threading
import time

import numpy as np
import pytest

from predictionio_tpu.common import devicewatch
from predictionio_tpu.data.datamap import DataMap
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage import App, Storage
from predictionio_tpu.realtime import foldin
from predictionio_tpu.workflow.create_server import QueryAPI, ServerConfig

APP = "FoldinApp"


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

def _mk_event(u, i, r, minute=0, month=1):
    return Event(
        event="rate", entity_type="user", entity_id=u,
        target_entity_type="item", target_entity_id=i,
        properties=DataMap({"rating": r}),
        event_time=dt.datetime(2021, month, 1, 0, minute % 60,
                               tzinfo=dt.timezone.utc))


def _train(storage, app_name=APP):
    """Seed a parity-preference app (even users like even items) and
    train one small ALS instance; returns the engine."""
    from predictionio_tpu.controller import EngineParams
    from predictionio_tpu.models.recommendation import (
        ALSAlgorithmParams, DataSourceParams, RecommendationEngine,
    )
    from predictionio_tpu.workflow import WorkflowContext, run_train

    apps = storage.get_meta_data_apps()
    app_id = apps.insert(App(0, app_name, None))
    storage.get_events().init(app_id)
    events = []
    for u in range(8):
        for i in range(6):
            events.append(_mk_event(
                f"u{u}", f"i{i}", 5.0 if (u % 2) == (i % 2) else 1.0,
                minute=u * 6 + i))
    storage.get_events().insert_batch(events, app_id)
    engine = RecommendationEngine()
    ep = EngineParams(
        data_source_params=DataSourceParams(appName=app_name),
        algorithm_params_list=(
            ("als", ALSAlgorithmParams(rank=4, numIterations=4,
                                       lambda_=0.05, seed=3)),))
    run_train(WorkflowContext(storage=storage), engine, ep,
              engine_factory="foldin-test",
              params_json={
                  "datasource": {"params": {"appName": app_name}},
                  "algorithms": [{"name": "als", "params": {
                      "rank": 4, "numIterations": 4, "lambda": 0.05,
                      "seed": 3}}]})
    return engine


@pytest.fixture(scope="module")
def trained():
    """Module-scoped trained engine on memory storage: every test
    shares the same model shapes, so the AOT memo pays each compile
    once for the whole file."""
    storage = Storage(env={
        "PIO_STORAGE_SOURCES_M_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
    })
    engine = _train(storage)
    return storage, engine


@pytest.fixture(autouse=True)
def _foldin_env(monkeypatch, tmp_path):
    """Small, constant fold-in shapes: headroom is pinned per-deploy in
    the tests (constant => the AOT memo reuses every program), buckets
    and the per-user cap stay tiny so tier-1 compiles stay cheap, and
    each test gets a private cursor directory."""
    monkeypatch.setenv("PIO_FOLDIN_CURSOR_DIR", str(tmp_path / "cur"))
    monkeypatch.setenv("PIO_FOLDIN_USER_BUCKETS", "1,4")
    monkeypatch.setenv("PIO_FOLDIN_MAX_EVENTS", "16")
    monkeypatch.delenv("PIO_FOLDIN", raising=False)
    yield


HEADROOM = 16   # constant across tests => constant padded shapes


def _api(storage, engine, **kw):
    kw.setdefault("batching", "on")
    kw.setdefault("foldin", "on")
    kw.setdefault("foldin_tick_ms", 20.0)
    kw.setdefault("foldin_headroom", HEADROOM)
    return QueryAPI(storage=storage, engine=engine,
                    config=ServerConfig(**kw))


def _post(api, user, num=4):
    status, body = api.handle(
        "POST", "/queries.json",
        body=json.dumps({"user": user, "num": num}).encode())
    return status, body


def _app_id(storage):
    return storage.get_meta_data_apps().get_by_name(APP).id


# ---------------------------------------------------------------------------
# eventlog incremental cursor surface
# ---------------------------------------------------------------------------

@pytest.fixture()
def el_events(tmp_path):
    storage = Storage(env={
        "PIO_STORAGE_SOURCES_M_TYPE": "memory",
        "PIO_STORAGE_SOURCES_EL_TYPE": "eventlog",
        "PIO_STORAGE_SOURCES_EL_PATH": str(tmp_path / "el"),
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EL",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
    })
    ev = storage.get_events()
    ev.init(1)
    return storage, ev


def test_eventlog_cursor_incremental_read(el_events):
    _storage, ev = el_events
    ev.insert_batch([_mk_event("u1", "i1", 5.0),
                     _mk_event("u2", "i2", 3.0)], 1)
    head = ev.head_cursor(1)
    assert head == {"seq": 0, "row": 2}
    assert ev.cursor_lag(1, cursor={"seq": 0, "row": 0}) == 2
    assert ev.cursor_lag(1, cursor=head) == 0
    ev.insert_batch([_mk_event("u3", "i3", 1.0)], 1)
    cur, cols = ev.read_columns_since(
        1, cursor=head, event_names=["rate", "buy"],
        entity_type="user", target_entity_type="item")
    pool = cols["pool"]
    assert [pool[c] for c in cols["entity_code"]] == ["u3"]
    assert cols["creation_ms"].shape == (1,)
    assert cur == {"seq": 0, "row": 3}
    # a full read from the zero cursor reproduces read_columns
    _c0, full = ev.read_columns_since(1, cursor=None)
    bulk = ev.read_columns(1)
    np.testing.assert_array_equal(full["entity_code"],
                                  bulk["entity_code"])
    np.testing.assert_array_equal(full["rating"], bulk["rating"])


def test_eventlog_cursor_stable_across_compaction(el_events):
    _storage, ev = el_events
    ev.insert_batch([_mk_event(f"u{j}", f"i{j}", 1.0 + j)
                     for j in range(4)], 1)
    cur, _ = ev.read_columns_since(1, cursor=None)
    ev.flush(1)   # buffer -> chunk: positions must not move
    assert ev.cursor_lag(1, cursor=cur) == 0
    _cur2, cols2 = ev.read_columns_since(1, cursor=cur)
    assert cols2["entity_code"].shape[0] == 0   # no replay
    ev.insert_batch([_mk_event("u9", "i9", 2.0)], 1)
    cur3, cols3 = ev.read_columns_since(1, cursor=cur)
    assert [cols3["pool"][c] for c in cols3["entity_code"]] == ["u9"]
    # a mid-chunk cursor sees exactly the suffix
    _c, mid = ev.read_columns_since(1, cursor={"seq": 0, "row": 3})
    assert [mid["pool"][c] for c in mid["entity_code"]] == ["u3", "u9"]
    # a cursor past the head (external reset) clamps instead of raising
    c_over, cols_over = ev.read_columns_since(
        1, cursor={"seq": 99, "row": 0})
    assert cols_over["entity_code"].shape[0] == 0
    assert c_over["seq"] <= 99
    assert ev.cursor_lag(1, cursor=cur3) == 0


@pytest.fixture()
def sq_events(tmp_path):
    storage = Storage(env={
        "PIO_STORAGE_SOURCES_M_TYPE": "memory",
        "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQ_PATH": str(tmp_path / "pio.sqlite"),
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "SQ",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
    })
    ev = storage.get_events()
    ev.init(1)
    return storage, ev


def test_sqlite_cursor_incremental_read(sq_events):
    """The sqlite twin of the eventlog cursor contract (ISSUE 14
    satellite; same assertions as test_eventlog_cursor_incremental_read
    modulo the backend's rowid positions): incremental windows, filters
    narrowing output but not the consumed range, creation_ms present,
    zero-cursor reproducing the bulk read."""
    _storage, ev = sq_events
    ev.insert_batch([_mk_event("u1", "i1", 5.0),
                     _mk_event("u2", "i2", 3.0)], 1)
    head = ev.head_cursor(1)
    assert head == {"seq": 0, "row": 2}
    assert ev.cursor_lag(1, cursor={"seq": 0, "row": 0}) == 2
    assert ev.cursor_lag(1, cursor=head) == 0
    ev.insert_batch([_mk_event("u3", "i3", 1.0)], 1)
    cur, cols = ev.read_columns_since(
        1, cursor=head, event_names=["rate", "buy"],
        entity_type="user", target_entity_type="item")
    pool = cols["pool"]
    assert [pool[c] for c in cols["entity_code"]] == ["u3"]
    assert cols["creation_ms"].shape == (1,)
    assert cur == {"seq": 0, "row": 3}
    # a full read from the zero cursor reproduces read_columns
    _c0, full = ev.read_columns_since(1, cursor=None)
    bulk = ev.read_columns(1)
    assert full["entity_code"].shape == bulk["entity_code"].shape
    assert sorted(full["rating"].tolist()) == \
        sorted(bulk["rating"].tolist())
    # a cursor past the head (external reset) clamps instead of raising
    c_over, cols_over = ev.read_columns_since(1, cursor={"seq": 0,
                                                         "row": 999})
    assert cols_over["entity_code"].shape[0] == 0
    assert c_over["row"] <= 3
    # filters narrow output, never the consumed range: a filtered
    # follower's cursor still converges on the head
    ev.insert_batch([_mk_event("u4", "i4", 2.0)], 1)
    cur2, cols2 = ev.read_columns_since(1, cursor=cur,
                                        event_names=["no-such-event"])
    assert cols2["entity_code"].shape[0] == 0
    assert ev.cursor_lag(1, cursor=cur2) == 0


def test_sqlite_foldin_tail_selected(sq_events):
    """The fold-in worker no longer refuses sqlite: tail_for picks the
    columnar cursor tail (the README backend matrix row)."""
    from predictionio_tpu.realtime import foldin

    _storage, ev = sq_events
    ev.insert_batch([_mk_event("u1", "i1", 5.0)], 1)
    cfg = foldin.FoldinConfig(app_name=APP)
    tail = foldin.tail_for(ev, 1, cfg)
    assert tail is not None and tail.kind == "columnar"
    cur, rows = tail.read({"seq": 0, "row": 0})
    assert rows == [("u1", "i1", "rate", 5.0, rows[0][4])]
    assert tail.lag(cur) == 0
    ev.insert_batch([_mk_event("u9", "i1", 4.0)], 1)
    assert tail.lag(cur) == 1
    cur2, rows2 = tail.read(cur)
    assert [r[0] for r in rows2] == ["u9"]


def test_memory_cursor_surface(memory_storage):
    ev = memory_storage.get_events()
    ev.init(1)
    ev.insert_batch([_mk_event("u1", "i1", 5.0)], 1)
    head = ev.head_cursor(1)
    assert head == 1 and ev.cursor_lag(1, cursor=0) == 1
    eid = ev.insert(_mk_event("u2", "i2", 3.0), 1)
    cur, evs = ev.read_events_since(1, cursor=head)
    assert cur == 2 and [e.entity_id for e in evs] == ["u2"]
    # deletes keep positions (cursor stability) but filter the result
    ev.delete(eid, 1)
    _cur, evs2 = ev.read_events_since(1, cursor=head)
    assert evs2 == []
    assert ev.head_cursor(1) == 2


@pytest.fixture()
def remote_events(el_events):
    """The eventlog store served over a live storage server, consumed
    through the `remote` driver — the fold-in backend matrix's last
    open row (ISSUE 15 satellite)."""
    from predictionio_tpu.data.storage.remote import serve_storage

    storage, ev = el_events
    server = serve_storage(storage, host="127.0.0.1", port=0)
    remote = Storage(env={
        "PIO_STORAGE_SOURCES_R_TYPE": "remote",
        "PIO_STORAGE_SOURCES_R_URL":
            f"http://127.0.0.1:{server.server_address[1]}",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "R",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "R",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "R",
    })
    yield ev, remote.get_events()
    server.shutdown()
    server.server_close()


def test_remote_cursor_tail_matches_backend(remote_events):
    """The remote driver's cursor tail (proto 3: head_cursor /
    cursor_lag DAO calls + the binary /rpc/read_columns_since route)
    answers byte-identically to the backing eventlog store."""
    backend, ev = remote_events
    assert ev.cursor_tail_supported()
    backend.insert_batch([_mk_event("u1", "i1", 5.0),
                          _mk_event("u2", "i2", 3.0)], 1)
    head = ev.head_cursor(1)
    assert head == backend.head_cursor(1)
    assert ev.cursor_lag(1, cursor={"seq": 0, "row": 0}) == 2
    assert ev.cursor_lag(1, cursor=head) == 0
    backend.insert_batch([_mk_event("u3", "i3", 1.0)], 1)
    cur, cols = ev.read_columns_since(
        1, cursor=head, event_names=["rate", "buy"],
        entity_type="user", target_entity_type="item")
    d_cur, d_cols = backend.read_columns_since(
        1, cursor=head, event_names=["rate", "buy"],
        entity_type="user", target_entity_type="item")
    assert cur == d_cur
    assert cols["pool"] == d_cols["pool"]
    for key in ("entity_code", "target_code", "event_code", "rating",
                "time_ms", "creation_ms"):
        np.testing.assert_array_equal(cols[key], d_cols[key])
    assert [cols["pool"][c] for c in cols["entity_code"]] == ["u3"]


def test_remote_foldin_tail_selected(remote_events):
    """The fold-in worker no longer refuses a remote-backed deployment:
    tail_for picks the forwarded columnar cursor tail — and an OLD
    storage server (proto < 3) still refuses cleanly at bind time."""
    from predictionio_tpu.realtime import foldin

    backend, ev = remote_events
    backend.insert_batch([_mk_event("u1", "i1", 5.0)], 1)
    cfg = foldin.FoldinConfig(app_name=APP)
    tail = foldin.tail_for(ev, 1, cfg)
    assert tail is not None and tail.kind == "columnar"
    cur, rows = tail.read({"seq": 0, "row": 0})
    assert [(r[0], r[1], r[2], r[3]) for r in rows] == \
        [("u1", "i1", "rate", 5.0)]
    assert tail.lag(cur) == 0
    backend.insert_batch([_mk_event("u9", "i1", 4.0)], 1)
    assert tail.lag(cur) == 1
    _cur2, rows2 = tail.read(cur)
    assert [r[0] for r in rows2] == ["u9"]
    # an old server: the feature probe says no, the worker refuses at
    # bind time instead of failing per tick
    ev.c._proto = 2
    assert not ev.cursor_tail_supported()
    assert foldin.tail_for(ev, 1, cfg) is None


# ---------------------------------------------------------------------------
# solve-kernel parity vs an independent numpy half-step
# ---------------------------------------------------------------------------

def test_foldin_solve_matches_numpy_half_step():
    rng = np.random.default_rng(11)
    rank, n_items = 4, 12
    V = rng.normal(size=(n_items, rank)).astype(np.float32)
    lam = 0.05
    users = [[(1, 5.0), (3, 1.0), (7, 4.0)],
             [(0, 2.0), (2, 2.5)]]
    bucket, me = 4, 16
    nnz_pad = bucket * me
    item_rows = np.zeros((nnz_pad, rank), np.float32)
    self_idx = np.full((nnz_pad,), bucket, np.int32)
    rating = np.zeros((nnz_pad,), np.float32)
    counts = np.zeros((bucket,), np.int32)
    pos = 0
    for j, ratings in enumerate(users):
        counts[j] = len(ratings)
        for ii, rv in ratings:
            item_rows[pos] = V[ii]
            self_idx[pos] = j
            rating[pos] = rv
            pos += 1
    import jax
    rows = np.asarray(jax.device_get(foldin.foldin_solve(
        item_rows, self_idx, rating, counts, np.float32(lam),
        n_self=bucket, chunk=nnz_pad)))
    for j, ratings in enumerate(users):
        Vs = np.stack([V[ii] for ii, _ in ratings])
        r = np.asarray([rv for _, rv in ratings], np.float32)
        A = Vs.T @ Vs + lam * len(ratings) * np.eye(rank)
        expect = np.linalg.solve(A, Vs.T @ r)
        np.testing.assert_allclose(rows[j], expect, rtol=2e-3, atol=1e-4)
    # padding users solve to ~zero rows
    assert np.abs(rows[len(users):]).max() < 1e-5


# ---------------------------------------------------------------------------
# THE freshness demo: live deploy, unseen user, <= 2 s, nothing dropped
# ---------------------------------------------------------------------------

def _freshness_demo(storage, engine, api_kwargs, expect_items,
                    uid, parity):
    """Shared body for the replicated and sharded+quant demos: query a
    LIVE HTTP deploy for an unseen user while a burst of concurrent
    clients hammers it; the user's events must turn into personalized
    top-k within 2 s with zero dropped queries, zero post-warmup
    recompiles, and no generation change."""
    import http.client

    from predictionio_tpu.data.api.http import make_server

    api = _api(storage, engine, **api_kwargs)
    server = make_server(api, "127.0.0.1", 0)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        assert api._foldin_worker is not None and \
            api._foldin_worker.supported
        recompiles_before = devicewatch.post_warmup_recompiles()
        generation_before = api.generation

        burst_errors = []
        stop = threading.Event()

        def burst(cx):
            # num=10 clamps to the DECLARED k (PIO_AOT_KS), so the
            # 0-recompiles assertion below is honest: any other num
            # would legitimately compile a lazy program (the declared-k
            # contract, same as every serving path)
            try:
                conn = http.client.HTTPConnection("127.0.0.1", port)
                while not stop.is_set():
                    conn.request(
                        "POST", "/queries.json",
                        body=json.dumps({"user": f"u{cx}", "num": 10}),
                        headers={"Content-Type": "application/json"})
                    resp = conn.getresponse()
                    resp.read()
                    if resp.status != 200:
                        burst_errors.append(resp.status)
                        return
                conn.close()
            except Exception as e:   # a dropped query IS a failure
                burst_errors.append(e)

        clients = [threading.Thread(target=burst, args=(cx,))
                   for cx in range(4)]
        for t in clients:
            t.start()
        try:
            # the unseen user's events land mid-burst
            events = [_mk_event(uid, f"i{i}",
                                5.0 if (i % 2) == parity else 1.0)
                      for i in range(6)]
            t0 = time.perf_counter()
            storage.get_events().insert_batch(events, _app_id(storage))
            conn = http.client.HTTPConnection("127.0.0.1", port)
            body = None
            while time.perf_counter() - t0 < 2.0:
                conn.request(
                    "POST", "/queries.json",
                    body=json.dumps({"user": uid, "num": 10}),
                    headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                body = json.loads(resp.read())
                assert resp.status == 200
                if body.get("itemScores"):
                    break
                time.sleep(0.01)
            freshness_s = time.perf_counter() - t0
            conn.close()
        finally:
            stop.set()
            for t in clients:
                t.join(timeout=10)

        assert not burst_errors, burst_errors      # 0 dropped queries
        assert freshness_s <= 2.0, freshness_s     # the contract
        items = [s["item"] for s in body["itemScores"]]
        assert items, body
        # personalized, not degraded: the TOP items are the user's
        # preferred parity class, and the response carries no
        # degraded flag
        assert set(items[:3]) == expect_items, (items, body)
        assert "degraded" not in body
        assert api.generation == generation_before   # no /reload
        assert devicewatch.post_warmup_recompiles() \
            == recompiles_before                     # no recompiles
        # the worker surfaces its state on GET /
        st = api.handle("GET", "/")[1]["foldin"]
        assert st["enabled"] and st["usersFolded"] >= 1
    finally:
        server.shutdown()
        api.close()


def test_freshness_demo_replicated(trained):
    storage, engine = trained
    _freshness_demo(storage, engine, {},
                    expect_items={"i1", "i3", "i5"},
                    uid="fresh_replicated", parity=1)


def test_freshness_demo_sharded_quant(trained):
    storage, engine = trained
    _freshness_demo(storage, engine,
                    {"shard_serving": "on", "serve_quant": "on"},
                    expect_items={"i0", "i2", "i4"},
                    uid="fresh_sq", parity=0)


def test_foldin_updates_existing_user(trained):
    """A user the TRAINER knew keeps serving while fold-in re-solves
    them from new events — their ranking flips to the new signal."""
    storage, engine = trained
    api = _api(storage, engine)
    try:
        worker = api._foldin_worker
        worker.stop()   # drive ticks deterministically
        # u0 (even-liker) suddenly loves odd items, strongly — the new
        # events are strictly NEWER, so the per-user history cap keeps
        # all of them and the re-solve flips the ranking
        evs = [_mk_event("u0", f"i{i}", 5.0 if i % 2 else 0.5, month=3)
               for i in range(6)] * 2
        storage.get_events().insert_batch(evs, _app_id(storage))
        summary = worker.tick()
        assert summary["folded"] >= 1
        status, body = _post(api, "u0", num=10)
        assert status == 200
        items = [s["item"] for s in body["itemScores"]]
        assert set(items[:3]) == {"i1", "i3", "i5"}, items
    finally:
        api.close()


# ---------------------------------------------------------------------------
# wire parity off
# ---------------------------------------------------------------------------

def test_wire_parity_foldin_off(trained, monkeypatch):
    """PIO_FOLDIN=0 / --foldin off answers byte-for-byte what a
    default server answers, and GET / keeps the legacy key set."""
    storage, engine = trained
    queries = [("u1", 5), ("u3", 3), ("nobody", 4)]

    def answers(api):
        return [json.dumps(_post(api, u, n)[1], sort_keys=True)
                for u, n in queries]

    api_default = QueryAPI(storage=storage, engine=engine,
                           config=ServerConfig(batching="on"))
    try:
        baseline = answers(api_default)
        assert "foldin" not in api_default.handle("GET", "/")[1]
        assert api_default._foldin_worker is None
    finally:
        api_default.close()
    monkeypatch.setenv("PIO_FOLDIN", "0")
    api_off = QueryAPI(storage=storage, engine=engine,
                       config=ServerConfig(batching="on", foldin="on"))
    try:
        assert answers(api_off) == baseline
        assert "foldin" not in api_off.handle("GET", "/")[1]
        assert api_off._foldin_worker is None   # env override wins
    finally:
        api_off.close()


# ---------------------------------------------------------------------------
# crash-safe cursor resume + headroom fallback + drift probe
# ---------------------------------------------------------------------------

def test_cursor_resume_refolds_after_restart(trained):
    """A restarted deploy (fresh QueryAPI, same cursor dir) re-folds
    the users the previous worker folded — the persisted fold set is
    the crash-safety contract."""
    storage, engine = trained
    api1 = _api(storage, engine)
    try:
        w1 = api1._foldin_worker
        w1.stop()
        storage.get_events().insert_batch(
            [_mk_event("resumer", f"i{i}", 4.0) for i in range(4)],
            _app_id(storage))
        assert w1.tick()["appended"] == 1
        assert api1.models[0].user_vocab.get("resumer") is not None
    finally:
        api1.close()
    # "restart": a new server over the same storage + cursor dir
    api2 = _api(storage, engine)
    try:
        w2 = api2._foldin_worker
        w2.stop()
        # no new events, but the persisted fold set queues the re-fold
        assert w2.tick()["appended"] == 1
        status, body = _post(api2, "resumer", num=2)
        assert status == 200 and body["itemScores"]
    finally:
        api2.close()


def test_headroom_exhaustion_falls_back_to_reload(trained):
    """More new users than headroom: the worker journals a WARN, the
    /reload fallback bumps the generation with re-grown capacity, and
    every user is servable afterwards."""
    from predictionio_tpu.common import journal

    storage, engine = trained
    journal.clear()
    api = _api(storage, engine, foldin_headroom=2)
    try:
        worker = api._foldin_worker
        worker.stop()
        uids = [f"horde{j}" for j in range(5)]
        for uid in uids:
            storage.get_events().insert_batch(
                [_mk_event(uid, f"i{i}", 4.0) for i in range(3)],
                _app_id(storage))
        gen_before = api.generation
        summary = worker.tick()
        assert summary.get("reloaded") is True
        assert api.generation == gen_before + 1     # hot-swap happened
        # the reload restarted the worker thread; stop it again so the
        # re-fold tick below stays deterministic
        worker.stop()
        worker.tick()
        for uid in uids:
            status, body = _post(api, uid, num=2)
            assert status == 200 and body["itemScores"], uid
        warns = [e for e in journal.snapshot(level="warn")["events"]
                 if e["category"] == "foldin"]
        assert any("headroom" in e["message"] for e in warns)
    finally:
        api.close()


def test_drift_probe_clean_and_corrupted(trained, monkeypatch):
    from predictionio_tpu.common import journal

    # force the host-numpy layout so the corruption below can write
    # the published row in place
    monkeypatch.setenv("PIO_SERVE_DEVICE_MS", "0")
    storage, engine = trained
    api = _api(storage, engine)
    try:
        worker = api._foldin_worker
        worker.stop()
        storage.get_events().insert_batch(
            [_mk_event("drifter", f"i{i}", 4.5 - i * 0.5)
             for i in range(5)], _app_id(storage))
        worker.tick()
        worker._drift_probe()
        st = worker.state()
        assert st["drift"]["ok"] and st["drift"]["recall"] == 1.0
        # corrupt the published row behind the probe's back: the probe
        # must notice and journal a WARN
        journal.clear()
        model = api.models[0]
        ix = model.user_vocab.get("drifter")
        model.user_factors[ix] = -model.user_factors[ix]
        worker._drift_probe()
        st = worker.state()
        assert not st["drift"]["ok"]
        warns = [e for e in journal.snapshot(level="warn")["events"]
                 if e["category"] == "foldin"]
        assert any("drift" in e["message"] for e in warns)
    finally:
        api.close()


# ---------------------------------------------------------------------------
# /reload hot-swap under a concurrent query burst (ROADMAP item 1's
# re-shard-without-restart path, previously untested under load)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("extra", [
    {},                                            # replicated
    {"shard_serving": "on"},                       # re-shard on swap
    {"shard_serving": "on", "serve_quant": "on"},  # re-quantize too
], ids=["replicated", "sharded", "sharded+quant"])
def test_reload_hot_swap_under_burst_drops_nothing(trained, extra):
    storage, engine = trained
    api = QueryAPI(storage=storage, engine=engine,
                   config=ServerConfig(batching="on", **extra))
    try:
        gen_before = api.generation
        errors = []
        stop = threading.Event()

        def burst(cx):
            try:
                while not stop.is_set():
                    status, body = _post(api, f"u{cx % 8}", num=3)
                    if status != 200 or not body.get("itemScores"):
                        errors.append((status, body))
                        return
            except Exception as e:
                errors.append(e)

        clients = [threading.Thread(target=burst, args=(cx,))
                   for cx in range(4)]
        for t in clients:
            t.start()
        try:
            status, _ = api.handle("POST", "/reload")
            assert status == 200
            deadline = time.perf_counter() + 30
            while api.generation == gen_before \
                    and time.perf_counter() < deadline:
                time.sleep(0.02)
            # keep the burst running a moment across the swap window
            time.sleep(0.2)
        finally:
            stop.set()
            for t in clients:
                t.join(timeout=10)
        assert not errors, errors[:3]          # zero dropped queries
        assert api.generation == gen_before + 1
    finally:
        api.close()


# ---------------------------------------------------------------------------
# doctor fold-in line
# ---------------------------------------------------------------------------

def _scrape_stub(metrics_text, device_body):
    blank = {"status": 404, "body": ""}
    return {
        "url": "http://x", "healthz": {"status": 200, "body": "{}"},
        "readyz": {"status": 200, "body": '{"status": "ready"}'},
        "metrics": {"status": 200, "body": metrics_text},
        "traces": {"status": 200, "body": '{"spanCount": 0}'},
        "device": {"status": 200, "body": json.dumps(device_body)},
        "slow": dict(blank), "events": dict(blank),
    }


def test_doctor_foldin_line_states():
    import datetime as _dt

    from predictionio_tpu.tools import doctor

    now = _dt.datetime.now(_dt.timezone.utc).timestamp()
    dev = {"telemetry": True,
           "foldin": {"enabled": True, "cursorLag": 3, "tickMs": 20.0,
                      "lastTickMs": 1.8, "lastTickAt": now,
                      "freshness": {"p99S": 0.12},
                      "drift": {"recall": 1.0, "ok": True}}}
    checks = {c: (s, d) for c, s, d in
              doctor.diagnose(_scrape_stub("", dev))}
    state, detail = checks["foldin"]
    assert state == doctor.OK
    assert "cursor lag 3" in detail and "freshness p99 0.12" in detail
    # stale cursor -> WARN, never RED
    dev_stale = {"telemetry": True,
                 "foldin": {"enabled": True, "cursorLag": 900,
                            "tickMs": 20.0, "lastTickMs": 1.8,
                            "lastTickAt": now - 3600}}
    state, detail = {c: (s, d) for c, s, d in doctor.diagnose(
        _scrape_stub("", dev_stale))}["foldin"]
    assert state == doctor.WARN and "STALE" in detail
    # failed drift probe -> WARN
    dev_drift = {"telemetry": True,
                 "foldin": {"enabled": True, "cursorLag": 0,
                            "tickMs": 20.0, "lastTickAt": now,
                            "drift": {"recall": 0.4, "ok": False}}}
    state, detail = {c: (s, d) for c, s, d in doctor.diagnose(
        _scrape_stub("", dev_drift))}["foldin"]
    assert state == doctor.WARN and "FAILED" in detail
    # no worker: quiet NA line
    state, detail = {c: (s, d) for c, s, d in doctor.diagnose(
        _scrape_stub("", {"telemetry": True}))}["foldin"]
    assert state == doctor.NA and "fold-in off" in detail


# ---------------------------------------------------------------------------
# standalone runner (`pio foldin`)
# ---------------------------------------------------------------------------

def test_standalone_pipeline_folds_into_local_copy(trained):
    """The `pio foldin` soak pipeline (its engine-resolution inputs
    assembled directly — the trained fixture's factory name is not
    importable): loads the persisted model, folds a new user into the
    LOCAL copy, and leaves its cursor in the standalone namespace."""
    import os

    storage, _engine = trained
    from predictionio_tpu.models.recommendation import RecommendationEngine
    from predictionio_tpu.workflow import model_io
    from predictionio_tpu.workflow.create_server import (
        ServerConfig, engine_params_from_instance, resolve_engine_instance,
    )
    instance = resolve_engine_instance(storage, ServerConfig())
    engine_params = engine_params_from_instance(
        RecommendationEngine(), instance)
    blob = storage.get_model_data_models().get(instance.id)
    models = model_io.deserialize_models(blob.models)
    cfg = foldin.config_for(engine_params, tick_ms=20.0)
    cfg.namespace = "standalone"
    prep = foldin.pad_capacity(models, 8)
    worker = foldin.FoldinWorker(storage, cfg)
    worker.bind(models[prep["index"]], generation=1, prep=prep)
    # events land AFTER the worker's head cursor — the stream it tails
    storage.get_events().insert_batch(
        [_mk_event("solo", f"i{i}", 4.0) for i in range(4)],
        _app_id(storage))
    summary = worker.tick()
    assert summary["appended"] >= 1
    assert models[prep["index"]].user_vocab.get("solo") is not None
    assert os.path.exists(worker._store.path)
    assert ".standalone." in worker._store.path


def test_pio_foldin_cli_parses():
    from predictionio_tpu.tools.cli import build_parser
    args = build_parser().parse_args(
        ["foldin", "--tick-ms", "50", "--max-ticks", "3"])
    assert args.command == "foldin" and args.tick_ms == 50.0
    args = build_parser().parse_args(
        ["deploy", "--foldin", "on", "--foldin-tick-ms", "100",
         "--foldin-headroom", "64"])
    assert args.foldin == "on" and args.foldin_headroom == 64


# ---------------------------------------------------------------------------
# AOT + journal wiring
# ---------------------------------------------------------------------------

def test_foldin_programs_registered_and_enumerated():
    from predictionio_tpu.serving import aot

    names = aot.registered_names()
    assert {"foldin_solve", "scatter_user_rows",
            "scatter_user_rows_sharded",
            "scatter_user_rows_sharded_quant",
            "scatter_user_rows_quant"} <= names
    specs = foldin.solve_program_specs(rank=4)
    assert len(specs) == len(foldin.user_buckets())
    assert all(s.name == "foldin_solve" for s in specs)


def test_worker_bind_emits_journal_and_state(trained):
    from predictionio_tpu.common import journal

    storage, engine = trained
    journal.clear()
    api = _api(storage, engine)
    try:
        infos = [e for e in journal.snapshot()["events"]
                 if e["category"] == "foldin"]
        assert any("bound to generation" in e["message"] for e in infos)
        dev = devicewatch.debug_snapshot()
        # devicewatch carries the foldin block only under telemetry;
        # the worker state itself is always live on GET /
        st = api.handle("GET", "/")[1]["foldin"]
        assert st["capacity"]["rows"] >= st["capacity"]["used"]
        assert st["backend"] == "object"
        assert isinstance(dev, dict)
    finally:
        api.close()


# ---------------------------------------------------------------------------
# item fold-in: unseen ITEMS become rankable without a retrain (the
# transposed half-step into every serving layout)
# ---------------------------------------------------------------------------

def _rate_new_item(storage, iid, parity=0, month=7):
    """Known users of one parity class rate a brand-new item highly —
    its solved factors land in that parity's item cluster."""
    evs = [_mk_event(f"u{u}", iid, 5.0, minute=u, month=month)
           for u in range(parity, 8, 2)]
    storage.get_events().insert_batch(evs, _app_id(storage))


@pytest.mark.parametrize("extra", [
    {},
    {"shard_serving": "on", "serve_quant": "on"},
], ids=["replicated", "sharded+quant"])
def test_unseen_item_servable_within_two_ticks(trained, extra):
    """An item the trainer never saw is rated by live events and must
    rank in an even user's top-k after at most two worker ticks — no
    retrain, no /reload, vocab grown in place — on the replicated AND
    sharded+quantized layouts. Freshness is bounded in events here; in
    seconds it is a deployed server's figure (PERF.md 7.4)."""
    storage, engine = trained
    iid = f"inew_{'sq' if extra else 'rep'}"
    api = _api(storage, engine, **extra)
    try:
        worker = api._foldin_worker
        assert worker is not None and worker.supported
        worker.stop()   # drive the ticks deterministically
        generation_before = api.generation
        _rate_new_item(storage, iid, parity=0)
        items = []
        for _tick in range(2):
            worker.tick()
            # u2: an even user no other test of this module's shared
            # store re-rates (test_foldin_updates_existing_user leaves
            # u0 preferring odd items, and every fresh worker re-folds
            # that)
            status, body = _post(api, "u2", num=10)
            assert status == 200
            items = [s["item"] for s in body["itemScores"]]
            if iid in items:
                break
        # rankable AND ranked like the even cluster it was rated into
        assert iid in items[:4], items
        assert api.generation == generation_before   # no /reload
        st = api.handle("GET", "/")[1]["foldin"]
        assert st["itemsFolded"] >= 1
        assert st["itemCapacity"]["rows"] > st["itemCapacity"]["used"]
    finally:
        api.close()


@pytest.mark.parametrize("extra", [
    {},
    {"shard_serving": "on"},
    {"serve_quant": "on"},
    {"shard_serving": "on", "serve_quant": "on"},
], ids=["fp32", "sharded", "int8", "sharded+int8"])
def test_item_foldin_bit_parity_per_layout(trained, extra):
    """The folded item row every layout actually serves equals a fresh
    transposed half-step on the same events — bit-level: fp32 layouts
    carry the solve output verbatim, int8 layouts carry exactly its
    per-row symmetric quantization."""
    import jax

    from predictionio_tpu.ops import quant as quant_mod

    storage, engine = trained
    iid = "ipar_" + "_".join(sorted(extra)) if extra else "ipar_rep"
    api = _api(storage, engine, **extra)
    try:
        worker = api._foldin_worker
        worker.stop()   # drive the tick deterministically
        _rate_new_item(storage, iid, parity=1, month=8)
        summary = worker.tick()
        assert summary["itemsAppended"] >= 1, summary
        model = api.models[0]
        ix = model.item_vocab.get(iid)
        assert ix is not None and ix >= 6   # appended past the 6
                                            # trained items
        # the tick re-solved the rating users AFTER the item folded
        # (items fold first); re-fold the item so both sides of the
        # comparison see the same, now-stable user matrix
        folded, _appended, _deferred = worker._fold_items([iid], {})
        assert folded == 1
        ratings, unknown = worker._gather_item_ratings(
            iid, model.user_vocab)
        assert ratings and unknown == 0
        fresh = np.asarray(jax.device_get(
            worker._solve([ratings], factors=worker._user_factors)[0]),
            np.float32)
        # the worker's host mirror (the user solves' gather source)
        # carries the solve output verbatim on every layout
        np.testing.assert_array_equal(worker._item_factors[int(ix)],
                                      fresh)
        pub = worker._published_item_row(model, int(ix))
        sharding = getattr(model, "sharding", None)
        int8 = (getattr(model, "quant", None) is not None
                or (sharding is not None and sharding.dtype == "int8"))
        expect = fresh
        if int8:
            q, s = quant_mod.quantize_rows(fresh[None])
            expect = quant_mod.dequantize_rows(q, s)[0]
        np.testing.assert_array_equal(pub, expect)
    finally:
        api.close()


def test_trained_items_never_resolved_by_foldin(trained):
    """New events against an item the TRAINER knew must not overwrite
    its batch-solved row with a single half-step (the item-side
    correctness rule; users re-solve, trained items do not)."""
    storage, engine = trained
    api = _api(storage, engine)
    try:
        worker = api._foldin_worker
        worker.stop()
        model = api.models[0]
        ix = model.item_vocab.get("i0")
        before = np.array(worker._item_factors[int(ix)])
        storage.get_events().insert_batch(
            [_mk_event(f"u{u}", "i0", 1.0, month=9) for u in range(4)],
            _app_id(storage))
        summary = worker.tick()
        assert summary.get("itemsFolded", 0) == 0
        np.testing.assert_array_equal(worker._item_factors[int(ix)],
                                      before)
    finally:
        api.close()


def test_item_drift_probe_clean_and_corrupted(trained, monkeypatch):
    from predictionio_tpu.common import journal

    # host-numpy layout so the corruption below can write the
    # published row in place (same trick as the user-side probe test)
    monkeypatch.setenv("PIO_SERVE_DEVICE_MS", "0")
    storage, engine = trained
    api = _api(storage, engine)
    try:
        worker = api._foldin_worker
        worker.stop()
        _rate_new_item(storage, "idrift", parity=0, month=10)
        worker.tick()
        worker._item_drift_probe()
        st = worker.state()
        assert st["itemDrift"]["ok"] and st["itemDrift"]["recall"] == 1.0
        journal.clear()
        model = api.models[0]
        ix = model.item_vocab.get("idrift")
        model.item_factors[int(ix)] = -model.item_factors[int(ix)]
        worker._item_factors[int(ix)] = \
            np.array(model.item_factors[int(ix)])
        worker._item_drift_probe()
        st = worker.state()
        assert not st["itemDrift"]["ok"]
        warns = [e for e in journal.snapshot(level="warn")["events"]
                 if e["category"] == "foldin"]
        assert any("ITEM drift" in e["message"] for e in warns)
    finally:
        api.close()
