"""Engine (deploy) server tests — CreateServer parity: instance resolution,
model load + device placement, /queries.json hot path, /reload hot-swap,
/stop, feedback loop to a live event server (CreateServer.scala:105-697)."""

import dataclasses
import json
import time
import urllib.request

import pytest

from predictionio_tpu.controller import EngineParams
from predictionio_tpu.data.api import EventAPI
from predictionio_tpu.data.api.http import serve_background
from predictionio_tpu.data.datamap import DataMap
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage import AccessKey, App
from predictionio_tpu.models.recommendation import (
    ALSAlgorithmParams, DataSourceParams, RecommendationEngine,
)
from predictionio_tpu.workflow import WorkflowContext, run_train
from predictionio_tpu.workflow.create_server import (
    QueryAPI, ServerConfig, engine_params_from_instance,
    resolve_engine_instance, undeploy,
)
from predictionio_tpu.workflow.server_plugins import (
    OUTPUT_BLOCKER, EngineServerPlugin, EngineServerPluginContext,
)


@pytest.fixture()
def trained(memory_storage):
    """App with events + one COMPLETED EngineInstance."""
    apps = memory_storage.get_meta_data_apps()
    app_id = apps.insert(App(0, "MyApp1", None))
    memory_storage.get_events().init(app_id)
    import datetime as dt
    from predictionio_tpu.data import store
    events = []
    minute = 0
    for u in range(8):
        for i in range(6):
            minute += 1
            r = 5.0 if (u % 2) == (i % 2) else 1.0
            events.append(Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{i}",
                properties=DataMap({"rating": r}),
                event_time=dt.datetime(2021, 1, 1, 0, minute % 60,
                                       tzinfo=dt.timezone.utc)))
    store.write(events, app_id, storage=memory_storage)

    engine = RecommendationEngine()
    ep = EngineParams(
        data_source_params=DataSourceParams(appName="MyApp1"),
        algorithm_params_list=(
            ("als", ALSAlgorithmParams(rank=4, numIterations=5,
                                       lambda_=0.05, seed=3)),))
    ctx = WorkflowContext(storage=memory_storage)
    instance_id = run_train(
        ctx, engine, ep,
        engine_factory=("predictionio_tpu.models.recommendation"
                        ":RecommendationEngine"),
        params_json={
            "datasource": {"params": {"appName": "MyApp1"}},
            "algorithms": [{"name": "als", "params": {
                "rank": 4, "numIterations": 5, "lambda": 0.05, "seed": 3}}],
        })
    return memory_storage, app_id, instance_id


def test_resolve_and_params_roundtrip(trained):
    storage, _app_id, instance_id = trained
    instance = resolve_engine_instance(storage, ServerConfig())
    assert instance.id == instance_id and instance.status == "COMPLETED"
    ep = engine_params_from_instance(RecommendationEngine(), instance)
    assert ep.data_source_params.appName == "MyApp1"
    name, ap = ep.algorithm_params_list[0]
    assert name == "als" and ap.rank == 4 and ap.lambda_ == 0.05

    with pytest.raises(ValueError, match="not found"):
        resolve_engine_instance(
            storage, ServerConfig(engine_instance_id="missing"))


def test_resolve_refuses_incomplete(memory_storage):
    with pytest.raises(ValueError, match="No valid engine instance"):
        resolve_engine_instance(memory_storage, ServerConfig())


def test_query_roundtrip_and_status(trained):
    storage, _app_id, _iid = trained
    api = QueryAPI(storage=storage)
    status, body = api.handle(
        "POST", "/queries.json", body=json.dumps(
            {"user": "u1", "num": 4}).encode())
    assert status == 200
    assert len(body["itemScores"]) == 4
    scores = [s["score"] for s in body["itemScores"]]
    assert scores == sorted(scores, reverse=True)
    # odd user should prefer odd items (the training signal)
    assert body["itemScores"][0]["item"] in {"i1", "i3", "i5"}

    # unknown user -> empty itemScores, not an error
    status, body = api.handle(
        "POST", "/queries.json", body=json.dumps(
            {"user": "nobody", "num": 4}).encode())
    assert status == 200 and body == {"itemScores": []}

    # malformed query -> 400
    status, _ = api.handle("POST", "/queries.json", body=b"{")
    assert status == 400
    status, _ = api.handle(
        "POST", "/queries.json", body=json.dumps({"user": "u1"}).encode())
    assert status == 400

    status, info = api.handle("GET", "/")
    assert status == 200 and info["requestCount"] == 2
    assert info["engineInstance"]["id"] == _iid
    assert info["avgServingSec"] > 0


def test_reload_hot_swap(trained):
    storage, app_id, first_id = trained
    api = QueryAPI(storage=storage)
    assert api.engine_instance.id == first_id

    # train a second instance, then hot-swap
    engine = RecommendationEngine()
    ep = EngineParams(
        data_source_params=DataSourceParams(appName="MyApp1"),
        algorithm_params_list=(
            ("als", ALSAlgorithmParams(rank=3, numIterations=4,
                                       lambda_=0.05, seed=5)),))
    second_id = run_train(
        WorkflowContext(storage=storage), engine, ep,
        engine_factory=("predictionio_tpu.models.recommendation"
                        ":RecommendationEngine"),
        params_json={"datasource": {"params": {"appName": "MyApp1"}},
                     "algorithms": [{"name": "als", "params": {
                         "rank": 3, "numIterations": 4, "lambda": 0.05,
                         "seed": 5}}]})
    status, _ = api.handle("POST", "/reload")
    assert status == 200
    for _ in range(100):
        if api.engine_instance.id == second_id:
            break
        time.sleep(0.05)
    assert api.engine_instance.id == second_id
    status, body = api.handle(
        "POST", "/queries.json",
        body=json.dumps({"user": "u1", "num": 2}).encode())
    assert status == 200 and len(body["itemScores"]) == 2


def test_stop_flag_and_undeploy(trained):
    storage, _app_id, _iid = trained
    api = QueryAPI(storage=storage)
    assert not api.stop_requested
    status, body = api.handle("POST", "/stop")
    assert status == 200 and not undeploy("localhost", 1)  # nothing listening
    assert api.stop_requested


def test_output_blocker_plugin(trained):
    storage, _app_id, _iid = trained

    class Cap(EngineServerPlugin):
        plugin_name = "cap"
        plugin_description = "keeps only the top result"
        plugin_type = OUTPUT_BLOCKER

        def process(self, engine_instance, query_obj, prediction_obj, context):
            return {"itemScores": prediction_obj["itemScores"][:1]}

    api = QueryAPI(storage=storage,
                   plugin_context=EngineServerPluginContext([Cap()]))
    status, body = api.handle(
        "POST", "/queries.json",
        body=json.dumps({"user": "u1", "num": 4}).encode())
    assert status == 200 and len(body["itemScores"]) == 1
    status, desc = api.handle("GET", "/plugins.json")
    assert "cap" in desc["plugins"]["outputblockers"]


def test_feedback_loop_to_event_server(trained):
    storage, app_id, instance_id = trained
    storage.get_meta_data_access_keys().insert(AccessKey("fk", app_id, ()))
    event_api = EventAPI(storage=storage)
    server, port = serve_background(event_api)
    try:
        api = QueryAPI(
            storage=storage,
            config=ServerConfig(feedback=True, event_server_port=port,
                                access_key="fk"))
        status, _body = api.handle(
            "POST", "/queries.json",
            body=json.dumps({"user": "u1", "num": 2}).encode())
        assert status == 200
        # wait for the async feedback POST to land
        got = None
        for _ in range(100):
            sts, got = event_api.handle(
                "GET", "/events.json",
                {"accessKey": "fk", "entityType": "pio_pr"})
            if sts == 200:
                break
            time.sleep(0.05)
        assert sts == 200 and len(got) == 1
        fb = got[0]
        assert fb["event"] == "predict"
        props = fb["properties"]
        assert props["engineInstanceId"] == instance_id
        assert props["query"] == {"user": "u1", "num": 2}
        assert len(props["prediction"]["itemScores"]) == 2
    finally:
        server.shutdown()


def test_http_transport_smoke(trained):
    storage, _app_id, _iid = trained
    api = QueryAPI(storage=storage)
    server, port = serve_background(api)
    try:
        req = urllib.request.Request(
            f"http://localhost:{port}/queries.json",
            data=json.dumps({"user": "u2", "num": 3}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req) as r:
            assert r.status == 200
            assert len(json.loads(r.read())["itemScores"]) == 3
    finally:
        server.shutdown()
        api.close()


# ---------------------------------------------------------------------------
# micro-batched serving (serving/batcher.py wired behind ServerConfig)
# ---------------------------------------------------------------------------

QUERY_SET = [{"user": f"u{k % 8}", "num": 4} for k in range(10)] + [
    {"user": "nobody", "num": 4},      # unknown user -> empty
    {"user": "u3", "num": 2},          # smaller k in a mixed batch
]


def _post(api, q):
    return api.handle("POST", "/queries.json", body=json.dumps(q).encode())


def test_batching_off_is_the_legacy_inline_path(trained):
    """`batching: off` must not construct a batcher and must answer
    byte-for-byte what the inline supplement -> predict -> serve chain
    produces (replicated here literally)."""
    storage, _app_id, _iid = trained
    api = QueryAPI(storage=storage, config=ServerConfig(batching="off"))
    assert api._batcher is None
    status, info = api.handle("GET", "/")
    assert status == 200 and info["batching"] == {"enabled": False}
    from predictionio_tpu.workflow import json_extractor
    for q in QUERY_SET:
        status, body = _post(api, q)
        assert status == 200
        query = json_extractor.extract_query(
            api.algorithms[0].query_class, json.dumps(q).encode())
        supplemented = api.serving.supplement(query)
        predictions = [a.predict(m, supplemented)
                       for a, m in zip(api.algorithms, api.models)]
        expected = json_extractor.to_json_obj(
            api.serving.serve(query, predictions))
        assert json.dumps(body) == json.dumps(expected)


def test_batched_responses_match_sequential(trained, monkeypatch):
    """Acceptance parity: under `batching: on` (queries sent alone AND as
    a coalesced concurrent burst, exercising different padding buckets)
    responses are identical to the sequential single-query path."""
    import threading

    monkeypatch.setenv("PIO_SERVE_DEVICE_MS", "1e9")  # pin the device path
    storage, _app_id, _iid = trained
    api_off = QueryAPI(storage=storage, config=ServerConfig(batching="off"))
    api_on = QueryAPI(storage=storage)     # auto -> ALS is batch-capable
    try:
        assert api_on._batcher is not None
        expected = [_post(api_off, q) for q in QUERY_SET]

        # one at a time through the batcher: batch=1 degenerate case
        for q, (st_exp, body_exp) in zip(QUERY_SET, expected):
            st, body = _post(api_on, q)
            assert (st, json.dumps(body)) == (st_exp, json.dumps(body_exp))

        # concurrent burst: queries coalesce into multi-query batches
        results = [None] * len(QUERY_SET)

        def hit(k):
            results[k] = _post(api_on, QUERY_SET[k])

        threads = [threading.Thread(target=hit, args=(k,))
                   for k in range(len(QUERY_SET))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        for (st, body), (st_exp, body_exp) in zip(results, expected):
            assert (st, json.dumps(body)) == (st_exp, json.dumps(body_exp))

        status, info = api_on.handle("GET", "/")
        b = info["batching"]
        assert b["enabled"] and b["queries"] == 2 * len(QUERY_SET)
        assert b["rejected"] == 0
        assert sum(b["batchSizeHist"].values()) == b["batches"]
        assert b["avgFlushMs"] >= 0 and b["avgQueueWaitMs"] >= 0
    finally:
        api_on.close()
        api_off.close()


def test_bucket_padding_never_changes_results(trained, monkeypatch):
    """predict_batch through different padding-bucket configurations must
    return identical results (padding rows are dropped before results are
    built), and items/ordering must match sequential predict()."""
    monkeypatch.setenv("PIO_SERVE_DEVICE_MS", "1e9")  # pin the device path
    storage, _app_id, _iid = trained
    api = QueryAPI(storage=storage)
    try:
        algo, model = api.algorithms[0], api.models[0]
        from predictionio_tpu.models.recommendation.engine import Query
        queries = [Query(user=f"u{k}", num=3) for k in range(3)]  # B=3
        queries.append(Query(user="nobody", num=3))

        def run(buckets):
            monkeypatch.setenv("PIO_SERVE_BUCKETS", buckets)
            return algo.predict_batch(model, queries)

        by_bucket = {b: run(b) for b in ("4", "16", "64", "1,4,16,64")}
        baseline = by_bucket["4"]
        for b, res in by_bucket.items():
            assert res == baseline, f"bucket config {b} changed results"
        monkeypatch.delenv("PIO_SERVE_BUCKETS")
        seq = [algo.predict(model, q) for q in queries]
        assert baseline == seq  # device path: bitwise at this scale
        assert baseline[3].itemScores == ()
    finally:
        api.close()


def _gated_batcher(api):
    """Wrap the deployed batcher's flush so batches block on a gate —
    deterministic queue buildup for the admission-control tests. Each
    batch that reaches the callback releases `entered` once: two
    acquires prove both lanes busy inside a flush (i.e. the next
    submits can only queue, not be picked up)."""
    import threading

    entered = threading.Semaphore(0)
    gate = threading.Event()
    batcher = api._batcher
    real = batcher._flush_fn

    def gated(items):
        entered.release()
        gate.wait(30)
        return real(items)

    batcher._flush_fn = gated
    return gate, entered


def test_admission_control_503_retry_after(trained):
    import threading

    storage, _app_id, _iid = trained
    api = QueryAPI(storage=storage, config=ServerConfig(
        batching="on", batch_max_size=1, batch_max_delay_ms=1.0,
        batch_max_queue=2))
    gate, entered = _gated_batcher(api)
    try:
        threads = [threading.Thread(
            target=_post, args=(api, {"user": "u1", "num": 2}))]
        threads[0].start()
        assert entered.acquire(timeout=10)
        threads.append(threading.Thread(
            target=_post, args=(api, {"user": "u1", "num": 2})))
        threads[1].start()         # a full batch of one: the second lane
        assert entered.acquire(timeout=10)   # both lanes busy in a flush
        for _ in range(2):         # fill the queue to max_queue
            t = threading.Thread(
                target=_post, args=(api, {"user": "u1", "num": 2}))
            t.start()
            threads.append(t)
        deadline = time.time() + 10
        while time.time() < deadline:
            with api._batcher._cond:
                if len(api._batcher._q) >= 2:
                    break
            time.sleep(0.01)
        response = _post(api, {"user": "u1", "num": 2})
        assert len(response) == 3
        status, body, headers = response
        assert status == 503 and "saturated" in body["message"]
        assert int(headers["Retry-After"]) >= 1
        gate.set()
        for t in threads:
            t.join(30)
        status, info = api.handle("GET", "/")
        assert info["batching"]["rejected"] == 1
    finally:
        gate.set()
        api.close()


def test_admission_control_503_over_http(trained):
    """The transport forwards the 3-tuple's Retry-After header."""
    import threading

    storage, _app_id, _iid = trained
    api = QueryAPI(storage=storage, config=ServerConfig(
        batching="on", batch_max_size=1, batch_max_delay_ms=1.0,
        batch_max_queue=1))
    gate, entered = _gated_batcher(api)
    server, port = serve_background(api)
    try:
        def post_http():
            req = urllib.request.Request(
                f"http://localhost:{port}/queries.json",
                data=json.dumps({"user": "u1", "num": 2}).encode(),
                headers={"Content-Type": "application/json"}, method="POST")
            with urllib.request.urlopen(req) as r:
                return r.status

        threads = [threading.Thread(target=post_http)]
        threads[0].start()
        assert entered.acquire(timeout=10)
        threads.append(threading.Thread(target=post_http))
        threads[1].start()         # a full batch of one: the second lane
        assert entered.acquire(timeout=10)   # both lanes busy in a flush
        threads.append(threading.Thread(target=post_http))
        threads[2].start()         # fills the 1-slot queue
        deadline = time.time() + 10
        while time.time() < deadline:
            with api._batcher._cond:
                if len(api._batcher._q) >= 1:
                    break
            time.sleep(0.01)
        with pytest.raises(urllib.error.HTTPError) as ei:
            post_http()
        assert ei.value.code == 503
        assert int(ei.value.headers["Retry-After"]) >= 1
        gate.set()
        for t in threads:
            t.join(30)
    finally:
        gate.set()
        server.shutdown()
        api.close()


def test_concurrent_burst_smoke(trained):
    """Tier-1 smoke: a 4-query concurrent burst through the batcher over
    real HTTP on CPU — every response correct, stats consistent."""
    import threading

    storage, _app_id, _iid = trained
    api = QueryAPI(storage=storage)
    assert api._batcher is not None      # auto: ALS is batch-capable
    server, port = serve_background(api)
    try:
        out = [None] * 4

        def post_http(k):
            req = urllib.request.Request(
                f"http://localhost:{port}/queries.json",
                data=json.dumps({"user": f"u{k}", "num": 3}).encode(),
                headers={"Content-Type": "application/json"}, method="POST")
            with urllib.request.urlopen(req) as r:
                out[k] = (r.status, json.loads(r.read()))

        threads = [threading.Thread(target=post_http, args=(k,))
                   for k in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        for status, body in out:
            assert status == 200 and len(body["itemScores"]) == 3
        _, info = api.handle("GET", "/")
        assert info["requestCount"] == 4
        b = info["batching"]
        assert b["queries"] == 4 and b["rejected"] == 0
        assert sum(b["batchSizeHist"].values()) == b["batches"] <= 4
    finally:
        server.shutdown()
        api.close()


def test_reload_swaps_batcher(trained):
    storage, _app_id, _iid = trained
    api = QueryAPI(storage=storage)
    first = api._batcher
    assert first is not None
    api._reload()           # synchronous variant of POST /reload
    assert api._batcher is not None and api._batcher is not first
    assert first._closed    # retired batcher was drained and closed
    status, body = _post(api, {"user": "u1", "num": 2})
    assert status == 200 and len(body["itemScores"]) == 2
    api.close()


@pytest.mark.slow
def test_concurrent_load_throughput(trained):
    """Sustained concurrent load through the batcher: 16 keep-alive
    clients x 25 queries, no rejects, everything coalesces correctly."""
    import http.client
    import threading

    storage, _app_id, _iid = trained
    api = QueryAPI(storage=storage)
    server, port = serve_background(api)
    n_clients, per_client = 16, 25
    errors = []
    try:
        def client(cx):
            try:
                conn = http.client.HTTPConnection("localhost", port)
                for q in range(per_client):
                    conn.request(
                        "POST", "/queries.json",
                        body=json.dumps(
                            {"user": f"u{(cx + q) % 8}", "num": 4}),
                        headers={"Content-Type": "application/json"})
                    resp = conn.getresponse()
                    body = json.loads(resp.read())
                    assert resp.status == 200, body
                    assert len(body["itemScores"]) == 4
                conn.close()
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=client, args=(cx,))
                   for cx in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not errors, errors[:3]
        _, info = api.handle("GET", "/")
        b = info["batching"]
        assert b["queries"] == n_clients * per_client
        assert b["rejected"] == 0
        # concurrency must actually coalesce: fewer batches than queries
        assert b["batches"] < b["queries"]
    finally:
        server.shutdown()
        api.close()


# ---------------------------------------------------------------------------
# the codec on the request path (workflow/json_extractor.py plans, the
# finite check folded into the pass that builds the reply)
# ---------------------------------------------------------------------------

class _FixedTopK:
    """Stands where a sharded layout's device program does: the fetched
    (bucket, k) arrays are fixed float32 / int32, whoever asks. Row r is
    a rotation of one row; index 6 is fold-in headroom past the six-item
    vocabulary and never surfaces."""
    n_shards = 1
    VALS = [3.1415927, 2.7182817, 1.0e-7, 0.1, -0.0, -1.5, -123456.79]
    IDX = [4, 2, 0, 6, 5, 1, 3]

    def __init__(self, poison=None):
        import numpy as np
        self.vals = np.asarray([self.VALS[r % 7:] + self.VALS[:r % 7]
                                for r in range(64)], np.float32)
        self.idx = np.asarray([self.IDX[r % 7:] + self.IDX[:r % 7]
                               for r in range(64)], np.int32)
        if poison is not None:
            self.vals[:, 1] = poison

    def topk(self, pix, k):
        return self.vals[:len(pix), :k], self.idx[:len(pix), :k]


def _fixed_api(storage, poison=None, **kw):
    api = QueryAPI(storage=storage, **kw)
    api.models[0] = dataclasses.replace(
        api.models[0], sharding=_FixedTopK(poison))
    return api


def _wire(api, q):
    from predictionio_tpu.data.api.http import dispatch_request
    out = dispatch_request(api, "POST", "/queries.json",
                           json.dumps(q).encode(), {})
    return out.status, out.data


#: what the server answered for `_FixedTopK` before the codec was planned
#: and the unpack went through `tolist()`: status and bytes, to the byte
_FIVE = (b'{"itemScores": [{"item": "i4", "score": 3.1415927410125732}, '
         b'{"item": "i2", "score": 2.7182817459106445}, '
         b'{"item": "i0", "score": 1.0000000116860974e-07}, '
         b'{"item": "i5", "score": -0.0}, {"item": "i1", "score": -1.5}]}')
GOLDEN_FLUSH = [
    ({"user": "u0", "num": 7}, 200, _FIVE),      # k = the 6 items; one is pad
    ({"user": "u1", "num": 3}, 200,
     b'{"itemScores": [{"item": "i4", "score": 3.1415927410125732}, '
     b'{"item": "i2", "score": 2.7182817459106445}, '
     b'{"item": "i0", "score": 1.0000000116860974e-07}]}'),
    ({"user": "u2", "num": 1}, 200,
     b'{"itemScores": [{"item": "i4", "score": 3.1415927410125732}]}'),
    ({"user": "nobody", "num": 4}, 200, b'{"itemScores": []}'),
    ({"user": "u3", "num": 0}, 200, b'{"itemScores": []}'),
    ({"user": "u4"}, 400, b'{"message": "field num is required for Query"}'),
    ({"user": "u5", "num": 2, "extra": 1}, 400,
     b'{"message": "unknown field(s) [\'extra\'] for Query '
     b'(accepts [\'num\', \'user\'])"}'),
]


@pytest.mark.parametrize("batching", ["on", "off"])
def test_reply_bytes_are_the_parents(trained, monkeypatch, batching):
    monkeypatch.setenv("PIO_SERVE_DEVICE_MS", "1e9")  # pin the device path
    storage, _app_id, _iid = trained
    api = _fixed_api(storage, config=ServerConfig(batching=batching))
    try:
        assert (api._batcher is not None) == (batching == "on")
        assert [(q, *_wire(api, q))
                for q, _st, _data in GOLDEN_FLUSH] == GOLDEN_FLUSH
    finally:
        api.close()


NON_FINITE_MESSAGE = (
    "prediction contains non-finite scores (the deployed model is "
    "numerically invalid); retrain or /reload a healthy instance")


@pytest.mark.parametrize("poison", [float("nan"), float("inf"),
                                    float("-inf")], ids=str)
@pytest.mark.parametrize("batching", ["on", "off"])
def test_non_finite_score_is_still_the_same_500(trained, monkeypatch, caplog,
                                               batching, poison):
    monkeypatch.setenv("PIO_SERVE_DEVICE_MS", "1e9")
    storage, _app_id, iid = trained
    api = _fixed_api(storage, poison=poison,
                     config=ServerConfig(batching=batching))
    try:
        with caplog.at_level("ERROR", logger="predictionio_tpu.server"):
            status, data = _wire(api, {"user": "u1", "num": 4})
        assert status == 500
        assert json.loads(data) == {"message": NON_FINITE_MESSAGE}
        assert (f"prediction for instance {iid} contains non-finite "
                "scores; refusing to serve it") in caplog.text
        # a finite cut of the same row is served
        status, data = _wire(api, {"user": "u1", "num": 1})
        assert status == 200 and len(json.loads(data)["itemScores"]) == 1
    finally:
        api.close()


@pytest.mark.parametrize("poison", [float("nan"), float("inf")], ids=str)
@pytest.mark.parametrize("batching", ["on", "off"])
def test_non_finite_put_in_after_the_fold_is_caught(trained, batching,
                                                    poison):
    """An output blocker runs after the pass that builds (and checks) the
    reply: what it puts in is found by the walk kept for that case."""
    storage, _app_id, _iid = trained

    class Spoil(EngineServerPlugin):
        plugin_name = "spoil"
        plugin_description = "overwrites the first score"
        plugin_type = OUTPUT_BLOCKER

        def process(self, engine_instance, query_obj, prediction_obj, context):
            prediction_obj["itemScores"][0]["score"] = poison   # in place
            return prediction_obj

    api = QueryAPI(storage=storage, config=ServerConfig(batching=batching),
                   plugin_context=EngineServerPluginContext([Spoil()]))
    try:
        walked0 = api.handle("GET", "/")[1]["codec"]["replyChecks"]["walked"]
        status, data = _wire(api, {"user": "u1", "num": 4})
        assert status == 500
        assert json.loads(data) == {"message": NON_FINITE_MESSAGE}
        checks = api.handle("GET", "/")[1]["codec"]["replyChecks"]
        assert checks["walked"] == walked0 + 1
    finally:
        api.close()


def test_codec_counters_on_status_and_metrics(trained):
    storage, _app_id, _iid = trained
    api = QueryAPI(storage=storage)
    try:
        for k in range(3):      # warm-up: the classes are seen here
            assert _post(api, {"user": f"u{k}", "num": 4})[0] == 200
        before = api.handle("GET", "/")[1]["codec"]
        assert set(before) == {"plans", "requests", "replyChecks"}
        assert before["plans"] >= 3   # Query, PredictedResult, ItemScore
        for k in range(100):
            assert _post(api, {"user": f"u{k % 8}", "num": 1 + k % 5})[0] == 200
        assert _post(api, {"user": "u1"})[0] == 400     # planned, and refused
        after = api.handle("GET", "/")[1]["codec"]
        assert after["plans"] == before["plans"]
        assert (after["requests"]["planned"]
                - before["requests"]["planned"]) == 101
        assert after["requests"]["reflected"] == before["requests"]["reflected"]
        assert (after["replyChecks"]["folded"]
                - before["replyChecks"]["folded"]) == 100
        assert after["replyChecks"]["walked"] == before["replyChecks"]["walked"]
        status, text, _headers = api.handle("GET", "/metrics")
        assert status == 200
        for line in (f'pio_codec_plans_total {after["plans"]}',
                     'pio_codec_requests_total{path="planned"} '
                     f'{after["requests"]["planned"]}',
                     'pio_codec_requests_total{path="reflected"} '
                     f'{after["requests"]["reflected"]}',
                     'pio_reply_checks_total{kind="folded"} '
                     f'{after["replyChecks"]["folded"]}',
                     'pio_reply_checks_total{kind="walked"} '
                     f'{after["replyChecks"]["walked"]}'):
            assert line in text.splitlines(), line
    finally:
        api.close()


# the transport under the query server (data/api/http.py: one head parse
# and one write a reply), counted beside the codec
# ---------------------------------------------------------------------------

_TRANSPORT_KEYS = {"mode", "requests", "writes", "protocolErrors",
                   "cpuSeconds"}


@pytest.mark.parametrize("transport", ["threaded", "async"])
def test_transport_counters_on_status_and_metrics(trained, monkeypatch,
                                                  transport):
    """`GET /` `transport`: one write a reply, and the names /metrics
    carries are declared."""
    import http.client

    from predictionio_tpu.common import declarations
    storage, _app_id, _iid = trained
    monkeypatch.setenv("PIO_TRANSPORT", transport)
    api = QueryAPI(storage=storage)
    server, port = serve_background(api)
    try:
        before = api.handle("GET", "/")[1]["transport"]
        assert set(before) == _TRANSPORT_KEYS
        assert before["mode"] == transport
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        for k in range(100):
            conn.request("POST", "/queries.json",
                         json.dumps({"user": f"u{k % 8}", "num": 1 + k % 5}))
            resp = conn.getresponse()
            assert resp.status == 200 and resp.read()
        conn.request("GET", "/")
        over_http = json.loads(conn.getresponse().read())["transport"]
        conn.close()
        # its own reply is counted as it goes out, after the page is made
        assert over_http["requests"] - before["requests"] == 100
        after = api.handle("GET", "/")[1]["transport"]
        assert after["requests"] - before["requests"] == 101
        assert after["writes"] - before["writes"] == 101
        assert after["protocolErrors"] == before["protocolErrors"]
        text = api.handle("GET", "/metrics")[1]
        for name, value in (("pio_transport_requests_total", "requests"),
                            ("pio_transport_writes_total", "writes")):
            assert name in declarations.METRICS
            assert f"{name} {after[value]}" in text.splitlines()
        assert "pio_transport_protocol_errors_total" in declarations.METRICS
    finally:
        server.shutdown()
        api.close()


@pytest.mark.parametrize("transport", ["threaded", "async"])
def test_transport_protocol_errors_counted_by_code(trained, transport):
    import socket
    storage, _app_id, _iid = trained
    api = QueryAPI(storage=storage)
    server, port = serve_background(api, transport=transport)

    def by_code():
        text = api.handle("GET", "/metrics")[1]
        return {c: sum(
            float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
            if line.startswith(
                f'pio_transport_protocol_errors_total{{code="{c}"}}'))
            for c in ("400", "501")}

    try:
        info0 = api.handle("GET", "/")[1]["transport"]
        codes0 = by_code()
        for request, status in (
                (b"POST /queries.json HTTP/1.1\r\nno colon here\r\n\r\n",
                 b"HTTP/1.1 400 Bad header line\r\n"),
                (b"POST /queries.json HTTP/1.1\r\nContent-Length: x\r\n\r\n",
                 b"HTTP/1.1 400 Bad Content-Length\r\n"),
                (b"PATCH /queries.json HTTP/1.1\r\n\r\n",
                 b"HTTP/1.1 501 Unsupported method ('PATCH')\r\n")):
            sock = socket.create_connection(("127.0.0.1", port), timeout=30)
            sock.sendall(request)
            reply = b""
            while True:       # an error reply ends the connection
                got = sock.recv(65536)
                if not got:
                    break
                reply += got
            sock.close()
            assert reply.startswith(status), reply[:80]
        codes = by_code()
        assert codes["400"] - codes0["400"] == 2
        assert codes["501"] - codes0["501"] == 1
        info = api.handle("GET", "/")[1]["transport"]
        assert info["protocolErrors"] - info0["protocolErrors"] == 3
        # a refusal is a request answered in one write too
        assert info["requests"] - info0["requests"] == 3
        assert info["writes"] - info0["writes"] == 3
    finally:
        server.shutdown()
        api.close()
