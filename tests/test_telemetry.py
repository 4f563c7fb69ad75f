"""Observability subsystem tests (common/telemetry.py + common/tracing.py).

Covers the acceptance surface: registry thread-safety under concurrent
writers, histogram bucket correctness, `GET /metrics` parsing as
Prometheus text exposition on all three daemons, X-PIO-Trace propagation
query-server → storage-server with admission/flush/dispatch/storage
spans, the degraded batches-vs-queries distinction (KNOWN_ISSUES #6),
and WIRE PARITY: with telemetry off (the default) responses and RPC
headers are byte-identical to the pre-telemetry code.
"""

import json
import re
import threading
import urllib.request

import pytest

from predictionio_tpu.common import resilience, telemetry, tracing
from predictionio_tpu.common.telemetry import (
    Counter, Histogram, MetricsRegistry,
)
from predictionio_tpu.controller import EngineParams
from predictionio_tpu.data.api import EventAPI
from predictionio_tpu.data.api.http import serve_background
from predictionio_tpu.data.datamap import DataMap
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage import App, Storage
from predictionio_tpu.data.storage.remote import StorageRPCAPI
from predictionio_tpu.models.recommendation import (
    ALSAlgorithmParams, DataSourceParams, RecommendationEngine,
)
from predictionio_tpu.models.recommendation.als_algorithm import ALSAlgorithm
from predictionio_tpu.workflow import WorkflowContext, run_train
from predictionio_tpu.workflow.create_server import QueryAPI, ServerConfig


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """No telemetry override, trace override, or recorded spans leak
    between tests (the process registry is additive by design — families
    persist — so tests assert on deltas or fresh label children)."""
    telemetry.set_enabled(None)
    tracing.set_enabled(None)
    tracing.clear()
    yield
    telemetry.set_enabled(None)
    tracing.set_enabled(None)
    tracing.clear()


# ---------------------------------------------------------------------------
# registry primitives
# ---------------------------------------------------------------------------

def test_counter_thread_safety_under_concurrent_writers():
    c = Counter()
    n_threads, per_thread = 8, 5000

    def pump():
        for _ in range(per_thread):
            c.inc()

    threads = [threading.Thread(target=pump) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * per_thread


def test_histogram_thread_safety_and_totals():
    h = Histogram(buckets=(1.0, 10.0))
    n_threads, per_thread = 8, 2000

    def pump(v):
        for _ in range(per_thread):
            h.observe(v)

    threads = [threading.Thread(target=pump, args=(0.5 if k % 2 else 5.0,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = h.snapshot()
    total = n_threads * per_thread
    assert snap["count"] == total
    assert snap["buckets"][1.0] == total // 2          # the 0.5 observes
    assert snap["buckets"][10.0] == total              # cumulative
    assert snap["buckets"][float("inf")] == total
    assert snap["sum"] == pytest.approx(total // 2 * 0.5 + total // 2 * 5.0)


def test_histogram_bucket_edges():
    h = Histogram(buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.1, 0.2, 1.0, 2.0, 100.0):
        h.observe(v)
    snap = h.snapshot()
    # le buckets are INCLUSIVE upper bounds, cumulative
    assert snap["buckets"][0.1] == 2       # 0.05, 0.1
    assert snap["buckets"][1.0] == 4       # + 0.2, 1.0
    assert snap["buckets"][10.0] == 5      # + 2.0
    assert snap["buckets"][float("inf")] == 6
    assert snap["count"] == 6


def test_family_label_validation_and_kind_conflicts():
    reg = MetricsRegistry()
    fam = reg.counter("x_total", "x", labelnames=("k",))
    with pytest.raises(ValueError, match="takes labels"):
        fam.labels(wrong="v")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")
    # same (name, kind, labels) is idempotent and shares children
    assert reg.counter("x_total", labelnames=("k",)) is fam
    fam.labels(k="a").inc(3)
    assert fam.labels(k="a").value == 3


def test_metric_name_validation_at_registration():
    """Prometheus-grammar violations fail the registration that
    introduced them, not a 3am scrape (ISSUE 5 satellite)."""
    reg = MetricsRegistry()
    for bad in ("0starts_with_digit", "has-dash", "has space", "", "x.y"):
        with pytest.raises(ValueError, match="invalid metric name"):
            reg.counter(bad)
    for bad_label in ("0num", "has-dash", "", "x.y"):
        with pytest.raises(ValueError, match="invalid label name"):
            reg.gauge("ok_name", labelnames=(bad_label,))
    with pytest.raises(ValueError, match="reserved"):
        reg.histogram("ok_hist", labelnames=("le",))
    with pytest.raises(ValueError, match="reserved"):
        reg.counter("ok_counter", labelnames=("__meta",))
    # colons are legal in metric names (recording-rule convention)
    reg.counter("ns:sub_total", labelnames=("k",))


def test_all_registered_names_validate_after_importing_everything():
    """Import every instrumented module (and touch the instance-level
    registrations) — every name/label the process registers must pass
    the validator. Guards against drift in modules that build metric
    names dynamically."""
    import predictionio_tpu.common.devicewatch  # noqa: F401
    import predictionio_tpu.common.resilience  # noqa: F401
    import predictionio_tpu.common.tracing  # noqa: F401
    import predictionio_tpu.data.api.stats  # noqa: F401
    import predictionio_tpu.data.storage.eventlog  # noqa: F401
    import predictionio_tpu.data.storage.remote  # noqa: F401
    import predictionio_tpu.models.recommendation.als_algorithm  # noqa: F401
    import predictionio_tpu.ops.staging  # noqa: F401
    import predictionio_tpu.serving.batcher as batcher_mod
    import predictionio_tpu.workflow.context  # noqa: F401
    import predictionio_tpu.workflow.create_server  # noqa: F401

    # instance-level registrations (batcher) on top of import-time ones
    b = batcher_mod.MicroBatcher(lambda items: items, max_batch_size=2)
    try:
        reg = telemetry.registry()
        with reg._lock:
            families = list(reg._families.values())
        assert families, "nothing registered?"
        for fam in families:
            telemetry.validate_names(fam.name, fam.labelnames)
    finally:
        b.close()


def test_metrics_scrape_under_concurrent_mutation():
    """A scraper looping against writer threads: every exposition must
    parse, and per-series counter totals must be monotone (ISSUE 5
    satellite — the scrape takes no registry-wide lock, so this is the
    test that the per-child locking story actually holds)."""
    reg = MetricsRegistry()
    c = reg.counter("mut_total", "m", labelnames=("k",))
    h = reg.histogram("mut_seconds", "m", buckets=(0.01, 0.1, 1.0)
                      ).labels()
    stop = threading.Event()
    errors = []

    def writer(label):
        child = c.labels(k=label)
        v = 0.001
        while not stop.is_set():
            child.inc()
            h.observe(v)
            v = (v * 7) % 1.7

    threads = [threading.Thread(target=writer, args=(f"w{i}",))
               for i in range(4)]
    for t in threads:
        t.start()
    try:
        last_totals = {}
        last_count = 0
        for _ in range(50):
            try:
                types, samples = parse_prometheus(reg.exposition())
            except AssertionError as e:
                errors.append(f"unparseable exposition: {e}")
                break
            for labels, v in samples.get("mut_total", []):
                prev = last_totals.get(labels, 0.0)
                if v < prev:
                    errors.append(
                        f"counter went backwards: {labels} {prev}->{v}")
                last_totals[labels] = v
            for _labels, v in samples.get("mut_seconds_count", []):
                if v < last_count:
                    errors.append(
                        f"histogram count went backwards: "
                        f"{last_count}->{v}")
                last_count = v
    finally:
        stop.set()
        for t in threads:
            t.join(5)
    assert not errors, errors[:5]


def test_registry_dict_is_dictlike_and_registry_backed():
    reg = MetricsRegistry()
    fam = reg.counter("layout_total", "t", labelnames=("result",))
    d = telemetry.RegistryDict(fam, "result", ("hits", "builds"))
    d["hits"] += 1
    d["hits"] += 1
    d["builds"] += 1
    assert d["hits"] == 2 and d["builds"] == 1
    assert fam.labels(result="hits").value == 2     # same storage
    assert dict(d.items()) == {"hits": 2, "builds": 1}


# ---------------------------------------------------------------------------
# Prometheus text exposition
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})?\s(\S+)$')
_LABELS_RE = re.compile(
    r'\{((?:[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*",?)*)\}')


def parse_prometheus(text):
    """Strict-enough 0.0.4 text parser: validates comment structure,
    sample-line grammar, numeric values, and histogram le-monotonicity.
    Returns (types, samples: name -> [(labelstr, float)])."""
    types, samples = {}, {}
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            assert len(line.split(" ", 3)) >= 3, line
            continue
        if line.startswith("# TYPE "):
            _h, _t, name, kind = line.split(" ", 3)
            assert kind in ("counter", "gauge", "histogram"), line
            types[name] = kind
            continue
        assert not line.startswith("#"), f"unknown comment: {line}"
        # OpenMetrics exemplar suffix (waterfall stage histograms):
        # validate its grammar, then parse the sample body as usual
        body, ex_sep, exemplar = line.partition(" # ")
        if ex_sep:
            assert re.fullmatch(r'\{[^{}]*\}\s+\S+', exemplar.strip()), \
                f"malformed exemplar: {line!r}"
        m = _SAMPLE_RE.match(body)
        assert m, f"unparseable sample line: {line!r}"
        name, labels, value = m.groups()
        if labels:
            assert _LABELS_RE.fullmatch(labels), f"bad labels: {line!r}"
        v = float(value.replace("+Inf", "inf").replace("-Inf", "-inf"))
        samples.setdefault(name, []).append((labels or "", v))
    # histogram buckets must be cumulative in le order per label set
    for name in types:
        if types[name] != "histogram":
            continue
        series = {}
        for labels, v in samples.get(name + "_bucket", []):
            le = re.search(r'le="([^"]+)"', labels).group(1)
            rest = re.sub(r'le="[^"]+",?', "", labels)
            series.setdefault(rest, []).append(
                (float(le.replace("+Inf", "inf")), v))
        for rest, pts in series.items():
            pts.sort()
            counts = [c for _le, c in pts]
            assert counts == sorted(counts), f"{name}{rest} not cumulative"
            assert pts[-1][0] == float("inf"), f"{name}{rest} missing +Inf"
    return types, samples


def test_exposition_round_trips_through_parser():
    reg = MetricsRegistry()
    reg.counter("a_total", "with \"quotes\" and spaces",
                labelnames=("k",)).labels(k='va"l\nue').inc(2)
    reg.gauge("b_depth", "depth").labels().set(3.5)
    h = reg.histogram("c_seconds", "lat", labelnames=("svc",),
                      buckets=(0.001, 0.1)).labels(svc="s")
    h.observe(0.0005)
    h.observe(5.0)
    types, samples = parse_prometheus(reg.exposition())
    assert types == {"a_total": "counter", "b_depth": "gauge",
                     "c_seconds": "histogram"}
    assert samples["a_total"][0][1] == 2
    assert samples["b_depth"][0][1] == 3.5
    assert samples["c_seconds_count"][0][1] == 2
    assert samples["c_seconds_sum"][0][1] == pytest.approx(5.0005)


# ---------------------------------------------------------------------------
# daemons: GET /metrics and /traces.json next to /healthz
# ---------------------------------------------------------------------------

def _trained_query_api(storage, **config):
    """Seed, train, and deploy a small recommendation engine."""
    apps = storage.get_meta_data_apps()
    app_id = apps.insert(App(0, "TelApp", None))
    storage.get_events().init(app_id)
    import datetime as dt
    events = []
    for u in range(8):
        for i in range(6):
            events.append(Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{i}",
                properties=DataMap(
                    {"rating": 5.0 if (u % 2) == (i % 2) else 1.0}),
                event_time=dt.datetime(2021, 1, 1, 0, (u * 6 + i) % 60,
                                       tzinfo=dt.timezone.utc)))
    storage.get_events().insert_batch(events, app_id)
    engine = RecommendationEngine()
    ep = EngineParams(
        data_source_params=DataSourceParams(appName="TelApp"),
        algorithm_params_list=(
            ("als", ALSAlgorithmParams(rank=4, numIterations=3,
                                       lambda_=0.05, seed=3)),))
    run_train(WorkflowContext(storage=storage), engine, ep,
              engine_factory="telemetry-test",
              params_json={
                  "datasource": {"params": {"appName": "TelApp"}},
                  "algorithms": [{"name": "als", "params": {
                      "rank": 4, "numIterations": 3, "lambda": 0.05,
                      "seed": 3}}]})
    return QueryAPI(storage=storage, engine=engine,
                    config=ServerConfig(**config)), app_id


def test_metrics_route_on_all_three_daemons(memory_storage, tmp_path):
    query_api, _ = _trained_query_api(memory_storage)
    event_api = EventAPI(storage=memory_storage)
    storage_api = StorageRPCAPI(memory_storage, key="sekrit")
    try:
        for api in (query_api, event_api, storage_api):
            # unauthenticated, like /healthz (note the storage server has
            # key auth on and still serves the scrape)
            status, payload, headers = api.handle("GET", "/metrics")
            assert status == 200, type(api).__name__
            assert headers["Content-Type"].startswith("text/plain")
            types, samples = parse_prometheus(payload)
            assert types, "empty exposition"
            status, traces = api.handle("GET", "/traces.json")
            assert status == 200 and "traces" in traces
    finally:
        query_api.close()


def test_metrics_content_type_over_http(memory_storage):
    api = EventAPI(storage=memory_storage)
    server, port = serve_background(api)
    try:
        with urllib.request.urlopen(
                f"http://localhost:{port}/metrics") as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            assert "version=0.0.4" in r.headers["Content-Type"]
            parse_prometheus(r.read().decode("utf-8"))
    finally:
        server.shutdown()


def test_batcher_stats_are_registry_backed(memory_storage):
    """`GET /` batching stats and `GET /metrics` read the same counters
    (single source of truth) and the legacy JSON shape is unchanged."""
    api, _ = _trained_query_api(memory_storage)
    try:
        assert api._batcher is not None
        for k in range(3):
            st, _ = api.handle("POST", "/queries.json", body=json.dumps(
                {"user": f"u{k}", "num": 2}).encode())
            assert st == 200
        _, info = api.handle("GET", "/")
        b = info["batching"]
        assert set(b) == {"enabled", "maxBatchSize", "maxDelayMs",
                          "maxQueue", "buckets", "queueDepth", "batches",
                          "overlapped", "queries", "rejected",
                          "batchSizeHist",
                          "bucketHist", "avgQueueWaitMs", "avgFlushMs",
                          "wakeSeconds", "lanes", "topkSelection", "layout",
                          "shards", "perShardBytes"}
        assert b["queries"] == 3
        # the same numbers, straight from the registry instruments
        assert int(api._batcher._m_queries.value) == 3
        assert int(api._batcher._m_batches.value) == b["batches"]
        assert int(api._batcher._m_overlapped.value) == b["overlapped"]
        _st, payload, _h = api.handle("GET", "/metrics")
        types, samples = parse_prometheus(payload)
        assert types["pio_batcher_queries_total"] == "counter"
        inst = api._batcher._inst["batcher"]
        got = [v for labels, v in samples["pio_batcher_queries_total"]
               if f'batcher="{inst}"' in labels]
        assert got == [3.0]
    finally:
        api.close()


def test_status_names_the_topk_selection_from_the_kernels_shape_test(
        memory_storage, monkeypatch):
    """`GET /`'s batching block says which selection the deployed top-k
    programs were built with, from ops/topk.py's own test of the static
    shape: `sort` for the six-item catalog, `chunked` with its L and C
    once the same instance holds a catalog long enough for two stages;
    the same test over one shard's rows where the layout is row-sharded;
    null when the CPU harness moved serving to host arrays."""
    import numpy as np

    # the CPU backend times a query at deploy and serves from the host
    # when it is slow (a loaded test machine): pin each layout in turn
    monkeypatch.setenv("PIO_SERVE_DEVICE_MS", "1e9")

    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.data.storage import Model
    from predictionio_tpu.models.recommendation.als_algorithm import ALSModel
    from predictionio_tpu.ops import topk
    from predictionio_tpu.workflow import model_io

    api, _ = _trained_query_api(memory_storage)
    try:
        _, info = api.handle("GET", "/")
        assert info["batching"]["topkSelection"] == {"6": "sort"}
        assert topk.chunk_plan(6, 6) is None
        instance_id = api.engine_instance.id
    finally:
        api.close()
    n_items = 2 * 11 * topk.CHUNK + 5
    rng = np.random.default_rng(0)
    long_model = ALSModel(
        rank=4,
        user_factors=rng.normal(size=(8, 4)).astype(np.float32),
        item_factors=rng.normal(size=(n_items, 4)).astype(np.float32),
        user_vocab=BiMap({f"u{k}": k for k in range(8)}),
        item_vocab=BiMap({f"i{k}": k for k in range(n_items)}))
    memory_storage.get_model_data_models().insert(Model(
        id=instance_id, models=model_io.serialize_models([long_model])))
    api = QueryAPI(storage=memory_storage, engine=RecommendationEngine(),
                   config=ServerConfig(batching="on"))
    try:
        assert api.engine_instance.id == instance_id
        _, info = api.handle("GET", "/")
        plan = topk.chunk_plan(n_items, 10)
        assert plan == (topk.CHUNK, 22)
        assert info["batching"]["topkSelection"] == {
            "10": "chunked L=%d C=%d" % plan}
        st, body = api.handle("POST", "/queries.json", body=json.dumps(
            {"user": "u1", "num": 10}).encode())
        assert st == 200 and len(body["itemScores"]) == 10
    finally:
        api.close()
    # row-sharded over the harness's devices: the same shape test, over
    # the rows of ONE shard, which is what each device selects from
    api = QueryAPI(storage=memory_storage, engine=RecommendationEngine(),
                   config=ServerConfig(batching="on", shard_serving="on"))
    try:
        sharding = api.models[0].sharding
        rows_dev_i = sharding.rows_dev_i
        assert rows_dev_i == -(-n_items // sharding.n_shards) < n_items
        _, info = api.handle("GET", "/")
        assert info["batching"]["topkSelection"] == {
            "10": topk.selection_name(rows_dev_i, 10)}
    finally:
        api.close()
    monkeypatch.setenv("PIO_SERVE_DEVICE_MS", "0")
    api = QueryAPI(storage=memory_storage, engine=RecommendationEngine(),
                   config=ServerConfig(batching="on"))
    try:
        _, info = api.handle("GET", "/")
        assert info["batching"]["topkSelection"] is None
    finally:
        api.close()


def test_event_stats_book_collected_into_metrics(memory_storage):
    from predictionio_tpu.data.api import EventServerConfig
    from predictionio_tpu.data.storage import AccessKey
    app_id = memory_storage.get_meta_data_apps().insert(App(0, "SApp"))
    memory_storage.get_meta_data_access_keys().insert(
        AccessKey("sk", app_id, ()))
    memory_storage.get_events().init(app_id)
    api = EventAPI(storage=memory_storage,
                   config=EventServerConfig(stats=True))
    st, _ = api.handle("POST", "/events.json", {"accessKey": "sk"},
                       json.dumps({"event": "rate", "entityType": "user",
                                   "entityId": "u1"}).encode())
    assert st == 201
    # /stats.json keeps its byte-compatible legacy shape...
    st, stats = api.handle("GET", "/stats.json", {"accessKey": "sk"})
    assert st == 200
    assert set(stats) == {"comment", "startTime", "currentHour",
                          "prevHour", "longLive"}
    # ...and the same book feeds the scrape via its collector
    _st, payload, _h = api.handle("GET", "/metrics")
    assert re.search(
        rf'pio_events_requests_total\{{app_id="{app_id}",status="201"\}} 1',
        payload)


def test_layout_stats_visible_in_metrics(memory_storage):
    from predictionio_tpu.models.recommendation import als_algorithm
    before = als_algorithm.LAYOUT_STATS["builds"]
    _api, _ = _trained_query_api(memory_storage)
    _api.close()
    assert als_algorithm.LAYOUT_STATS["builds"] >= before + 1
    status, payload, _h = EventAPI(storage=memory_storage).handle(
        "GET", "/metrics")
    assert 'pio_layout_cache_total{result="builds"}' in payload


# ---------------------------------------------------------------------------
# tracing: propagation + the batched-serving span chain
# ---------------------------------------------------------------------------

class _LookupALS(ALSAlgorithm):
    """ALS whose batched predict does one live storage lookup — the
    side-channel shape of the e-commerce template, small enough to trace
    end to end in a test."""

    def predict_batch(self, model, queries):
        self._serving_storage.get_meta_data_apps().get_all()   # remote RPC
        return super().predict_batch(model, queries)

    def bind_serving(self, ctx) -> None:
        self._serving_storage = ctx.storage


def _lookup_engine():
    from predictionio_tpu.controller import Engine, FirstServing
    from predictionio_tpu.models.recommendation.data_source import (
        DataSource,
    )
    from predictionio_tpu.models.recommendation.preparator import Preparator
    return Engine(data_source_class=DataSource,
                  preparator_class=Preparator,
                  algorithm_class_map={"als": _LookupALS},
                  serving_class=FirstServing)


def test_trace_propagates_query_server_to_storage_server(tmp_path):
    """The acceptance trace: one batched query -> admission, flush,
    dispatch, and storage spans, plus the STORAGE SERVER's own span, all
    under ONE trace id carried by X-PIO-Trace."""
    from predictionio_tpu.data.storage.remote import serve_storage

    backing = Storage(env={
        "PIO_STORAGE_SOURCES_B_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "B",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "B",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "B",
    })
    engine = _lookup_engine()
    # train directly against the backing store (tracing off: no spans)
    apps = backing.get_meta_data_apps()
    app_id = apps.insert(App(0, "TraceApp", None))
    backing.get_events().init(app_id)
    import datetime as dt
    backing.get_events().insert_batch([
        Event(event="rate", entity_type="user", entity_id=f"u{u}",
              target_entity_type="item", target_entity_id=f"i{i}",
              properties=DataMap({"rating": float(1 + (u + i) % 5)}),
              event_time=dt.datetime(2021, 1, 1, tzinfo=dt.timezone.utc))
        for u in range(6) for i in range(5)], app_id)
    ep = EngineParams(
        data_source_params=DataSourceParams(appName="TraceApp"),
        algorithm_params_list=(
            ("als", ALSAlgorithmParams(rank=3, numIterations=2,
                                       lambda_=0.05, seed=1)),))
    run_train(WorkflowContext(storage=backing), engine, ep,
              engine_factory="trace-test",
              params_json={
                  "datasource": {"params": {"appName": "TraceApp"}},
                  "algorithms": [{"name": "als", "params": {
                      "rank": 3, "numIterations": 2, "lambda": 0.05,
                      "seed": 1}}]})

    rpc_server = serve_storage(backing, host="127.0.0.1", port=0)
    rpc_port = rpc_server.server_address[1]
    remote = Storage(env={
        "PIO_STORAGE_SOURCES_R_TYPE": "remote",
        "PIO_STORAGE_SOURCES_R_URL": f"http://127.0.0.1:{rpc_port}",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "R",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "R",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "R",
    })
    api = QueryAPI(storage=remote, engine=engine,
                   config=ServerConfig(batching="on"))
    server, port = serve_background(api)
    tracing.clear()
    tracing.set_enabled(True)      # the query server originates the trace
    try:
        req = urllib.request.Request(
            f"http://localhost:{port}/queries.json",
            data=json.dumps({"user": "u1", "num": 3}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req) as r:
            assert r.status == 200
        snap = tracing.snapshot()
        # find the trace that carried the query (it has an admission span)
        by_name = None
        for trace in snap["traces"]:
            names = {s["name"] for s in trace["spans"]}
            if "admission" in names:
                by_name = {s["name"]: s for s in trace["spans"]}
                break
        assert by_name is not None, snap
        for expected in ("server:/queries.json", "admission", "flush",
                         "dispatch", "storage", "server:/rpc"):
            assert expected in by_name, sorted(by_name)
        # one trace id across process boundaries = propagation worked
        # (server:/rpc was recorded by the STORAGE SERVER's handler off
        # the X-PIO-Trace header the remote driver sent)
        assert by_name["server:/rpc"]["service"] == "StorageRPCAPI"
        # and /traces.json serves the same thing over the wire
        with urllib.request.urlopen(
                f"http://localhost:{port}/traces.json") as r:
            served = json.loads(r.read())
        assert served["spanCount"] >= 6
    finally:
        tracing.set_enabled(None)
        server.shutdown()
        api.close()
        rpc_server.shutdown()
        rpc_server.server_close()


# ---------------------------------------------------------------------------
# degraded: batches vs queries upper bound (KNOWN_ISSUES #6)
# ---------------------------------------------------------------------------

def test_degraded_batches_vs_queries_upper_bound(memory_storage):
    """One tainted 3-query flush: degraded_batches_total counts 1,
    degraded_queries_upper_bound (== legacy degradedCount) counts 3."""
    api, _ = _trained_query_api(
        memory_storage, batching="on", batch_max_size=3,
        batch_max_delay_ms=500.0)
    try:
        algo = api.algorithms[0]
        real = type(algo).predict_batch

        def tainted(model, queries):
            resilience.note_degraded("test side-channel failure")
            return real(algo, model, queries)

        algo.predict_batch = tainted
        results = [None] * 3

        def hit(k):
            results[k] = api.handle(
                "POST", "/queries.json",
                body=json.dumps({"user": f"u{k}", "num": 2}).encode())

        threads = [threading.Thread(target=hit, args=(k,))
                   for k in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        for st, body in results:
            assert st == 200 and body.get("degraded") is True
        assert int(api._m_degraded_batches.value) == 1
        assert int(api._m_degraded_queries.value) == 3
        _, info = api.handle("GET", "/")
        assert info["degradedCount"] == 3     # legacy field == upper bound
    finally:
        api.close()


# ---------------------------------------------------------------------------
# wire parity: telemetry off == pre-telemetry bytes
# ---------------------------------------------------------------------------

def test_no_trace_header_emitted_by_default(tmp_path):
    """With defaults (no PIO_TRACE, no active context) the remote driver
    sends exactly the legacy header set — no X-PIO-Trace."""
    from predictionio_tpu.data.api.http import make_server

    backing = Storage(env={
        "PIO_STORAGE_SOURCES_B_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "B",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "B",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "B",
    })
    rpc_api = StorageRPCAPI(backing)
    seen = []
    orig = rpc_api.handle

    def spy(method, path, query=None, body=b"", headers=None):
        seen.append({k.lower() for k in (headers or {})})
        return orig(method, path, query, body, headers)

    rpc_api.handle = spy
    server, port = serve_background(rpc_api)
    try:
        remote = Storage(env={
            "PIO_STORAGE_SOURCES_R_TYPE": "remote",
            "PIO_STORAGE_SOURCES_R_URL": f"http://127.0.0.1:{port}",
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "R",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "R",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "R",
        })
        remote.get_meta_data_apps().get_all()
        assert seen and all("x-pio-trace" not in h for h in seen)

        # positive control: an ACTIVE context adds exactly that header
        seen.clear()
        with tracing.activate(tracing.new_context()):
            with tracing.span("probe"):
                remote.get_meta_data_apps().get_all()
        assert any("x-pio-trace" in h for h in seen)
    finally:
        server.shutdown()


def test_responses_byte_identical_with_telemetry_on_and_off(memory_storage):
    """Flipping PIO_TELEMETRY must never change a response byte: metrics
    observe, they do not decorate."""
    api, _ = _trained_query_api(memory_storage)
    try:
        body = json.dumps({"user": "u1", "num": 4}).encode()
        telemetry.set_enabled(False)
        st_off, off = api.handle("POST", "/queries.json", body=body)
        telemetry.set_enabled(True)
        st_on, on = api.handle("POST", "/queries.json", body=body)
        assert (st_off, json.dumps(off)) == (st_on, json.dumps(on))
        # legacy GET / key set unchanged (no telemetry keys leak in;
        # "aot" is the AOT-deploy section, present because this server
        # prebuilt its programs — PIO_AOT=0 parity is tests/test_aot.py)
        _, info = api.handle("GET", "/")
        assert set(info) == {
            "status", "engineInstance", "algorithms", "requestCount",
            "avgServingSec", "lastServingSec", "degradedCount", "draining",
            "serverStartTime", "generation", "batching", "aot",
            "codec", "transport", "hostSpans", "host"}
    finally:
        telemetry.set_enabled(None)
        api.close()


def test_telemetry_on_records_serve_latency(memory_storage):
    telemetry.set_enabled(True)
    api, _ = _trained_query_api(memory_storage)
    try:
        st, _ = api.handle("POST", "/queries.json", body=json.dumps(
            {"user": "u1", "num": 2}).encode())
        assert st == 200
        fam = telemetry.registry().histogram(
            "pio_serve_seconds", labelnames=("mode", "tenant"))
        assert fam.labels(mode="batched", tenant="default").count >= 1
    finally:
        api.close()
