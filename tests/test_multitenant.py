"""Multi-tenant serving tests (serving/registry.py + the --engines
deploy path): registry generations + HBM budgets, per-access-key
admission (401/429), one process serving N engine instances with
per-key wire routing, per-tenant saturation isolation, shared-AOT
compile flatness, and legacy single-tenant wire parity."""

import dataclasses
import json
import threading
import time

import pytest

from predictionio_tpu.controller import EngineParams
from predictionio_tpu.data.datamap import DataMap
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage import AccessKey, App
from predictionio_tpu.serving import registry as registry_mod
from predictionio_tpu.serving.registry import (
    AdmissionController, AdmissionError, ModelRegistry, ServableModel,
    TenantSpec, load_engines_conf, model_hbm_bytes, parse_tenant_specs,
)
from predictionio_tpu.workflow import WorkflowContext, run_train
from predictionio_tpu.workflow.create_server import QueryAPI, ServerConfig


# ---------------------------------------------------------------------------
# training helpers: N independent apps, each its own trained instance
# ---------------------------------------------------------------------------

def _train_als(storage, app_name, key, invert=False):
    """One ALS app + COMPLETED instance + access key. ``invert`` flips
    the parity signal so two tenants' models give DIFFERENT answers to
    the same query — the wire-isolation assertion needs that."""
    import datetime as dt

    from predictionio_tpu.data import store
    from predictionio_tpu.models.recommendation import (
        ALSAlgorithmParams, DataSourceParams, RecommendationEngine,
    )
    apps = storage.get_meta_data_apps()
    app_id = apps.insert(App(0, app_name, None))
    storage.get_events().init(app_id)
    storage.get_meta_data_access_keys().insert(AccessKey(key, app_id, ()))
    events = []
    minute = 0
    for u in range(8):
        for i in range(6):
            minute += 1
            match = (u % 2) == (i % 2)
            if invert:
                match = not match
            events.append(Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{i}",
                properties=DataMap({"rating": 5.0 if match else 1.0}),
                event_time=dt.datetime(2021, 1, 1, 0, minute % 60,
                                       tzinfo=dt.timezone.utc)))
    store.write(events, app_id, storage=storage)
    ep = EngineParams(
        data_source_params=DataSourceParams(appName=app_name),
        algorithm_params_list=(
            ("als", ALSAlgorithmParams(rank=4, numIterations=5,
                                       lambda_=0.05, seed=3)),))
    iid = run_train(
        WorkflowContext(storage=storage), RecommendationEngine(), ep,
        engine_factory=("predictionio_tpu.models.recommendation"
                        ":RecommendationEngine"),
        params_json={
            "datasource": {"params": {"appName": app_name}},
            "algorithms": [{"name": "als", "params": {
                "rank": 4, "numIterations": 5, "lambda": 0.05,
                "seed": 3}}]})
    return app_id, iid


def _train_cls(storage, app_name, key):
    """One classification app + instance + key — the host-served
    template tenant (NaiveBayes has no batched predict, so `auto`
    batching keeps the inline path for it)."""
    import datetime as dt

    from predictionio_tpu.data import store
    from predictionio_tpu.models.classification import (
        ClassificationEngine, DataSourceParams, NaiveBayesAlgorithmParams,
    )
    apps = storage.get_meta_data_apps()
    app_id = apps.insert(App(0, app_name, None))
    storage.get_events().init(app_id)
    storage.get_meta_data_access_keys().insert(AccessKey(key, app_id, ()))
    events = []
    for n in range(20):
        plan = n % 2
        lo, hi = 0.0 + (n % 3), 8.0 + (n % 3)
        events.append(Event(
            event="$set", entity_type="user", entity_id=f"u{n}",
            properties=DataMap({
                "plan": float(plan),
                "attr0": hi if plan == 0 else lo,
                "attr1": 2.0,
                "attr2": lo if plan == 0 else hi}),
            event_time=dt.datetime(2021, 1, 1, 0, n % 60,
                                   tzinfo=dt.timezone.utc)))
    store.write(events, app_id, storage=storage)
    ep = EngineParams(
        data_source_params=DataSourceParams(appName=app_name),
        algorithm_params_list=(
            ("naive", NaiveBayesAlgorithmParams(lambda_=1.0)),))
    iid = run_train(
        WorkflowContext(storage=storage), ClassificationEngine(), ep,
        engine_factory=("predictionio_tpu.models.classification"
                        ":ClassificationEngine"),
        params_json={
            "datasource": {"params": {"appName": app_name}},
            "algorithms": [{"name": "naive", "params": {"lambda": 1.0}}]})
    return app_id, iid


@pytest.fixture()
def mt_trained(memory_storage):
    """Two ALS tenants (opposite parity signals) + one host-served
    classification tenant, each with its own app and access key."""
    a = _train_als(memory_storage, "TenantA", "key-a")
    b = _train_als(memory_storage, "TenantB", "key-b", invert=True)
    c = _train_cls(memory_storage, "TenantC", "key-c")
    return memory_storage, {"a": a, "b": b, "c": c}


def _specs(tenants, **overrides):
    """TenantSpecs for the trained fixture, one per tenant name."""
    out = []
    for name, (_app_id, iid) in tenants.items():
        extra = overrides.get(name, {})
        out.append(TenantSpec(
            name=name, access_key=f"key-{name}",
            engine_instance_id=iid, **extra))
    return tuple(out)


def _resp(api, body, key=None):
    query = {"accessKey": key} if key else None
    r = api.handle("POST", "/queries.json", query=query,
                   body=json.dumps(body).encode())
    status, payload = r[0], r[1]
    headers = r[2] if len(r) == 3 else {}
    return status, payload, headers


# ---------------------------------------------------------------------------
# conf parsing
# ---------------------------------------------------------------------------

class TestEnginesConf:
    def test_parse_shapes(self):
        specs = parse_tenant_specs([{"name": "a"}, {"name": "b"}])
        assert [s.name for s in specs] == ["a", "b"]
        specs = parse_tenant_specs({"tenants": [
            {"name": "a", "accessKey": "k", "batchMaxQueue": 8,
             "hbmBudgetMb": 128, "rate": 10, "burst": 20}]})
        s = specs[0]
        assert s.access_key == "k" and s.batch_max_queue == 8
        assert s.hbm_budget_mb == 128 and s.rate == 10 and s.burst == 20

    @pytest.mark.parametrize("bad,match", [
        ([], "non-empty list"),
        ({"tenants": {}}, "non-empty list"),
        (["x"], "not an object"),
        ([{"name": "a", "hbmBudget": 1}], "unknown key"),
        ([{"name": ""}], "has no name"),
        ([{"accessKey": "k"}], "has no name"),
        ([{"name": "a"}, {"name": "a"}], "not unique"),
        ([{"name": "a", "accessKey": "k"},
          {"name": "b", "accessKey": "k"}], "keys are not unique"),
    ])
    def test_parse_rejects(self, bad, match):
        with pytest.raises(ValueError, match=match):
            parse_tenant_specs(bad)

    def test_load_conf_file(self, tmp_path):
        p = tmp_path / "engines.json"
        p.write_text(json.dumps([{"name": "a"}, {"name": "b"}]))
        assert len(load_engines_conf(str(p))) == 2
        p.write_text("{nope")
        with pytest.raises(ValueError, match="not valid JSON"):
            load_engines_conf(str(p))


# ---------------------------------------------------------------------------
# registry: generations, budgets, hard cap
# ---------------------------------------------------------------------------

class _Inst:
    def __init__(self, iid):
        self.id = iid


def _servable(name, model_bytes=0, budget_mb=None):
    return ServableModel(
        name=name,
        spec=TenantSpec(name=name, hbm_budget_mb=budget_mb),
        instance=_Inst(f"i-{name}"), engine=None, engine_params=None,
        algorithms=[], models=[], serving=None,
        model_bytes=model_bytes)


class TestModelRegistry:
    def test_per_tenant_generations(self):
        reg = ModelRegistry(hard_cap_mb=None)
        assert reg.install(_servable("a")) is None
        reg.install(_servable("b"))
        assert reg.generations() == {"a": 1, "b": 1}
        prior = reg.install(_servable("a"))      # hot-swap a only
        assert prior is not None and prior.generation == 1
        assert reg.generations() == {"a": 2, "b": 1}
        assert reg.names() == ["a", "b"] and len(reg) == 2

    def test_soft_budget_flags_not_refuses(self):
        reg = ModelRegistry(hard_cap_mb=None)
        s = _servable("fat", model_bytes=3 * 1024 * 1024, budget_mb=2)
        reg.install(s)                           # serves anyway
        assert s.over_budget and reg.oversubscribed() == ["fat"]
        state = reg.get("fat").state()
        assert state["overBudget"] and state["budgetMb"] == 2

    def test_hard_cap_refuses_and_keeps_prior(self):
        reg = ModelRegistry(hard_cap_mb=4)
        first = _servable("a", model_bytes=3 * 1024 * 1024)
        reg.install(first)
        with pytest.raises(ValueError, match="hard HBM cap"):
            reg.install(_servable("b", model_bytes=2 * 1024 * 1024))
        assert reg.names() == ["a"]              # b never published
        # a reload of `a` itself that grows past the cap is refused too
        # and generation 1 keeps serving
        with pytest.raises(ValueError, match="hard HBM cap"):
            reg.install(_servable("a", model_bytes=5 * 1024 * 1024))
        assert reg.get("a") is first and first.generation == 1

    def test_model_hbm_bytes_walks_arrays(self):
        import numpy as np

        class M:
            def __init__(self):
                self.x = np.zeros((4, 4), dtype=np.float32)
                self.d = {"y": np.zeros(8, dtype=np.float64)}
                self.t = (np.zeros(2, dtype=np.int32),)
                self.alias = self.x              # same array: not double-counted
                self.s = "not-an-array"

        assert model_hbm_bytes([M()]) == 4 * 4 * 4 + 8 * 8 + 2 * 4
        assert model_hbm_bytes([None]) == 0


# ---------------------------------------------------------------------------
# admission: 401 / 429
# ---------------------------------------------------------------------------

class TestAdmission:
    def _controller(self, storage, tenants, **kw):
        by_appid = {app_id: name
                    for name, (app_id, _iid) in tenants.items()}
        return AdmissionController(storage, by_appid, **kw)

    def test_resolve_and_401(self, mt_trained):
        storage, tenants = mt_trained
        adm = self._controller(storage, tenants)
        assert adm.admit("key-a") == "a"
        assert adm.admit("key-b") == "b"
        with pytest.raises(AdmissionError) as ei:
            adm.admit(None)
        assert ei.value.status == 401 and "Missing" in ei.value.message
        with pytest.raises(AdmissionError) as ei:
            adm.admit("nope")
        assert ei.value.status == 401 and "Invalid" in ei.value.message

    def test_key_created_after_deploy_works(self, mt_trained):
        storage, tenants = mt_trained
        adm = self._controller(storage, tenants)
        with pytest.raises(AdmissionError):
            adm.admit("late-key")
        app_id = tenants["a"][0]
        storage.get_meta_data_access_keys().insert(
            AccessKey("late-key", app_id, ()))
        assert adm.admit("late-key") == "a"      # no negative cache

    def test_rate_limit_429_retry_after(self, mt_trained):
        storage, tenants = mt_trained
        adm = self._controller(
            storage, tenants,
            tenant_limits={"a": (1.0, 1.0), "b": (None, None)})
        assert adm.admit("key-a") == "a"         # burst of 1
        with pytest.raises(AdmissionError) as ei:
            adm.admit("key-a")
        assert ei.value.status == 429
        assert ei.value.retry_after_s >= 1
        # tenant b is unlimited (rate 0 default): the flood on a never
        # touches b's bucket
        for _ in range(20):
            assert adm.admit("key-b") == "b"


# ---------------------------------------------------------------------------
# the tentpole: one process, three engines, per-key wire routing
# ---------------------------------------------------------------------------

class TestMultiTenantDeploy:
    def test_three_engines_wire_isolation(self, mt_trained):
        storage, tenants = mt_trained
        api = QueryAPI(storage=storage, config=ServerConfig(
            tenants=_specs(tenants)))
        try:
            # tenant a: trained so even users prefer even items
            status, body, headers = _resp(
                api, {"user": "u2", "num": 3}, key="key-a")
            assert status == 200
            assert headers.get("X-PIO-Tenant") == "a"
            top_a = body["itemScores"][0]["item"]
            assert top_a in {"i0", "i2", "i4"}
            # tenant b: the SAME query body through b's key hits the
            # inverted model — even users prefer odd items. Same wire,
            # different model: per-key routing proven at the response.
            status, body, headers = _resp(
                api, {"user": "u2", "num": 3}, key="key-b")
            assert status == 200
            assert headers.get("X-PIO-Tenant") == "b"
            assert body["itemScores"][0]["item"] in {"i1", "i3", "i5"}
            # tenant c: a different engine TEMPLATE entirely
            # (classification, host-served inline path)
            status, body, headers = _resp(
                api, {"features": [9.0, 2.0, 1.0]}, key="key-c")
            assert status == 200 and body["label"] == 0.0
            assert headers.get("X-PIO-Tenant") == "c"
            # no key / unknown key: admission 401s before any model work
            status, body, _ = _resp(api, {"user": "u2", "num": 3})
            assert status == 401 and "Missing" in body["message"]
            status, body, _ = _resp(api, {"user": "u2", "num": 3},
                                    key="bogus")
            assert status == 401 and "Invalid" in body["message"]
        finally:
            api.close()

    def test_status_and_readyz_per_tenant(self, mt_trained):
        storage, tenants = mt_trained
        api = QueryAPI(storage=storage, config=ServerConfig(
            tenants=_specs(tenants)))
        try:
            status, info = api.handle("GET", "/")
            assert status == 200
            assert set(info["tenants"]) == {"a", "b", "c"}
            assert info["generations"] == {"a": 1, "b": 1, "c": 1}
            assert info["generation"] == 1
            for name, block in info["tenants"].items():
                assert block["generation"] == 1
                assert block["instanceId"] == tenants[name][1]
                assert "queueDepth" in block and "modelBytes" in block
            assert info["modelBytesTotal"] == sum(
                t["modelBytes"] for t in info["tenants"].values())
            assert set(info["transport"]) == {
                "mode", "requests", "writes", "protocolErrors", "cpuSeconds"}
            assert {"hostSpans", "host"} <= set(info)
            status, ready = api.handle("GET", "/readyz")
            assert status == 200 and ready["status"] == "ready"
            assert ready["generations"] == {"a": 1, "b": 1, "c": 1}
            assert ready["modelLoaded"] is True
        finally:
            api.close()

    def test_rate_limited_tenant_429_on_wire(self, mt_trained):
        storage, tenants = mt_trained
        api = QueryAPI(storage=storage, config=ServerConfig(
            tenants=_specs(tenants, a={"rate": 1.0, "burst": 1.0})))
        try:
            status, _, _ = _resp(api, {"user": "u1", "num": 2}, key="key-a")
            assert status == 200
            r = api.handle("POST", "/queries.json",
                           query={"accessKey": "key-a"},
                           body=json.dumps({"user": "u1", "num": 2}).encode())
            assert r[0] == 429 and int(r[2]["Retry-After"]) >= 1
            # b is untouched by a's limit
            status, _, _ = _resp(api, {"user": "u1", "num": 2}, key="key-b")
            assert status == 200
        finally:
            api.close()

    def test_hard_cap_refuses_deploy(self, mt_trained, monkeypatch):
        storage, tenants = mt_trained
        monkeypatch.setenv("PIO_TENANT_HBM_HARD_CAP_MB", "0.0001")
        with pytest.raises(ValueError, match="hard HBM cap"):
            QueryAPI(storage=storage, config=ServerConfig(
                tenants=_specs(tenants)))

    def test_soft_budget_reported_oversubscribed(self, mt_trained):
        storage, tenants = mt_trained
        api = QueryAPI(storage=storage, config=ServerConfig(
            tenants=_specs(tenants, a={"hbm_budget_mb": 1e-6})))
        try:
            status, info = api.handle("GET", "/")
            assert info["oversubscribed"] == ["a"]
            assert info["tenants"]["a"]["overBudget"] is True
            # over budget is a WARN, not an outage: a still serves
            status, _, _ = _resp(api, {"user": "u1", "num": 2}, key="key-a")
            assert status == 200
        finally:
            api.close()

    def test_duplicate_app_resolution_refused(self, mt_trained):
        storage, tenants = mt_trained
        iid_a = tenants["a"][1]
        specs = (TenantSpec(name="a", access_key="key-a",
                            engine_instance_id=iid_a),
                 # same instance, no key: falls back to the datasource
                 # appName -> the SAME app -> ambiguous per-key routing
                 TenantSpec(name="a2", engine_instance_id=iid_a))
        with pytest.raises(ValueError, match="both resolve to app id"):
            QueryAPI(storage=storage, config=ServerConfig(tenants=specs))


# ---------------------------------------------------------------------------
# noisy neighbor: saturation isolation at the wire
# ---------------------------------------------------------------------------

def _gate_tenant_batcher(api, name):
    """tests/test_create_server.py's _gated_batcher, aimed at one
    tenant's OWN batcher."""
    entered = threading.Semaphore(0)
    gate = threading.Event()
    batcher = api.registry.get(name).batcher
    real = batcher._flush_fn

    def gated(items):
        entered.release()
        gate.wait(30)
        return real(items)

    batcher._flush_fn = gated
    return gate, entered


def test_tenant_saturation_is_isolated(mt_trained):
    """Flooding tenant a 503s tenant a ONLY: b keeps answering 200 from
    its own queue while a's 1-slot queue rejects — the per-tenant
    batcher claim asserted at the wire."""
    storage, tenants = mt_trained
    api = QueryAPI(storage=storage, config=ServerConfig(
        batching="on", batch_max_size=1, batch_max_delay_ms=1.0,
        tenants=_specs(tenants, a={"batch_max_queue": 1})))
    gate, entered = _gate_tenant_batcher(api, "a")
    try:
        threads = [threading.Thread(
            target=_resp, args=(api, {"user": "u1", "num": 2}, "key-a"))]
        threads[0].start()
        assert entered.acquire(timeout=10)
        for _ in range(2):               # a's second lane, then its queue
            t = threading.Thread(
                target=_resp, args=(api, {"user": "u1", "num": 2},
                                    "key-a"))
            t.start()
            threads.append(t)
            if len(threads) == 2:        # both of a's lanes mid-flush
                assert entered.acquire(timeout=10)
        batcher = api.registry.get("a").batcher
        deadline = time.time() + 10
        while time.time() < deadline:
            with batcher._cond:
                if len(batcher._q) >= 1:
                    break
            time.sleep(0.01)
        status, body, headers = _resp(api, {"user": "u1", "num": 2},
                                      key="key-a")
        assert status == 503 and "saturated" in body["message"]
        assert int(headers["Retry-After"]) >= 1
        # tenant b — same process, same moment — is untouched
        for _ in range(3):
            status, body, _ = _resp(api, {"user": "u1", "num": 2},
                                    key="key-b")
            assert status == 200 and body["itemScores"]
        # and the host-served tenant c too
        status, body, _ = _resp(api, {"features": [1.0, 2.0, 9.0]},
                                key="key-c")
        assert status == 200 and body["label"] == 1.0
        gate.set()
        for t in threads:
            t.join(30)
    finally:
        gate.set()
        api.close()


# ---------------------------------------------------------------------------
# shared AOT: compile count flat as tenants multiply
# ---------------------------------------------------------------------------

def test_aot_compile_count_flat_across_tenants(mt_trained, monkeypatch):
    """Three ALS tenants pad onto ONE (bucket x template x k) program
    set: tenant 1 compiles, tenants 2..N memoize — the total compiled
    count equals a single-tenant deploy's."""
    from predictionio_tpu.serving import aot

    # pin the device path: on a loaded host the CPU backend's deploy
    # probe can move the FIRST tenant to host arrays (no programs), and
    # the second then compiles in its place
    monkeypatch.setenv("PIO_SERVE_DEVICE_MS", "1e9")
    storage, tenants = mt_trained
    third = _train_als(storage, "TenantD", "key-d")
    all_als = {"a": tenants["a"], "b": tenants["b"], "d": third}

    def deploy(names):
        aot.reset_memo()
        specs = _specs({n: all_als[n] for n in names})
        api = QueryAPI(storage=storage, config=ServerConfig(
            batching="on", aot="on", tenants=specs))
        try:
            states = [api.registry.get(n).aot_state for n in names]
            assert all(s and s.get("enabled") for s in states)
            return states
        finally:
            api.close()

    solo = deploy(["a"])
    compiled_solo = solo[0]["compiled"]
    assert compiled_solo > 0

    states = deploy(["a", "b", "d"])
    compiled_total = sum(s["compiled"] for s in states)
    assert compiled_total == compiled_solo, (
        f"compile count grew with tenant count: "
        f"{compiled_total} != {compiled_solo}")
    # the later tenants' programs were memo hits, not new compiles
    assert states[1]["compiled"] == 0 and states[2]["compiled"] == 0
    assert states[1]["memoized"] == compiled_solo
    assert states[2]["memoized"] == compiled_solo


# ---------------------------------------------------------------------------
# legacy parity: no --engines => the exact single-tenant wire shape
# ---------------------------------------------------------------------------

def test_legacy_wire_shape_without_engines_conf(mt_trained):
    """A deploy WITHOUT tenants keeps the exact legacy key set on
    `GET /` and /readyz — no tenants/generations leakage — and
    /queries.json answers the legacy 2-tuple (no X-PIO-Tenant)."""
    storage, tenants = mt_trained
    api = QueryAPI(storage=storage, config=ServerConfig(
        engine_instance_id=tenants["a"][1]))
    try:
        status, info = api.handle("GET", "/")
        assert status == 200
        assert set(info) == {
            "status", "engineInstance", "algorithms", "requestCount",
            "avgServingSec", "lastServingSec", "degradedCount",
            "draining", "serverStartTime", "generation", "batching",
            "aot", "codec", "transport", "hostSpans", "host"}
        status, ready = api.handle("GET", "/readyz")
        assert status == 200
        assert "generations" not in ready and "queueDepths" not in ready
        r = api.handle("POST", "/queries.json",
                       body=json.dumps({"user": "u1", "num": 2}).encode())
        assert r[0] == 200 and len(r) == 2
        # the registry still tracks the model internally (under the
        # reserved 'default' name) without leaking onto the wire
        assert api.registry.names() == [registry_mod.DEFAULT_TENANT]
    finally:
        api.close()
