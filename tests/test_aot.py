"""AOT serving/training compilation subsystem (serving/aot.py).

The acceptance surface of ISSUE 6: every enumerated (bucket, template,
k) program is bit-identical to the lazy-jit path; deploy prebuilds the
program set before /readyz flips ready, marks the recompile watchdog's
warmup done, and records time-to-ready; the compile cache exports from
`pio train` as a deploy artifact and imports gracefully (a mismatched
environment degrades to lazy compile, never errors); ``PIO_AOT=0``
deploy is wire-byte-identical to the pre-AOT server; and a tier-1 lint
fails when a ``@jax.jit`` entry point on the serving path is not
registered with the AOT enumerator.
"""

import ast
import datetime as dt
import json
import os

import numpy as np
import pytest

from predictionio_tpu.common import devicewatch, telemetry
from predictionio_tpu.controller import EngineParams
from predictionio_tpu.data.datamap import DataMap
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage import App, Model
from predictionio_tpu.models.recommendation import (
    ALSAlgorithmParams, DataSourceParams, RecommendationEngine,
)
from predictionio_tpu.ops import als, topk
from predictionio_tpu.serving import aot, protocol
from predictionio_tpu.workflow import WorkflowContext, model_io, run_train
from predictionio_tpu.workflow.create_server import QueryAPI, ServerConfig

PKG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "predictionio_tpu")


def _clear_counter_family(name):
    """Zero one counter family's children (the process registry is
    additive by design; doctor-style readers consume absolutes)."""
    reg = telemetry.registry()
    with reg._lock:
        fam = reg._families.get(name)
    if fam is not None:
        with fam._lock:
            fam._children.clear()


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    """AOT state never leaks across tests: telemetry override reset,
    watchdog reset. The program memo is left alone on purpose (it is
    additive and shape-keyed, like the jit cache it mirrors)."""
    telemetry.set_enabled(None)
    devicewatch.reset_watchdog()
    yield
    telemetry.set_enabled(None)
    devicewatch.reset_watchdog()
    devicewatch.note_aot(None)


def _train_engine(storage, n_items=7, rank=3):
    """Item count unique to this module so its programs are not already
    jit-cached by other test files."""
    app_id = storage.get_meta_data_apps().insert(App(0, "AotApp"))
    storage.get_events().init(app_id)
    events = []
    for u in range(9):
        for i in range(n_items):
            events.append(Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{i}",
                properties=DataMap(
                    {"rating": 5.0 if (u % 2) == (i % 2) else 1.0}),
                event_time=dt.datetime(2021, 1, 3, 0, (u + i) % 60,
                                       tzinfo=dt.timezone.utc)))
    storage.get_events().insert_batch(events, app_id)
    engine = RecommendationEngine()
    ep = EngineParams(
        data_source_params=DataSourceParams(appName="AotApp"),
        algorithm_params_list=(
            ("als", ALSAlgorithmParams(rank=rank, numIterations=2,
                                       lambda_=0.05, seed=7)),))
    iid = run_train(WorkflowContext(storage=storage), engine, ep,
                    engine_factory="aot-test",
                    params_json={
                        "datasource": {"params": {"appName": "AotApp"}},
                        "algorithms": [{"name": "als", "params": {
                            "rank": rank, "numIterations": 2,
                            "lambda": 0.05, "seed": 7}}]})
    return engine, iid


# ---------------------------------------------------------------------------
# the registration lint: no unregistered @jax.jit on the serving path
# ---------------------------------------------------------------------------

def _jit_decorated_defs(path):
    """Function names in ``path`` decorated with jax.jit (bare or via
    functools.partial(jax.jit, ...)) — AST-based so aliasing/formatting
    can't hide one."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for dec in node.decorator_list:
            target = dec
            if (isinstance(dec, ast.Call) and dec.args
                    and isinstance(dec.func, ast.Name)
                    and dec.func.id == "partial"):
                target = dec.args[0]
            if isinstance(target, ast.Call):
                target = target.func
            if (isinstance(target, ast.Attribute) and target.attr == "jit"
                    and isinstance(target.value, ast.Name)
                    and target.value.id == "jax"):
                out.append(node.name)
    return out


def test_every_serving_path_jit_is_registered():
    """RUNTIME half of the AOT-registration lint: after real imports,
    every jitted def in these modules is the SAME OBJECT a register_jit
    call recorded (catches registration of a stale alias/wrapper). The
    static half — which modules are in scope at all — is now the
    structural `aot-registration` pass of `pio lint`
    (tools/analyze/passes/aot_registration.py): repo-wide, no opt-in
    list; tests/test_lint.py asserts this list is a subset of what the
    pass discovers, so a module added here without the pass knowing it
    is impossible."""
    import importlib

    serving_modules = [
        ("ops/topk.py", "predictionio_tpu.ops.topk"),
        # the sharded serving kernel lives with its layout machinery in
        # parallel/ but is very much on the serving path
        ("parallel/serve_dist.py", "predictionio_tpu.parallel.serve_dist"),
    ]
    serving_dir = os.path.join(PKG, "serving")
    for f in sorted(os.listdir(serving_dir)):
        if f.endswith(".py") and f != "__init__.py":
            serving_modules.append(
                (f"serving/{f}", f"predictionio_tpu.serving.{f[:-3]}"))
    # import every linted module FIRST: registration happens at import
    # time (serve_dist registers its kernel in its own module body)
    modules = {rel: importlib.import_module(modname)
               for rel, modname in serving_modules}
    registered_fns = {id(r.fn) for r in aot._REGISTRY.values()}
    # jit wrappers may nest (e.g. devicewatch.watch_jit); compare on
    # the module attribute object itself
    offenders = []
    for rel, modname in serving_modules:
        mod = modules[rel]
        for name in _jit_decorated_defs(os.path.join(PKG, rel)):
            fn = getattr(mod, name, None)
            if fn is None:
                continue
            if id(fn) not in registered_fns:
                offenders.append(f"{rel}:{name}")
    assert not offenders, (
        "jitted serving-path entry points not registered with the AOT "
        "enumerator (serving/aot.py register_jit) — they would compile "
        "lazily on the first request and reintroduce the warmup cliff:"
        "\n  " + "\n  ".join(offenders))


def test_lint_actually_detects_jit_defs(tmp_path):
    src = ("from functools import partial\nimport jax\n"
           "@partial(jax.jit, static_argnames=('k',))\n"
           "def f(x, k=1):\n    return x\n"
           "@jax.jit\ndef g(x):\n    return x\n"
           "def h(x):\n    return x\n")
    p = tmp_path / "m.py"
    p.write_text(src)
    assert _jit_decorated_defs(str(p)) == ["f", "g"]


# ---------------------------------------------------------------------------
# shape oracle: k clamp + bucket pruning
# ---------------------------------------------------------------------------

def test_serving_ks_default_clamps_to_model(monkeypatch):
    monkeypatch.delenv("PIO_AOT_KS", raising=False)
    assert aot.serving_ks(100) == (10,)
    assert aot.serving_ks(6) == (6,)   # min(num, n_items), like serving
    monkeypatch.setenv("PIO_AOT_KS", "1, 5,10, junk, -2")
    assert aot.serving_ks(100) == (1, 5, 10)
    assert aot.serving_ks(7) == (1, 5, 7)   # 10 clamps onto 7, deduped


def test_prune_buckets():
    buckets = (1, 4, 16, 64)
    # no observations: nothing pruned (a fresh process must stay safe)
    assert aot.prune_buckets(buckets, observed={}) == buckets
    # observed 3-query flushes map to bucket 4; the top bucket is
    # always kept as the overflow cap
    assert aot.prune_buckets(buckets, observed={3: 5}) == (4, 64)
    assert aot.prune_buckets(buckets, observed={1: 9, 20: 1}) == (1, 64)
    # everything observed: everything survives
    assert aot.prune_buckets(
        buckets, observed={1: 1, 3: 1, 9: 1, 40: 1}) == buckets


def test_prune_buckets_env_off(monkeypatch):
    monkeypatch.setenv("PIO_AOT_PRUNE", "0")
    assert aot.prune_buckets((1, 4, 16, 64),
                             observed={1: 5}) == (1, 4, 16, 64)


def test_pruned_serve_buckets_caps_at_batch_size(monkeypatch):
    monkeypatch.delenv("PIO_SERVE_BUCKETS", raising=False)
    # pruning pinned off: the process registry may hold flush-size
    # observations from earlier tests (by design — that histogram is
    # exactly what a live /reload prunes against)
    monkeypatch.setenv("PIO_AOT_PRUNE", "0")
    assert aot.pruned_serve_buckets(8) == (1, 4)
    assert aot.pruned_serve_buckets(64) == (1, 4, 16, 64)
    # observed sizes recorded by the batcher feed the pruning
    monkeypatch.delenv("PIO_AOT_PRUNE")
    assert aot.prune_buckets((1, 4, 16, 64), observed={2: 3}) == (4, 64)


def test_flush_scoped_buckets_resolution():
    """The batcher installs its pruned set on the worker thread for the
    duration of a flush; outside that scope — and on every other
    thread — resolution stays env/default."""
    assert protocol.pad_buckets() == (1, 4, 16, 64)
    with protocol.flush_buckets((4, 64)):
        assert protocol.pad_buckets() == (4, 64)        # scoped set wins
        assert protocol.bucket_for(2) == 4
        assert protocol.pad_buckets((1, 2)) == (1, 2)   # explicit arg wins
        # nesting restores correctly
        with protocol.flush_buckets((1, 8)):
            assert protocol.pad_buckets() == (1, 8)
        assert protocol.pad_buckets() == (4, 64)
        # other threads are unaffected
        import threading
        seen = []
        t = threading.Thread(
            target=lambda: seen.append(protocol.pad_buckets()))
        t.start(); t.join()
        assert seen == [(1, 4, 16, 64)]
    assert protocol.pad_buckets() == (1, 4, 16, 64)
    with protocol.flush_buckets(None):                  # passthrough
        assert protocol.pad_buckets() == (1, 4, 16, 64)


def test_batcher_flush_sees_its_own_buckets():
    """predict_batch inside a flush resolves the BATCHER's bucket set —
    the set whose programs the deploy prebuilt."""
    seen = []

    def flush(items):
        seen.append(protocol.pad_buckets())
        return list(items)

    from predictionio_tpu.serving import MicroBatcher
    b = MicroBatcher(flush, max_batch_size=2, max_delay_ms=1,
                     buckets=(2, 64))
    try:
        b.submit("x")
    finally:
        b.close()
    assert seen == [(2, 64)]
    assert protocol.pad_buckets() == (1, 4, 16, 64)     # nothing leaked


# ---------------------------------------------------------------------------
# AOT / lazy-jit parity: bit-identical programs
# ---------------------------------------------------------------------------

def test_topk_programs_aot_jit_parity():
    """Every enumerated (bucket, k) serving program, compiled via
    jit(...).lower().compile() from declared shapes, produces BIT-
    identical outputs to the lazy jit path."""
    import jax

    rng = np.random.RandomState(11)
    n_users, n_items, rank = 13, 8, 4
    U = jax.device_put(rng.randn(n_users, rank).astype(np.float32))
    V = jax.device_put(rng.randn(n_items, rank).astype(np.float32))
    for spec in aot.specs_topk_for_users(n_users, n_items, rank,
                                         buckets=(1, 4), ks=(1, 3)):
        bucket, k = spec.key[-2], spec.key[-1]
        compiled = spec.build()
        ix = np.asarray(rng.randint(0, n_users, bucket), dtype=np.int32)
        va, ia = jax.device_get(compiled(U, V, jax.device_put(ix)))
        vj, ij = jax.device_get(topk.topk_for_users(U, V, ix, k=k))
        assert np.array_equal(va, vj) and np.array_equal(ia, ij), spec.key
    for spec in aot.specs_topk_for_user(n_users, n_items, rank, ks=(3,)):
        compiled = spec.build()
        va, ia = jax.device_get(
            compiled(U, V, jax.device_put(np.int32(5))))
        vj, ij = jax.device_get(topk.topk_for_user(U, V, np.int32(5), k=3))
        assert np.array_equal(va, vj) and np.array_equal(ia, ij)


def test_training_program_aot_jit_parity():
    """The declared-shape-lowered scan trainer (bucket_units as the
    shape oracle) matches train_explicit(kernel="scan") bit for bit."""
    rng = np.random.RandomState(3)
    nnz, n_u, n_i, rank = 150, 11, 8, 3
    u = rng.randint(0, n_u, nnz).astype(np.int32)
    i = rng.randint(0, n_i, nnz).astype(np.int32)
    r = (rng.randint(1, 11, nnz) * 0.5).astype(np.float32)
    data = als.prepare_ratings(u, i, r, n_users=n_u, n_items=n_i,
                               chunk=64, device=True)
    U_jit, V_jit = als.train_explicit(data, rank=rank, iterations=3,
                                      seed=5, chunk=64, kernel="scan")
    compiled = als.lower_train_explicit(n_u, n_i, rank, nnz,
                                        chunk=64).compile()
    u0, v0 = als._seed_factors(5, n_u, n_i, rank)
    bu, bi = data.by_user, data.by_item
    U_aot, V_aot = compiled(
        bu.self_idx, bu.other_idx, bu.rating, bu.counts,
        bi.self_idx, bi.other_idx, bi.rating, bi.counts,
        u0, v0, 3, 0.01)
    assert np.array_equal(np.asarray(U_jit), np.asarray(U_aot))
    assert np.array_equal(np.asarray(V_jit), np.asarray(V_aot))


def test_training_program_specs_scan_only(monkeypatch):
    monkeypatch.setenv("PIO_ALS_KERNEL", "scan")
    specs = aot.training_program_specs(10, 8, 4, 100, chunk=64)
    assert [s.name for s in specs] == ["als_train_scan"]
    monkeypatch.setenv("PIO_ALS_KERNEL", "hybrid")
    assert aot.training_program_specs(10, 8, 4, 100, chunk=64) == []


def test_prebuild_reports_and_memoizes():
    import jax

    rng = np.random.RandomState(0)
    U = jax.device_put(rng.randn(17, 3).astype(np.float32))
    V = jax.device_put(rng.randn(5, 3).astype(np.float32))
    specs = aot.specs_topk_for_users(17, 5, 3, (1, 4), (2,),
                                     arrays=(U, V))
    rep = aot.prebuild(specs)
    assert rep.summary()["programs"] == 2
    assert rep.summary()["failed"] == 0
    assert rep.summary()["compiled"] + rep.summary()["memoized"] == 2
    # second prebuild of the same keys is memoized (a /reload of same-
    # shape factors costs nothing)
    rep2 = aot.prebuild(specs)
    assert rep2.summary()["memoized"] == 2


def test_prebuild_failure_degrades_to_lazy():
    bad = aot.ProgramSpec(name="broken", key=("broken", 1),
                          lower=lambda: (_ for _ in ()).throw(
                              RuntimeError("boom")))
    rep = aot.prebuild([bad])
    assert rep.summary()["failed"] == 1   # logged + counted, not raised
    # the deliberate failure must not poison later doctor green paths
    # (the registry is process-global and doctor reads absolutes)
    _clear_counter_family("pio_aot_programs_total")


# ---------------------------------------------------------------------------
# compile-cache artifact: export / import / graceful mismatch
# ---------------------------------------------------------------------------

def _fake_cache(d, entries):
    os.makedirs(d, exist_ok=True)
    for name, payload in entries.items():
        with open(os.path.join(d, name), "wb") as f:
            f.write(payload)


def test_cache_artifact_roundtrip(tmp_path):
    src = str(tmp_path / "src")
    dst = str(tmp_path / "dst")
    _fake_cache(src, {"old_entry": b"OLD"})
    before = model_io.cache_snapshot(src)
    _fake_cache(src, {"new_a": b"AAAA", "new_b": b"BB"})
    blob = model_io.export_compile_cache(src, since=before)
    assert blob is not None
    summary = model_io.import_compile_cache(blob, dst)
    assert summary == {"imported": 2, "skipped": 0, "reason": ""}
    with open(os.path.join(dst, "new_a"), "rb") as f:
        assert f.read() == b"AAAA"
    assert not os.path.exists(os.path.join(dst, "old_entry"))
    # existing files are never overwritten
    summary = model_io.import_compile_cache(blob, dst)
    assert summary["imported"] == 0 and summary["skipped"] == 2


def test_cache_artifact_empty_delta_exports_nothing(tmp_path):
    src = str(tmp_path / "src")
    _fake_cache(src, {"only": b"X"})
    before = model_io.cache_snapshot(src)
    assert model_io.export_compile_cache(src, since=before) is None


def test_cache_artifact_mismatch_degrades_gracefully(tmp_path):
    """A jaxlib/platform mismatch — the portability hazard of shipped
    cache entries (KNOWN_ISSUES #9) — imports nothing and reports why,
    instead of erroring or seeding unusable entries."""
    import pickle

    src = str(tmp_path / "src")
    dst = str(tmp_path / "dst")
    _fake_cache(src, {"e1": b"X"})
    blob = model_io.export_compile_cache(src)
    artifact = pickle.loads(blob)
    artifact["meta"]["jaxlib"] = "0.0.0-elsewhere"
    summary = model_io.import_compile_cache(pickle.dumps(artifact), dst)
    assert summary["imported"] == 0 and summary["skipped"] == 1
    assert "mismatch" in summary["reason"]
    assert not os.path.exists(os.path.join(dst, "e1"))
    # corrupt blob: summary, not an exception
    summary = model_io.import_compile_cache(b"\x80garbage", dst)
    assert summary["imported"] == 0 and summary["reason"]


def test_cache_artifact_refuses_path_traversal(tmp_path):
    import pickle

    dst = str(tmp_path / "dst")
    blob = pickle.dumps({
        "format": "pio-jaxcache-v1", "meta": model_io.cache_fingerprint(),
        "entries": {"../escape": b"X", ".hidden": b"Y", "fine": b"Z"}})
    summary = model_io.import_compile_cache(blob, dst)
    assert summary["imported"] == 1 and summary["skipped"] == 2
    assert not os.path.exists(str(tmp_path / "escape"))


def test_export_train_artifact_inserts_models_row(memory_storage,
                                                  tmp_path):
    """The `pio train` side: serving programs AOT-build from declared
    shapes (host numpy model — no device residency needed) and the
    cache delta lands in the Models store under <instance>.jaxcache."""
    from predictionio_tpu.models.recommendation.als_algorithm import (
        ALSAlgorithm, ALSModel,
    )
    from predictionio_tpu.data.bimap import BiMap

    cache = str(tmp_path / "cache")
    _fake_cache(cache, {"seed": b"S"})
    before = model_io.cache_snapshot(cache)
    _fake_cache(cache, {"train_entry": b"T"})
    rng = np.random.RandomState(1)
    model = ALSModel(
        rank=3,
        user_factors=rng.randn(6, 3).astype(np.float32),
        item_factors=rng.randn(4, 3).astype(np.float32),
        user_vocab=BiMap.string_int([f"u{i}" for i in range(6)]),
        item_vocab=BiMap.string_int([f"i{i}" for i in range(4)]))
    algo = ALSAlgorithm(ALSAlgorithmParams(rank=3))
    summary = aot.export_train_artifact(
        memory_storage, "inst-1", [algo], [model], cache, before)
    assert summary["programs"] >= 1 and summary["failed"] == 0
    assert summary["entries"] == 1
    row = memory_storage.get_model_data_models().get(
        model_io.cache_artifact_id("inst-1"))
    assert row is not None
    imported = model_io.import_compile_cache(
        row.models, str(tmp_path / "replica"))
    assert imported["imported"] == 1


# ---------------------------------------------------------------------------
# deploy integration: prebuild before ready, explicit warmup mark,
# artifact import, time-to-ready
# ---------------------------------------------------------------------------

def test_deploy_prebuilds_and_marks_warmup(memory_storage):
    engine, _iid = _train_engine(memory_storage)
    telemetry.set_enabled(True)
    assert not devicewatch.serving_warmup_done()
    api = QueryAPI(storage=memory_storage, engine=engine,
                   config=ServerConfig(batching="on"))
    try:
        # warmup end is the AOT-complete mark, not a flush count
        assert devicewatch.serving_warmup_done()
        assert api.time_to_ready_s is not None
        st, info = api.handle("GET", "/")
        assert st == 200
        assert info["aot"]["enabled"] is True
        assert info["aot"]["programs"] >= 1
        assert info["aot"]["failed"] == 0
        assert info["aot"]["timeToReadyS"] is not None
        st, rz = api.handle("GET", "/readyz")
        assert st == 200 and rz["aotPrograms"] == info["aot"]["programs"]
        # the metrics surface doctor scrapes
        _st, payload, _h = api.handle("GET", "/metrics")
        assert "pio_aot_programs_total" in payload
        assert "pio_time_to_ready_seconds" in payload
        # /debug/device.json carries the same summary
        _st, dev, _h = api.handle("GET", "/debug/device.json")
        assert json.loads(dev)["aot"]["programs"] >= 1
        # a post-ready query compiles NOTHING: the prebuilt program set
        # covers the standard bucketed path for the declared k
        base = devicewatch.post_warmup_recompiles()
        st, body = api.handle("POST", "/queries.json", body=json.dumps(
            {"user": "u1", "num": 10}).encode())
        assert st == 200 and body["itemScores"]
        assert devicewatch.post_warmup_recompiles() == base
    finally:
        api.close()


def test_deploy_imports_cache_artifact(memory_storage, tmp_path,
                                       monkeypatch):
    engine, iid = _train_engine(memory_storage)
    art_src = str(tmp_path / "train_cache")
    _fake_cache(art_src, {"shipped_entry": b"E"})
    memory_storage.get_model_data_models().insert(Model(
        id=model_io.cache_artifact_id(iid),
        models=model_io.export_compile_cache(art_src)))
    replica_cache = str(tmp_path / "replica_cache")
    monkeypatch.setattr(aot, "ensure_persistent_cache",
                        lambda: replica_cache)
    api = QueryAPI(storage=memory_storage, engine=engine)
    try:
        st, info = api.handle("GET", "/")
        assert info["aot"]["cacheImport"]["imported"] == 1
        assert os.path.exists(os.path.join(replica_cache,
                                           "shipped_entry"))
    finally:
        api.close()


def test_deploy_mismatched_artifact_never_errors(memory_storage,
                                                 tmp_path, monkeypatch):
    import pickle

    engine, iid = _train_engine(memory_storage)
    blob = pickle.dumps({
        "format": "pio-jaxcache-v1",
        "meta": {"jax": "?", "jaxlib": "other", "backend": "mars"},
        "entries": {"e": b"X"}})
    memory_storage.get_model_data_models().insert(Model(
        id=model_io.cache_artifact_id(iid), models=blob))
    replica_cache = str(tmp_path / "replica_cache")
    monkeypatch.setattr(aot, "ensure_persistent_cache",
                        lambda: replica_cache)
    api = QueryAPI(storage=memory_storage, engine=engine)
    try:
        st, info = api.handle("GET", "/")
        ci = info["aot"]["cacheImport"]
        assert ci["imported"] == 0 and "mismatch" in ci["reason"]
        # the deploy still serves (lazy compile fallback)
        st, body = api.handle("POST", "/queries.json", body=json.dumps(
            {"user": "u1", "num": 3}).encode())
        assert st == 200 and body["itemScores"]
    finally:
        api.close()


def test_pio_aot_0_wire_byte_identical(memory_storage, monkeypatch):
    """The escape hatch: PIO_AOT=0 restores the pre-AOT deploy exactly
    — legacy `GET /` key set, no warmup mark, byte-identical query
    responses."""
    engine, _iid = _train_engine(memory_storage)
    body = json.dumps({"user": "u2", "num": 4}).encode()

    monkeypatch.setenv("PIO_AOT", "0")
    devicewatch.reset_watchdog()
    api_off = QueryAPI(storage=memory_storage, engine=engine)
    st_off, resp_off = api_off.handle("POST", "/queries.json", body=body)
    _, info_off = api_off.handle("GET", "/")
    assert set(info_off) == {
        "status", "engineInstance", "algorithms", "requestCount",
        "avgServingSec", "lastServingSec", "degradedCount", "draining",
        "serverStartTime", "generation", "batching", "codec",
        "transport", "hostSpans", "host"}
    assert not devicewatch.serving_warmup_done()
    _, rz_off = api_off.handle("GET", "/readyz")
    assert "aotPrograms" not in rz_off
    api_off.close()

    monkeypatch.delenv("PIO_AOT")
    api_on = QueryAPI(storage=memory_storage, engine=engine)
    st_on, resp_on = api_on.handle("POST", "/queries.json", body=body)
    api_on.close()
    assert (st_off, json.dumps(resp_off)) == (st_on, json.dumps(resp_on))


def test_aot_off_config_mode(memory_storage):
    engine, _iid = _train_engine(memory_storage)
    api = QueryAPI(storage=memory_storage, engine=engine,
                   config=ServerConfig(aot="off"))
    try:
        _, info = api.handle("GET", "/")
        assert "aot" not in info
    finally:
        api.close()
    with pytest.raises(ValueError, match="auto/on/off"):
        QueryAPI(storage=memory_storage, engine=engine,
                 config=ServerConfig(aot="bogus"))


def test_deploy_installs_pruned_buckets(memory_storage, monkeypatch):
    """The deploy's bucket set is capped at the batcher's max batch
    size and handed to the batcher, so flush padding resolves exactly
    the prebuilt programs. (Pruning is pinned off here: the process
    registry may hold flush-size observations from earlier tests.)"""
    monkeypatch.setenv("PIO_AOT_PRUNE", "0")
    engine, _iid = _train_engine(memory_storage)
    api = QueryAPI(storage=memory_storage, engine=engine,
                   config=ServerConfig(batching="on", batch_max_size=8))
    try:
        _, info = api.handle("GET", "/")
        assert info["aot"]["buckets"] == [1, 4]
        assert info["batching"]["buckets"] == [1, 4]
        # outside any flush, process defaults are untouched
        assert protocol.pad_buckets() == (1, 4, 16, 64)
    finally:
        api.close()


# ---------------------------------------------------------------------------
# doctor satellites
# ---------------------------------------------------------------------------

def _scraped(metrics_body="", device=None):
    ok = {"status": 200, "body": json.dumps({"status": "ok"})}
    return {
        "url": "http://t", "healthz": dict(ok), "readyz": dict(ok),
        "metrics": {"status": 200, "body": metrics_body},
        "traces": {"status": 404, "body": ""},
        "device": {"status": 200,
                   "body": json.dumps(device or {"telemetry": True})},
    }


def _aot_check(checks):
    return next(c for c in checks if c[0] == "aot")


def test_doctor_aot_line():
    from predictionio_tpu.tools import doctor

    # no AOT metrics at all: informational, not a failure
    checks = doctor.diagnose(_scraped())
    assert _aot_check(checks)[1] == doctor.NA

    body = ('pio_aot_programs_total{status="primed"} 4\n'
            'pio_aot_programs_total{status="memoized"} 4\n'
            'pio_aot_prebuild_seconds 2.5\n'
            'pio_time_to_ready_seconds{server="query#0"} 3.25\n')
    check = _aot_check(doctor.diagnose(_scraped(body)))
    assert check[1] == doctor.OK
    assert "8 programs" in check[2] and "50% hit" in check[2]
    assert "ready in 3.2" in check[2]

    # failed builds are RED (lazy compiles back on the latency path)
    body_fail = body + 'pio_aot_programs_total{status="failed"} 1\n'
    assert _aot_check(doctor.diagnose(_scraped(body_fail)))[1] == doctor.RED

    # over the 10 s warm-replica target: WARN
    slow = body.replace("3.25", "45.0")
    assert _aot_check(doctor.diagnose(_scraped(slow)))[1] == doctor.WARN


def test_time_to_ready_gauge_exported(memory_storage):
    engine, _iid = _train_engine(memory_storage)
    telemetry.set_enabled(True)
    api = QueryAPI(storage=memory_storage, engine=engine)
    try:
        _st, payload, _h = api.handle("GET", "/metrics")
        values = [float(ln.rsplit(" ", 1)[1])
                  for ln in payload.splitlines()
                  if ln.startswith("pio_time_to_ready_seconds{")]
        # per-server labels; earlier instances whose constructor raised
        # (deliberately, in other tests) legitimately sit at 0
        assert values and max(values) > 0
    finally:
        api.close()


# ---------------------------------------------------------------------------
# the compile cache is placed from outside (PR 25)
# ---------------------------------------------------------------------------

@pytest.fixture()
def cache_spy(monkeypatch):
    """Record what ensure_persistent_cache asks of jax, change nothing."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    calls = {"update": [], "reset": 0}
    # an earlier run_train in this process may have placed the default
    real_update, prev = jax.config.update, jax.config.jax_compilation_cache_dir
    real_update("jax_compilation_cache_dir", None)
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls["update"].append(
                            (name, value)))
    monkeypatch.setattr(compilation_cache, "reset_cache",
                        lambda: calls.__setitem__(
                            "reset", calls["reset"] + 1))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("PIO_COMPILE_CACHE_DIR", raising=False)
    yield calls
    real_update("jax_compilation_cache_dir", prev)


def test_cache_dir_from_jax_env_sets_no_directory(cache_spy, monkeypatch,
                                                 tmp_path):
    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    assert aot.ensure_persistent_cache() == placed
    assert "jax_compilation_cache_dir" not in dict(cache_spy["update"])
    assert cache_spy["reset"] == 0
    # --compile-cache naming the same directory is fine, and is what
    # the train artifact is then snapshotted from
    monkeypatch.setenv("PIO_COMPILE_CACHE_DIR", placed + os.sep)
    assert aot.ensure_persistent_cache() == placed
    assert aot.artifact_cache_dir() == placed + os.sep
    assert "jax_compilation_cache_dir" not in dict(cache_spy["update"])


def test_cache_dir_disagreement_is_refused(cache_spy, monkeypatch,
                                           tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "a"))
    monkeypatch.setenv("PIO_COMPILE_CACHE_DIR", str(tmp_path / "b"))
    with pytest.raises(ValueError, match="JAX_COMPILATION_CACHE_DIR"):
        aot.ensure_persistent_cache()
    assert cache_spy["update"] == []


@pytest.mark.parametrize("explicit", [False, True])
def test_cache_dir_default_is_the_checkout(cache_spy, monkeypatch,
                                          tmp_path, explicit):
    """Neither variable set: <checkout>/.jax_cache — a fixed path, never
    a temp name; an explicit --compile-cache places it instead."""
    want = os.path.join(os.path.dirname(PKG), ".jax_cache")
    if explicit:
        want = str(tmp_path / "explicit")
        monkeypatch.setenv("PIO_COMPILE_CACHE_DIR", want)
    assert aot.ensure_persistent_cache() == want
    assert dict(cache_spy["update"])["jax_compilation_cache_dir"] == want
    assert cache_spy["reset"] == 1
    assert aot.artifact_cache_dir() == (want if explicit else "")


def test_default_cache_ships_no_artifact(memory_storage, monkeypatch):
    """The train-time export of cache entries stays tied to an explicit
    --compile-cache: a default cache does not start shipping hundreds
    of MB with every model."""
    monkeypatch.delenv("PIO_COMPILE_CACHE_DIR", raising=False)
    _engine, iid = _train_engine(memory_storage)
    assert memory_storage.get_model_data_models().get(
        model_io.cache_artifact_id(iid)) is None


def test_importing_the_package_initialises_no_backend():
    """A process that imports any module of the package — a daemon that
    never serves a query included — must not take the chip: no
    import-time jnp constant, jax.devices() or jit call."""
    import subprocess
    import sys
    code = (
        "import importlib, pkgutil, predictionio_tpu\n"
        "from jax._src import xla_bridge\n"
        "for m in pkgutil.walk_packages(predictionio_tpu.__path__,\n"
        "                               'predictionio_tpu.'):\n"
        "    if m.name.endswith('._pio_native'):\n"
        "        continue\n"
        "    importlib.import_module(m.name)\n"
        "    assert not xla_bridge._backends, m.name\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.path.dirname(PKG)}
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
