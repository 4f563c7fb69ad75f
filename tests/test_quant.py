"""Quantized serving (ops/quant.py).

The acceptance surface of ISSUE 11: int8 per-row-scale quantization of
both factor matrices with dequantize-free int8 x int8 scoring; the
replicated kernel bit-identical to an integer NumPy reference AND to
the sharded int8 kernel, ties included; the ranking-parity contract
(recall@k >= 0.99, exact-match@1 >= 0.999 vs fp32 on a trained model —
KNOWN_ISSUES #12); PIO_SERVE_QUANT=off wire-byte identical to the
pre-quant server (replicated and sharded); AOT-prebuilt quant programs
keeping post_warmup_recompiles at 0 with quant on; and the doctor /
deploy-state surfaces, including the requested-but-fell-back WARN.
"""

import datetime as dt
import json

import numpy as np
import pytest

import jax

from predictionio_tpu.common import devicewatch, telemetry
from predictionio_tpu.controller import EngineParams
from predictionio_tpu.data.datamap import DataMap
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage import App, Storage
from predictionio_tpu.models.recommendation import (
    ALSAlgorithmParams, DataSourceParams, RecommendationEngine,
)
from predictionio_tpu.ops import quant
from predictionio_tpu.parallel import serve_dist
from predictionio_tpu.workflow import WorkflowContext, model_io, run_train
from predictionio_tpu.workflow.create_server import QueryAPI, ServerConfig


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv("PIO_SERVE_QUANT", raising=False)
    yield
    quant.record_state(None)
    serve_dist.record_state(None)
    telemetry.set_enabled(None)


def _factors(n_users=33, n_items=1100, rank=10, seed=0):
    rng = np.random.default_rng(seed)
    U = rng.normal(size=(n_users, rank)).astype(np.float32)
    V = rng.normal(size=(n_items, rank)).astype(np.float32)
    return U, V


# ---------------------------------------------------------------------------
# quantization properties
# ---------------------------------------------------------------------------

def test_quantize_rows_properties():
    M = np.array([[1.0, -2.0, 0.5],
                  [0.0, 0.0, 0.0],          # all-zero row: scale 1.0
                  [127.0, -127.0, 63.5],
                  [1e-6, -1e-6, 0.0]], dtype=np.float32)
    q, s = quant.quantize_rows(M)
    assert q.dtype == np.int8 and s.dtype == np.float32
    assert np.abs(q).max() <= 127
    assert s[1] == 1.0 and not q[1].any()
    # max round-trip error per element is half a quantization step
    deq = quant.dequantize_rows(q, s)
    assert np.all(np.abs(deq - M) <= s[:, None] / 2 + 1e-9)
    # the row max always hits +/-127 exactly (symmetric per-row scale)
    assert np.abs(q[0]).max() == 127 and np.abs(q[2]).max() == 127


def test_quantized_factors_bytes():
    U, V = _factors()
    qf = quant.QuantizedFactors.from_factors(U, V)
    assert qf.n_users == 33 and qf.n_items == 1100 and qf.rank == 10
    assert qf.fp32_bytes() == (33 + 1100) * 10 * 4
    assert qf.int8_bytes() == (33 + 1100) * 10 + (33 + 1100) * 4
    # the int8 MATRICES are exactly 0.25x of fp32
    assert ((33 + 1100) * 10) / qf.fp32_bytes() == 0.25


# ---------------------------------------------------------------------------
# kernel parity: replicated int8 == integer NumPy reference == sharded int8
# ---------------------------------------------------------------------------

def test_quant_kernel_matches_integer_reference_bit_identical():
    """The replicated kernel against the same arithmetic in NumPy (exact
    int32 dot products, one fp32 rescale, descending score with ties to
    the lowest index): constructed ties (duplicated item rows quantize
    identically), k from 1 to the whole catalog, bucket sizes down to
    1, and never a layout-padding column in an answer."""
    U, V = _factors()
    V[707] = V[3]
    V[13] = V[3]
    qf = quant.QuantizedFactors.from_factors(U, V)
    qs = quant.QuantizedServing.build(qf)
    assert np.shape(qs.vt_q) == (10, 1536)      # 1100 -> 3 x ITEM_TILE
    s32 = qf.u_q.astype(np.int32) @ qf.v_q.astype(np.int32).T
    want = s32.astype(np.float32) * (qf.u_scale[:, None]
                                     * qf.v_scale[None, :])
    for ixs in (np.arange(16, dtype=np.int32),
                np.asarray([7], dtype=np.int32)):
        for k in (1, 5, 10, 300, 1100):
            gv, gi = jax.device_get(qs.topk(ixs, k))
            order = np.argsort(-want[ixs], axis=1, kind="stable")[:, :k]
            np.testing.assert_array_equal(gi, order, err_msg=f"k={k}")
            np.testing.assert_array_equal(
                gv.view(np.int32),
                np.take_along_axis(want[ixs], order, 1).view(np.int32),
                err_msg=f"k={k} b={len(ixs)}")
    # the tie rule itself: clones of item 3 rank lowest-index first
    _gv, gi = jax.device_get(qs.topk(np.arange(8, dtype=np.int32), 1100))
    for row in gi:
        pos = [int(np.flatnonzero(row == c)[0]) for c in (3, 13, 707)]
        assert pos == sorted(pos), pos


def test_inline_quant_matches_batched_row():
    U, V = _factors(seed=1)
    qf = quant.QuantizedFactors.from_factors(U, V)
    qs = quant.QuantizedServing.build(qf)
    iv, ii = jax.device_get(qs.topk_one(np.int32(7), 10))
    bv, bi = jax.device_get(qs.topk(np.asarray([7], np.int32), 10))
    np.testing.assert_array_equal(iv.view(np.int32),
                                  bv[0].view(np.int32))
    np.testing.assert_array_equal(ii, bi[0])


def test_sharded_quant_matches_replicated_quant_bit_identical():
    """8 int8 shards vs the replicated quant kernel: exact integer
    scores + elementwise rescale leave no room for drift."""
    U, V = _factors(seed=2)
    V[1099] = V[5]     # cross-shard tie with the clone in shard 0
    qf = quant.QuantizedFactors.from_factors(U, V)
    qs = quant.QuantizedServing.build(qf)
    sharded = serve_dist.shard_factors(U, V, quant=qf)
    assert sharded.dtype == "int8" and sharded.n_shards == 8
    ixs = np.array([0, 5, 12, 0, 31], dtype=np.int32)
    for k in (1, 10, 200):
        sv, si = jax.device_get(sharded.topk(ixs, k))
        rv, ri = jax.device_get(qs.topk(ixs, k))
        np.testing.assert_array_equal(sv.view(np.int32),
                                      rv.view(np.int32), err_msg=f"k={k}")
        np.testing.assert_array_equal(si, ri, err_msg=f"k={k}")


def test_sharded_quant_per_shard_bytes_quartered():
    U, V = _factors(rank=64, seed=3)
    qf = quant.QuantizedFactors.from_factors(U, V)
    int8 = serve_dist.shard_factors(U, V, quant=qf)
    fp32 = serve_dist.shard_factors(U, V)
    ratio = int8.per_shard_bytes() / fp32.per_shard_bytes()
    assert ratio <= 0.30, ratio
    assert int8.summary()["dtype"] == "int8"
    assert "dtype" not in fp32.summary()     # fp32 keeps the PR 8 keys


# ---------------------------------------------------------------------------
# mode resolution
# ---------------------------------------------------------------------------

def test_mode_resolution(monkeypatch):
    # bare defaults: auto + CPU backend -> fp32
    assert quant.configured_mode() == "auto"
    assert not quant.serving_enabled()
    with quant.deploy_scope("on"):
        assert quant.serving_enabled()
    with quant.deploy_scope("off"):
        assert not quant.serving_enabled()
    # env wins over the config scope
    monkeypatch.setenv("PIO_SERVE_QUANT", "0")
    with quant.deploy_scope("on"):
        assert not quant.serving_enabled()
    monkeypatch.setenv("PIO_SERVE_QUANT", "1")
    with quant.deploy_scope("off"):
        assert quant.serving_enabled()
    monkeypatch.delenv("PIO_SERVE_QUANT")
    # auto engages on accelerator backends
    monkeypatch.setattr(quant, "_accelerator_platform", lambda: True)
    with quant.deploy_scope("auto"):
        assert quant.serving_enabled()
    with pytest.raises(ValueError):
        with quant.deploy_scope("sideways"):
            pass


def test_accept_parity(monkeypatch):
    low = {"k": 10, "recall": 0.5, "exact1": 0.5}
    high = {"k": 10, "recall": 1.0, "exact1": 1.0}
    with quant.deploy_scope("auto"):
        assert not quant.accept_parity(low)
        assert quant.accept_parity(high)
    with quant.deploy_scope("on"):
        assert quant.accept_parity(low)      # operator's explicit call
    monkeypatch.setenv("PIO_SERVE_QUANT_RECALL_MIN", "0.4")
    with quant.deploy_scope("auto"):
        assert quant.accept_parity(low)


# ---------------------------------------------------------------------------
# the ranking-parity contract on a TRAINED model
# ---------------------------------------------------------------------------

def _ladder_storage():
    """A trained model with real top-10 structure: each user rates a
    12-item preference ladder (5.0 stepping down by 0.3) over a 1.0
    background — trained score margins comfortably exceed the int8
    quantization noise, which is what the contract requires of a model
    before quantized serving makes sense (KNOWN_ISSUES #12)."""
    storage = Storage(env={
        "PIO_STORAGE_SOURCES_M_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
    })
    app_id = storage.get_meta_data_apps().insert(App(0, "QuantApp"))
    storage.get_events().init(app_id)
    rng = np.random.default_rng(7)
    n_u, n_i = 60, 48
    events = []
    for u in range(n_u):
        rated = {}
        for j in range(12):
            rated[(u * 7 + j * 3) % n_i] = 5.0 - 0.3 * j
        for i in range(n_i):
            if i not in rated and rng.random() < 0.5:
                rated[i] = 1.0
        for i, r in rated.items():
            events.append(Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{i}",
                properties=DataMap({"rating": r}),
                event_time=dt.datetime(2021, 2, 3, 0, (u + i) % 60,
                                       tzinfo=dt.timezone.utc)))
    storage.get_events().insert_batch(events, app_id)
    engine = RecommendationEngine()
    ep = EngineParams(
        data_source_params=DataSourceParams(appName="QuantApp"),
        algorithm_params_list=(
            ("als", ALSAlgorithmParams(rank=10, numIterations=12,
                                       lambda_=0.03, seed=5)),))
    run_train(WorkflowContext(storage=storage), engine, ep,
              engine_factory="quant-test",
              params_json={
                  "datasource": {"params": {"appName": "QuantApp"}},
                  "algorithms": [{"name": "als", "params": {
                      "rank": 10, "numIterations": 12,
                      "lambda": 0.03, "seed": 5}}]})
    return storage, engine


@pytest.fixture(scope="module")
def trained():
    return _ladder_storage()


def _trained_factors(storage):
    instance = storage.get_meta_data_engine_instances() \
        .get_latest_completed("default", "NOT_USED", "default")
    blob = storage.get_model_data_models().get(instance.id)
    m = model_io.deserialize_models(blob.models)[0]
    return np.asarray(m.user_factors), np.asarray(m.item_factors)


def test_trained_model_ranking_parity_contract(trained):
    """THE contract: recall@k >= 0.99 and exact-match@1 >= 0.999 vs the
    fp32 path on a trained model."""
    storage, _engine = trained
    U, V = _trained_factors(storage)
    qf = quant.QuantizedFactors.from_factors(U, V)
    parity = quant.ranking_parity(U, V, qf, k=10)
    assert parity["recall"] >= 0.99, parity
    assert parity["exact1"] >= 0.999, parity
    # and the deploy gate accepts it in auto mode
    with quant.deploy_scope("auto"):
        assert quant.accept_parity(parity)


def _post(api, user, num=10):
    status, body = api.handle(
        "POST", "/queries.json",
        body=json.dumps({"user": user, "num": num}).encode())
    assert status == 200, body
    return json.dumps(body, sort_keys=True)


def _items(payload: str):
    return [s["item"] for s in json.loads(payload).get("itemScores", [])]


def test_quant_server_ranking_parity_at_the_wire(trained, monkeypatch):
    """Two live servers over the SAME trained model — fp32 vs int8 —
    compared at the wire: recall@10 >= 0.99, exact-match@1 >= 0.999."""
    monkeypatch.setenv("PIO_SERVE_DEVICE_MS", "1e9")
    storage, engine = trained
    queries = [(f"u{u}", 10) for u in range(60)]

    api_fp = QueryAPI(storage=storage, engine=engine,
                      config=ServerConfig(batching="on",
                                          serve_quant="off"))
    try:
        fp = [_post(api_fp, u, n) for u, n in queries]
    finally:
        api_fp.close()
    api_q = QueryAPI(storage=storage, engine=engine,
                     config=ServerConfig(batching="on",
                                         serve_quant="on"))
    try:
        qn = [_post(api_q, u, n) for u, n in queries]
        status = api_q.handle("GET", "/")[1]
    finally:
        api_q.close()
    recalls, top1 = [], []
    for a, b in zip(fp, qn):
        ia, ib = _items(a), _items(b)
        recalls.append(len(set(ia) & set(ib)) / max(len(ia), 1))
        top1.append(1.0 if ia[0] == ib[0] else 0.0)
    assert np.mean(recalls) >= 0.99, np.mean(recalls)
    assert np.mean(top1) >= 0.999, np.mean(top1)
    # the deploy recorded its own probe on the quant surface
    q = status["quant"]
    assert q["enabled"] and q["dtype"] == "int8"
    assert q["recall"] >= 0.99 and q["exact1"] >= 0.999


# ---------------------------------------------------------------------------
# deployed server: wire parity off, surfaces, sharding composition, AOT
# ---------------------------------------------------------------------------

def test_quant_off_wire_byte_identical(trained, monkeypatch):
    """PIO_SERVE_QUANT=off (and the auto default on CPU) answers
    byte-for-byte what a pre-quant server answers — replicated AND
    sharded — and keeps the legacy GET / key set."""
    monkeypatch.setenv("PIO_SERVE_DEVICE_MS", "1e9")
    storage, engine = trained
    queries = [("u1", 5), ("u3", 9), ("nobody", 5), ("u7", 1)]
    for shard in ("off", "on"):
        api_off = QueryAPI(storage=storage, engine=engine,
                           config=ServerConfig(batching="on",
                                               shard_serving=shard,
                                               serve_quant="off"))
        try:
            off_answers = [_post(api_off, u, n) for u, n in queries]
            assert "quant" not in api_off.handle("GET", "/")[1]
        finally:
            api_off.close()
        api_default = QueryAPI(storage=storage, engine=engine,
                               config=ServerConfig(batching="on",
                                                   shard_serving=shard))
        try:
            assert [_post(api_default, u, n)
                    for u, n in queries] == off_answers
            assert "quant" not in api_default.handle("GET", "/")[1]
        finally:
            api_default.close()


def test_quant_sharded_server_matches_quant_replicated(trained,
                                                       monkeypatch):
    """quant x sharding compose, and because the int8 kernels are
    exact, the two layouts answer byte-identically at the wire."""
    monkeypatch.setenv("PIO_SERVE_DEVICE_MS", "1e9")
    storage, engine = trained
    queries = [("u1", 5), ("u3", 10), ("nobody", 5), ("u7", 1)]
    api_rep = QueryAPI(storage=storage, engine=engine,
                       config=ServerConfig(batching="on",
                                           serve_quant="on"))
    try:
        rep = [_post(api_rep, u, n) for u, n in queries]
    finally:
        api_rep.close()
    api_sh = QueryAPI(storage=storage, engine=engine,
                      config=ServerConfig(batching="on",
                                          shard_serving="on",
                                          serve_quant="on"))
    try:
        sh = [_post(api_sh, u, n) for u, n in queries]
        status = api_sh.handle("GET", "/")[1]
        assert status["sharding"]["dtype"] == "int8"
        assert status["sharding"]["shards"] == 8
        q = status["quant"]
        assert q["enabled"] and q["sharded"] and q["dtype"] == "int8"
        model = api_sh.models[0]
        assert model.sharding is not None and model.sharding.dtype == "int8"
    finally:
        api_sh.close()
    assert rep == sh


def test_quant_gauges_recorded(trained, monkeypatch):
    monkeypatch.setenv("PIO_SERVE_DEVICE_MS", "1e9")
    storage, engine = trained
    api = QueryAPI(storage=storage, engine=engine,
                   config=ServerConfig(batching="on", serve_quant="on"))
    try:
        reg = telemetry.registry()
        assert reg.gauge("pio_serve_quant_mode", "x").labels().value == 1.0
        i8 = reg.gauge("pio_serve_factor_bytes", "x",
                       labelnames=("dtype",)).labels(dtype="int8").value
        f32 = reg.gauge("pio_serve_factor_bytes", "x",
                        labelnames=("dtype",)).labels(dtype="fp32").value
        assert 0 < i8 < f32
        rec = reg.gauge("pio_serve_quant_recall", "x",
                        labelnames=("metric",)).labels(
                            metric="recall").value
        assert rec >= 0.99
    finally:
        api.close()
    # a fresh fp32 deploy clears the mode gauge
    api2 = QueryAPI(storage=storage, engine=engine,
                    config=ServerConfig(batching="on", serve_quant="off"))
    try:
        assert telemetry.registry().gauge(
            "pio_serve_quant_mode", "x").labels().value == 0.0
    finally:
        api2.close()


def test_quant_programs_prebuilt_no_post_warmup_recompiles(
        trained, monkeypatch):
    """With quant on, every (bucket x k) program quant_program_specs
    enumerates is primed before ready: a post-AOT serving burst must
    compile NOTHING."""
    monkeypatch.setenv("PIO_SERVE_DEVICE_MS", "1e9")
    storage, engine = trained
    telemetry.set_enabled(True)
    devicewatch.install()
    devicewatch.reset_watchdog()
    api = QueryAPI(storage=storage, engine=engine,
                   config=ServerConfig(batching="on", serve_quant="on"))
    try:
        assert api.models[0].quant is not None
        assert devicewatch.serving_warmup_done()    # AOT marked it
        before = devicewatch.post_warmup_recompiles()
        for q in range(6):
            _post(api, f"u{q}", 10)
        assert devicewatch.post_warmup_recompiles() == before
    finally:
        api.close()
        devicewatch.reset_watchdog()


def test_auto_mode_falls_back_below_recall_floor(trained, monkeypatch):
    """auto + accelerator + a failing probe -> fp32 serving, an explicit
    fellBack record on GET /, and answers identical to serve_quant=off."""
    monkeypatch.setenv("PIO_SERVE_DEVICE_MS", "1e9")
    monkeypatch.setattr(quant, "_accelerator_platform", lambda: True)
    monkeypatch.setattr(
        quant, "ranking_parity",
        lambda *a, **k: {"k": 10, "sampledUsers": 4,
                         "recall": 0.5, "exact1": 0.5})
    storage, engine = trained
    api = QueryAPI(storage=storage, engine=engine,
                   config=ServerConfig(batching="on", serve_quant="auto"))
    try:
        status = api.handle("GET", "/")[1]
        assert status["quant"] == {"enabled": False, "fellBack": True}
        assert api.models[0].quant is None
        fell_back = _post(api, "u1", 5)
    finally:
        api.close()
    api_off = QueryAPI(storage=storage, engine=engine,
                       config=ServerConfig(batching="on",
                                           serve_quant="off"))
    try:
        assert _post(api_off, "u1", 5) == fell_back
    finally:
        api_off.close()


# ---------------------------------------------------------------------------
# doctor: the quant line + the hbm note
# ---------------------------------------------------------------------------

def _scrape_stub(metrics_text, device_body):
    blank = {"status": None, "body": ""}
    return {
        "url": "http://x", "healthz": {"status": 200, "body": "{}"},
        "readyz": {"status": 200, "body": '{"status": "ready"}'},
        "metrics": {"status": 200, "body": metrics_text},
        "traces": {"status": 200, "body": '{"spanCount": 0}'},
        "device": {"status": 200, "body": json.dumps(device_body)},
        "slow": dict(blank),
    }


def test_doctor_quant_line_states():
    from predictionio_tpu.tools import doctor

    dev = {"telemetry": True,
           "quant": {"enabled": True, "dtype": "int8",
                     "int8Bytes": 14 * 2**20, "fp32Bytes": 40 * 2**20,
                     "recall": 0.9975}}
    metrics = "pio_serve_quant_mode 1\n"
    checks = {c: (s, d) for c, s, d in
              doctor.diagnose(_scrape_stub(metrics, dev))}
    state, detail = checks["quant"]
    assert state == doctor.OK
    assert "int8" in detail and "0.35x" in detail
    assert "recall gate 0.9975" in detail
    # requested but fell back -> WARN naming the cost
    dev_fb = {"telemetry": True, "quant": {"enabled": False,
                                           "fellBack": True}}
    state, detail = {c: (s, d) for c, s, d in doctor.diagnose(
        _scrape_stub("", dev_fb))}["quant"]
    assert state == doctor.WARN and "fell back" in detail
    # fp32 daemon: quiet NA line
    state, detail = {c: (s, d) for c, s, d in doctor.diagnose(
        _scrape_stub("", {"telemetry": True}))}["quant"]
    assert state == doctor.NA and "fp32" in detail


def test_doctor_hbm_line_reflects_quant_footprint():
    from predictionio_tpu.tools import doctor

    dev = {"telemetry": True,
           "quant": {"enabled": True, "dtype": "int8",
                     "int8Bytes": 10 * 2**20, "fp32Bytes": 40 * 2**20}}
    metrics = ('pio_hbm_bytes_in_use{device="tpu:0"} 1073741824\n'
               'pio_hbm_bytes_limit{device="tpu:0"} 17179869184\n')
    checks = {c: (s, d) for c, s, d in
              doctor.diagnose(_scrape_stub(metrics, dev))}
    state, detail = checks["hbm"]
    assert state == doctor.OK
    assert "int8 factors save 30.0 MiB" in detail


# ---------------------------------------------------------------------------
# persistence + footprint accounting (workflow/model_io.py)
# ---------------------------------------------------------------------------

def test_quantized_factors_survive_model_io_roundtrip():
    U, V = _factors(n_users=6, n_items=9, rank=4, seed=4)
    qf = quant.QuantizedFactors.from_factors(U, V)
    qf.recall = 1.0
    blob = model_io.serialize_models([qf])
    back = model_io.deserialize_models(blob)[0]
    assert back.u_q.dtype == np.int8
    np.testing.assert_array_equal(back.u_q, qf.u_q)
    np.testing.assert_array_equal(back.v_scale, qf.v_scale)
    assert back.recall == 1.0


def test_factor_bytes_by_dtype_accounting():
    U, V = _factors(n_users=6, n_items=9, rank=4, seed=4)
    qf = quant.QuantizedFactors.from_factors(U, V)
    by = model_io.factor_bytes_by_dtype(qf)
    assert by["int8"] == (6 + 9) * 4          # the two int8 matrices
    assert by["float32"] == (6 + 9) * 4       # the two scale vectors
    assert model_io.factor_bytes_by_dtype({"U": U, "V": V}) == {
        "float32": (6 + 9) * 4 * 4}


# ---------------------------------------------------------------------------
# the quantized HBM ceiling (on the 8-device tier-1 mesh)
# ---------------------------------------------------------------------------

def _factors_past_sharded_budget(budget: int, n_dev: int, rank: int = 64):
    """A catalog at ~3.5x what ``n_dev`` fp32 shards of ``budget`` bytes
    hold (the ideal int8 gain is 4x; the fp32 per-row scale vectors trim
    it to (4r)/(r+4) = 3.76x at rank 64): each fp32 shard lands ~3.5x
    past the budget while the int8 shards fit at ~0.93x of it."""
    n_items = int(budget * 3.5) * n_dev // (rank * 4)
    rng = np.random.default_rng(0)
    U = rng.standard_normal((1024, rank), dtype=np.float32)
    V = rng.standard_normal((n_items, rank), dtype=np.float32)
    return U, V


def test_quant_hbm_ceiling_serves_past_fp32_sharded_budget():
    """Even the SHARDED fp32 layout busts the per-device (demonstration)
    budget; the int8 shards fit, and the quantized sharded top-k
    actually answers."""
    budget, rank = 2**20, 64
    n_dev = len(jax.devices())
    assert n_dev == 8
    U, V = _factors_past_sharded_budget(budget, n_dev, rank)
    n_items = V.shape[0]
    assert -(-n_items // n_dev) * rank * 4 > budget   # fp32 shard: no
    assert n_items / (budget * n_dev // (rank * 4)) >= 3.0
    qf = quant.QuantizedFactors.from_factors(U, V)
    sharded = serve_dist.shard_factors(U, V, quant=qf)
    assert sharded.per_shard_bytes() <= budget        # int8 shard: yes
    vals, idx = jax.device_get(
        sharded.topk(np.arange(8, dtype=np.int32), 10))
    assert np.isfinite(vals).all()
    assert (idx >= 0).all() and (idx < n_items).all()


# ---------------------------------------------------------------------------
# tier-1 Pallas coverage: the ALS solver's interpret path (satellite —
# until now its only coverage rode inside test_als.py's solver A/B)
# ---------------------------------------------------------------------------

def test_solve_pallas_interpret_matches_solve_factors():
    from predictionio_tpu.ops import als
    from predictionio_tpu.ops.solve_pallas import solve_factors_pallas

    rng = np.random.default_rng(11)
    n, r = 70, 6
    G = rng.normal(size=(n, r, r)).astype(np.float32)
    A = np.einsum("nij,nkj->nik", G, G)       # PSD batch
    b = rng.normal(size=(n, r)).astype(np.float32)
    reg = np.full((n,), 0.05, dtype=np.float32)
    got = np.asarray(solve_factors_pallas(
        jax.numpy.asarray(A), jax.numpy.asarray(b),
        jax.numpy.asarray(reg), interpret=True))
    want = np.asarray(als.solve_factors(
        jax.numpy.asarray(A), jax.numpy.asarray(b),
        jax.numpy.asarray(reg)))
    # fp32 elimination-order differences between the in-VMEM kernel and
    # the XLA sweep leave ~1e-4 relative drift on marginally-conditioned
    # rows; the ALS A/B in test_als.py holds the tighter end-to-end bar
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)
