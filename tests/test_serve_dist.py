"""Sharded serving (parallel/serve_dist.py) on the 8-device virtual mesh.

The acceptance surface of ISSUE 8: sharded and replicated serving return
BIT-identical (values, indices) top-k — at 1 device and at 8 simulated
devices, including constructed score ties across shard boundaries — the
mode knob resolves config/env/auto correctly (auto falls back on /reload
hot-swap), the deployed server's wire bytes are unchanged by sharding,
and the sharded (bucket x k) programs are AOT-prebuilt so
post_warmup_recompiles stays 0 with sharding on.
"""

import datetime as dt
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from predictionio_tpu.common import devicewatch, telemetry
from predictionio_tpu.controller import EngineParams
from predictionio_tpu.data.datamap import DataMap
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage import App
from predictionio_tpu.models.recommendation import (
    ALSAlgorithmParams, DataSourceParams, RecommendationEngine,
)
from predictionio_tpu.ops import topk
from predictionio_tpu.parallel import serve_dist
from predictionio_tpu.workflow import WorkflowContext, run_train
from predictionio_tpu.workflow.create_server import QueryAPI, ServerConfig


@pytest.fixture(autouse=True)
def _clean():
    yield
    serve_dist.record_state(None)
    telemetry.set_enabled(None)


def _factors(n_users=13, n_items=45, rank=5, seed=0):
    rng = np.random.default_rng(seed)
    U = rng.normal(size=(n_users, rank)).astype(np.float32)
    V = rng.normal(size=(n_items, rank)).astype(np.float32)
    return U, V


def _replicated(U, V, ixs, k):
    return jax.device_get(topk.topk_for_users(
        jnp.asarray(U), jnp.asarray(V), np.asarray(ixs, np.int32), k=k))


def _assert_same_scores(sv, rv, err_msg=""):
    """The sharded-vs-replicated score contract (serve_dist docstring,
    "Parity"): the ranking — asserted next to every call of this — is
    identical, ties included; the float32 scores agree within
    SCORE_RTOL/SCORE_ATOL, since XLA may order a dot product's
    additions differently in the two programs."""
    np.testing.assert_allclose(sv, rv, rtol=serve_dist.SCORE_RTOL,
                               atol=serve_dist.SCORE_ATOL, err_msg=err_msg)


# ---------------------------------------------------------------------------
# kernel parity: the replicated path's ranking, scores within tolerance
# ---------------------------------------------------------------------------

def test_sharded_matches_replicated_bit_identical():
    """8 shards, n_items NOT divisible by the device count (padding rows
    on the last shard), k spanning below/at/above rows-per-shard."""
    U, V = _factors()
    sharded = serve_dist.shard_factors(U, V)
    assert sharded.n_shards == 8
    ixs = np.array([0, 5, 12, 0, 7], dtype=np.int32)
    for k in (1, 3, 6, 20, 45):     # rows_dev_i = 6: 20 and 45 exceed it
        sv, si = jax.device_get(sharded.topk(ixs, k))
        rv, ri = _replicated(U, V, ixs, k)
        _assert_same_scores(sv, rv, err_msg=f"k={k}")
        np.testing.assert_array_equal(si, ri, err_msg=f"k={k}")


def test_sharded_single_device_mesh_parity():
    U, V = _factors(seed=1)
    sharded = serve_dist.shard_factors(U, V, n_shards=1)
    assert sharded.n_shards == 1
    ixs = np.array([2, 2, 9], dtype=np.int32)
    sv, si = jax.device_get(sharded.topk(ixs, 7))
    rv, ri = _replicated(U, V, ixs, 7)
    _assert_same_scores(sv, rv)
    np.testing.assert_array_equal(si, ri)


def test_tie_across_shard_boundaries():
    """Duplicated item rows in different shards score identically; both
    paths must rank the clones lowest-global-index first."""
    U, V = _factors(n_items=40, seed=2)
    V[39] = V[3]      # last shard
    V[20] = V[3]      # middle shard
    sharded = serve_dist.shard_factors(U, V)
    ixs = np.arange(8, dtype=np.int32)
    sv, si = jax.device_get(sharded.topk(ixs, 40))
    rv, ri = _replicated(U, V, ixs, 40)
    _assert_same_scores(sv, rv)
    np.testing.assert_array_equal(si, ri)
    # the rule itself, not just parity: clone 3 outranks 20 outranks 39
    for row in si:
        pos = [int(np.flatnonzero(row == c)[0]) for c in (3, 20, 39)]
        assert pos == sorted(pos), pos


def test_all_equal_scores_rank_by_global_index():
    """Total tie (zero item factors): the top-k must be exactly the k
    lowest global indices on both paths — the strongest cross-shard
    tie-break case there is."""
    U, _ = _factors(seed=3)
    V = np.zeros((37, U.shape[1]), dtype=np.float32)
    sharded = serve_dist.shard_factors(U, V)
    ixs = np.array([1, 4], dtype=np.int32)
    sv, si = jax.device_get(sharded.topk(ixs, 9))
    rv, ri = _replicated(U, V, ixs, 9)
    np.testing.assert_array_equal(si, np.tile(np.arange(9), (2, 1)))
    np.testing.assert_array_equal(si, ri)
    _assert_same_scores(sv, rv)


def test_more_users_and_items_than_one_shard_row():
    """n_users < n_dev (some shards own no real user rows) still gathers
    correctly through the psum."""
    U, V = _factors(n_users=3, n_items=11, seed=4)
    sharded = serve_dist.shard_factors(U, V)
    ixs = np.array([0, 1, 2, 2], dtype=np.int32)
    sv, si = jax.device_get(sharded.topk(ixs, 11))
    rv, ri = _replicated(U, V, ixs, 11)
    _assert_same_scores(sv, rv)
    np.testing.assert_array_equal(si, ri)


# ---------------------------------------------------------------------------
# the shard's own selection: stable_topk's two stages on a long shard, one
# whole sort on a short one, the same candidates either way
# ---------------------------------------------------------------------------

K_SEL = 3                           # two stages from 2 (k + 1) = 8 chunks
_LONG = 2 * (K_SEL + 1) * topk.CHUNK
#: items over four shards -> the branch of chunk_plan a shard takes
SHARD_SHAPES = {
    "short": 4 * 300,                       # one whole sort a shard
    "chunked": 4 * _LONG,                   # 8 whole chunks, no ragged end
    "ragged": 4 * (_LONG + 37),             # 37 items past the last chunk
    "padded": 4 * (_LONG + 37) - 3,         # + 3 padding rows, last shard
}
TOP, OVERFLOW = 120, 4              # the tied level; the row of +-inf


def _levels(n_items, rows_dev):
    """(6, n_items) small integers of magnitude 2 and up, so that every
    score is exact in float32 and in int8 and equal levels are exact
    ties; the items whose scores are NaN for every user, a whole chunk
    of a shard among them; and per row the expected top-K_SEL (None:
    whatever the order gives). Rows: 0 ties inside one chunk, 1 across
    a chunk boundary, 2 across shard boundaries and in the last item of
    a shard (its ragged end), 3 all equal, 4 the row whose user scale
    overflows float32 (-inf under a positive level, +inf under a
    negative one), 5 random."""
    rng = np.random.default_rng(n_items)
    L = rng.integers(2, 100, size=(6, n_items)) * rng.choice(
        [-1, 1], size=(6, n_items))
    step = min(topk.CHUNK, rows_dev // 4)
    inside = [rows_dev + 3, rows_dev + 4, rows_dev + 9, rows_dev + 11]
    chunks = [rows_dev + step - 1, rows_dev + step,
              rows_dev + 3 * step + 7, rows_dev + 3 * step + 8]
    shards = [rows_dev - 1, rows_dev, 3 * rows_dev - 1, 3 * rows_dev + 1]
    for row, at in enumerate((inside, chunks, shards)):
        L[row, at] = TOP
    L[3] = 7
    plus_inf = [rows_dev - 2, 2 * rows_dev + step + 5]
    L[OVERFLOW] = np.abs(L[OVERFLOW])
    L[OVERFLOW, plus_inf] = -2
    nan_items = np.r_[5, 6, rows_dev + 2,
                      2 * rows_dev:2 * rows_dev + step]
    expected = [inside[:3], chunks[:3], shards[:3], [0, 1, 2],
                plus_inf + [0], None]
    return L, nan_items, expected


def _lexsort_topk(scores, k):
    """First k of each row by (score descending, index ascending), NaN
    after everything: lax.sort's order on the negated scores."""
    ix = np.arange(scores.shape[1])
    return np.stack([np.lexsort((ix, -row))[:k] for row in scores])


@pytest.mark.parametrize("dtype", ["float32", "int8"])
@pytest.mark.parametrize("shape", list(SHARD_SHAPES))
def test_shard_selection_is_the_whole_shard_sorts(shape, dtype):
    """Four shards whose shape takes each branch of ops.topk.chunk_plan,
    fp32 and int8, at k = 3 and at a k past one shard's rows: the
    indices are a NumPy lexsort's over the padded address space (what
    the two-key sort of the whole shard returned: a padding row scores
    NEG_INF under a global id >= n_items) and the replicated kernel's;
    the int8 layouts agree to the bit. Only an answer that reaches
    below NEG_INF (the -inf and NaN scores of a poisoned model) can
    tell a padded layout from an unpadded one, as it always could."""
    from predictionio_tpu.ops import quant

    n_items = SHARD_SHAPES[shape]
    rows_dev = serve_dist._rows_dev(n_items, 4)
    assert (topk.chunk_plan(rows_dev, K_SEL) is None) == (shape == "short")
    assert (rows_dev % topk.CHUNK != 0) == (shape != "chunked")
    assert (4 * rows_dev > n_items) == (shape == "padded")
    L, nan_items, expected = _levels(n_items, rows_dev)
    rank = L.shape[0]
    u_scale = np.ones(rank, np.float32)
    u_scale[OVERFLOW] = -3e38           # times a level of 2 and up: -inf
    v_scale = np.ones(n_items, np.float32)
    v_scale[nan_items] = np.nan
    if dtype == "int8":
        qf = quant.QuantizedFactors(
            u_q=np.eye(rank, dtype=np.int8), u_scale=u_scale,
            v_q=L.T.astype(np.int8), v_scale=v_scale)
        sharded = serve_dist.shard_factors(None, None, n_shards=4, quant=qf)
        replicated = quant.QuantizedServing.build(qf)
        unpadded = (4 * rows_dev == n_items
                    and int(replicated.vt_q.shape[1]) == n_items)
    else:
        U = np.diag(u_scale)
        V = L.T.astype(np.float32) * v_scale[:, None]
        sharded = serve_dist.shard_factors(U, V, n_shards=4)
        unpadded = 4 * rows_dev == n_items
    assert sharded.n_shards == 4 and sharded.rows_dev_i == rows_dev
    with np.errstate(over="ignore"):
        S = L.astype(np.float32) * u_scale[:, None] * v_scale[None, :]
    assert np.isinf(S[OVERFLOW]).sum() == n_items - nan_items.size
    S_pad = np.full((rank, 4 * rows_dev), topk.NEG_INF, np.float32)
    S_pad[:, :n_items] = S
    ixs = np.arange(rank, dtype=np.int32)
    for k in (K_SEL, rows_dev + 2):
        sv, si = jax.device_get(sharded.topk(ixs, k))
        np.testing.assert_array_equal(si, _lexsort_topk(S_pad, k))
        np.testing.assert_array_equal(
            np.take_along_axis(S_pad, si, axis=1), sv)
        if k == K_SEL:
            for row, want in enumerate(expected):
                if want is not None and (unpadded or row != OVERFLOW):
                    assert list(si[row]) == want, (row, si[row])
        if dtype == "int8":
            rv, ri = jax.device_get(replicated.topk(ixs, k))
        else:
            rv, ri = _replicated(U, V, ixs, k)
        # a NaN is not above NEG_INF either
        rows = (slice(None) if unpadded
                else (sv > topk.NEG_INF).all(axis=1))
        np.testing.assert_array_equal(si[rows], ri[rows])
        if dtype == "int8":
            np.testing.assert_array_equal(sv[rows], rv[rows])
        else:
            _assert_same_scores(sv[rows], rv[rows])


@pytest.mark.parametrize("b", [1, 4, 16, 64])
def test_chunk_fetch_kernel_copies_what_the_gather_copies(b):
    """serve_dist._fetch_chunks (what a mesh of TPUs hands stable_topk
    for its `merge` stage), in Pallas's interpreter at every serving
    bucket: the chunks' own bits, NaN and -inf among them, on rows
    with a ragged end; and stable_topk through it is stable_topk."""
    n, k, L = 9 * topk.CHUNK + 37, K_SEL, topk.CHUNK
    rng = np.random.default_rng(b)
    rows = rng.integers(-5, 5, size=(b, n)).astype(np.float32)
    rows[0, 5], rows[-1, 700], rows[b // 2, n - 40] = np.nan, -np.inf, 9.0
    picked = rng.integers(0, n // L, size=(b, k)).astype(np.int32)
    picked[0, 0], picked[-1, 1] = 0, n // L - 1
    want = np.stack([[rows[r, c * L:(c + 1) * L] for c in picked[r]]
                     for r in range(b)])
    fetch = lambda r, p, l: serve_dist._fetch_chunks(      # noqa: E731
        r, p, l, interpret=True)
    got = fetch(jnp.asarray(rows), jnp.asarray(picked), L)
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  want.view(np.uint32))
    assert topk.chunk_plan(n, k) == (L, 9)
    for a, w in zip(topk.stable_topk(jnp.asarray(rows), k, fetch=fetch),
                    topk.stable_topk(jnp.asarray(rows), k)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(w))


# ---------------------------------------------------------------------------
# mode resolution
# ---------------------------------------------------------------------------

def test_mode_resolution(monkeypatch):
    monkeypatch.delenv("PIO_SERVE_SHARD", raising=False)
    # bare defaults: auto + virtual CPU devices -> replicated
    assert serve_dist.configured_mode() == "auto"
    assert not serve_dist.serving_enabled()
    with serve_dist.deploy_scope("on"):
        assert serve_dist.serving_enabled()
    with serve_dist.deploy_scope("off"):
        assert not serve_dist.serving_enabled()
    # env wins over the config scope (the PIO_AOT override shape)
    monkeypatch.setenv("PIO_SERVE_SHARD", "0")
    with serve_dist.deploy_scope("on"):
        assert not serve_dist.serving_enabled()
    monkeypatch.setenv("PIO_SERVE_SHARD", "1")
    with serve_dist.deploy_scope("off"):
        assert serve_dist.serving_enabled()


def test_auto_falls_back_on_reload_and_cpu(monkeypatch):
    monkeypatch.delenv("PIO_SERVE_SHARD", raising=False)
    # auto on a "real" multi-device mesh: sharded...
    monkeypatch.setattr(serve_dist, "_multi_device_platform", lambda: True)
    with serve_dist.deploy_scope("auto"):
        assert serve_dist.serving_enabled()
    # ...but not during a /reload hot-swap
    with serve_dist.deploy_scope("auto", reload=True):
        assert not serve_dist.serving_enabled()
    # "on" stays sharded even across a reload (explicit operator call)
    with serve_dist.deploy_scope("on", reload=True):
        assert serve_dist.serving_enabled()
    # virtual CPU devices: auto stays replicated
    monkeypatch.setattr(serve_dist, "_multi_device_platform",
                        lambda: False)
    with serve_dist.deploy_scope("auto"):
        assert not serve_dist.serving_enabled()


def test_bad_mode_rejected():
    with pytest.raises(ValueError):
        with serve_dist.deploy_scope("sideways"):
            pass
    with pytest.raises(ValueError):
        serve_dist.configured_mode("sideways")


# ---------------------------------------------------------------------------
# deployed server: wire parity, status surface, AOT coverage
# ---------------------------------------------------------------------------

def _train_engine(storage, n_items=9, rank=3):
    app_id = storage.get_meta_data_apps().insert(App(0, "ShardApp"))
    storage.get_events().init(app_id)
    events = []
    for u in range(8):
        for i in range(n_items):
            events.append(Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{i}",
                properties=DataMap(
                    {"rating": 5.0 if (u % 3) == (i % 3) else 1.5}),
                event_time=dt.datetime(2021, 2, 3, 0, (u + i) % 60,
                                       tzinfo=dt.timezone.utc)))
    storage.get_events().insert_batch(events, app_id)
    engine = RecommendationEngine()
    ep = EngineParams(
        data_source_params=DataSourceParams(appName="ShardApp"),
        algorithm_params_list=(
            ("als", ALSAlgorithmParams(rank=rank, numIterations=2,
                                       lambda_=0.05, seed=5)),))
    run_train(WorkflowContext(storage=storage), engine, ep,
              engine_factory="shard-test",
              params_json={
                  "datasource": {"params": {"appName": "ShardApp"}},
                  "algorithms": [{"name": "als", "params": {
                      "rank": rank, "numIterations": 2,
                      "lambda": 0.05, "seed": 5}}]})
    return engine


def _post(api, user, num=5):
    status, body = api.handle(
        "POST", "/queries.json",
        body=json.dumps({"user": user, "num": num}).encode())
    assert status == 200, body
    return json.dumps(body, sort_keys=True)


def test_query_api_sharded_wire_parity(memory_storage, monkeypatch):
    """A sharded deploy answers byte-for-byte what the replicated deploy
    answers, exposes its layout on GET / + the gauge, and keeps the
    legacy key set when replicated."""
    # pin the replicated leg to the device path: the parity contract is
    # sharded-vs-replicated DEVICE kernels (host BLAS accumulates in a
    # different order) and the probe must not flip it on a slow CI host
    monkeypatch.setenv("PIO_SERVE_DEVICE_MS", "1e9")
    engine = _train_engine(memory_storage)
    queries = [("u1", 5), ("u3", 9), ("nobody", 5), ("u7", 1)]

    api_off = QueryAPI(storage=memory_storage, engine=engine,
                       config=ServerConfig(batching="on",
                                           shard_serving="off"))
    try:
        off_answers = [_post(api_off, u, n) for u, n in queries]
        off_status = api_off.handle("GET", "/")[1]
        assert "sharding" not in off_status     # legacy key set intact
    finally:
        api_off.close()

    api_on = QueryAPI(storage=memory_storage, engine=engine,
                      config=ServerConfig(batching="on",
                                          shard_serving="on"))
    try:
        on_answers = [_post(api_on, u, n) for u, n in queries]
        on_status = api_on.handle("GET", "/")[1]
        sh = on_status["sharding"]
        assert sh["enabled"] and sh["shards"] == 8
        assert sh["merge"] == serve_dist.MERGE_STRATEGY
        gauge = telemetry.registry().gauge(
            "pio_serve_shards", "x").labels()
        assert gauge.value == 8.0
        model = api_on.models[0]
        assert model.sharding is not None
    finally:
        api_on.close()
    assert on_answers == off_answers


def test_reload_falls_back_to_replicated_on_auto(memory_storage,
                                                 monkeypatch):
    monkeypatch.delenv("PIO_SERVE_SHARD", raising=False)
    monkeypatch.setenv("PIO_SERVE_DEVICE_MS", "1e9")
    monkeypatch.setattr(serve_dist, "_multi_device_platform",
                        lambda: True)
    engine = _train_engine(memory_storage, n_items=8)
    api = QueryAPI(storage=memory_storage, engine=engine,
                   config=ServerConfig(batching="on",
                                       shard_serving="auto"))
    try:
        assert api.handle("GET", "/")[1]["sharding"]["shards"] == 8
        before = _post(api, "u2", 4)
        api._reload()                       # hot-swap: auto -> replicated
        assert "sharding" not in api.handle("GET", "/")[1]
        assert getattr(api.models[0], "sharding", None) is None
        assert _post(api, "u2", 4) == before
        # the gauge reflects the fallback
        assert telemetry.registry().gauge(
            "pio_serve_shards", "x").labels().value == 0.0
    finally:
        api.close()


def test_sharded_programs_prebuilt_no_post_warmup_recompiles(
        memory_storage):
    """With sharding on, every (bucket x k) sharded program is primed
    before ready: a post-AOT serving burst must compile NOTHING."""
    telemetry.set_enabled(True)
    devicewatch.install()
    devicewatch.reset_watchdog()
    engine = _train_engine(memory_storage, n_items=10, rank=4)
    api = QueryAPI(storage=memory_storage, engine=engine,
                   config=ServerConfig(batching="on",
                                       shard_serving="on"))
    try:
        assert devicewatch.serving_warmup_done()    # AOT marked it
        before = devicewatch.post_warmup_recompiles()
        for q in range(6):
            _post(api, f"u{q}", 10)                 # k=10 clamps to 10
        assert devicewatch.post_warmup_recompiles() == before
    finally:
        api.close()
        devicewatch.reset_watchdog()


def test_sharded_program_specs_cover_inline_bucket():
    U, V = _factors(seed=6)
    sharded = serve_dist.shard_factors(U, V)
    specs = serve_dist.sharded_program_specs(sharded, (4, 16), (10,))
    buckets = sorted({s.key[-2] for s in specs})
    assert buckets == [1, 4, 16]      # bucket 1 forced in for inline
    assert all(s.name == "topk_for_users_sharded" for s in specs)
    # a spec is genuinely AOT-compilable from declared (sharded) shapes
    specs[0].build()


def _factors_past_budget(budget: int, rank: int = 64):
    """An item matrix ~1.2x one device's ``budget`` bytes (replicated
    placement needs all of it on every device), a small user matrix."""
    n_items = int(budget * 1.2) // (rank * 4)
    rng = np.random.default_rng(0)
    U = rng.standard_normal((1024, rank), dtype=np.float32)
    V = rng.standard_normal((n_items, rank), dtype=np.float32)
    return U, V


def test_hbm_ceiling_demo_shards_past_one_device_budget():
    """The HBM ceiling on the 8-device mesh: a factor matrix sized past
    one device's (demonstration) budget serves only sharded — replicated
    placement exceeds the budget, each shard fits, and the sharded top-k
    actually answers."""
    budget = 2**20
    assert len(jax.devices()) == 8
    U, V = _factors_past_budget(budget)
    factor_bytes = (U.shape[0] + V.shape[0]) * U.shape[1] * 4
    assert factor_bytes > budget            # replicated does not fit
    sharded = serve_dist.shard_factors(U, V)
    per_shard = sharded.per_shard_bytes()
    assert per_shard <= budget
    assert per_shard < factor_bytes // 4
    vals, idx = jax.device_get(
        sharded.topk(np.arange(8, dtype=np.int32), 10))
    assert np.isfinite(vals).all()
    assert (idx >= 0).all() and (idx < V.shape[0]).all()


# ---------------------------------------------------------------------------
# doctor: the sharding line
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


def test_doctor_sharding_line_states():
    from predictionio_tpu.tools import doctor

    dev = {"telemetry": True,
           "sharding": {"shards": 8, "merge": "all_gather",
                        "perShardFactorBytes": 2 * 2**20}}
    # healthy headroom on every device
    metrics = ("pio_serve_shards 8\n"
               'pio_hbm_bytes_in_use{device="tpu:0"} 100\n'
               'pio_hbm_bytes_limit{device="tpu:0"} 1000\n'
               'pio_hbm_bytes_in_use{device="tpu:1"} 300\n'
               'pio_hbm_bytes_limit{device="tpu:1"} 1000\n')
    checks = {c: (s, d) for c, s, d in
              doctor.diagnose(_scrape_stub(metrics, dev))}
    state, detail = checks["sharding"]
    assert state == doctor.OK
    assert "8 shard(s), all_gather merge" in detail
    assert "headroom 70%" in detail
    # one shard within 10% of HBM -> WARN names the fix
    metrics_hot = metrics.replace(
        'pio_hbm_bytes_in_use{device="tpu:1"} 300',
        'pio_hbm_bytes_in_use{device="tpu:1"} 950')
    state, detail = {c: (s, d) for c, s, d in doctor.diagnose(
        _scrape_stub(metrics_hot, dev))}["sharding"]
    assert state == doctor.WARN and "within 10%" in detail
    # replicated daemon: informational NA-ish OK line, never noisy
    state, detail = {c: (s, d) for c, s, d in doctor.diagnose(
        _scrape_stub("", {"telemetry": True}))}["sharding"]
    assert state == doctor.NA and "replicated" in detail


# ---------------------------------------------------------------------------
# the benchmark's four-chip deployment (rec-als-amazon14-r128) at a small
# size: four shards, the benchmark's own reference and comparison
# ---------------------------------------------------------------------------

def _benchmark_path(*parts):
    import os
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", *parts)


def _benchmark_module(name, *sub):
    """A module of benchmark/ by path, so tests/ keeps its sys.path."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        f"_bench_{name}", _benchmark_path(*sub, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _amazon14_config():
    with open(_benchmark_path("configs",
                              "rec-als-amazon14-r128.json")) as f:
        return json.load(f)


@pytest.fixture()
def four_devices(monkeypatch):
    """The deployment's host: `--shard-serving on` shards over every
    visible device, so the harness's eight are cut to four."""
    devs = jax.devices()[:4]
    assert len(devs) == 4
    monkeypatch.setattr(jax, "devices", lambda *a, **kw: devs)
    return devs


def _deploy(storage, U, V, **config):
    """A completed instance holding an ALSModel of these factors, made
    as benchmark/child_serve.py makes its own, behind a QueryAPI."""
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.data.storage import EngineInstance, Model
    from predictionio_tpu.models.recommendation.als_algorithm import ALSModel
    from predictionio_tpu.workflow import model_io

    algos = [{"name": "als", "params": {
        "rank": int(U.shape[1]), "numIterations": 1, "lambda": 0.01,
        "seed": 3}}]
    now = dt.datetime.now(dt.timezone.utc)
    instance_id = storage.get_meta_data_engine_instances().insert(
        EngineInstance(
            id="", status="COMPLETED", start_time=now, end_time=now,
            engine_id="default", engine_version="NOT_USED",
            engine_variant="default", engine_factory="shard-test",
            data_source_params=json.dumps({"params": {"appName": "x"}}),
            preparator_params="{}", algorithms_params=json.dumps(algos),
            serving_params="{}"))
    model = ALSModel(
        rank=int(U.shape[1]), user_factors=U, item_factors=V,
        user_vocab=BiMap({f"u{k}": k for k in range(U.shape[0])}),
        item_vocab=BiMap({f"i{k}": k for k in range(V.shape[0])}))
    storage.get_model_data_models().insert(Model(
        id=instance_id, models=model_io.serialize_models([model])))
    return QueryAPI(storage=storage, engine=RecommendationEngine(),
                    config=ServerConfig(batching="on", **config))


def _spectrum_factors(n_users, n_items, rank, seed):
    """The benchmark's factors at a small size (gen_factors: column d
    scaled by (d+1)^-0.5), with clones of one item in the first, a
    middle and the last shard so that every user's scores hold ties."""
    gen = _benchmark_module("gen_factors")
    U = gen.matrix(seed, "user", n_users, rank, 0.5)
    V = gen.matrix(seed, "item", n_items, rank, 0.5)
    V[n_items // 2] = V[2]
    V[n_items - 1] = V[2]
    return U, V


@pytest.mark.parametrize("n_users,n_items", [(203, 1_031), (17, 94)])
def test_sharded4_replies_equal_the_benchmarks_reference(
        memory_storage, four_devices, n_users, n_items):
    """Through QueryAPI with `--shard-serving on` over four devices, for
    row counts that do not divide by 4 (the last shard is padded): the
    replies are benchmark/reference/topk_reference.py's top-10 — same
    items in the same order, ties by lowest index — and the benchmark's
    own comparison reads them inside the new configuration's limits.
    The users asked include the first and the last of the last shard."""
    compare = _benchmark_module("compare")
    reference = _benchmark_module("topk_reference", "reference")
    config = _amazon14_config()
    k = config["query"]["num"]
    U, V = _spectrum_factors(n_users, n_items, 16, seed=2**31 + 7)
    rows_dev_u = -(-n_users // 4)
    users = [0, 1, rows_dev_u - 1, rows_dev_u, 3 * rows_dev_u,
             n_users - 2, n_users - 1]
    api = _deploy(memory_storage, U, V, shard_serving="on")
    try:
        sh = api.models[0].sharding
        assert sh.n_shards == 4
        assert sh.rows_dev_u * 4 > n_users and sh.rows_dev_i * 4 > n_items
        replies = []
        for u in users:
            body = json.loads(_post(api, f"u{u}", k))
            replies.append((u, [(int(s["item"][1:]), s["score"])
                                for s in body["itemScores"]]))
    finally:
        api.close()
    ref = reference.scores(U[users], reference.prepare(V))
    lookup = dict(zip(users, ref))
    for (u, items), row in zip(replies, ref):
        assert [i for i, _ in items] == list(reference.topk(row, k)), u
    numbers = compare.topk_numbers(replies, lookup.__getitem__, k)
    ok, compared = compare.judge(
        {**numbers, "compiles_in_window": 0.0}, config["limits"])
    assert ok, compared
    # the clones tie exactly, so the order among them is the rule's
    clones = [2, n_items // 2, n_items - 1]
    full = reference.topk(ref[0], n_items)
    pos = [int(np.flatnonzero(full == c)[0]) for c in clones]
    assert pos == sorted(pos) and pos[2] - pos[0] == 2


@pytest.mark.parametrize("n_items", [1_031, 94])
def test_the_four_shares_merged_are_the_unsharded_answer(n_items):
    """Each shard's own candidate list — the program run on that
    shard's item rows alone — with the shard's base offset added, merged
    by merge_candidates, is the reference's answer over the whole
    catalog: the shares add up to the whole, ties included."""
    reference = _benchmark_module("topk_reference", "reference")
    k, n_users = 10, 29
    U, V = _spectrum_factors(n_users, n_items, 16, seed=5)
    ixs = np.asarray([0, 7, n_users - 1], dtype=np.int32)
    rows_dev_i = serve_dist._rows_dev(n_items, 4)
    vals, gids = [], []
    for d in range(4):
        lo, hi = d * rows_dev_i, min((d + 1) * rows_dev_i, n_items)
        share = serve_dist.shard_factors(U, V[lo:hi], n_shards=1)
        v, i = jax.device_get(share.topk(ixs, k))
        vals.append(v)
        gids.append(i + lo)
    vals, gids = np.concatenate(vals, 1), np.concatenate(gids, 1)
    whole = serve_dist.shard_factors(U, V, n_shards=4)
    wv, wi = jax.device_get(whole.topk(ixs, k))
    ref = reference.scores(U[ixs], reference.prepare(V))
    for r in range(len(ixs)):
        mv, mg, _ = serve_dist.merge_candidates(vals[r], gids[r], k)
        np.testing.assert_array_equal(mg, reference.topk(ref[r], k))
        np.testing.assert_array_equal(mg, wi[r])
        _assert_same_scores(mv, wv[r])


def test_model_bytes_of_a_sharded_model_are_one_devices(four_devices):
    """model_hbm_bytes and the sharding summary both state what ONE
    device holds: rows_dev x rank x 4 bytes a matrix, padding rows
    included — not the whole model."""
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.models.recommendation.als_algorithm import ALSModel
    from predictionio_tpu.serving.registry import model_hbm_bytes

    n_users, n_items, rank = 203, 1_031, 16
    U, V = _spectrum_factors(n_users, n_items, rank, seed=9)
    sharded = serve_dist.shard_factors(U, V)
    assert (sharded.rows_dev_u, sharded.rows_dev_i) == (51, 258)
    per_device = (51 + 258) * rank * 4
    assert sharded.summary()["perShardFactorBytes"] == per_device
    telemetry.set_enabled(True)     # /debug/device.json's payload
    assert devicewatch.debug_snapshot()["sharding"][
        "perShardFactorBytes"] == per_device
    model = ALSModel(rank=rank, user_factors=sharded.user_shards,
                     item_factors=sharded.item_shards,
                     user_vocab=BiMap({}), item_vocab=BiMap({}),
                     sharding=sharded)
    assert model_hbm_bytes([model]) == per_device
    # a replicated device array counts whole
    import types
    replicated = types.SimpleNamespace(item_factors=jnp.asarray(V))
    assert model_hbm_bytes([replicated]) == V.nbytes
    for shard in sharded.item_shards.addressable_shards:
        assert shard.data.nbytes == 258 * rank * 4


@pytest.mark.parametrize("mode,layout,shards,n_items,selection", [
    ("on", "row-sharded", 4, 1_031, "sort"),
    # 22 = 2 (10 + 1) whole chunks a shard, and a ragged end
    ("on", "row-sharded", 4, 4 * 22 * topk.CHUNK + 6, "chunked L=512 C=22"),
    ("off", "replicated", 1, 1_031, "sort")],
    ids=["sharded-short", "sharded-long", "replicated"])
def test_status_batching_block_names_the_layout(
        memory_storage, four_devices, monkeypatch, mode, layout, shards,
        n_items, selection):
    """... and the selection its programs were built with: on the
    row-sharded layout ops.topk.selection_name over ONE shard's rows,
    the static shape serve_dist hands stable_topk."""
    from predictionio_tpu.serving.registry import model_hbm_bytes

    monkeypatch.setenv("PIO_SERVE_DEVICE_MS", "1e9")   # stay on the device
    U, V = _spectrum_factors(203, n_items, 16, seed=11)
    api = _deploy(memory_storage, U, V, shard_serving=mode)
    try:
        b = api.handle("GET", "/")[1]["batching"]
        assert (b["layout"], b["shards"]) == (layout, shards)
        assert b["topkSelection"] == {"10": selection}
        if mode == "on":
            rows_dev_i = -(-n_items // 4)
            assert b["perShardBytes"] == (51 + rows_dev_i) * 16 * 4
            assert selection == topk.selection_name(rows_dev_i, 10)
        else:
            assert b["perShardBytes"] == U.nbytes + V.nbytes
        assert b["perShardBytes"] == model_hbm_bytes(api.models)
    finally:
        api.close()
