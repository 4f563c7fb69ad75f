"""The e-commerce engine's device layout, on the CPU at a small size.

ops/topk.py masked_topk_rows (gather > score > mask > exclude > select)
against the plain reference the benchmark's cell is held to
(benchmark/reference/ecomm_rules_reference.py, loaded by path: NumPy,
nothing of the program): the same items in the same order, scores to
float32 rounding. Then the engine through `pio deploy`'s QueryAPI: the
device layout answers as the host layout does, reply for reply; an
event and a new constraint written while the server is up are honoured
by the next query; nothing compiles after warm-up. And the in-memory
event store's index by entity against a scan.
"""

import contextlib
import datetime as dt
import importlib.util
import json
import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

import jax

from predictionio_tpu.common import devicewatch, telemetry
from predictionio_tpu.data import store
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.data.datamap import DataMap
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage import (App, EngineInstance, Model,
                                           reset_storage,
                                           use_memory_storage)
from predictionio_tpu.data.storage.base import event_matches
from predictionio_tpu.data.storage.memory import MemoryEvents
from predictionio_tpu.models.ecommerce import als_algorithm as ecomm
from predictionio_tpu.models.ecommerce.engine import Item, Query
from predictionio_tpu.models.similarproduct.als_algorithm import (
    build_category_masks, candidate_mask)
from predictionio_tpu.ops import topk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFERENCE_DIR = os.path.join(ROOT, "benchmark", "reference")


@pytest.fixture(scope="module")
def reference():
    """benchmark/reference/ecomm_rules_reference.py, as the benchmark's
    adapter imports it (its directory on the path for the sibling it
    reads), gone from sys.path and sys.modules afterwards."""
    before = set(sys.modules)
    sys.path.insert(0, REFERENCE_DIR)
    try:
        spec = importlib.util.spec_from_file_location(
            "_ecomm_rules_reference",
            os.path.join(REFERENCE_DIR, "ecomm_rules_reference.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        yield mod
    finally:
        sys.path.remove(REFERENCE_DIR)
        for name in set(sys.modules) - before:
            if (getattr(sys.modules[name], "__file__", "") or ""
                    ).startswith(REFERENCE_DIR):
                del sys.modules[name]


# ---------------------------------------------------------------------------
# the program against the reference
# ---------------------------------------------------------------------------

N_USERS, RANK, N_CATS, K = 40, 8, 5, 10
WIDEST = topk.EXCLUDE_WIDTHS[-1]


def _catalog(n_items, seed=7, tied=False):
    """Seeded factors, one category an item, 1 % of the items
    unavailable. `tied`: item factors on a coarse grid with every item
    repeated, so that equal scores are many."""
    rng = np.random.default_rng(seed)
    U = rng.standard_normal((N_USERS, RANK), dtype=np.float32)
    V = rng.standard_normal((n_items, RANK), dtype=np.float32)
    if tied:
        U, V = np.round(U), np.round(V[:n_items // 4])
        V = np.tile(V, (4, 1))[:n_items]
    cats = rng.integers(0, N_CATS, n_items)
    gone = rng.choice(n_items, max(1, n_items // 100), replace=False)
    return U, V, cats, gone


def _device_arguments(cats, gone, n_items):
    items = {i: Item(categories=(f"c{c}",)) for i, c in enumerate(cats)}
    bits, words = ecomm.category_words(items, n_items)
    eligible = np.ones(n_items, bool)
    eligible[gone] = False
    return bits, words, eligible


#: case -> (n_items, bucket, tied, what each row's rules are): a row is
#: (categories or None, exclusion list length, exclude every candidate)
CASES = {
    "ties": (1500, 4, True, [(None, 3, False), ((1,), 0, False),
                             ((0, 3), 40, False), (None, 0, False)]),
    "no_category_left": (1500, 4, False, [((), 0, False), (None, 5, False),
                                          ((2,), 1, False),
                                          ((), 17, False)]),
    "width_128": (1500, 4, False, [(None, 128, False), ((4,), 2, False),
                                   (None, 0, False), ((1, 2), 127, False)]),
    "width_widest": (6000, 4, False, [(None, WIDEST, False),
                                      ((0,), 9, False), (None, 129, False),
                                      ((3,), WIDEST - 1, False)]),
    "every_candidate_excluded": (1500, 4, False, [
        ((2,), 0, True), (None, 4, False), ((0,), 0, True),
        (None, 0, False)]),
    "num_above_the_candidates_left": (300, 4, False, [
        ((1,), 0, "but_three"), (None, 0, False), ((2,), 0, "but_three"),
        ((4,), 1, False)]),
    "bucket_1": (1500, 1, False, [((1, 4), 6, False)]),
    "bucket_64": (1500, 64, False, [
        ((None, (r % N_CATS,), (r % N_CATS, (r + 2) % N_CATS))[r % 3],
         (0, 3, 20, 128)[r % 4], False) for r in range(64)]),
    # 12,000 items >= 2 * (k + 1) * CHUNK: stable_topk's two stages
    "long_row_chunked": (12_000 + 37, 4, False, [
        (None, 70, False), ((1,), 4, False), ((0, 2), 0, False),
        (None, WIDEST, False)]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_masked_program_equals_the_reference(case, reference):
    n_items, bucket, tied, rules = CASES[case]
    U, V, cats, gone = _catalog(n_items, tied=tied)
    bits, words, eligible = _device_arguments(cats, gone, n_items)
    if case == "long_row_chunked":
        assert topk.chunk_plan(n_items, K) is not None
    rng = np.random.default_rng(11)
    users = rng.integers(0, N_USERS, bucket).astype(np.int32)
    longest = max(n for _c, n, _a in rules)
    width = topk.exclude_width(max(longest, 1))
    want = np.full((bucket, words.shape[0]), 0xFFFFFFFF, np.uint32)
    exclude = np.full((bucket, width), n_items, np.int32)
    masks = []
    for r, (categories, n_out, everything) in enumerate(rules):
        out = rng.choice(n_items, n_out, replace=False)
        mask = reference.candidates(n_items, out, gone, cats,
                                    categories, ())
        if everything:
            left = np.flatnonzero(mask)
            if everything == "but_three":
                left = left[:-3]
            # the candidates themselves are what the row excludes
            out = np.concatenate([out, left])
            mask[left] = False
            if len(out) > exclude.shape[1]:
                width = topk.exclude_width(len(out))
                exclude = np.concatenate([exclude, np.full(
                    (bucket, width - exclude.shape[1]), n_items,
                    np.int32)], axis=1)
        if categories is not None:
            want[r] = 0
            for c in categories:
                word, bit = bits[f"c{c}"]
                want[r, word] |= bit
        exclude[r, :len(out)] = out
        masks.append(mask)
    assert exclude.shape[1] in topk.EXCLUDE_WIDTHS
    vals, idx = jax.device_get(topk.masked_topk_rows(
        U, V, words, eligible, users, want, exclude, k=K))
    ref = reference.scores(U[users], reference.prepare(V))
    for r, mask in enumerate(masks):
        due = reference.recommend(ref[r], mask, K)
        kept = vals[r] > 0
        got = idx[r][kept]
        # rank bit for bit: the same items in the same order
        assert got.tolist() == due.tolist(), (case, r)
        np.testing.assert_allclose(vals[r][kept], ref[r][due], rtol=2e-6,
                                   atol=2e-6)
        if len(due) < K:
            # what is left of the row is nothing but ruled-out items
            assert (vals[r][~kept] == topk.NEG_INF).all() \
                or (vals[r][~kept] <= 0).all()
    if case == "ties":
        assert any(len(set(ref[r][reference.recommend(ref[r], m, K)]))
                   < K for r, m in enumerate(masks)), "no tie in the case"


def test_category_words_are_the_host_paths_category_masks():
    """One function builds the words, for engine and tests; what it
    encodes is similarproduct's build_category_masks / candidate_mask,
    the host path's."""
    rng = np.random.default_rng(3)
    n_items = 400
    names = [f"k{j}" for j in range(40)]        # two words of bits
    items = {i: Item(categories=tuple(
        rng.choice(names, rng.integers(0, 4), replace=False)))
        for i in range(n_items)}
    items[5] = Item(categories=None)
    masks = build_category_masks(items, n_items)
    bits, words = ecomm.category_words(items, n_items, masks)
    bits2, words2 = ecomm.category_words(items, n_items)
    assert bits == bits2 and (words == words2).all()
    assert words.shape == (2, n_items) and words.dtype == np.uint32
    assert (words[0] & topk.RULE_ANY_BIT).all()
    trained = np.ones(n_items, bool)
    for wanted in (None, ("k3",), ("k1", "k39", "nobody_has_this"), ()):
        host = candidate_mask(n_items, trained, masks, wanted, None,
                              set(), set())
        want = np.full(2, 0xFFFFFFFF, np.uint32)
        if wanted is not None:
            want[:] = 0
            for name in wanted:
                word, bit = bits.get(name, (0, 0))
                want[word] |= bit
        device = ((words & want[:, None]) != 0).any(axis=0)
        assert (device == host).all(), wanted


# ---------------------------------------------------------------------------
# the engine through QueryAPI: device layout against host layout
# ---------------------------------------------------------------------------

N_ITEMS, APP = 5000, "shop"
T0 = dt.datetime(2021, 1, 1, tzinfo=dt.timezone.utc)
#: users with a seen list at each declared width, one past the widest
LONG_LISTS = {0: 128, 1: 129, 2: WIDEST, 3: WIDEST + 40}


def _seen(u):
    rng = np.random.default_rng([5, u])
    n = LONG_LISTS.get(u, int(min(rng.zipf(1.6), 60)))
    return rng.choice(N_ITEMS, n, replace=False).tolist()


def _view(u, i, at):
    return Event(event=("view", "buy")[i % 2], entity_type="user",
                 entity_id=f"u{u}", target_entity_type="item",
                 target_entity_id=f"i{i}", event_time=at)


def _set_unavailable(items, at):
    return Event(event="$set", entity_type="constraint",
                 entity_id="unavailableItems",
                 properties=DataMap({"items": [f"i{i}" for i in items]}),
                 event_time=at)


@contextlib.contextmanager
def _deployed(device_ms):
    """`pio deploy`'s QueryAPI on a COMPLETED instance of a seeded
    ECommModel with its events in a memory store. `device_ms` is
    PIO_SERVE_DEVICE_MS: 1e9 keeps the device layout on the CPU backend,
    0 the host layout."""
    from predictionio_tpu.models.ecommerce import ECommerceEngine
    from predictionio_tpu.workflow import model_io
    from predictionio_tpu.workflow.create_server import (QueryAPI,
                                                         ServerConfig)

    mp = pytest.MonkeyPatch()
    mp.setenv("PIO_SERVE_DEVICE_MS", device_ms)
    telemetry.set_enabled(True)
    devicewatch.install()
    storage = use_memory_storage()
    U, V, cats, gone = _catalog(N_ITEMS, seed=21)
    items = {i: Item(categories=(f"c{c}",)) for i, c in enumerate(cats)}
    trained = np.ones(N_ITEMS, bool)
    trained[[7, 8]] = False
    model = ecomm.ECommModel(
        rank=RANK, user_features=U, product_features=V,
        user_vocab=BiMap({f"u{k}": k for k in range(N_USERS)}),
        item_vocab=BiMap({f"i{k}": k for k in range(N_ITEMS)}),
        items=items, user_trained=np.ones(N_USERS, bool),
        item_trained=trained,
        category_masks=build_category_masks(items, N_ITEMS),
        product_features_hat=V / np.linalg.norm(V, axis=1, keepdims=True))
    app_id = storage.get_meta_data_apps().insert(App(0, APP, None))
    storage.get_events().init(app_id)
    events = [_view(u, i, T0 + dt.timedelta(seconds=u))
              for u in range(N_USERS) for i in _seen(u)]
    # a visitor the model does not know, with recent views
    events += [_view(900, i, T0) for i in (11, 12, 13)]
    events.append(_set_unavailable(gone, T0 + dt.timedelta(days=1)))
    store.write(events, app_id, storage=storage)
    algorithms = [{"name": "ecomm", "params": {
        "appName": APP, "unseenOnly": True, "seenEvents": ["buy", "view"],
        "similarEvents": ["view"], "rank": RANK}}]
    now = dt.datetime.now(dt.timezone.utc)
    instance_id = storage.get_meta_data_engine_instances().insert(
        EngineInstance(
            id="", status="COMPLETED", start_time=now, end_time=now,
            engine_id="default", engine_version="NOT_USED",
            engine_variant="default",
            engine_factory="predictionio_tpu.models.ecommerce.engine:"
                           "ECommerceEngine",
            data_source_params=json.dumps({"params": {"appName": APP}}),
            preparator_params="{}",
            algorithms_params=json.dumps(algorithms),
            serving_params="{}"))
    storage.get_model_data_models().insert(Model(
        id=instance_id,
        models=model_io.serialize_models([model], check_finite=True)))
    api = QueryAPI(storage=storage, engine=ECommerceEngine(),
                   config=ServerConfig(batching="on"))
    try:
        yield api, storage, app_id, gone
    finally:
        api.close()
        reset_storage()
        telemetry.set_enabled(None)
        mp.undo()


def _mixed_queries(n, seed=13):
    """Known users (the long lists among them) with categories and
    black lists as the cell's adapter draws them, and what the device
    program has no argument for: a white list, an unknown visitor."""
    rng = np.random.default_rng(seed)
    out = []
    for j in range(n):
        query = {"user": f"u{int(rng.integers(0, N_USERS))}", "num": K}
        if j % 23 == 0:
            query["user"] = f"u{j // 23 % len(LONG_LISTS)}"
        if rng.random() < 0.5:
            query["categories"] = [f"c{c}" for c in rng.choice(
                N_CATS, int(rng.integers(1, 3)), replace=False)]
        if rng.random() < 1 / 3:
            query["blackList"] = [f"i{i}" for i in rng.choice(
                N_ITEMS, int(rng.integers(1, 6)), replace=False)]
        if j % 17 == 0:
            query["whiteList"] = [f"i{i}" for i in rng.choice(
                N_ITEMS, 300, replace=False)]
        if j % 29 == 0:
            query["user"] = "u900"
        if j % 31 == 0:
            query["categories"] = ["no_such_category"]
        out.append(query)
    return out


def _ask(api, queries, threads=16):
    def one(query):
        status, body = api.handle("POST", "/queries.json",
                                  body=json.dumps(query).encode())
        assert status == 200, body
        return [(s["item"], s["score"]) for s in body["itemScores"]]

    with ThreadPoolExecutor(threads) as pool:
        return list(pool.map(one, queries))


@pytest.fixture(scope="module")
def both_layouts():
    """200 mixed queries answered by the device layout (after a warm-up
    round, with the compile counter read round them) and by the host
    layout of the same deployment."""
    queries = _mixed_queries(200)
    out = {"queries": queries}
    with _deployed("1e9") as (api, _storage, _app, gone):
        out["gone"] = set(gone.tolist())
        _st, page = api.handle("GET", "/")
        out["page_before"] = page
        _ask(api, _mixed_queries(64, seed=1))        # warm-up
        _st, warm = api.handle("GET", "/")
        c0 = devicewatch.compiles_total()
        out["device"] = _ask(api, queries)
        out["compiles"] = (c0, devicewatch.compiles_total())
        _st, out["page_after"] = api.handle("GET", "/")
        out["page_warm"] = warm
        _st, out["metrics"], _h = api.handle("GET", "/metrics")
    with _deployed("0") as (api, _storage, _app, _gone):
        _st, out["host_page"] = api.handle("GET", "/")
        out["host"] = _ask(api, queries)
    return out


def test_device_layout_is_what_a_deploy_ends_on(both_layouts):
    """No flag: `GET /` names the layout, its widths, the selection and
    what the device holds; the host layout says so too."""
    b = both_layouts["page_before"]["batching"]
    assert b["layout"] == "replicated+rules" and b["shards"] == 1
    assert b["excludeWidths"] == list(topk.EXCLUDE_WIDTHS)
    factors = (N_USERS + N_ITEMS) * RANK * 4
    assert b["perShardBytes"] == factors + N_ITEMS * 4 + N_ITEMS
    assert b["topkSelection"] == {str(K): topk.selection_name(N_ITEMS, K)}
    # a program a (bucket, width): the deploy's buckets (pruned by what
    # this process has seen flushed) and bucket 1, always
    aot = both_layouts["page_before"]["aot"]
    assert aot["programs"] == len({1, *aot["buckets"]}) * len(
        topk.EXCLUDE_WIDTHS) and aot["failed"] == 0
    host = both_layouts["host_page"]["batching"]
    assert host["layout"] == "host" and host["perShardBytes"] == 0


def test_device_layout_answers_as_the_host_layout_does(both_layouts):
    """Reply for reply: the same items in the same order, scores to
    float32 rounding; and the rules hold in every reply."""
    n_full = 0
    for query, dev, host in zip(both_layouts["queries"],
                                both_layouts["device"],
                                both_layouts["host"]):
        assert [i for i, _ in dev] == [i for i, _ in host], query
        np.testing.assert_allclose([s for _, s in dev],
                                   [s for _, s in host], rtol=2e-6,
                                   atol=2e-6)
        n_full += len(dev) == K
        served = {int(i[1:]) for i, _ in dev}
        assert not served & both_layouts["gone"]
        assert not served & {7, 8}                  # untrained items
        assert not served & {int(i[1:])
                             for i in query.get("blackList", ())}
        if query["user"] != "u900":
            assert not served & set(_seen(int(query["user"][1:])))
        assert all(s > 0 for _, s in dev)
    assert n_full > 100        # the rules did not empty the catalog


def test_flush_callback_is_reentrant_and_flags_only_its_own_flush(
        monkeypatch):
    """`pio deploy`'s flush callback on two lanes at once, on the device
    layout: each flush answers as it does alone, and a seen-events read
    that fails in one of them flags that flush's replies degraded and
    not the other's (the flag is the lane's own)."""
    import threading

    from tests.test_serving_batcher import at_once

    def as_query(q):
        return Query(user=q["user"], num=q["num"],
                     categories=q.get("categories"),
                     whiteList=q.get("whiteList"),
                     blackList=q.get("blackList"))

    queries = [as_query(q) for q in _mixed_queries(40, seed=3)]
    batches = [[q for q in queries if q.user != "u5"][:4],
               [q for q in queries if q.user != "u5"][4:]]
    batches[0].append(Query(user="u5", num=K))
    with _deployed("1e9") as (api, _storage, _app, _gone):
        flush = api._batcher._flush_fn
        alone = [flush(b) for b in batches]
        assert not any(bad for r in alone for _p, bad in r)
        for got, due in zip(at_once(flush, batches), alone):
            assert all(g == due for g in got)
        # both flushes inside predict_batch before u5's read fails
        real = store.find_target_ids
        inside = threading.Barrier(2)
        first = threading.local()

        def reads(*a, **kw):
            if not getattr(first, "done", False):
                first.done = True
                inside.wait(30)
            if kw.get("entity_id") == "u5":
                raise OSError("seen-events store unreachable")
            return real(*a, **kw)

        monkeypatch.setattr(store, "find_target_ids", reads)
        tainted, clean = at_once(flush, batches, rounds=1)
    assert [bad for _p, bad in tainted[0]] == [True] * len(batches[0])
    assert [bad for _p, bad in clean[0]] == [False] * len(batches[1])
    assert [p for p, _bad in clean[0]] == [p for p, _bad in alone[1]]


def test_nothing_compiles_after_warm_up(both_layouts):
    before, after = both_layouts["compiles"]
    assert after == before


def test_ecomm_block_counts_the_rule_work(both_layouts):
    """`GET /` `ecomm`: monotone counters of this window's 200
    queries; the same numbers as pio_ecomm_* on /metrics."""
    warm, after = (both_layouts[k]["ecomm"]
                   for k in ("page_warm", "page_after"))
    queries = both_layouts["queries"]
    rose = {k: after[k] - warm[k] for k in after if k != "excludeWidths"}
    assert rose["queries"] == len(queries)
    def past_the_widest(q):
        gone = set(_seen(int(q["user"][1:]))) | {
            int(i[1:]) for i in q.get("blackList", ())}
        return len(gone) > WIDEST

    on_host = sum(1 for q in queries if "whiteList" in q
                  or q["user"] == "u900" or past_the_widest(q))
    assert rose["hostFallbacks"] == on_host > 0
    assert any(past_the_widest(q) for q in queries
               if "whiteList" not in q and q["user"] != "u900")
    # one read a query that reached the device's plan (a query past the
    # widest width reads again on the host), one a host query
    assert rose["seenReads"] >= len(queries)
    # one constraint read a device flush, one a host-answered query
    widths = {w: after["excludeWidths"][w] - warm["excludeWidths"][w]
              for w in after["excludeWidths"]}
    assert set(widths) == {str(w) for w in topk.EXCLUDE_WIDTHS}
    assert all(n > 0 for n in widths.values())
    assert rose["constraintReads"] == sum(widths.values()) + on_host
    assert rose["excludedItems"] > 0
    assert rose["constraintUploads"] == 0       # one $set, placed once
    assert after["constraintUploads"] >= 2      # the deploy's, the $set's
    for name in ("queries", "excluded_items", "seen_reads",
                 "constraint_reads", "constraint_uploads",
                 "host_fallbacks"):
        assert f"pio_ecomm_{name}_total" in both_layouts["metrics"]
    assert 'pio_ecomm_exclude_width_flushes_total{width="128"}' in \
        both_layouts["metrics"]


def test_a_view_and_a_new_constraint_are_honoured_by_the_next_query():
    """Freshness, with the server up and no reload: the rule reads are
    live."""
    with _deployed("1e9") as (api, storage, app_id, gone):
        query = {"user": "u20", "num": K}
        (first,) = _ask(api, [query], threads=1)
        assert len(first) == K
        best, second = first[0][0], first[1][0]
        store.write([_view(20, int(best[1:]), T0 + dt.timedelta(days=2))],
                    app_id, storage=storage)
        (after_view,) = _ask(api, [query], threads=1)
        assert best not in [i for i, _ in after_view]
        assert after_view[0][0] == second
        _st, page = api.handle("GET", "/")
        uploads = page["ecomm"]["constraintUploads"]
        fallbacks = page["ecomm"]["hostFallbacks"]
        store.write([_set_unavailable(
            [int(second[1:])], T0 + dt.timedelta(days=3))],
            app_id, storage=storage)
        (after_set,) = _ask(api, [query], threads=1)
        names = [i for i, _ in after_set]
        assert second not in names and best not in names
        # the new $set REPLACES the list: what was unavailable is back
        was_gone = {f"i{i}" for i in gone.tolist()}
        back = _ask(api, [{"user": f"u{u}", "num": 40}
                          for u in range(4, 12)])
        assert any(was_gone & {i for i, _ in reply} for reply in back)
        _st, page = api.handle("GET", "/")
        assert page["ecomm"]["constraintUploads"] == uploads + 1
        assert page["ecomm"]["hostFallbacks"] == fallbacks


def test_predict_rides_the_bucket_1_program(monkeypatch):
    """Batching off: `predict` is a flush of one on the device layout,
    and makes no call of the host kernels for a known user."""
    with _deployed("1e9") as (api, _storage, _app, _gone):
        algo, model = api.algorithms[0], api.models[0]
        assert model.device is not None

        def no_host(*_a, **_k):
            raise AssertionError("a host kernel answered a known user")

        monkeypatch.setattr(topk, "host_masked_topk", no_host)
        monkeypatch.setattr(topk, "host_masked_topk_batch", no_host)
        c0 = devicewatch.compiles_total()
        one = algo.predict(model, Query(user="u5", num=K,
                                        categories=("c1", "c2")))
        many = algo.predict_batch(model, [
            Query(user="u5", num=K, categories=("c1", "c2")),
            Query(user="u6", num=3), Query(user="u7", num=0)])
        assert devicewatch.compiles_total() == c0
        # the same items; a score is the bucket-1 program's or the
        # bucket-4 program's, which round alike only on a TPU
        assert [s.item for s in one.itemScores] == [
            s.item for s in many[0].itemScores]
        np.testing.assert_allclose([s.score for s in one.itemScores],
                                   [s.score for s in many[0].itemScores],
                                   rtol=2e-6)
        assert len(one.itemScores) == K
        assert len(many[1].itemScores) == 3 and many[2].itemScores == ()


# ---------------------------------------------------------------------------
# the in-memory event store: reads by entity against a scan
# ---------------------------------------------------------------------------

def _scan(events, **filt):
    """What `find` did before the index: every event of the app
    filtered, then a stable sort by time."""
    reverse = filt.pop("reversed_", False)
    limit = filt.pop("limit", None)
    hits = [e for e in events.values() if event_matches(e, **filt)]
    hits.sort(key=lambda e: e.event_time, reverse=reverse)
    return hits if limit is None else hits[:limit]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_memory_store_index_equals_a_scan(seed):
    rng = np.random.default_rng(seed)
    dao, table = MemoryEvents(), {}
    dao.init(1)
    ids = []

    def check():
        for _ in range(12):
            et = str(rng.choice(["user", "constraint", "item"]))
            eid = f"e{int(rng.integers(0, 12))}"
            names = [None, ["view"], ["buy", "view"], ["$set"]][
                int(rng.integers(0, 4))]
            filt = dict(entity_type=et, entity_id=eid, event_names=names)
            for extra in ({}, {"limit": 1, "reversed_": True},
                          {"target_entity_type": "item"},
                          {"start_time": T0 + dt.timedelta(seconds=30)}):
                got = list(dao.find(1, **filt, **extra))
                assert got == _scan(table, **filt, **extra)
            want = [e.target_entity_id for e in _scan(
                table, **filt, target_entity_type="item")
                if e.target_entity_id is not None]
            assert sorted(dao.find_target_ids(
                1, **filt, target_entity_type="item")) == sorted(want)
        # a read with half an entity key, or none, still scans
        assert list(dao.find(1, entity_type="user")) == _scan(
            table, entity_type="user")
        assert list(dao.find(1, event_names=["buy"], limit=5)) == _scan(
            table, event_names=["buy"], limit=5)

    for step in range(400):
        roll = rng.random()
        if roll < 0.7 or not ids:
            et = str(rng.choice(["user", "constraint", "item"]))
            event = Event(
                event=str(rng.choice(["view", "buy", "$set"])),
                entity_type=et, entity_id=f"e{int(rng.integers(0, 12))}",
                target_entity_type="item" if rng.random() < 0.8 else None,
                target_entity_id=(f"i{int(rng.integers(0, 30))}"
                                  if rng.random() < 0.8 else None),
                event_time=T0 + dt.timedelta(
                    seconds=int(rng.integers(0, 60))),
                # now and then an id written again, under any entity
                event_id=(str(rng.choice(ids)) if ids and roll < 0.05
                          else None))
            event_id = dao.insert(event, 1)
            table[event_id] = dao.get(event_id, 1)
            ids.append(event_id)
        else:
            event_id = ids.pop(int(rng.integers(0, len(ids))))
            assert dao.delete(event_id, 1) == (event_id in table)
            table.pop(event_id, None)
        if step % 50 == 49:
            check()
    check()
    head, log = dao.read_events_since(1)
    assert head >= len(log)       # deleted events keep their place
    assert [e.event_id for e in log] == [
        e.event_id for e in dao._log[(1, None)] if e.event_id in table]
    assert dao.remove(1) and list(dao.find(1, entity_type="user",
                                           entity_id="e1")) == []
