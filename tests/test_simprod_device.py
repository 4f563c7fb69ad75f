"""The similar-product engine's device layout, on the CPU at a small size.

ops/topk.py itemset_topk_rows (gather > score > mask > exclude > select)
against the plain reference the benchmark's cell is held to
(benchmark/reference/simprod_reference.py, loaded by path: NumPy,
nothing of the program): the same items in the same order, scores to
float32 rounding. Then the engine: the device layout answers as the host
layout does, reply for reply, through `predict`, `predict_batch`,
LikeAlgorithm and `pio deploy`'s QueryAPI; what the program has no
argument for is answered by the host code and counted; nothing compiles
after warm-up.
"""

import contextlib
import dataclasses
import datetime as dt
import importlib.util
import json
import os
import pickle
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

import jax

from predictionio_tpu.common import devicewatch, telemetry
from predictionio_tpu.data.storage import (EngineInstance, Model,
                                           reset_storage,
                                           use_memory_storage)
from predictionio_tpu.models import item_rules
from predictionio_tpu.models.similarproduct import als_algorithm as simprod
from predictionio_tpu.models.similarproduct.engine import Item, Query
from predictionio_tpu.ops import topk
from predictionio_tpu.serving import protocol

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFERENCE_DIR = os.path.join(ROOT, "benchmark", "reference")


@pytest.fixture(scope="module")
def reference():
    """benchmark/reference/simprod_reference.py, as the benchmark's
    adapter imports it (its directory on the path for the siblings it
    reads), gone from sys.path and sys.modules afterwards."""
    before = set(sys.modules)
    sys.path.insert(0, REFERENCE_DIR)
    try:
        spec = importlib.util.spec_from_file_location(
            "_simprod_reference",
            os.path.join(REFERENCE_DIR, "simprod_reference.py"))
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

RANK, N_CATS, K, Q = 8, 5, 10, topk.QUERY_WIDTH
WIDEST = topk.EXCLUDE_WIDTHS[-1]


def _catalog(n_items, seed=7, tied=False):
    """Seeded raw factors, one category an item, 2 % of the items
    untrained. `tied`: factors on a coarse grid with every item repeated
    three times more, so that equal scores are many."""
    rng = np.random.default_rng(seed)
    V = rng.standard_normal((n_items, RANK), dtype=np.float32)
    if tied:
        V = np.tile(np.round(V[:n_items // 4]) + np.float32(0.5),
                    (4, 1))[:n_items]
    cats = rng.integers(0, N_CATS, n_items)
    trained = np.ones(n_items, bool)
    trained[rng.choice(n_items, max(1, n_items // 50), replace=False)] = False
    return V, cats, trained


def _rule_words(cats, n_items):
    items = {i: Item(categories=(f"c{c}",)) for i, c in enumerate(cats)}
    return item_rules.category_words(items, n_items)


#: case -> (n_items, bucket, tied, each row's (query items, categories
#: or None, black-list length, rule out every candidate))
CASES = {
    "one_item": (1500, 4, False, [(1, None, 0, False), (1, (1,), 0, False),
                                  (1, None, 3, False), (1, (0, 3), 2, False)]),
    "several_items": (1500, 4, False, [(2, None, 0, False),
                                       (Q, None, 5, False),
                                       (5, (2,), 1, False),
                                       (3, (1, 4), 0, False)]),
    "ties": (1500, 4, True, [(1, None, 3, False), (2, (1,), 0, False),
                             (4, (0, 3), 40, False), (1, None, 0, False)]),
    "no_category_left": (1500, 4, False, [(1, (), 0, False),
                                          (2, None, 5, False),
                                          (1, (2,), 1, False),
                                          (3, (), 17, False)]),
    "width_128": (1500, 4, False, [(1, None, 127, False), (2, (4,), 2, False),
                                   (1, None, 0, False),
                                   (Q, (1, 2), 128 - Q, False)]),
    "width_widest": (6000, 4, False, [(1, None, WIDEST - 1, False),
                                      (1, (0,), 9, False),
                                      (2, None, 127, False),
                                      (Q, (3,), WIDEST - Q, False)]),
    "every_candidate_excluded": (1500, 4, False, [
        (1, (2,), 0, True), (2, None, 4, False), (1, (0,), 0, True),
        (1, None, 0, False)]),
    "num_above_the_candidates_left": (300, 4, False, [
        (1, (1,), 0, "but_three"), (1, None, 0, False),
        (2, (2,), 0, "but_three"), (1, (4,), 1, False)]),
    "bucket_1": (1500, 1, False, [(3, (1, 4), 6, False)]),
    "bucket_64": (1500, 64, False, [
        (1 + r % Q,
         (None, (r % N_CATS,), (r % N_CATS, (r + 2) % N_CATS))[r % 3],
         (0, 3, 20, 100)[r % 4], False) for r in range(64)]),
    # 12,000 items >= 2 * (k + 1) * CHUNK: stable_topk's two stages
    "long_row_chunked": (12_000 + 37, 4, False, [
        (1, None, 70, False), (2, (1,), 4, False), (Q, (0, 2), 0, False),
        (1, None, WIDEST - 1, False)]),
}


def _flush(case, reference, pad_rows=0):
    """The case's arguments for itemset_topk_rows (the bucket `pad_rows`
    rows longer, all padding) and each row's reference answer."""
    n_items, bucket, tied, rules = CASES[case]
    V, cats, trained = _catalog(n_items, tied=tied)
    V_hat = reference.normalize(V.copy())
    bits, words = _rule_words(cats, n_items)
    rng = np.random.default_rng(11)
    longest = max(n_q + n_out for n_q, _c, n_out, _a in rules)
    ok = np.flatnonzero(trained)
    rows = []
    for n_q, categories, n_out, everything in rules:
        own = np.sort(rng.choice(ok, n_q, replace=False))
        black = rng.choice(n_items, n_out, replace=False)
        mask = reference.candidates(n_items, cats, categories, own, black,
                                    trained)
        if everything:
            left = np.flatnonzero(mask)
            if everything == "but_three":
                left = left[:-3]
            # the candidates themselves are what the row rules out
            black = np.concatenate([black, left])
            mask[left] = False
        gone = sorted(set(own.tolist()) | set(black.tolist()))
        longest = max(longest, len(gone))
        rows.append((own, categories, gone, mask))
    want, exclude = topk.blank_rule_arguments(
        bucket + pad_rows, words.shape[0], longest, n_items)
    query = np.full((bucket + pad_rows, Q), n_items, np.int32)
    for r, (own, categories, gone, _mask) in enumerate(rows):
        query[r, :len(own)] = own
        want[r] = item_rules.want_bits(
            bits, words.shape[0],
            None if categories is None else [f"c{c}" for c in categories])
        exclude[r, :len(gone)] = gone
    assert exclude.shape[1] in topk.EXCLUDE_WIDTHS
    vectors = np.stack([reference.query_vector(V_hat[own], own)
                        for own, *_ in rows])
    ref = reference.scores(vectors, reference.prepare(V_hat))
    due = [reference.recommend(ref[r], mask, K)
           for r, (*_, mask) in enumerate(rows)]
    return ((V_hat, words, trained, query, want, exclude), ref, due,
            [mask for *_, mask in rows])


@pytest.mark.parametrize("case", sorted(CASES))
def test_itemset_program_equals_the_reference(case, reference):
    args, ref, due, _masks = _flush(case, reference)
    if case == "long_row_chunked":
        assert topk.chunk_plan(args[0].shape[0], K) is not None
    vals, idx = jax.device_get(topk.itemset_topk_rows(*args, k=K))
    for r, items in enumerate(due):
        kept = vals[r] > 0
        # rank bit for bit: the same items in the same order
        assert idx[r][kept].tolist() == items.tolist(), (case, r)
        np.testing.assert_allclose(vals[r][kept], ref[r][items], rtol=3e-6,
                                   atol=3e-6)
        if len(items) < K:
            assert (vals[r][~kept] <= 0).all()
    if case == "ties":
        assert any(len(set(ref[r][items])) < len(items)
                   for r, items in enumerate(due)), "no tie in the case"
    if case == "every_candidate_excluded":
        assert len(due[0]) == 0 and len(due[2]) == 0
    if case == "num_above_the_candidates_left":
        assert 0 < len(due[0]) <= 3


def test_ties_across_the_kth_place_break_by_lowest_index(reference):
    """Every item repeated four times: the k-th and (k+1)-th candidates
    score alike, and the lower index is served."""
    args, ref, due, masks = _flush("ties", reference)
    vals, idx = jax.device_get(topk.itemset_topk_rows(*args, k=K))
    crossed = 0
    for r, items in enumerate(due):
        tied = np.flatnonzero(masks[r] & (ref[r] == ref[r][items[-1]]))
        left_out = sorted(set(tied.tolist()) - set(items.tolist()))
        if left_out:
            crossed += 1
            # what was served of the tie is its lowest indices
            assert max(set(tied.tolist()) & set(items.tolist())) \
                < left_out[0]
        assert idx[r][vals[r] > 0].tolist() == items.tolist()
    assert crossed, "no tie ran across the k-th place"


@pytest.mark.parametrize("case", ["several_items", "bucket_1"])
def test_padding_rows_and_padding_items_change_nothing(case, reference):
    """Rows of nothing but padding beside the real ones, and the padded
    entries of a short item list: the real rows' bits are the same, and
    a padding row answers nothing."""
    args, *_ = _flush(case, reference)
    vals, idx = jax.device_get(topk.itemset_topk_rows(*args, k=K))
    more, *_ = _flush(case, reference, pad_rows=3)
    vals2, idx2 = jax.device_get(topk.itemset_topk_rows(*more, k=K))
    n = len(vals)
    # the same items; a score is another bucket's program's, and two
    # programs round alike only on a TPU
    assert (idx2[:n] == idx).all()
    np.testing.assert_allclose(vals2[:n], vals, rtol=2e-6)
    assert (vals2[n:] <= 0).all()
    # a row's item list written again with its padding in other places
    query = more[3].copy()
    n_items = more[0].shape[0]
    for r in range(n):
        own = query[r][query[r] < n_items]
        query[r] = n_items
        query[r, Q - len(own):] = own       # same order, padding first
    vals3, idx3 = jax.device_get(topk.itemset_topk_rows(
        *more[:3], query, *more[4:], k=K))
    assert (idx3 == idx2).all() and (vals3 == vals2).all()


def test_device_rows_pads_one_and_two_dimensional_indices():
    """serving/protocol.py device_rows: a (b,) vector of users pads
    with index 0, a (b, q) array of item lists with the caller's own
    padding value; one dispatch either way."""
    seen = []

    def fake(pix, k):
        seen.append(np.array(pix))
        return (np.zeros((len(pix), k), np.float32),
                np.zeros((len(pix), k), np.int32))

    with protocol.flush_buckets((1, 4, 16)):
        vals, idx = protocol.device_rows(fake, np.asarray([5, 6, 7]), 2)
        assert vals.shape == idx.shape == (3, 2)
        assert seen[-1].tolist() == [5, 6, 7, 0] \
            and seen[-1].dtype == np.int32
        lists = np.asarray([[1, 2, 99], [3, 99, 99], [4, 5, 6], [7, 99, 99],
                            [8, 9, 99]])
        vals, idx = protocol.device_rows(fake, lists, 3, fill=99)
        assert vals.shape == (5, 3) and seen[-1].shape == (16, 3)
        assert (seen[-1][:5] == lists).all() and (seen[-1][5:] == 99).all()
    assert len(seen) == 2


# ---------------------------------------------------------------------------
# the engine: device layout against host layout
# ---------------------------------------------------------------------------

N_ITEMS = 5000
UNTRAINED = (7, 8)
CARRIED = ("train", "old_pickle")


def _train(V, cats, untrained=()):
    """`ALSAlgorithm.train`'s model of a catalog whose raw item factors
    are ``V``: the trainer kernels (ops/als.py) stand aside for them,
    everything else is train's own: the vocabulary, which items count
    as trained (one view each but ``untrained``), the categories as
    rule words, the rows at unit length."""
    from predictionio_tpu.models.similarproduct.data_source import (
        TrainingData, ViewEvent)

    n = len(V)
    data = TrainingData(
        users={"u0": None},
        items={f"i{i}": Item(categories=(f"c{c}",))
               for i, c in enumerate(cats)},
        view_events=[ViewEvent("u0", f"i{i}", 0.0) for i in range(n)
                     if i not in untrained])
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(simprod.als, "prepare_ratings", lambda *a, **kw: None)
        mp.setattr(simprod.als, "train_implicit",
                   lambda *a, **kw: (None, V))
        model = simprod.ALSAlgorithm(
            simprod.ALSAlgorithmParams(rank=RANK, seed=3)).train(None, data)
    assert [model.item_vocab(f"i{i}") for i in (0, n - 1)] == [0, n - 1]
    return model


def _model(carried="train", seed=21):
    """A seeded ALSModel as `train` leaves it (`carried` "train"), or
    as a pickle from before the categories were rule words comes back
    (`carried` "old_pickle": it held one Item an index and one boolean
    vector a category)."""
    V, cats, _ = _catalog(N_ITEMS, seed=seed)
    model = _train(V, cats, UNTRAINED)
    if carried == "train":
        return model
    items = {i: Item(categories=(f"c{c}",)) for i, c in enumerate(cats)}
    old = simprod.ALSModel.__new__(simprod.ALSModel)
    old.__dict__.update(
        product_features=model.product_features,
        item_vocab=model.item_vocab, items=items,
        trained_mask=model.trained_mask,
        category_masks=item_rules.build_category_masks(items, N_ITEMS))
    return pickle.loads(pickle.dumps(old))


def _mixed_queries(n, seed=13):
    """Queries as the cell's adapter draws them (one item mostly, else
    up to the declared width; categories; black lists), and beside them
    what a deployment also sees: a repeated item, unknown and untrained
    items, nothing known at all, a category nobody has, `num` 0, and
    what the device program has no argument for: a white list, more
    items than the declared width, a black list past the widest width."""
    rng = np.random.default_rng(seed)
    out = []
    for j in range(n):
        n_q = 1 if rng.random() < 0.7 else int(rng.integers(2, Q + 1))
        items = [f"i{i}" for i in rng.choice(N_ITEMS, n_q, replace=False)]
        query = {"items": items, "num": K}
        if rng.random() < 0.5:
            query["categories"] = [f"c{c}" for c in rng.choice(
                N_CATS, int(rng.integers(1, 3)), replace=False)]
        if rng.random() < 1 / 3:
            query["blackList"] = [f"i{i}" for i in rng.choice(
                N_ITEMS, int(rng.integers(1, 6)), replace=False)]
        if j % 11 == 0:
            query["items"] = items + items[:1]              # repeated
        if j % 13 == 0:
            query["items"] = items + ["nobody", f"i{UNTRAINED[0]}"]
        if j % 17 == 0:
            query["whiteList"] = [f"i{i}" for i in rng.choice(
                N_ITEMS, 300, replace=False)]
        if j % 19 == 0:
            query["items"] = ["nobody", f"i{UNTRAINED[1]}"]  # none known
        if j % 23 == 0:
            query["items"] = [f"i{i}" for i in rng.choice(
                N_ITEMS, Q + 3, replace=False)]             # past q
        if j % 29 == 0:
            query["blackList"] = [f"i{i}" for i in rng.choice(
                N_ITEMS, WIDEST + 5, replace=False)]        # past E
        if j % 31 == 0:
            query["categories"] = ["no_such_category"]
        if j % 37 == 0:
            query["num"] = 0
        if j % 41 == 0:
            query["blackList"] = [f"i{i}" for i in rng.choice(
                N_ITEMS, 200, replace=False)]               # the wide E
        out.append(query)
    return out


def _on_host(query):
    """Does the device layout hand this query to the host code?"""
    known = {i for i in query["items"]
             if i != "nobody" and int(i[1:]) not in UNTRAINED}
    if not known or query["num"] <= 0:
        return False            # answered empty before any layout
    gone = known | set(query.get("blackList", ()))
    return "whiteList" in query or len(known) > Q or len(gone) > WIDEST


def _as_query(q):
    return Query(items=tuple(q["items"]), num=q["num"],
                 categories=q.get("categories"),
                 whiteList=q.get("whiteList"), blackList=q.get("blackList"))


def _pairs(result):
    return [(s.item, s.score) for s in result.itemScores]


def _same_replies(got, due):
    assert [i for i, _ in got] == [i for i, _ in due]
    np.testing.assert_allclose([s for _, s in got], [s for _, s in due],
                               rtol=3e-6, atol=3e-6)


@pytest.fixture(scope="module")
def layouts():
    """One algorithm, the model on the host layout and on the device
    layout (PIO_SERVE_DEVICE_MS 1e9 keeps it on the CPU backend), for
    the model as `train` leaves it and as an old pickle comes back."""
    mp = pytest.MonkeyPatch()
    telemetry.set_enabled(True)
    algo = simprod.ALSAlgorithm(simprod.ALSAlgorithmParams(rank=RANK))
    out = {"algo": algo}
    try:
        for carried in CARRIED:
            model = _model(carried)
            mp.setenv("PIO_SERVE_DEVICE_MS", "0")
            out[carried, "host"] = algo.prepare_serving(model)
            mp.setenv("PIO_SERVE_DEVICE_MS", "1e9")
            out[carried, "device"] = algo.prepare_serving(model)
        yield out
    finally:
        telemetry.set_enabled(None)
        mp.undo()


def test_prepare_serving_chooses_by_the_probe_on_the_cpu_backend(layouts):
    for carried in CARRIED:
        host, dev = layouts[carried, "host"], layouts[carried, "device"]
        assert host.device is None and dev.device is not None
        assert host.serving_layout() == {"layout": "host", "shards": 0,
                                         "perShardBytes": 0}
        assert host.hbm_bytes() == 0 and host.topk_rows() is None
        want = N_ITEMS * RANK * 4 + N_ITEMS * 4 + N_ITEMS
        assert dev.serving_layout() == {
            "layout": "items+rules", "shards": 1, "perShardBytes": want,
            "queryWidth": Q, "excludeWidths": list(topk.EXCLUDE_WIDTHS)}
        assert dev.hbm_bytes() == want and dev.topk_rows() == N_ITEMS
        assert dev.status_block()[0] == "simprod"
    # an old pickle's items make the words `train` now leaves, and the
    # model carries nothing else of them
    a, b = (layouts[c, "device"] for c in CARRIED)
    assert a.category_bits == b.category_bits
    assert (np.asarray(a.rule_words) == np.asarray(b.rule_words)).all()
    assert (a.device.rule_words == b.device.rule_words).all()
    assert set(vars(a)) == set(vars(b)) == {
        f.name for f in dataclasses.fields(simprod.ALSModel)}


def test_a_layout_that_fails_raises_on_an_accelerator(monkeypatch):
    """On the CPU backend a failed layout falls back to the host arrays;
    on an accelerator it raises: a deploy never quietly serves another
    way than `GET /` says."""
    algo = simprod.ALSAlgorithm(simprod.ALSAlgorithmParams(rank=RANK))

    def broken(_model):
        raise RuntimeError("no room on the device")

    monkeypatch.setattr(simprod, "_place", broken)
    assert algo.prepare_serving(_model()).device is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="no room"):
        algo.prepare_serving(_model())


@pytest.mark.parametrize("carried", CARRIED)
def test_device_layout_answers_as_the_host_layout_does(layouts, carried):
    """Reply for reply over 300 mixed queries, in flushes of every
    bucket: the same items in the same order, scores to float32
    rounding; the rules hold in every reply; what the program has no
    argument for is counted as a host fallback and nothing else is."""
    algo = layouts["algo"]
    host, dev = layouts[carried, "host"], layouts[carried, "device"]
    queries = _mixed_queries(300)
    cats = _catalog(N_ITEMS, seed=21)[1]
    before = simprod.stats()
    got = []
    for lo, hi in ((0, 1), (1, 4), (4, 17), (17, 81), (81, 300)):
        batch = [_as_query(q) for q in queries[lo:hi]]
        for s in range(0, len(batch), 64):
            got += algo.predict_batch(dev, batch[s:s + 64])
    after = simprod.stats()
    due = algo.predict_batch(host, [_as_query(q) for q in queries])
    n_full = 0
    for query, d, h in zip(queries, got, due):
        _same_replies(_pairs(d), _pairs(h))
        served = {int(s.item[1:]) for s in d.itemScores}
        n_full += len(served) == K
        assert not served & set(UNTRAINED)
        assert not served & {int(i[1:]) for i in query["items"]
                             if i != "nobody"}
        assert not served & {int(i[1:]) for i in query.get("blackList", ())}
        assert all(s.score > 0 for s in d.itemScores)
        if "categories" in query:
            assert all(f"c{cats[i]}" in query["categories"]
                       for i in served)
        if query["items"][0] == "nobody" or query["num"] == 0:
            assert d.itemScores == ()
    assert n_full > 150         # the rules did not empty the catalog
    rose = {k: after[k] - before[k] for k in after
            if k not in ("excludeWidths", "queryWidth")}
    assert rose["queries"] == len(queries)
    assert rose["queryItems"] == sum(len(q["items"]) for q in queries)
    assert rose["unknownItems"] == sum(
        1 for q in queries for i in q["items"]
        if i == "nobody" or int(i[1:]) in UNTRAINED)
    assert rose["hostFallbacks"] == sum(map(_on_host, queries)) > 0
    for why in ("whiteList", ):
        assert any(why in q and _on_host(q) for q in queries)
    assert any(len(q["items"]) > Q for q in queries)
    assert any(len(q.get("blackList", ())) > WIDEST for q in queries)
    assert rose["excludedItems"] > 0
    widths = {w: after["excludeWidths"][w] - before["excludeWidths"][w]
              for w in after["excludeWidths"]}
    assert set(widths) == {str(w) for w in topk.EXCLUDE_WIDTHS}
    assert all(n > 0 for n in widths.values())
    assert after["queryWidth"] == Q


def test_predict_batch_is_reentrant(layouts):
    """Two flushes at once on the device layout (the batcher's two
    lanes), at two buckets, the host fallbacks among them: each answers
    as it does alone."""
    from tests.test_serving_batcher import at_once

    algo, dev = layouts["algo"], layouts["train", "device"]
    queries = [_as_query(q) for q in _mixed_queries(64, seed=5)]
    batches = [queries[:3], queries[3:64]]
    alone = [algo.predict_batch(dev, b) for b in batches]
    for got, due in zip(at_once(lambda b: algo.predict_batch(dev, b),
                                batches), alone):
        assert all([_pairs(r) for r in g] == [_pairs(r) for r in due]
                   for g in got)


def test_predict_is_the_row_of_predict_batch(layouts, monkeypatch):
    """`predict` on the device layout is a flush of one, on the
    bucket-1 program, and no host kernel answers a query the program
    has arguments for."""
    algo, dev = layouts["algo"], layouts["train", "device"]

    def no_host(*_a, **_k):
        raise AssertionError("a host kernel answered a device query")

    monkeypatch.setattr(topk, "host_masked_topk", no_host)
    monkeypatch.setattr(topk, "host_masked_topk_batch", no_host)
    queries = [Query(items=("i5", "i900"), num=K, categories=("c1", "c2")),
               Query(items=("i6",), num=3, blackList=("i1", "i2")),
               Query(items=("i7", "nobody"), num=K),      # none trained
               Query(items=("i9",), num=0)]
    many = algo.predict_batch(dev, queries)
    for query, row in zip(queries, many):
        one = algo.predict(dev, query)
        _same_replies(_pairs(one), _pairs(row))
    assert len(many[0].itemScores) == K and len(many[1].itemScores) == 3
    assert many[2].itemScores == () and many[3].itemScores == ()


def test_like_algorithm_serves_through_the_same_path(layouts):
    like = simprod.LikeAlgorithm(simprod.ALSAlgorithmParams(rank=RANK))
    assert vars(simprod.LikeAlgorithm).keys() & {
        "predict", "predict_batch", "prepare_serving",
        "aot_serving_programs"} == set()
    dev, host = layouts["train", "device"], layouts["train", "host"]
    queries = [_as_query(q) for q in _mixed_queries(40, seed=3)]
    for d, h in zip(like.predict_batch(dev, queries),
                    like.predict_batch(host, queries)):
        _same_replies(_pairs(d), _pairs(h))
    specs = like.aot_serving_programs(dev, (4, 64))
    assert {s.name for s in specs} == {"itemset_topk_rows"}
    assert len(specs) == 3 * len(topk.EXCLUDE_WIDTHS)       # 1, 4, 64
    assert like.aot_serving_programs(host, (4, 64)) == ()
    assert len(like.aot_serving_programs(host, (4,), declared=True)) == \
        2 * len(topk.EXCLUDE_WIDTHS)


def test_scaling_raw_factors_changes_no_reply():
    """Cosine ignores magnitude: scaling an item's raw factors before
    `train` normalizes them changes no reply (what
    tests/test_topk.py::test_cosine_topk_scale_invariant holds the
    program to, held here to the engine on both layouts)."""
    mp = pytest.MonkeyPatch()
    algo = simprod.ALSAlgorithm(simprod.ALSAlgorithmParams(rank=RANK))
    queries = [_as_query(q) for q in _mixed_queries(30, seed=5)]
    V, cats, _ = _catalog(N_ITEMS, seed=21)
    scale = np.random.default_rng(2).uniform(0.01, 100, (N_ITEMS, 1)
                                             ).astype(np.float32)
    replies = []
    try:
        for raw in (V, V * scale):
            model = _train(raw, cats, UNTRAINED)
            for ms in ("0", "1e9"):
                mp.setenv("PIO_SERVE_DEVICE_MS", ms)
                replies.append(algo.predict_batch(
                    algo.prepare_serving(model), queries))
    finally:
        mp.undo()
    for other in replies[1:]:
        for a, b in zip(replies[0], other):
            assert [s.item for s in a.itemScores] == \
                [s.item for s in b.itemScores]
            np.testing.assert_allclose([s.score for s in a.itemScores],
                                       [s.score for s in b.itemScores],
                                       rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# the engine through `pio deploy`'s QueryAPI
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _deployed(device_ms, algorithm="als"):
    """`pio deploy`'s QueryAPI on a COMPLETED instance of a seeded
    ALSModel. `device_ms` is PIO_SERVE_DEVICE_MS: 1e9 keeps the device
    layout on the CPU backend, 0 the host layout."""
    from predictionio_tpu.models.similarproduct import SimilarProductEngine
    from predictionio_tpu.workflow import model_io
    from predictionio_tpu.workflow.create_server import (QueryAPI,
                                                         ServerConfig)

    mp = pytest.MonkeyPatch()
    mp.setenv("PIO_SERVE_DEVICE_MS", device_ms)
    telemetry.set_enabled(True)
    devicewatch.install()
    storage = use_memory_storage()
    algorithms = [{"name": algorithm, "params": {"rank": RANK}}]
    now = dt.datetime.now(dt.timezone.utc)
    instance_id = storage.get_meta_data_engine_instances().insert(
        EngineInstance(
            id="", status="COMPLETED", start_time=now, end_time=now,
            engine_id="default", engine_version="NOT_USED",
            engine_variant="default",
            engine_factory="predictionio_tpu.models.similarproduct.engine:"
                           "SimilarProductEngine",
            data_source_params=json.dumps({"params": {"appName": "shop"}}),
            preparator_params="{}",
            algorithms_params=json.dumps(algorithms),
            serving_params="{}"))
    storage.get_model_data_models().insert(Model(
        id=instance_id,
        models=model_io.serialize_models([_model()],
                                         check_finite=True)))
    api = QueryAPI(storage=storage, engine=SimilarProductEngine(),
                   config=ServerConfig(batching="on"))
    try:
        yield api
    finally:
        api.close()
        reset_storage()
        telemetry.set_enabled(None)
        mp.undo()


def _ask(api, queries, threads=16):
    def one(query):
        status, body = api.handle("POST", "/queries.json",
                                  body=json.dumps(query).encode())
        assert status == 200, body
        return [(s["item"], s["score"]) for s in body["itemScores"]]

    with ThreadPoolExecutor(threads) as pool:
        return list(pool.map(one, queries))


@pytest.fixture(scope="module")
def deployed():
    """200 mixed queries answered through QueryAPI by the device layout
    (after a warm-up round, with the compile counter read round them)
    and by the host layout of the same deployment."""
    queries = _mixed_queries(200, seed=17)
    out = {"queries": queries}
    with _deployed("1e9") as api:
        _st, out["page_before"] = api.handle("GET", "/")
        _st, ready = api.handle("GET", "/readyz")
        assert ready["status"] == "ready"
        _ask(api, _mixed_queries(64, seed=1))        # warm-up
        _st, out["page_warm"] = api.handle("GET", "/")
        c0 = devicewatch.compiles_total()
        out["device"] = _ask(api, queries)
        out["compiles"] = (c0, devicewatch.compiles_total())
        _st, out["page_after"] = api.handle("GET", "/")
        _st, out["metrics"], _h = api.handle("GET", "/metrics")
    with _deployed("0") as api:
        _st, out["host_page"] = api.handle("GET", "/")
        out["host"] = _ask(api, queries)
    return out


def test_device_layout_is_what_a_deploy_ends_on(deployed):
    """No flag: `GET /` names the layout, its widths, the selection and
    what the device holds; the host layout says so too."""
    b = deployed["page_before"]["batching"]
    assert b["layout"] == "items+rules" and b["shards"] == 1
    assert b["excludeWidths"] == list(topk.EXCLUDE_WIDTHS)
    assert b["queryWidth"] == Q
    assert b["perShardBytes"] == N_ITEMS * RANK * 4 + N_ITEMS * 4 + N_ITEMS
    assert b["topkSelection"] == {str(K): topk.selection_name(N_ITEMS, K)}
    # a program a (bucket, width): the deploy's buckets (pruned by what
    # this process has seen flushed) and bucket 1, always
    aot = deployed["page_before"]["aot"]
    assert aot["programs"] == len({1, *aot["buckets"]}) * len(
        topk.EXCLUDE_WIDTHS) and aot["failed"] == 0
    host = deployed["host_page"]["batching"]
    assert host["layout"] == "host" and host["perShardBytes"] == 0
    assert "simprod" in deployed["host_page"]


def test_a_deploy_answers_as_the_host_layout_and_compiles_nothing(deployed):
    for dev, host in zip(deployed["device"], deployed["host"]):
        _same_replies(dev, host)
    assert sum(len(r) == K for r in deployed["device"]) > 100
    before, after = deployed["compiles"]
    assert after == before


def test_simprod_block_and_metrics_count_the_window(deployed):
    """`GET /` `simprod`: monotone counters of this window's 200
    queries; the same numbers as pio_simprod_* on /metrics."""
    warm, after = (deployed[k]["simprod"]
                   for k in ("page_warm", "page_after"))
    queries = deployed["queries"]
    rose = {k: after[k] - warm[k] for k in after
            if k not in ("excludeWidths", "queryWidth")}
    assert rose["queries"] == len(queries)
    assert rose["queryItems"] == sum(len(q["items"]) for q in queries)
    assert rose["hostFallbacks"] == sum(map(_on_host, queries)) > 0
    assert rose["unknownItems"] > 0 and rose["excludedItems"] > 0
    assert set(after["excludeWidths"]) == {
        str(w) for w in topk.EXCLUDE_WIDTHS}
    assert after["queryWidth"] == Q
    for name in ("queries", "query_items", "unknown_items",
                 "excluded_items", "host_fallbacks"):
        assert f"pio_simprod_{name}_total" in deployed["metrics"]
    assert 'pio_simprod_exclude_width_flushes_total{width="128"}' in \
        deployed["metrics"]


def test_neither_rule_engine_imports_the_other():
    """models/item_rules.py is the rules' one home."""
    import predictionio_tpu.models.ecommerce.als_algorithm as ecomm
    for module, other in ((ecomm, "similarproduct"), (simprod, "ecommerce")):
        with open(module.__file__) as f:
            source = f.read()
        assert f"models.{other}" not in source, module.__name__
    assert ecomm.candidate_mask is item_rules.candidate_mask
    assert simprod.candidate_mask is item_rules.candidate_mask
    assert ecomm.category_words is item_rules.category_words
    assert issubclass(ecomm.RuleDevice, item_rules.RuleDevice)
    assert issubclass(simprod.ItemSetDevice, item_rules.RuleDevice)
