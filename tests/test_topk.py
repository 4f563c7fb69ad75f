"""Top-K scoring ops (serving hot path)."""

import numpy as np
import pytest

from predictionio_tpu.ops import topk


def test_topk_scores_basic():
    V = np.array([[1.0, 0], [0, 1], [2, 0], [0.5, 0.5]], dtype=np.float32)
    q = np.array([1.0, 0.0], dtype=np.float32)
    vals, idx = topk.topk_scores(q, V, k=2)
    np.testing.assert_array_equal(np.asarray(idx), [2, 0])
    np.testing.assert_allclose(np.asarray(vals), [2.0, 1.0])


def test_topk_scores_mask_excludes():
    V = np.array([[1.0, 0], [0, 1], [2, 0], [0.5, 0.5]], dtype=np.float32)
    q = np.array([1.0, 0.0], dtype=np.float32)
    mask = np.array([True, True, False, True])  # best item excluded
    vals, idx = topk.topk_scores(q, V, mask, k=2)
    np.testing.assert_array_equal(np.asarray(idx), [0, 3])


def test_topk_batch_matches_loop():
    rng = np.random.default_rng(0)
    V = rng.normal(size=(50, 8)).astype(np.float32)
    Q = rng.normal(size=(7, 8)).astype(np.float32)
    bv, bi = topk.topk_scores_batch(Q, V, k=5)
    for row in range(7):
        sv, si = topk.topk_scores(Q[row], V, k=5)
        np.testing.assert_array_equal(np.asarray(bi)[row], np.asarray(si))


def test_cosine_topk_scale_invariant(monkeypatch):
    """Cosine ignores magnitude. The similar-product engine's `train`
    brings the trainer's raw item factors to unit rows, once, and
    ops/topk.py itemset_topk_rows sums the query's rows and takes one
    product: scaling an item's raw factors changes no reply."""
    from predictionio_tpu.models.similarproduct import (
        als_algorithm as simprod)
    from predictionio_tpu.models.similarproduct.data_source import (
        TrainingData, ViewEvent)
    from predictionio_tpu.models.similarproduct.engine import Item, Query

    raw = np.array([[10.0, 0], [0, 0.1], [3, 3], [4, 0.5]],
                   dtype=np.float32)
    scaled = raw * np.array([[0.01], [300.0], [7.0], [1.0]], np.float32)
    data = TrainingData(
        users={"u": None}, items={f"i{i}": Item() for i in range(4)},
        view_events=[ViewEvent("u", f"i{i}", 0.0) for i in range(4)])
    algo = simprod.ALSAlgorithm(simprod.ALSAlgorithmParams(rank=2, seed=1))
    # the trainer kernels stand aside for the factors under test; the
    # device layout, whatever the CPU backend's probe would time
    monkeypatch.setattr(simprod.als, "prepare_ratings",
                        lambda *a, **kw: None)
    monkeypatch.setenv("PIO_SERVE_DEVICE_MS", "1e9")
    replies = []
    for V in (raw, scaled):
        monkeypatch.setattr(simprod.als, "train_implicit",
                            lambda *a, V=V, **kw: (None, V))
        model = algo.prepare_serving(algo.train(None, data))
        assert model.device is not None
        np.testing.assert_allclose(
            np.linalg.norm(model.product_features, axis=1), 1, rtol=1e-6)
        res = algo.predict(model, Query(items=("i3",), num=3))
        replies.append(([s.item for s in res.itemScores],
                        [s.score for s in res.itemScores]))
    (items, vals), (items2, vals2) = replies
    # item 0 is the most parallel to item 3, whatever its length; item 1
    # is nearly at right angles to it, and still above 0
    assert items == items2 == ["i0", "i2", "i1"]
    np.testing.assert_allclose(vals, vals2, rtol=1e-6)
    np.testing.assert_allclose(vals[0], 4 / np.hypot(4, 0.5), rtol=1e-6)


#: StableHLO text of jit_masked_topk_rows at one shape, as commit
#: 6315a47 (before `mask` > `exclude` > `select` moved into
#: _rules_and_select for itemset_topk_rows to share) lowered it
MASKED_TOPK_ROWS_PIN = {
    "jax": "0.9.0", "chars": 18743,
    "sha256": "d56912824b2d9ac50b44d4b2bf9659f5"
              "de66038383dc36735faa00d6665e051d"}


def test_masked_topk_rows_lowers_to_the_pinned_text():
    """The e-commerce engine's program is what it was, text for text,
    after its rule stages became the function the similar-product
    program calls too: the e-commerce cell's trace metrics key on this
    module, and its replies are held bit for bit. Lowering text depends
    on the jax that lowers: under another one, take the pin again from
    that commit before trusting a difference."""
    import hashlib

    import jax
    import jax.numpy as jnp

    if jax.__version__ != MASKED_TOPK_ROWS_PIN["jax"]:
        pytest.skip(f"pinned under jax {MASKED_TOPK_ROWS_PIN['jax']}")
    S = jax.ShapeDtypeStruct
    b, n_users, n, r, w, E = 16, 3000, 50_000, 32, 2, 128
    text = topk.masked_topk_rows.lower(
        S((n_users, r), jnp.float32), S((n, r), jnp.float32),
        S((w, n), jnp.uint32), S((n,), jnp.bool_), S((b,), jnp.int32),
        S((b, w), jnp.uint32), S((b, E), jnp.int32), k=10).as_text()
    assert "jit_masked_topk_rows" in text
    assert len(text) == MASKED_TOPK_ROWS_PIN["chars"]
    assert hashlib.sha256(text.encode()).hexdigest() \
        == MASKED_TOPK_ROWS_PIN["sha256"]


def test_topk_for_users_tie_breaks_lowest_index():
    """Equal scores break by LOWEST item index (stable_topk): the
    contract the sharded serving merge reproduces bit-for-bit."""
    U = np.eye(2, dtype=np.float32)
    # items 1, 3, 4 score identically for user 0; 0 and 2 for user 1
    V = np.array([[0.0, 1.0], [2.0, 0.0], [0.0, 1.0],
                  [2.0, 0.0], [2.0, 0.0]], dtype=np.float32)
    vals, idx = topk.topk_for_users(U, V, np.array([0, 1], np.int32), k=4)
    np.testing.assert_array_equal(np.asarray(idx)[0], [1, 3, 4, 0])
    np.testing.assert_array_equal(np.asarray(idx)[1], [0, 2, 1, 3])
    np.testing.assert_allclose(np.asarray(vals)[0], [2, 2, 2, 0])


def test_topk_for_user_tie_breaks_lowest_index():
    U = np.eye(2, dtype=np.float32)
    V = np.array([[3.0, 0], [1.0, 0], [3.0, 0]], dtype=np.float32)
    _vals, idx = topk.topk_for_user(U, V, np.int32(0), k=3)
    np.testing.assert_array_equal(np.asarray(idx), [0, 2, 1])


def test_stable_topk_total_tie_is_iota():
    scores = np.zeros((3, 17), dtype=np.float32)
    vals, idx = topk.stable_topk(scores, 5)
    np.testing.assert_array_equal(np.asarray(idx),
                                  np.tile(np.arange(5), (3, 1)))
    assert np.asarray(vals).shape == (3, 5)


def test_host_topk_boundary_ties_lowest_index():
    """argpartition's selection at the k-th-value boundary is arbitrary
    among tied entries; host_topk must still pick (and order) the
    LOWEST indices — the same rule as stable_topk."""
    scores = np.array([2.0, 1.0, 2.0, 2.0, 0.5, 1.0], dtype=np.float32)
    vals, idx = topk.host_topk(scores, 4)
    np.testing.assert_array_equal(idx, [0, 2, 3, 1])
    np.testing.assert_allclose(vals, [2, 2, 2, 1])
    # all-equal scores: exactly the k lowest indices, in order
    ties = np.full(50, 7.0, dtype=np.float32)
    _v, i = topk.host_topk(ties, 5)
    np.testing.assert_array_equal(i, np.arange(5))
    # ties below the boundary don't disturb the strict head
    scores2 = np.array([9.0, 3.0, 3.0, 8.0, 3.0], dtype=np.float32)
    _v, i2 = topk.host_topk(scores2, 3)
    np.testing.assert_array_equal(i2, [0, 3, 1])


def test_host_masked_topk_batch_deterministic_ties():
    """The batched host kernel (per-row host_topk) breaks ties by
    lowest index with each query's own k."""
    factors = np.array([[1.0], [1.0], [2.0], [1.0]], dtype=np.float32)
    queries = np.array([[1.0], [1.0]], dtype=np.float32)
    masks = [np.ones(4, bool), np.array([True, True, False, True])]
    rows = topk.host_masked_topk_batch(factors, queries, masks, [3, 3])
    np.testing.assert_array_equal(rows[0][1], [2, 0, 1])
    np.testing.assert_array_equal(rows[1][1], [0, 1, 3])


def test_host_topk_matches_device_stable_topk():
    """Host and device kernels agree on selection AND order for data
    with engineered duplicates (low-bit float noise excluded by
    construction: scores are exact)."""
    rng = np.random.default_rng(7)
    scores = rng.integers(-5, 5, size=64).astype(np.float32)
    hv, hi = topk.host_topk(scores, 10)
    dv, di = topk.stable_topk(scores, 10)
    np.testing.assert_array_equal(hi, np.asarray(di))
    np.testing.assert_array_equal(hv, np.asarray(dv))


def test_host_topk_nonpositive_k_returns_empty():
    """A negative num from request JSON must not return ~all entries
    (negative argpartition slice keeps n+k elements)."""
    import numpy as np
    from predictionio_tpu.ops.topk import host_topk
    scores = np.array([3.0, 1.0, 2.0])
    for k in (0, -1, -3):
        vals, idx = host_topk(scores, k)
        assert vals.size == 0 and idx.size == 0


# ---------------------------------------------------------------------------
# the two-stage selection against the whole-row two-key sort, bit for bit
# ---------------------------------------------------------------------------

def _whole_row(scores, k):
    """stable_topk as it was before the two stages: one two-key sort of
    the whole row. The reference every chunked case is held to."""
    import jax.numpy as jnp
    from jax import lax
    scores = jnp.asarray(scores)
    idx = lax.broadcasted_iota(jnp.int32, scores.shape, scores.ndim - 1)
    return topk._sort_topk(scores, idx, k)


def _same_bits(got, want):
    gv, gi = (np.asarray(x) for x in got)
    wv, wi = (np.asarray(x) for x in want)
    np.testing.assert_array_equal(gi, wi)
    # values bit for bit (so -0.0 is not 0.0), any NaN standing for NaN
    nan = np.isnan(wv)
    np.testing.assert_array_equal(np.isnan(gv), nan)
    np.testing.assert_array_equal(gv.view(np.int32)[~nan],
                                  wv.view(np.int32)[~nan])


#: (b, n, k, L): n a multiple of L and not; C = n // L exactly at the
#: threshold 2 (k + 1), one item under it, and at or under k; every
#: serving bucket; L under a lane, one lane (128), several lanes (512)
_SHAPES = [
    (1, 96, 1, 8),          # n = 12 L
    (4, 100, 1, 8),         # ragged: 12 chunks and 4 items
    (16, 176, 10, 8),       # C = 22 = 2 (k + 1): the threshold
    (16, 175, 10, 8),       # one item under it: the plan says sort
    (64, 1003, 10, 8),
    (4, 2100, 64, 16),      # k = 64, C = 131
    (4, 700, 64, 16),       # k >= C = 43: nothing to discard
    (1, 4173, 10, 128),     # one lane a chunk
    (2, 12005, 10, 512),    # four lanes a chunk, the serving L
]


def _fill(kind, b, n, k, L, rng):
    s = rng.normal(size=(b, n)).astype(np.float32)
    top = np.float32(9.0)
    if kind == "random":
        pass
    elif kind == "few_values":            # ties everywhere
        s = rng.integers(-3, 3, size=(b, n)).astype(np.float32)
    elif kind == "ties_inside_chunk":
        s[:, L + 1:L + 4] = top
    elif kind == "ties_across_boundary":
        s[:, 2 * L - 2:2 * L + 2] = top
    elif kind == "tie_at_kth_place":
        # k - 1 clear winners, then one value held by items of many
        # chunks: the k-th reply is the lowest index among them
        s[:, 3:3 + 5 * (k - 1):5] = top
        s[:, n // 2::L // 2 + 1] = np.float32(5.0)
    elif kind == "ties_in_tail":
        s[:, n - 3:] = top
        s[:, L:L + 2] = top
    elif kind == "neg_inf_masked":        # ops/quant.py's layout padding
        s[:, n - n // 3:] = topk.NEG_INF
        s[0, :] = topk.NEG_INF
    elif kind == "infinities":
        s[:, 5] = np.inf
        s[:, n - 1] = np.inf
        s[:, L:3 * L] = -np.inf
        s[-1, :] = -np.inf
    elif kind == "some_nan":
        s[:, 0:2 * L + 3] = np.nan        # whole chunks and part of one
        s[:, n - 2] = np.nan
        s[0, :] = np.nan                  # fewer than k left in row 0:
        s[0, [n // 2, n - 1]] = [1.0, -np.inf]   # NaN fills the reply
    elif kind == "all_nan":
        s[:] = np.nan
    elif kind == "signed_zeros":
        s[:] = 0.0
        s[:, 1::3] = -0.0
    else:
        raise AssertionError(kind)
    return s


_FILLS = ["random", "few_values", "ties_inside_chunk",
          "ties_across_boundary", "tie_at_kth_place", "ties_in_tail",
          "neg_inf_masked", "infinities", "some_nan", "all_nan",
          "signed_zeros"]



@pytest.mark.parametrize("kind", _FILLS)
@pytest.mark.parametrize("b,n,k,L", _SHAPES)
def test_chunked_topk_is_the_whole_row_sort(b, n, k, L, kind):
    """Values and indices, ties and non-finite scores included. The
    shape test alone decides which of the two runs, so wherever the two
    stages CAN run (C > k) they are held to the sort, on both sides of
    the threshold; where they cannot, the plan must say sort."""
    rng = np.random.default_rng(hash((b, n, k, L)) % 2 ** 32)
    scores = _fill(kind, b, n, k, L, rng)
    C = n // L
    plan = topk.chunk_plan(n, k, chunk=L)
    assert plan == ((L, C) if C >= 2 * (k + 1) else None)
    want = _whole_row(scores, k)
    if kind == "all_nan":
        # the serving layer's non-finite gate must still see them
        assert np.isnan(np.asarray(want[0])).all()
    if C > k:
        _same_bits(topk._chunked_topk(np.asarray(scores), k, L, C), want)
    else:
        assert plan is None


@pytest.mark.parametrize("n,chunked", [
    (2 * 11 * topk.CHUNK, True),          # k = 10: the threshold itself
    (2 * 11 * topk.CHUNK - 1, False),
    (26_744, True),                       # the ML-20M catalog
    (topk.CHUNK * 10, False),
])
def test_stable_topk_takes_the_branch_the_shape_test_names(n, chunked):
    """The public function at the serving L, 1-D (the inline path) and
    2-D (a bucket): the same bits from whichever branch the plan names,
    and `GET /`'s name for it from the same test."""
    k = 10
    rng = np.random.default_rng(n)
    scores = rng.integers(-50, 50, size=(4, n)).astype(np.float32)
    assert (topk.chunk_plan(n, k) is not None) == chunked
    assert topk.selection_name(n, k) == (
        f"chunked L={topk.CHUNK} C={n // topk.CHUNK}" if chunked else "sort")
    _same_bits(topk.stable_topk(scores, k), _whole_row(scores, k))
    _same_bits(topk.stable_topk(scores[0], k), _whole_row(scores[0], k))


def test_cell_shape_lowers_without_a_whole_row_sort():
    """`topk_for_users` at the benchmark cell's shape (6,643,669 x 128
    users, 2,441,053 x 128 items, bucket 64, k 10), lowered from shapes
    alone: no sort, and no iota, as long as the catalog. The 2.44 M-key
    sort was 99.2 % of the device's time there (ledger, PR 27)."""
    import re

    import jax
    S = jax.ShapeDtypeStruct
    n_items = 2_441_053
    text = topk.topk_for_users.lower(
        S((6_643_669, 128), np.float32), S((n_items, 128), np.float32),
        S((64,), np.int32), k=10).as_text()
    sorts = re.findall(r"stablehlo\.sort.*?\}\) : \((.*?)\) ->", text, re.S)
    L, C = topk.chunk_plan(n_items, 10)
    assert sorts == [
        f"tensor<64x{C}xf32>, tensor<64x{C}xi32>",
        f"tensor<64x{10 * L + n_items - C * L}xf32>, "
        f"tensor<64x{10 * L + n_items - C * L}xi32>"]
    assert not re.search(rf"stablehlo\.iota[^\n]*x{n_items}x", text)
    # the scores are still one materialised (64, n_items) matrix that
    # the selection reads: nothing recomputes the winners' dot products
    assert len(re.findall(r"stablehlo\.dot_general", text)) == 1
