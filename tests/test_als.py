"""ALS kernel correctness (parity target: MLlib ALS as used by the
recommendation template, ALSAlgorithm.scala:50-94)."""

import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import als


def make_problem(n_u=30, n_i=20, rank=3, density=0.7, seed=0):
    rng = np.random.default_rng(seed)
    U0 = rng.normal(size=(n_u, rank))
    V0 = rng.normal(size=(n_i, rank))
    R = U0 @ V0.T
    mask = rng.random((n_u, n_i)) < density
    ui, ii = np.nonzero(mask)
    return ui.astype(np.int32), ii.astype(np.int32), R[ui, ii].astype(np.float32)


def test_prepare_ratings_layout():
    ui = np.array([2, 0, 1, 0], dtype=np.int32)
    ii = np.array([1, 0, 1, 2], dtype=np.int32)
    r = np.array([1.0, 2.0, 3.0, 4.0], dtype=np.float32)
    data = als.prepare_ratings(ui, ii, r, n_users=3, n_items=3, chunk=8)
    bu = data.by_user
    # sorted by user, padded to 8 with self_idx == n_users
    assert bu.self_idx.shape == (8,)
    np.testing.assert_array_equal(bu.self_idx[:4], [0, 0, 1, 2])
    np.testing.assert_array_equal(bu.self_idx[4:], [3, 3, 3, 3])
    np.testing.assert_array_equal(bu.counts, [2, 1, 1])
    np.testing.assert_array_equal(bu.rating[4:], 0.0)
    bi = data.by_item
    np.testing.assert_array_equal(bi.self_idx[:4], [0, 1, 1, 2])
    np.testing.assert_array_equal(bi.counts, [1, 2, 1])
    assert data.nnz == 4


def test_half_step_solves_normal_equations():
    """One U half-step must equal the per-user ridge solution (numpy)."""
    ui, ii, vals = make_problem()
    n_u, n_i = 30, 20
    rank, lam = 3, 0.1
    data = als.prepare_ratings(ui, ii, vals, n_u, n_i, chunk=64)
    rng = np.random.default_rng(1)
    V = rng.normal(size=(n_i, rank)).astype(np.float32)

    bu = data.by_user
    import jax.numpy as jnp
    U = als._half_step_explicit(
        jnp.asarray(V), jnp.asarray(bu.self_idx), jnp.asarray(bu.other_idx),
        jnp.asarray(bu.rating), jnp.asarray(bu.counts), n_u, lam,
        chunk=64, reg_scaling="count")
    U = np.asarray(U)

    for u in range(n_u):
        sel = ui == u
        Vu = V[ii[sel]]
        A = Vu.T @ Vu + lam * sel.sum() * np.eye(rank)
        b = Vu.T @ vals[sel]
        expected = np.linalg.solve(A + 1e-8 * np.eye(rank), b)
        np.testing.assert_allclose(U[u], expected, rtol=2e-3, atol=2e-3)


def test_train_recovers_low_rank_matrix():
    ui, ii, vals = make_problem(n_u=50, n_i=35, rank=4, seed=2)
    data = als.prepare_ratings(ui, ii, vals, 50, 35, chunk=256)
    U, V = als.train_explicit(data, rank=4, iterations=15, lambda_=1e-6,
                              chunk=256)
    pred = np.sum(np.asarray(U)[ui] * np.asarray(V)[ii], axis=1)
    assert np.sqrt(np.mean((pred - vals) ** 2)) < 1e-3


def test_train_multiple_chunks_matches_single_chunk():
    ui, ii, vals = make_problem(seed=3)
    data1 = als.prepare_ratings(ui, ii, vals, 30, 20, chunk=1 << 12)
    data2 = als.prepare_ratings(ui, ii, vals, 30, 20, chunk=32)
    U1, V1 = als.train_explicit(data1, rank=3, iterations=3, lambda_=0.05)
    U2, V2 = als.train_explicit(data2, rank=3, iterations=3, lambda_=0.05,
                                chunk=32)
    np.testing.assert_allclose(np.asarray(U1), np.asarray(U2), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(V1), np.asarray(V2), rtol=1e-4,
                               atol=1e-5)


def test_implicit_half_step_matches_dense_hkv():
    """Implicit U half-step vs dense Hu-Koren-Volinsky solution."""
    rng = np.random.default_rng(4)
    n_u, n_i, rank, lam, alpha = 12, 9, 3, 0.1, 5.0
    counts_mat = (rng.random((n_u, n_i)) < 0.5) * rng.integers(1, 6, (n_u, n_i))
    ui, ii = np.nonzero(counts_mat)
    vals = counts_mat[ui, ii].astype(np.float32)
    data = als.prepare_ratings(ui.astype(np.int32), ii.astype(np.int32),
                               vals, n_u, n_i, chunk=32)
    V = rng.normal(size=(n_i, rank)).astype(np.float32)

    import jax.numpy as jnp
    bu = data.by_user
    U = als._half_step_implicit(
        jnp.asarray(V), jnp.asarray(bu.self_idx), jnp.asarray(bu.other_idx),
        jnp.asarray(bu.rating), jnp.asarray(bu.counts), n_u, lam, alpha,
        chunk=32, reg_scaling="count")
    U = np.asarray(U)

    YtY = V.T @ V
    for u in range(n_u):
        sel = ui == u
        Vu = V[ii[sel]]
        Cu = alpha * vals[sel]
        A = YtY + Vu.T @ (Cu[:, None] * Vu) + lam * sel.sum() * np.eye(rank)
        b = Vu.T @ (1.0 + Cu)
        expected = np.linalg.solve(A + 1e-8 * np.eye(rank), b)
        np.testing.assert_allclose(U[u], expected, rtol=2e-3, atol=2e-3)


def test_train_implicit_ranks_preferred_items_higher():
    rng = np.random.default_rng(5)
    n_u, n_i = 20, 15
    # users 0-9 view items 0-7 heavily; users 10-19 view items 8-14
    ui, ii, vals = [], [], []
    for u in range(n_u):
        items = range(0, 8) if u < 10 else range(8, 15)
        for i in items:
            if rng.random() < 0.8:
                ui.append(u); ii.append(i); vals.append(rng.integers(1, 5))
    data = als.prepare_ratings(
        np.array(ui, np.int32), np.array(ii, np.int32),
        np.array(vals, np.float32), n_u, n_i, chunk=64)
    U, V = als.train_implicit(data, rank=4, iterations=10, lambda_=0.01,
                              alpha=10.0, chunk=64)
    scores = np.asarray(U) @ np.asarray(V).T
    # group-A user scores group-A items above group-B items on average
    assert scores[0, :8].mean() > scores[0, 8:].mean()
    assert scores[15, 8:].mean() > scores[15, :8].mean()


def test_zero_rating_user_stays_finite():
    # user 2 has no ratings at all
    ui = np.array([0, 1], dtype=np.int32)
    ii = np.array([0, 1], dtype=np.int32)
    vals = np.array([1.0, 2.0], dtype=np.float32)
    data = als.prepare_ratings(ui, ii, vals, n_users=3, n_items=2, chunk=8)
    U, V = als.train_explicit(data, rank=2, iterations=2, lambda_=0.1, chunk=8)
    assert np.isfinite(np.asarray(U)).all()
    np.testing.assert_allclose(np.asarray(U)[2], 0.0, atol=1e-6)


def test_rmse_helper():
    ui, ii, vals = make_problem(seed=6)
    data = als.prepare_ratings(ui, ii, vals, 30, 20, chunk=64)
    U, V = als.train_explicit(data, rank=3, iterations=10, lambda_=1e-5,
                              chunk=64)
    bu = data.by_user
    mask = (bu.self_idx < 30).astype(np.float32)
    import jax.numpy as jnp
    err = als.rmse(U, V, jnp.asarray(np.clip(bu.self_idx, 0, 29)),
                   jnp.asarray(bu.other_idx), jnp.asarray(bu.rating),
                   jnp.asarray(mask), chunk=64)
    assert float(err) < 0.01


@pytest.mark.parametrize("implicit", [False, True])
def test_csrb_kernel_matches_scan_kernel(implicit):
    """The csrb (mini-block wide-gather) and scan (per-entry segment-sum)
    kernels are the same math; full trains must agree to float tolerance."""
    ui, ii, vals = make_problem(n_u=40, n_i=25, rank=4, density=0.4, seed=7)
    if implicit:
        vals = np.abs(vals) + 0.5
    data = als.prepare_ratings(ui, ii, vals, 40, 25, chunk=64)
    train = als.train_implicit if implicit else als.train_explicit
    U1, V1 = train(data, rank=4, iterations=4, lambda_=0.05, seed=11,
                   chunk=64, kernel="scan")
    U2, V2 = train(data, rank=4, iterations=4, lambda_=0.05, seed=11,
                   chunk=64, kernel="csrb")
    np.testing.assert_allclose(np.asarray(U1), np.asarray(U2),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(V1), np.asarray(V2),
                               rtol=2e-4, atol=2e-5)


def test_csrb_layout_roundtrip():
    """Every real entry appears exactly once in the csrb layout, in a
    mini-block owned by its row; all other slots are zero-weight."""
    ui, ii, vals = make_problem(n_u=17, n_i=9, rank=2, density=0.5, seed=3)
    data = als.prepare_ratings(ui, ii, vals, 17, 9, chunk=32)
    bu = data.by_user
    b = 8
    n_mb, _ = als._csrb_plan(data.nnz, 17, b, 32)
    oi, rat, pres, seg = als.csrb_layout(
        np.asarray(bu.other_idx), np.asarray(bu.rating),
        np.asarray(bu.counts), 17, b, n_mb)
    oi, rat, pres, seg = (np.asarray(x) for x in (oi, rat, pres, seg))
    assert pres.sum() == data.nnz
    rows = np.repeat(seg, b)
    got = sorted(zip(rows[pres > 0].tolist(), oi[pres > 0].tolist(),
                     rat[pres > 0].tolist()))
    want = sorted(zip(ui.tolist(), ii.tolist(), vals.tolist()))
    assert got == want
    # padding slots carry zero weight and a nondecreasing segment map
    assert np.all(np.diff(seg) >= 0)
    assert np.all(rat[pres == 0] == 0.0)


def test_ship_coo_narrow_dtypes_lossless():
    """Narrow-dtype device shipping (uint16 ids / int8 half-star codes)
    must be exactly lossless, and must fall back to full width for big
    vocabularies or non-half-step ratings (incl. signed implicit weights)."""
    rng = np.random.default_rng(0)
    n = 1000
    u = rng.integers(0, 70_000, n).astype(np.int32)     # > uint16 range
    i = rng.integers(0, 30_000, n).astype(np.int32)     # fits uint16
    r = (rng.integers(-10, 11, n) / 2.0).astype(np.float32)  # signed halves
    ju, ji, jr = als._ship_coo(u, i, r, 70_000, 30_000)
    np.testing.assert_array_equal(np.asarray(ju), u)
    np.testing.assert_array_equal(np.asarray(ji), i)
    np.testing.assert_array_equal(np.asarray(jr), r)
    # arbitrary floats fall back untouched
    r2 = rng.uniform(0, 5, n).astype(np.float32)
    _ju, _ji, jr2 = als._ship_coo(u, i, r2, 70_000, 30_000)
    np.testing.assert_array_equal(np.asarray(jr2), r2)
    # boundary: id exactly 65535 fits, 65536-vocab still narrow
    ub = np.array([0, 65_535], np.int32)
    jub, _, _ = als._ship_coo(ub, ub, np.ones(2, np.float32), 1 << 16,
                              1 << 16)
    np.testing.assert_array_equal(np.asarray(jub), ub)


def test_solve_factors_clamps_indefinite_rows():
    """Round-4 postmortem regression: kernel rounding pushed per-row Grams
    slightly indefinite and the unpivoted sweep turned a near-zero Schur
    pivot into inf -> model-wide NaN two iterations later. The solve must
    (a) stay exact on clean SPD systems and (b) return BOUNDED finite
    solutions on indefinite ones (sign-preserving pivot magnitude floor)."""
    rng = np.random.default_rng(0)
    r, n = 6, 64
    M = rng.normal(0, 1, (n, r, r)).astype(np.float32)
    A = np.einsum("nij,nkj->nik", M, M)              # SPD batch
    # poison a few rows: rank-1 negative update far beyond the ridge
    for row in (3, 17, 40):
        v = rng.normal(0, 1, r).astype(np.float32)
        A[row] -= 3.0 * np.linalg.norm(A[row]) * np.outer(v, v) \
            / np.dot(v, v)
    b = rng.normal(0, 1, (n, r)).astype(np.float32)
    reg = np.full(n, 0.05, np.float32)
    x = np.asarray(als.solve_factors(
        jnp.asarray(A), jnp.asarray(b), jnp.asarray(reg)))
    assert np.isfinite(x).all()
    clean = np.setdiff1d(np.arange(n), [3, 17, 40])
    ref = np.linalg.solve(
        A[clean] + reg[clean, None, None] * np.eye(r),
        b[clean][..., None])[..., 0]
    np.testing.assert_allclose(x[clean], ref, rtol=2e-3, atol=2e-3)
    # bounded: the floor caps the inverse around 2/reg per sweep step
    assert np.abs(x).max() < np.abs(b).max() * (2 / 0.05) * r


def test_split_hilo_dense_path_precision():
    """Round-4 postmortem regression: single-bf16 quantization of
    X = [v(x)v | v] left ~4e-3 relative Gram error, which exceeded the
    ridge once factors grew to |v|~50 at ML-20M. The split hi/lo pair
    must keep the dense-hot Gram within ~1e-4 relative of the f32
    reference at exactly those magnitudes (single-bf16 fails this by two
    orders)."""
    rng = np.random.default_rng(1)
    n_u, K, r = 256, 32, 8
    V_hot = (rng.normal(0, 1, (K, r)) * 50).astype(np.float32)
    D = np.zeros((n_u, 2 * K), np.float32)
    D[:, :K] = rng.integers(0, 3, (n_u, K))          # counts
    D[:, K:] = D[:, :K] * rng.uniform(0.5, 5.0, (n_u, K))
    X_hot = np.asarray(als._expand_X(jnp.asarray(V_hot), r, jnp.float32))
    AB = np.asarray(als._dense_hot_user(
        jnp.asarray(D, dtype=als._HYBRID_DTYPE), jnp.asarray(X_hot), K, r))
    ref_gram = D[:, :K] @ X_hot[:, :r * r]
    err = np.abs(AB[:, :r * r] - ref_gram).max()
    scale = np.abs(ref_gram).max()
    assert err / scale < 1e-4, f"dense gram rel err {err/scale:.2e}"


@pytest.mark.parametrize("implicit", [False, True])
def test_hybrid_kernel_matches_csrb(implicit, monkeypatch):
    """The hybrid (dense-hot + csrb-tail) kernel uses bf16 for the hot
    matmuls, so parity is at model level: ~1% Frobenius on factors and
    equivalent reconstruction RMSE vs the f32 csrb kernel. The threshold
    is lowered so the bf16 dense path is ACTUALLY exercised (avg user
    count here is ~24; the default 64 would zero out D entirely)."""
    monkeypatch.setenv("PIO_ALS_HOT_K", "64")
    monkeypatch.setenv("PIO_ALS_DENSE_MIN_COUNT", "8")
    rng = np.random.default_rng(3)
    n_u, n_i, nnz = 500, 300, 12000
    item_w = 1.0 / np.arange(1, n_i + 1) ** 0.8
    ii = np.searchsorted(np.cumsum(item_w / item_w.sum()),
                         rng.random(nnz)).astype(np.int32)
    np.clip(ii, 0, n_i - 1, out=ii)
    ui = rng.integers(0, n_u, nnz).astype(np.int32)
    vals = np.clip(np.round(rng.uniform(0.5, 5.0, nnz) * 2) / 2,
                   0.5, 5.0).astype(np.float32)
    data = als.prepare_ratings(ui, ii, vals, n_u, n_i, chunk=1024)
    train = als.train_implicit if implicit else als.train_explicit
    U1, V1 = train(data, rank=6, iterations=4, lambda_=0.05, seed=7,
                   chunk=1024, kernel="csrb")
    U2, V2 = train(data, rank=6, iterations=4, lambda_=0.05, seed=7,
                   chunk=1024, kernel="hybrid")
    U1, V1, U2, V2 = map(np.asarray, (U1, V1, U2, V2))
    assert np.linalg.norm(U1 - U2) / np.linalg.norm(U1) < 0.02
    assert np.linalg.norm(V1 - V2) / np.linalg.norm(V1) < 0.02
    if not implicit:
        p1 = (U1 @ V1.T)[ui, ii]
        p2 = (U2 @ V2.T)[ui, ii]
        r1 = float(np.sqrt(np.mean((p1 - vals) ** 2)))
        r2 = float(np.sqrt(np.mean((p2 - vals) ** 2)))
        assert abs(r1 - r2) < 0.01 * max(r1, 1e-6)


def test_hybrid_kernel_counts_repeated_pairs(monkeypatch):
    """A (user, item) pair may repeat — a re-rating in the event log;
    thousands of times for the heaviest pairs `pio train --synthetic`
    draws. The dense-hot block must count every repeat: accumulated in
    bf16 a running count stops at 256 while the rating sum keeps
    growing, and the hybrid model of such data came out worse than the
    global mean (training RMSE 1.54 against 1.03 at 20 M events)."""
    from predictionio_tpu.data import synthetic

    monkeypatch.setenv("PIO_ALS_HOT_K", "64")
    n_u, n_i = 300, 200
    src = synthetic.chunk_source(300_000, seed=7, n_users=n_u, n_items=n_i)
    ui, ii, vals = src.chunk_codes(0)
    pair = ui.astype(np.int64) * n_i + ii
    assert np.bincount(pair).max() > 1000       # far past bf16's 256
    data = als.prepare_ratings(ui, ii, vals, n_u, n_i, chunk=1 << 14)

    def rmse(kernel):
        U, V = map(np.asarray, als.train_explicit(
            data, rank=6, iterations=4, lambda_=0.01, seed=3,
            chunk=1 << 14, kernel=kernel))
        pred = np.einsum("nr,nr->n", U[ui], V[ii])
        return float(np.sqrt(np.mean((pred - vals) ** 2)))

    exact, hybrid = rmse("csrb"), rmse("hybrid")
    assert exact < float(vals.std())            # beats the global mean
    assert abs(hybrid - exact) < 0.01 * exact


def test_hybrid_small_item_set_falls_back(monkeypatch):
    """n_items < 2K: hybrid silently uses the csrb path (bit-identical)."""
    monkeypatch.setenv("PIO_ALS_HOT_K", "4096")
    ui, ii, vals = make_problem(n_u=40, n_i=25, rank=4, density=0.4, seed=7)
    data = als.prepare_ratings(ui, ii, vals, 40, 25, chunk=64)
    U1, V1 = als.train_explicit(data, rank=4, iterations=3, lambda_=0.05,
                                seed=11, chunk=64, kernel="csrb")
    U2, V2 = als.train_explicit(data, rank=4, iterations=3, lambda_=0.05,
                                seed=11, chunk=64, kernel="hybrid")
    np.testing.assert_array_equal(np.asarray(U1), np.asarray(U2))
    np.testing.assert_array_equal(np.asarray(V1), np.asarray(V2))


def test_hybrid_below_floor_hot_items_stay_on_tail(monkeypatch):
    """Tail-budget regression (review r4): candidate hot items whose count
    is below the dense floor must be BUDGETED into the tail, not silently
    dropped. Flat popularity + dense-eligible users exercises it."""
    monkeypatch.setenv("PIO_ALS_HOT_K", "8")
    rng = np.random.default_rng(1)
    n_u, n_i = 5, 20
    ui = np.repeat(np.arange(n_u, dtype=np.int32), 100)      # 100 each >= 64
    ii = rng.integers(0, n_i, 500).astype(np.int32)          # ~25/item < 64
    vals = rng.uniform(0.5, 5.0, 500).astype(np.float32)
    data = als.prepare_ratings(ui, ii, vals, n_u, n_i, chunk=64)
    U1, V1 = als.train_explicit(data, rank=3, iterations=3, lambda_=0.05,
                                seed=5, chunk=64, kernel="csrb")
    U2, V2 = als.train_explicit(data, rank=3, iterations=3, lambda_=0.05,
                                seed=5, chunk=64, kernel="hybrid")
    np.testing.assert_allclose(np.asarray(U1), np.asarray(U2),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(V1), np.asarray(V2),
                               rtol=1e-4, atol=1e-5)


def test_layout_cache_reused_across_variants(memory_storage):
    """Two trains over the SAME TrainingData (the FastEval grid shape)
    compute the COO layout once; a different TrainingData gets its own."""
    from unittest import mock

    from predictionio_tpu.models.recommendation.als_algorithm import (
        ALSAlgorithm, ALSAlgorithmParams)
    from predictionio_tpu.models.recommendation.data_source import (
        TrainingData)
    from predictionio_tpu.models.recommendation.preparator import (
        PreparedData)
    from predictionio_tpu.data.bimap import BiMap

    rng = np.random.default_rng(0)
    n = 500
    td = TrainingData(
        user_idx=rng.integers(0, 40, n).astype(np.int32),
        item_idx=rng.integers(0, 30, n).astype(np.int32),
        rating=rng.uniform(1, 5, n).astype(np.float32),
        user_vocab=BiMap.string_int(f"u{k}" for k in range(40)),
        item_vocab=BiMap.string_int(f"i{k}" for k in range(30)))
    pd = PreparedData(ratings=td)
    real = als.prepare_ratings
    with mock.patch.object(als, "prepare_ratings",
                           side_effect=real) as spy:
        ALSAlgorithm(ALSAlgorithmParams(rank=4, numIterations=2,
                                        seed=1)).train(None, pd)
        ALSAlgorithm(ALSAlgorithmParams(rank=6, numIterations=2,
                                        seed=2)).train(None, pd)
        assert spy.call_count == 1          # second variant reused layout
    m1 = ALSAlgorithm(ALSAlgorithmParams(rank=4, numIterations=3,
                                         seed=3)).train(None, pd)
    assert m1.user_factors.shape == (40, 4)


def test_batch_predict_clamps_nonpositive_num(memory_storage):
    """Eval-path parity with predict(): num <= 0 yields empty results, and
    an all-nonpositive batch must not reach lax.top_k with negative k."""
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.models.recommendation.als_algorithm import (
        ALSAlgorithm, ALSAlgorithmParams)
    from predictionio_tpu.models.recommendation.data_source import (
        TrainingData)
    from predictionio_tpu.models.recommendation.engine import Query
    from predictionio_tpu.models.recommendation.preparator import (
        PreparedData)

    rng = np.random.default_rng(1)
    n = 300
    td = TrainingData(
        user_idx=rng.integers(0, 20, n).astype(np.int32),
        item_idx=rng.integers(0, 15, n).astype(np.int32),
        rating=rng.uniform(1, 5, n).astype(np.float32),
        user_vocab=BiMap.string_int(f"u{k}" for k in range(20)),
        item_vocab=BiMap.string_int(f"i{k}" for k in range(15)))
    algo = ALSAlgorithm(ALSAlgorithmParams(rank=4, numIterations=2, seed=1))
    model = algo.train(None, PreparedData(ratings=td))
    res = dict(algo.batch_predict(model, [
        (0, Query(user="u1", num=-1)),
        (1, Query(user="u2", num=3)),
        (2, Query(user="u3", num=0))]))
    assert res[0].itemScores == () and res[2].itemScores == ()
    assert len(res[1].itemScores) == 3
    # all-nonpositive batch: no device call, all empty
    res2 = dict(algo.batch_predict(model, [
        (0, Query(user="u1", num=0)), (1, Query(user="u2", num=-5))]))
    assert all(r.itemScores == () for r in res2.values())


@pytest.mark.parametrize("kernel", ["csrb", "scan"])
def test_implicit_cold_rows_do_not_poison_model(kernel):
    """An item (or user) with ZERO interactions must solve to a zero row,
    not NaN: with the bare 1e-8 ridge (invisible in f32 next to YtY) the
    cold row's unpivoted solve produced 0/0, and one NaN row made the
    next iteration's YtY — and the entire model — NaN."""
    u = np.array([0, 0, 1, 1, 2], dtype=np.int32)
    i = np.array([0, 1, 0, 1, 2], dtype=np.int32)
    r = np.ones(5, dtype=np.float32)
    # item 3 and user 3 exist in the vocab but have no interactions
    data = als.prepare_ratings(u, i, r, n_users=4, n_items=4)
    U, V = als.train_implicit(data, rank=4, iterations=10, lambda_=0.01,
                              alpha=1.0, seed=3, kernel=kernel)
    U, V = np.asarray(U), np.asarray(V)
    assert np.isfinite(U).all() and np.isfinite(V).all()
    np.testing.assert_allclose(U[3], 0.0)
    np.testing.assert_allclose(V[3], 0.0)
    # trained rows still reconstruct the signal
    pred = np.sum(U[u] * V[i], axis=1)
    assert (pred > 0).all()


class TestPallasSolver:
    """ops/solve_pallas.py: the VMEM Gauss-Jordan batch solver."""

    @staticmethod
    def systems(n=700, r=10, seed=0):
        rng = np.random.default_rng(seed)
        F = rng.normal(size=(n, r, 3)).astype(np.float32)
        A = np.einsum("nri,nsi->nrs", F, F)     # PSD, rank 3 < r
        b = rng.normal(size=(n, r)).astype(np.float32)
        reg = rng.uniform(0.05, 0.5, n).astype(np.float32)
        return A, b, reg

    def test_matches_xla_gj_interpret(self, monkeypatch):
        """Interpret mode (runs everywhere) must agree with solve_factors
        bit-for-bit at an awkward (non-BN-multiple) batch size."""
        import jax.numpy as jnp
        from predictionio_tpu.ops.solve_pallas import solve_factors_pallas
        monkeypatch.setenv("PIO_ALS_SOLVER", "gj")   # reference path
        A, b, reg = self.systems()
        x_ref = np.asarray(als.solve_factors(
            jnp.asarray(A), jnp.asarray(b), jnp.asarray(reg)))
        x = np.asarray(solve_factors_pallas(
            jnp.asarray(A), jnp.asarray(b), jnp.asarray(reg),
            interpret=True))
        # rank-deficient PSD + small ridge is deliberately marginal, so
        # compare by residual (the solver contract), plus a loose direct
        # comparison
        np.testing.assert_allclose(x, x_ref, rtol=5e-2, atol=5e-3)
        r = A.shape[-1]
        Ar = A + reg[:, None, None] * np.eye(r, dtype=np.float32)[None]
        resid = np.einsum("nrs,ns->nr", Ar, x) - b
        ref_resid = np.einsum("nrs,ns->nr", Ar, x_ref) - b
        assert np.abs(resid).max() < max(2 * np.abs(ref_resid).max(), 1e-3)

    def test_solver_choice_env_and_platform(self, monkeypatch):
        from predictionio_tpu.ops import solve_pallas as sp
        monkeypatch.setenv("PIO_ALS_SOLVER", "gj")
        assert sp.solver_choice() == "gj"
        monkeypatch.setenv("PIO_ALS_SOLVER", "pallas")
        # off-TPU the opt-in downgrades (with a warning) instead of
        # failing to lower; on a real TPU backend it engages
        import jax
        expected = "pallas" if jax.default_backend() == "tpu" else "gj"
        assert sp.solver_choice() == expected
        monkeypatch.delenv("PIO_ALS_SOLVER")
        # default is gj: the pallas solver measured end-to-end neutral
        # (it overlaps other work in the fused loop), so it is opt-in
        assert sp.solver_choice() == "gj"

    def test_env_flip_retraces_cached_trainer(self, monkeypatch):
        """Flipping PIO_ALS_XPAD between same-shape trains must change the
        compiled program (the knobs are trace-time env reads; the tuning
        static arg makes them part of the jit cache key)."""
        monkeypatch.setenv("PIO_ALS_XPAD", "1")
        u = np.array([0, 0, 1, 2], dtype=np.int32)
        i = np.array([0, 1, 1, 0], dtype=np.int32)
        r = np.ones(4, dtype=np.float32)
        data = als.prepare_ratings(u, i, r, 3, 2, chunk=32)
        U1, V1 = als.train_explicit(data, rank=2, iterations=2,
                                    lambda_=0.1, seed=1, chunk=32,
                                    kernel="csrb")
        n_compiled = als._train_csrb_jit._cache_size()
        monkeypatch.setenv("PIO_ALS_XPAD", "0")
        U2, V2 = als.train_explicit(data, rank=2, iterations=2,
                                    lambda_=0.1, seed=1, chunk=32,
                                    kernel="csrb")
        assert als._train_csrb_jit._cache_size() == n_compiled + 1
        np.testing.assert_allclose(np.asarray(U1), np.asarray(U2),
                                   rtol=1e-5, atol=1e-6)
