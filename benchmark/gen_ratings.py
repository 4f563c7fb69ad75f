"""Ratings for the training cells: a fixed bipartite structure, relabelled
and rated from the seed.

The STRUCTURE (which degree-slot user meets which degree-slot item) is
drawn once from a constant, never from `--seed`: every count the trainer
compiles a shape from (entity counts, nnz, per-row cold counts, the hot
block) is then the same multiset for every seed and every job. `--seed`
and the job number decide which label sits on which slot, the planted
factors and the noise, so no two jobs train on the same ratings.

Structure: user degrees are zipf quantiles between `min` and `max` that
sum to nnz exactly. Each user draws its items by systematic sampling with
inclusion probability min(1, t * w_item) (t solved so the probabilities
sum to the user's degree): exactly `degree` DISTINCT items, hot items
capped at "everybody heavy has rated it", as in real logs. Item degrees
are what that gives (their targets are zipf quantiles too); an item that
nobody drew takes one edge from a hot item, so every entity appears.
NumPy only; imports nothing of the program.
"""

import numpy as np

STRUCT_SEED = 20_260_930


def zipf_quantile_degrees(n, total, dmin, dmax, exponent):
    """n integer degrees, descending, summing to `total` exactly:
    d_k = clip(c * (k+1)^-exponent, dmin, dmax), a power law with a
    crowded floor (ML-20M kept users with 20 ratings or more) and a cap,
    the scale c solved by bisection; what flooring took off is given
    back one each to the rows just under the cap."""
    k = np.arange(1, n + 1, dtype=np.float64)
    if not dmin * n <= total <= dmax * n:
        raise ValueError(f"no degrees in [{dmin},{dmax}] x {n} sum to {total}")
    base = k ** (-float(exponent))
    lo, hi = 0.0, float(dmax) * n ** float(exponent) * 4
    for _ in range(200):
        c = 0.5 * (lo + hi)
        if np.clip(c * base, dmin, dmax).sum() > total:
            hi = c
        else:
            lo = c
    d = np.floor(np.clip(lo * base, dmin, dmax)).astype(np.int64)
    short = int(total - d.sum())
    room = np.flatnonzero(d < dmax)
    if short > room.size:
        raise ValueError("degrees cannot be rounded up to the total")
    d[room[:short]] += 1
    assert d.sum() == total and d.min() >= dmin and d.max() <= dmax
    return d


def build_structure(shape, struct_seed=STRUCT_SEED):
    """-> (slot_u, slot_i) int32 arrays of length nnz: a simple bipartite
    graph (no pair twice) on n_users x n_items slots, edges in a fixed
    scrambled order. `shape`: n_users, n_items, nnz, user_degree and
    item_degree {min, max, exponent}."""
    n_users, n_items, nnz = shape["n_users"], shape["n_items"], shape["nnz"]
    rng = np.random.default_rng(struct_seed)
    ud, idg = shape["user_degree"], shape["item_degree"]
    du = zipf_quantile_degrees(n_users, nnz, ud["min"],
                               min(ud["max"], n_items), ud["exponent"])
    w = zipf_quantile_degrees(n_items, nnz, idg["min"],
                              min(idg["max"], n_users), idg["exponent"]
                              ).astype(np.float64)
    # capped inclusion probabilities: pi_i = min(1, t*w_i), sum = d.
    # With w descending, m items are capped where d <= m + S_m / w_m.
    suffix = np.concatenate([np.cumsum(w[::-1])[::-1], [0.0]])
    g = np.arange(n_items) + suffix[:-1] / w
    out_u = np.empty(nnz, np.int32)
    out_i = np.empty(nnz, np.int32)
    pos = 0
    twice = []
    degs, starts, counts = np.unique(-du, return_index=True,
                                     return_counts=True)
    for d, start, cnt in zip(-degs, starts, counts):
        d, start, cnt = int(d), int(start), int(cnt)
        m = int(np.searchsorted(g, d, side="left"))
        t = (d - m) / suffix[m] if m < n_items else 0.0
        pi = np.minimum(1.0, t * w)
        pi[:m] = 1.0
        perm = rng.permutation(n_items)
        cum = np.cumsum(pi[perm])
        pts = rng.random((cnt, 1)) + np.arange(d, dtype=np.float64)
        pts *= cum[-1] / d
        sel = np.minimum(np.searchsorted(cum, pts.ravel(), side="left"),
                         n_items - 1).reshape(cnt, d)
        # one user's points ascend, so a pair drawn twice is adjacent
        # (rounding at a capped item's edge); mended below
        rows, cols = np.nonzero(sel[:, 1:] == sel[:, :-1])
        twice.extend((pos + rows * d + cols + 1).tolist())
        n = cnt * d
        out_i[pos:pos + n] = perm[sel.ravel()]
        out_u[pos:pos + n] = np.repeat(
            np.arange(start, start + cnt, dtype=np.int32), d)
        pos += n
    assert pos == nnz
    _repair(out_u, out_i, du, twice, n_items, rng)
    order = rng.permutation(nnz)
    return out_u[order], out_i[order]


def _repair(u, i, du, twice, n_items, rng):
    """In place: no (user, item) pair twice, no item without an edge.
    Edges lie grouped by user here. A pair drawn twice moves to an item
    that user has not got; an item nobody drew takes one edge off a hot
    item, whose user cannot hold the empty item already."""
    first = np.concatenate([[0], np.cumsum(du)])
    for e in twice:
        usr = int(u[e])
        have = set(i[first[usr]:first[usr + 1]].tolist())
        while True:
            cand = int(rng.integers(0, n_items))
            if cand not in have:
                i[e] = cand
                break
    deg = np.bincount(i, minlength=n_items)
    empty = np.flatnonzero(deg == 0)
    if empty.size:
        hot = np.flatnonzero(deg[i] > 4 * empty.size + 4)
        i[rng.choice(hot, empty.size, replace=False)] = empty
    assert np.bincount(i, minlength=n_items).min() >= 1


def make_ratings(structure, shape, seed, job):
    """One data set for (seed, job): -> (user_idx, item_idx, rating)
    int32, int32, float32. Labels are a seeded permutation of the slots;
    rating = clip(half-star round(mean + scale * p_u.q_i + noise))."""
    slot_u, slot_i = structure
    n_users, n_items = shape["n_users"], shape["n_items"]
    pl = shape["planted"]
    rng = np.random.default_rng([int(seed), int(job), 0x7A7])
    pu = rng.permutation(n_users).astype(np.int32)
    pi = rng.permutation(n_items).astype(np.int32)
    user_idx, item_idx = pu[slot_u], pi[slot_i]
    r = int(pl["rank"])
    P = rng.standard_normal((n_users, r), dtype=np.float32)
    Q = rng.standard_normal((n_items, r), dtype=np.float32)
    noise = rng.standard_normal(user_idx.size, dtype=np.float32)
    rating = np.empty(user_idx.size, np.float32)
    step = 1 << 21
    scale = np.float32(pl["signal"] / np.sqrt(r))
    for s in range(0, user_idx.size, step):
        e = s + step
        dot = np.einsum("nr,nr->n", P[user_idx[s:e]], Q[item_idx[s:e]])
        raw = pl["mean"] + scale * dot + pl["noise"] * noise[s:e]
        rating[s:e] = np.clip(np.rint(raw * 2.0) * 0.5, 0.5, 5.0)
    return user_idx, item_idx, rating


def scaled_shape(shape, factor):
    """The same shape cut down by `factor` for rehearsals and tests;
    never used for a measured run."""
    n_users = max(64, int(shape["n_users"] / factor))
    n_items = max(64, int(shape["n_items"] / factor))
    nnz = max(n_users * shape["user_degree"]["min"],
              int(shape["nnz"] / factor ** 1.5))
    return {**shape, "n_users": n_users, "n_items": n_items, "nnz": nnz,
            "user_degree": {**shape["user_degree"],
                            "max": max(shape["user_degree"]["min"] + 1,
                                       min(shape["user_degree"]["max"],
                                           n_items // 3))},
            "item_degree": {**shape["item_degree"],
                            "max": max(2, min(shape["item_degree"]["max"],
                                              n_users // 2))}}
