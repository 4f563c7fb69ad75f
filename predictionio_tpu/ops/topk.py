"""Masked top-K scoring from device-resident factor matrices.

The serving hot path: replaces `MatrixFactorizationModel.recommendProducts`
(invoked at tests/pio_tests/engines/recommendation-engine/src/main/scala/
ALSAlgorithm.scala:95-112) and the cosine-similarity scoring loops of the
similarproduct/ecommerce templates with one fused matmul + mask + lax.top_k.

Everything is jitted once per (n_items, rank, k) shape and reused across
queries, so a deployed engine server answers from HBM with no recompile.

AOT contract (serving/aot.py): every ``@jax.jit`` entry point in this
module MUST be registered with the AOT enumerator (a tier-1 lint in
tests/test_aot.py enforces it), so `pio deploy` can compile the full
(padding bucket x template x k) program set from declared shapes before
/readyz flips ready. Adding a jitted serving kernel here without
registering it would silently reintroduce the first-dispatch warmup
cliff — the lint makes that a test failure instead.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: a NumPy scalar, not a jnp one: a jnp constant would initialize the
#: backend — and take the chip — in every process that imports this
#: module, a daemon that never serves a query included
NEG_INF = np.float32(-3.4e38)


def fp32_matmul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """``a @ b`` in true float32. A TPU's default matmul precision
    rounds float32 operands to bfloat16 on the MXU; scores then carry
    ~1e-3 relative error that DIFFERS between the matvec and the
    batched programs, so the same user got a different top-k alone and
    inside a batch (first run on the v5e, PR 25: 3.75763 alone against
    3.75782 in a bucket of 64, near-tied items re-ranked). A rank-length
    contraction costs nothing at full precision; on the CPU backend the
    flag changes nothing."""
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


def stable_topk(scores: jnp.ndarray, k: int
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Deterministic top-k along the last axis: descending score, equal
    scores broken by LOWEST index.

    ``lax.top_k``'s tie order is backend-defined; a two-key ``lax.sort``
    over (negated score, index) makes the selection total — every
    (score, index) pair is unique — so the result is identical on every
    backend and, crucially, recomposable from per-shard partial top-ks
    (parallel/serve_dist.py): the sharded and replicated serving paths
    can only be bit-identical if the tie rule is explicit. On TPU this
    costs nothing — lax.top_k lowers to a full sort there anyway."""
    idx = lax.broadcasted_iota(jnp.int32, scores.shape, scores.ndim - 1)
    neg, sidx = lax.sort((-scores, idx), num_keys=2, dimension=-1)
    # -(-x) is a bitwise round-trip for floats (two sign flips)
    return -neg[..., :k], sidx[..., :k]


@partial(jax.jit, static_argnames=("k",))
def topk_scores(
    query_vec: jnp.ndarray,      # (r,)
    item_factors: jnp.ndarray,   # (n_items, r)
    mask: Optional[jnp.ndarray] = None,  # (n_items,) bool, True = eligible
    k: int = 10,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """scores = V @ q with ineligible items masked to -inf; returns (vals, idx)."""
    scores = fp32_matmul(item_factors, query_vec)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    return jax.lax.top_k(scores, k)


@partial(jax.jit, static_argnames=("k",))
def topk_for_user(
    user_factors: jnp.ndarray,   # (n_users, r) device-resident
    item_factors: jnp.ndarray,   # (n_items, r) device-resident
    user_ix: jnp.ndarray,        # () int32
    k: int = 10,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused single-query serve: row gather + matvec + top_k in ONE
    dispatch, so the device costs one round-trip per query
    instead of four (gather, matmul, and two fetches). `user_ix` must be
    in-bounds — callers resolve it against the model's user vocabulary
    first (an OOB index would gather NaN, KNOWN_ISSUES.md #5).
    Tie-deterministic (stable_topk) so the inline path agrees bit-for-bit
    with the batched and sharded kernels on tied scores."""
    q = jnp.take(user_factors, user_ix, axis=0)
    return stable_topk(fp32_matmul(item_factors, q), k)


def host_masked_topk(factors, query_vec, mask, k: int, weights=None):
    """Host serving kernel shared by the item-scoring templates: one BLAS
    matvec, optional per-item score multipliers (the weighted-items
    business rule), -inf outside the candidate mask, argpartition top-K.
    Callers drop non-finite/non-positive entries when building results."""
    import numpy as np

    scores = np.asarray(factors) @ np.asarray(query_vec)
    if weights is not None:
        scores = scores * np.asarray(weights)
    scores = np.where(np.asarray(mask), scores, -np.inf)
    return host_topk(scores, k)


def host_topk(scores, k: int):
    """numpy argpartition top-K for host-side serving (small models or
    remote devices where per-query dispatch latency dominates). k <= 0
    (e.g. a negative `num` straight from request JSON) returns empty —
    a negative argpartition slice would return nearly ALL entries.

    Tie-deterministic like stable_topk: equal scores break by lowest
    index. argpartition alone can't promise that — its selection at the
    k-th-value boundary is arbitrary among tied entries — so entries
    STRICTLY above the boundary keep the fast partitioned path and the
    boundary ties are re-resolved from the full array (one vectorized
    equality scan; flatnonzero yields them already index-ascending)."""
    import numpy as np

    k = min(k, scores.shape[-1])
    if k <= 0:
        return scores[:0], np.zeros((0,), dtype=np.int64)
    sel = np.argpartition(-scores, k - 1)[:k]
    kth = scores[sel].min()          # the boundary value
    if np.isnan(kth):
        # non-finite scores (a poisoned model): keep the legacy
        # selection so the NaNs PROPAGATE to the caller — the serving
        # layer's non-finite gate must see them and 500; a
        # deterministic-but-empty answer would mask the bad model
        sel = sel[np.argsort(-scores[sel], kind="stable")]
        return scores[sel], sel
    strict = sel[scores[sel] > kth]
    # lexsort: primary -score descending, secondary index ascending
    strict = strict[np.lexsort((strict, -scores[strict]))]
    ties = np.flatnonzero(scores == kth)[:k - strict.size]
    idx = np.concatenate([strict, ties])
    return scores[idx], idx


@partial(jax.jit, static_argnames=("k",))
def topk_scores_batch(
    query_vecs: jnp.ndarray,     # (b, r)
    item_factors: jnp.ndarray,   # (n_items, r)
    mask: Optional[jnp.ndarray] = None,  # (b, n_items) or (n_items,)
    k: int = 10,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Batched variant for batchPredict/eval: one (b, r) x (r, n) matmul."""
    scores = fp32_matmul(query_vecs, item_factors.T)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    return jax.lax.top_k(scores, k)


@partial(jax.jit, static_argnames=("k",))
def topk_for_users(
    user_factors: jnp.ndarray,   # (n_users, r) device-resident
    item_factors: jnp.ndarray,   # (n_items, r) device-resident
    user_ixs: jnp.ndarray,       # (b,) int32 — padded to a serving bucket
    k: int = 10,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused batched serve (the micro-batcher's device hot path): B row
    gathers + ONE (b, r) x (r, n_items) matmul + batched top_k in a single
    dispatch — B concurrent queries cost one device round-trip instead of
    B. Callers pad `user_ixs` up to a bucket size (serving/protocol.py)
    with any in-bounds index (an OOB pad index would gather NaN,
    KNOWN_ISSUES.md #5) and drop the padding rows from the result; this
    compiles once per (bucket, k, shapes), not once per batch size.
    Tie-deterministic (stable_topk): equal scores break by lowest item
    index — the contract the sharded serving path's cross-shard merge
    (parallel/serve_dist.py) reproduces bit-for-bit."""
    Q = jnp.take(user_factors, user_ixs, axis=0)
    return stable_topk(fp32_matmul(Q, item_factors.T), k)


def host_masked_topk_batch(factors, query_vecs, masks, ks, weights=None):
    """Batched host serving kernel: ONE (b, r) x (r, n_items) BLAS matmul
    for the whole micro-batch, then the per-row mask/weight/argpartition
    pipeline of host_masked_topk with each query's own k. Returns a list
    of (vals, idx) rows. `masks` is an iterable of per-row (n_items,)
    bool masks; `weights` an optional shared (n_items,) multiplier."""
    import numpy as np

    scores = np.asarray(query_vecs) @ np.asarray(factors).T
    if weights is not None:
        scores = scores * np.asarray(weights)[None, :]
    out = []
    for row, mask, k in zip(scores, masks, ks):
        out.append(host_topk(np.where(np.asarray(mask), row, -np.inf), k))
    return out


@partial(jax.jit, static_argnames=("k",))
def cosine_topk(
    query_vec: jnp.ndarray,
    item_factors: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    k: int = 10,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Cosine-similarity top-K (similarproduct template scoring)."""
    qn = query_vec / jnp.maximum(jnp.linalg.norm(query_vec), 1e-12)
    norms = jnp.linalg.norm(item_factors, axis=1)
    scores = fp32_matmul(item_factors, qn) / jnp.maximum(norms, 1e-12)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    return jax.lax.top_k(scores, k)
