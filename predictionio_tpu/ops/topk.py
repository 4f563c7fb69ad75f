"""Masked top-K scoring from device-resident factor matrices.

The serving hot path: replaces `MatrixFactorizationModel.recommendProducts`
(invoked at tests/pio_tests/engines/recommendation-engine/src/main/scala/
ALSAlgorithm.scala:95-112) and the cosine-similarity scoring loops of the
similarproduct/ecommerce templates with one fused matmul + mask + top-k
(masked_topk_rows: a user a row; itemset_topk_rows: a set of items a row).

The serving kernels select with :func:`stable_topk` (a total order: score
descending, index ascending), which sorts a whole score row only when it
is short. On the 2.44 M-item catalog that sort was 512 of the 526 ms of a
64-query flush (ledger, PR 27); a long row is now reduced to chunk maxima,
and only the k chunks that can hold the answer are sorted — same bits.

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

import math
from functools import partial
from typing import Callable, Optional, Tuple

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


#: a TPU tile: 8 rows (sublanes) of 128 items (lanes). The chunk maxima
#: are first taken over whole lanes of the score matrix through a view
#: of it as (rows / 8, 8, n / 128, 128): on a TPU that view is the
#: layout the matrix already has, so the one pass over the scores reads
#: them where they lie. A view as (rows, chunks, L) would be a 625 MB
#: relayout copy at 64 x 2,441,053 (8.0 of a 17.3 ms program), and a
#: reduce-window over the row is accepted only 128 items wide and ran
#: at 36 GB/s (17.3 of 22.8 ms; my chip runs, PR 28).
_SUBLANES, _LANES = 8, 128

#: items per chunk of the two-stage selection; see stable_topk
CHUNK = 512


def chunk_plan(n: int, k: int, chunk: int = CHUNK
               ) -> Optional[Tuple[int, int]]:
    """The shape test of :func:`stable_topk`, from static shapes alone:
    ``(L, C)`` when a row of ``n`` scores is selected in two stages (C
    whole chunks of L items; the n - C*L items past them join the second
    stage as they are), ``None`` when the whole row is sorted. Two stages
    need more chunks than k, or the first stage discards nothing, and pay
    once the second stage sorts under half the row: k*L + L <= n/2."""
    C = n // chunk
    return (chunk, C) if k >= 1 and C >= 2 * (k + 1) else None


def selection_name(n: int, k: int) -> str:
    """What `GET /` says of a deployed top-k program: "sort", or
    "chunked L=512 C=4767"."""
    plan = chunk_plan(n, k)
    return "sort" if plan is None else "chunked L=%d C=%d" % plan


def _sort_topk(scores: jnp.ndarray, idx: jnp.ndarray, k: int
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """First k of ``scores`` along the last axis under the total order
    (score descending, ``idx`` ascending; NaN after everything, as
    lax.sort orders floats): one two-key sort of the whole axis. Every
    (score, idx) pair is unique, so there is one sorted order and no
    stability to ask for: asking cost a third operand and 27 s of
    compile in place of 19 at 4,767 keys (described v5e, PR 28)."""
    neg, sidx = lax.sort((-scores, idx), num_keys=2, dimension=-1,
                         is_stable=False)
    # -(-x) is a bitwise round-trip for floats (two sign flips)
    return -neg[..., :k], sidx[..., :k]


def _nanmax(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """max that reads NaN as "nothing here": NaN only when both are. With
    NaN as its identity this is the order's own maximum (NaN is last),
    so a chunk that holds a NaN is not lost with it and a chunk of
    nothing but NaN still ranks after a chunk that holds a -inf."""
    return jnp.where(jnp.isnan(a), b,
                     jnp.where(jnp.isnan(b), a, jnp.maximum(a, b)))


def _chunked_topk(scores: jnp.ndarray, k: int, L: int, C: int,
                  fetch: Optional[Callable] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The two-stage selection of :func:`stable_topk` on rows of at
    least C*L scores, C > k. Exact: see the proof there."""
    *lead, n = scores.shape
    # the selection reads a score matrix that is THERE: written once by
    # whatever made it, read once in full by the chunk maxima and in k
    # slices a row by the gather. Left free, the TPU compiler fuses the
    # maxima into the int8 path's score fusion (ops/quant.py) and takes
    # 153 s over that one program in place of 20 (described v5e, 64 x
    # 27,136, PR 28); the float32 program is the same with and without.
    rows = lax.optimization_barrier(scores.reshape(-1, n))
    b = rows.shape[0]
    lane = min(L, _LANES)
    per = L // lane
    assert lane * per == L and C * L <= n and C > k
    nan = jnp.array(jnp.nan, scores.dtype)
    with jax.named_scope("chunk_max"):
        # one pass over the scores, and the only one: each chunk's best
        # element under the total order (its maximum; NaN iff all NaN),
        # lane by lane first (see _LANES), then `per` lanes to a chunk
        g = math.gcd(b, _SUBLANES)
        m = n // lane
        tiles = rows[:, :m * lane].reshape(b // g, g, m, lane)
        best = lax.reduce(tiles, nan, _nanmax, (3,)).reshape(b, m)
        best = lax.reduce(best[:, :C * per].reshape(b, C, per), nan,
                          _nanmax, (2,))
    with jax.named_scope("pick"):
        chunk = lax.broadcasted_iota(jnp.int32, (b, C), 1)
        _, picked = _sort_topk(best, chunk, k)               # (b, k)
    with jax.named_scope("merge"):
        # the picked chunks' RAW scores, sliced out of the score matrix
        if fetch is not None:
            cand = fetch(rows, picked, L)                    # (b, k, L)
        else:
            row = lax.broadcasted_iota(jnp.int32, (b, k), 0)
            cand = lax.gather(
                rows, jnp.stack([row, picked * L], axis=-1),
                lax.GatherDimensionNumbers(offset_dims=(2,),
                                           collapsed_slice_dims=(0,),
                                           start_index_map=(0, 1)),
                slice_sizes=(1, L), unique_indices=True,
                mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)
        gidx = picked[..., None] * L + lax.broadcasted_iota(
            jnp.int32, (b, k, L), 2)
        cand, gidx = cand.reshape(b, k * L), gidx.reshape(b, k * L)
        if n > C * L:
            # the ragged end joins the candidates as it is
            tail = rows[:, C * L:]
            cand = jnp.concatenate([cand, tail], axis=-1)
            gidx = jnp.concatenate([gidx, C * L + lax.broadcasted_iota(
                jnp.int32, tail.shape, 1)], axis=-1)
        vals, idx = _sort_topk(cand, gidx, k)
    return vals.reshape(*lead, k), idx.reshape(*lead, k)


def stable_topk(scores: jnp.ndarray, k: int,
                fetch: Optional[Callable] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Deterministic top-k along the last axis: descending score, equal
    scores broken by LOWEST index.

    ``lax.top_k``'s tie order is backend-defined; a two-key ``lax.sort``
    over (negated score, index) makes the selection total — every
    (score, index) pair is unique — so the result is identical on every
    backend and, crucially, recomposable from per-shard partial top-ks
    (parallel/serve_dist.py): the sharded and replicated serving paths
    can only be bit-identical if the tie rule is explicit.

    That sort is not free on a TPU: over whole rows of a large catalog
    it WAS the serving program — 512 of the 526 ms of a flush at 64 x
    2,441,053, 99.2 % of the device's traced seconds (ledger, PR 27).
    So a long row (:func:`chunk_plan`, a test of the static shape only)
    is selected in two stages, to the same bits:

      1. `chunk_max`: the row is cut into C contiguous chunks of L
         items and each reduced to its best element's score, in one
         pass over the scores.
      2. `pick`: the chunks are ordered by (best descending, chunk
         number ascending) and the first k kept. Chunks are contiguous
         index ranges, so this is the order of each chunk's best
         element under the total order above.
      3. `merge`: the k chunks' raw scores are sliced out of the score
         matrix with their global indices (and the n - C*L items past
         the last whole chunk beside them), and the two-key sort runs
         over those k*L-odd candidates alone.

    Every element e of the true top-k lies in a picked chunk: were its
    chunk not among the first k, each of k other chunks would hold an
    element that beats e (its best scores higher, or the same from a
    lower-numbered chunk and so at a lower index), and e would be
    (k+1)-th at best. Sorting a set that holds the top-k under the same
    order gives the same first k, values and indices, ties included.
    Non-finite scores keep the whole-row sort's behaviour because the
    chunk order is the element order (:func:`_nanmax`): NaN is never
    selected while k other scores exist, and an all-NaN row still
    returns NaN for the serving layer's non-finite gate to refuse.

    ``fetch(rows, picked, L) -> (b, k, L)`` replaces the `merge` stage's
    XLA gather of chunk ``picked[r, j]`` of row r: a copy, so the same
    bits by another route. parallel/serve_dist.py hands in a kernel
    (_fetch_chunks, which says why); without one, every program of this
    module is what it was before the argument existed, op for op."""
    n = scores.shape[-1]
    plan = chunk_plan(n, k)
    if plan is not None:
        return _chunked_topk(scores, k, *plan, fetch=fetch)
    idx = lax.broadcasted_iota(jnp.int32, scores.shape, scores.ndim - 1)
    return _sort_topk(scores, idx, k)


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
    with the batched and sharded kernels on tied scores; like them it
    sorts k chunks of a long catalog's scores, never the whole row."""
    with jax.named_scope("gather"):
        q = jnp.take(user_factors, user_ix, axis=0)
    with jax.named_scope("score"):
        scores = fp32_matmul(item_factors, q)
    with jax.named_scope("select"):
        return stable_topk(scores, k)


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
    (parallel/serve_dist.py) reproduces bit-for-bit. The scores are
    written once, as one (b, n_items) matrix, and read once in full, by
    the selection's chunk maxima; on a long catalog the sort then sees k
    chunks a row (stable_topk), where sorting whole rows took 512 of a
    flush's 526 ms (ledger, PR 27). The three stages carry
    ``jax.named_scope`` names (`gather`, `score`, `select`; inside
    `select`: `chunk_max`, `pick`, `merge`) into the ops' metadata, so a
    profiler capture groups the program's device time by stage whatever
    XLA calls the fusions; the names change no operation and no
    compile-cache key."""
    with jax.named_scope("gather"):
        Q = jnp.take(user_factors, user_ixs, axis=0)
    with jax.named_scope("score"):
        scores = fp32_matmul(Q, item_factors.T)
    with jax.named_scope("select"):
        return stable_topk(scores, k)


#: widths E of the per-row exclusion lists :func:`masked_topk_rows` is
#: compiled for: a flush pads its longest list (black list + seen items)
#: up to the smallest of these, so the programs of a deploy are (padding
#: bucket x width x k) and none compiles under load. A list longer than
#: the largest width is not cut: that query is answered on the host
#: (models/ecommerce counts it as a host fallback; KNOWN_ISSUES.md).
EXCLUDE_WIDTHS: Tuple[int, ...] = (128, 4352)

#: bit 0 of word 0 of every item's rule words: "an item", what a query
#: with no categories asks for; category j of the model is bit j + 1
RULE_ANY_BIT = np.uint32(1)


def exclude_width(n: int) -> Optional[int]:
    """The declared width a flush whose longest exclusion list holds
    ``n`` indices is padded to; None past the largest."""
    for w in EXCLUDE_WIDTHS:
        if n <= w:
            return w
    return None


def blank_rule_arguments(bucket: int, n_words: int, longest: int,
                         n_items: int):
    """A flush's rule arguments for :func:`masked_topk_rows` with no
    rule in them yet (host arrays, the caller's to fill): (bucket, w)
    wanted bits, every one set, and (bucket, E) exclusions, every one
    padding, E the declared width that holds ``longest``."""
    return (np.full((bucket, n_words), 0xFFFFFFFF, np.uint32),
            np.full((bucket, exclude_width(longest)), n_items, np.int32))


def _exclude(scores: jnp.ndarray, exclude_ixs: jnp.ndarray) -> jnp.ndarray:
    """``scores`` (b, n) with ``NEG_INF`` at ``[r, exclude_ixs[r, e]]``
    for every e up to row r's first index that is not under n (padding
    closes a row's list). One element a step, written where the matrix
    lies: a loop over the indices that are THERE, a handful a query,
    and not over the padded width. The TPU compiler lowers one scatter
    of the same (b, E) indices to two relayout copies of the whole
    score matrix (to a flat array and back: 2 x 625 MB moved each way
    at 64 x 2,441,053, and a second copy held) round a sort of all b*E
    indices, padding included (described v5e, PR 35)."""
    b, n = scores.shape
    width = exclude_ixs.shape[1]
    if not width:
        return scores
    gone = jnp.full((1, 1), NEG_INF, scores.dtype)

    def row(r, scores):
        # a step reads ONE index, the next one, and carries it: on the
        # v5e every scalar read of the list is an op of its own, 0.8 us
        # beside the 0.8 us of the write itself (my chip runs, PR 35)
        def more(state):
            e, ix, _ = state
            return (e < width) & (ix < n)

        def one(state):
            e, ix, scores = state
            scores = lax.dynamic_update_slice(scores, gone, (r, ix))
            return (e + 1, exclude_ixs[r, jnp.minimum(e + 1, width - 1)],
                    scores)

        return lax.while_loop(
            more, one, (jnp.int32(0), exclude_ixs[r, 0], scores))[2]

    return lax.fori_loop(0, b, row, scores)


def _rules_and_select(scores: jnp.ndarray, rule_words: jnp.ndarray,
                      eligible: jnp.ndarray, want_words: jnp.ndarray,
                      exclude_ixs: jnp.ndarray, k: int
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """`mask` > `exclude` > `select` on a flush's (b, n_items) scores:
    the stages every program with business rules runs, one function for
    the e-commerce engine's :func:`masked_topk_rows` and the
    similar-product engine's :func:`itemset_topk_rows` (what each
    stage does: masked_topk_rows). Nothing here knows what a row of
    the flush was gathered from."""
    with jax.named_scope("mask"):
        shared = rule_words[None, :, :] & want_words[:, :, None]
        ok = eligible[None, :] & jnp.any(shared != 0, axis=1)
        scores = jnp.where(ok, scores, NEG_INF)
    with jax.named_scope("exclude"):
        scores = _exclude(scores, exclude_ixs)
    with jax.named_scope("select"):
        return stable_topk(scores, k)


@partial(jax.jit, static_argnames=("k",))
def masked_topk_rows(
    user_factors: jnp.ndarray,   # (n_users, r) device-resident
    item_factors: jnp.ndarray,   # (n_items, r) device-resident
    rule_words: jnp.ndarray,     # (w, n_items) uint32 device-resident
    eligible: jnp.ndarray,       # (n_items,) bool device-resident
    user_ixs: jnp.ndarray,       # (b,) int32, padded to a serving bucket
    want_words: jnp.ndarray,     # (b, w) uint32
    exclude_ixs: jnp.ndarray,    # (b, E) int32, padded with n_items
    k: int = 10,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`topk_for_users` with business rules between `score` and
    `select` (the e-commerce template's isCandidateItem, on the device):
    the same gather, the same one fp32 matmul, the same
    :func:`stable_topk` (score descending, ties by lowest index), and
    between them (:func:`_rules_and_select`)

    - `mask`: a score becomes ``NEG_INF`` where the item is not
      ``eligible`` (untrained, or on the constraint's unavailable list:
      one array for every row, replaced on the device when the
      constraint changes) or shares no bit with the row's
      ``want_words``. An item's rule words hold :data:`RULE_ANY_BIT`
      and one bit a category; a query with categories asks for their
      bits, one with none for every bit;
    - `exclude`: ``NEG_INF`` written at each row's own ``exclude_ixs``
      (its black list and what its user has seen), into the score matrix
      where it lies (:func:`_exclude`). A row's list ends at its first
      index of ``n_items`` or more: padding. Negative indices are the
      caller's to keep out. E is one of :data:`EXCLUDE_WIDTHS`.

    What a flush sends is (b,) + (b, w) + (b, E) small integers, never a
    mask as long as the catalog. Rows whose every candidate is ruled out
    return ``NEG_INF`` scores, which the caller drops with the scores
    <= 0. Padding rows: any in-bounds user, any bits, all-padding
    exclusions."""
    with jax.named_scope("gather"):
        Q = jnp.take(user_factors, user_ixs, axis=0)
    with jax.named_scope("score"):
        scores = fp32_matmul(Q, item_factors.T)
    return _rules_and_select(scores, rule_words, eligible, want_words,
                             exclude_ixs, k)


#: the declared width q of a query's item list in
#: :func:`itemset_topk_rows`: a flush pads every row's list to it, so
#: the programs of a deploy stay (padding bucket x exclusion width x k).
#: A query that names more items (distinct, known, trained) is not cut:
#: it is answered on the host (models/similarproduct counts it as a
#: host fallback; KNOWN_ISSUES.md).
QUERY_WIDTH = 8


@partial(jax.jit, static_argnames=("k",))
def itemset_topk_rows(
    item_factors_hat: jnp.ndarray,  # (n_items, r) unit rows, resident
    rule_words: jnp.ndarray,        # (w, n_items) uint32 device-resident
    eligible: jnp.ndarray,          # (n_items,) bool device-resident
    query_ixs: jnp.ndarray,         # (b, q) int32, padded with n_items
    want_words: jnp.ndarray,        # (b, w) uint32
    exclude_ixs: jnp.ndarray,       # (b, E) int32, padded with n_items
    k: int = 10,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """A flush of the similar-product template's queries, each a SET of
    items: score = the sum over the row's query items of the cosine
    between their vectors and the candidate's. The rows of
    ``item_factors_hat`` have unit length (ALSAlgorithm.train stores
    them so), so that sum is one product of the summed query rows
    against the same matrix:

    - `gather`: the (b, q) query rows of the resident matrix, an entry
      of ``n_items`` or more (padding) contributing exactly 0, summed
      over q left to right: the same bits in every bucket;
    - `score`: one fp32 matmul against the matrix the rows came from;
    - `mask` > `exclude` > `select`: :func:`_rules_and_select`, the
      code :func:`masked_topk_rows` runs. The caller puts the row's own
      query items on its exclusion list beside its black list: a
      query's items are never among its answers.

    q is :data:`QUERY_WIDTH`, E one of :data:`EXCLUDE_WIDTHS`. Padding
    rows: all-padding query items (a zero vector: every score 0, which
    the caller drops), any bits, all-padding exclusions."""
    n = item_factors_hat.shape[0]
    with jax.named_scope("gather"):
        there = query_ixs < n
        rows = jnp.take(item_factors_hat, jnp.where(there, query_ixs, 0),
                        axis=0)                             # (b, q, r)
        rows = jnp.where(there[:, :, None], rows, 0.0)
        Q = rows[:, 0]
        for j in range(1, query_ixs.shape[1]):
            Q = Q + rows[:, j]
    with jax.named_scope("score"):
        scores = fp32_matmul(Q, item_factors_hat.T)
    return _rules_and_select(scores, rule_words, eligible, want_words,
                             exclude_ixs, k)


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
