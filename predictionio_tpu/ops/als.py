"""Alternating Least Squares on TPU — explicit and implicit feedback.

Replaces `org.apache.spark.mllib.recommendation.ALS` as invoked by the
reference's templates (tests/pio_tests/engines/recommendation-engine/src/main/
scala/ALSAlgorithm.scala:40-94 for explicit `ALS.train`; examples/
scala-parallel-similarproduct/.../ALSAlgorithm.scala for `ALS.trainImplicit`).

Design (TPU-first, not a port of MLlib's block-to-block shuffle):

- Ratings live on device as **sorted, padded COO** (structure-of-arrays);
  all shapes are static.
- One half-iteration solves, for every user u (symmetrically items):
      (sum_i c_ui v_i v_i^T + reg_u I) x_u = sum_i b_ui v_i
  The Gram matrices are accumulated by one of three kernels (see the
  "Device kernels" section): the default **hybrid** puts the Zipf head
  on the MXU as dense bf16 matmuls and the tail on the **csrb**
  mini-block wide-row-gather path; "scan" is the legacy per-entry
  sorted segment-sum.
- The per-row solves are **batched unrolled Gauss-Jordan sweeps** over
  (n, r, r) — millions of tiny SPD systems as r fully-parallel
  elementwise passes (batched LAPACK LU serializes badly on TPU).
- Regularization follows MLlib's ALS-WR scaling: lambda * n_ratings(u)
  (reg_scaling="count"), with "constant" available.
- The whole `iterations`-loop compiles as one XLA program via
  `lax.fori_loop`; factors are initialized like MLlib (seeded normal,
  scaled by 1/sqrt(rank)).

The distributed variant lives in predictionio_tpu/parallel/als_dist.py:
users/items block-sharded over a 1-D mesh, opposite factors replicated via
all-gather per half-iteration (ICI), zero scatter traffic across devices.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from predictionio_tpu import native
from predictionio_tpu.common import devicewatch
from predictionio_tpu.parallel.mesh import pad_to_multiple

_EPS = 1e-8


# ---------------------------------------------------------------------------
# Host-side data preparation
# ---------------------------------------------------------------------------

@dataclass
class COOSide:
    """Ratings sorted by one side ("self"), padded to a chunk multiple.

    Padding rows carry self_idx == n_self (an extra dummy segment sliced off
    after accumulation) and weight 0.
    """
    self_idx: np.ndarray    # (nnz_pad,) int32, sorted ascending
    other_idx: np.ndarray   # (nnz_pad,) int32
    rating: np.ndarray      # (nnz_pad,) float32, 0 in padding
    counts: np.ndarray      # (n_self,) int32 ratings per self row
    n_self: int
    n_other: int


@dataclass
class ALSData:
    """Both orientations of the ratings, device-ready."""
    by_user: COOSide
    by_item: COOSide
    n_users: int
    n_items: int
    nnz: int


def group_coo(keys: np.ndarray, other: np.ndarray, vals: np.ndarray,
              n_keys: int):
    """Stable-sort the COO triple by key + per-key counts.

    Hot ETL: the native O(n) counting sort (predictionio_tpu.native) when
    the toolchain is available, numpy argsort otherwise.
    """
    res = native.counting_sort_coo(keys, other, vals, n_keys)
    if res is not None:
        return res
    order = np.argsort(keys, kind="stable")
    s = keys[order]
    return (s, other[order], vals[order],
            np.bincount(s, minlength=n_keys).astype(np.int32))


def _ship_coo(user_idx, item_idx, rating, n_users: int, n_items: int):
    """Host->device COO transfer with narrow dtypes where lossless.

    On the remote backend of the early rounds the host link was the
    cold-ETL wall (~11 MB/s measured there; not measured on the attached
    chip), so bytes matter: ids that fit uint16 ship half-width, and
    ratings that are exact half-steps (the dominant case: star ratings,
    presence weights, small counts) ship as int8 twice-codes — 240 MB ->
    140 MB at ML-20M. Widening back on device is free next to the sorts.
    Arbitrary float ratings fall back to f32 untouched."""
    def narrow_ids(a, n):
        if n <= (1 << 16):
            return jnp.asarray(a.astype(np.uint16)).astype(jnp.int32)
        return jnp.asarray(a)

    u = narrow_ids(user_idx, n_users)
    i = narrow_ids(item_idx, n_items)
    twice = rating * 2.0
    codes = np.rint(twice)
    if (np.abs(codes) <= 127).all() and np.array_equal(codes, twice):
        r = jnp.asarray(codes.astype(np.int8)).astype(jnp.float32) * 0.5
    else:
        r = jnp.asarray(rating)
    return u, i, r


@partial(jax.jit, static_argnames=("n_a", "nnz_pad"))
def _side_device(a, b, r, n_a: int, nnz_pad: int):
    """On-device layout: variadic XLA sort keyed on the self index + padded
    COO + per-row counts, entirely in HBM (no host round-trip)."""
    s, o, rr = lax.sort((a, b, r), num_keys=1)
    counts = jnp.bincount(a, length=n_a).astype(jnp.int32)
    extra = nnz_pad - s.shape[0]
    return (jnp.pad(s, (0, extra), constant_values=n_a),
            jnp.pad(o, (0, extra)), jnp.pad(rr, (0, extra)), counts)


def _both_sides_impl(u, i, r, n_users: int, n_items: int, nnz_pad: int):
    """Both sorted orientations in ONE program: identical per-side ops to
    :func:`_side_device` (bit-parity preserved), but the raw COO is read
    by a single executable — which is what makes input DONATION sound:
    with `donate_argnums=(0,1,2)` XLA reuses the raw (u, i, r) buffers
    for the outputs, so the streamed train path's device peak is ~2x the
    COO (both orientations) instead of 3x (raw + both)."""
    s_u, o_u, r_u = lax.sort((u, i, r), num_keys=1)
    counts_u = jnp.bincount(u, length=n_users).astype(jnp.int32)
    s_i, o_i, r_i = lax.sort((i, u, r), num_keys=1)
    counts_i = jnp.bincount(i, length=n_items).astype(jnp.int32)
    extra = nnz_pad - s_u.shape[0]

    def pad(side, n_self):
        s, o, rr = side
        return (jnp.pad(s, (0, extra), constant_values=n_self),
                jnp.pad(o, (0, extra)), jnp.pad(rr, (0, extra)))

    return (*pad((s_u, o_u, r_u), n_users), counts_u,
            *pad((s_i, o_i, r_i), n_items), counts_i)


_SIDE_STATICS = ("n_users", "n_items", "nnz_pad")
_both_sides_jit = partial(jax.jit, static_argnames=_SIDE_STATICS)(
    _both_sides_impl)
_both_sides_donate = partial(jax.jit, static_argnames=_SIDE_STATICS,
                             donate_argnums=(0, 1, 2))(_both_sides_impl)


def _donation_supported() -> bool:
    """Buffer donation is a no-op (with a warning per call) on the CPU
    backend; only engage it where XLA actually aliases buffers."""
    return jax.default_backend() not in ("cpu",)


def prepare_ratings(
    user_idx: np.ndarray,
    item_idx: np.ndarray,
    rating: np.ndarray,
    n_users: int,
    n_items: int,
    chunk: int = 1 << 18,
    device: bool = False,
    donate: bool = False,
) -> ALSData:
    """Sort + pad the COO ratings both ways.

    This subsumes the reference's BiMap-encode + RDD repartition ETL
    (ALSAlgorithm.scala:50-94): encoding happened upstream in
    store.find_columnar; here we lay the data out for the device.

    device=False lays out on host with an O(n)-pass pack-sort (for the
    mesh-sharded path, which re-partitions on host); device=True ships the
    raw COO to the device once and does both sorted layouts there with XLA
    variadic sorts — the single-device trainers consume the resulting
    jax arrays with zero further host work, so `pio train` ETL is one
    240MB-at-20M transfer plus two in-HBM sorts. device=True also accepts
    jax arrays already resident in HBM (the overlapped read's staging
    buffers, ops/staging.py): the transfer was overlapped with chunk
    decode upstream, so the narrow-dtype host shipping is skipped and the
    in-HBM sorts run on identical values — layouts match the host path
    bit for bit. ``donate=True`` (the streamed train path, which owns
    its staged buffers outright) additionally donates the raw COO to
    the layout program so XLA reuses those buffers for the sorted
    outputs — the caller's input arrays are INVALID afterwards.
    """
    if device and isinstance(user_idx, jax.Array):
        nnz = int(user_idx.shape[0])
        nnz_pad = bucket_units(max(-(-nnz // chunk), 1)) * chunk
        u = user_idx.astype(jnp.int32)
        i = item_idx.astype(jnp.int32)
        r = rating.astype(jnp.float32)
        layout = (_both_sides_donate
                  if donate and _donation_supported() else _both_sides_jit)
        (s_u, o_u, r_u, c_u, s_i, o_i, r_i, c_i) = layout(
            u, i, r, n_users=n_users, n_items=n_items, nnz_pad=nnz_pad)
        return ALSData(
            by_user=COOSide(self_idx=s_u, other_idx=o_u, rating=r_u,
                            counts=c_u, n_self=n_users, n_other=n_items),
            by_item=COOSide(self_idx=s_i, other_idx=o_i, rating=r_i,
                            counts=c_i, n_self=n_items, n_other=n_users),
            n_users=n_users, n_items=n_items, nnz=nnz,
        )

    user_idx = np.asarray(user_idx, dtype=np.int32)
    item_idx = np.asarray(item_idx, dtype=np.int32)
    rating = np.asarray(rating, dtype=np.float32)
    nnz = user_idx.shape[0]

    if device:
        # bucketed pad: a growing event log re-trains on O(log) distinct
        # shapes instead of one new compile per chunk multiple
        nnz_pad = bucket_units(max(-(-nnz // chunk), 1)) * chunk
        u, i, r = _ship_coo(user_idx, item_idx, rating, n_users, n_items)

        def side_dev(a, b, n_a, n_b) -> COOSide:
            s, o, rr, counts = _side_device(a, b, r, n_a, nnz_pad)
            return COOSide(self_idx=s, other_idx=o, rating=rr,
                           counts=counts, n_self=n_a, n_other=n_b)

        return ALSData(
            by_user=side_dev(u, i, n_users, n_items),
            by_item=side_dev(i, u, n_items, n_users),
            n_users=n_users, n_items=n_items, nnz=nnz,
        )

    def side(a_idx, b_idx, n_a, n_b) -> COOSide:
        s, o, r, counts = group_coo(a_idx, b_idx, rating, n_a)
        pad = bucket_units(max(-(-s.shape[0] // chunk), 1)) * chunk
        return COOSide(
            self_idx=pad_to_multiple(s, pad, n_a),
            other_idx=pad_to_multiple(o, pad, 0),
            rating=pad_to_multiple(r, pad, 0.0),
            counts=counts, n_self=n_a, n_other=n_b,
        )

    return ALSData(
        by_user=side(user_idx, item_idx, n_users, n_items),
        by_item=side(item_idx, user_idx, n_items, n_users),
        n_users=n_users, n_items=n_items, nnz=nnz,
    )


# ---------------------------------------------------------------------------
# Device kernels
# ---------------------------------------------------------------------------
#
# Three interchangeable Gram accumulators (A/B-testable via the trainers'
# kernel= param / PIO_ALS_KERNEL env var):
#
#   "hybrid" (default) — dense-hot head on the MXU + csrb tail; see the
#       hybrid section below (its time on this chip: PERF.md section
#       5; csrb and scan are not measured there). Falls back to csrb
#       when the item set is too small to split.
#
#   "csrb" — row-aligned mini-block layout + wide-row gather.
#       Each row's entries are padded to a multiple of b (=32) so every
#       mini-block of b consecutive entries belongs to exactly ONE row.
#       Per half-step the opposite factors are expanded ONCE into
#       X = [v ⊗ v | v]  (n_other, r²+r)  — the flattened outer product
#       depends only on the column, never the pair — and the kernel
#       gathers full 440-byte rows of X (86% of a 512B HBM transaction,
#       vs 8% when gathering bare (r,) factor rows), scales by the two
#       per-entry coefficients, and block-reduces to one partial per
#       mini-block. The only scatter left is the mini-block combine:
#       ~nnz/b sorted segment-sum updates instead of nnz.
#
#   "scan" — the round-2/3 kernel: chunked gather + in-loop flattened
#       outer products + per-entry sorted segment_sum with the full
#       (n_self+1, r²+r) accumulator riding the scan carry. Kept for A/B
#       and as the reference implementation for parity tests.


def _tuning_key() -> tuple:
    """Env-tunable kernel knobs that are READ AT TRACE TIME deep inside the
    jitted trainers (PIO_ALS_XPAD in _expand_X, PIO_ALS_SOLVER in
    solve_factors). Passed to every module-level jitted trainer as a static
    arg so flipping a knob re-traces instead of silently reusing the
    cached executable compiled under the old value."""
    from predictionio_tpu.ops.solve_pallas import solver_choice
    return (_xpad_enabled(), solver_choice())


def _kernel_flag(kernel: Optional[str]) -> str:
    import os
    k = kernel or os.environ.get("PIO_ALS_KERNEL", "hybrid")
    if k not in ("csrb", "scan", "hybrid"):
        raise ValueError(
            f"unknown ALS kernel {k!r} (want 'csrb', 'hybrid' or 'scan')")
    return k


def gram_rhs(
    other_factors: jnp.ndarray,  # (n_other, r)
    self_idx: jnp.ndarray,       # (nnz_pad,) padded with n_self
    other_idx: jnp.ndarray,      # (nnz_pad,)
    coeff_a: jnp.ndarray,        # (nnz_pad,) per-entry Gram weight
    coeff_b: jnp.ndarray,        # (nnz_pad,) per-entry RHS weight
    n_self: int,
    chunk: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Accumulate A_s = sum_n a_n v_n v_n^T and b_s = sum_n b_n v_n per row.

    Chunked so at most (chunk, r*r + r) of flattened outer products exists
    at once; the (n_self+1, r*r+r) accumulator rides the scan carry in
    HBM. Padding rows fall into segment n_self and are sliced off.

    PRECONDITION: self_idx must be NONDECREASING (globally, hence within
    every chunk) — the segment reduction runs with indices_are_sorted=True
    and silently produces wrong sums otherwise. prepare_ratings and
    als_dist._shard_side both emit sorted layouts with end padding.
    """
    nnz_pad = self_idx.shape[0]
    n_chunks = max(-(-nnz_pad // chunk), 1)
    target = n_chunks * chunk
    if target != nnz_pad:
        # shapes are static, so this pad compiles away into the layout
        extra = target - nnz_pad
        self_idx = jnp.pad(self_idx, (0, extra), constant_values=n_self)
        other_idx = jnp.pad(other_idx, (0, extra))
        coeff_a = jnp.pad(coeff_a, (0, extra))
        coeff_b = jnp.pad(coeff_b, (0, extra))
    r = other_factors.shape[1]

    si = self_idx.reshape(n_chunks, chunk)
    oi = other_idx.reshape(n_chunks, chunk)
    ca = coeff_a.reshape(n_chunks, chunk)
    cb = coeff_b.reshape(n_chunks, chunk)

    # TPU layout note: a (chunk, r, r) outer-product tensor tiles each
    # trailing (r, r) to (8, 128) — a ~20x padding blowup at r=10 that
    # made the scatter memory-bound (measured 4.7x slower). Flattening to
    # (chunk, r*r [+ r]) keeps everything 2D and lane-aligned, and the
    # Gram and RHS accumulate through ONE sorted segment_sum.
    ia, ib = np.divmod(np.arange(r * r), r)
    col_a, col_b = jnp.asarray(ia), jnp.asarray(ib)

    def body(carry, xs):
        AB = carry
        s, o, a_w, b_w = xs
        v = jnp.take(other_factors, o, axis=0)          # (chunk, r) gather
        flat = (v * a_w[:, None])[:, col_a] * v[:, col_b]   # (chunk, r*r)
        both = jnp.concatenate([flat, v * b_w[:, None]], axis=1)
        AB = AB + jax.ops.segment_sum(
            both, s, num_segments=n_self + 1, indices_are_sorted=True)
        return AB, None

    AB0 = jnp.zeros((n_self + 1, r * r + r), dtype=jnp.float32)
    AB, _ = lax.scan(body, AB0, (si, oi, ca, cb))
    A = AB[:-1, : r * r].reshape(n_self, r, r)
    b = AB[:-1, r * r:]
    return A, b


def csrb_layout(other_idx: jnp.ndarray, rating: jnp.ndarray,
                counts: jnp.ndarray, n_self: int, b: int, n_mb: int):
    """Row-sorted COO -> row-aligned mini-block layout (traceable).

    Every mini-block of b consecutive slots belongs to exactly one row, so
    per-mini-block partial Grams need no per-entry scatter. Pure gather
    construction (no scatter): each destination slot computes its source
    entry from the row cumsums. Returns (other_idx_p, rating_p, present_p)
    of shape (n_mb*b,) and mb_seg (n_mb,) with dummy row n_self for padding
    blocks past the real data.
    """
    counts = counts.astype(jnp.int32)
    mbc = -(-counts // b)                       # mini-blocks per row
    cum_mb = jnp.cumsum(mbc)                    # inclusive
    row_start = jnp.cumsum(counts) - counts     # exclusive entry offsets
    mb_index = jnp.arange(n_mb, dtype=jnp.int32)
    mb_seg = jnp.searchsorted(cum_mb, mb_index, side="right").astype(jnp.int32)
    row = jnp.repeat(mb_seg, b, total_repeat_length=n_mb * b)
    rowc = jnp.minimum(row, n_self - 1)
    start_pad = (jnp.take(cum_mb, rowc) - jnp.take(mbc, rowc)) * b
    off = jnp.arange(n_mb * b, dtype=jnp.int32) - start_pad
    valid = (row < n_self) & (off >= 0) & (off < jnp.take(counts, rowc))
    src = jnp.clip(jnp.take(row_start, rowc) + off, 0, other_idx.shape[0] - 1)
    o = jnp.where(valid, jnp.take(other_idx, src), 0)
    rr = jnp.where(valid, jnp.take(rating, src), 0.0)
    return o, rr, valid.astype(jnp.float32), mb_seg


def gram_rhs_csrb(
    other_factors: jnp.ndarray,  # (n_other, r)
    other_idx: jnp.ndarray,      # (n_mb*b,) csrb layout
    coeff_a: jnp.ndarray,        # (n_mb*b,) per-entry Gram weight
    coeff_b: jnp.ndarray,        # (n_mb*b,) per-entry RHS weight
    mb_seg: jnp.ndarray,         # (n_mb,) nondecreasing row per mini-block
    n_self: int,
    b: int,
    chunk: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Wide-row-gather Gram accumulator over the csrb layout.

    X = [v⊗v | v] is expanded once (the outer product depends only on the
    gathered row), each entry gathers ONE lane-aligned (r²+r)-wide row, and
    partials reduce within mini-blocks before a single sorted segment-sum
    of ~nnz/b updates. See the kernel comparison note above gram_rhs.

    TRACE-TIME ENV DEPENDENCY: _expand_X reads PIO_ALS_XPAD when traced.
    The module-level trainers key their jit cache on it (_tuning_key), but
    if YOU wrap this function in your own jax.jit, flipping the env var
    after the first trace silently reuses the executable compiled under
    the old value — add _xpad_enabled() to your static args.
    """
    r = other_factors.shape[1]
    X = _expand_X(other_factors, r, jnp.float32)
    AB = _gram_rhs_csrb_flat(X, other_idx, coeff_a, coeff_b, mb_seg,
                             n_self, b, chunk, r)
    return (AB[:, :r * r].reshape(n_self, r, r),
            AB[:, r * r:r * r + r])


# ---------------------------------------------------------------------------
# Hybrid dense-hot kernel ("hybrid"): Zipf head on the MXU, tail on csrb
# ---------------------------------------------------------------------------
#
# Under the power-law item popularity of real ratings data, the top-K items
# (K=4096 default) carry ~60-70% of all entries. Those entries' Gram
# contributions don't need gathers at all: build ONE pair of dense
# coefficient matrices  D = [D_a | D_b]  (n_users, 2K, bf16) once per
# training run (column j < K: the Gram weight of user-row u vs hot item j;
# column K+j: the RHS weight), and then EVERY iteration both half-steps
# become one MXU matmul each over the SAME matrix:
#     user side :  AB_hot = [D_a @ Xo_hot | D_b @ V_hot]   (n_users, r²+r)
#     item side :  AB_hot = [D_aᵀ @ Uo    | D_bᵀ @ U    ]  (K, r²+r)
# (the item side reads D transposed — no second matrix). Only the cold
# ~30-40% of entries ride the csrb gather path, shrinking its HBM-random
# traffic proportionally. bf16 is lossless for half-star ratings and
# presence/confidence weights; accumulation is f32 on the MXU.

_HOT_K = 4096  # hot-item count; PIO_ALS_HOT_K overrides
_HYBRID_DTYPE = jnp.bfloat16  # dense-hot matmul dtype (tests may override)
# Rows with fewer ratings than this stay entirely on the f32 gather
# tail: a row with count < rank has a rank-deficient Gram whose ridge
# (lambda*count) amplifies dense-path rounding by ~1/lambda — measured
# 43% factor error on 1-rating users riding the bf16 dense path. Applied
# to USERS (all their entries go cold) and to candidate hot ITEMS (an
# unpopular "hot" item under flat popularity would hit the same wall).
# PIO_ALS_DENSE_MIN_COUNT overrides (tests lower it to cover the path).
_DENSE_MIN_COUNT = 64


def _dense_min_count() -> int:
    import os
    return int(os.environ.get("PIO_ALS_DENSE_MIN_COUNT", _DENSE_MIN_COUNT))


@dataclass
class HybridData:
    """One-time per-train layout for the hybrid kernel."""
    D: jnp.ndarray            # (n_users, 2K) bf16 dense hot coefficients
    hot_ids: jnp.ndarray      # (K,) int32 hot item rows
    u_tail: tuple             # (oi, rat, pres, seg) csrb layout, cold by user
    i_tail: tuple             # (oi, rat, pres, seg) csrb layout, cold by item
    u_chunk: int
    i_chunk: int
    K: int


@partial(jax.jit, static_argnames=("K",))
def _hybrid_top_jit(counts_i, K: int):
    top_counts, hot_ids = lax.top_k(counts_i, K)
    return top_counts, hot_ids.astype(jnp.int32)


@partial(jax.jit, static_argnames=(
    "n_users", "n_items", "K", "implicit", "b", "n_mb_u", "n_mb_i",
    "min_count"))
def _hybrid_prep_jit(u, i, r, hot_ids, counts_u, counts_i,
                     n_users: int, n_items: int, K: int,
                     implicit: bool, alpha, b: int, n_mb_u: int, n_mb_i: int,
                     min_count: int):
    """From (possibly padded) raw COO to D + cold-tail csrb layouts.

    Padding entries carry u == n_users; they sort last and scatter out of
    bounds (dropped). counts_u/counts_i come from prepare_ratings (no
    re-bincount). All passes are sorts/gathers plus two 20M-scalar
    scatter-adds for D — one-time costs, amortized over every iteration.
    The stages carry ``jax.named_scope`` names (hot_split, dense_scatter,
    user_tail, item_tail) into the ops' metadata, so a capture says which
    of them the prep's seconds are."""
    # an unpopular candidate "hot" item is as rank-deficient as a sparse
    # user; both stay on the f32 tail (see _DENSE_MIN_COUNT)
    with jax.named_scope("hot_split"):
        item_ok = jnp.take(counts_i, hot_ids) >= min_count
        hot_rank = jnp.full((n_items,), -1, jnp.int32).at[hot_ids].set(
            jnp.where(item_ok, jnp.arange(K, dtype=jnp.int32), -1))
        hr = jnp.take(hot_rank, jnp.clip(i, 0, n_items - 1))
        valid = u < n_users
        dense_ok = jnp.take(counts_u, jnp.clip(u, 0, n_users - 1)) \
            >= min_count
        hot = (hr >= 0) & valid & dense_ok
        if implicit:
            conf = alpha * jnp.abs(r)
            av = conf
            bv = (1.0 + conf) * (r > 0).astype(jnp.float32)
        else:
            av = jnp.ones_like(r)
            bv = r
    # accumulate in f32, round ONCE: a (user, item) pair may repeat (a
    # re-rating in the event log; heavy (user, item) pairs of the
    # synthetic generator repeat thousands of times), and a bf16
    # running sum stops counting at 256 — the presence count saturates
    # while the rating sum keeps growing, and the heavy rows' solves
    # come out garbage (train RMSE above the global mean's). One final
    # rounding only reweights each term by 1 +- 2^-9 (_split_hilo).
    with jax.named_scope("dense_scatter"):
        # non-hot/padding entries target a dummy column (sliced off)
        col_a = jnp.where(hot, hr, 2 * K)
        col_b = jnp.where(hot, K + hr, 2 * K)
        row = jnp.where(valid, u, n_users)   # OOB rows drop
        D = jnp.zeros((n_users, 2 * K + 1), jnp.float32)
        D = D.at[row, col_a].add(av, mode="drop")
        D = D.at[row, col_b].add(bv, mode="drop")
        D = D[:, : 2 * K].astype(_HYBRID_DTYPE)

    # cold tail, user orientation: cold entries first, sorted by user
    with jax.named_scope("user_tail"):
        sort_key = jnp.where(valid, hot.astype(jnp.int32), 2)
        ks, uu, ii, rr = lax.sort((sort_key, u, i, r), num_keys=2)
        cold_n_u = jnp.where(ks == 0, uu, n_users)   # ks: the SORTED key
        counts_u_cold = jnp.bincount(cold_n_u, length=n_users + 1
                                     )[:n_users].astype(jnp.int32)
        u_tail = csrb_layout(ii, rr, counts_u_cold, n_users, b, n_mb_u)

    # cold tail, item orientation
    with jax.named_scope("item_tail"):
        ks2, ii2, uu2, rr2 = lax.sort((sort_key, i, u, r), num_keys=2)
        cold_n_i = jnp.where(ks2 == 0, ii2, n_items)
        counts_i_cold = jnp.bincount(cold_n_i, length=n_items + 1
                                     )[:n_items].astype(jnp.int32)
        i_tail = csrb_layout(uu2, rr2, counts_i_cold, n_items, b, n_mb_i)
    return D, u_tail, i_tail


def _hybrid_prepare(data: ALSData, K: int, implicit: bool, alpha: float,
                    b: int, chunk: int) -> HybridData:
    bu, bi = data.by_user, data.by_item
    u, i, r = bu.self_idx, bu.other_idx, bu.rating
    n_users, n_items = data.n_users, data.n_items
    min_count = _dense_min_count()
    counts_i = jnp.asarray(bi.counts).astype(jnp.int32)
    top_counts, hot_ids = _hybrid_top_jit(counts_i, K)
    # one small host sync: tail-size bound -> tight static tail shapes
    # (cold entries + every entry of below-threshold users, which stay on
    # the f32 tail for conditioning)
    counts_u_h = np.asarray(bu.counts)
    sparse_extra = int(counts_u_h[counts_u_h < min_count].sum())
    top_h = np.asarray(top_counts)
    # only top-K items that PASS the min-count floor actually leave the
    # tail; a below-floor "hot" candidate's entries stay cold and must be
    # budgeted (overlap with sparse-user entries double-counts — fine for
    # an upper bound; underestimating would silently DROP ratings)
    dense_served = int(top_h[top_h >= min_count].sum())
    n_cold = max(int(data.nnz) - dense_served + sparse_extra, 1)
    n_mb_u, u_chunk = _csrb_plan(n_cold, n_users, b, chunk)
    n_mb_i, i_chunk = _csrb_plan(n_cold, n_items, b, chunk)
    D, u_tail, i_tail = _hybrid_prep_jit(
        jnp.asarray(u), jnp.asarray(i), jnp.asarray(r), hot_ids,
        jnp.asarray(bu.counts).astype(jnp.int32), counts_i,
        n_users, n_items, K, implicit, jnp.float32(alpha), b,
        n_mb_u, n_mb_i, min_count)
    return HybridData(D=D, hot_ids=hot_ids, u_tail=u_tail, i_tail=i_tail,
                      u_chunk=u_chunk, i_chunk=i_chunk, K=K)


def _gram_col_mask(r: int, wp: int):
    # select gram columns from the a-product and rhs columns from the
    # b-product via mask-add: concatenating offset SLICES miscompiled on
    # the early rounds' backend (measured wrong values on a plain input
    # array; not re-tested on the attached chip), so only row slices +
    # elementwise ops are used here. `wp` >= r²+r covers
    # 512B-padded X rows; the pad region is harmless under (1-mask)
    # because padded X columns are zero.
    return jnp.concatenate([jnp.ones((r * r,), jnp.float32),
                            jnp.zeros((wp - r * r,), jnp.float32)])


def _split_hilo(x):
    """f32 -> (hi, lo) bf16 pair with hi + lo ≈ x to ~16 mantissa bits.

    WHY (round-4 postmortem, VERDICT r04 Weak #1): quantizing the expanded
    factors X = [v⊗v | v] straight to bf16 leaves ~2^-8 relative error in
    the Gram contribution of every hot entry. The per-row Gram is then
    A_true + E with ||E|| ≈ 7e-4·||A||; once training grows the factor
    magnitudes (|V| ≈ 50 by iteration 3 at ML-20M), ||E|| passes the ridge
    (0.01·count), tens of thousands of per-row systems go indefinite, the
    unpivoted solve explodes, and the model NaN-poisons within two more
    iterations (measured on a v5e: 74k rows with gram error > ridge, 25k
    negative Schur pivots, max|solution| 1.7e4 at the bench seed). Two
    matmuls against the hi/lo pair (f32 accumulation) cut the error 256x —
    back under the ridge with margin — while keeping the MXU on bf16.
    D itself stays single bf16: its rounding only REWEIGHTS each PSD term
    v⊗v by 1±2^-8 (weights stay nonnegative), which cannot break PSD."""
    hi = x.astype(_HYBRID_DTYPE)
    lo = (x - hi.astype(jnp.float32)).astype(_HYBRID_DTYPE)
    return hi, lo


def _dense_hot_user(D, X_hot, K: int, r: int):
    """[D_a @ X_hot(gram cols) | D_b @ X_hot(rhs cols)] via mask-add.
    X_hot arrives f32 and is consumed as a split hi/lo bf16 pair.

    The optimization_barrier is load-bearing (KNOWN_ISSUES.md #2): on the
    early rounds' backend, letting XLA fuse the _expand_X concat-producer
    chain into these dot_generals MISCOMPILED the matmul at bench scale —
    measured 1.05e6 absolute error on the hot Gram block (~30% of its
    magnitude) vs 50.75 (= f32 accumulation roundoff over 138k-term dot
    products, i.e. correct) with the operand materialized first. That
    corruption, iterated, was the entire round-4 ML-20M NaN blowup."""
    X_hot = lax.optimization_barrier(X_hot)
    Xh, Xl = _split_hilo(X_hot)

    def mm(Dcols):
        return sum(jax.lax.dot_general(
            Dcols, Xp, (((1,), (0,)), ((), ())),
            precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32) for Xp in (Xh, Xl))

    g = mm(D[:, :K])
    h = mm(D[:, K:])
    m = _gram_col_mask(r, X_hot.shape[1])
    return g * m + h * (1.0 - m)


def _dense_hot_item(D, Z, K: int, r: int):
    """[D_aᵀ @ Z(gram cols) | D_bᵀ @ Z(rhs cols)] -> (K, r²+r).
    Z arrives f32 and is consumed as a split hi/lo bf16 pair.
    The barrier is load-bearing — see _dense_hot_user."""
    Z = lax.optimization_barrier(Z)
    Zh, Zl = _split_hilo(Z)
    out = sum(jax.lax.dot_general(
        D, Zp, (((0,), (0,)), ((), ())),
        precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32) for Zp in (Zh, Zl))  # (2K, wp)
    m = _gram_col_mask(r, Z.shape[1])
    return out[:K] * m + out[K:] * (1.0 - m)


def _xpad_enabled() -> bool:
    import os
    return os.environ.get("PIO_ALS_XPAD", "1") != "0"


def _xpad_width(w: int) -> int:
    """Pad the expanded-X row width to a 512-byte (128-float) multiple so
    every tail gather reads whole aligned HBM transactions: a 440-byte
    (r=10) row at arbitrary stride straddles two 512B transactions (~43%
    useful bandwidth); padded+aligned it is exactly one (86%)."""
    if not _xpad_enabled():
        return w
    return -(-w // 128) * 128


def _expand_X(factors, r: int, dtype):
    w = r * r + r
    out = jnp.concatenate(
        [(factors[:, :, None] * factors[:, None, :]).reshape(-1, r * r),
         factors], axis=1).astype(dtype)
    wp = _xpad_width(w)
    if wp != w:
        out = jnp.concatenate(
            [out, jnp.zeros((out.shape[0], wp - w), dtype)], axis=1)
    return out


def _gram_tail(other_factors_X, tail, n_self, b, chunk, implicit, alpha,
               r):
    oi, rat, pres, seg = tail
    if implicit:
        conf = alpha * jnp.abs(rat)
        ca, cb = conf, (1.0 + conf) * (rat > 0).astype(jnp.float32)
    else:
        ca, cb = pres, rat
    return _gram_rhs_csrb_flat(other_factors_X, oi, ca, cb, seg,
                               n_self, b, chunk, r)


def _gram_rhs_csrb_flat(X, other_idx, coeff_a, coeff_b, mb_seg,
                        n_self: int, b: int, chunk: int,
                        r: int) -> jnp.ndarray:
    """gram_rhs_csrb but taking a prebuilt (possibly 512B-row-padded) X
    and returning flat (n, X.shape[1]) so hybrid can sum dense + tail
    before slicing into A and rhs. Pad columns of X are zero, so the
    rhs-side (1-mask) weighting contributes nothing there."""
    w = X.shape[1]
    n_mb = mb_seg.shape[0]
    m = max(chunk // b, 1)
    n_chunks = max(n_mb // m, 1)
    r2 = r * r
    mask_a = jnp.concatenate([jnp.ones((r2,), jnp.float32),
                              jnp.zeros((w - r2,), jnp.float32)])

    def body(_, xs):
        o, ca, cb = xs
        rows = jnp.take(X, o, axis=0).astype(jnp.float32)
        s = ca[:, None] * mask_a[None, :] + cb[:, None] * (1 - mask_a)[None, :]
        M = jnp.sum((rows * s).reshape(m, b, w), axis=1)
        return 0, M

    _, Ms = lax.scan(body, 0, (other_idx.reshape(n_chunks, m * b),
                               coeff_a.reshape(n_chunks, m * b),
                               coeff_b.reshape(n_chunks, m * b)))
    return jax.ops.segment_sum(Ms.reshape(n_mb, w), mb_seg,
                               num_segments=n_self + 1,
                               indices_are_sorted=True)[:-1]


def solve_factors(A: jnp.ndarray, b: jnp.ndarray, reg: jnp.ndarray) -> jnp.ndarray:
    """Batched SPD solve: (A + reg I) x = b over leading axis.

    Small ranks use an unrolled vectorized Gauss-Jordan: r fully-parallel
    elementwise sweeps over the (n, r, r) batch. Pivoting is unnecessary —
    A is PSD and reg > 0 keeps every Schur-complement diagonal >= reg.
    Batched LAPACK-style LU (jnp.linalg.solve) serializes badly on TPU:
    measured 377 ms vs 8.6 ms for this sweep at (138k, 10, 10) on a v5e.

    Every pivot's MAGNITUDE is additionally floored at 0.5*reg, keeping its
    sign: inert for a true SPD system (whose Schur diagonals are >= reg up
    to f32 roundoff), but a hard bound on the inverse when accumulated
    kernel rounding has pushed a row's Gram indefinite — a bounded solution
    for that row instead of a division blow-up that NaN-poisons the whole
    model two iterations later (the round-4 ML-20M failure mode; see
    _split_hilo for the primary fix). Sign preservation matters: flooring a
    substantially NEGATIVE pivot to a tiny positive value would divide the
    row by ~floor and explode far worse than the unclamped sweep (measured:
    all-NaN on an engineered indefinite batch).
    """
    r = A.shape[-1]
    if r <= 32:
        from predictionio_tpu.ops.solve_pallas import (solve_factors_pallas,
                                                       solver_choice)
        if solver_choice() == "pallas":
            # all sweeps in VMEM: one tile read + solution write per block
            # (the XLA sweep materializes every elimination step to HBM)
            return solve_factors_pallas(A, b, reg)
    A = A + reg[:, None, None] * jnp.eye(r, dtype=A.dtype)[None]
    if r > 32:
        return jnp.linalg.solve(A, b[..., None])[..., 0]
    M = jnp.concatenate([A, b[..., None]], axis=2)      # (n, r, r+1)
    floor = (0.5 * reg)[:, None, None]
    for k in range(r):
        d0 = M[:, k:k + 1, k:k + 1]
        den = jnp.where(d0 >= 0, jnp.maximum(d0, floor),
                        jnp.minimum(d0, -floor))
        piv = M[:, k:k + 1, :] / den
        M = M - M[:, :, k:k + 1] * piv
        M = M.at[:, k, :].set(piv[:, 0, :])
    return M[:, :, r]


def _reg_vec(counts, n_self, lambda_, reg_scaling):
    """MLlib ALS-WR regularization: lambda * n_ratings(row) or constant.

    Zero-count rows get one rating's worth of lambda, not the bare _EPS:
    1e-8 is below f32 resolution next to YtY entries, so the implicit
    path's A = YtY + 0 + eps*I is numerically singular for a cold row and
    the unpivoted Gauss-Jordan sweep hits an exactly-zero pivot → 0/0 →
    one NaN row → the NEXT iteration's YtY is all-NaN and the whole model
    is poisoned. The solve's result for a cold row is 0 either way (rhs is
    0); the floor only makes it numerically reachable. Trained rows
    (count >= 1) are unchanged."""
    if reg_scaling == "count":
        return lambda_ * jnp.maximum(counts, 1).astype(jnp.float32) + _EPS
    return jnp.full((n_self,), lambda_ + _EPS, dtype=jnp.float32)


def _half_step_explicit(other, side_idx, side_other, side_rating, counts,
                        n_self, lambda_, chunk, reg_scaling):
    # Presence weight: explicit ALS uses an unweighted Gram over observed
    # entries. A genuine 0.0 rating is still an observation, so presence is
    # encoded via self_idx < n_self (padding rows use n_self), not the value.
    with jax.named_scope("gram"):
        present = (side_idx < n_self).astype(jnp.float32)
        A, b = gram_rhs(other, side_idx, side_other, present, side_rating,
                        n_self, chunk)
    with jax.named_scope("solve"):
        return solve_factors(
            A, b, _reg_vec(counts, n_self, lambda_, reg_scaling))


def _half_step_explicit_csrb(other, oi, rat, pres, seg, counts, n_self,
                             lambda_, b, chunk, reg_scaling):
    # rat is 0 in padding slots (and a genuine 0.0 rating contributes 0 to
    # the RHS anyway); presence carries the Gram weight.
    with jax.named_scope("gram"):
        A, rhs = gram_rhs_csrb(other, oi, pres, rat, seg, n_self, b, chunk)
    with jax.named_scope("solve"):
        return solve_factors(
            A, rhs, _reg_vec(counts, n_self, lambda_, reg_scaling))


def _half_step_implicit_csrb(other, oi, rat, pres, seg, counts, n_self,
                             lambda_, alpha, b, chunk, reg_scaling):
    # Hu-Koren-Volinsky (see _half_step_implicit); padding slots have rat=0
    # so conf=0 and pref=0 — they contribute to neither term.
    with jax.named_scope("gram"):
        YtY = other.T @ other
        conf = alpha * jnp.abs(rat)
        pref = (rat > 0).astype(jnp.float32)
        A_corr, rhs = gram_rhs_csrb(other, oi, conf, (1.0 + conf) * pref,
                                    seg, n_self, b, chunk)
    with jax.named_scope("solve"):
        return solve_factors(YtY[None] + A_corr, rhs,
                             _reg_vec(counts, n_self, lambda_, reg_scaling))


_CSRB_B = 32  # mini-block size; 32 keeps row padding ~10-20% at ML-20M skew


def bucket_units(n: int, step: float = 1.25) -> int:
    """Round a unit count up to a geometric bucket boundary (~step ratio).

    Shapes derived from nnz are jit statics, so an event log that grows a
    little between trains would otherwise recompile the whole trainer per
    run. Geometric buckets cap the number of distinct compiled shapes at
    O(log_step nnz) for <= (step-1) padding overhead. Disable with
    PIO_NNZ_BUCKETING=0 (exact shapes, maximal recompiles)."""
    import os
    if n <= 1 or os.environ.get("PIO_NNZ_BUCKETING", "1") == "0":
        return max(n, 1)
    b = 1
    while b < n:
        b = max(b + 1, int(b * step))
    return b


def declared_nnz_pad(nnz: int, chunk: int = 1 << 18) -> int:
    """The COO pad :func:`prepare_ratings` would apply to ``nnz``
    ratings — computable from the declared count alone, no data. This
    makes :func:`bucket_units` the AOT shape oracle (serving/aot.py):
    the trainer program for a declared event-log size can be lowered
    and compiled before any ratings are read."""
    return bucket_units(max(-(-nnz // chunk), 1)) * chunk


def lower_train_explicit(n_users: int, n_items: int, rank: int, nnz: int,
                         chunk: int = 1 << 18,
                         reg_scaling: str = "count"):
    """AOT-lower the scan-kernel explicit trainer from declared shapes.

    Returns the jax Lowered for exactly the program
    :func:`train_explicit`(kernel="scan") would trace for a layout of
    ``nnz`` ratings: array shapes come from :func:`declared_nnz_pad`,
    iteration count and lambda stay traced (concrete exemplars abstract
    to the same weak-typed scalars), and the statics — including the
    env-derived tuning key — match the lazy path's jit cache key, so
    ``.compile()`` seeds the persistent cache entry the real train
    would otherwise build. The hybrid/csrb kernels derive statics from
    data skew and are NOT declarable; their programs ship via the
    compile-cache artifact instead (workflow/model_io.py)."""
    nnz_pad = declared_nnz_pad(nnz, chunk)
    chunk_eff = min(chunk, nnz_pad)

    def side(n_self: int):
        return (jax.ShapeDtypeStruct((nnz_pad,), jnp.int32),
                jax.ShapeDtypeStruct((nnz_pad,), jnp.int32),
                jax.ShapeDtypeStruct((nnz_pad,), jnp.float32),
                jax.ShapeDtypeStruct((n_self,), jnp.int32))

    return _train_explicit_jit.lower(
        *side(n_users), *side(n_items),
        jax.ShapeDtypeStruct((n_users, rank), jnp.float32),
        jax.ShapeDtypeStruct((n_items, rank), jnp.float32),
        1, 0.01,
        n_users=n_users, n_items=n_items, chunk=chunk_eff,
        reg_scaling=reg_scaling, tuning=_tuning_key())


def _csrb_plan(nnz: int, n_self: int, b: int, chunk: int) -> Tuple[int, int]:
    """(n_mb, chunk_eff): static mini-block count + scan chunk, shrunk for
    tiny inputs so tests don't pad 100 entries to a 2^18 slab."""
    raw = max((nnz + n_self * (b - 1) + b - 1) // b, 1)
    m = max(chunk // b, 1)
    m = min(m, 1 << (raw - 1).bit_length())
    n_mb = bucket_units(((raw + m - 1) // m)) * m
    return n_mb, m * b


_csrb_layout_jit = partial(
    jax.jit, static_argnames=("n_self", "b", "n_mb"))(csrb_layout)


def _csrb_side(side: COOSide, b: int, chunk: int, nnz: int):
    """Build the csrb layout for one orientation (device, jitted once)."""
    n_mb, chunk_eff = _csrb_plan(nnz, side.n_self, b, chunk)
    oi, rat, pres, seg = _csrb_layout_jit(
        side.other_idx, side.rating, side.counts,
        n_self=side.n_self, b=b, n_mb=n_mb)
    return oi, rat, pres, seg, chunk_eff


@partial(jax.jit, static_argnames=(
    "n_users", "n_items", "b", "u_chunk", "i_chunk", "reg_scaling",
    "implicit", "tuning"))
def _train_csrb_jit(
    u_oi, u_rat, u_pres, u_seg, u_counts,
    i_oi, i_rat, i_pres, i_seg, i_counts,
    U0, V0,
    iterations, lambda_: float, alpha: float,
    n_users: int, n_items: int, b: int, u_chunk: int, i_chunk: int,
    reg_scaling: str, implicit: bool,
    tuning: tuple = ()):
    # iterations is traced: one compiled program serves any count
    def one_iter(_, UV):
        U, V = UV
        if implicit:
            with jax.named_scope("user_step"):
                U = _half_step_implicit_csrb(
                    V, u_oi, u_rat, u_pres, u_seg, u_counts, n_users,
                    lambda_, alpha, b, u_chunk, reg_scaling)
            with jax.named_scope("item_step"):
                V = _half_step_implicit_csrb(
                    U, i_oi, i_rat, i_pres, i_seg, i_counts, n_items,
                    lambda_, alpha, b, i_chunk, reg_scaling)
        else:
            with jax.named_scope("user_step"):
                U = _half_step_explicit_csrb(
                    V, u_oi, u_rat, u_pres, u_seg, u_counts, n_users,
                    lambda_, b, u_chunk, reg_scaling)
            with jax.named_scope("item_step"):
                V = _half_step_explicit_csrb(
                    U, i_oi, i_rat, i_pres, i_seg, i_counts, n_items,
                    lambda_, b, i_chunk, reg_scaling)
        return (U, V)

    return lax.fori_loop(0, iterations, one_iter, (U0, V0))


def _run_csrb(data: ALSData, rank, iterations, lambda_, alpha, seed, chunk,
              reg_scaling, implicit, u0, v0, checkpoint_every, checkpointer):
    """Shared csrb-kernel driver for both public trainers."""
    b = _CSRB_B
    bu, bi = data.by_user, data.by_item
    u_oi, u_rat, u_pres, u_seg, u_chunk = _csrb_side(bu, b, chunk, data.nnz)
    i_oi, i_rat, i_pres, i_seg, i_chunk = _csrb_side(bi, b, chunk, data.nnz)
    if u0 is None or v0 is None:
        u0, v0 = _seed_factors(int(seed), data.n_users, data.n_items, rank)

    def run(u, v, n_iters):
        # compile attribution (common/devicewatch.py): a re-trace of the
        # trainer shows up as pio_xla_compiles_total{fn="als_train_csrb"}
        with devicewatch.attribution("als_train_csrb", phase="train"):
            return _train_csrb_jit(
                u_oi, u_rat, u_pres, u_seg, bu.counts,
                i_oi, i_rat, i_pres, i_seg, bi.counts,
                u, v, iterations=n_iters, lambda_=float(lambda_),
                alpha=float(alpha), n_users=data.n_users,
                n_items=data.n_items,
                b=b, u_chunk=u_chunk, i_chunk=i_chunk,
                reg_scaling=reg_scaling, implicit=implicit,
                tuning=_tuning_key())

    return _run_segmented(run, u0, v0, iterations, checkpoint_every,
                          checkpointer)


@partial(jax.jit, static_argnames=(
    "n_users", "n_items", "K", "b", "u_chunk", "i_chunk", "reg_scaling",
    "implicit", "tuning"))
def _train_hybrid_jit(
    D, hot_ids, u_oi, u_rat, u_pres, u_seg, i_oi, i_rat, i_pres, i_seg,
    u_counts, i_counts, U0, V0, iterations, lambda_: float, alpha: float,
    n_users: int, n_items: int, K: int, b: int, u_chunk: int, i_chunk: int,
    reg_scaling: str, implicit: bool,
    tuning: tuple = ()):
    r = U0.shape[1]
    u_reg = _reg_vec(u_counts, n_users, lambda_, reg_scaling)
    i_reg = _reg_vec(i_counts, n_items, lambda_, reg_scaling)

    def one_iter(_, UV):
        U, V = UV
        # ---- user half-step: dense hot items + csrb cold tail
        with jax.named_scope("user_step"):
            with jax.named_scope("gram"):
                X = _expand_X(V, r, jnp.float32)     # (n_items, wp >= r²+r)
                X_hot = jnp.take(X, hot_ids, axis=0)    # f32; split inside
                AB = _dense_hot_user(D, X_hot, K, r)
                AB = AB + _gram_tail(X, (u_oi, u_rat, u_pres, u_seg),
                                     n_users, b, u_chunk, implicit, alpha,
                                     r)
                A = AB[:, : r * r].reshape(n_users, r, r)
                if implicit:
                    A = A + (V.T @ V)[None]
            with jax.named_scope("solve"):
                U = solve_factors(A, AB[:, r * r:r * r + r], u_reg)
        # ---- item half-step: same D transposed + csrb cold tail
        with jax.named_scope("item_step"):
            with jax.named_scope("gram"):
                Z = _expand_X(U, r, jnp.float32)        # (n_users, wp)
                AB_hot = _dense_hot_item(D, Z, K, r)    # f32; split inside
                ABi = _gram_tail(Z, (i_oi, i_rat, i_pres, i_seg),
                                 n_items, b, i_chunk, implicit, alpha, r)
                ABi = ABi.at[hot_ids].add(AB_hot)
                Ai = ABi[:, : r * r].reshape(n_items, r, r)
                if implicit:
                    Ai = Ai + (U.T @ U)[None]
            with jax.named_scope("solve"):
                V = solve_factors(Ai, ABi[:, r * r:r * r + r], i_reg)
        return (U, V)

    return lax.fori_loop(0, iterations, one_iter, (U0, V0))


# one-entry HybridData cache: repeated trains over the SAME ALSData object
# (bench slope passes, warm-started resumes, and the layout cache in the
# recommendation template) skip the per-train host sync + D scatter + two
# csrb tail layouts. Identity-keyed (`data is cached`), so a new layout
# can never alias a stale one; PIO_ALS_LAYOUT_CACHE=0 disables.
_HYBRID_CACHE: list = []   # [(data, params_key, HybridData)]


def _layout_cache_enabled() -> bool:
    import os
    return os.environ.get("PIO_ALS_LAYOUT_CACHE", "1") != "0"


def _run_hybrid(data: ALSData, rank, iterations, lambda_, alpha, seed, chunk,
                reg_scaling, implicit, u0, v0, checkpoint_every,
                checkpointer):
    """Hybrid-kernel driver; falls back to csrb when the item set is too
    small for a meaningful hot/cold split."""
    import os
    K = int(os.environ.get("PIO_ALS_HOT_K", _HOT_K))
    if data.n_items < 2 * K or data.n_users < 2:
        return _run_csrb(data, rank, iterations, lambda_, alpha, seed, chunk,
                         reg_scaling, implicit, u0, v0, checkpoint_every,
                         checkpointer)
    b = _CSRB_B
    pkey = (K, implicit, float(alpha), b, chunk, _dense_min_count())
    hy = None
    if _layout_cache_enabled() and _HYBRID_CACHE:
        cd, ck, chy = _HYBRID_CACHE[0]
        if cd is data and ck == pkey:
            hy = chy
    if hy is None:
        # evict any stale entry BEFORE building: holding the old D (bf16,
        # GBs at scale) across the new scatter would double retained HBM
        _HYBRID_CACHE.clear()
        hy = _hybrid_prepare(data, K, implicit, float(alpha), b, chunk)
        if _layout_cache_enabled():
            _HYBRID_CACHE[:] = [(data, pkey, hy)]
    if u0 is None or v0 is None:
        u0, v0 = _seed_factors(int(seed), data.n_users, data.n_items, rank)
    bu, bi = data.by_user, data.by_item

    def run(u, v, n_iters):
        with devicewatch.attribution("als_train_hybrid", phase="train"):
            return _train_hybrid_jit(
                hy.D, hy.hot_ids, *hy.u_tail, *hy.i_tail,
                bu.counts, bi.counts, u, v, iterations=n_iters,
                lambda_=float(lambda_), alpha=float(alpha),
                n_users=data.n_users, n_items=data.n_items, K=hy.K, b=b,
                u_chunk=hy.u_chunk, i_chunk=hy.i_chunk,
                reg_scaling=reg_scaling, implicit=implicit,
                tuning=_tuning_key())

    return _run_segmented(run, u0, v0, iterations, checkpoint_every,
                          checkpointer)


def init_factors(key, n: int, rank: int) -> jnp.ndarray:
    """MLlib-style init: abs(normal)/sqrt(rank) keeps first solves well-scaled."""
    return jnp.abs(jax.random.normal(key, (n, rank), dtype=jnp.float32)) / jnp.sqrt(
        jnp.asarray(rank, dtype=jnp.float32))


@partial(jax.jit, static_argnames=(
    "n_users", "n_items", "chunk", "reg_scaling", "tuning"))
def _train_explicit_jit(
    u_self, u_other, u_rating, u_counts,
    i_self, i_other, i_rating, i_counts,
    U0, V0,
    iterations, lambda_: float,
    n_users: int, n_items: int, chunk: int, reg_scaling: str,
    tuning: tuple = ()):
    # iterations is traced: one compiled program serves any count (the
    # fori_loop lowers to while), so warm-up and segment runs share it
    def one_iter(_, UV):
        U, V = UV
        with jax.named_scope("user_step"):
            U = _half_step_explicit(V, u_self, u_other, u_rating, u_counts,
                                    n_users, lambda_, chunk, reg_scaling)
        with jax.named_scope("item_step"):
            V = _half_step_explicit(U, i_self, i_other, i_rating, i_counts,
                                    n_items, lambda_, chunk, reg_scaling)
        return (U, V)

    return lax.fori_loop(0, iterations, one_iter, (U0, V0))


def _seed_factors(seed: int, n_users: int, n_items: int, rank: int):
    ku, ki = jax.random.split(jax.random.PRNGKey(seed))
    return init_factors(ku, n_users, rank), init_factors(ki, n_items, rank)


def _run_segmented(run, u0, v0, iterations: int,
                   checkpoint_every: Optional[int], checkpointer):
    """Shared restore + segmented-execution loop for both trainers.

    `run(u, v, n_iters)` executes one compiled segment. Intermediate
    snapshots only: the final state persists via the model blob.
    """
    start = 0
    if checkpointer is not None:
        restored = checkpointer.latest()
        if restored is not None:
            start, arrays = restored
            expect_u = tuple(np.shape(u0))
            expect_v = tuple(np.shape(v0))
            got_u = tuple(np.shape(arrays["U"]))
            got_v = tuple(np.shape(arrays["V"]))
            # rank/entity-count drift (engine.json edited between runs) must
            # fail loudly, not silently train at the snapshot's rank
            if got_u != expect_u or got_v != expect_v:
                raise ValueError(
                    "incompatible checkpoint: snapshot factors are "
                    f"U{got_u} / V{got_v} but this run expects "
                    f"U{expect_u} / V{expect_v}; the engine params "
                    "(rank) or training data changed since the snapshot "
                    "was written — delete the checkpoint directory or "
                    "restore the original params to resume")
            u0, v0 = arrays["U"], arrays["V"]
    if start >= iterations:
        return u0, v0
    if checkpoint_every is None or checkpointer is None:
        return run(u0, v0, iterations - start)
    U, V = u0, v0
    step = start
    while step < iterations:
        seg = min(checkpoint_every, iterations - step)
        U, V = run(U, V, seg)
        step += seg
        if step < iterations:
            checkpointer.save(step, {"U": np.asarray(U), "V": np.asarray(V)})
    return U, V


def train_explicit(
    data: ALSData,
    rank: int = 10,
    iterations: int = 10,
    lambda_: float = 0.01,
    seed: int = 3,
    chunk: int = 1 << 18,
    reg_scaling: str = "count",
    u0=None,
    v0=None,
    checkpoint_every: Optional[int] = None,
    checkpointer=None,
    kernel: Optional[str] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """ALS.train parity (defaults = recommendation-engine engine.json:14-17).

    Returns (user_factors (n_users, rank), item_factors (n_items, rank)).
    u0/v0 warm-start the factors (resume path); with checkpoint_every and
    a checkpointer (workflow.checkpoint.FactorCheckpointer protocol:
    save(step, {...}) / latest() -> (step, {...}) | None), training runs
    in compiled segments and snapshots factors between them — the
    iteration-level resume the reference lacks (SURVEY.md §5
    checkpoint/resume). kernel selects the Gram accumulator ("hybrid"
    default — dense-hot MXU head + f32 gather tail; "csrb" pure-gather;
    "scan" legacy; PIO_ALS_KERNEL overrides).
    """
    k = _kernel_flag(kernel)
    if k == "hybrid":
        return _run_hybrid(data, rank, iterations, lambda_, 0.0, seed, chunk,
                           reg_scaling, False, u0, v0, checkpoint_every,
                           checkpointer)
    if k == "csrb":
        return _run_csrb(data, rank, iterations, lambda_, 0.0, seed, chunk,
                         reg_scaling, False, u0, v0, checkpoint_every,
                         checkpointer)
    bu, bi = data.by_user, data.by_item
    chunk = min(chunk, bu.self_idx.shape[0], bi.self_idx.shape[0])
    if u0 is None or v0 is None:
        u0, v0 = _seed_factors(int(seed), data.n_users, data.n_items, rank)

    def run(u, v, n_iters):
        with devicewatch.attribution("als_train_scan", phase="train"):
            return _train_explicit_jit(
                bu.self_idx, bu.other_idx, bu.rating, bu.counts,
                bi.self_idx, bi.other_idx, bi.rating, bi.counts,
                u, v, iterations=n_iters, lambda_=float(lambda_),
                n_users=data.n_users, n_items=data.n_items,
                chunk=chunk, reg_scaling=reg_scaling,
                tuning=_tuning_key())

    return _run_segmented(run, u0, v0, iterations, checkpoint_every,
                          checkpointer)


def _half_step_implicit(other, side_idx, side_other, side_rating, counts,
                        n_self, lambda_, alpha, chunk, reg_scaling):
    """Hu-Koren-Volinsky: A_u = Y'Y + Y'(C_u - I)Y,  b_u = Y'C_u p_u.

    MLlib ALS.trainImplicit parity for SIGNED ratings (used by the
    similarproduct LikeAlgorithm's dislike = -1): confidence derives from
    |r| (c - 1 = alpha * |r|, keeping A_u positive definite) and the
    preference is p = 1 iff r > 0, so disliked items pull factors toward 0
    with high confidence instead of flipping the Gram correction negative.
    The dense Y'Y term is one (r, n) x (n, r) matmul; only the
    confidence-weighted correction runs through the sparse accumulator.
    """
    with jax.named_scope("gram"):
        YtY = other.T @ other                           # (r, r) MXU
        conf = alpha * jnp.abs(side_rating)             # c_ui - 1 >= 0
        pref = (side_rating > 0).astype(jnp.float32)    # p_ui
        A_corr, b = gram_rhs(
            other, side_idx, side_other, conf, (1.0 + conf) * pref,
            n_self, chunk)
        A = YtY[None] + A_corr
    with jax.named_scope("solve"):
        return solve_factors(A, b, _reg_vec(counts, n_self, lambda_,
                                            reg_scaling))


@partial(jax.jit, static_argnames=(
    "n_users", "n_items", "chunk", "reg_scaling", "tuning"))
def _train_implicit_jit(
    u_self, u_other, u_rating, u_counts,
    i_self, i_other, i_rating, i_counts,
    U0, V0,
    iterations, lambda_: float, alpha: float,
    n_users: int, n_items: int, chunk: int, reg_scaling: str,
    tuning: tuple = ()):
    def one_iter(_, UV):
        U, V = UV
        with jax.named_scope("user_step"):
            U = _half_step_implicit(
                V, u_self, u_other, u_rating, u_counts,
                n_users, lambda_, alpha, chunk, reg_scaling)
        with jax.named_scope("item_step"):
            V = _half_step_implicit(
                U, i_self, i_other, i_rating, i_counts,
                n_items, lambda_, alpha, chunk, reg_scaling)
        return (U, V)

    return lax.fori_loop(0, iterations, one_iter, (U0, V0))


def train_implicit(
    data: ALSData,
    rank: int = 10,
    iterations: int = 10,
    lambda_: float = 0.01,
    alpha: float = 1.0,
    seed: int = 3,
    chunk: int = 1 << 18,
    reg_scaling: str = "count",
    u0=None,
    v0=None,
    checkpoint_every: Optional[int] = None,
    checkpointer=None,
    kernel: Optional[str] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """ALS.trainImplicit parity (similarproduct/ecommerce templates).

    `rating` carries the implicit preference weight (view counts etc.);
    padding rows have weight 0 so they contribute nothing. Checkpoint
    semantics match train_explicit; kernel as in train_explicit.
    """
    k = _kernel_flag(kernel)
    if k == "hybrid":
        return _run_hybrid(data, rank, iterations, lambda_, alpha, seed,
                           chunk, reg_scaling, True, u0, v0,
                           checkpoint_every, checkpointer)
    if k == "csrb":
        return _run_csrb(data, rank, iterations, lambda_, alpha, seed, chunk,
                         reg_scaling, True, u0, v0, checkpoint_every,
                         checkpointer)
    bu, bi = data.by_user, data.by_item
    chunk = min(chunk, bu.self_idx.shape[0], bi.self_idx.shape[0])
    if u0 is None or v0 is None:
        u0, v0 = _seed_factors(int(seed), data.n_users, data.n_items, rank)

    def run(u, v, n_iters):
        with devicewatch.attribution("als_train_scan", phase="train"):
            return _train_implicit_jit(
                bu.self_idx, bu.other_idx, bu.rating, bu.counts,
                bi.self_idx, bi.other_idx, bi.rating, bi.counts,
                u, v, iterations=n_iters, lambda_=float(lambda_),
                alpha=float(alpha), n_users=data.n_users,
                n_items=data.n_items,
                chunk=chunk, reg_scaling=reg_scaling,
                tuning=_tuning_key())

    return _run_segmented(run, u0, v0, iterations, checkpoint_every,
                          checkpointer)


# ---------------------------------------------------------------------------
# Diagnostics
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("chunk",))
def rmse(U, V, user_idx, item_idx, rating, mask, chunk: int = 1 << 18):
    """Root-mean-square error over observed (possibly padded) entries."""
    nnz_pad = user_idx.shape[0]
    n_chunks = max(-(-nnz_pad // chunk), 1)
    target = n_chunks * chunk
    if target != nnz_pad:
        extra = target - nnz_pad
        user_idx = jnp.pad(user_idx, (0, extra))
        item_idx = jnp.pad(item_idx, (0, extra))
        rating = jnp.pad(rating, (0, extra))
        mask = jnp.pad(mask, (0, extra))
    c = target // n_chunks

    def body(carry, xs):
        se, n = carry
        u, i, r, m = xs
        # padding rows carry u == n_users; an unclipped take fills NaN
        # (jnp out-of-bounds gather), and NaN * 0-mask is still NaN
        uc = jnp.minimum(u, U.shape[0] - 1)
        ic = jnp.minimum(i, V.shape[0] - 1)
        pred = jnp.sum(jnp.take(U, uc, axis=0) * jnp.take(V, ic, axis=0),
                       axis=1)
        err = (pred - r) * m
        return (se + jnp.sum(err * err), n + jnp.sum(m)), None

    (se, n), _ = lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)),
        (user_idx.reshape(n_chunks, c), item_idx.reshape(n_chunks, c),
         rating.reshape(n_chunks, c), mask.reshape(n_chunks, c)))
    return jnp.sqrt(se / jnp.maximum(n, 1.0))
