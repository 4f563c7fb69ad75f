"""Sharded serving: answer top-k queries from row-sharded factor matrices.

Training already block-shards factors over the mesh (parallel/als_dist.py)
but the query server has served from a single-device replicated copy — the
serving ceiling was one chip's HBM. This module removes it: both factor
matrices are laid out ROW-SHARDED across a 1-D mesh and `topk_for_users`
runs as a per-device local top-k over each item shard via shard_map, so a
factor matrix that cannot fit one device serves fine across eight.

Layout. Training's capacity-constrained LPT deal balances nnz because a
half-step's cost is proportional to a row's rating count. Serving cost per
item row is ONE rank-length dot product — uniform — so the same deal
degenerates to "equal row counts per device": contiguous row blocks of
``rows_dev = ceil(n / n_dev)`` rows (the exact padded address space the
training deal uses, with uniform weights). Contiguous blocks additionally
make shard-local -> global index recovery a single base-offset add AND
preserve the tie-break order: within a shard, ascending local index IS
ascending global index, so the per-shard top-k's lowest-local-index tie
rule composes into the global lowest-index rule.

Kernel (one fused device dispatch, same contract as ops.topk.topk_for_users):

  1. user-vector gather: each device gathers the batch rows IT owns from
     its user-factor shard and a psum replicates the (b, rank) query
     block — the batch axis stays unsharded, so the micro-batcher and
     padding buckets carry over unchanged;
  2. local scores: (b, rank) x (rank, rows_dev) against the local item
     shard. The contraction axis (rank) is never split, so every score
     is the same float32 dot product the replicated kernel computes —
     up to the order in which the compiler adds its rank terms (see
     Parity);
  3. local top-k: ops.topk.stable_topk on the shard's own (b,
     rows_dev) scores, then ``d * rows_dev + local index``. A long
     shard (ops.topk.chunk_plan, a test of the static shape) is
     selected in stable_topk's two stages — chunk maxima in the one
     pass over the scores, a sort of the k chunks that can hold the
     answer — a short one by one whole sort; the whole-shard sort was
     597.85 of a flush's 602.2 ms at 64 x 2,337,500 (ledger, PR 29).
     On a mesh of TPUs the k chunks are copied out of the scores by
     one kernel (_fetch_chunks) where stable_topk's own XLA gather is
     a loop of b*k slices: the same bits, a quarter of the time, and
     a profiler capture of four chips that can be stopped.
     Same candidates, to the bit, as a two-key sort of the whole shard
     by (-score, global index): contiguous blocks make ascending local
     index ascending global index, so stable_topk's total order (score
     descending, local index ascending) IS that sort's order, non-finite
     scores included (the same negation and lax.sort comparator). The
     padding rows of a last shard are masked to NEG_INF before the
     selection; they lie at the shard's highest local indices, so they
     lose every tie among NEG_INFs and map to global ids >= n_items;
  4. merge: ONE small all_gather of the k·n_dev candidates (~k·n_dev
     floats per query) + a final two-key sort, on device.

Merge strategy: all-gather, not host merge. The candidate set is tiny
(k·n_dev values per query — hundreds of bytes), it rides the same ICI the
training all-gathers use, and the result comes back as a plain (b, k)
replicated array, so the caller contract, the AOT program registry, and
the waterfall's `execute` stage (which must end in a real host transfer,
KNOWN_ISSUES #3) are identical to the replicated path. A host merge would
put an O(b·k·n_dev log) sort plus a second result reshape on the request
thread and leak shard-count-dependent shapes into the protocol layer.

Parity. For any model, batch, and k, the sharded result has the SAME
RANKING as the replicated ``topk_for_users`` — identical indices, ties
included: ties break by lowest global index on both paths (ops/topk.py
stable_topk is the shared contract) — and float32 scores within
``SCORE_ATOL + SCORE_RTOL * |replicated|`` (1e-5 each). Not bit-identical
scores: the two are different XLA programs, and the installed compiler
orders the rank-length sum differently in them (1-3 ULP on the CPU
backend of jaxlib 0.9; PR 8 claimed bit identity on the jaxlib of its
day). The int8 layouts ARE bit-identical to each other — integer dot
products are exact. Asserted by tests/test_serve_dist.py at 1 and 8
devices, including constructed score ties across shard boundaries, by
the multichip harness (__graft_entry__.dryrun_multichip), and on four
real chips by ``chip_smoke.py --chips 4``.

Mode resolution (`pio deploy --shard-serving auto/on/off`, env override
``PIO_SERVE_SHARD``): "on" always shards over all visible devices (even a
1-device mesh — the bench's overhead leg uses this); "off" never; "auto"
shards only on a real multi-device accelerator mesh (the tier-1 virtual
CPU devices share one host memory, so sharding there buys no HBM and
costs collectives) and falls back to the replicated path on ``/reload``
hot-swap — the swap window holds the old AND new model, and re-laying-out
shards mid-swap risks exceeding per-device headroom exactly when the
operator can least afford it; ``on`` remains the explicit opt-in.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import os
import threading
from functools import partial
from typing import Any, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from predictionio_tpu.common import devicewatch, telemetry
from predictionio_tpu.ops.topk import NEG_INF, fp32_matmul, stable_topk
from predictionio_tpu.parallel.mesh import shard_map_compat

logger = logging.getLogger("predictionio_tpu.serve_dist")

#: the sharded-vs-replicated score contract (module docstring, "Parity"):
#: |sharded - replicated| <= SCORE_ATOL + SCORE_RTOL * |replicated|
SCORE_RTOL = 1e-5
SCORE_ATOL = 1e-5

#: the merge strategy this module implements (doctor/status surface it)
MERGE_STRATEGY = "all_gather"

#: mesh axis name for serving shards (distinct from training's "block"
#: so the two subsystems' programs never alias)
AXIS = "shard"


# ---------------------------------------------------------------------------
# mode resolution: ServerConfig.shard_serving + PIO_SERVE_SHARD
# ---------------------------------------------------------------------------

_scope = threading.local()


def _normalize_mode(mode: str) -> str:
    m = (mode or "auto").lower()
    if m in ("0", "off"):
        return "off"
    if m in ("1", "on"):
        return "on"
    if m == "auto":
        return "auto"
    raise ValueError(f"shard-serving mode must be auto/on/off, got {mode!r}")


def configured_mode(mode: Optional[str] = None) -> str:
    """Effective mode: ``PIO_SERVE_SHARD`` wins over the config value
    (the same override shape as PIO_AOT vs ServerConfig.aot)."""
    env = os.environ.get("PIO_SERVE_SHARD", "")
    if env:
        return _normalize_mode(env)
    if mode is not None:
        return _normalize_mode(mode)
    return _normalize_mode(getattr(_scope, "mode", "auto"))


@contextlib.contextmanager
def deploy_scope(mode: str, reload: bool = False):
    """Install the deploy's shard-serving mode for the calling thread
    (QueryAPI._load wraps prepare_serving in this): algorithms resolve
    the mode without threading ServerConfig through every signature.
    Validates eagerly so a bad config fails the deploy, not a query."""
    _normalize_mode(mode)
    prev = (getattr(_scope, "mode", None), getattr(_scope, "reload", None))
    _scope.mode, _scope.reload = mode, bool(reload)
    try:
        yield
    finally:
        _scope.mode, _scope.reload = prev


def _multi_device_platform() -> bool:
    """A real multi-device accelerator mesh? Virtual CPU devices (the
    tier-1 harness) share one host memory — auto stays replicated there
    (tests monkeypatch this to exercise the auto path)."""
    devs = jax.devices()
    return len(devs) > 1 and devs[0].platform != "cpu"


def serving_enabled(mode: Optional[str] = None) -> bool:
    """Should prepare_serving lay this model out sharded?"""
    m = configured_mode(mode)
    if m == "off":
        return False
    if m == "on":
        return True
    # auto: multi-device accelerator only, and never mid-hot-swap
    if getattr(_scope, "reload", False):
        return False
    return _multi_device_platform()


# ---------------------------------------------------------------------------
# partition-routed serving (cross-process twin of the on-device merge)
# ---------------------------------------------------------------------------

def parse_partition(spec: str) -> Tuple[int, int]:
    """Parse a ``pio deploy --partition i/N`` scope into (index, count).

    ``i`` is zero-based and must satisfy 0 <= i < N; N >= 1. Raises
    ValueError on anything else so a typo'd fleet never silently serves
    the wrong rows."""
    txt = str(spec).strip()
    try:
        left, right = txt.split("/", 1)
        index, count = int(left), int(right)
    except ValueError:
        raise ValueError(
            f"--partition must look like i/N (got {spec!r})") from None
    if count < 1 or not 0 <= index < count:
        raise ValueError(
            f"--partition index out of range: {index}/{count}")
    return index, count


def partition_rows(n_items: int, index: int, count: int) -> Tuple[int, int]:
    """Contiguous row range [lo, hi) owned by partition ``index`` of
    ``count``: the same floor split every partition computes
    independently, so the fleet tiles [0, n_items) exactly."""
    lo = index * n_items // count
    hi = (index + 1) * n_items // count
    return lo, hi


def merge_candidates(values, gids, k: int):
    """Host-side twin of the kernel's final merge: two-key stable sort by
    (-value, global index ascending), truncated to ``k``.

    ``values``/``gids`` are the concatenated per-partition top-k
    candidates for ONE query. Returns (merged_values, merged_gids,
    order) where ``order`` indexes into the concatenated inputs — the
    router uses it to reorder already-parsed response entries so the
    merged wire answer reuses the replicas' own floats byte-for-byte.

    Tie rule matches ``topk_for_users_sharded``'s
    ``lax.sort((-cand_v, cand_g), num_keys=2)`` for every finite score;
    the one divergence is IEEE total order on signed zeros (-0.0 sorts
    before +0.0 on device, equal here) — ALS scores are dot products
    where a -0.0 tie with +0.0 at the k boundary has never been
    observed, and the parity tests construct ties with nonzero values."""
    v = np.asarray(values)
    g = np.asarray(gids)
    order = np.lexsort((g, -v))[:max(int(k), 0)]
    return v[order], g[order], order


# ---------------------------------------------------------------------------
# the sharded serving kernel
# ---------------------------------------------------------------------------

def _fetch_chunks(rows: jnp.ndarray, picked: jnp.ndarray, L: int,
                  interpret: bool = False) -> jnp.ndarray:
    """``out[r, j] = rows[r, picked[r, j] * L:][:L]``: stable_topk's
    `merge` stage's fetch of the picked chunks, as ONE kernel on the
    device's op line. The XLA gather it stands in for is a `while` of
    b*k = 640 iterations of five ops at bucket 64, 0.83 ms and 3,200
    trace events a program; on four chips at 25 flushes a second a 5 s
    profiler capture of them took 134 s to stop, past the 120 s the
    benchmark's harness waits (one chip, the same loop: 37 s; my chip
    runs, PR 30). A copy, so the bits are the gather's. The grid walks
    (row, rank); the chunk numbers ride in as a scalar-prefetch operand
    and pick the block: the row's sublane tile of 8 rows (the whole
    batch under 8) by L lanes, of which the kernel keeps the row's own
    sublane. A chunk is whole (picked < C, C * L <= n), so the ragged
    last block of a row is never addressed."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, k = picked.shape
    g = min(b, 8)

    def kernel(_picked, x_ref, o_ref):
        o_ref[0] = x_ref[pl.ds(pl.program_id(0) % g, 1), :]

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, k),
            in_specs=[pl.BlockSpec(
                (g, L), lambda r, j, p: (r // g, p[r * k + j]))],
            # (1, 1, L): the last two dims of a block are whole dims
            out_specs=pl.BlockSpec(
                (1, 1, L), lambda r, j, p: (r * k + j, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((b * k, 1, L), rows.dtype),
        interpret=interpret,
    )(picked.reshape(b * k), rows)
    return out.reshape(b, k, L)


def _select_and_merge(scores: jnp.ndarray, mesh: Mesh, *, k: int,
                      n_items: int, rows_dev_i: int
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Steps 3 and 4 of both sharded kernels (module docstring), from
    one shard's (b, rows_dev_i) scores inside the shard_map body: the
    shard's top-k by stable_topk, its local indices made global by the
    shard's base offset, and the all-gather merge. On a mesh of TPUs
    stable_topk fetches its chunks through :func:`_fetch_chunks`; on
    the CPU's virtual devices through its own XLA gather."""
    axis, n_dev = mesh.axis_names[0], int(mesh.devices.size)
    on_tpu = mesh.devices.flat[0].platform == "tpu"
    base = lax.axis_index(axis) * rows_dev_i
    k_local = min(int(k), int(rows_dev_i))
    with jax.named_scope("local_topk"):
        if n_dev * rows_dev_i > n_items:
            # only a layout with padding rows pays for the mask, and it
            # is a row vector against a scalar: no (b, rows_dev_i) index
            # is written (two iotas, 1.7 ms a flush, while the sort
            # carried one; ledger, PR 29)
            real = lax.broadcasted_iota(
                jnp.int32, (1, rows_dev_i), 1) < n_items - base
            scores = jnp.where(real, scores, NEG_INF)
        vals, idx = stable_topk(scores, k_local,
                                fetch=_fetch_chunks if on_tpu else None)
        gids = base + idx
    # any global top-k element is inside its own shard's top-k_local,
    # so the candidate set always covers the answer (k_local = rows_dev
    # when k exceeds a shard, hence n_dev * k_local >= min(k, n_items))
    with jax.named_scope("merge"):
        cand_v = lax.all_gather(vals, axis, axis=1, tiled=True)
        cand_g = lax.all_gather(gids, axis, axis=1, tiled=True)
        mneg, mg = lax.sort((-cand_v, cand_g), num_keys=2, dimension=-1)
        return -mneg[:, :k], mg[:, :k]


@partial(jax.jit, static_argnames=("k", "n_items", "rows_dev_u",
                                   "rows_dev_i", "mesh"))
def topk_for_users_sharded(
    user_shards: jnp.ndarray,    # (n_dev * rows_dev_u, r) row-sharded
    item_shards: jnp.ndarray,    # (n_dev * rows_dev_i, r) row-sharded
    user_ixs: jnp.ndarray,       # (b,) int32 global user ids, replicated
    *,
    k: int,
    n_items: int,
    rows_dev_u: int,
    rows_dev_i: int,
    mesh: Mesh,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Row-sharded batched top-k serve over ``mesh``: per-device local
    top-k + one small all-gather merge; the same ranking (indices, tie
    order) as ops.topk.topk_for_users on the replicated factors, scores
    within SCORE_RTOL/SCORE_ATOL of it (module docstring, "Parity").
    Compiles once per (mesh, shapes, bucket, k) — the AOT enumerator
    (serving/aot.py via ALSAlgorithm.aot_serving_programs) prebuilds
    every (bucket x k) program before /readyz flips ready."""
    axis = mesh.axis_names[0]

    def step(U_blk, V_blk, ixs):
        # the steps carry jax.named_scope names into the ops' metadata, so
        # a capture groups device and collective time by step
        d = lax.axis_index(axis)
        # 1. replicate the batch's user vectors: each device contributes
        # the rows it owns, the psum fills in the rest with exact zeros
        # (x + 0.0 == x), so Q is bit-identical to the replicated gather
        with jax.named_scope("gather"):
            loc = ixs - d * rows_dev_u
            own = (loc >= 0) & (loc < rows_dev_u)
            Q = jnp.take(U_blk, jnp.clip(loc, 0, rows_dev_u - 1), axis=0)
            Q = lax.psum(Q * own[:, None].astype(U_blk.dtype), axis)
        # 2. local scores; the contraction axis (rank) is unsplit, so
        # each score is the same float32 dot product as replicated
        with jax.named_scope("score"):
            scores = fp32_matmul(Q, V_blk.T)          # (b, rows_dev_i)
        # 3. the shard's top-k by stable_topk (contiguous blocks make
        # local order == global order, so shard ties break exactly like
        # replicated) and 4. the all-gather of the k·n_dev candidates +
        # final two-key sort
        return _select_and_merge(scores, mesh, k=k, n_items=n_items,
                                 rows_dev_i=rows_dev_i)

    return shard_map_compat(
        step, mesh,
        (P(axis, None), P(axis, None), P()),
        (P(), P()),
    )(user_shards, item_shards, user_ixs)


@partial(jax.jit, static_argnames=("k", "n_items", "rows_dev_u",
                                   "rows_dev_i", "mesh"))
def topk_for_users_sharded_quant(
    user_shards: jnp.ndarray,    # (n_dev * rows_dev_u, r) int8, sharded
    user_scales: jnp.ndarray,    # (n_dev * rows_dev_u,) fp32, sharded
    item_shards: jnp.ndarray,    # (n_dev * rows_dev_i, r) int8, sharded
    item_scales: jnp.ndarray,    # (n_dev * rows_dev_i,) fp32, sharded
    user_ixs: jnp.ndarray,       # (b,) int32 global user ids, replicated
    *,
    k: int,
    n_items: int,
    rows_dev_u: int,
    rows_dev_i: int,
    mesh: Mesh,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Row-sharded QUANTIZED top-k serve (ops/quant.py factors): the
    same shard/merge shape as :func:`topk_for_users_sharded`, with the
    local scores computed as exact int8 x int8 -> int32 dot products
    plus the fused per-row rescale. Because the integer arithmetic is
    exact and the rescale elementwise, the result is BIT-IDENTICAL
    (values, indices, ties) to the replicated quantized kernels —
    there is no accumulation-order drift for sharding to introduce."""
    axis = mesh.axis_names[0]

    def step(U_blk, su_blk, V_blk, sv_blk, ixs):
        d = lax.axis_index(axis)
        # 1. replicate the batch's quantized user rows + scales: the
        # owning device contributes, the psum fills in exact zeros —
        # integer adds for the int8 rows (widened to int32: psum over
        # int8 would wrap at 127), so Q is exactly the replicated gather
        with jax.named_scope("gather"):
            loc = jnp.clip(ixs - d * rows_dev_u, 0, rows_dev_u - 1)
            own = ((ixs - d * rows_dev_u >= 0)
                   & (ixs - d * rows_dev_u < rows_dev_u))
            Qi = jnp.take(U_blk, loc, axis=0).astype(jnp.int32)
            Q = lax.psum(Qi * own[:, None].astype(jnp.int32), axis)
            su = lax.psum(jnp.take(su_blk, loc, axis=0)
                          * own.astype(sv_blk.dtype), axis)
        # 2. local int32 scores over the local int8 item shard (exact),
        # then the same elementwise rescale as the replicated kernels
        with jax.named_scope("score"):
            s32 = lax.dot_general(Q, V_blk.astype(jnp.int32),
                                  (((1,), (1,)), ((), ())))
        with jax.named_scope("rescale"):
            scores = s32.astype(jnp.float32) * (su[:, None]
                                                * sv_blk[None, :])
        # 3.+4. local top-k + all-gather merge: the fp32 sharded
        # kernel's (the tie rule and candidate-coverage argument carry
        # over unchanged)
        return _select_and_merge(scores, mesh, k=k, n_items=n_items,
                                 rows_dev_i=rows_dev_i)

    return shard_map_compat(
        step, mesh,
        (P(axis, None), P(axis), P(axis, None), P(axis), P()),
        (P(), P()),
    )(user_shards, user_scales, item_shards, item_scales, user_ixs)


# ---------------------------------------------------------------------------
# realtime fold-in publication: scatter updated user rows into the live
# row-sharded layout (predictionio_tpu/realtime/foldin.py drives these)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("mesh",))
def scatter_user_rows_sharded(
    user_shards: jnp.ndarray,    # (n_dev * rows_dev_u, r) fp32, sharded
    ixs: jnp.ndarray,            # (b,) int32 global row ids, replicated
    rows: jnp.ndarray,           # (b, r) fp32 replacement rows, replicated
    *,
    mesh: Mesh,
) -> jnp.ndarray:
    """One-dispatch row scatter into the sharded user matrix: each
    device applies exactly the updates that land in its contiguous row
    block (the replicated update set is tiny — a fold-in tick's dirty
    users — so shipping it everywhere costs less than any routing
    protocol would). ``ixs`` must be in-bounds of the padded row space;
    the fold-in worker resolves them against the model's vocabulary +
    headroom bookkeeping first (KNOWN_ISSUES #5). Duplicate indices
    must carry identical rows (the worker dedups per tick). Returns a
    NEW sharded array — publication is the caller's atomic reference
    swap, so in-flight queries keep reading the old layout."""
    out = user_shards.at[ixs].set(rows)
    return lax.with_sharding_constraint(
        out, NamedSharding(mesh, P(mesh.axis_names[0], None)))


@partial(jax.jit, static_argnames=("mesh",))
def scatter_user_rows_sharded_quant(
    user_shards: jnp.ndarray,    # (n_dev * rows_dev_u, r) int8, sharded
    user_scales: jnp.ndarray,    # (n_dev * rows_dev_u,) fp32, sharded
    ixs: jnp.ndarray,            # (b,) int32 global row ids, replicated
    q_rows: jnp.ndarray,         # (b, r) int8 quantized rows, replicated
    scales: jnp.ndarray,         # (b,) fp32 per-row scales, replicated
    *,
    mesh: Mesh,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The int8 twin: per-row symmetric quantization makes re-quantizing
    exactly the touched rows local and exact (ops/quant.py quantize_rows
    runs host-side on the new rows; nothing else re-quantizes), so the
    published int8 rows + scales are bit-identical to what a full
    re-quantization of the updated matrix would produce for those rows.
    Same in-bounds/dedup contract as the fp32 scatter."""
    axis = mesh.axis_names[0]
    out_q = lax.with_sharding_constraint(
        user_shards.at[ixs].set(q_rows),
        NamedSharding(mesh, P(axis, None)))
    out_s = lax.with_sharding_constraint(
        user_scales.at[ixs].set(scales),
        NamedSharding(mesh, P(axis)))
    return out_q, out_s


# ---------------------------------------------------------------------------
# layout: canonical factors -> row-sharded device arrays
# ---------------------------------------------------------------------------

def _rows_dev(n: int, n_dev: int) -> int:
    return max(-(-n // n_dev), 1)


def _shard_rows(arr: np.ndarray, rows_dev: int, spec: NamedSharding):
    """Place each contiguous block of ``rows_dev`` rows on its device, as
    if axis 0 were padded to rows_dev * n_dev with zero rows (every
    process holds the full host array, so each one donates its
    addressable shards — the same strategy als_dist._shard_put uses).
    A block goes to its device as a view of ``arr``; only the block
    that reaches past the last real row is copied, to pad it — a
    matrix that fills most of the host's memory is never held twice."""
    n, n_pad = arr.shape[0], rows_dev * spec.mesh.devices.size

    def block(idx):
        lo, hi, _ = idx[0].indices(n_pad)
        part = arr[lo:min(hi, n)]
        if part.shape[0] == hi - lo:
            return part
        out = np.zeros((hi - lo,) + arr.shape[1:], dtype=arr.dtype)
        out[:part.shape[0]] = part
        return out

    return jax.make_array_from_callback((n_pad,) + arr.shape[1:], spec,
                                        block)


@dataclasses.dataclass
class ShardedFactors:
    """One model's factors laid out for sharded serving, plus the jit
    statics its programs need. ``topk`` is the drop-in replacement for
    the replicated ``topk_for_users(U, V, ixs, k)`` call.

    ``dtype`` records the shard element type: "float32" (the PR 8
    layout) or "int8" when ``shard_factors`` was handed quantized
    factors (ops/quant.py) — then ``user_scales``/``item_scales`` hold
    the row-sharded fp32 scale vectors and ``topk`` dispatches the
    quantized shard_map kernel."""
    mesh: Mesh
    n_users: int
    n_items: int
    rank: int
    rows_dev_u: int
    rows_dev_i: int
    user_shards: Any
    item_shards: Any
    user_scales: Any = None
    item_scales: Any = None
    dtype: str = "float32"
    quant_recall: Optional[float] = None
    quant_exact1: Optional[float] = None

    @property
    def n_shards(self) -> int:
        return int(self.mesh.devices.size)

    def per_shard_bytes(self) -> int:
        """Per-device factor bytes (padded rows included) — the number
        the HBM-ceiling story is about: total/n_dev instead of total.
        Quantized shards count 1 byte per element plus their fp32
        per-row scales."""
        rows = self.rows_dev_u + self.rows_dev_i
        if self.dtype == "int8":
            return rows * self.rank + rows * 4
        return rows * self.rank * 4

    def topk(self, user_ixs, k: int):
        ixs = np.asarray(user_ixs, dtype=np.int32)
        if self.dtype == "int8":
            return topk_for_users_sharded_quant(
                self.user_shards, self.user_scales,
                self.item_shards, self.item_scales, ixs,
                k=int(k), n_items=self.n_items,
                rows_dev_u=self.rows_dev_u, rows_dev_i=self.rows_dev_i,
                mesh=self.mesh)
        return topk_for_users_sharded(
            self.user_shards, self.item_shards, ixs,
            k=int(k), n_items=self.n_items,
            rows_dev_u=self.rows_dev_u, rows_dev_i=self.rows_dev_i,
            mesh=self.mesh)

    @property
    def user_capacity(self) -> int:
        """Padded user-row capacity (rows_dev_u * n_dev): the headroom
        the realtime fold-in layer appends new users into."""
        return int(self.rows_dev_u) * self.n_shards

    def apply_user_rows(self, ixs, rows_fp32) -> "ShardedFactors":
        """A NEW ShardedFactors with ``rows_fp32`` scattered into the
        user matrix at global rows ``ixs`` (item shards unchanged — the
        fold-in contract is a fixed item matrix). fp32 layouts scatter
        the rows directly; int8 layouts re-quantize exactly the touched
        rows (per-row scales keep it local and exact) and scatter rows
        + scales in one dispatch. The caller publishes by swapping its
        model's ``sharding`` reference to the returned object — one
        atomic Python assignment, zero dropped queries."""
        ixs = np.asarray(ixs, dtype=np.int32)
        rows = np.asarray(rows_fp32, dtype=np.float32)
        if self.dtype == "int8":
            from predictionio_tpu.ops.quant import quantize_rows
            q_rows, scales = quantize_rows(rows)
            new_q, new_s = scatter_user_rows_sharded_quant(
                self.user_shards, self.user_scales, ixs, q_rows, scales,
                mesh=self.mesh)
            return dataclasses.replace(
                self, user_shards=new_q, user_scales=new_s)
        new_u = scatter_user_rows_sharded(
            self.user_shards, ixs, rows, mesh=self.mesh)
        return dataclasses.replace(self, user_shards=new_u)

    @property
    def item_capacity(self) -> int:
        """Padded item-row capacity (rows_dev_i * n_dev): the headroom
        the realtime fold-in layer appends new items into."""
        return int(self.rows_dev_i) * self.n_shards

    def apply_item_rows(self, ixs, rows_fp32) -> "ShardedFactors":
        """Item-side twin of :meth:`apply_user_rows`: scatter folded
        ITEM rows into the sharded item matrix (user shards unchanged —
        the transposed fold-in half-step holds the user matrix fixed).
        The scatter kernels are shape-generic functional updates, so
        the item side rides the SAME jitted programs with the item
        shapes — no new kernels, just new (shape, bucket) entries in
        the AOT registry via scatter_item_program_specs."""
        ixs = np.asarray(ixs, dtype=np.int32)
        rows = np.asarray(rows_fp32, dtype=np.float32)
        if self.dtype == "int8":
            from predictionio_tpu.ops.quant import quantize_rows
            q_rows, scales = quantize_rows(rows)
            new_q, new_s = scatter_user_rows_sharded_quant(
                self.item_shards, self.item_scales, ixs, q_rows, scales,
                mesh=self.mesh)
            return dataclasses.replace(
                self, item_shards=new_q, item_scales=new_s)
        new_v = scatter_user_rows_sharded(
            self.item_shards, ixs, rows, mesh=self.mesh)
        return dataclasses.replace(self, item_shards=new_v)

    def summary(self) -> Dict[str, Any]:
        out = {
            "shards": self.n_shards,
            "merge": MERGE_STRATEGY,
            "rowsPerShard": {"users": self.rows_dev_u,
                             "items": self.rows_dev_i},
            "perShardFactorBytes": self.per_shard_bytes(),
        }
        if self.dtype == "int8":
            # only on quantized layouts: fp32 sharded deploys keep the
            # exact PR 8 key set (wire parity on GET /)
            out["dtype"] = self.dtype
        return out

    def quant_summary(self) -> Dict[str, Any]:
        """The quant block of a sharded int8 layout (GET / "quant"
        section + ops/quant.summarize_deploy)."""
        rows = self.n_users + self.n_items
        return {
            "dtype": "int8",
            "shards": self.n_shards,
            "int8Bytes": rows * self.rank + rows * 4,
            "fp32Bytes": rows * self.rank * 4,
            "recall": self.quant_recall,
            "exact1": self.quant_exact1,
        }


def shard_factors(user_factors, item_factors,
                  n_shards: Optional[int] = None,
                  mesh: Optional[Mesh] = None,
                  quant: Optional[Any] = None) -> ShardedFactors:
    """Lay a model's factor matrices out row-sharded for serving.

    Default mesh: all visible devices on a fresh 1-D "shard" axis.
    ``quant`` (an ops/quant.QuantizedFactors) shards the int8 blocks
    and their fp32 per-row scale vectors instead of the fp32 matrices —
    the sharded AND quantized layout, per-device footprint
    ~total/(4·n_dev). Records the ``pio_serve_shards`` gauge and the
    /debug/device.json sharding block so `pio doctor` can see the
    layout."""
    if mesh is None:
        devices = jax.devices()
        if n_shards is not None:
            if n_shards > len(devices):
                raise ValueError(
                    f"requested {n_shards} serving shards but only "
                    f"{len(devices)} devices are visible")
            devices = devices[:n_shards]
        mesh = Mesh(np.asarray(devices), (AXIS,))
    axis = mesh.axis_names[0]
    n_dev = mesh.devices.size
    if quant is not None:
        U, V = quant.u_q, quant.v_q
    else:
        U = np.asarray(user_factors, dtype=np.float32)
        V = np.asarray(item_factors, dtype=np.float32)
    n_users, rank = U.shape
    n_items = V.shape[0]
    rows_u = _rows_dev(n_users, n_dev)
    rows_i = _rows_dev(n_items, n_dev)
    row_spec = NamedSharding(mesh, P(axis, None))
    extra: Dict[str, Any] = {}
    if quant is not None:
        vec_spec = NamedSharding(mesh, P(axis))
        extra = {
            "user_scales": _shard_rows(quant.u_scale, rows_u, vec_spec),
            "item_scales": _shard_rows(quant.v_scale, rows_i, vec_spec),
            "dtype": "int8",
            "quant_recall": quant.recall,
            "quant_exact1": quant.exact1,
        }
    sharded = ShardedFactors(
        mesh=mesh, n_users=n_users, n_items=n_items, rank=rank,
        rows_dev_u=rows_u, rows_dev_i=rows_i,
        user_shards=_shard_rows(U, rows_u, row_spec),
        item_shards=_shard_rows(V, rows_i, row_spec), **extra)
    record_state(sharded.summary())
    logger.info("factors sharded for serving: %d users + %d items x r=%d "
                "(%s) over %d device(s), %.1f MiB/shard", n_users, n_items,
                rank, sharded.dtype, n_dev,
                sharded.per_shard_bytes() / 2**20)
    return sharded


def record_state(summary: Optional[Dict[str, Any]]) -> None:
    """Publish (or with None, clear) the live sharded-serving layout:
    the ``pio_serve_shards`` gauge + the /debug/device.json sharding
    block `pio doctor`'s sharding line reads."""
    telemetry.registry().gauge(
        "pio_serve_shards",
        "Serving shards the deployed factor matrices are split over "
        "(0 = replicated single-device serving)").labels().set(
            float(summary.get("shards", 0)) if summary else 0.0)
    devicewatch.note_sharding(summary)


# ---------------------------------------------------------------------------
# AOT program enumeration (serving/aot.py plugs these into prebuild)
# ---------------------------------------------------------------------------

def sharded_program_specs(sharded: ShardedFactors, buckets: Iterable[int],
                          ks: Iterable[int]) -> List[Any]:
    """One ProgramSpec per (bucket x k) sharded serving program, with
    prime closures over the live sharded arrays so deploy prebuild
    warms the exact jit dispatch cache the flush path hits. Bucket 1 is
    always included: the inline (batching-off) path serves single
    queries through the same sharded kernel at b=1."""
    from predictionio_tpu.serving.aot import ProgramSpec

    out: List[Any] = []
    name = ("topk_for_users_sharded_quant" if sharded.dtype == "int8"
            else "topk_for_users_sharded")
    all_buckets = sorted({1, *(int(b) for b in buckets)})
    for b in all_buckets:
        for k in ks:
            out.append(ProgramSpec(
                name=name,
                key=(name, sharded.n_users,
                     sharded.n_items, sharded.rank, sharded.n_shards,
                     int(b), int(k)),
                lower=_sharded_lowerer(sharded, int(b), int(k)),
                prime=_sharded_primer(sharded, int(b), int(k))))
    return out


def _sharded_lowerer(sharded: ShardedFactors, bucket: int, k: int):
    def lower():
        axis = sharded.mesh.axis_names[0]
        row = NamedSharding(sharded.mesh, P(axis, None))
        vec = NamedSharding(sharded.mesh, P(axis))
        rep = NamedSharding(sharded.mesh, P())
        n_dev = sharded.n_shards
        statics = dict(k=k, n_items=sharded.n_items,
                       rows_dev_u=sharded.rows_dev_u,
                       rows_dev_i=sharded.rows_dev_i, mesh=sharded.mesh)
        ixs = jax.ShapeDtypeStruct((bucket,), np.int32, sharding=rep)
        if sharded.dtype == "int8":
            return topk_for_users_sharded_quant.lower(
                jax.ShapeDtypeStruct(
                    (sharded.rows_dev_u * n_dev, sharded.rank),
                    np.int8, sharding=row),
                jax.ShapeDtypeStruct(
                    (sharded.rows_dev_u * n_dev,), np.float32,
                    sharding=vec),
                jax.ShapeDtypeStruct(
                    (sharded.rows_dev_i * n_dev, sharded.rank),
                    np.int8, sharding=row),
                jax.ShapeDtypeStruct(
                    (sharded.rows_dev_i * n_dev,), np.float32,
                    sharding=vec),
                ixs, **statics)
        return topk_for_users_sharded.lower(
            jax.ShapeDtypeStruct(
                (sharded.rows_dev_u * n_dev, sharded.rank),
                np.float32, sharding=row),
            jax.ShapeDtypeStruct(
                (sharded.rows_dev_i * n_dev, sharded.rank),
                np.float32, sharding=row),
            ixs, **statics)
    return lower


def _sharded_primer(sharded: ShardedFactors, bucket: int, k: int):
    def prime():
        # index 0 is always a real user row; device_get ends the
        # dispatch in a real host transfer (KNOWN_ISSUES #3)
        ix = np.zeros((bucket,), dtype=np.int32)
        jax.device_get(sharded.topk(ix, k))
    return prime


def scatter_program_specs(sharded: ShardedFactors,
                          buckets: Iterable[int]) -> List[Any]:
    """One ProgramSpec per fold-in publication bucket: the row-scatter
    program the realtime layer dispatches every tick. Prebuilt with the
    serving programs so the first fold-in publication after /readyz
    compiles nothing (post-warmup recompiles stay 0 with fold-in on)."""
    from predictionio_tpu.serving.aot import ProgramSpec

    name = ("scatter_user_rows_sharded_quant" if sharded.dtype == "int8"
            else "scatter_user_rows_sharded")
    out: List[Any] = []
    for b in sorted({int(x) for x in buckets}):
        out.append(ProgramSpec(
            name=name,
            key=(name, sharded.n_users, sharded.rank,
                 sharded.n_shards, int(b)),
            prime=_scatter_primer(sharded, int(b))))
    return out


def _scatter_primer(sharded: ShardedFactors, bucket: int):
    def prime():
        # a no-op update of row 0 onto itself: same program, same
        # shapes, harmless content. int8 layouts prime the quantized
        # scatter through apply_user_rows (zero rows quantize to zeros
        # with scale 1.0 — row 0 is headroom-or-real either way, and
        # the result is discarded after the transfer below)
        ix = np.zeros((bucket,), dtype=np.int32)
        if sharded.dtype == "int8":
            rows = np.zeros((bucket, sharded.rank), dtype=np.float32)
            from predictionio_tpu.ops.quant import quantize_rows
            q_rows, scales = quantize_rows(rows)
            jax.device_get(scatter_user_rows_sharded_quant(
                sharded.user_shards, sharded.user_scales, ix, q_rows,
                scales, mesh=sharded.mesh)[1][:1])
        else:
            rows = jax.device_get(sharded.user_shards[:1])
            rows = np.broadcast_to(rows, (bucket, sharded.rank)).copy()
            jax.device_get(scatter_user_rows_sharded(
                sharded.user_shards, ix, rows, mesh=sharded.mesh)[:1])
    return prime


def scatter_item_program_specs(sharded: ShardedFactors,
                               buckets: Iterable[int]) -> List[Any]:
    """Item-side twin of :func:`scatter_program_specs`: the SAME
    shape-generic scatter kernels dispatched with the item-shard
    shapes, so item fold-in publication also compiles nothing
    post-warmup. Distinct registry keys come from the item row count
    (the kernels are keyed by (name, rows, rank, shards, bucket))."""
    from predictionio_tpu.serving.aot import ProgramSpec

    name = ("scatter_user_rows_sharded_quant" if sharded.dtype == "int8"
            else "scatter_user_rows_sharded")
    out: List[Any] = []
    for b in sorted({int(x) for x in buckets}):
        out.append(ProgramSpec(
            name=name,
            key=(name, sharded.n_items, sharded.rank,
                 sharded.n_shards, int(b)),
            prime=_item_scatter_primer(sharded, int(b))))
    return out


def _item_scatter_primer(sharded: ShardedFactors, bucket: int):
    def prime():
        ix = np.zeros((bucket,), dtype=np.int32)
        if sharded.dtype == "int8":
            rows = np.zeros((bucket, sharded.rank), dtype=np.float32)
            from predictionio_tpu.ops.quant import quantize_rows
            q_rows, scales = quantize_rows(rows)
            jax.device_get(scatter_user_rows_sharded_quant(
                sharded.item_shards, sharded.item_scales, ix, q_rows,
                scales, mesh=sharded.mesh)[1][:1])
        else:
            rows = jax.device_get(sharded.item_shards[:1])
            rows = np.broadcast_to(rows, (bucket, sharded.rank)).copy()
            jax.device_get(scatter_user_rows_sharded(
                sharded.item_shards, ix, rows, mesh=sharded.mesh)[:1])
    return prime


# ---------------------------------------------------------------------------
# AOT registry entry (the tier-1 lint in tests/test_aot.py checks every
# @jax.jit def in this module against the registry)
# ---------------------------------------------------------------------------

def _register() -> None:
    from predictionio_tpu.serving import aot
    aot.register_jit(
        "topk_for_users_sharded", topk_for_users_sharded, kind="serving",
        note="enumerated per (bucket, k) by sharded_program_specs when "
             "prepare_serving chose the sharded layout; mesh-topology-"
             "specific, so the train-time declared export skips it and "
             "the deploy-side prebuild owns it")
    aot.register_jit(
        "topk_for_users_sharded_quant", topk_for_users_sharded_quant,
        kind="serving",
        note="enumerated per (bucket, k) by sharded_program_specs when "
             "the sharded layout carries int8 factors (ops/quant.py); "
             "mesh-topology-specific like its fp32 sibling, deploy-side "
             "prebuild owns it")
    aot.register_jit(
        "scatter_user_rows_sharded", scatter_user_rows_sharded,
        kind="serving",
        note="fold-in publication scatter (realtime/foldin.py); "
             "enumerated per publication bucket by scatter_program_specs "
             "when the deploy runs with fold-in on a sharded layout")
    aot.register_jit(
        "scatter_user_rows_sharded_quant", scatter_user_rows_sharded_quant,
        kind="serving",
        note="int8 fold-in publication scatter (rows re-quantized "
             "per-row host-side); enumerated per publication bucket by "
             "scatter_program_specs on int8 sharded fold-in deploys")


_register()
