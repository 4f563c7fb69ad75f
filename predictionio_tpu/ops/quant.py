"""Quantized serving: int8 factor matrices with per-row fp32 scales.

Serving reads two fp32 factor matrices to produce k indices — pure HBM
bandwidth — and the row-sharded path (parallel/serve_dist.py) made HBM
*capacity* the binding constraint on catalog size. Symmetric per-row
int8 quantization cuts both ~4x: each factor row r_i stores
``q_i = round(r_i / s_i)`` as int8 with ``s_i = max|r_i| / 127`` kept
as one fp32 scale per row.

Scoring never dequantizes: the user x item dot products run as int8 x
int8 ``dot_general`` with ``preferred_element_type=int32`` (EXACT
integer arithmetic — no accumulation-order nondeterminism), then one
fused elementwise rescale ``s32 * (scale_u[u] * scale_v)`` recovers
fp32 scores. Because the integer part is exact and the rescale is
elementwise, both quantized serving paths — the replicated kernel here
and the row-sharded shard_map kernel (parallel/serve_dist.py) — produce
BIT-IDENTICAL (values, indices), ties included (stable_topk's
lowest-index rule).

Contract: bit-parity against the fp32 path is off the table for int8,
so the gate is RANKING parity — recall@k >= 0.99 and exact-match@1 >=
0.999 on the trained model (tier-1; KNOWN_ISSUES #12).
:func:`ranking_parity` measures it at deploy time on a deterministic
user sample; "auto" mode falls back to fp32 serving
(and says so on the `pio doctor` quant line) when the model misses the
bar, "on" keeps quantizing and records the value.

Mode resolution (``pio deploy --serve-quant auto/on/off``, env override
``PIO_SERVE_QUANT``): "off" is today's bit-compatible fp32 path, wire
byte for wire byte; "on" always quantizes; "auto" quantizes only on a
real accelerator backend (the tier-1 CPU harness serves fp32 by
default) and only when the ranking-parity probe passes. ``/reload``
hot-swap re-quantizes on load — the int8 copies are the small
footprint, so the swap window argument that keeps "auto" sharding
replicated does not apply here.
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

from predictionio_tpu.common import devicewatch, telemetry
from predictionio_tpu.ops.topk import NEG_INF, stable_topk

logger = logging.getLogger("predictionio_tpu.quant")

#: symmetric int8 range: round(row / scale) lands in [-127, 127]
QMAX = 127.0

#: the transposed item layout pads its item axis up to a multiple of
#: this many columns (4 x the 128-lane register width)
ITEM_TILE = 512

#: the fp32 itemsize quantization is measured against
_F32 = 4


# ---------------------------------------------------------------------------
# quantization (host-side, once per model load)
# ---------------------------------------------------------------------------

def quantize_rows(M: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Symmetric per-row int8 quantization: ``(q, scales)`` with
    ``q[i] = clip(round(M[i] / scales[i]), -127, 127)`` and
    ``scales[i] = max|M[i]| / 127`` (1.0 for an all-zero row, which
    quantizes to zeros — no 0/0). Host numpy: runs once at train/model-
    load time, never on the query path."""
    M = np.asarray(M, dtype=np.float32)
    amax = np.abs(M).max(axis=1)
    scales = np.where(amax > 0, amax / QMAX, 1.0).astype(np.float32)
    q = np.clip(np.rint(M / scales[:, None]), -QMAX, QMAX).astype(np.int8)
    return q, scales


def dequantize_rows(q: np.ndarray, scales: np.ndarray) -> np.ndarray:
    """The fp32 matrix a (q, scales) pair represents (tests/debugging —
    serving never materializes it)."""
    return q.astype(np.float32) * np.asarray(scales, np.float32)[:, None]


@dataclasses.dataclass
class QuantizedFactors:
    """One model's factor matrices quantized, host-side.

    Plain numpy throughout, so the container rides model_io's
    structural pickle walk unchanged (int8 blocks persist and restore
    byte-exact); the device layouts — replicated
    (:class:`QuantizedServing`) and row-sharded
    (``serve_dist.shard_factors(..., quant=...)``) — are built FROM it
    at deploy time. ``recall``/``exact1`` hold the most recent
    ranking-parity probe against the fp32 factors."""
    u_q: np.ndarray          # (n_users, rank) int8
    u_scale: np.ndarray      # (n_users,) fp32
    v_q: np.ndarray          # (n_items, rank) int8
    v_scale: np.ndarray      # (n_items,) fp32
    recall: Optional[float] = None
    exact1: Optional[float] = None

    @classmethod
    def from_factors(cls, user_factors, item_factors) -> "QuantizedFactors":
        u_q, u_scale = quantize_rows(user_factors)
        v_q, v_scale = quantize_rows(item_factors)
        return cls(u_q=u_q, u_scale=u_scale, v_q=v_q, v_scale=v_scale)

    @property
    def n_users(self) -> int:
        return int(self.u_q.shape[0])

    @property
    def n_items(self) -> int:
        return int(self.v_q.shape[0])

    @property
    def rank(self) -> int:
        return int(self.u_q.shape[1])

    def int8_bytes(self) -> int:
        """Serving footprint of the quantized factors (int8 blocks +
        fp32 scale vectors)."""
        return ((self.n_users + self.n_items) * self.rank
                + (self.n_users + self.n_items) * _F32)

    def fp32_bytes(self) -> int:
        """What the same factors cost un-quantized."""
        return (self.n_users + self.n_items) * self.rank * _F32


# ---------------------------------------------------------------------------
# ranking-parity probe (the deploy-time gate value)
# ---------------------------------------------------------------------------

def ranking_parity(user_factors, item_factors, qf: QuantizedFactors,
                   k: int = 10, sample: int = 256) -> Dict[str, Any]:
    """recall@k and exact-match@1 of the quantized ranking against the
    fp32 ranking, on a deterministic evenly-spaced user sample (no RNG:
    the probe must give the same verdict on every load of the same
    model). Host numpy — deploy-time only, never on the query path.

    Both rankings break ties by lowest item index (stable argsort on
    the negated scores), matching the serving kernels' stable_topk
    rule, so a model with exactly-tied scores is not penalized for the
    tie order."""
    U = np.asarray(user_factors, np.float32)
    V = np.asarray(item_factors, np.float32)
    n_users, n_items = U.shape[0], V.shape[0]
    k = min(int(k), n_items)
    take = min(int(sample), n_users)
    ixs = np.unique(np.linspace(0, n_users - 1, take).astype(np.int64))
    sf = U[ixs] @ V.T
    s32 = qf.u_q[ixs].astype(np.int32) @ qf.v_q.astype(np.int32).T
    sq = s32.astype(np.float32) * (qf.u_scale[ixs][:, None]
                                   * qf.v_scale[None, :])
    top_f = np.argsort(-sf, axis=1, kind="stable")[:, :k]
    top_q = np.argsort(-sq, axis=1, kind="stable")[:, :k]
    inter = np.asarray([np.intersect1d(a, b).size
                        for a, b in zip(top_f, top_q)])
    return {
        "k": k,
        "sampledUsers": int(ixs.size),
        "recall": float(np.mean(inter / k)),
        "exact1": float(np.mean(top_f[:, 0] == top_q[:, 0])),
    }


def ranking_agreement(user_factors_a, item_factors_a,
                      user_factors_b, item_factors_b,
                      k: int = 10, sample: int = 256,
                      user_map: Optional[np.ndarray] = None,
                      item_map: Optional[np.ndarray] = None
                      ) -> Dict[str, Any]:
    """recall@k and exact-match@1 of factor pair B's ranking against
    factor pair A's, on the same deterministic evenly-spaced user
    sample and stable tie rule as :func:`ranking_parity` — the probe
    generalized from "quantized vs fp32 of ONE model" to "any two
    models over a common vocabulary" (autotrain validates a retrain
    candidate against the live generation with it).

    ``user_map``/``item_map`` align B's index space to A's: entry i is
    B's index for A's user/item i (identity when omitted — same
    vocabulary). B's top-k indices are mapped back into A's item space
    before the overlap is scored, so the figure reads "of A's top k,
    how many does B also rank top k"."""
    Ua = np.asarray(user_factors_a, np.float32)
    Va = np.asarray(item_factors_a, np.float32)
    Ub = np.asarray(user_factors_b, np.float32)
    Vb = np.asarray(item_factors_b, np.float32)
    n_users = Ua.shape[0]
    if user_map is None:
        user_map = np.arange(min(n_users, Ub.shape[0]), dtype=np.int64)
    else:
        user_map = np.asarray(user_map, np.int64)
    if item_map is None:
        item_map = np.arange(min(Va.shape[0], Vb.shape[0]),
                             dtype=np.int64)
    else:
        item_map = np.asarray(item_map, np.int64)
    n_common_users = int(user_map.shape[0])
    n_common_items = int(item_map.shape[0])
    if n_common_users == 0 or n_common_items == 0:
        return {"k": 0, "sampledUsers": 0, "commonItems": 0,
                "recall": 0.0, "exact1": 0.0}
    k = min(int(k), n_common_items)
    take = min(int(sample), n_common_users)
    pick = np.unique(np.linspace(0, n_common_users - 1,
                                 take).astype(np.int64))
    sa = Ua[pick] @ Va[item_map].T
    sb = Ub[user_map[pick]] @ Vb[item_map].T
    top_a = np.argsort(-sa, axis=1, kind="stable")[:, :k]
    top_b = np.argsort(-sb, axis=1, kind="stable")[:, :k]
    inter = np.asarray([np.intersect1d(a, b).size
                        for a, b in zip(top_a, top_b)])
    return {
        "k": k,
        "sampledUsers": int(pick.size),
        "commonItems": n_common_items,
        "recall": float(np.mean(inter / max(k, 1))),
        "exact1": float(np.mean(top_a[:, 0] == top_b[:, 0])),
    }


def recall_floor() -> float:
    """The recall@k below which "auto" mode refuses to quantize
    (``PIO_SERVE_QUANT_RECALL_MIN``, default 0.99 — the KNOWN_ISSUES
    #12 ranking-parity contract)."""
    try:
        return float(os.environ.get("PIO_SERVE_QUANT_RECALL_MIN", "0.99"))
    except ValueError:
        return 0.99


def note_fallback(reason: str, **fields: Any) -> None:
    """Journal a quantized-serving fallback to fp32 (probe refusal,
    failed quantization, failed int8 layout): the operator asked for
    the 4x-smaller footprint and is not getting it — `pio doctor`
    WARNs on the live state, this records WHEN and WHY it happened."""
    from predictionio_tpu.common import journal
    journal.emit("quant", f"quantized serving fell back to fp32: "
                 f"{reason}", level=journal.WARN, reason=reason, **fields)


def accept_parity(parity: Dict[str, Any],
                  mode: Optional[str] = None) -> bool:
    """Does this probe result clear the deploy gate? "on" always serves
    quantized (the operator's explicit call — the value is recorded and
    `pio doctor` shows it); "auto" requires recall@k >= the floor."""
    if configured_mode(mode) == "on":
        return True
    return float(parity.get("recall", 0.0)) >= recall_floor()


# ---------------------------------------------------------------------------
# mode resolution: ServerConfig.serve_quant + PIO_SERVE_QUANT
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
    raise ValueError(f"serve-quant mode must be auto/on/off, got {mode!r}")


def configured_mode(mode: Optional[str] = None) -> str:
    """Effective mode: ``PIO_SERVE_QUANT`` wins over the config value
    (the PIO_SERVE_SHARD / PIO_AOT override shape)."""
    env = os.environ.get("PIO_SERVE_QUANT", "")
    if env:
        return _normalize_mode(env)
    if mode is not None:
        return _normalize_mode(mode)
    return _normalize_mode(getattr(_scope, "mode", "auto"))


@contextlib.contextmanager
def deploy_scope(mode: str, reload: bool = False):
    """Install the deploy's serve-quant mode for the calling thread
    (QueryAPI._load wraps prepare_serving in this, next to
    serve_dist.deploy_scope). Unlike sharding, "auto" does NOT fall
    back on /reload — re-quantizing on hot-swap is the contract (the
    int8 copies are the small footprint), so ``reload`` is recorded
    for observability only. Validates eagerly so a bad config fails
    the deploy, not a query."""
    _normalize_mode(mode)
    prev = (getattr(_scope, "mode", None), getattr(_scope, "reload", None))
    _scope.mode, _scope.reload = mode, bool(reload)
    try:
        yield
    finally:
        _scope.mode, _scope.reload = prev


def _accelerator_platform() -> bool:
    """A real accelerator backend? The tier-1 CPU harness answers
    False, so "auto" keeps the bit-compatible fp32 path there (tests
    monkeypatch this to exercise the auto path)."""
    try:
        return jax.default_backend() != "cpu"
    except Exception:
        return False


def serving_enabled(mode: Optional[str] = None) -> bool:
    """Should prepare_serving quantize this model's factors? ("auto"
    additionally requires the ranking-parity probe to pass — that half
    of the decision lives in :func:`accept_parity`.)"""
    m = configured_mode(mode)
    if m == "off":
        return False
    if m == "on":
        return True
    return _accelerator_platform()


# ---------------------------------------------------------------------------
# the dequantize-free serving kernels
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("k", "n_items"))
def topk_for_users_quant(
    u_q: jnp.ndarray,        # (n_users, r) int8
    u_scale: jnp.ndarray,    # (n_users,) fp32
    vt_q: jnp.ndarray,       # (r, n_pad) int8 — item factors TRANSPOSED
    v_scale: jnp.ndarray,    # (n_pad,) fp32, 0 on pad columns
    user_ixs: jnp.ndarray,   # (b,) int32
    *,
    k: int,
    n_items: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Batched quantized serve: B int8 row gathers + ONE int8 x int8
    ``dot_general`` (int32 accumulate — exact) + the fused rescale +
    stable_topk, in a single dispatch. ``user_ixs`` must be in-bounds —
    callers resolve them against the model's user vocabulary first
    (KNOWN_ISSUES #5). Item columns at/past ``n_items`` are layout
    padding, masked to NEG_INF so they can never rank — under either
    selection stable_topk makes over the n_pad columns (two stages on
    a long catalog, the whole-row sort on a short one: the same bits).
    Bit-identical (values AND indices, ties included) to the sharded
    quant kernel — the integer scores are exact and the rescale is
    elementwise, so there is no accumulation-order drift between the
    paths."""
    with jax.named_scope("gather"):
        Q = jnp.take(u_q, user_ixs, axis=0)                  # (b, r)
        su = jnp.take(u_scale, user_ixs, axis=0)             # (b,)
    with jax.named_scope("score"):
        s32 = lax.dot_general(Q, vt_q, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    with jax.named_scope("rescale"):                         # (b, n_pad)
        scores = s32.astype(jnp.float32) * (su[:, None]
                                            * v_scale[None, :])
        gid = lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        scores = jnp.where(gid < n_items, scores, NEG_INF)
    with jax.named_scope("select"):
        return stable_topk(scores, k)


@jax.jit
def scatter_user_rows_quant(
    u_q: jnp.ndarray,        # (n_users, r) int8, device
    u_scale: jnp.ndarray,    # (n_users,) fp32, device
    ixs: jnp.ndarray,        # (b,) int32 rows to replace
    q_rows: jnp.ndarray,     # (b, r) int8 replacement rows
    scales: jnp.ndarray,     # (b,) fp32 replacement per-row scales
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fold-in publication scatter for the replicated quantized layout:
    replace the touched user rows AND their per-row scales in one
    dispatch (realtime/foldin.py re-quantizes exactly the updated rows
    host-side — per-row symmetric quantization keeps that local and
    exact). ``ixs`` must be in-bounds (the worker's capacity
    bookkeeping guarantees it, KNOWN_ISSUES #5) and duplicate indices
    must carry identical rows. Returns NEW arrays — the caller swaps a
    rebuilt QuantizedServing in one atomic reference assignment."""
    return u_q.at[ixs].set(q_rows), u_scale.at[ixs].set(scales)


@jax.jit
def scatter_item_cols_quant(
    vt_q: jnp.ndarray,       # (r, n_pad) int8, device — items TRANSPOSED
    v_scale: jnp.ndarray,    # (n_pad,) fp32, device
    ixs: jnp.ndarray,        # (b,) int32 item columns to replace
    q_rows: jnp.ndarray,     # (b, r) int8 replacement item rows
    scales: jnp.ndarray,     # (b,) fp32 replacement per-item scales
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Item fold-in publication scatter for the replicated quantized
    layout: the item matrix serves TRANSPOSED, so folded item rows land
    as COLUMNS of ``vt_q`` plus their per-item scales, in one dispatch.
    Same contract as :func:`scatter_user_rows_quant`: in-bounds ``ixs``
    (item capacity bookkeeping), duplicate indices carry identical
    rows, and the caller swaps a rebuilt QuantizedServing in one atomic
    reference assignment."""
    return (vt_q.at[:, ixs].set(q_rows.T.astype(vt_q.dtype)),
            v_scale.at[ixs].set(scales))


@partial(jax.jit, static_argnames=("k", "n_items"))
def topk_for_user_quant(
    u_q: jnp.ndarray,        # (n_users, r) int8
    u_scale: jnp.ndarray,    # (n_users,) fp32
    vt_q: jnp.ndarray,       # (r, n_pad) int8
    v_scale: jnp.ndarray,    # (n_pad,) fp32, 0 on pad columns
    user_ix: jnp.ndarray,    # () int32
    *,
    k: int,
    n_items: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Inline (batching-off) single-query quantized serve, one fused
    dispatch. ``user_ix`` must be in-bounds (KNOWN_ISSUES #5).
    Bit-identical to row b of the batched kernel — same exact integer
    dot, same elementwise rescale, same stable_topk tie rule."""
    with jax.named_scope("gather"):
        q = jnp.take(u_q, user_ix, axis=0)                   # (r,)
        su = jnp.take(u_scale, user_ix, axis=0)              # ()
    with jax.named_scope("score"):
        s32 = lax.dot_general(q, vt_q, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    with jax.named_scope("rescale"):                         # (n_pad,)
        scores = s32.astype(jnp.float32) * (su * v_scale)
        gid = lax.broadcasted_iota(jnp.int32, scores.shape, 0)
        scores = jnp.where(gid < n_items, scores, NEG_INF)
    with jax.named_scope("select"):
        return stable_topk(scores, k)


# ---------------------------------------------------------------------------
# the replicated device layout
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class QuantizedServing:
    """One model's quantized factors laid out device-resident for
    replicated serving, plus the statics its programs need. ``topk`` /
    ``topk_one`` are the drop-in replacements for the fp32
    ``topk_for_users`` / ``topk_for_user`` calls.

    The item matrix lives TRANSPOSED, ``(rank, n_pad)`` with n_pad
    rounded up to :data:`ITEM_TILE`."""
    u_q: Any                 # (n_users, r) int8, device
    u_scale: Any             # (n_users,) fp32, device
    vt_q: Any                # (r, n_pad) int8, device
    v_scale: Any             # (n_pad,) fp32, device (0 on pad columns)
    n_users: int
    n_items: int
    rank: int
    recall: Optional[float] = None
    exact1: Optional[float] = None

    @classmethod
    def build(cls, qf: QuantizedFactors) -> "QuantizedServing":
        n_items = qf.n_items
        n_pad = -(-max(n_items, 1) // ITEM_TILE) * ITEM_TILE
        vt = np.zeros((qf.rank, n_pad), dtype=np.int8)
        vt[:, :n_items] = qf.v_q.T
        sv = np.zeros((n_pad,), dtype=np.float32)
        sv[:n_items] = qf.v_scale
        return cls(
            u_q=jax.device_put(qf.u_q),
            u_scale=jax.device_put(qf.u_scale),
            vt_q=jax.device_put(vt),
            v_scale=jax.device_put(sv),
            n_users=qf.n_users, n_items=n_items, rank=qf.rank,
            recall=qf.recall, exact1=qf.exact1)

    def topk(self, user_ixs, k: int):
        ixs = np.asarray(user_ixs, dtype=np.int32)
        return topk_for_users_quant(
            self.u_q, self.u_scale, self.vt_q, self.v_scale, ixs,
            k=int(k), n_items=self.n_items)

    def topk_one(self, user_ix, k: int):
        return topk_for_user_quant(
            self.u_q, self.u_scale, self.vt_q, self.v_scale,
            jnp.int32(user_ix), k=int(k), n_items=self.n_items)

    def apply_user_rows(self, ixs, rows_fp32) -> "QuantizedServing":
        """A NEW QuantizedServing with ``rows_fp32`` re-quantized
        per-row and scattered into the user matrix at ``ixs`` (the item
        layout is untouched — fold-in's fixed-item-matrix contract).
        The caller publishes by swapping its model's ``quant``
        reference: one atomic assignment, so every in-flight query
        reads a consistent (rows, scales) pair."""
        ixs = np.asarray(ixs, dtype=np.int32)
        q_rows, scales = quantize_rows(np.asarray(rows_fp32, np.float32))
        new_q, new_s = scatter_user_rows_quant(
            self.u_q, self.u_scale, ixs, q_rows, scales)
        return dataclasses.replace(self, u_q=new_q, u_scale=new_s)

    def apply_item_rows(self, ixs, rows_fp32) -> "QuantizedServing":
        """The item-side twin of :meth:`apply_user_rows`: ``rows_fp32``
        re-quantized per-row and scattered as COLUMNS of the transposed
        item layout at ``ixs`` (item fold-in publishes into the item
        headroom the deploy pre-padded; ``n_items`` is that padded
        count, so the statics — and the prebuilt programs — never
        change). Same one-atomic-swap publication contract."""
        ixs = np.asarray(ixs, dtype=np.int32)
        q_rows, scales = quantize_rows(np.asarray(rows_fp32, np.float32))
        new_vt, new_s = scatter_item_cols_quant(
            self.vt_q, self.v_scale, ixs, q_rows, scales)
        return dataclasses.replace(self, vt_q=new_vt, v_scale=new_s)

    def int8_bytes(self) -> int:
        """Logical serving footprint (int8 matrices + fp32 scales; same
        accounting as the sharded layout's quant_summary). The
        transposed layout additionally pads the item axis up to the
        tile — at most tile x rank extra bytes, noise at catalog scale
        — which HBM gauges report but this comparison figure omits so
        the int8-vs-fp32 ratio stays layout-independent."""
        rows = self.n_users + self.n_items
        return rows * self.rank + rows * _F32

    def fp32_bytes(self) -> int:
        return (self.n_users + self.n_items) * self.rank * _F32

    def summary(self) -> Dict[str, Any]:
        return {
            "dtype": "int8",
            "tile": ITEM_TILE,
            "int8Bytes": self.int8_bytes(),
            "fp32Bytes": self.fp32_bytes(),
            "recall": self.recall,
            "exact1": self.exact1,
        }


# ---------------------------------------------------------------------------
# AOT program enumeration (serving/aot.py plugs these into prebuild)
# ---------------------------------------------------------------------------

def quant_program_specs(qs: QuantizedServing, buckets: Iterable[int],
                        ks: Iterable[int]) -> List[Any]:
    """One ProgramSpec per (bucket x k) quantized serving program —
    the batched kernel the micro-batcher flushes onto — plus one per k
    for the inline single-query path. Prime closures dispatch the live
    jitted entry points so deploy prebuild warms the exact dispatch
    cache the flush hits; post-warmup recompiles stay 0 with quant on."""
    from predictionio_tpu.serving.aot import ProgramSpec

    out: List[Any] = []
    n_pad = int(np.shape(qs.vt_q)[1])
    for b in sorted({int(x) for x in buckets}):
        for k in ks:
            out.append(ProgramSpec(
                name="topk_for_users_quant",
                key=("topk_for_users_quant", qs.n_users, qs.n_items,
                     qs.rank, n_pad, int(b), int(k)),
                lower=_quant_users_lowerer(qs, int(b), int(k)),
                prime=_quant_users_primer(qs, int(b), int(k))))
    for k in ks:
        out.append(ProgramSpec(
            name="topk_for_user_quant",
            key=("topk_for_user_quant", qs.n_users, qs.n_items,
                 qs.rank, n_pad, int(k)),
            lower=_quant_user_lowerer(qs, int(k)),
            prime=_quant_user_primer(qs, int(k))))
    return out


def _quant_shapes(qs: QuantizedServing):
    n_pad = int(np.shape(qs.vt_q)[1])
    return (jax.ShapeDtypeStruct((qs.n_users, qs.rank), np.int8),
            jax.ShapeDtypeStruct((qs.n_users,), np.float32),
            jax.ShapeDtypeStruct((qs.rank, n_pad), np.int8),
            jax.ShapeDtypeStruct((n_pad,), np.float32))


def _quant_users_lowerer(qs: QuantizedServing, bucket: int, k: int):
    def lower():
        uq, su, vt, sv = _quant_shapes(qs)
        ix = jax.ShapeDtypeStruct((bucket,), np.int32)
        return topk_for_users_quant.lower(
            uq, su, vt, sv, ix, k=k, n_items=qs.n_items)
    return lower


def _quant_users_primer(qs: QuantizedServing, bucket: int, k: int):
    def prime():
        # index 0 is always a real user row (an OOB pad would gather
        # garbage, KNOWN_ISSUES #5); device_get ends the dispatch in a
        # real host transfer (KNOWN_ISSUES #3)
        ix = np.zeros((bucket,), dtype=np.int32)
        jax.device_get(qs.topk(ix, k))
    return prime


def _quant_user_lowerer(qs: QuantizedServing, k: int):
    def lower():
        uq, su, vt, sv = _quant_shapes(qs)
        return topk_for_user_quant.lower(
            uq, su, vt, sv, jax.ShapeDtypeStruct((), np.int32),
            k=k, n_items=qs.n_items)
    return lower


def _quant_user_primer(qs: QuantizedServing, k: int):
    def prime():
        jax.device_get(qs.topk_one(np.int32(0), k))
    return prime


def scatter_program_specs(qs: QuantizedServing,
                          buckets: Iterable[int]) -> List[Any]:
    """One ProgramSpec per fold-in publication bucket for the
    replicated int8 layout (the row+scale scatter the realtime layer
    dispatches per tick); prebuilt with the serving programs so
    fold-in publication never compiles post-warmup."""
    from predictionio_tpu.serving.aot import ProgramSpec

    out: List[Any] = []
    for b in sorted({int(x) for x in buckets}):
        out.append(ProgramSpec(
            name="scatter_user_rows_quant",
            key=("scatter_user_rows_quant", qs.n_users, qs.rank, int(b)),
            prime=_scatter_primer(qs, int(b))))
    return out


def _scatter_primer(qs: QuantizedServing, bucket: int):
    def prime():
        # no-op shaped update (results discarded): zero rows quantize
        # to zeros with scale 1.0; device_get ends the dispatch in a
        # real host transfer (KNOWN_ISSUES #3)
        ix = np.zeros((bucket,), dtype=np.int32)
        q_rows, scales = quantize_rows(
            np.zeros((bucket, qs.rank), dtype=np.float32))
        jax.device_get(scatter_user_rows_quant(
            qs.u_q, qs.u_scale, ix, q_rows, scales)[1][:1])
    return prime


def scatter_item_program_specs(qs: QuantizedServing,
                               buckets: Iterable[int]) -> List[Any]:
    """Item-side twin of :func:`scatter_program_specs`: one ProgramSpec
    per publication bucket for the transposed item-column scatter the
    realtime layer dispatches when items fold in."""
    from predictionio_tpu.serving.aot import ProgramSpec

    n_pad = int(np.shape(qs.vt_q)[1])
    out: List[Any] = []
    for b in sorted({int(x) for x in buckets}):
        out.append(ProgramSpec(
            name="scatter_item_cols_quant",
            key=("scatter_item_cols_quant", n_pad, qs.rank, int(b)),
            prime=_item_scatter_primer(qs, int(b))))
    return out


def _item_scatter_primer(qs: QuantizedServing, bucket: int):
    def prime():
        ix = np.zeros((bucket,), dtype=np.int32)
        q_rows, scales = quantize_rows(
            np.zeros((bucket, qs.rank), dtype=np.float32))
        jax.device_get(scatter_item_cols_quant(
            qs.vt_q, qs.v_scale, ix, q_rows, scales)[1][:1])
    return prime


# ---------------------------------------------------------------------------
# deploy-state surface: GET / "quant" section, gauges, /debug/device.json
# ---------------------------------------------------------------------------

def summarize_deploy(models: Iterable[Any],
                     requested: bool) -> Optional[Dict[str, Any]]:
    """The deploy's quantized-serving state, from the prepared models:
    the replicated handle's summary, the sharded layout's quant block,
    or — when quantization was requested but every model fell back to
    fp32 — an explicit ``fellBack`` record so `pio doctor` WARNs
    instead of the operator silently serving 4x the HBM they asked
    for. None when quant was neither requested nor active (wire
    parity: GET / keeps the legacy key set)."""
    for m in models:
        qs = getattr(m, "quant", None)
        if qs is not None:
            return {"enabled": True, **qs.summary()}
        sh = getattr(m, "sharding", None)
        if sh is not None and getattr(sh, "dtype", "float32") == "int8":
            out = {"enabled": True, "sharded": True, **sh.quant_summary()}
            return out
    if requested:
        return {"enabled": False, "fellBack": True}
    return None


def record_state(summary: Optional[Dict[str, Any]]) -> None:
    """Publish (or with None, clear) the live quantized-serving state:
    ``pio_serve_quant_mode``, the ``pio_serve_factor_bytes{dtype}``
    pair, ``pio_serve_quant_recall{metric}``, and the
    /debug/device.json quant block `pio doctor`'s quant line reads."""
    reg = telemetry.registry()
    active = bool(summary and summary.get("enabled"))
    reg.gauge(
        "pio_serve_quant_mode",
        "1 while the deployed factor matrices serve quantized (int8 + "
        "per-row scales); 0 = fp32 serving").labels().set(
            1.0 if active else 0.0)
    g_bytes = reg.gauge(
        "pio_serve_factor_bytes",
        "Deployed factor-matrix bytes by dtype: the live serving "
        "footprint (int8 includes the fp32 scale vectors) next to its "
        "fp32 equivalent", labelnames=("dtype",))
    g_recall = reg.gauge(
        "pio_serve_quant_recall",
        "Most recent deploy-time ranking-parity probe of the quantized "
        "path vs fp32 (recall@k and exact-match@1; KNOWN_ISSUES #12)",
        labelnames=("metric",))
    if active:
        g_bytes.labels(dtype="int8").set(float(summary.get("int8Bytes", 0)))
        g_bytes.labels(dtype="fp32").set(float(summary.get("fp32Bytes", 0)))
        if summary.get("recall") is not None:
            g_recall.labels(metric="recall").set(float(summary["recall"]))
        if summary.get("exact1") is not None:
            g_recall.labels(metric="exact1").set(float(summary["exact1"]))
    else:
        g_bytes.labels(dtype="int8").set(0.0)
        g_bytes.labels(dtype="fp32").set(0.0)
    devicewatch.note_quant(summary)


# ---------------------------------------------------------------------------
# AOT registry entry (the tier-1 lint checks every @jax.jit def in this
# module against the registry)
# ---------------------------------------------------------------------------

def _register() -> None:
    from predictionio_tpu.serving import aot
    aot.register_jit(
        "topk_for_users_quant", topk_for_users_quant, kind="serving",
        note="enumerated per (bucket, k) by quant_program_specs when "
             "prepare_serving chose the quantized replicated layout")
    aot.register_jit(
        "topk_for_user_quant", topk_for_user_quant, kind="serving",
        note="enumerated per k by quant_program_specs (inline / "
             "batching-off quantized path)")
    aot.register_jit(
        "scatter_user_rows_quant", scatter_user_rows_quant,
        kind="serving",
        note="fold-in publication scatter for the replicated int8 "
             "layout (realtime/foldin.py); enumerated per publication "
             "bucket by scatter_program_specs on fold-in deploys")
    aot.register_jit(
        "scatter_item_cols_quant", scatter_item_cols_quant,
        kind="serving",
        note="item fold-in publication scatter for the replicated int8 "
             "layout's transposed item matrix (realtime/foldin.py); "
             "enumerated per publication bucket by "
             "scatter_item_program_specs on fold-in deploys")


_register()
