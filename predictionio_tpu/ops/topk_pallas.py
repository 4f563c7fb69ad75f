"""Fused Pallas score->mask->per-tile-top-k over quantized factors.

The XLA quantized kernel (ops/quant.py) materializes the full
``(b, n_items)`` score matrix to HBM before the top-k sort reads it
back — at catalog scale that round-trip IS the serve latency. This
kernel tiles the ITEM axis instead: each grid step loads one
``(rank, tile)`` int8 block of the transposed item matrix into VMEM,
computes the int8 x int8 -> int32 scores for the whole batch against
that tile, rescales, masks the layout padding, and reduces the tile to
its top ``min(k, tile)`` (score, global index) candidates WITHOUT the
scores ever leaving VMEM. Only ``k x n_tiles`` candidates per query are
written back; a final two-key sort (the stable_topk tie rule) merges
them into the answer.

Exactness. The per-tile selection extracts candidates by repeated
(max, lowest-global-index-of-max) — precisely stable_topk's total
order — and any global top-k element is necessarily inside its own
tile's top-k, so the merged result is BIT-IDENTICAL (values, indices,
ties) to ``ops.quant.topk_for_users_quant`` on the same inputs: the
integer dot products are exact, the rescale is elementwise, and both
selections realize the same total order. Asserted in tier-1 across
bucket sizes, k above/below the tile, and constructed score ties.

Platform resolution (``PIO_SERVE_FUSED``): "auto" (default) resolves to
the XLA int8 kernel (``ops.quant.topk_for_users_quant``) on EVERY
platform. The TPU compiler refuses this kernel's output block shape at
every serving bucket — "the last two dimensions of your block shape are
divisible by 8 and 128 ... block shape (b, 10), array shape (b, 530)",
the ``out_specs`` below (tests/test_chip_compile.py holds the refusal
as a strict xfail) — so it is opt-in until a later PR repairs the block
shapes and can claim something for it. "1"/"on" forces the kernel: on a
TPU backend it is compiled and the compiler's error propagates (no
interpret mode, no catch — nothing serves some other way); off-TPU it
runs in ``interpret=True`` mode, slowly but bit-equivalently, which is
how tier-1 exercises the exact kernel code path on CPU. "0"/"off"
forces the XLA kernel (today the same as "auto").
``PIO_SERVE_FUSED_TILE`` sets the item-axis tile (default 512 lanes —
4 x the 128-lane register width, same rationale as the Pallas ALS
solver's batch tile in ops/solve_pallas.py).
"""

from __future__ import annotations

import os
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

#: item-axis tile default: 4 x 128 lanes (ops/solve_pallas.py uses the
#: same width for its batch-as-lanes layout)
_DEF_TILE = 512

#: match ops.topk.NEG_INF bit-for-bit, but as a PYTHON float — a
#: module-level jnp constant would be captured into the kernel jaxpr as
#: a traced constant, which pallas_call rejects
_NEG_INF = -3.4e38
_IMAX = 2 ** 31 - 1


def serve_tile() -> int:
    """The fused kernel's item-axis tile (``PIO_SERVE_FUSED_TILE``,
    default 512). Resolved once at deploy layout time — the padded item
    layout and the jit statics both depend on it."""
    try:
        t = int(os.environ.get("PIO_SERVE_FUSED_TILE", str(_DEF_TILE)))
    except ValueError:
        return _DEF_TILE
    return max(t, 1)


def fused_mode() -> str:
    """``PIO_SERVE_FUSED`` normalized to auto/on/off."""
    raw = os.environ.get("PIO_SERVE_FUSED", "").lower()
    if raw in ("0", "off"):
        return "off"
    if raw in ("1", "on"):
        return "on"
    return "auto"


def fused_choice() -> Tuple[bool, bool]:
    """-> (use_fused, interpret). "auto" and "off": the XLA int8 kernel
    on every platform (the TPU compiler refuses the Pallas kernel's
    block shapes — module docstring); "on": the Pallas kernel, compiled
    on a TPU backend (a compile error propagates) and in interpreter
    mode elsewhere (bit-equivalent, slow — tier-1's CPU coverage of the
    real kernel body)."""
    if fused_mode() != "on":
        return False, False
    return True, jax.default_backend() != "tpu"


def _score_mask_topk_kernel(q_ref, su_ref, v_ref, sv_ref,
                            vals_ref, idx_ref, *,
                            k: int, n_items: int, tile: int):
    """One grid step = one item tile, entirely in VMEM.

    int8 x int8 -> int32 scores for the whole batch against this tile,
    elementwise rescale to fp32, layout padding masked to -inf, then k
    rounds of (row max, lowest global index attaining it) — the
    stable_topk total order, realized without a sort so it lowers as
    plain VPU reductions. Each extraction masks its winner and repeats;
    the tile's k candidates are the only bytes written back."""
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    Q = q_ref[:]                        # (b, r) int8
    V = v_ref[:]                        # (r, tile) int8
    su = su_ref[:]                      # (b, 1) fp32
    sv = sv_ref[:]                      # (1, tile) fp32, 0 on padding
    s32 = lax.dot_general(Q, V, (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.int32)
    scores = s32.astype(jnp.float32) * (su * sv)     # (b, tile)
    gid = i * tile + lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(gid < n_items, scores, _NEG_INF)
    vals, idxs = [], []
    for _ in range(k):
        m = jnp.max(scores, axis=1, keepdims=True)               # (b, 1)
        sel = jnp.min(jnp.where(scores == m, gid, _IMAX),
                      axis=1, keepdims=True)                     # (b, 1)
        vals.append(m[:, 0])
        idxs.append(sel[:, 0])
        scores = jnp.where(gid == sel, _NEG_INF, scores)
    vals_ref[:] = jnp.stack(vals, axis=1)
    idx_ref[:] = jnp.stack(idxs, axis=1)


@partial(jax.jit, static_argnames=("k", "n_items", "tile", "interpret"))
def topk_for_users_quant_fused(
    u_q: jnp.ndarray,        # (n_users, r) int8
    u_scale: jnp.ndarray,    # (n_users,) fp32
    vt_q: jnp.ndarray,       # (r, n_pad) int8, n_pad a multiple of tile
    v_scale: jnp.ndarray,    # (n_pad,) fp32, 0 on pad columns
    user_ixs: jnp.ndarray,   # (b,) int32
    *,
    k: int,
    n_items: int,
    tile: int,
    interpret: bool,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused quantized batched serve: ONE dispatch whose Pallas grid
    tiles the item axis; candidate scores never round-trip through HBM.
    ``user_ixs`` must be in-bounds — callers resolve them against the
    model's user vocabulary first (KNOWN_ISSUES #5). Bit-identical
    (values AND indices, ties included) to
    ``ops.quant.topk_for_users_quant``; compiles once per (shapes,
    bucket, k) and is AOT-prebuilt per (bucket, k) by
    ``ops.quant.quant_program_specs``."""
    from jax.experimental import pallas as pl

    b = user_ixs.shape[0]
    r, n_pad = vt_q.shape
    n_tiles = n_pad // tile
    k_local = min(int(k), int(tile))
    Q = jnp.take(u_q, user_ixs, axis=0)                  # (b, r) int8
    su = jnp.take(u_scale, user_ixs, axis=0)[:, None]    # (b, 1)
    vals, idx = pl.pallas_call(
        partial(_score_mask_topk_kernel, k=k_local, n_items=n_items,
                tile=tile),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((b, r), lambda i: (0, 0)),
            pl.BlockSpec((b, 1), lambda i: (0, 0)),
            pl.BlockSpec((r, tile), lambda i: (0, i)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
        ],
        out_specs=[pl.BlockSpec((b, k_local), lambda i: (0, i)),
                   pl.BlockSpec((b, k_local), lambda i: (0, i))],
        out_shape=[
            jax.ShapeDtypeStruct((b, n_tiles * k_local), jnp.float32),
            jax.ShapeDtypeStruct((b, n_tiles * k_local), jnp.int32)],
        interpret=interpret,
    )(Q, su, vt_q, v_scale[None, :])
    # merge the k·n_tiles candidates: the same two-key (-score, global
    # index) sort the sharded path's all-gather merge uses — any global
    # top-k element is inside its own tile's top-k_local, so the
    # candidate set always covers the answer (k_local = tile when k
    # exceeds a tile, hence n_tiles * k_local >= min(k, n_pad) >= k)
    neg, gi = lax.sort((-vals, idx), num_keys=2, dimension=-1)
    return -neg[:, :k], gi[:, :k]


def _register() -> None:
    from predictionio_tpu.serving import aot
    aot.register_jit(
        "topk_for_users_quant_fused", topk_for_users_quant_fused,
        kind="serving",
        note="enumerated per (bucket, k) by ops/quant.py's "
             "quant_program_specs when the deploy resolved the fused "
             "quantized path (PIO_SERVE_FUSED)")


_register()
