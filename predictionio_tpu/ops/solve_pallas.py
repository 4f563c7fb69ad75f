"""Pallas batched small-SPD solver: the ALS per-row normal equations.

`ops.als.solve_factors`'s unrolled Gauss-Jordan is r functional sweeps
over an (n, r, r+1) tensor; XLA materializes every sweep to HBM, so the
ML-20M-shape solve (138k rows, r=10) moves ~600 MB — and it runs twice
per ALS iteration.

This kernel runs ALL sweeps in VMEM: the augmented systems are laid out
batch-as-lanes ((r*(r+1), n) — row-major (i, j) system coordinates in
the sublane dimension, batch in lanes, so every Gauss-Jordan operation
is an elementwise op over 512-lane vectors), each grid block reads its
(r*(r+1), 512) tile once, eliminates in registers/VMEM, and writes only
the (r, 512) solution rows.

OUTCOME (previous backend, before PR 25; not measured on this chip):
standalone the kernel was faster than the XLA sweep, but the END-TO-END
training iteration was unchanged — inside the fused fori_loop the solve
overlaps other work and is off the critical path. The solver therefore
stays OPT-IN (PIO_ALS_SOLVER=pallas) as an A/B instrument rather than
the default (ROADMAP D2).

Unpivoted elimination is safe for the ALS systems (PSD + ridge > 0
keeps Schur diagonals positive — see solve_factors).
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp

_BN = 512          # batch lanes per grid block (4 x 128)
_WARNED_OFF_TPU = False


def _gj_kernel(m_ref, out_ref, *, r: int):
    M = m_ref[:]                         # (r*(r+1)+1, BN) f32 in VMEM
    w = r + 1
    rows = [M[i] for i in range(r * w)]  # unrolled: each (BN,) vector
    floor = M[r * w]                     # per-system pivot floor (0.5*reg)
    for k in range(r):
        # true division (not reciprocal-multiply) keeps parity with the
        # XLA sweep tight even on marginally-conditioned systems; the
        # sign-preserving magnitude floor mirrors solve_factors (inert for
        # true SPD + ridge, a hard bound when kernel rounding broke PSD)
        d0 = rows[k * w + k]
        den = jnp.where(d0 >= 0, jnp.maximum(d0, floor),
                        jnp.minimum(d0, -floor))
        piv = [rows[k * w + j] / den for j in range(w)]
        for i in range(r):
            if i == k:
                continue
            fac = rows[i * w + k]
            for j in range(w):
                rows[i * w + j] = rows[i * w + j] - fac * piv[j]
        for j in range(w):
            rows[k * w + j] = piv[j]
    out_ref[:] = jnp.stack([rows[i * w + r] for i in range(r)])


def solve_factors_pallas(A: jnp.ndarray, b: jnp.ndarray, reg: jnp.ndarray,
                         interpret: bool = False) -> jnp.ndarray:
    """(A + reg I) x = b over the leading batch axis, (n, r, r)/(n, r)."""
    from jax.experimental import pallas as pl

    n, r = b.shape
    if r > 32:
        # the kernel fully unrolls O(r^3) vector ops and allocates
        # (r*(r+1), _BN) VMEM tiles; past r=32 that's pathological compile
        # time / VMEM exhaustion, not a slow solve. solve_factors guards
        # this; direct callers get a clear error instead.
        raise ValueError(
            f"solve_factors_pallas supports r <= 32 (got r={r}); use "
            "jnp.linalg.solve or ops.als.solve_factors for larger ranks")
    w = r + 1
    A = A + reg[:, None, None] * jnp.eye(r, dtype=A.dtype)[None]
    M = jnp.concatenate([A, b[..., None]], axis=2)    # (n, r, w)
    n_pad = -(-n // _BN) * _BN
    if n_pad != n:
        # padded systems are identity: diag 1, rhs 0 (no 0-pivot division)
        eye_aug = jnp.concatenate(
            [jnp.eye(r, dtype=M.dtype),
             jnp.zeros((r, 1), dtype=M.dtype)], axis=1)
        M = jnp.concatenate(
            [M, jnp.broadcast_to(eye_aug, (n_pad - n, r, w))], axis=0)
    Mt = jnp.transpose(M.reshape(n_pad, r * w), (1, 0))  # (r*w, n_pad)
    # last row: per-system pivot floor (0 for identity padding -> inert)
    floor = jnp.pad(0.5 * reg.astype(M.dtype), (0, n_pad - n))
    Mt = jnp.concatenate([Mt, floor[None, :]], axis=0)   # (r*w+1, n_pad)

    out = pl.pallas_call(
        partial(_gj_kernel, r=r),
        grid=(n_pad // _BN,),
        in_specs=[pl.BlockSpec((r * w + 1, _BN), lambda i: (0, i))],
        out_specs=pl.BlockSpec((r, _BN), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((r, n_pad), M.dtype),
        interpret=interpret,
    )(Mt)
    return out[:, :n].T


def solver_choice() -> str:
    """gj (the default — see OUTCOME above) unless
    PIO_ALS_SOLVER=pallas explicitly opts in ON A TPU backend; elsewhere
    the opt-in downgrades with a warning instead of failing to lower."""
    if os.environ.get("PIO_ALS_SOLVER") != "pallas":
        return "gj"
    try:
        on_tpu = jax.default_backend() == "tpu"
    except Exception:
        on_tpu = False
    if not on_tpu:
        global _WARNED_OFF_TPU
        if not _WARNED_OFF_TPU:
            _WARNED_OFF_TPU = True
            import logging
            logging.getLogger("predictionio_tpu.ops").warning(
                "PIO_ALS_SOLVER=pallas requested on a %s backend; using "
                "the XLA gj sweep (the Pallas kernel only lowers on TPU)",
                jax.default_backend())
        return "gj"
    return "pallas"
