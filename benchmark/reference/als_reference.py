"""Plain explicit ALS (ALS-WR), the reference the training cells are
held to. Straightforward jax.numpy in float32, no kernels, no sparse
layout: the ratings are one dense int8 matrix of half-star counts
(rating * 2, 0 = not rated; the benchmark's ratings have no pair twice),
and a half-step is the textbook

    A_u = sum_i m_ui v_i v_i^T + lambda * n_u * I,  b_u = sum_i r_ui v_i

computed as two matrix products per block of rows. Imports nothing of
the program.

`precision`: "float32" (the reference) multiplies exactly: the 0/1 and
half-star operand is exact in bfloat16 and the other is split into three
bfloat16 pieces that hold all its 24 bits, so every product is exact and
the MXU sums in float32, whatever a precision flag would have meant
(PERF.md, PR 26: `Precision.HIGHEST` on float32 operands gave a wrong
ALS at this size on the v5e). "bfloat16" is the CONTROL: the factor
operand rounded once to bfloat16, the step below the hi/lo split of two
bfloat16 halves that the program's trainer states (ops/als.py
`_split_hilo`), i.e. that split with its lo half dropped.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def init_factors(seed, n_users, n_items, rank):
    """The configuration's initial factors: MLlib-style |N(0,1)|/sqrt(rank)
    from the engine.json seed, users from the first half of the split
    key and items from the second (threefry, jax's default generator)."""
    ku, ki = jax.random.split(jax.random.PRNGKey(int(seed)))
    scale = jnp.sqrt(jnp.asarray(rank, jnp.float32))

    def draw(key, n):
        return jnp.abs(jax.random.normal(key, (n, rank), jnp.float32)) / scale

    return draw(ku, n_users), draw(ki, n_items)


def dense_half_stars(user_idx, item_idx, rating, n_users, n_items):
    """Host: the (n_users, n_items) int8 matrix of rating * 2."""
    R2 = np.zeros((n_users, n_items), np.int8)
    R2[user_idx, item_idx] = np.rint(rating * 2.0).astype(np.int8)
    return R2


def _solve_spd(A, b):
    """Batched (n, r, r) x = (n, r): Gauss-Jordan without pivoting, r
    unrolled sweeps; A is SPD (Gram + ridge)."""
    r = A.shape[-1]
    M = jnp.concatenate([A, b[..., None]], axis=2)
    for k in range(r):
        piv = M[:, k:k + 1, :] / M[:, k:k + 1, k:k + 1]
        M = M - M[:, :, k:k + 1] * piv
        M = M.at[:, k, :].set(piv[:, 0, :])
    return M[:, :, r]


def _top_bits(x):
    """float32 with its low 16 bits cleared: a value bfloat16 holds
    exactly. Done on the bits, not by a round trip through bfloat16: the
    TPU compiler works such a round trip in excess precision inside a
    fusion, and `x - f32(bf16(x))` then comes out 0 (PERF.md, PR 26)."""
    bits = lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(0xFFFF0000)
    return lax.bitcast_convert_type(bits, jnp.float32)


def _split3(x):
    """float32 -> three bfloat16 pieces whose sum is x exactly: 8 + 8 + 8
    of its 24 significant bits, each piece cut off and the rest exact."""
    bf = jnp.bfloat16
    hi = _top_bits(x)
    rest = x - hi
    mid = _top_bits(rest)
    return (rest - mid).astype(bf), mid.astype(bf), hi.astype(bf)


def _matmul(exact_bf16, other, precision):
    """exact_bf16 (holds only values bfloat16 can hold) @ other (f32)."""
    f32, bf = jnp.float32, jnp.bfloat16
    a = exact_bf16.astype(bf)
    if precision == "bfloat16":
        return jnp.matmul(a, other.astype(bf), preferred_element_type=f32)
    out = None
    for piece in _split3(other):        # smallest first
        part = jnp.matmul(a, piece, preferred_element_type=f32)
        out = part if out is None else out + part
    return out


def _products(Mb, Rb, other, precision):
    """Gram (n, r, r) and right-hand side (n, r) of a block of rows."""
    r = other.shape[1]
    X = (other[:, :, None] * other[:, None, :]).reshape(other.shape[0], r * r)
    A = _matmul(Mb, X, precision)
    b = _matmul(Rb, other, precision)
    return A.reshape(-1, r, r), b


@partial(jax.jit, static_argnames=("block", "by_columns", "precision"))
def half_step(R2, other, lam, block, by_columns, precision="float32"):
    """Solve every row (by_columns: every column) of R2 against `other`."""
    n = R2.shape[1] if by_columns else R2.shape[0]
    r = other.shape[1]
    block = min(block, n)
    n_blocks = -(-n // block)

    def body(k, out):
        start = jnp.minimum(k * block, n - block)   # last block overlaps
        if by_columns:
            blk = lax.dynamic_slice(R2, (0, start), (R2.shape[0], block)).T
        else:
            blk = lax.dynamic_slice(R2, (start, 0), (block, R2.shape[1]))
        Mb = (blk != 0).astype(jnp.float32)
        Rb = blk.astype(jnp.float32) * 0.5
        A, b = _products(Mb, Rb, other, precision)
        count = jnp.maximum(Mb.sum(axis=1), 1.0)
        A = A + (lam * count)[:, None, None] * jnp.eye(r, dtype=jnp.float32)
        return lax.dynamic_update_slice(out, _solve_spd(A, b), (start, 0))

    return lax.fori_loop(0, n_blocks, body,
                         jnp.zeros((n, r), jnp.float32))


def train(R2, U0, V0, iterations, lam, precision="float32",
          user_block=8192, item_block=2048):
    """`iterations` of (users from items, items from users), as MLlib and
    the template order them. -> (U, V) on the device."""
    U, V = U0, V0
    for _ in range(int(iterations)):
        U = half_step(R2, V, lam, user_block, False, precision)
        V = half_step(R2, U, lam, item_block, True, precision)
    return U, V


@partial(jax.jit, static_argnames=("block",))
def prediction_errors(R2, Ua, Va, Ub, Vb, block=4096):
    """Over the rated pairs: (sum of squared error of model a, of model
    b, sum of squared difference between the two, pairs)."""
    n = R2.shape[0]
    block = min(block, n)
    n_blocks = -(-n // block)
    hi = lax.Precision.HIGHEST

    def body(k, acc):
        start = jnp.minimum(k * block, n - block)
        fresh = (jnp.arange(block) + start >= k * block)[:, None]
        blk = lax.dynamic_slice(R2, (start, 0), (block, R2.shape[1]))
        m = ((blk != 0) & fresh).astype(jnp.float32)
        rat = blk.astype(jnp.float32) * 0.5
        pa = jnp.matmul(lax.dynamic_slice(Ua, (start, 0), (block, Ua.shape[1])),
                        Va.T, precision=hi)
        pb = jnp.matmul(lax.dynamic_slice(Ub, (start, 0), (block, Ub.shape[1])),
                        Vb.T, precision=hi)
        return acc + jnp.stack([(m * (pa - rat) ** 2).sum(),
                                (m * (pb - rat) ** 2).sum(),
                                (m * (pa - pb) ** 2).sum(), m.sum()])

    return lax.fori_loop(0, n_blocks, body, jnp.zeros(4, jnp.float32))
