"""Factors of a serve-only configuration, made from the seed with NumPy
off the chip, in blocks of rows so that threads share the work and any
block can be made again alone (the reference makes only what it needs
of the users, and all of the items).

Column d of both matrices is N(0, 1) * (d+1)^-decay, normalised so that
a row's expected squared norm is 1: a spectrum that decays, so a user's
scores are spread and not near-ties. Imports nothing of the program.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 1 << 12
SIDES = {"user": 1, "item": 2}


def column_scales(rank, decay):
    s = np.arange(1, rank + 1, dtype=np.float64) ** (-float(decay))
    return (s / np.sqrt((s * s).sum())).astype(np.float32)


def block(seed, side, index, n_rows, rank, decay):
    """Rows [index*BLOCK, ...) of one side."""
    rows = min(BLOCK, n_rows - index * BLOCK)
    rng = np.random.default_rng([int(seed), SIDES[side], int(index)])
    return rng.standard_normal((rows, rank), dtype=np.float32) \
        * column_scales(rank, decay)


def matrix(seed, side, n_rows, rank, decay, threads=8):
    out = np.empty((n_rows, rank), np.float32)
    n_blocks = -(-n_rows // BLOCK)

    def fill(b):
        out[b * BLOCK:b * BLOCK + BLOCK] = block(
            seed, side, b, n_rows, rank, decay)

    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(fill, range(n_blocks)))
    return out


def rows(seed, side, ixs, n_rows, rank, decay):
    """Only the rows `ixs` (any order), making each block they touch."""
    ixs = np.asarray(ixs, np.int64)
    out = np.empty((ixs.size, rank), np.float32)
    for b in np.unique(ixs // BLOCK):
        here = np.flatnonzero(ixs // BLOCK == b)
        out[here] = block(seed, side, b, n_rows, rank, decay)[
            ixs[here] - b * BLOCK]
    return out


def scaled_model(model, factor):
    """The model's shape cut down for rehearsals and tests only."""
    return {**model, "n_users": max(256, int(model["n_users"] / factor)),
            "n_items": max(256, int(model["n_items"] / factor))}
