"""Plain top-k scoring, the reference the serving cells are held to:
score = item_factors @ user_factor in NumPy float32, every item, no
kernel, no batching. `precision="bf16x3"` is the CONTROL for a
configuration that serves float32 at HIGHEST precision: the same
product in three bfloat16 passes (jax's Precision.HIGH), the step below.
Imports nothing of the program.
"""

import numpy as np


def to_bf16(x):
    """float32 -> nearest-even bfloat16, returned as float32."""
    b = np.ascontiguousarray(x, np.float32).view(np.uint32)
    rounded = b + np.uint32(0x7FFF) + ((b >> np.uint32(16)) & np.uint32(1))
    return (rounded & np.uint32(0xFFFF0000)).view(np.float32)


def split_bf16(x):
    hi = to_bf16(x)
    return hi, to_bf16(x - hi)


def prepare(item_factors, precision="float32"):
    """What `scores` needs of the item matrix, made once."""
    if precision == "float32":
        return (item_factors,)
    if precision == "bf16x3":
        return split_bf16(item_factors)
    raise ValueError(precision)


def scores(user_rows, prepared, precision="float32"):
    """(b, r) x prepared (n, r) -> (b, n) float32 scores."""
    if precision == "float32":
        return user_rows @ prepared[0].T
    if precision == "bf16x3":
        uh, ul = split_bf16(user_rows)
        vh, vl = prepared
        return uh @ vh.T + (uh @ vl.T + ul @ vh.T)
    raise ValueError(precision)


def topk(score_row, k):
    """Descending score, equal scores by lowest index."""
    k = min(k, score_row.size)
    part = np.argpartition(-score_row, k - 1)[:k]
    order = np.lexsort((part, -score_row[part]))
    return part[order]
