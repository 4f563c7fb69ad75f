"""Plain e-commerce recommendation with its business rules, the
reference the cell `serve.ecomm-amazon-r128.closed128` is held to
(upstream's E-Commerce Recommendation template, train-with-rate-event,
`unseenOnly`, for a user the model knows):

- an item is a candidate unless the user has viewed or bought it, it is
  on the constraint's `unavailableItems` as last set, on the query's
  black list, or outside the query's categories (a query with none
  takes every category);
- score = item_factors @ user_factor in NumPy float32 over EVERY item;
- scores <= 0 are dropped; descending score, equal scores by lowest
  index; at most `num`.

The CONTROLS for a configuration that serves float32 at HIGHEST
precision are the same product a step and two below, as topk_reference
has them: `bf16x3` (three bfloat16 passes, jax's Precision.HIGH) and
`bfloat16` (one pass: both factors rounded first). NumPy only; imports
nothing of the program.
"""

import numpy as np

import topk_reference
from topk_reference import to_bf16


def topk(score_row, k):
    """Descending score, equal scores by lowest index, also where the
    tie runs across the k-th place (topk_reference.topk orders the k
    items a partition picked, and a partition picks any of the tied)."""
    k = min(k, score_row.size)
    kth = np.partition(score_row, score_row.size - k)[score_row.size - k]
    above = np.flatnonzero(score_row > kth)
    tied = np.flatnonzero(score_row == kth)[:k - above.size]
    top = np.concatenate([above, tied])
    return top[np.lexsort((top, -score_row[top]))]


def candidates(n_items, seen, unavailable, item_categories, categories,
               black):
    """(n_items,) bool. `item_categories` is (n_items,) category
    indices; `categories` None or the indices the query allows; the
    other three are arrays of item indices."""
    if categories is None:
        mask = np.ones(n_items, bool)
    else:
        mask = np.isin(item_categories, np.asarray(categories, np.int64))
    for out in (seen, unavailable, black):
        mask[np.asarray(out, np.int64)] = False
    return mask


def prepare(item_factors, precision="float32"):
    """What `scores` needs of the item matrix, made once."""
    if precision == "bfloat16":
        return (to_bf16(item_factors),)
    return topk_reference.prepare(item_factors, precision)


def scores(user_rows, prepared, precision="float32"):
    """(b, r) x prepared (n, r) -> (b, n) float32 scores."""
    if precision == "bfloat16":
        return to_bf16(user_rows) @ prepared[0].T
    return topk_reference.scores(user_rows, prepared, precision)


def recommend(score_row, mask, k):
    """-> item indices, best first: at most k, fewer where fewer
    candidates score above 0."""
    masked = np.where(mask, score_row, -np.inf).astype(np.float32)
    top = topk(masked, k)
    return top[masked[top] > 0]
