"""Plain e-commerce recommendation, the reference the toy cell of
another engine is held to (upstream's E-Commerce Recommendation
template, train-with-rate-event, for a user the model knows): an item
is a candidate unless the user has seen it, it is on the constraint's
`unavailableItems`, on the query's black list, or outside the query's
categories; score = item_factors @ user_factor in NumPy float32 over
every item; scores <= 0 are dropped; descending score, equal scores by
lowest index. `precision="bfloat16"` is the CONTROL for a
configuration that serves plain float32: both factors rounded to
bfloat16 first. Imports nothing of the program.
"""

import numpy as np

from topk_reference import to_bf16, topk


def candidates(n_items, seen, unavailable, item_categories, categories,
               black):
    """(n_items,) bool. `item_categories` is (n_items,) category indices;
    `categories` None or the indices the query allows; the other three
    are arrays of item indices."""
    mask = np.ones(n_items, bool)
    if categories is not None:
        mask &= np.isin(item_categories, np.asarray(categories, np.int64))
    for out in (seen, unavailable, black):
        mask[np.asarray(out, np.int64)] = False
    return mask


def scores(user_row, item_factors, precision="float32"):
    if precision == "float32":
        return item_factors @ user_row
    if precision == "bfloat16":
        return to_bf16(item_factors) @ to_bf16(user_row)
    raise ValueError(precision)


def recommend(score_row, mask, k):
    """-> item indices, best first: at most k, fewer where fewer
    candidates score above 0."""
    masked = np.where(mask, score_row, -np.inf).astype(np.float32)
    top = topk(masked, k)
    return top[masked[top] > 0]
