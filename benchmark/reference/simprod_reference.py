"""Plain similar-product scoring with its rules, the reference the cell
`serve.simprod-amazon14-r128.closed128` is held to (upstream's Similar
Product template, examples/scala-parallel-similarproduct, algorithm
`als`, ALSAlgorithm.scala predict / cosine / isCandidateItem):

- the query's items the model has a trained vector for, as a SET (a
  repeated name counts once; unknown and untrained ones are dropped; a
  query left with none is answered with nothing);
- score of a candidate = the sum, over the query's items, of the cosine
  between the item's vector and the candidate's;
- an item is a candidate unless it is one of the query's own items, on
  the query's black list, untrained, or outside the query's categories
  (a query with none takes every category);
- scores <= 0 are dropped; descending score, equal scores by lowest
  index, also where the tie runs across the `num`-th place; at most
  `num`.

Two departures from upstream's arithmetic, both the program's too
(models/similarproduct ALSAlgorithm.train / predict), neither of which
changes what is computed beyond rounding:

- rows are normalized to unit length ONCE (`normalize`, float32),
  where upstream divides by both norms a pair; a zero row stays zero
  (divisor floored at 1e-12) where upstream's cosine of it is NaN;
- the sum of cosines is ONE product: unit rows summed over the query's
  items (float32, in index order), then a single float32 product with
  every unit row, where upstream sums the per-item cosines in double.

The CONTROL for a configuration that serves float32 at HIGHEST
precision is the same product a step below, as topk_reference has it:
`bf16x3` (three bfloat16 passes, jax's Precision.HIGH). NumPy only;
imports nothing of the program.
"""

import numpy as np

import topk_reference
from ecomm_rules_reference import recommend, topk  # noqa: F401  (tie-exact)


def normalize(item_factors):
    """(n, r) float32 rows at unit length, in place where it can."""
    V = np.asarray(item_factors, np.float32)
    V /= np.maximum(np.linalg.norm(V, axis=1, keepdims=True), 1e-12)
    return V


def query_items(item_ixs, trained=None):
    """The query's items as the model sees them: sorted distinct
    indices, `None` (an unknown name) and untrained ones dropped."""
    known = {int(i) for i in item_ixs if i is not None}
    if trained is not None:
        known = {i for i in known if trained[i]}
    return sorted(known)


def query_vector(unit_rows, items):
    """The sum of the query items' unit rows, float32, in index order.
    `unit_rows` are the rows of `items`, in that order."""
    out = np.zeros(unit_rows.shape[1], np.float32)
    for row in unit_rows[:len(items)]:
        out += row
    return out


def candidates(n_items, item_categories, categories, items, black,
               trained=None):
    """(n_items,) bool. `item_categories` is (n_items,) category
    indices; `categories` None or the indices the query allows; `items`
    (the query's own) and `black` arrays of item indices."""
    if categories is None:
        mask = np.ones(n_items, bool)
    else:
        mask = np.zeros(n_items, bool)
        for c in categories:
            mask |= item_categories == c
    if trained is not None:
        mask &= trained
    for out in (items, black):
        mask[np.asarray(out, np.int64)] = False
    return mask


prepare = topk_reference.prepare
scores = topk_reference.scores
