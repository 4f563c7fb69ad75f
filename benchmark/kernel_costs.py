"""Operations and bytes the ALGORITHM needs for one call of each kernel,
from shapes alone. Kept with the benchmark so that no PR that claims a
gain can change what a roofline share is measured against. Recomputed
or padded work does not count: padding rows of a bucket, the r^2+r
expansion a kernel chooses to materialize, sort passes.

Each function takes the cell's configuration and the run's facts and
returns a dict; reduce.py divides by the peaks.
"""


def _als_shape(config):
    s = config["data"]
    a = config["engine_params"]
    return s["n_users"], s["n_items"], s["nnz"], a["rank"], a["numIterations"]


def als_iteration(config, facts=None):
    """One ALS iteration = a user half-step and an item half-step.

    Gram and right-hand side: every rating adds v v^T (r^2 multiply-adds)
    and rating * v (r multiply-adds) to its row, on both sides:
    2 sides * 2 * (r^2 + r) * nnz operations. Solves: one r x r SPD
    system a row, Cholesky r^3/3 + two triangular solves 2 r^2.
    Bytes: each side reads its layout once (index 4 B + rating 4 B per
    rating) and gathers one factor row of r floats per rating, then
    writes its factors once."""
    n_users, n_items, nnz, r, _ = _als_shape(config)
    gram = 2 * 2 * (r * r + r) * nnz
    solves = (n_users + n_items) * (r ** 3 / 3.0 + 2 * r * r)
    bytes_ = 2 * nnz * (8 + 4 * r) + 4 * r * (n_users + n_items)
    return {"ops_per_call": gram + solves, "bytes_per_call": bytes_}


def als_program(config, facts=None):
    """One call of the trainer program = numIterations iterations."""
    it = als_iteration(config)
    n = _als_shape(config)[4]
    return {"ops_per_call": n * it["ops_per_call"],
            "bytes_per_call": n * it["bytes_per_call"]}


def als_jobs(config, facts):
    """All the traced jobs' operations: iterations only; layout, read
    and persist need none that the algorithm defines."""
    jobs = facts.get("traced_jobs")
    if not jobs:
        return None
    return {"ops_total": jobs * als_program(config)["ops_per_call"]}


def topk_flush(config, facts):
    """One flush of the serving kernel at its mean asked-for batch:
    scores 2 * rows * n_items * r operations; bytes: the item matrix
    once (n_items * r * bytes per element as served), the rows' user
    factors, the scores written once and read once by the selection
    (4 B each), the k results. Rows are those really asked for."""
    m = config["model"]
    rows = facts.get("mean_flush_rows")
    if not rows:
        return None
    n_items, r, k = m["n_items"], m["rank"], config["query"]["num"]
    el = config["serving"]["bytes_per_element"]
    return {"ops_per_call": 2.0 * rows * n_items * r,
            "bytes_per_call": (n_items * r * el + rows * r * el
                               + 2 * 4 * rows * n_items + rows * k * 8)}


def topk_window(config, facts):
    """Every query answered in the traced window: 2 * n_items * r each."""
    q = facts.get("traced_queries")
    if not q:
        return None
    m = config["model"]
    return {"ops_total": 2.0 * q * m["n_items"] * m["rank"]}
