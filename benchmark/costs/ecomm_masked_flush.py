"""What one flush of the e-commerce engine's device program (ops/topk.py
masked_topk_rows) needs at its mean asked-for batch: scores 2 * rows *
n_items * r operations; bytes: the item matrix once, the rows' user
factors, the scores written once (the rules are applied as they are
written) and read once by the selection (4 B each), every item's rule
words and the eligibility array once (4 * words + 1 B an item), the k
results. Left out: the flush's own exclusion indices and the elements
they overwrite, a few hundred 4 B words beside 1.2 GB (under 0.01 %).
Rows are those really asked for, as kernel_costs.topk_flush counts
them. One cost function a file, named as the file."""


def ecomm_masked_flush(config, facts):
    rows = facts.get("mean_flush_rows")
    if not rows:
        return None
    m = config["model"]
    n_items, r, k = m["n_items"], m["rank"], config["query"]["num"]
    el = config["serving"]["bytes_per_element"]
    words = -(-(m["n_categories"] + 1) // 32)
    return {"ops_per_call": 2.0 * rows * n_items * r,
            "bytes_per_call": (n_items * r * el + rows * r * el
                               + 2 * 4 * rows * n_items
                               + n_items * (4 * words + 1)
                               + rows * k * 8)}
