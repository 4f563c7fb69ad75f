"""What one flush of the similar-product engine's device program
(ops/topk.py itemset_topk_rows) needs at its mean asked-for batch:
scores 2 * rows * n_items * r operations; bytes: the item matrix once,
the scores written once (the rules are applied as they are written) and
read once by the selection (4 B each), every item's rule words and the
eligibility array once (4 * words + 1 B an item), the rows' query items
gathered from the same matrix (the mean number a query of the
configuration's mix names: padding entries are the program's, not the
algorithm's), the k results. Left out: the flush's own exclusion
indices and the elements they overwrite, a few hundred 4 B words beside
7.2 GB. Rows are those really asked for, as kernel_costs.topk_flush
counts them. One cost function a file, named as the file."""


def simprod_flush(config, facts):
    rows = facts.get("mean_flush_rows")
    if not rows:
        return None
    m, q = config["model"], config["query"]
    n_items, r, k = m["n_items"], m["rank"], q["num"]
    el = config["serving"]["bytes_per_element"]
    words = -(-(m["n_categories"] + 1) // 32)
    items_a_query = q["one_item_share"] + (1.0 - q["one_item_share"]) \
        * (2 + q["items_max"]) / 2.0
    return {"ops_per_call": 2.0 * rows * n_items * r,
            "bytes_per_call": (n_items * r * el
                               + 2 * 4 * rows * n_items
                               + n_items * (4 * words + 1)
                               + rows * items_a_query * r * el
                               + rows * k * 8)}
