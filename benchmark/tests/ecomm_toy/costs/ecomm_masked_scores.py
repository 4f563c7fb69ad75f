"""Operations of the e-commerce engine's scoring for every query asked
between the trace's marks: 2 * n_items * rank each (one product over
the whole catalog; the mask and the selection need none the algorithm
defines). One cost function a file, named as the file."""


def ecomm_masked_scores(config, facts):
    q = facts.get("traced_queries")
    if not q:
        return None
    m = config["model"]
    return {"ops_total": 2.0 * q * m["n_items"] * m["rank"]}
