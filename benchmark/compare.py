"""The comparisons that decide `correct`, and the numbers they give.
NumPy only. Limits live in the configuration files, set from readings
on the chip (PERF.md gives them); nothing here knows a limit.
"""

import numpy as np


def factor_gap(prog, ref):
    """Relative distance of two factor matrices: the Frobenius norm of
    the difference over that of the reference. Same initial factors and
    the same algorithm, so the matrices themselves agree, not only what
    they predict."""
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    if prog.shape != ref.shape or not np.isfinite(prog).all():
        return float("inf")
    return float(np.linalg.norm(prog - ref) / np.linalg.norm(ref))


def training_numbers(prog_U, prog_V, ref_U, ref_V, errors):
    """errors = prediction_errors(R2, prog, ref): sums over rated pairs.
    -> the numbers compared for a training job."""
    se_p, se_r, se_between, n = (float(x) for x in errors)
    rmse_p, rmse_r = np.sqrt(se_p / n), np.sqrt(se_r / n)
    return {
        "user_factor_gap": factor_gap(prog_U, ref_U),
        "item_factor_gap": factor_gap(prog_V, ref_V),
        "prediction_gap": float(np.sqrt(se_between / n)),
        "train_rmse_gap": float(abs(rmse_p - rmse_r) / rmse_r),
        "_train_rmse_program": float(rmse_p),
        "_train_rmse_reference": float(rmse_r),
    }


def judge(numbers, limits):
    """-> (correct, {name: (value, limit)}) for the names that have a
    limit; a number that is not finite or not there fails."""
    compared, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name, float("nan"))
        compared[name] = (value, limit)
        if not (np.isfinite(value) and value <= limit):
            ok = False
    return ok, compared


def topk_numbers(replies, ref_scores_for, k):
    """replies: [(user_ix, [(item_ix, score), ...] or None)]. For each,
    the reference's float32 scores of that user over every item.
    rank_gap: by how much a served item's reference score lies below the
    reference's own item at that rank; score_gap: served score against
    the reference's score of the same item; both relative to the
    reference's best score of that user. bad_replies: missing, short,
    or repeating an item."""
    rank_gap = score_gap = 0.0
    bad = 0
    for user_ix, items in replies:
        if items is None or len(items) != k \
                or len({i for i, _ in items}) != k:
            bad += 1
            continue
        ref = ref_scores_for(user_ix)
        best = np.sort(ref[np.argpartition(ref, -k)[-k:]])[::-1]
        scale = max(abs(float(best[0])), 1e-30)
        for pos, (item_ix, score) in enumerate(items):
            rank_gap = max(rank_gap, (float(best[pos]) - float(ref[item_ix])) / scale)
            score_gap = max(score_gap, abs(score - float(ref[item_ix])) / scale)
    return {"rank_gap": rank_gap, "score_gap": score_gap,
            "bad_replies": float(bad)}
