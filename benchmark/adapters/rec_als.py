"""The recommendation template (explicit ALS, `U[u] . V` top-k) as the
benchmark deploys and queries it: everything that knows that engine.
A configuration names this file by `"adapter": "rec_als"`.

Two halves in one module. The parent half is NumPy only (run.py's
parent never imports jax or the program): the queries from the seed, a
query's request body, a reply parsed, whether a reply is whole for its
query, and the check of a sample of replies against the plain reference
(reference/topk_reference.py). The child half imports the program
inside its functions: the model a completed instance holds, and the
faults the tests plant under the timed path.
"""

import json

import numpy as np

import compare
import gen_factors
import topk_reference

# ---------------------------------------------------------------------------
# parent half
# ---------------------------------------------------------------------------


def rehearsal_model(model, cut):
    """The model's shape cut down for rehearsals and tests only."""
    return gen_factors.scaled_model(model, cut)


def query_users(seed, n, n_users, zipf_a):
    """n user indices, zipf(a) over ranks scrambled by a seeded
    multiplicative map so that popularity is not index order (copied
    from data/synthetic.py query_keys' draw: bounded zipf by rejection
    of ranks past the population)."""
    rng = np.random.default_rng([int(seed), 0x51])
    out = np.empty(0, np.int64)
    while out.size < n:
        draw = rng.zipf(zipf_a, size=int((n - out.size) * 1.3) + 16)
        out = np.concatenate([out, draw[draw <= n_users] - 1])
    ranks = out[:n]
    # odd multiplier modulo n_users' next power of two, cycle-walked
    # back into range: a fixed bijection of [0, n_users)
    bits = max(1, int(n_users - 1).bit_length())
    mask = (1 << bits) - 1
    mult = (int(rng.integers(1, 1 << 30)) * 2 + 1) & mask or 1
    add = int(rng.integers(0, 1 << 30)) & mask
    x = (ranks * mult + add) & mask
    while True:
        bad = x >= n_users
        if not bad.any():
            break
        x[bad] = (x[bad] * mult + add) & mask
    return x


def queries(spec, model, seed, n):
    """The warm-up's and the window's queries: n user indices."""
    return query_users(seed, n, model["n_users"],
                       spec["config"]["query"]["zipf_a"])


class Wire:
    """One cell's side of the wire, called on the sending threads a
    request at a time: a query is a user index."""

    def __init__(self, num):
        self.num = num

    def body(self, user_ix):
        return json.dumps({"user": f"u{int(user_ix)}", "num": self.num})

    @staticmethod
    def parse(status, data):
        """-> [(item_name, score)] or None for anything but a full reply."""
        if status != 200:
            return None
        try:
            items = json.loads(data)["itemScores"]
            return [(s["item"], float(s["score"])) for s in items]
        except (ValueError, KeyError, TypeError):
            return None

    def whole(self, user_ix, reply):
        """No filter: every user has `num` items to get."""
        return reply is not None and len(reply) == self.num


def wire(spec):
    return Wire(spec["config"]["query"]["num"])


def check(spec, model, seed, asked, records, control=False):
    """A sample of the window's requests, drawn from the seed, against
    the reference: every one has to have come, with k distinct items
    whose reference scores are the best to within the limits. A record's
    first field is its query's index in `asked`, the window's queries."""
    traffic, config = spec["traffic"], spec["config"]
    k = config["query"]["num"]
    rng = np.random.default_rng([int(seed), 0xC4])
    n = min(int(traffic["checked_replies"]), len(records))
    picks = rng.choice(len(records), n, replace=False)
    nu, ni, r, decay = (model["n_users"], model["n_items"], model["rank"],
                        model["decay"])
    V = gen_factors.matrix(seed, "item", ni, r, decay)
    user_ixs = np.asarray([asked[records[p][0]] for p in picks], np.int64)
    rows = gen_factors.rows(seed, "user", user_ixs, nu, r, decay)
    replies = []
    for p in picks:
        items = records[p][-1]
        if items is not None:
            try:
                items = [(int(name[1:]), s) for name, s in items]
            except ValueError:
                items = None
        replies.append(items)
    precisions = {"program": "float32"}
    if control:
        precisions["control"] = config["serving"]["control_precision"]
    step = 32
    prepared = {prec: topk_reference.prepare(V, prec)
                for prec in set(precisions.values())}
    state = {name: {"rank_gap": 0.0, "score_gap": 0.0, "bad_replies": 0.0}
             for name in precisions}
    for s in range(0, n, step):
        ref = topk_reference.scores(rows[s:s + step], prepared["float32"])
        for name, prec in precisions.items():
            if name == "program":
                got = [(int(user_ixs[s + j]), replies[s + j])
                       for j in range(ref.shape[0])]
            else:
                # the control in the program's place: what the lower
                # precision would have served for the same queries
                low = topk_reference.scores(rows[s:s + step], prepared[prec], prec)
                got = []
                for j in range(ref.shape[0]):
                    top = topk_reference.topk(low[j], k)
                    got.append((int(user_ixs[s + j]),
                                [(int(i), float(low[j][i])) for i in top]))
            lookup = {int(user_ixs[s + j]): ref[j]
                      for j in range(ref.shape[0])}
            # one user may be asked twice in a block; same row either way
            nums = compare.topk_numbers(got, lookup.__getitem__, k)
            for key in state[name]:
                state[name][key] = (state[name][key] + nums[key]
                                    if key == "bad_replies"
                                    else max(state[name][key], nums[key]))
    return {**state, "checked": int(n)}


# ---------------------------------------------------------------------------
# child half: imports the program
# ---------------------------------------------------------------------------


def models(config, model, seed, storage, variant):
    """What `model_io.serialize_models` is given for the completed
    instance: the template's ALSModel on factors made from the seed.
    This deployment reads no event while it serves, so `storage` gets
    nothing."""
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.models.recommendation.als_algorithm import ALSModel

    nu, ni, r = model["n_users"], model["n_items"], model["rank"]
    U = gen_factors.matrix(seed, "user", nu, r, model["decay"])
    V = gen_factors.matrix(seed, "item", ni, r, model["decay"])
    return [ALSModel(rank=r, user_factors=U, item_factors=V,
                     user_vocab=BiMap({f"u{k}": k for k in range(nu)}),
                     item_vocab=BiMap({f"i{k}": k for k in range(ni)}))]


def _altered_answer():
    """Every answer leaves with its best item replaced."""
    from predictionio_tpu.models.recommendation import als_algorithm
    from predictionio_tpu.models.recommendation.engine import (
        ItemScore, PredictedResult)

    honest = als_algorithm.ALSAlgorithm.predict_batch

    def altered(self, model, queries):
        out = []
        for res in honest(self, model, queries):
            items = list(res.itemScores)
            if items:
                items[0] = ItemScore(item="i0", score=items[0].score)
            out.append(PredictedResult(tuple(items)))
        return out

    als_algorithm.ALSAlgorithm.predict_batch = altered


#: tests only: name -> what plants the fault under the timed path
FAULTS = {"altered_answer": _altered_answer}
