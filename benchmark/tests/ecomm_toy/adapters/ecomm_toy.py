"""The e-commerce engine (ALS + business rules read LIVE from the event
store a query: seen items, `unavailableItems`, categories, black list)
at toy size, as the benchmark's own tests deploy and query it: the
proof that a deployment of another engine is added with new files only
(tests/test_cells.py copies this tree over benchmark/ in a copy). It is
no cell and has no configuration of real size.

Same two halves as adapters/rec_als.py. What both halves need of the
deployment's data (who has seen what, what is unavailable, an item's
category) comes from the seed through the functions below, never from
what the program has made.
"""

import datetime
import json

import numpy as np

import ecomm_reference
import gen_factors
import harness

rec_als = harness.load_adapter("rec_als")    # its zipf users, its reply

# ---------------------------------------------------------------------------
# the deployment's data, from the seed
# ---------------------------------------------------------------------------


def item_categories(seed, model):
    """(n_items,) the one category index of each item."""
    rng = np.random.default_rng([int(seed), 0xE1])
    return rng.integers(0, model["n_categories"], model["n_items"])


def seen_items(seed, model):
    """[n_users] arrays of the item indices a user has viewed or bought:
    heavy-tailed lengths (zipf, cut at `seen_max`), distinct items."""
    rng = np.random.default_rng([int(seed), 0xE2])
    lengths = np.minimum(rng.zipf(1.5, model["n_users"]), model["seen_max"])
    return [rng.choice(model["n_items"], int(n), replace=False)
            for n in lengths]


def unavailable_items(seed, model):
    rng = np.random.default_rng([int(seed), 0xE3])
    return rng.choice(model["n_items"], model["n_unavailable"], replace=False)


# ---------------------------------------------------------------------------
# parent half
# ---------------------------------------------------------------------------


def rehearsal_model(model, cut):
    """Already the size of a rehearsal."""
    return model


def queries(spec, model, seed, n):
    """n queries as the JSON objects they are sent as: zipf users, half
    of them with one or two categories, a third with a black list."""
    q = spec["config"]["query"]
    users = rec_als.query_users(seed, n, model["n_users"], q["zipf_a"])
    rng = np.random.default_rng([int(seed), 0xE4])
    out = []
    for u in users:
        query = {"user": f"u{int(u)}", "num": q["num"]}
        if rng.random() < 0.5:
            cats = rng.choice(model["n_categories"], int(rng.integers(1, 3)),
                              replace=False)
            query["categories"] = [f"c{int(c)}" for c in cats]
        if rng.random() < 1 / 3:
            black = rng.choice(model["n_items"], int(rng.integers(1, 6)),
                               replace=False)
            query["blackList"] = [f"i{int(i)}" for i in black]
        out.append(query)
    return out


class Wire:
    def __init__(self, num):
        self.num = num

    @staticmethod
    def body(query):
        return json.dumps(query)

    parse = staticmethod(rec_als.Wire.parse)

    def whole(self, query, reply):
        """A filtered query may rightly get fewer than `num` items; how
        many are due is the reference's to say (`check`)."""
        return reply is not None and len(reply) <= self.num


def wire(spec):
    return Wire(spec["config"]["query"]["num"])


def _ixs(names):
    return [int(name[1:]) for name in names]


def check(spec, model, seed, asked, records, control=False):
    """A sample of the window's requests against ecomm_reference: the
    items due, in order, to within the limits. -> the numbers compared:
    `filter_leaks` counts served items the rules exclude; `bad_replies`
    replies missing, repeating an item, or not as long as the
    reference's; `rank_gap` / `score_gap` as compare.topk_numbers, over
    the candidates."""
    traffic, config = spec["traffic"], spec["config"]
    k = config["query"]["num"]
    rng = np.random.default_rng([int(seed), 0xC4])
    n = min(int(traffic["checked_replies"]), len(records))
    picks = rng.choice(len(records), n, replace=False)
    nu, ni, r, decay = (model["n_users"], model["n_items"], model["rank"],
                        model["decay"])
    V = gen_factors.matrix(seed, "item", ni, r, decay)
    cats, seen = item_categories(seed, model), seen_items(seed, model)
    gone = unavailable_items(seed, model)
    precisions = {"program": None}
    if control:
        precisions["control"] = config["serving"]["control_precision"]
    state = {name: {"rank_gap": 0.0, "score_gap": 0.0, "bad_replies": 0.0,
                    "filter_leaks": 0.0} for name in precisions}
    for p in picks:
        query = asked[records[p][0]]
        u = int(query["user"][1:])
        row = gen_factors.rows(seed, "user", [u], nu, r, decay)[0]
        mask = ecomm_reference.candidates(
            ni, seen[u], gone, cats,
            _ixs(query["categories"]) if "categories" in query else None,
            _ixs(query.get("blackList", ())))
        ref = ecomm_reference.scores(row, V)
        due = ecomm_reference.recommend(ref, mask, k)
        for name, prec in precisions.items():
            if name == "program":
                reply = records[p][-1]
                got = None if reply is None else \
                    [(int(item[1:]), s) for item, s in reply]
            else:
                # the control in the program's place: what the lower
                # precision would have served for the same query
                low = ecomm_reference.scores(row, V, prec)
                got = [(int(i), float(low[i]))
                       for i in ecomm_reference.recommend(low, mask, k)]
            st = state[name]
            if got is None or len({i for i, _ in got}) != len(got):
                st["bad_replies"] += 1
                continue
            leaks = [i for i, _ in got if not mask[i]]
            st["filter_leaks"] += len(leaks)
            if len(got) != len(due):
                st["bad_replies"] += 1
            scale = max(abs(float(ref[due[0]])), 1e-30) if len(due) else 1.0
            for pos, (i, s) in enumerate(got):
                if i in leaks or pos >= len(due):
                    continue
                st["rank_gap"] = max(st["rank_gap"], (
                    float(ref[due[pos]]) - float(ref[i])) / scale)
                st["score_gap"] = max(st["score_gap"],
                                      abs(s - float(ref[i])) / scale)
    return {**state, "checked": int(n)}


# ---------------------------------------------------------------------------
# child half: imports the program
# ---------------------------------------------------------------------------


def models(config, model, seed, storage, variant):
    """The engine's model on factors from the seed, and what it reads
    while it serves, written through the program's own storage calls:
    the app, every user's `view` / `buy` events, one `$set` on
    constraint/unavailableItems."""
    from predictionio_tpu.data import store
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.data.datamap import DataMap
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.data.storage import App
    from predictionio_tpu.models.ecommerce.als_algorithm import ECommModel
    from predictionio_tpu.models.ecommerce.engine import Item
    from predictionio_tpu.models.similarproduct.als_algorithm import (
        build_category_masks)

    nu, ni, r = model["n_users"], model["n_items"], model["rank"]
    U = gen_factors.matrix(seed, "user", nu, r, model["decay"])
    V = gen_factors.matrix(seed, "item", ni, r, model["decay"])
    items = {i: Item(categories=(f"c{int(c)}",))
             for i, c in enumerate(item_categories(seed, model))}
    app_name = variant["algorithms"][0]["params"]["appName"]
    app_id = storage.get_meta_data_apps().insert(App(0, app_name, None))
    storage.get_events().init(app_id)
    t0 = datetime.datetime(2021, 1, 1, tzinfo=datetime.timezone.utc)
    events = [Event(event=("view", "buy")[int(i) % 2], entity_type="user",
                    entity_id=f"u{u}", target_entity_type="item",
                    target_entity_id=f"i{int(i)}",
                    event_time=t0 + datetime.timedelta(seconds=u))
              for u, seen in enumerate(seen_items(seed, model))
              for i in seen]
    events.append(Event(
        event="$set", entity_type="constraint", entity_id="unavailableItems",
        properties=DataMap({"items": [
            f"i{int(i)}" for i in unavailable_items(seed, model)]}),
        event_time=t0 + datetime.timedelta(days=1)))
    store.write(events, app_id, storage=storage)
    return [ECommModel(
        rank=r, user_features=U, product_features=V,
        user_vocab=BiMap({f"u{k}": k for k in range(nu)}),
        item_vocab=BiMap({f"i{k}": k for k in range(ni)}),
        items=items, user_trained=np.ones(nu, bool),
        item_trained=np.ones(ni, bool),
        category_masks=build_category_masks(items, ni),
        product_features_hat=V / np.maximum(
            np.linalg.norm(V, axis=1, keepdims=True), 1e-12))]


def _altered_answer():
    """Every answer leaves with its best item replaced."""
    from predictionio_tpu.models.ecommerce import als_algorithm
    from predictionio_tpu.models.ecommerce.engine import (ItemScore,
                                                          PredictedResult)

    honest = als_algorithm.ECommAlgorithm.predict_batch

    def altered(self, model, queries):
        out = []
        for res in honest(self, model, queries):
            items = list(res.itemScores)
            if items:
                items[0] = ItemScore(item="i0", score=items[0].score)
            out.append(PredictedResult(tuple(items)))
        return out

    als_algorithm.ECommAlgorithm.predict_batch = altered


def _ignored_seen_filter():
    """`unseenOnly` ignored: nothing a user has seen is held back."""
    from predictionio_tpu.models.ecommerce import als_algorithm

    als_algorithm.ECommAlgorithm._seen_items = lambda self, user: set()


FAULTS = {"altered_answer": _altered_answer,
          "ignored_seen_filter": _ignored_seen_filter}
