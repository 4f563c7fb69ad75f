"""The e-commerce recommendation template (explicit ALS + business rules
read LIVE from the event store while it serves: seen items, the
`unavailableItems` constraint, categories, black lists) as the
benchmark deploys and queries it: everything that knows that engine at
the size of a cell. A configuration names this file by
`"adapter": "ecomm_als"`. Structure, `check` and `FAULTS` after
tests/ecomm_toy/adapters/ecomm_toy.py, the proof the seam was cut for
this deployment.

Same two halves as adapters/rec_als.py: NumPy at the top for the parent,
the program imported inside the child's functions. What both halves need
of the deployment's data (who has seen what, what is unavailable, an
item's category) comes from the seed through the functions below, never
from what the program has made. A user's seen items come from a
generator of that user's own, so the child writes the events of the
users the queries name and the parent checks a sample of them without
either making all 6.6 M lists.
"""

import datetime
import json

import numpy as np

import ecomm_rules_reference as reference
import gen_factors
import harness

rec_als = harness.load_adapter("rec_als")    # its zipf users, its reply

# ---------------------------------------------------------------------------
# the deployment's data, from the seed
# ---------------------------------------------------------------------------


def item_categories(seed, model):
    """(n_items,) the one category index of each item: category c is
    drawn with weight 1/(c+1)."""
    rng = np.random.default_rng([int(seed), 0xE1])
    weight = 1.0 / np.arange(1, model["n_categories"] + 1)
    return rng.choice(model["n_categories"], model["n_items"],
                      p=weight / weight.sum())


def seen_items(seed, model, user):
    """The item indices user `user` has viewed or bought: a heavy-tailed
    length (zipf, cut at `seen_max`) of distinct items, from a
    generator of the user's own."""
    rng = np.random.default_rng([int(seed), 0xE2, int(user)])
    n = min(int(rng.zipf(model["seen_zipf_a"])), model["seen_max"])
    return rng.choice(model["n_items"], n, replace=False)


def event_users(config, model, seed):
    """The users whose seen events the child writes, each with its whole
    list: those the first `first_queries` queries of the seed name (the
    configuration's one cut, `seen_events_written`: the cell's warm-up
    and window). Sorted, distinct."""
    cut = config["seen_events_written"]
    return np.unique(rec_als.query_users(
        seed, int(cut["first_queries"]), model["n_users"],
        config["query"]["zipf_a"]))


def unavailable_items(seed, model):
    rng = np.random.default_rng([int(seed), 0xE3])
    return rng.choice(model["n_items"], model["n_unavailable"], replace=False)


# ---------------------------------------------------------------------------
# parent half
# ---------------------------------------------------------------------------


def rehearsal_model(model, cut):
    """The model's shape cut down for rehearsals and tests only; the
    lists that have to fit the catalog cut with it."""
    small = gen_factors.scaled_model(model, cut)
    return {**small,
            "seen_max": min(model["seen_max"], small["n_items"] // 8),
            "n_unavailable": max(1, small["n_items"] // 100)}


def queries(spec, model, seed, n):
    """n queries, each the JSON text it is sent as: zipf users (all of
    them known to the model), half of them with one or two categories,
    a third with a black list of 1 to 5 items; no white list."""
    q = spec["config"]["query"]
    users = rec_als.query_users(seed, n, model["n_users"], q["zipf_a"])
    rng = np.random.default_rng([int(seed), 0xE4])
    C, ni = model["n_categories"], model["n_items"]
    n_cats = np.where(rng.random(n) < q["categories_share"],
                      rng.integers(1, 3, n), 0)
    first = rng.integers(0, C, n)
    second = (first + rng.integers(1, C, n)) % C        # another one
    n_black = np.where(rng.random(n) < q["black_list_share"],
                       rng.integers(1, q["black_list_max"] + 1, n), 0)
    black = rng.integers(0, ni, (n, q["black_list_max"]))
    out = []
    for j in range(n):
        query = {"user": f"u{users[j]}", "num": q["num"]}
        if n_cats[j]:
            query["categories"] = [f"c{c}" for c in
                                   (first[j], second[j])[:n_cats[j]]]
        if n_black[j]:
            query["blackList"] = [f"i{i}" for i in black[j, :n_black[j]]]
        out.append(json.dumps(query))
    return out


class Wire:
    def __init__(self, num):
        self.num = num

    @staticmethod
    def body(query):
        return query

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
    """A sample of the window's requests against ecomm_rules_reference:
    the items due, in order, to within the limits. -> the numbers
    compared: `filter_leaks` counts served items the rules exclude;
    `bad_replies` replies missing, repeating an item, or not as long as
    the reference's; `rank_gap` / `score_gap` as compare.topk_numbers,
    over the candidates."""
    traffic, config = spec["traffic"], spec["config"]
    k = config["query"]["num"]
    rng = np.random.default_rng([int(seed), 0xC4])
    n = min(int(traffic["checked_replies"]), len(records))
    picks = rng.choice(len(records), n, replace=False)
    nu, ni, r, decay = (model["n_users"], model["n_items"], model["rank"],
                        model["decay"])
    V = gen_factors.matrix(seed, "item", ni, r, decay)
    cats, gone = item_categories(seed, model), unavailable_items(seed, model)
    written = set(event_users(config, model, seed).tolist())
    sample = [json.loads(asked[records[p][0]]) for p in picks]
    users = np.asarray([int(q["user"][1:]) for q in sample], np.int64)
    rows = gen_factors.rows(seed, "user", users, nu, r, decay)
    precisions = {"program": "float32"}
    if control:
        precisions["control"] = config["serving"]["control_precision"]
    prepared = {prec: reference.prepare(V, prec)
                for prec in set(precisions.values())}
    state = {name: {"rank_gap": 0.0, "score_gap": 0.0, "bad_replies": 0.0,
                    "filter_leaks": 0.0} for name in precisions}
    step = 32
    for s in range(0, n, step):
        refs = reference.scores(rows[s:s + step], prepared["float32"])
        lows = (reference.scores(rows[s:s + step], prepared[
            precisions["control"]], precisions["control"])
            if control else None)
        for j, ref in enumerate(refs):
            query, u = sample[s + j], int(users[s + j])
            seen = seen_items(seed, model, u) if u in written else ()
            mask = reference.candidates(
                ni, seen, gone, cats,
                _ixs(query["categories"]) if "categories" in query else None,
                _ixs(query.get("blackList", ())))
            due = reference.recommend(ref, mask, k)
            for name in precisions:
                if name == "program":
                    reply = records[picks[s + j]][-1]
                    got = None if reply is None else \
                        [(int(item[1:]), sc) for item, sc in reply]
                else:
                    # the control in the program's place: what the lower
                    # precision would have served for the same query
                    low = lows[j]
                    got = [(int(i), float(low[i]))
                           for i in reference.recommend(low, mask, k)]
                st = state[name]
                if got is None or len({i for i, _ in got}) != len(got):
                    st["bad_replies"] += 1
                    continue
                leaks = [i for i, _ in got if not mask[i]]
                st["filter_leaks"] += len(leaks)
                if len(got) != len(due):
                    st["bad_replies"] += 1
                scale = max(abs(float(ref[due[0]])), 1e-30) \
                    if len(due) else 1.0
                for pos, (i, sc) in enumerate(got):
                    if i in leaks or pos >= len(due):
                        continue
                    st["rank_gap"] = max(st["rank_gap"], (
                        float(ref[due[pos]]) - float(ref[i])) / scale)
                    st["score_gap"] = max(st["score_gap"],
                                          abs(sc - float(ref[i])) / scale)
    return {**state, "checked": int(n)}


# ---------------------------------------------------------------------------
# child half: imports the program
# ---------------------------------------------------------------------------


def models(config, model, seed, storage, variant):
    """The engine's model on factors from the seed, and what it reads
    while it serves, written through the program's own storage calls:
    the app, the `view` / `buy` events of the users the queries name
    (`event_users`), one `$set` on constraint/unavailableItems."""
    from predictionio_tpu.data import store
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.data.datamap import DataMap
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.data.storage import App
    from predictionio_tpu.models.ecommerce.als_algorithm import (
        ECommAlgorithm, ECommModel)
    from predictionio_tpu.models.ecommerce.engine import Item

    if "aot_serving_programs" not in vars(ECommAlgorithm):
        # a program from before the engine's device layout answers this
        # deployment from host arrays, 6.7 queries/s with nothing on the
        # chip (my chip run, PR 35): that is another configuration than
        # the one `serving.layout` states, and a traced run of it holds
        # no device operation. Said here, before the minutes of set-up
        harness.fail("this program has no device layout for the "
                     "e-commerce engine (ops/topk.py masked_topk_rows): it "
                     "cannot run the configuration "
                     f"{config['name']!r} as its serving.layout states it")
    nu, ni, r = model["n_users"], model["n_items"], model["rank"]
    U = gen_factors.matrix(seed, "user", nu, r, model["decay"])
    V = gen_factors.matrix(seed, "item", ni, r, model["decay"])
    cats = item_categories(seed, model)
    # one Item a category, shared by its items: what the blob pickles
    kinds = [Item(categories=(f"c{c}",))
             for c in range(model["n_categories"])]
    items = {i: kinds[c] for i, c in enumerate(cats.tolist())}
    app_name = variant["algorithms"][0]["params"]["appName"]
    app_id = storage.get_meta_data_apps().insert(App(0, app_name, None))
    storage.get_events().init(app_id)
    t0 = datetime.datetime(2021, 1, 1, tzinfo=datetime.timezone.utc)
    events = [Event(event=("view", "buy")[i % 2], entity_type="user",
                    entity_id=f"u{u}", target_entity_type="item",
                    target_entity_id=f"i{i}",
                    event_time=t0 + datetime.timedelta(seconds=u % 86400))
              for u in event_users(config, model, seed).tolist()
              for i in seen_items(seed, model, u).tolist()]
    events.append(Event(
        event="$set", entity_type="constraint", entity_id="unavailableItems",
        properties=DataMap({"items": [
            f"i{i}" for i in unavailable_items(seed, model).tolist()]}),
        event_time=t0 + datetime.timedelta(days=1)))
    store.write(events, app_id, storage=storage)
    harness.log("ecomm:events", written=len(events))
    return [ECommModel(
        rank=r, user_features=U, product_features=V,
        user_vocab=BiMap({f"u{k}": k for k in range(nu)}),
        item_vocab=BiMap({f"i{k}": k for k in range(ni)}),
        items=items, user_trained=np.ones(nu, bool),
        item_trained=np.ones(ni, bool),
        category_masks={f"c{c}": cats == c
                        for c in range(model["n_categories"])},
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


def _ignored_constraint():
    """constraint/unavailableItems never read: nothing is unavailable."""
    from predictionio_tpu.models.ecommerce import als_algorithm

    als_algorithm.ECommAlgorithm._unavailable_items = lambda self: set()
    # the read both layouts share; a program from before the device
    # layout (the parent, under these files) has only the one above
    if hasattr(als_algorithm.ECommAlgorithm, "_constraint_event"):
        als_algorithm.ECommAlgorithm._constraint_event = lambda self: None


#: tests only: name -> what plants the fault under the timed path
FAULTS = {"altered_answer": _altered_answer,
          "ignored_seen_filter": _ignored_seen_filter,
          "ignored_constraint": _ignored_constraint}
