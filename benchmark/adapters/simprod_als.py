"""The Similar Product template (implicit ALS item vectors; a query is a
SET of items, a candidate's score the sum of its cosines with them;
categories, black lists, the query's own items never among the answers)
as the benchmark deploys and queries it: everything that knows that
engine at the size of a cell. A configuration names this file by
`"adapter": "simprod_als"`. Structure, `check` and `FAULTS` after
adapters/ecomm_als.py.

Same two halves as adapters/rec_als.py: NumPy at the top for the parent,
the program imported inside the child's functions. What both halves need
of the deployment's data (an item's category) comes from the seed
through the functions below, never from what the program has made. This
deployment reads no event while it serves.
"""

import json
import os

import numpy as np

import gen_factors
import harness
import simprod_reference as reference

rec_als = harness.load_adapter("rec_als")    # its zipf draw
ecomm_als = harness.load_adapter("ecomm_als")    # its wire

# ---------------------------------------------------------------------------
# the deployment's data, from the seed
# ---------------------------------------------------------------------------


def item_categories(seed, model):
    """(n_items,) the one category index of each item: category c is
    drawn with weight 1/(c+1)."""
    rng = np.random.default_rng([int(seed), 0x5D1])
    weight = 1.0 / np.arange(1, model["n_categories"] + 1)
    return rng.choice(model["n_categories"], model["n_items"],
                      p=weight / weight.sum())


def unit_rows(seed, model):
    """The item matrix as `ALSAlgorithm.train` stores it: the seed's
    factors (gen_factors, the spectrum every serving configuration
    has), each row at unit length."""
    return reference.normalize(gen_factors.matrix(
        seed, "item", model["n_items"], model["rank"], model["decay"]))


# ---------------------------------------------------------------------------
# parent half
# ---------------------------------------------------------------------------


def rehearsal_model(model, cut):
    """The model's shape cut down for rehearsals and tests only."""
    return {**model, "n_items": max(256, int(model["n_items"] / cut))}


def queries(spec, model, seed, n):
    """n queries, each the JSON text it is sent as. A query's items are
    drawn without repeat from zipf(a) over a seeded permutation of the
    catalog (rec_als.query_users' draw, over items): one item with
    probability `one_item_share`, else 2 to `items_max` uniformly; half
    of the queries with one or two categories, a third with a black
    list of 1 to 5 items; no white list; every item known and trained."""
    q = spec["config"]["query"]
    ni, C, most = model["n_items"], model["n_categories"], q["items_max"]
    # twice the columns a query can need: what is left after the
    # repeats of a heavy-headed draw are struck
    drawn = rec_als.query_users(seed, n * 2 * most, ni,
                                q["zipf_a"]).reshape(n, 2 * most)
    rng = np.random.default_rng([int(seed), 0x5D4])
    n_items = np.where(rng.random(n) < q["one_item_share"], 1,
                       rng.integers(2, most + 1, n))
    n_cats = np.where(rng.random(n) < q["categories_share"],
                      rng.integers(1, 3, n), 0)
    first = rng.integers(0, C, n)
    second = (first + rng.integers(1, C, n)) % C        # another one
    n_black = np.where(rng.random(n) < q["black_list_share"],
                       rng.integers(1, q["black_list_max"] + 1, n), 0)
    black = rng.integers(0, ni, (n, q["black_list_max"]))
    out = []
    for j in range(n):
        items = drawn[j, :1] if n_items[j] == 1 else \
            drawn[j, np.sort(np.unique(drawn[j], return_index=True)[1])
                  ][:n_items[j]]
        query = {"items": [f"i{i}" for i in items], "num": q["num"]}
        if n_cats[j]:
            query["categories"] = [f"c{c}" for c in
                                   (first[j], second[j])[:n_cats[j]]]
        if n_black[j]:
            query["blackList"] = [f"i{i}" for i in black[j, :n_black[j]]]
        out.append(json.dumps(query))
    return out


# a query goes out as its own text, and a filtered query may rightly get
# fewer than `num` items: the e-commerce adapter's side of the wire
Wire, wire = ecomm_als.Wire, ecomm_als.wire


def _ixs(names):
    return [int(name[1:]) for name in names]


def check(spec, model, seed, asked, records, control=False):
    """A sample of the window's requests against simprod_reference:
    the items due, in order, to within the limits. -> the numbers
    compared: `filter_leaks` counts served items the rules exclude (one
    of the query's own items, a black-listed item, an item outside the
    asked categories); `bad_replies` replies missing, repeating an
    item, or not as long as the reference's; `rank_gap` / `score_gap`
    as compare.topk_numbers, over the candidates."""
    traffic, config = spec["traffic"], spec["config"]
    k = config["query"]["num"]
    rng = np.random.default_rng([int(seed), 0xC4])
    n = min(int(traffic["checked_replies"]), len(records))
    picks = rng.choice(len(records), n, replace=False)
    ni = model["n_items"]
    V = unit_rows(seed, model)
    cats = item_categories(seed, model)
    sample = [json.loads(asked[records[p][0]]) for p in picks]
    own = [reference.query_items(_ixs(q["items"])) for q in sample]
    rows = np.stack([reference.query_vector(V[items], items)
                     for items in own])
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
            query = sample[s + j]
            mask = reference.candidates(
                ni, cats,
                _ixs(query["categories"]) if "categories" in query else None,
                own[s + j], _ixs(query.get("blackList", ())))
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


def _hold_to_the_layout(config):
    """The deploy this child goes on to make answers from the layout
    the configuration states (`serving.deploy_layout`, what `GET /`
    shows as `batching.layout`) or ends here, before `/readyz`: the
    harness hands an adapter no page to look at afterwards. On an
    accelerator the engine raises by itself where its layout fails; on
    the CPU backend it would serve from the host arrays, and a
    rehearsal's small model goes there whenever the probe's round trip
    runs over 3 ms, so a rehearsal holds the probe off."""
    from predictionio_tpu.models.similarproduct.als_algorithm import (
        ALSAlgorithm)

    if os.environ.get("BENCH_REHEARSE"):
        os.environ["PIO_SERVE_DEVICE_MS"] = "1e9"
    prepare = ALSAlgorithm.prepare_serving

    def prepared(self, model):
        out = prepare(self, model)
        layout = out.serving_layout()["layout"]
        if layout != config["serving"]["deploy_layout"]:
            harness.fail(f"the deploy's batching.layout is {layout!r}, not "
                         f"{config['serving']['deploy_layout']!r}: another "
                         f"configuration than {config['name']!r}")
        return out

    ALSAlgorithm.prepare_serving = prepared


def models(config, model, seed, storage, variant):
    """The engine's model as `ALSAlgorithm.train` leaves it (unit rows,
    the items' categories as rule words), on factors made from the
    seed: upstream's model for this template holds item vectors only.
    This deployment reads no event while it serves, so `storage` gets
    nothing."""
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.models.similarproduct.als_algorithm import (
        ALSAlgorithm, ALSModel)

    if "aot_serving_programs" not in vars(ALSAlgorithm):
        # a program from before the engine's device layout answers this
        # deployment with one host BLAS product over the whole catalog
        # a flush (153 GFLOP at 64 x 9,350,000 x 128) and nothing on the
        # chip: another configuration than the one `serving.layout`
        # states, and a traced run of it holds no device operation.
        # Said here, before the minutes of set-up
        harness.fail("this program has no device layout for the "
                     "similar-product engine (ops/topk.py "
                     "itemset_topk_rows): it cannot run the configuration "
                     f"{config['name']!r} as its serving.layout states it")
    from predictionio_tpu.models import item_rules

    _hold_to_the_layout(config)
    ni = model["n_items"]
    cats = item_categories(seed, model)
    # train's own call, on one boolean vector a category in the place
    # of one Python object an item
    bits, words = item_rules.category_words(
        {}, ni, {f"c{c}": cats == c for c in range(model["n_categories"])})
    return [ALSModel(
        product_features=unit_rows(seed, model),
        item_vocab=BiMap({f"i{k}": k for k in range(ni)}),
        trained_mask=np.ones(ni, bool),
        rule_words=words, category_bits=bits)]


def _altered_answer():
    """Every answer leaves with its best item replaced."""
    from predictionio_tpu.models.similarproduct import als_algorithm
    from predictionio_tpu.models.similarproduct.engine import (
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


def _ignored_categories():
    """A query's categories ignored: every category answers it."""
    import dataclasses

    from predictionio_tpu.models.similarproduct import als_algorithm

    honest = als_algorithm.ALSAlgorithm.predict_batch
    als_algorithm.ALSAlgorithm.predict_batch = \
        lambda self, model, queries: honest(self, model, [
            dataclasses.replace(q, categories=None) for q in queries])


def _own_items_served():
    """The query's own items (and its black list) not held back: on the
    device layout the flush's exclusion rows stay padding, on the host
    layout the candidate mask is made without them."""
    from predictionio_tpu.models import item_rules
    from predictionio_tpu.models.similarproduct import als_algorithm

    fill = item_rules.RuleDevice.fill_rule_row
    item_rules.RuleDevice.fill_rule_row = \
        lambda self, want, exclude, r, categories, gone: fill(
            self, want, exclude, r, categories, ())
    mask = als_algorithm.candidate_mask
    als_algorithm.candidate_mask = lambda **kw: mask(
        **{**kw, "black": set(), "exclude": set()})


def _host_layout():
    """The device layout cannot be placed: on the CPU backend the engine
    then serves from the host arrays, the deploy `_hold_to_the_layout`
    refuses."""
    from predictionio_tpu.models.similarproduct import als_algorithm

    def no_room(_model):
        raise RuntimeError("planted: no room on the device")

    als_algorithm._place = no_room


#: tests only: name -> what plants the fault under the timed path
FAULTS = {"altered_answer": _altered_answer,
          "ignored_categories": _ignored_categories,
          "own_items_served": _own_items_served,
          "host_layout": _host_layout}
