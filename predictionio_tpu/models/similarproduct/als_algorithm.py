"""ALSAlgorithm: implicit ALS item vectors + summed-cosine top-K.

Parity: scala-parallel-similarproduct/multi/src/main/scala/
ALSAlgorithm.scala (train :57-120, predict :122-160, cosine :214-231,
isCandidateItem :233+) and LikeAlgorithm.scala (like/dislike ratings,
latest event wins). The per-item RDD lookup + driver-side cosine loop
becomes one matmul: sum of cosines against Q query vectors equals
(V_hat @ sum(q_hat)) where hats are L2-normalized rows.

Serving layouts (``prepare_serving``): on an accelerator the normalized
item factors, one word array of category bits an item and one
eligibility array (trained) live on the device, and a flush is ONE
dispatch of ops/topk.py itemset_topk_rows: a query sends the indices of
its items (padded to ``topk.QUERY_WIDTH``), a row of category bits and a
short list of excluded item indices (its own items + its black list),
never a mask as long as the catalog. A query the device program has no
argument for (whiteList, more items than the declared width, an
exclusion list past ``topk.EXCLUDE_WIDTHS``) is answered by the host
layout's code and counted (``hostFallbacks``); the host copy of the
factors stays for it (KNOWN_ISSUES.md). On the CPU backend a tiny model
keeps the host layout: one BLAS product + argpartition.
"""

from __future__ import annotations

import dataclasses
import logging
import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from predictionio_tpu.common import telemetry, waterfall
from predictionio_tpu.controller import Algorithm, Params
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models import item_rules
from predictionio_tpu.models.item_rules import (   # noqa: F401  (their
    build_category_masks, candidate_mask,          # old home: importers)
)
from predictionio_tpu.models.similarproduct.data_source import TrainingData
from predictionio_tpu.models.similarproduct.engine import (
    ItemScore, PredictedResult, Query,
)
from predictionio_tpu.ops import als, topk
from predictionio_tpu.serving.protocol import (
    bucket_for, device_layout_or_host, device_rows,
)

logger = logging.getLogger("predictionio_tpu.similarproduct")

_REG = telemetry.registry()
_M_QUERIES = _REG.counter(
    "pio_simprod_queries_total",
    "Similar-product queries answered, by any layout").child()
_M_QUERY_ITEMS = _REG.counter(
    "pio_simprod_query_items_total",
    "Item names the queries carried in `items`").child()
_M_UNKNOWN_ITEMS = _REG.counter(
    "pio_simprod_unknown_items_total",
    "Query items dropped: unknown to the model or untrained").child()
_M_EXCLUDED = _REG.counter(
    "pio_simprod_excluded_items_total",
    "Item indices (the query's own items + its black list) handed to "
    "the device program as exclusions").child()
_M_HOST_FALLBACKS = _REG.counter(
    "pio_simprod_host_fallbacks_total",
    "Queries answered by the host kernels while a device layout is "
    "deployed (whiteList, more query items than the declared width, "
    "exclusion list past the largest declared width)").child()
_M_WIDTH_FLUSHES = _REG.counter(
    "pio_simprod_exclude_width_flushes_total",
    "Device flushes by the declared exclusion width they were padded "
    "to", labelnames=("width",))


def stats() -> Dict[str, Any]:
    """`GET /`'s ``simprod`` block: the process-wide counters /metrics
    has as ``pio_simprod_*``, and the widths the device program is
    compiled for."""
    return {
        "queries": int(_M_QUERIES.value),
        "queryItems": int(_M_QUERY_ITEMS.value),
        "unknownItems": int(_M_UNKNOWN_ITEMS.value),
        "excludedItems": int(_M_EXCLUDED.value),
        "hostFallbacks": int(_M_HOST_FALLBACKS.value),
        "queryWidth": topk.QUERY_WIDTH,
        "excludeWidths": {
            str(w): int(_M_WIDTH_FLUSHES.labels(width=str(w)).value)
            for w in topk.EXCLUDE_WIDTHS},
    }


def _result(model, vals, idx) -> PredictedResult:
    """(score, index) rows -> PredictedResult, dropping scores <= 0
    (the reference keeps only positive scores, ALSAlgorithm.scala:167)
    and non-finite ones (``topk.NEG_INF``: no candidate left)."""
    inv = model.item_vocab.inverse()
    return PredictedResult(tuple(
        ItemScore(item=inv(int(ix)), score=float(s))
        for s, ix in zip(vals, idx) if 0 < s < math.inf))


@dataclass(frozen=True)
class ALSAlgorithmParams(Params):
    rank: int = 10
    numIterations: int = 20
    lambda_: float = 0.01
    seed: Optional[int] = None

    JSON_ALIASES = {"lambda": "lambda_"}


@dataclass
class ALSModel:
    """productFeatures + itemStringIntMap + the items' categories
    (ALSModel, ALSAlgorithm.scala:31-55). `product_features` holds UNIT
    rows (train normalizes once). `trained_mask` excludes items with no
    interactions — the analogue of ids absent from MLlib's
    productFeatures RDD. Upstream's `items: Map[Int, Item]` is carried
    as arrays: `rule_words` + `category_bits` (models/item_rules.py
    category_words), one word of category bits an item whatever the
    catalog's length. The device layout keeps the words resident; the
    host code reads a query's category vector off them."""
    product_features: "np.ndarray"      # (n_items, rank), unit rows
    item_vocab: BiMap
    trained_mask: "np.ndarray"          # (n_items,) bool
    rule_words: "np.ndarray"            # (w, n_items) uint32
    category_bits: item_rules.CategoryBits
    #: serve-time-only state (ItemSetDevice) when prepare_serving chose
    #: the device layout; never persisted (train's output has None, and
    #: a pickle from before the field lacks it: read with getattr)
    device: Optional["ItemSetDevice"] = None

    def __setstate__(self, state: Dict[str, Any]) -> None:
        """A pickle from before the categories were words carried one
        Item an index (`items`) and one boolean vector a category
        (`category_masks`): made into words once, here at load."""
        if "rule_words" not in state:
            state = dict(state)
            state["category_bits"], state["rule_words"] = \
                item_rules.category_words(
                    state.pop("items"), len(state["item_vocab"]),
                    state.pop("category_masks", None))
        self.__dict__.update(state)

    def __str__(self) -> str:
        return (f"ALSModel(productFeatures: [{len(self.item_vocab)}], "
                f"itemStringIntMap: [{len(self.item_vocab)}])")

    def serving_layout(self) -> Dict[str, Any]:
        """`GET /` ``batching``: where the arrays that answer the
        flushes live."""
        dev = getattr(self, "device", None)
        if dev is None:
            return {"layout": "host", "shards": 0, "perShardBytes": 0}
        return {"layout": "items+rules", "shards": 1,
                "perShardBytes": dev.nbytes(),
                "queryWidth": topk.QUERY_WIDTH,
                "excludeWidths": list(topk.EXCLUDE_WIDTHS)}

    def topk_rows(self) -> Optional[int]:
        """The score row the deployed device programs select from."""
        dev = getattr(self, "device", None)
        return None if dev is None else dev.n_items

    def hbm_bytes(self) -> int:
        """serving/registry.py model_hbm_bytes: what the device holds."""
        dev = getattr(self, "device", None)
        return 0 if dev is None else dev.nbytes()

    def status_block(self) -> Tuple[str, Dict[str, Any]]:
        return "simprod", stats()


@dataclass
class ItemSetDevice(item_rules.RuleDevice):
    """What the device holds of a deployed ALSModel: the rule arrays of
    models/item_rules.py RuleDevice, `item_factors` the UNIT rows a
    flush's query items are gathered from and scored against."""

    def topk(self, want, exclude):
        """device_rows' ``topk_fn`` for one flush's rule arguments."""
        return lambda query_ixs, k: topk.itemset_topk_rows(
            self.item_factors, self.rule_words, self.eligible,
            query_ixs, want, exclude, k=k)


def _place(model: ALSModel) -> ItemSetDevice:
    """The device layout of ``model``."""
    import jax

    return ItemSetDevice(
        item_factors=jax.device_put(
            np.asarray(model.product_features, np.float32)),
        rule_words=jax.device_put(
            np.asarray(model.rule_words, np.uint32)),
        category_bits=model.category_bits,
        eligible=jax.device_put(np.array(model.trained_mask, dtype=bool)))


class ALSAlgorithm(Algorithm):
    params_class = ALSAlgorithmParams
    query_class = Query

    def __init__(self, params: ALSAlgorithmParams):
        self.ap = params

    # ------------------------------------------------------------- training
    def _ratings(self, data: TrainingData, user_vocab: BiMap,
                 item_vocab: BiMap):
        """view events -> (u, i, count) implicit ratings
        (ALSAlgorithm.scala:80-103: duplicate views aggregate by sum)."""
        if not data.view_events:
            raise ValueError(
                "viewEvents in PreparedData cannot be empty. Please check "
                "if DataSource generates TrainingData correctly.")
        counts: Dict[Tuple[int, int], float] = {}
        for v in data.view_events:
            u, i = user_vocab.get(v.user), item_vocab.get(v.item)
            if u is None:
                logger.info("Couldn't convert nonexistent user ID %s", v.user)
                continue
            if i is None:
                logger.info("Couldn't convert nonexistent item ID %s", v.item)
                continue
            counts[(u, i)] = counts.get((u, i), 0.0) + 1.0
        return counts

    def train(self, ctx, data: TrainingData) -> ALSModel:
        if not data.users:
            raise ValueError("users in PreparedData cannot be empty.")
        if not data.items:
            raise ValueError("items in PreparedData cannot be empty.")
        user_vocab = BiMap.string_int(data.users.keys())
        item_vocab = BiMap.string_int(data.items.keys())
        ratings = self._ratings(data, user_vocab, item_vocab)
        if not ratings:
            raise ValueError(
                "ratings cannot be empty. Please check if your events "
                "contain valid user and item ID.")
        u_idx = np.array([u for u, _ in ratings], dtype=np.int32)
        i_idx = np.array([i for _, i in ratings], dtype=np.int32)
        vals = np.array(list(ratings.values()), dtype=np.float32)
        seed = self.ap.seed if self.ap.seed is not None else (
            np.random.SeedSequence().entropy % (2 ** 31))
        prepared = als.prepare_ratings(
            u_idx, i_idx, vals,
            n_users=len(user_vocab), n_items=len(item_vocab), device=True)
        _U, V = als.train_implicit(
            prepared, rank=self.ap.rank, iterations=self.ap.numIterations,
            lambda_=self.ap.lambda_, alpha=1.0, seed=int(seed))
        trained = np.zeros(len(item_vocab), dtype=bool)
        trained[np.unique(i_idx)] = True
        bits, words = item_rules.category_words(
            {item_vocab(k): v for k, v in data.items.items()},
            len(item_vocab))
        # pre-normalize once: sum-of-cosines per item is then one matvec
        V = np.asarray(V)
        V_hat = V / np.maximum(
            np.linalg.norm(V, axis=1, keepdims=True), 1e-12)
        return ALSModel(product_features=V_hat, item_vocab=item_vocab,
                        trained_mask=trained, rule_words=words,
                        category_bits=bits)

    # ------------------------------------------------------ serving layout
    def prepare_serving(self, model: ALSModel) -> ALSModel:
        """On an accelerator the device layout (module docstring),
        always; on the CPU backend by the probe every rule engine uses
        (serving/protocol.py device_layout_or_host)."""
        def probe(dev):
            run = dev.topk(*dev.rule_arguments(1, 0))
            ixs = np.full((1, topk.QUERY_WIDTH), dev.n_items, np.int32)
            ixs[0, 0] = 0
            return lambda: run(ixs, min(10, dev.n_items))

        return dataclasses.replace(model, device=device_layout_or_host(
            lambda: _place(model), probe, logger))

    def aot_serving_programs(self, model: ALSModel, buckets,
                             declared: bool = False):
        """This model's device programs from declared shapes
        (serving/aot.py): itemset_topk_rows per (bucket, exclusion
        width, k), bucket 1 always among them for ``predict``. Nothing
        on the host layout; ``declared=True`` (the `pio train`
        cache-artifact export) enumerates regardless."""
        from predictionio_tpu.serving import aot

        dev = getattr(model, "device", None)
        if dev is None and not declared:
            return ()
        n_items, rank = (int(d) for d in np.shape(model.product_features))
        return aot.specs_itemset_topk_rows(
            n_items, rank, int(np.shape(model.rule_words)[0]),
            sorted({1, *buckets}),
            aot.serving_ks(n_items), device=dev)

    # ------------------------------------------------------------ serving
    def _query_ixs(self, model: ALSModel, query: Query,
                   count: bool = True) -> List[int]:
        """The query's items the model has a vector for, as sorted
        distinct indices (upstream's queryList is a Set): unknown and
        untrained ones dropped and, unless the device layout counted
        them before it handed the query to the host code, counted."""
        ixs = [model.item_vocab.get(i) for i in query.items]
        known = sorted({ix for ix in ixs
                        if ix is not None and model.trained_mask[ix]})
        if count:
            _M_QUERY_ITEMS.inc(len(ixs))
            _M_UNKNOWN_ITEMS.inc(sum(
                1 for ix in ixs
                if ix is None or not model.trained_mask[ix]))
        if not known:
            logger.info("No productFeatures vector for query items %s.",
                        query.items)
        return known

    def _plan(self, model: ALSModel, query: Query, count: bool = True):
        """Per-query prep of the host layout: encode the query items,
        build the sum-of-normalized-vectors query vector and the
        candidate mask. None when no query item has a trained vector
        (the reference's empty-result path)."""
        query_ixs = self._query_ixs(model, query, count)
        if not query_ixs:
            return None
        V_hat = np.asarray(model.product_features)
        q = np.sum(V_hat[query_ixs], axis=0)
        mask = candidate_mask(
            n_items=len(model.item_vocab),
            trained=model.trained_mask,
            category_masks={}, categories=None,
            white=self._encode_set(model, query.whiteList),
            black=self._encode_set(model, query.blackList) or set(),
            exclude=set(query_ixs),
        )
        if query.categories is not None:
            mask &= item_rules.category_mask_of_words(
                model.category_bits, model.rule_words, query.categories)
        return q, mask

    def predict(self, model: ALSModel, query: Query) -> PredictedResult:
        """Sum-of-cosines against the query items' vectors, filtered
        and top-K'd (replaces the reference's driver-side
        productFeatures scan, ALSAlgorithm.scala:122-212): with rows
        pre-normalized, sum_q cos(q, v) == V_hat @ sum(q_hat). A flush
        of one, on the bucket-1 program where the device layout is
        deployed."""
        return self.predict_batch(model, [query])[0]

    def predict_batch(self, model: ALSModel,
                      queries) -> List[PredictedResult]:
        """Serving micro-batch, by the layout prepare_serving chose."""
        queries = list(queries)
        _M_QUERIES.inc(len(queries))
        dev = getattr(model, "device", None)
        if dev is not None:
            return self._predict_batch_device(model, dev, queries)
        return self._predict_batch_host(model, queries)

    def _predict_batch_device(self, model: ALSModel, dev: ItemSetDevice,
                              queries) -> List[PredictedResult]:
        """One flush on the device layout. `rules` (host): item names
        to indices (`rules.items`), then the flush's arguments: a (b, q)
        array of query-item indices, a (b, w) array of wanted-category
        bits and a (b, E) array of excluded indices (the query's own
        items and its black list), padded to the flush's bucket and to
        the declared widths. Then the shared device half (`pad`,
        `execute` > `enqueue`, `device_get`) and `unpack`."""
        out: List[Optional[PredictedResult]] = [None] * len(queries)
        n_items = dev.n_items
        item_ix = model.item_vocab.get
        host: List[int] = []
        rows: List[Tuple[int, Query, List[int], set]] = []
        with waterfall.stage("rules"):
            with waterfall.stage("rules.items"):
                for qx, query in enumerate(queries):
                    ixs = self._query_ixs(model, query)
                    if not ixs or min(query.num, n_items) <= 0:
                        out[qx] = PredictedResult(())
                        continue
                    gone = {item_ix(x) for x in query.blackList or ()}
                    gone.discard(None)
                    gone.update(ixs)
                    if query.whiteList is not None \
                            or len(ixs) > topk.QUERY_WIDTH \
                            or len(gone) > topk.EXCLUDE_WIDTHS[-1]:
                        host.append(qx)
                    else:
                        rows.append((qx, query, ixs, gone))
            if rows:
                want, exclude = dev.rule_arguments(
                    bucket_for(len(rows)),
                    max(len(gone) for *_, gone in rows))
                items = np.full((len(rows), topk.QUERY_WIDTH), n_items,
                                np.int32)
                for r, (_qx, query, ixs, gone) in enumerate(rows):
                    items[r, :len(ixs)] = ixs
                    dev.fill_rule_row(want, exclude, r, query.categories,
                                      gone)
                _M_EXCLUDED.inc(sum(len(gone) for *_, gone in rows))
                _M_WIDTH_FLUSHES.labels(
                    width=str(exclude.shape[1])).inc()
        if rows:
            k = min(max(q.num for _qx, q, _ixs, _g in rows), n_items)
            vals, idx = device_rows(dev.topk(want, exclude), items, k,
                                    fill=n_items)
            waterfall.note("rules", int(exclude.shape[1]))
            with waterfall.stage("unpack"):
                for (qx, query, _ixs, _g), rvals, ridx in zip(
                        rows, vals.tolist(), idx.tolist()):
                    n = min(query.num, k)
                    out[qx] = _result(model, rvals[:n], ridx[:n])
        if host:
            _M_HOST_FALLBACKS.inc(len(host))
            answers = self._predict_batch_host(
                model, [queries[qx] for qx in host], count=False)
            for qx, res in zip(host, answers):
                out[qx] = res
        return out

    def _predict_batch_host(self, model: ALSModel, queries,
                            count: bool = True) -> List[PredictedResult]:
        """The host layout: the per-query matvec becomes ONE
        (B, rank) @ (rank, n_items) BLAS matmul over the stacked query
        vectors; masking/top-K/positive-score filtering stay per row.
        ``count=False``: the device layout's fallback, whose query
        items were counted when it sorted the flush."""
        out: List[Optional[PredictedResult]] = [None] * len(queries)
        plans = []
        for qx, query in enumerate(queries):
            plan = self._plan(model, query, count)
            if plan is None or not plan[1].any():
                out[qx] = PredictedResult(())
            else:
                plans.append((qx, query, plan))
        if not plans:
            return out
        rows = topk.host_masked_topk_batch(
            model.product_features,
            np.stack([q for _qx, _query, (q, _m) in plans]),
            [m for _qx, _query, (_q, m) in plans],
            [min(query.num, m.shape[0])
             for _qx, query, (_q, m) in plans])
        for (qx, _query, _plan), (vals, idx) in zip(plans, rows):
            out[qx] = _result(model, vals, idx)
        return out

    @staticmethod
    def _encode_set(model: ALSModel, names) -> Optional[set]:
        if names is None:
            return None
        out = {model.item_vocab.get(n) for n in names}
        out.discard(None)
        return out


class LikeAlgorithm(ALSAlgorithm):
    """Trains on like/dislike events: per (user, item) the LATEST event
    wins; like -> 1, dislike -> -1 (LikeAlgorithm.scala:25-80)."""

    def _ratings(self, data: TrainingData, user_vocab: BiMap,
                 item_vocab: BiMap):
        if not data.like_events:
            raise ValueError(
                "likeEvents in PreparedData cannot be empty. Please check "
                "if DataSource generates TrainingData correctly.")
        latest: Dict[Tuple[int, int], Tuple[float, bool]] = {}
        for ev in data.like_events:
            u, i = user_vocab.get(ev.user), item_vocab.get(ev.item)
            if u is None or i is None:
                continue
            cur = latest.get((u, i))
            if cur is None or ev.t > cur[0]:
                latest[(u, i)] = (ev.t, ev.like)
        return {k: (1.0 if like else -1.0)
                for k, (_t, like) in latest.items()}
