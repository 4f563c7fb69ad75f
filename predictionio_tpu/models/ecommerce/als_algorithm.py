"""ECommAlgorithm: explicit ALS + live business-rule filtering at serve time.

Parity: scala-parallel-ecommercerecommendation/train-with-rate-event/src/
main/scala/ALSAlgorithm.scala — train :49-131 (rate events, latest value
per (user, item) wins, ALS.train); predict :133-260 (seen-events and
unavailable-items constraints read LIVE from the event store per query,
known users score by U[u] . V, unknown users by similarity to their
recent views).

Serving layouts (``prepare_serving``): on an accelerator the factors,
one word array of category bits an item and one eligibility array
(trained AND NOT on the constraint's unavailableItems) live on the
device, and a flush is ONE dispatch of ops/topk.py masked_topk_rows:
what a query's rules add to it is a row of category bits and a short
list of excluded item indices (black list + seen items), never a mask
as long as the catalog. The rule reads stay LIVE: one seen-items read a
query, one constraint read a flush. A query the device program has no
argument for (whiteList, a user the model does not know, an exclusion
list past ops/topk.py EXCLUDE_WIDTHS) is answered by the host layout's
code and counted (``hostFallbacks``). On the CPU backend a tiny model
keeps the host layout: one BLAS product + argpartition.
"""

from __future__ import annotations

import dataclasses
import logging
import math
import threading
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Set, Tuple

import jax.numpy as jnp
import numpy as np

from predictionio_tpu.common import resilience, telemetry, waterfall
from predictionio_tpu.controller import Algorithm, Params
from predictionio_tpu.data import store
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models.ecommerce.data_source import TrainingData
from predictionio_tpu.models.ecommerce.engine import (
    Item, ItemScore, PredictedResult, Query,
)
from predictionio_tpu.models import item_rules
from predictionio_tpu.models.item_rules import (
    build_category_masks, candidate_mask, category_words,
)
from predictionio_tpu.ops import als, topk
from predictionio_tpu.serving.protocol import (
    bucket_for, device_layout_or_host, device_rows,
)

logger = logging.getLogger("predictionio_tpu.ecommerce")

_REG = telemetry.registry()
_M_QUERIES = _REG.counter(
    "pio_ecomm_queries_total",
    "E-commerce queries answered, by any layout").child()
_M_EXCLUDED = _REG.counter(
    "pio_ecomm_excluded_items_total",
    "Item indices (black list + seen items) handed to the device "
    "program as exclusions").child()
_M_SEEN_READS = _REG.counter(
    "pio_ecomm_seen_reads_total",
    "Live seen-items reads of the event store (one a query with "
    "unseenOnly)").child()
_M_CONSTRAINT_READS = _REG.counter(
    "pio_ecomm_constraint_reads_total",
    "Live reads of constraint/unavailableItems (one a flush on the "
    "device layout, one a query on the host layout)").child()
_M_CONSTRAINT_UPLOADS = _REG.counter(
    "pio_ecomm_constraint_uploads_total",
    "Eligibility arrays placed on the device: one a deploy and one "
    "each time the constraint's last $set changed").child()
_M_HOST_FALLBACKS = _REG.counter(
    "pio_ecomm_host_fallbacks_total",
    "Queries answered by the host kernels while a device layout is "
    "deployed (whiteList, unknown user, exclusion list past the "
    "largest declared width)").child()
_M_WIDTH_FLUSHES = _REG.counter(
    "pio_ecomm_exclude_width_flushes_total",
    "Device flushes by the declared exclusion width they were padded "
    "to", labelnames=("width",))


def stats() -> Dict[str, Any]:
    """`GET /`'s ``ecomm`` block: the process-wide counters /metrics
    has as ``pio_ecomm_*``, and the widths the device program is
    compiled for."""
    return {
        "queries": int(_M_QUERIES.value),
        "excludedItems": int(_M_EXCLUDED.value),
        "seenReads": int(_M_SEEN_READS.value),
        "constraintReads": int(_M_CONSTRAINT_READS.value),
        "constraintUploads": int(_M_CONSTRAINT_UPLOADS.value),
        "hostFallbacks": int(_M_HOST_FALLBACKS.value),
        "excludeWidths": {
            str(w): int(_M_WIDTH_FLUSHES.labels(width=str(w)).value)
            for w in topk.EXCLUDE_WIDTHS},
    }


@dataclass(frozen=True)
class ECommAlgorithmParams(Params):
    """ALSAlgorithmParams (:33-41): appName (was appId), unseenOnly,
    seenEvents, similarEvents, rank, numIterations, lambda, seed."""
    appName: str
    unseenOnly: bool = False
    seenEvents: Tuple[str, ...] = ("buy", "view")
    similarEvents: Tuple[str, ...] = ("view",)
    rank: int = 10
    numIterations: int = 20
    lambda_: float = 0.01
    seed: Optional[int] = None
    #: weighted-items variant: live $set constraint/weightedItems boosts
    #: (weighted-items/ALSAlgorithm.scala:234-261). Off by default — the
    #: base reference template has a two-lookup hot path, and this adds an
    #: event-store point read (plus an O(n_items) weight vector when the
    #: constraint exists) per query. Opt in via engine.json.
    weightedItems: bool = False

    JSON_ALIASES = {"lambda": "lambda_"}

    def __post_init__(self):
        for f in ("seenEvents", "similarEvents"):
            v = getattr(self, f)
            if not isinstance(v, tuple):
                object.__setattr__(self, f, tuple(v))


@dataclass
class ECommModel:
    """ALSModel (:43-67): both factor sides + vocabs + item metadata;
    trained masks play the role of Option[Array] feature rows."""
    rank: int
    user_features: "np.ndarray"     # (n_users, rank)
    product_features: "np.ndarray"  # (n_items, rank)
    user_vocab: BiMap
    item_vocab: BiMap
    items: Dict[int, Item]
    user_trained: "np.ndarray"      # (n_users,) bool
    item_trained: "np.ndarray"      # (n_items,) bool
    category_masks: Dict[str, "np.ndarray"] = None
    product_features_hat: "np.ndarray" = None   # L2-normalized rows
    #: serve-time-only state (RuleDevice) when prepare_serving chose the
    #: device layout; never persisted (train's output has None, and a
    #: pickle from before the field lacks it: read with getattr)
    device: Optional["RuleDevice"] = None

    def serving_layout(self) -> Dict[str, Any]:
        """`GET /` ``batching``: where the arrays that answer the
        flushes live."""
        dev = getattr(self, "device", None)
        if dev is None:
            return {"layout": "host", "shards": 0, "perShardBytes": 0}
        return {"layout": "replicated+rules", "shards": 1,
                "perShardBytes": dev.nbytes(),
                "excludeWidths": list(topk.EXCLUDE_WIDTHS)}

    def topk_rows(self) -> Optional[int]:
        """The score row the deployed device programs select from."""
        dev = getattr(self, "device", None)
        return None if dev is None else int(dev.item_factors.shape[0])

    def hbm_bytes(self) -> int:
        """serving/registry.py model_hbm_bytes: what the device holds."""
        dev = getattr(self, "device", None)
        return 0 if dev is None else dev.nbytes()

    def status_block(self) -> Tuple[str, Dict[str, Any]]:
        return "ecomm", stats()


@dataclass
class RuleDevice(item_rules.RuleDevice):
    """What the device holds of a deployed ECommModel: the rule arrays
    every device layout with rules has (models/item_rules.py: item
    factors, the items' category bits, the eligibility array, here of
    the constraint as last read: ``constraint_key`` says which $set)
    and the user factors a flush's rows are gathered from."""
    user_factors: Any = None     # (n_users, r) float32, device
    constraint_key: Any = None
    lock: Any = dataclasses.field(default_factory=threading.Lock)

    def rule_arrays(self):
        return (self.user_factors, *super().rule_arrays())

    def topk(self, eligible, want, exclude):
        """device_rows' ``topk_fn`` for one flush's rule arguments."""
        return lambda pix, k: topk.masked_topk_rows(
            self.user_factors, self.item_factors, self.rule_words,
            eligible, pix, want, exclude, k=k)


def _place_eligible(model: ECommModel, unavailable):
    """(n_items,) bool on the device: trained AND NOT unavailable."""
    import jax

    eligible = np.array(model.item_trained, dtype=bool)
    gone = [model.item_vocab.get(x) for x in unavailable]
    eligible[[ix for ix in gone if ix is not None]] = False
    _M_CONSTRAINT_UPLOADS.inc()
    return jax.device_put(eligible)


def _place(model: ECommModel) -> RuleDevice:
    """The device layout of ``model``. The eligibility array starts
    from ``item_trained`` alone; the first flush reads the constraint."""
    import jax

    n_items = len(model.item_vocab)
    bits, words = category_words(model.items, n_items,
                                 model.category_masks)
    return RuleDevice(
        user_factors=jax.device_put(
            np.asarray(model.user_features, np.float32)),
        item_factors=jax.device_put(
            np.asarray(model.product_features, np.float32)),
        rule_words=jax.device_put(words), category_bits=bits,
        eligible=_place_eligible(model, ()))


class ECommAlgorithm(Algorithm):
    params_class = ECommAlgorithmParams
    query_class = Query

    def __init__(self, params: ECommAlgorithmParams):
        self.ap = params

    # ------------------------------------------------------------- training
    def train(self, ctx, data: TrainingData) -> ECommModel:
        if not data.rate_events:
            raise ValueError("rateEvents in PreparedData cannot be empty.")
        if not data.users:
            raise ValueError("users in PreparedData cannot be empty.")
        if not data.items:
            raise ValueError("items in PreparedData cannot be empty.")
        user_vocab = BiMap.string_int(data.users.keys())
        item_vocab = BiMap.string_int(data.items.keys())
        # latest rating per (user, item) wins (:76-97)
        latest: Dict[Tuple[int, int], Tuple[float, float]] = {}
        for r in data.rate_events:
            u, i = user_vocab.get(r.user), item_vocab.get(r.item)
            if u is None:
                logger.info("Couldn't convert nonexistent user ID %s", r.user)
                continue
            if i is None:
                logger.info("Couldn't convert nonexistent item ID %s", r.item)
                continue
            cur = latest.get((u, i))
            if cur is None or r.t > cur[0]:
                latest[(u, i)] = (r.t, r.rating)
        if not latest:
            raise ValueError(
                "ratings cannot be empty. Please check if your events "
                "contain valid user and item ID.")
        u_idx = np.array([u for u, _ in latest], dtype=np.int32)
        i_idx = np.array([i for _, i in latest], dtype=np.int32)
        vals = np.array([v for _t, v in latest.values()], dtype=np.float32)
        seed = self.ap.seed if self.ap.seed is not None else (
            np.random.SeedSequence().entropy % (2 ** 31))
        prepared = als.prepare_ratings(
            u_idx, i_idx, vals,
            n_users=len(user_vocab), n_items=len(item_vocab), device=True)
        U, V = als.train_explicit(
            prepared, rank=self.ap.rank, iterations=self.ap.numIterations,
            lambda_=self.ap.lambda_, seed=int(seed))
        user_trained = np.zeros(len(user_vocab), dtype=bool)
        user_trained[np.unique(u_idx)] = True
        item_trained = np.zeros(len(item_vocab), dtype=bool)
        item_trained[np.unique(i_idx)] = True
        items = {item_vocab(k): v for k, v in data.items.items()}
        V = np.asarray(V)
        V_hat = V / np.maximum(
            np.linalg.norm(V, axis=1, keepdims=True), 1e-12)
        return ECommModel(
            rank=self.ap.rank, user_features=np.asarray(U),
            product_features=V,
            user_vocab=user_vocab, item_vocab=item_vocab, items=items,
            user_trained=user_trained, item_trained=item_trained,
            category_masks=build_category_masks(items, len(item_vocab)),
            product_features_hat=V_hat)

    # ---------------------------------------------------------- live lookups
    def bind_serving(self, ctx) -> None:
        """Capture the workflow's storage for serve-time lookups so deploy
        and eval read the same store training did, not the process-global
        singleton (Algorithm.bind_serving hook)."""
        self._serving_storage = getattr(ctx, "storage", None)

    @property
    def _storage(self):
        return getattr(self, "_serving_storage", None)

    def _seen_items(self, user: str) -> Set[str]:
        """Seen events for this user, queried live (:148-176) — via the
        columnar target-id fast path (no Event materialization)."""
        if not self.ap.unseenOnly:
            return set()
        _M_SEEN_READS.inc()
        try:
            return set(store.find_target_ids(
                app_name=self.ap.appName, entity_type="user",
                entity_id=user, event_names=list(self.ap.seenEvents),
                target_entity_type="item", storage=self._storage))
        except Exception as e:
            logger.error("Error when read seen events: %s", e)
            # fail soft: serve from on-device factors without the seen
            # filter, flagged `degraded: true` by the query server
            resilience.note_degraded(f"seen-events lookup failed: {e}")
            return set()

    def _constraint_event(self):
        """Latest $set on constraint/unavailableItems, or None
        (:178-200): a point read. Raises what the store raises."""
        _M_CONSTRAINT_READS.inc()
        events = store.find_by_entity(
            app_name=self.ap.appName, entity_type="constraint",
            entity_id="unavailableItems", event_names=["$set"],
            limit=1, latest=True, storage=self._storage)
        return events[0] if events else None

    def _unavailable_items(self) -> Set[str]:
        """The host layout's read, one a query."""
        try:
            event = self._constraint_event()
        except Exception as e:
            logger.error("Error when read set unavailableItems event: %s", e)
            resilience.note_degraded(
                f"unavailableItems lookup failed: {e}")
            return set()
        if event is None:
            return set()
        return set(event.properties.get_opt("items") or ())

    def _eligible(self, model: "ECommModel", dev: "RuleDevice"):
        """The device layout's read, one a flush: the eligibility array
        of the constraint as it stands now. Placed on the device again
        only when its last $set is another event than the one the
        resident array was made from; a read that fails keeps the
        resident array and flags the flush degraded."""
        try:
            event = self._constraint_event()
        except Exception as e:
            logger.error("Error when read set unavailableItems event: %s", e)
            resilience.note_degraded(
                f"unavailableItems lookup failed: {e}")
            return dev.eligible
        key = None if event is None else (event.event_id, event.event_time)
        with dev.lock:
            if key != dev.constraint_key:
                names = () if event is None else (
                    event.properties.get_opt("items") or ())
                dev.eligible = _place_eligible(model, names)
                dev.constraint_key = key
            return dev.eligible

    def _item_weights(self, model: "ECommModel") -> Optional[np.ndarray]:
        """Latest $set on constraint/weightedItems → per-item score
        multipliers, default 1.0 (the weighted-items template variant,
        weighted-items/ALSAlgorithm.scala:234-261: groups of
        {items: [...], weight: w} so business rules can boost or bury
        item groups without retraining)."""
        try:
            events = store.find_by_entity(
                app_name=self.ap.appName, entity_type="constraint",
                entity_id="weightedItems", event_names=["$set"],
                limit=1, latest=True, storage=self._storage)
        except Exception as e:
            logger.error("Error when reading set weightedItems event: %s", e)
            resilience.note_degraded(f"weightedItems lookup failed: {e}")
            return None
        if not events:
            return None
        groups = events[0].properties.get_opt("weights") or ()
        w: Optional[np.ndarray] = None
        for g in groups:
            try:
                items = g.get("items") or ()
                weight = float(g.get("weight", 1.0))
                if isinstance(items, str) or not hasattr(items, "__iter__"):
                    raise TypeError(f"items must be a list, got {items!r}")
                for item in items:
                    ix = model.item_vocab.get(item)
                    if ix is not None:
                        if w is None:
                            w = np.ones(len(model.item_vocab),
                                        dtype=np.float32)
                        w[ix] = weight
            except (AttributeError, TypeError, ValueError) as e:
                # a malformed group must not turn every query into a 500
                logger.error("Malformed WeightsGroup %r ignored: %s", g, e)
        return w

    # ------------------------------------------------------ serving layout
    def prepare_serving(self, model: ECommModel) -> ECommModel:
        """On an accelerator the device layout (module docstring),
        always; on the CPU backend by the probe every rule engine uses
        (serving/protocol.py device_layout_or_host). With
        ``weightedItems`` the host layout stays: the device program
        takes no weights."""
        if self.ap.weightedItems:
            logger.info("weightedItems is on: serving from host arrays")
            return dataclasses.replace(model, device=None)

        def probe(dev):
            run = dev.topk(dev.eligible, *dev.rule_arguments(1, 0))
            ix, k = np.zeros(1, np.int32), min(10, len(model.item_vocab))
            return lambda: run(ix, k)

        return dataclasses.replace(model, device=device_layout_or_host(
            lambda: _place(model), probe, logger))

    def aot_serving_programs(self, model: ECommModel, buckets,
                             declared: bool = False):
        """This model's device programs from declared shapes
        (serving/aot.py): masked_topk_rows per (bucket, exclusion
        width, k), bucket 1 always among them for ``predict``. Nothing
        on the host layout; ``declared=True`` (the `pio train`
        cache-artifact export) enumerates regardless."""
        from predictionio_tpu.serving import aot

        dev = getattr(model, "device", None)
        if dev is None and not declared:
            return ()
        n_users, rank = (int(d) for d in np.shape(model.user_features))
        n_items = int(np.shape(model.product_features)[0])
        n_words = -(-(len(model.category_masks or {}) + 1) // 32) \
            if dev is None else int(dev.rule_words.shape[0])
        return aot.specs_masked_topk_rows(
            n_users, n_items, rank, n_words,
            sorted({1, *buckets}), aot.serving_ks(n_items), device=dev)

    # ------------------------------------------------------------- serving
    def _predict_batch_device(self, model: ECommModel, dev: "RuleDevice",
                              queries) -> List[PredictedResult]:
        """One flush on the device layout. `rules` (host): one
        seen-items read a query, ONE constraint read (every query of
        the flush sees a snapshot taken after it arrived), and the rule
        arguments: a row of wanted-category bits and a row of excluded
        item indices a query, padded to the flush's bucket and to a
        declared width. Then the shared device half (`pad`, `execute` >
        `enqueue`, `device_get`) and `unpack`."""
        out: List[Optional[PredictedResult]] = [None] * len(queries)
        n_items = len(model.item_vocab)
        item_ix = model.item_vocab.get
        host: List[int] = []
        rows: List[Tuple[int, Query, int, set]] = []
        with waterfall.stage("rules"):
            with waterfall.stage("rules.seen"):
                for qx, query in enumerate(queries):
                    if min(query.num, n_items) <= 0:
                        out[qx] = PredictedResult(())
                        continue
                    user_ix = model.user_vocab.get(query.user)
                    if query.whiteList is not None or user_ix is None \
                            or not model.user_trained[user_ix]:
                        host.append(qx)
                        continue
                    gone = {item_ix(x) for x in query.blackList or ()}
                    gone.update(item_ix(x)
                                for x in self._seen_items(query.user))
                    gone.discard(None)
                    if len(gone) > topk.EXCLUDE_WIDTHS[-1]:
                        host.append(qx)
                    else:
                        rows.append((qx, query, user_ix, gone))
            if rows:
                with waterfall.stage("rules.constraint"):
                    eligible = self._eligible(model, dev)
                want, exclude = dev.rule_arguments(
                    bucket_for(len(rows)),
                    max(len(gone) for *_, gone in rows))
                for r, (_qx, query, _ix, gone) in enumerate(rows):
                    dev.fill_rule_row(want, exclude, r, query.categories,
                                      gone)
                _M_EXCLUDED.inc(sum(len(gone) for *_, gone in rows))
                _M_WIDTH_FLUSHES.labels(
                    width=str(exclude.shape[1])).inc()
        if rows:
            k = min(max(q.num for _qx, q, _ix, _g in rows), n_items)
            vals, idx = device_rows(
                dev.topk(eligible, want, exclude),
                np.asarray([ix for _qx, _q, ix, _g in rows], np.int32), k)
            waterfall.note("rules", int(exclude.shape[1]))
            with waterfall.stage("unpack"):
                inv = model.item_vocab.inverse()
                for (qx, query, _ix, _g), rvals, ridx in zip(
                        rows, vals.tolist(), idx.tolist()):
                    n = min(query.num, k)
                    # _rows_to_result's rule: scores <= 0 (NEG_INF: no
                    # candidate left) and non-finite ones are dropped
                    out[qx] = PredictedResult(tuple(
                        ItemScore(item=inv(i), score=s)
                        for s, i in zip(rvals[:n], ridx[:n])
                        if 0 < s < math.inf))
        if host:
            _M_HOST_FALLBACKS.inc(len(host))
            answers = self._predict_batch_host(
                model, [queries[qx] for qx in host])
            for qx, res in zip(host, answers):
                out[qx] = res
        return out

    def _query_plan(self, model: ECommModel, query: Query):
        """Per-query business-rule prep shared by predict and
        predict_batch — the LIVE event-store lookups (seen events,
        unavailable items, recent views for unknown users) stay per query
        in both paths. Returns (query_vec, use_hat, mask) or None for the
        empty-result paths."""
        white = None
        if query.whiteList is not None:
            white = {model.item_vocab.get(x) for x in query.whiteList}
            white.discard(None)
        black_names = set(query.blackList or ())
        black_names |= self._seen_items(query.user)
        black_names |= self._unavailable_items()
        black = {model.item_vocab.get(x) for x in black_names}
        black.discard(None)

        user_ix = model.user_vocab.get(query.user)
        if user_ix is not None and model.user_trained[user_ix]:
            query_vec = np.asarray(model.user_features)[user_ix]
            use_hat = False
        else:
            logger.info("No userFeature found for user %s.", query.user)
            query_vec = self._recent_views_vector(model, query.user)
            if query_vec is None:
                return None
            use_hat = True
        mask = candidate_mask(
            n_items=len(model.item_vocab),
            trained=model.item_trained,
            category_masks=model.category_masks or {},
            categories=query.categories,
            white=white, black=black, exclude=set(),
        )
        if not mask.any():
            return None
        return query_vec, use_hat, mask

    def _rows_to_result(self, model: ECommModel, vals, idx) -> PredictedResult:
        inv = model.item_vocab.inverse()
        return PredictedResult(tuple(
            ItemScore(item=inv(int(ix)), score=float(s))
            for s, ix in zip(vals, idx) if s > 0 and np.isfinite(s)))

    def predict(self, model: ECommModel, query: Query) -> PredictedResult:
        """Known users score U[u] . V; unknown users fall back to
        similarity with their recent views — both as one masked device
        top-K (:202-260). On the device layout: a flush of one, on the
        bucket-1 program."""
        if getattr(model, "device", None) is not None:
            return self.predict_batch(model, [query])[0]
        _M_QUERIES.inc()
        plan = self._query_plan(model, query)
        if plan is None:
            return PredictedResult(())
        query_vec, use_hat, mask = plan
        factors = model.product_features_hat if use_hat \
            else model.product_features
        k = min(query.num, mask.shape[0])
        # host serving: the factor matrices are host numpy after train, and
        # one BLAS matvec + argpartition beats a per-query device dispatch
        # everywhere except a locally-attached chip with a huge catalog
        # (273 ms p50 through the early rounds' remote device vs <1 ms
        # host; not measured on the attached chip)
        weights = self._item_weights(model) if self.ap.weightedItems \
            else None
        vals, idx = topk.host_masked_topk(factors, query_vec, mask, k,
                                          weights=weights)
        return self._rows_to_result(model, vals, idx)

    def predict_batch(self, model: ECommModel,
                      queries) -> List[PredictedResult]:
        """Serving micro-batch, by the layout prepare_serving chose."""
        queries = list(queries)
        _M_QUERIES.inc(len(queries))
        dev = getattr(model, "device", None)
        if dev is not None:
            return self._predict_batch_device(model, dev, queries)
        return self._predict_batch_host(model, queries)

    def _predict_batch_host(self, model: ECommModel,
                            queries) -> List[PredictedResult]:
        """The host layout: per-query business rules stay live (one
        event-store lookup chain per query, as in predict), but the
        scoring matvecs coalesce into one (B, rank) @ (rank, n_items)
        matmul per factor side (known users score against raw factors,
        unknown users against the normalized ones). weightedItems reads
        ONE constraint snapshot per batch rather than per query — within
        a flush every query sees the same weights, which is also the
        stronger consistency story."""
        out: List[Optional[PredictedResult]] = [None] * len(queries)
        weights = self._item_weights(model) if self.ap.weightedItems \
            else None
        groups: Dict[bool, list] = {False: [], True: []}
        for qx, query in enumerate(queries):
            plan = self._query_plan(model, query)
            if plan is None:
                out[qx] = PredictedResult(())
            else:
                query_vec, use_hat, mask = plan
                groups[use_hat].append((qx, query, query_vec, mask))
        for use_hat, group in groups.items():
            if not group:
                continue
            factors = model.product_features_hat if use_hat \
                else model.product_features
            rows = topk.host_masked_topk_batch(
                factors,
                np.stack([vec for _qx, _q, vec, _m in group]),
                [m for _qx, _q, _vec, m in group],
                [min(q.num, m.shape[0]) for _qx, q, _vec, m in group],
                weights=weights)
            for (qx, _q, _vec, _m), (vals, idx) in zip(group, rows):
                out[qx] = self._rows_to_result(model, vals, idx)
        return out

    def _recent_views_vector(self, model: ECommModel,
                             user: str) -> Optional[jnp.ndarray]:
        """New-user fallback query vector: sum of normalized vectors of the
        latest 10 similar-events items; against normalized factors this
        scores the sum of cosines (predictNewUser, :262-330)."""
        try:
            events = store.find_by_entity(
                app_name=self.ap.appName, entity_type="user", entity_id=user,
                event_names=list(self.ap.similarEvents),
                target_entity_type="item", limit=10, latest=True,
                storage=self._storage)
        except Exception as e:
            logger.error("Error when read recent events: %s", e)
            resilience.note_degraded(f"recent-events lookup failed: {e}")
            return None
        recent_ixs = {model.item_vocab.get(e.target_entity_id)
                      for e in events if e.target_entity_id is not None}
        recent_ixs.discard(None)
        recent_ixs = {ix for ix in recent_ixs if model.item_trained[ix]}
        if not recent_ixs:
            return None
        V_hat = np.asarray(model.product_features_hat)
        return np.sum(V_hat[sorted(recent_ixs)], axis=0)
