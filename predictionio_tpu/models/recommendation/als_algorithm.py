"""ALSAlgorithm: explicit ALS on TPU + device-resident top-K serving.

Parity: recommendation-engine/src/main/scala/ALSAlgorithm.scala
(params :30-37, train :50-94, predict :95-110, batchPredict :113-148) and
ALSModel.scala. MLlib `ALS.train` becomes ops.als.train_explicit (or the
mesh-sharded variant when the WorkflowContext carries a mesh); the factor
matrices stay in HBM and predict is one fused matmul + top_k.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Iterable, List, Optional, Tuple

import numpy as np

from predictionio_tpu.controller import Algorithm, Params
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models.recommendation.engine import (
    ItemScore, PredictedResult, Query,
)
from predictionio_tpu.models.recommendation.preparator import PreparedData
from predictionio_tpu.ops import als, topk
from predictionio_tpu.serving.protocol import (
    device_rows, host_serves_faster,
)


@dataclass(frozen=True)
class ALSAlgorithmParams(Params):
    """engine.json keys (rank, numIterations, lambda, seed) — `lambda` is a
    Python keyword, accepted via the alias (ALSAlgorithm.scala:30-37).
    checkpointInterval additionally snapshots factors every N iterations
    so an interrupted train resumes (improvement; no reference analogue)."""
    rank: int = 10
    numIterations: int = 10
    lambda_: float = 0.01
    seed: Optional[int] = None
    checkpointInterval: Optional[int] = None

    # engine.json uses "lambda"; dataclass fields cannot, so extraction maps it
    JSON_ALIASES = {"lambda": "lambda_"}


@dataclass
class ALSModel:
    """Factor matrices + vocabs (ALSModel.scala: MatrixFactorizationModel +
    the two BiMaps). Arrays may be jax.Array (serving) or numpy (persisted).

    ``sharding`` is serve-time-only state (parallel/serve_dist.py): when
    prepare_serving chose the row-sharded layout it holds the
    ShardedFactors handle (mesh + padded shard arrays + the sharded
    top-k program) and ``user_factors``/``item_factors`` alias the
    PADDED sharded device arrays. Persisted blobs never carry it —
    serialization happens on the train output, where it is None — and
    loaders of pre-sharding pickles simply lack the attribute, hence
    the defensive ``getattr(model, "sharding", None)`` at every read.

    ``quant`` is the same shape of serve-time-only state for the
    QUANTIZED replicated layout (ops/quant.py QuantizedServing: device
    int8 factor blocks + fp32 per-row scales + the dequantize-free
    top-k programs). When set, ``user_factors``/``item_factors`` stay
    HOST fp32 numpy — the whole point is that no fp32 device copy
    exists; the eval/batch_predict paths keep reading the host arrays.
    A sharded AND quantized deploy carries the int8 layout inside
    ``sharding`` (ShardedFactors.dtype == "int8") with ``quant``
    None. /reload re-quantizes on load, so persisted blobs never carry
    either; pre-quant pickles lack the attribute, hence the defensive
    ``getattr(model, "quant", None)`` at every read."""
    rank: int
    user_factors: "np.ndarray"   # (n_users, rank)
    item_factors: "np.ndarray"   # (n_items, rank)
    user_vocab: BiMap
    item_vocab: BiMap
    sharding: Optional[object] = None
    quant: Optional[object] = None

    def __str__(self) -> str:
        return (f"ALSModel(rank={self.rank}, users={len(self.user_vocab)}, "
                f"items={len(self.item_vocab)})")


#: one-entry process-wide device-layout cache for full-scale trains.
#: Keyed on a CONTENT fingerprint (cheap meta tuple + a blake2b digest
#: over the three COO arrays): a changed event store can never reuse a
#: stale layout — the 128-bit digest makes a collision with identical
#: nnz/vocab sizes cryptographically impossible (the earlier 32-bit CRC
#: left a ~2^-32 silent-stale-layout window, ADVICE.md round 5) and
#: still hashes at ~GB/s vs ~10 s of transfer + in-HBM sorts. The digest
#: only runs when the cheap meta prefix already matches, and is computed
#: at most once per train (threaded from probe to store).
_BIG_LAYOUT_CACHE: list = []   # [(meta, digest, ALSData)]


def _layout_meta(td, use_mesh: bool):
    # "raw" fingerprints hash the raw chunk columns (streamed AND
    # in-core reads of a chunked store — mode-agnostic, so the two
    # share cache entries); "enc" hashes the encoded host arrays (reads
    # with no chunk stream). The kind bit keeps the two digest
    # keyspaces from ever comparing.
    kind = "raw" if getattr(td, "_stream_digest", None) else "enc"
    return (use_mesh, kind, td.n,
            len(td.user_vocab), len(td.item_vocab))


def _layout_crc(td) -> bytes:
    import hashlib
    digest = getattr(td, "_stream_digest", None)
    if digest:
        # incremental digest over the raw chunk columns, computed
        # during the scan in both retention modes (same collision
        # bound as the encoded hash; under the streamed read the host
        # COO never existed, so this is also the ONLY possible
        # fingerprint there)
        return digest
    h = hashlib.blake2b(digest_size=16)
    for a in (td.user_idx, td.item_idx, td.rating):
        h.update(np.ascontiguousarray(a).view(np.uint8))
    return h.digest()


def _big_layout_cached(td, use_mesh: bool):
    """-> (data_or_None, crc_or_None). crc is returned when computed so a
    following store never hashes the same arrays twice."""
    if not als._layout_cache_enabled() or not _BIG_LAYOUT_CACHE:
        return None, None
    meta, crc, data = _BIG_LAYOUT_CACHE[0]
    if meta != _layout_meta(td, use_mesh):
        return None, None
    got = _layout_crc(td)
    return (data, got) if got == crc else (None, got)


def _big_layout_store(td, use_mesh: bool, data, crc=None) -> None:
    if als._layout_cache_enabled():
        if crc is None:
            crc = _layout_crc(td)
        _BIG_LAYOUT_CACHE[:] = [(_layout_meta(td, use_mesh), crc, data)]


#: layout-reuse instrumentation: hits = a train (or prepare_layout) served
#: its device layout from either cache tier; builds = prepare_ratings ran.
#: Registry-backed (common/telemetry.py): the counters live in the
#: process metrics registry (`pio_layout_cache_total{result=...}` on
#: GET /metrics); this dict-like view keeps every existing call site
#: (`LAYOUT_STATS["hits"] += 1`, the tests' delta reads) byte-compatible.
from predictionio_tpu.common import telemetry as _telemetry

LAYOUT_STATS = _telemetry.RegistryDict(
    _telemetry.registry().counter(
        "pio_layout_cache_total",
        "Device COO layout requests by outcome (hit = served from a "
        "cache tier, build = prepare_ratings ran)",
        labelnames=("result",)),
    "result", ("hits", "builds"))


def staging_wanted() -> bool:
    """Should the bulk read stage its COO chunks to device while decoding?

    Yes unless a process-wide big-layout entry exists that an unchanged
    event store would hit — a warm retrain must skip the host→HBM transfer
    entirely, not overlap it. (PIO_READ_STAGE=0 kills staging outright in
    ops/staging.py; this gate only spares warm runs the wasted copy.)"""
    from predictionio_tpu.ops.staging import staging_available
    if not staging_available():
        return False
    return not (als._layout_cache_enabled() and _BIG_LAYOUT_CACHE)


def stream_wanted(ctx=None) -> bool:
    """Should the TRAINING read run the O(chunk)-host streamed pipeline
    (PIO_TRAIN_STREAM)? `auto` resolves to the streamed path wherever
    staging would engage; it declines a warm retrain (a populated
    big-layout cache means the in-core read's fingerprint will hit
    without paying any transfer), while an explicit `on` streams
    unconditionally — the digest-keyed cache still works there, it just
    costs the staged copy to find out."""
    from predictionio_tpu.data import store as _store
    mode = _store.train_stream_mode()
    if mode == "off":
        return False
    if not _store.resolve_train_stream():
        return False
    if mode == "on":
        return True
    return staging_wanted()


def _ensure_layout(ctx, td, use_mesh: bool):
    """The device-side COO layout for one TrainingData, through both cache
    tiers (train's "layout" phase body, shared with prepare_layout).

    The COO layout is rank-independent, so an eval grid's variants sharing
    one fold (FastEval memoizes the PreparedData object) reuse it instead
    of re-sorting the same ratings per variant. Eval-scale data caches on
    the TrainingData object; FULL-scale data (td.n > 2M) caches ONE entry
    process-wide keyed on a content fingerprint, so repeat trains over an
    unchanged event store (retrain-on-deploy)
    skip the transfer + in-HBM sorts entirely. The retained HBM (~0.5 GB
    at 20M) is bounded at one entry; PIO_ALS_LAYOUT_CACHE=0 disables
    retention."""
    import os
    cacheable = td.n <= int(os.environ.get(
        "PIO_ALS_BIG_LAYOUT_MIN", 2_000_000))
    cache_key = ("als_layout", use_mesh)
    cached = getattr(td, "_pio_layout_cache", None) \
        if cacheable else None
    big_crc = None
    if cached is not None and cached[0] == cache_key:
        data = cached[1]
    else:
        data, big_crc = _big_layout_cached(td, use_mesh)
    if data is not None:
        LAYOUT_STATS["hits"] += 1
        return data
    LAYOUT_STATS["builds"] += 1
    if not cacheable:
        # evict stale entries BEFORE building the replacement: holding the
        # old device layout + hybrid prep across the rebuild would
        # transiently double retained HBM
        _BIG_LAYOUT_CACHE.clear()
        als._HYBRID_CACHE.clear()
    if td.streamed:
        # out-of-core read: the device mirrors are the ONLY copy. The
        # layout consumes (and, off-CPU, DONATES) them — the staged
        # buffers are dead after this, so drop the reference either way
        u_in, i_in, r_in = td._staged_coo
        if use_mesh:
            from predictionio_tpu.parallel import als_dist
            data = als_dist.shard_staged_coo(
                ctx.mesh, u_in, i_in, r_in,
                n_users=len(td.user_vocab), n_items=len(td.item_vocab))
        else:
            data = als.prepare_ratings(
                u_in, i_in, r_in,
                n_users=len(td.user_vocab), n_items=len(td.item_vocab),
                device=True, donate=True)
        del u_in, i_in, r_in
        td._staged_coo = None
    else:
        # the overlapped read may have pre-staged the encoded COO in HBM
        # (ops/staging.py rides it on the TrainingData); the staged
        # arrays are value-identical to the host columns, so
        # prepare_ratings consumes them directly and skips its own host
        # shipping
        staged = getattr(td, "_staged_coo", None) if not use_mesh else None
        if staged is not None and int(staged[0].shape[0]) == td.n:
            u_in, i_in, r_in = staged
        else:
            u_in, i_in, r_in = td.user_idx, td.item_idx, td.rating
        data = als.prepare_ratings(
            u_in, i_in, r_in,
            n_users=len(td.user_vocab), n_items=len(td.item_vocab),
            # single-device: sort/pad in HBM; mesh path re-partitions on
            # host
            device=not use_mesh)
    by_user = getattr(data, "by_user", None)   # PreshardedData barriers
    if by_user is not None \
            and not isinstance(by_user.self_idx, np.ndarray):
        # block_until_ready returned before results landed on the early
        # rounds' backend (KNOWN_ISSUES #3); fetching one element forces
        # the in-HBM sort so the layout phase owns its wall-clock
        # instead of leaking into train
        import jax

        jax.device_get((data.by_user.self_idx[-1:],
                        data.by_item.self_idx[-1:]))
    if cacheable:
        td._pio_layout_cache = (cache_key, data)
    else:
        _big_layout_store(td, use_mesh, data, crc=big_crc)
    return data


class ALSAlgorithm(Algorithm):
    params_class = ALSAlgorithmParams
    query_class = Query

    def __init__(self, params: ALSAlgorithmParams):
        self.ap = params
        if isinstance(params.seed, dict):  # tolerate {"value": n} Option form
            raise ValueError("seed must be an integer or null")

    def train(self, ctx, prepared: PreparedData) -> ALSModel:
        td = prepared.ratings
        if td.n == 0:
            raise ValueError(
                "No ratings found. Please check if DataSource generates "
                "TrainingData and Preparator generates PreparedData correctly.")
        # MLlib uses System.nanoTime when no seed given (ALSAlgorithm.scala:56)
        seed = self.ap.seed if self.ap.seed is not None else (
            np.random.SeedSequence().entropy % (2 ** 31))
        use_mesh = ctx is not None and getattr(ctx, "mesh", None) is not None
        if ctx is not None and hasattr(ctx, "phase"):
            layout = ctx.phase("layout")
        else:
            import contextlib
            layout = contextlib.nullcontext()
        with layout:
            data = _ensure_layout(ctx, td, use_mesh)
        checkpointer = None
        ckpt_dir = getattr(ctx, "checkpoint_dir", None)
        if self.ap.checkpointInterval and ckpt_dir:
            from predictionio_tpu.workflow.checkpoint import (
                FactorCheckpointer,
            )
            checkpointer = FactorCheckpointer(ckpt_dir)
        if ctx is not None and getattr(ctx, "mesh", None) is not None:
            from predictionio_tpu.parallel import als_dist
            U, V = als_dist.train_explicit_sharded(
                ctx.mesh, data, rank=self.ap.rank,
                iterations=self.ap.numIterations,
                lambda_=self.ap.lambda_, seed=int(seed),
                checkpoint_every=self.ap.checkpointInterval,
                checkpointer=checkpointer)
        else:
            U, V = als.train_explicit(
                data, rank=self.ap.rank, iterations=self.ap.numIterations,
                lambda_=self.ap.lambda_, seed=int(seed),
                checkpoint_every=self.ap.checkpointInterval,
                checkpointer=checkpointer)
        import jax

        # train phase owns its wall-clock: a one-row fetch forces both
        # factor buffers even where block_until_ready is unreliable
        # (KNOWN_ISSUES #3)
        jax.device_get((U[-1:], V[-1:]))
        return ALSModel(
            rank=self.ap.rank, user_factors=U, item_factors=V,
            user_vocab=td.user_vocab, item_vocab=td.item_vocab)

    def prepare_layout(self, ctx, prepared: PreparedData) -> None:
        """Eval-grid hoist (workflow/fast_eval.py): build — or reuse — the
        device COO layout for this fold's ratings BEFORE any variant
        trains. The layout is rank-independent, so one prepare_layout per
        fold serves every rank/iteration variant of the grid; subsequent
        train() calls hit the TrainingData-object cache."""
        td = prepared.ratings
        if td.n == 0:
            return
        use_mesh = ctx is not None and getattr(ctx, "mesh", None) is not None
        if ctx is not None and hasattr(ctx, "phase"):
            with ctx.phase("layout"):
                _ensure_layout(ctx, td, use_mesh)
        else:
            _ensure_layout(ctx, td, use_mesh)

    def prepare_serving(self, model: ALSModel) -> ALSModel:
        """Pick the serving path by MEASURING the deployed device.

        Quantization first (ops/quant.py): when the deploy scope
        resolves serve-quant on (`pio deploy --serve-quant`,
        PIO_SERVE_QUANT), both factor matrices are quantized to int8
        with per-row fp32 scales and the deploy-time ranking-parity
        probe runs against the fp32 factors; "auto" refuses the
        quantized layout (and records why) when recall@k misses the
        floor. The quantized blocks then ride whichever layout wins
        below — sharded (int8 shards + sharded scale vectors) or
        replicated (QuantizedServing) — so sharding x quantization
        compose. On the CPU backend a failed quantization degrades to
        fp32 serving; on an accelerator a failed device layout raises.

        Sharded next (parallel/serve_dist.py): when the deploy scope
        resolves shard-serving on (`pio deploy --shard-serving`,
        PIO_SERVE_SHARD), the factor blocks are laid out row-sharded
        over the mesh and every query serves from the per-device local
        top-k + merge kernel — the per-device HBM footprint drops to
        total/n_dev, which is what lets a factor matrix larger than one
        chip serve at all. Results match the replicated path: the same
        ranking, scores within serve_dist.SCORE_RTOL/SCORE_ATOL.
        On the CPU backend a failed shard layout degrades to the
        replicated path below; on an accelerator it raises.

        Otherwise: device-resident replicated serving (one fused
        dispatch per query, topk.topk_for_user) on an attached
        accelerator, always. On the CPU backend, where a tiny model
        serves faster from host BLAS + argpartition than through a
        dispatch, probe a real query at deploy time and keep whichever
        layout serves faster (threshold PIO_SERVE_DEVICE_MS, default
        3 ms). No reference analogue — MLlib serving is always
        JVM-host-side."""
        import logging

        import jax

        from predictionio_tpu.ops import quant as quant_mod
        from predictionio_tpu.parallel import serve_dist

        log = logging.getLogger("predictionio_tpu.recommendation")
        # on an accelerator backend a layout that fails RAISES: a deploy
        # that quietly serves some other way would pass every check
        # without its chosen layout ever having reached the chip. The
        # degrade-and-log behaviour below is the CPU harness's only.
        on_chip = jax.default_backend() != "cpu"
        qf = None
        if quant_mod.serving_enabled():
            try:
                U = np.asarray(model.user_factors)
                V = np.asarray(model.item_factors)
                qf = quant_mod.QuantizedFactors.from_factors(U, V)
                parity = quant_mod.ranking_parity(U, V, qf)
                qf.recall = parity["recall"]
                qf.exact1 = parity["exact1"]
                if not quant_mod.accept_parity(parity):
                    log.warning(
                        "quantized serving refused by the ranking-parity "
                        "probe (recall@%d=%.4f < %.2f floor; "
                        "KNOWN_ISSUES #12); serving fp32",
                        parity["k"], parity["recall"],
                        quant_mod.recall_floor())
                    quant_mod.note_fallback(
                        "ranking-parity probe below the floor "
                        "(KNOWN_ISSUES #12)",
                        recall=round(parity["recall"], 4),
                        floor=quant_mod.recall_floor(), k=parity["k"])
                    qf = None
            except Exception as e:
                log.exception("factor quantization failed; serving fp32")
                quant_mod.note_fallback(
                    "factor quantization raised",
                    error=f"{type(e).__name__}: {e}")
                qf = None

        if serve_dist.serving_enabled():
            try:
                sharded = serve_dist.shard_factors(
                    np.asarray(model.user_factors),
                    np.asarray(model.item_factors), quant=qf)
                return ALSModel(
                    rank=model.rank,
                    user_factors=sharded.user_shards,
                    item_factors=sharded.item_shards,
                    user_vocab=model.user_vocab,
                    item_vocab=model.item_vocab,
                    sharding=sharded)
            except Exception:
                if on_chip:
                    raise
                log.exception(
                    "sharded serving layout failed; falling back to "
                    "replicated serving")

        if qf is not None:
            try:
                qs = quant_mod.QuantizedServing.build(qf)
                # factors stay HOST fp32: the int8 blocks are the only
                # device copy (the 4x footprint win), and the eval
                # paths keep their host BLAS
                return ALSModel(
                    rank=model.rank,
                    user_factors=np.asarray(model.user_factors),
                    item_factors=np.asarray(model.item_factors),
                    user_vocab=model.user_vocab,
                    item_vocab=model.item_vocab,
                    quant=qs)
            except Exception as e:
                if on_chip:
                    raise
                log.exception("quantized serving layout failed; "
                              "falling back to fp32 serving")
                quant_mod.note_fallback(
                    "int8 device layout failed",
                    error=f"{type(e).__name__}: {e}")

        U = jax.device_put(np.asarray(model.user_factors))
        V = jax.device_put(np.asarray(model.item_factors))
        if not on_chip:
            # the CPU backend only: "device" arrays buy nothing for a
            # tiny model there, so time a real query and move serving
            # to host numpy when a dispatch is slow. On an attached
            # accelerator the factors stay on the device, always.
            k = min(10, len(model.item_vocab))
            if host_serves_faster(
                    lambda: topk.topk_for_user(U, V, np.int32(0), k=k), log):
                return ALSModel(
                    rank=model.rank,
                    user_factors=np.asarray(model.user_factors),
                    item_factors=np.asarray(model.item_factors),
                    user_vocab=model.user_vocab,
                    item_vocab=model.item_vocab)
        return ALSModel(
            rank=model.rank, user_factors=U, item_factors=V,
            user_vocab=model.user_vocab, item_vocab=model.item_vocab)

    def aot_serving_programs(self, model: ALSModel, buckets,
                             declared: bool = False):
        """Enumerate this model's device serving programs from declared
        shapes (serving/aot.py): topk_for_users per (bucket, k) — the
        micro-batcher's flush kernel — plus topk_for_user per k for the
        batching-off inline path. When prepare_serving chose the host
        path (numpy factors) there are no device programs to build and
        deploy stays instant; ``declared=True`` (the `pio train` cache-
        artifact export) enumerates regardless, since the eventual
        deploy may well pick the device path on its own hardware.

        A SHARDED model (prepare_serving chose the row-sharded layout)
        enumerates the (bucket x k) sharded programs instead — bucket 1
        always included for the inline path — so `post_warmup_recompiles
        == 0` holds with sharding on. Sharded programs are mesh-
        topology-specific, so the declared train-time export does not
        enumerate them; the deploy-side prebuild owns them (the
        persistent compile cache still amortizes them per machine).

        A QUANTIZED replicated model enumerates the (bucket x k)
        quantized programs plus the per-k inline quant programs, so
        `post_warmup_recompiles == 0` holds with quant on. Quant
        programs depend on the deploy environment's mode resolution,
        so — like sharded — the declared train-time export skips
        them."""
        from predictionio_tpu.serving import aot

        sharding = getattr(model, "sharding", None)
        if sharding is not None and not declared:
            from predictionio_tpu.parallel import serve_dist

            return serve_dist.sharded_program_specs(
                sharding, buckets, aot.serving_ks(sharding.n_items))
        quant = getattr(model, "quant", None)
        if quant is not None and not declared:
            from predictionio_tpu.ops import quant as quant_mod

            return quant_mod.quant_program_specs(
                quant, buckets, aot.serving_ks(quant.n_items))
        if not declared and isinstance(model.user_factors, np.ndarray):
            return ()

        n_users, rank = (int(d) for d in np.shape(model.user_factors))
        n_items = int(np.shape(model.item_factors)[0])
        ks = aot.serving_ks(n_items)
        arrays = (None if declared
                  else (model.user_factors, model.item_factors))
        return (aot.specs_topk_for_users(n_users, n_items, rank,
                                         buckets, ks, arrays=arrays)
                + aot.specs_topk_for_user(n_users, n_items, rank, ks,
                                          arrays=arrays))

    def predict(self, model: ALSModel, query: Query) -> PredictedResult:
        user_ix = model.user_vocab.get(query.user)
        if user_ix is None:
            # unknown user -> empty result (ALSAlgorithm.scala:104-108)
            return PredictedResult(())
        k = min(query.num, len(model.item_vocab))
        if k <= 0:
            # num <= 0 straight from request JSON: empty, not a device
            # error (lax.top_k rejects negative k)
            return PredictedResult(())
        sharding = getattr(model, "sharding", None)
        quant = getattr(model, "quant", None)
        if sharding is not None:
            import jax

            # inline sharded serve rides the same (bucket=1, k) program
            # the batched path uses — sharded_program_specs always
            # prebuilds bucket 1 for exactly this path
            vals, idx = jax.device_get(sharding.topk(
                np.asarray([user_ix], dtype=np.int32), k))
            vals, idx = vals[0], idx[0]
        elif quant is not None:
            import jax

            # inline quantized serve: the per-k program
            # quant_program_specs prebuilds for exactly this path;
            # bit-identical to a row of the batched quant kernels
            vals, idx = jax.device_get(quant.topk_one(
                np.int32(user_ix), k))
        elif isinstance(model.user_factors, np.ndarray):
            # host serving: one BLAS matvec + argpartition
            scores = model.item_factors @ model.user_factors[user_ix]
            vals, idx = topk.host_topk(scores, k)
        else:
            import jax

            vals, idx = jax.device_get(topk.topk_for_user(
                model.user_factors, model.item_factors,
                np.int32(user_ix), k=k))
        # fold-in headroom guard: with item fold-in on, the item matrix
        # carries zero pad rows past the vocab (realtime/foldin.py
        # pad_capacity) that are unmasked in the replicated layouts and
        # can surface when k reaches the catalog size — drop any index
        # past the registered vocab (a no-op when fold-in is off: the
        # matrix row count equals the vocab size)
        n_real = len(model.item_vocab)
        inv = model.item_vocab.inverse()
        return PredictedResult(tuple(
            ItemScore(item=inv(int(i)), score=float(s))
            for s, i in zip(vals, idx) if int(i) < n_real))

    def predict_batch(self, model: ALSModel,
                      queries) -> List[PredictedResult]:
        """Serving micro-batch (serving/batcher.py): stack the user-factor
        gathers into a (B, rank) matrix, ONE (B, rank) @ (rank, n_items)
        matmul + batched top-k for the whole batch instead of B dispatches.
        The device path pads B up to a serving bucket so the jitted kernel
        compiles once per bucket, never per batch size; padding rows reuse
        index 0 (in-bounds — an OOB pad would gather NaN, KNOWN_ISSUES.md
        #5) and are dropped before results are built."""
        queries = list(queries)
        out: List[Optional[PredictedResult]] = [None] * len(queries)
        valid: List[Tuple[int, Query, int]] = []
        for qx, q in enumerate(queries):
            ix = model.user_vocab.get(q.user)
            if ix is None or min(q.num, len(model.item_vocab)) <= 0:
                out[qx] = PredictedResult(())   # same empties as predict()
            else:
                valid.append((qx, q, ix))
        if not valid:
            return out
        k = min(max(q.num for _qx, q, _ix in valid), len(model.item_vocab))
        ixs = np.asarray([ix for _qx, _q, ix in valid], dtype=np.int32)
        from predictionio_tpu.common import waterfall
        sharding = getattr(model, "sharding", None)
        quant = getattr(model, "quant", None)
        fetched = None   # a device layout's (values, indices) arrays
        if sharding is not None:
            # sharded device path (parallel/serve_dist.py): ONE fused
            # shard_map dispatch — per-device local top-k over each item
            # shard + the all-gather merge. The shards note turns
            # "execute is slow" into "it's the n-way sharded program",
            # one hop from /debug/slow.json.
            fetched = device_rows(sharding.topk, ixs, k)
            waterfall.note("shards", sharding.n_shards)
        elif quant is not None:
            # quantized device path (ops/quant.py): ONE dequantize-free
            # dispatch — int8 x int8 scores + fused rescale + top-k. The
            # quant note turns "execute is slow" into "it's the int8
            # path", one hop from /debug/slow.json.
            fetched = device_rows(quant.topk, ixs, k)
            waterfall.note("quant", "int8")
        elif isinstance(model.user_factors, np.ndarray):
            # host: one BLAS gemm for the batch, per-row argpartition with
            # each query's own k (identical selection to predict())
            with waterfall.stage("execute"):
                scores = model.user_factors[ixs] @ model.item_factors.T
                rows = [tuple(a.tolist() for a in
                              topk.host_topk(scores[r], min(q.num, k)))
                        for r, (_qx, q, _ix) in enumerate(valid)]
        else:
            fetched = device_rows(
                lambda pix, k: topk.topk_for_users(
                    model.user_factors, model.item_factors, pix, k=k),
                ixs, k)
        # same fold-in headroom guard as predict(): pad rows past the
        # item vocab never surface in a result
        with waterfall.stage("unpack"):
            if fetched is not None:
                # the device's (rows, k) arrays: two conversions a
                # flush, not two a score (float32 -> float is exact
                # either way); each query's own num is cut below
                vals, idx = fetched
                rows = zip(vals.tolist(), idx.tolist())
            n_real = len(model.item_vocab)
            inv = model.item_vocab.inverse()
            for (qx, q, _ix), (rvals, ridx) in zip(valid, rows):
                n = min(q.num, k)
                out[qx] = PredictedResult(tuple(
                    ItemScore(item=inv(i), score=s)
                    for s, i in zip(rvals[:n], ridx[:n]) if i < n_real))
        return out

    def batch_predict(self, model: ALSModel,
                      queries: Iterable[Tuple[int, Query]]
                      ) -> List[Tuple[int, PredictedResult]]:
        """Eval path: one (b, r) x (r, n_items) matmul + batched top_k for
        all known users (ALSAlgorithm.scala:113-148 did a cartesian join)."""
        queries = list(queries)
        known = [(qx, q, model.user_vocab.get(q.user)) for qx, q in queries]
        out: List[Tuple[int, PredictedResult]] = [
            (qx, PredictedResult(())) for qx, _q, ix in known if ix is None]
        valid = [(qx, q, ix) for qx, q, ix in known if ix is not None]
        if not valid:
            return out
        max_num = max(q.num for _qx, q, _ix in valid)
        k = min(max_num, len(model.item_vocab))
        if k <= 0:      # every query asked for num <= 0
            out.extend((qx, PredictedResult(())) for qx, _q, _ix in valid)
            return out
        U = np.asarray(model.user_factors)
        ixs = np.asarray([ix for _qx, _q, ix in valid], dtype=np.int32)
        vals, idx = topk.topk_scores_batch(U[ixs], model.item_factors, k=k)
        vals, idx = np.asarray(vals), np.asarray(idx)
        n_real = len(model.item_vocab)   # fold-in headroom guard
        inv = model.item_vocab.inverse()
        for row, (qx, q, _ix) in enumerate(valid):
            n = max(min(q.num, k), 0)   # a negative num is empty, not top-n
            out.append((qx, PredictedResult(tuple(
                ItemScore(item=inv(int(i)), score=float(s))
                for s, i in zip(vals[row, :n], idx[row, :n])
                if int(i) < n_real))))
        return out
