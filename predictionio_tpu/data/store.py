"""Event store access for engines: LEventStore/PEventStore equivalents.

Reference: data/src/main/scala/org/apache/predictionio/data/store/
(PEventStore.scala:35-120, LEventStore.scala:33-145, Common.scala).

The reference's `PEventStore.find` returns an `RDD[Event]` materialized on
Spark executors. The TPU-native analogue is twofold:

- :func:`find` — an iterator of Events (host side), the direct parity API;
- :func:`find_columnar` — bulk read into **columnar numpy buffers**
  (entity ids, target ids, event names, times, plus one chosen numeric
  property), the ingestion path that feeds `jax.device_put` straight to HBM
  (BASELINE.json north star: "PEventStore streams training events ... straight
  into HBM"). String IDs are vocab-encoded with BiMap in the same pass.
"""

from __future__ import annotations

import datetime as _dt
import time as _time
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.data.datamap import PropertyMap
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage import Storage, get_storage


class StoreError(RuntimeError):
    pass


def _resolve_app(app_name: str, channel_name: Optional[str],
                 storage: Optional[Storage]) -> Tuple[int, Optional[int]]:
    """appName (+channel) → (appId, channelId), mirroring Common.scala."""
    storage = storage or get_storage()
    app = storage.get_meta_data_apps().get_by_name(app_name)
    if app is None:
        raise StoreError(
            f"Invalid app name {app_name}. Please use valid appName in your "
            "engine configuration.")
    channel_id: Optional[int] = None
    if channel_name is not None:
        channels = storage.get_meta_data_channels().get_by_appid(app.id)
        match = next((c for c in channels if c.name == channel_name), None)
        if match is None:
            raise StoreError(
                f"Invalid channel name {channel_name} for app {app_name}.")
        channel_id = match.id
    return app.id, channel_id


def find(
    app_name: str,
    channel_name: Optional[str] = None,
    start_time: Optional[_dt.datetime] = None,
    until_time: Optional[_dt.datetime] = None,
    entity_type: Optional[str] = None,
    entity_id: Optional[str] = None,
    event_names: Optional[Sequence[str]] = None,
    target_entity_type: Optional[str] = None,
    target_entity_id: Optional[str] = None,
    limit: Optional[int] = None,
    storage: Optional[Storage] = None,
) -> Iterator[Event]:
    """Read events by app name (PEventStore.find, PEventStore.scala:59-97)."""
    storage = storage or get_storage()
    app_id, channel_id = _resolve_app(app_name, channel_name, storage)
    return storage.get_events().find(
        app_id=app_id, channel_id=channel_id,
        start_time=start_time, until_time=until_time,
        entity_type=entity_type, entity_id=entity_id,
        event_names=event_names,
        target_entity_type=target_entity_type,
        target_entity_id=target_entity_id,
        limit=limit,
    )


def find_target_ids(
    app_name: str,
    entity_type: str,
    entity_id: str,
    channel_name: Optional[str] = None,
    event_names: Optional[Sequence[str]] = None,
    target_entity_type: Optional[str] = None,
    storage: Optional[Storage] = None,
) -> List[str]:
    """Target entity ids of matching events — the serving-time seen/similar
    lookup (ECommAlgorithm.scala:148-176 uses only targetEntityId). Takes
    the backend's columnar fast path when it has one (eventlog:
    postings + target-code gather, no Event objects); falls back to
    find_by_entity otherwise."""
    storage = storage or get_storage()
    events_dao = storage.get_events()
    if hasattr(events_dao, "find_target_ids"):
        app_id, channel_id = _resolve_app(app_name, channel_name, storage)
        return events_dao.find_target_ids(
            app_id, channel_id, entity_type=entity_type,
            entity_id=entity_id, event_names=event_names,
            target_entity_type=target_entity_type)
    return [e.target_entity_id for e in find_by_entity(
        app_name, entity_type, entity_id, channel_name=channel_name,
        event_names=event_names, target_entity_type=target_entity_type,
        storage=storage) if e.target_entity_id is not None]


def find_by_entity(
    app_name: str,
    entity_type: str,
    entity_id: str,
    channel_name: Optional[str] = None,
    event_names: Optional[Sequence[str]] = None,
    target_entity_type: Optional[str] = None,
    target_entity_id: Optional[str] = None,
    start_time: Optional[_dt.datetime] = None,
    until_time: Optional[_dt.datetime] = None,
    limit: Optional[int] = None,
    latest: bool = True,
    storage: Optional[Storage] = None,
) -> List[Event]:
    """LEventStore.findByEntity (LEventStore.scala:61-115): the serving-time
    lookup used by e-commerce templates for live seen-event filters."""
    storage = storage or get_storage()
    app_id, channel_id = _resolve_app(app_name, channel_name, storage)
    return list(storage.get_events().find(
        app_id=app_id, channel_id=channel_id,
        start_time=start_time, until_time=until_time,
        entity_type=entity_type, entity_id=entity_id,
        event_names=event_names,
        target_entity_type=target_entity_type,
        target_entity_id=target_entity_id,
        limit=limit, reversed_=latest,
    ))


def aggregate_properties(
    app_name: str,
    entity_type: str,
    channel_name: Optional[str] = None,
    start_time: Optional[_dt.datetime] = None,
    until_time: Optional[_dt.datetime] = None,
    required: Optional[Sequence[str]] = None,
    storage: Optional[Storage] = None,
) -> Dict[str, PropertyMap]:
    """PEventStore.aggregateProperties (PEventStore.scala:99-120)."""
    storage = storage or get_storage()
    app_id, channel_id = _resolve_app(app_name, channel_name, storage)
    return storage.get_events().aggregate_properties(
        app_id=app_id, channel_id=channel_id, entity_type=entity_type,
        start_time=start_time, until_time=until_time, required=required,
    )


def extract_entity_map(
    app_name: str,
    entity_type: str,
    extract,
    channel_name: Optional[str] = None,
    start_time: Optional[_dt.datetime] = None,
    until_time: Optional[_dt.datetime] = None,
    required: Optional[Sequence[str]] = None,
    storage: Optional[Storage] = None,
) -> "EntityMap":
    """Aggregate an entityType's properties and extract typed objects
    (PEvents.extractEntityMap, PEvents.scala:134-165).

    `extract(property_map) -> A` runs per entity; extraction errors name the
    failing entity. The EntityMap's dense id→ix assignment is the row order
    for positional feature arrays on device.
    """
    from predictionio_tpu.data.bimap import EntityMap

    props = aggregate_properties(
        app_name, entity_type, channel_name=channel_name,
        start_time=start_time, until_time=until_time, required=required,
        storage=storage)
    id_to_data = {}
    for eid, dm in props.items():
        try:
            id_to_data[eid] = extract(dm)
        except Exception as e:
            raise StoreError(
                f"Failed to extract entity from DataMap of entityId "
                f"{eid!r}: {e}") from e
    return EntityMap(id_to_data)


# ---------------------------------------------------------------------------
# Columnar TPU ingestion
# ---------------------------------------------------------------------------

@dataclass
class ColumnarEvents:
    """Events in structure-of-arrays layout, vocab-encoded, device-ready.

    entity_idx / target_idx are dense int32 via the included BiMaps;
    `rating` is the chosen numeric property (NaN when absent);
    `event_name_idx` indexes into `event_names`.

    Under the STREAMED training read (``columnar_from_stream(stream=
    True)`` — the out-of-core `pio train` path) the host arrays are
    ``None``: the encoded columns exist only as the device-resident
    ``staged`` mirrors (ops/staging.StagedColumns), host peak memory
    stays O(chunk), and ``stream_digest`` carries the incremental
    content fingerprint the layout cache keys on instead of hashing
    host arrays that no longer exist.
    """
    entity_ids: BiMap            # str -> int32 (e.g. users)
    target_ids: BiMap            # str -> int32 (e.g. items)
    event_names: List[str]
    entity_idx: Optional[np.ndarray]       # (n,) int32; None when streamed
    target_idx: Optional[np.ndarray]       # (n,) int32, -1 = no target
    event_name_idx: Optional[np.ndarray]   # (n,) int32
    rating: Optional[np.ndarray]     # (n,) float32, NaN where absent
    event_time_ms: Optional[np.ndarray]    # (n,) int64 epoch millis
    #: optional device-resident mirrors of the encoded arrays
    #: (ops/staging.StagedColumns), populated by the overlapped read path
    #: when the caller asked for staging — value-identical to the host
    #: arrays above, already in HBM so the ALS layout skips its transfer
    staged: Optional[object] = None
    #: blake2b digest of the raw chunk columns (streamed reads only) —
    #: the content fingerprint of a dataset whose host copy was never
    #: materialized
    stream_digest: Optional[bytes] = None

    @property
    def n(self) -> int:
        if self.entity_idx is not None:
            return int(self.entity_idx.shape[0])
        return int(self.staged.n) if self.staged is not None else 0


def _columnar_from_codes(cols: Dict[str, object],
                         event_names: Optional[Sequence[str]],
                         entity_vocab: Optional[BiMap],
                         target_vocab: Optional[BiMap],
                         presence: Optional[Dict[str, np.ndarray]] = None,
                         luts_out: Optional[Dict[str, object]] = None,
                         ) -> ColumnarEvents:
    """Vectorized dict-code → dense-vocab encode (zero per-event Python).

    Vocab ids are assigned in dictionary-code order (≈ first-ingested order)
    rather than the object path's first-matching-event order; downstream
    kernels treat ids as opaque, so only the BiMap contents matter.

    `presence`, when given, carries pool-presence masks precomputed
    incrementally by the streamed read path ("entity"/"target" bool arrays
    over the pool) so that work overlapped chunk decode instead of running
    here. `luts_out`, when given, receives the dense LUTs + whether every
    row was kept — the device-staging finalize needs them to replay the
    identical remap in HBM.
    """
    pool: List[str] = cols["pool"]  # type: ignore[assignment]
    ecode = np.asarray(cols["entity_code"])
    tcode = np.asarray(cols["target_code"])
    ncode = np.asarray(cols["event_code"])
    rating = np.asarray(cols["rating"])
    tms = np.asarray(cols["time_ms"])

    def dense(codes, vocab, present):
        valid = codes >= 0  # -1 = event has no such entity (targets)
        if vocab is None:
            if present is None:
                # presence via bincount + LUT gather: O(n + pool), no sort
                present = np.bincount(
                    codes[valid], minlength=len(pool)).astype(bool)
            used = np.nonzero(present)[0]
            lut = np.full(len(pool), -1, np.int32)
            lut[used] = np.arange(used.size, dtype=np.int32)
            out_vocab = BiMap({pool[int(c)]: int(lut[c])
                               for c in used.tolist()})
            idx = np.where(valid, lut[np.maximum(codes, 0)],
                           -1).astype(np.int32)
            return idx, out_vocab, np.ones(codes.shape[0], dtype=bool), lut
        lut = np.full(len(pool), -1, np.int32)
        str2code = {s: c for c, s in enumerate(pool)}
        for s, i in vocab.to_dict().items():
            c = str2code.get(s)
            if c is not None:
                lut[c] = i
        idx = np.where(valid, lut[np.maximum(codes, 0)], -1).astype(np.int32)
        # fixed vocab: drop events referencing unseen (non-null) entities
        keep = ~(valid & (idx < 0))
        return idx, vocab, keep, lut

    presence = presence or {}
    e_idx, e_vocab, e_keep, e_lut = dense(
        ecode, entity_vocab, presence.get("entity"))
    t_idx, t_vocab, t_keep, t_lut = dense(
        tcode, target_vocab, presence.get("target"))
    keep = e_keep & t_keep
    kept_all = bool(keep.all())
    if not kept_all:
        e_idx, t_idx, ncode = e_idx[keep], t_idx[keep], ncode[keep]
        rating, tms = rating[keep], tms[keep]

    if event_names:
        name_order = list(event_names)
    else:
        name_order = [pool[int(c)] for c in np.unique(ncode).tolist()]
    name_lut = np.full(len(pool) + 1, -1, np.int32)
    for i, n in enumerate(name_order):
        try:
            name_lut[pool.index(n)] = i
        except ValueError:
            pass
    if luts_out is not None:
        luts_out.update(e_lut=e_lut, t_lut=t_lut, name_lut=name_lut,
                        kept_all=kept_all)
    return ColumnarEvents(
        entity_ids=e_vocab, target_ids=t_vocab, event_names=name_order,
        entity_idx=e_idx, target_idx=t_idx,
        event_name_idx=name_lut[ncode].astype(np.int32),
        rating=rating.astype(np.float32), event_time_ms=tms.astype(np.int64),
    )


def _overlap_enabled() -> bool:
    """PIO_READ_OVERLAP=0 turns the streamed decode∥encode pipeline off
    (the read then runs read→encode strictly in sequence, as before)."""
    import os
    return os.environ.get("PIO_READ_OVERLAP", "1") != "0"


def train_stream_mode() -> str:
    """``PIO_TRAIN_STREAM`` — the out-of-core training knob:

    - ``auto`` (default): stream when the event source exposes a chunk
      stream AND device staging is available (jax importable,
      ``PIO_READ_STAGE`` not 0); the warm-layout-cache veto lives in the
      template layer (als_algorithm.stream_wanted);
    - ``on``: force the streamed path (still requires staging — without
      a device there is nowhere for the columns to live);
    - ``off``: the exact in-core path, bit-compatible with pre-stream
      releases (host arrays retained, same read/encode/layout code).
    """
    import os
    mode = os.environ.get("PIO_TRAIN_STREAM", "auto").lower()
    return mode if mode in ("auto", "on", "off") else "auto"


def resolve_train_stream(chunk_src=None) -> bool:
    """Resolve :func:`train_stream_mode` against a chunk source (an
    events DAO with ``read_columns_streamed``, a synthetic ChunkSource,
    or None = capability-only). Returns whether the TRAINING read runs
    the O(chunk)-host streamed pipeline."""
    mode = train_stream_mode()
    if mode == "off":
        return False
    from predictionio_tpu.ops.staging import staging_available
    if not staging_available():
        if mode == "on":
            import logging
            logging.getLogger(__name__).warning(
                "PIO_TRAIN_STREAM=on but device staging is unavailable "
                "(PIO_READ_STAGE=0 or no jax); training in-core")
        return False
    if chunk_src is not None and not (
            hasattr(chunk_src, "read_columns_streamed")
            or hasattr(chunk_src, "chunks")):
        return False
    return True


def columnar_from_stream(
    pool: List[str],
    chunks,
    event_names: Optional[Sequence[str]] = None,
    entity_vocab: Optional[BiMap] = None,
    target_vocab: Optional[BiMap] = None,
    stage: bool = True,
    stream: bool = False,
    timings: Optional[Dict[str, float]] = None,
) -> ColumnarEvents:
    """Consume a columnar chunk stream into vocab-encoded columns.

    The shared body of the overlapped bulk read: per-chunk vocab
    presence (and, when staging is on, the async host→HBM copy) folds
    into the chunk-decode wall-clock. Two retention modes:

    - ``stream=False`` (default): host chunks are retained and
      concatenated — byte-identical to the non-streamed read; the
      in-core path;
    - ``stream=True``: host chunks are RELEASED as soon as their raw
      codes are staged to the device, so peak host memory is O(chunk) +
      O(vocab) instead of O(dataset). The encoded columns exist only as
      ``ColumnarEvents.staged`` device mirrors (value-identical to what
      the in-core path would have built — the device remap runs the
      same integer ops on the same inputs), and ``stream_digest``
      carries an incremental blake2b over the raw chunk columns so the
      layout cache can still recognize an unchanged dataset. Requires
      grow-both vocabs and available staging; falls back to in-core
      retention otherwise (a fixed vocab can drop rows, which needs the
      host columns).

    Timing split: read_io = time spent waiting on chunk decode;
    read_encode = per-chunk accumulation + the final dense remap.
    """
    import hashlib

    stager = None
    grow_both = entity_vocab is None and target_vocab is None
    if (stage or stream) and grow_both:
        from predictionio_tpu.ops import staging as _staging
        if _staging.staging_available():
            stager = _staging.ColumnStager()
    stream = stream and stager is not None
    # the raw-chunk digest is computed in BOTH retention modes (cheap
    # next to decode): it is the MODE-AGNOSTIC content fingerprint, so
    # a layout cached by a streamed train is hit by a later in-core
    # retrain of the unchanged store and vice versa
    digest = hashlib.blake2b(digest_size=16) if grow_both else None
    parts = []
    n_rows = 0
    name_codes: set = set()
    e_present = (np.zeros(len(pool), dtype=bool)
                 if entity_vocab is None else None)
    t_present = (np.zeros(len(pool), dtype=bool)
                 if target_vocab is None else None)
    io_s = 0.0
    t_mark = _time.perf_counter()
    for ch in chunks:
        now = _time.perf_counter()
        io_s += now - t_mark
        n_rows += int(ch["entity_code"].shape[0])
        # vocab-presence accumulates per chunk WHILE later chunks decode
        if e_present is not None:
            ec = ch["entity_code"]
            e_present[ec[ec >= 0]] = True
        if t_present is not None:
            tc = ch["target_code"]
            t_present[tc[tc >= 0]] = True
        if stager is not None:
            stager.add(ch)      # async host→HBM copy rides the decode
        if digest is not None:
            for key in ("entity_code", "target_code", "event_code",
                        "rating", "time_ms"):
                digest.update(np.ascontiguousarray(ch[key]).view(np.uint8))
        if stream:
            # the host chunk dies here: digest + event-name census are
            # the only host state that outlives it
            if event_names is None:
                name_codes.update(np.unique(ch["event_code"]).tolist())
        else:
            parts.append(ch)
        t_mark = _time.perf_counter()
    t1 = _time.perf_counter()

    presence = {}
    if e_present is not None:
        presence["entity"] = e_present
    if t_present is not None:
        presence["target"] = t_present

    if stream:
        luts: Dict[str, object] = {}
        out = _stream_vocabs(pool, presence, sorted(name_codes),
                             event_names, luts_out=luts)
        out.stream_digest = digest.digest()
        out.staged = stager.finalize(luts["e_lut"], luts["t_lut"],
                                     luts["name_lut"])
        if timings is not None:
            timings["read_io"] = io_s
            timings["read_encode"] = _time.perf_counter() - t1
        return out

    def cat(key, dtype):
        xs = [p[key] for p in parts]
        return np.concatenate(xs) if xs else np.empty(0, dtype=dtype)

    cols = {
        "pool": pool,
        "entity_code": cat("entity_code", np.int32),
        "target_code": cat("target_code", np.int32),
        "event_code": cat("event_code", np.int32),
        "rating": cat("rating", np.float32),
        "time_ms": cat("time_ms", np.int64),
    }
    luts = {}
    out = _columnar_from_codes(cols, event_names, entity_vocab, target_vocab,
                               presence=presence, luts_out=luts)
    if digest is not None:
        out.stream_digest = digest.digest()
    if stager is not None and luts.get("kept_all"):
        out.staged = stager.finalize(luts["e_lut"], luts["t_lut"],
                                     luts["name_lut"])
    if timings is not None:
        timings["read_io"] = io_s
        timings["read_encode"] = _time.perf_counter() - t1
    return out


def _stream_vocabs(pool: List[str], presence: Dict[str, np.ndarray],
                   name_codes: Sequence[int],
                   event_names: Optional[Sequence[str]],
                   luts_out: Dict[str, object]) -> ColumnarEvents:
    """Vocabs + dense LUTs from presence bitmaps alone (the streamed
    read's encode: no row arrays exist on host). The vocab-id
    assignment — dictionary-code order over present codes — is exactly
    ``_columnar_from_codes.dense``'s grow branch, so streamed and
    in-core reads of the same store build identical BiMaps and the
    device remap (ops/staging.finalize) reproduces the host encode
    value for value."""
    def dense(present):
        used = np.nonzero(present)[0]
        lut = np.full(len(pool), -1, np.int32)
        lut[used] = np.arange(used.size, dtype=np.int32)
        vocab = BiMap({pool[int(c)]: int(lut[c]) for c in used.tolist()})
        return vocab, lut

    e_vocab, e_lut = dense(presence["entity"])
    t_vocab, t_lut = dense(presence["target"])
    if event_names:
        name_order = list(event_names)
    else:
        name_order = [pool[int(c)] for c in name_codes]
    name_lut = np.full(len(pool) + 1, -1, np.int32)
    for i, n in enumerate(name_order):
        try:
            name_lut[pool.index(n)] = i
        except ValueError:
            pass
    luts_out.update(e_lut=e_lut, t_lut=t_lut, name_lut=name_lut,
                    kept_all=True)
    return ColumnarEvents(
        entity_ids=e_vocab, target_ids=t_vocab, event_names=name_order,
        entity_idx=None, target_idx=None, event_name_idx=None,
        rating=None, event_time_ms=None)


def _find_columnar_streamed(events_dao, app_id, channel_id, event_names,
                            entity_type, target_entity_type, rating_property,
                            entity_vocab, target_vocab, stage, timings,
                            stream=False):
    """Overlapped bulk read: consume per-chunk column arrays as decode
    workers finish (see :func:`columnar_from_stream` for the retention
    modes; ``stream=False`` output is byte-identical to the
    non-streamed path)."""
    pool, chunks = events_dao.read_columns_streamed(
        app_id, channel_id, event_names=event_names,
        entity_type=entity_type, target_entity_type=target_entity_type,
        rating_property=rating_property)
    return columnar_from_stream(
        pool, chunks, event_names=event_names, entity_vocab=entity_vocab,
        target_vocab=target_vocab, stage=stage, stream=stream,
        timings=timings)


def find_columnar(
    app_name: str,
    channel_name: Optional[str] = None,
    event_names: Optional[Sequence[str]] = None,
    entity_type: Optional[str] = None,
    target_entity_type: Optional[str] = None,
    rating_property: str = "rating",
    entity_vocab: Optional[BiMap] = None,
    target_vocab: Optional[BiMap] = None,
    storage: Optional[Storage] = None,
    timings: Optional[Dict[str, float]] = None,
    stage: bool = False,
    stream: bool = False,
) -> ColumnarEvents:
    """Single-pass events → columnar buffers + vocabs.

    `timings`, when given, receives {"read_io": s, "read_encode": s} on the
    columnar fast path (store scan vs vocab-encode split; under the
    overlapped pipeline, read_io is the time actually spent *waiting* on chunk decode).

    `stage=True` additionally asks for device-resident mirrors of the
    encoded arrays (`ColumnarEvents.staged`, ops/staging.py): each chunk is
    `device_put` while later chunks are still decoding, so the host→HBM
    COO transfer overlaps the read instead of following it. Only engaged
    when both vocabs grow (no rows dropped) and `PIO_READ_STAGE` != 0.

    `stream=True` (the out-of-core `pio train` path, PIO_TRAIN_STREAM)
    goes further: host chunks are released the moment their raw codes
    are staged, so peak host memory is O(chunk) + O(vocab) and the
    returned ColumnarEvents carries ONLY the device mirrors (host array
    fields are None; `stream_digest` fingerprints the dataset). Same
    engagement preconditions as staging; falls back to the retained
    in-core read when they don't hold.

    This replaces the reference's full Spark job for `BiMap.stringInt`
    (BiMap.scala:96-128) plus the per-template `.map`/`.filter` RDD chains:
    one host pass builds vocabularies and encoded COO arrays together.
    Pass pre-built vocabs to encode eval data consistently with training.

    When the event store is the columnar event log
    (data/storage/eventlog.py) the whole read runs vectorized over
    dictionary codes — no Event objects, no JSON — with chunks decoding on
    a thread pool (PIO_READ_THREADS); otherwise it falls back to the
    generic per-event path. The remote driver's read is one binary RPC
    (no local streaming), but the storage *server* decodes its chunks in
    parallel the same way.
    """
    storage = storage or get_storage()
    events_dao = storage.get_events()
    if hasattr(events_dao, "read_columns_streamed") and _overlap_enabled():
        app_id, channel_id = _resolve_app(app_name, channel_name, storage)
        return _find_columnar_streamed(
            events_dao, app_id, channel_id, event_names, entity_type,
            target_entity_type, rating_property, entity_vocab, target_vocab,
            stage, timings, stream=stream)
    if hasattr(events_dao, "read_columns"):
        app_id, channel_id = _resolve_app(app_name, channel_name, storage)
        t0 = _time.perf_counter()
        try:
            cols = events_dao.read_columns(
                app_id, channel_id, event_names=event_names,
                entity_type=entity_type,
                target_entity_type=target_entity_type,
                rating_property=rating_property)
        except NotImplementedError:
            # a remote driver whose BACKING store has no columnar support
            # reports it this way; fall through to the per-event path
            cols = None
        if cols is not None:
            t1 = _time.perf_counter()
            out = _columnar_from_codes(cols, event_names, entity_vocab,
                                       target_vocab)
            if timings is not None:
                timings["read_io"] = t1 - t0
                timings["read_encode"] = _time.perf_counter() - t1
            return out
    events = find(
        app_name, channel_name=channel_name, event_names=event_names,
        entity_type=entity_type, target_entity_type=target_entity_type,
        storage=storage,
    )
    ename_index: Dict[str, int] = (
        {n: i for i, n in enumerate(event_names)} if event_names else {})
    e_fwd: Dict[str, int] = dict(entity_vocab.to_dict()) if entity_vocab else {}
    t_fwd: Dict[str, int] = dict(target_vocab.to_dict()) if target_vocab else {}
    grow_e, grow_t = entity_vocab is None, target_vocab is None

    ent, tgt, enm, rat, tms = [], [], [], [], []
    for e in events:
        # Decide acceptance fully before touching either vocab, so dropped
        # events never leave orphan vocab entries.
        eid, tid = e.entity_id, e.target_entity_id
        if eid not in e_fwd and not grow_e:
            continue  # unseen entity under a fixed vocab: drop
        if tid is not None and tid not in t_fwd and not grow_t:
            continue
        if eid not in e_fwd:
            e_fwd[eid] = len(e_fwd)
        if tid is not None:
            if tid not in t_fwd:
                t_fwd[tid] = len(t_fwd)
            tgt.append(t_fwd[tid])
        else:
            tgt.append(-1)
        ent.append(e_fwd[eid])
        if e.event not in ename_index:
            ename_index[e.event] = len(ename_index)
        enm.append(ename_index[e.event])
        r = e.properties.get_opt(rating_property)
        try:
            rat.append(float(r) if r is not None else np.nan)
        except (TypeError, ValueError):
            rat.append(np.nan)
        tms.append(int(e.event_time.timestamp() * 1000))

    names_sorted = [n for n, _ in sorted(ename_index.items(), key=lambda kv: kv[1])]
    return ColumnarEvents(
        entity_ids=entity_vocab or BiMap(e_fwd),
        target_ids=target_vocab or BiMap(t_fwd),
        event_names=names_sorted,
        entity_idx=np.asarray(ent, dtype=np.int32),
        target_idx=np.asarray(tgt, dtype=np.int32),
        event_name_idx=np.asarray(enm, dtype=np.int32),
        rating=np.asarray(rat, dtype=np.float32),
        event_time_ms=np.asarray(tms, dtype=np.int64),
    )


def write(events: Sequence[Event], app_id: int,
          channel_id: Optional[int] = None,
          storage: Optional[Storage] = None) -> List[str]:
    """PEvents.write equivalent (PEvents.scala:172-185), used by import."""
    storage = storage or get_storage()
    return storage.get_events().insert_batch(events, app_id, channel_id)
