"""Read↔device overlap: stream columnar event chunks into HBM while later
chunks are still decoding.

The bulk train read (eventlog.read_columns_streamed) yields per-chunk code
arrays as decode workers finish. Serially, the whole host→HBM transfer of
the COO staging buffers happens *after* the read, inside the ALS layout
phase — over a slow host-device link that transfer is seconds of
wall-clock sitting squarely on the critical path. The :class:`ColumnStager` instead
``jax.device_put``s every chunk the moment it is decoded: JAX transfers are
asynchronous, so the copy of chunk *k* rides the link while chunk *k+1* is
still in ``np.load`` (the double-buffered host→HBM pattern, generalized to
N in-flight buffers by the async dispatch queue). ``finalize`` then does
the dense-vocab remap on device (a LUT gather at HBM bandwidth) and one
concatenate, producing device-resident mirrors of the host columns.

Correctness contract: the staged arrays are **value-identical** to the host
columns find_columnar returns — the device remap runs the same integer ops
(`where(code >= 0, lut[max(code, 0)], -1)`) on the same inputs, and the
float32 ratings pass through untouched. ops/als.prepare_ratings accepts the
staged arrays directly and skips its own host shipping, so layouts (and
therefore models) are bit-identical to the unstaged path; a tier-1 test
asserts the mirrors byte for byte. Staging is only engaged in grow-both
vocab mode (no rows dropped); ``PIO_READ_STAGE=0`` disables it.

Timing honesty (KNOWN_ISSUES.md #3): nothing here blocks — the read phase
ends when decode ends, and the in-flight transfers are absorbed by the
layout phase, whose existing one-element ``jax.device_get`` barrier is what
makes the overlapped phase table trustworthy on any backend.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np


def staging_available() -> bool:
    """Staging needs an importable jax; env kill switch PIO_READ_STAGE=0."""
    import os
    if os.environ.get("PIO_READ_STAGE", "1") == "0":
        return False
    try:
        import jax  # noqa: F401
    except Exception:   # pragma: no cover - jax is a hard dep in practice
        return False
    return True


@dataclass
class StagedColumns:
    """Device-resident mirrors of ColumnarEvents' encoded arrays."""
    entity_idx: object       # jax (n,) int32, == ColumnarEvents.entity_idx
    target_idx: object       # jax (n,) int32
    event_name_idx: object   # jax (n,) int32
    rating: object           # jax (n,) float32

    @property
    def n(self) -> int:
        return int(self.entity_idx.shape[0])

    def training_view(self, buy_pos: Optional[int], buy_rating: float):
        """(entity_idx, target_idx, rating') with the template's buy→rating
        mapping applied on device — mirrors
        recommendation.data_source.training_data_from_columnar."""
        import jax.numpy as jnp
        r = self.rating
        if buy_pos is not None:
            r = jnp.where(self.event_name_idx == buy_pos,
                          jnp.float32(buy_rating), r)
        return self.entity_idx, self.target_idx, r


class ColumnStager:
    """Accumulates per-chunk raw code arrays on device during a streamed
    bulk read; finalize() remaps + concatenates them into StagedColumns."""

    def __init__(self):
        self._chunks: List[tuple] = []

    def add(self, chunk: Dict[str, np.ndarray]) -> None:
        import jax
        # async transfers: device_put returns immediately and the copies
        # overlap the decode of later chunks
        self._chunks.append((
            jax.device_put(np.ascontiguousarray(chunk["entity_code"])),
            jax.device_put(np.ascontiguousarray(chunk["target_code"])),
            jax.device_put(np.ascontiguousarray(chunk["event_code"])),
            jax.device_put(np.ascontiguousarray(chunk["rating"])),
        ))
        from predictionio_tpu.common import telemetry
        if telemetry.on():
            reg = telemetry.registry()
            reg.counter(
                "pio_staging_chunks_total",
                "COO chunks staged to device during the overlapped read"
            ).labels().inc()
            reg.counter(
                "pio_staging_rows_total",
                "COO rows staged to device during the overlapped read"
            ).labels().inc(int(chunk["entity_code"].shape[0]))

    def finalize(self, e_lut: np.ndarray, t_lut: np.ndarray,
                 name_lut: np.ndarray) -> Optional[StagedColumns]:
        """Dense remap on device with the host-built LUTs (identical integer
        semantics to store._columnar_from_codes.dense); None when the read
        produced no rows."""
        if not self._chunks:
            return None
        from predictionio_tpu.common import telemetry
        t0 = None
        if telemetry.on():
            import time as _t
            t0 = _t.perf_counter()
        import jax
        import jax.numpy as jnp
        e_lut_d = jax.device_put(np.asarray(e_lut, np.int32))
        t_lut_d = jax.device_put(np.asarray(t_lut, np.int32))
        n_lut_d = jax.device_put(np.asarray(name_lut, np.int32))
        es, ts, ns, rs = [], [], [], []
        # consume the chunk list front-to-back and DROP each raw buffer
        # as its remap is enqueued: at any moment at most one chunk's
        # raw codes coexist with its remapped twin, so the streamed
        # train path's device peak stays ~1x the COO (+1 chunk) rather
        # than 2x while the old list held every raw buffer alive
        self._chunks.reverse()
        while self._chunks:
            ec, tc, nc, r = self._chunks.pop()
            es.append(jnp.where(ec >= 0, e_lut_d[jnp.maximum(ec, 0)],
                                jnp.int32(-1)))
            ts.append(jnp.where(tc >= 0, t_lut_d[jnp.maximum(tc, 0)],
                                jnp.int32(-1)))
            # host indexes name_lut[-1] (its sentinel last slot, always -1)
            # for an uncoded event; gather semantics differ on device, so
            # spell the -1 out explicitly
            ns.append(jnp.where(nc >= 0, n_lut_d[jnp.maximum(nc, 0)],
                                jnp.int32(-1)))
            rs.append(r)
        one = len(es) == 1
        out = StagedColumns(
            entity_idx=es[0] if one else jnp.concatenate(es),
            target_idx=ts[0] if one else jnp.concatenate(ts),
            event_name_idx=ns[0] if one else jnp.concatenate(ns),
            rating=rs[0] if one else jnp.concatenate(rs),
        )
        if t0 is not None:
            import time as _t
            # ENQUEUE time only: the dispatches above are async, and this
            # deliberately does NOT add a sync — the in-flight transfers
            # are absorbed by the layout phase, whose one-element
            # jax.device_get barrier is the honest clock (KNOWN_ISSUES #3)
            telemetry.registry().histogram(
                "pio_staging_finalize_enqueue_seconds",
                "Device-side remap/concat ENQUEUE time (async; the real "
                "transfer cost lands in pio_train_phase_seconds{phase="
                "'layout'}, which ends in a host transfer)").labels(
            ).observe(_t.perf_counter() - t0)
        return out
