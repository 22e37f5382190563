"""The host half of filtered kNN on the exhaustive routes: a request's
sorted `filter_rows` (engine rows allowed to match) become one bool row of
the batch's [B_pad, N] mask, and the mask's bytes are counted as they are
handed to the device.

Every route that builds such a mask (`vectors/store.py`: single device and
mesh; `segments/generational.py`: the fan-out's legs) does it through
`allowed_rows`, inside ONE `dispatch.mask_build` stage a batch, and counts
the upload with `note_upload`: `_nodes/stats telemetry` then shows what a
filter costs the host apart from the rest of `dispatch.prepare` and
`dispatch.h2d`.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from elasticsearch_tpu.telemetry import metrics as _metrics


def allowed_rows(row_map: np.ndarray,
                 filters: Sequence[Optional[np.ndarray]],
                 live: Optional[np.ndarray] = None,
                 out: Optional[np.ndarray] = None) -> np.ndarray:
    """[len(filters), len(row_map)] bool: request i may match corpus row j
    (`row_map[j]` is among its `filter_rows`; every row where it carries
    no filter), and the row is `live` where tombstones are given. `out`:
    write there (a view of the batch's padded mask) and not into a new
    array."""
    if out is None:
        out = np.empty((len(filters), len(row_map)), dtype=bool)
    for i, fr in enumerate(filters):
        if fr is None:
            out[i] = True if live is None else live
        else:
            out[i] = np.isin(row_map, fr)
            if live is not None:
                out[i] &= live
    return out


def through_slots(mesh_state, allowed: np.ndarray, b_pad: int) -> np.ndarray:
    """`allowed` ([n, rows] of a corpus) as the [b_pad, slots] mask a mesh
    program reads: each row laid through the sharded copy's slot map."""
    m = np.zeros((b_pad, len(mesh_state.slot_map)), dtype=bool)
    for i, row in enumerate(allowed):
        m[i] = mesh_state.filter_mask(row)
    return m


def note_requests(filters: Sequence[Optional[np.ndarray]]) -> None:
    """Count the batch's requests that reached the store with a filter,
    and the rows their filters matched (an operator's selectivity)."""
    lengths = [len(fr) for fr in filters if fr is not None]
    if lengths:
        _metrics.counter("knn.filtered_searches").inc(len(lengths))
        _metrics.counter("knn.filter_matched_rows").inc(sum(lengths))


def note_upload(mask: np.ndarray) -> np.ndarray:
    """Count the bytes of a host mask on its way to `device_put`."""
    _metrics.counter("dispatch.mask_bytes").inc(mask.nbytes)
    return mask
