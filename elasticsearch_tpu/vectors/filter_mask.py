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

A row is written in time proportional to the filter's length: a
`RowLocator`, built once when a row map is set (a store's sync, a
generation's construction) and never on the search path, turns engine
rows into positions, and `True` is scattered there. Only a map the
locator cannot hold exactly and cheaply is searched whole (`np.isin`);
`note_built` counts which of the two a request took.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from elasticsearch_tpu.telemetry import metrics as _metrics

# a position table spans [row_map[0], row_map[-1]]: beyond this many
# table entries a row held, the map is searched instead (a small sealed
# generation of scattered rows must not cost a table the size of the index)
TABLE_SPAN_LIMIT = 8


class RowLocator:
    """Engine row -> position in one row map, in the cheapest form that
    is exact for THAT array (read from it, no setting):

    - `contiguous`: the map is `base, base + 1, ...`; position = row - base
    - `table`: strictly ascending with gaps, its span at most
      `TABLE_SPAN_LIMIT` times its length: an int32 table over the span,
      -1 where the map holds no such row
    - `search`: anything else (out of order, a repeated row, a wide
      span); nothing is kept and `allowed_rows` searches the map
    """

    __slots__ = ("row_map", "form", "base", "span", "table")

    def __init__(self, row_map: np.ndarray):
        self.row_map = row_map
        self.base = 0
        self.span = 0
        self.table = None
        n = len(row_map)
        if n == 0:
            self.form = "contiguous"
            return
        self.base = int(row_map[0])
        span = int(row_map[-1]) - self.base + 1
        if not (n <= span <= TABLE_SPAN_LIMIT * n
                and bool(np.all(row_map[1:] > row_map[:-1]))):
            self.form = "search"
            return
        self.span = span
        if span == n:
            self.form = "contiguous"
            return
        self.form = "table"
        self.table = np.full(span, -1, dtype=np.int32)
        self.table[row_map - self.base] = np.arange(n, dtype=np.int32)

    @property
    def exact(self) -> bool:
        """Positions come from the locator, not from a search of the map."""
        return self.form != "search"

    def positions(self, rows: np.ndarray) -> np.ndarray:
        """Where the map holds `rows` (any order); rows it does not hold
        are dropped. Only for an `exact` locator."""
        rel = np.asarray(rows, dtype=np.int64)
        if self.base:
            rel = rel - self.base
        if len(rel) and (rel.min() < 0 or rel.max() >= self.span):
            rel = rel[(rel >= 0) & (rel < self.span)]
        if self.table is not None:
            rel = self.table[rel]
            rel = rel[rel >= 0]
        return rel


def allowed_rows(locator: RowLocator,
                 filters: Sequence[Optional[np.ndarray]],
                 live: Optional[np.ndarray] = None,
                 out: Optional[np.ndarray] = None) -> np.ndarray:
    """[len(filters), len(row_map)] bool: request i may match corpus row j
    (`row_map[j]` is among its `filter_rows`; every row where it carries
    no filter), and the row is `live` where tombstones are given. `out`:
    write there (a view of the batch's padded mask, which may hold
    anything: every row of it is written whole) and not into a new
    array."""
    if out is None:
        out = np.empty((len(filters), len(locator.row_map)), dtype=bool)
    for i, fr in enumerate(filters):
        if fr is None:
            out[i] = True if live is None else live
        elif locator.exact:
            pos = locator.positions(fr)
            if live is not None:
                pos = pos[live[pos]]
            out[i] = False
            out[i, pos] = True
        else:
            out[i] = np.isin(locator.row_map, fr)
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


def note_built(filters: Sequence[Optional[np.ndarray]],
               locators: Sequence[RowLocator]) -> None:
    """Count the batch's filtered requests by how `allowed_rows` wrote
    their rows, ONCE a request whatever the number of row maps it met
    (a fan-out's generations): scattered where every map located them,
    searched where any map had to be searched whole."""
    n = sum(fr is not None for fr in filters)
    if n:
        scattered = all(loc.exact for loc in locators)
        _metrics.counter("dispatch.mask_scattered" if scattered
                         else "dispatch.mask_searched").inc(n)


def note_upload(mask: np.ndarray) -> np.ndarray:
    """Count the bytes of a host mask on its way to `device_put`."""
    _metrics.counter("dispatch.mask_bytes").inc(mask.nbytes)
    return mask
