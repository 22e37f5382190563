"""A filter's row of the batch's mask is a scatter through the row map's
locator (ISSUE 34), and holds the bits `np.isin(row_map, filter_rows)`
wrote:

- `filter_mask.RowLocator` reads its form from the array: `contiguous`
  (a base), `table` (ascending with gaps, a bounded span), `search`
  (out of order, a repeated row, a span over the limit: `np.isin` stays);
- `filter_mask.allowed_rows` equals the search row for row on each form,
  whatever the filter holds, with tombstones and without, into a new
  array, a zeroed view and a view that holds a previous batch's rows;
- `dispatch.mask_scattered` / `dispatch.mask_searched` count a filtered
  request once, by the way its rows were written, on the three routes;
- the benchmark's `stats_ratio` reader, as it is, reads the scattered
  share as 100 from two `_nodes/stats` snapshots around a served batch,
  and nothing from a program without the counter;
- the aggregation engine's caller (ISSUE 38): `ops/aggs.StoreSnapshot.
  filter_mask(rows)` equals `np.isin(row_map, rows)` padded with `False`
  to the row bucket, on each form of the map and whatever `rows` holds,
  and searches only a map its locator cannot hold.
"""

import contextlib
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.readers import stats_ratio  # noqa: E402
from elasticsearch_tpu.ops.aggs import StoreSnapshot  # noqa: E402
from elasticsearch_tpu.telemetry import metrics  # noqa: E402
from elasticsearch_tpu.vectors import filter_mask  # noqa: E402
from test_filtered_knn_tags import Served  # noqa: E402

N = 96
_RNG = np.random.default_rng(34)
MAPS = {
    # name: (row map, the form its locator has to take)
    "contiguous_from_0": (np.arange(N, dtype=np.int64), "contiguous"),
    "contiguous_from_a_base": (np.arange(5000, 5000 + N, dtype=np.int64),
                               "contiguous"),
    "ascending_with_gaps": (np.sort(_RNG.choice(
        np.arange(700, 700 + 4 * N), N, replace=False)).astype(np.int64),
        "table"),
    "unsorted": (_RNG.permutation(np.arange(40, 40 + N)).astype(np.int64),
                 "search"),
    "a_row_twice": (np.sort(np.r_[np.arange(N - 1), 7]).astype(np.int64),
                    "search"),
    "span_over_the_limit": (
        np.arange(N, dtype=np.int64)
        * (filter_mask.TABLE_SPAN_LIMIT + 1) + 3, "search"),
}


def _filters(kind: str, row_map: np.ndarray) -> list:
    """A batch's `filter_rows` of one shape, sorted as the searches hand
    them over."""
    held = np.sort(row_map)
    lo, hi = int(held[0]), int(held[-1])
    outside = np.array([lo - 9, lo - 1, hi + 1, hi + 70000], dtype=np.int64)
    some = np.unique(_RNG.choice(row_map, N // 3))
    if kind == "empty":
        return [np.zeros(0, dtype=np.int64)]
    if kind == "rows_outside_the_map":
        # below, above, and (where the map has gaps) between its rows
        between = np.setdiff1d(np.arange(lo, hi + 1), row_map)[:5]
        return [np.sort(np.r_[outside, between, some]).astype(np.int64),
                outside]
    if kind == "the_first_and_the_last_row":
        return [np.array([row_map[0], row_map[-1]], dtype=np.int64),
                np.array([lo, hi], dtype=np.int64)]
    if kind == "every_row":
        return [np.unique(row_map)]
    if kind == "no_filter_among_filtered":
        return [some, None, np.unique(_RNG.choice(row_map, 3)), None]
    raise AssertionError(kind)


KINDS = ["empty", "rows_outside_the_map", "the_first_and_the_last_row",
         "every_row", "no_filter_among_filtered"]


def _reference(row_map, filters, live):
    want = np.empty((len(filters), len(row_map)), dtype=bool)
    for i, fr in enumerate(filters):
        want[i] = True if fr is None else np.isin(row_map, fr)
        if live is not None:
            want[i] &= live
    return want


@pytest.mark.parametrize("name", list(MAPS))
def test_the_locator_takes_the_form_the_array_allows(name):
    row_map, form = MAPS[name]
    loc = filter_mask.RowLocator(row_map)
    assert loc.form == form and loc.exact == (form != "search")
    assert (loc.table is not None) == (form == "table")
    if form == "table":
        assert loc.table.dtype == np.int32
        assert len(loc.table) == row_map[-1] - row_map[0] + 1
    assert loc.row_map is row_map


def test_the_locator_of_an_empty_map_holds_no_row():
    loc = filter_mask.RowLocator(np.zeros(0, dtype=np.int64))
    assert loc.exact
    got = filter_mask.allowed_rows(loc, [np.array([0, 5]), None])
    assert got.shape == (2, 0)


def test_a_span_at_the_limit_keeps_a_table_and_one_past_it_does_not():
    n, limit = 16, filter_mask.TABLE_SPAN_LIMIT
    at = np.r_[np.arange(n - 1), limit * n - 1].astype(np.int64)
    assert filter_mask.RowLocator(at).form == "table"
    assert filter_mask.RowLocator(np.r_[at[:-1], limit * n]).form == "search"


@pytest.mark.parametrize("out_mode", ["new", "zeroed_view", "stale_view"])
@pytest.mark.parametrize("with_live", [False, True],
                         ids=["all_live", "tombstones"])
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("name", list(MAPS))
def test_allowed_rows_equals_isin(name, kind, with_live, out_mode):
    row_map, _ = MAPS[name]
    filters = _filters(kind, row_map)
    live = None
    if with_live:
        live = np.ones(len(row_map), dtype=bool)
        live[[0, 3, len(row_map) - 1]] = False
        live[_RNG.choice(len(row_map), 9)] = False
    want = _reference(row_map, filters, live)
    loc = filter_mask.RowLocator(row_map)
    if out_mode == "new":
        got = filter_mask.allowed_rows(loc, filters, live=live)
        assert got.dtype == np.bool_ and got.shape == want.shape
    else:
        # the batch's padded mask: two requests and seven rows of pad,
        # which are the caller's and stay as they were
        fill = out_mode == "stale_view"
        big = np.full((len(filters) + 2, len(row_map) + 7), fill)
        view = big[:len(filters), :len(row_map)]
        got = filter_mask.allowed_rows(loc, filters, live=live, out=view)
        assert got is view
        assert (big[len(filters):] == fill).all()
        assert (big[:, len(row_map):] == fill).all()
    np.testing.assert_array_equal(got, want)


def test_filter_rows_in_any_order_and_twice_are_the_same_rows():
    """The searches hand sorted rows; the scatter does not lean on it."""
    for name in ("contiguous_from_a_base", "ascending_with_gaps"):
        row_map, _ = MAPS[name]
        fr = _RNG.choice(np.r_[row_map, row_map[:9], row_map[0] - 2], 50)
        got = filter_mask.allowed_rows(filter_mask.RowLocator(row_map), [fr])
        np.testing.assert_array_equal(got[0], np.isin(row_map, fr))


# ------------------------------------------- the aggregation engine's mask


def _agg_rows(kind: str, row_map: np.ndarray) -> np.ndarray:
    """A request's matched rows of one shape, as `AggEngine.compute`
    hands them to `StoreSnapshot.filter_mask`."""
    held = np.sort(row_map)
    lo, hi = int(held[0]), int(held[-1])
    if kind == "empty":
        return np.zeros(0, dtype=np.int64)
    if kind == "every_row":
        return held
    if kind == "one_run":                   # a time range over a log
        return row_map[N // 4: N // 4 + N // 2].copy()
    if kind == "one_row":
        return row_map[N // 3: N // 3 + 1].copy()
    if kind == "the_first_and_the_last_row":
        return np.array([lo, hi], dtype=np.int64)
    if kind == "scattered":
        return np.unique(_RNG.choice(row_map, N // 3))
    if kind == "rows_the_map_does_not_hold":
        # below, above, and (where the map has gaps) between its rows
        between = np.setdiff1d(np.arange(lo, hi + 1), row_map)[:5]
        return np.sort(np.r_[lo - 9, lo - 1, hi + 1, hi + 70000, between,
                             _RNG.choice(row_map, N // 3)]).astype(np.int64)
    if kind == "only_rows_the_map_does_not_hold":
        return np.array([lo - 2, hi + 1, hi + 5], dtype=np.int64)
    if kind == "unsorted":
        return _RNG.permutation(np.unique(_RNG.choice(row_map, N // 2)))
    if kind == "unsorted_with_a_run_s_ends":
        # the ends as far apart as the rows are many, and yet no run:
        # the shape a test of the ends alone would mask wrong
        return held[[10, 40, 42, 13]]
    if kind == "a_run_s_ends_with_a_row_twice":
        return held[[20, 21, 22, 22, 24]]   # five rows, ends four apart
    if kind == "int32_rows":
        return held[5:50].astype(np.int32)
    raise AssertionError(kind)


AGG_KINDS = ["empty", "every_row", "one_run", "one_row",
             "the_first_and_the_last_row", "scattered",
             "rows_the_map_does_not_hold", "only_rows_the_map_does_not_hold",
             "unsorted", "unsorted_with_a_run_s_ends",
             "a_run_s_ends_with_a_row_twice", "int32_rows"]


@pytest.mark.parametrize("kind", AGG_KINDS)
@pytest.mark.parametrize("name", list(MAPS))
def test_the_snapshots_mask_equals_isin_padded_to_the_row_bucket(
        name, kind, monkeypatch):
    row_map, form = MAPS[name]
    snap = StoreSnapshot(("v", name), row_map)
    assert snap.locator.form == form and snap.locator.row_map is row_map
    assert snap.r_pad == 128 and snap.n_rows == N
    rows = _agg_rows(kind, row_map)
    want = np.zeros(snap.r_pad, dtype=bool)
    want[:N] = np.isin(row_map, rows)
    calls = []
    isin = np.isin
    monkeypatch.setattr(np, "isin",
                        lambda *a, **kw: calls.append(1) or isin(*a, **kw))
    got = snap.filter_mask(rows)
    monkeypatch.undo()
    assert got.dtype == np.bool_ and got.shape == (snap.r_pad,)
    np.testing.assert_array_equal(got, want)
    assert not got[N:].any()                        # the pad stays False
    # `np.isin` only behind a locator that is not exact
    assert len(calls) == (1 if form == "search" and len(rows) else 0)


@pytest.mark.parametrize("name", [n for n, (_, f) in MAPS.items()
                                  if f != "search"])
@pytest.mark.parametrize("kind", ["every_row", "one_run", "one_row"])
def test_a_run_of_the_map_is_a_slice_and_no_scatter(name, kind, monkeypatch):
    """Where `rows` is one run of the map the locator is asked for its
    two ends alone: the run is proved against the map and written as a
    slice."""
    row_map, _form = MAPS[name]
    snap = StoreSnapshot(("v", name), row_map)
    rows = _agg_rows(kind, row_map)
    asked = []
    positions = snap.locator.positions
    monkeypatch.setattr(
        type(snap.locator), "positions",
        lambda self, r: asked.append(len(r)) or positions(r))
    got = snap.filter_mask(rows)
    assert asked == [2]
    assert got.sum() == len(rows)
    np.testing.assert_array_equal(got[:N], np.isin(row_map, rows))


def test_the_snapshot_of_an_empty_map_masks_nothing():
    snap = StoreSnapshot(("v", 0), np.zeros(0, dtype=np.int64))
    assert snap.r_pad == 1 and snap.locator.exact
    for rows in (np.zeros(0, dtype=np.int64), np.array([0, 5])):
        assert not snap.filter_mask(rows).any()


COUNTERS = ("knn.filtered_searches", "dispatch.mask_scattered",
            "dispatch.mask_searched")


def _counters():
    return {n: metrics.counter(n).value for n in COUNTERS}


def _rise(before):
    now = _counters()
    return {n: now[n] - before[n] for n in COUNTERS}


@pytest.mark.parametrize("name", [n for n, (_, f) in MAPS.items()
                                  if f != "search"])
def test_a_located_map_is_never_searched(name, monkeypatch):
    row_map, _ = MAPS[name]
    loc = filter_mask.RowLocator(row_map)
    filters = _filters("no_filter_among_filtered", row_map)
    want = _reference(row_map, filters, None)

    def searched(*a, **kw):
        raise AssertionError("np.isin over a row map the locator holds")
    monkeypatch.setattr(np, "isin", searched)
    before = _counters()
    got = filter_mask.allowed_rows(loc, filters)
    filter_mask.note_built(filters, [loc])
    np.testing.assert_array_equal(got, want)
    rise = _rise(before)
    assert rise["dispatch.mask_scattered"] == 2       # of the batch's four
    assert rise["dispatch.mask_searched"] == 0


@pytest.mark.parametrize("name", [n for n, (_, f) in MAPS.items()
                                  if f == "search"])
def test_a_map_the_locator_cannot_hold_is_searched_and_counted(
        name, monkeypatch):
    row_map, _ = MAPS[name]
    loc = filter_mask.RowLocator(row_map)
    filters = _filters("no_filter_among_filtered", row_map)
    calls = []
    isin = np.isin
    monkeypatch.setattr(np, "isin",
                        lambda *a, **kw: calls.append(1) or isin(*a, **kw))
    before = _counters()
    filter_mask.allowed_rows(loc, filters)
    filter_mask.note_built(filters, [loc])
    assert len(calls) == 2
    rise = _rise(before)
    assert rise["dispatch.mask_searched"] == 2
    assert rise["dispatch.mask_scattered"] == 0


def test_a_request_is_counted_once_whatever_the_maps_it_met():
    held = filter_mask.RowLocator(MAPS["contiguous_from_0"][0])
    table = filter_mask.RowLocator(MAPS["ascending_with_gaps"][0])
    searched = filter_mask.RowLocator(MAPS["unsorted"][0])
    filters = [np.array([1, 2]), None, np.array([5])]
    before = _counters()
    filter_mask.note_built(filters, [held, table])
    assert _rise(before)["dispatch.mask_scattered"] == 2
    filter_mask.note_built(filters, [held, searched, table])
    rise = _rise(before)
    assert rise["dispatch.mask_scattered"] == 2
    assert rise["dispatch.mask_searched"] == 2
    filter_mask.note_built([None, None], [searched])     # no filter: none
    assert _rise(before) == rise


# ---------------------------------------------------------------- served


@pytest.fixture(scope="module", params=["single", "generational", "mesh"])
def served(request):
    from elasticsearch_tpu.parallel import policy
    policy.reset(full=True)
    if request.param == "mesh":
        policy.configure(enabled=True, num_shards=4, min_rows=1)
        if policy.serving_mesh() is None:
            policy.reset(full=True)
            pytest.skip("needs 4 jax devices (forced-host-device-count)")
    s = Served(request.param)
    yield s
    s.node.close()
    policy.reset(full=True)


@contextlib.contextmanager
def _every_locator_made_to_search(fc):
    """Swap the field's locators (the view's own, where it has built
    one, and each generation's) for ones in the form an unsorted map
    takes. The rows stay where they are, so the answers do too."""
    owners = [(fc, "_locator")] if fc._locator is not None else []
    if fc.gens is not None:
        owners += [(g, "locator") for g in fc.gens.snapshot().generations]
    kept = [getattr(o, a) for o, a in owners]
    try:
        for (o, a), loc in zip(owners, kept):
            searching = filter_mask.RowLocator(loc.row_map)
            searching.form = "search"
            setattr(o, a, searching)
        yield
    finally:
        for (o, a), loc in zip(owners, kept):
            setattr(o, a, loc)


def _locators(served):
    fc = served.store._fields["v"]
    if served.route == "generational":
        return [g.locator for g in fc.gens.snapshot().generations]
    return [fc.locator]


@pytest.mark.multidevice
def test_a_served_batch_is_scattered_on_every_route(served, monkeypatch):
    """The three callers of `allowed_rows`: the store's single-device and
    mesh routes and the generational fan-out (a locator a generation),
    each with the contiguous maps a load leaves: answers equal the
    reference's with `np.isin` out of `filter_mask`'s reach, and the two
    counters sum to the filtered requests."""
    locators = _locators(served)
    assert len(locators) == (2 if served.route == "generational" else 1)
    assert [loc.form for loc in locators] == ["contiguous"] * len(locators)
    if served.route == "generational":
        assert locators[1].base == len(locators[0].row_map) > 0
    q, tags = served.rows.queries(2000, 6)
    served.check(q[0], tags[0])                    # warm: compiles

    class NoSearch:
        """numpy as `filter_mask` sees it, without `isin`."""
        def __getattr__(self, attr):
            if attr == "isin":
                raise AssertionError("np.isin on the scatter's route")
            return getattr(np, attr)
    monkeypatch.setattr(filter_mask, "np", NoSearch())
    before = _counters()
    for vec, t in zip(q[1:], tags[1:]):
        served.check(vec, t)
    rise = _rise(before)
    assert rise["knn.filtered_searches"] == 5
    assert rise["dispatch.mask_scattered"] == 5
    assert rise["dispatch.mask_searched"] == 0


@pytest.mark.multidevice
def test_a_served_batch_the_locator_cannot_place_is_searched(served):
    """The same requests where no row map may be trusted to be in order
    (each locator swapped for one in the form a permuted map takes; the
    rows stay where they are, so the answers do too): the fallback
    answers on every route, and is counted once a request."""
    q, tags = served.rows.queries(2100, 3)
    served.check(q[0], tags[0])
    with _every_locator_made_to_search(served.store._fields["v"]):
        before = _counters()
        for vec, t in zip(q[1:], tags[1:]):
            served.check(vec, t)
        rise = _rise(before)
    assert rise["knn.filtered_searches"] == 2
    assert rise["dispatch.mask_searched"] == 2
    assert rise["dispatch.mask_scattered"] == 0


@pytest.mark.parametrize("shape", ["a_monolithic_map_with_gaps",
                                   "generations_with_tombstones"])
def test_deleted_rows_leave_a_table_or_tombstones_and_the_same_answers(shape):
    """Deletes leave a monolithic store a row map with gaps (a `table`)
    and a generational one tombstones (`live` given): a filter that holds
    deleted rows answers as it does with every locator made to search."""
    import test_segments as seg
    rng = np.random.default_rng(3434)
    gen, mono = seg._stores()
    mapper = seg._mapper()
    segs = seg._corpus_segments(rng, [300, 80])
    for upto in (1, 2):           # a refresh a segment: two generations
        seg._sync_both(gen, mono, mapper,
                       [seg.SegmentView(s) for s in segs[:upto]])
    deleted = [{0, 17, 250}, {5}]
    for store in (gen, mono):
        store.sync(seg.ShardReader(
            [seg.SegmentView(s, deleted_locals=set(d))
             for s, d in zip(segs, deleted)]), {"v": mapper})
    store = mono if shape.startswith("a_monolithic") else gen
    fc = store._fields["v"]
    gens = list(fc.gens.snapshot().generations) if fc.gens else []
    if store is mono:
        assert fc.locator.form == "table" and len(fc.row_map) == 376
    else:
        assert [g.locator.form for g in gens] == ["contiguous"] * 2
        assert sum(g.dead_rows for g in gens) == 4
    fr = np.sort(np.r_[rng.choice(380, 60, replace=False),
                       [0, 17, 250, 305, 379, 4000]]).astype(np.int64)
    fr = np.unique(fr)
    qs = rng.standard_normal((3, seg.DIMS)).astype(np.float32)
    before = _counters()
    got = [store.search("v", q, 20, filter_rows=fr) for q in qs]
    assert _rise(before)["dispatch.mask_scattered"] == 3
    for rows, _ in got:
        assert len(rows) == 20 and np.isin(rows, fr).all()
        assert not np.isin(rows, [0, 17, 250, 305]).any()
    with _every_locator_made_to_search(fc):
        want = [store.search("v", q, 20, filter_rows=fr) for q in qs]
    assert _rise(before)["dispatch.mask_searched"] == 3
    for (rows, scores), (w_rows, w_scores) in zip(got, want):
        np.testing.assert_array_equal(rows, w_rows)
        np.testing.assert_array_equal(scores, w_scores)


# what a `benchmark` PR's `layer_metrics/mask_scatter_share.json` would hold
# (PERF.md section 7: this PR could not add it, the filtered cell's own test
# counts its metrics); the reader is the benchmark's, as it is
SHARE = {"reader": "stats_ratio",
         "paths": ["telemetry/counters/dispatch.mask_scattered"],
         "over": ["telemetry/counters/knn.filtered_searches"], "scale": 100}


@pytest.mark.multidevice
def test_the_benchmarks_reader_reads_100_and_nothing_from_the_parent(served):
    spec = SHARE
    q, tags = served.rows.queries(2200, 4)
    served.check(q[0], tags[0])
    before = served.node.local_node_stats()
    for vec, t in zip(q[1:], tags[1:]):
        served.check(vec, t)
    after = served.node.local_node_stats()
    ctx = {"before": before, "after": after, "seconds": 1.0}
    assert stats_ratio.read(spec, ctx) == 100.0
    # the parent commit: the same window, a program without the counter

    def parent(stats):
        stats = json.loads(json.dumps(stats))
        for name in ("dispatch.mask_scattered", "dispatch.mask_searched"):
            stats["telemetry"]["counters"].pop(name, None)
        return stats
    assert stats_ratio.read(spec, {"before": parent(before),
                                   "after": parent(after),
                                   "seconds": 1.0}) is None
    # and a window that carried no filter at all: no share, not 0 / 0
    assert stats_ratio.read(spec, {"before": after, "after": after,
                                   "seconds": 1.0}) is None
