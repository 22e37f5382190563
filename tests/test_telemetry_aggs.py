"""The stages and counters of the device aggregation engine (ISSUE 35),
in `telemetry.stage`'s one call form, always on:

    aggs.plan          once a request with aggregations (`plan_for`)
    aggs.mask          once a request that has a device node: the first
                       node pays it, the others share the mask
    aggs.device        once a node the device answers, with inside it
    aggs.launch        once a program handed to the device, and
    aggs.sync_wait     once a board read back as numpy
    aggs.assemble      once a node the device answers
    aggs.host          once a node the host walker answers, never else
    counters           aggs.device_nodes, aggs.host_nodes, aggs.mask_bytes
                       (the padded row bucket, once a LAUNCH on one
                       device: the host mask rides every call; once a
                       REQUEST under a mesh: every program takes the one
                       sharded copy, ISSUE 38), aggs.board_lanes,
                       aggs.matched_rows, aggs.dispatches.<family>,
                       aggs.programs.narrow and aggs.programs.x64 (ISSUE
                       36: one of the two a program, by its arithmetic),
                       aggs.mask_scattered and aggs.mask_searched (ISSUE
                       38: one of the two a request that built a mask, by
                       whether the snapshot's locator placed its rows or
                       the row map was searched; their sum is the count
                       of aggs.mask)

`indices.aggs`'s `device_nanos`, `assemble_nanos` and `host_nanos` are the
sums of the same clock marks, and a request without aggregations records
none of it.
"""

import contextlib
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.readers import stats_ratio  # noqa: E402
from elasticsearch_tpu.telemetry import metrics  # noqa: E402

ROWS = 300
T0 = 893894400          # 1998-04-30T00:00:00Z, in seconds
STAGES = ("aggs.plan", "aggs.mask", "aggs.device", "aggs.launch",
          "aggs.sync_wait", "aggs.assemble", "aggs.host", "search.took")
COUNTERS = ("aggs.device_nodes", "aggs.host_nodes", "aggs.mask_bytes",
            "aggs.board_lanes", "aggs.matched_rows",
            "aggs.dispatches.date_histogram", "aggs.dispatches.metric",
            "aggs.dispatches.terms", "aggs.dispatches.date_histogram_tree",
            "aggs.dispatches.range", "aggs.programs.narrow",
            "aggs.programs.x64", "aggs.mask_scattered",
            "aggs.mask_searched")
BY_HOUR = {"date_histogram": {"field": "@timestamp",
                              "fixed_interval": "1h"}}


def _read():
    hist = {n: (metrics.histogram(n).count, metrics.histogram(n).sum_ns)
            for n in STAGES}
    return hist, {n: metrics.counter(n).value for n in COUNTERS}


def _delta(before):
    hist, count = _read()
    return ({n: hist[n][0] - before[0][n][0] for n in STAGES},
            {n: hist[n][1] - before[0][n][1] for n in STAGES},
            {n: count[n] - before[1][n] for n in COUNTERS})


@pytest.fixture()
def node(tmp_path):
    from elasticsearch_tpu.node import Node
    n = Node(str(tmp_path / "n"),
             settings={"telemetry.tracing.sample_rate": 0.0,
                       "search.aggs.cost_router": "false"})
    n.create_index_with_templates("logs", settings={}, mappings={
        "properties": {
            "@timestamp": {"type": "date", "format":
                           "strict_date_optional_time||epoch_second"},
            "status": {"type": "integer"}, "size": {"type": "integer"},
            "clientip": {"type": "ip"}}})
    ops = []
    for i in range(ROWS):
        ops.append({"index": {"_index": "logs"}})
        ops.append({"@timestamp": T0 + 977 * i, "status": (200, 304, 404)[i % 3],
                    "size": 100 + i, "clientip": f"40.0.{i % 7}.0"})
    n.bulk(ops)
    n.indices.get("logs").refresh()
    yield n
    n.close()


def _snap(node):
    (_svc, engine), = node._aggs.values()
    return engine.store.snapshot(node.indices.get("logs").combined_reader())


def _r_pad(node):
    return _snap(node).r_pad


def _search(node, aggs, query=None):
    body = {"size": 0, "aggs": aggs, "request_cache": False}
    if query:
        body["query"] = query
    return node.search("logs", body)


def test_a_device_node_records_each_stage_once(node):
    aggs = {"by_status": {"terms": {"field": "status"}}}
    first_day = {"range": {"@timestamp": {"gte": T0, "lt": T0 + 86400}}}
    _search(node, aggs, first_day)                       # warm: compiles
    before = _read()
    resp = _search(node, aggs, first_day)
    matched = resp["hits"]["total"]["value"]
    assert 0 < matched < ROWS
    assert sum(b["doc_count"] for b in resp["aggregations"]["by_status"]
               ["buckets"]) == matched
    counts, nanos, counters = _delta(before)
    for name in ("aggs.plan", "aggs.mask", "aggs.device", "aggs.launch",
                 "aggs.sync_wait", "aggs.assemble", "search.took"):
        assert counts[name] == 1, f"{name} recorded {counts[name]} times"
    assert counts["aggs.host"] == 0
    # launch and the wait lie inside the device leg, all of it inside took
    assert nanos["aggs.launch"] + nanos["aggs.sync_wait"] \
        <= nanos["aggs.device"]
    assert nanos["aggs.plan"] + nanos["aggs.mask"] + nanos["aggs.device"] \
        + nanos["aggs.assemble"] <= nanos["search.took"]
    assert counters["aggs.device_nodes"] == 1
    assert counters["aggs.host_nodes"] == 0
    assert counters["aggs.matched_rows"] == matched
    assert counters["aggs.mask_bytes"] == _r_pad(node)
    assert counters["aggs.mask_scattered"] == 1
    assert counters["aggs.mask_searched"] == 0
    assert counters["aggs.dispatches.terms"] == 1
    assert counters["aggs.board_lanes"] == 8 + 1         # the rung + trash


THREE_PROGRAMS = {
    # `bytes-by-hour`'s shape: a histogram with a `sum` under it and a
    # top-level `sum`: two nodes, three programs, one mask
    "by_hour": dict(BY_HOUR, aggs={"bytes": {"sum": {"field": "size"}}}),
    "total_bytes": {"sum": {"field": "size"}}}


def _spy_on_launches(monkeypatch):
    """Every `_launch` of the engine: (the request's box, the program's
    arguments, its mesh)."""
    from elasticsearch_tpu.search.agg_plan import AggEngine
    seen = []
    launch = AggEngine._launch

    def spy(mask_box, name, *args, mesh=None, **statics):
        seen.append((mask_box, args, mesh))
        return launch(mask_box, name, *args, mesh=mesh, **statics)

    monkeypatch.setattr(AggEngine, "_launch", staticmethod(spy))
    return seen


def test_the_mask_is_built_once_a_request_and_rides_every_launch(
        node, monkeypatch):
    _search(node, THREE_PROGRAMS)
    seen = _spy_on_launches(monkeypatch)
    before = _read()
    resp = _search(node, THREE_PROGRAMS)
    assert resp["aggregations"]["total_bytes"]["value"] == sum(
        100 + i for i in range(ROWS))
    counts, _nanos, counters = _delta(before)
    assert counts["aggs.plan"] == 1 and counts["aggs.mask"] == 1
    assert counts["aggs.device"] == 2 and counts["aggs.assemble"] == 2
    assert counts["aggs.launch"] == 3
    assert counts["aggs.sync_wait"] == 3     # ONE packed board a program
    assert counts["aggs.host"] == 0
    assert counters["aggs.device_nodes"] == 2
    assert counters["aggs.mask_bytes"] == 3 * _r_pad(node)
    assert counters["aggs.mask_scattered"] == 1          # ONE mask built
    assert counters["aggs.mask_searched"] == 0
    assert counters["aggs.dispatches.date_histogram"] == 2
    assert counters["aggs.dispatches.metric"] == 1
    assert counters["aggs.matched_rows"] == ROWS
    # the three programs took the ONE host mask
    assert len(seen) == 3
    box = seen[0][0]
    assert box["mask"].shape == (_r_pad(node),) and not box["sharded"]
    for mask_box, args, mesh in seen:
        assert mask_box is box and mesh is None
        assert sum(a is box["mask"] for a in args) == 1


@contextlib.contextmanager
def _a_row_map_the_locator_cannot_hold(node):
    """The cached snapshot's locator in the form a row map out of order
    takes. The rows stay where they are, so the answers do too."""
    snap = _snap(node)
    assert snap.locator.form == "contiguous"
    snap.locator.form = "search"
    try:
        yield snap
    finally:
        snap.locator.form = "contiguous"


def test_a_row_map_out_of_order_is_searched_and_counted_as_searched(
        node, monkeypatch):
    first_day = {"range": {"@timestamp": {"gte": T0, "lt": T0 + 86400}}}
    want = _search(node, THREE_PROGRAMS, first_day)["aggregations"]
    calls = []
    isin = np.isin
    before = _read()
    with _a_row_map_the_locator_cannot_hold(node) as snap:
        monkeypatch.setattr(np, "isin", lambda *a, **kw: calls.append(
            a[0] is snap.row_map) or isin(*a, **kw))
        got = _search(node, THREE_PROGRAMS, first_day)["aggregations"]
        monkeypatch.undo()
    assert got == want
    assert calls.count(True) == 1            # the whole map, once
    counts, _nanos, counters = _delta(before)
    assert counts["aggs.mask"] == 1
    assert counters["aggs.mask_searched"] == 1
    assert counters["aggs.mask_scattered"] == 0
    assert counters["aggs.mask_bytes"] == 3 * _r_pad(node)


def test_a_located_row_map_is_never_searched(node, monkeypatch):
    first_day = {"range": {"@timestamp": {"gte": T0, "lt": T0 + 86400}}}
    want = _search(node, THREE_PROGRAMS, first_day)["aggregations"]
    snap = _snap(node)
    isin = np.isin

    def searched(*a, **kw):
        assert a[0] is not snap.row_map, "np.isin over a located row map"
        return isin(*a, **kw)
    monkeypatch.setattr(np, "isin", searched)
    assert _search(node, THREE_PROGRAMS, first_day)["aggregations"] == want


def test_scattered_and_searched_sum_to_the_count_of_the_mask_stage(node):
    """Over requests of one node, of three programs, of a node for the
    walker beside one for the device, of the walker alone and of no
    aggregation, on a located map and on a searched one."""
    bodies = [{"by_status": {"terms": {"field": "status"}}}, THREE_PROGRAMS,
              {"p": {"percentiles": {"field": "size"}},
               "by_status": {"terms": {"field": "status"}}},
              {"p": {"percentiles": {"field": "size"}}}]
    before = _read()
    for aggs in bodies:
        _search(node, aggs)
    node.search("logs", {"size": 1})
    with _a_row_map_the_locator_cannot_hold(node):
        for aggs in bodies[:2]:
            _search(node, aggs)
    counts, _nanos, counters = _delta(before)
    assert counters["aggs.mask_scattered"] == 3
    assert counters["aggs.mask_searched"] == 2
    assert counts["aggs.mask"] == 5


# what a `benchmark` PR's `layer_metrics/aggs_mask_scatter_share.json`
# would hold (PERF.md section 7 (s): this PR could not add it,
# `tests/benchmark/test_beat_metrics.py` holds the last entries of
# `per_layer` to PR 37's seven); the reader is the benchmark's, as it is
SHARE = {"reader": "stats_ratio",
         "paths": ["telemetry/counters/aggs.mask_scattered"],
         "over": ["telemetry/counters/aggs.mask_scattered",
                  "telemetry/counters/aggs.mask_searched"], "scale": 100}


def test_the_benchmarks_reader_reads_the_scattered_share(node):
    _search(node, THREE_PROGRAMS)
    before = node.local_node_stats()
    for _ in range(3):
        _search(node, THREE_PROGRAMS)
    after = node.local_node_stats()
    ctx = {"before": before, "after": after, "seconds": 1.0}
    assert stats_ratio.read(SHARE, ctx) == 100.0
    with _a_row_map_the_locator_cannot_hold(node):
        _search(node, THREE_PROGRAMS)
    ctx["after"] = node.local_node_stats()
    assert stats_ratio.read(SHARE, ctx) == 75.0
    # the parent commit: the same window, a program without the counters

    def parent(stats):
        stats = json.loads(json.dumps(stats))
        for name in ("aggs.mask_scattered", "aggs.mask_searched"):
            stats["telemetry"]["counters"].pop(name, None)
        return stats
    assert stats_ratio.read(SHARE, {"before": parent(before),
                                    "after": parent(after),
                                    "seconds": 1.0}) is None
    # and a window that built no mask: no share, not 0 / 0
    assert stats_ratio.read(SHARE, {"before": after, "after": after,
                                    "seconds": 1.0}) is None


@pytest.mark.multidevice
def test_the_mesh_route_shards_the_mask_once_a_request(node, monkeypatch,
                                                       mesh_serving):
    """Two nodes, three programs, ONE sharded copy (a copy a node
    before ISSUE 38)."""
    from jax.sharding import NamedSharding
    want = sum(100 + i for i in range(ROWS))
    assert _search(node, THREE_PROGRAMS)["aggregations"]["total_bytes"][
        "value"] == want
    seen = _spy_on_launches(monkeypatch)
    before = _read()
    resp = _search(node, THREE_PROGRAMS)
    assert resp["aggregations"]["total_bytes"]["value"] == want
    counts, _nanos, counters = _delta(before)
    assert counts["aggs.mask"] == 1 and counts["aggs.launch"] == 3
    assert counters["aggs.mask_bytes"] == _r_pad(node)
    assert counters["aggs.mask_scattered"] == 1
    assert node._aggs_stats_section()["mesh_dispatches"] >= 2
    box = seen[0][0]
    ((mesh, held),) = box["sharded"].items()
    assert mesh is not None and isinstance(held.sharding, NamedSharding)
    assert len(held.sharding.device_set) == 8
    for mask_box, args, used in seen:
        assert mask_box is box and used is mesh
        assert sum(a is held for a in args) == 1
    np.testing.assert_array_equal(np.asarray(held), box["mask"])


PROGRAMS = {
    # aggs -> (32-bit programs, x64 programs)
    "integral columns: every program 32-bit": (
        {"by_status": {"terms": {"field": "status"},
                       "aggs": {"bytes": {"sum": {"field": "size"}}}},
         "by_hour": BY_HOUR, "total": {"stats": {"field": "size"}}},
        4, 0),
    "a range keeps the x64 programs": (
        {"sizes": {"range": {"field": "size",
                             "ranges": [{"to": 200}, {"from": 200}]},
                   "aggs": {"bytes": {"sum": {"field": "size"}}}}},
        0, 2),
    "both in one request": (
        {"sizes": {"range": {"field": "size", "ranges": [{"to": 200}]}},
         "by_hour": dict(BY_HOUR, aggs={"by_status": {
             "terms": {"field": "status"}}})},
        2, 1),
}


@pytest.mark.parametrize("case", sorted(PROGRAMS))
def test_a_program_counts_once_as_narrow_or_as_x64(node, monkeypatch, case):
    """`aggs.programs.narrow` + `aggs.programs.x64` is the request's
    programs: `mask_box["dispatches"]`, the launches, the families'."""
    from elasticsearch_tpu.search.agg_plan import AggEngine
    aggs, narrow, x64 = PROGRAMS[case]
    boxes = []
    mask_for = AggEngine._mask_for

    def spy(self, rows, mask_box):
        if not any(b is mask_box for b in boxes):
            boxes.append(mask_box)
        return mask_for(self, rows, mask_box)

    monkeypatch.setattr(AggEngine, "_mask_for", spy)
    _search(node, aggs)
    del boxes[:]
    before = _read()
    _search(node, aggs)
    counts, _nanos, counters = _delta(before)
    (box,) = boxes
    assert counters["aggs.programs.narrow"] == narrow
    assert counters["aggs.programs.x64"] == x64
    assert narrow + x64 == box["dispatches"] == counts["aggs.launch"] \
        == sum(v for n, v in counters.items()
               if n.startswith("aggs.dispatches."))
    assert counts["aggs.host"] == 0


def test_the_two_level_tree_is_one_node_and_a_program_a_level(node):
    """A counts board for the hours, one for hours x statuses: each
    launched and read before the next (`_run_tree_node`)."""
    aggs = {"by_hour": dict(BY_HOUR, aggs={
        "by_status": {"terms": {"field": "status"}}})}
    _search(node, aggs)
    before = _read()
    _search(node, aggs)
    counts, _nanos, counters = _delta(before)
    assert counts["aggs.device"] == 1 and counts["aggs.launch"] == 2
    assert counters["aggs.dispatches.date_histogram_tree"] == 2
    assert counters["aggs.mask_bytes"] == 2 * _r_pad(node)


def test_a_node_the_walker_answers_records_aggs_host_and_no_other(node):
    """`percentiles` has no device form: its node falls to the walker,
    the `terms` beside it stays on the device."""
    aggs = {"p": {"percentiles": {"field": "size"}},
            "by_status": {"terms": {"field": "status"}}}
    _search(node, aggs)
    before = _read()
    _search(node, aggs)
    counts, _nanos, counters = _delta(before)
    assert counts["aggs.host"] == 1 and counters["aggs.host_nodes"] == 1
    assert counts["aggs.device"] == 1 and counters["aggs.device_nodes"] == 1
    assert counts["aggs.mask"] == 1 and counts["aggs.assemble"] == 1
    # a body with no device-eligible node at all: the plan, nothing more
    before = _read()
    _search(node, {"p": {"percentiles": {"field": "size"}}})
    counts, _nanos, counters = _delta(before)
    assert counts["aggs.plan"] == 1
    assert sum(counts[n] for n in STAGES[1:7]) == 0
    assert all(v == 0 for v in counters.values()), counters


def test_indices_aggs_sums_the_stages_own_clock_marks(node):
    aggs = {"by_hour": dict(BY_HOUR,
                            aggs={"bytes": {"sum": {"field": "size"}}}),
            "p": {"percentiles": {"field": "size"}}}
    _search(node, aggs)
    stats0 = node._aggs_stats_section()
    before = _read()
    for _ in range(3):
        _search(node, aggs)
    stats = node._aggs_stats_section()
    _counts, nanos, _counters = _delta(before)
    assert stats["device_nanos"] - stats0["device_nanos"] \
        == nanos["aggs.device"] > 0
    assert stats["assemble_nanos"] - stats0["assemble_nanos"] \
        == nanos["aggs.assemble"] > 0
    assert stats["host_nanos"] - stats0["host_nanos"] \
        == nanos["aggs.host"] > 0
    assert stats["device_nodes"] - stats0["device_nodes"] == 3
    assert stats["host_nodes"] - stats0["host_nodes"] == 3


def test_a_request_without_aggregations_records_none_of_them(node):
    _search(node, {"by_status": {"terms": {"field": "status"}}})
    before = _read()
    for _ in range(3):
        resp = node.search("logs", {"size": 2, "query": {"range": {
            "@timestamp": {"gte": T0, "lt": T0 + 3600}}}})
        assert len(resp["hits"]["hits"]) == 2
    counts, _nanos, counters = _delta(before)
    assert counts["search.took"] == 3
    assert sum(counts[n] for n in STAGES[:7]) == 0
    assert all(v == 0 for v in counters.values()), counters
