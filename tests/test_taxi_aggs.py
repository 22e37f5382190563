"""The deployment `nyc-taxis-dash` on the NORMAL path, at 32,768 rides on
the CPU: `PUT` the track's mapping, `_bulk` its rides, `POST
/bench/_search?request_cache=false` with one of the track's three
aggregation operations, through the REST controller as the HTTP layer
drives it.

Every answer has to equal the plain reference's
(`benchmark/kinds/taxi_aggs_reference.py`, numpy alone: exact but for a
`stats`' `sum` and `avg`, within 1e-12 of the exact cents over 100) from
BOTH engines of one node: the device engine, which answers every node
in 32-bit programs (the two amounts on their scaled_float lattice,
`auto_date_histogram` in two programs), and the host walker
(`search.aggs.device_enabled=false`). Three seeds; the date operations at
windows of 1, 7 and 21 days (the roundings `3h`, `12h`, `7d`) and at the
track's own 21 days from 01/01/2015; an empty window; a scaled_float at a
half cent.
"""

import contextlib
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.kinds import taxi_aggs_reference as reference  # noqa: E402
from elasticsearch_tpu.index.mapping import (  # noqa: E402
    ScaledFloatFieldMapper, round_half_up)
from elasticsearch_tpu.node import Node  # noqa: E402
from elasticsearch_tpu.rest.actions import register_all  # noqa: E402
from elasticsearch_tpu.rest.controller import RestController  # noqa: E402
from elasticsearch_tpu.telemetry import metrics  # noqa: E402

with open(os.path.join(REPO, "benchmark", "configs",
                       "nyc-taxis-dash.json")) as _f:
    CONFIG = json.load(_f)
with open(os.path.join(REPO, "benchmark", "traffic",
                       "taxi-aggs-steady.json")) as _f:
    MIX = json.load(_f)
WIDTHS = tuple(MIX["window_days"])
ROWS = 32768
SEEDS = (2 ** 31 + 4301, 4302, 3_000_000_043)
DAY = reference.DAY_MS
JAN_1 = CONFIG["data"]["rides"]["span_start_s"] * 1000
ROUNDING = {1: "3h", 7: "12h", 21: "7d"}


class Served:
    """One node behind the REST controller, loaded with a seed's rides."""

    def __init__(self, path, seed):
        self.node = Node(str(path), settings={
            "telemetry.tracing.sample_rate": 0.0,
            "search.aggs.cost_router": "false"})
        self.rc = RestController()
        register_all(self.rc, self.node)
        status, body = self.req("PUT", "/bench", {
            "settings": dict(CONFIG["index"]["settings"],
                             **CONFIG["load"]["settings"]),
            "mappings": CONFIG["index"]["mappings"]})
        assert status == 200, body
        corpus = reference.RideCorpus(seed, CONFIG, ROWS, MIX)
        docs = corpus.block_docs
        blocks = [(b, docs) for b in range(ROWS // docs)]
        for b, n in blocks:
            status, resp = self.req("POST", "/_bulk",
                                    corpus.bulk_body(b, "bench", n))
            assert status == 200 and not resp["errors"], resp
        assert self.req("POST", "/bench/_refresh")[0] == 200
        self.rows = corpus.rows(blocks)

    def req(self, method, path, body=None, **query):
        raw = body if isinstance(body, bytes) else (
            json.dumps(body).encode() if body is not None else b"")
        ctype = ("application/x-ndjson" if path.endswith("_bulk")
                 else "application/json")
        return self.rc.dispatch(method, path,
                                {k: str(v) for k, v in query.items()}, raw,
                                ctype)

    def search(self, body):
        status, resp = self.req("POST", "/bench/_search", body,
                                request_cache="false")
        assert status == 200 and not resp["_shards"]["failed"], resp
        return {"total": resp["hits"]["total"],
                "aggregations": resp["aggregations"]}

    @contextlib.contextmanager
    def walker(self):
        """The host walker alone for what runs inside: the node's
        device engine switched off as `search.aggs.device_enabled`
        does."""
        self.node.settings["search.aggs.device_enabled"] = "false"
        try:
            yield
        finally:
            del self.node.settings["search.aggs.device_enabled"]

    def aggs_stats(self):
        return self.node._aggs_stats_section()


@pytest.fixture(scope="module", params=SEEDS, ids=["seed-a", "seed-b",
                                                   "seed-c"])
def served(request, tmp_path_factory):
    s = Served(tmp_path_factory.mktemp("taxi"), request.param)
    yield s
    s.node.close()


def _counter(name):
    return metrics.counter(name).value


def _both(served, req, body=None):
    """(device answer, walker answer, reference answer) of one request,
    the device's with no node on the host and every program narrow."""
    body = body or served.rows.body(*req)
    stats0 = served.aggs_stats()
    narrow0 = _counter("aggs.programs.narrow")
    wide0 = _counter("aggs.programs.x64")
    dev = served.search(body)
    stats1 = served.aggs_stats()
    assert stats1["host_nodes"] == stats0["host_nodes"], \
        stats1["fallback_reasons"]
    assert stats1["device_nodes"] == stats0["device_nodes"] + 1
    # aggs_narrow_share reads 100: every program in 32-bit arithmetic
    assert _counter("aggs.programs.x64") == wide0
    assert _counter("aggs.programs.narrow") > narrow0
    with served.walker():
        host = served.search(body)
    return dev, host, served.rows.answer(*req)


@pytest.mark.parametrize("panel", reference.PANELS)
def test_the_track_bodies_verbatim(served, panel):
    """The track's own operations: the distance histogram over every
    ride, the two date operations from 01/01/2015 to 21/01/2015."""
    req = (panel, None, None) if panel == "distance_amount_agg" \
        else (panel, JAN_1, 21)
    body = served.rows.body(*req)
    if panel != "distance_amount_agg":
        assert body["query"]["range"]["dropoff_datetime"] == {
            "gte": "01/01/2015", "lte": "21/01/2015", "format": "dd/MM/yyyy"}
    dev, host, want = _both(served, req, body)
    assert not reference.differs(dev, want), (dev, want)
    assert not reference.differs(host, want), (host, want)
    if panel == "autohisto_agg":
        got = dev["aggregations"]["dropoffs_over_time"]
        assert got["interval"] == "7d" and len(got["buckets"]) == 3
        assert got["buckets"][0]["key_as_string"] == "2015-01-01 00:00:00"
    if panel == "distance_amount_agg":
        got = dev["aggregations"]["distance_histo"]["buckets"]
        # every mile from 0 on, the empty ones too; none from 50 on
        assert [b["key"] for b in got] == [float(k)
                                           for k in range(len(got))]
        assert 40 <= len(got) <= 50
        # the device's sums are the exact cents over 100
        for b, w in zip(got, want["aggregations"]["distance_histo"][
                "buckets"]):
            assert b["total_amount_stats"]["sum"] == \
                w["total_amount_stats"]["sum"]


@pytest.mark.parametrize("width", WIDTHS)
def test_a_window_of_1_7_21_days_chooses_its_rounding(served, width):
    rows = served.rows
    starts = [t for name, t, w in rows.requests(0, 90)
              if name == "autohisto_agg" and w == width][:2]
    assert starts
    for start in starts:
        for panel in ("autohisto_agg", "date_histogram_agg"):
            req = (panel, start, width)
            dev, host, want = _both(served, req)
            assert not reference.differs(dev, want), (req, dev, want)
            assert not reference.differs(host, want), (req, host, want)
        got = served.search(served.rows.body("autohisto_agg", start, width))
        assert got["aggregations"]["dropoffs_over_time"]["interval"] == \
            ROUNDING[width]


def test_an_empty_window_answers_no_bucket(served):
    before = served.aggs_stats()["device_nodes"]
    for panel in ("autohisto_agg", "date_histogram_agg"):
        req = (panel, JAN_1 + 400 * DAY, 7)        # past the rides
        dev, host, want = _both(served, req)
        assert dev == host == want
        assert dev["total"] == {"value": 0, "relation": "eq"}
    assert dev["aggregations"]["dropoffs_over_time"] == {"buckets": []}
    assert served.aggs_stats()["device_nodes"] == before + 2
    auto = _both(served, ("autohisto_agg", JAN_1 + 400 * DAY, 7))[0]
    assert auto["aggregations"]["dropoffs_over_time"] == {
        "buckets": [], "interval": "1s"}


def test_the_amounts_are_built_on_their_lattice(served):
    """Both scaled_float columns are held as their stored longs; the
    dropoff stamps as seconds; counted once a column build."""
    served.search(served.rows.body("distance_amount_agg", None, None))
    store = next(iter(served.node._aggs.values()))[1].store
    for name in ("trip_distance", "total_amount"):
        col = store._columns[name]
        assert col.k_scale == 100 and col.k32 is not None
        present = col.present[:col.n_rows]
        back = (col.k_base + col.k32[:col.n_rows][present].astype(np.int64)
                * col.k_unit) / 100
        assert np.array_equal(back, col.vals[:col.n_rows][present])
    assert store._columns["dropoff_datetime"].k_scale == 1
    assert store._columns["dropoff_datetime"].k_unit == 1000
    assert _counter("aggs.columns.lattice") >= 2


def test_a_round_after_each_choice_is_counted(served):
    before = {w: _counter("aggs.auto_date.rounding." + r)
              for w, r in ROUNDING.items()}
    for width in WIDTHS:
        served.search(served.rows.body("autohisto_agg", JAN_1 + 40 * DAY,
                                       width))
    for width, r in ROUNDING.items():
        assert _counter("aggs.auto_date.rounding." + r) == before[width] + 1
    assert metrics.histogram("aggs.auto_round").count >= 3


# ---------------------------------------------------------------------------
# a scaled_float at a half cent
# ---------------------------------------------------------------------------

def test_a_half_cent_rounds_up_as_math_round_does():
    m = ScaledFloatFieldMapper("a", {"scaling_factor": 100})
    assert m.doc_value(0.125) == 0.13 and m.doc_value(-0.125) == -0.12
    # the product is rounded first: 1.005 x 100 is 100.49999999999999
    assert m.doc_value(12.345) == 12.35 and m.doc_value(1.005) == 1.0
    assert round_half_up(2.5) == 3 and round_half_up(-2.5) == -2
    assert round_half_up(0.49999999999999994) == 0


def test_a_half_cent_survives_ingest_and_search(tmp_path):
    node = Node(str(tmp_path / "n"), settings={
        "telemetry.tracing.sample_rate": 0.0,
        "search.aggs.cost_router": "false"})
    rc = RestController()
    register_all(rc, node)

    def req(method, path, body, **q):
        return rc.dispatch(method, path, {k: str(v) for k, v in q.items()},
                           json.dumps(body).encode(), "application/json")

    try:
        assert req("PUT", "/h", {"mappings": {"properties": {
            "a": {"type": "scaled_float", "scaling_factor": 100}}}})[0] \
            == 200
        for i, v in enumerate((0.125, -0.125, 1.005, 10.0)):
            assert req("PUT", f"/h/_doc/{i}", {"a": v}, refresh="true")[0] \
                in (200, 201)
        body = {"size": 0, "aggs": {"s": {"stats": {"field": "a"}}},
                "query": {"range": {"a": {"gte": 0.13, "lte": 0.13}}}}
        status, resp = req("POST", "/h/_search", body,
                           request_cache="false")
        assert status == 200 and resp["hits"]["total"]["value"] == 1
        assert resp["aggregations"]["s"]["min"] == 0.13
        status, resp = req("POST", "/h/_search", {
            "size": 0, "aggs": {"s": {"stats": {"field": "a"}}}},
            request_cache="false")
        # 0.13 - 0.12 + 1.0 (1.005 x 100 is 100.49999...) + 10.0
        assert resp["aggregations"]["s"] == {
            "count": 4, "min": -0.12, "max": 10.0, "avg": 11.01 / 4,
            "sum": 11.01}
    finally:
        node.close()


def test_a_range_reads_its_bounds_in_its_own_format():
    """`format: dd/MM/yyyy` on a `range`: `lte` rounds up to the end of
    the day the pattern names, `gte` does not; a named format or a
    bound with date math is parsed as before (None here)."""
    from elasticsearch_tpu.index.mapping import parse_date_in_format
    assert parse_date_in_format("01/01/2015", "dd/MM/yyyy") == JAN_1
    assert parse_date_in_format("21/01/2015", "dd/MM/yyyy", round_up=True) \
        == JAN_1 + 21 * DAY - 1
    assert parse_date_in_format("2015-02", "yyyy-MM", round_up=True) \
        == JAN_1 + (31 + 28) * DAY - 1
    assert parse_date_in_format("01/01/2015", "strict_date_optional_time") \
        is None
    assert parse_date_in_format("01/01/2015||+1d", "dd/MM/yyyy") is None
    assert parse_date_in_format("2015-01-01", "dd/MM/yyyy") is None


def test_a_range_summing_an_amount_falls_back_to_the_walker(served):
    """A `range`'s metric boards are x64 alone, where an f64 sum of the
    quotients is no exact sum: that node goes to the walker, by name."""
    body = {"size": 0, "aggs": {"r": {
        "range": {"field": "trip_distance",
                  "ranges": [{"to": 1}, {"from": 1, "to": 5}, {"from": 5}]},
        "aggs": {"s": {"sum": {"field": "total_amount"}}}}}}
    before = served.aggs_stats()
    got = served.search(body)
    after = served.aggs_stats()
    assert after["host_nodes"] == before["host_nodes"] + 1
    assert after["fallback_reasons"]["non_integral_sum"]["count"] >= 1
    with served.walker():
        assert served.search(body) == got
