"""The deployment `http-logs-dash` (ISSUE 35) on the NORMAL path, at 8,192
rows on the CPU: `PUT` the track's mapping, `_bulk` its documents, `POST
/bench/_search?request_cache=false` with `size: 0` and one of the four
dashboard panels, through the REST controller as the HTTP layer drives it.

Every answer has to equal the plain reference's
(`benchmark/kinds/aggs_reference.py`, numpy alone) AND the host walker's
(`search/aggregations.py` `compute_aggs`, a node with
`search.aggs.device_enabled=false`), byte for byte in the aggregations
tree, while the device answers every node: on the single-device route,
after a second `_bulk` + `_refresh` (the column's delta rebuild), with the
cost router on and off, on the `aggs.mesh_*` twins over the conftest's
virtual devices, and (ISSUE 38) after a delete-by-id of scattered rows
and a second `_bulk` + `_refresh`: a row map of two segments with gaps,
whose masks go through the locator's table.
"""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.kinds import aggs_reference as reference  # noqa: E402
from elasticsearch_tpu.node import Node  # noqa: E402
from elasticsearch_tpu.rest.actions import register_all  # noqa: E402
from elasticsearch_tpu.rest.controller import RestController  # noqa: E402
from elasticsearch_tpu.telemetry import metrics  # noqa: E402

with open(os.path.join(REPO, "benchmark", "configs",
                       "http-logs-dash.json")) as _f:
    CONFIG = json.load(_f)
ROWS, SEED = 8192, 2 ** 31 + 3501
PANELS = [name for name, _width in reference.PANELS]
HOUR = reference.HOUR


def _mappings():
    """The track's five fields: the file's vector property is there for
    the unedited benchmark tests alone (`kinds/aggs.py` drops it too)."""
    props = {k: v for k, v in
             CONFIG["index"]["mappings"]["properties"].items()
             if k != CONFIG["data"]["vector_field"]}
    return {"properties": props}


class Served:
    """One node behind the REST controller, loaded with the first
    `loaded` rows of the corpus."""

    def __init__(self, path, corpus, settings=None):
        self.node = Node(str(path), settings=dict(
            {"telemetry.tracing.sample_rate": 0.0}, **(settings or {})))
        self.rc = RestController()
        register_all(self.rc, self.node)
        self.corpus = corpus
        self.blocks = []
        status, body = self.req("PUT", "/bench", {
            "settings": dict(CONFIG["index"]["settings"],
                             **CONFIG["load"]["settings"]),
            "mappings": _mappings()})
        assert status == 200, body

    def req(self, method, path, body=None, **query):
        raw = body if isinstance(body, bytes) else (
            json.dumps(body).encode() if body is not None else b"")
        ctype = ("application/x-ndjson" if path.endswith("_bulk")
                 else "application/json")
        return self.rc.dispatch(method, path,
                                {k: str(v) for k, v in query.items()}, raw,
                                ctype)

    def load(self, blocks):
        for b, n in blocks:
            status, resp = self.req("POST", "/_bulk",
                                    self.corpus.bulk_body(b, "bench", n))
            assert status == 200 and not resp["errors"], resp
        self.blocks += list(blocks)
        assert self.req("POST", "/bench/_refresh")[0] == 200
        return self.corpus.rows(self.blocks)

    def panel(self, panel, t):
        status, resp = self.req("POST", "/bench/_search",
                                reference.body(panel, t),
                                request_cache="false")
        assert status == 200 and not resp["_shards"]["failed"], resp
        assert resp["hits"]["hits"] == [] and not resp["timed_out"]
        return {"total": resp["hits"]["total"],
                "aggregations": resp["aggregations"]}

    def aggs_stats(self):
        return self.node._aggs_stats_section()

    def close(self):
        self.node.close()


def _blocks(n_rows, docs):
    return [(b, min(docs, n_rows - b * docs))
            for b in range(-(-n_rows // docs))]


@pytest.fixture(scope="module")
def corpus():
    return reference.LogCorpus(SEED, CONFIG, ROWS)


def _served(tmp_path_factory, corpus, name, settings):
    s = Served(tmp_path_factory.mktemp(name), corpus, settings)
    return s, s.load(_blocks(ROWS, corpus.block_docs))


@pytest.fixture(scope="module")
def device(tmp_path_factory, corpus):
    """As the cell runs it: every eligible node on the device."""
    s, rows = _served(tmp_path_factory, corpus, "device",
                      {"search.aggs.cost_router": "false"})
    yield s, rows
    s.close()


@pytest.fixture(scope="module")
def walker(tmp_path_factory, corpus):
    """The host walker alone (`compute_aggs`)."""
    s, rows = _served(tmp_path_factory, corpus, "walker",
                      {"search.aggs.device_enabled": "false"})
    yield s, rows
    s.close()


def _tree(x):
    return json.dumps(x, sort_keys=True)


def _times(rows, panel, count=3):
    """A few of the stream's own draws for this panel."""
    return [t for name, t in rows.requests(0, 64) if name == panel][:count]


@pytest.mark.parametrize("panel", PANELS)
def test_a_panel_equals_the_reference_and_the_walker(device, walker, panel):
    served, rows = device
    before = served.aggs_stats()
    for t in _times(rows, panel):
        got = served.panel(panel, t)
        assert not reference.differs(got, rows.answer(panel, t)), (panel, t)
        assert _tree(got["aggregations"]) == _tree(
            walker[0].panel(panel, t)["aggregations"]), (panel, t)
        assert got["total"] == walker[0].panel(panel, t)["total"]
    after = served.aggs_stats()
    assert after["host_nodes"] == before["host_nodes"]
    assert after["device_nodes"] > before["device_nodes"]
    assert after["fallback_reasons"] == {}
    assert walker[0].aggs_stats()["device_nodes"] == 0


def test_hourly_is_a_calendar_hour_on_the_device_with_its_empty_hours(
        device, walker):
    """Rally's `hourly_agg` verbatim: 1,176 buckets, the hours that hold
    no row among them (`min_doc_count` 0 is the default), from
    `aggs.cal_counts`, not from the host walker."""
    served, rows = device
    got = served.panel("hourly", None)["aggregations"]["by_hour"]["buckets"]
    assert len(got) == 49 * 24
    assert got[0]["key_as_string"] == "1998-04-30T00:00:00.000Z"
    assert got[-1]["key_as_string"] == "1998-06-17T23:00:00.000Z"
    assert sum(b["doc_count"] for b in got) == ROWS
    assert any(b["doc_count"] == 0 for b in got)        # at this density
    assert metrics.counter("aggs.dispatches.date_histogram").value > 0
    # the walker fills a calendar interval's gaps too
    assert len(walker[0].panel("hourly", None)["aggregations"]["by_hour"]
               ["buckets"]) == 49 * 24


def test_a_range_that_matches_nothing_and_one_that_overhangs(device, walker):
    served, rows = device
    c = rows.corpus
    first, last = c.t0, c.t0 + (c.hours - 1) * HOUR
    for panel, width in reference.PANELS[1:]:
        for t in (first - 30 * reference.DAY,        # nothing at all
                  first - (width - 1) * HOUR,        # only its last hour
                  last):                             # only its first hour
            got = served.panel(panel, t)
            assert not reference.differs(got, rows.answer(panel, t)), \
                (panel, t)
            assert _tree(got["aggregations"]) == _tree(
                walker[0].panel(panel, t)["aggregations"]), (panel, t)
    nothing = served.panel("bytes-by-hour", first - 30 * reference.DAY)
    assert nothing["total"] == {"value": 0, "relation": "eq"}
    assert nothing["aggregations"]["by_hour"]["buckets"] == []
    assert nothing["aggregations"]["total_bytes"]["value"] == 0


@pytest.fixture(scope="module")
def rehearsal_size(tmp_path_factory):
    """32,768 rows, the size the cell is rehearsed at: a week's bytes
    pass 2^24 there (the float32 control's trap) and a panel's board is
    the cell's own 2,049 lanes. ONE node: the same requests are answered
    again by the host walker with the device engine switched off."""
    n = 32768
    s = Served(tmp_path_factory.mktemp("rehearsal"),
               reference.LogCorpus(SEED + 1, CONFIG, n),
               {"search.aggs.cost_router": "false"})
    yield s, s.load(_blocks(n, s.corpus.block_docs))
    s.close()


def _programs():
    return {k: metrics.counter("aggs.programs." + k).value
            for k in ("narrow", "x64")}


@pytest.mark.parametrize("panel", PANELS)
def test_a_panel_at_32768_rows_is_answered_by_the_32_bit_programs(
        rehearsal_size, panel):
    """ISSUE 36: every program of the four panels is a 32-bit one
    (`@timestamp` in whole seconds, `size` and `status` integers), and
    its answer is the reference's and the host walker's, byte for
    byte."""
    served, rows = rehearsal_size
    times = _times(rows, panel, 2)
    before, stats = _programs(), served.aggs_stats()
    got = [served.panel(panel, t) for t in times]
    after = _programs()
    assert after["narrow"] > before["narrow"]
    assert after["x64"] == before["x64"]
    assert served.aggs_stats()["host_nodes"] == stats["host_nodes"]
    served.node.settings["search.aggs.device_enabled"] = "false"
    try:
        walked = [served.panel(panel, t) for t in times]
    finally:
        del served.node.settings["search.aggs.device_enabled"]
    assert _programs() == after                 # the walker launched none
    for t, g, w in zip(times, got, walked):
        assert not reference.differs(g, rows.answer(panel, t)), (panel, t)
        assert _tree(g) == _tree(w), (panel, t)
    if panel == "bytes-by-hour":
        # the trap the control falls into: a week's bytes in float32
        week = got[0]["aggregations"]["total_bytes"]["value"]
        assert week > 2 ** 24 and week == float(int(week))
        assert reference.differs(got[0], rows.answer(
            panel, times[0], sum_dtype=np.float32))


def test_a_week_counts_past_the_default_track_total_hits(tmp_path, corpus):
    """`hits.total` is exact up to 10,000 and a lower bound past it."""
    s = Served(tmp_path / "n", reference.LogCorpus(SEED, CONFIG, 12288),
               {"search.aggs.cost_router": "false"})
    try:
        rows = s.load(_blocks(12288, s.corpus.block_docs))
        got = s.panel("hourly", None)
        assert got["total"] == {"value": 10000, "relation": "gte"}
        assert not reference.differs(got, rows.answer("hourly", None))
    finally:
        s.close()


def test_after_a_second_bulk_and_refresh(tmp_path, corpus):
    """Half of the rows, the panels, the other half: the columns'
    delta rebuild answers over all of them."""
    s = Served(tmp_path / "n", corpus, {"search.aggs.cost_router": "false"})
    try:
        blocks = _blocks(ROWS, corpus.block_docs)
        half = s.load(blocks[:len(blocks) // 2])
        for panel in PANELS:
            t = _times(half, panel, 1)[0]
            assert not reference.differs(s.panel(panel, t),
                                         half.answer(panel, t)), panel
        rebuilds = s.aggs_stats()["column_rebuilds"]
        rows = s.load(blocks[len(blocks) // 2:])
        assert len(rows) == ROWS
        for panel in PANELS:
            for t in _times(rows, panel, 2):
                assert not reference.differs(s.panel(panel, t),
                                             rows.answer(panel, t)), panel
        stats = s.aggs_stats()
        assert stats["column_rebuilds"] > rebuilds
        assert stats["host_nodes"] == 0
    finally:
        s.close()


def _snapshot(served):
    svc = served.node.indices.get("bench")
    return served.node._agg_engine(svc).store.snapshot(svc.combined_reader())


def _masks():
    return {k: metrics.counter("aggs.mask_" + k).value
            for k in ("scattered", "searched")}


def test_the_sealed_index_the_cell_runs_has_a_contiguous_row_map(device):
    """One load, no delete: every panel's mask is written through the
    locator (a slice for a time range over rows in time order), none by
    a search of the map."""
    served, rows = device
    snap = _snapshot(served)
    assert snap.locator.form == "contiguous" and snap.n_rows == ROWS
    before = _masks()
    for panel in PANELS:
        t = _times(rows, panel, 1)[0]
        assert not reference.differs(served.panel(panel, t),
                                     rows.answer(panel, t)), panel
        got = snap.filter_mask(np.sort(rows.panel_rows(panel, t)))
        assert got.sum() == rows.matched_rows(panel, t)
    after = _masks()
    assert after["scattered"] - before["scattered"] == len(PANELS)
    assert after["searched"] == before["searched"]


@pytest.fixture(scope="module")
def gapped(tmp_path_factory, corpus):
    """Three quarters of the rows, a delete-by-id of scattered ones (and
    of one whole run), a `_refresh`, then the last quarter and another:
    two segments, the first with gaps. The reference holds the rows that
    are left."""
    s = Served(tmp_path_factory.mktemp("gapped"), corpus,
               {"search.aggs.cost_router": "false"})
    blocks = _blocks(ROWS, corpus.block_docs)
    cut = 3 * len(blocks) // 4

    def bulk(body):
        status, resp = s.req("POST", "/_bulk", body)
        assert status == 200 and not resp["errors"], resp
        return [item["index"]["_id"] for item in resp["items"]
                if "index" in item]

    ids = [i for b, n in blocks[:cut]
           for i in bulk(corpus.bulk_body(b, "bench", n))]
    assert s.req("POST", "/bench/_refresh")[0] == 200
    rng = np.random.default_rng(SEED)
    gone = np.unique(np.r_[rng.choice(len(ids), len(ids) // 9,
                                      replace=False),
                           np.arange(700, 760), 0, len(ids) - 1])
    bulk("".join('{"delete":{"_index":"bench","_id":"%s"}}\n' % ids[i]
                 for i in gone).encode())
    for b, n in blocks[cut:]:
        bulk(corpus.bulk_body(b, "bench", n))
    assert s.req("POST", "/bench/_refresh")[0] == 200
    every = corpus.rows(blocks)
    keep = np.ones(ROWS, dtype=bool)
    keep[gone] = False
    rows = reference.LogRows(corpus, every.ts[keep], every.status[keep],
                             every.size[keep])
    yield s, rows, len(gone)
    s.close()


@pytest.mark.parametrize("panel", PANELS)
def test_a_panel_after_deletes_and_a_second_segment_goes_through_the_table(
        gapped, walker, panel):
    served, rows, n_gone = gapped
    snap = _snapshot(served)
    assert snap.n_rows == len(rows) == ROWS - n_gone
    assert snap.locator.form == "table"
    assert len(served.node.indices.get("bench").combined_reader().views) >= 2
    before, stats = _masks(), served.aggs_stats()
    times = _times(rows, panel, 3)
    for t in times:
        got = served.panel(panel, t)
        assert not reference.differs(got, rows.answer(panel, t)), (panel, t)
    after = _masks()
    assert after["scattered"] - before["scattered"] == len(times)
    assert after["searched"] == before["searched"]
    assert served.aggs_stats()["host_nodes"] == stats["host_nodes"]
    # and the walker over the same rows says the same
    served.node.settings["search.aggs.device_enabled"] = "false"
    try:
        walked = served.panel(panel, times[0])
    finally:
        del served.node.settings["search.aggs.device_enabled"]
    assert _tree(walked) == _tree(served.panel(panel, times[0]))


def test_with_the_cost_router_on_every_answer_is_exact(tmp_path, corpus,
                                                       walker):
    """The default router may send a node to the walker or to the
    device: either way the answer is the reference's."""
    s = Served(tmp_path / "n", corpus, {"search.aggs.cost_router": "true"})
    try:
        rows = s.load(_blocks(ROWS, corpus.block_docs))
        for _round in range(3):
            for panel in PANELS:
                for t in _times(rows, panel, 2):
                    got = s.panel(panel, t)
                    assert not reference.differs(
                        got, rows.answer(panel, t)), panel
                    assert _tree(got["aggregations"]) == _tree(
                        walker[0].panel(panel, t)["aggregations"])
        stats = s.aggs_stats()
        assert stats["device_nodes"] + stats["host_nodes"] == 3 * 2 * 5
    finally:
        s.close()


@pytest.mark.multidevice
def test_the_mesh_twins_answer_the_panels(tmp_path, corpus, walker,
                                          mesh_serving):
    """Shared code (the cell itself is one chip): the `aggs.mesh_*`
    programs over the row bucket sharded eight ways."""
    s = Served(tmp_path / "n", corpus, {"search.aggs.cost_router": "false"})
    try:
        rows = s.load(_blocks(ROWS, corpus.block_docs))
        for panel in PANELS:
            t = _times(rows, panel, 1)[0]
            got = s.panel(panel, t)
            assert not reference.differs(got, rows.answer(panel, t)), panel
            assert _tree(got["aggregations"]) == _tree(
                walker[0].panel(panel, t)["aggregations"]), panel
        stats = s.aggs_stats()
        assert stats["mesh_dispatches"] > 0 and stats["host_nodes"] == 0
    finally:
        s.close()


def test_a_numeric_bound_on_an_epoch_second_field_is_seconds(device):
    """The mapping's format is `strict_date_optional_time||epoch_second`:
    a bound parses as a value of the field does, so a number is seconds
    (it was read as milliseconds and matched nothing)."""
    served, rows = device
    t = _times(rows, "status-in-range", 1)[0]
    want = rows.matched_rows("status-in-range", t)

    def count(gte, lt):
        status, resp = served.req("POST", "/bench/_search", {
            "size": 0, "track_total_hits": True,
            "query": {"range": {"@timestamp": {"gte": gte, "lt": lt}}}})
        assert status == 200
        return resp["hits"]["total"]["value"]

    assert want > 0
    assert count(t, t + reference.DAY) == want
    assert count(str(t), str(t + reference.DAY)) == want
    assert count(reference.iso(t), reference.iso(t + reference.DAY)) == want


# ---------------------------------------------------------------------------
# the reference itself
# ---------------------------------------------------------------------------

def test_the_corpus_is_a_log_in_time_order_made_from_the_seed(corpus):
    rows = corpus.rows(_blocks(ROWS, corpus.block_docs))
    assert np.all(np.diff(rows.ts) >= 0)
    assert rows.ts.min() >= corpus.t0
    assert rows.ts.max() < corpus.t0 + corpus.hours * HOUR
    days = np.bincount((rows.ts - corpus.t0) // reference.DAY, minlength=49)
    assert days.min() > 0 and days[41:].mean() > 2 * days[:41].mean()
    share = (rows.status == 200).mean()
    assert 0.75 < share < 0.85
    assert np.all(rows.size[rows.status == 304] == 0)
    assert 3000 < np.median(rows.size[rows.status != 304]) < 5500
    again = reference.LogCorpus(SEED, CONFIG, ROWS)
    assert again.bulk_body(1, "bench", 5) == corpus.bulk_body(1, "bench", 5)
    other = reference.LogCorpus(SEED + 1, CONFIG, ROWS)
    assert other.bulk_body(1, "bench", 5) != corpus.bulk_body(1, "bench", 5)
    line = json.loads(corpus.bulk_body(0, "bench", 1).splitlines()[1])
    assert set(line) == {"@timestamp", "clientip", "request", "status",
                         "size"}
    assert isinstance(line["@timestamp"], int)


def test_request_i_is_a_function_of_seed_and_i(corpus):
    rows = corpus.rows(_blocks(ROWS, corpus.block_docs))
    span = rows.requests(1000, 60)              # crosses a chunk at 1024
    assert span[20:30] == rows.requests(1020, 10)
    for i, (panel, t) in enumerate(span):
        name, width = reference.PANELS[(1000 + i) % 4]
        assert panel == name
        if not width:
            assert t is None
            continue
        assert (t - corpus.t0) % HOUR == 0
        assert corpus.t0 <= t and \
            t + width * HOUR <= corpus.t0 + corpus.hours * HOUR
    drawn = {t for _p, t in rows.requests(0, 4000) if t is not None}
    assert len(drawn) > 900                     # no body repeats much
