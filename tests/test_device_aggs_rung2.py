"""Device aggregations rung 2: calendar intervals, composite sub-agg
trees, HLL cardinality, and the measured cost router.

Same two contracts as test_device_aggs.py — json-identical parity with
the host walkers (final AND distributed-partial mode) and a closed
dispatch grid (zero steady-state recompiles under strict mode) — over
the rung-2 surface:

* calendar date_histograms (month/quarter/year/week, timezone-shifted
  days across DST transitions, leap years) via the boundary-table
  `aggs.cal_*` kernels;
* multi-level sub-agg trees (3 deep, empty parents, min_doc_count: 0)
  via composite-id `aggs.tree_*` boards;
* cardinality via `aggs.hll_board` register boards whose packed `$p`
  states merge byte-identically with the host's on skewed shard splits;
* the measured cost router (`routed_host_cheaper`), fallback-reason doc
  totals, and the observed-cardinality / warmup-clamp satellites.
"""

import json
import tempfile

import numpy as np
import pytest

from elasticsearch_tpu.common.errors import IllegalArgumentError
from elasticsearch_tpu.index.engine import Engine
from elasticsearch_tpu.index.mapping import MapperService
from elasticsearch_tpu.ops import aggs as aggs_ops
from elasticsearch_tpu.ops import dispatch
from elasticsearch_tpu.search.agg_partials import (
    compute_partial_aggs, finalize_aggs, merge_partial_aggs,
)
from elasticsearch_tpu.search.agg_plan import AggEngine, CostRouter
from elasticsearch_tpu.search.aggregations import compute_aggs
from elasticsearch_tpu.search.queries import SearchContext

MAPPING = {"properties": {
    "cat": {"type": "keyword"},
    "sub": {"type": "keyword"},
    "tags": {"type": "keyword"},
    "v": {"type": "long"},
    "price": {"type": "double"},
    "ts": {"type": "date"},       # weekly spread over ~7 years
    "ts_dst": {"type": "date"},   # hourly spread across DST transitions
}}

# 2019-01-01; weekly steps cross leap day 2020-02-29 and leap year 2024
TS0 = 1_546_300_800_000
# 2020-03-07; hourly steps cross the America/New_York spring-forward
# (2020-03-08 02:00) — and, offset by docs, the 2020-11-01 fall-back
DST0 = 1_583_550_000_000


def _index_docs(e, n=360):
    for i in range(n):
        doc = {"cat": ["red", "green", "blue"][i % 3],
               "sub": ["x", "y"][i % 2],
               "tags": ["a", "b"] if i % 5 == 0 else "c",
               "v": i,
               "ts": TS0 + i * 7 * 86_400_000,
               "ts_dst": DST0 + i * 3_600_000
               + (20_000_000_000 if i % 2 else 0)}
        if i % 7 != 0:
            doc["price"] = i * 0.5
        if i % 11 == 0:
            del doc["cat"]
        e.index(str(i), doc)
    e.refresh()


@pytest.fixture(scope="module")
def ctx():
    e = Engine(tempfile.mkdtemp() + "/shard", MapperService(MAPPING))
    _index_docs(e)
    yield SearchContext(e.acquire_searcher(), e.mapper_service)
    e.close()


@pytest.fixture()
def engine(ctx):
    return AggEngine(ctx.mapper_service)


def _rows(ctx, frac=3):
    rows = ctx.all_rows()
    return rows[rows % frac != 0]


def _json(x):
    return json.dumps(x, sort_keys=True, default=str)


# ---------------------------------------------------------------------------
# calendar intervals
# ---------------------------------------------------------------------------


CAL_SPECS = [
    {"d": {"date_histogram": {"field": "ts",
                              "calendar_interval": "month"}}},
    {"d": {"date_histogram": {"field": "ts",
                              "calendar_interval": "quarter",
                              "format": "yyyy-MM-dd"}}},
    {"d": {"date_histogram": {"field": "ts",
                              "calendar_interval": "year"}}},
    {"d": {"date_histogram": {"field": "ts",
                              "calendar_interval": "week"}}},
    # leap-year February boundaries under a real IANA zone
    {"d": {"date_histogram": {"field": "ts", "calendar_interval": "month",
                              "time_zone": "America/New_York"}}},
    # tz-shifted days across the spring-forward (23h day) and fall-back
    # (25h day) transitions: boundary table, not fixed 24h arithmetic
    {"d": {"date_histogram": {"field": "ts_dst",
                              "calendar_interval": "day",
                              "time_zone": "America/New_York"}}},
    {"d": {"date_histogram": {"field": "ts_dst",
                              "calendar_interval": "day",
                              "time_zone": "Europe/Berlin"}}},
    {"d": {"date_histogram": {"field": "ts_dst",
                              "calendar_interval": "hour",
                              "time_zone": "America/New_York"}}},
    # offset + sub-metrics ride the same boards as fixed intervals
    {"d": {"date_histogram": {"field": "ts", "calendar_interval": "month",
                              "offset": "+6h"},
           "aggs": {"s": {"stats": {"field": "v"}}}}},
]


@pytest.mark.parametrize("spec", CAL_SPECS)
def test_calendar_final_parity(ctx, engine, spec):
    rows = _rows(ctx)
    host = compute_aggs(ctx, rows, spec)
    got = engine.compute(ctx, rows, spec, partial=False)
    assert got is not None, "expected a device-eligible plan"
    dev, prof = got
    assert _json(dev) == _json(host)
    assert all(n["engine"].startswith("device") for n in prof["nodes"])


@pytest.mark.parametrize("spec", CAL_SPECS[:5])
def test_calendar_partial_parity(ctx, engine, spec):
    rows = ctx.all_rows()
    n = len(rows)
    splits = [rows[: n // 6], rows[n // 6: n // 2], rows[n // 2:]]
    hp = [compute_partial_aggs(ctx, r, spec) for r in splits]
    hm = hp[0]
    for p in hp[1:]:
        hm = merge_partial_aggs(hm, p, spec)
    dp = []
    for r in splits:
        got = engine.compute(ctx, r, spec, partial=True)
        assert got is not None
        dp.append(got[0])
    dm = dp[0]
    for p in dp[1:]:
        dm = merge_partial_aggs(dm, p, spec)
    assert _json(finalize_aggs(dm, spec)) == _json(finalize_aggs(hm, spec))


def test_calendar_empty_match_set(ctx, engine):
    rows = np.zeros(0, dtype=np.int64)
    for spec in CAL_SPECS[:3]:
        host = compute_aggs(ctx, rows, spec)
        got = engine.compute(ctx, rows, spec, partial=False)
        assert got is not None
        assert _json(got[0]) == _json(host)


# ---------------------------------------------------------------------------
# composite sub-agg trees
# ---------------------------------------------------------------------------


TREE_SPECS = [
    # 2-level: terms > terms with metric leaves at both depths
    {"t": {"terms": {"field": "cat"},
           "aggs": {"mx": {"max": {"field": "v"}},
                    "by_sub": {"terms": {"field": "sub"},
                               "aggs": {"s": {"stats": {"field": "v"}}}}}}},
    # 3-level: terms > terms > histogram, metric at the leaf
    {"t": {"terms": {"field": "cat"},
           "aggs": {"by_sub": {"terms": {"field": "sub"},
                               "aggs": {"h": {"histogram": {
                                   "field": "v", "interval": 100},
                                   "aggs": {"m": {"min": {
                                       "field": "price"}}}}}}}}},
    # calendar child under a terms parent (boundary table inside a tree)
    {"t": {"terms": {"field": "cat"},
           "aggs": {"q": {"date_histogram": {
               "field": "ts", "calendar_interval": "quarter"}}}}},
    # min_doc_count: 0 at BOTH levels — zero-count parents still emit
    # their children's full zero-count universe
    {"t": {"terms": {"field": "cat", "min_doc_count": 0},
           "aggs": {"by_sub": {"terms": {"field": "sub",
                                         "min_doc_count": 0}}}}},
    # missing-bucket parent merges lanes before children decompose
    {"t": {"terms": {"field": "cat", "missing": "zzz"},
           "aggs": {"by_sub": {"terms": {"field": "sub"},
                               "aggs": {"c": {"value_count": {
                                   "field": "v"}}}}}}},
    # histogram parent with terms child + extended_bounds gap buckets
    {"h": {"histogram": {"field": "v", "interval": 120,
                         "extended_bounds": {"min": -120, "max": 600}},
           "aggs": {"by_sub": {"terms": {"field": "sub"}}}}},
    # meta on a tree node (final mode attaches it at the top level)
    {"t": {"terms": {"field": "cat"}, "meta": {"who": "dash"},
           "aggs": {"by_sub": {"terms": {"field": "sub"}}}}},
]


@pytest.mark.parametrize("spec", TREE_SPECS)
def test_tree_final_parity(ctx, engine, spec):
    rows = _rows(ctx)
    host = compute_aggs(ctx, rows, spec)
    got = engine.compute(ctx, rows, spec, partial=False)
    assert got is not None, "expected a device-eligible plan"
    dev, prof = got
    assert _json(dev) == _json(host)
    assert all(n["engine"].startswith("device") for n in prof["nodes"])


def test_tree_empty_parent_buckets(ctx, engine):
    """Rows filtered so one whole cat value has zero matches: its parent
    bucket (min_doc_count: 0) must still carry the children's zero-count
    universes, exactly like the host's empty-rows recursion."""
    rows = ctx.all_rows()
    rows = rows[rows % 3 != 0]  # cat 'red' rides i % 3 == 0 docs only
    spec = {"t": {"terms": {"field": "cat", "min_doc_count": 0},
                  "aggs": {"by_sub": {"terms": {"field": "sub",
                                                "min_doc_count": 0},
                                      "aggs": {"s": {"stats": {
                                          "field": "v"}}}}}}}
    host = compute_aggs(ctx, rows, spec)
    got = engine.compute(ctx, rows, spec, partial=False)
    assert got is not None
    assert _json(got[0]) == _json(host)


def test_tree_partial_parity(ctx, engine):
    rows = ctx.all_rows()
    n = len(rows)
    splits = [rows[: n // 8], rows[n // 8: n // 2], rows[n // 2:]]
    for spec in TREE_SPECS[:4]:
        hp = [compute_partial_aggs(ctx, r, spec) for r in splits]
        hm = hp[0]
        for p in hp[1:]:
            hm = merge_partial_aggs(hm, p, spec)
        dp = []
        for r in splits:
            got = engine.compute(ctx, r, spec, partial=True)
            assert got is not None
            dp.append(got[0])
        dm = dp[0]
        for p in dp[1:]:
            dm = merge_partial_aggs(dm, p, spec)
        assert _json(finalize_aggs(dm, spec)) == \
            _json(finalize_aggs(hm, spec))


def test_tree_too_deep_falls_back(ctx, engine):
    spec = {"t": {"terms": {"field": "cat"}, "aggs": {
        "l2": {"terms": {"field": "sub"}, "aggs": {
            "l3": {"histogram": {"field": "v", "interval": 100}, "aggs": {
                "l4": {"terms": {"field": "sub"}}}}}}}}}
    rows = _rows(ctx)
    host = compute_aggs(ctx, rows, spec)
    got = engine.compute(ctx, rows, spec, partial=False)
    if got is not None:
        assert _json(got[0]) == _json(host)
    assert "tree_too_deep" in engine.plan_for(spec).nodes["t"].host_reason


# ---------------------------------------------------------------------------
# HLL cardinality
# ---------------------------------------------------------------------------


CARD_SPECS = [
    {"c": {"cardinality": {"field": "cat"}}},
    {"c": {"cardinality": {"field": "v"}}},
    {"c": {"cardinality": {"field": "cat", "missing": "none"}}},
    {"t": {"terms": {"field": "cat"},
           "aggs": {"cd": {"cardinality": {"field": "sub"}},
                    "cv": {"cardinality": {"field": "v"}}}}},
    {"d": {"date_histogram": {"field": "ts", "calendar_interval": "year"},
           "aggs": {"cd": {"cardinality": {"field": "cat"}}}}},
]


@pytest.mark.parametrize("spec", CARD_SPECS)
def test_cardinality_final_parity(ctx, engine, spec):
    rows = _rows(ctx)
    host = compute_aggs(ctx, rows, spec)
    got = engine.compute(ctx, rows, spec, partial=False)
    assert got is not None, "expected a device-eligible plan"
    dev, prof = got
    assert _json(dev) == _json(host)
    assert all(n["engine"].startswith("device") for n in prof["nodes"])


def test_hll_merge_parity_skewed_splits(ctx, engine):
    """Device HLL register boards pack into `$p` states byte-identical
    to the host's, so merge_partial_aggs composes device and host
    partials interchangeably — including tiny and lopsided shards."""
    rows = ctx.all_rows()
    n = len(rows)
    for cuts in ([5, 20], [1, n - 1], [n // 10, n // 2]):
        splits = np.split(rows, cuts)
        for spec in CARD_SPECS:
            hp = [compute_partial_aggs(ctx, r, spec) for r in splits]
            dp = []
            for r in splits:
                got = engine.compute(ctx, r, spec, partial=True)
                assert got is not None
                dp.append(got[0])
            # cross-merge: host state folded into device state
            hm, dm = hp[0], dp[0]
            for p in hp[1:]:
                hm = merge_partial_aggs(hm, p, spec)
            for p in dp[1:]:
                dm = merge_partial_aggs(dm, p, spec)
            assert _json(dp[0]) == _json(hp[0])  # states, not just finals
            assert _json(finalize_aggs(dm, spec)) == \
                _json(finalize_aggs(hm, spec))


def test_cardinality_negative_precision_raises_like_host(ctx, engine):
    spec = {"c": {"cardinality": {"field": "cat",
                                  "precision_threshold": -1}}}
    rows = _rows(ctx)
    with pytest.raises(IllegalArgumentError, match="precisionThreshold"):
        compute_aggs(ctx, rows, spec)
    with pytest.raises(IllegalArgumentError, match="precisionThreshold"):
        engine.compute(ctx, rows, spec, partial=False)


# ---------------------------------------------------------------------------
# cost router + fallback-stat satellites
# ---------------------------------------------------------------------------


def test_cost_router_prior_routes_tiny_corpus_host():
    r = CostRouter()
    # 100 matched docs: host walker estimate beats the fixed dispatch
    # floor even with margin — prior routes host
    assert r.decide("terms", 100, 1024) == "host"
    # huge corpus: device wins on the prior
    assert r.decide("terms", 1_000_000, 1 << 20) == "device"


def test_cost_router_measurements_flip_decision():
    r = CostRouter()
    # measured: device is 10x faster than the host walker at this size
    for _ in range(8):
        r.observe_device("terms", 50_000)
        r.observe_host("terms", 500_000, 100)
    assert r.decide("terms", 100, 1024) == "device"
    # measured the other way: host wins, device only via reprobe cadence
    for _ in range(32):
        r.observe_device("hist", 5_000_000)
        r.observe_host("hist", 100_000, 1_000)
    decisions = [r.decide("hist", 1_000, 1024) for _ in range(CostRouter.REPROBE)]
    assert "probe" in decisions
    assert decisions.count("host") == CostRouter.REPROBE - 1
    snap = r.snapshot()
    assert "hist" in snap["device_ns"] and "hist" in snap["host_ns_per_doc"]


def test_cost_router_persists_and_restores_ewmas(tmp_path):
    """PR 19 leftover: learned EWMAs are durable. Every observation
    snapshots to disk; a fresh router on the same path boots with the
    tables (counted in `restores`) instead of cold priors."""
    path = str(tmp_path / "agg_router.json")
    r = CostRouter(persist_path=path)
    assert r.restores == 0                        # nothing to seed yet
    for _ in range(8):
        r.observe_device("terms", 50_000)
        r.observe_host("terms", 500_000, 100)
    snap = r.snapshot()

    r2 = CostRouter(persist_path=path)            # "restart"
    assert r2.restores == 2                       # one family, two tables
    assert r2.snapshot() == snap
    # the measured flip survives the restart: 100 docs would route host
    # on priors, but the restored model knows the device is faster here
    assert r2.decide("terms", 100, 1024) == "device"


def test_cost_router_restart_round_trip_through_node(tmp_path):
    """Node-level: train the shared router, restart the node on the
    same data path, and find the seeded families in
    `_nodes/stats indices.aggs router_restores`."""
    from elasticsearch_tpu.node import Node

    data = str(tmp_path / "data")
    n = Node(data)
    router = n._agg_cost_router()
    for _ in range(4):
        router.observe_device("terms", 50_000)
        router.observe_host("terms", 500_000, 100)
    snap = router.snapshot()
    assert n.local_node_stats()["indices"]["aggs"]["router_restores"] == 0
    n.close()

    n2 = Node(data)
    try:
        r2 = n2._agg_cost_router()
        assert r2.snapshot() == snap
        stats = n2.local_node_stats()["indices"]["aggs"]
        assert stats["router_restores"] == 2
    finally:
        n2.close()


def test_cost_router_engine_counts_and_parity(ctx):
    engine = AggEngine(ctx.mapper_service, cost_router=True)
    rows = _rows(ctx)
    spec = {"t": {"terms": {"field": "cat"}}}
    host = compute_aggs(ctx, rows, spec)
    got = engine.compute(ctx, rows, spec, partial=False)
    # tiny corpus: the prior routes host — identical json either way,
    # and the decision is COUNTED with a reason
    assert got is not None
    assert _json(got[0]) == _json(host)
    assert engine.stats["router_host_routed"] >= 1
    ent = engine.stats["fallback_reasons"]["routed_host_cheaper"]
    assert ent["count"] >= 1 and ent["docs"] >= len(rows)


def test_fallback_reasons_carry_doc_totals(ctx, engine):
    rows = _rows(ctx)
    spec = {"t": {"terms": {"field": "tags"}}}  # multi-valued: host path
    host = compute_aggs(ctx, rows, spec)
    got = engine.compute(ctx, rows, spec, partial=False)
    if got is not None:
        assert _json(got[0]) == _json(host)
    ent = engine.stats["fallback_reasons"]["multi_valued_field"]
    assert ent == {"count": 1, "docs": len(rows)}


def test_cardinality_off_grid_records_observed(ctx, engine, monkeypatch):
    """The ordinal-count fallback reports the cardinality that busted
    the ladder, so grid growth is driven by observed field shapes."""
    monkeypatch.setattr(aggs_ops, "AGG_B_LADDER", (8,))
    rows = _rows(ctx)
    spec = {"t": {"terms": {"field": "v"}}}
    host = compute_aggs(ctx, rows, spec)
    got = engine.compute(ctx, rows, spec, partial=False)
    assert got is not None
    assert _json(got[0]) == _json(host)  # host fallback, identical json
    ent = engine.stats["fallback_reasons"]["cardinality_off_grid"]
    assert ent["observed_max"] > 8
    assert ent["docs"] == len(rows)


def test_warmup_ord_rungs_clamped(ctx, engine):
    """One pathological high-cardinality field must not AOT-warm the
    giant grid rungs: the ordinal warmup probe clamps at
    WARMUP_MAX_ORD_B."""
    col = engine.store.column(ctx.reader, "cat", want_ords=True)
    assert col.ord_keys
    col.ord_keys = [str(i) for i in range(40_000)]  # pretend: huge field
    entries = engine.store.warmup_entries(col)
    # since ISSUE 36 a level of ordinals is counted by the 32-bit program
    # (`aggs.ord_counts` and the one-level `aggs.tree_counts` went)
    ord_rungs = [st["n_buckets"][0] for name, _spec, st in entries
                 if name == "aggs.n32_counts" and st["levels"] == ("ords",)]
    assert ord_rungs
    assert max(ord_rungs) <= aggs_ops.WARMUP_MAX_ORD_B
    # the rung-2 kernels ride the same warmup grid: `cat` holds no number,
    # so a metric under its ordinals is the x64 tree program's
    names = {name for name, _spec, _st in entries}
    assert "aggs.tree_metric" in names


# ---------------------------------------------------------------------------
# closed grid: strict zero-recompile second pass (single-device)
# ---------------------------------------------------------------------------


def test_strict_zero_recompile_second_pass_rung2(ctx, engine):
    rows = _rows(ctx)
    spec = {"cal": {"date_histogram": {"field": "ts",
                                       "calendar_interval": "month"}},
            "tree": {"terms": {"field": "cat"},
                     "aggs": {"by_sub": {"terms": {"field": "sub"},
                                         "aggs": {"s": {"stats": {
                                             "field": "v"}}}}}},
            "card": {"cardinality": {"field": "v"}}}
    engine.compute(ctx, rows, spec, partial=False)  # warm pass
    engine.compute(ctx, rows, spec, partial=True)   # warm the HLL boards
    before = dispatch.DISPATCH.compile_count()
    strict_before = dispatch.DISPATCH.strict
    dispatch.DISPATCH.strict = True
    try:
        got = engine.compute(ctx, rows, spec, partial=False)
        gp = engine.compute(ctx, rows, spec, partial=True)
    finally:
        dispatch.DISPATCH.strict = strict_before
    assert got is not None and gp is not None
    assert dispatch.DISPATCH.compile_count() == before


# ---------------------------------------------------------------------------
# SPMD mesh twins (the 8 virtual CPU devices conftest forces)
# ---------------------------------------------------------------------------


@pytest.mark.multidevice
class TestMeshRung2:
    def _mk(self, n=900):
        e = Engine(tempfile.mkdtemp() + "/shard", MapperService(MAPPING))
        _index_docs(e, n=n)  # 900 live rows -> 1024 row bucket: ragged
        ctx = SearchContext(e.acquire_searcher(), e.mapper_service)
        return e, ctx

    MESH_SPECS = [
        {"d": {"date_histogram": {"field": "ts",
                                  "calendar_interval": "quarter"}}},
        {"d": {"date_histogram": {"field": "ts_dst",
                                  "calendar_interval": "day",
                                  "time_zone": "America/New_York"}}},
        {"t": {"terms": {"field": "cat"},
               "aggs": {"by_sub": {"terms": {"field": "sub"},
                                   "aggs": {"s": {"stats": {
                                       "field": "v"}}}}}}},
        {"c": {"cardinality": {"field": "v"}}},
        {"t": {"terms": {"field": "cat"},
               "aggs": {"cd": {"cardinality": {"field": "sub"}}}}},
    ]

    def test_mesh_parity_rung2(self, mesh_serving):
        e, ctx = self._mk()
        try:
            engine = AggEngine(ctx.mapper_service)
            rows = _rows(ctx)
            for spec in self.MESH_SPECS:
                host = compute_aggs(ctx, rows, spec)
                got = engine.compute(ctx, rows, spec, partial=False)
                assert got is not None
                assert _json(got[0]) == _json(host)
            assert engine.stats["mesh_dispatches"] > 0
        finally:
            e.close()

    def test_mesh_partial_hll_states_merge_like_host(self, mesh_serving):
        e, ctx = self._mk()
        try:
            engine = AggEngine(ctx.mapper_service)
            rows = ctx.all_rows()
            splits = [rows[:100], rows[100:600], rows[600:]]
            spec = {"t": {"terms": {"field": "cat"},
                          "aggs": {"cd": {"cardinality": {
                              "field": "v"}}}}}
            hp = [compute_partial_aggs(ctx, r, spec) for r in splits]
            hm = hp[0]
            for p in hp[1:]:
                hm = merge_partial_aggs(hm, p, spec)
            dp = [engine.compute(ctx, r, spec, partial=True)[0]
                  for r in splits]
            dm = dp[0]
            for p in dp[1:]:
                dm = merge_partial_aggs(dm, p, spec)
            assert _json(finalize_aggs(dm, spec)) == \
                _json(finalize_aggs(hm, spec))
        finally:
            e.close()

    def test_mesh_strict_zero_recompile_second_pass(self, mesh_serving):
        e, ctx = self._mk()
        try:
            engine = AggEngine(ctx.mapper_service)
            rows = _rows(ctx)
            spec = {"cal": {"date_histogram": {
                        "field": "ts", "calendar_interval": "month"}},
                    "tree": {"terms": {"field": "cat"},
                             "aggs": {"by_sub": {"terms": {
                                 "field": "sub"}}}},
                    "card": {"cardinality": {"field": "v"}}}
            engine.compute(ctx, rows, spec, partial=False)  # warm
            before = dispatch.DISPATCH.compile_count()
            strict_before = dispatch.DISPATCH.strict
            dispatch.DISPATCH.strict = True
            try:
                got = engine.compute(ctx, rows, spec, partial=False)
            finally:
                dispatch.DISPATCH.strict = strict_before
            assert got is not None
            assert dispatch.DISPATCH.compile_count() == before
        finally:
            e.close()
