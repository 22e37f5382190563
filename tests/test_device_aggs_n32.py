"""The 32-bit programs of the device aggregation engine (ISSUE 36:
`aggs.n32_counts`, `aggs.n32_metric` and their mesh twins in
`ops/aggs.py`; the choice and the widening in `search/agg_plan.py`).

A column whose values are integers and whose span fits keeps a 32-bit
resident form (`AggColumn.k32`); the programs over it count in int32,
sum in limbs and derive bucket ids from an int32 table of bounds. Held
here, beside `test_device_aggs*.py`, which hold BOTH forms to the host
walker over their own corpora:

  * the traps: a sum past 2^24 (a float32 accumulator) and past 2^31 (an
    int32 one), negative values, a `missing` substitute on the key and
    on the value, an interval that does not divide the column's unit;
  * the limb width at every row bucket the grid admits, as a bound and
    not as a sample;
  * a column the 32-bit form cannot hold (fractions, a span past 2^31
    units) takes the x64 program and gives the same answer;
  * the mesh twins on the conftest's virtual devices;
  * both ways to fill a board (`board_form`) agree with numpy.

The four panels of `http-logs-dash` at 32,768 rows are in
`test_dash_aggs_http_logs.py`, over that file's corpus.
"""

import json
import tempfile

import numpy as np
import pytest

from elasticsearch_tpu.index.engine import Engine
from elasticsearch_tpu.index.mapping import MapperService
from elasticsearch_tpu.ops import aggs as aggs_ops
from elasticsearch_tpu.search.agg_plan import AggEngine
from elasticsearch_tpu.search.aggregations import compute_aggs
from elasticsearch_tpu.search.queries import SearchContext
from elasticsearch_tpu.telemetry import metrics

MAPPING = {"properties": {
    "cat": {"type": "keyword"}, "big": {"type": "long"},
    "neg": {"type": "long"}, "frac": {"type": "double"},
    "wide": {"type": "long"}, "ts": {"type": "date"},
    "sec": {"type": "date", "format": "epoch_second"},
}}
N_DOCS = 700
T0_MS = 1_600_000_000_000


def _index_docs(e, n=N_DOCS):
    for i in range(n):
        doc = {"cat": ("red", "green", "blue")[i % 3],
               # 700 values near 10^7: a bucket's sum passes 2^24, the
               # whole passes 2^31
               "big": 9_000_000 + 4099 * i,
               "frac": 0.25 + 1.5 * i,
               # a span of 2^33 units of 1: past what an int32 holds
               "wide": (i % 5) * (1 << 31) + i,
               "ts": T0_MS + (i % 50) * 1_800_000,
               "sec": 1_600_000_000 + 977 * i}
        if i % 4:
            doc["neg"] = 7 * i - 2000       # -1993 .. 2886, a few absent
        if i % 9 == 0:
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


def _rows(ctx):
    rows = ctx.all_rows()
    return rows[rows % 7 != 0]


def _json(x):
    return json.dumps(x, sort_keys=True, default=str)


def _programs():
    return (metrics.counter("aggs.programs.narrow").value,
            metrics.counter("aggs.programs.x64").value)


def _answer(ctx, engine, spec, rows=None):
    """(device answer, 32-bit programs, x64 programs), the answer held
    to the host walker's byte for byte."""
    rows = _rows(ctx) if rows is None else rows
    n0, x0 = _programs()
    got = engine.compute(ctx, rows, spec, partial=False)
    assert got is not None, "expected a device-eligible plan"
    n1, x1 = _programs()
    assert _json(got[0]) == _json(compute_aggs(ctx, rows, spec))
    assert engine.stats["host_nodes"] == 0, engine.stats
    return got[0], n1 - n0, x1 - x0


# ---------------------------------------------------------------------------
# (b), (c): the traps, every program 32-bit
# ---------------------------------------------------------------------------

NARROW_SPECS = {
    "a sum past 2^24 a bucket and 2^31 in all": {
        "t": {"terms": {"field": "cat"},
              "aggs": {"s": {"sum": {"field": "big"}}}},
        "all": {"sum": {"field": "big"}}},
    "stats and avg of negative values": {
        "t": {"terms": {"field": "cat", "missing": "none"},
              "aggs": {"s": {"stats": {"field": "neg"}},
                       "a": {"avg": {"field": "neg"}}}},
        "m": {"min": {"field": "neg"}}, "x": {"max": {"field": "neg"}},
        "c": {"value_count": {"field": "neg"}}},
    "a missing substitute on the value, past the greatest value": {
        # on `neg`'s lattice (7 * i - 2000 + 7): -1993 + 7 * 1000
        "s": {"stats": {"field": "neg", "missing": 5007}},
        "t": {"terms": {"field": "cat"},
              "aggs": {"s": {"sum": {"field": "neg", "missing": 100}}}}},
    "a missing substitute on the key": {
        "h": {"histogram": {"field": "neg", "interval": 500,
                            "missing": -4100, "min_doc_count": 0},
              "aggs": {"s": {"sum": {"field": "big"}},
                       "m": {"max": {"field": "neg", "missing": 107}}}}},
    "an interval the column's unit does not divide": {
        # `neg` is 7 * i - 2000: its unit is 7, the interval 25
        "h": {"histogram": {"field": "neg", "interval": 25, "offset": 3}}},
    "a date in whole seconds: its unit is 1000 ms": {
        "d": {"date_histogram": {"field": "sec", "fixed_interval": "1h"},
              "aggs": {"s": {"sum": {"field": "big"}}}},
        "c": {"date_histogram": {"field": "sec",
                                 "calendar_interval": "day",
                                 "time_zone": "America/New_York"}}},
    "a two-level tree with a sum at its leaves": {
        "d": {"date_histogram": {"field": "ts", "fixed_interval": "3h"},
              "aggs": {"t": {"terms": {"field": "cat", "missing": "x"},
                             "aggs": {"s": {"sum": {"field": "big"}},
                                      "n": {"stats": {"field": "neg"}}}}}}},
    "a tree whose widest level is the second": {
        "t": {"terms": {"field": "cat"},
              "aggs": {"d": {"date_histogram": {"field": "ts",
                                                "fixed_interval": "30m"},
                             "aggs": {"s": {"sum": {"field": "neg"}}}}}}},
}


@pytest.mark.parametrize("case", sorted(NARROW_SPECS))
def test_a_trap_is_answered_exactly_by_32_bit_programs(ctx, engine, case):
    got, narrow, x64 = _answer(ctx, engine, NARROW_SPECS[case])
    assert narrow > 0 and x64 == 0, (narrow, x64)
    if case.startswith("a sum past"):
        assert got["all"]["value"] > 2 ** 31
        assert all(b["s"]["value"] > 2 ** 24 for b in got["t"]["buckets"])
        # the control of `dash-aggs-steady`: the same sum in float32
        rows = _rows(ctx)
        big = 9_000_000 + 4099 * rows.astype(np.int64)
        assert float(np.cumsum(big.astype(np.float32))[-1]) \
            != got["all"]["value"] == float(big.sum())


def test_an_empty_match_and_every_row(ctx, engine):
    spec = dict(NARROW_SPECS["a two-level tree with a sum at its leaves"],
                **NARROW_SPECS["stats and avg of negative values"])
    for rows in (np.zeros(0, dtype=np.int64), ctx.all_rows()):
        _got, narrow, x64 = _answer(ctx, engine, spec, rows)
        assert narrow > 0 and x64 == 0


# ---------------------------------------------------------------------------
# (e): what the 32-bit form cannot hold keeps the x64 program
# ---------------------------------------------------------------------------

X64_SPECS = {
    # case -> (spec, 32-bit programs, x64 programs)
    "fractions under ordinals": (
        {"t": {"terms": {"field": "cat"},
               "aggs": {"m": {"max": {"field": "frac"}}}}}, 1, 1),
    "a key of fractions": (
        {"h": {"histogram": {"field": "frac", "interval": 100},
               "aggs": {"s": {"sum": {"field": "big"}}}}}, 0, 2),
    "a span past 2^31 units": (
        {"s": {"sum": {"field": "wide"}},
         "h": {"histogram": {"field": "wide", "interval": 2 ** 30}}}, 0, 2),
    "an interval of a fraction": (
        {"h": {"histogram": {"field": "neg", "interval": 12.5}}}, 0, 1),
    "a missing substitute off the column's lattice": (
        # `neg`'s unit is 7 from -1993: 5 is not on it
        {"s": {"stats": {"field": "neg", "missing": 5}}}, 0, 1),
    "a missing substitute under the least value": (
        {"s": {"stats": {"field": "neg", "missing": -2700}}}, 0, 1),
}


@pytest.mark.parametrize("case", sorted(X64_SPECS))
def test_what_does_not_fit_takes_the_x64_program(ctx, engine, case):
    spec, narrow, x64 = X64_SPECS[case]
    _got, n, x = _answer(ctx, engine, spec)
    assert (n, x) == (narrow, x64)


def test_the_choice_is_read_from_the_column(ctx, engine):
    col = {f: engine.store.column(ctx.reader, f)
           for f in ("big", "neg", "frac", "wide", "sec", "ts")}
    assert col["frac"].k32 is None and col["wide"].k32 is None
    assert (col["neg"].k_base, col["neg"].k_unit) == (-1993, 7)
    assert col["sec"].k_unit == 977_000 and col["ts"].k_unit == 1_800_000
    assert col["big"].k32.dtype == np.int32
    present = col["neg"].present
    assert (col["neg"].k32[~present] == -1).all()
    assert np.array_equal(
        col["neg"].k32[present].astype(np.int64) * 7 - 1993,
        col["neg"].vals[present].astype(np.int64))
    # a KEY needs whole numbers, not the sums' 2^53: the cell's
    # `@timestamp` (524,288 stamps near 9 * 10^11 ms) is past it
    stamps = aggs_ops.AggColumn("@timestamp")
    stamps.r_pad = 1 << 14
    stamps.vals = 893_894_400_000.0 + 1000.0 * np.arange(1 << 14)
    stamps.present = np.ones(1 << 14, dtype=bool)
    stamps.vmin, stamps.vmax = stamps.vals[0], stamps.vals[-1]
    assert float(np.abs(stamps.vals).sum()) > 2 ** 53
    stamps.build_k32(integral=True)
    assert stamps.k_unit == 1000 and stamps.k_max == (1 << 14) - 1
    assert col["neg"].to_k32(-2700) is None        # under the least
    assert col["neg"].to_k32(5) is None and col["neg"].to_k32(0.5) is None
    assert col["neg"].to_k32(-1993 + 7 * 12) == 12


# ---------------------------------------------------------------------------
# (d): no accumulator can overflow, by construction from the row bucket
# ---------------------------------------------------------------------------

def test_limb_width_at_every_row_bucket_the_grid_admits():
    top_rows = aggs_ops.N32_MAX_ROWS
    assert top_rows == 1 << 30
    r = 1
    while r <= top_rows:
        bits = aggs_ops.limb_bits(r)
        limb = (1 << bits) - 1
        assert 1 <= bits <= 8
        # every row the largest limb: the int32 accumulator, the psum of
        # the mesh twins among them (bounded by the WHOLE bucket)
        assert r * limb < 1 << 31
        # a tile's sum in float32, and a bf16 operand
        assert min(aggs_ops.ONEHOT_TILE, r) * limb < 1 << 24
        assert limb < 1 << 8
        # the limbs hold every value an int32 column can
        n = aggs_ops.n_limbs_for(aggs_ops.I32_MAX - 1, bits)
        assert n * bits >= 31 and (n - 1) * bits < 31
        statics = {"levels": ("ords",), "n_buckets": (8,),
                   "form": "onehot", "parts": ("sum",), "limb_bits": bits,
                   "n_limbs": n}
        sigs = [((r,), "bool", None)]
        assert aggs_ops._grid_n32(statics, sigs)
        if bits < 8:
            # one bit more would overflow: the grid refuses it
            assert r * ((1 << (bits + 1)) - 1) >= 1 << 31
            assert not aggs_ops._grid_n32(
                dict(statics, limb_bits=bits + 1), sigs)
        r *= 2
    assert aggs_ops.limb_bits(1 << 19) == 8 == aggs_ops.limb_bits(1 << 23)
    assert aggs_ops.limb_bits(1 << 24) == 7
    assert not aggs_ops._grid_n32(
        {"levels": ("ords",), "n_buckets": (8,), "form": "onehot"},
        [((2 * top_rows,), "bool", None)])


def test_a_sum_of_the_largest_values_a_column_can_hold():
    """Every row 2^31 - 2 in the rebased domain, all in one lane: each
    limb's accumulator holds rows x its largest value."""
    r = 1 << 14
    k = np.full(r, aggs_ops.I32_MAX - 1, dtype=np.int32)
    mask = np.ones(r, dtype=bool)
    bits = aggs_ops.limb_bits(r)
    n = aggs_ops.n_limbs_for(aggs_ops.I32_MAX - 1, bits)
    for form in ("onehot", "scatter"):
        board = np.asarray(aggs_ops._agg_n32_metric(
            mask, k, np.int32(-1), np.zeros(r, np.int32), levels=("ords",),
            n_buckets=(8,), parts=("sum", "min", "max"), limb_bits=bits,
            n_limbs=n, form=form)).astype(np.int64)
        total = sum(board[1 + j] << (bits * j) for j in range(n))
        assert total[1] == r * (aggs_ops.I32_MAX - 1) and board[0][1] == r
        assert board[1 + n][1] == board[2 + n][1] == aggs_ops.I32_MAX - 1
        assert total.sum() == total[1]


# ---------------------------------------------------------------------------
# the two ways to fill a board
# ---------------------------------------------------------------------------

def test_board_form_follows_the_shape():
    assert aggs_ops.board_form(2049, "bounds") == "onehot"
    assert aggs_ops.board_form(65537, "bounds") == "onehot"
    assert aggs_ops.board_form(8193, "ords") == "scatter"
    assert aggs_ops.board_form(8192, "ords") == "onehot"
    # four columns a row: the product is worth it four times as far
    assert aggs_ops.board_form(16385, "ords", cols=4) == "onehot"
    assert aggs_ops.board_form(65537, "ords", cols=4) == "scatter"


@pytest.mark.parametrize("levels,ks", [
    ((), ()), (("ords",), (8,)), (("bounds",), (64,)),
    (("bounds", "ords"), (64, 8)), (("ords", "bounds", "ords"), (8, 32, 16)),
], ids=["whole", "ords", "bounds", "bounds-ords", "three-levels"])
def test_both_forms_fill_the_same_board_as_numpy(levels, ks):
    rng = np.random.default_rng(36)
    r = 4096
    mask = rng.random(r) < 0.7
    mask[3500:] = False
    args, lanes = [], []
    for kind, k in zip(levels, ks):
        if kind == "ords":
            o = rng.integers(-1, k, r).astype(np.int32)
            args.append(o)
            lanes.append(o + 1)
        else:
            key = rng.integers(-1, 5001, r).astype(np.int32)
            table = np.full(k + 1, aggs_ops.I32_MAX, dtype=np.int32)
            table[0], table[1] = -1, 0
            table[2:k - 1] = np.sort(rng.choice(np.arange(1, 5000), k - 3,
                                                replace=False))
            args += [key, table]
            lanes.append(np.searchsorted(table, key, side="right") - 1)
    total = int(np.prod([k + 1 for k in ks])) if ks else 1
    flat = np.zeros(r, dtype=np.int64)
    for lane, k in zip(lanes, ks):
        flat = flat * (k + 1) + lane
    v = rng.integers(-1, 1 << 24, r).astype(np.int32)
    ok = mask & (v >= 0)
    bits, n = 8, 3
    want_sum = np.zeros(total, dtype=np.int64)
    np.add.at(want_sum, flat[ok], v[ok].astype(np.int64))
    for form in ("onehot", "scatter"):
        counts = np.asarray(aggs_ops._agg_n32_counts(
            mask, *args, levels=levels, n_buckets=ks, form=form))
        assert counts.dtype == np.int32
        assert np.array_equal(counts, np.bincount(flat[mask],
                                                  minlength=total))
        board = np.asarray(aggs_ops._agg_n32_metric(
            mask, v, np.int32(-1), *args, levels=levels, n_buckets=ks,
            parts=("sum",), limb_bits=bits, n_limbs=n,
            form=form)).astype(np.int64)
        assert board.shape == (1 + n, total)
        assert np.array_equal(board[0], np.bincount(flat[ok],
                                                    minlength=total))
        assert np.array_equal(
            sum(board[1 + j] << (bits * j) for j in range(n)), want_sum)


# ---------------------------------------------------------------------------
# (f): the mesh twins (the 8 virtual CPU devices conftest forces)
# ---------------------------------------------------------------------------

@pytest.mark.multidevice
@pytest.mark.parametrize("case", sorted(NARROW_SPECS))
def test_the_mesh_twins_answer_the_traps(mesh_serving, case):
    e = Engine(tempfile.mkdtemp() + "/shard", MapperService(MAPPING))
    _index_docs(e)              # 700 live rows -> 1,024: ragged shards
    mctx = SearchContext(e.acquire_searcher(), e.mapper_service)
    try:
        engine = AggEngine(mctx.mapper_service)
        _got, narrow, x64 = _answer(mctx, engine, NARROW_SPECS[case])
        assert narrow > 0 and x64 == 0
        assert engine.stats["mesh_dispatches"] > 0
    finally:
        e.close()
