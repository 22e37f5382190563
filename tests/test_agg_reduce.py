"""Distributed aggregation reduce: partial states merged across skewed
shards must match single-shard ground truth (InternalAggregation.reduce,
SearchPhaseController.java:734 analog)."""

import math
import random

import numpy as np
import pytest

from elasticsearch_tpu.index.engine import Engine
from elasticsearch_tpu.index.mapping import MapperService
from elasticsearch_tpu.search.agg_partials import (
    _hll_estimate, _hll_from_values, _hll_merge, _td_from_values, _td_merge,
    _td_quantile, compute_partial_aggs, finalize_aggs, merge_partial_aggs,
)
from elasticsearch_tpu.search.aggregations import compute_aggs
from elasticsearch_tpu.search.queries import SearchContext

MAPPING = {
    "properties": {
        "cat": {"type": "keyword"},
        "name": {"type": "keyword"},
        "v": {"type": "double"},
        "w": {"type": "double"},
        "ts": {"type": "date"},
        "pt": {"type": "geo_point"},
    }
}


def _mk_docs():
    rng = random.Random(7)
    docs = []
    for i in range(240):
        docs.append({
            "cat": ["red", "green", "blue", "teal"][i % 4],
            "name": f"u{i % 37}",
            "v": float(i),
            "w": float(1 + (i % 5)),
            "ts": 1_600_000_000_000 + (i % 6) * 3_600_000,
            "pt": {"lat": rng.uniform(-60, 60), "lon": rng.uniform(-170, 170)},
        })
    return docs


@pytest.fixture(scope="module")
def ctx(tmp_path_factory):
    e = Engine(str(tmp_path_factory.mktemp("aggred") / "shard"),
               MapperService(MAPPING))
    for i, d in enumerate(_mk_docs()):
        e.index(str(i), d)
    e.refresh()
    yield SearchContext(e.acquire_searcher(), e.mapper_service)
    e.close()


def _skewed_split(ctx, parts=3):
    """Deliberately skewed row partition: contiguous value ranges, so every
    per-shard metric differs wildly from the global one."""
    rows = ctx.all_rows()
    n = len(rows)
    cut1, cut2 = n // 6, n // 2  # uneven sizes
    return [rows[:cut1], rows[cut1:cut2], rows[cut2:]]


def _reduce(ctx, splits, spec):
    partials = [compute_partial_aggs(ctx, rows, spec) for rows in splits]
    merged = partials[0]
    for p in partials[1:]:
        merged = merge_partial_aggs(merged, p, spec)
    return finalize_aggs(merged, spec)


def _assert_close(a, b, path="$"):
    if isinstance(a, dict):
        assert isinstance(b, dict) and set(a) == set(b), \
            f"{path}: keys {sorted(a)} != {sorted(b)}"
        for k in a:
            _assert_close(a[k], b[k], f"{path}.{k}")
    elif isinstance(a, list):
        assert isinstance(b, list) and len(a) == len(b), f"{path}: len differs"
        for i, (x, y) in enumerate(zip(a, b)):
            _assert_close(x, y, f"{path}[{i}]")
    elif isinstance(a, float) and isinstance(b, (int, float)):
        assert math.isclose(a, float(b), rel_tol=1e-6, abs_tol=1e-9), \
            f"{path}: {a} != {b}"
    else:
        assert a == b, f"{path}: {a!r} != {b!r}"


METRIC_SPECS = {
    "the_avg": {"avg": {"field": "v"}},
    "the_sum": {"sum": {"field": "v"}},
    "the_min": {"min": {"field": "v"}},
    "the_max": {"max": {"field": "v"}},
    "the_stats": {"stats": {"field": "v"}},
    "the_count": {"value_count": {"field": "v"}},
    "the_wavg": {"weighted_avg": {"value": {"field": "v"},
                                  "weight": {"field": "w"}}},
}


def test_exact_metrics_match_ground_truth(ctx):
    spec = METRIC_SPECS
    truth = compute_aggs(ctx, ctx.all_rows(), spec)
    got = _reduce(ctx, _skewed_split(ctx), spec)
    _assert_close(got, truth)


def test_extended_stats_match(ctx):
    spec = {"es": {"extended_stats": {"field": "v", "sigma": 3.0}}}
    truth = compute_aggs(ctx, ctx.all_rows(), spec)
    got = _reduce(ctx, _skewed_split(ctx), spec)
    for k in ("count", "min", "max", "avg", "sum", "sum_of_squares"):
        assert math.isclose(got["es"][k], truth["es"][k], rel_tol=1e-9)
    assert math.isclose(got["es"]["variance"], truth["es"]["variance"],
                        rel_tol=1e-6)
    assert math.isclose(got["es"]["std_deviation_bounds"]["upper"],
                        truth["es"]["std_deviation_bounds"]["upper"],
                        rel_tol=1e-6)


def test_cardinality_across_shards(ctx):
    # 37 distinct names spread across all three skewed splits: per-shard
    # cardinalities sum to far more than 37, the merged HLL must not
    spec = {"names": {"cardinality": {"field": "name"}}}
    got = _reduce(ctx, _skewed_split(ctx), spec)
    assert got["names"]["value"] == 37


def test_percentiles_and_mad_across_shards(ctx):
    spec = {
        "pct": {"percentiles": {"field": "v", "percents": [25, 50, 75, 99]}},
        "mad": {"median_absolute_deviation": {"field": "v"}},
        "box": {"boxplot": {"field": "v"}},
        "ranks": {"percentile_ranks": {"field": "v", "values": [60.0, 200.0]}},
    }
    truth = compute_aggs(ctx, ctx.all_rows(), spec)
    got = _reduce(ctx, _skewed_split(ctx), spec)
    # t-digest is exact below compression (240 values ≈ near-exact)
    for p in ("25.0", "50.0", "75.0", "99.0"):
        assert math.isclose(got["pct"]["values"][p], truth["pct"]["values"][p],
                            rel_tol=0.02, abs_tol=1.5), (p, got["pct"], truth["pct"])
    assert math.isclose(got["mad"]["value"], truth["mad"]["value"],
                        rel_tol=0.05, abs_tol=2.0)
    assert got["box"]["min"] == truth["box"]["min"]
    assert got["box"]["max"] == truth["box"]["max"]
    assert math.isclose(got["box"]["q2"], truth["box"]["q2"],
                        rel_tol=0.02, abs_tol=1.5)
    for t in ("60.0", "200.0"):
        assert math.isclose(got["ranks"]["values"][t],
                            truth["ranks"]["values"][t],
                            rel_tol=0.03, abs_tol=1.0)


def test_terms_with_sub_aggs_across_shards(ctx):
    # the round-1 bug: merged terms buckets added doc_count but kept the
    # FIRST shard's sub-agg values; with contiguous-range splits every
    # shard's per-bucket avg differs from the global per-bucket avg
    spec = {"cats": {"terms": {"field": "cat"},
                     "aggs": {"m": {"avg": {"field": "v"}},
                              "u": {"cardinality": {"field": "name"}}}}}
    truth = compute_aggs(ctx, ctx.all_rows(), spec)
    got = _reduce(ctx, _skewed_split(ctx), spec)
    t_buckets = {b["key"]: b for b in truth["cats"]["buckets"]}
    g_buckets = {b["key"]: b for b in got["cats"]["buckets"]}
    assert set(t_buckets) == set(g_buckets)
    for key, tb in t_buckets.items():
        gb = g_buckets[key]
        assert gb["doc_count"] == tb["doc_count"]
        assert math.isclose(gb["m"]["value"], tb["m"]["value"], rel_tol=1e-9), \
            f"bucket {key}: merged avg {gb['m']['value']} != {tb['m']['value']}"
        assert gb["u"]["value"] == tb["u"]["value"]


def test_terms_order_and_truncation(ctx):
    spec = {"cats": {"terms": {"field": "cat", "size": 2,
                               "order": {"m": "desc"}},
                     "aggs": {"m": {"avg": {"field": "v"}}}}}
    truth = compute_aggs(ctx, ctx.all_rows(), spec)
    got = _reduce(ctx, _skewed_split(ctx), spec)
    assert [b["key"] for b in got["cats"]["buckets"]] == \
        [b["key"] for b in truth["cats"]["buckets"]]
    assert got["cats"]["sum_other_doc_count"] == \
        truth["cats"]["sum_other_doc_count"]


def test_histogram_and_date_histogram(ctx):
    spec = {
        "h": {"histogram": {"field": "v", "interval": 50.0},
              "aggs": {"s": {"sum": {"field": "w"}}}},
        "dh": {"date_histogram": {"field": "ts", "fixed_interval": "1h"}},
    }
    truth = compute_aggs(ctx, ctx.all_rows(), spec)
    got = _reduce(ctx, _skewed_split(ctx), spec)
    _assert_close(got, truth)


def test_range_filters_composite(ctx):
    spec = {
        "r": {"range": {"field": "v",
                        "ranges": [{"to": 60.0}, {"from": 60.0, "to": 180.0},
                                   {"from": 180.0}]},
              "aggs": {"m": {"max": {"field": "w"}}}},
        "f": {"filters": {"filters": {
            "reds": {"term": {"cat": "red"}},
            "high": {"range": {"v": {"gte": 120}}}}},
            "aggs": {"a": {"avg": {"field": "v"}}}},
        "c": {"composite": {"size": 6, "sources": [
            {"cc": {"terms": {"field": "cat"}}}]}},
    }
    truth = compute_aggs(ctx, ctx.all_rows(), spec)
    got = _reduce(ctx, _skewed_split(ctx), spec)
    _assert_close(got, truth)


def test_geo_and_string_and_matrix(ctx):
    spec = {
        "gb": {"geo_bounds": {"field": "pt"}},
        "gc": {"geo_centroid": {"field": "pt"}},
        "ss": {"string_stats": {"field": "name"}},
        "mx": {"matrix_stats": {"fields": ["v", "w"]}},
    }
    truth = compute_aggs(ctx, ctx.all_rows(), spec)
    got = _reduce(ctx, _skewed_split(ctx), spec)
    assert math.isclose(got["gb"]["bounds"]["top_left"]["lat"],
                        truth["gb"]["bounds"]["top_left"]["lat"])
    assert math.isclose(got["gc"]["location"]["lat"],
                        truth["gc"]["location"]["lat"], rel_tol=1e-9)
    assert got["ss"]["count"] == truth["ss"]["count"]
    assert math.isclose(got["ss"]["entropy"], truth["ss"]["entropy"],
                        rel_tol=1e-6)
    tm = {f["name"]: f for f in truth["mx"]["fields"]}
    gm = {f["name"]: f for f in got["mx"]["fields"]}
    for f in tm:
        assert math.isclose(gm[f]["mean"], tm[f]["mean"], rel_tol=1e-9)
        assert math.isclose(gm[f]["variance"], tm[f]["variance"], rel_tol=1e-6)
        assert math.isclose(gm[f]["correlation"]["v"], tm[f]["correlation"]["v"],
                            rel_tol=1e-6)
        assert math.isclose(gm[f]["skewness"], tm[f]["skewness"],
                            rel_tol=1e-5, abs_tol=1e-9)


def test_pipeline_aggs_run_after_reduce(ctx):
    spec = {
        "h": {"histogram": {"field": "v", "interval": 60.0},
              "aggs": {"s": {"sum": {"field": "w"}},
                       "cum": {"cumulative_sum": {"buckets_path": "s"}}}},
        "avg_of_sums": {"avg_bucket": {"buckets_path": "h>s"}},
    }
    truth = compute_aggs(ctx, ctx.all_rows(), spec)
    got = _reduce(ctx, _skewed_split(ctx), spec)
    _assert_close(got, truth)


def test_single_bucket_kinds(ctx):
    spec = {
        "miss": {"missing": {"field": "nope"},
                 "aggs": {"c": {"value_count": {"field": "v"}}}},
        "filt": {"filter": {"term": {"cat": "blue"}},
                 "aggs": {"a": {"avg": {"field": "v"}}}},
    }
    truth = compute_aggs(ctx, ctx.all_rows(), spec)
    got = _reduce(ctx, _skewed_split(ctx), spec)
    _assert_close(got, truth)


# ---------------------------------------------------------------------------
# sketch unit tests
# ---------------------------------------------------------------------------


def test_hll_accuracy_and_merge():
    a = _hll_from_values(range(0, 60_000))
    b = _hll_from_values(range(40_000, 100_000))
    est = _hll_estimate(_hll_merge(a, b))
    assert abs(est - 100_000) / 100_000 < 0.05
    # sparse path
    s = _hll_from_values(range(100))
    assert "sparse" in s
    assert abs(_hll_estimate(s) - 100) <= 2


def test_tdigest_exact_when_small_and_merge_quantiles():
    rng = np.random.default_rng(3)
    vals = rng.normal(0, 100, size=5000)
    a = _td_from_values(vals[:1000])
    b = _td_from_values(vals[1000:])
    m = _td_merge(a, b)
    for q in (0.01, 0.25, 0.5, 0.75, 0.99):
        approx = _td_quantile(m, q)
        exact = float(np.quantile(vals, q))
        assert abs(approx - exact) < 12.0, (q, approx, exact)
    # small inputs are exact at the median
    small = _td_from_values(np.asarray([1.0, 2.0, 3.0, 4.0, 5.0]))
    assert abs(_td_quantile(small, 0.5) - 3.0) < 1e-9


def test_auto_date_histogram_with_sub_aggs(ctx):
    # coarsening at finalize must merge the still-partial sub-agg states
    # (regression: rebucketing finalized sub values raised ParsingError)
    spec = {"adh": {"auto_date_histogram": {"field": "ts", "buckets": 3},
                    "aggs": {"m": {"avg": {"field": "v"}}}}}
    got = _reduce(ctx, _skewed_split(ctx), spec)
    assert len(got["adh"]["buckets"]) <= 3
    total = sum(b["doc_count"] for b in got["adh"]["buckets"])
    assert total == len(ctx.all_rows())
    for b in got["adh"]["buckets"]:
        assert isinstance(b["m"]["value"], float)


def test_histogram_min_doc_count_no_shard_zero_fill(ctx):
    # min_doc_count>0 must not trigger dense shard-side zero-filling; the
    # threshold applies to MERGED counts (each shard alone is below 30
    # for some buckets the union keeps)
    spec = {"h": {"histogram": {"field": "v", "interval": 40.0,
                                "min_doc_count": 30}}}
    truth = compute_aggs(ctx, ctx.all_rows(), spec)
    got = _reduce(ctx, _skewed_split(ctx), spec)
    _assert_close(got, truth)


def test_terms_shard_size_bounds_candidates(ctx):
    from elasticsearch_tpu.search.agg_partials import _partial_spec
    s = _partial_spec("terms", {"field": "name", "size": 10})
    assert s["size"] == 25  # size*1.5+10, reference default
    s = _partial_spec("terms", {"field": "name", "size": 10, "shard_size": 99})
    assert s["size"] == 99
    s = _partial_spec("rare_terms", {"field": "name"})
    assert s["size"] == 1000 and s["max_doc_count"] > 1 << 50


def test_histogram_too_many_buckets_guard(ctx):
    from elasticsearch_tpu.common.errors import IllegalArgumentError
    with pytest.raises(IllegalArgumentError):
        compute_aggs(ctx, ctx.all_rows(),
                     {"h": {"histogram": {"field": "v", "interval": 0.00001,
                                          "min_doc_count": 0}}})


def test_scripted_metric_cross_shard_reduce(ctx):
    """scripted_metric: init/map/combine per shard (Painless), reduce at
    the coordinator over all shard states — the distributed result equals
    the single-pass ground truth (ScriptedMetricAggregator.java:38)."""
    spec = {"profit": {"scripted_metric": {
        "init_script": "state.vals = []",
        "map_script": "state.vals.add(doc['v'].value)",
        "combine_script":
            "double s = 0; for (t in state.vals) { s += t } return s",
        "reduce_script":
            "double s = 0; for (a in states) { s += a } return s"}}}
    single = compute_aggs(ctx, ctx.all_rows(), spec)
    distributed = _reduce(ctx, _skewed_split(ctx), spec)
    assert single["profit"]["value"] == sum(float(i) for i in range(240))
    _assert_close(distributed, single)


def test_scripted_metric_states_without_reduce(ctx):
    """No reduce_script: the states list itself comes back (one combined
    state per shard), matching InternalScriptedMetric's default."""
    spec = {"m": {"scripted_metric": {
        "init_script": "state.n = 0",
        "map_script": "state.n += 1",
        "combine_script": "return state.n"}}}
    distributed = _reduce(ctx, _skewed_split(ctx), spec)
    assert sorted(distributed["m"]["value"]) == sorted([40, 80, 120])


def test_scripted_metric_params_and_missing_map_script(ctx):
    spec = {"m": {"scripted_metric": {
        "init_script": "state.n = 0",
        "map_script": "state.n += params.step",
        "combine_script": "return state.n",
        "reduce_script":
            "double s = 0; for (a in states) { s += a } return s",
        "params": {"step": 2}}}}
    out = compute_aggs(ctx, ctx.all_rows(), spec)
    assert out["m"]["value"] == 480
    import pytest as _pytest
    from elasticsearch_tpu.common.errors import IllegalArgumentError
    with _pytest.raises(IllegalArgumentError, match="map_script"):
        compute_aggs(ctx, ctx.all_rows(),
                     {"m": {"scripted_metric": {"combine_script": "return 1"}}})


# ---------------------------------------------------------------------------
# auto_date_histogram as Elasticsearch 7.x answers it (`search/auto_date.py`)
# against the plain reference of `nyc-taxis-dash`, and date keys in the
# field's own format
# ---------------------------------------------------------------------------

def _taxi_reference():
    import json
    import os
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    from benchmark.kinds import taxi_aggs_reference
    with open(os.path.join(repo, "benchmark", "configs",
                           "nyc-taxis-dash.json")) as f:
        config = json.load(f)
    with open(os.path.join(repo, "benchmark", "traffic",
                           "taxi-aggs-steady.json")) as f:
        mix = json.load(f)
    # four weeks of rides, so dense that every day and hour holds some
    config["data"]["rides"]["span_days"] = 28
    return taxi_aggs_reference, config, mix


@pytest.fixture(scope="module")
def rides(tmp_path_factory):
    reference, config, mix = _taxi_reference()
    n = 4096
    corpus = reference.RideCorpus(2 ** 31 + 4310, config, n, mix)
    rows = corpus.rows([(b, 2048) for b in range(n // 2048)])
    e = Engine(str(tmp_path_factory.mktemp("rides") / "shard"),
               MapperService({"properties": {"dropoff_datetime": {
                   "type": "date", "format": "yyyy-MM-dd HH:mm:ss"}}}))
    for i, ms in enumerate(rows.dropoff.tolist()):
        e.index(str(i), {"dropoff_datetime": reference.stamp(ms)})
    e.refresh()
    yield (reference, rows, corpus.t0_ms,
           SearchContext(e.acquire_searcher(), e.mapper_service))
    e.close()


@pytest.mark.parametrize("width,interval", [(1, "3h"), (7, "12h"),
                                            (21, "7d")])
def test_auto_date_histogram_windows_against_the_reference(rides, width,
                                                           interval):
    """The walker and its partial reduce over three skewed shards both
    answer a 1-, 7- and 21-day window as the reference does: the
    rounding table, the merge from the first key, `interval` as "7d",
    keys in the field's `yyyy-MM-dd HH:mm:ss`."""
    reference, rows, t0, ctx = rides
    start = t0 + 3 * reference.DAY_MS
    want = rows.answer("autohisto_agg", start, width)["aggregations"]
    assert want["dropoffs_over_time"]["interval"] == interval
    matched = np.sort(ctx.all_rows()[rows.matching("autohisto_agg", start,
                                                   width)])
    spec = {"dropoffs_over_time": {"auto_date_histogram": {
        "field": "dropoff_datetime", "buckets": 20}}}
    assert compute_aggs(ctx, matched, spec) == want
    n = len(matched)
    # shards split in time: each alone chooses a finer rounding
    splits = [matched[:n // 6], matched[n // 6:n // 2], matched[n // 2:]]
    assert _reduce(ctx, splits, spec) == want


def test_auto_date_histogram_reduce_of_empty_and_coarser_shards(rides):
    reference, rows, t0, ctx = rides
    start = t0 + 2 * reference.DAY_MS
    matched = np.sort(ctx.all_rows()[rows.matching("autohisto_agg", start,
                                                   7)])
    spec = {"a": {"auto_date_histogram": {"field": "dropoff_datetime",
                                          "buckets": 20}}}
    empty = compute_partial_aggs(ctx, matched[:0], spec)
    assert empty["a"]["buckets"] == [] and empty["a"]["interval"] == "1s"
    assert finalize_aggs(empty, spec) == {"a": {"buckets": [],
                                                "interval": "1s"}}
    # a shard of one hour (minutes) merged with one of a week (hours)
    one_hour = matched[:8]
    parts = [compute_partial_aggs(ctx, rows_, spec)
             for rows_ in (one_hour, matched[8:], matched[:0])]
    assert parts[0]["a"]["interval"] in ("1s", "1m")
    assert parts[1]["a"]["interval"] == "1h"
    merged = merge_partial_aggs(merge_partial_aggs(parts[0], parts[1], spec),
                                parts[2], spec)
    assert merged["a"]["interval"] == "1h"
    assert finalize_aggs(merged, spec) == compute_aggs(ctx, matched, spec)


def test_date_keys_print_in_the_fields_own_format(rides):
    from elasticsearch_tpu.search.aggregations import field_date_format
    reference, rows, t0, ctx = rides
    mapper = ctx.mapper_service.get("dropoff_datetime")
    assert field_date_format(mapper) == "yyyy-MM-dd HH:mm:ss"
    # a named first format keeps ISO 8601 (`http-logs-dash`'s keys)
    named = MapperService({"properties": {"t": {
        "type": "date", "format": "strict_date_optional_time||epoch_second"},
        "u": {"type": "date"}}})
    assert field_date_format(named.get("t")) is None
    assert field_date_format(named.get("u")) is None
    start = t0 + 5 * reference.DAY_MS
    matched = np.sort(ctx.all_rows()[rows.matching("date_histogram_agg",
                                                   start, 7)])
    spec = {"d": {"date_histogram": {"field": "dropoff_datetime",
                                     "calendar_interval": "day"}}}
    want = {"d": rows.answer("date_histogram_agg", start, 7)[
        "aggregations"]["dropoffs_over_time"]}
    assert compute_aggs(ctx, matched, spec) == want
    # the shards hold days 1-2 and 5-7: the coordinator makes days 3 and 4
    window = rows.matching("date_histogram_agg", start, 7)
    day = (rows.dropoff[window] - start) // reference.DAY_MS
    gap = np.sort(ctx.all_rows()[window[(day < 2) | (day > 3)]])
    got = _reduce(ctx, [gap[:len(gap) // 3], gap[len(gap) // 3:]], spec)
    filled = [b for b in got["d"]["buckets"] if b["doc_count"] == 0]
    assert [b["key"] for b in filled] == [start + 2 * reference.DAY_MS,
                                          start + 3 * reference.DAY_MS]
    assert [b["key_as_string"] for b in filled] == [
        reference.stamp(start + 2 * reference.DAY_MS),
        reference.stamp(start + 3 * reference.DAY_MS)]
    # an aggregation's own format wins over the field's
    own = {"d": {"date_histogram": {"field": "dropoff_datetime",
                                    "calendar_interval": "day",
                                    "format": "dd/MM/yyyy"}}}
    first = compute_aggs(ctx, matched, own)["d"]["buckets"][0]
    assert first["key_as_string"] == reference.ddmmyyyy(start)
