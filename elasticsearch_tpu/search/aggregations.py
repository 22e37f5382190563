"""Aggregations: bucket, metric, and pipeline aggs over candidate rows.

Re-design of `search/aggregations/` (SURVEY.md §2.5, ~45k LoC): instead of
per-doc collector trees, every aggregation reduces **vectorized** over the
matching row set (numpy today; the partial-reduction shape is chosen so
per-shard partials can later batch onto the device and merge cross-shard
like `InternalAggregation.reduce`).

Buckets carry their row subsets so sub-aggregations recurse naturally.
Pipeline aggs post-process sibling/parent bucket outputs, mirroring
`search/aggregations/pipeline/`.
"""

from __future__ import annotations

import math
import re
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from elasticsearch_tpu.common.errors import (
    ArrayIndexOutOfBoundsError, IllegalArgumentError, ParsingError,
)
from elasticsearch_tpu.index.mapping import parse_date_millis
from elasticsearch_tpu.search.queries import SearchContext, parse_query

# ---------------------------------------------------------------------------
# value source helpers
# ---------------------------------------------------------------------------
#
# The hot path used to be a per-row `reader.get_doc_value` loop — a Python
# call plus a linear segment scan (`ShardReader.resolve`) per row, so a
# terms agg over 100k matched rows cost 100k interpreter round-trips. The
# columnar fast path below concatenates each segment's DocValuesColumn
# once per reader snapshot (cached on the reader instance; a refresh makes
# a new reader, invalidating implicitly) and turns every lookup into a
# vectorized searchsorted + gather. The device agg store
# (`ops/aggs.AggFieldStore`) builds its resident columns from the same
# per-segment columns.


def _reader_columnar(reader, field: str):
    """Dense numeric column over the reader's max_doc space (segment-major
    concat): (bases, sizes, offsets, vals f64, present bool) — or None
    when any segment's column isn't numeric (the caller loops)."""
    cache = reader.__dict__.setdefault("_agg_columnar", {})
    key = ("num", field)
    if key in cache:
        return cache[key]
    bases, sizes, offsets = [], [], []
    vals_parts, pres_parts = [], []
    total = 0
    ent = None
    numeric_ok = True
    for view in reader.views:
        seg = view.segment
        bases.append(seg.base)
        sizes.append(seg.num_docs)
        offsets.append(total)
        col = seg.doc_values.get(field)
        if col is None:
            vals_parts.append(np.full(seg.num_docs, np.nan,
                                      dtype=np.float64))
            pres_parts.append(np.zeros(seg.num_docs, dtype=bool))
        elif col.numeric is not None:
            v = col.numeric.copy()
            v[~col.present] = np.nan  # the documented absent-value shape
            vals_parts.append(v)
            pres_parts.append(col.present)
        else:
            numeric_ok = False
            break
        total += seg.num_docs
    if numeric_ok:
        ent = (np.asarray(bases, dtype=np.int64),
               np.asarray(sizes, dtype=np.int64),
               np.asarray(offsets, dtype=np.int64),
               np.concatenate(vals_parts) if vals_parts
               else np.zeros(0, dtype=np.float64),
               np.concatenate(pres_parts) if pres_parts
               else np.zeros(0, dtype=bool))
    cache[key] = ent
    return ent


def _reader_objects(reader, field: str):
    """Dense raw-value object column (same layout as _reader_columnar);
    always available — replaces the per-row resolve() scan."""
    cache = reader.__dict__.setdefault("_agg_columnar", {})
    key = ("obj", field)
    if key in cache:
        return cache[key]
    bases, sizes, offsets = [], [], []
    parts = []
    total = 0
    for view in reader.views:
        seg = view.segment
        bases.append(seg.base)
        sizes.append(seg.num_docs)
        offsets.append(total)
        col = seg.doc_values.get(field)
        arr = np.empty(seg.num_docs, dtype=object)
        if col is not None:
            for i, v in enumerate(col.values):
                arr[i] = v
        parts.append(arr)
        total += seg.num_docs
    ent = (np.asarray(bases, dtype=np.int64),
           np.asarray(sizes, dtype=np.int64),
           np.asarray(offsets, dtype=np.int64),
           np.concatenate(parts) if parts
           else np.zeros(0, dtype=object))
    cache[key] = ent
    return ent


def _gather_positions(bases, sizes, offsets, rows):
    """rows (engine global) -> (dense positions, in-bounds mask)."""
    vi = np.searchsorted(bases, rows, side="right") - 1
    vi = np.clip(vi, 0, max(len(bases) - 1, 0))
    loc = rows - bases[vi]
    ok = (loc >= 0) & (loc < sizes[vi])
    return offsets[vi] + np.where(ok, loc, 0), ok


def numeric_values(ctx: SearchContext, rows: np.ndarray, field: str,
                   missing: Optional[float] = None) -> Tuple[np.ndarray, np.ndarray]:
    """(values float64[], present bool[]) for one field over rows.

    Multi-valued docs contribute their first value here; use all_values for
    per-value expansion (terms/cardinality need it).
    """
    field = ctx.mapper_service.resolve_field(field)
    rows = np.asarray(rows, dtype=np.int64)
    ent = _reader_columnar(ctx.reader, field) if len(rows) else None
    if ent is not None and len(ent[0]):
        bases, sizes, offsets, dvals, dpres = ent
        t, ok = _gather_positions(bases, sizes, offsets, rows)
        vals = np.where(ok, dvals[t], np.nan)
        present = ok & dpres[t]
        if missing is not None:
            vals[~present] = missing
            present = np.ones(len(rows), dtype=bool)
        return vals, present
    vals = np.full(len(rows), np.nan, dtype=np.float64)
    present = np.zeros(len(rows), dtype=bool)
    for i, row in enumerate(rows):
        v = ctx.reader.get_doc_value(field, int(row))
        if isinstance(v, list):
            v = v[0] if v else None
        if v is None:
            continue
        if isinstance(v, bool):
            v = 1.0 if v else 0.0
        if isinstance(v, (int, float)):
            vals[i] = float(v)
            present[i] = True
        elif isinstance(v, tuple):  # geo_point
            continue
    if missing is not None:
        vals[~present] = missing
        present[:] = True
    return vals, present


def all_values(ctx: SearchContext, rows: np.ndarray, field: str) -> List[Tuple[int, Any]]:
    """[(row_index, value)] expanded over multi-valued fields."""
    if field == "_index":
        name = getattr(ctx, "index_name", "index")
        return [(i, name) for i in range(len(rows))]
    field = ctx.mapper_service.resolve_field(field)
    rows = np.asarray(rows, dtype=np.int64)
    out: List[Tuple[int, Any]] = []
    ent = _reader_objects(ctx.reader, field) if len(rows) else None
    if ent is not None and len(ent[0]):
        bases, sizes, offsets, dobjs = ent
        t, ok = _gather_positions(bases, sizes, offsets, rows)
        taken = dobjs[t]
        for i in range(len(rows)):
            if not ok[i]:
                continue
            v = taken[i]
            if v is None:
                continue
            if isinstance(v, list):
                for item in v:
                    if item is not None:
                        out.append((i, item))
            else:
                out.append((i, v))
        return out
    for i, row in enumerate(rows):
        v = ctx.reader.get_doc_value(field, int(row))
        if v is None:
            continue
        if isinstance(v, list):
            for item in v:
                if item is not None:
                    out.append((i, item))
        else:
            out.append((i, v))
    return out


# ---------------------------------------------------------------------------
# metric aggregations
# ---------------------------------------------------------------------------

def _es_percentile(v_sorted: np.ndarray, p: float):
    """TDigest singleton-centroid quantile (TDigestState): centroid i sits at
    cumulative position i+0.5, extremes clamp to min/max — NOT numpy's
    linear-between-order-statistics interpolation."""
    n = len(v_sorted)
    if n == 0:
        return None
    if n == 1:
        return float(v_sorted[0])
    idx = p / 100.0 * n
    return float(np.interp(idx, np.arange(n) + 0.5, v_sorted))


def _metric_stats(vals: np.ndarray, present: np.ndarray) -> dict:
    v = vals[present]
    n = len(v)
    if n == 0:
        return {"count": 0, "min": None, "max": None, "avg": None, "sum": 0.0}
    return {"count": int(n), "min": float(v.min()), "max": float(v.max()),
            "avg": float(v.mean()), "sum": float(v.sum())}


def _extended_stats(vals: np.ndarray, present: np.ndarray, sigma: float = 2.0) -> dict:
    base = _metric_stats(vals, present)
    v = vals[present]
    if len(v) == 0:
        base.update({"sum_of_squares": None, "variance": None, "std_deviation": None,
                     "std_deviation_bounds": {"upper": None, "lower": None}})
        return base
    ss = float((v ** 2).sum())
    var = float(v.var())
    std = float(v.std())
    mean = base["avg"]
    base.update({
        "sum_of_squares": ss, "variance": var,
        "variance_population": var, "variance_sampling":
            float(v.var(ddof=1)) if len(v) > 1 else 0.0,
        "std_deviation": std,
        "std_deviation_bounds": {"upper": mean + sigma * std, "lower": mean - sigma * std},
    })
    return base


_NUMERIC_ONLY_METRICS = {
    "sum", "avg", "min", "max", "stats", "extended_stats", "percentiles",
    "percentile_ranks", "median_absolute_deviation", "weighted_avg",
}


def compute_metric(ctx: SearchContext, rows: np.ndarray, kind: str, spec: dict,
                   name: str = "") -> Any:
    if kind in _NUMERIC_ONLY_METRICS:
        mapper = ctx.mapper_service.get(spec.get("field", "")) \
            if spec.get("field") else None
        tname = getattr(mapper, "type_name", None)
        if tname in ("keyword", "text"):
            raise IllegalArgumentError(
                f"Field [{spec.get('field')}] of type [{tname}] is not "
                f"supported for aggregation [{kind}]")
    field = spec.get("field")
    missing = spec.get("missing")
    script = spec.get("script")

    if kind == "string_stats":
        return compute_string_stats(ctx, rows, spec)
    if kind == "top_metrics":
        return compute_top_metrics(ctx, rows, spec)
    if kind == "matrix_stats":
        return compute_matrix_stats(ctx, rows, spec)
    if kind == "scripted_metric":
        state = scripted_metric_map_combine(ctx, rows, spec)
        return {"value": scripted_metric_reduce(spec, [state])}

    if kind == "top_hits":
        return _top_hits(ctx, rows, spec)

    if kind == "value_count":
        if field is None:
            return {"value": len(rows)}
        values = all_values(ctx, rows, field)
        count = len(values)
        if missing is not None:
            count += len(rows) - len({i for i, _ in values})
        return {"value": count}

    if kind in ("geo_bounds", "geo_centroid"):
        pts = _gather_geo_points(ctx, rows, field)
        if not pts:
            return ({"bounds": None} if kind == "geo_bounds"
                    else {"count": 0})
        lats = np.asarray([p[1] for p in pts])
        lons = np.asarray([p[2] for p in pts])
        if kind == "geo_bounds":
            return {"bounds": {
                "top_left": {"lat": float(lats.max()), "lon": float(lons.min())},
                "bottom_right": {"lat": float(lats.min()),
                                 "lon": float(lons.max())}}}
        return {"location": {"lat": float(lats.mean()),
                             "lon": float(lons.mean())},
                "count": len(pts)}

    if kind == "cardinality":
        pt = spec.get("precision_threshold")
        if pt is not None and int(pt) < 0:
            raise IllegalArgumentError(
                f"[precisionThreshold] must be greater than or equal to 0. "
                f"Found [{int(pt)}] in [{name}]")
        values = all_values(ctx, rows, field)
        distinct = {_hashable(v) for _, v in values}
        if missing is not None and len({i for i, _ in values}) < len(rows):
            distinct.add(_hashable(missing))
        return {"value": len(distinct)}

    if script is not None and field is None:
        from elasticsearch_tpu.search.script_score import Script
        s = Script(script)
        vals = s.evaluate(ctx, rows, np.zeros(len(rows), dtype=np.float32)).astype(np.float64)
        present = np.ones(len(rows), dtype=bool)
    else:
        vals, present = numeric_values(ctx, rows, field, missing)

    if kind == "avg":
        v = vals[present]
        out = {"value": float(v.mean()) if len(v) else None}
        tname = getattr(ctx.mapper_service.get(field), "type_name", None) \
            if field else None
        if out["value"] is not None and tname in ("date", "date_nanos"):
            ms = out["value"] / 1e6 if tname == "date_nanos" \
                else out["value"]
            out["value_as_string"] = _millis_to_iso(int(round(ms)))
        return out
    if kind == "sum":
        return {"value": float(vals[present].sum())}
    if kind == "min":
        v = vals[present]
        return {"value": float(v.min()) if len(v) else None}
    if kind == "max":
        v = vals[present]
        return {"value": float(v.max()) if len(v) else None}
    if kind == "stats":
        return _metric_stats(vals, present)
    if kind == "extended_stats":
        sigma = float(spec.get("sigma", 2.0))
        if sigma < 0:
            raise IllegalArgumentError(
                f"[sigma] must be greater than or equal to 0. "
                f"Found [{sigma}] in [{name}]")
        return _extended_stats(vals, present, sigma)
    if kind == "median_absolute_deviation":
        v = vals[present]
        if len(v) == 0:
            return {"value": None}
        med = np.median(v)
        return {"value": float(np.median(np.abs(v - med)))}
    if kind == "percentiles":
        pcts = spec.get("percents", [1, 5, 25, 50, 75, 95, 99])
        tdigest = spec.get("tdigest")
        if tdigest is not None and "compression" in tdigest:
            comp = float(tdigest["compression"] or 0)
            if comp < 0:
                raise IllegalArgumentError(
                    f"[compression] must be greater than or equal to 0. "
                    f"Found [{comp}] in [{name}]")
        v = np.sort(vals[present])
        hdr = spec.get("hdr")
        if hdr is not None:
            if v.size and v[0] < 0:
                # DoubleHistogram cannot record negatives: the reference
                # fails the whole shard (AIOOBE out of the aggregator), so
                # the same query returns the same hits with or without the
                # hdr agg attached — never a silently filtered result set
                raise ArrayIndexOutOfBoundsError("out of covered value range")
            raw_digits = hdr.get("number_of_significant_value_digits", 3)
            try:
                digits = int(raw_digits)
            except (TypeError, ValueError):
                raise IllegalArgumentError(
                    "[numberOfSignificantValueDigits] must be between 0 and 5")
            if not 0 <= digits <= 5:
                raise IllegalArgumentError(
                    "[numberOfSignificantValueDigits] must be between 0 and 5")

        def _hdr_quantize(x: float) -> float:
            """DoubleHistogram highestEquivalentValue: the reported value is
            the top of x's equivalent bucket at the configured precision
            (sub-bucket count 2^ceil(log2(10^digits)); base unit auto-ranged
            from the smallest recorded magnitude)."""
            if x <= 0 or len(v) == 0:
                return float(x)
            sub = 1 << math.ceil(math.log2(10 ** max(digits, 1)))
            vmin = float(v[v > 0][0]) if (v > 0).any() else 1.0
            unit = 2.0 ** math.floor(math.log2(vmin)) / sub
            erange = max(2.0 ** math.floor(math.log2(x)) / sub, unit)
            lowest = math.floor(x / erange) * erange
            return lowest + erange - unit

        def one(p):
            if len(v) == 0:
                return None
            if hdr is not None:
                # HDRHistogram.getValueAtPercentile: highest equivalent
                # value of the bucket at the rank (round-half-up, no
                # interpolation)
                rank = max(int(math.floor(p / 100.0 * len(v) + 0.5)), 1)
                rank = min(rank, len(v))
                return _hdr_quantize(float(v[rank - 1]))
            return _es_percentile(v, float(p))

        if spec.get("keyed", True) is False:
            return {"values": [{"key": float(p), "value": one(float(p))}
                               for p in pcts]}
        return {"values": {f"{float(p)}": one(float(p)) for p in pcts}}
    if kind == "percentile_ranks":
        targets = spec.get("values", [])
        v = np.sort(vals[present])
        out = {}
        for t in targets:
            if len(v) == 0:
                out[f"{float(t)}"] = None
            else:
                out[f"{float(t)}"] = float(100.0 * np.searchsorted(v, t, side="right") / len(v))
        return {"values": out}
    if kind == "weighted_avg":
        vspec = spec.get("value", {})
        wspec = spec.get("weight", {})
        vv, vp = numeric_values(ctx, rows, vspec.get("field"), vspec.get("missing"))
        wv, wp = numeric_values(ctx, rows, wspec.get("field"), wspec.get("missing", 1.0))
        both = vp & wp
        den = wv[both].sum()
        return {"value": float((vv[both] * wv[both]).sum() / den) if den else None}
    if kind == "boxplot":
        # reference: x-pack/plugin/analytics BoxplotAggregator
        v = vals[present]
        if len(v) == 0:
            return {"min": None, "max": None, "q1": None, "q2": None,
                    "q3": None, "lower": None, "upper": None}
        q1, q2, q3 = (float(np.percentile(v, p)) for p in (25, 50, 75))
        iqr = q3 - q1
        inside = v[(v >= q1 - 1.5 * iqr) & (v <= q3 + 1.5 * iqr)]
        return {"min": float(v.min()), "max": float(v.max()),
                "q1": q1, "q2": q2, "q3": q3,
                "lower": float(inside.min()) if len(inside) else q1,
                "upper": float(inside.max()) if len(inside) else q3}
    raise ParsingError(f"unknown metric aggregation [{kind}]")


def _script_source(s) -> str:
    if isinstance(s, dict):
        return s.get("source") or s.get("inline") or ""
    return s or ""


def scripted_metric_map_combine(ctx: SearchContext, rows: np.ndarray,
                                spec: dict):
    """One shard's init → map → combine, returning the shippable state
    (reference ScriptedMetricAggregator.java:38: init_script seeds
    `state`, map_script runs per matched doc with `doc` values, and
    combine_script folds the shard state into whatever crosses the wire
    to the coordinator). Scripts run on the sandboxed Painless
    interpreter (script/painless.py) with the same `doc[...]` bindings as
    script_score."""
    from elasticsearch_tpu.script.painless import (
        FrozenParams, compile_painless, execute,
    )
    from elasticsearch_tpu.search.script_score import _ScalarDoc

    params = FrozenParams(spec.get("params") or {})
    state: Dict[str, Any] = {}
    bindings = {"state": state, "params": params}
    init = _script_source(spec.get("init_script"))
    if init:
        execute(compile_painless(init), dict(bindings))
    map_src = _script_source(spec.get("map_script"))
    if not map_src:
        raise IllegalArgumentError(
            "[map_script] must be provided in [scripted_metric]")
    prog = compile_painless(map_src)
    score_of = None
    if "_score" in map_src:
        # the reference's map_script sees each doc's real score; the query
        # phase stashes agg-scope scores on the context (service.py)
        srows = getattr(ctx, "agg_score_rows", None)
        if srows is not None:
            score_of = {int(r): float(s)
                        for r, s in zip(srows, ctx.agg_scores)}.get
    for row in rows:
        execute(prog, {**bindings, "doc": _ScalarDoc(ctx, int(row)),
                       "_score": score_of(int(row), 0.0)
                       if score_of else 0.0})
    combine = _script_source(spec.get("combine_script"))
    if combine:
        return execute(compile_painless(combine), dict(bindings))
    return state


def scripted_metric_reduce(spec: dict, states: list):
    """Coordinator reduce over every shard's combined state. Without a
    reduce_script the reference returns the raw states list."""
    from elasticsearch_tpu.script.painless import (
        FrozenParams, compile_painless, execute,
    )

    reduce_src = _script_source(spec.get("reduce_script"))
    if not reduce_src:
        return list(states)
    return execute(compile_painless(reduce_src),
                   {"states": list(states),
                    "params": FrozenParams(spec.get("params") or {})})


def compute_string_stats(ctx: SearchContext, rows: np.ndarray,
                         spec: dict) -> dict:
    """reference: x-pack/plugin/analytics StringStatsAggregator."""
    values = [str(v) for _, v in all_values(ctx, rows, spec.get("field"))]
    if not values:
        return {"count": 0, "min_length": None, "max_length": None,
                "avg_length": None, "entropy": 0.0}
    lengths = [len(v) for v in values]
    freq: Dict[str, int] = {}
    total_chars = 0
    for v in values:
        for ch in v:
            freq[ch] = freq.get(ch, 0) + 1
            total_chars += 1
    entropy = 0.0
    for c in freq.values():
        p = c / total_chars
        entropy -= p * math.log2(p)
    out = {"count": len(values), "min_length": min(lengths),
           "max_length": max(lengths),
           "avg_length": sum(lengths) / len(lengths),
           "entropy": round(entropy, 10)}
    if spec.get("show_distribution"):
        out["distribution"] = {ch: c / total_chars
                               for ch, c in sorted(freq.items())}
    return out


def compute_top_metrics(ctx: SearchContext, rows: np.ndarray,
                        spec: dict) -> dict:
    """reference: x-pack/plugin/analytics TopMetricsAggregator — the metric
    values of the top-N docs by a sort key."""
    metrics = spec.get("metrics", [])
    if isinstance(metrics, dict):
        metrics = [metrics]
    sort_spec = spec.get("sort", [{"_doc": "asc"}])
    if isinstance(sort_spec, (str, dict)):
        sort_spec = [sort_spec]
    size = int(spec.get("size", 1))
    entry = sort_spec[0]
    if isinstance(entry, str):
        sort_field, order = entry, "asc"
    else:
        sort_field, order = next(iter(entry.items()))
        if isinstance(order, dict):
            order = order.get("order", "asc")
    if sort_field == "_doc":
        keys = rows.astype(np.float64)
        kp = np.ones(len(rows), dtype=bool)
    else:
        keys, kp = numeric_values(ctx, rows, sort_field)
    idx = np.nonzero(kp)[0]
    idx = idx[np.argsort(keys[idx], kind="stable")]
    if order == "desc":
        idx = idx[::-1]
    top = []
    for i in idx[:size]:
        row = int(rows[i])
        mvals = {}
        for m in metrics:
            mf = m.get("field")
            v = ctx.reader.get_doc_value(ctx.mapper_service.resolve_field(mf),
                                         row)
            if isinstance(v, list):
                v = v[0] if v else None
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                mvals[mf] = float(v)
            else:
                mvals[mf] = v
        top.append({"sort": [float(keys[i])], "metrics": mvals})
    return {"top": top}


def compute_matrix_stats(ctx: SearchContext, rows: np.ndarray,
                         spec: dict) -> dict:
    """reference: modules/aggs-matrix-stats MatrixStatsAggregator —
    per-field moments + pairwise covariance/correlation."""
    fields = spec.get("fields", [])
    cols = {}
    presents = {}
    for f in fields:
        cols[f], presents[f] = numeric_values(ctx, rows, f)
    # rows where every field is present (reference: listwise deletion)
    if fields:
        mask = np.logical_and.reduce([presents[f] for f in fields])
    else:
        mask = np.zeros(0, dtype=bool)
    n = int(mask.sum())
    if n == 0:
        return {"doc_count": 0, "fields": []}
    # one pass of per-field moments, then symmetric pairwise products
    stats = {}
    for f in fields:
        v = cols[f][mask]
        mean = float(v.mean())
        centered = v - mean
        var = float((centered ** 2).sum() / (n - 1)) if n > 1 else 0.0
        stats[f] = (mean, centered, var, math.sqrt(var))
    cov: Dict[str, Dict[str, float]] = {f: {} for f in fields}
    for i, f in enumerate(fields):
        for g in fields[i:]:
            c = float((stats[f][1] * stats[g][1]).sum() / (n - 1)) \
                if n > 1 else 0.0
            cov[f][g] = cov[g][f] = c
    out_fields = []
    for f in fields:
        mean, centered, var, sd = stats[f]
        skew = float(((centered / sd) ** 3).mean()) if sd else 0.0
        kurt = float(((centered / sd) ** 4).mean()) if sd else 0.0
        corr = {}
        for g in fields:
            sd_g = stats[g][3]
            corr[g] = (cov[f][g] / (sd * sd_g)) if sd and sd_g else (
                1.0 if f == g else 0.0)
        out_fields.append({"name": f, "count": n, "mean": mean,
                           "variance": var, "skewness": skew,
                           "kurtosis": kurt, "covariance": cov[f],
                           "correlation": corr})
    return {"doc_count": n, "fields": out_fields}


def _mix64(k: int) -> int:
    """hppc BitMixer.mix64 (David Stafford mix13 variant) — the
    reference's PartitionedLongFilter hash; returns a SIGNED 64-bit value
    so that Python's % matches Java's Math.floorMod."""
    m = 0xFFFFFFFFFFFFFFFF
    k &= m
    k = ((k ^ (k >> 32)) * 0x4CD6944C5CC20B6D) & m
    k = ((k ^ (k >> 29)) * 0xFC12C5B19D3259E9) & m
    k = k ^ (k >> 32)
    return k - (1 << 64) if k >= (1 << 63) else k


def _murmur3_x86_32(data: bytes, seed: int) -> int:
    """Lucene StringHelper.murmurhash3_x86_32 (signed int32 result) — the
    reference's PartitionedStringFilter hash (IncludeExclude seed 31)."""
    c1, c2 = 0xCC9E2D51, 0x1B873593
    h1 = seed & 0xFFFFFFFF
    rounded = len(data) & ~3
    for i in range(0, rounded, 4):
        k1 = (data[i] | (data[i + 1] << 8) | (data[i + 2] << 16)
              | (data[i + 3] << 24))
        k1 = (k1 * c1) & 0xFFFFFFFF
        k1 = ((k1 << 15) | (k1 >> 17)) & 0xFFFFFFFF
        k1 = (k1 * c2) & 0xFFFFFFFF
        h1 ^= k1
        h1 = ((h1 << 13) | (h1 >> 19)) & 0xFFFFFFFF
        h1 = (h1 * 5 + 0xE6546B64) & 0xFFFFFFFF
    k1 = 0
    tail = len(data) & 3
    if tail == 3:
        k1 ^= data[rounded + 2] << 16
    if tail >= 2:
        k1 ^= data[rounded + 1] << 8
    if tail >= 1:
        k1 ^= data[rounded]
        k1 = (k1 * c1) & 0xFFFFFFFF
        k1 = ((k1 << 15) | (k1 >> 17)) & 0xFFFFFFFF
        k1 = (k1 * c2) & 0xFFFFFFFF
        h1 ^= k1
    h1 ^= len(data)
    h1 ^= h1 >> 16
    h1 = (h1 * 0x85EBCA6B) & 0xFFFFFFFF
    h1 ^= h1 >> 13
    h1 = (h1 * 0xC2B2AE35) & 0xFFFFFFFF
    h1 ^= h1 >> 16
    return h1 - (1 << 32) if h1 >= (1 << 31) else h1


def _hashable(v):
    return tuple(v) if isinstance(v, (list, tuple)) else v


# ---------------------------------------------------------------------------
# bucket aggregations
# ---------------------------------------------------------------------------

BUCKET_AGGS = {"terms", "histogram", "date_histogram", "range", "date_range",
               "filters", "filter", "missing", "global", "composite",
               "significant_terms", "significant_text", "rare_terms",
               "sampler", "ip_range",
               "auto_date_histogram", "adjacency_matrix", "geohash_grid",
               "geotile_grid"}
METRIC_AGGS = {"avg", "sum", "min", "max", "stats", "extended_stats", "value_count",
               "cardinality", "percentiles", "percentile_ranks", "top_hits",
               "weighted_avg", "median_absolute_deviation", "geo_bounds",
               "geo_centroid", "boxplot", "string_stats", "top_metrics",
               "matrix_stats", "scripted_metric"}
PIPELINE_AGGS = {"avg_bucket", "max_bucket", "min_bucket", "sum_bucket",
                 "stats_bucket", "extended_stats_bucket", "percentiles_bucket",
                 "derivative", "cumulative_sum", "bucket_script",
                 "bucket_selector", "bucket_sort", "serial_diff", "moving_fn"}


def _parse_float_param(spec: dict, key: str, default: float,
                       agg_name: str) -> float:
    raw = spec.get(key, default)
    try:
        return float(raw)
    except (TypeError, ValueError):
        raise ParsingError(
            f"x_content_parse_exception: [{key}] failed to parse value "
            f"[{raw}] in [{agg_name}]")


def _parse_int_param(spec: dict, key: str, default: int,
                     agg_name: str) -> int:
    raw = spec.get(key, default)
    try:
        return int(raw)
    except (TypeError, ValueError):
        raise ParsingError(
            f"x_content_parse_exception: [{key}] failed to parse value "
            f"[{raw}] in [{agg_name}]")


def validate_aggs(aggs_spec: dict, field_type=None) -> None:
    """Builder-time parameter validation, applied before any shard work
    (reference: each AggregationBuilder validates in its constructor /
    parse, so errors surface even for zero-shard searches).
    `field_type(field) -> type_name or None` enables mapper-aware checks."""
    for name, spec in (aggs_spec or {}).items():
        if not isinstance(spec, dict):
            raise ParsingError(f"aggregation [{name}] must be an object")
        sub = spec.get("aggs") or spec.get("aggregations") or {}
        for kind, body in spec.items():
            if kind in ("aggs", "aggregations", "meta") \
                    or not isinstance(body, dict):
                continue
            if kind == "extended_stats":
                sigma = _parse_float_param(body, "sigma", 2.0, name)
                if sigma < 0:
                    raise IllegalArgumentError(
                        f"[sigma] must be greater than or equal to 0. "
                        f"Found [{sigma}] in [{name}]")
            if kind == "cardinality" and "precision_threshold" in body:
                pt = _parse_int_param(body, "precision_threshold", 0, name)
                if pt < 0:
                    raise IllegalArgumentError(
                        f"[precisionThreshold] must be greater than or "
                        f"equal to 0. Found [{pt}] in [{name}]")
            if kind == "percentiles":
                td = body.get("tdigest")
                if isinstance(td, dict) and "compression" in td:
                    comp = _parse_float_param(td, "compression", 100.0, name)
                    if comp < 0:
                        raise IllegalArgumentError(
                            f"[compression] must be greater than or equal "
                            f"to 0. Found [{comp}] in [{name}]")
                if "percents" in body:
                    pc = body["percents"]
                    if not isinstance(pc, list) or not pc:
                        raise IllegalArgumentError(
                            "[percents] must not be empty")
                    for p in pc:
                        try:
                            fp = float(p)
                        except (TypeError, ValueError):
                            raise ParsingError(
                                f"x_content_parse_exception: [percents] "
                                f"failed to parse [{p}]")
                        if not 0.0 <= fp <= 100.0:
                            raise IllegalArgumentError(
                                f"percent must be in [0,100], got [{fp}]")
                hdr = body.get("hdr")
                if isinstance(hdr, dict):
                    raw = hdr.get("number_of_significant_value_digits", 3)
                    try:
                        digits = int(raw)
                    except (TypeError, ValueError):
                        raise IllegalArgumentError(
                            "[numberOfSignificantValueDigits] must be "
                            "between 0 and 5")
                    if not 0 <= digits <= 5:
                        raise IllegalArgumentError(
                            "[numberOfSignificantValueDigits] must be "
                            "between 0 and 5")
            if kind == "median_absolute_deviation" \
                    and "compression" in body:
                comp = _parse_float_param(body, "compression", 1000.0, name)
                if comp <= 0:
                    raise IllegalArgumentError(
                        f"[compression] must be greater than 0. "
                        f"Found [{comp}] in [{name}]")
            if kind == "moving_fn":
                window = _parse_int_param(body, "window", 5, name) \
                    if body.get("window") is not None else 5
                if window <= 0:
                    raise IllegalArgumentError(
                        "[window] must be a positive, non-zero integer.")
            if kind == "filters" and not body.get("filters"):
                raise IllegalArgumentError("[filters] cannot be empty")
            if kind in ("significant_terms", "significant_text"):
                import difflib
                for k in body:
                    if k not in _SIG_KNOWN_FIELDS:
                        close = difflib.get_close_matches(
                            k, _SIG_KNOWN_FIELDS, n=1)
                        hint = f" did you mean [{close[0]}]?" if close else ""
                        raise ParsingError(
                            f"[{kind}] unknown field [{k}]{hint}")
            if kind in ("terms", "significant_terms", "significant_text",
                        "rare_terms"):
                inc, exc = body.get("include"), body.get("exclude")
                field = body.get("field", "")
                # regex include/exclude only applies to string fields; the
                # non-string check here mirrors ValuesSourceType guards for
                # the obvious field-name cases (ip/date/numeric suites)
                if isinstance(inc, str) or isinstance(exc, str):
                    tname = field_type(field) if field_type else None
                    if tname is not None and tname not in (
                            "keyword", "text", "wildcard",
                            "constant_keyword"):
                        raise IllegalArgumentError(
                            f"Aggregation [{name}] cannot support regular "
                            f"expression style include/exclude settings as "
                            f"they can only be applied to string fields. "
                            f"Use an array of values for include/exclude "
                            f"clauses")
        if sub:
            validate_aggs(sub, field_type)


def compute_aggs(ctx: SearchContext, rows: np.ndarray, aggs_spec: dict) -> dict:
    """Compute an aggregation tree over candidate rows."""
    out: Dict[str, Any] = {}
    pipelines: List[Tuple[str, str, dict]] = []
    for name, spec in (aggs_spec or {}).items():
        if not isinstance(spec, dict):
            raise ParsingError(f"aggregation [{name}] must be an object")
        sub = spec.get("aggs") or spec.get("aggregations") or {}
        kinds = [k for k in spec if k not in ("aggs", "aggregations", "meta")]
        if len(kinds) != 1:
            raise ParsingError(f"aggregation [{name}] must define exactly one type")
        kind = kinds[0]
        if kind in PIPELINE_AGGS:
            pipelines.append((name, kind, spec[kind]))
            continue
        if kind in METRIC_AGGS:
            out[name] = compute_metric(ctx, rows, kind, spec[kind], name=name)
        elif kind in BUCKET_AGGS or kind in ("nested", "reverse_nested"):
            # parent pipelines (cumulative_sum/derivative/... declared as
            # sub-aggs) run over the parent's bucket list after it's built
            sub_normal, sub_pipes = {}, []
            for sname, sspec in sub.items():
                skinds = [k for k in sspec if k not in ("aggs", "aggregations", "meta")]
                if len(skinds) == 1 and skinds[0] in PIPELINE_AGGS:
                    sub_pipes.append((sname, skinds[0], sspec[skinds[0]]))
                else:
                    sub_normal[sname] = sspec
            out[name] = _compute_bucket(ctx, rows, kind, spec[kind], sub_normal)
            for pname, pkind, pspec in sub_pipes:
                wrapper = {"__parent__": out[name]}
                pspec2 = dict(pspec)
                bp = pspec2.get("buckets_path")
                if isinstance(bp, str):
                    pspec2["buckets_path"] = "__parent__>" + bp
                elif isinstance(bp, dict):
                    pspec2["buckets_path"] = {k: "__parent__>" + v for k, v in bp.items()}
                res = _compute_pipeline(wrapper, pkind, pspec2, pname)
                if not (isinstance(res, dict) and "_applied" in res):
                    out[name].setdefault("__pipeline_results__", {})[pname] = res
        else:
            raise ParsingError(f"unknown aggregation type [{kind}]")
        if isinstance(spec.get("meta"), dict) and isinstance(out.get(name), dict):
            out[name]["meta"] = spec["meta"]
    for name, kind, spec in pipelines:
        res = _compute_pipeline(out, kind, spec, name)
        # in-place pipelines (derivative, cumulative_sum, bucket_script/
        # selector/sort) mutate parent buckets and emit no sibling output
        if not (isinstance(res, dict) and "_applied" in res):
            out[name] = res
    return out


def _bucketize(ctx, rows, sub_aggs, buckets: List[Tuple[Any, np.ndarray]],
               key_name: str = "key", recurse=None) -> List[dict]:
    recurse = recurse or compute_aggs
    out = []
    for key, brows in buckets:
        b = {key_name: key, "doc_count": int(len(brows))}
        if sub_aggs:
            b.update(recurse(ctx, brows, sub_aggs))
        out.append(b)
    return out


def _geohash_encode(lat: float, lon: float, precision: int) -> str:
    """Classic base-32 geohash (reference: Lucene Geohash/`geogrid` aggs)."""
    base32 = "0123456789bcdefghjkmnpqrstuvwxyz"
    lat_lo, lat_hi = -90.0, 90.0
    lon_lo, lon_hi = -180.0, 180.0
    bits = []
    even = True
    while len(bits) < precision * 5:
        if even:
            mid = (lon_lo + lon_hi) / 2
            if lon >= mid:
                bits.append(1)
                lon_lo = mid
            else:
                bits.append(0)
                lon_hi = mid
        else:
            mid = (lat_lo + lat_hi) / 2
            if lat >= mid:
                bits.append(1)
                lat_lo = mid
            else:
                bits.append(0)
                lat_hi = mid
        even = not even
    out = []
    for i in range(0, len(bits), 5):
        out.append(base32[int("".join(map(str, bits[i:i + 5])), 2)])
    return "".join(out)


def _geotile_encode(lat: float, lon: float, precision: int) -> str:
    """z/x/y map-tile key (reference: GeoTileUtils.longEncode)."""
    import math as _m
    n = 2 ** precision
    x = int((lon + 180.0) / 360.0 * n)
    lat_r = _m.radians(max(min(lat, 85.05112878), -85.05112878))
    y = int((1.0 - _m.log(_m.tan(lat_r) + 1 / _m.cos(lat_r)) / _m.pi) / 2.0 * n)
    return f"{precision}/{min(max(x, 0), n - 1)}/{min(max(y, 0), n - 1)}"


def _gather_geo_points(ctx: SearchContext, rows: np.ndarray, field: str):
    pts = []
    for row in rows:
        v = ctx.reader.get_doc_value(field, int(row))
        if v is None:
            continue
        if isinstance(v, list) and v and isinstance(v[0], (list, tuple)):
            for p in v:
                pts.append((int(row), float(p[0]), float(p[1])))
        elif isinstance(v, (list, tuple)) and len(v) == 2:
            pts.append((int(row), float(v[0]), float(v[1])))
    return pts


def _compute_bucket(ctx: SearchContext, rows: np.ndarray, kind: str,
                    spec: dict, sub_aggs: dict, recurse=None) -> dict:
    """One bucket agg. `recurse` computes sub-agg trees over bucket rows —
    `compute_aggs` for final output, or the partial-mode walker
    (`agg_partials.compute_partial_aggs`) for the distributed reduce."""
    recurse = recurse or compute_aggs
    field = spec.get("field")

    # composite may only nest under `nested` (CompositeAggregationBuilder
    # rejects every other parent)
    if kind != "nested":
        for sname, sspec in (sub_aggs or {}).items():
            if isinstance(sspec, dict) and "composite" in sspec:
                raise IllegalArgumentError(
                    f"[composite] aggregation cannot be used with a parent "
                    f"aggregation of type: [{kind}]")

    if kind in ("geohash_grid", "geotile_grid"):
        default_prec = 5 if kind == "geohash_grid" else 7
        precision = int(spec.get("precision", default_prec))
        encode = _geohash_encode if kind == "geohash_grid" else _geotile_encode
        cells: Dict[str, List[int]] = {}
        for row, lat, lon in _gather_geo_points(ctx, rows, field):
            cells.setdefault(encode(lat, lon, precision), []).append(row)
        size = int(spec.get("size", 10000))
        buckets = []
        for key in sorted(cells, key=lambda k: (-len(cells[k]), k))[:size]:
            brows = np.asarray(sorted(set(cells[key])), dtype=np.int64)
            b = {"key": key, "doc_count": int(len(brows))}
            if sub_aggs:
                b.update(recurse(ctx, brows, sub_aggs))
            buckets.append(b)
        return {"buckets": buckets}

    if kind == "filter" or (kind == "filters" and False):
        q = parse_query(spec) if kind == "filter" else None
        match = q.execute(ctx).rows
        brows = rows[np.isin(rows, match)]
        b = {"doc_count": int(len(brows))}
        if sub_aggs:
            b.update(recurse(ctx, brows, sub_aggs))
        return b

    if kind == "filters":
        filters = spec.get("filters", {})
        if not filters:
            raise IllegalArgumentError("[filters] cannot be empty")
        named = isinstance(filters, dict)
        items = filters.items() if named else enumerate(filters)
        buckets = {} if named else []
        for key, qspec in items:
            match = parse_query(qspec).execute(ctx).rows
            brows = rows[np.isin(rows, match)]
            b = {"doc_count": int(len(brows))}
            if sub_aggs:
                b.update(recurse(ctx, brows, sub_aggs))
            if named:
                buckets[key] = b
            else:
                buckets.append(b)
        return {"buckets": buckets}

    if kind == "global":
        grows = ctx.all_rows()
        b = {"doc_count": int(len(grows))}
        if sub_aggs:
            b.update(recurse(ctx, grows, sub_aggs))
        return b

    if kind == "missing":
        if spec.get("missing") is not None:
            # a missing-value substitute means no doc is ever missing
            brows = rows[:0]
        else:
            vals = [ctx.reader.get_doc_value(field, int(r)) for r in rows]
            brows = rows[[v is None for v in vals]]
        b = {"doc_count": int(len(brows))}
        if sub_aggs:
            b.update(recurse(ctx, brows, sub_aggs))
        return b

    if kind in ("significant_terms", "significant_text"):
        return _compute_significant(ctx, rows, kind, spec, sub_aggs,
                                    recurse)

    if kind in ("terms", "rare_terms"):
        size = int(spec.get("size", 10))
        tname = getattr(ctx.mapper_service.get(field), "type_name", None) \
            if field else None
        # an unmapped field aggregates under the caller-declared value_type
        # (ValuesSourceConfig.resolve with a user value type)
        tname = tname or spec.get("value_type")

        def fmt_key(k):
            if tname == "ip":
                from elasticsearch_tpu.index.mapping import IpFieldMapper
                try:
                    return IpFieldMapper.format_value(int(k))
                except (ValueError, TypeError):
                    return k
            return k

        values = all_values(ctx, rows, field)
        missing_val = spec.get("missing")
        if missing_val is not None:
            # docs without the field bucket under the missing key, coerced
            # per the effective type (terms `missing` param)
            mv = missing_val
            if tname in ("date", "date_nanos") and isinstance(mv, str):
                try:
                    mv = parse_date_millis(mv)
                except Exception:
                    pass
            elif tname in ("long", "integer", "short", "byte"):
                try:
                    mv = int(mv)
                except (TypeError, ValueError):
                    raise ParsingError(
                        f"failed to parse [missing] value [{mv}] as a long")
            elif tname in ("double", "float", "half_float"):
                try:
                    mv = float(mv)
                except (TypeError, ValueError):
                    raise ParsingError(
                        f"failed to parse [missing] value [{mv}] as a double")
            have = {i for i, _ in values}
            values = values + [(i, mv) for i in range(len(rows))
                               if i not in have]
        groups: Dict[Any, List[int]] = {}
        for idx, v in values:
            groups.setdefault(_hashable(v), []).append(idx)
        mapper_t = ctx.mapper_service.get(field) if field else None
        _tn = getattr(mapper_t, "type_name", None)
        if (_tn == "keyword" or (_tn == "text"
                                 and (mapper_t.params or {})
                                 .get("fielddata"))) \
                and spec.get("execution_hint") != "map":
            # loading global ordinals materializes fielddata (the map hint
            # iterates values without building it)
            ctx.mapper_service.mark_fielddata_loaded(field)
        # include/exclude term filtering (IncludeExclude): exact-value lists,
        # a regex, or a {partition, num_partitions} hash partition
        inc, exc = spec.get("include"), spec.get("exclude")
        if isinstance(inc, dict):
            if exc is not None:
                raise IllegalArgumentError(
                    "Cannot specify any excludes when using a "
                    "partition-based include")
            part = int(inc.get("partition", 0))
            n_part = int(inc.get("num_partitions", 1))

            def _in_partition(k):
                if isinstance(k, bool):
                    h = _mix64(1 if k else 0)
                elif isinstance(k, (int, float)) and not isinstance(k, bool):
                    h = _mix64(int(k))
                else:
                    h = _murmur3_x86_32(str(k).encode("utf-8"), 31)
                return h % n_part == part  # Math.floorMod semantics
            groups = {k: i for k, i in groups.items() if _in_partition(k)}
            inc = None
        if inc is not None or exc is not None:
            def _coerce_list(entries):
                # list entries compare in the field's keyspace: date
                # strings parse to millis (DocValueFormat round-trip)
                out = set()
                for x in entries:
                    if tname in ("date", "date_nanos"):
                        try:
                            out.add(str(parse_date_millis(x)))
                            continue
                        except Exception:
                            pass
                    out.add(str(x))
                return out
            inc_set = _coerce_list(inc) if isinstance(inc, list) else None
            exc_set = _coerce_list(exc) if isinstance(exc, list) else None

            def _passes(k):
                ks = str(fmt_key(k))
                if isinstance(k, float) and k == int(k):
                    ks = str(int(k))
                if inc_set is not None and ks not in inc_set:
                    return False
                if isinstance(inc, str) and not re.fullmatch(inc, ks):
                    return False
                if exc_set is not None and ks in exc_set:
                    return False
                if isinstance(exc, str) and re.fullmatch(exc, ks):
                    return False
                return True
            groups = {k: i for k, i in groups.items() if _passes(k)}
        # min_doc_count: 0 surfaces zero-count terms from the whole index
        # (TermsAggregator#buildEmptyAggregation path)
        if kind == "terms" and int(spec.get("min_doc_count", 1)) == 0:
            if field == "_index":
                universe = {getattr(ctx, "index_name", "index")}
            else:
                universe = {_hashable(v2) for _i2, v2 in
                            all_values(ctx, ctx.all_rows(), field)}
            for t in universe:
                groups.setdefault(t, [])
        # under a nested scope each VALUE OCCURRENCE is one nested doc:
        # bucket doc_count counts nested docs (NestedAggregator semantics,
        # consistent with the enclosing nested agg's doc_count) while
        # sub-aggs still aggregate over the unique parent rows the
        # flattened store addresses — which is exactly what makes a
        # reverse_nested sub-agg meaningful (nested-doc count above,
        # parent-doc count inside)
        nested_scope = getattr(ctx, "nested_path", None)
        occ = None
        if nested_scope and isinstance(field, str) \
                and field.startswith(nested_scope + "."):
            occ = {k: len(i_list) for k, i_list in groups.items()}
        # sort: doc_count desc then key asc (reference terms agg default)
        order_spec = spec.get("order")
        items = [(k, np.asarray(sorted(set(i_list)), dtype=np.int64))
                 for k, i_list in groups.items()]
        cnt = (lambda k, i: occ[k]) if occ is not None \
            else (lambda k, i: len(i))
        if kind == "rare_terms":
            max_count = int(spec.get("max_doc_count", 1))
            items = [(k, i) for k, i in items if cnt(k, i) <= max_count]
            items.sort(key=lambda kv: (cnt(*kv), _sort_key(kv[0])))
        elif order_spec and isinstance(order_spec, dict):
            ((okey, odir),) = order_spec.items()
            reverse = odir == "desc"
            if okey == "_key":
                items.sort(key=lambda kv: _sort_key(kv[0]), reverse=reverse)
            elif okey == "_count":
                items.sort(key=lambda kv: (cnt(*kv),), reverse=reverse)
            else:
                def metric_val(kv):
                    sub_out = recurse(ctx, rows[kv[1]], sub_aggs)
                    node = sub_out
                    for part in okey.split("."):
                        node = node[part] if isinstance(node, dict) else None
                    return node if isinstance(node, (int, float)) else (node or {}).get("value", 0)
                items.sort(key=metric_val, reverse=reverse)
        else:
            items.sort(key=lambda kv: (-cnt(*kv), _sort_key(kv[0])))
        total_other = sum(cnt(k, i) for k, i in items[size:])
        _check_max_buckets(ctx, min(len(items), size))
        buckets = _bucketize(ctx, rows, sub_aggs,
                             [(k, rows[i]) for k, i in items[:size]],
                             recurse=recurse)
        if occ is not None:
            for b, (k, _i) in zip(buckets, items[:size]):
                b["doc_count"] = int(occ[k])
        # mapper-typed key rendering (DocValueFormat): ip ints back to
        # addresses, booleans to 1/0 + key_as_string, dates to ISO strings
        # (fmt_key is the same transform include/exclude matched against)
        if tname == "ip":
            for b in buckets:
                b["key"] = fmt_key(b["key"])
        elif tname == "boolean":
            for b in buckets:
                truthy = bool(b["key"])
                b["key"] = 1 if truthy else 0
                b["key_as_string"] = "true" if truthy else "false"
        elif tname == "date":
            for b in buckets:
                if isinstance(b["key"], (int, float)):
                    b["key_as_string"] = _millis_to_iso(int(b["key"]))
        return {"doc_count_error_upper_bound": 0,
                "sum_other_doc_count": int(total_other), "buckets": buckets}

    if kind == "histogram":
        interval = float(spec["interval"])
        offset = float(spec.get("offset", 0.0))
        min_count = int(spec.get("min_doc_count", 0))
        vals, present = numeric_values(ctx, rows, field, spec.get("missing"))
        keys = np.floor((vals - offset) / interval) * interval + offset
        out = _histo_buckets(ctx, rows, sub_aggs, keys, present, min_count,
                             spec.get("extended_bounds"), interval,
                             recurse=recurse)
        fmt = spec.get("format")
        if fmt:
            for b in out["buckets"]:
                b["key_as_string"] = _decimal_format(b["key"], fmt)
        return out

    if kind == "date_histogram":
        interval_ms, calendar = _date_interval(spec)
        min_count = int(spec.get("min_doc_count", 0))
        mapper = ctx.mapper_service.get(field)
        from elasticsearch_tpu.index.mapping import RangeFieldMapperBase
        if isinstance(mapper, RangeFieldMapperBase):
            return _range_field_histo(ctx, rows, sub_aggs, spec, field,
                                      recurse=recurse)
        vals, present = numeric_values(ctx, rows, field)
        if getattr(mapper, "type_name", None) == "date_nanos":
            vals = vals / 1e6  # stored nanos; histogram buckets in millis
        offset_ms = _date_offset_ms(spec.get("offset"))
        tz = _resolve_tz(spec.get("time_zone"))
        if calendar:
            keys = np.asarray(
                [_calendar_floor(int(v - offset_ms), calendar, tz) + offset_ms
                 if p else np.nan
                 for v, p in zip(vals, present)], dtype=np.float64)
        else:
            keys = np.floor((vals - offset_ms) / interval_ms) * interval_ms \
                + offset_ms
        return _histo_buckets(ctx, rows, sub_aggs, keys, present, min_count,
                              None, interval_ms, date=True, recurse=recurse,
                              fmt=spec.get("format"), tz=tz,
                              calendar=(calendar, offset_ms) if calendar
                              else None)

    if kind == "auto_date_histogram":
        target = int(spec.get("buckets", 10))
        vals, present = numeric_values(ctx, rows, field)
        v = vals[present]
        if len(v) == 0:
            return {"buckets": [], "interval": "1ms"}
        span = max(v.max() - v.min(), 1.0)
        interval_ms = max(span / target, 1.0)
        # snap to a sane unit
        for unit in (1, 1000, 60_000, 3_600_000, 86_400_000, 2_592_000_000, 31_536_000_000):
            if interval_ms <= unit:
                interval_ms = unit
                break
        keys = np.floor(vals / interval_ms) * interval_ms
        out = _histo_buckets(ctx, rows, sub_aggs, keys, present, 0, None,
                             interval_ms, date=True, recurse=recurse)
        out["interval"] = f"{int(interval_ms)}ms"
        return out

    if kind in ("range", "date_range", "ip_range"):
        ranges = spec.get("ranges", [])
        vals, present = numeric_values(ctx, rows, field, spec.get("missing"))
        mapper = ctx.mapper_service.get(field) if field else None
        date_fmt = (mapper.params.get("format", "")
                    if mapper is not None else "")
        if kind == "date_range":
            def conv(x):
                if x is None:
                    return None
                if "epoch_second" in str(date_fmt):
                    # bounds parse with the field's format: numbers (and
                    # numeric strings) are seconds
                    try:
                        return float(x) * 1000.0
                    except (TypeError, ValueError):
                        pass
                return float(parse_date_millis(x))
        elif kind == "ip_range":
            def conv(x):
                from elasticsearch_tpu.index.mapping import IpFieldMapper
                return float(IpFieldMapper.parse_ip(x)) if x is not None else None
        else:
            def conv(x):
                return float(x) if x is not None else None

        def render_bound(x, numeric):
            # key/from/to rendering per value source (RangeAggregator's
            # DocValueFormat): doubles as "50.0", ips as addresses, dates
            # keep the caller's raw input in the key
            if kind == "ip_range":
                from elasticsearch_tpu.index.mapping import IpFieldMapper
                return IpFieldMapper.format_value(int(numeric))
            if kind == "date_range":
                return numeric
            return float(numeric)

        buckets = []
        for r in ranges:
            cidr = r.get("mask")
            if cidr is not None and kind == "ip_range":
                import ipaddress
                net = ipaddress.ip_network(cidr, strict=False)
                lo = net.network_address
                if lo.version == 4:
                    lo = ipaddress.IPv6Address("::ffff:" + str(lo))
                frm = float(int(lo))
                to = frm + float(net.num_addresses)
            else:
                frm = conv(r.get("from"))
                to = conv(r.get("to"))
            mask = present.copy()
            if frm is not None:
                mask &= vals >= frm
            if to is not None:
                mask &= vals < to
            brows = rows[mask]
            key = r.get("key")
            if key is None and cidr is not None:
                key = cidr
            if key is None:
                lo_s = "*" if frm is None else \
                    (str(r.get("from")) if kind == "date_range"
                     else render_bound(r.get("from"), frm))
                hi_s = "*" if to is None else \
                    (str(r.get("to")) if kind == "date_range"
                     else render_bound(r.get("to"), to))
                key = f"{lo_s}-{hi_s}"
            b = {"key": key, "doc_count": int(len(brows))}
            if frm is not None:
                b["from"] = render_bound(r.get("from"), frm)
            if to is not None:
                b["to"] = render_bound(r.get("to"), to)
            if sub_aggs:
                b.update(recurse(ctx, brows, sub_aggs))
            b["_sort"] = (frm if frm is not None else -np.inf,
                          to if to is not None else np.inf)
            buckets.append(b)
        # RangeAggregator emits buckets ordered by (from, to), not in the
        # order the caller listed them
        buckets.sort(key=lambda b: b.pop("_sort"))
        return {"buckets": buckets}

    if kind == "sampler":
        shard_size = int(spec.get("shard_size", 100))
        brows = rows[:shard_size]
        b = {"doc_count": int(len(brows))}
        if sub_aggs:
            b.update(recurse(ctx, brows, sub_aggs))
        return b

    if kind == "composite":
        import itertools as _it
        sources = spec.get("sources", [])
        if not sources:
            raise IllegalArgumentError(
                "Required [sources]: Composite [sources] cannot be null "
                "or empty")
        size = int(spec.get("size", 10))
        max_b = getattr(ctx, "max_buckets", None) or 65536
        if size > max_b:
            from elasticsearch_tpu.common.errors import TooManyBucketsError
            raise TooManyBucketsError(
                f"Trying to create too many buckets. Must be less than or "
                f"equal to: [{max_b}] but was [{size}]. This limit can be "
                f"set by changing the [search.max_buckets] cluster level "
                f"setting.")
        after = spec.get("after")
        names = []
        formats = []
        source_tzs: Dict[int, Any] = {}
        per_source_vals: List[Dict[int, list]] = []
        for src in sources:
            ((sname, sdef),) = src.items()
            if sname in names:
                raise IllegalArgumentError(
                    f"Composite source names must be unique, found "
                    f"duplicates: [{sname}]")
            names.append(sname)
            ((stype, sspec),) = sdef.items()
            # a multi-valued doc contributes ONE composite key per value
            # (CompositeValuesSourceBuilder cartesian semantics)
            col: Dict[int, list] = {}
            fmt = None
            if stype == "terms":
                is_ip = getattr(ctx.mapper_service.get(sspec["field"]),
                                "type_name", None) == "ip"
                for idx, v in all_values(ctx, rows, sspec["field"]):
                    if is_ip and isinstance(v, (int, float)):
                        from elasticsearch_tpu.index.mapping import (
                            IpFieldMapper)
                        v = IpFieldMapper.format_value(int(v))
                    col.setdefault(idx, []).append(v)
            elif stype == "histogram":
                vals, present = numeric_values(ctx, rows, sspec["field"])
                interval = float(sspec["interval"])
                for idx in np.nonzero(present)[0]:
                    col[int(idx)] = [float(np.floor(vals[idx] / interval)
                                           * interval)]
            elif stype == "date_histogram":
                vals, present = numeric_values(ctx, rows, sspec["field"])
                if getattr(ctx.mapper_service.get(sspec["field"]),
                           "type_name", None) == "date_nanos":
                    vals = vals / 1e6
                ims, cal = _date_interval(sspec)
                off = _date_offset_ms(sspec.get("offset"))
                fmt = sspec.get("format")
                tz = _resolve_tz(sspec.get("time_zone"))
                if tz is not None:
                    source_tzs[len(names) - 1] = tz
                for idx in np.nonzero(present)[0]:
                    v = int(vals[idx])
                    key = (_calendar_floor(v - off, cal, tz) + off if cal
                           else float(np.floor((v - off) / ims) * ims + off))
                    col[int(idx)] = [key]
            elif stype == "geotile_grid":
                precision = int(sspec.get("precision", 7))
                row_pos = {int(r): i for i, r in enumerate(rows)}
                for row, lat, lon in _gather_geo_points(
                        ctx, rows, sspec["field"]):
                    i = row_pos.get(int(row))
                    if i is not None:
                        col.setdefault(i, []).append(
                            _geotile_encode(lat, lon, precision))
            else:
                raise IllegalArgumentError(
                    f"unknown composite source type [{stype}]")
            if sspec.get("missing_bucket"):
                for i in range(len(rows)):
                    col.setdefault(i, [None])
            per_source_vals.append(col)
            formats.append(fmt)
        source_types = [next(iter(next(iter(s.values())))) for s in sources]

        def src_sort_key(value, pos):
            # geotile "z/x/y" orders by tile coordinates, not string order
            if source_types[pos] == "geotile_grid" and isinstance(value, str):
                try:
                    return (0,) + tuple(int(p) for p in value.split("/"))
                except ValueError:
                    pass
            return _sort_key(value)

        keyed: Dict[tuple, List[int]] = {}
        for i in range(len(rows)):
            value_lists = [col.get(i) for col in per_source_vals]
            if any(not vl for vl in value_lists):
                continue
            for key in _it.product(*value_lists):
                keyed.setdefault(key, []).append(i)
        items = sorted(keyed.items(),
                       key=lambda kv: tuple(src_sort_key(k, p)
                                            for p, k in enumerate(kv[0])))
        if after is not None:
            after_vals = []
            for p, n in enumerate(names):
                v = after.get(n)
                if formats[p] and isinstance(v, str):
                    # a formatted after_key round-trips: parse it back into
                    # the internal millis domain before comparing; bare
                    # local datetimes read in the source's time_zone
                    try:
                        raw = v
                        v = float(parse_date_millis(v))
                        tz = source_tzs.get(p)
                        has_offset = raw.endswith("Z") or bool(
                            __import__("re").search(
                                r"[+-]\d\d:?\d\d$", raw))
                        if tz is not None and not has_offset:
                            import datetime as _dt
                            # offset AT the parsed instant (DST-correct)
                            at = _dt.datetime.fromtimestamp(
                                v / 1000.0, _dt.timezone.utc)
                            off = tz.utcoffset(at)
                            v -= off.total_seconds() * 1000.0
                    except Exception:
                        pass
                after_vals.append(v)
            after_rank = tuple(src_sort_key(v, p)
                               for p, v in enumerate(after_vals))
            items = [it for it in items
                     if tuple(src_sort_key(k, p)
                              for p, k in enumerate(it[0])) > after_rank]
        items = items[:size]

        def render(key):
            out_key = {}
            for p, (n, k, fmt) in enumerate(zip(names, key, formats)):
                if fmt and isinstance(k, (int, float)):
                    out_key[n] = _format_date_key(int(k), fmt,
                                                  tz=source_tzs.get(p))
                elif isinstance(k, float) and k.is_integer():
                    out_key[n] = int(k)
                else:
                    out_key[n] = k
            return out_key

        buckets = []
        for key, idxs in items:
            b = {"key": render(key), "doc_count": len(set(idxs))}
            if sub_aggs:
                b.update(recurse(ctx, rows[np.asarray(sorted(set(idxs)),
                                                      dtype=np.int64)],
                                 sub_aggs))
            buckets.append(b)
        out = {"buckets": buckets}
        if buckets:
            out["after_key"] = buckets[-1]["key"]
        return out

    if kind == "adjacency_matrix":
        filters = spec.get("filters", {})
        matches = {name: parse_query(q).execute(ctx).rows for name, q in filters.items()}
        names = sorted(matches)
        buckets = []
        for i, a in enumerate(names):
            ra = rows[np.isin(rows, matches[a])]
            if len(ra):
                b = {"key": a, "doc_count": int(len(ra))}
                if sub_aggs:
                    b.update(recurse(ctx, ra, sub_aggs))
                buckets.append(b)
            for bname in names[i + 1:]:
                rb = ra[np.isin(ra, matches[bname])]
                if len(rb):
                    b = {"key": f"{a}&{bname}", "doc_count": int(len(rb))}
                    if sub_aggs:
                        b.update(recurse(ctx, rb, sub_aggs))
                    buckets.append(b)
        return {"buckets": buckets}

    if kind == "nested":
        # nested docs are stored flattened; nested agg scopes to docs having
        # the path, and descendants (top_hits) may expand per nested doc.
        # doc_count counts NESTED documents, not parents (NestedAggregator
        # collects one bucket entry per child doc under each matched root)
        path = spec.get("path")
        b = {"doc_count": _count_nested_docs(ctx, rows, path)}
        if sub_aggs:
            prev = getattr(ctx, "nested_path", None)
            ctx.nested_path = path
            try:
                b.update(recurse(ctx, rows, sub_aggs))
            finally:
                ctx.nested_path = prev
        return b

    if kind == "reverse_nested":
        # ReverseNestedAggregator.java:48 — joins from the nested context
        # back to the parent docs (or an outer nested level via `path`).
        # Rows are already parent rows in the flattened design, so the
        # bucket is the parent-doc count and sub-aggs recurse with the
        # nested scope popped to the target level.
        cur = getattr(ctx, "nested_path", None)
        if cur is None:
            raise ParsingError(
                "Reverse nested aggregation must be used inside a [nested] "
                "aggregation")
        target = spec.get("path")
        if target is not None and not cur.startswith(target + "."):
            # equality is invalid too: reverse_nested must step OUT of the
            # current scope, to a strict ancestor level
            raise ParsingError(
                f"Invalid path [{target}] for reverse_nested aggregation: "
                f"not an ancestor of the current nested scope [{cur}]")
        b = {"doc_count": int(len(rows))} if target is None else \
            {"doc_count": _count_nested_docs(ctx, rows, target)}
        if sub_aggs:
            ctx.nested_path = target
            try:
                b.update(recurse(ctx, rows, sub_aggs))
            finally:
                ctx.nested_path = cur
        return b

    raise ParsingError(f"unknown bucket aggregation [{kind}]")


def _count_nested_docs(ctx, rows, path: Optional[str]) -> int:
    """Number of nested documents at `path` across `rows` (source walk —
    the flattened store keeps nested objects inside the parent doc).
    List-aware at every level, so multi-level paths like
    `comments.replies` count the leaves. Memoized per (reader gen, path)
    row count so repeated buckets in one request don't re-parse sources."""
    if not path:
        return int(len(rows))
    from elasticsearch_tpu.search.queries_ext import _values_at
    cache = getattr(ctx, "_nested_count_cache", None)
    if cache is None:
        cache = ctx._nested_count_cache = {}
    total = 0
    for row in rows:
        key = (path, int(row))
        n = cache.get(key)
        if n is None:
            src = ctx.reader.get_source(int(row)) or {}
            n = sum(1 for it in _values_at(src, path) if it is not None)
            cache[key] = n
        total += n
    return total


_SIG_KNOWN_FIELDS = ["field", "size", "shard_size", "min_doc_count",
                     "shard_min_doc_count", "background_filter", "include",
                     "exclude", "execution_hint", "jlh", "gnd", "chi_square",
                     "mutual_information", "percentage", "script_heuristic",
                     "filter_duplicate_text", "source_fields", "missing"]


def _compute_significant(ctx, rows, kind, spec, sub_aggs, recurse) -> dict:
    """significant_terms / significant_text (reference:
    SignificantTermsAggregatorFactory + SignificantTextAggregator): JLH
    scoring of foreground vs background term frequencies; significant_text
    re-analyzes _source with optional duplicate-sequence filtering
    (DeDuplicatingTokenFilter)."""
    field = spec.get("field")
    size = int(spec.get("size", 10))
    min_count = int(spec.get("min_doc_count", 3))
    mapper = ctx.mapper_service.get(field)
    analyzed = kind == "significant_text" \
        or getattr(mapper, "type_name", None) == "text"
    dedup = bool(spec.get("filter_duplicate_text"))

    def _terms_per_doc(doc_rows, use_dedup=False):
        """row -> set(terms), with cross-doc 6-gram dedup when asked."""
        seen_shingles: set = set()
        out = {}
        for row in doc_rows:
            if analyzed:
                src = ctx.reader.get_source(int(row)) or {}
                node = src
                for part in str(field).split("."):
                    node = node.get(part) if isinstance(node, dict) else None
                vals = node if isinstance(node, list) else [node]
                tokens: List[str] = []
                for v in vals:
                    if v is None:
                        continue
                    if mapper is not None and hasattr(mapper, "analyze"):
                        tokens.extend(mapper.analyze(str(v)))
                    else:
                        tokens.extend(str(v).lower().split())
                if use_dedup and len(tokens) >= 6:
                    dup = [False] * len(tokens)
                    for p in range(len(tokens) - 5):
                        if tuple(tokens[p:p + 6]) in seen_shingles:
                            for q in range(p, p + 6):
                                dup[q] = True
                    for p in range(len(tokens) - 5):
                        seen_shingles.add(tuple(tokens[p:p + 6]))
                    tokens = [t for t, d in zip(tokens, dup) if not d]
                out[int(row)] = set(tokens)
            else:
                v = ctx.reader.get_doc_value(field, int(row))
                vals = v if isinstance(v, list) else ([v] if v is not None else [])
                out[int(row)] = {_hashable(x) for x in vals}
        return out

    fg_terms = _terms_per_doc([int(r) for r in rows], use_dedup=dedup)
    fg_total = len(rows)
    fg_count: Dict[Any, int] = {}
    fg_rows_by_term: Dict[Any, List[int]] = {}
    for row, terms in fg_terms.items():
        for t in terms:
            fg_count[t] = fg_count.get(t, 0) + 1
            fg_rows_by_term.setdefault(t, []).append(row)
    # background frequencies depend only on the index, not the bucket:
    # memoize per (field, analyzed) so nesting under a terms agg doesn't
    # re-analyze the whole index once per parent bucket
    bg_cache = ctx.__dict__.setdefault("_sig_bg_cache", {})
    bg_key = (str(field), analyzed)
    if bg_key in bg_cache:
        bg_count, bg_total = bg_cache[bg_key]
    else:
        bg_rows = ctx.all_rows()
        bg_total = len(bg_rows)
        bg_count = {}
        for terms in _terms_per_doc([int(r) for r in bg_rows]).values():
            for t in terms:
                bg_count[t] = bg_count.get(t, 0) + 1
        bg_cache[bg_key] = (bg_count, bg_total)
    scored = []
    for t, fg in fg_count.items():
        if fg < min_count:
            continue
        bg = bg_count.get(t, fg)
        fg_freq = fg / fg_total if fg_total else 0.0
        bg_freq = bg / bg_total if bg_total else 0.0
        if fg_freq <= bg_freq or bg_freq == 0:
            continue
        score = (fg_freq - bg_freq) * (fg_freq / bg_freq)  # JLH
        scored.append((score, t, fg, bg))
    scored.sort(key=lambda x: (-x[0], _sort_key(x[1])))
    tname = getattr(mapper, "type_name", None)
    inc, exc = spec.get("include"), spec.get("exclude")
    import re as _re
    buckets = []
    for score, t, fg, bg in scored:
        if len(buckets) >= size:
            break
        key = t
        if tname == "ip" and isinstance(t, (int, float)):
            from elasticsearch_tpu.index.mapping import IpFieldMapper
            key = IpFieldMapper.format_value(int(t))
        ks = str(key)
        if isinstance(inc, list) and ks not in {str(x) for x in inc}:
            continue
        if isinstance(exc, list) and ks in {str(x) for x in exc}:
            continue
        if isinstance(inc, str) and not _re.fullmatch(inc, ks):
            continue
        if isinstance(exc, str) and _re.fullmatch(exc, ks):
            continue
        b = {"key": key, "doc_count": fg, "score": score, "bg_count": bg}
        if tname == "date" and isinstance(t, (int, float)):
            b["key_as_string"] = _millis_to_iso(int(t))
        if sub_aggs:
            brows = np.asarray(sorted(set(fg_rows_by_term[t])),
                               dtype=np.int64)
            b.update(recurse(ctx, brows, sub_aggs))
        buckets.append(b)
    return {"doc_count": fg_total, "bg_count": bg_total, "buckets": buckets}


def _range_field_histo(ctx, rows, sub_aggs, spec, field, recurse=None) -> dict:
    """date_histogram over a date_range field: every doc counts in EVERY
    bucket its range overlaps (reference: RangeHistogramAggregator)."""
    recurse = recurse or compute_aggs
    interval_ms, calendar = _date_interval(spec)
    offset_ms = _date_offset_ms(spec.get("offset"))
    tz = _resolve_tz(spec.get("time_zone"))
    fmt = spec.get("format")
    groups: Dict[float, List[int]] = {}
    for i, row in enumerate(rows):
        v = ctx.reader.get_doc_value(field, int(row))
        if isinstance(v, list):
            v = v[0] if v else None
        if not isinstance(v, dict):
            continue
        lo = float(v.get("gte", np.nan))
        hi = float(v.get("lte", np.nan))
        if not (np.isfinite(lo) and np.isfinite(hi)):
            continue

        def floor_of(ms):
            if calendar:
                return _calendar_floor(int(ms - offset_ms), calendar) \
                    + offset_ms
            return float(np.floor((ms - offset_ms) / interval_ms)
                         * interval_ms + offset_ms)
        cur = floor_of(lo)
        end = floor_of(hi)
        guard = 0
        while cur <= end and guard < 100_000:
            groups.setdefault(float(cur), []).append(int(row))
            guard += 1
            if calendar:
                # advance to the next calendar bucket: probe forward until
                # the floor moves (calendar units are variable-length)
                step = cur + interval_ms / 2
                while floor_of(step) <= cur and guard < 100_000:
                    step += 86_400_000
                    guard += 1
                cur = floor_of(step)
            else:
                cur += interval_ms
    buckets = []
    _check_max_buckets(ctx, len(groups))
    for key in sorted(groups):
        brows = np.asarray(sorted(set(groups[key])), dtype=np.int64)
        b = {"key": int(key), "doc_count": int(len(brows)),
             "key_as_string": _format_date_key(int(key), fmt, tz) if fmt
             else _millis_to_iso_tz(int(key), tz)}
        if sub_aggs:
            b.update(recurse(ctx, brows, sub_aggs))
        buckets.append(b)
    return {"buckets": buckets}


def _decimal_format(value, pattern: str) -> str:
    """Minimal Java DecimalFormat: literal prefix/suffix around a #/0 run
    with optional fraction digits ("Value is ##0.0" -> "Value is 51.0")."""
    m = re.search(r"[#0][#0,.]*", pattern)
    if not m:
        return pattern
    num = m.group(0)
    prefix, suffix = pattern[:m.start()], pattern[m.end():]
    if "." in num:
        frac = num.split(".", 1)[1]
        min_frac, max_frac = frac.count("0"), len(frac)
    else:
        min_frac = max_frac = 0
    v = float(value)
    if max_frac == 0:
        s = str(int(round(v)))
    else:
        s = f"{v:.{max_frac}f}"
        int_part, frac_part = s.split(".")
        frac_part = frac_part.rstrip("0").ljust(min_frac, "0")
        s = int_part + ("." + frac_part if frac_part else "")
    return prefix + s + suffix


def _check_max_buckets(ctx, n: int) -> None:
    """search.max_buckets guard (MultiBucketConsumerService)."""
    mx = getattr(ctx, "max_buckets", None)
    if mx is not None and n > mx:
        from elasticsearch_tpu.common.errors import TooManyBucketsError
        raise TooManyBucketsError(
            f"Trying to create too many buckets. Must be less than or "
            f"equal to: [{mx}] but was [{n}]. This limit can be set by "
            f"changing the [search.max_buckets] cluster level setting.")


def _sort_key(v):
    if v is None:
        return (2, "")
    if isinstance(v, bool):
        return (1, str(v))
    if isinstance(v, (int, float)):
        return (0, float(v))
    return (1, str(v))


MAX_BUCKETS = 65536  # reference: search.max_buckets default


def _histo_buckets(ctx, rows, sub_aggs, keys, present, min_count,
                   extended_bounds, interval, date=False, recurse=None,
                   fmt=None, tz=None, calendar=None) -> dict:
    recurse = recurse or compute_aggs
    groups: Dict[float, np.ndarray] = {}
    valid = present & ~np.isnan(keys)
    for key in np.unique(keys[valid]):
        groups[float(key)] = rows[valid & (keys == key)]
    all_keys = sorted(groups)

    def _guard_span(lo_key, hi_key):
        # reference: search.max_buckets / MultiBucketConsumer
        if interval and (hi_key - lo_key) / interval > MAX_BUCKETS:
            raise IllegalArgumentError(
                f"Trying to create too many buckets. Must be less than or "
                f"equal to: [{MAX_BUCKETS}].")

    if extended_bounds and interval:
        lo, hi = float(extended_bounds.get("min", np.inf)), float(extended_bounds.get("max", -np.inf))
        k = min([lo] + all_keys) if all_keys or lo != np.inf else lo
        top = max([hi] + all_keys) if all_keys or hi != -np.inf else hi
        _guard_span(k, top)
        cur = k
        full = []
        while cur <= top + 1e-9:
            full.append(round(cur, 10))
            cur += interval
        all_keys = full
    elif min_count == 0 and all_keys and interval:
        _guard_span(all_keys[0], all_keys[-1])
        full = []
        cur = all_keys[0]
        while cur <= all_keys[-1] + 1e-9:
            full.append(round(cur, 10))
            cur += interval
        all_keys = full
    elif min_count == 0 and all_keys and calendar:
        all_keys = _calendar_keys(all_keys[0], all_keys[-1], calendar[0],
                                  tz, calendar[1])
    _check_max_buckets(ctx, len(all_keys))
    buckets = []
    for key in all_keys:
        brows = groups.get(key, np.zeros(0, dtype=np.int64))
        if len(brows) < min_count and min_count > 0:
            continue
        b = {"key": int(key) if date else key, "doc_count": int(len(brows))}
        if date:
            b["key_as_string"] = _format_date_key(int(key), fmt, tz) if fmt \
                else _millis_to_iso_tz(int(key), tz)
        if sub_aggs:
            b.update(recurse(ctx, brows, sub_aggs))
        buckets.append(b)
    return {"buckets": buckets}


_CAL_UNITS = {"minute": "T", "1m": "T", "hour": "H", "1h": "H", "day": "D", "1d": "D",
              "week": "W", "1w": "W", "month": "M", "1M": "M", "quarter": "Q",
              "1q": "Q", "year": "Y", "1y": "Y"}
_FIXED_RE = re.compile(r"^(\d+)(ms|s|m|h|d)$")
_FIXED_FACTORS = {"ms": 1, "s": 1000, "m": 60_000, "h": 3_600_000, "d": 86_400_000}


def _date_interval(spec: dict) -> Tuple[float, Optional[str]]:
    cal = spec.get("calendar_interval")
    if cal:
        unit = _CAL_UNITS.get(cal)
        if unit is None:
            raise ParsingError(f"unknown calendar interval [{cal}]")
        return 0.0, unit
    fixed = spec.get("fixed_interval") or spec.get("interval")
    if fixed is None:
        raise ParsingError("date_histogram requires calendar_interval or fixed_interval")
    if isinstance(fixed, (int, float)):
        return float(fixed), None
    m = _FIXED_RE.match(str(fixed))
    if m:
        return float(int(m.group(1)) * _FIXED_FACTORS[m.group(2)]), None
    unit = _CAL_UNITS.get(str(fixed))
    if unit:
        return 0.0, unit
    raise ParsingError(f"unknown interval [{fixed}]")


def _resolve_tz(tz_spec):
    """time_zone param -> tzinfo: fixed offsets ("-07:00") or IANA names
    (America/Phoenix) via zoneinfo."""
    import datetime as dt
    if not tz_spec:
        return None
    s = str(tz_spec)
    m = re.fullmatch(r"([+-])(\d{2}):?(\d{2})", s)
    if m:
        sign = 1 if m.group(1) == "+" else -1
        return dt.timezone(sign * dt.timedelta(hours=int(m.group(2)),
                                               minutes=int(m.group(3))))
    try:
        import zoneinfo
        return zoneinfo.ZoneInfo(s)
    except Exception:
        return None


def _millis_to_iso_tz(millis: int, tz) -> str:
    """ISO rendering in a zone with its offset suffix
    ("2015-12-31T17:00:00.000-07:00"); UTC renders with Z."""
    import datetime as dt
    if tz is None:
        return _millis_to_iso(millis)
    d = dt.datetime.fromtimestamp(millis / 1000.0, tz=tz)
    base = d.strftime("%Y-%m-%dT%H:%M:%S") + f".{d.microsecond // 1000:03d}"
    off = d.utcoffset() or dt.timedelta(0)
    if off == dt.timedelta(0):
        return base + "Z"
    total = int(off.total_seconds())
    sign = "+" if total >= 0 else "-"
    total = abs(total)
    return base + f"{sign}{total // 3600:02d}:{(total % 3600) // 60:02d}"


def _format_date_key(millis: int, fmt: str, tz=None) -> str:
    """Joda-pattern-lite date rendering for agg keys ("yyyy-MM-dd",
    "iso8601", "strict_date_time", epoch_millis, "e" day-of-week)."""
    if fmt in ("iso8601", "strict_date_time", "date_time"):
        return _millis_to_iso_tz(millis, tz) if tz else _millis_to_iso(millis)
    if fmt == "epoch_millis":
        return str(millis)
    import datetime as dt
    try:
        d = dt.datetime.fromtimestamp(millis / 1000.0,
                                      tz=tz or dt.timezone.utc)
    except (OverflowError, OSError, ValueError):
        return str(millis)
    if fmt == "e":
        # Joda dayOfWeek number (ISO: Monday=1 .. Sunday=7)
        return str(d.isoweekday())
    strf = (fmt.replace("yyyy", "%Y").replace("MM", "%m")
            .replace("dd", "%d").replace("HH", "%H").replace("mm", "%M")
            .replace("ss", "%S"))
    out = d.strftime(strf)
    if "SSS" in out:
        out = out.replace("SSS", f"{d.microsecond // 1000:03d}")
    return out


def _date_offset_ms(offset) -> float:
    """date_histogram `offset` like "+6h"/"-1d" → millis."""
    if not offset:
        return 0.0
    s = str(offset)
    sign = -1.0 if s.startswith("-") else 1.0
    s = s.lstrip("+-")
    units = {"ms": 1, "s": 1000, "m": 60_000, "h": 3_600_000,
             "d": 86_400_000}
    for suffix in ("ms", "s", "m", "h", "d"):
        if s.endswith(suffix):
            return sign * float(s[:-len(suffix)]) * units[suffix]
    try:
        return sign * float(s)
    except ValueError:
        return 0.0


def _calendar_floor(millis: int, unit: str, tz=None) -> float:
    """Floor to a calendar unit, in `tz`'s local wall time when given
    (Rounding.Builder timeZone semantics — buckets align to local
    midnight/month starts, not UTC)."""
    import datetime as dt
    d = dt.datetime.fromtimestamp(millis / 1000.0, tz=tz or dt.timezone.utc)
    if unit == "T":
        d = d.replace(second=0, microsecond=0)
    elif unit == "H":
        d = d.replace(minute=0, second=0, microsecond=0)
    elif unit == "D":
        d = d.replace(hour=0, minute=0, second=0, microsecond=0)
    elif unit == "W":
        d = (d - dt.timedelta(days=d.weekday())).replace(hour=0, minute=0, second=0, microsecond=0)
    elif unit == "M":
        d = d.replace(day=1, hour=0, minute=0, second=0, microsecond=0)
    elif unit == "Q":
        d = d.replace(month=((d.month - 1) // 3) * 3 + 1, day=1, hour=0, minute=0,
                      second=0, microsecond=0)
    elif unit == "Y":
        d = d.replace(month=1, day=1, hour=0, minute=0, second=0, microsecond=0)
    return float(int(d.timestamp() * 1000))


# nominal calendar-unit lengths in millis: probe steps for the boundary
# walk, NOT bucket widths (DST and leap realities come from _calendar_floor)
_CAL_NOMINAL = {"T": 60_000, "H": 3_600_000, "D": 86_400_000,
                "W": 604_800_000, "M": 28 * 86_400_000,
                "Q": 90 * 86_400_000, "Y": 365 * 86_400_000}


def _calendar_next(cur: float, unit: str, tz=None) -> float:
    """The calendar boundary after the boundary `cur`: probe a nominal
    step, then correct with the true floor, so DST-shifted days and
    variable months and years land where `_calendar_floor` puts them."""
    step = _CAL_NOMINAL[unit]
    nxt = _calendar_floor(int(cur + step), unit, tz)
    while nxt <= cur:           # a short step inside a long month
        step += 3_600_000
        nxt = _calendar_floor(int(cur + step), unit, tz)
    back = _calendar_floor(int(nxt - 1), unit, tz)
    while back > cur:           # the probe overshot a boundary
        nxt = back
        back = _calendar_floor(int(nxt - 1), unit, tz)
    return nxt


def _calendar_keys(first: float, last: float, unit: str, tz=None,
                   offset: float = 0.0) -> List[float]:
    """Every bucket key of a calendar-interval histogram from `first` to
    `last` (`min_doc_count` 0: the empty buckets between two that hold
    documents exist too, as with a fixed interval). Keys are boundaries
    plus `offset`."""
    if (last - first) / _CAL_NOMINAL[unit] > MAX_BUCKETS:
        raise IllegalArgumentError(
            f"Trying to create too many buckets. Must be less than or "
            f"equal to: [{MAX_BUCKETS}].")
    keys = [first]
    cur = first - offset
    while True:
        cur = _calendar_next(cur, unit, tz)
        if cur + offset > last:
            return keys
        keys.append(cur + offset)


def _millis_to_iso(millis: int) -> str:
    import datetime as dt
    try:
        d = dt.datetime.fromtimestamp(millis / 1000.0, tz=dt.timezone.utc)
    except (OverflowError, OSError, ValueError):
        # out-of-range epoch (e.g. nanos mistakenly fed as millis): render
        # the raw number instead of 500ing the whole response
        return str(millis)
    return d.strftime("%Y-%m-%dT%H:%M:%S.") + f"{d.microsecond // 1000:03d}Z"


# ---------------------------------------------------------------------------
# pipeline aggregations
# ---------------------------------------------------------------------------

def _top_hits(ctx, rows, spec) -> dict:
    """top_hits metric (TopHitsAggregator): source hits per bucket with
    optional sort (incl. nested sort paths) and seq_no/primary_term.
    Directly under a `nested` agg the hits are the NESTED documents, each
    carrying its parent _id and a _nested {field, offset} locator."""
    size = int(spec.get("size", 3))
    want_seq = bool(spec.get("seq_no_primary_term"))
    index_name = getattr(ctx, "index_name", "index")
    nested_ctx = getattr(ctx, "nested_path", None)

    def parse_sort(ss):
        if isinstance(ss, str):
            return ss, "asc", None
        if isinstance(ss, list) and ss:
            return parse_sort(ss[0])
        if isinstance(ss, dict) and ss:
            ((f, o),) = list(ss.items())[:1]
            if isinstance(o, dict):
                return f, o.get("order", "asc"), \
                    (o.get("nested") or {}).get("path")
            return f, str(o), None
        return None, "asc", None

    sfield, sorder, sort_nested = parse_sort(spec.get("sort"))
    if sfield and sfield.endswith(".keyword"):
        sfield = sfield[: -len(".keyword")]

    def walk(obj, path):
        cur = obj
        for p in path.split("."):
            cur = cur.get(p) if isinstance(cur, dict) else None
        return cur

    reverse = sorder == "desc"
    if nested_ctx and sfield and sfield.startswith(nested_ctx + "."):
        rel = sfield[len(nested_ctx) + 1:]
        entries = []
        for row in rows:
            src = ctx.reader.get_source(int(row)) or {}
            items = walk(src, nested_ctx)
            if isinstance(items, dict):
                items = [items]
            for off, item in enumerate(items or []):
                if isinstance(item, dict):
                    entries.append((walk(item, rel), int(row), off, item))
        present_e = [e for e in entries if e[0] is not None]
        absent_e = [e for e in entries if e[0] is None]
        present_e.sort(key=lambda e: (isinstance(e[0], str), e[0]),
                       reverse=reverse)
        entries = present_e + absent_e
        hits = []
        for val, row, off, item in entries[:size]:
            hits.append({"_index": index_name,
                         "_id": ctx.reader.get_id(row),
                         "_nested": {"field": nested_ctx, "offset": off},
                         "_source": item, "_score": None, "sort": [val]})
        return {"hits": {"total": {"value": len(entries), "relation": "eq"},
                         "max_score": None, "hits": hits}}

    entries = []
    for row in rows:
        key = None
        if sfield:
            npath = sort_nested
            if not npath:
                dv = ctx.reader.get_doc_value(sfield, int(row))
                if dv is not None:
                    key = dv[0] if isinstance(dv, list) and dv else dv
                    entries.append((key, int(row)))
                    continue
            src = ctx.reader.get_source(int(row)) or {}
            if npath and sfield.startswith(npath + "."):
                items = walk(src, npath)
                if isinstance(items, dict):
                    items = [items]
                vals = [walk(it, sfield[len(npath) + 1:])
                        for it in items or [] if isinstance(it, dict)]
                vals = [v for v in vals if v is not None]
                key = (max(vals) if reverse else min(vals)) if vals else None
            else:
                key = walk(src, sfield)
                if isinstance(key, list):
                    key = key[0] if key else None
        entries.append((key, int(row)))
    if sfield:
        present_e = [e for e in entries if e[0] is not None]
        absent_e = [e for e in entries if e[0] is None]
        present_e.sort(key=lambda e: (isinstance(e[0], str), e[0]),
                       reverse=reverse)
        entries = present_e + absent_e
    hits = []
    for key, row in entries[:size]:
        h = {"_index": index_name, "_id": ctx.reader.get_id(row),
             "_source": ctx.reader.get_source(row), "_score": None}
        if sfield:
            h["sort"] = [key]
        if want_seq:
            sq = ctx.reader.get_seq_no(row)
            h["_seq_no"] = int(sq) if sq is not None else 0
            h["_primary_term"] = 1
        hits.append(h)
    return {"hits": {"total": {"value": len(rows), "relation": "eq"},
                     "max_score": None, "hits": hits}}


def _resolve_buckets_path(sibling_outputs: dict, path: str):
    """Resolve 'agg>metric' / 'agg.value' buckets_path over computed outputs.

    Sibling pipelines may only step INTO one multi-bucket aggregation; a
    second multi-bucket agg mid-path (or as the terminal element) is the
    reference's AggregationPath validation error."""
    agg_path, _, metric = path.partition(">")
    node = sibling_outputs.get(agg_path)
    if node is None:
        raise ParsingError(f"buckets_path [{path}] references unknown aggregation")
    buckets = node.get("buckets")
    if buckets is None:
        raise ParsingError(f"buckets_path [{path}] target has no buckets")
    head = metric.split(">", 1)[0].split(".")[0] if metric else ""
    sample = next(iter(buckets.values() if isinstance(buckets, dict)
                       else buckets), None)
    if head and isinstance(sample, dict):
        inner = sample.get(head)
        if isinstance(inner, dict) and "buckets" in inner:
            if ">" in metric:
                # a multi-bucket agg mid-path: the reference renders the
                # owning agg's Java bucket type in the message
                raise IllegalArgumentError(
                    f"buckets_path must reference either a number value or "
                    f"a single value numeric metric aggregation, got: "
                    f"[Object[]] at aggregation [{head}]")
            raise IllegalArgumentError(
                f"buckets_path must reference either a number value or a "
                f"single value numeric metric aggregation, got: "
                f"[LongTerms] at aggregation [{head}]")
        if isinstance(inner, dict) and "values" in inner \
                and "." not in metric:
            raise IllegalArgumentError(
                f"buckets_path must reference either a number value or a "
                f"single value numeric metric aggregation, but [{head}] "
                f"contains multiple values. Please specify which to use.")
    values = []
    for b in (buckets.values() if isinstance(buckets, dict) else buckets):
        if not metric or metric == "_count":
            values.append(float(b["doc_count"]))
        else:
            m = b
            for part in metric.split("."):
                m = m.get(part) if isinstance(m, dict) else None
            if isinstance(m, dict):
                m = m.get("value")
            values.append(float(m) if m is not None else None)
    return node, buckets, values


def _compute_pipeline(outputs: dict, kind: str, spec: dict, name: str = "") -> Any:
    if kind in ("bucket_script", "bucket_selector", "bucket_sort"):
        return _compute_bucket_pipeline(outputs, kind, spec, name)
    path = spec.get("buckets_path")
    node, buckets, values = _resolve_buckets_path(outputs, path)
    present = [v for v in values if v is not None]
    if kind == "avg_bucket":
        return {"value": sum(present) / len(present) if present else None}
    if kind == "sum_bucket":
        return {"value": sum(present) if present else 0.0}
    if kind == "max_bucket":
        if not present:
            return {"value": None, "keys": []}
        mx = max(present)
        keys = [str(b.get("key")) for b, v in zip(buckets, values) if v == mx]
        return {"value": mx, "keys": keys}
    if kind == "min_bucket":
        if not present:
            return {"value": None, "keys": []}
        mn = min(present)
        keys = [str(b.get("key")) for b, v in zip(buckets, values) if v == mn]
        return {"value": mn, "keys": keys}
    if kind == "stats_bucket":
        if not present:
            return {"count": 0, "min": None, "max": None, "avg": None, "sum": 0.0}
        return {"count": len(present), "min": min(present), "max": max(present),
                "avg": sum(present) / len(present), "sum": sum(present)}
    if kind == "extended_stats_bucket":
        arr = np.asarray(present, dtype=np.float64)
        return _extended_stats(arr, np.ones(len(arr), dtype=bool),
                               float(spec.get("sigma", 2.0)))
    if kind == "percentiles_bucket":
        pcts = spec.get("percents", [1.0, 5.0, 25.0, 50.0, 75.0, 95.0, 99.0])
        arr = np.asarray(present, dtype=np.float64)
        return {"values": {f"{float(p)}":
                           (float(np.percentile(arr, p)) if len(arr) else None)
                           for p in pcts}}
    if kind == "cumulative_sum":
        total = 0.0
        for b, v in zip(buckets, values):
            total += v or 0.0
            b.setdefault(name, {})["value"] = total
        return {"_applied": True}
    if kind == "derivative":
        prev = None
        for b, v in zip(buckets, values):
            if prev is not None and v is not None:
                b.setdefault(name, {})["value"] = v - prev
            prev = v
        return {"_applied": True}
    if kind == "serial_diff":
        lag = int(spec.get("lag", 1))
        for i, b in enumerate(buckets):
            if i >= lag and values[i] is not None and values[i - lag] is not None:
                b.setdefault(name, {})["value"] = values[i] - values[i - lag]
        return {"_applied": True}
    if kind == "moving_fn":
        window = int(spec.get("window", 5))
        if window <= 0:
            raise IllegalArgumentError(
                "[window] must be a positive, non-zero integer.")
        for i, b in enumerate(buckets):
            win = [v for v in values[max(0, i - window):i] if v is not None]
            b.setdefault(name, {})["value"] = (sum(win) / len(win)) if win else None
        return {"_applied": True}
    raise ParsingError(f"unknown pipeline aggregation [{kind}]")


def _compute_bucket_pipeline(outputs: dict, kind: str, spec: dict, name: str = "") -> Any:
    paths: Dict[str, str] = spec.get("buckets_path", {})
    # all paths must target the same parent agg buckets
    parents = set()
    series: Dict[str, List[Optional[float]]] = {}
    buckets_ref = None
    for var, path in paths.items():
        agg_path = path.partition(">")[0]
        parents.add(agg_path)
        _, buckets_ref, values = _resolve_buckets_path(outputs, path)
        series[var] = values
    if buckets_ref is None:
        return {"_applied": False}
    script = spec.get("script", "")
    source = script["source"] if isinstance(script, dict) else script
    import ast as _ast

    def eval_for(i: int):
        env = {var: vals[i] for var, vals in series.items()}
        if any(v is None for v in env.values()):
            return None
        tree = _ast.parse(source.replace("params.", ""), mode="eval")

        def ev(node):
            if isinstance(node, _ast.Expression):
                return ev(node.body)
            if isinstance(node, _ast.Constant):
                return node.value
            if isinstance(node, _ast.Name):
                if node.id in env:
                    return env[node.id]
                raise ParsingError(f"unknown variable [{node.id}] in bucket script")
            if isinstance(node, _ast.BinOp):
                ops = {_ast.Add: lambda a, b: a + b, _ast.Sub: lambda a, b: a - b,
                       _ast.Mult: lambda a, b: a * b, _ast.Div: lambda a, b: a / b}
                return ops[type(node.op)](ev(node.left), ev(node.right))
            if isinstance(node, _ast.Compare):
                left = ev(node.left)
                right = ev(node.comparators[0])
                ops = {_ast.Gt: left > right, _ast.GtE: left >= right,
                       _ast.Lt: left < right, _ast.LtE: left <= right,
                       _ast.Eq: left == right, _ast.NotEq: left != right}
                return ops[type(node.ops[0])]
            if isinstance(node, _ast.UnaryOp) and isinstance(node.op, _ast.USub):
                return -ev(node.operand)
            raise ParsingError("unsupported bucket script construct")

        return ev(tree)

    bl = buckets_ref if isinstance(buckets_ref, list) else list(buckets_ref.values())
    if kind == "bucket_script":
        name = spec.get("_name", "bucket_script")
        for i, b in enumerate(bl):
            v = eval_for(i)
            if v is not None:
                b.setdefault(name, {})["value"] = float(v)
        return {"_applied": True}
    if kind == "bucket_selector":
        keep = [bool(eval_for(i)) for i in range(len(bl))]
        bl[:] = [b for b, k in zip(bl, keep) if k]
        return {"_applied": True}
    if kind == "bucket_sort":
        sort_spec = spec.get("sort", [])
        size = spec.get("size")
        frm = int(spec.get("from", 0))
        for s in reversed(sort_spec):
            if isinstance(s, dict):
                ((path, order),) = s.items()
                direction = order.get("order", "asc") if isinstance(order, dict) else order
                def keyfn(b, p=path):
                    node = b
                    for part in p.split("."):
                        node = node.get(part) if isinstance(node, dict) else None
                    if isinstance(node, dict):
                        node = node.get("value")
                    return node if node is not None else -math.inf
                bl.sort(key=keyfn, reverse=direction == "desc")
        end = frm + size if size is not None else None
        bl[:] = bl[frm:end]
        return {"_applied": True}
    return {"_applied": False}

