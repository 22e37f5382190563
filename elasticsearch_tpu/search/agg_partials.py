"""Mergeable partial aggregation states for the distributed reduce.

Re-design of the reference's internal-aggregation reduce
(`search/aggregations/InternalAggregation.java` reduce(),
`action/search/SearchPhaseController.java:734`): shards never ship
finalized JSON for aggregations — they ship *partial states* (sum/count
pairs, HyperLogLog sketches for cardinality, t-digest sketches for
percentiles, per-term sub-agg trees) that the coordinator merges
associatively and finalizes once.  This is what makes `avg`,
`cardinality`, `percentiles`, and `terms`-with-sub-aggs correct across
shards with divergent data.

Three spec-driven walkers:

  compute_partial_aggs(ctx, rows, spec)  — per-shard, partial states
  merge_partial_aggs(a, b, spec)         — associative coordinator merge
  finalize_aggs(partial, spec)           — final JSON + pipeline aggs

Partial states are plain JSON-safe dicts tagged with "$p" so they
serialize over the node-to-node transport unchanged.
"""

from __future__ import annotations

import hashlib
import math
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from elasticsearch_tpu.common.errors import ParsingError
from elasticsearch_tpu.search import aggregations as A
from elasticsearch_tpu.search.aggregations import (
    BUCKET_AGGS, METRIC_AGGS, PIPELINE_AGGS, SearchContext, _hashable,
    _sort_key, all_values, numeric_values,
)

# single-bucket aggs: one {doc_count, subs...} object, no bucket list
SINGLE_BUCKET = {"filter", "global", "missing", "sampler", "nested",
                 "reverse_nested"}

# ---------------------------------------------------------------------------
# HyperLogLog (cardinality) — reference: HyperLogLogPlusPlus in
# search/aggregations/metrics/; here: classic HLL, p=12 (4096 registers,
# ~1.6% stderr), sparse representation below 512 occupied registers.
# ---------------------------------------------------------------------------

_HLL_P = 12
_HLL_M = 1 << _HLL_P
_HLL_ALPHA = 0.7213 / (1 + 1.079 / _HLL_M)
_HLL_SPARSE_MAX = 512


def _hll_hash(v) -> int:
    if isinstance(v, bool):
        b = b"b1" if v else b"b0"
    elif isinstance(v, (int, float)):
        b = repr(float(v)).encode()
    else:
        b = repr(v).encode()
    return int.from_bytes(hashlib.blake2b(b, digest_size=8).digest(), "big")


def _hll_from_values(values) -> dict:
    regs: Dict[int, int] = {}
    for v in values:
        h = _hll_hash(v)
        idx = h & (_HLL_M - 1)
        rest = h >> _HLL_P
        rank = (64 - _HLL_P) - rest.bit_length() + 1
        if rank > regs.get(idx, 0):
            regs[idx] = rank
    return _hll_pack(regs)


def _hll_pack(regs: Dict[int, int]) -> dict:
    if len(regs) <= _HLL_SPARSE_MAX:
        return {"$p": "hll", "sparse": {str(k): v for k, v in regs.items()}}
    dense = [0] * _HLL_M
    for k, v in regs.items():
        dense[k] = v
    return {"$p": "hll", "dense": dense}


def _hll_regs(state: dict) -> Dict[int, int]:
    if "sparse" in state:
        return {int(k): v for k, v in state["sparse"].items()}
    return {i: v for i, v in enumerate(state["dense"]) if v}


def _hll_merge(a: dict, b: dict) -> dict:
    regs = _hll_regs(a)
    for k, v in _hll_regs(b).items():
        if v > regs.get(k, 0):
            regs[k] = v
    return _hll_pack(regs)


def _hll_estimate(state: dict) -> int:
    regs = _hll_regs(state)
    zeros = _HLL_M - len(regs)
    inv_sum = zeros + sum(2.0 ** -r for r in regs.values())
    raw = _HLL_ALPHA * _HLL_M * _HLL_M / inv_sum
    if raw <= 2.5 * _HLL_M and zeros:
        raw = _HLL_M * math.log(_HLL_M / zeros)
    return int(round(raw))


# ---------------------------------------------------------------------------
# t-digest (percentiles / ranks / MAD / boxplot) — reference: TDigestState in
# search/aggregations/metrics/. Merging-digest variant; centroid weights are
# bounded by 4·W·q(1−q)/δ, so with ≤δ values the sketch is exact.
# ---------------------------------------------------------------------------

_TD_COMPRESSION = 200


def _td_compress(cents: List[List[float]]) -> List[List[float]]:
    if not cents:
        return []
    cents = sorted(cents)
    total = sum(w for _, w in cents)
    out: List[List[float]] = []
    cum = 0.0
    for mean, w in cents:
        if out:
            q = (cum + out[-1][1] / 2) / total
            limit = max(1.0, 4.0 * total * q * (1 - q) / _TD_COMPRESSION)
            if out[-1][1] + w <= limit:
                m0, w0 = out[-1]
                out[-1] = [(m0 * w0 + mean * w) / (w0 + w), w0 + w]
                continue
            cum += out[-1][1]
        out.append([float(mean), float(w)])
    return out


def _td_from_values(vals: np.ndarray) -> dict:
    cents = _td_compress([[float(v), 1.0] for v in vals])
    return {"$p": "tdigest",
            "c": cents,
            "min": float(vals.min()) if len(vals) else None,
            "max": float(vals.max()) if len(vals) else None,
            "n": int(len(vals))}


def _td_merge(a: dict, b: dict) -> dict:
    mins = [x for x in (a.get("min"), b.get("min")) if x is not None]
    maxs = [x for x in (a.get("max"), b.get("max")) if x is not None]
    return {"$p": "tdigest",
            "c": _td_compress([list(c) for c in a["c"]] + [list(c) for c in b["c"]]),
            "min": min(mins) if mins else None,
            "max": max(maxs) if maxs else None,
            "n": a.get("n", 0) + b.get("n", 0)}


def _td_quantile(state: dict, q: float) -> Optional[float]:
    cents = state["c"]
    if not cents:
        return None
    total = sum(w for _, w in cents)
    if total == 1 or len(cents) == 1:
        return cents[0][0] if len(cents) == 1 else None
    target = q * total
    # centroid i's mass is centered at cum + w/2
    cum = 0.0
    prev_mean, prev_mid = state["min"], 0.0
    for mean, w in cents:
        mid = cum + w / 2.0
        if target <= mid:
            if mid == prev_mid:
                return float(mean)
            t = (target - prev_mid) / (mid - prev_mid)
            return float(prev_mean + t * (mean - prev_mean))
        prev_mean, prev_mid = mean, mid
        cum += w
    return float(state["max"])


def _td_cdf(state: dict, x: float) -> float:
    cents = state["c"]
    if not cents:
        return 0.0
    total = sum(w for _, w in cents)
    if state["min"] is not None and x < state["min"]:
        return 0.0
    if state["max"] is not None and x >= state["max"]:
        return 1.0
    cum = 0.0
    prev_mean, prev_mid = state["min"], 0.0
    for mean, w in cents:
        mid = cum + w / 2.0
        if x < mean:
            if mean == prev_mean:
                return prev_mid / total
            t = (x - prev_mean) / (mean - prev_mean)
            return (prev_mid + t * (mid - prev_mid)) / total
        prev_mean, prev_mid = mean, mid
        cum += w
    return 1.0


# ---------------------------------------------------------------------------
# per-shard partial computation
# ---------------------------------------------------------------------------


def compute_partial_aggs(ctx: SearchContext, rows: np.ndarray,
                         aggs_spec: dict) -> dict:
    """Per-shard partial agg tree. Pipelines are deferred to finalize."""
    out: Dict[str, Any] = {}
    for name, spec in (aggs_spec or {}).items():
        if not isinstance(spec, dict):
            raise ParsingError(f"aggregation [{name}] must be an object")
        sub = spec.get("aggs") or spec.get("aggregations") or {}
        kinds = [k for k in spec if k not in ("aggs", "aggregations", "meta")]
        if len(kinds) != 1:
            raise ParsingError(f"aggregation [{name}] must define exactly one type")
        kind = kinds[0]
        if kind in PIPELINE_AGGS:
            continue
        if kind in METRIC_AGGS:
            out[name] = _compute_metric_partial(ctx, rows, kind, spec[kind])
        elif kind in BUCKET_AGGS or kind in ("nested", "reverse_nested"):
            sub_normal = {
                sname: sspec for sname, sspec in sub.items()
                if not _is_pipeline(sspec)
            }
            out[name] = A._compute_bucket(
                ctx, rows, kind, _partial_spec(kind, spec[kind]), sub_normal,
                recurse=compute_partial_aggs)
        else:
            raise ParsingError(f"unknown aggregation type [{kind}]")
    return out


def _is_pipeline(sspec: dict) -> bool:
    skinds = [k for k in sspec if k not in ("aggs", "aggregations", "meta")]
    return len(skinds) == 1 and skinds[0] in PIPELINE_AGGS


def _partial_spec(kind: str, spec: dict) -> dict:
    """Shard-side spec: ordering/pruning/threshold filtering move to the
    coordinator (post-merge), and per-shard candidate sets are bounded by
    `shard_size` exactly like the reference (TermsAggregatorFactory:
    shard_size defaults to size*1.5+10) so a high-cardinality field does
    not ship its full term dictionary."""
    if kind == "terms":
        s = {k: v for k, v in spec.items() if k != "order"}
        size = int(spec.get("size", 10))
        s["size"] = int(spec.get("shard_size") or (size * 3 // 2 + 10))
        return s
    if kind in ("significant_terms", "significant_text"):
        # ship unpruned candidates: the min_doc_count threshold and JLH
        # ranking re-apply at the coordinator over merged fg/bg counts
        size = int(spec.get("size", 10))
        return {**spec, "min_doc_count": 1,
                "size": int(spec.get("shard_size") or (size * 3 // 2 + 10))}
    if kind == "rare_terms":
        # unpruned counts (max_doc_count filter applies post-merge); the
        # shard_size cap bounds the rarest-candidates set per shard, the
        # role the reference's CuckooFilters play
        return {**spec, "max_doc_count": 1 << 60,
                "size": int(spec.get("shard_size", 1000))}
    if kind in ("geohash_grid", "geotile_grid"):
        size = int(spec.get("size", 10000))
        return {**spec,
                "size": int(spec.get("shard_size") or (size * 3 // 2 + 10))}
    if kind in ("histogram", "date_histogram"):
        # -1: disable threshold pruning WITHOUT enabling the per-shard
        # zero-fill that min_doc_count=0 implies (the coordinator
        # re-fills gaps after the merge)
        return {**spec, "min_doc_count": -1}
    return spec


def _metric_numeric(ctx, rows, spec):
    field = spec.get("field")
    script = spec.get("script")
    if script is not None and field is None:
        from elasticsearch_tpu.search.script_score import Script
        s = Script(script)
        vals = s.evaluate(ctx, rows,
                          np.zeros(len(rows), dtype=np.float32)).astype(np.float64)
        return vals, np.ones(len(rows), dtype=bool)
    return numeric_values(ctx, rows, field, spec.get("missing"))


def _compute_metric_partial(ctx: SearchContext, rows: np.ndarray, kind: str,
                            spec: dict) -> dict:
    field = spec.get("field")

    if kind == "scripted_metric":
        # the shard ships its COMBINED state (init+map+combine run here);
        # reduce_script runs once at the coordinator over all states —
        # exactly the reference's wire contract (ScriptedMetricAggregator
        # ships InternalScriptedMetric with the combine result)
        return {"$p": "scripted_metric",
                "states": [A.scripted_metric_map_combine(ctx, rows, spec)]}

    if kind == "value_count":
        n = len(rows) if field is None else len(all_values(ctx, rows, field))
        return {"$p": "value_count", "n": int(n)}

    if kind == "cardinality":
        return _hll_from_values(
            _hashable(v) for _, v in all_values(ctx, rows, field))

    if kind == "top_hits":
        final = A.compute_metric(ctx, rows, "top_hits", spec)
        return {"$p": "top_hits", "size": int(spec.get("size", 3)),
                "total": final["hits"]["total"]["value"],
                "hits": final["hits"]["hits"]}

    if kind == "top_metrics":
        final = A.compute_top_metrics(ctx, rows, spec)
        return {"$p": "top_metrics", "top": final["top"]}

    if kind == "string_stats":
        values = [str(v) for _, v in all_values(ctx, rows, field)]
        freq: Dict[str, int] = {}
        for v in values:
            for ch in v:
                freq[ch] = freq.get(ch, 0) + 1
        return {"$p": "string_stats", "n": len(values),
                "len_sum": sum(len(v) for v in values),
                "min_len": min((len(v) for v in values), default=None),
                "max_len": max((len(v) for v in values), default=None),
                "freq": freq}

    if kind == "matrix_stats":
        return _matrix_partial(ctx, rows, spec)

    if kind in ("geo_bounds", "geo_centroid"):
        pts = A._gather_geo_points(ctx, rows, field)
        if kind == "geo_bounds":
            if not pts:
                return {"$p": "geo_bounds", "n": 0}
            lats = [p[1] for p in pts]
            lons = [p[2] for p in pts]
            return {"$p": "geo_bounds", "n": len(pts),
                    "minlat": min(lats), "maxlat": max(lats),
                    "minlon": min(lons), "maxlon": max(lons)}
        return {"$p": "geo_centroid", "n": len(pts),
                "lat_sum": sum(p[1] for p in pts),
                "lon_sum": sum(p[2] for p in pts)}

    if kind == "weighted_avg":
        vspec = spec.get("value", {})
        wspec = spec.get("weight", {})
        vv, vp = numeric_values(ctx, rows, vspec.get("field"), vspec.get("missing"))
        wv, wp = numeric_values(ctx, rows, wspec.get("field"),
                                wspec.get("missing", 1.0))
        both = vp & wp
        return {"$p": "weighted_avg",
                "vw": float((vv[both] * wv[both]).sum()),
                "w": float(wv[both].sum())}

    vals, present = _metric_numeric(ctx, rows, spec)
    v = vals[present]

    if kind == "avg":
        return {"$p": "avg", "sum": float(v.sum()), "n": int(len(v))}
    if kind == "sum":
        return {"$p": "sum", "sum": float(v.sum())}
    if kind == "min":
        return {"$p": "min", "v": float(v.min()) if len(v) else None}
    if kind == "max":
        return {"$p": "max", "v": float(v.max()) if len(v) else None}
    if kind == "stats":
        return {"$p": "stats", "n": int(len(v)), "sum": float(v.sum()),
                "min": float(v.min()) if len(v) else None,
                "max": float(v.max()) if len(v) else None}
    if kind == "extended_stats":
        return {"$p": "extended_stats", "n": int(len(v)), "sum": float(v.sum()),
                "ss": float((v ** 2).sum()),
                "min": float(v.min()) if len(v) else None,
                "max": float(v.max()) if len(v) else None}
    if kind in ("percentiles", "percentile_ranks",
                "median_absolute_deviation", "boxplot"):
        return _td_from_values(v)
    raise ParsingError(f"unknown metric aggregation [{kind}]")


def _matrix_partial(ctx, rows, spec) -> dict:
    fields = spec.get("fields", [])
    cols, presents = {}, {}
    for f in fields:
        cols[f], presents[f] = numeric_values(ctx, rows, f)
    if fields:
        mask = np.logical_and.reduce([presents[f] for f in fields])
    else:
        mask = np.zeros(0, dtype=bool)
    n = int(mask.sum())
    # power sums merge by addition; moments are recovered at finalize
    s = {f: [float((cols[f][mask] ** k).sum()) for k in (1, 2, 3, 4)]
         for f in fields}
    sxy = {}
    for i, f in enumerate(fields):
        for g in fields[i + 1:]:
            sxy[f + "|" + g] = float((cols[f][mask] * cols[g][mask]).sum())
    return {"$p": "matrix_stats", "n": n, "fields": list(fields),
            "s": s, "sxy": sxy}


# ---------------------------------------------------------------------------
# coordinator merge
# ---------------------------------------------------------------------------


def merge_partial_aggs(a: dict, b: dict, aggs_spec: dict) -> dict:
    out = dict(a)
    for name, spec in (aggs_spec or {}).items():
        kinds = [k for k in spec if k not in ("aggs", "aggregations", "meta")]
        if len(kinds) != 1 or kinds[0] in PIPELINE_AGGS:
            continue
        kind = kinds[0]
        if name not in b:
            continue
        if name not in out:
            out[name] = b[name]
            continue
        sub = spec.get("aggs") or spec.get("aggregations") or {}
        sub = {sn: ss for sn, ss in sub.items() if not _is_pipeline(ss)}
        if kind in METRIC_AGGS:
            out[name] = _merge_metric(out[name], b[name])
        else:
            out[name] = _merge_bucket_agg(kind, spec[kind], out[name],
                                          b[name], sub)
    return out


def _merge_metric(a: dict, b: dict) -> dict:
    tag = a.get("$p")
    if tag != b.get("$p"):
        raise ParsingError(f"partial agg mismatch: {tag} vs {b.get('$p')}")
    if tag == "hll":
        return _hll_merge(a, b)
    if tag == "tdigest":
        return _td_merge(a, b)
    if tag == "value_count":
        return {"$p": tag, "n": a["n"] + b["n"]}
    if tag == "scripted_metric":
        return {"$p": tag, "states": a["states"] + b["states"]}
    if tag == "avg":
        return {"$p": tag, "sum": a["sum"] + b["sum"], "n": a["n"] + b["n"]}
    if tag == "sum":
        return {"$p": tag, "sum": a["sum"] + b["sum"]}
    if tag in ("min", "max"):
        vs = [x for x in (a["v"], b["v"]) if x is not None]
        pick = (min if tag == "min" else max)(vs) if vs else None
        return {"$p": tag, "v": pick}
    if tag == "stats":
        return {"$p": tag, "n": a["n"] + b["n"], "sum": a["sum"] + b["sum"],
                "min": _opt(min, a["min"], b["min"]),
                "max": _opt(max, a["max"], b["max"])}
    if tag == "extended_stats":
        return {"$p": tag, "n": a["n"] + b["n"], "sum": a["sum"] + b["sum"],
                "ss": a["ss"] + b["ss"],
                "min": _opt(min, a["min"], b["min"]),
                "max": _opt(max, a["max"], b["max"])}
    if tag == "weighted_avg":
        return {"$p": tag, "vw": a["vw"] + b["vw"], "w": a["w"] + b["w"]}
    if tag == "geo_bounds":
        if not a["n"]:
            return b
        if not b["n"]:
            return a
        return {"$p": tag, "n": a["n"] + b["n"],
                "minlat": min(a["minlat"], b["minlat"]),
                "maxlat": max(a["maxlat"], b["maxlat"]),
                "minlon": min(a["minlon"], b["minlon"]),
                "maxlon": max(a["maxlon"], b["maxlon"])}
    if tag == "geo_centroid":
        return {"$p": tag, "n": a["n"] + b["n"],
                "lat_sum": a["lat_sum"] + b["lat_sum"],
                "lon_sum": a["lon_sum"] + b["lon_sum"]}
    if tag == "top_hits":
        return {"$p": tag, "size": a["size"], "total": a["total"] + b["total"],
                "hits": (a["hits"] + b["hits"])[:a["size"]]}
    if tag == "top_metrics":
        return {"$p": tag, "top": a["top"] + b["top"]}
    if tag == "string_stats":
        freq = dict(a["freq"])
        for ch, c in b["freq"].items():
            freq[ch] = freq.get(ch, 0) + c
        return {"$p": tag, "n": a["n"] + b["n"],
                "len_sum": a["len_sum"] + b["len_sum"],
                "min_len": _opt(min, a["min_len"], b["min_len"]),
                "max_len": _opt(max, a["max_len"], b["max_len"]),
                "freq": freq}
    if tag == "matrix_stats":
        s = {f: [x + y for x, y in zip(a["s"][f], b["s"][f])]
             for f in a["fields"]}
        sxy = {k: a["sxy"][k] + b["sxy"][k] for k in a["sxy"]}
        return {"$p": tag, "n": a["n"] + b["n"], "fields": a["fields"],
                "s": s, "sxy": sxy}
    raise ParsingError(f"unmergeable partial state [{tag}]")


def _opt(fn, *vals):
    vs = [v for v in vals if v is not None]
    return fn(vs) if vs else None


def _bucket_key(kind: str, bucket: dict):
    key = bucket.get("key")
    if isinstance(key, dict):  # composite
        return tuple(sorted(key.items()))
    return _hashable(key)


def _merge_buckets(kind: str, a_bucket: dict, b_bucket: dict,
                   sub_spec: dict) -> dict:
    m = dict(a_bucket)
    m["doc_count"] = a_bucket.get("doc_count", 0) + b_bucket.get("doc_count", 0)
    if "bg_count" in a_bucket or "bg_count" in b_bucket:
        # significant buckets: background freqs sum; the score recomputes
        # at finalize from the merged counts (SignificanceHeuristic)
        m["bg_count"] = a_bucket.get("bg_count", 0) + b_bucket.get("bg_count", 0)
    a_subs = {n: a_bucket[n] for n in (sub_spec or {}) if n in a_bucket}
    b_subs = {n: b_bucket[n] for n in (sub_spec or {}) if n in b_bucket}
    m.update(merge_partial_aggs(a_subs, b_subs, sub_spec))
    return m


def _merge_bucket_agg(kind: str, spec: dict, a, b, sub_spec: dict):
    if kind in SINGLE_BUCKET:
        return _merge_buckets(kind, a, b, sub_spec)

    if kind == "filters":
        if isinstance(a.get("buckets"), dict):
            merged = dict(a["buckets"])
            for bname, bb in b.get("buckets", {}).items():
                merged[bname] = (_merge_buckets(kind, merged[bname], bb, sub_spec)
                                 if bname in merged else bb)
            return {**a, "buckets": merged}
        merged_list = []
        bl = b.get("buckets", [])
        for i, ab in enumerate(a.get("buckets", [])):
            merged_list.append(_merge_buckets(kind, ab, bl[i], sub_spec)
                               if i < len(bl) else ab)
        merged_list.extend(bl[len(merged_list):])
        return {**a, "buckets": merged_list}

    if kind == "auto_date_histogram":
        ia = int(str(a.get("interval", "1ms")).rstrip("ms") or 1)
        ib = int(str(b.get("interval", "1ms")).rstrip("ms") or 1)
        interval = max(ia, ib)
        a_buckets = _rebucket(a.get("buckets", []), interval, sub_spec)
        b_buckets = _rebucket(b.get("buckets", []), interval, sub_spec)
        merged = _merge_keyed(kind, a_buckets, b_buckets, sub_spec)
        return {"buckets": merged, "interval": f"{interval}ms"}

    # keyed bucket lists: terms/histograms/ranges/grids/composite/adjacency
    merged = _merge_keyed(kind, a.get("buckets", []), b.get("buckets", []),
                          sub_spec)
    out = {**a, "buckets": merged}
    out.pop("after_key", None)  # recomputed at finalize (composite)
    if "sum_other_doc_count" in out:
        out["sum_other_doc_count"] = (a.get("sum_other_doc_count", 0)
                                      + b.get("sum_other_doc_count", 0))
    for k in ("doc_count", "bg_count"):  # significant_* totals
        if k in a or k in b:
            out[k] = a.get(k, 0) + b.get(k, 0)
    return out


def _merge_keyed(kind: str, a_buckets: list, b_buckets: list,
                 sub_spec: dict) -> list:
    index: Dict[Any, int] = {}
    merged: List[dict] = []
    for bucket in a_buckets:
        index[_bucket_key(kind, bucket)] = len(merged)
        merged.append(bucket)
    for bucket in b_buckets:
        k = _bucket_key(kind, bucket)
        if k in index:
            merged[index[k]] = _merge_buckets(kind, merged[index[k]],
                                              bucket, sub_spec)
        else:
            index[k] = len(merged)
            merged.append(bucket)
    return merged


def _rebucket(buckets: list, interval: int, sub_spec: dict) -> list:
    """Re-floor date_histogram buckets onto a coarser interval, merging
    sub-agg partials of collapsed buckets (auto_date_histogram reduce)."""
    out: Dict[float, dict] = {}
    for bucket in buckets:
        key = float(np.floor(float(bucket["key"]) / interval) * interval)
        if key in out:
            out[key] = _merge_buckets("date_histogram", out[key],
                                      {**bucket, "key": key}, sub_spec)
        else:
            out[key] = {**bucket, "key": int(key),
                        "key_as_string": A._millis_to_iso(int(key))}
    return [out[k] for k in sorted(out)]


# ---------------------------------------------------------------------------
# finalize (coordinator, once, after all merges)
# ---------------------------------------------------------------------------


def finalize_aggs(partial: dict, aggs_spec: dict) -> dict:
    out: Dict[str, Any] = {}
    pipelines: List[Tuple[str, str, dict]] = []
    for name, spec in (aggs_spec or {}).items():
        kinds = [k for k in spec if k not in ("aggs", "aggregations", "meta")]
        kind = kinds[0]
        if kind in PIPELINE_AGGS:
            pipelines.append((name, kind, spec[kind]))
            continue
        if name not in partial:
            continue
        sub = spec.get("aggs") or spec.get("aggregations") or {}
        if kind in METRIC_AGGS:
            out[name] = _finalize_metric(kind, spec[kind], partial[name])
            continue
        sub_normal = {sn: ss for sn, ss in sub.items() if not _is_pipeline(ss)}
        sub_pipes = [(sn, next(k for k in ss if k not in ("aggs", "aggregations", "meta")), ss)
                     for sn, ss in sub.items() if _is_pipeline(ss)]
        out[name] = _finalize_bucket_agg(kind, spec[kind], partial[name],
                                         sub_normal)
        # parent pipelines (cumulative_sum/derivative/... as sub-aggs) run on
        # the final bucket list, same as compute_aggs
        for pname, pkind, psub in sub_pipes:
            pspec = dict(psub[pkind])
            wrapper = {"__parent__": out[name]}
            bp = pspec.get("buckets_path")
            if isinstance(bp, str):
                pspec["buckets_path"] = "__parent__>" + bp
            elif isinstance(bp, dict):
                pspec["buckets_path"] = {k: "__parent__>" + v
                                         for k, v in bp.items()}
            res = A._compute_pipeline(wrapper, pkind, pspec, pname)
            if not (isinstance(res, dict) and "_applied" in res):
                out[name].setdefault("__pipeline_results__", {})[pname] = res
    for name, kind, spec in pipelines:
        res = A._compute_pipeline(out, kind, spec, name)
        if not (isinstance(res, dict) and "_applied" in res):
            out[name] = res
    return out


def _finalize_metric(kind: str, spec: dict, state: dict):
    if kind == "value_count":
        return {"value": state["n"]}
    if kind == "scripted_metric":
        return {"value": A.scripted_metric_reduce(spec, state["states"])}
    if kind == "cardinality":
        return {"value": _hll_estimate(state)}
    if kind == "avg":
        return {"value": state["sum"] / state["n"] if state["n"] else None}
    if kind == "sum":
        return {"value": state["sum"]}
    if kind in ("min", "max"):
        return {"value": state["v"]}
    if kind == "stats":
        n = state["n"]
        return {"count": n, "min": state["min"], "max": state["max"],
                "avg": state["sum"] / n if n else None,
                "sum": state["sum"]}
    if kind == "extended_stats":
        n = state["n"]
        base = {"count": n, "min": state["min"], "max": state["max"],
                "avg": state["sum"] / n if n else None, "sum": state["sum"]}
        if n == 0:
            base.update({"sum_of_squares": None, "variance": None,
                         "std_deviation": None,
                         "std_deviation_bounds": {"upper": None, "lower": None}})
            return base
        mean = state["sum"] / n
        var = max(state["ss"] / n - mean * mean, 0.0)
        std = math.sqrt(var)
        sigma = float(spec.get("sigma", 2.0))
        base.update({
            "sum_of_squares": state["ss"], "variance": var,
            "variance_population": var,
            "variance_sampling": (max(state["ss"] - n * mean * mean, 0.0)
                                  / (n - 1)) if n > 1 else 0.0,
            "std_deviation": std,
            "std_deviation_bounds": {"upper": mean + sigma * std,
                                     "lower": mean - sigma * std},
        })
        return base
    if kind == "weighted_avg":
        return {"value": state["vw"] / state["w"] if state["w"] else None}
    if kind == "percentiles":
        pcts = spec.get("percents", [1, 5, 25, 50, 75, 95, 99])
        return {"values": {f"{float(p)}": _td_quantile(state, p / 100.0)
                           for p in pcts}}
    if kind == "percentile_ranks":
        targets = spec.get("values", [])
        empty = not state["c"]
        return {"values": {
            f"{float(t)}": None if empty else 100.0 * _td_cdf(state, float(t))
            for t in targets}}
    if kind == "median_absolute_deviation":
        return {"value": _td_mad(state)}
    if kind == "boxplot":
        return _finalize_boxplot(state)
    if kind == "geo_bounds":
        if not state["n"]:
            return {"bounds": None}
        return {"bounds": {
            "top_left": {"lat": state["maxlat"], "lon": state["minlon"]},
            "bottom_right": {"lat": state["minlat"], "lon": state["maxlon"]}}}
    if kind == "geo_centroid":
        if not state["n"]:
            return {"count": 0}
        return {"location": {"lat": state["lat_sum"] / state["n"],
                             "lon": state["lon_sum"] / state["n"]},
                "count": state["n"]}
    if kind == "top_hits":
        return {"hits": {"total": {"value": state["total"], "relation": "eq"},
                         "hits": state["hits"][:state["size"]]}}
    if kind == "top_metrics":
        size = int(spec.get("size", 1))
        order = _top_metrics_order(spec)
        top = sorted(state["top"],
                     key=lambda t: t["sort"][0],
                     reverse=(order == "desc"))
        return {"top": top[:size]}
    if kind == "string_stats":
        return _finalize_string_stats(spec, state)
    if kind == "matrix_stats":
        return _finalize_matrix(state)
    raise ParsingError(f"unknown metric aggregation [{kind}]")


def _top_metrics_order(spec) -> str:
    sort_spec = spec.get("sort", [{"_doc": "asc"}])
    if isinstance(sort_spec, (str, dict)):
        sort_spec = [sort_spec]
    entry = sort_spec[0]
    if isinstance(entry, str):
        return "asc"
    _, order = next(iter(entry.items()))
    if isinstance(order, dict):
        order = order.get("order", "asc")
    return order


def _td_mad(state: dict):
    if not state["c"]:
        return None
    med = _td_quantile(state, 0.5)
    lo, hi = 0.0, max(state["max"] - state["min"], 0.0)
    if hi == 0.0:
        return 0.0
    for _ in range(50):
        mid = (lo + hi) / 2
        mass = _td_cdf(state, med + mid) - _td_cdf(state, med - mid)
        if mass >= 0.5:
            hi = mid
        else:
            lo = mid
    return hi


def _finalize_boxplot(state: dict):
    if not state["c"]:
        return {"min": None, "max": None, "q1": None, "q2": None,
                "q3": None, "lower": None, "upper": None}
    q1, q2, q3 = (_td_quantile(state, q) for q in (0.25, 0.5, 0.75))
    iqr = q3 - q1
    inside = [m for m, _ in state["c"]
              if q1 - 1.5 * iqr <= m <= q3 + 1.5 * iqr]
    return {"min": state["min"], "max": state["max"],
            "q1": q1, "q2": q2, "q3": q3,
            "lower": min(inside) if inside else q1,
            "upper": max(inside) if inside else q3}


def _finalize_string_stats(spec: dict, state: dict):
    if state["n"] == 0:
        return {"count": 0, "min_length": None, "max_length": None,
                "avg_length": None, "entropy": 0.0}
    total_chars = sum(state["freq"].values())
    entropy = 0.0
    for c in state["freq"].values():
        p = c / total_chars
        entropy -= p * math.log2(p)
    out = {"count": state["n"], "min_length": state["min_len"],
           "max_length": state["max_len"],
           "avg_length": state["len_sum"] / state["n"],
           "entropy": round(entropy, 10)}
    if spec.get("show_distribution"):
        out["distribution"] = {ch: c / total_chars
                               for ch, c in sorted(state["freq"].items())}
    return out


def _finalize_matrix(state: dict):
    n = state["n"]
    fields = state["fields"]
    if n == 0:
        return {"doc_count": 0, "fields": []}
    mean = {f: state["s"][f][0] / n for f in fields}
    var = {f: max((state["s"][f][1] - n * mean[f] ** 2) / (n - 1), 0.0)
           if n > 1 else 0.0 for f in fields}
    sd = {f: math.sqrt(var[f]) for f in fields}

    def comoment(f, g):
        if f == g:
            return state["s"][f][1] - n * mean[f] ** 2
        k = f + "|" + g if f + "|" + g in state["sxy"] else g + "|" + f
        return state["sxy"][k] - n * mean[f] * mean[g]

    out_fields = []
    for f in fields:
        s1, s2, s3, s4 = state["s"][f]
        if sd[f]:
            m = mean[f]
            # central power sums from raw power sums
            c3 = s3 - 3 * m * s2 + 2 * n * m ** 3
            c4 = s4 - 4 * m * s3 + 6 * m * m * s2 - 3 * n * m ** 4
            pop_var = max(s2 / n - m * m, 0.0)
            psd = math.sqrt(pop_var)
            skew = (c3 / n) / psd ** 3 if psd else 0.0
            kurt = (c4 / n) / psd ** 4 if psd else 0.0
        else:
            skew = kurt = 0.0
        cov = {}
        corr = {}
        for g in fields:
            c = comoment(f, g) / (n - 1) if n > 1 else 0.0
            cov[g] = c
            corr[g] = (c / (sd[f] * sd[g])) if sd[f] and sd[g] else (
                1.0 if f == g else 0.0)
        out_fields.append({"name": f, "count": n, "mean": mean[f],
                           "variance": var[f], "skewness": skew,
                           "kurtosis": kurt, "covariance": cov,
                           "correlation": corr})
    return {"doc_count": n, "fields": out_fields}


def _finalize_bucket_agg(kind: str, spec: dict, node, sub_spec: dict):
    if kind in SINGLE_BUCKET:
        return _finalize_one_bucket(node, sub_spec)

    if kind == "filters":
        if isinstance(node.get("buckets"), dict):
            return {"buckets": {n: _finalize_one_bucket(b, sub_spec)
                                for n, b in node["buckets"].items()}}
        return {"buckets": [_finalize_one_bucket(b, sub_spec)
                            for b in node.get("buckets", [])]}

    if kind == "auto_date_histogram":
        # coarsen on the RAW partial buckets (sub-agg states still
        # mergeable), then finalize once
        target = int(spec.get("buckets", 10))
        interval = int(str(node.get("interval", "1ms")).rstrip("ms") or 1)
        raw = node.get("buckets", [])
        while len(raw) > target:
            for unit in (1, 1000, 60_000, 3_600_000, 86_400_000,
                         2_592_000_000, 31_536_000_000):
                if unit > interval:
                    interval = unit
                    break
            else:
                interval *= 2
            raw = _rebucket(raw, interval, sub_spec)
        buckets = [_finalize_one_bucket(b, sub_spec) for b in raw]
        buckets.sort(key=lambda b: float(b["key"]))
        return {"buckets": buckets, "interval": f"{interval}ms"}

    buckets = [_finalize_one_bucket(b, sub_spec)
               for b in node.get("buckets", [])]

    if kind in ("significant_terms", "significant_text"):
        size = int(spec.get("size", 10))
        min_count = int(spec.get("min_doc_count", 3))
        fg_total = int(node.get("doc_count", 0))
        bg_total = int(node.get("bg_count", 0)) or fg_total
        rescored = []
        for b in buckets:
            fg, bg = b.get("doc_count", 0), b.get("bg_count", 0)
            if fg < min_count or bg == 0:
                continue
            fg_freq = fg / fg_total if fg_total else 0.0
            bg_freq = bg / bg_total if bg_total else 0.0
            if fg_freq <= bg_freq or bg_freq == 0:
                continue
            rescored.append({**b, "score":
                             (fg_freq - bg_freq) * (fg_freq / bg_freq)})
        rescored.sort(key=lambda b: (-b["score"], _sort_key(b["key"])))
        return {"doc_count": fg_total, "bg_count": bg_total,
                "buckets": rescored[:size]}

    if kind == "terms":
        size = int(spec.get("size", 10))
        order_spec = spec.get("order")
        if order_spec and isinstance(order_spec, dict):
            ((okey, odir),) = order_spec.items()
            reverse = odir == "desc"
            if okey == "_key":
                buckets.sort(key=lambda b: _sort_key(b["key"]), reverse=reverse)
            elif okey == "_count":
                buckets.sort(key=lambda b: b["doc_count"], reverse=reverse)
            else:
                def metric_val(b, path=okey):
                    v = b
                    for part in path.split("."):
                        v = v.get(part) if isinstance(v, dict) else None
                    if isinstance(v, (int, float)):
                        return v
                    return (v or {}).get("value", 0) if isinstance(v, dict) else 0
                buckets.sort(key=metric_val, reverse=reverse)
        else:
            buckets.sort(key=lambda b: (-b["doc_count"], _sort_key(b["key"])))
        other = sum(b["doc_count"] for b in buckets[size:])
        return {"doc_count_error_upper_bound": 0,
                "sum_other_doc_count": int(other), "buckets": buckets[:size]}

    if kind == "rare_terms":
        max_count = int(spec.get("max_doc_count", 1))
        buckets = [b for b in buckets if b["doc_count"] <= max_count]
        buckets.sort(key=lambda b: (b["doc_count"], _sort_key(b["key"])))
        return {"doc_count_error_upper_bound": 0, "sum_other_doc_count": 0,
                "buckets": buckets}

    if kind in ("histogram", "date_histogram"):
        min_count = int(spec.get("min_doc_count", 0))
        buckets.sort(key=lambda b: float(b["key"]))
        if min_count > 0:
            buckets = [b for b in buckets if b["doc_count"] >= min_count]
        elif buckets and kind == "histogram" and spec.get("interval"):
            buckets = _fill_gaps(buckets, float(spec["interval"]), date=False)
        elif buckets and kind == "date_histogram":
            interval_ms, calendar = A._date_interval(spec)
            if calendar:
                tz = A._resolve_tz(spec.get("time_zone"))
                keys = A._calendar_keys(
                    float(buckets[0]["key"]), float(buckets[-1]["key"]),
                    calendar, tz, A._date_offset_ms(spec.get("offset")))
                buckets = _fill_date_keys(buckets, keys, tz)
            else:
                buckets = _fill_gaps(buckets, interval_ms, date=True)
        return {"buckets": buckets}

    if kind in ("geohash_grid", "geotile_grid"):
        size = int(spec.get("size", 10000))
        buckets.sort(key=lambda b: (-b["doc_count"], b["key"]))
        return {"buckets": buckets[:size]}

    if kind == "composite":
        size = int(spec.get("size", 10))
        names = [next(iter(src)) for src in spec.get("sources", [])]
        buckets.sort(key=lambda b: tuple(_sort_key(b["key"].get(n))
                                         for n in names))
        buckets = buckets[:size]
        out = {"buckets": buckets}
        if buckets:
            out["after_key"] = buckets[-1]["key"]
        return out

    if kind == "adjacency_matrix":
        buckets.sort(key=lambda b: b["key"])
        return {"buckets": buckets}

    # range / date_range / ip_range: keep spec order (a-side first)
    return {**{k: v for k, v in node.items() if k != "buckets"},
            "buckets": buckets}


def _finalize_one_bucket(bucket: dict, sub_spec: dict) -> dict:
    out = {k: v for k, v in bucket.items() if k not in (sub_spec or {})}
    if sub_spec:
        subs = {n: bucket[n] for n in sub_spec if n in bucket}
        out.update(finalize_aggs(subs, sub_spec))
    return out


def _fill_date_keys(buckets: List[dict], keys: List[float],
                    tz=None) -> List[dict]:
    """`buckets` with a zero bucket at every one of `keys` that none of
    them holds (a calendar interval's gaps: the keys are boundaries, not
    an arithmetic progression)."""
    by_key = {float(b["key"]): b for b in buckets}
    return [by_key.get(float(k))
            or {"key": int(k), "doc_count": 0,
                "key_as_string": A._millis_to_iso_tz(int(k), tz)}
            for k in keys]


def _fill_gaps(buckets: List[dict], interval: float, date: bool) -> List[dict]:
    """Zero-fill inter-shard gaps after the merge (min_doc_count=0)."""
    if not buckets or interval <= 0:
        return buckets
    out = []
    cur = float(buckets[0]["key"])
    by_key = {float(b["key"]): b for b in buckets}
    last = float(buckets[-1]["key"])
    guard = 0
    while cur <= last + 1e-9 and guard < 100_000:
        b = by_key.get(round(cur, 10)) or by_key.get(cur)
        if b is None:
            b = {"key": int(cur) if date else round(cur, 10), "doc_count": 0}
            if date:
                b["key_as_string"] = A._millis_to_iso(int(cur))
        out.append(b)
        cur += interval
        guard += 1
    return out if guard < 100_000 else buckets
