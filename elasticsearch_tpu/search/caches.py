"""Search-path caches + can_match shard pre-filtering.

Re-design of the reference's three search accelerators (SURVEY.md §2.5):

- **Shard request cache** (`indices/IndicesRequestCache.java`): caches whole
  shard-level query-phase results, keyed on the request body. Like the
  reference, only hits-free requests (size=0: aggs/counts) are cacheable by
  default — full hit payloads are cheap to recompute and expensive to hold —
  and an explicit `request_cache=true` opts in. Entries key on the reader
  generation, so a refresh that actually changed the shard naturally
  invalidates (the reference invalidates by reader identity the same way).
- **Node query cache** (`indices/IndicesQueryCache.java`): caches filter-
  context DocSet row arrays keyed (reader generation, filter source).
  Filters are score-free, so a cached row array is exact; scoring clauses
  are never cached (same as Lucene's UsageTrackingQueryCachingPolicy caching
  only filters).
- **can_match** (`CanMatchPreFilterSearchPhase.java:57`): a lightweight
  per-shard test — do the query's range constraints overlap the shard's
  field min/max? — that lets the coordinator skip shards before the query
  phase fans out.

Caches are node-level singletons shared by all shards (the reference sizes
them as a fraction of heap; here entry-count LRU bounds them).
"""

from __future__ import annotations

import hashlib
import json
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

import numpy as np


def _approx_bytes(value: Any, _depth: int = 0) -> int:
    """Approximate resident size of a cached entry: array payloads by
    nbytes, strings/bytes by length, containers by shallow recursion
    (bounded — a pathological deep value degrades to the flat estimate,
    which is fine for a stats gauge)."""
    if value is None or isinstance(value, (bool, int, float)):
        return 8
    if isinstance(value, np.ndarray):
        return int(value.nbytes)
    if isinstance(value, (str, bytes)):
        return len(value)
    if _depth >= 6:
        return 64
    if isinstance(value, dict):
        return 64 + sum(_approx_bytes(k, _depth + 1)
                        + _approx_bytes(v, _depth + 1)
                        for k, v in value.items())
    if isinstance(value, (list, tuple, set, frozenset)):
        return 64 + sum(_approx_bytes(v, _depth + 1) for v in value)
    nb = getattr(value, "nbytes", None)
    if isinstance(nb, (int, np.integer)):
        return int(nb)
    # opaque objects (ShardSearchResult, ...): sum their array slots
    d = getattr(value, "__dict__", None)
    if isinstance(d, dict) and _depth < 6:
        return 64 + sum(_approx_bytes(v, _depth + 1) for v in d.values())
    return 64


class LruCache:
    """Entry-count-bounded LRU with hit/miss/eviction/byte stats.

    Byte accounting is approximate (`_approx_bytes` at put time) but
    real: `memory_size_in_bytes` in `_nodes/stats` reports this gauge
    instead of the hardcoded 0 it used to."""

    def __init__(self, max_entries: int = 1024):
        import threading
        self.max_entries = max_entries
        self._map: "OrderedDict[Any, Any]" = OrderedDict()
        self._entry_bytes: Dict[Any, int] = {}
        # get/put race from client threads (node.search) and finalize
        # threads (hybrid executor); byte accounting + LRU eviction need
        # a consistent view
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.bytes = 0

    def get(self, key):
        with self._lock:
            try:
                value = self._map[key]
            except KeyError:
                self.misses += 1
                return None
            self._map.move_to_end(key)
            self.hits += 1
            return value

    def put(self, key, value) -> None:
        nb = _approx_bytes(key) + _approx_bytes(value)
        with self._lock:
            if key in self._map:
                self.bytes -= self._entry_bytes.get(key, 0)
            self._map[key] = value
            self._entry_bytes[key] = nb
            self.bytes += nb
            self._map.move_to_end(key)
            while len(self._map) > self.max_entries:
                old_key, _ = self._map.popitem(last=False)
                self.bytes -= self._entry_bytes.pop(old_key, 0)
                self.evictions += 1

    def clear(self) -> None:
        with self._lock:
            self._map.clear()
            self._entry_bytes.clear()
            self.bytes = 0

    def __len__(self):
        return len(self._map)

    def stats(self) -> dict:
        return {"entries": len(self._map), "hits": self.hits,
                "misses": self.misses, "evictions": self.evictions,
                "memory_size_in_bytes": self.bytes}


def _canonical(body: Any) -> str:
    return json.dumps(body, sort_keys=True, separators=(",", ":"),
                      default=str)


class RequestCache(LruCache):
    """Shard request cache: (shard key, reader epoch, body) -> query result.

    `cacheable(body)` mirrors `IndicesRequestCache` policy: size==0 requests
    cache by default; `request_cache` in the body forces either way; requests
    with non-deterministic parts (scripts, "now"-relative ranges) never cache.

    `skipped_uncacheable` counts requests that explicitly opted IN
    (`request_cache: true`) but were refused for being non-deterministic —
    without it those refusals read as ordinary misses and the stats-side
    hit-rate math overstates cold traffic.
    """

    def __init__(self, max_entries: int = 1024):
        super().__init__(max_entries)
        self.skipped_uncacheable = 0

    @staticmethod
    def deterministic(body: dict) -> bool:
        """False for bodies whose results can differ between identical
        requests (scripts, "now"-relative ranges) — never cacheable."""
        src = _canonical(body)
        return '"script' not in src and '"now' not in src.lower()

    @staticmethod
    def cacheable(body: dict) -> bool:
        flag = body.get("request_cache")
        if flag is False:
            return False
        if flag is not True and body.get("size", None) != 0:
            return False  # before _canonical: don't serialize large bodies
        return RequestCache.deterministic(body)

    def cacheable_tracked(self, body: dict) -> bool:
        """`cacheable` plus the opt-in bookkeeping: a body that asked
        for caching (`request_cache: true`) but is non-deterministic
        counts as `skipped_uncacheable`, not as a plain miss."""
        flag = body.get("request_cache")
        if flag is True and not self.deterministic(body):
            self.skipped_uncacheable += 1
            return False
        return self.cacheable(body)

    def device_cacheable(self, body: dict) -> bool:
        """Device-path extension: bodies whose query phase runs a device
        kNN dispatch cache by default even with size > 0 — the query
        phase (the matmul + top-k) is the expensive part and its result
        is small; the fetch phase re-runs per request against the same
        reader the fingerprint pinned. `request_cache: false` still
        opts out; non-deterministic parts still refuse."""
        flag = body.get("request_cache")
        if flag is False:
            return False
        q = body.get("query")
        has_knn = "knn" in body or (isinstance(q, dict) and "knn" in q)
        if not has_knn:
            # non-kNN bodies belong to the host rung's policy
            return False
        if not self.deterministic(body):
            if flag is True:
                self.skipped_uncacheable += 1
            return False
        return True

    def key(self, shard_key: Any, reader_epoch, body: dict) -> tuple:
        """`reader_epoch` is either the legacy reader generation or a
        content fingerprint tuple (`reader_fingerprint`) — the latter
        keeps entries valid across refreshes that changed nothing."""
        return (shard_key, reader_epoch, _canonical(
            {k: v for k, v in body.items() if k != "request_cache"}))

    def stats(self) -> dict:
        out = super().stats()
        out["skipped_uncacheable"] = self.skipped_uncacheable
        return out


# ---------------------------------------------------------------------------
# device-path request-cache keys
# ---------------------------------------------------------------------------

def reader_fingerprint(reader) -> tuple:
    """Content fingerprint of a point-in-time reader: per-segment
    (seg_id, num_docs, live_count) — the same identity the columnar
    block store keys its arrays on (`columnar/blocks.fingerprint`).

    Keying request-cache entries on this instead of `reader.gen` keeps
    them valid across refreshes that changed nothing (an idle index
    refreshing on its interval rotates gens but not content), while any
    ingest, delete, or merge rotates at least one component. Memoized on
    the reader: views snapshot their live bitmaps at construction, so a
    reader's fingerprint never changes after the first call."""
    fp = getattr(reader, "_content_fingerprint", None)
    if fp is None:
        from elasticsearch_tpu.columnar.blocks import fingerprint
        fp = reader._content_fingerprint = tuple(
            fingerprint(v) for v in reader.views)
    return fp


def value_fingerprint(body: Any) -> str:
    """Digest of a request body's VALUE slots, the complement of
    `plan_cache_key`'s shape normalization: where the plan key scrubs
    query vectors to dims and match text to placeholders (so plans
    dedupe), the request cache must distinguish those values — but
    without storing a 768-float JSON string per key. Vectors hash as
    raw f32 bytes; everything else as canonical JSON."""
    h = hashlib.blake2b(digest_size=16)

    def walk(node):
        if isinstance(node, dict):
            for k in sorted(node):
                v = node[k]
                h.update(k.encode())
                if k == "query_vector":
                    try:
                        arr = np.asarray(v, dtype=np.float32)
                        h.update(repr(arr.shape).encode())
                        h.update(arr.tobytes())
                    except (ValueError, TypeError):
                        walk(v)  # malformed vector: hash as plain JSON
                else:
                    walk(v)
        elif isinstance(node, (list, tuple)):
            h.update(b"[")
            for v in node:
                walk(v)
            h.update(b"]")
        else:
            h.update(_canonical(node).encode())

    walk(body)
    return h.hexdigest()


def request_cache_key(plan_key, body: dict, *, fingerprint, epoch=()) -> tuple:
    """Sanctioned device-path request-cache key (tpulint TPU005):

    - `plan_key`: the normalized shape key (`hybrid_plan.plan_cache_key` /
      `agg_plan.plan_cache_key`) — values already scrubbed;
    - `body`: hashed through `value_fingerprint` so distinct vectors /
      texts with the same shape stay distinct keys;
    - `fingerprint`: REQUIRED reader content fingerprint
      (`reader_fingerprint`) — refresh-driven invalidation lives here;
      a key without it serves stale bytes across refresh;
    - `epoch`: live settings the response depends on (max_buckets,
      allow-expensive, ...) so a settings change misses instead of
      serving results computed under the old limits."""
    body = {k: v for k, v in body.items()
            if k not in ("request_cache", "profile")}
    return (plan_key, value_fingerprint(body), tuple(fingerprint),
            tuple(epoch))


def has_range_clauses(query: Optional[dict]) -> bool:
    """True when the query carries at least one must/filter range clause —
    the coordinator's trigger for running the can_match pre-filter phase
    below the shard-count threshold (a time-range dashboard body over
    time-partitioned indices is exactly this shape)."""
    return next(_iter_range_clauses(query), None) is not None


class QueryCache(LruCache):
    """Node query cache: (reader gen, filter source) -> matching row array."""

    def get_rows(self, reader_gen: int, filter_source: str) -> Optional[np.ndarray]:
        return self.get((reader_gen, filter_source))

    def put_rows(self, reader_gen: int, filter_source: str,
                 rows: np.ndarray) -> None:
        self.put((reader_gen, filter_source), rows)


# ---------------------------------------------------------------------------
# can_match
# ---------------------------------------------------------------------------

def _iter_range_clauses(query: Optional[dict]):
    """Yield (field, spec) for every range clause that constrains the whole
    query (top-level range, or range inside bool.must / bool.filter — a
    `should` range does not constrain, matching the conservative skipping in
    the reference's coordinator rewrite)."""
    if not isinstance(query, dict):
        return
    for kind, spec in query.items():
        if kind == "range" and isinstance(spec, dict):
            for field, bounds in spec.items():
                if isinstance(bounds, dict):
                    yield field, bounds
        elif kind == "bool" and isinstance(spec, dict):
            for clause in ("must", "filter"):
                items = spec.get(clause, [])
                if isinstance(items, dict):
                    items = [items]
                for sub in items:
                    yield from _iter_range_clauses(sub)
        elif kind == "constant_score" and isinstance(spec, dict):
            yield from _iter_range_clauses(spec.get("filter"))


def _to_number(value, mapper_service, field, round_up: bool = False,
               fmt=None) -> Optional[float]:
    if isinstance(value, bool):
        return None
    if isinstance(value, (int, float)):
        return float(value)
    if isinstance(value, str):
        mapper = mapper_service.get(field) if mapper_service else None
        type_name = getattr(mapper, "type_name", None)
        if type_name == "date":
            try:
                from elasticsearch_tpu.index.mapping import (
                    parse_date_in_format, parse_date_millis)
                # mirror RangeQuery's rounding (queries.py _coerce_bound):
                # upper bounds round UP to unit end so can_match never
                # skips a shard the real query would hit
                millis = parse_date_in_format(value, fmt, round_up)
                if millis is not None:
                    return float(millis)
                return float(parse_date_millis(value, round_up=round_up))
            except Exception:
                return None
        try:
            return float(value)
        except ValueError:
            return None
    return None


def can_match(reader, mapper_service, body: dict) -> bool:
    """True unless a must/filter range clause provably excludes every live
    doc in this shard (field max < gte, or field min > lte). Conservative:
    anything unparseable means "might match"."""
    query = body.get("query")
    for field, bounds in _iter_range_clauses(query):
        stats = field_stats(reader, field)
        if stats is None:
            # field absent from the shard entirely: a required range on it
            # cannot match any doc
            if reader.num_docs > 0 and not _shard_has_field(reader, field):
                return False
            continue
        fmin, fmax = stats
        gte = _to_number(bounds.get("gte", bounds.get("gt")), mapper_service, field,
                         round_up="gte" not in bounds and "gt" in bounds,
                         fmt=bounds.get("format"))
        lte = _to_number(bounds.get("lte", bounds.get("lt")), mapper_service, field,
                         round_up="lte" in bounds, fmt=bounds.get("format"))
        if gte is not None:
            if "gt" in bounds and "gte" not in bounds:
                if fmax <= gte:
                    return False
            elif fmax < gte:
                return False
        if lte is not None:
            if "lt" in bounds and "lte" not in bounds:
                if fmin >= lte:
                    return False
            elif fmin > lte:
                return False
    return True


def _shard_has_field(reader, field: str) -> bool:
    for v in reader.views:
        if field in v.segment.doc_values or field in v.segment.postings:
            return True
    return False


def field_stats(reader, field: str) -> Optional[Tuple[float, float]]:
    """(min, max) of a numeric/date field over live docs, cached per reader
    (the per-shard PointValues min/max the reference's can_match reads)."""
    cache: Dict[str, Optional[Tuple[float, float]]] = getattr(
        reader, "_field_stats_cache", None)
    if cache is None:
        cache = reader._field_stats_cache = {}
    if field in cache:
        return cache[field]
    fmin = fmax = None
    for v in reader.views:
        col = v.segment.doc_values.get(field)
        if col is None or col.numeric is None:
            continue
        mask = v.live & col.present
        if not mask.any():
            continue
        vals = col.numeric[mask]
        lo, hi = float(vals.min()), float(vals.max())
        fmin = lo if fmin is None else min(fmin, lo)
        fmax = hi if fmax is None else max(fmax, hi)
    result = None if fmin is None else (fmin, fmax)
    cache[field] = result
    return result


class NodeCaches:
    """Node-level cache singletons (the reference wires both caches into
    IndicesService and shares them across shards).

    `device_request` is the device-path rung of the shard request cache:
    fused hybrid responses and kNN query-phase results, keyed through
    `request_cache_key` (plan key + value digest + reader fingerprint +
    settings epoch). A separate instance from the legacy host `request`
    cache so each rung's hit-rate math stays honest in stats."""

    def __init__(self, request_entries: int = 1024, query_entries: int = 2048,
                 device_request_entries: int = 512):
        self.request = RequestCache(request_entries)
        self.device_request = RequestCache(device_request_entries)
        self.query = QueryCache(query_entries)

    def stats(self) -> dict:
        return {"request_cache": self.request.stats(),
                "device_request_cache": self.device_request.stats(),
                "query_cache": self.query.stats()}
