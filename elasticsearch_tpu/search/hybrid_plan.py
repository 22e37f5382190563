"""Fused hybrid execution: one plan, one dispatch per leg kind, RRF, fetch.

Before this module, a hybrid `rank: {rrf}` search paid per query: a DSL
parse, a host-Python BM25 pass per term, a device round-trip for the kNN
leg, a dict-based fusion, and a fetch — and only the kNN leg's device
dispatch could coalesce with concurrent traffic. This is the structural
reason config 3 was the record's one losing row vs the reference's
BulkScorer (`QueryPhase.java:171`).

The fused path compiles the body ONCE into a `HybridPlan` (cached per
index, keyed on the normalized body — repeated shapes skip parse/plan
entirely) and executes whole *batches* of hybrid queries that coalesced in
the serving layer (`serving/batcher.py` BoundedBatcher):

  plan    normalize → classify sub-searches into legs:
            lexical  — match/term on text fields → `ops/bm25.py` device
                       engine (tile-padded precomputed impacts)
            knn      — dense_vector → `vectors/store.py` batched corpus
            generic  — anything else → the per-query query phase
  score   ONE lexical dispatch per text field for the whole batch + ONE
          kNN dispatch per vector field for the whole batch; filters for
          filtered kNN legs evaluate host-side per query (the same
          pre-filter contract as `search/knn_query.py`)
  fuse    reciprocal-rank fusion, vectorized over the batch; f64
          accumulation in sub-search order reproduces the coordinator
          dict fold bit-for-bit, so fused results are byte-identical to
          the two-phase path (`tests/test_hybrid_plan.py` pins this)
  hydrate fetch only the final `from+size` window per query

Per-phase timings thread into `profile.hybrid` and the node's
`_nodes/stats` hybrid section.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from elasticsearch_tpu import native
from elasticsearch_tpu.common.errors import IllegalArgumentError
from elasticsearch_tpu.index.mapping import TextFieldMapper
from elasticsearch_tpu.ops import similarity as sim
from elasticsearch_tpu.ops.bm25 import LexicalShard
from elasticsearch_tpu.search.queries import (
    SearchContext, parse_query, resolve_msm,
)
from elasticsearch_tpu.search.service import (
    ShardSearchResult, execute_fetch_phase, execute_query_phase,
)
from elasticsearch_tpu.serving.batcher import BoundedBatcher
from elasticsearch_tpu.telemetry import stage as _stage
from elasticsearch_tpu.telemetry import stage_done as _stage_done

DEFAULT_RANK_CONSTANT = 60
DEFAULT_WINDOW = 100


class LexicalLeg:
    """match/term sub-search on a text field, lowered to the device
    lexical engine."""

    __slots__ = ("field", "terms", "required", "boost")

    def __init__(self, field: str, terms: List[str], required: int,
                 boost: float):
        self.field = field
        self.terms = terms
        self.required = required
        self.boost = boost


class EmptyLeg:
    """A leg whose analysis produced nothing searchable (match text that
    analyzes to zero terms): contributes an empty ranked list — the same
    empty-DocSet semantics the host query phase returns for it."""

    __slots__ = ()


class LexicalTemplate:
    """Compile-time half of a lexical leg: everything except the query
    TEXT, which is normalized out of the plan-cache key and bound per
    query (`bind`). operator/msm/boost are structural (part of the key)."""

    __slots__ = ("field", "kind", "operator", "msm", "boost")

    def __init__(self, field: str, kind: str, operator: str, msm,
                 boost: float):
        self.field = field
        self.kind = kind          # "match" | "term"
        self.operator = operator
        self.msm = msm
        self.boost = boost

    def bind(self, qspec, mapper_service):
        if self.kind == "term":
            text = qspec.get("value") if isinstance(qspec, dict) else qspec
            return LexicalLeg(self.field, [str(text)], 1, self.boost)
        text = qspec.get("query") if isinstance(qspec, dict) else qspec
        mapper = mapper_service.get(self.field)
        terms = mapper.search_analyzer.terms(str(text))
        if not terms:
            return EmptyLeg()
        required = len(terms) if self.operator == "and" \
            else resolve_msm(self.msm, len(terms))
        return LexicalLeg(self.field, terms, required, self.boost)


class KnnTemplate:
    """Compile-time half of a kNN leg: the query VECTOR is normalized out
    of the plan-cache key (only its dimensionality is structural) and
    bound per query; k/num_candidates/filter/boost/metric live in the key
    and are resolved once at compile."""

    __slots__ = ("field", "dims", "k", "num_candidates", "filter_spec",
                 "boost", "metric")

    def __init__(self, field, dims, k, num_candidates, filter_spec, boost,
                 metric):
        self.field = field
        self.dims = dims
        self.k = k
        self.num_candidates = num_candidates
        self.filter_spec = filter_spec
        self.boost = boost
        self.metric = metric

    def bind(self, spec):
        qv = np.asarray(spec["query_vector"], dtype=np.float32)
        if qv.shape[0] != self.dims:
            # same 400 KnnQuery._metric raises on the oracle — validated
            # per QUERY (the cached plan only pins the field's dims)
            raise IllegalArgumentError(
                f"[knn] query vector has {qv.shape[0]} dims, field "
                f"[{self.field}] expects {self.dims}")
        return KnnLeg(self.field, qv, self.k, self.num_candidates,
                      self.filter_spec, self.boost, self.metric)


class GenericTemplate:
    """Anything the specialized engines don't cover: bound to the BODY's
    own sub-query at execution (never the compile-time body's — generic
    values may legitimately be normalized out of the key by the
    match/term scrubbing)."""

    __slots__ = ()

    @staticmethod
    def bind(qspec):
        return GenericLeg(qspec)


class SparseTemplate:
    """Compile-time half of a learned-sparse leg (`sparse_vector` /
    `weighted_tokens` on a rank_features-style field): the query TOKEN
    MAP is normalized out of the plan-cache key and bound per query;
    field/boost are structural. Token COUNT is a bind-time concern: a
    body wider than the device grid binds to a counted host-walker
    fallback (the EmptyLeg precedent — same template, per-body leg)."""

    __slots__ = ("field", "kind", "boost")

    def __init__(self, field: str, kind: str, boost: float):
        self.field = field
        self.kind = kind          # "sparse_vector" | "weighted_tokens"
        self.boost = boost

    def bind(self, qspec: dict):
        from elasticsearch_tpu.ops.sparse import MAX_QUERY_TOKENS
        spec = qspec[self.kind]
        if self.kind == "sparse_vector":
            tokens = spec.get("query_vector") or {}
        else:
            tokens = (spec[self.field] or {}).get("tokens") or {}
        if not tokens:
            return EmptyLeg()
        if len(tokens) > MAX_QUERY_TOKENS:
            return SparseFallbackLeg(
                qspec, f"query tokens {len(tokens)} exceed device grid "
                f"cap {MAX_QUERY_TOKENS}")
        return SparseLeg(self.field, tokens, self.boost)


class MaxSimTemplate:
    """Compile-time half of a late-interaction leg (`late_interaction`
    on a `rank_vectors` field): query TOKEN VECTORS are normalized out
    of the key (their dimensionality is structural, like knn's);
    field/k/boost are structural. Over-grid token counts bind to a
    counted host-walker fallback."""

    __slots__ = ("field", "dims", "k", "boost")

    def __init__(self, field: str, dims: int, k: int, boost: float):
        self.field = field
        self.dims = dims
        self.k = k
        self.boost = boost

    def bind(self, qspec: dict):
        from elasticsearch_tpu.vectors.late_interaction import (
            MAX_QUERY_TOKENS)
        spec = qspec["late_interaction"]
        qt = np.asarray(spec["query_tokens"], dtype=np.float32)
        if qt.ndim == 1:
            qt = qt.reshape(1, -1)
        if qt.ndim != 2 or qt.shape[1] != self.dims:
            raise IllegalArgumentError(
                f"[late_interaction] query tokens have "
                f"{qt.shape[-1] if qt.ndim else 0} dims, field "
                f"[{self.field}] expects {self.dims}")
        if qt.shape[0] > MAX_QUERY_TOKENS:
            return MaxSimFallbackLeg(
                qspec, f"query tokens {qt.shape[0]} exceed device grid "
                f"cap {MAX_QUERY_TOKENS}")
        return MaxSimLeg(self.field, qt, self.k, self.boost)


class SparseLeg:
    __slots__ = ("field", "tokens", "boost")

    def __init__(self, field: str, tokens: Dict[str, float], boost: float):
        self.field = field
        self.tokens = tokens
        self.boost = boost


class MaxSimLeg:
    __slots__ = ("field", "query_tokens", "k", "boost")

    def __init__(self, field: str, query_tokens, k: int, boost: float):
        self.field = field
        self.query_tokens = query_tokens
        self.k = k
        self.boost = boost


class KnnLeg:
    __slots__ = ("field", "query_vector", "k", "num_candidates",
                 "filter_spec", "boost", "metric")

    def __init__(self, field: str, query_vector, k: int,
                 num_candidates: int, filter_spec: Optional[dict],
                 boost: float, metric: str):
        self.field = field
        self.query_vector = np.asarray(query_vector, dtype=np.float32)
        self.k = k
        self.num_candidates = num_candidates
        self.filter_spec = filter_spec
        self.boost = boost
        self.metric = metric


class GenericLeg:
    """Fallback: any sub-search the specialized engines don't cover runs
    through the ordinary per-query query phase (still inside the batch's
    single runner, still fused + fetched with the rest)."""

    __slots__ = ("query",)

    def __init__(self, query: dict):
        self.query = query


class SparseFallbackLeg(GenericLeg):
    """A sparse leg that fell off the device grid (query wider than the
    tile-scan cap): runs the host walker via the query phase, with the
    reason surfaced in leg profiles and counted in executor stats."""

    __slots__ = ("reason",)

    def __init__(self, query: dict, reason: str):
        super().__init__(query)
        self.reason = reason


class MaxSimFallbackLeg(GenericLeg):
    """A late-interaction leg that fell off the device grid: runs the
    exact host MaxSim walker via the query phase, reason counted."""

    __slots__ = ("reason",)

    def __init__(self, query: dict, reason: str):
        super().__init__(query)
        self.reason = reason


class HybridPlan:
    """Compiled structure of a hybrid body: leg templates + fusion
    parameters. Per-query VALUES (query vectors, match text) are NOT part
    of the plan — `bind` extracts them from each body, so one cached plan
    serves every query with the same shape (the r06 bench showed
    `plan_cache_hits: 0` across 108 structurally identical bodies because
    the old key hashed the values too)."""

    __slots__ = ("legs", "rank_constant", "window", "size", "frm",
                 "fetch_body")

    def __init__(self, legs, rank_constant, window, size, frm, fetch_body):
        self.legs = legs          # templates (Lexical/Knn/Generic)
        self.rank_constant = rank_constant
        self.window = window
        self.size = size
        self.frm = frm
        self.fetch_body = fetch_body

    def bind(self, body: dict, mapper_service) -> List[Any]:
        """Resolve the per-query values of `body` against the templates →
        executable legs. O(legs), no DSL parse, no classification."""
        subs = _sub_queries_of(body)
        bound: List[Any] = []
        for template, q in zip(self.legs, subs):
            if isinstance(template, LexicalTemplate):
                bound.append(template.bind(q[template.kind][template.field],
                                           mapper_service))
            elif isinstance(template, KnnTemplate):
                bound.append(template.bind(q["knn"]))
            elif isinstance(template, (SparseTemplate, MaxSimTemplate)):
                bound.append(template.bind(q))
            else:
                bound.append(GenericTemplate.bind(q))
        return bound


def _canonical_settings(svc) -> str:
    """Flat index settings as canonical JSON — the settings component of
    the request-cache epoch (a put_settings change must miss)."""
    import json
    return json.dumps(svc.settings.as_flat_dict(), sort_keys=True,
                      default=str)


def plan_cache_key(body: dict) -> str:
    """Normalized plan-cache key: the body with per-query VALUE slots
    scrubbed — `knn.query_vector` → its length (shape is structural,
    content is not), match/term text → a placeholder. Everything else
    (fields, k, num_candidates, filters, boosts, rank params, size/from,
    fuzziness) stays: those change the compiled plan."""
    def scrub_query(q):
        if not isinstance(q, dict) or len(q) != 1:
            return q
        ((kind, spec),) = q.items()
        if kind == "knn" and isinstance(spec, dict) \
                and "query_vector" in spec:
            qv = spec["query_vector"]
            spec = {**spec,
                    "query_vector": {"__dims__": len(qv)
                                     if hasattr(qv, "__len__") else 0}}
            return {kind: spec}
        if kind == "sparse_vector" and isinstance(spec, dict) \
                and "query_vector" in spec:
            # token MAPS scrub whole (count is NOT structural — the tile
            # planner pads it, and over-cap bodies fall back at bind)
            return {kind: {**spec, "query_vector": "__tokens__"}}
        if kind == "weighted_tokens" and isinstance(spec, dict) \
                and len(spec) == 1:
            ((field, v),) = spec.items()
            if isinstance(v, dict) and "tokens" in v:
                return {kind: {field: {**v, "tokens": "__tokens__"}}}
            return q
        if kind == "late_interaction" and isinstance(spec, dict) \
                and "query_tokens" in spec:
            qt = spec["query_tokens"]
            first = qt[0] if isinstance(qt, (list, tuple)) and qt else qt
            dims = len(first) if hasattr(first, "__len__") else 0
            return {kind: {**spec, "query_tokens": {"__dims__": dims}}}
        if kind in ("match", "term") and isinstance(spec, dict) \
                and len(spec) == 1:
            ((field, v),) = spec.items()
            if kind == "term":
                v = {**v, "value": "__text__"} if isinstance(v, dict) \
                    else "__text__"
            else:
                v = {**v, "query": "__text__"} if isinstance(v, dict) \
                    else "__text__"
            return {kind: {field: v}}
        return q

    norm = dict(body)
    if norm.get("sub_searches"):
        norm["sub_searches"] = [
            {**s, "query": scrub_query(s.get("query", {"match_all": {}}))}
            for s in norm["sub_searches"]]
    else:
        if norm.get("query") is not None:
            norm["query"] = scrub_query(norm["query"])
        if norm.get("knn") is not None:
            knn = norm["knn"]
            if isinstance(knn, list):
                norm["knn"] = [scrub_query({"knn": s})["knn"] for s in knn]
            else:
                norm["knn"] = scrub_query({"knn": knn})["knn"]
    from elasticsearch_tpu.search.caches import _canonical
    return _canonical(norm)


def _sub_queries_of(body: dict) -> List[dict]:
    subs: List[dict] = []
    if body.get("sub_searches"):
        subs = [s.get("query", {"match_all": {}})
                for s in body["sub_searches"]]
    else:
        if body.get("query") is not None:
            subs.append(body["query"])
        if body.get("knn") is not None:
            knn = body["knn"]
            if isinstance(knn, list):
                subs.extend({"knn": spec} for spec in knn)
            else:
                subs.append({"knn": knn})
    return subs


def _compile_lexical(spec_kind: str, qspec: dict,
                     mapper_service) -> Optional[LexicalTemplate]:
    """Lower a match/term sub-search to a lexical-engine template when it
    scores exactly like the host path would (text field, no fuzziness).
    Classification is purely STRUCTURAL (field type + spec shape), never
    value-dependent — the plan-cache key scrubs values out, so two bodies
    with one key must classify identically."""
    if not isinstance(qspec, dict) or len(qspec) != 1:
        return None
    ((field, v),) = qspec.items()
    mapper = mapper_service.get(field)
    if not isinstance(mapper, TextFieldMapper):
        return None
    if spec_kind == "term":
        boost = float(v.get("boost", 1.0)) if isinstance(v, dict) else 1.0
        return LexicalTemplate(field, "term", "or", None, boost)
    # match
    if isinstance(v, dict):
        if v.get("fuzziness") is not None:
            return None
        operator = str(v.get("operator", "or")).lower()
        msm = v.get("minimum_should_match")
        boost = float(v.get("boost", 1.0))
    else:
        operator, msm, boost = "or", None, 1.0
    return LexicalTemplate(field, "match", operator, msm, boost)


def _compile_sparse(spec_kind: str, qspec,
                    mapper_service) -> Optional[SparseTemplate]:
    """Lower a sparse_vector/weighted_tokens sub-search to the learned-
    sparse device engine when the field stores feature→weight maps
    (`rank_features` or the legacy `sparse_vector` mapping). Purely
    STRUCTURAL, like `_compile_lexical` — token values never reach the
    plan-cache key."""
    if not isinstance(qspec, dict):
        return None
    if spec_kind == "sparse_vector":
        field = qspec.get("field")
        boost = float(qspec.get("boost", 1.0))
    else:
        if len(qspec) != 1:
            return None
        ((field, v),) = qspec.items()
        boost = float(v.get("boost", 1.0)) if isinstance(v, dict) else 1.0
    if not field:
        return None
    mapper = mapper_service.get(field)
    if getattr(mapper, "type_name", "") not in ("rank_features",
                                                "sparse_vector"):
        return None
    return SparseTemplate(field, spec_kind, boost)


def compile_plan(body: dict, mapper_service) -> HybridPlan:
    """Parse + classify ONE hybrid body into an executable plan."""
    rrf = (body.get("rank") or {}).get("rrf") or {}
    rank_constant = int(rrf.get("rank_constant", DEFAULT_RANK_CONSTANT))
    window = int(rrf.get("rank_window_size",
                         rrf.get("window_size", DEFAULT_WINDOW)))
    size = int(body.get("size", 10))
    frm = int(body.get("from", 0) or 0)
    subs = _sub_queries_of(body)
    if len(subs) < 2:
        raise IllegalArgumentError(
            "[rrf] requires at least 2 ranked lists (sub_searches, or "
            "query + knn)")
    legs: List[Any] = []
    for q in subs:
        leg: Any = None
        if isinstance(q, dict) and len(q) == 1:
            kind = next(iter(q))
            spec = q[kind]
            if kind == "knn" and isinstance(spec, dict):
                from elasticsearch_tpu.index.mapping import (
                    DenseVectorFieldMapper)
                from elasticsearch_tpu.vectors.store import _METRIC_MAP
                mapper = mapper_service.get(spec["field"])
                if isinstance(mapper, DenseVectorFieldMapper):
                    # EXACT parse_query("knn") semantics — the oracle's:
                    # k defaults to 10 (not num_candidates), and
                    # num_candidates clamps up to k (KnnQuery.__init__)
                    k = int(spec.get("k", 10))
                    nc = max(int(spec.get("num_candidates",
                                          spec.get("k", 10))), k)
                    leg = KnnTemplate(
                        spec["field"], mapper.dims, k, nc,
                        spec.get("filter"), float(spec.get("boost", 1.0)),
                        _METRIC_MAP[mapper.similarity])
            elif kind in ("match", "term"):
                leg = _compile_lexical(kind, spec, mapper_service)
            elif kind in ("sparse_vector", "weighted_tokens"):
                leg = _compile_sparse(kind, spec, mapper_service)
            elif kind == "late_interaction" and isinstance(spec, dict):
                from elasticsearch_tpu.index.mapping import (
                    RankVectorsFieldMapper)
                mapper = mapper_service.get(spec.get("field", ""))
                if isinstance(mapper, RankVectorsFieldMapper):
                    leg = MaxSimTemplate(
                        spec["field"], mapper.dims,
                        int(spec.get("k", 10)),
                        float(spec.get("boost", 1.0)))
        if leg is None:
            leg = GenericTemplate()
        legs.append(leg)
    fetch_body = {k: v for k, v in body.items()
                  if k in ("_source", "docvalue_fields")}
    fetch_body["size"] = size
    return HybridPlan(legs, rank_constant, window, size, frm, fetch_body)


def fuse_rrf(leg_rows: List[np.ndarray], rank_constant: int
             ) -> Tuple[np.ndarray, np.ndarray]:
    """RRF over ranked row lists → (unique rows ascending, f64 scores).

    f64 accumulation in leg order reproduces the coordinator's python-dict
    fold exactly: per row, contributions add one leg at a time, so the
    floating-point sum order (and hence every last bit) matches."""
    non_empty = [r for r in leg_rows if len(r)]
    if not non_empty:
        return (np.zeros(0, dtype=np.int64), np.zeros(0, dtype=np.float64))
    uniq = np.unique(np.concatenate(non_empty))
    scores = np.zeros(len(uniq), dtype=np.float64)
    for rows in leg_rows:
        if not len(rows):
            continue
        idx = np.searchsorted(uniq, rows)
        np.add.at(scores, idx,
                  1.0 / (rank_constant + np.arange(1, len(rows) + 1,
                                                   dtype=np.float64)))
    return uniq, scores


class HybridExecutor:
    """Per-index hybrid serving path: plan cache + bounded combining queue.

    Whole hybrid queries (not just their kNN legs) coalesce here: the
    first thread in becomes the runner and executes every body that
    accumulated while the previous batch was in flight — one lexical
    dispatch per text field, one kNN dispatch per vector field, for the
    entire batch. Admission control (depth + deadline) sheds overload as
    HTTP 429 instead of queueing into the p99 tail.
    """

    def __init__(self, node, svc, max_batch: int = 64,
                 max_queue_depth: int = 256,
                 deadline_ms: Optional[float] = 10_000.0,
                 plan_cache_entries: int = 256, topup: bool = True,
                 target_batch_latency_ms: float = 2.0,
                 async_depth: int = 2):
        from elasticsearch_tpu.ops import dispatch as _dispatch
        from elasticsearch_tpu.search.caches import LruCache
        self.node = node
        self.svc = svc
        self.lexical = LexicalShard(
            dtype=str(svc.settings.get("index.lexical.impact_dtype",
                                       "f32")))
        from elasticsearch_tpu.ops.sparse import SparseShard
        from elasticsearch_tpu.vectors.late_interaction import (
            LateInteractionShard)
        self.sparse = SparseShard(
            dtype=str(svc.settings.get("index.sparse.impact_dtype",
                                       "f32")))
        self.late = LateInteractionShard()
        self.plan_cache = LruCache(max_entries=plan_cache_entries)
        # pipelined continuous batching: the runner holds the scheduler
        # lock only for plan-bind + the un-synced leg dispatches
        # (_dispatch_batch); device sync, RRF fusion and hydrate run
        # outside it (_finalize_batch), overlapping the next batch's
        # device dispatch. `_run_batch` stays the synchronous
        # (dispatch+finalize) path for poisoned-batch serial retries.
        self.batcher = BoundedBatcher(self._run_batch, max_batch=max_batch,
                                      max_queue_depth=max_queue_depth,
                                      deadline_ms=deadline_ms,
                                      warmup=self._warmup
                                      if _dispatch.warmup_enabled()
                                      else None,
                                      dispatch_fn=self._dispatch_batch,
                                      finalize_fn=self._finalize_batch,
                                      topup=topup,
                                      target_batch_latency_ms=(
                                          target_batch_latency_ms),
                                      async_depth=async_depth)
        self.stats = {"searches": 0, "batches": 0, "max_batch_seen": 0,
                      "plan_cache_hits": 0, "plan_cache_misses": 0,
                      "plan_nanos": 0, "score_nanos": 0, "fuse_nanos": 0,
                      "hydrate_nanos": 0, "queue_wait_nanos": 0,
                      "dispatch_nanos": 0, "sync_nanos": 0,
                      "request_cache_hits": 0, "request_cache_misses": 0,
                      "request_cache_stores": 0,
                      "sparse_grid_fallbacks": 0,
                      "maxsim_grid_fallbacks": 0}
        # finalize stages of different batches run CONCURRENTLY when
        # async_depth > 1; their stats writes must not lose updates
        # (dispatch-stage writes serialize under the batcher lock)
        self._stats_lock = threading.Lock()

    # ------------------------------------------------------------- entry
    def submit(self, body: dict) -> dict:
        """Request-cache short-circuit, then the bounded batcher.

        The shard request cache sits BEFORE the batcher: a repeated
        dashboard body (same shape, same values, same reader content,
        same live settings) returns the stored response without
        occupying a batch slot or a device dispatch. Refresh rotates the
        reader fingerprint inside the key, so invalidation is free.
        Profiled bodies are never SERVED from cache — the profile must
        describe a real execution — but report the cache state in a
        `cache` annotation."""
        key = self._request_cache_key(body)
        if key is None:
            return self.batcher.submit(body)
        cache = self.node.caches.device_request
        if not body.get("profile"):
            cached = cache.get(key)
            if cached is not None:
                with self._stats_lock:
                    self.stats["request_cache_hits"] += 1
                return self._serve_cached(cached)
            with self._stats_lock:
                self.stats["request_cache_misses"] += 1
        resp = self.batcher.submit(body)
        if body.get("profile"):
            prof = resp.get("profile")
            if prof is not None and "hybrid" in prof:
                prof["hybrid"]["cache"] = {
                    "rung": "device_request", "served": False,
                    "policy": "profile_bypass"}
        else:
            import copy as _copy
            entry = _copy.deepcopy(
                {k: v for k, v in resp.items()
                 if k not in ("took", "_took_phases")})
            cache.put(key, entry)
            with self._stats_lock:
                self.stats["request_cache_stores"] += 1
        return resp

    def _request_cache_key(self, body: dict):
        """None when this body must not cache (disabled, opted out, or
        non-deterministic); otherwise the sanctioned layered key:
        normalized plan key + value digest + reader content fingerprint
        + live settings epoch (`search/caches.request_cache_key`)."""
        node = self.node
        if not getattr(node, "_device_request_cache_enabled", lambda: False)():
            return None
        from elasticsearch_tpu.search import caches as _caches
        cache = node.caches.device_request
        flag = body.get("request_cache")
        if flag is False:
            return None
        if not cache.deterministic(body):
            if flag is True:
                cache.skipped_uncacheable += 1
            return None
        svc = self.svc
        reader = svc.combined_reader()
        # epoch: everything outside the body the response depends on —
        # the index identity (uuid guards same-name recreation reusing
        # segment ids), its live settings, and the node's dynamic limits
        from elasticsearch_tpu.parallel import policy as _policy
        epoch = (svc.name, getattr(svc, "uuid", None),
                 hash(_canonical_settings(svc)),
                 node._max_buckets(), node._allow_expensive(),
                 _policy.config_epoch())
        return _caches.request_cache_key(
            plan_cache_key(body), body,
            fingerprint=_caches.reader_fingerprint(reader),
            epoch=epoch)

    @staticmethod
    def _serve_cached(entry: dict) -> dict:
        import copy as _copy
        resp = _copy.deepcopy(entry)
        resp["took"] = 0
        return resp

    def _warmup(self) -> None:
        """Batcher-start warmup (runs on the batcher's daemon thread):
        build the lexical impact layout for every text field NOW instead
        of inside the first hybrid query, and pre-compile the BM25
        scatter-add kernel for the interactive bucket grid against that
        layout's board width. Vector-field grids warm separately at
        corpus sync (`vectors/store._schedule_warmup`)."""
        import jax
        import jax.numpy as _jnp

        from elasticsearch_tpu.index.mapping import TextFieldMapper
        from elasticsearch_tpu.ops import dispatch as _dispatch
        from elasticsearch_tpu.ops.bm25 import _pow2
        reader = self.svc.combined_reader()
        entries = []

        def scatter_entries(lf, kernel: str):
            """Shape-only warmup entries for one impact layout (bm25 or
            learned-sparse — same scoring program, own dispatch name).

            The kernel's term-tile dimension pads pow-2 to the batch's
            max TOTAL tile count (`plan_queries` sums a query's terms),
            and a zipf-popular term alone can span dozens of impact
            tiles — warm the m ladder up to a few-wide-term query over
            this field's layout (4 × widest term), not a fixed {1,2,4}.
            The r06-shape closed-loop bench showed exactly this gap: a
            timed-loop batch hit m=16 and paid a 750 ms XLA compile
            mid-flight. Still a floor, not a ceiling — a many-term
            query over several wide terms can exceed the cap and
            compile once; the persistent cache absorbs it across
            restarts."""
            width = _pow2(max(lf.n_slots, 1)) + 1
            imp_dtype = {"f32": _jnp.float32, "bf16": _jnp.bfloat16,
                         "int8": _jnp.int8}[lf.dtype]
            n_tiles = max(int(lf.tile_slots.shape[0]), 1)
            scales = (jax.ShapeDtypeStruct((n_tiles,), _jnp.float32)
                      if lf.dtype == "int8" else None)
            max_nt = max((nt for _first, nt in lf.term_tiles.values()),
                         default=1)
            m_cap = _pow2(min(max(4 * max_nt, 4), 256))
            m_rungs = [m for m in (1, 2, 4, 8, 16, 32, 64, 128, 256)
                       if m <= m_cap]
            for q in (1, 8, 16):
                for m in m_rungs:
                    entries.append((
                        kernel,
                        (jax.ShapeDtypeStruct((q, width), _jnp.float32),
                         jax.ShapeDtypeStruct((q, width), _jnp.int32),
                         jax.ShapeDtypeStruct((q, m), _jnp.int32),
                         jax.ShapeDtypeStruct((q, m), _jnp.float32),
                         jax.ShapeDtypeStruct((q,), _jnp.int32),
                         jax.ShapeDtypeStruct((n_tiles, 128), _jnp.int32),
                         jax.ShapeDtypeStruct((n_tiles, 128), imp_dtype),
                         scales),
                        {"k": _dispatch.bucket_k(
                            min(DEFAULT_WINDOW, lf.n_slots),
                            limit=width - 1)}))

        for field, mapper in self.svc.mapper_service.all_mappers():
            type_name = getattr(mapper, "type_name", "")
            if isinstance(mapper, TextFieldMapper):
                lf = self.lexical.field(reader, field)
                if lf.n_slots:
                    scatter_entries(lf, "bm25.topk")
            elif type_name in ("rank_features", "sparse_vector"):
                sf = self.sparse.field(reader, field)
                if sf.n_slots:
                    scatter_entries(sf, "sparse.topk")
            elif type_name == "rank_vectors":
                entries.extend(self.late.warmup_entries(reader, mapper))
        if entries:
            _dispatch.DISPATCH.warmup(entries, background=False)

    def plan_for(self, body: dict) -> Tuple[HybridPlan, bool]:
        """Plan-cache lookup (hit) or compile (miss), keyed on the
        normalized body — per-query values (query vectors, match text)
        are scrubbed from the key, so repeated SHAPES hit regardless of
        what they search for."""
        key = plan_cache_key(body)
        plan = self.plan_cache.get(key)
        if plan is not None:
            self.stats["plan_cache_hits"] += 1
            return plan, True
        plan = compile_plan(body, self.svc.mapper_service)
        self.plan_cache.put(key, plan)
        self.stats["plan_cache_misses"] += 1
        return plan, False

    # ------------------------------------------------------------- batch
    def _run_batch(self, bodies: List[dict]) -> List[dict]:
        """Synchronous serving of one batch: dispatch + finalize back to
        back. The batcher's main path splits the two stages so finalize
        overlaps the next dispatch; this entry is the poisoned-batch
        serial-retry path and the parity oracle for tests."""
        return self._finalize_batch(self._dispatch_batch(bodies))

    def _dispatch_batch(self, bodies: List[dict]):
        """Dispatch stage (runs under the batcher's scheduler lock):
        plan-cache bind, generic/lexical leg execution, and the UN-SYNCED
        kNN device dispatches. Returns the in-flight handle
        `_finalize_batch` lands; no blocking device sync happens here."""
        start = time.perf_counter()
        svc = self.svc
        reader = svc.combined_reader()
        from elasticsearch_tpu.node import _MultiShardVectorStore
        store = _MultiShardVectorStore(svc)
        self.stats["searches"] += len(bodies)
        self.stats["batches"] += 1
        self.stats["max_batch_seen"] = max(self.stats["max_batch_seen"],
                                           len(bodies))
        sched_meta = self.batcher.batch_meta()
        self.stats["queue_wait_nanos"] += sched_meta.get(
            "queue_wait_max_nanos", 0)

        t0 = time.perf_counter_ns()
        plans: List[HybridPlan] = []
        bound: List[List[Any]] = []
        cache_state: List[bool] = []
        for body in bodies:
            plan, hit = self.plan_for(body)
            plans.append(plan)
            bound.append(plan.bind(body, self.svc.mapper_service))
            cache_state.append(hit)
        plan_nanos = time.perf_counter_ns() - t0
        self.stats["plan_nanos"] += plan_nanos
        # fine-grained stages of the batch: this thread runs inside the
        # batcher's `serving.device_dispatch` / `serving.device_sync`
        # stage, whose context is the batch LEADER's trace, so these hang
        # under it (followers link to the batcher's span). Every reading
        # was taken anyway: zero added host syncs
        _stage_done("hybrid.plan", t0, t0 + plan_nanos)

        breaker_bytes = reader.num_docs * 16 * max(len(bodies), 1)
        self.node.breakers.add_estimate("request", breaker_bytes,
                                        "<hybrid>")
        # the per-dispatch event trace costs a dict per kernel call;
        # only pay it when some query in the batch asked to profile
        trace = any(body.get("profile") for body in bodies)
        from elasticsearch_tpu.ops import dispatch as _dispatch
        from elasticsearch_tpu.parallel import policy as _mesh_policy
        mesh_before = _mesh_policy.stats() if trace else None
        if trace:
            _dispatch.DISPATCH.record_events(True)
        try:
            ctx = SearchContext(reader, svc.mapper_service,
                                query_cache=self.node.caches.query)
            ctx.index_settings = svc.settings.as_flat_dict()
            ctx.vector_store = store

            t0 = time.perf_counter_ns()
            leg_results, leg_info, pending = self._score_legs_async(
                reader, store, ctx, plans, bound)
            dispatch_nanos = time.perf_counter_ns() - t0
            self.stats["dispatch_nanos"] += dispatch_nanos
            _stage_done("hybrid.device_dispatch", t0, t0 + dispatch_nanos,
                        coalesced=len(bodies))
        except BaseException:
            if trace:
                _dispatch.DISPATCH.drain_events()
                _dispatch.DISPATCH.record_events(False)
            self.node.breakers.release("request", breaker_bytes)
            raise
        return {"start": start, "reader": reader, "store": store,
                "bodies": bodies, "plans": plans,
                "cache_state": cache_state, "plan_nanos": plan_nanos,
                "dispatch_nanos": dispatch_nanos,
                "leg_results": leg_results, "leg_info": leg_info,
                "pending": pending, "trace": trace,
                "mesh_before": mesh_before,
                "breaker_bytes": breaker_bytes,
                "sched_meta": sched_meta}

    def _finalize_batch(self, handle) -> List[dict]:
        """Finalize stage (runs OUTSIDE the scheduler lock, overlapping
        the next batch's dispatch): land the un-synced kNN boards, fuse
        RRF, hydrate the final windows, assemble responses. Byte-
        identical to the pre-pipeline single-stage path — only the
        timing moved."""
        svc = self.svc
        reader = handle["reader"]
        store = handle["store"]
        bodies = handle["bodies"]
        plans = handle["plans"]
        cache_state = handle["cache_state"]
        plan_nanos = handle["plan_nanos"]
        leg_results = handle["leg_results"]
        leg_info = handle["leg_info"]
        trace = handle["trace"]
        start = handle["start"]
        from elasticsearch_tpu.ops import dispatch as _dispatch
        from elasticsearch_tpu.parallel import policy as _mesh_policy
        try:
            dispatch_events = []
            mesh_delta = None
            try:
                t0 = time.perf_counter_ns()
                self._land_knn_legs(handle["pending"], plans, leg_results,
                                    leg_info, store)
                sync_nanos = time.perf_counter_ns() - t0
                with self._stats_lock:
                    self.stats["sync_nanos"] += sync_nanos
                _stage_done("hybrid.device_sync", t0, t0 + sync_nanos)
            finally:
                if trace:
                    dispatch_events = _dispatch.DISPATCH.drain_events()
                    _dispatch.DISPATCH.record_events(False)
            if trace:
                # which legs of this batch rode the serving mesh
                # (process-wide counters, so concurrent batches can bleed
                # into the delta — `_nodes/stats indices.mesh` stays the
                # authoritative total, same caveat as the dispatch trace)
                from elasticsearch_tpu.search.profile import (
                    mesh_stats_delta)
                mesh_delta = mesh_stats_delta(handle["mesh_before"],
                                              _mesh_policy.stats())
            # score = launch + device wait: the pre-pipeline figure,
            # preserved so dashboards comparing rounds stay meaningful
            score_nanos = handle["dispatch_nanos"] + sync_nanos
            with self._stats_lock:
                self.stats["score_nanos"] += score_nanos

            t0 = time.perf_counter_ns()
            fused = []
            for bi, plan in enumerate(plans):
                rows, scores = fuse_rrf(
                    [leg_results[(bi, li)]
                     for li in range(len(plan.legs))],
                    plan.rank_constant)
                # exact two-phase ordering: (-score, row asc)
                order = np.lexsort((rows, -scores))
                top = order[plan.frm:plan.frm + plan.size]
                fused.append((rows, scores, top))
            fuse_nanos = time.perf_counter_ns() - t0
            with self._stats_lock:
                self.stats["fuse_nanos"] += fuse_nanos
            _stage_done("hybrid.fuse", t0, t0 + fuse_nanos)

            t0 = time.perf_counter_ns()
            out = []
            for bi, (plan, body, (rows, scores, top)) in enumerate(
                    zip(plans, bodies, fused)):
                top_rows = rows[top]
                top_scores = scores[top]
                final = ShardSearchResult(
                    0, top_rows.astype(np.int64),
                    top_scores.astype(np.float32), None, len(rows), "eq",
                    None, float(top_scores[0]) if len(top) else None)
                hits = execute_fetch_phase(
                    reader, svc.mapper_service, plan.fetch_body, final,
                    index_name=svc.name)
                for h, s in zip(hits, top_scores):
                    h["_score"] = float(s)
                resp = {
                    "took": int((time.perf_counter() - start) * 1000),
                    "timed_out": False,
                    "hits": {"total": {"value": int(len(rows)),
                                       "relation": "eq"},
                             "max_score": hits[0]["_score"] if hits
                             else None,
                             "hits": hits}}
                if body.get("profile"):
                    from elasticsearch_tpu.search.profile import (
                        hybrid_profile)
                    resp["profile"] = hybrid_profile(
                        svc.name, plan_nanos, score_nanos, fuse_nanos,
                        0, cache_state[bi], len(bodies),
                        [leg_info[(bi, li)]
                         for li in range(len(plan.legs))],
                        dispatch_events=dispatch_events,
                        mesh=mesh_delta,
                        queue_wait_nanos=handle["sched_meta"].get(
                            "queue_wait_max_nanos", 0),
                        device_dispatch_nanos=handle["dispatch_nanos"],
                        device_sync_nanos=sync_nanos,
                        scheduler=self.scheduler_snapshot())
                out.append(resp)
            hydrate_nanos = time.perf_counter_ns() - t0
            with self._stats_lock:
                self.stats["hydrate_nanos"] += hydrate_nanos
            _stage_done("hybrid.hydrate", t0, t0 + hydrate_nanos)
            # private key (popped by _search_rrf): the slow log needs
            # the phase breakdown on EVERY breach, not just profiled
            # requests — batch-scoped figures, same semantics as the
            # profile breakdown
            took_phases = {
                "plan_nanos": plan_nanos,
                "queue_wait_nanos": handle["sched_meta"].get(
                    "queue_wait_max_nanos", 0),
                "device_dispatch_nanos": handle["dispatch_nanos"],
                "device_sync_nanos": sync_nanos,
                "fuse_nanos": fuse_nanos,
                "hydrate_nanos": hydrate_nanos,
                "batch_size": len(bodies)}
            for resp in out:
                resp["_took_phases"] = dict(took_phases)
                prof = resp.get("profile")
                if prof is not None:
                    prof["hybrid"]["breakdown"]["hydrate_nanos"] = \
                        hydrate_nanos
            return out
        finally:
            self.node.breakers.release("request",
                                       handle["breaker_bytes"])

    def scheduler_snapshot(self) -> dict:
        """The continuous batcher's scheduler counters (topups, deadline
        sheds, dispatch/finalize overlap hits) — profile + stats feed."""
        sched = self.batcher.sched
        return {"topups": sched["topups"],
                "deadline_sheds": sched["deadline_sheds"],
                "overlap_hits": sched["overlap_hits"],
                "pipelined_batches": sched["pipelined_batches"]}

    # -------------------------------------------------------------- legs
    def _score_legs_async(self, reader, store, ctx, plans, bound):
        """Execute every body's BOUND legs, grouped so each engine sees
        ONE batched dispatch: lexical legs group per text field, kNN legs
        per (field, k, num_candidates). Generic and lexical legs complete
        here; kNN legs LAUNCH un-synced (`search_many_async`) and return
        as pending handles `_land_knn_legs` finalizes. Returns
        ({(body_idx, leg_idx): ranked row array}, per-leg profile info,
        pending kNN groups)."""
        leg_results: Dict[Tuple[int, int], np.ndarray] = {}
        leg_info: Dict[Tuple[int, int], dict] = {}

        lex_groups: Dict[str, List[Tuple[int, int, LexicalLeg]]] = {}
        sparse_groups: Dict[str, List[Tuple[int, int, SparseLeg]]] = {}
        maxsim_groups: Dict[Tuple[str, int],
                            List[Tuple[int, int, MaxSimLeg]]] = {}
        knn_groups: Dict[Tuple[str, int, Optional[int]],
                         List[Tuple[int, int, KnnLeg]]] = {}
        for bi, legs in enumerate(bound):
            for li, leg in enumerate(legs):
                if isinstance(leg, EmptyLeg):
                    leg_results[(bi, li)] = np.zeros(0, dtype=np.int64)
                    leg_info[(bi, li)] = {"type": "empty"}
                elif isinstance(leg, LexicalLeg):
                    lex_groups.setdefault(leg.field, []).append(
                        (bi, li, leg))
                elif isinstance(leg, SparseLeg):
                    sparse_groups.setdefault(leg.field, []).append(
                        (bi, li, leg))
                elif isinstance(leg, MaxSimLeg):
                    maxsim_groups.setdefault((leg.field, leg.k),
                                             []).append((bi, li, leg))
                elif isinstance(leg, KnnLeg):
                    knn_groups.setdefault(
                        (leg.field, leg.k, leg.num_candidates),
                        []).append((bi, li, leg))
                else:
                    result = execute_query_phase(
                        reader, self.svc.mapper_service,
                        {"query": leg.query, "size": plans[bi].window},
                        vector_store=store,
                        query_cache=self.node.caches.query,
                        index_settings=self.svc.settings.as_flat_dict(),
                        max_buckets=self.node._max_buckets(),
                        allow_expensive=self.node._allow_expensive(),
                        index_name=self.svc.name)
                    leg_results[(bi, li)] = np.asarray(result.rows,
                                                       dtype=np.int64)
                    if isinstance(leg, (SparseFallbackLeg,
                                        MaxSimFallbackLeg)):
                        key = ("sparse_grid_fallbacks"
                               if isinstance(leg, SparseFallbackLeg)
                               else "maxsim_grid_fallbacks")
                        self.stats[key] += 1
                        leg_info[(bi, li)] = {
                            "type": "query_phase_fallback",
                            "reason": leg.reason}
                    else:
                        leg_info[(bi, li)] = {"type": "query_phase"}

        for field, entries in lex_groups.items():
            window = max(plans[bi].window for bi, _li, _leg in entries)
            queries = [(leg.terms, leg.boost) for _bi, _li, leg in entries]
            required = [leg.required for _bi, _li, leg in entries]
            results = self.lexical.search_batch(
                reader, field, queries, window, required=required)
            lf = self.lexical.field(reader, field)
            for (bi, li, leg), (rows, _scores) in zip(entries, results):
                leg_results[(bi, li)] = rows[:plans[bi].window]
                leg_info[(bi, li)] = {
                    "type": "lexical_device", "field": field,
                    "terms": len(leg.terms), "corpus_slots": lf.n_slots,
                    "impact_tiles": int(lf.tile_slots.shape[0])}

        for field, entries in sparse_groups.items():
            window = max(plans[bi].window for bi, _li, _leg in entries)
            queries = [(leg.tokens, leg.boost) for _bi, _li, leg in entries]
            results = self.sparse.search_batch(reader, field, queries,
                                               window)
            sf = self.sparse.field(reader, field)
            for (bi, li, leg), (rows, _scores) in zip(entries, results):
                leg_results[(bi, li)] = rows[:plans[bi].window]
                leg_info[(bi, li)] = {
                    "type": "sparse_device", "field": field,
                    "tokens": len(leg.tokens), "corpus_slots": sf.n_slots,
                    "impact_tiles": int(sf.tile_slots.shape[0])}

        # MaxSim legs complete synchronously in the dispatch stage: the
        # fused rescore's inputs depend on its own coarse phase's ids,
        # so there is no un-synced board to land later
        for (field, k), entries in maxsim_groups.items():
            mapper = self.svc.mapper_service.get(field)
            queries = [(leg.query_tokens, leg.boost)
                       for _bi, _li, leg in entries]
            results = self.late.search_batch(reader, mapper, queries, k)
            lf = self.late.field(reader, mapper)
            for (bi, li, leg), (rows, _scores) in zip(entries, results):
                leg_results[(bi, li)] = rows[:plans[bi].window]
                leg_info[(bi, li)] = {
                    "type": "maxsim_device", "field": field, "k": k,
                    "encoding": lf.encoding,
                    "coarse_window": (lf.coarse_window(k)
                                      if lf.n_docs else 0),
                    "docs": lf.n_docs}

        pending = []
        for (field, k, num_candidates), entries in knn_groups.items():
            reqs = []
            for _bi, _li, leg in entries:
                filter_rows = None
                if leg.filter_spec is not None:
                    with _stage("knn.filter_resolve"):
                        filter_rows = parse_query(
                            leg.filter_spec).execute(ctx).rows
                reqs.append((leg.query_vector, filter_rows))
            # launch only: the device arrays stay un-synced until the
            # finalize stage lands them (batch N's host work overlaps
            # batch N+1's dispatch)
            knn_handle = store.search_many_async(
                field, reqs, k, num_candidates=num_candidates)
            phases = dict(getattr(store, "last_knn_phases", None) or {})
            pending.append((entries, knn_handle, field, k, phases))
        return leg_results, leg_info, pending

    def _land_knn_legs(self, pending, plans, leg_results, leg_info,
                       store) -> None:
        """Finalize the batch's kNN legs: one bulk device→host landing
        per group, then post-processing identical to KnnQuery.execute +
        the query phase's score-ranked cut."""
        for entries, knn_handle, field, k, phases in pending:
            batch_out = store.finalize_many(knn_handle)
            for (bi, li, leg), (rows, raw) in zip(entries, batch_out):
                scores = (np.asarray(sim.to_es_score(raw, leg.metric))
                          * leg.boost)
                order = np.argsort(rows, kind="stable")
                rows = rows[order].astype(np.int64)
                scores = scores[order].astype(np.float32)
                kk = min(plans[bi].window, len(rows))
                idx = native.topk(scores, kk)
                leg_results[(bi, li)] = rows[idx]
                leg_info[(bi, li)] = {
                    "type": "knn_device", "field": field, "k": k,
                    **({"engine": phases.get("engine")}
                       if phases.get("engine") else {})}
