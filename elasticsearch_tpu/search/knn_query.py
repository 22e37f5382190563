"""kNN query: the `_search { "knn": ... }` device path.

The north-star query (SURVEY.md §2.8, BASELINE.json): where the reference
runs `script_score` with a per-doc Painless CosineSimilarity loop
(`ScoreScriptUtils.java:145-171`), this query dispatches to the shard's
device vector store — batched matmul + lax.top_k — and composes with an
optional boolean pre-filter evaluated host-side and shipped as a mask
(SURVEY.md §7 "Filtered kNN").

Scores follow the `_search` knn `_score` convention via
`similarity.to_es_score`.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from elasticsearch_tpu.common.errors import IllegalArgumentError
from elasticsearch_tpu.index.mapping import DenseVectorFieldMapper
from elasticsearch_tpu.ops import similarity as sim
from elasticsearch_tpu.search.queries import DocSet, Query, SearchContext
from elasticsearch_tpu.telemetry import stage


class KnnQuery(Query):
    def __init__(self, field: str, query_vector, k: int = 10,
                 num_candidates: int = 10, filter_query: Optional[Query] = None,
                 boost: float = 1.0):
        self.field = field
        self.query_vector = np.asarray(query_vector, dtype=np.float32)
        self.k = k
        self.num_candidates = max(num_candidates, k)
        self.filter_query = filter_query
        self.boost = boost

    def _metric(self, ctx: SearchContext) -> str:
        mapper = ctx.mapper_service.get(self.field)
        if not isinstance(mapper, DenseVectorFieldMapper):
            raise IllegalArgumentError(
                f"[knn] field [{self.field}] is not a dense_vector field")
        if self.query_vector.shape[0] != mapper.dims:
            raise IllegalArgumentError(
                f"[knn] query vector has {self.query_vector.shape[0]} dims, "
                f"field [{self.field}] expects {mapper.dims}")
        from elasticsearch_tpu.vectors.store import _METRIC_MAP
        return _METRIC_MAP[mapper.similarity]

    def execute(self, ctx: SearchContext) -> DocSet:
        metric = self._metric(ctx)
        filter_rows = None
        if self.filter_query is not None:
            with stage("knn.filter_resolve"):
                filter_rows = self.filter_query.execute(ctx).rows

        store = getattr(ctx, "vector_store", None)
        if store is not None and store.field(self.field) is not None:
            rows, raw = store.search(self.field, self.query_vector, self.k,
                                     filter_rows=filter_rows,
                                     num_candidates=self.num_candidates,
                                     deadline_at=getattr(
                                         ctx, "deadline_at", None))
            # per-phase engine timings (route/score/merge for tpu_ivf) for
            # the profiler and shard result; plus the columnar refresh
            # ledger for this field (segment block store): how the last
            # sync composed — cached / delta / full extraction — so
            # profile.knn shows the O(delta) claim per search instead of
            # burying it in node stats
            phases = getattr(store, "last_knn_phases", None)
            col = getattr(store, "columnar_refresh", None)
            if col and self.field in col:
                phases = dict(phases or {})
                phases.setdefault("columnar", col[self.field])
            if phases:
                ctx.knn_phases = phases
        else:
            rows, raw = self._host_fallback(ctx, metric, filter_rows)

        scores = np.asarray(sim.to_es_score(raw, metric)) * self.boost
        order = np.argsort(rows, kind="stable")
        return DocSet(rows[order].astype(np.int64), scores[order].astype(np.float32))

    def _host_fallback(self, ctx: SearchContext, metric: str,
                       filter_rows: Optional[np.ndarray]):
        """Exact numpy path when no device store is attached (unit tests,
        tiny shards): same math, same ordering semantics."""
        mats, rows = [], []
        for view in ctx.reader.views:
            seg = view.segment
            if self.field not in seg.vectors:
                continue
            mat, present = seg.vectors[self.field]
            keep = present & view.live
            locs = np.nonzero(keep)[0]
            if len(locs):
                mats.append(mat[locs])
                rows.append(locs.astype(np.int64) + seg.base)
        if not mats:
            return np.zeros(0, dtype=np.int64), np.zeros(0, dtype=np.float32)
        mat = np.concatenate(mats)
        rows = np.concatenate(rows)
        if filter_rows is not None:
            keep = np.isin(rows, filter_rows)
            mat, rows = mat[keep], rows[keep]
            if len(rows) == 0:
                return rows, np.zeros(0, dtype=np.float32)
        q = self.query_vector
        if metric == sim.COSINE:
            qn = q / max(np.linalg.norm(q), 1e-30)
            cn = mat / np.maximum(np.linalg.norm(mat, axis=1, keepdims=True), 1e-30)
            raw = cn @ qn
        elif metric in (sim.DOT_PRODUCT, sim.MAX_INNER_PRODUCT):
            raw = mat @ q
        else:  # l2
            raw = -((mat - q[None, :]) ** 2).sum(axis=1)
        k = min(self.k, len(rows))
        top = np.argpartition(-raw, k - 1)[:k] if k < len(rows) else np.arange(len(rows))
        top = top[np.argsort(-raw[top], kind="stable")]
        return rows[top], raw[top].astype(np.float32)

    def to_dict(self):
        d = {"field": self.field, "query_vector": self.query_vector.tolist(),
             "k": self.k, "num_candidates": self.num_candidates}
        if self.filter_query is not None:
            d["filter"] = self.filter_query.to_dict()
        return {"knn": d}
