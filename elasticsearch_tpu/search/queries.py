"""Query DSL: executable queries over a ShardReader.

Re-design of the reference's query layer (`index/query/` — 73 builder files —
plus Lucene's scorers; SURVEY.md §2.5). Instead of per-document iterator
scorers (BulkScorer over postings), every query evaluates **vectorized**:

    execute(ctx) -> DocSet(rows: int64[], scores: float32[] | None)

Rows are sorted global row ids; scores align with rows. Boolean composition
is set algebra on sorted arrays (intersect/union/diff) with score summing —
the same query/filter-context semantics as the reference (filter clauses
never score, `BoolQueryBuilder`), shaped so score math stays in numpy and
can batch to the device.

BM25 matches Lucene's BM25Similarity (k1=1.2, b=0.75):
    idf = ln(1 + (N - df + 0.5) / (df + 0.5))
    tf  = f / (f + k1 * (1 - b + b * len / avg_len))
    score = idf * tf * (k1 + 1)
"""

from __future__ import annotations

import math
import re
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from elasticsearch_tpu import native
from elasticsearch_tpu.common.errors import IllegalArgumentError, ParsingError
from elasticsearch_tpu.index.mapping import (
    BooleanFieldMapper, DateFieldMapper, DateNanosFieldMapper,
    DenseVectorFieldMapper, IpFieldMapper,
    KeywordFieldMapper, MapperService, RangeFieldMapperBase, TextFieldMapper,
    _NumericMapper, parse_date_millis,
)
from elasticsearch_tpu.index.segment import ShardReader

BM25_K1 = 1.2
BM25_B = 0.75


class DocSet:
    """Sorted matching rows + aligned scores (None in filter context)."""

    __slots__ = ("rows", "scores")

    def __init__(self, rows: np.ndarray, scores: Optional[np.ndarray] = None):
        self.rows = rows
        self.scores = scores

    @staticmethod
    def empty() -> "DocSet":
        return DocSet(np.zeros(0, dtype=np.int64), np.zeros(0, dtype=np.float32))

    def with_scores(self) -> "DocSet":
        if self.scores is None:
            return DocSet(self.rows, np.zeros(len(self.rows), dtype=np.float32))
        return self

    def constant(self, value: float = 0.0) -> "DocSet":
        return DocSet(self.rows, np.full(len(self.rows), value, dtype=np.float32))


class SearchContext:
    """Per-shard execution context (reference: SearchContext/QueryShardContext)."""

    def __init__(self, reader: ShardReader, mapper_service: MapperService,
                 query_cache=None):
        self.reader = reader
        self.mapper_service = mapper_service
        self._all_rows: Optional[np.ndarray] = None
        # node query cache (search/caches.py): filter-context row arrays
        # keyed on (reader gen, filter source); None disables caching
        self.query_cache = query_cache
        # search.max_buckets cluster setting (MultiBucketConsumerService);
        # None = unlimited, set by the search entry from cluster settings
        self.max_buckets: Optional[int] = None

    def all_rows(self) -> np.ndarray:
        if self._all_rows is None:
            self._all_rows = np.sort(self.reader.live_global_rows())
        return self._all_rows


class Query:
    def execute(self, ctx: SearchContext) -> DocSet:
        raise NotImplementedError

    def to_dict(self) -> dict:
        raise NotImplementedError


# ---------------------------------------------------------------------------
# Leaf queries
# ---------------------------------------------------------------------------

class MatchAllQuery(Query):
    def __init__(self, boost: float = 1.0):
        self.boost = boost

    def execute(self, ctx: SearchContext) -> DocSet:
        rows = ctx.all_rows()
        return DocSet(rows, np.full(len(rows), self.boost, dtype=np.float32))

    def to_dict(self):
        return {"match_all": {}}


class MatchNoneQuery(Query):
    def execute(self, ctx):
        return DocSet.empty()

    def to_dict(self):
        return {"match_none": {}}


def _id_rows(ctx: SearchContext, ids) -> np.ndarray:
    """Rows for _id metadata-field lookups (term/terms/ids queries on _id).
    The id→row map is built once per reader (the Lucene _id terms dict)."""
    cache = getattr(ctx.reader, "_id_row_cache", None)
    if cache is None:
        cache = {}
        for v in ctx.reader.views:
            seg = v.segment
            for local, did in enumerate(seg.ids):
                if v.live[local]:
                    cache[did] = seg.base + local
        ctx.reader._id_row_cache = cache
    rows = sorted(r for r in (cache.get(str(i)) for i in ids) if r is not None)
    return np.asarray(rows, dtype=np.int64)


def _term_postings(ctx: SearchContext, field: str, term: str):
    """Collect (rows, freqs) for a term across segments, live docs only."""
    field = ctx.mapper_service.resolve_field(field)
    rows_parts, freq_parts = [], []
    for view in ctx.reader.views:
        p = view.segment.get_postings(field, term)
        if p is None:
            continue
        live = view.live[p.doc_ids]
        ids = p.doc_ids[live]
        rows_parts.append(ids.astype(np.int64) + view.segment.base)
        freq_parts.append(p.freqs[live])
    if not rows_parts:
        return np.zeros(0, dtype=np.int64), np.zeros(0, dtype=np.int32)
    return np.concatenate(rows_parts), np.concatenate(freq_parts)


def _field_lengths_for(ctx: SearchContext, field: str, rows: np.ndarray) -> np.ndarray:
    out = np.zeros(len(rows), dtype=np.float32)
    for view in ctx.reader.views:
        seg = view.segment
        fl = seg.field_lengths.get(field)
        if fl is None:
            continue
        in_seg = (rows >= seg.base) & (rows < seg.base + seg.num_docs)
        out[in_seg] = fl[rows[in_seg] - seg.base]
    return out


def bm25_scores(ctx: SearchContext, field: str, rows: np.ndarray,
                freqs: np.ndarray, boost: float = 1.0) -> np.ndarray:
    field = ctx.mapper_service.resolve_field(field)
    n = max(ctx.reader.docs_with_field_count(field), 1)
    df = len(rows)
    idf = math.log(1.0 + (n - df + 0.5) / (df + 0.5))
    avg_len = ctx.reader.avg_field_length(field) or 1.0
    lengths = _field_lengths_for(ctx, field, rows)
    return native.bm25_score(freqs, lengths, idf, avg_len,
                             BM25_K1, BM25_B, boost)


def _index_term_for(mapper, value: Any) -> Optional[str]:
    """Coerce a query value to the indexed term representation."""
    if mapper is None:
        return str(value)
    try:
        terms = mapper.index_terms(value)
    except Exception:
        return None
    return terms[0] if terms else None


class TermQuery(Query):
    def __init__(self, field: str, value: Any, boost: float = 1.0):
        self.field = field
        self.value = value
        self.boost = boost

    def execute(self, ctx: SearchContext) -> DocSet:
        if self.field == "_id":
            rows = _id_rows(ctx, [self.value])
            return DocSet(rows, np.full(len(rows), self.boost, dtype=np.float32))
        mapper = ctx.mapper_service.get(self.field)
        if isinstance(mapper, RangeFieldMapperBase):
            # membership: the queried point lies inside the stored interval
            v = mapper.query_bound(self.value)
            return _scan_range_docs(
                ctx, ctx.mapper_service.resolve_field(self.field),
                lambda lo, hi: lo <= v <= hi, self.boost)
        if isinstance(mapper, TextFieldMapper):
            # term query on text matches the single analyzed-or-raw token as-is
            term = str(self.value)
        else:
            term = _index_term_for(mapper, self.value)
            if term is None:
                return DocSet.empty()
        rows, freqs = _term_postings(ctx, self.field, term)
        order = np.argsort(rows, kind="stable")
        rows, freqs = rows[order], freqs[order]
        if isinstance(mapper, TextFieldMapper):
            scores = bm25_scores(ctx, self.field, rows, freqs, self.boost)
        else:
            scores = np.full(len(rows), self.boost, dtype=np.float32)
        return DocSet(rows, scores)

    def to_dict(self):
        return {"term": {self.field: {"value": self.value, "boost": self.boost}}}


class TermsQuery(Query):
    def __init__(self, field: str, values: List[Any], boost: float = 1.0,
                 user_supplied: bool = False):
        self.field = field
        self.values = values
        self.boost = boost
        # index.max_terms_count bounds only caller-provided term arrays;
        # internal multi-term rewrites (prefix/wildcard/regexp expansion)
        # are governed by max_clause_count in the reference
        self.user_supplied = user_supplied

    def execute(self, ctx: SearchContext) -> DocSet:
        max_terms = int(getattr(ctx, "index_settings", {})
                        .get("index.max_terms_count", 65536))
        if self.user_supplied and len(self.values) > max_terms:
            raise IllegalArgumentError(
                f"The number of terms [{len(self.values)}] used in the "
                f"Terms Query request has exceeded the allowed maximum "
                f"of [{max_terms}]. This maximum can be set by changing "
                f"the [index.max_terms_count] index level setting.")
        if self.field == "_id":
            rows = _id_rows(ctx, self.values)
            return DocSet(rows, np.full(len(rows), self.boost, dtype=np.float32))
        mapper = ctx.mapper_service.get(self.field)
        all_rows = []
        for v in self.values:
            term = str(v) if isinstance(mapper, TextFieldMapper) else _index_term_for(mapper, v)
            if term is None:
                continue
            rows, _ = _term_postings(ctx, self.field, term)
            all_rows.append(rows)
        if not all_rows:
            return DocSet.empty()
        rows = np.unique(np.concatenate(all_rows))
        return DocSet(rows, np.full(len(rows), self.boost, dtype=np.float32))

    def to_dict(self):
        return {"terms": {self.field: self.values}}


class MatchQuery(Query):
    def __init__(self, field: str, text: Any, operator: str = "or",
                 minimum_should_match: Optional[int] = None, boost: float = 1.0,
                 fuzziness: Optional[str] = None):
        self.field = field
        self.text = text
        self.operator = operator.lower()
        self.minimum_should_match = minimum_should_match
        self.boost = boost
        self.fuzziness = fuzziness

    def _analyzed_terms(self, ctx: SearchContext) -> List[str]:
        mapper = ctx.mapper_service.get(self.field)
        if isinstance(mapper, TextFieldMapper):
            return mapper.search_analyzer.terms(str(self.text))
        term = str(self.text) if mapper is None else _index_term_for(mapper, self.text)
        return [term] if term is not None else []

    def execute(self, ctx: SearchContext) -> DocSet:
        terms = self._analyzed_terms(ctx)
        if not terms:
            return DocSet.empty()
        if self.fuzziness is not None:
            expanded = []
            for t in terms:
                expanded.extend(_fuzzy_expand(ctx, self.field, t, self.fuzziness))
            terms = expanded or terms
        clause_sets = []
        for t in terms:
            rows, freqs = _term_postings(ctx, self.field, t)
            order = np.argsort(rows, kind="stable")
            rows, freqs = rows[order], freqs[order]
            scores = bm25_scores(ctx, self.field, rows, freqs, self.boost)
            clause_sets.append(DocSet(rows, scores))
        if self.operator == "and":
            required = len(clause_sets)
        else:
            required = resolve_msm(self.minimum_should_match, len(clause_sets))
        return _combine_should(clause_sets, required)

    def to_dict(self):
        return {"match": {self.field: {"query": self.text, "operator": self.operator}}}


class MatchPhraseQuery(Query):
    def __init__(self, field: str, text: str, slop: int = 0, boost: float = 1.0):
        self.field = field
        self.text = text
        self.slop = slop
        self.boost = boost

    def execute(self, ctx: SearchContext) -> DocSet:
        mapper = ctx.mapper_service.get(self.field)
        if not isinstance(mapper, TextFieldMapper):
            return TermQuery(self.field, self.text, self.boost).execute(ctx)
        terms = mapper.search_analyzer.terms(str(self.text))
        if not terms:
            return DocSet.empty()
        rows_out, scores_out = [], []
        for view in ctx.reader.views:
            seg = view.segment
            plists = [seg.get_postings(self.field, t) for t in terms]
            if any(p is None or p.positions is None for p in plists):
                if any(p is None for p in plists):
                    continue
            # candidate docs: intersection of all term postings
            cand = plists[0].doc_ids
            for p in plists[1:]:
                cand = np.intersect1d(cand, p.doc_ids, assume_unique=True)
            for local in cand:
                if not view.live[local]:
                    continue
                pos_lists = []
                ok = True
                for p in plists:
                    idx = int(np.searchsorted(p.doc_ids, local))
                    pl = p.positions[idx] if p.positions else None
                    if pl is None:
                        ok = False
                        break
                    pos_lists.append(set(pl))
                if not ok:
                    continue
                if _phrase_match(pos_lists, self.slop):
                    rows_out.append(seg.base + int(local))
        if not rows_out:
            return DocSet.empty()
        rows = np.asarray(sorted(rows_out), dtype=np.int64)
        # phrase scoring: sum of member-term BM25, like Lucene's PhraseQuery approx
        total = np.zeros(len(rows), dtype=np.float32)
        for t in terms:
            trows, tfreqs = _term_postings(ctx, self.field, t)
            order = np.argsort(trows, kind="stable")
            trows, tfreqs = trows[order], tfreqs[order]
            ts = bm25_scores(ctx, self.field, trows, tfreqs, self.boost)
            idx = np.searchsorted(trows, rows)
            idx = np.clip(idx, 0, len(trows) - 1)
            hit = trows[idx] == rows
            total[hit] += ts[idx][hit]
        return DocSet(rows, total)

    def to_dict(self):
        return {"match_phrase": {self.field: {"query": self.text, "slop": self.slop}}}


def _phrase_match(pos_sets: List[set], slop: int) -> bool:
    first = pos_sets[0]
    for start in first:
        if _phrase_from(pos_sets, 1, start, slop):
            return True
    return False


def _phrase_from(pos_sets, i, prev, slop) -> bool:
    if i == len(pos_sets):
        return True
    for p in pos_sets[i]:
        if 0 < p - prev <= 1 + slop:
            if _phrase_from(pos_sets, i + 1, p, slop):
                return True
    return False


def scan_doc_values(ctx: SearchContext, field: str, value_match,
                    boost: float = 1.0) -> DocSet:
    """Docs whose (possibly multi-valued) doc value satisfies value_match —
    the shared scan for fields matched by value inspection rather than
    postings (range fields, geo shapes)."""
    rows_parts = []
    for view in ctx.reader.views:
        seg = view.segment
        col = seg.doc_values.get(field)
        if col is None:
            continue
        locs = []
        for i, v in enumerate(col.values):
            if v is None or not view.live[i]:
                continue
            if any(value_match(item) for item in
                   (v if isinstance(v, list) else [v])):
                locs.append(i)
        if locs:
            rows_parts.append(np.asarray(locs, dtype=np.int64) + seg.base)
    if not rows_parts:
        return DocSet.empty()
    rows = np.sort(np.concatenate(rows_parts))
    return DocSet(rows, np.full(len(rows), boost, dtype=np.float32))


def _scan_range_docs(ctx: SearchContext, field: str, predicate,
                     boost: float) -> DocSet:
    """Range-field scan: predicate over the stored inclusive interval."""
    return scan_doc_values(
        ctx, field,
        lambda v: isinstance(v, dict) and predicate(v.get("gte", -np.inf),
                                                    v.get("lte", np.inf)),
        boost)


class RangeQuery(Query):
    def __init__(self, field: str, gte=None, gt=None, lte=None, lt=None,
                 boost: float = 1.0, fmt: Optional[str] = None,
                 relation: str = "intersects"):
        self.field = field
        self.gte, self.gt, self.lte, self.lt = gte, gt, lte, lt
        self.boost = boost
        self.fmt = fmt
        self.relation = relation

    def _coerce_bound(self, ctx, value, round_up: bool = False):
        from elasticsearch_tpu.index.mapping import (parse_date_in_format,
                                                     parse_date_nanos)
        mapper = ctx.mapper_service.get(self.field)
        if isinstance(mapper, DateFieldMapper) and self.fmt:
            # the query's own `format` reads its bounds
            millis = parse_date_in_format(value, self.fmt, round_up)
            if millis is not None:
                return float(millis * 1_000_000
                             if isinstance(mapper, DateNanosFieldMapper)
                             else millis)
        if isinstance(mapper, DateNanosFieldMapper):
            if isinstance(value, str) and ("||" in value
                                           or value.startswith("now")
                                           or round_up):
                return float(parse_date_millis(value, round_up=round_up)
                             * 1_000_000)
            return float(parse_date_nanos(value))
        if isinstance(mapper, DateFieldMapper):
            # same unit as storage; gt/lte round date math UP to unit end
            # (JavaDateMathParser roundUp semantics); custom locale-aware
            # formats parse through the mapper's formatter
            fmt = str(mapper.params.get("format", ""))
            if isinstance(value, str) and fmt \
                    and ("E" in fmt or "MMM" in fmt):
                try:
                    return float(mapper._parse(value))
                except Exception:
                    pass
            if "epoch_second" in fmt and not isinstance(value, bool) and (
                    isinstance(value, (int, float))
                    or re.fullmatch(r"-?\d{5,}(\.\d+)?", str(value))):
                # a bound parses with the field's format, as its values
                # do (`DateFieldMapper._parse`): a number is SECONDS
                return float(value) * 1000.0
            return float(parse_date_millis(value, round_up=round_up))
        if isinstance(mapper, IpFieldMapper):
            return float(mapper.coerce(value))
        if isinstance(mapper, RangeFieldMapperBase):
            return mapper.query_bound(value, round_up=round_up)
        return float(value)

    def execute(self, ctx: SearchContext) -> DocSet:
        mapper = ctx.mapper_service.get(self.field)
        if isinstance(mapper, (TextFieldMapper, KeywordFieldMapper)) \
                and getattr(ctx, "allow_expensive", True) is False:
            # term-scan ranges over strings are the expensive path
            # (TermBasedFieldType rangeQuery gate)
            raise IllegalArgumentError(
                "[range] queries on [text] or [keyword] fields cannot be "
                "executed when 'search.allow_expensive_queries' is set to "
                "false.")
        lo = -np.inf
        hi = np.inf
        lo_inc = hi_inc = True
        numeric_bounds = True
        try:
            if self.gte is not None:
                lo = self._coerce_bound(ctx, self.gte)
            if self.gt is not None:
                lo, lo_inc = self._coerce_bound(ctx, self.gt,
                                                round_up=True), False
            if self.lte is not None:
                hi = self._coerce_bound(ctx, self.lte, round_up=True)
            if self.lt is not None:
                hi, hi_inc = self._coerce_bound(ctx, self.lt), False
        except (ValueError, TypeError) as e:
            # on a NUMERIC/date/ip field an unparseable bound is the
            # caller's error — never silently degrade to string compare
            mapper = ctx.mapper_service.get(self.field)
            if isinstance(mapper, (_NumericMapper, DateFieldMapper,
                                   IpFieldMapper, RangeFieldMapperBase,
                                   BooleanFieldMapper)):
                raise IllegalArgumentError(
                    f"failed to parse range bound on field "
                    f"[{self.field}]: {e}")
            # keyword/text/unmapped: the string-doc-values path applies
            numeric_bounds = False

        mapper = ctx.mapper_service.get(self.field)
        if isinstance(mapper, RangeFieldMapperBase):
            # interval-vs-interval with the requested relation
            # (reference: RangeFieldMapper query relations)
            qlo = lo if lo_inc else (lo + 1 if mapper.discrete
                                     else float(np.nextafter(lo, np.inf)))
            qhi = hi if hi_inc else (hi - 1 if mapper.discrete
                                     else float(np.nextafter(hi, -np.inf)))
            if self.relation == "within":     # stored ⊆ query
                pred = lambda slo, shi: slo >= qlo and shi <= qhi
            elif self.relation == "contains":  # stored ⊇ query
                pred = lambda slo, shi: slo <= qlo and shi >= qhi
            else:                              # intersects
                pred = lambda slo, shi: slo <= qhi and shi >= qlo
            return _scan_range_docs(
                ctx, ctx.mapper_service.resolve_field(self.field),
                pred, self.boost)

        field = ctx.mapper_service.resolve_field(self.field)
        rows_parts = []
        for view in ctx.reader.views:
            seg = view.segment
            col = seg.doc_values.get(field)
            if col is None or col.numeric is None or not numeric_bounds:
                # fall back to string doc values (keyword ranges)
                if col is not None:
                    locs = [i for i, v in enumerate(col.values)
                            if v is not None and view.live[i]
                            and _str_in_range(v, self.gte, self.gt, self.lte, self.lt)]
                    if locs:
                        rows_parts.append(np.asarray(locs, dtype=np.int64) + seg.base)
                continue
            vals = col.numeric
            mask = col.present & view.live
            mask &= (vals >= lo) if lo_inc else (vals > lo)
            mask &= (vals <= hi) if hi_inc else (vals < hi)
            locs = np.nonzero(mask)[0]
            if len(locs):
                rows_parts.append(locs.astype(np.int64) + seg.base)
        if not rows_parts:
            return DocSet.empty()
        rows = np.sort(np.concatenate(rows_parts))
        return DocSet(rows, np.full(len(rows), self.boost, dtype=np.float32))

    def to_dict(self):
        body = {}
        for k in ("gte", "gt", "lte", "lt"):
            v = getattr(self, k)
            if v is not None:
                body[k] = v
        return {"range": {self.field: body}}


def _str_in_range(v, gte, gt, lte, lt) -> bool:
    s = str(v)
    if gte is not None and s < str(gte):
        return False
    if gt is not None and s <= str(gt):
        return False
    if lte is not None and s > str(lte):
        return False
    if lt is not None and s >= str(lt):
        return False
    return True


class ExistsQuery(Query):
    def __init__(self, field: str, boost: float = 1.0):
        self.field = field
        self.boost = boost

    _META_ALWAYS = {"_id", "_index", "_type", "_seq_no", "_primary_term",
                    "_version"}

    def execute(self, ctx: SearchContext) -> DocSet:
        from elasticsearch_tpu.common.errors import QueryShardError
        field = ctx.mapper_service.resolve_field(self.field)
        if field == "_source":
            # ExistsQueryBuilder rejects _source outright
            raise QueryShardError(
                "Cannot run exists query on [_source]")
        if field in self._META_ALWAYS:
            # metadata every live doc carries: all docs match
            rows_parts = [
                (np.nonzero(view.live)[0].astype(np.int64)
                 + view.segment.base)
                for view in ctx.reader.views]
            rows = (np.sort(np.concatenate(rows_parts))
                    if rows_parts else np.zeros(0, dtype=np.int64))
            return DocSet(rows, np.full(len(rows), self.boost,
                                        dtype=np.float32))
        prefix = field + "."
        rows_parts = []
        for view in ctx.reader.views:
            seg = view.segment
            mask = None
            # direct columns plus subfield columns: an `object` field
            # exists wherever ANY of its properties does (the reference
            # rewrites object exists to a sub-field disjunction)
            for store, extract in ((seg.doc_values,
                                    lambda c: c.present),
                                   (seg.field_lengths, lambda fl: fl > 0),
                                   (seg.vectors, lambda v: v[1])):
                for name, col in store.items():
                    if name == field or name.startswith(prefix):
                        m = extract(col)
                        mask = m.copy() if mask is None else (mask | m)
            if mask is None:
                continue
            locs = np.nonzero(mask & view.live)[0]
            if len(locs):
                rows_parts.append(locs.astype(np.int64) + seg.base)
        if not rows_parts:
            # columnless MAPPED fields (e.g. binary with doc_values:
            # false, object with unindexed members): fall back to a
            # stored-source presence walk — unmapped fields still return
            # empty without scanning
            mapper = ctx.mapper_service.get(field)
            if mapper is not None or self._maps_object(ctx, prefix):
                rows_parts = self._source_walk(ctx, field)
        if not rows_parts:
            return DocSet.empty()
        rows = np.sort(np.concatenate(rows_parts))
        return DocSet(rows, np.full(len(rows), self.boost, dtype=np.float32))

    @staticmethod
    def _maps_object(ctx, prefix: str) -> bool:
        to_dict = getattr(ctx.mapper_service, "to_dict", None)
        if to_dict is None:
            return False

        def walk(props, pre=""):
            for name, d in (props or {}).items():
                full = pre + name
                if full == prefix[:-1] or full.startswith(prefix):
                    return True
                if isinstance(d, dict) and "properties" in d:
                    if walk(d["properties"], full + "."):
                        return True
            return False
        return walk((to_dict() or {}).get("properties"))

    def _source_walk(self, ctx, field: str):
        parts = field.split(".")
        rows_parts = []
        for view in ctx.reader.views:
            seg = view.segment
            hits = []
            for local in np.nonzero(view.live)[0]:
                node = ctx.reader.get_source(int(seg.base + local)) or {}
                for p in parts:
                    node = node.get(p) if isinstance(node, dict) else None
                    if node is None:
                        break
                if node is not None:
                    hits.append(int(seg.base + local))
            if hits:
                rows_parts.append(np.asarray(hits, dtype=np.int64))
        return rows_parts

    def to_dict(self):
        return {"exists": {"field": self.field}}


class IdsQuery(Query):
    def __init__(self, values: List[str], boost: float = 1.0):
        self.values = values
        self.boost = boost

    def execute(self, ctx: SearchContext) -> DocSet:
        wanted = set(map(str, self.values))
        rows = []
        for view in ctx.reader.views:
            seg = view.segment
            for local, doc_id in enumerate(seg.ids):
                if doc_id in wanted and view.live[local]:
                    rows.append(seg.base + local)
        rows = np.asarray(sorted(rows), dtype=np.int64)
        return DocSet(rows, np.full(len(rows), self.boost, dtype=np.float32))

    def to_dict(self):
        return {"ids": {"values": self.values}}


def _pattern_terms(ctx: SearchContext, field: str, predicate) -> List[str]:
    field = ctx.mapper_service.resolve_field(field)
    seen = set()
    for view in ctx.reader.views:
        for term in view.segment.terms_of(field):
            if term not in seen and predicate(term):
                seen.add(term)
    return sorted(seen)


def _check_expensive(ctx: SearchContext, qtype: str, extra: str = "") -> None:
    """search.allow_expensive_queries gate (QueryShardContext
    allowExpensiveQueries)."""
    if getattr(ctx, "allow_expensive", True) is False:
        raise IllegalArgumentError(
            f"[{qtype}] queries cannot be executed when "
            f"'search.allow_expensive_queries' is set to false.{extra}")


class PrefixQuery(Query):
    def __init__(self, field: str, value: str, boost: float = 1.0):
        self.field = field
        self.value = str(value)
        self.boost = boost

    def execute(self, ctx: SearchContext) -> DocSet:
        _check_expensive(ctx, "prefix",
                         " For optimised prefix queries on text fields "
                         "please enable [index_prefixes].")
        terms = _pattern_terms(ctx, self.field, lambda t: t.startswith(self.value))
        return TermsQuery(self.field, terms, self.boost).execute(ctx) if terms else DocSet.empty()

    def to_dict(self):
        return {"prefix": {self.field: {"value": self.value}}}


class WildcardQuery(Query):
    def __init__(self, field: str, value: str, boost: float = 1.0):
        self.field = field
        self.value = str(value)
        self.boost = boost

    def execute(self, ctx: SearchContext) -> DocSet:
        _check_expensive(ctx, "wildcard")
        pattern = re.compile(
            "^" + "".join(".*" if c == "*" else "." if c == "?" else re.escape(c)
                          for c in self.value) + "$")
        terms = _pattern_terms(ctx, self.field, lambda t: pattern.match(t) is not None)
        return TermsQuery(self.field, terms, self.boost).execute(ctx) if terms else DocSet.empty()

    def to_dict(self):
        return {"wildcard": {self.field: {"value": self.value}}}


class RegexpQuery(Query):
    def __init__(self, field: str, value: str, boost: float = 1.0):
        self.field = field
        self.value = str(value)
        self.boost = boost

    def execute(self, ctx: SearchContext) -> DocSet:
        _check_expensive(ctx, "regexp")
        max_len = int(getattr(ctx, "index_settings", {}).get(
            "index.max_regex_length", 1000))
        if len(self.value) > max_len:
            raise IllegalArgumentError(
                f"The length of regex [{len(self.value)}] used in the "
                f"Regexp Query request has exceeded the allowed maximum "
                f"of [{max_len}]. This maximum can be set by changing the "
                f"[index.max_regex_length] index level setting.")
        try:
            pattern = re.compile("^" + self.value + "$")
        except re.error as e:
            raise IllegalArgumentError(f"invalid regexp [{self.value}]: {e}")
        terms = _pattern_terms(ctx, self.field, lambda t: pattern.match(t) is not None)
        return TermsQuery(self.field, terms, self.boost).execute(ctx) if terms else DocSet.empty()

    def to_dict(self):
        return {"regexp": {self.field: {"value": self.value}}}


def _edit_distance_le(a: str, b: str, k: int) -> bool:
    """Restricted Damerau-Levenshtein (OSA) — Lucene's fuzzy automata count
    an adjacent transposition as ONE edit (transpositions=true default)."""
    if abs(len(a) - len(b)) > k:
        return False
    prev2: Optional[List[int]] = None
    prev = list(range(len(b) + 1))
    for i, ca in enumerate(a, 1):
        cur = [i] + [0] * len(b)
        best = cur[0]
        for j, cb in enumerate(b, 1):
            cur[j] = min(prev[j] + 1, cur[j - 1] + 1, prev[j - 1] + (ca != cb))
            if (prev2 is not None and i > 1 and j > 1
                    and ca == b[j - 2] and a[i - 2] == cb):
                cur[j] = min(cur[j], prev2[j - 2] + 1)
            best = min(best, cur[j])
        if best > k:
            return False
        prev2, prev = prev, cur
    return prev[-1] <= k


def _fuzzy_expand(ctx: SearchContext, field: str, term: str, fuzziness) -> List[str]:
    if fuzziness in ("AUTO", "auto", None):
        k = 0 if len(term) <= 2 else 1 if len(term) <= 5 else 2
    else:
        k = int(fuzziness)
    if k == 0:
        return [term]
    return _pattern_terms(ctx, field, lambda t: _edit_distance_le(term, t, k))


class FuzzyQuery(Query):
    def __init__(self, field: str, value: str, fuzziness="AUTO", boost: float = 1.0):
        self.field = field
        self.value = str(value)
        self.fuzziness = fuzziness
        self.boost = boost

    def execute(self, ctx: SearchContext) -> DocSet:
        _check_expensive(ctx, "fuzzy")
        terms = _fuzzy_expand(ctx, self.field, self.value, self.fuzziness)
        if not terms:
            return DocSet.empty()
        sets = [TermQuery(self.field, t, self.boost).execute(ctx) for t in terms]
        return _combine_should(sets, 1)

    def to_dict(self):
        return {"fuzzy": {self.field: {"value": self.value, "fuzziness": self.fuzziness}}}


class MatchPhrasePrefixQuery(Query):
    def __init__(self, field: str, text: str, boost: float = 1.0):
        self.field = field
        self.text = str(text)
        self.boost = boost

    def execute(self, ctx: SearchContext) -> DocSet:
        mapper = ctx.mapper_service.get(self.field)
        if not isinstance(mapper, TextFieldMapper):
            return PrefixQuery(self.field, self.text, self.boost).execute(ctx)
        terms = mapper.search_analyzer.terms(self.text)
        if not terms:
            return DocSet.empty()
        *head, last = terms
        expansions = _pattern_terms(ctx, self.field, lambda t: t.startswith(last))[:50]
        if not expansions:
            return DocSet.empty()
        sets = []
        for exp in expansions:
            phrase = " ".join(head + [exp]) if head else exp
            sets.append(MatchPhraseQuery(self.field, phrase, boost=self.boost).execute(ctx))
        return _combine_should(sets, 1)

    def to_dict(self):
        return {"match_phrase_prefix": {self.field: {"query": self.text}}}


class MatchBoolPrefixQuery(Query):
    """`match_bool_prefix` (reference: MatchBoolPrefixQueryBuilder): analyze
    the text; every term is a SHOULD term clause except the last, which
    matches as a prefix. The canonical companion of search_as_you_type."""

    def __init__(self, field: str, text: str, boost: float = 1.0,
                 operator: str = "or",
                 minimum_should_match=None, analyzer: Optional[str] = None,
                 fuzziness=None):
        self.field = field
        self.text = str(text)
        self.boost = boost
        self.operator = str(operator).lower()
        self.minimum_should_match = minimum_should_match
        self.analyzer = analyzer
        self.fuzziness = fuzziness

    def execute(self, ctx: SearchContext) -> DocSet:
        mapper = ctx.mapper_service.get(self.field)
        if self.analyzer is not None:
            terms = ctx.mapper_service.registry.get(self.analyzer).terms(
                self.text)
        elif isinstance(mapper, TextFieldMapper):
            terms = mapper.search_analyzer.terms(self.text)
        else:
            terms = [self.text]
        if not terms:
            return DocSet.empty()
        *head, last = terms
        if self.fuzziness is not None:
            # fuzziness applies to the complete (non-prefix) terms only
            # (MatchBoolPrefixQueryBuilder setFuzziness)
            sets = [FuzzyQuery(self.field, t, self.fuzziness,
                               self.boost).execute(ctx) for t in head]
        else:
            sets = [TermQuery(self.field, t, self.boost).execute(ctx)
                    for t in head]
        sets.append(PrefixQuery(self.field, last, self.boost).execute(ctx))
        if self.minimum_should_match is not None:
            required = resolve_msm(self.minimum_should_match, len(sets))
        else:
            required = len(sets) if self.operator == "and" else 1
        return _combine_should(sets, required)

    def to_dict(self):
        return {"match_bool_prefix": {self.field: {"query": self.text}}}


class QueryStringQuery(Query):
    """Lucene-lite query_string (reference: `index/query/QueryStringQueryBuilder`
    via Lucene's classic QueryParser): supports `field:value`, quoted phrases,
    AND/OR/NOT operators, and free terms over default_field or all text
    fields."""

    def __init__(self, query: str, default_fields=None,
                 default_operator: str = "or", boost: float = 1.0):
        self.query = str(query)
        if isinstance(default_fields, str):
            default_fields = [default_fields]
        self.default_fields_param = list(default_fields or [])
        op = str(default_operator).strip().lower()
        if op not in ("and", "or"):
            raise ParsingError(f"invalid default_operator [{default_operator}], expected AND or OR")
        self.default_operator = op
        self.boost = boost

    _TOKEN_RE = re.compile(
        r'([+-]?)(?:(\w[\w.]*):)?'
        r'("(?:[^"]*)"|[\[{][^\]}]*[\]}]|\S+)')

    _RANGE_RE = re.compile(
        r'^([\[{])\s*(\S+)\s+TO\s+(\S+)\s*([\]}])$')

    def _default_fields(self, ctx: SearchContext) -> List[str]:
        fields = [f for f in self.default_fields_param if f != "*"]
        if fields:
            return [f.split("^")[0] for f in fields]
        return [p for p in ctx.mapper_service.field_names()
                if isinstance(ctx.mapper_service.get(p), TextFieldMapper)]

    def execute(self, ctx: SearchContext) -> DocSet:
        if self.query.strip() == "*":
            return MatchAllQuery(self.boost).execute(ctx)

        # Lucene regex syntax: a whole-query /re/ compiles to a RegexpQuery
        # per default field (QueryParserBase.getRegexpQuery) — length limits
        # apply before matching
        q = self.query.strip()
        if len(q) > 2 and q.startswith("/") and q.endswith("/"):
            fields = self._default_fields(ctx) or ["_all"]
            subs = [RegexpQuery(f, q[1:-1]) for f in fields]
            sub = subs[0] if len(subs) == 1 else DisMaxQuery(subs)
            return sub.execute(ctx)

        # pass 1: tokenize into clauses and the connectors between them
        clauses: List[dict] = []       # {sign, field, text, phrase, negated}
        connectors: List[Optional[str]] = []  # between clause i and i+1
        negate_next = False
        for m in self._TOKEN_RE.finditer(self.query):
            sign, field, text = m.group(1), m.group(2), m.group(3)
            if text in ("AND", "OR"):
                if connectors:
                    connectors[-1] = text
                continue
            if text == "NOT":
                negate_next = True
                continue
            phrase = text.startswith('"') and text.endswith('"')
            clauses.append({"sign": sign, "field": field,
                            "text": text[1:-1] if phrase else text,
                            "phrase": phrase, "negated": negate_next})
            negate_next = False
            connectors.append(None)

        if not clauses:
            return DocSet.empty()

        # pass 2: resolve required/optional — an explicit AND binds BOTH
        # neighbors; an explicit OR makes both optional; otherwise the
        # default operator decides (Lucene classic parser semantics).
        n = len(clauses)
        required = [self.default_operator == "and"] * n
        for i in range(n - 1):
            if connectors[i] == "AND":
                required[i] = required[i + 1] = True
            elif connectors[i] == "OR":
                required[i] = required[i + 1] = False

        must: List[Query] = []
        should: List[Query] = []
        must_not: List[Query] = []
        for i, c in enumerate(clauses):
            # sub-queries carry boost 1.0 — the wrapping BoolQuery applies
            # self.boost exactly once
            if c["field"]:
                range_m = self._RANGE_RE.match(c["text"])
                if range_m and not c["phrase"]:
                    # Lucene range syntax: [a TO b] inclusive, {a TO b}
                    # exclusive, * = open bound
                    open_b, lo, hi, close_b = range_m.groups()
                    kw = {}
                    if lo != "*":
                        kw["gte" if open_b == "[" else "gt"] = lo
                    if hi != "*":
                        kw["lte" if close_b == "]" else "lt"] = hi
                    sub: Query = RangeQuery(c["field"], **kw)
                elif (len(c["text"]) > 2 and c["text"].startswith("/")
                      and c["text"].endswith("/") and not c["phrase"]):
                    sub = RegexpQuery(c["field"], c["text"][1:-1])
                elif not c["phrase"] and ("*" in c["text"]
                                          or "?" in c["text"]):
                    # wildcard terms normalize through the analyzer chain
                    # (QueryParserBase.getWildcardQuery + normalization)
                    sub = WildcardQuery(c["field"], c["text"].lower())
                else:
                    sub = (MatchPhraseQuery(c["field"], c["text"])
                           if c["phrase"]
                           else MatchQuery(c["field"], c["text"]))
            else:
                fields = self._default_fields(ctx)
                if not c["phrase"] and ("*" in c["text"]
                                        or "?" in c["text"]):
                    # default-field wildcards behave like the fielded form
                    subs: List[Query] = [
                        WildcardQuery(f, c["text"].lower()) for f in fields]
                else:
                    subs = [
                        MatchPhraseQuery(f, c["text"]) if c["phrase"]
                        else MatchQuery(f, c["text"])
                        for f in fields]
                if not subs:
                    continue
                sub = subs[0] if len(subs) == 1 else DisMaxQuery(subs)
            if c["sign"] == "-" or c["negated"]:
                must_not.append(sub)
            elif c["sign"] == "+" or required[i]:
                must.append(sub)
            else:
                should.append(sub)
        if not (must or should or must_not):
            return DocSet.empty()
        return BoolQuery(must=must, should=should, must_not=must_not,
                         boost=self.boost).execute(ctx)

    def to_dict(self):
        return {"query_string": {"query": self.query}}


class MultiMatchQuery(Query):
    def __init__(self, query: str, fields: List[str], mm_type: str = "best_fields",
                 operator: str = "or", boost: float = 1.0,
                 analyzer: Optional[str] = None, minimum_should_match=None,
                 fuzziness=None):
        self.query = query
        self.fields = fields
        self.mm_type = mm_type
        self.operator = operator
        self.boost = boost
        self.analyzer = analyzer
        self.minimum_should_match = minimum_should_match
        self.fuzziness = fuzziness

    def execute(self, ctx: SearchContext) -> DocSet:
        def split_boost(f):
            if "^" in f:
                name, b = f.split("^", 1)
                return name, float(b)
            return f, 1.0

        # wildcard field patterns expand against the mapping
        # (QueryParserHelper.resolveMappingFields)
        import fnmatch as _fn
        resolved: List[str] = []
        for f in self.fields:
            name, _b = split_boost(f)
            if "*" in name:
                suffix = f[len(name):]
                for path, m in ctx.mapper_service.all_mappers():
                    if getattr(m, "type_name", None) in ("text", "keyword",
                                                         "search_as_you_type") \
                            and _fn.fnmatch(path, name):
                        resolved.append(path + suffix)
            else:
                resolved.append(f)

        sets = []
        for f in resolved:
            name, fboost = split_boost(f)
            if self.mm_type == "bool_prefix":
                # search_as_you_type target: all terms match, last as prefix
                # (reference: MatchBoolPrefixQueryBuilder)
                sets.append(MatchBoolPrefixQuery(
                    name, self.query, boost=self.boost * fboost,
                    operator=self.operator,
                    minimum_should_match=self.minimum_should_match,
                    analyzer=self.analyzer,
                    fuzziness=self.fuzziness).execute(ctx))
            else:
                sets.append(MatchQuery(name, self.query, operator=self.operator,
                                       boost=self.boost * fboost).execute(ctx))
        if not sets:
            return DocSet.empty()
        if self.mm_type == "best_fields":
            return _combine_max(sets)
        return _combine_should(sets, 1)  # most_fields / bool_prefix: sum

    def to_dict(self):
        return {"multi_match": {"query": self.query, "fields": self.fields,
                                "type": self.mm_type}}


class ConstantScoreQuery(Query):
    def __init__(self, filter_query: Query, boost: float = 1.0):
        self.filter_query = filter_query
        self.boost = boost

    def execute(self, ctx: SearchContext) -> DocSet:
        inner = self.filter_query.execute(ctx)
        return DocSet(inner.rows, np.full(len(inner.rows), self.boost, dtype=np.float32))

    def to_dict(self):
        return {"constant_score": {"filter": self.filter_query.to_dict(),
                                   "boost": self.boost}}


class BoostingQuery(Query):
    def __init__(self, positive: Query, negative: Query, negative_boost: float):
        self.positive = positive
        self.negative = negative
        self.negative_boost = negative_boost

    def execute(self, ctx: SearchContext) -> DocSet:
        pos = self.positive.execute(ctx).with_scores()
        neg = self.negative.execute(ctx)
        scores = pos.scores.copy()
        in_neg = np.isin(pos.rows, neg.rows)
        scores[in_neg] *= self.negative_boost
        return DocSet(pos.rows, scores)

    def to_dict(self):
        return {"boosting": {"positive": self.positive.to_dict(),
                             "negative": self.negative.to_dict(),
                             "negative_boost": self.negative_boost}}


class DisMaxQuery(Query):
    def __init__(self, queries: List[Query], tie_breaker: float = 0.0, boost: float = 1.0):
        self.queries = queries
        self.tie_breaker = tie_breaker
        self.boost = boost

    def execute(self, ctx: SearchContext) -> DocSet:
        sets = [q.execute(ctx).with_scores() for q in self.queries]
        if not sets:
            return DocSet.empty()
        rows = np.unique(np.concatenate([s.rows for s in sets]))
        best = np.zeros(len(rows), dtype=np.float32)
        total = np.zeros(len(rows), dtype=np.float32)
        for s in sets:
            idx = np.searchsorted(rows, s.rows)
            np.maximum.at(best, idx, s.scores)
            np.add.at(total, idx, s.scores)
        scores = best + self.tie_breaker * (total - best)
        return DocSet(rows, scores * self.boost)

    def to_dict(self):
        return {"dis_max": {"queries": [q.to_dict() for q in self.queries],
                            "tie_breaker": self.tie_breaker}}


# ---------------------------------------------------------------------------
# Bool composition
# ---------------------------------------------------------------------------

def resolve_msm(msm, n_clauses: int) -> int:
    """Parse minimum_should_match: int, numeric string, or 'N%' of clauses
    (reference: `Queries.calculateMinShouldMatch`). Negative values mean
    'all but N'."""
    if msm is None:
        return 1
    if isinstance(msm, int):
        value = msm
    else:
        s = str(msm).strip()
        try:
            if s.endswith("%"):
                pct = int(s[:-1])
                value = (n_clauses * pct) // 100 if pct >= 0 else \
                    n_clauses + (n_clauses * pct) // 100
            else:
                value = int(s)
        except ValueError:
            raise ParsingError(f"invalid minimum_should_match [{msm}]")
    if value < 0:
        value = n_clauses + value
    return max(min(value, n_clauses), 0)


def _combine_should(sets: List[DocSet], minimum_match: int) -> DocSet:
    """Union with score summing; keep docs matching >= minimum_match clauses."""
    sets = [s for s in sets]
    if not sets:
        return DocSet.empty()
    if minimum_match <= 1:
        # pure union-sum: fold through the native streaming merge
        rows, scores = sets[0].rows, sets[0].scores
        for s in sets[1:]:
            rows, scores = native.union_sum(rows, scores, s.rows, s.scores)
        return DocSet(rows, scores if scores is not None
                      else np.zeros(len(rows), dtype=np.float32))
    rows = np.unique(np.concatenate([s.rows for s in sets]))
    scores = np.zeros(len(rows), dtype=np.float32)
    counts = np.zeros(len(rows), dtype=np.int32)
    for s in sets:
        if len(s.rows) == 0:
            continue
        idx = np.searchsorted(rows, s.rows)
        np.add.at(scores, idx, s.scores if s.scores is not None else 0.0)
        np.add.at(counts, idx, 1)
    keep = counts >= minimum_match
    return DocSet(rows[keep], scores[keep])


def _combine_max(sets: List[DocSet]) -> DocSet:
    rows = np.unique(np.concatenate([s.rows for s in sets])) if sets else np.zeros(0, np.int64)
    scores = np.zeros(len(rows), dtype=np.float32)
    for s in sets:
        if len(s.rows) == 0:
            continue
        idx = np.searchsorted(rows, s.rows)
        np.maximum.at(scores, idx, s.scores if s.scores is not None else 0.0)
    return DocSet(rows, scores)


def _cached_filter_rows(ctx: SearchContext, q: Query) -> np.ndarray:
    """Filter-context execution through the node query cache: filters never
    score, so the row array alone is the full result (Lucene caches filter
    bitsets the same way; scoring clauses are never cached)."""
    cache = ctx.query_cache
    if cache is None:
        return q.execute(ctx).rows
    try:
        import json
        source = json.dumps(q.to_dict(), sort_keys=True, default=str)
    except Exception:
        return q.execute(ctx).rows
    gen = getattr(ctx.reader, "gen", None)
    if gen is None:
        return q.execute(ctx).rows
    rows = cache.get_rows(gen, source)
    if rows is None:
        rows = q.execute(ctx).rows
        cache.put_rows(gen, source, rows)
    return rows


class BoolQuery(Query):
    """must/filter/should/must_not with reference semantics
    (`index/query/BoolQueryBuilder.java`): filter and must_not never score;
    should adds to the score; minimum_should_match defaults to 1 when there
    are no must/filter clauses, else 0."""

    def __init__(self, must: List[Query] = (), filter: List[Query] = (),
                 should: List[Query] = (), must_not: List[Query] = (),
                 minimum_should_match: Optional[int] = None, boost: float = 1.0):
        self.must = list(must)
        self.filter = list(filter)
        self.should = list(should)
        self.must_not = list(must_not)
        self.minimum_should_match = minimum_should_match
        self.boost = boost

    def execute(self, ctx: SearchContext) -> DocSet:
        rows: Optional[np.ndarray] = None
        scores: Optional[np.ndarray] = None

        for q in self.must:
            s = q.execute(ctx).with_scores()
            if rows is None:
                rows, scores = s.rows, s.scores.copy()
            else:
                i1, i2 = native.intersect_sorted(rows, s.rows)
                rows = rows[i1]
                scores = scores[i1] + s.scores[i2]

        for q in self.filter:
            f_rows = _cached_filter_rows(ctx, q)
            if rows is None:
                rows = f_rows
                scores = np.zeros(len(rows), dtype=np.float32)
            else:
                i1, _ = native.intersect_sorted(rows, f_rows)
                rows = rows[i1]
                scores = scores[i1]

        msm = self.minimum_should_match
        if msm is not None:
            msm = resolve_msm(msm, len(self.should))
        if self.should:
            should_set = _combine_should([q.execute(ctx).with_scores() for q in self.should],
                                         msm if msm is not None else 1)
            if rows is None:
                rows, scores = should_set.rows, should_set.scores
            else:
                if msm is None or msm == 0:
                    # optional should: add scores where they match
                    idx = np.searchsorted(should_set.rows, rows)
                    idx = np.clip(idx, 0, max(len(should_set.rows) - 1, 0))
                    if len(should_set.rows):
                        hit = should_set.rows[idx] == rows
                        scores[hit] += should_set.scores[idx][hit]
                else:
                    i1, i2 = native.intersect_sorted(rows, should_set.rows)
                    rows = rows[i1]
                    scores = scores[i1] + should_set.scores[i2]

        if rows is None:
            rows = ctx.all_rows()
            scores = np.zeros(len(rows), dtype=np.float32)

        for q in self.must_not:
            s = q.execute(ctx)
            keep = ~np.isin(rows, s.rows, assume_unique=True)
            rows, scores = rows[keep], scores[keep]

        return DocSet(rows, scores * self.boost)

    def to_dict(self):
        out = {}
        if self.must:
            out["must"] = [q.to_dict() for q in self.must]
        if self.filter:
            out["filter"] = [q.to_dict() for q in self.filter]
        if self.should:
            out["should"] = [q.to_dict() for q in self.should]
        if self.must_not:
            out["must_not"] = [q.to_dict() for q in self.must_not]
        if self.minimum_should_match is not None:
            out["minimum_should_match"] = self.minimum_should_match
        return {"bool": out}


# ---------------------------------------------------------------------------
# Scoring wrappers
# ---------------------------------------------------------------------------

class FunctionScoreQuery(Query):
    """Subset of function_score (`index/query/functionscore/`): weight,
    field_value_factor, and script-free boost_mode/score_mode algebra."""

    def __init__(self, query: Query, functions: List[dict],
                 boost_mode: str = "multiply", score_mode: str = "multiply"):
        self.query = query
        self.functions = functions
        self.boost_mode = boost_mode
        self.score_mode = score_mode

    def execute(self, ctx: SearchContext) -> DocSet:
        base = self.query.execute(ctx).with_scores()
        if len(base.rows) == 0 or not self.functions:
            return base
        func_scores = []
        for fn in self.functions:
            weight = float(fn.get("weight", 1.0))
            if "field_value_factor" in fn:
                spec = fn["field_value_factor"]
                field = spec["field"]
                factor = float(spec.get("factor", 1.0))
                missing = float(spec.get("missing", 1.0))
                modifier = spec.get("modifier", "none")
                vals = np.full(len(base.rows), missing, dtype=np.float64)
                for i, row in enumerate(base.rows):
                    v = ctx.reader.get_doc_value(field, int(row))
                    if v is not None and not isinstance(v, (list, str, bool)):
                        vals[i] = float(v)
                vals = vals * factor
                if modifier == "log1p":
                    vals = np.log1p(np.maximum(vals, 0))
                elif modifier == "sqrt":
                    vals = np.sqrt(np.maximum(vals, 0))
                elif modifier == "square":
                    vals = vals ** 2
                func_scores.append(weight * vals.astype(np.float32))
            else:
                func_scores.append(np.full(len(base.rows), weight, dtype=np.float32))
        combined = func_scores[0]
        for fs in func_scores[1:]:
            if self.score_mode == "sum":
                combined = combined + fs
            elif self.score_mode == "max":
                combined = np.maximum(combined, fs)
            elif self.score_mode == "min":
                combined = np.minimum(combined, fs)
            elif self.score_mode == "avg":
                combined = (combined + fs) / 2
            else:
                combined = combined * fs
        if self.boost_mode == "replace":
            new = combined
        elif self.boost_mode == "sum":
            new = base.scores + combined
        elif self.boost_mode == "max":
            new = np.maximum(base.scores, combined)
        elif self.boost_mode == "min":
            new = np.minimum(base.scores, combined)
        elif self.boost_mode == "avg":
            new = (base.scores + combined) / 2
        else:
            new = base.scores * combined
        return DocSet(base.rows, new.astype(np.float32))

    def to_dict(self):
        return {"function_score": {"query": self.query.to_dict(),
                                   "functions": self.functions}}


# ---------------------------------------------------------------------------
# Parser: DSL dict -> Query
# ---------------------------------------------------------------------------

def parse_query(body: Optional[dict]) -> Query:
    """Parse the JSON query DSL (reference: QueryBuilders registered in
    `SearchModule.registerQueryParsers`)."""
    if body is None:
        return MatchAllQuery()
    if not isinstance(body, dict) or len(body) != 1:
        raise ParsingError(f"query must be an object with exactly one key, got {body!r}")
    kind, spec = next(iter(body.items()))

    if kind == "match_all":
        return MatchAllQuery(boost=float(spec.get("boost", 1.0)) if isinstance(spec, dict) else 1.0)
    if kind == "match_none":
        return MatchNoneQuery()
    if kind == "term":
        field, v = _single(spec, "term")
        if isinstance(v, dict):
            return TermQuery(field, v.get("value"), float(v.get("boost", 1.0)))
        return TermQuery(field, v)
    if kind == "terms":
        spec = dict(spec)
        boost = float(spec.pop("boost", 1.0))
        field, values = _single(spec, "terms")
        if not isinstance(values, list):
            raise ParsingError("[terms] query requires an array of values")
        return TermsQuery(field, values, boost, user_supplied=True)
    if kind == "match":
        field, v = _single(spec, "match")
        if isinstance(v, dict):
            return MatchQuery(field, v.get("query"), v.get("operator", "or"),
                              v.get("minimum_should_match"),
                              float(v.get("boost", 1.0)), v.get("fuzziness"))
        return MatchQuery(field, v)
    if kind == "match_phrase":
        field, v = _single(spec, "match_phrase")
        if isinstance(v, dict):
            return MatchPhraseQuery(field, v.get("query"), int(v.get("slop", 0)),
                                    float(v.get("boost", 1.0)))
        return MatchPhraseQuery(field, v)
    if kind == "match_phrase_prefix":
        field, v = _single(spec, "match_phrase_prefix")
        text = v.get("query") if isinstance(v, dict) else v
        return MatchPhrasePrefixQuery(field, text)
    if kind == "match_bool_prefix":
        field, v = _single(spec, "match_bool_prefix")
        if isinstance(v, dict):
            return MatchBoolPrefixQuery(
                field, v.get("query"), float(v.get("boost", 1.0)),
                v.get("operator", "or"),
                minimum_should_match=v.get("minimum_should_match"),
                analyzer=v.get("analyzer"),
                fuzziness=v.get("fuzziness"))
        return MatchBoolPrefixQuery(field, v)
    if kind in ("query_string", "simple_query_string"):
        fields = spec.get("fields") or (
            [spec["default_field"]] if spec.get("default_field") else [])
        return QueryStringQuery(spec.get("query", ""), fields,
                                spec.get("default_operator", "or"),
                                float(spec.get("boost", 1.0)))
    if kind == "multi_match":
        mmt = spec.get("type", "best_fields")
        if spec.get("slop") is not None and mmt in ("bool_prefix",
                                                    "cross_fields"):
            raise ParsingError(f"[slop] not allowed for type [{mmt}]")
        return MultiMatchQuery(spec.get("query"), spec.get("fields", []),
                               mmt, spec.get("operator", "or"),
                               analyzer=spec.get("analyzer"),
                               minimum_should_match=spec.get(
                                   "minimum_should_match"),
                               fuzziness=spec.get("fuzziness"))
    if kind == "range":
        field, v = _single(spec, "range")
        return RangeQuery(field, gte=v.get("gte", v.get("from")), gt=v.get("gt"),
                          lte=v.get("lte", v.get("to")), lt=v.get("lt"),
                          boost=float(v.get("boost", 1.0)),
                          fmt=v.get("format"),
                          relation=v.get("relation", "intersects").lower())
    if kind == "exists":
        return ExistsQuery(spec["field"])
    if kind == "ids":
        return IdsQuery(spec.get("values", []))
    if kind == "prefix":
        field, v = _single(spec, "prefix")
        return PrefixQuery(field, v.get("value") if isinstance(v, dict) else v)
    if kind == "span_multi":
        # SpanMultiTermQueryWrapper: a multi-term query (prefix/wildcard/
        # fuzzy/regexp) used in span position; standalone it matches the
        # wrapped query's documents
        return parse_query(spec.get("match") or {"match_all": {}})
    if kind == "wildcard":
        field, v = _single(spec, "wildcard")
        return WildcardQuery(field, (v.get("value") or v.get("wildcard")) if isinstance(v, dict) else v)
    if kind == "regexp":
        field, v = _single(spec, "regexp")
        return RegexpQuery(field, v.get("value") if isinstance(v, dict) else v)
    if kind == "fuzzy":
        field, v = _single(spec, "fuzzy")
        if isinstance(v, dict):
            return FuzzyQuery(field, v.get("value"), v.get("fuzziness", "AUTO"))
        return FuzzyQuery(field, v)
    if kind == "bool":
        def clause(name):
            c = spec.get(name, [])
            if isinstance(c, dict):
                c = [c]
            return [parse_query(q) for q in c]

        return BoolQuery(must=clause("must"), filter=clause("filter"),
                         should=clause("should"), must_not=clause("must_not"),
                         minimum_should_match=spec.get("minimum_should_match"),
                         boost=float(spec.get("boost", 1.0)))
    if kind == "constant_score":
        return ConstantScoreQuery(parse_query(spec["filter"]),
                                  float(spec.get("boost", 1.0)))
    if kind == "boosting":
        return BoostingQuery(parse_query(spec["positive"]),
                             parse_query(spec["negative"]),
                             float(spec.get("negative_boost", 0.5)))
    if kind == "dis_max":
        return DisMaxQuery([parse_query(q) for q in spec.get("queries", [])],
                           float(spec.get("tie_breaker", 0.0)))
    if kind == "function_score":
        inner = parse_query(spec.get("query", {"match_all": {}}))
        functions = spec.get("functions")
        if functions is None:
            functions = [{k: v for k, v in spec.items()
                          if k in ("field_value_factor", "weight")}]
        return FunctionScoreQuery(inner, functions,
                                  spec.get("boost_mode", "multiply"),
                                  spec.get("score_mode", "multiply"))
    if kind == "script_score":
        from elasticsearch_tpu.search.script_score import ScriptScoreQuery
        return ScriptScoreQuery(parse_query(spec.get("query", {"match_all": {}})),
                                spec.get("script", {}))
    if kind == "knn":
        from elasticsearch_tpu.search.knn_query import KnnQuery
        return KnnQuery(field=spec["field"], query_vector=spec["query_vector"],
                        k=int(spec.get("k", 10)),
                        num_candidates=int(spec.get("num_candidates", spec.get("k", 10))),
                        filter_query=parse_query(spec["filter"]) if "filter" in spec else None,
                        boost=float(spec.get("boost", 1.0)))
    # extended query types (geo, nested, join, percolate, span, …) register
    # in queries_ext — the analog of plugin-contributed query parsers
    # (reference: SearchPlugin.getQueries)
    from elasticsearch_tpu.search.queries_ext import parse_extended
    q = parse_extended(kind, spec)
    if q is not None:
        return q
    import difflib
    known = ("match", "match_all", "match_none", "match_phrase",
             "match_phrase_prefix", "multi_match", "term", "terms", "range",
             "bool", "exists", "prefix", "wildcard", "regexp", "fuzzy", "ids",
             "query_string", "simple_query_string", "nested", "knn",
             "constant_score", "function_score", "script_score", "dis_max",
             "boosting", "more_like_this", "terms_set", "span_term",
             "span_near", "intervals", "percolate", "rank_feature", "shape",
             "geo_shape", "geo_distance", "geo_bounding_box")
    hint = difflib.get_close_matches(str(kind), known, n=1)
    suffix = f" did you mean [{hint[0]}]?" if hint else ""
    raise ParsingError(f"unknown query [{kind}]{suffix}")


def _single(spec: Any, kind: str) -> Tuple[str, Any]:
    if not isinstance(spec, dict) or len(spec) != 1:
        raise ParsingError(f"[{kind}] query malformed, expected a single field")
    return next(iter(spec.items()))
