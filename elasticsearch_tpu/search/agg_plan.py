"""Fused filter→aggregate device plans for the core aggregation family.

The execution layer over `ops/aggs.py`: an agg body compiles ONCE into an
`AggPlan` (cached per index on the normalized body — the hybrid plan-cache
template trick generalized to agg bodies, so a dashboard's repeated shape
plans once and only the per-query numeric slots re-bind), and each search
executes the plan as a handful of pre-compiled dispatches: the matched row
set becomes a boolean mask over the columnar store's row bucket, bucket
ids derive in-kernel from resident key columns, and boards come back as
`n_buckets + 1` lanes of counts / sums / mins / maxs. A node's levels
bind once (`_bind_level`) to what either arithmetic takes; each program
(`_launch_counts`, `_launch_metric`) is the 32-bit one where every column
it reads has its 32-bit form and the x64 one otherwise (`ops/aggs.py`),
and its boards are read back into one layout (`_Pending.widen`).

Supported on device — numerically IDENTICAL to `compute_aggs` (final
mode) and `compute_partial_aggs` (distributed partial mode), pinned by
tests/test_device_aggs.py:

  terms            keyword / numeric / boolean / date / ip fields
                   (size, shard_size, missing, min_doc_count incl. 0,
                   order by _key/_count)
  histogram        interval, offset, missing, min_doc_count,
                   extended_bounds, format
  date_histogram   fixed intervals (+ offset, format, time_zone
                   rendering) and calendar intervals (hour .. year,
                   any time_zone) by the table of bounds the host's
                   own key math gives (`_n32_bounds`; the calendar's
                   is walked once a column version, `_calendar_bounds`)
  range            numeric from/to/key ranges (overlaps allowed)
  metrics          avg, sum, min, max, stats, value_count — top-level and
                   as one-level sub-aggs of any bucket agg above

Everything else — geo, cardinality/HLL, percentiles, pipelines as
sub-aggs, scripted, include/exclude, nested, composite, multi-valued
fields — falls through PER NODE to the host path (`compute_aggs` /
`compute_partial_aggs`), and sum-bearing metrics (sum/avg/stats) ride the
device only for integral columns where f64 scatter-adds are provably
order-free (see ops/aggs.py): exactness is a contract, not a tolerance.

Every request files its stages and counters through `telemetry.stage`
(`aggs.plan`, `aggs.mask`, `aggs.device` with `aggs.launch` and
`aggs.sync_wait` inside it, `aggs.assemble`, `aggs.host`; README
"End-to-end telemetry"); `indices.aggs`'s `device_nanos`,
`assemble_nanos` and `host_nanos` are sums of the same clock marks.

Partial mode emits the SAME `$p`-tagged partial-reduction states
`search/agg_partials.py` merges today, so mesh/multi-index serving gets
per-shard device partials merged through the existing
`merge_partial_aggs` with zero coordinator changes. The SPMD row-sharded
twins route through `parallel/policy.py` like every other kernel.
"""

from __future__ import annotations

import logging
import math
import threading
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from elasticsearch_tpu import telemetry
from elasticsearch_tpu.common.errors import (
    IllegalArgumentError, ParsingError, SearchEngineError,
)
from elasticsearch_tpu.ops import aggs as aggs_ops
from elasticsearch_tpu.ops import dispatch
from elasticsearch_tpu.search import aggregations as A

logger = logging.getLogger("elasticsearch_tpu.agg_plan")

SUPPORTED_METRICS = ("avg", "sum", "min", "max", "stats", "value_count")
SUM_KINDS = ("avg", "sum", "stats")

# mapper types whose doc values live faithfully in the f64 column
_NUMERIC_TNAMES = ("long", "integer", "short", "byte", "double", "float",
                   "half_float", "scaled_float", "date", "date_nanos",
                   "boolean", "ip")

_TERMS_ALLOWED_KEYS = {"field", "size", "shard_size", "missing",
                       "min_doc_count", "order", "value_type"}
_HISTO_ALLOWED_KEYS = {"field", "interval", "offset", "min_doc_count",
                       "missing", "extended_bounds", "format"}
_DATE_HISTO_ALLOWED_KEYS = {"field", "interval", "fixed_interval",
                            "calendar_interval", "offset", "min_doc_count",
                            "format", "time_zone"}
_RANGE_ALLOWED_KEYS = {"field", "ranges", "keyed"}
_CARD_ALLOWED_KEYS = {"field", "precision_threshold", "missing"}

# composite sub-agg trees: bucket-in-bucket nesting compiles to ONE flat
# board per (depth, metric) whose lane is parent_id * k_child + child_id
MAX_TREE_DEPTH = aggs_ops.TREE_MAX_DEPTH


def _mesh_call(name, *args, mesh, **kw):
    """Launch-guarded mesh dispatch: collective programs that share
    devices must ENQUEUE in one global order (`parallel/mesh.
    launch_guard`) — an aggs reduce racing a kNN/BM25 mesh launch on
    overlapping devices could otherwise deadlock the all-gather
    rendezvous. Execution stays async; the guard covers only the
    enqueue."""
    from elasticsearch_tpu.parallel import mesh as mesh_lib
    with mesh_lib.launch_guard(mesh):
        return dispatch.call(name, *args, mesh=mesh, **kw)


class _Fallback(Exception):
    """Bind-time device rejection: run this node on the host instead.
    `observed` optionally carries the measured quantity that busted the
    grid (e.g. the ordinal cardinality) so ladder growth is data-driven."""

    def __init__(self, reason: str, observed: Optional[int] = None):
        super().__init__(reason)
        self.reason = reason
        self.observed = observed


_TWO52 = float(1 << 52)

# the boards a metric's kind reads: the 32-bit program computes no other
_N32_PARTS = {"value_count": (), "sum": ("sum",), "avg": ("sum",),
              "min": ("min",), "max": ("max",),
              "stats": ("sum", "min", "max")}


class _Pending:
    """A launched program: its boards, still on the device, and how the
    host reads them (`widen`: the list of numpy boards to what the
    assembly consumes)."""

    __slots__ = ("boards", "widen")

    def __init__(self, boards: tuple, widen):
        self.boards = boards
        self.widen = widen


def _assembly_lanes(board: np.ndarray, ks, folds, merge=np.add,
                  empty=0) -> np.ndarray:
    """A 32-bit program's board (last axis: (k + 1) lanes a level, lane 0
    the rows whose key is absent) as the assembly reads it: prod(k)
    lanes and one more. A level's absent lane is merged into the lane
    `folds` names for it (its `missing` bucket) or dropped; the last
    lane is the absent keys' of a single level (a terms' `missing`
    bucket, as the x64 board has it) and empty under a tree. `board` is
    the host's own widened copy and is written to."""
    lead = board.ndim - 1
    a = board.reshape(board.shape[:lead] + tuple(k + 1 for k in ks))
    last = np.full(board.shape[:lead] + (1,), empty, dtype=board.dtype)
    for j, fold in enumerate(folds):
        a = np.moveaxis(a, lead + j, 0)
        if fold is not None and 0 <= fold < ks[j]:
            a[fold + 1] = merge(a[fold + 1], a[0])
        elif len(ks) == 1:
            last = a[0][..., None]
        a = np.moveaxis(a[1:], 0, lead + j)
    return np.concatenate(
        [a.reshape(board.shape[:lead] + (-1,)), last], axis=-1)


def _widen_metric(board, parts, bits, n_limbs, col, ks, folds):
    """(count int64, sum f64, min f64, max f64) over the assembly's lanes
    from a 32-bit metric program's board (`ops/aggs._agg_n32_metric`):
    the limbs recombined in int64 (sum of limb_j * 2^(bits * j), times
    the column's unit, plus count times its base: under 2^62 by
    `AggColumn.build_k32`), the extrema moved back from the rebased
    domain, an empty lane's to +-inf."""
    wide = board.astype(np.int64)
    cnt = _assembly_lanes(wide[0], ks, folds)
    total = np.zeros(cnt.shape, dtype=np.float64)
    mn = np.full(cnt.shape, np.inf)
    mx = np.full(cnt.shape, -np.inf)
    row = 1
    if "sum" in parts:
        units = np.zeros(wide.shape[1:], dtype=np.int64)
        for j in range(n_limbs):
            units += wide[row + j] << (bits * j)
        row += n_limbs
        total = (_assembly_lanes(units, ks, folds) * col.k_unit
                 + cnt * col.k_base).astype(np.float64)
    has = cnt > 0
    for part, merge, empty, out in (("min", np.minimum, aggs_ops.I32_MAX, mn),
                                    ("max", np.maximum, -1, mx)):
        if part in parts:
            k = _assembly_lanes(wide[row], ks, folds, merge=merge, empty=empty)
            out[has] = (k[has] * col.k_unit + col.k_base).astype(np.float64)
            row += 1
    return cnt, total, mn, mx


class _SubMetric:
    __slots__ = ("name", "kind", "field")

    def __init__(self, name, kind, field):
        self.name = name
        self.kind = kind
        self.field = field


class _Node:
    """One agg's compiled classification. mode: 'host' | 'metric' |
    'cardinality' | 'terms' | 'histogram' | 'date_histogram' | 'range'.
    Bucket nodes may carry `children` (nested bucket _Nodes — the
    composite-id tree) and `cards` (cardinality leaves) next to the
    metric `subs`."""

    __slots__ = ("name", "mode", "kind", "field", "subs", "host_reason",
                 "children", "cards")

    def __init__(self, name, mode, kind=None, field=None, subs=(),
                 host_reason=None, children=(), cards=()):
        self.name = name
        self.mode = mode
        self.kind = kind
        self.field = field
        self.subs = list(subs)
        self.host_reason = host_reason
        self.children = list(children)
        self.cards = list(cards)


class AggPlan:
    __slots__ = ("nodes", "device_count")

    def __init__(self, nodes: Dict[str, _Node]):
        self.nodes = nodes
        self.device_count = sum(1 for n in nodes.values()
                                if n.mode != "host")


# ---------------------------------------------------------------------------
# plan cache key: the hybrid `plan_cache_key` trick for agg bodies — the
# per-query numeric slots (interval/offset/bounds/missing) scrub to
# placeholders so a dashboard sweeping a slider re-uses one plan; kinds,
# fields, sizes and everything classification reads stay structural.
# ---------------------------------------------------------------------------


def plan_cache_key(aggs_spec: dict) -> str:
    def scrub_node(spec):
        if not isinstance(spec, dict):
            return spec
        out = {}
        for kind, body in spec.items():
            if kind in ("aggs", "aggregations"):
                out[kind] = {n: scrub_node(s)
                             for n, s in (body or {}).items()}
                continue
            if not isinstance(body, dict):
                out[kind] = body
                continue
            b = dict(body)
            if kind == "histogram":
                for key in ("interval", "offset", "missing",
                            "extended_bounds"):
                    if key in b:
                        b[key] = "__v__"
            elif kind == "date_histogram":
                # interval strings stay: "month" vs "1h" changes the
                # calendar-vs-fixed classification itself
                for key in ("offset", "missing"):
                    if key in b:
                        b[key] = "__v__"
            elif kind == "range":
                if isinstance(b.get("ranges"), list):
                    b["ranges"] = [
                        {k: ("__v__" if k in ("from", "to") else v)
                         for k, v in r.items()} if isinstance(r, dict)
                        else r
                        for r in b["ranges"]]
            elif kind in SUPPORTED_METRICS or kind == "cardinality":
                if "missing" in b:
                    b["missing"] = "__v__"
            out[kind] = b
        return out

    from elasticsearch_tpu.search.caches import _canonical
    return _canonical({n: scrub_node(s)
                       for n, s in (aggs_spec or {}).items()})


# ---------------------------------------------------------------------------
# plan compile (structural classification only — column-dependent checks
# happen at bind time, because columns change with every refresh)
# ---------------------------------------------------------------------------


def _classify_metric(kind: str, body, mapper_service) -> Optional[str]:
    """None = device-eligible; otherwise the host-fallback reason."""
    if not isinstance(body, dict):
        return "malformed"
    if body.get("script") is not None:
        return "script"
    field = body.get("field")
    if not isinstance(field, str):
        return "no_field"
    mapper = mapper_service.get(field)
    tname = getattr(mapper, "type_name", None)
    if tname is None:
        return "unmapped_field"
    if tname not in _NUMERIC_TNAMES:
        # keyword/text raise host-side for numeric-only metrics, and
        # value_count over keyword counts string values the f64 column
        # can't see — both are host business
        return "non_numeric_field"
    return None


def _classify_cardinality(body, mapper_service) -> Optional[str]:
    """None = device-eligible cardinality; otherwise the fallback
    reason. Keyword fields are in: the HLL register columns hash the raw
    doc values, not the f64 view."""
    if not isinstance(body, dict):
        return "malformed"
    if not set(body) <= _CARD_ALLOWED_KEYS:
        return "unsupported_param"
    if body.get("script") is not None:
        return "script"
    field = body.get("field")
    if not isinstance(field, str):
        return "no_field"
    mapper = mapper_service.get(field)
    tname = getattr(mapper, "type_name", None)
    if tname is None:
        return "unmapped_field"
    if tname not in _NUMERIC_TNAMES + ("keyword",):
        return "unsupported_field_type"
    return None


def _classify_subs(sub_spec: dict, mapper_service, depth: int = 1,
                   allow_buckets: bool = True
                   ) -> Tuple[list, list, list, str]:
    """Classify one bucket agg's sub-agg spec → (metric leaves,
    cardinality leaves, nested bucket children, reason). Bucket children
    recurse up to MAX_TREE_DEPTH levels (the composite-id tree); range
    parents pass allow_buckets=False (ranges overlap, so their members
    don't partition into composite ids)."""
    subs: List[_SubMetric] = []
    cards: List[_SubMetric] = []
    children: List[_Node] = []
    for sname, sspec in (sub_spec or {}).items():
        if not isinstance(sspec, dict):
            return [], [], [], "malformed_sub"
        skinds = [k for k in sspec
                  if k not in ("aggs", "aggregations", "meta")]
        if len(skinds) != 1:
            return [], [], [], "unsupported_sub_agg"
        skind = skinds[0]
        inner = sspec.get("aggs") or sspec.get("aggregations") or {}
        if skind in SUPPORTED_METRICS:
            if inner:
                return [], [], [], "sub_sub_aggs"
            reason = _classify_metric(skind, sspec[skind], mapper_service)
            if reason is not None:
                return [], [], [], f"sub_{reason}"
            subs.append(_SubMetric(sname, skind, sspec[skind]["field"]))
            continue
        if skind == "cardinality":
            if inner:
                return [], [], [], "unsupported_sub_agg"
            reason = _classify_cardinality(sspec[skind], mapper_service)
            if reason is not None:
                return [], [], [], f"sub_{reason}"
            cards.append(_SubMetric(sname, skind, sspec[skind]["field"]))
            continue
        if skind in ("terms", "histogram", "date_histogram") \
                and isinstance(sspec[skind], dict):
            if not allow_buckets:
                return [], [], [], "unsupported_sub_agg"
            if depth >= MAX_TREE_DEPTH:
                return [], [], [], "tree_too_deep"
            body = sspec[skind]
            reason = _classify_bucket(skind, body, mapper_service)
            if reason:
                return [], [], [], f"sub_{reason}"
            if skind == "terms" and isinstance(body.get("order"), dict) \
                    and next(iter(body["order"])) == "_count":
                # explicit _count order below the root would need per-row
                # first-occurrence tie-breaks inside every parent bucket —
                # host business (the DEFAULT sort's count tie-break is by
                # _key, which the device reproduces fine)
                return [], [], [], "order_count_in_subtree"
            csubs, ccards, cchildren, creason = _classify_subs(
                inner, mapper_service, depth + 1)
            if creason:
                return [], [], [], creason
            children.append(_Node(sname, skind, kind=skind,
                                  field=body.get("field"), subs=csubs,
                                  cards=ccards, children=cchildren))
            continue
        return [], [], [], "unsupported_sub_agg"
    return subs, cards, children, ""


def compile_plan(aggs_spec: dict, mapper_service) -> AggPlan:
    nodes: Dict[str, _Node] = {}
    for name, spec in (aggs_spec or {}).items():
        if not isinstance(spec, dict):
            nodes[name] = _Node(name, "host", host_reason="malformed")
            continue
        kinds = [k for k in spec
                 if k not in ("aggs", "aggregations", "meta")]
        if len(kinds) != 1:
            nodes[name] = _Node(name, "host", host_reason="malformed")
            continue
        kind = kinds[0]
        body = spec[kind]
        sub_spec = spec.get("aggs") or spec.get("aggregations") or {}
        if kind in A.PIPELINE_AGGS:
            nodes[name] = _Node(name, "host", kind=kind,
                                host_reason="pipeline")
            continue
        if kind in SUPPORTED_METRICS and not sub_spec:
            reason = _classify_metric(kind, body, mapper_service)
            if reason is None:
                nodes[name] = _Node(name, "metric", kind=kind,
                                    field=body["field"])
            else:
                nodes[name] = _Node(name, "host", kind=kind,
                                    host_reason=reason)
            continue
        if kind == "cardinality" and not sub_spec:
            reason = _classify_cardinality(body, mapper_service)
            if reason is None:
                nodes[name] = _Node(name, "cardinality", kind=kind,
                                    field=body["field"])
            else:
                nodes[name] = _Node(name, "host", kind=kind,
                                    host_reason=reason)
            continue
        if kind in ("terms", "histogram", "date_histogram", "range") \
                and isinstance(body, dict):
            reason = _classify_bucket(kind, body, mapper_service)
            subs, cards, children = [], [], []
            if not reason:
                subs, cards, children, reason = _classify_subs(
                    sub_spec, mapper_service,
                    allow_buckets=kind != "range")
            if not reason and kind == "range" and cards:
                # range members overlap — no composite-id partition for
                # the per-bucket HLL boards to scatter into
                reason = "unsupported_sub_agg"
            if not reason:
                nodes[name] = _Node(name, kind, kind=kind,
                                    field=body.get("field"), subs=subs,
                                    cards=cards, children=children)
                continue
            nodes[name] = _Node(name, "host", kind=kind,
                                host_reason=reason)
            continue
        nodes[name] = _Node(name, "host", kind=kind,
                            host_reason="unsupported_agg")
    return AggPlan(nodes)


def _classify_bucket(kind: str, body: dict, mapper_service) -> str:
    field = body.get("field")
    if not isinstance(field, str) or field == "_index":
        return "no_field"
    if body.get("script") is not None:
        return "script"
    if kind == "terms":
        if not set(body) <= _TERMS_ALLOWED_KEYS:
            return "unsupported_param"
        order = body.get("order")
        if order is not None:
            if not (isinstance(order, dict) and len(order) == 1
                    and next(iter(order)) in ("_key", "_count")):
                return "order_by_metric"
            if next(iter(order)) == "_count" \
                    and int(body.get("min_doc_count", 1)) == 0:
                # zero-count buckets tie at 0 and the host breaks that tie
                # by its term-universe SET iteration order — not a
                # contract the device path can reproduce
                return "order_count_zero_buckets"
        return ""
    mapper = mapper_service.get(field)
    tname = getattr(mapper, "type_name", None)
    if kind == "histogram":
        if not set(body) <= _HISTO_ALLOWED_KEYS:
            return "unsupported_param"
        return ""
    if kind == "date_histogram":
        if not set(body) <= _DATE_HISTO_ALLOWED_KEYS:
            return "unsupported_param"
        from elasticsearch_tpu.index.mapping import RangeFieldMapperBase
        if isinstance(mapper, RangeFieldMapperBase):
            return "range_field"
        return ""
    if kind == "range":
        if not set(body) <= _RANGE_ALLOWED_KEYS:
            return "unsupported_param"
        ranges = body.get("ranges")
        if not isinstance(ranges, list) or not ranges or any(
                not isinstance(r, dict) or "mask" in r for r in ranges):
            return "unsupported_ranges"
        return ""
    return "unsupported_agg"


# ---------------------------------------------------------------------------
# measured cost router
# ---------------------------------------------------------------------------


class CostRouter:
    """Per-kernel-family device-vs-host cost model calibrated from live
    timings: device legs record end-to-end (dispatch + assembly) nanos
    per family, host walkers record nanos per matched doc. A node routes
    to the device only when the device estimate beats the host estimate
    with margin — so tiny corpora on CPU floors take the host walker
    instead of paying the fixed dispatch cost — and every REPROBE-th
    otherwise-host decision probes the device to keep the model live.

    Priors (before any measurement) deliberately favor the device: the
    router exists to catch the measured-slow case, not to predict it.

    `persist_path` makes the learned EWMAs durable: every observation
    writes the snapshot (atomic tmp+rename, a few hundred bytes) and a
    restart seeds the tables back from disk instead of re-probing cold —
    the per-NODE router state, so one file serves every index's engine
    (`<data>/_state/agg_router.json`, wired in `node._agg_cost_router`).
    `restores` counts families seeded at boot (`_nodes/stats
    indices.aggs router_restores`)."""

    EWMA = 0.25
    MARGIN = 1.25
    REPROBE = 32
    DEV_PRIOR_BASE = 250_000.0      # ~fixed dispatch+assembly floor (ns)
    DEV_PRIOR_PER_ROW = 0.5         # ns per padded row
    HOST_PRIOR_BASE = 30_000.0
    HOST_PRIOR_PER_DOC = 400.0      # ns per matched doc (python walker)

    def __init__(self, persist_path: Optional[str] = None):
        self._lock = threading.Lock()
        self._dev: Dict[str, float] = {}       # family -> ewma ns
        self._host: Dict[str, float] = {}      # family -> ewma ns/doc
        self._miss: Dict[str, int] = {}        # family -> host streak
        self.persist_path = persist_path
        self.restores = 0
        if persist_path:
            self._load(persist_path)

    def _load(self, path: str) -> None:
        """Seed the EWMA tables from a prior run's snapshot. Corrupt or
        missing files mean cold priors, never a boot failure."""
        import json as _json
        try:
            with open(path, "r", encoding="utf-8") as f:
                state = _json.load(f)
        except (OSError, ValueError):
            return
        if not isinstance(state, dict):
            return
        restored = 0
        with self._lock:
            for table, key in ((self._dev, "device_ns"),
                               (self._host, "host_ns_per_doc")):
                ent = state.get(key)
                if not isinstance(ent, dict):
                    continue
                for fam, v in ent.items():
                    try:
                        table[str(fam)] = float(v)
                    except (TypeError, ValueError):
                        continue
                    restored += 1
        self.restores = restored

    def _persist(self) -> None:
        if not self.persist_path:
            return
        import json as _json
        import os as _os
        tmp = self.persist_path + ".tmp"
        try:
            with open(tmp, "w", encoding="utf-8") as f:
                _json.dump(self.snapshot(), f, sort_keys=True)
            _os.replace(tmp, self.persist_path)
        except OSError:  # pragma: no cover - disk-full/readonly boot
            pass

    def est_device(self, fam: str, r_pad: int) -> float:
        with self._lock:
            d = self._dev.get(fam)
        return d if d is not None else (
            self.DEV_PRIOR_BASE + self.DEV_PRIOR_PER_ROW * r_pad)

    def est_host(self, fam: str, n_docs: int) -> float:
        with self._lock:
            rate = self._host.get(fam)
        if rate is None:
            rate = self.HOST_PRIOR_PER_DOC
        return self.HOST_PRIOR_BASE + rate * max(n_docs, 1)

    def decide(self, fam: str, n_docs: int, r_pad: int) -> str:
        """'device' | 'probe' | 'host'. A probe runs on the device and
        feeds the model, keeping a stale host-favored estimate honest."""
        if self.est_host(fam, n_docs) * self.MARGIN \
                >= self.est_device(fam, r_pad):
            with self._lock:
                self._miss.pop(fam, None)
            return "device"
        with self._lock:
            streak = self._miss.get(fam, 0) + 1
            if streak >= self.REPROBE:
                self._miss[fam] = 0
                return "probe"
            self._miss[fam] = streak
        return "host"

    def _ewma(self, table: Dict[str, float], fam: str, x: float) -> None:
        with self._lock:
            prev = table.get(fam)
            table[fam] = x if prev is None else (
                prev + self.EWMA * (x - prev))

    def observe_device(self, fam: str, nanos: int) -> None:
        self._ewma(self._dev, fam, float(nanos))
        self._persist()

    def observe_host(self, fam: str, nanos: int, n_docs: int) -> None:
        self._ewma(self._host, fam, float(nanos) / max(n_docs, 1))
        self._persist()

    def snapshot(self) -> dict:
        with self._lock:
            return {"device_ns": dict(self._dev),
                    "host_ns_per_doc": dict(self._host)}


_counter = telemetry.metrics.counter


def _family(node: _Node) -> str:
    """Cost-model family: the top-level mode, with '_tree' marking the
    composite multi-board shape (very different cost profile)."""
    fam = node.mode
    if node.children or node.cards:
        fam += "_tree"
    return fam


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------


class AggEngine:
    """Per-index device aggregation engine: columnar store + plan cache +
    per-node device/host routing. `compute` returns (aggregations tree,
    profile info) — final JSON in single-pass mode, `$p` partial states in
    distributed-partial mode — or None when no node is device-eligible
    (the caller then runs the unchanged host path)."""

    def __init__(self, mapper_service, plan_cache_entries: int = 128,
                 warmup: Optional[bool] = None,
                 cost_router=False):
        from elasticsearch_tpu.search.caches import LruCache
        self.mapper_service = mapper_service
        self.store = aggs_ops.AggFieldStore(warmup=warmup)
        self.plan_cache = LruCache(max_entries=plan_cache_entries)
        # bool (own fresh router) or a CostRouter INSTANCE — the node
        # passes one shared, disk-backed router so every index's engine
        # trains (and restores) the same per-node cost model
        self.cost_router = (cost_router if isinstance(cost_router, CostRouter)
                            else (CostRouter() if cost_router else None))
        self._lock = threading.Lock()
        self._cal_cache = LruCache(max_entries=64)
        self.stats = {
            "searches": 0, "device_nodes": 0, "host_nodes": 0,
            "plan_cache_hits": 0, "plan_cache_misses": 0,
            "device_nanos": 0, "assemble_nanos": 0, "host_nanos": 0,
            "mesh_dispatches": 0, "router_host_routed": 0,
            "router_probes": 0, "fallback_reasons": {},
        }

    # ---------------------------------------------------------------- plan
    def plan_for(self, aggs_spec: dict) -> AggPlan:
        key = plan_cache_key(aggs_spec)
        plan = self.plan_cache.get(key)
        if plan is not None:
            with self._lock:
                self.stats["plan_cache_hits"] += 1
            return plan
        plan = compile_plan(aggs_spec, self.mapper_service)
        self.plan_cache.put(key, plan)
        with self._lock:
            self.stats["plan_cache_misses"] += 1
        return plan

    def _count(self, key: str, n: int = 1) -> None:
        with self._lock:
            self.stats[key] += n

    def _reason(self, reason: str, docs: int = 0,
                observed: Optional[int] = None) -> None:
        """fallback_reasons entries are {count, docs[, observed_max]}:
        matched-doc totals rank reasons by WORK routed host, not request
        volume, and observed_max (e.g. the ordinal cardinality that
        busted the ladder) makes grid growth data-driven."""
        with self._lock:
            r = self.stats["fallback_reasons"]
            ent = r.get(reason)
            if ent is None:
                ent = r[reason] = {"count": 0, "docs": 0}
            ent["count"] += 1
            ent["docs"] += int(docs)
            if observed is not None:
                ent["observed_max"] = max(int(observed),
                                          ent.get("observed_max", 0))

    # ------------------------------------------------------------- compute
    def compute(self, ctx, rows: np.ndarray, aggs_spec: dict,
                partial: bool = False) -> Optional[Tuple[dict, dict]]:
        if getattr(ctx, "nested_path", None):
            return None
        with telemetry.stage("aggs.plan"):
            plan = self.plan_for(aggs_spec)
        if plan.device_count == 0:
            return None
        self._count("searches")
        _counter("aggs.matched_rows").inc(len(rows))
        # one immutable row-space snapshot for the whole pass: a refresh
        # resync advancing the store mid-request can't skew the mask.
        # The box is the request's: its mask, and what its nodes handed
        # to the device and read back
        mask_box: Dict[str, Any] = {"snap": self.store.snapshot(ctx.reader),
                                    "dispatches": 0, "sharded": {}}
        out: Dict[str, Any] = {}
        pipelines: List[Tuple[str, str, dict]] = []
        prof_nodes: List[dict] = []
        device_nanos = 0
        assemble_nanos = 0
        host_nanos = 0
        for name, spec in aggs_spec.items():
            if not isinstance(spec, dict):
                raise ParsingError(f"aggregation [{name}] must be an object")
            kinds = [k for k in spec
                     if k not in ("aggs", "aggregations", "meta")]
            if len(kinds) != 1:
                raise ParsingError(
                    f"aggregation [{name}] must define exactly one type")
            kind = kinds[0]
            if kind in A.PIPELINE_AGGS:
                if not partial:
                    pipelines.append((name, kind, spec[kind]))
                continue
            node = plan.nodes.get(name)
            res = None
            engine = "host"
            fam = None
            reason = node.host_reason if node is not None else None
            if node is not None and node.mode != "host":
                fam = _family(node)
                route = "device"
                if self.cost_router is not None:
                    route = self.cost_router.decide(
                        fam, len(rows), mask_box["snap"].r_pad)
                if route == "host":
                    reason = "routed_host_cheaper"
                    self._reason(reason, docs=len(rows))
                    self._count("router_host_routed")
                else:
                    if route == "probe":
                        self._count("router_probes")
                    if "mask" not in mask_box:
                        # the request's rows to a [r_pad] bool: the
                        # first device node pays it, the others share it
                        with telemetry.stage("aggs.mask"):
                            self._mask_for(rows, mask_box)
                    compiles0 = dispatch.DISPATCH.compile_count()
                    launched0 = mask_box["dispatches"]
                    dev = telemetry.stage("aggs.device")
                    asm = None
                    try:
                        with dev:
                            boards, mesh_used = self._run_device_node(
                                ctx, node, spec, rows, mask_box, partial)
                        asm = telemetry.stage("aggs.assemble")
                        with asm:
                            res = self._assemble_node(
                                ctx, node, spec, rows, boards, partial)
                        engine = "device_mesh" if mesh_used else "device"
                        self._count("device_nodes")
                        _counter("aggs.device_nodes").inc()
                        # a call that compiled is not a sample of what
                        # the device costs: booked as one, the compile
                        # seconds sent the family's next REPROBE requests
                        # to the host walker
                        if self.cost_router is not None and \
                                dispatch.DISPATCH.compile_count() \
                                == compiles0:
                            self.cost_router.observe_device(
                                fam, dev.nanos + asm.nanos)
                    except _Fallback as fb:
                        reason = fb.reason
                        self._reason(fb.reason, docs=len(rows),
                                     observed=fb.observed)
                    except SearchEngineError:
                        raise  # parity errors (max_buckets, bad params)
                    except Exception:
                        # an unexpected device error is NOT a reasoned
                        # fallback: count it and let it fail the shard
                        # (node.search reports it in `_shards.failures`)
                        # — the host walker answering instead hid a
                        # broken dispatcher for a whole release
                        self._reason("device_error", docs=len(rows))
                        raise
                    finally:
                        # what the stages recorded, an attempt that fell
                        # back included: `indices.aggs` sums the marks
                        device_nanos += dev.nanos
                        if asm is not None:
                            assemble_nanos += asm.nanos
                        launched = mask_box["dispatches"] - launched0
                        if launched:
                            _counter("aggs.dispatches." + fam).inc(launched)
            if res is None:
                if node is not None and node.mode == "host" \
                        and node.host_reason:
                    self._reason(node.host_reason, docs=len(rows))
                sub = {name: spec}
                with telemetry.stage("aggs.host") as walked:
                    if partial:
                        from elasticsearch_tpu.search.agg_partials import (
                            compute_partial_aggs)
                        res = compute_partial_aggs(ctx, rows, sub).get(name)
                    else:
                        res = A.compute_aggs(ctx, rows, sub).get(name)
                host_nanos += walked.nanos
                self._count("host_nodes")
                _counter("aggs.host_nodes").inc()
                if self.cost_router is not None and fam is not None:
                    self.cost_router.observe_host(fam, walked.nanos,
                                                  len(rows))
            elif not partial and isinstance(spec.get("meta"), dict) \
                    and isinstance(res, dict):
                res["meta"] = spec["meta"]
            out[name] = res
            prof_nodes.append({"name": name, "engine": engine,
                               **({"fallback_reason": reason}
                                  if engine == "host" and reason else {})})
        # top-level pipelines run over the combined outputs, exactly as
        # compute_aggs does (partial mode defers them to the coordinator's
        # finalize, like agg_partials)
        for name, kind, spec in pipelines:
            res = A._compute_pipeline(out, kind, spec, name)
            if not (isinstance(res, dict) and "_applied" in res):
                out[name] = res
        with self._lock:
            self.stats["device_nanos"] += device_nanos
            self.stats["assemble_nanos"] += assemble_nanos
            self.stats["host_nanos"] += host_nanos
        profile = {"nodes": prof_nodes, "device_nanos": device_nanos,
                   "assemble_nanos": assemble_nanos}
        if self.store.columnar_refresh:
            # per-field segment-block-store composition of the last
            # column (re)build — surfaces as profile.aggregations[].
            # columnar so the delta-vs-full extraction story is visible
            # per request
            profile["columnar"] = {
                f: dict(v)
                for f, v in self.store.columnar_refresh.items()}
        return out, profile

    # ----------------------------------------------------------- dispatch
    def _mask_for(self, rows, mask_box) -> np.ndarray:
        """The request's host mask, built once (`aggs.mask_scattered` /
        `aggs.mask_searched`: one of the two a request, by whether its
        snapshot's locator placed the rows or the map was searched)."""
        mask = mask_box.get("mask")
        if mask is None:
            snap = mask_box["snap"]
            mask = mask_box["mask"] = snap.filter_mask(rows)
            located = snap.locator.exact
            _counter("aggs.mask_scattered").inc(int(located))
            _counter("aggs.mask_searched").inc(int(not located))
        return mask

    @staticmethod
    def _mask_io(mask_box, mesh):
        """The request's mask as this route's programs take it. On one
        device the host mask itself, which rides every call (`_launch`
        counts it there). Under a mesh a copy sharded by rows ahead of
        the launch, made ONCE a request and mesh, where
        `aggs.mask_bytes` counts it."""
        mask = mask_box["mask"]
        if mesh is None:
            return mask
        sharded = mask_box["sharded"].get(mesh)
        if sharded is None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            from elasticsearch_tpu.parallel import mesh as mesh_lib
            _counter("aggs.mask_bytes").inc(mask.nbytes)
            sharded = mask_box["sharded"][mesh] = jax.device_put(
                mask, NamedSharding(mesh, P(mesh_lib.SHARD_AXIS)))
        return sharded

    @staticmethod
    def _launch(mask_box, name, *args, mesh=None, **statics):
        """One program of this request handed to the device: bind, what
        rides the call (the host mask among it) and the enqueue, not the
        wait. A mask still on the host is uploaded by the call, every
        call anew: `aggs.mask_bytes` counts it there. With a mesh the
        program is the kernel's `aggs.mesh_*` twin. `aggs.programs.narrow`
        and `.x64` count the programs by their arithmetic."""
        mask_box["dispatches"] += 1
        narrow = name in aggs_ops.N32_KERNELS
        _counter("aggs.programs.narrow").inc(int(narrow))
        _counter("aggs.programs.x64").inc(int(not narrow))
        host_mask = mask_box.get("mask")
        if host_mask is not None and any(a is host_mask for a in args):
            _counter("aggs.mask_bytes").inc(host_mask.nbytes)
        with telemetry.stage("aggs.launch"):
            if mesh is not None:
                return _mesh_call(name.replace("aggs.", "aggs.mesh_"),
                                  *args, mesh=mesh, **statics)
            return dispatch.call(name, *args, **statics)

    @staticmethod
    def _read(board) -> np.ndarray:
        """One board back on the host as numpy: the wait for its
        program and the copy."""
        with telemetry.stage("aggs.sync_wait"):
            out = np.asarray(board)
        _counter("aggs.board_lanes").inc(out.size)
        return out

    def _read_pending(self, pend: "_Pending"):
        return pend.widen([self._read(b) for b in pend.boards])

    def _mesh_for(self, mask_box):
        """Route this node's reduce: mesh or single-device (counted by
        parallel/policy like every other kernel leg)."""
        from elasticsearch_tpu.parallel import policy
        snap = mask_box["snap"]
        mesh = policy.decide("aggs", snap.n_rows,
                             has_mesh_state=self.store.mesh_ready(
                                 snap, policy.serving_mesh()))
        return mesh

    @staticmethod
    def _check_metric_col(kind: str, col) -> None:
        if kind in SUM_KINDS and not col.integral_exact:
            raise _Fallback("non_integral_sum")
        if kind == "value_count" and col.multi_valued:
            # value_count counts every VALUE (all_values) while the f64
            # column keeps only a doc's first — host business
            raise _Fallback("multi_valued_field")

    def _metric_cols(self, ctx, node, snap):
        cols = {}
        for m in node.subs:
            col = self.store.column(ctx.reader, m.field, snap=snap)
            self._check_metric_col(m.kind, col)
            cols[m.name] = (m, col)
        return cols

    @staticmethod
    def _mparams(mspec: dict) -> np.ndarray:
        missing = mspec.get("missing")
        if missing is None:
            return np.zeros(2, dtype=np.float64)
        try:
            return np.asarray([1.0, float(missing)], dtype=np.float64)
        except (TypeError, ValueError):
            raise _Fallback("bad_missing_value")

    # ------------------------------------------------------------ levels --
    def _bind_level(self, ctx, node, body, snap, mesh, single=False):
        """One bucket level of a node's chain: its rung `k`, what the
        x64 program takes for it (`x64`, fetched only if that program
        runs: the column's f64 pair is uploaded there) and, where the
        column's 32-bit form gives the host's own ids, what the 32-bit
        program takes (`n32`: kind, arguments, the lane its absent keys
        fold into). `single`: the node has no level under it, and a
        terms' absent keys stay in the board's last lane."""
        reader = ctx.reader
        if node.kind == "terms":
            return dict(self._ords_level(ctx, node.field, body, snap, mesh,
                                         single), meta=None, body=body)
        col = self.store.column(reader, node.field, snap=snap)
        hparams, meta = self._hist_params(node, body, col)
        k = meta["n_buckets"]
        lvl = {"kind": "hist", "k": k, "col": col, "miss": False,
               "meta": meta, "body": body, "n32": None}
        if k == 0:
            # empty key column and no missing substitute: the whole
            # subtree reduces to zero boards (assembly-only)
            return dict(lvl, kind="empty")
        if meta.get("cal_args") is not None:
            cbounds, cparams = meta["cal_args"]
            lvl.update(kind="cal", x64=lambda: (
                col.device("vals", mesh), col.device("present", mesh),
                cbounds, cparams))
        else:
            lvl["x64"] = lambda: (col.device("vals", mesh),
                                  col.device("present", mesh), hparams)
        table = self._n32_bounds(col, meta)
        if table is not None:
            lvl["n32"] = ("bounds",
                          lambda: (col.device("k32", mesh), table),
                          meta["miss_lane"])
        return lvl

    def _ords_level(self, ctx, field, body, snap, mesh, single=False):
        """A level of a field's global ordinals (a terms, or the extra
        level an exact cardinality counts over). Absent keys: dropped;
        with a `missing` in `body` the level's last lane, sized for it;
        `single` (a terms with no level under it): the board's last lane
        whatever `body` says, where the assembly looks for them."""
        col = self.store.column(ctx.reader, field, want_ords=True,
                                snap=snap)
        if col.multi_valued:
            raise _Fallback("multi_valued_field")
        n_keys = len(col.ord_keys)
        miss = body.get("missing") is not None and not single
        k = aggs_ops.bucket_count(max(n_keys, 1) + (1 if miss else 0))
        if k is None:
            raise _Fallback("cardinality_off_grid", observed=n_keys)
        oparams = np.asarray([-1.0 if single else float(miss)],
                             dtype=np.float64)
        return {"kind": "ord", "k": k, "col": col, "miss": miss,
                "x64": lambda: (col.device("ords", mesh), oparams),
                "n32": ("ords", lambda: (col.device("ords", mesh),),
                        k - 1 if miss else None)}

    @staticmethod
    def _n32_bounds(col, meta) -> Optional[np.ndarray]:
        """The level's int32 table in the column's rebased domain: entry
        0 is -1 (the lane of absent keys), entry 1 + j the least k32
        inside bucket j, ceil((bound_j - k_base) / k_unit) in exact
        integer arithmetic over the host's own bounds (the calendar
        table, or (base + j) * interval + offset): for an integral v,
        v >= bound <=> k32 >= that. None where the 32-bit form cannot
        give the host's ids: a column without `k32`, date_nanos'
        division, an interval or offset that is no integer (the host's
        f64 floor may round at a boundary), magnitudes past 2^52."""
        k = meta["n_buckets"]
        offset = meta["offset"]
        if col.k32 is None or meta["div"] != 1.0 \
                or not float(offset).is_integer() \
                or max(abs(col.vmin - offset),
                       abs(col.vmax - offset)) >= _TWO52:
            return None
        cal = meta.get("cal_bounds")
        if cal is not None:
            real = np.asarray(cal, dtype=np.float64).astype(np.int64)
        else:
            interval, base = meta["interval"], meta["base"]
            if not (float(interval).is_integer()
                    and (abs(base) + k) * interval < _TWO52):
                return None
            real = (int(base) + np.arange(k, dtype=np.int64)) \
                * int(interval)
        lo = -((col.k_base - (real + int(offset))) // col.k_unit)
        table = np.full(k + 1, aggs_ops.I32_MAX, dtype=np.int32)
        table[0] = -1
        table[1:1 + len(lo)] = np.clip(lo, 0, col.k_max + 1)
        return table

    def _zero_level(self, snap, mesh):
        """The one-lane level of a whole-match metric under the x64
        program: every row's ordinal is 0."""
        zeros = self.store.zero_ords(snap.r_pad, mesh)
        oparams = np.zeros(1, dtype=np.float64)
        return {"kind": "ord", "k": aggs_ops.AGG_B_LADDER[0],
                "x64": lambda: (zeros, oparams), "n32": None}

    @staticmethod
    def _chain_n32(chain, cols=1):
        """(levels, n_buckets, form, flat arguments, folds) of a chain
        every level of which has its 32-bit form, else None. `cols`:
        what a row adds to a board (`ops/aggs.board_form`)."""
        if any(lv["n32"] is None for lv in chain):
            return None
        wide = max(chain, key=lambda lv: lv["k"], default=None)
        return (tuple(lv["n32"][0] for lv in chain),
                tuple(lv["k"] for lv in chain),
                aggs_ops.board_form(wide["k"] + 1, wide["n32"][0], cols)
                if chain else "onehot",
                tuple(a for lv in chain for a in lv["n32"][1]()),
                [lv["n32"][2] for lv in chain])

    @staticmethod
    def _chain_x64(chain):
        return (tuple(lv["kind"] for lv in chain),
                tuple(lv["k"] for lv in chain),
                tuple(a for lv in chain for a in lv["x64"]()))

    def _launch_counts(self, mask_box, mask_io, chain, mesh) -> "_Pending":
        """The chain's doc counts: one program, 32-bit where every level
        has that form. Read back as int64 [prod(k) + 1], the last lane
        the rows no bucket took."""
        n32 = self._chain_n32(chain)
        if n32 is not None:
            levels, ks, form, flat, folds = n32
            board = self._launch(mask_box, "aggs.n32_counts", mask_io,
                                 *flat, mesh=mesh, levels=levels,
                                 n_buckets=ks, form=form)
            return _Pending((board,), lambda got: _assembly_lanes(
                got[0].astype(np.int64), ks, folds))
        levels, ks, flat = self._chain_x64(chain)
        board = self._launch(mask_box, "aggs.tree_counts", mask_io, *flat,
                             mesh=mesh, levels=levels, n_buckets=ks)
        return _Pending((board,), lambda got: got[0])

    def _launch_metric(self, mask_box, mask_io, chain, kind, mcol, mbody,
                       mesh) -> "_Pending":
        """One metric field's boards over the chain's lanes, read back as
        (count int64, sum f64, min f64, max f64). 32-bit where the chain
        and the field's column have that form and the `missing`
        substitute lies on the column's lattice; there only the boards
        the metric's kind reads are computed."""
        snap = mask_box["snap"]
        narrow = mcol.k32 is not None \
            and all(lv["n32"] is not None for lv in chain)
        mmiss = -1
        if narrow and mbody.get("missing") is not None:
            mmiss = mcol.to_k32(mbody["missing"])
            narrow = mmiss is not None
        if narrow:
            parts = _N32_PARTS[kind]
            bits = aggs_ops.limb_bits(snap.r_pad) if "sum" in parts else 0
            n_limbs = aggs_ops.n_limbs_for(max(mcol.k_max, mmiss), bits) \
                if bits else 0
            levels, ks, form, flat, folds = self._chain_n32(
                chain, 1 + n_limbs)
            out = self._launch(
                mask_box, "aggs.n32_metric", mask_io,
                mcol.device("k32", mesh), np.int32(mmiss), *flat, mesh=mesh,
                levels=levels, n_buckets=ks, parts=parts, limb_bits=bits,
                n_limbs=n_limbs, form=form)
            return _Pending((out,), lambda got: _widen_metric(
                got[0], parts, bits, n_limbs, mcol, ks, folds))
        levels, ks, flat = self._chain_x64(
            chain or [self._zero_level(snap, mesh)])
        out = self._launch(
            mask_box, "aggs.tree_metric", mask_io, self._mparams(mbody),
            mcol.device("vals", mesh), mcol.device("present", mesh), *flat,
            mesh=mesh, levels=levels, n_buckets=ks)
        return _Pending(tuple(out), tuple)

    def _record_mesh_leg(self, mesh, n_boards, lanes) -> None:
        from elasticsearch_tpu.parallel import mesh as mesh_lib
        from elasticsearch_tpu.parallel import policy
        s = int(mesh.shape[mesh_lib.SHARD_AXIS])
        policy.record_leg("aggs", policy.gather_bytes(s, n_boards, lanes))
        self._count("mesh_dispatches")

    def _run_device_node(self, ctx, node, spec, rows, mask_box,
                         partial=False):
        store = self.store
        reader = ctx.reader
        snap = mask_box["snap"]
        if node.mode == "cardinality" or node.children or node.cards:
            return self._run_tree_node(ctx, node, spec, rows, mask_box,
                                       partial)
        body = spec[node.kind]
        mask = self._mask_for(rows, mask_box)
        mesh = self._mesh_for(mask_box)
        boards: Dict[str, Any] = {"n_matched": int(len(rows))}
        mask_io = self._mask_io(mask_box, mesh)

        if node.mode == "range":
            col = store.column(reader, node.field, snap=snap)
            bounds, frm_to = self._range_bounds(body)
            boards["frm_to"] = frm_to
            rparams = self._mparams(body)
            mcols = self._metric_cols(ctx, node, snap)
            keys_d = col.device("vals", mesh)
            kp_d = col.device("present", mesh)
            tail = (bounds, rparams)
            counts = self._launch(mask_box, "aggs.range_counts", keys_d,
                                  kp_d, mask_io, *tail, mesh=mesh)
            mboards = {}
            for mname, (m, mc) in mcols.items():
                mv_d = mc.device("vals", mesh)
                mp_d = mc.device("present", mesh)
                mp = self._mparams(_sub_body(spec, mname))
                # the mesh twin takes the row-shaped arrays first
                args = (mv_d, mp_d) + tail + (mp,) if mesh is not None \
                    else tail + (mp, mv_d, mp_d)
                mboards[mname] = self._launch(
                    mask_box, "aggs.range_metric", keys_d, kp_d, mask_io,
                    *args, mesh=mesh)
            boards.update(
                counts=self._read(counts),
                metrics={n: tuple(self._read(x) for x in b)
                         for n, b in mboards.items()}, col=col)

        elif node.mode == "metric":
            col = store.column(reader, node.field, snap=snap)
            self._check_metric_col(node.kind, col)
            pend = self._launch_metric(mask_box, mask_io, [], node.kind,
                                       col, body, mesh)
            boards.update(metric=self._read_pending(pend), col=col)

        else:  # terms, histogram, date_histogram: one level
            lvl = self._bind_level(ctx, node, body, snap, mesh, single=True)
            mcols = self._metric_cols(ctx, node, snap)
            if node.mode != "terms":
                boards["hist_meta"] = lvl["meta"]
            if lvl["kind"] == "empty":
                # nothing present and no missing substitute: zero boards
                boards.update(
                    counts=np.zeros(1, dtype=np.int64),
                    metrics={n: (np.zeros(1, np.int64),
                                 np.zeros(1, np.float64),
                                 np.full(1, np.inf), np.full(1, -np.inf))
                             for n in mcols},
                    col=lvl["col"])
                return boards, False
            counts = self._launch_counts(mask_box, mask_io, [lvl], mesh)
            mpend = {mname: self._launch_metric(
                mask_box, mask_io, [lvl], m.kind, mc,
                _sub_body(spec, mname), mesh)
                for mname, (m, mc) in mcols.items()}
            boards.update(counts=self._read_pending(counts),
                          metrics={n: self._read_pending(p)
                                   for n, p in mpend.items()},
                          col=lvl["col"], mask=mask)

        if mesh is not None:
            b_len = len(boards.get("counts",
                                   boards.get("metric", (np.zeros(1),))[0]))
            self._record_mesh_leg(mesh, 1 + 4 * len(node.subs), b_len)
        return boards, mesh is not None

    # ------------------------------------------------- composite trees --
    def _run_tree_node(self, ctx, node, spec, rows, mask_box, partial):
        """Composite-id tree dispatch: each bucket level along a path
        binds an in-kernel id source (ordinals / a table of bounds /
        the x64 histogram floor), and every tree node gets ONE flat
        board per (counts | metric leaf | cardinality leaf) whose lane is
        the composite `parent_id * k_child + child_id` over its level
        chain. Top-level `cardinality` is the zero-level degenerate
        case."""
        store = self.store
        reader = ctx.reader
        snap = mask_box["snap"]
        mask = self._mask_for(rows, mask_box)
        mesh = self._mesh_for(mask_box)
        boards: Dict[str, Any] = {"n_matched": int(len(rows)),
                                  "mask": mask}
        lanes_out = [0]
        mask_io = self._mask_io(mask_box, mesh)

        def read(pend):
            got = self._read_pending(pend)
            lanes_out[0] += sum(int(np.size(x)) for x in (
                got if isinstance(got, tuple) else (got,)))
            return got

        def bind_card(body, chain, empty):
            total = 1
            for lv in chain:
                total *= lv["k"]
            if partial:
                # partial mode mirrors the host's HLL walker (which
                # ignores `missing` — host parity, not an oversight)
                col = store.column(reader, body.get("field"),
                                   want_hll=True, snap=snap)
                if col.multi_valued:
                    raise _Fallback("multi_valued_field")
                if total > aggs_ops.HLL_MAX_LANES:
                    raise _Fallback("hll_off_grid")
                if empty:
                    return {"partial": True, "board": None, "col": col,
                            "body": body}
                levels, ks, flat = self._chain_x64(chain)
                board = self._launch(
                    mask_box, "aggs.hll_board", mask_io,
                    col.device("hll_idx", mesh),
                    col.device("hll_rho", mesh), *flat, mesh=mesh,
                    levels=levels, n_buckets=ks)
                return {"partial": True,
                        "board": read(_Pending((board,),
                                               lambda got: got[0])),
                        "col": col, "body": body}
            # final mode is EXACT (the host counts a distinct set): the
            # field rides one more level of ordinals on the counts board
            lvl = self._ords_level(ctx, body.get("field"), body, snap, mesh)
            if total * lvl["k"] > aggs_ops.TREE_MAX_LANES:
                raise _Fallback("tree_off_grid")
            rec = {"partial": False, "board": None, "k": lvl["k"],
                   "col": lvl["col"], "miss": lvl["miss"], "body": body}
            if not empty:
                rec["board"] = read(self._launch_counts(
                    mask_box, mask_io, chain + [lvl], mesh))
            return rec

        def run_node(node_, spec_node, chain):
            ks = tuple(lv["k"] for lv in chain)
            empty = any(lv["kind"] == "empty" for lv in chain)
            total = 1
            for kk in ks:
                total *= kk
            if not empty and total > aggs_ops.TREE_MAX_LANES:
                raise _Fallback("tree_off_grid")
            tnode: Dict[str, Any] = {"node": node_, "chain": chain,
                                     "ks": ks}
            # every program of this node is launched, then read
            counts = None if empty else self._launch_counts(
                mask_box, mask_io, chain, mesh)
            mpend = {}
            for m in node_.subs:
                mcol = store.column(reader, m.field, snap=snap)
                self._check_metric_col(m.kind, mcol)
                mpend[m.name] = None if empty else self._launch_metric(
                    mask_box, mask_io, chain, m.kind, mcol,
                    _sub_body(spec_node, m.name), mesh)
            tnode["counts"] = None if counts is None else read(counts)
            tnode["metrics"] = {n: None if p is None else read(p)
                                for n, p in mpend.items()}
            tnode["cards"] = {
                c.name: bind_card(_sub_body(spec_node, c.name), chain,
                                  empty)
                for c in node_.cards}
            children = {}
            sub_spec = (spec_node.get("aggs")
                        or spec_node.get("aggregations") or {})
            for ch in node_.children:
                ch_spec = sub_spec[ch.name]
                lvl = self._bind_level(ctx, ch, ch_spec[ch.kind], snap,
                                       mesh)
                children[ch.name] = run_node(ch, ch_spec, chain + [lvl])
            tnode["children"] = children
            return tnode

        if node.mode == "cardinality":
            troot: Dict[str, Any] = {
                "node": node, "chain": [], "ks": (), "counts": None,
                "metrics": {}, "children": {},
                "cards": {node.name: bind_card(spec[node.kind], [],
                                               False)}}
        else:
            lvl0 = self._bind_level(ctx, node, spec[node.kind], snap, mesh)
            troot = run_node(node, spec, [lvl0])
        boards["tree"] = troot
        if mesh is not None and lanes_out[0]:
            self._record_mesh_leg(mesh, 1, lanes_out[0])
        return boards, mesh is not None

    def _calendar_bounds(self, field, col, unit, tz_spec, offset, div):
        """Sorted `_calendar_floor` boundary table spanning the column's
        [vmin, vmax] for one (unit, tz): host wall-clock math runs ONCE
        here (cached per column version), the kernel only searchsorts.
        Walks boundary to boundary by `A._calendar_next`, so DST-shifted
        days and variable months/years land exactly where the host
        walker puts them."""
        key = (field, col.version, unit, str(tz_spec), offset, div)
        cached = self._cal_cache.get(key)
        if cached is not None:
            return cached
        tz = A._resolve_tz(tz_spec)
        lo = math.trunc(col.vmin / div - offset)
        hi = math.trunc(col.vmax / div - offset)
        if (hi - lo) / A._CAL_NOMINAL[unit] + 2 > aggs_ops.AGG_B_LADDER[-1]:
            raise _Fallback("span_off_grid")
        start = A._calendar_floor(int(lo), unit, tz)
        bounds = [start]
        cur = start
        limit = aggs_ops.AGG_B_LADDER[-1] + 2
        while True:
            cur = A._calendar_next(cur, unit, tz)
            if cur > hi:
                break
            bounds.append(cur)
            if len(bounds) > limit:
                raise _Fallback("span_off_grid")
        entry = (tuple(bounds), tz)
        self._cal_cache.put(key, entry)
        return entry

    def _hist_params(self, node, body, col):
        date = node.mode == "date_histogram"
        if date:
            interval, calendar = A._date_interval(body)
            offset = A._date_offset_ms(body.get("offset"))
            mapper = self.mapper_service.get(node.field)
            div = 1e6 if getattr(mapper, "type_name", None) == "date_nanos" \
                else 1.0
            missing = None
            if calendar:
                fmt = body.get("format")
                if col.vmin is None:
                    meta = {"interval": 0.0, "offset": offset, "base": 0.0,
                            "date": True, "n_buckets": 0, "fmt": fmt,
                            "tz": A._resolve_tz(body.get("time_zone")),
                            "cal_bounds": (), "div": div,
                            "miss_lane": None}
                    return None, meta
                if not (math.isfinite(col.vmin)
                        and math.isfinite(col.vmax)):
                    raise _Fallback("non_finite_keys")
                real, tz = self._calendar_bounds(
                    node.field, col, calendar, body.get("time_zone"),
                    offset, div)
                b = aggs_ops.bucket_count(len(real))
                if b is None:
                    raise _Fallback("span_off_grid")
                cbounds = np.full(b, np.inf, dtype=np.float64)
                cbounds[: len(real)] = real
                cparams = np.asarray([div, offset], dtype=np.float64)
                meta = {"interval": 0.0, "offset": offset, "base": 0.0,
                        "date": True, "n_buckets": b, "fmt": fmt,
                        "tz": tz, "cal_bounds": real,
                        "cal_args": (cbounds, cparams), "div": div,
                        "miss_lane": None}
                return None, meta
        else:
            try:
                interval = float(body["interval"])
            except (KeyError, TypeError, ValueError):
                raise _Fallback("bad_interval")
            offset = float(body.get("offset", 0.0))
            div = 1.0
            missing = body.get("missing")
        if not (interval > 0) or not math.isfinite(interval):
            raise _Fallback("bad_interval")
        vmin, vmax = col.vmin, col.vmax
        if div != 1.0:
            vmin = None if vmin is None else vmin / div
            vmax = None if vmax is None else vmax / div
        kflag, kmiss = 0.0, 0.0
        if missing is not None:
            try:
                kmiss = float(missing)
            except (TypeError, ValueError):
                raise _Fallback("bad_missing_value")
            kflag = 1.0
            has_absent = not bool(col.present[: col.n_rows].all())
            if vmin is None:
                vmin = vmax = kmiss
            elif has_absent:
                vmin, vmax = min(vmin, kmiss), max(vmax, kmiss)
        if vmin is None or not (math.isfinite(vmin) and math.isfinite(vmax)):
            base = 0.0
            n_buckets = 0 if vmin is None else None
            if n_buckets is None:
                raise _Fallback("non_finite_keys")
        else:
            base = math.floor((vmin - offset) / interval)
            top = math.floor((vmax - offset) / interval)
            span = int(top - base) + 1
            bb = aggs_ops.bucket_count(span)
            if bb is None:
                raise _Fallback("span_off_grid")
            n_buckets = bb
        hparams = np.asarray([interval, offset, base, div, kflag, kmiss],
                             dtype=np.float64)
        # the key's `missing` substitute as ONE precomputed lane, by the
        # host's own f64 key math
        miss_lane = int(math.floor((kmiss - offset) / interval) - base) \
            if kflag and n_buckets else None
        meta = {"interval": interval, "offset": offset, "base": base,
                "date": date, "n_buckets": n_buckets, "div": div,
                "miss_lane": miss_lane,
                "fmt": body.get("format"),
                "tz": A._resolve_tz(body.get("time_zone")) if date
                else None}
        return hparams, meta

    @staticmethod
    def _range_bounds(body):
        ranges = body.get("ranges", [])
        b = aggs_ops.bucket_count(len(ranges))
        if b is None:
            raise _Fallback("ranges_off_grid")
        bounds = np.full((b, 2), np.inf, dtype=np.float64)
        frm_to = []
        for i, r in enumerate(ranges):
            try:
                frm = float(r["from"]) if r.get("from") is not None else None
                to = float(r["to"]) if r.get("to") is not None else None
            except (TypeError, ValueError):
                raise _Fallback("bad_range_bound")
            bounds[i, 0] = -np.inf if frm is None else frm
            bounds[i, 1] = np.inf if to is None else to
            frm_to.append((frm, to))
        return bounds, frm_to

    # ----------------------------------------------------------- assembly
    def _assemble_node(self, ctx, node, spec, rows, boards, partial):
        if "tree" in boards:
            if node.mode == "cardinality":
                rec = boards["tree"]["cards"][node.name]
                return self._card_out(ctx, rec, [0], partial, node.name)
            return self._assemble_tree(ctx, boards["tree"], spec, [0],
                                       partial, boards)
        body = spec[node.kind]
        sub_bodies = {m.name: _sub_body(spec, m.name) for m in node.subs}
        sub_kinds = {m.name: m.kind for m in node.subs}
        if node.mode == "metric":
            cnt, s, mn, mx = boards["metric"]
            return self._metric_out(node.kind, body, int(cnt[0]),
                                    float(s[0]), float(mn[0]),
                                    float(mx[0]), node.field, partial)
        if node.mode == "terms":
            return self._assemble_terms(ctx, node, body, boards,
                                        sub_kinds, sub_bodies, partial)
        if node.mode in ("histogram", "date_histogram"):
            return self._assemble_histo(ctx, node, body, boards,
                                        sub_kinds, sub_bodies, partial)
        if node.mode == "range":
            return self._assemble_range(ctx, node, body, boards,
                                        sub_kinds, sub_bodies, partial)
        raise _Fallback("unsupported_agg")

    def _metric_out(self, kind, mspec, cnt, s, mn, mx, field, partial):
        if partial:
            if kind == "value_count":
                return {"$p": "value_count", "n": int(cnt)}
            if kind == "avg":
                return {"$p": "avg", "sum": float(s), "n": int(cnt)}
            if kind == "sum":
                return {"$p": "sum", "sum": float(s)}
            if kind == "min":
                return {"$p": "min", "v": float(mn) if cnt else None}
            if kind == "max":
                return {"$p": "max", "v": float(mx) if cnt else None}
            if kind == "stats":
                return {"$p": "stats", "n": int(cnt), "sum": float(s),
                        "min": float(mn) if cnt else None,
                        "max": float(mx) if cnt else None}
            raise _Fallback("unsupported_metric")
        if kind == "value_count":
            return {"value": int(cnt)}
        if kind == "avg":
            out = {"value": s / cnt if cnt else None}
            tname = getattr(self.mapper_service.get(field), "type_name",
                            None) if field else None
            if out["value"] is not None and tname in ("date", "date_nanos"):
                ms = out["value"] / 1e6 if tname == "date_nanos" \
                    else out["value"]
                out["value_as_string"] = A._millis_to_iso(int(round(ms)))
            return out
        if kind == "sum":
            return {"value": float(s)}
        if kind == "min":
            return {"value": float(mn) if cnt else None}
        if kind == "max":
            return {"value": float(mx) if cnt else None}
        if kind == "stats":
            if cnt == 0:
                return {"count": 0, "min": None, "max": None, "avg": None,
                        "sum": 0.0}
            return {"count": int(cnt), "min": float(mn), "max": float(mx),
                    "avg": s / cnt, "sum": float(s)}
        raise _Fallback("unsupported_metric")

    def _sub_outputs(self, b, lane, metrics, sub_kinds, sub_bodies,
                     partial, merge_lane=None):
        for mname, (cnt, s, mn, mx) in metrics.items():
            c, ss, m1, m2 = (int(cnt[lane]), float(s[lane]),
                             float(mn[lane]), float(mx[lane]))
            if merge_lane is not None:
                c += int(cnt[merge_lane])
                ss += float(s[merge_lane])
                m1 = min(m1, float(mn[merge_lane]))
                m2 = max(m2, float(mx[merge_lane]))
            mbody = sub_bodies[mname]
            field = mbody.get("field")
            b[mname] = self._metric_out(sub_kinds[mname], mbody, c, ss,
                                        m1, m2, field, partial)

    def _empty_sub_outputs(self, b, metrics, sub_kinds, sub_bodies,
                           partial):
        # a zero-count (gap-filled) bucket has no rows, so its metrics are
        # the empty-set outputs regardless of any `missing` substitute
        for mname in metrics:
            mbody = sub_bodies[mname]
            b[mname] = self._metric_out(sub_kinds[mname], mbody, 0, 0.0,
                                        float("inf"), float("-inf"),
                                        mbody.get("field"), partial)

    # ------------------------------------------------------------- terms
    def _assemble_terms(self, ctx, node, body, boards, sub_kinds,
                        sub_bodies, partial):
        from elasticsearch_tpu.index.mapping import parse_date_millis
        col = boards["col"]
        counts = boards["counts"]
        metrics = boards["metrics"]
        trash = len(counts) - 1
        field = node.field
        mapper = self.mapper_service.get(field) if field else None
        tname = getattr(mapper, "type_name", None) or body.get("value_type")

        size = int(body.get("size", 10))
        if partial:
            size = int(body.get("shard_size") or (size * 3 // 2 + 10))

        def fmt_key(k):
            if tname == "ip":
                from elasticsearch_tpu.index.mapping import IpFieldMapper
                try:
                    return IpFieldMapper.format_value(int(k))
                except (ValueError, TypeError):
                    return k
            return k

        key_index = {A._hashable(k): i for i, k in enumerate(col.ord_keys)}
        items: List[Tuple[Any, int, Any]] = []  # (key, count, lane)
        for i, k in enumerate(col.ord_keys):
            items.append([A._hashable(k), int(counts[i]), i, None])

        missing_val = body.get("missing")
        if missing_val is not None:
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
                        f"failed to parse [missing] value [{mv}] as a "
                        f"double")
            miss_cnt = int(counts[trash])
            ki = key_index.get(A._hashable(mv))
            if ki is not None:
                items[ki][1] += miss_cnt
                items[ki][3] = trash
            elif miss_cnt > 0:
                items.append([A._hashable(mv), miss_cnt, trash, None])

        mdc = int(body.get("min_doc_count", 1))
        if mdc != 0:
            items = [it for it in items if it[1] > 0]

        if mapper is not None:
            _tn = getattr(mapper, "type_name", None)
            if (_tn == "keyword" or (_tn == "text"
                                     and (mapper.params or {})
                                     .get("fielddata"))):
                self.mapper_service.mark_fielddata_loaded(field)

        order_spec = body.get("order")
        if not partial and order_spec and isinstance(order_spec, dict):
            ((okey, odir),) = order_spec.items()
            reverse = odir == "desc"
            if okey == "_key":
                items.sort(key=lambda it: A._sort_key(it[0]),
                           reverse=reverse)
            else:  # "_count" (order-by-metric never compiles to device)
                # host ties break by groups-dict insertion order = first
                # occurrence among the MATCHED rows; reproduce it from the
                # mask, then stable-sort by count so ties keep that order
                # under both directions (python's reverse=True keeps the
                # pre-sort order for equal keys, like the host's)
                mask = boards["mask"]
                marr = col.ords[: col.n_rows][mask[: col.n_rows]]
                marr = marr[marr >= 0]
                uniq, first = np.unique(marr, return_index=True)
                pos = {int(o): int(f) for o, f in zip(uniq, first)}
                items.sort(key=lambda it: pos.get(it[2], float("inf")))
                items.sort(key=lambda it: (it[1],), reverse=reverse)
        else:
            items.sort(key=lambda it: (-it[1], A._sort_key(it[0])))

        total_other = sum(it[1] for it in items[size:])
        A._check_max_buckets(ctx, min(len(items), size))
        buckets = []
        for key, c, lane, merge_lane in items[:size]:
            b = {"key": key, "doc_count": int(c)}
            if metrics:
                self._sub_outputs(b, lane, metrics, sub_kinds, sub_bodies,
                                  partial, merge_lane=merge_lane)
            buckets.append(b)
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
                    b["key_as_string"] = A._millis_to_iso(int(b["key"]))
        return {"doc_count_error_upper_bound": 0,
                "sum_other_doc_count": int(total_other),
                "buckets": buckets}

    # ---------------------------------------------------------- histogram
    def _assemble_histo(self, ctx, node, body, boards, sub_kinds,
                        sub_bodies, partial):
        meta = boards["hist_meta"]
        counts = boards["counts"]
        metrics = boards["metrics"]
        interval = meta["interval"]
        offset = meta["offset"]
        base = meta["base"]
        date = meta["date"]
        fmt = meta["fmt"]
        tz = meta["tz"]
        n_b = meta["n_buckets"]
        min_count = -1 if partial else int(body.get("min_doc_count", 0))
        extended_bounds = body.get("extended_bounds")

        cal_bounds = meta.get("cal_bounds")
        groups: Dict[float, int] = {}  # float key -> board lane
        if cal_bounds is not None:
            # calendar lanes map to the precomputed boundary table, not
            # to a fixed-width arithmetic progression
            for i in range(min(n_b, len(cal_bounds))):
                if int(counts[i]) > 0:
                    groups[float(cal_bounds[i] + offset)] = i
        else:
            for i in range(n_b):
                if int(counts[i]) > 0:
                    key = float((base + i) * interval + offset)
                    groups[key] = i
        all_keys = sorted(groups)

        def _guard_span(lo_key, hi_key):
            if interval and (hi_key - lo_key) / interval > A.MAX_BUCKETS:
                raise IllegalArgumentError(
                    f"Trying to create too many buckets. Must be less "
                    f"than or equal to: [{A.MAX_BUCKETS}].")

        if extended_bounds and interval:
            lo = float(extended_bounds.get("min", np.inf))
            hi = float(extended_bounds.get("max", -np.inf))
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
        elif min_count == 0 and all_keys and cal_bounds is not None:
            all_keys = _cal_keys_between(cal_bounds, offset, all_keys)
        A._check_max_buckets(ctx, len(all_keys))
        buckets = []
        for key in all_keys:
            lane = groups.get(key)
            c = int(counts[lane]) if lane is not None else 0
            if c < min_count and min_count > 0:
                continue
            b = {"key": int(key) if date else key, "doc_count": c}
            if date:
                b["key_as_string"] = A._format_date_key(int(key), fmt, tz) \
                    if fmt else A._millis_to_iso_tz(int(key), tz)
            if metrics:
                if lane is not None:
                    self._sub_outputs(b, lane, metrics, sub_kinds,
                                      sub_bodies, partial)
                else:
                    self._empty_sub_outputs(b, metrics, sub_kinds,
                                            sub_bodies, partial)
            buckets.append(b)
        out = {"buckets": buckets}
        if not date:
            f = body.get("format")
            if f:
                for b in out["buckets"]:
                    b["key_as_string"] = A._decimal_format(b["key"], f)
        return out

    # -------------------------------------------------------------- range
    def _assemble_range(self, ctx, node, body, boards, sub_kinds,
                        sub_bodies, partial):
        counts = boards["counts"]
        metrics = boards["metrics"]
        frm_to = boards["frm_to"]
        ranges = body.get("ranges", [])
        buckets = []
        for i, r in enumerate(ranges):
            frm, to = frm_to[i]
            key = r.get("key")
            if key is None:
                lo_s = "*" if frm is None else float(frm)
                hi_s = "*" if to is None else float(to)
                key = f"{lo_s}-{hi_s}"
            b = {"key": key, "doc_count": int(counts[i])}
            if frm is not None:
                b["from"] = float(frm)
            if to is not None:
                b["to"] = float(to)
            if metrics:
                self._sub_outputs(b, i, metrics, sub_kinds, sub_bodies,
                                  partial)
            b["_sort"] = (frm if frm is not None else -np.inf,
                          to if to is not None else np.inf)
            buckets.append(b)
        buckets.sort(key=lambda b: b.pop("_sort"))
        return {"buckets": buckets}

    # ------------------------------------------------- tree assembly ----
    def _tree_eff_counts(self, tnode, P) -> np.ndarray:
        """Per-lane doc counts of this node's level given the parent
        composite selection P (ids over the chain MINUS the last level).
        The flat board reshapes to (parents, k) and the selected parent
        rows sum — exact int64 adds, order-free."""
        ks = tnode["ks"]
        k = ks[-1]
        counts = tnode["counts"]
        if counts is None or not P or k == 0:
            return np.zeros(max(k, 0), dtype=np.int64)
        total = 1
        for kk in ks:
            total *= kk
        return counts[:total].reshape(total // k, k)[
            np.asarray(P)].sum(axis=0)

    def _tree_sub_outputs(self, b, P_i, tnode, spec_node, partial):
        for mname, board4 in tnode["metrics"].items():
            mbody = _sub_body(spec_node, mname)
            kind = next(k for k in (spec_node.get("aggs")
                                    or spec_node.get("aggregations")
                                    or {})[mname]
                        if k not in ("aggs", "aggregations", "meta"))
            if board4 is None or not P_i:
                c, ss, m1, m2 = 0, 0.0, float("inf"), float("-inf")
            else:
                cnt, s, mn, mx = board4
                idx = np.asarray(P_i)
                c = int(cnt[idx].sum())
                ss = float(s[idx].sum())
                m1 = float(mn[idx].min())
                m2 = float(mx[idx].max())
            b[mname] = self._metric_out(kind, mbody, c, ss, m1, m2,
                                        mbody.get("field"), partial)

    def _card_out(self, ctx, rec, P, partial, name):
        from elasticsearch_tpu.search import agg_partials as AP
        body = rec["body"]
        if partial:
            board = rec["board"]
            if board is None or not P:
                regs: Dict[int, int] = {}
            else:
                v = board[np.asarray(P)].max(axis=0)
                nz = np.nonzero(v)[0]
                regs = {int(i): int(v[i]) for i in nz}
            return AP._hll_pack(regs)
        pt = body.get("precision_threshold")
        if pt is not None and int(pt) < 0:
            raise IllegalArgumentError(
                f"[precisionThreshold] must be greater than or equal to "
                f"0. Found [{int(pt)}] in [{name}]")
        board = rec["board"]
        k_card = rec["k"]
        col = rec["col"]
        n_keys = len(col.ord_keys)
        if board is None or not P:
            sub = np.zeros(k_card, dtype=np.int64)
        else:
            total = (len(board) - 1) // k_card
            sub = board[: total * k_card].reshape(total, k_card)[
                np.asarray(P)].sum(axis=0)
        distinct = int(np.count_nonzero(sub[:n_keys]))
        if rec["miss"] and int(sub[k_card - 1]) > 0:
            # the host adds _hashable(missing) to the distinct SET — it
            # only grows the count when no counted key already equals it
            mi = None
            mv = A._hashable(body.get("missing"))
            for i, kk in enumerate(col.ord_keys):
                if A._hashable(kk) == mv:
                    mi = i
                    break
            if mi is None or int(sub[mi]) == 0:
                distinct += 1
        return {"value": distinct}

    def _assemble_tree(self, ctx, tnode, spec_node, P, partial, boards):
        """Assemble one tree node's bucket list for the parent composite
        selection P, recursing into children with each bucket's own
        composite list — the flat boards decompose into exactly the
        nested JSON the host's `_bucketize` recursion emits."""
        node_ = tnode["node"]
        lvl = tnode["chain"][-1]
        k = lvl["k"]
        body = spec_node[node_.kind]
        eff = self._tree_eff_counts(tnode, P)

        def bucket_fill(b, P_i):
            self._tree_sub_outputs(b, P_i, tnode, spec_node, partial)
            for cname, rec in tnode["cards"].items():
                b[cname] = self._card_out(ctx, rec, P_i, partial, cname)
            sub_spec = (spec_node.get("aggs")
                        or spec_node.get("aggregations") or {})
            for chname, ch in tnode["children"].items():
                res = self._assemble_tree(ctx, ch, sub_spec[chname],
                                          P_i, partial, boards)
                if not partial \
                        and isinstance(sub_spec[chname].get("meta"),
                                       dict) and isinstance(res, dict):
                    res["meta"] = sub_spec[chname]["meta"]
                b[chname] = res

        if lvl["kind"] == "ord":
            return self._tree_terms(ctx, node_, body, lvl, eff, P, k,
                                    partial, bucket_fill, tnode, boards)
        return self._tree_histo(ctx, node_, body, lvl, eff, P, k,
                                partial, bucket_fill)

    def _tree_terms(self, ctx, node, body, lvl, eff, P, k, partial,
                    bucket_fill, tnode, boards):
        from elasticsearch_tpu.index.mapping import parse_date_millis
        col = lvl["col"]
        field = node.field
        mapper = self.mapper_service.get(field) if field else None
        tname = getattr(mapper, "type_name", None) or body.get(
            "value_type")
        size = int(body.get("size", 10))
        if partial:
            size = int(body.get("shard_size") or (size * 3 // 2 + 10))

        key_index = {A._hashable(kk): i
                     for i, kk in enumerate(col.ord_keys)}
        items: List[list] = []
        for i, kk in enumerate(col.ord_keys):
            items.append([A._hashable(kk), int(eff[i]), i, None])

        missing_val = body.get("missing")
        if missing_val is not None:
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
                        f"failed to parse [missing] value [{mv}] as a "
                        f"long")
            elif tname in ("double", "float", "half_float"):
                try:
                    mv = float(mv)
                except (TypeError, ValueError):
                    raise ParsingError(
                        f"failed to parse [missing] value [{mv}] as a "
                        f"double")
            miss_cnt = int(eff[k - 1])
            ki = key_index.get(A._hashable(mv))
            if ki is not None:
                items[ki][1] += miss_cnt
                items[ki][3] = k - 1
            elif miss_cnt > 0:
                items.append([A._hashable(mv), miss_cnt, k - 1, None])

        mdc = int(body.get("min_doc_count", 1))
        if mdc != 0:
            items = [it for it in items if it[1] > 0]

        if mapper is not None:
            _tn = getattr(mapper, "type_name", None)
            if (_tn == "keyword" or (_tn == "text"
                                     and (mapper.params or {})
                                     .get("fielddata"))):
                self.mapper_service.mark_fielddata_loaded(field)

        order_spec = body.get("order")
        if not partial and order_spec and isinstance(order_spec, dict):
            ((okey, odir),) = order_spec.items()
            reverse = odir == "desc"
            if okey == "_key":
                items.sort(key=lambda it: A._sort_key(it[0]),
                           reverse=reverse)
            else:
                # "_count" compiles to the tree only at depth 1 (the
                # classifier rejects it deeper): the host tie-break is
                # first occurrence among matched rows, recovered from
                # the mask exactly like the single-level path
                mask = boards["mask"]
                marr = col.ords[: col.n_rows][mask[: col.n_rows]]
                marr = marr[marr >= 0]
                uniq, first = np.unique(marr, return_index=True)
                pos = {int(o): int(f) for o, f in zip(uniq, first)}
                items.sort(key=lambda it: pos.get(it[2], float("inf")))
                items.sort(key=lambda it: (it[1],), reverse=reverse)
        else:
            items.sort(key=lambda it: (-it[1], A._sort_key(it[0])))

        total_other = sum(it[1] for it in items[size:])
        A._check_max_buckets(ctx, min(len(items), size))
        buckets = []
        for key, c, lane, merge_lane in items[:size]:
            b = {"key": key, "doc_count": int(c)}
            P_i = [p * k + lane for p in P]
            if merge_lane is not None:
                P_i += [p * k + merge_lane for p in P]
            bucket_fill(b, P_i)
            buckets.append(b)
        if tname == "ip":
            from elasticsearch_tpu.index.mapping import IpFieldMapper
            for b in buckets:
                try:
                    b["key"] = IpFieldMapper.format_value(int(b["key"]))
                except (ValueError, TypeError):
                    pass
        elif tname == "boolean":
            for b in buckets:
                truthy = bool(b["key"])
                b["key"] = 1 if truthy else 0
                b["key_as_string"] = "true" if truthy else "false"
        elif tname == "date":
            for b in buckets:
                if isinstance(b["key"], (int, float)):
                    b["key_as_string"] = A._millis_to_iso(int(b["key"]))
        return {"doc_count_error_upper_bound": 0,
                "sum_other_doc_count": int(total_other),
                "buckets": buckets}

    def _tree_histo(self, ctx, node, body, lvl, eff, P, k, partial,
                    bucket_fill):
        meta = lvl["meta"]
        interval = meta["interval"]
        offset = meta["offset"]
        base = meta["base"]
        date = meta["date"]
        fmt = meta["fmt"]
        tz = meta["tz"]
        cal_bounds = meta.get("cal_bounds")
        min_count = -1 if partial else int(body.get("min_doc_count", 0))
        extended_bounds = body.get("extended_bounds")

        groups: Dict[float, int] = {}
        if cal_bounds is not None:
            for i in range(len(cal_bounds)):
                if i < len(eff) and int(eff[i]) > 0:
                    groups[float(cal_bounds[i] + offset)] = i
        else:
            for i in range(k):
                if int(eff[i]) > 0:
                    groups[float((base + i) * interval + offset)] = i
        all_keys = sorted(groups)

        def _guard_span(lo_key, hi_key):
            if interval and (hi_key - lo_key) / interval > A.MAX_BUCKETS:
                raise IllegalArgumentError(
                    f"Trying to create too many buckets. Must be less "
                    f"than or equal to: [{A.MAX_BUCKETS}].")

        if extended_bounds and interval:
            lo = float(extended_bounds.get("min", np.inf))
            hi = float(extended_bounds.get("max", -np.inf))
            kk = min([lo] + all_keys) if all_keys or lo != np.inf else lo
            top = max([hi] + all_keys) if all_keys or hi != -np.inf \
                else hi
            _guard_span(kk, top)
            cur = kk
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
        elif min_count == 0 and all_keys and cal_bounds is not None:
            all_keys = _cal_keys_between(cal_bounds, offset, all_keys)
        A._check_max_buckets(ctx, len(all_keys))
        buckets = []
        for key in all_keys:
            lane = groups.get(key)
            c = int(eff[lane]) if lane is not None else 0
            if c < min_count and min_count > 0:
                continue
            b = {"key": int(key) if date else key, "doc_count": c}
            if date:
                b["key_as_string"] = A._format_date_key(int(key), fmt,
                                                        tz) \
                    if fmt else A._millis_to_iso_tz(int(key), tz)
            P_i = [p * k + lane for p in P] if lane is not None else []
            bucket_fill(b, P_i)
            buckets.append(b)
        out = {"buckets": buckets}
        if not date:
            f = body.get("format")
            if f:
                for b in out["buckets"]:
                    b["key_as_string"] = A._decimal_format(b["key"], f)
        return out


def _cal_keys_between(cal_bounds, offset, keys: List[float]) -> List[float]:
    """Every calendar bucket key from the first to the last of `keys`
    (the buckets that hold documents): the boundary table spans the
    column, so the empty buckets between them are read off it
    (`min_doc_count` 0, as `A._histo_buckets` fills them)."""
    return [float(b + offset) for b in cal_bounds
            if keys[0] <= b + offset <= keys[-1]]


def _sub_body(spec: dict, sub_name: str) -> dict:
    sub = spec.get("aggs") or spec.get("aggregations") or {}
    sspec = sub.get(sub_name) or {}
    for k, v in sspec.items():
        if k not in ("aggs", "aggregations", "meta"):
            return v if isinstance(v, dict) else {}
    return {}


