"""REST handlers: the API surface table.

Covers the core of the reference's 124 handlers (`action/ActionModule.java`
initRestHandlers + `rest-api-spec/api/*.json` contract): document CRUD,
_bulk/_mget/_update, _search/_count/_msearch, index admin (create/delete/
mapping/settings/refresh/flush/forcemerge/aliases/stats/exists), _analyze,
cluster health/state/stats, _cat APIs, and the root banner.
"""

from __future__ import annotations

import json
import time
from typing import Any, Dict, Optional, Tuple

from elasticsearch_tpu import telemetry
from elasticsearch_tpu.common.errors import (
    DocumentMissingError, IllegalArgumentError, IndexNotFoundError,
    SearchEngineError,
)
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.rest.controller import RestController, RestRequest
from elasticsearch_tpu.version import __version__


def _rest_telemetry(req, node, action: str, force_trace: bool = False,
                    description: str = "", parsed=None):
    """Per-request telemetry binding for an instrumented handler: live
    task (tasks API + cancellation token), trace when sampled or forced,
    X-Opaque-ID captured once from the header and threaded through
    both."""
    return telemetry.rest_request(
        node, action,
        opaque_id=(req.headers or {}).get("x-opaque-id"),
        force_trace=force_trace, description=description, parsed=parsed)


def _cat_table(req, headers, rows) -> Tuple[int, Any]:
    """Legacy shim over rest/cat.py's RestTable renderer."""
    from elasticsearch_tpu.rest.cat import Col, render
    return render(req, [Col(h) for h in headers], rows)


def apply_uri_query(req, body):
    """URI q= parameter -> query_string clause (RestSearchAction
    parseSearchRequest; shared by search/count/explain)."""
    q = req.param("q")
    if not q:
        return body
    if "query" in body:
        raise IllegalArgumentError(
            "cannot specify both [q] parameter and a request body query")
    qs = {"query": q}
    if req.param("df"):
        qs["default_field"] = req.param("df")
    if req.param("default_operator"):
        qs["default_operator"] = req.param("default_operator")
    if req.param("lenient") is not None:
        qs["lenient"] = req.bool_param("lenient", False)
    if req.param("analyzer"):
        qs["analyzer"] = req.param("analyzer")
    if req.param("analyze_wildcard") is not None:
        qs["analyze_wildcard"] = req.bool_param("analyze_wildcard", False)
    body["query"] = {"query_string": qs}
    return body


def register_all(rc: RestController, node: Node) -> None:
    from elasticsearch_tpu.rest.actions_extra import register_extra
    register_extra(rc, node)
    from elasticsearch_tpu.rest.actions_script import register_script
    register_script(rc, node)
    from elasticsearch_tpu.rest.actions_xpack import register_xpack
    register_xpack(rc, node)
    from elasticsearch_tpu.rest.actions_admin import register_admin
    register_admin(rc, node)
    from elasticsearch_tpu.rest.actions_conf import register_conf
    register_conf(rc, node)
    from elasticsearch_tpu.security.rest_filter import (
        make_security_filter, register_security,
    )
    register_security(rc, node)
    rc.add_filter(make_security_filter(node.security))
    # plugin-contributed REST handlers (reference:
    # ActionPlugin.getRestHandlers); on_node_start fires in Node.__init__
    node.plugins.register_rest(rc, node)
    # ------------------------------------------------------------------ root
    def root(req):
        return 200, {
            "name": node.node_name, "cluster_name": node.cluster_name,
            "cluster_uuid": node.node_id,
            "version": {"number": __version__,
                        "build_flavor": "tpu", "lucene_version": "none"},
            "tagline": "You Know, for (TPU) Search",
        }

    rc.register("GET", "/", root)

    # ------------------------------------------------------------- documents
    def put_doc(req):
        with _rest_telemetry(req, node, "indices:data/write/index",
                             force_trace=req.bool_param("trace"),
                             description=f"[{req.params['index']}]"):
            resp = node.index_doc(
                req.params["index"], req.params.get("id"), req.json() or {},
                op_type=req.param("op_type", "index"),
                refresh=req.param("refresh"),
                routing=req.param("routing"),
                if_seq_no=req.int_param("if_seq_no"),
                if_primary_term=req.int_param("if_primary_term"),
                version=req.int_param("version"),
                version_type=req.param("version_type", "internal"),
                pipeline=req.param("pipeline"))
            return (201 if resp["result"] == "created" else 200), resp

    def post_doc_auto_id(req):
        with _rest_telemetry(req, node, "indices:data/write/index",
                             force_trace=req.bool_param("trace"),
                             description=f"[{req.params['index']}]"):
            resp = node.index_doc(req.params["index"], None,
                                  req.json() or {},
                                  refresh=req.param("refresh"),
                                  routing=req.param("routing"))
            return 201, resp

    def create_doc(req):
        if req.param("version_type") in ("external", "external_gte"):
            from elasticsearch_tpu.common.errors import (
                ActionRequestValidationError)
            raise ActionRequestValidationError(
                "Validation Failed: 1: create operations only support "
                "internal versioning. use index instead;")
        with _rest_telemetry(req, node, "indices:data/write/index",
                             force_trace=req.bool_param("trace"),
                             description=f"[{req.params['index']}]"):
            resp = node.index_doc(req.params["index"], req.params["id"],
                                  req.json() or {}, op_type="create",
                                  refresh=req.param("refresh"),
                                  routing=req.param("routing"))
            return 201, resp

    def _get_source_filter(req):
        src = req.param("_source")
        inc, exc = req.param("_source_includes"), req.param("_source_excludes")
        source_filter = None
        if isinstance(src, str) and src.lower() == "false" or src is False:
            source_filter = False
        elif isinstance(src, str) and src.lower() == "true" or src is True:
            source_filter = True
        elif src:
            source_filter = src.split(",") if isinstance(src, str) else src
        if inc or exc:
            source_filter = {"includes": inc.split(",") if inc else [],
                             "excludes": exc.split(",") if exc else []}
        return source_filter

    def get_doc(req):
        from elasticsearch_tpu.common.errors import VersionConflictError
        if req.bool_param("refresh", False):
            # overridable: clustered nodes broadcast, local ones refresh
            # the service directly
            node._refresh_indices([req.params["index"]])
        resp = node.get_doc(req.params["index"], req.params["id"],
                            routing=req.param("routing"),
                            realtime=req.bool_param("realtime", True))
        v = req.int_param("version")
        if v is not None and resp.get("found") \
                and resp.get("_version") != v:
            raise VersionConflictError(
                f"[{req.params['id']}]: version conflict, current version "
                f"[{resp.get('_version')}] is different than the one "
                f"provided [{v}]")
        sf = req.param("stored_fields")
        node._apply_mget_projection(
            resp, {}, sf.split(",") if sf else None,
            req.params["index"], _get_source_filter(req))
        return (200 if resp.get("found") else 404), resp

    def get_source(req):
        if req.bool_param("refresh", False):
            node._refresh_indices([req.params["index"]])
        resp = node.get_doc(req.params["index"], req.params["id"],
                            routing=req.param("routing"),
                            realtime=req.bool_param("realtime", True))
        if not resp.get("found") or "_source" not in resp:
            # missing doc OR _source disabled in the mapping: both 404
            # (RestGetSourceAction)
            return 404, {"error": f"source [{req.params['id']}] not found"}
        node._apply_mget_projection(resp, {}, None, req.params["index"],
                                    _get_source_filter(req))
        return 200, resp.get("_source")

    def delete_doc(req):
        with _rest_telemetry(req, node, "indices:data/write/delete",
                             force_trace=req.bool_param("trace"),
                             description=f"[{req.params['index']}]"):
            try:
                resp = node.delete_doc(
                    req.params["index"], req.params["id"],
                    refresh=req.param("refresh"),
                    routing=req.param("routing"),
                    if_seq_no=req.int_param("if_seq_no"),
                    if_primary_term=req.int_param("if_primary_term"),
                    version=req.int_param("version"),
                    version_type=req.param("version_type", "internal"))
                return 200, resp
            except DocumentMissingError:
                return 404, {"_index": req.params["index"],
                             "_id": req.params["id"],
                             "result": "not_found"}

    def update_doc(req):
        with _rest_telemetry(req, node, "indices:data/write/update",
                             force_trace=req.bool_param("trace"),
                             description=f"[{req.params['index']}]"):
            return 200, node.update_doc(
                req.params["index"], req.params["id"], req.json() or {},
                refresh=req.param("refresh"),
                routing=req.param("routing"),
                if_seq_no=req.int_param("if_seq_no"),
                if_primary_term=req.int_param("if_primary_term"),
                source_filter=_get_source_filter(req))

    rc.register("PUT", "/{index}/_doc/{id}", put_doc)
    rc.register("POST", "/{index}/_doc/{id}", put_doc)
    rc.register("POST", "/{index}/_doc", post_doc_auto_id)
    rc.register("PUT", "/{index}/_create/{id}", create_doc)
    rc.register("POST", "/{index}/_create/{id}", create_doc)
    # no direct HEAD registration: RestController's HEAD fallback reuses GET
    # and strips the body (a HEAD body would desync keep-alive connections)
    rc.register("GET", "/{index}/_doc/{id}", get_doc)
    rc.register("GET", "/{index}/_source/{id}", get_source)
    rc.register("DELETE", "/{index}/_doc/{id}", delete_doc)
    rc.register("POST", "/{index}/_update/{id}", update_doc)

    def _total_hits_as_int(resp):
        """?rest_total_hits_as_int=true renders hits.total as the pre-7.0
        plain number (RestSearchAction.TOTAL_HITS_AS_INT_PARAM); with hit
        counting disabled the legacy rendering is -1."""
        hits = resp.get("hits") if isinstance(resp, dict) else None
        if hits is None:
            return
        total = hits.get("total")
        if isinstance(total, dict):
            hits["total"] = total.get("value")
        elif total is None:
            hits["total"] = -1
        for h in hits.get("hits", []):
            for ih in (h.get("inner_hits") or {}).values():
                _total_hits_as_int(ih)

    def _apply_typed_keys(resp, body):
        """?typed_keys=true prefixes agg names with their internal type
        (RestSearchAction TYPED_KEYS_PARAM; e.g. `avg#name`, `sterms#name`)
        so clients can re-parse responses type-safely."""
        _NUMERIC_TYPES = {"long", "integer", "short", "byte", "double",
                          "float", "half_float", "scaled_float", "date",
                          "boolean"}

        def type_prefix(kind, spec, result):
            if kind == "terms":
                # prefix comes from the FIELD type, not the matched buckets
                # (an empty result must keep the same typed key)
                field = spec.get("field") if isinstance(spec, dict) else None
                for svc in node.indices.indices.values():
                    mapper = svc.mapper_service.get(field) if field else None
                    if mapper is not None:
                        return ("lterms" if mapper.type_name in _NUMERIC_TYPES
                                else "sterms")
                buckets = result.get("buckets") or []
                numeric = buckets and all(
                    isinstance(b.get("key"), (int, float))
                    and not isinstance(b.get("key"), bool) for b in buckets)
                return "lterms" if numeric else "sterms"
            if kind == "percentiles":
                if isinstance(spec, dict) and spec.get("hdr") is not None:
                    return "hdr_percentiles"
                return "tdigest_percentiles"
            if kind == "significant_terms":
                field = spec.get("field") if isinstance(spec, dict) else None
                for svc in node.indices.indices.values():
                    mapper = svc.mapper_service.get(field) if field else None
                    if mapper is not None:
                        return ("siglterms"
                                if mapper.type_name in _NUMERIC_TYPES
                                else "sigsterms")
                return "sigsterms"
            if kind == "significant_text":
                return "sigsterms"
            if kind == "sampler":
                return "sampler"
            if kind == "percentile_ranks":
                return "tdigest_percentile_ranks"
            if kind == "max_bucket" or kind == "min_bucket":
                return "bucket_metric_value"
            return kind

        def walk(aggs_out, aggs_spec):
            if not isinstance(aggs_out, dict) or not aggs_spec:
                return
            for name, spec in list(aggs_spec.items()):
                if name not in aggs_out or not isinstance(spec, dict):
                    continue
                kinds = [k for k in spec
                         if k not in ("aggs", "aggregations", "meta")]
                if len(kinds) != 1:
                    continue
                result = aggs_out.pop(name)
                aggs_out[f"{type_prefix(kinds[0], spec[kinds[0]], result)}"
                         f"#{name}"] = result
                sub = spec.get("aggs") or spec.get("aggregations")
                if sub and isinstance(result, dict):
                    buckets = result.get("buckets")
                    if isinstance(buckets, dict):  # named filters buckets
                        buckets = buckets.values()
                    for bucket in buckets or []:
                        walk(bucket, sub)
                    walk(result, sub)

        if isinstance(resp.get("aggregations"), dict):
            walk(resp["aggregations"],
                 body.get("aggs") or body.get("aggregations") or {})
        # suggesters prefix too: suggest.{kind}#{name}
        if isinstance(resp.get("suggest"), dict):
            for name, sspec in (body.get("suggest") or {}).items():
                if name not in resp["suggest"] or not isinstance(sspec, dict):
                    continue
                kind = next((k for k in ("term", "phrase", "completion")
                             if k in sspec), None)
                if kind:
                    resp["suggest"][f"{kind}#{name}"] = \
                        resp["suggest"].pop(name)

    def bulk(req):
        t_parse = time.monotonic_ns()
        ops = req.ndjson()
        parsed = (t_parse, time.monotonic_ns())
        with _rest_telemetry(req, node, "indices:data/write/bulk",
                             force_trace=req.bool_param("trace"),
                             description=f"requests[{len(ops)}]",
                             parsed=parsed):
            with telemetry.stage("bulk.execute", ops=len(ops)):
                resp = node.bulk(ops,
                                 default_index=req.params.get("index"),
                                 refresh=req.param("refresh"),
                                 source_filter=_get_source_filter(req))
            return 200, resp

    rc.register("POST", "/_bulk", bulk)
    rc.register("PUT", "/_bulk", bulk)
    rc.register("POST", "/{index}/_bulk", bulk)

    def mget(req):
        sf = req.param("stored_fields")
        return 200, node.mget(
            req.json() or {}, req.params.get("index"),
            stored_fields=sf.split(",") if sf else None,
            realtime=req.param("realtime") not in ("false", False),
            refresh=req.param("refresh") in ("true", "", True),
            source_filter=_get_source_filter(req))

    rc.register("GET", "/_mget", mget)
    rc.register("POST", "/_mget", mget)
    rc.register("GET", "/{index}/_mget", mget)
    rc.register("POST", "/{index}/_mget", mget)

    # ---------------------------------------------------------------- search
    def search(req):
        t_parse = time.monotonic_ns()
        body = req.json() or {}
        parsed = (t_parse, time.monotonic_ns())
        # every search runs as a live task under telemetry: sampled by
        # telemetry.tracing.sample_rate, forced by ?trace=true or a
        # profile body; X-Opaque-ID rides the task, the trace, and any
        # slow-log breach
        with _rest_telemetry(
                req, node, "indices:data/read/search",
                force_trace=(req.bool_param("trace")
                             or bool(body.get("profile"))),
                description=f"indices[{req.params.get('index') or '_all'}]",
                parsed=parsed) as tr:
            status, resp = _search_inner(req, body)
            if tr is not None and isinstance(resp, dict) \
                    and body.get("profile"):
                from elasticsearch_tpu.search.profile import trace_profile
                resp.setdefault("profile", {})["trace"] = trace_profile(tr)
            return status, resp

    def _search_inner(req, body):
        # URI-search params (q=, size=, from=, sort=)
        body = apply_uri_query(req, body)
        for p, key in (("size", "size"), ("from", "from")):
            v = req.int_param(p)
            if v is not None:
                body[key] = v
        pfs = req.param("pre_filter_shard_size")
        if pfs is not None:
            if int(pfs) < 1:
                raise IllegalArgumentError("preFilterShardSize must be >= 1")
            body["__pre_filter_shard_size__"] = int(pfs)
        tth = req.param("track_total_hits")
        if tth is not None:
            body["track_total_hits"] = (
                True if tth in ("true", "") else
                False if tth == "false" else int(tth))
        sort = req.param("sort")
        if sort:
            body["sort"] = [
                {s.split(":")[0]: s.split(":")[1]} if ":" in s else s
                for s in sort.split(",")]
        # URL-level _source / docvalue_fields filtering (RestSearchAction
        # parses these into the SearchSourceBuilder)
        src_inc = req.param("_source_includes")
        src_exc = req.param("_source_excludes")
        if src_inc is not None or src_exc is not None:
            body["_source"] = {
                "includes": src_inc.split(",") if src_inc else [],
                "excludes": src_exc.split(",") if src_exc else []}
        elif req.param("_source") is not None:
            raw = req.param("_source")
            body["_source"] = ({"true": True, "false": False}.get(raw, None)
                               if raw in ("true", "false")
                               else raw.split(","))
        dvf = req.param("docvalue_fields")
        if dvf:
            body["docvalue_fields"] = dvf.split(",")
        if req.bool_param("seq_no_primary_term", False):
            body["seq_no_primary_term"] = True
        if req.bool_param("version", False):
            body["version"] = True
        st = req.param("search_type")
        if st in ("query_and_fetch", "dfs_query_and_fetch"):
            raise IllegalArgumentError(
                f"Unsupported search type [{st}]")
        brs = req.int_param("batched_reduce_size")
        if brs is None and body.get("batched_reduce_size") is not None:
            brs = int(body["batched_reduce_size"])
        if brs is not None:
            if brs < 2:
                raise IllegalArgumentError("batchedReduceSize must be >= 2")
            body["batched_reduce_size"] = brs
        pfss = req.int_param("pre_filter_shard_size")
        if pfss is not None and pfss < 1:
            raise IllegalArgumentError("preFilterShardSize must be >= 1")
        tt = body.get("track_total_hits")
        if isinstance(tt, int) and not isinstance(tt, bool) and tt < -1:
            raise IllegalArgumentError(
                f"[track_total_hits] parameter must be positive or "
                f"equals to -1, got {tt}")
        if req.bool_param("rest_total_hits_as_int", False):
            if isinstance(tt, int) and not isinstance(tt, bool) and tt != -1:
                raise IllegalArgumentError(
                    f"[rest_total_hits_as_int] cannot be used if the "
                    f"tracking of total hits is not accurate, got {tt}")
        scroll = req.param("scroll")
        if scroll:
            if body.get("size") == 0:
                raise IllegalArgumentError(
                    "[size] cannot be [0] in a scroll context")
            if req.param("request_cache") is not None:
                raise IllegalArgumentError(
                    "[request_cache] cannot be used in a scroll context")
            if body.get("track_total_hits") is False:
                raise IllegalArgumentError(
                    "disabling [track_total_hits] is not allowed in a "
                    "scroll context")
            check_scroll_keep_alive(node, scroll)
            resp = node.search_scroll_start(
                req.params.get("index"), body, keep_alive=scroll,
                ignore_throttled=req.bool_param("ignore_throttled", True))
        else:
            if req.param("request_cache") is not None:
                # the URI param form of the per-request cache opt-in/out
                # (RestSearchAction); the cache policy reads it from the
                # body (search/caches.RequestCache)
                body["request_cache"] = req.bool_param(
                    "request_cache", True)
            resp = node.search(req.params.get("index"), body,
                               ignore_throttled=req.bool_param(
                                   "ignore_throttled", True),
                               ignore_unavailable=req.bool_param(
                                   "ignore_unavailable", False),
                               allow_no_indices=req.bool_param(
                                   "allow_no_indices", True),
                               expand_wildcards=req.param(
                                   "expand_wildcards"))
        if req.bool_param("rest_total_hits_as_int", False):
            _total_hits_as_int(resp)
        if req.bool_param("typed_keys", False):
            _apply_typed_keys(resp, body)
        return 200, resp

    rc.register("GET", "/_search", search)
    rc.register("POST", "/_search", search)
    rc.register("GET", "/{index}/_search", search)
    rc.register("POST", "/{index}/_search", search)

    def count(req):
        body = apply_uri_query(req, req.json() or {})
        return 200, node.count(req.params.get("index"), body)

    rc.register("GET", "/_count", count)
    rc.register("POST", "/_count", count)
    rc.register("GET", "/{index}/_count", count)
    rc.register("POST", "/{index}/_count", count)

    def msearch(req):
        lines = req.ndjson()
        if req.bool_param("rest_total_hits_as_int", False):
            for i in range(1, len(lines), 2):
                tth = (lines[i] or {}).get("track_total_hits")
                if isinstance(tth, int) and not isinstance(tth, bool):
                    raise IllegalArgumentError(
                        "[rest_total_hits_as_int] cannot be used if the "
                        f"tracking of total hits is not accurate, got {tth}")
        resp = node.msearch(lines)
        bodies = [lines[i] for i in range(1, len(lines), 2)]
        for i, r in enumerate(resp.get("responses", [])):
            if req.bool_param("rest_total_hits_as_int", False):
                _total_hits_as_int(r)
            if req.bool_param("typed_keys", False) and i < len(bodies):
                _apply_typed_keys(r, bodies[i])
        return 200, resp

    rc.register("GET", "/_msearch", msearch)
    rc.register("POST", "/_msearch", msearch)
    rc.register("POST", "/{index}/_msearch", msearch)

    def analyze(req):
        return 200, node.analyze(req.json() or {},
                                 index=req.params.get("index"))

    rc.register("GET", "/_analyze", analyze)
    rc.register("POST", "/_analyze", analyze)
    rc.register("GET", "/{index}/_analyze", analyze)
    rc.register("POST", "/{index}/_analyze", analyze)

    # ----------------------------------------------------------- index admin
    def create_index(req):
        body = req.json() or {}
        svc = node.create_index_with_templates(
            req.params["index"], settings=body.get("settings"),
            mappings=body.get("mappings"), aliases=body.get("aliases"))
        return 200, {"acknowledged": True, "shards_acknowledged": True,
                     "index": svc.name}

    def delete_index(req):
        expr = req.params["index"]
        ignore_unavailable = req.bool_param("ignore_unavailable", False)
        allow_no = req.bool_param("allow_no_indices", True)
        to_delete = []
        for part in expr.split(","):
            part = part.strip()
            if not part:
                continue
            if "*" not in part and part != "_all":
                if part not in node.indices.indices:
                    if ignore_unavailable:
                        # lenient options skip alias and missing names alike
                        # (indices.delete/10_basic "ignore unavailable")
                        continue
                    # aliases may not be delete targets
                    if any(part in s.aliases
                           for s in node.indices.indices.values()):
                        raise IllegalArgumentError(
                            f"The provided expression [{part}] matches an "
                            f"alias, specify the corresponding concrete "
                            f"indices instead.")
                    raise IndexNotFoundError(part)
                to_delete.append(part)
            else:
                import fnmatch as _fn
                pat = "*" if part == "_all" else part
                matched = [n for n in node.indices.indices
                           if _fn.fnmatch(n, pat)]
                if not matched and not allow_no:
                    raise IndexNotFoundError(part)
                to_delete.extend(matched)
        for name in dict.fromkeys(to_delete):
            node.indices.delete_index(name)
        return 200, {"acknowledged": True}

    def _resolve_with_options(req, expr):
        """IndicesOptions resolution shared by the index-info APIs:
        ignore_unavailable drops missing concretes, allow_no_indices
        tolerates empty wildcards, expand_wildcards picks open/closed."""
        expand = req.param("expand_wildcards") or "open"
        if isinstance(expand, (list, tuple)):
            expand = ",".join(str(t) for t in expand)
        tokens = {t for t in expand.split(",") if t}
        want_open = bool(tokens & {"open", "all"}) or not tokens
        want_closed = bool(tokens & {"closed", "all"})
        ignore_unavailable = req.bool_param("ignore_unavailable", False)
        allow_no = req.bool_param("allow_no_indices", True)
        out = []
        for part in (expr or "_all").split(","):
            part = part.strip()
            if not part:
                continue
            if "*" in part or part == "_all":
                import fnmatch as _fn
                pat = "*" if part == "_all" else part
                for n, svc in node.indices.indices.items():
                    if not _fn.fnmatch(n, pat):
                        continue
                    if svc.closed and not want_closed:
                        continue
                    if not svc.closed and not want_open:
                        continue
                    if svc.hidden and not (tokens & {"all", "hidden"}) \
                            and not (pat.startswith(".")
                                     and n.startswith(".")):
                        continue
                    out.append(svc)
            else:
                try:
                    svc = node.indices.get(part)
                except SearchEngineError:
                    if ignore_unavailable:
                        continue
                    raise
                out.append(svc)
        if not out and not allow_no:
            raise IndexNotFoundError(expr)
        seen = set()
        return [s for s in out
                if s.name not in seen and not seen.add(s.name)]

    def get_index(req):
        from elasticsearch_tpu.indices.service import IndicesService
        for part in req.params["index"].split(","):
            part = part.strip()
            if part.startswith("_") and part not in ("_all",):
                # reserved names are a request error, not a missing index
                IndicesService.validate_index_name(part)
        human = req.bool_param("human", False)
        out = {}
        for svc in _resolve_with_options(req, req.params["index"]):
            idx_settings = {
                **{k.replace("index.", "", 1): v
                   for k, v in svc.settings.as_flat_dict().items()},
                "uuid": svc.uuid,
                "creation_date": str(svc.creation_date),
                "provided_name": svc.name,
            }
            if human:
                idx_settings["creation_date_string"] = _fmt_iso_millis(
                    svc.creation_date)
                idx_settings.setdefault("version", {})
                if isinstance(idx_settings["version"], dict):
                    idx_settings["version"]["created_string"] = __version__
                    idx_settings["version"].setdefault("created", "8000099")
            out[svc.name] = {
                "aliases": svc.aliases,
                "mappings": svc.mapper_service.to_dict(),
                "settings": {"index": idx_settings},
            }
        if not out and not req.bool_param("ignore_unavailable", False) \
                and "*" not in req.params["index"] \
                and req.bool_param("allow_no_indices", True) is False:
            raise IndexNotFoundError(req.params["index"])
        return 200, out

    def index_exists(req):
        return (200 if all(node.indices.exists(p) or "*" in p
                           for p in req.params["index"].split(","))
                else 404), None

    rc.register("PUT", "/{index}", create_index)
    rc.register("DELETE", "/{index}", delete_index)
    rc.register("GET", "/{index}", get_index)
    rc.register("HEAD", "/{index}", index_exists)

    def get_mapping(req):
        out = {}
        for svc in _resolve_with_options(req, req.params.get("index")):
            out[svc.name] = {"mappings": svc.mapper_service.to_dict()}
        return 200, out

    def put_mapping(req):
        # wildcard/_all expressions update every matching index
        # (MetaDataMappingService applies to all resolved concretes);
        # matching nothing is an error, not a silent ack
        body = req.json() or {}
        if "_doc" in body and isinstance(body["_doc"], dict) \
                and "properties" in body["_doc"]:
            raise IllegalArgumentError(
                "Types cannot be provided in put mapping requests")
        resolved = node.indices.resolve(req.params["index"])
        if not resolved:
            raise IndexNotFoundError(req.params["index"])
        for svc in resolved:
            node.indices.update_mapping(svc.name, body)
        return 200, {"acknowledged": True}

    def get_field_mapping(req):
        """GET [/{index}]/_mapping/field/{fields} (reference:
        RestGetFieldMappingAction / TransportGetFieldMappingsAction):
        per-index {mappings: {full_name: {full_name, mapping: {leaf: def}}}};
        unknown fields yield an empty mappings object."""
        import fnmatch
        fields = [f.strip() for f in req.params["fields"].split(",")]
        include_defaults = req.param("include_defaults") in ("true", "", True)
        out = {}
        for svc in node.indices.resolve(req.params.get("index")):
            ms = svc.mapper_service
            matched = {}
            for pat in fields:
                if "*" in pat:
                    names = [n for n in ms.field_names()
                             if fnmatch.fnmatchcase(n, pat)]
                else:
                    names = [pat] if ms.get_raw(pat) is not None else []
                for full in names:
                    mapper = ms.get_raw(full)
                    if mapper is None or mapper.type_name == "nested":
                        continue
                    d = mapper.to_def()
                    if include_defaults and d.get("type") == "text" \
                            and "analyzer" not in d:
                        d["analyzer"] = "default"
                    leaf = full.rsplit(".", 1)[-1]
                    matched[full] = {"full_name": full, "mapping": {leaf: d}}
            out[svc.name] = {"mappings": matched}
        return 200, out

    rc.register("GET", "/_mapping", get_mapping)
    rc.register("GET", "/{index}/_mapping", get_mapping)
    rc.register("GET", "/_mapping/field/{fields}", get_field_mapping)
    rc.register("GET", "/{index}/_mapping/field/{fields}", get_field_mapping)
    rc.register("PUT", "/{index}/_mapping", put_mapping)
    rc.register("POST", "/{index}/_mapping", put_mapping)

    def _settings_str(v):
        # the reference renders every setting value as a string
        if isinstance(v, bool):
            return "true" if v else "false"
        if isinstance(v, (list, tuple)):
            return [_settings_str(x) for x in v]
        return str(v)

    _SETTINGS_DEFAULTS = {
        "index.refresh_interval": "1s",
        "index.max_result_window": "10000",
        "index.max_inner_result_window": "100",
        "index.max_rescore_window": "10000",
        "index.flush_after_merge": "512mb",
        "index.translog.durability": "request",
        "index.translog.flush_threshold_size": "512mb",
        "index.write.wait_for_active_shards": "1",
        "index.highlight.max_analyzed_offset": "1000000",
    }

    def _nest(flat: dict) -> dict:
        nested: dict = {}
        for k, v in flat.items():
            parts = k.split(".")
            cur = nested
            for p in parts[:-1]:
                cur = cur.setdefault(p, {})
            cur[parts[-1]] = v
        return nested

    def get_settings(req):
        import fnmatch as _fn
        name_filter = req.params.get("name")
        patterns = ([p.strip() for p in name_filter.split(",")]
                    if name_filter and name_filter not in ("_all", "*")
                    else None)
        flat_mode = req.bool_param("flat_settings", False)
        include_defaults = req.bool_param("include_defaults", False)
        out = {}
        for svc in node.indices.resolve(req.params.get("index")):
            flat = {"index.uuid": svc.uuid,
                    "index.provided_name": svc.name,
                    "index.creation_date": str(svc.creation_date),
                    **svc.settings.as_flat_dict()}
            if patterns is not None:
                flat = {k: v for k, v in flat.items()
                        if any(_fn.fnmatch(k, p) for p in patterns)}
            flat = {k: _settings_str(v) for k, v in flat.items()
                    if v is not None}
            entry = {"settings": flat if flat_mode
                     else {"index": _nest({k.replace("index.", "", 1): v
                                           for k, v in flat.items()})}}
            if include_defaults:
                defaults = {k: v for k, v in _SETTINGS_DEFAULTS.items()
                            if k not in flat}
                entry["defaults"] = defaults if flat_mode else _nest(defaults)
            out[svc.name] = entry
        return 200, out

    rc.register("GET", "/_settings", get_settings)
    rc.register("GET", "/{index}/_settings", get_settings)
    rc.register("GET", "/_settings/{name}", get_settings)
    rc.register("GET", "/{index}/_settings/{name}", get_settings)

    def _shards_of(services) -> dict:
        n = sum(len(svc.shards) for svc in services)
        return {"_shards": {"total": n, "successful": n, "failed": 0}}

    def refresh(req):
        services = node.indices.resolve_open(req.params.get("index"))
        for svc in services:
            svc.refresh()
        return 200, _shards_of(services)

    def flush(req):
        force = req.param("force") in ("true", "", True)
        wait = req.param("wait_if_ongoing")
        if force and wait in ("false", False):
            from elasticsearch_tpu.common.errors import (
                ActionRequestValidationError)
            raise ActionRequestValidationError(
                "Validation Failed: 1: wait_if_ongoing must be true for a "
                "force flush;")
        services = node.indices.resolve_open(req.params.get("index"))
        for svc in services:
            svc.flush()
        return 200, _shards_of(services)

    def forcemerge(req):
        if req.param("only_expunge_deletes") in ("true", "", True) \
                and req.param("max_num_segments") is not None:
            from elasticsearch_tpu.common.errors import (
                ActionRequestValidationError)
            raise ActionRequestValidationError(
                "Validation Failed: 1: cannot set only_expunge_deletes and "
                "max_num_segments at the same time, those two parameters "
                "are mutually exclusive;")
        services = node.indices.resolve_open(req.params.get("index"))
        for svc in services:
            svc.force_merge()
        return 200, _shards_of(services)

    rc.register("POST", "/_refresh", refresh)
    rc.register("POST", "/{index}/_refresh", refresh)
    rc.register("GET", "/{index}/_refresh", refresh)
    rc.register("POST", "/_flush", flush)
    rc.register("POST", "/{index}/_flush", flush)
    rc.register("POST", "/_forcemerge", forcemerge)
    rc.register("POST", "/{index}/_forcemerge", forcemerge)

    def index_stats(req):
        metric = req.params.get("metric")
        metrics = [m.strip() for m in metric.split(",")] if metric else None
        expand = req.param("expand_wildcards") or ""
        if isinstance(expand, (list, tuple)):
            expand = ",".join(str(t) for t in expand)
        return 200, node.index_stats(
            req.params.get("index"), metrics,
            level=req.param("level") or "indices",
            fields=req.param("fields"),
            fielddata_fields=req.param("fielddata_fields"),
            completion_fields=req.param("completion_fields"),
            groups=req.param("groups"),
            include_segment_file_sizes=req.bool_param(
                "include_segment_file_sizes", False),
            include_unloaded_segments=req.bool_param(
                "include_unloaded_segments", False),
            forbid_closed_indices=req.bool_param(
                "forbid_closed_indices", True),
            expand_hidden=any(t in ("all", "hidden")
                              for t in expand.split(",") if t))

    rc.register("GET", "/_stats", index_stats)
    rc.register("GET", "/_stats/{metric}", index_stats)
    rc.register("GET", "/{index}/_stats", index_stats)
    rc.register("GET", "/{index}/_stats/{metric}", index_stats)

    def aliases_post(req):
        node.indices.update_aliases((req.json() or {}).get("actions", []))
        return 200, {"acknowledged": True}

    def _split_alias_patterns(patterns):
        """`-pat` subtracts when a wildcard include appeared earlier OR the
        exclusion itself is a wildcard pattern; otherwise `-name` is a
        literal name (IndexNameExpressionResolver wildcard resolution)."""
        includes, excludes = [], []
        seen_wildcard = False
        for p in patterns:
            if p.startswith("-") and (seen_wildcard or "*" in p):
                excludes.append(p[1:])
                if "*" in p:  # a wildcard EXCLUSION also arms later `-name`s
                    seen_wildcard = True
                continue
            includes.append(p)
            if "*" in p or p == "_all":
                seen_wildcard = True
        return includes, excludes

    def _alias_matches(alias: str, patterns) -> bool:
        import fnmatch as _fn
        includes, excludes = _split_alias_patterns(patterns)
        if not any(p in ("_all", "*") or _fn.fnmatch(alias, p)
                   for p in includes):
            return False
        return not any(p in ("_all", "*") or _fn.fnmatch(alias, p)
                       for p in excludes)

    def _missing_aliases(patterns, found) -> list:
        includes, _ = _split_alias_patterns(patterns)
        return [p for p in includes
                if "*" not in p and p != "_all" and p not in found]

    def _alias_missing_response(missing, extra=None):
        label = "alias" if len(missing) == 1 else "aliases"
        return 404, {"error": f"{label} [{','.join(sorted(missing))}] missing",
                     "status": 404, **(extra or {})}

    def get_aliases(req):
        """GET [/{index}]/_alias[/{name}] (TransportGetAliasesAction):
        name filters (csv, wildcards, _all, `-` exclusions); concrete
        names matching nothing anywhere are a 404 `alias(es) [x] missing`."""
        name = req.params.get("alias")
        patterns = [p.strip() for p in name.split(",")] if name else None
        out = {}
        tokens = {t.strip() for t in
                  str(req.param("expand_wildcards") or "all").split(",") if t}
        want_open = bool(tokens & {"open", "all"})
        want_closed = bool(tokens & {"closed", "all"})
        resolved = node.indices.resolve(req.params.get("index"),
                                        expand_closed=want_closed)
        resolved = [s for s in resolved
                    if (want_open and not s.closed)
                    or (want_closed and s.closed)]

        def render(spec):
            # alias "routing" renders split into index_/search_routing
            # (AliasMetadata#toXContent)
            spec = dict(spec or {})
            routing = spec.pop("routing", None)
            if routing is not None:
                spec.setdefault("index_routing", routing)
                spec.setdefault("search_routing", routing)
            return spec

        for svc in resolved:
            if patterns is None:
                out[svc.name] = {"aliases": {a: render(s)
                                             for a, s in svc.aliases.items()}}
                continue
            matched = {a: render(spec) for a, spec in svc.aliases.items()
                       if _alias_matches(a, patterns)}
            if matched:
                out[svc.name] = {"aliases": matched}
        if patterns:
            # missing is judged WITHIN the requested index scope
            # (RestGetAliasesAction checks the response, not the cluster)
            scope_aliases = {a for svc in resolved for a in svc.aliases}
            missing = _missing_aliases(patterns, scope_aliases)
            if missing:
                return _alias_missing_response(missing, out)
        return 200, out

    def alias_exists(req):
        status, _body = get_aliases(req)
        return (200 if status == 200 else 404), None

    def put_alias(req):
        alias = req.params.get("alias")
        if alias:
            bad = set('#\\/*?"<>| ,:')
            if any(c in bad for c in alias) \
                    or alias.startswith(("-", "_", "+")):
                raise IllegalArgumentError(
                    f"Invalid alias name [{alias}]: must be lowercase and "
                    "must not contain spaces, commas, or special characters")
            if alias in node.indices.indices:
                raise IllegalArgumentError(
                    f"Invalid alias name [{alias}]: an index or data stream "
                    "exists with the same name as the alias")
        body = req.json() or {}
        spec = {k: v for k, v in body.items()
                if k in ("filter", "routing", "index_routing",
                         "search_routing", "is_write_index", "is_hidden")}
        targets = node.indices.resolve(req.params["index"])
        if not targets:
            raise IndexNotFoundError(req.params["index"])
        for svc in targets:
            node.indices.update_aliases([{"add": {
                "index": svc.name, "alias": req.params["alias"], **spec}}])
        return 200, {"acknowledged": True}

    def delete_alias(req):
        """DELETE /{index}/_alias/{name}: names/indices take csv +
        wildcards. Validation-first and ATOMIC: a missing concrete name
        404s with NOTHING removed (the reference validates all alias
        actions before mutating)."""
        patterns = [p.strip() for p in req.params["alias"].split(",")]
        targets = node.indices.resolve(req.params["index"])
        if not targets:
            raise IndexNotFoundError(req.params["index"])
        removals = [(svc.name, a) for svc in targets
                    for a in list(svc.aliases)
                    if _alias_matches(a, patterns)]
        scope_aliases = {a for _, a in removals}
        missing = _missing_aliases(patterns, scope_aliases)
        if missing:
            return _alias_missing_response(missing)
        for index_name, alias in removals:
            node.indices.update_aliases([{"remove": {
                "index": index_name, "alias": alias}}])
        return 200, {"acknowledged": True}

    rc.register("POST", "/_aliases", aliases_post)
    for path in ("/_alias", "/{index}/_alias", "/_alias/{alias}",
                 "/{index}/_alias/{alias}"):
        rc.register("GET", path, get_aliases)
        rc.register("HEAD", path, alias_exists)
    for path in ("/{index}/_alias/{alias}", "/{index}/_aliases/{alias}"):
        rc.register("PUT", path, put_alias)
        rc.register("POST", path, put_alias)
        rc.register("DELETE", path, delete_alias)

    # ---------------------------------------------------------------- cluster
    def cluster_health(req):
        # wait_for_* resolves immediately: single-node state is
        # deterministic, so a target is either already met or never will
        # be within the request (reference waits on a state observer)
        expand = req.param("expand_wildcards") or "all"
        if isinstance(expand, (list, tuple)):
            expand = ",".join(str(t) for t in expand)
        out = node.cluster_health(req.params.get("index"),
                                  level=req.param("level", "cluster"),
                                  expand_wildcards=expand)
        timed_out = bool(out.get("timed_out"))
        want = req.param("wait_for_status")
        order = {"green": 0, "yellow": 1, "red": 2}
        if want and order.get(out["status"], 2) > order.get(want, 0):
            timed_out = True
        wn = req.param("wait_for_nodes")
        if wn:
            import re as _re
            m = _re.fullmatch(r"(>=|<=|>|<|==|eq\()?\s*(\d+)\)?", str(wn))
            if m:
                op = m.group(1) or ">="
                n = int(m.group(2))
                have = out["number_of_nodes"]
                ok = {">=": have >= n, "<=": have <= n, ">": have > n,
                      "<": have < n, "==": have == n,
                      "eq(": have == n}[op]
                if not ok:
                    timed_out = True
        was = req.param("wait_for_active_shards")
        if was and was != "all" and int(was) > out["active_shards"]:
            timed_out = True
        if timed_out:
            out["timed_out"] = True
            return 408, out
        return 200, out

    def cluster_stats(req):
        import resource as _res
        import shutil as _sh
        total_docs = sum(s.doc_count() for s in node.indices.indices.values())
        segs = sum(len(sh.engine.segments)
                   for s in node.indices.indices.values()
                   for sh in s.shards)
        # field type census incl. synthesized object parents, with
        # per-index attribution (MappingStats)
        from elasticsearch_tpu.node_admin import _index_field_caps
        field_types: dict = {}
        for s in node.indices.indices.values():
            per_index_types: dict = {}
            for _path, (t, _se, _ag, _m) in _index_field_caps(
                    s.mapper_service).items():
                per_index_types[t] = per_index_types.get(t, 0) + 1
            for t, c in per_index_types.items():
                e = field_types.setdefault(t, {"count": 0, "indices": 0})
                e["count"] += c
                e["indices"] += 1
        du = _sh.disk_usage(node.data_path)
        mem_total = 8 * 1024 ** 3
        try:
            with open("/proc/meminfo") as f:
                for line in f:
                    if line.startswith("MemTotal:"):
                        mem_total = int(line.split()[1]) * 1024
                        break
        except OSError:
            pass
        mem_used = mem_total // 2
        health = node.cluster_health()
        return 200, {
            "cluster_name": node.cluster_name,
            "cluster_uuid": node.node_id,
            "timestamp": int(time.time() * 1000),
            "status": health["status"],
            "indices": {
                "count": len(node.indices.indices),
                "shards": {"total": sum(
                    s.num_shards for s in node.indices.indices.values())},
                "docs": {"count": total_docs, "deleted": 0},
                "store": {"size_in_bytes": 0, "reserved_in_bytes": 0},
                "fielddata": {"memory_size_in_bytes": 0, "evictions": 0},
                "query_cache": {"memory_size_in_bytes": 0, "hit_count": 0,
                                "miss_count": 0, "evictions": 0},
                "completion": {"size_in_bytes": 0},
                "segments": {"count": segs, "memory_in_bytes": 0},
                "mappings": {"field_types": [
                    {"name": t, "count": e["count"],
                     "index_count": e["indices"]}
                    for t, e in sorted(field_types.items())]},
                "analysis": {"analyzer_types": [], "char_filter_types": [],
                             "filter_types": [], "tokenizer_types": []},
            },
            "nodes": {
                "count": {"total": 1, "data": 1, "master": 1, "ingest": 1,
                          "coordinating_only": 0,
                          "voting_only": 0, "ml": 1,
                          "remote_cluster_client": 1, "transform": 1},
                "versions": [__version__],
                "os": {"available_processors": _os_cpus(),
                       "allocated_processors": _os_cpus(),
                       "names": [{"name": "Linux", "count": 1}],
                       "mem": {"total_in_bytes": mem_total,
                               "free_in_bytes": mem_total - mem_used,
                               "used_in_bytes": mem_used,
                               "free_percent": 50, "used_percent": 50}},
                "process": {"cpu": {"percent": 1},
                            "open_file_descriptors": {"min": 64, "max": 512,
                                                      "avg": 128}},
                "jvm": {"versions": [], "mem": {
                    "heap_used_in_bytes": 256 * 1024 * 1024,
                    "heap_max_in_bytes": 4 * 1024 ** 3},
                    "threads": 16, "max_uptime_in_millis": 1},
                "fs": {"total_in_bytes": du.total, "free_in_bytes": du.free,
                       "available_in_bytes": du.free},
                "plugins": [{"name": p, "version": __version__}
                            for p in ("sql", "eql", "ilm")],
                "network_types": {"transport_types": {"tcp": 1},
                                  "http_types": {"asyncio": 1}},
                "discovery_types": {"zen": 1},
                "packaging_types": [{"flavor": "tpu", "type": "source",
                                     "count": 1}],
            },
        }

    def _os_cpus():
        import os as _os
        return _os.cpu_count() or 1

    def cluster_state(req):
        """GET /_cluster/state[/{metric}[/{index}]] — metric filtering
        (ClusterStateRequest: version, master_node, nodes, metadata,
        routing_table, routing_nodes, blocks; cluster_name + cluster_uuid
        always present)."""
        from elasticsearch_tpu.common.settings import setting_bool
        _VALID_METRICS = {"_all", "version", "master_node", "nodes",
                          "metadata", "routing_table", "routing_nodes",
                          "blocks"}
        metric = req.params.get("metric")
        metrics = ({m.strip() for m in metric.split(",")} if metric else None)
        if metrics is not None:
            unknown = metrics - _VALID_METRICS
            if unknown:
                raise IllegalArgumentError(
                    f"request [/_cluster/state/{metric}] contains "
                    f"unrecognized metric: [{sorted(unknown)[0]}]")
            if "_all" in metrics:
                metrics = None  # _all anywhere in the list = everything
        index_filter = req.params.get("index")
        tokens = {t.strip() for t in
                  str(req.param("expand_wildcards") or "open,closed")
                  .split(",") if t.strip()}
        want_open = bool(tokens & {"open", "all"})
        want_closed = bool(tokens & {"closed", "all"})
        ignore_unavailable = req.bool_param("ignore_unavailable", False)
        allow_no = req.bool_param("allow_no_indices", True)
        if index_filter:
            if ignore_unavailable:
                svcs = []
                for part in index_filter.split(","):
                    try:
                        svcs.extend(node.indices.resolve(
                            part.strip(), expand_closed=True))
                    except SearchEngineError:
                        continue
            else:
                svcs = node.indices.resolve(index_filter,
                                            expand_closed=True)
            if not svcs and not allow_no:
                raise IndexNotFoundError(index_filter)
        else:
            svcs = list(node.indices.indices.values())
        svcs = [s for s in svcs
                if (want_open and not s.closed)
                or (want_closed and s.closed)]
        meta = {}
        routing = {}
        index_blocks = {}
        for svc in svcs:
            meta[svc.name] = {"settings": svc.settings.as_flat_dict(),
                              "mappings": svc.mapper_service.to_dict(),
                              "aliases": list(svc.aliases),
                              "state": "close" if svc.closed else "open"}
            routing[svc.name] = {"shards": {
                str(s.shard_id): [{"state": "STARTED", "primary": True,
                                   "node": node.node_id,
                                   "shard": s.shard_id, "index": svc.name}]
                for s in svc.shards}}
            b = {}
            if setting_bool(svc.settings.get("index.blocks.read_only")):
                b["5"] = {"description": "index read-only (api)",
                          "retryable": False,
                          "levels": ["write", "metadata_write"]}
            if setting_bool(svc.settings.get("index.blocks.write")):
                b["8"] = {"description": "index write (api)",
                          "retryable": False, "levels": ["write"]}
            if b:
                index_blocks[svc.name] = b
        sections = {
            "version": 1,
            "master_node": node.node_id,
            "blocks": {"indices": index_blocks} if index_blocks else {},
            "nodes": {node.node_id: {"name": node.node_name}},
            "metadata": {"indices": meta,
                         "cluster_uuid": node.node_id},
            "routing_table": {"indices": routing},
            "routing_nodes": {"unassigned": [],
                              "nodes": {node.node_id: [
                                  e for r in routing.values()
                                  for shards in r["shards"].values()
                                  for e in shards]}},
        }
        out = {"cluster_name": node.cluster_name,
               "cluster_uuid": node.node_id,
               "state_uuid": node.node_id}
        for key, value in sections.items():
            if metrics is None or key in metrics:
                out[key] = value
        return 200, out

    _NODES_INFO_METRICS = {"settings", "os", "process", "jvm",
                           "thread_pool", "transport", "http", "plugins",
                           "ingest", "aggregations", "indices", "_all"}
    _INFO_BASE_KEYS = {"name", "roles", "transport_address", "host", "ip",
                       "version", "build_flavor", "build_type",
                       "build_hash", "attributes"}

    def _filter_info(info, metrics):
        if not metrics or "_all" in metrics:
            return info
        keep = set(metrics)
        info = dict(info)
        info["nodes"] = {
            nid: {k: v for k, v in sec.items()
                  if k in keep or k in _INFO_BASE_KEYS}
            for nid, sec in info["nodes"].items()}
        return info

    def nodes_info(req):
        # /_nodes[/{selector-or-metrics}[/{metrics}]] — a lone segment is
        # METRICS when every comma part is a known metric name, else a
        # node selector (RestNodesInfoAction's exact disambiguation).
        # Single-node build: every selector (_all/_local/_master/
        # data:true/names) resolves to this node.
        # the trie keeps the FIRST param name registered at a level, so
        # this segment may arrive as either {seg} or {node_id}
        seg = req.params.get("seg", req.params.get("node_id"))
        metrics_seg = req.params.get("metrics")
        metrics = []
        if metrics_seg is not None:
            metrics = [m for m in str(metrics_seg).split(",") if m]
            if metrics == ["stats"]:
                # /_nodes/{selector}/stats is the node-scoped STATS path
                return nodes_stats(req)
            for m in metrics:
                if m not in _NODES_INFO_METRICS:
                    raise IllegalArgumentError(
                        f"request [/_nodes/{seg}/{metrics_seg}] contains "
                        f"unrecognized metric: [{m}]")
        elif seg is not None:
            parts = [p for p in str(seg).split(",") if p]
            if parts and all(p in _NODES_INFO_METRICS for p in parts):
                metrics = parts
        info = _filter_info(node.nodes_info_api(), metrics)
        if req.bool_param("flat_settings", False):
            # ?flat_settings=true renders settings as dotted keys with
            # string values (Settings#toXContent flat mode)
            def _flatten(obj, prefix=""):
                out = {}
                for k, v in obj.items():
                    if isinstance(v, dict):
                        out.update(_flatten(v, f"{prefix}{k}."))
                    else:
                        out[f"{prefix}{k}"] = v if isinstance(v, str) \
                            else ("true" if v is True else
                                  "false" if v is False else str(v))
                return out
            for sec in info["nodes"].values():
                if isinstance(sec.get("settings"), dict):
                    sec["settings"] = _flatten(sec["settings"])
        return 200, info

    def nodes_stats(req):
        from elasticsearch_tpu.common.settings import setting_bool
        return 200, node.nodes_stats_api(
            level=req.param("level"),
            include_segment_file_sizes=setting_bool(
                req.param("include_segment_file_sizes")))

    rc.register("GET", "/_cluster/health", cluster_health)
    rc.register("GET", "/_cluster/health/{index}", cluster_health)
    rc.register("GET", "/_cluster/stats", cluster_stats)
    rc.register("GET", "/_cluster/state", cluster_state)
    rc.register("GET", "/_cluster/state/{metric}", cluster_state)
    rc.register("GET", "/_cluster/state/{metric}/{index}", cluster_state)
    rc.register("GET", "/_nodes", nodes_info)
    rc.register("GET", "/_nodes/{seg}", nodes_info)
    rc.register("GET", "/_nodes/{seg}/{metrics}", nodes_info)
    rc.register("GET", "/_nodes/stats", nodes_stats)

    # -------------------------------------------------------------------- cat
    # (reference: rest/action/cat/Rest*Action column catalogs + RestTable)
    from elasticsearch_tpu.rest.cat import (
        Bytes, Col, Millis, dir_size, render as cat_render,
    )

    def _index_health(svc) -> str:
        # single-node semantics: replicas can never assign, so any
        # replicated index reports yellow (ClusterHealthStatus)
        if svc.num_replicas > 0 and len(getattr(node, "cluster_nodes", [])) <= 1:
            return "yellow"
        return "green"

    def _store_bytes(svc) -> int:
        import os as _os
        tlog = sum(dir_size(_os.path.join(s.engine.path, "translog"))
                   for s in svc.shards)
        return max(sum(dir_size(s.engine.path) for s in svc.shards) - tlog, 0)

    _INDICES_COLS = [
        Col("health", "h", "current health status"),
        Col("status", "s", "open/close status"),
        Col("index", "i,idx", "index name"),
        Col("uuid", "id,uuid", "index uuid"),
        Col("pri", "p,shards.primary,shardsPrimary", "number of primary shards", right=True),
        Col("rep", "r,shards.replica,shardsReplica", "number of replica shards", right=True),
        Col("docs.count", "dc,docsCount", "available docs", right=True),
        Col("docs.deleted", "dd,docsDeleted", "deleted docs", right=True),
        Col("creation.date", "cd", "index creation date (millis)", right=True, default=False),
        Col("creation.date.string", "cds", "index creation date (ISO)", default=False),
        Col("store.size", "ss,storeSize", "store size of primaries and replicas", right=True),
        Col("pri.store.size", "", "store size of primaries", right=True),
    ]

    def cat_indices(req):
        expand = req.param("expand_wildcards") or ""
        if isinstance(expand, (list, tuple)):
            expand = ",".join(str(t) for t in expand)
        expand_hidden = any(t in ("all", "hidden")
                            for t in expand.split(",") if t)
        health_filter = req.param("health")
        rows = []
        for svc in node.indices.resolve(req.params.get("index"),
                                        expand_hidden=expand_hidden):
            health = _index_health(svc)
            if health_filter and health != health_filter:
                continue
            sb = _store_bytes(svc)
            rows.append([health, "close" if svc.closed else "open",
                         svc.name, svc.uuid, svc.num_shards,
                         svc.num_replicas, svc.doc_count(), 0,
                         svc.creation_date,
                         _fmt_iso_millis(svc.creation_date),
                         Bytes(sb), Bytes(sb)])
        # closed indices drop out of wildcard resolve(); list them too
        # when explicitly requested or matching the expression
        import fnmatch as _fn
        expr = req.params.get("index")
        emitted = {r[2] for r in rows}
        for name, svc in node.indices.indices.items():
            if not svc.closed or name in emitted:
                continue
            if expr in (None, "", "_all", "*") or any(
                    _fn.fnmatch(name, p.strip())
                    for p in (expr or "*").split(",")):
                health = _index_health(svc)
                if health_filter and health != health_filter:
                    continue
                rows.append([health, "close", name, svc.uuid,
                             svc.num_shards, svc.num_replicas,
                             None, None, svc.creation_date,
                             _fmt_iso_millis(svc.creation_date), None, None])
        rows.sort(key=lambda r: r[2])
        return cat_render(req, _INDICES_COLS, rows)

    _HEALTH_COLS = [
        Col("epoch", "t,time", "seconds since 1970-01-01 00:00:00", right=True),
        Col("timestamp", "ts,hms,hhmmss", "time in HH:MM:SS"),
        Col("cluster", "cl", "cluster name"),
        Col("status", "st", "health status"),
        Col("node.total", "nt,nodeTotal", "total number of nodes", right=True),
        Col("node.data", "nd,nodeData", "number of nodes that can store data", right=True),
        Col("shards", "t,sh,shards.total,shardsTotal", "total number of shards", right=True),
        Col("pri", "p,shards.primary,shardsPrimary", "number of primary shards", right=True),
        Col("relo", "r,shards.relocating,shardsRelocating", "number of relocating nodes", right=True),
        Col("init", "i,shards.initializing,shardsInitializing", "number of initializing nodes", right=True),
        Col("unassign", "u,shards.unassigned,shardsUnassigned", "number of unassigned shards", right=True),
        Col("pending_tasks", "pt,pendingTasks", "number of pending tasks", right=True),
        Col("max_task_wait_time", "mtwt,maxTaskWaitTime", "wait time of longest task pending"),
        Col("active_shards_percent", "asp,activeShardsPercent", "active number of shards in percent", right=True),
    ]

    def cat_health(req):
        h = node.cluster_health()
        cols = _HEALTH_COLS
        if req.param("ts") in ("false", False):
            cols = _HEALTH_COLS[2:]
        row = [h["cluster_name"], h["status"],
               h["number_of_nodes"], h["number_of_data_nodes"],
               h["active_shards"], h["active_primary_shards"],
               h["relocating_shards"], h["initializing_shards"],
               h["unassigned_shards"],
               h.get("number_of_pending_tasks", 0),
               "-",
               f"{h.get('active_shards_percent_as_number', 100.0):.1f}%"]
        if cols is _HEALTH_COLS:
            row = [int(time.time()),
                   time.strftime("%H:%M:%S", time.gmtime())] + row
        return cat_render(req, cols, [row])

    _SHARDS_COLS = [
        Col("index", "i,idx", "index name"),
        Col("shard", "s,sh", "shard name", right=True),
        Col("prirep", "p,pr,primaryOrReplica", "primary or replica"),
        Col("state", "st", "shard state"),
        Col("docs", "d,dc", "number of docs in shard", right=True),
        Col("store", "sto", "store size of shard", right=True),
        Col("ip", "", "ip of node where it lives"),
        Col("id", "", "unique id of node where it lives", default=False),
        Col("node", "n", "name of node where it lives"),
    ] + [Col(n, a, d, right=r, default=False) for (n, a, d, r) in [
        ("sync_id", "", "sync id", False),
        ("unassigned.reason", "ur", "reason shard became unassigned", False),
        ("unassigned.at", "ua", "time shard became unassigned", False),
        ("unassigned.for", "uf", "time has been unassigned", True),
        ("unassigned.details", "ud", "additional details as to why the shard became unassigned", False),
        ("recoverysource.type", "rs", "recovery source type", False),
        ("completion.size", "cs,completionSize", "size of completion", True),
        ("fielddata.memory_size", "fm,fielddataMemory", "used fielddata cache", True),
        ("fielddata.evictions", "fe,fielddataEvictions", "fielddata evictions", True),
        ("query_cache.memory_size", "qcm,queryCacheMemory", "used query cache", True),
        ("query_cache.evictions", "qce,queryCacheEvictions", "query cache evictions", True),
        ("flush.total", "ft,flushTotal", "number of flushes", True),
        ("flush.total_time", "ftt,flushTotalTime", "time spent in flush", True),
        ("get.current", "gc,getCurrent", "number of current get ops", True),
        ("get.time", "gti,getTime", "time spent in get", True),
        ("get.total", "gto,getTotal", "number of get ops", True),
        ("get.exists_time", "geti,getExistsTime", "time spent in successful gets", True),
        ("get.exists_total", "geto,getExistsTotal", "number of successful gets", True),
        ("get.missing_time", "gmti,getMissingTime", "time spent in failed gets", True),
        ("get.missing_total", "gmto,getMissingTotal", "number of failed gets", True),
        ("indexing.delete_current", "idc,indexingDeleteCurrent", "number of current deletions", True),
        ("indexing.delete_time", "idti,indexingDeleteTime", "time spent in deletions", True),
        ("indexing.delete_total", "idto,indexingDeleteTotal", "number of delete ops", True),
        ("indexing.index_current", "iic,indexingIndexCurrent", "number of current indexing ops", True),
        ("indexing.index_time", "iiti,indexingIndexTime", "time spent in indexing", True),
        ("indexing.index_total", "iito,indexingIndexTotal", "number of indexing ops", True),
        ("indexing.index_failed", "iif,indexingIndexFailed", "number of failed indexing ops", True),
        ("merges.current", "mc,mergesCurrent", "number of current merges", True),
        ("merges.current_docs", "mcd,mergesCurrentDocs", "number of current merging docs", True),
        ("merges.current_size", "mcs,mergesCurrentSize", "size of current merges", True),
        ("merges.total", "mt,mergesTotal", "number of completed merge ops", True),
        ("merges.total_docs", "mtd,mergesTotalDocs", "docs merged", True),
        ("merges.total_size", "mts,mergesTotalSize", "size merged", True),
        ("merges.total_time", "mtt,mergesTotalTime", "time spent in merges", True),
        ("refresh.total", "rto,refreshTotal", "total refreshes", True),
        ("refresh.time", "rti,refreshTime", "time spent in refreshes", True),
        ("refresh.external_total", "rto,refreshTotal", "total external refreshes", True),
        ("refresh.external_time", "rti,refreshTime", "time spent in external refreshes", True),
        ("refresh.listeners", "rli,refreshListeners", "number of pending refresh listeners", True),
        ("search.fetch_current", "sfc,searchFetchCurrent", "current fetch phase ops", True),
        ("search.fetch_time", "sfti,searchFetchTime", "time spent in fetch phase", True),
        ("search.fetch_total", "sfto,searchFetchTotal", "total fetch ops", True),
        ("search.open_contexts", "so,searchOpenContexts", "open search contexts", True),
        ("search.query_current", "sqc,searchQueryCurrent", "current query phase ops", True),
        ("search.query_time", "sqti,searchQueryTime", "time spent in query phase", True),
        ("search.query_total", "sqto,searchQueryTotal", "total query phase ops", True),
        ("search.scroll_current", "scc,searchScrollCurrent", "open scroll contexts", True),
        ("search.scroll_time", "scti,searchScrollTime", "time scroll contexts held open", True),
        ("search.scroll_total", "scto,searchScrollTotal", "completed scroll contexts", True),
        ("segments.count", "sc,segmentsCount", "number of segments", True),
        ("segments.memory", "sm,segmentsMemory", "memory used by segments", True),
        ("segments.index_writer_memory", "siwm,segmentsIndexWriterMemory", "memory used by index writer", True),
        ("segments.version_map_memory", "svmm,segmentsVersionMapMemory", "memory used by version map", True),
        ("segments.fixed_bitset_memory", "sfbm,fixedBitsetMemory", "memory used by fixed bit sets", True),
        ("seq_no.max", "sqm,maxSeqNo", "max sequence number", True),
        ("seq_no.local_checkpoint", "sql,localCheckpoint", "local checkpoint", True),
        ("seq_no.global_checkpoint", "sqg,globalCheckpoint", "global checkpoint", True),
        ("warmer.current", "wc,warmerCurrent", "current warmer ops", True),
        ("warmer.total", "wto,warmerTotal", "total warmer ops", True),
        ("warmer.total_time", "wtt,warmerTotalTime", "time spent in warmers", True),
        ("path.data", "pd,dataPath", "shard data path", False),
        ("path.state", "ps,statsPath", "shard state path", False),
    ]]

    def cat_shards(req):
        rows = []
        for svc in node.indices.resolve(req.params.get("index"),
                                        expand_hidden=True):
            for shard in svc.shards:
                ckpt = shard.engine.local_checkpoint
                by_name = {
                    "recoverysource.type": "EXISTING_STORE",
                    "completion.size": Bytes(0),
                    "fielddata.memory_size": Bytes(0),
                    "query_cache.memory_size": Bytes(0),
                    "merges.current_size": Bytes(0),
                    "merges.total_size": Bytes(0),
                    "segments.count": len(shard.engine.segments),
                    "segments.memory": Bytes(0),
                    "segments.index_writer_memory": Bytes(0),
                    "segments.version_map_memory": Bytes(0),
                    "segments.fixed_bitset_memory": Bytes(0),
                    "indexing.index_total": ckpt + 1,
                    "seq_no.max": ckpt,
                    "seq_no.local_checkpoint": ckpt,
                    "seq_no.global_checkpoint": ckpt,
                    "path.data": shard.engine.path,
                    "path.state": shard.engine.path,
                    "sync_id": None,
                    "unassigned.reason": None, "unassigned.at": None,
                    "unassigned.for": None, "unassigned.details": None,
                }
                extras = []
                for c in _SHARDS_COLS[9:]:
                    if c.name in by_name:
                        extras.append(by_name[c.name])
                    elif c.name.endswith(("_time", ".time", "total_time")):
                        extras.append(Millis(0))
                    else:
                        extras.append(0)
                rows.append([svc.name, shard.shard_id, "p", "STARTED",
                             shard.engine.doc_count(),
                             Bytes(dir_size(shard.engine.path)),
                             "127.0.0.1", node.node_id, node.node_name]
                            + extras)
                for _ in range(svc.num_replicas):
                    rows.append([svc.name, shard.shard_id, "r", "UNASSIGNED"]
                                + [None] * (len(_SHARDS_COLS) - 4))
        return cat_render(req, _SHARDS_COLS, rows)

    _NODES_COLS = [
        Col("id", "id,nodeId", "unique node id", default=False),
        Col("pid", "p", "process id", right=True, default=False),
        Col("ip", "i", "ip address"),
        Col("port", "po", "bound transport port", right=True, default=False),
        Col("http_address", "http", "bound http address", default=False),
        Col("version", "v", "es version", default=False),
        Col("heap.current", "hc,heapCurrent", "used heap", right=True, default=False),
        Col("heap.percent", "hp,heapPercent", "used heap ratio", right=True),
        Col("heap.max", "hm,heapMax", "max configured heap", right=True, default=False),
        Col("ram.percent", "rp,ramPercent", "used machine memory ratio", right=True),
        Col("cpu", "", "recent cpu usage", right=True),
        Col("load_1m", "l", "1m load avg", right=True),
        Col("load_5m", "", "5m load avg", right=True),
        Col("load_15m", "", "15m load avg", right=True),
        Col("file_desc.current", "fdc,fileDescriptorCurrent", "used file descriptors", right=True, default=False),
        Col("file_desc.percent", "fdp,fileDescriptorPercent", "used file descriptor ratio", right=True, default=False),
        Col("file_desc.max", "fdm,fileDescriptorMax", "max file descriptors", right=True, default=False),
        Col("disk.total", "dt,diskTotal", "total disk space", right=True, default=False),
        Col("disk.used", "du,diskUsed", "used disk space", right=True, default=False),
        Col("disk.avail", "d,da,disk,diskAvail", "available disk space", right=True, default=False),
        Col("disk.used_percent", "dup,diskUsedPercent", "used disk space percentage", right=True, default=False),
        Col("node.role", "r,role,nodeRole", "m:master eligible node, d:data node, i:ingest node, -:coordinating node only"),
        Col("master", "m", "*:current master"),
        Col("name", "n", "node name"),
    ]

    def cat_nodes(req):
        import shutil as _sh
        du = _sh.disk_usage(node.data_path)
        import resource as _res
        heap_pct = 42
        try:
            la1, la5, la15 = __import__("os").getloadavg()
        except OSError:
            la1 = la5 = la15 = 0.0
        soft, _hard = _res.getrlimit(_res.RLIMIT_NOFILE)
        full_id = req.param("full_id") in ("true", "", True)
        nid = node.node_id if full_id else node.node_id[:4]
        row = [nid, __import__("os").getpid(), "127.0.0.1", 9300,
               "127.0.0.1:9200", __version__,
               Bytes(256 * 1024 * 1024), heap_pct,
               Bytes(4 * 1024 ** 3), 50, 1,
               f"{la1:.2f}", f"{la5:.2f}", f"{la15:.2f}",
               64, 1, soft,
               Bytes(du.total), Bytes(du.used), Bytes(du.free),
               f"{du.used / du.total * 100:.2f}",
               "dim", "*", node.node_name]
        return cat_render(req, _NODES_COLS, [row])

    _COUNT_COLS = [
        Col("epoch", "t,time", "seconds since 1970-01-01 00:00:00", right=True),
        Col("timestamp", "ts,hms,hhmmss", "time in HH:MM:SS"),
        Col("count", "dc,docs.count,docsCount", "the document count", right=True),
    ]

    def cat_count(req):
        total = sum(s.doc_count()
                    for s in node.indices.resolve(req.params.get("index"),
                                                  expand_hidden=True))
        return cat_render(req, _COUNT_COLS,
                          [[int(time.time()),
                            time.strftime("%H:%M:%S", time.gmtime()), total]])

    _ALIASES_COLS = [
        Col("alias", "a", "alias name"),
        Col("index", "i,idx", "index alias points to"),
        Col("filter", "f,fi", "filter"),
        Col("routing.index", "ri,routingIndex", "index routing"),
        Col("routing.search", "rs,routingSearch", "search routing"),
        Col("is_write_index", "w,isWriteIndex", "write index"),
    ]

    def cat_aliases(req):
        import fnmatch as _fn
        name_filter = req.params.get("name")
        expand = req.param("expand_wildcards") or ""
        if isinstance(expand, (list, tuple)):
            expand = ",".join(str(t) for t in expand)
        # default is lenient (hidden shown); an explicit expand_wildcards
        # without all/hidden drops hidden indices and hidden aliases
        strict = expand and not any(
            t in ("all", "hidden") for t in expand.split(","))
        rows = []
        for name, svc in sorted(node.indices.indices.items()):
            for alias, opts in svc.aliases.items():
                if strict and (svc.hidden or (opts or {}).get("is_hidden")):
                    continue
                if name_filter and not any(
                        _fn.fnmatch(alias, p.strip())
                        for p in name_filter.split(",")):
                    continue
                opts = opts or {}
                routing = opts.get("routing")
                rows.append([
                    alias, name,
                    "*" if opts.get("filter") else "-",
                    opts.get("index_routing") or routing or "-",
                    opts.get("search_routing") or routing or "-",
                    str(opts["is_write_index"]).lower()
                    if opts.get("is_write_index") is not None else "-",
                ])
        return cat_render(req, _ALIASES_COLS, rows)

    # -------------------------------------------------------- open / close
    def close_index_h(req):
        names = [s.name for s in node.indices.resolve(req.params["index"])]
        if not names and "*" not in req.params["index"]:
            raise IndexNotFoundError(req.params["index"])
        for name in names:
            node.indices.close_index_state(name)
        return 200, {"acknowledged": True, "shards_acknowledged": True,
                     "indices": {n: {"closed": True} for n in names}}

    def open_index_h(req):
        # match closed indices too: resolve() skips them for wildcards;
        # each comma part resolves independently (mixed lists work)
        import fnmatch as _fn
        names = []
        for part in req.params["index"].split(","):
            part = part.strip()
            if not part:
                continue
            if "*" in part or part == "_all":
                pat = "*" if part == "_all" else part
                names.extend(n for n in node.indices.indices
                             if _fn.fnmatch(n, pat))
            elif part in node.indices.indices:
                names.append(part)
            else:
                raise IndexNotFoundError(part)
        if not names:
            raise IndexNotFoundError(req.params["index"])
        for name in dict.fromkeys(names):
            node.indices.open_index_state(name)
        return 200, {"acknowledged": True, "shards_acknowledged": True}

    rc.register("POST", "/{index}/_close", close_index_h)
    rc.register("POST", "/{index}/_open", open_index_h)

    rc.register("GET", "/_cat/indices", cat_indices)
    rc.register("GET", "/_cat/indices/{index}", cat_indices)
    rc.register("GET", "/_cat/health", cat_health)
    rc.register("GET", "/_cat/shards", cat_shards)
    rc.register("GET", "/_cat/shards/{index}", cat_shards)
    rc.register("GET", "/_cat/nodes", cat_nodes)
    rc.register("GET", "/_cat/count", cat_count)
    rc.register("GET", "/_cat/count/{index}", cat_count)
    rc.register("GET", "/_cat/aliases", cat_aliases)
    rc.register("GET", "/_cat/aliases/{name}", cat_aliases)


from elasticsearch_tpu.rest.cat import fmt_iso_millis as _fmt_iso_millis


def check_scroll_keep_alive(node, value) -> None:
    """search.max_keep_alive gate for scroll keepalives (SearchService
    validateKeepAlives)."""
    mka = node._cluster_setting("search.max_keep_alive") \
        if hasattr(node, "_cluster_setting") else None
    if not value or mka is None:
        return
    from elasticsearch_tpu.common.settings import parse_time_value
    if parse_time_value(str(value), "scroll") > \
            parse_time_value(str(mka), "max_keep_alive"):
        raise IllegalArgumentError(
            f"Keep alive for scroll ({value}) is too large. It must be "
            f"less than ({mka}). This limit can be set by changing the "
            f"[search.max_keep_alive] cluster level setting.")


def _query_string_to_dsl(q: str) -> dict:
    return {"query_string": {"query": q}}
