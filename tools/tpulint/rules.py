"""tpulint rules: our historical JAX bug classes as AST checks.

Every rule docstring cites the concrete bug it encodes — these are not
style opinions, each one shipped (or nearly shipped) as a serving defect
and cost a review round to catch by hand. Rules return findings only on
statically certain facts (the dataflow helpers answer "unknown" freely),
so suppressions stay rare and meaningful.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Set, Tuple

from tools.tpulint.dataflow import (
    DeviceTaint,
    assign_targets,
    base_name,
    call_name,
    dotted,
    infer_rank,
    is_dispatch_call,
    iter_functions,
    mesh_axes_of,
    numpy_aliases,
    spec_axis_names,
    spec_ranks,
)
from tools.tpulint.engine import Finding, ModuleContext, ProjectIndex


def _body_statements(body, *, in_loop: bool = False):
    """Yield (stmt, in_loop) linearly through nested blocks, NOT entering
    nested function/class definitions (they get their own analysis)."""
    for stmt in body:
        yield stmt, in_loop
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        if isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
            yield from _body_statements(stmt.body, in_loop=True)
            yield from _body_statements(stmt.orelse, in_loop=in_loop)
        elif isinstance(stmt, ast.If):
            yield from _body_statements(stmt.body, in_loop=in_loop)
            yield from _body_statements(stmt.orelse, in_loop=in_loop)
        elif isinstance(stmt, (ast.With, ast.AsyncWith)):
            yield from _body_statements(stmt.body, in_loop=in_loop)
        elif isinstance(stmt, ast.Try):
            yield from _body_statements(stmt.body, in_loop=in_loop)
            for h in stmt.handlers:
                yield from _body_statements(h.body, in_loop=in_loop)
            yield from _body_statements(stmt.orelse, in_loop=in_loop)
            yield from _body_statements(stmt.finalbody, in_loop=in_loop)


def _stmt_expressions(stmt: ast.stmt):
    """Walk one statement's OWN expression trees (nested defs excluded,
    nested compound-statement bodies excluded — _body_statements already
    visits those as separate statements)."""
    blocks = ("body", "orelse", "finalbody", "handlers")
    todo: List[ast.AST] = []
    for field, value in ast.iter_fields(stmt):
        if field in blocks:
            continue
        if isinstance(value, ast.AST):
            todo.append(value)
        elif isinstance(value, list):
            todo.extend(v for v in value if isinstance(v, ast.AST))
    while todo:
        node = todo.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        yield node
        todo.extend(ast.iter_child_nodes(node))


class Rule:
    rule_id = "TPU000"
    summary = ""

    def run(self, ctx: ModuleContext,
            index: ProjectIndex) -> List[Finding]:  # pragma: no cover
        raise NotImplementedError


# ---------------------------------------------------------------------------
# TPU001 — raw compilation outside the dispatcher
# ---------------------------------------------------------------------------

class RawJitRule(Rule):
    """TPU001: no raw `jax.jit` / `pjit` / raw-JAX `shard_map` outside
    `ops/dispatch.py` registrations.

    Historical bug (a round-6 CPU capture → PR 4): every distinct (batch, k,
    corpus) shape hit `jax.jit`'s tracing path in the serving hot loop —
    batch=4 ran at 149 ms p50 vs batch=16 at 31.6 ms, all of it XLA
    recompilation. The fix was the shape-bucketed dispatcher: ONE module
    owns `jax.jit(...).lower(...).compile()`, a closed bucket grid, and
    strict-mode enforcement. A raw `jax.jit` anywhere else is a second,
    unbucketed compile path the strict gate cannot see. Raw-JAX
    `shard_map` references (`jax.shard_map`, or the deprecated
    `jax.experimental.shard_map` spelling) are confined to the wrapper in
    `parallel/sharded_knn.py` for the same reason; building programs
    THROUGH that wrapper and registering them is the sanctioned pattern.
    """

    rule_id = "TPU001"
    summary = "raw jit/pjit/shard_map compilation outside the dispatcher"

    def run(self, ctx: ModuleContext, index: ProjectIndex) -> List[Finding]:
        findings: List[Finding] = []
        jit_ok = ctx.matches(ctx.config.raw_jit_allowed)
        sm_ok = ctx.matches(ctx.config.raw_shard_map_allowed)
        # `import jax as j` must not evade the rule (same alias blindness
        # TPU002 had for numpy): every name the jax module is bound to
        jax_mods = {"jax"}
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "jax":
                        jax_mods.add(a.asname or "jax")
        jit_names = {f"{m}.jit" for m in jax_mods}
        sm_names = {f"{m}.shard_map" for m in jax_mods} | {
            f"{m}.experimental.shard_map.shard_map" for m in jax_mods}
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Attribute):
                name = dotted(node)
                if not jit_ok and name in jit_names:
                    findings.append(ctx.finding(
                        self.rule_id, node,
                        "raw jax.jit compiles outside the shape-bucketed "
                        "dispatcher (register the kernel in ops/dispatch "
                        "and route through dispatch.call)"))
                elif not jit_ok and name.endswith(".pjit"):
                    findings.append(ctx.finding(
                        self.rule_id, node,
                        "raw pjit compiles outside the dispatcher"))
                elif not sm_ok and name in sm_names:
                    findings.append(ctx.finding(
                        self.rule_id, node,
                        "raw JAX shard_map reference — use the "
                        "parallel/sharded_knn wrapper"))
            elif isinstance(node, ast.Name) and node.id == "pjit" \
                    and isinstance(node.ctx, ast.Load) and not jit_ok:
                findings.append(ctx.finding(
                    self.rule_id, node,
                    "raw pjit compiles outside the dispatcher"))
            elif isinstance(node, ast.ImportFrom) and node.module:
                if not jit_ok and node.module.endswith("pjit"):
                    findings.append(ctx.finding(
                        self.rule_id, node,
                        "raw pjit import outside the dispatcher"))
                elif not jit_ok and node.module == "jax" \
                        and any(a.name in ("jit", "pjit")
                                for a in node.names):
                    # `from jax import jit` (any alias) is the most
                    # common idiom for the same unbucketed compile path
                    findings.append(ctx.finding(
                        self.rule_id, node,
                        "raw jit import outside the dispatcher — "
                        "register the kernel in ops/dispatch and route "
                        "through dispatch.call"))
                elif not sm_ok and node.module in (
                        "jax", "jax.experimental.shard_map",
                        "jax.experimental") \
                        and any(a.name == "shard_map"
                                for a in node.names):
                    findings.append(ctx.finding(
                        self.rule_id, node,
                        "raw JAX shard_map import — build sharded "
                        "programs through the wrapper "
                        "(parallel/sharded_knn.shard_map) and register "
                        "them with the dispatcher"))
        return findings


# ---------------------------------------------------------------------------
# TPU002 — host syncs on device arrays in hot paths
# ---------------------------------------------------------------------------

_SCALAR_PULLS = ("item", "tolist")


class HostSyncRule(Rule):
    """TPU002: host-sync calls on device arrays inside hot-path modules.

    Historical bug (PR 6): the host agg walkers resolved doc values
    through a per-row `get_doc_value` loop — thousands of tiny host
    round-trips where one columnar gather was value-identical and orders
    of magnitude faster. On the serving path a host sync is worse: it
    stalls a batch that OTHER requests coalesced into.

    The rule is structural about what "response assembly" means: one bulk
    device→host transfer (`np.asarray` on a whole board) or one
    `block_until_ready` at result time, OUTSIDE any loop, is the
    sanctioned pattern — exactly how `vectors/store.py` lands mesh
    results. What fires is (a) any sync inside a for/while loop — the
    per-row round-trip shape — and (b) scalar pulls (`.item()`,
    `.tolist()`, `float()`, `int()`) on device arrays anywhere in a hot
    module: a scalar pull per element is the loop, just written inline.
    """

    rule_id = "TPU002"
    summary = "host sync on a device array in a hot-path module"

    def run(self, ctx: ModuleContext, index: ProjectIndex) -> List[Finding]:
        if not ctx.hot_path:
            return []
        findings: List[Finding] = []
        np_mods, np_fns = numpy_aliases(ctx.tree)
        for fn in iter_functions(ctx.tree):
            taint = DeviceTaint(np_mods, np_fns)
            for stmt, in_loop in _body_statements(fn.body):
                for node in _stmt_expressions(stmt):
                    if not isinstance(node, ast.Call):
                        continue
                    f = self._judge(node, taint, in_loop)
                    if f is not None:
                        findings.append(ctx.finding(self.rule_id, node, f))
                taint.observe(stmt)
        return findings

    @staticmethod
    def _judge(node: ast.Call, taint: DeviceTaint,
               in_loop: bool) -> Optional[str]:
        if isinstance(node.func, ast.Attribute):
            attr = node.func.attr
            if attr in _SCALAR_PULLS \
                    and taint.expr_is_device(node.func.value):
                return (f".{attr}() pulls a device array to host "
                        "element-by-element — keep reductions on device "
                        "and land results with one bulk np.asarray at "
                        "response-assembly time")
            if attr == "block_until_ready" and in_loop \
                    and taint.expr_is_device(node.func.value):
                return ("block_until_ready inside a loop serializes "
                        "device dispatches — sync once, outside the "
                        "loop, at response-assembly time")
            if call_name(node) in taint.host_converters \
                    and in_loop and node.args \
                    and taint.expr_is_device(node.args[0]):
                return ("device→host transfer inside a loop — batch the "
                        "work and land it with one bulk np.asarray "
                        "outside the loop")
        elif isinstance(node.func, ast.Name):
            if node.func.id in ("float", "int") \
                    and len(node.args) == 1 \
                    and taint.expr_is_device(node.args[0]):
                return (f"{node.func.id}() on a device array is a "
                        "blocking scalar pull — convert whole result "
                        "boards with np.asarray at response-assembly "
                        "time")
            if node.func.id in taint.np_fn_converters and in_loop \
                    and node.args \
                    and taint.expr_is_device(node.args[0]):
                return ("device→host transfer inside a loop — batch the "
                        "work and land it with one bulk np.asarray "
                        "outside the loop")
        return None


# ---------------------------------------------------------------------------
# TPU003 — id()-keyed caches
# ---------------------------------------------------------------------------

_KEYISH = re.compile(r"key|sig", re.IGNORECASE)


class IdKeyedCacheRule(Rule):
    """TPU003: caches keyed on `id(...)` of long-lived objects.

    Historical bug (PR 5 review round): the lexical mesh-CSR cache keyed
    on `id(mesh)`. CPython recycles addresses — after the mesh was GC'd
    and a new Mesh allocated at the same address, the cache handed back
    arrays laid out for a DEAD mesh. The fix holds the mesh OBJECT
    (identity compare keeps the referent alive). `id()` in a cache key is
    only sound if the key also pins the object, which `id()` by
    construction does not; fire on every id() that flows into a
    subscript key, a cache `.get/.setdefault/.pop`, or a key/sig-named
    binding, and let the one deliberate site carry its pragma.
    """

    rule_id = "TPU003"
    summary = "cache keyed on id() of a long-lived object"

    def run(self, ctx: ModuleContext, index: ProjectIndex) -> List[Finding]:
        findings: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "id" and len(node.args) == 1):
                continue
            why = self._key_context(ctx, node)
            if why:
                findings.append(ctx.finding(
                    self.rule_id, node,
                    f"id() used as a cache-key component ({why}) — "
                    "addresses recycle after GC; key on the object "
                    "itself (holding it alive) or a stable fingerprint"))
        return findings

    @staticmethod
    def _key_context(ctx: ModuleContext, node: ast.AST) -> Optional[str]:
        child = node
        cur = ctx.parents.get(node)
        while cur is not None:
            if isinstance(cur, ast.Subscript) and cur.slice is child:
                return "subscript key"
            if isinstance(cur, ast.Call) \
                    and isinstance(cur.func, ast.Attribute) \
                    and cur.func.attr in ("get", "setdefault", "pop") \
                    and child in cur.args \
                    and "cache" in dotted(cur.func.value).lower():
                return f"cache .{cur.func.attr}()"
            if isinstance(cur, ast.Assign) and cur.value is child:
                for t in cur.targets:
                    tname = base_name(t) or ""
                    if _KEYISH.search(tname):
                        return f"assigned to {tname!r}"
            if isinstance(cur, ast.Return):
                fn = ctx.parents.get(cur)
                while fn is not None and not isinstance(
                        fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    fn = ctx.parents.get(fn)
                if fn is not None and _KEYISH.search(fn.name):
                    return f"returned from {fn.name}()"
            child = cur
            cur = ctx.parents.get(cur)
        return None


# ---------------------------------------------------------------------------
# TPU004 — read-after-donate
# ---------------------------------------------------------------------------

class ReadAfterDonateRule(Rule):
    """TPU004: re-reading an argument after passing it to a kernel
    registered with `donate_argnums`.

    Historical bug (PR 5 review round): `mesh.append` donated the old
    shard buffers while a search dispatched against the previously-
    installed FieldCorpus was still reading them — donated-then-deleted
    arrays and torn slot_map bookkeeping, visible only under concurrent
    refresh+search. XLA reuses a donated buffer's HBM for the outputs;
    ANY later read of that Python name is a read of freed memory. The
    donated positions come from the project-wide registration index
    (`register("bm25.topk", ..., donate_argnums=(0, 1))` →
    `dispatch.call("bm25.topk", board, count, ...)` consumes board and
    count).
    """

    rule_id = "TPU004"
    summary = "argument read again after donation to a kernel"

    def run(self, ctx: ModuleContext, index: ProjectIndex) -> List[Finding]:
        if not index.donated_kernels:
            return []
        findings: List[Finding] = []
        for fn in iter_functions(ctx.tree):
            consumed: Dict[str, Tuple[str, int]] = {}
            for stmt, _ in _body_statements(fn.body):
                if consumed:
                    for node in _stmt_expressions(stmt):
                        if isinstance(node, ast.Name) \
                                and isinstance(node.ctx, ast.Load) \
                                and node.id in consumed \
                                and node.lineno > consumed[node.id][1]:
                            kernel, line = consumed[node.id]
                            findings.append(ctx.finding(
                                self.rule_id, node,
                                f"{node.id!r} was donated to kernel "
                                f"[{kernel}] on line {line} "
                                f"(donate_argnums) — its buffer is "
                                "freed/reused by XLA; reading it is "
                                "use-after-free on HBM"))
                            del consumed[node.id]
                new_consumed: List[Tuple[str, str, int]] = []
                for node in _stmt_expressions(stmt):
                    if not (isinstance(node, ast.Call)
                            and is_dispatch_call(node) and node.args):
                        continue
                    head = node.args[0]
                    if not (isinstance(head, ast.Constant)
                            and isinstance(head.value, str)):
                        continue
                    donated = index.donated_kernels.get(head.value)
                    if not donated:
                        continue
                    for argnum in donated:
                        pos = argnum + 1  # args[0] is the kernel name
                        if pos < len(node.args) and isinstance(
                                node.args[pos], ast.Name):
                            new_consumed.append(
                                (node.args[pos].id, head.value,
                                 node.lineno))
                for name, kernel, line in new_consumed:
                    consumed[name] = (kernel, line)
                # rebinds clear consumption LAST: `x = call("k", x)` binds
                # x to the fresh result, not the donated buffer
                for name in assign_targets(stmt):
                    consumed.pop(name, None)
        return findings


# ---------------------------------------------------------------------------
# TPU005 — unscrubbed request payloads in cache keys
# ---------------------------------------------------------------------------

_REQUEST_NAMES = frozenset(
    {"body", "bodies", "request", "requests", "req", "payload",
     "aggs_spec", "query"})
_SANCTIONED_WRAPPER = re.compile(r"key|normali[sz]e|scrub|fingerprint",
                                 re.IGNORECASE)
# reader-identity evidence inside a request-cache key expression: a
# fingerprint/epoch-named value, a reader generation, or a call to the
# sanctioned `search/caches.request_cache_key` helper (which REQUIRES
# the fingerprint argument)
_READER_IDENTITY = re.compile(r"fingerprint|reader_gen|epoch"
                              r"|request_cache_key", re.IGNORECASE)


class UnscrubbedCacheKeyRule(Rule):
    """TPU005: cache keys built from raw request-payload values without a
    `plan_cache_key`-style normalizer.

    Historical bug (PR 4): the hybrid plan cache hashed the WHOLE request
    body — including the query vector and match text — so 108 identical-
    shape dashboard bodies produced `plan_cache_hits: 0` and the plan
    compiler ran per request. The fix (`hybrid_plan.plan_cache_key`)
    scrubs per-query values down to shapes/placeholders before hashing;
    the agg plan cache (PR 6) reuses the same trick. Any cache access
    whose key expression touches a request-payload name (`body`,
    `request`, `aggs_spec`, ...) without passing it through a
    key/normalize/scrub/fingerprint-named function rebuilds that bug.

    Second check (PR 16): REQUEST caches on the device read paths must
    key on reader identity. A request-cache access whose key is built
    INLINE (a tuple or call right in the get/put) with no reader
    fingerprint / reader gen / epoch in it — and no call to the
    sanctioned `search/caches.request_cache_key` helper, which requires
    the fingerprint argument — caches query-phase results across
    refreshes: stale hits after every ingest/delete/merge. Keys bound
    to a variable first are out of scope (provenance unknowable
    intra-module); the inline form is the one that reads plausibly
    correct in review and isn't.
    """

    rule_id = "TPU005"
    summary = "cache key built from a raw request payload"

    def run(self, ctx: ModuleContext, index: ProjectIndex) -> List[Finding]:
        findings: List[Finding] = []
        for node in ast.walk(ctx.tree):
            key_expr = None
            where = None
            target = ""
            if isinstance(node, ast.Subscript) \
                    and "cache" in (dotted(node.value) or "").lower():
                key_expr, where = node.slice, "subscript"
                target = (dotted(node.value) or "").lower()
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in ("get", "put", "setdefault") \
                    and node.args \
                    and "cache" in dotted(node.func.value).lower():
                key_expr, where = node.args[0], f".{node.func.attr}()"
                target = dotted(node.func.value).lower()
            if key_expr is None:
                continue
            name = self._raw_payload_name(ctx, key_expr)
            if name:
                findings.append(ctx.finding(
                    self.rule_id, key_expr,
                    f"cache {where} keys on raw request payload "
                    f"{name!r} — per-query values (vectors, match text) "
                    "in the key defeat the cache and leak payload data "
                    "into key storage; scrub through a plan_cache_key-"
                    "style normalizer first"))
            elif "request" in target \
                    and isinstance(key_expr, (ast.Tuple, ast.Call)) \
                    and not self._has_reader_identity(key_expr):
                findings.append(ctx.finding(
                    self.rule_id, key_expr,
                    f"request cache {where} keyed without a reader "
                    "fingerprint — a key that ignores reader identity "
                    "serves stale query-phase results across refresh/"
                    "delete/merge; build the key with search/caches."
                    "request_cache_key (fingerprint required) or "
                    "include the reader fingerprint/gen explicitly"))
        return findings

    @staticmethod
    def _has_reader_identity(key_expr: ast.AST) -> bool:
        for node in ast.walk(key_expr):
            if isinstance(node, ast.Name) \
                    and _READER_IDENTITY.search(node.id):
                return True
            if isinstance(node, ast.Attribute) \
                    and (node.attr == "gen"
                         or _READER_IDENTITY.search(node.attr)):
                return True
            if isinstance(node, ast.keyword) and node.arg \
                    and _READER_IDENTITY.search(node.arg):
                return True
            if isinstance(node, ast.Call) \
                    and _READER_IDENTITY.search(call_name(node)):
                return True
        return False

    @staticmethod
    def _raw_payload_name(ctx: ModuleContext,
                          key_expr: ast.AST) -> Optional[str]:
        for node in ast.walk(key_expr):
            if not (isinstance(node, ast.Name)
                    and node.id in _REQUEST_NAMES):
                continue
            cur = ctx.parents.get(node)
            sanctioned = False
            while cur is not None and cur is not key_expr:
                if isinstance(cur, ast.Call) and _SANCTIONED_WRAPPER.search(
                        call_name(cur).split(".")[-1]):
                    sanctioned = True
                    break
                cur = ctx.parents.get(cur)
            if not sanctioned:
                return node.id
        return None


# ---------------------------------------------------------------------------
# TPU006 — enable_x64 outside the dispatcher
# ---------------------------------------------------------------------------

class ScopedX64Rule(Rule):
    """TPU006: `enable_x64` entered outside the dispatcher's scoped-x64
    path.

    Historical context (PR 6): the agg kernels need int64 counts and f64
    sums (date millis don't fit int32/f32), but the process default must
    stay 32-bit — the serving kernels are f32 by design, and a global
    x64 flip silently doubles every buffer and retraces every cached
    executable. The dispatcher's `register(..., x64=True)` scopes the
    flag around BOTH lower() and execution (`_x64_scope`), which is the
    only sound placement: tracing canonicalization and the AOT arg-aval
    check both read the active config. An `enable_x64` (or
    `jax.config.update("jax_enable_x64", ...)`) anywhere else either
    leaks process-wide or desyncs trace-time from call-time dtypes.
    """

    rule_id = "TPU006"
    summary = "enable_x64 outside the dispatcher's scoped path"

    def run(self, ctx: ModuleContext, index: ProjectIndex) -> List[Finding]:
        if ctx.matches(ctx.config.x64_allowed):
            return []
        findings: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ImportFrom) \
                    and any(a.name == "enable_x64" for a in node.names):
                findings.append(ctx.finding(
                    self.rule_id, node,
                    "enable_x64 import outside ops/dispatch.py — x64 "
                    "kernels must register with dispatch.register(..., "
                    "x64=True) so the flag scopes trace AND execution"))
            elif isinstance(node, ast.Attribute) \
                    and dotted(node).endswith("enable_x64"):
                findings.append(ctx.finding(
                    self.rule_id, node,
                    "enable_x64 reference outside the dispatcher's "
                    "scoped-x64 path"))
            elif isinstance(node, ast.Call) \
                    and call_name(node).endswith("config.update") \
                    and node.args \
                    and isinstance(node.args[0], ast.Constant) \
                    and node.args[0].value == "jax_enable_x64":
                findings.append(ctx.finding(
                    self.rule_id, node,
                    "global jax_enable_x64 flip — doubles every buffer "
                    "and invalidates the AOT executable cache; use "
                    "dispatch.register(..., x64=True)"))
        return findings


# ---------------------------------------------------------------------------
# TPU007 — PartitionSpec rank mismatches
# ---------------------------------------------------------------------------

class SpecRankRule(Rule):
    """TPU007: statically inferable PartitionSpec-rank vs array-rank
    mismatches at `shard_map` call sites.

    Historical bug (PR 5 review round): the sharded BM25 kernel's int8
    tile-scales spec was `P(None, None)` — rank 2 — for a rank-1 scales
    array, so EVERY mesh-routed BM25 dispatch on an `impact_dtype: int8`
    index raised inside shard_map. The mismatch was fully visible in the
    source: the spec literal and the array construction were lines
    apart. This rule checks exactly that: where both the spec tuple and
    the argument's rank are statically certain, they must agree — and
    the positional arity of the call must match the spec tuple.

    The dp-axis extension (PR 11): where the MESH being mapped over has
    statically-known axis names (a literal `Mesh(grid, ("dp", "shard"))`
    or one of the policy-owned builders), every string axis named in
    in_specs/out_specs must be one of them — the dp-axis TYPO class
    (`P("pd", None)`, or an axis left over from a renamed mesh), which
    shard_map only rejects at dispatch time.
    """

    rule_id = "TPU007"
    summary = "PartitionSpec rank does not match array rank in shard_map"

    def run(self, ctx: ModuleContext, index: ProjectIndex) -> List[Finding]:
        findings: List[Finding] = []
        for fn in iter_functions(ctx.tree):
            ranks: Dict[str, int] = {}
            tuples: Dict[str, ast.AST] = {}
            sharded: Dict[str, List[Optional[int]]] = {}
            meshes: Dict[str, frozenset] = {}
            for stmt, _ in _body_statements(fn.body):
                # judge calls of previously-bound shard_map programs
                for node in _stmt_expressions(stmt):
                    if not isinstance(node, ast.Call):
                        continue
                    if self._is_shard_map(node):
                        findings.extend(self._axis_findings(
                            ctx, node, meshes, tuples))
                    specs = None
                    label = None
                    if isinstance(node.func, ast.Name) \
                            and node.func.id in sharded:
                        specs, label = sharded[node.func.id], node.func.id
                    elif isinstance(node.func, ast.Call) \
                            and self._is_shard_map(node.func):
                        specs = self._specs_of(node.func, tuples)
                        label = "shard_map(...)"
                    if specs is None:
                        continue
                    if not any(isinstance(a, ast.Starred)
                               for a in node.args) \
                            and len(node.args) != len(specs):
                        findings.append(ctx.finding(
                            self.rule_id, node,
                            f"{label} declares {len(specs)} in_specs but "
                            f"is called with {len(node.args)} arguments"))
                        continue
                    for i, (arg, srank) in enumerate(
                            zip(node.args, specs)):
                        if srank is None:
                            continue
                        arank = infer_rank(arg, ranks)
                        if arank is not None and arank != srank:
                            findings.append(ctx.finding(
                                self.rule_id, arg,
                                f"in_specs[{i}] of {label} is rank "
                                f"{srank} but the argument is rank "
                                f"{arank} — shard_map raises on rank "
                                "mismatch at dispatch time (the PR 5 "
                                "int8 tile-scales bug)"))
                # then update bindings
                if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                        and isinstance(stmt.targets[0], ast.Name):
                    tname = stmt.targets[0].id
                    ranks.pop(tname, None)
                    tuples.pop(tname, None)
                    sharded.pop(tname, None)
                    meshes.pop(tname, None)
                    value = stmt.value
                    if isinstance(value, (ast.Tuple, ast.List)):
                        tuples[tname] = value
                    elif isinstance(value, ast.Call) \
                            and self._is_shard_map(value):
                        specs = self._specs_of(value, tuples)
                        if specs is not None:
                            sharded[tname] = specs
                    else:
                        axes = mesh_axes_of(value, meshes)
                        if axes is not None:
                            meshes[tname] = axes
                        r = infer_rank(value, ranks)
                        if r is not None:
                            ranks[tname] = r
        return findings

    def _axis_findings(self, ctx: ModuleContext, node: ast.Call,
                       meshes: Dict[str, frozenset],
                       tuples: Dict[str, ast.AST]) -> List[Finding]:
        """dp-axis typo check at one shard_map construction: every
        string axis named in in_specs/out_specs must be an axis of the
        (statically known) mesh being mapped over."""
        mesh_kw = next((kw.value for kw in node.keywords
                        if kw.arg == "mesh"), None)
        axes = (mesh_axes_of(mesh_kw, meshes)
                if mesh_kw is not None else None)
        if not axes:
            return []
        out: List[Finding] = []
        for kw in node.keywords:
            if kw.arg not in ("in_specs", "out_specs"):
                continue
            for name, spec_node in spec_axis_names(kw.value, tuples):
                if name not in axes:
                    out.append(ctx.finding(
                        self.rule_id, spec_node,
                        f"PartitionSpec names axis '{name}' absent from "
                        f"the mesh being mapped over (axes "
                        f"{sorted(axes)}) — shard_map raises at "
                        "dispatch time (the dp-axis typo class)"))
        return out

    @staticmethod
    def _is_shard_map(node: ast.Call) -> bool:
        return call_name(node).split(".")[-1] == "shard_map"

    @staticmethod
    def _specs_of(node: ast.Call, tuples: Dict[str, ast.AST]):
        for kw in node.keywords:
            if kw.arg == "in_specs":
                return spec_ranks(kw.value, tuples)
        return None


# ---------------------------------------------------------------------------
# TPU008 — unlocked module-level cache mutation
# ---------------------------------------------------------------------------

_MUTATORS = frozenset({"append", "add", "setdefault", "pop", "popitem",
                       "clear", "update", "remove", "discard", "extend",
                       "insert"})
_CONTAINER_CTORS = frozenset({"dict", "list", "set", "defaultdict",
                              "OrderedDict", "Counter", "deque"})


class ModuleCacheLockRule(Rule):
    """TPU008: module-level mutable caches mutated without the module's
    declared lock.

    Historical context: every process-wide cache in this engine is
    mutated from multiple threads by construction — the serving batcher
    coalesces requests from N REST threads, warmup runs on a background
    thread, refresh listeners run on the flush path. The dispatcher's
    executable cache and `parallel/policy.py`'s config/counters each
    pair their module/instance state with one lock and take it on every
    mutation; PR 5's review round still found the double-build race in
    `serving_mesh()` (two first callers caching distinct equal Meshes,
    forcing identity-keyed caches through a redundant corpus re-upload).
    This rule makes the convention checkable at the module level: a
    module-level mutable container mutated inside any function must hold
    a module-level lock while doing it — and a module with such caches
    and NO lock declared is itself a finding.
    """

    rule_id = "TPU008"
    summary = "module-level cache mutated outside the module's lock"

    def run(self, ctx: ModuleContext, index: ProjectIndex) -> List[Finding]:
        locks: Set[str] = set()
        containers: Set[str] = set()
        for stmt in ctx.tree.body:
            if not isinstance(stmt, (ast.Assign, ast.AnnAssign)):
                continue
            value = stmt.value
            if value is None:
                continue
            for name in assign_targets(stmt):
                if isinstance(value, ast.Call):
                    cname = call_name(value)
                    if cname.split(".")[-1] in ("Lock", "RLock"):
                        locks.add(name)
                    elif cname.split(".")[-1] in _CONTAINER_CTORS:
                        containers.add(name)
                elif isinstance(value, (ast.Dict, ast.List, ast.Set,
                                        ast.DictComp, ast.ListComp,
                                        ast.SetComp)):
                    containers.add(name)
        if not containers:
            return []
        findings: List[Finding] = []
        for fn in iter_functions(ctx.tree):
            # THIS function's own `global` declarations (nested functions
            # are analyzed separately — _body_statements stops at them,
            # so a helper's `global` can't un-shadow our local)
            declared_global = {
                n for s, _ in _body_statements(fn.body)
                if isinstance(s, ast.Global) for n in s.names}
            local_names: set = set()
            for stmt, _ in _body_statements(fn.body):
                # a local shadowing the module name is not the cache —
                # unless declared global
                local_names |= set(assign_targets(stmt)) - declared_global
                for node in _stmt_expressions(stmt):
                    target = self._mutation_target(node, ctx)
                    if target is None or target not in containers \
                            or target in local_names:
                        continue
                    if self._under_lock(ctx, node, locks):
                        continue
                    if locks:
                        lock_list = ", ".join(sorted(locks))
                        msg = (f"module-level cache {target!r} mutated "
                               f"without holding the module's lock "
                               f"({lock_list}) — serving threads, warmup "
                               "and refresh listeners all reach "
                               "module state concurrently")
                    else:
                        msg = (f"module-level cache {target!r} is mutated "
                               "from functions but the module declares "
                               "no lock — add a module-level "
                               "threading.Lock and take it on every "
                               "mutation")
                    findings.append(ctx.finding(self.rule_id, node, msg))
        return findings

    @staticmethod
    def _mutation_target(node: ast.AST,
                         ctx: ModuleContext) -> Optional[str]:
        if isinstance(node, ast.Subscript) \
                and isinstance(node.ctx, (ast.Store, ast.Del)):
            return base_name(node)
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr in _MUTATORS:
            return base_name(node.func.value)
        return None

    @staticmethod
    def _under_lock(ctx: ModuleContext, node: ast.AST,
                    locks: Set[str]) -> bool:
        if not locks:
            return False
        cur = ctx.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.With, ast.AsyncWith)):
                for item in cur.items:
                    for sub in ast.walk(item.context_expr):
                        if isinstance(sub, ast.Name) and sub.id in locks:
                            return True
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                break
            cur = ctx.parents.get(cur)
        return False


# ---------------------------------------------------------------------------
# TPU009 — blocking sync inside a lock-held critical section
# ---------------------------------------------------------------------------

_FUTURISH = re.compile(r"fut", re.IGNORECASE)


class LockedSyncRule(Rule):
    """TPU009: blocking syncs while holding a serving lock (the batcher
    lock / drain critical section).

    Historical context (PR 8): the continuous-batching rewrite's whole
    point is that the scheduler lock is held only for the UN-SYNCED
    device dispatch — device sync, `Future.result`, and d2h transfers
    happen at response-assembly time, outside the lock, so batch N's
    host work overlaps batch N+1's dispatch. A blocking sync inside a
    `with <lock>:` body silently re-serializes the pipeline: every
    request queued on that lock stalls behind one batch's device wait,
    which is exactly the closed-loop convoy the r06 p99/p50 = 6.2 gate
    failure measured. Fires on `block_until_ready()`, `.item()` on a
    device array, `.result()` on a future-named receiver, and bulk
    device→host transfers (`np.asarray` on a device array) lexically
    inside a with-block whose context manager is lock-named. Scoped to
    hot-path modules like TPU002.
    """

    rule_id = "TPU009"
    summary = "blocking sync while holding a serving lock"

    def run(self, ctx: ModuleContext, index: ProjectIndex) -> List[Finding]:
        if not ctx.hot_path:
            return []
        findings: List[Finding] = []
        np_mods, np_fns = numpy_aliases(ctx.tree)
        for fn in iter_functions(ctx.tree):
            taint = DeviceTaint(np_mods, np_fns)
            self._walk(fn.body, False, taint, ctx, findings)
        return findings

    def _walk(self, body, in_lock: bool, taint, ctx, findings) -> None:
        """Linear statement walk carrying the lock-held flag; taint
        observes statements in source order so device-array facts are
        current when a sync site is judged."""
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            if in_lock:
                for node in _stmt_expressions(stmt):
                    if not isinstance(node, ast.Call):
                        continue
                    msg = self._judge(node, taint)
                    if msg is not None:
                        findings.append(
                            ctx.finding(self.rule_id, node, msg))
            if isinstance(stmt, (ast.With, ast.AsyncWith)):
                self._walk(stmt.body,
                           in_lock or self._locks_a_lock(stmt), taint,
                           ctx, findings)
            elif isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
                self._walk(stmt.body, in_lock, taint, ctx, findings)
                self._walk(stmt.orelse, in_lock, taint, ctx, findings)
            elif isinstance(stmt, ast.If):
                self._walk(stmt.body, in_lock, taint, ctx, findings)
                self._walk(stmt.orelse, in_lock, taint, ctx, findings)
            elif isinstance(stmt, ast.Try):
                self._walk(stmt.body, in_lock, taint, ctx, findings)
                for h in stmt.handlers:
                    self._walk(h.body, in_lock, taint, ctx, findings)
                self._walk(stmt.orelse, in_lock, taint, ctx, findings)
                self._walk(stmt.finalbody, in_lock, taint, ctx, findings)
            taint.observe(stmt)

    @staticmethod
    def _locks_a_lock(stmt) -> bool:
        """`with self._run_lock:` / `with lock, other:` — any context
        manager whose dotted name's last component is lock-named. A
        Condition used as a context manager counts (it wraps its lock)."""
        for item in stmt.items:
            expr = item.context_expr
            if isinstance(expr, ast.Call):
                expr = expr.func
            name = dotted(expr) if isinstance(
                expr, (ast.Name, ast.Attribute)) else ""
            last = name.split(".")[-1].lower()
            if last.endswith("lock") or last.endswith("cond") \
                    or last.endswith("condition"):
                return True
        return False

    def _judge(self, node: ast.Call, taint) -> Optional[str]:
        if not isinstance(node.func, ast.Attribute):
            if isinstance(node.func, ast.Name) \
                    and node.func.id in taint.np_fn_converters \
                    and node.args \
                    and taint.expr_is_device(node.args[0]):
                return ("device→host transfer while holding a lock — "
                        "every request queued on this lock stalls behind "
                        "the sync; dispatch under the lock, land results "
                        "outside it at response-assembly time")
            return None
        attr = node.func.attr
        if attr == "block_until_ready":
            return ("block_until_ready while holding a lock serializes "
                    "the dispatch pipeline — sync outside the critical "
                    "section, at response-assembly time")
        if attr == "item" and taint.expr_is_device(node.func.value):
            return (".item() on a device array while holding a lock is a "
                    "blocking scalar pull inside the drain critical "
                    "section — land results outside the lock")
        if attr == "result" and _FUTURISH.search(dotted(node.func.value)):
            return ("Future.result() while holding a lock blocks the "
                    "scheduler — wait on futures outside the critical "
                    "section (the combining batcher's submit tail)")
        if call_name(node) in taint.host_converters and node.args \
                and taint.expr_is_device(node.args[0]):
            return ("device→host transfer while holding a lock — every "
                    "request queued on this lock stalls behind the sync; "
                    "dispatch under the lock, land results outside it at "
                    "response-assembly time")
        return None


class UnguardedFanoutRule(Rule):
    """TPU010: transport fan-outs that can hang on a silent drop.

    Historical context (PR 12): `cluster_node._query_phase` waited for
    `pending == 0` with NO timer while fanning QUERY-phase RPCs — one
    slow or dead data node hung the whole search accumulator forever
    (the deterministic transport drops messages silently, exactly like
    a real network partition; neither `on_response` nor `on_failure`
    ever fires). The same idiom had spread to the scroll, refresh, and
    replication fan-outs. The fix is serving/fanout.py's ScatterGather
    (per-item timers make completion structural); this rule keeps the
    idiom from growing back. Two patterns fire:

    * a `transport.send(...)` call site with no `on_failure` handler —
      a failed delivery is silently lost, so the caller's completion
      accounting can never see the error;
    * a function that fans out over `transport.send` and joins on a
      mutable pending-counter dict (`pending = {"count": len(...)}`
      ... `pending["count"] -= 1` ... `== 0`) without arming ANY
      scheduler timer (`schedule_in`/`schedule_at`) — the unbounded
      coordinator wait. Route the fan-out through
      `serving.fanout.ScatterGather` (or arm an explicit timeout).
    """

    rule_id = "TPU010"
    summary = "transport fan-out without failure handling or a timer"

    def run(self, ctx: ModuleContext, index: ProjectIndex) -> List[Finding]:
        findings: List[Finding] = []
        analyzed: Set[ast.AST] = set()
        for fn in iter_functions(ctx.tree):
            # analyze OUTERMOST functions whole (the pending-counter
            # idiom spans the nested response closures), skipping
            # functions already covered by an enclosing analysis
            cur = ctx.parents.get(fn)
            nested = False
            while cur is not None:
                if cur in analyzed:
                    nested = True
                    break
                cur = ctx.parents.get(cur)
            if nested:
                continue
            analyzed.add(fn)
            findings.extend(self._judge_function(fn, ctx))
        return findings

    @staticmethod
    def _is_transport_send(node: ast.Call) -> bool:
        if not (isinstance(node.func, ast.Attribute)
                and node.func.attr == "send"):
            return False
        return "transport" in dotted(node.func.value).lower()

    def _judge_function(self, fn, ctx: ModuleContext) -> List[Finding]:
        findings: List[Finding] = []
        sends: List[ast.Call] = []
        counters: Dict[str, ast.stmt] = {}   # var -> defining Assign
        decremented: Set[str] = set()
        zero_tested: Set[str] = set()
        has_timer = False

        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                if self._is_transport_send(node):
                    sends.append(node)
                    kws = {kw.arg for kw in node.keywords}
                    # positional form carries on_failure as the 6th arg
                    if "on_failure" not in kws and len(node.args) < 6:
                        findings.append(ctx.finding(
                            self.rule_id, node,
                            "transport.send without an on_failure "
                            "handler: a failed delivery is silently "
                            "lost and the fan-out's completion "
                            "accounting can never see it"))
                elif isinstance(node.func, ast.Attribute) \
                        and node.func.attr in ("schedule_in",
                                               "schedule_at"):
                    has_timer = True
            elif isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Dict) \
                    and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                # `pending = {"count": len(targets)}` — a fan-out join
                # counter seeded from the target-set size
                if any(isinstance(c, ast.Call)
                       and isinstance(c.func, ast.Name)
                       and c.func.id == "len"
                       for v in node.value.values if v is not None
                       for c in ast.walk(v)):
                    counters[node.targets[0].id] = node
            elif isinstance(node, ast.AugAssign) \
                    and isinstance(node.op, ast.Sub) \
                    and isinstance(node.target, ast.Subscript):
                name = base_name(node.target)
                if name:
                    decremented.add(name)
            elif isinstance(node, ast.Compare) \
                    and isinstance(node.left, ast.Subscript) \
                    and len(node.ops) == 1 \
                    and isinstance(node.ops[0], ast.Eq) \
                    and len(node.comparators) == 1 \
                    and isinstance(node.comparators[0], ast.Constant) \
                    and node.comparators[0].value == 0:
                name = base_name(node.left)
                if name:
                    zero_tested.add(name)

        if sends and not has_timer:
            for name, assign in counters.items():
                if name in decremented and name in zero_tested:
                    findings.append(ctx.finding(
                        self.rule_id, assign,
                        f"fan-out joins on pending counter [{name}] "
                        "with no scheduler timer: a silently dropped "
                        "response hangs the accumulator forever — "
                        "route through serving.fanout.ScatterGather "
                        "(per-item timers) or arm schedule_in as a "
                        "backstop"))
        return findings


# ---------------------------------------------------------------------------
# TPU011 — private per-segment extraction caches outside columnar/
# ---------------------------------------------------------------------------

_SEG_KEY_ATTRS = frozenset({"seg_id", "fingerprint"})
_SEG_KEY_NAMES = frozenset({"seg_id", "fingerprint", "fp"})
_DICT_READERS = frozenset({"get", "setdefault", "pop"})


class PrivateSegmentCacheRule(Rule):
    """TPU011: private per-segment extraction caches outside
    `elasticsearch_tpu/columnar/`.

    Historical context (PR 13): three subsystems each grew a private
    per-segment extraction cache — the vector store's per-refresh
    extract, `ops/aggs.py`'s `_seg_cache`, `ops/bm25.py`'s
    `_seg_cache` — with three sets of fingerprint semantics and three
    lifetimes. The duplication is why refresh paid an O(corpus) host
    memcpy per vector field and why every `Generation` pinned its own
    corpus-sized `host_vectors`. The columnar segment block store now
    owns per-(segment, field) extraction: blocks extract once, share
    across consumers, and evict with the segment. This rule keeps a
    fourth private cache from growing back: in hot-path modules outside
    `columnar/`, a PERSISTENT dict (an instance attribute on `self` or
    a module-level container) read or written with a key derived from
    `seg_id`/`fingerprint` — or whose very name says segment-cache — is
    a finding; read through `columnar.STORE` instead. Transient locals
    keyed by seg_id inside one pass are fine (they cache nothing across
    refreshes).
    """

    rule_id = "TPU011"
    summary = "private per-segment extraction cache outside columnar/"

    def run(self, ctx: ModuleContext, index: ProjectIndex) -> List[Finding]:
        if not ctx.hot_path or ctx.matches(ctx.config.seg_cache_allowed):
            return []
        module_containers: Set[str] = set()
        for stmt in ctx.tree.body:
            if isinstance(stmt, (ast.Assign, ast.AnnAssign)) \
                    and stmt.value is not None \
                    and isinstance(stmt.value, (ast.Dict, ast.DictComp)):
                module_containers |= set(assign_targets(stmt))
        findings: List[Finding] = []
        for node in ast.walk(ctx.tree):
            recv = key = None
            if isinstance(node, ast.Subscript):
                recv, key = node.value, node.slice
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _DICT_READERS and node.args:
                recv, key = node.func.value, node.args[0]
            if recv is None or not self._persistent(recv,
                                                    module_containers):
                continue
            if self._cache_named(recv):
                findings.append(ctx.finding(
                    self.rule_id, node,
                    f"private per-segment cache [{dotted(recv)}] — "
                    "per-(segment, field) extraction belongs in the "
                    "shared segment block store (columnar.STORE): one "
                    "extraction, every consumer, evicted with the "
                    "segment"))
            elif self._seg_keyed(key):
                findings.append(ctx.finding(
                    self.rule_id, node,
                    f"persistent dict [{dotted(recv)}] keyed by "
                    "seg_id/fingerprint is a private per-segment "
                    "extraction cache — read through columnar.STORE "
                    "(one extraction, every consumer, evicted with "
                    "the segment)"))
        return findings

    @staticmethod
    def _persistent(recv: ast.AST, module_containers: Set[str]) -> bool:
        """Instance state (`self.X`, any depth) or a module-level
        container — the shapes that outlive one pass. Plain locals are
        transient and stay out of scope."""
        if isinstance(recv, ast.Attribute):
            base = base_name(recv)
            return base == "self"
        if isinstance(recv, ast.Name):
            return recv.id in module_containers
        return False

    @staticmethod
    def _cache_named(recv: ast.AST) -> bool:
        name = dotted(recv).split(".")[-1].lower()
        return "seg" in name and "cache" in name

    @staticmethod
    def _seg_keyed(key: Optional[ast.AST]) -> bool:
        if key is None:
            return False
        for sub in ast.walk(key):
            if isinstance(sub, ast.Attribute) \
                    and sub.attr in _SEG_KEY_ATTRS:
                return True
            if isinstance(sub, ast.Name) and sub.id in _SEG_KEY_NAMES:
                return True
        return False


# ---------------------------------------------------------------------------
# TPU012 — wall-clock durations in hot modules & leaked telemetry spans
# ---------------------------------------------------------------------------

class TelemetryDisciplineRule(Rule):
    """TPU012: two telemetry bug classes from ISSUE 14's always-on
    observability layer.

    (a) `time.time()` in a HOT-PATH module. Telemetry made duration
    measurement ubiquitous (every request records queue-wait / dispatch /
    sync / took), and a wall-clock duration is wrong twice: NTP steps it
    (negative or wildly long "latencies" polluting the log2 histograms
    that now feed `_nodes/stats telemetry` p99), and it costs a VDSO
    gettimeofday on every hot-path call for less guarantee than
    `time.monotonic()`/`perf_counter()` give. Epoch TIMESTAMPS for
    display belong outside hot modules (Task.start_ms lives in
    node_admin for exactly this reason).

    (b) a live telemetry span opened via `begin_span(...)`/
    `start_span(...)` and bound to a local variable with NO structural
    close in the enclosing function — no `end_span(x)`, no
    `x.end()`/`x.finish()`, not a `with` item. A leaked span stays open
    forever: the tasks API reports it as the request's `current_span`
    after the request finished, and the trace ring shows a span with
    `dur_ns: null` that sums into nothing. The fix is the
    `telemetry.stage()` context manager, `end_span` in a `finally:`, or
    — for a stretch both of whose ends were read at existing sync
    points — `telemetry.stage_done(name, start_ns, end_ns)`, whose span
    is born closed and cannot leak.
    Spans stored onto objects (attributes, dict slots) are cross-thread
    handoffs the analysis cannot follow and stay out of scope, like
    TPU004's aliasing rules.
    """

    rule_id = "TPU012"
    summary = "wall-clock duration in hot module / leaked telemetry span"

    _SPAN_OPENERS = frozenset({"begin_span", "start_span"})

    def run(self, ctx: ModuleContext, index: ProjectIndex) -> List[Finding]:
        findings: List[Finding] = []
        if ctx.hot_path:
            self._wall_clock_findings(ctx, findings)
        analyzed: Set[ast.AST] = set()
        for fn in iter_functions(ctx.tree):
            # outermost functions whole: the open and its close may live
            # in different closures of one coordinator function (the
            # scatter-gather launch/resolve shape)
            cur = ctx.parents.get(fn)
            nested = False
            while cur is not None:
                if cur in analyzed:
                    nested = True
                    break
                cur = ctx.parents.get(cur)
            if nested:
                continue
            analyzed.add(fn)
            self._leaked_span_findings(fn, ctx, findings)
        return findings

    def _wall_clock_findings(self, ctx: ModuleContext,
                             findings: List[Finding]) -> None:
        time_mods: Set[str] = set()
        time_fns: Set[str] = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name == "time":
                        time_mods.add(alias.asname or "time")
            elif isinstance(node, ast.ImportFrom) and node.module == "time":
                for alias in node.names:
                    if alias.name == "time":
                        time_fns.add(alias.asname or "time")
        if not time_mods and not time_fns:
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call) or node.args or node.keywords:
                continue
            fn = node.func
            hit = (isinstance(fn, ast.Attribute) and fn.attr == "time"
                   and isinstance(fn.value, ast.Name)
                   and fn.value.id in time_mods) \
                or (isinstance(fn, ast.Name) and fn.id in time_fns)
            if hit:
                findings.append(ctx.finding(
                    self.rule_id, node,
                    "time.time() in a hot-path module: wall clocks step "
                    "under NTP, so durations built from them poison the "
                    "telemetry histograms — use time.monotonic() / "
                    "time.perf_counter_ns() for durations (epoch "
                    "timestamps belong outside hot modules)"))

    def _leaked_span_findings(self, fn, ctx: ModuleContext,
                              findings: List[Finding]) -> None:
        opens: Dict[str, ast.Call] = {}
        closed: Set[str] = set()
        with_items: Set[ast.AST] = set()
        for node in ast.walk(fn):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    with_items.add(item.context_expr)
            elif isinstance(node, ast.Assign) \
                    and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and isinstance(node.value, ast.Call) \
                    and isinstance(node.value.func, ast.Attribute) \
                    and node.value.func.attr in self._SPAN_OPENERS:
                opens[node.targets[0].id] = node.value
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute):
                if node.func.attr == "end_span" and node.args \
                        and isinstance(node.args[0], ast.Name):
                    closed.add(node.args[0].id)
                elif node.func.attr in ("end", "finish"):
                    base = base_name(node.func.value)
                    if base:
                        closed.add(base)
        for name, call in opens.items():
            if call in with_items or name in closed:
                continue
            findings.append(ctx.finding(
                self.rule_id, call,
                f"span [{name}] opened with "
                f"{call.func.attr}() but never closed in this function "
                "(leaked-span class): the tasks API keeps reporting it "
                "as current_span and the trace ring shows dur_ns: null "
                "— use the telemetry.stage() context manager, end_span "
                "in a finally:, or telemetry.stage_done(name, start_ns, "
                "end_ns), born closed"))


# ---------------------------------------------------------------------------
# TPU013 — hand-rolled quantization arithmetic outside quant/
# ---------------------------------------------------------------------------

_ROUND_NAMES = frozenset({"round", "rint"})


class HandRolledQuantRule(Rule):
    """TPU013: quantize/dequantize arithmetic outside the vector codec
    registry (`elasticsearch_tpu/quant/`).

    Historical context (ISSUE 15): by PR 14 the int8 recipe existed in
    four hand-rolled copies — `ops/quantization` (the nominal owner),
    the binned Pallas kernel's in-trace query quantization, the host
    VNNI mirror's packer, and the bench harness's jit — and the int4 /
    binary rungs would have added four more each. A recipe drift between
    any pair breaks byte parity between host twins and device kernels,
    which the two-phase rescore contract depends on. The codec registry
    (`quant/codec.py`) now owns every encode/decode, with np+jnp twins
    pinned byte-identical by test; this rule keeps a fifth copy from
    growing back. Two patterns fire outside `quant/`:

    * scale-divide-round-clip — a `clip(...)` call whose first argument
      contains a `round`/`rint` of a division: the symmetric scalar
      quantization idiom (`clip(round(x / scale), lo, hi)`), however the
      calls are spelled (np/jnp/method form);
    * sign-bit packing — `packbits(...)`, or a left-shift whose left
      operand derives from a sign comparison against zero
      (`(x >= 0) << j`): the binary-encoding idiom;
    * nibble-plane packing — a bitwise-or of a `<< 4` where the
      expression carries array evidence (an `.astype(...)` cast or a
      step-2 plane slice like `q[:, 0::2]`): the int4 token-block
      idiom `lo | (hi << 4)` that `quant/tokens.py` owns for
      `rank_vectors` fields. Scalar nibble pairs built from plain ints
      (the Uid `_id` encoding) carry neither signal and stay clean.

    Route through `quant.codec.get(name).encode_np/encode_jnp` or
    `quant.tokens.encode_tokens` (or the codec helpers for in-kernel
    unpack) instead.
    """

    rule_id = "TPU013"
    summary = "hand-rolled quantization arithmetic outside quant/"

    def run(self, ctx: ModuleContext, index: ProjectIndex) -> List[Finding]:
        if ctx.matches(ctx.config.quant_allowed):
            return []
        findings: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call):
                name = call_name(node).split(".")[-1]
                if name == "clip" and node.args \
                        and self._has_round_of_div(node.args[0]):
                    findings.append(ctx.finding(
                        self.rule_id, node,
                        "scale-divide-round-clip quantization outside "
                        "elasticsearch_tpu/quant/ — the codec registry "
                        "owns every encoding recipe (quant.codec.get("
                        "...).encode_np / encode_jnp); a drifted copy "
                        "breaks host-twin/device byte parity"))
                elif name == "packbits":
                    findings.append(ctx.finding(
                        self.rule_id, node,
                        "sign-bit packing outside elasticsearch_tpu/"
                        "quant/ — the binary codec owns the bit layout "
                        "(quant.codec.get('binary') / "
                        "pack_sign_bits_jnp)"))
            elif isinstance(node, ast.BinOp) \
                    and isinstance(node.op, ast.LShift) \
                    and self._has_sign_compare(node.left):
                findings.append(ctx.finding(
                    self.rule_id, node,
                    "sign-bit packing ((x >= 0) << ...) outside "
                    "elasticsearch_tpu/quant/ — the binary codec owns "
                    "the bit layout (quant.codec.get('binary') / "
                    "pack_sign_bits_jnp)"))
            elif isinstance(node, ast.BinOp) \
                    and isinstance(node.op, ast.BitOr) \
                    and self._is_nibble_pack(node):
                findings.append(ctx.finding(
                    self.rule_id, node,
                    "nibble-plane packing (lo | (hi << 4) on array "
                    "data) outside elasticsearch_tpu/quant/ — "
                    "quant.tokens.encode_tokens owns the int4 "
                    "token-block layout; a drifted plane order breaks "
                    "the fused MaxSim kernel's even/odd dim convention"))
        return findings

    @staticmethod
    def _has_round_of_div(node: ast.AST) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call) \
                    and call_name(sub).split(".")[-1] in _ROUND_NAMES \
                    and any(isinstance(inner, ast.BinOp)
                            and isinstance(inner.op, ast.Div)
                            for arg in sub.args
                            for inner in ast.walk(arg)):
                return True
        return False

    @staticmethod
    def _is_nibble_pack(node: ast.BinOp) -> bool:
        """`x | (y << 4)` (either order) with array evidence somewhere
        in the expression: an `.astype(...)` call, or an extended slice
        whose step is the literal 2 (the `q[:, 0::2]` plane split).
        Plain-int nibble pairs (`(b1 << 4) | b2` in the Uid encoder)
        carry neither signal."""
        shift = None
        for side in (node.left, node.right):
            if isinstance(side, ast.BinOp) \
                    and isinstance(side.op, ast.LShift) \
                    and isinstance(side.right, ast.Constant) \
                    and side.right.value == 4:
                shift = side
        if shift is None:
            return False
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call) \
                    and isinstance(sub.func, ast.Attribute) \
                    and sub.func.attr == "astype":
                return True
            if isinstance(sub, ast.Slice) and sub.step is not None \
                    and isinstance(sub.step, ast.Constant) \
                    and sub.step.value == 2:
                return True
        return False

    @staticmethod
    def _has_sign_compare(node: ast.AST) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Compare) and len(sub.ops) == 1 \
                    and isinstance(sub.ops[0], (ast.GtE, ast.Lt)) \
                    and len(sub.comparators) == 1 \
                    and isinstance(sub.comparators[0], ast.Constant) \
                    and sub.comparators[0].value == 0:
                return True
        return False


# ---------------------------------------------------------------------------
# TPU014 — durability discipline: verify content blobs, don't mutate
# sealed-generation state outside its owners
# ---------------------------------------------------------------------------

class DurabilityRule(Rule):
    """TPU014: durable-elasticity discipline (ISSUE 17).

    Every byte in the content-addressed areas — repository `blobs/` and
    the peer-recovery block cache — is named by its sha256, and every
    consumer between the wire and an `Engine` re-verifies it: a torn
    upload, a bit-rotted file, or a truncated chunk must surface as a
    retryable digest failure, never as a silently corrupt commit the
    shard then serves. Likewise the sealed-generation trio the commit
    point captures (`segments` list, `deleted_rows`, `version_map`) is
    mutated ONLY by its owners — the engine (indexing/merge), the
    segments machinery, and the recovery assembler that rebuilds commits
    byte-identically; a mutation anywhere else desyncs the live state
    from the durable one, and the divergence only shows up after the
    next restore. Two patterns fire:

    * a `read_blob(...)` call whose key names the content-addressed
      `blobs/` area, in a function with no digest-verification call
      (sha256/digest/verify/crc32 in the callee name) — size probes and
      "just a peek" reads included: route through the repository's
      verified `get_bytes`, or verify inline;
    * assignment to / deletion of / a mutating method call on an
      attribute named `segments`, `deleted_rows` or `version_map`
      outside the owning modules (`durability_allowed` globs).
    """

    rule_id = "TPU014"
    summary = ("unverified content-blob read, or sealed-generation "
               "state mutated outside its owners")

    _SEALED = frozenset({"segments", "deleted_rows", "version_map"})
    _MUTATORS = frozenset({"append", "add", "update", "pop", "popitem",
                           "clear", "setdefault", "discard", "remove",
                           "extend", "insert"})
    _VERIFY_TOKENS = ("sha256", "digest", "verify", "crc32")

    def run(self, ctx: ModuleContext, index: ProjectIndex) -> List[Finding]:
        findings = self._unverified_blob_reads(ctx)
        if not ctx.matches(ctx.config.durability_allowed):
            findings.extend(self._sealed_mutations(ctx))
        return findings

    # -- unverified reads of content-addressed blobs ------------------------

    def _unverified_blob_reads(self, ctx: ModuleContext) -> List[Finding]:
        findings: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call)
                    and call_name(node).split(".")[-1] == "read_blob"
                    and node.args
                    and self._names_blob_area(node.args[0])):
                continue
            if self._scope_verifies(ctx, node):
                continue
            findings.append(ctx.finding(
                self.rule_id, node,
                "content-addressed blob read without digest "
                "verification — a torn or bit-rotted blob flows "
                "straight into the caller; route through the "
                "repository's get_bytes (sha256-verified, raises "
                "RepositoryError on mismatch) or verify the digest "
                "in this function"))
        return findings

    @staticmethod
    def _names_blob_area(arg: ast.AST) -> bool:
        """The key expression mentions the content-addressed `blobs/`
        prefix (plain string or any piece of an f-string)."""
        for sub in ast.walk(arg):
            if isinstance(sub, ast.Constant) and isinstance(sub.value, str) \
                    and "blobs/" in sub.value:
                return True
        return False

    def _scope_verifies(self, ctx: ModuleContext, node: ast.AST) -> bool:
        """Does the enclosing function (or the module, for top-level
        code) CALL anything that verifies bytes? Mentioning a digest is
        not enough — only a sha256/…/verify call counts as evidence."""
        scope: ast.AST = ctx.tree
        cur = ctx.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scope = cur
                break
            cur = ctx.parents.get(cur)
        for sub in ast.walk(scope):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and sub is not scope:
                continue
            if isinstance(sub, ast.Call):
                callee = call_name(sub).split(".")[-1].lower()
                if any(tok in callee for tok in self._VERIFY_TOKENS):
                    return True
        return False

    # -- sealed-generation state mutated outside its owners -----------------

    def _sealed_mutations(self, ctx: ModuleContext) -> List[Finding]:
        findings: List[Finding] = []

        def sealed_attr(expr: ast.AST):
            """The sealed attribute an expression reaches through (e.g.
            `eng.deleted_rows[k]` or `eng.version_map`), if any."""
            for sub in ast.walk(expr):
                if isinstance(sub, ast.Attribute) \
                        and sub.attr in self._SEALED:
                    return sub.attr
            return None

        def fire(node: ast.AST, attr: str, how: str) -> None:
            findings.append(ctx.finding(
                self.rule_id, node,
                f"{how} of sealed-generation state [.{attr}] outside "
                "its owners (index/engine.py, segments/, recovery/) — "
                "the commit point no longer matches the live state, "
                "and the divergence surfaces only after the next "
                "restore; go through the engine's API instead"))

        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for t in targets:
                    if isinstance(t, ast.Attribute) \
                            and t.attr in self._SEALED:
                        fire(node, t.attr, "assignment")
                    elif isinstance(t, ast.Subscript):
                        attr = sealed_attr(t.value)
                        if attr is not None:
                            fire(node, attr, "item assignment")
            elif isinstance(node, ast.Delete):
                for t in node.targets:
                    attr = sealed_attr(t)
                    if attr is not None:
                        fire(node, attr, "deletion")
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in self._MUTATORS:
                attr = sealed_attr(node.func.value)
                if attr is not None:
                    fire(node, attr, f"{node.func.attr}() mutation")
        return findings


class EventLoopBlockingRule(Rule):
    """TPU015: blocking IO / sleeps lexically on an asyncio event loop.

    The multi-process cluster serves ALL of a node's RPCs on one asyncio
    loop (`transport/tcp.py`): a single `time.sleep` or synchronous
    socket/file/subprocess call inside an `async def` — or inside a
    callback handed to the loop's own scheduling primitives
    (`call_soon`/`call_later`/`call_at`) — parks every in-flight
    request, response, and keepalive on that node. The symptom is a
    cross-node p99 spike with no device work to blame; the first
    real-socket bench run surfaced exactly this shape. Blocking work
    belongs on a worker thread (`run_in_executor`, or the recovery tier's
    upload pools).

    Scope is `async_actor_globs` (transport/, cluster/) and the rule is
    LEXICAL: it only judges code that demonstrably runs on the loop.
    Plain sync helpers in the same files — thread-loop bodies, CLI
    entry points, `AsyncioScheduler.schedule` callbacks (which run
    engine work by design, on the sim queue and loop alike) — are out
    of scope: being in the file is not evidence of running on the loop.
    """

    rule_id = "TPU015"

    _BLOCKING = {
        "time.sleep": "parks the whole event loop for the duration",
        "socket.create_connection": "synchronous connect stalls the loop",
        "subprocess.run": "waiting on a child process stalls the loop",
        "subprocess.check_output":
            "waiting on a child process stalls the loop",
        "subprocess.check_call":
            "waiting on a child process stalls the loop",
        "urllib.request.urlopen": "synchronous HTTP stalls the loop",
    }
    _BARE = {"open": "synchronous file IO stalls the loop"}
    _LOOP_SCHEDULERS = {"call_soon", "call_soon_threadsafe",
                        "call_later", "call_at"}

    def run(self, ctx: ModuleContext, index: ProjectIndex) -> List[Finding]:
        if not ctx.matches(getattr(ctx.config, "async_actor_globs", ())):
            return []
        # local sync defs by name, to resolve `loop.call_soon(pump)`
        local_defs: Dict[str, ast.FunctionDef] = {}
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.FunctionDef):
                local_defs.setdefault(node.name, node)
        targets: List[Tuple[ast.AST, str]] = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.AsyncFunctionDef):
                targets.append((node, f"async handler [{node.name}]"))
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in self._LOOP_SCHEDULERS:
                args = list(node.args) + [kw.value for kw in node.keywords]
                for arg in args:
                    if isinstance(arg, ast.Lambda):
                        targets.append(
                            (arg, f"{node.func.attr}() callback"))
                    elif isinstance(arg, ast.Name) \
                            and arg.id in local_defs:
                        targets.append((local_defs[arg.id],
                                        f"{node.func.attr}() callback "
                                        f"[{arg.id}]"))
        findings: List[Finding] = []
        seen: Set[int] = set()
        for fn, how in targets:
            if id(fn) in seen:
                continue
            seen.add(id(fn))
            findings.extend(self._scan(ctx, fn, how))
        findings.sort(key=lambda f: (f.line, f.col))
        return findings

    def _scan(self, ctx: ModuleContext, fn: ast.AST,
              how: str) -> List[Finding]:
        # lexically inside THIS function only: nested defs get their own
        # judgment (a nested sync def may run on a thread)
        if isinstance(fn, ast.Lambda):
            exprs: List[ast.AST] = list(ast.walk(fn.body))
        else:
            exprs = []
            for stmt, _ in _body_statements(fn.body):
                exprs.extend(_stmt_expressions(stmt))
        out: List[Finding] = []
        for node in exprs:
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            why = self._BLOCKING.get(name) or self._BARE.get(name)
            if why is None:
                continue
            out.append(ctx.finding(
                self.rule_id, node,
                f"blocking call [{name}] inside {how} — {why}; every "
                "in-flight RPC and keepalive on this node's loop stalls "
                "behind it. Move it to a worker thread "
                "(run_in_executor) or make it async"))
        return out


ALL_RULES: List[Rule] = [
    RawJitRule(), HostSyncRule(), IdKeyedCacheRule(), ReadAfterDonateRule(),
    UnscrubbedCacheKeyRule(), ScopedX64Rule(), SpecRankRule(),
    ModuleCacheLockRule(), LockedSyncRule(), UnguardedFanoutRule(),
    PrivateSegmentCacheRule(), TelemetryDisciplineRule(),
    HandRolledQuantRule(), DurabilityRule(), EventLoopBlockingRule(),
]
