"""Shape-bucketed kernel dispatch: every device program is pre-compiled.

A round-6 CPU capture showed the serving path dominated by XLA recompilation,
not arithmetic: batch=4 ran at 149 ms p50 while batch=16 ran at 31.6 ms,
and both closed-loop rows blew the p99 <= 3x p50 gate — every distinct
(batch, k, corpus) shape hit `jax.jit`'s tracing path in the serving hot
loop. LLM inference stacks solved this problem years ago (Orca's
iteration-level batching, vLLM's bucketed serving): the set of compiled
shapes must be SMALL and CLOSED, and steady-state traffic must only ever
execute programs compiled before it arrived. This module is that layer
for the search engine — every device kernel (`ops/knn.py`, `ops/knn_ivf
.py`, `ops/bm25.py`, `ops/topk.py`, `ops/pallas_knn_binned.py`) routes
through one dispatcher that owns:

* the global bucketing policy — pow-2 query-batch buckets, k rounded up
  to a fixed ladder, corpora already tile-padded at build time — so the
  shape universe per kernel is a grid, not a stream;
* a keyed executable cache over `jax.jit(...).lower(...).compile()` AOT
  artifacts, with `donate_argnums` on score-board/accumulator buffers
  (the caller allocates them fresh per call; XLA reuses their HBM for
  the outputs); `configure_compile_cache` owns JAX's persistent
  compilation cache so node restarts don't re-pay compiles;
* warmup — `warmup()` pre-compiles a declared bucket grid on a
  background thread when an index opens / a batcher starts, so the
  first real query of any bucket finds its program ready;
* observability — global and per-bucket hit/miss/compile-time counters
  (`stats()`), surfaced in `_nodes/stats indices.dispatch` and, via the
  thread-local event trace, in `profile.dispatch`.

Composability rule: a dispatched kernel called with TRACERS (i.e. from
inside another jit/scan) falls through to the raw function and
inlines into the enclosing trace — the dispatcher only manages
OUTERMOST calls on concrete arrays.

Closed-grid enforcement: each kernel registers a grid predicate over its
(static args, arg shapes). A cache miss whose key falls outside the grid
counts `out_of_grid_compiles` (and raises under strict mode — the tier-1
recompile-regression test in tests/test_dispatch.py runs strict), so a
future caller that forgets to pad to a bucket fails CI instead of
silently reintroducing shape churn.
"""

from __future__ import annotations

import logging
import os
import threading
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

logger = logging.getLogger("elasticsearch_tpu.dispatch")

# ---------------------------------------------------------------------------
# Bucketing policy
# ---------------------------------------------------------------------------

# k rounds UP this ladder (then clamps to the corpus/slot count): lax.top_k
# at a larger k returns a superset in identical order, so slicing the first
# k_req columns is byte-identical to running at k_req — one compile serves
# every k in the gap.
K_BUCKETS = (1, 4, 10, 16, 32, 64, 100, 128, 256, 512, 1024)

# query batches pad to pow-2 up to this; beyond it, to multiples of it
# (a 4096-query dispatch is a bulk job, not a serving shape)
MAX_QUERY_BUCKET = 2048


def bucket_queries(n: int) -> int:
    """Query-count bucket (the vectors/store + bm25 pad policy,
    centralized): 1, 8, 16, 32, ..., MAX, then multiples of MAX.

    2 and 4 are DEAD RUNGS on purpose — measured on the r06 CPU floor,
    XLA-CPU's dot_general hits a pathological small-M gemm path for
    M in {2..7} ([4, 131072] scores ran ~350 ms vs ~100 ms at M=8 and
    ~40 ms at M=1: the literal batch=4-slower-than-batch=16 anomaly,
    with zero recompiles). Padding 2..7 up to 8 rides the fast path
    everywhere; on TPU the MXU pads sublanes to 8 regardless, so the
    rung costs nothing there. Batch 1 keeps its own bucket — the
    single-query latency path beats the 8-bucket on every backend."""
    if n <= 1:
        return 1
    if n <= 8:
        return 8
    if n > MAX_QUERY_BUCKET:
        return -(-n // MAX_QUERY_BUCKET) * MAX_QUERY_BUCKET
    p = 8
    while p < n:
        p *= 2
    return p


def bucket_k(k: int, limit: Optional[int] = None) -> int:
    """Round k up the K_BUCKETS ladder, clamped to `limit` (corpus rows /
    live slots — lax.top_k requires k <= N). A clamped value is inside
    the grid by definition: it is a function of the corpus, not the
    request stream."""
    k = max(int(k), 1)
    kb = K_BUCKETS[-1]
    for b in K_BUCKETS:
        if b >= k:
            kb = b
            break
    else:
        # beyond the ladder: next multiple of the last rung
        kb = -(-k // K_BUCKETS[-1]) * K_BUCKETS[-1]
    if limit is not None:
        kb = min(kb, int(limit))
        kb = max(kb, min(k, int(limit)))
    return kb


def is_query_bucket(n: int) -> bool:
    return n >= 1 and n == bucket_queries(n)


# generational device segments (elasticsearch_tpu/segments/): sealed
# generations pad their row count to this pow-2 ladder so the per-
# generation search kernel (`segments.knn`) compiles over a closed,
# bounded shape universe — refresh deltas of any size reuse a handful
# of programs. The ladder tops out at MAX_GEN_ROW_BUCKET (merged base
# generations in the millions of rows would waste up to 2x HBM on pow-2
# padding); beyond it, multiples of the cap keep the universe closed.
GEN_ROW_BUCKET_MIN = 128          # one lane tile (ops/knn.LANE)
MAX_GEN_ROW_BUCKET = 1 << 20


def bucket_gen_rows(n: int) -> int:
    """Row bucket a device generation pads to: pow-2 from
    GEN_ROW_BUCKET_MIN up to MAX_GEN_ROW_BUCKET, then multiples of the
    cap."""
    n = max(int(n), 1)
    if n > MAX_GEN_ROW_BUCKET:
        return -(-n // MAX_GEN_ROW_BUCKET) * MAX_GEN_ROW_BUCKET
    b = GEN_ROW_BUCKET_MIN
    while b < n:
        b *= 2
    return b


def in_gen_row_grid(n: int) -> bool:
    """True when a generation row count sits on the sealed-generation
    ladder (the `segments.knn` grid predicate)."""
    return n >= GEN_ROW_BUCKET_MIN and n == bucket_gen_rows(n)


def bucket_headroom(n: int, max_batch: Optional[int] = None) -> int:
    """Free rows left in `n` requests' dispatch bucket — the continuous
    batcher's top-up budget. A batch of n dispatches padded to
    `bucket_queries(n)` rows either way, so admitting up to this many
    late arrivals into the forming batch costs ZERO recompiles (the
    compiled shape is the bucket) and zero extra padding work. `max_batch`
    additionally clamps to a caller's batch ceiling."""
    bucket = bucket_queries(n)
    if max_batch is not None:
        bucket = min(bucket, int(max_batch))
    return max(bucket - n, 0)


def backend_platform() -> str:
    """Platform of the default jax backend ("tpu", "cpu", ...), as JAX
    reports it. Whatever JAX raises when it finds no backend propagates:
    a failed probe must never read as "this is the CPU"."""
    import jax
    return jax.devices()[0].platform


def is_accelerator_backend() -> bool:
    """True when the default jax backend is a TPU — the ONE probe behind
    every TPU-class policy: whether compiles stall serving (warmup),
    whether the binned Pallas kernel serves, and how deep the hybrid
    executor pipelines."""
    return backend_platform() == "tpu"


def pallas_interpret(interpret: Optional[bool] = None) -> bool:
    """Resolve a Pallas entry's `interpret=None`: interpret mode only
    when the backend IS the CPU (the tests — Mosaic cannot compile
    there), never because a probe failed. Any other backend compiles
    the kernel for real and fails loudly if it cannot."""
    if interpret is not None:
        return bool(interpret)
    return backend_platform() == "cpu"


def in_k_grid(k: int, limit: Optional[int] = None) -> bool:
    """True when k sits on the ladder or equals the clamp limit."""
    return k in K_BUCKETS or (limit is not None and k == int(limit)) \
        or (k > K_BUCKETS[-1] and k % K_BUCKETS[-1] == 0)


# ---------------------------------------------------------------------------
# Persistent compilation cache
# ---------------------------------------------------------------------------

# `<checkout>/.jax_cache` (git-ignored): a fixed path, because the path is
# part of what a restart must find again — never under a data directory
# (tests and benches run nodes on mkdtemp paths), a pid or a time.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

def configure_compile_cache() -> str:
    """The one owner of JAX's persistent compilation cache, so a node
    restart re-loads compiled executables from disk instead of re-paying
    XLA compiles. Where `JAX_COMPILATION_CACHE_DIR` is set JAX reads it
    itself and nothing is set in code; otherwise the cache lives at
    `DEFAULT_COMPILE_CACHE_DIR`. Either way every executable is kept
    (serving kernels are small and quick to compile; the defaults would
    skip them). The server calls this before the first backend touch.
    Returns the directory."""
    import jax
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_COMPILE_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


# ---------------------------------------------------------------------------
# Dispatcher
# ---------------------------------------------------------------------------

class DispatchGridEscape(RuntimeError):
    """A kernel compiled for a shape outside its declared bucket grid."""


class _Kernel:
    __slots__ = ("name", "fn", "static_argnames", "donate_argnums",
                 "grid_check", "jitted", "x64")

    def __init__(self, name, fn, static_argnames, donate_argnums, grid_check,
                 x64=False):
        self.name = name
        self.fn = fn
        self.static_argnames = tuple(static_argnames)
        self.donate_argnums = tuple(donate_argnums)
        self.grid_check = grid_check
        self.jitted = None  # built lazily (jax import cost)
        # x64 kernels trace AND execute under jax.enable_x64:
        # the process default stays 32-bit (the serving kernels are f32 by
        # design), but 64-bit accumulator kernels (aggs.*: int64 counts,
        # f64 sums — date millis don't fit int32/f32) need the scoped flag
        # both at lower() time (canonicalization runs during tracing) and
        # at call time (the AOT executable's arg-aval check canonicalizes
        # host numpy inputs against the active config).
        self.x64 = bool(x64)


def _named(kernel: "_Kernel"):
    """The kernel's function under `jax.named_scope("es.<kernel name>")`:
    every device operation of the program then carries its dispatch
    kernel's name in the profiler trace's operation metadata, not only
    its HLO line."""
    import functools

    import jax

    fn, scope = kernel.fn, "es." + kernel.name

    @functools.wraps(fn)
    def named(*args, **kwargs):
        with jax.named_scope(scope):
            return fn(*args, **kwargs)

    return named


def _x64_scope(enabled: bool):
    if not enabled:
        import contextlib
        return contextlib.nullcontext()
    import jax
    return jax.enable_x64(True)


class _Entry:
    __slots__ = ("compiled", "key_str", "hits", "compile_nanos")

    def __init__(self, compiled, key_str, compile_nanos):
        self.compiled = compiled
        self.key_str = key_str
        self.hits = 0
        self.compile_nanos = compile_nanos


class _PinnedLeaf:
    """Identity key for a non-primitive python leaf in a cache signature.

    Keying on bare `id(x)` is the PR 5 mesh-cache bug class (tpulint
    TPU003): addresses recycle after GC, so a dead object's cache entries
    alias a new object at the same address. The wrapper compares by
    identity but HOLDS the referent — while the cache entry lives, the
    address cannot be reused, so aliasing is impossible by construction.
    (Identity, not value, semantics on purpose: an executable compiled
    against one leaf object must not serve a merely-equal other.)
    """

    __slots__ = ("obj",)

    def __init__(self, obj):
        self.obj = obj

    def __hash__(self):
        # id() is safe HERE precisely because self.obj is a strong
        # reference: the address is pinned for this wrapper's lifetime
        return id(self.obj)

    def __eq__(self, other):
        return isinstance(other, _PinnedLeaf) and self.obj is other.obj


def _leaf_sig(x) -> Any:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        # an AOT executable bakes its input shardings at lower() time, so
        # a mesh-sharded array and a single-device array of identical
        # shape must key to DIFFERENT executables. Only NamedShardings
        # (mesh layouts) join the key: host numpy, single-device arrays,
        # and sharding-less ShapeDtypeStructs all normalize to None so
        # warmup specs keep hitting the entries serving calls use.
        sharding = getattr(x, "sharding", None)
        try:
            from jax.sharding import NamedSharding
            if not isinstance(sharding, NamedSharding):
                sharding = None
        except Exception:
            sharding = None
        return (tuple(shape), str(dtype), sharding)
    return ("py", type(x).__name__, x if isinstance(
        x, (int, float, bool, str, bytes, type(None))) else _PinnedLeaf(x))


class Dispatcher:
    """Keyed AOT-executable cache + bucket grid + counters (one process-
    wide instance, `dispatch.DISPATCH`). Thread-safe; compiles serialize
    per key so concurrent first-callers of one bucket pay one compile."""

    def __init__(self, strict: Optional[bool] = None):
        self._kernels: Dict[str, _Kernel] = {}
        self._cache: Dict[Any, _Entry] = {}
        self._lock = threading.Lock()
        self._compile_locks: Dict[Any, threading.Lock] = {}
        self.strict = (os.environ.get("ES_TPU_DISPATCH_STRICT", "") == "1"
                       if strict is None else strict)
        self._counters = {"hits": 0, "misses": 0, "compiles": 0,
                          "compile_nanos": 0, "out_of_grid_compiles": 0,
                          "warmup_compiles": 0, "inline_calls": 0,
                          "async_calls": 0}
        # compiles in flight right now (a gauge, not reset with the
        # counters): with `compiles` it tells a caller when the warmup
        # grid has really finished — a long compile moves no counter
        self._compiling = 0
        self._bucket: Dict[str, Dict[str, int]] = {}
        self._trace = threading.local()

    # ------------------------------------------------------------ registry
    def register(self, name: str, fn: Callable, *,
                 static_argnames: Sequence[str] = (),
                 donate_argnums: Sequence[int] = (),
                 grid_check: Optional[Callable[..., bool]] = None,
                 x64: bool = False) -> None:
        """Register a raw (un-jitted) kernel. `grid_check(statics, sigs)`
        receives the static kwargs dict and the flat arg signature list
        [(shape, dtype) | py-leaf ...]; return False to flag the compile
        as outside the declared grid. `x64` kernels trace and execute
        under the scoped jax enable_x64 flag (64-bit accumulators)."""
        with self._lock:
            self._kernels[name] = _Kernel(name, fn, static_argnames,
                                          donate_argnums, grid_check,
                                          x64=x64)

    def kernels(self) -> List[str]:
        return sorted(self._kernels)

    # ------------------------------------------------------------- tracing
    def record_events(self, on: bool) -> None:
        """Enable/disable the thread-local per-call event trace (the
        profile.dispatch feed). Events: {kernel, bucket, hit, compile_ms}."""
        self._trace.events = [] if on else None

    def drain_events(self) -> List[dict]:
        events = getattr(self._trace, "events", None)
        if events is None:
            return []
        self._trace.events = []
        return events

    def events_enabled(self) -> bool:
        """Is THIS thread currently recording a dispatch trace?"""
        return getattr(self._trace, "events", None) is not None

    def event_count(self) -> int:
        events = getattr(self._trace, "events", None)
        return 0 if events is None else len(events)

    def annotate_events(self, since: int, **fields) -> None:
        """Tag events appended after index `since` on THIS thread's
        trace. The combining batcher uses this to label a coalesced
        batch's dispatches (`coalesced_batch: N`): the runner thread
        executes device work on behalf of N requests, and without the
        tag a profiled leader's trace silently claims the followers'
        dispatches as its own."""
        events = getattr(self._trace, "events", None)
        if events is None:
            return
        for e in events[since:]:
            e.update(fields)

    def _event(self, kernel: str, key_str: str, hit: bool,
               compile_nanos: int) -> None:
        events = getattr(self._trace, "events", None)
        if events is not None:
            events.append({"kernel": kernel, "bucket": key_str,
                           "cache": "hit" if hit else "miss",
                           "compile_ms": round(compile_nanos / 1e6, 3)})

    # ---------------------------------------------------------------- call
    def call(self, name: str, *args, **static_kwargs):
        """Execute `name` on concrete arrays through the AOT cache.

        Inside an enclosing trace (any arg is a jax Tracer) the raw
        function inlines instead — the dispatcher manages only outermost
        dispatches."""
        import jax

        kernel = self._kernels[name]
        # one flatten serves both the tracer check and the cache key —
        # this runs on every steady-state dispatch
        leaves, treedef = jax.tree_util.tree_flatten(args)
        if any(isinstance(leaf, jax.core.Tracer) for leaf in leaves):
            with self._lock:
                self._counters["inline_calls"] += 1
            return kernel.fn(*args, **static_kwargs)
        sig = (treedef, tuple(_leaf_sig(x) for x in leaves))
        entry, key_str, compiled_now, compile_nanos = self._get_entry(
            kernel, args, static_kwargs, warmup=False, sig=sig)
        self._event(name, key_str, not compiled_now, compile_nanos)
        with _x64_scope(kernel.x64):
            return entry.compiled(*args)

    def note_async(self, n: int = 1) -> None:
        """Count `n` dispatches whose device sync was deferred to
        response-assembly time (the pipelined serving path). The handle
        PRODUCER calls this when it hands back un-synced arrays —
        `vectors/store._dispatch_many` for the exhaustive kNN path — so
        `_nodes/stats indices.dispatch` `async_calls` honestly reports
        how much of the serving load actually pipelines, including
        dispatches that go through higher-level wrappers rather than
        `call_async` itself."""
        with self._lock:
            self._counters["async_calls"] += n

    def call_async(self, name: str, *args, **static_kwargs):
        """`call`, with the no-sync contract made explicit (and counted).

        JAX dispatch is asynchronous on every backend: the returned
        arrays are futures whose values materialize when the host first
        reads them (`np.asarray` / `block_until_ready`). `call` already
        returns them un-synced — this entry exists for callers built
        around that fact (the continuous batcher's pipelined dispatch
        stage): it promises the caller launches work and DEFERS the sync
        to response-assembly time, letting batch N's host hydrate overlap
        batch N+1's device dispatch. Every read of a result is a round
        trip to the device and a hand-over of the interpreter lock, so a
        kernel whose result is read on the host returns ONE array (the
        served kNN board, `ops/topk.pack_board`) and its caller reads it
        once. Feeds the `async_calls` counter
        (as does `note_async` for wrapped dispatches)."""
        self.note_async()
        return self.call(name, *args, **static_kwargs)

    def _signature(self, args) -> Tuple[Any, Tuple]:
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(args)
        return treedef, tuple(_leaf_sig(x) for x in leaves)

    @staticmethod
    def _key_str(name: str, static_kwargs: dict, sigs: Tuple) -> str:
        statics = ",".join(f"{k}={v}" for k, v in sorted(static_kwargs.items()))
        shapes = ",".join("x".join(map(str, s[0])) + f":{s[1]}"
                          for s in sigs if not (s and s[0] == "py"))
        return f"{name}[{statics}|{shapes}]"

    def _get_entry(self, kernel: _Kernel, args, static_kwargs: dict,
                   warmup: bool, sig: Optional[Tuple[Any, Tuple]] = None):
        treedef, sigs = self._signature(args) if sig is None else sig
        key = (kernel.name, tuple(sorted(static_kwargs.items())),
               treedef, sigs)
        with self._lock:
            entry = self._cache.get(key)
            if entry is not None:
                entry.hits += 1
                self._counters["hits"] += 1
                b = self._bucket.setdefault(
                    entry.key_str, {"hits": 0, "misses": 0,
                                    "compile_nanos": 0})
                b["hits"] += 1
                return entry, entry.key_str, False, 0
            clock = self._compile_locks.setdefault(key, threading.Lock())
        with clock:
            with self._lock:
                entry = self._cache.get(key)
                if entry is not None:  # raced: another thread compiled it
                    entry.hits += 1
                    self._counters["hits"] += 1
                    self._bucket[entry.key_str]["hits"] += 1
                    return entry, entry.key_str, False, 0
            key_str = self._key_str(kernel.name, static_kwargs, sigs)
            in_grid = True
            if kernel.grid_check is not None:
                try:
                    in_grid = bool(kernel.grid_check(static_kwargs, sigs))
                except Exception:
                    in_grid = False
            if not in_grid:
                with self._lock:
                    self._counters["out_of_grid_compiles"] += 1
                if self.strict:
                    raise DispatchGridEscape(
                        f"dispatch grid escape: {key_str} is outside "
                        f"[{kernel.name}]'s declared bucket grid")
                logger.warning("dispatch grid escape (compiling anyway): %s",
                               key_str)
            entry = self._compile(kernel, args, static_kwargs, key, key_str,
                                  warmup)
            return entry, key_str, True, entry.compile_nanos

    def _compile(self, kernel: _Kernel, args, static_kwargs: dict, key,
                 key_str: str, warmup: bool) -> _Entry:
        import jax

        if kernel.jitted is None:
            kernel.jitted = jax.jit(
                _named(kernel), static_argnames=kernel.static_argnames,
                donate_argnums=kernel.donate_argnums)
        # CPU backends can't honor donation; the fallback is silent
        # copy-free-anyway execution, not an error worth a log line. The
        # filter re-installs per compile (misses are rare; filterwarnings
        # dedups an already-present filter) rather than once behind a
        # latch — an enclosing catch_warnings() (pytest wraps every test
        # in one) would pop a latched install for good — and rather than
        # catch_warnings() here, which mutates GLOBAL warning state and
        # is unsafe across concurrent compiles (warmup thread + serving
        # thread compiling different buckets).
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        # stage `dispatch.compile`: a live p99 over compile cost (and,
        # as its count, a compile rate) sits next to the serving
        # latency histograms in `_nodes/stats telemetry` — a nonzero
        # steady-state rate there is the recompile-regression signal
        # without waiting for the strict-mode gate; a sampled request
        # that had to compile shows it as a span
        from elasticsearch_tpu.telemetry import stage as _stage
        with self._lock:
            self._compiling += 1
        try:
            with _stage("dispatch.compile", kernel=key_str) as st, \
                    _x64_scope(kernel.x64):
                compiled = kernel.jitted.lower(
                    *args, **static_kwargs).compile()
        finally:
            with self._lock:
                self._compiling -= 1
        nanos = st.nanos
        entry = _Entry(compiled, key_str, nanos)
        with self._lock:
            self._cache[key] = entry
            self._counters["misses"] += 1
            self._counters["compiles"] += 1
            self._counters["compile_nanos"] += nanos
            if warmup:
                self._counters["warmup_compiles"] += 1
            b = self._bucket.setdefault(
                key_str, {"hits": 0, "misses": 0, "compile_nanos": 0})
            b["misses"] += 1
            b["compile_nanos"] += nanos
        return entry

    # -------------------------------------------------------------- warmup
    def warmup(self, entries: Sequence[Tuple[str, tuple, dict]],
               background: bool = True) -> Optional[threading.Thread]:
        """AOT-compile a bucket grid off the critical path.

        entries: (kernel name, arg specs, static kwargs) — arg specs may
        be `jax.ShapeDtypeStruct` pytrees (no data materialized). Already-
        cached buckets are skipped for free. Returns the warmup thread
        (joinable, for deterministic tests) when `background`."""
        def run():
            for name, args, statics in entries:
                kernel = self._kernels.get(name)
                if kernel is None:
                    continue
                try:
                    self._get_entry(kernel, args, statics, warmup=True)
                except Exception as exc:
                    logger.debug("warmup compile failed for %s: %s",
                                 name, exc)
        if not background:
            run()
            return None
        t = threading.Thread(target=run, daemon=True,
                             name="dispatch-warmup")
        t.start()
        return t

    # --------------------------------------------------------------- stats
    def stats(self, per_bucket: bool = True) -> dict:
        import jax
        with self._lock:
            out = dict(self._counters)
            out["compiling"] = self._compiling
            out["cached_executables"] = len(self._cache)
            out["compile_cache_dir"] = jax.config.jax_compilation_cache_dir
            if per_bucket:
                out["buckets"] = {k: dict(v)
                                  for k, v in sorted(self._bucket.items())}
            return out

    def compile_count(self) -> int:
        with self._lock:
            return self._counters["compiles"]

    def reset_stats(self) -> None:
        """Zero the counters (tests); compiled executables stay cached."""
        with self._lock:
            for k in self._counters:
                self._counters[k] = 0
            self._bucket.clear()

    def clear(self) -> None:
        """Drop every cached executable AND counters (tests only)."""
        with self._lock:
            self._cache.clear()
            self._compile_locks.clear()
            for k in self._counters:
                self._counters[k] = 0
            self._bucket.clear()


DISPATCH = Dispatcher()


def call(name: str, *args, **static_kwargs):
    return DISPATCH.call(name, *args, **static_kwargs)


def call_async(name: str, *args, **static_kwargs):
    return DISPATCH.call_async(name, *args, **static_kwargs)


def stats(per_bucket: bool = True) -> dict:
    return DISPATCH.stats(per_bucket=per_bucket)


# ---------------------------------------------------------------------------
# The dispatcher's own fixed cost
# ---------------------------------------------------------------------------

_overhead_lock = threading.Lock()
_overhead_ms: Optional[float] = None


def _probe_kernel(x):
    """Tiny round-trip kernel for `device_overhead_ms`."""
    return x + 1.0


def device_overhead_ms() -> float:
    """One-time measurement of a tiny dispatch round-trip against the
    live backend — the fixed cost a device dispatch must amortize
    (`ops/bm25.py` `_prefer_device` prices its host twin against it). A
    probe that fails raises: a guessed overhead would route on no
    evidence."""
    global _overhead_ms
    if _overhead_ms is not None:
        return _overhead_ms
    with _overhead_lock:
        if _overhead_ms is not None:
            return _overhead_ms
        import jax.numpy as jnp

        import numpy as _np

        # the probe rides the same dispatcher every serving kernel
        # uses (a raw jax.jit here was a second compile path outside
        # the AOT cache — tpulint TPU001), so the measured round trip
        # includes the dispatch layer a real serving call pays
        DISPATCH.register("serving.overhead_probe", _probe_kernel)
        x = _np.zeros((8,), _np.float32)
        # tpulint: disable=TPU009(one-time-per-process probe under the measurement latch, not a serving queue lock — nothing queues on it)
        _np.asarray(DISPATCH.call("serving.overhead_probe",
                                  jnp.asarray(x)))
        samples = []
        for _ in range(3):
            # a serving dispatch pays h2d (queries/mask), execute, AND
            # d2h (results) — measure the full round trip
            t0 = time.perf_counter()
            # tpulint: disable=TPU002(the probe MEASURES the per-dispatch d2h round trip on purpose; 3 iterations, once per process, not a serving loop),TPU009(same: the measurement latch is not a serving queue lock)
            _np.asarray(DISPATCH.call("serving.overhead_probe",
                                      jnp.asarray(x)))
            samples.append((time.perf_counter() - t0) * 1000.0)
        _overhead_ms = max(0.05, min(samples))
    return _overhead_ms


# ---------------------------------------------------------------------------
# Spec helpers (warmup grids)
# ---------------------------------------------------------------------------

def specs_like(tree):
    """Map a pytree of concrete arrays to `jax.ShapeDtypeStruct`s (warmup
    without materializing data)."""
    import jax

    def spec(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
        return x
    return jax.tree_util.tree_map(spec, tree)


def query_spec(n_queries: int, dims: int):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct((n_queries, dims), jnp.float32)


# default warmup ladders: the interactive serving shapes. Kept small on
# purpose — warmup is a floor, not the whole grid; the persistent cache
# catches the tail across restarts.
WARMUP_QUERY_BUCKETS = (1, 8, 16, 64)
WARMUP_K_BUCKETS = (10, 100)


def query_buckets_upto(n: int) -> Tuple[int, ...]:
    """Every rung of the query ladder up to the one that holds n queries
    (1, 8, 16, 32, ...): a grid that leaves no rung out under its top."""
    top = bucket_queries(n)
    rungs, b = [1], 8
    while b <= top:
        rungs.append(b)
        b *= 2
    return tuple(rungs)


_default_warmup: Optional[bool] = None


def set_default_warmup(value: Optional[bool]) -> None:
    """Node-level warmup override (`search.dispatch.warmup` setting);
    None restores the env/platform auto policy."""
    global _default_warmup
    _default_warmup = value


def warmup_enabled(override: Optional[bool] = None) -> bool:
    """Shared warmup policy: explicit override > node setting >
    ES_TPU_DISPATCH_WARMUP env > platform auto (warm only where compiles
    actually stall serving — real accelerator backends; CPU test runs
    skip the background threads)."""
    if override is not None:
        return override
    if _default_warmup is not None:
        return _default_warmup
    env = os.environ.get("ES_TPU_DISPATCH_WARMUP")
    if env is not None:
        return env != "0"
    return is_accelerator_backend()
