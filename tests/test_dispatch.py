"""Shape-bucketed dispatch layer (`ops/dispatch.py`) — the tier-1
recompile-regression suite.

What's pinned here:
* bucket selection is monotone and closed (the policy-level kill of the
  r06 "batch=4 slower than batch=16" inversion: a smaller batch can never
  map to a bigger — or freshly-compiled — program than a larger one);
* bucket-boundary parity: results are byte-identical across a pad
  boundary (a query riding in a batch of 8 == the same query in 9);
* steady-state zero-recompile: a fixed workload driven twice compiles
  only on the first pass — the dispatch compile counter stays flat on
  the second (the acceptance gate for the serving path);
* closed-grid enforcement: a compile for a shape outside the declared
  bucket grid raises under strict mode, and the PUBLIC serving paths
  never escape the grid even when fed ragged batch sizes — a future
  caller that forgets to pad fails here instead of silently
  reintroducing shape churn;
* donation safety: only the declared accumulator buffers are donated;
  corpus-resident arrays survive a dispatch and remain readable.
"""

import numpy as np
import pytest

from elasticsearch_tpu.ops import dispatch
from elasticsearch_tpu.ops import knn as knn_ops
from elasticsearch_tpu.ops import similarity as sim
from elasticsearch_tpu.vectors.store import VectorStoreShard


@pytest.fixture
def strict_dispatch():
    """Run a test with grid escapes raising; restore after."""
    old = dispatch.DISPATCH.strict
    dispatch.DISPATCH.strict = True
    yield dispatch.DISPATCH
    dispatch.DISPATCH.strict = old


def _corpus(n=256, d=16, seed=0, dtype="bf16"):
    rng = np.random.default_rng(seed)
    return knn_ops.build_corpus(
        rng.standard_normal((n, d), dtype=np.float32), dtype=dtype)


# ---------------------------------------------------------------------------
# bucket policy
# ---------------------------------------------------------------------------

class TestBucketSelection:
    def test_query_buckets_are_pow2_and_cover(self):
        for n in range(1, 300):
            b = dispatch.bucket_queries(n)
            assert b >= n
            assert b & (b - 1) == 0 or b % dispatch.MAX_QUERY_BUCKET == 0
            assert dispatch.is_query_bucket(b)

    def test_dead_rungs_2_and_4(self):
        """2..7 pad to 8: XLA-CPU's dot_general small-M path made a
        [4, N] score matmul ~3.5x SLOWER than [8, N] (the measured root
        cause of the r06 batch=4 @ 149 ms vs batch=16 @ 31.6 ms
        inversion, alongside the recompile churn); on TPU the MXU pads
        sublanes to 8 anyway, so the rung is free."""
        assert dispatch.bucket_queries(1) == 1
        for n in (2, 3, 4, 5, 6, 7, 8):
            assert dispatch.bucket_queries(n) == 8
        assert dispatch.bucket_queries(9) == 16
        assert not dispatch.is_query_bucket(2)
        assert not dispatch.is_query_bucket(4)

    def test_query_bucket_monotone(self):
        """No inversion is possible at the policy level: a smaller batch
        never selects a larger compiled program than a bigger batch (the
        r06 anomaly had batch=4 at 149 ms p50 vs batch=16 at 31.6 ms —
        4 was recompiling while 16 hit a cache)."""
        prev = 0
        for n in range(1, 2050):
            b = dispatch.bucket_queries(n)
            assert b >= prev
            prev = b

    def test_query_bucket_idempotent(self):
        for n in (1, 2, 8, 64, 2048, 4096):
            assert dispatch.bucket_queries(dispatch.bucket_queries(n)) \
                == dispatch.bucket_queries(n)

    def test_k_bucket_ladder(self):
        assert dispatch.bucket_k(10) == 10
        assert dispatch.bucket_k(11) == 16
        assert dispatch.bucket_k(65) == 100
        assert dispatch.bucket_k(101) == 128
        prev = 0
        for k in range(1, 1200):
            kb = dispatch.bucket_k(k)
            assert kb >= k and kb >= prev
            assert dispatch.in_k_grid(kb)
            prev = kb

    def test_k_bucket_clamps_to_corpus(self):
        assert dispatch.bucket_k(10, limit=7) == 7
        assert dispatch.bucket_k(3, limit=7) == 4
        assert dispatch.in_k_grid(7, limit=7)

    def test_beyond_ladder_multiples(self):
        kb = dispatch.bucket_k(1500)
        assert kb == 2048 and dispatch.in_k_grid(kb)

    def test_bucket_headroom_is_free_topup_budget(self):
        """The continuous batcher's top-up query: free rows left in a
        batch's dispatch bucket. A batch sitting ON a bucket boundary
        (incl. the lone-query bucket 1) has zero headroom — so a top-up
        can never change the compiled shape set."""
        assert dispatch.bucket_headroom(1) == 0
        assert dispatch.bucket_headroom(5) == 3
        assert dispatch.bucket_headroom(8) == 0
        assert dispatch.bucket_headroom(9) == 7
        assert dispatch.bucket_headroom(2048) == 0
        # a caller's max_batch ceiling clamps the budget
        assert dispatch.bucket_headroom(5, max_batch=6) == 1
        for n in range(1, 300):
            b = n + dispatch.bucket_headroom(n)
            assert dispatch.is_query_bucket(b) or b == n


# ---------------------------------------------------------------------------
# bucket-boundary parity
# ---------------------------------------------------------------------------

class TestPadBoundaryParity:
    def test_batch_8_vs_9_byte_identical(self):
        """The same query must return the same ids, and scores within
        1 ulp, whether it coalesced into a batch of 8 (exact bucket) or
        9 (padded to 16). Byte-identical scores across query buckets are
        not something the installed XLA gives on the CPU (the 8- and
        16-row gemms round differently); the name is historical."""
        store = VectorStoreShard(warmup=False)
        corpus = _corpus(512, 24)
        from elasticsearch_tpu.vectors.store import FieldCorpus
        fc = FieldCorpus(corpus, np.arange(512, dtype=np.int64),
                         sim.COSINE, 24, version=("t",))
        store._fields["v"] = fc
        rng = np.random.default_rng(7)
        queries = rng.standard_normal((9, 24), dtype=np.float32)
        reqs9 = [(q, None) for q in queries]
        out9 = store.search_many("v", reqs9, k=10)
        out8 = store.search_many("v", reqs9[:8], k=10)
        for i in range(8):
            ids8, scores8 = out8[i]
            ids9, scores9 = out9[i]
            np.testing.assert_array_equal(ids8, ids9)
            np.testing.assert_array_max_ulp(scores8, scores9, maxulp=1)

    def test_k_bucket_slice_parity(self):
        """k=11 buckets to 16 and slices: identical to a direct k=11
        top-k (top-k prefixes are exact)."""
        store = VectorStoreShard(warmup=False)
        corpus = _corpus(512, 24)
        from elasticsearch_tpu.vectors.store import FieldCorpus
        fc = FieldCorpus(corpus, np.arange(512, dtype=np.int64),
                         sim.COSINE, 24, version=("t",))
        store._fields["v"] = fc
        rng = np.random.default_rng(3)
        q = rng.standard_normal((4, 24), dtype=np.float32)
        out11 = store.search_many("v", [(x, None) for x in q], k=11)
        out16 = store.search_many("v", [(x, None) for x in q], k=16)
        for i in range(4):
            np.testing.assert_array_equal(out11[i][0], out16[i][0][:11])
            np.testing.assert_array_equal(out11[i][1], out16[i][1][:11])


# ---------------------------------------------------------------------------
# steady-state zero-recompile
# ---------------------------------------------------------------------------

class TestZeroRecompile:
    def test_fixed_workload_second_pass_compiles_nothing(self):
        """Acceptance gate: after the first pass of a fixed workload
        (which IS the warmup), a repeat records 0 new compiles."""
        store = VectorStoreShard(warmup=False)
        corpus = _corpus(384, 32, seed=1)
        from elasticsearch_tpu.vectors.store import FieldCorpus
        fc = FieldCorpus(corpus, np.arange(384, dtype=np.int64),
                         sim.COSINE, 32, version=("t",))
        store._fields["v"] = fc
        rng = np.random.default_rng(11)

        def drive():
            for batch, k in ((1, 10), (3, 10), (5, 13), (8, 10), (9, 40)):
                qs = rng.standard_normal((batch, 32), dtype=np.float32)
                store.search_many("v", [(q, None) for q in qs], k=k)

        drive()  # first pass: compiles the bucket grid
        before = dispatch.DISPATCH.compile_count()
        drive()  # steady state
        after = dispatch.DISPATCH.compile_count()
        assert after == before, (
            f"steady-state workload recompiled: {after - before} new "
            f"compiles; stats={dispatch.stats(per_bucket=True)}")

    def test_warmup_precompiles_grid(self):
        """An AOT-warmed bucket is a HIT on its first real query."""
        corpus = _corpus(256, 16, seed=5)
        spec = dispatch.specs_like(corpus)
        statics = {"k": 10, "metric": sim.COSINE, "precision": "bf16",
                   "block_size": None}
        entries = [("knn.exact",
                    (dispatch.query_spec(4, 16), spec, None), statics)]
        t = dispatch.DISPATCH.warmup(entries, background=True)
        t.join(timeout=120)
        before = dispatch.DISPATCH.compile_count()
        import jax.numpy as jnp
        q = np.zeros((4, 16), dtype=np.float32)
        knn_ops.knn_search(jnp.asarray(q), corpus, k=10)
        assert dispatch.DISPATCH.compile_count() == before

    def test_stats_shape(self):
        s = dispatch.stats(per_bucket=True)
        for key in ("hits", "misses", "compiles", "compile_nanos",
                    "out_of_grid_compiles", "buckets",
                    "cached_executables"):
            assert key in s
        for bucket_stats in s["buckets"].values():
            assert set(bucket_stats) == {"hits", "misses",
                                         "compile_nanos"}


# ---------------------------------------------------------------------------
# closed-grid enforcement (the CI regression gate)
# ---------------------------------------------------------------------------

class TestClosedGrid:
    def test_unbucketed_direct_call_is_flagged(self, strict_dispatch):
        """A raw kernel call with a non-bucket batch size is an escape:
        strict mode raises (this is what a future unpadded caller hits)."""
        import jax.numpy as jnp
        corpus = _corpus(256, 16, seed=2)
        q = jnp.zeros((3, 16), dtype=jnp.float32)  # 3 is not a bucket
        with pytest.raises(dispatch.DispatchGridEscape):
            knn_ops.knn_search(q, corpus, k=10)

    def test_public_serving_path_never_escapes(self, strict_dispatch):
        """The serving path pads every ragged batch to a bucket, so
        strict mode never fires — if this raises, somebody broke the
        pad-to-bucket coalescing."""
        store = VectorStoreShard(warmup=False)
        corpus = _corpus(320, 16, seed=3)
        from elasticsearch_tpu.vectors.store import FieldCorpus
        fc = FieldCorpus(corpus, np.arange(320, dtype=np.int64),
                         sim.COSINE, 16, version=("t",))
        store._fields["v"] = fc
        rng = np.random.default_rng(13)
        for batch in (1, 2, 3, 5, 7, 9, 11):
            qs = rng.standard_normal((batch, 16), dtype=np.float32)
            out = store.search_many("v", [(q, None) for q in qs], k=12)
            assert len(out) == batch

    def test_escape_counter_increments_when_lenient(self):
        import jax.numpy as jnp
        corpus = _corpus(256, 16, seed=4)
        before = dispatch.stats(per_bucket=False)["out_of_grid_compiles"]
        q = jnp.zeros((5, 16), dtype=jnp.float32)  # 5 is not a bucket
        knn_ops.knn_search(q, corpus, k=10)
        after = dispatch.stats(per_bucket=False)["out_of_grid_compiles"]
        assert after == before + 1


# ---------------------------------------------------------------------------
# donation safety
# ---------------------------------------------------------------------------

class TestDonationSafety:
    def _lexical_reader(self):
        from elasticsearch_tpu.index.mapping import MapperService
        from elasticsearch_tpu.index.engine import Engine
        import tempfile
        tmp = tempfile.mkdtemp(prefix="dispatch_bm25_")
        mapper = MapperService(
            {"properties": {"body": {"type": "text"}}})
        engine = Engine(tmp, mapper, translog_sync="async")
        words = ["alpha", "beta", "gamma", "delta", "epsilon"]
        rng = np.random.default_rng(23)
        for i in range(64):
            text = " ".join(rng.choice(words, size=6))
            engine.index(str(i), {"body": text})
        engine.refresh()
        return engine.acquire_searcher()

    def test_bm25_device_donation_correct_and_repeatable(self):
        """The donated score/count boards are freshly allocated per call,
        so back-to-back device dispatches stay correct — and the device
        route (donating) stays byte-identical to the host twin."""
        from elasticsearch_tpu.ops.bm25 import LexicalShard
        reader = self._lexical_reader()
        shard = LexicalShard()
        queries = [(["alpha", "beta"], 1.0), (["gamma"], 2.0)]
        host = shard.search_batch(reader, "body", queries, 10,
                                  route="host")
        for _ in range(3):  # repeatability: donation must not corrupt
            dev = shard.search_batch(reader, "body", queries, 10,
                                     route="device")
            for (hr, hs), (dr, ds) in zip(host, dev):
                np.testing.assert_array_equal(hr, dr)
                np.testing.assert_array_equal(hs, ds)

    def test_non_donated_args_survive(self):
        """Only the declared boards are donated: the tile arrays (the
        corpus-resident HBM state) survive a dispatch and remain
        readable."""
        import jax.numpy as jnp
        nq, width, m, n_tiles = 2, 129, 2, 2
        tile_slots = jnp.asarray(
            np.arange(n_tiles * 128, dtype=np.int32).reshape(n_tiles, 128)
            % (width - 1))
        tile_impacts = jnp.ones((n_tiles, 128), dtype=jnp.float32)
        args = (jnp.zeros((nq, width), jnp.float32),
                jnp.zeros((nq, width), jnp.int32),
                jnp.zeros((nq, m), jnp.int32),
                jnp.ones((nq, m), jnp.float32),
                jnp.ones((nq,), jnp.int32),
                tile_slots, tile_impacts, None)
        dispatch.call("bm25.topk", *args, k=4)
        # corpus arrays not donated: still alive and consistent
        assert not tile_slots.is_deleted()
        assert not tile_impacts.is_deleted()
        assert float(jnp.sum(tile_impacts)) == n_tiles * 128

    def test_registered_donation_argnums(self):
        """The registry pins donation to the board argnums only — a
        registration drift here silently donates the corpus."""
        import elasticsearch_tpu.ops.knn_ivf  # noqa: F401 (registers ivf.*)
        kernel = dispatch.DISPATCH._kernels["bm25.topk"]
        assert kernel.donate_argnums == (0, 1)
        for name in ("knn.exact", "ivf.route", "ivf.score_probes",
                     "topk.top_k", "topk.masked_top_k"):
            assert dispatch.DISPATCH._kernels[name].donate_argnums == ()


# ---------------------------------------------------------------------------
# dispatcher mechanics
# ---------------------------------------------------------------------------

class TestDispatcherMechanics:
    def test_tracer_calls_inline(self):
        """A dispatched kernel inside an enclosing jit inlines instead of
        touching the executable cache."""
        import jax
        import jax.numpy as jnp
        from elasticsearch_tpu.ops import topk as topk_ops
        before = dispatch.DISPATCH.compile_count()

        @jax.jit
        def outer(x):
            return topk_ops.top_k(x, 4)[0]

        out = outer(jnp.arange(32.0).reshape(2, 16))
        assert out.shape == (2, 4)
        # outer's own jit compiles via jax, not via the dispatcher
        assert dispatch.DISPATCH.compile_count() == before
        assert dispatch.stats(per_bucket=False)["inline_calls"] >= 1

    def test_event_trace_thread_local(self):
        import jax.numpy as jnp
        from elasticsearch_tpu.ops import topk as topk_ops
        dispatch.DISPATCH.record_events(True)
        try:
            topk_ops.top_k(jnp.arange(64.0).reshape(4, 16), 10)
            events = dispatch.DISPATCH.drain_events()
        finally:
            dispatch.DISPATCH.record_events(False)
        assert events and events[0]["kernel"] == "topk.top_k"
        assert events[0]["cache"] in ("hit", "miss")
        # recording off: drain yields nothing
        assert dispatch.DISPATCH.drain_events() == []

    @pytest.mark.parametrize("env_dir", [None, "env_cache"])
    def test_compile_cache_has_one_owner(self, tmp_path, monkeypatch,
                                         env_dir):
        """JAX_COMPILATION_CACHE_DIR set: JAX reads it, the code sets no
        directory of its own. Unset: `<checkout>/.jax_cache`, a fixed
        path with no mkdtemp, pid or time in it."""
        import jax
        updates = []
        real_update = jax.config.update

        def spy(name, value):
            updates.append(name)
            if name != "jax_compilation_cache_dir":
                real_update(name, value)
        old = (jax.config.jax_persistent_cache_min_compile_time_secs,
               jax.config.jax_persistent_cache_min_entry_size_bytes)
        monkeypatch.setattr(jax.config, "update", spy)
        monkeypatch.setattr(dispatch, "DEFAULT_COMPILE_CACHE_DIR",
                            str(tmp_path / ".jax_cache"))
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                               str(tmp_path / env_dir))
        try:
            got = dispatch.configure_compile_cache()
            if env_dir is None:
                assert got == str(tmp_path / ".jax_cache")
                assert (tmp_path / ".jax_cache").is_dir()
                assert "jax_compilation_cache_dir" in updates
            else:
                assert got == str(tmp_path / env_dir)
                assert "jax_compilation_cache_dir" not in updates
            assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        finally:
            real_update("jax_persistent_cache_min_compile_time_secs", old[0])
            real_update("jax_persistent_cache_min_entry_size_bytes", old[1])

    def test_default_compile_cache_dir_is_fixed_under_the_checkout(self):
        import os
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert dispatch.DEFAULT_COMPILE_CACHE_DIR == os.path.join(
            repo, ".jax_cache")


# ---------------------------------------------------------------------------
# no kernel key without a caller
# ---------------------------------------------------------------------------

def _const_str(node):
    import ast
    return node.value if (isinstance(node, ast.Constant)
                          and isinstance(node.value, str)) else None


def test_every_knn_kernel_key_has_a_caller_in_the_package():
    """An executable nobody serves is a compile at every boot and a key
    the next reader has to rule out. Each `knn.*` / `segments.knn` key
    is named by a `dispatch.call` in a package module other than the one
    that registers it, or reached through a public function of its own
    module that names the key and that another package module uses."""
    import ast
    import pathlib

    import elasticsearch_tpu.ops.pallas_knn_binned  # noqa: F401 registers
    import elasticsearch_tpu.segments  # noqa: F401 registers
    keys = {name for name in dispatch.DISPATCH._kernels
            if name.startswith("knn.") or name == "segments.knn"}
    assert {"knn.exact", "knn.binned", "knn.binned_rescored_packed",
            "segments.knn"} <= keys
    assert not {"knn.binned_rescored", "knn.binned_rescored_hybrid"} & keys

    package = pathlib.Path(dispatch.__file__).resolve().parents[1]
    registered_in, called_in, wrappers, used = {}, {}, {}, {}
    for path in package.rglob("*.py"):
        mod = str(path.relative_to(package))
        tree = ast.parse(path.read_text())
        used[mod] = ({n.id for n in ast.walk(tree)
                      if isinstance(n, ast.Name)}
                     | {n.attr for n in ast.walk(tree)
                        if isinstance(n, ast.Attribute)})
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute) and node.args):
                key = _const_str(node.args[0])
                if key in keys and node.func.attr == "register":
                    registered_in[key] = mod
                elif key in keys and node.func.attr in ("call",
                                                        "call_async"):
                    called_in.setdefault(key, set()).add(mod)
        for fn in tree.body:
            if (isinstance(fn, ast.FunctionDef)
                    and not fn.name.startswith("_")):
                for key in keys & {_const_str(n) for n in ast.walk(fn)}:
                    wrappers.setdefault((key, mod), set()).add(fn.name)
    for key in sorted(keys):
        home = registered_in[key]
        elsewhere = called_in.get(key, set()) - {home}
        through = {fn for fn in wrappers.get((key, home), ())
                   if any(fn in names for mod, names in used.items()
                          if mod != home)}
        assert elsewhere or through, \
            f"[{key}] (registered in {home}) has no caller in the package"
