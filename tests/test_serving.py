"""Serving layer: the CombiningBatcher coalescing concurrent requests into
one dispatch, and what VectorStoreShard answers through it, held against
plain float32 numpy.
"""

import threading

import numpy as np
import pytest

from elasticsearch_tpu.serving.batcher import CombiningBatcher


def _exact_topk(raw, k):
    order = np.lexsort((np.arange(raw.shape[-1]), -raw))
    return order[:k]


class TestCombiningBatcher:
    def test_single_thread_executes_immediately(self):
        calls = []

        def execute(reqs):
            calls.append(len(reqs))
            return [r * 2 for r in reqs]

        b = CombiningBatcher(execute)
        assert b.submit(21) == 42
        assert calls == [1]

    def test_concurrent_requests_coalesce(self):
        batch_sizes = []
        gate = threading.Event()

        def execute(reqs):
            gate.wait(5)
            batch_sizes.append(len(reqs))
            return [r + 100 for r in reqs]

        b = CombiningBatcher(execute)
        results = {}

        def worker(i):
            results[i] = b.submit(i)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(12)]
        for t in threads:
            t.start()
        # let every request enqueue, then open the gate: the first runner
        # serves its batch; everything queued behind coalesces
        import time
        time.sleep(0.2)
        gate.set()
        for t in threads:
            t.join(10)
        assert results == {i: i + 100 for i in range(12)}
        assert sum(batch_sizes) == 12
        assert len(batch_sizes) <= 3  # coalescing actually happened

    def test_coalesced_batch_events_are_labeled(self):
        """Dispatch-trace attribution: a profiled runner executing a
        coalesced batch labels those events `coalesced_batch: N` instead
        of silently claiming follower dispatches as its own; a solo
        dispatch stays unlabeled."""
        from concurrent.futures import Future

        from elasticsearch_tpu.ops import dispatch

        dispatch.DISPATCH.register("test.batcher_trace", lambda x: x + 1.0)

        def execute(reqs):
            import jax.numpy as jnp
            return [float(np.asarray(dispatch.call(
                "test.batcher_trace", jnp.float32(r)))) for r in reqs]

        b = CombiningBatcher(execute)
        dispatch.DISPATCH.record_events(True)
        try:
            # a queued follower makes the submitting thread a runner
            # executing a 2-request batch deterministically
            follower = Future()
            b._enqueue(1.0, follower)
            assert b.submit(2.0) == 3.0
            assert follower.result(timeout=5) == 2.0
            events = dispatch.DISPATCH.drain_events()
            batch_events = [e for e in events
                            if e["kernel"] == "test.batcher_trace"]
            assert len(batch_events) == 2
            assert all(e.get("coalesced_batch") == 2
                       for e in batch_events)
            # solo dispatch: no coalescing marker
            dispatch.DISPATCH.record_events(True)
            assert b.submit(5.0) == 6.0
            (solo,) = [e for e in dispatch.DISPATCH.drain_events()
                       if e["kernel"] == "test.batcher_trace"]
            assert "coalesced_batch" not in solo

            # poisoned batch: the serial per-request retries run on the
            # same runner thread — their dispatches must be labeled too
            def poisoned_execute(reqs):
                if len(reqs) > 1:
                    raise RuntimeError("poisoned batch")
                return execute(reqs)

            b2 = CombiningBatcher(poisoned_execute)
            dispatch.DISPATCH.record_events(True)
            follower2 = Future()
            b2._enqueue(1.0, follower2)
            assert b2.submit(2.0) == 3.0
            assert follower2.result(timeout=5) == 2.0
            retry_events = [e for e in dispatch.DISPATCH.drain_events()
                            if e["kernel"] == "test.batcher_trace"]
            assert len(retry_events) == 2
            assert all(e.get("coalesced_batch") == 2
                       for e in retry_events)
        finally:
            dispatch.DISPATCH.record_events(False)

    def test_error_propagates_to_all_waiters(self):
        def execute(reqs):
            raise RuntimeError("boom")

        b = CombiningBatcher(execute)
        with pytest.raises(RuntimeError, match="boom"):
            b.submit(1)

    def test_poisoned_request_does_not_fail_coalesced_peers(self):
        """A batch failure retries each request alone: only the offender
        errors, healthy requests that coalesced with it still succeed."""
        import threading

        calls = []

        def execute(reqs):
            calls.append(list(reqs))
            if any(r == "bad" for r in reqs):
                raise ValueError("poisoned")
            return [f"ok:{r}" for r in reqs]

        b = CombiningBatcher(execute)
        release = threading.Event()
        slow_started = threading.Event()

        def slow_execute(reqs):
            slow_started.set()
            release.wait(5)
            return execute(reqs)

        b._execute = slow_execute
        results: dict = {}

        def run(r):
            try:
                results[r] = b.submit(r)
            except Exception as e:  # noqa: BLE001
                results[r] = e

        # occupy the runner so the next two coalesce into one batch
        t0 = threading.Thread(target=run, args=("warm",))
        t0.start()
        slow_started.wait(5)
        b._execute = execute
        t1 = threading.Thread(target=run, args=("good",))
        t2 = threading.Thread(target=run, args=("bad",))
        t1.start(); t2.start()
        import time
        time.sleep(0.05)  # let both enqueue behind the held lock
        release.set()
        for t in (t0, t1, t2):
            t.join(5)
        assert results["warm"] == "ok:warm"
        assert results["good"] == "ok:good"
        assert isinstance(results["bad"], ValueError)


def _build_store(n=400, dims=32, seed=5, similarity="cosine", mat=None):
    from elasticsearch_tpu.index.mapping import DenseVectorFieldMapper
    from elasticsearch_tpu.vectors.store import VectorStoreShard

    class FakeSeg:
        def __init__(self, mat):
            self.seg_id = "s0"
            self.num_docs = len(mat)
            self.base = 0
            self.vectors = {"v": (mat, np.ones(len(mat), dtype=bool))}

    class FakeView:
        def __init__(self, seg):
            self.segment = seg
            self.live = np.ones(seg.num_docs, dtype=bool)

    class FakeReader:
        def __init__(self, mat):
            self.views = [FakeView(FakeSeg(mat))]

    rng = np.random.default_rng(seed)
    if mat is None:
        mat = rng.standard_normal((n, dims)).astype(np.float32)
    mapper = DenseVectorFieldMapper("v", {"dims": mat.shape[1],
                                          "similarity": similarity})
    store = VectorStoreShard()
    store.sync(FakeReader(mat), {"v": mapper})
    return store, mat, rng


class TestStoreRouting:
    def _store(self, n=400, dims=32, seed=5):
        return _build_store(n=n, dims=dims, seed=seed)

    def test_cosine_top10_is_the_exact_f32_ranking(self):
        """On a corpus whose leading cosines sit 0.02 apart (far above
        bf16's rounding) the served ids ARE the float32 ranking, in
        order, and the scores the raw cosines."""
        rng = np.random.default_rng(0)
        dims, nq, planted = 64, 4, 12
        mat = rng.standard_normal((600, dims)).astype(np.float32)
        queries = rng.standard_normal((nq, dims)).astype(np.float32)
        for i, q in enumerate(queries):
            u = q / np.linalg.norm(q)
            for j in range(planted):
                w = rng.standard_normal(dims).astype(np.float32)
                w -= (w @ u) * u
                w /= np.linalg.norm(w)
                c = 0.98 - 0.02 * j
                mat[i * planted + j] = 3.0 * (c * u + np.sqrt(1 - c * c) * w)
        store, mat, _ = _build_store(mat=mat)
        out = store.search_many("v", [(q, None) for q in queries], 10)
        vn = mat / np.linalg.norm(mat, axis=-1, keepdims=True)
        for q, (rows, scores) in zip(queries, out):
            exact = vn @ (q / np.linalg.norm(q))
            ref = _exact_topk(exact, 10)
            np.testing.assert_array_equal(rows, ref)
            np.testing.assert_allclose(scores, exact[ref], atol=1e-2)
            assert np.all(np.diff(scores) <= 0)

    @pytest.mark.parametrize("similarity", ["l2_norm", "dot_product"])
    def test_raw_score_convention(self, similarity):
        """`l2_norm` lands -||q - c||^2 and `dot_product` q.c: bigger is
        better, descending, the rows the float32 reference ranks first."""
        rng = np.random.default_rng(1)
        mat = rng.standard_normal((1000, 32)).astype(np.float32)
        queries = rng.standard_normal((2, 32)).astype(np.float32)
        if similarity == "dot_product":     # the mapping wants unit rows
            mat /= np.linalg.norm(mat, axis=-1, keepdims=True)
            queries /= np.linalg.norm(queries, axis=-1, keepdims=True)
        store, mat, _ = _build_store(similarity=similarity, mat=mat)
        out = store.search_many("v", [(q, None) for q in queries], 5)
        for q, (rows, scores) in zip(queries, out):
            exact = (-((mat - q) ** 2).sum(axis=-1)
                     if similarity == "l2_norm" else mat @ q)
            assert len(rows) == 5
            np.testing.assert_allclose(scores, exact[rows],
                                       rtol=2e-2, atol=2e-2)
            assert np.all(np.diff(scores) <= 0)
            assert len(set(rows.tolist())
                       & set(_exact_topk(exact, 5).tolist())) >= 4

    def test_one_batch_mixes_unfiltered_shared_and_per_request_filters(self):
        """One dispatch, three kinds of request: no filter, two requests
        under the SAME filter, one under its own. Each is held to its own
        filter and to the float32 ranking inside it."""
        store, mat, rng = self._store(n=800, dims=48, seed=2)
        queries = rng.standard_normal((4, 48)).astype(np.float32)
        shared = np.flatnonzero(rng.random(800) < 0.3).astype(np.int64)
        own = np.flatnonzero(rng.random(800) < 0.3).astype(np.int64)
        filters = [None, shared, shared, own]
        out = store.search_many("v", list(zip(queries, filters)), 20)
        vn = mat / np.linalg.norm(mat, axis=-1, keepdims=True)
        for q, fr, (rows, scores) in zip(queries, filters, out):
            assert len(rows) == 20
            exact = vn @ (q / np.linalg.norm(q))
            if fr is not None:
                assert np.all(np.isin(rows, fr))
                outside = np.ones(800, dtype=bool)
                outside[fr] = False
                exact[outside] = -np.inf
            assert len(set(rows.tolist())
                       & set(_exact_topk(exact, 20).tolist())) >= 18

    def test_fewer_than_k_eligible_returns_them_and_no_padding(self):
        store, mat, rng = self._store(n=50, dims=16, seed=3)
        q = rng.standard_normal(16).astype(np.float32)
        allowed = np.arange(7, dtype=np.int64)
        (rows, scores), = store.search_many("v", [(q, allowed)], 20)
        assert sorted(rows.tolist()) == list(range(7))
        assert len(scores) == 7 and np.all(np.isfinite(scores))
        assert np.all(scores > -1e37)

    def test_k_larger_than_the_corpus_returns_every_row_once(self):
        store, mat, rng = self._store(n=50, dims=16, seed=4)
        q = rng.standard_normal(16).astype(np.float32)
        (rows, scores), = store.search_many("v", [(q, None)], 200)
        assert sorted(rows.tolist()) == list(range(50))
        assert np.all(np.isfinite(scores)) and np.all(np.diff(scores) <= 0)

    def test_filtered_search_respects_filter(self):
        store, mat, rng = self._store()
        q = rng.standard_normal(32).astype(np.float32)
        filter_rows = np.arange(0, 400, 3, dtype=np.int64)
        rows, _ = store.search("v", q, 15, filter_rows=filter_rows)
        assert len(rows) == 15
        assert np.all(np.isin(rows, filter_rows))

    def test_concurrent_store_searches(self):
        store, mat, rng = self._store(n=2000)
        queries = rng.standard_normal((16, 32)).astype(np.float32)
        results = {}

        def worker(i):
            results[i] = store.search("v", queries[i], 5)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert len(results) == 16
        vn = mat / np.linalg.norm(mat, axis=-1, keepdims=True)
        for i in range(16):
            rows, scores = results[i]
            qn = queries[i] / np.linalg.norm(queries[i])
            exact = vn @ qn
            ref = set(_exact_topk(exact, 5).tolist())
            assert len(ref & set(rows.tolist())) >= 4


class TestContinuousScheduler:
    """The PR-8 continuous-batching scheduler: deadline-aware EDF
    admission with schedule-time shedding, in-flight bucket top-up
    (byte-identical to an up-front batch, zero new compiles), and
    dispatch/finalize pipelining."""

    def test_drain_is_edf_and_sheds_expired_oldest_first(self):
        """Queued requests schedule earliest-deadline-first; an entry
        whose deadline passed is shed the moment the scheduler touches
        it (429-typed), and the later-deadline entry is NOT starved —
        it serves in the next turn."""
        import time as _time

        from concurrent.futures import Future

        from elasticsearch_tpu.common.threadpool import (
            EsRejectedExecutionError)
        from elasticsearch_tpu.serving.batcher import BoundedBatcher

        executed = []

        def execute(reqs):
            executed.append(list(reqs))
            return list(reqs)

        b = BoundedBatcher(execute, max_batch=1, deadline_ms=10_000.0)
        now = _time.monotonic()
        f_far, f_near, f_dead = Future(), Future(), Future()
        e_far = b._enqueue("far", f_far)
        e_near = b._enqueue("near", f_near)
        e_dead = b._enqueue("dead", f_dead)
        # forge the schedule: "dead" expired long ago, "near" is due
        # before "far" despite arriving later
        e_dead.deadline = now - 1.0
        e_near.deadline = now + 1.0
        e_far.deadline = now + 100.0
        b._run_once()
        with pytest.raises(EsRejectedExecutionError):
            f_dead.result(timeout=1)
        assert f_near.result(timeout=1) == "near"
        assert executed == [["near"]]
        assert b.stats["shed_deadline"] == 1
        assert b.sched["deadline_sheds"] == 1
        b._run_once()   # the large/old request is not starved
        assert f_far.result(timeout=1) == "far"
        assert executed == [["near"], ["far"]]

    def test_topup_batch_byte_identical_and_zero_recompiles(self):
        """Late arrivals joining a forming batch at the bucket boundary
        return byte-identical results to the same requests batched up
        front — and the topped-up dispatch compiles NOTHING new (the
        compiled shape is the bucket), checked under strict mode."""
        import threading
        import time as _time

        from concurrent.futures import Future

        from elasticsearch_tpu.ops import dispatch
        from elasticsearch_tpu.serving.batcher import CombiningBatcher

        store, mat, rng = _build_store(n=512)
        queries = rng.standard_normal((8, 32)).astype(np.float32)
        baseline = store.search_many("v", [(q, None) for q in queries], 10)

        fc = store._fields["v"]

        def dispatch_fn(reqs):
            return store._dispatch_many(fc, 10, "bf16", reqs)

        b = CombiningBatcher(None, dispatch_fn=dispatch_fn,
                             finalize_fn=store.finalize_many,
                             topup=True, target_batch_latency_ms=500.0)
        futs = [Future() for _ in range(8)]
        for q, f in zip(queries[:5], futs[:5]):
            b._enqueue((q, None), f)

        def late():
            _time.sleep(0.02)
            for q, f in zip(queries[5:], futs[5:]):
                b._enqueue((q, None), f)

        t = threading.Thread(target=late)
        t.start()
        compiles_before = dispatch.DISPATCH.compile_count()
        old_strict = dispatch.DISPATCH.strict
        dispatch.DISPATCH.strict = True
        try:
            b._run_once()
        finally:
            dispatch.DISPATCH.strict = old_strict
        t.join(5)
        # the 5 early + 3 late requests rode ONE bucket-8 dispatch
        assert b.sched["batches"] == 1
        assert b.sched["topups"] == 3
        # zero new compiles: the bucket-8 program was already compiled
        # by the up-front baseline batch
        assert dispatch.DISPATCH.compile_count() == compiles_before
        for f, (rows_ref, scores_ref) in zip(futs, baseline):
            rows, scores = f.result(timeout=5)
            np.testing.assert_array_equal(rows, rows_ref)
            np.testing.assert_array_equal(scores, scores_ref)

    def test_idle_single_query_never_waits_for_topup(self):
        """bucket_queries(1) == 1: a lone request has zero bucket
        headroom, so the top-up window must not add idle latency."""
        import time as _time

        from elasticsearch_tpu.serving.batcher import CombiningBatcher

        b = CombiningBatcher(lambda reqs: list(reqs),
                             topup=True, target_batch_latency_ms=500.0)
        t0 = _time.monotonic()
        assert b.submit("solo") == "solo"
        assert (_time.monotonic() - t0) < 0.25  # far under the 500ms window
        assert b.sched["topups"] == 0

    def test_pipelined_finalize_overlaps_next_dispatch(self):
        """While batch N finalizes (outside the scheduler lock), batch
        N+1 must be able to dispatch — the overlap the tail fix is made
        of. Results stay correct and the overlap is counted."""
        import threading
        import time as _time

        from elasticsearch_tpu.serving.batcher import CombiningBatcher

        started_finalize = threading.Event()
        release_finalize = threading.Event()

        def dispatch_fn(reqs):
            return list(reqs)

        def finalize_fn(handle):
            started_finalize.set()
            release_finalize.wait(5)
            return [r * 10 for r in handle]

        b = CombiningBatcher(None, dispatch_fn=dispatch_fn,
                             finalize_fn=finalize_fn, topup=False)
        results = {}

        def worker(i):
            results[i] = b.submit(i)

        t1 = threading.Thread(target=worker, args=(1,))
        t1.start()
        assert started_finalize.wait(5)
        # batch 1 is mid-finalize and holds NO lock: batch 2 dispatches
        t2 = threading.Thread(target=worker, args=(2,))
        t2.start()
        deadline = _time.monotonic() + 5
        while (b.sched["overlap_hits"] < 1
               and _time.monotonic() < deadline):
            _time.sleep(0.005)
        assert b.sched["overlap_hits"] >= 1
        release_finalize.set()
        t1.join(5)
        t2.join(5)
        assert results == {1: 10, 2: 20}
        assert b.sched["pipelined_batches"] == 2

    def test_pipelined_poisoned_batch_retries_serially(self):
        """A finalize failure on a coalesced batch retries each request
        alone through the synchronous path — 429/error semantics are
        identical to the pre-pipeline batcher."""
        from concurrent.futures import Future

        from elasticsearch_tpu.serving.batcher import CombiningBatcher

        def dispatch_fn(reqs):
            return list(reqs)

        def finalize_fn(handle):
            if any(r == "bad" for r in handle):
                raise ValueError("poisoned")
            return [f"ok:{r}" for r in handle]

        b = CombiningBatcher(None, dispatch_fn=dispatch_fn,
                             finalize_fn=finalize_fn, topup=False)
        follower = Future()
        b._enqueue("bad", follower)
        assert b.submit("good") == "ok:good"
        with pytest.raises(ValueError, match="poisoned"):
            follower.result(timeout=5)

    def test_queue_wait_and_scheduler_counters_accumulate(self):
        from elasticsearch_tpu.serving.batcher import CombiningBatcher

        from elasticsearch_tpu.telemetry import REGISTRY

        def hist(name):
            h = REGISTRY.histogram(name)
            return h.count, h.sum_ns

        before = {n: hist(n) for n in ("serving.queue_wait",
                                       "serving.device_dispatch",
                                       "serving.batch_form")}
        b = CombiningBatcher(lambda reqs: list(reqs))
        for i in range(4):
            assert b.submit(i) == i
        assert b.sched["batches"] == 4
        assert b.sched["requests"] == 4
        # the scheduler's times live in the telemetry stages, once a
        # request / once a batch, and nowhere else
        assert not [k for k in b.sched if k.endswith("_nanos")]
        for name, (count, total) in before.items():
            after_count, after_total = hist(name)
            assert after_count == count + 4, name
            assert after_total >= total, name
        assert hist("serving.device_dispatch")[1] > \
            before["serving.device_dispatch"][1]

    def test_store_scheduler_stats_survive_batcher_retirement(self):
        """Refresh drops stale (field, k) batchers; their scheduler
        counters must fold into the retired total, not vanish."""
        store, mat, rng = _build_store(n=128)
        q = rng.standard_normal(32).astype(np.float32)
        store.search("v", q, 5)
        before = store.scheduler_stats()
        assert before.get("batches", 0) >= 1
        with store._batchers_lock:
            for key in list(store._batchers):
                store._retire_sched(store._batchers.pop(key))
        after = store.scheduler_stats()
        assert after.get("batches", 0) == before.get("batches", 0)


class TestRrfFastPath:
    """RRF fuses query-phase ranked lists and fetches only `size` docs
    (node.py _search_rrf fast path); results must match the definition
    score(d) = sum_lists 1/(rank_constant + rank)."""

    def _node(self, tmp_path):
        import jax

        try:
            jax.config.update("jax_platforms", "cpu")
        except RuntimeError:
            pass
        from elasticsearch_tpu.node import Node

        rng = np.random.default_rng(9)
        node = Node(str(tmp_path))
        node.create_index_with_templates("h", mappings={"properties": {
            "body": {"type": "text"},
            "v": {"type": "dense_vector", "dims": 8}}})
        ops = []
        for i in range(300):
            ops.append({"index": {"_index": "h", "_id": str(i)}})
            ops.append({"body": " ".join(rng.choice(list("abcde"), 4)),
                        "v": rng.standard_normal(8).tolist()})
        node.bulk(ops)
        node.indices.get("h").refresh()
        return node, rng

    def test_matches_manual_fusion(self, tmp_path):
        node, rng = self._node(tmp_path)
        qv = rng.standard_normal(8).tolist()
        body = {"rank": {"rrf": {"rank_constant": 60,
                                 "rank_window_size": 50}},
                "query": {"match": {"body": "a b"}},
                "knn": {"field": "v", "query_vector": qv, "k": 50},
                "size": 10}
        resp = node.search("h", body)
        fused = {}
        for q in (body["query"], {"knn": body["knn"]}):
            sub = node.search("h", {"query": q, "size": 50})
            for rp, hit in enumerate(sub["hits"]["hits"]):
                fused[hit["_id"]] = fused.get(hit["_id"], 0.0) \
                    + 1.0 / (60 + rp + 1)
        expect = sorted(fused.values(), reverse=True)[:10]
        got = [h["_score"] for h in resp["hits"]["hits"]]
        np.testing.assert_allclose(got, expect, rtol=1e-9)
        assert resp["hits"]["total"]["value"] == len(fused)
        assert "_source" in resp["hits"]["hits"][0]
        node.close()

    def test_source_false_and_window_clamp(self, tmp_path):
        node, rng = self._node(tmp_path)
        body = {"rank": {"rrf": {"rank_window_size": 20}},
                "query": {"match": {"body": "a"}},
                "knn": {"field": "v",
                        "query_vector": rng.standard_normal(8).tolist(),
                        "k": 20},
                "size": 5, "_source": False}
        resp = node.search("h", body)
        assert len(resp["hits"]["hits"]) == 5
        assert "_source" not in resp["hits"]["hits"][0]
        node.close()
