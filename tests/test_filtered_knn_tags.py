"""The deployment `yfcc-192-uint8-tags` at a test's size (4,096 rows x
192-d) on the CPU: `_search` with a `knn` clause whose `filter` is a `bool`
of one or two `term`s over a `keyword` ARRAY, on an `l2_norm` field of
whole numbers 0..255, against the plain reference of the benchmark
(`benchmark/kinds/knn_tags_reference.py`, numpy alone).

Ids equal the reference's, and d2 = 1 / `_score` - 1 is the reference's
INTEGER (a uint8 value is exact in bf16, its products and their sum in
float32), on the three routes a filtered search can take: one device, the
generational fan-out (a second `_bulk` and `_refresh`, no merge) and the
mesh (4 of the conftest's virtual devices); in batches of 1, 8 and 64
through the batcher; with a conjunction that fewer than k rows hold and
one that none holds.
"""

import json
import os
import sys
import tempfile
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.kinds import knn_tags_reference as reference  # noqa: E402

pytestmark = pytest.mark.multidevice

ROWS, K, SHARDS, SEED = 4096, 10, 4, 2 ** 31 + 33
with open(os.path.join(REPO, "benchmark", "configs",
                       "yfcc-192-uint8-tags.json")) as _f:
    CONFIG = json.load(_f)
INDEX = "yfcc"


def _bulk(node, corpus, block):
    lines = corpus.bulk_body(block, INDEX).decode().splitlines()
    node.bulk([json.loads(ln) for ln in lines])


class Served:
    def __init__(self, route: str):
        from elasticsearch_tpu.node import Node
        self.route = route
        corpus = reference.TagCorpus(SEED, CONFIG)
        docs = corpus.block_docs
        assert ROWS == 2 * docs
        self.node = Node(tempfile.mkdtemp())
        self.node.create_index_with_templates(
            INDEX, settings=CONFIG["index"]["settings"],
            mappings=CONFIG["index"]["mappings"])
        _bulk(self.node, corpus, 0)
        if route == "generational":
            # a refresh between the two loads: two generations, no merge
            self.node.indices.get(INDEX).refresh()
        _bulk(self.node, corpus, 1)
        self.node.indices.get(INDEX).refresh()
        self.rows = corpus.rows([(0, docs), (1, docs)])
        self.store = self.node.indices.get(INDEX).shards[0].vector_store

    def search(self, vec, tags):
        flt = {"bool": {"filter": [{"term": {"tags": self.rows.name(t)}}
                                   for t in tags]}}
        resp = self.node.search(INDEX, {
            "size": K, "_source": False,
            "knn": {"field": "v", "query_vector": vec.tolist(), "k": K,
                    "num_candidates": 100, "filter": flt}})
        assert not resp["_shards"].get("failed")
        hits = resp["hits"]["hits"]
        return ([int(h["_id"]) for h in hits],
                [float(h["_score"]) for h in hits])

    def check(self, vec, tags):
        """One answer against the reference: ids, integer d2, count."""
        ids, scores = self.search(vec, tags)
        want_ids, want_d2, matching = self.rows.topk(vec, tags, K)
        assert len(ids) == min(K, matching)
        assert ids == want_ids.tolist(), (tags, matching)
        d2 = 1.0 / np.asarray(scores, dtype=np.float64) - 1.0
        assert np.rint(d2).astype(np.int64).tolist() == want_d2.tolist()
        np.testing.assert_allclose(d2, want_d2, rtol=1e-6, atol=0)
        assert all(self.rows.holds(i, tags) for i in ids)
        return matching


@pytest.fixture(scope="module", params=["single", "generational", "mesh"])
def served(request):
    from elasticsearch_tpu.parallel import policy
    policy.reset(full=True)
    if request.param == "mesh":
        policy.configure(enabled=True, num_shards=SHARDS, min_rows=1)
        if policy.serving_mesh() is None:
            policy.reset(full=True)
            pytest.skip("needs 4 jax devices (forced-host-device-count)")
    s = Served(request.param)
    yield s
    s.node.close()
    policy.reset(full=True)


def test_the_route_is_the_one_the_case_names(served):
    from elasticsearch_tpu.parallel import policy
    q, tags = served.rows.queries(1000, 1)     # no query is sent twice
    before = policy.stats()["router"]["mesh"]
    served.check(q[0], tags[0])
    phases = served.store.last_knn_phases or {}
    if served.route == "generational":
        assert phases.get("engine") == "tpu_generational"
        assert phases.get("generations") == 2
    elif served.route == "mesh":
        assert phases.get("engine") == "tpu_mesh"
        assert policy.stats()["router"]["mesh"] == before + 1
    else:
        assert phases.get("engine") not in ("tpu_generational", "tpu_mesh")


@pytest.mark.parametrize("batch", [1, 8, 64])
def test_batches_through_the_batcher_agree_with_the_reference(served, batch):
    """64 queries of the run's stream (about half with one tag, half with
    two), `batch` of them in flight at once."""
    first = {1: 0, 8: 64, 64: 128}[batch]
    q, tags = served.rows.queries(first, 64)
    assert {len(t) for t in tags} == {1, 2}
    sched = served.store.scheduler_stats()
    gate = threading.Barrier(batch)

    def one(i):
        gate.wait(timeout=60)
        return served.check(q[i], tags[i])

    with ThreadPoolExecutor(max_workers=batch) as pool:
        matching = list(pool.map(one, range(64)))
    assert min(matching) >= 1          # a query's anchor holds its tags
    now = served.store.scheduler_stats()
    assert now["requests"] - sched["requests"] == 64
    if batch > 1:
        # they did share dispatches
        assert now["batches"] - sched["batches"] < 64


def _rare_pair(rows, fewer_than):
    """Two tags that some row holds together, held together by fewer
    than `fewer_than` rows."""
    for row in range(len(rows)):
        bag = rows.bag(row)
        if len(bag) >= 2:
            pair = (int(bag[-1]), int(bag[-2]))    # its rarest two
            if len(rows.matching(pair)) < fewer_than:
                return pair
    raise AssertionError("no such pair in these rows")


def test_a_conjunction_that_fewer_than_k_rows_hold(served):
    pair = _rare_pair(served.rows, K)
    q, _ = served.rows.queries(300, 4)
    for vec in q:
        assert 1 <= served.check(vec, pair) < K


def test_a_conjunction_that_no_row_holds(served):
    rows = served.rows
    # two tags that are each held, never together
    held = [t for t in range(rows.corpus.vocabulary)
            if len(rows.postings(t))]
    pair = next((a, b) for a in held[-40:] for b in held[-40:]
                if a != b and not len(rows.matching((a, b))))
    q, _ = rows.queries(310, 2)
    for vec in q:
        assert served.search(vec, pair) == ([], [])
        assert served.check(vec, pair) == 0
    # and a word of the vocabulary that no row holds at all
    absent = next(t for t in range(rows.corpus.vocabulary - 1, 0, -1)
                  if not len(rows.postings(t)))
    assert served.search(q[0], (absent,)) == ([], [])


def test_the_generator_is_a_function_of_seed_and_block_alone():
    a = reference.TagCorpus(SEED, CONFIG)
    b = reference.TagCorpus(SEED, CONFIG)
    b.block(0)                          # another order of asking
    assert a.bulk_body(1, INDEX) == b.bulk_body(1, INDEX)
    assert a.bulk_body(1, INDEX, 5) == b.bulk_body(1, INDEX)[
        :len(a.bulk_body(1, INDEX, 5))]
    other = reference.TagCorpus(SEED + 1, CONFIG)
    assert other.bulk_body(1, INDEX) != a.bulk_body(1, INDEX)
    blk = a.block(1)
    counts = np.diff(blk["offsets"])
    assert counts.min() >= 1 and 9 < counts.mean() < 13
    for j in range(a.block_docs):       # a bag holds no tag twice
        bag = blk["tags"][blk["offsets"][j]:blk["offsets"][j + 1]]
        assert len(set(bag.tolist())) == len(bag)
    assert blk["vectors"].dtype == np.uint8
