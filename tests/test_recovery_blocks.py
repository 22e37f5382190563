"""Durable elasticity foundation (`elasticsearch_tpu/recovery/`).

Pins the block-level durability contracts:
* collect/assemble round-trip — a flushed shard serialized into
  content-addressed blocks reassembles into an engine with identical
  docs, checkpoints and row layout, and an HONEST empty-translog
  checkpoint (a restored primary must never claim ops history it
  cannot replay);
* `BlockCache` digest discipline — a put whose bytes do not hash to
  the claimed digest is rejected; a blob corrupted at rest reads back
  as a miss (and is evicted), never as bad bytes;
* snapshot -> delete -> restore through a repository serves BYTE-
  identical responses with zero re-encoding: the codec extract counter
  for the packed field stays flat (blocks arrive via the seed sidecar)
  and knn results match exactly;
* the second snapshot of a churning index ships only blocks the
  repository has never seen (blob-count delta == blocks_shipped);
* a trained IVF layout restores into a fresh store without k-means:
  `ivf_restores` increments, `ivf_trains` stays 0, results identical.
"""

import os

import numpy as np
import pytest

from elasticsearch_tpu import columnar
from elasticsearch_tpu.index.engine import Engine
from elasticsearch_tpu.index.mapping import (
    DenseVectorFieldMapper, MapperService,
)
from elasticsearch_tpu.index.segment import Segment, SegmentView, ShardReader
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.recovery.blocks import block_digest
from elasticsearch_tpu.recovery.peer import BlockCache
from elasticsearch_tpu.recovery.snapshot import (
    assemble_shard, collect_shard_blocks,
)
from elasticsearch_tpu.vectors.store import VectorStoreShard

MAPPING = {
    "properties": {
        "title": {"type": "text", "analyzer": "standard"},
        "tag": {"type": "keyword"},
        "views": {"type": "long"},
    }
}

DIMS = 32


# ---------------------------------------------------------------------------
# collect/assemble round-trip at the engine level
# ---------------------------------------------------------------------------

def test_collect_assemble_roundtrip(tmp_path):
    src = Engine(str(tmp_path / "src"), MapperService(MAPPING))
    for i in range(20):
        src.index(str(i), {"title": f"doc number {i}", "tag": f"t{i % 3}",
                           "views": i})
    src.refresh()
    for i in range(0, 20, 5):
        src.delete(str(i))
    src.flush()
    entries, payloads, meta = collect_shard_blocks(src)
    # every entry addresses a payload and the digest matches the bytes
    for e in entries:
        assert block_digest(payloads[e["digest"]]) == e["digest"]
        assert e["size"] == len(payloads[e["digest"]])

    dst_path = str(tmp_path / "dst")
    out = assemble_shard(dst_path, entries, meta, payloads.__getitem__)
    assert out["segments"] >= 1 and out["blocks_total"] == len(entries)

    dst = Engine(dst_path, MapperService(MAPPING))
    try:
        assert dst.doc_count() == src.doc_count() == 16
        assert dst.local_checkpoint == src.local_checkpoint
        for i in range(20):
            a, b = src.get(str(i)), dst.get(str(i))
            if a is None:
                assert b is None
            else:
                assert b["_source"] == a["_source"]
                assert b["_version"] == a["_version"]
        # the restored translog checkpoint is HONEST: an empty translog
        # cannot claim it can replay history from seq_no 0
        assert not dst.can_replay_from(0)
        assert dst.can_replay_from(dst.local_checkpoint + 1)
    finally:
        dst.close()
        src.close()


def test_assemble_rejects_corrupt_block(tmp_path):
    src = Engine(str(tmp_path / "src"), MapperService(MAPPING))
    src.index("1", {"title": "x"})
    src.flush()
    entries, payloads, meta = collect_shard_blocks(src)
    src.close()
    bad = dict(payloads)
    victim = entries[0]["digest"]
    bad[victim] = bad[victim][:-1] + b"\x00"
    with pytest.raises(ValueError, match="digest verification"):
        assemble_shard(str(tmp_path / "dst"), entries, meta, bad.__getitem__)


# ---------------------------------------------------------------------------
# BlockCache digest discipline
# ---------------------------------------------------------------------------

def test_block_cache_put_get_roundtrip(tmp_path):
    cache = BlockCache(str(tmp_path / "blocks"))
    data = b"some block bytes"
    digest = block_digest(data)
    assert not cache.has(digest) and cache.get(digest) is None
    cache.put(digest, data)
    assert cache.has(digest)
    assert cache.get(digest) == data
    assert digest in cache.held()
    cache.evict(digest)
    assert not cache.has(digest)


def test_block_cache_rejects_mismatched_put(tmp_path):
    cache = BlockCache(str(tmp_path / "blocks"))
    with pytest.raises(ValueError):
        cache.put(block_digest(b"expected"), b"different")
    assert cache.held() == set()


def test_block_cache_corrupt_at_rest_reads_as_miss(tmp_path):
    cache = BlockCache(str(tmp_path / "blocks"))
    data = b"block payload"
    digest = block_digest(data)
    cache.put(digest, data)
    # flip a byte on disk behind the cache's back
    path = os.path.join(str(tmp_path / "blocks"), digest)
    with open(path, "wb") as f:
        f.write(b"rotten")
    assert cache.get(digest) is None          # corrupt -> miss, not bytes
    assert not os.path.exists(path)           # and the corpse is evicted


def test_block_cache_rejects_traversal_keys(tmp_path):
    cache = BlockCache(str(tmp_path / "blocks"))
    for key in ("../escape", "not-hex!", ""):
        with pytest.raises(ValueError):
            cache.put(key, b"x")


# ---------------------------------------------------------------------------
# node-level: snapshot -> delete -> restore, byte-identical, zero re-encode
# ---------------------------------------------------------------------------

def _vec_mapping(otype="int4_flat"):
    return {"properties": {
        "title": {"type": "text"},
        "v": {"type": "dense_vector", "dims": DIMS, "similarity": "cosine",
              "index_options": {"type": otype}},
    }}


def _bulk_vectors(node, index, n, base=0, seed=5):
    rng = np.random.default_rng(seed + base)
    ops = []
    for i in range(n):
        ops.append({"index": {"_index": index, "_id": str(base + i)}})
        ops.append({"title": f"doc {base + i}",
                    "v": rng.standard_normal(DIMS).astype(np.float32)
                    .tolist()})
    node.bulk(ops)
    node.indices.get(index).refresh()


def _knn(node, index, seed=99):
    q = np.random.default_rng(seed).standard_normal(DIMS).tolist()
    body = {"knn": {"field": "v", "query_vector": q, "k": 5,
                    "num_candidates": 32}, "size": 5}
    resp = node.search(index, body)
    return [(h["_id"], h["_score"]) for h in resp["hits"]["hits"]]


def test_snapshot_delete_restore_byte_identical_zero_reencode(tmp_path):
    node = Node(str(tmp_path / "data"))
    try:
        node.create_index_with_templates("src", mappings=_vec_mapping())
        _bulk_vectors(node, "src", 64)
        before = _knn(node, "src")
        assert len(before) == 5

        node.snapshots.put_repository("mem", {
            "type": "memory", "settings": {"location": "dur-mem"}})
        node.snapshots.create_snapshot("mem", "s1", {"indices": "src"})
        node.indices.delete_index("src")

        stats0 = columnar.STORE.stats()
        enc0 = stats0["fields"].get("v:vector_enc", {}).get("extracts", 0)
        seeds0 = stats0["seeds"]

        node.snapshots.restore_snapshot("mem", "s1", {"indices": "src"})
        after = _knn(node, "src")

        # byte-identical serving: same hits, same scores, same order
        assert after == before
        stats1 = columnar.STORE.stats()
        enc1 = stats1["fields"].get("v:vector_enc", {}).get("extracts", 0)
        assert enc1 == enc0, "restore must not re-encode packed vectors"
        assert stats1["seeds"] > seeds0, \
            "restored encoded blocks arrive via the seed sidecar"
        # restore accounted at block level for `_recovery`
        bstats = node.indices.get("src").recovery_block_stats
        assert bstats and all(st["blocks_total"] > 0
                              for st in bstats.values())
    finally:
        node.close()


def test_second_snapshot_ships_only_new_blocks(tmp_path):
    node = Node(str(tmp_path / "data"))
    try:
        node.create_index_with_templates("churn", mappings=_vec_mapping())
        _bulk_vectors(node, "churn", 48)
        node.snapshots.put_repository("mem", {
            "type": "memory", "settings": {"location": "churn-mem"}})
        node.snapshots.create_snapshot("mem", "s1", {"indices": "churn"})
        repo = node.snapshots.get_repository("mem")
        blobs1 = set(repo.store.list_blobs("blobs/"))

        _bulk_vectors(node, "churn", 16, base=48)      # delta ingest
        node.snapshots.create_snapshot("mem", "s2", {"indices": "churn"})
        blobs2 = set(repo.store.list_blobs("blobs/"))

        m1 = repo.get_manifest("s1")["indices"]["churn"]["shards"]["0"]
        m2 = repo.get_manifest("s2")["indices"]["churn"]["shards"]["0"]
        d1 = {e["digest"] for e in m1["blocks"]}
        d2 = {e["digest"] for e in m2["blocks"]}

        # incrementality: s2 uploaded exactly the blocks s1 didn't have
        assert m2["stats"]["blocks_shipped"] == len(blobs2) - len(blobs1)
        assert m2["stats"]["blocks_shipped"] == len(d2 - d1)
        assert m2["stats"]["blocks_reused"] == len(d2 & d1)
        assert m2["stats"]["blocks_reused"] > 0, \
            "sealed generations from s1 must be reused, not re-shipped"
    finally:
        node.close()


# ---------------------------------------------------------------------------
# store-level: trained IVF layout restores without k-means
# ---------------------------------------------------------------------------

def _seg(seg_id, base, mat):
    n = mat.shape[0]
    return Segment(
        seg_id=seg_id, base=base, num_docs=n, postings={},
        field_lengths={}, total_terms={}, doc_values={},
        vectors={"v": (mat, np.ones(n, dtype=bool))},
        ids=[f"d{base + i}" for i in range(n)], sources=[None] * n,
        seq_nos=np.arange(base, base + n, dtype=np.int64))


def _mapper(otype):
    return DenseVectorFieldMapper("v", {
        "type": "dense_vector", "dims": DIMS, "similarity": "cosine",
        "index_options": {"type": otype}})


def _store():
    return VectorStoreShard(segments_background_merge=False)


def test_ivf_layout_restore_skips_training():
    rng = np.random.default_rng(7)
    centers = rng.standard_normal((8, DIMS)).astype(np.float32) * 2.0
    mat = (centers[rng.integers(0, 8, size=900)]
           + 0.4 * rng.standard_normal((900, DIMS)).astype(np.float32))
    reader = ShardReader([SegmentView(_seg(0, 0, mat))])
    mappers = {"v": _mapper("int4_ivf")}

    trained = _store()
    trained.sync(reader, mappers)
    assert trained.knn_stats["ivf_trains"] == 1
    assert trained.knn_stats["ivf_restores"] == 0
    layouts = trained.export_ivf_layout()
    assert "v" in layouts and layouts["v"]["trained_on"] > 0

    restored = _store()
    restored.restore_ivf_layout(layouts)
    restored.sync(reader, mappers)
    assert restored.knn_stats["ivf_trains"] == 0, \
        "restore must re-place rows into snapshotted centroids, not retrain"
    assert restored.knn_stats["ivf_restores"] == 1

    q = mat[3] + 0.1 * rng.standard_normal(DIMS).astype(np.float32)
    rows_a, scores_a = trained.search("v", q, 10)
    rows_b, scores_b = restored.search("v", q, 10)
    np.testing.assert_array_equal(rows_a, rows_b)
    np.testing.assert_allclose(scores_a, scores_b, rtol=1e-6)


# ---------------------------------------------------------------------------
# snapshot stream limiter: bounded concurrency + per-node byte throttle
# ---------------------------------------------------------------------------

class _CountingRepo:
    """In-memory repo that records upload concurrency high-water."""

    def __init__(self):
        import threading
        self.blobs = {}
        self._lock = threading.Lock()
        self._active = 0
        self.max_active = 0

    def has_blob(self, digest):
        return digest in self.blobs

    def put_bytes(self, data):
        import time
        with self._lock:
            self._active += 1
            self.max_active = max(self.max_active, self._active)
        time.sleep(0.02)  # widen the overlap window
        with self._lock:
            self.blobs[block_digest(data)] = data
            self._active -= 1


def test_stream_limiter_token_bucket_sleeps_out_deficit():
    import time
    from elasticsearch_tpu.recovery.snapshot import SnapshotStreamLimiter
    lim = SnapshotStreamLimiter(max_streams=1, max_bytes_per_sec=100_000)
    lim.throttle(100_000)            # consumes the initial 1s burst
    t0 = time.monotonic()
    lim.throttle(15_000)             # ~150ms deficit at 100KB/s
    waited = time.monotonic() - t0
    assert waited >= 0.1
    assert lim.stats["blocks_throttled"] == 1
    assert lim.stats["throttle_time_in_millis"] > 0


def test_stream_limiter_reapplying_same_rate_keeps_spent_allowance():
    from elasticsearch_tpu.recovery.snapshot import SnapshotStreamLimiter
    lim = SnapshotStreamLimiter(max_streams=1, max_bytes_per_sec=100_000)
    lim.throttle(100_000)
    # every shard upload re-reads cluster settings: the SAME rate must
    # not refund the spent bucket...
    lim.configure(max_bytes_per_sec=100_000)
    assert lim._allowance <= 1_000
    # ...but a CHANGED rate restarts the bucket full
    lim.configure(max_bytes_per_sec=50_000)
    assert lim._allowance == 50_000.0


def test_stream_limiter_configure_from_settings_parses_units():
    from elasticsearch_tpu.recovery.snapshot import SnapshotStreamLimiter
    lim = SnapshotStreamLimiter()
    lim.configure_from_settings({"snapshot.max_bytes_per_sec": "2mb",
                                 "snapshot.max_concurrent_streams": "3"})
    assert lim.max_bytes_per_sec == 2 * 1024 * 1024
    assert lim.max_streams == 3
    # garbage values are ignored, not fatal (snapshots must not break on
    # a bad setting)
    lim.configure_from_settings({"snapshot.max_bytes_per_sec": "alot"})
    assert lim.max_bytes_per_sec == 2 * 1024 * 1024


def test_snapshot_shard_uploads_concurrently_under_limiter(tmp_path):
    from elasticsearch_tpu.recovery.snapshot import (
        SnapshotStreamLimiter, snapshot_shard)
    src = Engine(str(tmp_path / "src"), MapperService(MAPPING))
    try:
        # two refresh generations -> >=3 blocks (2 segments + ledger)
        for i in range(10):
            src.index(str(i), {"title": f"doc {i}", "tag": "a", "views": i})
        src.refresh()
        for i in range(10, 20):
            src.index(str(i), {"title": f"doc {i}", "tag": "b", "views": i})
        src.flush()
        repo = _CountingRepo()
        lim = SnapshotStreamLimiter(max_streams=3, max_bytes_per_sec=0)
        entry = snapshot_shard(repo, src, limiter=lim)
        assert entry["stats"]["blocks_shipped"] >= 3
        assert repo.max_active >= 2, "uploads never overlapped"
        assert lim.stats["max_concurrent_streams"] >= 2
        # every manifest digest landed in the repo
        for e in entry["blocks"]:
            assert repo.has_blob(e["digest"])
        # second snapshot of identical state ships nothing
        entry2 = snapshot_shard(repo, src, limiter=lim)
        assert entry2["stats"]["blocks_shipped"] == 0
        assert entry2["stats"]["blocks_reused"] > 0
    finally:
        src.close()


def test_snapshot_stream_stats_ride_nodes_stats(tmp_path):
    """`_nodes/stats indices.recovery.snapshot_streams` surfaces the
    node-wide limiter's counters and configuration."""
    import json
    node = Node(str(tmp_path / "data"))
    try:
        from elasticsearch_tpu.rest.actions import register_all
        from elasticsearch_tpu.rest.controller import RestController
        rc = RestController()
        register_all(rc, node)
        st, body = rc.dispatch("GET", "/_nodes/stats", {}, b"",
                               "application/json")
        assert st == 200
        node_stats = next(iter(body["nodes"].values()))
        streams = node_stats["indices"]["recovery"]["snapshot_streams"]
        for key in ("throttle_time_in_millis", "blocks_throttled",
                    "blocks_uploaded", "bytes_uploaded",
                    "max_concurrent_streams", "max_streams",
                    "max_bytes_per_sec"):
            assert key in streams, key
        json.dumps(streams)
    finally:
        node.close()
