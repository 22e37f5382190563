"""Config-1 kernel benchmark: kNN QPS @ recall@10 >= 0.95 on a SIFT-1M-shaped corpus.

One process that measures on the chip or fails. It times the
binned-reduction Pallas kernel (`ops/pallas_knn_binned.py` — matmul +
in-VMEM bin-max, one small top-k) for BASELINE.md config 1 (SIFT-1M-like,
128-d, cosine, single chip), with the query batches scanned inside one
compiled program. That is a KERNEL number, not a request: the served path
(`_search` over REST) is what `chip_smoke.py` drives and what ROADMAP S0's
benchmark will time; this harness goes with ROADMAP D1.

Baseline model: the reference's execution is a per-document scripted scoring
loop (`ScoreScriptUtils.cosineSimilarity` per doc per query from the Lucene
collector, `QueryPhase.java:171`), emulated as a per-doc numpy dot loop over
a subsample and extrapolated to the full corpus.

Recall@10 is measured against the exact f32 result and gates the metric
(same recall >= 0.95 gate as BASELINE).

Without a TPU it exits non-zero and prints no number: a CPU timing, or an
older record's, is never written under the device metric's name.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...,
"platform", "device_kind", "device_count"}.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

METRIC = "knn_qps_sift1m_cosine_recall_gated"


def main():
    import numpy as np
    import jax
    import jax.numpy as jnp

    from elasticsearch_tpu.ops import knn as knn_ops
    from elasticsearch_tpu.ops import similarity as sim
    from elasticsearch_tpu.ops.pallas_knn_binned import binned_knn_search

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"bench.py measures on a TPU; JAX found platform "
              f"{device.platform!r}. No number is printed.", file=sys.stderr)
        sys.exit(2)

    small = os.environ.get("BENCH_SMALL") == "1"
    n = 131_072 if small else 1_000_000
    d = 128
    k = 10
    batch = 256
    # several batches per dispatch: the scan amortizes the dispatch cost,
    # which is why this is a kernel number and not a request latency
    n_batches = 16 if small else 64
    n_queries = batch * n_batches

    rng = np.random.default_rng(1234)
    # SIFT-like: clustered data so near-neighbor structure exists
    centers = rng.standard_normal((256, d)).astype(np.float32) * 2.0
    assign = rng.integers(0, 256, size=n)
    vectors = centers[assign] + rng.standard_normal((n, d)).astype(np.float32)
    q_assign = rng.integers(0, n, size=n_queries)
    queries = vectors[q_assign] + 0.3 * rng.standard_normal((n_queries, d)).astype(np.float32)

    corpus = knn_ops.build_corpus(vectors, metric=sim.COSINE, dtype="bf16")
    qstack = jnp.asarray(queries.reshape(n_batches, batch, d))
    jax.block_until_ready(corpus)

    @functools.partial(jax.jit, static_argnames=("kk",))
    def search_all(qs, c, kk):
        def body(carry, qb):
            return carry, binned_knn_search(qb, c, kk)
        _, out = jax.lax.scan(body, None, qs)
        return out

    # warmup/compile
    out = search_all(qstack, corpus, k)
    np.asarray(out[1])

    # timed runs: whole stack in one dispatch; report amortized throughput
    # (min over runs — the steady-state device rate, matching bench_matrix)
    runs = []
    for _ in range(3 if not small else 2):
        t0 = time.perf_counter()
        out = search_all(qstack, corpus, k)
        all_ids = np.asarray(out[1])
        runs.append(time.perf_counter() - t0)
    total_time = float(np.min(runs))
    qps = n_queries / total_time
    batch_ms = total_time / n_batches * 1000.0

    # recall@10 of the fast path vs exact f32 (first batch)
    s_ref, ids_ref = knn_ops.knn_search(qstack[0], corpus, k=k,
                                        metric=sim.COSINE, precision="f32")
    ids_ref = np.asarray(ids_ref)
    hits = sum(len(set(all_ids[0][r]) & set(ids_ref[r])) for r in range(batch))
    recall = hits / (batch * k)

    # baseline: per-doc scripted loop emulation (the reference's per-doc
    # CosineSimilarity call), measured on a subsample and scaled to n docs
    sub = 20_000
    subv = vectors[:sub]
    sub_norms = np.linalg.norm(subv, axis=1)
    q0 = queries[0]
    q0n = np.linalg.norm(q0)
    t0 = time.perf_counter()
    scores = np.empty(sub, dtype=np.float32)
    for j in range(sub):
        v = subv[j]
        scores[j] = float(np.dot(q0, v)) / (q0n * sub_norms[j])
    np.argpartition(-scores, k)[:k]
    t_loop = time.perf_counter() - t0
    baseline_qps = 1.0 / (t_loop * (n / sub))

    out = {
        "metric": METRIC,
        "value": round(qps, 2),
        "unit": "qps",
        "vs_baseline": round(qps / baseline_qps, 1),
        "recall_at_10": round(recall, 4),
        "amortized_batch_ms": round(batch_ms, 2),
        "batch_size": batch,
        "n_docs": n,
        "dims": d,
        "kernel": "pallas_binned",
        "baseline_qps_scripted_loop": round(baseline_qps, 4),
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
    }

    print(json.dumps(out))
    if recall < 0.95:
        sys.exit(1)


if __name__ == "__main__":
    main()
