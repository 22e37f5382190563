#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user would call:

    python -m elasticsearch_tpu.server   (ONE child process; it owns the chip)
    REST _bulk -> _refresh -> device corpus -> REST _search {knn} ->
    dispatcher -> device kernel -> JSON hits

and checks what comes out against plain references computed here, in a
parent that never imports jax and talks HTTP only.

Deployment: BASELINE.json config 1 — `dense_vector` 128-d, cosine,
single-shard index, bf16 device corpus; target 1,048,576 docs through
`_bulk`, vectors made from `--seed`. Rows (never dims) are cut to the
largest power of two REST ingest can load inside the ingest budget, not
below 131,072; the cut and the measured rate are printed.

Phases (each fatal): ingest; one kNN search; one filtered kNN (~10 %
selectivity, exact route); a burst of 64 concurrent kNN searches (the
batcher forms batches); one `size: 0` terms + date_histogram aggregation
(device agg route); `_nodes/stats` checks (the Pallas kNN kernel,
`knn.exact` and an `aggs.*` kernel were dispatched, zero searches on the
host mirror, zero `device_error` agg reasons, platform == "tpu"); then the
server exits and a second one starts on the same data and compile-cache
directories, answers the same kNN requests one by one, and must add no
entry to the compile cache.

    python chip_smoke.py                  one chip (what the driver runs)
    python chip_smoke.py --chips 4        ONLY the four-chip mesh phase and
                                          the mesh-off run it is compared with
    JAX_PLATFORMS=cpu python chip_smoke.py --rows 4096 --rehearse
                                          the same phases on the CPU

`--rehearse` relaxes exactly two checks — the platform, and "the Pallas
kernel key was dispatched" (the store does not route to it on the CPU) —
and lets `--rows` go under 131,072. The last line of stdout is the
contract line; its device is whatever the server child reported, so a
rehearsal can never read as a chip run. Without the flag and without a
chip the script exits non-zero and prints no contract line.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
MIN_ROWS = 1 << 17          # rows are never cut below 131,072
K = 10
BURST = 64
RECALL_FLOOR = 0.95         # BASELINE.json's gate
BULK_DOCS = 2048            # docs per _bulk request, and per data block
N_TAGS = 10                 # `tag` keyword: 10 values -> ~10 % selectivity
N_CATS = 16                 # `cat` keyword: the terms agg's buckets
DAY_MS = 86_400_000
T0_MS = 1_700_000_000_000   # date_histogram base (2023-11-14T22:13:20Z)
N_DAYS = 30


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# data, made from the seed in blocks — and the plain references
# ---------------------------------------------------------------------------

class Data:
    """Block b of the corpus is a function of (seed, b) alone, so only
    what is ingested is ever generated and a cut changes no row. Vectors
    are rounded to 4 decimals BEFORE they are sent: the float32 the
    server parses from the JSON text is bit-for-bit the float32 the
    oracle scores."""

    def __init__(self, seed: int, rows: int, dims: int):
        self.seed, self.rows, self.dims = seed, rows, dims
        rng = np.random.default_rng([seed, 0])
        # clustered, like real embeddings: 256 centres + noise
        self.centres = rng.standard_normal((256, dims)).astype(np.float32)
        self._blocks: dict = {}
        anchors = self._block(0)[0].astype(np.float32)
        q = (anchors[rng.integers(0, len(anchors), size=BURST + 2)]
             + 0.3 * rng.standard_normal((BURST + 2, dims),
                                         dtype=np.float32))
        self.queries = np.round(q.astype(np.float64), 4)

    def _block(self, b: int):
        got = self._blocks.get(b)
        if got is None:
            rng = np.random.default_rng([self.seed, 1, b])
            n = BULK_DOCS
            vecs = (self.centres[rng.integers(0, 256, size=n)]
                    + 0.6 * rng.standard_normal((n, self.dims),
                                                dtype=np.float32))
            got = (np.round(vecs.astype(np.float64), 4),
                   rng.integers(0, N_TAGS, size=n),
                   rng.integers(0, N_CATS, size=n),
                   T0_MS + rng.integers(0, N_DAYS * DAY_MS, size=n))
            self._blocks[b] = got
        return got

    def n_blocks(self) -> int:
        return -(-self.rows // BULK_DOCS)

    def bulk_body(self, b: int, index: str) -> bytes:
        vec64, tags, cats, ts = self._block(b)
        lo = b * BULK_DOCS
        n = min(BULK_DOCS, self.rows - lo)
        lines = []
        for j, vec in enumerate(vec64[:n].tolist()):
            lines.append('{"index":{"_index":"%s","_id":"%d"}}'
                         % (index, lo + j))
            lines.append(
                '{"v":%s,"tag":"t%d","cat":"c%d","ts":%d,"n":%d}'
                % (json.dumps(vec, separators=(",", ":")), tags[j],
                   cats[j], ts[j], lo + j))
        return ("\n".join(lines) + "\n").encode()

    def seal(self) -> None:
        """Ingest is over: materialise what was sent as flat arrays."""
        parts = [self._block(b) for b in range(self.n_blocks())]
        self.vectors = np.concatenate(
            [p[0] for p in parts]).astype(np.float32)[:self.rows]
        self.tags = np.concatenate([p[1] for p in parts])[:self.rows]
        self.cats = np.concatenate([p[2] for p in parts])[:self.rows]
        self.ts = np.concatenate([p[3] for p in parts])[:self.rows]
        self._blocks.clear()

    def oracle_topk(self, queries: np.ndarray,
                    allowed: Optional[np.ndarray] = None) -> np.ndarray:
        """Plain numpy f32 cosine top-K ids (the reference)."""
        v = self.vectors
        vn = v / np.maximum(np.linalg.norm(v, axis=1, keepdims=True), 1e-30)
        q = queries.astype(np.float32)
        qn = q / np.maximum(np.linalg.norm(q, axis=1, keepdims=True), 1e-30)
        out = np.empty((len(q), K), dtype=np.int64)
        for lo in range(0, len(q), 16):
            s = qn[lo:lo + 16] @ vn.T
            if allowed is not None:
                s[:, ~allowed] = -np.inf
            part = np.argpartition(-s, K, axis=1)[:, :K]
            order = np.argsort(-np.take_along_axis(s, part, axis=1), axis=1)
            out[lo:lo + 16] = np.take_along_axis(part, order, axis=1)
        return out

    def expected_aggs(self) -> Tuple[dict, dict]:
        """Plain counts for the terms and date_histogram aggregations."""
        cats = {f"c{c}": int(n) for c, n in
                enumerate(np.bincount(self.cats, minlength=N_CATS)) if n}
        day0 = T0_MS // DAY_MS
        days = {int((day0 + d) * DAY_MS): int(n) for d, n in
                enumerate(np.bincount(self.ts // DAY_MS - day0)) if n}
        return cats, days


# ---------------------------------------------------------------------------
# the server child and its HTTP surface
# ---------------------------------------------------------------------------

def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    """One `python -m elasticsearch_tpu.server` child. It is the only
    process that touches JAX, so it is the one that holds the chip."""

    def __init__(self, out_dir: str, data_dir: str, tag: str,
                 settings: Tuple[str, ...] = ()):
        self.tag = tag
        self.port = free_port()
        self.log_path = os.path.join(out_dir, f"server_{tag}.log")
        self._log = open(self.log_path, "wb")
        cmd = [sys.executable, "-m", "elasticsearch_tpu.server",
               "--port", str(self.port), "--data", data_dir]
        for kv in settings:
            cmd += ["-E", kv]
        self.started = time.monotonic()
        self.proc = subprocess.Popen(cmd, cwd=HERE, stdout=self._log,
                                     stderr=subprocess.STDOUT)

    def alive(self) -> bool:
        return self.proc.poll() is None

    def request(self, method: str, path: str, body=None, timeout=600.0):
        if not self.alive():
            raise SmokeFailure(f"server child [{self.tag}] exited with "
                               f"code {self.proc.returncode}")
        if body is not None and not isinstance(body, bytes):
            body = json.dumps(body).encode()
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=timeout)
        try:
            ctype = ("application/x-ndjson" if path.endswith("_bulk")
                     else "application/json")
            conn.request(method, path, body=body,
                         headers={"Content-Type": ctype})
            resp = conn.getresponse()
            raw = resp.read()
        except (OSError, http.client.HTTPException) as e:
            raise SmokeFailure(f"{method} {path}: {type(e).__name__}: {e}")
        finally:
            conn.close()
        try:
            parsed = json.loads(raw) if raw else None
        except ValueError:
            parsed = raw.decode(errors="replace")
        return resp.status, parsed

    def ok(self, method: str, path: str, body=None, timeout=600.0):
        status, parsed = self.request(method, path, body, timeout)
        if status >= 300:
            raise SmokeFailure(f"{method} {path} -> {status}: "
                               f"{json.dumps(parsed)[:1200]}")
        return parsed

    def wait_ready(self, limit: float = 300.0) -> float:
        deadline = time.monotonic() + limit
        while time.monotonic() < deadline:
            if not self.alive():
                raise SmokeFailure(
                    f"server child [{self.tag}] exited with code "
                    f"{self.proc.returncode} before it answered "
                    f"/_cluster/health")
            try:
                status, _ = self.request("GET", "/_cluster/health",
                                         timeout=2.0)
                if status == 200:
                    return time.monotonic() - self.started
            except SmokeFailure:
                pass
            time.sleep(0.25)
        raise SmokeFailure(f"server [{self.tag}] did not answer "
                           f"/_cluster/health within {limit:.0f}s")

    def node_stats(self) -> dict:
        (node,) = self.ok("GET", "/_nodes/stats")["nodes"].values()
        return node

    def stop(self) -> None:
        if self.alive():
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30)
        self._log.close()


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def create_index(srv: Server, index: str, dims: int,
                 index_options: Optional[dict] = None) -> None:
    vec = {"type": "dense_vector", "dims": dims, "similarity": "cosine"}
    if index_options:
        vec["index_options"] = index_options
    srv.ok("PUT", f"/{index}", {
        "settings": {"number_of_shards": 1, "number_of_replicas": 0,
                     "refresh_interval": "-1"},
        "mappings": {"properties": {
            "v": vec, "tag": {"type": "keyword"},
            "cat": {"type": "keyword"}, "ts": {"type": "date"},
            "n": {"type": "long"}}}})


def ingest(srv: Server, data: Data, index: str, budget_s: float,
           floor: int) -> float:
    """`_bulk` the corpus; returns docs/s. The next body is built while
    the server indexes the current one. After the first 8 requests the
    measured rate decides the cut: the largest power of two (>= floor)
    that loads inside `budget_s`."""
    t0 = time.monotonic()
    decided = False
    with ThreadPoolExecutor(max_workers=1) as pool:
        nxt = pool.submit(data.bulk_body, 0, index)
        b = 0
        while b < data.n_blocks():
            body = nxt.result()
            if b + 1 < data.n_blocks():
                nxt = pool.submit(data.bulk_body, b + 1, index)
            resp = srv.ok("POST", "/_bulk", body)
            if resp.get("errors"):
                bad = next(i for i in resp["items"]
                           if i["index"].get("error"))
                raise SmokeFailure(f"_bulk item failed: {json.dumps(bad)}")
            b += 1
            if not decided and b == 8 and b < data.n_blocks():
                decided = True
                rate = b * BULK_DOCS / (time.monotonic() - t0)
                fit = int(rate * budget_s)
                cut = max(floor, 1 << (max(fit, 1).bit_length() - 1))
                if cut < data.rows:
                    say(f"ingest_cut rows {data.rows} -> {cut} (measured "
                        f"{rate:.0f} docs/s over the first "
                        f"{b * BULK_DOCS} docs; the {budget_s:.0f}s ingest "
                        f"budget holds {fit})")
                    data.rows = cut
    data.seal()
    return data.rows / (time.monotonic() - t0)


def knn_body(query: np.ndarray, flt: Optional[dict] = None) -> dict:
    knn = {"field": "v", "query_vector": query.tolist(), "k": K,
           "num_candidates": 100}
    if flt is not None:
        knn["filter"] = flt
    return {"size": K, "_source": False, "knn": knn}


def hit_ids(resp: dict) -> List[int]:
    shards = resp["_shards"]
    if shards.get("failed"):
        raise SmokeFailure(f"_search reported failed shards: "
                           f"{json.dumps(shards)[:1200]}")
    return [int(h["_id"]) for h in resp["hits"]["hits"]]


def recall(got: List[List[int]], want: np.ndarray) -> float:
    inter = sum(len(set(g) & set(w.tolist())) for g, w in zip(got, want))
    return inter / float(want.size)


def search_twice(srv: Server, index: str, body: dict):
    """Send one request twice (the request cache is off for every search
    this script sends): the first call may compile, the second is a
    dispatch-cache hit — so every kernel the phase rides shows a HIT in
    `indices.dispatch` (a warmup compile alone leaves only a miss) — and
    the same request must answer the same. Returns (response, first-call
    seconds, second-call ms)."""
    t = time.monotonic()
    first = srv.ok("POST", f"/{index}/_search?request_cache=false", body)
    first_s = time.monotonic() - t
    t = time.monotonic()
    again = srv.ok("POST", f"/{index}/_search?request_cache=false", body)
    again_ms = (time.monotonic() - t) * 1000.0
    for r in (first, again):
        r.pop("took", None)
    if first != again:
        raise SmokeFailure("the same request answered differently twice: "
                           f"{json.dumps(first)[:300]} vs "
                           f"{json.dumps(again)[:300]}")
    return first, first_s, again_ms


def knn_sequential(srv: Server, data: Data, index: str) -> dict:
    """The single kNN search and the filtered one, each sent twice."""
    out = {}
    resp, out["first_search_s"], out["single_ms"] = search_twice(
        srv, index, knn_body(data.queries[0]))
    out["single"] = hit_ids(resp)
    resp, out["filtered_first_s"], out["filtered_ms"] = search_twice(
        srv, index, knn_body(data.queries[BURST + 1],
                             {"term": {"tag": "t0"}}))
    out["filtered"] = hit_ids(resp)
    bad = [i for i in out["filtered"] if data.tags[i] != 0]
    if bad:
        raise SmokeFailure(f"filtered kNN returned docs outside the "
                           f"filter: {bad}")
    return out


def knn_burst(srv: Server, data: Data, index: str,
              at_once: bool) -> Tuple[List[List[int]], dict]:
    """The 64 burst queries: all at once (the batcher forms batches), or
    one after another (every request its own batch of 1)."""
    def one(i):
        t1 = time.monotonic()
        ids = hit_ids(srv.ok("POST", f"/{index}/_search?request_cache=false",
                             knn_body(data.queries[1 + i])))
        return ids, (time.monotonic() - t1) * 1000.0
    t = time.monotonic()
    with ThreadPoolExecutor(max_workers=BURST if at_once else 1) as pool:
        got = list(pool.map(one, range(BURST)))
    return [ids for ids, _ in got], {
        "wall_ms": (time.monotonic() - t) * 1000.0,
        "p50_ms": float(np.median([ms for _, ms in got]))}


def check_recall(name: str, value: float, floor: float) -> None:
    say(f"{name} {value:.4f} (floor {floor})")
    if not value >= floor:
        raise SmokeFailure(f"{name} {value:.4f} is under {floor}")


def agg_phase(srv: Server, data: Data, index: str) -> float:
    body = {"size": 0, "track_total_hits": True,
            "aggs": {
                "cats": {"terms": {"field": "cat", "size": N_CATS}},
                "days": {"date_histogram": {"field": "ts",
                                            "fixed_interval": "1d"}}}}
    resp, _first_s, ms = search_twice(srv, index, body)
    if resp["_shards"].get("failed"):
        raise SmokeFailure(f"agg search reported failed shards: "
                           f"{json.dumps(resp['_shards'])[:1200]}")
    want_cats, want_days = data.expected_aggs()
    got_cats = {b["key"]: b["doc_count"]
                for b in resp["aggregations"]["cats"]["buckets"]}
    got_days = {b["key"]: b["doc_count"]
                for b in resp["aggregations"]["days"]["buckets"]
                if b["doc_count"]}
    if got_cats != want_cats:
        raise SmokeFailure(f"terms agg differs from the plain count: "
                           f"{got_cats} vs {want_cats}")
    if got_days != want_days:
        raise SmokeFailure("date_histogram differs from the plain count: "
                           f"{got_days} vs {want_days}")
    if resp["hits"]["total"]["value"] != data.rows:
        raise SmokeFailure(f"total hits {resp['hits']['total']} != "
                           f"{data.rows}")
    return ms


def wait_compiles_settle(srv: Server, quiet_s: float = 3.0,
                         limit_s: float = 300.0) -> int:
    """Block until no compile is in flight and the dispatcher's compile
    count has not moved for `quiet_s`: the background warmup grid is
    done. A server stopped mid-warmup would leave the next start entries
    to add, and a search that overtakes the warmup thread compiles its
    kernel from another call path."""
    deadline = time.monotonic() + limit_s
    last, since = -1, time.monotonic()
    while time.monotonic() < deadline:
        d = srv.node_stats()["indices"]["dispatch"]
        if d["compiles"] != last or d["compiling"]:
            last, since = d["compiles"], time.monotonic()
        elif time.monotonic() - since >= quiet_s:
            return last
        time.sleep(0.5)
    raise SmokeFailure(f"compiles still arriving after {limit_s:.0f}s")


def dispatch_hits(node: dict, prefix: str) -> int:
    """Dispatch-cache HITS of the kernels under `prefix`: calls that ran
    an executable (a miss may be a warmup compile nobody called)."""
    buckets = node["indices"]["dispatch"]["buckets"]
    return sum(b["hits"] for key, b in buckets.items()
               if key.startswith(prefix))


def device_of(node: dict) -> dict:
    dev = node["device"]
    return {"platform": dev["platform"], "kind": dev["device_kind"],
            "count": dev["count"]}


def stats_checks(node: dict, rehearse: bool, knn_key: str,
                 expect_aggs: bool = True) -> None:
    """What ran, from `_nodes/stats`: the platform, the kernels, and
    that nothing was answered from the host behind the device's back.
    `knn_key` is the kernel unfiltered kNN must have ridden — the one
    check besides the platform that a rehearsal relaxes."""
    dev = node["device"]
    say(f"device platform={dev['platform']} kind={dev['device_kind']!r} "
        f"count={dev['count']} peak_hbm_bytes="
        f"{[m.get('peak_bytes_in_use') for m in dev['memory']]}")
    say(f"cost_model {json.dumps(dev['cost_model'])}")
    if dev["platform"] != "tpu" and not rehearse:
        raise SmokeFailure(f"the node ran on platform "
                           f"{dev['platform']!r}, not 'tpu'")
    d = node["indices"]["dispatch"]
    hits = {knn_key: dispatch_hits(node, knn_key + "[")}
    if knn_key != "mesh.knn":
        hits["knn.exact"] = dispatch_hits(node, "knn.exact[")
    if expect_aggs:
        hits["aggs.*"] = dispatch_hits(node, "aggs.")
    say(f"dispatch compiles={d['compiles']} "
        f"compile_s={d['compile_nanos'] / 1e9:.2f} hits={d['hits']} "
        f"out_of_grid={d['out_of_grid_compiles']} "
        f"kernel_hits={json.dumps(hits)}")
    for key, n in hits.items():
        if n == 0 and not (rehearse and key == "knn.binned"):
            raise SmokeFailure(f"no device dispatch of [{key}] in "
                               f"_nodes/stats indices.dispatch")
    knn = node["indices"]["knn"]
    say(f"knn searches={knn['searches']} "
        f"host_mirror_searches={knn['host_mirror_searches']} "
        f"mesh_searches={knn['mesh_searches']}")
    if knn["host_mirror_searches"]:
        raise SmokeFailure(
            f"{knn['host_mirror_searches']} searches went to the HOST "
            f"mirror")
    if not expect_aggs:
        return
    aggs = node["indices"]["aggs"]
    reasons = aggs.get("fallback_reasons", {})
    say(f"aggs device_nodes={aggs.get('device_nodes')} "
        f"host_nodes={aggs.get('host_nodes')} "
        f"router_host_routed={aggs.get('router_host_routed')} "
        f"reasons={json.dumps(reasons)}")
    if not aggs.get("device_nodes"):
        raise SmokeFailure("no aggregation node ran on the device")
    if "device_error" in reasons:
        raise SmokeFailure(f"device agg errors: {reasons['device_error']}")


def compile_cache_dir() -> str:
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(HERE, ".jax_cache"))


def cache_entries(cache_dir: str) -> set:
    try:
        return {n for n in os.listdir(cache_dir)
                if not n.endswith("-atime")}
    except OSError:
        return set()


def check_platform(srv: Server, rehearse: bool) -> None:
    """Fail before the minutes of ingest, not after them."""
    dev = srv.node_stats()["device"]
    if dev["platform"] != "tpu" and not rehearse:
        raise SmokeFailure(f"JAX found no accelerator: the server runs on "
                           f"platform {dev['platform']!r} "
                           f"({dev['device_kind']!r})")


def load_corpus(srv: Server, data: Data, index: str, args) -> float:
    """ingest -> refresh, with the rates printed; seals `data`. Returns
    the seconds spent in `_bulk`."""
    floor = MIN_ROWS if not args.rehearse else min(MIN_ROWS, data.rows)
    t = time.monotonic()
    rate = ingest(srv, data, index, args.ingest_budget, floor)
    bulk_s = time.monotonic() - t
    say(f"ingest rows={data.rows} dims={data.dims} docs_per_s={rate:.0f}")
    if data.rows < MIN_ROWS and not args.rehearse:
        raise SmokeFailure(f"{data.rows} rows is under {MIN_ROWS}")
    t = time.monotonic()
    srv.ok("POST", f"/{index}/_refresh")
    say(f"refresh_s={time.monotonic() - t:.1f} (device corpus build)")
    return bulk_s


# ---------------------------------------------------------------------------
# one chip: the default run
# ---------------------------------------------------------------------------

def run_one_chip(args, out_dir: str) -> dict:
    index = "smoke"
    data_dir = os.path.join(out_dir, "data")
    data = Data(args.seed, args.rows or (1 << 20), dims=128)
    say(f"deployment BASELINE.json config 1: rows={data.rows} dims=128 "
        f"cosine bf16 single shard, seed={args.seed}")
    cache_dir = compile_cache_dir()
    say(f"compile_cache dir={cache_dir} "
        f"entries_at_start={len(cache_entries(cache_dir))}")

    srv = Server(out_dir, data_dir, "cold")
    try:
        up_cold = srv.wait_ready()
        say(f"server_cold pid={srv.proc.pid} port={srv.port} "
            f"up_s={up_cold:.1f}")
        check_platform(srv, args.rehearse)
        create_index(srv, index, data.dims)
        bulk_s = load_corpus(srv, data, index, args)
        # the references, computed while nothing else runs here
        want = data.oracle_topk(data.queries[:1 + BURST])
        allowed = data.tags == 0
        want_f = data.oracle_topk(data.queries[BURST + 1:], allowed)
        say(f"filter selectivity={allowed.mean():.3f}")

        wait_compiles_settle(srv)       # the warmup grid, if the store warms
        seq = knn_sequential(srv, data, index)
        # start -> first answer, less the time spent loading documents
        cold_s = time.monotonic() - srv.started - bulk_s
        burst, bt = knn_burst(srv, data, index, at_once=True)
        say(f"knn first_search_s={seq['first_search_s']:.2f} "
            f"single_ms={seq['single_ms']:.2f} "
            f"filtered_first_s={seq['filtered_first_s']:.2f} "
            f"filtered_ms={seq['filtered_ms']:.2f} "
            f"burst_wall_ms={bt['wall_ms']:.1f} "
            f"burst_p50_ms={bt['p50_ms']:.1f}")
        r = recall([seq["single"]] + burst, want)
        check_recall("recall_at_10", 0.0 if args.break_recall else r,
                     RECALL_FLOOR)
        check_recall("filtered_recall_at_10",
                     recall([seq["filtered"]], want_f), RECALL_FLOOR)
        agg_ms = agg_phase(srv, data, index)
        say(f"aggs terms+date_histogram over {data.rows} docs equal the "
            f"plain count; ms={agg_ms:.1f}")
        # flush now (a flush refreshes, and a refresh may start background
        # warmups), so that nothing is in flight when the server is stopped
        srv.ok("POST", f"/{index}/_flush")
        wait_compiles_settle(srv)
        node = srv.node_stats()
        stats_checks(node, args.rehearse, "knn.binned")
        sched = node["indices"]["knn"].get("scheduler", {})
        say(f"batcher batches={sched.get('batches')} "
            f"requests={sched.get('requests')}")
        if not sched.get("batches") or \
                sched["requests"] <= sched["batches"]:
            raise SmokeFailure("the burst never formed a batch: "
                               f"{json.dumps(sched)}")
        device = device_of(node)
    finally:
        srv.stop()
    entries_cold = cache_entries(cache_dir)
    say(f"server_cold exit_code={srv.proc.returncode} "
        f"cache_entries={len(entries_cold)}")
    if not entries_cold:
        raise SmokeFailure(f"the compile cache at {cache_dir} is empty")

    # second start: same data directory, same cache directory; the same
    # kNN requests, one by one (a burst would form whatever batch sizes
    # the moment gives, and a new size is a new executable)
    srv2 = Server(out_dir, data_dir, "warm")
    try:
        up_warm = srv2.wait_ready()
        count = srv2.ok("GET", f"/{index}/_count")["count"]
        recovery_s = time.monotonic() - srv2.started
        if count != data.rows:
            raise SmokeFailure(f"the recovered index holds {count} docs, "
                               f"not {data.rows}")
        say(f"server_warm pid={srv2.proc.pid} up_s={up_warm:.1f} "
            f"recovery_s={recovery_s:.1f} docs={count}")
        wait_compiles_settle(srv2)
        seq2 = knn_sequential(srv2, data, index)
        warm_s = time.monotonic() - srv2.started
        one_by_one, _ = knn_burst(srv2, data, index, at_once=False)
        for name in ("single", "filtered"):
            if seq2[name] != seq[name]:
                raise SmokeFailure(
                    f"the restarted server answered the {name} kNN "
                    f"request differently: {seq2[name]} vs {seq[name]}")
        check_recall("warm_recall_at_10",
                     recall([seq2["single"]] + one_by_one, want),
                     RECALL_FLOOR)
        wait_compiles_settle(srv2)
        d2 = srv2.node_stats()["indices"]["dispatch"]
        say(f"warm dispatch compiles={d2['compiles']} "
            f"compile_s={d2['compile_nanos'] / 1e9:.2f}")
    finally:
        srv2.stop()
    entries_warm = cache_entries(cache_dir)
    say(f"time_to_first_answer cold_s={cold_s:.1f} warm_s={warm_s:.1f} "
        f"(server start, warmup grid compiled, first kNN answer; cold "
        f"leaves out the {bulk_s:.0f}s spent in _bulk)")
    say(f"first_search cold_s={seq['first_search_s']:.2f} "
        f"warm_s={seq2['first_search_s']:.2f}")
    say(f"compile_cache entries cold={len(entries_cold)} "
        f"warm={len(entries_warm)}")
    if entries_warm != entries_cold:
        added = sorted(n[:60] for n in entries_warm - entries_cold)
        raise SmokeFailure(f"the second start changed the compile cache: "
                           f"{len(entries_cold)} -> {len(entries_warm)} "
                           f"entries; added {added}")
    return device


# ---------------------------------------------------------------------------
# four chips: the mesh phase and the mesh-off run it is compared with
# ---------------------------------------------------------------------------

def run_four_chips(args, out_dir: str) -> dict:
    """BASELINE.json config 4's shape on a 2x2 host: int8 768-d cosine,
    one index whose corpus rows shard over the four devices
    (`search.mesh.enabled: true`, `search.mesh.num_shards: 4`), served
    as ONE SPMD program. Then the same index from the same data
    directory with the mesh off, in a second server started after the
    first has exited."""
    index = "smoke4"
    data_dir = os.path.join(out_dir, "data")
    data = Data(args.seed, args.rows or (1 << 22), dims=768)
    n_dev = 4
    say(f"deployment BASELINE.json config 4 shape: rows={data.rows} "
        f"dims=768 cosine int8_flat, mesh of {n_dev}, seed={args.seed}")

    mesh_settings = ("search.mesh.enabled=true",
                     f"search.mesh.num_shards={n_dev}")
    if args.rehearse:
        # a rehearsal's few thousand rows sit under the policy's default
        # row floor (32,768); the chip run keeps the default
        mesh_settings += ("search.mesh.min_rows=1",)
    srv = Server(out_dir, data_dir, "mesh", mesh_settings)
    try:
        up = srv.wait_ready()
        say(f"server_mesh pid={srv.proc.pid} port={srv.port} up_s={up:.1f}")
        check_platform(srv, args.rehearse)
        create_index(srv, index, data.dims, {"type": "int8_flat"})
        load_corpus(srv, data, index, args)
        want = data.oracle_topk(data.queries[:1 + BURST])
        allowed = data.tags == 0
        want_f = data.oracle_topk(data.queries[BURST + 1:], allowed)
        seq = knn_sequential(srv, data, index)
        burst, bt = knn_burst(srv, data, index, at_once=True)
        say(f"mesh knn first_search_s={seq['first_search_s']:.2f} "
            f"single_ms={seq['single_ms']:.2f} "
            f"filtered_ms={seq['filtered_ms']:.2f} "
            f"burst_wall_ms={bt['wall_ms']:.1f}")
        mesh_answers = [seq["single"]] + burst
        check_recall("mesh_recall_at_10", recall(mesh_answers, want),
                     RECALL_FLOOR)
        check_recall("mesh_filtered_recall_at_10",
                     recall([seq["filtered"]], want_f), RECALL_FLOOR)
        node = srv.node_stats()
        stats_checks(node, args.rehearse, "mesh.knn", expect_aggs=False)
        mesh = node["indices"]["mesh"]
        say(f"mesh available={mesh['available']} "
            f"num_shards={mesh['num_shards']} dp={mesh['dp']} "
            f"router={json.dumps(mesh['router'].get('reasons'))} "
            f"mesh_decisions={mesh['router']['mesh']} "
            f"single_device_decisions={mesh['router']['single_device']}")
        if not mesh["available"] or mesh["num_shards"] != n_dev:
            raise SmokeFailure(f"no {n_dev}-shard serving mesh: "
                               f"{json.dumps(mesh)[:600]}")
        knn = node["indices"]["knn"]
        if knn["mesh_searches"] < 3 or mesh["router"]["single_device"]:
            raise SmokeFailure(
                f"searches left the mesh: mesh_searches="
                f"{knn['mesh_searches']} router="
                f"{json.dumps(mesh['router'])[:600]}")
        device = device_of(node)
        if device["count"] != n_dev:
            raise SmokeFailure(f"the server saw {device['count']} "
                               f"devices, not {n_dev}")
        # the corpus must be resident on every device, not the first:
        # each holds at least its quarter of the int8 matrix
        in_use = [m.get("bytes_in_use") for m in node["device"]["memory"]]
        shard_bytes = data.rows * data.dims // n_dev
        say(f"per_device_bytes_in_use={in_use} "
            f"int8_shard_bytes={shard_bytes}")
        if not args.rehearse and not all(
                b is not None and b >= shard_bytes for b in in_use):
            raise SmokeFailure("the sharded corpus is not resident on "
                               f"all {n_dev} devices: {in_use}")
        srv.ok("POST", f"/{index}/_flush")
    finally:
        srv.stop()

    srv2 = Server(out_dir, data_dir, "single",
                  ("search.mesh.enabled=false",))
    try:
        up = srv2.wait_ready()
        count = srv2.ok("GET", f"/{index}/_count")["count"]
        if count != data.rows:
            raise SmokeFailure(f"the recovered index holds {count} docs, "
                               f"not {data.rows}")
        say(f"server_single pid={srv2.proc.pid} up_s={up:.1f} "
            f"recovery_s={time.monotonic() - srv2.started:.1f}")
        seq2 = knn_sequential(srv2, data, index)
        one_by_one, _ = knn_burst(srv2, data, index, at_once=False)
        single_answers = [seq2["single"]] + one_by_one
        node2 = srv2.node_stats()
        if node2["indices"]["knn"]["mesh_searches"]:
            raise SmokeFailure("the mesh-off server used the mesh")
        check_recall("single_device_recall_at_10",
                     recall(single_answers, want), RECALL_FLOOR)
        # the filtered request rides the exact kernel on both: same ids.
        # Unfiltered, the mesh is exact per shard while one chip rides
        # the binned kernel (one candidate per 64-row bin, recall about
        # 1 - C(k,2)/n_bins by design), so a rare id may differ
        if sorted(seq2["filtered"]) != sorted(seq["filtered"]):
            raise SmokeFailure(
                f"mesh and mesh-off disagree on the filtered request: "
                f"{seq['filtered']} vs {seq2['filtered']}")
        same = sum(sorted(a) == sorted(b)
                   for a, b in zip(mesh_answers, single_answers))
        say(f"mesh_vs_single identical_answers={same}/"
            f"{len(mesh_answers)} filtered_identical=True")
        check_recall("mesh_vs_single_overlap",
                     recall(mesh_answers, np.asarray(single_answers)),
                     RECALL_FLOOR)
    finally:
        srv2.stop()
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=0,
                    help="corpus rows (default: the deployment's size)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: relaxes the platform check and "
                         "the Pallas-kernel-dispatched check, nothing else")
    ap.add_argument("--ingest-budget", type=float, default=400.0,
                    help="seconds REST ingest may take before rows are cut")
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out",
                                                  "smoke"))
    # fault injection for tests/test_chip_smoke.py: the script must not
    # be able to exit 0 past a failed phase
    ap.add_argument("--break-recall", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    out_dir = os.path.abspath(args.out)
    shutil.rmtree(out_dir, ignore_errors=True)     # no stale data or logs
    os.makedirs(out_dir)
    # a SIGTERM (a test's time limit, the driver's) must still stop the
    # server child: turn it into an exception the finally blocks see
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    t0 = time.monotonic()
    try:
        run = run_four_chips if args.chips == 4 else run_one_chip
        try:
            device = run(args, out_dir)
        finally:
            # the logs stay; the index does not (it would not fit what
            # the chip tool brings back)
            shutil.rmtree(os.path.join(out_dir, "data"), ignore_errors=True)
    except SmokeFailure as e:
        say(f"FAILED: {e}")
        for tag in ("cold", "warm", "mesh", "single"):
            path = os.path.join(out_dir, f"server_{tag}.log")
            if os.path.exists(path):
                with open(path, "rb") as f:
                    tail = f.read().splitlines()[-25:]
                say(f"--- server_{tag}.log (tail) ---")
                say(b"\n".join(ln[:600] for ln in tail).decode(
                    errors="replace"))
        return 1
    say(f"total_s={time.monotonic() - t0:.1f}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
