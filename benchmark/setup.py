"""What a run's set-up is made of, shared by `run.py` and the traffic kinds
under `kinds/`: the cell as `BENCHMARK.json` and the data files state it,
the look for a chip, the index, the `_bulk` load, the wait for the
dispatcher's compiles, and bursts that form a batch size. No JAX here.
"""

from __future__ import annotations

import http.client
import json
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from benchmark import loadgen
from benchmark.child import Child, Connection, RunFailure
from benchmark.data import Corpus

INDEX = "bench"


def note(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Cell:
    """What `BENCHMARK.json` and the data files say about one workload."""

    def __init__(self, bench_dir: str, name: str):
        self.bench = load_json(bench_dir, "BENCHMARK.json")
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise RunFailure(f"no workload {name!r} in BENCHMARK.json "
                             f"(has: {sorted(cells)})")
        self.entry = cells[name]
        self.name = name
        cfg = {c["name"]: c
               for c in self.bench["configs"]}[self.entry["config"]]
        self.config = load_json(bench_dir, cfg["file"])
        self.home = os.path.join(bench_dir, self.bench["paths"][0])
        self.traffic = load_json(self.home, "traffic",
                                 self.entry["traffic"] + ".json")
        self.chips = int(self.entry["chips"])

    def _in_cell(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self) -> list:
        return [m for m in self.bench["end_to_end"] if self._in_cell(m)]

    def per_layer(self) -> list:
        return [m for m in self.bench["per_layer"] if self._in_cell(m)]


def device_of(node: dict) -> dict:
    dev = node["device"]
    peaks = [m.get("peak_bytes_in_use") for m in dev.get("memory", [])]
    peaks = [p for p in peaks if p is not None]
    return {"platform": dev["platform"], "kind": dev["device_kind"],
            "count": dev["count"],
            "memory_peak_bytes": max(peaks) if peaks else 0}


def check_device(child: Child, chips: int, rehearse: bool) -> dict:
    """Fail before the rows are loaded, not after."""
    dev = device_of(child.node_stats())
    note(f"device platform={dev['platform']} kind={dev['kind']!r} "
         f"count={dev['count']}")
    if dev["platform"] != "tpu" and not rehearse:
        raise RunFailure(f"JAX found no accelerator: the server runs on "
                         f"platform {dev['platform']!r} ({dev['kind']!r})")
    if dev["count"] < chips:
        raise RunFailure(f"the cell asks for {chips} chips, the server sees "
                         f"{dev['count']}")
    return dev


def create_index(child: Child, config: dict, which: str, name: str = INDEX):
    settings = dict(config["index"]["settings"])
    settings.update(config[which]["settings"])
    child.ok("PUT", f"/{name}", {"settings": settings,
                                 "mappings": config["index"]["mappings"]})


def load_rows(child: Child, corpus: Corpus, n_rows: int):
    """`_bulk` the corpus, the next body built while the server indexes the
    current one. (Two or three connections at once load no faster: the
    server's one interpreter is the limit.)"""
    docs = corpus.block_docs
    blocks = [(b, min(docs, n_rows - b * docs))
              for b in range(-(-n_rows // docs))]
    t = time.monotonic()
    with ThreadPoolExecutor(max_workers=1) as pool:
        nxt = pool.submit(corpus.bulk_body, 0, INDEX, blocks[0][1])
        for i, (b, n) in enumerate(blocks):
            body = nxt.result()
            if i + 1 < len(blocks):
                nxt = pool.submit(corpus.bulk_body, blocks[i + 1][0], INDEX,
                                  blocks[i + 1][1])
            resp = child.ok("POST", "/_bulk", body)
            if resp.get("errors"):
                bad = next(it for it in resp["items"]
                           if it["index"].get("error"))
                raise RunFailure(f"_bulk item failed: {json.dumps(bad)[:600]}")
    bulk_s = time.monotonic() - t
    note(f"load rows={n_rows} dims={corpus.dims} bulk_s={bulk_s:.1f} "
         f"docs_per_s={n_rows / bulk_s:.0f}")
    return blocks


def settle_compiles(child: Child, quiet_s: float = 1.5,
                    limit_s: float = 300.0) -> dict:
    """Block until no compile is in flight and the dispatcher's compile
    count has stood still for `quiet_s`: the background warm-up grid that a
    refresh starts is done."""
    deadline = time.monotonic() + limit_s
    last, since = -1, time.monotonic()
    while time.monotonic() < deadline:
        node = child.node_stats()
        d = node["indices"]["dispatch"]
        if d["compiles"] != last or d["compiling"]:
            last, since = d["compiles"], time.monotonic()
        elif time.monotonic() - since >= quiet_s:
            return node
        time.sleep(0.25)
    raise RunFailure(f"compiles still arriving after {limit_s:.0f}s")


def misses(node: dict) -> int:
    d = node["indices"]["dispatch"]
    return d["misses"] + d["out_of_grid_compiles"]


def burst(port: int, items: list) -> None:
    """Send `items` at once, one connection each."""
    gate = threading.Barrier(len(items), timeout=loadgen.GATE_S)
    errors = []

    def one(item):
        conn = Connection(port)
        try:
            conn.request("GET", "/")
            gate.wait()
            status, raw = conn.request(item.method, item.path, item.body)
            if status != 200:
                errors.append(f"{status}: {raw[:300]!r}")
        except (OSError, http.client.HTTPException,
                threading.BrokenBarrierError) as e:
            gate.abort()        # the others must not wait for this one
            errors.append(f"{type(e).__name__}: {e}")
        finally:
            conn.close()

    threads = [threading.Thread(target=one, args=(it,), daemon=True)
               for it in items]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 300
    for t in threads:
        t.join(timeout=max(0.1, deadline - time.monotonic()))
    if any(t.is_alive() for t in threads):
        errors.append("a warm-up burst did not end within 300s")
    if errors:
        raise RunFailure(f"warm-up request failed: {errors[0]}")
