"""The least time the chip could take for the kNN work of a window.

    least = max(2*Q*N*d / peak_ops, N*d*element_bytes / peak_bytes_per_s)

Q requests answered from the device, N rows, d dims. Both terms are floors
under ANY kernel, tiling or batching: every query scores every row once, and
the corpus is read at least once. So the share cannot pass 100 %, and a PR
that swaps the kernel cannot make the count stale.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ELEMENT_BYTES = {"bf16": 2, "int8": 1}


def peaks_for(device_kind: str, path: str = os.path.join(HERE, "peaks.json")):
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it to "
                       f"peaks.json with its source, never a default")
    return table[device_kind]


def knn_ops(queries: int, rows: int, dims: int) -> float:
    return 2.0 * queries * rows * dims


def corpus_bytes(rows: int, dims: int, dtype: str) -> float:
    return float(rows) * dims * ELEMENT_BYTES[dtype]


def least_seconds(queries: int, rows: int, dims: int, dtype: str,
                  peaks: dict) -> dict:
    """The two floors and which binds."""
    compute = knn_ops(queries, rows, dims) / peaks["ops_per_s"][dtype]
    memory = corpus_bytes(rows, dims, dtype) / peaks["bytes_per_s"]
    return {"seconds": max(compute, memory), "compute_s": compute,
            "memory_s": memory,
            "bound_by": "compute" if compute >= memory else "memory"}


def share_percent(queries: int, rows: int, dims: int, dtype: str,
                  peaks: dict, busy_s: float):
    """Least time over device-busy time, in percent; None (never 0) where
    there is nothing to read."""
    if not queries or not busy_s or busy_s <= 0:
        return None
    return 100.0 * least_seconds(queries, rows, dims, dtype,
                                 peaks)["seconds"] / busy_s
