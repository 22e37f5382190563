"""Native process management for the ML sidecar.

Reference mapping:
- `bootstrap/Spawner.java:42` — spawns native controller daemons at startup.
- `x-pack/plugin/ml/.../process/NativeController.java:26-37` — singleton that
  starts per-job processes on request.
- `ProcessPipes.java` / `AbstractNativeProcess.java` — named-pipe I/O with the
  C++ process; results parsed from JSON (`IndexingStateProcessor.java`).

Protocol here: 4-byte big-endian length + JSON payload, both directions
(see native/ml_autodetect.cc header). A reader thread drains result frames
and hands them to a callback; a pure-Python model with identical semantics
is used when no C++ toolchain is available (same fallback discipline as
elasticsearch_tpu/native for the search kernels).
"""

from __future__ import annotations

import json
import logging
import math
import os
import queue
import struct
import subprocess
import threading
from typing import Callable, Dict, List, Optional

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native")
_BIN_PATH = os.path.join(_NATIVE_DIR, "ml_autodetect")

logger = logging.getLogger("elasticsearch_tpu.ml")

def autodetect_binary() -> Optional[str]:
    """Locate (building on demand) the ml_autodetect binary, or None —
    the in-process twin below serves a host without a compiler."""
    from elasticsearch_tpu.native import build_target
    try:
        build_target("ml_autodetect", "ml_autodetect.cc")
    except RuntimeError as exc:
        logger.warning("ml_autodetect binary unavailable, in-process "
                       "twin serves: %s", exc)
        return None
    return _BIN_PATH


class AutodetectProcess:
    """One running analytics process for one open job.

    Reference: NativeAutodetectProcess.java — writes records, reads results
    asynchronously, supports flush (with ack id) and state persistence.
    """

    def __init__(self, job_config: dict, result_handler: Callable[[dict], None],
                 state: Optional[dict] = None):
        self.job_id = job_config.get("job_id", "")
        self._handler = result_handler
        self._flush_acks: "queue.Queue[dict]" = queue.Queue()
        self._state_frames: "queue.Queue[dict]" = queue.Queue()
        self._lock = threading.Lock()
        self._closed = False

        binary = autodetect_binary()
        if binary is not None:
            self._proc: Optional[subprocess.Popen] = subprocess.Popen(
                [binary], stdin=subprocess.PIPE, stdout=subprocess.PIPE)
            self._py: Optional[PyAutodetect] = None
            self._reader = threading.Thread(target=self._read_loop, daemon=True,
                                            name=f"ml-reader[{self.job_id}]")
            self._reader.start()
        else:  # pragma: no cover - exercised only without a C++ toolchain
            self._proc = None
            self._py = PyAutodetect(job_config, self._dispatch)
        self._send({"type": "config", "job": job_config,
                    **({"state": state} if state else {})})

    @property
    def is_native(self) -> bool:
        return self._proc is not None

    # ----------------------------------------------------------------- I/O
    def _send(self, msg: dict) -> None:
        if self._closed:
            return
        if self._proc is not None:
            payload = json.dumps(msg).encode("utf-8")
            with self._lock:
                assert self._proc.stdin is not None
                self._proc.stdin.write(struct.pack(">I", len(payload)) + payload)
                self._proc.stdin.flush()
        else:
            assert self._py is not None
            self._py.handle(msg)

    def _read_loop(self) -> None:
        assert self._proc is not None and self._proc.stdout is not None
        stream = self._proc.stdout
        while True:
            hdr = stream.read(4)
            if len(hdr) < 4:
                break
            (n,) = struct.unpack(">I", hdr)
            payload = stream.read(n)
            if len(payload) < n:
                break
            try:
                msg = json.loads(payload)
            except ValueError:
                continue
            self._dispatch(msg)

    def _dispatch(self, msg: dict) -> None:
        t = msg.get("type")
        if t == "flush_ack":
            self._flush_acks.put(msg)
        elif t == "state":
            self._state_frames.put(msg)
        else:
            self._handler(msg)

    # ------------------------------------------------------------- commands
    def write_record(self, epoch_seconds: float, fields: dict) -> None:
        self._send({"type": "record", "time": epoch_seconds, "fields": fields})

    def flush(self, flush_id: str = "f", timeout: float = 30.0) -> dict:
        self._send({"type": "flush", "id": flush_id})
        return self._flush_acks.get(timeout=timeout)

    def persist_state(self, timeout: float = 30.0) -> dict:
        self._send({"type": "persist"})
        return self._state_frames.get(timeout=timeout).get("state", {})

    def close(self) -> None:
        if self._closed:
            return
        self._send({"type": "quit"})
        self._closed = True
        if self._proc is not None:
            assert self._proc.stdin is not None
            self._proc.stdin.close()
            self._proc.wait(timeout=30)
            if self._reader.is_alive():
                self._reader.join(timeout=10)

    def kill(self) -> None:
        self._closed = True
        if self._proc is not None:
            self._proc.kill()
            self._proc.wait(timeout=10)


# ---------------------------------------------------------------------------
# Pure-Python fallback model — protocol- and semantics-identical to
# native/ml_autodetect.cc so tests/behavior don't depend on a compiler.
# ---------------------------------------------------------------------------

class _Welford:
    __slots__ = ("n", "mean", "m2")

    def __init__(self, n=0.0, mean=0.0, m2=0.0):
        self.n, self.mean, self.m2 = n, mean, m2

    def add(self, x: float) -> None:
        self.n += 1
        d = x - self.mean
        self.mean += d / self.n
        self.m2 += d * (x - self.mean)

    def probability(self, x: float, side: int) -> float:
        if self.n < 3:
            return 1.0
        var = self.m2 / (self.n - 1) if self.n > 1 else 0.0
        sd = math.sqrt(var) if var > 0 else abs(self.mean) * 0.01 + 1e-9
        z = (x - self.mean) / sd
        if side < 0 and z > 0:
            return 1.0
        if side > 0 and z < 0:
            return 1.0
        p = math.erfc(abs(z) / math.sqrt(2.0))
        return p if side == 0 else p / 2


def _score(p: float) -> float:
    if p >= 1:
        return 0.0
    p = max(p, 1e-308)
    return max(0.0, min(100.0, -10 * math.log10(p) - 13))


class PyAutodetect:
    """In-process twin of native/ml_autodetect.cc (see its header comment)."""

    def __init__(self, job_config: dict, emit: Callable[[dict], None]):
        self._emit = emit
        self.job_id = job_config.get("job_id", "")
        ac = job_config.get("analysis_config", {}) or {}
        self.bucket_span = _parse_span(ac.get("bucket_span", 300))
        self.detectors: List[dict] = []
        for d in ac.get("detectors", []) or [{"function": "count"}]:
            fn = d.get("function", "count")
            side = 0
            if fn.startswith("low_"):
                side, fn = -1, fn[4:]
            elif fn.startswith("high_"):
                side, fn = 1, fn[5:]
            self.detectors.append({
                "function": fn, "side": side,
                "field_name": d.get("field_name", ""),
                "by_field": d.get("by_field_name", ""),
                "partition_field": d.get("partition_field_name", ""),
                "models": {}, "rare": {},
            })
        if not self.detectors:
            self.detectors.append({"function": "count", "side": 0,
                                   "field_name": "", "by_field": "",
                                   "partition_field": "", "models": {},
                                   "rare": {}})
        self.bucket_start = -1.0
        self.latest_time = -1.0
        self.accum: Dict[tuple, dict] = {}

    def handle(self, msg: dict) -> None:
        t = msg.get("type")
        if t == "record":
            self._add(msg.get("time", 0), msg.get("fields", {}) or {})
        elif t == "flush":
            if self.accum:
                self._close_bucket()
            self._emit({"type": "flush_ack", "id": msg.get("id", ""),
                        "last_finalized_bucket_end":
                            self.bucket_start * 1000 if self.bucket_start > 0 else 0})
        elif t == "persist":
            self._emit({"type": "state", "state": self._state()})
        elif t == "config":
            st = msg.get("state")
            if st:
                self._restore(st)
        elif t == "quit":
            if self.accum:
                self._close_bucket()

    # ------------------------------------------------------------ modelling
    def _entity(self, det: dict, fields: dict) -> str:
        part = str(fields.get(det["partition_field"], "")) if det["partition_field"] else ""
        by = ""
        if det["by_field"] and det["function"] not in ("rare", "distinct_count"):
            by = str(fields.get(det["by_field"], ""))
        return part + "\x1e" + by

    def _add(self, t: float, fields: dict) -> None:
        if t < self.latest_time:
            return
        if self.bucket_start >= 0 and t < self.bucket_start:
            return  # bucket already finalized by flush
        self.latest_time = t
        bstart = math.floor(t / self.bucket_span) * self.bucket_span
        if self.bucket_start < 0:
            self.bucket_start = bstart
        while bstart >= self.bucket_start + self.bucket_span:
            self._close_bucket()
        for i, det in enumerate(self.detectors):
            key = (i, self._entity(det, fields))
            agg = self.accum.setdefault(
                key, {"count": 0.0, "sum": 0.0, "min": math.inf,
                      "max": -math.inf, "by": {}})
            agg["count"] += 1
            if det["field_name"]:
                v = fields.get(det["field_name"])
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    agg["sum"] += v
                    agg["min"] = min(agg["min"], v)
                    agg["max"] = max(agg["max"], v)
                else:
                    agg["count"] -= 1
            if det["by_field"] and det["function"] in ("rare", "distinct_count"):
                bv = fields.get(det["by_field"])
                if bv is not None and bv != "":
                    agg["by"][str(bv)] = agg["by"].get(str(bv), 0) + 1

    def _close_bucket(self) -> None:
        if self.bucket_start < 0:
            return
        max_score = 0.0
        records: List[dict] = []
        for i, det in enumerate(self.detectors):
            for (di, entity), agg in list(self.accum.items()):
                if di != i:
                    continue
                if det["function"] == "rare":
                    rm = det["rare"].setdefault(entity, {"counts": {}, "total": 0.0})
                    for bv, c in agg["by"].items():
                        if rm["total"] < 10:
                            p = 1.0
                        else:
                            p = (rm["counts"].get(bv, 0) + 1) / (rm["total"] + 1)
                        s = _score(p)
                        if s > 0.1:
                            records.append(self._record(det, entity, bv, s, p, c, 0))
                        max_score = max(max_score, s)
                    for bv, c in agg["by"].items():
                        rm["counts"][bv] = rm["counts"].get(bv, 0) + c
                        rm["total"] += c
                    continue
                fn = det["function"]
                if fn == "count":
                    actual = agg["count"]
                elif fn == "sum":
                    actual = agg["sum"]
                elif fn == "min":
                    actual = agg["min"] if agg["count"] else 0.0
                elif fn == "max":
                    actual = agg["max"] if agg["count"] else 0.0
                elif fn == "distinct_count":
                    actual = float(len(agg["by"]))
                else:
                    actual = agg["sum"] / agg["count"] if agg["count"] else 0.0
                m = det["models"].setdefault(entity, _Welford())
                p = m.probability(actual, det["side"])
                s = _score(p)
                if s > 0.1:
                    records.append(self._record(det, entity, "", s, p, actual, m.mean))
                max_score = max(max_score, s)
                m.add(actual)
        event_count = sum(a["count"] for (di, _), a in self.accum.items() if di == 0)
        self._emit({"type": "bucket", "job_id": self.job_id,
                    "timestamp": self.bucket_start * 1000,
                    "bucket_span": self.bucket_span,
                    "anomaly_score": max_score,
                    "initial_anomaly_score": max_score,
                    "event_count": event_count, "is_interim": False,
                    "result_type": "bucket"})
        for r in records:
            self._emit(r)
        self.accum.clear()
        self.bucket_start += self.bucket_span

    def _record(self, det, entity, by_value, score, prob, actual, typical) -> dict:
        part, _, byv = entity.partition("\x1e")
        prefix = {-1: "low_", 1: "high_", 0: ""}[det["side"]]
        r = {"type": "record", "job_id": self.job_id, "result_type": "record",
             "timestamp": self.bucket_start * 1000,
             "bucket_span": self.bucket_span, "record_score": score,
             "initial_record_score": score, "probability": prob,
             "function": prefix + det["function"], "actual": [actual],
             "is_interim": False}
        if det["field_name"]:
            r["field_name"] = det["field_name"]
        if det["partition_field"]:
            r["partition_field_name"] = det["partition_field"]
            r["partition_field_value"] = part
        if det["by_field"]:
            r["by_field_name"] = det["by_field"]
            r["by_field_value"] = by_value or byv
        if det["function"] != "rare":
            r["typical"] = [typical]
        return r

    # --------------------------------------------------------------- state
    def _state(self) -> dict:
        dets = []
        for det in self.detectors:
            dets.append({
                "models": {k: [m.n, m.mean, m.m2]
                           for k, m in det["models"].items()},
                "rare": {k: dict(v["counts"]) for k, v in det["rare"].items()},
            })
        return {"detectors": dets, "latest_time": self.latest_time}

    def _restore(self, st: dict) -> None:
        for i, d in enumerate(st.get("detectors", [])):
            if i >= len(self.detectors):
                break
            det = self.detectors[i]
            for k, (n, mean, m2) in (d.get("models") or {}).items():
                det["models"][k] = _Welford(n, mean, m2)
            for k, counts in (d.get("rare") or {}).items():
                det["rare"][k] = {"counts": dict(counts),
                                  "total": float(sum(counts.values()))}
        self.latest_time = st.get("latest_time", -1)


def _parse_span(v) -> float:
    if isinstance(v, (int, float)):
        return float(v)
    s = str(v).strip()
    units = {"s": 1, "m": 60, "h": 3600, "d": 86400}
    if s and s[-1] in units:
        try:
            return float(s[:-1]) * units[s[-1]]
        except ValueError:
            pass
    try:
        return float(s)
    except ValueError:
        return 300.0
