"""Per-shard write-ahead log with generations and checkpoints.

Re-design of the reference translog (`index/translog/Translog.java:85-106`):
append-only generation files plus a checkpoint file recording the current
generation and the durability horizon. Every operation is length-prefixed,
CRC-checked, and carries (seq_no, primary_term); recovery replays operations
above the last commit's local checkpoint, exactly like
`InternalEngine.recoverFromTranslog`.

Format per record:  vint(length) | payload | crc32(payload) as 4 bytes BE
Payload: StreamOutput generic dict {op, id, source?, seq_no, primary_term,
version}. Checkpoint file: JSON {generation, min_translog_generation,
global_checkpoint, max_seq_no}.
"""

from __future__ import annotations

import json
import os
import struct
import zlib
from typing import Any, Dict, Iterator, List, Optional

from elasticsearch_tpu.common.errors import SearchEngineError
from elasticsearch_tpu.common.serialization import StreamInput, StreamOutput
from elasticsearch_tpu.telemetry import metrics

CHECKPOINT_FILE = "translog.ckp"

# counters: records appended, and lists in them that `write_generic` wrote as
# one array (a vector a document: the two read the same after a vector load)
COUNT_OPS = "translog.ops"
COUNT_PACKED_LISTS = "translog.packed_lists"

OP_INDEX = "index"
OP_DELETE = "delete"
OP_NOOP = "noop"


class TranslogCorruptedError(SearchEngineError):
    status = 500


class Translog:
    def __init__(self, directory: str, sync_policy: str = "request"):
        """sync_policy: 'request' fsyncs every add; 'async' leaves it to sync()."""
        self.directory = directory
        self.sync_policy = sync_policy
        os.makedirs(directory, exist_ok=True)
        ckp = self._read_checkpoint()
        self.generation = ckp.get("generation", 1)
        self.min_generation = ckp.get("min_translog_generation", self.generation)
        self.global_checkpoint = ckp.get("global_checkpoint", -1)
        self.max_seq_no = ckp.get("max_seq_no", -1)
        # lowest seq_no this translog still guarantees to hold; raised (and
        # persisted) only when a trim actually discards history
        self.min_retained_seq_no = ckp.get("min_retained_seq_no", 0)
        self._file = open(self._gen_path(self.generation), "ab")
        metrics.counter(COUNT_OPS)              # read 0, not absent, before
        metrics.counter(COUNT_PACKED_LISTS)     # the first record

    # -- paths / checkpoint ---------------------------------------------------
    def _gen_path(self, gen: int) -> str:
        return os.path.join(self.directory, f"translog-{gen}.tlog")

    def _read_checkpoint(self) -> dict:
        path = os.path.join(self.directory, CHECKPOINT_FILE)
        if not os.path.exists(path):
            return {}
        with open(path) as f:
            return json.load(f)

    def _write_checkpoint(self) -> None:
        path = os.path.join(self.directory, CHECKPOINT_FILE)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({
                "generation": self.generation,
                "min_translog_generation": self.min_generation,
                "global_checkpoint": self.global_checkpoint,
                "max_seq_no": self.max_seq_no,
                "min_retained_seq_no": self.min_retained_seq_no,
            }, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    # -- write path -----------------------------------------------------------
    def add(self, op: Dict[str, Any]) -> None:
        """Append one operation (dict with op/id/seq_no/primary_term/...)."""
        out = StreamOutput()
        out.write_generic(op)
        payload = out.bytes()
        rec = StreamOutput()
        rec.write_vint(len(payload))
        rec.write_bytes(payload)
        rec.write_bytes(struct.pack(">I", zlib.crc32(payload) & 0xFFFFFFFF))
        self._file.write(rec.bytes())
        metrics.counter(COUNT_OPS).inc()
        if out.packed_lists:
            metrics.counter(COUNT_PACKED_LISTS).inc(out.packed_lists)
        self.max_seq_no = max(self.max_seq_no, op.get("seq_no", -1))
        if self.sync_policy == "request":
            self.sync()

    def sync(self) -> None:
        self._file.flush()
        os.fsync(self._file.fileno())
        self._write_checkpoint()

    def update_global_checkpoint(self, value: int) -> None:
        if value > self.global_checkpoint:
            self.global_checkpoint = value

    def roll_generation(self) -> None:
        """Start a new generation file (reference: Translog.rollGeneration)."""
        self.sync()
        self._file.close()
        self.generation += 1
        self._file = open(self._gen_path(self.generation), "ab")
        self._write_checkpoint()

    def trim_below(self, generation: int,
                   min_retained_seq_no: Optional[int] = None) -> None:
        """Delete generations below `generation` (after a commit persists them).

        min_retained_seq_no: the lowest seq_no still guaranteed retained
        after the trim (the committing caller's checkpoint + 1)."""
        for gen in range(self.min_generation, generation):
            path = self._gen_path(gen)
            if os.path.exists(path):
                os.remove(path)
        self.min_generation = max(self.min_generation, generation)
        if min_retained_seq_no is not None:
            self.min_retained_seq_no = max(self.min_retained_seq_no,
                                           min_retained_seq_no)
        self._write_checkpoint()

    # -- read path ------------------------------------------------------------
    def _read_gen(self, gen: int) -> Iterator[Dict[str, Any]]:
        path = self._gen_path(gen)
        if not os.path.exists(path):
            return
        with open(path, "rb") as f:
            data = f.read()
        inp = StreamInput(data)
        while inp.remaining() > 0:
            try:
                length = inp.read_vint()
                payload = inp.read_bytes(length)
                crc = struct.unpack(">I", inp.read_bytes(4))[0]
            except SearchEngineError:
                raise TranslogCorruptedError(f"truncated translog record in generation {gen}")
            if zlib.crc32(payload) & 0xFFFFFFFF != crc:
                raise TranslogCorruptedError(f"translog CRC mismatch in generation {gen}")
            yield StreamInput(payload).read_generic()

    def read_ops(self, from_seq_no: int = 0) -> List[Dict[str, Any]]:
        """All operations with seq_no >= from_seq_no, in log order.

        Serves both startup recovery (replay past the last commit) and
        ops-based peer recovery / CCR shard-changes
        (`RecoverySourceHandler.java:290`, `ShardChangesAction.java:59`).
        """
        # async-durability shards buffer appends; flush (no fsync needed for a
        # same-process read) so recovery sees every operation
        self._file.flush()
        ops = []
        for gen in range(self.min_generation, self.generation + 1):
            for op in self._read_gen(gen):
                if op.get("seq_no", -1) >= from_seq_no:
                    ops.append(op)
        return ops

    def close(self) -> None:
        try:
            self.sync()
        finally:
            self._file.close()
