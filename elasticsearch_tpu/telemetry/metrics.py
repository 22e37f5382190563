"""Process-wide metrics registry: counters, gauges, log2-bucket histograms.

Before this module the repo's latency numbers lived in two places that
could not answer "what is p99 RIGHT NOW": cumulative nanos totals in
per-subsystem stats dicts (`_nodes/stats` could report a mean but never a
tail) and closed-loop percentiles computed by a harness outside the
program. This registry is the one in-tree home for live distributions:
subsystems record durations as they already measure them (no new clock
reads, no device syncs), and
`_nodes/stats telemetry` renders p50/p90/p99/p999 from the histograms on
demand.

Histograms use FIXED log2 buckets over nanoseconds (bucket i covers
(2^(i-1), 2^i]); 64 buckets span sub-nanosecond to ~584 years, so there
is no configuration and no rescaling; recording appends to a pending
list that is folded into the buckets every `FOLD_AT` values and before
every read. Percentiles interpolate
linearly inside the winning bucket, which bounds the error to one bucket
width.

Process-wide like the kernel dispatcher (`ops/dispatch.DISPATCH`): one
registry serves every node in the process, and the stats section is
node-level by construction.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence

N_BUCKETS = 64


def bucket_index(value_ns: int) -> int:
    """Bucket for a nanosecond duration: bucket i (i >= 1) covers
    (2^(i-1), 2^i] — exact powers of two land in their own bucket's
    upper edge, not one higher; bucket 0 holds <= 1 ns (zero/negative
    clock noise must not throw)."""
    v = int(value_ns)
    if v <= 1:
        return 0
    return min((v - 1).bit_length(), N_BUCKETS - 1)


def bucket_upper_ns(i: int) -> int:
    """Inclusive upper bound of bucket i."""
    return 1 if i <= 0 else 1 << i


def percentile_from_counts(counts: Sequence[int], q: float) -> float:
    """Percentile (ns) from a bucket-count vector: find the bucket where
    the cumulative count crosses q, interpolate linearly inside it. The
    answer is within one log2 bucket of the true value by construction."""
    total = sum(counts)
    if total <= 0:
        return 0.0
    target = q * total
    cum = 0.0
    for i, c in enumerate(counts):
        if c <= 0:
            continue
        if cum + c >= target:
            lo = float(0 if i == 0 else 1 << max(i - 1, 0))
            hi = float(bucket_upper_ns(i))
            frac = (target - cum) / c
            return lo + frac * (hi - lo)
        cum += c
    return float(bucket_upper_ns(N_BUCKETS - 1))


class Counter:
    __slots__ = ("name", "value", "_lock")

    def __init__(self, name: str):
        self.name = name
        self.value = 0
        self._lock = threading.Lock()

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self.value += n


class Gauge:
    __slots__ = ("name", "value", "_lock")

    def __init__(self, name: str):
        self.name = name
        self.value = 0.0
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        with self._lock:
            self.value = v

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self.value += n

    def dec(self, n: float = 1.0) -> None:
        with self._lock:
            self.value -= n


FOLD_AT = 64    # pending durations a histogram keeps before it folds them


class Histogram:
    """Fixed log2-bucket latency histogram over nanoseconds.

    `record` is on the hot path of every request, some twenty times, from
    every thread: it appends the duration to a pending list (one atomic
    list operation under the interpreter lock, no lock of ours, three
    cache lines) and folds the list into the buckets, `count` and
    `sum_ns` once it holds `FOLD_AT` values, in one tight loop. Every
    reader folds first, so what is read is exact."""

    __slots__ = ("name", "_counts", "_count", "_sum_ns", "_max_ns",
                 "_pending", "_lock")

    def __init__(self, name: str):
        self.name = name
        self._counts: List[int] = [0] * N_BUCKETS
        self._count = 0
        self._sum_ns = 0
        self._max_ns = 0
        self._pending: List[int] = []
        self._lock = threading.Lock()

    def record(self, value_ns: int) -> None:
        pending = self._pending
        pending.append(value_ns)
        if len(pending) >= FOLD_AT:
            self._fold()

    def _fold(self) -> None:
        with self._lock:
            pending = self._pending
            # take what is there now and leave what other threads append
            # meanwhile: both steps are atomic under the interpreter lock
            values = pending[:len(pending)]
            del pending[:len(values)]
            counts = self._counts
            total = 0
            top = self._max_ns
            for v in values:
                v = int(v)
                i = (v - 1).bit_length() if v > 1 else 0
                counts[i if i < N_BUCKETS else N_BUCKETS - 1] += 1
                if v > 0:
                    total += v
                    if v > top:
                        top = v
            self._count += len(values)
            self._sum_ns += total
            self._max_ns = top

    @property
    def count(self) -> int:
        self._fold()
        return self._count

    @property
    def sum_ns(self) -> int:
        self._fold()
        return self._sum_ns

    @property
    def max_ns(self) -> int:
        self._fold()
        return self._max_ns

    def percentile(self, q: float) -> float:
        self._fold()
        with self._lock:
            counts = list(self._counts)
        return percentile_from_counts(counts, q)

    def snapshot(self, raw: bool = False) -> dict:
        self._fold()
        with self._lock:
            counts = list(self._counts)
            count, sum_ns, max_ns = self._count, self._sum_ns, self._max_ns
        out = {
            "count": count,
            "sum_nanos": sum_ns,
            "mean_nanos": (sum_ns / count) if count else 0.0,
            "max_nanos": max_ns,
            "p50_nanos": percentile_from_counts(counts, 0.50),
            "p90_nanos": percentile_from_counts(counts, 0.90),
            "p99_nanos": percentile_from_counts(counts, 0.99),
            "p999_nanos": percentile_from_counts(counts, 0.999),
        }
        if raw:
            out["counts"] = counts
        return out


class MetricsRegistry:
    """Named metric registry: get-or-create, thread-safe, snapshot-able.

    Metric creation takes the registry lock; recording takes only the
    metric's own lock, so the steady-state cost is one uncontended lock
    acquire per record."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._histograms: Dict[str, Histogram] = {}

    def counter(self, name: str) -> Counter:
        c = self._counters.get(name)
        if c is None:
            with self._lock:
                c = self._counters.setdefault(name, Counter(name))
        return c

    def gauge(self, name: str) -> Gauge:
        g = self._gauges.get(name)
        if g is None:
            with self._lock:
                g = self._gauges.setdefault(name, Gauge(name))
        return g

    def histogram(self, name: str) -> Histogram:
        h = self._histograms.get(name)
        if h is None:
            with self._lock:
                h = self._histograms.setdefault(name, Histogram(name))
        return h

    def snapshot(self, raw: bool = False) -> dict:
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            hists = dict(self._histograms)
        return {
            "counters": {n: c.value for n, c in sorted(counters.items())},
            "gauges": {n: g.value for n, g in sorted(gauges.items())},
            "histograms": {n: h.snapshot(raw=raw)
                           for n, h in sorted(hists.items())},
        }

    def reset(self) -> None:
        """Drop every metric (tests/bench only)."""
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._histograms.clear()


REGISTRY = MetricsRegistry()


def counter(name: str) -> Counter:
    return REGISTRY.counter(name)


def gauge(name: str) -> Gauge:
    return REGISTRY.gauge(name)


def histogram(name: str) -> Histogram:
    return REGISTRY.histogram(name)


def record(name: str, value_ns: int) -> None:
    """One-call histogram record — the subsystem-facing entry."""
    h = REGISTRY._histograms.get(name)
    if h is None:
        h = REGISTRY.histogram(name)
    h.record(value_ns)


def snapshot(raw: bool = False) -> dict:
    return REGISTRY.snapshot(raw=raw)
