#!/usr/bin/env python3
"""Read the clock anchor out of a profiler trace: `python tools/beat_anchor.py
<trace dir or .xplane.pb>`.

While a `jax.profiler` session is on, the server's heartbeat
(`elasticsearch_tpu/telemetry/beat.py`) writes an event `es.runtime.beat`
every 10 ms whose stat `mono_ns` is the `time.monotonic_ns()` reading of
its start. This prints, as one JSON object: the offset to ADD to a
monotonic reading (a span of `GET _nodes/traces`, a `stage_done` wait, a
stall record's `at_ns`) to place it on the clock of the trace's events
(the device's `XLA Ops` line among them), that offset second by second
and its drift over the session, and the beats' own lock waits (an event's
length less the period). Needs JAX to read the file, and no device.
"""

from __future__ import annotations

import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from elasticsearch_tpu.telemetry.beat import (  # noqa: E402
    PERIOD_NS, anchor_offset_ns)


def read_beats(path: str) -> list:
    """[(start on the profiler's clock, duration, mono_ns)] of the host
    plane's `es.runtime.beat` events, in time order."""
    import jax

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    data = jax.profiler.ProfileData.from_file(path)
    beats = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == "es.runtime.beat":
                    mono = dict(e.stats).get("mono_ns")
                    if mono is not None:
                        beats.append((e.start_ns, e.duration_ns, int(mono)))
    return sorted(beats)


def summary(beats: list) -> dict:
    offset = anchor_offset_ns((start, mono) for start, _d, mono in beats)
    first = beats[0][2]
    by_second: dict = {}
    for start, _dur, mono in beats:
        by_second.setdefault((mono - first) // 1_000_000_000,
                             []).append((start, mono))
    per_second = [anchor_offset_ns(by_second[s]) for s in sorted(by_second)]
    waits = [max(0.0, dur - PERIOD_NS) for _s, dur, _m in beats]
    return {"beats": len(beats),
            "session_s": (beats[-1][2] - first) / 1e9,
            "offset_ns": offset,
            "offset_by_second_less_least_ns": [o - offset
                                               for o in per_second],
            "drift_ns": per_second[-1] - per_second[0],
            "lock_wait_mean_ms": sum(waits) / len(waits) / 1e6,
            "lock_wait_max_ms": max(waits) / 1e6}


if __name__ == "__main__":
    if len(sys.argv) != 2:
        sys.exit(__doc__)
    print(json.dumps(summary(read_beats(sys.argv[1]))))
