"""From a profiler trace to device busy time, its operations and idle gaps.

`read_xplane` is the only part that needs JAX (it reads the `.xplane.pb`
the profiler wrote, in the child that holds the chip). `reduce_events` is
plain Python over (name, start_ns, duration_ns) tuples, one list a device,
and is what the recorded trace under `tests/benchmark/data/` checks.

Busy is the UNION of the intervals in which an operation ran on a device,
averaged over the devices; idle is the rest of the traced window. Idle gaps
are listed by length and marked unattributed: no host span of the program
shares the profiler's clock yet (the `tracing` issue's first job).
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Tuple

Event = Tuple[str, float, float]
TOP = 10
NAME_CHARS = 120     # the trace names an operation by its whole HLO line


def read_xplane(trace_dir: str) -> Dict[str, List[Event]]:
    """Device operations by device. On a TPU: the `XLA Ops` line of each
    `/device:TPU:n` plane. On the CPU backend (rehearsals only) XLA's
    operations run on host threads: the host plane's events that carry an
    `hlo_op` stat stand in, as one device."""
    import jax

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices.setdefault(plane.name, []).extend(
                        (e.name[:NAME_CHARS], e.start_ns, e.duration_ns)
                        for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0 and any(k == "hlo_op"
                                                 for k, _ in e.stats):
                        host.append((e.name, e.start_ns, e.duration_ns))
    if not devices and host:
        devices["/host:CPU"] = host
    if not devices:
        seen = [f"{p.name}: {[ln.name for ln in p.lines][:12]}"
                for p in data.planes]
        raise ValueError(f"no device operations in the trace; planes: {seen}")
    return devices


def _union(events: List[Event]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for _name, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def reduce_events(devices: Dict[str, List[Event]], window_s: float) -> dict:
    """busy_s (mean over devices of the union), the operations that took
    most summed time (mean over devices), and the longest idle gaps (of the
    first device, between its first and last operation)."""
    if not devices:
        return {"busy_s": 0.0, "window_s": window_s, "device_ops": [],
                "idle_gaps": [], "devices": 0}
    busy, by_name, gaps = [], {}, []
    for i, (_dev, events) in enumerate(sorted(devices.items())):
        spans = _union(events)
        busy.append(sum(b - a for a, b in spans) / 1e9)
        for name, _start, dur in events:
            by_name[name] = by_name.get(name, 0.0) + dur / 1e9
        if i == 0:
            gaps = sorted(((b0 - a1) / 1e9 for (_a0, a1), (b0, _b1)
                           in zip(spans, spans[1:])), reverse=True)[:TOP]
    n = len(devices)
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": sum(busy) / n, "window_s": window_s,
            "device_ops": [[name, s / n] for name, s in ops],
            "idle_gaps": [["unattributed", g] for g in gaps],
            "devices": n}
