"""Hot threads: stack dumps of the busiest threads, by subsystem.

Reference: `monitor/jvm/HotThreads.java:41` — samples thread CPU over an
interval and prints the top-N stacks. Python analog: sample
`sys._current_frames` twice and report threads whose top frame advanced
(busy) with their current stacks.

Serving threads carry subsystem-identifying names so a busy stack is
attributable at a glance: the node thread pools prefix `es[<pool>]`
(common/threadpool.py), background workers name themselves at spawn
(`segments-merge`, `dispatch-warmup`, `batcher-warmup`,
`agg-column-resync`, `telemetry-beat`), and the combining batcher — which runs on BORROWED
submitter threads — tags the current thread for the duration of its
dispatch and finalize stages (the `section` of the same `telemetry.stage`
call that times `serving.device_dispatch` / `serving.device_sync`:
`»batcher-drain`, `»batcher-finalize`), so hot threads and spans cannot
name one stretch two ways. The report maps each thread to its subsystem
from that name.
"""

from __future__ import annotations

import sys
import threading
import time
import traceback
from typing import Dict, List

# thread-name fragment -> subsystem label, most specific first
_SUBSYSTEMS = (
    ("»batcher-drain", "serving/batcher dispatch"),
    ("»batcher-finalize", "serving/batcher finalize"),
    ("batcher-warmup", "serving/batcher warmup"),
    ("segments-merge", "segments background merge"),
    ("dispatch-warmup", "ops/dispatch warmup"),
    ("agg-column-resync", "aggs column resync"),
    ("telemetry-beat", "telemetry heartbeat"),
    ("es[search_throttled]", "search_throttled pool"),
    ("es[search]", "search pool"),
    ("es[write]", "write pool"),
    ("es[get]", "get pool"),
    ("es[generic]", "generic pool"),
    ("es[snapshot]", "snapshot pool"),
    ("es[force_merge]", "force_merge pool"),
)


def subsystem_of(thread_name: str) -> str:
    for fragment, label in _SUBSYSTEMS:
        if fragment in thread_name:
            return label
    if thread_name.startswith("es["):
        return thread_name.split("]")[0] + "] pool"
    return "other"


def hot_threads_report(interval_s: float = 0.05, top_n: int = 3,
                       node_name: str = "node") -> str:
    first: Dict[int, str] = {
        tid: _top_frame_key(frame)
        for tid, frame in sys._current_frames().items()
    }
    time.sleep(max(0.0, interval_s))
    frames = sys._current_frames()
    names = {t.ident: t.name for t in threading.enumerate()}
    lines = [f"::: {{{node_name}}}",
             f"   Hot threads at {time.strftime('%Y-%m-%dT%H:%M:%S')}, "
             f"interval={interval_s}s, busiestThreads={top_n}:"]
    busy_first = sorted(
        frames.items(),
        key=lambda kv: (first.get(kv[0]) == _top_frame_key(kv[1])),  # moved first
    )
    for tid, frame in busy_first[:top_n]:
        name = names.get(tid, str(tid))
        state = "runnable" if first.get(tid) != _top_frame_key(frame) else "waiting"
        lines.append(f"   0.0% cpu usage by thread '{name}' ({state}) "
                     f"[{subsystem_of(name)}]")
        for entry in traceback.format_stack(frame)[-10:]:
            for ln in entry.rstrip().splitlines():
                lines.append("     " + ln.strip())
    return "\n".join(lines) + "\n"


def _top_frame_key(frame) -> str:
    return f"{frame.f_code.co_filename}:{frame.f_lineno}"


def frame_keys(frame, depth: int) -> List[str]:
    """A thread's innermost `depth` frames, innermost first, each as
    `file:line function`: the bounded form a stall's record keeps
    (`telemetry/beat.py`)."""
    out: List[str] = []
    while frame is not None and len(out) < depth:
        out.append(f"{_top_frame_key(frame)} {frame.f_code.co_name}")
        frame = frame.f_back
    return out
