#!/usr/bin/env python3
"""The ONE child of a run: the program's own `elasticsearch_tpu.server.main`,
in this process, with the arguments a user would pass.

It holds the chip, so what only the holder can do is done here, on commands
that the parent writes to this process's standard input, one a line, each
answered with one JSON line appended to `--ctl-out`:

    counters      counts this launcher keeps (the pauses of the
                  interpreter's garbage collector)
    trace_start   jax.profiler.start_trace(<--trace-dir>)
    trace_stop    stop_trace, then reduce the trace (`benchmark/trace.py`)

The commands run on a thread of their own, never on the server's loop. The
program is not edited. `--fault` (tests only) breaks the served path
underneath so that the tests can see `correct` come out false.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

COUNTS = {"gc_pauses": 0, "gc_pause_nanos": 0, "gc_pause_max_nanos": 0}


def time_gc() -> None:
    """The interpreter's collector stops every thread of the server while
    it runs: count its pauses and their time. `gc_pause_max_nanos` is the
    longest since the `counters` command last read it."""
    began = [0]

    def on_gc(phase, _info):
        if phase == "start":
            began[0] = time.monotonic_ns()
        else:
            took = time.monotonic_ns() - began[0]
            COUNTS["gc_pauses"] += 1
            COUNTS["gc_pause_nanos"] += took
            COUNTS["gc_pause_max_nanos"] = max(COUNTS["gc_pause_max_nanos"],
                                               took)

    gc.callbacks.append(on_gc)


def plant_fault(name: str) -> None:
    """Break the served path where the answer is produced."""
    def alter_hits(edit) -> None:
        from elasticsearch_tpu.node import Node
        real = Node.search

        def search(self, *a, **kw):
            resp = real(self, *a, **kw)
            for hit in resp.get("hits", {}).get("hits", []):
                edit(hit)
            return resp

        Node.search = search

    if name == "alter_ids":
        def edit(hit):
            hit["_id"] = str(int(hit["_id"]) + 1)
        alter_hits(edit)
    elif name == "alter_scores":
        def edit(hit):
            if hit.get("_score") is not None:
                hit["_score"] += 0.004
        alter_hits(edit)
    else:
        raise SystemExit(f"unknown fault {name!r}")


def control(ctl_out: str, trace_dir: str) -> None:
    started = {}

    def reply(obj: dict) -> None:
        with open(ctl_out, "a") as f:
            f.write(json.dumps(obj) + "\n")

    for line in sys.stdin:
        cmd = line.strip()
        try:
            if cmd == "counters":
                reply({"cmd": cmd, **COUNTS})
                COUNTS["gc_pause_max_nanos"] = 0
            elif cmd == "trace_start":
                import jax
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                started["t"] = time.monotonic()
                reply({"cmd": cmd, "t": started["t"]})
            elif cmd == "trace_stop":
                import jax
                t = time.monotonic()
                jax.profiler.stop_trace()
                from benchmark import trace
                devices = trace.read_xplane(trace_dir)
                summary = trace.reduce_events(devices, t - started["t"])
                # the head of the trace, to be looked at by hand
                with open(os.path.join(trace_dir, "events_head.json"),
                          "w") as f:
                    json.dump({d: evs[:400] for d, evs in devices.items()},
                              f)
                reply({"cmd": cmd, "t": t, **summary})
            elif cmd:
                reply({"cmd": cmd, "error": "unknown command"})
        except Exception as e:          # the parent reports it and fails
            reply({"cmd": cmd, "error": f"{type(e).__name__}: {e}"})


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ctl-out", required=True)
    ap.add_argument("--trace-dir", required=True)
    ap.add_argument("--fault", default="")
    args, server_argv = ap.parse_known_args()
    time_gc()
    if args.fault:
        plant_fault(args.fault)
    threading.Thread(target=control, args=(args.ctl_out, args.trace_dir),
                     daemon=True).start()
    from elasticsearch_tpu import server
    return server.main(server_argv)


if __name__ == "__main__":
    code = main()
    # as `python -m elasticsearch_tpu.server` leaves: daemon threads may
    # still be inside XLA, and finalizing under them aborts
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
