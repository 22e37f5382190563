"""Two shares of a traced window on a MESH, where `trace_reduction.py`
would misread: that reader reckons the roofline with all rows on one
device, and a mesh gives each device rows / chips of them.

    "quantity": "roofline"      the least time ONE device could take for
        the traced window's searches over its own shard
        (`roofline.least_seconds(Q, rows / chips, dims, dtype, peaks)`:
        every query scores every row of the shard once, the shard is read
        once, whatever kernel does it), over the MEAN per-device busy time
        that `trace.py` returns. A count of the work, so it cannot pass
        100 %.
    "quantity": "gather_share"  percent of the devices' operation time
        spent in the SPMD program's all-gathers and in what runs after
        them in the same execution (the merge every device makes of the
        gathered candidates). The trace names an operation by its HLO line
        and not by its `jax.named_scope` (`es.mesh.gather`, `es.mesh.merge`
        in `parallel/sharded_knn.py`), so the all-gather is found by name
        and the merge by its place: inside one event of the device's
        `XLA Modules` line, every operation from the first `all-gather` on.

Both give None (the metric is then left out, never 0) on a CPU rehearsal,
on an empty trace, and where the trace file cannot be read. The second
reads the `.xplane.pb` the child wrote, with `jaxlib`'s reader of that
format and no backend: the parent still never imports JAX. `run.py` hands a
reader no path, so the run's output directory is worked out as `run.py`
does, from the same arguments (PERF.md, Open questions).
"""

from __future__ import annotations

import argparse
import glob
import os
import sys

from benchmark import arithmetic, roofline

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def trace_dir(argv=None) -> str:
    """Where this run's child wrote its trace: `run.py`'s `out_dir` and
    `child.py`'s `trace_<tag>` under it."""
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload", default="")
    ap.add_argument("--out", default="")
    args, _rest = ap.parse_known_args(sys.argv[1:] if argv is None else argv)
    return os.path.join(HERE, "out", args.out or args.workload, "trace_main")


def device_lines(path: str) -> dict:
    """{device: (module events, operation events)}, each event
    (name, start_ns, duration_ns), from one `.xplane.pb`."""
    from jaxlib import _profile_data

    out = {}
    for plane in _profile_data.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {ln.name: [(e.name, e.start_ns, e.duration_ns)
                           for e in ln.events] for ln in plane.lines}
        out[plane.name] = (lines.get("XLA Modules", []),
                           lines.get("XLA Ops", []))
    return out


def gather_share(devices: dict):
    """Over all devices: time of the operations from the first all-gather
    of a module's execution to its end, over the time of all operations;
    in percent. None where no operation or no all-gather is found."""
    total = tail = 0.0
    for modules, ops in devices.values():
        ops = sorted(ops, key=lambda e: e[1])
        total += sum(dur for _n, _s, dur in ops)
        at = 0
        for _name, start, dur in sorted(modules, key=lambda e: e[1]):
            while at < len(ops) and ops[at][1] < start:
                at += 1
            seen = False
            while at < len(ops) and ops[at][1] < start + dur:
                # the operation's own name, left of the "=": later lines
                # name the all-gather among their operands
                seen = seen or "all-gather" in ops[at][0].split("=", 1)[0]
                if seen:
                    tail += ops[at][2]
                at += 1
    if total <= 0 or tail <= 0:
        return None
    return 100.0 * tail / total


def read(spec: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or not trace.get("busy_s") or ctx["platform"] != "tpu":
        return None         # a CPU rehearsal has no chip to take a share of
    if spec["quantity"] == "roofline":
        searches = arithmetic.delta(ctx["trace_before"], ctx["trace_after"],
                                    spec["searches"])
        cfg = ctx["config"]
        shard_rows = -(-ctx["rows"] // cfg["chips"])
        return roofline.share_percent(
            int(searches or 0), shard_rows, cfg["dims"], cfg["device_dtype"],
            roofline.peaks_for(ctx["device_kind"]), trace["busy_s"])
    if spec["quantity"] == "gather_share":
        paths = sorted(glob.glob(os.path.join(
            trace_dir(), "plugins", "profile", "*", "*.xplane.pb")))
        if not paths:
            return None
        try:
            return gather_share(device_lines(paths[-1]))
        except (ImportError, OSError, ValueError):
            return None
    raise ValueError(f"unknown quantity {spec['quantity']!r}")
