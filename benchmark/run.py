#!/usr/bin/env python3
"""One run of one cell: `python3 benchmark/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>`.

The parent (this process) never imports JAX. It starts ONE child,
`benchmark/serve.py`, which runs the program's own server; hands the set-up
to the traffic kind the cell's traffic file names (`kinds/<kind>.py`: the
index, the rows made from `--seed` over REST `_bulk`, the warm-up of every
shape the traffic can form); drives the traffic file's mix for `--seconds`
over keep-alive connections; reads `_nodes/stats` before and after; and only
then has the kind compute the plain reference and the comparison that
decides `correct`. The last line of standard output is the contract's one
JSON object.

Everything that belongs to one configuration, traffic mix, traffic kind,
end-to-end metric or per-layer metric is a file found by the name in
`BENCHMARK.json` (`configs/`, `traffic/`, `kinds/`, `end_to_end/`,
`layer_metrics/` + `readers/`): see README.md.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import shutil
import signal
import sys
import time

T_START = time.monotonic()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import arithmetic, loadgen, verify  # noqa: E402
from benchmark.child import Child, RunFailure  # noqa: E402
from benchmark.data import Corpus  # noqa: E402
from benchmark.setup import (Cell, check_device, device_of,  # noqa: E402
                             load_json, note)

TRACE_SECONDS = 5.0         # the traced part of the window: its last seconds


class Run:
    """What a traffic kind is handed: the cell, the arguments, the child,
    the corpus and where the run may write."""

    def __init__(self, cell: Cell, args, child: Child, corpus: Corpus,
                 n_rows: int, out_dir: str, data_dir: str):
        self.cell, self.args, self.child = cell, args, child
        self.corpus, self.n_rows = corpus, n_rows
        self.out_dir, self.data_dir = out_dir, data_dir


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

class Tracer:
    """Switches the child's profiler on for the window's last seconds."""

    def __init__(self, child: Child):
        self.child = child
        self.before = None
        self.error = None

    def start(self) -> None:
        try:
            self.child.command("trace_start")
            self.before = self.child.node_stats()
        except RunFailure as e:
            self.error = e

    def stop(self, after: dict) -> dict:
        if self.error:
            raise self.error
        if self.before is None:
            raise RunFailure("the trace never started")
        summary = self.child.command("trace_stop")
        summary["before"], summary["after"] = self.before, after
        return summary


def drive(child: Child, traffic: dict, seconds: float, seed: int, make_items,
          tracer) -> loadgen.Sample:
    """The window: the traffic file's mix, with the trace switch beside it
    on a thread of its own."""
    at = []
    if tracer is not None:
        at.append((max(0.0, seconds - TRACE_SECONDS), tracer.start))
    # the generator's own collector must not stop its threads in the window
    gc.collect()
    gc.disable()
    try:
        if traffic["loop"] == "closed":
            source = loadgen.ItemSource(make_items,
                                        loadgen.pool_size(traffic, seconds))
            return loadgen.closed_loop(child.port, traffic["clients"],
                                       seconds, source, at)
        offsets = loadgen.arrival_offsets(traffic["rate_per_s"], seconds,
                                          seed, traffic.get("burst"))
        return loadgen.open_loop(child.port, traffic["clients"], seconds,
                                 offsets, make_items(0, len(offsets)), at)
    finally:
        gc.enable()


def window(child: Child, traffic: dict, args, make_items) -> dict:
    """The measured window between two readings of the program's counts."""
    tracer = Tracer(child) if args.trace else None
    counters_before = child.command("counters")
    before = child.node_stats()
    sample = drive(child, traffic, args.seconds, args.seed, make_items,
                   tracer)
    after = child.node_stats()
    counters_after = child.command("counters")
    return {"sample": sample, "before": before, "after": after,
            "trace": tracer.stop(after) if tracer else None,
            "device": device_of(after),
            "counters": (counters_before, counters_after)}


def read_end_to_end(cell: Cell, sample, ok: list, setup_s: float) -> dict:
    """Each of the cell's end-to-end metrics by the statistic its file
    under `end_to_end/` names."""
    out = {}
    for m in cell.end_to_end():
        spec = load_json(cell.home, "end_to_end", m["name"] + ".json")
        out[m["name"]] = {"value": arithmetic.end_to_end(spec, sample, ok,
                                                         setup_s),
                          "unit": m["unit"]}
    return out


def read_layers(cell: Cell, ctx: dict) -> dict:
    out = {}
    for m in cell.per_layer():
        spec = load_json(cell.home, "layer_metrics", m["name"] + ".json")
        reader = importlib.import_module("benchmark.readers." + spec["reader"])
        value = reader.read(spec, ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def keep_sample(out_dir: str, sample, ok: list) -> None:
    """The window as the generator saw it, one entry a request, seconds
    from the window's opening: to be looked at by hand."""
    def rel(ts):
        return [None if t is None else round(t - sample.t0, 6) for t in ts]
    with open(os.path.join(out_dir, "sample.json"), "w") as f:
        json.dump({"seconds": sample.seconds, "due": rel(sample.due),
                   "sent": rel(sample.sent), "done": rel(sample.done),
                   "ok": [int(g) for g in ok]}, f)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run(args) -> dict:
    cell = Cell(ROOT, args.workload)
    config, traffic = cell.config, cell.traffic
    kind = importlib.import_module("benchmark.kinds." + traffic["kind"])
    n_rows = config["rows"]
    if args.rows:
        if not args.rehearse:
            raise RunFailure("--rows is for rehearsals")
        n_rows = args.rows
    out_dir = os.path.join(HERE, "out", args.out or cell.name)
    shutil.rmtree(out_dir, ignore_errors=True)      # no stale data or logs
    os.makedirs(out_dir)
    data_dir = os.path.join(out_dir, "data")
    corpus = Corpus(args.seed, config)

    child = Child(out_dir, data_dir, "main", config["server_settings"],
                  fault=args.fault)
    try:
        up_s = child.wait_ready()
        note(f"server pid={child.proc.pid} port={child.port} up_s={up_s:.1f}")
        check_device(child, cell.chips, args.rehearse)
        this = Run(cell, args, child, corpus, n_rows, out_dir, data_dir)
        state = kind.prepare(this)
        got = window(child, traffic, args,
                     lambda first, count: kind.make_items(state, first,
                                                          count))
        got.update(kind.judge(this, state, got))
    except RunFailure:
        note(f"--- {child.log_path} (tail) ---\n{child.log_tail()}")
        raise
    finally:
        child.stop()
        shutil.rmtree(data_dir, ignore_errors=True)

    sample, ok = got["sample"], got["ok"]
    keep_sample(out_dir, sample, ok)
    compared = verify.judge(got["numbers"], config["limits"])
    result = {"correct": all(c["ok"] for c in compared.values()),
              "attempted": len(ok), "failed": ok.count(False)}
    setup_s = sample.t0 - T_START
    end_to_end = read_end_to_end(cell, sample, ok, setup_s)
    if args.trace:
        trace = got["trace"]
        ctx = {"before": got["before"], "after": got["after"],
               "seconds": sample.seconds, "sample": sample, "trace": trace,
               "trace_before": trace["before"], "trace_after": trace["after"],
               "config": config, "rows": got["rows"],
               "device_kind": got["device"]["kind"],
               "platform": got["device"]["platform"],
               "counters_before": got["counters"][0],
               "counters_after": got["counters"][1]}
        result["metrics"] = read_layers(cell, ctx)
        result["device"] = dict(got["device"], busy_s=trace["busy_s"],
                                window_s=trace["window_s"])
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    else:
        result["metrics"] = end_to_end
        result["device"] = got["device"]
    if args.rehearse:
        result["rehearsal"] = True
    if got["control"] is not None:
        result["control"] = got["control"]
        for name, value in got["control"].items():
            note(f"control {name} {value!r}")
    result["compared"] = {k: {"value": c["value"], "limit": c["limit"]}
                          for k, c in compared.items()}
    note(f"setup_s={setup_s:.1f} window_s={sample.seconds} "
         f"attempted={len(ok)} failed={ok.count(False)} "
         f"total_s={time.monotonic() - T_START:.1f}")
    # the end-to-end readings of a traced run too, beside its per-layer ones
    note("window " + " ".join(f"{k}={v['value']!r}"
                              for k, v in end_to_end.items()))
    per_s = [0] * (int(sample.seconds) + 1)
    for d in sample.done:
        if d is not None and 0 <= d - sample.t0 < len(per_s):
            per_s[int(d - sample.t0)] += 1
    note(f"answers_per_second={per_s}")
    late = arithmetic.lateness_ms(sample.due, sample.sent)
    took = arithmetic.lookup(got["after"],
                             "telemetry/histograms/search.took/max_nanos")
    note(f"sent_late_max_ms={max(late, default=0.0):.1f} "
         f"server_took_max_ms={(took or 0) / 1e6:.1f}")
    for name, c in compared.items():
        note(f"compared {name} {c['value']!r} (must be {c['must']} "
             f"{c['limit']!r}) {'ok' if c['ok'] else 'NOT OK'}")
    return result


def finite(obj):
    """The line has to parse everywhere: an infinity (the latency of a
    request that failed, an error with no hit to take it over) is written
    as 1e12."""
    if isinstance(obj, dict):
        return {k: finite(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [finite(v) for v in obj]
    if isinstance(obj, float) and obj in (float("inf"), float("-inf")):
        return 1e12 if obj > 0 else -1e12
    return obj


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: relaxes the platform check, "
                         "nothing else; the line names the platform the "
                         "child reported")
    ap.add_argument("--rows", type=int, default=0,
                    help="rows to load (rehearsals only)")
    ap.add_argument("--control", action="store_true",
                    help="also read the control (the reference in int8) "
                         "against the same limits; not part of a run")
    ap.add_argument("--out", default="",
                    help="output directory: a name under benchmark/out/ "
                         "(default: the workload's), or an absolute path")
    ap.add_argument("--fault", default="", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    # a SIGTERM (a test's or the driver's time limit) must still stop the
    # child: turn it into an exception the finally blocks see
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        result = run(args)
    except RunFailure as e:
        note(f"FAILED: {e}")
        return 1
    print(json.dumps(finite(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
