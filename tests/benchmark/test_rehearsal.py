"""Each cell end to end on the CPU (`--rehearse`, tiny rows), as
subprocesses started together, each under its own limit (the pattern of
`tests/test_chip_smoke.py`).

A healthy rehearsal exits 0 with the contract's object LAST, naming the
platform the child reported ("cpu" here, so it never reads as a chip run).
A run whose timed path is broken underneath (an answer's ids or scores altered
where it is produced) still ends, and says `correct: false`. A killed child and a missing chip each exit non-zero and
print no contract line.
"""

import json
import os
import re
import signal
import subprocess
import sys
import threading

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LIMIT_S = 240

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

# name -> (workload, trace, extra flags, kill the child?)
CASES = {
    "knn-saturated": ("knn-saturated", 0, ["--rehearse", "--control"], False),
    "knn-saturated-traced": ("knn-saturated", 1, ["--rehearse"], False),
    "knn-steady": ("knn-steady", 1, ["--rehearse"], False),
    "knn-steady-e2e": ("knn-steady", 0, ["--rehearse"], False),
    "fault-alter-ids": ("knn-saturated", 0,
                        ["--rehearse", "--fault", "alter_ids"], False),
    "fault-alter-scores": ("knn-steady", 0,
                           ["--rehearse", "--fault", "alter_scores"], False),
    "killed-child": ("knn-saturated", 0, ["--rehearse"], True),
    "no-chip": ("knn-saturated", 0, [], False),
}
HEALTHY = [n for n in CASES if not n.startswith(("fault", "killed", "no-"))]


class _Run:
    def __init__(self, root, name):
        workload, trace, flags, kill = CASES[name]
        self.out, self.err = [], []
        self.killed_pid = None
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(root / f"{name}_cache"))
        env.pop("XLA_FLAGS", None)
        cmd = [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
               "--workload", workload, "--seed", str(2 ** 31 + len(name)),
               "--seconds", "2", "--trace", str(trace),
               "--out", str(root / name), *flags]
        if "--rehearse" in flags:
            cmd += ["--rows", "2048"]
        self.proc = subprocess.Popen(cmd, cwd=REPO, env=env, text=True,
                                     stdout=subprocess.PIPE,
                                     stderr=subprocess.PIPE)
        self._threads = [
            threading.Thread(target=self._read_out, daemon=True),
            threading.Thread(target=self._read_err, args=(kill,),
                             daemon=True)]
        for t in self._threads:
            t.start()

    def _read_out(self):
        for line in self.proc.stdout:
            self.out.append(line.rstrip("\n"))

    def _read_err(self, kill):
        for line in self.proc.stderr:
            self.err.append(line.rstrip("\n"))
            m = re.match(r"server pid=(\d+)", line)
            if m and kill:
                # the server is up and the load has just begun
                self.killed_pid = int(m.group(1))
                threading.Timer(0.5, os.kill, (self.killed_pid,
                                               signal.SIGKILL)).start()

    def wait(self):
        try:
            rc = self.proc.wait(timeout=LIMIT_S)
        except subprocess.TimeoutExpired:
            self.proc.send_signal(signal.SIGTERM)
            self.proc.wait(timeout=60)
            pytest.fail(f"the rehearsal passed its {LIMIT_S}s limit:\n"
                        + "\n".join(self.err[-20:]))
        for t in self._threads:
            t.join(timeout=10)
        return rc

    def tail(self):
        return "\n".join(self.err[-40:])


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench")
    started = {name: _Run(root, name) for name in CASES}
    yield started
    for run in started.values():
        if run.proc.poll() is None:
            run.proc.send_signal(signal.SIGTERM)
            run.proc.wait(timeout=60)


def _metrics_of(cell, kind):
    return {m["name"] for m in BENCH[kind]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("name", HEALTHY)
def test_a_healthy_rehearsal_ends_with_the_contract_line(runs, name):
    run = runs[name]
    rc = run.wait()
    assert rc == 0, run.tail()
    workload, trace, _flags, _kill = CASES[name]
    last = json.loads(run.out[-1])
    assert [ln for ln in run.out if ln.startswith('{"correct"')] == \
        [run.out[-1]]
    assert list(last)[:4] == ["correct", "attempted", "failed", "metrics"]
    assert list(last)[-1] == "compared"
    assert last["correct"] is True, run.tail()
    assert last["failed"] == 0 and last["attempted"] > 0
    # a rehearsal can never read as a chip run
    assert last["device"]["platform"] == "cpu" and last["rehearsal"] is True
    assert last["device"]["count"] == 1
    assert "memory_peak_bytes" in last["device"]
    for name_, m in last["metrics"].items():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], float) and m["value"] == m["value"]
    if trace:
        # the per-layer metrics of the cell; a reader that finds nothing to
        # read (a roofline share on the CPU) leaves its metric out
        want = _metrics_of(workload, "per_layer")
        got = set(last["metrics"])
        assert got <= want
        assert want - got <= {"knn_roofline.sat", "knn_roofline.steady"}
        assert last["device"]["window_s"] > 0
        assert last["device"]["busy_s"] > 0
        assert len(last["breakdown"]["device_ops"]) <= 10
        assert len(last["breakdown"]["idle_gaps"]) <= 10
    else:
        assert set(last["metrics"]) == _metrics_of(workload, "end_to_end")
        assert all(m["value"] > 0 for m in last["metrics"].values())
    # each number compared stands beside its limit, on stderr too
    for key, c in last["compared"].items():
        assert set(c) == {"value", "limit"}
        assert any(ln.startswith(f"compared {key} ") for ln in run.err[-12:])
    assert last["compared"]["host_mirror_searches"]["value"] == 0
    assert last["compared"]["recall_at_k"]["value"] >= 0.95
    if "--control" in CASES[name][2]:
        # the control reads worse than the program on the number it is for
        assert last["control"]["score_rms_err"] > \
            last["compared"]["score_rms_err"]["value"]


@pytest.mark.parametrize("name, number", [
    ("fault-alter-ids", "recall_at_k"),
    ("fault-alter-scores", "score_rms_err"),
])
def test_a_broken_timed_path_comes_out_not_correct(runs, name, number):
    run = runs[name]
    rc = run.wait()
    assert rc == 0, run.tail()
    last = json.loads(run.out[-1])
    assert last["correct"] is False
    c = last["compared"][number]
    assert c["value"] != c["limit"]
    assert any(ln.startswith(f"compared {number} ") and ln.endswith("NOT OK")
               for ln in run.err[-12:]), run.tail()


@pytest.mark.parametrize("name", ["killed-child", "no-chip"])
def test_no_result_without_a_live_child_on_a_chip(runs, name):
    run = runs[name]
    rc = run.wait()
    assert rc != 0, run.tail()
    assert not [ln for ln in run.out if ln.startswith("{")]
    if name == "killed-child":
        assert run.killed_pid is not None, run.tail()
        assert any("exited with code" in ln or "FAILED" in ln
                   for ln in run.err)
    else:
        assert any("JAX found no accelerator" in ln for ln in run.err)
