"""`chip_smoke.py`'s CPU rehearsal, as subprocesses (4,096 rows healthy).

The script is the quickest proof that the system still starts on the
chip, so it must not be able to exit 0 past a failed phase: a healthy
rehearsal exits 0 with the contract line LAST (its platform the one the
server child reported — "cpu" here, so a rehearsal never reads as a chip
run); a killed server child, a forced recall failure and a missing chip
each exit non-zero with no contract line.

All five runs start together (each with its own output and compile-cache
directory) and every test waits for its own, under its own 120 s limit.
"""

import json
import os
import re
import signal
import subprocess
import sys
import threading

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIMIT_S = 120


class _Run:
    def __init__(self, root, name, *flags, kill_server=False,
                 cpu_devices=None, rows=4096):
        self.lines = []
        self.killed_pid = None
        self._kill_server = kill_server
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(root / f"{name}_cache"))
        env.pop("XLA_FLAGS", None)
        if cpu_devices:
            env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                                f"{cpu_devices}")
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(REPO, "chip_smoke.py"),
             "--rows", str(rows), "--out", str(root / name), *flags],
            cwd=REPO, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self):
        for line in self.proc.stdout:
            self.lines.append(line.rstrip("\n"))
            m = re.match(r"server_cold pid=(\d+)", line)
            if m and self._kill_server:
                # the server is up and ingest has just begun: kill it
                # mid-run, one second in
                self.killed_pid = int(m.group(1))
                threading.Timer(1.0, os.kill, (self.killed_pid,
                                               signal.SIGKILL)).start()

    def wait(self):
        try:
            rc = self.proc.wait(timeout=LIMIT_S)
        except subprocess.TimeoutExpired:
            self.proc.send_signal(signal.SIGTERM)
            self.proc.wait(timeout=30)
            pytest.fail("chip_smoke.py rehearsal passed its "
                        f"{LIMIT_S}s limit:\n" + "\n".join(self.lines[-20:]))
        self._reader.join(timeout=10)
        return rc

    def contract_lines(self):
        return [ln for ln in self.lines if ln.startswith('{"ok"')]


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    root = tmp_path_factory.mktemp("smoke")
    started = {
        "healthy": _Run(root, "healthy", "--rehearse"),
        "killed": _Run(root, "killed", "--rehearse", kill_server=True),
        # the fault-injection runs load one _bulk block, not two: five
        # servers fsync-ing at once is load the rest of the suite feels
        "bad_recall": _Run(root, "bad_recall", "--rehearse",
                           "--break-recall", rows=2048),
        "no_chip": _Run(root, "no_chip"),
        "four": _Run(root, "four", "--rehearse", "--chips", "4",
                     cpu_devices=4, rows=2048),
    }
    yield started
    for run in started.values():
        if run.proc.poll() is None:
            run.proc.send_signal(signal.SIGTERM)
            run.proc.wait(timeout=30)


def test_healthy_rehearsal_exits_zero_with_the_contract_line_last(runs):
    run = runs["healthy"]
    rc = run.wait()
    out = "\n".join(run.lines)
    assert rc == 0, out[-3000:]
    last = json.loads(run.lines[-1])
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": last["device"]["kind"], "count": 1}}
    assert run.contract_lines() == [run.lines[-1]]
    # what the earlier lines must carry
    assert re.search(r"^ingest rows=4096 dims=128 docs_per_s=\d+", out, re.M)
    assert re.search(r"^recall_at_10 (0\.9[5-9]\d*|1\.0+) ", out, re.M)
    assert "host_mirror_searches=0" in out
    assert re.search(r'kernel_hits=\{"knn.binned": 0, "knn.exact": [1-9]\d*,'
                     r' "aggs\.\*": [1-9]', out)
    m = re.search(r"^compile_cache entries cold=(\d+) warm=(\d+)$", out,
                  re.M)
    assert m and int(m.group(1)) == int(m.group(2)) > 0
    assert re.search(r"^time_to_first_answer cold_s=[\d.]+ warm_s=[\d.]+",
                     out, re.M)


def test_killed_server_child_exits_nonzero(runs):
    run = runs["killed"]
    rc = run.wait()
    assert run.killed_pid is not None, "\n".join(run.lines)
    assert rc != 0
    assert not run.contract_lines()
    assert any(ln.startswith("FAILED:") for ln in run.lines)


def test_failed_recall_check_exits_nonzero(runs):
    run = runs["bad_recall"]
    rc = run.wait()
    assert rc != 0
    assert not run.contract_lines()
    assert any(re.match(r"FAILED: recall_at_10 0\.0000 is under", ln)
               for ln in run.lines), "\n".join(run.lines[-15:])


def test_without_a_chip_and_without_the_flag_exits_nonzero(runs):
    run = runs["no_chip"]
    rc = run.wait()
    assert rc != 0
    assert not run.contract_lines()
    assert any("JAX found no accelerator" in ln for ln in run.lines)


def test_four_chip_phase_rehearses_on_four_virtual_devices(runs):
    """`--chips 4` runs only the mesh phase and the mesh-off run it is
    compared with; the contract line's count is 4."""
    run = runs["four"]
    rc = run.wait()
    out = "\n".join(run.lines)
    assert rc == 0, out[-3000:]
    assert json.loads(run.lines[-1])["device"]["count"] == 4
    assert re.search(r"^mesh available=True num_shards=4 ", out, re.M)
    assert re.search(r'kernel_hits=\{"mesh.knn": [1-9]', out)
    assert re.search(r"^mesh_vs_single identical_answers=\d+/65 "
                     r"filtered_identical=True$", out, re.M)
    assert "server_cold" not in out and "aggs " not in out
