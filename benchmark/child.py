"""The parent's handle on the server child, and its HTTP surface.

Copied from `chip_smoke.py` `Server` (sound there), with keep-alive
connections for the load generator and the control commands of
`benchmark/serve.py`. The parent never imports JAX.
"""

from __future__ import annotations

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
from typing import Optional, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class RunFailure(Exception):
    """The run cannot give a result: no contract line, exit code not 0."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Connection:
    """One keep-alive HTTP connection (one per client thread)."""

    def __init__(self, port: int, timeout: float = 120.0):
        self.port, self.timeout = port, timeout
        self._conn: Optional[http.client.HTTPConnection] = None

    def request(self, method: str, path: str, body: Optional[bytes] = None):
        """(status, raw body). Reconnects once where the server closed an
        idle connection; any other failure raises OSError/HTTPException."""
        ctype = ("application/x-ndjson" if path.endswith("_bulk")
                 else "application/json")
        for attempt in (0, 1):
            if self._conn is None:
                self._conn = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=self.timeout)
                self._conn.connect()
                self._conn.sock.setsockopt(socket.IPPROTO_TCP,
                                           socket.TCP_NODELAY, 1)
            try:
                self._conn.request(method, path, body=body,
                                   headers={"Content-Type": ctype})
                resp = self._conn.getresponse()
                return resp.status, resp.read()
            except (http.client.RemoteDisconnected, BrokenPipeError,
                    ConnectionResetError):
                self.close()
                if attempt:
                    raise
        raise AssertionError("unreachable")

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None


class Child:
    """One `benchmark/serve.py` process: the only one that touches JAX."""

    def __init__(self, out_dir: str, data_dir: str, tag: str,
                 settings: Sequence[str] = (), fault: str = "",
                 env: Optional[dict] = None):
        self.tag = tag
        self.port = free_port()
        self.log_path = os.path.join(out_dir, f"server_{tag}.log")
        self.ctl_path = os.path.join(out_dir, f"ctl_{tag}.jsonl")
        self.trace_dir = os.path.join(out_dir, f"trace_{tag}")
        open(self.ctl_path, "w").close()
        self._replies = 0
        self._log = open(self.log_path, "wb")
        cmd = [sys.executable, os.path.join(HERE, "serve.py"),
               "--ctl-out", self.ctl_path, "--trace-dir", self.trace_dir]
        if fault:
            cmd += ["--fault", fault]
        cmd += ["--port", str(self.port), "--data", data_dir]
        for kv in settings:
            cmd += ["-E", kv]
        # one source of run-to-run spread less: the child's str hashes (and
        # so its dict and set orders) are the same in every run
        env = dict(os.environ if env is None else env, PYTHONHASHSEED="0")
        self.started = time.monotonic()
        self.proc = subprocess.Popen(cmd, cwd=ROOT, stdin=subprocess.PIPE,
                                     stdout=self._log,
                                     stderr=subprocess.STDOUT, env=env)
        self._conn = Connection(self.port, timeout=600.0)
        self._lock = threading.Lock()

    def alive(self) -> bool:
        return self.proc.poll() is None

    def _must_live(self) -> None:
        if not self.alive():
            raise RunFailure(f"server child [{self.tag}] exited with code "
                             f"{self.proc.returncode}")

    def request(self, method: str, path: str, body=None):
        self._must_live()
        if body is not None and not isinstance(body, bytes):
            body = json.dumps(body).encode()
        try:
            with self._lock:
                status, raw = self._conn.request(method, path, body)
        except (OSError, http.client.HTTPException) as e:
            self._conn.close()
            raise RunFailure(f"{method} {path}: {type(e).__name__}: {e}")
        try:
            parsed = json.loads(raw) if raw else None
        except ValueError:
            parsed = raw.decode(errors="replace")
        return status, parsed

    def ok(self, method: str, path: str, body=None):
        status, parsed = self.request(method, path, body)
        if status >= 300:
            raise RunFailure(f"{method} {path} -> {status}: "
                             f"{json.dumps(parsed)[:1200]}")
        return parsed

    def wait_ready(self, limit: float = 300.0) -> float:
        deadline = time.monotonic() + limit
        while time.monotonic() < deadline:
            self._must_live()
            try:
                status, _ = self.request("GET", "/_cluster/health")
                if status == 200:
                    return time.monotonic() - self.started
            except RunFailure:
                self._must_live()
            time.sleep(0.1)
        raise RunFailure(f"server [{self.tag}] did not answer "
                         f"/_cluster/health within {limit:.0f}s")

    def node_stats(self) -> dict:
        (node,) = self.ok("GET", "/_nodes/stats")["nodes"].values()
        return node

    def command(self, cmd: str, limit: float = 240.0) -> dict:
        """Send one control command to `serve.py`; wait for its reply."""
        self._must_live()
        self.proc.stdin.write((cmd + "\n").encode())
        self.proc.stdin.flush()
        deadline = time.monotonic() + limit
        while time.monotonic() < deadline:
            with open(self.ctl_path) as f:
                lines = f.read().splitlines()
            if len(lines) > self._replies:
                reply = json.loads(lines[self._replies])
                self._replies += 1
                if "error" in reply:
                    raise RunFailure(f"child command {cmd}: {reply['error']}")
                return reply
            self._must_live()
            time.sleep(0.01)
        raise RunFailure(f"child command {cmd}: no reply in {limit:.0f}s")

    def kill(self) -> None:
        """SIGKILL: what a crash leaves is what recovery gets."""
        if self.alive():
            self.proc.kill()
        self._finish()

    def stop(self) -> None:
        # the program's `HttpServer.stop` waits for every open connection
        # (asyncio's `wait_closed`), so an idle keep-alive one would hold
        # the SIGTERM for the whole timeout below: close ours first
        self._conn.close()
        if self.alive():
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
        self._finish()

    def _finish(self) -> None:
        self.proc.wait(timeout=60)
        self._conn.close()
        if self.proc.stdin:
            try:
                self.proc.stdin.close()
            except OSError:
                pass
        if not self._log.closed:
            self._log.close()

    def log_tail(self, lines: int = 25) -> str:
        try:
            with open(self.log_path, "rb") as f:
                tail = f.read().splitlines()[-lines:]
        except OSError:
            return ""
        return b"\n".join(ln[:600] for ln in tail).decode(errors="replace")
