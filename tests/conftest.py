"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

The tests run on the CPU; sharding correctness is validated on 8 virtual
CPU devices (the same XLA partitioner runs either way). The chip is
reached through `chip_smoke.py`, never from here.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running sweeps excluded from tier-1 "
                   "(`-m 'not slow'`)")
    config.addinivalue_line(
        "markers", "multidevice: exercises the SPMD mesh serving path on "
                   "the 8 virtual CPU devices this conftest forces; runs "
                   "in tier-1, and `-m multidevice` under "
                   "ES_TPU_DISPATCH_STRICT=1 is the sharded-grid "
                   "recompile-regression gate (see ROADMAP)")


import pytest


@pytest.fixture
def mesh_serving():
    """Force the mesh serving policy ON over the 8 virtual devices (row
    floor 1 so tiny test corpora route to the mesh), restore the
    process-wide auto policy afterwards. Yields the policy module so
    tests can read `stats()` / flip config mid-test."""
    from elasticsearch_tpu.parallel import policy
    policy.reset(full=True)
    policy.configure(enabled=True, num_shards=8, min_rows=1)
    if policy.serving_mesh() is None:
        policy.reset(full=True)
        pytest.skip("needs >= 2 jax devices (forced-host-device-count)")
    yield policy
    policy.reset(full=True)


@pytest.fixture
def mesh_serving_dp():
    """Replicated-mesh policy: (dp=2, shard=4) over the 8 virtual
    devices, row floor 1 — the dp > 1 serving grid (test_mesh_serving's
    dp cases and the strict dp-grid recompile gate)."""
    from elasticsearch_tpu.parallel import policy
    policy.reset(full=True)
    policy.configure(enabled=True, dp=2, num_shards=4, min_rows=1)
    mesh = policy.serving_mesh()
    if mesh is None or policy.dp_size() != 2:
        policy.reset(full=True)
        pytest.skip("needs 8 jax devices (forced-host-device-count)")
    yield policy
    policy.reset(full=True)


import contextlib
import socket
import subprocess


@contextlib.contextmanager
def http_server_subprocess(port: int, data_dir: str, startup_timeout=60.0):
    """Spawn a real `python -m elasticsearch_tpu.server` and wait until it
    accepts connections (shared by end-to-end client/wire tests)."""
    import time as _time

    proc = subprocess.Popen(
        [sys.executable, "-m", "elasticsearch_tpu.server", "--port",
         str(port), "--data", str(data_dir)],
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "PYTHONPATH": "."},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    deadline = _time.time() + startup_timeout
    try:
        while True:
            try:
                socket.create_connection(("127.0.0.1", port),
                                         timeout=1).close()
                break
            except OSError:
                if _time.time() > deadline or proc.poll() is not None:
                    proc.terminate()
                    raise RuntimeError("server did not start")
                _time.sleep(0.5)
        yield proc
    finally:
        proc.terminate()
        proc.wait(timeout=10)


@pytest.fixture(autouse=True)
def _isolate_stored_scripts():
    """GLOBAL_SCRIPTS is the process-wide cluster-state analog; clear it
    between tests so stored scripts don't leak across test cases."""
    yield
    from elasticsearch_tpu.script.service import GLOBAL_SCRIPTS
    GLOBAL_SCRIPTS.clear()
    GLOBAL_SCRIPTS._path = None
