"""Kill-and-restart recovery test against real server processes
(ref: integration_tests/recovery/run.sh:30-45 — write, kill -9, restart,
verify; repeat after explicit flush)."""

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def start_server(data_dir: str, port: int) -> subprocess.Popen:
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO,
    }
    proc = subprocess.Popen(
        [sys.executable, "-m", "horaedb_tpu.server",
         "--data-dir", data_dir, "--port", str(port)],
        env=env,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            urllib.request.urlopen(f"http://127.0.0.1:{port}/health", timeout=1)
            return proc
        except Exception:
            if proc.poll() is not None:
                raise RuntimeError("server died during startup")
            time.sleep(0.2)
    proc.kill()
    raise RuntimeError("server did not become healthy")


def post(port, path, payload) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        json.dumps(payload).encode(),
        {"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=10) as resp:
        body = resp.read()
        return json.loads(body) if body else {}


@pytest.mark.slow
def test_kill9_recovery_cycle(tmp_path):
    data = str(tmp_path / "data")
    port = free_port()
    proc = start_server(data, port)
    try:
        post(port, "/sql", {"query": (
            "CREATE TABLE r (host string TAG, v double NOT NULL, "
            "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) WITH (segment_duration='1h')"
        )})
        post(port, "/write", {"table": "r", "rows": [
            {"host": f"h{i%3}", "v": float(i), "ts": i * 1000} for i in range(50)
        ]})
        # no flush: rows live in WAL + memtable only
    finally:
        proc.send_signal(signal.SIGKILL)
        proc.wait()

    # Restart 1: WAL replay must recover everything.
    port2 = free_port()
    proc = start_server(data, port2)
    try:
        out = post(port2, "/sql", {"query": "SELECT count(*) AS c, max(v) AS m FROM r"})
        assert out["rows"] == [{"c": 50, "m": 49.0}]
        # More writes + enough volume to reach SSTs via tiny buffer table.
        post(port2, "/write", {"table": "r", "rows": [
            {"host": "hX", "v": 999.0, "ts": 999_000}
        ]})
    finally:
        proc.send_signal(signal.SIGKILL)
        proc.wait()

    # Restart 2: both the old rows and the post-recovery write survive.
    port3 = free_port()
    proc = start_server(data, port3)
    try:
        out = post(port3, "/sql", {"query": "SELECT count(*) AS c, max(v) AS m FROM r"})
        assert out["rows"] == [{"c": 51, "m": 999.0}]
        out = post(port3, "/sql", {"query": "SELECT v FROM r WHERE host = 'hX'"})
        assert out["rows"] == [{"v": 999.0}]
    finally:
        proc.send_signal(signal.SIGKILL)
        proc.wait()
