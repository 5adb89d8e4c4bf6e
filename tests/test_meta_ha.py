"""Meta HA: leader election + follower redirect + leader failover
(ref model: horaemeta member election, member.go:41-283)."""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from horaedb_tpu.meta.election import FileLease

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestFileLease:
    def test_single_acquire_and_renew(self, tmp_path):
        l1 = FileLease(str(tmp_path / "lock"), "m1:1", ttl_s=5)
        assert l1.try_acquire()
        assert l1.leader() == "m1:1"
        assert l1.renew()

    def test_second_candidate_stands_down(self, tmp_path):
        l1 = FileLease(str(tmp_path / "lock"), "m1:1", ttl_s=5)
        l2 = FileLease(str(tmp_path / "lock"), "m2:2", ttl_s=5)
        assert l1.try_acquire()
        assert not l2.try_acquire()
        assert l2.leader() == "m1:1"
        assert not l2.renew()

    def test_takeover_after_expiry(self, tmp_path):
        l1 = FileLease(str(tmp_path / "lock"), "m1:1", ttl_s=0.2)
        l2 = FileLease(str(tmp_path / "lock"), "m2:2", ttl_s=5)
        assert l1.try_acquire()
        time.sleep(0.3)
        assert l2.try_acquire()
        assert not l1.renew()  # old leader sees it lost

    def test_resign_frees_lock(self, tmp_path):
        l1 = FileLease(str(tmp_path / "lock"), "m1:1", ttl_s=5)
        l2 = FileLease(str(tmp_path / "lock"), "m2:2", ttl_s=5)
        assert l1.try_acquire()
        l1.resign()
        assert l2.try_acquire()


# ---- two-meta process e2e --------------------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(method, url, payload=None, timeout=10.0):
    data = json.dumps(payload).encode() if payload is not None else None
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"}, method=method
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read().decode() or "{}")
    except urllib.error.HTTPError as e:
        try:
            return e.code, json.loads(e.read().decode() or "{}")
        except Exception:
            return e.code, {}


def wait_until(fn, timeout=60.0, desc=""):
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        try:
            last = fn()
            if last:
                return last
        except Exception as e:
            last = e
        time.sleep(0.3)
    raise TimeoutError(f"{desc}: last={last}")


CPU_ENV = {
    **os.environ,
    "JAX_PLATFORMS": "cpu",
    "PYTHONPATH": REPO,
}


class TestTwoMetaFailover:
    def test_leader_failover_preserves_state(self, tmp_path):
        ha_dir = str(tmp_path / "ha")
        ports = [free_port(), free_port()]
        procs = []
        for port in ports:
            procs.append(
                subprocess.Popen(
                    [
                        sys.executable, "-m", "horaedb_tpu.meta",
                        "--port", str(port),
                        "--ha-dir", ha_dir,
                        "--advertise", f"127.0.0.1:{port}",
                        "--num-shards", "2",
                        "--lease-ttl", "1.0",
                        "--tick-interval", "0.2",
                    ],
                    env=CPU_ENV,
                    stdout=open(tmp_path / f"meta{port}.log", "wb"),
                    stderr=subprocess.STDOUT,
                )
            )
        try:
            for port in ports:
                wait_until(
                    lambda p=port: http("GET", f"http://127.0.0.1:{p}/health")[0] == 200,
                    desc=f"meta {port} health",
                )

            def leader_port():
                leaders = [
                    p for p in ports
                    if http("GET", f"http://127.0.0.1:{p}/health")[1].get("leader")
                ]
                return leaders[0] if len(leaders) == 1 else None

            lp = wait_until(leader_port, desc="exactly one leader")
            follower = next(p for p in ports if p != lp)

            # follower redirects mutations with a leader hint (421)
            status, body = http(
                "POST",
                f"http://127.0.0.1:{follower}/meta/v1/node/heartbeat",
                {"endpoint": "127.0.0.1:59999"},
            )
            assert status == 421 and body.get("leader") == f"127.0.0.1:{lp}", body

            # MetaClient follows the hint transparently
            from horaedb_tpu.cluster.meta_client import MetaClient

            client = MetaClient([f"127.0.0.1:{follower}", f"127.0.0.1:{lp}"])
            out = client.heartbeat("127.0.0.1:59999")
            assert "desired" in out

            # kill the leader: the follower takes over and RELOADS state
            # (the registered node survives in the shared journal)
            victim = procs[ports.index(lp)]
            victim.kill()
            victim.wait(timeout=10)

            def new_leader():
                s, b = http("GET", f"http://127.0.0.1:{follower}/health")
                return s == 200 and b.get("leader")

            wait_until(new_leader, desc="follower takes leadership")
            s, nodes = http("GET", f"http://127.0.0.1:{follower}/meta/v1/nodes")
            assert s == 200
            assert any(
                n["endpoint"] == "127.0.0.1:59999" for n in nodes["nodes"]
            ), nodes
            out = client.heartbeat("127.0.0.1:59999")
            assert "desired" in out
        finally:
            for p in procs:
                if p.poll() is None:
                    p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
