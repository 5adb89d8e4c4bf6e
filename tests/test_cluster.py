"""Cluster groundwork tests: shard state machine, router, 2-node forwarding
(ref model: cluster shard_set tests + the 2-node sqlness cluster env)."""

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import pytest

from horaedb_tpu.cluster import Route, RuleBasedRouter, Shard, ShardSet, ShardState
from horaedb_tpu.cluster.router import LocalOnlyRouter
from horaedb_tpu.cluster.shard import ShardError, ShardInfo


class TestShardStateMachine:
    def test_lifecycle(self):
        s = Shard(ShardInfo(shard_id=1, version=1, table_ids=(10,)))
        assert s.state is ShardState.INIT
        s.begin_open()
        assert s.state is ShardState.OPENING
        s.finish_open()
        assert s.state is ShardState.READY
        s.ensure_writable()
        s.freeze()
        with pytest.raises(ShardError, match="write fenced"):
            s.ensure_writable()
        s.thaw()
        s.ensure_writable()
        s.freeze()
        s.close()
        assert s.state is ShardState.INIT

    def test_illegal_transitions(self):
        s = Shard(ShardInfo(shard_id=1))
        with pytest.raises(ShardError):
            s.finish_open()  # not opening
        s.begin_open()
        with pytest.raises(ShardError):
            s.begin_open()  # already opening
        with pytest.raises(ShardError):
            s.freeze()  # not ready

    def test_version_fencing(self):
        s = Shard(ShardInfo(shard_id=1, version=5, table_ids=(1,)))
        with pytest.raises(ShardError, match="stale"):
            s.apply_update(ShardInfo(shard_id=1, version=5, table_ids=(2,)))
        s.apply_update(ShardInfo(shard_id=1, version=6, table_ids=(2,)))
        assert s.table_ids == (2,)

    def test_shard_set(self):
        ss = ShardSet()
        s = Shard(ShardInfo(shard_id=7))
        ss.insert(s)
        with pytest.raises(ShardError):
            ss.insert(Shard(ShardInfo(shard_id=7)))
        assert ss.get(7) is s
        assert ss.ready_count() == 0
        s.begin_open(); s.finish_open()
        assert ss.ready_count() == 1
        assert ss.remove(7) is s
        assert ss.get(7) is None


class TestRouter:
    def test_rule_pins_win(self):
        r = RuleBasedRouter("a:1", ["a:1", "b:2"], {"pinned": "b:2"})
        assert r.route("pinned") == Route("pinned", "b:2", False)

    def test_hash_fallback_stable_and_covering(self):
        r1 = RuleBasedRouter("a:1", ["a:1", "b:2"])
        r2 = RuleBasedRouter("b:2", ["a:1", "b:2"])
        # same topology -> identical routing decisions on every node
        for t in ("t1", "t2", "zzz", "cpu"):
            assert r1.route(t).endpoint == r2.route(t).endpoint
        # both nodes get some tables (hash spreads)
        eps = {r1.route(f"table_{i}").endpoint for i in range(32)}
        assert eps == {"a:1", "b:2"}

    def test_self_must_be_in_topology(self):
        with pytest.raises(ValueError, match="not in topology"):
            RuleBasedRouter("c:3", ["a:1", "b:2"])
        with pytest.raises(ValueError, match="unknown endpoint"):
            RuleBasedRouter("a:1", ["a:1"], {"t": "b:2"})

    def test_local_only(self):
        assert LocalOnlyRouter().route("anything").is_local


# ---- two real nodes over HTTP ------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def write_config(tmp_path, name, port, peer_port, data_dir, rules):
    self_ep = f"127.0.0.1:{port}"
    peer_ep = f"127.0.0.1:{peer_port}"
    rules_lines = "\n".join(f'{t} = "{ep}"' for t, ep in rules.items())
    p = tmp_path / f"{name}.toml"
    p.write_text(f"""
[server]
http_port = {port}

[engine]
data_dir = "{data_dir}"

[cluster]
self_endpoint = "{self_ep}"
endpoints = ["127.0.0.1:{min(port, peer_port)}", "127.0.0.1:{max(port, peer_port)}"]

[cluster.rules]
{rules_lines}
""")
    return str(p)


def start_node(config_path) -> subprocess.Popen:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    return subprocess.Popen(
        [sys.executable, "-m", "horaedb_tpu.server", "--config", config_path],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )


def wait_healthy(port, proc, timeout=30):
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            urllib.request.urlopen(f"http://127.0.0.1:{port}/health", timeout=1)
            return
        except Exception:
            if proc.poll() is not None:
                raise RuntimeError("node died during startup")
            time.sleep(0.2)
    raise RuntimeError("node not healthy in time")


def post(port, path, payload):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        json.dumps(payload).encode(),
        {"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=15) as resp:
            return resp.status, json.loads(resp.read() or b"{}")
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


@pytest.mark.slow
def test_two_node_forwarding(tmp_path):
    port_a, port_b = free_port(), free_port()
    # 'demo' pinned to node B; everything else hashes over both.
    rules = {"demo": f"127.0.0.1:{port_b}"}
    cfg_a = write_config(tmp_path, "a", port_a, port_b, tmp_path / "da", rules)
    cfg_b = write_config(tmp_path, "b", port_b, port_a, tmp_path / "db", rules)
    pa, pb = start_node(cfg_a), start_node(cfg_b)
    try:
        wait_healthy(port_a, pa)
        wait_healthy(port_b, pb)

        # DDL sent to node A forwards to owner B.
        status, out = post(port_a, "/sql", {"query": (
            "CREATE TABLE demo (h string TAG, v double NOT NULL, "
            "ts timestamp NOT NULL, TIMESTAMP KEY(ts))"
        )})
        assert status == 200 and out == {"affected_rows": 0}

        # Writes via A land on B; query via A reads them back.
        status, out = post(port_a, "/write", {"table": "demo", "rows": [
            {"h": "x", "v": 1.5, "ts": 1000}, {"h": "y", "v": 2.5, "ts": 2000},
        ]})
        assert status == 200 and out == {"affected_rows": 2}
        status, out = post(port_a, "/sql", {"query": "SELECT count(*) AS c FROM demo"})
        assert out["rows"] == [{"c": 2}]

        # The data REALLY lives on B only: B answers locally,
        # and B's debug view has the table while A's doesn't.
        status, out = post(port_b, "/sql", {"query": "SELECT max(v) AS m FROM demo"})
        assert out["rows"] == [{"m": 2.5}]
        tables_a = json.loads(
            urllib.request.urlopen(f"http://127.0.0.1:{port_a}/debug/tables", timeout=5).read()
        )
        tables_b = json.loads(
            urllib.request.urlopen(f"http://127.0.0.1:{port_b}/debug/tables", timeout=5).read()
        )
        assert "demo" not in tables_a and "demo" in tables_b

        # /route reports the owner from both nodes.
        ra = json.loads(
            urllib.request.urlopen(f"http://127.0.0.1:{port_a}/route/demo", timeout=5).read()
        )
        assert ra["routes"][0]["endpoint"] == f"127.0.0.1:{port_b}"
        assert ra["routes"][0]["is_local"] is False
    finally:
        for p in (pa, pb):
            p.send_signal(signal.SIGKILL)
            p.wait()
