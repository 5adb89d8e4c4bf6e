"""Multi-meta soak: shard procedure churn while leaders fail over
(ref model: horaemeta HA — coordinator procedures must survive leader
kills; ROADMAP r4 item 5). Two HA metas over a shared journal, two data
nodes, a split -> migrate -> kill-leader -> restart -> merge loop, with
full data-integrity and routing checks at every step."""

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

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CPU_ENV = {
    **os.environ,
    "JAX_PLATFORMS": "cpu",
    "PYTHONPATH": REPO,
}


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(method, url, payload=None, timeout=10.0):
    data = json.dumps(payload).encode() if payload is not None else None
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"},
        method=method,
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read().decode() or "{}")
    except urllib.error.HTTPError as e:
        try:
            return e.code, json.loads(e.read().decode() or "{}")
        except Exception:
            return e.code, {}
    except (urllib.error.URLError, OSError) as e:
        return 0, {"error": str(e)}


def wait_until(fn, timeout=45.0, interval=0.2, desc="condition"):
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        try:
            last = fn()
            if last:
                return last
        except Exception as e:  # noqa: BLE001
            last = e
        time.sleep(interval)
    raise TimeoutError(f"timed out waiting for {desc}: last={last}")


class MetaPool:
    """Issue meta ops against whichever meta currently leads, following
    421 leader hints and retrying across failovers."""

    def __init__(self, ports: list[int]) -> None:
        self.ports = ports

    def op(self, method: str, path: str, payload=None, timeout=60.0):
        deadline = time.monotonic() + timeout
        last = None
        while time.monotonic() < deadline:
            for port in self.ports:
                s, body = http(
                    method, f"http://127.0.0.1:{port}{path}", payload,
                    timeout=30,
                )
                if s == 200 and body.get("role") != "follower":
                    return body
                last = (port, s, body)
                # 421 -> try the hinted leader next loop; 0/5xx -> retry
            time.sleep(0.3)
        raise TimeoutError(f"meta op {path} never succeeded: {last}")

    def leader(self):
        leaders = [
            p for p in self.ports
            if http("GET", f"http://127.0.0.1:{p}/health", timeout=3)[1].get("leader")
        ]
        return leaders[0] if len(leaders) == 1 else None


@pytest.fixture()
def churn_cluster(tmp_path):
    ha_dir = str(tmp_path / "ha")
    meta_ports = [free_port(), free_port()]
    node_ports = [free_port(), free_port()]
    data_dir = str(tmp_path / "shared-store")
    procs: dict[str, subprocess.Popen] = {}

    def spawn_meta(i: int) -> subprocess.Popen:
        port = meta_ports[i]
        p = subprocess.Popen(
            [
                sys.executable, "-m", "horaedb_tpu.meta",
                "--port", str(port),
                "--ha-dir", ha_dir,
                "--advertise", f"127.0.0.1:{port}",
                "--num-shards", "4",
                "--lease-ttl", "1.5",
                "--heartbeat-timeout", "2.5",
                "--election-ttl", "2.0",
                "--tick-interval", "0.25",
            ],
            env=CPU_ENV,
            stdout=open(tmp_path / f"meta{i}-{port}.log", "ab"),
            stderr=subprocess.STDOUT,
        )
        procs[f"meta{i}"] = p
        return p

    for i in range(2):
        spawn_meta(i)
    meta_eps = ", ".join(f'"127.0.0.1:{p}"' for p in meta_ports)
    for i, port in enumerate(node_ports):
        cfg = tmp_path / f"node{i}.toml"
        cfg.write_text(
            f"""
[server]
host = "127.0.0.1"
http_port = {port}

[engine]
data_dir = "{data_dir}"

[cluster]
self_endpoint = "127.0.0.1:{port}"
meta_endpoints = [{meta_eps}]
"""
        )
        procs[f"node{i}"] = subprocess.Popen(
            [sys.executable, "-m", "horaedb_tpu.server", "--config", str(cfg)],
            env=CPU_ENV,
            stdout=open(tmp_path / f"node{i}.log", "wb"),
            stderr=subprocess.STDOUT,
        )

    for port in (*meta_ports, *node_ports):
        wait_until(
            lambda p=port: http("GET", f"http://127.0.0.1:{p}/health", timeout=2)[0] == 200,
            desc=f"{port} health",
        )
    yield meta_ports, node_ports, procs, spawn_meta
    for p in procs.values():
        if p.poll() is None:
            p.terminate()
    for p in procs.values():
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()


DDL = (
    "CREATE TABLE {name} (host string TAG, v double, "
    "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic"
)


def sql(port, query, timeout=20.0):
    return http("POST", f"http://127.0.0.1:{port}/sql", {"query": query},
                timeout=timeout)


class TestProcedureChurnUnderFailover:
    def test_split_migrate_merge_survive_leader_kills(self, churn_cluster):
        meta_ports, node_ports, procs, spawn_meta = churn_cluster
        pool = MetaPool(meta_ports)
        wait_until(pool.leader, desc="initial leader")

        names = [f"ch{i}" for i in range(6)]
        for n in names:
            pool.op("POST", "/meta/v1/table/create",
                    {"name": n, "create_sql": DDL.format(name=n)})
        for n in names:
            def write(n=n):
                s, b = sql(
                    node_ports[0],
                    f"INSERT INTO {n} (host, v, ts) VALUES "
                    + ", ".join(f"('h{j}', {j}.5, {1000 + j})" for j in range(20)),
                )
                return s == 200
            wait_until(write, desc=f"seed {n}")

        def counts_ok():
            for port in node_ports:
                for n in names:
                    s, b = sql(port, f"SELECT count(1) AS c FROM {n}")
                    if s != 200 or b.get("rows", [{}])[0].get("c") != 20:
                        return None
            return True

        wait_until(counts_ok, desc="initial data visible everywhere")

        split_sids: list[int] = []
        for cycle in range(3):
            # 1. split the fattest shard
            shards = pool.op("GET", "/meta/v1/shards")["shards"]
            src = max(shards, key=lambda s: len(s["table_ids"]))
            out = pool.op("POST", "/meta/v1/shard/split",
                          {"shard_id": src["shard_id"]})
            new_sid = out["new_shard_id"]
            split_sids.append(new_sid)

            # 2. migrate it to whichever node doesn't hold it
            view = next(
                s for s in pool.op("GET", "/meta/v1/shards")["shards"]
                if s["shard_id"] == new_sid
            )
            target = next(
                f"127.0.0.1:{p}" for p in node_ports
                if f"127.0.0.1:{p}" != view["node"]
            )
            pool.op("POST", "/meta/v1/shard/migrate",
                    {"shard_id": new_sid, "to_node": target})

            # 3. kill the leader mid-churn; follower takes over
            lp = pool.leader()
            assert lp is not None
            idx = meta_ports.index(lp)
            victim = procs[f"meta{idx}"]
            victim.kill()
            victim.wait(timeout=10)
            other = meta_ports[1 - idx]
            wait_until(
                lambda: http("GET", f"http://127.0.0.1:{other}/health",
                             timeout=3)[1].get("leader"),
                desc=f"failover cycle {cycle}",
            )

            # 4. data must still be fully readable through the churn
            wait_until(counts_ok, desc=f"data integrity cycle {cycle}")

            # 5. merge the split shard back under the NEW leader
            shards = pool.op("GET", "/meta/v1/shards")["shards"]
            assert any(s["shard_id"] == new_sid for s in shards)
            dst = max(
                (s for s in shards if s["shard_id"] != new_sid),
                key=lambda s: len(s["table_ids"]),
            )
            pool.op("POST", "/meta/v1/shard/merge",
                    {"shard_id": new_sid, "into_shard_id": dst["shard_id"]})

            # 6. restart the killed meta: rejoins as follower
            spawn_meta(idx)
            wait_until(
                lambda p=lp: http("GET", f"http://127.0.0.1:{p}/health",
                                  timeout=3)[0] == 200,
                desc=f"meta {idx} rejoin",
            )

        # Steady state: split shards retired, every table routable with
        # all its data, exactly one leader.
        shards = pool.op("GET", "/meta/v1/shards")["shards"]
        assert not any(s["shard_id"] in split_sids for s in shards)
        for n in names:
            r = pool.op("GET", f"/meta/v1/route/{n}")
            assert r["node"], r
        wait_until(counts_ok, desc="final data integrity")
        assert pool.leader() is not None
