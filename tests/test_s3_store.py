"""S3 backend + disk cache tests.

The fake S3 server implements the protocol subset (GET/Range, PUT, HEAD,
DELETE, ListObjectsV2 with continuation, multipart upload) and VERIFIES
every request's Signature V4 by recomputing it with the known secret —
the tests prove the signing algorithm, not just request plumbing.
"""

from __future__ import annotations

import threading
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from horaedb_tpu.utils.object_store import DiskCacheStore, MemoryStore
from horaedb_tpu.utils.s3 import S3Store, sigv4_headers

ACCESS, SECRET, REGION, BUCKET = "AKTEST", "s3cr3t", "us-test-1", "tsdb"


class FakeS3Handler(BaseHTTPRequestHandler):
    objects: dict[str, bytes] = {}
    uploads: dict[str, dict[int, bytes]] = {}
    lock = threading.Lock()
    list_page_size = 2  # force continuation in tests

    def log_message(self, *a):  # quiet
        pass

    # ---- sigv4 verification --------------------------------------------
    def _verify_auth(self, body: bytes) -> bool:
        auth = self.headers.get("Authorization", "")
        amz_date = self.headers.get("x-amz-date", "")
        payload_sha = self.headers.get("x-amz-content-sha256", "")
        if not auth.startswith("AWS4-HMAC-SHA256"):
            return False
        # honor the client's SignedHeaders list (e.g. range on GETs)
        signed = ""
        for part in auth.split(", "):
            if part.startswith("SignedHeaders="):
                signed = part[len("SignedHeaders="):]
        extra = {
            name: self.headers.get(name, "")
            for name in signed.split(";")
            if name not in ("host", "x-amz-date", "x-amz-content-sha256")
        }
        url = f"http://{self.headers.get('host')}{self.path}"
        expected = sigv4_headers(
            self.command, url, REGION, ACCESS, SECRET, payload_sha,
            amz_date=amz_date, extra_headers=extra,
        )["Authorization"]
        return auth == expected

    def _deny(self):
        self.send_response(403)
        self.end_headers()
        self.wfile.write(b"<Error>SignatureDoesNotMatch</Error>")

    def _key(self) -> str:
        path = urllib.parse.urlsplit(self.path).path
        assert path.startswith(f"/{BUCKET}")
        return urllib.parse.unquote(path[len(BUCKET) + 2 :])

    # ---- verbs ----------------------------------------------------------
    def do_PUT(self):
        length = int(self.headers.get("Content-Length", 0))
        body = self.rfile.read(length)
        if not self._verify_auth(body):
            return self._deny()
        q = dict(urllib.parse.parse_qsl(urllib.parse.urlsplit(self.path).query))
        key = self._key()
        if "partNumber" in q:
            with self.lock:
                self.uploads.setdefault(q["uploadId"], {})[int(q["partNumber"])] = body
            self.send_response(200)
            self.send_header("ETag", f'"part-{q["partNumber"]}"')
            self.end_headers()
            return
        with self.lock:
            self.objects[key] = body
        self.send_response(200)
        self.end_headers()

    def do_GET(self):
        if not self._verify_auth(b""):
            return self._deny()
        split = urllib.parse.urlsplit(self.path)
        q = dict(urllib.parse.parse_qsl(split.query))
        if split.path == f"/{BUCKET}" and q.get("list-type") == "2":
            return self._list(q)
        key = self._key()
        with self.lock:
            data = self.objects.get(key)
        if data is None:
            self.send_response(404)
            self.end_headers()
            return
        rng = self.headers.get("Range")
        status = 200
        if rng and rng.startswith("bytes="):
            lo, _, hi = rng[len("bytes="):].partition("-")
            data = data[int(lo) : int(hi) + 1]
            status = 206
        self.send_response(status)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def _list(self, q):
        prefix = q.get("prefix", "")
        token = q.get("continuation-token")
        with self.lock:
            keys = sorted(k for k in self.objects if k.startswith(prefix))
        start = int(token) if token else 0
        page = keys[start : start + self.list_page_size]
        truncated = start + self.list_page_size < len(keys)
        contents = "".join(f"<Contents><Key>{k}</Key></Contents>" for k in page)
        nxt = (
            f"<NextContinuationToken>{start + self.list_page_size}</NextContinuationToken>"
            if truncated
            else ""
        )
        xml = (
            f"<ListBucketResult><IsTruncated>{str(truncated).lower()}</IsTruncated>"
            f"{nxt}{contents}</ListBucketResult>"
        ).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(xml)))
        self.end_headers()
        self.wfile.write(xml)

    def do_HEAD(self):
        if not self._verify_auth(b""):
            return self._deny()
        with self.lock:
            data = self.objects.get(self._key())
        if data is None:
            self.send_response(404)
        else:
            self.send_response(200)
            self.send_header("Content-Length", str(len(data)))
        self.end_headers()

    def do_DELETE(self):
        if not self._verify_auth(b""):
            return self._deny()
        with self.lock:
            self.objects.pop(self._key(), None)
        self.send_response(204)
        self.end_headers()

    def do_POST(self):
        length = int(self.headers.get("Content-Length", 0))
        body = self.rfile.read(length)
        if not self._verify_auth(body):
            return self._deny()
        q = dict(urllib.parse.parse_qsl(urllib.parse.urlsplit(self.path).query, keep_blank_values=True))
        key = self._key()
        if "uploads" in q:
            upload_id = f"up-{len(self.uploads) + 1}"
            with self.lock:
                self.uploads[upload_id] = {}
            xml = f"<InitiateMultipartUploadResult><UploadId>{upload_id}</UploadId></InitiateMultipartUploadResult>".encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(xml)))
            self.end_headers()
            self.wfile.write(xml)
            return
        if "uploadId" in q:
            with self.lock:
                parts = self.uploads.pop(q["uploadId"], {})
                self.objects[key] = b"".join(parts[i] for i in sorted(parts))
            self.send_response(200)
            self.send_header("Content-Length", "0")
            self.end_headers()
            return
        self.send_response(400)
        self.end_headers()


@pytest.fixture()
def fake_s3():
    FakeS3Handler.objects = {}
    FakeS3Handler.uploads = {}
    server = ThreadingHTTPServer(("127.0.0.1", 0), FakeS3Handler)
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{server.server_port}"
    server.shutdown()


def make_store(endpoint, **kw) -> S3Store:
    return S3Store(BUCKET, endpoint, ACCESS, SECRET, region=REGION, **kw)


class TestS3Store:
    def test_put_get_head_delete(self, fake_s3):
        s = make_store(fake_s3)
        s.put("a/b.sst", b"hello world")
        assert s.get("a/b.sst") == b"hello world"
        assert s.head("a/b.sst") == 11
        assert s.exists("a/b.sst")
        s.delete("a/b.sst")
        assert not s.exists("a/b.sst")
        with pytest.raises(FileNotFoundError):
            s.get("a/b.sst")

    def test_get_range(self, fake_s3):
        s = make_store(fake_s3)
        s.put("r", bytes(range(100)))
        assert s.get_range("r", 10, 20) == bytes(range(10, 20))

    def test_list_with_continuation(self, fake_s3):
        s = make_store(fake_s3)
        for i in range(5):
            s.put(f"t/{i}", b"x")
        assert list(s.list("t/")) == [f"t/{i}" for i in range(5)]

    def test_prefix_scoping(self, fake_s3):
        s = make_store(fake_s3, prefix="cluster1")
        s.put("x", b"1")
        assert FakeS3Handler.objects.get("cluster1/x") == b"1"
        assert list(s.list("")) == ["x"]

    def test_bad_secret_rejected(self, fake_s3):
        s = S3Store(BUCKET, fake_s3, ACCESS, "wrong", region=REGION)
        with pytest.raises(Exception):
            s.put("a", b"1")

    def test_multipart_upload(self, fake_s3):
        s = make_store(fake_s3, multipart_threshold=100, multipart_part_size=64)
        data = bytes(i % 251 for i in range(1000))
        s.put("big", data)
        assert s.get("big") == data

    def test_engine_runs_on_s3(self, fake_s3):
        from horaedb_tpu.db import Connection

        conn = Connection(make_store(fake_s3))
        conn.execute(
            "CREATE TABLE s3t (h string TAG, v double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic"
        )
        conn.execute("INSERT INTO s3t (h, v, ts) VALUES ('a', 1.5, 100), ('b', 2.5, 200)")
        conn.flush_all()
        out = conn.execute("SELECT h, v FROM s3t ORDER BY h").to_pylist()
        assert out == [{"h": "a", "v": 1.5}, {"h": "b", "v": 2.5}]
        # cold reopen straight from "cloud" storage
        conn2 = Connection(make_store(fake_s3))
        out = conn2.execute("SELECT count(*) AS c FROM s3t").to_pylist()
        assert out == [{"c": 2}]


class TestDiskCacheStore:
    def test_range_reads_cached_by_page(self, tmp_path):
        inner = MemoryStore()
        inner.put("obj", bytes(range(256)) * 16)  # 4096 bytes
        cache = DiskCacheStore(inner, str(tmp_path / "c"), page_size=1024)
        assert cache.get_range("obj", 100, 200) == (bytes(range(256)) * 16)[100:200]
        assert cache.misses == 1 and cache.hits == 0
        assert cache.get_range("obj", 0, 50) == (bytes(range(256)) * 16)[:50]
        assert cache.hits == 1  # same page
        assert cache.get_range("obj", 1000, 3000) == (bytes(range(256)) * 16)[1000:3000]

    def test_corrupt_page_refetches(self, tmp_path):
        import os

        inner = MemoryStore()
        inner.put("obj", b"A" * 2048)
        cache = DiskCacheStore(inner, str(tmp_path / "c"), page_size=1024)
        cache.get_range("obj", 0, 10)
        # corrupt the cached page on disk
        files = os.listdir(str(tmp_path / "c"))
        with open(str(tmp_path / "c" / files[0]), "r+b") as f:
            f.seek(8)
            f.write(b"\xff\xff")
        assert cache.get_range("obj", 0, 10) == b"A" * 10  # CRC miss -> refetch
        assert cache.misses == 2

    def test_eviction_under_capacity(self, tmp_path):
        inner = MemoryStore()
        inner.put("obj", b"B" * 8192)
        cache = DiskCacheStore(
            inner, str(tmp_path / "c"), page_size=1024, capacity_bytes=2100
        )
        cache.get_range("obj", 0, 8192)  # 8 pages, only ~2 fit
        assert cache._bytes <= 2100

    def test_put_invalidates(self, tmp_path):
        inner = MemoryStore()
        inner.put("obj", b"old" * 400)
        cache = DiskCacheStore(inner, str(tmp_path / "c"), page_size=256)
        assert cache.get_range("obj", 0, 3) == b"old"
        cache.put("obj", b"new" * 400)
        assert cache.get_range("obj", 0, 3) == b"new"

    def test_index_survives_restart(self, tmp_path):
        inner = MemoryStore()
        inner.put("obj", b"C" * 1024)
        cache = DiskCacheStore(inner, str(tmp_path / "c"), page_size=1024)
        cache.get_range("obj", 0, 100)
        cache2 = DiskCacheStore(inner, str(tmp_path / "c"), page_size=1024)
        assert cache2.get_range("obj", 0, 100) == b"C" * 100
        assert cache2.hits == 1 and cache2.misses == 0


class TestServerOnS3:
    def test_server_process_on_s3_with_cold_restart(self, fake_s3, tmp_path):
        """Full node on cloud storage: HTTP writes land in the fake S3,
        a fresh process serves them back (WAL + manifest + SSTs all in
        the bucket — diskless recovery)."""
        import json
        import os
        import socket
        import subprocess
        import sys
        import time
        import urllib.request

        port = _free_port()
        cfg = tmp_path / "s3node.toml"
        cfg.write_text(
            f"""
[server]
host = "127.0.0.1"
http_port = {port}

[s3]
bucket = "{BUCKET}"
endpoint = "{fake_s3}"
region = "{REGION}"
access_key = "{ACCESS}"
secret_key = "{SECRET}"
disk_cache_dir = "{tmp_path}/cache"
"""
        )
        env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        }

        def spawn():
            return subprocess.Popen(
                [sys.executable, "-m", "horaedb_tpu.server", "--config", str(cfg)],
                env=env,
                stdout=open(tmp_path / "s3node.log", "wb"),
                stderr=subprocess.STDOUT,
            )

        def sql(q):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/sql",
                data=json.dumps({"query": q}).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=30) as r:
                return json.loads(r.read().decode())

        def wait_health(deadline=60):
            end = time.monotonic() + deadline
            while time.monotonic() < end:
                try:
                    urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/health", timeout=1
                    )
                    return
                except Exception:
                    time.sleep(0.3)
            raise TimeoutError(open(tmp_path / "s3node.log").read()[-2000:])

        p = spawn()
        try:
            wait_health()
            sql(
                "CREATE TABLE cloud (h string TAG, v double, ts timestamp NOT NULL, "
                "TIMESTAMP KEY(ts)) ENGINE=Analytic"
            )
            sql("INSERT INTO cloud (h, v, ts) VALUES ('a', 1.5, 100), ('b', 2.5, 200)")
            # unflushed rows live only in the S3-backed WAL now
        finally:
            p.kill()
            p.wait(timeout=10)
        assert any(k.startswith("wal/") for k in FakeS3Handler.objects), (
            "WAL pages should be in the bucket"
        )
        p = spawn()
        try:
            wait_health()
            out = sql("SELECT h, v FROM cloud ORDER BY h")
            assert out["rows"] == [{"h": "a", "v": 1.5}, {"h": "b", "v": 2.5}]
        finally:
            p.terminate()
            p.wait(timeout=10)


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class TestRemoteConcurrentScan:
    def test_multi_sst_read_from_s3_parallel_and_correct(self, fake_s3):
        from horaedb_tpu.db import Connection
        from horaedb_tpu.engine.instance import EngineConfig

        conn = Connection(
            make_store(fake_s3), config=EngineConfig(compaction_l0_trigger=1000)
        )
        conn.execute(
            "CREATE TABLE par (h string TAG, v double, ts timestamp NOT NULL, "
            "TIMESTAMP KEY(ts)) ENGINE=Analytic WITH (update_mode='append')"
        )
        t = conn.catalog.open("par")
        # several flushes -> several SSTs in the bucket
        for run in range(4):
            conn.execute(
                "INSERT INTO par (h, v, ts) VALUES "
                + ", ".join(f"('h{i%3}', {run * 100 + i}, {1000 + i})" for i in range(50))
            )
            t.flush()
        assert len(t.physical_datas()[0].version.levels.all_files()) >= 4
        out = conn.execute("SELECT count(*) AS c, sum(v) AS s FROM par").to_pylist()
        expect_sum = float(sum(run * 100 + i for run in range(4) for i in range(50)))
        assert out == [{"c": 200, "s": expect_sum}]
