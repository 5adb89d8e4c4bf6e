"""sst_convert tool tests (ref: src/tools sst-convert bin)."""

import json
import os
import subprocess
import sys

import pyarrow.parquet as pq
import pytest

import horaedb_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    return env


def _run(*args):
    return subprocess.run(
        [sys.executable, "-m", "horaedb_tpu.tools.sst_convert", *args],
        capture_output=True, text=True, env=_env(), cwd=REPO,
    )


@pytest.fixture()
def data_dir(tmp_path):
    d = str(tmp_path / "db")
    db = horaedb_tpu.connect(d)
    db.execute(
        "CREATE TABLE c (host string TAG, v double, ts timestamp NOT NULL, "
        "TIMESTAMP KEY(ts)) ENGINE=Analytic"
    )
    rows = ", ".join(f"('h{i%3}', {float(i)}, {i*1000})" for i in range(300))
    db.execute(f"INSERT INTO c (host, v, ts) VALUES {rows}")
    db.catalog.open("c").flush()
    expected = db.execute(
        "SELECT host, sum(v) AS s FROM c GROUP BY host ORDER BY host"
    ).to_pylist()
    db.close()
    ssts = [
        os.path.join(root, f)
        for root, _, files in os.walk(d)
        for f in files
        if f.endswith(".sst")
    ]
    return d, ssts[0], expected


class TestSstConvert:
    def test_recompress_and_engine_reads_it(self, data_dir):
        d, sst, expected = data_dir
        r = _run(sst, "--out", sst + ".new", "--compression", "lz4",
                 "--row-group-size", "64")
        assert r.returncode == 0, r.stderr[-400:]
        out = json.loads(r.stdout)
        assert out["rows"] == 300 and out["format"] == "sst"
        os.replace(sst + ".new", sst)
        db = horaedb_tpu.connect(d)
        got = db.execute(
            "SELECT host, sum(v) AS s FROM c GROUP BY host ORDER BY host"
        ).to_pylist()
        db.close()
        assert got == expected
        # row groups actually resized
        assert pq.ParquetFile(sst).metadata.num_row_groups == -(-300 // 64)

    def test_export_plain_parquet(self, data_dir, tmp_path):
        _, sst, _ = data_dir
        out_path = str(tmp_path / "plain.parquet")
        r = _run(sst, "--out", out_path, "--export-parquet")
        assert r.returncode == 0, r.stderr[-400:]
        t = pq.read_table(out_path)
        assert t.num_rows == 300
        assert (t.schema.metadata or {}) == {}  # custom metadata stripped

    def test_legacy_sst_without_embedded_schema(self, data_dir):
        """Files from before schemas were embedded resolve via --data-dir
        (manifest lookup); without it the tool refuses loudly."""
        d, sst, _ = data_dir
        from horaedb_tpu.engine.sst.meta import SST_META_KEY

        pf = pq.ParquetFile(sst)
        kv = dict(pf.schema_arrow.metadata or {})
        payload = json.loads(kv[SST_META_KEY])
        payload.pop("schema")
        table = pq.read_table(sst)
        table = table.replace_schema_metadata(
            {SST_META_KEY: json.dumps(payload).encode()}
        )
        pq.write_table(table, sst)

        r = _run(sst, "--out", sst + ".x")
        assert r.returncode != 0 and "no embedded schema" in r.stderr

        r2 = _run(sst, "--out", sst + ".new", "--data-dir", d)
        assert r2.returncode == 0, r2.stderr[-400:]
        assert json.loads(r2.stdout)["rows"] == 300
