"""The concurrency fuzz harness as a CI target (the sanitizer-analog;
ref model: the reference's ASan/MSan engine-test builds, Makefile:95-114).
Short seeded runs here; longer soaks are `python -m horaedb_tpu.tools.fuzz
--duration 60 --reopen` by hand. The disk+reopen config is the one that
caught the manifest snapshot-truncation data-loss bug (seed 2)."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_fuzz(*args: str, timeout: float = 120.0) -> dict:
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO,
    }
    p = subprocess.run(
        [sys.executable, "-m", "horaedb_tpu.tools.fuzz", *args],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
        cwd=REPO,
    )
    lines = [ln for ln in p.stdout.strip().splitlines() if ln.startswith("{")]
    assert lines, f"no JSON output; stderr tail: {p.stderr[-500:]}"
    out = json.loads(lines[-1])
    assert p.returncode == (0 if out["ok"] else 1)
    return out


class TestFuzzHarness:
    def test_memory_backend(self):
        out = run_fuzz("--seed", "11", "--duration", "3", "--threads", "4")
        assert out["ok"], out["violations"]
        assert out["ops"].get("insert", 0) > 0
        assert out["ops"].get("select", 0) > 0

    def test_disk_with_reopen_cycles(self, tmp_path):
        out = run_fuzz(
            "--seed", "2", "--duration", "4", "--threads", "4",
            "--data-dir", str(tmp_path / "fz"), "--reopen",
        )
        assert out["ok"], out["violations"]
        assert out["ops"].get("reopen", 0) >= 1

    @pytest.mark.parametrize("backend", ["object_store", "shared_log"])
    def test_alternative_wal_backends(self, tmp_path, backend):
        """Row conservation across restarts must hold on every WAL
        implementation, not just the framed local log."""
        out = run_fuzz(
            "--seed", "5", "--duration", "3", "--threads", "3",
            "--data-dir", str(tmp_path / "fz"), "--reopen",
            "--wal-backend", backend,
        )
        assert out["ok"], out["violations"]
        assert out["wal_backend"] == backend
