"""The cell that PR 32 added, ``cpu-4000x12h-dist4.double-groupby-all``, at the
rehearsal's size on the CPU's virtual devices: over a mesh of four a sound run
is correct and every query is the sharded program's, the bf16 control is not
correct, and the two readers that came with the cell read nothing, without
raising, on a program that lacks the gauge and the counter."""

import json
import os
import subprocess
import sys
import types

CELL = "cpu-4000x12h-dist4.double-groupby-all"
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rehearse_on_four(trace: int, seed: int, **env) -> dict:
    """The CPU rehearsal in a child process that has four devices (this one
    has imported JAX with the devices it has)."""
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(seed), "--seconds", "2", "--trace", str(trace), "--rehearse"],
        env={**os.environ, "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
             "JAX_PLATFORMS": "cpu", **env},
        capture_output=True, text=True, timeout=600, cwd=os.path.dirname(BENCH),
    )
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct_and_served_by_the_mesh():
    result = rehearse_on_four(trace=1, seed=3_200_000_032)
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["count"] == 4
    assert result["compared"]["device_served_compared"]["value"] >= 1
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics["mesh_route_share"] == 100.0
    assert metrics["device_route_share"] == 100.0
    assert metrics["dispatches_per_query"] == 1.0
    assert metrics["shard_row_skew"] == 1.0  # 276,480 rows: 69,120 a device
    # 64 hosts x 12 h in 64 x 16 segments: int32 counts and ten f32 sums
    assert metrics["combine_bytes_per_query"] == 4 * 64 * 16 * 11
    assert metrics["compiles_in_window"] == 0
    assert metrics["kernel_refusals_in_window"] == 0


def test_end_to_end_line_reports_what_the_cell_lists():
    result = rehearse_on_four(trace=0, seed=3_200_000_033)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"query_p50_ms", "query_rate", "setup_s"}
    assert set(result["routes"]) == {"device-dist/scatter"}


def test_bf16_cache_is_not_correct():
    result = rehearse_on_four(trace=0, seed=3_200_000_034, HORAEDB_CACHE_DTYPE="bf16")
    assert result["correct"] is False
    assert result["compared"]["value_gap"]["ok"] is False


def test_the_new_readers_read_nothing_where_the_program_lacks_what_they_read():
    """The parent of PR 32 exports neither the gauge nor the counter: the
    readers return None, and the result line leaves the metrics out."""
    import run

    metrics = {'horaedb_query_route_total{route="device-dist"}': 9.0}
    evidence = types.SimpleNamespace(
        before={"metrics": {}}, after={"metrics": metrics},
        counter=lambda key: metrics.get(key, 0.0),
        counters=lambda prefix: {k: v for k, v in metrics.items() if k.startswith(prefix)},
    )
    readers = {}
    for name in ("shard_row_skew", "combine_bytes_per_query"):
        spec = run.load_json("layer_metrics", name + ".json")
        readers[name] = (run.load_module("reducers", spec["reducer"]), spec["args"])
        assert readers[name][0].read(evidence, spec["args"]) is None
    for shard, rows in enumerate((8_388_608, 8_388_608, 502_784, 0)):  # the parent's lie
        metrics[f'horaedb_scan_cache_shard_rows{{shard="{shard}",table="cpu"}}'] = rows
    metrics["horaedb_dist_combine_bytes_total"] = 9 * 2_883_584.0
    reader, args = readers["shard_row_skew"]
    assert round(reader.read(evidence, args), 2) == 1.94
    reader, args = readers["combine_bytes_per_query"]
    assert reader.read(evidence, args) == 2_883_584.0
