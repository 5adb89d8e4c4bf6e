"""The cell that PR 27 added, ``cpu-4000x12h.double-groupby-all``, at the
rehearsal's size on the CPU: a sound run is correct, the bf16 control is not,
and the two per-layer metrics that came with it read what the program counts
(and nothing, without raising, on a program that lacks the counters)."""

import json
import types

CELL = "cpu-4000x12h.double-groupby-all"


def test_sound_run_is_correct(rehearse):
    result = rehearse(CELL)
    assert result["correct"] is True and result["failed"] == 0
    assert result["compared"]["device_served_compared"]["value"] >= 1
    assert set(result["metrics"]) == {"query_p50_ms", "query_rate", "setup_s"}


def test_bf16_cache_is_not_correct(rehearse, monkeypatch):
    monkeypatch.setenv("HORAEDB_CACHE_DTYPE", "bf16")
    result = rehearse(CELL)
    assert result["correct"] is False
    assert result["compared"]["value_gap"]["ok"] is False


def test_traced_line_reports_the_build_and_no_refusal(capsys):
    import run

    rc = run.main(["--workload", CELL, "--seed", "2700000027", "--seconds", "2",
                   "--trace", "1", "--rehearse"])
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is True
    metrics = result["metrics"]
    assert metrics["cache_build_s"]["value"] > 0 and metrics["cache_build_s"]["unit"] == "s"
    assert metrics["kernel_refusals_in_window"]["value"] == 0  # 0, not absent
    assert metrics["compiles_in_window"]["value"] == 0


def test_the_new_readers_read_nothing_where_the_program_lacks_the_counter():
    """The parent of PR 27 exports neither counter: the readers return None,
    and the result line leaves the metric out."""
    import run

    evidence = types.SimpleNamespace(
        before={"metrics": {}}, after={"metrics": {"horaedb_other_total": 3.0}},
        counter=lambda key: 0.0,
    )
    for name in ("cache_build_s", "kernel_refusals_in_window"):
        spec = run.load_json("layer_metrics", name + ".json")
        reader = run.load_module("reducers", spec["reducer"])
        assert reader.read(evidence, spec["args"]) is None
    evidence.after["metrics"]["horaedb_scan_cache_build_seconds_total"] = 34.5
    spec = run.load_json("layer_metrics", "cache_build_s.json")
    assert run.load_module("reducers", spec["reducer"]).read(evidence, spec["args"]) == 34.5
