"""Benchmark driver. Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": "rows/s", "vs_baseline": R}

Configs (select with BENCH_CONFIG, default "readme") — the BASELINE.md
target list:

    readme              SELECT avg(value) GROUP BY name, 1M rows
    tsbs-1-1-1          single-groupby-1-1-1, scale 100
    tsbs-5-8-1          single-groupby-5-8-1, scale 4000 (headline)
    double-groupby-all  10 metrics, group by (host, hour), scale 4000, 24h
    high-cpu-all        usage_user > 90 pushdown, scale 4000, 12h
    compaction-64       BASELINE config 5: 64 overlapping L0 SSTs through
                        Compactor._device_merge vs the numpy host merge
    groupby             learned kernel-router A/B: cardinality sweep
                        8 -> 256k + skew shapes, router vs the static
                        _MXU_MAX_SEGMENTS policy (mxu/scatter/hash)
    rawscan             device raw-read A/B: fused filter + top-k /
                        bounded selection over the HBM scan cache vs the
                        host-only path, selectivity 0.001 -> 1.0 x
                        LIMIT 10 -> 10k (ORDER BY ts DESC dashboards)
    follower            replicated follower reads: 1 meta + 3 data nodes
                        (real processes, shared store, --read-replicas 2),
                        hot-table read storm round-robin across all nodes
                        (followers serve route=follower locally) vs the
                        same storm pinned to the shard leader; gates on
                        result agreement + followers actually serving +
                        never-worse on the leader-only open-tail shape
    flood               multi-query fused serving A/B: 100s of concurrent
                        shape-identical dashboard aggregates (literals
                        varied per query) through the proxy with cohort
                        batching ([wlm.batch]) vs per-query dispatch;
                        gates on dispatches-per-query reduction (>=4x
                        once cohorts reach 8), emits p50/p99 both arms
    devicetel           device-telemetry overhead gate: the groupby and
                        rawscan serving shapes with the device plane ON
                        (default 1-in-8 sampled block_until_ready
                        timing) vs HORAEDB_DEVICE_TELEMETRY=0,
                        interleaved min-of-N; gate: overhead <= 2%
    rollup              continuous-query A/B: dashboard range aggregate
                        (time_bucket 5m x host x avg) served from the
                        maintained 1m rollup (route=rollup) vs the same
                        query forced onto the raw table
                        (HORAEDB_ROLLUP=0), interleaved min-of-N; also
                        times the PromQL range-query face of the same
                        rewrite
    decisions           decision-plane overhead gate: the flood shape
                        with the decision journal ON (kernel-router +
                        admission record/resolve per query) vs
                        HORAEDB_DECISIONS=0, interleaved min-of-N;
                        gate: on within 2% of off
    profile             profile-plane overhead gate: the flood shape
                        with the span-tree fold ON (every finish_trace
                        folds into the streaming aggregator) vs
                        HORAEDB_PROFILE=0, interleaved min-of-N;
                        gate: on within 2% of off
    livewindow          steady-state dashboard-refresh latency under
                        concurrent ingest: the open-tail (time_bucket
                        1m x host) panel served from device ring state
                        (route=livewindow) vs the same query forced
                        raw (HORAEDB_LIVEWINDOW=0); equivalence
                        checked with ingest quiesced; also times the
                        PromQL increase() face (write-time folded
                        counter partials vs the raw chain fold)

An all-configs run (no BENCH_CONFIG) honours BENCH_WALL_BUDGET seconds:
stages that no longer fit are skipped with an explicit emitted line and
listed in the final record's ``stages_skipped`` (always present, [] when
everything ran).

Every config runs the FULL query path (SQL -> plan -> merge read -> fused
device kernel) against data ingested through the real engine (memtable ->
flush -> Parquet SSTs). ``value`` is scanned-rows/sec of the steady-state
device-path query; ``vs_baseline`` is the speedup over the same query
forced onto the host (vectorized numpy) executor — the framework's own
CPU path, standing in for the reference's DataFusion executor.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import time

import numpy as np

REPEATS = 5


def _connect_mem():
    import horaedb_tpu

    return horaedb_tpu.connect(None)


def build_readme():
    from horaedb_tpu.common_types import ColumnSchema, DatumKind, RowGroup, Schema
    from horaedb_tpu.common_types.schema import compute_tsid

    db = _connect_mem()
    db.execute(
        "CREATE TABLE demo (name string TAG, value double, t timestamp KEY) "
        "ENGINE=Analytic WITH (segment_duration='2h')"
    )
    n = 1_000_000
    rng = np.random.default_rng(123)
    names = np.array([f"host_{i}" for i in rng.integers(0, 100, n)], dtype=object)
    schema = db.catalog.open("demo").schema
    rows = RowGroup(
        schema,
        {
            "tsid": compute_tsid([names]),
            "t": rng.integers(0, 3_600_000, n).astype(np.int64),
            "name": names,
            "value": rng.normal(10.0, 3.0, n),
        },
    )
    t = db.catalog.open("demo")
    t.write(rows)
    t.flush()

    def arrow_fn(dset):
        import pyarrow.compute as pc  # noqa: F401

        t = dset.to_table(columns=["name", "value"])
        out = t.group_by("name").aggregate([("value", "mean")])
        return [
            {"name": n_, "a": a}
            for n_, a in zip(
                out["name"].to_pylist(), out["value_mean"].to_pylist()
            )
        ]

    return db, "SELECT name, avg(value) AS a FROM demo GROUP BY name", n, arrow_fn


def _bucket(col, width_ms: int):
    import pyarrow as pa
    import pyarrow.compute as pc

    # SSTs store the key as timestamp[ms]; bucket in int64 ms space
    # (integer divide truncates: floor(ts / w) * w).
    as_ms = pc.cast(col, pa.int64())
    return pc.multiply(pc.divide(as_ms, width_ms), width_ms)


def _ts_literal(ms: int):
    import pyarrow as pa

    return pa.scalar(ms, type=pa.timestamp("ms"))


def _build_tsbs(scale, hours, query, arrow_fn):
    from horaedb_tpu.tools import tsbs

    db = _connect_mem()
    db.execute(
        "CREATE TABLE cpu (hostname string TAG, region string TAG, "
        "datacenter string TAG, "
        + ", ".join(f"{f} double" for f in tsbs.CPU_FIELDS)
        + ", ts timestamp NOT NULL, TIMESTAMP KEY(ts)) "
        "ENGINE=Analytic WITH (segment_duration='2h')"
    )
    rows = tsbs.generate_cpu(scale, hours * 3_600_000)
    t = db.catalog.open("cpu")
    t.write(rows)
    t.flush()
    return db, query.sql, len(rows), arrow_fn


def build_tsbs_111():
    return _build_tsbs(100, 1, _sg(1, 1, 1), _sg_arrow(1, 1, 1))


def build_tsbs_581():
    return _build_tsbs(4000, 1, _sg(5, 8, 1), _sg_arrow(5, 8, 1))


def _sg(m, h, hr):
    from horaedb_tpu.tools.tsbs import single_groupby

    return single_groupby(m, h, hr)


def _sg_arrow(m, h, hr):
    """single-groupby-{m}-{h}-{hr} as a pyarrow Acero pipeline."""

    def arrow_fn(dset):
        import pyarrow.compute as pc
        from horaedb_tpu.tools.tsbs import CPU_FIELDS

        fields = list(CPU_FIELDS[:m])
        hosts = [f"host_{i}" for i in range(h)]
        end = hr * 3_600_000
        t = dset.to_table(
            columns=["hostname", "ts"] + fields,
            filter=(
                pc.field("hostname").isin(hosts)
                & (pc.field("ts") >= _ts_literal(0))
                & (pc.field("ts") < _ts_literal(end))
            ),
        )
        t = t.append_column("minute", _bucket(t["ts"], 60_000))
        out = t.group_by("minute").aggregate([(f, "max") for f in fields])
        rows = []
        for i in range(len(out)):
            r = {"minute": out["minute"][i].as_py()}
            for f in fields:
                r[f"max_{f}"] = out[f"{f}_max"][i].as_py()
            rows.append(r)
        return rows

    return arrow_fn


# BASELINE.md configs 3/4 blueprint scale: 4000 hosts, 24h/12h spans.
# Overridable for quick runs (BENCH_SCALE=400 BENCH_DG_HOURS=12
# reproduces the r4 shapes); the committed default IS the blueprint
# (VERDICT r4 item 4).
TSBS_SCALE = int(os.environ.get("BENCH_SCALE", "4000"))
DG_HOURS = int(os.environ.get("BENCH_DG_HOURS", "24"))
HC_HOURS = int(os.environ.get("BENCH_HC_HOURS", "12"))


def build_double_groupby():
    from horaedb_tpu.tools.tsbs import CPU_FIELDS, double_groupby_all

    def arrow_fn(dset):
        import pyarrow.compute as pc

        end = DG_HOURS * 3_600_000
        t = dset.to_table(
            columns=["hostname", "ts"] + list(CPU_FIELDS),
            filter=(pc.field("ts") >= _ts_literal(0))
            & (pc.field("ts") < _ts_literal(end)),
        )
        t = t.append_column("hour", _bucket(t["ts"], 3_600_000))
        out = t.group_by(["hostname", "hour"]).aggregate(
            [(f, "mean") for f in CPU_FIELDS]
        )
        rows = []
        for i in range(len(out)):
            r = {
                "hostname": out["hostname"][i].as_py(),
                "hour": out["hour"][i].as_py(),
            }
            for f in CPU_FIELDS:
                r[f"avg_{f}"] = out[f"{f}_mean"][i].as_py()
            rows.append(r)
        return rows

    return _build_tsbs(TSBS_SCALE, DG_HOURS, double_groupby_all(DG_HOURS), arrow_fn)


def build_high_cpu():
    from horaedb_tpu.tools.tsbs import high_cpu_all

    def arrow_fn(dset):
        import pyarrow.compute as pc

        end = HC_HOURS * 3_600_000
        t = dset.to_table(
            columns=["usage_user"],
            filter=(
                (pc.field("usage_user") > 90)
                & (pc.field("ts") >= _ts_literal(0))
                & (pc.field("ts") < _ts_literal(end))
            ),
        )
        return [{
            "c": len(t),
            "peak": pc.max(t["usage_user"]).as_py(),
        }]

    return _build_tsbs(TSBS_SCALE, HC_HOURS, high_cpu_all(HC_HOURS), arrow_fn)


CONFIGS = {
    "readme": build_readme,
    "tsbs-1-1-1": build_tsbs_111,
    "tsbs-5-8-1": build_tsbs_581,
    "double-groupby-all": build_double_groupby,
    "high-cpu-all": build_high_cpu,
}

# ---- compaction config (BASELINE config 5) -----------------------------
#
# 64 overlapping L0 SSTs through Compactor._device_merge vs the same merge
# forced onto a vectorized-numpy host path. SSTs are written directly via
# SstWriter (the flush discipline, flush.py:95-120) so the build phase
# measures SST production, not the WAL/memtable write path.

# BASELINE config 5 blueprint shape IS the default: 64 SSTs / 100M rows
# (the table builds TWICE for the device/host A-B; ~10 min wall on this
# 1-core host, inside PER_CONFIG_TIMEOUT). BENCH_COMPACTION_ROWS=32000000
# reproduces the r4 quick shape.
COMPACTION_SSTS = int(os.environ.get("BENCH_COMPACTION_SSTS", "64"))
COMPACTION_ROWS = int(os.environ.get("BENCH_COMPACTION_ROWS", "100000000"))


def _build_compaction_db(seed: int):
    """One table with COMPACTION_SSTS overlapping L0 runs in one window."""
    from horaedb_tpu.common_types import RowGroup
    from horaedb_tpu.common_types.schema import compute_tsid
    from horaedb_tpu.engine.manifest import AddFile, Flushed
    from horaedb_tpu.engine.sst.manager import FileHandle
    from horaedb_tpu.engine.sst.writer import SstWriter, WriteOptions

    db = _connect_mem()
    db.execute(
        "CREATE TABLE demo (name string TAG, value double, t timestamp KEY) "
        "ENGINE=Analytic WITH (segment_duration='2h')"
    )
    table = db.catalog.open("demo").physical_datas()[0]
    seg_ms = table.options.segment_duration_ms
    n_per = COMPACTION_ROWS // COMPACTION_SSTS
    n_series = 1000
    rng = np.random.default_rng(seed)
    writer = SstWriter(
        table.store,
        WriteOptions(
            num_rows_per_row_group=table.options.num_rows_per_row_group,
            compression=table.options.compression,
        ),
    )
    # All runs overlap: same key space (series x one segment window), ts
    # drawn from a pool sized so ~1/3 of keys collide across runs — the
    # dedup work the merge must do.
    names_pool = np.array([f"host_{i}" for i in range(n_series)], dtype=object)
    tsid_pool = compute_tsid([names_pool])
    ts_space = max(1, (COMPACTION_ROWS // n_series) * 3 // 4)
    ts_step = max(1, seg_ms // ts_space)
    edits = []
    for i in range(COMPACTION_SSTS):
        sidx = rng.integers(0, n_series, n_per)
        rows = RowGroup(
            table.schema,
            {
                "tsid": tsid_pool[sidx],
                "t": ((rng.integers(0, ts_space, n_per) * ts_step) % seg_ms
                      ).astype(np.int64),
                "name": names_pool[sidx],
                "value": rng.normal(10.0, 3.0, n_per),
            },
        ).sorted_by_key()
        fid = table.alloc_file_id()
        path = table.sst_object_path(fid)
        meta = writer.write(path, fid, rows, max_sequence=i + 1)
        edits.append(AddFile(0, meta, path))
        table.version.levels.add_file(0, FileHandle(meta, path, 0))
    edits.append(Flushed(COMPACTION_SSTS))
    table.manifest.append_edits(edits)
    table.version.flushed_sequence = COMPACTION_SSTS
    return db, table


# ---- ingest config (pipelined background flush vs seed baseline) ------
#
# N concurrent writers against ONE table with a small memtable budget (so
# flushes happen DURING the write storm) and a latency-injected object
# store (every SST put pays a synthetic upload delay — the remote-store
# shape the pipelined flush exists for). Timestamps spread across several
# segment buckets so one flush writes several SSTs: the background path
# writes them concurrently on the io pool while writers keep committing.
#
# The baseline pass emulates the PRE-pipeline seed behavior this PR
# replaced: flush inline on the write leader, ``serial_lock`` held across
# the ENTIRE dump (so every writer blocks for the full upload), and one
# bucket uploaded at a time. ``vs_baseline`` is baseline_wall /
# background_wall; p99 commit latency is reported for both so the "a
# commit no longer includes the SST upload" claim is visible in the
# record. The stall bound is raised to match the artificially tiny
# memtable budget (the default count bound assumes 32mb memtables, not
# 1mb) so the background pass measures the pipeline, not the stall.

INGEST_WRITERS = int(os.environ.get("BENCH_INGEST_WRITERS", "4"))
INGEST_BATCHES = int(os.environ.get("BENCH_INGEST_BATCHES", "40"))
INGEST_BATCH_ROWS = int(os.environ.get("BENCH_INGEST_BATCH_ROWS", "5000"))
INGEST_PUT_DELAY_S = float(os.environ.get("BENCH_INGEST_PUT_DELAY", "0.02"))
INGEST_BUCKETS = 8


def _latency_sst_store(inner, delay_s: float):
    """A per-put delay on SST objects only (manifest/WAL appends stay
    fast — the point is the upload cost). The ad-hoc wrapper this bench
    once carried is now the shared utils/object_store.FaultInjectingStore
    (same layer tools/tenantsim uses)."""
    from horaedb_tpu.utils.object_store import FaultInjectingStore

    return FaultInjectingStore(inner, put_latency_s=delay_s, suffix=".sst")


@contextlib.contextmanager
def _seed_flush_semantics():
    """Emulate the pre-pipeline flush this PR replaced, for the baseline
    pass: ``serial_lock`` held across the ENTIRE dump (every writer
    blocks for the full upload) and one bucket uploaded at a time (the
    thread rename steers flush.py onto its serial bucket path — the
    same guard that keeps a flush running ON the io pool from
    deadlocking against its own slots)."""
    import threading

    from horaedb_tpu.engine.flush import Flusher

    orig = Flusher.flush

    def seed_flush(self):
        th = threading.current_thread()
        saved = th.name
        th.name = "sst-io-seed-baseline"
        try:
            with self.table.serial_lock:
                return orig(self)
        finally:
            th.name = saved

    Flusher.flush = seed_flush
    try:
        yield
    finally:
        Flusher.flush = orig


def _run_ingest_pass(background: bool) -> tuple[float, float, int]:
    """(wall_seconds, p99_commit_ms, rows_written) for one full pass."""
    import threading

    from horaedb_tpu.common_types import ColumnSchema, DatumKind, RowGroup, Schema
    from horaedb_tpu.common_types.schema import compute_tsid
    from horaedb_tpu.engine.instance import EngineConfig, Instance
    from horaedb_tpu.engine.options import TableOptions
    from horaedb_tpu.utils.object_store import MemoryStore

    schema = Schema.build(
        [
            ColumnSchema("name", DatumKind.STRING, is_tag=True),
            ColumnSchema("value", DatumKind.DOUBLE),
            ColumnSchema("t", DatumKind.TIMESTAMP),
        ],
        timestamp_column="t",
    )
    inst = Instance(
        _latency_sst_store(MemoryStore(), INGEST_PUT_DELAY_S),
        EngineConfig(
            background_flush=background,
            compaction_l0_trigger=10**9,  # isolate flush behavior
            compaction_interval_s=0,
            # The 1mb bench memtable is ~1/32 the default; scale the
            # frozen-count bound accordingly so backpressure measures the
            # pipeline, not the deliberately tiny buffer.
            write_stall_immutable_count=64,
        ),
    )
    table = inst.create_table(
        0, 1, "ingest", schema,
        TableOptions.from_kv(
            {"segment_duration": "1h", "write_buffer_size": "1mb"}
        ),
    )
    span_ms = INGEST_BUCKETS * 3_600_000
    rng = np.random.default_rng(7)
    names = np.array([f"host_{i}" for i in range(100)], dtype=object)

    def make_batch(seed: int) -> RowGroup:
        r = np.random.default_rng(seed)
        idx = r.integers(0, len(names), INGEST_BATCH_ROWS)
        tags = names[idx]
        return RowGroup(
            schema,
            {
                "tsid": compute_tsid([tags]),
                "t": r.integers(0, span_ms, INGEST_BATCH_ROWS).astype(np.int64),
                "name": tags,
                "value": r.normal(10.0, 3.0, INGEST_BATCH_ROWS),
            },
        )

    batches = [
        [make_batch(w * INGEST_BATCHES + b) for b in range(INGEST_BATCHES)]
        for w in range(INGEST_WRITERS)
    ]
    latencies: list[list[float]] = [[] for _ in range(INGEST_WRITERS)]
    errors: list = []

    def writer(w: int) -> None:
        try:
            for rows in batches[w]:
                s = time.perf_counter()
                inst.write(table, rows)
                latencies[w].append(time.perf_counter() - s)
        except Exception as e:  # a shed/stall surfacing here fails the run
            errors.append(e)

    threads = [
        threading.Thread(target=writer, args=(w,)) for w in range(INGEST_WRITERS)
    ]
    ctx = (
        contextlib.nullcontext() if background else _seed_flush_semantics()
    )
    with ctx:
        s = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        inst.flush_table(table)  # drain: both passes end fully durable
        wall = time.perf_counter() - s
    inst.close()
    if errors:
        raise errors[0]
    all_lat = np.concatenate([np.asarray(l) for l in latencies])
    rows_written = INGEST_WRITERS * INGEST_BATCHES * INGEST_BATCH_ROWS
    return wall, float(np.percentile(all_lat, 99) * 1000), rows_written


def run_ingest_config() -> dict:
    """Write-path A/B: pipelined background flush vs the seed baseline
    (inline flush, serial_lock across the dump, serial bucket uploads),
    same data, same latency-injected store. Pure host path (no kernels),
    so no TPU/CPU labeling applies."""
    config = "ingest"
    base_s, base_p99_ms, n = _run_ingest_pass(background=False)
    bg_s, bg_p99_ms, _ = _run_ingest_pass(background=True)
    return {
        "metric": f"{config}-{INGEST_WRITERS}w_rows_per_sec_background-flush",
        "value": round(n / bg_s),
        "unit": "rows/s",
        "vs_baseline": round(base_s / bg_s, 3),
        "p99_commit_ms": round(bg_p99_ms, 1),
        "p99_commit_ms_baseline": round(base_p99_ms, 1),
        "baseline_rows_per_sec": round(n / base_s),
        "baseline": "seed-inline-flush-locked-dump",
        "sst_put_delay_ms": round(INGEST_PUT_DELAY_S * 1000, 1),
        "platform": "host",
    }


# ---- selfscrape config (self-monitoring recorder overhead) -------------
#
# The acceptance gate for the self-monitoring pipeline (engine/
# metrics_recorder): ingest throughput with the recorder scraping the
# node's own registry into system_metrics.samples THROUGH THE SAME WRITE
# PATH, vs the identical workload with the recorder off. The recorder is
# deliberately over-driven (SELFSCRAPE_INTERVAL_S far below the 10s
# production default) so the measured overhead is an upper bound.
SELFSCRAPE_WRITERS = int(os.environ.get("BENCH_SELFSCRAPE_WRITERS", "2"))
SELFSCRAPE_BATCHES = int(os.environ.get("BENCH_SELFSCRAPE_BATCHES", "40"))
SELFSCRAPE_BATCH_ROWS = int(
    os.environ.get("BENCH_SELFSCRAPE_BATCH_ROWS", "2000")
)
# Each writer cycles its prebuilt batches REPEAT times so one pass spans
# many scrape intervals (0 rounds would measure nothing).
SELFSCRAPE_REPEAT = int(os.environ.get("BENCH_SELFSCRAPE_REPEAT", "40"))
SELFSCRAPE_INTERVAL_S = float(
    os.environ.get("BENCH_SELFSCRAPE_INTERVAL_S", "0.1")
)
SELFSCRAPE_REPEATS = int(os.environ.get("BENCH_SELFSCRAPE_REPEATS", "7"))


def _run_selfscrape_pass(with_recorder: bool) -> tuple[float, int, int]:
    """(wall_seconds, rows_written, scrape_rounds) for one full pass."""
    import threading

    from horaedb_tpu.common_types import RowGroup
    from horaedb_tpu.common_types.schema import compute_tsid
    from horaedb_tpu.engine.metrics_recorder import MetricsRecorder

    db = _connect_mem()
    db.execute(
        "CREATE TABLE scrape_load (name string TAG, value double, "
        "t timestamp KEY) ENGINE=Analytic "
        "WITH (segment_duration='1h', write_buffer_size='4mb')"
    )
    table = db.catalog.open("scrape_load")
    schema = table.schema
    names = np.array([f"host_{i}" for i in range(100)], dtype=object)

    def make_batch(seed: int) -> RowGroup:
        r = np.random.default_rng(seed)
        tags = names[r.integers(0, len(names), SELFSCRAPE_BATCH_ROWS)]
        return RowGroup(
            schema,
            {
                "tsid": compute_tsid([tags]),
                "t": r.integers(0, 3_600_000, SELFSCRAPE_BATCH_ROWS).astype(
                    np.int64
                ),
                "name": tags,
                "value": r.normal(10.0, 3.0, SELFSCRAPE_BATCH_ROWS),
            },
        )

    batches = [
        [make_batch(w * SELFSCRAPE_BATCHES + b) for b in range(SELFSCRAPE_BATCHES)]
        for w in range(SELFSCRAPE_WRITERS)
    ]
    errors: list = []

    def writer(w: int) -> None:
        try:
            for _ in range(SELFSCRAPE_REPEAT):
                for rows in batches[w]:
                    table.write(rows)
        except Exception as e:
            errors.append(e)

    recorder = None
    if with_recorder:
        recorder = MetricsRecorder(
            db, interval_s=SELFSCRAPE_INTERVAL_S, retention_s=24 * 3600.0,
            node="bench",
        ).start()
    threads = [
        threading.Thread(target=writer, args=(w,))
        for w in range(SELFSCRAPE_WRITERS)
    ]
    s = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - s
    rounds = 0
    if recorder is not None:
        rounds = recorder.rounds
        recorder.close()
    db.close()
    if errors:
        raise errors[0]
    rows = (
        SELFSCRAPE_WRITERS * SELFSCRAPE_BATCHES * SELFSCRAPE_BATCH_ROWS
        * SELFSCRAPE_REPEAT
    )
    return wall, rows, rounds


def run_selfscrape_config() -> dict:
    """Self-monitoring overhead A/B: same ingest workload with the
    recorder off (baseline) then on; `value` is recorder-on throughput
    and `overhead_pct` the throughput cost — the acceptance bound is
    <3%. Pure host path (no kernels), so no TPU/CPU labeling applies."""
    _run_selfscrape_pass(with_recorder=False)  # warmup (JIT/numpy import)
    # Interleaved min-of-N pairs: the shared 1-core hosts are noisy
    # enough (20%+ between identical passes) that a single A/B would
    # measure the neighbors, not the recorder. Min wall per arm is the
    # noise-robust estimator of the true cost.
    off_walls, on_walls, rounds, n = [], [], 0, 0
    for _ in range(SELFSCRAPE_REPEATS):
        off_s, n, _ = _run_selfscrape_pass(with_recorder=False)
        on_s, _, r = _run_selfscrape_pass(with_recorder=True)
        off_walls.append(off_s)
        on_walls.append(on_s)
        rounds += r
    off_s, on_s = min(off_walls), min(on_walls)
    overhead_pct = max(0.0, (on_s - off_s) / off_s * 100.0)
    return {
        "metric": f"selfscrape-{SELFSCRAPE_WRITERS}w_rows_per_sec_recorder-on",
        "value": round(n / on_s),
        "unit": "rows/s",
        "vs_baseline": round(off_s / on_s, 3),
        "baseline_rows_per_sec": round(n / off_s),
        "overhead_pct": round(overhead_pct, 2),
        "scrape_rounds": rounds,
        "scrape_interval_s": SELFSCRAPE_INTERVAL_S,
        "platform": "host",
    }


# ---- devicetel config (device telemetry overhead gate) ----------------
#
# ISSUE-15 acceptance: the device telemetry plane ON (default sampling)
# must stay within 2% of telemetry-off on the groupby- and rawscan-shaped
# serving paths. Interleaved min-of-N pairs on one process (flip
# HORAEDB_DEVICE_TELEMETRY between arms — every knob is read per
# dispatch), so host noise cancels and the jit caches are shared.
DEVICETEL_REPEATS = int(os.environ.get("BENCH_DEVICETEL_REPEATS", "7"))
DEVICETEL_RUNS_PER_ARM = int(os.environ.get("BENCH_DEVICETEL_RUNS", "3"))


def run_devicetel_config() -> dict:
    import jax

    platform = jax.devices()[0].platform
    db, agg_sql, n_rows, _ = build_readme()
    raw_sql = (
        "SELECT name, value, t FROM demo WHERE value > 16.0 "
        "ORDER BY t DESC LIMIT 100"
    )
    queries = {"groupby": agg_sql, "rawscan": raw_sql}

    def run_arm(sql: str) -> float:
        best = np.inf
        for _ in range(DEVICETEL_RUNS_PER_ARM):
            s = time.perf_counter()
            db.execute(sql)
            best = min(best, time.perf_counter() - s)
        return best

    prior = os.environ.get("HORAEDB_DEVICE_TELEMETRY")
    try:
        # warm both shapes fully (scan-cache candidate -> build -> hit,
        # jit compiles) with telemetry ON so neither arm pays one-offs
        os.environ["HORAEDB_DEVICE_TELEMETRY"] = "1"
        for sql in queries.values():
            for _ in range(4):
                db.execute(sql)
        off = {k: np.inf for k in queries}
        on = {k: np.inf for k in queries}
        for _ in range(DEVICETEL_REPEATS):
            os.environ["HORAEDB_DEVICE_TELEMETRY"] = "0"
            for k, sql in queries.items():
                off[k] = min(off[k], run_arm(sql))
            os.environ["HORAEDB_DEVICE_TELEMETRY"] = "1"
            for k, sql in queries.items():
                on[k] = min(on[k], run_arm(sql))
    finally:
        # restore the caller's setting, not the default (an operator
        # running the whole config list with telemetry pinned off must
        # not have later configs silently measured with it back on)
        if prior is None:
            os.environ.pop("HORAEDB_DEVICE_TELEMETRY", None)
        else:
            os.environ["HORAEDB_DEVICE_TELEMETRY"] = prior
        db.close()
    overhead = {
        k: max(0.0, (on[k] - off[k]) / off[k] * 100.0) for k in queries
    }
    worst = max(overhead, key=overhead.get)
    suffix = "" if platform == "tpu" else "_CPU-FALLBACK"
    return {
        "metric": f"devicetel_overhead_pct{suffix}",
        "value": round(overhead[worst], 2),
        "unit": "%",
        "vs_baseline": round(
            min(off[k] / on[k] for k in queries), 3
        ),
        "within_2pct": all(v <= 2.0 for v in overhead.values()),
        "overhead_pct": {k: round(v, 2) for k, v in overhead.items()},
        "on_ms": {k: round(on[k] * 1000, 3) for k in queries},
        "off_ms": {k: round(off[k] * 1000, 3) for k in queries},
        "platform": platform,
    }


# ---- groupby config (learned aggregation-kernel routing A/B) -----------
#
# The acceptance gate for the kernel router (query/path_router.
# KernelRouter): sweep group cardinality 8 -> 256k plus heavy-hitter
# skew shapes through the REAL dispatch path (build_padded_batch ->
# ScanAggSpec -> scan_aggregate, jit cache keys and all), comparing the
# static `_MXU_MAX_SEGMENTS` policy (segment_impl="auto", what the seed
# shipped) against the learned router warmed the same way production
# warms it (probe each candidate, drop the compile-tainted sample,
# serve the measured winner). The router must match or beat static at
# EVERY swept shape and the hash kernel must win at least one
# low-cardinality/skewed shape — the 2411.13245 win region.
GROUPBY_ROWS = int(os.environ.get("BENCH_GROUPBY_ROWS", str(1 << 18)))
GROUPBY_REPEATS = int(os.environ.get("BENCH_GROUPBY_REPEATS", "3"))

# (label, domain cardinality, live groups actually present)
GROUPBY_SHAPES = (
    ("uniform-8", 8, 8),
    ("uniform-64", 64, 64),
    ("uniform-512", 512, 512),
    ("uniform-4k", 4096, 4096),
    ("uniform-32k", 32768, 32768),
    ("uniform-256k", 262144, 262144),
    ("skew-64k-live4", 65536, 4),
    ("skew-256k-live16", 262144, 16),
)


def run_groupby_config() -> dict:
    import dataclasses

    import jax

    from horaedb_tpu.ops.encoding import build_padded_batch
    from horaedb_tpu.ops.hash_agg import hash_slots_for
    from horaedb_tpu.ops.scan_agg import (
        ScanAggSpec,
        resolve_segment_impl,
        scan_aggregate,
    )
    from horaedb_tpu.query.path_router import (
        KernelRouter,
        candidate_kernels,
        seed_kernel,
    )

    platform = jax.devices()[0].platform
    backend = jax.default_backend()
    rng = np.random.default_rng(7)
    n = GROUPBY_ROWS

    def dispatch(batch, spec):
        t0 = time.perf_counter()
        state = scan_aggregate(batch, spec, [])
        return time.perf_counter() - t0, state

    def timed(batch, spec):
        best = None
        for _ in range(GROUPBY_REPEATS):
            s, state = dispatch(batch, spec)
            best = s if best is None else min(best, s)
        return best, state

    sweep = []
    total_static = total_routed = 0.0
    for label, domain, live in GROUPBY_SHAPES:
        if live < domain:
            # heavy-hitter skew: the rows present touch `live` groups
            # scattered across a `domain`-wide dense encoding (the shape
            # a selective dashboard filter produces)
            groups = np.sort(rng.choice(domain, size=live, replace=False))
            codes = groups[rng.integers(0, live, n)].astype(np.int32)
        else:
            codes = rng.integers(0, domain, n).astype(np.int32)
        vals = rng.normal(size=n).astype(np.float32)
        batch = build_padded_batch(
            codes, np.zeros(n, np.int32), np.ones(n, bool), [vals]
        )
        spec = ScanAggSpec(
            n_groups=domain, n_buckets=1, n_agg_fields=1,
        ).padded()

        # Arm A: the seed's static policy (import-time threshold).
        static_impl = resolve_segment_impl(domain, "auto")
        static_s, static_state = timed(batch, spec)

        # Arm B: the learned router, warmed exactly like production —
        # seeded from the cardinality estimate, each candidate probed
        # (first sample compile-tainted and dropped), winner served.
        router = KernelRouter()
        key = (label, domain)
        cands = candidate_kernels(domain, n, live)
        seed = seed_kernel(domain, live, backend)
        per_impl: dict[str, float] = {}
        for _ in range(2 * len(cands)):
            impl = router.choose(key, seed, cands)
            rspec = dataclasses.replace(
                spec,
                segment_impl=impl,
                hash_slots=hash_slots_for(domain, live) if impl == "hash" else 0,
            )
            s, state = dispatch(batch, rspec)
            router.record(key, impl, s)
            per_impl[impl] = min(per_impl.get(impl, s), s)
            # honesty: every probed impl must agree with the static arm
            if not (
                np.array_equal(state.counts, static_state.counts)
                and np.allclose(state.sums, static_state.sums, rtol=1e-4)
            ):
                return {"metric": "groupby_error", "value": 0,
                        "unit": f"impl {impl} mismatch at {label}",
                        "vs_baseline": 0, "platform": platform}
        routed_impl = router.choose(key, seed, cands)
        routed_spec = dataclasses.replace(
            spec,
            segment_impl=routed_impl,
            hash_slots=(
                hash_slots_for(domain, live) if routed_impl == "hash" else 0
            ),
        )
        routed_s, _ = timed(batch, routed_spec)
        total_static += static_s
        total_routed += routed_s
        sweep.append({
            "shape": label, "cardinality": domain, "live_groups": live,
            "static_impl": static_impl, "static_ms": round(static_s * 1e3, 2),
            "routed_impl": routed_impl, "routed_ms": round(routed_s * 1e3, 2),
            "probed_ms": {k: round(v * 1e3, 2) for k, v in per_impl.items()},
        })

    # Gates: router never loses to static anywhere, hash wins somewhere.
    # A shape where the router chose the SAME impl as static matches by
    # construction (identical computation; any timing delta is host
    # jitter, 20%+ between identical passes on these shared 1-core
    # hosts); only a DIFFERENT choice must prove itself on the clock.
    never_worse = all(
        e["routed_impl"] == e["static_impl"]
        or e["routed_ms"] <= e["static_ms"] * 1.05 + 2.0
        for e in sweep
    )
    hash_wins = [
        e["shape"] for e in sweep
        if e["routed_impl"] == "hash" and e["routed_ms"] < e["static_ms"]
    ]
    suffix = "" if platform == "tpu" else "_CPU-FALLBACK"
    return {
        "metric": f"groupby_rows_per_sec_learned-router{suffix}",
        "value": round(len(GROUPBY_SHAPES) * n / total_routed),
        "unit": "rows/s",
        "vs_baseline": round(total_static / total_routed, 3),
        "baseline": "static-mxu-max-segments-policy",
        "router_never_worse": never_worse,
        "hash_win_shapes": hash_wins,
        "sweep": sweep,
        "platform": platform,
    }


# ---- rawscan config (device raw reads: fused filter + top-k A/B) --------
#
# The acceptance gate for the raw device-read path (query/executor.
# _try_raw_device over ops/scan_topk): sweep numeric-filter selectivity
# 0.001 -> 1.0 against LIMIT 10 -> 10k on the dashboard staple
# ``SELECT ... ORDER BY ts DESC LIMIT n`` through the REAL SQL path
# (scan-cache build, packed session upload, top-k kernel, host gather),
# A/B'd against the host-only baseline (HORAEDB_RAW_DEVICE=0 — the
# exact pre-PR execution: full table.read + host filter + np.lexsort).
# Gates: the learned routing must never lose to host-only anywhere on
# the sweep (impl-aware: a rep the router itself served from host
# matches by construction), and the low-selectivity LIMIT 100 dashboard
# shape must show a measured >= 2x win on a cached table.
# Just under the 2^19 shape bucket: the resident arrays pad to
# shape_bucket(n+1), and a count one past a boundary doubles every
# kernel pass for pad rows — bench at the friendly size (the sweep's
# RELATIVE numbers at unfriendly sizes shift both arms' constants, not
# the routing story).
RAWSCAN_ROWS = int(os.environ.get("BENCH_RAWSCAN_ROWS", str((1 << 19) - 256)))
RAWSCAN_REPEATS = int(os.environ.get("BENCH_RAWSCAN_REPEATS", "5"))
RAWSCAN_SELECTIVITIES = (0.001, 0.01, 0.1, 0.5, 1.0)
RAWSCAN_LIMITS = (10, 100, 1000, 10000)


def run_rawscan_config() -> dict:
    import jax

    import horaedb_tpu
    from horaedb_tpu.common_types import RowGroup
    from horaedb_tpu.common_types.schema import compute_tsid

    platform = jax.devices()[0].platform
    db = horaedb_tpu.connect(None)
    try:
        db.execute(
            "CREATE TABLE rawscan (host string TAG, v double, "
            "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) "
            "ENGINE=Analytic WITH (segment_duration='24h')"
        )
        rng = np.random.default_rng(11)
        n = RAWSCAN_ROWS
        hosts = np.array(
            [f"host_{i}" for i in rng.integers(0, 64, n)], dtype=object
        )
        schema = db.catalog.open("rawscan").schema
        rows = RowGroup(
            schema,
            {
                "tsid": compute_tsid([hosts]),
                "host": hosts,
                "v": rng.random(n),
                # unique timestamps: result sets compare exactly (no
                # ORDER BY tie ambiguity between the two arms)
                "ts": (1_700_000_000_000 + np.arange(n)).astype(np.int64),
            },
        )
        t = db.catalog.open("rawscan")
        t.write(rows)
        t.flush()

        def timed_pair(sql: str) -> tuple[float, list, str, float, list]:
            """Interleaved A/B (same trick as the ingest config): the
            routed and host-only arms alternate rep by rep so drift on
            the noisy shared host cancels instead of biasing one arm."""
            for _ in range(3):  # cache build + router settle (2 device
                db.execute(sql)  # probes, 1 host sample)
            os.environ["HORAEDB_RAW_DEVICE"] = "0"
            db.execute(sql)  # host-arm warmup
            os.environ.pop("HORAEDB_RAW_DEVICE", None)
            best_d = best_h = np.inf
            d_rows = h_rows = None
            path = ""
            for _ in range(RAWSCAN_REPEATS):
                s = time.perf_counter()
                out = db.execute(sql)
                dt = time.perf_counter() - s
                if dt < best_d:
                    best_d, d_rows = dt, out.to_pylist()
                    path = db.interpreters.executor.last_path
                os.environ["HORAEDB_RAW_DEVICE"] = "0"
                s = time.perf_counter()
                out = db.execute(sql)
                dt = time.perf_counter() - s
                if dt < best_h:
                    best_h, h_rows = dt, out.to_pylist()
                os.environ.pop("HORAEDB_RAW_DEVICE", None)
            return best_d, d_rows, path, best_h, h_rows

        shapes = [
            (f"sel-{s}-limit-{lim}", s, lim,
             f"SELECT host, v, ts FROM rawscan WHERE v < {s} "
             f"ORDER BY ts DESC LIMIT {lim}")
            for s in RAWSCAN_SELECTIVITIES
            for lim in RAWSCAN_LIMITS
        ] + [
            # the dashboard staple: one host's panel, newest first
            ("dash-single-host-limit-100", 1.0 / 64, 100,
             "SELECT host, v, ts FROM rawscan WHERE host = 'host_3' "
             "ORDER BY ts DESC LIMIT 100"),
            # bounded-selection shapes: multi-key ORDER BY needs the
            # complete passing set (no top-k), still device-served
            ("select-multikey", 0.01, None,
             "SELECT host, v, ts FROM rawscan WHERE v < 0.01 "
             "ORDER BY host ASC, ts DESC"),
            ("select-offset", 0.01, 100,
             "SELECT host, v, ts FROM rawscan WHERE v < 0.01 "
             "ORDER BY ts ASC LIMIT 100 OFFSET 50"),
        ]
        sweep = []
        total_dev = total_host = 0.0
        for label, sel, lim, sql in shapes:
            dev_s, dev_rows, dev_path, host_s, host_rows = timed_pair(sql)
            if dev_rows != host_rows:
                return {"metric": "rawscan_error", "value": 0,
                        "unit": f"device/host mismatch at {label}",
                        "vs_baseline": 0, "platform": platform}
            total_dev += dev_s
            total_host += host_s
            sweep.append({
                "shape": label, "selectivity": sel, "limit": lim,
                "served": dev_path,
                "device_ms": round(dev_s * 1e3, 2),
                "host_ms": round(host_s * 1e3, 2),
            })

        # Gates. A shape the router itself served from host matches the
        # baseline by construction (identical computation; timing deltas
        # are host jitter on these shared 1-core boxes); only a shape
        # the device actually served must prove itself on the clock.
        never_worse = all(
            e["served"] != "raw_device"
            or e["device_ms"] <= e["host_ms"] * 1.10 + 2.0
            for e in sweep
        )
        dash = [
            e["host_ms"] / max(e["device_ms"], 1e-9)
            for e in sweep
            if e["limit"] == 100 and e["selectivity"] <= 0.02
            and e["served"] == "raw_device"
        ]
        dashboard_speedup = round(max(dash), 2) if dash else 0.0
        suffix = "" if platform == "tpu" else "_CPU-FALLBACK"
        return {
            "metric": f"rawscan_rows_per_sec_device{suffix}",
            "value": round(len(shapes) * n / max(total_dev, 1e-9)),
            "unit": "rows/s",
            "vs_baseline": round(total_host / max(total_dev, 1e-9), 3),
            "baseline": "host-only-raw-path (HORAEDB_RAW_DEVICE=0)",
            "router_never_worse": never_worse,
            "dashboard_speedup": dashboard_speedup,
            "dashboard_win_ok": dashboard_speedup >= 2.0,
            "sweep": sweep,
            "platform": platform,
        }
    finally:
        os.environ.pop("HORAEDB_RAW_DEVICE", None)
        db.close()


# ---- flood config (multi-query fused serving A/B) -------------------------


def run_flood_config() -> dict:
    """The dashboard flood (ROADMAP item 1): hundreds of concurrent
    shape-identical aggregate SELECTs — same dashboard query, different
    tenant/host/time literals — through the proxy, A/B-ing cohort
    batching ([wlm.batch], wlm/batch.CohortBatcher + the vmapped
    ops/scan_agg.cached_scan_agg_cohort kernel) against today's
    per-query dispatch path.

    The headline is DISPATCHES PER QUERY, counted from the database's
    own ledger counters (horaedb_query_jit_compiles_total +
    jit_cache_hits_total — every device-kernel dispatch feeds exactly
    one of them): the fused arm must serve the flood with strictly
    fewer device dispatches per query (>= 4x fewer once cohorts reach
    8). p50/p99 per-query latency rides in the record for both arms
    (on an accelerator the per-dispatch round-trip saving is the
    point; on XLA-CPU dispatch is cheap so latency parity is the bar)."""
    import threading

    from horaedb_tpu.proxy import Proxy
    from horaedb_tpu.utils.config import BatchSection
    from horaedb_tpu.utils.querystats import _FIELD_COUNTERS
    from horaedb_tpu.utils.metrics import REGISTRY
    import jax

    platform = jax.devices()[0].platform
    hosts = int(os.environ.get("BENCH_FLOOD_HOSTS", "48"))
    rows_per_host = int(os.environ.get("BENCH_FLOOD_ROWS", "300"))
    queries = int(os.environ.get("BENCH_FLOOD_QUERIES", "800"))
    workers = int(os.environ.get("BENCH_FLOOD_WORKERS", "32"))
    window_s = float(os.environ.get("BENCH_FLOOD_WINDOW_S", "0.005"))

    db = _connect_mem()
    db.execute(
        "CREATE TABLE dash (host string TAG, v double, "
        "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic"
    )
    rng = np.random.default_rng(11)
    t0 = 1_700_000_000_000
    chunk = []
    for h in range(hosts):
        vs = rng.random(rows_per_host) * 100.0
        for i in range(rows_per_host):
            chunk.append(f"('h{h}', {vs[i]:.3f}, {t0 + i * 1000})")
        if len(chunk) >= 4000 or h == hosts - 1:
            db.execute(
                "INSERT INTO dash (host, v, ts) VALUES " + ",".join(chunk)
            )
            chunk = []
    db.flush_all()
    span = rows_per_host * 1000

    def sql_for(q: int) -> str:
        # one plan shape, literals varied per query: sliding time range
        # + a numeric filter literal (the dashboard-refresh pattern)
        lo = t0 + (q % 64) * 1000
        return (
            f"SELECT host, count(v), sum(v), max(v) FROM dash "
            f"WHERE ts >= {lo} AND ts < {t0 + span} AND v >= {q % 7}.5 "
            f"GROUP BY host"
        )

    def dispatches() -> float:
        return (
            _FIELD_COUNTERS["jit_compiles"].value
            + _FIELD_COUNTERS["jit_cache_hits"].value
        )

    def flood(proxy, n: int, record: list | None) -> None:
        idx = iter(range(n))
        lock = threading.Lock()

        def worker():
            while True:
                with lock:
                    q = next(idx, None)
                if q is None:
                    return
                t_q = time.perf_counter()
                proxy.handle_sql(sql_for(q))
                if record is not None:
                    record.append(time.perf_counter() - t_q)

        threads = [threading.Thread(target=worker) for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    def arm(batch_cfg) -> dict:
        proxy = Proxy(db, batch_cfg=batch_cfg)
        try:
            # warmup: build the scan cache, compile the kernels (and the
            # cohort kernel's pow2 batch buckets in the fused arm) so
            # the measured flood is steady-state serving
            flood(proxy, min(128, queries), None)
            lat: list = []
            d0 = dispatches()
            t_arm = time.perf_counter()
            flood(proxy, queries, lat)
            wall = time.perf_counter() - t_arm
            d1 = dispatches()
            lat.sort()
            return {
                "dispatches_per_query": round((d1 - d0) / queries, 4),
                "p50_ms": round(lat[len(lat) // 2] * 1000, 3),
                "p99_ms": round(lat[int(len(lat) * 0.99) - 1] * 1000, 3),
                "qps": round(queries / max(wall, 1e-9), 1),
            }
        finally:
            proxy.close()

    try:
        solo = arm(None)  # batching disabled: today's per-query path
        fused = arm(
            BatchSection(enabled=True, window_s=window_s, max_cohort=32)
        )
        # mean fused cohort size, from the database's own family
        sizes = {"1": 1, "2": 2, "4": 3, "8": 6, "16": 12, "32+": 24}
        cohorts = served = 0.0
        for b, approx in sizes.items():
            c = REGISTRY.counter(
                "horaedb_batch_cohort_total",
                "fused cohorts served, by cohort-size bucket",
                labels={"size": b},
            ).value
            cohorts += c
            served += c * approx
        mean_cohort = round(served / cohorts, 2) if cohorts else 0.0
        reduction = round(
            solo["dispatches_per_query"]
            / max(fused["dispatches_per_query"], 1e-9),
            2,
        )
        suffix = "" if platform == "tpu" else "_CPU-FALLBACK"
        return {
            "metric": f"flood_dispatch_reduction{suffix}",
            "value": reduction,
            "unit": "solo dispatches-per-query / fused dispatches-per-query",
            "vs_baseline": reduction,
            "baseline": "per-query dispatch ([wlm.batch] enabled=false)",
            "queries": queries,
            "workers": workers,
            "window_ms": window_s * 1000,
            "mean_cohort": mean_cohort,
            "reduction_ok": reduction >= 4.0 or mean_cohort < 8,
            "solo": solo,
            "fused": fused,
            "platform": platform,
        }
    finally:
        db.close()


# ---- decisions config (decision-journal overhead A/B) ---------------------


def run_decisions_config() -> dict:
    """Decision-plane overhead gate: the flood's dashboard shape served
    twice through the proxy — decision journal ON (every query records a
    kernel-router pick and an admission cost prediction, and resolves
    both) vs ``HORAEDB_DECISIONS=0`` (record returns 0, resolve is a
    no-op). The journal is bookkeeping on the serving path, so the gate
    is wall-clock parity: the on arm must land within 2% of off.

    Arms are interleaved across reps and each arm's MINIMUM wall is
    compared (min is robust to the one-off GC/compile hiccup a mean
    would smear into a false overhead). The record carries the journal's
    own accounting — recorded/resolved counts from DecisionJournal.stats()
    — so a "0% overhead" line where the journal never actually recorded
    anything is self-evidently vacuous."""
    import threading

    from horaedb_tpu.proxy import Proxy
    from horaedb_tpu.obs.decisions import DECISION_JOURNAL
    import jax

    platform = jax.devices()[0].platform
    hosts = int(os.environ.get("BENCH_DECISIONS_HOSTS", "32"))
    rows_per_host = int(os.environ.get("BENCH_DECISIONS_ROWS", "200"))
    queries = int(os.environ.get("BENCH_DECISIONS_QUERIES", "400"))
    workers = int(os.environ.get("BENCH_DECISIONS_WORKERS", "8"))
    reps = int(os.environ.get("BENCH_DECISIONS_REPS", "3"))

    db = _connect_mem()
    db.execute(
        "CREATE TABLE dash (host string TAG, v double, "
        "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic"
    )
    rng = np.random.default_rng(13)
    t0 = 1_700_000_000_000
    chunk = []
    for h in range(hosts):
        vs = rng.random(rows_per_host) * 100.0
        for i in range(rows_per_host):
            chunk.append(f"('h{h}', {vs[i]:.3f}, {t0 + i * 1000})")
        if len(chunk) >= 4000 or h == hosts - 1:
            db.execute(
                "INSERT INTO dash (host, v, ts) VALUES " + ",".join(chunk)
            )
            chunk = []
    db.flush_all()
    span = rows_per_host * 1000

    def sql_for(q: int) -> str:
        lo = t0 + (q % 64) * 1000
        return (
            f"SELECT host, count(v), sum(v), max(v) FROM dash "
            f"WHERE ts >= {lo} AND ts < {t0 + span} AND v >= {q % 7}.5 "
            f"GROUP BY host"
        )

    def flood(proxy, n: int) -> None:
        idx = iter(range(n))
        lock = threading.Lock()

        def worker():
            while True:
                with lock:
                    q = next(idx, None)
                if q is None:
                    return
                proxy.handle_sql(sql_for(q))

        threads = [threading.Thread(target=worker) for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    proxy = Proxy(db)
    prior = os.environ.get("HORAEDB_DECISIONS")
    try:
        # warmup: scan cache + kernel compiles, with the journal ON so
        # both code paths (record + resolve) are warm before timing
        os.environ["HORAEDB_DECISIONS"] = "1"
        flood(proxy, min(128, queries))
        issued0 = DECISION_JOURNAL.stats()["issued"]
        walls: dict = {"on": [], "off": []}
        for rep in range(reps):
            order = ("on", "off") if rep % 2 == 0 else ("off", "on")
            for arm in order:
                os.environ["HORAEDB_DECISIONS"] = (
                    "1" if arm == "on" else "0"
                )
                t_arm = time.perf_counter()
                flood(proxy, queries)
                walls[arm].append(time.perf_counter() - t_arm)
        stats = DECISION_JOURNAL.stats()
    finally:
        if prior is None:
            os.environ.pop("HORAEDB_DECISIONS", None)
        else:
            os.environ["HORAEDB_DECISIONS"] = prior
        proxy.close()
        db.close()

    on_s, off_s = min(walls["on"]), min(walls["off"])
    overhead_pct = round((on_s / max(off_s, 1e-9) - 1.0) * 100.0, 3)
    resolved = sum(l["resolved"] for l in stats["loops"].values())
    suffix = "" if platform == "tpu" else "_CPU-FALLBACK"
    return {
        "metric": f"decisions_overhead_pct{suffix}",
        "value": overhead_pct,
        "unit": "% wall overhead, decision journal on vs HORAEDB_DECISIONS=0",
        "vs_baseline": round(on_s / max(off_s, 1e-9), 4),
        "baseline": "HORAEDB_DECISIONS=0 (journal off)",
        "overhead_ok": on_s <= off_s * 1.02,
        "on_s": round(on_s, 4),
        "off_s": round(off_s, 4),
        "reps": reps,
        "queries": queries,
        "workers": workers,
        "decisions_recorded": stats["issued"] - issued0,
        "decisions_resolved": resolved,
        "platform": platform,
    }


def run_profile_config() -> dict:
    """Profile-plane overhead gate: the flood's dashboard shape served
    twice through the proxy — profile fold ON (every finish_trace folds
    its span tree into the streaming aggregator) vs ``HORAEDB_PROFILE=0``
    (fold returns at the env check). The fold walks a finished tree
    after the response is ready, so the gate is wall-clock parity: the
    on arm must land within 2% of off.

    Arms are interleaved across reps and each arm's MINIMUM wall is
    compared (same discipline as the decisions gate). The record carries
    the aggregator's own accounting — traces/spans folded during the on
    arms from PROFILE.stats() — so a "0% overhead" line where nothing
    actually folded is self-evidently vacuous."""
    import threading

    from horaedb_tpu.proxy import Proxy
    from horaedb_tpu.obs.profile import PROFILE, flush as profile_flush
    import jax

    platform = jax.devices()[0].platform
    hosts = int(os.environ.get("BENCH_PROFILE_HOSTS", "32"))
    rows_per_host = int(os.environ.get("BENCH_PROFILE_ROWS", "200"))
    queries = int(os.environ.get("BENCH_PROFILE_QUERIES", "400"))
    workers = int(os.environ.get("BENCH_PROFILE_WORKERS", "8"))
    reps = int(os.environ.get("BENCH_PROFILE_REPS", "3"))

    db = _connect_mem()
    db.execute(
        "CREATE TABLE dash (host string TAG, v double, "
        "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic"
    )
    rng = np.random.default_rng(17)
    t0 = 1_700_000_000_000
    chunk = []
    for h in range(hosts):
        vs = rng.random(rows_per_host) * 100.0
        for i in range(rows_per_host):
            chunk.append(f"('h{h}', {vs[i]:.3f}, {t0 + i * 1000})")
        if len(chunk) >= 4000 or h == hosts - 1:
            db.execute(
                "INSERT INTO dash (host, v, ts) VALUES " + ",".join(chunk)
            )
            chunk = []
    db.flush_all()
    span = rows_per_host * 1000

    def sql_for(q: int) -> str:
        lo = t0 + (q % 64) * 1000
        return (
            f"SELECT host, count(v), sum(v), max(v) FROM dash "
            f"WHERE ts >= {lo} AND ts < {t0 + span} AND v >= {q % 7}.5 "
            f"GROUP BY host"
        )

    def flood(proxy, n: int) -> None:
        idx = iter(range(n))
        lock = threading.Lock()

        def worker():
            while True:
                with lock:
                    q = next(idx, None)
                if q is None:
                    return
                proxy.handle_sql(sql_for(q))

        threads = [threading.Thread(target=worker) for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    proxy = Proxy(db)
    prior = os.environ.get("HORAEDB_PROFILE")
    try:
        # warmup: scan cache + kernel compiles, with the fold ON so the
        # aggregator's key rows exist before timing
        os.environ["HORAEDB_PROFILE"] = "1"
        flood(proxy, min(128, queries))
        profile_flush(10.0)
        traces0 = PROFILE.stats()["traces"]
        walls: dict = {"on": [], "off": []}
        for rep in range(reps):
            order = ("on", "off") if rep % 2 == 0 else ("off", "on")
            for arm in order:
                os.environ["HORAEDB_PROFILE"] = (
                    "1" if arm == "on" else "0"
                )
                # the arm's wall includes draining the fold queue — the
                # deferred fold is part of the plane's cost, so the on
                # arm must pay it inside the timed window (the off arm's
                # flush returns immediately: nothing queued)
                t_arm = time.perf_counter()
                flood(proxy, queries)
                profile_flush(30.0)
                walls[arm].append(time.perf_counter() - t_arm)
        stats = PROFILE.stats()
    finally:
        if prior is None:
            os.environ.pop("HORAEDB_PROFILE", None)
        else:
            os.environ["HORAEDB_PROFILE"] = prior
        proxy.close()
        db.close()

    on_s, off_s = min(walls["on"]), min(walls["off"])
    overhead_pct = round((on_s / max(off_s, 1e-9) - 1.0) * 100.0, 3)
    suffix = "" if platform == "tpu" else "_CPU-FALLBACK"
    return {
        "metric": f"profile_overhead_pct{suffix}",
        "value": overhead_pct,
        "unit": "% wall overhead, profile fold on vs HORAEDB_PROFILE=0",
        "vs_baseline": round(on_s / max(off_s, 1e-9), 4),
        "baseline": "HORAEDB_PROFILE=0 (fold off)",
        "overhead_ok": on_s <= off_s * 1.02,
        "on_s": round(on_s, 4),
        "off_s": round(off_s, 4),
        "reps": reps,
        "queries": queries,
        "workers": workers,
        "traces_folded": stats["traces"] - traces0,
        "profile_keys": stats["keys"],
        "untracked_ratio": stats["untracked_ratio"],
        "platform": platform,
    }


def _host_merge_permutation(tsid, ts, seq, dedup=True):
    """Vectorized-numpy merge baseline with the device kernel's exact
    semantics: sort (tsid, ts, seq desc, input-row desc), keep the first
    row of each (tsid, ts) key."""
    n = len(tsid)
    negseq = ~seq.astype(np.uint64)
    negidx = np.arange(n - 1, -1, -1, dtype=np.uint64)
    order = np.lexsort((negidx, negseq, ts, tsid)).astype(np.int32)
    if not dedup:
        return order, np.ones(n, dtype=np.bool_)
    s_tsid, s_ts = tsid[order], ts[order]
    same = (s_tsid[1:] == s_tsid[:-1]) & (s_ts[1:] == s_ts[:-1])
    return order, np.concatenate([np.ones(1, dtype=np.bool_), ~same])


def run_compaction_config() -> dict:
    """BASELINE config 5: time Compactor.compact() with the device merge
    kernel vs the numpy host merge on an identical second table; verify
    both produce the same compacted data via a post-compaction scan."""
    import jax

    from horaedb_tpu.engine import compaction as compaction_mod

    platform = jax.devices()[0].platform
    config = "compaction-64"

    # Device pass. Warm the chunked pipeline's sort kernels on their
    # padded bucket shapes first so compile time (minutes for a TPU) isn't billed to the merge.
    db_dev, table_dev = _build_compaction_db(seed=7)
    n_input = sum(h.meta.num_rows for h in table_dev.version.levels.files_at(0))
    compaction_mod.Compactor(table_dev).warm_device_merge(n_input)
    # The 100M-row build leaves GBs of garbage; collect BEFORE timing so
    # allocator churn lands on neither side of the A/B unevenly.
    import gc

    gc.collect()
    s = time.perf_counter()
    res_dev = compaction_mod.Compactor(table_dev).compact()
    dev_s = time.perf_counter() - s
    dev_check = db_dev.execute(
        "SELECT count(1) AS c, avg(value) AS v FROM demo"
    ).to_pylist()
    # Release the device pass's multi-GB MemoryStore before the host
    # build so both passes run under comparable memory pressure.
    db_dev.close()
    del db_dev, table_dev
    gc.collect()

    # Host pass: identical table (same seed), merge forced onto numpy by
    # replacing the WHOLE _merge_stream (the merge engine's single
    # override point — patching anything narrower would leave the "host"
    # pass on the device pipeline).
    db_host, table_host = _build_compaction_db(seed=7)
    from horaedb_tpu.common_types import RowGroup as _RG
    from horaedb_tpu.engine.options import UpdateMode

    def _forced_host_merge(self, parts, versions):
        rows = _RG.concat(parts) if len(parts) > 1 else parts[0]
        seq = np.concatenate(versions)
        schema = rows.schema
        tsid = rows.columns[schema.columns[schema.tsid_index].name]
        dedup = self.table.options.update_mode is UpdateMode.OVERWRITE
        perm, keep = _host_merge_permutation(
            tsid, rows.timestamps.astype(np.int64), seq, dedup=dedup
        )
        sel = perm[keep]
        yield rows.take(sel), seq[sel]

    orig = compaction_mod.Compactor._merge_stream
    compaction_mod.Compactor._merge_stream = _forced_host_merge
    try:
        gc.collect()  # same settle as the device pass
        s = time.perf_counter()
        res_host = compaction_mod.Compactor(table_host).compact()
        host_s = time.perf_counter() - s
    finally:
        compaction_mod.Compactor._merge_stream = orig
    host_check = db_host.execute(
        "SELECT count(1) AS c, avg(value) AS v FROM demo"
    ).to_pylist()

    if (res_dev.rows_written != res_host.rows_written
            or not _rows_agree(dev_check, host_check)):
        return {"metric": f"{config}_error", "value": 0,
                "unit": "device/host merge mismatch", "vs_baseline": 0,
                "platform": platform}

    suffix = "" if platform == "tpu" else "_CPU-FALLBACK"
    return {
        "metric": f"{config}_rows_per_sec_device-merge{suffix}",
        "value": round(n_input / dev_s),
        "unit": "rows/s",
        "vs_baseline": round(host_s / dev_s, 3),
        "platform": platform,
        "input_rows": n_input,
        "ssts": COMPACTION_SSTS,
    }


# ---- rollup config (continuous-query rewrite A/B) -----------------------
#
# Dashboard-shaped range aggregation over a rollup-maintained table: the
# SAME statement served from the 1m tier (route=rollup, pre-aggregated
# partials + empty raw tail) vs forced onto the raw table with
# HORAEDB_ROLLUP=0. Interleaved pairs (shared-host drift cancels),
# min-of-N, results must agree numerically, and the gate is impl-aware:
# the rollup arm must actually have served route=rollup.

ROLLUP_ROWS = int(os.environ.get("BENCH_ROLLUP_ROWS", str((1 << 20) - 256)))
ROLLUP_HOURS = 6
ROLLUP_STEP_MS = 300_000  # the 5m dashboard step


def _prom_matrices_agree(a, b, rtol: float = 2e-3) -> bool:
    """Prom 'matrix' results from the two arms must agree series-for-
    series, point-for-point (same tolerance as the SQL arm)."""
    if a is None or b is None or len(a) != len(b):
        return False
    ka = sorted(a, key=lambda s: sorted(s["metric"].items()))
    kb = sorted(b, key=lambda s: sorted(s["metric"].items()))
    for sa, sb in zip(ka, kb):
        if sa["metric"] != sb["metric"] or len(sa["values"]) != len(sb["values"]):
            return False
        for (ta, va), (tb, vb) in zip(sa["values"], sb["values"]):
            if ta != tb or not np.isclose(
                float(va), float(vb), rtol=rtol, atol=1e-3, equal_nan=True
            ):
                return False
    return True


def run_rollup_config() -> dict:
    import jax

    import horaedb_tpu
    from horaedb_tpu.common_types import RowGroup
    from horaedb_tpu.common_types.schema import compute_tsid
    from horaedb_tpu.proxy.promql import evaluate_expr_range, parse_promql
    from horaedb_tpu.rules import ROLLUPS, RuleEngine
    from horaedb_tpu.utils.config import RulesSection

    platform = jax.devices()[0].platform
    suffix = "" if platform == "tpu" else "_CPU-FALLBACK"
    ROLLUPS.reset()
    db = _connect_mem()
    db.execute(
        "CREATE TABLE dash (host string TAG, value double, ts timestamp "
        "NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic "
        "WITH (segment_duration='2h', update_mode='append')"
    )
    n = ROLLUP_ROWS
    end = (1_786_000_000_000 // 3_600_000) * 3_600_000  # hour-aligned
    start = end - ROLLUP_HOURS * 3_600_000
    rng = np.random.default_rng(42)
    hosts = np.array(
        [f"host_{i}" for i in rng.integers(0, 8, n)], dtype=object
    )
    schema = db.catalog.open("dash").schema
    t = db.catalog.open("dash")
    t.write(RowGroup(
        schema,
        {
            "tsid": compute_tsid([hosts]),
            "ts": rng.integers(start, end, n).astype(np.int64),
            "host": hosts,
            "value": rng.normal(10.0, 3.0, n),
        },
    ))
    t.flush()

    # one catch-up round builds the whole 1m + 1h ladder (untimed setup —
    # maintenance is amortized background work at eval_interval cadence)
    eng = RuleEngine(db, RulesSection(
        rollup_tables=["dash"], grace_s=0, rollup_raw_ttl_s=0,
    ))
    eng.load()
    s = time.perf_counter()
    eng.run_once(now_ms=end)
    maintain_s = time.perf_counter() - s

    sql = (
        f"SELECT time_bucket(ts, '5m') AS b, host, avg(value) AS v "
        f"FROM dash WHERE ts >= {start} AND ts < {end} "
        f"GROUP BY time_bucket(ts, '5m'), host"
    )
    pq = parse_promql("dash")

    def run_sql():
        s = time.perf_counter()
        out = db.execute(sql)
        return time.perf_counter() - s, out.to_pylist(), \
            db.interpreters.executor.last_path

    def run_prom():
        s = time.perf_counter()
        out = evaluate_expr_range(db, pq, start, end - 1, ROLLUP_STEP_MS)
        return time.perf_counter() - s, out

    @contextlib.contextmanager
    def raw_forced():
        os.environ["HORAEDB_ROLLUP"] = "0"
        try:
            yield
        finally:
            os.environ.pop("HORAEDB_ROLLUP", None)

    # warm both arms (compile + scan-cache build are one-off costs)
    run_sql(); run_prom()
    with raw_forced():
        run_sql(); run_prom()

    roll_best = raw_best = proll_best = praw_best = np.inf
    roll_rows = raw_rows = prows = praw_rows = None
    roll_path = raw_path = prom_path = ""
    for _ in range(max(REPEATS, 7)):
        dt, rows, path = run_sql()
        if dt < roll_best:
            roll_best, roll_rows, roll_path = dt, rows, path
        pdt, pr = run_prom()
        if pdt < proll_best:
            proll_best, prows = pdt, pr
            prom_path = db.interpreters.executor.last_path
        with raw_forced():
            dt, rows, path = run_sql()
            if dt < raw_best:
                raw_best, raw_rows, raw_path = dt, rows, path
            pdt, pr = run_prom()
            if pdt < praw_best:
                praw_best, praw_rows = pdt, pr

    if roll_path != "rollup" or prom_path != "rollup":
        return {"metric": f"rollup_error{suffix}", "value": 0,
                "unit": f"rollup arm served sql={roll_path} "
                        f"promql={prom_path}",
                "vs_baseline": 0, "platform": platform}
    # the raw arm rides f32 device kernels vs the rollup's f64 partials:
    # the same 2e-3 tolerance the equivalence tests establish
    if not _rows_agree(roll_rows, raw_rows, rtol=2e-3):
        return {"metric": f"rollup_error{suffix}", "value": 0,
                "unit": "rollup/raw result mismatch", "vs_baseline": 0,
                "platform": platform}
    if not _prom_matrices_agree(prows, praw_rows):
        return {"metric": f"rollup_error{suffix}", "value": 0,
                "unit": "rollup/raw PromQL result mismatch",
                "vs_baseline": 0, "platform": platform}
    speedup = raw_best / roll_best
    return {
        "metric": f"rollup_dashboard_rows_per_sec{suffix}",
        "value": round(n / roll_best),
        "unit": "rows/s",
        # headline ratio: the raw-table path vs the rollup-served path
        "vs_baseline": round(speedup, 3),
        "promql_speedup": round(praw_best / proll_best, 3),
        "never_worse": bool(roll_best <= raw_best * 1.05),
        "target_3x": bool(speedup >= 3.0),
        "rollup_ms": round(roll_best * 1000, 3),
        "raw_ms": round(raw_best * 1000, 3),
        "maintain_ms": round(maintain_s * 1000, 1),
        "raw_path": raw_path,
        "platform": platform,
    }


LIVEWINDOW_ROWS = int(os.environ.get("BENCH_LIVEWINDOW_ROWS", "300000"))


def run_livewindow_config() -> dict:
    """Steady-state dashboard-refresh latency under concurrent ingest:
    the open-tail (time_bucket 1m x host) panel served from device ring
    state (route=livewindow) vs the same query forced raw
    (HORAEDB_LIVEWINDOW=0). Each arm measures with a live trickle
    ingest running; equivalence is checked between arms with ingest
    quiesced (state answers must equal the raw rescan). Also times the
    PromQL increase() face of the same state (write-time folded counter
    partials vs the raw host-side chain fold)."""
    import threading

    import jax

    import horaedb_tpu
    from horaedb_tpu.common_types import RowGroup
    from horaedb_tpu.common_types.schema import compute_tsid
    from horaedb_tpu.proxy.promql import evaluate_expr_range, parse_promql
    from horaedb_tpu.state.livewindow import STORE

    platform = jax.devices()[0].platform
    suffix = "" if platform == "tpu" else "_CPU-FALLBACK"
    STORE.clear()
    db = _connect_mem()
    db.execute(
        "CREATE TABLE panel (host string TAG, value double NOT NULL, "
        "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic "
        "WITH (segment_duration='2h', update_mode='append')"
    )
    schema = db.catalog.open("panel").schema
    t = db.catalog.open("panel")
    rng = np.random.default_rng(7)
    w = 60_000
    live_start = (1_786_000_000_000 // w) * w
    seed_start = live_start - 120 * w

    def mk_batch(lo, hi, n):
        hosts = np.array(
            [f"host_{i}" for i in rng.integers(0, 8, n)], dtype=object
        )
        ts = np.sort(rng.integers(lo, hi, n).astype(np.int64))
        return RowGroup(schema, {
            "tsid": compute_tsid([hosts]),
            "ts": ts,
            "host": hosts,
            "value": rng.normal(10.0, 3.0, n),
        })

    # older-than-the-panel history (below the promotion watermark)
    t.write(mk_batch(seed_start, live_start, 20_000))

    sql = (
        f"SELECT time_bucket(ts, '1m') AS b, host, avg(value) AS v, "
        f"count(value) AS c FROM panel WHERE ts >= {live_start} "
        f"GROUP BY time_bucket(ts, '1m'), host"
    )
    for _ in range(3):  # usage-driven promotion (HORAEDB_LIVEWINDOW_PROMOTE)
        db.execute(sql)
    if not STORE.stats()["states"]:
        return {"metric": f"livewindow_error{suffix}", "value": 0,
                "unit": "shape did not promote", "vs_baseline": 0,
                "platform": platform}

    # the live bulk: ~90 buckets of open tail folded at write time in
    # ONE committed batch, then a trickle keeps the tail moving during
    # each measured arm
    n_live = LIVEWINDOW_ROWS
    t.write(mk_batch(live_start, live_start + 90 * w, n_live))
    rows_written = [n_live]
    cursor = [live_start + 90 * w]

    def start_ingest():
        stop = threading.Event()

        def loop():
            while not stop.is_set():
                lo = cursor[0]
                cursor[0] = lo + 15_000  # the open tail keeps advancing
                t.write(mk_batch(lo, cursor[0], 500))
                rows_written[0] += 500
                time.sleep(0.02)

        th = threading.Thread(target=loop, daemon=True)
        th.start()
        return th, stop

    pq = parse_promql("increase(panel[1m])")

    def run_sql():
        s = time.perf_counter()
        out = db.execute(sql)
        return time.perf_counter() - s, out.to_pylist(), \
            db.interpreters.executor.last_path

    def run_prom():
        s = time.perf_counter()
        out = evaluate_expr_range(db, pq, live_start, cursor[0], w)
        return time.perf_counter() - s, out

    @contextlib.contextmanager
    def raw_forced():
        os.environ["HORAEDB_LIVEWINDOW"] = "0"
        try:
            yield
        finally:
            os.environ.pop("HORAEDB_LIVEWINDOW", None)

    # ---- state arm (concurrent ingest running) ----
    th, stop = start_ingest()
    run_sql(); run_prom()  # warm (compile + first gather)
    state_best = pstate_best = np.inf
    state_path = ""
    for _ in range(max(REPEATS, 7)):
        dt, _rows, path = run_sql()
        if dt < state_best:
            state_best, state_path = dt, path
        pdt, _pr = run_prom()
        pstate_best = min(pstate_best, pdt)
    n_at_state = rows_written[0]
    stop.set(); th.join()

    if state_path != "livewindow":
        return {"metric": f"livewindow_error{suffix}", "value": 0,
                "unit": f"state arm served path={state_path}",
                "vs_baseline": 0, "platform": platform}

    # ---- equivalence (ingest quiesced: no write, so the kill switch
    # cannot drop the state while we read the raw reference) ----
    _, state_rows, _ = run_sql()
    _, state_prom = run_prom()
    with raw_forced():
        _, raw_rows, _ = run_sql()
        _, raw_prom = run_prom()
    # state partials accumulate in f32; the raw arm folds f64 — the same
    # 2e-3 tolerance the equivalence tests establish
    if not _rows_agree(state_rows, raw_rows, rtol=2e-3):
        return {"metric": f"livewindow_error{suffix}", "value": 0,
                "unit": "state/raw result mismatch", "vs_baseline": 0,
                "platform": platform}
    if not _prom_matrices_agree(state_prom, raw_prom):
        return {"metric": f"livewindow_error{suffix}", "value": 0,
                "unit": "state/raw PromQL result mismatch",
                "vs_baseline": 0, "platform": platform}

    # ---- raw arm (concurrent ingest running; the first write under the
    # kill switch drops the state, which is the documented contract) ----
    th, stop = start_ingest()
    with raw_forced():
        run_sql(); run_prom()
        raw_best = praw_best = np.inf
        for _ in range(max(REPEATS, 7)):
            dt, _rows, _path = run_sql()
            raw_best = min(raw_best, dt)
            pdt, _pr = run_prom()
            praw_best = min(praw_best, pdt)
    stop.set(); th.join()

    speedup = raw_best / state_best
    return {
        "metric": f"livewindow_refresh_rows_per_sec{suffix}",
        "value": round(n_at_state / state_best),
        "unit": "rows/s",
        # headline ratio: the raw open-tail rescan vs the state gather
        "vs_baseline": round(speedup, 3),
        "promql_speedup": round(praw_best / pstate_best, 3),
        "never_worse": bool(state_best <= raw_best * 1.05),
        "target_3x": bool(speedup >= 3.0),
        "state_ms": round(state_best * 1000, 3),
        "raw_ms": round(raw_best * 1000, 3),
        "live_rows": int(n_at_state),
        "platform": platform,
    }


def time_arrow(db, table_name: str, arrow_fn) -> tuple[float, list]:
    """External anchor: the same query through pyarrow's Acero (an
    Arrow-native C++ vectorized engine — the closest runnable stand-in
    for the reference's DataFusion executor, which cannot run here: the
    image has no Rust toolchain, no prebuilt horaedb binary, and no
    network egress; see BASELINE.md). Scans the SAME Parquet SSTs through
    pyarrow.dataset -> filter -> group_by, exactly DataFusion's scan
    shape. SST dumping to disk is untimed setup."""
    import shutil
    import tempfile

    import pyarrow.dataset as pads

    data = db.catalog.open(table_name).physical_datas()[0]
    tmp = tempfile.mkdtemp(prefix="bench_arrow_")
    try:
        paths = []
        for i, h in enumerate(data.version.levels.all_files()):
            p = os.path.join(tmp, f"{i}.parquet")
            with open(p, "wb") as f:
                f.write(data.store.get(h.path))
            paths.append(p)
        dset = pads.dataset(paths, format="parquet")
        out = arrow_fn(dset)  # warmup
        best = np.inf
        for _ in range(REPEATS):
            s = time.perf_counter()
            out = arrow_fn(pads.dataset(paths, format="parquet"))
            best = min(best, time.perf_counter() - s)
        return best, out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def time_query(db, sql) -> tuple[float, list, str]:
    db.execute(sql)  # warmup (compile)
    best = np.inf
    best_path = ""
    out = None
    for _ in range(REPEATS):
        s = time.perf_counter()
        out = db.execute(sql)
        dt = time.perf_counter() - s
        if dt < best:
            best = dt
            # adaptive routing may serve different reps from different
            # paths; the metric is labeled by the path of the BEST rep
            best_path = db.interpreters.executor.last_path
    return best, out.to_pylist(), best_path


def _rows_agree(a: list, b: list, rtol: float = 1e-3, atol: float = 1e-3) -> bool:
    if len(a) != len(b):
        return False

    # Row order is unspecified without ORDER BY; canonicalize before the
    # pairwise numeric comparison. Sort by the exact-typed fields (group
    # keys) first — float aggregates differ slightly between paths and
    # must not drive the pairing.
    def key(row):
        exact = tuple(
            (k, v) for k, v in sorted(row.items()) if not isinstance(v, float)
        )
        approx = tuple(
            (k, round(v, 4)) for k, v in sorted(row.items()) if isinstance(v, float)
        )
        return (exact, approx)

    a = sorted(a, key=key)
    b = sorted(b, key=key)
    for ra, rb in zip(a, b):
        if set(ra) != set(rb):
            return False
        for k in ra:
            va, vb = ra[k], rb[k]
            if isinstance(va, float) or isinstance(vb, float):
                if not np.isclose(va, vb, rtol=rtol, atol=atol, equal_nan=True):
                    return False
            elif va != vb:
                return False
    return True


LAYOUT_SERIES = int(os.environ.get("BENCH_LAYOUT_SERIES", "400"))
LAYOUT_TS = int(os.environ.get("BENCH_LAYOUT_TS", "256"))
LAYOUT_METRICS = int(os.environ.get("BENCH_LAYOUT_METRICS", "10"))
LAYOUT_REPEATS = int(os.environ.get("BENCH_LAYOUT_REPEATS", "5"))


def run_layout_config() -> dict:
    """Compressed device-resident layouts A/B (ISSUE 19): TSBS-shaped
    data (hosts x aligned timestamps x low-cardinality integer metrics)
    served encoded (HORAEDB_CACHE_LAYOUT=auto, the default) vs pinned
    raw, interleaved rep by rep. Gates: resident logical rows per HBM
    byte >= 4x the raw arm (read from system.public.device — the
    inventory IS the accounting), bit-identical results, and
    groupby/rawscan never-worse on the clock."""
    import jax

    import horaedb_tpu
    from horaedb_tpu.common_types import RowGroup
    from horaedb_tpu.common_types.schema import compute_tsid

    platform = jax.devices()[0].platform
    n_series, n_ts, n_metrics = LAYOUT_SERIES, LAYOUT_TS, LAYOUT_METRICS
    n = n_series * n_ts

    def mk_db(table: str, raw: bool):
        """Identical TSBS-shaped data under `table`; layout mode is read
        at BUILD time, so the raw arm pins the env only around its own
        executes."""
        if raw:
            os.environ["HORAEDB_CACHE_LAYOUT"] = "raw"
        else:
            os.environ.pop("HORAEDB_CACHE_LAYOUT", None)
        try:
            db = horaedb_tpu.connect(None)
            cols = ", ".join(f"m{i} double" for i in range(n_metrics))
            db.execute(
                f"CREATE TABLE {table} (host string TAG, {cols}, "
                "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) "
                "ENGINE=Analytic WITH (segment_duration='24h')"
            )
            rng = np.random.default_rng(19)  # same draw in both arms
            hosts = np.repeat(
                np.array(
                    [f"host_{i:04d}" for i in range(n_series)], dtype=object
                ),
                n_ts,
            )
            ts = np.tile(
                1_700_000_000_000
                + np.arange(n_ts, dtype=np.int64) * 1000,
                n_series,
            )
            data = {"tsid": compute_tsid([hosts]), "host": hosts, "ts": ts}
            for m in range(n_metrics):
                # TSBS cpu-style gauges: integers in [0, 100)
                data[f"m{m}"] = rng.integers(0, 100, n).astype(np.float64)
            t = db.catalog.open(table)
            t.write(RowGroup(t.schema, data))
            t.flush()
            return db
        finally:
            os.environ.pop("HORAEDB_CACHE_LAYOUT", None)

    def queries(table: str) -> list[tuple[str, str]]:
        return [
            ("groupby",
             f"SELECT host, count(*) AS c, sum(m0) AS s0, avg(m1) AS a1, "
             f"max(m2) AS x2 FROM {table} GROUP BY host ORDER BY host"),
            ("bucket",
             f"SELECT time_bucket(ts, '1m') AS b, sum(m3) AS s "
             f"FROM {table} GROUP BY time_bucket(ts, '1m') ORDER BY b"),
            ("filter-code-domain",
             f"SELECT host, count(*) AS c, sum(m4) AS s FROM {table} "
             f"WHERE m5 > 50 GROUP BY host ORDER BY host"),
            ("rawscan",
             f"SELECT host, m0, ts FROM {table} WHERE m1 = 3 "
             f"ORDER BY host ASC, ts DESC"),
        ]

    def column_bytes(db, table: str) -> tuple[int, int]:
        rows = db.execute(
            "SELECT table_name, component, bytes, logical_rows "
            "FROM system.public.device"
        ).to_pylist()
        mine = [
            r for r in rows
            if r["table_name"] == table and r["component"] == "column"
        ]
        return (
            sum(r["bytes"] for r in mine),
            max((r["logical_rows"] for r in mine), default=0),
        )

    enc_db = mk_db("layout_auto", raw=False)
    raw_db = mk_db("layout_raw", raw=True)
    try:
        enc_qs, raw_qs = queries("layout_auto"), queries("layout_raw")

        def run_raw(sql: str):
            os.environ["HORAEDB_CACHE_LAYOUT"] = "raw"
            try:
                return raw_db.execute(sql)
            finally:
                os.environ.pop("HORAEDB_CACHE_LAYOUT", None)

        sweep = []
        total_enc = total_raw = 0.0
        for (label, enc_sql), (_, raw_sql) in zip(enc_qs, raw_qs):
            for _ in range(2):  # candidate -> build, then a warm hit
                enc_db.execute(enc_sql)
                run_raw(raw_sql)
            best_e = best_r = np.inf
            e_rows = r_rows = None
            path = ""
            for _ in range(LAYOUT_REPEATS):
                s = time.perf_counter()
                out = enc_db.execute(enc_sql)
                dt = time.perf_counter() - s
                if dt < best_e:
                    best_e, e_rows = dt, out.to_pylist()
                    path = enc_db.interpreters.executor.last_path
                s = time.perf_counter()
                out = run_raw(raw_sql)
                dt = time.perf_counter() - s
                if dt < best_r:
                    best_r, r_rows = dt, out.to_pylist()
            if e_rows != r_rows:
                return {"metric": "layout_error", "value": 0,
                        "unit": f"encoded/raw mismatch at {label}",
                        "vs_baseline": 0, "platform": platform}
            total_enc += best_e
            total_raw += best_r
            sweep.append({
                "shape": label, "served": path,
                "encoded_ms": round(best_e * 1e3, 2),
                "raw_ms": round(best_r * 1e3, 2),
            })

        enc_bytes, enc_logical = column_bytes(enc_db, "layout_auto")
        raw_bytes, raw_logical = column_bytes(raw_db, "layout_raw")
        if not enc_bytes or not raw_bytes:
            return {"metric": "layout_error", "value": 0,
                    "unit": "no resident column bytes in "
                    "system.public.device", "vs_baseline": 0,
                    "platform": platform}
        # same logical rows on both arms -> rows-per-HBM-byte ratio is
        # exactly the byte compression ratio
        ratio = raw_bytes / enc_bytes
        never_worse = all(
            e["encoded_ms"] <= e["raw_ms"] * 1.10 + 2.0 for e in sweep
        )
        suffix = "" if platform == "tpu" else "_CPU-FALLBACK"
        return {
            "metric": f"layout_rows_per_hbm_byte{suffix}",
            "value": round(enc_logical / enc_bytes, 5),
            "unit": "rows/byte",
            "vs_baseline": round(ratio, 3),
            "baseline": "HORAEDB_CACHE_LAYOUT=raw",
            "compression_ratio": round(ratio, 3),
            "compression_4x_ok": bool(ratio >= 4.0),
            "never_worse": never_worse,
            "encoded_bytes": enc_bytes,
            "raw_bytes": raw_bytes,
            "logical_rows": enc_logical,
            "sweep": sweep,
            "platform": platform,
        }
    finally:
        os.environ.pop("HORAEDB_CACHE_LAYOUT", None)
        enc_db.close()
        raw_db.close()


def _emit(obj: dict) -> None:
    print(json.dumps(obj))


# All-configs order: headline (tsbs-5-8-1) LAST — the driver parses the
# final stdout line, and every config still gets its own line.
ALL_CONFIGS = (
    "readme", "tsbs-1-1-1", "double-groupby-all", "high-cpu-all",
    "compaction-64", "ingest", "groupby", "rawscan", "rollup", "flood",
    "devicetel", "decisions", "profile", "livewindow", "layout",
    "tsbs-5-8-1",
)
# 2400s: the 100M-row compaction config (BASELINE blueprint scale)
# builds the table twice for the device/host A-B and genuinely needs
# ~20 min of 1-core wall; the query configs finish far inside it.
PER_CONFIG_TIMEOUT = int(os.environ.get("BENCH_TIMEOUT", "2400"))
# Total wall budget for an all-configs run (0 = unbounded). When the
# budget can no longer fit a stage, the stage is SKIPPED with an explicit
# emitted line and listed in the final record's `stages_skipped` — a
# truncated run must say what it didn't measure, never silently omit it.
# The DEFAULT is bounded: an unbudgeted all-configs run that outlives the
# caller's own timeout gets killed mid-stage (rc 124) with the headline
# line never emitted — exactly the silent truncation the skip protocol
# exists to prevent. The old 5400s default still lost that race: the
# budget must fit INSIDE the strictest caller window, not merely exist.
# 1200s does —
# stages that don't fit skip explicitly and the final record's
# stages_skipped says so. Export BENCH_WALL_BUDGET=0 for an explicitly
# unbounded run.
WALL_BUDGET = float(os.environ.get("BENCH_WALL_BUDGET", "1200") or 0)
# Wall held back from non-headline stages so the headline config (the
# line the driver parses) always gets a real attempt instead of the
# STAGE_FLOOR crumbs left after a slow middle stage.
HEADLINE_RESERVE = float(os.environ.get("BENCH_HEADLINE_RESERVE", "240"))
# A stage that can't get at least this much wall isn't worth starting —
# it would only burn the remaining budget into a timeout line.
STAGE_FLOOR = float(os.environ.get("BENCH_STAGE_FLOOR", "60"))

def run_all() -> None:
    """Run every BASELINE config, one subprocess + one JSON line each.

    Subprocess isolation means a config that hangs or crashes costs only
    its own line; the rest still report. Emitted lines flush immediately
    so partial progress survives a driver kill. This parent never touches
    JAX: a chip belongs to one process at a time, and each config child
    needs it. Children run on the backend JAX finds (``BENCH_FORCE_CPU=1``
    in the environment makes them explicit CPU runs, labeled
    ``_CPU-FALLBACK`` in the metric NAME)."""
    import subprocess

    t_run = time.monotonic()
    stages_skipped: list[str] = []

    def remaining() -> float:
        if WALL_BUDGET <= 0:
            return float("inf")
        return WALL_BUDGET - (time.monotonic() - t_run)

    def _run_one(
        config: str, timeout: float | None = None
    ) -> tuple[str, dict | None]:
        env = dict(os.environ)
        env["BENCH_CONFIG"] = config
        line = None
        try:
            p = subprocess.run(
                [sys.executable, os.path.abspath(__file__)],
                env=env,
                capture_output=True,
                timeout=min(timeout or PER_CONFIG_TIMEOUT, PER_CONFIG_TIMEOUT),
                text=True,
            )
            for ln in reversed(p.stdout.strip().splitlines()):
                if ln.startswith("{"):
                    line = ln
                    break
        except subprocess.TimeoutExpired:
            pass
        if line is None:
            return json.dumps({
                "metric": f"{config}_error", "value": 0,
                "unit": "timeout or no output", "vs_baseline": 0,
                "platform": "unknown",
            }), None
        try:
            return line, json.loads(line)
        except json.JSONDecodeError:
            return line, None

    results: dict[str, str] = {}
    last_printed = None
    headline = ALL_CONFIGS[-1]
    for config in ALL_CONFIGS:
        budget_s = remaining()
        if config != headline and WALL_BUDGET > 0:
            # Non-headline stages spend only what the headline reserve
            # leaves over — the driver parses the FINAL line, so the
            # headline must always get a real attempt.
            budget_s = max(0.0, budget_s - HEADLINE_RESERVE)
        if config != headline and budget_s < STAGE_FLOOR:
            # Wall budget exhausted: skip the stage EXPLICITLY (own line
            # + listed in the headline's stages_skipped) and save what's
            # left for the headline config.
            stages_skipped.append(config)
            line = json.dumps({
                "metric": f"{config}_skipped", "value": 0,
                "unit": "wall budget exhausted before stage", "vs_baseline": 0,
                "platform": "none",
            })
            results[config] = line
            print(line)
            last_printed = line
            sys.stdout.flush()
            continue
        line, parsed = _run_one(config, timeout=max(budget_s, STAGE_FLOOR))
        hung = parsed is None or parsed.get("unit") == "timeout or no output"
        if hung and budget_s < PER_CONFIG_TIMEOUT:
            # The stage was cut short by the RUN budget, not its own
            # timeout — account it as skipped, not merely errored.
            stages_skipped.append(config)
        results[config] = line
        print(line)
        last_printed = line
        sys.stdout.flush()

    # Headline config's line must be LAST on stdout (the driver parses
    # the final line), and a budget-truncated run must carry the explicit
    # skipped list — stages_skipped rides on the headline record (always
    # present, [] when everything ran).
    try:
        hrec = json.loads(results[headline])
        if not isinstance(hrec, dict):
            raise ValueError(type(hrec).__name__)
    except (json.JSONDecodeError, ValueError):
        hrec = {
            "metric": f"{headline}_error", "value": 0,
            "unit": "no parseable headline line", "vs_baseline": 0,
            "platform": "unknown",
        }
    hrec["stages_skipped"] = stages_skipped
    final_line = json.dumps(hrec)
    if last_printed != final_line:
        print(final_line)
        sys.stdout.flush()


def run_follower_config() -> dict:
    """Replicated follower reads: 1 meta (--read-replicas 2) + 3 data
    nodes over one shared store (real processes), a hot table flushed and
    replicated to both followers, then an interleaved A/B read storm:

    - LEADER-ONLY arm: every request hits the shard leader (the
      pre-replica serving model — one node answers the hot table);
    - FOLLOWER arm: requests round-robin across all three nodes; the
      followers serve the watermark-covered dashboard query locally
      (route=follower), only the leader's share runs on the leader.

    Gates carried in the emitted record: result agreement between
    leader-served and follower-served reps (`agreement`), an impl-aware
    check that the follower arm really served route=follower on BOTH
    followers (`follower_served`), and a never-worse latency check on a
    leader-only shape — the fresh open-tail query, which both arms must
    serve from the leader (`tail_never_worse`, ratio with 1.5x noise
    headroom: subprocess HTTP on a loaded host jitters).

    ``value`` is the follower arm's aggregate qps; ``vs_baseline`` the
    qps ratio over the leader-only arm. NB on a single-core host the
    three node processes share one CPU, so the ratio measures protocol/
    queueing relief only — the `cores` field labels that honestly (the
    >=2x scale-out claim needs >=3 cores to be physically possible)."""
    import json as _json
    import os
    import shutil
    import socket
    import subprocess
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    duration_s = float(os.environ.get("BENCH_FOLLOWER_SECS", "4"))
    workers = int(os.environ.get("BENCH_FOLLOWER_WORKERS", "6"))
    # large enough that the per-query serving WORK (scan+group-by over
    # the hot table) dominates the HTTP round-trip — the quantity that
    # actually scales out when followers serve; a tiny table would
    # benchmark socket overhead instead
    n_rows = int(os.environ.get("BENCH_FOLLOWER_ROWS", "120000"))
    passes = int(os.environ.get("BENCH_FOLLOWER_PASSES", "2"))

    def free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def http(method, url, payload=None, timeout=15.0, headers=None):
        data = _json.dumps(payload).encode() if payload is not None else None
        req = urllib.request.Request(
            url, data=data,
            headers={"Content-Type": "application/json", **(headers or {})},
            method=method,
        )
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                return resp.status, _json.loads(resp.read().decode() or "{}")
        except urllib.error.HTTPError as e:
            try:
                return e.code, _json.loads(e.read().decode() or "{}")
            except Exception:
                return e.code, {}

    def sql(port, query, timeout=15.0):
        return http(
            "POST", f"http://127.0.0.1:{port}/sql", {"query": query},
            timeout=timeout,
        )

    def wait_until(fn, timeout=90.0, interval=0.2, desc="condition"):
        deadline = time.monotonic() + timeout
        last = None
        while time.monotonic() < deadline:
            try:
                last = fn()
                if last:
                    return last
            except Exception as e:
                last = e
            time.sleep(interval)
        raise TimeoutError(f"timed out waiting for {desc}: last={last}")

    tmp = tempfile.mkdtemp(prefix="bench_follower_")
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": os.path.dirname(os.path.abspath(__file__)),
    }
    meta_port = free_port()
    node_ports = [free_port() for _ in range(3)]
    procs = []
    try:
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "horaedb_tpu.meta",
             "--port", str(meta_port),
             "--data-dir", f"{tmp}/meta",
             "--num-shards", "3",
             "--read-replicas", "2",
             "--lease-ttl", "2.0",
             "--heartbeat-timeout", "3.0",
             "--tick-interval", "0.25"],
            env=env,
            stdout=open(f"{tmp}/meta.log", "wb"), stderr=subprocess.STDOUT,
        ))
        for i, port in enumerate(node_ports):
            cfg = f"{tmp}/node{i}.toml"
            with open(cfg, "w") as f:
                f.write(
                    f"[server]\nhost = \"127.0.0.1\"\nhttp_port = {port}\n\n"
                    f"[engine]\ndata_dir = \"{tmp}/store\"\n\n"
                    f"[cluster]\nself_endpoint = \"127.0.0.1:{port}\"\n"
                    f"meta_endpoints = [\"127.0.0.1:{meta_port}\"]\n"
                )
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "horaedb_tpu.server", "--config", cfg],
                env=env,
                stdout=open(f"{tmp}/node{i}.log", "wb"),
                stderr=subprocess.STDOUT,
            ))
        for port in (meta_port, *node_ports):
            wait_until(
                lambda p=port: http(
                    "GET", f"http://127.0.0.1:{p}/health", timeout=2
                )[0] == 200,
                desc=f"port {port} health",
            )

        def shards_assigned():
            s, body = http(
                "GET", f"http://127.0.0.1:{meta_port}/meta/v1/shards",
                timeout=2,
            )
            if s == 200 and body.get("shards") and all(
                sh["node"] for sh in body["shards"]
            ):
                return True
            return None

        wait_until(shards_assigned, desc="shards assigned")
        ddl = ("CREATE TABLE hot (host string TAG, v double, ts timestamp "
               "NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic "
               "WITH (segment_duration='2h')")
        status, out = sql(node_ports[0], ddl)
        assert status == 200, out
        _, route = http(
            "GET", f"http://127.0.0.1:{meta_port}/meta/v1/route/hot"
        )
        leader_port = int(route["node"].rsplit(":", 1)[1])
        follower_ports = [p for p in node_ports if p != leader_port]

        now_ms = int(time.time() * 1000)
        rng = np.random.default_rng(42)
        hosts = rng.integers(0, 16, n_rows)
        vals = rng.normal(10.0, 3.0, n_rows)
        tss = now_ms - 3_600_000 + rng.permutation(n_rows)
        for lo in range(0, n_rows, 2000):
            batch = [
                {"host": f"h{hosts[i]}", "v": float(vals[i]),
                 "ts": int(tss[i])}
                for i in range(lo, min(lo + 2000, n_rows))
            ]
            status, out = http(
                "POST", f"http://127.0.0.1:{leader_port}/write",
                {"table": "hot", "rows": batch}, timeout=60,
            )
            assert status == 200, out
        status, out = http(
            "POST", f"http://127.0.0.1:{leader_port}/admin/flush?table=hot",
            timeout=60,
        )
        assert status == 200, out
        wm = int(tss.max()) + 1

        def both_followers_ready():
            for p in follower_ports:
                s, out = http(
                    "GET", f"http://127.0.0.1:{p}/debug/shards", timeout=2
                )
                if s != 200:
                    return None
                reps = [
                    sh for sh in out.get("shards", [])
                    if sh.get("role") == "replica"
                    and (sh.get("watermarks_ms") or {}).get("hot", 0) >= wm
                ]
                if not reps:
                    return None
            return True

        wait_until(both_followers_ready, desc="followers replicated")

        # VARIED dashboard queries (per-host panels over shifting
        # windows): identical texts would coalesce in the single-flight
        # dedup and benchmark the dedup instead of the serving path
        variants = []
        for h in range(16):
            for k in range(4):
                q = (f"SELECT count(v) AS c, sum(v) AS s FROM hot WHERE "
                     f"ts <= {wm - 1 - k} AND host = 'h{h}'")
                s, ref = sql(leader_port, q, timeout=60)
                assert s == 200, ref
                variants.append((q, ref["rows"]))
        tail_q = "SELECT count(v) AS c FROM hot"

        def storm(ports, secs) -> tuple[float, int, int, int]:
            stop = time.monotonic() + secs
            served = [0]
            mismatches = [0]
            errors = [0]
            lock = threading.Lock()

            def worker(wid):
                i = wid
                while time.monotonic() < stop:
                    port = ports[i % len(ports)]
                    q, ref_rows = variants[(i * 7 + wid) % len(variants)]
                    i += 1
                    try:
                        s, out = sql(port, q, timeout=30)
                    except Exception:
                        with lock:
                            errors[0] += 1
                        continue
                    with lock:
                        if s != 200:
                            errors[0] += 1
                        elif not _rows_agree(out.get("rows", []), ref_rows):
                            mismatches[0] += 1
                        else:
                            served[0] += 1

            t0 = time.perf_counter()
            threads = [
                threading.Thread(target=worker, args=(w,))
                for w in range(workers)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - t0
            return served[0] / elapsed, mismatches[0], errors[0], served[0]

        # warmup (compile + cache both paths everywhere)
        storm(node_ports, 1.0)
        storm([leader_port], 1.0)

        leader_qps, follower_qps = [], []
        mismatch_total = error_total = 0
        for _ in range(passes):
            q, m, e, _n = storm([leader_port], duration_s)
            leader_qps.append(q)
            mismatch_total += m
            error_total += e
            q, m, e, _n = storm(node_ports, duration_s)
            follower_qps.append(q)
            mismatch_total += m
            error_total += e

        # impl-aware: BOTH followers must have served route=follower
        follower_served = True
        for p in follower_ports:
            s, qs = http(
                "GET", f"http://127.0.0.1:{p}/debug/query_stats", timeout=5
            )
            if s != 200 or not any(
                row.get("route") == "follower"
                for row in qs.get("queries", [])
            ):
                follower_served = False

        # leader-only shape (fresh open tail): both arms serve it from
        # the leader — the follower arm must not make it worse
        def min_latency(port, q, n=5):
            best = float("inf")
            for _ in range(n):
                t0 = time.perf_counter()
                s, _out = sql(port, q, timeout=30)
                if s == 200:
                    best = min(best, time.perf_counter() - t0)
            return best

        # the follower path costs the fresh shape exactly one local
        # staleness refusal + the forward hop any non-owner pays; the
        # gate bounds that overhead (1.5x + one 10ms hop allowance)
        # rather than pretending the hop is free
        tail_leader = min_latency(leader_port, tail_q)
        tail_via_follower = min_latency(follower_ports[0], tail_q)
        tail_never_worse = tail_via_follower <= tail_leader * 1.5 + 0.010

        best_leader = max(leader_qps)
        best_follower = max(follower_qps)
        # Honesty label (same convention as _CPU-FALLBACK): three node
        # processes on fewer than 3 cores CANNOT express aggregate
        # scale-out — the arms are work-conserving and the ratio measures
        # scheduling overhead, not the serving architecture. The >=2x
        # scaling claim is only meaningful un-suffixed.
        cores = os.cpu_count() or 1
        suffix = "" if cores >= 3 else f"_{cores}CORE-HOST"
        return {
            "metric": f"follower_agg_qps{suffix}",
            "value": round(best_follower, 1),
            "unit": "queries/s (3-node round-robin, hot-table read storm)",
            "vs_baseline": round(best_follower / best_leader, 3)
            if best_leader else 0,
            "leader_only_qps": round(best_leader, 1),
            "agreement": mismatch_total == 0,
            "errors": error_total,
            "follower_served": follower_served,
            "tail_never_worse": tail_never_worse,
            "tail_leader_ms": round(tail_leader * 1e3, 2),
            "tail_via_follower_ms": round(tail_via_follower * 1e3, 2),
            "cores": cores,
            "rows": n_rows,
            "platform": "cpu-subprocess",
        }
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                p.kill()
        shutil.rmtree(tmp, ignore_errors=True)


def run_tenantsim_config() -> dict:
    """Tenant-scale scenario torture (ROADMAP item 5): the multi-tenant
    production simulator (horaedb_tpu/tools/tenantsim) at moderate scale
    — a real in-process 1-meta+3-node cluster, 100 tenants, the full
    fault schedule (storm, latency burst, error burst, leader kill) —
    with the acceptance gates read from the DATABASE'S OWN tables:
    system.public.slo verdicts (cheap p99 never burned), zero wrong
    answers, a gapless accounted event journal, an alert firing AND
    resolving on the injected store faults, and acked-write readback
    through the kill. ``value`` is the sustained query throughput under
    torture; the gates ride in the record (a fast-but-wrong run must
    never look like a success)."""
    import os

    from horaedb_tpu.tools.tenantsim import SimConfig, run_sim

    cfg = SimConfig(
        nodes=3,
        tenants=int(os.environ.get("BENCH_TENANTSIM_TENANTS", "100")),
        tables=3,
        duration_s=float(os.environ.get("BENCH_TENANTSIM_SECS", "30")),
        workers=6,
        ingest_workers=2,
        rows_per_table=int(os.environ.get("BENCH_TENANTSIM_ROWS", "15000")),
        read_replicas=1,
        lease_flap_at=0.72,
        shard_move_at=0.8,
        settle_timeout_s=35.0,
    )
    report = run_sim(cfg)
    violations = report.violations()
    return {
        "metric": "tenantsim_served_qps",
        "value": report.qps,
        "unit": "queries/s served under the full fault schedule",
        "vs_baseline": None,
        "gates_passed": not violations,
        "violations": violations,
        "wrong_answers": report.wrong_answers,
        "served": report.served,
        "ingest_acked_rows": report.ingest_acked_rows,
        "shed": report.shed,
        "quota_rejected": report.quota_rejected,
        "alerts_cycled": bool(
            report.alerts_fired and report.alerts_resolved
        ),
        "slo_burn_recover": (
            "store_faults" in report.slo_burned_objectives
            and "store_faults" in report.slo_recovered_objectives
        ),
        "event_seq_gaps": report.event_seq_gaps,
        "killed_node": report.killed_node,
        "kill_recovered": report.kill_recovered,
        "follower_served": report.follower_served,
        "tenants": cfg.tenants,
        "platform": "cpu-inprocess",
    }


def run_config(config: str) -> dict:
    """Build + run one config against the CURRENT jax backend; returns the
    result dict (never raises for result-shape problems — errors come back
    as labeled `_error` records so callers always have a line to emit)."""
    import jax

    if config == "tenantsim":
        return run_tenantsim_config()
    if config == "follower":
        return run_follower_config()
    if config == "compaction-64":
        return run_compaction_config()
    if config == "ingest":
        return run_ingest_config()
    if config == "selfscrape":
        return run_selfscrape_config()
    if config == "devicetel":
        return run_devicetel_config()
    if config == "groupby":
        return run_groupby_config()
    if config == "rawscan":
        return run_rawscan_config()
    if config == "flood":
        return run_flood_config()
    if config == "decisions":
        return run_decisions_config()
    if config == "profile":
        return run_profile_config()
    if config == "rollup":
        return run_rollup_config()
    if config == "livewindow":
        return run_livewindow_config()
    if config == "layout":
        return run_layout_config()
    builder = CONFIGS.get(config)
    if builder is None:
        return {"metric": f"{config}_error", "value": 0,
                "unit": f"unknown config {config}", "vs_baseline": 0,
                "platform": "none"}
    platform = jax.devices()[0].platform
    db, sql, n_rows, arrow_fn = builder()

    dev_s, dev_rows, dev_path = time_query(db, sql)
    assert dev_path in (
        "device-cached", "device-dist", "device", "device-partial", "host",
    ), dev_path

    # Baseline: force the host (vectorized numpy) executor — disable both
    # the device path and the device-resident cache.
    ex = db.interpreters.executor
    orig_cap, orig_cached = ex._device_capable, ex._try_cached_agg
    ex._device_capable = lambda plan, rows: False
    ex._try_cached_agg = lambda plan, table, m: None
    host_s, host_rows, _ = time_query(db, sql)
    ex._device_capable = orig_cap
    ex._try_cached_agg = orig_cached

    # Both paths must agree numerically (a fast-but-wrong kernel must not
    # benchmark as a success).
    if not _rows_agree(dev_rows, host_rows):
        return {"metric": f"{config}_error", "value": 0,
                "unit": "path mismatch", "vs_baseline": 0,
                "platform": platform}

    # External anchor: pyarrow Acero over the same parquet SSTs (the
    # runnable stand-in for the reference's DataFusion executor). A
    # result mismatch zeroes the ratio rather than erroring the config —
    # the anchor must never take down the primary metric.
    table_name = "demo" if config == "readme" else "cpu"
    try:
        arrow_s, arrow_rows = time_arrow(db, table_name, arrow_fn)
        vs_arrow = (
            round(arrow_s / dev_s, 3)
            if _rows_agree(dev_rows, arrow_rows) else 0
        )
    except Exception:
        arrow_s, vs_arrow = None, None

    # Honesty label: the bench targets the TPU; any run that ended up on
    # XLA-CPU carries the fallback in the metric NAME so it can never be
    # mistaken for a chip number (VERDICT r3 item 1).
    suffix = "" if platform == "tpu" else "_CPU-FALLBACK"
    return {
        "metric": f"{config}_rows_per_sec_{dev_path}{suffix}",
        "value": round(n_rows / dev_s),
        "unit": "rows/s",
        "vs_baseline": round(host_s / dev_s, 3),
        "vs_arrow": vs_arrow,
        "platform": platform,
    }


def main() -> None:
    config = os.environ.get("BENCH_CONFIG")
    if config is None:
        run_all()
        return

    import jax

    if os.environ.get("BENCH_FORCE_CPU") == "1":
        # the explicit CPU run: run_config labels the metric
        # _CPU-FALLBACK from the platform
        jax.config.update("jax_platforms", "cpu")
    # otherwise: the backend JAX finds, and its error where it finds none
    from horaedb_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    _emit(run_config(config))


if __name__ == "__main__":
    main()  # an exception is a traceback and a non-zero exit
    sys.stdout.flush()
    sys.stderr.flush()
    # XLA's CPU runtime occasionally aborts in its C++ teardown during
    # interpreter shutdown (after all output is produced). The driver
    # checks our exit code, so exit deterministically once the JSON line
    # is flushed.
    os._exit(0)
