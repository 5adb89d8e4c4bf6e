"""The sharded scan-cache entry as the served path uses it (PR 32): every
device holds its share of the valid rows, the sharded program's answers are
the plain reference's, its shapes stay few as a table grows, and a program
the device refuses is an event and another route's answer.

On the CPU's virtual devices (``conftest.py`` gives 8), over a mesh of the
first 2, 4 or 8 of them. The reference is a plain numpy group-by in float64
(``np.add.at``) over the rows as they were written."""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

import horaedb_tpu
from horaedb_tpu.common_types import RowGroup
from horaedb_tpu.common_types.schema import compute_tsid
from horaedb_tpu.ops.encoding import FOR_BLOCK
from horaedb_tpu.parallel import mesh as mesh_mod
from horaedb_tpu.parallel.mesh import ShardLayout, shard_bucket
from horaedb_tpu.utils.metrics import REGISTRY

DDL = (
    "CREATE TABLE t (host string TAG, v double, w double, ts timestamp KEY) "
    "WITH (segment_duration='2h')"
)
HOUR = 3_600_000
STEP = 10_000
HOSTS = 97
# far from a power of two, and one past one
SIZES = (300_000, 2**18 + 1)
DEVICES = (2, 4, 8)
GAUGE = "horaedb_scan_cache_shard_rows"


def make_rows(n: int, seed: int, t0: int = 0, hosts: int = HOSTS) -> dict:
    """``n`` rows over ``hosts`` series of random lengths, one point per
    10 s from ``t0``; values exactly representable in f32."""
    rng = np.random.default_rng(seed)
    counts = rng.multinomial(n, np.full(hosts, 1.0 / hosts))
    host_id = np.repeat(np.arange(hosts), counts)
    ts = t0 + STEP * np.concatenate([np.arange(c) for c in counts])
    order = rng.permutation(n)  # written in no order
    return {
        "host": np.array([f"h{i:03d}" for i in host_id], dtype=object)[order],
        "v": rng.uniform(0, 100, n).astype(np.float32).astype(np.float64)[order],
        "w": rng.uniform(0, 100, n).astype(np.float32).astype(np.float64)[order],
        "ts": ts.astype(np.int64)[order],
    }


def write(conn, rows: dict, name: str = "t") -> None:
    table = conn.catalog.open(name)
    columns = dict(rows)
    columns["tsid"] = compute_tsid([columns["host"]])
    table.write(RowGroup(table.schema, columns))


def reference(rows: dict, where=None) -> dict:
    """{(host, hour): (count, sum v, sum w, min v, max w)} in float64."""
    keep = np.ones(len(rows["ts"]), bool) if where is None else where(rows)
    hosts, host_code = np.unique(rows["host"][keep], return_inverse=True)
    hour = rows["ts"][keep] // HOUR
    n_hours = int(hour.max()) + 1
    seg = host_code * n_hours + hour
    size = len(hosts) * n_hours
    count = np.zeros(size, np.int64)
    sum_v, sum_w = np.zeros(size), np.zeros(size)
    min_v, max_w = np.full(size, np.inf), np.full(size, -np.inf)
    np.add.at(count, seg, 1)
    np.add.at(sum_v, seg, rows["v"][keep])
    np.add.at(sum_w, seg, rows["w"][keep])
    np.minimum.at(min_v, seg, rows["v"][keep])
    np.maximum.at(max_w, seg, rows["w"][keep])
    return {
        (hosts[s // n_hours], int(s % n_hours) * HOUR):
            (int(count[s]), sum_v[s], sum_w[s], min_v[s], max_w[s])
        for s in np.flatnonzero(count)
    }


def hold(out, want: dict, minmax: bool) -> None:
    """Counts, min and max exact; sums to 2e-5 relative (f32 sums of at most
    360 values; a series cut by a block boundary is summed in two parts)."""
    got = {(r["host"], r["hour"]): r for r in out.to_pylist()}
    assert set(got) == set(want)
    for key, (count, sum_v, sum_w, min_v, max_w) in want.items():
        row = got[key]
        assert row["c"] == count
        np.testing.assert_allclose(row["a"] * count, sum_v, rtol=2e-5)
        np.testing.assert_allclose(row["aw"] * count, sum_w, rtol=2e-5)
        if minmax:
            assert row["lo"] == min_v and row["hi"] == max_w


def sql(minmax: bool = False, where: str = "") -> str:
    extra = ", min(v) AS lo, max(w) AS hi" if minmax else ""
    return (
        "SELECT host, time_bucket(ts, '1h') AS hour, count(v) AS c, "
        f"avg(v) AS a, avg(w) AS aw{extra} FROM t {where} "
        "GROUP BY host, time_bucket(ts, '1h')"
    )


def use_mesh(monkeypatch, n_devices: int) -> Mesh:
    """The serving mesh over the first ``n_devices`` local devices."""
    mesh = Mesh(np.array(jax.devices()[:n_devices]), ("shard",))
    monkeypatch.setattr(mesh_mod, "serving_mesh", lambda min_devices=2: mesh)
    monkeypatch.setenv("HORAEDB_ADAPTIVE_PATH", "0")  # device-first
    return mesh


def shard_rows(n_devices: int) -> list:
    return [
        REGISTRY.gauge(GAUGE, labels={"table": "t", "shard": str(i)}).value
        for i in range(n_devices)
    ]


@pytest.fixture(scope="module", params=[(n, d) for n in SIZES for d in DEVICES],
                ids=lambda p: f"{p[0]}rows-{p[1]}dev")
def sharded(request):
    """-> (conn, the rows written, n_devices): a flushed table whose entry is
    built over a mesh of ``n_devices``."""
    n, d = request.param
    with pytest.MonkeyPatch.context() as mp:
        use_mesh(mp, d)
        conn = horaedb_tpu.connect(None)
        conn.execute(DDL)
        rows = make_rows(n, seed=n + d)
        write(conn, rows)
        conn.flush_all()
        conn.execute(sql())  # the candidate; the next statement builds
        yield conn, rows, d
        conn.close()


class TestShardedEntryServes:
    def served(self, sharded, statement: str):
        conn, rows, d = sharded
        out = conn.execute(statement)
        ex = conn.interpreters.executor
        assert ex.last_path == "device-dist", ex.last_metrics
        assert ex.last_metrics["mesh_devices"] == d
        return out, rows

    def test_grouped_full_scan(self, sharded):
        out, rows = self.served(sharded, sql())
        hold(out, reference(rows), minmax=False)

    def test_with_a_numeric_filter(self, sharded):
        out, rows = self.served(sharded, sql(where="WHERE w > 50.0"))
        hold(out, reference(rows, lambda r: r["w"] > 50.0), minmax=False)

    def test_with_min_and_max(self, sharded):
        out, rows = self.served(sharded, sql(minmax=True))
        hold(out, reference(rows), minmax=True)

    def test_every_device_holds_its_share_of_the_valid_rows(self, sharded):
        conn, rows, d = sharded
        self.served(sharded, sql())
        n = len(rows["ts"])
        entry = conn.interpreters.executor.scan_cache._entries["t"]
        held = shard_rows(d)
        assert sum(held) == n == entry.n_valid
        # within one granule of n / d: here within one row
        assert all(abs(h - n / d) < 1 for h in held), held
        assert entry.shards.valid_rows.tolist() == held
        assert entry.padded_rows == d * shard_bucket(-(-n // d))
        for dev in (entry.series_codes_dev, entry.ts_rel_dev,
                    *entry.value_cols_dev.values()):
            assert dev.shape == (entry.padded_rows,)
            assert {s.data.shape for s in dev.addressable_shards} == {
                (entry.shards.shard_len,)
            }
        # the device layout maps back onto the host's rows
        codes = np.asarray(entry.series_codes_dev)
        device_rows = np.flatnonzero(codes != entry.n_series)
        assert np.array_equal(
            entry.shards.host_rows(device_rows), np.arange(n)
        )
        assert np.array_equal(
            np.asarray(entry.ts_rel_dev)[device_rows], entry.ts_rel_host
        )

    def test_rows_written_after_the_build_fold_in(self, sharded):
        """Last in the class: it writes to the shared table."""
        conn, rows, d = sharded
        self.served(sharded, sql())
        late = make_rows(500, seed=7, t0=int(rows["ts"].max()) + STEP, hosts=5)
        write(conn, late)
        out, _ = self.served(sharded, sql(minmax=True))
        ex = conn.interpreters.executor
        assert ex.last_metrics["cache"] == "hit+delta", ex.last_metrics
        both = {k: np.concatenate([rows[k], late[k]]) for k in rows}
        hold(out, reference(both), minmax=True)


class TestShardLayout:
    @pytest.mark.parametrize("d", DEVICES)
    @pytest.mark.parametrize("n", (*SIZES, 17_280_000, 4_320_000, 1, 7))
    def test_blocks_are_equal_and_map_back(self, n, d):
        lay = ShardLayout.of(n, d)
        valid = lay.valid_rows
        assert valid.sum() == n and valid.max() - valid.min() <= 1
        assert lay.shard_len >= valid.max() and lay.padded_rows == d * lay.shard_len
        if n <= 2**19:
            host = np.arange(n, dtype=np.int32)
            placed = lay.place(host, fill=-1)
            assert len(placed) == lay.padded_rows
            device_rows = np.flatnonzero(placed >= 0)
            assert np.array_equal(lay.host_rows(device_rows), host)
            assert np.array_equal(placed[device_rows], host)

    def test_bucket_is_whole_chunks_and_few_shapes(self):
        from horaedb_tpu.ops.scan_agg import scatter_chunk_rows

        chunk = scatter_chunk_rows(10)
        assert shard_bucket(4_320_000) == 66 * chunk  # the four-chip cell
        assert shard_bucket(chunk) == chunk  # up to a chunk: the power of two
        assert shard_bucket(37_500) == 65_536
        for rows in (chunk + 1, 10**6, 4_320_000, 2**23 + 1, 10**8):
            bucket = shard_bucket(rows)
            assert bucket >= rows and bucket % chunk == 0
            assert bucket - rows < max(chunk, rows / 32)
        # at most 32 shapes while a block doubles
        shapes = {shard_bucket(r) for r in range(2**22 + 1, 2**23 + 1, 4099)}
        assert len(shapes) <= 32


def test_a_growing_table_compiles_a_bounded_number_of_shapes(monkeypatch):
    """Ten flushes of different sizes, a rebuild and a serve after each: the
    per-device length steps by granules, so the sharded program is compiled
    for two lengths at most."""
    from horaedb_tpu.utils import querystats
    from horaedb_tpu.utils.events import EVENT_STORE

    use_mesh(monkeypatch, 4)
    # a shape another test has dispatched would not count as a compile
    monkeypatch.setattr(querystats, "_seen_kernel_keys", set())
    conn = horaedb_tpu.connect(None)
    try:
        conn.execute(DDL)
        rows = make_rows(270_000, seed=1)
        write(conn, rows)
        conn.flush_all()
        statement = sql()
        before = len(EVENT_STORE.list(kind="kernel_compile"))
        lengths, t0 = set(), int(rows["ts"].max()) + STEP
        for i, size in enumerate((1500, 2500, 1000, 3000, 500, 2000, 3500, 700,
                                  1800, 2900)):
            more = make_rows(size, seed=10 + i, t0=t0)
            t0 = int(more["ts"].max()) + STEP
            write(conn, more)
            conn.flush_all()
            rows = {k: np.concatenate([rows[k], more[k]]) for k in rows}
            ex = conn.interpreters.executor
            for _ in range(4):  # the candidate (again after a compaction), the build
                out = conn.execute(statement)
                assert ex.last_path == "device-dist"
                if ex.last_metrics.get("cache") == "build":
                    break
            else:
                pytest.fail(f"flush {i}: the entry was never rebuilt")
            entry = ex.scan_cache._entries["t"]
            assert entry.n_valid == len(rows["ts"])
            lengths.add(entry.shards.shard_len)
        hold(out, reference(rows), minmax=False)
        compiled = [
            e for e in EVENT_STORE.list(kind="kernel_compile")[before:]
            if e["attrs"]["kernel"] == "cached_dist"
        ]
        assert 1 <= len(compiled) <= 2 and len(lengths) <= 2, (lengths, compiled)
    finally:
        conn.close()


class TestRefusedOnTheMesh:
    """The mesh arm's guard: a sharded program the device refuses for memory
    is a typed event and another route's exact answer."""

    REFUSAL = (
        "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
        "memory in memory space hbm. Used 18.28G of 15.75G hbm. Exceeded "
        "hbm capacity by 2.54G.\n\nTotal hbm usage >= 18.80G:"
    )
    SQL = "SELECT host, count(v) AS c, sum(v) AS s FROM t GROUP BY host"

    @pytest.fixture()
    def served(self, monkeypatch):
        from horaedb_tpu.parallel import dist_agg
        from horaedb_tpu.proxy import Proxy
        from horaedb_tpu.query.kernel_choice import KERNEL_ROUTER

        use_mesh(monkeypatch, 4)
        monkeypatch.setenv("HORAEDB_DIST_MIN_ROWS", "1")
        KERNEL_ROUTER.reset()
        real = dist_agg.make_cached_dist_scan_agg
        refuse: set = set()
        calls: list = []

        def make(mesh, spec, *layout):
            calls.append(spec.segment_impl)
            if spec.segment_impl in refuse:
                def step(*args):
                    raise jax.errors.JaxRuntimeError(self.REFUSAL)
                return step
            return real(mesh, spec, *layout)

        monkeypatch.setattr(dist_agg, "make_cached_dist_scan_agg", make)
        conn = horaedb_tpu.connect(None)
        conn.execute(DDL)
        rows = make_rows(4000, seed=3, hosts=20)  # 32 segments: two candidates
        write(conn, rows)
        conn.flush_all()
        proxy = Proxy(conn)
        want = sorted(
            (host, int((rows["host"] == host).sum()),
             float(rows["v"][rows["host"] == host].sum()))
            for host in np.unique(rows["host"])
        )
        yield (lambda: proxy.handle_sql(self.SQL)), refuse, calls, want
        proxy.close()
        conn.close()
        KERNEL_ROUTER.reset()

    @staticmethod
    def events():
        from horaedb_tpu.utils.events import EVENT_STORE

        return EVENT_STORE.list(kind="kernel_refused")

    @staticmethod
    def rows_of(out):
        return sorted((r["host"], r["c"], r["s"]) for r in out.to_pylist())

    def hold(self, out, want):
        got = self.rows_of(out)
        assert [g[:2] for g in got] == [w[:2] for w in want]
        np.testing.assert_allclose(
            [g[2] for g in got], [w[2] for w in want], rtol=2e-5
        )

    def test_refused_impl_is_an_event_and_the_next_candidate_serves(self, served):
        run, refuse, calls, want = served
        counter = 'horaedb_events_total{kind="kernel_refused"}'
        counted = lambda: float(  # noqa: E731
            [ln for ln in REGISTRY.expose().splitlines()
             if ln.startswith(counter + " ")][0].rpartition(" ")[2]
        )
        before, counted_before, seen = len(self.events()), counted(), []
        refuse.add("scatter")  # the CPU's seed for 32 segments
        for _ in range(5):
            out = run()
            self.hold(out, want)
            seen.append((out.metrics["path"], out.metrics.get("kernel")))
        assert seen[-1] == ("device-dist", "mxu"), seen
        events = self.events()[before:]
        assert len(events) == 1 and counted() == counted_before + 1, events
        attrs = events[0]["attrs"]
        assert attrs["kernel"] == "cached_dist" and attrs["impl"] == "scatter"
        assert attrs["message"] == self.REFUSAL.splitlines()[0]
        assert calls.count("scatter") == 1  # never offered for the shape again

    def test_every_impl_refused_is_the_hosts_exact_answer(self, served):
        run, refuse, calls, want = served
        before = len(self.events())
        refuse.update(("scatter", "mxu"))
        for _ in range(4):
            out = run()
            self.hold(out, want)
        assert out.metrics["path"] == "host", out.metrics
        assert "mesh_devices" not in out.metrics
        assert {e["attrs"]["impl"] for e in self.events()[before:]} == {
            "scatter", "mxu"
        }
        assert sorted(calls) == ["mxu", "scatter"]


def test_the_mesh_arms_spans_and_counters(monkeypatch):
    """``dispatch`` carries program, mesh_devices, shard_rows and chunks;
    ``cache_build`` the devices and the rows per shard; the combine counter
    is exported from start-up and moves by the static bytes a dispatch."""
    from horaedb_tpu.proxy import Proxy
    from horaedb_tpu.utils.tracectx import TRACE_STORE

    counter = "horaedb_dist_combine_bytes_total"

    def combined() -> float:
        return float([ln for ln in REGISTRY.expose().splitlines()
                      if ln.startswith(counter + " ")][0].rpartition(" ")[2])

    assert combined() >= 0  # exported whether or not anything was sharded
    use_mesh(monkeypatch, 4)
    monkeypatch.setenv("HORAEDB_DIST_MIN_ROWS", "1")
    conn = horaedb_tpu.connect(None)
    proxy = Proxy(conn)
    try:
        conn.execute(DDL)
        write(conn, make_rows(4001, seed=5, hosts=20))
        conn.flush_all()
        statement = (
            "SELECT host, time_bucket(ts, '1h') AS hour, avg(v) AS a, avg(w) "
            "AS aw FROM t GROUP BY host, time_bucket(ts, '1h')"
        )

        def spans_of_last() -> dict:
            root = TRACE_STORE.get(TRACE_STORE.list()[0]["trace_id"])["root"]
            found = {}

            def walk(node):
                found[node["name"]] = node.get("attrs", {})
                for child in node.get("children", []):
                    walk(child)

            walk(root)
            return found

        proxy.handle_sql(statement)  # the candidate
        proxy.handle_sql(statement)  # builds
        build = spans_of_last()["cache_build"]
        assert build["mesh_devices"] == 4
        assert build["shard_rows"] == [1001, 1000, 1000, 1000]
        before = combined()
        out = proxy.handle_sql(statement)
        spans = spans_of_last()
        dispatch = spans["dispatch"]
        assert dispatch["kernel"] == "cached_dist"
        assert dispatch["program"] == "cached_dist_" + dispatch["impl"]
        assert dispatch["mesh_devices"] == 4
        assert dispatch["shard_rows"] == [1001, 1000]
        assert dispatch["chunks"] == 1
        # 32 groups (20 padded) x 1 hour: int32 counts + two f32 sums each
        assert out.num_rows == 20
        assert combined() - before == 4 * 32 * 3
    finally:
        proxy.close()
        conn.close()


# ---- the per-series tables read through 128-row blocks ----------------------

BLOCK_LOOKUPS = "horaedb_scan_block_lookups_total"
WHERE = {
    "all": ("", None),
    "tag": ("WHERE host IN ('h003', 'h011', 'h017')",
            lambda r: np.isin(r["host"], ["h003", "h011", "h017"])),
    "time": ("WHERE ts >= 1800000 AND ts < 7200000",
             lambda r: (r["ts"] >= 1_800_000) & (r["ts"] < 7_200_000)),
    "tag-and-time": ("WHERE host IN ('h003', 'h011', 'h017') AND ts >= 1800000 "
                     "AND ts < 7200000",
                     lambda r: np.isin(r["host"], ["h003", "h011", "h017"])
                     & (r["ts"] >= 1_800_000) & (r["ts"] < 7_200_000)),
}


def block_lookups() -> float:
    return float([ln for ln in REGISTRY.expose().splitlines()
                  if ln.startswith(BLOCK_LOOKUPS + " ")][0].rpartition(" ")[2])


def by_row(out) -> list:
    return sorted(out.to_pylist(), key=lambda r: (r["host"], r["hour"]))


@pytest.fixture(scope="class")
def by_block():
    """-> (conn, rows): 20 series of about 1000 rows on four devices, 5001 or
    5000 valid rows a device (no shard is whole 128-row blocks), the entry
    built. Class-scoped: its mesh and threshold end with the class."""
    with pytest.MonkeyPatch.context() as mp:
        use_mesh(mp, 4)
        mp.setenv("HORAEDB_DIST_MIN_ROWS", "1")
        conn = horaedb_tpu.connect(None)
        conn.execute(DDL)
        rows = make_rows(20_003, seed=38, hosts=20)
        write(conn, rows)
        conn.flush_all()
        for _ in range(2):  # the candidate, the build
            conn.execute(sql())
        yield conn, rows
        conn.close()


class TestTablesReadByBlock:
    """A sharded entry whose 128-row blocks span few series reads the allow
    list and the series -> group map through each block's candidates
    (``series_block_width``), not one row at a time: the same rows reach the
    same segments, so the answers are the per-row program's bit for bit."""

    @staticmethod
    def served(conn, statement: str):
        out = conn.execute(statement)
        ex = conn.interpreters.executor
        assert ex.last_path == "device-dist", ex.last_metrics
        return out, ex.scan_cache._entries["t"]

    @pytest.mark.parametrize("where", sorted(WHERE))
    def test_by_block_is_the_per_row_answer_bit_for_bit(self, by_block, where,
                                                        monkeypatch):
        conn, rows = by_block
        clause, keep = WHERE[where]
        statement = sql(minmax=True, where=clause)
        out, entry = self.served(conn, statement)
        assert entry.series_block_width == 1
        hold(out, reference(rows, keep), minmax=True)
        monkeypatch.setattr(entry, "series_block_width", None)  # per row
        per_row, _ = self.served(conn, statement)
        assert by_row(per_row) == by_row(out)

    def test_pad_rows_count_nowhere(self, by_block):
        """Each device's last block holds its last valid rows and pad rows
        (the pad series' code, the largest); on the first devices that code
        lies past the width from the block's least code and is read as the
        pad series all the same."""
        conn, rows = by_block
        out, entry = self.served(conn, sql(minmax=True))
        valid, n_len = entry.shards.valid_rows, entry.shards.shard_len
        assert valid.tolist() == [5001, 5001, 5001, 5000]
        assert all(v % FOR_BLOCK for v in valid)
        codes = np.asarray(entry.series_codes_dev).reshape(4, n_len)
        past = [
            entry.n_series - int(c[v // FOR_BLOCK * FOR_BLOCK])
            >= 1 << entry.series_block_width
            for c, v in zip(codes, valid)
        ]
        assert any(past) and not all(past), past
        hold(out, reference(rows), minmax=True)
        assert sum(r["c"] for r in out.to_pylist()) == len(rows["ts"])

    def test_each_dispatch_by_block_counts_once(self, by_block):
        conn, _ = by_block
        before = block_lookups()
        for _ in range(3):
            self.served(conn, sql())
        assert block_lookups() - before == 3

    def test_one_row_series_fall_back_to_the_row_lookup(self, monkeypatch):
        """A block of one-row series spans 128 of them, past
        ``BLOCK_LOOKUP_MAX_WIDTH``: the entry keeps the per-row program,
        answers exactly and counts no block lookup."""
        use_mesh(monkeypatch, 4)
        monkeypatch.setenv("HORAEDB_DIST_MIN_ROWS", "1")
        conn = horaedb_tpu.connect(None)
        try:
            conn.execute(DDL)
            rows = make_rows(6000, seed=39, hosts=6000)
            write(conn, rows)
            conn.flush_all()
            for _ in range(2):
                conn.execute(sql())
            before = block_lookups()
            out, entry = self.served(conn, sql(minmax=True))
            assert entry.series_block_width is None
            assert block_lookups() == before
            hold(out, reference(rows), minmax=True)
        finally:
            conn.close()


def test_the_one_device_full_scan_counts_its_block_lookups(monkeypatch):
    """On one device the full scan over ``("delta", w <= 4)`` series codes
    reads the tables by block and counts; the ``_sel`` program gathers its
    picked rows and does not."""
    monkeypatch.setenv("HORAEDB_ADAPTIVE_PATH", "0")
    conn = horaedb_tpu.connect(None)
    try:
        conn.execute(DDL)
        write(conn, make_rows(5000, seed=40, hosts=13))
        conn.flush_all()
        for _ in range(2):
            conn.execute(sql())
        ex = conn.interpreters.executor
        assert ex.scan_cache._entries["t"].series_layout == ("delta", 1)
        before = block_lookups()
        conn.execute(sql())
        assert ex.last_path == "device-cached" and "cache_rows" not in ex.last_metrics
        assert block_lookups() == before + 1
        conn.execute(sql(where="WHERE host = 'h003'"))
        assert ex.last_path == "device-cached" and "cache_rows" in ex.last_metrics
        assert block_lookups() == before + 1
    finally:
        conn.close()


def test_the_one_device_entry_is_what_it_was(monkeypatch):
    """Below the sharding threshold nothing moved: the power-of-two bucket
    of n + 1 rows, the explicit pad row, the fills, no gauge."""
    from horaedb_tpu.ops.encoding import shape_bucket

    monkeypatch.setenv("HORAEDB_ADAPTIVE_PATH", "0")
    monkeypatch.setenv("HORAEDB_CACHE_LAYOUT", "raw")
    conn = horaedb_tpu.connect(None)
    try:
        conn.execute(DDL.replace("TABLE t ", "TABLE solo "))
        rows = make_rows(5000, seed=9, hosts=13)
        write(conn, rows, "solo")
        conn.flush_all()
        statement = sql().replace(" FROM t ", " FROM solo ")
        conn.execute(statement)
        conn.execute(statement)
        ex = conn.interpreters.executor
        assert ex.last_path == "device-cached"
        entry = ex.scan_cache._entries["solo"]
        n = 5000
        assert entry.mesh is None and entry.shards is None
        assert entry.padded_rows == shape_bucket(n + 1) == 8192
        counts = np.diff(entry.series_offsets)
        codes = np.full(8192, 13, np.int32)
        codes[:n] = np.repeat(np.arange(13), counts)
        assert np.array_equal(np.asarray(entry.series_codes_dev), codes)
        ts_rel = np.full(8192, -1, np.int32)
        ts_rel[:n] = entry.ts_rel_host
        assert np.array_equal(np.asarray(entry.ts_rel_dev), ts_rel)
        order = np.lexsort((rows["ts"], compute_tsid([rows["host"]])))
        for name in ("v", "w"):
            want = np.zeros(8192, np.float32)
            want[:n] = rows[name][order]
            assert np.array_equal(np.asarray(entry.value_cols_dev[name]), want)
        assert not [ln for ln in REGISTRY.expose().splitlines()
                    if ln.startswith(GAUGE + "{") and 'table="solo"' in ln]
    finally:
        conn.close()
