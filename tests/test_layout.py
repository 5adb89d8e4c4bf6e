"""Compressed device-resident layouts (ISSUE 19).

Codec properties, encoded-domain kernel equivalence against the
``HORAEDB_CACHE_LAYOUT=raw`` arm, layout_tuner journaling (incl. the
evicted-before-reupload promotion regression), and the memtable
dictionary handoff.
"""

import numpy as np
import pytest

import horaedb_tpu
from horaedb_tpu.ops.encoding import (
    BLOCK_LOOKUP_MAX_WIDTH,
    FOR_BLOCK,
    BlockedSeries,
    DictEncoded,
    decode_series,
    decode_ts,
    decode_value,
    delta_for_encode,
    dict_encode,
    lookup_series,
    pack_bits,
    series_block_width,
    unpack_bits,
    unpack_bits_all,
    unpack_bits_host,
)


@pytest.fixture()
def db():
    conn = horaedb_tpu.connect(None)
    yield conn
    conn.close()


DDL = (
    "CREATE TABLE t (host string TAG, v double, ts timestamp KEY) "
    "WITH (segment_duration='1h')"
)


def seed(db, n=200, t_base=1_700_000_000_000, card=8):
    """Low-cardinality values: v cycles over `card` distinct floats."""
    db.execute(DDL)
    vals = ", ".join(
        f"('h{i % 5}', {float(i % card)}, {t_base + i * 1000})"
        for i in range(n)
    )
    db.execute(f"INSERT INTO t (host, v, ts) VALUES {vals}")
    db.flush_all()


def warm(db, sql):
    db.execute(sql)
    return db.execute(sql)


def _sorted_codes(n, run, step=1):
    """Non-decreasing int32 codes: a new series every ``run`` rows, codes
    ``step`` apart (a 128-row block then spans 128 / run * step of them)."""
    return (np.arange(n, dtype=np.int32) // run) * step


class TestCodecs:
    @pytest.mark.parametrize("n", [128, 4096, 1 << 16])
    @pytest.mark.parametrize("width", range(1, 17))
    def test_pack_unpack_roundtrip_all_widths(self, width, n):
        """Both device unpacks — the full scan's static form and the
        gather form over every row — are bit-equal to the host mirror."""
        import jax.numpy as jnp

        rng = np.random.default_rng(7 * width + n)
        vals = rng.integers(0, 1 << width, size=n).astype(np.uint32)
        vals[[0, -1]] = (1 << width) - 1  # all ones at both ends
        words = pack_bits(vals, width)
        assert np.array_equal(unpack_bits_host(words, width, n), vals)
        dev = jnp.asarray(words)
        static = unpack_bits_all(dev, width, n)
        gathered = unpack_bits(dev, width, jnp.arange(n, dtype=jnp.int32))
        assert static.dtype == gathered.dtype == jnp.uint32
        assert np.array_equal(np.asarray(static), vals)
        assert np.array_equal(np.asarray(gathered), vals)

    @pytest.mark.parametrize(
        "layout",
        [("delta", 1), ("delta", 3), ("delta", 8), ("delta", 13)],
        ids=lambda l: f"delta{l[1]}",
    )
    @pytest.mark.parametrize("decode", [decode_series, decode_ts])
    def test_delta_decode_full_scan_equals_gather(self, decode, layout):
        import jax.numpy as jnp

        n = 8 * FOR_BLOCK
        rng = np.random.default_rng(layout[1])
        offsets = rng.integers(0, 1 << layout[1], size=(n // FOR_BLOCK, FOR_BLOCK))
        offsets[:, 0], offsets[:, -1] = 0, (1 << layout[1]) - 1
        base = rng.integers(-50, 1000, size=(n // FOR_BLOCK, 1))
        vals = (base + offsets).ravel().astype(np.int32)
        enc = delta_for_encode(vals, 16)
        assert enc.width == layout[1]
        parts = (jnp.asarray(enc.words), jnp.asarray(enc.base))
        full = decode(parts, layout, n)
        picked = decode(parts, layout, n, jnp.arange(n, dtype=jnp.int32))
        assert np.array_equal(np.asarray(full), vals)
        assert np.array_equal(np.asarray(picked), vals)

    @pytest.mark.parametrize("card", [2, 100, 300, 5000])
    def test_dict_decode_full_scan_equals_gather(self, card):
        """``dict`` timestamps and values, and a filter-only field's bare
        codes: idx=None against idx=arange, bit for bit."""
        import jax.numpy as jnp

        n = 8192
        rng = np.random.default_rng(card)
        idx = jnp.arange(n, dtype=jnp.int32)
        ts = rng.integers(0, card, n).astype(np.int32) * 10_000
        ts[:card] = np.arange(card) * 10_000
        enc = dict_encode(ts, 1 << 16)
        parts = (jnp.asarray(enc.words), jnp.asarray(enc.dictionary))
        layout = ("dict", enc.width)
        assert np.array_equal(np.asarray(decode_ts(parts, layout, n)), ts)
        assert np.array_equal(np.asarray(decode_ts(parts, layout, n, idx)), ts)
        vals = (ts / 7).astype(np.float32)
        enc = dict_encode(vals, 1 << 16)
        parts = (jnp.asarray(enc.words), jnp.asarray(enc.dictionary))
        for full_decode in (True, False):
            layout = ("dict", enc.width, full_decode)
            want = vals if full_decode else np.searchsorted(
                enc.dict_host, vals).astype(np.float32)
            full = np.asarray(decode_value(parts, layout, n))
            picked = np.asarray(decode_value(parts, layout, n, idx))
            assert full.tobytes() == picked.tobytes() == want.tobytes()

    def test_dict_encode_bit_exact_roundtrip(self):
        import jax.numpy as jnp

        rng = np.random.default_rng(3)
        base = np.asarray(
            [-1.5, 0.0, 2.25, 1e30, -7.0, 3.3], dtype=np.float32
        )
        vals = base[rng.integers(0, len(base), size=512)]
        enc = dict_encode(vals, 64)
        assert isinstance(enc, DictEncoded)
        codes = unpack_bits(
            jnp.asarray(enc.words), enc.width,
            jnp.arange(len(vals), dtype=jnp.int32),
        )
        dec = np.asarray(enc.dict_host)[np.asarray(codes)]
        assert dec.tobytes() == vals.tobytes()  # bit-exact, not approx
        # the dictionary is SORTED: code order == value order (this is
        # what lets filters and sort keys run in the code domain)
        assert np.all(np.diff(enc.dict_host) > 0)

    def test_dict_encode_rejects_nan_and_high_cardinality(self):
        vals = np.arange(100, dtype=np.float32)
        assert dict_encode(vals, 64) is None  # 100 distinct > cap 64
        with_nan = np.asarray([1.0, np.nan, 2.0], dtype=np.float32)
        assert dict_encode(with_nan, 64) is None

    def test_dict_encode_negative_zero_not_collapsed(self):
        # -0.0 == 0.0 compares equal but has different bits; a lossless
        # codec must refuse rather than silently canonicalize
        vals = np.asarray([0.0, -0.0, 1.0] * 8, dtype=np.float32)
        enc = dict_encode(vals, 64)
        if enc is not None:
            import jax.numpy as jnp

            codes = unpack_bits(
                jnp.asarray(enc.words), enc.width,
                jnp.arange(len(vals), dtype=jnp.int32),
            )
            dec = np.asarray(enc.dict_host)[np.asarray(codes)]
            assert dec.tobytes() == vals.tobytes()

    def test_delta_for_roundtrip(self):
        import jax.numpy as jnp

        n = 4 * FOR_BLOCK
        rng = np.random.default_rng(11)
        vals = np.sort(rng.integers(0, 50_000, size=n)).astype(np.int32)
        enc = delta_for_encode(vals, 16)
        if enc is None:
            pytest.skip("range too wide for this draw")
        idx = jnp.arange(n, dtype=jnp.int32)
        rel = unpack_bits(jnp.asarray(enc.words), enc.width, idx)
        base = jnp.asarray(enc.base)[idx >> 7]
        assert np.array_equal(np.asarray(rel + base), vals)

    def test_delta_for_rejects_wide_ranges(self):
        vals = np.arange(0, FOR_BLOCK * 100_000, 100_000, dtype=np.int32)
        assert delta_for_encode(vals, 8) is None


class TestLayoutEquivalence:
    """The lossless contract: auto layouts return bit-identical results
    to the raw arm, across groupby, time_bucket, filters in the code
    domain, top-k and bounded selection."""

    QUERIES = (
        "SELECT host, count(*) AS c, sum(v) AS s, avg(v) AS a "
        "FROM t GROUP BY host ORDER BY host",
        "SELECT time_bucket(ts, '1m') AS b, count(*) AS c, sum(v) AS s "
        "FROM t GROUP BY time_bucket(ts, '1m') ORDER BY b",
        "SELECT host, count(*) AS c FROM t WHERE v > 2.5 GROUP BY host "
        "ORDER BY host",
        "SELECT host, sum(v) AS s FROM t WHERE v >= 3 AND v != 5 "
        "GROUP BY host ORDER BY host",
        "SELECT host, v, ts FROM t WHERE v = 3 ORDER BY ts DESC LIMIT 7",
        "SELECT host, v, ts FROM t ORDER BY ts DESC LIMIT 9",
        "SELECT host, v, ts FROM t WHERE v <= 1.5 ORDER BY ts LIMIT 11",
    )

    def _run_all(self, db):
        seed(db)
        return [warm(db, q).to_pylist() for q in self.QUERIES]

    def test_encoded_matches_raw_arm(self, db, monkeypatch):
        auto = self._run_all(db)
        ex = db.interpreters.executor
        entry = ex.scan_cache._entries["t"]
        # the tuner really engaged: sorted series/ts packed, v dict-coded
        assert entry.series_layout[0] == "delta"
        assert entry.ts_layout[0] in ("delta", "dict")
        assert entry.value_layout("v")[0] == "dict"

        monkeypatch.setenv("HORAEDB_CACHE_LAYOUT", "raw")
        raw_db = horaedb_tpu.connect(None)
        try:
            raw = self._run_all(raw_db)
            raw_entry = raw_db.interpreters.executor.scan_cache._entries["t"]
            assert raw_entry.series_layout == ("raw",)
            assert raw_entry.value_layout("v") == ("raw",)
            assert auto == raw
        finally:
            raw_db.close()

    def test_literal_between_and_outside_dictionary(self, db):
        """Translated literals that fall BETWEEN dictionary entries or
        outside the value range must keep exact semantics."""
        seed(db)
        warm(db, "SELECT host, sum(v) AS s FROM t GROUP BY host")
        ex = db.interpreters.executor
        assert ex.scan_cache._entries["t"].value_layout("v")[0] == "dict"
        cases = {
            "v > 2.5": sum(1 for i in range(200) if i % 8 > 2.5),
            "v < -1": 0,
            "v >= 100": 0,
            "v = 2.5": 0,  # not a dictionary member
            "v != 2.5": 200,
            "v <= 0": sum(1 for i in range(200) if i % 8 == 0),
        }
        for pred, want in cases.items():
            out = db.execute(
                f"SELECT count(*) AS c FROM t WHERE {pred}"
            ).to_pylist()
            assert out == [{"c": want}], pred

    def test_high_cardinality_column_stays_raw_and_exact(self, db):
        seed(db, n=300, card=10_000)  # v = i, 300 distinct... under cap
        # force the dict cap below the cardinality so v stays raw
        import os

        os.environ["HORAEDB_CACHE_DICT_MAX"] = "16"
        try:
            sql = (
                "SELECT host, sum(v) AS s FROM t WHERE v > 100 "
                "GROUP BY host ORDER BY host"
            )
            out = warm(db, sql).to_pylist()
            entry = db.interpreters.executor.scan_cache._entries["t"]
            assert entry.value_layout("v") == ("raw",)
            want = {
                f"h{h}": sum(
                    float(i) for i in range(300) if i % 5 == h and i > 100
                )
                for h in range(5)
            }
            got = {r["host"]: r["s"] for r in out}
            assert got == pytest.approx(want)
        finally:
            os.environ.pop("HORAEDB_CACHE_DICT_MAX", None)


class TestLayoutTunerJournal:
    def test_encodes_are_journaled_and_resolved(self, db):
        from horaedb_tpu.obs.decisions import DECISION_JOURNAL

        before = (
            DECISION_JOURNAL.stats()["loops"]
            .get("layout_tuner", {})
            .get("resolved", 0)
        )
        seed(db)
        warm(db, "SELECT host, sum(v) AS s FROM t GROUP BY host")
        stats = DECISION_JOURNAL.stats()["loops"]["layout_tuner"]
        assert stats["resolved"] > before
        ours = [
            e for e in DECISION_JOURNAL.list(loop="layout_tuner")
            if e["key"].startswith("t:")
        ]
        assert ours
        for e in ours:
            assert e["resolved"] and e["outcome"] == "encoded"
            assert e["predicted"] and e["actual"]
        # the realized encoded bytes for resident columns price the LRU
        entry = db.interpreters.executor.scan_cache._entries["t"]
        assert entry.device_bytes < 3 * 4 * entry.padded_rows

    def test_promotion_decision_evicted_before_reupload_resolves(self, db, monkeypatch):
        """Satellite regression: a bf16->f32 promotion whose column is
        evicted before the re-upload must resolve outcome=evicted, never
        dangle unresolved."""
        from horaedb_tpu.obs.decisions import DECISION_JOURNAL

        monkeypatch.setenv("HORAEDB_CACHE_DTYPE", "auto")
        seed(db)
        # count-only usage -> v resident bf16
        warm(db, "SELECT host, count(*) AS c FROM t GROUP BY host")
        cache = db.interpreters.executor.scan_cache
        warm(db, "SELECT host, min(v) AS m FROM t GROUP BY host")
        entry = cache._entries["t"]
        import jax.numpy as jnp

        assert entry.value_cols_dev["v"].dtype == jnp.bfloat16
        # promotion decision fires, then the entry is evicted before any
        # re-upload can resolve it
        cache._drop_bf16_columns(entry, ["v"])
        assert entry.pending_promotions == {"v"}
        cache.invalidate("t")
        evicted = [
            e for e in DECISION_JOURNAL.list(loop="layout_tuner")
            if e["key"] == "t:v" and e["choice"] == "promote_f32"
        ]
        assert evicted
        assert evicted[-1]["resolved"]
        assert evicted[-1]["outcome"] == "evicted"
        stats = DECISION_JOURNAL.stats()["loops"]["layout_tuner"]
        assert (
            stats["issued"]
            == stats["resolved"] + stats["expired"] + stats["unresolved"]
        )

    def test_promotion_through_reupload_resolves_promoted(self, db, monkeypatch):
        from horaedb_tpu.obs.decisions import DECISION_JOURNAL

        monkeypatch.setenv("HORAEDB_CACHE_DTYPE", "auto")
        seed(db)
        warm(db, "SELECT host, min(v) AS m FROM t GROUP BY host")
        cache = db.interpreters.executor.scan_cache
        import jax.numpy as jnp

        assert cache._entries["t"].value_cols_dev["v"].dtype == jnp.bfloat16
        # sum usage promotes: the re-upload resolves the journaled choice
        warm(db, "SELECT host, sum(v) AS s FROM t GROUP BY host")
        promos = [
            e for e in DECISION_JOURNAL.list(loop="layout_tuner")
            if e["key"] == "t:v" and e["choice"] == "promote_f32"
        ]
        assert promos and promos[-1]["resolved"]
        assert promos[-1]["outcome"] == "promoted"
        assert cache._entries["t"].pending_promotions in (None, set())


class TestMemtableLayoutHandoff:
    def test_hinted_columns_freeze_dictionary_coded(self):
        from horaedb_tpu.common_types.dict_column import DictColumn
        from horaedb_tpu.common_types.layout_hints import (
            clear_hints,
            low_cardinality_hint,
            note_low_cardinality,
        )

        conn = horaedb_tpu.connect(None)
        try:
            conn.execute(
                "CREATE TABLE lh (host string TAG, v double, ts timestamp "
                "NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic WITH ("
                "memtable_type='layered', "
                "mutable_segment_switch_threshold='256b')"
            )
            clear_hints()
            # the scan cache's dict encode publishes this observation;
            # here the hint is planted directly to pin the handoff
            note_low_cardinality("lh", "v", 4)
            assert low_cardinality_hint("lh", "v") == 4
            for i in range(64):
                conn.execute(
                    f"INSERT INTO lh (host, v, ts) VALUES "
                    f"('h{i % 2}', {float(i % 4)}, {1000 + i})"
                )
            table = conn.catalog.open("lh")
            mt = table.data.version.mutable
            segs = mt.frozen_segments()
            assert segs, "switch threshold never crossed"
            assert any(
                isinstance(s.rows.columns["v"], DictColumn) for s in segs
            )
            # reads through the dictionary-coded segments stay exact
            out = conn.execute(
                "SELECT host, sum(v) AS s FROM lh GROUP BY host ORDER BY host"
            ).to_pylist()
            assert out == [
                {"host": "h0", "s": sum(float(i % 4) for i in range(0, 64, 2))},
                {"host": "h1", "s": sum(float(i % 4) for i in range(1, 64, 2))},
            ]
        finally:
            clear_hints()
            conn.close()

    def test_cache_dict_encode_publishes_hint(self, db):
        from horaedb_tpu.common_types.layout_hints import (
            clear_hints,
            low_cardinality_hint,
        )

        clear_hints()
        try:
            seed(db)
            warm(db, "SELECT host, sum(v) AS s FROM t GROUP BY host")
            assert db.interpreters.executor.scan_cache._entries[
                "t"
            ].value_layout("v")[0] == "dict"
            assert low_cardinality_hint("t", "v") == 8
        finally:
            clear_hints()


def _stablehlo_gathers(lowered):
    """Element counts of every ``stablehlo.gather`` result of a lowering."""
    import math
    import re

    shapes = re.findall(
        r'"stablehlo\.gather".*?-> tensor<((?:\d+x)*)[a-z]+\d+>',
        lowered.as_text(),
    )
    return [math.prod(int(d) for d in s.split("x") if d) for s in shapes]


def _fold_gathers(impl, rows, n_fields):
    """What the scatter impl's fold gathers (``_fold_rows``), per run slot
    (``fold_cap``, one more for a step padded to whole blocks): a segment
    id, a lane row of run-end flags (``_run_ends``), and each field's lane
    row of the run's first and of its last block (``_run_totals``: one
    ``jnp.take``, lowered once for both)."""
    from horaedb_tpu.ops.scan_agg import _FOLD_BLOCK, fold_cap

    if impl != "scatter":
        return []
    slots = fold_cap(rows) + (rows % _FOLD_BLOCK > 0)
    lane_rows = slots * _FOLD_BLOCK
    return [slots, lane_rows, n_fields * lane_rows]


class TestFullScanNoRowGather:
    """PR 26: a full scan reads its packed streams by their static
    structure and the per-series tables through the FOR blocks. On a v5e
    a gather of N rows cost 7-9 ns a row (four of them were 271 of the
    272 ms of ``cached_scan_single`` at 2^23 rows), so the lowering itself
    is guarded: plain ``jax.jit(...).lower``, no described chip."""

    N, S, M = 4096, 7, 64

    def _lower(self, impl, selective, packed_streams=False):
        import jax
        import jax.numpy as jnp

        from horaedb_tpu.ops.scan_agg import cached_scan_agg_packed

        n, sds = self.N, jax.ShapeDtypeStruct
        series = (sds((n // 32 + 1,), jnp.uint32), sds((n // 128,), jnp.int32))
        if packed_streams:
            ts = (sds((n * 13 // 32 + 1,), jnp.uint32), sds((8192,), jnp.int32))
            value = (sds((n * 7 // 32 + 1,), jnp.uint32), sds((128,), jnp.float32))
            values, ts_layout = (value, value), ("dict", 13)
            value_layouts = (("dict", 7, True), ("dict", 7, False))
        else:  # what ScanCache builds for the benchmark's cpu table
            ts, ts_layout = (sds((n,), jnp.int32),), ("raw",)
            values, value_layouts = sds((2, n), jnp.float32), (("raw",),) * 2
        n_groups, n_buckets = (1, 1) if impl == "single" else (8, 16)
        return cached_scan_agg_packed.lower(
            series, ts, values, sds((2 * (self.S + 1),), jnp.int32),
            sds((1 + 4 + (self.M if selective else 0),), jnp.int32),
            n_groups=n_groups, n_buckets=n_buckets,
            n_agg_fields=1 if packed_streams else 2,
            numeric_filters=((1 if packed_streams else 0, 4),),
            need_minmax=True, segment_impl=impl,
            selective=selective, value_layouts=value_layouts,
            ts_layout=ts_layout, series_layout=("delta", 1),
        )

    @pytest.mark.parametrize("impl", ["single", "scatter", "mxu"])
    def test_full_scan_lowers_without_row_sized_gather(self, impl):
        gathers = _stablehlo_gathers(self._lower(impl, selective=False))
        # the allow-list (and the group map, where groups exist) through
        # two candidate series a block, and the fold's lane rows; no gather
        # of one element a row
        assert sorted(gathers) == sorted(
            [self.N // FOR_BLOCK * 2] * (1 if impl == "single" else 2)
            + _fold_gathers(impl, self.N, 2)
        )

    @pytest.mark.parametrize("impl", ["single", "scatter", "mxu"])
    def test_full_scan_of_packed_streams_gathers_only_dictionaries(self, impl):
        """Every stream packed (ts ``dict`` 13, an aggregated and a
        filter-only ``dict`` 7 value): the unpacks add no gather; what
        stays row-sized are the two dictionary lookups themselves."""
        gathers = _stablehlo_gathers(
            self._lower(impl, selective=False, packed_streams=True)
        )
        assert sorted(g for g in gathers if g >= self.N) == [self.N] * 2
        fold = _fold_gathers(impl, self.N, 1)
        assert len(gathers) == 2 + (1 if impl == "single" else 2) + len(fold)

    @pytest.mark.parametrize("packed_streams", [False, True], ids=["raw", "packed"])
    @pytest.mark.parametrize("impl", ["single", "scatter", "mxu"])
    def test_selective_program_gathers_as_before(self, impl, packed_streams):
        """The ``_sel`` programs decode M picked rows with the index: their
        gathers are those of the parent of PR 26, all of M elements."""
        gathers = _stablehlo_gathers(
            self._lower(impl, selective=True, packed_streams=packed_streams)
        )
        for g in _fold_gathers(impl, self.M, 1 if packed_streams else 2):
            gathers.remove(g)
        assert set(gathers) == {self.M}
        tables = 1 if impl == "single" else 2  # allow-list (+ group map)
        # series words x2 + base, then ts and two values: raw 1 gather
        # each, dict words x2 + dictionary (the filter-only value: no
        # dictionary)
        assert len(gathers) == 3 + (3 + 3 + 2 if packed_streams else 3) + tables

    @pytest.mark.parametrize("width,run", [(1, 300), (3, 19), (4, 9)])
    def test_lookup_series_matches_row_lookup(self, width, run):
        import jax.numpy as jnp

        assert width <= BLOCK_LOOKUP_MAX_WIDTH
        n, rng = 4096, np.random.default_rng(width)
        codes = np.minimum(_sorted_codes(n, run), (n - 37) // run)
        n_series = int(codes.max())  # the last code is the pad series
        enc = delta_for_encode(codes, 8)
        assert enc.width == width
        parts = (jnp.asarray(enc.words), jnp.asarray(enc.base))
        series = decode_series(parts, ("delta", width), n, blocked=True)
        assert isinstance(series, BlockedSeries)
        for table in (
            rng.integers(0, 1 << 20, n_series + 1).astype(np.int32),
            rng.random(n_series + 1) > 0.5,
        ):
            got = lookup_series(jnp.asarray(table), series)
            assert np.array_equal(np.asarray(got), table[codes])

    def test_wide_series_layout_keeps_the_row_lookup(self):
        """Past ``BLOCK_LOOKUP_MAX_WIDTH`` the candidates of a block are no
        longer few: the codes decode whole and tables are read per row."""
        import jax.numpy as jnp

        n = 4096
        codes = _sorted_codes(n, 5)  # 26-27 series a block: 5 bits
        enc = delta_for_encode(codes, 8)
        assert enc.width == BLOCK_LOOKUP_MAX_WIDTH + 1
        parts = (jnp.asarray(enc.words), jnp.asarray(enc.base))
        series = decode_series(parts, ("delta", enc.width), n, blocked=True)
        assert not isinstance(series, BlockedSeries)
        assert np.array_equal(np.asarray(series), codes)
        table = jnp.arange(int(codes.max()) + 1, dtype=jnp.int32) * 3
        assert np.array_equal(np.asarray(lookup_series(table, series)), codes * 3)


    @pytest.mark.parametrize("width,run", [(1, 300), (3, 19), (4, 9)])
    def test_raw_codes_read_by_block(self, width, run):
        """A sharded entry's raw codes under ``("blocked", w)``: each block's
        base and offsets come from the codes in the program; pad rows past
        the width of the block they share with the last valid rows read the
        pad series' entry, and a gather of picked rows stays raw."""
        import jax.numpy as jnp

        n, n_valid = 4096, 29 * FOR_BLOCK + 87  # block 29 straddles the pad
        rng = np.random.default_rng(width)
        codes = _sorted_codes(n, run)
        n_series = int(codes[n_valid - 1]) + 40  # past the width from block 29
        codes[n_valid:] = n_series
        assert series_block_width([codes[:n_valid]]) == width
        parts = (jnp.asarray(codes),)
        series = decode_series(parts, ("blocked", width), n, blocked=True)
        assert isinstance(series, BlockedSeries) and series.pad_past_width
        for table in (
            rng.integers(0, 1 << 20, n_series + 1).astype(np.int32),
            np.append(rng.random(n_series) > 0.5, False),
        ):
            got = lookup_series(jnp.asarray(table), series)
            assert np.array_equal(np.asarray(got), table[codes])
        idx = jnp.asarray(np.arange(n_valid - 5, n_valid + 5, dtype=np.int32))
        picked = decode_series(parts, ("blocked", width), n, idx=idx, blocked=True)
        assert np.array_equal(np.asarray(picked), codes[n_valid - 5 : n_valid + 5])

    def test_block_width_counts_each_piece_from_its_own_blocks(self):
        """Pieces are laid from a block's start each (a shard's valid rows),
        so a block never spans two pieces; past ``BLOCK_LOOKUP_MAX_WIDTH``
        there is no width."""
        a = np.repeat(np.arange(3, dtype=np.int32), 100)  # 300 rows: 3 blocks
        b = np.repeat(np.arange(40, 43, dtype=np.int32), 100)
        assert series_block_width([a, b]) == 1  # [a | b] would span 0..40
        assert series_block_width([a[:1], b[:1]]) == 1  # one code a block
        assert series_block_width([np.arange(300, dtype=np.int32) // 9]) == 4
        assert series_block_width([np.arange(300, dtype=np.int32) // 5]) is None
        assert series_block_width([]) == 1


ALLOW_LISTS = {
    "none": lambda rng, s: np.zeros(s + 1, bool),
    "half": lambda rng, s: np.append(rng.random(s) > 0.5, False),
    "all": lambda rng, s: np.ones(s + 1, bool),  # the pad series too
    "pad-masked": lambda rng, s: np.append(np.ones(s, bool), False),
}


class TestBlockLookupKernelEquivalence:
    """The cached kernel over encoded series codes against the same
    kernel over the raw codes (what ``HORAEDB_CACHE_LAYOUT=raw`` serves):
    synthetic resident columns whose series are short enough for offset
    widths 1, 3 (looked up per block) and 8 (per row), with the block
    that straddles the last series and the pad rows."""

    @pytest.mark.parametrize("allow_kind", sorted(ALLOW_LISTS))
    @pytest.mark.parametrize("impl", ["single", "scatter", "mxu"])
    @pytest.mark.parametrize("width,run,step", [(1, 300, 1), (3, 19, 1), (8, 1, 2)])
    def test_encoded_series_equal_raw(self, width, run, step, impl, allow_kind):
        import jax.numpy as jnp

        import jax

        from horaedb_tpu.ops.scan_agg import cached_scan_agg_body

        cached_scan_agg = jax.jit(cached_scan_agg_body, static_argnames=(
            "n_groups", "n_buckets", "n_agg_fields", "numeric_filters",
            "need_minmax", "segment_impl", "value_layouts", "series_layout",
        ))
        n, n_valid = 4096, 29 * FOR_BLOCK + 87  # block 29 straddles the pad
        rng = np.random.default_rng(width * 100 + len(allow_kind))
        codes = _sorted_codes(n, run, step)
        n_series = int(codes[n_valid - 1]) + 1
        codes[n_valid:] = n_series  # pad rows: the pad series
        enc = delta_for_encode(codes, 8)
        assert enc is not None and enc.width == width
        ts = rng.integers(0, 64_000, n).astype(np.int32)
        vals = rng.normal(size=(2, n)).astype(np.float32)
        allow = ALLOW_LISTS[allow_kind](rng, n_series)
        n_groups, n_buckets = (1, 1) if impl == "single" else (8, 16)
        groups = rng.integers(0, n_groups, n_series + 1).astype(np.int32)
        args = (
            jnp.asarray(ts), jnp.asarray(vals), jnp.asarray(groups),
            jnp.asarray(allow), jnp.asarray(np.float32([-0.5])),
            jnp.int32(1000), jnp.int32(60_000), jnp.int32(0), jnp.int32(4000),
        )
        static = dict(
            n_groups=n_groups, n_buckets=n_buckets, n_agg_fields=2,
            numeric_filters=((1, 4),), need_minmax=True, segment_impl=impl,
        )
        raw = cached_scan_agg(jnp.asarray(codes), *args, **static)
        encoded = cached_scan_agg(
            (jnp.asarray(enc.words), jnp.asarray(enc.base)), *args, **static,
            series_layout=("delta", width),
            value_layouts=(("raw",), ("raw",)),
        )
        m = allow[codes] & (ts >= 1000) & (ts < 60_000) & (vals[1] > -0.5)
        assert int(np.asarray(raw[0]).sum()) == int(m.sum())
        for a, b in zip(encoded, raw):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
