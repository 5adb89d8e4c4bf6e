"""Learned aggregation-kernel routing (PR 6).

Covers: the three-segment-impl equivalence property (mxu / scatter /
hash must be indistinguishable on every input), the KernelRouter's
probe/serve/re-probe loop and cardinality seeding, the guarded env-int
satellite, the dist-agg step-cache LRU bound, the scan-cache dtype
auto-tuning, and the end-to-end kill switch + ledger surfaces.
"""

import dataclasses

import numpy as np
import pytest

import horaedb_tpu
from horaedb_tpu.ops.encoding import build_padded_batch, next_pow2
from horaedb_tpu.ops.scan_agg import (
    ScanAggSpec,
    mxu_max_segments,
    pinned_segment_impl,
    resolve_segment_impl,
    scan_aggregate,
)


@pytest.fixture()
def db():
    conn = horaedb_tpu.connect(None)
    yield conn
    conn.close()


@pytest.fixture(autouse=True)
def _fresh_router():
    from horaedb_tpu.query.path_router import KERNEL_ROUTER

    KERNEL_ROUTER.reset()
    yield
    KERNEL_ROUTER.reset()


def _dispatch(batch, spec, impl, slots=0, literals=()):
    return scan_aggregate(
        batch,
        dataclasses.replace(spec, segment_impl=impl, hash_slots=slots),
        list(literals),
    )


def _assert_states_equal(a, b, label):
    assert np.array_equal(np.asarray(a.counts), np.asarray(b.counts)), label
    for fa, fb, name in (
        (a.sums, b.sums, "sums"),
        (a.mins, b.mins, "mins"),
        (a.maxs, b.maxs, "maxs"),
    ):
        assert np.allclose(
            np.asarray(fa), np.asarray(fb), rtol=1e-5, atol=1e-5,
            equal_nan=True,
        ), f"{label}: {name}"


class TestKernelEquivalence:
    """Satellite: all three segment impls return identical
    counts/sums/mins/maxs over randomized specs."""

    def test_randomized_specs(self, monkeypatch):
        # keep the hash arm on-device even for tiny randomized inputs
        monkeypatch.setenv("HORAEDB_HASH_HOST_MAX_ROWS", "0")
        from horaedb_tpu.ops.hash_agg import default_hash_slots

        rng = np.random.default_rng(42)
        for trial in range(8):
            n = int(rng.integers(5, 1500))
            n_groups = int(rng.integers(2, 40))
            n_buckets = int(rng.integers(1, 5))
            n_fields = int(rng.integers(0, 3))
            # empty groups: codes drawn from a PREFIX of the domain, so
            # the tail groups exist in the spec but hold no rows
            live_groups = max(1, n_groups // 2)
            codes = rng.integers(0, live_groups, n).astype(np.int32)
            buckets = rng.integers(0, n_buckets, n).astype(np.int32)
            mask = rng.random(n) < 0.8  # masked rows
            vals = [rng.normal(size=n).astype(np.float32) for _ in range(n_fields)]
            batch = build_padded_batch(codes, buckets, mask, vals)
            spec = ScanAggSpec(
                n_groups=n_groups,
                n_buckets=n_buckets,
                n_agg_fields=n_fields,
                need_minmax=bool(trial % 2),
            ).padded()
            n_seg = spec.n_groups * spec.n_buckets
            ref = _dispatch(batch, spec, "scatter")
            _assert_states_equal(
                ref, _dispatch(batch, spec, "mxu"), f"trial {trial}: mxu"
            )
            for slots in (16, default_hash_slots(n_seg)):
                got = _dispatch(batch, spec, "hash", slots=slots)
                _assert_states_equal(
                    ref, got, f"trial {trial}: hash slots={slots}"
                )

    def test_hash_at_slot_table_boundary(self, monkeypatch):
        """n_seg == slot-count boundary: every slot needed, load factor
        1.0 — the probe budget can't place everything and the overflow
        fallback must make up the difference exactly."""
        monkeypatch.setenv("HORAEDB_HASH_HOST_MAX_ROWS", "0")
        rng = np.random.default_rng(7)
        n_groups = 16  # spec pads to pow2: n_seg == 16 == slots
        n = 600
        codes = rng.integers(0, n_groups, n).astype(np.int32)
        mask = np.ones(n, bool)
        vals = [rng.normal(size=n).astype(np.float32)]
        batch = build_padded_batch(codes, np.zeros(n, np.int32), mask, vals)
        spec = ScanAggSpec(n_groups=n_groups, n_buckets=1, n_agg_fields=1).padded()
        n_seg = spec.n_groups * spec.n_buckets
        assert n_seg == next_pow2(n_seg) == 16
        ref = _dispatch(batch, spec, "scatter")
        _assert_states_equal(
            ref, _dispatch(batch, spec, "hash", slots=16), "boundary"
        )

    def test_single_segment_bypasses_routing(self):
        """n_seg == 1 (global aggregate) resolves to the pure-reduction
        impl regardless of the requested kernel."""
        assert resolve_segment_impl(1, "auto") == "single"
        assert resolve_segment_impl(1, "hash") == "single"
        rng = np.random.default_rng(3)
        n = 300
        batch = build_padded_batch(
            np.zeros(n, np.int32), np.zeros(n, np.int32),
            np.ones(n, bool), [rng.normal(size=n).astype(np.float32)],
        )
        spec = ScanAggSpec(n_groups=1, n_buckets=1, n_agg_fields=1).padded()
        ref = _dispatch(batch, spec, "auto")
        _assert_states_equal(ref, _dispatch(batch, spec, "hash"), "single")

    def test_hash_host_fallback_is_exact(self, monkeypatch):
        """Below HORAEDB_HASH_HOST_MAX_ROWS the hash route serves from
        host numpy — same numbers as the device impls."""
        monkeypatch.delenv("HORAEDB_SEGMENT_IMPL", raising=False)
        rng = np.random.default_rng(11)
        n = 200
        codes = rng.integers(0, 6, n).astype(np.int32)
        mask = rng.random(n) < 0.9
        vals = [rng.normal(size=n).astype(np.float32)]
        batch = build_padded_batch(codes, np.zeros(n, np.int32), mask, vals)
        spec = ScanAggSpec(n_groups=8, n_buckets=1, n_agg_fields=1).padded()
        ref = _dispatch(batch, spec, "scatter")
        monkeypatch.setenv("HORAEDB_HASH_HOST_MAX_ROWS", "100000")
        _assert_states_equal(ref, _dispatch(batch, spec, "hash"), "host")

    def test_live_pin_flip_retraces_warm_shapes(self, monkeypatch):
        """Review regression: the pin used to resolve INSIDE the jitted
        body — a warm shape kept serving the stale compiled branch after
        an operator flipped HORAEDB_SEGMENT_IMPL (the bisect tool's whole
        purpose). Host-side resolution makes the concrete impl the jit
        key, so the flip must mint a new trace through the new branch."""
        from horaedb_tpu.ops import scan_agg as sa

        rng = np.random.default_rng(9)
        n = 100
        batch = build_padded_batch(
            rng.integers(0, 8, n).astype(np.int32), np.zeros(n, np.int32),
            np.ones(n, bool), [rng.normal(size=n).astype(np.float32)],
        )
        spec = ScanAggSpec(n_groups=8, n_buckets=1, n_agg_fields=1).padded()
        monkeypatch.setenv("HORAEDB_SEGMENT_IMPL", "scatter")
        ref = _dispatch(batch, spec, "auto")  # warm: compiles scatter
        traced = []
        orig = sa._mxu_segment_agg

        def spy(*args, **kwargs):
            traced.append(1)
            return orig(*args, **kwargs)

        monkeypatch.setattr(sa, "_mxu_segment_agg", spy)
        monkeypatch.setenv("HORAEDB_SEGMENT_IMPL", "mxu")
        got = _dispatch(batch, spec, "auto")
        assert traced, "pin flip did not re-trace the warm shape"
        _assert_states_equal(ref, got, "pin flip")

    def test_pin_disables_host_fallback(self, monkeypatch):
        """HORAEDB_SEGMENT_IMPL exists to bisect device lowerings: a
        pinned run must actually run them, even on tiny inputs."""
        monkeypatch.setenv("HORAEDB_SEGMENT_IMPL", "hash")
        monkeypatch.setenv("HORAEDB_HASH_HOST_MAX_ROWS", "100000")
        rng = np.random.default_rng(5)
        n = 50
        batch = build_padded_batch(
            rng.integers(0, 4, n).astype(np.int32), np.zeros(n, np.int32),
            np.ones(n, bool), [rng.normal(size=n).astype(np.float32)],
        )
        spec = ScanAggSpec(n_groups=4, n_buckets=1, n_agg_fields=1).padded()
        assert pinned_segment_impl() == "hash"
        got = _dispatch(batch, spec, "auto")
        monkeypatch.setenv("HORAEDB_SEGMENT_IMPL", "scatter")
        ref = _dispatch(batch, spec, "auto")
        _assert_states_equal(ref, got, "pinned")


class TestEnvInt:
    """Satellite: malformed env ints degrade to defaults, never raise."""

    def test_env_int_guards(self, monkeypatch):
        from horaedb_tpu.utils.env import env_float, env_int

        monkeypatch.delenv("X_LINT_INT", raising=False)
        assert env_int("X_LINT_INT", 7) == 7
        monkeypatch.setenv("X_LINT_INT", "12")
        assert env_int("X_LINT_INT", 7) == 12
        monkeypatch.setenv("X_LINT_INT", "8k")  # the operator typo
        assert env_int("X_LINT_INT", 7) == 7
        monkeypatch.setenv("X_LINT_INT", "")
        assert env_int("X_LINT_INT", 7) == 7
        monkeypatch.setenv("X_LINT_INT", "nope")
        assert env_float("X_LINT_INT", 1.5) == 1.5

    def test_malformed_mxu_threshold_does_not_abort(self, monkeypatch):
        """Regression: scan_agg read HORAEDB_MXU_MAX_SEGMENTS with a bare
        int() at import time — a typo killed the whole server."""
        monkeypatch.setenv("HORAEDB_MXU_MAX_SEGMENTS", "8k")
        assert mxu_max_segments() == 8192
        assert resolve_segment_impl(500, "auto") in ("mxu", "scatter")

    def test_other_guarded_readers(self, monkeypatch):
        from horaedb_tpu.engine.compaction import merge_chunk_count
        from horaedb_tpu.engine.merge import device_merge_min_rows
        from horaedb_tpu.parallel.mesh import dist_min_rows
        from horaedb_tpu.query.scan_cache import ScanCache

        monkeypatch.setenv("HORAEDB_MERGE_CHUNK_ROWS", "4m")
        assert merge_chunk_count(10_000_000) >= 1
        monkeypatch.setenv("HORAEDB_DIST_MIN_ROWS", "lots")
        assert dist_min_rows() > 0
        monkeypatch.setenv("HORAEDB_DEVICE_MERGE_MIN_ROWS", "???")
        assert device_merge_min_rows() > 0
        # review regression: explicit values — including negatives, which
        # force the device merge at every size — are honored, only
        # unset/malformed fall back to the backend default
        monkeypatch.setenv("HORAEDB_DEVICE_MERGE_MIN_ROWS", "-1")
        assert device_merge_min_rows() == -1
        monkeypatch.setenv("HORAEDB_CACHE_HOST_ROWS_MB", "1gb")
        assert ScanCache().max_host_rows_bytes == 256 << 20


class TestKernelRouter:
    def test_probes_then_serves_winner(self):
        from horaedb_tpu.query.path_router import KernelRouter

        r = KernelRouter()
        cands = ("scatter", "mxu", "hash")
        seen = []
        # synthetic latencies: hash fastest; first sample of each impl is
        # compile-tainted (huge) and must not poison the estimate
        lat = {"scatter": 0.05, "mxu": 0.03, "hash": 0.01}
        for i in range(2 * len(cands)):
            k = r.choose("key", "scatter", cands)
            seen.append(k)
            r.record("key", k, 5.0 if seen.count(k) == 1 else lat[k])
        assert set(seen) == set(cands)  # every candidate warmed
        assert r.choose("key", "scatter", cands) == "hash"
        r.record("key", "hash", lat["hash"])

    def test_reprobes_losers_on_cadence(self):
        from horaedb_tpu.query.path_router import PROBE_EVERY, KernelRouter

        r = KernelRouter()
        cands = ("scatter", "hash")
        for i in range(2 * len(cands)):
            k = r.choose("key", "scatter", cands)
            r.record("key", k, 0.01 if k == "scatter" else 0.05)
        def serve(calls):
            picks = []
            for i in range(calls):
                k = r.choose("key", "scatter", cands)
                picks.append(k)
                r.record("key", k, 0.01 if k == "scatter" else 0.05)
            return picks

        # hash's one clean sample is confirmed after PROBE_EVERY calls ...
        assert serve(PROBE_EVERY + 1) == ["scatter"] * PROBE_EVERY + ["hash"]
        # ... then the cadence is served time, not calls: hash (5 x slower) is
        # due once scatter has served PROBE_EVERY x 0.05 s, every 81st call
        picks = serve(2 * (5 * PROBE_EVERY + 2))
        assert picks.count("hash") == 2  # losers still get probed
        assert "hash" not in picks[:5 * PROBE_EVERY - 1]
        # the probes cost one part in PROBE_EVERY + 1 of the served seconds
        assert picks.count("hash") * 0.05 <= (
            picks.count("scatter") * 0.01 + picks.count("hash") * 0.05
        ) / (PROBE_EVERY + 1) + 0.05

    def test_lru_bound(self):
        from horaedb_tpu.query.path_router import MAX_KEYS, KernelRouter

        r = KernelRouter()
        for i in range(MAX_KEYS + 50):
            r.choose(("k", i), "scatter", ("scatter",))
        assert len(r._stats) <= MAX_KEYS

    def test_observed_segments_feedback(self):
        from horaedb_tpu.query.path_router import KernelRouter

        r = KernelRouter()
        assert r.observed_segments("key") is None
        r.note_segments("key", 100)
        assert r.observed_segments("key") == 100
        r.note_segments("key", 0)  # EWMA decays, doesn't snap
        assert 0 < r.observed_segments("key") < 100

    def test_candidate_gating(self):
        from horaedb_tpu.query.path_router import candidate_kernels

        # tiny domain: no hash (the table can't beat direct impls)
        assert "hash" not in candidate_kernels(64, 10_000)
        # dense estimate: no hash (near-full table = all overflow)
        assert "hash" not in candidate_kernels(1024, 10_000, est_distinct=1024)
        # sparse estimate: hash is worth probing
        assert "hash" in candidate_kernels(65536, 10_000, est_distinct=8)
        # scatter is always a candidate
        assert "scatter" in candidate_kernels(10**6, 10_000)

    def test_seed_kernel(self):
        from horaedb_tpu.query.path_router import seed_kernel

        assert seed_kernel(65536, 8, "tpu") == "hash"
        assert seed_kernel(65536, 8, "cpu") == "hash"
        assert seed_kernel(1024, None, "tpu") == "mxu"
        assert seed_kernel(10**6, None, "tpu") == "scatter"
        assert seed_kernel(1024, None, "cpu") == "scatter"

    def test_hash_slots_sizing(self, monkeypatch):
        from horaedb_tpu.ops.hash_agg import default_hash_slots, hash_slots_for

        assert hash_slots_for(65536, 4) == 16  # 4x headroom, pow2
        assert hash_slots_for(65536, 100) == 512
        assert hash_slots_for(65536, None) == default_hash_slots(65536)
        assert hash_slots_for(10**6, 10**6) == 4096  # cap
        monkeypatch.setenv("HORAEDB_HASH_MAX_SLOTS", "256")
        assert hash_slots_for(10**6, 10**6) == 256
        monkeypatch.setenv("HORAEDB_HASH_MAX_SLOTS", "bogus")
        assert hash_slots_for(10**6, 10**6) == 4096


class TestStepCacheLRU:
    """Satellite: the dist-agg compiled-step cache must not grow without
    bound across distinct query shapes."""

    def test_step_cache_bounded(self, monkeypatch):
        from horaedb_tpu.parallel import dist_agg
        from horaedb_tpu.parallel.mesh import serving_mesh

        mesh = serving_mesh()
        assert mesh is not None  # conftest forces the 8-device CPU mesh
        monkeypatch.setattr(
            "horaedb_tpu.query.path_router.MAX_KEYS", 8
        )
        dist_agg._STEP_CACHE.clear()
        for i in range(2, 30):
            spec = ScanAggSpec(
                n_groups=i, n_buckets=1, n_agg_fields=1
            ).padded()
            dist_agg.make_cached_dist_scan_agg(mesh, spec)
        assert len(dist_agg._STEP_CACHE) <= 8
        # LRU: the most recent shape is still resident (cache keys carry
        # the host-RESOLVED impl, not "auto" — that's what makes a live
        # env flip re-key warm shapes)
        spec = dist_agg._resolved(
            ScanAggSpec(n_groups=29, n_buckets=1, n_agg_fields=1).padded()
        )
        assert spec.segment_impl in ("mxu", "scatter")
        assert (mesh, spec, "cached") in dist_agg._STEP_CACHE
        dist_agg._STEP_CACHE.clear()


GROUP_DDL = (
    "CREATE TABLE kr (host string TAG, v double, w double, "
    "ts timestamp NOT NULL, TIMESTAMP KEY(ts))"
)


def _seed_groupby(db, n=500, hosts=20):
    db.execute(GROUP_DDL)
    rows = ", ".join(
        f"('h{i % hosts}', {float(i)}, {float(2 * i)}, {1_700_000_000_000 + i * 1000})"
        for i in range(n)
    )
    db.execute(f"INSERT INTO kr (host, v, w, ts) VALUES {rows}")


class TestRoutingEndToEnd:
    SQL = "SELECT host, count(1) AS c, sum(v) AS s, min(w) AS lo FROM kr GROUP BY host"

    def test_pinned_impls_agree_over_sql(self, db, monkeypatch):
        _seed_groupby(db)
        results = {}
        for impl in ("scatter", "mxu", "hash"):
            monkeypatch.setenv("HORAEDB_SEGMENT_IMPL", impl)
            out = db.execute(self.SQL)
            results[impl] = sorted(
                tuple(r.values()) for r in out.to_pylist()
            )
            if out.metrics.get("path", "").startswith("device"):
                assert out.metrics.get("kernel") == impl
        assert results["scatter"] == results["mxu"] == results["hash"]

    def test_kernel_in_ledger_and_query_stats(self, db):
        # ledgers open per SQL statement at the PROXY (the wire layer's
        # shared gateway) — route through it like a real request
        from horaedb_tpu.proxy import Proxy

        proxy = Proxy(db)
        try:
            _seed_groupby(db)
            for _ in range(3):
                out = proxy.handle_sql(self.SQL)
            kernel = out.metrics.get("kernel")
            assert kernel in ("mxu", "scatter", "hash", "single", "host")
            stats = proxy.handle_sql(
                "SELECT kernel, agg_segments FROM system.public.query_stats"
            ).to_pylist()
            mine = [r for r in stats if r["kernel"] == kernel]
            assert mine, f"no query_stats row with kernel={kernel}: {stats}"
            assert max(r["agg_segments"] for r in mine) > 0
        finally:
            proxy.close()

    def test_router_disabled_matches_static(self, db, monkeypatch):
        monkeypatch.setenv("HORAEDB_KERNEL_ROUTER", "0")
        _seed_groupby(db)
        for _ in range(3):
            out = db.execute(self.SQL)
        if out.metrics.get("path", "").startswith("device"):
            import jax

            n_seg = 32  # 20 hosts padded to pow2, 1 bucket
            expect = (
                "mxu"
                if jax.default_backend() == "tpu" and n_seg <= mxu_max_segments()
                else "scatter"
            )
            assert out.metrics["kernel"] == expect

    def test_agg_kernel_counter_moves(self, db):
        from horaedb_tpu.utils.metrics import REGISTRY

        _seed_groupby(db)
        db.execute(self.SQL)
        db.execute(self.SQL)
        text = REGISTRY.expose()
        assert "horaedb_agg_kernel_total" in text

    def test_bootstrap_from_query_stats_history(self, db):
        from horaedb_tpu.proxy import Proxy
        from horaedb_tpu.query.path_router import bootstrap_observed_segments

        proxy = Proxy(db)
        try:
            _seed_groupby(db)
            for _ in range(3):
                proxy.handle_sql(self.SQL)
        finally:
            proxy.close()
        # the finalized history carries the live segment count; a fresh
        # sighting of the same normalized SQL shape seeds from it
        segs = bootstrap_observed_segments(self.SQL)
        assert segs is not None and segs > 0
        # an unrelated shape finds nothing
        assert bootstrap_observed_segments(
            "SELECT count(1) FROM never_seen_table"
        ) is None


class TestCacheDtypeAutoTune:
    def _warm_cached(self, db, sql, times=4):
        out = None
        for _ in range(times):
            out = db.execute(sql)
        return out

    def _entry(self, db, table="kr"):
        return db.interpreters.executor.scan_cache._entries.get(table)

    def test_minmax_only_column_stored_bf16(self, db, monkeypatch):
        import jax.numpy as jnp

        monkeypatch.setenv("HORAEDB_CACHE_DTYPE", "auto")
        _seed_groupby(db)
        self._warm_cached(
            db, "SELECT host, min(w) AS lo, max(w) AS hi, sum(v) AS s "
            "FROM kr GROUP BY host",
        )
        entry = self._entry(db)
        assert entry is not None, "cache never built"
        assert entry.value_cols_dev["w"].dtype == jnp.bfloat16
        assert entry.value_cols_dev["v"].dtype == jnp.float32

    def test_promotion_on_new_sum_usage(self, db, monkeypatch):
        import jax.numpy as jnp

        monkeypatch.setenv("HORAEDB_CACHE_DTYPE", "auto")
        _seed_groupby(db)
        self._warm_cached(
            db, "SELECT host, min(w) AS lo FROM kr GROUP BY host"
        )
        entry = self._entry(db)
        assert entry is not None
        assert entry.value_cols_dev["w"].dtype == jnp.bfloat16
        out = self._warm_cached(
            db, "SELECT host, sum(w) AS s FROM kr GROUP BY host"
        )
        entry = self._entry(db)
        assert entry.value_cols_dev["w"].dtype == jnp.float32
        # exact f32 sums after promotion (bf16 would be visibly off)
        expect = {}
        for i in range(500):
            expect.setdefault(f"h{i % 20}", 0.0)
            expect[f"h{i % 20}"] += float(2 * i)
        got = {r["host"]: r["s"] for r in out.to_pylist()}
        for h, s in expect.items():
            assert abs(got[h] - s) < 1e-6, h

    def test_filter_usage_pins_f32(self, db, monkeypatch):
        import jax.numpy as jnp

        monkeypatch.setenv("HORAEDB_CACHE_DTYPE", "auto")
        _seed_groupby(db)
        self._warm_cached(
            db, "SELECT host, min(w) AS lo FROM kr WHERE w > 10 GROUP BY host"
        )
        entry = self._entry(db)
        assert entry is not None
        assert entry.value_cols_dev["w"].dtype == jnp.float32

    def test_default_mode_stays_f32(self, db, monkeypatch):
        import jax.numpy as jnp

        monkeypatch.delenv("HORAEDB_CACHE_DTYPE", raising=False)
        _seed_groupby(db)
        self._warm_cached(
            db, "SELECT host, min(w) AS lo FROM kr GROUP BY host"
        )
        entry = self._entry(db)
        assert entry is not None
        assert entry.value_cols_dev["w"].dtype == jnp.float32


class TestRefusedKernel:
    """The served cached path's guard (PR 27): a packed program the device
    refuses for memory is a typed event and another route's exact answer,
    never the request's error."""

    SQL = TestRoutingEndToEnd.SQL
    # what the v5e's compiler said of the scatter impl at 2^25 rows (PR 27)
    REFUSAL = (
        "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
        "memory in memory space hbm. Used 18.28G of 15.75G hbm. Exceeded "
        "hbm capacity by 2.54G.\n\nTotal hbm usage >= 18.80G:"
    )

    @pytest.fixture()
    def served(self, db, monkeypatch):
        """-> (run, refuse, calls): ``run()`` serves SQL once through the
        proxy; ``refuse`` is the set of impls whose packed program the fake
        device refuses; ``calls`` lists the impls dispatched."""
        import jax

        from horaedb_tpu.ops import scan_agg
        from horaedb_tpu.proxy import Proxy

        monkeypatch.delenv("HORAEDB_SEGMENT_IMPL", raising=False)
        monkeypatch.setenv("HORAEDB_ADAPTIVE_PATH", "0")
        real = scan_agg.cached_scan_agg_packed
        refuse: set = set()
        calls: list = []

        def packed(*args, **kwargs):
            calls.append(kwargs["segment_impl"])
            if kwargs["segment_impl"] in refuse:
                raise jax.errors.JaxRuntimeError(self.REFUSAL)
            return real(*args, **kwargs)

        packed.lower = real.lower
        monkeypatch.setattr(scan_agg, "cached_scan_agg_packed", packed)
        _seed_groupby(db)
        proxy = Proxy(db)
        yield (lambda: proxy.handle_sql(self.SQL)), refuse, calls
        proxy.close()

    @staticmethod
    def _rows(out):
        return sorted(tuple(r.values()) for r in out.to_pylist())

    @staticmethod
    def _events():
        from horaedb_tpu.utils.events import EVENT_STORE

        return EVENT_STORE.list(kind="kernel_refused")

    def _want(self, db):
        # the host's answer: 25 rows a host, v = i, w = 2 i
        return sorted(
            (f"h{h}", 25, float(sum(range(h, 500, 20))), float(2 * h))
            for h in range(20)
        )

    def test_refused_impl_is_an_event_and_the_next_candidate_serves(
        self, db, served
    ):
        from horaedb_tpu.query.path_router import KERNEL_ROUTER
        from horaedb_tpu.utils.metrics import REGISTRY

        run, refuse, calls = served
        counter = 'horaedb_events_total{kind="kernel_refused"}'
        assert f"{counter} " in REGISTRY.expose()  # exported before any
        before, seen = len(self._events()), []
        refuse.add("scatter")  # the CPU's seed for 32 segments
        for _ in range(5):
            out = run()
            assert self._rows(out) == self._want(db)
            seen.append((out.metrics["path"], out.metrics.get("kernel")))
        # served from the cache by the other candidate once the cache is built
        assert seen[-1] == ("device-cached", "mxu"), seen
        events = self._events()[before:]
        assert len(events) == 1, events
        attrs = events[0]["attrs"]
        assert attrs["kernel"] == "cached_packed" and attrs["impl"] == "scatter"
        assert attrs["message"] == self.REFUSAL.splitlines()[0]
        assert "scatter" in attrs["shape"]
        assert calls.count("scatter") == 1  # never offered for the shape again
        (stats,) = [
            KERNEL_ROUTER.stats(k) for k in list(KERNEL_ROUTER._stats)
            if KERNEL_ROUTER.stats(k).get("refused")
        ]
        assert stats["refused"] == {"scatter"}

    def test_every_impl_refused_is_the_hosts_exact_answer(self, db, served):
        run, refuse, calls = served
        before = len(self._events())
        refuse.update(("scatter", "mxu", "hash"))
        paths = []
        for _ in range(5):
            out = run()  # never raises
            assert self._rows(out) == self._want(db)
            paths.append(out.metrics["path"])
        assert paths[-1] == "host" and out.metrics["kernel_refused"] is True
        refused = [e["attrs"]["impl"] for e in self._events()[before:]]
        assert sorted(refused) == ["mxu", "scatter"]  # hash: never a candidate
        assert sorted(calls) == ["mxu", "scatter"]  # one try each, then none

    def test_a_pinned_impl_refused_goes_to_the_host(self, db, served, monkeypatch):
        run, refuse, calls = served
        monkeypatch.setenv("HORAEDB_SEGMENT_IMPL", "scatter")
        refuse.add("scatter")
        for _ in range(4):
            out = run()
            assert self._rows(out) == self._want(db)
        assert out.metrics["path"] == "host"

    def test_another_failure_is_still_the_requests_error(self, db, monkeypatch):
        from horaedb_tpu.ops import scan_agg

        monkeypatch.setenv("HORAEDB_ADAPTIVE_PATH", "0")

        def broken(*args, **kwargs):
            raise ValueError("not a refusal")

        _seed_groupby(db)
        db.execute(self.SQL)  # first sighting: no cached dispatch yet
        monkeypatch.setattr(scan_agg, "cached_scan_agg_packed", broken)
        with pytest.raises(ValueError, match="not a refusal"):
            for _ in range(3):
                db.execute(self.SQL)
