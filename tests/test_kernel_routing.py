"""Which segment kernel runs (query/kernel_choice.py; PR 6, PR 30).

Covers: the segment-impl equivalence property (mxu / scatter must be
indistinguishable on every input), the one chooser — a concrete impl at
every shape, the static rule at the benchmark cells' shapes, the kernels'
refusal of anything but a concrete name — the KernelRouter's
probe/serve/re-probe loop, the guarded env-int satellite, the dist-agg
step-cache LRU bound, the scan-cache dtype auto-tuning, the ledger
surfaces and the refused-program guard.
"""

import dataclasses

import numpy as np
import pytest

import horaedb_tpu
from horaedb_tpu.ops.encoding import build_padded_batch
from horaedb_tpu.ops.scan_agg import ScanAggSpec, scan_aggregate
from horaedb_tpu.query import kernel_choice
from horaedb_tpu.query.kernel_choice import KERNEL_ROUTER, KernelRouter


@pytest.fixture()
def db():
    conn = horaedb_tpu.connect(None)
    yield conn
    conn.close()


@pytest.fixture(autouse=True)
def _fresh_router():
    KERNEL_ROUTER.reset()
    yield
    KERNEL_ROUTER.reset()


def _dispatch(batch, spec, impl, literals=()):
    return scan_aggregate(
        batch, dataclasses.replace(spec, segment_impl=impl), list(literals)
    )


def _only(monkeypatch, *impls):
    """Offer the chooser these candidates and no others."""
    monkeypatch.setattr(
        kernel_choice, "candidate_kernels", lambda *a, **k: tuple(impls)
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
    """Satellite: both segment impls return identical
    counts/sums/mins/maxs over randomized specs."""

    def test_randomized_specs(self):
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
            ref = _dispatch(batch, spec, "scatter")
            _assert_states_equal(
                ref, _dispatch(batch, spec, "mxu"), f"trial {trial}: mxu"
            )

    def test_single_segment_bypasses_routing(self):
        """n_seg == 1 (global aggregate): the chooser names the
        pure-reduction impl, hands out no token and asks no router."""
        rng = np.random.default_rng(3)
        n = 300
        batch = build_padded_batch(
            np.zeros(n, np.int32), np.zeros(n, np.int32),
            np.ones(n, bool), [rng.normal(size=n).astype(np.float32)],
        )
        spec = ScanAggSpec(n_groups=1, n_buckets=1, n_agg_fields=1).padded()
        chosen, token = kernel_choice.choose(("t", "shape"), spec, n)
        assert (chosen.segment_impl, token) == ("single", None)
        assert not KERNEL_ROUTER._stats
        _assert_states_equal(
            scan_aggregate(batch, chosen), _dispatch(batch, spec, "scatter"),
            "single",
        )

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
        r = KernelRouter()
        cands = ("scatter", "mxu", "third")  # any number of candidates
        seen = []
        # synthetic latencies: the third fastest; first sample of each impl
        # is compile-tainted (huge) and must not poison the estimate
        lat = {"scatter": 0.05, "mxu": 0.03, "third": 0.01}
        for i in range(2 * len(cands)):
            k, est = r.choose("key", "scatter", cands)
            assert est is None  # no clean sample of it yet
            seen.append(k)
            r.record("key", k, 5.0 if seen.count(k) == 1 else lat[k])
        assert set(seen) == set(cands)  # every candidate warmed
        # the winner comes with its estimate: the journal's prediction
        assert r.choose("key", "scatter", cands) == ("third", lat["third"])
        r.record("key", "third", lat["third"])

    def test_reprobes_losers_on_cadence(self):
        from horaedb_tpu.query.path_router import PROBE_EVERY

        r = KernelRouter()
        cands = ("scatter", "mxu")
        for i in range(2 * len(cands)):
            k, _ = r.choose("key", "scatter", cands)
            r.record("key", k, 0.01 if k == "scatter" else 0.05)
        def serve(calls):
            picks = []
            for i in range(calls):
                k, _ = r.choose("key", "scatter", cands)
                picks.append(k)
                r.record("key", k, 0.01 if k == "scatter" else 0.05)
            return picks

        # mxu's one clean sample is confirmed after PROBE_EVERY calls ...
        assert serve(PROBE_EVERY + 1) == ["scatter"] * PROBE_EVERY + ["mxu"]
        # ... then the cadence is served time, not calls: mxu (5 x slower) is
        # due once scatter has served PROBE_EVERY x 0.05 s, every 81st call
        picks = serve(2 * (5 * PROBE_EVERY + 2))
        assert picks.count("mxu") == 2  # losers still get probed
        assert "mxu" not in picks[:5 * PROBE_EVERY - 1]
        # the probes cost one part in PROBE_EVERY + 1 of the served seconds
        assert picks.count("mxu") * 0.05 <= (
            picks.count("scatter") * 0.01 + picks.count("mxu") * 0.05
        ) / (PROBE_EVERY + 1) + 0.05

    def test_lru_bound(self):
        from horaedb_tpu.query.path_router import MAX_KEYS

        r = KernelRouter()
        for i in range(MAX_KEYS + 50):
            r.choose(("k", i), "scatter", ("scatter",))
        assert len(r._stats) <= MAX_KEYS

    def test_candidate_gating(self, monkeypatch):
        import jax

        from horaedb_tpu.query.kernel_choice import candidate_kernels

        # no matrix unit here: the one-hot is worth a probe to 256 segments
        assert candidate_kernels(64, 10_000) == ("scatter", "mxu")
        assert candidate_kernels(256, 10_000) == ("scatter", "mxu")
        assert candidate_kernels(257, 10_000) == ("scatter",)
        # on the TPU: to four times the static crossover
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert candidate_kernels(4 * 8192, 10_000) == ("scatter", "mxu")
        assert candidate_kernels(4 * 8192 + 1, 10_000) == ("scatter",)
        # scatter is always a candidate
        assert "scatter" in candidate_kernels(10**6, 10_000)

    def test_seed_kernel(self, monkeypatch):
        """The static rule, which seeds a never-measured shape."""
        import jax

        from horaedb_tpu.query.kernel_choice import static_kernel

        assert static_kernel(1024) == "scatter"  # the CPU has no MXU
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert static_kernel(1) == "single"
        assert static_kernel(1024) == "mxu"
        assert static_kernel(8192) == "mxu"
        assert static_kernel(8193) == "scatter"
        assert static_kernel(10**6) == "scatter"
        # a fresh key starts on the seed, then warms the other candidate
        spec = ScanAggSpec(n_groups=64, n_buckets=16, n_agg_fields=1).padded()
        picks = []
        for _ in range(3):
            chosen, token = kernel_choice.choose(("t", "seed"), spec, 10_000)
            picks.append(chosen.segment_impl)
            KERNEL_ROUTER.record(token[0], token[1], 0.01)
        assert picks == ["mxu", "mxu", "scatter"]


# The four cells of BENCHMARK.json as the served path hands them to the
# chooser, and what the chip found there (PERF.md §5, "programs (XLA
# Modules): ms per execution"): cell -> (n_groups, n_buckets, fields,
# need_minmax, resident rows, the fastest program's impl, the candidates).
CELLS = {
    # cached_scan_single 1.868 ms
    "high-cpu-count-max": (1, 1, 1, True, 4_320_000, "single", None),
    # cached_scan_mxu_sel 2.494 ms / cached_scan_scatter_sel 2.667 ms
    "single-groupby-5-8-1": (
        1, 64, 5, True, 4_320_000, "mxu", ("scatter", "mxu")),
    # cached_scan_scatter 158.5 ms / cached_scan_mxu 480.4 ms
    "double-groupby-all": (
        1024, 16, 10, False, 4_320_000, "scatter", ("scatter", "mxu")),
    # cached_scan_scatter 721.7 ms, mxu not offered
    "cpu-4000x12h.double-groupby-all": (
        4096, 16, 10, False, 17_280_000, "scatter", ("scatter",)),
}


class TestOneChooser:
    """PR 30: one module decides, and what it hands on is concrete."""

    @pytest.mark.parametrize("cell", sorted(CELLS))
    def test_choose_is_concrete_and_the_rule_names_the_chips_fastest(
        self, monkeypatch, cell
    ):
        import jax

        from horaedb_tpu.obs import device

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(
            device, "device_free_bytes", lambda: int(15.75 * 2**30)
        )
        n_groups, n_buckets, fields, minmax, rows, fastest, cands = CELLS[cell]
        n_seg = n_groups * n_buckets
        assert n_seg in (1, 64, 16_384, 65_536)
        spec = ScanAggSpec(
            n_groups=n_groups, n_buckets=n_buckets, n_agg_fields=fields,
            need_minmax=minmax,
        ).padded()
        assert spec.segment_impl == "auto"  # unchosen until here
        assert kernel_choice.static_kernel(n_seg) == fastest
        chosen, token = kernel_choice.choose(("cpu", cell), spec, rows)
        assert chosen.segment_impl == fastest  # a fresh key starts on the seed
        assert chosen == dataclasses.replace(spec, segment_impl=fastest)
        if cands is None:
            assert token is None
        else:
            assert token[1] == fastest
            assert kernel_choice.candidate_kernels(
                n_seg, rows, fields, minmax
            ) == cands

    @staticmethod
    def _entry_points():
        """name -> call(segment_impl): every way into a segment kernel."""
        import jax
        import jax.numpy as jnp

        from horaedb_tpu.ops import scan_agg
        from horaedb_tpu.parallel import dist_agg
        from horaedb_tpu.parallel.mesh import serving_mesh

        n, s = 128, 3
        batch = build_padded_batch(
            np.zeros(n, np.int32), np.zeros(n, np.int32), np.ones(n, bool),
            [np.ones(n, np.float32)],
        )
        static = dict(n_groups=8, n_buckets=1, n_agg_fields=1,
                      numeric_filters=(), need_minmax=True)

        def spec(impl):
            return ScanAggSpec(n_groups=8, n_buckets=1, n_agg_fields=1,
                               segment_impl=impl)

        resident = (jnp.zeros(n, jnp.int32), jnp.zeros(n, jnp.int32),
                    jnp.ones((1, n), jnp.float32))
        session = jnp.zeros(2 * (s + 1), jnp.int32)
        dyn = jnp.asarray([0, 10, 0, 1], jnp.int32)
        layouts = dict(value_layouts=(("raw",),), ts_layout=("raw",),
                       series_layout=("raw",))
        packed = ((resident[0],), (resident[1],), resident[2], session, dyn)
        return {
            "scan_agg_body": lambda impl: scan_agg.scan_agg_body(
                jnp.asarray(batch.group_codes), jnp.asarray(batch.bucket_ids),
                jnp.asarray(batch.mask), jnp.asarray(batch.values),
                jnp.zeros(0, jnp.float32), segment_impl=impl, **static),
            "scan_aggregate": lambda impl: scan_aggregate(batch, spec(impl)),
            "cached_scan_agg_packed": lambda impl: scan_agg.cached_scan_agg_packed(
                *packed, segment_impl=impl, selective=False, **static, **layouts),
            "cached_scan_agg_packed.lower": lambda impl: (
                scan_agg.cached_scan_agg_packed.lower(
                    *packed, segment_impl=impl, selective=True, **static,
                    **layouts)),
            "cached_scan_agg_cohort": lambda impl: scan_agg.cached_scan_agg_cohort(
                *packed[:3], jnp.stack([session] * 2), jnp.stack([dyn] * 2),
                segment_impl=impl, **static, **layouts),
            "dist_scan_aggregate": lambda impl: dist_agg.dist_scan_aggregate(
                serving_mesh(), batch, spec(impl)),
            "make_dist_scan_agg": lambda impl: dist_agg.make_dist_scan_agg(
                serving_mesh(), spec(impl)),
            "make_cached_dist_scan_agg": lambda impl: (
                dist_agg.make_cached_dist_scan_agg(serving_mesh(), spec(impl))),
        }

    @pytest.mark.parametrize("entry", [
        "scan_agg_body", "scan_aggregate", "cached_scan_agg_packed",
        "cached_scan_agg_packed.lower", "cached_scan_agg_cohort",
        "dist_scan_aggregate", "make_dist_scan_agg",
        "make_cached_dist_scan_agg",
    ])
    def test_a_kernel_takes_only_a_concrete_name(self, entry):
        """No entry point derives an impl: "auto" (an unchosen spec) and
        "hash" (the kernel that went) raise, a chosen name runs."""
        call = self._entry_points()[entry]
        for impl in ("auto", "hash"):
            with pytest.raises(ValueError, match="segment_impl"):
                call(impl)
        call("scatter")

    def test_a_routed_request_takes_the_routers_lock_twice(self):
        """``choose`` hands the impl's estimate back with the choice and
        ``finish`` records once: two locks a request (five before)."""
        from horaedb_tpu.ops.scan_agg import AggState

        class Counting:
            def __init__(self, lock):
                self.lock, self.taken = lock, 0

            def __enter__(self):
                self.taken += 1
                return self.lock.__enter__()

            def __exit__(self, *exc):
                return self.lock.__exit__(*exc)

        lock = KERNEL_ROUTER._lock = Counting(KERNEL_ROUTER._lock)
        try:
            spec = ScanAggSpec(n_groups=8, n_buckets=4, n_agg_fields=1).padded()
            chosen, token = kernel_choice.choose(("t", "locks"), spec, 1000)
            state = AggState(counts=np.ones((8, 4), np.int64), sums=None,
                             mins=None, maxs=None)
            m: dict = {}
            kernel_choice.finish(token, chosen, m, state, 0.01)
        finally:
            KERNEL_ROUTER._lock = lock.lock
        assert lock.taken == 2
        assert m["kernel"] == chosen.segment_impl != "auto"

    REMOVED = (
        "HORAEDB_SEGMENT_IMPL", "HORAEDB_KERNEL_ROUTER",
        "HORAEDB_MXU_MAX_SEGMENTS", "HORAEDB_HASH_HOST_MAX_ROWS",
        "HORAEDB_HASH_PROBE_ROUNDS", "HORAEDB_HASH_MAX_SLOTS", "hash_slots",
    )

    @staticmethod
    def _sources(*parts):
        import os

        root = os.path.join(os.path.dirname(horaedb_tpu.__file__), *parts)
        for folder, _, files in os.walk(root):
            for name in files:
                if name.endswith(".py"):
                    path = os.path.join(folder, name)
                    yield os.path.relpath(path, root), open(path).read()

    def test_the_pins_the_switch_and_the_third_kernel_are_gone(self):
        found = [
            (path, name) for path, text in self._sources()
            for name in self.REMOVED if name in text
        ]
        assert not found, found

    def test_ops_imports_nothing_of_query(self):
        """The arrow points down: query/kernel_choice -> ops, never back."""
        import re

        back = re.compile(
            r"^\s*(from\s+(\.\.|horaedb_tpu\.)query\b"
            r"|import\s+horaedb_tpu\.query\b|from\s+\.\.\s+import\s+query\b)",
            re.M,
        )
        found = [path for path, text in self._sources("ops") if back.search(text)]
        assert not found, found


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
                n_groups=i, n_buckets=1, n_agg_fields=1,
                segment_impl="scatter",
            ).padded()
            dist_agg.make_cached_dist_scan_agg(mesh, spec)
        assert len(dist_agg._STEP_CACHE) <= 8
        # LRU: the most recent shape is still resident, keyed by the spec
        # with the chooser's concrete impl
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

    @pytest.mark.parametrize("impl", ["scatter", "mxu"])
    def test_pinned_impls_agree_over_sql(self, db, monkeypatch, impl):
        """Each impl, the only candidate offered, agrees with the host."""
        _only(monkeypatch, impl)
        monkeypatch.setenv("HORAEDB_ADAPTIVE_PATH", "0")
        _seed_groupby(db)
        want = sorted(
            (f"h{h}", 25, float(sum(range(h, 500, 20))), float(2 * h))
            for h in range(20)
        )
        for _ in range(3):
            out = db.execute(self.SQL)
            assert sorted(tuple(r.values()) for r in out.to_pylist()) == want
            assert out.metrics["path"].startswith("device")
            assert out.metrics["kernel"] == impl
        assert out.metrics["path"] == "device-cached"

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
            assert kernel in ("mxu", "scatter")
            stats = proxy.handle_sql(
                "SELECT kernel, agg_segments FROM system.public.query_stats"
            ).to_pylist()
            mine = [r for r in stats if r["kernel"] == kernel]
            assert mine, f"no query_stats row with kernel={kernel}: {stats}"
            assert max(r["agg_segments"] for r in mine) > 0
        finally:
            proxy.close()

    def test_agg_kernel_counter_moves(self, db):
        from horaedb_tpu.utils.metrics import REGISTRY

        _seed_groupby(db)
        db.execute(self.SQL)
        db.execute(self.SQL)
        text = REGISTRY.expose()
        assert "horaedb_agg_kernel_total" in text


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
        refuse.update(("scatter", "mxu"))
        paths = []
        for _ in range(5):
            out = run()  # never raises
            assert self._rows(out) == self._want(db)
            paths.append(out.metrics["path"])
        assert paths[-1] == "host" and out.metrics["kernel_refused"] is True
        refused = [e["attrs"]["impl"] for e in self._events()[before:]]
        assert sorted(refused) == ["mxu", "scatter"]
        assert sorted(calls) == ["mxu", "scatter"]  # one try each, then none

    def test_the_only_candidate_refused_goes_to_the_host(
        self, db, served, monkeypatch
    ):
        run, refuse, calls = served
        _only(monkeypatch, "scatter")
        before = len(self._events())
        refuse.add("scatter")
        for _ in range(4):
            out = run()
            assert self._rows(out) == self._want(db)
        assert out.metrics["path"] == "host"
        assert out.metrics["kernel_refused"] is True
        assert calls == ["scatter"]  # one try, then never offered again
        assert [e["attrs"]["impl"] for e in self._events()[before:]] == ["scatter"]

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
