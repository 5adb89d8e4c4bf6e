"""How a router keeps its estimate of a losing route
(query/path_router.py): which samples it folds, and when it pays for
another.

* The schedule: a loser is re-probed once the winner has SERVED for
  ``PROBE_EVERY`` times the loser's estimate (each serve counted at the
  winner's estimate), so re-measuring it costs at most one part in
  ``PROBE_EVERY + 1`` of the serving time at any ratio. One schedule, two
  routers: every property is checked on both.
* The guard: an estimate that rests on one sample is confirmed after
  ``PROBE_EVERY`` calls of the winner, whatever that sample read.
* The clean sample: a request that compiled a program or built the scan
  cache never becomes an estimate (the cold start of a served cell,
  replayed from its log; and through the executor on the CPU)."""

import pytest

from horaedb_tpu.query.kernel_choice import KernelRouter
from horaedb_tpu.query.path_router import (
    _PROBES,
    PROBE_EVERY,
    PathRouter,
    plan_shape_key,
)
from horaedb_tpu.utils import querystats
from horaedb_tpu.utils.metrics import Counter

RATIOS = (1, 4, 16, 64)


class _Path:
    """PathRouter behind the two calls the tests need; ``win`` is the route
    that starts as the winner."""

    label = "path"
    win, lose = "device", "host"
    first_samples = 2  # device, host

    def __init__(self):
        self.r = PathRouter()

    def choose(self):
        return self.r.choose("key")

    def record(self, route, seconds):
        self.r.record("key", route, seconds)


class _Kernel(_Path):
    label = "kernel"
    win, lose = "scatter", "mxu"
    first_samples = 4  # two of each impl, the first compile-tainted

    def __init__(self):
        self.r = KernelRouter()

    def choose(self):
        return self.r.choose("key", self.win, (self.win, self.lose))[0]


@pytest.fixture(params=[_Path, _Kernel], ids=["path", "kernel"])
def router(request, monkeypatch):
    r = request.param()
    # the process's counter may move under a server another test left up
    monkeypatch.setitem(_PROBES, r.label, Counter("probes", ""))
    return r


def _warm(router, lat):
    """The routers' own first samples, then the loser's confirmation: its
    one sample is trusted for ``PROBE_EVERY`` calls of the winner. Both
    routes then rest on two samples and the budget rules."""
    for _ in range(router.first_samples):
        k = router.choose()
        router.record(k, lat[k])
    picks = _serve(router, lat, PROBE_EVERY + 1)
    assert picks == [router.win] * PROBE_EVERY + [router.lose]


def _serve(router, lat, calls):
    """``calls`` requests, each recorded at its route's latency -> picks."""
    picks = []
    for _ in range(calls):
        k = router.choose()
        picks.append(k)
        router.record(k, lat[k](len(picks)) if callable(lat[k]) else lat[k])
    return picks


def _probes(router) -> float:
    return _PROBES[router.label].value


class TestProbeSchedule:
    @pytest.mark.parametrize("ratio", RATIOS)
    def test_losers_share_of_served_seconds(self, router, ratio):
        """Over ten probe periods the loser takes at most 1 / (PROBE_EVERY
        + 1) of the served seconds, give or take one probe — and is never
        starved."""
        lat = {router.win: 1.0, router.lose: float(ratio)}
        _warm(router, lat)
        period = PROBE_EVERY * ratio + 1  # winner calls, then the probe
        picks = _serve(router, lat, 10 * period)
        lost = picks.count(router.lose) * lat[router.lose]
        total = lost + picks.count(router.win) * lat[router.win]
        assert lost > 0  # no starvation
        assert lost / total <= 1 / (PROBE_EVERY + 1) + lat[router.lose] / total
        # never more often than the fixed 1-in-16 cadence it replaces
        at = [i for i, k in enumerate(picks) if k == router.lose]
        assert all(b - a > PROBE_EVERY for a, b in zip(at, at[1:]))
        assert len(at) == 10

    def test_equal_routes_probe_within_every_17_calls(self, router):
        lat = {router.win: 1.0, router.lose: 1.0}
        _warm(router, lat)
        picks = _serve(router, lat, 10 * (PROBE_EVERY + 1))
        for i in range(len(picks) - PROBE_EVERY):
            assert router.lose in picks[i:i + PROBE_EVERY + 1], i

    def test_winner_that_slows_flips_with_no_probe(self, router):
        """The cache was evicted, the data grew: the winner's own samples
        carry its estimate past the loser's and the route flips, before any
        probe is due."""
        lat = {router.win: 1.0, router.lose: 2.0}
        _warm(router, lat)
        before = _probes(router)
        lat[router.win] = 3.0
        picks = _serve(router, lat, 12)
        flip = picks.index(router.lose)
        assert flip <= 9  # 1.1 ** 8 > 2: up by 10 % a sample
        assert set(picks[flip:]) == {router.lose}  # served, not probed
        assert _probes(router) == before

    @pytest.mark.parametrize("ratio", RATIOS)
    def test_loser_that_improved_is_found_at_the_due_probe(self, router, ratio):
        lat = {router.win: 1.0, router.lose: float(ratio)}
        _warm(router, lat)
        lat[router.lose] = 0.1  # e.g. the scan cache finished building
        picks = _serve(router, lat, PROBE_EVERY * ratio + 2)
        # due within PROBE_EVERY x its last time of serving ...
        assert picks.index(router.lose) <= PROBE_EVERY * ratio
        # ... and from that sample on it is the winner
        assert picks[-1] == router.lose
        assert set(picks[picks.index(router.lose):]) == {router.lose}

    def test_concurrent_callers_take_one_probe_not_n(self, router):
        """choose() N times with no record() between (requests in flight
        together): the budget is spent at hand-out."""
        lat = {router.win: 1.0, router.lose: 4.0}
        _warm(router, lat)
        picks = _serve(router, lat, PROBE_EVERY * 4)
        assert router.lose not in picks
        before = _probes(router)
        in_flight = [router.choose() for _ in range(8)]
        assert in_flight.count(router.lose) == 1
        assert _probes(router) == before + 1
        # without record() nothing is served, so nothing more falls due
        assert {router.choose() for _ in range(4 * PROBE_EVERY)} == {router.win}

    @pytest.mark.parametrize("sigma", (0.3, 1.0, 1.5))
    def test_noisy_samples_never_probe_more_than_the_old_cadence(
        self, router, sigma
    ):
        """Samples that spread (eight clients on one GIL, a GC pause) must
        not buy probes: served time is paid in at the winner's ESTIMATE,
        which no sample can push past the loser's, so PROBE_EVERY calls at
        least lie between two probes whatever the noise. (Paid in raw, a
        lognormal sigma of 1 probed one call in 10, not one in 17.) One slow
        sample does not buy one either."""
        import random

        rnd = random.Random(28)
        lat = {router.win: 1.0, router.lose: 1.5}
        _warm(router, lat)
        calls, at, last = 4000, [], _probes(router)
        for i in range(calls):
            k = router.choose()
            if _probes(router) != last:
                at.append(i)
                last = _probes(router)
            router.record(k, lat[k] * rnd.lognormvariate(0.0, sigma))
        assert 1 <= len(at) <= calls / (PROBE_EVERY + 1)
        assert all(b - a > PROBE_EVERY for a, b in zip(at, at[1:]))

    def test_threads_share_one_budget(self, router):
        """Eight callers on one key (cell 1's eight clients), more than the
        cores allow to run at once: no sample is lost to a race, and the
        loser is still handed out no more than once per PROBE_EVERY + 1
        recorded calls."""
        import sys
        import threading

        lat = {router.win: 1.0, router.lose: 2.0}
        _warm(router, lat)
        before, per_thread, errors = _probes(router), 1500, []

        def client():
            try:
                for _ in range(per_thread):
                    k = router.choose()
                    router.record(k, lat[k])
            except Exception as e:  # the assert below reports it
                errors.append(e)

        threads = [threading.Thread(target=client) for _ in range(8)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(interval)
        assert not errors and not any(t.is_alive() for t in threads)
        probes = _probes(router) - before
        assert 1 <= probes <= 8 * per_thread / (PROBE_EVERY + 1)
        n = router.r.stats("key")["n"]
        assert n[router.win] + n[router.lose] >= 8 * per_thread

    def test_one_hiccup_buys_no_probe(self, router):
        lat = {router.win: 1.0, router.lose: 5.0}
        _warm(router, lat)
        router.record(router.win, 1000.0)  # a GC pause, a stall
        assert router.choose() == router.win

    @pytest.mark.parametrize("ratio", RATIOS)
    @pytest.mark.parametrize("cls", [_Path, _Kernel], ids=["path", "kernel"])
    def test_rule_has_no_unit(self, cls, ratio):
        """engine/merge.py records seconds PER ROW: every sample scaled by
        1e-6 gives the same picks (jittered samples, so that no budget
        falls due by a rounding of the sum)."""
        runs = []
        for scale in (1.0, 1e-6):
            router = cls()
            lat = {
                router.win: lambda i, s=scale: s * (1.0 + 0.07 * (i % 5)),
                router.lose: lambda i, s=scale: s * ratio * 1.03,
            }
            runs.append(_serve(router, lat, 3 * (PROBE_EVERY * ratio + 1) + 8))
        assert runs[0] == runs[1]
        assert runs[0].count(cls.lose) >= 3


class TestKernelRouterLosers:
    # the schedule takes any number of losers: "third" is only a label
    CANDS = ("scatter", "mxu", "third")
    LAT = {"scatter": 1.0, "mxu": 2.0, "third": 8.0}

    def _run(self, r, calls):
        picks = []
        for _ in range(calls):
            k, _ = r.choose("key", "scatter", self.CANDS)
            picks.append(k)
            r.record("key", k, self.LAT[k])
        return picks

    def _warmed(self):
        """Two samples of each impl (the first dropped), then both losers'
        confirmations: each estimate rests on two samples."""
        r = KernelRouter()
        picks = self._run(r, 2 * len(self.CANDS) + PROBE_EVERY + 2)
        assert picks[-2:] == ["mxu", "third"]
        return r

    def test_each_loser_has_its_own_budget(self):
        """Two losers, 2 x and 8 x slower: each is probed on its own time,
        the nearer one four times as often, each within its share."""
        picks = self._run(self._warmed(), 10 * (PROBE_EVERY * 8 + 1))
        served = picks.count("scatter") * self.LAT["scatter"]
        for k in ("mxu", "third"):
            n = picks.count(k)
            assert n >= 1
            assert (n - 1) * self.LAT[k] <= served / PROBE_EVERY
        assert 3.5 <= picks.count("mxu") / picks.count("third") <= 4.5

    def test_most_overdue_loser_goes_first(self):
        r = self._warmed()
        for _ in range(200):  # served with no choose(): both fall due
            r.record("key", "scatter", self.LAT["scatter"])
        # mxu is 200 / 2 = 100 of its times behind, the third 200 / 8 = 25
        # (the impl, its estimate: what the decision journal predicts from)
        assert r.choose("key", "scatter", self.CANDS) == ("mxu", 2.0)
        assert r.choose("key", "scatter", self.CANDS) == ("third", 8.0)
        assert r.choose("key", "scatter", self.CANDS) == ("scatter", 1.0)

    def test_refused_kernel_is_never_a_probe(self):
        r = self._warmed()
        r.refuse("key", "third")
        picks = self._run(r, 4 * (PROBE_EVERY * 8 + 1))
        assert "third" not in picks
        assert "mxu" in picks  # the other loser keeps its schedule
        # a refused WINNER takes its estimate with it, also when a dispatch
        # of it that was in flight reports after the refusal
        r.refuse("key", "scatter")
        r.record("key", "scatter", self.LAT["scatter"])
        assert "scatter" not in r.stats("key")["t"]
        picks = [r.choose("key", "scatter", self.CANDS)[0] for _ in range(3)]
        assert set(picks) == {"mxu"}


class TestOneSampleIsNotTrustedForLong:
    """The guard: whatever tainted a loser's only sample, the wrong
    estimate lives ``PROBE_EVERY`` calls, not ``PROBE_EVERY`` times
    itself."""

    def test_a_compile_folded_as_clean_is_confirmed_within_sixteen_calls(self):
        """PR 28's trap with the executor's signal taken away: the device's
        one sample is a compile of 21.1 s, the host reads 3.7 s."""
        r = PathRouter()
        r.record("k", "device", 21.1)
        assert r.choose("k") == "host"
        r.record("k", "host", 3.7)
        picks = []
        while "device" not in picks:
            picks.append(r.choose("k"))
            r.record("k", picks[-1], 3.7 if picks[-1] == "host" else 0.38)
            assert len(picks) <= PROBE_EVERY
        # not after 16 x 21.1 s = 338 s of host serving, ~90 requests
        assert picks == ["host"] * (PROBE_EVERY - 1) + ["device"]
        assert r.stats("k")["device"] == 0.38
        assert r.choose("k") == "device"  # and from its second sample it wins

    def test_two_samples_wait_for_the_budget(self, router):
        """From its second sample on a loser is due by served time alone:
        not after PROBE_EVERY calls, but after PROBE_EVERY of its own
        times."""
        lat = {router.win: 1.0, router.lose: 8.0}
        _warm(router, lat)
        picks = _serve(router, lat, PROBE_EVERY * 8 + 1)
        assert picks.index(router.lose) == PROBE_EVERY * 8

    def test_a_tainted_confirmation_is_asked_for_again(self):
        """The confirmation itself compiled (dropped): the loser still has
        one sample, and is due after another PROBE_EVERY calls."""
        r = PathRouter()
        r.record("k", "device", 1.0)
        r.record("k", "host", 4.0)
        lat = {"device": 1.0, "host": 4.0}
        picks = []
        for _ in range(2 * (PROBE_EVERY + 1)):
            picks.append(r.choose("k"))
            first_probe = picks.count("host") == 1 and picks[-1] == "host"
            r.record("k", picks[-1], lat[picks[-1]], clean=not first_probe)
        assert [i for i, k in enumerate(picks) if k == "host"] == [16, 33]
        assert r.stats("k")["n"]["host"] == 2


class TestOnlyACleanSampleBecomesAnEstimate:
    def test_cold_start_replayed_from_the_log(self):
        """The parent's cold-cache run of cpu-1000x12h.double-groupby-all
        (PERF.md §6, PR 29): two compiles and a build come before the first
        clean device serve. The route is ``device`` from the first judged
        call on, and no estimate ever reads 21 s."""
        r = PathRouter()
        log = [  # (seconds, clean)
            (4.409, False),   # uncached device path, compiled
            (28.925, False),  # the scan cache's build
            (21.102, False),  # first cached program, compiled
            (0.38, True),
        ]
        for seconds, clean in log:
            assert r.choose("k") == "device"
            r.record("k", "device", seconds, clean=clean)
            assert r.stats("k").get("device", 0.0) < 1.0
        assert r.choose("k") == "host"  # the host's one sample
        r.record("k", "host", 3.7)
        lat = {"device": 0.38, "host": 3.7}
        picks = _serve_path(r, lat, 3 * PROBE_EVERY)
        # the host is confirmed once, and the device serves everything else
        assert picks.count("host") == 1 and picks[PROBE_EVERY] == "host"
        assert r.stats("k")["device"] == 0.38

    def test_sixteen_tainted_in_a_row_and_the_next_is_folded(self):
        """A shape that compiles on every call really is that slow."""
        r = PathRouter()
        for i in range(PROBE_EVERY):
            assert r.choose("k") == "device"
            r.record("k", "device", 5.0, clean=False)
            assert "device" not in r.stats("k")
        r.record("k", "device", 5.0, clean=False)
        assert r.stats("k")["device"] == 5.0
        assert r.choose("k") == "host"
        # a clean sample between starts the count again
        r.record("k", "device", 4.0)
        for i in range(PROBE_EVERY):
            r.record("k", "device", 9.0, clean=False)
        assert r.stats("k")["device"] == 4.0

    def test_merge_router_samples_are_all_clean(self):
        """engine/merge.py records per row with no compile signal: the
        default is a clean sample."""
        r = PathRouter()
        r.record("k", "device", 2e-7)
        assert r.stats("k")["device"] == 2e-7


def _serve_path(r, lat, calls):
    picks = []
    for _ in range(calls):
        picks.append(r.choose("k"))
        r.record("k", picks[-1], lat[picks[-1]])
    return picks


@pytest.fixture()
def routed(monkeypatch):
    """A connection whose aggregates go through the PathRouter on the CPU,
    a statement of a shape no other test compiles, and what the executor
    handed its router."""
    import horaedb_tpu

    monkeypatch.setenv("HORAEDB_ADAPTIVE_PATH", "1")
    conn = horaedb_tpu.connect(None)
    conn.execute(
        "CREATE TABLE ps (host string TAG, a double, b double, c double, "
        "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic"
    )
    rows = ", ".join(
        f"('h{i % 11}', {float(i)}, {i * 2.0}, {i * 3.0}, {1000 * (i + 1)})"
        for i in range(330)
    )
    conn.execute(f"INSERT INTO ps (host, a, b, c, ts) VALUES {rows}")
    ex = conn.interpreters.executor
    handed = []
    record = ex.path_router.record

    def spy(key, kind, seconds, clean=True):
        handed.append((kind, clean))
        record(key, kind, seconds, clean=clean)

    monkeypatch.setattr(ex.path_router, "record", spy)
    yield conn, handed
    conn.close()


def _stats(conn, sql):
    plan = conn.frontend.statement_to_plan(conn.frontend.parse_sql(sql))
    return conn.interpreters.executor.path_router.stats(plan_shape_key(plan))


class TestExecutorHandsOnlyCleanSamples:
    @pytest.mark.parametrize("telemetry", ["1", "0"])
    def test_a_request_that_compiled_is_not_folded(
        self, routed, monkeypatch, telemetry
    ):
        """With device telemetry off there is no ``compile_hit`` and here
        there is no ledger either: the fact travels with the request."""
        conn, handed = routed
        monkeypatch.setenv("HORAEDB_DEVICE_TELEMETRY", telemetry)
        # a fresh static shape for each case: another count of aggregates
        aggs = "min(a), max(b)" if telemetry == "1" else "min(a), max(b), sum(c)"
        sql = f"SELECT host, {aggs} FROM ps GROUP BY host"
        compiled = []
        for _ in range(8):
            before = querystats.kernel_compiles()
            out = conn.execute(sql)
            tainted = (
                querystats.kernel_compiles() != before
                or out.metrics.get("cache") == "build"
            )
            compiled.append(tainted)
            assert handed[-1][1] == (not tainted)
            if all(compiled):
                assert out.metrics["route"] == "device"
                assert "device" not in _stats(conn, sql)
        assert compiled[0] and not compiled[-1]
        assert "_compiles_before" not in out.metrics
        # the first clean request is the device's first estimate, and the
        # host is not sampled before it
        first_clean = compiled.index(False)
        assert [k for k, _ in handed[:first_clean + 1]] == (
            ["device"] * (first_clean + 1)
        )
        assert _stats(conn, sql)["n"]["device"] >= 1

    def test_a_shape_that_compiles_every_call_is_folded_after_sixteen(
        self, routed, monkeypatch
    ):
        conn, handed = routed
        sql = "SELECT host, avg(a) FROM ps GROUP BY host"
        ticks = iter(range(10 ** 6))
        monkeypatch.setattr(querystats, "kernel_compiles", lambda: next(ticks))
        for i in range(PROBE_EVERY):
            out = conn.execute(sql)
            assert out.metrics["route"] == "device"
            assert "device" not in _stats(conn, sql)
        conn.execute(sql)
        assert handed == [("device", False)] * (PROBE_EVERY + 1)
        assert _stats(conn, sql)["n"] == {"device": 1}
        assert conn.execute(sql).metrics["route"] == "host"
