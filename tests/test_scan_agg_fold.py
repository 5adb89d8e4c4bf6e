"""The scatter impl's fold: a step whose rows hold at most ``fold_cap`` runs of
equal segment ids scatters one update row per run, any other its rows.

Each case is held to a plain numpy group-by (float64): counts equal, sums
within 4e-6 relative, mins and maxs exact, and the number of steps that
folded as the run counts say. The steps are cut small (``_SCATTER_TILE_BYTES``
= 4096 rows, so ``fold_cap`` = 16 runs a step) to reach every case with few
rows, and the loop takes them one or two at a time (``_FOLD_GROUP_ROWS``).
"""

import numpy as np
import pytest

CHUNK = 4096
CAP = 16
N_SEG = 64


def _runs(*lengths):
    """Segment ids: run i of ``lengths[i]`` rows holds id ``i % N_SEG``."""
    return np.repeat(np.arange(len(lengths)) % N_SEG, lengths).astype(np.int32)


def _with_runs(first_chunk_runs, n=2 * CHUNK):
    """``n`` rows whose first step holds exactly ``first_chunk_runs`` runs;
    the rest is one run per 1000 rows."""
    head = [1] * (first_chunk_runs - 1) + [CHUNK - (first_chunk_runs - 1)]
    tail = [1000] * ((n - CHUNK) // 1000) + [(n - CHUNK) % 1000]
    return _runs(*head, *[t for t in tail if t])


def _case(name):
    """-> (seg, mask, folded steps expected)."""
    mask = None
    if name == "runs-of-360":  # every step folds
        seg, folded = _runs(*[360] * 40), 4
    elif name == "runs-of-6":  # ~680 runs a step: every step falls back
        seg, folded = _runs(*[6] * 1400), 0
    elif name == "long-and-short":  # 1-3 row runs among long ones, one step
        seg, folded = _runs(2000, 1, 2, 3, 1, 1500, 2, 1, 586), 1
    elif name == "five-steps":  # two groups of two and one padded
        seg, folded = _runs(*[360] * 56, 320), 5
    elif name == "run-across-a-boundary":
        seg, folded = _runs(3000, 3000, 2192), 2
    elif name == "masked-rows":  # inside a run, and the whole second step
        seg, folded = _runs(*[360] * 20), 2
        mask = np.ones(len(seg), bool)
        mask[100:110] = False
        mask[CHUNK:] = False
    elif name == "below-one-step":  # 3000 rows: one step padded to blocks
        seg, folded = _runs(*[300] * 10), 1
    elif name == "at-the-cap":
        seg, folded = _with_runs(CAP), 2
    elif name == "one-over-the-cap":
        seg, folded = _with_runs(CAP + 1), 1
    else:
        raise KeyError(name)
    if mask is None:
        mask = np.ones(len(seg), bool)
    return seg, mask, folded


CASES = ["runs-of-360", "runs-of-6", "long-and-short", "five-steps",
         "run-across-a-boundary",
         "masked-rows", "below-one-step", "at-the-cap", "one-over-the-cap"]


def _numpy_group_by(seg, mask, vals):
    seg, vals = seg[mask], vals[:, mask].astype(np.float64)
    counts = np.zeros(N_SEG, np.int64)
    np.add.at(counts, seg, 1)
    sums = np.zeros((vals.shape[0], N_SEG))
    mins = np.full((vals.shape[0], N_SEG), np.inf)
    maxs = np.full((vals.shape[0], N_SEG), -np.inf)
    for f in range(vals.shape[0]):
        np.add.at(sums[f], seg, vals[f])
        np.minimum.at(mins[f], seg, vals[f])
        np.maximum.at(maxs[f], seg, vals[f])
    return counts, sums, mins, maxs


@pytest.fixture(params=[1, 2], ids=["step-by-step", "two-steps-a-group"])
def small_steps(monkeypatch, request):
    from horaedb_tpu.ops import scan_agg

    monkeypatch.setattr(scan_agg, "_SCATTER_TILE_BYTES", CHUNK * 512)
    monkeypatch.setattr(scan_agg, "_FOLD_GROUP_ROWS", request.param * CHUNK)
    assert scan_agg.scatter_chunk_rows(3) == CHUNK
    assert scan_agg.fold_cap(CHUNK) == CAP
    return scan_agg


def test_groups_are_even_and_never_fall_to_one_step():
    """67 steps of 2^16 rows: three groups of 23, the last padded by two."""
    from horaedb_tpu.ops.scan_agg import fold_group

    assert fold_group(67, 1 << 16) == 23
    assert fold_group(66, 1 << 16) == 22
    assert fold_group(512, 1 << 16) == 32
    assert fold_group(1, 1 << 16) == 1


@pytest.mark.parametrize("n_fields", [3, 0], ids=["3-fields", "counts-only"])
@pytest.mark.parametrize("need_minmax", [False, True], ids=["avg", "minmax"])
@pytest.mark.parametrize("case", CASES)
def test_fold_equals_numpy_group_by(small_steps, case, need_minmax, n_fields):
    import jax.numpy as jnp

    seg, mask, want_folded = _case(case)
    n = len(seg)
    rng = np.random.default_rng(35)
    vals = rng.uniform(0, 100, (max(n_fields, 1), n)).astype(np.float32)
    counts, sums, mins, maxs = _numpy_group_by(seg, mask, vals)
    got = small_steps._scatter_segment_agg(
        jnp.asarray(seg), jnp.asarray(mask),
        jnp.asarray(vals) if n_fields else None, N_SEG, need_minmax,
    )
    assert int(got[4]) == want_folded
    np.testing.assert_array_equal(np.asarray(got[0]), counts)
    if not n_fields:
        assert got[1:4] == (None, None, None)
        return
    np.testing.assert_allclose(np.asarray(got[1]), sums, rtol=4e-6, atol=0)
    if need_minmax:
        live = counts > 0
        np.testing.assert_array_equal(
            np.asarray(got[2])[:, live], mins[:, live].astype(np.float32))
        np.testing.assert_array_equal(
            np.asarray(got[3])[:, live], maxs[:, live].astype(np.float32))
    else:
        assert not np.asarray(got[2]).any() and not np.asarray(got[3]).any()


def test_folded_steps_are_summed_over_the_mesh(small_steps):
    """Through ``parallel/dist_agg.py`` on four of the CPU mesh's devices:
    each shard of 8192 rows is two steps of runs of 360 (both fold), and the
    step's result is every shard's folded steps, with the numpy answer."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from horaedb_tpu.ops.scan_agg import ScanAggSpec
    from horaedb_tpu.parallel.dist_agg import make_dist_scan_agg

    mesh = Mesh(np.array(jax.devices()[:4]), ("shard",))
    n = 4 * 2 * CHUNK
    seg = _runs(*[360] * (n // 360), n % 360)
    mask = np.ones(n, bool)
    vals = np.random.default_rng(36).uniform(0, 100, (2, n)).astype(np.float32)
    spec = ScanAggSpec(n_groups=N_SEG, n_buckets=1, n_agg_fields=2,
                       need_minmax=False, segment_impl="scatter")
    step = make_dist_scan_agg(mesh, spec)
    counts, sums, _, _, folded = step(
        jnp.asarray(seg), jnp.zeros(n, jnp.int32), jnp.asarray(mask),
        jnp.asarray(vals), jnp.zeros(0, jnp.float32),
    )
    assert int(folded) == 8
    want = _numpy_group_by(seg, mask, vals)
    np.testing.assert_array_equal(np.asarray(counts).reshape(-1), want[0])
    np.testing.assert_allclose(
        np.asarray(sums).reshape(2, -1), want[1], rtol=4e-6, atol=0)


@pytest.mark.parametrize("second", ["folds", "falls-back"])
def test_a_cohort_folds_a_step_only_where_every_member_does(small_steps, second):
    """Under the cohort program's ``vmap`` the members agree on each step's
    choice: the choice stays a ``cond`` (a batched one would run both
    branches), and a step folds only where it folds for both members."""
    import jax
    import jax.numpy as jnp

    first = _runs(*[360] * 22, 272)  # two steps, both fold
    other = first if second == "folds" else _with_runs(CAP + 1)  # step 1 not
    segs = np.stack([first, other])
    masks = np.ones_like(segs, bool)
    vals = np.random.default_rng(37).uniform(0, 100, (2, 2, segs.shape[1]))
    vals = vals.astype(np.float32)

    def one(seg, m, v):
        return small_steps._scatter_segment_agg(
            seg, m, v, N_SEG, True, small_steps._COHORT_AXIS)

    run = jax.vmap(one, axis_name=small_steps._COHORT_AXIS)
    args = (jnp.asarray(segs), jnp.asarray(masks), jnp.asarray(vals))
    assert "cond[" in str(jax.make_jaxpr(run)(*args))
    got = run(*args)
    assert [int(f) for f in got[4]] == ([2, 2] if second == "folds" else [1, 1])
    for k in range(2):
        want = _numpy_group_by(segs[k], masks[k], vals[k])
        np.testing.assert_array_equal(np.asarray(got[0][k]), want[0])
        np.testing.assert_allclose(np.asarray(got[1][k]), want[1], rtol=4e-6)
        live = want[0] > 0
        np.testing.assert_array_equal(
            np.asarray(got[2][k])[:, live], want[2][:, live].astype(np.float32))
        np.testing.assert_array_equal(
            np.asarray(got[3][k])[:, live], want[3][:, live].astype(np.float32))
