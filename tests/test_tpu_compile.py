"""The served path's jitted entry points, compiled for a DESCRIBED TPU v5e
at chip_smoke.py's real shapes (4000 hosts x 1 h: 1,440,000 rows padded to
2^21, the layouts ScanCache builds for that data).

Nothing runs and no chip is attached: the TPU compiler installed beside
JAX refuses here what the chip would refuse (a program that does not fit
HBM, a shape it cannot tile), so these guard every later PR at no chip
time. A compile that passes is not a chip run.

The topology is described inside the module-scoped fixture below — never
at import, in a skipif, in parametrize or in conftest.py — so every xdist
worker collects the same tests and only the worker that runs this file
loads the TPU library. Keep all such tests in THIS file.
"""

import re

import numpy as np
import pytest

N = 1 << 21  # padded rows of the 1.44M-row cache entry
S = 4000  # series
# the encoded streams ScanCache built for this data (CPU rehearsal, PR 23)
SERIES_PARTS = (((65537,), "uint32"), ((16384,), "int32"))  # ("delta", 1)
TS_PARTS = (((589825,), "uint32"), ((512,), "int32"))  # ("dict", 9)
LAYOUTS = dict(ts_layout=("dict", 9), series_layout=("delta", 1))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here / library held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def cache_off():
    """The persistent compilation cache is off around these compiles: an
    entry written for a described device cannot be read back without a
    chip."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def compile_for(topo, cache_off):
    """-> compile(fn, arg_specs, **static) for one described chip; arg
    specs are (shape, dtype) leaves."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])

    def compile_(fn, arg_specs, **static):
        args = jax.tree_util.tree_map(
            lambda leaf: jax.ShapeDtypeStruct(
                leaf[0], jnp.dtype(leaf[1]), sharding=one_chip
            ),
            arg_specs, is_leaf=_is_spec,
        )
        return fn.lower(*args, **static).compile()

    return compile_


@pytest.fixture(scope="module")
def mesh4(topo, cache_off):
    """(mesh, spec(shape, dtype, *partition)) over the four described
    chips, rows on the "shard" axis like parallel/mesh.py lays them."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(topo.devices), ("shard",))

    def spec(shape, dtype, *partition):
        return jax.ShapeDtypeStruct(
            shape, jnp.dtype(dtype), sharding=NamedSharding(mesh, P(*partition))
        )

    return mesh, spec


def _is_spec(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def _packed_args(n_fields, n_filters=0, selective_rows=0):
    return (
        SERIES_PARTS, TS_PARTS, ((n_fields, N), "float32"),
        ((2 * (S + 1),), "int32"),
        ((n_filters + 4 + selective_rows,), "int32"),
    )


def _packed_static(n_groups, n_buckets, n_fields, need_minmax, impl,
                   filters=(), selective=False):
    return dict(
        n_groups=n_groups, n_buckets=n_buckets, n_agg_fields=n_fields,
        numeric_filters=filters, need_minmax=need_minmax, segment_impl=impl,
        selective=selective,
        value_layouts=(("raw",),) * n_fields, **LAYOUTS,
    )


# statement -> (args, static) of cached_scan_agg_packed with the impl the
# static policy resolves to on a TPU
PACKED = {
    # S1 single-groupby-5-8-1: 8 hosts' 4096 gathered rows, 64 minutes
    "S1-selective-mxu": (
        _packed_args(5, selective_rows=4096),
        _packed_static(1, 64, 5, True, "mxu", selective=True),
    ),
    # S2 double-groupby-all: 4000 hosts -> 4096 segments, avg only
    "S2-4096seg-mxu": (
        _packed_args(10), _packed_static(4096, 1, 10, False, "mxu"),
    ),
    # S3 high-cpu-all: one filter, global aggregate
    "S3-single": (
        _packed_args(1, n_filters=1),
        _packed_static(1, 1, 1, True, "single", filters=((0, 4),)),
    ),
    # the shape the chip's compiler REFUSED before PR 23 chunked the MXU
    # impl's min/max: pred[8192, 2^21] = 16 GB of HBM
    "minmax-8192seg-mxu": (
        _packed_args(5), _packed_static(8192, 1, 5, True, "mxu"),
    ),
}


# cpu-4000x12h (benchmark/configs): 17,280,000 rows padded to 2^25, the
# layouts ScanCache builds for them (series ("delta", 1), ts and values raw),
# double-groupby-all's 4000 hosts x 12 h -> 4096 x 16 = 65,536 segments, 48,000
# of them live. Before PR 27 the compiler refused the scatter impl at 2^25
# rows whatever the segment count ("Used 18.28G of 15.75G hbm": its (N, F)
# update tile, F padded to 128 lanes).
N_4000X12H = 1 << 25
AT_4000X12H = {
    "avg-10-fields": (10, False),
    "minmax-5-fields": (5, True),
}


@pytest.mark.parametrize("case", sorted(AT_4000X12H))
def test_cached_packed_compiles_at_4000x12h(monkeypatch, compile_for, case):
    """The grouped full scan of the 2^25-row deployment compiles under the
    impl the policy picks for it, with temporaries under 4 GB and under what
    the policy reckons for the impl (``segment_temp_bytes``)."""
    import jax

    from horaedb_tpu.ops.scan_agg import (
        cached_scan_agg_packed,
        segment_temp_bytes,
    )
    from horaedb_tpu.query.kernel_choice import candidate_kernels, static_kernel

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n_fields, need_minmax = AT_4000X12H[case]
    n, n_groups, n_buckets = N_4000X12H, 4096, 16
    n_seg = n_groups * n_buckets
    impl = static_kernel(n_seg)
    assert impl == "scatter"
    assert candidate_kernels(n_seg, n, n_fields, need_minmax) == (impl,)
    args = (
        (((n // 32 + 1,), "uint32"), ((n // 128,), "int32")),  # ("delta", 1)
        (((n,), "int32"),),
        ((n_fields, n), "float32"),
        ((2 * (S + 1),), "int32"), ((4,), "int32"),
    )
    static = dict(
        _packed_static(n_groups, n_buckets, n_fields, need_minmax, impl),
        ts_layout=("raw",),
    )
    mem = compile_for(cached_scan_agg_packed, args, **static).memory_analysis()
    assert mem.temp_size_in_bytes < (4 << 30), mem
    assert mem.temp_size_in_bytes <= segment_temp_bytes(
        impl, n, n_seg, n_fields, need_minmax
    ), mem


@pytest.mark.parametrize("case", sorted(PACKED))
def test_cached_packed_compiles(compile_for, case):
    from horaedb_tpu.ops.scan_agg import (
        cached_scan_agg_packed,
        packed_program_name,
    )

    args, static = PACKED[case]
    compiled = compile_for(cached_scan_agg_packed, args, **static)
    # HBM beyond the resident columns stays a small share of the 16 GB
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (1 << 30), mem
    if not static["selective"]:  # what the policy reckons is from above
        from horaedb_tpu.ops.scan_agg import segment_temp_bytes

        assert mem.temp_size_in_bytes <= segment_temp_bytes(
            static["segment_impl"], N,
            static["n_groups"] * static["n_buckets"],
            static["n_agg_fields"], static["need_minmax"],
        ), mem
    # what a device trace's ``XLA Modules`` line will call it
    name = packed_program_name(static["segment_impl"], static["selective"])
    assert f"HloModule jit_{name}," in compiled.as_text()


def test_raw_topk_compiles(compile_for):
    """S4: ORDER BY ts DESC LIMIT 100 -> k=128 over the encoded streams."""
    from horaedb_tpu.ops.scan_topk import raw_topk_packed

    compile_for(
        raw_topk_packed,
        (SERIES_PARTS, TS_PARTS, ((0, N), "float32"),
         ((S + 1,), "int32"), ((4,), "int32")),
        k=128, descending=True, key_is_ts=True, key_field=0,
        numeric_filters=(), value_layouts=(), **LAYOUTS,
    )


def test_livewindow_fold_and_gather_compile(compile_for):
    from horaedb_tpu.ops.livewindow import _fold_body, _gather_body

    depth, cap, rows = 128, 4096, 1024  # ring_depth() x max_groups()
    rings = (((depth, cap), "int32"),) + (((depth, cap), "float32"),) * 4
    batch = (((rows,), "int32"), ((rows,), "int32"), ((rows,), "float32"))
    compile_for(_fold_body, (*rings, ((depth,), "bool"), *batch, *batch))
    compile_for(_gather_body, (*rings, ((64,), "int32")))


def test_merge_sort_kernel_compiles(compile_for):
    """The compaction/merge-read sort at one 2^21-row bucket."""
    from horaedb_tpu.ops.merge_dedup import _ranked_kernel

    compile_for(
        _ranked_kernel,
        (((N,), "uint32"), ((N,), "uint32"), ((), "uint32"), ((), "uint32"),
         ((), "int32")),
        dedup=True,
    )


def test_sharded_steps_compile_on_four_chips(mesh4):
    """The shard_map steps a four-chip host serves S1 and S4 with: rows
    split four ways, partials combined by psum/pmin/pmax."""
    from horaedb_tpu.ops.scan_agg import ScanAggSpec
    from horaedb_tpu.ops.scan_topk import RawScanSpec
    from horaedb_tpu.parallel.dist_agg import make_cached_dist_scan_agg
    from horaedb_tpu.parallel.dist_raw import make_dist_raw_topk

    mesh, spec = mesh4
    scalars = [spec((), "int32")] * 4
    agg = make_cached_dist_scan_agg(
        mesh, ScanAggSpec(n_groups=1, n_buckets=64, n_agg_fields=5,
                          segment_impl="mxu"),
    )
    compiled = agg.lower(
        spec((N,), "int32", "shard"), spec((N,), "int32", "shard"),
        spec((5, N), "float32", None, "shard"),
        spec((S + 1,), "int32"), spec((S + 1,), "bool"),
        spec((0,), "float32"), *scalars,
    ).compile()
    assert "all-reduce" in compiled.as_text()
    topk = make_dist_raw_topk(
        mesh, RawScanSpec(k=128, descending=True, key_is_ts=True)
    )
    topk.lower(
        spec((N,), "int32", "shard"), spec((N,), "int32", "shard"),
        spec((0, N), "float32", None, "shard"),
        spec((S + 1,), "bool"), spec((0,), "float32"), *scalars,
    ).compile()


def test_sharded_scatter_compiles_at_4000x12h_on_four_chips(mesh4):
    """cpu-4000x12h-dist4's program: 17,280,000 rows in four equal blocks
    (``ShardLayout``), each chip's chunked scatter into 65,536 segments and
    the collectives over them, under the name the trace shows (one case: a
    compile of it takes 40 s). Its 1000 series of 4320 rows a chip span at
    most two series a 128-row block, so the per-series tables are read by
    block (``series_block_width`` 1)."""
    from horaedb_tpu.ops.scan_agg import ScanAggSpec, segment_temp_bytes
    from horaedb_tpu.parallel.dist_agg import make_cached_dist_scan_agg
    from horaedb_tpu.parallel.mesh import ShardLayout

    mesh, spec = mesh4
    n_fields, need_minmax = AT_4000X12H["avg-10-fields"]
    shards = ShardLayout.of(17_280_000, 4)
    assert shards.valid_rows.tolist() == [4_320_000] * 4
    assert shards.shard_len == 66 * 65_536  # whole scatter chunks
    n = shards.padded_rows
    step = make_cached_dist_scan_agg(
        mesh, ScanAggSpec(n_groups=4096, n_buckets=16, n_agg_fields=n_fields,
                          need_minmax=need_minmax, segment_impl="scatter"),
        block_width=1,
    )
    compiled = step.lower(
        spec((n,), "int32", "shard"), spec((n,), "int32", "shard"),
        spec((n_fields, n), "float32", None, "shard"),
        spec((S + 1,), "int32"), spec((S + 1,), "bool"),
        spec((0,), "float32"), *[spec((), "int32")] * 4,
    ).compile()
    text = compiled.as_text()
    assert "HloModule jit_cached_dist_scatter," in text
    # counts and sums; mins and maxs only where a statement asks for them
    assert text.count(" all-reduce(") + text.count(" all-reduce-start(") == 2, [
        ln for ln in text.splitlines() if "all-reduce" in ln
    ][:8]
    # the allow list and the group map through each block's two candidate
    # series (33,792 blocks a chip); no gather of one element a row
    gathers = re.findall(r"= (\w+)\[([\d,]+)\]\S* gather\(", text)
    blocks = shards.shard_len // 128
    assert ("pred", f"{blocks},2") in gathers and ("s32", f"{blocks},2") in gathers
    assert not [g for g in gathers if g[1] == str(shards.shard_len)], gathers
    mem = compiled.memory_analysis()  # per chip
    assert mem.temp_size_in_bytes <= segment_temp_bytes(
        "scatter", shards.shard_len, 65_536, n_fields, need_minmax
    ), mem


# ---- CPU tests: the policy and the repair the compiles above rest on ------

# (segment impl, selective) -> the program's documented name
# (docs/OBSERVABILITY.md, "Names on the device")
PROGRAM_NAMES = {
    ("single", False): "cached_scan_single",
    ("single", True): "cached_scan_single_sel",
    ("mxu", False): "cached_scan_mxu",
    ("mxu", True): "cached_scan_mxu_sel",
    ("scatter", False): "cached_scan_scatter",
    ("scatter", True): "cached_scan_scatter_sel",
}


@pytest.mark.parametrize("impl,selective", sorted(PROGRAM_NAMES))
def test_named_program_lowers_under_its_name(impl, selective):
    """Each (segment impl, selective) is a jitted program of its own,
    named as documented, and its stages carry their ``named_scope`` into
    the HLO metadata (what a device trace shows for ``fusion.N``)."""
    import jax
    import jax.numpy as jnp

    from horaedb_tpu.ops import scan_agg

    n, s, m = 1024, 7, 64
    n_groups, n_buckets = (1, 1) if impl == "single" else (8, 16)
    series = (jax.ShapeDtypeStruct((n // 16 + 1,), jnp.uint32),
              jax.ShapeDtypeStruct((n // 128,), jnp.int32))  # ("delta", 1)
    ts = (jax.ShapeDtypeStruct((n * 9 // 32 + 1,), jnp.uint32),
          jax.ShapeDtypeStruct((512,), jnp.int32))  # ("dict", 9)
    value = (jax.ShapeDtypeStruct((n * 7 // 32 + 1,), jnp.uint32),
             jax.ShapeDtypeStruct((128,), jnp.float32))  # ("dict", 7, True)
    lowered = scan_agg.cached_scan_agg_packed.lower(
        series, ts, (value, value),
        jax.ShapeDtypeStruct((2 * (s + 1),), jnp.int32),
        jax.ShapeDtypeStruct((1 + 4 + (m if selective else 0),), jnp.int32),
        n_groups=n_groups, n_buckets=n_buckets, n_agg_fields=2,
        numeric_filters=((0, 4),), need_minmax=True, segment_impl=impl,
        selective=selective,
        value_layouts=(("dict", 7, True),) * 2,
        ts_layout=("dict", 9), series_layout=("delta", 1),
    )
    name = PROGRAM_NAMES[impl, selective]
    assert scan_agg.packed_program_name(impl, selective) == name
    text = lowered.as_text(debug_info=True)
    assert f"module @jit_{name} " in text
    scopes = ["decode_series", "decode_ts", "decode_values", "filter",
              "segment_" + impl, "pack"]
    if impl == "scatter":  # its stages, one scatter each, in both branches
        scopes += ["segment_scatter/runs"]
        scopes += [f"segment_scatter/cond/branch_{b}_fun/{stage}"
                   for b in (0, 1) for stage in ("counts", "sums", "mins", "maxs")]
        scopes += [f"segment_scatter/cond/branch_1_fun/{stage}"  # the fold
                   for stage in ("fold_ends", "fold_totals")]
    for scope in scopes:
        assert f"jit({name})/{scope}/" in text, scope


# Step 2's outcome on this tree at N = 2^21 with min/max wanted, per
# (impl, n_seg): True compiled for the described v5e, False refused.
# Before PR 23 ("mxu", 8192) and ("mxu", 32768) were False.
STEP2_AT_2M_ROWS = {
    ("mxu", 64): True, ("scatter", 64): True,
    ("mxu", 4096): True, ("scatter", 4096): True,
    ("mxu", 8192): True, ("scatter", 8192): True,
    ("mxu", 32768): True,
}


# The same at N = 2^25 (cpu-4000x12h), 10 fields avg-only and 5 fields with
# min/max, per (impl, n_seg), on this tree (scratch compiles for the described
# v5e, PR 27; temporaries 0.17-0.94 GB at 65,536 segments). On the parent (81d0b0f) every
# ("scatter", *) was False at this row count, 64 segments included: "Used
# 17.25G-18.69G of 15.75G hbm".
STEP2_AT_32M_ROWS = {
    (impl, n_seg): True
    for impl in ("mxu", "scatter")
    for n_seg in (64, 4096, 8192, 32768, 65536)
}


@pytest.mark.parametrize("n_rows,table", [
    (N, STEP2_AT_2M_ROWS), (N_4000X12H, STEP2_AT_32M_ROWS),
], ids=["2M-rows", "32M-rows"])
def test_tpu_policy_offers_no_refused_impl(monkeypatch, n_rows, table):
    """Told the backend is a TPU, the one place that chooses a segment impl
    (its seed and its candidates) never offers one whose program the chip's
    compiler refused at that (rows, segments)."""
    import jax

    from horaedb_tpu.query.kernel_choice import candidate_kernels, static_kernel

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert static_kernel(64) == "mxu"  # the TPU branch is live
    for n_seg in sorted({n for _, n in table}):
        offered = {static_kernel(n_seg)}
        offered.update(candidate_kernels(n_seg, n_rows, 10, False))
        offered.update(candidate_kernels(n_seg, n_rows, 5, True))
        for impl in offered:
            assert table.get((impl, n_seg), True), (impl, n_seg)


def test_policy_offers_only_what_fits_the_device(monkeypatch):
    """``candidate_kernels`` holds each impl's reckoned temporaries against
    the device's free memory: with a v5e's 15.75 GB free the 2^25-row
    deployment's shape keeps its scatter; a segment domain whose three
    accumulators alone outgrow the chip is offered nothing (the host serves
    it), and so is anything once the device is nearly full."""
    from horaedb_tpu.obs import device
    from horaedb_tpu.query.kernel_choice import candidate_kernels

    free = [int(15.75 * 2**30)]
    monkeypatch.setattr(device, "device_free_bytes", lambda: free[0])
    shape = (65_536, N_4000X12H)
    assert candidate_kernels(*shape, 10, False) == ("scatter",)
    assert candidate_kernels(*shape, 5, True) == ("scatter",)
    # 17.28M rows by host and 10 s tick: 2^25 segments x 3 x 512 B x 2
    assert candidate_kernels(1 << 25, N_4000X12H, 5, True) == ()
    free[0] = 1 << 30
    assert candidate_kernels(*shape, 10, False) == ()
    monkeypatch.setattr(device, "device_free_bytes", lambda: None)  # the CPU
    assert candidate_kernels(1 << 25, N_4000X12H, 5, True) == ("scatter",)


def _numpy_group_by(seg, mask, vals, n_seg):
    """The plain reference: counts, and float64 sums / mins / maxs per field,
    by ``np.add.at`` / ``np.minimum.at`` / ``np.maximum.at``."""
    seg, vals = seg[mask], vals[:, mask].astype(np.float64)
    counts = np.zeros(n_seg, np.int64)
    np.add.at(counts, seg, 1)
    sums = np.zeros((vals.shape[0], n_seg))
    mins = np.full((vals.shape[0], n_seg), np.inf)
    maxs = np.full((vals.shape[0], n_seg), -np.inf)
    for f in range(vals.shape[0]):
        np.add.at(sums[f], seg, vals[f])
        np.minimum.at(mins[f], seg, vals[f])
        np.maximum.at(maxs[f], seg, vals[f])
    return counts, sums, mins, maxs


# rows, chunk rows: one piece; chunks that divide the rows; a last chunk that
# is mostly padding; a last chunk of one row
SCATTER_CUTS = {
    "whole": (1000, 1024), "even": (1024, 128), "padded-tail": (1000, 128),
    "tail-of-one": (1025, 256),
}


@pytest.mark.parametrize("need_minmax", [False, True], ids=["avg", "minmax"])
@pytest.mark.parametrize("cut", sorted(SCATTER_CUTS))
def test_chunked_scatter_equals_numpy_group_by(monkeypatch, cut, need_minmax):
    """The scatter impl, cut into row chunks by ``_SCATTER_TILE_BYTES``, against
    a plain numpy group-by on seeded data laid out as the cache lays it (rows
    sorted by segment, so segments span chunk boundaries): masked rows, a run
    of masked rows that covers a whole chunk, an empty segment.

    Tolerances: counts, mins and maxs exact (integers; f32 values picked, not
    computed). Sums are f32 accumulations of <= 360 values of [0, 100]
    (1000 rows over 37 segments: ~27 each) against float64: 2e-5 relative,
    the limit the benchmark's double-groupby-all cells hold ``value_gap`` to;
    avg = sum / count inherits it."""
    import jax.numpy as jnp

    from horaedb_tpu.ops import scan_agg

    n, chunk = SCATTER_CUTS[cut]
    n_fields, n_seg = 3, 40
    monkeypatch.setattr(scan_agg, "_SCATTER_TILE_BYTES", chunk * 512)
    assert scan_agg.scatter_chunk_rows(n_fields) == chunk
    assert scan_agg.segment_row_chunks(
        "scatter", n, n_seg, n_fields, need_minmax
    ) == -(-n // chunk)
    rng = np.random.default_rng(27)
    seg = np.sort(rng.integers(0, 37, n)).astype(np.int32)  # 37..39 stay empty
    seg[seg == 11] = 12  # and one inside the range
    mask = rng.random(n) > 0.2
    mask[128:256] = False  # a whole chunk of the 128-row cuts
    vals = rng.uniform(0, 100, (n_fields, n)).astype(np.float32)
    want = _numpy_group_by(seg, mask, vals, n_seg)
    counts, sums, mins, maxs = (
        np.asarray(a) for a in scan_agg._scatter_segment_agg(
            jnp.asarray(seg), jnp.asarray(mask), jnp.asarray(vals), n_seg,
            need_minmax,
        )[:4]
    )
    np.testing.assert_array_equal(counts, want[0])
    assert counts.dtype == np.int32 and counts[11] == 0 and counts[37:].sum() == 0
    np.testing.assert_allclose(sums, want[1], rtol=2e-5, atol=0)
    live = want[0] > 0
    np.testing.assert_allclose(
        (sums / np.maximum(counts, 1))[:, live],
        (want[1] / np.maximum(want[0], 1))[:, live], rtol=2e-5, atol=0,
    )
    if need_minmax:
        np.testing.assert_array_equal(mins, want[2].astype(np.float32))
        np.testing.assert_array_equal(maxs, want[3].astype(np.float32))
    else:  # not wanted: zeros in their slots
        assert not mins.any() and not maxs.any()
    # counts only (count(*) with no field): the same cuts
    only = scan_agg._scatter_segment_agg(
        jnp.asarray(seg), jnp.asarray(mask), None, n_seg, need_minmax
    )
    np.testing.assert_array_equal(np.asarray(only[0]), want[0])
    assert only[1:4] == (None, None, None)


def test_chunked_scatter_through_the_packed_program(monkeypatch):
    """``cached_scan_scatter`` end to end with the cut forced small: count, sum,
    min, max and avg of a grouped, bucketed, filtered scan equal the uncut
    program's bit for bit, and the numpy group-by's within f32."""
    import jax.numpy as jnp

    from horaedb_tpu.ops import scan_agg
    from horaedb_tpu.ops.scan_agg import (
        ScanAggSpec, cached_scan_agg_packed, pack_dyn, pack_session,
        unpack_packed_state,
    )

    rng = np.random.default_rng(28)
    n_series, per, n = 12, 80, 1024  # 960 rows + 64 pad rows
    codes = np.full(n, n_series, np.int32)
    codes[: n_series * per] = np.repeat(np.arange(n_series, dtype=np.int32), per)
    ts = np.full(n, -1, np.int32)
    ts[: n_series * per] = np.tile(np.arange(per, dtype=np.int32) * 10, n_series)
    vals = rng.uniform(0, 100, (2, n)).astype(np.float32)
    gos = np.append(np.arange(n_series, dtype=np.int32) % 5, 0)  # 5 groups
    allow = np.append(rng.random(n_series) > 0.25, False)
    spec = ScanAggSpec(n_groups=8, n_buckets=4, n_agg_fields=2,
                       numeric_filters=((1, ">"),), need_minmax=True,
                       segment_impl="scatter")
    args = ((jnp.asarray(codes),), (jnp.asarray(ts),), jnp.asarray(vals),
            jnp.asarray(pack_session(gos, allow)),
            jnp.asarray(pack_dyn([30.0], 100, 700, 0, 200)))
    static = dict(
        n_groups=8, n_buckets=4, n_agg_fields=2, numeric_filters=((1, 4),),
        need_minmax=True, segment_impl="scatter",
        selective=False, value_layouts=(("raw",),) * 2,
        ts_layout=("raw",), series_layout=("raw",),
    )

    def run():
        scan_agg._packed_programs.clear()  # the constant is read at trace time
        return np.asarray(cached_scan_agg_packed(*args, **static))

    whole = run()
    monkeypatch.setattr(scan_agg, "_SCATTER_TILE_BYTES", 128 * 512)
    cut = run()
    scan_agg._packed_programs.clear()
    np.testing.assert_array_equal(whole, cut)
    state = unpack_packed_state(cut, spec)
    live = (codes < n_series) & allow[codes] & (ts >= 100) & (ts < 700) & (vals[1] > 30.0)
    seg = gos[codes] * 4 + np.clip(ts // 200, 0, 3)
    counts, sums, mins, maxs = _numpy_group_by(seg, live, vals, 32)
    np.testing.assert_array_equal(state.counts.reshape(-1), counts)
    got = (state.sums, state.mins, state.maxs)
    for a, b in zip(got, (sums, mins, maxs)):
        a, b = a.reshape(2, 32)[:, counts > 0], b[:, counts > 0]
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=0)


def test_mxu_minmax_chunks_are_exact(monkeypatch):
    """The row-chunked min/max (what keeps the match mask inside HBM on
    the chip) equals the one-shot reduce and the scatter impl, pad rows
    and masked rows included."""
    import jax.numpy as jnp

    from horaedb_tpu.ops import scan_agg

    rng = np.random.default_rng(7)
    n, n_fields, n_seg = 5000, 3, 37  # 5000 rows: the last chunk is padded
    seg = jnp.asarray(rng.integers(0, n_seg, n).astype(np.int32))
    mask = jnp.asarray(rng.random(n) > 0.3)
    vals = jnp.asarray(rng.normal(size=(n_fields, n)).astype(np.float32))
    whole = scan_agg._mxu_segment_agg(seg, mask, vals, n_seg, True)
    monkeypatch.setattr(scan_agg, "_MINMAX_MASK_BYTES", 128 * n_seg)
    chunked = scan_agg._mxu_segment_agg(seg, mask, vals, n_seg, True)
    scatter = scan_agg._scatter_segment_agg(seg, mask, vals, n_seg, True)
    for a, b, c in zip(whole[2:], chunked[2:], scatter[2:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    np.testing.assert_array_equal(np.asarray(whole[0]), np.asarray(chunked[0]))
