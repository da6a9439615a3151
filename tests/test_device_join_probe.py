"""The device join probe's search (kernels/device_join._range_probe_kernel)
against a plain numpy reference: ``np.searchsorted`` left and right over the
same sorted keys, the valid prefix by ``np.cumsum``. The kernel makes ONE
lower-bound search a probe row, its first ``PROBE_COMPARE_LEVELS`` levels by
compares and the rest by gathers, and reads the run's end from the build: its
four outputs must equal the two-search formulation's element for element,
whatever the build's size, on both sides of the compare depth."""

import itertools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from daft_tpu.kernels import device_join as dj

L = dj.PROBE_COMPARE_LEVELS
BUILDS = (1, 2, (1 << L) // 2, 1 << L, (1 << L) * 2, (1 << L) * 64)


def reference(bv, bm, pv, pm):
    """(lo, counts, perm, dup) by two ``np.searchsorted`` over the sorted
    build keys: null and padding lanes keyed ``iinfo.max``, valid lanes
    first within a run of equal keys."""
    k = np.where(bm, bv, np.iinfo(bv.dtype).max)
    perm = np.lexsort((~bm, k))
    sk = k[perm]
    sv = bm[perm]
    dup = bool(np.any((sk[1:] == sk[:-1]) & sv[1:] & sv[:-1]))
    vp = np.concatenate([[0], np.cumsum(sv)])
    lo = np.searchsorted(sk, pv, side="left")
    hi = np.searchsorted(sk, pv, side="right")
    counts = np.where(pm, vp[hi] - vp[lo], 0)
    return lo, counts, perm, dup


def build_keys(rng, b, keys, dtype):
    ii = np.iinfo(dtype)
    if keys == "unique":
        return rng.permutation(3 * b)[:b].astype(dtype) - b
    if keys == "duplicated":
        return rng.integers(-2, max(b // 3, 1), b).astype(dtype)
    if keys == "all_equal":
        return np.full(b, 7, dtype)
    assert keys == "extremes"  # valid keys AT iinfo.min and iinfo.max
    return rng.choice(np.array([ii.min, ii.min + 1, -1, 0, ii.max - 1, ii.max],
                               dtype), b)


def probe_keys(rng, bv, p):
    """Hits, near misses on both sides of a build key, and the extremes."""
    ii = np.iinfo(bv.dtype)
    pool = np.concatenate([bv, bv[bv < ii.max] + 1, bv[bv > ii.min] - 1,
                           np.array([ii.min, ii.max, 0], bv.dtype)])
    return rng.choice(pool, p)


def masks(rng, b, p, nulls):
    bm = np.ones(b, bool)
    pm = np.ones(p, bool)
    if nulls == "build":
        bm = rng.random(b) < 0.7
    elif nulls == "probe":
        pm = rng.random(p) < 0.7
    elif nulls == "all_null_build":
        bm[:] = False
    return bm, pm


def check(bv, bm, pv, pm):
    got = dj._range_probe_kernel(jnp.asarray(bv), jnp.asarray(bm),
                                 jnp.asarray(pv), jnp.asarray(pm))
    want = reference(bv, bm, pv, pm)
    for name, g, w in zip(("lo", "counts", "perm"), got, want):
        g = np.asarray(g)
        assert g.dtype == np.int32, (name, g.dtype)
        np.testing.assert_array_equal(g, w, err_msg=name)
    assert bool(got[3]) == want[3]


# every combination, but the largest build in int32 and with unique or
# duplicated keys only (seconds of CPU a case there; the other key patterns
# and the lane dtype do not depend on the build's size)
CASES = [(b, probe, keys, nulls, dtype)
         for b, probe, keys, nulls, dtype in itertools.product(
             BUILDS, ("shorter", "longer"),
             ("unique", "duplicated", "all_equal", "extremes"),
             ("none", "build", "probe", "all_null_build"),
             (np.int32, np.int64))
         if b < BUILDS[-1] or (dtype is np.int32
                               and keys in ("unique", "duplicated"))]


@pytest.mark.parametrize(
    "b,probe,keys,nulls,dtype", CASES,
    ids=["-".join(map(str, c[:4])) + "-" + c[4].__name__ for c in CASES])
def test_probe_equals_two_searchsorted(b, probe, keys, nulls, dtype):
    rng = np.random.default_rng([b, len(probe), len(keys), len(nulls)])
    p = max(b // 4, 1) if probe == "shorter" else b + b // 4 + 3
    bv = build_keys(rng, b, keys, dtype)
    bm, pm = masks(rng, b, p, nulls)
    check(bv, bm, probe_keys(rng, bv, p), pm)


@pytest.mark.parametrize("b", [3, 1000, (1 << L) + 1, 3 * (1 << L) + 5])
def test_probe_over_a_build_that_is_no_power_of_two(b):
    """size_bucket hands the kernel powers of two; lanes past the end read
    as +infinity, so any other size is searched exactly too."""
    rng = np.random.default_rng(b)
    bv = build_keys(rng, b, "duplicated", np.int32)
    bm, pm = masks(rng, b, 2 * b, "build")
    check(bv, bm, probe_keys(rng, bv, 2 * b), pm)


def test_null_probe_lanes_keep_their_lower_bound():
    """``lo`` is a function of the keys alone: a null probe lane still
    reports where its (arbitrary) value would go, with a count of 0."""
    bv = np.arange(0, 64, 2, dtype=np.int32)
    pv = np.array([5, 6, 200, -3], np.int32)
    lo, counts, _, dup = dj._range_probe_kernel(
        jnp.asarray(bv), jnp.ones(32, bool), jnp.asarray(pv),
        jnp.asarray([True, False, False, True]))
    assert np.asarray(lo).tolist() == [3, 3, 32, 0]
    assert np.asarray(counts).tolist() == [0, 0, 0, 0]
    assert not bool(dup)


@pytest.mark.parametrize("runs", ["short", "long", "one"])
@pytest.mark.parametrize("n", [1, 2, 1000, 1024, 2048, 3000, 5 * 1024,
                               1 << 17, 1 << 21])
def test_left_in_run_is_the_segmented_sum_from_the_far_end(n, runs):
    """Rows of 1024 lanes, then what the rows' first lanes came to (twice
    over at 2**21); runs shorter than a row, longer than many, and one run."""
    rng = np.random.default_rng(n)
    count = rng.integers(0, 3, n).astype(np.int32)
    p_end = {"short": 0.3, "long": 3.0 / max(n, 3), "one": 0.0}[runs]
    last = rng.random(n) < p_end
    last[-1] = True
    got = jax.jit(dj._left_in_run)(jnp.asarray(count), jnp.asarray(last))
    want = np.zeros(n, np.int64)
    acc = 0
    for i in range(n - 1, -1, -1):
        acc = count[i] + (0 if last[i] else acc)
        want[i] = acc
    np.testing.assert_array_equal(np.asarray(got), want)


def _gathers(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "gather"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _gathers(sub)
    return n


def _probe_jaxpr(b, p=256):
    args = (jnp.zeros(b, jnp.int32), jnp.ones(b, bool),
            jnp.zeros(p, jnp.int32), jnp.ones(p, bool))
    return jax.make_jaxpr(dj._range_probe_kernel)(*args).jaxpr


@pytest.mark.parametrize("levels_over", [1, 6])
def test_no_gather_up_to_the_compare_depth_one_loop_beyond(levels_over):
    """A build of at most 2**L lanes is searched with no gather at all;
    beyond it ONE loop of gathers (a step a level) finishes the search."""
    at_depth = _gathers(_probe_jaxpr(1 << L))
    assert _gathers(_probe_jaxpr(1 << (L - 1))) == at_depth
    beyond = _probe_jaxpr((1 << L) << levels_over)
    assert _gathers(beyond) == at_depth + 1
    assert dj.probe_search_levels((1 << L) << levels_over) == (L, levels_over)


@pytest.mark.parametrize("b,want", [
    (1, (0, 0)), (2, (1, 0)), (1 << L, (L, 0)), ((1 << L) + 1, (L, 1)),
    ((1 << L) * 2, (L, 1)), (1 << 23, (L, 23 - L))])
def test_search_levels_follow_the_build_size(b, want):
    assert dj.probe_search_levels(b) == want


def test_compiled_probe_holds_nothing_of_lanes_times_pivots():
    """2**L pivots are compared with every probe lane, a chunk a pass: the
    compiled program's temporaries stay a few arrays of P lanes, far under
    the P x pivots booleans a broadcast compare would materialise."""
    p, b = 1 << 16, (1 << L) * 16
    args = (jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.bool_),
            jax.ShapeDtypeStruct((p,), jnp.int32),
            jax.ShapeDtypeStruct((p,), jnp.bool_))
    mem = dj._range_probe_kernel.lower(*args).compile().memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 4 * (p + b), mem.temp_size_in_bytes
    assert mem.temp_size_in_bytes < p * (1 << L) // 8


def test_launch_counts_the_levels_of_both_orientations():
    """``join_probe_compare_levels`` / ``join_probe_gather_levels``: one bump
    a launched probe, from its build's bucket: the right-build probe, and
    the left-build probe an inner join makes when the right keys repeat."""
    from daft_tpu.execution import RuntimeStats
    from daft_tpu.profile import timeline

    n = 1 << (L + 2)
    lv = jnp.arange(n, dtype=jnp.int32)
    rv = jnp.arange(2 * n, dtype=jnp.int32) // 2  # every right key twice
    stats = RuntimeStats()
    with timeline.DeviceFrame(stats, "dispatch", "device_dispatch_ns"):
        resolve = dj._launch_probe(lv, jnp.ones(n, bool), rv,
                                   jnp.ones(2 * n, bool), n, 2 * n, "inner")
        side, hit, bidx = resolve()
    assert side == "left_build" and bool(hit.all())
    np.testing.assert_array_equal(bidx, np.arange(2 * n) // 2)
    c = stats.snapshot()["counters"]
    assert c["join_probe_compare_levels"] == 2 * L
    assert c["join_probe_gather_levels"] == 3 + 2


@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti"])
@pytest.mark.parametrize("n", [300, (1 << L) * 3])
def test_n_to_m_range_join_matches_the_host_join(how, n):
    """Duplicates on BOTH sides take the range join, which needs ``lo`` and
    ``counts`` exact for runs longer than one: its (left, right) row pairs
    are the host join's multiset."""
    from daft_tpu import col
    from daft_tpu.table import Table

    rng = np.random.default_rng(n)
    lk = rng.integers(0, n // 4, n).astype(np.int64)
    rk = rng.integers(n // 8, n // 3, n + 11).astype(np.int64)
    left = Table.from_pydict({"k": lk, "l": np.arange(n)})
    right = Table.from_pydict({"k": rk, "r": np.arange(n + 11)})
    side, a, b = dj.device_join_indices(left, right, col("k"), col("k"),
                                        how=how)
    host = left.hash_join(right, [col("k")], [col("k")], how=how).to_pydict()
    if how in ("semi", "anti"):
        assert side == "right_build"
        keep = a if how == "semi" else ~a
        assert sorted(np.arange(n)[keep].tolist()) == sorted(host["l"])
        return
    assert side == "expanded"
    got = sorted(zip(a.tolist(), b.tolist()))
    want = sorted((li, -1 if ri is None else ri)
                  for li, ri in zip(host["l"], host["r"]))
    assert got == want
