"""The dense segment reductions compiled for a v5e at the scan cell's size,
here, without the chip (the TPU's compiler is installed; nothing runs).

What a CPU run cannot show: whether the chip's compiler fuses a fused
aggregate program's fan of per-group reductions into a few passes over the
staged columns, or writes a 64M-row temporary a reduction (a Python loop over
the groups did: 10 GB of temporaries; one variadic reduce did not fit the
chip at all). The programs below are Q1's and Q6's shape through the
program's own ``segment_reduce``; the compiler's own account of their
temporaries is the guard. The join probe's search (PR 35) is held the same
way at ``tpch1-join``'s Q5 shape, and the float sum over more than 4096
groups at an eighth of ``tpch1-sql-subquery``'s Q18, and the runtime
join filter's keep-mask at its Q17. No time is read here.
"""

import pytest

ROWS = 1 << 26  # tpch10-scan-agg's bucket: 60M rows of LINEITEM
GIB = 1 << 30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no compiler for the chip on this machine
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, dtypes, rows=None):
    import jax

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep it out
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        args = [jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)
                for dt, n in zip(dtypes, rows or [ROWS] * len(dtypes))]
        return jax.jit(fn).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)


def _sums_and_counts(columns, sel, codes, groups):
    """What ``_compile_agg`` asks of ``segment_reduce`` for float sums."""
    from daft_tpu.kernels import device as dev

    outs = []
    for v, m in columns:
        m = m & sel
        outs.append((dev.segment_reduce(v, m, codes, groups, "sum"),
                     dev.segment_reduce(m, m, codes, groups, "count")[0]))
    outs.append(dev.segment_reduce(sel, sel, codes, groups, "count")[0])
    outs.append(dev.segment_first_index(sel, codes, groups))
    return outs


def test_q1_shape_fuses_into_few_passes(one_chip):
    import jax.numpy as jnp

    def q1(qty, price, disc, tax, ship, codes, vq, vp, vd, vt, vs):
        sel = (ship <= 10471) & vs
        disc_price = price * (1 - disc)
        charge = disc_price * (1 + tax)
        columns = [(qty, vq), (price, vp), (disc_price, vp & vd),
                   (charge, vp & vd & vt), (disc, vd)]
        return _sums_and_counts(columns, sel, codes, 8)

    f32, i32, b = jnp.float32, jnp.int32, jnp.bool_
    compiled = _compile(q1, one_chip,
                        [f32, f32, f32, f32, i32, i32, b, b, b, b, b])
    text = compiled.as_text()
    assert "tpu_custom_call" not in text  # the compiler's code, no kernel
    # no stacked or padded (K, rows) operand, no minor dimension of groups
    assert "f32[8,67108864]" not in text and "f32[7,67108864]" not in text
    # the masks and the two products are still written out (0.81 GiB); a
    # temporary a reduction would be tens of GiB
    assert compiled.memory_analysis().temp_size_in_bytes < 1 * GIB


def test_ungrouped_shape_stays_fused(one_chip):
    import jax.numpy as jnp

    # Q6: a lone group is given a bucket of two (device._dense_hits): with
    # the group axis gone the compiler writes the value column out and
    # reduces it unfused
    def q6(price, disc, qty, codes, vp, vd, vq):
        sel = (disc >= 0.05) & (disc <= 0.07) & (qty < 24) & vd & vq
        return _sums_and_counts([(price * disc, vp & vd)], sel, codes, 2)

    f32, i32, b = jnp.float32, jnp.int32, jnp.bool_
    compiled = _compile(q6, one_chip, [f32, f32, f32, i32, b, b, b])
    entry = compiled.as_text().split("ENTRY", 1)[1]
    written = [line.split(" = ", 1)[0].strip() for line in entry.splitlines()
               if " fusion(" in line
               and "f32[67108864]" in line.split(" fusion(", 1)[0]]
    assert not written, written  # no float column is written out
    assert compiled.memory_analysis().temp_size_in_bytes < GIB // 2


def test_join_probe_search_keeps_one_probe_sized_temporary(one_chip):
    import jax.numpy as jnp

    from daft_tpu.kernels import device_join as dj

    # Q5's second orientation: 8M probe lanes (LINEITEM) over a 64k build.
    # peak_hbm_gib is set inside a probe: with the gather levels unrolled
    # the compiler kept three arrays of P lanes live (102 MB); as a loop,
    # and with the pivots compared a chunk a pass, it keeps one (34 MB)
    p, b = 1 << 23, 1 << 16
    compiled = _compile(lambda *a: dj._match_ranges(*a), one_chip,
                        [jnp.int32, jnp.bool_, jnp.int32, jnp.bool_],
                        rows=[b, b, p, p])
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5 * 4 * p


def test_float_sum_over_many_groups_grows_with_rows_plus_groups(one_chip):
    import jax.numpy as jnp

    from daft_tpu.kernels import device as dev

    # an eighth of Q18's SUM(l_quantity) GROUP BY l_orderkey at SF1 (8M
    # lanes, a 2M-segment bucket), to keep the compile short. A row of
    # partials a chunk of 8192 rows asked for 128 x 262144 floats here, 128
    # MiB and its Kahan carries (8.06 GiB at Q18's shape); the sorted form
    # keeps a few row-sized arrays (32.1 MiB at Q18's shape)
    rows, groups = 1 << 20, 1 << 18
    assert groups > dev._ONEHOT_MAX_SEGMENTS

    def q18(qty, valid, codes):
        return (dev.segment_reduce(qty, valid, codes, groups, "sum"),
                dev.segment_reduce(valid, valid, codes, groups, "count")[0])

    compiled = _compile(q18, one_chip, [jnp.float32, jnp.bool_, jnp.int32],
                        rows=[rows] * 3)
    text = compiled.as_text()
    assert f"f32[{rows // 8192},{groups}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_join_filter_keep_mask_reads_each_lane_once(one_chip):
    import jax.numpy as jnp

    from daft_tpu.exchange import joinfilter

    # Q17's outer join at SF1: LINEITEM's 8M-lane bucket of l_partkey
    # against the direct bits of ~200 part keys spanning 200k values. The
    # host-indexed Bloom gather it replaced read 4 x 6M positions into a
    # u8[24000000]; the keep program writes the bool mask and nothing else
    p, words = 1 << 23, 1 << 13
    keep = joinfilter._keep_program()
    compiled = _compile(
        lambda v, m, w, lo, hi: keep(v, m, w, lo[0], hi[0]), one_chip,
        [jnp.int32, jnp.bool_, jnp.uint32, jnp.int32, jnp.int32],
        rows=[p, p, words, 1, 1])
    assert compiled.memory_analysis().temp_size_in_bytes < p // 4
