"""The lane's way out (tpu/runtime.py _LaneFetch / _unpack_lanes): a
leave cohort's frontiers are unpacked out of the live rows of its
fetched block, found once a cohort, or out of each leaver's whole
column where the live rows per leaver pass LANE_UNPACK_LIVE_SHARE of
the table.  Either way the arrays are, element for element and dtype
for dtype, what the formula the resolver used before gives — kept
here as the reference.  CPU jax: no number here is a device number.
"""
import numpy as np
import pytest

from nebula_tpu.cluster import LocalCluster
from nebula_tpu.common.flags import flags
from nebula_tpu.tpu.runtime import (LANE_UNPACK_LIVE_SHARE, _LaneFetch,
                                    _unpack_lanes)


def _reference(cols, perm, leavers, cols_of):
    """_LaneFetch.__call__'s loop as it stood before PR 28."""
    outs = []
    for (lane, _upto), j in zip(leavers, cols_of):
        bit = (cols[:, j] >> (lane & 7)) & np.uint8(1)
        old = bit[perm]                         # old dense order
        outs.append(np.nonzero(old)[0].astype(np.int64))
    return outs


def _index(n, buckets, seed=0):
    """perm / inv as EllIndex.build makes them: new ids ordered by
    degree bucket, stable inside one, so ``inv`` of ascending rows is
    one ascending run a bucket."""
    rng = np.random.default_rng(seed)
    width = rng.integers(0, buckets, n)
    vorder = np.lexsort((np.arange(n), width))
    perm = np.empty(n, np.int32)
    perm[vorder] = np.arange(n, dtype=np.int32)
    return perm, np.asarray(vorder, np.int32)


def _block(n, extra, P, perm, frontiers):
    """The extract's [n + extra + 1, P] block: ``frontiers`` is
    [(column, lane, old dense ids)].  Rows >= n hold junk (a pull's
    partial ORs), the pad row is zero."""
    cols = np.zeros((n + extra + 1, P), np.uint8)
    for j, lane, old_ids in frontiers:
        rows = perm[np.asarray(old_ids, np.int64)]
        cols[rows, j] |= np.uint8(1 << (lane & 7))
    cols[n:n + extra] = np.random.default_rng(1).integers(
        1, 256, (extra, P), np.uint8)
    return cols


def _case(name):
    """(cols, n, perm, inv, np_pairs, leavers, cols_of) of one
    hand-made cohort."""
    rng = np.random.default_rng(7)
    n, extra, P = 400, 6, 8
    perm, inv = _index(n, 5)
    few = lambda k, s: np.sort(np.random.default_rng(s).choice(  # noqa: E731
        n, k, replace=False))
    if name == "one_leaver":
        fr = [(0, 3, few(9, 1))]
        leavers, cols_of, pairs = [(3, False)], [0], 1
    elif name == "leavers_sharing_one_word":
        fr = [(0, 0, few(12, 1)), (0, 5, few(7, 2)), (0, 7, few(20, 3))]
        leavers = [(0, False), (5, False), (7, False)]
        cols_of, pairs = [0, 0, 0], 1
    elif name == "a_seated_lane_in_the_leavers_word":
        # lane 1 stays seated: its bits are in the column and in no answer
        fr = [(0, 1, few(60, 1)), (0, 2, few(5, 2))]
        leavers, cols_of, pairs = [(2, False)], [0], 1
    elif name == "exact_and_upto_of_one_word":
        # word 1 twice: column 0 the frontier, column 1 the accumulator
        fr = [(0, 9, few(6, 1)), (1, 9, few(30, 2)), (1, 12, few(11, 3))]
        leavers = [(9, False), (12, True)]
        cols_of, pairs = [0, 1], 2
    elif name == "several_words_P8":
        fr = [(j, 8 * j + b, few(4 + 3 * j + b, 10 * j + b))
              for j in range(5) for b in (0, 6)]
        leavers = [(8 * j + b, False) for j in range(5) for b in (0, 6)]
        cols_of, pairs = [j for j in range(5) for _b in (0, 6)], 5
    elif name == "P16_nine_pairs":
        P = 16
        fr = [(j, 8 * j + 2, few(5 + j, j)) for j in range(9)]
        leavers = [(8 * j + 2, False) for j in range(9)]
        cols_of, pairs = list(range(9)), 9
    elif name == "P16_pad_columns_repeat_word_0":
        # columns >= np_pairs hold frontier word 0 of lanes still seated
        P = 16
        fr = [(0, 1, few(8, 1)), (1, 9, few(8, 2))] + \
             [(j, 4, few(50, 3)) for j in range(2, 16)]
        leavers, cols_of, pairs = [(1, False), (9, False)], [0, 1], 2
    elif name == "empty_frontier":
        fr = [(0, 2, few(10, 1))]
        leavers, cols_of, pairs = [(2, False), (4, False)], [0, 0], 1
    elif name == "empty_block":
        fr = []
        leavers, cols_of, pairs = [(0, False)], [0], 1
    elif name == "every_row_live":
        fr = [(0, 6, np.arange(n))]
        leavers, cols_of, pairs = [(6, False)], [0], 1
    elif name == "every_row_live_twenty_leavers":
        # all rows live, yet few a leaver: the cohort shares the pass
        parts = np.array_split(rng.permutation(n), 20)
        fr = [(i >> 3, i, parts[i]) for i in range(20)]
        leavers = [(i, False) for i in range(20)]
        cols_of, pairs = [i >> 3 for i in range(20)], 3
    elif name == "just_under_the_share":
        fr = [(0, 0, few(int(LANE_UNPACK_LIVE_SHARE * n), 1))]
        leavers, cols_of, pairs = [(0, False)], [0], 1
    elif name == "just_over_the_share":
        fr = [(0, 0, few(int(LANE_UNPACK_LIVE_SHARE * n) + 1, 1))]
        leavers, cols_of, pairs = [(0, False)], [0], 1
    elif name == "no_hub_rows":
        extra = 0
        fr = [(0, 3, few(9, 1))]
        leavers, cols_of, pairs = [(3, False)], [0], 1
    elif name == "no_vertices":
        n, extra = 0, 0
        perm, inv = _index(0, 1)
        fr = []
        leavers, cols_of, pairs = [(0, False)], [0], 1
    else:
        raise AssertionError(name)
    cols = _block(n, extra, P, perm, fr)
    return cols, n, perm, inv, pairs, leavers, cols_of


# name -> does the cohort go out of the live rows?
CASES = {
    "one_leaver": True, "leavers_sharing_one_word": True,
    "a_seated_lane_in_the_leavers_word": True,
    "exact_and_upto_of_one_word": True, "several_words_P8": True,
    "P16_nine_pairs": True, "P16_pad_columns_repeat_word_0": True,
    "empty_frontier": True, "empty_block": True,
    "every_row_live": False, "every_row_live_twenty_leavers": True,
    "just_under_the_share": True, "just_over_the_share": False,
    "no_hub_rows": True, "no_vertices": True,
}


@pytest.mark.parametrize("order", ["rows_contiguous", "columns_contiguous"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_unpack_equals_the_whole_column_formula(name, order):
    cols, n, perm, inv, pairs, leavers, cols_of = _case(name)
    if order == "columns_contiguous":
        # what np.asarray gives on the TPU for P of 8 and 16 (PERF.md
        # section 6, PR 28); CPU jax and P = 128 give rows
        cols = np.asfortranarray(cols)
        assert cols.flags.f_contiguous
    cols.setflags(write=False)                  # as np.asarray's is
    assert not cols[-1].any()                   # the pad row
    want = _reference(cols, perm, leavers, cols_of)
    outs, live, rows = _unpack_lanes(cols, n, perm, inv, pairs, leavers,
                                     cols_of)
    assert len(outs) == len(leavers)
    for got, ref in zip(outs, want):
        assert got.dtype == np.int64 and got.ndim == 1
        assert np.array_equal(got, ref)
        assert np.all(np.diff(got) > 0)         # ascending, no repeat
    assert live == (len(leavers) if CASES[name] else 0)
    # the rows the pass found: those under n with a bit in a real pair
    # (rows >= n and the pad columns never count)
    assert rows == int(np.count_nonzero(cols[:n, :pairs].any(axis=1)))


def test_inv_of_ascending_rows_is_not_ascending():
    """The cases above mean something only if the sort has work to
    do: with several buckets ``inv`` of ascending rows is several
    ascending runs."""
    cols, n, perm, inv, pairs, leavers, cols_of = _case("one_leaver")
    rows = np.flatnonzero(cols[:n, 0])
    assert len(rows) > 3 and np.any(np.diff(inv[rows]) < 0)


def test_junk_in_the_hub_rows_reaches_no_answer():
    cols, n, perm, inv, pairs, leavers, cols_of = _case("one_leaver")
    clean = cols.copy()
    clean[n:-1] = 0
    assert cols[n:-1].any()
    a = _unpack_lanes(cols, n, perm, inv, pairs, leavers, cols_of)
    b = _unpack_lanes(clean, n, perm, inv, pairs, leavers, cols_of)
    assert np.array_equal(a[0][0], b[0][0]) and a[1:] == b[1:]


# ====================================== a real session, both sides
@pytest.fixture(scope="module")
def star():
    """60 vertices.  1 -> 2 -> {3, 4, 5}: two hops from 1 reach 3 of
    60 (under the share).  10 -> 11 -> {20..59}: two hops from 10
    reach 40 of 60 (over it)."""
    flags.set("go_dispatch_mode", "continuous")
    c = LocalCluster(num_storage=1, tpu_backend=True)
    g = c.client()

    def ok(stmt):
        r = g.execute(stmt)
        assert r.ok(), f"{stmt}: {r.error_msg}"
        return r

    ok("CREATE SPACE lf(partition_num=3, replica_factor=1)")
    c.refresh_all()
    ok("USE lf")
    ok("CREATE EDGE e(w int)")
    c.refresh_all()
    pairs = [(1, 2), (2, 3), (2, 4), (2, 5), (10, 11)] \
        + [(11, v) for v in range(20, 60)] \
        + [(v, v + 1) for v in range(12, 19)] \
        + [(v, 1) for v in range(6, 10)]
    ok("INSERT EDGE e(w) VALUES " + ", ".join(
        f"{a} -> {b}:({a + b})" for a, b in pairs))
    ok("GO 2 STEPS FROM 1 OVER e")          # stream anchored, compiled
    yield c, ok
    c.stop()


def _cpu(ok, stmt):
    flags.set("storage_backend", "cpu")
    try:
        return ok(stmt)
    finally:
        flags.set("storage_backend", "tpu")


def _session(c):
    rt = c.tpu_runtime
    st = next(s for s in rt.dispatcher.continuous.streams()
              if s.session is not None)
    return rt.continuous_session(st.space_id, st.et_tuple)


@pytest.mark.parametrize("start,live", [(1, 1), (10, 0)])
def test_session_join_two_hops_extract_equals_cpu(star, start, live):
    c, ok = star
    sess = _session(c)
    n = sess.ix.n
    # lane 9: word 1, so the pad columns (word 0) are another word
    sess.join([(9, [start])])
    sess.hop()
    sess.hop()
    resolver = sess.extract([(9, False), (9, True)])
    assert isinstance(resolver, _LaneFetch)
    exact, upto = resolver()
    want = sorted({row[0] for row in _cpu(
        ok, f"GO 2 STEPS FROM {start} OVER e YIELD e._dst").rows})
    assert sess.m.vids[exact].tolist() == want
    # the accumulator's column: depth 0 and everything one or two
    # steps away
    want_upto = sorted({start} | {row[0] for row in _cpu(
        ok, f"GO UPTO 2 STEPS FROM {start} OVER e YIELD e._dst").rows})
    assert sess.m.vids[upto].tolist() == want_upto
    for arr in (exact, upto):
        assert arr.dtype == np.int64 and np.all(np.diff(arr) > 0)
    # which side of the share: live rows per leaver against the table
    assert resolver.unpack_leavers == 2
    assert resolver.unpack_rows == len(want_upto)
    assert (resolver.unpack_rows <= LANE_UNPACK_LIVE_SHARE * n * 2) \
        == bool(live)
    assert resolver.unpack_live == 2 * live


@pytest.mark.parametrize("start", [1, 10])
def test_served_statements_equal_cpu_on_both_sides(star, start):
    c, ok = star
    for stmt in (f"GO 3 STEPS FROM {start} OVER e YIELD e._dst",
                 f"GO 3 STEPS FROM {start} OVER e YIELD e._dst "
                 f"| YIELD COUNT(*)",
                 f"GO UPTO 3 STEPS FROM {start} OVER e YIELD e._dst"):
        served = ok(stmt)
        assert sorted(map(tuple, served.rows)) == \
            sorted(map(tuple, _cpu(ok, stmt).rows)), stmt
