"""The lane's way out (tpu/ell.py make_lane_extract_kernel, tpu/runtime.py
_LaneFetch / _unpack_lanes): the extract packs each leaving lane down
the vertex rows on the device, eight rows a byte, plane by plane, and
the cohort's bitmaps become its leavers' id arrays in one native call
(native/unpack.cc, PR 47: a bitmap of id bits read off in order) or,
where the library lacks the entry, in numpy: a leaver's frontier out
of the non-zero bytes of its own bitmap (one pass for all such leavers
of a cohort), or out of the whole bitmap through ``perm`` where its
set rows pass LANE_UNPACK_LIVE_SHARE of the table.  Every case runs
against both forms (the ``form`` fixture; the numpy one is reached by
hiding the symbol).  Whichever way, the arrays are,
element for element and dtype for dtype, what the formula the resolver
used before PR 28 gives over the lane's word column — kept here as the
reference, and every hand-made cohort it was held to is kept with it.
CPU jax: no number here is a device number.
"""
import numpy as np
import pytest

from nebula_tpu.cluster import LocalCluster
from nebula_tpu.common.flags import flags
from nebula_tpu.tpu import ell as E
from nebula_tpu.tpu.runtime import (LANE_UNPACK_LIVE_SHARE, _LaneFetch,
                                    _unpack_lanes)


@pytest.fixture(params=["native", "numpy"])
def form(request, stale_native):
    """Which form of _unpack_lanes the test meets: the library's one
    call, or numpy behind a library that lacks the entry (a stale
    build), which is how the fallback is reached anywhere."""
    from nebula_tpu import native
    if request.param == "numpy":
        stale_native("neb_unpack_lanes")
    elif not hasattr(native.lib(), "neb_unpack_lanes"):
        pytest.skip("native lib unavailable")
    return request.param


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


def _pack_rows(bits, n):
    """A lane's bitmap as the extract lays it: ``bits`` is the lane's
    0/1 column over the rows < n; plane k (rows k * nb .. k * nb + nb
    - 1) goes to bit k of the nb bytes — np.packbits, little, of the
    column laid plane by plane."""
    nb = E.lane_bitmap_bytes(n)
    planes = np.zeros(8 * nb, np.uint8)
    planes[:n] = bits
    return np.packbits(planes.reshape(8, nb).T, axis=1,
                       bitorder="little")[:, 0]


def _packed(cols, n, leavers, cols_of, rung=None, order="C"):
    """What the extract hands back for a cohort whose word columns are
    ``cols`` (the block the extract returned until PR 37, which the
    reference still reads): uint8 [rung, lane_bitmap_bytes(n)], a
    leaver's row its bit of its column over the rows < n; the rung's
    padding rows hold another lane's bits, as a padding leaver's do
    (word 0, bit 0)."""
    L = rung or E.lane_extract_rung(len(leavers), 128)
    out = np.zeros((L, E.lane_bitmap_bytes(n)), np.uint8)
    for i, ((lane, _upto), j) in enumerate(zip(leavers, cols_of)):
        out[i] = _pack_rows((cols[:n, j] >> (lane & 7)) & np.uint8(1), n)
    out[len(leavers):] = _pack_rows(cols[:n, 0] & np.uint8(1), n)
    return np.asarray(out, order=order)


def _case(name):
    """(cols, n, perm, inv, np_pairs, leavers, cols_of) of one
    hand-made cohort: ``cols`` is the word-column block of the
    reference, _packed turns it into what _unpack_lanes is fed."""
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
    elif name == "full_lanes_among_sparse_ones":
        # each leaver by its own count: two go through perm, three out
        # of their non-zero bytes, and keep their places in the cohort
        fr = [(0, 0, few(5, 1)), (0, 1, np.arange(n)), (1, 8, few(30, 2)),
              (1, 9, few(300, 3)), (1, 10, few(1, 4))]
        leavers = [(i, False) for i in (0, 1, 8, 9, 10)]
        cols_of, pairs = [0, 0, 1, 1, 1], 2
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


# name -> do the leavers go out of their non-zero bytes?  (a number:
# so many of them do)
CASES = {
    "full_lanes_among_sparse_ones": 3,
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
def test_unpack_equals_the_whole_column_formula(name, order, form):
    cols, n, perm, inv, pairs, leavers, cols_of = _case(name)
    # a leaver's bitmap contiguous (what np.asarray gives on the TPU
    # and on CPU jax) or strided (a buffer handed over the other way
    # round, as the column block was on the TPU: PERF.md section 6,
    # PR 28): the unpack reads either
    packed = _packed(cols, n, leavers, cols_of,
                     order="C" if order == "rows_contiguous" else "F")
    packed.setflags(write=False)                # as np.asarray's is
    want = _reference(cols, perm, leavers, cols_of)
    outs, live, rows, native = _unpack_lanes(packed, n, perm, inv,
                                             len(leavers))
    assert native == (len(leavers) if form == "native" else 0)
    assert len(outs) == len(leavers)
    for got, ref in zip(outs, want):
        assert got.dtype == np.int64 and got.ndim == 1
        assert np.array_equal(got, ref)
        assert np.all(np.diff(got) > 0)         # ascending, no repeat
    assert live == (len(leavers) if CASES[name] is True else CASES[name])
    # the set rows found, leaver by leaver (rows >= n, the seated
    # lanes of a leaver's word and the rung's padding never count)
    assert rows == sum(len(ref) for ref in want)


def test_inv_of_ascending_rows_is_not_ascending():
    """The cases above mean something only if the sort has work to
    do: with several buckets ``inv`` of ascending rows is several
    ascending runs."""
    cols, n, perm, inv, pairs, leavers, cols_of = _case("one_leaver")
    rows = np.flatnonzero(cols[:n, 0])
    assert len(rows) > 3 and np.any(np.diff(inv[rows]) < 0)


def test_junk_in_the_hub_rows_reaches_no_answer(form):
    """The hub extra rows' junk never reaches the packed form, so
    both blocks pack to the same buffer and unpack to one answer."""
    cols, n, perm, inv, pairs, leavers, cols_of = _case("one_leaver")
    clean = cols.copy()
    clean[n:-1] = 0
    assert cols[n:-1].any()
    pa, pb = (_packed(c, n, leavers, cols_of) for c in (cols, clean))
    assert np.array_equal(pa, pb)
    a = _unpack_lanes(pa, n, perm, inv, len(leavers))
    b = _unpack_lanes(pb, n, perm, inv, len(leavers))
    assert np.array_equal(a[0][0], b[0][0]) and a[1:] == b[1:]


@pytest.mark.parametrize("k", [0, 1, 79, 80, 81, 200, 399, 400])
def test_both_routes_give_one_array_on_either_side_of_the_share(
        k, monkeypatch, form):
    """A leaver of k set rows of 400, unpacked with the share put
    under and over k: the route is a speed choice only (numpy), and
    no choice at all (native: the share only says which side of it
    the leaver is counted on)."""
    from nebula_tpu.tpu import runtime
    n = 400
    perm, inv = _index(n, 5)
    old_ids = np.sort(np.random.default_rng(k).choice(n, k, replace=False))
    bits = np.zeros(n, np.uint8)
    bits[perm[old_ids]] = 1
    bitmap = _pack_rows(bits, n)
    packed = np.stack([bitmap, bitmap])     # the second row: padding
    got = {}
    for share, sparse in ((1.0, 1), (-1.0, 0)):
        monkeypatch.setattr(runtime, "LANE_UNPACK_LIVE_SHARE", share)
        (ids,), was_sparse, found, _native = _unpack_lanes(
            packed, n, perm, inv, 1)
        assert was_sparse == sparse and found == k
        assert ids.dtype == np.int64
        got[sparse] = ids
    assert np.array_equal(got[1], got[0])
    assert np.array_equal(got[1], old_ids)
    # the shipped share puts the turn at 80 of 400
    monkeypatch.setattr(runtime, "LANE_UNPACK_LIVE_SHARE",
                        LANE_UNPACK_LIVE_SHARE)
    assert _unpack_lanes(packed, n, perm, inv, 1)[1] \
        == (k <= LANE_UNPACK_LIVE_SHARE * n)


# ===================================== cohorts of every size, both forms
def _cohort(n, sizes, seed, rung=None, poison=0xFF):
    """(packed, perm, inv, want) of a cohort whose leaver i has
    sizes[i] set rows: each leaver's 0/1 column over the vertex rows,
    packed as the extract packs it, and ``np.nonzero(column_bit[perm])
    [0]`` as what the unpack owes.  The rung's padding rows, past the
    leavers, are all ones (or another byte): whoever read one would
    count its rows."""
    perm, inv = _index(n, 5, seed)
    rng = np.random.default_rng(seed)
    L = rung or E.lane_extract_rung(len(sizes), 128)
    packed = np.full((L, E.lane_bitmap_bytes(n)), poison, np.uint8)
    want = []
    for i, k in enumerate(sizes):
        column_bit = np.zeros(n, np.uint8)
        column_bit[rng.choice(n, k, replace=False)] = 1
        packed[i] = _pack_rows(column_bit, n)
        want.append(np.nonzero(column_bit[perm])[0])
    return packed, perm, inv, want


def _same(got, want):
    outs, live, rows, _native = got
    assert len(outs) == len(want)
    for ids, ref in zip(outs, want):
        assert ids.dtype == np.int64 and ids.ndim == 1
        assert np.array_equal(ids, ref)
    assert rows == sum(len(ref) for ref in want)
    return live


@pytest.mark.parametrize("n", [1, 61, 64, 65, 400, 1003, 4099])
@pytest.mark.parametrize("leavers", [1, 4, 25, 128])
def test_cohorts_of_1_4_25_and_128_leavers_at_any_n(n, leavers, form):
    """n under 64, a multiple of 64 and not; a cohort that fills its
    rung (4, 128) and one that leaves padding (1, 25), poisoned with
    0xFF and never read; among the leavers one of no set rows and one
    of all n; ``inv`` int32, as the index holds it."""
    rng = np.random.default_rng(n + leavers)
    sizes = [0, n] + rng.integers(0, n + 1, leavers).tolist()
    sizes = sizes[2:] if leavers == 1 else sizes[:leavers]
    packed, perm, inv, want = _cohort(n, sizes, seed=n * leavers)
    assert inv.dtype == np.int32
    assert packed[len(sizes):].all() or leavers in (4, 128)
    live = _same(_unpack_lanes(packed, n, perm, inv, len(sizes)), want)
    assert live == sum(k <= LANE_UNPACK_LIVE_SHARE * n for k in sizes)


@pytest.mark.parametrize("sizes", [[0], [400], [0, 400, 0, 400]])
def test_a_leaver_of_no_set_rows_and_one_of_all_n(sizes, form):
    packed, perm, inv, want = _cohort(400, sizes, seed=3)
    outs = _unpack_lanes(packed, 400, perm, inv, len(sizes))[0]
    _same((outs, None, sum(sizes), None), want)
    for ids, k in zip(outs, sizes):
        assert ids.tolist() == list(range(k))


@pytest.mark.parametrize("layout", [
    "every_other_row", "inside_a_wider_buffer", "rows_backwards",
    "off_the_word"])
def test_a_strided_packed_is_read_where_it_lies(layout, form):
    """The leavers' rows at a stride that is not their length, a
    negative one, and a start that is no multiple of eight bytes: the
    native pass takes the stride and loads a word from any address;
    the numpy form copies first.  What lies between the rows is all
    ones and reaches no answer."""
    n, sizes = 1003, [0, 7, 300, 1003, 64]
    tight, perm, inv, want = _cohort(n, sizes, seed=11, rung=8)
    L, nb = tight.shape
    if layout == "every_other_row":
        wide = np.full((2 * L, nb), 0xFF, np.uint8)
        packed = wide[::2]
    elif layout == "inside_a_wider_buffer":
        wide = np.full((L, nb + 40), 0xFF, np.uint8)
        packed = wide[:, 16:16 + nb]
    elif layout == "rows_backwards":
        wide = np.full((L, nb), 0xFF, np.uint8)
        packed = wide[::-1]
    else:
        wide = np.full(L * nb + 3, 0xFF, np.uint8)
        packed = wide[3:].reshape(L, nb)
        assert packed.ctypes.data % 8 != wide.ctypes.data % 8
    packed[:] = tight
    assert layout == "off_the_word" or not packed.flags.c_contiguous
    assert packed.strides[1] == 1
    packed.setflags(write=False)
    _same(_unpack_lanes(packed, n, perm, inv, len(sizes)), want)


@pytest.mark.parametrize("dtype", [np.int32, np.int64])
@pytest.mark.parametrize("strided", [False, True])
def test_inv_is_read_as_int32_however_it_comes(dtype, strided, form):
    """The index holds ``inv`` as contiguous int32 and the native pass
    reads that in place; any other array is made one first."""
    n, sizes = 400, [5, 0, 390]
    packed, perm, inv, want = _cohort(n, sizes, seed=5)
    inv = inv.astype(dtype)
    if strided:
        inv = np.stack([inv, inv], axis=1)[:, 0]
        assert not inv.flags.c_contiguous
    _same(_unpack_lanes(packed, n, perm, inv, len(sizes)), want)


def test_both_forms_give_equal_arrays_and_counts():
    """One cohort through whatever form the library gives and through
    numpy: the same arrays, the same ``live`` (the share of 4,099 is
    819.8 rows) and ``rows``; only ``native`` differs."""
    from nebula_tpu.tpu.runtime import _unpack_lanes_numpy
    n, sizes = 4099, [0, 1, 63, 64, 65, 819, 820, 2000, 4099]
    packed, perm, inv, want = _cohort(n, sizes, seed=9)
    got = _unpack_lanes(packed, n, perm, inv, len(sizes))
    ref = _unpack_lanes_numpy(packed, n, perm, inv, len(sizes))
    assert _same(got, want) == _same(ref + (0,), want) == 6
    assert got[1:3] == ref[1:]


def test_the_native_pass_skips_what_the_contract_rules_out():
    """Not the extract's output, but nothing the pass may follow out
    of its arrays: bits of rows from n on (the bitmap has room for
    them up to the next 64) are passed over, and so is a row whose
    ``inv`` lies outside the table."""
    from nebula_tpu import native
    if not hasattr(native.lib(), "neb_unpack_lanes"):
        pytest.skip("native lib unavailable")
    n = 61
    perm, inv = _index(n, 5)
    packed = np.full((4, E.lane_bitmap_bytes(n)), 0xFF, np.uint8)
    (ids,), live, rows, native_n = _unpack_lanes(packed, n, perm, inv, 1)
    assert ids.tolist() == list(range(n)) and rows == n
    assert (live, native_n) == (0, 1)
    bad = inv.copy()
    bad[perm[7]], bad[perm[9]] = -1, n
    (ids,), _live, rows, _n = _unpack_lanes(packed, n, perm, bad, 1)
    assert ids.tolist() == [i for i in range(n) if i not in (7, 9)]
    assert rows == n - 2
    with pytest.raises(IndexError):
        _unpack_lanes(packed, n, perm, inv[:n - 1], 1)


def test_the_fallback_is_said_once_on_stderr(form, capfd, monkeypatch):
    from nebula_tpu.tpu import runtime
    monkeypatch.setattr(runtime, "_said", set())
    packed, perm, inv, want = _cohort(400, [3, 0], seed=1)
    for _ in range(3):
        native = _unpack_lanes(packed, 400, perm, inv, 2)[3]
    said = capfd.readouterr().err
    assert said.count("native lane unpack missing") \
        == (0 if form == "native" else 1)
    assert native == (2 if form == "native" else 0)


# ============================== the device's pack, on CPU jax
class _N:
    """What make_lane_extract_kernel reads of an EllIndex."""

    def __init__(self, n):
        self.n = n


def _pair(n, extra, W, seed):
    """A resident pair with bits everywhere, junk in the hub extra
    rows and (against the contract, to show it is never read) in the
    pad row."""
    rng = np.random.default_rng(seed)
    fp = rng.integers(0, 256, (n + extra + 1, W), dtype=np.uint8) \
        & rng.integers(0, 256, (n + extra + 1, W), dtype=np.uint8)
    acc = fp | rng.integers(0, 256, fp.shape, dtype=np.uint8)
    return fp, acc


def _want(fp, acc, n, lanes):
    return np.stack([
        _pack_rows(((acc if carrier else fp)[:n, word] >> bit) & 1, n)
        for word, bit, carrier in lanes.T])


def _rows_of(bitmap, n):
    """The set rows of one bitmap, ascending, by the map the host
    uses (ell.lane_bitmap_rows)."""
    at, bit = np.nonzero(np.unpackbits(bitmap[:, None], axis=1,
                                       bitorder="little"))
    return np.sort(E.lane_bitmap_rows(at, bit, n))


@pytest.mark.parametrize("n", [61, 1000, 1003, 1024])
@pytest.mark.parametrize("L", E.lane_extract_rungs(1024))
def test_device_pack_equals_packbits_at_every_rung(n, L):
    """n under 64, a multiple of 8 and not of 64, of neither, of
    both; every rung of the 128-lane width and of the 1,024-lane
    width the ladder widens to (the first's are among the second's);
    each lane against np.packbits(..., bitorder="little") of its
    column, plane by plane, and against the column itself by the
    host's map."""
    import jax.numpy as jnp
    assert set(E.lane_extract_rungs(128)) < set(E.lane_extract_rungs(1024))
    W = E.lanes_width(max(L, 128))
    fp, acc = _pair(n, 5, W, seed=n + L)
    rng = np.random.default_rng(L)
    lanes = np.stack([rng.integers(0, W, L), rng.integers(0, 8, L),
                      rng.integers(0, 2, L)]).astype(np.int32)
    got = np.asarray(E.make_lane_extract_kernel(_N(n))(
        jnp.asarray(fp), jnp.asarray(acc), lanes))
    assert got.dtype == np.uint8
    assert got.shape == (L, E.lane_bitmap_bytes(n))
    assert got.shape[1] % 8 == 0 and got.shape[1] * 8 >= n
    assert np.array_equal(got, _want(fp, acc, n, lanes))
    for row, (word, bit, carrier) in zip(got[:3], lanes.T):
        col = (acc if carrier else fp)[:n, word]
        assert np.array_equal(_rows_of(row, n),
                              np.flatnonzero((col >> bit) & 1))


def test_rungs_hold_every_leaver_count():
    assert E.lane_extract_rungs(128) == (4, 8, 16, 32, 64, 128)
    for B in (128, 1024, 96, 100, 4):
        rungs = E.lane_extract_rungs(B)
        assert rungs[-1] == B and list(rungs) == sorted(set(rungs))
        for k in range(1, B + 1):
            L = E.lane_extract_rung(k, B)
            assert L in rungs and L >= k
            assert all(r < k for r in rungs if r < L)


def test_device_pack_of_an_empty_and_a_full_lane_and_one_word_twice():
    """One cohort: a lane with no bit, a lane with every bit, and an
    exact-depth and an UPTO leaver of one word (two carriers, one
    word, two rows of the result); hub rows and pad row all ones."""
    import jax.numpy as jnp
    n, extra, W = 203, 4, E.lanes_width(128)
    fp = np.zeros((n + extra + 1, W), np.uint8)
    acc = np.zeros_like(fp)
    fp[:n, 2] |= 1 << 5                     # lane 21: full
    fp[::3, 1] |= 1 << 1                    # lane 9, the frontier
    acc[::2, 1] |= 1 << 1                   # lane 9, the accumulator
    fp[n:] = 0xFF                           # junk: never packed
    acc[n:] = 0xFF
    # lane 40 (word 5, bit 0): no bit anywhere
    lanes = np.array([[5, 0, 0], [2, 5, 0], [1, 1, 0], [1, 1, 1]],
                     np.int32).T
    got = np.asarray(E.make_lane_extract_kernel(_N(n))(
        jnp.asarray(fp), jnp.asarray(acc), lanes))
    rows = [_rows_of(r, n) for r in got]
    assert rows[0].tolist() == []
    assert rows[1].tolist() == list(range(n))       # nothing past n
    assert rows[2].tolist() == list(range(0, n, 3))
    assert rows[3].tolist() == list(range(0, n, 2))
    clean_fp, clean_acc = fp.copy(), acc.copy()
    clean_fp[n:] = 0
    clean_acc[n:] = 0
    again = np.asarray(E.make_lane_extract_kernel(_N(n))(
        jnp.asarray(clean_fp), jnp.asarray(clean_acc), lanes))
    assert np.array_equal(got, again)


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
def test_session_join_two_hops_extract_equals_cpu(star, start, live,
                                                  form):
    c, ok = star
    sess = _session(c)
    n = sess.ix.n
    # lane 9: word 1, so the pad columns (word 0) are another word;
    # both carriers of the lane are read below, so the seat may not
    # take its first hop (the accumulator needs depth 0 apart)
    assert sess.join([(9, [start], False)]) == [False]
    sess.hop()
    sess.hop()
    before = sess.rt.stats["fetch_bytes"]
    native_before = sess.rt.stats["unpack_native"]
    resolver = sess.extract([(9, False), (9, True)])
    assert isinstance(resolver, _LaneFetch)
    exact, upto = resolver()
    # what crossed the link: the rung's buffer, the least rung's four
    # bitmaps of the vertex rows for two leavers
    assert sess.rt.stats["fetch_bytes"] - before \
        == 4 * E.lane_bitmap_bytes(n)
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
    # which side of the share: each leaver's own set rows against the
    # table (here both leavers fall on one side)
    assert resolver.unpack_leavers == 2
    assert resolver.unpack_rows == len(want) + len(want_upto)
    for rows in (want, want_upto):
        assert (len(rows) <= LANE_UNPACK_LIVE_SHARE * n) == bool(live)
    assert resolver.unpack_live == 2 * live
    # which form unpacked them, on the resolver and in rt.stats
    assert resolver.unpack_native == (2 if form == "native" else 0)
    assert sess.rt.stats["unpack_native"] - native_before \
        == resolver.unpack_native


@pytest.mark.parametrize("start", [1, 10])
def test_session_seat_takes_the_first_of_two_hops(star, start):
    """The same exact-depth answer out of ONE device hop: the join
    scattered the start's out-neighbours (PR 43)."""
    c, ok = star
    sess = _session(c)
    assert sess.join([(9, [start], True)]) == [True]
    sess.hop()
    (exact,) = sess.extract([(9, False)])()
    want = sorted({row[0] for row in _cpu(
        ok, f"GO 2 STEPS FROM {start} OVER e YIELD e._dst").rows})
    assert sess.m.vids[exact].tolist() == want
    assert exact.dtype == np.int64 and np.all(np.diff(exact) > 0)


def test_every_rung_runs_once_and_only_where_a_stream_fetches(star):
    """A session whose leavers only count runs no extract program, so
    loads none; the first fetching cohort over these table shapes at
    this width runs every rung of leavers once, and a session
    re-anchored over the same shapes runs only its cohort's."""
    c, _ok = star
    rt = c.tpu_runtime
    sess = _session(c)
    sig = sess.ix.shape_sig()
    assert (sig, sess.B) in rt.extract_rungs_run    # the fixture's GO
    rt.extract_rungs_run.discard((sig, sess.B))
    kern, ran = rt._kernels[("ell_lane_extract", sig)], []
    rt._kernels[("ell_lane_extract", sig)] = \
        lambda fp, accp, lanes: ran.append(lanes.shape[1]) or kern(
            fp, accp, lanes)
    try:
        sess.join([(9, [1], False)])
        sess.hop()
        assert list(sess.count([9])()) == [1]
        assert not ran and (sig, sess.B) not in rt.extract_rungs_run
        first = sess.extract([(9, False)])()
        assert ran == list(E.lane_extract_rungs(sess.B)) + [4]
        assert (sig, sess.B) in rt.extract_rungs_run
        again = _session(c)
        again.join([(9, [1], False)])
        again.hop()
        assert np.array_equal(again.extract([(9, False)])()[0], first[0])
        assert ran[len(E.lane_extract_rungs(sess.B)):] == [4, 4]
    finally:
        rt._kernels[("ell_lane_extract", sig)] = kern


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
