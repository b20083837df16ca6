"""The continuous hop program (PR 40: and its two-sided form, the
OVER set of a GO ... BIDIRECT), the join program beside it at every
rung of scatter rows (PR 43), the batched BFS program whose levels
take the same step (PR 30), the windowed GO (PR 39: the three programs
that pull, each cut by the cell's reach at 4 and at 8 column ranges
and held against the whole sweep), the per-lane count of the resident
frontier (PR 33) and the leavers' extract at every rung of leavers
(PR 37), compiled for the v5e at the
benchmark cell's real table shapes — no chip needed: the TPU's
compiler is installed here and compiles for a described, unattached
chip (PERF.md §6, PR 25).

What it guards is what no CPU test can see: the push branch once made
the compiler lay whole slot tables out row-major for a single row read
(2 x 197 MB of copies and temporaries a hop for the 8-wide bucket) and
a flat running sum over 670 k rows cost it 33 s of compile.  Both read
as a scratch size and a compile time here.  Nothing runs: no time, no
result.  One file, one module-scoped topology (one process may hold
the TPU library; see the on-chip-measurement guide)."""
import time

import numpy as np
import pytest

# graph500-s20's buckets (rows, width) as the cell builds them — the
# same in each direction's table (PR 35) — its vertices, slot rows and
# hub extra rows with the 8 growth spares (PERF.md §5); the etype
# columns are int8 there (one edge type)
S20_BUCKETS = [(452588, 8), (56666, 16), (74253, 32), (6223, 64),
               (34651, 128), (15422, 256), (17871, 512)]
S20_N, S20_ROWS, S20_EXTRAS, S20_HUBS = 646081, 657674, 11593, 6197
LANES = 128

# The cell's reach (EllIndex.reach, PR 39) at 4 and at 8 column ranges,
# (in-table, out-table) x bucket x range: the leading main rows of a
# bucket that hold a real slot from the range's first column on,
# rounded up to 1,024 rows.  Made by a replay of the configuration's
# graph on the sandbox's host, numpy only: benchmark/generators/
# kronecker.py at structure_seed 50020, self-loops and duplicates
# dropped (16,084,349 edges, 646,081 vertices with one), both
# directions handed to EllIndex.build(cap=512, min_d=8, growth_slack=8),
# which gives S20_BUCKETS to the row; the reach depends on the degrees
# alone, so every --seed's relabelling has this one.  The cap bucket's
# 6,278 main rows reach every range (6,196 of them are full), so it
# keeps one loop over its 17,871 rows.
S20_REACH = {
    4: (((353280, 135168, 64512, 27648), (56666, 51200, 33792, 4096),
         (74253, 74253, 66560, 19456), (6223, 6144, 6144, 4096),
         (34651, 34651, 26624, 1024), (15422, 15422, 15422, 15360),
         (6278,) * 4),
        ((452588, 452588, 452588, 451584), (56666,) * 4, (74253,) * 4,
         (6223, 6223, 6223, 5120), (34651, 34651, 34651, 33792),
         (15422,) * 4, (6278,) * 4)),
    8: (((353280, 207872, 135168, 92160, 64512, 45056, 27648, 12288),
         (56666, 56320, 51200, 41984, 33792, 12288, 4096, 2048),
         (74253, 74253, 74253, 73728, 66560, 44032, 19456, 5120),
         (6223, 6223, 6144, 6144, 6144, 5120, 4096, 4096),
         (34651, 34651, 34651, 34651, 26624, 3072, 1024, 1024),
         (15422, 15422, 15422, 15422, 15422, 15422, 15360, 4096),
         (6278,) * 8),
        ((452588,) * 5 + (451584,) * 3, (56666,) * 8, (74253,) * 8,
         (6223,) * 6 + (5120,) * 2,
         (34651,) * 6 + (33792, 1024), (15422,) * 8, (6278,) * 8)),
}
# slots a forward pull gathers under each (ell.swept_slots), of the
# in-table's 24,835,040
S20_SWEPT = {4: 20178536, 8: 18994048}


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — any failure means: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


class _Shapes:
    """What a kernel builder reads of an EllIndex: sizes only (the
    zero tables are never touched)."""

    def __init__(self, ranges=None):
        self.n, self.n_rows = S20_N, S20_ROWS
        # None: every row at every column, the program until PR 39
        self.reach = S20_REACH.get(ranges)
        self.extra_owner = np.zeros(S20_EXTRAS, np.int32)
        self.bucket_nbr = [np.zeros(s, np.int32) for s in S20_BUCKETS]
        self.bucket_et = [np.zeros(s, np.int8) for s in S20_BUCKETS]
        self.out_nbr, self.out_et = self.bucket_nbr, self.bucket_et


def _compile(fn, one_chip, carriers=2):
    import jax
    from nebula_tpu.tpu import ell as E

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fp = sd((S20_ROWS + 1, E.lanes_width(LANES)), np.uint8)
    # tables as EllIndex.kernel_args orders them: (*in_nbr, *in_et,
    # *out_nbr, *out_et)
    args = (fp,) * carriers \
        + (sd((S20_EXTRAS,), np.int32), sd((S20_HUBS,), np.int32)) \
        + (tuple(sd(s, np.int32) for s in S20_BUCKETS)
           + tuple(sd(s, np.int8) for s in S20_BUCKETS)) * 2
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    return compiled, time.perf_counter() - t0


@pytest.fixture(scope="module")
def whole(one_chip):
    """The programs over an index that carries no reach — every row at
    every column, what the parent of PR 39 compiled — as (scratch
    bytes, code bytes), each compiled once for the module."""
    from nebula_tpu.tpu import ell as E
    made = {}

    def get(name):
        if name not in made:
            fn = {"hop": lambda: E.make_continuous_hop_kernel(
                      _Shapes(), (1,), donate=True),
                  "bfs": lambda: E.make_batched_bfs_lanes_kernel(
                      _Shapes(), 5, (1,), stop_when_found=True,
                      donate=True),
                  "go": lambda: E.make_batched_go_lanes_kernel(
                      _Shapes(), 3, (1,), donate=True)}[name]()
            made[name] = _sizes(_compile(
                fn, one_chip, carriers=1 if name == "go" else 2)[0])
        return made[name]

    return get


def _sizes(compiled):
    mem = compiled.memory_analysis()
    return mem.temp_size_in_bytes, mem.generated_code_size_in_bytes


@pytest.fixture(scope="module")
def bfs_at(one_chip):
    """jit_bfs at the cell's shapes (5 steps, shortest) by (column
    ranges, OVER set) as (compiled, compile seconds), each compiled
    once for the module."""
    from nebula_tpu.tpu import ell as E
    made = {}

    def get(ranges, etypes):
        if (ranges, etypes) not in made:
            made[ranges, etypes] = _compile(
                E.make_batched_bfs_lanes_kernel(
                    _Shapes(ranges), 5, etypes, stop_when_found=True,
                    donate=True), one_chip)
        return made[ranges, etypes]

    return get


# What a program of PR 39 may take on the device over its parent's, in
# generated code: every column range of a bucket is a loop with a
# gather of its own, 0.3-0.4 MB each, and code is device memory while
# the program is loaded (PERF.md section 7, "Left by PR 37" (a)).
# Measured +4.0 MB (jit_hop) and +4.1 MB (jit_bfs) at 4 ranges, +8.1
# and +7.9 MB at 8; device_bytes_per_edge's bound of 0.01 is 8 MB at
# this graph's 16.08 M edges.  The accumulators no longer span their
# buckets, so scratch gives back more than the code takes: scratch +
# code falls by 33 / 55 MB at 4 ranges, by 22 / 43 MB at 8.
CODE_MARGIN = 5 * 10**6


@pytest.mark.parametrize("ranges", [4, 8])
def test_hop_program_compiles_for_the_v5e_at_cell_size(one_chip, whole,
                                                       ranges):
    import jax
    import jax.numpy as jnp
    from nebula_tpu.tpu import ell as E
    ix = _Shapes(ranges)
    assert E.table_slots(ix, (1,)) == 24835040
    assert E.swept_slots(ix, (1,)) == S20_SWEPT[ranges]
    nb = len(S20_BUCKETS)
    hop, hop_s = _compile(
        E.make_continuous_hop_kernel(ix, (1,), donate=True), one_chip)

    def pull_only(fp, accp, eslot, hrows, *tables):
        nxt = E._hop_body_packed(jnp, jax, ix.n, S20_EXTRAS,
                                 E._read_sides((1,), tables, nb),
                                 eslot, hrows, fp,
                                 E._side_reaches(ix, (1,)))
        return nxt, accp | nxt

    pull, _s = _compile(jax.jit(pull_only, donate_argnums=(0, 1)),
                        one_chip)
    text = hop.as_text()
    assert "conditional" in text                  # both branches, one program
    scratch, code = _sizes(hop)
    scratch_pull = pull.memory_analysis().temp_size_in_bytes
    # the two branches share their scratch; what the program adds over
    # the pull alone is the conditional's own frontier-sized result
    # (measured 141.3 MB against 118 MB at 4 ranges).  A table laid out
    # anew for a row read, or for a column prefix, shows as +395 MB
    assert scratch <= scratch_pull + 48 * 2**20, (scratch, scratch_pull)
    # against the whole sweep (178.3 MB of scratch, 10.55 MB of code):
    # 141.3 + 14.55 at 4 ranges, 148.2 + 18.66 at 8
    scratch_whole, code_whole = whole("hop")
    assert scratch + code <= scratch_whole + code_whole, \
        (scratch, code, scratch_whole, code_whole)
    if ranges == E.PULL_COLUMN_RANGES:
        assert code <= code_whole + CODE_MARGIN, (code, code_whole)
    # measured 5-6 s; the flat running sum alone was 33 s
    assert hop_s < 25.0, hop_s


def test_two_sided_hop_program_compiles_for_the_v5e_at_cell_size(
        one_chip):
    """jit_hop as graph500-s20-bidir.bicount16 runs it (PR 40): the
    OVER set (-t, +t) of ``GO ... OVER knows BIDIRECT``, so the pull
    carries every bucket's loops twice, once a direction table, and the
    push scatters a live row into its slots of both.  Held beside the
    forward program and the REVERSELY one compiled with it: the
    out-table's rows stand by IN-degree (PR 39), so its reach cuts
    next to nothing (24,787,896 of 24,835,040 slots gathered, where
    the in-table's pull gathers 20,178,536)."""
    from nebula_tpu.tpu import ell as E
    ix = _Shapes(E.PULL_COLUMN_RANGES)
    assert E.sides_read((-1, 1)) == 2
    assert E.table_slots(ix, (-1, 1)) == 2 * 24835040
    assert E.swept_slots(ix, (-1,)) == 24787896
    assert E.swept_slots(ix, (-1, 1)) \
        == S20_SWEPT[E.PULL_COLUMN_RANGES] + 24787896
    sizes = {}
    for etypes in ((1,), (-1,), (-1, 1)):
        hop, hop_s = _compile(
            E.make_continuous_hop_kernel(ix, etypes, donate=True),
            one_chip)
        assert "conditional" in hop.as_text()
        sizes[etypes] = _sizes(hop)
        # measured 4-9 s each
        assert hop_s < 30.0, (etypes, hop_s)
    (s_fwd, c_fwd), (s_rev, c_rev), (s_two, c_two) = (
        sizes[(1,)], sizes[(-1,)], sizes[(-1, 1)])
    # measured: forwards 141.3 MB of scratch + 14.55 MB of code,
    # REVERSELY 182.0 + 11.98 (its accumulators span their buckets:
    # nearly every range reaches every row), two-sided 206.2 + 22.96
    assert c_two <= c_fwd + c_rev, (c_two, c_fwd, c_rev)
    assert c_two >= max(c_fwd, c_rev)       # it does hold both
    # the sides run one after the other into one accumulator, so the
    # scratch is NOT the sum (323 MB); a table laid out anew for a row
    # read shows as +395 MB (the forward test)
    assert s_two <= max(s_fwd, s_rev) + 48 * 2**20, (s_two, s_fwd, s_rev)


@pytest.mark.parametrize("ranges, etypes", [
    (4, (1,)), (8, (1,)), (4, (-1, 1))],
    ids=["4", "8", "4-two-signed"])
def test_bfs_program_compiles_for_the_v5e_at_cell_size(bfs_at, whole,
                                                       ranges, etypes):
    """jit_bfs as graph500-s20-path.closed16 dispatches it (the
    128-lane rung, UPTO 5 STEPS, shortest): every level is the hop's
    step, so the conditional sits inside the level loop, beside a
    171 MB depth matrix that is live across it.  The two-signed case
    is graph500-s20-bipath.bipath16's (PR 46: ``FIND SHORTEST PATH ...
    OVER knows BIDIRECT``, the OVER set (-t, +t)): a pulled level
    carries every bucket's loops twice, once a direction table."""
    from nebula_tpu.tpu import ell as E
    bfs, bfs_s = bfs_at(ranges, etypes)
    text = bfs.as_text()
    assert "conditional" in text and "while" in text
    if len(etypes) == 2:
        # measured 625.6 MB of scratch + 26.66 MB of code against the
        # forward program's 511.1 + 17.26 (PR 46): the sides run one
        # after the other into one accumulator, so the scratch is not
        # the sum (+114 MB, where the two-sided hop's is +65: 206.2 +
        # 22.96 against 141.3 + 14.55), and the code holds both
        # tables' loops.  It is the one BFS program bipath16 loads:
        # +124 MB on the device over closed16's, 7.7 B an edge
        scratch, code = _sizes(bfs)
        fwd_scratch, fwd_code = _sizes(bfs_at(ranges, (1,))[0])
        assert fwd_code <= code <= 2 * fwd_code + CODE_MARGIN, \
            (code, fwd_code)
        assert scratch <= fwd_scratch + 160 * 2**20, (scratch, fwd_scratch)
        # measured 14.8 s (the forward program 6-9 s)
        assert bfs_s < 45.0, bfs_s
        return
    # measured 511.1 MB at 4 ranges, 519.1 at 8 (the whole sweep's
    # program: 570.3; PR 29's sweep-only program: 516.0); a slot
    # table laid out anew for a row read inside the loop shows as
    # +390 MB (a budget of ONE row reads 908.5 MB), and one
    # accumulator a range ORed together afterwards as +19 MB
    scratch, code = _sizes(bfs)
    assert scratch <= 640e6, scratch
    # against the whole sweep (570.3 MB of scratch, 13.17 MB of code):
    # 511.1 + 17.26 at 4 ranges, 519.1 + 21.04 at 8
    scratch_whole, code_whole = whole("bfs")
    assert scratch + code <= scratch_whole + code_whole, \
        (scratch, code, scratch_whole, code_whole)
    if ranges == E.PULL_COLUMN_RANGES:
        assert code <= code_whole + CODE_MARGIN, (code, code_whole)
    # measured 6-9 s, as the sweep-only program
    assert bfs_s < 30.0, bfs_s


@pytest.mark.parametrize("ranges", [4, 8])
def test_windowed_go_compiles_for_the_v5e_at_cell_size(one_chip, whole,
                                                       ranges):
    """jit_go as the warm-up and every ContinuousUnavailable bounce
    load it (go_dispatch_mode=windowed: make_batched_go_lanes_kernel,
    3 steps at the 128-lane rung): the pull alone, in a loop over the
    hops."""
    from nebula_tpu.tpu import ell as E
    go, go_s = _compile(
        E.make_batched_go_lanes_kernel(_Shapes(ranges), 3, (1,),
                                       donate=True), one_chip, carriers=1)
    scratch, code = _sizes(go)
    # against the whole sweep (229.1 MB of scratch, 6.55 MB of code):
    # 187.9 + 10.59 at 4 ranges; 234.0 + 14.52 at 8, which takes more
    # of the device than it gives back here
    scratch_whole, code_whole = whole("go")
    assert scratch + code <= scratch_whole + code_whole + 16 * 10**6, \
        (scratch, code, scratch_whole, code_whole)
    if ranges == E.PULL_COLUMN_RANGES:
        assert scratch + code <= scratch_whole + code_whole
        assert code <= code_whole + CODE_MARGIN, (code, code_whole)
    # measured 2-4 s
    assert go_s < 25.0, go_s


def test_count_program_compiles_for_the_v5e_at_cell_size(one_chip):
    """jit_count as graph500-s20-khop.count16 runs it a tick with
    counting leavers (the 128-lane rung): one pass over the resident
    frontier's vertex rows, 10.3 MB read, 512 B written, no scratch
    to speak of — a bit plane materialised at [646 k, 128] would show
    as 83 MB of it."""
    import jax
    from nebula_tpu.tpu import ell as E
    fp = jax.ShapeDtypeStruct((S20_ROWS + 1, E.lanes_width(LANES)),
                              np.uint8, sharding=one_chip)
    t0 = time.perf_counter()
    count = E.make_lane_count_kernel(_Shapes()).lower(fp).compile()
    count_s = time.perf_counter() - t0
    mem = count.memory_analysis()
    assert mem.output_size_in_bytes == 4 * LANES
    # measured 0.29 MB
    assert mem.temp_size_in_bytes <= 4 * 2**20, mem.temp_size_in_bytes
    # measured 2.5 s
    assert count_s < 20.0, count_s


@pytest.mark.parametrize("lanes", [128, 1024])
def test_extract_program_compiles_for_the_v5e_at_every_rung(one_chip, lanes):
    """jit_extract as a leave cohort runs it, at every rung of leavers
    of the 128-lane width the cells run and of the 1,024-lane width
    the ladder widens to: a lane's bitmap of the vertex rows a leaver
    comes out (80,768 B at this table's 646,081 vertices), packed
    plane by plane out of eight contiguous slices of its word column,
    one lane a turn.

    What the rungs cost the device while they are loaded is their
    PROGRAMS (generated code), and what they cost while they run is
    scratch beside the hop's own 179.6 MB, which sets the process's
    peak (PERF.md section 6, PR 35 and PR 37): both are held here.
    The 128-lane pair lies column-major on the device, so a lane's
    word column is a contiguous run; the 1,024-lane pair lies
    row-major (128 bytes a row fill the TPU's lanes), and there every
    rung first lays one carrier out anew into 84.4 MB of scratch and
    the other beside it: a cost no cell runs and no chip has timed
    (PERF.md section 7)."""
    import jax
    from nebula_tpu.tpu import ell as E
    fp = jax.ShapeDtypeStruct((S20_ROWS + 1, E.lanes_width(lanes)),
                              np.uint8, sharding=one_chip)
    kern = E.make_lane_extract_kernel(_Shapes())
    rungs = E.lane_extract_rungs(lanes)
    assert rungs[0] == 4 and rungs[-1] == lanes
    code = 0
    for L in rungs:
        cohort = jax.ShapeDtypeStruct((3, L), np.int32, sharding=one_chip)
        t0 = time.perf_counter()
        ext = kern.lower(fp, fp, cohort).compile()
        ext_s = time.perf_counter() - t0
        mem = ext.memory_analysis()
        assert mem.output_size_in_bytes == L * 80768, \
            (L, mem.output_size_in_bytes)
        # measured 0.19 MB at every rung of 128 lanes, 84.39 MB (one
        # carrier's [657,675, 128] bytes) at every rung of 1,024
        assert mem.temp_size_in_bytes <= \
            (2**20 if lanes == 128 else 85 * 10**6), \
            (L, mem.temp_size_in_bytes)
        code += mem.generated_code_size_in_bytes
        # measured 0.5-1.5 s a rung
        assert ext_s < 20.0, (L, ext_s)
    # measured 0.50-0.51 MB a rung at 128 lanes (3.0 MB over the six),
    # 0.66-0.67 MB at 1,024
    assert code <= 0.75e6 * len(rungs), code


def test_join_program_compiles_for_the_v5e_at_every_rung(one_chip):
    """jit_join as the seat runs it (PR 43: a joiner's first frontier,
    hundreds of rows a tick where a start was one): one program a rung
    of ell.LANE_JOIN_RUNGS, all loaded from a session's first join on
    (runtime._ContinuousGoSession._join_kernel), both carriers donated
    and scattered in place.  What the ladder costs the device is its
    programs' code, and that is why it ends at 512 rows: from 1,024 on
    the compiler sorts the indices first and a program weighs ten
    times as much."""
    import jax
    from nebula_tpu.tpu import ell as E

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fp = sd((S20_ROWS + 1, E.lanes_width(LANES)), np.uint8)
    kern = E.make_lane_join_kernel(_Shapes(), donate=True)

    def sizes(Sp):
        t0 = time.perf_counter()
        join = kern.lower(fp, fp, sd((Sp,), np.int32),
                          sd((Sp,), np.int32),
                          sd((Sp,), np.uint8)).compile()
        # measured 0.1-1.5 s a rung
        assert time.perf_counter() - t0 < 20.0, Sp
        return _sizes(join)

    assert E.LANE_JOIN_RUNGS[-1] == 512
    code = 0
    for Sp in E.LANE_JOIN_RUNGS:
        scratch, rung_code = sizes(Sp)
        # measured: no scratch at all (the scatter runs in place)
        assert scratch <= 2**20, (Sp, scratch)
        code += rung_code
    # measured 0.14 MB at 8 rows and 0.25-0.26 MB at 32, 128 and 512:
    # 0.91 MB over the four, where the 8 and 16 of a warm-up statement
    # were 0.40; device_bytes_per_edge's 0.3 % is 2.4 MB at this graph
    assert code <= 1.2e6, code
    # measured 2.34 MB of code and 0.32 MB of scratch at 1,024 rows
    # (5.5 MB at 8,192): a larger table goes in as several programs of
    # the top rung instead
    _scratch, above = sizes(2 * E.LANE_JOIN_RUNGS[-1])
    assert above >= 4 * code / len(E.LANE_JOIN_RUNGS), (above, code)
