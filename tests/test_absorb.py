"""Incremental delta absorption — versioned mirror generations
(docs/durability.md "The generation state machine").

Tiers:
  * randomized absorb-vs-rebuild parity differential — event streams
    mixing inserts / in-place updates / deletes (and, in one stream,
    new-vertex edges that legitimately rebuild) served from ABSORBED
    generations, checked per step against the CPU oracle and at the
    end against the rebuild oracle (mirrors cleared, fresh store
    scan), across packed + int8 layouts and 2/8-way virtual meshes
    (both mesh designs);
  * generation semantics — the published generation is immutable once
    absorbed past (in-flight dispatches finish on the tables they
    captured), read-your-writes ordering holds, and shape signatures
    survive absorption so cached kernels keep serving;
  * delta-budget overflow observability — blowing past
    mirror_delta_max pays an OBSERVABLE rebuild (counter + journaled
    mirror.absorb_failed event), never a silent one.
"""
import threading

import numpy as np
import pytest

from nebula_tpu.cluster import LocalCluster
from nebula_tpu.common.flags import flags


def _boot(space="ab", parts=3, n=40):
    flags.set("storage_backend", "tpu")
    c = LocalCluster(num_storage=1, tpu_backend=True)
    cl = c.client()

    def ok(s):
        r = cl.execute(s)
        assert r.ok(), f"{s}: {r.error_msg}"
        return r

    ok(f"CREATE SPACE {space}(partition_num={parts}, replica_factor=1)")
    c.refresh_all()
    ok(f"USE {space}")
    ok("CREATE TAG player(name string, age int)")
    ok("CREATE EDGE follow(degree int)")
    c.refresh_all()
    players = ", ".join(f'{100 + i}:("p{i}", {20 + i})'
                        for i in range(n))
    ok(f"INSERT VERTEX player(name, age) VALUES {players}")
    ok("INSERT EDGE follow(degree) VALUES "
       + ", ".join(f"{100 + i} -> {100 + (i + 1) % n}:({50 + i})"
                   for i in range(n)))
    return c, cl, ok


def _cpu_parity(ok, q):
    r = ok(q)
    flags.set("storage_backend", "cpu")
    try:
        r2 = ok(q)
    finally:
        flags.set("storage_backend", "tpu")
    assert sorted(map(tuple, r.rows)) == sorted(map(tuple, r2.rows)), q
    return sorted(map(tuple, r.rows))


class TestAbsorbDifferential:
    """Randomized event streams: every step must stay bit-exact with
    the CPU loop, the whole stream must cost ZERO full rebuilds, and
    the final absorbed state must equal a from-scratch rebuild."""

    QUERIES = [
        "GO FROM 100, 105, 110 OVER follow "
        "YIELD follow._src, follow._dst, follow.degree",
        "GO 2 STEPS FROM 100 OVER follow YIELD follow._dst",
        "GO 3 STEPS FROM 101, 107 OVER follow YIELD follow._dst",
        "GO FROM 103 OVER follow REVERSELY YIELD follow._dst",
        "GO FROM 100 OVER follow | YIELD COUNT(*)",
        "FIND SHORTEST PATH FROM 100 TO 115 OVER follow UPTO 4 STEPS",
    ]

    @pytest.mark.parametrize("mesh,mesh_mode", [
        (0, "sparse"),             # single chip
        (2, "sparse"),             # frontier-sharded mesh design
        (8, "dense"),              # replicated-frontier mesh design
    ])
    def test_randomized_stream_absorbs_with_parity(self, mesh,
                                                   mesh_mode):
        import random
        c, cl, ok = _boot(space=f"ab{mesh}")
        saved = {k: flags.get(k) for k in
                 ("tpu_mesh_devices", "tpu_mesh_mode")}
        flags.set("tpu_mesh_devices", mesh)
        flags.set("tpu_mesh_mode", mesh_mode)
        try:
            rt = c.tpu_runtime
            for q in self.QUERIES:
                ok(q)                        # build + warm under mesh
            builds0 = rt.stats["mirror_builds"]
            rng = random.Random(18 + mesh)
            live = {(100 + i, 100 + (i + 1) % 40, 0)
                    for i in range(40)}      # (src, dst, rank)
            for step in range(10):
                op = rng.choice(["insert", "insert", "update", "delete"])
                if op == "insert":
                    s, d = rng.randrange(40), rng.randrange(40)
                    r = 1000 + step
                    ok(f"INSERT EDGE follow(degree) VALUES "
                       f"{100 + s} -> {100 + d}@{r}:({200 + step})")
                    live.add((100 + s, 100 + d, r))
                elif op == "update":
                    s, d, r = rng.choice(sorted(live))
                    ok(f"INSERT EDGE follow(degree) VALUES "
                       f"{s} -> {d}@{r}:({900 + step})")
                elif op == "delete" and len(live) > 5:
                    s, d, r = rng.choice(sorted(live))
                    ok(f"DELETE EDGE follow {s} -> {d}@{r}")
                    live.discard((s, d, r))
                q = self.QUERIES[step % len(self.QUERIES)]
                _cpu_parity(ok, q)
            # the whole stream rode absorption: zero O(m) rebuilds
            assert rt.stats["mirror_builds"] == builds0, \
                (builds0, rt.stats["mirror_builds"])
            assert rt.stats["mirror_absorbs"] > 0
            assert rt.stats["mirror_delta_overflow"] == 0
            # rebuild oracle: a from-scratch store scan must serve the
            # exact same rows the absorbed generation does
            final_a = [sorted(map(tuple, ok(q).rows))
                       for q in self.QUERIES]
            with rt._lock:
                rt.mirrors.clear()
            final_b = [sorted(map(tuple, ok(q).rows))
                       for q in self.QUERIES]
            assert final_a == final_b
        finally:
            for k, v in saved.items():
                flags.set(k, v)
            c.stop()

    def test_stream_with_new_vertices_stays_exact(self):
        """New-vertex edges change the vertex plan — those windows pay
        an OBSERVABLE rebuild; every result stays exact throughout."""
        import random
        c, cl, ok = _boot(space="abnv")
        try:
            rt = c.tpu_runtime
            ok(self.QUERIES[0])
            rng = random.Random(23)
            next_vid = 900
            for step in range(8):
                if step % 3 == 2:
                    # edge to a vid with no vertex record: extra_vids
                    ok(f"INSERT EDGE follow(degree) VALUES "
                       f"{100 + rng.randrange(40)} -> {next_vid}:(7)")
                    next_vid += 1
                else:
                    s, d = rng.randrange(40), rng.randrange(40)
                    ok(f"INSERT EDGE follow(degree) VALUES "
                       f"{100 + s} -> {100 + d}@{77 + step}:(9)")
                _cpu_parity(ok, self.QUERIES[step % 4])
            assert rt.stats["mirror_absorbs"] > 0
            assert rt.stats["mirror_absorb_failed"] > 0
        finally:
            c.stop()

    def test_multi_hop_delete_absorbs_without_rebuild(self):
        """Reachability-changing deletes used to force the rebuild for
        multi-hop queries (the overlay could not subtract edges);
        tombstones now fold into the tables at absorb time, so even
        multi-hop traffic keeps serving rebuild-free."""
        c, cl, ok = _boot(space="abdel")
        try:
            rt = c.tpu_runtime
            ok("GO 2 STEPS FROM 100 OVER follow YIELD follow._dst")
            builds0 = rt.stats["mirror_builds"]
            ok("DELETE EDGE follow 101 -> 102@0")
            rows = _cpu_parity(
                ok, "GO 2 STEPS FROM 100 OVER follow YIELD follow._dst")
            assert (102,) not in rows, "deleted mid-path edge traversed"
            assert rt.stats["mirror_builds"] == builds0, \
                "a delete must absorb as a tombstone, not rebuild"
            assert rt.stats["mirror_absorbs"] > 0
        finally:
            c.stop()

    def test_reduced_pushdown_serves_from_absorbed_generation(self):
        """The PR 8 gate forced mirror_full for reduced queries under
        a live delta; LIMIT/COUNT pushdown now runs against the
        absorbed generation — correct counts, zero rebuilds."""
        c, cl, ok = _boot(space="abred")
        try:
            rt = c.tpu_runtime
            q = "GO FROM 100 OVER follow | YIELD COUNT(*)"
            ok(q)
            builds0 = rt.stats["mirror_builds"]
            reduced0 = rt.stats.get("go_reduced", 0)
            ok("INSERT EDGE follow(degree) VALUES 100 -> 120@3:(1), "
               "100 -> 121@3:(2)")
            rows = _cpu_parity(ok, q)
            assert rows == [(3,)], rows       # ring edge + 2 fresh
            assert rt.stats["mirror_builds"] == builds0
            assert rt.stats["mirror_absorbs"] > 0
            assert rt.stats.get("go_reduced", 0) > reduced0, \
                "COUNT must still ride the device reduction"
        finally:
            c.stop()


class TestGenerationSemantics:
    def test_absorb_publishes_immutable_generation(self):
        """The old generation's host/device tables stay byte-identical
        after an absorption publishes the next one — in-flight
        dispatches finish on the state they captured — and the shape
        signature survives, so shape-keyed kernels keep serving."""
        c, cl, ok = _boot(space="gen1")
        try:
            rt = c.tpu_runtime
            ok("GO 2 STEPS FROM 100 OVER follow YIELD follow._dst")
            space = next(iter(rt.mirrors))
            m0 = rt.mirrors[space]
            ix0 = rt.ell(m0)
            snap = [a.copy() for a in ix0.bucket_nbr]
            snap_et = [a.copy() for a in ix0.bucket_et]
            g0 = getattr(m0, "generation", 0)
            ok("INSERT EDGE follow(degree) VALUES 100 -> 117@5:(1)")
            rows = set(map(tuple, ok(
                "GO FROM 100 OVER follow YIELD follow._dst").rows))
            assert (117,) in rows            # read-your-writes
            m1 = rt.mirrors[space]
            assert m1 is not m0
            assert m1.generation == g0 + 1
            assert m1._ell is not ix0
            assert m1._ell.shape_sig() == ix0.shape_sig()
            for a, b in zip(ix0.bucket_nbr, snap):
                assert np.array_equal(a, b), \
                    "old generation's host tables mutated in place"
            for a, b in zip(ix0.bucket_et, snap_et):
                assert np.array_equal(a, b)
            # the retired generation still ANSWERS (an in-flight
            # dispatch would): hop over its tables finds the old view
            from test_ell import run_go
            et = rt.sm.to_edge_type(space, "follow").value()
            f0 = ix0.start_frontier([m0.to_dense([100])], B=8)
            assert run_go(ix0, 2, (et,), f0)[:, 0].any()
        finally:
            c.stop()

    def test_read_your_writes_ordering_under_concurrency(self):
        """A write acked at generation g must be visible to every
        query ADMITTED after g publishes, while concurrent readers
        never observe a half-absorbed table (they see g-1 or g)."""
        c, cl, ok = _boot(space="gen2")
        try:
            ok("GO FROM 100 OVER follow")
            stop = threading.Event()
            errors = []

            def reader():
                g = c.client()
                g.execute("USE gen2")
                while not stop.is_set():
                    r = g.execute("GO FROM 100 OVER follow "
                                  "YIELD follow._dst")
                    if not r.ok():
                        errors.append(r.error_msg)
                        return
                    # either generation is consistent: the ring edge
                    # is ALWAYS there; fresh edges may or may not be
                    if (101,) not in set(map(tuple, r.rows)):
                        errors.append(f"torn read: {r.rows}")
                        return

            ts = [threading.Thread(target=reader) for _ in range(4)]
            for t in ts:
                t.start()
            try:
                for i in range(12):
                    ok(f"INSERT EDGE follow(degree) VALUES "
                       f"100 -> {110 + i}@9:({i})")
                    # acked write -> a query admitted NOW sees it
                    rows = set(map(tuple, ok(
                        "GO FROM 100 OVER follow "
                        "YIELD follow._dst").rows))
                    assert (110 + i,) in rows, (i, rows)
            finally:
                stop.set()
                for t in ts:
                    t.join()
            assert not errors, errors
        finally:
            c.stop()


class TestSlotGrowth:
    """In-place ELL slot growth (ISSUE 13 satellite): degree growth
    past an existing vertex's resident row claims a cap-bucket spare
    (EllIndex.build growth_slack) instead of paying the slot-overflow
    rebuild — narrow scope: existing-vertex extension only."""

    GROW_Q = "GO FROM 117 OVER follow REVERSELY YIELD follow._dst"

    def test_degree_growth_claims_spare_in_place(self):
        c, cl, ok = _boot(space="grow")
        try:
            rt = c.tpu_runtime
            ok("GO FROM 100 OVER follow YIELD follow._dst")
            builds0 = rt.stats["mirror_builds"]
            grows0 = rt.stats["mirror_slot_grows"]
            # vertex 117 holds 2 in-slots (ring fwd + rev) in a D=8
            # row; 9 fresh in-edges in one window overflow it — the
            # spare claim must absorb what used to re-bucket
            ok("INSERT EDGE follow(degree) VALUES "
               + ", ".join(f"{100 + i} -> 117@7:({i})"
                           for i in range(2, 11)))
            rows = _cpu_parity(ok, self.GROW_Q)
            assert len(rows) >= 10
            assert rt.stats["mirror_builds"] == builds0, \
                "degree growth within the slack must absorb, not rebuild"
            assert rt.stats["mirror_slot_grows"] > grows0
            assert rt.stats["mirror_absorbs"] > 0
            # multi-hop + packed paths serve the grown generation
            _cpu_parity(ok, "GO 2 STEPS FROM 116 OVER follow "
                            "YIELD follow._dst")
            # rebuild oracle: a from-scratch scan serves identical rows
            final_a = sorted(map(tuple, ok(self.GROW_Q).rows))
            with rt._lock:
                rt.mirrors.clear()
            assert sorted(map(tuple, ok(self.GROW_Q).rows)) == final_a
        finally:
            c.stop()

    def test_growth_disabled_restores_rebuild(self):
        saved = flags.get("tpu_ell_growth_slack")
        flags.set("tpu_ell_growth_slack", 0)
        c, cl, ok = _boot(space="grow0")
        try:
            rt = c.tpu_runtime
            ok("GO FROM 100 OVER follow YIELD follow._dst")
            builds0 = rt.stats["mirror_builds"]
            ok("INSERT EDGE follow(degree) VALUES "
               + ", ".join(f"{100 + i} -> 117@7:({i})"
                           for i in range(2, 11)))
            rows = _cpu_parity(ok, self.GROW_Q)
            assert len(rows) >= 10
            assert rt.stats["mirror_builds"] > builds0, \
                "slack 0 must restore the slot-overflow rebuild"
            assert rt.stats["mirror_slot_grows"] == 0
        finally:
            flags.set("tpu_ell_growth_slack", saved)
            c.stop()


class TestOverflowObservability:
    def test_delta_overflow_counted_and_journaled(self):
        """A write burst past mirror_delta_max pays the rebuild — and
        says so: tpu.mirror.delta_overflow counts it, the journal
        carries mirror.absorb_failed with the delta-overflow reason,
        and results stay exact."""
        from nebula_tpu.common.events import journal
        c, cl, ok = _boot(space="ovf")
        saved = flags.get("mirror_delta_max")
        try:
            rt = c.tpu_runtime
            ok("GO FROM 100 OVER follow")
            flags.set("mirror_delta_max", 2)
            builds0 = rt.stats["mirror_builds"]
            o0 = rt.stats["mirror_delta_overflow"]
            # 2 edges = 4 stored rows (fwd+rev) > budget 2
            ok("INSERT EDGE follow(degree) VALUES "
               "100 -> 130@1:(1), 100 -> 131@1:(2)")
            rows = _cpu_parity(
                ok, "GO FROM 100 OVER follow YIELD follow._dst")
            assert (130,) in rows and (131,) in rows
            assert rt.stats["mirror_delta_overflow"] > o0
            assert rt.stats["mirror_builds"] > builds0
            evs = [e for e in journal.dump(200)
                   if e["kind"] == "mirror.absorb_failed"]
            assert any(e.get("reason") == "delta-overflow"
                       for e in evs), evs
        finally:
            flags.set("mirror_delta_max", saved)
            c.stop()

    def test_absorb_off_restores_rebuild_per_write(self):
        """mirror_absorb=false is the differential oracle: the same
        write stream pays rebuilds and still serves exact rows."""
        c, cl, ok = _boot(space="aboff")
        saved = flags.get("mirror_absorb")
        try:
            rt = c.tpu_runtime
            ok("GO FROM 100 OVER follow")
            flags.set("mirror_absorb", False)
            builds0 = rt.stats["mirror_builds"]
            ok("INSERT EDGE follow(degree) VALUES 100 -> 125@2:(5)")
            rows = _cpu_parity(
                ok, "GO FROM 100 OVER follow YIELD follow._dst")
            assert (125,) in rows
            assert rt.stats["mirror_builds"] > builds0
        finally:
            flags.set("mirror_absorb", saved)
            c.stop()


class TestVertexDoubleAbsorbsInPlace:
    """A numeric vertex write absorbs in place whatever the value: the
    host column holds any double the store does, and nothing compares
    it in float32 any more."""

    def test_a_double_float32_does_not_hold_absorbs_and_filters(self):
        c, _cl, ok = _boot("vd", n=12)
        try:
            ok("CREATE TAG acct(bal double)")
            c.refresh_all()
            # every stored balance is a float32 too
            ok("INSERT VERTEX acct(bal) VALUES " + ", ".join(
                f"{100 + i}:({i / 8})" for i in range(12)))
            q = ("GO FROM " + ", ".join(str(100 + i) for i in range(12))
                 + " OVER follow WHERE $$.acct.bal > 0.1 "
                 "YIELD follow._dst, $$.acct.bal")
            before_rows = _cpu_parity(ok, q)
            rt = c.tpu_runtime
            before = dict(rt.stats)
            # float32(0.1) > 0.1: neither new balance is a float32,
            # and only one is over 0.1
            ok("INSERT VERTEX acct(bal) VALUES 103:(0.1), "
               "104:(0.10000000000000002)")
            rows = _cpu_parity(ok, q)
            grew = {k: rt.stats[k] - before[k] for k in
                    ("mirror_deltas", "mirror_absorb_failed",
                     "mirror_builds")}
            assert grew == {"mirror_deltas": 1, "mirror_absorb_failed": 0,
                            "mirror_builds": 0}
            assert (104, 0.10000000000000002) in rows
            assert 103 not in [r[0] for r in rows]
            assert 103 in [r[0] for r in before_rows]      # it was 0.375
        finally:
            c.stop()


class TestReachAfterAbsorb:
    """PR 39: a pull gathers, per column range, only the leading rows
    of a bucket that hold a real slot there (EllIndex.reach).  An
    absorb that hands a row PAST such a prefix a new in-edge hands out
    an index whose reach covers it: read anew off the rewritten
    buckets' slots, never patched."""

    @pytest.mark.parametrize("kind", ["in_place", "claimed_spare"])
    def test_reach_covers_a_row_past_a_prefix(self, kind, monkeypatch):
        import jax.numpy as jnp
        from nebula_tpu.tpu import ell as E
        monkeypatch.setattr(E, "PULL_COLUMN_RANGES", 4)
        monkeypatch.setattr(E, "PULL_REACH_STEP", 4)
        rng = np.random.default_rng(39)
        n, m = 300, 5000
        dst = (rng.zipf(1.5, m) % n).astype(np.int32)
        src = rng.integers(0, n, m).astype(np.int32)
        _, first = np.unique(src.astype(np.int64) * n + dst,
                             return_index=True)
        src, dst = src[first], dst[first]

        def build(s, d):
            one = np.ones(len(s), np.int32)
            return E.EllIndex.build(
                np.concatenate([s, d]), np.concatenate([d, s]),
                np.concatenate([one, -one]), n, cap=16, min_d=2,
                growth_slack=3)

        ix = build(src, dst)
        # the last main row of the widest bucket: the fewest in-edges
        # of its bucket, past the prefix of every range but the first
        nbr = ix.bucket_nbr[-1]
        n_main = E._main_rows(ix.n, ix.bucket_nbr)[-1]
        D = nbr.shape[1]
        b0 = ix.n - n_main
        row = n_main - 1
        fill = int((nbr[row] != ix.n_rows).sum())
        reach = ix.reach[0][-1]
        assert fill <= D // 4 and all(r <= row for r in reach[1:])
        v = int(ix.inv[b0 + row])
        have = set(src[dst == v].tolist()) | {v}
        k = (D - fill - 1) if kind == "in_place" else (D - fill + 5)
        new_src = np.asarray([u for u in range(n) if u not in have][:k],
                             np.int32)
        new_dst = np.full(k, v, np.int32)
        # the mirror's form: (dst, src, etype) rows, both directions
        ins = (np.concatenate([new_dst, new_src]),
               np.concatenate([new_src, new_dst]),
               np.concatenate([np.ones(k, np.int32),
                               -np.ones(k, np.int32)]))
        none = np.zeros(0, np.int32)
        claims = []
        plan = E.plan_ell_absorb(ix, *ins, none, none, none,
                                 claims_out=claims)
        assert plan is not None
        assert bool(claims) == (kind == "claimed_spare")
        ix2 = E.apply_ell_absorb_host(ix, plan, ix.m + 2 * k,
                                      claims=claims)
        # the row now holds a real slot in the last range (in place) or
        # in every column (its overflow went to the claimed spare)
        got = ix2.reach[0][-1]
        assert got != reach and all(r > row for r in got)
        assert ix2.reach == E.pull_reach(ix2)
        assert ix2.shape_sig() != ix.shape_sig()
        nb = len(ix.bucket_nbr)
        for side in (0, 1):
            for b in range(nb):
                if side * nb + b not in plan:
                    assert ix2.reach[side][b] is ix.reach[side][b]
        mains = E._main_rows(ix2.n, ix2.bucket_nbr)
        for nbrs, side in zip((ix2.bucket_nbr, ix2.out_nbr), ix2.reach):
            for t, nm, rb in zip(nbrs, mains, side):
                for c, r in zip(E._range_bounds(t.shape[1], 4), rb):
                    assert (t[r:nm, c:] == ix2.n_rows).all()
        # a hop over the absorbed index is the rebuilt one's
        ix3 = build(np.concatenate([src, new_src]),
                    np.concatenate([dst, new_dst]))
        starts = [np.asarray(s) for s in
                  (new_src[:2], [v], rng.choice(n, 5), new_src[-1:])]
        outs = []
        for index in (ix2, ix3):
            assert E.swept_slots(index, (1,)) < E.table_slots(index, (1,))
            eslot, hrows = index.hub_merge()
            f0 = index.start_frontier(starts, B=8)
            kern = E.make_batched_go_lanes_kernel(index, 3, (1,))
            out = kern(jnp.asarray(E.pack_lanes_host(f0)),
                       jnp.asarray(eslot), jnp.asarray(hrows),
                       *index.kernel_args()[1:])
            outs.append(index.to_old(
                E.unpack_lanes_host(np.asarray(out), 8)))
        assert outs[0].any() and np.array_equal(outs[0], outs[1])
