"""GoBatchDispatcher — concurrent GO queries must coalesce into fewer
device dispatches while returning exactly the per-query results.
(The reference has no cross-query batching; the parity oracle is the
CPU executor path on an identical cluster, as in test_tpu_backend.)"""
import threading

import numpy as np
import pytest

from nebula_tpu.cluster import LocalCluster
from nebula_tpu.common.flags import flags


@pytest.fixture
def nba():
    # this suite exercises the WINDOWED pipeline's internals (leader
    # election, coalescing, pooling windows); the continuous seat-map
    # tier has its own suite (test_continuous.py)
    flags.set("go_dispatch_mode", "windowed")
    c = LocalCluster(num_storage=1, tpu_backend=True)
    g = c.client()

    def ok(stmt):
        r = g.execute(stmt)
        assert r.ok(), f"{stmt}: {r.error_msg}"
        return r

    ok("CREATE SPACE s(partition_num=3, replica_factor=1)")
    c.refresh_all()
    ok("USE s")
    ok("CREATE EDGE follow(w int)")
    c.refresh_all()
    ok("INSERT EDGE follow(w) VALUES 1->2:(1), 2->3:(1), 3->4:(1), "
       "4->5:(1), 1->6:(1), 6->7:(1), 2->7:(1)")
    yield c, ok
    c.stop()
    flags.set("go_batch_window_ms", 0)
    flags.set("go_dispatch_mode", "continuous")


def test_route_eligible_table(monkeypatch):
    """The continuous tier's routing rule, written down once: a key
    rides the seat map iff dispatch is continuous, the tables are not
    mesh-sharded, and the key is a multi-hop batched GO whose
    reduction (if any) is a count or a limit.  The rule reads two
    flags and the key — how a frontier is stored is not the
    dispatcher's business."""
    from nebula_tpu.graph.batch_dispatch import ContinuousGoScheduler
    from nebula_tpu.tpu import runtime  # noqa: F401 — defines the mesh flag

    def key(steps, reduce=None, method="go_batch_execute", upto=False):
        return (method, 1, (1,), steps, upto, reduce)

    table = [
        # mode,        mesh, key,                              eligible
        ("continuous", 0, key(2),                               True),
        ("continuous", 0, key(3, upto=True),                    True),
        ("continuous", 0, key(3, ("count",)),                   True),
        ("continuous", 0, key(2, ("limit", 10)),                True),
        ("continuous", 1, key(2),                               True),
        ("continuous", 0, key(1),                               False),
        ("continuous", 0, key(0),                               False),
        ("continuous", 0, key("x"),                             False),
        ("continuous", 0, key(2, ("sum", "w")),                 False),
        ("continuous", 0, key(4, method="bfs_batch_execute"),   False),
        ("continuous", 0, ("go_batch_execute", 1, (1,), 2),     False),
        ("continuous", 2, key(2),                               False),
        ("continuous", 8, key(3, ("count",)),                   False),
        ("windowed",   0, key(2),                               False),
        ("windowed",   0, key(3, ("limit", 5)),                 False),
        ("windowed",   4, key(2),                               False),
    ]
    saved = {k: flags.get(k) for k in ("go_dispatch_mode",
                                       "tpu_mesh_devices")}
    read = set()
    real_get = flags.get

    def spy(name, default=None):
        read.add(name)
        return real_get(name, default)

    try:
        for mode, mesh, k, want in table:
            flags.set("go_dispatch_mode", mode)
            flags.set("tpu_mesh_devices", mesh)
            assert ContinuousGoScheduler.route_eligible(k) is want, \
                (mode, mesh, k)
        flags.set("go_dispatch_mode", "continuous")
        flags.set("tpu_mesh_devices", 0)
        monkeypatch.setattr(flags, "get", spy)
        assert ContinuousGoScheduler.route_eligible(key(2)) is True
        monkeypatch.undo()
        assert read == {"go_dispatch_mode", "tpu_mesh_devices"}
    finally:
        for k, v in saved.items():
            flags.set(k, v)


def test_unfiltered_go_uses_dispatcher(nba):
    c, ok = nba
    r = ok("GO 2 STEPS FROM 1 OVER follow YIELD follow._dst")
    assert sorted(x[0] for x in r.rows) == [3, 7, 7]
    d = c.tpu_runtime.dispatcher
    assert d.stats["batches"] >= 1
    assert d.stats["batched_queries"] >= 1


def test_concurrent_queries_coalesce(nba):
    c, ok = nba
    ok("GO 1 STEPS FROM 1 OVER follow")     # warm mirror + kernel cache
    d = c.tpu_runtime.dispatcher
    flags.set("go_batch_window_ms", 120)    # force a coalescing window

    results = {}
    errors = []

    def worker(vid):
        try:
            g2 = c.client()
            g2.execute("USE s")
            r = g2.execute(f"GO 2 STEPS FROM {vid} OVER follow "
                           f"YIELD follow._dst")
            assert r.ok(), r.error_msg
            results[vid] = sorted(x[0] for x in r.rows)
        except Exception as ex:             # noqa: BLE001
            errors.append(ex)

    before = d.stats["batches"]
    threads = [threading.Thread(target=worker, args=(v,))
               for v in (1, 2, 1, 6, 2, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    flags.set("go_batch_window_ms", 0)

    assert not errors, errors
    assert results[1] == [3, 7, 7]
    assert results[2] == [4]                # 2->3->4 (and 2->7->nothing)
    assert results[6] == []                 # 6->7-> nothing
    batches = d.stats["batches"] - before
    assert batches < 6, f"no coalescing: {batches} batches for 6 queries"
    assert d.stats["max_batch"] >= 2


def test_dispatcher_parity_with_cpu_path(nba):
    c, ok = nba
    r_tpu = ok("GO 3 STEPS FROM 1 OVER follow YIELD follow._dst")
    flags.set("storage_backend", "cpu")
    try:
        r_cpu = ok("GO 3 STEPS FROM 1 OVER follow YIELD follow._dst")
    finally:
        flags.set("storage_backend", "tpu")
    assert sorted(map(tuple, r_tpu.rows)) == sorted(map(tuple, r_cpu.rows))


def test_dispatcher_error_propagates():
    """A failing batch launch must wake every waiter with the error."""
    class Boom(RuntimeError):
        pass

    class FakeRuntime:
        def go_batch_execute(self, *a):
            raise Boom("device fell over")

    from nebula_tpu.graph.batch_dispatch import GoBatchDispatcher
    d = GoBatchDispatcher(FakeRuntime())
    with pytest.raises(Boom):
        d.submit_batched(("go_batch_execute", 1, (1,), 2), [1])
    assert d.stats["batches"] == 1


def test_concurrent_find_path_coalesce(nba):
    """Concurrent same-shaped FIND PATH queries must coalesce into one
    BFS dispatch (submit_batched generalization), with exact per-query
    paths."""
    c, ok = nba
    ok("FIND SHORTEST PATH FROM 1 TO 4 OVER follow")   # warm kernel
    d = c.tpu_runtime.dispatcher
    flags.set("go_batch_window_ms", 120)
    results = {}
    errors = []

    # session setup (connect + USE) staggers threads by whole RPC round
    # trips on a loaded box — the barrier makes the four FIND PATH
    # statements actually CONCURRENT, which is the property under test
    gate = threading.Barrier(4)

    def worker(src, dst):
        try:
            g2 = c.client()
            g2.execute("USE s")
            gate.wait(timeout=10)
            r = g2.execute(f"FIND SHORTEST PATH FROM {src} TO {dst} "
                           f"OVER follow")
            assert r.ok(), r.error_msg
            results[(src, dst)] = sorted(x[0] for x in r.rows)
        except Exception as ex:            # noqa: BLE001
            errors.append(ex)

    before = d.stats["batches"]
    pairs = [(1, 4), (2, 5), (1, 7), (6, 7)]
    ts = [threading.Thread(target=worker, args=p) for p in pairs]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    flags.set("go_batch_window_ms", 0)
    assert not errors, errors
    assert results[(1, 4)] == ["1 <follow,0> 2 <follow,0> 3 <follow,0> 4"]
    assert results[(2, 5)] == ["2 <follow,0> 3 <follow,0> 4 <follow,0> 5"]
    assert results[(6, 7)] == ["6 <follow,0> 7"]
    assert results[(1, 7)]                      # 1->2->7 and/or 1->6->7
    batches = d.stats["batches"] - before
    assert batches < 4, f"no coalescing: {batches} for 4 path queries"


def test_per_query_error_isolation():
    """A poisoned query must fail ALONE; its 50 batch-mates succeed
    (VERDICT round-2 weak #5; reference semantics are per-request
    partial failure — StorageClient.h:22-72).  Also exercises the
    two-phase _Pending path: launch releases leadership, finish maps
    per-query results."""
    from nebula_tpu.graph.batch_dispatch import GoBatchDispatcher

    class Bad(RuntimeError):
        pass

    class _P:
        def __init__(self, fn):
            self.finish = fn

    class FakeRuntime:
        def exec_batch(self, space_id, payloads):
            def finish():
                return [Bad("poisoned") if p == "bad" else p * 2
                        for p in payloads], "mirror"
            return _P(finish)

    d = GoBatchDispatcher(FakeRuntime())
    flags.set("go_batch_window_ms", 80)
    outs, errs = {}, {}

    def worker(i, payload):
        try:
            r, m = d.submit_batched(("exec_batch", 1), payload)
            outs[i] = (r, m)
        except Bad as e:
            errs[i] = e

    try:
        ts = [threading.Thread(target=worker,
                               args=(i, "bad" if i == 3 else i))
              for i in range(51)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    finally:
        flags.set("go_batch_window_ms", 0)
    assert list(errs) == [3], f"wrong failures: {sorted(errs)}"
    assert len(outs) == 50
    assert outs[5] == (10, "mirror")
    assert d.stats["query_errors"] >= 1


def test_leader_section_failure_resets_dispatching():
    """An exception between taking leadership and entering _run must
    hand leadership back — a stuck `dispatching` flag deadlocks every
    future request on the key (found via a mistyped window flag: the
    leader raised at `window > 0` and the dispatcher wedged forever)."""
    from nebula_tpu.graph.batch_dispatch import GoBatchDispatcher

    class FakeRuntime:
        def exec_batch(self, space_id, payloads):
            return [p for p in payloads], "m"

    d = GoBatchDispatcher(FakeRuntime())
    # simulate a corrupted flag value (flags.set coerces, so poke the
    # registry directly — an early define() with the wrong type did
    # exactly this in the wild)
    flags._flags["go_batch_window_ms"].value = "boom"
    try:
        with pytest.raises(ValueError):
            d.submit_batched(("exec_batch", 1), 7)
    finally:
        flags._flags["go_batch_window_ms"].value = 0
    # the key must still be serviceable
    r, m = d.submit_batched(("exec_batch", 1), 9)
    assert (r, m) == (9, "m")


def test_adaptive_window_scales_with_roundtrip():
    """go_batch_window_ms=-1 (default): the pooling window tracks
    go_batch_window_frac of the key's EMA batch round-trip, capped at
    go_batch_window_max_ms — so a ~100 ms-RTT device link pools wide
    batches while a local chip's ~ms round-trips cost ~no wait.  A key
    with no completed batch yet must never sleep on a guess."""
    from nebula_tpu.graph.batch_dispatch import GoBatchDispatcher, _KeyState

    d = GoBatchDispatcher(runtime=None)
    st = _KeyState()
    prev = flags.get("go_batch_window_ms")
    try:
        flags.set("go_batch_window_ms", -1)
        assert d._window_s(st.rt_ema_s) == 0.0            # no sample yet
        st.rt_ema_s = 0.2                        # 200 ms round trips
        frac = float(flags.get("go_batch_window_frac"))
        assert abs(d._window_s(st.rt_ema_s) - 0.2 * frac) < 1e-9
        st.rt_ema_s = 30.0                       # compile outlier
        cap = float(flags.get("go_batch_window_max_ms")) / 1000.0
        assert d._window_s(st.rt_ema_s) == cap            # capped
        flags.set("go_batch_window_ms", 7)       # fixed override wins
        assert abs(d._window_s(st.rt_ema_s) - 0.007) < 1e-9
        flags.set("go_batch_window_ms", 0)       # immediate mode
        assert d._window_s(st.rt_ema_s) == 0.0
    finally:
        flags.set("go_batch_window_ms", prev)


def test_adaptive_window_ema_updates_from_batches():
    """Completed batches feed the key's round-trip EMA (launch ->
    results materialized), including two-phase _Pending results; a
    regime change re-centers the EMA within a few batches."""
    import time as _time

    from nebula_tpu.graph.batch_dispatch import GoBatchDispatcher

    class FakeRuntime:
        def exec_batch(self, space_id, payloads):
            _time.sleep(0.05)
            return [p for p in payloads], "m"

    d = GoBatchDispatcher(FakeRuntime())
    key = ("exec_batch", 1)
    prev = flags.get("go_batch_window_ms")
    try:
        flags.set("go_batch_window_ms", -1)
        d.submit_batched(key, 1)
        st = d._state(key)
        first = st.rt_ema_s
        assert first >= 0.05
        for _ in range(3):
            d.submit_batched(key, 2)
        assert st.rt_ema_s >= 0.05              # stays in regime
        # the observed window stays proportional and bounded
        w = d._window_s(st.rt_ema_s)
        frac = float(flags.get("go_batch_window_frac"))
        cap = float(flags.get("go_batch_window_max_ms")) / 1000.0
        assert w <= cap and w <= st.rt_ema_s * frac + 1e-9
    finally:
        flags.set("go_batch_window_ms", prev)


def test_adaptive_window_skips_lone_requests_and_honors_zero_caps():
    """A lone request on an idle key must dispatch immediately even
    with a warm high-RTT EMA (nothing to pool with), and an operator's
    EXPLICIT go_batch_window_max_ms=0 / go_batch_window_frac=0 must not
    be silently replaced by defaults."""
    import time as _time

    from nebula_tpu.graph.batch_dispatch import GoBatchDispatcher, _KeyState

    class FakeRuntime:
        def exec_batch(self, space_id, payloads):
            return [p for p in payloads], "m"

    d = GoBatchDispatcher(FakeRuntime())
    key = ("exec_batch", 1)
    prev = flags.get("go_batch_window_ms")
    try:
        flags.set("go_batch_window_ms", -1)
        st = d._state(key)
        st.rt_ema_s = 1.0                       # warm, high-RTT regime
        t0 = _time.perf_counter()
        r, _ = d.submit_batched(key, 5)         # lone request
        solo_ms = (_time.perf_counter() - t0) * 1000
        assert r == 5
        assert solo_ms < 25, f"lone request paid the window: {solo_ms}ms"
        # explicit zeros are respected, not defaulted away
        st2 = _KeyState()
        st2.rt_ema_s = 1.0
        prev_cap = flags.get("go_batch_window_max_ms")
        prev_frac = flags.get("go_batch_window_frac")
        flags.set("go_batch_window_max_ms", 0)
        assert d._window_s(st2.rt_ema_s) == 0.0
        flags.set("go_batch_window_max_ms", prev_cap)
        flags.set("go_batch_window_frac", 0)
        assert d._window_s(st2.rt_ema_s) == 0.0
        flags.set("go_batch_window_frac", prev_frac)
    finally:
        flags.set("go_batch_window_ms", prev)
