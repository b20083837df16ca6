"""nebulint self-tests: each of the six checks must fire on a minimal
fixture snippet, honor inline suppression, and the whole-package run is
the tier-1 gate (zero unsuppressed violations).  Also the runtime half:
the OrderedLock watchdog must detect a deliberately seeded inversion.

Run just these: ``pytest -m lint``.
"""
import json
import os
import textwrap
import threading

import pytest

from nebula_tpu.tools.lint import (ALL_CHECKS, Baseline, LintError,
                                   lint_paths, run_lint)
from nebula_tpu.tools.lint.core import DEFAULT_BASELINE

pytestmark = pytest.mark.lint

PKG_ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "nebula_tpu")
FIXTURE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "lint_fixtures")


def fixture_src(name):
    """One deliberately-broken module from tests/lint_fixtures/."""
    with open(os.path.join(FIXTURE_DIR, name), encoding="utf-8") as fh:
        return fh.read()


def run_fixture(tmp_path, files, checks=None):
    """Write {relpath: source} under a fake package root and lint it."""
    root = tmp_path / "pkg"
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return lint_paths(str(root), checks=checks, repo_root=str(tmp_path))


def names(violations):
    return [v.check for v in violations]


# ================================================== 1 · lock-discipline
_UNGUARDED = """
    import threading

    class Daemon:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0

        def process_put(self, req):
            self.count = self.count + 1
"""


def test_lock_discipline_unguarded_mutation(tmp_path):
    vs = run_fixture(tmp_path, {"daemon.py": _UNGUARDED},
                     checks=["lock-discipline"])
    assert names(vs) == ["lock-discipline"]
    assert "self.count" in vs[0].message


def test_lock_discipline_guarded_is_clean(tmp_path):
    ok = _UNGUARDED.replace(
        "            self.count = self.count + 1",
        "            with self._lock:\n"
        "                self.count = self.count + 1")
    assert run_fixture(tmp_path, {"daemon.py": ok},
                       checks=["lock-discipline"]) == []


def test_lock_discipline_caller_holds_contract(tmp_path):
    ok = _UNGUARDED.replace(
        "        def process_put(self, req):",
        "        def process_put(self, req):\n"
        '            """Caller holds the lock."""')
    assert run_fixture(tmp_path, {"daemon.py": ok},
                       checks=["lock-discipline"]) == []


def test_lock_discipline_blocking_call_under_lock(tmp_path):
    vs = run_fixture(tmp_path, {"daemon.py": """
        import threading
        import time

        class Daemon:
            def __init__(self):
                self._lock = threading.Lock()

            def slow(self):
                with self._lock:
                    time.sleep(1)
    """}, checks=["lock-discipline"])
    assert names(vs) == ["lock-discipline"]
    assert "blocking call" in vs[0].message


def test_lock_discipline_inline_suppression(tmp_path):
    sup = _UNGUARDED.replace(
        "            self.count = self.count + 1",
        "            self.count = self.count + 1  "
        "# nebulint: disable=lock-discipline")
    assert run_fixture(tmp_path, {"daemon.py": sup},
                       checks=["lock-discipline"]) == []


# ===================================================== 2 · lock-order
_CYCLE = """
    import threading

    class Pair:
        def __init__(self):
            self.la = threading.Lock()
            self.lb = threading.Lock()

        def one(self):
            with self.la:
                with self.lb:
                    pass

        def two(self):
            with self.lb:
                with self.la:
                    pass
"""


def test_lock_order_cycle(tmp_path):
    vs = run_fixture(tmp_path, {"pair.py": _CYCLE}, checks=["lock-order"])
    assert names(vs) == ["lock-order"]
    assert "Pair.la" in vs[0].message and "Pair.lb" in vs[0].message


def test_lock_order_consistent_is_clean(tmp_path):
    ok = _CYCLE.replace(
        "            with self.lb:\n                with self.la:",
        "            with self.la:\n                with self.lb:")
    assert run_fixture(tmp_path, {"pair.py": ok},
                       checks=["lock-order"]) == []


def test_lock_order_file_suppression(tmp_path):
    sup = "# nebulint: disable-file=lock-order\n" + textwrap.dedent(_CYCLE)
    root = tmp_path / "pkg"
    root.mkdir()
    (root / "pair.py").write_text(sup)
    assert lint_paths(str(root), checks=["lock-order"],
                      repo_root=str(tmp_path)) == []


# ================================================== 3 · status-discard
_DISCARD = """
    from common.status import Status

    def save() -> Status:
        return Status.OK()

    def caller():
        save()
"""


def test_status_discard(tmp_path):
    vs = run_fixture(tmp_path, {"mod.py": _DISCARD},
                     checks=["status-discard"])
    assert names(vs) == ["status-discard"]
    assert "save" in vs[0].message


def test_status_used_is_clean(tmp_path):
    ok = _DISCARD.replace("    save()", "    st = save()\n    return st")
    assert run_fixture(tmp_path, {"mod.py": ok},
                       checks=["status-discard"]) == []


def test_status_discard_suppression(tmp_path):
    sup = _DISCARD.replace(
        "    save()", "    save()  # nebulint: disable=status-discard")
    assert run_fixture(tmp_path, {"mod.py": sup},
                       checks=["status-discard"]) == []


def test_status_fixpoint_through_wrappers(tmp_path):
    """A function returning another status-returning function's result
    is itself status-returning (the MUST_USE_RESULT fixpoint)."""
    vs = run_fixture(tmp_path, {"mod.py": """
        def inner():
            return Status.OK()

        def outer():
            return inner()

        def caller():
            outer()
    """}, checks=["status-discard"])
    assert [v.symbol for v in vs] == ["caller"]


# ==================================================== 4 · jax-hotpath
def test_hotpath_jit_in_loop(tmp_path):
    vs = run_fixture(tmp_path, {"tpu/runtime.py": """
        import jax

        def traverse(frontiers):
            for f in frontiers:
                step = jax.jit(lambda x: x)
                f = step(f)
    """}, checks=["jax-hotpath"])
    assert names(vs) == ["jax-hotpath"]
    assert "loop" in vs[0].message


def test_hotpath_host_sync_on_device_value(tmp_path):
    vs = run_fixture(tmp_path, {"tpu/kernels.py": """
        def drain(frontier_dev):
            total = 0
            while total < 10:
                total += int(frontier_dev)
            return total
    """}, checks=["jax-hotpath"])
    assert names(vs) == ["jax-hotpath"]
    assert "frontier_dev" in vs[0].message


def test_hotpath_outside_hot_files_ignored(tmp_path):
    assert run_fixture(tmp_path, {"graph/parser/x.py": """
        import jax

        def setup(items):
            for i in items:
                f = jax.jit(lambda x: x)
    """}, checks=["jax-hotpath"]) == []


def test_hotpath_jit_outside_loop_is_clean(tmp_path):
    assert run_fixture(tmp_path, {"tpu/runtime.py": """
        import jax

        step = jax.jit(lambda x: x)

        def traverse(frontiers):
            for f in frontiers:
                f = step(f)
    """}, checks=["jax-hotpath"]) == []


# ================================================== 5 · flag-registry
def test_flag_registry_missing_define(tmp_path):
    vs = run_fixture(tmp_path, {"mod.py": """
        from common.flags import flags

        def f():
            return flags.get("never_defined_anywhere")
    """}, checks=["flag-registry"])
    assert names(vs) == ["flag-registry"]
    assert "never_defined_anywhere" in vs[0].message


def test_flag_registry_dead_define(tmp_path):
    vs = run_fixture(tmp_path, {"flagdefs.py": """
        from common.flags import flags

        flags.define("dead_knob", 1, "never read")
    """}, checks=["flag-registry"])
    assert names(vs) == ["flag-registry"]
    assert "dead_knob" in vs[0].message


def test_flag_registry_defined_and_read_is_clean(tmp_path):
    assert run_fixture(tmp_path, {"flagdefs.py": """
        from common.flags import flags

        flags.define("live_knob", 1, "read below")

        def f():
            return flags.get("live_knob")
    """}, checks=["flag-registry"]) == []


# ================================================== 6 · span-registry
_SPAN_REG = """
    from common import tracing

    SPAN_NAMES = ("graph.query", "rpc.client")

    def f():
        with tracing.span("rpc.client"):
            pass

    def g():
        with tracing.start_trace("graph.query", forced=True):
            pass
"""


def test_span_registry_clean(tmp_path):
    assert run_fixture(tmp_path, {"tracing.py": _SPAN_REG},
                       checks=["span-registry"]) == []


def test_span_registry_unknown_name(tmp_path):
    bad = _SPAN_REG.replace('tracing.span("rpc.client")',
                            'tracing.span("rpc.mystery")')
    vs = run_fixture(tmp_path, {"tracing.py": bad},
                     checks=["span-registry"])
    msgs = [v.message for v in vs]
    assert any("rpc.mystery" in m and "not in the SPAN_NAMES" in m
               for m in msgs)
    # the now-unused registry entry is flagged dead too
    assert any("'rpc.client'" in m and "never used" in m for m in msgs)


def test_span_registry_dynamic_name_rejected(tmp_path):
    bad = _SPAN_REG.replace('tracing.span("rpc.client")',
                            'tracing.span(name)')
    vs = run_fixture(tmp_path, {"tracing.py": bad},
                     checks=["span-registry"])
    assert any("literal" in v.message for v in vs)


def test_span_registry_requires_single_registry(tmp_path):
    files = {"tracing.py": _SPAN_REG,
             "other.py": 'SPAN_NAMES = ("dup.reg",)\n'}
    vs = run_fixture(tmp_path, files, checks=["span-registry"])
    assert any("ONE registry" in v.message for v in vs)


def test_span_registry_missing_registry(tmp_path):
    vs = run_fixture(tmp_path, {"mod.py": """
        from common import tracing

        def f():
            with tracing.span("orphan.name"):
                pass
    """}, checks=["span-registry"])
    assert any("no SPAN_NAMES registry" in v.message for v in vs)


def test_span_registry_ignores_unrelated_span_calls(tmp_path):
    """A local helper also called span() (numpy span, etc.) must not
    trip the check — only tracing.* receivers count."""
    assert run_fixture(tmp_path, {"mod.py": """
        def span(x):
            return x

        def f():
            return span("whatever")
    """}, checks=["span-registry"]) == []


# ================================================ 7 · metric-registry
_METRIC_REG = """
    from common.stats import stats

    METRIC_NAMES = ("graph.qps", "graph.stmt.*", "raft.term")

    def f(kind):
        stats.add_value("graph.qps")
        stats.observe(f"graph.stmt.{kind}.latency_us", 1.0)
        stats.set_gauge("raft.term", 3, space=1)
"""


def test_metric_registry_clean(tmp_path):
    assert run_fixture(tmp_path, {"stats.py": _METRIC_REG},
                       checks=["metric-registry"]) == []


def test_metric_registry_unknown_name(tmp_path):
    bad = _METRIC_REG.replace('stats.add_value("graph.qps")',
                              'stats.add_value("graph.mystery")')
    vs = run_fixture(tmp_path, {"stats.py": bad},
                     checks=["metric-registry"])
    msgs = [v.message for v in vs]
    assert any("graph.mystery" in m and "not in the METRIC_NAMES" in m
               for m in msgs)
    # the now-unused registry entry is flagged dead too
    assert any("'graph.qps'" in m and "never used" in m for m in msgs)


def test_metric_registry_fstring_needs_wildcard(tmp_path):
    bad = _METRIC_REG.replace(
        'stats.observe(f"graph.stmt.{kind}.latency_us", 1.0)',
        'stats.observe(f"rogue.family.{kind}", 1.0)')
    vs = run_fixture(tmp_path, {"stats.py": bad},
                     checks=["metric-registry"])
    msgs = [v.message for v in vs]
    assert any("rogue.family." in m and "not in the METRIC_NAMES" in m
               for m in msgs)
    assert any("'graph.stmt.*'" in m and "never used" in m for m in msgs)


def test_metric_registry_short_fstring_head_rejected(tmp_path):
    """An f-string whose literal head is a PREFIX of a wildcard entry
    ("graph." under "graph.stmt.*") could name any family — it must
    NOT satisfy the registry."""
    bad = _METRIC_REG.replace(
        'stats.observe(f"graph.stmt.{kind}.latency_us", 1.0)',
        'stats.observe(f"graph.{kind}", 1.0)')
    vs = run_fixture(tmp_path, {"stats.py": bad},
                     checks=["metric-registry"])
    assert any("'graph.'" in v.message and "not in the METRIC_NAMES"
               in v.message for v in vs)


def test_metric_registry_dynamic_name_rejected(tmp_path):
    bad = _METRIC_REG.replace('stats.add_value("graph.qps")',
                              'stats.add_value(kind)')
    vs = run_fixture(tmp_path, {"stats.py": bad},
                     checks=["metric-registry"])
    assert any("literal" in v.message for v in vs)


def test_metric_registry_ifexp_literals_resolved(tmp_path):
    ok = _METRIC_REG.replace(
        'stats.add_value("graph.qps")',
        'stats.add_value("graph.qps" if kind else "raft.term")')
    # both arms resolve; raft.term now has a second use — still clean
    assert run_fixture(tmp_path, {"stats.py": ok},
                       checks=["metric-registry"]) == []


def test_metric_registry_requires_single_registry(tmp_path):
    files = {"stats.py": _METRIC_REG,
             "other.py": 'METRIC_NAMES = ("dup.reg",)\n'}
    vs = run_fixture(tmp_path, files, checks=["metric-registry"])
    assert any("ONE registry" in v.message for v in vs)


def test_metric_registry_missing_registry(tmp_path):
    vs = run_fixture(tmp_path, {"mod.py": """
        from common.stats import stats

        def f():
            stats.add_value("orphan.metric")
    """}, checks=["metric-registry"])
    assert any("no METRIC_NAMES registry" in v.message for v in vs)


def test_metric_registry_ignores_unrelated_receivers(tmp_path):
    """Only stats-ish receivers count — a runtime's own `self.stats`
    dict ops or random add_value helpers must not trip the check."""
    assert run_fixture(tmp_path, {"mod.py": """
        def add_value(x):
            return x

        class R:
            def f(self):
                return add_value("whatever")
    """}, checks=["metric-registry"]) == []


def test_metric_registry_suppression_round_trip(tmp_path):
    bad = _METRIC_REG.replace(
        'stats.add_value("graph.qps")',
        'stats.add_value("graph.qps")\n'
        '        stats.add_value(kind)  '
        '# nebulint: disable=metric-registry')
    assert run_fixture(tmp_path, {"stats.py": bad},
                       checks=["metric-registry"]) == []


# ====================================================== baseline rules
def test_baseline_entry_requires_reason():
    with pytest.raises(LintError):
        Baseline([{"check": "status-discard", "file": "x.py",
                   "symbol": "f", "reason": "  "}])


def test_baseline_matches_and_reports_stale(tmp_path):
    vs = run_fixture(tmp_path, {"mod.py": _DISCARD},
                     checks=["status-discard"])
    bl = Baseline([
        {"check": "status-discard", "file": "pkg/mod.py",
         "symbol": "caller", "reason": "fixture"},
        {"check": "status-discard", "file": "pkg/gone.py",
         "symbol": "f", "reason": "stale entry"},
    ])
    assert [v for v in vs if not bl.match(v)] == []
    assert [e["file"] for e in bl.unused()] == ["pkg/gone.py"]


# ============================================== whole-package tier-1 gate
def test_package_is_clean():
    """THE gate: nebulint over nebula_tpu reports zero unsuppressed
    violations (suppressions and baseline entries each carry a reason)."""
    vs, _bl = run_lint(PKG_ROOT, baseline_path=DEFAULT_BASELINE)
    assert vs == [], "unsuppressed nebulint violations:\n" + "\n".join(
        repr(v) for v in vs)


def test_package_has_no_stale_baseline_entries():
    vs, bl = run_lint(PKG_ROOT, baseline_path=DEFAULT_BASELINE)
    if bl is not None:
        stale = bl.unused()
        assert stale == [], f"stale baseline entries: {stale}"


def test_all_checks_registered():
    assert set(ALL_CHECKS) == {"lock-discipline", "lock-order",
                               "status-discard", "jax-hotpath",
                               "flag-registry", "span-registry",
                               "metric-registry", "event-registry",
                               "guard-inference", "blocking-under-lock",
                               "context-capture", "jaxpr-audit",
                               "mesh-audit", "carveout-inventory",
                               "wire-contract", "obligation-tracking",
                               "protocol-registry", "mc-coverage",
                               "stale-suppression"}


# ========================================== OrderedLock runtime watchdog
def test_watchdog_detects_seeded_inversion():
    """The mini-TSan self-test demanded by the acceptance criteria: two
    threads acquiring two ranks in opposite orders — even without losing
    the race — must produce a recorded inversion."""
    from nebula_tpu.common.ordered_lock import OrderedLock, watchdog
    a = OrderedLock("selftest.A")
    b = OrderedLock("selftest.B")
    watchdog.enable()
    try:
        def ab():
            with a:
                with b:
                    pass

        def ba():
            with b:
                with a:
                    pass

        for fn in (ab, ba):
            t = threading.Thread(target=fn)
            t.start()
            t.join()
        violations = watchdog.drain()
    finally:
        watchdog.disable()
    assert violations, "seeded inversion went undetected"
    assert "selftest.A" in violations[0] and "selftest.B" in violations[0]


def test_watchdog_consistent_order_is_clean():
    from nebula_tpu.common.ordered_lock import OrderedLock, watchdog
    a = OrderedLock("clean.A")
    b = OrderedLock("clean.B")
    watchdog.enable()
    try:
        for _ in range(3):
            with a:
                with b:
                    pass
        violations = watchdog.drain()
    finally:
        watchdog.disable()
    assert violations == []


def test_watchdog_strict_raises():
    from nebula_tpu.common.ordered_lock import (LockOrderError, OrderedLock,
                                                watchdog)
    a = OrderedLock("strict.A")
    b = OrderedLock("strict.B")
    watchdog.enable(strict=True)
    try:
        with a:
            with b:
                pass
        with pytest.raises(LockOrderError):
            with b:
                with a:
                    pass
    finally:
        watchdog.drain()
        watchdog.disable()


def test_ordered_lock_works_with_condition():
    """raftex wraps its part lock in a Condition — the OrderedLock must
    support wait/notify (full reentrant unwind mirrored in the
    watchdog's held stack)."""
    from nebula_tpu.common.ordered_lock import OrderedLock, watchdog
    lk = OrderedLock("cond.part", reentrant=True)
    cond = threading.Condition(lk)
    state = {"ready": False}
    watchdog.enable()
    try:
        def producer():
            with cond:
                state["ready"] = True
                cond.notify_all()

        t = threading.Thread(target=producer)
        with cond:
            with lk:   # reentrant: wait() must unwind BOTH levels
                t.start()
                assert cond.wait_for(lambda: state["ready"], timeout=5)
        t.join()
        assert watchdog.drain() == []
    finally:
        watchdog.disable()


def test_hotpath_mutable_static_args_flagged(tmp_path):
    vs = run_fixture(tmp_path, {"tpu/runtime.py": """
        import jax

        f = jax.jit(lambda x: x, static_argnums=[0])
    """}, checks=["jax-hotpath"])
    assert names(vs) == ["jax-hotpath"]


def test_hotpath_mutable_literal_in_other_kwarg_not_flagged(tmp_path):
    """Only the static_arg* value itself may trip the mutable-literal
    rule — a list in donate_argnums/in_shardings must not."""
    assert run_fixture(tmp_path, {"tpu/runtime.py": """
        import jax

        f = jax.jit(lambda x: x, static_argnums=(0,), donate_argnums=[1])
    """}, checks=["jax-hotpath"]) == []


def test_missing_explicit_baseline_is_config_error(tmp_path):
    with pytest.raises(LintError):
        run_lint(PKG_ROOT, baseline_path=str(tmp_path / "typo.json"))


# ================================================== 7 · jaxpr-audit
def _audit(specs, phases, span_names=("tpu.kernel",)):
    from nebula_tpu.tools.lint.jaxaudit import audit_specs
    vs, _kinds = audit_specs(specs, None, phases,
                             span_names, lambda s: ("pkg/fake.py", 1))
    return vs


def _spec(fn, avals, *, name="k", budget=4, donate=(), dispatch=(),
          frontier=(), buckets=None):
    from nebula_tpu.tpu.kernels import KernelSpec
    return KernelSpec(
        name, fn, phase_kind="k", budget=budget,
        instantiate=(buckets or (lambda fx: [(("k",), fn, avals)])),
        donate=donate, dispatch=dispatch, frontier=frontier)


_PHASES_1IN_1OUT = {"k": {"phases": ("tpu.kernel",), "h2d": 1, "d2h": 1}}


def test_jaxaudit_flags_loop_callback():
    """Seeded violation: a pure_callback inside the hop loop — the
    exact host-round-trip-per-hop class the audit exists to block."""
    import jax
    import numpy as np

    @jax.jit
    def bad(x):
        def body(i, acc):
            return acc + jax.pure_callback(
                lambda v: v, jax.ShapeDtypeStruct((8,), np.int32), x)
        return jax.lax.fori_loop(0, 4, body, x)

    vs = _audit([_spec(bad, (jax.ShapeDtypeStruct((8,), np.int32),),
                       dispatch=(0,))], _PHASES_1IN_1OUT)
    assert any("host callback" in v.message for v in vs), vs


def test_jaxaudit_flags_64bit_promotion():
    """Seeded violation: an int64 loop-carried buffer (visible because
    the audit traces under enable_x64)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def bad(x):
        def body(i, acc):
            return acc + x.astype(jnp.int64)
        acc0 = jnp.zeros(x.shape, jnp.int64)
        return jax.lax.fori_loop(0, 3, body, acc0).astype(jnp.int32)

    vs = _audit([_spec(bad, (jax.ShapeDtypeStruct((8,), np.int32),),
                       dispatch=(0,))], _PHASES_1IN_1OUT)
    assert any("int64" in v.message and "carry" in v.message
               for v in vs), vs


def test_jaxaudit_flags_unbounded_bucket_space():
    """Seeded violation: more distinct (cache key, signature) pairs
    than the declared retrace budget."""
    import jax
    import numpy as np

    @jax.jit
    def k(x):
        return x + 1

    def buckets(fx):
        return [((("k", s)), k, (jax.ShapeDtypeStruct((s,), np.int32),))
                for s in (8, 16, 32, 64)]

    vs = _audit([_spec(k, None, budget=2, dispatch=(0,),
                       buckets=buckets)], _PHASES_1IN_1OUT)
    assert any("retrace budget" in v.message for v in vs), vs


def test_jaxaudit_flags_donation_drift():
    """Seeded violations, both directions: claiming donation the jit
    doesn't perform, and donating what the spec says is cached."""
    import jax
    import numpy as np

    @jax.jit
    def undonated(x):
        return x + 1

    donated = jax.jit(lambda x: x + 1, donate_argnums=(0,))
    av = (jax.ShapeDtypeStruct((8,), np.int8),)
    vs = _audit([_spec(undonated, av, donate=(0,), dispatch=(0,))],
                _PHASES_1IN_1OUT)
    assert any("donation drift" in v.message for v in vs), vs
    vs = _audit([_spec(donated, av, donate=(), dispatch=(0,))],
                _PHASES_1IN_1OUT)
    assert any("donation drift" in v.message for v in vs), vs


def test_jaxaudit_flags_transfer_drift():
    """Seeded violation: a kernel growing a second output (an extra
    device->host fetch) without updating DEVICE_PHASES."""
    import jax
    import numpy as np

    @jax.jit
    def two_out(x):
        return x + 1, x * 2

    vs = _audit([_spec(two_out, (jax.ShapeDtypeStruct((8,), np.int32),),
                       dispatch=(0,))], _PHASES_1IN_1OUT)
    assert any("output fetches" in v.message for v in vs), vs


def test_jaxaudit_flags_wide_frontier():
    """Seeded violation: a declared frontier bitmap that is int32."""
    import jax
    import numpy as np

    @jax.jit
    def k(f):
        return f

    vs = _audit([_spec(k, (jax.ShapeDtypeStruct((8,), np.int32),),
                       dispatch=(0,), frontier=(0,))], _PHASES_1IN_1OUT)
    assert any("frontier argument" in v.message for v in vs), vs


def test_jaxaudit_package_registry_is_clean_within_budgets():
    """Acceptance: the auditor runs over EVERY registered kernel
    factory across all shape buckets and the per-kernel retrace-budget
    table holds — zero violations on the real registry."""
    from nebula_tpu.common.tracing import SPAN_NAMES
    from nebula_tpu.tools.lint.jaxaudit import audit_specs
    from nebula_tpu.tpu import runtime as rt
    from nebula_tpu.tpu.kernels import AuditFixture, kernel_registry

    registry = kernel_registry()
    assert {"ell_go", "ell_go_hop", "sparse_go", "ell_bfs", "ell_absorb",
            "ell_absorb_sharded"} <= set(registry)
    # a kernel nothing in the package dispatches is not registered
    assert not {"go", "go_filtered", "bfs", "sharded_go",
                "expr_filter"} & set(registry)
    fx = AuditFixture()
    vs, kinds = audit_specs(registry.values(), fx, rt.DEVICE_PHASES,
                            SPAN_NAMES, lambda s: ("x", 1))
    assert vs == [], "\n".join(repr(v) for v in vs)
    # every spec declares a positive budget (the table is the proof
    # surface TestRetraceBudget's runtime smoke test now leans on)
    assert all(s.budget >= 1 for s in registry.values())


def test_jaxaudit_skips_fixture_roots(tmp_path):
    """Fixture packages have no device path: the package check is a
    no-op there (the self-tests above drive audit_specs directly)."""
    assert run_fixture(tmp_path, {"mod.py": "x = 1"},
                       checks=["jaxpr-audit"]) == []


# ================================================== 8 · wire-contract
_WIRE_ORPHANS = """
    class Client:
        def fetch(self, addr):
            resp = self.cm.call(addr, "fetchThing", {"space_id": 1})
            return resp

    class Service:
        def rpc_storeThing(self, req):
            return {"ok": True}
"""


def test_wirecheck_orphan_method_and_handler(tmp_path):
    vs = run_fixture(tmp_path, {"svc.py": _WIRE_ORPHANS},
                     checks=["wire-contract"])
    msgs = [v.message for v in vs]
    assert any("no rpc_fetchThing handler" in m for m in msgs), msgs
    assert any("rpc_storeThing has no in-tree caller" in m
               for m in msgs), msgs


_WIRE_DRIFT = """
    class Client:
        def put(self, addr):
            resp = self.cm.call(addr, "putThing",
                                {"space_id": 1, "stale_key": 2})
            return resp.get("phantom_field")

    class Service:
        def rpc_putThing(self, req):
            part = req["part_id"]
            return {"ok": True, "latency_us": 1}
"""


def test_wirecheck_argument_and_envelope_drift(tmp_path):
    vs = run_fixture(tmp_path, {"svc.py": _WIRE_DRIFT},
                     checks=["wire-contract"])
    msgs = [v.message for v in vs]
    # arity drift: required key never sent
    assert any("never sends key 'part_id'" in m for m in msgs), msgs
    # dead payload: sent key never read
    assert any("sends key 'stale_key'" in m for m in msgs), msgs
    # phantom envelope field: read but never written
    assert any("reads response field 'phantom_field'" in m
               for m in msgs), msgs
    # dead envelope field: written but no caller reads it
    assert any("'latency_us'" in m and "no caller reads" in m
               for m in msgs), msgs


def test_wirecheck_matched_contract_is_clean(tmp_path):
    ok = """
    class Client:
        def put(self, addr):
            resp = self.cm.call(addr, "putThing",
                                {"space_id": 1, "part_id": 2})
            return resp.get("ok")

    class Service:
        def rpc_putThing(self, req):
            part = req["part_id"]
            space = req.get("space_id")
            return {"ok": True}
    """
    assert run_fixture(tmp_path, {"svc.py": ok},
                       checks=["wire-contract"]) == []


def test_wirecheck_open_handlers_exempt_from_key_checks(tmp_path):
    """A handler that hands the request to non-self code (the storage
    processors) cannot be key-checked exactly — no false positives."""
    open_h = """
    class Client:
        def put(self, addr):
            return self.cm.call(addr, "putThing", {"anything": 1})

    class Service:
        def rpc_putThing(self, req):
            return process(req)
    """
    assert run_fixture(tmp_path, {"svc.py": open_h},
                       checks=["wire-contract"]) == []


def test_wirecheck_suppression_roundtrip(tmp_path):
    """Inline suppression silences a wire-contract finding like any
    other check."""
    suppressed = _WIRE_ORPHANS.replace(
        'resp = self.cm.call(addr, "fetchThing", {"space_id": 1})',
        'resp = self.cm.call(  # nebulint: disable=wire-contract\n'
        '                addr, "fetchThing", {"space_id": 1})').replace(
        "def rpc_storeThing(self, req):",
        "def rpc_storeThing(self, req):"
        "  # nebulint: disable=wire-contract")
    assert run_fixture(tmp_path, {"svc.py": suppressed},
                       checks=["wire-contract"]) == []


def test_wirecheck_delegation_resolves_alias_handlers(tmp_path):
    """rpc_X bodies that forward to rpc_Y inherit Y's request/response
    contract (the meta.thrift spelling aliases)."""
    alias = """
    class Client:
        def put(self, addr):
            resp = self.cm.call(addr, "createTag", {"name": "t"})
            return resp.get("id")

    class Service:
        def rpc_createTagSchema(self, req):
            name = req["name"]
            return {"id": 7}

        def rpc_createTag(self, req):
            return self.rpc_createTagSchema(req)
    """
    vs = run_fixture(tmp_path, {"svc.py": alias},
                     checks=["wire-contract"])
    # rpc_createTagSchema has no DIRECT caller but IS a delegation
    # target; the alias's contract resolves through it
    assert vs == [], vs


def test_wirecheck_scatter_gather_make_req_tuples(tmp_path):
    """The ``return "method", {...}`` make_req closures count as call
    sites (the StorageClient collect contract)."""
    sg = """
    class Client:
        def get_props(self):
            def make(parts):
                return "bulkFetch", {"space_id": 1}
            return self.collect(make)
    """
    vs = run_fixture(tmp_path, {"svc.py": sg}, checks=["wire-contract"])
    assert any("no rpc_bulkFetch handler" in v.message for v in vs), vs


# ================================================ lint wall-time guard
def test_lint_wall_time_budget():
    """The whole-package analysis (all eight checks, jaxpr tracing
    included) must stay fast enough to gate tier-1 — micro_bench's
    lint component enforces the tighter interactive budget."""
    import time
    t0 = time.perf_counter()
    run_lint(PKG_ROOT, baseline_path=DEFAULT_BASELINE)
    elapsed = time.perf_counter() - t0
    assert elapsed < 60.0, f"nebulint took {elapsed:.1f}s"


def test_wirecheck_frame_contract_drops_untraced_frame(tmp_path):
    """Seeded violation: interface/rpc.py losing the 2-element untraced
    frame (every call would pay the trace envelope)."""
    rpc = """
    _TRACED = "__spans__"
    _RESP = "__resp__"

    def client_call(method, payload, sp):
        return _pack([method, payload, [sp.trace_id, sp.span_id]])

    def server(frame):
        parts = _unpack(frame)
        method, payload = parts[0], parts[1]
        wctx = parts[2] if len(parts) > 2 else None
        return {_TRACED: [], _RESP: payload}

    def absorb(resp):
        return resp.get(_TRACED), resp.get(_RESP)
    """
    vs = run_fixture(tmp_path, {"interface/rpc.py": rpc},
                     checks=["wire-contract"])
    assert any("2-element" in v.message for v in vs), vs


def test_wirecheck_frame_contract_envelope_constant_drift(tmp_path):
    """Seeded violation: an envelope constant written server-side but
    never read by the client (dead piggyback payload)."""
    rpc = """
    _TRACED = "__spans__"
    _RESP = "__resp__"

    def client_call(method, payload):
        return _pack([method, payload])

    def client_traced(method, payload, sp):
        return _pack([method, payload, [sp.trace_id, sp.span_id]])

    def server(frame):
        parts = _unpack(frame)
        return {_TRACED: [], _RESP: parts[1]}

    def absorb(resp):
        return resp.get(_RESP)      # __spans__ never read
    """
    vs = run_fixture(tmp_path, {"interface/rpc.py": rpc},
                     checks=["wire-contract"])
    assert any("_TRACED" in v.message and "never read" in v.message
               for v in vs), vs


def test_wirecheck_endpoint_contract_drift(tmp_path):
    """Seeded violation: a contract endpoint returning a payload key
    the ENDPOINT_CONTRACT declaration doesn't name."""
    ws = """
    class WebService:
        def __init__(self):
            self.register_handler("/faults", self._faults)
            self.register_handler("/get_stats", self._get_stats)
            self.register_handler("/traces", self._traces)

        def _faults(self, q, body):
            return 200, {"seed": 1, "rules": [], "bogus_field": 2}

        def _get_stats(self, q, body):
            return 200, dump()

        def _traces(self, q, body):
            return 200, {"traces": []}
    """
    vs = run_fixture(tmp_path, {"webservice/service.py": ws},
                     checks=["wire-contract"])
    assert any("bogus_field" in v.message and "/faults" in v.message
               for v in vs), vs


# ================================================ 10 · event-registry
_EVENT_REG = """
    from common.events import journal

    EVENT_KINDS = ("raft.leader_elected", "query.shed")

    def f():
        journal.record("raft.leader_elected", detail="x")
        journal.record("query.shed", detail="y", space=1)
"""


def test_event_registry_clean(tmp_path):
    assert run_fixture(tmp_path, {"events.py": _EVENT_REG},
                       checks=["event-registry"]) == []


def test_event_registry_unknown_kind(tmp_path):
    bad = _EVENT_REG.replace('journal.record("query.shed"',
                             'journal.record("query.mystery"')
    vs = run_fixture(tmp_path, {"events.py": bad},
                     checks=["event-registry"])
    msgs = [v.message for v in vs]
    assert any("query.mystery" in m and "not in the EVENT_KINDS" in m
               for m in msgs)
    # the now-unrecorded registry entry is flagged dead too
    assert any("'query.shed'" in m and "never recorded" in m
               for m in msgs)


def test_event_registry_dynamic_kind_rejected(tmp_path):
    bad = _EVENT_REG.replace('journal.record("query.shed"',
                             'journal.record(kind')
    vs = run_fixture(tmp_path, {"events.py": bad},
                     checks=["event-registry"])
    assert any("literal" in v.message for v in vs)


def test_event_registry_single_registry(tmp_path):
    files = {"events.py": _EVENT_REG,
             "other.py": 'EVENT_KINDS = ("dup.kind",)\n'}
    vs = run_fixture(tmp_path, files, checks=["event-registry"])
    assert any("ONE registry" in v.message for v in vs)


def test_event_registry_ignores_unrelated_record_calls(tmp_path):
    """slow-log / router `.record` methods are out of scope — only a
    journal-named receiver is the event seam."""
    assert run_fixture(tmp_path, {"mod.py": """
        class R:
            def f(self, slow_log, router):
                slow_log.record("not an event", 12)
                router.record(("k",), "device", 1.0)
    """}, checks=["event-registry"]) == []


def test_event_registry_suppression_round_trip(tmp_path):
    bad = _EVENT_REG.replace(
        'journal.record("query.shed", detail="y", space=1)',
        'journal.record("query.mystery", detail="y")  '
        '# nebulint: disable=event-registry — fixture')
    vs = run_fixture(tmp_path, {"events.py": bad},
                     checks=["event-registry"])
    assert not any("query.mystery" in v.message for v in vs)


# ================================================ 11 · guard-inference
def test_guards_seeded_fixture_fires(tmp_path):
    """The checked-in deliberately-racy module must trip BOTH rules:
    the unguarded read and the mixed-lock access."""
    vs = run_fixture(tmp_path,
                     {"kvstore/racy.py": fixture_src("guards_racy.py")},
                     checks=["guard-inference"])
    msgs = [v.message for v in vs]
    assert any("unguarded read of self._entries" in m for m in msgs), msgs
    assert any("mixed-lock write of self._seq" in m and "_side" in m
               for m in msgs), msgs


def test_guards_fixed_fixture_is_clean(tmp_path):
    """Taking the right lock at both seeded sites silences the pass."""
    fixed = fixture_src("guards_racy.py").replace(
        "        return list(self._entries)",
        "        with self._lock:\n"
        "            return list(self._entries)").replace(
        "        with self._side:\n            self._seq = 0",
        "        with self._lock:\n            self._seq = 0")
    assert run_fixture(tmp_path, {"kvstore/racy.py": fixed},
                       checks=["guard-inference"]) == []


def test_guards_out_of_scope_path_ignored(tmp_path):
    """The same racy class outside the concurrency-bearing packages
    (GUARD_SCOPE) is not analysed — inference needs real threaded
    access patterns to be meaningful."""
    assert run_fixture(tmp_path,
                       {"parser/racy.py": fixture_src("guards_racy.py")},
                       checks=["guard-inference"]) == []


def test_guards_guarded_by_pin_overrides_majority(tmp_path):
    """A minority-guarded attribute is unflagged by inference; the
    guarded-by declaration pins it and the bare accesses light up."""
    src = """
        import threading

        class Pinned:
            def __init__(self):
                self._lock = threading.Lock()
                # nebulint: guarded-by=_lock
                self._cache = {}

            def fill(self, k, v):
                with self._lock:
                    self._cache[k] = v

            def peek_a(self, k):
                return self._cache.get(k)

            def peek_b(self, k):
                return self._cache.get(k)

            def peek_c(self, k):
                return self._cache.get(k)
    """
    # without the pin: 1 guarded / 3 bare -> no majority, clean
    unpinned = src.replace("                # nebulint: guarded-by=_lock\n",
                           "")
    assert run_fixture(tmp_path, {"kvstore/mod.py": unpinned},
                       checks=["guard-inference"]) == []
    vs = run_fixture(tmp_path, {"kvstore/mod.py": src},
                     checks=["guard-inference"])
    assert len([v for v in vs
                if "unguarded read of self._cache" in v.message]) == 3, vs


def test_guards_guarded_by_none_exempts(tmp_path):
    """guarded-by=none declares a deliberately unguarded attribute —
    majority inference is overridden the other way."""
    racy = fixture_src("guards_racy.py").replace(
        "        self._entries = []",
        "        # nebulint: guarded-by=none\n"
        "        self._entries = []")
    vs = run_fixture(tmp_path, {"kvstore/racy.py": racy},
                     checks=["guard-inference"])
    assert not any("_entries" in v.message for v in vs), vs


def test_guards_unknown_lock_name_flagged(tmp_path):
    """A pin naming a lock the class does not declare is itself a
    violation — stale declarations must not disable the analysis."""
    vs = run_fixture(tmp_path, {"kvstore/mod.py": """
        import threading

        class Typo:
            def __init__(self):
                self._lock = threading.Lock()
                # nebulint: guarded-by=_lok
                self._x = 0

            def a(self):
                with self._lock:
                    self._x += 1

            def b(self):
                with self._lock:
                    self._x += 1
    """}, checks=["guard-inference"])
    assert any("no lock named '_lok'" in v.message for v in vs), vs


def test_guards_caller_holds_contract(tmp_path):
    """A documented caller-holds method is analysed as holding every
    class lock (the locks.py convention, shared)."""
    ok = fixture_src("guards_racy.py").replace(
        "    def peek(self):",
        "    def peek(self):\n"
        '        """Caller holds the lock."""')
    vs = run_fixture(tmp_path, {"kvstore/racy.py": ok},
                     checks=["guard-inference"])
    assert not any("unguarded read" in v.message for v in vs), vs


def test_guards_suppression_round_trip(tmp_path):
    sup = fixture_src("guards_racy.py").replace(
        "        return list(self._entries)",
        "        return list(self._entries)  "
        "# nebulint: disable=guard-inference").replace(
        "            self._seq = 0",
        "            self._seq = 0  # nebulint: disable=guard-inference")
    assert run_fixture(tmp_path, {"kvstore/racy.py": sup},
                       checks=["guard-inference"]) == []


def test_guards_init_only_attrs_exempt(tmp_path):
    """Configuration wired in __init__ before threads exist is never
    flagged, even when other attrs establish a guard."""
    vs = run_fixture(tmp_path, {"kvstore/mod.py": """
        import threading

        class Cfg:
            def __init__(self):
                self._lock = threading.Lock()
                self.limit = 10
                self._n = 0

            def bump(self):
                with self._lock:
                    self._n += 1

            def bump2(self):
                with self._lock:
                    self._n += 1

            def read(self):
                return self.limit
    """}, checks=["guard-inference"])
    assert vs == [], vs


# ============================================ 12 · blocking-under-lock
def test_blocking_seeded_fixture_fires(tmp_path):
    """The PR 6 bug class, reconstructed: an RPC fan-out reached only
    THROUGH a helper call while the catalog-style lock is held."""
    vs = run_fixture(tmp_path,
                     {"svc.py": fixture_src("blocking_racy.py")},
                     checks=["blocking-under-lock"])
    assert len(vs) == 1, vs
    v = vs[0]
    assert "rpc" in v.message and "_fan_out()" in v.message
    assert v.symbol == "RacyCatalog.rpc_download"


def test_blocking_fixed_fixture_is_clean(tmp_path):
    """Moving the fan-out OUT of the locked region (snapshot under the
    lock, dial outside — the rpc_download fix shape) silences it."""
    fixed = fixture_src("blocking_racy.py").replace(
        """    def rpc_download(self, req):
        with self._lock:
            # 120 s of peer dials under the write lock
            self._fan_out("download")
            return {"ok": True}""",
        """    def rpc_download(self, req):
        with self._lock:
            pending = list(self.hosts)
        del pending
        self._fan_out("download")
        return {"ok": True}""")
    assert run_fixture(tmp_path, {"svc.py": fixed},
                       checks=["blocking-under-lock"]) == []


def test_blocking_direct_sleep_left_to_lock_discipline(tmp_path):
    """A DIRECT sleep under a lock is lock-discipline's finding — this
    pass must not duplicate it (only interprocedural reachability and
    the new effect classes are its job)."""
    assert run_fixture(tmp_path, {"svc.py": """
        import threading
        import time

        class D:
            def __init__(self):
                self._lock = threading.Lock()

            def slow(self):
                with self._lock:
                    time.sleep(1)
    """}, checks=["blocking-under-lock"]) == []


def test_blocking_untimed_wait_on_other_lock(tmp_path):
    """Waiting (no timeout) on some OTHER condition while holding a
    lock is an unbounded stall; waiting on the condition that wraps
    the single held lock is how Conditions work — clean."""
    vs = run_fixture(tmp_path, {"svc.py": """
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self.other = threading.Condition()

            def stall(self):
                with self._lock:
                    self.other.wait()
    """}, checks=["blocking-under-lock"])
    assert len(vs) == 1 and "cond-wait" in vs[0].message, vs
    assert run_fixture(tmp_path, {"svc.py": """
        import threading

        class W:
            def __init__(self):
                self.cond = threading.Condition()

            def ok(self):
                with self.cond:
                    self.cond.wait()
    """}, checks=["blocking-under-lock"]) == []


def test_blocking_timed_wait_is_clean(tmp_path):
    assert run_fixture(tmp_path, {"svc.py": """
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self.other = threading.Condition()

            def bounded(self):
                with self._lock:
                    self.other.wait(0.5)
    """}, checks=["blocking-under-lock"]) == []


def test_blocking_device_sync_under_lock(tmp_path):
    vs = run_fixture(tmp_path, {"svc.py": """
        import threading

        class D:
            def __init__(self):
                self._lock = threading.Lock()

            def publish(self, arrs):
                with self._lock:
                    for a in arrs:
                        a.block_until_ready()
    """}, checks=["blocking-under-lock"])
    assert len(vs) == 1 and "device" in vs[0].message, vs


def test_blocking_caller_holds_vouches_file_io_not_rpc(tmp_path):
    """A caller-holds docstring vouches for bounded disk I/O (the raft
    hard-state fsync pattern) but can NEVER vouch for an RPC dial."""
    vouched_io = """
        import threading
        import os

        class P:
            def __init__(self):
                self._lock = threading.Lock()

            def _persist(self):
                \"\"\"Caller holds the lock.\"\"\"
                with open("/tmp/x", "w") as f:
                    os.fsync(f.fileno())

            def commit(self):
                with self._lock:
                    self._persist()
    """
    assert run_fixture(tmp_path, {"svc.py": vouched_io},
                       checks=["blocking-under-lock"]) == []
    vouched_rpc = vouched_io.replace(
        'with open("/tmp/x", "w") as f:\n'
        '                    os.fsync(f.fileno())',
        'self.cm.call("h", "persist", {})')
    vs = run_fixture(tmp_path, {"svc.py": vouched_rpc},
                     checks=["blocking-under-lock"])
    assert len(vs) == 1 and "rpc" in vs[0].message, vs


def test_blocking_nested_def_not_charged_to_encloser(tmp_path):
    """A closure DEFINED under the lock runs later on its own stack —
    defining it is free; only calling it under the lock blocks."""
    assert run_fixture(tmp_path, {"svc.py": """
        import threading
        import time

        class D:
            def __init__(self):
                self._lock = threading.Lock()

            def arm(self):
                with self._lock:
                    def later():
                        time.sleep(1)
                    self.cb = later
    """}, checks=["blocking-under-lock"]) == []


def test_blocking_suppression_round_trip(tmp_path):
    sup = fixture_src("blocking_racy.py").replace(
        '            self._fan_out("download")',
        '            # nebulint: disable=blocking-under-lock\n'
        '            self._fan_out("download")')
    assert run_fixture(tmp_path, {"svc.py": sup},
                       checks=["blocking-under-lock"]) == []


# ============================================== 13 · context-capture
def test_capture_seeded_fixture_fires_all_three(tmp_path):
    """The checked-in fixture drops the trace AND the deadline at the
    submission, and consults the dead binding in the worker."""
    vs = run_fixture(tmp_path,
                     {"client.py": fixture_src("capture_racy.py")},
                     checks=["context-capture"])
    msgs = [v.message for v in vs]
    assert any("never calls tracing.attach_captured" in m
               for m in msgs), msgs
    assert any("never rebinds the budget" in m for m in msgs), msgs
    assert any("consulted on a pool thread" in m for m in msgs), msgs


def test_capture_rebinding_worker_is_clean(tmp_path):
    """The storage/client.py collect/_call_host idiom — capture on the
    submitting side, attach + bind in the worker — is the clean
    shape."""
    fixed = fixture_src("capture_racy.py").replace(
        """    def _worker(self, host, dl):
        # consults the submitting thread's binding, which is gone
        timeout = deadlines.remaining_or(10.0)
        return self.cm.call(host, "bulkGet", {}, timeout=timeout)""",
        """    def _worker(self, host, dl, tctx=None):
        with tracing.attach_captured(tctx):
            with deadlines.bind(dl):
                timeout = deadlines.remaining_or(10.0)
                return self.cm.call(host, "bulkGet", {},
                                    timeout=timeout)""")
    assert run_fixture(tmp_path, {"client.py": fixed},
                       checks=["context-capture"]) == []


def test_capture_unbound_background_thread_is_clean(tmp_path):
    """A daemon background thread started OUTSIDE any span/deadline
    scope carries no context to drop — never flagged."""
    assert run_fixture(tmp_path, {"daemon.py": """
        import threading

        class Rebuilder:
            def kick(self, space_id):
                t = threading.Thread(target=self._rebuild,
                                     args=(space_id,), daemon=True)
                t.start()

            def _rebuild(self, space_id):
                return space_id
    """}, checks=["context-capture"]) == []


def test_capture_thread_target_from_span_scope(tmp_path):
    """Thread(target=...) inside a span is a submission too."""
    vs = run_fixture(tmp_path, {"mod.py": """
        import threading
        from common import tracing

        class T:
            def go(self):
                with tracing.span("graph.query"):
                    threading.Thread(target=self._work).start()

            def _work(self):
                return 1
    """}, checks=["context-capture"])
    assert len(vs) == 1 and "attach_captured" in vs[0].message, vs


def test_capture_unresolvable_worker_skipped(tmp_path):
    """An externally imported worker can't be proven either way — the
    pass stays package-local and silent."""
    assert run_fixture(tmp_path, {"mod.py": """
        from common import tracing
        from elsewhere import external_worker

        class T:
            def go(self, pool):
                with tracing.span("graph.query"):
                    pool.submit(external_worker, 1)
    """}, checks=["context-capture"]) == []


def test_capture_suppression_round_trip(tmp_path):
    sup = fixture_src("capture_racy.py").replace(
        "            futs = [self.pool.submit(self._worker, h, dl) "
        "for h in hosts]",
        "            # background probe: budget deliberately not "
        "inherited\n"
        "            # nebulint: disable=context-capture\n"
        "            futs = [self.pool.submit(self._worker, h, dl) "
        "for h in hosts]").replace(
        "        timeout = deadlines.remaining_or(10.0)",
        "        timeout = deadlines.remaining_or(10.0)  "
        "# nebulint: disable=context-capture")
    assert run_fixture(tmp_path, {"client.py": sup},
                       checks=["context-capture"]) == []


# ============================================ 14 · stale-suppression
def test_stale_suppression_flags_fossil(tmp_path):
    """A disable= comment whose check runs clean at that site is
    itself a violation."""
    src = _DISCARD.replace(
        "    save()",
        "    st = save()  # nebulint: disable=status-discard\n"
        "    return st")
    vs = run_fixture(tmp_path, {"mod.py": src},
                     checks=["status-discard", "stale-suppression"])
    assert len(vs) == 1, vs
    assert vs[0].check == "stale-suppression"
    assert "status-discard" in vs[0].message


def test_stale_suppression_live_comment_not_flagged(tmp_path):
    """A suppression that actually suppresses is not stale."""
    src = _DISCARD.replace(
        "    save()", "    save()  # nebulint: disable=status-discard")
    assert run_fixture(tmp_path, {"mod.py": src},
                       checks=["status-discard",
                               "stale-suppression"]) == []


def test_stale_suppression_only_for_checks_that_ran(tmp_path):
    """A fossil for a check that did NOT run this invocation is not
    judged — partial runs must not produce false staleness."""
    src = _DISCARD.replace(
        "    save()",
        "    st = save()  # nebulint: disable=status-discard\n"
        "    return st")
    assert run_fixture(tmp_path, {"mod.py": src},
                       checks=["lock-order", "stale-suppression"]) == []


def test_stale_suppression_disable_all_exempt(tmp_path):
    """disable=all cannot be attributed to one check — never stale."""
    src = _DISCARD.replace(
        "    save()",
        "    st = save()  # nebulint: disable=all\n    return st")
    assert run_fixture(tmp_path, {"mod.py": src},
                       checks=["status-discard",
                               "stale-suppression"]) == []


def test_stale_suppression_stale_file_disable(tmp_path):
    import textwrap as _tw
    root = tmp_path / "pkg"
    root.mkdir()
    (root / "mod.py").write_text(
        "# nebulint: disable-file=lock-order\nx = 1\n")
    vs = lint_paths(str(root), checks=["lock-order", "stale-suppression"],
                    repo_root=str(tmp_path))
    assert len(vs) == 1 and "disable-file" in vs[0].message, vs


# ====================================== 15 · jaxpr-audit: HBM budget
def _hbm_audit(specs, hbm):
    from nebula_tpu.common.tracing import SPAN_NAMES  # noqa: F401
    from nebula_tpu.tools.lint.jaxaudit import audit_specs
    vs, _k = audit_specs(specs, None, _PHASES_1IN_1OUT, ("tpu.kernel",),
                         lambda s: ("pkg/fake.py", 1), hbm=hbm)
    return vs


def test_hbm_budget_seeded_violation():
    """Seeded violation: a bucket whose resident bytes exceed the
    declared per-device budget fails the rung gate."""
    import jax
    import numpy as np

    @jax.jit
    def k(x):
        return x + 1

    av = (jax.ShapeDtypeStruct((1 << 16,), np.int32),)   # 256 KiB
    vs = _hbm_audit([_spec(k, av, dispatch=(0,))],
                    {"device_hbm_bytes": 1 << 10})
    assert any("per-device HBM budget" in v.message for v in vs), vs
    # and the same spec fits a real-sized budget
    vs = _hbm_audit([_spec(k, av, dispatch=(0,))],
                    {"device_hbm_bytes": 1 << 30})
    assert not any("HBM budget" in v.message for v in vs), vs


def test_hbm_donation_accounting():
    """A donated single-use input's buffer is reused for the output —
    the peak must not double-count it."""
    import jax
    import numpy as np

    donated = jax.jit(lambda x: x + 1, donate_argnums=(0,))
    n = 1 << 14
    av = (jax.ShapeDtypeStruct((n,), np.int8),)
    # budget fits input+0 extra but NOT input+output undonated
    budget = int(n * 1.5)
    vs = _hbm_audit([_spec(donated, av, donate=(0,), dispatch=(0,))],
                    {"device_hbm_bytes": budget})
    assert not any("HBM budget" in v.message for v in vs), vs
    undonated = jax.jit(lambda x: x + 1)
    vs = _hbm_audit([_spec(undonated, av, dispatch=(0,))],
                    {"device_hbm_bytes": budget})
    assert any("per-device HBM budget" in v.message for v in vs), vs


def test_hbm_ceiling_arithmetic():
    """The published-capacity proof: ceiling x bytes/edge must fit the
    table budget, which must fit the device."""
    from nebula_tpu.tools.lint.jaxaudit import hbm_ceiling_findings
    ok = {"device_hbm_bytes": 16 * 1000**3,
          "table_budget_bytes": 14 * 1000**3,
          "table_bytes_per_edge": 21.9,
          "edge_ceiling": 639_000_000}
    assert hbm_ceiling_findings(ok) == []
    over = dict(ok, edge_ceiling=800_000_000)
    assert any("capacity claim" in m for m in hbm_ceiling_findings(over))
    squeezed = dict(ok, table_budget_bytes=17 * 1000**3)
    assert any("headroom" in m for m in hbm_ceiling_findings(squeezed))


def test_hbm_model_consistent_and_enforced_package_wide():
    """Acceptance: the shipped HBM_MODEL is arithmetically consistent,
    every registered kernel rung fits it, and the audit path is ARMED
    (a 1-byte budget makes every rung fail)."""
    from nebula_tpu.common.tracing import SPAN_NAMES
    from nebula_tpu.tools.lint.jaxaudit import (audit_specs,
                                                hbm_ceiling_findings)
    from nebula_tpu.tpu import runtime as rt
    from nebula_tpu.tpu.kernels import AuditFixture, kernel_registry

    assert hbm_ceiling_findings(rt.HBM_MODEL) == []
    registry = kernel_registry()
    fx = AuditFixture()
    vs, _ = audit_specs(registry.values(), fx, rt.DEVICE_PHASES,
                        SPAN_NAMES, lambda s: ("x", 1),
                        hbm=rt.HBM_MODEL)
    assert vs == [], "\n".join(repr(v) for v in vs)
    vs, _ = audit_specs(registry.values(), fx, rt.DEVICE_PHASES,
                        SPAN_NAMES, lambda s: ("x", 1),
                        hbm={"device_hbm_bytes": 1})
    assert any("per-device HBM budget" in v.message for v in vs)


def test_hbm_residency_rows_positive():
    """The docs budget table's source: every registered kernel bucket
    reports a positive peak with mirror+dispatch+output parts."""
    import jax
    from nebula_tpu.tools.lint.jaxaudit import hbm_residency
    from nebula_tpu.tpu.kernels import AuditFixture, kernel_registry

    fx = AuditFixture()
    spec = kernel_registry()["ell_go"]
    key, fn, avals = spec.instantiate(fx)[0]
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(fn)(*avals)
    mirror_b, dispatch_b, out_b, peak = hbm_residency(spec, closed, avals)
    assert mirror_b > 0 and dispatch_b > 0 and out_b > 0
    assert peak >= mirror_b + dispatch_b


# ==================== round-10 audit regressions (named fixes)
def test_guards_regression_device_ready_shape(tmp_path):
    """Regression for the round-10 audit fix in storage/service.py
    device_ready: a health probe reading lock-guarded runtime handles
    WITHOUT the lock.  The old shape must fire; the fixed (locked)
    shape must be clean."""
    racy = """
        import threading

        class Service:
            def __init__(self):
                self._device_rt_lock = threading.Lock()
                self._device_rt = None

            def rpc_a(self):
                with self._device_rt_lock:
                    self._device_rt = object()

            def rpc_b(self):
                with self._device_rt_lock:
                    self._device_rt = None

            def device_ready(self):
                return self._device_rt is not None
    """
    vs = run_fixture(tmp_path, {"storage/service.py": racy},
                     checks=["guard-inference"])
    assert any("unguarded read of self._device_rt" in v.message
               for v in vs), vs
    fixed = racy.replace(
        "                return self._device_rt is not None",
        "                with self._device_rt_lock:\n"
        "                    return self._device_rt is not None")
    assert run_fixture(tmp_path, {"storage/service.py": fixed},
                       checks=["guard-inference"]) == []


def test_window_s_takes_snapshot_value():
    """Regression for the round-10 audit fix in batch_dispatch: the
    pooling window computes from an EMA value the leader SNAPSHOTTED
    under the key's condition — the helper must not reach back into
    shared _KeyState after the lock was released."""
    import inspect
    from nebula_tpu.common.flags import flags
    from nebula_tpu.graph.batch_dispatch import GoBatchDispatcher

    d = GoBatchDispatcher(runtime=None)
    prev = flags.get("go_batch_window_ms")
    try:
        flags.set("go_batch_window_ms", -1)
        frac = float(flags.get("go_batch_window_frac"))
        # a plain float in, deterministic window out — no shared state
        assert abs(d._window_s(0.1) - min(
            0.1 * frac, d.window.cap_s())) < 1e-9
        assert d._window_s(0.0) == 0.0
    finally:
        flags.set("go_batch_window_ms", prev)
    params = list(inspect.signature(d._window_s).parameters)
    assert params == ["rt_ema_s"]


def test_stale_baseline_judged_only_for_ran_checks(tmp_path):
    """A partial --check run must not condemn baseline entries whose
    check never ran (caught by the round-10 verify drive: --check
    guard-inference reported all 24 wire-contract parity entries as
    stale and exited 1)."""
    vs, bl = run_lint(PKG_ROOT, baseline_path=DEFAULT_BASELINE,
                      checks=["guard-inference", "stale-suppression"])
    assert vs == []
    assert bl is not None and bl.unused() == []


def test_guards_wrapped_pin_attaches(tmp_path):
    """Review regression: a guarded-by pin whose comment wraps onto a
    continuation line must still attach to the first code line below
    it (the breaker's _cells pin is written exactly this way)."""
    src = """
        import threading

        class Pinned:
            def __init__(self):
                self._lock = threading.Lock()
                # nebulint: guarded-by=_lock (state transitions; the
                # fast paths below are documented exceptions)
                self._cache = {}

            def fill(self, k, v):
                with self._lock:
                    self._cache[k] = v

            def peek_a(self, k):
                return self._cache.get(k)

            def peek_b(self, k):
                return self._cache.get(k)

            def peek_c(self, k):
                return self._cache.get(k)
    """
    vs = run_fixture(tmp_path, {"kvstore/mod.py": src},
                     checks=["guard-inference"])
    assert len([v for v in vs
                if "unguarded read of self._cache" in v.message]) == 3, vs


def test_guards_orphan_pin_flagged(tmp_path):
    """A pin that attaches to no attribute line is itself a violation
    — a silently detached declaration would fake enforcement."""
    vs = run_fixture(tmp_path, {"kvstore/mod.py": """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                # nebulint: guarded-by=_lock

            def noop(self):
                return None
    """}, checks=["guard-inference"])
    assert any("attaches to no" in v.message for v in vs), vs


def test_capture_escape_deduped_across_submitters(tmp_path):
    """Review regression: one worker submitted from two sites is ONE
    escaped-deadline defect, not two."""
    src = fixture_src("capture_racy.py").replace(
        "    def _worker(self, host, dl):",
        "    def collect2(self, hosts):\n"
        "        with tracing.span(\"storage.collect.pass\"):\n"
        "            return [self.pool.submit(self._worker, h, None)\n"
        "                    for h in hosts]\n"
        "\n"
        "    def _worker(self, host, dl):")
    vs = run_fixture(tmp_path, {"client.py": src},
                     checks=["context-capture"])
    escapes = [v for v in vs if "consulted on a pool thread" in v.message]
    assert len(escapes) == 1, vs


def test_guards_mutator_counts_once(tmp_path):
    """Review regression: `self._q.append(x)` is ONE write access, not
    a write plus a read of the receiver — double-counting dilutes the
    majority below inference threshold and hides the race."""
    vs = run_fixture(tmp_path, {"kvstore/mod.py": """
        import threading

        class Q:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = []

            def a(self):
                with self._lock:
                    x = self._q

            def b(self):
                with self._lock:
                    y = self._q

            def push(self, x):
                self._q.append(x)
    """}, checks=["guard-inference"])
    # true counts: 2 guarded reads vs 1 unguarded write -> strict
    # majority -> exactly ONE violation (the write), not two
    assert len(vs) == 1, vs
    assert "unguarded write of self._q" in vs[0].message


def test_guards_pin_scoped_to_owning_class(tmp_path):
    """Review regression: a pin inside class A must not bleed onto a
    same-named attribute of class B in the same file."""
    vs = run_fixture(tmp_path, {"kvstore/mod.py": """
        import threading

        class A:
            def __init__(self):
                self._mu = threading.Lock()
                # nebulint: guarded-by=_mu
                self._cells = {}

            def w1(self):
                with self._mu:
                    self._cells[1] = 1

            def w2(self):
                with self._mu:
                    self._cells[2] = 2

        class B:
            def __init__(self):
                self._lock = threading.Lock()
                self._cells = {}

            def w1(self):
                with self._lock:
                    self._cells[1] = 1

            def w2(self):
                with self._lock:
                    self._cells[2] = 2
    """}, checks=["guard-inference"])
    # B must NOT report "declares no lock named '_mu'" from A's pin
    assert vs == [], vs


def test_blocking_mixed_with_items_alignment(tmp_path):
    """Review regression: `with tracing.span(...), self.cond:` then
    `self.cond.wait()` is the normal Condition idiom — the span item
    must not shift the rank/source pairing and fake a stall."""
    assert run_fixture(tmp_path, {"svc.py": """
        import threading

        class W:
            def __init__(self):
                self.cond = threading.Condition()

            def ok(self, tracing):
                with tracing.span("x"), self.cond:
                    self.cond.wait()
    """}, checks=["blocking-under-lock"]) == []


# ================================================ 15 · mesh-audit (v4)
def _mesh_fixture():
    """A tiny shared mesh-audit fixture: 2 devices are enough to make
    collectives real (tier-1 forces 8 virtual CPU devices)."""
    from nebula_tpu.tpu.kernels import AuditFixture
    return AuditFixture()


def _mesh_spec(fn, avals, *, name="mk", collective=None, ici=None,
               donate=(), shard_args=(), shard_outs=(), packed=(),
               frontier=()):
    from nebula_tpu.tpu.kernels import KernelSpec
    return KernelSpec(
        name, fn, phase_kind="mk", budget=4,
        instantiate=lambda fx: [],
        mesh_instantiate=lambda fx, mesh: [(("mk",
                                             mesh.shape["parts"]),
                                            fn, avals)],
        collective=collective, ici_bytes=ici, donate=donate,
        shard_args=shard_args, shard_outs=shard_outs, packed=packed,
        frontier=frontier)


def _mesh_audit(specs, hbm=None, sizes=(2,)):
    from nebula_tpu.tools.lint.meshaudit import mesh_audit_specs
    return mesh_audit_specs(specs, _mesh_fixture(),
                            lambda s: ("pkg/fake.py", 1), hbm=hbm,
                            sizes=sizes)


def _psum_kernel(fx, mesh):
    """A shard_map kernel whose ONLY collective is a psum over parts."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    def per_shard(x):
        return jax.lax.psum(x, "parts")

    return jax.jit(shard_map(per_shard, mesh=mesh, in_specs=(P("parts"),),
                             out_specs=P(), check_vma=False))


def test_meshaudit_flags_undeclared_collective():
    """Seeded violation: the trace psums but the COLLECTIVE_MODEL
    declares nothing — undeclared ICI traffic."""
    import numpy as np
    fx = _mesh_fixture()
    mesh = fx.mesh(2)
    kern = _psum_kernel(fx, mesh)
    spec = _mesh_spec(kern, (fx.aval((16,), np.float32),),
                      collective=(), ici=lambda fx, k: 1 << 20)
    vs = _mesh_audit([spec])
    assert any("UNDECLARED collective" in v.message
               and "psum" in v.message for v in vs), vs


def test_meshaudit_flags_implicit_resharding():
    """Seeded violation: a with_sharding_constraint re-replication the
    model does not declare — the implicit-all-gather class."""
    import numpy as np
    fx = _mesh_fixture()
    mesh = fx.mesh(2)
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    replicate = NamedSharding(mesh, P())

    @jax.jit
    def kern(x):
        return jax.lax.with_sharding_constraint(x * 2, replicate)

    spec = _mesh_spec(kern, (fx.aval((16, 8), np.uint8),),
                      collective=(("psum", ("parts",)),),
                      ici=lambda fx, k: 1 << 20)
    vs = _mesh_audit([spec])
    assert any("UNDECLARED collective" in v.message
               and "sharding_constraint" in v.message for v in vs), vs


def test_meshaudit_flags_stale_declared_collective():
    """A declared collective absent from the trace is a stale model."""
    import numpy as np
    import jax

    @jax.jit
    def kern(x):
        return x + 1

    spec = _mesh_spec(kern, (_mesh_fixture().aval((16,), np.float32),),
                      collective=(("psum", ("parts",)),),
                      ici=lambda fx, k: 1 << 20)
    vs = _mesh_audit([spec])
    assert any("absent from the k=2 trace" in v.message for v in vs), vs


def test_meshaudit_flags_ici_over_bound():
    """Seeded violation: measured exchange bytes above the declared
    ici_bytes bound."""
    import numpy as np
    fx = _mesh_fixture()
    kern = _psum_kernel(fx, fx.mesh(2))
    spec = _mesh_spec(kern, (fx.aval((1 << 12,), np.float32),),
                      collective=(("psum", ("parts",)),),
                      ici=lambda fx, k: 4)
    vs = _mesh_audit([spec])
    assert any("above the declared ici_bytes bound" in v.message
               for v in vs), vs


def test_meshaudit_flags_missing_ici_model():
    import numpy as np
    fx = _mesh_fixture()
    kern = _psum_kernel(fx, fx.mesh(2))
    spec = _mesh_spec(kern, (fx.aval((16,), np.float32),),
                      collective=(("psum", ("parts",)),))
    vs = _mesh_audit([spec])
    assert any("no ici_bytes bound declared" in v.message for v in vs), vs


def test_meshaudit_flags_over_budget_mesh_rung():
    """Seeded violation: per-shard residency (replicated arg dominates)
    over a tiny device budget."""
    import numpy as np
    import jax

    @jax.jit
    def kern(x):
        return x + 1

    fx = _mesh_fixture()
    spec = _mesh_spec(kern, (fx.aval((1 << 12,), np.float32),),
                      collective=())
    vs = _mesh_audit([spec], hbm={"device_hbm_bytes": 64})
    assert any("this mesh rung cannot serve" in v.message
               for v in vs), vs


def test_meshaudit_flags_closure_captured_buffer():
    """Seeded violation: a table closed over instead of passed as an
    argument — every chip would pin a replica."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    big = jnp.asarray(np.zeros((1 << 18,), np.float32))

    @jax.jit
    def kern(x):
        return x + big[:16]

    fx = _mesh_fixture()
    spec = _mesh_spec(kern, (fx.aval((16,), np.float32),),
                      collective=())
    vs = _mesh_audit([spec])
    assert any("closes over" in v.message for v in vs), vs


def test_meshaudit_int8_sharded_frontier_regression_fails():
    """THE layout gate the issue names: a sharded family regressing to
    the int8-per-lane frontier fails on the aval dtype at every mesh
    size."""
    import numpy as np
    import jax

    @jax.jit
    def kern(f):
        return f

    fx = _mesh_fixture()
    spec = _mesh_spec(kern, (fx.aval((49, 128), np.int8),),
                      collective=(), packed=(0,), frontier=(0,))
    vs = _mesh_audit([spec])
    assert any("not a bit-packed uint8 lane matrix" in v.message
               for v in vs), vs


def test_meshaudit_undeclared_sharded_family_flagged():
    """mesh_instantiate without a COLLECTIVE_MODEL (and vice versa)
    is itself a violation — no sharded family goes unaudited."""
    import numpy as np
    import jax

    @jax.jit
    def kern(x):
        return x

    fx = _mesh_fixture()
    spec = _mesh_spec(kern, (fx.aval((8,), np.float32),),
                      collective=None)
    vs = _mesh_audit([spec])
    assert any("without a declared COLLECTIVE_MODEL" in v.message
               for v in vs), vs
    from nebula_tpu.tpu.kernels import KernelSpec
    spec2 = KernelSpec("mk2", kern, phase_kind="mk", budget=1,
                       instantiate=lambda fx: [],
                       collective=(("psum", ("parts",)),))
    vs2 = _mesh_audit([spec2])
    assert any("unprovable" in v.message for v in vs2), vs2


def test_meshaudit_clean_declared_kernel_passes():
    """The fixed variant: declared psum + sane bounds = clean."""
    import numpy as np
    fx = _mesh_fixture()
    kern = _psum_kernel(fx, fx.mesh(2))
    spec = _mesh_spec(kern, (fx.aval((16,), np.float32),),
                      collective=(("psum", ("parts",)),),
                      ici=lambda fx, k: 1 << 20, shard_args=(0,))
    assert _mesh_audit([spec],
                       hbm={"device_hbm_bytes": 16 * 1000**3}) == []


def test_meshaudit_capacity_table_arithmetic():
    """The published multi-chip capacity table is arithmetic over the
    declarations: an over-claimed rung, a shrinking rung, and a k=1
    row disagreeing with HBM_MODEL all fire."""
    from nebula_tpu.tools.lint.meshaudit import mesh_capacity_findings
    hbm = {"table_bytes_per_edge": 20.0,
           "table_budget_bytes": 1000, "edge_ceiling": 50}
    ok = {"mesh_sizes": (1, 2), "capacity_edges": {1: 50, 2: 100}}
    assert mesh_capacity_findings(hbm, ok) == []
    over = {"mesh_sizes": (1, 2), "capacity_edges": {1: 50, 2: 200}}
    assert any("exceeds" in m for m in mesh_capacity_findings(hbm, over))
    shrink = {"mesh_sizes": (1, 2), "capacity_edges": {1: 50, 2: 40}}
    msgs = mesh_capacity_findings(hbm, shrink)
    assert any("below the previous rung" in m for m in msgs), msgs
    drift = {"mesh_sizes": (1, 2), "capacity_edges": {1: 40, 2: 80}}
    assert any("disagrees" in m for m in mesh_capacity_findings(
        hbm, drift))
    missing = {"mesh_sizes": (1, 2, 4), "capacity_edges": {1: 50}}
    assert any("do not match mesh_sizes" in m
               for m in mesh_capacity_findings(hbm, missing))


def test_meshaudit_package_registry_is_clean():
    """Every registered sharded family proves its COLLECTIVE_MODEL,
    ICI bound and per-shard residency at every audited mesh size —
    the tier-1 half of the acceptance criteria (mesh shapes {1,2,4,8}
    under the conftest-forced 8-device platform)."""
    import jax
    assert len(jax.devices()) >= 8, jax.devices()
    vs = lint_paths(PKG_ROOT, checks=["mesh-audit"])
    assert vs == [], "\n".join(repr(v) for v in vs)


def test_meshaudit_registry_covers_all_sharded_families():
    """Every kernel family whose factory builds on a Mesh must carry
    mesh_instantiate — a new sharded kernel cannot ship unaudited."""
    from nebula_tpu.tpu.kernels import kernel_registry
    reg = kernel_registry()
    sharded = {name for name, s in reg.items()
               if "sharded" in name or "mesh" in name}
    assert sharded == {"ell_go_sharded",
                       "ell_bfs_sharded", "mesh_sparse_go",
                       "mesh_sparse_bfs", "ell_absorb_sharded"}
    for name in sharded:
        assert reg[name].mesh_instantiate is not None, name
        assert reg[name].collective is not None, name
        assert reg[name].ici_bytes is not None, name


def test_meshaudit_suppression_roundtrip(tmp_path):
    """A justified mesh finding suppresses like any other check: the
    capacity-table finding anchors at MESH_MODEL in a fixture
    runtime.py (fixture roots carry no kernel registry, so only the
    declaration checks run there)."""
    bad = """
    MESH_CARVEOUTS = {}
    """
    vs = run_fixture(tmp_path, {"tpu/runtime.py": bad},
                     checks=["mesh-audit"])
    assert vs == []        # no registry module -> no trace findings


# ====================================== 16 · carveout-inventory (v4)
def test_carveout_fixture_fires_all_three():
    src = fixture_src("carveout_racy.py")
    import tempfile
    import textwrap
    with tempfile.TemporaryDirectory() as td:
        root = os.path.join(td, "pkg")
        os.makedirs(os.path.join(root, "tpu"))
        with open(os.path.join(root, "tpu", "runtime.py"), "w") as fh:
            fh.write(textwrap.dedent(src))
        vs = lint_paths(root, checks=["carveout-inventory"],
                        repo_root=td)
    msgs = [v.message for v in vs]
    assert any("untagged carve-out" in m for m in msgs), msgs
    assert any("unknown carve-out reason "
               "'not-a-registered-reason'" in m for m in msgs), msgs
    assert any("dead carve-out registry entry 'ghost-reason'" in m
               for m in msgs), msgs
    # exactly two untagged sites (one gate return, one raise)
    assert sum("untagged carve-out" in m for m in msgs) == 2, msgs


def test_carveout_clean_module_passes(tmp_path):
    clean = """
    class TpuDecline(Exception):
        pass

    MESH_CARVEOUTS = {
        "plan-decline": "planner cannot reproduce the query",
    }

    def can_run_go(space_id):
        if space_id < 0:
            return False        # nebulint: carveout=plan-decline
        return True

    def serve(space_id):
        if space_id == 1:
            # nebulint: carveout=plan-decline
            raise TpuDecline("nope")
    """
    assert run_fixture(tmp_path, {"tpu/runtime.py": clean},
                       checks=["carveout-inventory"]) == []


def test_carveout_missing_registry_flagged(tmp_path):
    src = """
    class TpuDecline(Exception):
        pass

    def serve():
        raise TpuDecline("nope")
    """
    vs = run_fixture(tmp_path, {"tpu/runtime.py": src},
                     checks=["carveout-inventory"])
    assert any("no MESH_CARVEOUTS registry" in v.message for v in vs), vs


def test_carveout_reason_without_justification_flagged(tmp_path):
    src = """
    class TpuDecline(Exception):
        pass

    MESH_CARVEOUTS = {"x": ""}

    def serve():
        # nebulint: carveout=x
        raise TpuDecline("nope")
    """
    vs = run_fixture(tmp_path, {"tpu/runtime.py": src},
                     checks=["carveout-inventory"])
    assert any("carries no justification" in v.message for v in vs), vs


def test_carveout_scope_is_runtime_only(tmp_path):
    """TpuDecline raises OUTSIDE tpu/runtime.py are other modules'
    business (storage/device.py defines the type) — not this pass's."""
    src = """
    class TpuDecline(Exception):
        pass

    def serve():
        raise TpuDecline("nope")
    """
    assert run_fixture(tmp_path, {"storage/device.py": src},
                       checks=["carveout-inventory"]) == []


def test_carveout_suppression_roundtrip(tmp_path):
    src = """
    class TpuDecline(Exception):
        pass

    MESH_CARVEOUTS = {"y": "kept for the suppression round-trip"}

    def can_run_go(s):
        if s:
            return False        # nebulint: carveout=y
        return True

    def serve():  # noqa
        raise TpuDecline("x")  # nebulint: disable=carveout-inventory
    """
    assert run_fixture(tmp_path, {"tpu/runtime.py": src},
                       checks=["carveout-inventory"]) == []


def test_carveout_package_sites_all_tagged():
    vs = lint_paths(PKG_ROOT, checks=["carveout-inventory"])
    assert vs == [], "\n".join(repr(v) for v in vs)


# ================================================ 17 · incremental cache
def _cached_lint(root, repo_root, cache_dir):
    from nebula_tpu.tools.lint.cache import LintCache
    cache = LintCache(path=os.path.join(str(cache_dir), "cache.json"))
    vs = lint_paths(str(root), checks=["flag-registry"],
                    repo_root=str(repo_root), cache=cache)
    return vs, cache


def test_cache_hit_and_invalidation_on_edit(tmp_path):
    """The correctness contract: a warm cache replays, an EDIT to an
    in-scope file forces re-analysis and surfaces the new violation."""
    import textwrap
    root = tmp_path / "pkg"
    root.mkdir()
    mod = root / "m.py"
    mod.write_text(textwrap.dedent("""
        from common.flags import flags

        def f():
            return flags.get("undefined_flag_a")
    """))
    cdir = tmp_path / "cache"
    vs1, c1 = _cached_lint(root, tmp_path, cdir)
    assert c1.misses == 1 and c1.hits == 0
    n1 = len(vs1)
    vs2, c2 = _cached_lint(root, tmp_path, cdir)
    assert c2.hits == 1 and c2.misses == 0
    assert [repr(v) for v in vs2] == [repr(v) for v in vs1]
    # edit the file: new flag read must be re-discovered, not replayed
    mod.write_text(mod.read_text().replace(
        'flags.get("undefined_flag_a")',
        'flags.get("undefined_flag_a"), flags.get("undefined_flag_b")'))
    vs3, c3 = _cached_lint(root, tmp_path, cdir)
    assert c3.misses == 1 and c3.hits == 0
    assert len(vs3) > n1
    assert any("undefined_flag_b" in v.message for v in vs3), vs3


def test_cache_suppression_still_live_on_replay(tmp_path):
    """A suppression added AFTER the cache was written must apply on
    replay (raw violations are cached pre-suppression) — and its
    suppress hit feeds stale-suppression as usual."""
    import textwrap
    root = tmp_path / "pkg"
    root.mkdir()
    mod = root / "m.py"
    mod.write_text(textwrap.dedent("""
        from common.flags import flags

        def f():
            return flags.get("undefined_flag_a")
    """))
    cdir = tmp_path / "cache"
    vs1, _ = _cached_lint(root, tmp_path, cdir)
    assert vs1, "fixture must fire"
    # suppressing the line EDITS the file -> miss; the point is the
    # round trip stays coherent through the cache layer
    mod.write_text(mod.read_text().replace(
        'return flags.get("undefined_flag_a")',
        'return flags.get("undefined_flag_a")  '
        '# nebulint: disable=flag-registry'))
    vs2, c2 = _cached_lint(root, tmp_path, cdir)
    assert vs2 == [] and c2.misses == 1
    # replay (no edit): suppression applies against CACHED raw results
    vs3, c3 = _cached_lint(root, tmp_path, cdir)
    assert vs3 == [] and c3.hits == 1


def test_cache_invalidated_by_lint_source_change(tmp_path, monkeypatch):
    """Check-version invalidation: a different lint-package sha drops
    every entry."""
    import textwrap
    import nebula_tpu.tools.lint.cache as cache_mod
    root = tmp_path / "pkg"
    root.mkdir()
    (root / "m.py").write_text(textwrap.dedent("""
        def f():
            return 1
    """))
    cdir = tmp_path / "cache"
    _vs, c1 = _cached_lint(root, tmp_path, cdir)
    assert c1.misses == 1
    monkeypatch.setattr(cache_mod, "_LINT_SHA", "deadbeef")
    _vs, c2 = _cached_lint(root, tmp_path, cdir)
    assert c2.misses == 1 and c2.hits == 0


def test_cli_no_cache_flag(tmp_path, monkeypatch):
    """--no-cache runs clean end-to-end (and never writes the store)."""
    from nebula_tpu.tools.lint.__main__ import main
    import textwrap
    root = tmp_path / "pkg"
    root.mkdir()
    (root / "m.py").write_text(textwrap.dedent("""
        def f():
            return 1
    """))
    monkeypatch.setenv("NEBULINT_CACHE_DIR", str(tmp_path / "cc"))
    rc = main(["--no-cache", "--no-baseline", str(root)])
    assert rc == 0
    assert not (tmp_path / "cc").exists()


# ==================================================== 18 · SARIF output
SARIF_GOLDEN = os.path.join(FIXTURE_DIR, "golden.sarif")


def _sarif_fixture_run(tmp_path, capsys):
    """One seeded flag-registry violation plus one seeded
    obligation-tracking violation through the CLI in SARIF mode; paths
    are repo-root-relative, so the payload is stable."""
    from nebula_tpu.tools.lint.__main__ import main
    import textwrap
    root = tmp_path / "pkg"
    root.mkdir()
    (root / "mod.py").write_text(textwrap.dedent("""
        from common.flags import flags

        def f():
            return flags.get("undefined_flag_a")

        def seat(self):
            lane = self.ledger.alloc()
            return lane
    """))
    rc = main(["--format=sarif", "--no-baseline", "--no-cache",
               "--check", "flag-registry",
               "--check", "obligation-tracking", str(root)])
    out = capsys.readouterr().out
    return rc, json.loads(out)


def test_sarif_golden_file(tmp_path, capsys):
    """Golden-file contract: the SARIF payload for a seeded violation
    is byte-stable (modulo the JSON round trip) — CI annotation
    surfaces parse exactly this."""
    rc, doc = _sarif_fixture_run(tmp_path, capsys)
    assert rc == 1
    with open(SARIF_GOLDEN, encoding="utf-8") as fh:
        golden = json.load(fh)
    assert doc == golden, json.dumps(doc, indent=2, sort_keys=True)


def test_sarif_clean_run_is_valid_and_empty(tmp_path, capsys):
    from nebula_tpu.tools.lint.__main__ import main
    import textwrap
    root = tmp_path / "pkg"
    root.mkdir()
    (root / "mod.py").write_text(textwrap.dedent("""
        def f():
            return 1
    """))
    rc = main(["--format=sarif", "--no-baseline", "--no-cache",
               str(root)])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["version"] == "2.1.0"
    assert doc["runs"][0]["results"] == []

# ============================================= 19 · obligation-tracking
def test_obligation_fixture_fires_all_historical_bugs(tmp_path):
    """The three review-record bug classes (PR 7 unreleased probe
    token, PR 6 missed wakeup, PR 15 stranded seat on extract failure)
    plus the annotation edge cases — six violations, no more: the
    decline branch, the handler settle, the canonical _PrioritySlots
    shape, the named handoff and the with-bound deadline all pass."""
    vs = run_fixture(tmp_path,
                     {"graph/stream.py": fixture_src(
                         "obligations_racy.py")},
                     checks=["obligation-tracking"])
    msgs = {v.symbol: v.message for v in vs}
    assert len(vs) == 6, "\n".join(repr(v) for v in vs)
    assert "probe token" in msgs["Stream.go_via_device"]
    assert "leaks the obligation" in msgs["Stream.go_via_device"]
    assert "wakes nobody" in msgs["Stream.finish"]
    assert "exception edge" in msgs["Stream.tick"]
    assert "never discharged" in msgs["Stream.seat_forever"]
    assert "without a reason" in msgs["Stream.handoff_unnamed"]
    assert "binds a thread context" in msgs["Stream.poison_thread"]


def test_obligation_historical_fixes_restore_clean(tmp_path):
    """Each historical bug's FIX, re-applied to the fixture, silences
    exactly its violation — the fixture is the reverted-fix state."""
    src = fixture_src("obligations_racy.py")
    # PR 7: settle the probe token before the early return
    src = src.replace(
        "            return None             "
        "# PR 7: the probe token leaks here",
        "            self.breaker.release_probe(key)\n"
        "            return None")
    # PR 6: notify under the same condition
    src = src.replace(
        "            rider.done = True       # PR 6: nobody is notified",
        "            rider.done = True\n"
        "            self.cond.notify_all()")
    # PR 15: release the seat on the extract exception edge too
    src = src.replace(
        "        resolver = self.sess.extract([(lane, rider)])\n"
        "        self.ledger.release(lane)",
        "        try:\n"
        "            resolver = self.sess.extract([(lane, rider)])\n"
        "        except BaseException:\n"
        "            self.ledger.release(lane)\n"
        "            raise\n"
        "        self.ledger.release(lane)")
    vs = run_fixture(tmp_path, {"graph/stream.py": src},
                     checks=["obligation-tracking"])
    symbols = sorted(v.symbol for v in vs)
    assert symbols == ["Stream.handoff_unnamed", "Stream.poison_thread",
                       "Stream.seat_forever"], \
        "\n".join(repr(v) for v in vs)


def test_obligation_handed_off_annotation_waives(tmp_path):
    src = """
    class S:
        def seat(self, r):
            # nebulint: obligation=handed-off/released-by-the-pump
            lane = self.ledger.alloc()
            self.seated[lane] = r
    """
    assert run_fixture(tmp_path, {"m.py": src},
                       checks=["obligation-tracking"]) == []


def test_obligation_callee_discharge_propagates(tmp_path):
    """The blocking.py call-graph reuse: submit's slot is settled by
    the _run it hands the batch to — no violation at the acquire."""
    src = """
    class D:
        def submit(self, req):
            self._inflight.acquire(1)
            try:
                return self._run(req)
            except BaseException:
                self._inflight.release()
                raise

        def _run(self, req):
            try:
                return req
            finally:
                self._inflight.release()
    """
    assert run_fixture(tmp_path, {"m.py": src},
                       checks=["obligation-tracking"]) == []


def test_obligation_suppression_roundtrip(tmp_path):
    src = """
    class S:
        def seat(self):
            lane = self.ledger.alloc()  # nebulint: disable=obligation-tracking
            return lane
    """
    assert run_fixture(tmp_path, {"m.py": src},
                       checks=["obligation-tracking"]) == []


def test_obligation_package_sites_all_discharged():
    vs = lint_paths(PKG_ROOT, checks=["obligation-tracking"])
    assert vs == [], "\n".join(repr(v) for v in vs)


# ============================================== 20 · protocol-registry
_PROTO_REGISTRY = """
    ABSORB_PART_MOVED = "part-moved"
    ABSORB_DELTA_OVERFLOW = "delta-overflow"
    SHED_QUEUE_FULL = "queue_full"
    DEAD_REASON = "never-emitted"

    PROTOCOL_REASONS = {
        "absorb-decline": (ABSORB_PART_MOVED, ABSORB_DELTA_OVERFLOW),
        "shed": (SHED_QUEUE_FULL,),
        "dead": (DEAD_REASON,),
    }

    TYPED_RAISES = ("AdmissionShed",)

    STATE_MACHINES = {
        "breaker-cell": {
            "module": "storage/device.py",
            "fields": ("state",),
            "writers": ("__init__", "record_failure"),
        },
    }
"""


def test_protocol_fixture_fires_every_leg(tmp_path):
    vs = run_fixture(tmp_path, {
        "common/protocol.py": _PROTO_REGISTRY,
        "storage/device.py": fixture_src("protocol_racy.py"),
    }, checks=["protocol-registry"])
    msgs = [v.message for v in vs]
    assert any("bare literal 'queue_full' at a typed _shed site" in m
               for m in msgs), msgs
    assert any("unknown reason 'weird-reason'" in m for m in msgs), msgs
    assert any("AdmissionShed(...) constructed without a typed reason"
               in m for m in msgs), msgs
    assert any("bare literal 'part-moved' at a typed reason site" in m
               for m in msgs), msgs
    assert any("bare literal 'delta-overflow' duplicates" in m
               for m in msgs), msgs
    assert any("write to breaker-cell state field .state outside" in m
               for m in msgs), msgs
    assert any("'never-emitted' (DEAD_REASON) is registered but never"
               in m for m in msgs), msgs
    assert len(vs) == 7, "\n".join(repr(v) for v in vs)


def test_protocol_constants_everywhere_is_clean(tmp_path):
    sites = """
    class AdmissionShed(Exception):
        pass


    def _shed(key, reason, depth):
        raise AdmissionShed(f"shed ({reason})", reason)


    def admit(key, depth):
        if depth > 10:
            _shed(key, protocol.SHED_QUEUE_FULL, depth)


    def note(space_id):
        journal(reason=protocol.ABSORB_PART_MOVED)


    def count_overflow(reason):
        if reason == protocol.ABSORB_DELTA_OVERFLOW:
            return 1
        return 0


    def legacy():
        return protocol.DEAD_REASON


    class Breaker:
        def __init__(self):
            self.state = "closed"

        def record_failure(self, key, reason):
            self.state = "open"
    """
    assert run_fixture(tmp_path, {
        "common/protocol.py": _PROTO_REGISTRY,
        "storage/device.py": sites,
    }, checks=["protocol-registry"]) == []


def test_protocol_unknown_reason_flagged(tmp_path):
    sites = """
    def _shed(key, reason, depth):
        pass

    def admit(key, depth):
        _shed(key, "mystery", depth)
    """
    vs = run_fixture(tmp_path, {
        "common/protocol.py": _PROTO_REGISTRY,
        "graph/dispatch.py": sites,
    }, checks=["protocol-registry"])
    assert any("unknown reason 'mystery'" in v.message for v in vs), vs


def test_protocol_second_registry_flagged(tmp_path):
    vs = run_fixture(tmp_path, {
        "common/protocol.py": _PROTO_REGISTRY,
        "common/protocol_copy.py": _PROTO_REGISTRY,
    }, checks=["protocol-registry"])
    assert any("second PROTOCOL_REASONS registry" in v.message
               for v in vs), vs


def test_protocol_suppression_roundtrip(tmp_path):
    reg = """
    SHED_QUEUE_FULL = "queue_full"
    PROTOCOL_REASONS = {"shed": (SHED_QUEUE_FULL,)}
    """
    sites = """
    def _shed(key, reason, depth):
        pass

    def admit(key, depth):
        _shed(key, "queue_full", depth)  # nebulint: disable=protocol-registry
    """
    assert run_fixture(tmp_path, {
        "common/protocol.py": reg,
        "graph/dispatch.py": sites,
    }, checks=["protocol-registry"]) == []


def test_protocol_package_vocabulary_closed():
    vs = lint_paths(PKG_ROOT, checks=["protocol-registry"])
    assert vs == [], "\n".join(repr(v) for v in vs)


# ================================================= 21 · mc-coverage (v6)
_MC_PROTO = """
    STATE_MACHINES = {
        "breaker-cell": {
            "module": "storage/device.py",
            "fields": ("state",),
            "writers": ("admit", "record_success"),
        },
    }

    OBLIGATIONS = {
        "probe-token": {
            "acquire": "DeviceCircuitBreaker.admit",
            "discharge": ("release_probe",),
            "quiescence": "no probe token outstanding",
        },
    }
    """

_MC_FULL_COVERS = ("machine:breaker-cell", "obligation:probe-token")


def _mc_scen(covers=(), classes=()):
    """A fake Scenario — mc-coverage only reads .covers/.classes."""
    import types
    return types.SimpleNamespace(covers=tuple(covers),
                                 classes=tuple(classes))


def _mc_lint(tmp_path, files, registry):
    """check_mc_coverage over a fake package with an injected scenario
    registry (the live tools/mc import is exactly what fixtures must
    not depend on)."""
    from nebula_tpu.tools.lint.core import load_package
    from nebula_tpu.tools.lint.mccheck import check_mc_coverage
    root = tmp_path / "pkg"
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    ctx = load_package(str(root), str(tmp_path))
    return check_mc_coverage(ctx, registry=registry)


def test_mc_uncovered_entries_flagged_at_their_key_lines(tmp_path):
    vs = _mc_lint(tmp_path, {"common/protocol.py": _MC_PROTO},
                  registry={})
    assert len(vs) == 2, vs
    machine = next(v for v in vs if v.symbol == "breaker-cell")
    assert "covered by no registered nebulamc scenario" in machine.message
    assert machine.line > 1, "must point at the key, not the file header"
    oblig = next(v for v in vs if v.symbol == "probe-token")
    assert "quiescence property is never asserted" in oblig.message
    assert oblig.line > machine.line


def test_mc_full_coverage_is_clean(tmp_path):
    reg = {"breaker-probe": _mc_scen(covers=_MC_FULL_COVERS)}
    assert _mc_lint(tmp_path, {"common/protocol.py": _MC_PROTO},
                    reg) == []


def test_mc_stale_and_malformed_tags_flagged(tmp_path):
    reg = {"ghost": _mc_scen(
        covers=_MC_FULL_COVERS + ("machine:ghost", "bogus-tag"))}
    vs = _mc_lint(tmp_path, {"common/protocol.py": _MC_PROTO}, reg)
    assert len(vs) == 2, vs
    assert any("stale tag claims coverage" in v.message for v in vs)
    assert any("malformed tag" in v.message for v in vs)
    assert all(v.symbol == "ghost" for v in vs)


_MC_LEDGER = """
    class Ledger:
        def __init__(self):
            self._lock = object()
            self.count = 0          # __init__ precedes concurrency

        def alloc(self):
            with self._lock:
                self.count += 1     # under the lock: schedulable

        def tick(self):
            mc_yield("ledger.tick")
            self.count += 1         # yield point: schedulable

        def evict(self):
            self.count -= 1         # naked: invisible to the scheduler
    """


def test_mc_naked_write_flagged_sync_ops_silence(tmp_path):
    reg = {"churn": _mc_scen(covers=_MC_FULL_COVERS,
                             classes=("pkg.graph.ledger.Ledger",))}
    vs = _mc_lint(tmp_path, {
        "common/protocol.py": _MC_PROTO,
        "graph/ledger.py": _MC_LEDGER,
    }, reg)
    assert len(vs) == 1, vs
    v = vs[0]
    assert v.symbol == "Ledger.evict"
    assert v.path.endswith("graph/ledger.py")
    assert "cannot preempt inside evict()" in v.message
    assert "mc=caller-synced" in v.message


def test_mc_method_waiver_is_not_a_class_waiver(tmp_path):
    """A caller-synced annotation above ONE def silences that method
    only — the next naked method in the same class still fires."""
    src = """
    class Brief:
        # single collector thread owns this mark
        # nebulint: mc=caller-synced/metrics scrape is single-threaded
        def scrape(self):
            self.mark = 1

        def rogue(self):
            self.mark = 2
    """
    reg = {"s": _mc_scen(covers=_MC_FULL_COVERS,
                         classes=("pkg.graph.brief.Brief",))}
    vs = _mc_lint(tmp_path, {
        "common/protocol.py": _MC_PROTO,
        "graph/brief.py": src,
    }, reg)
    assert [v.symbol for v in vs] == ["Brief.rogue"], vs


def test_mc_class_header_waiver_blankets_the_class(tmp_path):
    """The _LaneLedger idiom: the annotation between the docstring and
    the first statement waives every method."""
    src = """
    class Brief:
        '''Caller-sequenced read-side brief.'''
        # nebulint: mc=caller-synced/all writers hold the dispatcher lock

        def scrape(self):
            self.mark = 1

        def rogue(self):
            self.mark = 2
    """
    reg = {"s": _mc_scen(covers=_MC_FULL_COVERS,
                         classes=("pkg.graph.brief.Brief",))}
    assert _mc_lint(tmp_path, {
        "common/protocol.py": _MC_PROTO,
        "graph/brief.py": src,
    }, reg) == []


def test_mc_waiver_inside_a_method_does_not_blanket(tmp_path):
    """An annotation buried in a method BODY is not a class waiver —
    other methods' naked writes still fire."""
    src = """
    class Brief:
        def scrape(self):
            x = 1  # nebulint: mc=caller-synced/only about this line
            self.mark = x

        def rogue(self):
            self.mark = 2
    """
    reg = {"s": _mc_scen(covers=_MC_FULL_COVERS,
                         classes=("pkg.graph.brief.Brief",))}
    vs = _mc_lint(tmp_path, {
        "common/protocol.py": _MC_PROTO,
        "graph/brief.py": src,
    }, reg)
    assert "Brief.rogue" in {v.symbol for v in vs}, vs


def test_mc_missing_class_flagged(tmp_path):
    reg = {"s": _mc_scen(covers=_MC_FULL_COVERS,
                         classes=("pkg.graph.nosuch.Ghost",))}
    vs = _mc_lint(tmp_path, {"common/protocol.py": _MC_PROTO}, reg)
    assert len(vs) == 1
    assert "not in the linted package" in vs[0].message


def test_mc_registry_import_failure_is_one_violation(tmp_path,
                                                     monkeypatch):
    """A broken scenarios.py fails the lint with a pointer, it does
    not crash the whole run."""
    import nebula_tpu.tools.lint.mccheck as mccheck_mod

    def boom():
        raise ImportError("scenario module is on fire")
    monkeypatch.setattr(mccheck_mod, "_scenario_registry", boom)
    vs = _mc_lint(tmp_path, {"common/protocol.py": _MC_PROTO},
                  registry=None)
    assert len(vs) == 1
    assert "cannot import the nebulamc scenario registry" in vs[0].message
    assert "on fire" in vs[0].message


def test_mc_package_coverage_closed():
    """The real gate: every live STATE_MACHINES/OBLIGATIONS entry is
    covered by a registered scenario and every scenario-driven class
    is fully instrumented (or carries a reasoned waiver)."""
    vs = lint_paths(PKG_ROOT, checks=["mc-coverage"])
    assert vs == [], "\n".join(repr(v) for v in vs)
