"""nebulint — project-invariant static analysis for nebula_tpu.

The reference C++ Nebula leans on compiler enforcement (MUST_USE_RESULT
on Status/StatusOr, clang-tidy, sanitizer builds) plus a Thrift IDL
that makes wire drift a compile error — both lost in a Python
reproduction.  nebulint restores the project-specific part as nineteen
whole-package checks gated as a tier-1 test (tests/test_lint.py):

  lock-discipline   attributes mutated from thread entry points without
                    the owning class's declared lock; blocking calls
                    (RPC, sleep, fsync) made while a lock is held
  lock-order        cycles in the static lock acquisition graph
                    (runtime counterpart: common/ordered_lock.py)
  status-discard    a call whose callee returns Status/StatusOr with the
                    result discarded — the MUST_USE_RESULT analogue
  jax-hotpath       host syncs and jit-cache busters inside the TPU
                    frontier loops (tpu/runtime.py, tpu/kernels.py,
                    graph/executors/)
  flag-registry     flags.get("x") without a define(), and dead defines
  span-registry     tracing.span()/start_trace()/annotate()/emit() names
                    must be literal
                    dotted strings from the single SPAN_NAMES registry
                    (common/tracing.py), with dead entries flagged
  metric-registry   StatsManager names (add_value/observe/set_gauge/
                    register_*) must be literals from the single
                    METRIC_NAMES registry (common/stats.py); entries
                    ending `.*` license f-string families; dead
                    entries flagged
  guard-inference   FLOW: for every lock-declaring class in the
                    concurrency-bearing packages, infer which
                    ``self._x`` attributes the lock guards (strict
                    majority of accesses under ``with self._lock``)
                    and flag unguarded/mixed-lock accesses;
                    ``# nebulint: guarded-by=_lock`` pins the
                    inference (guards.py — the static mini-TSan)
  blocking-under-lock  FLOW: within-module call-graph propagation of
                    blocking effects (RPC dials, sleeps, untimed
                    cond-waits, file I/O, device syncs) into ``with
                    <lock>`` regions — the interprocedural form of
                    the "fan-out under the catalog write lock stalls
                    heartbeats" bug class (blocking.py)
  context-capture   FLOW: pool/Thread submissions from Deadline- or
                    trace-bound code must capture-and-rebind both
                    (tracing.capture/attach_captured +
                    deadlines.bind); thread-local deadline consults
                    inside pool workers outside any bind scope are
                    flagged too (capture.py)
  jaxpr-audit       SEMANTIC: traces every registered kernel factory
                    (tpu/kernels.py KERNEL_REGISTRY) across the
                    runtime's real shape buckets and proves, on the
                    jaxpr: no host callbacks in loop bodies, no 64-bit
                    promotion of persistent buffers, donation where
                    claimed, a bounded recompile-key space, transfer
                    counts matching runtime.DEVICE_PHASES, and — new
                    in v3 — per-rung peak resident bytes within the
                    declared per-device HBM budget plus the
                    edge-ceiling arithmetic (runtime.HBM_MODEL)
  mesh-audit        SEMANTIC (v4): re-traces every sharded kernel
                    family under REAL 2/4/8-way meshes and proves the
                    declared COLLECTIVE_MODEL on the IR — exact
                    collective inventory (psum/all_gather/all_to_all/
                    ppermute + sharding_constraint re-replication,
                    axes included), no closure-captured device
                    buffers, per-dispatch ICI exchange bytes within
                    the declared ici_bytes bound, bit-packed frontier
                    layout across shard boundaries, donation through
                    shard_map, per-shard HBM residency per mesh size,
                    and the MESH_MODEL multi-chip capacity table
                    arithmetic (meshaudit.py)
  carveout-inventory  AST (v4): every CPU-decline site in
                    tpu/runtime.py (TpuDecline raises, can_run_*
                    gates) must carry a '# nebulint: carveout=<reason>'
                    tag from the closed MESH_CARVEOUTS registry;
                    untagged sites, unknown reasons and dead registry
                    entries are flagged — the mesh carve-out list is
                    enumerable and baselined (meshaudit.py)
  wire-contract     SEMANTIC: cross-checks every RPC client call site
                    against the rpc_* handlers (orphan methods and
                    handlers, request-key drift, response-envelope
                    drift, the transport frame contract, the
                    /get_stats//traces//faults endpoint payloads) —
                    the Thrift-IDL guarantee, restored mechanically
  event-registry    EventJournal.record() kinds must be literals from
                    the single EVENT_KINDS registry (common/events.py);
                    dead kinds flagged
  obligation-tracking  FLOW (v5): acquire/discharge pairs declared in
                    common/protocol.py OBLIGATIONS (lane seats, probe
                    tokens, pipeline slots, waiter-heap entries, busy-
                    meter marks, rebuild markers) discharged on every
                    path, including exceptional ones (obligations.py)
  protocol-registry  the typed-reason vocabulary is closed and
                    STATE_MACHINES fields move only inside their
                    declared transition methods (protocol.py)
  mc-coverage       v6: the protocol registries and the nebulamc
                    scenario registry (tools/mc/scenarios.py) move
                    together — every STATE_MACHINES / OBLIGATIONS
                    entry covered by >=1 registered scenario, no stale
                    covers tags, and every scenario-driven class free
                    of shared-state writes the scheduler cannot
                    preempt ('# nebulint: mc=caller-synced/<reason>'
                    waives caller-sequenced classes) (mccheck.py)

  stale-suppression META: a ``# nebulint: disable=`` comment whose
                    check ran but suppressed nothing at that site is
                    itself flagged (core.py) — fossils must not swallow
                    the NEXT violation landing on their line; the CLI
                    treats unused baseline.json entries the same way

Suppression: ``# nebulint: disable=<check>[,<check>]`` on the flagged
line (or the line above), ``# nebulint: disable-file=<check>`` anywhere
in a file, or an entry in baseline.json (every baseline entry must carry
a one-line justification).  See docs/static_analysis.md.
"""
from .core import (ALL_CHECKS, Baseline, LintError, Violation, lint_paths,
                   run_lint)

__all__ = ["ALL_CHECKS", "Baseline", "LintError", "Violation",
           "lint_paths", "run_lint"]
