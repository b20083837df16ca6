"""ExecutionContext — per-query resources (reference ExecutionContext.h)."""
from __future__ import annotations

from typing import Optional

from ..meta.client import MetaClient
from ..meta.schema_manager import SchemaManager
from ..storage.client import StorageClient
from .interim import InterimResult, VariableHolder


class ClientSession:
    """Session state (reference ClientSession.h): current space + user."""

    def __init__(self, session_id: int, user: str = ""):
        self.session_id = session_id
        self.user = user
        self.space_name = ""
        self.space_id = -1
        import time
        self._last_access = time.time()

    def charge(self) -> None:
        import time
        self._last_access = time.time()

    def idle_seconds(self) -> float:
        import time
        return time.time() - self._last_access


class ExecutionContext:
    def __init__(self, session: ClientSession, meta: MetaClient,
                 schema_man: SchemaManager, storage: StorageClient,
                 tpu_runtime=None, router=None):
        self.session = session
        self.meta = meta
        self.schema_man = schema_man
        self.storage = storage
        self.variables = VariableHolder()
        # set by Pipe: the left-hand result available as $- to the right
        self.input: Optional[InterimResult] = None
        # partial-result accounting: executors that accept a degraded
        # scatter-gather response (some parts failed, completeness
        # 0 < % < 100) record it here instead of silently returning a
        # subset — ExecutionEngine surfaces it on the client response
        self.completeness: int = 100
        self.warnings: list = []
        # TPU query runtime (tpu/runtime.py) — executors prefer it when the
        # current space has a device CSR mirror and the flag allows
        self.tpu_runtime = tpu_runtime
        # adaptive device-vs-CPU router (graph/backend_router.py),
        # engine-scoped so estimates persist across queries
        self.router = router
        # pipe-reduction hint (traverse.PipeExecutor → GoExecutor):
        # ("limit", n) / ("count",) / ("count_distinct",) when the
        # enclosing pipe can consume a device-reduced GO result
        # (LIMIT/COUNT pushdown — fetch returns only surviving/reduced
        # rows, docs/roofline.md); where none is set the GO executor
        # sets ("distinct",) itself for the one DISTINCT the device
        # answers (traverse._go_distinct_dst)
        self.go_reduce = None

    def note_partial(self, resp) -> None:
        """Record a degraded StorageRpcResponse (reference
        GoExecutor.cpp:356-366 tolerates completeness < 100; we also
        report it instead of silently dropping the failed parts)."""
        pct = resp.completeness()
        self.completeness = min(self.completeness, pct)
        first = next(iter(resp.failed_parts.values()))
        self.warnings.append(
            f"partial result: {len(resp.failed_parts)}/{resp.total_parts} "
            f"storage parts failed ({first.to_string()})")

    def space_id(self) -> int:
        return self.session.space_id

    def space_chosen(self) -> bool:
        return self.session.space_id >= 0
