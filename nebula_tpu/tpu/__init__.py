"""TPU traversal backend — the device-resident storage mirror and query
kernels (the project's north star, BASELINE.json).

The reference executes multi-hop GO as one RPC round trip per hop with
host-side set dedup (GoExecutor.cpp:377-431, QueryBaseProcessor.inl
prefix scans).  Here the hop loop runs on-device: each graph space's
edge partitions are folded into a host CSR mirror (csr.py) and from it
into HBM-resident ELL tables (ell.py), frontier expansion is a jitted
batched program over those tables — optionally sharded over a
jax.sharding.Mesh — and the pushed filter expression tree is compiled
to vectorized numpy ops (expr_compile.py) that meet the final
frontier's candidate edges on the host.  TpuQueryRuntime (runtime.py)
plugs into the graphd executor seam (graph/executors/traverse.py).
"""
from .runtime import TpuQueryRuntime

__all__ = ["TpuQueryRuntime"]
