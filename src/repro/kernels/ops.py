"""jit'd public wrapper for the DILI search kernel.

Dispatch policy:
  * tables fit the VMEM budget -> Pallas kernel (compiled on a TPU,
    interpreted elsewhere), with an XLA fallback pass for lanes flagged
    needs_fallback (depth overflow);
  * otherwise -> the pure-XLA batched path (core/search.py), which keeps
    tables in HBM and lets XLA schedule the gathers.

A caller that passes a `routes` dict gets the route of each batch
counted into it, so the size-based dispatch is never invisible; one that
passes its `telemetry` gets the launches and the route read as
`engine.launch` / `engine.fetch` spans.

Keys are f32 on this path; the snapshot must have been built under
``placement_dtype(np.float32)`` so construction and kernel arithmetic agree
(see core/dili.py).  build_f32_index() below does exactly that.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ..core import search as core_search
from ..core.dili import bulk_load, placement_dtype
from ..core.flat import FlatDILI, flatten
from ..obs.telemetry import NULL_TELEMETRY
from .dili_search import BLOCK_Q, dili_search_pallas

VMEM_BUDGET_BYTES = 12 * 1024 * 1024


def build_f32_index(keys: np.ndarray, vals: np.ndarray | None = None, **kw):
    """Bulk-load a DILI whose placement arithmetic is exactly float32."""
    keys32 = np.unique(np.asarray(keys, np.float64).astype(np.float32))
    if vals is None:
        vals = np.arange(len(keys32), dtype=np.int64)
    with placement_dtype(np.float32):
        d = bulk_load(keys32.astype(np.float64), vals, **kw)
    return d, keys32


def kernel_arrays(flat: FlatDILI) -> dict:
    """Device arrays in kernel dtypes (f32 keys/models, i32 the rest)."""
    return dict(
        a=jnp.asarray(flat.a, jnp.float32),
        b=jnp.asarray(flat.b, jnp.float32),
        base=jnp.asarray(flat.base, jnp.int32),
        fo=jnp.asarray(flat.fo, jnp.int32),
        dense=jnp.asarray(flat.dense.astype(np.int32)),
        tag=jnp.asarray(flat.tag.astype(np.int32)),
        key=jnp.asarray(flat.key, jnp.float32),
        val=jnp.asarray(flat.val, jnp.int32),
        root=jnp.asarray([flat.root], jnp.int32),
        max_depth=flat.max_depth,
    )


def table_bytes(arrs: dict) -> int:
    return sum(int(np.prod(v.shape)) * v.dtype.itemsize
               for k, v in arrs.items() if hasattr(v, "dtype"))


#: the three routes a batch can take
ROUTE_KERNEL, ROUTE_KERNEL_RECHECK, ROUTE_XLA = (
    "kernel", "kernel+xla_recheck", "xla")


def dili_search(arrs: dict, queries: jnp.ndarray,
                interpret: bool | None = None,
                vmem_budget: int | None = None,
                routes: dict | None = None, telemetry=NULL_TELEMETRY):
    """Batched lookup via the Pallas kernel with XLA fallback lanes.

    `vmem_budget` overrides the module-level `VMEM_BUDGET_BYTES` dispatch
    ceiling (the `IndexConfig.vmem_budget_bytes` knob of the api facade);
    tables above it take the pure-XLA path outright.  `interpret=None`
    follows the backend (`dili_search.resolve_interpret`).  When `routes`
    is given, the route this batch took is counted into it: the kernel
    alone, the kernel plus an XLA recheck of flagged lanes, or XLA only.
    """
    tel = telemetry
    max_depth = int(arrs["max_depth"])
    nq = queries.shape[0]
    pad = (-nq) % BLOCK_Q
    budget = VMEM_BUDGET_BYTES if vmem_budget is None else vmem_budget
    if table_bytes(arrs) <= budget:
        with tel.span("engine.launch"):
            qp = jnp.pad(queries, (0, pad), constant_values=jnp.inf)
            out, found, fb = dili_search_pallas(
                arrs["a"], arrs["b"], arrs["base"], arrs["fo"],
                arrs["dense"], arrs["tag"], arrs["key"], arrs["val"],
                arrs["root"], qp, max_depth=max_depth, interpret=interpret)
            any_fb = jnp.any(fb)
        with tel.fetch("route"):
            recheck = bool(any_fb)
        _count(routes, ROUTE_KERNEL_RECHECK if recheck else ROUTE_KERNEL)
        with tel.span("engine.launch"):
            if recheck:
                # rare path: depth overflow — recheck those lanes in XLA
                idx = _as_search_idx(arrs)
                v2, f2 = core_search.search_batch(idx, qp,
                                                  max_depth=max_depth)
                out = jnp.where(fb, v2, out)
                found = jnp.where(fb, f2, found)
            return out[:nq], found[:nq]

    _count(routes, ROUTE_XLA)
    with tel.span("engine.launch"):
        qp = jnp.pad(queries, (0, pad), constant_values=jnp.inf)
        idx = _as_search_idx(arrs)
        v, f = core_search.search_batch(idx, qp, max_depth=max_depth,
                                        early_exit=True)
        return v[:nq], f[:nq]


def _count(routes: dict | None, route: str) -> None:
    if routes is not None:
        routes[route] = routes.get(route, 0) + 1


def _as_search_idx(arrs: dict) -> dict:
    return dict(a=arrs["a"], b=arrs["b"], base=arrs["base"], fo=arrs["fo"],
                dense=arrs["dense"].astype(jnp.int8),
                tag=arrs["tag"].astype(jnp.int8), key=arrs["key"],
                val=arrs["val"], root=arrs["root"][0],
                max_depth=arrs["max_depth"])
