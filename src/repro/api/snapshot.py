"""`DeviceSnapshot`: the typed, self-describing device snapshot.

PR 0-2 passed the flattened index around as a bare ``dict`` of jnp arrays
with `max_depth` smuggled in as an int32 scalar and `has_dense` as a host
bool — every call site had to know which keys were arrays, which were
static, and to thread `max_depth` by hand into anything traced.  This class
replaces that contract: the arrays are pytree children, and the traversal
statics (`max_depth`, `has_dense`, the key dtype) ride along as aux data,
so a snapshot crosses `jit`/`device_put` boundaries intact and the search
entry points (`core.search`) derive their trip counts from it without any
caller-side depth plumbing.

`core.search` accepts a `DeviceSnapshot` anywhere it accepts the raw dict
(duck-typed via `as_dict()`, so `core` never imports `api`).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..core import search as S
from ..core.flat import FlatDILI


@jax.tree_util.register_pytree_node_class
@dataclass
class DeviceSnapshot:
    """Immutable device snapshot of one flattened DILI.

    `arrays` holds every device table as a 1-D column
    (`a/b/base/fo/dense/tag/key/val` and the sorted pair table) plus
    `root`.  `max_depth` / `has_dense` / `dtype` are static
    metadata: they parameterize the compiled search, not its operands.
    """

    arrays: dict
    max_depth: int
    has_dense: bool
    dtype: Any = jnp.float64

    # -- construction --------------------------------------------------------

    @classmethod
    def from_flat(cls, flat: FlatDILI, dtype=jnp.float64,
                  pad: bool = True) -> "DeviceSnapshot":
        """Upload a host `FlatDILI` (pow2-padded by default so republishes
        reuse the compiled executable)."""
        d = S.device_arrays(flat, dtype, pad=pad)
        has_dense = bool(d.pop("has_dense", True))
        max_depth = int(d.pop("max_depth"))
        return cls(arrays=d, max_depth=max_depth, has_dense=has_dense,
                   dtype=dtype)

    # -- interop with the dict-based low-level layer -------------------------

    def as_dict(self) -> dict:
        """The legacy `core.search` dict view (arrays + embedded statics)."""
        return dict(self.arrays, max_depth=self.max_depth,
                    has_dense=self.has_dense)

    # -- introspection -------------------------------------------------------

    @property
    def nbytes(self) -> int:
        return sum(int(np.prod(v.shape)) * v.dtype.itemsize
                   for v in self.arrays.values() if hasattr(v, "dtype"))

    def table_shape(self, name: str) -> tuple:
        return tuple(self.arrays[name].shape)

    def same_shapes(self, other: "DeviceSnapshot | None") -> bool:
        """True when a republish into these shapes would NOT re-trace."""
        if other is None:
            return False
        return (set(self.arrays) == set(other.arrays)
                and all(self.arrays[k].shape == other.arrays[k].shape
                        for k in self.arrays))

    # -- pytree protocol -----------------------------------------------------

    def tree_flatten(self):
        names = tuple(sorted(self.arrays))
        children = tuple(self.arrays[k] for k in names)
        aux = (names, self.max_depth, self.has_dense,
               np.dtype(self.dtype).name)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        names, max_depth, has_dense, dtype_name = aux
        return cls(arrays=dict(zip(names, children)), max_depth=max_depth,
                   has_dense=has_dense, dtype=np.dtype(dtype_name))
