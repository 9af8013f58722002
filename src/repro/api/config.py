"""`IndexConfig`: one declarative knob set for every engine.

Engine choice (`local` / `pallas` / `sharded`), key dtype, snapshot
padding, merge policy, overlay sizing, shard layout, and the Pallas kernel
budget all live here, so swapping engines is a config edit — not a code
path — and every facade method reads the same object instead of threading
six keyword arguments.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any

import jax.numpy as jnp
import numpy as np

from ..durability.config import DurabilityConfig
from ..maintain import MaintenanceConfig
from ..online.merge import MergePolicy

ENGINES = ("local", "pallas", "sharded")


def manual_merge_policy() -> MergePolicy:
    """A policy that never auto-merges: writes stay in the overlay until an
    explicit `flush()` (the overlay still doubles, so `full_fraction` can
    never reach the disabled triggers)."""
    return MergePolicy(max_fill=1.1, max_writes=1 << 62,
                       pressure_check_every=1 << 62)


@dataclass(frozen=True)
class IndexConfig:
    """Configuration for `repro.api.LearnedIndex`.

    engine            : "local" (XLA fused snapshot+overlay), "pallas"
                        (VMEM kernel dispatch with XLA fallback, f32 keys),
                        or "sharded" (mesh + per-shard overlays).
    dtype             : key/model dtype; None picks the engine default
                        (f64 for local/sharded, f32 for pallas).  A
                        64-bit dtype needs `jax_enable_x64`; building
                        without it raises.
    pad               : pow2-pad device tables so republishes reuse the
                        compiled search executable.
    merge             : `repro.online.MergePolicy` deciding when pending
                        writes fold through the host tree (Alg. 7/8).
    maintenance       : `repro.maintain.MaintenanceConfig` switching the
                        merge to the adaptive pipeline — incremental
                        splice-flatten, drift-triggered subtree retrains,
                        and (local engine only) background merges.  None =
                        legacy monolithic full-flatten merges.
    overlay_cap       : initial tombstone-overlay capacity (doubles).
    sample_stride     : bulk-load sampling stride (Alg. 4, Table 13).
    bulk_kw           : extra `core.dili.bulk_load` kwargs (cost model,
                        lambda, local_optimized, ...).
    n_shards          : sharded engine only; None = all visible devices.
    mesh_axis         : mesh axis name for the sharded engine.
    lookup_strategy   : sharded lookup collective: "gather" (exact) or
                        "a2a" (capacity-bounded buckets).
    interpret         : Pallas interpret mode; None = follow the backend
                        (compiled on a TPU, interpreted elsewhere); True
                        on a TPU raises.
    vmem_budget_bytes : table-size ceiling for the kernel path; bigger
                        snapshots dispatch to the XLA fallback.
    early_exit        : batch-convergence early exit (local engine; the
                        sharded engine always runs the fixed-trip scan,
                        since shard_map's varying-axes check refuses the
                        early-exit while_loop's carry, and the kernel walks
                        each query only until it resolves).
    max_hits          : default per-query range-window bound.
    telemetry         : enable per-op latency histograms + merge-pipeline
                        trace spans (`repro.obs`, DESIGN.md section 13).
                        Off by default: the hot path then pays one flag
                        check per facade call; retrace accounting stays
                        live either way (it rides jax's compile hooks).
    durability        : `repro.durability.DurabilityConfig` arming the
                        write-ahead log + checkpoint subsystem (DESIGN.md
                        section 14): upserts/deletes append to a per-shard
                        WAL before being acknowledged, merge publishes
                        checkpoint + truncate it, `LearnedIndex.recover`
                        replays the tail after a crash.  None (default) =
                        in-memory only, no durability I/O.

    `pad` applies to the local/pallas snapshots; the sharded engine's
    stacked per-shard tables are always pow2-padded (republish without
    re-trace is structural there).
    """

    engine: str = "local"
    dtype: Any = None
    pad: bool = True
    merge: MergePolicy = field(default_factory=MergePolicy)
    maintenance: MaintenanceConfig | None = None
    overlay_cap: int = 4096
    sample_stride: int = 1
    bulk_kw: tuple = ()                      # (("lam", 4.0), ...) — hashable
    n_shards: int | None = None
    mesh_axis: str = "data"
    lookup_strategy: str = "gather"
    interpret: bool | None = None
    vmem_budget_bytes: int = 12 * 1024 * 1024
    early_exit: bool = True
    max_hits: int = 128
    telemetry: bool = False
    durability: DurabilityConfig | None = None

    def __post_init__(self):
        if self.engine not in ENGINES:
            raise ValueError(f"unknown engine {self.engine!r}; "
                             f"expected one of {ENGINES}")
        if self.lookup_strategy not in ("gather", "a2a"):
            raise ValueError(f"unknown lookup_strategy "
                             f"{self.lookup_strategy!r}")

    @property
    def resolved_dtype(self):
        if self.dtype is not None:
            return self.dtype
        return jnp.float32 if self.engine == "pallas" else jnp.float64

    def bulk_load_kw(self) -> dict:
        return dict(self.bulk_kw, sample_stride=self.sample_stride)

    def with_engine(self, engine: str) -> "IndexConfig":
        return replace(self, engine=engine)

    # -- (de)serialization for LearnedIndex.save/load ------------------------

    def to_json_dict(self) -> dict:
        return dict(
            engine=self.engine,
            dtype=(None if self.dtype is None
                   else np.dtype(self.dtype).name),
            pad=self.pad,
            merge=dict(max_fill=self.merge.max_fill,
                       max_writes=self.merge.max_writes,
                       pressure_lambda=self.merge.pressure_lambda,
                       pressure_check_every=self.merge.pressure_check_every,
                       pressure_min_pending=self.merge.pressure_min_pending),
            maintenance=(None if self.maintenance is None
                         else self.maintenance.to_json_dict()),
            overlay_cap=self.overlay_cap,
            sample_stride=self.sample_stride,
            bulk_kw=list(list(kv) for kv in self.bulk_kw),
            n_shards=self.n_shards,
            mesh_axis=self.mesh_axis,
            lookup_strategy=self.lookup_strategy,
            interpret=self.interpret,
            vmem_budget_bytes=self.vmem_budget_bytes,
            early_exit=self.early_exit,
            max_hits=self.max_hits,
            telemetry=self.telemetry,
            durability=(None if self.durability is None
                        else self.durability.to_json_dict()),
        )

    @classmethod
    def from_json_dict(cls, d: dict) -> "IndexConfig":
        d = dict(d)
        merge = MergePolicy(**d.pop("merge"))
        maint = d.pop("maintenance", None)
        if maint is not None:
            maint = MaintenanceConfig.from_json_dict(maint)
        dur = d.pop("durability", None)
        if dur is not None:
            dur = DurabilityConfig.from_json_dict(dur)
        dtype = d.pop("dtype")
        bulk_kw = tuple(tuple(kv) for kv in d.pop("bulk_kw", []))
        return cls(merge=merge, maintenance=maint, durability=dur,
                   bulk_kw=bulk_kw,
                   dtype=None if dtype is None else np.dtype(dtype), **d)
