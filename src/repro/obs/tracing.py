"""Trace spans over the merge pipeline (DESIGN.md section 13).

A span is one timed stage of a pipeline run: `(name, t0, dur_s, attrs)`.
The recorder keeps a bounded ring of recent spans (for debugging "what did
the last merge do") plus running per-name duration lists (for percentile
export), and is safe for the one-writer-plus-maintenance-worker threading
model the merge pipeline already guarantees: each span is recorded by
whichever single thread ran that stage, and list.append is atomic.

The merge span taxonomy is fixed (`MERGE_SPANS`) so every engine exports
the same span names:

  merge.queue_wait   — submit -> worker pickup (background scheduler only)
  merge.fold         — overlay fold through the host tree (Alg. 7/8)
  merge.retrain      — drift/tombstone-triggered subtree rebuilds
  merge.recluster    — heat-triggered locality splits of hot leaf segments
  merge.flatten      — full or incremental-splice flatten
  merge.publish      — device upload + epoch flip
  merge.frozen_dwell — overlay freeze -> frozen drop (reads resolve the
                       frozen overlay for this long; background only)
  merge.failed       — one failed merge attempt (duration = time spent in
                       the pipeline before it died; see the bounded-retry
                       loop in `online.merge`)

Engines that run a stage synchronously inside another (e.g. the sharded
engine's per-shard fold) record one span per shard with a `shard` attr.

`RECOVERY_SPANS` is the crash-recovery taxonomy (DESIGN.md section 14):
load (checkpoint walk + npz read), replay (WAL tail through the fold
path), publish (fresh base checkpoint + WAL re-arm).  Recovery spans are
recorded unconditionally — bypassing the telemetry `enabled` gate —
because recovery is rare and always worth seeing.

A context span (`SpanRecorder.span`, reached through an enabled
`Telemetry.span`) also opens a `jax.profiler.TraceAnnotation` of the same
name, so a `jax.profiler` trace shows it on the host plane, on the clock
the device's events use.  Retroactive spans (`record`) are not annotated.
"""

from __future__ import annotations

import time
from collections import deque
from typing import NamedTuple

from .metrics import latency_summary

MERGE_SPANS = ("merge.queue_wait", "merge.fold", "merge.retrain",
               "merge.recluster", "merge.flatten", "merge.publish",
               "merge.frozen_dwell", "merge.failed")

RECOVERY_SPANS = ("recovery.load", "recovery.replay", "recovery.publish")

# Serving front-end taxonomy (DESIGN.md section 15).  NOT part of the
# default declaration: a bare index exports exactly the merge + recovery
# span set (pinned by the telemetry schema tests); the serve spans join a
# Telemetry bundle only when a `RequestBatcher` attaches to the index,
# via `SpanRecorder.declare`.
#
#   serve.queue_wait — head request's submit -> worker dispatch (the
#                      admission-queue delay component of e2e latency)
#   serve.exec       — one coalesced facade batch, dispatch -> results
#                      sliced back to clients (attr `op`)
#   serve.wait_for_work — the worker blocked on an empty queue
#   serve.dwell      — the worker's bounded wait for a batch to fill
#   serve.dispatch   — the worker's turn for one batch: serve.exec and the
#                      per-request accounting after it, so that the worker
#                      is always inside a wait or a dispatch
#   serve.complete   — journal, result slicing and waking the clients
#                      (inside serve.exec)
SERVE_SPANS = ("serve.queue_wait", "serve.exec", "serve.wait_for_work",
               "serve.dwell", "serve.dispatch", "serve.complete")

# One Python garbage collection (attr `generation`), recorded from
# `gc.callbacks` while a batcher with enabled telemetry is attached
# (`Telemetry.watch_gc`): every thread stops for it.
GC_SPAN = "host.gc"

# Engine taxonomy: the stages of one facade call, declared on every engine
# so the key tree is identical across engines.
#
#   engine.prep   — the facade's asarray, finiteness check and pow2 pad
#   engine.upload — host-to-device copy of the queries, plus the overlay
#                   mirror rebuild (attr `overlay=1` when it ran)
#   engine.launch — calling a jitted executable or the kernel, until
#                   control returns (dispatch is asynchronous)
#   engine.fetch  — one blocking device-to-host read (attr `what`: route,
#                   recheck, overflow or result); each one also counts
#                   `engine.host_syncs`
#   engine.write  — the host-side overlay apply of an upsert or delete
ENGINE_SPANS = ("engine.prep", "engine.upload", "engine.launch",
                "engine.fetch", "engine.write")

_annotation = None


def trace_annotation(name: str, **attrs):
    """A `jax.profiler.TraceAnnotation` to enter, or None while no
    profiler trace is being taken (jax is imported on first use, so the
    module stays importable without it)."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation(name, **attrs) if _annotation.is_enabled() else None


class Span(NamedTuple):
    name: str
    t0: float                  # perf_counter timestamp at stage start
    dur_s: float
    attrs: dict


class _OpenSpan:
    """One context span in flight: timed, recorded on exit, and annotated
    on the profiler trace when one is being taken."""

    __slots__ = ("rec", "name", "attrs", "note", "t0")

    def __init__(self, rec: "SpanRecorder", name: str, attrs: dict):
        self.rec, self.name, self.attrs = rec, name, attrs

    def __enter__(self):
        self.note = trace_annotation(self.name, **self.attrs)
        if self.note is not None:
            self.note.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t0 = self.t0
        self.rec._record(self.name, time.perf_counter() - t0, t0, self.attrs)
        if self.note is not None:
            self.note.__exit__(*exc)
        return False


class SpanRecorder:
    """Bounded span ring + per-name duration accumulators."""

    def __init__(self, maxlen: int = 2048,
                 declare: tuple[str, ...] = MERGE_SPANS + RECOVERY_SPANS):
        self.ring: deque[tuple] = deque(maxlen=maxlen)
        self._durations: dict[str, list[float]] = {n: [] for n in declare}
        # optional causal-trace tap: when set (see Telemetry.start_trace)
        # every recorded span is also forwarded as
        # `sink(name, t0, dur_s, attrs)` — the TraceBuffer adapter
        self.sink = None

    def record(self, name: str, dur_s: float, t0: float | None = None,
               **attrs) -> None:
        if t0 is None:
            t0 = time.perf_counter() - dur_s
        self._record(name, dur_s, t0, attrs)

    def _record(self, name: str, dur_s: float, t0: float,
                attrs: dict) -> None:
        self.ring.append((name, t0, dur_s, attrs))   # a Span's fields
        durs = self._durations.get(name)
        if durs is None:
            durs = self._durations.setdefault(name, [])
        durs.append(dur_s)
        if self.sink is not None:
            self.sink(name, t0, dur_s, attrs)

    def span(self, name: str, **attrs) -> _OpenSpan:
        """Context manager timing (and annotating) one stage."""
        return _OpenSpan(self, name, attrs)

    def declare(self, *names: str) -> None:
        """Add span names to the exported taxonomy (zero-count until
        recorded).  Late opt-in for subsystems that aren't part of every
        index — e.g. the serving front-end declares `SERVE_SPANS` on
        attach, so only served indexes export them."""
        for name in names:
            self._durations.setdefault(name, [])

    def spans(self, name: str | None = None) -> list[Span]:
        return [Span(*s) for s in list(self.ring)
                if name is None or s[0] == name]

    def count(self, name: str) -> int:
        return len(self._durations.get(name, ()))

    def summary(self) -> dict:
        """{span name: shared percentile summary} over every declared or
        recorded span name — JSON-able, stable key set per taxonomy.

        Safe to call while another thread records: the name dict and each
        duration list are snapshotted atomically (`dict()`/`list()` are
        single bytecodes over the live object), so a concurrent append
        lands in this summary or the next, never in a RuntimeError."""
        return {name: latency_summary(list(durs))
                for name, durs in sorted(dict(self._durations).items())}
