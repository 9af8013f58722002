"""The program's own spans on a profiler trace, and the device's idle time
named by them.

With telemetry on, every context span of the program (`serve.*`,
`engine.*`, `merge.*`, `host.gc`) is also a `jax.profiler.TraceAnnotation`,
so it lands in the trace's host plane on the clock of the device's events.
`extract_spans(xplane_path)` keeps them as plain lists,
`[[name, start_ns, dur_ns, thread], ...]`, one `thread` per host line.  A
`trace_reduce.extract` dict that carries them under the key `spans` is
reduced by:

  idle_gaps(ex)          the longest idle gaps of the first device, each
                         named by what the host was doing in it
  idle_share_under(ex)   the share of the device's idle time under given
                         spans or threads
"""

from __future__ import annotations

import numpy as np

from . import trace_reduce as tr

PREFIXES = ("serve.", "engine.", "merge.", "recovery.", "host.")
GC, EXEC, MERGE = "host.gc", "serve.exec", "merge."


def extract_spans(xplane_path: str) -> list:
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            out.extend([e.name, float(e.start_ns), float(e.duration_ns),
                        f"{plane.name}#{i}"]
                       for e in line.events if e.name.startswith(PREFIXES))
    return out


def idle_intervals(ex: dict) -> np.ndarray:
    """[start, end) rows in which the first device ran nothing, inside
    the traced window."""
    lo, hi = tr.window_ns(ex)
    planes = sorted(ex["devices"])
    if not planes:
        return np.asarray([[lo, hi]])
    busy = tr.clip(tr.merged((s, d) for _, s, d in
                             ex["devices"][planes[0]]["ops"]), lo, hi)
    edges = np.concatenate([[lo], busy.ravel(), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def self_time(spans) -> list:
    """[(name, start, end, thread), ...]: the stretches in which each span
    was the innermost open span of its thread (its self time: the span
    less its children).  Spans of one thread nest, being context
    managers, so one sweep with a stack finds them."""
    by_thread: dict = {}
    for name, s, d, th in spans:
        by_thread.setdefault(th, []).append((s, s + d, name))
    out = []
    for th, evs in by_thread.items():
        evs.sort(key=lambda e: (e[0], -e[1]))     # outer span first
        stack, t = [], 0.0                        # open (end, name)s
        for s, e, name in evs + [(np.inf, np.inf, None)]:
            while stack and stack[-1][0] <= s:    # close what ended
                end, top = stack.pop()
                if end > t:
                    out.append((top, t, end, th))
                t = max(t, end)
            if stack and s > t and name is not None:
                out.append((stack[-1][1], t, s, th))
            stack.append((e, name))
            t = s
    return out


def _overlap(iv, g0: float, g1: float) -> float:
    return sum(max(0.0, min(e, g1) - max(s, g0)) for s, e in iv)


def _bench_name(ex: dict, g0: float, g1: float) -> str:
    """The benchmark annotation over most of the gap (the rule of
    `trace_reduce.idle_gaps`)."""
    best, cover = "unannotated", 0.0
    for n, s, d in ex["host"]:
        c = min(s + d, g1) - max(s, g0)
        if n != tr.WINDOW and c > cover:
            best, cover = n, c
    return best


def worker_threads(ex: dict) -> set:
    """Threads that ran the batcher's dispatch (`serve.exec`)."""
    return {th for n, _, _, th in ex.get("spans", []) if n == EXEC}


def idle_gaps(ex: dict, k: int = 10) -> list:
    """[[what the host was doing, seconds], ...] for the k longest idle
    gaps of the first device.  A gap is named by the span whose self time
    overlaps it most, leaving out merge stages on threads other than the
    batcher's, except that `host.gc` wins wherever collections cover at
    least half of it (every thread stops for them).  A merge stage
    overlapping the gap on another thread is appended after "+": it
    contends for the interpreter lock.  Where no span is over the gap,
    the benchmark annotation over it names it."""
    gaps = idle_intervals(ex)
    gaps = gaps[np.argsort(gaps[:, 0] - gaps[:, 1])][:k]
    spans = ex.get("spans", [])
    workers = worker_threads(ex)
    segs = self_time(spans)
    gc_iv = [(s, s + d) for n, s, d, _ in spans if n == GC]
    out = []
    for g0, g1 in gaps:
        own: dict = {}
        beside: dict = {}
        for name, s, e, th in segs:
            c = min(e, g1) - max(s, g0)
            if c > 0:
                d = (beside if name.startswith(MERGE) and th not in workers
                     else own)
                d[name] = d.get(name, 0.0) + c
        if gc_iv and _overlap(gc_iv, g0, g1) >= 0.5 * (g1 - g0):
            name = GC
        elif own:
            name = max(own, key=own.get)
        else:
            name = _bench_name(ex, g0, g1)
        if beside:
            name += "+" + max(beside, key=beside.get)
        out.append([name, float(g1 - g0) * 1e-9])
    return out


def idle_share_under(ex: dict, names=(), threads=()) -> float | None:
    """Percent of the first device's idle time in the window that lies
    under the union of the spans named in `names` (on any thread) and of
    every span on `threads`."""
    gaps = idle_intervals(ex)
    idle = _measure(gaps)
    if idle <= 0:
        return None
    iv = tr.clip(tr.merged((s, d) for n, s, d, th in ex.get("spans", [])
                           if n in names or th in threads),
                 *tr.window_ns(ex))
    both = tr.merged((s, e - s) for s, e in np.concatenate([gaps, iv]))
    # |idle & spans| = |idle| + |spans| - |idle | spans|
    return 100.0 * (idle + _measure(iv) - _measure(both)) / idle


def _measure(iv: np.ndarray) -> float:
    return float((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0.0
