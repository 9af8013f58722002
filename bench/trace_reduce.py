"""Reduction of a JAX profiler trace to the numbers the readers take.

`extract(xplane_path)` keeps what the metrics need from the `.xplane.pb`
file that `jax.profiler` writes, as plain lists (so a recorded extract can
be checked in and reduced again by the tests):

  devices  {plane name: {"ops": [[name, start_ns, dur_ns], ...],
                         "modules": [[name, start_ns, dur_ns], ...]}}
           from each TPU device plane's "XLA Ops" and "XLA Modules" lines
  host     [[name, start_ns, dur_ns], ...] of the benchmark's own
           annotations (names starting "bench.")

Device and host events of one trace share the profiler's clock.  The
traced window is the host annotation "bench.window".
"""

from __future__ import annotations

import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
WINDOW = "bench.window"


def extract(xplane_path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    devices, host = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            d = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is not None:
                    d[key].extend([e.name, float(e.start_ns),
                                   float(e.duration_ns)]
                                  for e in line.events)
            devices[plane.name] = d
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, float(e.start_ns),
                             float(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith("bench."))
    return {"devices": devices, "host": host}


def op_name(event_name: str) -> str:
    """A device op's short name: the HLO instruction's name (what precedes
    " = " in the event's text), with a custom call's target beside it."""
    name = event_name.split(" = ", 1)[0].lstrip("%")
    target = re.search(r'custom_call_target="([^"]+)"', event_name)
    return f"{name} {target.group(1)}" if target else name


def window_ns(ex: dict) -> tuple[float, float]:
    """(start, end) of the traced window on the trace's clock."""
    w = [e for e in ex["host"] if e[0] == WINDOW]
    if not w:
        raise ValueError("trace has no bench.window annotation")
    return w[0][1], w[0][1] + w[0][2]


def merged(intervals) -> np.ndarray:
    """Union of [start, end) intervals as sorted disjoint rows."""
    iv = np.asarray(sorted((s, s + d) for s, d in intervals), np.float64)
    if len(iv) == 0:
        return iv.reshape(0, 2)
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    c = np.clip(iv, lo, hi)
    return c[c[:, 1] > c[:, 0]]


def busy_ns(ex: dict, plane: str, lo: float, hi: float) -> float:
    """Time within [lo, hi) in which some operation ran on the device."""
    ops = ex["devices"][plane]["ops"]
    iv = clip(merged((s, d) for _, s, d in ops), lo, hi)
    return float((iv[:, 1] - iv[:, 0]).sum())


def mean_busy_s(ex: dict) -> tuple[float, float]:
    """(busy seconds averaged over the devices, window seconds)."""
    lo, hi = window_ns(ex)
    planes = sorted(ex["devices"])
    if not planes:
        return 0.0, (hi - lo) * 1e-9
    busy = [busy_ns(ex, p, lo, hi) for p in planes]
    return float(np.mean(busy)) * 1e-9, (hi - lo) * 1e-9


def idle_share(ex: dict) -> float | None:
    """1 - busy / window, in percent, averaged over the devices."""
    busy, win = mean_busy_s(ex)
    if not ex["devices"] or win <= 0:
        return None
    return 100.0 * (1.0 - busy / win)


def _in_window(events, lo, hi):
    return [e for e in events if e[1] >= lo and e[1] < hi]


def time_ns(ex: dict, line: str, match: str) -> float:
    """Summed device duration, over all devices, of the window's events
    on `line` whose name contains `match`: on "ops" the instruction's own
    name (not its operands'), on "modules" the executable's name."""
    lo, hi = window_ns(ex)
    short = op_name if line == "ops" else (lambda n: n)
    return float(sum(d for p in ex["devices"].values()
                     for name, _, d in _in_window(p[line], lo, hi)
                     if match in short(name)))


def top_ops(ex: dict, k: int = 10) -> list:
    """[[op name, seconds], ...]: the k operations (by `op_name`) that
    took most device time in the window, summed over devices."""
    lo, hi = window_ns(ex)
    tot: dict[str, float] = {}
    for p in ex["devices"].values():
        for name, _, d in _in_window(p["ops"], lo, hi):
            tot[op_name(name)] = tot.get(op_name(name), 0.0) + d
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t * 1e-9] for n, t in best]


def idle_gaps(ex: dict, k: int = 10) -> list:
    """[[what the host was doing, seconds], ...]: the k longest idle gaps
    of the first device in the window, each named by the benchmark
    annotation (other than the window itself) that overlaps it most, or
    "unannotated"."""
    lo, hi = window_ns(ex)
    planes = sorted(ex["devices"])
    if not planes:
        return []
    busy = clip(merged((s, d) for _, s, d in
                       ex["devices"][planes[0]]["ops"]), lo, hi)
    edges = np.concatenate([[lo], busy.ravel(), [hi]]).reshape(-1, 2)
    gaps = edges[edges[:, 1] > edges[:, 0]]
    gaps = gaps[np.argsort(gaps[:, 0] - gaps[:, 1])][:k]
    notes = [(n, s, s + d) for n, s, d in ex["host"] if n != WINDOW]
    out = []
    for g0, g1 in gaps:
        best, cover = "unannotated", 0.0
        for n, s, e in notes:
            c = min(e, g1) - max(s, g0)
            if c > cover:
                best, cover = n, c
        out.append([best, float(g1 - g0) * 1e-9])
    return out
