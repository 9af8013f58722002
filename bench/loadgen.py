"""Load drivers: the open-loop schedule, the closed loop, and the knee sweep.

The open-loop schedule and the sweep's keep-up rule are copies of
`repro.serve.loadgen` (`open_loop`, `saturation_search`): request i is due
at `t0 + (ops of all earlier requests) / rate`, requests are dealt
round-robin to the client threads, a thread that falls behind submits
late and the request's latency still counts from when it was due.

Every driver returns a `Window`: each accepted request with its client,
its position in that client's program order, and its latency, so the
metrics are taken over all requests and the checker can replay them.
"""

from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass, field

#: a submit later than this past its schedule counts as late
LATE_S = 1e-3


@dataclass
class Sent:
    """One accepted request as a client saw it."""
    client: int
    seq: int                 # position in the client's program order
    req: object              # traffic.Req
    handle: object           # repro.serve Request (the future)


@dataclass
class Window:
    loop: str
    seconds: float
    t0: float = 0.0
    t_end: float = 0.0       # t0 + seconds
    t_drained: float = 0.0
    sent: list = field(default_factory=list)      # [Sent]
    shed_ops: int = 0
    shed_reqs: int = 0
    late: int = 0
    n_scheduled: int = 0

    def done(self):
        return [s for s in self.sent if s.handle.done
                and s.handle.error is None]

    def latencies_s(self) -> list[float]:
        """End-to-end seconds of every request that completed: from its
        scheduled arrival (open loop) or its submit (closed loop)."""
        return [s.handle.latency_s for s in self.done()]

    def ops_completed_in_window(self) -> int:
        return sum(s.req.n_ops for s in self.done()
                   if s.handle.t_done <= self.t_end)


def _no_note(name: str):
    return contextlib.nullcontext()


def open_loop(frontend, reqs, rate_ops_per_s: float, seconds: float,
              n_clients: int, rejected=Exception, annotate=_no_note,
              timeout_s: float = 120.0) -> Window:
    """Submit `reqs` on the fixed schedule from `n_clients` threads, then
    drain.  `rejected` is the admission-control exception (shed);
    `annotate(name)` wraps each submit and the drain (a profiler
    annotation in traced runs)."""
    win = Window("open", seconds, n_scheduled=len(reqs))
    offsets, acc = [], 0.0
    for r in reqs:
        offsets.append(acc / rate_ops_per_s)
        acc += r.n_ops
    lanes = [[] for _ in range(n_clients)]
    for i, r in enumerate(reqs):
        lanes[i % n_clients].append((r, offsets[i]))
    sent = [[] for _ in range(n_clients)]
    shed = [[0, 0] for _ in range(n_clients)]
    late = [0] * n_clients

    def drive(ci: int) -> None:
        client = frontend.client(f"bench-{ci}")
        for seq, (r, off) in enumerate(lanes[ci]):
            t_due = win.t0 + off
            now = time.perf_counter()
            if t_due > now:
                time.sleep(t_due - now)
            elif now - t_due > LATE_S:
                late[ci] += 1
            try:
                with annotate("bench.client.submit"):
                    h = client.submit(r.op, t_arrival=t_due, **r.payload())
            except rejected:
                shed[ci][0] += r.n_ops
                shed[ci][1] += 1
                continue
            sent[ci].append(Sent(ci, seq, r, h))

    threads = [threading.Thread(target=drive, args=(ci,), daemon=True,
                                name=f"bench-client-{ci}")
               for ci in range(n_clients)]
    win.t0 = time.perf_counter()
    win.t_end = win.t0 + seconds
    for t in threads:
        t.start()
    for t in threads:
        t.join(seconds + timeout_s)
    with annotate("bench.drain"):
        frontend.drain(timeout_s)
    win.t_drained = time.perf_counter()
    win.sent = [s for lane in sent for s in lane]
    win.shed_ops = sum(s[0] for s in shed)
    win.shed_reqs = sum(s[1] for s in shed)
    win.late = sum(late)
    return win


def closed_loop(frontend, traffic, seconds: float, n_clients: int,
                rejected=Exception, annotate=_no_note,
                timeout_s: float = 120.0) -> Window:
    """`n_clients` callers, each submitting its next request when the last
    one returned, until the window closes; then drain."""
    win = Window("closed", seconds)
    sent = [[] for _ in range(n_clients)]
    shed = [[0, 0] for _ in range(n_clients)]

    def call(ci: int) -> None:
        client = frontend.client(f"bench-{ci}")
        rng = traffic.caller_rng(ci)
        seq = 0
        while time.perf_counter() < win.t_end:
            r = traffic.next_request(rng)
            try:
                with annotate("bench.client.submit"):
                    h = client.submit(r.op, **r.payload())
            except rejected:
                shed[ci][0] += r.n_ops
                shed[ci][1] += 1
                continue
            sent[ci].append(Sent(ci, seq, r, h))
            seq += 1
            try:
                h.wait(timeout_s)
            except Exception:       # noqa: BLE001 -- kept on the handle
                pass                # and counted by the checker

    threads = [threading.Thread(target=call, args=(ci,), daemon=True,
                                name=f"bench-caller-{ci}")
               for ci in range(n_clients)]
    win.t0 = time.perf_counter()
    win.t_end = win.t0 + seconds
    for t in threads:
        t.start()
    for t in threads:
        t.join(seconds + timeout_s)
    with annotate("bench.drain"):
        frontend.drain(timeout_s)
    win.t_drained = time.perf_counter()
    win.sent = [s for lane in sent for s in lane]
    win.n_scheduled = len(win.sent)
    win.shed_ops = sum(s[0] for s in shed)
    win.shed_reqs = sum(s[1] for s in shed)
    return win


def kept_up(offered: float, achieved: float, shed_frac: float,
            keep_up_frac: float = 0.9, shed_tol: float = 0.01) -> bool:
    """The sweep's rule (`saturation_search`): a leg keeps up when it
    achieved at least `keep_up_frac` of the offered rate and shed at most
    `shed_tol` of its ops."""
    return achieved >= keep_up_frac * offered and shed_frac <= shed_tol


def sweep_rates(start: float, factor: float, legs: int) -> list[float]:
    """The geometric ramp of offered rates."""
    return [start * factor ** i for i in range(legs)]
