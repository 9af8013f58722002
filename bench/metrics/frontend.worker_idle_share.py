"""Share of the window in which the batcher's worker waited: blocked on an
empty queue (`serve.wait_for_work`) or for a batch to fill (`serve.dwell`),
from the batcher's `stats()` totals of those spans, with the wait in
progress when the window opened cut at its start."""


def read(run):
    b, a, w = run.serve_before, run.serve_after, run.window
    span = w.t_drained - w.t0
    if "worker_idle_s" not in a or span <= 0:
        return None
    idle = a["worker_idle_s"] - b["worker_idle_s"]
    if b["worker_idle_since"] is not None:        # began before the window
        idle -= max(0.0, w.t0 - b["worker_idle_since"])
    if a["worker_idle_since"] is not None:        # began before the drain
        idle += max(0.0, w.t_drained - a["worker_idle_since"])
    return 100.0 * idle / span
