"""Share of the window the host spent in Python's garbage collector (all
generations, any thread): every thread of the process, the batcher and
the client threads among them, stops for it."""


def read(run):
    w = run.window
    span = w.t_drained - w.t0
    if not run.trace or span <= 0:
        return None
    inside = sum(min(e, w.t_drained) - max(s, w.t0)
                 for s, e, _ in run.gc_pauses if e > w.t0 and s < w.t_drained)
    return 100.0 * inside / span
