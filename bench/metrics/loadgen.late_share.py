"""Share of open-loop requests submitted more than 1 ms after they were
due: how far the client threads fell behind their schedule."""


def read(run):
    w = run.window
    if w.loop != "open" or not w.n_scheduled:
        return None
    return 100.0 * w.late / w.n_scheduled
