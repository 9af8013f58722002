"""Operations completed within the window, over the window's length."""


def read(run):
    return run.window.ops_completed_in_window() / run.seconds
