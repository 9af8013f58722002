"""Device memory per loaded key: the fullest chip's bytes in use once the
window has drained and maintenance is idle, over the keys loaded.  Read
after the window, so a snapshot a set-up merge held for a while does not
count; the process's peak is `device.memory_peak_bytes`."""


def read(run):
    if run.live_bytes is None:
        return None
    return run.live_bytes / run.n_keys
