"""Mean time per batch in the window that `serve.exec` spent on the host,
not blocked on the device: the `serve.exec` spans less the `engine.fetch`
spans inside them, over the window's batches."""
from bench.span_delta import per_batch, total_ms


def read(run):
    fetch = total_ms(run, "engine.fetch")
    exec_ = total_ms(run, "serve.exec")
    if fetch is None or exec_ is None:
        return None
    return per_batch(run, exec_ - fetch)
