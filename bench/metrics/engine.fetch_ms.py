"""Mean time per batch in the window that the facade and the engine spent
blocked on device-to-host reads: the `engine.fetch` spans (route, recheck,
overflow and result reads), over the window's batches."""
from bench.span_delta import per_batch, total_ms


def read(run):
    return per_batch(run, total_ms(run, "engine.fetch"))
