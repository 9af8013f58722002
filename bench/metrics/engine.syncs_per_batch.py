"""Blocking device-to-host reads per batch in the window: the count of
`engine.fetch` spans, each of which also counts `engine.host_syncs`, over
the window's batches."""
from bench.span_delta import count, per_batch


def read(run):
    return per_batch(run, count(run, "engine.fetch"))
