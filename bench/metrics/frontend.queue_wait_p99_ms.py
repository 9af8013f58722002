"""99th percentile of the batcher's `serve.queue_wait` spans: the head
request's wait from submit to dispatch."""


def read(run):
    s = run.spans.get("serve.queue_wait")
    return s["ms_p99"] if s and s["count"] else None
