"""Mean time of one background merge published inside the window: its
fold, retrain, re-cluster, flatten and publish spans, over the merges
published (`merge.publish` spans) between the window's start and the
drain."""

STAGES = ("merge.fold", "merge.retrain", "merge.recluster",
          "merge.flatten", "merge.publish")


def _total_ms(spans, name):
    s = spans.get(name)
    return s["ms_mean"] * s["count"] if s and s["count"] else 0.0


def read(run):
    b, a = run.spans_before, run.spans
    if "merge.publish" not in a:
        return None
    n = a["merge.publish"]["count"] - b.get("merge.publish",
                                            {"count": 0})["count"]
    if n <= 0:
        return None
    return sum(_total_ms(a, s) - _total_ms(b, s) for s in STAGES) / n
