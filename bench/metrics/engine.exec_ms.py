"""Mean `serve.exec` span: one coalesced batch through the facade and the
engine, dispatch to results sliced back."""


def read(run):
    s = run.spans.get("serve.exec")
    return s["ms_mean"] if s and s["count"] else None
