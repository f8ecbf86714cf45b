"""Mean host self time of a batcher iteration, in ms: each ``iteration``
span of the program's tracer less the ``decode``, ``insert`` and
``context`` calls inside it, over the iterations started in the window.
Reads the tracer's events (``run.spans``, Chrome-trace dicts in seconds
on the window's clock), which carry each span's id and its parent's.
Layer: batcher (``serving/continuous.py``)."""

CALLS = ("decode", "insert", "context")


def read(run):
    spans = [e for e in getattr(run, "spans", None) or []
             if e.get("ph") == "X" and "id" in (e.get("args") or {})]
    parent = {e["args"]["id"]: e["args"].get("parent") for e in spans}
    name = {e["args"]["id"]: e["name"] for e in spans}
    iters = {e["args"]["id"]: e["dur"] for e in spans
             if e["name"] == "iteration" and 0 <= e["ts"] < run.seconds}
    if not iters:
        return None
    calls = {i: 0.0 for i in iters}
    for e in spans:
        if e["name"] not in CALLS:
            continue
        up = parent.get(e["args"]["id"])
        while up is not None and name.get(up) != "iteration":
            up = parent.get(up)
        if up in calls:
            calls[up] += e["dur"]
    return 1e3 * sum(iters[i] - calls[i] for i in iters) / len(iters)
