"""Mean device idle time inside a ``hex:decode`` span of the program's
tracer (``harness/program_trace.py``), in ms: the span's length less the
device's busy time inside it, over the decode calls of the traced window.
What the host holds the chip back with inside the decode call (embedding,
uploads, dispatch, the head op by op, the logits' copy). Layer: model
step."""


def read(run):
    spans = (run.trace or {}).get("hex_decode")
    if not spans:
        return None
    return 1e3 * sum(s - b for s, b in spans) / len(spans)
