"""Device time per decode call of the operations under the ``attention``
named scope (rope, the paged K/V write and gather, scores, softmax and the
value sum), in ms, over the ``hex:decode`` spans of the traced window
(``harness/program_trace.py``). Layer: decode step program."""


def read(run):
    t = run.trace or {}
    spans, scopes = t.get("hex_decode"), t.get("decode_scopes")
    if not spans or scopes is None:
        return None
    return 1e3 * scopes.get("attention", 0.0) / len(spans)
