"""Share of the HBM roofline the decode steps reach, in percent: the bytes
a decode step needs (``counters.decode_bytes``: the weights, the keys and
values of the live rows at their real lengths, the logits) over the
device's busy time inside the traced decode calls times the chips' peak
bandwidth. Layer: the decode step program (XLA kernels)."""
from harness import counters


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    need = busy = 0.0
    for i, s in run.trace["call_busy_s"].items():
        k = run.recorder.calls[i]
        if k.kind == "decode" and k.rows:
            need += counters.decode_bytes(run.config, k.rows, k.ctx_tokens)
            busy += s
    if busy <= 0:
        return None
    return 100.0 * need / (busy * run.chips * run.peaks["hbm_bytes_per_s"])
