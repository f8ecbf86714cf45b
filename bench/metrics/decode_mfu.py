"""Model FLOP/s utilization of the whole decode step, in percent: the
operations the decode calls started in the window need
(``counters.decode_flops``: two per weight per live row, and attention at
the rows' real lengths) over their host wall time times the chips' peak.
Layer: the whole decode step."""
from harness import counters


def read(run):
    if run.peaks is None:
        return None
    calls = [k for k in run.calls if k.kind == "decode" and k.rows]
    wall = sum(k.t1 - k.t0 for k in calls)
    if wall <= 0:
        return None
    flops = sum(counters.decode_flops(run.config, k.rows, k.ctx_tokens)
                for k in calls)
    return 100.0 * flops / (wall * run.chips * run.peaks["bf16_flops_per_s"])
