"""95th percentile of the wait from a request's due time to its admission
by the serve loop (``Request.start_time``, stamped by the program on the
serve loop's clock), over the requests due in the window. One never
admitted counts as infinitely long. Layer: serve loop and router."""
import math

from harness.stats import percentile


def read(run):
    if not run.attempted:
        return None
    waits = [r.start_time - r.arrival if r.start_time is not None
             else math.inf for r in run.attempted]
    return 1e3 * percentile(waits, 95)
