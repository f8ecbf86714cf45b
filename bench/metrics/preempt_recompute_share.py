"""Share of the prefill work that preemption by recompute adds, in percent:
the cached tokens that preemptions threw away (``ServeStats.
recomputed_tokens``, the program's counter), over every prompt token the
insert and context calls of the same serve prefilled. Both count the whole
serve, the drain after the window included. A program without the counter
gives nothing to read. Layer: batcher."""

PREFILL = ("insert", "context")


def read(run):
    thrown = getattr(run.stats, "recomputed_tokens", None)
    prefilled = sum(k.tokens for k in run.recorder.calls
                    if k.kind in PREFILL)
    if thrown is None or not prefilled:
        return None
    return 100.0 * thrown / prefilled
