"""Host wall time of the prefill calls (one-shot insert or context chunk)
started in the window, per 1,000 real prompt tokens they prefilled. Each
call returns host logits, so its time covers the device work and the
transfer. Layer: model step."""


def read(run):
    calls = [k for k in run.calls if k.kind in ("insert", "context")]
    tokens = sum(k.tokens for k in calls)
    if not tokens:
        return None
    return 1e3 * sum(k.t1 - k.t0 for k in calls) / (tokens / 1e3)
