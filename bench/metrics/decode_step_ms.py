"""Mean host wall time of a decode call started in the window. It returns
the logits of every slot to the host, so it covers the device step and
the transfer. Layer: model step."""


def read(run):
    calls = [k for k in run.calls if k.kind == "decode"]
    if not calls:
        return None
    return 1e3 * sum(k.t1 - k.t0 for k in calls) / len(calls)
