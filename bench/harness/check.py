"""Whether what the timed path served is correct.

Once the window has closed, a sample of the requests it finished, drawn
from the seed and always holding the one with the most output tokens, is
run through the plain float32 reference (``reference.py``) over its prompt
and its served tokens. Serving is greedy, so each served token should be
the reference's best at its position, up to the rounding of the served
precision. The numbers compared are taken over the gaps, one per sampled
position, by which a served token's reference logit lies below the
reference's best logit at that position: ``worst_logit_gap``, the widest,
and ``mean_logit_gap``, their mean. The cell's file names the numbers it
compares under ``limits``, each with its limit; the run is correct where
each lies within its limit.

The control (limit setting only) puts the reference at a lower precision
in the program's place: at the same positions, the gap of the token that
the lower precision puts first. Its tokens then stand where the served
tokens stood, so the verdict is the control's, held to the same limits;
the served tokens' numbers are still printed beside them.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from harness import reference


def sample(requests, seed: int, spec: Dict) -> List:
    """The finished requests to check: the one with the most output
    tokens, then others in an order drawn from the seed, until
    ``spec['tokens']`` served tokens or ``spec['requests']`` requests."""
    done = [r for r in requests if r.served and r.output is not None
            and len(r.output) == r.max_new_tokens and len(r.output)]
    if not done:
        return []
    done.sort(key=lambda r: (-len(r.output), r.rid))
    first, rest = done[0], done[1:]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 1])
    out, tokens = [first], len(first.output)
    for i in rng.permutation(len(rest)):
        if tokens >= spec["tokens"] or len(out) >= spec["requests"]:
            break
        out.append(rest[i])
        tokens += len(rest[i].output)
    return out


def gaps(ref: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """Per position, how far the given token's logit lies below the best."""
    ref = np.asarray(ref, np.float64)
    return ref.max(-1) - ref[np.arange(len(tokens)), tokens]


NUMBERS = {"worst_logit_gap": np.max, "mean_logit_gap": np.mean}


def compare(cfg: Dict, seed: int, reqs, devices, cell: Dict, *,
            control: str = "") -> Dict:
    limits = cell["limits"]
    take = {name: NUMBERS[name] for name in limits}
    if not reqs:
        return {"correct": False, "positions": 0,
                "summary": "no finished request to check",
                "compared": {name: {"value": None, "limit": limit}
                             for name, limit in limits.items()}}
    seqs, rows, outs = [], [], []
    for r in reqs:
        out = np.asarray(r.output, np.int64)
        seqs.append(np.concatenate([r.prompt, out[:-1]]).astype(np.int32))
        rows.append(np.arange(len(r.prompt) - 1,
                              len(r.prompt) - 1 + len(out)))
        outs.append(out)
    pad = cell["check"]["pad"]
    ref = reference.forward_logits(cfg, seed, seqs, rows, devices=devices,
                                   pad=pad)
    g = np.concatenate([gaps(lg, o) for lg, o in zip(ref, outs)])
    agree = np.mean(np.concatenate(
        [lg.argmax(-1) == o for lg, o in zip(ref, outs)]))
    summary = (f"logit gap worst {float(g.max())!r}, mean "
               f"{float(g.mean())!r} (limits {limits!r}), served token is "
               f"the reference's best at {float(agree)!r} of positions")
    compared = {name: {"value": float(f(g)), "limit": limits[name]}
                for name, f in take.items()}
    judged = g
    if control:
        low = reference.forward_logits(
            cfg, seed, seqs, rows, devices=devices, pad=pad,
            weights=reference.CONTROLS[control])
        judged = np.concatenate([gaps(lg, lo.argmax(-1))
                                 for lg, lo in zip(ref, low)])
        summary += (f"; control ({control} weights): logit gap worst "
                    f"{float(judged.max())!r}, mean "
                    f"{float(judged.mean())!r}")
        compared.update({f"control_{name}": {"value": float(f(judged)),
                                             "limit": limits[name]}
                         for name, f in take.items()})
    correct = all(float(f(judged)) <= limits[name]
                  for name, f in take.items())
    return {"correct": bool(correct), "positions": int(len(g)),
            "summary": summary, "compared": compared}
