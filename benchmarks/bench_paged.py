"""The paged KV cache under a fixed per-stage memory budget.

A cache-token budget B buys B / block_size blocks, and the paged engine
admits by ACTUAL footprint (prompt + headroom, growing one block per
decoded token). So a mixed-length workload — mostly short requests under
a long-request ceiling — runs more slots at once than the B / max_len
rows a worst-case reservation per slot could hold.

Rows land in results/paged.jsonl; the acceptance bar is a peak above
B / max_len concurrent slots.
"""
from __future__ import annotations

import json
import os

import jax
import numpy as np

from benchmarks.common import emit
from repro.configs import get_config
from repro.models import model as M
from repro.serving.continuous import PagedPipelineBatcher
from repro.serving.loop import VirtualClock, run_serve_loop
from repro.serving.pipeline import AsymmetricPipeline
from repro.serving.request import Request

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")

# equal cache budget per stage, in tokens
BUDGET_TOKENS = 256
MAX_LEN = 64                 # per-request ceiling (the long tail must fit)
BLOCK = 8


def _emit_json(name: str, payload: dict) -> None:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    row = json.dumps({"bench": name, **payload}, sort_keys=True)
    with open(os.path.join(RESULTS_DIR, "paged.jsonl"), "a") as f:
        f.write(row + "\n")
    print("# json: " + row)


def _workload(cfg, *, n=24, seed=7):
    """Mixed lengths: mostly short chats, a few long documents — the regime
    where worst-case reservation wastes almost the whole pool."""
    rng = np.random.RandomState(seed)
    reqs = []
    for i in range(n):
        long = (i % 8 == 7)
        plen = int(rng.randint(24, 40)) if long else int(rng.randint(4, 10))
        out = 12 if long else 6
        reqs.append(Request(
            rid=i, prompt=rng.randint(0, cfg.vocab_size,
                                      size=plen).astype(np.int32),
            max_new_tokens=out, arrival=0.0))
    return reqs


class _PeakConcurrency:
    """Wraps a slot engine to record the peak number of occupied slots."""

    def __init__(self, eng):
        self.eng = eng
        self.peak = 0

    def __getattr__(self, name):
        return getattr(self.eng, name)

    def run_iteration(self, now):
        out = self.eng.run_iteration(now)
        busy = sum(1 for s in self.eng.slots if not s.free)
        self.peak = max(self.peak, busy)
        return out


def run() -> None:
    cfg = get_config("granite-8b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    dev = jax.devices()[0]
    L = cfg.num_layers

    def pipe():
        return AsymmetricPipeline(cfg, params, [1, L - 1], [[dev], [dev]])

    # the budget buys BUDGET/BLOCK blocks; slots are bounded only by
    # bookkeeping, admission by actual prompt + headroom
    n_blocks = BUDGET_TOKENS // BLOCK + 1          # + reserved null block
    paged = _PeakConcurrency(PagedPipelineBatcher(
        pipe(), n_slots=16, max_len=MAX_LEN, block_size=BLOCK,
        stage_blocks=[n_blocks, n_blocks]))
    st_p = run_serve_loop([paged], _workload(cfg), deadline=1e9,
                          clock=VirtualClock())
    emit("paged/paged_slots", 0.0,
         f"peak={paged.peak}/16 thpt={st_p.throughput:.3f} req/iter "
         f"iters={st_p.iterations} preempt={st_p.preemptions}")
    _emit_json("paged_at_budget", {
        "arch": cfg.name, "stage_blocks": [n_blocks, n_blocks],
        "peak": paged.peak, "thpt_req_per_iter": float(st_p.throughput),
        "preemptions": st_p.preemptions,
    })

    # asymmetric per-stage pools: a big-HBM stage 1 with a small stage 0
    # no longer drags concurrency down to the small peer's worst case —
    # stage pools are sized independently and admission takes the min
    asym = _PeakConcurrency(PagedPipelineBatcher(
        pipe(), n_slots=16, max_len=MAX_LEN, block_size=BLOCK,
        stage_blocks=[n_blocks // 2 + 1, 2 * n_blocks]))
    st_a = run_serve_loop([asym], _workload(cfg), deadline=1e9,
                          clock=VirtualClock())
    emit("paged/asymmetric_pools", 0.0,
         f"stage_blocks=[{n_blocks // 2 + 1},{2 * n_blocks}] "
         f"peak={asym.peak} thpt={st_a.throughput:.3f} req/iter "
         f"preempt={st_a.preemptions}")
    _emit_json("paged_asymmetric_pools", {
        "arch": cfg.name,
        "stage_blocks": [n_blocks // 2 + 1, 2 * n_blocks],
        "peak": asym.peak, "thpt_req_per_iter": float(st_a.throughput),
        "preemptions": st_a.preemptions,
    })

    reserved_rows = BUDGET_TOKENS // MAX_LEN
    assert paged.peak > reserved_rows, \
        f"acceptance: paged should run more than {reserved_rows} slots " \
        f"on the budget, peaked at {paged.peak}"


if __name__ == "__main__":
    run()
