"""Beyond-paper: continuous vs static batching under a bursty workload
(the paper's Appendix-D limitation). Same replicas, same requests; latency
comes from the measured CPU engine (relative numbers are what matter).

MULTI-STAGE asymmetric pipeline replicas — the paper's actual artifact —
served statically vs at iteration granularity (the paged continuous
engine) through the shared loop. A JSON row records this path so the
perf trajectory tracks it across PRs.
"""
from __future__ import annotations

import json
import os

import jax
import numpy as np

from benchmarks.common import emit
from repro.configs import get_config
from repro.core.plan import Assignment, PipelinePlan, StagePlan
from repro.models import model as M
from repro.serving.engine import InferenceEngine
from repro.serving.request import synth_workload

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")


def _emit_json(name: str, payload: dict) -> None:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    row = json.dumps({"bench": name, **payload}, sort_keys=True)
    with open(os.path.join(RESULTS_DIR, "continuous.jsonl"), "a") as f:
        f.write(row + "\n")
    print("# json: " + row)


def run() -> None:
    cfg = get_config("xlstm-125m").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))

    def workload(seed):
        return synth_workload(rate=12.0, duration=1.0, vocab=cfg.vocab_size,
                              prompt_len=8, prompt_jitter=6, out_len=6,
                              seed=seed)

    # ---- multi-stage asymmetric replicas through the unified router ------
    L = cfg.num_layers
    split = [max(1, L // 3), L - max(1, L // 3)]
    asg2 = Assignment([
        PipelinePlan([StagePlan([0], split[0]), StagePlan([1], split[1])],
                     cost=0.1, bottleneck=0.1),
        PipelinePlan([StagePlan([2], L)], cost=0.1, bottleneck=0.1),
    ])
    results = {}
    for policy in ("static", "continuous"):
        eng = InferenceEngine(cfg, asg2, params=params, max_batch=4,
                              policy=policy, n_slots=4, max_len=64)
        # warm with the SAME workload as the measured pass (requests are
        # re-created fresh) so the timed run pays no unseen-shape compiles
        eng.serve(workload(5), deadline=60.0)
        stats = eng.serve(workload(5), deadline=60.0)
        results[policy] = stats
        emit(f"continuous/pipeline_{policy}",
             np.mean(stats.latencies) * 1e6,
             f"p50={np.percentile(stats.latencies, 50):.2f}s "
             f"thpt={stats.throughput:.2f} iters={stats.iterations}")
    gain = (np.mean(results["static"].latencies)
            / np.mean(results["continuous"].latencies))
    emit("continuous/pipeline_latency_gain", 0.0,
         f"{gain:.2f}x lower mean latency on 2-stage replicas")
    _emit_json("continuous_pipeline", {
        "arch": cfg.name, "stages": split, "replicas": 2,
        "static_mean_lat_s": float(np.mean(results["static"].latencies)),
        "static_p50_lat_s": float(
            np.percentile(results["static"].latencies, 50)),
        "static_thpt_rps": float(results["static"].throughput),
        "continuous_mean_lat_s": float(
            np.mean(results["continuous"].latencies)),
        "continuous_p50_lat_s": float(
            np.percentile(results["continuous"].latencies, 50)),
        "continuous_thpt_rps": float(results["continuous"].throughput),
        "latency_gain_x": float(gain),
    })


if __name__ == "__main__":
    run()
