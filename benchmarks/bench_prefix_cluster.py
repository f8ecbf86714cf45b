"""Cluster-scale prefix reuse: shared directory + host page tier +
prefix-aware routing vs private per-replica caches.

Two replicas serve a workload of hot prompt FAMILIES (long shared head,
short unique tail) whose combined working set does NOT fit any single
replica's device pool. The private-cache baseline loses twice: least-
loaded routing scatters a family's revisits across replicas (each cache
holds a cold copy), and pool pressure EVICTS the shared heads outright,
so revisits re-prefill. The cluster treatment demotes evicted heads to a
host tier, swaps them back on re-hit, fetches peer-resident heads over
the modeled link, and routes revisits to the replica already holding the
family — so prefill collapses to first-toucher + tails.

Both sides pay the same ``prefill_token_cost`` on the virtual clock, and
host swaps/fetches are charged there too, so the TTFT delta is earned
reuse, not free transfers. Token streams must stay identical to isolated
generation (tiers change where pages COME FROM, never what gets
generated).

Rows land in results/prefix_cluster.jsonl (run.py --check validates and
folds them into BENCH_trajectory.json).
"""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import emit, emit_json
from repro.configs import get_config
from repro.models import model as M
from repro.serving.loop import VirtualClock
from repro.serving.pipeline import AsymmetricPipeline
from repro.serving.request import Request
from repro.serving.router import Router

N_FAMILIES = 3
N_VISITS = 8
SHARED_LEN = 40              # 5 whole blocks of 8: the hot head
TAIL_LEN = 8
OUT_LEN = 4
BLOCK = 8
MAX_LEN = 56  # 40 + 8 + 4 rounded to whole blocks
STAGE_BLOCKS = [12, 12]      # 11 usable/stage: < the 15-block shared set
HOST_BLOCKS = 64
TOKEN_COST = 0.5            # virtual iteration fraction per prefill token
SWAP_COST = 0.02             # virtual iteration fraction per swapped block
ARRIVAL_GAP = 3.0            # sparse enough that TTFT is prefill, not queue


def _workload(cfg):
    """N_FAMILIES hot families, N_VISITS visits each, interleaved so
    every family's head is long cold between revisits under LRU."""
    reqs = []
    rid = 0
    for visit in range(N_VISITS):
        for fam in range(N_FAMILIES):
            rng = np.random.RandomState(100 + fam)
            head = rng.randint(0, cfg.vocab_size, SHARED_LEN)
            tail = np.random.RandomState(1000 + rid).randint(
                0, cfg.vocab_size, TAIL_LEN)
            reqs.append(Request(
                rid=rid,
                prompt=np.concatenate([head, tail]).astype(np.int32),
                max_new_tokens=OUT_LEN, arrival=ARRIVAL_GAP * rid))
            rid += 1
    return reqs


def _serve(mk_replicas, reqs, **kw):
    router = Router(mk_replicas(), n_slots=2, max_len=MAX_LEN,
                    block_size=BLOCK,
                    stage_blocks=STAGE_BLOCKS, prefix_caching=True,
                    prefill_token_cost=TOKEN_COST, **kw)
    stats = router.serve(reqs, deadline=1e9, clock=VirtualClock())
    ttft = [r.first_token_time - r.arrival for r in reqs
            if r.first_token_time is not None]
    return stats, float(np.percentile(ttft, 50))


def run() -> None:
    cfg = get_config("granite-8b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    dev = jax.devices()[0]
    L = cfg.num_layers

    def mk_replicas():
        return [AsymmetricPipeline(cfg, params, [1, L - 1], [[dev], [dev]])
                for _ in range(2)]

    # isolated generation: the token-identity oracle
    ref = AsymmetricPipeline(cfg, params, [1, L - 1], [[dev], [dev]])
    reqs_cold = _workload(cfg)
    for r in reqs_cold:
        r.output = ref.generate(r.prompt[None], max_new=OUT_LEN)[0]

    # baseline: private per-replica caches, least-loaded routing,
    # eviction deletes
    reqs_b = _workload(cfg)
    st_b, p50_b = _serve(mk_replicas, reqs_b)

    # treatment: shared directory + host tier + prefix-aware routing
    reqs_t = _workload(cfg)
    st_t, p50_t = _serve(mk_replicas, reqs_t, host_blocks=HOST_BLOCKS,
                         host_swap_cost=SWAP_COST, cluster_prefix=True,
                         prefix_route_weight=0.5)

    # (c) tiers and the directory are invisible to the token stream
    for rc, rb, rt in zip(reqs_cold, reqs_b, reqs_t):
        assert list(rc.output) == list(rb.output), rb.rid
        assert list(rc.output) == list(rt.output), rt.rid

    total_prompt = sum(len(r.prompt) for r in reqs_b)
    # cache-served fraction of prompt tokens: whatever was NOT prefilled
    # came from a tier (device hit, host promotion, or peer fetch)
    hit_b = 1.0 - st_b.prefill_tokens / total_prompt
    hit_t = 1.0 - st_t.prefill_tokens / total_prompt
    # (a) the cluster serves strictly more prompt tokens from cache
    assert hit_t > hit_b, (hit_t, hit_b)
    # (b) routed + tiered reuse buys >= 2x p50 TTFT
    speedup = p50_b / max(p50_t, 1e-9)
    assert speedup >= 2.0, (p50_b, p50_t)

    emit("prefix_cluster/private_baseline", 0.0,
         f"prefill={st_b.prefill_tokens}tok hit={hit_b * 100:.0f}% "
         f"p50_ttft={p50_b:.2f} preempt={st_b.preemptions}")
    emit("prefix_cluster/cluster_tiered", 0.0,
         f"prefill={st_t.prefill_tokens}tok hit={hit_t * 100:.0f}% "
         f"p50_ttft={p50_t:.2f} host={st_t.host_promotions}in/"
         f"{st_t.host_demotions}out fetch={st_t.prefix_fetches}")
    emit("prefix_cluster/gain", 0.0,
         f"{speedup:.2f}x p50 TTFT, cache-served "
         f"{hit_b * 100:.0f}% -> {hit_t * 100:.0f}% on a "
         f"{N_FAMILIES}-family working set {sum(STAGE_BLOCKS[:1]) * 2}"
         f"-block pools cannot hold")
    emit_json("prefix_cluster.jsonl", "prefix_cluster_vs_private", {
        "arch": cfg.name, "n_requests": len(reqs_b),
        "n_families": N_FAMILIES, "shared_len": SHARED_LEN,
        "block_size": BLOCK, "stage_blocks": STAGE_BLOCKS,
        "host_blocks": HOST_BLOCKS, "host_swap_cost": SWAP_COST,
        "prefill_token_cost": TOKEN_COST,
        "base_prefill_tokens": st_b.prefill_tokens,
        "cluster_prefill_tokens": st_t.prefill_tokens,
        "base_hit_rate": float(hit_b),
        "cluster_hit_rate": float(hit_t),
        "host_demotions": st_t.host_demotions,
        "host_promotions": st_t.host_promotions,
        "host_hit_tokens": st_t.host_hit_tokens,
        "prefix_fetches": st_t.prefix_fetches,
        "prefix_fetched_bytes": st_t.prefix_fetched_bytes,
        "base_p50_ttft": p50_b, "cluster_p50_ttft": p50_t,
        "p50_ttft_speedup_x": float(speedup),
        "token_identical": True,
    })


if __name__ == "__main__":
    run()
