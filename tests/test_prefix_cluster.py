"""Cluster-scale KV: host page tier, shared prefix directory, and
prefix-aware routing.

Correctness bar (same as every serving feature): tiers and the directory
change WHERE pages come from — device pool, host spill, a peer replica —
never what gets generated. Outputs stay token-identical to isolated
generation; tier bookkeeping is checked against an independent model under
randomized demote/promote/fetch interleavings (every page in exactly one
tier, refcounts conserved, the directory never pointing at a freed
page)."""
import jax
import numpy as np
import pytest

from _hyp import given, settings, st  # noqa: F401 (skips when absent)
from _isolated import serve_isolated

from repro.configs import get_config
from repro.core import cluster as cl
from repro.core import cost_model as cm
from repro.core.genetic import choose_host_tiers, search
from repro.models import model as M
from repro.serving.block_manager import (BlockPool, BlockTable,
                                         HostPagePool, PrefixIndex,
                                         chunk_hashes)
from repro.serving.cluster_kv import (ClusterPrefixDirectory,
                                      wire_cluster_prefix)
from repro.serving.continuous import PagedPipelineBatcher
from repro.serving.loop import VirtualClock, run_serve_loop
from repro.serving.pipeline import AsymmetricPipeline
from repro.serving.request import Request
from repro.serving.router import Router

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# Satellite: partial-prefix re-hit must refresh LRU order
# ---------------------------------------------------------------------------

def test_partial_prefix_rehit_refreshes_lru_order():
    """A chain whose short head keeps hitting must not be evicted
    wholesale: re-acquiring the head refreshes ITS recency, so eviction
    trims the cold deep tail (and colder chains) first."""
    pool = BlockPool(8, block_size=4)
    ix = PrefixIndex(pool)
    evicted = []
    ix.spill = lambda h, bid: evicted.append(h)

    hs_a = chunk_hashes(np.arange(12, dtype=np.int32), 4)          # 3 blocks
    ta = BlockTable(pool)
    assert ta.allocate_tokens(12)
    ix.register(hs_a, ta.blocks)
    hs_b = chunk_hashes(100 + np.arange(8, dtype=np.int32), 4)     # 2 blocks
    tb = BlockTable(pool)
    assert tb.allocate_tokens(8)
    ix.register(hs_b, tb.blocks)
    ta.release()
    tb.release()

    # the short head of chain A keeps hitting
    t = BlockTable(pool, ix.acquire(hs_a[:1]))
    t.release()

    # pressure for two blocks: A's cold DEEP TAIL goes (deepest first —
    # a chained hash only matches head-first, so evicting the head would
    # orphan the whole chain), the re-hit head and chain B survive
    assert ix.evict(2) == 2
    assert evicted == [hs_a[2], hs_a[1]]
    assert ix.match_len(hs_a) == 1          # head still serves
    assert ix.match_len(hs_b) == 2          # untouched chain intact

    # next pressure takes the colder chain B tail before A's hot head
    assert ix.evict(1) == 1
    assert evicted[-1] == hs_b[1]
    assert ix.match_len(hs_a) == 1


def test_register_orders_chain_tail_first_for_eviction():
    """Freshly registered chains evict tail-first even without re-hits."""
    pool = BlockPool(6, block_size=4)
    ix = PrefixIndex(pool)
    order = []
    ix.spill = lambda h, bid: order.append(h)
    hs = chunk_hashes(np.arange(16, dtype=np.int32), 4)            # 4 blocks
    t = BlockTable(pool)
    assert t.allocate_tokens(16)
    ix.register(hs, t.blocks)
    t.release()
    assert ix.evict(3) == 3
    assert order == [hs[3], hs[2], hs[1]]
    assert ix.match_len(hs) == 1


# ---------------------------------------------------------------------------
# HostPagePool unit behavior
# ---------------------------------------------------------------------------

def test_host_pool_put_get_one_tier_and_lru_bound():
    hp = HostPagePool(2, block_size=4)
    dropped = []
    hp.on_evict = dropped.append
    hp.put(1, [{"k": np.ones(2)}])
    hp.put(2, [{"k": np.ones(2) * 2}])
    assert hp.match_len([1, 2, 3]) == 2
    # get POPS: the payload lives in exactly one tier
    p = hp.get(1)
    assert p is not None and 1 not in hp
    assert hp.promotions == 1
    # peek does not promote (cluster export ships a copy)
    assert hp.peek(2) is not None and 2 in hp
    # over capacity: LRU drop fires on_evict
    hp.put(3, [{"k": np.zeros(2)}])
    hp.put(4, [{"k": np.zeros(2)}])
    assert dropped == [2] and hp.evictions == 1
    # restore is counter-neutral (a failed promotion never happened)
    d, pr = hp.demotions, hp.promotions
    q = hp.get(3)
    hp.restore(3, q)
    assert (hp.demotions, hp.promotions) == (d, pr)
    assert 3 in hp


# ---------------------------------------------------------------------------
# ClusterPrefixDirectory unit behavior
# ---------------------------------------------------------------------------

def test_directory_publish_holders_resident_blocks():
    d = ClusterPrefixDirectory()
    d.publish(7, 0, "host")
    d.publish(7, 1, "device")
    d.publish(7, 2, "device")
    # device tier first (no swap-in on export), then lowest replica id
    assert d.holders(7) == [(1, "device"), (2, "device"), (0, "host")]
    assert d.holders(7, exclude=1) == [(2, "device"), (0, "host")]
    # re-publish moves tiers; unpublish drops the claim entirely
    d.publish(1, 0, "device")
    d.publish(2, 0, "host")
    # chain walk stops at the first gap: hash 3 unpublished
    assert d.resident_blocks([1, 2, 3, 7], 0) == (1, 1)
    d.unpublish(2, 0)
    assert d.resident_blocks([1, 2, 3, 7], 0) == (1, 0)
    d.unpublish(7, 0)
    d.unpublish(7, 1)
    d.unpublish(7, 2)
    assert d.tier(7, 2) is None


# ---------------------------------------------------------------------------
# Property: tier invariants under demote/promote/fetch interleavings
# ---------------------------------------------------------------------------

class _Rep:
    """One replica's tier stack in miniature, wired exactly like
    PagedPipelineBatcher: eviction spills to the host pool and publishes
    "host"; the host LRU drop unpublishes; registration publishes
    "device" and discards any stale host copy."""

    def __init__(self, rid, directory, n_usable, block_size, host_cap):
        self.rid = rid
        self.d = directory
        self.pool = BlockPool(n_usable + 1, block_size)
        self.ix = PrefixIndex(self.pool)
        self.host = HostPagePool(host_cap, block_size)
        self.tables = []

        def spill(h, bid):
            self.host.put(h, {"blk": int(bid)})
            self.d.publish(h, self.rid, "host")
        self.ix.spill = spill
        self.host.on_evict = lambda h: self.d.unpublish(h, self.rid)


def _admit(rep, peers, prompt, block_size):
    """Mirror of _match_slot's tier materialization: alias the device
    match, then per missing block promote from host (pop BEFORE alloc)
    or fetch from a peer, register + adopt, publish."""
    hs = chunk_hashes(prompt, block_size)
    L = rep.ix.match_len(hs)
    t = BlockTable(rep.pool, rep.ix.acquire(hs[:L]))
    for h in hs[L:]:
        pay, src = rep.host.get(h), "host"
        if pay is None:
            src = None
            for peer in peers:
                if peer.ix.lookup(h) is not None \
                        or peer.host.peek(h) is not None:
                    pay, src = {"blk": -1}, "fetch"
                    break
        if pay is None:
            break
        if rep.pool.n_free < 1:
            rep.ix.evict(1)
        blks = rep.pool.alloc(1)
        if blks is None:
            if src == "host":
                rep.host.restore(h, pay)
            break
        rep.ix.register([h], blks)
        t.adopt(blks)
        rep.host.discard(h)
        rep.d.publish(h, rep.rid, "device")
    n_have = t.n_blocks * block_size
    if len(prompt) > n_have and not t.ensure(len(prompt) - 1):
        rep.ix.evict(len(prompt) // block_size + 1)
        if not t.ensure(len(prompt) - 1):
            t.release()
            return
    k = min(len(hs), t.n_blocks)
    rep.ix.register(hs[:k], t.blocks[:k])
    for h in hs[:k]:
        rep.host.discard(h)
        rep.d.publish(h, rep.rid, "device")
    rep.tables.append(t)


def _check_invariants(reps, directory):
    for rep in reps:
        # refcount conservation: pool refs == table holds + index holds
        holds = np.zeros(rep.pool.n_blocks, np.int64)
        for t in rep.tables:
            for b in t.blocks:
                holds[b] += 1
        for b in rep.ix._lru:
            holds[b] += 1
        for b in range(1, rep.pool.n_blocks):
            assert rep.pool.ref(b) == holds[b], (rep.rid, b)
        # every page in exactly one tier
        assert not set(rep.ix._block_of) & set(rep.host._pages), rep.rid
        # host tier honors its capacity bound
        assert len(rep.host) <= rep.host.capacity
    # directory residency never points at a freed/absent page
    for h, m in directory._res.items():
        for rid, tier in m.items():
            rep = reps[rid]
            if tier == "device":
                assert rep.ix.lookup(h) is not None, (h, rid)
            else:
                assert h in rep.host, (h, rid)


def _run_tier_interleaving(seed, n_ops=40):
    rng = np.random.RandomState(seed % (2 ** 31))
    bs = 4
    d = ClusterPrefixDirectory()
    reps = [_Rep(0, d, 5, bs, 3), _Rep(1, d, 7, bs, 2)]
    for _ in range(n_ops):
        rep = reps[rng.randint(len(reps))]
        peers = [r for r in reps if r is not rep]
        op = rng.randint(4)
        if op == 0:                     # admit from a tiny alphabet
            n_tok = rng.randint(1, 3 * bs + 2)
            _admit(rep, peers, rng.randint(0, 3, size=n_tok), bs)
        elif op == 1 and rep.tables:    # finish a request
            rep.tables.pop(rng.randint(len(rep.tables))).release()
        elif op == 2:                   # eviction pressure -> demotions
            rep.ix.evict(rng.randint(1, 3))
        else:                           # host churn via repeat admits
            n_tok = rng.randint(bs, 2 * bs + 1)
            _admit(rep, peers, rng.randint(0, 2, size=n_tok), bs)
        _check_invariants(reps, d)
    for rep in reps:
        for t in rep.tables:
            t.release()
        rep.tables = []
    _check_invariants(reps, d)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_tier_invariants_property(seed):
    _run_tier_interleaving(seed)


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5, 6, 7])
def test_tier_invariants_seeded(seed):
    """Always-run fallback for environments without hypothesis."""
    _run_tier_interleaving(seed * 7919 + 13)


# ---------------------------------------------------------------------------
# Satellite: routing determinism + prefix-aware dispatch
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def two_replica_router_parts():
    cfg = get_config("granite-8b").reduced()
    params = M.init_params(cfg, KEY)
    dev = jax.devices()[0]
    L = cfg.num_layers

    def mk_router(**kw):
        reps = [AsymmetricPipeline(cfg, params, [L], [[dev]])
                for _ in range(2)]
        base = dict(n_slots=2, max_len=48, block_size=8,
                    prefix_caching=True)
        base.update(kw)
        return Router(reps, **base)

    return cfg, mk_router


def test_router_tiebreak_deterministic_lowest_replica_id(
        two_replica_router_parts):
    cfg, mk_router = two_replica_router_parts
    r = mk_router()
    req = Request(rid=0, prompt=np.arange(9, dtype=np.int32),
                  max_new_tokens=2, arrival=0.0)
    # idle workers tie on load: lowest replica id wins, in EITHER order
    w = r._dispatch(list(r.workers), req, 0.0)
    assert w.replica_id == 0
    w = r._dispatch(list(reversed(r.workers)), req, 0.0)
    assert w.replica_id == 0


def test_router_seeded_tiebreak_reproducible(two_replica_router_parts):
    cfg, mk_router = two_replica_router_parts
    req = Request(rid=0, prompt=np.arange(9, dtype=np.int32),
                  max_new_tokens=2, arrival=0.0)
    picks = []
    for _ in range(2):
        r = mk_router(route_seed=123)
        picks.append([r._dispatch(list(r.workers), req, 0.0).replica_id
                      for _ in range(12)])
    assert picks[0] == picks[1]          # same seed, same route sequence


def test_router_prefix_aware_dispatch_prefers_resident_replica(
        two_replica_router_parts):
    cfg, mk_router = two_replica_router_parts
    r = mk_router(cluster_prefix=True)
    assert r.cluster_dir is not None
    prompt = np.arange(24, dtype=np.int32)
    for h in chunk_hashes(prompt, 8):
        r.cluster_dir.publish(h, 1, "device")
    req = Request(rid=0, prompt=prompt, max_new_tokens=2, arrival=0.0)
    # equal load, but replica 1 holds the whole prefix: affinity wins
    assert r._dispatch(list(r.workers), req, 0.0).replica_id == 1
    # host-resident blocks count at a discount but still attract
    for h in chunk_hashes(prompt, 8):
        r.cluster_dir.publish(h, 1, "host")
    assert r._dispatch(list(r.workers), req, 0.0).replica_id == 1
    # weight 0 restores pure least-loaded + deterministic tiebreak
    r.prefix_route_weight = 0.0
    assert r._dispatch(list(r.workers), req, 0.0).replica_id == 0


# ---------------------------------------------------------------------------
# End-to-end: host-tier spill/promotion and cluster fetch are invisible
# to the token stream
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cluster_served_cold():
    cfg = get_config("granite-8b").reduced()
    params = M.init_params(cfg, KEY)
    dev = jax.devices()[0]
    L = cfg.num_layers

    def pipe():
        return AsymmetricPipeline(cfg, params, [1, L - 1], [[dev], [dev]])

    def mk_reqs():
        reqs = []
        for i in range(6):
            rng = np.random.RandomState(100 + i % 3)   # 3 prompt families
            prompt = rng.randint(0, cfg.vocab_size, size=24).astype(np.int32)
            reqs.append(Request(rid=i, prompt=prompt, max_new_tokens=4,
                                arrival=0.3 * i))
        return reqs

    reqs_c = serve_isolated(pipe(), mk_reqs())
    return cfg, params, pipe, mk_reqs, reqs_c


def test_host_tier_promotion_bit_identical(cluster_served_cold):
    """Device pools too small for three 24-token families: evictions
    demote to the host tier and revisits promote back instead of
    re-prefilling — with the token streams unchanged."""
    cfg, params, pipe, mk_reqs, reqs_c = cluster_served_cold
    reqs_h = mk_reqs()
    w = PagedPipelineBatcher(pipe(), n_slots=2, max_len=48, block_size=8,
                             stage_blocks=[10, 10], prefix_caching=True,
                             host_blocks=64, host_swap_cost=0.05,
                             prefill_token_cost=0.125)
    stats = run_serve_loop([w], reqs_h, deadline=1e9, clock=VirtualClock())
    for rc, rh in zip(reqs_c, reqs_h):
        assert list(rc.output) == list(rh.output), rc.rid
    assert stats.host_demotions > 0
    assert stats.host_promotions > 0
    assert stats.host_hit_tokens > 0
    assert "host=" in stats.summary()


def test_cluster_prefix_fetch_bit_identical(cluster_served_cold):
    """Two replicas behind a shared directory: a prompt landing on the
    replica that never saw its family fetches the prefix pages from the
    peer instead of cold-prefilling — token streams unchanged."""
    cfg, params, pipe, mk_reqs, reqs_c = cluster_served_cold
    reqs_x = mk_reqs()
    ws = [PagedPipelineBatcher(pipe(), n_slots=2, max_len=48, block_size=8,
                               prefix_caching=True, replica_id=i,
                               prefill_token_cost=0.125)
          for i in range(2)]
    directory = wire_cluster_prefix(ws)
    stats = run_serve_loop(ws, reqs_x, deadline=1e9, clock=VirtualClock())
    for rc, rx in zip(reqs_c, reqs_x):
        assert list(rc.output) == list(rx.output), rc.rid
    assert stats.prefix_fetches > 0
    assert stats.prefix_fetched_bytes > 0
    assert len(directory) > 0
    assert "fetch=" in stats.summary()


def test_preempt_recovery_consults_host_tier(cluster_served_cold):
    """Preemption's truncated blocks land in the index; the pressure that
    caused it demotes them to the host tier, and the re-admitted request
    PROMOTES instead of re-prefilling — outputs still cold-identical."""
    cfg, params, pipe, mk_reqs, reqs_c = cluster_served_cold
    reqs_p = mk_reqs()
    # pools tight enough that decode growth forces preemption
    w = PagedPipelineBatcher(pipe(), n_slots=3, max_len=48, block_size=8,
                             stage_blocks=[9, 9], prefix_caching=True,
                             host_blocks=64, prefill_token_cost=0.125)
    stats = run_serve_loop([w], reqs_p, deadline=1e9, clock=VirtualClock())
    for rc, rp in zip(reqs_c, reqs_p):
        assert list(rc.output) == list(rp.output), rc.rid
    assert stats.host_promotions > 0


# ---------------------------------------------------------------------------
# Scheduler layer: host-tier sizing and residency-derived hit rates
# ---------------------------------------------------------------------------

def test_effective_prefix_hit_rate_model():
    # no working set: the static scalar stands
    assert cm.effective_prefix_hit_rate(
        0.6, working_set_blocks=0, device_blocks=0) == 0.6
    # full device coverage: shareable fraction achieved outright
    assert cm.effective_prefix_hit_rate(
        0.6, working_set_blocks=100, device_blocks=100) == 0.6
    # half coverage halves the rate
    assert cm.effective_prefix_hit_rate(
        0.6, working_set_blocks=100, device_blocks=50) \
        == pytest.approx(0.3)
    # host blocks extend reach, discounted by swap cost
    lo = cm.effective_prefix_hit_rate(
        0.6, working_set_blocks=100, device_blocks=50)
    hi = cm.effective_prefix_hit_rate(
        0.6, working_set_blocks=100, device_blocks=50, host_blocks=50,
        tier_discount=0.5)
    assert lo < hi < 0.6
    # a swap as dear as recompute makes the tier worthless
    assert cm.effective_prefix_hit_rate(
        0.6, working_set_blocks=100, device_blocks=50, host_blocks=500,
        tier_discount=1.0) == pytest.approx(0.3)


def test_host_tier_block_arithmetic():
    cfg = get_config("granite-8b")
    prof = cm.ModelProfile.from_config(cfg)
    task = cm.Task(batch=1, s_in=96, s_out=16)
    blk = cm.kv_block_bytes(prof, task, 16)
    assert blk > 0
    assert cm.host_tier_blocks(10 * blk, prof, task, 16) == 10
    # quantized pools spill at their narrow width: more blocks per byte
    assert cm.host_tier_blocks(10 * blk, prof, task, 16, kv_dtype="int8") \
        > 10
    assert cm.host_swap_seconds_per_block(prof, task, 16, 0.0) == 0.0
    s = cm.host_swap_seconds_per_block(prof, task, 16, 8.0)
    assert s == pytest.approx(cm.kv_block_bytes(prof, task, 16) / 1e9)


def test_choose_host_tiers_targets_deficit_replicas():
    class P:                           # plan stub: only .cost is read
        def __init__(self, cost):
            self.cost = cost

    plans = [P(1.0), P(1.0)]
    caps = {id(plans[0]): 100, id(plans[1]): 1}   # replica 1 is starved
    out = choose_host_tiers(plans, lambda p: caps[id(p)], rate=20.0,
                            blocks_per_seq=4, budget_blocks=90)
    assert out[1] > out[0] == 0        # the small-HBM replica gets it all
    # no deficit anywhere: the budget still backs prefix churn, evenly
    out = choose_host_tiers(plans, lambda p: 1000, rate=1.0,
                            blocks_per_seq=4, budget_blocks=7)
    assert out == [4, 3]
    assert choose_host_tiers([], lambda p: 0, rate=1.0,
                             blocks_per_seq=4, budget_blocks=7) == []


def test_search_places_host_tier(monkeypatch):
    pool = cl.case_study_cluster()
    cfg = get_config("h2o-danube-1.8b")
    prof = cm.ModelProfile.from_config(cfg)
    task = cm.Task(batch=1, s_in=96, s_out=16)
    res = search(pool, prof, task, deadline=30.0, rate=2.0, iters=2,
                 seed=0, kv_block_size=16, prefix_hit_rate=0.6,
                 prefix_working_set=4096, host_tier_bytes=4e9,
                 host_swap_gbps=32.0, cluster_prefix=True)
    assert res.host_blocks is not None
    assert len(res.host_blocks) == len(res.assignment.pipelines)
    assert sum(res.host_blocks) > 0
    # without the knob the dimension stays out of the result
    res2 = search(pool, prof, task, deadline=30.0, rate=2.0, iters=1,
                  seed=0, kv_block_size=16)
    assert res2.host_blocks is None
