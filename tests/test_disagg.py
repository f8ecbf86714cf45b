"""Disaggregated prefill/decode serving: the KV handoff must change WHERE
a request's phases run — prefill on one replica, decode on another, the
pages shipped between them over a modeled link — while the token stream
stays BIT-identical to colocated serving. Covers the page round-trip
(pool -> wire -> pool scatter, across different stage splits), end-to-end
identity (plain, warm-prefix, and mid-prefill-chunked), virtual-clock
transfer-cost accounting, decode-side capacity rejection, and the
scheduler's role-assignment search."""
import warnings

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import slo_sim
from repro.core.genetic import best_role_split
from repro.core.plan import Assignment, PipelinePlan, StagePlan
from repro.core.slo_sim import PhasedReplicaModel
from repro.models import model as M
from repro.serving.block_manager import BlockPool, BlockTable, \
    blocks_for_tokens
from repro.serving.continuous import PagedPipelineBatcher
from repro.serving.disagg import KVLink, KVMigration, wire_disaggregation
from repro.serving.engine import InferenceEngine
from repro.serving.loop import VirtualClock, run_serve_loop
from repro.serving.pipeline import AsymmetricPipeline
from repro.serving.request import Request, shared_prefix_workload

KEY = jax.random.PRNGKey(0)
BLOCK = 8
MAX_LEN = 48


# ---------------------------------------------------------------------------
# Shared model/pipelines (jit amortized across the module)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def setup():
    cfg = get_config("granite-8b").reduced()
    params = M.init_params(cfg, KEY)
    dev = jax.devices()[0]
    L = cfg.num_layers

    def pipe(split=None):
        split = split if split is not None else [1, L - 1]
        return AsymmetricPipeline(cfg, params, split, [[dev]] * len(split))

    return cfg, pipe, L


def _mk_reqs(cfg, *, out_len=5, seed=3, n=6):
    rng = np.random.RandomState(seed)
    reqs = []
    for i in range(n):
        plen = 8 + int(rng.randint(0, 12))
        reqs.append(Request(
            rid=i, prompt=rng.randint(0, cfg.vocab_size,
                                      size=plen).astype(np.int32),
            max_new_tokens=out_len, arrival=0.4 * i))
    return reqs


def _serve(workers, reqs, roles=None, link=None):
    if roles is not None:
        wire_disaggregation(workers, roles, link)
    return run_serve_loop(workers, reqs, deadline=1e9, clock=VirtualClock())


# ---------------------------------------------------------------------------
# Wire round-trip: pool -> extract -> scatter -> pool
# ---------------------------------------------------------------------------

def test_kv_page_roundtrip_across_stage_splits(setup):
    """Pages extracted from a [1, L-1] pipeline land bit-identically in a
    [L-1, 1] pipeline: the wire format is per-GLOBAL-layer, so regrouping
    layers across stages is just a different iteration order."""
    cfg, pipe, L = setup
    src, dst = pipe([1, L - 1]), pipe([L - 1, 1])
    src.init_paged_caches(2, MAX_LEN, block_size=BLOCK)
    dst.init_paged_caches(2, MAX_LEN, block_size=BLOCK)
    # poke recognizable values into three src blocks of every layer
    rng = np.random.RandomState(0)
    src_blocks = [3, 1, 4]
    for si, st in enumerate(src.stages):
        for k, c in enumerate(src.paged_caches[si]):
            for n in ("k", "v"):
                arr = np.array(c[n])
                arr[src_blocks] = rng.standard_normal(
                    (3,) + arr.shape[1:]).astype(arr.dtype)
                c[n] = jax.numpy.asarray(arr)
    payload = src.extract_kv_pages([src_blocks] * len(src.stages))
    assert len(payload) == L
    nbytes = KVMigration.payload_bytes(payload)
    assert nbytes == sum(a.nbytes for lkv in payload
                         for a in lkv.values())
    dst_blocks = [5, 2, 1]
    dst.scatter_kv_pages([dst_blocks] * len(dst.stages), payload)
    # reassemble dst per global layer and compare against the wire
    got = dst.extract_kv_pages([dst_blocks] * len(dst.stages))
    for lkv_want, lkv_got in zip(payload, got):
        for n in ("k", "v"):
            np.testing.assert_array_equal(lkv_want[n], lkv_got[n])


def test_block_table_adopt_takes_over_references():
    pool = BlockPool(6, block_size=4)
    donor = pool.alloc(2)
    t = BlockTable(pool)
    t.adopt(donor)
    assert t.blocks == donor and pool.n_free == 3
    t.release()
    assert pool.n_free == 5


# ---------------------------------------------------------------------------
# End-to-end identity: disaggregated == colocated token streams
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served_colocated(setup):
    cfg, pipe, L = setup
    reqs = _mk_reqs(cfg)
    w = PagedPipelineBatcher(pipe(), n_slots=4, max_len=MAX_LEN,
                             block_size=BLOCK)
    _serve([w], reqs)
    assert all(r.output is not None and len(r.output) == r.max_new_tokens
               for r in reqs)
    return reqs


def test_disagg_bit_identical_to_colocated(setup, served_colocated):
    """Prefill on a [1, L-1] replica, decode on a [L-1, 1] replica (the
    stage splits deliberately differ): token streams must match colocated
    serving bit for bit, with every request migrated exactly once."""
    cfg, pipe, L = setup
    reqs = _mk_reqs(cfg)
    p = PagedPipelineBatcher(pipe([1, L - 1]), n_slots=4, max_len=MAX_LEN,
                             block_size=BLOCK, role="prefill")
    d = PagedPipelineBatcher(pipe([L - 1, 1]), n_slots=4, max_len=MAX_LEN,
                             block_size=BLOCK, role="decode")
    stats = _serve([p, d], reqs, roles=["prefill", "decode"], link=KVLink())
    for rc, rd in zip(served_colocated, reqs):
        assert list(rc.output) == list(rd.output), rc.rid
    assert stats.migrations == len(reqs)
    assert stats.migrated_kv_bytes > 0
    assert stats.rejected == 0 and stats.dropped == 0
    # the decode replica stamped first tokens; the prefill replica stamped
    # the handoffs, never a token
    assert all(r.first_token_time is not None
               and r.prefill_finish_time is not None
               and r.first_token_time >= r.prefill_finish_time
               for r in reqs)


def test_disagg_with_prefix_cache_and_chunking_identical(setup):
    """Warm-prefix + mid-prefill chunking on the PREFILL replica compose
    with the handoff: same tokens as cold colocated serving, with real
    prefix hits on the prefill side."""
    cfg, pipe, L = setup

    def wl():
        return shared_prefix_workload(
            rate=2.0, duration=4.0, vocab=cfg.vocab_size, shared_len=24,
            unique_len=6, out_len=5, seed=11)

    cold = wl()
    _serve([PagedPipelineBatcher(pipe(), n_slots=4, max_len=MAX_LEN,
                                 block_size=BLOCK)], cold)
    warm = wl()
    p = PagedPipelineBatcher(pipe(), n_slots=4, max_len=MAX_LEN,
                             block_size=BLOCK, role="prefill",
                             prefix_caching=True, prefill_chunk=BLOCK)
    d = PagedPipelineBatcher(pipe([L - 1, 1]), n_slots=4, max_len=MAX_LEN,
                             block_size=BLOCK, role="decode")
    stats = _serve([p, d], warm, roles=["prefill", "decode"], link=KVLink())
    for rc, rw in zip(cold, warm):
        assert list(rc.output) == list(rw.output), rc.rid
    assert stats.prefix_hits > 0
    assert stats.migrations == len(warm)


# ---------------------------------------------------------------------------
# Transfer-cost accounting on the virtual clock
# ---------------------------------------------------------------------------

def test_transfer_cost_delays_first_token_by_bytes_over_bandwidth(setup):
    cfg, pipe, L = setup

    def one():
        return [Request(rid=0, prompt=np.arange(16, dtype=np.int32),
                        max_new_tokens=4, arrival=0.0)]

    ttft, bytes_seen = {}, {}
    for gbps in (0.0, 1e-6):      # ideal link vs ~125 B per clock unit
        reqs = one()
        p = PagedPipelineBatcher(pipe(), n_slots=2, max_len=32,
                                 block_size=BLOCK, role="prefill")
        d = PagedPipelineBatcher(pipe(), n_slots=2, max_len=32,
                                 block_size=BLOCK, role="decode")
        st = _serve([p, d], reqs, roles=["prefill", "decode"],
                    link=KVLink(gbps=gbps))
        ttft[gbps] = reqs[0].first_token_time
        bytes_seen[gbps] = st.migrated_kv_bytes
    # payload size is exact: whole blocks of K and V for every layer
    nb = blocks_for_tokens(16, BLOCK)
    el = np.dtype(np.float32).itemsize
    want = nb * BLOCK * cfg.num_kv_heads * cfg.head_dim_ * el * 2 * L
    assert bytes_seen[0.0] == bytes_seen[1e-6] == want
    # and the finite link delays the first token by exactly bytes/bw on
    # the virtual clock (both runs pay the same prefill iterations)
    delay = want / (1e-6 * 1e9 / 8)
    assert ttft[1e-6] - ttft[0.0] == pytest.approx(delay, rel=1e-9)


def test_decode_replica_rejects_impossible_migration(setup):
    """A migration whose full generation can never fit the decode pools is
    rejected with an empty output instead of preempt-thrashing forever."""
    cfg, pipe, L = setup
    reqs = [Request(rid=0, prompt=np.arange(24, dtype=np.int32),
                    max_new_tokens=8, arrival=0.0)]
    p = PagedPipelineBatcher(pipe(), n_slots=2, max_len=MAX_LEN,
                             block_size=BLOCK, role="prefill")
    d = PagedPipelineBatcher(pipe(), n_slots=2, max_len=MAX_LEN,
                             block_size=BLOCK, role="decode",
                             stage_blocks=[3, 3])   # 2 usable blocks
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        stats = _serve([p, d], reqs, roles=["prefill", "decode"],
                       link=KVLink())
    assert stats.rejected == 1 and stats.migrations == 1
    assert not reqs[0].served and reqs[0].finish_time is not None


def test_kvlink_from_cluster_minimizes_latency_plus_transfer():
    """The per-pair link choice must minimize lat + bytes/bw PER PAYLOAD,
    like the scheduler's role search: a low-latency thin link wins small
    transfers, a fat high-latency link wins big ones."""
    from repro.core.cluster import Cluster, Device

    devs = [Device(0, "A6000", 0, "r0"), Device(1, "A6000", 1, "r0"),
            Device(2, "A6000", 2, "r0")]
    lat = np.zeros((3, 3))
    bw = np.full((3, 3), np.inf)
    # replica 0 = {0}; replica 1 = {1, 2}: two candidate links with
    # opposite strengths
    lat[0, 1] = lat[1, 0] = 1e-3; bw[0, 1] = bw[1, 0] = 1e6   # low lat, thin
    lat[0, 2] = lat[2, 0] = 1e-1; bw[0, 2] = bw[2, 0] = 1e12  # high lat, fat
    cluster = Cluster(devs, lat=lat, bw=bw)
    link = KVLink.from_cluster(cluster, [[0], [1, 2]])
    small, big = 100, 10 ** 9
    assert link.delay(small, 0, 1) == pytest.approx(1e-3 + small / 1e6)
    assert link.delay(big, 0, 1) == pytest.approx(1e-1 + big / 1e12)
    # never worse than either single link
    for n in (small, big, 10 ** 6):
        assert link.delay(n, 0, 1) <= min(1e-3 + n / 1e6, 1e-1 + n / 1e12)


# ---------------------------------------------------------------------------
# Router / engine gating
# ---------------------------------------------------------------------------

def test_engine_roles_and_gating(setup):
    cfg, pipe, L = setup
    asg = Assignment([
        PipelinePlan([StagePlan([0], 1), StagePlan([1], L - 1)], 0.1, 0.1),
        PipelinePlan([StagePlan([2], L)], 0.1, 0.1),
    ])
    eng = InferenceEngine(cfg, asg, key=KEY, policy="continuous",
                          n_slots=4, max_len=MAX_LEN, block_size=BLOCK,
                          disaggregate=True)
    assert eng.roles.count("prefill") == 1
    assert eng.roles.count("decode") == 1
    # the static engine cannot ship pages: falls back to colocated, loudly
    with warnings.catch_warnings(record=True) as ws:
        warnings.simplefilter("always")
        eng_s = InferenceEngine(cfg, asg, key=KEY, policy="static",
                                max_len=MAX_LEN, disaggregate=True)
    assert eng_s.roles == ["both", "both"]
    assert any("colocated" in str(w.message) for w in ws)


# ---------------------------------------------------------------------------
# Scheduler: role assignment as a search dimension
# ---------------------------------------------------------------------------

def test_role_split_matches_workload_shape():
    """Decode-heavy workloads want decode-majority splits; prefill-heavy
    ones shift replicas back toward prefill."""
    decode_heavy = [PhasedReplicaModel(0.1, 0.1, 0.8, 0.8)
                    for _ in range(4)]
    roles_d, att_d = best_role_split(decode_heavy, rate=3.0, deadline=2.5,
                                     duration=60.0)
    assert roles_d.count("decode") > roles_d.count("prefill")
    prefill_heavy = [PhasedReplicaModel(0.8, 0.8, 0.1, 0.1)
                     for _ in range(4)]
    roles_p, att_p = best_role_split(prefill_heavy, rate=3.0, deadline=2.5,
                                     duration=60.0)
    assert roles_p.count("prefill") >= roles_d.count("prefill")
    assert att_d > 0 and att_p > 0


def test_role_split_beats_colocated_on_heterogeneous_pool():
    """The HexGen-2 case: one compute-rich replica (fast prefill) + one
    memory-rich replica (slow prefill, deep decode queue). Colocated
    serving drags half the arrivals through the slow prefill; the split
    routes every prefill to the fast replica and wins attainment — even
    paying a real transfer cost."""
    models = [PhasedReplicaModel(0.2, 0.2, 1.0, 0.5, max_concurrent=4),
              PhasedReplicaModel(3.0, 3.0, 1.0, 0.25, max_concurrent=64)]
    col = slo_sim.simulate([m.colocated() for m in models], 1.5, 4.0,
                           duration=60.0)
    roles, att = best_role_split(models, rate=1.5, deadline=4.0,
                                 duration=60.0, kv_bytes=1e6, link_bw=1e9)
    assert roles == ["prefill", "decode"]
    assert att > col


def test_simulate_disagg_all_both_equals_simulate():
    models = [PhasedReplicaModel(0.2, 0.1, 0.6, 0.3, max_concurrent=8)
              for _ in range(2)]
    a = slo_sim.simulate([m.colocated() for m in models], 2.0, 3.0,
                         duration=40.0, seed=1)
    b = slo_sim.simulate_disagg(models, ["both", "both"], 2.0, 3.0,
                                duration=40.0, seed=1)
    assert a == b
