"""Paged KV cache: block pool/table invariants, paged-vs-contiguous
gather/scatter round-trips, the Pallas paged-decode kernel, and end-to-end
bit-identity of paged serving against contiguous serving (including under
preemption-by-recompute). The correctness bar for the whole refactor is
BIT-identity: the paged layout must change where cache bytes live, never
what attention computes."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st  # noqa: F401 (skips when absent)

from repro.configs import get_config
from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.paged_attention import paged_decode_attention_pallas
from repro.models import model as M
from repro.serving.block_manager import (BlockPool, BlockTable, NULL_BLOCK,
                                         blocks_for_tokens)
from repro.serving.continuous import PagedPipelineBatcher, PipelineBatcher
from repro.serving.pipeline import AsymmetricPipeline
from repro.serving.request import Request

KEY = jax.random.PRNGKey(0)


def rn(i, *shape):
    return jax.random.normal(jax.random.fold_in(KEY, i), shape, jnp.float32)


# ---------------------------------------------------------------------------
# Block pool / table bookkeeping
# ---------------------------------------------------------------------------

def test_block_pool_alloc_free_refcount():
    pool = BlockPool(6, block_size=8)        # 5 usable + null
    assert pool.n_free == 5
    got = pool.alloc(3)
    assert got is not None and len(set(got)) == 3 and NULL_BLOCK not in got
    assert pool.n_free == 2 and pool.n_used == 3
    assert pool.alloc(3) is None             # all-or-nothing
    assert pool.n_free == 2                  # failed alloc took nothing
    pool.incref(got[0])                      # prefix-sharing style alias
    pool.free(got[0])
    assert pool.n_free == 2                  # still referenced
    pool.free(got[0])
    assert pool.n_free == 3                  # now returned
    for b in got[1:]:
        pool.free(b)
    assert pool.n_free == 5


def test_block_table_grow_release():
    pool = BlockPool(5, block_size=4)
    t = BlockTable(pool)
    assert t.allocate_tokens(9)              # 3 blocks
    assert t.n_blocks == 3 and pool.n_free == 1
    assert t.ensure(10)                      # pos 10 -> 3 blocks, no growth
    assert t.n_blocks == 3
    assert t.ensure(12)                      # pos 12 -> 4th block
    assert t.n_blocks == 4 and pool.n_free == 0
    assert not t.ensure(16)                  # pool dry
    arr = t.as_array(6)
    assert arr.shape == (6,) and (arr[4:] == NULL_BLOCK).all()
    t.release()
    assert pool.n_free == 4 and t.n_blocks == 0


def test_block_table_fork_refcounts():
    pool = BlockPool(4, block_size=2)
    t = BlockTable(pool)
    assert t.allocate_tokens(4)
    f = t.fork()
    assert f.blocks == t.blocks
    t.release()
    assert pool.n_free == 1                  # fork still holds them
    f.release()
    assert pool.n_free == 3


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 4), st.integers(1, 24), st.integers(1, 8),
       st.integers(0, 10 ** 6))
def test_block_table_roundtrip_property(n_seqs, max_tokens, block_size, seed):
    """Property: scatter-to-pages then gather-through-tables reproduces the
    contiguous scatter_cache_rows layout for any (seqs, lengths, block
    size) — BlockTable gather/scatter and the contiguous path agree."""
    rng = np.random.RandomState(seed % (2 ** 31))
    lens = rng.randint(1, max_tokens + 1, size=n_seqs)
    max_blocks = blocks_for_tokens(max_tokens, block_size)
    S = max_blocks * block_size
    pool = BlockPool(1 + n_seqs * max_blocks, block_size)
    tables = []
    for L in lens:
        t = BlockTable(pool)
        assert t.allocate_tokens(int(L))
        tables.append(t)
    h, d = 2, 4
    rows = {"k": jnp.asarray(rng.randn(n_seqs, S, h, d), jnp.float32)}
    # contiguous: rows scattered into a slot pool, read back directly
    contig = M.scatter_cache_rows(
        {"k": jnp.zeros((n_seqs, S, h, d), jnp.float32)}, rows,
        list(range(n_seqs)))
    # paged: rows scattered into pages, gathered back through the tables
    dest = np.stack([t.as_array(max_blocks) for t in tables]).reshape(-1)
    pages = M.scatter_rows_to_pages(
        {"k": jnp.zeros((pool.n_blocks, block_size, h, d), jnp.float32)},
        rows, dest)
    bt = jnp.asarray(np.stack([t.as_array(max_blocks) for t in tables]))
    back = ref.gather_pages(pages["k"], bt)
    for i, L in enumerate(lens):
        # identical within the valid prefix; beyond it the null page
        # absorbs the padding (masked by kv_len everywhere it matters)
        nb = blocks_for_tokens(int(L), block_size)
        np.testing.assert_array_equal(
            np.asarray(back[i, :nb * block_size]),
            np.asarray(contig["k"][i, :nb * block_size]))


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("skv,kvb", [(100, 32), (129, 64)])
def test_decode_pallas_ragged_last_block(skv, kvb):
    """Satellite: skv need not divide kv_block — the final block is padded
    and masked instead of asserted away."""
    b, hq, hkv, d = 2, 4, 2, 32
    q = rn(1, b, 1, hq, d)
    k = rn(2, b, skv, hkv, d)
    v = rn(3, b, skv, hkv, d)
    kv_len = jnp.array([skv - 13, skv])
    o1 = decode_attention_pallas(q, k, v, kv_len=kv_len, kv_block=kvb,
                                 interpret=True)
    o2 = ref.decode_attention_ref(q, k, v, kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)
    # and with no kv_len at all
    o3 = decode_attention_pallas(q, k, v, kv_block=kvb, interpret=True)
    o4 = ref.decode_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(o3), np.asarray(o4), atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernel_vs_ref(dtype):
    b, hq, hkv, d = 2, 4, 2, 32
    bs, n_blocks, nb = 16, 16, 5
    q = rn(1, b, 1, hq, d).astype(dtype)
    kp = rn(2, n_blocks, bs, hkv, d).astype(dtype)
    vp = rn(3, n_blocks, bs, hkv, d).astype(dtype)
    bt = jnp.asarray(
        np.array([[3, 1, 4, 0, 0], [5, 9, 2, 6, 8]], np.int32))
    kv_len = jnp.array([41, 80])             # ragged + full tables
    o1 = paged_decode_attention_pallas(q, kp, vp, bt, kv_len=kv_len,
                                       interpret=True)
    o2 = ref.paged_decode_attention_ref(q, kp, vp, bt, kv_len=kv_len)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), atol=tol)


def test_paged_ops_xla_bit_identical_to_contiguous():
    """The ops.paged_decode_attention XLA path must be BITWISE equal to
    contiguous decode on the gathered cache (same shapes, same HLO)."""
    b, hq, hkv, d = 2, 4, 2, 16
    bs, n_blocks, nb = 8, 12, 4
    q = rn(1, b, 1, hq, d)
    kp = rn(2, n_blocks, bs, hkv, d)
    vp = rn(3, n_blocks, bs, hkv, d)
    bt = jnp.asarray(np.array([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32))
    kv_len = jnp.array([19, 32])
    o_paged = ops.paged_decode_attention(q, kp, vp, bt, kv_len=kv_len)
    o_contig = ops.decode_attention(q, ref.gather_pages(kp, bt),
                                    ref.gather_pages(vp, bt), kv_len=kv_len)
    assert np.array_equal(np.asarray(o_paged), np.asarray(o_contig))


# ---------------------------------------------------------------------------
# Model-level bit-identity (monolithic decode_step_paged)
# ---------------------------------------------------------------------------

def test_decode_step_paged_bit_identical():
    cfg = get_config("granite-8b").reduced()
    params = M.init_params(cfg, KEY)
    rng = np.random.RandomState(0)
    n_slots, slot_len, bs = 2, 32, 8
    nbmax = slot_len // bs
    lens = np.array([5, 9], np.int32)
    toks = np.zeros((n_slots, 16), np.int32)
    for i in range(n_slots):
        toks[i, :lens[i]] = rng.randint(0, cfg.vocab_size, lens[i])

    scratch = M.init_cache(cfg, n_slots, slot_len)
    lg, scratch = M.prefill(cfg, params, {"tokens": jnp.asarray(toks)},
                            scratch, lens=jnp.asarray(lens))
    pool_c = M.scatter_cache_rows(M.init_cache(cfg, n_slots, slot_len),
                                  scratch, [0, 1], batch_axis=1)
    bt = (1 + np.arange(n_slots * nbmax, dtype=np.int32)
          ).reshape(n_slots, nbmax)
    pool_p = {
        k: M.scatter_cache_rows_paged(
            M.init_paged_cache(cfg, 1 + n_slots * nbmax, bs, n_slots)[k],
            scratch[k], [0, 1], bt.reshape(-1), batch_axis=1)
        for k in scratch}

    pos = lens.copy()
    lg_c = lg_p = np.asarray(lg)
    for step in range(6):
        nxt = jnp.asarray(np.argmax(lg_c, -1).astype(np.int32))
        lg_c, pool_c = M.decode_step(cfg, params, nxt, pool_c,
                                     jnp.asarray(pos))
        nxt_p = jnp.asarray(np.argmax(lg_p, -1).astype(np.int32))
        lg_p, pool_p = M.decode_step_paged(cfg, params, nxt_p, pool_p,
                                           jnp.asarray(pos),
                                           jnp.asarray(bt))
        lg_c, lg_p = np.asarray(lg_c), np.asarray(lg_p)
        assert np.array_equal(lg_c, lg_p), f"step {step} diverged"
        pos += 1


# ---------------------------------------------------------------------------
# End-to-end: paged serving == contiguous serving on a 2-stage pipeline
# ---------------------------------------------------------------------------

def _mk_reqs(cfg, *, n=4, max_new=5, seed=0):
    rng = np.random.RandomState(seed)
    return [Request(rid=i,
                    prompt=rng.randint(0, cfg.vocab_size,
                                       size=5 + 3 * i).astype(np.int32),
                    max_new_tokens=max_new, arrival=0.02 * i)
            for i in range(n)]


def _pipe(cfg, params):
    dev = jax.devices()[0]
    L = cfg.num_layers
    return AsymmetricPipeline(cfg, params, [1, L - 1], [[dev], [dev]])


@pytest.mark.parametrize("arch", ["granite-8b", "jamba-v0.1-52b"])
def test_pipeline_paged_equals_contiguous(arch):
    """Tentpole gate: on a 2-stage asymmetric pipeline, paged serving must
    produce the same tokens as contiguous serving for every request —
    including hybrid stacks where recurrent layers keep O(1) slot states
    while attention layers page."""
    cfg = get_config(arch).reduced()
    params = M.init_params(cfg, KEY)
    reqs_c = _mk_reqs(cfg)
    PipelineBatcher(_pipe(cfg, params), n_slots=3,
                    max_len=48).serve(reqs_c, deadline=1e9)
    reqs_p = _mk_reqs(cfg)
    stats = PagedPipelineBatcher(_pipe(cfg, params), n_slots=3, max_len=48,
                                 block_size=8).serve(reqs_p, deadline=1e9)
    assert stats.preemptions == 0            # full-occupancy pool
    for rc, rp in zip(reqs_c, reqs_p):
        assert list(rc.output) == list(rp.output), rc.rid


def test_paged_preemption_recomputes_identically():
    """A pool too small for all slots' full generations forces
    preempt-by-recompute; the evicted requests still finish with exactly
    the tokens contiguous serving produces."""
    cfg = get_config("granite-8b").reduced()
    params = M.init_params(cfg, KEY)

    def reqs(seed=1):
        rng = np.random.RandomState(seed)
        return [Request(rid=i,
                        prompt=rng.randint(0, cfg.vocab_size,
                                           size=6).astype(np.int32),
                        max_new_tokens=20, arrival=0.0) for i in range(3)]

    reqs_c = reqs()
    PipelineBatcher(_pipe(cfg, params), n_slots=3,
                    max_len=32).serve(reqs_c, deadline=1e9)
    # each request ends at 26 tokens = 4 blocks of 8; three concurrent
    # need 12 blocks but the pools hold 8 usable -> eviction mid-decode
    reqs_p = reqs()
    stats = PagedPipelineBatcher(
        _pipe(cfg, params), n_slots=3, max_len=32, block_size=8,
        stage_blocks=[9, 9], admit_headroom=2).serve(reqs_p, deadline=1e9)
    assert stats.preemptions > 0
    for rc, rp in zip(reqs_c, reqs_p):
        assert list(rc.output) == list(rp.output), rc.rid


@pytest.mark.parametrize("prefill_chunk", [0, 8])
def test_recomputed_tokens_count_each_thrown_away_token_once(prefill_chunk):
    """Preemption throws away a slot's cached tokens (its prompt, and its
    outputs once decoding) and computes them again. The tokens computed
    in all (prefilled, and decoded by live rows) exceed those of a pool
    that never preempts by exactly ``recomputed_tokens``, and each
    ``preempt`` instant carries the tokens it threw away."""
    from repro.obs.trace import Tracer
    cfg = get_config("granite-8b").reduced()
    params = M.init_params(cfg, KEY)

    def serve(stage_blocks):
        rng = np.random.RandomState(3)
        reqs = [Request(rid=i, prompt=rng.randint(
                    0, cfg.vocab_size, size=6 + 5 * i).astype(np.int32),
                        max_new_tokens=14, arrival=0.0) for i in range(3)]
        eng = PagedPipelineBatcher(
            _pipe(cfg, params), n_slots=3, max_len=40, block_size=8,
            stage_blocks=stage_blocks, admit_headroom=2,
            prefill_chunk=prefill_chunk)
        eng.tracer = Tracer()
        stats = eng.serve(reqs, deadline=1e9)
        ev = eng.tracer.events
        work = stats.prefill_tokens + sum(
            e["args"]["rows"] for e in ev if e["name"] == "decode")
        thrown = [e["args"]["tokens"] for e in ev if e["name"] == "preempt"]
        return stats, work, thrown, [r.output for r in reqs]

    full, work_full, _, out_full = serve(None)
    small, work_small, thrown, out_small = serve([9, 9])
    assert full.preemptions == full.recomputed_tokens == 0
    assert small.preemptions == len(thrown) > 0
    assert small.recomputed_tokens == sum(thrown) > 0
    assert work_small - work_full == small.recomputed_tokens
    assert [list(o) for o in out_small] == [list(o) for o in out_full]


def test_oversized_request_rejected_and_counted():
    cfg = get_config("granite-8b").reduced()
    params = M.init_params(cfg, KEY)
    reqs = _mk_reqs(cfg, n=2) + [
        Request(rid=99, prompt=np.arange(40, dtype=np.int32),
                max_new_tokens=20, arrival=0.0)]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        stats = PagedPipelineBatcher(
            _pipe(cfg, params), n_slots=2, max_len=32,
            block_size=8).serve(reqs, deadline=1e9)
    assert stats.rejected == 1
    assert len(reqs[-1].output) == 0
    for r in reqs[:2]:
        assert len(r.output) == r.max_new_tokens
    # a rejected request served nobody: it cannot count as SLO-attained
    assert stats.attainment == pytest.approx(2 / 3)


def test_search_kv_capacity_bound():
    """kv_block_size threads cost_model.concurrent_capacity into the
    genetic search's simulated replicas: bounding capacity can only lower
    simulated attainment."""
    from repro.core import cluster as cl
    from repro.core import cost_model as cm
    from repro.core.genetic import Evaluator
    from repro.core.plan import PipelinePlan, StagePlan
    task = cm.Task(batch=1, s_in=128, s_out=64)
    prof = cm.ModelProfile.from_config(get_config("llama2-70b"),
                                       paper_exact=True)
    c = cl.case_study_cluster()
    # the paper's feasible case-study layout: [4,2,2] GPUs / 48-20-12 layers
    plan = PipelinePlan([StagePlan([0, 1, 2, 3], 48), StagePlan([4, 5], 20),
                        StagePlan([6, 7], 12)], cost=1.0, bottleneck=0.2)
    ev_ideal = Evaluator(c, prof, task, deadline=3.0, rate=4.0)
    ev_paged = Evaluator(c, prof, task, deadline=3.0, rate=4.0,
                         kv_block_size=16)
    assert ev_ideal._max_concurrent(plan) == 0            # unbounded
    mc = ev_paged._max_concurrent(plan)
    assert mc > 0
    # the bound is the TIGHTEST stage's capacity
    assert mc == min(
        cm.concurrent_capacity(c, st.device_ids, st.num_layers, prof,
                               task, block_size=16)
        for st in plan.stages)


# ---------------------------------------------------------------------------
# Scheduler-side block accounting
# ---------------------------------------------------------------------------

def test_cost_model_block_granularity():
    from repro.core import cluster as cl
    from repro.core import cost_model as cm
    task = cm.Task(batch=1, s_in=128, s_out=64)
    prof = cm.ModelProfile.from_config(get_config("llama2-70b"),
                                       paper_exact=True)
    c = cl.case_study_cluster()
    devs = [0, 1, 2, 3]
    # paged rounds actual usage UP to whole blocks...
    m0 = cm.mem_bytes_per_device(c, devs, 48, prof, task)
    m1 = cm.mem_bytes_per_device(c, devs, 48, prof, task, block_size=24)
    assert m1 >= m0
    # ...but capacity planning no longer reserves worst-case rows: far
    # more concurrent sequences fit in the same memory
    contig = cm.concurrent_capacity(c, devs, 48, prof, task, max_len=2048)
    paged = cm.concurrent_capacity(c, devs, 48, prof, task, block_size=16)
    assert paged >= 2 * contig


def test_slo_sim_reflects_paged_capacity():
    from repro.core.slo_sim import ReplicaModel, simulate
    kw = dict(rate=4.0, deadline=3.0, duration=30.0)
    tight = simulate([ReplicaModel(1.0, 0.2, max_concurrent=1)], **kw)
    roomy = simulate([ReplicaModel(1.0, 0.2, max_concurrent=8)], **kw)
    free = simulate([ReplicaModel(1.0, 0.2)], **kw)
    assert tight < roomy <= free


# ---------------------------------------------------------------------------
# Quantized KV pages (int8/fp8 payload pools + per-token-per-head scales)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_quant_kernels_bit_identical_to_materialized_dequant(kv_dtype):
    """The exactness gate for fused dequant: each quantized Pallas kernel
    (interpret mode) against its unquantized twin run on pre-dequantized
    pages. In-register dequant performs the exact same float32 multiply
    the oracle materializes, so the dequantized values are identical.

    int8 outputs are BITWISE identical. fp8 outputs agree within a few
    float32 ulps of the output's scale: XLA's CPU backend fuses the
    fp8 -> f32 convert and the scale multiply into the score dot's loop
    fusion, which sums the dot in a different order than the library dot
    the materialized twin runs (the dequantized operands themselves are
    bit-equal). A dequant that rounded through a narrower type would miss
    by far more than this bound."""
    from repro.kernels.paged_attention import (
        paged_context_attention_pallas, paged_context_attention_quant_pallas,
        paged_decode_attention_quant_pallas, paged_verify_attention_pallas,
        paged_verify_attention_quant_pallas)
    from repro.models import quant as Q

    def assert_fused_matches(fused, mat):
        fused, mat = np.asarray(fused), np.asarray(mat)
        if kv_dtype == "int8":
            assert np.array_equal(fused, mat)
            return
        ulp = np.finfo(np.float32).eps * np.abs(mat).max()
        assert np.abs(fused - mat).max() <= 4 * ulp

    b, hq, hkv, d = 2, 4, 2, 32
    bs, n_blocks = 16, 16
    k = rn(2, n_blocks, bs, hkv, d)
    v = rn(3, n_blocks, bs, hkv, d)
    kq, ks = Q.quantize_kv_rows(k, kv_dtype)
    vq, vs = Q.quantize_kv_rows(v, kv_dtype)
    kd, vd = Q.dequantize_kv(kq, ks), Q.dequantize_kv(vq, vs)

    bt = jnp.asarray(np.array([[3, 1, 4, 0, 0], [5, 9, 2, 6, 8]], np.int32))
    q = rn(1, b, 1, hq, d)
    kv_len = jnp.array([41, 80])             # ragged + full tables
    o_fused = paged_decode_attention_quant_pallas(
        q, kq, vq, ks, vs, bt, kv_len=kv_len, interpret=True)
    o_mat = paged_decode_attention_pallas(q, kd, vd, bt, kv_len=kv_len,
                                          interpret=True)
    assert_fused_matches(o_fused, o_mat)

    qc = rn(4, b, 8, hq, d)
    q_start = jnp.array([5, 0])
    c_len = jnp.array([13, 8])
    o_fused = paged_context_attention_quant_pallas(
        qc, kq, vq, ks, vs, bt, q_start=q_start, kv_len=c_len,
        interpret=True)
    o_mat = paged_context_attention_pallas(
        qc, kd, vd, bt, q_start=q_start, kv_len=c_len, interpret=True)
    assert_fused_matches(o_fused, o_mat)

    qv = rn(5, b, 4, hq, d)
    kv_start = jnp.array([41, 76])
    v_len = jnp.array([45, 78])              # ragged candidate counts
    o_fused = paged_verify_attention_quant_pallas(
        qv, kq, vq, ks, vs, bt, kv_start=kv_start, kv_len=v_len,
        interpret=True)
    o_mat = paged_verify_attention_pallas(
        qv, kd, vd, bt, kv_start=kv_start, kv_len=v_len, interpret=True)
    assert_fused_matches(o_fused, o_mat)


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_quant_kernels_vs_oracle_and_xla_dispatch(kv_dtype):
    """Quantized Pallas kernels against the pure-JAX dequant-whole-pool
    oracles at the repo's established kernel tolerance, and the ops XLA
    dispatch BITWISE against contiguous decode on dequantized gathered
    pages (mirroring test_paged_ops_xla_bit_identical_to_contiguous)."""
    from repro.kernels.paged_attention import (
        paged_decode_attention_quant_pallas)
    from repro.models import quant as Q

    b, hq, hkv, d = 2, 4, 2, 32
    bs, n_blocks = 16, 12
    k = rn(2, n_blocks, bs, hkv, d)
    v = rn(3, n_blocks, bs, hkv, d)
    kq, ks = Q.quantize_kv_rows(k, kv_dtype)
    vq, vs = Q.quantize_kv_rows(v, kv_dtype)
    bt = jnp.asarray(np.array([[3, 1, 4, 0], [5, 9, 2, 6]], np.int32))
    q = rn(1, b, 1, hq, d)
    kv_len = jnp.array([19, 64])

    o_pal = paged_decode_attention_quant_pallas(
        q, kq, vq, ks, vs, bt, kv_len=kv_len, interpret=True)
    o_ref = ref.paged_decode_attention_quant_ref(
        q, kq, vq, ks, vs, bt, kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               atol=2e-5)

    o_ops = ops.paged_decode_attention(q, kq, vq, bt, kv_len=kv_len,
                                       k_scale=ks, v_scale=vs)
    o_contig = ops.decode_attention(
        q, ref.gather_pages(ref.dequant_pages(kq, ks), bt),
        ref.gather_pages(ref.dequant_pages(vq, vs), bt), kv_len=kv_len)
    assert np.array_equal(np.asarray(o_ops), np.asarray(o_contig))


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_quant_scatter_gather_roundtrip_error_bound(kv_dtype):
    """quantize -> scatter_rows_to_pages -> gather -> dequant round-trip:
    the landed pages must equal direct quantization of the rows (scatter
    adds no error), and the dequantized values must sit within the
    scheme's per-element bound of the originals."""
    from repro.models import quant as Q

    m, S, h, d, bs = 2, 16, 2, 32, 8
    n_blocks = 1 + m * (S // bs)
    rows = {"k": rn(11, m, S, h, d), "v": rn(12, m, S, h, d)}
    pool = {
        "k": jnp.zeros((n_blocks, bs, h, d), Q.kv_storage_dtype(kv_dtype)),
        "v": jnp.zeros((n_blocks, bs, h, d), Q.kv_storage_dtype(kv_dtype)),
        "k_scale": jnp.zeros((n_blocks, bs, h), jnp.float32),
        "v_scale": jnp.zeros((n_blocks, bs, h), jnp.float32),
    }
    dest = jnp.arange(1, n_blocks, dtype=jnp.int32)
    out = M.scatter_rows_to_pages(pool, rows, dest)
    for n in ("k", "v"):
        direct_q, direct_s = Q.quantize_kv_rows(rows[n], kv_dtype)
        landed_q = np.asarray(out[n][dest]).reshape(m, S, h, d)
        landed_s = np.asarray(out[n + "_scale"][dest]).reshape(m, S, h)
        np.testing.assert_array_equal(
            landed_q, np.asarray(direct_q, landed_q.dtype))
        np.testing.assert_array_equal(landed_s, np.asarray(direct_s))
        back = np.asarray(Q.dequantize_kv(out[n][dest], out[n + "_scale"][dest])
                          ).reshape(m, S, h, d)
        want = np.asarray(rows[n])
        if kv_dtype == "int8":
            # symmetric rounding: at most half a quantization step per
            # element, with the step set by each token-head's scale
            step = np.asarray(direct_s)[..., None]
            assert (np.abs(back - want) <= step * 0.51).all()
        else:
            # fp8 e4m3: half-ulp relative error (2^-4) in the normal
            # range plus the fixed subnormal step (2^-9 of the scale)
            # for elements that quantize below the min normal exponent
            step = np.asarray(direct_s)[..., None]
            assert (np.abs(back - want)
                    <= np.abs(want) * 0.0625 + step * 0.0021).all()


def test_quant_pool_init_guard_layers_and_legacy_width():
    """init_layer_paged_cache: kv_dtype=None keeps the legacy pool (no
    scale leaves, model dtype); "bf16" forces the storage width without
    scales; "int8" adds f32 scale pools; guard layers ignore kv_dtype."""
    cfg = get_config("granite-8b").reduced()
    legacy = M.init_layer_paged_cache(cfg, 1, 6, 8, 2)
    assert "k_scale" not in legacy
    assert legacy["k"].dtype == jnp.dtype(cfg.dtype)
    wide = M.init_layer_paged_cache(cfg, 1, 6, 8, 2, kv_dtype="bf16")
    assert "k_scale" not in wide and wide["k"].dtype == jnp.bfloat16
    quant = M.init_layer_paged_cache(cfg, 1, 6, 8, 2, kv_dtype="int8")
    assert quant["k"].dtype == jnp.int8
    assert quant["k_scale"].dtype == jnp.float32
    assert quant["k_scale"].shape == quant["k"].shape[:3]
    guarded = M.init_layer_paged_cache(cfg, 1, 6, 8, 2, kv_dtype="int8",
                                       kv_guard_layers=(1,))
    assert "k_scale" not in guarded
    assert guarded["k"].dtype == jnp.dtype(cfg.dtype)


def test_cow_after_quantize_copies_scales_with_payload():
    """COW safety on quantized pools: copy_cache_pages must duplicate the
    scale leaves alongside the payload — a payload copied without its
    scales dequantizes to garbage — and writing to the copy must leave
    the source page untouched (the refcount contract)."""
    cfg = get_config("granite-8b").reduced()
    cache = M.init_paged_cache(cfg, 6, 4, 2, kv_dtype="int8")
    poked = {}
    for lk, sub in cache.items():
        if "k_scale" not in sub:
            poked[lk] = sub
            continue
        poked[lk] = {
            "k": sub["k"].at[:, 2].set(7),
            "v": sub["v"].at[:, 2].set(-7),
            "k_scale": sub["k_scale"].at[:, 2].set(0.25),
            "v_scale": sub["v_scale"].at[:, 2].set(0.5),
        }
    out = M.copy_cache_pages(poked, [2], [4])
    checked = 0
    for lk, sub in out.items():
        if "k_scale" not in sub:
            continue
        checked += 1
        for n in ("k", "v", "k_scale", "v_scale"):
            np.testing.assert_array_equal(np.asarray(sub[n][:, 4]),
                                          np.asarray(poked[lk][n][:, 2]))
        # divergence after the copy: the source page keeps its contents
        div = sub["k_scale"].at[:, 4].set(9.0)
        assert (np.asarray(div[:, 2]) == 0.25).all()
    assert checked > 0


@pytest.mark.parametrize("kv_dtype", ["int8", "bf16"])
def test_paged_serving_int8_pool_matches_fp32_tokens(kv_dtype):
    """End-to-end: serving with a quantized (or narrowed) page pool must
    produce the same greedy tokens as the model-precision pool on a short
    workload — KV quantization error at these scales stays under the
    argmax margin on all but a near-tie logit pair, so at most one
    request may diverge (the statistical match RATE is measured by
    benchmarks/bench_quant_kv.py, not asserted here) — and report the
    byte savings in ServeStats."""
    cfg = get_config("granite-8b").reduced()
    params = M.init_params(cfg, KEY)
    reqs_f = _mk_reqs(cfg)
    PagedPipelineBatcher(_pipe(cfg, params), n_slots=3, max_len=48,
                         block_size=8).serve(reqs_f, deadline=1e9)
    reqs_q = _mk_reqs(cfg)
    eng = PagedPipelineBatcher(_pipe(cfg, params), n_slots=3, max_len=48,
                               block_size=8, kv_dtype=kv_dtype)
    stats = eng.serve(reqs_q, deadline=1e9)
    assert stats.kv_bytes_resident > 0
    assert stats.kv_bytes_saved > 0
    assert f"kv=" in stats.summary()
    matched = sum(list(rf.output) == list(rq.output)
                  for rf, rq in zip(reqs_f, reqs_q))
    assert matched >= len(reqs_f) - 1, (matched, len(reqs_f))


def test_quant_serving_guard_layers_stay_model_precision():
    cfg = get_config("granite-8b").reduced()
    params = M.init_params(cfg, KEY)
    # the reduced config has 2 layers; guard the first only so the test
    # still sees one quantized pool alongside the pinned one
    eng = PagedPipelineBatcher(_pipe(cfg, params), n_slots=2, max_len=48,
                               block_size=8, kv_dtype="int8",
                               kv_guard_layers=(0,))
    reqs = _mk_reqs(cfg, n=2)
    eng.serve(reqs, deadline=1e9)
    dts = set()
    for st_caches in eng.pipeline.paged_caches:
        for c in st_caches:
            if isinstance(c, dict) and "k" in c:
                dts.add(np.asarray(c["k"]).dtype.name)
    # both the guarded (model-precision) and the quantized pools exist
    assert "int8" in dts and len(dts) == 2, dts


# ---------------------------------------------------------------------------
# Scheduler-side precision pricing
# ---------------------------------------------------------------------------

def test_cost_model_kv_dtype_pricing():
    from repro.core import cluster as cl
    from repro.core import cost_model as cm
    task = cm.Task(batch=1, s_in=128, s_out=64)
    prof = cm.ModelProfile.from_config(get_config("llama2-70b"),
                                       paper_exact=True)
    c = cl.case_study_cluster()
    devs = [0, 1, 2, 3]
    base = cm.concurrent_capacity(c, devs, 48, prof, task, block_size=16)
    for name, payload in (("int8", 1.0), ("fp8", 1.0), ("bf16", 2.0)):
        capped = cm.concurrent_capacity(c, devs, 48, prof, task,
                                        block_size=16, kv_dtype=name)
        eff = cm.kv_dtype_bytes_per_el(name)
        want = task.bytes_per_el / eff
        assert capped >= base, (name, capped, base)
        # capacity scales (within rounding) by the width ratio
        assert abs(capped / base - want) / want < 0.1, (name, capped, base)
        mig0 = cm.kv_migration_bytes(prof, task, block_size=16)
        mig1 = cm.kv_migration_bytes(prof, task, block_size=16,
                                     kv_dtype=name)
        assert mig1 == pytest.approx(mig0 * eff / task.bytes_per_el)
    # int8 at a bf16 task: ~1.94x capacity, ~1.94x fewer migration bytes
    int8 = cm.concurrent_capacity(c, devs, 48, prof, task, block_size=16,
                                  kv_dtype="int8")
    assert int8 >= 1.8 * base


def test_choose_kv_dtypes_quantizes_only_memory_bound_replicas():
    from repro.core.genetic import choose_kv_dtypes
    from repro.core.plan import PipelinePlan, StagePlan

    plans = [PipelinePlan([StagePlan([0], 48)], cost=1.0, bottleneck=0.5),
             PipelinePlan([StagePlan([1], 48)], cost=1.0, bottleneck=0.5)]
    # replica 0 roomy, replica 1 memory-bound at default precision
    caps = {0: 100, 1: 1}

    def capacity_at(p, kvd):
        return caps[p.stages[0].device_ids[0]]
    out = choose_kv_dtypes(plans, capacity_at, rate=4.0)
    assert out == [None, "int8"]


def test_search_kv_dtype_lands_in_result():
    """kv_dtype_search=True: the genetic search reports a per-replica
    precision vector aligned with the winning assignment, quantizing the
    capacity-constrained replicas."""
    from repro.core import cluster as cl
    from repro.core import cost_model as cm
    from repro.core.scheduler import schedule
    task = cm.Task(batch=1, s_in=512, s_out=256)
    res = schedule(cl.case_study_cluster(), "llama2-70b", task,
                   deadline=10.0, rate=40.0, iters=6, seed=0,
                   paper_exact=True, kv_block_size=16,
                   kv_dtype_search=True)
    assert res.kv_dtypes is not None
    assert len(res.kv_dtypes) == len(res.assignment.pipelines)
    assert all(d in (None, "int8", "fp8") for d in res.kv_dtypes)
    # the demanding workload must push at least one replica to quantize
    assert any(d is not None for d in res.kv_dtypes), res.kv_dtypes
