"""Copy-on-write prefix caching + chunked prefill on the paged engine.

Correctness bar (same as the paged refactor): warm-prefix serving and
chunked prefill must change WHERE prefill compute and cache bytes come
from, never what gets generated — outputs are token-identical to cold
one-shot serving on a multi-stage asymmetric pipeline. Host-side refcount
bookkeeping (PrefixIndex / BlockTable.writable) is checked against an
independent reference-count model under randomized match/alias/COW/release
interleavings: no block leaked, none double-freed.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st  # noqa: F401 (skips when absent)

from repro.configs import get_config
from repro.kernels import ops, ref
from repro.kernels.paged_attention import paged_context_attention_pallas
from repro.models import model as M
from repro.serving.block_manager import (BlockPool, BlockTable, NULL_BLOCK,
                                         PrefixIndex, blocks_for_tokens,
                                         chunk_hashes)
from repro.serving.continuous import PagedPipelineBatcher, PipelineBatcher
from repro.serving.pipeline import AsymmetricPipeline, context_mode_supported
from repro.serving.request import Request, shared_prefix_workload

KEY = jax.random.PRNGKey(0)


def rn(i, *shape):
    return jax.random.normal(jax.random.fold_in(KEY, i), shape, jnp.float32)


# ---------------------------------------------------------------------------
# Host-side bookkeeping: chunk hashes, index, COW
# ---------------------------------------------------------------------------

def test_chunk_hashes_prefix_property():
    bs = 4
    a = np.array([1, 2, 3, 4, 5, 6, 7, 8, 9], np.int32)
    b = np.array([1, 2, 3, 4, 9, 9, 9, 9], np.int32)    # diverges in chunk 2
    ha, hb = chunk_hashes(a, bs), chunk_hashes(b, bs)
    assert len(ha) == 2 and len(hb) == 2                # full blocks only
    assert ha[0] == hb[0] and ha[1] != hb[1]
    # chained: equal chunk content under a different PREFIX hashes apart
    c = np.array([9, 9, 9, 9, 5, 6, 7, 8], np.int32)
    assert chunk_hashes(c, bs)[1] != ha[1]


def test_prefix_index_match_acquire_register_evict():
    pool = BlockPool(8, block_size=4)
    ix = PrefixIndex(pool)
    prompt = np.arange(12, dtype=np.int32)
    hs = chunk_hashes(prompt, 4)
    t = BlockTable(pool)
    assert t.allocate_tokens(12)
    assert ix.match_len(hs) == 0
    ix.register(hs, t.blocks)
    assert ix.match_len(hs) == 3
    assert all(pool.ref(b) == 2 for b in t.blocks)      # table + index
    # a second request aliases the whole indexed prefix
    t2 = BlockTable(pool, ix.acquire(hs))
    assert t2.blocks == t.blocks
    assert all(pool.ref(b) == 3 for b in t.blocks)
    # owners release: blocks stay resident (index ref), become evictable
    t.release()
    t2.release()
    assert pool.n_free == 4 and ix.n_evictable() == 3
    assert ix.match_len(hs) == 3                        # cache survived
    # pool pressure evicts LRU-first and unmaps — a registered chain is
    # touched head-most-recent, so eviction peels it from the TAIL and
    # the head stays matchable (chained-hash matches are head-first)
    assert ix.evict(2) == 2
    assert pool.n_free == 6
    assert ix.match_len(hs) == 1                        # head chunk survives
    ix.clear()
    assert pool.n_free == 7 and len(ix) == 0


def test_block_table_writable_cow():
    pool = BlockPool(5, block_size=4)
    t = BlockTable(pool)
    assert t.allocate_tokens(8)
    assert t.writable(0) is None                        # exclusive already
    f = t.fork()
    src = t.blocks[0]
    cow = t.writable(0)
    assert cow is not None and cow is not False
    assert cow == (src, t.blocks[0]) and t.blocks[0] != src
    assert pool.ref(src) == 1 and pool.ref(t.blocks[0]) == 1
    # drain the pool: a COW on the still-shared block 1 must fail gracefully
    f2 = t.fork()
    extra = pool.alloc(pool.n_free)
    assert pool.n_free == 0
    assert t.writable(1) is False
    for b in extra:
        pool.free(b)
    f.release()
    f2.release()
    t.release()
    assert pool.n_free == 4


@settings(max_examples=30, deadline=None)
@given(st.integers(2, 12), st.integers(1, 8), st.integers(0, 10 ** 6))
def test_prefix_refcount_invariants_property(n_usable, block_size, seed):
    """Random interleavings of admit(match/alias + alloc + register),
    COW-write, release, and evict against an independent model of who
    holds references: pool refcounts must equal table-holds + index-holds
    for every block, nothing leaks, nothing double-frees (BlockPool
    asserts), and draining everything returns the pool to full."""
    rng = np.random.RandomState(seed % (2 ** 31))
    pool = BlockPool(n_usable + 1, block_size)
    ix = PrefixIndex(pool)
    tables = []                     # live (table, hashes) pairs

    def check():
        holds = np.zeros(pool.n_blocks, np.int64)
        for t, _ in tables:
            for b in t.blocks:
                holds[b] += 1
        for b in ix._lru:
            holds[b] += 1
        for b in range(1, pool.n_blocks):
            assert pool.ref(b) == holds[b], (b, pool.ref(b), holds[b])
        assert pool.n_free == (pool.n_blocks - 1) - int(
            np.count_nonzero(holds[1:]))
        # the O(1) evictable counter must agree with a full scan
        assert ix.n_evictable() == sum(
            1 for bid in ix._lru if pool.ref(bid) == 1)

    for _ in range(30):
        op = rng.randint(4)
        if op == 0:                 # admit a prompt from a tiny alphabet
            n_tok = rng.randint(1, 3 * block_size + 2)
            prompt = rng.randint(0, 3, size=n_tok)
            hs = chunk_hashes(prompt, block_size)
            L = ix.match_len(hs)
            t = BlockTable(pool, ix.acquire(hs[:L]))
            if not t.allocate_tokens(n_tok):
                need = blocks_for_tokens(n_tok, block_size) - t.n_blocks
                ix.evict(need - pool.n_free)
                if not t.allocate_tokens(n_tok):
                    t.release()
                    continue
            ix.register(hs, t.blocks[:len(hs)])
            tables.append((t, hs))
        elif op == 1 and tables:    # COW-write a random block
            t, _ = tables[rng.randint(len(tables))]
            if t.blocks:
                bi = rng.randint(len(t.blocks))
                if pool.n_free == 0:
                    ix.evict(1)
                t.writable(bi)      # None/False/copy all legal
        elif op == 2 and tables:    # release a random request
            t, _ = tables.pop(rng.randint(len(tables)))
            t.release()
        else:                       # background eviction pressure
            ix.evict(rng.randint(1, 3))
        check()

    for t, _ in tables:
        t.release()
    ix.clear()
    assert pool.n_free == pool.n_blocks - 1


# ---------------------------------------------------------------------------
# Kernels: paged context attention (warm-prefix / chunked-prefill primitive)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_context_kernel_vs_ref(dtype):
    b, C, hq, hkv, d = 2, 8, 4, 2, 32
    bs, n_blocks, nb = 8, 16, 6
    q = rn(1, b, C, hq, d).astype(dtype)
    kp = rn(2, n_blocks, bs, hkv, d).astype(dtype)
    vp = rn(3, n_blocks, bs, hkv, d).astype(dtype)
    bt = jnp.asarray(np.array([[3, 1, 4, 7, 0, 0],
                               [5, 9, 2, 6, 8, 10]], np.int32))
    q_start = jnp.array([17, 40])           # mid-block and block-aligned
    kv_len = jnp.array([17 + 8, 40 + 5])    # row 1 carries 3 pad queries
    o1 = paged_context_attention_pallas(q, kp, vp, bt, q_start=q_start,
                                        kv_len=kv_len, interpret=True)
    o2 = ref.paged_context_attention_ref(q, kp, vp, bt, q_start=q_start,
                                         kv_len=kv_len)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), atol=tol)


def test_context_ref_degenerates_to_causal_prefill():
    """q_start == 0 with the chunk covering the whole cache reduces the
    context oracle to ordinary causal attention."""
    b, C, hq, hkv, d = 2, 8, 4, 2, 16
    q = rn(1, b, C, hq, d)
    k = rn(2, b, C, hkv, d)
    v = rn(3, b, C, hkv, d)
    lens = jnp.array([8, 5])
    o1 = ref.context_attention_ref(q, k, v, q_start=jnp.zeros(2, jnp.int32),
                                   kv_len=lens)
    o2 = ref.attention_ref(q, k, v, causal=True, kv_len=lens)
    for i, L in enumerate([8, 5]):
        np.testing.assert_allclose(np.asarray(o1)[i, :L],
                                   np.asarray(o2)[i, :L], atol=1e-6)


def test_ops_context_xla_matches_gathered_oracle():
    b, C, hq, hkv, d = 2, 4, 4, 2, 16
    bs, n_blocks = 8, 12
    q = rn(1, b, C, hq, d)
    kp = rn(2, n_blocks, bs, hkv, d)
    vp = rn(3, n_blocks, bs, hkv, d)
    bt = jnp.asarray(np.array([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32))
    q_start = jnp.array([10, 0])
    kv_len = jnp.array([14, 4])
    o = ops.paged_context_attention(q, kp, vp, bt, q_start=q_start,
                                    kv_len=kv_len)
    want = ref.paged_context_attention_ref(q, kp, vp, bt, q_start=q_start,
                                           kv_len=kv_len)
    assert np.array_equal(np.asarray(o), np.asarray(want))


@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("group", [1, 2, 3, 5])
def test_ops_context_xla_groups_rows_and_skips_pad_groups(monkeypatch, group,
                                                          quant):
    """Rows whose score tiles exceed the budget run in groups of `group`
    (5 = all at once): real rows match the gathered oracle, and a group of
    pad rows (kv_len 0) is skipped, reading zeros."""
    b, C, hq, hkv, d = 5, 4, 4, 2, 16
    bs, n_blocks = 8, 12
    monkeypatch.setattr(ops, "CONTEXT_SCORE_BYTES",
                        group * 4 * C * 4 * bs * hq)
    q = rn(1, b, C, hq, d)
    kp = rn(2, n_blocks, bs, hkv, d)
    vp = rn(3, n_blocks, bs, hkv, d)
    bt = jnp.asarray(np.array([[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 0, 0],
                               [0, 0, 0, 0], [0, 0, 0, 0]], np.int32))
    q_start = jnp.array([10, 0, 3, 0, 0])
    kv_len = jnp.array([14, 4, 7, 0, 0])
    kw = dict(q_start=q_start, kv_len=kv_len)
    if quant:
        ks = jnp.abs(rn(4, n_blocks, bs, hkv)) + 0.5
        vs = jnp.abs(rn(5, n_blocks, bs, hkv)) + 0.5
        o = ops.paged_context_attention(q, kp, vp, bt, k_scale=ks,
                                        v_scale=vs, **kw)
        want = ref.paged_context_attention_quant_ref(q, kp, vp, ks, vs, bt,
                                                     **kw)
    else:
        o = ops.paged_context_attention(q, kp, vp, bt, **kw)
        want = ref.paged_context_attention_ref(q, kp, vp, bt, **kw)
    o = np.asarray(o)
    assert o.shape == (b, C, hq, d)
    np.testing.assert_allclose(o[:3], np.asarray(want)[:3], rtol=1e-6,
                               atol=1e-6)
    if group < b:       # rows 3 and 4 share no group with a real row
        assert not o[4].any()
# ---------------------------------------------------------------------------

def test_prefill_paged_context_chunked_equals_one_shot():
    cfg = get_config("granite-8b").reduced()
    assert context_mode_supported(cfg)
    params = M.init_params(cfg, KEY)
    rng = np.random.RandomState(0)
    n_slots, slot_len, bs = 2, 32, 8
    nbmax = slot_len // bs
    lens = np.array([13, 9], np.int32)
    toks = np.zeros((n_slots, 16), np.int32)
    for i in range(n_slots):
        toks[i, :lens[i]] = rng.randint(0, cfg.vocab_size, lens[i])

    scratch = M.init_cache(cfg, n_slots, slot_len)
    lg, scratch = M.prefill(cfg, params, {"tokens": jnp.asarray(toks)},
                            scratch, lens=jnp.asarray(lens))
    bt = (1 + np.arange(n_slots * nbmax, dtype=np.int32)
          ).reshape(n_slots, nbmax)
    pool_ref = {k: M.scatter_cache_rows_paged(
        M.init_paged_cache(cfg, 1 + n_slots * nbmax, bs, n_slots)[k],
        scratch[k], [0, 1], bt.reshape(-1), batch_axis=1) for k in scratch}

    # same prompts through TWO context chunks into fresh pages
    pool_ctx = M.init_paged_cache(cfg, 1 + n_slots * nbmax, bs, n_slots)
    c1 = np.array([8, 5], np.int32)
    _, pool_ctx = M.prefill_paged_context(
        cfg, params, jnp.asarray(toks[:, :8]), pool_ctx,
        np.zeros(2, np.int32), c1, jnp.asarray(bt))
    rem = lens - c1
    t2 = np.zeros((n_slots, int(rem.max())), np.int32)
    for i in range(n_slots):
        t2[i, :rem[i]] = toks[i, c1[i]:lens[i]]
    lg2, pool_ctx = M.prefill_paged_context(
        cfg, params, jnp.asarray(t2), pool_ctx, c1, rem, jnp.asarray(bt))

    assert (np.argmax(np.asarray(lg), -1)
            == np.argmax(np.asarray(lg2), -1)).all()
    pos = lens.copy()
    lg_a, lg_b = np.asarray(lg), np.asarray(lg2)
    for step in range(4):
        na = jnp.asarray(np.argmax(lg_a, -1).astype(np.int32))
        nb_ = jnp.asarray(np.argmax(lg_b, -1).astype(np.int32))
        assert np.array_equal(np.asarray(na), np.asarray(nb_)), step
        lg_a, pool_ref = M.decode_step_paged(cfg, params, na, pool_ref,
                                             jnp.asarray(pos),
                                             jnp.asarray(bt))
        lg_b, pool_ctx = M.decode_step_paged(cfg, params, nb_, pool_ctx,
                                             jnp.asarray(pos),
                                             jnp.asarray(bt))
        lg_a, lg_b = np.asarray(lg_a), np.asarray(lg_b)
        pos += 1


def test_copy_cache_pages_duplicates_attn_leaves_only():
    cfg = get_config("granite-8b").reduced()
    cache = M.init_paged_cache(cfg, 6, 4, 2)
    poked = {k: {n: (l.at[(0,) * l.ndim].add(1.0)
                     if n in ("k", "v") else l)
                 for n, l in sub.items()} for k, sub in cache.items()}
    # write something recognizable into page 2, copy 2 -> 4
    for k in poked:
        poked[k]["k"] = poked[k]["k"].at[:, 2].set(7.0)
    out = M.copy_cache_pages(poked, [2], [4])
    for k in out:
        np.testing.assert_array_equal(np.asarray(out[k]["k"][:, 4]),
                                      np.asarray(poked[k]["k"][:, 2]))


# ---------------------------------------------------------------------------
# End-to-end: warm-prefix / chunked serving == cold serving (2-stage pipe)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served_cold():
    cfg = get_config("granite-8b").reduced()
    params = M.init_params(cfg, KEY)
    dev = jax.devices()[0]
    L = cfg.num_layers

    def pipe():
        return AsymmetricPipeline(cfg, params, [1, L - 1], [[dev], [dev]])

    def mk_reqs():
        rng = np.random.RandomState(3)
        shared = rng.randint(0, cfg.vocab_size, size=17).astype(np.int32)
        reqs = []
        for i in range(4):
            tail = rng.randint(0, cfg.vocab_size,
                               size=3 + 2 * i).astype(np.int32)
            reqs.append(Request(rid=i,
                                prompt=np.concatenate([shared, tail]),
                                max_new_tokens=5, arrival=0.05 * i))
        # an exact duplicate with a BLOCK-ALIGNED length (24 = 3 * 8): the
        # full-hit path that re-runs only the last token and must
        # copy-on-write the shared tail block
        dup = np.concatenate([shared,
                              np.arange(7, dtype=np.int32)])
        assert len(dup) % 8 == 0
        reqs.append(Request(rid=8, prompt=dup, max_new_tokens=4,
                            arrival=0.3))
        # arrives after everything drained: matches rid 8's FULLY indexed
        # prompt (all 3 blocks), so only the last token re-runs — and its
        # K/V write lands in the shared tail block, forcing COW
        reqs.append(Request(rid=9, prompt=dup.copy(), max_new_tokens=4,
                            arrival=25.0))
        return reqs

    reqs_c = mk_reqs()
    PipelineBatcher(pipe(), n_slots=3, max_len=48).serve(reqs_c,
                                                         deadline=1e9)
    return cfg, params, pipe, mk_reqs, reqs_c


def test_warm_prefix_serving_bit_identical_and_counted(served_cold):
    cfg, params, pipe, mk_reqs, reqs_c = served_cold
    reqs_w = mk_reqs()
    stats = PagedPipelineBatcher(
        pipe(), n_slots=3, max_len=48, block_size=8,
        prefix_caching=True).serve(reqs_w, deadline=1e9)
    for rc, rw in zip(reqs_c, reqs_w):
        assert list(rc.output) == list(rw.output), rc.rid
    assert stats.prefix_lookups == len(reqs_w)
    assert stats.prefix_hits >= 4            # every non-first rider hits
    assert stats.prefix_hit_tokens > 0
    assert stats.cow_copies >= 1             # the duplicate full hit
    # warm prefill touched far fewer tokens than the prompts contain
    total_prompt = sum(len(r.prompt) for r in reqs_w)
    assert stats.prefill_tokens < total_prompt
    assert "hit=" in stats.summary()


def test_chunked_prefill_bit_identical(served_cold):
    cfg, params, pipe, mk_reqs, reqs_c = served_cold
    reqs_k = mk_reqs()
    stats = PagedPipelineBatcher(
        pipe(), n_slots=3, max_len=48, block_size=8,
        prefill_chunk=8).serve(reqs_k, deadline=1e9)
    for rc, rk in zip(reqs_c, reqs_k):
        assert list(rc.output) == list(rk.output), rc.rid
    assert stats.prefix_hits == 0            # caching off: chunking alone
    assert stats.prefill_tokens == sum(len(r.prompt) for r in reqs_k)


def test_prefix_plus_chunked_combined(served_cold):
    cfg, params, pipe, mk_reqs, reqs_c = served_cold
    reqs_b = mk_reqs()
    stats = PagedPipelineBatcher(
        pipe(), n_slots=3, max_len=48, block_size=8, prefix_caching=True,
        prefill_chunk=8).serve(reqs_b, deadline=1e9)
    for rc, rb in zip(reqs_c, reqs_b):
        assert list(rc.output) == list(rb.output), rc.rid
    # chunked registration lands later (prompt completes over several
    # iterations), so concurrent riders hit less than one-shot warm serving
    # — but the serialized duplicate and late riders still hit
    assert stats.prefix_hits >= 2


def test_chunked_prefill_fairness_long_prompt_does_not_stall_decode():
    """Iteration-level fairness: with chunking, a short request riding
    behind a giant prompt starts decoding while the giant is still
    prefilling — its first token lands EARLIER on the virtual clock than
    under one-shot prefill (prefill_token_cost makes prefill work visible
    to the clock)."""
    cfg = get_config("granite-8b").reduced()
    params = M.init_params(cfg, KEY)
    dev = jax.devices()[0]
    L = cfg.num_layers

    def pipe():
        return AsymmetricPipeline(cfg, params, [1, L - 1], [[dev], [dev]])

    def mk():
        rng = np.random.RandomState(5)
        return [Request(rid=0, prompt=rng.randint(0, cfg.vocab_size, 40
                                                  ).astype(np.int32),
                        max_new_tokens=4, arrival=0.0),
                Request(rid=1, prompt=rng.randint(0, cfg.vocab_size, 5
                                                  ).astype(np.int32),
                        max_new_tokens=4, arrival=0.01)]

    kw = dict(n_slots=2, max_len=64, block_size=8, prefill_token_cost=0.125)
    one = mk()
    PagedPipelineBatcher(pipe(), **kw).serve(one, deadline=1e9)
    chunked = mk()
    PagedPipelineBatcher(pipe(), prefill_chunk=8, **kw).serve(chunked,
                                                              deadline=1e9)
    assert list(one[0].output) == list(chunked[0].output)
    assert list(one[1].output) == list(chunked[1].output)
    # the short request's TTFT improves; the giant prompt pays the chunks
    assert chunked[1].first_token_time < one[1].first_token_time


def test_chunk_calls_pad_rows_to_a_power_of_two():
    """Chunks of several slots run as one context call whose row count is
    the next power of two (at most n_slots), so a stage compiles one
    context program per power of two instead of one per group size; the
    pad rows change no request's tokens."""
    cfg = get_config("granite-8b").reduced()
    params = M.init_params(cfg, KEY)
    dev = jax.devices()[0]
    L = cfg.num_layers

    def mk():
        rng = np.random.RandomState(9)
        return [Request(rid=i, prompt=rng.randint(
                    0, cfg.vocab_size, 12 + 9 * i).astype(np.int32),
                        max_new_tokens=4, arrival=0.0) for i in range(5)]

    kw = dict(n_slots=6, max_len=64, block_size=8)
    one = mk()
    PagedPipelineBatcher(AsymmetricPipeline(cfg, params, [1, L - 1],
                                            [[dev], [dev]]),
                         **kw).serve(one, deadline=1e9)
    pipe = AsymmetricPipeline(cfg, params, [1, L - 1], [[dev], [dev]])
    shapes, call = [], pipe.context_slots_paged

    def spy(tokens, lens, q_start, tables):
        shapes.append((tokens.shape[0], int(np.count_nonzero(lens))))
        return call(tokens, lens, q_start, tables)
    pipe.context_slots_paged = spy
    chunked = mk()
    PagedPipelineBatcher(pipe, prefill_chunk=8, **kw).serve(chunked,
                                                            deadline=1e9)
    assert {rows for rows, _ in shapes} <= {1, 2, 4, 6}
    assert any(real == 5 for _, real in shapes)   # 5 real rows, 6 in all
    assert any(real == 3 for _, real in shapes)   # 3 real rows, 4 in all
    for a, b in zip(one, chunked):
        assert list(a.output) == list(b.output), a.rid


def test_prefix_cache_eviction_under_pool_pressure():
    """A pool too small to keep every cached prefix resident must evict
    LRU prefixes (not crash, not corrupt): distinct prompts streamed
    through a tight pool still decode exactly like cold serving."""
    cfg = get_config("granite-8b").reduced()
    params = M.init_params(cfg, KEY)
    dev = jax.devices()[0]
    L = cfg.num_layers

    def pipe():
        return AsymmetricPipeline(cfg, params, [1, L - 1], [[dev], [dev]])

    def mk():
        rng = np.random.RandomState(11)
        return [Request(rid=i, prompt=rng.randint(0, cfg.vocab_size, 18
                                                  ).astype(np.int32),
                        max_new_tokens=4, arrival=1.0 * i)
                for i in range(5)]

    reqs_c = mk()
    PipelineBatcher(pipe(), n_slots=2, max_len=32).serve(reqs_c,
                                                         deadline=1e9)
    reqs_p = mk()
    # 9 usable blocks: one request needs 3; five distinct cached prefixes
    # (2 full blocks each) cannot all stay resident
    stats = PagedPipelineBatcher(
        pipe(), n_slots=2, max_len=32, block_size=8, stage_blocks=[10, 10],
        prefix_caching=True).serve(reqs_p, deadline=1e9)
    for rc, rp in zip(reqs_c, reqs_p):
        assert list(rc.output) == list(rp.output), rc.rid
    assert stats.prefix_hits == 0            # all prompts distinct


def test_shared_prefix_workload_generator():
    reqs = shared_prefix_workload(rate=50.0, duration=0.3, vocab=100,
                                  shared_len=24, unique_len=6, out_len=4,
                                  seed=2)
    assert len(reqs) >= 3
    for r in reqs:
        assert np.array_equal(r.prompt[:24], reqs[0].prompt[:24])
        assert len(r.prompt) >= 30
    # >= 50% of every prompt is the shared system prompt
    assert all(24 / len(r.prompt) >= 0.5 for r in reqs)


def test_hybrid_stack_disables_context_mode_gracefully():
    cfg = get_config("jamba-v0.1-52b").reduced()
    assert not context_mode_supported(cfg)
    params = M.init_params(cfg, KEY)
    dev = jax.devices()[0]
    pipe = AsymmetricPipeline(cfg, params, [cfg.num_layers], [[dev]])
    with pytest.warns(UserWarning, match="attention-only"):
        eng = PagedPipelineBatcher(pipe, n_slots=2, max_len=32,
                                   block_size=8, prefix_caching=True,
                                   prefill_chunk=8)
    assert not eng.prefix_caching and eng.prefill_chunk == 0


# ---------------------------------------------------------------------------
# Scheduler: prefix-hit-aware effective KV demand
# ---------------------------------------------------------------------------

def test_concurrent_capacity_prefix_hit_aware():
    from repro.core import cluster as cl
    from repro.core import cost_model as cm
    task = cm.Task(batch=1, s_in=512, s_out=64)
    prof = cm.ModelProfile.from_config(get_config("llama2-70b"),
                                       paper_exact=True)
    c = cl.case_study_cluster()
    devs = [0, 1, 2, 3]
    base = cm.concurrent_capacity(c, devs, 48, prof, task, block_size=16)
    half = cm.concurrent_capacity(c, devs, 48, prof, task, block_size=16,
                                  prefix_hit_rate=0.5)
    full = cm.concurrent_capacity(c, devs, 48, prof, task, block_size=16,
                                  prefix_hit_rate=1.0)
    assert base < half < full
    # dedup is block-granular: a sub-block hit changes nothing
    tiny = cm.concurrent_capacity(c, devs, 48, prof, task, block_size=16,
                                  prefix_hit_rate=15 / 512)
    assert tiny == base


def test_evaluator_threads_prefix_hit_rate():
    from repro.core import cluster as cl
    from repro.core import cost_model as cm
    from repro.core.genetic import Evaluator
    from repro.core.plan import PipelinePlan, StagePlan
    task = cm.Task(batch=1, s_in=128, s_out=64)
    prof = cm.ModelProfile.from_config(get_config("llama2-70b"),
                                       paper_exact=True)
    c = cl.case_study_cluster()
    plan = PipelinePlan([StagePlan([0, 1, 2, 3], 48), StagePlan([4, 5], 20),
                         StagePlan([6, 7], 12)], cost=1.0, bottleneck=0.2)
    ev = Evaluator(c, prof, task, deadline=3.0, rate=4.0, kv_block_size=16)
    ev_hit = Evaluator(c, prof, task, deadline=3.0, rate=4.0,
                       kv_block_size=16, prefix_hit_rate=0.75)
    assert ev_hit._max_concurrent(plan) > ev._max_concurrent(plan)
