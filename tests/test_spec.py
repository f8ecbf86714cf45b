"""Speculative decoding: verification kernel vs oracle, proposer units,
speculative-page rollback refcount safety (BlockTable.truncate + prefix
aliasing), model-level multi-token verification vs sequential decode, and
end-to-end TOKEN-IDENTITY of spec-enabled serving against plain greedy
decode — including under prefix-cache hits, chunked prefill, preemption and
disaggregated decode replicas. The subsystem's correctness bar: speculation
may only change HOW MANY target steps a generation takes, never which
tokens it produces."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st  # noqa: F401 (skips when absent)

from repro.configs import get_config
from repro.kernels import ops, ref
from repro.models import model as M
from repro.serving.block_manager import (BlockPool, BlockTable, PrefixIndex,
                                         blocks_for_tokens, chunk_hashes)
from repro.serving.continuous import PagedPipelineBatcher
from repro.serving.pipeline import AsymmetricPipeline
from repro.serving.request import Request, shared_prefix_workload
from repro.serving.spec import (DraftModelProposer, NgramProposer,
                                SpecConfig, greedy_accept,
                                rejection_sample_accept)

KEY = jax.random.PRNGKey(0)


def rn(i, *shape):
    return jax.random.normal(jax.random.fold_in(KEY, i), shape, jnp.float32)


# ---------------------------------------------------------------------------
# Verification kernel vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_verify_kernel_vs_ref(dtype):
    """The Pallas multi-token verification kernel (interpret mode) against
    the gathered oracle: per-slot KV-start offsets, ragged candidate
    counts, and a dead row (zero candidates)."""
    b, T, hq, hkv, d, bs, nblk = 3, 4, 4, 2, 32, 16, 12
    q = rn(1, b, T, hq, d).astype(dtype)
    kp = rn(2, nblk, bs, hkv, d).astype(dtype)
    vp = rn(3, nblk, bs, hkv, d).astype(dtype)
    bt = jnp.asarray(np.array([[3, 1, 4, 0], [5, 9, 2, 6], [7, 8, 0, 0]],
                              np.int32))
    kv_start = jnp.array([17, 40, 0])
    kv_len = jnp.array([17 + 4, 40 + 2, 0])      # row 2: dead (no valid KV)
    with ops.backend("pallas_interpret"):
        got = ops.paged_verify_attention(q, kp, vp, bt, kv_start=kv_start,
                                         kv_len=kv_len)
    want = ref.paged_verify_attention_ref(q, kp, vp, bt, kv_start=kv_start,
                                          kv_len=kv_len)
    atol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)
    assert np.all(np.asarray(got, np.float32)[2] == 0.0)   # dead row exact


def test_ops_verify_xla_matches_gathered_oracle():
    b, T, hq, hkv, d, bs, nblk = 2, 3, 4, 2, 16, 8, 10
    q = rn(4, b, T, hq, d)
    kp = rn(5, nblk, bs, hkv, d)
    vp = rn(6, nblk, bs, hkv, d)
    bt = jnp.asarray(np.array([[2, 4, 6, 1], [3, 5, 7, 9]], np.int32))
    kv_start = jnp.array([9, 20])
    kv_len = jnp.array([12, 23])
    got = ops.paged_verify_attention(q, kp, vp, bt, kv_start=kv_start,
                                     kv_len=kv_len)
    want = ref.paged_verify_attention_ref(q, kp, vp, bt, kv_start=kv_start,
                                          kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_verify_single_token_degenerates_to_decode():
    """A one-candidate chunk (the bonus token alone) is exactly a paged
    decode step: same attention output at the same position."""
    b, hq, hkv, d, bs, nblk = 2, 4, 2, 16, 8, 9
    kp = rn(7, nblk, bs, hkv, d)
    vp = rn(8, nblk, bs, hkv, d)
    q = rn(9, b, 1, hq, d)
    bt = jnp.asarray(np.array([[2, 4, 6], [3, 5, 7]], np.int32))
    pos = jnp.array([11, 19])
    got = ref.paged_verify_attention_ref(q, kp, vp, bt, kv_start=pos,
                                         kv_len=pos + 1)
    want = ref.paged_decode_attention_ref(q, kp, vp, bt, kv_len=pos + 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_verify_step_paged_matches_sequential_decode():
    """Model-level: verifying a chunk of ALREADY-COMMITTED tokens in one
    multi-token step reproduces the logits sequential single-token decode
    produces at each position — the identity greedy acceptance rides on."""
    cfg = get_config("granite-8b").reduced()
    params = M.init_params(cfg, KEY)
    rng = np.random.RandomState(0)
    bs, n_slots, T = 8, 2, 4
    nbmax = 4
    prompt_len = 6
    toks = rng.randint(0, cfg.vocab_size, size=(n_slots, prompt_len)
                       ).astype(np.int32)
    lens = np.full((n_slots,), prompt_len, np.int32)
    # contiguous prefill, scattered into pages (round-robin disjoint tables)
    cache = M.init_cache(cfg, n_slots, nbmax * bs)
    lg, cache = M.prefill(cfg, params, {"tokens": jnp.asarray(toks)}, cache,
                          lens=jnp.asarray(lens))
    bt = (1 + np.arange(n_slots * nbmax, dtype=np.int32)
          ).reshape(n_slots, nbmax)
    pages = {
        k: M.scatter_cache_rows_paged(
            M.init_paged_cache(cfg, 1 + n_slots * nbmax, bs, n_slots)[k],
            cache[k], list(range(n_slots)), bt.reshape(-1), batch_axis=1)
        for k in cache}
    # sequential: decode T tokens one at a time, collecting logits
    chunk = rng.randint(0, cfg.vocab_size, size=(n_slots, T)).astype(np.int32)
    pages_seq = jax.tree.map(lambda x: x, pages)
    seq_logits = []
    pos = lens.copy()
    for t in range(T):
        lg_t, pages_seq = M.decode_step_paged(
            cfg, params, jnp.asarray(chunk[:, t]), pages_seq,
            jnp.asarray(pos), jnp.asarray(bt))
        seq_logits.append(np.asarray(lg_t))
        pos += 1
    # one multi-token verification step over the same chunk
    ver_logits, _ = M.verify_step_paged(
        cfg, params, jnp.asarray(chunk), pages, jnp.asarray(lens),
        jnp.asarray(np.full((n_slots,), T, np.int32)), jnp.asarray(bt))
    ver_logits = np.asarray(ver_logits)
    for t in range(T):
        np.testing.assert_allclose(ver_logits[:, t], seq_logits[t],
                                   atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# Acceptance rules
# ---------------------------------------------------------------------------

def test_greedy_accept_commits_matching_prefix():
    V = 8
    logits = np.full((4, V), -1.0, np.float32)
    logits[0, 3] = 1.0       # after bonus: target says 3
    logits[1, 5] = 1.0       # after draft 3: target says 5
    logits[2, 2] = 1.0       # after draft 5: target says 2 (draft said 7)
    commit, a = greedy_accept(logits, bonus=1, drafts=[3, 5, 7])
    assert commit == [1, 3, 5] and a == 2
    # all accepted: commit = bonus + every draft
    commit, a = greedy_accept(logits, bonus=1, drafts=[3, 5])
    assert commit == [1, 3, 5] and a == 2
    # first draft wrong: only the bonus commits
    commit, a = greedy_accept(logits, bonus=1, drafts=[4])
    assert commit == [1] and a == 0
    # no drafts: plain decode
    commit, a = greedy_accept(logits, bonus=6, drafts=[])
    assert commit == [6] and a == 0


def test_rejection_sample_accept():
    V = 4
    pt = np.zeros((3, V)); pt[:, 0] = 1.0           # target is certain of 0
    pd = np.zeros((3, V)); pd[:, 0] = 1.0
    # draft proposes exactly the target's token: always accepted
    commit, a = rejection_sample_accept(pt, pd, [0, 0, 0],
                                        np.array([0.99, 0.99, 0.99]))
    assert commit == [0, 0, 0] and a == 3
    # draft proposes a token the target gives zero mass: rejected at j=0
    # and the resample comes from the residual (= the target itself)
    pd2 = np.zeros((3, V)); pd2[:, 1] = 1.0
    commit, a = rejection_sample_accept(pt, pd2, [1, 1, 1],
                                        np.array([0.999, 0.5, 0.5]))
    assert a == 0 and commit[0] == 0 and len(commit) == 1


# ---------------------------------------------------------------------------
# Proposers
# ---------------------------------------------------------------------------

def test_ngram_proposer_prompt_lookup():
    p = NgramProposer(ngram_max=3, ngram_min=1)
    # history ends with (7, 8), seen earlier followed by 9, 4
    hist = np.array([1, 7, 8, 9, 4, 2, 7, 8], np.int32)
    out = p.propose([(0, hist, 2)])
    assert list(out[0]) == [9, 4]
    # cap respected
    out = p.propose([(0, hist, 1)])
    assert list(out[0]) == [9]
    # the MOST RECENT earlier occurrence wins
    hist2 = np.array([7, 8, 1, 7, 8, 2, 7, 8], np.int32)
    out = p.propose([(0, hist2, 1)])
    assert list(out[0]) == [2]
    # nothing repeats: no proposal (slot absent from the result)
    out = p.propose([(0, np.arange(8, dtype=np.int32), 3)])
    assert 0 not in out
    # zero cap: skipped
    assert p.propose([(0, hist, 0)]) == {}


def test_draft_proposer_matches_draft_greedy_chain():
    """The draft proposer's k proposals are exactly the draft model's own
    greedy continuation of the history, and accepted commits keep its
    cache in sync (no re-prefill on the next round)."""
    cfg = get_config("granite-8b").reduced()
    params = M.init_params(cfg, KEY)
    prop = DraftModelProposer(cfg, params, n_slots=2, max_len=32)
    rng = np.random.RandomState(1)
    hist = rng.randint(0, cfg.vocab_size, size=7).astype(np.int32)
    k = 3
    got = prop.propose([(0, hist, k)])[0]
    # independent greedy reference on the same model
    cache = M.init_cache(cfg, 1, 32)
    lg, cache = M.prefill(cfg, params, {"tokens": jnp.asarray(hist[None])},
                          cache, lens=jnp.asarray([len(hist)]))
    # prefill consumed the full history; its logits predict the token
    # AFTER hist[-1], which is the first proposal
    want = []
    pos = len(hist)
    for _ in range(k):
        nxt = int(np.asarray(lg)[0].argmax())
        want.append(nxt)
        lg, cache = M.decode_step(cfg, params, jnp.asarray([nxt]), cache,
                                  jnp.asarray([pos]))
        pos += 1
    assert list(got) == want
    # accept ALL 3 (the full-acceptance path: the extra write-only step
    # must have cached the final proposal's K/V): the next round syncs
    # without re-prefilling and still matches the reference chain
    steps_before = prop.draft_steps
    prop.commit(0, k)
    bonus2 = int(np.asarray(lg)[0].argmax())         # token after want[-1]
    hist2 = np.concatenate([hist, np.asarray(want, np.int32),
                            np.asarray([bonus2], np.int32)])
    got2 = prop.propose([(0, hist2, k)])
    # k proposal steps + 1 write-only step, no re-prefill
    assert prop.draft_steps == steps_before + k + 1
    want2 = []
    pos2 = len(hist2) - 1
    lg2, cache2 = M.decode_step(cfg, params, jnp.asarray([bonus2]), cache,
                                jnp.asarray([pos2]))
    for _ in range(k):
        nxt = int(np.asarray(lg2)[0].argmax())
        want2.append(nxt)
        lg2, cache2 = M.decode_step(cfg, params, jnp.asarray([nxt]),
                                    cache2, jnp.asarray([pos2 + 1]))
        pos2 += 1
    assert list(got2[0]) == want2
    # release forgets the slot: next propose re-prefills from scratch
    prop.release(0)
    assert prop._pos[0] == 0


# ---------------------------------------------------------------------------
# Speculative-page rollback: BlockTable.truncate
# ---------------------------------------------------------------------------

def test_truncate_frees_trailing_blocks():
    pool = BlockPool(8, block_size=4)
    t = BlockTable(pool)
    assert t.allocate_tokens(20)             # 5 blocks
    assert t.n_blocks == 5 and pool.n_free == 2
    assert t.truncate(9) == 2                # keep 3 blocks (9 tokens)
    assert t.n_blocks == 3 and pool.n_free == 4
    assert t.truncate(9) == 0                # idempotent
    assert t.truncate(0) == 3
    assert pool.n_free == 7 and t.n_blocks == 0


def test_truncate_shared_block_keeps_other_references():
    """Rolling back a speculative tail that aliases an index-registered
    block must not free it out from under the index (prefix-index-safe)."""
    pool = BlockPool(8, block_size=4)
    ix = PrefixIndex(pool)
    t = BlockTable(pool)
    assert t.allocate_tokens(12)             # 3 blocks
    toks = list(range(8))                    # 2 full chunks
    hashes = chunk_hashes(toks, 4)
    ix.register(hashes, t.blocks[:2])        # index holds blocks 0..1
    shared = t.blocks[1]
    assert pool.ref(shared) == 2
    t.truncate(4)                            # drop blocks 1 and 2
    assert pool.ref(shared) == 1             # the index's reference lives
    assert ix.n_evictable() >= 1
    # a later prompt can still alias the registered prefix
    t2 = BlockTable(pool)
    t2.adopt(ix.acquire(hashes[:2]))
    assert t2.blocks[1] == shared and pool.ref(shared) == 2
    t.release()
    t2.release()
    ix.clear()
    assert pool.n_free == pool.n_blocks - 1  # nothing stranded


@settings(max_examples=40, deadline=None)
@given(st.integers(6, 16), st.integers(1, 4), st.integers(0, 10 ** 6))
def test_truncate_adopt_refcount_property(n_usable, block_size, seed):
    """Property (the rollback/adopt interaction): random interleavings of
    prefix registration, prefix adoption, speculative growth and
    truncate-rollback keep every block's refcount equal to an independent
    holder model's count — and releasing everything drains the pool
    completely (no stranded refcounts, no double frees)."""
    rng = np.random.RandomState(seed)
    pool = BlockPool(n_usable + 1, block_size)
    ix = PrefixIndex(pool)
    prompt = list(range(3 * block_size))     # 3 registrable chunks
    hashes = chunk_hashes(prompt, block_size)
    tables = [BlockTable(pool) for _ in range(3)]
    committed = [0] * 3                      # committed tokens per table

    for _ in range(30):
        i = rng.randint(3)
        t = tables[i]
        op = rng.choice(["adopt", "grow", "truncate", "register",
                         "release"])
        if op == "adopt" and not t.blocks:
            L = ix.match_len(hashes)
            if L:
                t.adopt(ix.acquire(hashes[:L]))
                committed[i] = L * block_size
        elif op == "grow":
            # speculative chunk: may fail when the pool is dry — that is
            # the engine's preempt path, not an invariant violation
            want = committed[i] + rng.randint(1, 2 * block_size + 1)
            if t.allocate_tokens(want):
                committed[i] = want if rng.rand() < 0.5 else committed[i]
        elif op == "truncate":
            # rollback to the committed length (or a random earlier point)
            back = rng.randint(0, committed[i] + 1)
            t.truncate(back)
            committed[i] = min(committed[i], back)
        elif op == "register" and t.n_blocks >= 1:
            n_full = min(t.n_blocks, len(hashes))
            ix.register(hashes[:n_full], t.blocks[:n_full])
        elif op == "release":
            t.release()
            committed[i] = 0
        # refcount == table holders + index holder, every block
        for bid in range(1, pool.n_blocks):
            want = sum(b == bid for tt in tables for b in tt.blocks) \
                + (1 if bid in ix._hash_of else 0)
            assert pool.ref(bid) == want, (bid, op)
    for t in tables:
        t.release()
    ix.clear()
    assert pool.n_free == pool.n_blocks - 1


# ---------------------------------------------------------------------------
# End-to-end: spec serving == plain greedy serving, token for token
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

    return cfg, params, pipe


def _mk_reqs(cfg, *, n=4, max_new=8, seed=0):
    rng = np.random.RandomState(seed)
    return [Request(rid=i,
                    prompt=rng.randint(0, cfg.vocab_size,
                                       size=5 + 3 * i).astype(np.int32),
                    max_new_tokens=max_new, arrival=0.02 * i)
            for i in range(n)]


@pytest.fixture(scope="module")
def served_baseline(setup):
    cfg, params, pipe = setup
    reqs = _mk_reqs(cfg)
    PagedPipelineBatcher(pipe(), n_slots=3, max_len=48,
                         block_size=8).serve(reqs, deadline=1e9)
    return reqs


def test_spec_ngram_token_identical(setup, served_baseline):
    cfg, params, pipe = setup
    reqs = _mk_reqs(cfg)
    stats = PagedPipelineBatcher(
        pipe(), n_slots=3, max_len=48, block_size=8,
        spec=SpecConfig(k=3, proposer="ngram")).serve(reqs, deadline=1e9)
    assert stats.spec_steps > 0
    assert stats.spec_tokens == sum(len(r.output) for r in reqs)
    for rc, rs in zip(served_baseline, reqs):
        assert list(rc.output) == list(rs.output), rc.rid


def test_spec_self_draft_identical_and_fewer_steps(setup, served_baseline):
    """Draft == target: acceptance is near-total (up to argmax ties
    between the monolithic draft path and the pipeline verify path), so
    each target step commits well over one token."""
    cfg, params, pipe = setup
    reqs = _mk_reqs(cfg)
    stats = PagedPipelineBatcher(
        pipe(), n_slots=3, max_len=48, block_size=8,
        spec=SpecConfig(k=3, proposer="draft", draft_cfg=cfg,
                        draft_params=params)).serve(reqs, deadline=1e9)
    for rc, rs in zip(served_baseline, reqs):
        assert list(rc.output) == list(rs.output), rc.rid
    assert stats.spec_tokens / stats.spec_steps > 1.5, \
        (stats.spec_tokens, stats.spec_steps)


def test_spec_with_prefix_cache_and_chunked_prefill(setup):
    cfg, params, pipe = setup

    def wl():
        return shared_prefix_workload(rate=4.0, duration=1.5,
                                      vocab=cfg.vocab_size, shared_len=24,
                                      unique_len=6, out_len=6, seed=3)

    cold = wl()
    PagedPipelineBatcher(pipe(), n_slots=4, max_len=48,
                         block_size=8).serve(cold, deadline=1e9)
    warm = wl()
    stats = PagedPipelineBatcher(
        pipe(), n_slots=4, max_len=48, block_size=8, prefix_caching=True,
        prefill_chunk=16, spec=SpecConfig(k=3)).serve(warm, deadline=1e9)
    assert stats.prefix_hits > 0 and stats.spec_steps > 0
    for rc, rw in zip(cold, warm):
        assert list(rc.output) == list(rw.output), rc.rid


def test_spec_preemption_recomputes_identically(setup):
    """A dry pool mid-speculation preempts by recompute, and the requeued
    request still finishes with exactly the baseline tokens — rollback,
    release and draft-state reset compose."""
    cfg, params, pipe = setup

    def reqs(seed=1):
        rng = np.random.RandomState(seed)
        return [Request(rid=i,
                        prompt=rng.randint(0, cfg.vocab_size,
                                           size=6).astype(np.int32),
                        max_new_tokens=20, arrival=0.0) for i in range(3)]

    rc = reqs()
    PagedPipelineBatcher(pipe(), n_slots=3, max_len=32,
                         block_size=8).serve(rc, deadline=1e9)
    rs = reqs()
    stats = PagedPipelineBatcher(
        pipe(), n_slots=3, max_len=32, block_size=8, stage_blocks=[9, 9],
        admit_headroom=2, spec=SpecConfig(k=3)).serve(rs, deadline=1e9)
    assert stats.preemptions > 0
    for a, b in zip(rc, rs):
        assert list(a.output) == list(b.output), a.rid


def test_spec_on_disaggregated_decode_replica(setup, served_baseline):
    """Speculation composes with the prefill/decode split: migrated slots
    seed the verify loop from the migrated logits bit-identically."""
    from repro.serving.disagg import wire_disaggregation
    from repro.serving.loop import VirtualClock, run_serve_loop
    cfg, params, pipe = setup
    reqs = _mk_reqs(cfg)
    workers = [
        PagedPipelineBatcher(pipe(), n_slots=3, max_len=48, block_size=8,
                             role="prefill", spec=SpecConfig(k=3)),
        PagedPipelineBatcher(pipe(), n_slots=3, max_len=48, block_size=8,
                             role="decode", spec=SpecConfig(k=3)),
    ]
    wire_disaggregation(workers, ["prefill", "decode"])
    stats = run_serve_loop(workers, reqs, deadline=1e9,
                           clock=VirtualClock())
    assert stats.migrations == len(reqs) and stats.spec_steps > 0
    for rc, rs in zip(served_baseline, reqs):
        assert list(rc.output) == list(rs.output), rc.rid


def test_spec_counters_and_bounds(setup):
    """Per-step commits stay within [1, k + 1]; accepted <= proposed;
    committed spec tokens equal the served output tokens."""
    cfg, params, pipe = setup
    reqs = _mk_reqs(cfg, n=3, max_new=10, seed=7)
    k = 3
    stats = PagedPipelineBatcher(
        pipe(), n_slots=3, max_len=48, block_size=8,
        spec=SpecConfig(k=k, proposer="draft", draft_cfg=cfg,
                        draft_params=params)).serve(reqs, deadline=1e9)
    total_out = sum(len(r.output) for r in reqs)
    assert stats.spec_tokens == total_out
    assert stats.spec_accepted <= stats.spec_proposed
    assert stats.spec_steps <= total_out                 # never worse
    assert stats.spec_tokens <= stats.spec_steps * (k + 1)


def test_spec_gating_warns_on_hybrid_and_static():
    cfg_h = get_config("jamba-v0.1-52b").reduced()
    params_h = M.init_params(cfg_h, KEY)
    dev = jax.devices()[0]
    ph = AsymmetricPipeline(cfg_h, params_h, [1, cfg_h.num_layers - 1],
                            [[dev], [dev]])
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        eng = PagedPipelineBatcher(ph, n_slots=2, max_len=32, block_size=8,
                                   spec=SpecConfig(k=2))
    assert eng.spec is None
    assert any("attention-only" in str(x.message) for x in w)
    # router-level gating: the static engine cannot verify through pages
    from repro.serving.router import Router
    cfg = get_config("granite-8b").reduced()
    params = M.init_params(cfg, KEY)
    pipe = AsymmetricPipeline(cfg, params, [cfg.num_layers], [[dev]])
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        Router([pipe], policy="static", spec=SpecConfig(k=2))
    assert any("paged" in str(x.message) for x in w)


def test_engine_unsuitable_draft_falls_back_to_ngram():
    """A draft config the verification contract cannot support (recurrent
    state, or a mismatched vocab) must not crash serving from a CLI flag:
    the engine warns and speculates with the weight-free proposer."""
    from repro.core.plan import Assignment, PipelinePlan, StagePlan
    from repro.serving.engine import InferenceEngine
    from repro.serving.spec import NgramProposer
    cfg = get_config("granite-8b").reduced()
    asg = Assignment([PipelinePlan([StagePlan([0], cfg.num_layers)],
                                   cost=0.1, bottleneck=0.1)])
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        eng = InferenceEngine(
            cfg, asg, key=KEY, policy="continuous", n_slots=2, max_len=32,
            block_size=8, spec_decode=True, spec_k=2,
            draft_model="h2o-danube-1.8b")     # SWA stack: no rollback
    assert any("n-gram" in str(x.message) for x in w)
    worker = eng.router.workers[0]
    assert worker.spec is not None
    assert isinstance(worker._proposer, NgramProposer)


def test_spec_virtual_clock_charges_draft_cost(setup):
    """draft_token_cost > 0 makes proposals visible to the virtual clock:
    the same workload finishes later than with free proposals."""
    from repro.serving.loop import VirtualClock
    cfg, params, pipe = setup
    free = _mk_reqs(cfg, n=2, max_new=6, seed=9)
    PagedPipelineBatcher(
        pipe(), n_slots=2, max_len=48, block_size=8,
        spec=SpecConfig(k=3)).serve(free, deadline=1e9)
    costly = _mk_reqs(cfg, n=2, max_new=6, seed=9)
    PagedPipelineBatcher(
        pipe(), n_slots=2, max_len=48, block_size=8,
        spec=SpecConfig(k=3, draft_token_cost=0.5)).serve(
            costly, deadline=1e9)
    assert max(r.finish_time for r in costly) \
        > max(r.finish_time for r in free)
    for a, b in zip(free, costly):
        assert list(a.output) == list(b.output)      # cost, not content


# ---------------------------------------------------------------------------
# Quantized pages under verification (int8 pools + spec rollback)
# ---------------------------------------------------------------------------

def test_verify_kernel_dead_row_exact_zero_under_int8_pages():
    """The verify kernel's dead-row contract (kv_len == kv_start == 0 ->
    exact zeros) must survive quantized pools: a free slot riding the
    joint dispatch scatters into the null page and its masked row may
    never leak dequantized garbage."""
    from repro.kernels.paged_attention import (
        paged_verify_attention_quant_pallas)
    from repro.models import quant as Q

    b, T, hq, hkv, d, bs, nblk = 3, 4, 4, 2, 32, 16, 12
    q = rn(31, b, T, hq, d)
    kp = rn(32, nblk, bs, hkv, d)
    vp = rn(33, nblk, bs, hkv, d)
    kq, ks = Q.quantize_kv_rows(kp, "int8")
    vq, vs = Q.quantize_kv_rows(vp, "int8")
    bt = jnp.asarray(np.array([[3, 1, 4, 0], [5, 9, 2, 6], [0, 0, 0, 0]],
                              np.int32))
    kv_start = jnp.array([17, 40, 0])
    kv_len = jnp.array([17 + 4, 40 + 2, 0])      # row 2: dead (free slot)
    got = paged_verify_attention_quant_pallas(
        q, kq, vq, ks, vs, bt, kv_start=kv_start, kv_len=kv_len,
        interpret=True)
    want = ref.paged_verify_attention_quant_ref(
        q, kq, vq, ks, vs, bt, kv_start=kv_start, kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert np.all(np.asarray(got)[2] == 0.0)     # dead row exact
    assert np.all(np.asarray(want)[2] == 0.0)
    # the XLA dispatch honors the same contract with scale operands
    got_x = ops.paged_verify_attention(q, kq, vq, bt, kv_start=kv_start,
                                       kv_len=kv_len, k_scale=ks, v_scale=vs)
    assert np.all(np.asarray(got_x)[2] == 0.0)


def test_spec_serving_int8_pool_token_identical(setup):
    """Speculation over int8 pages: rejected candidates' quantized page
    writes (payload AND scales) sit past the committed length after
    BlockTable.truncate, masked by kv_len and overwritten by the next
    chunk — so spec+int8 must reproduce plain int8 greedy decode token
    for token. (int8 vs fp32 is a STATISTICAL match — quantization may
    legitimately flip a near-tie argmax — and is measured by
    benchmarks/bench_quant_kv.py, not asserted here.)"""
    cfg, params, pipe = setup
    reqs_q = _mk_reqs(cfg)
    PagedPipelineBatcher(pipe(), n_slots=3, max_len=48, block_size=8,
                         kv_dtype="int8").serve(reqs_q, deadline=1e9)
    reqs_s = _mk_reqs(cfg)
    stats = PagedPipelineBatcher(
        pipe(), n_slots=3, max_len=48, block_size=8, kv_dtype="int8",
        spec=SpecConfig(k=3, proposer="ngram")).serve(reqs_s, deadline=1e9)
    assert stats.spec_steps > 0
    assert stats.kv_bytes_saved > 0
    for rq, rs in zip(reqs_q, reqs_s):
        assert list(rq.output) == list(rs.output), rq.rid
