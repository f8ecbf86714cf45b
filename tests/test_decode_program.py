"""The paged decode call as one compiled program per stage.

``AsymmetricPipeline.decode_slots_paged`` gathers the tokens' embeddings
inside the first stage's program and applies the final norm and head
inside the last stage's, with nothing run op by op around them. These
tests hold it to the op-by-op composition it replaced (the embedding
gather, each stage's ``decode_paged`` program, the head), to its
contract (host logits of the head's dtype), and to its count of
dispatched programs, on one- and two-stage pipelines.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import core
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.models import model as M
from repro.obs.trace import Tracer
from repro.serving.continuous import PagedPipelineBatcher
from repro.serving.loop import WallClock, run_serve_loop
from repro.serving.pipeline import AsymmetricPipeline
from repro.serving.request import Request

KEY = jax.random.PRNGKey(0)
N_SLOTS, MAX_LEN, BLOCK = 3, 32, 8

# (architecture, layers per stage; None = one stage, dtype)
CASES = {
    "granite-1stage": ("granite-8b", None, None),
    "granite-2stage": ("granite-8b", 1, None),
    "granite-1stage-bf16": ("granite-8b", None, "bfloat16"),
    "jamba-2stage": ("jamba-v0.1-52b", 1, None),
}


def _config(arch, dtype):
    cfg = get_config(arch).reduced()
    return cfg if dtype is None else dataclasses.replace(cfg, dtype=dtype)


def _pipes(case, n=2):
    """``n`` pipelines of the same weights, with their page pools."""
    arch, first, dtype = CASES[case]
    cfg = _config(arch, dtype)
    params = M.init_params(cfg, KEY)
    L = cfg.num_layers
    split = [L] if first is None else [first, L - first]
    dev = jax.devices()[0]
    out = []
    for _ in range(n):
        p = AsymmetricPipeline(cfg, params, split, [[dev]] * len(split))
        p.init_paged_caches(N_SLOTS, MAX_LEN, block_size=BLOCK)
        out.append(p)
    return cfg, out


def _op_by_op(pipe, tokens, positions, tables):
    """The decode call as it ran before: the eager embedding gather, each
    stage's ``decode_paged`` program on uploaded inputs, the eager head."""
    pos = jnp.asarray(positions, jnp.int32)
    x = pipe._embed_decode_tokens(jnp.asarray(tokens), pos)
    for si, st in enumerate(pipe.stages):
        x = jax.device_put(x, NamedSharding(st.mesh, P()))
        x, pipe.paged_caches[si] = st._decode_paged_jit(
            x, pipe.paged_caches[si], pos, jnp.asarray(tables[si]))
    return np.asarray(pipe._head(x)[:, 0])


def _steps(cfg, n_stages, n=6):
    """Decode inputs over ``n`` steps: three slots at different depths
    whose block tables grow as they cross block boundaries, and a slot
    that is freed (all-null table) half-way."""
    rng = np.random.default_rng(3)
    start = np.array([0, 6, 13], np.int32)
    blocks = [[1], [2], [3, 4]]
    nxt = 5
    for t in range(n):
        pos = start + t
        for i in range(N_SLOTS):
            while len(blocks[i]) * BLOCK <= pos[i]:
                blocks[i].append(nxt)
                nxt += 1
        tab = np.zeros((N_SLOTS, MAX_LEN // BLOCK), np.int32)
        for i in range(N_SLOTS):
            if not (i == 1 and t >= n // 2):        # slot 1 freed
                tab[i, :len(blocks[i])] = blocks[i]
        yield (rng.integers(0, cfg.vocab_size, N_SLOTS).astype(np.int32),
               np.where(tab.any(1), pos, 0).astype(np.int32),
               [tab] * n_stages)


@pytest.mark.parametrize("case", list(CASES))
def test_decode_call_matches_the_op_by_op_composition(case):
    cfg, (one, ref) = _pipes(case)
    n_stages = len(one.stages)
    dt = jnp.dtype(cfg.dtype)
    tol = 2 * float(jnp.finfo(dt).eps)
    for toks, pos, tabs in _steps(cfg, n_stages):
        got = one.decode_slots_paged(toks, pos, tabs)
        want = _op_by_op(ref, toks, pos, tabs)
        scale = float(np.abs(want.astype(np.float32)).max())
        np.testing.assert_allclose(got.astype(np.float32),
                                   want.astype(np.float32),
                                   rtol=tol, atol=tol * scale)
        assert (got.argmax(-1) == want.argmax(-1)).all()


@pytest.mark.parametrize("case", ["granite-2stage", "granite-1stage-bf16"])
def test_decode_call_returns_host_logits_of_the_head_dtype(case):
    cfg, (pipe,) = _pipes(case, n=1)
    toks, pos, tabs = next(_steps(cfg, len(pipe.stages)))
    out = pipe.decode_slots_paged(toks, pos, tabs)
    head = pipe.stages[-1].head_params["lm_head"]
    assert type(out) is np.ndarray
    assert out.shape == (N_SLOTS, cfg.vocab_size)
    assert out.dtype == head.dtype == jnp.dtype(cfg.dtype)


@pytest.mark.parametrize("case", ["granite-1stage", "jamba-2stage"])
def test_decode_call_runs_no_op_outside_its_programs(case, monkeypatch):
    """Once compiled, the call binds no primitive eagerly: every device
    operation is inside a stage program. The op-by-op composition binds
    several (the check sees them)."""
    cfg, (pipe,) = _pipes(case, n=1)
    steps = _steps(cfg, len(pipe.stages))
    pipe.decode_slots_paged(*next(steps))          # compile
    bound = []
    eager = core.EvalTrace.process_primitive

    def spy(self, prim, args, params):
        bound.append(prim.name)
        return eager(self, prim, args, params)
    monkeypatch.setattr(core.EvalTrace, "process_primitive", spy)
    toks, pos, tabs = next(steps)
    pipe.decode_slots_paged(toks, pos, tabs)
    assert bound == []
    assert pipe.decode_programs == len(pipe.stages)
    _op_by_op(pipe, toks, pos, tabs)
    assert "gather" in bound


@pytest.mark.parametrize("case", ["granite-1stage", "granite-2stage"])
def test_traced_decode_spans_count_one_program_per_stage(case):
    """On the wall clock every ``decode`` span reads ``programs`` equal to
    the number of stages, and no ``embed`` or ``head`` span falls inside
    one; the inserts still have theirs."""
    cfg, (pipe,) = _pipes(case, n=1)
    b = PagedPipelineBatcher(pipe, n_slots=2, max_len=48, block_size=8)
    tracer = Tracer()
    b.tracer = tracer
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 9 + i
                                               ).astype(np.int32),
                    max_new_tokens=4, arrival=0.0) for i in range(3)]
    run_serve_loop([b], reqs, deadline=1e9, clock=WallClock(), tracer=tracer)
    assert all(len(r.output) == 4 for r in reqs)
    ev = [e for e in tracer.events if "id" in (e.get("args") or {})]
    decode = [e for e in ev if e["name"] == "decode"]
    assert decode
    assert all(e["args"]["programs"] == len(pipe.stages) for e in decode)
    ids = {e["args"]["id"] for e in decode}
    inner = [e for e in ev if e["name"] in ("embed", "head")]
    assert inner and all(e["args"]["parent"] not in ids for e in inner)
    for d in decode:
        end = d["ts"] + d["dur"]
        assert not any(d["ts"] <= e["ts"] < end for e in inner)
