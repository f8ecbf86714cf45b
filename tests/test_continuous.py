"""Continuous (iteration-level) batching — beyond-paper extension.
Correctness bar: a request's tokens are identical to isolated generation
regardless of what shares the batch, including slot reuse under queueing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as M
from repro.serving.continuous import PagedPipelineBatcher
from repro.serving.pipeline import AsymmetricPipeline
from repro.serving.request import Request

KEY = jax.random.PRNGKey(0)


def _engine(cfg, params, **kw):
    """The continuous engine over a one-stage pipeline of the whole model."""
    pipe = AsymmetricPipeline(cfg, params, [cfg.num_layers],
                              [[jax.devices()[0]]])
    return PagedPipelineBatcher(pipe, **kw)


def _isolated(cfg, params, prompt, n):
    cache = M.init_cache(cfg, 1, 48)
    lg, cache = M.prefill(cfg, params,
                          {"tokens": jnp.asarray(prompt)[None]}, cache)
    outs = []
    pos = len(prompt)
    for _ in range(n):
        nxt = int(np.asarray(lg).argmax())
        outs.append(nxt)
        lg, cache = M.decode_step(cfg, params, jnp.asarray([nxt]), cache, pos)
        pos += 1
    return outs


@pytest.mark.parametrize("arch", ["granite-8b", "phi3.5-moe-42b-a6.6b",
                                  "xlstm-125m"])
def test_continuous_equals_isolated(arch):
    cfg = get_config(arch).reduced()
    params = M.init_params(cfg, KEY)
    rng = np.random.RandomState(0)
    reqs = [Request(rid=i,
                    prompt=rng.randint(0, cfg.vocab_size,
                                       size=6 + 3 * i).astype(np.int32),
                    max_new_tokens=5) for i in range(3)]
    cb = _engine(cfg, params, n_slots=2, max_len=48, block_size=8)
    cb.serve(reqs, deadline=1e9)
    for r in reqs:
        assert list(r.output) == _isolated(cfg, params, r.prompt, 5), r.rid


def test_swa_rejected():
    cfg = get_config("h2o-danube-1.8b").reduced()
    params = M.init_params(cfg, KEY)
    with pytest.raises(AssertionError):
        _engine(cfg, params)


def test_slot_lifecycle():
    cfg = get_config("xlstm-125m").reduced()
    params = M.init_params(cfg, KEY)
    cb = _engine(cfg, params, n_slots=2, max_len=32, block_size=8)
    assert cb.free_slots() == [0, 1]
    r = Request(rid=7, prompt=np.arange(4, dtype=np.int32), max_new_tokens=2)
    cb.insert(r)
    assert cb.free_slots() == [1]
    done = {}
    while not done:
        done = cb.step()
    assert 7 in done and len(done[7]) == 2
    assert cb.free_slots() == [0, 1]
