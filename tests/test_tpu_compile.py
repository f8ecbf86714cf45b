"""Compiles for a described TPU v5e, made here without a chip.

The TPU compiler is installed with JAX and compiles for a topology that is
described, not attached. These tests compile the stage programs the chip
path runs, at granite-8b's published widths, so that a program the chip's
compiler refuses fails here at no chip time. Nothing runs: they say nothing
of results or speed.

Only one process at a time may load the TPU library, and it keeps it until
it exits, so the topology is described only inside the module-scoped
fixture below, never while a module is imported. All such compiles live
in this one file, so one test worker loads the library.
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ATTN
from repro.configs.granite_8b import ONE_CHIP as CFG
from repro.models import model as M
from repro.serving import pipeline as PL

N_SLOTS, PROMPT, MAX_LEN, BLOCK, N_BLOCKS = 8, 512, 592, 16, 2048
KEY = jax.random.PRNGKey(0)
BF16 = jnp.bfloat16
I32 = jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _mesh(topo, n):
    return Mesh(np.array(topo.devices[:n]), ("model",))


def _sds(shape, dtype, mesh, spec=P()):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _layer_shapes(mesh, tp):
    """One granite-8b layer's params as sharded shapes on `mesh`."""
    shapes = jax.eval_shape(partial(M.init_layer_params, CFG, i=0), KEY)
    specs = PL.layer_specs(CFG, 0, shapes, tp)
    return [jax.tree.map(lambda s, p: _sds(s.shape, s.dtype, mesh, p),
                         shapes, specs)]


def _pools(mesh):
    """One layer's page pool, sharded as a stage allocates it."""
    kv = jax.ShapeDtypeStruct(
        (N_BLOCKS, BLOCK, CFG.num_kv_heads, CFG.head_dim_), BF16)
    pools = [{"k": kv, "v": kv}]
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        pools, PL.cache_shardings(CFG, mesh, pools))


POOL_BYTES = 2 * N_BLOCKS * BLOCK * CFG.num_kv_heads * CFG.head_dim_ * 2


def _compile_decode(mesh, tp):
    progs = PL.stage_programs(CFG, (ATTN,))
    return progs.decode_paged.lower(
        _layer_shapes(mesh, tp), _sds((N_SLOTS, 1, CFG.d_model), BF16, mesh),
        _pools(mesh), _sds((N_SLOTS,), I32, mesh),
        _sds((N_SLOTS, MAX_LEN // BLOCK), I32, mesh)).compile()


def test_paged_decode_layer_compiles_on_one_chip(topo):
    compiled = _compile_decode(_mesh(topo, 1), 1)
    # the pools are donated: the step writes them in place, no copy
    assert compiled.memory_analysis().temp_size_in_bytes < POOL_BYTES / 2
    assert "tpu_custom_call" not in compiled.as_text()   # the XLA path


def test_paged_decode_call_compiles_on_one_chip(topo):
    """The whole paged decode call of a one-stage pipeline: one layer with
    the embedding gather of the token ids in front and the final norm and
    head behind, in one program."""
    mesh = _mesh(topo, 1)
    names = PL.head_names(CFG, is_first=True, is_last=True)
    head = jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, mesh),
        {n: s for n, s in jax.eval_shape(
            partial(M.init_head_params, CFG), KEY).items() if n in names})
    progs = PL.stage_programs(CFG, (ATTN,), is_first=True, is_last=True)
    compiled = progs.decode_paged_call.lower(
        _layer_shapes(mesh, 1), head, _sds((N_SLOTS,), I32, mesh),
        _pools(mesh), _sds((N_SLOTS,), I32, mesh),
        _sds((N_SLOTS, MAX_LEN // BLOCK), I32, mesh)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < POOL_BYTES / 2
    assert "tpu_custom_call" not in compiled.as_text()   # the XLA path
    logits = compiled.out_info[0]
    assert logits.shape == (N_SLOTS, CFG.vocab_size)
    assert logits.dtype == jnp.dtype(CFG.dtype)


def test_insert_prefill_layer_compiles_on_one_chip(topo):
    mesh = _mesh(topo, 1)
    progs = PL.stage_programs(CFG, (ATTN,))
    scratch = (N_SLOTS, MAX_LEN, CFG.num_kv_heads, CFG.head_dim_)
    compiled = progs.prefill.lower(
        _layer_shapes(mesh, 1),
        _sds((N_SLOTS, PROMPT, CFG.d_model), BF16, mesh),
        [{"k": _sds(scratch, BF16, mesh), "v": _sds(scratch, BF16, mesh)}],
        _sds((N_SLOTS, PROMPT), I32, mesh), None,
        _sds((N_SLOTS, PROMPT), I32, mesh), None,
        _sds((N_SLOTS,), I32, mesh)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


def test_paged_context_layer_compiles_on_one_chip(topo):
    """The prefix-caching / chunked-prefill path: a 64-token chunk."""
    mesh = _mesh(topo, 1)
    progs = PL.stage_programs(CFG, (ATTN,))
    chunk = 64
    compiled = progs.context_paged.lower(
        _layer_shapes(mesh, 1), _sds((1, chunk, CFG.d_model), BF16, mesh),
        _pools(mesh), _sds((1, chunk), I32, mesh), _sds((1,), I32, mesh),
        _sds((1, MAX_LEN // BLOCK), I32, mesh)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


def test_tp2_layer_compiles_with_a_collective(topo):
    """One layer over two chips with the stage's Megatron shardings: the
    row-parallel projections need a collective to combine their sums, and
    the head-sharded pools are still updated in place (a pool whose
    sharding the step changed would be copied whole)."""
    compiled = _compile_decode(_mesh(topo, 2), 2)
    text = compiled.as_text()
    assert any(c in text for c in ("all-reduce", "reduce-scatter",
                                   "all-gather"))
    assert compiled.memory_analysis().temp_size_in_bytes < POOL_BYTES / 4


# ---- Pallas kernels at granite-8b widths: refused by the chip's compiler.
# The serving path runs the XLA ops; the PR that repairs a kernel flips its
# mark.

def _one_chip(topo):
    return _mesh(topo, 1)


_BLOCK_SHAPE = ("Mosaic refuses the block shape: the size-1 KV-head block "
                "is one of the last two block dimensions, which must be "
                "divisible by 8 and 128 or equal the array's")
_RANK1 = ("Mosaic refuses the rank-1 block (1,) over the per-row lengths: "
          "it must equal the batch or be a multiple of 128")


@pytest.mark.xfail(strict=True, raises=ValueError, reason=_BLOCK_SHAPE)
def test_pallas_paged_decode_compiles(topo):
    from repro.kernels.paged_attention import paged_decode_attention_pallas
    mesh = _one_chip(topo)
    hq, hkv, d = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim_
    kv = _sds((N_BLOCKS, BLOCK, hkv, d), BF16, mesh)
    jax.jit(paged_decode_attention_pallas).lower(
        _sds((N_SLOTS, 1, hq, d), BF16, mesh), kv, kv,
        _sds((N_SLOTS, MAX_LEN // BLOCK), I32, mesh),
        kv_len=_sds((N_SLOTS,), I32, mesh)).compile()


@pytest.mark.xfail(strict=True, raises=ValueError, reason=_BLOCK_SHAPE)
def test_pallas_paged_context_compiles(topo):
    from repro.kernels.paged_attention import paged_context_attention_pallas
    mesh = _one_chip(topo)
    hq, hkv, d = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim_
    kv = _sds((N_BLOCKS, BLOCK, hkv, d), BF16, mesh)
    jax.jit(paged_context_attention_pallas).lower(
        _sds((1, 64, hq, d), BF16, mesh), kv, kv,
        _sds((1, MAX_LEN // BLOCK), I32, mesh),
        q_start=_sds((1,), I32, mesh), kv_len=_sds((1,), I32, mesh)).compile()


@pytest.mark.xfail(strict=True, raises=ValueError, reason=_RANK1)
def test_pallas_flash_attention_compiles(topo):
    from repro.kernels.flash_attention import flash_attention_pallas
    mesh = _one_chip(topo)
    hq, hkv, d = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim_
    kv = _sds((N_SLOTS, PROMPT, hkv, d), BF16, mesh)
    jax.jit(flash_attention_pallas).lower(
        _sds((N_SLOTS, PROMPT, hq, d), BF16, mesh), kv, kv).compile()


@pytest.mark.xfail(strict=True, raises=ValueError, reason=_RANK1)
def test_pallas_decode_attention_compiles(topo):
    from repro.kernels.decode_attention import decode_attention_pallas
    mesh = _one_chip(topo)
    hq, hkv, d = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim_
    kv = _sds((N_SLOTS, MAX_LEN, hkv, d), BF16, mesh)
    jax.jit(decode_attention_pallas).lower(
        _sds((N_SLOTS, 1, hq, d), BF16, mesh), kv, kv,
        kv_len=_sds((N_SLOTS,), I32, mesh)).compile()
