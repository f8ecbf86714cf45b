"""Mixture-of-Experts MLP: top-k router + capacity-buffer dispatch.

Two dispatch strategies (MOE_DISPATCH module flag):

"grouped" (default; §Perf hillclimb in EXPERIMENTS.md): ranking and
capacity are computed PER BATCH ROW, so the (row, expert, capacity, d)
dispatch buffers inherit the batch's data-axis sharding and the rank cumsum
never crosses shards. Expert matmuls run as one batched einsum; under the
production mesh the only collective left is the row-parallel psum of the
d_ff-sharded second projection (or the expert-sharded all-to-all when
num_experts % tp == 0).

"global" (the naive baseline kept for the before/after measurement): one
global rank cumsum over all tokens and globally-indexed buffers — GSPMD
materializes cross-shard all-gathers/all-reduces for the scatter (the
collective-bound pathology in EXPERIMENTS.md §Perf).

Both drop overflowing tokens (combine weight 0) per capacity-factor
semantics, and both switch to dropless capacity for small token counts
(decode): per-row dropless needs only C = s slots since an expert appears at
most once in a token's top-k.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import parallel_ctx as ctx
from repro.models import quant

MOE_DISPATCH = "grouped"            # "grouped" | "global"


def moe_mlp(p, x, cfg, *, return_aux=False):
    if MOE_DISPATCH == "grouped" and ctx.active():
        return _moe_shard_map(p, x, cfg, return_aux=return_aux)
    if MOE_DISPATCH == "grouped":
        return _moe_grouped(p, x, cfg, return_aux=return_aux)
    return _moe_global(p, x, cfg, return_aux=return_aux)


# ---------------------------------------------------------------------------
# shard_map path (production mesh): dispatch and combine are SHARD-LOCAL by
# construction; the only collective is one psum(model) of the combined
# (b_local, s, d) activations per layer — the same pattern as a Megatron
# row-parallel MLP. Two expert-weight layouts:
#   E % tp == 0: expert-parallel — each model shard owns E/tp experts and
#                computes only its experts' contributions (partial over the
#                token's top-k set), summed by the psum;
#   else:        d_ff-parallel — every shard holds all experts with an f
#                slice; outputs are partial over f, summed by the psum.
# ---------------------------------------------------------------------------

def _moe_shard_map(p, x, cfg, *, return_aux):
    mesh = ctx.MESH
    model_ax = ctx.MODEL_AXIS
    data_axes = ctx.DATA_AXES
    tp = 1
    n_data = 1
    for n, sz in zip(mesh.axis_names, mesh.devices.shape):
        if n == model_ax:
            tp = sz
        if n in data_axes:
            n_data *= sz
    if x.shape[0] % n_data:
        data_axes = ()                 # tiny decode batch: replicate rows
    E, f = cfg.num_experts, cfg.d_ff
    expert_parallel = tp > 1 and E % tp == 0
    f_parallel = tp > 1 and not expert_parallel and f % tp == 0

    if expert_parallel:
        wspec = {"router": P(), "w_gate": P(model_ax, None, None),
                 "w_up": P(model_ax, None, None),
                 "w_down": P(model_ax, None, None)}
    elif f_parallel:
        wspec = {"router": P(), "w_gate": P(None, None, model_ax),
                 "w_up": P(None, None, model_ax),
                 "w_down": P(None, model_ax, None)}
    else:
        wspec = {k: P() for k in ("router", "w_gate", "w_up", "w_down")}
    wspec = {k: wspec[k] for k in p}           # align key order/presence
    xspec = P(data_axes if data_axes else None, None, None)

    def local(pl, xl):
        out, aux = _moe_local(pl, xl, cfg,
                              expert_offset_axis=(model_ax if expert_parallel
                                                  else None),
                              tp=tp if expert_parallel else 1)
        if tp > 1:
            out = jax.lax.psum(out, model_ax)
        if data_axes:
            aux = jax.lax.pmean(aux, data_axes)
        if tp > 1:
            aux = jax.lax.pmean(aux, model_ax) if not expert_parallel else \
                jax.lax.psum(aux, model_ax)
        return out, aux

    out, aux = jax.shard_map(
        local, mesh=mesh, in_specs=(wspec, xspec),
        out_specs=(xspec, P()), check_vma=False)(p, x)
    if return_aux:
        return out, aux
    return out


def _moe_local(p, x, cfg, *, expert_offset_axis, tp):
    """Per-shard grouped dispatch. With expert parallelism the shard owns
    experts [idx*E_loc, (idx+1)*E_loc) and drops other assignments (their
    contributions come from sibling shards via the psum)."""
    b, s, d = x.shape
    E, k = cfg.num_experts, cfg.top_k
    E_loc = E // tp

    logits = (x @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    if s * k <= 64:
        C = s
    else:
        C = max(int(cfg.capacity_factor * s * k / E), 1)

    fe = expert_ids.reshape(b, s * k)
    fg = gate_vals.reshape(b, s * k)

    if expert_offset_axis is not None:
        shard = jax.lax.axis_index(expert_offset_axis)
        fe_loc = fe - shard * E_loc
        owned = (fe_loc >= 0) & (fe_loc < E_loc)
    else:
        fe_loc = fe
        owned = jnp.ones_like(fe, bool)

    onehot = jax.nn.one_hot(jnp.where(owned, fe_loc, E_loc), E_loc,
                            dtype=jnp.int32)
    ranks = jnp.cumsum(onehot, axis=1) - onehot
    rank = jnp.where(owned, jnp.take_along_axis(
        ranks, jnp.clip(fe_loc, 0, E_loc - 1)[:, :, None], axis=2)[..., 0],
        C)
    keep = owned & (rank < C)
    slot = jnp.where(keep, fe_loc * C + rank, E_loc * C)

    src = jnp.repeat(x, k, axis=1)
    bidx = jnp.arange(b)[:, None]
    buf = jnp.zeros((b, E_loc * C + 1, d), x.dtype).at[bidx, slot].set(src)
    buf = buf[:, :E_loc * C].reshape(b, E_loc, C, d)

    out_buf = _expert_ffn(p, buf, cfg)
    flat = jnp.concatenate(
        [out_buf.reshape(b, E_loc * C, d),
         jnp.zeros((b, 1, d), out_buf.dtype)], axis=1)
    gathered = flat[bidx, slot]
    w = (fg * keep).astype(x.dtype)
    out = (gathered * w[..., None]).reshape(b, s, k, d).sum(axis=2)
    aux = _aux_loss(cfg, probs, fe, b * s * k)
    if expert_offset_axis is not None:
        aux = aux / tp                 # psum over shards reassembles it
    return out, aux


def _eins(buf, w, eq):
    """Expert einsum for plain or int8 w ({"q","s"}, s per (E, out))."""
    if quant.is_quantized(w):
        y = jnp.einsum(eq, buf, w["q"].astype(buf.dtype))
        s = w["s"].astype(buf.dtype)          # (E, out)
        return y * s[:, None, :]
    return jnp.einsum(eq, buf, w)


def _expert_ffn(p, buf, cfg):
    """buf (..., C, d) batched over the expert axis E."""
    if cfg.activation == "silu":
        hidden = jax.nn.silu(_eins(buf, p["w_gate"], "...ecd,edf->...ecf")) \
            * _eins(buf, p["w_up"], "...ecd,edf->...ecf")
    else:
        hidden = jax.nn.gelu(_eins(buf, p["w_up"], "...ecd,edf->...ecf"))
    return _eins(hidden, p["w_down"], "...ecf,efd->...ecd")


def _aux_loss(cfg, probs, flat_expert, denom):
    E = cfg.num_experts
    me = probs.mean(axis=tuple(range(probs.ndim - 1)))
    ce = jnp.zeros(E).at[flat_expert.reshape(-1)].add(1.0) / denom
    return E * jnp.sum(me * ce) * cfg.router_aux_coef


def _moe_grouped(p, x, cfg, *, return_aux):
    b, s, d = x.shape
    E, k = cfg.num_experts, cfg.top_k

    logits = (x @ p["router"]).astype(jnp.float32)               # (b,s,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)              # (b,s,k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    if s * k <= 64:
        C = s                      # per-row dropless (decode)
    else:
        C = max(int(cfg.capacity_factor * s * k / E), 1)

    fe = expert_ids.reshape(b, s * k)                            # (b,sk)
    fg = gate_vals.reshape(b, s * k)
    onehot = jax.nn.one_hot(fe, E, dtype=jnp.int32)              # (b,sk,E)
    ranks = jnp.cumsum(onehot, axis=1) - onehot                  # row-local
    rank = jnp.take_along_axis(ranks, fe[:, :, None], axis=2)[..., 0]
    keep = rank < C
    slot = jnp.where(keep, fe * C + rank, E * C)                 # (b,sk)

    src = jnp.repeat(x, k, axis=1)                               # (b,sk,d)
    bidx = jnp.arange(b)[:, None]
    buf = jnp.zeros((b, E * C + 1, d), x.dtype).at[bidx, slot].set(src)
    buf = buf[:, :E * C].reshape(b, E, C, d)

    out_buf = _expert_ffn(p, buf, cfg)                           # (b,E,C,d)
    flat = jnp.concatenate(
        [out_buf.reshape(b, E * C, d),
         jnp.zeros((b, 1, d), out_buf.dtype)], axis=1)
    gathered = flat[bidx, slot]                                  # (b,sk,d)
    w = (fg * keep).astype(x.dtype)
    out = (gathered * w[..., None]).reshape(b, s, k, d).sum(axis=2)

    if return_aux:
        return out, _aux_loss(cfg, probs, fe, b * s * k)
    return out


def _moe_global(p, x, cfg, *, return_aux):
    b, s, d = x.shape
    E, k = cfg.num_experts, cfg.top_k
    T = b * s
    xt = x.reshape(T, d)

    logits = (xt @ p["router"]).astype(jnp.float32)              # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)              # (T,k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    if T <= 8192:
        C = T                      # dropless
    else:
        C = max(int(cfg.capacity_factor * T * k / E), 1)

    flat_expert = expert_ids.reshape(T * k)
    flat_gate = gate_vals.reshape(T * k)
    onehot = jax.nn.one_hot(flat_expert, E, dtype=jnp.int32)
    ranks = jnp.cumsum(onehot, axis=0) - onehot                  # GLOBAL
    rank = jnp.take_along_axis(ranks, flat_expert[:, None], axis=1)[:, 0]
    keep = rank < C
    slot = jnp.where(keep, flat_expert * C + rank, E * C)

    src = jnp.repeat(xt, k, axis=0)
    buf = jnp.zeros((E * C + 1, d), x.dtype).at[slot].set(src)
    buf = buf[:E * C].reshape(E, C, d)

    expert_out = _expert_ffn(p, buf, cfg)
    flat_out = jnp.concatenate(
        [expert_out.reshape(E * C, d), jnp.zeros((1, d), expert_out.dtype)],
        axis=0)
    gathered = flat_out[slot]
    w = (flat_gate * keep).astype(x.dtype)
    out = (gathered * w[:, None]).reshape(T, k, d).sum(axis=1).reshape(
        b, s, d)
    if return_aux:
        return out, _aux_loss(cfg, probs, flat_expert, T * k)
    return out
