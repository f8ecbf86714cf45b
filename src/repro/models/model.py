"""Model assembly: init / train-forward / prefill / decode for every family.

Layer stacks are organized into *periods*: the layer pattern of a hybrid
model (e.g. Jamba's mamba x7 + attn, MoE every 2) repeats with period
``period_len(cfg)``; parameters for each period position are stacked over a
leading ``n_periods`` axis and the stack is applied with ``jax.lax.scan`` so
the lowered HLO contains one period body regardless of depth — this keeps
the 512-device dry-run compiles tractable.

Caches are pytrees with the same period stacking and are carried through the
scan as (xs -> ys).
"""
from __future__ import annotations

import math
import zlib
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, MAMBA, MLSTM, SLSTM, ModelConfig
from repro.models import layers, mamba, moe, quant, xlstm
from repro.models.quant import mm


# ---------------------------------------------------------------------------
# Period structure
# ---------------------------------------------------------------------------

def period_len(cfg: ModelConfig) -> int:
    p = len(cfg.layer_pattern) if cfg.layer_pattern else 1
    m = cfg.moe_every if cfg.num_experts else 1
    return math.lcm(p, m)


def n_periods(cfg: ModelConfig) -> int:
    pl = period_len(cfg)
    assert cfg.num_layers % pl == 0, (cfg.name, cfg.num_layers, pl)
    return cfg.num_layers // pl


def sub_kinds(cfg: ModelConfig):
    """Kind + moe flag for each position within one period."""
    return [(cfg.layer_kind(j), cfg.is_moe_layer(j))
            for j in range(period_len(cfg))]


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------

def _norm_params(cfg, periods, d=None):
    d = d or cfg.d_model
    P = periods.shape[0]
    w = jnp.ones((P, d), _pdt(cfg))
    if cfg.is_encoder_decoder:                      # LayerNorm with bias
        return {"w": w, "b": jnp.zeros((P, d), _pdt(cfg))}
    return {"w": w}


def _pdt(cfg):
    return jnp.dtype(cfg.dtype)


def _name_key(key, name):
    # crc32, not hash(): str hashes are salted per process, and the weights
    # must come out the same in every process that uses the same seed
    return jax.random.fold_in(key, zlib.crc32(name.encode()) % (2 ** 31))


def _draw(key, shape, periods, sample):
    """``sample(k, shape)`` once per period, each from its own key, stacked
    over a leading period axis. Period p's values do not depend on which
    other periods are drawn, so one layer can be built alone
    (``init_layer_params``) with exactly the values of the whole stack."""
    return jax.vmap(lambda p: sample(jax.random.fold_in(key, p), shape))(
        periods)


def _rand(key, name, periods, shape, cfg, scale=0.02):
    w = _draw(_name_key(key, name), shape, periods,
              lambda k, s: jax.random.normal(k, s, jnp.float32))
    return (w * scale).astype(_pdt(cfg))


def _init_attn(key, cfg, periods, cross=False):
    d, hd = cfg.d_model, cfg.head_dim_
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    P = periods.shape[0]
    out_scale = 0.02 / math.sqrt(2 * cfg.num_layers)
    p = {
        "wq": _rand(key, "wq", periods, (d, hq * hd), cfg),
        "wk": _rand(key, "wk", periods, (d, hkv * hd), cfg),
        "wv": _rand(key, "wv", periods, (d, hkv * hd), cfg),
        "wo": _rand(key, "wo", periods, (hq * hd, d), cfg, out_scale),
    }
    if cfg.attn_bias and not cross:
        p["bq"] = jnp.zeros((P, hq * hd), _pdt(cfg))
        p["bk"] = jnp.zeros((P, hkv * hd), _pdt(cfg))
        p["bv"] = jnp.zeros((P, hkv * hd), _pdt(cfg))
    return p


def _init_mlp(key, cfg, periods):
    d, f = cfg.d_model, cfg.d_ff
    out_scale = 0.02 / math.sqrt(2 * cfg.num_layers)
    if cfg.activation == "silu":
        return {"w_gate": _rand(key, "w_gate", periods, (d, f), cfg),
                "w_up": _rand(key, "w_up", periods, (d, f), cfg),
                "w_down": _rand(key, "w_down", periods, (f, d), cfg,
                                out_scale)}
    return {"w_up": _rand(key, "w_up", periods, (d, f), cfg),
            "w_down": _rand(key, "w_down", periods, (f, d), cfg, out_scale)}


def _init_moe(key, cfg, periods):
    d, f, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    out_scale = 0.02 / math.sqrt(2 * cfg.num_layers)
    p = {"router": _rand(key, "router", periods, (d, E), cfg)}
    if cfg.activation == "silu":
        p["w_gate"] = _rand(key, "moe_gate", periods, (E, d, f), cfg)
        p["w_up"] = _rand(key, "moe_up", periods, (E, d, f), cfg)
    else:
        p["w_up"] = _rand(key, "moe_up", periods, (E, d, f), cfg)
    p["w_down"] = _rand(key, "moe_down", periods, (E, f, d), cfg, out_scale)
    return p


def _init_mamba(key, cfg, periods):
    d = cfg.d_model
    din = mamba.d_inner(cfg)
    dtr = mamba._dt_rank(cfg)
    ds = cfg.ssm_d_state
    w = cfg.ssm_d_conv
    P = periods.shape[0]
    A = jnp.tile(jnp.arange(1, ds + 1, dtype=jnp.float32)[None, None],
                 (P, din, 1))
    dt_init = jnp.exp(_draw(jax.random.fold_in(key, 7), (din,), periods,
                            jax.random.uniform) *
                      (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    dt_bias = dt_init + jnp.log(-jnp.expm1(-dt_init))   # inv softplus
    return {
        "in_proj": _rand(key, "in_proj", periods, (d, 2 * din), cfg),
        "conv_w": _rand(key, "conv_w", periods, (din, w), cfg, 0.1),
        "conv_b": jnp.zeros((P, din), _pdt(cfg)),
        "x_proj": _rand(key, "x_proj", periods, (din, dtr + 2 * ds), cfg),
        "dt_proj": _rand(key, "dt_proj", periods, (dtr, din), cfg, 0.1),
        "dt_bias": dt_bias.astype(jnp.float32),
        "A_log": jnp.log(A),
        "D": jnp.ones((P, din), jnp.float32),
        "out_proj": _rand(key, "mam_out", periods, (din, d), cfg,
                          0.02 / math.sqrt(2 * cfg.num_layers)),
    }


def _init_mlstm(key, cfg, periods):
    d = cfg.d_model
    din = xlstm.m_d_inner(cfg)
    qk = xlstm.m_qk_dim(cfg)
    h = cfg.num_heads
    return {
        "w_up": _rand(key, "w_up", periods, (d, 2 * din), cfg),
        "wq": _rand(key, "m_wq", periods, (din, qk), cfg),
        "wk": _rand(key, "m_wk", periods, (din, qk), cfg),
        "wv": _rand(key, "m_wv", periods, (din, din), cfg),
        "w_i": _rand(key, "m_wi", periods, (din, h), cfg),
        "w_f": _rand(key, "m_wf", periods, (din, h), cfg),
        "out_proj": _rand(key, "m_out", periods, (din, d), cfg,
                          0.02 / math.sqrt(2 * cfg.num_layers)),
    }


def _init_slstm(key, cfg, periods):
    d = cfg.d_model
    heads = cfg.num_heads
    dh = d // heads
    P = periods.shape[0]
    p = {"out_proj": _rand(key, "s_out", periods, (d, d), cfg,
                           0.02 / math.sqrt(2 * cfg.num_layers))}
    for g in ("z", "i", "f", "o"):
        p[f"w_{g}"] = _rand(key, f"s_w{g}", periods, (d, d), cfg)
        p[f"r_{g}"] = _rand(key, f"s_r{g}", periods, (heads, dh, dh), cfg)
        b = jnp.zeros((P, d), _pdt(cfg))
        if g == "f":
            b = b + 1.0  # forget-gate bias toward remembering
        p[f"b_{g}"] = b
    return p


def _init_sub(key, cfg, j, kind, is_moe, periods):
    key = jax.random.fold_in(key, j)
    sub = {"ln1": _norm_params(cfg, periods)}
    if kind == ATTN:
        sub["mixer"] = _init_attn(key, cfg, periods)
    elif kind == MAMBA:
        sub["mixer"] = _init_mamba(key, cfg, periods)
    elif kind == MLSTM:
        sub["mixer"] = _init_mlstm(key, cfg, periods)
    elif kind == SLSTM:
        sub["mixer"] = _init_slstm(key, cfg, periods)
    if cfg.is_encoder_decoder:
        sub["lnx"] = _norm_params(cfg, periods)
        sub["xattn"] = _init_attn(jax.random.fold_in(key, 91), cfg, periods,
                                  cross=True)
    has_mlp = cfg.d_ff > 0 and kind in (ATTN, MAMBA)
    if has_mlp:
        sub["ln2"] = _norm_params(cfg, periods)
        if is_moe:
            sub["moe"] = _init_moe(jax.random.fold_in(key, 17), cfg, periods)
        else:
            sub["mlp"] = _init_mlp(jax.random.fold_in(key, 19), cfg, periods)
    return sub


def init_head_params(cfg: ModelConfig, key):
    """Everything outside the layer stack: embedding, final norm, output
    head, and the encoder of an encoder-decoder. Equal to those entries of
    ``init_params(cfg, key)``."""
    def rand(name, shape):
        w = jax.random.normal(_name_key(key, name), shape, jnp.float32)
        return (w * 0.02).astype(_pdt(cfg))

    params = {
        "embed": rand("embed", (cfg.vocab_size, cfg.d_model)),
        "final_norm": {"w": jnp.ones((cfg.d_model,), _pdt(cfg)),
                       **({"b": jnp.zeros((cfg.d_model,), _pdt(cfg))}
                          if cfg.is_encoder_decoder else {})},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = rand("lm_head", (cfg.d_model, cfg.vocab_size))
    if cfg.is_encoder_decoder:
        pe = jnp.arange(cfg.num_encoder_layers)
        ekey = jax.random.fold_in(key, 1234)
        params["encoder"] = {
            "blocks": {"sub0": {
                "ln1": _norm_params(cfg, pe),
                "mixer": _init_attn(ekey, cfg, pe),
                "ln2": _norm_params(cfg, pe),
                "mlp": _init_mlp(jax.random.fold_in(ekey, 3), cfg, pe),
            }},
            "final_norm": {"w": jnp.ones((cfg.d_model,), _pdt(cfg)),
                           "b": jnp.zeros((cfg.d_model,), _pdt(cfg))},
        }
    return params


def init_params(cfg: ModelConfig, key):
    periods = jnp.arange(n_periods(cfg))
    params = init_head_params(cfg, key)
    params["blocks"] = {
        f"sub{j}": _init_sub(key, cfg, j, kind, is_moe, periods)
        for j, (kind, is_moe) in enumerate(sub_kinds(cfg))
    }
    return params


def init_layer_params(cfg: ModelConfig, key, i: int):
    """Un-stacked params of global layer ``i``, equal to
    ``slice_layer_params(cfg, init_params(cfg, key), i)`` but built without
    the rest of the stack."""
    p, j = layer_sub_index(cfg, i)
    return init_period_layer(cfg, key, p, j=j)


def init_period_layer(cfg: ModelConfig, key, p, *, j: int):
    """``init_layer_params`` of the layer at position ``j`` of period
    ``p``. ``p`` may be traced, so one compile builds every layer at
    position ``j``."""
    kind, is_moe = sub_kinds(cfg)[j]
    sub = _init_sub(key, cfg, j, kind, is_moe,
                    jnp.asarray(p, jnp.int32)[None])
    return jax.tree.map(lambda l: l[0], sub)


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------

def _sub_cache(cfg: ModelConfig, kind, lead, batch: int, max_len: int, dt):
    """One sublayer's contiguous cache with leading dims ``lead``: ``(P,)``
    for the period-stacked cache, ``()`` for a single layer."""
    hd = cfg.head_dim_
    c = {}
    if kind == ATTN:
        S = min(max_len, cfg.swa_window) if cfg.swa_window else max_len
        c["k"] = jnp.zeros((*lead, batch, S, cfg.num_kv_heads, hd), dt)
        c["v"] = jnp.zeros((*lead, batch, S, cfg.num_kv_heads, hd), dt)
        if cfg.is_encoder_decoder:
            c["cross_k"] = jnp.zeros(
                (*lead, batch, cfg.encoder_seq_len, cfg.num_kv_heads, hd), dt)
            c["cross_v"] = jnp.zeros(
                (*lead, batch, cfg.encoder_seq_len, cfg.num_kv_heads, hd), dt)
    elif kind == MAMBA:
        din = mamba.d_inner(cfg)
        c["conv"] = jnp.zeros((*lead, batch, cfg.ssm_d_conv - 1, din), dt)
        c["h"] = jnp.zeros((*lead, batch, din, cfg.ssm_d_state), jnp.float32)
    elif kind == MLSTM:
        h = cfg.num_heads
        qk_h = xlstm.m_qk_dim(cfg) // h
        v_h = xlstm.m_d_inner(cfg) // h
        c["C"] = jnp.zeros((*lead, batch, h, qk_h, v_h), jnp.float32)
        c["n"] = jnp.zeros((*lead, batch, h, qk_h), jnp.float32)
    elif kind == SLSTM:
        for nm in ("c", "n", "m", "h"):
            c[nm] = jnp.zeros((*lead, batch, cfg.d_model), jnp.float32)
    return c


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    """Stacked (n_periods, ...) cache pytree. max_len = prompt + new tokens."""
    lead = (n_periods(cfg),)
    dt = dtype or _pdt(cfg)
    return {f"sub{j}": _sub_cache(cfg, kind, lead, batch, max_len, dt)
            for j, (kind, _) in enumerate(sub_kinds(cfg))}


def _sub_paged_cache(cfg: ModelConfig, kind, lead, n_blocks: int,
                     block_size: int, n_slots: int, dtype, kv_dtype):
    """One sublayer's PAGED cache with leading dims ``lead`` (see
    ``init_paged_cache``): a page pool for attention, per-slot states for
    recurrent kinds."""
    if kind != ATTN:
        return _sub_cache(cfg, kind, lead, n_slots, 1, dtype or _pdt(cfg))
    if kv_dtype is None:
        dt = dtype or _pdt(cfg)
    else:
        dt = quant.kv_storage_dtype(kv_dtype)
    shape = (*lead, n_blocks, block_size, cfg.num_kv_heads)
    c = {"k": jnp.zeros((*shape, cfg.head_dim_), dt),
         "v": jnp.zeros((*shape, cfg.head_dim_), dt)}
    if kv_dtype is not None and quant.kv_is_quantized(kv_dtype):
        c["k_scale"] = jnp.zeros(shape, jnp.float32)
        c["v_scale"] = jnp.zeros(shape, jnp.float32)
    return c


def init_paged_cache(cfg: ModelConfig, n_blocks: int, block_size: int,
                     n_slots: int, dtype=None, kv_dtype=None):
    """Stacked (n_periods, ...) PAGED cache pytree.

    Attention sublayers get page pools ``(P, n_blocks, block_size, hkv, hd)``
    shared by every in-flight sequence and addressed through per-request
    block tables (serving.block_manager); recurrent-state sublayers
    (Mamba/xLSTM) keep their O(1) per-slot states exactly as in the
    contiguous layout — there is nothing to page. Block 0 of each pool is
    the reserved null/trash page.

    kv_dtype (models/quant.KV_DTYPES) selects the pool precision: None
    keeps the legacy behavior (``dtype`` or the model dtype), "fp32"/"bf16"
    force an unquantized pool at that width, and "int8"/"fp8" store scaled
    payloads with float32 per-token-per-head scale pools ``k_scale`` /
    ``v_scale`` of shape (P, n_blocks, block_size, hkv) alongside the
    payload — addressed by the same block ids, so COW / truncate /
    migration treat them as just another pool leaf.

    SWA ring caches and encoder-decoder cross-KV stay on the contiguous
    path (slot mode already excludes them — serving.pipeline.
    slot_mode_supported).
    """
    assert not (cfg.swa_window or cfg.is_encoder_decoder), \
        "paged layout covers full-KV text decoders"
    lead = (n_periods(cfg),)
    return {f"sub{j}": _sub_paged_cache(cfg, kind, lead, n_blocks,
                                        block_size, n_slots, dtype, kv_dtype)
            for j, (kind, _) in enumerate(sub_kinds(cfg))}


# ---------------------------------------------------------------------------
# Stack application
# ---------------------------------------------------------------------------

def _norm(cfg, p, x):
    if cfg.is_encoder_decoder:
        return layers.layer_norm(x, p["w"], p["b"], cfg.norm_eps)
    return layers.rms_norm(x, p["w"], cfg.norm_eps)


def apply_sublayer_seq(cfg, kind, sp, x, sc, *, positions, kv_start, valid,
                       enc_out, mode, lens=None):
    """One block (mixer [+ cross-attn] [+ MLP/MoE]) over a full sequence.
    mode: 'train' (no cache) | 'prefill' (write cache).
    lens (b,) marks RIGHT-padded rows (slot insertion); kv_start marks
    LEFT-padded rows (static batching). Returns (x, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    h = _norm(cfg, sp["ln1"], x)
    if kind == ATTN:
        mixer_cache = None
        if mode == "prefill" and sc is not None:
            mixer_cache = {"k": sc["k"], "v": sc["v"]}
        o, mc = layers.attn_prefill(sp["mixer"], h, cfg, positions=positions,
                                    kv_start=kv_start, cache=mixer_cache)
        nc = dict(mc) if mc is not None else {}
    elif kind == MAMBA:
        o, mc = mamba.mamba_prefill(sp["mixer"], h, cfg, valid=valid,
                                    lens=lens,
                                    cache=sc if mode == "prefill" else None)
        nc = mc or {}
    elif kind == MLSTM:
        o, mc = xlstm.mlstm_prefill(sp["mixer"], h, cfg, valid=valid,
                                    cache=sc if mode == "prefill" else None)
        nc = mc or {}
    elif kind == SLSTM:
        o, mc = xlstm.slstm_prefill(sp["mixer"], h, cfg, valid=valid,
                                    cache=sc if mode == "prefill" else None)
        nc = mc or {}
    x = x + o
    if cfg.is_encoder_decoder:
        hx = _norm(cfg, sp["lnx"], x)
        if mode == "prefill" and sc is not None:
            o, ekv = layers.cross_attn(sp["xattn"], hx, cfg, enc_out=enc_out)
            nc["cross_k"] = ekv["k"].astype(sc["cross_k"].dtype)
            nc["cross_v"] = ekv["v"].astype(sc["cross_v"].dtype)
        else:
            o, _ = layers.cross_attn(sp["xattn"], hx, cfg, enc_out=enc_out)
        x = x + o
    if "mlp" in sp:
        x = x + layers.mlp(sp["mlp"], _norm(cfg, sp["ln2"], x), cfg)
    elif "moe" in sp:
        o, a = moe.moe_mlp(sp["moe"], _norm(cfg, sp["ln2"], x), cfg,
                           return_aux=True)
        x = x + o
        aux = aux + a
    return x, nc, aux


def apply_sublayer_decode(cfg, kind, sp, x, sc, *, pos, kv_start):
    """One block for a single decode token. Returns (x, new_cache)."""
    h = _norm(cfg, sp["ln1"], x)
    if kind == ATTN:
        o, mc = layers.attn_decode(sp["mixer"], h, cfg, pos=pos,
                                   kv_start=kv_start,
                                   cache={"k": sc["k"], "v": sc["v"]})
        nc = dict(mc)
        if cfg.is_encoder_decoder:
            nc["cross_k"], nc["cross_v"] = sc["cross_k"], sc["cross_v"]
    elif kind == MAMBA:
        o, nc = mamba.mamba_decode(sp["mixer"], h, cfg, cache=sc)
    elif kind == MLSTM:
        o, nc = xlstm.mlstm_decode(sp["mixer"], h, cfg, cache=sc)
    elif kind == SLSTM:
        o, nc = xlstm.slstm_decode(sp["mixer"], h, cfg, cache=sc)
    x = x + o
    if cfg.is_encoder_decoder:
        hx = _norm(cfg, sp["lnx"], x)
        o, _ = layers.cross_attn(
            sp["xattn"], hx, cfg,
            enc_kv={"k": sc["cross_k"], "v": sc["cross_v"]})
        x = x + o
    if "mlp" in sp:
        x = x + layers.mlp(sp["mlp"], _norm(cfg, sp["ln2"], x), cfg)
    elif "moe" in sp:
        x = x + moe.moe_mlp(sp["moe"], _norm(cfg, sp["ln2"], x), cfg)
    return x, nc


def _paged_attn_cache(sc):
    """The attention leaves of one sublayer's paged cache — payload pools
    plus, for quantized pools, their scale companions."""
    return {n: sc[n] for n in ("k", "v", "k_scale", "v_scale") if n in sc}


def apply_sublayer_decode_paged(cfg, kind, sp, x, sc, *, pos,
                                block_tables):
    """One block for a single decode token against a PAGED cache.
    Attention sublayers address page pools through `block_tables`;
    recurrent-state sublayers are identical to the contiguous path (their
    cache rows ARE the slots). Returns (x, new_cache)."""
    h = _norm(cfg, sp["ln1"], x)
    if kind == ATTN:
        o, nc = layers.attn_decode_paged(sp["mixer"], h, cfg, pos=pos,
                                         block_tables=block_tables,
                                         cache=_paged_attn_cache(sc))
    elif kind == MAMBA:
        o, nc = mamba.mamba_decode(sp["mixer"], h, cfg, cache=sc)
    elif kind == MLSTM:
        o, nc = xlstm.mlstm_decode(sp["mixer"], h, cfg, cache=sc)
    elif kind == SLSTM:
        o, nc = xlstm.slstm_decode(sp["mixer"], h, cfg, cache=sc)
    x = x + o
    if "mlp" in sp:
        x = x + layers.mlp(sp["mlp"], _norm(cfg, sp["ln2"], x), cfg)
    elif "moe" in sp:
        x = x + moe.moe_mlp(sp["moe"], _norm(cfg, sp["ln2"], x), cfg)
    return x, nc


def apply_sublayer_context_paged(cfg, kind, sp, x, sc, *, positions, q_len,
                                 block_tables):
    """One block over a CHUNK of new tokens against a PAGED cache: the
    chunk's K/V scatter into pages and attention reads the prior context
    back through `block_tables` (layers.attn_context_paged) — the
    warm-prefix / chunked-prefill path. Attention-only by construction:
    a recurrent sublayer's state is a running summary with no per-block
    identity to share or resume, so hybrid stacks keep the one-shot
    prefill (serving.pipeline.context_mode_supported gates this).
    Returns (x, new_cache)."""
    assert kind == ATTN, \
        "paged context prefill covers attention-only stacks " \
        "(recurrent state cannot be resumed per block)"
    h = _norm(cfg, sp["ln1"], x)
    o, nc = layers.attn_context_paged(sp["mixer"], h, cfg,
                                      positions=positions, q_len=q_len,
                                      block_tables=block_tables,
                                      cache=_paged_attn_cache(sc))
    x = x + o
    if "mlp" in sp:
        x = x + layers.mlp(sp["mlp"], _norm(cfg, sp["ln2"], x), cfg)
    elif "moe" in sp:
        x = x + moe.moe_mlp(sp["moe"], _norm(cfg, sp["ln2"], x), cfg)
    return x, nc


def apply_sublayer_verify_paged(cfg, kind, sp, x, sc, *, positions, q_len,
                                block_tables):
    """One block over a slot's CANDIDATE CHUNK (bonus token + draft
    proposals) against a PAGED cache — the speculative-decoding
    verification step. The chunk's K/V scatter into pages at the slot's
    committed offset and every candidate attends to the committed context
    plus the candidate prefix (layers.attn_verify_paged); the caller reads
    the head at EVERY chunk position to run acceptance. Attention-only by
    construction, like the context path: a recurrent sublayer's state
    cannot be rolled back when candidates are rejected.
    Returns (x, new_cache)."""
    assert kind == ATTN, \
        "paged verification covers attention-only stacks " \
        "(recurrent state cannot be rolled back on rejection)"
    h = _norm(cfg, sp["ln1"], x)
    o, nc = layers.attn_verify_paged(sp["mixer"], h, cfg,
                                     positions=positions, q_len=q_len,
                                     block_tables=block_tables,
                                     cache=_paged_attn_cache(sc))
    x = x + o
    if "mlp" in sp:
        x = x + layers.mlp(sp["mlp"], _norm(cfg, sp["ln2"], x), cfg)
    elif "moe" in sp:
        x = x + moe.moe_mlp(sp["moe"], _norm(cfg, sp["ln2"], x), cfg)
    return x, nc


def _apply_period_verify_paged(cfg, pp, x, cache_p, *, positions, q_len,
                               block_tables):
    new_cache = {}
    for j, (kind, _) in enumerate(sub_kinds(cfg)):
        x, nc = apply_sublayer_verify_paged(
            cfg, kind, pp[f"sub{j}"], x, cache_p[f"sub{j}"],
            positions=positions, q_len=q_len, block_tables=block_tables)
        new_cache[f"sub{j}"] = nc
    return x, new_cache


def _apply_period_context_paged(cfg, pp, x, cache_p, *, positions, q_len,
                                block_tables):
    new_cache = {}
    for j, (kind, _) in enumerate(sub_kinds(cfg)):
        x, nc = apply_sublayer_context_paged(
            cfg, kind, pp[f"sub{j}"], x, cache_p[f"sub{j}"],
            positions=positions, q_len=q_len, block_tables=block_tables)
        new_cache[f"sub{j}"] = nc
    return x, new_cache


def _apply_period_seq(cfg, pp, x, cache_p, *, positions, kv_start, valid,
                      enc_out, mode, lens=None):
    new_cache = {}
    aux = jnp.zeros((), jnp.float32)
    for j, (kind, _) in enumerate(sub_kinds(cfg)):
        sc = cache_p.get(f"sub{j}") if cache_p is not None else None
        x, nc, a = apply_sublayer_seq(cfg, kind, pp[f"sub{j}"], x, sc,
                                      positions=positions, kv_start=kv_start,
                                      valid=valid, enc_out=enc_out, mode=mode,
                                      lens=lens)
        aux = aux + a
        new_cache[f"sub{j}"] = nc
    return x, new_cache, aux


def _apply_period_decode(cfg, pp, x, cache_p, *, pos, kv_start):
    new_cache = {}
    for j, (kind, _) in enumerate(sub_kinds(cfg)):
        x, nc = apply_sublayer_decode(cfg, kind, pp[f"sub{j}"], x,
                                      cache_p[f"sub{j}"], pos=pos,
                                      kv_start=kv_start)
        new_cache[f"sub{j}"] = nc
    return x, new_cache


def _apply_period_decode_paged(cfg, pp, x, cache_p, *, pos, block_tables):
    new_cache = {}
    for j, (kind, _) in enumerate(sub_kinds(cfg)):
        x, nc = apply_sublayer_decode_paged(cfg, kind, pp[f"sub{j}"], x,
                                            cache_p[f"sub{j}"], pos=pos,
                                            block_tables=block_tables)
        new_cache[f"sub{j}"] = nc
    return x, new_cache


# Activation checkpointing for training: recompute each period in the
# backward pass instead of saving its internals (the flash-attention chunk
# stats would otherwise grow O(s^2)). Policy is swappable for perf studies.
REMAT_TRAIN = True
REMAT_POLICY = None            # e.g. jax.checkpoint_policies.dots_saveable


def _scan_stack(cfg, blocks, x, cache, body):
    """scan over the period axis. cache may be None (train mode)."""
    if cache is None:
        def f(x, pp):
            x, _, aux = body(x, pp, None)
            return x, aux
        if REMAT_TRAIN:
            f = jax.checkpoint(f, policy=REMAT_POLICY)
        x, auxs = jax.lax.scan(f, x, blocks)
        return x, None, auxs.sum()

    def f(x, per):
        pp, cp = per
        x, nc, aux = body(x, pp, cp)
        return x, (nc, aux)

    x, (new_cache, auxs) = jax.lax.scan(f, x, (blocks, cache))
    return x, new_cache, auxs.sum()


# ---------------------------------------------------------------------------
# Per-layer access (asymmetric pipeline executor: stages hold arbitrary
# contiguous layer ranges, so they index into the period-stacked params)
# ---------------------------------------------------------------------------

def layer_sub_index(cfg: ModelConfig, i: int):
    """Global layer i -> (period index, sub index within period)."""
    pl = period_len(cfg)
    return i // pl, i % pl


def slice_layer_params(cfg: ModelConfig, params, i: int):
    """Un-stacked params of global layer i (leading period dim removed)."""
    p, j = layer_sub_index(cfg, i)
    return jax.tree.map(lambda l: l[p], params["blocks"][f"sub{j}"])


def init_layer_cache(cfg: ModelConfig, i: int, batch: int, max_len: int,
                     dtype=None):
    """Single-layer cache (no period axis), built at its own shape."""
    return _sub_cache(cfg, cfg.layer_kind(i), (), batch, max_len,
                      dtype or _pdt(cfg))


def init_layer_paged_cache(cfg: ModelConfig, i: int, n_blocks: int,
                           block_size: int, n_slots: int, dtype=None,
                           kv_dtype=None, kv_guard_layers=()):
    """Single-layer PAGED cache (no period axis), built at its own shape:
    attention layers get a page pool, recurrent layers their per-slot
    states.

    kv_guard_layers is the quality guard: global layer indices in it keep
    the model-default (unquantized) pool precision whatever ``kv_dtype``
    says — attention sinks concentrate in the first/last layers, so
    pinning those limits the quantization error where it compounds."""
    assert not (cfg.swa_window or cfg.is_encoder_decoder), \
        "paged layout covers full-KV text decoders"
    if i in kv_guard_layers:
        kv_dtype = None
    return _sub_paged_cache(cfg, cfg.layer_kind(i), (), n_blocks, block_size,
                            n_slots, dtype, kv_dtype)


# ---------------------------------------------------------------------------
# Slot cache pools (continuous batching): a replica owns one pre-allocated
# cache whose batch rows are SLOTS; inserting a request scatters its freshly
# prefilled cache rows over the free slots, fully replacing whatever a
# previous occupant left there. batch_axis=0 covers the per-layer caches of
# the asymmetric pipeline; batch_axis=1 the period-stacked monolithic cache.
# ---------------------------------------------------------------------------

def scatter_cache_rows(pool, rows, slot_ids, *, batch_axis=0):
    """Write `rows` (cache pytree, batch = len(slot_ids)) into `pool` at the
    given slot indices. Row seq lengths must match the pool's."""
    idx = jnp.asarray(slot_ids, jnp.int32)

    def put(big, small):
        if batch_axis == 0:
            return big.at[idx].set(small.astype(big.dtype))
        return big.at[:, idx].set(small.astype(big.dtype))

    return jax.tree.map(put, pool, rows)


def scatter_rows_to_pages(pages, rows, dest_blocks, *, batch_axis=0):
    """Write freshly prefilled contiguous cache rows into a PAGED pool.

    pages: {"k","v"} page pools (n_blocks, bs, h, d), or period-stacked
        (P, n_blocks, bs, h, d) with batch_axis=1.
    rows:  {"k","v"} contiguous rows (m, S, h, d) (resp. (P, m, S, h, d))
        with S a multiple of the block size.
    dest_blocks: (m * S // bs,) int32 physical page of each (row, logical
        block) pair, row-major; unallocated tail entries point at the null
        page and their (garbage, past-lens) contents are never unmasked.

    A QUANTIZED pool (``"k_scale"`` present) quantizes on write: each K/V
    row is split into an int8/fp8 payload plus per-token-per-head scales
    (models/quant.quantize_kv_rows, scheme inferred from the payload
    dtype), and both scatter through the same dest_blocks.
    """
    dest = jnp.asarray(dest_blocks, jnp.int32)

    def put(pool, row):
        if batch_axis == 0:
            m, S, h, d = row.shape
            bs = pool.shape[1]
            blocks = row.reshape(m * (S // bs), bs, h, d)
            return pool.at[dest].set(blocks.astype(pool.dtype))
        P, m, S, h, d = row.shape
        bs = pool.shape[2]
        blocks = row.reshape(P, m * (S // bs), bs, h, d)
        return pool.at[:, dest].set(blocks.astype(pool.dtype))

    def put_scale(pool, row):
        if batch_axis == 0:
            m, S, h = row.shape
            bs = pool.shape[1]
            blocks = row.reshape(m * (S // bs), bs, h)
            return pool.at[dest].set(blocks)
        P, m, S, h = row.shape
        bs = pool.shape[2]
        blocks = row.reshape(P, m * (S // bs), bs, h)
        return pool.at[:, dest].set(blocks)

    if isinstance(pages, dict) and "k_scale" in pages:
        kvd = quant.kv_dtype_name(pages["k"].dtype)
        out = {}
        for n in ("k", "v"):
            payload, sc = quant.quantize_kv_rows(rows[n], kvd)
            out[n] = put(pages[n], payload)
            out[n + "_scale"] = put_scale(pages[n + "_scale"], sc)
        return out
    return jax.tree.map(put, pages, rows)


def copy_cache_pages(cache, src_blocks, dst_blocks, *, stacked=True):
    """Copy-on-write support: duplicate page contents src -> dst in every
    attention K/V pool of a paged cache pytree (init_paged_cache layout
    when stacked=True, init_layer_paged_cache when False). Recurrent-state
    leaves are untouched — they are per-slot, never shared."""
    src = jnp.asarray(src_blocks, jnp.int32)
    dst = jnp.asarray(dst_blocks, jnp.int32)

    def one(c):
        if not (isinstance(c, dict) and "k" in c and "v" in c):
            return c
        out = dict(c)
        for n in ("k", "v", "k_scale", "v_scale"):
            if n not in c:
                continue
            if stacked:
                out[n] = c[n].at[:, dst].set(c[n][:, src])
            else:
                out[n] = c[n].at[dst].set(c[n][src])
        return out

    return {name: one(c) for name, c in cache.items()} \
        if isinstance(cache, dict) and all(
            isinstance(v, dict) for v in cache.values()) else one(cache)


def scatter_cache_rows_paged(pool, rows, slot_ids, dest_blocks, *,
                             batch_axis=0):
    """Paged counterpart of ``scatter_cache_rows`` for one sublayer's cache:
    attention K/V leaves scatter into pages via `dest_blocks`; every other
    leaf (recurrent states) scatters by slot id exactly as the contiguous
    path does."""
    if "k" in pool and "v" in pool:
        kv_names = ("k", "v", "k_scale", "v_scale")
        paged_part = scatter_rows_to_pages(
            {n: pool[n] for n in kv_names if n in pool},
            {"k": rows["k"], "v": rows["v"]},
            dest_blocks, batch_axis=batch_axis)
        rest_pool = {n: l for n, l in pool.items() if n not in kv_names}
        rest_rows = {n: l for n, l in rows.items() if n not in kv_names}
        out = dict(paged_part)
        if rest_pool:
            out.update(scatter_cache_rows(rest_pool, rest_rows, slot_ids,
                                          batch_axis=batch_axis))
        return out
    return scatter_cache_rows(pool, rows, slot_ids, batch_axis=batch_axis)


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def _embed(cfg, params, tokens):
    x = params["embed"][tokens]
    if cfg.family == "vlm":                      # gemma-style scaling
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def _head(cfg, params, x):
    x = _norm(cfg, params["final_norm"], x)
    if cfg.tie_embeddings:
        return x @ params["embed"].T
    return mm(x, params["lm_head"])


def _encoder_forward(cfg, params, frames):
    """Whisper encoder over stub frame embeddings (b, se, d)."""
    b, se, d = frames.shape
    pos = jnp.arange(se)[None].repeat(b, 0)
    x = frames + layers.sinusoidal_positions(pos, d).astype(frames.dtype)
    ep = params["encoder"]

    def body(x, pp):
        h = _norm(cfg, pp["ln1"], x)
        x = x + layers.attn_encoder(pp["mixer"], h, cfg)
        x = x + layers.mlp(pp["mlp"], _norm(cfg, pp["ln2"], x), cfg)
        return x, None

    x, _ = jax.lax.scan(body, x, ep["blocks"]["sub0"])
    return layers.layer_norm(x, ep["final_norm"]["w"], ep["final_norm"]["b"],
                             cfg.norm_eps)


def _prep_input_seq(cfg, params, batch):
    """tokens (+ modality stubs) -> (x, positions, extra_prefix_len)."""
    tokens = batch["tokens"]
    b, st = tokens.shape
    x = _embed(cfg, params, tokens)
    prefix = 0
    if cfg.num_image_tokens:
        img = batch["image_embeds"].astype(x.dtype)   # (b, n_img, d)
        x = jnp.concatenate([img, x], axis=1)
        prefix = cfg.num_image_tokens
    s = x.shape[1]
    positions = jnp.arange(s)[None].repeat(b, 0)
    if cfg.is_encoder_decoder and cfg.rope_theta == 0.0:
        x = x + layers.sinusoidal_positions(positions, cfg.d_model).astype(x.dtype)
    return x, positions, prefix


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def train_forward(cfg: ModelConfig, params, batch):
    """Full-sequence causal logits for training.
    batch: {"tokens": (b,s)} + optional "image_embeds"/"enc_frames".
    Returns (logits (b, s_total, V), aux_loss)."""
    x, positions, _ = _prep_input_seq(cfg, params, batch)
    enc_out = None
    if cfg.is_encoder_decoder:
        enc_out = _encoder_forward(cfg, params, batch["enc_frames"])

    def body(x, pp, cp):
        return _apply_period_seq(cfg, pp, x, cp, positions=positions,
                                 kv_start=None, valid=None, enc_out=enc_out,
                                 mode="train")

    x, _, aux = _scan_stack(cfg, params["blocks"], x, None, body)
    return _head(cfg, params, x), aux


def loss_fn(cfg: ModelConfig, params, batch):
    """Next-token cross entropy over the text positions."""
    logits, aux = train_forward(cfg, params, batch)
    tokens = batch["tokens"]
    prefix = cfg.num_image_tokens
    logits = logits[:, prefix:, :]
    pred = logits[:, :-1]
    tgt = tokens[:, 1:]
    logp = jax.nn.log_softmax(pred.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    return nll.mean() + aux


def prefill(cfg: ModelConfig, params, batch, cache, *, kv_start=None,
            lens=None):
    """Prompt pass; fills cache; returns (last-position logits (b,V), cache).

    Two padding conventions:
      * kv_start (b,): LEFT-padded rows (static batching) — pads consume the
        leading positions; logits read at the uniform last position.
      * lens (b,): RIGHT-padded rows (continuous-batching slot insertion) —
        row i's prompt occupies [0, lens[i]); trailing pads are masked to
        identity steps and the logits are gathered at each row's own last
        real token. Token positions then match isolated generation exactly,
        so a row's computation is independent of its batch-mates.
    """
    assert kv_start is None or lens is None, "pick one padding convention"
    x, positions, _ = _prep_input_seq(cfg, params, batch)
    b, s = x.shape[:2]
    valid = None
    if kv_start is not None:
        valid = (jnp.arange(s)[None, :] >= kv_start[:, None]).astype(jnp.int32)
    if lens is not None:
        valid = (jnp.arange(s)[None, :] < lens[:, None]).astype(jnp.int32)
    enc_out = None
    if cfg.is_encoder_decoder:
        enc_out = _encoder_forward(cfg, params, batch["enc_frames"])

    def body(x, pp, cp):
        return _apply_period_seq(cfg, pp, x, cp, positions=positions,
                                 kv_start=kv_start, valid=valid,
                                 enc_out=enc_out, mode="prefill", lens=lens)

    x, new_cache, _ = _scan_stack(cfg, params["blocks"], x, cache, body)
    if lens is not None:
        x_last = x[jnp.arange(b), lens - 1][:, None]
    else:
        x_last = x[:, -1:, :]
    logits = _head(cfg, params, x_last)[:, 0]
    return logits, new_cache


def decode_step(cfg: ModelConfig, params, tokens, cache, pos, *,
                kv_start=None):
    """One decode step. tokens (b,); pos: scalar absolute position of the
    new token (uniform batch, left-padded prompts) or an int32 (b,) array of
    per-row positions (continuous batching)."""
    x = _embed(cfg, params, tokens[:, None])
    if cfg.is_encoder_decoder and cfg.rope_theta == 0.0:
        b = tokens.shape[0]
        pos_a = jnp.asarray(pos)
        posb = pos_a[:, None] if pos_a.ndim else jnp.full((b, 1), pos_a)
        x = x + layers.sinusoidal_positions(posb, cfg.d_model).astype(x.dtype)

    def f(x, per):
        pp, cp = per
        x, nc = _apply_period_decode(cfg, pp, x, cp, pos=pos,
                                     kv_start=kv_start)
        return x, nc

    x, new_cache = jax.lax.scan(f, x, (params["blocks"], cache))
    logits = _head(cfg, params, x)[:, 0]
    return logits, new_cache


def prefill_paged_context(cfg: ModelConfig, params, tokens, cache, q_start,
                          q_len, block_tables):
    """CONTEXT PREFILL against the PAGED cache: run a chunk of new tokens
    (b, C) whose row-i token j sits at absolute position q_start[i] + j,
    attending to the pages holding [0, q_start) plus itself causally, and
    scatter the chunk's K/V into the pages through `block_tables`
    (b, max_blocks). This is how a warm-prefix request prefills only its
    cold suffix and how a long prompt prefills in fixed-size chunks.
    q_len (b,) real chunk lengths (trailing pads write the null page).
    Returns (last-real-token logits (b, V), cache). Attention-only stacks
    (apply_sublayer_context_paged asserts)."""
    x = _embed(cfg, params, tokens)
    b, C = tokens.shape
    starts = jnp.asarray(q_start, jnp.int32)
    lens = jnp.asarray(q_len, jnp.int32)
    positions = starts[:, None] + jnp.arange(C)[None]
    bt = jnp.asarray(block_tables, jnp.int32)

    def f(x, per):
        pp, cp = per
        x, nc = _apply_period_context_paged(cfg, pp, x, cp,
                                            positions=positions, q_len=lens,
                                            block_tables=bt)
        return x, nc

    x, new_cache = jax.lax.scan(f, x, (params["blocks"], cache))
    x_last = x[jnp.arange(b), lens - 1][:, None]
    logits = _head(cfg, params, x_last)[:, 0]
    return logits, new_cache


def verify_step_paged(cfg: ModelConfig, params, tokens, cache, kv_start,
                      q_len, block_tables):
    """MULTI-TOKEN VERIFICATION against the PAGED cache: run each row's
    candidate chunk `tokens` (b, T) — bonus token + draft proposals, row
    i's candidate j at absolute position kv_start[i] + j — in ONE forward
    pass, scattering the chunk's K/V through `block_tables`
    (b, max_blocks) and returning logits at EVERY chunk position:
    (logits (b, T, V), cache). Greedy acceptance then commits the longest
    candidate prefix matching the argmax chain; rejected candidates' page
    writes sit past the committed length (masked, overwritten next step).
    q_len (b,) real candidate counts (rows with 0 are dead padding).
    Attention-only stacks (apply_sublayer_verify_paged asserts)."""
    x = _embed(cfg, params, tokens)
    b, T = tokens.shape
    starts = jnp.asarray(kv_start, jnp.int32)
    lens = jnp.asarray(q_len, jnp.int32)
    positions = starts[:, None] + jnp.arange(T)[None]
    bt = jnp.asarray(block_tables, jnp.int32)

    def f(x, per):
        pp, cp = per
        x, nc = _apply_period_verify_paged(cfg, pp, x, cp,
                                           positions=positions, q_len=lens,
                                           block_tables=bt)
        return x, nc

    x, new_cache = jax.lax.scan(f, x, (params["blocks"], cache))
    return _head(cfg, params, x), new_cache


def decode_step_paged(cfg: ModelConfig, params, tokens, cache, pos,
                      block_tables):
    """One decode step against the PAGED cache (init_paged_cache layout).
    tokens (b,); pos (b,) per-row absolute positions; block_tables
    (b, max_blocks) int32, shared by every layer (each period's page pools
    are indexed with the same table)."""
    x = _embed(cfg, params, tokens[:, None])
    bt = jnp.asarray(block_tables, jnp.int32)

    def f(x, per):
        pp, cp = per
        x, nc = _apply_period_decode_paged(cfg, pp, x, cp, pos=pos,
                                           block_tables=bt)
        return x, nc

    x, new_cache = jax.lax.scan(f, x, (params["blocks"], cache))
    logits = _head(cfg, params, x)[:, 0]
    return logits, new_cache
