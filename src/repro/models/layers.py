"""Shared layer primitives: RMSNorm, RoPE, GQA attention blocks, MLP.

All functions are pure; parameters are plain dict pytrees. Sequence mixing
goes through repro.kernels.ops so the Pallas/XLA backend switch applies
uniformly.

Named scopes (``jax.named_scope``; ``repro.obs.trace.SCOPE_NAMES``) mark
what each device operation computes in its HLO metadata: ``attn_proj``
for the q/k/v and output projections, ``attention`` for rope, the paged
K/V write and the attention kernels, ``mlp`` for the MLP. They change
metadata only.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models.quant import kv_dtype_name, mm, quantize_kv_rows


def rms_norm(x, w, eps=1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def layer_norm(x, w, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w + b


@jax.named_scope("attention")
def rope(x, positions, theta):
    """x: (b, s, h, d); positions: (b, s) or (s,). theta==0 disables."""
    if theta == 0.0:
        return x
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs      # (b,s,d/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(positions, d):
    """Whisper-style sinusoidal embeddings. positions (b,s) -> (b,s,d)."""
    half = d // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                    / (half - 1))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Attention block
# ---------------------------------------------------------------------------

@jax.named_scope("attn_proj")
def _qkv(p, x, cfg):
    b, s, _ = x.shape
    hd = cfg.head_dim_
    q = mm(x, p["wq"])
    k = mm(x, p["wk"])
    v = mm(x, p["wv"])
    if cfg.attn_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(b, s, cfg.num_heads, hd)
    k = k.reshape(b, s, cfg.num_kv_heads, hd)
    v = v.reshape(b, s, cfg.num_kv_heads, hd)
    return q, k, v


@jax.named_scope("attn_proj")
def _wo(p, o):
    """Output projection of the attention heads o (b, s, heads * hd)."""
    return mm(o, p["wo"])


@jax.named_scope("attention")
def _write_pages(cache, blk, off, k, v):
    """Scatter new K/V rows into the page pools at (page blk, offset off);
    a QUANTIZED pool (cache carries "k_scale"/"v_scale") quantizes the rows
    on write (models/quant.quantize_kv_rows). Returns the new cache."""
    if "k_scale" in cache:
        kvd = kv_dtype_name(cache["k"].dtype)
        kq, ks = quantize_kv_rows(k, kvd)
        vq, vs = quantize_kv_rows(v, kvd)
        return {"k": cache["k"].at[blk, off].set(kq),
                "v": cache["v"].at[blk, off].set(vq),
                "k_scale": cache["k_scale"].at[blk, off].set(ks),
                "v_scale": cache["v_scale"].at[blk, off].set(vs)}
    return {"k": cache["k"].at[blk, off].set(k.astype(cache["k"].dtype)),
            "v": cache["v"].at[blk, off].set(v.astype(cache["v"].dtype))}


def attn_prefill(p, x, cfg, *, positions, kv_start=None, cache=None,
                 window=None):
    """Self-attention over a full (left-padded) prompt.

    positions (b,s) absolute; kv_start (b,) first valid index per row.
    Returns (out, new_cache). cache is written when provided:
      full cache:  {"k": (b,S,hkv,hd), "v": ...} written at [0:s]
      ring cache:  {"k": (b,W,hkv,hd), ...} last W keys
    """
    window = cfg.swa_window if window is None else window
    q, k, v = _qkv(p, x, cfg)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    o = ops.flash_attention(q, k, v, causal=True, window=window,
                            kv_start=kv_start)
    b, s, _, _ = q.shape
    out = _wo(p, o.reshape(b, s, -1))
    new_cache = None
    if cache is not None:
        new_cache = _write_rows(cache, k, v, s, window)
    return out, new_cache


@jax.named_scope("attention")
def _write_rows(cache, k, v, s, window):
    """A prompt's K/V (b, s, hkv, hd) written into a contiguous cache from
    position 0, or the last W keys of a ring cache. Returns the cache."""
    if window and cache["k"].shape[1] <= window:
        W = cache["k"].shape[1]
        if s >= W:
            # true ring layout: position p lives at slot p % W, so the
            # decode write at slot pos % W evicts exactly the oldest key
            ck = jnp.roll(k[:, -W:], s % W, axis=1)
            cv = jnp.roll(v[:, -W:], s % W, axis=1)
        else:
            pad = W - s
            ck = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            cv = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return {"k": ck.astype(cache["k"].dtype),
                "v": cv.astype(cache["v"].dtype)}
    nk = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k.astype(cache["k"].dtype), 0, axis=1)
    nv = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(cache["v"].dtype), 0, axis=1)
    return {"k": nk, "v": nv}


def attn_decode(p, x, cfg, *, pos, kv_start=None, cache=None, window=None):
    """One-token decode. x (b,1,d); pos: scalar int (uniform batch — the
    static-batching and dry-run path, in-place DUS write) or an int32 (b,)
    array of PER-ROW positions (continuous batching — scatter write).

    Full cache: write k,v at [pos], attend to [0:pos+1) minus kv_start pad.
    Ring cache (SWA): write at pos % W, attend to all valid ring slots.
    """
    window = cfg.swa_window if window is None else window
    q, k, v = _qkv(p, x, cfg)
    b = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    per_row = pos.ndim > 0
    posb = pos[:, None] if per_row else jnp.full((b, 1), pos, jnp.int32)
    q = rope(q, posb, cfg.rope_theta)
    k = rope(k, posb, cfg.rope_theta)
    o, nk, nv = _decode_core(q, k, v, pos, per_row, cache, window,
                             kv_start)
    out = _wo(p, o.reshape(b, 1, -1))
    return out, {"k": nk, "v": nv}


@jax.named_scope("attention")
def _decode_core(q, k, v, pos, per_row, cache, window, kv_start):
    """The new token's K/V write into a contiguous (or ring) cache and
    attention over it. Returns (o, new k cache, new v cache)."""
    b = q.shape[0]
    ring = window and cache["k"].shape[1] <= window
    ridx = jnp.arange(b)
    if ring:
        W = cache["k"].shape[1]
        slot = jnp.mod(pos, W)
        if per_row:
            nk = cache["k"].at[ridx, slot].set(
                k[:, 0].astype(cache["k"].dtype))
            nv = cache["v"].at[ridx, slot].set(
                v[:, 0].astype(cache["v"].dtype))
        else:
            nk = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
            nv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
        kv_len = jnp.broadcast_to(jnp.minimum(pos + 1, W), (b,))
        o = ops.decode_attention(q, nk, nv, kv_len=kv_len)
    else:
        if per_row:
            nk = cache["k"].at[ridx, pos].set(k[:, 0].astype(cache["k"].dtype))
            nv = cache["v"].at[ridx, pos].set(v[:, 0].astype(cache["v"].dtype))
        else:
            nk = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), pos, axis=1)
            nv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), pos, axis=1)
        kv_len = jnp.broadcast_to(pos + 1, (b,))
        o = ops.decode_attention(q, nk, nv, kv_len=kv_len, kv_start=kv_start)
    return o, nk, nv


def attn_decode_paged(p, x, cfg, *, pos, block_tables, cache):
    """One-token decode against a BLOCK-PAGED cache. x (b,1,d); pos (b,)
    per-row absolute positions; block_tables (b, max_blocks) int32;
    cache {"k","v"}: (n_blocks, block_size, hkv, hd) page pools shared by
    every row. The new token's K/V are scattered into the page holding
    position pos (block_tables[i, pos // bs], offset pos % bs) and
    attention gathers through the table (ops.paged_decode_attention).

    Rows whose table is all-null (free slots riding a joint iteration)
    write into the reserved trash page and read garbage that the caller
    discards — exactly like free slots in the contiguous path.

    A QUANTIZED pool (cache carries "k_scale"/"v_scale") quantizes the new
    token's K/V row on write (models/quant.quantize_kv_rows) and hands the
    scale pools to the fused-dequant kernel.
    """
    q, k, v = _qkv(p, x, cfg)
    b = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    assert pos.ndim == 1, "paged decode is per-row by construction"
    posb = pos[:, None]
    q = rope(q, posb, cfg.rope_theta)
    k = rope(k, posb, cfg.rope_theta)
    with jax.named_scope("attention"):
        bs = cache["k"].shape[1]
        ridx = jnp.arange(b)
        blk = jnp.asarray(block_tables, jnp.int32)[ridx, pos // bs]
        off = pos % bs
        nc = _write_pages(cache, blk, off, k[:, 0], v[:, 0])
        o = ops.paged_decode_attention(q, nc["k"], nc["v"], block_tables,
                                       kv_len=pos + 1,
                                       k_scale=nc.get("k_scale"),
                                       v_scale=nc.get("v_scale"))
    return _wo(p, o.reshape(b, 1, -1)), nc


def attn_context_paged(p, x, cfg, *, positions, q_len, block_tables, cache):
    """CONTEXT PREFILL against a BLOCK-PAGED cache: x (b,C,d) is a chunk of
    new tokens whose row-i token j sits at absolute position
    positions[i, j] = positions[i, 0] + j; the chunk attends causally to
    the pages holding positions [0, positions[:, 0]) AND to itself. The
    chunk's K/V are scattered into the pages first (same write the decode
    path does, C tokens at once), then attention reads back through the
    table (ops.paged_context_attention) — warm-prefix serving prefills
    only a prompt's cold suffix this way, chunked prefill feeds a long
    prompt through in several such calls.

    q_len (b,): real chunk length per row; padding tokens (j >= q_len)
    scatter into the reserved null page and their outputs are garbage the
    caller discards.
    """
    q, k, v = _qkv(p, x, cfg)
    b, C = x.shape[:2]
    positions = jnp.asarray(positions, jnp.int32)       # (b, C) absolute
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    with jax.named_scope("attention"):
        tbl = jnp.asarray(block_tables, jnp.int32)
        blk, off = _chunk_pages(cache, tbl, positions, q_len)
        q_start = positions[:, 0]
        kv_len = q_start + jnp.asarray(q_len, jnp.int32)
        nc = _write_pages(cache, blk, off, k, v)
        o = ops.paged_context_attention(q, nc["k"], nc["v"], tbl,
                                        q_start=q_start, kv_len=kv_len,
                                        k_scale=nc.get("k_scale"),
                                        v_scale=nc.get("v_scale"))
    return _wo(p, o.reshape(b, C, -1)), nc


def _chunk_pages(cache, tbl, positions, q_len):
    """Page and offset of each chunk token (b, C); padding tokens
    (j >= q_len) go to the reserved null page."""
    bs = cache["k"].shape[1]
    C = positions.shape[1]
    max_pos = tbl.shape[1] * bs - 1
    valid = jnp.arange(C)[None, :] < jnp.asarray(q_len, jnp.int32)[:, None]
    posc = jnp.minimum(positions, max_pos)              # pad rows stay legal
    blk = jnp.take_along_axis(tbl, posc // bs, axis=1)  # (b, C)
    blk = jnp.where(valid, blk, 0)                      # pads -> null page
    return blk, posc % bs


def attn_verify_paged(p, x, cfg, *, positions, q_len, block_tables, cache):
    """MULTI-TOKEN VERIFICATION against a BLOCK-PAGED cache (speculative
    decoding): x (b,T,d) is each slot's candidate chunk — the bonus token
    plus its draft proposals — whose row-i token j sits at absolute
    position positions[i, j] = positions[i, 0] + j, the slot's committed
    KV length. The chunk's K/V scatter into the pages first (the same
    write the decode path does, T tokens at once), then every candidate
    attends causally to the committed pages AND the candidate prefix
    through the per-slot-start verification kernel
    (ops.paged_verify_attention). The caller keeps the output at EVERY
    position: acceptance compares the target's next-token choice after
    each candidate against the next candidate.

    q_len (b,): real candidate count per row; rows with q_len == 0 (free /
    mid-prefill slots riding the joint dispatch) scatter into the reserved
    null page and come back dead. Rolling back REJECTED candidates is the
    caller's job (BlockTable.truncate) — their stale page writes sit past
    the committed length, masked by kv_len, and are overwritten by the
    next verification chunk.
    """
    q, k, v = _qkv(p, x, cfg)
    b, T = x.shape[:2]
    positions = jnp.asarray(positions, jnp.int32)       # (b, T) absolute
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    with jax.named_scope("attention"):
        tbl = jnp.asarray(block_tables, jnp.int32)
        blk, off = _chunk_pages(cache, tbl, positions, q_len)
        kv_start = positions[:, 0]
        kv_len = kv_start + jnp.asarray(q_len, jnp.int32)
        nc = _write_pages(cache, blk, off, k, v)
        o = ops.paged_verify_attention(q, nc["k"], nc["v"], tbl,
                                       kv_start=kv_start, kv_len=kv_len,
                                       k_scale=nc.get("k_scale"),
                                       v_scale=nc.get("v_scale"))
    return _wo(p, o.reshape(b, T, -1)), nc


def cross_attn(p, x, cfg, *, enc_kv=None, enc_out=None):
    """Whisper cross-attention. enc_kv: precomputed {"k","v"} over encoder
    frames (cached at prefill); or compute from enc_out."""
    b, s, _ = x.shape
    hd = cfg.head_dim_
    q = mm(x, p["wq"]).reshape(b, s, cfg.num_heads, hd)
    if enc_kv is None:
        se = enc_out.shape[1]
        k = mm(enc_out, p["wk"]).reshape(b, se, cfg.num_kv_heads, hd)
        v = mm(enc_out, p["wv"]).reshape(b, se, cfg.num_kv_heads, hd)
        enc_kv = {"k": k, "v": v}
    o = ops.flash_attention(q, enc_kv["k"].astype(q.dtype),
                            enc_kv["v"].astype(q.dtype), causal=False)
    return _wo(p, o.reshape(b, s, -1)), enc_kv


def attn_encoder(p, x, cfg):
    """Bidirectional self-attention (whisper encoder)."""
    q, k, v = _qkv(p, x, cfg)
    o = ops.flash_attention(q, k, v, causal=False)
    b, s = x.shape[:2]
    return _wo(p, o.reshape(b, s, -1))


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

@jax.named_scope("mlp")
def mlp(p, x, cfg):
    if cfg.activation == "silu":
        return mm(jax.nn.silu(mm(x, p["w_gate"])) * mm(x, p["w_up"]),
                  p["w_down"])
    return mm(jax.nn.gelu(mm(x, p["w_up"])), p["w_down"])
