"""Plain float32 reference forward of an attention-only dense decoder.

Written from the architecture's equations (llama-style: RMSNorm, rotary
positions on the two halves of each head, grouped-query causal attention,
SwiGLU or GELU MLP) in straightforward ``jax.numpy``. It shares no code
with ``models/layers.py``, the kernels or the caches, so the serving path
can be checked against it. Matmuls run at ``highest`` precision: on a TPU a
float32 matmul otherwise runs in bf16 passes.

The forward runs one layer at a time and upcasts only that layer's weights,
so at published widths it fits beside a serving engine on the same chip.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, ModelConfig

F32 = jnp.float32


def supports(cfg: ModelConfig) -> bool:
    """The configurations this reference covers."""
    return (cfg.num_experts == 0 and not cfg.swa_window
            and not cfg.is_encoder_decoder and not cfg.num_image_tokens
            and cfg.family != "vlm" and cfg.rope_theta > 0
            and all(cfg.layer_kind(i) == ATTN
                    for i in range(cfg.num_layers)))


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rotary(x, positions, theta):
    """x (s, h, d); rotate the pair (x[i], x[i + d/2]) by positions *
    theta^(-2i/d)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions[:, None].astype(F32) * inv[None, :]       # (s, d/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@partial(jax.jit, static_argnums=0)
def _layer(cfg: ModelConfig, lp, x):
    """One decoder layer over the whole sequence x (s, d), float32."""
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    s = x.shape[0]
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    pos = jnp.arange(s)
    at = lp["mixer"]
    h = _rms_norm(x, lp["ln1"]["w"], cfg.norm_eps)
    q, k, v = h @ at["wq"], h @ at["wk"], h @ at["wv"]
    if cfg.attn_bias:
        q, k, v = q + at["bq"], k + at["bk"], v + at["bv"]
    q = _rotary(q.reshape(s, hq, hd), pos, cfg.rope_theta)
    k = _rotary(k.reshape(s, hkv, hd), pos, cfg.rope_theta)
    v = v.reshape(s, hkv, hd)
    g = hq // hkv                       # query heads per KV head
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(hd))
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", attn, v).reshape(s, hq * hd)
    x = x + o @ at["wo"]
    mp = lp["mlp"]
    h = _rms_norm(x, lp["ln2"]["w"], cfg.norm_eps)
    if cfg.activation == "silu":
        y = jax.nn.silu(h @ mp["w_gate"]) * (h @ mp["w_up"])
    else:
        y = jax.nn.gelu(h @ mp["w_up"])
    return x + y @ mp["w_down"]


@partial(jax.jit, static_argnums=0)
def _logits(cfg: ModelConfig, head, x):
    x = _rms_norm(x, head["final_norm"]["w"].astype(F32), cfg.norm_eps)
    out = head["embed"].T if cfg.tie_embeddings else head["lm_head"]
    return x @ out.astype(F32)


def forward_logits(cfg: ModelConfig, head, layer, tokens, rows):
    """Float32 logits (len(rows), V) of the sequence ``tokens`` (s,) at
    positions ``rows``. ``head`` holds ``embed``, ``final_norm`` and
    ``lm_head`` (as ``init_head_params`` builds them); ``layer(i)`` returns
    layer i's un-stacked params. Weights may be in any float dtype."""
    assert supports(cfg), cfg.name
    with jax.default_matmul_precision("highest"):
        x = head["embed"][jnp.asarray(tokens)].astype(F32)
        for i in range(cfg.num_layers):
            x = _layer(cfg, layer(i), x)
        return _logits(cfg, head, x[jnp.asarray(rows)])
