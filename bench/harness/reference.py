"""The plain reference a served configuration is checked against.

A llama-style decoder (RMSNorm, rotary positions on the two halves of each
head, grouped-query causal attention, SwiGLU MLP) in straightforward
``jax.numpy`` and float32, at ``highest`` matmul precision: on a TPU a
float32 matmul otherwise runs in bf16 passes. Its weights are made here from
the seed, with the same draws as the served model's initialisation, one
layer at a time, so that nothing the program made is read.

It imports nothing of the program. Sizes come from the configuration file
(Hugging Face key names). Attention runs over blocks of queries and the MLP
over blocks of rows, so a sequence of tens of thousands of tokens fits on
one chip.
"""
from __future__ import annotations

import math
import zlib
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
BLOCK = 512                     # queries (and MLP rows) per block


def sizes(cfg: Dict) -> Dict:
    """The sizes the reference needs, from a configuration file."""
    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"d": d, "f": cfg["intermediate_size"], "hq": hq,
            "hkv": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // hq,
            "L": cfg["num_hidden_layers"], "V": cfg["vocab_size"],
            "eps": cfg["rms_norm_eps"], "theta": cfg["rope_theta"],
            "dtype": jnp.dtype(cfg["torch_dtype"])}


def supports(cfg: Dict) -> bool:
    return (cfg.get("model_type") == "llama" and cfg["hidden_act"] == "silu"
            and not cfg["attention_bias"] and not cfg["tie_word_embeddings"])


# ---- weights from the seed -------------------------------------------------
# The served model draws each matrix as normal(fold_in(name_key, layer)) *
# scale in float32 and stores it in the configuration's dtype; name_key
# folds crc32(name) into the seed's key (a layer's sub-key first folds in
# its position in the layer pattern, 0 here, and the MLP's a further 19).

def seed_key(seed: int):
    """The key every weight is drawn from. Seeds beyond 32 bits fold their
    high bits in, so no two seeds share weights."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _name_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) % (2 ** 31))


def _draw(key, name, layer, shape, scale, dtype):
    k = _name_key(key, name)
    w = jax.vmap(lambda p: jax.random.normal(jax.random.fold_in(k, p),
                                             shape, F32))(layer[None])[0]
    return (w * scale).astype(dtype)


@partial(jax.jit, static_argnums=0)
def _layer_weights(s, key, layer):
    d, f, hq, hkv, hd = s["d"], s["f"], s["hq"], s["hkv"], s["hd"]
    dt = s["dtype"]
    out = 0.02 / math.sqrt(2 * s["L"])
    k = jax.random.fold_in(key, 0)
    m = jax.random.fold_in(k, 19)
    return {
        "wq": _draw(k, "wq", layer, (d, hq * hd), 0.02, dt),
        "wk": _draw(k, "wk", layer, (d, hkv * hd), 0.02, dt),
        "wv": _draw(k, "wv", layer, (d, hkv * hd), 0.02, dt),
        "wo": _draw(k, "wo", layer, (hq * hd, d), out, dt),
        "w_gate": _draw(m, "w_gate", layer, (d, f), 0.02, dt),
        "w_up": _draw(m, "w_up", layer, (d, f), 0.02, dt),
        "w_down": _draw(m, "w_down", layer, (f, d), out, dt),
        "ln1": jnp.ones((d,), dt), "ln2": jnp.ones((d,), dt)}


@partial(jax.jit, static_argnums=0)
def _head_weights(s, key):
    def rand(name, shape):
        w = jax.random.normal(_name_key(key, name), shape, F32)
        return (w * 0.02).astype(s["dtype"])
    return {"embed": rand("embed", (s["V"], s["d"])),
            "lm_head": rand("lm_head", (s["d"], s["V"])),
            "final_norm": jnp.ones((s["d"],), s["dtype"])}


def _hashable(s: Dict):
    return tuple(sorted(s.items()))


class _Sizes(dict):
    """A sizes dict usable as a static jit argument."""

    def __hash__(self):
        return hash(_hashable(self))

    def __eq__(self, other):
        return dict.__eq__(self, other)


def layer_weights(cfg: Dict, seed: int, layer: int):
    return _layer_weights(_Sizes(sizes(cfg)), seed_key(seed),
                          jnp.int32(layer))


def head_weights(cfg: Dict, seed: int):
    return _head_weights(_Sizes(sizes(cfg)), seed_key(seed))


# ---- lower-precision weights (the control) ---------------------------------

def int8_weights(lp):
    """Each matrix rounded to int8 with one scale per output column (the
    largest magnitude of the column over 127), and back to float32."""
    def q(w):
        if w.ndim < 2:
            return w
        w = w.astype(F32)
        scale = jnp.maximum(jnp.abs(w).max(axis=0, keepdims=True) / 127,
                            1e-12)
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale
    return jax.tree.map(q, lp)


CONTROLS = {"int8": int8_weights}


# ---- the forward -----------------------------------------------------------

def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rotary(x, positions, theta):
    """x (s, h, d): rotate the pair (x[i], x[i + d/2]) by position *
    theta^(-2i/d)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions[:, None].astype(F32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@partial(jax.jit, static_argnums=0)
def _layer(s, lp, x):
    """One decoder layer over the sequence x (S, d), S a multiple of
    BLOCK, float32."""
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    S = x.shape[0]
    hq, hkv, hd = s["hq"], s["hkv"], s["hd"]
    pos = jnp.arange(S)
    h = _rms_norm(x, lp["ln1"], s["eps"])
    q = _rotary((h @ lp["wq"]).reshape(S, hq, hd), pos, s["theta"])
    k = _rotary((h @ lp["wk"]).reshape(S, hkv, hd), pos, s["theta"])
    v = (h @ lp["wv"]).reshape(S, hkv, hd)
    g = hq // hkv
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)

    def attend(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * BLOCK, BLOCK)
        qpos = i * BLOCK + jnp.arange(BLOCK)
        sc = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(F32(hd))
        sc = jnp.where((pos[None, :] <= qpos[:, None])[None], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)

    o = jax.lax.map(attend, jnp.arange(S // BLOCK)).reshape(S, hq * hd)
    x = x + o @ lp["wo"]

    def mlp(xb):
        hb = _rms_norm(xb, lp["ln2"], s["eps"])
        y = jax.nn.silu(hb @ lp["w_gate"]) * (hb @ lp["w_up"])
        return xb + y @ lp["w_down"]

    return jax.lax.map(mlp, x.reshape(S // BLOCK, BLOCK, -1)).reshape(S, -1)


@partial(jax.jit, static_argnums=0)
def _embed(s, head, tokens):
    return head["embed"][tokens].astype(F32)


@partial(jax.jit, static_argnums=0)
def _logits(s, head, x, rows):
    x = _rms_norm(x[rows], head["final_norm"].astype(F32), s["eps"])
    return x @ head["lm_head"].astype(F32)


def forward_logits(cfg: Dict, seed: int, seqs: Sequence[np.ndarray],
                   rows: Sequence[np.ndarray], *, devices: Sequence,
                   pad: int = BLOCK,
                   weights: Optional[Callable] = None) -> List[np.ndarray]:
    """Float32 logits (len(rows[r]), V) of each token sequence ``seqs[r]``
    at positions ``rows[r]``. Each sequence is padded at its end to a
    multiple of ``pad`` (causal: the padding changes no earlier position)
    and runs on ``devices[r % len(devices)]``, so that the devices work in
    parallel. ``weights`` maps a layer's weights before use (the control's
    lower precision)."""
    assert supports(cfg), cfg.get("name")
    s = _Sizes(sizes(cfg))
    assert pad % BLOCK == 0
    key = seed_key(seed)
    devs = [devices[r % len(devices)] for r in range(len(seqs))]
    used = list(dict.fromkeys(devs))
    with jax.default_matmul_precision("highest"):
        heads = {d: _head_weights(s, jax.device_put(key, d)) for d in used}
        xs = []
        for seq, d in zip(seqs, devs):
            n = -(-len(seq) // pad) * pad
            toks = np.zeros(n, np.int32)
            toks[:len(seq)] = seq
            xs.append(_embed(s, heads[d], jax.device_put(toks, d)))
        for i in range(s["L"]):
            lps = {d: _layer_weights(s, jax.device_put(key, d),
                                     jax.device_put(jnp.int32(i), d))
                   for d in used}
            if weights is not None:
                lps = {d: jax.jit(weights)(lp) for d, lp in lps.items()}
            xs = [_layer(s, lps[d], x) for x, d in zip(xs, devs)]
        out = [_logits(s, heads[d], x, jax.device_put(
            np.asarray(r, np.int32), d)) for x, r, d in zip(xs, rows, devs)]
        return [np.asarray(o) for o in out]
