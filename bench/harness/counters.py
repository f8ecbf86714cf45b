"""Operations and bytes of a served step, from the configuration's shapes.

Counted as the algorithm needs them, not as the program happens to move
them: a decode step reads every weight once (the embedding only at the rows
it looks up), the keys and values of each live row at its real length, and
writes the logits of its live rows. Waste such as gathering the whole
``max_len`` of every slot is then visible as a low roofline share.
"""
from __future__ import annotations

from typing import Dict

import jax.numpy as jnp


def shapes(cfg: Dict) -> Dict:
    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // hq
    hkv = cfg["num_key_value_heads"]
    f = cfg["intermediate_size"]
    attn = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
    mlp = 3 * d * f
    return {"d": d, "hq": hq, "hkv": hkv, "hd": hd, "f": f,
            "L": cfg["num_hidden_layers"], "V": cfg["vocab_size"],
            "item": jnp.dtype(cfg["torch_dtype"]).itemsize,
            "matmul_per_layer": attn + mlp,
            "params_per_layer": attn + mlp + 2 * d}


def weight_bytes(cfg: Dict) -> int:
    """Every weight: layers, embedding, final norm and output head."""
    s = shapes(cfg)
    n = s["L"] * s["params_per_layer"] + 2 * s["V"] * s["d"] + s["d"]
    return n * s["item"]


def kv_bytes_per_token(cfg: Dict) -> int:
    s = shapes(cfg)
    return s["L"] * 2 * s["hkv"] * s["hd"] * s["item"]


def decode_bytes(cfg: Dict, live: int, ctx_tokens: int,
                 logit_item: int = 2) -> int:
    """Bytes one decode step needs: the weights (embedding rows only), the
    KV of ``live`` rows attending to ``ctx_tokens`` tokens in all, and the
    live rows' logits."""
    s = shapes(cfg)
    w = (s["L"] * s["params_per_layer"] + s["V"] * s["d"] + s["d"]) \
        * s["item"]
    emb = live * s["d"] * s["item"]
    return w + emb + ctx_tokens * kv_bytes_per_token(cfg) \
        + live * s["V"] * logit_item


def decode_flops(cfg: Dict, live: int, ctx_tokens: int) -> int:
    """Operations one decode step needs: two per weight of every matrix
    (layers and output head) per live row, and the scores and the weighted
    sum of values over ``ctx_tokens`` attended tokens in every layer."""
    s = shapes(cfg)
    dense = 2 * (s["L"] * s["matmul_per_layer"] + s["d"] * s["V"]) * live
    attn = s["L"] * 4 * s["hq"] * s["hd"] * ctx_tokens
    return dense + attn
