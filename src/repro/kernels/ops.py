"""jit-ready kernel entry points used by the model code.

Each op has (i) a chunked, memory-frugal XLA implementation (the default on
CPU and the dry-run lowering path — flash-style online softmax / chunked scan
so 32k-500k sequences never materialize O(s^2) score tensors), and (ii) an
optional Pallas TPU kernel behind ``set_backend("pallas")`` (validated in
interpret mode by tests). The oracles live in ref.py.
"""
from __future__ import annotations

import contextlib
import functools
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref

_BACKEND = "xla"          # "xla" | "pallas" | "pallas_interpret"


def set_backend(name: str) -> None:
    """Select the kernel backend. Interpret mode exists to check kernels
    on the CPU; on a TPU it would silently run the kernels in the slow
    interpreter instead of on the chip, so it is refused there."""
    global _BACKEND
    assert name in ("xla", "pallas", "pallas_interpret"), name
    if name == "pallas_interpret" and jax.default_backend() == "tpu":
        raise ValueError("the pallas_interpret backend is for CPU checks; "
                         "the default backend is tpu")
    _BACKEND = name


def get_backend() -> str:
    return _BACKEND


@contextlib.contextmanager
def backend(name: str):
    """Scoped backend switch: ``with ops.backend("pallas_interpret"): ...``
    restores the previous backend even on error, so a failing kernel check
    can't leak the global into every later test in the process."""
    prev = _BACKEND
    set_backend(name)
    try:
        yield
    finally:
        set_backend(prev)


# ---------------------------------------------------------------------------
# Flash attention (prefill / training)
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal=True, window=0, kv_len=None,
                    kv_start=None, q_block=512, kv_block=512, scale=None):
    """Chunked attention. q (b,sq,hq,d); k,v (b,skv,hkv,d); GQA via hq%hkv==0.

    window > 0: sliding-window (each query sees the previous `window` keys,
    inclusive of itself) -- computed sub-quadratically via a static-width KV
    slice per query block.
    """
    if _BACKEND in ("pallas", "pallas_interpret"):
        from repro.kernels import flash_attention as fa
        return fa.flash_attention_pallas(
            q, k, v, causal=causal, window=window, kv_len=kv_len,
            kv_start=kv_start, q_block=q_block, kv_block=kv_block,
            scale=scale, interpret=(_BACKEND == "pallas_interpret"))
    return _flash_attention_xla(q, k, v, causal=causal, window=window,
                                kv_len=kv_len, kv_start=kv_start,
                                q_block=q_block, kv_block=kv_block,
                                scale=scale)


def _flash_attention_xla(q, k, v, *, causal, window, kv_len, kv_start,
                         q_block, kv_block, scale):
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    if window >= skv:
        window = 0                  # full-width band == plain causal
    q_block = min(q_block, sq)
    kv_block = min(kv_block, skv)
    if sq % q_block or (window == 0 and skv % kv_block):
        # Small/odd shapes (tests): fall back to the oracle.
        return ref.attention_ref(q, k, v, causal=causal, window=window,
                                 kv_len=kv_len, kv_start=kv_start,
                                 scale=scale)
    if window == 0:
        # flash path with FA2-style custom VJP: the backward recomputes
        # p blockwise instead of saving O(s^2) probabilities
        return _fa_full(causal, q_block, kv_block, scale, q, k, v,
                        kv_len, kv_start)

    nq = sq // q_block
    qf = q.astype(jnp.float32).reshape(b, nq, q_block, hkv, hq // hkv, d)
    qf = jnp.moveaxis(qf, 1, 0)                        # (nq,b,qblk,hkv,g,d)
    out = _swa_blocks(qf, k.astype(jnp.float32), v.astype(jnp.float32),
                      window=window, q_block=q_block, kv_len=kv_len,
                      kv_start=kv_start, causal=causal, scale=scale)
    out = jnp.moveaxis(out, 0, 1).reshape(b, sq, hq, d)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# FA2-style custom VJP for the full (non-windowed) flash path
# ---------------------------------------------------------------------------

def _fa_fwd_blocks(causal, q_block, kv_block, scale, q, k, v, kv_len,
                   kv_start):
    """Returns (out (b,sq,hq,d) f32-accumulated, lse (b,hkv,g,sq))."""
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    g = hq // hkv
    nq = sq // q_block
    qf = jnp.moveaxis(
        q.astype(jnp.float32).reshape(b, nq, q_block, hkv, g, d), 1, 0)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    nk = skv // kv_block
    kb = jnp.moveaxis(kf.reshape(b, nk, kv_block, hkv, d), 1, 0)
    vb = jnp.moveaxis(vf.reshape(b, nk, kv_block, hkv, d), 1, 0)

    def q_step(_, qi_and_idx):
        qi, iq = qi_and_idx
        qpos = iq * q_block + jnp.arange(q_block)

        def kv_step(carry, kj_and_idx):
            m, l, acc = carry
            kj, vj, jk = kj_and_idx
            s = _masked_scores(qi, kj, qpos, jk * kv_block, kv_block,
                               causal, kv_len, kv_start, scale)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.where(s <= ref.NEG_INF / 2, 0.0,
                          jnp.exp(s - m_new[..., None]))
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum("bhgqk,bkhd->bhgqd",
                                                     p, vj)
            return (m_new, l, acc), None

        m0 = jnp.full((b, hkv, g, q_block), ref.NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, q_block), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, q_block, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      (kb, vb, jnp.arange(nk)))
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        lse = jnp.where(m <= ref.NEG_INF / 2, 0.0,
                        m + jnp.log(jnp.maximum(l, 1e-30)))
        return None, (jnp.moveaxis(o, 3, 1), lse)

    _, (out, lse) = jax.lax.scan(q_step, None, (qf, jnp.arange(nq)))
    # out: (nq,b,qblk,hkv,g,d); lse: (nq,b,hkv,g,qblk)
    out = jnp.moveaxis(out, 0, 1).reshape(b, sq, hq, d)
    lse = jnp.moveaxis(lse, 0, 3).reshape(b, hkv, g, sq)
    return out, lse


def _masked_scores(qi, kj, qpos, kstart, kv_block, causal, kv_len, kv_start,
                   scale):
    kpos = kstart + jnp.arange(kv_block)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qi, kj) * scale
    if causal:
        mask = kpos[None, :] <= qpos[:, None]
        s = jnp.where(mask[None, None, None], s, ref.NEG_INF)
    if kv_len is not None:
        lm = kpos[None, :] < kv_len[:, None]
        s = jnp.where(lm[:, None, None, None, :], s, ref.NEG_INF)
    if kv_start is not None:
        sm = kpos[None, :] >= kv_start[:, None]
        s = jnp.where(sm[:, None, None, None, :], s, ref.NEG_INF)
    return s


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _fa_full(causal, q_block, kv_block, scale, q, k, v, kv_len, kv_start):
    out, _ = _fa_fwd_blocks(causal, q_block, kv_block, scale, q, k, v,
                            kv_len, kv_start)
    return out.astype(q.dtype)


def _fa_full_fwd(causal, q_block, kv_block, scale, q, k, v, kv_len, kv_start):
    out, lse = _fa_fwd_blocks(causal, q_block, kv_block, scale, q, k, v,
                              kv_len, kv_start)
    out = out.astype(q.dtype)
    return out, (q, k, v, out, lse, kv_len, kv_start)


def _fa_full_bwd(causal, q_block, kv_block, scale, res, do):
    q, k, v, o, lse, kv_len, kv_start = res
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    g = hq // hkv
    nq, nk = sq // q_block, skv // kv_block

    dof = do.astype(jnp.float32)
    of = o.astype(jnp.float32)
    # D_i = rowsum(do * o): (b,hkv,g,sq)
    Dx = jnp.moveaxis((dof * of).sum(-1).reshape(b, sq, hkv, g), 1, 3)

    def rq(t):
        return jnp.moveaxis(
            t.astype(jnp.float32).reshape(b, nq, q_block, hkv, g, d), 1, 0)

    qb = rq(q)
    dob = rq(do)
    kb = jnp.moveaxis(
        k.astype(jnp.float32).reshape(b, nk, kv_block, hkv, d), 1, 0)
    vb = jnp.moveaxis(
        v.astype(jnp.float32).reshape(b, nk, kv_block, hkv, d), 1, 0)
    lseb = jnp.moveaxis(lse.reshape(b, hkv, g, nq, q_block), 3, 0)
    Db = jnp.moveaxis(Dx.reshape(b, hkv, g, nq, q_block), 3, 0)

    def kv_step(dq_acc, kj_and):
        kj, vj, jk = kj_and

        def q_step(carry, qi_and):
            dk_j, dv_j = carry
            qi, doi, lse_i, D_i, iq = qi_and
            qpos = iq * q_block + jnp.arange(q_block)
            s = _masked_scores(qi, kj, qpos, jk * kv_block, kv_block,
                               causal, kv_len, kv_start, scale)
            p = jnp.where(s <= ref.NEG_INF / 2, 0.0,
                          jnp.exp(s - lse_i[..., None]))
            dv_j = dv_j + jnp.einsum("bhgqk,bqhgd->bkhd", p, doi)
            dp = jnp.einsum("bqhgd,bkhd->bhgqk", doi, vj)
            ds = p * (dp - D_i[..., None]) * scale
            dk_j = dk_j + jnp.einsum("bhgqk,bqhgd->bkhd", ds, qi)
            dq_i = jnp.einsum("bhgqk,bkhd->bqhgd", ds, kj)
            return (dk_j, dv_j), dq_i

        z = jnp.zeros((b, kv_block, hkv, d), jnp.float32)
        (dk_j, dv_j), dq_contrib = jax.lax.scan(
            q_step, (z, z), (qb, dob, lseb, Db, jnp.arange(nq)))
        # dq_contrib: (nq,b,qblk,hkv,g,d)
        dq_acc = dq_acc + jnp.moveaxis(dq_contrib, 0, 1).reshape(
            b, sq, hq, d)
        return dq_acc, (dk_j, dv_j)

    dq0 = jnp.zeros((b, sq, hq, d), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(kv_step, dq0, (kb, vb, jnp.arange(nk)))
    dk = jnp.moveaxis(dks, 0, 1).reshape(b, skv, hkv, d)
    dv = jnp.moveaxis(dvs, 0, 1).reshape(b, skv, hkv, d)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None, None)


_fa_full.defvjp(_fa_full_fwd, _fa_full_bwd)


def _full_blocks(qf, kf, vf, *, kv_block, q_block, kv_len, kv_start, causal,
                 scale):
    nq, b, _, hkv, g, d = qf.shape
    skv = kf.shape[1]
    nk = skv // kv_block
    kb = kf.reshape(b, nk, kv_block, hkv, d)
    vb = vf.reshape(b, nk, kv_block, hkv, d)

    def q_step(_, qi_and_idx):
        qi, iq = qi_and_idx                            # (b,qblk,hkv,g,d), scalar
        qpos = iq * q_block + jnp.arange(q_block)

        def kv_step(carry, kj_and_idx):
            m, l, acc = carry
            kj, vj, jk = kj_and_idx
            kpos = jk * kv_block + jnp.arange(kv_block)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qi, kj) * scale
            mask = jnp.ones((q_block, kv_block), bool)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            s = jnp.where(mask[None, None, None], s, ref.NEG_INF)
            if kv_len is not None:
                lm = kpos[None, :] < kv_len[:, None]
                s = jnp.where(lm[:, None, None, None, :], s, ref.NEG_INF)
            if kv_start is not None:
                sm = kpos[None, :] >= kv_start[:, None]
                s = jnp.where(sm[:, None, None, None, :], s, ref.NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.where(s <= ref.NEG_INF / 2, 0.0,
                          jnp.exp(s - m_new[..., None]))
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum("bhgqk,bkhd->bhgqd", p, vj)
            return (m_new, l, acc), None

        m0 = jnp.full((b, hkv, g, q_block), ref.NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, q_block), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, q_block, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), jnp.arange(nk)))
        o = acc / jnp.maximum(l, 1e-30)[..., None]     # (b,hkv,g,qblk,d)
        return None, jnp.moveaxis(o, 3, 1)             # (b,qblk,hkv,g,d)

    _, out = jax.lax.scan(q_step, None, (qf, jnp.arange(nq)))
    return out


def _swa_blocks(qf, kf, vf, *, window, q_block, kv_len, kv_start, causal,
                scale):
    """Sliding window: per q block, slice a static (window + q_block)-wide KV
    band -- FLOPs scale with s*window, not s^2."""
    nq, b, _, hkv, g, d = qf.shape
    skv = kf.shape[1]
    wlen = min(window + q_block, skv)

    def q_step(_, qi_and_idx):
        qi, iq = qi_and_idx
        qstart = iq * q_block
        start = jnp.maximum(qstart + q_block - wlen, 0)
        start = jnp.minimum(start, skv - wlen)
        kj = jax.lax.dynamic_slice_in_dim(kf, start, wlen, axis=1)
        vj = jax.lax.dynamic_slice_in_dim(vf, start, wlen, axis=1)
        qpos = qstart + jnp.arange(q_block)
        kpos = start + jnp.arange(wlen)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qi, kj) * scale
        mask = jnp.ones((q_block, wlen), bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        mask &= kpos[None, :] > (qpos[:, None] - window)
        s = jnp.where(mask[None, None, None], s, ref.NEG_INF)
        if kv_len is not None:
            lm = kpos[None, :] < kv_len[:, None]
            s = jnp.where(lm[:, None, None, None, :], s, ref.NEG_INF)
        if kv_start is not None:
            sm = kpos[None, :] >= kv_start[:, None]
            s = jnp.where(sm[:, None, None, None, :], s, ref.NEG_INF)
        m = s.max(-1, keepdims=True)
        p = jnp.where(s <= ref.NEG_INF / 2, 0.0, jnp.exp(s - m))
        o = jnp.einsum("bhgqk,bkhd->bhgqd", p, vj) / jnp.maximum(
            p.sum(-1, keepdims=True), 1e-30)
        return None, jnp.moveaxis(o, 3, 1)

    _, out = jax.lax.scan(q_step, None, (qf, jnp.arange(nq)))
    return out


# ---------------------------------------------------------------------------
# Decode attention (one new token against a cache)
# ---------------------------------------------------------------------------

def decode_attention(q, k, v, *, kv_len=None, kv_start=None, kv_block=0,
                     scale=None):
    """q (b,1,hq,d) against cache k,v (b,S,hkv,d). kv_len (b,) valid lengths.

    The XLA path materializes (b,hq,1,S) scores -- tiny even at 500k -- and
    keeps the cache in its storage dtype (bf16 MXU dot with f32 accumulation
    via preferred_element_type) instead of materializing an f32 copy: decode
    is HBM-bandwidth-bound on the cache stream (EXPERIMENTS.md §Perf).
    kv_block requests the Pallas flash-decode kernel's block size.
    """
    if _BACKEND in ("pallas", "pallas_interpret"):
        from repro.kernels import decode_attention as da
        return da.decode_attention_pallas(
            q, k, v, kv_len=kv_len, kv_start=kv_start,
            kv_block=kv_block or 512, scale=scale,
            interpret=(_BACKEND == "pallas_interpret"))
    return _decode_attention_xla(q, k, v, kv_len=kv_len, kv_start=kv_start,
                                 scale=scale)


def _decode_attention_xla(q, k, v, *, kv_len, kv_start, scale):
    b, one, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    qg = q.reshape(b, hkv, g, d)
    s = jnp.einsum("bhgd,bthd->bhgt", qg, k,
                   preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(skv)
    if kv_len is not None:
        s = jnp.where((kpos[None] < kv_len[:, None])[:, None, None],
                      s, ref.NEG_INF)
    if kv_start is not None:
        s = jnp.where((kpos[None] >= kv_start[:, None])[:, None, None],
                      s, ref.NEG_INF)
    m = s.max(-1, keepdims=True)
    p = jnp.where(s <= ref.NEG_INF / 2, 0.0, jnp.exp(s - m))
    l = p.sum(-1, keepdims=True)
    o = jnp.einsum("bhgt,bthd->bhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    o = o / jnp.maximum(l, 1e-30)
    o = jnp.where(m <= ref.NEG_INF / 2, 0.0, o)
    return o.reshape(b, 1, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged decode attention (one new token against a block-paged cache)
# ---------------------------------------------------------------------------

def paged_decode_attention(q, k_pages, v_pages, block_tables, *, kv_len=None,
                           scale=None, k_scale=None, v_scale=None):
    """q (b,1,hq,d) against a paged cache: k_pages/v_pages
    (n_blocks, block_size, hkv, d) shared by all sequences, block_tables
    (b, max_blocks) int32 mapping logical block j of row i to a physical
    page, kv_len (b,) valid lengths.

    The XLA path gathers each row's pages into a contiguous (b, S, hkv, d)
    view and reuses the contiguous decode kernel — with S equal to the
    contiguous slot length this is BIT-IDENTICAL to contiguous decode (the
    gathered values match everywhere attention can look, and masked tail
    positions contribute exact zeros either way). The Pallas path streams
    pages directly through the block table (kernels.paged_attention) and
    never materializes the gather.

    k_scale/v_scale (n_blocks, block_size, hkv) f32 mark a QUANTIZED pool
    (int8/fp8 payload, models/quant.py): the Pallas path fuses the dequant
    in-register before the score dot, the XLA path dequantizes the pool and
    gathers — both match ref.paged_decode_attention_quant_ref.
    """
    if k_scale is not None:
        if _BACKEND in ("pallas", "pallas_interpret"):
            from repro.kernels import paged_attention as pa
            return pa.paged_decode_attention_quant_pallas(
                q, k_pages, v_pages, k_scale, v_scale, block_tables,
                kv_len=kv_len, scale=scale,
                interpret=(_BACKEND == "pallas_interpret"))
        k = ref.gather_pages(ref.dequant_pages(k_pages, k_scale),
                             block_tables)
        v = ref.gather_pages(ref.dequant_pages(v_pages, v_scale),
                             block_tables)
        if kv_len is None:
            kv_len = jnp.full((q.shape[0],), k.shape[1], jnp.int32)
        return _decode_attention_xla(q, k, v, kv_len=kv_len, kv_start=None,
                                     scale=scale)
    if _BACKEND in ("pallas", "pallas_interpret"):
        from repro.kernels import paged_attention as pa
        return pa.paged_decode_attention_pallas(
            q, k_pages, v_pages, block_tables, kv_len=kv_len, scale=scale,
            interpret=(_BACKEND == "pallas_interpret"))
    k = ref.gather_pages(k_pages, block_tables)
    v = ref.gather_pages(v_pages, block_tables)
    if kv_len is None:
        kv_len = jnp.full((q.shape[0],), k.shape[1], jnp.int32)
    return _decode_attention_xla(q, k, v, kv_len=kv_len, kv_start=None,
                                 scale=scale)


def paged_context_attention(q, k_pages, v_pages, block_tables, *, q_start,
                            kv_len, scale=None, k_scale=None, v_scale=None):
    """CONTEXT PREFILL against a block-paged cache: q (b,C,hq,d) is a chunk
    of new tokens (row i's token j at absolute position q_start[i] + j)
    attending causally to the prior pages AND itself — the chunk's K/V must
    already be scattered into the pages at [q_start, kv_len) through the
    same block tables (layers.attn_context_paged does the write).

    This is the kernel behind warm-prefix serving (only the cold suffix of
    a prompt runs as the chunk, the shared prefix is reused from resident
    pages) and chunked prefill (a long prompt runs as several chunks
    interleaved with decode iterations). The XLA path gathers each row's
    pages into a contiguous view and materializes the (C, S) score tile —
    C is a bounded chunk width, so this stays small; the Pallas path
    streams pages through the block table with online softmax
    (kernels.paged_attention.paged_context_attention_pallas).

    k_scale/v_scale mark a quantized pool, as in paged_decode_attention.
    """
    if k_scale is not None:
        if _BACKEND in ("pallas", "pallas_interpret"):
            from repro.kernels import paged_attention as pa
            return pa.paged_context_attention_quant_pallas(
                q, k_pages, v_pages, k_scale, v_scale, block_tables,
                q_start=q_start, kv_len=kv_len, scale=scale,
                interpret=(_BACKEND == "pallas_interpret"))
        k = ref.gather_pages(ref.dequant_pages(k_pages, k_scale),
                             block_tables)
        v = ref.gather_pages(ref.dequant_pages(v_pages, v_scale),
                             block_tables)
        return ref.context_attention_ref(q, k, v, q_start=q_start,
                                         kv_len=kv_len, scale=scale)
    if _BACKEND in ("pallas", "pallas_interpret"):
        from repro.kernels import paged_attention as pa
        return pa.paged_context_attention_pallas(
            q, k_pages, v_pages, block_tables, q_start=q_start,
            kv_len=kv_len, scale=scale,
            interpret=(_BACKEND == "pallas_interpret"))
    k = ref.gather_pages(k_pages, block_tables)
    v = ref.gather_pages(v_pages, block_tables)
    return ref.context_attention_ref(q, k, v, q_start=q_start,
                                     kv_len=kv_len, scale=scale)


def paged_verify_attention(q, k_pages, v_pages, block_tables, *, kv_start,
                           kv_len, scale=None, k_scale=None, v_scale=None):
    """MULTI-TOKEN VERIFICATION against a block-paged cache (speculative
    decoding): q (b,T,hq,d) is each slot's candidate chunk — the bonus
    token plus up to T-1 draft proposals — whose row-i token j sits at
    absolute position kv_start[i] + j, the slot's per-request committed KV
    length. Candidates attend causally to the committed pages
    [0, kv_start[i]) AND the candidate prefix up to themselves; their K/V
    must already be scattered into the pages at [kv_start, kv_len)
    (layers.attn_verify_paged does the write). Unlike the context-prefill
    entry, callers consume the output at EVERY chunk position: greedy (or
    rejection-sampling) acceptance compares the target's argmax after
    candidate j against candidate j+1, so all T distributions matter.

    Rows with kv_len == kv_start carry zero real candidates (free /
    mid-prefill slots riding the joint dispatch) and come back as exact
    zeros. The Pallas path streams pages through the block table on the
    context grid with per-slot start offsets
    (kernels.paged_attention.paged_verify_attention_pallas); the XLA path
    gathers pages into a contiguous view and runs the oracle — T is k+1,
    a handful of tokens, so the (T, S) score tile stays tiny.

    k_scale/v_scale mark a quantized pool, as in paged_decode_attention.
    """
    if k_scale is not None:
        if _BACKEND in ("pallas", "pallas_interpret"):
            from repro.kernels import paged_attention as pa
            return pa.paged_verify_attention_quant_pallas(
                q, k_pages, v_pages, k_scale, v_scale, block_tables,
                kv_start=kv_start, kv_len=kv_len, scale=scale,
                interpret=(_BACKEND == "pallas_interpret"))
        k = ref.gather_pages(ref.dequant_pages(k_pages, k_scale),
                             block_tables)
        v = ref.gather_pages(ref.dequant_pages(v_pages, v_scale),
                             block_tables)
        return ref.context_attention_ref(q, k, v, q_start=kv_start,
                                         kv_len=kv_len, scale=scale)
    if _BACKEND in ("pallas", "pallas_interpret"):
        from repro.kernels import paged_attention as pa
        return pa.paged_verify_attention_pallas(
            q, k_pages, v_pages, block_tables, kv_start=kv_start,
            kv_len=kv_len, scale=scale,
            interpret=(_BACKEND == "pallas_interpret"))
    k = ref.gather_pages(k_pages, block_tables)
    v = ref.gather_pages(v_pages, block_tables)
    return ref.context_attention_ref(q, k, v, q_start=kv_start,
                                     kv_len=kv_len, scale=scale)


# ---------------------------------------------------------------------------
# Selective scan (Mamba S6)
# ---------------------------------------------------------------------------

def ssm_scan(x, dt, A, B, C, D, *, h0=None, chunk=128):
    """Chunked selective scan; see ref.ssm_scan_ref for semantics."""
    if _BACKEND in ("pallas", "pallas_interpret"):
        from repro.kernels import ssm_scan as sk
        return sk.ssm_scan_pallas(x, dt, A, B, C, D, h0=h0, chunk=chunk,
                                  interpret=(_BACKEND == "pallas_interpret"))
    return _ssm_scan_xla(x, dt, A, B, C, D, h0=h0, chunk=chunk)


def _ssm_scan_xla(x, dt, A, B, C, D, *, h0, chunk):
    b, s, din = x.shape
    ds = A.shape[-1]
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))   # dt=0 -> identity step
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    sp = s + pad
    nc = sp // chunk

    xf = x.astype(jnp.float32).reshape(b, nc, chunk, din)
    dtf = dt.astype(jnp.float32).reshape(b, nc, chunk, din)
    Bf = B.astype(jnp.float32).reshape(b, nc, chunk, ds)
    Cf = C.astype(jnp.float32).reshape(b, nc, chunk, ds)
    Af = A.astype(jnp.float32)

    h = jnp.zeros((b, din, ds), jnp.float32) if h0 is None \
        else h0.astype(jnp.float32)
    y, h = _ssm_chunks(xf, dtf, Bf, Cf, Af, h)
    y = y.reshape(b, sp, din)[:, :s]
    y = y + x.astype(jnp.float32)[:, :s] * D[None, None].astype(jnp.float32)
    return y.astype(x.dtype), h


def _ssm_chunk_step(Af, h, xc, dtc, Bc, Cc):
    """One chunk of the selective scan: (h, (b,c,*) inputs) -> (h', y)."""
    a = jnp.exp(dtc[..., None] * Af[None, None])       # (b,c,din,ds)
    bb = (dtc * xc)[..., None] * Bc[:, :, None, :]

    def comb(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a1 * a2, a2 * b1 + b2

    a_cum, h_intra = jax.lax.associative_scan(comb, (a, bb), axis=1)
    h_all = h_intra + a_cum * h[:, None]
    y = jnp.einsum("bcdn,bcn->bcd", h_all, Cc)
    return h_all[:, -1], y


@jax.custom_vjp
def _ssm_chunks(xf, dtf, Bf, Cf, Af, h0):
    """Chunk-scan with recompute-in-backward: forward saves only the
    chunk-boundary states (O(s/chunk)), backward re-runs each chunk under
    jax.vjp in reverse -- the O(s * d_state) scan internals never persist."""
    y, h, _ = _ssm_chunks_fwd_impl(xf, dtf, Bf, Cf, Af, h0)
    return y, h


def _ssm_chunks_fwd_impl(xf, dtf, Bf, Cf, Af, h0):
    def step(h, inp):
        xc, dtc, Bc, Cc = inp
        h2, y = _ssm_chunk_step(Af, h, xc, dtc, Bc, Cc)
        return h2, (y, h)                      # save ENTRY state per chunk

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (xf, dtf, Bf, Cf))
    h, (ys, h_ins) = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1).reshape(xf.shape), h, h_ins


def _ssm_chunks_fwd(xf, dtf, Bf, Cf, Af, h0):
    y, h, h_ins = _ssm_chunks_fwd_impl(xf, dtf, Bf, Cf, Af, h0)
    return (y, h), (xf, dtf, Bf, Cf, Af, h_ins)


def _ssm_chunks_bwd(res, cts):
    xf, dtf, Bf, Cf, Af, h_ins = res
    dy, dh_out = cts
    b, nc, c, din = xf.shape
    dyc = jnp.moveaxis(dy.reshape(b, nc, c, din), 1, 0)
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (xf, dtf, Bf, Cf))

    def rev_step(carry, inp):
        lam, dA = carry                        # cotangent wrt chunk-exit h
        xc, dtc, Bc, Cc, h_in, dy_c = inp

        def f(h, xc, dtc, Bc, Cc, A):
            return _ssm_chunk_step(A, h, xc, dtc, Bc, Cc)

        _, vjp = jax.vjp(f, h_in, xc, dtc, Bc, Cc, Af)
        dh_in, dxc, ddtc, dBc, dCc, dA_i = vjp((lam, dy_c))
        return (dh_in, dA + dA_i), (dxc, ddtc, dBc, dCc)

    xs_rev = tuple(t[::-1] for t in xs) + (h_ins[::-1], dyc[::-1])
    (dh0, dA), (dx, ddt, dB, dC) = jax.lax.scan(
        rev_step, (dh_out, jnp.zeros_like(Af)), xs_rev)
    unrev = lambda t: jnp.moveaxis(t[::-1], 0, 1)
    return unrev(dx), unrev(ddt), unrev(dB), unrev(dC), dA, dh0


_ssm_chunks.defvjp(_ssm_chunks_fwd, _ssm_chunks_bwd)


def ssm_step(x_t, dt_t, A, B_t, C_t, D, h):
    """Single decode step. x_t,dt_t (b,din); B_t,C_t (b,ds); h (b,din,ds)."""
    xf = x_t.astype(jnp.float32)
    dtf = dt_t.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    dA = jnp.exp(dtf[..., None] * Af[None])
    dBx = (dtf * xf)[..., None] * B_t.astype(jnp.float32)[:, None, :]
    h = dA * h + dBx
    y = jnp.einsum("bdn,bn->bd", h, C_t.astype(jnp.float32))
    y = y + xf * D.astype(jnp.float32)[None]
    return y.astype(x_t.dtype), h


# ---------------------------------------------------------------------------
# mLSTM chunked linear attention
# ---------------------------------------------------------------------------

def mlstm_scan(q, k, v, i_gate, f_gate, *, C0=None, n0=None, chunk=128):
    """Chunked mLSTM; see ref.mlstm_scan_ref. Gates in (0,1) (sigmoid)."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        zq = ((0, 0), (0, pad), (0, 0), (0, 0))
        q = jnp.pad(q, zq)
        k = jnp.pad(k, zq)
        v = jnp.pad(v, zq)
        i_gate = jnp.pad(i_gate, ((0, 0), (0, pad), (0, 0)))        # i=0
        f_gate = jnp.pad(f_gate, ((0, 0), (0, pad), (0, 0)),
                         constant_values=1.0)                        # f=1
    sp = s + pad
    nc = sp // chunk
    scale = 1.0 / (dk ** 0.5)

    def r(t, last):
        return jnp.moveaxis(
            t.astype(jnp.float32).reshape(b, nc, chunk, h, last), 1, 0)

    qs, ks, vs = r(q, dk), r(k, dk), r(v, dv)
    i_s = jnp.moveaxis(i_gate.astype(jnp.float32).reshape(b, nc, chunk, h), 1, 0)
    f_s = jnp.moveaxis(f_gate.astype(jnp.float32).reshape(b, nc, chunk, h), 1, 0)

    C = jnp.zeros((b, h, dk, dv), jnp.float32) if C0 is None else C0.astype(jnp.float32)
    n = jnp.zeros((b, h, dk), jnp.float32) if n0 is None else n0.astype(jnp.float32)

    tri = jnp.tril(jnp.ones((chunk, chunk), bool))

    def chunk_step(carry, inp):
        C, n = carry
        qc, kc, vc, ic, fc = inp                       # (b,c,h,*)
        logf = jnp.log(jnp.maximum(fc, 1e-30))         # (b,c,h)
        cum = jnp.cumsum(logf, axis=1)                 # log F_t
        # intra-chunk: decay[t,s] = exp(cum_t - cum_s) for s <= t (<= 1)
        dec = jnp.exp(jnp.clip(cum[:, :, None] - cum[:, None, :], None, 0.0))
        sc = jnp.einsum("bthd,bshd->bhts", qc * scale, kc)
        sc = sc * jnp.moveaxis(dec * ic[:, None, :, :], 3, 1)  # *(i_s) on s axis
        sc = jnp.where(tri[None, None], sc, 0.0)
        Ft = jnp.exp(cum)                              # (b,c,h)
        q_dec = qc * scale * Ft[..., None]
        num = jnp.einsum("bhts,bshd->bthd", sc, vc) + jnp.einsum(
            "bthk,bhkv->bthv", q_dec, C)
        den_intra = jnp.moveaxis(sc.sum(-1), 1, 2)     # (b,t,h)
        den_inter = jnp.einsum("bthk,bhk->bth", q_dec, n)
        den = jnp.abs(den_intra + den_inter)
        y = num / jnp.maximum(den, 1.0)[..., None]
        # carry update
        Fc = Ft[:, -1]                                 # (b,h) total decay
        rdec = jnp.exp(jnp.clip(cum[:, -1:, :] - cum, None, 0.0)) * ic  # F_c/F_s * i_s
        kiv = jnp.einsum("bshk,bsh,bshv->bhkv", kc, rdec, vc)
        kin = jnp.einsum("bshk,bsh->bhk", kc, rdec)
        C = Fc[..., None, None] * C + kiv
        n = Fc[..., None] * n + kin
        return (C, n), y

    (C, n), ys = jax.lax.scan(chunk_step, (C, n), (qs, ks, vs, i_s, f_s))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, sp, h, dv)[:, :s]
    return y.astype(q.dtype), (C, n)


def mlstm_step(q_t, k_t, v_t, i_t, f_t, C, n):
    """Single decode step. q_t,k_t (b,h,dk); v_t (b,h,dv); gates (b,h)."""
    dk = q_t.shape[-1]
    scale = 1.0 / (dk ** 0.5)
    qf = q_t.astype(jnp.float32) * scale
    C = f_t[..., None, None] * C + i_t[..., None, None] * (
        k_t.astype(jnp.float32)[..., :, None] * v_t.astype(jnp.float32)[..., None, :])
    n = f_t[..., None] * n + i_t[..., None] * k_t.astype(jnp.float32)
    num = jnp.einsum("bhk,bhkv->bhv", qf, C)
    den = jnp.abs(jnp.einsum("bhk,bhk->bh", qf, n))
    y = num / jnp.maximum(den, 1.0)[..., None]
    return y.astype(q_t.dtype), (C, n)
