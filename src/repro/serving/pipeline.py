"""Asymmetric pipeline executor (Contribution 1, §3.2).

Each stage owns a disjoint device subset with its OWN tensor-parallel degree
and its OWN contiguous span of layers. Per stage we build a 1-axis
``jax.sharding.Mesh`` ("model"), place that stage's parameters with the
Megatron specs from models.shardings, and jit prefill/decode stage functions
with in/out shardings. Activations move between stages with
``jax.device_put`` onto the next stage's mesh — the paper's leader-GPU
relay + intra-group broadcast falls out of the resharding copy (DESIGN.md §3).
"""
from __future__ import annotations

import dataclasses
import functools
import types
from functools import partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ATTN, ModelConfig
from repro.models import model as M
from repro.models import layers, shardings
from repro.obs.trace import NULL_STEP, NULL_TRACER


def _rep(mesh):
    return NamedSharding(mesh, P())


def layer_specs(cfg: ModelConfig, i: int, lp, tp: int):
    """Megatron PartitionSpecs of global layer ``i``'s un-stacked params
    ``lp`` (arrays or shapes) on a ``tp``-wide ``"model"`` mesh."""
    j = M.layer_sub_index(cfg, i)[1]
    stacked = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((1, *x.shape), x.dtype), lp)
    spec = shardings.param_specs(
        cfg, {"blocks": {f"sub{j}": stacked}}, tp=tp)["blocks"][f"sub{j}"]
    # strip the leading period axis of the stacked spec
    return jax.tree.map(lambda s: P(*s[1:]), spec,
                        is_leaf=lambda s: isinstance(s, P))


_KV_LEAVES = ("k", "v", "k_scale", "v_scale", "cross_k", "cross_v")


def cache_shardings(cfg: ModelConfig, mesh: Mesh, shapes):
    """Shardings of a stage's caches (arrays or shapes) on its mesh. K/V
    leaves — contiguous rows (b, S, hkv, d) and page pools
    (n_blocks, bs, hkv[, d]) alike — split their KV-head axis over the
    ``model`` axis wherever ``wk``/``wv`` split their columns, so each
    device keeps the heads it computes and a donated step updates them in
    place. Everything else (recurrent state) is replicated."""
    tp = mesh.devices.size
    split = tp > 1 and cfg.num_kv_heads % tp == 0

    def one(path, _):
        kv = split and getattr(path[-1], "key", None) in _KV_LEAVES
        return NamedSharding(mesh, P(None, None, "model") if kv else P())
    return jax.tree_util.tree_map_with_path(one, shapes)


def head_names(cfg: ModelConfig, *, is_first: bool, is_last: bool):
    """The entries of ``init_head_params`` a stage holds: the embedding
    (and encoder) where tokens enter, the final norm and output head
    where logits leave."""
    names = set()
    if is_first:
        names.add("embed")
        if cfg.is_encoder_decoder:
            names.add("encoder")
    if is_last:
        names.add("final_norm")
        names.add("embed" if cfg.tie_embeddings else "lm_head")
    return names


# ---- stage bodies (pure): `lps` is the stage's per-layer params ----------
# The params are ARGUMENTS of the jitted programs, never closed over: a
# closed-over array is lowered as an HLO constant, which at published
# widths puts gigabytes of weights into the program text.

def _stage_seq(cfg, kinds, lps, x, caches, positions, kv_start, valid,
               enc_out, lens=None, *, mode):
    new_caches = []
    for kind, lp, sc in zip(kinds, lps, caches):
        x, nc, _ = M.apply_sublayer_seq(
            cfg, kind, lp, x, sc, positions=positions, kv_start=kv_start,
            valid=valid, enc_out=enc_out, mode=mode, lens=lens)
        new_caches.append(nc)
    return x, new_caches


def _stage_decode(cfg, kinds, lps, x, caches, pos, kv_start, enc_out):
    new_caches = []
    for kind, lp, sc in zip(kinds, lps, caches):
        x, nc = M.apply_sublayer_decode(cfg, kind, lp, x, sc, pos=pos,
                                        kv_start=kv_start)
        new_caches.append(nc)
    return x, new_caches


def _stage_decode_paged(cfg, kinds, lps, x, caches, pos, block_tables):
    new_caches = []
    for kind, lp, sc in zip(kinds, lps, caches):
        x, nc = M.apply_sublayer_decode_paged(
            cfg, kind, lp, x, sc, pos=pos, block_tables=block_tables)
        new_caches.append(nc)
    return x, new_caches


def _decode_embed(cfg, hp, tokens, positions):
    """Single-token decode embedding (b, 1, d) of tokens (b,), with the
    sinusoidal positions where the architecture uses them."""
    x = M._embed(cfg, hp, tokens[:, None])
    if cfg.is_encoder_decoder and cfg.rope_theta == 0.0:
        x = x + layers.sinusoidal_positions(positions[:, None],
                                            cfg.d_model).astype(x.dtype)
    return x


def _stage_decode_paged_call(cfg, kinds, is_first, is_last, lps, hp, x,
                             caches, pos, block_tables):
    """The whole paged decode call of a first and/or last stage: the
    first takes token ids (b,) and gathers their embeddings, the last
    ends with the final norm and head and returns logits (b, V)."""
    if is_first:
        x = _decode_embed(cfg, hp, x, pos)
    x, caches = _stage_decode_paged(cfg, kinds, lps, x, caches, pos,
                                    block_tables)
    if is_last:
        # the head reads the activations rounded to their dtype, as the
        # op-by-op head did: fused into the last residual add, the norm
        # would see them at excess precision and move near-tied tokens
        x = jax.lax.optimization_barrier(x)
        x = M._head(cfg, hp, x)[:, 0]
    return x, caches


def _stage_context_paged(cfg, kinds, lps, x, caches, positions, q_len,
                         block_tables):
    new_caches = []
    for kind, lp, sc in zip(kinds, lps, caches):
        x, nc = M.apply_sublayer_context_paged(
            cfg, kind, lp, x, sc, positions=positions, q_len=q_len,
            block_tables=block_tables)
        new_caches.append(nc)
    return x, new_caches


def _stage_verify_paged(cfg, kinds, lps, x, caches, positions, q_len,
                        block_tables):
    new_caches = []
    for kind, lp, sc in zip(kinds, lps, caches):
        x, nc = M.apply_sublayer_verify_paged(
            cfg, kind, lp, x, sc, positions=positions, q_len=q_len,
            block_tables=block_tables)
        new_caches.append(nc)
    return x, new_caches


def _stage_copy_pages(caches, src, dst):
    """Duplicate page contents src -> dst in every attention layer's
    pools (copy-on-write). Donated + jitted so XLA updates the pools
    in place instead of materializing a copy of each one."""
    return [M.copy_cache_pages(c, src, dst, stacked=False) for c in caches]


def _stage_scatter_pages(caches, dst, payload):
    """Write migrated-in page payloads (one {"k","v"[,"k_scale",
    "v_scale"]} pytree per layer of this stage, leading axis = len(dst)
    blocks) into the pools at block ids `dst` (KV migration landing).
    Quantized pools ship the payload at wire width plus the float32
    scale leaves — no requantization on landing."""
    out = []
    for c, p in zip(caches, payload):
        c = dict(c)
        for n in p:
            c[n] = c[n].at[dst].set(p[n].astype(c[n].dtype))
        out.append(c)
    return out


def _stage_scatter_rows_paged(pools, rows, slot_ids, dest):
    """Scatter the first len(slot_ids) rows of a joint prefill (the rest
    are compile-shape padding) into the stage's pools: attention K/V into
    pages at `dest`, recurrent state by slot id. Donated + jitted, so each
    pool is updated in place rather than copied."""
    m = slot_ids.shape[0]
    return [M.scatter_cache_rows_paged(
        pool, jax.tree.map(lambda r: r[:m], row), slot_ids, dest)
        for pool, row in zip(pools, rows)]


@functools.lru_cache(maxsize=None)
def _layer_programs(cfg: ModelConfig, kinds: tuple):
    bodies = {
        "prefill": (partial(_stage_seq, cfg, kinds, mode="prefill"), ()),
        "decode": (partial(_stage_decode, cfg, kinds), (2,)),
        "decode_paged": (partial(_stage_decode_paged, cfg, kinds), (2,)),
        "context_paged": (partial(_stage_context_paged, cfg, kinds), (2,)),
        "verify_paged": (partial(_stage_verify_paged, cfg, kinds), (2,)),
        "copy_pages": (_stage_copy_pages, (0,)),
        "scatter_pages": (_stage_scatter_pages, (0,)),
        "scatter_rows_paged": (_stage_scatter_rows_paged, (0,)),
    }
    # built once per stage shape (memoized above), never per iteration
    return types.SimpleNamespace(**{
        name: jax.jit(fn, donate_argnums=d)  # repro: noqa[jit-retrace]
        for name, (fn, d) in bodies.items()})


@functools.lru_cache(maxsize=None)
def stage_programs(cfg: ModelConfig, kinds: tuple, is_first: bool = False,
                   is_last: bool = False):
    """The jitted programs of a stage whose layers have ``kinds``, shared
    by every stage of that shape. Each takes the stage's per-layer params
    first; the caches that follow are donated (updated in place).

    A first or last stage (``is_first``/``is_last``) also gets
    ``decode_paged_call(lps, head_params, x, caches, pos, block_tables)``:
    ``decode_paged`` with the embedding gather of the token ids ``x`` in
    front (first stage) and the final norm and head behind (last stage),
    so the paged decode call runs one program per stage. Middle stages
    run ``decode_paged``, activations in and out. The layer programs are
    the same objects whatever the stage's place."""
    progs = _layer_programs(cfg, kinds)
    if not (is_first or is_last):
        return progs
    call = partial(_stage_decode_paged_call, cfg, kinds, is_first, is_last)
    # memoized per stage shape and place, never per iteration
    return types.SimpleNamespace(
        **vars(progs),
        decode_paged_call=jax.jit(  # repro: noqa[jit-retrace]
            call, donate_argnums=(3,)))


class StageExecutor:
    """One pipeline stage: layers [lo, hi) on `devices` with TP=len(devices).

    With ``params`` (the whole period-stacked pytree) the stage slices and
    places its layers. With ``params=None`` it builds them from ``key``
    directly in their sharding on its own devices, so no device ever holds
    more than this stage's share of the model. The values are
    ``M.init_params(cfg, key)``'s up to the last bit of the float32 draw:
    the jitted build may fuse the draw's scale differently."""

    def __init__(self, cfg: ModelConfig, params, lo: int, hi: int,
                 devices: Sequence[jax.Device], *, is_first: bool,
                 is_last: bool, key=None):
        self.cfg = cfg
        self.lo, self.hi = lo, hi
        self.is_first, self.is_last = is_first, is_last
        self.tp = len(devices)
        self.mesh = Mesh(np.array(devices), ("model",))
        self.kinds = [cfg.layer_kind(i) for i in range(lo, hi)]
        assert params is not None or key is not None, \
            "a stage needs the params or the key to build them from"

        self.layer_params = []
        build = {}                 # period position -> jitted layer init
        for i in range(lo, hi):
            shapes = (M.slice_layer_params(cfg, params, i)
                      if params is not None else
                      jax.eval_shape(partial(M.init_layer_params, cfg, i=i),
                                     key))
            sh = jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                              layer_specs(cfg, i, shapes, self.tp))
            if params is not None:
                self.layer_params.append(jax.device_put(shapes, sh))
                continue
            p, j = M.layer_sub_index(cfg, i)
            if j not in build:
                build[j] = jax.jit(partial(M.init_period_layer, cfg, j=j),
                                   out_shardings=sh)
            self.layer_params.append(build[j](key, jnp.int32(p)))

        self.head_params = None
        names = head_names(cfg, is_first=is_first, is_last=is_last)
        if names:
            if params is not None:
                hp = {n: params[n] for n in names}
                self.head_params = jax.device_put(hp, _rep(self.mesh))
            else:
                self.head_params = jax.jit(
                    lambda k: {n: v for n, v in
                               M.init_head_params(cfg, k).items()
                               if n in names},
                    out_shardings=_rep(self.mesh))(key)

        progs = self._progs = stage_programs(
            cfg, tuple(self.kinds), is_first=is_first, is_last=is_last)
        lps = self.layer_params
        self._prefill_jit = partial(progs.prefill, lps)
        self._decode_jit = partial(progs.decode, lps)
        self._decode_paged_jit = partial(progs.decode_paged, lps)
        self._context_paged_jit = partial(progs.context_paged, lps)
        self._verify_paged_jit = partial(progs.verify_paged, lps)
        self._copy_pages_jit = progs.copy_pages
        self._scatter_pages_jit = progs.scatter_pages
        self._scatter_rows_paged_jit = progs.scatter_rows_paged
        self._allocs = {}

    @property
    def has_attn(self) -> bool:
        return ATTN in self.kinds

    def decode_paged_call(self, x, caches, pos, block_tables):
        """This stage's part of a paged decode call, as one program: token
        ids in on the first stage, activations otherwise; logits out on
        the last stage, activations otherwise. Returns (out, caches)."""
        if self.is_first or self.is_last:
            return self._progs.decode_paged_call(
                self.layer_params, self.head_params, x, caches, pos,
                block_tables)
        return self._decode_paged_jit(x, caches, pos, block_tables)

    # ---- cache ------------------------------------------------------------
    def _alloc(self, name, make):
        """``make()`` (fresh caches) built directly on this stage's devices
        in their ``cache_shardings`` — never staged through the default
        device. One compile per ``name``, which must encode every shape
        ``make`` depends on."""
        fn = self._allocs.get(name)
        if fn is None:
            out = cache_shardings(self.cfg, self.mesh, jax.eval_shape(make))
            # memoized per name: one program per cache shape
            fn = self._allocs[name] = jax.jit(  # repro: noqa[jit-retrace]
                make, out_shardings=out)
        return fn()

    def make_caches(self, batch: int, max_len: int):
        return self._alloc(
            ("cache", batch, max_len),
            lambda: [M.init_layer_cache(self.cfg, i, batch, max_len)
                     for i in range(self.lo, self.hi)])

    def make_paged_caches(self, n_blocks: int, block_size: int,
                          n_slots: int, *, kv_dtype=None,
                          kv_guard_layers=()):
        """Per-layer paged caches; this stage's attention layers all share
        ONE physical pool id-space of `n_blocks` blocks (each layer holds
        its own page arrays, addressed by the same block table).
        `kv_dtype` selects the pool storage precision (None = model
        default); layers in `kv_guard_layers` (GLOBAL indices) stay at
        model precision regardless (quality guard)."""
        guard = tuple(kv_guard_layers)
        return self._alloc(
            ("paged", n_blocks, block_size, n_slots, kv_dtype, guard),
            lambda: [M.init_layer_paged_cache(
                self.cfg, i, n_blocks, block_size, n_slots,
                kv_dtype=kv_dtype, kv_guard_layers=guard)
                for i in range(self.lo, self.hi)])


def slot_mode_supported(cfg) -> bool:
    """Slot-based continuous batching drives uniform text decoders; SWA
    ring caches need uniform positions and encoder-decoder/VLM prompts
    carry per-request modality state."""
    return not (cfg.swa_window or cfg.is_encoder_decoder
                or cfg.num_image_tokens)


def context_mode_supported(cfg) -> bool:
    """Prefix caching and chunked prefill run prompts through the paged
    CONTEXT path, which needs every sublayer to be attention: a recurrent
    sublayer's state is a running summary of everything before it — there
    is no per-block piece to alias (prefix sharing) or resume from
    (chunked prefill). Hybrid stacks keep one-shot prefill."""
    return slot_mode_supported(cfg) and all(
        cfg.layer_kind(i) == ATTN for i in range(cfg.num_layers))


class AsymmetricPipeline:
    """A full model replica as a chain of StageExecutors. ``params=None``
    builds every stage's share from ``key`` on that stage's devices."""

    def __init__(self, cfg: ModelConfig, params, stage_layers: Sequence[int],
                 stage_devices: Sequence[Sequence[jax.Device]], *, key=None):
        assert sum(stage_layers) == cfg.num_layers
        self.cfg = cfg
        self.stages: List[StageExecutor] = []
        lo = 0
        for si, (nl, devs) in enumerate(zip(stage_layers, stage_devices)):
            self.stages.append(StageExecutor(
                cfg, params, lo, lo + nl, devs,
                is_first=(si == 0), is_last=(si == len(stage_layers) - 1),
                key=key))
            lo += nl
        self.caches = None
        self._pos = 0
        self._kv_start = None
        # slot-mode state (init_slot_caches): per-stage cache pools
        self.slot_caches = None
        self.n_slots = 0
        self.slot_len = 0
        # paged slot-mode state (init_paged_caches): per-stage page pools
        self.paged_caches = None
        self.block_size = 0
        self.stage_blocks: List[int] = []
        self.kv_dtype: Optional[str] = None
        self.kv_guard_layers: tuple = ()
        # device programs the last decode_slots_paged call dispatched
        self.decode_programs = 0
        # HexTrace: the engine driving this pipeline shares its tracer; the
        # model-step spans (embed, stage, head, to_host) ride it
        self.tracer = NULL_TRACER

    def _step(self, name: str, **args):
        tr = self.tracer
        return tr.step(name, **args) if tr.enabled else NULL_STEP

    # ---- embedding / head on first / last stage ---------------------------
    def _embed(self, tokens, batch_extras):
        s0 = self.stages[0]
        hp = s0.head_params
        x = hp["embed"][tokens]
        if self.cfg.family == "vlm":
            x = x * jnp.asarray(np.sqrt(self.cfg.d_model), x.dtype)
        if self.cfg.num_image_tokens:
            x = jnp.concatenate(
                [batch_extras["image_embeds"].astype(x.dtype), x], axis=1)
        return x

    def _head(self, x):
        return M._head(self.cfg, self.stages[-1].head_params, x)

    # ---- public API --------------------------------------------------------
    def prefill(self, tokens: np.ndarray, *, kv_start=None, max_new: int = 32,
                batch_extras=None):
        """tokens (b, s) left-padded; returns last-position logits (b, V)."""
        cfg = self.cfg
        b, s = tokens.shape
        total = s + cfg.num_image_tokens
        self.caches = [st.make_caches(b, total + max_new)
                       for st in self.stages]
        self._kv_start = None if kv_start is None else jnp.asarray(kv_start)
        batch_extras = batch_extras or {}

        enc_out = None
        if cfg.is_encoder_decoder:
            hp = self.stages[0].head_params
            enc_out = M._encoder_forward(cfg, hp, batch_extras["enc_frames"])

        x = self._embed(jnp.asarray(tokens), batch_extras)
        positions = jnp.arange(total)[None].repeat(b, 0)
        if cfg.is_encoder_decoder and cfg.rope_theta == 0.0:
            x = x + layers.sinusoidal_positions(positions, cfg.d_model
                                                ).astype(x.dtype)
        valid = None
        if self._kv_start is not None:
            valid = (jnp.arange(total)[None, :]
                     >= self._kv_start[:, None]).astype(jnp.int32)

        for si, st in enumerate(self.stages):
            with st.mesh:
                x = jax.device_put(x, _rep(st.mesh))
                eo = None
                if enc_out is not None:
                    eo = jax.device_put(enc_out, _rep(st.mesh))
                x, self.caches[si] = st._prefill_jit(
                    x, self.caches[si], positions, self._kv_start, valid, eo)
        self._pos = total
        return np.asarray(self._head(x[:, -1:, :])[:, 0])

    def _embed_decode_tokens(self, tokens, positions):
        """Single-token decode embedding (b,1,d), op by op: the contiguous
        ``decode_step`` and ``decode_slots`` run it. The paged decode call
        gathers inside the first stage's ``decode_paged_call`` program
        instead (``_decode_embed``)."""
        return _decode_embed(self.cfg, self.stages[0].head_params, tokens,
                             positions)

    def decode_step(self, tokens: np.ndarray):
        """tokens (b,) -> next-position logits (b, V)."""
        tokens = jnp.asarray(tokens)
        x = self._embed_decode_tokens(
            tokens, jnp.full((tokens.shape[0],), self._pos))
        pos = jnp.int32(self._pos)       # traced: no retrace per step
        for si, st in enumerate(self.stages):
            with st.mesh:
                x = jax.device_put(x, _rep(st.mesh))
                x, self.caches[si] = st._decode_jit(
                    x, self.caches[si], pos, self._kv_start, None)
        self._pos += 1
        return np.asarray(self._head(x)[:, 0])

    def generate(self, tokens: np.ndarray, *, max_new: int, kv_start=None,
                 batch_extras=None, greedy: bool = True):
        """Returns (b, max_new) generated ids."""
        logits = self.prefill(tokens, kv_start=kv_start, max_new=max_new,
                              batch_extras=batch_extras)
        out = []
        for _ in range(max_new):
            nxt = logits.argmax(-1).astype(np.int32)
            out.append(nxt)
            logits = self.decode_step(nxt)
        return np.stack(out, axis=1)

    # ---- slot mode (continuous batching) -----------------------------------
    # Each stage owns a pre-allocated cache POOL whose batch rows are decode
    # slots (allocated lazily on first insert). Arriving requests are prefilled jointly (right-padded, per-row
    # lengths) through the stage chain on scratch caches and their rows
    # scattered into free pool slots; decode iterations carry per-slot
    # positions so slots at different depths share one jitted step.

    def init_slot_caches(self, n_slots: int, max_len: int) -> None:
        assert slot_mode_supported(self.cfg), \
            "slot mode needs uniform text decode (SWA ring cache / " \
            "encoder-decoder / VLM); use static batching"
        self.n_slots = n_slots
        self.slot_len = max_len
        self.slot_caches = [st.make_caches(n_slots, max_len)
                            for st in self.stages]

    def insert_slots(self, tokens: np.ndarray, lens: np.ndarray,
                     slot_ids: Sequence[int]) -> np.ndarray:
        """Joint prefill of right-padded prompts `tokens` (m, P) with real
        lengths `lens` (m,), scattering each row's caches into pool slot
        `slot_ids[i]`. Returns each row's last-real-token logits (m, V).

        Right padding keeps every row's token positions identical to
        isolated generation (bit-identity), and leaves recurrent-state
        caches holding exactly the post-prompt state; trailing garbage in
        attention K/V beyond lens[i] is masked by kv_len during decode and
        progressively overwritten as the slot decodes.
        """
        assert self.slot_caches is not None, "call init_slot_caches first"
        m = len(slot_ids)          # rows beyond m are compile-shape padding
        b, P = tokens.shape
        lens = jnp.asarray(lens, jnp.int32)
        x = self._embed(jnp.asarray(tokens), {})
        positions = jnp.arange(P)[None].repeat(b, 0)
        valid = (jnp.arange(P)[None, :] < lens[:, None]).astype(jnp.int32)
        for si, st in enumerate(self.stages):
            with st.mesh:
                x = jax.device_put(x, _rep(st.mesh))
                scratch = st.make_caches(b, self.slot_len)
                x, rows = st._prefill_jit(x, scratch, positions, None,
                                          valid, None, lens)
                self.slot_caches[si] = [
                    M.scatter_cache_rows(pool,
                                         jax.tree.map(lambda r: r[:m], row),
                                         slot_ids)
                    for pool, row in zip(self.slot_caches[si], rows)]
        x_last = x[jnp.arange(m), lens[:m] - 1][:, None]
        return np.asarray(self._head(x_last)[:, 0])

    def decode_slots(self, tokens: np.ndarray,
                     positions: np.ndarray) -> np.ndarray:
        """One decode iteration over ALL slots. tokens (n_slots,) next input
        token per slot; positions (n_slots,) its absolute position. Free
        slots decode garbage that is simply discarded. Returns (n_slots, V).
        """
        pos = jnp.asarray(positions, jnp.int32)
        x = self._embed_decode_tokens(jnp.asarray(tokens), pos)
        for si, st in enumerate(self.stages):
            with st.mesh:
                x = jax.device_put(x, _rep(st.mesh))
                x, self.slot_caches[si] = st._decode_jit(
                    x, self.slot_caches[si], pos, None, None)
        return np.asarray(self._head(x)[:, 0])

    # ---- paged slot mode ---------------------------------------------------
    # Same joint-iteration contract as slot mode, but each stage owns a
    # BLOCK pool sized independently (∝ its devices' memory — the
    # asymmetric-capacity point) instead of n_slots pre-cut max_len rows.
    # Block allocation/preemption policy lives in the engine
    # (serving.continuous.PagedPipelineBatcher + serving.block_manager);
    # the pipeline only moves tensors.

    def init_paged_caches(self, n_slots: int, max_len: int, *,
                          block_size: int = 16,
                          stage_blocks: Optional[Sequence[int]] = None,
                          kv_dtype: Optional[str] = None,
                          kv_guard_layers: Sequence[int] = ()
                          ) -> None:
        """Per-stage page pools. `stage_blocks[si]` is stage si's pool size
        in blocks (including the reserved null block); None sizes every
        stage for full occupancy (n_slots * max_len tokens), which makes
        paged serving a drop-in replacement with zero preemptions.
        `kv_dtype` in {"fp32","bf16","int8","fp8"} selects pool precision
        (None = model default dtype, pre-quantization layout);
        `kv_guard_layers` pins those GLOBAL layer indices at model
        precision even under a quantized kv_dtype."""
        assert slot_mode_supported(self.cfg), \
            "paged slot mode needs uniform text decode (SWA ring cache / " \
            "encoder-decoder / VLM); use static batching"
        assert max_len % block_size == 0, (max_len, block_size)
        self.n_slots = n_slots
        self.slot_len = max_len
        self.block_size = block_size
        self.kv_dtype = kv_dtype
        self.kv_guard_layers = tuple(kv_guard_layers)
        full = n_slots * (max_len // block_size) + 1
        if stage_blocks is None:
            stage_blocks = [full] * len(self.stages)
        self.stage_blocks = list(stage_blocks)
        assert len(self.stage_blocks) == len(self.stages)
        self.paged_caches = [
            st.make_paged_caches(nb, block_size, n_slots,
                                 kv_dtype=kv_dtype,
                                 kv_guard_layers=self.kv_guard_layers)
            for st, nb in zip(self.stages, self.stage_blocks)]

    def insert_slots_paged(self, tokens: np.ndarray, lens: np.ndarray,
                           slot_ids: Sequence[int],
                           stage_dest: Sequence[np.ndarray]) -> np.ndarray:
        """Joint right-padded prefill (same compile shapes and math as
        ``insert_slots``) whose attention rows scatter into stage si's pages
        at ``stage_dest[si]`` ((m * max_blocks,) physical page per logical
        block, row-major; null-page entries absorb the padding) and whose
        recurrent rows scatter by slot id. Returns last-real-token logits
        (m, V)."""
        assert self.paged_caches is not None, "call init_paged_caches first"
        m = len(slot_ids)          # rows beyond m are compile-shape padding
        b, P = tokens.shape
        with self._step("embed"):
            lens = jnp.asarray(lens, jnp.int32)
            x = self._embed(jnp.asarray(tokens), {})
            positions = jnp.arange(P)[None].repeat(b, 0)
            valid = (jnp.arange(P)[None, :]
                     < lens[:, None]).astype(jnp.int32)
        for si, st in enumerate(self.stages):
            with self._step("stage", tid=si, stage=si), st.mesh:
                x = jax.device_put(x, _rep(st.mesh))
                scratch = st.make_caches(b, self.slot_len)
                x, rows = st._prefill_jit(x, scratch, positions, None,
                                          valid, None, lens)
                self.paged_caches[si] = st._scatter_rows_paged_jit(
                    self.paged_caches[si], rows,
                    jnp.asarray(slot_ids, jnp.int32),
                    jnp.asarray(stage_dest[si], jnp.int32))
        with self._step("head"):
            x_last = x[jnp.arange(m), lens[:m] - 1][:, None]
            out = self._head(x_last)[:, 0]
        with self._step("to_host"):
            return np.asarray(out)

    def context_slots_paged(self, tokens: np.ndarray, lens: np.ndarray,
                            q_start: np.ndarray,
                            stage_tables: Sequence[np.ndarray]) -> np.ndarray:
        """CONTEXT prefill of right-padded chunks `tokens` (m, C) whose
        row-i token j sits at ABSOLUTE position q_start[i] + j — the
        insert-with-nonzero-KV-start path behind warm-prefix serving (only
        a prompt's cold suffix runs here, the shared prefix is already
        resident in pages) and chunked prefill (a long prompt arrives as
        several such calls). Each chunk's K/V scatter into this stage's
        pages through `stage_tables[si]` (m, max_blocks) inside the
        attention layer, and attention reads the prior context back
        through the same table. Returns each row's last-real-token logits
        (m, V) — meaningful once the final chunk of a prompt runs.

        Attention-only stacks (context_mode_supported); q_start == 0 and
        lens == the whole prompt reduces to a one-shot paged prefill of a
        cold request through the context path."""
        assert self.paged_caches is not None, "call init_paged_caches first"
        assert context_mode_supported(self.cfg)
        m, C = tokens.shape
        with self._step("embed"):
            lens = jnp.asarray(lens, jnp.int32)
            starts = jnp.asarray(q_start, jnp.int32)
            positions = starts[:, None] + jnp.arange(C)[None]
            x = self._embed(jnp.asarray(tokens), {})
        for si, st in enumerate(self.stages):
            with self._step("stage", tid=si, stage=si), st.mesh:
                x = jax.device_put(x, _rep(st.mesh))
                bt = jnp.asarray(stage_tables[si], jnp.int32)
                x, self.paged_caches[si] = st._context_paged_jit(
                    x, self.paged_caches[si], positions, lens, bt)
        with self._step("head"):
            x_last = x[jnp.arange(m), lens - 1][:, None]
            out = self._head(x_last)[:, 0]
        with self._step("to_host"):
            return np.asarray(out)

    def verify_slots_paged(self, tokens: np.ndarray, q_len: np.ndarray,
                           q_start: np.ndarray,
                           stage_tables: Sequence[np.ndarray]) -> np.ndarray:
        """MULTI-TOKEN VERIFICATION over ALL slots (speculative decoding):
        tokens (n_slots, T) is each slot's candidate chunk — the bonus
        token plus its draft proposals, right-padded to the fixed chunk
        width T = spec_k + 1 so the step compiles ONCE — with row i's
        candidate j at absolute position q_start[i] + j (the slot's
        committed KV length). q_len (n_slots,) real candidate counts;
        rows of free / mid-prefill slots carry q_len == 0 and all-null
        tables, scatter into the trash page, and return garbage the
        engine discards — exactly like free slots in the joint decode.

        Returns logits (n_slots, T, V) at EVERY chunk position: position
        j is the target's next-token distribution after consuming
        candidate j, which is what greedy (or rejection-sampling)
        acceptance compares against candidate j + 1. With T == 1 this
        degenerates to the plain joint decode step (one bonus token, no
        proposals). Attention-only stacks (context_mode_supported)."""
        assert self.paged_caches is not None, "call init_paged_caches first"
        assert context_mode_supported(self.cfg)
        n, T = tokens.shape
        lens = jnp.asarray(q_len, jnp.int32)
        starts = jnp.asarray(q_start, jnp.int32)
        positions = starts[:, None] + jnp.arange(T)[None]
        x = self._embed(jnp.asarray(tokens), {})
        for si, st in enumerate(self.stages):
            with st.mesh:
                x = jax.device_put(x, _rep(st.mesh))
                bt = jnp.asarray(stage_tables[si], jnp.int32)
                x, self.paged_caches[si] = st._verify_paged_jit(
                    x, self.paged_caches[si], positions, lens, bt)
        return np.asarray(self._head(x))

    # ---- KV migration (disaggregated prefill/decode) -----------------------
    # The wire format is per-GLOBAL-LAYER so the source and destination
    # pipelines may split their stages differently: stage si's single block
    # table addresses every one of ITS layers' page pools, but each layer
    # owns its own K/V arrays, so regrouping layers across stages is just a
    # different iteration order over the same per-layer payloads.

    def extract_kv_pages(self, stage_blocks: Sequence[Optional[Sequence[int]]]
                         ) -> List[dict]:
        """Gather the page CONTENTS of each stage's block list into host
        arrays: returns ``layer_kv[l] = {"k","v"}`` of shape
        (n_blocks, block_size, kv_heads, head_dim) for every global layer l,
        in layer order. ``stage_blocks[si]`` is the (ordered) physical block
        list of one request on stage si — whole blocks, so a partial tail
        block ships its masked garbage rather than a ragged slice.
        Attention-only stacks (recurrent state has no page identity)."""
        assert self.paged_caches is not None, "no paged caches to extract"
        layer_kv: List[dict] = []
        for si, st in enumerate(self.stages):
            blocks = np.asarray(stage_blocks[si], np.int32)
            for c in self.paged_caches[si]:
                assert "k" in c and "v" in c, \
                    "KV migration covers attention-only stacks"
                lkv = {"k": np.asarray(c["k"][blocks]),
                       "v": np.asarray(c["v"][blocks])}
                # quantized pools ship at wire width + their scale leaves:
                # the int8/fp8 payload is what crosses the link, so the
                # modeled transfer bytes drop with the pool dtype
                for n in ("k_scale", "v_scale"):
                    if n in c:
                        lkv[n] = np.asarray(c[n][blocks])
                layer_kv.append(lkv)
        return layer_kv

    def scatter_kv_pages(self, stage_blocks: Sequence[Optional[Sequence[int]]],
                         layer_kv: Sequence[dict]) -> None:
        """Migrate-in: write per-layer page payloads (extract_kv_pages wire
        format, possibly from a pipeline with a DIFFERENT stage split) into
        this pipeline's pools at each stage's freshly allocated block list.
        A ``None`` entry in ``stage_blocks`` SKIPS that stage (its layers'
        payload slices are discarded) — a cluster prefix fetch lands only
        in the stages that miss locally. Jitted with donation per stage so
        the pools update in place; one compile per distinct payload block
        count."""
        assert self.paged_caches is not None, "call init_paged_caches first"
        li = 0
        for si, st in enumerate(self.stages):
            n_layers = st.hi - st.lo
            if stage_blocks[si] is None:
                li += n_layers
                continue
            payload = [
                {n: jnp.asarray(a) for n, a in layer_kv[li + k].items()}
                for k in range(n_layers)]
            li += n_layers
            with st.mesh:
                self.paged_caches[si] = st._scatter_pages_jit(
                    self.paged_caches[si],
                    jnp.asarray(stage_blocks[si], jnp.int32), payload)
        assert li == len(layer_kv), (li, len(layer_kv))

    # ---- host page tier (device <-> host demotion/promotion) ---------------
    def extract_stage_pages(self, stage_idx: int, blocks: Sequence[int]
                            ) -> List[dict]:
        """Gather stage `stage_idx`'s page contents for `blocks` into host
        arrays — one ``{"k","v"[,"k_scale","v_scale"]}`` pytree per layer
        OF THIS STAGE, at pool precision (quantized pages spill narrow).
        The single-stage slice of ``extract_kv_pages``: host-tier demotion
        is per stage because each stage's pool fills and evicts on its own
        clock."""
        assert self.paged_caches is not None, "no paged caches to extract"
        bl = np.asarray(blocks, np.int32)
        payload: List[dict] = []
        for c in self.paged_caches[stage_idx]:
            assert "k" in c and "v" in c, \
                "host page tier covers attention-only stacks"
            lkv = {"k": np.asarray(c["k"][bl]), "v": np.asarray(c["v"][bl])}
            for n in ("k_scale", "v_scale"):
                if n in c:
                    lkv[n] = np.asarray(c[n][bl])
            payload.append(lkv)
        return payload

    def scatter_stage_pages(self, stage_idx: int, blocks: Sequence[int],
                            payload: Sequence[dict]) -> None:
        """Write ``extract_stage_pages`` payloads back into stage
        `stage_idx`'s pools at `blocks` — host -> device promotion. The
        payload re-lands verbatim (same pool precision it spilled at)."""
        assert self.paged_caches is not None, "call init_paged_caches first"
        st = self.stages[stage_idx]
        jp = [{n: jnp.asarray(a) for n, a in lkv.items()} for lkv in payload]
        with st.mesh:
            self.paged_caches[stage_idx] = st._scatter_pages_jit(
                self.paged_caches[stage_idx],
                jnp.asarray(blocks, jnp.int32), jp)

    def copy_pages(self, stage_idx: int, src_blocks: Sequence[int],
                   dst_blocks: Sequence[int]) -> None:
        """Copy-on-write: duplicate page contents src -> dst in every
        attention layer of stage `stage_idx` (one shared block-id space
        per stage). Host-side bookkeeping (BlockTable.writable) decides
        WHEN; this only moves bytes — donated/jitted per stage, so the
        pools update in place."""
        if not src_blocks:
            return
        st = self.stages[stage_idx]
        with st.mesh:
            self.paged_caches[stage_idx] = st._copy_pages_jit(
                self.paged_caches[stage_idx],
                jnp.asarray(src_blocks, jnp.int32),
                jnp.asarray(dst_blocks, jnp.int32))

    def decode_slots_paged(self, tokens: np.ndarray, positions: np.ndarray,
                           stage_tables: Sequence[np.ndarray]) -> np.ndarray:
        """One decode iteration over ALL slots through the paged caches.
        stage_tables[si]: (n_slots, max_blocks) int32 block table for stage
        si (rows of free slots are all-null and decode into the trash
        page). Returns host logits (n_slots, V) in the head's dtype.

        Each stage runs ONE program (``StageExecutor.decode_paged_call``):
        the first gathers the tokens' embeddings inside it and the last
        ends with the final norm and head, so nothing runs op by op around
        them; the tokens, positions and tables go into the call as host
        arrays. ``decode_programs`` counts the programs the call
        dispatched. The insert, context and verify calls and the
        contiguous decode still embed and apply the head op by op
        (``_embed``, ``_embed_decode_tokens``, ``_head``)."""
        x = np.asarray(tokens, np.int32)
        pos = np.asarray(positions, np.int32)
        self.decode_programs = 0
        for si, st in enumerate(self.stages):
            with self._step("stage", tid=si, stage=si), st.mesh:
                if si:
                    x = jax.device_put(x, _rep(st.mesh))
                x, self.paged_caches[si] = st.decode_paged_call(
                    x, self.paged_caches[si], pos,
                    np.asarray(stage_tables[si], np.int32))
                self.decode_programs += 1
        with self._step("to_host"):
            return np.asarray(x)
