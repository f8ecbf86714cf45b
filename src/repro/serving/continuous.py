"""Continuous (iteration-level) batching — the paper's acknowledged
limitation (Appendix D), implemented here as a beyond-paper extension.

A replica owns a fixed pool of decode SLOTS over paged KV caches.
Requests admitted by the serve loop are buffered until the next iteration
boundary, then prefilled JOINTLY (one right-padded batch with per-row real
lengths) and their cache rows scattered into the pages of free slots;
every iteration decodes all slots jointly with PER-SLOT positions;
finished slots free immediately. Right padding keeps each row's token
positions identical to isolated generation and attention/MoE/SSM state is
row-independent, so a request's outputs are bit-identical to isolated
generation (tested).

``SlotEngine`` is the slot core (slot state, admission, the joint
insert/decode iteration); ``PagedPipelineBatcher`` runs it over an
``AsymmetricPipeline`` replica with per-stage page pools. Works for
full-KV and recurrent-state architectures; SWA ring caches require
uniform positions and fall back to static batching (noted).

The engine implements the replica port of ``serving.loop`` — scheduling,
clocking and accounting live there, not here.
"""
from __future__ import annotations

import dataclasses
import heapq
import warnings
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.models import model as M
from repro.obs.trace import NULL_STEP, NULL_TRACER
from repro.serving.block_manager import (BlockPool, BlockTable, HostPagePool,
                                         PrefixIndex, blocks_for_tokens,
                                         chunk_hashes)
from repro.serving.disagg import KVLink, KVMigration
from repro.serving.loop import (ServeStats, VirtualClock, WallClock,
                                run_serve_loop)
from repro.serving.request import Request
from repro.serving.spec import SpecConfig, greedy_accept


@dataclasses.dataclass
class _Slot:
    req: Optional[Request] = None
    pos: int = 0               # next write position (tokens cached so far)
    remaining: int = 0
    out: Optional[list] = None
    seq: int = 0               # admission order (paged preemption victims)
    # incremental-prefill state (prefix caching / chunked prefill): tokens
    # of the prompt not yet prefilled; None once decode can start
    pending: Optional[np.ndarray] = None
    hashes: Optional[list] = None   # full-block chunk hashes of the prompt
    matched: bool = False           # prefix lookup ran (lazily, first chunk)

    @property
    def free(self) -> bool:
        return self.req is None

    @property
    def decoding(self) -> bool:
        """Occupied and past prefill: participates in decode iterations."""
        return self.req is not None and self.pending is None


class SlotEngine:
    """The slot core: slot bookkeeping, admission and the joint
    insert/decode iteration. Subclasses provide:

      _prefill_insert(toks (b,P), lens (b,), slot_ids, rids) -> logits (m, V)
          where m = len(slot_ids) <= b; rows beyond m are compile-shape
          padding to be dropped before the cache scatter; rids are the
          rows' request ids when tracing is on (else empty)
      _decode_all(toks (n_slots,), pos (n_slots,))     -> logits (n_slots, V)
    """

    def __init__(self, *, n_slots: int, max_len: int, vocab_size: int,
                 pad_id: int = 0, virtual_step_cost: float = 1.0):
        self.n_slots = n_slots
        self.max_len = max_len
        self.pad_id = pad_id
        self.virtual_step_cost = virtual_step_cost
        # HexTrace: the Router (or a test) swaps in a live Tracer; the
        # null default keeps every emission site a single attribute check
        self.tracer = NULL_TRACER
        # the serve loop's clock (bind_clock): request stamps are read on
        # it once the call that produced them has returned
        self.clock = None
        self.replica_id = 0
        self.slots = [_Slot() for _ in range(n_slots)]
        self._queue: Deque[Request] = deque()
        self._last_logits = np.zeros((n_slots, vocab_size), np.float32)
        self.rejected = 0          # oversized requests turned away
        self.preemptions = 0       # paged: slots recomputed after eviction
        self.recomputed_tokens = 0  # paged: cached tokens evictions threw away
        self._admit_seq = 0

    @property
    def tracer(self):
        return self._tracer

    @tracer.setter
    def tracer(self, tracer) -> None:
        # the pipeline's model-step spans ride the engine's tracer
        self._tracer = tracer
        pipe = getattr(self, "pipeline", None)
        if pipe is not None:
            pipe.tracer = tracer

    def bind_clock(self, clock) -> None:
        """The serve loop's clock (``run_serve_loop`` binds it)."""
        self.clock = clock

    def _now(self, now: float) -> float:
        """The serve loop's clock now; ``now`` (the iteration's start,
        which a virtual clock holds all iteration) without a loop."""
        return self.clock.now() if self.clock is not None else now

    # ---- slot state ------------------------------------------------------
    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s.free]

    @property
    def active(self) -> bool:
        return any(not s.free for s in self.slots)

    # ---- replica port (serving.loop) -------------------------------------
    def capacity(self, now: float) -> int:
        return max(len(self.free_slots()) - len(self._queue), 0)

    def load(self, now: float) -> float:
        return (self.n_slots - len(self.free_slots())) + len(self._queue)

    def admit(self, reqs: Sequence[Request], now: float) -> None:
        self._queue.extend(reqs)

    def busy(self, now: float) -> bool:
        return bool(self._queue) or self.active

    def inflight(self) -> int:
        return len(self._queue) + (self.n_slots - len(self.free_slots()))

    def next_event(self, now: float):
        return None                # compute worker: work runs when busy

    def run_iteration(self, now: float):
        """Insert buffered admissions, then one joint decode iteration."""
        comps = []
        free = self.free_slots()
        if self._queue and free:
            batch = []
            tr = self.tracer
            admit = (tr.step("admit", pid=self.replica_id) if tr.enabled
                     else NULL_STEP)
            with admit:
                self._admit_batch(batch, len(free), comps)
                if tr.enabled:
                    admit.set(rids=[r.rid for r in batch])
            if batch:
                self._insert_batch(batch, free[:len(batch)], now)
        # nothing active (e.g. a rejection-only cycle): no decode to run —
        # and possibly no caches allocated yet to run it on
        done = self._step(now) if self.active else []
        comps.extend((req, np.asarray(out, np.int32), None)
                     for req, out in done)
        return comps, self.virtual_step_cost

    def _admit_batch(self, batch: List[Request], n_free: int,
                     comps: List) -> None:
        """Move queued requests into ``batch`` (at most ``n_free``), FIFO;
        a request that can never fit is rejected into ``comps``."""
        while self._queue and len(batch) < n_free:
            r = self._queue[0]
            # a request must fit prompt + all its decode steps on this
            # engine (slot length, and for the paged engine the whole
            # block pool); reject it alone (empty output, counted in
            # ServeStats.rejected) instead of crashing the serve loop
            # and losing every in-flight request
            if not self._fits(r):
                self._queue.popleft()
                self.rejected += 1
                warnings.warn(
                    f"request {r.rid}: prompt {len(r.prompt)} + "
                    f"max_new {r.max_new_tokens} cannot fit this "
                    "engine; rejected with empty output")
                comps.append((r, np.zeros(0, np.int32), None))
                continue
            # admissible later but not right now (paged: not enough
            # free blocks yet) — keep it queued, FIFO order intact
            if not self._can_admit(r, batch):
                break
            self._queue.popleft()
            batch.append(r)

    # ---- admission / paging hooks (overridden by the paged engine) --------
    def _fits(self, r: Request) -> bool:
        return len(r.prompt) + r.max_new_tokens <= self.max_len - 1

    def _can_admit(self, r: Request, batch: Sequence[Request]) -> bool:
        return True

    def _step(self, now: float):
        """One compute step once admissions are placed. The paged engine
        overrides this to interleave prefill chunks with the decode."""
        return self._decode_iteration(now)

    def _before_decode(self) -> None:
        pass                       # paged: allocate-on-decode / preemption

    def _decode_tables(self) -> None:
        pass                       # paged: the decode call's block tables

    def _on_slot_free(self, i: int) -> None:
        pass                       # paged: release the slot's block tables

    def _decode_call_args(self) -> dict:
        return {}                  # paged: the programs the call dispatched

    # ---- engine internals ------------------------------------------------
    def _insert_batch(self, reqs: Sequence[Request],
                      slot_ids: Sequence[int], now: float = 0.0) -> None:
        m = len(reqs)
        lens = np.asarray([len(r.prompt) for r in reqs], np.int32)
        assert int(lens.max()) < self.max_len, "prompt exceeds slot length"
        # bucket BOTH jit shape axes — padded width to multiples of 16,
        # insert count to the next power of two (capped at n_slots) — so a
        # bursty serve window compiles O(log) prefill shapes instead of one
        # per distinct (m, P) pair. Pad rows (and right pads) are masked in
        # the model and dropped by _prefill_insert before the scatter.
        P = min(-(-int(lens.max()) // 16) * 16, self.max_len - 1)
        m_pad = min(1 << (m - 1).bit_length(), self.n_slots)
        toks = np.full((m_pad, P), self.pad_id, np.int32)
        plens = np.ones((m_pad,), np.int32)
        plens[:m] = lens
        for i, r in enumerate(reqs):
            toks[i, :lens[i]] = r.prompt                   # right pad
        tr = self.tracer
        if tr.enabled:
            # one-shot joint prefill: every admitted prompt completes its
            # prefill within this iteration
            ntok = int(lens.sum())
            rids = [r.rid for r in reqs]
            prefill = tr.step(
                "prefill", virtual=self.virtual_step_cost
                * getattr(self, "prefill_token_cost", 0.0) * ntok,
                pid=self.replica_id, tokens=ntok, slots=m, rids=rids)
        else:
            rids, prefill = (), NULL_STEP
        with prefill:
            logits = self._prefill_insert(toks, plens, list(slot_ids), rids)
        t = self._now(now)
        for r in reqs:
            # first-wins: a preempt-recompute prefills the prompt again
            if r.prefill_finish_time is None:
                r.prefill_finish_time = t
        for i, (r, slot) in enumerate(zip(reqs, slot_ids)):
            self._last_logits[slot] = np.asarray(logits[i])
            self.slots[slot] = _Slot(req=r, pos=int(lens[i]),
                                     remaining=r.max_new_tokens, out=[],
                                     seq=self._admit_seq)
            self._admit_seq += 1

    def _decode_iteration(self, now: float = 0.0):
        tr = self.tracer
        pid = self.replica_id
        with (tr.step("schedule", pid=pid) if tr.enabled else NULL_STEP):
            self._before_decode()  # paged: grow tables, maybe preempt
            self._decode_tables()
        with (tr.step("sample", pid=pid) if tr.enabled else NULL_STEP):
            toks = np.zeros((self.n_slots,), np.int32)
            pos = np.zeros((self.n_slots,), np.int32)
            live = []
            for i, s in enumerate(self.slots):
                if s.decoding:     # mid-prefill slots sit this one out
                    toks[i] = int(self._last_logits[i].argmax())
                    pos[i] = s.pos
                    live.append(i)
        # one joint decode step; its virtual cost is the flat iteration
        # cost whatever the batch width
        with (tr.step("decode", virtual=self.virtual_step_cost, pid=pid,
                      rows=len(live),
                      ctx_tokens=int(sum(pos[i] + 1 for i in live)),
                      rids=[self.slots[i].req.rid for i in live])
              if tr.enabled else NULL_STEP) as call:
            logits = self._decode_all(toks, pos)
            if tr.enabled:
                call.set(**self._decode_call_args())
        with (tr.step("sample", pid=pid) if tr.enabled else NULL_STEP):
            return self._append_tokens(live, toks, logits, now)

    def _append_tokens(self, live, toks, logits, now: float):
        """Append each decoding slot's token, stamp first tokens on the
        loop's clock, stop finished slots; returns the finished
        (request, tokens) pairs."""
        done = []
        t = None
        for i in live:
            s = self.slots[i]
            s.out.append(int(toks[i]))
            if len(s.out) == 1 and s.req is not None:
                # first-wins: a preempt-recompute re-produces the token
                # stream, but the client saw the first token at the
                # ORIGINAL emission
                if s.req.first_token_time is None:
                    if t is None:
                        t = self._now(now)
                    s.req.first_token_time = t
            s.pos += 1
            s.remaining -= 1
            self._last_logits[i] = logits[i]
            if s.remaining <= 0 or s.pos >= self.max_len - 1:
                done.append((s.req, s.out))
                self._on_slot_free(i)
                self.slots[i] = _Slot()
        return done

    def _prefill_insert(self, toks, lens, slot_ids, rids=()):
        raise NotImplementedError

    def _decode_all(self, toks, pos):
        raise NotImplementedError

    # ---- single-replica convenience (shared loop underneath) --------------
    def serve(self, requests: Sequence[Request], *, deadline: float,
              realtime: bool = False) -> ServeStats:
        """Replays a workload on this replica alone. realtime=False uses the
        virtual clock: deterministic latencies in iteration units."""
        clock = WallClock() if realtime else VirtualClock()
        return run_serve_loop([self], requests, deadline=deadline,
                              clock=clock,
                              tracer=(self.tracer if self.tracer.enabled
                                      else None))

    # seed-API shims (tests, notebooks) ------------------------------------
    def insert(self, req: Request) -> int:
        """Immediate single insert; returns the slot index."""
        free = self.free_slots()
        assert free, "no free slot"
        self._insert_batch([req], free[:1])
        return free[0]

    def step(self) -> Dict[int, List[int]]:
        """One engine step (prefill chunks where pending, then the joint
        decode — identical to what run_iteration drives). Returns
        {rid: finished tokens}."""
        return {req.rid: out for req, out in self._step(0.0)}


class PagedPipelineBatcher(SlotEngine):
    """Slot-based continuous batching over an ``AsymmetricPipeline`` with a
    PAGED KV cache: each stage owns a block pool sized independently
    (``stage_blocks``, ∝ its devices' memory — the asymmetric-capacity
    point), requests hold per-stage BlockTables, admission requires enough
    free blocks for the prompt plus headroom rather than a worst-case
    ``max_len`` row, decode grows tables one block at a time, and a dry
    pool preempts the youngest slot by recompute (blocks freed, request
    requeued at the front; greedy decode regenerates the same tokens).

    ``max_len`` remains the per-request ceiling (block tables hold
    max_len / block_size entries); what paging removes is the RESERVATION:
    a slot only ever occupies the blocks its tokens actually fill, so a
    pool sized for actual usage serves far more concurrent slots than
    reserving a max_len row per slot would (benchmarks/bench_paged.py).

    ``prefix_caching=True`` cashes in the refcounts: each stage keeps a
    ``PrefixIndex`` (hash of block-aligned prompt chunks -> resident
    block), admission aliases a new prompt's longest indexed prefix
    (fork-style incref) and prefills only the COLD SUFFIX through the
    paged context path (pipeline.context_slots_paged); a write landing in
    a still-shared block copies it first (BlockTable.writable +
    pipeline.copy_pages). Cached blocks outlive their request — one index
    reference each — and are evicted LRU-first when a pool runs dry.

    ``prefill_chunk=N`` splits any prefill longer than N tokens into
    N-token chunks run one per iteration, so a giant prompt no longer
    stalls every in-flight decode for its whole prefill (iteration-level
    fairness). Both switches need an attention-only stack
    (pipeline.context_mode_supported): recurrent state is a running
    summary — nothing to alias per block, nothing to resume per chunk.

    ``prefill_token_cost`` (virtual clock only) charges each prefilled
    token that fraction of an iteration, so chunking and prefix hits show
    up in simulated TTFT/latency instead of hiding behind a flat
    per-iteration cost; 0.0 keeps the PR-2 flat-cost accounting.

    ``host_blocks > 0`` adds a HOST-MEMORY PAGE TIER (needs prefix
    caching): ``PrefixIndex`` eviction under pool pressure DEMOTES a
    prefix block's page payload into a per-stage ``HostPagePool`` (at
    pool precision — quantized pages spill narrow) instead of deleting
    it, and a later prompt that matches past the device-resident prefix
    PROMOTES pages back into fresh device blocks, block by block, so the
    shared-prefix working set survives a device pool too small to hold
    it. Preempt-by-recompute recovers through the same path: the victim's
    registered prefix demotes under the very pressure that evicted it and
    swaps back in at re-admission instead of re-prefilling.
    ``host_swap_cost`` (virtual clock) charges each block moved across
    the device<->host boundary that fraction of an iteration.

    ``attach_cluster`` (serving.cluster_kv.wire_cluster_prefix) joins a
    CLUSTER PREFIX DIRECTORY: the replica publishes its (hash -> tier)
    residency, and a prompt whose prefix lives only on a PEER replica
    fetches those pages over the KV link — the PR-4 ``KVMigration`` wire
    format (per-global-layer payloads) charged at ``KVLink.delay`` on the
    serving clock — before falling back to cold prefill. Token streams
    never depend on the directory: a stale entry just costs recompute.

    ``role`` splits the two inference phases across replicas (disaggregated
    serving, serving.disagg):

      * "both"    — colocated serving, the default: prefill and decode on
        this replica.
      * "prefill" — this replica only prefills. A slot whose prompt is
        fully cached is EXTRACTED (page payloads + cached token count +
        last logits) and handed to ``self.dispatcher`` instead of
        decoding; its blocks free immediately (index-registered prefix
        blocks stay resident for future prompts). The router never needs
        to know: completions simply arrive from the decode replica.
      * "decode"  — this replica admits no fresh arrivals
        (``capacity() == 0``); work arrives via ``migrate_in`` as
        in-transit migrations that land in free slots once their transfer
        delay elapses, resuming decode from the migrated pages and logits
        bit-identically to colocated serving. A preempted migrated slot
        falls back to local recompute (this is still a full replica).

    Disaggregation needs an attention-only stack: KV pages are the whole
    per-request state, so the handoff is a page transfer; recurrent
    running state has no page identity to ship.

    ``spec`` (a ``serving.spec.SpecConfig``) turns on SPECULATIVE
    DECODING: each decode iteration becomes a draft-then-verify step —
    a proposer (prompt-lookup n-grams, or a small draft model) guesses up
    to ``spec.k`` candidate tokens per slot, the target verifies the
    bonus token plus all candidates in ONE multi-token pipeline step
    (``pipeline.verify_slots_paged``), greedy acceptance commits the
    longest candidate prefix matching the target's argmax chain (1 to
    k + 1 tokens per step), and the speculative pages past the committed
    length roll back onto the pool (``BlockTable.truncate``). The
    committed stream is token-identical to plain greedy decode at any
    acceptance rate; only the step count changes. Needs an attention-only
    stack (the verification chunk cannot be rolled back through recurrent
    state); composes with prefix caching, chunked prefill, preemption and
    disaggregated decode replicas.
    """

    def __init__(self, pipeline, *, n_slots: int = 8, max_len: int = 256,
                 block_size: int = 16,
                 stage_blocks: Optional[Sequence[int]] = None,
                 admit_headroom: Optional[int] = None, pad_id: int = 0,
                 virtual_step_cost: float = 1.0,
                 prefix_caching: bool = False, prefill_chunk: int = 0,
                 prefill_token_cost: float = 0.0,
                 host_blocks: int = 0, host_swap_cost: float = 0.0,
                 role: str = "both", replica_id: int = 0,
                 spec: Optional[SpecConfig] = None,
                 kv_dtype: Optional[str] = None,
                 kv_guard_layers: Sequence[int] = (),
                 kvsan: bool = False):
        from repro.serving.pipeline import (context_mode_supported,
                                            slot_mode_supported)
        assert slot_mode_supported(pipeline.cfg), \
            "slot mode needs uniform text decode; use StaticBatcher"
        assert max_len % block_size == 0, (max_len, block_size)
        assert role in ("both", "prefill", "decode"), role
        if role != "both":
            assert context_mode_supported(pipeline.cfg), \
                "disaggregation needs an attention-only stack (recurrent " \
                "running state has no pages to migrate)"
        if ((prefix_caching or prefill_chunk)
                and not context_mode_supported(pipeline.cfg)):
            warnings.warn(
                f"{pipeline.cfg.name}: prefix caching / chunked prefill "
                "need an attention-only stack (recurrent state has no "
                "per-block identity); serving without them", stacklevel=2)
            prefix_caching, prefill_chunk = False, 0
        super().__init__(n_slots=n_slots, max_len=max_len,
                         vocab_size=pipeline.cfg.vocab_size, pad_id=pad_id,
                         virtual_step_cost=virtual_step_cost)
        self.pipeline = pipeline
        pipeline.tracer = self.tracer
        # devices of each stage: the model-step spans' `tp`
        self._stage_tp = [st.tp for st in pipeline.stages]
        self.block_size = block_size
        self.max_blocks = max_len // block_size
        # paged-pool storage precision ("fp32"/"bf16"/"int8"/"fp8"; None =
        # model default). Quantized pools need the paged CONTEXT/VERIFY
        # write paths, which exist for attention-only stacks only.
        from repro.models import quant as Q
        if kv_dtype is not None and Q.kv_is_quantized(kv_dtype) \
                and not context_mode_supported(pipeline.cfg):
            warnings.warn(
                f"{pipeline.cfg.name}: quantized KV pages need an "
                "attention-only stack (recurrent slot state has no paged "
                "rows to quantize); serving at model precision",
                stacklevel=2)
            kv_dtype = None
        self.kv_dtype = kv_dtype
        self.kv_guard_layers = tuple(kv_guard_layers)
        # tokens of decode headroom a request must find free at admission
        self.admit_headroom = (block_size if admit_headroom is None
                               else admit_headroom)
        full = n_slots * self.max_blocks + 1
        if stage_blocks is None:
            stage_blocks = [full] * len(pipeline.stages)
        self.stage_blocks = list(stage_blocks)
        assert len(self.stage_blocks) == len(pipeline.stages)
        # host-side bookkeeping exists from construction (capacity() needs
        # it before any insert); device page arrays allocate lazily
        self._pools: List[Optional[BlockPool]] = []
        self._tables: List[Optional[List[BlockTable]]] = []
        for st, nb in zip(pipeline.stages, self.stage_blocks):
            if st.has_attn:
                pool = BlockPool(nb, block_size)
                self._pools.append(pool)
                self._tables.append([BlockTable(pool)
                                     for _ in range(n_slots)])
            else:
                self._pools.append(None)
                self._tables.append(None)
        # ---- KVSAN: opt-in page-lifecycle sanitizer --------------------
        # (repro.analysis.kvsan) shadows every pool's alloc/incref/free,
        # tracks kernel write/read coverage per block, and audits refcount
        # conservation each iteration. Pure observation: token streams
        # are identical with it on or off.
        self.kvsan = bool(kvsan)
        self.kvsan_leaks = 0
        self._san = None
        if self.kvsan:
            from repro.analysis.kvsan import KVSanitizer
            self._san = KVSanitizer(
                quant=(self.kv_dtype is not None
                       and Q.kv_is_quantized(self.kv_dtype)))
            for si, p in enumerate(self._pools):
                if p is not None:
                    self._san.attach_pool(si, p)
        # typical next-request footprint for the capacity() port, learned
        # from admitted traffic (start at one block)
        self._need_sum = 0
        self._need_cnt = 0
        # per-stage stacked block-table arrays for the decode hot path;
        # rebuilt only when a table mutates (insert / growth / release)
        self._bt_cache: Optional[List[np.ndarray]] = None
        # ---- prefix caching / chunked prefill --------------------------
        self.prefix_caching = prefix_caching
        self.prefill_chunk = int(prefill_chunk)
        self.prefill_token_cost = prefill_token_cost
        # incremental mode routes prompts through the per-slot context
        # path instead of the joint one-shot insert
        self._incremental = prefix_caching or self.prefill_chunk > 0
        self._prefix: List[Optional[PrefixIndex]] = [
            PrefixIndex(p) if (prefix_caching and p is not None) else None
            for p in self._pools]
        # ---- host page tier (device -> host demotion) ------------------
        if host_blocks and not self.prefix_caching:
            warnings.warn(
                "host_blocks needs prefix_caching=True (the host tier is "
                "keyed by prefix chunk hashes); serving without a host "
                "tier", stacklevel=2)
            host_blocks = 0
        self.host_blocks = int(host_blocks)
        self.host_swap_cost = host_swap_cost
        self._host: List[Optional[HostPagePool]] = [
            HostPagePool(self.host_blocks, block_size)
            if (self.host_blocks > 0 and p is not None) else None
            for p in self._pools]
        # the first attention stage is the cluster directory's
        # REPRESENTATIVE: tier transitions publish once per hash, not once
        # per stage (stages register/evict near-symmetrically; the
        # directory is a hint and export verifies every stage anyway)
        self._rep_stage = next(
            (si for si, p in enumerate(self._pools) if p is not None), None)
        for si, (ix, host) in enumerate(zip(self._prefix, self._host)):
            if ix is not None and host is not None:
                ix.spill = self._make_spill(si)
                host.on_evict = self._make_host_drop(si)
        if self._san is not None:
            # after the on_evict wiring so the sanitizer's LRU-drop
            # shadowing chains onto (not replaces) the directory hook
            for si, host in enumerate(self._host):
                if host is not None:
                    self._san.attach_host(si, host)
        # ---- cluster prefix directory (attach_cluster wires these) -----
        self.cluster_dir = None
        self.cluster_link: Optional[KVLink] = None
        self._cluster_peers: Dict[int, "PagedPipelineBatcher"] = {}
        # ---- disaggregated prefill/decode ------------------------------
        self.role = role
        self.replica_id = replica_id
        # set by serving.disagg.wire_disaggregation (role="prefill" only)
        self.dispatcher = None
        # in-transit migrations: heap of (ready_time, seq, KVMigration)
        self._migrations: List = []
        self._mig_seq = 0
        # ---- speculative decoding --------------------------------------
        self.spec = spec
        self._proposer = None
        if spec is not None and not context_mode_supported(pipeline.cfg):
            warnings.warn(
                f"{pipeline.cfg.name}: speculative decoding needs an "
                "attention-only stack (a recurrent sublayer's state cannot "
                "roll back past a rejected candidate); serving without it",
                stacklevel=2)
            self.spec = None
        if self.spec is not None:
            self._proposer = self.spec.build(
                n_slots=n_slots, max_len=max_len,
                vocab_size=pipeline.cfg.vocab_size, pad_id=pad_id)
        # counters surfaced through ServeStats (loop reports deltas)
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.prefix_hit_tokens = 0
        self.prefill_tokens = 0
        self.cow_copies = 0
        self.migrations = 0            # prefills handed off (sender side)
        self.migrated_kv_bytes = 0     # payload bytes shipped (sender side)
        self.spec_steps = 0            # target multi-token verify steps
        self.spec_proposed = 0         # draft tokens proposed
        self.spec_accepted = 0         # draft tokens the target agreed with
        self.spec_tokens = 0           # tokens committed via verify steps
        self.kv_bytes_resident = 0     # allocated page-pool bytes (+scales)
        self.kv_bytes_saved = 0        # vs the model-default-dtype layout
        self.host_demotions = 0        # blocks spilled device -> host
        self.host_promotions = 0       # blocks swapped back host -> device
        self.host_evictions = 0        # host-tier LRU drops (pages lost)
        self.host_hit_tokens = 0       # prompt tokens served from host tier
        self.prefix_fetches = 0        # prefix blocks fetched from peers
        self.prefix_fetched_bytes = 0  # payload bytes shipped for fetches
        self._iter_prefill_tokens = 0
        self._iter_spec_proposed = 0
        self._iter_swap_blocks = 0
        self._iter_fetch_cost = 0.0

    # ---- block accounting -------------------------------------------------
    def _min_pool_free(self) -> int:
        # cached-prefix blocks held only by the index are reclaimable on
        # demand (LRU eviction), so admission counts them as free
        frees = [p.n_free + (ix.n_evictable() if ix is not None else 0)
                 for p, ix in zip(self._pools, self._prefix)
                 if p is not None]
        return min(frees) if frees else 1 << 30

    def _usable_blocks(self) -> int:
        sizes = [p.n_blocks - 1 for p in self._pools if p is not None]
        return min(sizes) if sizes else 1 << 30

    def _blocks_needed(self, r: Request) -> int:
        """Admission footprint: prompt + decode headroom (not worst case)."""
        toks = len(r.prompt) + min(self.admit_headroom, r.max_new_tokens)
        return blocks_for_tokens(toks, self.block_size)

    def _typical_blocks(self) -> int:
        if self._need_cnt == 0:
            return 1
        return -(-self._need_sum // self._need_cnt)

    # ---- replica port -----------------------------------------------------
    def capacity(self, now: float) -> int:
        """Admission switches from "free slot" to "enough blocks": the loop
        may only hand us another request if, beyond the queued ones' needs,
        a typical request's prompt + headroom still fits every stage
        pool. A decode-role replica admits NO fresh arrivals — its work
        arrives as migrations."""
        if self.role == "decode":
            return 0
        slots = len(self.free_slots()) - len(self._queue)
        if slots <= 0:
            return 0
        queued = sum(self._blocks_needed(r) for r in self._queue)
        if self._min_pool_free() < queued + self._typical_blocks():
            return 0
        return slots

    def load(self, now: float) -> float:
        # in-transit migrations are queue depth too: the dispatcher picks
        # decode replicas by this number
        return super().load(now) + len(self._migrations)

    def busy(self, now: float) -> bool:
        if super().busy(now):
            return True
        return bool(self._migrations) and self._migrations[0][0] <= now

    def inflight(self) -> int:
        return super().inflight() + len(self._migrations)

    def next_event(self, now: float):
        # earliest in-transit migration arrival: the idle loop must jump
        # there, not strand the request
        if self._migrations and self._migrations[0][0] > now:
            return self._migrations[0][0]
        return None

    def metrics_gauges(self):
        """Gauge snapshot for the loop's metrics publication: per-stage
        device-pool occupancy (current + high-water) and host-tier
        residency."""
        out = []
        for si, (pool, host) in enumerate(zip(self._pools, self._host)):
            if pool is None:
                continue
            st = {"stage": si}
            out.append(("kv_pool_used_blocks", st, pool.n_used))
            out.append(("kv_pool_peak_blocks", st, pool.peak_used))
            if host is not None:
                out.append(("host_pool_used_blocks", st, len(host)))
        return out

    # ---- KV migration (disaggregated prefill/decode) -----------------------
    def migrate_in(self, mig: KVMigration, ready: float) -> None:
        """Accept a finished prefill from another replica; it becomes
        placeable once the serving clock reaches `ready` (the modeled
        transfer completion)."""
        assert mig.block_size == self.block_size, \
            (mig.block_size, self.block_size)
        heapq.heappush(self._migrations, (ready, self._mig_seq, mig))
        self._mig_seq += 1

    def _place_migrations(self, now: float) -> List:
        """Land every arrived migration a free slot + blocks can take:
        allocate each stage's blocks, scatter the page payloads, and seed
        the slot at the migrated position with the migrated sampling state
        — the next decode iteration continues exactly where the prefill
        replica stopped. Returns reject completions (a migration whose
        full generation can never fit this replica's pools)."""
        comps: List = []
        while self._migrations and self._migrations[0][0] <= now:
            mig = self._migrations[0][2]
            r = mig.req
            # a LIVE migration (online rescheduler moving a mid-decode
            # slot) arrives with the tokens the source already emitted;
            # the destination owes only the remainder of the generation
            out = list(mig.out_tokens) if mig.out_tokens is not None \
                else []
            remaining = r.max_new_tokens - len(out)
            need_all = blocks_for_tokens(
                mig.n_tokens + remaining, self.block_size)
            if need_all > self._usable_blocks() \
                    or mig.n_tokens + remaining > self.max_len - 1:
                heapq.heappop(self._migrations)
                self.rejected += 1
                warnings.warn(
                    f"request {r.rid}: migrated KV ({mig.n_tokens} tokens) "
                    f"+ {remaining} more cannot fit this decode "
                    "replica; rejected with empty output")
                comps.append((r, np.zeros(0, np.int32), None))
                continue
            free = self.free_slots()
            need_now = blocks_for_tokens(
                mig.n_tokens + min(self.admit_headroom, remaining),
                self.block_size)
            if not free or self._min_pool_free() < need_now:
                break                  # wait for slots/blocks to free
            heapq.heappop(self._migrations)
            if remaining <= 0:
                # the source extracted a slot that had already emitted its
                # whole budget: nothing left to decode, complete it here
                comps.append((r, np.asarray(out, np.int32), None))
                continue
            self._ensure_device_caches()
            slot = free[0]
            dest = []
            for si, tabs in enumerate(self._tables):
                if tabs is None:
                    dest.append(None)
                    continue
                t = tabs[slot]
                assert not t.blocks, "slot freed without releasing blocks"
                ok = self._stage_alloc(si, t, mig.n_tokens)
                assert ok, "placement checked free blocks yet ran dry"
                dest.append(list(t.blocks))
            self.pipeline.scatter_kv_pages(dest, mig.layer_kv)
            if self._san is not None:
                for si, d in enumerate(dest):
                    if d is not None:
                        self._san.slot_access(si, d, mig.n_tokens, 0,
                                              self.block_size)
            self.slots[slot] = _Slot(req=r, pos=mig.n_tokens,
                                     remaining=remaining, out=out,
                                     seq=self._admit_seq)
            self._admit_seq += 1
            self._last_logits[slot] = mig.last_logits
            self._bt_cache = None
        return comps

    def _migrate_ready(self, now: float) -> None:
        """Hand every prefill-complete slot to the dispatcher: extract its
        pages and sampling state, free its blocks (index-registered prefix
        blocks stay resident), and clear the slot. Oldest first, so
        dispatch order matches admission order."""
        assert self.dispatcher is not None, \
            "role='prefill' needs wire_disaggregation to set a dispatcher"
        order = sorted((i for i, s in enumerate(self.slots)
                        if s.decoding), key=lambda i: self.slots[i].seq)
        for i in order:
            s = self.slots[i]
            blocks = [list(tabs[i].blocks) if tabs is not None else None
                      for tabs in self._tables]
            if self._san is not None:
                for si, b in enumerate(blocks):
                    if b is not None:   # pure read: the handoff extraction
                        self._san.slot_access(si, b, s.pos, s.pos,
                                              self.block_size)
            layer_kv = self.pipeline.extract_kv_pages(blocks)
            mig = KVMigration(
                req=s.req, n_tokens=s.pos, block_size=self.block_size,
                layer_kv=layer_kv,
                last_logits=np.array(self._last_logits[i]),
                kv_bytes=KVMigration.payload_bytes(layer_kv))
            if s.req.prefill_finish_time is None:
                s.req.prefill_finish_time = self._now(now)
            self.migrations += 1
            self.migrated_kv_bytes += mig.kv_bytes
            self.dispatcher.send(self, mig, now)
            self._on_slot_free(i)
            self.slots[i] = _Slot()

    # ---- live migration / evacuation (online rescheduling) -----------------
    def extract_live_slots(self, now: float,
                           slot_ids: Optional[Sequence[int]] = None
                           ) -> List[KVMigration]:
        """Package DECODING slots as live ``KVMigration``s — pages,
        sampling state, AND the tokens already emitted (``out_tokens``) —
        then free them. The destination's ``_place_migrations`` resumes
        the stream mid-flight: same pages, same ``last_logits``, same
        ``out`` prefix, so the token stream is identical to never having
        moved. Mid-prefill slots are not extractable (their cache is
        partial); ``evacuate`` requeues those for a cold re-prefill.

        This is the PLANNED-migration half of the online rescheduler: a
        healthy replica being rebalanced away hands its in-flight work to
        the new layout without draining."""
        ids = range(self.n_slots) if slot_ids is None else slot_ids
        order = sorted((i for i in ids if self.slots[i].decoding),
                       key=lambda i: self.slots[i].seq)
        migs: List[KVMigration] = []
        for i in order:
            s = self.slots[i]
            blocks = [list(tabs[i].blocks) if tabs is not None else None
                      for tabs in self._tables]
            if self._san is not None:
                for si, b in enumerate(blocks):
                    if b is not None:   # pure read: the handoff extraction
                        self._san.slot_access(si, b, s.pos, s.pos,
                                              self.block_size)
            layer_kv = self.pipeline.extract_kv_pages(blocks)
            migs.append(KVMigration(
                req=s.req, n_tokens=s.pos, block_size=self.block_size,
                layer_kv=layer_kv,
                last_logits=np.array(self._last_logits[i]),
                kv_bytes=KVMigration.payload_bytes(layer_kv),
                out_tokens=np.asarray(s.out, np.int32)))
            self.migrations += 1
            self.migrated_kv_bytes += migs[-1].kv_bytes
            if self.tracer.enabled:
                self.tracer.instant("live_move", ts=now,
                                    pid=self.replica_id, rid=s.req.rid,
                                    tokens=s.pos,
                                    bytes=migs[-1].kv_bytes)
            self._on_slot_free(i)
            self.slots[i] = _Slot()
        return migs

    def evacuate(self, now: float) -> List[Request]:
        """Release EVERYTHING in flight and return the orphaned requests:
        queued arrivals, mid-prefill slots, decoding slots, and in-transit
        migrations parked at this replica. Every page is released through
        the normal table path (KVSAN-clean — death must not leak), and the
        requests restart from their prompts wherever the caller
        re-dispatches them; greedy decode regenerates the identical token
        stream, so a replica kill costs latency, never correctness.

        This is the FAILURE half of the online rescheduler (and the
        drain-free teardown for planned removals after
        ``extract_live_slots`` took the movable slots)."""
        orphans: List[Request] = list(self._queue)
        self._queue.clear()
        for i, s in enumerate(self.slots):
            if s.free:
                continue
            orphans.append(s.req)
            self._on_slot_free(i)
            self.slots[i] = _Slot()
        while self._migrations:
            _, _, mig = heapq.heappop(self._migrations)
            orphans.append(mig.req)
        return orphans

    # ---- SlotEngine hooks --------------------------------------------------
    def _fits(self, r: Request) -> bool:
        if len(r.prompt) + r.max_new_tokens > self.max_len - 1:
            return False
        # a request whose full generation cannot fit the pool even alone
        # would preempt itself forever; turn it away instead
        need = blocks_for_tokens(len(r.prompt) + r.max_new_tokens,
                                 self.block_size)
        return need <= self._usable_blocks()

    def _can_admit(self, r: Request, batch: Sequence[Request]) -> bool:
        # prompt + headroom, same footprint capacity() advertises: admitting
        # on bare prompt blocks would prefill a request only to have its
        # first growth block evict it again (insert/preempt thrash)
        pending = sum(self._blocks_needed(q) for q in batch)
        if self._min_pool_free() < pending + self._blocks_needed(r):
            return False
        self._need_sum += self._blocks_needed(r)
        self._need_cnt += 1
        return True

    def _ensure_device_caches(self) -> None:
        if (self.pipeline.paged_caches is None
                or self.pipeline.n_slots != self.n_slots
                or self.pipeline.slot_len != self.max_len
                or self.pipeline.block_size != self.block_size
                or self.pipeline.stage_blocks != self.stage_blocks
                or self.pipeline.kv_dtype != self.kv_dtype
                or self.pipeline.kv_guard_layers != self.kv_guard_layers):
            self.pipeline.init_paged_caches(
                self.n_slots, self.max_len, block_size=self.block_size,
                stage_blocks=self.stage_blocks, kv_dtype=self.kv_dtype,
                kv_guard_layers=self.kv_guard_layers)
            self._account_kv_bytes()

    def _account_kv_bytes(self) -> None:
        """ServeStats counters: bytes the page pools actually occupy
        (payload + scale leaves) and bytes saved vs the model-default
        cache dtype (what kv_dtype=None would have allocated)."""
        base_itemsize = jnp.dtype(M._pdt(self.pipeline.cfg)).itemsize
        resident, baseline = 0, 0
        for caches in self.pipeline.paged_caches:
            for c in caches:
                if "k" not in c or "v" not in c:
                    continue       # recurrent slot state: not paged KV
                for n in ("k", "v"):
                    resident += c[n].size * c[n].dtype.itemsize
                    baseline += c[n].size * base_itemsize
                for n in ("k_scale", "v_scale"):
                    if n in c:
                        resident += c[n].size * c[n].dtype.itemsize
        self.kv_bytes_resident += int(resident)
        self.kv_bytes_saved += int(max(baseline - resident, 0))

    def _stage_alloc(self, si: int, table: BlockTable,
                     n_tokens: int) -> bool:
        """Grow `table` to hold n_tokens, reclaiming cached-prefix blocks
        from stage si's index if the pool proper is dry."""
        pool, ix = self._pools[si], self._prefix[si]
        need = blocks_for_tokens(n_tokens, self.block_size) - table.n_blocks
        if need <= 0:
            return True
        if pool.n_free < need and ix is not None:
            ix.evict(need - pool.n_free)
        before = table.n_blocks
        ok = table.allocate_tokens(n_tokens)
        if table.n_blocks != before:
            self._bt_cache = None
        return ok

    def _prefill_insert(self, toks, lens, slot_ids, rids=()):
        self._ensure_device_caches()
        self._bt_cache = None
        m = len(slot_ids)
        self.prefill_tokens += int(np.sum(lens[:m]))
        self._iter_prefill_tokens += int(np.sum(lens[:m]))
        stage_dest = []
        for si, tabs in enumerate(self._tables):
            if tabs is None:
                stage_dest.append(
                    np.zeros(m * self.max_blocks, np.int32))
                continue
            dest = np.zeros((m, self.max_blocks), np.int32)
            for row, slot in enumerate(slot_ids):
                t = tabs[slot]
                assert not t.blocks, "slot freed without releasing blocks"
                ok = self._stage_alloc(si, t, int(lens[row]))
                assert ok, "admission admitted more blocks than the pool has"
                if self._san is not None:
                    self._san.slot_access(si, t.blocks, int(lens[row]), 0,
                                          self.block_size)
                dest[row] = t.as_array(self.max_blocks)
            stage_dest.append(dest.reshape(-1))
        tr = self.tracer
        with (tr.step("insert", pid=self.replica_id, rows=m,
                      tokens=int(np.sum(lens[:m])), rids=list(rids))
              if tr.enabled else NULL_STEP):
            return self.pipeline.insert_slots_paged(toks, lens, slot_ids,
                                                    stage_dest)

    # ---- incremental insert: prefix match + deferred (chunked) prefill ----
    def _insert_batch(self, reqs: Sequence[Request],
                      slot_ids: Sequence[int], now: float = 0.0) -> None:
        if not self._incremental:
            return super()._insert_batch(reqs, slot_ids, now)
        self._ensure_device_caches()
        for r, slot in zip(reqs, slot_ids):
            self._setup_slot(r, slot)

    def _setup_slot(self, r: Request, slot: int) -> None:
        """Admission in incremental mode: queue the whole prompt as pending
        prefill. The prefix lookup runs LAZILY at the slot's first prefill
        step (_match_slot) rather than here: _prefill_step visits slots
        oldest-first, so a later arrival admitted in the same batch still
        sees the blocks an earlier one registered this very iteration.
        No model work happens here."""
        hashes = chunk_hashes(r.prompt, self.block_size) \
            if self.prefix_caching else []
        self.slots[slot] = _Slot(req=r, pos=0,
                                 remaining=r.max_new_tokens, out=[],
                                 seq=self._admit_seq,
                                 pending=np.asarray(r.prompt, np.int32),
                                 hashes=hashes,
                                 matched=not self.prefix_caching)
        self._admit_seq += 1

    def _match_slot(self, i: int) -> None:
        """First-touch prefix lookup for slot i: alias the longest
        device-indexed prefix (incref per stage), then EXTEND the match
        down the memory hierarchy — host-tier pages swap back into fresh
        device blocks, pages resident only on peer replicas migrate over
        the KV link — and drop the whole matched prefix from the pending
        prefill."""
        s = self.slots[i]
        s.matched = True
        if not s.hashes:
            return
        self.prefix_lookups += 1
        L = min(ix.match_len(s.hashes)
                for ix in self._prefix if ix is not None)
        if L:
            # alias the hit prefix in EVERY stage (symmetric indexes:
            # registered/evicted together, so L agrees up to eviction
            # races — min() above settles those), incref-ing BEFORE any
            # tier promotion so a promotion's eviction can never take
            # what this very match already claimed
            for tabs, ix in zip(self._tables, self._prefix):
                if tabs is None:
                    continue
                t = tabs[i]
                assert not t.blocks, "slot freed without releasing"
                t.adopt(ix.acquire(s.hashes[:L]))
        Lx = L
        if self._tiered:
            while Lx < len(s.hashes) \
                    and self._materialize_hash(i, s.hashes[Lx]):
                Lx += 1
        if not Lx:
            return
        # always leave >= 1 cold token: the final logits must come from a
        # real forward pass (a fully cached prompt re-runs its last token,
        # copy-on-write duplicating the shared tail block)
        cold = min(Lx * self.block_size, len(s.req.prompt) - 1)
        s.pos = cold
        s.pending = s.pending[cold:]
        self.prefix_hits += 1
        self.prefix_hit_tokens += cold
        self._bt_cache = None

    def _prepare_chunk(self, i: int, target_tokens: int) -> bool:
        """Make [slot i's tables] able to hold target_tokens AND the next
        write position exclusively owned (copy-on-write). False when some
        pool is dry even after eviction — caller preempts and retries."""
        pos = self.slots[i].pos
        for si, tabs in enumerate(self._tables):
            if tabs is None:
                continue
            t = tabs[i]
            if not self._stage_alloc(si, t, target_tokens):
                return False
            bi = pos // self.block_size
            if bi < t.n_blocks:
                pool, ix = self._pools[si], self._prefix[si]
                if pool.n_free < 1 and ix is not None \
                        and pool.ref(t.blocks[bi]) > 1:
                    ix.evict(1)
                cow = t.writable(bi)
                if cow is False:
                    return False
                if cow is not None:
                    src, dst = cow
                    self.pipeline.copy_pages(si, [src], [dst])
                    if self._san is not None:
                        self._san.on_copy(si, src, dst)
                    self.cow_copies += 1
                    self._bt_cache = None
        return True

    def _prefill_step(self, now: float) -> None:
        """Run ONE prefill chunk for every mid-prefill slot, oldest first —
        interleaved with the decode so a long cold prompt shares the
        iteration budget instead of monopolizing it. Same-iteration chunks
        coalesce into joint context dispatches; the batch flushes whenever
        a slot COMPLETES its prompt (it registers its blocks on flush, so
        a later same-iteration arrival with the same prefix still matches
        instead of re-prefilling — dedup beats batching there)."""
        order = sorted((i for i, s in enumerate(self.slots)
                        if not s.free and s.pending is not None),
                       key=lambda i: self.slots[i].seq)
        group: List = []               # (slot, chunk) awaiting one dispatch
        for i in order:
            s = self.slots[i]
            if s.free or s.pending is None:
                continue               # preempted by an earlier slot's turn
            if not s.matched:
                # match AFTER flushing so this lookup sees every block the
                # batch's completed prompts just registered
                self._dispatch_chunks(group, now)
                self._match_slot(i)
            chunk = len(s.pending) if self.prefill_chunk <= 0 \
                else min(self.prefill_chunk, len(s.pending))
            while not self.slots[i].free \
                    and not self._prepare_chunk(i, s.pos + chunk):
                active = [j for j, sl in enumerate(self.slots)
                          if not sl.free]
                self._preempt(max(active,
                                  key=lambda j: self.slots[j].seq))
            if self.slots[i].free:
                continue               # evicted itself; requeued up front
            group.append((i, chunk))
            if self.prefix_caching and chunk == len(s.pending):
                self._dispatch_chunks(group, now)
        self._dispatch_chunks(group, now)

    def _dispatch_chunks(self, group: List, now: float = 0.0) -> None:
        """Joint (m, C) right-padded context-prefill call for the queued
        (slot, chunk) pairs: slot i's next `chunk` pending tokens run at
        absolute positions [pos, pos+chunk). Width buckets to multiples of
        16 and the row count to the next power of two (capped at n_slots),
        so mixed chunk lengths and group sizes compile O(log) shapes; pad
        rows have length 0 and all-null tables, so they write only the
        trash page. Clears `group`."""
        pairs = [(i, c) for i, c in group
                 if not self.slots[i].free]   # a later prepare may preempt
        group.clear()
        if not pairs:
            return
        m = len(pairs)
        rows = min(1 << (m - 1).bit_length(), self.n_slots)
        C = min(-(-max(c for _, c in pairs) // 16) * 16, self.max_len - 1)
        toks = np.full((rows, C), self.pad_id, np.int32)
        lens = np.zeros(rows, np.int32)
        starts = np.zeros(rows, np.int32)
        for row, (i, c) in enumerate(pairs):
            s = self.slots[i]
            toks[row, :c] = s.pending[:c]
            lens[row] = c
            starts[row] = s.pos
        if self._san is not None:
            for si, tabs in enumerate(self._tables):
                if tabs is None:
                    continue
                for row, (i, c) in enumerate(pairs):
                    self._san.slot_access(
                        si, tabs[i].blocks, int(starts[row]) + c,
                        int(starts[row]), self.block_size)
        tables = []
        for tabs in self._tables:
            t = np.zeros((rows, self.max_blocks), np.int32)
            if tabs is not None:
                for row, (i, _) in enumerate(pairs):
                    t[row] = tabs[i].as_array(self.max_blocks)
            tables.append(t)
        tr = self.tracer
        if tr.enabled:
            ntok = int(lens.sum())
            rids = [self.slots[i].req.rid for i, _ in pairs]
            prefill = tr.step(
                "prefill",
                virtual=self.virtual_step_cost * self.prefill_token_cost
                * ntok, pid=self.replica_id, tokens=ntok, slots=m,
                rids=rids)
            context = tr.step("context", pid=self.replica_id, rows=m,
                              tokens=ntok, rids=rids,
                              starts=starts[:m].tolist(), tp=self._stage_tp)
        else:
            prefill = context = NULL_STEP
        with prefill, context:
            logits = np.asarray(self.pipeline.context_slots_paged(
                toks, lens, starts, tables))
        t = None
        for row, (i, c) in enumerate(pairs):
            s = self.slots[i]
            s.pos += c
            s.pending = s.pending[c:]
            self.prefill_tokens += c
            self._iter_prefill_tokens += c
            if len(s.pending) == 0:    # prompt fully cached: decode next
                s.pending = None
                self._last_logits[i] = logits[row]
                self._register_prefix(i, s)
                self._bt_cache = None
                if s.req.prefill_finish_time is None:
                    if t is None:
                        t = self._now(now)
                    s.req.prefill_finish_time = t

    def _register_prefix(self, i: int, s: _Slot) -> None:
        """Index the prompt's full blocks so later prompts can alias them
        (the index takes its own reference; entries already present keep
        their canonical block). Registration supersedes any host-tier copy
        (one-tier invariant) and publishes device residency to the cluster
        directory."""
        if not self.prefix_caching or not s.hashes:
            return
        for tabs, ix, host in zip(self._tables, self._prefix, self._host):
            if tabs is None or ix is None:
                continue
            ix.register(s.hashes, tabs[i].blocks[:len(s.hashes)])
            if host is not None:
                for h in s.hashes:
                    host.discard(h)
        if self.cluster_dir is not None:
            for h in s.hashes:
                self.cluster_dir.publish(h, self.replica_id, "device")

    # ---- tiered pages: host spill pool + cluster prefix directory ---------
    @property
    def _tiered(self) -> bool:
        return (any(hp is not None for hp in self._host)
                or self.cluster_dir is not None)

    def _make_spill(self, si: int):
        """Demotion closure for stage si's PrefixIndex: an evicted prefix
        block's page payload moves device -> host instead of vanishing."""
        host = self._host[si]

        def spill(h: int, bid: int) -> None:
            if self.pipeline.paged_caches is None:
                return             # nothing ever materialized on device
            if self._san is not None:
                self._san.on_spill(si, bid)
            host.put(h, self.pipeline.extract_stage_pages(si, [bid]))
            self.host_demotions += 1
            self._iter_swap_blocks += 1
            if self.tracer.enabled:
                self.tracer.complete(
                    "host_spill",
                    self.virtual_step_cost * self.host_swap_cost,
                    pid=self.replica_id, tid=si)
            if si == self._rep_stage and self.cluster_dir is not None:
                self.cluster_dir.publish(h, self.replica_id, "host")
        return spill

    def _make_host_drop(self, si: int):
        """LRU-bound closure for stage si's HostPagePool: the page has now
        left this replica entirely (bottom of the hierarchy)."""
        def dropped(h: int) -> None:
            self.host_evictions += 1
            if si == self._rep_stage and self.cluster_dir is not None:
                self.cluster_dir.unpublish(h, self.replica_id)
        return dropped

    def attach_cluster(self, directory, peers: Dict[int, object],
                       link: Optional[KVLink]) -> None:
        """Join a cluster prefix directory (cluster_kv.wire_cluster_prefix):
        publish this replica's residency and fetch hot prefixes from
        `peers` (replica_id -> engine) over `link`."""
        assert self.prefix_caching, \
            "cluster prefix sharing needs prefix_caching=True"
        self.cluster_dir = directory
        self.cluster_link = link if link is not None else KVLink()
        self._cluster_peers = {rid: w for rid, w in peers.items()
                               if rid != self.replica_id}
        # without a host tier, an evicted prefix block leaves the replica
        # entirely — retract the directory claim at eviction time so the
        # published residency never outlives the page (peers would only
        # have wasted a fetch attempt on the stale entry, but KVSAN's
        # directory audit rightly calls the dangling claim a violation)
        ix = (self._prefix[self._rep_stage]
              if self._rep_stage is not None else None)
        if ix is not None and ix.spill is None:
            def _unpublish_on_evict(h: int, bid: int) -> None:
                self.cluster_dir.unpublish(h, self.replica_id)
            ix.spill = _unpublish_on_evict

    def export_prefix_block(self, h: int):
        """Package chain hash `h`'s page payload for a peer replica —
        global layer order, the ``KVMigration`` wire format — sourcing
        each stage from its device index or host tier (a COPY ships;
        local residency is untouched). None when some stage no longer
        holds the page (the caller unpublishes the stale directory
        entry and prefills cold)."""
        if self.pipeline.paged_caches is None:
            return None
        layer_kv: List[dict] = []
        for si, (pool, ix, host) in enumerate(
                zip(self._pools, self._prefix, self._host)):
            if pool is None or ix is None:
                return None        # non-attention stage: nothing to export
            bid = ix.lookup(h)
            if bid is not None:
                if self._san is not None:   # peer export reads the page
                    self._san.on_spill(si, bid)
                layer_kv.extend(self.pipeline.extract_stage_pages(si, [bid]))
                continue
            payload = host.peek(h) if host is not None else None
            if payload is None:
                return None
            layer_kv.extend(payload)
        return layer_kv

    def _materialize_hash(self, i: int, h: int) -> bool:
        """Make chain hash `h` device-resident, registered, and aliased
        into slot i's tables in EVERY attention stage. Per stage the
        source is the device index (plain alias), this replica's host
        tier (swap-in: the payload scatters into a fresh block), or a
        peer replica named by the cluster directory (hot-prefix migration
        in the KVMigration wire format, charged at KVLink delay on the
        serving clock). False when some stage holds the page nowhere
        reachable or a pool stays dry even after eviction — the caller
        stops extending and prefills the remainder cold."""
        plan: List = []            # (si, "device" | "host" | "fetch")
        need_fetch = False
        for si, (pool, ix, host) in enumerate(
                zip(self._pools, self._prefix, self._host)):
            if pool is None or ix is None:
                continue
            if ix.lookup(h) is not None:
                plan.append((si, "device"))
            elif host is not None and h in host:
                plan.append((si, "host"))
            else:
                plan.append((si, "fetch"))
                need_fetch = True
        if not plan:
            return False
        layer_kv, src_rid = None, None
        if need_fetch:
            if self.cluster_dir is None:
                return False
            for rid, _tier in self.cluster_dir.holders(
                    h, exclude=self.replica_id):
                peer = self._cluster_peers.get(rid)
                if peer is None:
                    continue
                layer_kv = peer.export_prefix_block(h)
                if layer_kv is not None:
                    src_rid = rid
                    break
                self.cluster_dir.unpublish(h, rid)   # stale entry
            if layer_kv is None:
                return False
        # pop host payloads BEFORE allocating: allocation may evict-demote
        # other blocks into the host pool, and the LRU drop absorbing them
        # must never take the very payload being promoted
        payloads = {}
        for si, kind in plan:
            if kind == "host":
                payloads[si] = self._host[si].get(h)
                assert payloads[si] is not None, "planned host page vanished"
        alloc: Dict[int, int] = {}
        for si, kind in plan:
            if kind == "device":
                continue
            pool, ix = self._pools[si], self._prefix[si]
            if pool.n_free < 1:
                ix.evict(1)
            got = pool.alloc(1)
            if got is None:        # dry even after eviction: roll back
                for sj, bid in alloc.items():
                    self._pools[sj].free(bid)
                for sj, payload in payloads.items():
                    self._host[sj].restore(h, payload)
                return False
            alloc[si] = got[0]
        # land the payloads
        promoted = False
        dest: List = [None] * len(self._tables)
        for si, kind in plan:
            if kind == "host":
                self.pipeline.scatter_stage_pages(si, [alloc[si]],
                                                  payloads[si])
                if self._san is not None:
                    self._san.note_write(si, [alloc[si]])
                promoted = True
                self.host_promotions += 1
                self._iter_swap_blocks += 1
                if self.tracer.enabled:
                    self.tracer.complete(
                        "host_promote",
                        self.virtual_step_cost * self.host_swap_cost,
                        pid=self.replica_id, tid=si)
            elif kind == "fetch":
                dest[si] = [alloc[si]]
        if need_fetch:
            # only the locally-missing stages' layer slices cross the link
            self.pipeline.scatter_kv_pages(dest, layer_kv)
            if self._san is not None:
                for sj, d in enumerate(dest):
                    if d is not None:
                        self._san.note_write(sj, d)
            fetch_bytes, li = 0, 0
            for si, st in enumerate(self.pipeline.stages):
                n_layers = st.hi - st.lo
                if dest[si] is not None:
                    fetch_bytes += KVMigration.payload_bytes(
                        layer_kv[li:li + n_layers])
                li += n_layers
            self.prefix_fetches += 1
            self.prefix_fetched_bytes += fetch_bytes
            fetch_cost = self.cluster_link.delay(
                fetch_bytes, src_rid, self.replica_id)
            self._iter_fetch_cost += fetch_cost
            if self.tracer.enabled:
                self.tracer.complete("prefix_fetch", fetch_cost,
                                     pid=self.replica_id,
                                     src=src_rid, bytes=fetch_bytes)
        if promoted:
            self.host_hit_tokens += self.block_size
        # register + alias: the index takes its own reference, the table
        # takes over the allocation's — refcount 2, exactly the prefill
        # registration shape, so the new block is immune to eviction while
        # deeper hashes of this very chain materialize
        for si, kind in plan:
            ix, t = self._prefix[si], self._tables[si][i]
            if kind == "device":
                t.adopt(ix.acquire([h]))
            else:
                ix.register([h], [alloc[si]])
                t.adopt([alloc[si]])
        if self.cluster_dir is not None:
            self.cluster_dir.publish(h, self.replica_id, "device")
        return True

    def _ensure_blocks(self, i: int) -> bool:
        # decode writes at pos: grow to hold it AND copy-on-write if the
        # target block is still shared (defensive — full-block-only
        # sharing means decode normally lands in exclusive blocks)
        return self._prepare_chunk(i, self.slots[i].pos + 1)

    def _before_decode(self) -> None:
        """Allocate-on-decode growth; preempt-by-recompute when a pool runs
        dry. Oldest slots grow first and the YOUNGEST active slot is
        evicted — possibly the requester itself — so the head of the line
        always makes progress (no livelock: a request that cannot fit even
        alone was rejected by _fits)."""
        order = sorted((i for i, s in enumerate(self.slots)
                        if s.decoding), key=lambda i: self.slots[i].seq)
        for i in order:
            while self.slots[i].decoding and not self._ensure_blocks(i):
                active = [j for j, sl in enumerate(self.slots)
                          if not sl.free]
                self._preempt(max(active, key=lambda j: self.slots[j].seq))

    def _preempt(self, i: int) -> None:
        s = self.slots[i]
        for tabs in self._tables:
            if tabs is not None:
                tabs[i].release()
        self._bt_cache = None
        if self._proposer is not None:
            self._proposer.release(i)
        # recompute: the request restarts from its prompt (greedy decode
        # regenerates the same prefix), at the FRONT of the queue. The
        # slot's s.pos cached tokens (prompt, and outputs once decoding)
        # are thrown away and computed again.
        self._queue.appendleft(s.req)
        self.slots[i] = _Slot()
        self.preemptions += 1
        self.recomputed_tokens += s.pos
        if self.tracer.enabled:
            # the recompute itself shows up as this request's next
            # prefill span; the eviction is the instant
            self.tracer.instant("preempt", pid=self.replica_id,
                                rid=s.req.rid, slot=i, tokens=s.pos)

    def _on_slot_free(self, i: int) -> None:
        for tabs in self._tables:
            if tabs is not None:
                tabs[i].release()
        self._bt_cache = None
        if self._proposer is not None:
            self._proposer.release(i)

    # ---- speculative decoding (draft -> multi-token verify -> accept) ----
    def _spec_iteration(self, now: float):
        """One target step under speculative decoding: PROPOSE a candidate
        chunk per decoding slot (the bonus token — the argmax the plain
        decode would feed next — plus up to ``spec.k`` drafts), ENSURE
        blocks/COW for the whole chunk (a dry pool preempts the youngest
        active slot, exactly like plain decode growth), VERIFY every
        slot's chunk in one multi-token pipeline step, then ACCEPT the
        longest draft prefix matching the target's argmax chain and ROLL
        BACK the speculative pages past the committed length. Greedy
        acceptance keeps the committed stream token-identical to plain
        greedy decode; the win is committing up to k + 1 tokens per
        target step."""
        k = self.spec.k
        items = []
        for i, s in enumerate(self.slots):
            if not s.decoding:
                continue
            bonus = int(self._last_logits[i].argmax())
            # the chunk must fit the request's remaining budget AND the
            # slot ceiling (writes stop at max_len - 2, like decode)
            cap = max(min(k, s.remaining - 1, self.max_len - 2 - s.pos), 0)
            hist = np.concatenate([
                np.asarray(s.req.prompt, np.int32),
                np.asarray(s.out, np.int32),
                np.asarray([bonus], np.int32)])
            items.append((i, bonus, hist, cap))
        props = self._proposer.propose(
            [(i, hist, cap) for i, _, hist, cap in items])
        n_prop = sum(len(p) for p in props.values())
        self._iter_spec_proposed += n_prop
        if self.tracer.enabled and n_prop:
            self.tracer.complete(
                "spec_propose",
                self.virtual_step_cost * self.spec.draft_token_cost
                * n_prop,
                ts=now, pid=self.replica_id, tokens=n_prop)
        # block growth + copy-on-write for the whole chunk, oldest first
        plan = {}
        empty = np.zeros(0, np.int32)
        for i, bonus, hist, cap in sorted(
                items, key=lambda it: self.slots[it[0]].seq):
            if not self.slots[i].decoding:
                continue           # preempted by an earlier slot's turn
            drafts = np.asarray(props.get(i, empty), np.int32)[:cap]
            while self.slots[i].decoding and not self._prepare_chunk(
                    i, self.slots[i].pos + 1 + len(drafts)):
                active = [j for j, sl in enumerate(self.slots)
                          if not sl.free]
                self._preempt(max(active, key=lambda j: self.slots[j].seq))
            if self.slots[i].decoding:
                plan[i] = (bonus, drafts)
        if not plan:
            return []              # everyone preempted themselves away
        # joint verification dispatch: FIXED chunk width k + 1 (one
        # compile), per-slot real counts; absent slots are dead rows with
        # null tables, like free slots in the joint decode
        T = k + 1
        toks = np.zeros((self.n_slots, T), np.int32)
        qlen = np.zeros((self.n_slots,), np.int32)
        starts = np.zeros((self.n_slots,), np.int32)
        for i, (bonus, drafts) in plan.items():
            toks[i, 0] = bonus
            toks[i, 1:1 + len(drafts)] = drafts
            qlen[i] = 1 + len(drafts)
            starts[i] = self.slots[i].pos
        tables = [np.zeros((self.n_slots, self.max_blocks), np.int32)
                  if tabs is None else
                  np.stack([t.as_array(self.max_blocks) if j in plan
                            else np.zeros(self.max_blocks, np.int32)
                            for j, t in enumerate(tabs)])
                  for tabs in self._tables]
        if self._san is not None:
            for si, tabs in enumerate(self._tables):
                if tabs is None:
                    continue
                for i in plan:
                    self._san.slot_access(
                        si, tabs[i].blocks, int(starts[i]) + int(qlen[i]),
                        int(starts[i]), self.block_size)
        # the multi-token verification step is the iteration's target
        # pass: flat iteration cost, like a plain decode step
        tr = self.tracer
        with (tr.step("spec_verify", virtual=self.virtual_step_cost,
                      pid=self.replica_id, slots=len(plan),
                      rids=[self.slots[i].req.rid for i in plan])
              if tr.enabled else NULL_STEP):
            logits = np.asarray(self.pipeline.verify_slots_paged(
                toks, qlen, starts, tables))
        t = None
        done = []
        for i, (bonus, drafts) in plan.items():
            s = self.slots[i]
            commit, a = greedy_accept(logits[i], bonus, drafts)
            self.spec_steps += 1
            self.spec_proposed += len(drafts)
            self.spec_accepted += a
            self.spec_tokens += len(commit)
            # logits[a] is the distribution after the last committed
            # token — its argmax is the next step's bonus token
            self._last_logits[i] = logits[i, a]
            if not s.out and s.req is not None:
                # first-wins across preempt-recompute, like plain decode
                if s.req.first_token_time is None:
                    if t is None:
                        t = self._now(now)
                    s.req.first_token_time = t
            s.out.extend(commit)
            s.pos += len(commit)
            s.remaining -= len(commit)
            # speculative-page rollback: blocks wholly past the committed
            # length return to the pool (prefix-index aliases survive —
            # truncate drops one reference like any release)
            freed = 0
            for tabs in self._tables:
                if tabs is not None:
                    freed += tabs[i].truncate(s.pos)
            if freed:
                self._bt_cache = None
                if self.tracer.enabled:
                    self.tracer.instant("spec_rollback", ts=now,
                                        pid=self.replica_id, slot=i,
                                        blocks=freed)
            if s.remaining <= 0 or s.pos >= self.max_len - 1:
                done.append((s.req, s.out))
                self._on_slot_free(i)
                self.slots[i] = _Slot()
            else:
                self._proposer.commit(i, a)
        return done

    def _step(self, now: float):
        if self._incremental:
            self._prefill_step(now)
        if self.role == "prefill":
            self._migrate_ready(now)   # hand off instead of decoding
            return []
        if any(s.decoding for s in self.slots):
            if self.spec is not None:
                return self._spec_iteration(now)
            return self._decode_iteration(now)
        return []                  # every occupied slot is still prefilling

    def run_iteration(self, now: float):
        self._iter_prefill_tokens = 0
        self._iter_spec_proposed = 0
        self._iter_swap_blocks = 0
        self._iter_fetch_cost = 0.0
        # land arrived migrations BEFORE the base iteration so their slots
        # join this very decode step (mirrors colocated serving, where a
        # prefill finishing in iteration i decodes its first token in i)
        mig_comps = self._place_migrations(now) if self._migrations else []
        comps, cost = super().run_iteration(now)
        # virtual accounting: charge prefilled tokens a fraction of an
        # iteration so chunking/prefix hits show up in simulated latency
        if self._iter_prefill_tokens and self.prefill_token_cost:
            cost += (self.virtual_step_cost * self.prefill_token_cost
                     * self._iter_prefill_tokens)
        # ... and draft proposals their configured fraction, so the
        # acceptance-aware cost model's draft overhead is measurable
        if self._iter_spec_proposed and self.spec is not None \
                and self.spec.draft_token_cost:
            cost += (self.virtual_step_cost * self.spec.draft_token_cost
                     * self._iter_spec_proposed)
        # ... and every block crossing the device<->host boundary its swap
        # cost, plus cluster prefix fetches their modeled link delay — the
        # tiers are only a win when the swap is cheaper than the recompute
        # it replaces, and the clock must be able to say so
        if self._iter_swap_blocks and self.host_swap_cost:
            cost += (self.virtual_step_cost * self.host_swap_cost
                     * self._iter_swap_blocks)
        if self._iter_fetch_cost:
            cost += self._iter_fetch_cost
        if self._san is not None:
            self._kvsan_audit()
        return mig_comps + comps, cost

    def _kvsan_audit(self) -> None:
        """Iteration-boundary KVSAN audit: every pool reference must be
        explained by a slot's BlockTable or a PrefixIndex entry
        (unexplained references count as leaks -> kvsan_leaks; a
        reference a table expects but the pool lost raises), the host
        shadow must match the actual host tier, and every directory
        entry this replica published must point at a page it still
        holds."""
        san = self._san
        for si, pool in enumerate(self._pools):
            if pool is None:
                continue
            expected: Dict[int, int] = {}
            for t in self._tables[si]:
                for b in t.blocks:
                    expected[b] = expected.get(b, 0) + 1
            ix = self._prefix[si]
            if ix is not None:
                for bid in ix.indexed_blocks():
                    expected[bid] = expected.get(bid, 0) + 1
            self.kvsan_leaks += san.audit_pool(si, pool, expected)
            host = self._host[si]
            if host is not None:
                san.audit_host(si, host)
        if self.cluster_dir is not None and self._rep_stage is not None:
            ix = self._prefix[self._rep_stage]
            host = self._host[self._rep_stage]
            for h, tier in self.cluster_dir.entries_for(self.replica_id):
                if tier == "device" and (ix is None
                                         or ix.lookup(h) is None):
                    san.violate(
                        f"kvsan replica {self.replica_id}: directory "
                        f"says device for hash {h} but no block is "
                        "resident")
                elif tier == "host" and (host is None or h not in host):
                    san.violate(
                        f"kvsan replica {self.replica_id}: directory "
                        f"says host for hash {h} but the host tier "
                        "lacks it")

    def _decode_tables(self) -> None:
        """The decode call's per-stage block tables (cached until a table
        mutates); KVSAN records the decode's page accesses here."""
        if self._san is not None:
            for si, tabs in enumerate(self._tables):
                if tabs is None:
                    continue
                for j, s in enumerate(self.slots):
                    if s.decoding:
                        self._san.slot_access(
                            si, tabs[j].blocks, s.pos + 1, s.pos,
                            self.block_size)
        if self._bt_cache is None:
            # rows of slots that are NOT decoding (free, or mid-prefill)
            # present an all-null table so their joint-iteration garbage
            # write lands in the trash page, never in allocated blocks
            self._bt_cache = [
                np.zeros((self.n_slots, self.max_blocks), np.int32)
                if tabs is None else
                np.stack([t.as_array(self.max_blocks)
                          if self.slots[j].decoding else
                          np.zeros(self.max_blocks, np.int32)
                          for j, t in enumerate(tabs)])
                for tabs in self._tables]

    def _decode_all(self, toks, pos):
        return self.pipeline.decode_slots_paged(toks, pos, self._bt_cache)

    def _decode_call_args(self) -> dict:
        return {"programs": self.pipeline.decode_programs,
                "tp": self._stage_tp}
