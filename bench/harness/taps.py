"""Observation of the served path from outside the program.

``Recorder`` wraps each replica's ``insert_slots_paged``,
``context_slots_paged`` and ``decode_slots_paged``. Each returns the logits
of its rows to the host, so when a call comes back the host holds the next
token of each of those rows: the recorder stamps that token then, on the
serve loop's clock. It also records each call's host span, rows and
tokens, marks it in the profiler's trace, and can close the window: after
``stop_at`` every worker reports no capacity and no work, so the serve
loop admits nothing more and returns. The replicas' results pass through
unchanged.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.monitoring
import numpy as np

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")


@dataclasses.dataclass
class Call:
    kind: str                   # insert | context | decode
    replica: int
    t0: float
    t1: float
    rows: int                   # rows with real work
    tokens: int                 # prompt tokens prefilled (insert/context)
    ctx_tokens: int = 0         # decode: tokens the live rows attend to


class _Compiles:
    """Backend compilations and persistent-cache loads, stamped by the
    recorder that is active. jax.monitoring listeners cannot be removed,
    so one is registered per process and forwards to the active one."""
    active: Optional["Recorder"] = None
    registered = False

    @classmethod
    def install(cls) -> None:
        if cls.registered:
            return
        cls.registered = True

        def on_duration(name, _secs, **_kw):
            if name in COMPILE_EVENTS and cls.active is not None:
                cls.active.compiles.append((name, cls.active.now()))

        def on_event(name, **_kw):
            if name in COMPILE_EVENTS and cls.active is not None:
                cls.active.compiles.append((name, cls.active.now()))

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


class Recorder:
    """Token stamps, call spans and the window's end for one serve."""

    def __init__(self, workers, *, annotate: bool = False):
        self.workers = list(workers)
        self.annotate = annotate
        self.clock = None
        self.window = float("inf")
        self.stop_at = float("inf")
        self.calls: List[Call] = []
        self.tokens: Dict[int, Dict[int, float]] = {}   # rid -> {k: t}
        self.compiles: List = []
        self.before_call = None     # hook(now) run before every call
        self._rid: Dict[bytes, int] = {}
        for ri, w in enumerate(self.workers):
            self._wrap(ri, w)
        _Compiles.install()

    # ---- the window ---------------------------------------------------------
    def start(self, clock, requests, *, window: float, stop_at: float
              ) -> None:
        """Watch ``requests`` served on ``clock``, whose zero opens the
        window; close admission and work at ``stop_at``."""
        self.clock, self.window, self.stop_at = clock, window, stop_at
        self._rid = {np.asarray(r.prompt, np.int32).tobytes(): r.rid
                     for r in requests}
        _Compiles.active = self

    def finish(self) -> None:
        _Compiles.active = None

    def reset(self) -> None:
        """Forget what an earlier serve (a warm-up, another window) saw."""
        self.calls, self.tokens, self.compiles = [], {}, []
        self.before_call = None
        self.clock = None
        self.window = self.stop_at = float("inf")

    def now(self) -> float:
        return self.clock.now() if self.clock is not None else 0.0

    def closed(self) -> bool:
        return self.clock is not None and self.clock.now() >= self.stop_at

    # ---- wrapping -----------------------------------------------------------
    def _stamp(self, rid: Optional[int], k: int, t: float) -> None:
        if rid is None or k < 0:
            return
        self.tokens.setdefault(rid, {}).setdefault(k, t)   # first wins

    def _span(self, kind: str, t0: float):
        if not self.annotate:
            return _NoSpan()
        tag = "bench" if t0 < self.window else "bench_after"
        return jax.profiler.TraceAnnotation(
            f"{tag}:{kind}#{len(self.calls)}")

    def _call(self, kind, ri, fn, args, rows, tokens, ctx=0):
        if self.before_call is not None:
            self.before_call(self.now())
        t0 = self.now()
        with self._span(kind, t0):
            out = fn(*args)          # host logits: the call has finished
        t1 = self.now()
        self.calls.append(Call(kind, ri, t0, t1, rows, tokens, ctx))
        return out, t1

    def _wrap(self, ri: int, w) -> None:
        pipe = w.pipeline
        insert, context = pipe.insert_slots_paged, pipe.context_slots_paged
        decode = pipe.decode_slots_paged
        capacity, busy = w.capacity, w.busy

        def insert_tap(tokens, lens, slot_ids, stage_dest):
            m = len(slot_ids)
            rids = [self._rid.get(np.asarray(tokens[i, :lens[i]],
                                             np.int32).tobytes())
                    for i in range(m)]
            out, t1 = self._call("insert", ri, insert,
                                 (tokens, lens, slot_ids, stage_dest), m,
                                 int(np.sum(lens[:m])))
            for rid in rids:
                self._stamp(rid, 0, t1)
            return out

        def context_tap(tokens, lens, q_start, stage_tables):
            # a row that ends its prompt yields the first token; the slot
            # is the one whose next pending chunk this row is
            last = []
            for row in range(tokens.shape[0]):
                c, st = int(lens[row]), int(q_start[row])
                for s in w.slots:
                    if (s.req is not None and s.pending is not None
                            and s.pos == st and len(s.pending) >= c
                            and np.array_equal(s.pending[:c],
                                               tokens[row, :c])):
                        if len(s.pending) == c:
                            last.append(s.req.rid)
                        break
            out, t1 = self._call("context", ri, context,
                                 (tokens, lens, q_start, stage_tables),
                                 tokens.shape[0], int(np.sum(lens)))
            for rid in last:
                self._stamp(rid, 0, t1)
            return out

        def decode_tap(tokens, positions, stage_tables):
            live = [(s.req, int(positions[j]))
                    for j, s in enumerate(w.slots)
                    if s.decoding and s.req is not None]
            out, t1 = self._call("decode", ri, decode,
                                 (tokens, positions, stage_tables),
                                 len(live), 0,
                                 ctx=sum(p + 1 for _, p in live))
            for req, p in live:
                # consumed the token at position p; yields output p-plen+1
                self._stamp(req.rid, p - len(req.prompt) + 1, t1)
            return out

        def capacity_tap(now):
            return 0 if self.closed() else capacity(now)

        def busy_tap(now):
            return False if self.closed() else busy(now)

        pipe.insert_slots_paged = insert_tap
        pipe.context_slots_paged = context_tap
        pipe.decode_slots_paged = decode_tap
        w.capacity = capacity_tap
        w.busy = busy_tap

    # ---- what a request saw -------------------------------------------------
    def token_times(self, rid: int, n: int) -> Optional[np.ndarray]:
        """Host times of the request's first n output tokens, or None
        where one was never stamped."""
        got = self.tokens.get(rid, {})
        if any(k not in got for k in range(n)):
            return None
        return np.array([got[k] for k in range(n)])


class _NoSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False
