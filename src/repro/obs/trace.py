"""HexTrace: span-based request tracing over the serving clock.

The serving stack is instrumented with a single ``Tracer`` that rides
whatever clock the serve loop runs on (``WallClock`` or ``VirtualClock``)
and records three event shapes:

  * **steps** — ``with tracer.step(name, virtual=cost, **args):`` times
    one piece of work. On a real clock (``WallClock``) the step samples
    the clock on entry and exit, and also enters a profiler annotation
    ``hex:<name>`` (``jax.profiler.TraceAnnotation``), so the same span
    lands on the host plane of a profiler trace, next to the device's
    operations. On ``VirtualClock`` the clock does NOT advance while a
    worker iteration runs (the loop ticks once per cycle by the slowest
    worker's cost), so a step records a complete event at the entry time
    with the virtual cost the engine attributes to it; a step given no
    virtual cost (``virtual=None``: host work the cost model does not
    charge) records nothing there. Each step's event carries its own id
    (``args.id``) and the id of the step open around it
    (``args.parent``), so a span's self time is its duration less its
    children's, with no guessing by time containment.
  * **complete events / begin-end spans** — an interval with an explicit
    (modelled) duration, or a matched pair sampled from the clock for
    intervals that straddle loop cycles (the ``serve`` root span). Every
    ``begin`` must be closed by ``end`` on the same code path — the
    repro-lint ``span-pairing`` rule enforces this statically.
  * **instant events** — zero-duration markers (preemption, replica
    kill, KVSAN audit).

Events emitted while a step is open carry that step's id as
``args.parent``. While a tracer is bound to a real clock, one
``jax.monitoring`` listener records every backend compile inside an open
step as a ``compile`` complete event of its measured duration.

Determinism contract: with tracing ON, serving must stay token-identical
to an untraced run (the tracer only reads state), and two seeded
``VirtualClock`` runs must produce byte-identical exports: under a
virtual clock nothing here consults wall time, object ids, or unordered
iteration — events serialize in append order with sorted keys. On a
``WallClock`` the durations are measured, so exports differ run to run.

Zero-overhead contract: ``NULL_TRACER`` is a singleton with
``enabled = False``; each site guards with ``tracer.enabled`` (a step
site uses the shared ``NULL_STEP`` when it is off), so tracing off costs
one attribute load per site and creates no span object, no profiler
annotation and no clock read.

Export is the Chrome trace-event JSON format (the ``traceEvents`` array
of ``ph: "X"/"i"`` dicts) readable by Perfetto (https://ui.perfetto.dev)
and ``chrome://tracing``; ``pid`` is the replica id and ``tid`` the
stage/lane within it, so the timeline groups by replica.
"""
from __future__ import annotations

import contextlib
import json
import weakref
from typing import List, Optional, Sequence

import jax.monitoring
from jax.profiler import TraceAnnotation

# one trace-time unit (clock seconds) = 1e6 Chrome microseconds
_US = 1_000_000

# profiler annotations of real-clock steps are named PREFIX + step name
ANNOTATION_PREFIX = "hex:"

# span taxonomy (docs/observability.md mirrors this table)
SPAN_NAMES = (
    "queue_wait",        # admit: arrival -> start_time
    "iteration",         # one worker iteration (step)
    "admit",             # batcher: queued requests placed into slots
    "schedule",          # batcher: block growth, preemption, table build
    "sample",            # batcher: host argmax / token append, stops, frees
    "prefill",           # prompt tokens computed this iteration (chunk)
    "insert",            # model step: one-shot joint prefill call
    "context",           # model step: paged context-prefill call
    "decode",            # model step: one decode call over the batch
                         # (paged: args.programs, the programs it ran)
    "embed",             # model step: token embedding gather (insert,
                         # context; the paged decode gathers in `stage`)
    "stage",             # model step: one stage's upload + jit dispatch
    "head",              # model step: final norm + output head (insert,
                         # context; the paged decode's last `stage`)
    "to_host",           # model step: blocking copy of logits to host
    "compile",           # backend compile inside an open step
    "spec_propose",      # draft tokens proposed
    "spec_verify",       # multi-token verification step
    "spec_rollback",     # rejected-draft KV truncation
    "preempt",           # slot evicted (instant) + recompute accounted
    "host_spill",        # device -> host page demotion
    "host_promote",      # host -> device page swap-in
    "prefix_fetch",      # cluster prefix-directory block migration
    "kv_migration",      # disaggregated prefill -> decode KV handoff
    "live_move",         # online-resched live slot extraction
    "replica_kill",      # rescheduler killed a replica (instant)
)

# ``jax.named_scope`` names in the model programs: a device operation's
# HLO metadata (``op_name``) carries the innermost one around it
SCOPE_NAMES = (
    "embed",             # token embedding gather
    "attn_proj",         # q/k/v and output projections (weights)
    "attention",         # rope, paged K/V write and gather, scores,
                         # softmax, value sum
    "mlp",               # gate/up/down projections and activation
    "norm",              # RMS/layer norms
    "head",              # output head (logits)
)

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Span:
    """An open begin/end interval; closed by ``Tracer.end``."""

    __slots__ = ("name", "ts", "pid", "tid", "args")

    def __init__(self, name: str, ts: float, pid: int, tid: int,
                 args: Optional[dict]):
        self.name = name
        self.ts = ts
        self.pid = pid
        self.tid = tid
        self.args = args


class Step:
    """One piece of work timed by ``Tracer.step`` (a context manager).
    ``set`` adds args, or the virtual cost, known only once the work has
    run."""

    __slots__ = ("tracer", "name", "virtual", "pid", "tid", "args", "id",
                 "parent", "ts", "_annot")

    def __init__(self, tracer: "Tracer", name: str,
                 virtual: Optional[float], pid: Optional[int],
                 tid: Optional[int], args: dict):
        self.tracer = tracer
        self.name = name
        self.virtual = virtual
        self.pid = pid
        self.tid = tid
        self.args = args

    def set(self, virtual: Optional[float] = None, **args) -> None:
        if virtual is not None:
            self.virtual = virtual
        self.args.update(args)

    def __enter__(self) -> "Step":
        tr = self.tracer
        tr._ids += 1
        self.id = tr._ids
        up = tr._stack[-1] if tr._stack else None
        self.parent = up.id if up is not None else None
        if self.pid is None:
            self.pid = up.pid if up is not None else 0
        if self.tid is None:
            self.tid = up.tid if up is not None else 0
        tr._stack.append(self)
        if tr.real:
            self._annot = TraceAnnotation(ANNOTATION_PREFIX + self.name)
            self._annot.__enter__()
        self.ts = tr.now()
        return self

    def __exit__(self, *exc) -> bool:
        tr = self.tracer
        if tr.real:
            dur = tr.now() - self.ts
            self._annot.__exit__(None, None, None)
        else:
            dur = self.virtual
        tr._stack.pop()
        args = self.args
        args["id"] = self.id
        if self.parent is not None:
            args["parent"] = self.parent
        tr.events.append({"name": self.name, "ph": "X", "ts": self.ts,
                          "dur": dur, "pid": self.pid, "tid": self.tid,
                          "args": args})
        return False


class _NullStep:
    """The step of a site whose tracing is off: enters and records
    nothing."""

    __slots__ = ()

    def set(self, virtual=None, **args) -> None:
        pass

    def __enter__(self) -> "_NullStep":
        return self

    def __exit__(self, *exc) -> bool:
        return False


NULL_STEP = _NullStep()


class _Compiles:
    """Backend compiles on the timeline of the tracer last bound to a real
    clock. jax.monitoring listeners cannot be removed, so one is
    registered per process and forwards to that tracer."""

    tracer = None                   # weakref to the tracer, or None
    registered = False

    @classmethod
    def watch(cls, tracer: "Tracer") -> None:
        cls.tracer = weakref.ref(tracer)
        if not cls.registered:
            cls.registered = True
            jax.monitoring.register_event_duration_secs_listener(
                cls.on_duration)

    @classmethod
    def on_duration(cls, event: str, secs: float, **_kw) -> None:
        tr = cls.tracer() if cls.tracer is not None else None
        if event != _COMPILE_EVENT or tr is None or not tr.real \
                or not tr._stack:
            return
        top = tr._stack[-1]
        tr.complete("compile", secs, ts=max(tr.now() - secs, 0.0),
                    pid=top.pid, tid=top.tid)


class Tracer:
    """Collects trace events against a serving clock.

    Construct once per serve, ``bind_clock`` when the loop picks its
    clock (the serve loop does this), and hand the same instance to every
    engine. ``enabled`` is True; the NULL_TRACER stand-in is the off
    switch, so instrumentation sites never branch on a None check.
    """

    enabled = True

    def __init__(self, clock=None):
        self.events: List[dict] = []
        self._open = 0                 # begun-but-unended spans
        self._stack: List[Step] = []   # steps open now, innermost last
        self._ids = 0                  # the last step id handed out
        self.real = False
        self._clock = None
        if clock is not None:
            self.bind_clock(clock)

    # -- clock ------------------------------------------------------------
    def bind_clock(self, clock) -> None:
        """Ride ``clock``. A clock whose ``virtual`` attribute is False
        (``WallClock``) is real: steps then measure their durations and
        enter profiler annotations, and backend compiles are recorded."""
        self._clock = clock
        self.real = not getattr(clock, "virtual", True)
        if self.real:
            _Compiles.watch(self)

    def now(self) -> float:
        return self._clock.now() if self._clock is not None else 0.0

    # -- emission ---------------------------------------------------------
    def step(self, name: str, *, virtual: Optional[float] = None,
             pid: Optional[int] = None, tid: Optional[int] = None, **args):
        """A context manager timing one piece of work (see the module
        docstring); ``virtual`` is its cost on a virtual clock, None for
        work the virtual clock does not charge. ``pid``/``tid`` default
        to those of the step open around it (else 0)."""
        if virtual is None and not self.real:
            return NULL_STEP
        return Step(self, name, virtual, pid, tid, args)

    def _with_parent(self, args: dict) -> dict:
        if self._stack:
            args["parent"] = self._stack[-1].id
        return args

    def complete(self, name: str, dur: float, *, ts: Optional[float] = None,
                 pid: int = 0, tid: int = 0, **args) -> None:
        """Record a finished interval with an explicit duration."""
        ev = {"name": name, "ph": "X",
              "ts": self.now() if ts is None else ts,
              "dur": dur, "pid": pid, "tid": tid}
        args = self._with_parent(args)
        if args:
            ev["args"] = args
        self.events.append(ev)

    def instant(self, name: str, *, ts: Optional[float] = None,
                pid: int = 0, tid: int = 0, **args) -> None:
        ev = {"name": name, "ph": "i",
              "ts": self.now() if ts is None else ts,
              "pid": pid, "tid": tid, "s": "t"}
        args = self._with_parent(args)
        if args:
            ev["args"] = args
        self.events.append(ev)

    def begin(self, name: str, *, pid: int = 0, tid: int = 0,
              **args) -> Span:
        """Open a clock-sampled span; MUST be closed with ``end`` on the
        same code path (repro-lint: span-pairing)."""
        self._open += 1
        return Span(name, self.now(), pid, tid, args or None)

    def end(self, span: Span, **args) -> None:
        self._open -= 1
        merged = dict(span.args) if span.args else {}
        merged.update(args)
        ev = {"name": span.name, "ph": "X", "ts": span.ts,
              "dur": self.now() - span.ts, "pid": span.pid,
              "tid": span.tid}
        if merged:
            ev["args"] = merged
        self.events.append(ev)

    @contextlib.contextmanager
    def span(self, name: str, *, pid: int = 0, tid: int = 0, **args):
        s = self.begin(name, pid=pid, tid=tid, **args)
        try:
            yield s
        finally:
            self.end(s)

    # -- export -----------------------------------------------------------
    def to_chrome(self) -> dict:
        """The Chrome trace-event JSON object (ts/dur in microseconds)."""
        out = []
        for ev in self.events:
            d = dict(ev)
            d["ts"] = round(d["ts"] * _US)
            if "dur" in d:
                d["dur"] = round(d["dur"] * _US)
            out.append(d)
        return {"traceEvents": out, "displayTimeUnit": "ms",
                "otherData": {"producer": "repro.obs.trace",
                              "openSpans": self._open}}

    def dumps(self) -> str:
        """Byte-deterministic serialization (sorted keys, fixed
        separators, append-ordered events)."""
        return json.dumps(self.to_chrome(), sort_keys=True,
                          separators=(",", ":"))

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.dumps())
            f.write("\n")


class _NullTracer(Tracer):
    """Tracing off: every emission is a no-op; ``enabled`` is False so
    hot paths can skip argument construction entirely."""

    enabled = False

    def __init__(self):
        super().__init__()

    def complete(self, name, dur, *, ts=None, pid=0, tid=0, **args):
        pass

    def instant(self, name, *, ts=None, pid=0, tid=0, **args):
        pass

    def begin(self, name, *, pid=0, tid=0, **args):
        return _NULL_SPAN

    def end(self, span, **args):
        pass

    def step(self, name, *, virtual=None, pid=None, tid=None, **args):
        return NULL_STEP


_NULL_SPAN = Span("", 0.0, 0, 0, None)
NULL_TRACER = _NullTracer()


# ---------------------------------------------------------------------------
# Chrome-trace schema validation (ci.sh trace smoke, tests)
# ---------------------------------------------------------------------------

def validate_chrome_trace(obj, *, require_spans: Sequence[str] = ()
                          ) -> List[str]:
    """Structural check of a Chrome trace-event JSON object. Returns a
    list of problems (empty = valid). ``require_spans`` additionally
    demands at least one event with each given name."""
    errs: List[str] = []
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        return ["top level must be an object with a 'traceEvents' array"]
    evs = obj["traceEvents"]
    if not isinstance(evs, list):
        return ["'traceEvents' must be an array"]
    names = set()
    for i, ev in enumerate(evs):
        where = f"traceEvents[{i}]"
        if not isinstance(ev, dict):
            errs.append(f"{where}: not an object")
            continue
        for k in ("name", "ph", "ts", "pid", "tid"):
            if k not in ev:
                errs.append(f"{where}: missing '{k}'")
        ph = ev.get("ph")
        if ph not in ("X", "B", "E", "i", "I", "M", "C"):
            errs.append(f"{where}: unknown phase {ph!r}")
        if ph == "X" and "dur" not in ev:
            errs.append(f"{where}: complete event missing 'dur'")
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)) or ts < 0:
            errs.append(f"{where}: bad ts {ts!r}")
        if "dur" in ev and (not isinstance(ev["dur"], (int, float))
                            or ev["dur"] < 0):
            errs.append(f"{where}: bad dur {ev['dur']!r}")
        if isinstance(ev.get("name"), str):
            names.add(ev["name"])
    open_spans = (obj.get("otherData") or {}).get("openSpans", 0)
    if open_spans:
        errs.append(f"{open_spans} span(s) begun but never ended")
    for want in require_spans:
        if want not in names:
            errs.append(f"no '{want}' span in trace")
    return errs
