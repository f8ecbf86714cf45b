"""The program's own HexTrace spans and named scopes in a profiler trace.

While the program's ``Tracer`` is bound to a real clock, each of its steps
enters a profiler annotation ``hex:<name>`` (``iteration``, ``admit``,
``schedule``, ``sample``, ``prefill``, ``insert``, ``context``, ``decode``,
``embed``, ``stage``, ``head``, ``to_host``), so they land on the host
plane of the ``.xplane.pb``, nested as the program ran them. Each device
operation's scope (``jax.named_scope``: ``embed``, ``attn_proj``,
``attention``, ``mlp``, ``norm``, ``head``) is read from the HLO metadata
of its program, which the trace keeps in its ``/host:metadata`` plane.

``load`` adds both to what ``trace.load`` reads; ``reduce`` returns, over
the window ``trace.reduce`` uses (and on the same host clock):

* ``idle_by_span``: device idle time by the innermost ``hex:`` span the
  host was in, with ``unattributed`` for the rest; the whole sums to the
  window's idle time;
* ``hex_decode``: each ``hex:decode`` span's length and the device's busy
  time inside it;
* ``scopes``: device time by scope (``unscoped`` for operations without
  one); ``decode_scopes`` the same for operations inside ``hex:decode``
  spans, and ``decode_program_scopes`` for the program that takes most of
  the device time there; ``decode_inherited`` the part of
  ``decode_scopes`` whose scope came from a consuming operation, and
  ``decode_unscoped_ops`` the operations there that have none.

Times are in seconds, averaged over the chips. A trace without ``hex:``
spans (a program that lacks them) gives None.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional

import numpy as np

from harness import trace

HEX = "hex:"
SCOPES = ("embed", "attn_proj", "attention", "mlp", "norm", "head")
UNSCOPED = "unscoped"
UNATTRIBUTED = "unattributed"
_INSTR = re.compile(r"^%?([^\s=]+)")


# ---- a minimal protobuf reader: the fields of the XSpace it needs -------

def _varint(b, i: int):
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return r, i


def _fields(b):
    """(field number, value) of a serialized message: ints for varints,
    memoryviews for length-delimited fields; fixed-width ones skipped."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        f, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire == 1:
            v, i = None, i + 8
        elif wire == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield f, v


def _first(b, field: int):
    for f, v in _fields(b):
        if f == field:
            return v
    return None


def _varints(v) -> List[int]:
    """A repeated integer field's values: packed (bytes) or one varint."""
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def _scopes(hlo) -> Dict[str, tuple]:
    """Instruction name -> (scope, inherited) for every instruction of a
    serialized HloProto (module 1 > computations 3 > instructions 2 >
    name 1, metadata 7 > op_name 2, id 35, operand_ids 36). An
    instruction whose own op_name names no scope (a copy the compiler put
    in to lay a weight out for its matmul, say) takes the scope of the
    nearest instruction that consumes it, marked inherited."""
    out = {}
    module = _first(hlo, 1)
    if module is None:
        return out
    for f, comp in _fields(module):
        if f != 3:
            continue
        name, scope, users = {}, {}, {}
        for g, ins in _fields(comp):
            if g != 2:
                continue
            iid, nm, op, operands = None, None, None, []
            for h, v in _fields(ins):
                if h == 1:
                    nm = bytes(v).decode()
                elif h == 7:
                    op = _first(v, 2)
                elif h == 35:
                    iid = v
                elif h == 36:
                    operands += _varints(v)
            if nm is None:
                continue
            name[iid] = nm
            scope[iid] = scope_of(bytes(op).decode() if op else None)
            for o in operands:
                users.setdefault(o, []).append(iid)
        for iid, nm in name.items():
            own = scope[iid]
            if own != UNSCOPED:
                out[nm] = (own, False)
                continue
            seen, todo, found = {iid}, list(users.get(iid, [])), UNSCOPED
            while todo:
                u = todo.pop(0)
                if u in seen:
                    continue
                seen.add(u)
                if scope.get(u, UNSCOPED) != UNSCOPED:
                    found = scope[u]
                    break
                todo += users.get(u, [])
            out[nm] = (found, found != UNSCOPED)
    return out


def program_scopes(path: str) -> Dict[str, Dict[str, tuple]]:
    """Program name (as on the ``XLA Modules`` line) -> instruction name
    -> (scope, inherited), from the HLO protos of the ``/host:metadata``
    plane."""
    with open(path, "rb") as fh:
        data = memoryview(fh.read())
    out = {}
    for f, plane in _fields(data):            # XSpace.planes = 1
        if f != 1 or bytes(_first(plane, 2) or b"") != b"/host:metadata":
            continue
        stat_names = {}                        # XPlane.stat_metadata = 5
        for g, entry in _fields(plane):
            if g == 5:
                sm = _first(entry, 2)
                stat_names[_first(sm, 1)] = bytes(_first(sm, 2)).decode()
        for g, entry in _fields(plane):        # XPlane.event_metadata = 4
            if g != 4:
                continue
            em = _first(entry, 2)
            name = bytes(_first(em, 2) or b"").decode()
            for h, stat in _fields(em):        # XEventMetadata.stats = 5
                if h != 5:
                    continue
                sid, blob = None, None
                for k, v in _fields(stat):     # metadata_id 1, bytes 6
                    if k == 1:
                        sid = v
                    elif k == 6:
                        blob = v
                if blob is not None and stat_names.get(sid) == "Hlo Proto":
                    out[name] = _scopes(blob)
    return out


def scope_of(op_name: Optional[str]) -> str:
    """The innermost named scope in an op_name path, else ``unscoped``."""
    if not op_name:
        return UNSCOPED
    for part in reversed(re.split(r"[/;]", op_name)):
        if part in SCOPES:
            return part
    return UNSCOPED


# ---- loading --------------------------------------------------------------

def load(path: str) -> Dict:
    """``trace.load`` plus the ``hex:`` host spans (``hex``: name without
    the prefix, start, end in ns) and each operation's program, scope and
    whether the scope was inherited from a consumer (``op_meta``: by chip,
    a list parallel to the chip's ``ops``)."""
    from jax.profiler import ProfileData
    events = trace.load(path)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HEX):
                        spans.append((e.name[len(HEX):], e.start_ns,
                                      e.start_ns + e.duration_ns))
    spans.sort(key=lambda sp: (sp[1], -sp[2]))
    events["hex"] = spans
    scopes = program_scopes(path)
    events["op_meta"] = {}
    for key, chip in events["chips"].items():
        mods = sorted(chip["modules"], key=lambda m: m[1])
        m_lo = np.array([m[1] for m in mods], np.float64)
        m_hi = m_lo + np.array([m[2] for m in mods], np.float64)
        starts = np.array([s for _, s, _ in chip["ops"]], np.float64)
        js = np.searchsorted(m_lo, starts, side="right") - 1
        meta = []
        for (op, s, _), j in zip(chip["ops"], js):
            prog = mods[j][0] if j >= 0 and s < m_hi[j] else ""
            m = _INSTR.match(op)
            instr = m.group(1) if m else op
            meta.append((prog, *scopes.get(prog, {}).get(
                instr, (UNSCOPED, False))))
        events["op_meta"][key] = meta
    return events


# ---- reduction ------------------------------------------------------------

def innermost(spans) -> List:
    """Non-overlapping (name, start, end) segments, each labelled by the
    innermost of the nested spans covering it; spans sorted by (start,
    -end). A span that outlasts the one around it is cut at its end."""
    segs, stack = [], []                      # stack: [name, end]
    cur = None

    def close_until(t):
        nonlocal cur
        while stack and stack[-1][1] <= t:
            name, end = stack.pop()
            if end > cur:
                segs.append((name, cur, end))
            cur = max(cur, end)

    for name, s, e in spans:
        if cur is not None:
            close_until(s)
            if stack and s > cur:
                segs.append((stack[-1][0], cur, s))
        if stack:
            e = min(e, stack[-1][1])
        stack.append([name, e])
        cur = s if cur is None else max(cur, s)
    if stack:
        close_until(float("inf"))
    return segs


def reduce(events: Dict, top: int = 16) -> Optional[Dict]:
    """The figures of the module docstring, or None where the trace holds
    no ``hex:`` span, no device operation or no annotated call."""
    inside = [a for a in events["annots"] if a[0] == "bench"]
    chips, spans = events["chips"], events.get("hex") or []
    if not inside or not chips or not spans:
        return None
    lo = min(a[3] for a in inside)
    hi = max(a[4] for a in inside)
    n = len(chips)
    segs = innermost(spans)
    s_lo = np.array([g[1] for g in segs], np.float64)
    s_hi = np.array([g[2] for g in segs], np.float64)
    labels = [g[0] for g in segs]
    decode = [(s, e) for name, s, e in spans
              if name == "decode" and lo <= s < hi]
    d_lo = np.array([s for s, _ in decode], np.float64)
    d_hi = np.array([e for _, e in decode], np.float64)
    idle: Dict[str, float] = {}
    busy_in = np.zeros(len(decode))
    scopes: Dict[str, float] = {}
    dscopes: Dict[str, float] = {}
    dprog: Dict[str, Dict[str, float]] = {}
    inherited: Dict[str, float] = {}
    unscoped_ops: Dict[str, float] = {}
    op_meta = events.get("op_meta") or {}
    for key, chip in chips.items():
        off = trace.clock_offset(chip["modules"], events["annots"])
        iv = np.array([(s + off, s + off + d) for _, s, d in chip["ops"]],
                      np.float64)
        merged = trace._union(iv)
        for k, (s, e) in enumerate(decode):
            busy_in[k] += trace._clip_len(merged, s, e)
        meta = (op_meta.get(key)
                or [("", UNSCOPED, False)] * len(chip["ops"]))
        for (name, s, d), (prog, scope, inh) in zip(chip["ops"], meta):
            s += off
            if not lo <= s < hi:
                continue
            scopes[scope] = scopes.get(scope, 0.0) + d
            k = int(np.searchsorted(d_lo, s, side="right")) - 1
            if k >= 0 and s < d_hi[k]:
                dscopes[scope] = dscopes.get(scope, 0.0) + d
                p = dprog.setdefault(prog, {})
                p[scope] = p.get(scope, 0.0) + d
                if inh:
                    inherited[scope] = inherited.get(scope, 0.0) + d
                elif scope == UNSCOPED:
                    unscoped_ops[name[:120]] = (unscoped_ops.get(
                        name[:120], 0.0) + d)
        # idle gaps between merged busy intervals inside the window, split
        # by the innermost hex: span they overlap
        edges = np.concatenate([[lo], merged.ravel(), [hi]]).reshape(-1, 2)
        for s, e in edges:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            i0 = int(np.searchsorted(s_hi, s, side="right"))
            i1 = int(np.searchsorted(s_lo, e, side="left"))
            covered = 0.0
            for i in range(i0, i1):
                over = min(e, s_hi[i]) - max(s, s_lo[i])
                if over > 0:
                    idle[labels[i]] = idle.get(labels[i], 0.0) + over
                    covered += over
            if (e - s) - covered > 0:
                idle[UNATTRIBUTED] = (idle.get(UNATTRIBUTED, 0.0)
                                      + (e - s) - covered)

    def ranked(d):
        return [[k, v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    main = max(dprog, key=lambda p: sum(dprog[p].values()), default=None)
    return {"idle_by_span": ranked(idle),
            "hex_decode": [[(e - s) / 1e9, b / n / 1e9]
                           for (s, e), b in zip(decode, busy_in)],
            "scopes": ranked(scopes),
            "decode_scopes": {k: v / n / 1e9 for k, v in dscopes.items()},
            "decode_program": main,
            "decode_program_scopes": {
                k: v / n / 1e9 for k, v in dprog.get(main, {}).items()},
            "decode_inherited": {k: v / n / 1e9
                                 for k, v in inherited.items()},
            "decode_unscoped_ops": ranked(unscoped_ops)}


def reduce_path(path: str) -> Optional[Dict]:
    """``trace.reduce`` and ``reduce`` of one ``.xplane.pb``, merged; None
    where ``trace.reduce`` finds nothing to read."""
    events = load(path)
    out = trace.reduce(events)
    if out is not None:
        out.update(reduce(events) or {})
    return out
