"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

The benchmark marks each call into the served path with a host annotation
``bench:<kind>#<index>`` (``bench_after:...`` once the window has closed).
The traced window runs from the start of the first ``bench:`` annotation
to the end of the last. Within it, per chip:

* busy time: the union of the intervals in which an operation ran on the
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane);
* device time per program (``XLA Modules`` line) and per operation;
* busy time inside each annotated call;
* idle time, split by what the host was doing: inside a call of each
  kind, or ``serve_loop`` outside every call.

Chip figures are averaged over the chips that ran any operation.

The device planes and the host plane keep their own clocks: on a v5e the
device's timestamps lie about a millisecond before the host's. Each chip's
timeline is first shifted by the offset that puts the most of its programs
wholly inside an annotated call (every call waits for its results, so each
program it launched runs inside it).
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Optional

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOT = re.compile(r"^(bench|bench_after):([a-z_]+)#(\d+)$")


def find(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return files[-1] if files else None


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge overlapping [start, end) intervals (sorted by start)."""
    if not len(iv):
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.array(out, dtype=np.float64)


def _clip_len(iv: np.ndarray, lo: float, hi: float) -> float:
    """Total length of merged intervals iv within [lo, hi)."""
    if not len(iv):
        return 0.0
    s = np.clip(iv[:, 0], lo, hi)
    e = np.clip(iv[:, 1], lo, hi)
    return float(np.sum(e - s))


def load(path: str) -> Dict:
    """The events the reduction reads: per device plane its ops and
    programs, and the benchmark's host annotations (all in ns)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    chips, annots = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(e.name, e.start_ns, e.duration_ns)
                           for e in line.events]
                elif line.name == MODULES_LINE:
                    mods = [(e.name, e.start_ns, e.duration_ns)
                            for e in line.events]
            if ops:
                chips[plane.name] = {"ops": ops, "modules": mods}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    m = ANNOT.match(e.name)
                    if m:
                        annots.append((m.group(1), m.group(2),
                                       int(m.group(3)), e.start_ns,
                                       e.start_ns + e.duration_ns))
    annots.sort(key=lambda a: a[3])
    return {"chips": chips, "annots": annots}


def clock_offset(modules, annots, span_ns: float = 10e6,
                 step_ns: float = 10e3) -> float:
    """The shift (ns) to add to a chip's timestamps to put them on the
    host's clock: of the shifts within ``span_ns`` either way, the middle of
    those that put the most programs wholly inside an annotated call."""
    if not modules or not annots:
        return 0.0
    a_lo = np.array([a[3] for a in annots], np.float64)
    a_hi = np.array([a[4] for a in annots], np.float64)
    order = np.argsort(a_lo)
    a_lo, a_hi = a_lo[order], a_hi[order]
    s = np.array([m[1] for m in modules], np.float64)
    e = s + np.array([m[2] for m in modules], np.float64)
    near = (s > a_lo[0] - 2 * span_ns) & (e < a_hi[-1] + 2 * span_ns)
    s, e = s[near], e[near]
    shifts = np.arange(-span_ns, span_ns + step_ns / 2, step_ns)
    inside = np.empty(len(shifts), np.int64)
    for j, d in enumerate(shifts):
        i = np.searchsorted(a_lo, s + d, side="right") - 1
        ok = i >= 0
        inside[j] = np.count_nonzero(ok & (e + d <= a_hi[np.maximum(i, 0)]))
    best = shifts[inside == inside.max()]
    return float(np.median(best))


def reduce(events: Dict, top: int = 10) -> Optional[Dict]:
    """Device metrics of the traced window, or None where the trace holds
    no device operation or no annotated call inside the window."""
    inside = [a for a in events["annots"] if a[0] == "bench"]
    chips = events["chips"]
    if not inside or not chips:
        return None
    lo = min(a[3] for a in inside)
    hi = max(a[4] for a in inside)
    n = len(chips)
    busy, per_call = 0.0, {}
    ops: Dict[str, float] = {}
    mods: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    a_lo = np.array([a[3] for a in events["annots"]], np.float64)
    a_hi = np.array([a[4] for a in events["annots"]], np.float64)
    kinds = [a[1] for a in events["annots"]]
    offsets = []
    for chip in chips.values():
        off = clock_offset(chip["modules"], events["annots"])
        offsets.append(off)
        chip = {k: [(name, s + off, d) for name, s, d in v]
                for k, v in chip.items()}
        iv = np.array([(s, s + d) for _, s, d in chip["ops"]], np.float64)
        merged = _union(iv)
        busy += _clip_len(merged, lo, hi)
        for a in inside:
            per_call[a[2]] = per_call.get(a[2], 0.0) + _clip_len(
                merged, a[3], a[4])
        for name, s, d in chip["ops"]:
            if lo <= s < hi:
                ops[name] = ops.get(name, 0.0) + d
        for name, s, d in chip["modules"]:
            if lo <= s < hi:
                mods[name] = mods.get(name, 0.0) + d
        # idle gaps between merged busy intervals, inside the window, split
        # by the calls they overlap; the rest is the serve loop's own time
        edges = np.concatenate([[lo], merged.ravel(), [hi]]).reshape(-1, 2)
        for s, e in edges:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            over = np.clip(np.minimum(e, a_hi) - np.maximum(s, a_lo), 0,
                           None)
            for i in np.flatnonzero(over):
                gaps[kinds[i]] = gaps.get(kinds[i], 0.0) + over[i]
            loop = (e - s) - over.sum()
            if loop > 0:
                gaps["serve_loop"] = gaps.get("serve_loop", 0.0) + loop

    def ranked(d):
        return [[k, v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"chips": n, "window_s": (hi - lo) / 1e9, "busy_s": busy / n / 1e9,
            "call_busy_s": {i: v / n / 1e9 for i, v in per_call.items()},
            "device_ops": ranked(ops), "programs": ranked(mods),
            "idle_gaps": ranked(gaps), "clock_offsets_s": [
                o / 1e9 for o in offsets]}


def reduce_dir(trace_dir: str) -> Optional[Dict]:
    path = find(trace_dir)
    return reduce(load(path)) if path else None
