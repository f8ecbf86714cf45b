#!/usr/bin/env python3
"""Record a small profiler trace of the served path with the program's own
tracer on, for the tests of ``harness/program_trace.py``
(``tests/data/hex_trace.xplane.pb``, ``tests/data/hex_trace.spans.json``).

    python3 bench/record_hex_trace.py OUT_DIR

On the device JAX finds, the tests' tiny configuration
(``tests/data/tiny.json``) serves three requests on one paged engine with
two slots, on the wall clock, after a warm-up serve of the same shapes. The
served calls carry the recorder's annotations (``bench:<kind>#<i>``) and
the program's ``Tracer`` its spans (``hex:<name>``); the model's named
scopes are in the programs' metadata. Writes the ``.xplane.pb`` and the
tracer's events (``spans.json``: the events in seconds on the serve's
clock) under OUT_DIR and prints both paths.

The trace keeps only what the reductions read, so that it stays small: the
``XLA Ops`` and ``XLA Modules`` lines of the device planes (each event's
time, and its name cut to the instruction's), the ``bench:`` and ``hex:``
annotations of the host plane, and of the HLO of each program that
ran on a device and names a scope, the entry computation's instructions:
names, ids, operand ids, and of each op_name only its innermost scope.
"""
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

DEVICE_LINES = (b"XLA Ops", b"XLA Modules")
ANNOTATIONS = (b"bench:", b"bench_after:", b"hex:")


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _field(f: int, v) -> bytes:
    """One protobuf field: an int as a varint, bytes length-delimited."""
    if isinstance(v, int):
        return _varint(f << 3) + _varint(v)
    v = bytes(v)
    return _varint(f << 3 | 2) + _varint(len(v)) + v


def _slim_hlo(hlo) -> bytes:
    """An HloProto with only its entry computation, and of each instruction
    its name, id, operand ids and the innermost scope of its op_name."""
    from harness.program_trace import (UNSCOPED, _fields, _first,
                                       scope_of)
    module = _first(hlo, 1)
    comps = [c for f, c in _fields(module) if f == 3]
    # the entry computation by name, else the last (computations are kept
    # callees first)
    entry = bytes(_first(module, 2) or _first(comps[-1], 1) or b"")
    out = b""
    for comp in comps:
        if bytes(_first(comp, 1) or b"") != entry:
            continue
        body = _field(1, entry)
        for g, ins in _fields(comp):
            if g != 2:
                continue
            meta = _first(ins, 7)
            op = _first(meta, 2) if meta is not None else None
            scope = scope_of(bytes(op).decode() if op else None)
            keep = _field(1, _first(ins, 1) or b"")
            if scope != UNSCOPED:
                keep += _field(7, _field(2, scope.encode()))
            for h, v in _fields(ins):
                if h in (35, 36):                 # id, operand_ids
                    keep += _field(h, v)
            body += _field(2, keep)
        out += _field(3, body)
    return _field(1, _field(1, _first(module, 1) or b"") + _field(2, entry)
                  + out)


def _slim_event(event) -> bytes:
    """An XEvent with its metadata id and times, without its stats."""
    from harness.program_trace import _fields
    return b"".join(_field(f, v) for f, v in _fields(event)
                    if f != 4 and v is not None)


def _slim_line(line, keep=None) -> bytes:
    """An XLine whose events lose their stats; ``keep(metadata id)``
    chooses the events to keep (all by default)."""
    from harness.program_trace import _fields, _first
    out = b""
    for f, v in _fields(line):
        if f == 4:
            if keep is not None and not keep(_first(v, 1)):
                continue
            v = _slim_event(v)
        if v is not None:
            out += _field(f, v)
    return out


def _scoped(hlo) -> bool:
    """Whether an HloProto names any of the program's scopes."""
    from harness.program_trace import UNSCOPED, _scopes
    return any(s != UNSCOPED for s, _ in _scopes(hlo).values())


def prune(path: str) -> None:
    """Rewrite the ``.xplane.pb`` at ``path`` to what the reductions read
    (module docstring). Pruning a pruned trace changes nothing."""
    from harness.program_trace import _fields, _first
    data = memoryview(open(path, "rb").read())
    ran = set()                               # programs on a device plane
    for f, plane in _fields(data):
        if f == 1 and bytes(_first(plane, 2) or b"").startswith(
                b"/device:TPU:"):
            for g, v in _fields(plane):
                if g == 4:
                    ran.add(bytes(_first(_first(v, 2), 2) or b""))
    planes = b""
    for f, plane in _fields(data):
        if f != 1:                            # XSpace.planes
            continue
        name = bytes(_first(plane, 2) or b"")
        device = name.startswith(b"/device:TPU:")
        if not (device or name in (b"/host:CPU", b"/host:metadata")):
            continue
        names, hlo_stat = {}, None
        for g, v in _fields(plane):
            if g == 4:
                em = _first(v, 2)
                names[_first(v, 1)] = bytes(_first(em, 2) or b"")
            elif g == 5 and bytes(_first(_first(v, 2), 2)) == b"Hlo Proto":
                hlo_stat = _first(v, 1)
        out = b""
        for g, v in _fields(plane):
            if g == 3:
                line = bytes(_first(v, 2) or b"")
                events = [names.get(_first(e, 1), b"")
                          for h, e in _fields(v) if h == 4]
                keep = (line in DEVICE_LINES if device else
                        any(n.startswith(ANNOTATIONS) for n in events))
                if not keep:
                    continue
                v = _slim_line(v, None if device else lambda i: names.get(
                    i, b"").startswith(ANNOTATIONS))
            elif g == 4 and device:
                em = _first(v, 2)
                short = bytes(_first(em, 2) or b"").split(b" = ")[0]
                v = _field(1, _first(v, 1)) + _field(
                    2, _field(1, _first(em, 1)) + _field(2, short))
            elif g == 4 and name == b"/host:metadata":
                em = _first(v, 2)
                if bytes(_first(em, 2) or b"") not in ran:
                    continue
                body, scoped = b"", False
                for h, x in _fields(em):
                    if h == 5 and _first(x, 1) == hlo_stat:
                        hlo = _slim_hlo(_first(x, 6))
                        scoped = _scoped(memoryview(hlo))
                        x = _field(1, hlo_stat) + _field(6, hlo)
                    body += _field(h, x) if x is not None else b""
                if not scoped:
                    continue
                v = _field(1, _first(v, 1)) + _field(2, body)
                out += _field(g, v)
                continue
            if v is not None:
                out += _field(g, v)
        planes += _field(1, out)
    open(path, "wb").write(planes)


def main(out: str) -> int:
    import jax
    import numpy as np

    from harness import cell, taps, trace
    from repro.models import model as M
    from repro.obs.trace import Tracer
    from repro.serving.continuous import PagedPipelineBatcher
    from repro.serving.loop import WallClock, run_serve_loop
    from repro.serving.pipeline import AsymmetricPipeline
    from repro.serving.request import Request

    cfg = cell.model_config(json.loads(
        (BENCH / "tests" / "data" / "tiny.json").read_text()))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    pipe = AsymmetricPipeline(cfg, params, [cfg.num_layers],
                              [[jax.devices()[0]]])
    engine = PagedPipelineBatcher(pipe, n_slots=2, max_len=64,
                                  block_size=16)
    rng = np.random.default_rng(0)

    def requests():
        return [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 24,
                                                   dtype=np.int32),
                        max_new_tokens=4) for i in range(3)]

    run_serve_loop([engine], requests(), deadline=1e9, clock=WallClock())
    rec = taps.Recorder([engine], annotate=True)
    tracer = Tracer()
    engine.tracer = tracer
    clock = WallClock()
    reqs = requests()
    rec.start(clock, reqs, window=1e9, stop_at=1e9)
    jax.profiler.start_trace(out, profiler_options=cell.profiler_options())
    run_serve_loop([engine], reqs, deadline=1e9, clock=clock, tracer=tracer)
    jax.profiler.stop_trace()
    rec.finish()
    prune(trace.find(out))
    spans = Path(out) / "spans.json"
    spans.write_text(json.dumps({"events": tracer.events}))
    print(trace.find(out))
    print(spans)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
