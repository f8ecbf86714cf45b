"""The program's own spans and scopes in the benchmark's view: the tracer's
call spans against the recorder's taps on the wall clock; the reduction of
``hex:`` spans and named scopes (``harness/program_trace.py``) on events
made by hand, on the recorded v5e trace of the benchmark's first round
(which has neither, so its figures stay as they were) and on a small v5e
trace recorded with the program's tracer on (``record_hex_trace.py``); and
the three readers that need them."""
import json
import math
import types
from pathlib import Path

import jax
import numpy as np
import pytest

from harness import cell, program_trace, taps, trace
from harness.spec import Spec

DATA = Path(__file__).parent / "data"
OLD = DATA / "tpu_trace.xplane.pb"
HEX = DATA / "hex_trace.xplane.pb"
SPANS = DATA / "hex_trace.spans.json"
MS = 1_000_000                     # ns
READERS = ("decode_idle_ms.online", "iteration_host_ms.online",
           "decode_attention_ms.online")


def reader(name):
    return Spec(Path(__file__).resolve().parents[2]).reader(name)


# ---- the tracer's calls against the recorder's taps ------------------------

def served(chunk: int):
    """The tiny configuration served on the wall clock by one paged engine
    wrapped by the recorder, with the program's tracer on."""
    from repro.models import model as M
    from repro.obs.trace import Tracer
    from repro.serving.continuous import PagedPipelineBatcher
    from repro.serving.loop import WallClock, run_serve_loop
    from repro.serving.pipeline import AsymmetricPipeline
    from repro.serving.request import Request
    cfg = cell.model_config(json.loads((DATA / "tiny.json").read_text()))
    pipe = AsymmetricPipeline(cfg, M.init_params(cfg, jax.random.PRNGKey(1)),
                              [cfg.num_layers], [jax.devices()[:1]])
    eng = PagedPipelineBatcher(pipe, n_slots=3, max_len=64, block_size=16,
                               prefill_chunk=chunk)
    rng = np.random.default_rng(2)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 10 + 7 * i,
                                               dtype=np.int32),
                    max_new_tokens=4, arrival=0.002 * i) for i in range(5)]
    rec = taps.Recorder([eng])
    tracer = Tracer()
    eng.tracer = tracer
    clock = WallClock()
    rec.start(clock, reqs, window=1e9, stop_at=1e9)
    run_serve_loop([eng], reqs, deadline=1e9, clock=clock, tracer=tracer)
    rec.finish()
    return rec.calls, tracer.events


@pytest.mark.parametrize("chunk", [0, 16])
def test_call_spans_match_the_taps(chunk):
    """Each tapped call is one ``decode``, ``insert`` or ``context`` span of
    the program's tracer, in the same order, within 1 ms at both ends,
    with the same rows, prompt tokens and context tokens."""
    calls, events = served(chunk)
    spans = [e for e in events if e["name"] in ("decode", "insert",
                                                "context")]
    assert [k.kind for k in calls] == [e["name"] for e in spans]
    assert {k.kind for k in calls} == (
        {"decode", "context"} if chunk else {"decode", "insert"})
    for k, e in zip(calls, spans):
        a = e["args"]
        assert abs(k.t0 - e["ts"]) < 1e-3
        assert abs(k.t1 - (e["ts"] + e["dur"])) < 1e-3
        assert e["ts"] <= k.t0 and k.t1 <= e["ts"] + e["dur"]
        assert (a["rows"], a.get("tokens", 0), a.get("ctx_tokens", 0)) == \
            (k.rows, k.tokens, k.ctx_tokens)
        assert len(a["rids"]) == k.rows


# ---- the reduction on events made by hand ----------------------------------

def test_innermost_labels_each_stretch_by_the_deepest_span():
    spans = [("iteration", 0, 10), ("decode", 2, 5), ("stage", 3, 4),
             ("sample", 6, 8)]
    assert program_trace.innermost(spans) == [
        ("iteration", 0, 2), ("decode", 2, 3), ("stage", 3, 4),
        ("decode", 4, 5), ("iteration", 5, 6), ("sample", 6, 8),
        ("iteration", 8, 10)]


def events():
    """One chip. The window is [0, 100) ms (bench:decode#0 [10, 40),
    bench:decode#1 [60, 90)); hex:iteration [0, 50) holds hex:decode
    [10, 40) which holds hex:stage [10, 20); hex:decode [60, 90) stands
    alone. The program runs through each call (so the device's clock needs
    no shift); the chip is busy [20, 35) (an attention op and an mlp op)
    and [70, 80) (an unscoped op)."""
    return {
        "chips": {"/device:TPU:0": {
            "ops": [("%a.1 = x", 20 * MS, 5 * MS),
                    ("%m.2 = x", 25 * MS, 10 * MS),
                    ("%u.3 = x", 70 * MS, 10 * MS)],
            "modules": [("jit_step(1)", 10 * MS, 30 * MS),
                        ("jit_step(1)", 60 * MS, 30 * MS)]}},
        "op_meta": {"/device:TPU:0": [("jit_step(1)", "attention", False),
                                      ("jit_step(1)", "mlp", True),
                                      ("jit_step(1)", "unscoped", False)]},
        "annots": [("bench", "decode", 0, 10 * MS, 40 * MS),
                   ("bench", "decode", 1, 60 * MS, 90 * MS)],
        "hex": [("iteration", 0, 50 * MS), ("decode", 10 * MS, 40 * MS),
                ("stage", 10 * MS, 20 * MS), ("decode", 60 * MS, 90 * MS)],
    }


def test_idle_is_split_by_the_innermost_span():
    ev = events()
    base = trace.reduce(ev)
    r = program_trace.reduce(ev)
    # the window is [10, 90): idle [10, 20) under stage, [35, 40) under
    # decode, [40, 50) under iteration, [50, 60) nothing, [60, 70) and
    # [80, 90) under the second decode
    assert dict(r["idle_by_span"]) == pytest.approx({
        "stage": 0.010, "decode": 0.025, "iteration": 0.010,
        "unattributed": 0.010})
    total = sum(v for _, v in r["idle_by_span"])
    assert total == pytest.approx(base["window_s"] - base["busy_s"])
    assert np.allclose(r["hex_decode"], [[0.030, 0.015], [0.030, 0.010]])
    assert r["decode_scopes"] == pytest.approx(
        {"attention": 0.005, "mlp": 0.010, "unscoped": 0.010})
    assert r["decode_program"] == "jit_step(1)"
    assert r["decode_inherited"] == pytest.approx({"mlp": 0.010})
    assert r["decode_unscoped_ops"] == [["%u.3 = x", pytest.approx(0.010)]]


def test_readers_on_events_made_by_hand():
    run = types.SimpleNamespace(trace={**trace.reduce(events()),
                                       **program_trace.reduce(events())})
    assert reader("decode_idle_ms.online")(run) == pytest.approx(17.5)
    assert reader("decode_attention_ms.online")(run) == pytest.approx(2.5)
    # iteration self time: 50 ms less the 30 ms decode inside it; the
    # second iteration starts after the window and is not counted
    run.seconds = 1.0
    run.spans = [
        {"name": "iteration", "ph": "X", "ts": 0.0, "dur": 0.050,
         "args": {"id": 1}},
        {"name": "decode", "ph": "X", "ts": 0.010, "dur": 0.030,
         "args": {"id": 2, "parent": 3}},
        {"name": "prefill", "ph": "X", "ts": 0.005, "dur": 0.040,
         "args": {"id": 3, "parent": 1}},
        {"name": "iteration", "ph": "X", "ts": 1.5, "dur": 0.5,
         "args": {"id": 4}}]
    assert reader("iteration_host_ms.online")(run) == pytest.approx(20.0)


def test_scope_is_the_innermost_named_scope():
    from repro.obs import trace as program
    assert program_trace.SCOPES == program.SCOPE_NAMES
    assert program_trace.HEX == program.ANNOTATION_PREFIX
    f = program_trace.scope_of
    assert f("jit(step)/attention/attn_proj/dot_general") == "attn_proj"
    assert f("jit(step)/mlp/norm/reduce_sum") == "norm"
    assert f("jit(step)/while/body/attention/jit(_where)/select_n") == \
        "attention"
    assert f("jit(large)/dot_general") == f(None) == "unscoped"


# ---- the benchmark's first recorded trace: nothing moves -------------------

def test_first_recorded_trace_reduces_as_before():
    """The trace the first round recorded (no ``hex:`` span, no scope)
    reduces to the figures it gave before this reduction was added, and
    the new reduction and readers find nothing in it."""
    ev = program_trace.load(str(OLD))
    r = trace.reduce(ev)
    assert r == trace.reduce(trace.load(str(OLD)))
    assert r["window_s"] == pytest.approx(0.01734553, rel=1e-9)
    assert r["busy_s"] == pytest.approx(0.000145271, rel=1e-9)
    assert r["clock_offsets_s"] == pytest.approx([0.00161], rel=1e-9)
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"serve_loop": 0.01202701, "decode": 0.003411559,
         "insert": 0.00176169}, rel=1e-9)
    assert dict(r["programs"]) == pytest.approx(
        {"jit_large(11543105221590201639)": 9.4745e-05,
         "jit_small(15862713410515176225)": 5.0572e-05}, rel=1e-9)
    assert r["call_busy_s"] == pytest.approx(
        {0: 4.7361e-05, 1: 1.2637e-05, 2: 1.2637e-05, 3: 4.7359e-05,
         4: 1.2638e-05, 5: 1.2639e-05}, rel=1e-9)
    assert ev["hex"] == [] and program_trace.reduce(ev) is None
    run = types.SimpleNamespace(trace=r, spans=None, seconds=1.0)
    assert all(reader(name)(run) is None for name in READERS)


# ---- a v5e trace recorded with the program's tracer on ---------------------

@pytest.fixture(scope="module")
def recorded():
    r = program_trace.reduce_path(str(HEX))
    spans = json.loads(SPANS.read_text())["events"]
    return types.SimpleNamespace(trace=r, spans=spans, seconds=1e9)


def test_recorded_trace_is_small():
    assert HEX.stat().st_size < 100_000
    assert SPANS.stat().st_size < 100_000


def test_recorded_idle_by_span_sums_to_the_idle_time(recorded):
    r = recorded.trace
    by = dict(r["idle_by_span"])
    assert "unattributed" in by and by["decode"] > 0
    idle = r["window_s"] - r["busy_s"]
    assert sum(by.values()) == pytest.approx(idle, rel=1e-9)
    assert by["unattributed"] < 0.1 * idle      # 90% inside some span
    assert set(by) - {"unattributed"} <= {
        "iteration", "admit", "schedule", "sample", "prefill", "insert",
        "decode", "embed", "stage", "head", "to_host"}


def test_recorded_scopes_and_decode_spans(recorded):
    r = recorded.trace
    assert len(r["hex_decode"]) >= 4
    assert all(0 < b <= s for s, b in r["hex_decode"])
    scoped = r["decode_program_scopes"]
    assert scoped["attention"] > 0 and scoped["mlp"] > 0
    assert scoped.get("unscoped", 0) < 0.1 * sum(scoped.values())
    assert set(dict(r["scopes"])) <= set(program_trace.SCOPES) | {
        "unscoped"}


def test_recorded_trace_readers_are_finite(recorded):
    for name in READERS:
        v = reader(name)(recorded)
        assert v is not None and math.isfinite(v) and v > 0, name
