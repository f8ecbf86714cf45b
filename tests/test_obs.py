"""HexTrace observability (repro.obs): span tracing, metrics, calibration.

Four bars:

  * the tracer is PURE OBSERVATION — mixed prefix / chunked / spec /
    preemption / disaggregated traffic is token-identical with tracing on
    or off, and two seeded ``VirtualClock`` runs export byte-identical
    Chrome traces;
  * request timestamps (``first_token_time``, ``prefill_finish_time``)
    are the same with tracing on or off, and chunked-prefill TTFT equals
    the first decode-span start;
  * on a ``WallClock`` the tracer's steps measure real time, nest under
    the step open around them, enter ``hex:`` profiler annotations and
    record compiles; tracing off creates no span, annotation or clock
    read on the serve path, and tokens are stamped after the call that
    produced them;
  * ``ServeStats.merge`` / ``publish`` / ``from_metrics`` aggregate and
    round-trip counters, distributions and attainment correctly, down to
    empty/degenerate inputs;
  * the calibration layer turns predicted-vs-observed phase costs into
    per-(replica, phase) error rows that make ``DriftDetector`` fire its
    model-error signal.
"""
import json

import jax
import numpy as np
import pytest

from repro.core.resched import DriftDetector
from repro.obs.calibration import (CostCalibrator, PHASES,
                                   predictions_from_phase_costs)
from repro.obs.metrics import (DEFAULT_BUCKETS, Histogram, MetricsRegistry,
                               phase_histograms_from_trace)
from repro.obs.report import main as report_main
from repro.obs import trace as T
from repro.obs.trace import (NULL_TRACER, SPAN_NAMES, Tracer,
                             validate_chrome_trace)
from repro.serving.loop import (ServeStats, VirtualClock, WallClock,
                                run_serve_loop)

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# Tracer unit behavior
# ---------------------------------------------------------------------------

def test_tracer_complete_instant_and_span():
    clk = VirtualClock()
    tr = Tracer(clk)
    tr.complete("decode", 0.5, ts=1.0, pid=2, tid=1, tokens=3)
    tr.instant("preempt", ts=1.5, pid=2, rid=7)
    clk.sleep_until(2.0)
    with tr.span("iteration", pid=2):
        clk.tick(0.25)
    assert [e["name"] for e in tr.events] == ["decode", "preempt",
                                              "iteration"]
    dec, ins, it = tr.events
    assert dec["ph"] == "X" and dec["dur"] == 0.5 and \
        dec["args"]["tokens"] == 3
    assert ins["ph"] == "i" and "dur" not in ins
    assert it["ph"] == "X" and it["ts"] == 2.0 and it["dur"] == 0.25
    obj = tr.to_chrome()
    assert validate_chrome_trace(obj) == []
    # µs conversion
    assert obj["traceEvents"][0]["ts"] == 1_000_000
    assert obj["traceEvents"][0]["dur"] == 500_000


def test_tracer_dumps_is_deterministic():
    def build():
        tr = Tracer(VirtualClock())
        tr.complete("prefill", 0.125, ts=0.0, pid=0, tokens=17)
        tr.instant("preempt", ts=0.5, pid=1, slot=2, rid=4)
        return tr
    assert build().dumps() == build().dumps()
    # bytes, not just structure: key order and separators are pinned
    assert '"name":"prefill"' in build().dumps()


def test_unclosed_span_fails_validation():
    tr = Tracer(VirtualClock())
    sp = tr.begin("serve")  # repro: noqa[span-pairing] (deliberate leak)
    errs = validate_chrome_trace(tr.to_chrome())
    assert any("never ended" in e for e in errs)
    tr.end(sp)
    assert validate_chrome_trace(tr.to_chrome()) == []
    assert validate_chrome_trace(tr.to_chrome(),
                                 require_spans=["decode"]) != []


def test_validate_rejects_malformed_events():
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({"traceEvents": [{"ph": "X"}]}) != []
    bad_ph = {"traceEvents": [{"name": "x", "ph": "Z", "ts": 0,
                               "pid": 0, "tid": 0}]}
    assert any("unknown phase" in e for e in validate_chrome_trace(bad_ph))


def test_null_tracer_is_inert():
    assert NULL_TRACER.enabled is False
    NULL_TRACER.complete("decode", 1.0)
    NULL_TRACER.instant("preempt")
    sp = NULL_TRACER.begin("serve")
    NULL_TRACER.end(sp)
    assert NULL_TRACER.events == []


def test_virtual_steps_charge_their_cost_and_name_their_parent():
    """On a virtual clock a step records its virtual cost at entry time;
    a step with no virtual cost records nothing; events inside a step
    name it as their parent."""
    clk = VirtualClock(2.0)
    tr = Tracer(clk)
    with tr.step("iteration", virtual=0.0, pid=3) as it:
        with tr.step("decode", virtual=1.5, rows=2):
            tr.instant("preempt", rid=9)
        with tr.step("sample"):             # host work: not charged
            pass
        it.set(virtual=4.0, completions=1)
    assert [e["name"] for e in tr.events] == ["preempt", "decode",
                                              "iteration"]
    pre, dec, it_ev = tr.events
    assert it_ev["ts"] == dec["ts"] == 2.0
    assert (it_ev["dur"], dec["dur"]) == (4.0, 1.5)
    assert dec["pid"] == 3                  # inherited from the iteration
    assert dec["args"]["parent"] == it_ev["args"]["id"]
    assert pre["args"]["parent"] == dec["args"]["id"]
    assert "parent" not in it_ev["args"]
    assert it_ev["args"]["completions"] == 1
    assert validate_chrome_trace(tr.to_chrome()) == []


class _CountingAnnotation:
    names = []

    def __init__(self, name, **kw):
        type(self).names.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_wall_steps_measure_time_and_enter_profiler_annotations(
        monkeypatch):
    _CountingAnnotation.names = []
    monkeypatch.setattr(T, "TraceAnnotation", _CountingAnnotation)
    tr = Tracer(WallClock())
    with tr.step("decode", virtual=7.0, rows=1):
        with tr.step("to_host"):
            x = sum(range(20000))
    assert x and _CountingAnnotation.names == ["hex:decode", "hex:to_host"]
    inner, outer = tr.events
    assert inner["args"]["parent"] == outer["args"]["id"]
    assert 0 < inner["dur"] <= outer["dur"] < 1.0   # measured, not 7.0
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]


def test_compile_inside_a_step_is_recorded():
    tr = Tracer(WallClock())
    x = jax.numpy.arange(7.0)
    with tr.step("stage", stage=0):
        jax.jit(lambda v: v * 3.0 + 11.0)(x).block_until_ready()
    stage = tr.events[-1]
    comp = [e for e in tr.events if e["name"] == "compile"]
    assert comp and all(e["args"]["parent"] == stage["args"]["id"]
                        for e in comp)
    assert all(0 < e["dur"] <= stage["dur"] for e in comp)
    # outside an open step nothing is attributed
    n = len(tr.events)
    jax.jit(lambda v: v * 5.0 - 2.0)(x).block_until_ready()
    assert len(tr.events) == n


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_registry_instruments_and_labels():
    reg = MetricsRegistry()
    reg.counter("served", replica=0).inc(3)
    reg.counter("served", replica=1).inc()
    reg.gauge("occupancy", stage=0).set(5)
    reg.gauge("occupancy", stage=0).set(2)       # peak survives
    h = reg.histogram("lat")
    for v in (0.01, 0.2, 3.0):
        h.observe(v)
    assert reg.value("served", replica=0) == 3
    assert reg.value("served", replica=1) == 1
    assert reg.value("served", replica=9) is None
    assert reg.total("served") == 4
    g = reg.gauge("occupancy", stage=0)
    assert g.value == 2 and g.peak == 5
    assert h.count == 3 and h.mean == pytest.approx(3.21 / 3)
    assert h.min == 0.01 and h.max == 3.0
    assert h.quantile(0.5) in DEFAULT_BUCKETS


def test_histogram_bucket_edges_and_overflow():
    h = Histogram(buckets=(1.0, 2.0))
    for v in (0.5, 1.0, 1.5, 99.0):
        h.observe(v)
    assert h.counts == [2, 1, 1]         # <=1, <=2, +Inf overflow
    assert h.quantile(1.0) == 99.0


def test_registry_jsonl_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("served", replica=0).inc(7)
    reg.gauge("occ", stage=1).set(9)
    reg.gauge("occ", stage=1).set(4)
    reg.histogram("lat", phase="decode").observe(0.3)
    p = tmp_path / "metrics.jsonl"
    reg.to_jsonl(str(p))
    back = MetricsRegistry.from_jsonl(str(p))
    assert back.collect() == reg.collect()


# ---------------------------------------------------------------------------
# ServeStats: merge / publish / from_metrics (satellite)
# ---------------------------------------------------------------------------

def _stats(n, lats, att, thpt, **kw):
    s = ServeStats(latencies=list(lats), attainment=att, throughput=thpt,
                   n_requests=n)
    for k, v in kw.items():
        setattr(s, k, v)
    return s


def test_merge_empty_and_single():
    z = ServeStats.merge([])
    assert z.latencies == [] and z.attainment == 1.0 and z.throughput == 0.0
    one = _stats(3, [1.0, 2.0], 0.5, 4.0, preemptions=2)
    m = ServeStats.merge([one])
    assert m.latencies == [1.0, 2.0] and m.attainment == 0.5
    assert m.throughput == 4.0 and m.preemptions == 2 and m.n_requests == 3


def test_merge_weights_attainment_and_sums_counters():
    a = _stats(8, [1.0], 1.0, 2.0, prefix_hits=3, iterations=10)
    b = _stats(2, [5.0, 6.0], 0.0, 1.0, prefix_hits=1, iterations=4)
    m = ServeStats.merge([a, b])
    assert m.latencies == [1.0, 5.0, 6.0]
    assert m.attainment == pytest.approx(0.8)    # (8*1 + 2*0) / 10
    assert m.throughput == pytest.approx(3.0)
    assert m.prefix_hits == 4 and m.iterations == 14 and m.n_requests == 10


def test_merge_degenerate_zero_request_parts():
    a = _stats(0, [], 1.0, 0.0)
    b = _stats(0, [], 1.0, 0.0)
    m = ServeStats.merge([a, b])
    assert m.attainment == 1.0 and m.n_requests == 0
    # a zero-request part must not dilute a real part's attainment
    m2 = ServeStats.merge([a, _stats(4, [1.0], 0.25, 1.0)])
    assert m2.attainment == pytest.approx(0.25)


def test_publish_from_metrics_roundtrip():
    reg = MetricsRegistry()
    s = _stats(5, [0.02, 0.3], 0.8, 2.5, preemptions=3, spec_steps=7)
    s.queue_delays = [0.004, 0.04]
    s.publish(reg)
    assert reg.value("serve_preemptions") == 3
    assert reg.value("serve_spec_steps") == 7
    assert reg.value("serve_attainment") == pytest.approx(0.8)
    back = ServeStats.from_metrics(reg)
    assert back.preemptions == 3 and back.spec_steps == 7
    assert back.n_requests == 5
    assert back.attainment == pytest.approx(0.8)
    assert back.throughput == pytest.approx(2.5)
    # distributions come back at bucket resolution: counts survive exactly
    assert len(back.latencies) == 2 and len(back.queue_delays) == 2


# ---------------------------------------------------------------------------
# Calibration + DriftDetector model-error signal
# ---------------------------------------------------------------------------

def test_calibrator_report_and_units():
    cal = CostCalibrator()
    cal.predict(0, "decode", 1.0)
    cal.observe(0, "decode", 1.5)
    cal.observe(0, "decode", 0.9)
    cal.observe(1, "prefill", 6.0, units=12)     # per-token phase
    rows = cal.report()
    assert [(r["replica"], r["phase"]) for r in rows] == \
        [(0, "decode"), (1, "prefill")]
    dec, pre = rows
    assert dec["observed"] == pytest.approx(1.2) and dec["spans"] == 2
    assert dec["rel_err"] == pytest.approx(0.2)
    assert pre["observed"] == pytest.approx(0.5)
    assert pre["predicted"] is None and pre["rel_err"] is None
    assert "calibration:" in cal.summary()


def test_calibrator_observe_trace_and_metrics_agree():
    tr = Tracer(VirtualClock())
    tr.complete("prefill", 4.0, ts=0.0, pid=0, tokens=8)
    tr.complete("decode", 1.0, ts=1.0, pid=0, tokens=3)
    tr.complete("iteration", 9.0, ts=1.0, pid=0)   # excluded from PHASES
    assert "iteration" not in PHASES
    a = CostCalibrator()
    a.observe_trace(tr)
    reg = MetricsRegistry()
    phase_histograms_from_trace(tr, reg)
    b = CostCalibrator()
    b.observe_metrics(reg)
    ra, rb = a.report(), b.report()
    assert [(r["phase"], r["observed"]) for r in ra] == \
        [(r["phase"], r["observed"]) for r in rb]
    # prefill normalized per token, decode per span
    by = {r["phase"]: r for r in ra}
    assert by["prefill"]["observed"] == pytest.approx(0.5)
    assert by["decode"]["observed"] == pytest.approx(1.0)


def test_phase_costs_predictions_helper():
    from repro.core.cost_model import PhaseCosts
    pc = PhaseCosts(prefill_latency=2.0, prefill_bottleneck=1.5,
                    decode_latency=0.25, decode_bottleneck=0.2)
    assert pc.as_dict()["decode_latency"] == 0.25
    cal = CostCalibrator()
    predictions_from_phase_costs(cal, 3, pc, s_in=8)
    cal.observe(3, "prefill", 1.0, units=4)
    cal.observe(3, "decode", 0.25)
    rows = {r["phase"]: r for r in cal.report()}
    assert rows["prefill"]["predicted"] == pytest.approx(0.25)
    assert rows["decode"]["rel_err"] == pytest.approx(0.0)


def test_drift_detector_model_error_fires_and_reanchors():
    det = DriftDetector(rate=1.0, model_error_threshold=0.5,
                        model_error_min=1)
    det.observe_model_error("decode", 1.0, 1.2)      # 20% — in band
    assert det.poll(0.0) is None
    det.observe_model_error("decode", 1.0, 3.0)      # blows the band
    sig = det.poll(1.0)
    assert sig is not None and sig.kind == "model_error"
    assert sig.phase == "decode" and sig.factor > 1.5
    assert "model_error" in sig.describe()
    assert det.poll(2.0) is None                     # re-anchored: once


def test_model_error_is_lowest_priority():
    det = DriftDetector(rate=1.0, model_error_threshold=0.1,
                        model_error_min=1)
    det.observe_model_error("prefill", 1.0, 9.0)
    det.observe_death(frozenset({0}))
    assert det.poll(0.0).kind == "replica_death"     # death first
    assert det.poll(0.0).kind == "model_error"       # then calibration


def test_calibrator_feed_reaches_detector():
    cal = CostCalibrator()
    cal.predict(0, "decode", 1.0)
    cal.observe(0, "decode", 2.0)
    cal.observe(0, "prefill", 1.0)                   # no prediction: not fed
    det = DriftDetector(rate=1.0, model_error_threshold=0.5,
                        model_error_min=1)
    assert cal.feed(det) == 1
    assert det.poll(0.0).kind == "model_error"


# ---------------------------------------------------------------------------
# End-to-end: traced serving is pure observation
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def paged_setup():
    from repro.configs import get_config
    from repro.models import model as M
    from repro.serving.pipeline import AsymmetricPipeline

    cfg = get_config("granite-8b").reduced()
    params = M.init_params(cfg, KEY)
    dev = jax.devices()[0]
    L = cfg.num_layers

    def pipe(split=None):
        split = split if split is not None else [1, L - 1]
        return AsymmetricPipeline(cfg, params, split, [[dev]] * len(split))
    return cfg, pipe


def _mixed_reqs(cfg, seed):
    from repro.serving.request import Request
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab_size, size=17).astype(np.int32)
    reqs = []
    for i in range(7):
        if i % 2 == 0:
            tail = rng.integers(0, cfg.vocab_size,
                                size=int(rng.integers(3, 8))
                                ).astype(np.int32)
            prompt = np.concatenate([shared, tail])
        else:
            prompt = rng.integers(0, cfg.vocab_size,
                                  size=int(rng.integers(8, 16))
                                  ).astype(np.int32)
        reqs.append(Request(rid=i, prompt=prompt,
                            max_new_tokens=int(rng.integers(8, 13)),
                            arrival=0.1 * i))
    return reqs


def _serve_mixed(pipe, cfg, seed, *, tracer=None, kvsan=False):
    from repro.serving.continuous import PagedPipelineBatcher
    from repro.serving.spec import SpecConfig

    b = PagedPipelineBatcher(pipe(), n_slots=3, max_len=48, block_size=8,
                             stage_blocks=[9, 9], admit_headroom=0,
                             prefix_caching=True, prefill_chunk=8,
                             spec=SpecConfig(k=2), kvsan=kvsan)
    if tracer is not None:
        b.tracer = tracer
    reqs = _mixed_reqs(cfg, seed)
    stats = b.serve(reqs, deadline=1e9)
    return b, reqs, stats


@pytest.mark.parametrize("kvsan", [False, True])
def test_traced_serving_token_identical(paged_setup, kvsan):
    cfg, pipe = paged_setup
    _, reqs_off, stats_off = _serve_mixed(pipe, cfg, 3, kvsan=kvsan)
    tr = Tracer()
    _, reqs_on, stats_on = _serve_mixed(pipe, cfg, 3, tracer=tr,
                                        kvsan=kvsan)
    # the traffic genuinely mixes the lifecycle phases
    assert stats_off.prefix_hits > 0 and stats_off.spec_steps > 0
    assert stats_off.preemptions > 0
    for ro, rt in zip(reqs_off, reqs_on):
        assert list(ro.output) == list(rt.output), ro.rid
        # trace-derived timestamps equal the engines' inline stamps...
        assert rt.first_token_time == ro.first_token_time, ro.rid
        # ...and fill in what the untraced colocated path never stamps
        # (inline stamping of prefill_finish only exists on the disagg
        # handoff path — the satellite's point: the trace is the source
        # of truth for lifecycle timestamps when tracing is on)
        assert rt.prefill_finish_time is not None, ro.rid
        assert rt.prefill_finish_time <= rt.first_token_time, ro.rid
        if ro.prefill_finish_time is not None:
            assert rt.prefill_finish_time == ro.prefill_finish_time
    assert stats_on.preemptions == stats_off.preemptions
    names = {e["name"] for e in tr.events}
    # spec replaces the plain decode step with propose/verify spans; the
    # chunked-prefill TTFT test covers the plain "decode" span
    for want in ("serve", "queue_wait", "iteration", "prefill",
                 "spec_propose", "spec_verify", "preempt"):
        assert want in names, (want, sorted(names))
    assert set(names) <= set(SPAN_NAMES) | {"serve", "spec_draft"}
    assert validate_chrome_trace(tr.to_chrome()) == []


def test_trace_bytes_identical_across_seeded_runs(paged_setup):
    cfg, pipe = paged_setup
    tr1 = Tracer()
    _serve_mixed(pipe, cfg, 11, tracer=tr1)
    tr2 = Tracer()
    _serve_mixed(pipe, cfg, 11, tracer=tr2)
    assert tr1.dumps() == tr2.dumps()
    assert len(tr1.events) > 20


def test_chunked_prefill_ttft_equals_first_decode_span(paged_setup):
    """Satellite regression: with chunked prefill, the request's TTFT is
    exactly the start of the first decode span — not the end of the first
    chunk, not the prefill-finish mark."""
    from repro.serving.continuous import PagedPipelineBatcher
    from repro.serving.request import Request

    cfg, pipe = paged_setup
    rng = np.random.default_rng(5)
    req = Request(rid=0, prompt=rng.integers(0, cfg.vocab_size, size=20
                                             ).astype(np.int32),
                  max_new_tokens=4, arrival=0.0)
    tr = Tracer()
    b = PagedPipelineBatcher(pipe(), n_slots=2, max_len=48, block_size=8,
                             prefill_chunk=8)
    b.tracer = tr
    b.serve([req], deadline=1e9)
    assert list(req.output) and req.first_token_time is not None
    decode_ts = [e["ts"] for e in tr.events if e["name"] == "decode"]
    prefill_evs = [e for e in tr.events if e["name"] == "prefill"]
    assert len(prefill_evs) >= 3                 # 20 tokens / 8-chunks
    assert req.first_token_time == min(decode_ts)
    assert req.prefill_finish_time is not None
    assert req.prefill_finish_time <= req.first_token_time


def test_disagg_migration_spans(paged_setup):
    from repro.serving.continuous import PagedPipelineBatcher
    from repro.serving.disagg import KVLink, wire_disaggregation
    from repro.serving.request import Request

    cfg, pipe = paged_setup
    L = len(pipe().layer_split) if hasattr(pipe(), "layer_split") else 2

    def reqs():
        rng = np.random.RandomState(3)
        return [Request(rid=i,
                        prompt=rng.randint(0, cfg.vocab_size, size=8 + i
                                           ).astype(np.int32),
                        max_new_tokens=5, arrival=0.4 * i)
                for i in range(4)]

    def serve(tracer):
        p = PagedPipelineBatcher(pipe(), n_slots=4, max_len=48,
                                 block_size=8, role="prefill",
                                 replica_id=0)
        d = PagedPipelineBatcher(pipe(), n_slots=4, max_len=48,
                                 block_size=8, role="decode", replica_id=1)
        disp = wire_disaggregation([p, d], ["prefill", "decode"], KVLink())
        rs = reqs()
        if tracer is not None:
            p.tracer = d.tracer = disp.tracer = tracer
        stats = run_serve_loop([p, d], rs, deadline=1e9,
                               clock=VirtualClock(), tracer=tracer)
        return rs, stats

    rs_off, _ = serve(None)
    tr = Tracer()
    rs_on, stats = serve(tr)
    assert stats.migrations > 0
    for ro, rt in zip(rs_off, rs_on):
        assert list(ro.output) == list(rt.output), ro.rid
    migs = [e for e in tr.events if e["name"] == "kv_migration"]
    assert len(migs) == stats.migrations
    assert all(e["args"]["dst"] == 1 and e["pid"] == 0 for e in migs)


def test_loop_metrics_publication(paged_setup):
    cfg, pipe = paged_setup
    from repro.serving.continuous import PagedPipelineBatcher

    b = PagedPipelineBatcher(pipe(), n_slots=3, max_len=48, block_size=8,
                             prefix_caching=True)
    reqs = _mixed_reqs(cfg, 3)
    reg = MetricsRegistry()
    stats = run_serve_loop([b], reqs, deadline=1e9, clock=VirtualClock(),
                           metrics=reg)
    # per-replica counter deltas + the final ServeStats publication
    assert reg.value("serve_prefix_hits", replica="0") == \
        stats.prefix_hits > 0
    assert reg.total("serve_n_requests") == len(reqs)
    # engine gauges (metrics_gauges port): pool occupancy high-water
    g = reg.gauge("kv_pool_peak_blocks", replica="0", stage="1")
    assert g.value > 0
    back = ServeStats.from_metrics(reg)
    assert back.prefix_hits == stats.prefix_hits
    assert back.attainment == pytest.approx(stats.attainment)


def test_untraced_serving_emits_nothing(paged_setup):
    cfg, pipe = paged_setup
    b, _, _ = _serve_mixed(pipe, cfg, 3)
    assert b.tracer is NULL_TRACER and NULL_TRACER.events == []


class _CountingClock(WallClock):
    """A wall clock that counts its reads by the file that made them."""

    def __init__(self):
        super().__init__()
        self.reads = {}

    def now(self):
        import sys
        f = sys._getframe(1).f_code.co_filename
        self.reads[f] = self.reads.get(f, 0) + 1
        return super().now()

    def by(self, suffix):
        return sum(n for f, n in self.reads.items() if f.endswith(suffix))


def _serve_wall(pipe, cfg, *, tracer=None, chunk=0, n=4):
    from repro.serving.continuous import PagedPipelineBatcher
    from repro.serving.request import Request
    b = PagedPipelineBatcher(pipe(), n_slots=2, max_len=48, block_size=8,
                             prefill_chunk=chunk)
    if tracer is not None:
        b.tracer = tracer
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 12 + i
                                               ).astype(np.int32),
                    max_new_tokens=3, arrival=0.0) for i in range(n)]
    clock = _CountingClock()
    run_serve_loop([b], reqs, deadline=1e9, clock=clock, tracer=tracer)
    return b, reqs, clock


def test_null_tracer_serve_makes_no_span_annotation_or_clock_read(
        paged_setup, monkeypatch):
    """Tracing off: no step object and no profiler annotation is made, and
    neither the tracer nor the model step reads the clock; the batcher
    reads it only to stamp request times (at most two per request)."""
    cfg, pipe = paged_setup
    made = []

    class CountingStep(T.Step):
        __slots__ = ()

        def __init__(self, *a, **k):
            made.append(a[1])
            super().__init__(*a, **k)

    _CountingAnnotation.names = []
    monkeypatch.setattr(T, "Step", CountingStep)
    monkeypatch.setattr(T, "TraceAnnotation", _CountingAnnotation)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    b, reqs, clock = _serve_wall(pipe, cfg)
    assert all(len(r.output) == 3 for r in reqs)
    assert made == [] and _CountingAnnotation.names == []
    assert clock.by("obs/trace.py") == clock.by("serving/pipeline.py") == 0
    assert 0 < clock.by("serving/continuous.py") <= 2 * len(reqs)
    # the same serve traced does make them (the counters see the sites)
    _serve_wall(pipe, cfg, tracer=Tracer())
    assert made and _CountingAnnotation.names


def test_wall_clock_spans_nest_and_stamp_after_the_call(paged_setup):
    """On the wall clock every span lies inside its parent, the model-step
    spans nest under the calls, and a request's first token is stamped
    after the decode call that appended it returned, in the sample step
    that appended it; its prefill finish after its insert returned."""
    cfg, pipe = paged_setup
    tr = Tracer()
    b, reqs, _ = _serve_wall(pipe, cfg, tracer=tr)
    ev = {e["args"]["id"]: e for e in tr.events if "id" in
          (e.get("args") or {})}
    names = {e["name"] for e in ev.values()}
    assert {"iteration", "admit", "prefill", "insert", "schedule",
            "sample", "decode", "embed", "stage", "head",
            "to_host"} <= names
    assert names <= set(SPAN_NAMES)
    eps = 1e-9
    for e in ev.values():
        p = ev.get(e["args"].get("parent"))
        if p is None:
            assert e["name"] == "iteration"
            continue
        assert p["ts"] - eps <= e["ts"]
        assert e["ts"] + e["dur"] <= p["ts"] + p["dur"] + eps
        if e["name"] in ("embed", "stage", "head", "to_host"):
            assert p["name"] in ("insert", "decode")
    for r in reqs:
        ins = [e for e in ev.values() if e["name"] == "insert"
               and r.rid in e["args"]["rids"]][0]
        assert ins["ts"] + ins["dur"] <= r.prefill_finish_time
        dec = min((e for e in ev.values() if e["name"] == "decode"
                   and r.rid in e["args"]["rids"]), key=lambda e: e["ts"])
        end = dec["ts"] + dec["dur"]
        sample = [e for e in ev.values() if e["name"] == "sample"
                  and e["args"]["parent"] == dec["args"]["parent"]
                  and e["ts"] >= end][0]
        assert end <= r.first_token_time
        assert r.first_token_time <= sample["ts"] + sample["dur"]
    assert validate_chrome_trace(tr.to_chrome()) == []


def test_wall_clock_context_spans_carry_rows_and_tokens(paged_setup):
    """Chunked prefill runs through ``context`` calls inside ``prefill``
    steps; each carries its rows, prompt tokens and request ids."""
    cfg, pipe = paged_setup
    tr = Tracer()
    b, reqs, _ = _serve_wall(pipe, cfg, tracer=tr, chunk=8, n=2)
    ctx = [e for e in tr.events if e["name"] == "context"]
    pre = {e["args"]["id"]: e for e in tr.events if e["name"] == "prefill"}
    assert ctx and all(e["args"]["parent"] in pre for e in ctx)
    assert sum(e["args"]["tokens"] for e in ctx) == sum(
        len(r.prompt) for r in reqs)
    for e in ctx:
        assert e["args"]["rows"] == len(e["args"]["rids"])
        assert pre[e["args"]["parent"]]["args"]["tokens"] == \
            e["args"]["tokens"]


def test_wall_clock_model_step_spans_carry_devices_and_chunk_starts(
        paged_setup):
    """The ``decode`` and ``context`` calls carry ``tp``, the devices of
    each stage, and each ``context`` call the first position of each of
    its chunks, aligned with its request ids: one request's chunks start
    at 0, 8, 16, ... in turn."""
    cfg, pipe = paged_setup
    tr = Tracer()
    b, reqs, _ = _serve_wall(pipe, cfg, tracer=tr, chunk=8, n=2)
    calls = [e for e in tr.events if e["name"] in ("decode", "context")]
    assert {e["name"] for e in calls} == {"decode", "context"}
    assert all(e["args"]["tp"] == [1, 1] for e in calls)
    starts = {r.rid: [] for r in reqs}
    for e in calls:
        if e["name"] == "context":
            a = e["args"]
            assert len(a["starts"]) == len(a["rids"]) == a["rows"]
            for rid, start in zip(a["rids"], a["starts"]):
                starts[rid].append(start)
    for r in reqs:
        assert starts[r.rid] == list(range(0, len(r.prompt), 8))


# ---------------------------------------------------------------------------
# Report CLI
# ---------------------------------------------------------------------------

def test_report_cli_valid_and_invalid(tmp_path, capsys, paged_setup):
    cfg, pipe = paged_setup
    tr = Tracer()
    _serve_mixed(pipe, cfg, 3, tracer=tr)
    reg = MetricsRegistry()
    phase_histograms_from_trace(tr, reg)
    trace_p = tmp_path / "trace.json"
    metrics_p = tmp_path / "metrics.jsonl"
    tr.write(str(trace_p))
    reg.to_jsonl(str(metrics_p))
    rc = report_main([str(metrics_p), "--trace", str(trace_p),
                      "--require-spans", "prefill,spec_verify"])
    out = capsys.readouterr().out
    assert rc == 0 and "trace OK" in out and "calibration" in out
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [{"ph": "X"}]}))
    assert report_main(["--trace", str(bad)]) == 1
