"""Unified serving loop: continuous batching on multi-stage asymmetric
pipelines must be bit-identical to isolated generation, the virtual clock
must make whole served workloads deterministic, and the analytic SLO
simulator must share the loop's admission semantics."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import slo_sim
from repro.models import model as M
from repro.serving.continuous import PagedPipelineBatcher
from repro.serving.loop import VirtualClock, run_serve_loop
from repro.serving.pipeline import AsymmetricPipeline
from repro.serving.request import Request, synth_workload
from repro.serving.router import Router

KEY = jax.random.PRNGKey(0)


def _mk_pipeline(cfg, params, n_stages=2):
    dev = jax.devices()[0]
    L = cfg.num_layers
    if n_stages == 1:
        split = [L]
    else:
        split = [max(1, L // n_stages)] * (n_stages - 1)
        split.append(L - sum(split))
    return AsymmetricPipeline(cfg, params, split, [[dev]] * len(split))


def _reqs(cfg, *, n, base_len=5, stride=3, out=5, arrivals=None):
    rng = np.random.RandomState(0)
    return [Request(rid=i,
                    prompt=rng.randint(0, cfg.vocab_size,
                                       size=base_len + stride * i
                                       ).astype(np.int32),
                    max_new_tokens=out,
                    arrival=0.0 if arrivals is None else arrivals[i])
            for i in range(n)]


@pytest.mark.parametrize("arch", ["granite-8b", "phi3.5-moe-42b-a6.6b"])
def test_pipeline_continuous_equals_isolated(arch):
    """Slot-continuous serving on a 2-stage asymmetric pipeline: each
    request's tokens match AsymmetricPipeline.generate run in isolation,
    including slot reuse (4 requests through 2 slots) and joint insertion
    of mixed-length prompts."""
    cfg = get_config(arch).reduced()
    params = M.init_params(cfg, KEY)
    pipe = _mk_pipeline(cfg, params, n_stages=2)
    reqs = _reqs(cfg, n=4)
    worker = PagedPipelineBatcher(pipe, n_slots=2, max_len=48, block_size=8)
    stats = run_serve_loop([worker], reqs, deadline=1e9,
                           clock=VirtualClock())
    assert len(stats.latencies) == 4

    ref_pipe = _mk_pipeline(cfg, params, n_stages=2)
    for r in reqs:
        ref = ref_pipe.generate(r.prompt[None], max_new=r.max_new_tokens)
        assert list(r.output) == list(ref[0]), r.rid


def test_virtual_clock_determinism():
    """Same workload through fresh engines -> identical ServeStats, down to
    every latency value and iteration count."""
    cfg = get_config("xlstm-125m").reduced()
    params = M.init_params(cfg, KEY)
    reqs0 = synth_workload(rate=200.0, duration=0.05, vocab=cfg.vocab_size,
                           prompt_len=6, prompt_jitter=4, out_len=4, seed=7)

    def run():
        router = Router([_mk_pipeline(cfg, params, n_stages=2),
                         _mk_pipeline(cfg, params, n_stages=1)],
                        n_slots=2, max_len=32)
        reqs = synth_workload(rate=200.0, duration=0.05,
                              vocab=cfg.vocab_size, prompt_len=6,
                              prompt_jitter=4, out_len=4, seed=7)
        return router.serve(reqs, 1e9, clock=VirtualClock())

    assert len(reqs0) >= 3          # workload actually exercises queueing
    s1, s2 = run(), run()
    assert s1.latencies == s2.latencies
    assert s1.queue_delays == s2.queue_delays
    assert s1.attainment == s2.attainment
    assert s1.throughput == s2.throughput
    assert s1.iterations == s2.iterations and s1.iterations > 0


def test_least_loaded_dispatch_spreads_replicas():
    cfg = get_config("xlstm-125m").reduced()
    params = M.init_params(cfg, KEY)
    router = Router([_mk_pipeline(cfg, params, 1),
                     _mk_pipeline(cfg, params, 1)],
                    n_slots=1, max_len=32)
    reqs = _reqs(cfg, n=2, base_len=5, stride=0, out=3)
    router.serve(reqs, 1e9, clock=VirtualClock())
    # two single-slot replicas, two simultaneous arrivals: both admit at t=0
    assert [r.start_time for r in reqs] == [0.0, 0.0]

    solo = Router([_mk_pipeline(cfg, params, 1)], n_slots=1, max_len=32)
    reqs2 = _reqs(cfg, n=2, base_len=5, stride=0, out=3)
    solo.serve(reqs2, 1e9, clock=VirtualClock())
    # one slot total: the second request queues behind the first
    assert reqs2[0].start_time == 0.0 and reqs2[1].start_time > 0.0


class _StrandingWorker:
    """Pathological worker: admits one request and then never runs it —
    busy() stays False, no future event. The loop must break out and the
    stranded request must surface as DROPPED, not as a negative latency
    that counts toward SLO attainment."""

    def __init__(self):
        self.req = None

    def capacity(self, now):
        return 0 if self.req else 1

    def load(self, now):
        return 0

    def admit(self, reqs, now):
        self.req = reqs[0]

    def busy(self, now):
        return False               # admitted work never becomes runnable

    def inflight(self):
        return 1 if self.req else 0

    def next_event(self, now):
        return None

    def run_iteration(self, now):
        raise AssertionError("never runnable")


def test_stranded_request_reported_dropped_not_attained():
    """Regression: a worker stranding an inflight request used to leave
    finish_time = 0.0, which produced a NEGATIVE latency that passed the
    deadline check and inflated attainment + throughput."""
    reqs = [Request(rid=0, prompt=np.zeros(3, np.int32), max_new_tokens=2,
                    arrival=0.0),
            Request(rid=1, prompt=np.zeros(3, np.int32), max_new_tokens=2,
                    arrival=0.5)]
    stats = run_serve_loop([_StrandingWorker()], reqs, deadline=1e9,
                           clock=VirtualClock())
    # rid 0 admitted then stranded; rid 1 never admitted (capacity 0):
    # both are dropped, neither contributes a latency, attainment is 0
    assert stats.dropped == 2
    assert stats.latencies == []
    assert stats.attainment == 0.0
    assert stats.throughput == 0.0
    assert all(r.finish_time is None for r in reqs)
    stats.summary()                # degenerate summary must not crash


def test_empty_and_all_rejected_stats_summary():
    """Regression: ServeStats.summary() crashed on np.percentile of an
    empty array when zero requests completed (e.g. an all-rejected
    replay)."""
    from repro.serving.loop import ServeStats
    s = ServeStats.from_requests([], deadline=1.0)
    assert s.attainment == 1.0 and s.latencies == []
    assert "n=0" in s.summary()
    # all-rejected: finished instantly with empty outputs
    reqs = [Request(rid=i, prompt=np.zeros(4, np.int32), max_new_tokens=3,
                    arrival=0.1 * i, output=np.zeros(0, np.int32),
                    start_time=0.1 * i, finish_time=0.1 * i + 1e-3)
            for i in range(3)]
    s2 = ServeStats.from_requests(reqs, deadline=1.0)
    assert s2.latencies == [] and s2.attainment == 0.0
    assert s2.throughput == 0.0
    assert "p50=n/a" in s2.summary()


def test_rejected_requests_excluded_from_throughput_and_percentiles():
    """Regression: rejected requests (near-instant empty completions) used
    to count toward throughput and drag p50/p99 toward zero."""
    from repro.serving.loop import ServeStats
    served = [Request(rid=i, prompt=np.zeros(4, np.int32), max_new_tokens=2,
                      arrival=0.0, output=np.array([1, 2], np.int32),
                      start_time=0.0, finish_time=10.0) for i in range(2)]
    rejected = [Request(rid=10 + i, prompt=np.zeros(99, np.int32),
                        max_new_tokens=2, arrival=0.0,
                        output=np.zeros(0, np.int32), start_time=0.0,
                        finish_time=0.001) for i in range(2)]
    stats = ServeStats.from_requests(served + rejected, deadline=1e9)
    assert stats.latencies == [10.0, 10.0]          # rejects excluded
    assert stats.throughput == pytest.approx(2 / 10.0)
    assert stats.attainment == pytest.approx(0.5)   # rejects not attained


def test_static_batcher_rejects_oversized_instead_of_crashing():
    """Satellite: StaticBatcher gets the same oversized-request guard the
    continuous engine has — reject alone with an empty output, counted in
    ServeStats.rejected, instead of taking down the whole replay."""
    from repro.serving.router import StaticBatcher
    cfg = get_config("xlstm-125m").reduced()
    params = M.init_params(cfg, KEY)
    pipe = _mk_pipeline(cfg, params, n_stages=2)
    worker = StaticBatcher(pipe, max_batch=4, max_len=16)
    rng = np.random.RandomState(0)
    lens = [5, 29, 6]                       # ok, oversized, ok
    reqs = [Request(rid=i, prompt=rng.randint(0, cfg.vocab_size, size=n
                                              ).astype(np.int32),
                    max_new_tokens=3, arrival=0.0)
            for i, n in enumerate(lens)]
    with pytest.warns(UserWarning, match="rejected with empty output"):
        stats = run_serve_loop([worker], reqs, deadline=1e9,
                               clock=VirtualClock())
    assert stats.rejected == 1
    assert [len(r.output) for r in reqs] == [3, 0, 3]
    assert len(stats.latencies) == 2


class _StubWorker:
    """Single-slot compute worker: 3 iterations per request, cost 1.0."""

    def __init__(self):
        self.req, self.n = None, 0

    def capacity(self, now):
        return 0 if self.req else 1

    def load(self, now):
        return 1 if self.req else 0

    def admit(self, reqs, now):
        self.req, self.n = reqs[0], 3

    def busy(self, now):
        return self.req is not None

    def inflight(self):
        return 1 if self.req else 0

    def next_event(self, now):
        return None

    def run_iteration(self, now):
        self.n -= 1
        if self.n == 0:
            r, self.req = self.req, None
            return [(r, None, None)], 1.0
        return [], 1.0


def test_virtual_time_runs_replicas_in_parallel():
    """A virtual-clock cycle costs the SLOWEST busy worker's iteration, not
    the sum across replicas: two simultaneous requests on two single-slot
    replicas finish at t=3, exactly as one request on one replica would."""
    reqs = [Request(rid=i, prompt=np.zeros(1, np.int32), max_new_tokens=3,
                    arrival=0.0) for i in range(2)]
    run_serve_loop([_StubWorker(), _StubWorker()], reqs, deadline=1e9,
                   clock=VirtualClock())
    assert [r.latency for r in reqs] == [3.0, 3.0]


def test_analytic_worker_on_shared_loop():
    """The SLO simulator's analytic replicas run on the same loop with
    closed-form timing: request i admits every `bottleneck` and finishes
    `latency` later."""
    w = slo_sim.AnalyticWorker(slo_sim.ReplicaModel(latency=1.0,
                                                    bottleneck=0.25))
    reqs = [Request(rid=i, prompt=np.zeros(0, np.int32), max_new_tokens=0,
                    arrival=0.0) for i in range(4)]
    stats = run_serve_loop([w], reqs, deadline=1.6, clock=VirtualClock())
    fins = sorted(r.finish_time for r in reqs)
    assert fins == [1.0, 1.25, 1.5, 1.75]
    assert stats.attainment == 0.75          # 1.75 misses the 1.6 deadline


def test_simulate_matches_closed_form():
    """At rates far below 1/bottleneck every request should meet a deadline
    just above the latency, and miss one just below it."""
    reps = [slo_sim.ReplicaModel(latency=1.0, bottleneck=0.1)]
    assert slo_sim.simulate(reps, 0.2, 1.5, duration=30, seed=3) == 1.0
    assert slo_sim.simulate(reps, 0.2, 0.9, duration=30, seed=3) == 0.0
