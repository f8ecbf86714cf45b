"""One run of one cell: build the deployment from the seed, warm up every
shape the traffic uses, serve the traffic for the window on the wall
clock, reduce what was recorded to metrics, then check the served tokens
against the plain reference.

The entry point the window drives is the program's own:
``InferenceEngine.from_config`` on the plan ``core.scheduler.schedule``
returns for the configuration's pool, then ``engine.serve`` on a
``WallClock``: the serve loop, the router, the paged batcher, the pipeline
stage programs and the XLA kernels.
"""
from __future__ import annotations

import gc
import math
import shutil
import time
import types
import warnings
from pathlib import Path
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from harness import check, counters, reference, taps, trace
from harness.stats import percentile
from harness import traffic as T
from harness.spec import Spec

TRACE_DIR = ".bench_trace"      # under the checkout; removed after reading


def log(*args) -> None:
    print(*args, flush=True)


def model_config(c: Dict):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=c["name"], source=c["source"], family="dense",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], head_dim=c.get("head_dim", 0),
        norm_eps=c["rms_norm_eps"], rope_theta=c["rope_theta"],
        tie_embeddings=c["tie_word_embeddings"],
        attn_bias=c["attention_bias"], activation=c["hidden_act"],
        dtype=c["torch_dtype"])


def serving_config(cfg, c: Dict, trf: Dict, cell: Dict):
    """Paged continuous batching sized for the traffic's longest request.
    The scheduler's seed is fixed: the deployment belongs to the cell, and
    the run's seed only makes its weights and traffic."""
    from repro.serving.config import ServingConfig
    return ServingConfig(
        arch=cfg.name, cluster=c["pool"], rate=trf.get("rate_per_s", 1.0),
        duration=60.0, deadline=cell["deadline_s"],
        out_len=trf["output"]["max"], prompt_len=max(T.grid(trf["prompt"])),
        search_iters=4, seed=0, policy="continuous", cache_layout="paged",
        block_size=cell["block_size"],
        prefill_chunk=cell.get("prefill_chunk", 0)).normalized()


def fail_on_degraded_features() -> None:
    """A feature gate of the program that degrades with a UserWarning
    fails the run instead of serving another path than the one asked
    for."""
    warnings.filterwarnings("error", category=UserWarning,
                            module=r"repro(\.|$)")


def make_plan(cfg, sv, devices: List):
    from repro.core.scheduler import schedule
    plan = schedule(sv.pool(), cfg, sv.task(), **sv.schedule_kwargs()).plan
    used = sorted(d for p in plan.assignment.pipelines
                  for s in p.stages for d in s.device_ids)
    if len(set(used)) != len(used) or not set(used) <= set(
            range(len(devices))):
        raise RuntimeError(f"the plan names devices {used}; "
                           f"{len(devices)} are present")
    return plan


def device_limit(device, cell: Dict) -> int:
    """The bytes a device may hold; a backend that reports no memory (the
    CPU) takes the cell's ``bytes_limit``."""
    stats = device.memory_stats()
    return stats["bytes_limit"] if stats else cell["bytes_limit"]


def peak_bytes(devices) -> Optional[int]:
    """The peak of the fullest device, where the backend reports it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    return max(peaks) if None not in peaks else None


def pool_blocks(c: Dict, plan, devices: List, sv, cell: Dict) -> List[int]:
    """Page-pool blocks per stage: what each stage's fullest device has
    left after its share of the weights, the one-shot insert's scratch
    caches (n_slots rows of max_len) and the cell's reserve for the step's
    temporaries; never more than every slot full."""
    s = counters.shapes(c)
    pipes = plan.assignment.pipelines
    layouts = {tuple((tuple(st.device_ids), st.num_layers)
                     for st in p.stages) for p in pipes}
    if len(layouts) != 1:
        raise RuntimeError("replicas of different layouts are not sized")
    n, max_len, bs = cell["n_slots"], sv.max_len(), sv.block_size
    out = []
    stages = pipes[0].stages
    for si, st in enumerate(stages):
        tp = len(st.device_ids)
        split = tp if s["hkv"] % tp == 0 else 1
        kv_tok = st.num_layers * 2 * s["hkv"] * s["hd"] * s["item"] // split
        w = st.num_layers * s["params_per_layer"] * s["item"] / tp
        if si == 0:
            w += s["V"] * s["d"] * s["item"]
        if si == len(stages) - 1:
            w += (s["V"] * s["d"] + s["d"]) * s["item"]
        scratch = 0 if sv.prefill_chunk else n * max_len * kv_tok
        limit = min(device_limit(devices[d], cell) for d in st.device_ids)
        free = limit - w - scratch - cell["reserve_bytes"]
        blocks = min(int(free // (bs * kv_tok)), n * max_len // bs + 1)
        log(f"stage {si}: devices {st.device_ids}, {st.num_layers} layers, "
            f"weights {w:.0f} B per device, scratch {scratch} B, reserve "
            f"{cell['reserve_bytes']} B, limit {limit} B -> {blocks} blocks "
            f"of {bs} ({blocks * bs * kv_tok} B per device)")
        if blocks * bs < max_len:
            raise RuntimeError("the pool cannot hold one longest request")
        out.append(blocks)
    return out


def warm_sets(trf: Dict, cell: Dict) -> List:
    """(rows, prompt length) of every warm-up serve. Chunked prefill: one
    chunk of every row count. One-shot insert: every row count at every
    width of the prompt grid (the insert pads rows to a power of two and
    width to 16, but its scatter and output rows follow the real count)."""
    n, chunk = cell["n_slots"], cell.get("prefill_chunk", 0)
    if chunk:
        return [(m, chunk) for m in range(1, n + 1)]
    return [(m, w) for m in range(1, n + 1) for w in T.grid(trf["prompt"])]


def warm_up(engine, vocab: int, sets) -> None:
    """Serve each set once, all due at once, one output token each: every
    insert or context program of the traffic, the decode step and the
    host-side operations around them compile and load here."""
    from repro.serving.loop import WallClock
    from repro.serving.request import Request
    rng = np.random.default_rng(0)
    took = []
    for m, width in sets:
        reqs = [Request(rid=i, prompt=rng.integers(0, vocab, width,
                                                   dtype=np.int32),
                        max_new_tokens=1) for i in range(m)]
        t0 = time.monotonic()
        engine.serve(reqs, deadline=1e9, clock=WallClock())
        took.append((time.monotonic() - t0, m, width))
        if not all(r.served for r in reqs):
            raise RuntimeError(f"warm-up of {m} x {width} not served")
    log("slowest warm-up serves (s, rows, width): "
        + repr(sorted(took, reverse=True)[:8]))


def full(r) -> bool:
    return (r.served and r.output is not None
            and len(r.output) == r.max_new_tokens)


def account(trf: Dict, reqs, rec, seconds: float) -> Dict:
    """The requests the window attempted, and those of them that failed."""
    if trf["loop"] == "open":
        attempted = [r for r in reqs if r.arrival < seconds]
        failed = [r for r in attempted if not full(r)
                  or rec.token_times(r.rid, r.max_new_tokens) is None]
    else:
        attempted = [r for r in reqs if r.start_time is not None
                     and r.start_time < seconds]
        # a document the window cut is not a failure; a turned-away one is
        failed = [r for r in attempted if r.finish_time is not None
                  and not r.served]
    return {"attempted": attempted, "failed": failed}


def end_to_end(run, slo: Optional[float] = None) -> Dict[str, float]:
    """Every end-to-end number this run can give, by metric name. Logs the
    share of attempted requests whose first token came within ``slo``
    seconds (not a metric: it swings with the smallest change)."""
    out = {"setup_s": run.setup_s}
    ttft, tpot = [], []
    bad = {id(r) for r in run.failed}
    for r in run.attempted:
        times = None if id(r) in bad else run.recorder.token_times(
            r.rid, r.max_new_tokens)
        if times is None:
            ttft.append(math.inf)
            tpot.append(math.inf)
            continue
        ttft.append(times[0] - r.arrival)
        n = len(times)
        tpot.append((times[-1] - times[0]) / (n - 1) if n > 1 else 0.0)
    if slo is not None and ttft:
        log(f"SLO attainment (TTFT <= {slo} s): "
            f"{float(np.mean(np.asarray(ttft) <= slo))!r}")
    if run.loop == "open":
        out["ttft_p95_ms"] = 1e3 * percentile(ttft, 95)
        out["tpot_p95_ms"] = 1e3 * percentile(tpot, 95)
    served = 0
    for r in run.requests:
        stamps = run.recorder.tokens.get(r.rid, {})
        served += sum(1 for k, t in stamps.items()
                      if k < r.max_new_tokens and 0 <= t < run.seconds)
    out["output_tok_s"] = served / run.seconds
    return out


class GcPauses:
    """The garbage collector's pauses on the window's clock."""

    def __init__(self, clock):
        self.clock, self.pauses, self._t0 = clock, [], None

    def __call__(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            dt = time.perf_counter() - self._t0
            self.pauses.append((self.clock.now() - dt, dt,
                                info["generation"]))
            self._t0 = None

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)
        return False


def log_window(run) -> None:
    """What set the tails: the requests whose first token came latest, with
    the insert that served them, the host's longest gaps between calls and
    the collector's pauses in the window."""
    late = []
    for r in run.attempted:
        t = run.recorder.tokens.get(r.rid, {}).get(0)
        late.append((math.inf if t is None else t - r.arrival, r))
    late.sort(key=lambda x: -x[0])
    ends = {round(k.t1, 9): k for k in run.recorder.calls
            if k.kind != "decode"}
    rows = []
    for ttft, r in late[:8]:
        k = ends.get(round(ttft + r.arrival, 9))
        rows.append((r.rid, round(r.arrival, 3), round(1e3 * ttft, 1),
                     len(r.prompt), r.max_new_tokens,
                     None if k is None else (k.rows, round(k.t0, 3),
                                             round(1e3 * (k.t1 - k.t0), 1))))
    log("latest first tokens (rid, due s, ttft ms, prompt, output, "
        "(insert rows, start s, ms)): " + repr(rows))
    calls = [k for k in run.recorder.calls if k.t0 < run.seconds]
    gaps = sorted(((b.t0 - a.t1, a.t1) for a, b in zip(calls, calls[1:])),
                  reverse=True)[:5]
    log("longest host gaps between calls (ms, at s): "
        + repr([(round(1e3 * g, 1), round(t, 3)) for g, t in gaps]))
    p = [x for x in run.gc_pauses if 0 <= x[0] < run.seconds]
    log(f"collector pauses in the window: {len(p)}, "
        f"{1e3 * sum(x[1] for x in p):.1f} ms in all; longest (ms, at s, "
        "generation): " + repr([(round(1e3 * d, 1), round(t, 3), g) for
                                t, d, g in sorted(p, key=lambda x: -x[1])
                                [:5]]))


def device_info(devices, peak: Optional[int] = None) -> Dict:
    d = {"platform": devices[0].platform, "kind": devices[0].device_kind,
         "count": len(jax.devices())}
    if peak is not None:
        d["memory_peak_bytes"] = int(peak)
    return d


class Deployment:
    """The cell's deployment, built from the seed and warmed up: the plan
    ``core.scheduler.schedule`` returns for the configuration's pool, the
    engine ``InferenceEngine.from_config`` builds on it, and the recorder
    wrapped round its replicas."""

    def __init__(self, spec: Spec, workload: str, seed: int, devices: List,
                 *, annotate: bool = False, overrides: Optional[Dict] = None):
        """``overrides``: cell settings that replace the cell file's (the
        knee sweep tries other slot counts with it)."""
        from repro.serving.engine import InferenceEngine
        w = spec.workload(workload)
        self.spec, self.workload, self.seed = spec, workload, seed
        self.devices = devices
        self.config = spec.config(w["config"])
        self.traffic = spec.traffic(w["traffic"])
        self.cell = {**spec.cell(workload), **(overrides or {})}
        self.cfg = model_config(self.config)
        self.sv = serving_config(self.cfg, self.config, self.traffic,
                                 self.cell)
        self.plan = make_plan(self.cfg, self.sv, devices)
        log(f"plan: {self.plan.describe()}")
        blocks = pool_blocks(self.config, self.plan, devices, self.sv,
                             self.cell)
        t0 = time.monotonic()
        self.engine = InferenceEngine.from_config(
            self.cfg, self.plan, self.sv, key=reference.seed_key(seed),
            n_slots=self.cell["n_slots"], stage_blocks=blocks,
            devices=devices)
        jax.block_until_ready([(st.layer_params, st.head_params)
                               for r in self.engine.replicas
                               for st in r.stages])
        log(f"build: {time.monotonic() - t0:.3f} s")
        sets = warm_sets(self.traffic, self.cell)
        t0 = time.monotonic()
        warm_up(self.engine, self.cfg.vocab_size, sets)
        log(f"warm-up: {len(sets)} serves in {time.monotonic() - t0:.3f} s")
        self.recorder = taps.Recorder(self.engine.router.workers,
                                      annotate=annotate)
        self.chips = len({d for p in self.plan.assignment.pipelines
                          for s in p.stages for d in s.device_ids})

    def free(self) -> None:
        """Drop the engine and its device state."""
        self.engine = None
        self.recorder.workers = []
        gc.collect()


def profiler_options():
    """Device operations and host annotations; no Python tracer, which
    would trace every call of the serve loop and slow it severalfold."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def serve(dep: Deployment, traffic: Dict, seed: int, seconds: float, *,
          trace_dir: Optional[Path] = None, t_start: Optional[float] = None
          ) -> types.SimpleNamespace:
    """Serve the traffic of ``seed`` for a window of ``seconds`` on the wall
    clock and record what it saw. ``trace_dir``: profile there from the
    window's last ``trace_s`` seconds until the serve returns. ``t_start``:
    when set-up began."""
    from repro.serving.loop import WallClock
    from repro.serving.request import Request
    rec, cell = dep.recorder, dep.cell
    rec.reset()
    items = T.make(traffic, seed, seconds, dep.cfg.vocab_size)
    reqs = [Request(rid=it.rid, prompt=it.prompt, max_new_tokens=it.out_len,
                    arrival=it.due) for it in items]
    closed = traffic["loop"] == "closed"
    stop_at = seconds if closed else seconds + traffic["drain_s"]
    traced = {"on": False}
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_from = max(0.0, seconds - cell["trace_s"])

        def profile(now):
            """Profile from ``trace_from``. The trace stops once the serve
            has returned: writing it stalls the host for seconds, and no
            request may wait on that. Its reduction reads the window's
            calls alone."""
            if not traced["on"] and now >= trace_from:
                jax.profiler.start_trace(str(trace_dir),
                                         profiler_options=profiler_options())
                traced["on"] = True
        rec.before_call = profile
    clock = WallClock()
    setup_s = None if t_start is None else time.monotonic() - t_start
    rec.start(clock, reqs, window=seconds, stop_at=stop_at)
    with GcPauses(clock) as gc_pauses:
        stats = dep.engine.serve(reqs, deadline=cell["deadline_s"],
                                 clock=clock)
    rec.finish()
    served_s = clock.now()
    if traced["on"]:
        jax.profiler.stop_trace()
    acc = account(traffic, reqs, rec, seconds)
    if closed and all(r.start_time is not None and r.start_time < seconds
                      for r in reqs):
        raise RuntimeError("the queue ran dry inside the window: give the "
                           "traffic more blocks")
    run = types.SimpleNamespace(
        loop=traffic["loop"], seconds=seconds, setup_s=setup_s,
        recorder=rec, calls=[k for k in rec.calls if k.t0 < seconds],
        stats=stats, requests=reqs, attempted=acc["attempted"],
        failed=acc["failed"], config=dep.config, chips=dep.chips,
        peaks=(dep.spec.peaks(dep.devices[0].device_kind)
               if dep.devices[0].platform == "tpu" else None), trace=None,
        compiles=[t for _, t in rec.compiles if 0 <= t < seconds],
        gc_pauses=gc_pauses.pauses)
    log(f"served: {len(reqs)} requests, {len(run.attempted)} attempted, "
        f"{len(run.failed)} failed, loop ended at {served_s:.3f} s; "
        + stats.summary())
    log(f"compiles or cache loads in the window: {len(run.compiles)}")
    log_window(run)
    return run


def run_cell(spec: Spec, workload: str, seed: int, seconds: float,
             trace_on: bool, devices: List, t_start: float, *,
             control: str = "", fault: Optional[Callable] = None) -> Dict:
    """One run; returns the result line. ``control`` (limit setting only):
    ``int8`` judges the run on the tokens of the reference with int8
    weights in the program's place, beside the served tokens' number. ``fault`` is called with the engine
    before the window (tests plant a fault in the served path with it)."""
    dep = Deployment(spec, workload, seed, devices, annotate=trace_on)
    if fault is not None:
        fault(dep.engine)
    trace_dir = Path(TRACE_DIR).resolve() if trace_on else None
    run = serve(dep, dep.traffic, seed, seconds, trace_dir=trace_dir,
                t_start=t_start)
    peak = peak_bytes(devices)
    e2e = end_to_end(run, dep.cell.get("slo_ttft_s"))
    result = {"correct": False, "attempted": len(run.attempted),
              "failed": len(run.failed)}
    breakdown = None
    if trace_on:
        run.trace = trace.reduce_dir(str(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        metrics = {}
        for m in spec.per_layer(workload):
            v = spec.reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if run.trace is not None:
            breakdown = {"device_ops": run.trace["device_ops"],
                         "idle_gaps": run.trace["idle_gaps"]}
            log("trace: " + repr({k: run.trace[k] for k in
                                  ("chips", "window_s", "busy_s",
                                   "clock_offsets_s", "programs")}))
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in spec.end_to_end(workload)}
    log("metrics: " + repr(metrics))

    # ---- correctness: once the window has closed and the state is freed
    sample = check.sample(run.requests, seed, dep.cell["check"])
    dep.free()
    t0 = time.monotonic()
    verdict = check.compare(dep.config, seed, sample, devices, dep.cell,
                            control=control)
    log(f"check: {len(sample)} requests, {verdict['positions']} served "
        f"tokens against the float32 reference in "
        f"{time.monotonic() - t0:.3f} s; {verdict['summary']}")
    result["correct"] = verdict["correct"]
    result["metrics"] = metrics
    result["device"] = device_info(devices, peak)
    if run.trace is not None:
        result["device"]["busy_s"] = run.trace["busy_s"]
        result["device"]["window_s"] = run.trace["window_s"]
        result["breakdown"] = breakdown
    result["compared"] = verdict["compared"]
    return result
