"""Serving driver: schedule a heterogeneous pool, build the asymmetric
pipeline engine, and serve a Poisson workload end to end.

  PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-1.8b \
      --reduced --cluster case_study --rate 2 --duration 5 --deadline 30

Every flag is a ``serving.config.ServingConfig`` field — the CLI schema,
feature gating and derived planning inputs all live there; this driver is
just the parse -> schedule -> build -> serve spine.

Without ``--reduced`` the scheduler plans the model that is served, and on
an accelerator the pool may name no more devices than are present (e.g.
``--cluster v5e_1`` on one TPU v5e chip). ``--reduced`` is the CPU
demonstration of the paper's setting: the scheduler plans the FULL model
on the chosen pool, and the reduced variant runs through that plan's stage
layout, projected onto its layer count (stage count, TP degrees and layer
ratios kept; device ids fold onto the host's devices). ``chip_smoke.py`` at
the repository root is the end-to-end run on the chip.
"""
from __future__ import annotations

import jax

from repro.configs import get_config
from repro.core.plan import Assignment, PipelinePlan, StagePlan
from repro.core.scheduler import schedule
from repro.launch.compile_cache import configure_compile_cache
from repro.serving.config import ServingConfig


def scale_assignment(asg: Assignment, full_layers: int,
                     run_layers: int) -> Assignment:
    """Project a full-model layer split onto the reduced layer count,
    keeping stage proportions (>=1 layer per stage; stages collapse if the
    reduced model has fewer layers than stages)."""
    out = []
    for pipe in asg.pipelines:
        stages = pipe.stages[:run_layers]
        raw = [s.num_layers / full_layers * run_layers for s in stages]
        ls = [max(1, int(round(r))) for r in raw]
        while sum(ls) > run_layers:
            i = max(range(len(ls)), key=lambda i: ls[i] - raw[i])
            if ls[i] > 1:
                ls[i] -= 1
            else:
                ls.pop(i)
                stages = stages[:i] + stages[i + 1:]
                raw.pop(i)
        while sum(ls) < run_layers:
            i = min(range(len(ls)), key=lambda i: ls[i] - raw[i])
            ls[i] += 1
        out.append(PipelinePlan(
            [StagePlan(list(s.device_ids), l) for s, l in zip(stages, ls)],
            cost=pipe.cost, bottleneck=pipe.bottleneck))
    return Assignment(out)


def check_pool_fits(pool_size: int, devices) -> None:
    """On an accelerator a plan runs on the devices it names, so the pool
    may not be larger than the devices present. (On the CPU, plans for
    larger pools fold onto the host's devices.)"""
    if devices[0].platform != "cpu" and pool_size > len(devices):
        raise SystemExit(
            f"the pool has {pool_size} devices but only {len(devices)} "
            f"{devices[0].platform} devices are present")


def main() -> None:
    sv = ServingConfig.parse().normalized()
    pool = sv.pool()
    check_pool_fits(len(pool), jax.devices())
    configure_compile_cache()
    cfg_full = get_config(sv.arch)
    print(f"scheduling {sv.arch} on {sv.cluster} "
          f"({len(pool)} devices, ${pool.price_per_hour:.2f}/h)...")
    res = schedule(pool, cfg_full, sv.task(), **sv.schedule_kwargs())
    plan = res.plan
    print(f"  assignment: {plan.assignment.describe()}")
    print(f"  estimated SLO attainment: {res.attainment*100:.1f}%")
    if sv.disaggregate:
        print(f"  roles: "
              f"{plan.roles if plan.roles is not None else 'colocated'}")
    if sv.spec_decode:
        print(f"  spec-k per replica: {plan.spec_ks}")
    if sv.kv_dtype == "search":
        shown = [d or "auto" for d in (plan.kv_dtypes or [])]
        print(f"  kv-dtype per replica: {shown}")
    if sv.host_mem_gb > 0:
        print(f"  host-tier blocks per replica: {plan.host_blocks}")

    from repro.serving.engine import InferenceEngine
    cfg = cfg_full.reduced() if sv.reduced else cfg_full
    asg = scale_assignment(plan.assignment, cfg_full.num_layers,
                           cfg.num_layers) if sv.reduced else None
    engine = InferenceEngine.from_config(cfg, plan, sv, assignment=asg,
                                         cluster=pool)
    reqs = sv.workload(cfg.vocab_size)

    # ---- observability (repro.obs) --------------------------------------
    tracer = metrics = None
    if sv.trace_out or sv.calibrate:
        from repro.obs.trace import Tracer
        tracer = Tracer()
    if sv.metrics_out or sv.calibrate:
        from repro.obs.metrics import MetricsRegistry
        metrics = MetricsRegistry()

    print(f"serving {len(reqs)} requests...")
    stats = engine.serve(reqs, deadline=sv.deadline, tracer=tracer,
                         metrics=metrics)
    print("  " + stats.summary())

    if tracer is not None and metrics is not None:
        from repro.obs.metrics import phase_histograms_from_trace
        phase_histograms_from_trace(tracer, metrics)
    if sv.trace_out:
        tracer.write(sv.trace_out)
        print(f"  trace: {sv.trace_out} ({len(tracer.events)} events)")
    if sv.metrics_out:
        metrics.to_jsonl(sv.metrics_out)
        print(f"  metrics: {sv.metrics_out}")
    if sv.calibrate:
        from repro.core import cost_model as cm
        from repro.obs.calibration import (CostCalibrator,
                                           predictions_from_phase_costs)
        from repro.obs.report import calibration_table
        cal = CostCalibrator()
        task = sv.task()
        profile = cm.ModelProfile.from_config(
            cfg_full, bytes_per_el=task.bytes_per_el)
        for i, pipe in enumerate(plan.assignment.pipelines):
            pc = cm.pipeline_phase_costs(
                pool, [list(s.device_ids) for s in pipe.stages],
                [s.num_layers for s in pipe.stages], profile, task)
            predictions_from_phase_costs(cal, i, pc, task.s_in)
        cal.observe_trace(tracer)
        for line in calibration_table(cal):
            print("  " + line)


if __name__ == "__main__":
    main()
