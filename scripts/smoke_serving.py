#!/usr/bin/env python
"""Serving smokes, runnable by CI (scripts/ci.sh) and humans alike:

  PYTHONPATH=src python scripts/smoke_serving.py                 # everything
  PYTHONPATH=src python scripts/smoke_serving.py kernels         # one suite
  PYTHONPATH=src python scripts/smoke_serving.py serving disagg  # a subset

Suites:
  kernels  paged decode + context-prefill + multi-token verification
           Pallas kernels in interpret mode (a GPU-less CI's only route
           through the block-table index maps)
  serving  continuous + paged serving on a 2-stage TP=2 asymmetric pipeline
           over 4 virtual host devices, paged bit-identical to contiguous
  prefix   copy-on-write prefix caching + chunked prefill, warm == cold
  disagg   disaggregated prefill/decode with KV-page handoff, token-
           identical to colocated serving on the same 4-device pipeline
  cluster  host-tier page spill + shared prefix directory across two
           replicas: demotions/promotions/peer fetches on the virtual
           clock, token-identical to cold paged serving
  spec     speculative decoding (n-gram + self-draft proposers), token-
           identical to plain greedy decode on the same 4-device pipeline
           with strictly fewer target decode steps
  quant    quantized KV pages: int8/fp8 fused-dequant paged kernels in
           interpret mode (vs the unquantized kernels on materialized-
           dequant pages: int8 bitwise, fp8 within 4 f32 ulps of the
           output scale; tolerance vs the pure-JAX quant
           oracles), then int8-pool serving on the 4-device pipeline
           (greedy tokens vs fp32, resident-byte savings reported)
  obs      HexTrace observability: a traced + metered serve reproduces the
           untraced one token for token, and the exported Chrome trace +
           metrics JSONL pass the report CLI's schema gate

Each suite asserts hard invariants and prints one OK line; any failure is
a non-zero exit. The multi-device suites force 4 virtual CPU devices
themselves, so no XLA_FLAGS incantation is needed.
"""
from __future__ import annotations

import argparse
import os
import re
import sys
import time

# must happen before jax import: 4 virtual host devices, CPU only — an
# inherited count from the caller's shell is OVERRIDDEN, not trusted, so
# the suites' `len(devices) == 4` contract always holds
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                os.environ.get("XLA_FLAGS", ""))
os.environ["XLA_FLAGS"] = \
    (_flags + " --xla_force_host_platform_device_count=4").strip()

import jax                                              # noqa: E402
import numpy as np                                      # noqa: E402

T0 = time.monotonic()
KVSAN = False     # --kvsan: serve every suite under the lifecycle sanitizer


def _ok(msg: str) -> None:
    print(f"smoke OK [{time.monotonic() - T0:5.1f}s] {msg}", flush=True)


# ---------------------------------------------------------------------------
# Suite: kernels (Pallas interpret mode)
# ---------------------------------------------------------------------------

def suite_kernels() -> None:
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    key = jax.random.PRNGKey(0)
    b, hq, hkv, d, bs, nblk = 2, 4, 2, 32, 16, 12
    rn = lambda i, *s: jax.random.normal(jax.random.fold_in(key, i), s)  # noqa: E731
    q, kp, vp = (rn(1, b, 1, hq, d), rn(2, nblk, bs, hkv, d),
                 rn(3, nblk, bs, hkv, d))
    bt = jnp.asarray(np.array([[3, 1, 4, 0], [5, 9, 2, 6]], np.int32))
    kv_len = jnp.array([41, 64])
    qc = rn(4, b, 8, hq, d)                  # 8-token context chunk
    q_start = jnp.array([17, 40])
    ctx_len = jnp.array([17 + 8, 40 + 5])
    qv = rn(7, b, 4, hq, d)                  # 4-candidate verification chunk
    v_start = jnp.array([21, 33])
    v_len = jnp.array([21 + 4, 33 + 2])      # ragged candidate counts
    with ops.backend("pallas_interpret"):
        out = ops.paged_decode_attention(q, kp, vp, bt, kv_len=kv_len)
        out_c = ops.paged_context_attention(qc, kp, vp, bt,
                                            q_start=q_start, kv_len=ctx_len)
        out_v = ops.paged_verify_attention(qv, kp, vp, bt,
                                           kv_start=v_start, kv_len=v_len)
    assert ops.get_backend() == "xla", "backend leaked out of the context"
    want = ref.paged_decode_attention_ref(q, kp, vp, bt, kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    want_c = ref.paged_context_attention_ref(qc, kp, vp, bt,
                                             q_start=q_start, kv_len=ctx_len)
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(want_c),
                               atol=2e-5)
    want_v = ref.paged_verify_attention_ref(qv, kp, vp, bt,
                                            kv_start=v_start, kv_len=v_len)
    np.testing.assert_allclose(np.asarray(out_v), np.asarray(want_v),
                               atol=2e-5)
    _ok("paged decode + context + verify kernels (interpret mode)")


# ---------------------------------------------------------------------------
# Shared serving scaffolding (4 virtual devices)
# ---------------------------------------------------------------------------

def _setup():
    from repro.configs import get_config
    from repro.core.plan import Assignment, PipelinePlan, StagePlan

    devs = jax.devices()
    assert len(devs) == 4, devs
    cfg = get_config("granite-8b").reduced()
    L = cfg.num_layers
    # a TP=2 -> TP=2 two-stage asymmetric pipeline over all 4 devices —
    # the multi-device path a GPU-less CI would otherwise never run
    asg = Assignment([
        PipelinePlan([StagePlan([0, 1], 1), StagePlan([2, 3], L - 1)],
                     cost=0.1, bottleneck=0.1),
    ])
    return cfg, asg


def _engine(cfg, asg, **kw):
    from repro.serving.engine import InferenceEngine
    kw.setdefault("kvsan", KVSAN)
    eng = InferenceEngine(cfg, asg, key=jax.random.PRNGKey(0),
                          policy="continuous", n_slots=4, max_len=48, **kw)
    if kw["kvsan"]:
        # under --kvsan every serve must come back leak-free; violations
        # raise KVSanViolation mid-serve on their own
        inner = eng.serve

        def serve(reqs, **skw):
            stats = inner(reqs, **skw)
            assert stats.kvsan_leaks == 0, stats.summary()
            return stats
        eng.serve = serve
    return eng


def suite_serving() -> None:
    from repro.serving.request import synth_workload

    cfg, asg = _setup()
    reqs = synth_workload(rate=40.0, duration=0.25, vocab=cfg.vocab_size,
                          prompt_len=8, prompt_jitter=5, out_len=4, seed=1)
    stats = _engine(cfg, asg).serve(reqs, deadline=120.0)
    assert len(stats.latencies) == len(reqs) and len(reqs) > 0
    assert stats.attainment == 1.0, stats.summary()
    for r in reqs:
        assert r.output is not None and len(r.output) == 4, r.rid
    _ok(f"continuous serving: {stats.summary()}")

    # paged serving over the same pipeline: per-stage block pools,
    # identical outputs to the contiguous pass above
    reqs_p = synth_workload(rate=40.0, duration=0.25, vocab=cfg.vocab_size,
                            prompt_len=8, prompt_jitter=5, out_len=4, seed=1)
    stats_p = _engine(cfg, asg, cache_layout="paged",
                      block_size=8).serve(reqs_p, deadline=120.0)
    assert stats_p.attainment == 1.0, stats_p.summary()
    for r, rp in zip(reqs, reqs_p):
        assert list(r.output) == list(rp.output), (r.rid,)
    _ok(f"paged == contiguous: {stats_p.summary()}")


def suite_prefix() -> None:
    from repro.serving.request import shared_prefix_workload

    cfg, asg = _setup()

    def wl():
        return shared_prefix_workload(rate=4.0, duration=2.0,
                                      vocab=cfg.vocab_size, shared_len=24,
                                      unique_len=6, out_len=4, seed=3)

    reqs_cold = wl()
    _engine(cfg, asg, cache_layout="paged",
            block_size=8).serve(reqs_cold, deadline=120.0)
    reqs_warm = wl()
    stats_w = _engine(cfg, asg, cache_layout="paged", block_size=8,
                      prefix_caching=True,
                      prefill_chunk=16).serve(reqs_warm, deadline=120.0)
    assert stats_w.prefix_hits > 0, stats_w.summary()
    assert stats_w.prefill_tokens < sum(len(r.prompt) for r in reqs_warm)
    for rc, rw in zip(reqs_cold, reqs_warm):
        assert list(rc.output) == list(rw.output), (rc.rid,)
    _ok(f"prefix caching warm == cold: {stats_w.summary()}")


def suite_disagg() -> None:
    from repro.configs import get_config
    from repro.core.plan import Assignment, PipelinePlan, StagePlan
    from repro.serving.loop import VirtualClock
    from repro.serving.request import synth_workload

    cfg = get_config("granite-8b").reduced()
    L = cfg.num_layers
    # two replicas over the 4 devices, with DIFFERENT stage splits: the
    # prefill->decode page handoff must survive layer regrouping
    asg = Assignment([
        PipelinePlan([StagePlan([0], 1), StagePlan([1], L - 1)],
                     cost=0.1, bottleneck=0.1),
        PipelinePlan([StagePlan([2], L - 1), StagePlan([3], 1)],
                     cost=0.1, bottleneck=0.1),
    ])

    def wl():
        return synth_workload(rate=10.0, duration=1.0, vocab=cfg.vocab_size,
                              prompt_len=10, prompt_jitter=5, out_len=4,
                              seed=2)

    reqs_c = wl()
    _engine(cfg, asg, cache_layout="paged",
            block_size=8).serve(reqs_c, deadline=1e9, clock=VirtualClock())
    reqs_d = wl()
    stats_d = _engine(cfg, asg, cache_layout="paged", block_size=8,
                      disaggregate=True).serve(reqs_d, deadline=1e9,
                                               clock=VirtualClock())
    assert stats_d.migrations == len(reqs_d), stats_d.summary()
    assert stats_d.migrated_kv_bytes > 0
    for rc, rd in zip(reqs_c, reqs_d):
        assert list(rc.output) == list(rd.output), (rc.rid,)
    _ok(f"disaggregated == colocated: {stats_d.summary()}")


def suite_cluster() -> None:
    from repro.configs import get_config
    from repro.core.plan import Assignment, PipelinePlan, StagePlan
    from repro.serving.loop import VirtualClock
    from repro.serving.request import shared_prefix_workload

    cfg = get_config("granite-8b").reduced()
    L = cfg.num_layers
    # two replicas over the 4 devices: the shared prefix directory must
    # route revisits across them and fetch peer-resident pages
    asg = Assignment([
        PipelinePlan([StagePlan([0], 1), StagePlan([1], L - 1)],
                     cost=0.1, bottleneck=0.1),
        PipelinePlan([StagePlan([2], 1), StagePlan([3], L - 1)],
                     cost=0.1, bottleneck=0.1),
    ])

    def wl():
        return shared_prefix_workload(rate=6.0, duration=2.0,
                                      vocab=cfg.vocab_size, shared_len=24,
                                      unique_len=6, out_len=4, seed=7)

    reqs_c = wl()
    _engine(cfg, asg, cache_layout="paged",
            block_size=8).serve(reqs_c, deadline=1e9, clock=VirtualClock())
    # tiered + clustered: pools too small for the shared set, so hot
    # heads demote to the host tier and come back via promotion or a
    # peer fetch instead of a re-prefill
    reqs_t = wl()
    stats_t = _engine(cfg, asg, cache_layout="paged", block_size=8,
                      stage_blocks=[8, 8], prefix_caching=True,
                      host_blocks=32, host_swap_cost=0.01,
                      cluster_prefix=True, prefix_route_weight=0.5,
                      prefill_token_cost=0.125).serve(
                          reqs_t, deadline=1e9, clock=VirtualClock())
    assert stats_t.host_demotions > 0, stats_t.summary()
    assert stats_t.host_promotions + stats_t.prefix_fetches > 0, \
        stats_t.summary()
    assert stats_t.prefill_tokens < sum(len(r.prompt) for r in reqs_t)
    for rc, rt in zip(reqs_c, reqs_t):
        assert list(rc.output) == list(rt.output), (rc.rid,)
    _ok(f"tiered cluster prefix == cold: {stats_t.summary()}")


def suite_spec() -> None:
    from repro.serving.loop import VirtualClock
    from repro.serving.request import synth_workload
    from repro.serving.spec import SpecConfig

    cfg, asg = _setup()

    def wl():
        return synth_workload(rate=10.0, duration=0.5, vocab=cfg.vocab_size,
                              prompt_len=8, prompt_jitter=5, out_len=6,
                              seed=5)

    reqs_b = wl()
    _engine(cfg, asg, cache_layout="paged",
            block_size=8).serve(reqs_b, deadline=1e9, clock=VirtualClock())
    total = sum(len(r.output) for r in reqs_b)
    # n-gram proposing, then self-draft (the acceptance upper bound) —
    # both must reproduce plain greedy decode token for token, in
    # strictly fewer target decode steps for the draft
    reqs_n = wl()
    st_n = _engine(cfg, asg, cache_layout="paged", block_size=8,
                   spec_decode=True, spec_k=3).serve(
                       reqs_n, deadline=1e9, clock=VirtualClock())
    assert st_n.spec_steps > 0 and st_n.spec_tokens == total
    for rb, rn_ in zip(reqs_b, reqs_n):
        assert list(rb.output) == list(rn_.output), (rb.rid,)
    reqs_d = wl()
    st_d = _engine(cfg, asg, cache_layout="paged", block_size=8,
                   spec_decode=True, spec_k=3,
                   draft_model=cfg).serve(reqs_d, deadline=1e9,
                                          clock=VirtualClock())
    assert st_d.spec_steps < total, (st_d.spec_steps, total)
    for rb, rd in zip(reqs_b, reqs_d):
        assert list(rb.output) == list(rd.output), (rb.rid,)
    _ok(f"spec == greedy (ngram: {st_n.spec_steps} steps, draft: "
        f"{st_d.spec_steps} steps for {total} tokens)")


def suite_quant() -> None:
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    from repro.kernels.paged_attention import (
        paged_context_attention_pallas, paged_decode_attention_pallas,
        paged_verify_attention_pallas)
    from repro.models import quant as Q

    key = jax.random.PRNGKey(0)
    b, hq, hkv, d, bs, nblk = 2, 4, 2, 32, 16, 12
    rn = lambda i, *s: jax.random.normal(jax.random.fold_in(key, i), s)  # noqa: E731
    q, kp, vp = (rn(1, b, 1, hq, d), rn(2, nblk, bs, hkv, d),
                 rn(3, nblk, bs, hkv, d))
    bt = jnp.asarray(np.array([[3, 1, 4, 0], [5, 9, 2, 6]], np.int32))
    kv_len = jnp.array([41, 64])
    qc = rn(4, b, 8, hq, d)
    q_start = jnp.array([17, 40])
    ctx_len = jnp.array([17 + 8, 40 + 5])
    qv = rn(7, b, 4, hq, d)
    v_start = jnp.array([21, 33])
    v_len = jnp.array([21 + 4, 33 + 2])
    for kv_dtype in ("int8", "fp8"):
        kq, ks = Q.quantize_kv_rows(kp, kv_dtype)
        vq, vs = Q.quantize_kv_rows(vp, kv_dtype)
        kd, vd = Q.dequantize_kv(kq, ks), Q.dequantize_kv(vq, vs)
        with ops.backend("pallas_interpret"):
            out = ops.paged_decode_attention(q, kq, vq, bt, kv_len=kv_len,
                                             k_scale=ks, v_scale=vs)
            out_c = ops.paged_context_attention(
                qc, kq, vq, bt, q_start=q_start, kv_len=ctx_len,
                k_scale=ks, v_scale=vs)
            out_v = ops.paged_verify_attention(
                qv, kq, vq, bt, kv_start=v_start, kv_len=v_len,
                k_scale=ks, v_scale=vs)
        # fused dequant vs the unquantized kernels on materialized-dequant
        # pages: int8 bit for bit; fp8 within a few f32 ulps of the output
        # scale (XLA's CPU backend fuses the fp8 convert into the score
        # dot, which then sums in another order)...
        for fused, mat in (
                (out, paged_decode_attention_pallas(
                    q, kd, vd, bt, kv_len=kv_len, interpret=True)),
                (out_c, paged_context_attention_pallas(
                    qc, kd, vd, bt, q_start=q_start, kv_len=ctx_len,
                    interpret=True)),
                (out_v, paged_verify_attention_pallas(
                    qv, kd, vd, bt, kv_start=v_start, kv_len=v_len,
                    interpret=True))):
            fused, mat = np.asarray(fused), np.asarray(mat)
            if kv_dtype == "int8":
                assert np.array_equal(fused, mat), kv_dtype
            else:
                ulp = np.finfo(np.float32).eps * np.abs(mat).max()
                assert np.abs(fused - mat).max() <= 4 * ulp, kv_dtype
        # ...and sits at the kernel tolerance against the pure-JAX oracles
        np.testing.assert_allclose(np.asarray(out), np.asarray(
            ref.paged_decode_attention_quant_ref(q, kq, vq, ks, vs, bt,
                                                 kv_len=kv_len)), atol=2e-5)
        np.testing.assert_allclose(np.asarray(out_c), np.asarray(
            ref.paged_context_attention_quant_ref(
                qc, kq, vq, ks, vs, bt, q_start=q_start, kv_len=ctx_len)),
            atol=2e-5)
        np.testing.assert_allclose(np.asarray(out_v), np.asarray(
            ref.paged_verify_attention_quant_ref(
                qv, kq, vq, ks, vs, bt, kv_start=v_start, kv_len=v_len)),
            atol=2e-5)
    _ok("quantized paged kernels: fused dequant == materialized (int8 "
        "bitwise, fp8 within 4 f32 ulps), oracles within 2e-5 "
        "(interpret mode)")

    # int8 page pools end to end on the multi-device pipeline
    from repro.serving.request import synth_workload

    cfg, asg = _setup()

    def wl():
        return synth_workload(rate=40.0, duration=0.25,
                              vocab=cfg.vocab_size, prompt_len=8,
                              prompt_jitter=5, out_len=4, seed=1)

    reqs_f = wl()
    _engine(cfg, asg, cache_layout="paged",
            block_size=8).serve(reqs_f, deadline=120.0)
    reqs_q = wl()
    stats_q = _engine(cfg, asg, cache_layout="paged", block_size=8,
                      kv_dtype="int8").serve(reqs_q, deadline=120.0)
    assert stats_q.attainment == 1.0, stats_q.summary()
    assert stats_q.kv_bytes_resident > 0 and stats_q.kv_bytes_saved > 0, \
        stats_q.summary()
    match = sum(list(rf.output) == list(rq.output)
                for rf, rq in zip(reqs_f, reqs_q))
    # KV quantization may legitimately flip a near-tie argmax; on this
    # short workload the vast majority of generations must stay identical
    assert match >= 0.75 * len(reqs_f), (match, len(reqs_f))
    _ok(f"int8 KV serving: {match}/{len(reqs_f)} greedy outputs == fp32, "
        f"{stats_q.summary()}")


def suite_obs() -> None:
    import tempfile
    from repro.obs.metrics import MetricsRegistry
    from repro.obs.report import main as report_main
    from repro.obs.trace import Tracer, validate_chrome_trace
    from repro.serving.loop import VirtualClock
    from repro.serving.request import shared_prefix_workload

    cfg, asg = _setup()

    def wl():
        return shared_prefix_workload(rate=6.0, duration=1.5,
                                      vocab=cfg.vocab_size, shared_len=24,
                                      unique_len=6, out_len=4, seed=9)

    def eng():
        return _engine(cfg, asg, cache_layout="paged", block_size=8,
                       prefix_caching=True, prefill_chunk=16)

    # tracing is pure observation: the traced serve must reproduce the
    # untraced one token for token
    reqs_off = wl()
    eng().serve(reqs_off, deadline=1e9, clock=VirtualClock())
    reqs_on = wl()
    tracer, metrics = Tracer(), MetricsRegistry()
    stats = eng().serve(reqs_on, deadline=1e9, clock=VirtualClock(),
                        tracer=tracer, metrics=metrics)
    for ro, rt in zip(reqs_off, reqs_on):
        assert list(ro.output) == list(rt.output), (ro.rid,)
    errs = validate_chrome_trace(
        tracer.to_chrome(),
        require_spans=["serve", "queue_wait", "iteration", "prefill",
                       "decode"])
    assert not errs, errs
    assert metrics.total("serve_n_requests") == len(reqs_on), \
        metrics.collect()
    # exported artifacts must survive the report CLI's schema gate
    with tempfile.TemporaryDirectory() as td:
        trace_p = os.path.join(td, "trace.json")
        metrics_p = os.path.join(td, "metrics.jsonl")
        tracer.write(trace_p)
        metrics.to_jsonl(metrics_p)
        rc = report_main([metrics_p, "--trace", trace_p,
                          "--require-spans", "prefill,decode"])
        assert rc == 0, rc
    _ok(f"traced == untraced, {len(tracer.events)} events validate "
        f"({stats.summary()})")


def suite_chaos() -> None:
    from repro.configs import get_config
    from repro.core.plan import Assignment, PipelinePlan, StagePlan
    from repro.core.resched import DriftDetector
    from repro.serving.loop import VirtualClock
    from repro.serving.request import synth_workload
    from repro.serving.resched import OnlineRescheduler

    cfg = get_config("granite-8b").reduced()
    L = cfg.num_layers
    # two replicas with different stage splits (the disagg topology):
    # chaos must survive layer regrouping between source and survivors
    asg = Assignment([
        PipelinePlan([StagePlan([0], 1), StagePlan([1], L - 1)],
                     cost=0.1, bottleneck=0.1),
        PipelinePlan([StagePlan([2], L - 1), StagePlan([3], 1)],
                     cost=0.1, bottleneck=0.1),
    ])

    def wl(out_len=4):
        return synth_workload(rate=10.0, duration=1.0, vocab=cfg.vocab_size,
                              prompt_len=10, prompt_jitter=5,
                              out_len=out_len, seed=2)

    # replica kill mid-request: the controller evacuates the dead
    # replica's in-flight work and re-dispatches it from the prompts —
    # survivors regenerate the IDENTICAL token streams (greedy decode),
    # and under --kvsan the kill must release every page (zero leaks)
    reqs_c = wl()
    _engine(cfg, asg, cache_layout="paged",
            block_size=8).serve(reqs_c, deadline=1e9, clock=VirtualClock())
    reqs_k = wl()
    eng = _engine(cfg, asg, cache_layout="paged", block_size=8)
    ctl = OnlineRescheduler(kills=[(2.0, 1)])
    eng.router.attach_controller(ctl)
    stats = eng.serve(reqs_k, deadline=1e9, clock=VirtualClock())
    assert stats.dropped == 0, stats.summary()
    kills = [e for e in ctl.events if e["kind"] == "kill"]
    assert kills and kills[0]["orphans"] > 0, ctl.events
    assert ctl.redispatches > 0
    for rc, rk in zip(reqs_c, reqs_k):
        assert list(rc.output) == list(rk.output), (rc.rid,)
    _ok(f"replica kill: {kills[0]['orphans']} orphans re-dispatched, "
        f"tokens == cold ({stats.summary()})")

    # live role re-split mid-decode: decoding slots migrate WITH their
    # emitted tokens (pages + sampling state) and the streams continue
    # exactly where they stopped
    reqs_c2 = wl(out_len=6)
    _engine(cfg, asg, cache_layout="paged",
            block_size=8).serve(reqs_c2, deadline=1e9, clock=VirtualClock())
    reqs_m = wl(out_len=6)
    eng2 = _engine(cfg, asg, cache_layout="paged", block_size=8)
    fired = []

    def resolver(sig, c, now):
        if fired:
            return None
        fired.append(sig.kind)
        return {"roles": ["prefill", "decode"]}

    ctl2 = OnlineRescheduler(
        detector=DriftDetector(rate=1.0, min_events=4, window=5.0),
        resolver=resolver)
    eng2.router.attach_controller(ctl2)
    stats2 = eng2.serve(reqs_m, deadline=1e9, clock=VirtualClock())
    assert stats2.dropped == 0, stats2.summary()
    roles_ev = [e for e in ctl2.events if e["kind"] == "roles"]
    assert roles_ev and roles_ev[0]["moved"] > 0, ctl2.events
    for rc, rm in zip(reqs_c2, reqs_m):
        assert list(rc.output) == list(rm.output), (rc.rid,)
    _ok(f"live role migration: {roles_ev[0]['moved']} slots moved "
        f"mid-decode on {fired[0]}, tokens == cold ({stats2.summary()})")


SUITES = {
    "kernels": suite_kernels,
    "serving": suite_serving,
    "prefix": suite_prefix,
    "disagg": suite_disagg,
    "cluster": suite_cluster,
    "spec": suite_spec,
    "quant": suite_quant,
    "obs": suite_obs,
    "chaos": suite_chaos,
}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("suites", nargs="*", default=[],
                    choices=[*SUITES, []],
                    help="suites to run (default: all)")
    ap.add_argument("--kvsan", action="store_true",
                    help="serve every suite under the KVSAN page-lifecycle "
                         "sanitizer (repro.analysis.kvsan): violations "
                         "raise, leaks fail the suite, tokens must be "
                         "identical to the sanitizer-off baselines the "
                         "suites already compare against")
    args = ap.parse_args()
    global KVSAN
    KVSAN = args.kvsan
    names = args.suites or list(SUITES)
    for name in names:
        SUITES[name]()
    tag = " [kvsan]" if KVSAN else ""
    print(f"smoke_serving: {', '.join(names)} all OK{tag} "
          f"({time.monotonic() - T0:.1f}s)")


if __name__ == "__main__":
    sys.exit(main())
