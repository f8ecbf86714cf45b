"""Inference engine: builds replica pipelines from a scheduled Assignment and
serves workloads through the Router.

The Assignment's global device ids map onto actual jax devices: on a real
heterogeneous deployment those are the pool's accelerators; in this repo's
CPU demonstration they are host devices (tests spawn a subprocess with
``--xla_force_host_platform_device_count`` to get several).
"""
from __future__ import annotations

import warnings
from typing import List, Optional, Sequence

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.plan import Assignment
from repro.models import model as M
from repro.serving.disagg import KVLink
from repro.serving.pipeline import (AsymmetricPipeline,
                                    context_mode_supported,
                                    slot_mode_supported)
from repro.serving.request import Request
from repro.serving.router import Router, ServeStats, default_roles
from repro.serving.spec import SpecConfig


def plan_devices(devices: Sequence, device_ids: Sequence[int]) -> List:
    """The jax devices a plan stage names. On an accelerator every id must
    be a device that is present. On the CPU, where tests demonstrate plans
    for pools larger than the host, ids fold modulo the device count and
    duplicates collapse (numerically identical; TP only changes layout)."""
    if devices[0].platform != "cpu":
        missing = [d for d in device_ids if d >= len(devices)]
        if missing:
            raise ValueError(
                f"the plan names device ids {missing}, but only "
                f"{len(devices)} {devices[0].platform} devices are present")
        return [devices[d] for d in device_ids]
    return list(dict.fromkeys(devices[d % len(devices)] for d in device_ids))


class InferenceEngine:
    """``disaggregate=True`` splits the inference phases across replicas:
    arrivals prefill on ``role="prefill"`` replicas and their KV pages
    migrate to ``role="decode"`` replicas (serving.disagg). ``roles``
    overrides the default split (e.g. the scheduler's SLO-scored one);
    the transfer is modeled as ``kv_bytes / link_bandwidth`` on the
    serving clock — flat via ``kv_link_gbps`` (0 = ideal interconnect),
    or per-replica-pair from ``cluster``'s comm matrices when given.

    ``spec_decode=True`` turns on speculative decoding (serving.spec):
    a proposer guesses up to ``spec_k`` tokens per slot per iteration and
    the target commits the verified prefix in one multi-token step —
    token-identical to plain greedy decode. ``draft_model`` names a small
    draft architecture from ``configs/`` (or passes a ModelConfig
    directly); without it the weight-free n-gram/prompt-lookup proposer
    runs. ``spec_ks`` overrides the depth PER REPLICA (the scheduler's
    acceptance-aware ``SearchResult.spec_ks``; 0 disables speculation on
    that replica). Needs the paged layout and an attention-only stack.

    ``kv_dtype`` stores the paged KV pools at reduced precision
    ("fp32"/"bf16"/"int8"/"fp8"; int8/fp8 pages carry per-token-per-head
    scales and dequantize inside the paged kernels). ``kv_dtypes``
    overrides PER REPLICA (the scheduler's ``SearchResult.kv_dtypes``;
    None entry = model default); ``kv_guard_layers`` pins those global
    layer indices at model precision (quality guard, typically the
    first/last layers). Needs the paged layout.

    ``kvsan`` serves under the KVSAN page-lifecycle sanitizer
    (repro.analysis.kvsan): pure observation, token-identical, leaks
    surface as ``ServeStats.kvsan_leaks``. Needs the paged layout.

    ``host_blocks`` (one int, or per replica — the scheduler's
    ``SearchResult.host_blocks``) adds a host-memory page tier under each
    replica's device pools: prefix eviction demotes pages there instead
    of deleting them, and matches swap them back in at ``host_swap_cost``
    per block on the serving clock. ``cluster_prefix=True`` joins every
    replica into a shared prefix directory (serving.cluster_kv): prompts
    whose prefix lives only on a peer fetch the pages over the KV link,
    and the Router scores admission by resident prefix
    (``prefix_route_weight`` / ``host_route_weight``) against queue
    depth instead of pure least-loaded; ``route_seed`` makes tiebreaks
    seeded-random for routing benchmarks. Both need prefix_caching."""

    def __init__(self, cfg: ModelConfig, assignment: Assignment, *,
                 params=None, key=None, devices: Optional[Sequence] = None,
                 max_batch: int = 4, quantize: bool = False,
                 policy: str = "continuous", n_slots: int = 8,
                 max_len: int = 256, cache_layout: str = "contiguous",
                 block_size: int = 16, stage_blocks=None,
                 prefix_caching: bool = False, prefill_chunk: int = 0,
                 host_blocks=0, host_swap_cost: float = 0.0,
                 cluster_prefix: bool = False,
                 prefix_route_weight: float = 0.25,
                 host_route_weight: float = 0.5,
                 route_seed: Optional[int] = None,
                 disaggregate: bool = False,
                 roles: Optional[Sequence[str]] = None,
                 kv_link_gbps: float = 0.0, cluster=None,
                 step_costs: Optional[Sequence[float]] = None,
                 prefill_token_cost: float = 0.0,
                 spec_decode: bool = False, spec_k: int = 4,
                 draft_model=None,
                 spec_ks: Optional[Sequence[int]] = None,
                 spec_draft_token_cost: float = 0.0,
                 kv_dtype: Optional[str] = None,
                 kv_dtypes: Optional[Sequence[Optional[str]]] = None,
                 kv_guard_layers: Sequence[int] = (),
                 kvsan: bool = False):
        self.cfg = cfg
        devices = list(devices if devices is not None else jax.devices())
        key = key if key is not None else jax.random.PRNGKey(0)
        if quantize:
            from repro.models.quant import quantize_params
            params = quantize_params(
                params if params is not None else M.init_params(cfg, key),
                cfg)
        self.replicas: List[AsymmetricPipeline] = []
        for pipe in assignment.pipelines:
            stage_devs = [plan_devices(devices, st.device_ids)
                          for st in pipe.stages]
            # params=None: each stage builds its own share from the key
            self.replicas.append(AsymmetricPipeline(
                cfg, params, pipe.layer_split, stage_devs, key=key))
        if policy != "static" and not slot_mode_supported(cfg):
            warnings.warn(
                f"{cfg.name}: slot mode needs uniform text decode "
                "(SWA ring cache / encoder-decoder / VLM); serving with "
                "policy='static'", stacklevel=2)
            policy = "static"
        # ---- disaggregated prefill/decode ------------------------------
        if disaggregate and roles is None:
            roles = default_roles(len(self.replicas))
        if roles is not None and any(r != "both" for r in roles):
            if not context_mode_supported(cfg):
                warnings.warn(
                    f"{cfg.name}: disaggregation needs an attention-only "
                    "stack (recurrent running state has no pages to "
                    "migrate); serving colocated", stacklevel=2)
                roles = None
            elif len(self.replicas) < 2:
                warnings.warn(
                    "disaggregation needs >= 2 replicas; serving "
                    "colocated", stacklevel=2)
                roles = None
        # ---- speculative decoding --------------------------------------
        spec = None
        if spec_decode and spec_k < 1:
            # consistent with per-replica spec_ks, where 0 = plain decode
            warnings.warn("spec_k < 1 means plain decode; serving without "
                          "speculation", stacklevel=2)
            spec_decode = False
            spec_ks = None
        if spec_decode:
            if not context_mode_supported(cfg):
                warnings.warn(
                    f"{cfg.name}: speculative decoding needs an "
                    "attention-only stack (a recurrent sublayer's state "
                    "cannot roll back past a rejected candidate); serving "
                    "without it", stacklevel=2)
                spec_ks = None
            elif draft_model is not None:
                draft_cfg = draft_model
                if isinstance(draft_model, str):
                    from repro.configs import get_config
                    draft_cfg = get_config(draft_model)
                    if cfg.name.endswith("-reduced"):
                        draft_cfg = draft_cfg.reduced()
                if not context_mode_supported(draft_cfg):
                    warnings.warn(
                        f"{draft_cfg.name}: draft models must be "
                        "attention-only text decoders (recurrent draft "
                        "state cannot roll back past a rejected "
                        "candidate); falling back to the n-gram proposer",
                        stacklevel=2)
                    draft_cfg = None
                elif draft_cfg.vocab_size != cfg.vocab_size:
                    warnings.warn(
                        f"{draft_cfg.name}: draft vocab "
                        f"({draft_cfg.vocab_size}) differs from the "
                        f"target's ({cfg.vocab_size}); falling back to "
                        "the n-gram proposer", stacklevel=2)
                    draft_cfg = None
                if draft_cfg is not None:
                    spec = SpecConfig(
                        k=spec_k, proposer="draft", draft_cfg=draft_cfg,
                        draft_token_cost=spec_draft_token_cost)
                else:
                    spec = SpecConfig(
                        k=spec_k, draft_token_cost=spec_draft_token_cost)
            else:
                spec = SpecConfig(k=spec_k,
                                  draft_token_cost=spec_draft_token_cost)
        kv_link = None
        if (roles is not None and any(r != "both" for r in roles)) \
                or cluster_prefix:
            if cluster is not None:
                # per-pair alpha-beta costs: source replica's LAST stage to
                # destination replica's FIRST stage, like the cost model's
                # pipeline-comm term
                src = [list(p.stages[-1].device_ids)
                       for p in assignment.pipelines]
                dst = [list(p.stages[0].device_ids)
                       for p in assignment.pipelines]
                kv_link = KVLink.from_cluster(
                    cluster, [p.device_ids for p in assignment.pipelines],
                    src_stage_devices=src, dst_stage_devices=dst)
            else:
                kv_link = KVLink(gbps=kv_link_gbps)
        self.router = Router(self.replicas, max_batch=max_batch,
                             policy=policy, n_slots=n_slots, max_len=max_len,
                             cache_layout=cache_layout,
                             block_size=block_size,
                             stage_blocks=stage_blocks,
                             prefix_caching=prefix_caching,
                             prefill_chunk=prefill_chunk,
                             host_blocks=host_blocks,
                             host_swap_cost=host_swap_cost,
                             cluster_prefix=cluster_prefix,
                             prefix_route_weight=prefix_route_weight,
                             host_route_weight=host_route_weight,
                             route_seed=route_seed,
                             roles=roles, kv_link=kv_link,
                             step_costs=step_costs,
                             prefill_token_cost=prefill_token_cost,
                             spec=spec,
                             spec_ks=(list(spec_ks)
                                      if spec_ks is not None else None),
                             kv_dtype=kv_dtype,
                             kv_dtypes=(list(kv_dtypes)
                                        if kv_dtypes is not None else None),
                             kv_guard_layers=kv_guard_layers,
                             kvsan=kvsan)
        self.roles = self.router.roles

    @classmethod
    def from_config(cls, cfg: ModelConfig, plan, serving, *,
                    assignment: Optional[Assignment] = None, key=None,
                    cluster=None, **overrides) -> "InferenceEngine":
        """Build an engine from the two typed surfaces: a
        ``serving.config.ServingConfig`` (HOW to serve — policy, layout,
        feature flags) and a ``core.plan.DeploymentPlan`` (WHERE — the
        scheduler's replica layouts, roles, spec depths, KV precisions and
        host-tier split). ``assignment`` overrides the plan's layer split
        (e.g. the reduced-model projection from launch.serve) while the
        plan keeps supplying the per-replica dimensions; ``cluster`` feeds
        the per-pair KV-link cost model when no flat bandwidth is set;
        ``overrides`` pass through any raw ``__init__`` kwarg (n_slots,
        params, devices, ...)."""
        sv = serving.normalized()
        asg = assignment if assignment is not None else plan.assignment
        kw = dict(
            key=(key if key is not None
                 else jax.random.PRNGKey(sv.seed)),
            policy=sv.policy, max_len=sv.max_len(),
            cache_layout=sv.cache_layout, block_size=sv.block_size,
            prefix_caching=sv.prefix_caching,
            prefill_chunk=sv.prefill_chunk,
            host_blocks=(plan.host_blocks
                         if plan.host_blocks is not None else 0),
            host_swap_cost=sv.host_swap_cost,
            cluster_prefix=sv.cluster_prefix,
            prefix_route_weight=sv.prefix_route_weight,
            route_seed=sv.route_seed,
            # the role split is the SCHEDULER's verdict: roles=None means
            # colocated serving won the search, so don't force a default
            disaggregate=(sv.disaggregate and plan.roles is not None),
            roles=(plan.roles if sv.disaggregate else None),
            kv_link_gbps=sv.kv_link_gbps,
            cluster=(cluster if sv.disaggregate and sv.kv_link_gbps <= 0
                     else None),
            spec_decode=sv.spec_decode, spec_k=sv.spec_k,
            draft_model=(sv.draft_model or None),
            spec_draft_token_cost=sv.spec_draft_cost,
            spec_ks=(plan.spec_ks if sv.spec_decode else None),
            kv_dtype=sv.fixed_kv_dtype(),
            kv_dtypes=(plan.kv_dtypes if sv.kv_dtype == "search"
                       else None),
            kv_guard_layers=sv.guard_layers(cfg.num_layers),
            kvsan=sv.kvsan)
        kw.update(overrides)
        return cls(cfg, asg, **kw)

    def generate(self, prompts: Sequence[np.ndarray], *, max_new: int = 16
                 ) -> List[np.ndarray]:
        """One-shot batched generation on replica 0."""
        maxlen = max(len(p) for p in prompts)
        toks = np.zeros((len(prompts), maxlen), np.int32)
        kv_start = np.zeros(len(prompts), np.int32)
        for i, p in enumerate(prompts):
            toks[i, maxlen - len(p):] = p
            kv_start[i] = maxlen - len(p)
        out = self.replicas[0].generate(toks, max_new=max_new,
                                        kv_start=kv_start)
        return [out[i] for i in range(len(prompts))]

    def serve(self, requests: Sequence[Request], *, deadline: float,
              clock=None, tracer=None, metrics=None) -> ServeStats:
        return self.router.serve(requests, deadline, clock=clock,
                                 tracer=tracer, metrics=metrics)
