"""Chip smoke run: the serving path, end to end, at published widths.

``python chip_smoke.py`` (one chip) serves granite-8b cut to 16 of its 36
layers through the normal entry points: ``ServingConfig`` ->
``core.scheduler.schedule`` -> ``DeploymentPlan`` ->
``InferenceEngine.from_config`` -> paged continuous batching ->
``Router.serve``. It serves 8 requests of 512 prompt tokens and 64 output
tokens on the wall clock, then checks the logits the served path produced
for one request against a float32 reference forward of the same seeded
weights.

``python chip_smoke.py --chips 4`` runs only the four-chip phase: the full
36-layer model, more than one chip holds, planned by ``schedule`` over the
``v5e_2x2`` pool and served, then the same requests on a hand-built
asymmetric layout (three stages at TP 2, 1, 1) and a symmetric one (four
stages at TP 1), whose logits must agree.

Either run prints everything on earlier lines and ends with one JSON line,
``{"ok": true, "device": {...}}``. It exits non-zero, printing no result,
when JAX finds no TPU. It is a smoke run, not a benchmark: its times
include compilation and it claims no speed.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import warnings
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.configs.granite_8b import ONE_CHIP, ONE_CHIP_REDUCED
from repro.core.plan import Assignment, PipelinePlan, StagePlan
from repro.core.scheduler import schedule
from repro.kernels import ops
from repro.launch.compile_cache import configure_compile_cache
from repro.models import model as M
from repro.models import quant, reference
from repro.serving.config import ServingConfig
from repro.serving.engine import InferenceEngine
from repro.serving.loop import WallClock
from repro.serving.pipeline import head_names, layer_specs
from repro.serving.request import Request

CLOCK = WallClock()         # phase times, on the serve loop's wall clock
SEED = 0
N_REQUESTS = 8
PROMPT_LEN = 512
OUT_LEN = 64
N_SLOTS = 8
BLOCK_SIZE = 16
N_CHECK_DECODE = 4          # decode steps compared besides the prefill
ACTIVATION_RESERVE = 2 ** 30   # activations + compiler workspace
SHARE_SLACK = 2 ** 30       # in use beyond a device's weights and pools

# Tolerances for served logits against the float32 reference, by the
# configuration's dtype, over each compared position's logit vector:
# (relative L2 ||served - ref|| / ||ref||, max |served - ref| in units of
# std(ref)).
#
# bfloat16: the reference runs the same bf16 weight values in float32; the
# served path rounds activations and the KV cache to bf16 (8-bit mantissa:
# up to 2^-9 relative per rounding) through every layer, and on a TPU its
# float32 dots run in bf16 passes. On a TPU v5e at published widths and 16
# layers this measured relative L2 0.033-0.037 and max abs 0.14-0.16 std.
# The error of this random-weight model grows with width: on the CPU at 16
# layers, served bf16 measured 0.016 at d_model 1024, and int8 weights
# (one step below the configuration's precision) 0.033, 0.048 and 0.072 at
# d_model 512, 1024 and 2048, three times the bf16 error. The tolerance
# sits between the two, and every run checks that int8 weights
# (``CONTROL``) fail it. (fp8 KV pages add too little to be seen here.)
#
# float32 (the reduced configurations the tests serve): the served path
# and the reference differ only in summation order.
TOLERANCES = {"bfloat16": (0.06, 0.27), "float32": (1e-5, 1e-4)}
CONTROL = "int8 weights"

# Logits of two bf16 layouts of the same weights (four-chip phase) differ
# in where the tensor-parallel partial sums are rounded: each carries an
# error of the order above against float32, over 36 layers instead of 16.
# They get twice the room. A layout that misplaces a layer or sums a shard
# twice is off by the order of the logits themselves.
LAYOUT_REL_L2_TOL, LAYOUT_MAX_ABS_TOL_STD = (
    2 * t for t in TOLERANCES["bfloat16"])


def log(*args) -> None:
    print(*args, flush=True)


def fail_on_degraded_features() -> None:
    """Turn the UserWarnings that repro raises into errors: a feature gate
    that degrades with a warning must fail the run, not pass it on another
    path than the one asked for."""
    warnings.filterwarnings("error", category=UserWarning,
                            module=r"repro(\.|$)")


def device_info() -> Dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_line(devices: Sequence) -> str:
    parts = []
    for d in devices:
        ms = d.memory_stats() or {}
        parts.append(f"{d.id}: in_use={ms.get('bytes_in_use')} "
                     f"peak={ms.get('peak_bytes_in_use')} "
                     f"limit={ms.get('bytes_limit')}")
    return "; ".join(parts)


# ---- plan ------------------------------------------------------------------

def serving_config(arch: str, cluster: str, *, kvsan: bool) -> ServingConfig:
    return ServingConfig(
        arch=arch, cluster=cluster, rate=1.0, duration=float(N_REQUESTS),
        deadline=600.0, out_len=OUT_LEN, prompt_len=PROMPT_LEN,
        search_iters=4, seed=SEED, policy="continuous",
        cache_layout="paged", block_size=BLOCK_SIZE, kvsan=kvsan,
    ).normalized()


def plan_devices_used(plan) -> List[int]:
    return sorted(d for p in plan.assignment.pipelines
                  for s in p.stages for d in s.device_ids)


def make_plan(cfg: ModelConfig, sv: ServingConfig, n_devices: int, *,
              exact: bool = True):
    """Schedule ``cfg`` itself over the config's pool and check that the
    plan names exactly the devices present (``exact``), or at least none
    beyond them."""
    res = schedule(sv.pool(), cfg, sv.task(), **sv.schedule_kwargs())
    plan = res.plan
    used = plan_devices_used(plan)
    if exact:
        assert used == list(range(n_devices)), \
            f"the plan names devices {used}; {n_devices} are present"
    assert len(set(used)) == len(used) and set(used) <= set(
        range(n_devices)), f"the plan names devices {used}; " \
        f"{n_devices} are present"
    return plan


def layout_assignment(stages: Sequence[Sequence[int]],
                      layers: Sequence[int]) -> Assignment:
    """A one-replica plan with the given stage device sets and layers."""
    return Assignment([PipelinePlan(
        [StagePlan(list(d), n) for d, n in zip(stages, layers)])])


# ---- sizes -----------------------------------------------------------------

def weight_bytes(cfg: ModelConfig) -> int:
    shapes = jax.eval_shape(lambda k: M.init_params(cfg, k),
                            jax.random.PRNGKey(SEED))
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))


def kv_bytes_per_token(cfg: ModelConfig) -> int:
    item = jnp.dtype(cfg.dtype).itemsize
    return sum(cfg.kv_cache_bytes_per_token_layer(i, item)
               for i in range(cfg.num_layers))


def pool_blocks(cfg: ModelConfig, sv: ServingConfig, bytes_limit: int
                ) -> Dict:
    """Size the page pool from what one device has left after the weights
    and the step's temporaries: the prefill's scratch caches, what the
    float32 reference holds beside the engine (the embedding and output
    head it rebuilds, the head in float32, one layer in bf16 and in
    float32, which also covers the control's float32 layer), and
    ACTIVATION_RESERVE."""
    item = jnp.dtype(cfg.dtype).itemsize
    weights = weight_bytes(cfg)
    per_token = kv_bytes_per_token(cfg)
    scratch = N_SLOTS * sv.max_len() * per_token
    head = cfg.vocab_size * cfg.d_model
    layer = cfg.params_per_layer(0)
    ref = 2 * head * item + head * 4 + layer * (item + 4)
    reserve = scratch + ref + int(ACTIVATION_RESERVE)
    per_block = sv.block_size * per_token
    blocks = int((bytes_limit - weights - reserve) // per_block)
    assert blocks * sv.block_size >= N_SLOTS * sv.max_len(), \
        f"{blocks} blocks cannot hold {N_SLOTS} slots of {sv.max_len()}"
    return {"bytes_limit": int(bytes_limit), "weight_bytes": weights,
            "reserve_bytes": reserve, "kv_bytes_per_token": per_token,
            "blocks": blocks, "pool_bytes": blocks * per_block}


# ---- build and serve -------------------------------------------------------

def build_engine(cfg: ModelConfig, plan, sv: ServingConfig, *,
                 assignment: Optional[Assignment] = None,
                 stage_blocks=None, devices=None):
    """Build the engine from the plan, with weights made from SEED on each
    stage's own devices; return it with the build's wall seconds."""
    t0 = CLOCK.now()
    engine = InferenceEngine.from_config(
        cfg, plan, sv, assignment=assignment, n_slots=N_SLOTS,
        stage_blocks=stage_blocks, devices=devices)
    jax.block_until_ready(engine_weights(engine))
    return engine, CLOCK.now() - t0


def engine_weights(engine) -> List:
    return [(st.layer_params, st.head_params)
            for r in engine.replicas for st in r.stages]


def device_bytes(trees) -> Dict[int, int]:
    """Bytes each device holds of the arrays in ``trees`` (its shards)."""
    out: Dict[int, int] = {}
    for x in jax.tree.leaves(trees):
        for sh in x.addressable_shards:
            out[sh.device.id] = out.get(sh.device.id, 0) \
                + sh.data.size * sh.data.dtype.itemsize
    return out


def weight_shares(cfg: ModelConfig, asg: Assignment) -> Dict[int, float]:
    """The bytes of weights each device should hold under ``asg``: its
    stages' layers under the Megatron rules (a leaf sharded over the
    ``model`` axis splits over the stage's TP degree, the rest is
    replicated), plus the embedding or output head where the stage has
    one, replicated over the stage."""
    key = jax.random.PRNGKey(SEED)
    head = jax.eval_shape(lambda k: M.init_head_params(cfg, k), key)
    want: Dict[int, float] = {}
    for pipe in asg.pipelines:
        lo = 0
        for si, st in enumerate(pipe.stages):
            tp = len(st.device_ids)
            share = 0.0
            for i in range(lo, lo + st.num_layers):
                shapes = jax.eval_shape(
                    lambda k: M.init_layer_params(cfg, k, i), key)
                specs = jax.tree.leaves(
                    layer_specs(cfg, i, shapes, tp),
                    is_leaf=lambda s: isinstance(s, PartitionSpec))
                for x, spec in zip(jax.tree.leaves(shapes), specs):
                    split = tp if "model" in tuple(spec) else 1
                    share += x.size * x.dtype.itemsize / split
            names = head_names(cfg, is_first=si == 0,
                               is_last=si == len(pipe.stages) - 1)
            share += sum(x.size * x.dtype.itemsize for n in names
                         for x in jax.tree.leaves(head[n]))
            for d in st.device_ids:
                want[d] = want.get(d, 0.0) + share
            lo += st.num_layers
    return want


def make_requests(vocab: int, *, prompt_len: int = PROMPT_LEN,
                  out_len: int = OUT_LEN, n: int = N_REQUESTS,
                  seed: int = SEED) -> List[Request]:
    """n requests with seeded random prompts, all due at t=0 (one joint
    insert fills the slots)."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, size=prompt_len).astype(np.int32)
               for _ in range(n)]
    return [Request(rid=i, prompt=p, max_new_tokens=out_len, arrival=0.0)
            for i, p in enumerate(prompts)]


class LogitTap:
    """Records the logits the served path returns: every paged insert and
    the first ``max_decodes`` decode steps of each replica. Pure
    observation: the replicas' results pass through unchanged."""

    def __init__(self, replicas, max_decodes: int):
        self.inserts = []          # (replica, tokens, slot_ids, logits)
        self.decodes = []          # (replica, positions, logits)
        self._left = [max_decodes] * len(replicas)
        for ri, rep in enumerate(replicas):
            self._wrap(ri, rep)

    def _wrap(self, ri: int, rep) -> None:
        insert, decode = rep.insert_slots_paged, rep.decode_slots_paged

        def insert_tap(tokens, lens, slot_ids, stage_dest):
            out = insert(tokens, lens, slot_ids, stage_dest)
            self.inserts.append((ri, np.array(tokens), list(slot_ids),
                                 np.array(out)))
            return out

        def decode_tap(tokens, positions, stage_tables):
            out = decode(tokens, positions, stage_tables)
            if self._left[ri] > 0:
                self._left[ri] -= 1
                self.decodes.append((ri, np.array(positions),
                                     np.array(out)))
            return out

        rep.insert_slots_paged = insert_tap
        rep.decode_slots_paged = decode_tap

    def served_logits(self, req: Request, n_decode: int) -> np.ndarray:
        """(1 + n_decode, V): the prefill logits of ``req`` and those of its
        first n_decode decode steps (step k consumed output token k)."""
        plen = len(req.prompt)
        for ri, toks, slots, out in self.inserts:
            for row, slot in enumerate(slots):
                if np.array_equal(toks[row, :plen], req.prompt):
                    rows = [out[row]]
                    for rj, pos, dec in self.decodes:
                        if rj == ri and pos[slot] == plen + len(rows) - 1:
                            rows.append(dec[slot])
                        if len(rows) == 1 + n_decode:
                            return np.stack(rows)
                    raise AssertionError(
                        f"request {req.rid}: {len(rows) - 1} of "
                        f"{n_decode} decode steps recorded")
        raise AssertionError(f"request {req.rid}: no insert recorded")


def serve(engine, requests: Sequence[Request], deadline: float):
    """Serve on the wall clock and hold the run to: every request served
    in full, none rejected or dropped, no KV page leaked."""
    t0 = CLOCK.now()
    stats = engine.serve(list(requests), deadline=deadline)
    wall = CLOCK.now() - t0
    full = sum(1 for r in requests
               if r.served and len(r.output) == r.max_new_tokens)
    assert full == len(requests), \
        f"{full}/{len(requests)} requests served in full"
    assert stats.rejected == 0 and stats.dropped == 0, stats.summary()
    assert stats.kvsan_leaks == 0, stats.summary()
    return stats, wall


def warm_up_and_serve(engine, cfg: ModelConfig, sv: ServingConfig,
                      tag: str = ""):
    """A warm-up serve of the same shapes, which compiles the insert and
    decode programs (its time is mostly compilation), then the requests
    under a logit tap. Returns (tap, requests, stats, compile seconds)."""
    warm = make_requests(cfg.vocab_size, out_len=2)
    t0 = CLOCK.now()
    serve(engine, warm, sv.deadline)
    compile_s = CLOCK.now() - t0
    log(f"{tag}compile (warm-up serve of {len(warm)} x 2 tokens): "
        f"{compile_s:.3f} s")
    tap = LogitTap(engine.replicas, max_decodes=N_CHECK_DECODE + 2)
    reqs = make_requests(cfg.vocab_size)
    stats, wall = serve(engine, reqs, sv.deadline)
    log(f"{tag}served {len(reqs)}/{len(reqs)} in full in {wall:.3f} s: "
        + stats.summary())
    return tap, reqs, stats, compile_s


# ---- correctness -----------------------------------------------------------

def logit_errors(served: np.ndarray, ref: np.ndarray) -> Dict:
    """Per-position errors over each logit vector, and their worst case."""
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    err = served - ref
    rel = np.linalg.norm(err, axis=-1) / np.linalg.norm(ref, axis=-1)
    max_abs = np.abs(err).max(axis=-1)
    return {"rel_l2": rel.tolist(), "max_abs": max_abs.tolist(),
            "max_abs_std": (max_abs / ref.std(axis=-1)).tolist(),
            "worst_rel_l2": float(rel.max()),
            "worst_max_abs": float(max_abs.max()),
            "worst_max_abs_std": float((max_abs / ref.std(axis=-1)).max())}


def int8_round_trip(lp):
    """A layer's matrices rounded to int8 with per-column scales, as
    ``quant.quantize_leaf`` stores them, and back to float32."""
    return jax.tree.map(
        lambda w: quant.dequantize_leaf(quant.quantize_leaf(w))
        if w.ndim >= 2 else w, lp)


def reference_logits(cfg: ModelConfig, req: Request, n_decode: int,
                     key, *, weights=None) -> np.ndarray:
    """The float32 reference's logits at the request's last prompt
    position and its first n_decode output positions, from weights made
    from ``key`` one layer at a time and passed through ``weights`` (a
    function of one layer's params) if given."""
    tokens = np.concatenate([req.prompt, req.output[:n_decode]])
    rows = np.arange(len(req.prompt) - 1, len(tokens))
    head = jax.jit(M.init_head_params, static_argnums=0)(cfg, key)

    def build_layer(key, p, *, j):
        lp = M.init_period_layer(cfg, key, p, j=j)
        return weights(lp) if weights is not None else lp
    build = jax.jit(build_layer, static_argnames="j")

    def layer(i):
        p, j = M.layer_sub_index(cfg, i)
        return build(key, p, j=j)

    out = reference.forward_logits(cfg, head, layer, tokens, rows)
    return np.asarray(out)


def check_against_reference(cfg: ModelConfig, tap: LogitTap, req: Request,
                            key) -> Dict:
    """The served logits against the float32 reference, within the
    tolerances of the configuration's dtype; and the reference with int8
    weights against the reference, outside them (the check can see a path
    one step below the configuration's precision)."""
    rel_tol, abs_tol = TOLERANCES[jnp.dtype(cfg.dtype).name]
    served = tap.served_logits(req, N_CHECK_DECODE)
    ref = reference_logits(cfg, req, N_CHECK_DECODE, key)
    errs = logit_errors(served, ref)
    control = logit_errors(reference_logits(
        cfg, req, N_CHECK_DECODE, key, weights=int8_round_trip), ref)
    errs["control"] = control
    assert errs["worst_rel_l2"] <= rel_tol, errs
    assert errs["worst_max_abs_std"] <= abs_tol, errs
    assert control["worst_rel_l2"] > rel_tol, \
        (f"{CONTROL} pass the relative tolerance", control)
    assert control["worst_max_abs_std"] > abs_tol, \
        (f"{CONTROL} pass the max abs tolerance", control)
    return errs


def compare_layouts(a: LogitTap, b: LogitTap, reqs_a, reqs_b) -> Dict:
    """Errors between two layouts' logits for every request: the prefill,
    and each decode step whose input tokens agree in both runs (a flipped
    argmax feeds the two runs different tokens from there on)."""
    worst = {"worst_rel_l2": 0.0, "worst_max_abs_std": 0.0,
             "positions": 0}
    for ra, rb in zip(reqs_a, reqs_b):
        same = 0
        while same < N_CHECK_DECODE and ra.output[same] == rb.output[same]:
            same += 1
        la = a.served_logits(ra, same)
        lb = b.served_logits(rb, same)
        e = logit_errors(la, lb)
        worst["worst_rel_l2"] = max(worst["worst_rel_l2"],
                                    e["worst_rel_l2"])
        worst["worst_max_abs_std"] = max(worst["worst_max_abs_std"],
                                         e["worst_max_abs_std"])
        worst["positions"] += len(la)
    return worst


# ---- runs ------------------------------------------------------------------

def run_one_chip(cfg: ModelConfig, *, kvsan: bool,
                 bytes_limit: Optional[int] = None,
                 stage_blocks=None) -> Dict:
    """Plan, build, warm up, serve and check ``cfg`` on one device. On the
    chip the pool is sized from the device's memory limit; tests pass
    ``stage_blocks`` instead."""
    devices = jax.devices()[:1]
    sv = serving_config(cfg.name, "v5e_1", kvsan=kvsan)
    plan = make_plan(cfg, sv, 1)
    log(f"plan: {plan.describe()} (devices {plan_devices_used(plan)})")
    sizes = None
    if stage_blocks is None:
        sizes = pool_blocks(cfg, sv, bytes_limit)
        stage_blocks = [sizes["blocks"]]
        log("sizes: " + json.dumps(sizes))
    engine, build_s = build_engine(cfg, plan, sv, stage_blocks=stage_blocks,
                                   devices=devices)
    weights = sum(device_bytes(engine_weights(engine)).values())
    log(f"build: {build_s:.3f} s, weights {weights} B on device, pool "
        f"{stage_blocks[0]} blocks of {sv.block_size}")
    log("memory after build: " + memory_line(devices))
    tap, reqs, stats, compile_s = warm_up_and_serve(engine, cfg, sv)
    log("memory after serving: " + memory_line(devices))

    errs = check_against_reference(cfg, tap, reqs[0],
                                   jax.random.PRNGKey(sv.seed))
    rel_tol, abs_tol = TOLERANCES[jnp.dtype(cfg.dtype).name]
    for name, e in (("served", errs), (CONTROL, errs["control"])):
        log(f"logits vs float32 reference, {name} (request 0, prefill + "
            f"{N_CHECK_DECODE} decode steps): "
            f"max abs {e['worst_max_abs']!r} "
            f"({e['worst_max_abs_std']!r} std, tol {abs_tol}), "
            f"max rel L2 {e['worst_rel_l2']!r} (tol {rel_tol})")
        log("per position: " + json.dumps(
            {"rel_l2": e["rel_l2"], "max_abs": e["max_abs"],
             "max_abs_std": e["max_abs_std"]}))
    log("memory after the reference: " + memory_line(devices))
    return {"stats": stats, "errors": errs, "build_s": build_s,
            "compile_s": compile_s, "sizes": sizes}


def serve_layout(cfg: ModelConfig, plan, sv: ServingConfig, name: str, *,
                 assignment: Optional[Assignment] = None, devices=None):
    """Build one layout, serve the requests with a logit tap, check what
    each device holds, then free the layout. Returns (tap, requests)."""
    devices = list(devices or jax.devices())
    engine, build_s = build_engine(cfg, plan, sv, assignment=assignment,
                                   devices=devices)
    asg = assignment if assignment is not None else plan.assignment
    log(f"[{name}] layout {asg.describe()}: build {build_s:.3f} s")
    tap, reqs, _, _ = warm_up_and_serve(engine, cfg, sv, f"[{name}] ")
    log(f"[{name}] memory: " + memory_line(devices))
    check_shares(cfg, asg, engine, devices, name)
    del engine
    gc.collect()
    return tap, reqs


def check_shares(cfg: ModelConfig, asg: Assignment, engine,
                 devices: Sequence, name: str) -> None:
    """Every device holds its stages' share of the weights (within 1%),
    and, where the backend reports memory, nothing beyond those weights,
    its stages' page pools and SHARE_SLACK."""
    want = weight_shares(cfg, asg)
    weights = device_bytes(engine_weights(engine))
    pools = device_bytes([r.paged_caches for r in engine.replicas])
    log(f"[{name}] weight bytes per device: {weights} "
        f"(shares {({d: int(w) for d, w in want.items()})}); "
        f"page pool bytes: {pools}")
    for d in devices:
        got, share = weights.get(d.id, 0), want.get(d.id, 0.0)
        assert abs(got - share) <= 0.01 * share, \
            f"device {d.id} holds {got} B of weights, its share is {share:.0f}"
        in_use = (d.memory_stats() or {}).get("bytes_in_use")
        if in_use is not None:
            bound = got + pools.get(d.id, 0) + SHARE_SLACK
            assert in_use <= bound, \
                f"device {d.id} has {in_use} B in use, more than its " \
                f"weights, pools and slack ({bound} B)"


def run_four_chips(cfg: Optional[ModelConfig] = None, *,
                   kvsan: bool) -> Dict:
    """Full-depth granite-8b (or ``cfg``) over four devices: the scheduled
    plan, an asymmetric and a symmetric layout, each holding its share of
    the weights per device, with logits that agree."""
    devices = jax.devices()
    n = len(devices)
    assert n == 4, f"--chips 4 needs four devices, found {n}"
    cfg = cfg if cfg is not None else get_config("granite-8b")
    sv = serving_config(cfg.name, "v5e_2x2", kvsan=kvsan)
    # the scheduler may leave a chip idle for a model that fits on fewer
    plan = make_plan(cfg, sv, n, exact=False)
    log(f"plan: {plan.describe()} (devices {plan_devices_used(plan)})")
    L = cfg.num_layers
    layouts = {
        "scheduled": None,
        "asymmetric": layout_assignment([[0, 1], [2], [3]],
                                        [L // 2, L // 4, L - L // 2 - L // 4]),
        "symmetric": layout_assignment([[0], [1], [2], [3]], [L // 4] * 4),
    }
    taps = {}
    for name, asg in layouts.items():
        taps[name] = serve_layout(cfg, plan, sv, name, assignment=asg,
                                  devices=devices)
    base_tap, base_reqs = taps["symmetric"]
    out = {}
    for name in ("scheduled", "asymmetric"):
        tap, reqs = taps[name]
        e = compare_layouts(tap, base_tap, reqs, base_reqs)
        log(f"logits {name} vs symmetric: max rel L2 "
            f"{e['worst_rel_l2']!r} (tol {LAYOUT_REL_L2_TOL}), max abs "
            f"{e['worst_max_abs_std']!r} std "
            f"(tol {LAYOUT_MAX_ABS_TOL_STD}) over {e['positions']} "
            "positions")
        assert e["worst_rel_l2"] <= LAYOUT_REL_L2_TOL, (name, e)
        assert e["worst_max_abs_std"] <= LAYOUT_MAX_ABS_TOL_STD, (name, e)
        out[name] = e
    return out


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the one-chip phases; 4: only the four-chip "
                         "layout phase")
    ap.add_argument("--kvsan", action="store_true",
                    help="serve under the KVSAN page-lifecycle sanitizer")
    args = ap.parse_args(argv)

    info = device_info()
    log(f"device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    if info["platform"] != "tpu":
        log("no TPU found: this smoke run measures nothing elsewhere")
        return 1
    log(f"compile cache: {configure_compile_cache()}")
    fail_on_degraded_features()
    log(f"kernel backend: {ops.get_backend()}")

    if args.chips == 4:
        run_four_chips(kvsan=args.kvsan)
    else:
        cfg = ONE_CHIP
        log(f"model: {cfg.name} at published widths (d_model "
            f"{cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads}x"
            f"{cfg.head_dim_}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
            f"{cfg.dtype}), {cfg.num_layers} of "
            f"{ONE_CHIP_REDUCED['num_layers'][0]} layers")
        limit = jax.devices()[0].memory_stats()["bytes_limit"]
        run_one_chip(cfg, kvsan=args.kvsan, bytes_limit=limit)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
