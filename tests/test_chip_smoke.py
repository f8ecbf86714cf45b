"""The chip smoke run's phases on the CPU, and the bring-up pieces it needs:
per-stage weight builds, per-layer cache shapes, the plan surface, the
compile cache and the device checks. Times and device memory are only
measured on the chip (``python chip_smoke.py``)."""
import collections
import dataclasses
import os
import shutil
import subprocess
import sys
import textwrap
import types
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.granite_8b import ONE_CHIP
from repro.core import cluster as cl
from repro.core import cost_model as cm
from repro.core.scheduler import schedule
from repro.kernels import ops
from repro.launch import compile_cache, smoke
from repro.launch.serve import check_pool_fits
from repro.models import model as M
from repro.models import reference
from repro.serving.engine import plan_devices
from repro.serving.pipeline import AsymmetricPipeline

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEY = jax.random.PRNGKey(0)


def _run(args, env=None, cwd=ROOT, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, env=env, cwd=cwd, timeout=timeout)


# ---- the smoke run's phases -------------------------------------------------

def test_one_chip_phases_on_cpu():
    """Plan, build, warm-up, serve 8 x (512 + 64) and the float32 logit
    check, through the functions chip_smoke.py calls, at the reduced
    granite-8b (float32, so the check is far inside its tolerance)."""
    cfg = get_config("granite-8b").reduced()
    with warnings.catch_warnings():
        smoke.fail_on_degraded_features()
        out = smoke.run_one_chip(cfg, kvsan=True, stage_blocks=[8 * 37 + 1])
    stats = out["stats"]
    assert len(stats.latencies) == smoke.N_REQUESTS
    assert stats.rejected == stats.dropped == stats.kvsan_leaks == 0
    assert out["errors"]["worst_rel_l2"] < 1e-4
    assert len(out["errors"]["rel_l2"]) == 1 + smoke.N_CHECK_DECODE


def test_four_device_phase_on_virtual_devices():
    """The --chips 4 phase on four virtual CPU devices: scheduled,
    asymmetric (TP 2,1,1) and symmetric (TP 1 x4) layouts each hold their
    share of the weights per device and agree on logits."""
    code = textwrap.dedent("""
        import dataclasses
        from repro.configs import get_config
        from repro.launch import smoke
        smoke.fail_on_degraded_features()
        cfg = dataclasses.replace(get_config("granite-8b").reduced(),
                                  num_layers=4)
        out = smoke.run_four_chips(cfg, kvsan=True)
        assert set(out) == {"scheduled", "asymmetric"}, out
        print("OK")
    """)
    p = _run(["-c", code], env={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": "src"})
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-3000:]
    assert "OK" in p.stdout
    assert "[asymmetric] layout [2,1,1] layers=[2, 1, 1]" in p.stdout


def test_script_refuses_without_a_tpu():
    p = _run(["chip_smoke.py"])
    assert p.returncode != 0
    assert '"ok": true' not in p.stdout
    assert "no TPU" in p.stdout


def test_script_alone_fails(tmp_path):
    """Without the rest of the repository the script cannot run, and says
    nothing of a result."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    p = _run(["chip_smoke.py"], env={"PYTHONPATH": ""}, cwd=tmp_path)
    assert p.returncode != 0
    assert '"ok": true' not in p.stdout


def test_logit_errors_and_tap_lookup():
    ref = np.array([[1.0, -1.0, 2.0, 0.0]])
    e = smoke.logit_errors(ref * 1.01, ref)
    assert e["worst_rel_l2"] == pytest.approx(0.01)
    assert e["worst_max_abs"] == pytest.approx(0.02)
    tap = smoke.LogitTap([], max_decodes=2)
    req = types.SimpleNamespace(rid=0, prompt=np.array([5, 6, 7]))
    tap.inserts.append((0, np.array([[9, 9, 9, 0], [5, 6, 7, 0]]), [3, 1],
                        np.array([[0.0], [1.0]])))
    tap.decodes.append((0, np.array([0, 3, 0, 0]), np.array([[0], [2.0]] +
                                                             [[0]] * 2)))
    tap.decodes.append((0, np.array([0, 4, 0, 0]), np.array([[0], [3.0]] +
                                                             [[0]] * 2)))
    assert tap.served_logits(req, 2)[:, 0].tolist() == [1.0, 2.0, 3.0]


# ---- bring-up pieces --------------------------------------------------------

def test_reference_matches_model_forward():
    """The plain float32 reference against the model's own prefill at a
    small float32 size."""
    cfg = get_config("granite-8b").reduced()
    params = M.init_params(cfg, KEY)
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, 24)
    cache = M.init_cache(cfg, 1, 32)
    want, _ = M.prefill(cfg, params, {"tokens": jnp.asarray(toks)[None]},
                        cache)
    got = reference.forward_logits(
        cfg, params, lambda i: M.slice_layer_params(cfg, params, i), toks,
        [23])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


@pytest.mark.parametrize("arch", ["granite-8b", "jamba-v0.1-52b"])
def test_layer_params_equal_the_stacked_init(arch):
    cfg = get_config(arch).reduced()
    full = M.init_params(cfg, KEY)
    for i in range(cfg.num_layers):
        one = M.init_layer_params(cfg, KEY, i)
        stacked = M.slice_layer_params(cfg, full, i)
        assert jax.tree.structure(one) == jax.tree.structure(stacked)
        for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(stacked)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (arch, i)
    head = M.init_head_params(cfg, KEY)
    for n, v in head.items():
        for a, b in zip(jax.tree.leaves(v), jax.tree.leaves(full[n])):
            assert np.array_equal(np.asarray(a), np.asarray(b)), n


def test_stages_build_their_own_share_from_the_key():
    """Each stage builds only its layers and its end of the head, with the
    values of the stacked init. The build is one jitted program, and XLA
    may fuse the draw's scale differently from the op-by-op stacked init:
    the float32 values agree to the last bit or two."""
    cfg = get_config("granite-8b").reduced()
    full = M.init_params(cfg, KEY)
    dev = jax.devices()[0]
    pipe = AsymmetricPipeline(cfg, None, [1, 1], [[dev], [dev]], key=KEY)
    first, last = pipe.stages
    assert set(first.head_params) == {"embed"}
    assert set(last.head_params) == {"final_norm", "lm_head"}
    pairs = [(st.layer_params[0], M.slice_layer_params(cfg, full, i))
             for i, st in enumerate(pipe.stages)]
    pairs += [(first.head_params["embed"], full["embed"]),
              (last.head_params["lm_head"], full["lm_head"])]
    for got, want in pairs:
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-7, atol=0)


@pytest.mark.parametrize("paged", [False, True])
def test_layer_caches_are_built_at_their_own_shape(paged):
    """A layer's cache is allocated at its own shape: no period-stacked
    array of every layer appears on the way."""
    cfg = dataclasses.replace(get_config("granite-8b").reduced(),
                              num_layers=6)
    if paged:
        def make():
            return M.init_layer_paged_cache(cfg, 3, 40, 16, 4)
        want = (40, 16, cfg.num_kv_heads, cfg.head_dim_)
    else:
        def make():
            return M.init_layer_cache(cfg, 3, 4, 64)
        want = (4, 64, cfg.num_kv_heads, cfg.head_dim_)
    out = make()
    assert out["k"].shape == want and out["v"].shape == want
    jaxpr = jax.make_jaxpr(make)()
    shapes = [v.aval.shape for eqn in jaxpr.eqns for v in eqn.outvars]
    assert shapes and all(s[0] != M.n_periods(cfg) for s in shapes), shapes


def test_schedule_takes_the_served_model_config():
    task = cm.Task(batch=1, s_in=512, s_out=64)
    kw = dict(deadline=600.0, rate=1.0, iters=2, kv_block_size=16)
    res = schedule(cl.tpu_v5e_one(), ONE_CHIP, task, **kw)
    res.assignment.validate(ONE_CHIP.num_layers)
    assert ONE_CHIP.num_layers == 16
    by_name = schedule(cl.tpu_v5e_2x2(), "granite-8b", task, **kw)
    by_name.assignment.validate(36)
    assert len(cl.tpu_v5e_2x2()) == 4 and len(cl.tpu_v5e_one()) == 1


def test_plans_may_not_name_absent_accelerators():
    Dev = collections.namedtuple("Dev", "platform id")
    tpus = [Dev("tpu", i) for i in range(2)]
    assert plan_devices(tpus, [1]) == [tpus[1]]
    with pytest.raises(ValueError, match=r"\[2, 3\].*only 2 tpu"):
        plan_devices(tpus, [0, 2, 3])
    # on the CPU, plans for larger pools fold onto the host's devices
    cpus = [Dev("cpu", 0)]
    assert plan_devices(cpus, [0, 5, 7]) == cpus
    with pytest.raises(SystemExit, match="4 devices but only 2 tpu"):
        check_pool_fits(4, tpus)
    check_pool_fits(8, cpus)


def test_interpret_backend_refused_on_tpu(monkeypatch):
    before = ops.get_backend()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="tpu"):
        ops.set_backend("pallas_interpret")
    assert ops.get_backend() == before


def test_compile_cache_honours_the_env_var(monkeypatch, tmp_path):
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was   # set nothing


def test_compile_cache_default_is_in_the_checkout(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    try:
        got = compile_cache.configure_compile_cache()
        assert got == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_lands_in_the_env_dir(tmp_path):
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import configure_compile_cache
        configure_compile_cache()
        jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((64, 64))).block_until_ready()
    """)
    p = _run(["-c", code], env={
        "PYTHONPATH": "src", "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0"})
    assert p.returncode == 0, p.stderr[-2000:]
    assert os.listdir(tmp_path)
