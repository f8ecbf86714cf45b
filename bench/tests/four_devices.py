"""The four-chip path of the benchmark, rehearsed on four virtual CPU devices
(``test_bench_harness.py`` runs this in a process of its own, since the
device count is fixed when JAX starts).

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 bench/tests/four_devices.py OUT_DIR

Runs the ``tiny4.docs`` cell three times over one tensor-parallel stage on
all four devices: as served, with the exchange between the devices left
out (every row-parallel matrix keeps only the first device's rows, which
is what the first device holds once its all-reduce is gone), and with a
token altered where it is produced. Prints one JSON line per run.
"""
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent), str(HERE.parent.parent / "src")]


def tp4_plan(cfg, sv, devices):
    from repro.core.plan import DeploymentPlan
    from repro.launch.smoke import layout_assignment
    return DeploymentPlan.from_search(
        layout_assignment([[0, 1, 2, 3]], [cfg.num_layers]))


def leave_out_exchange(engine):
    import jax
    import jax.numpy as jnp
    for rep in engine.replicas:
        for st in rep.stages:
            for i, lp in enumerate(st.layer_params):
                def first_rows(w):
                    spec = getattr(w.sharding, "spec", ())
                    if w.ndim != 2 or not spec or spec[0] is None:
                        return w                  # not row-parallel
                    keep = jnp.arange(w.shape[0]) < w.shape[0] // 4
                    return jnp.where(keep[:, None], w, jnp.zeros_like(w))
                st.layer_params[i] = jax.tree.map(first_rows, lp)


def alter_tokens(engine):
    """Every fifth decode call puts each row's worst token first."""
    for w in engine.router.workers:
        pipe, decode = w.pipeline, w.pipeline.decode_slots_paged
        n = [0]

        def altered(*args, decode=decode, n=n):
            out = decode(*args)
            n[0] += 1
            return -out if n[0] % 5 == 0 else out
        pipe.decode_slots_paged = altered


def main(out: str) -> int:
    import jax
    import checkout
    from harness import cell
    from harness.spec import Spec
    assert len(jax.devices()) == 4, jax.devices()
    spec = Spec(checkout.make_root(Path(out)))
    cell.make_plan = tp4_plan
    for name, fault in (("served", None), ("exchange", leave_out_exchange),
                        ("token", alter_tokens)):
        res = cell.run_cell(spec, "tiny4.docs", 2 ** 31 + 3, 1.0, False,
                            jax.devices(), time.monotonic(), fault=fault)
        print(json.dumps({"run": name, **res}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
