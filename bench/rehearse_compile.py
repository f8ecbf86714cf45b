#!/usr/bin/env python3
"""Compile a cell's largest stage programs for a described TPU v5e, with no
chip attached, and print what each needs on one device.

    JAX_PLATFORMS=cpu python3 bench/rehearse_compile.py granite-8b-1chip.chat

For the cell's scheduled plan (its first stage), compiles at the
configuration's published widths: the one-shot insert prefill at the most
rows and the widest prompt with its scratch caches, or the chunked context
prefill at the most rows; and the paged decode step of every slot. Prints
each program's argument, output and temporary bytes per device and its
compile time. Nothing runs: this says what the chip's compiler accepts
and how much memory a step takes, not how fast it is. ``--slots`` compiles
for another slot count than the cell's, ``--width`` for
another insert width.
"""
import argparse
import os
import sys
import time
from functools import partial
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--slots", type=int, default=0)
    ap.add_argument("--width", type=int, default=0,
                    help="one-shot insert width to compile (default: the "
                         "widest of the traffic's grid)")
    ap.add_argument("--blocks", type=int, default=2048,
                    help="page-pool blocks to compile the steps with")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from harness import cell as C
    from harness import traffic as T
    from harness.spec import Spec
    from repro.models import model as M
    from repro.serving import pipeline as PL

    jax.config.update("jax_enable_compilation_cache", False)
    spec = Spec(BENCH.parent)
    w = spec.workload(args.workload)
    c, trf, cellf = (spec.config(w["config"]), spec.traffic(w["traffic"]),
                     spec.cell(args.workload))
    cfg = C.model_config(c)
    sv = C.serving_config(cfg, c, trf, cellf)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    plan = C.make_plan(cfg, sv, list(topo.devices))
    st = plan.assignment.pipelines[0].stages[0]
    tp = len(st.device_ids)
    mesh = Mesh(np.array(topo.devices[:tp]), ("model",))
    print(f"plan {plan.describe()}; compiling stage 0: {st.num_layers} "
          f"layers over {tp} device(s)", flush=True)
    n = args.slots or cellf["n_slots"]
    max_len, bs = sv.max_len(), sv.block_size
    bf16, i32 = jnp.bfloat16, jnp.int32
    key = jax.random.PRNGKey(0)

    def sds(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    def with_sharding(shapes, specs):
        return jax.tree.map(lambda s, p: sds(s.shape, s.dtype, p),
                            shapes, specs)

    lps = []
    for i in range(st.num_layers):
        shp = jax.eval_shape(partial(M.init_layer_params, cfg, i=i), key)
        lps.append(with_sharding(shp, PL.layer_specs(cfg, i, shp, tp)))

    def caches(lead):
        kv = jax.ShapeDtypeStruct(
            (*lead, cfg.num_kv_heads, cfg.head_dim_), bf16)
        one = [{"k": kv, "v": kv} for _ in range(st.num_layers)]
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=sh),
            one, PL.cache_shardings(cfg, mesh, one))

    progs = PL.stage_programs(cfg, tuple(cfg.layer_kind(i)
                                         for i in range(st.num_layers)))
    pools = caches((args.blocks, bs))
    d = cfg.d_model
    mb = max_len // bs
    todo = {"decode_paged": lambda: progs.decode_paged.lower(
        lps, sds((n, 1, d), bf16), pools, sds((n,), i32),
        sds((n, mb), i32))}
    chunk = cellf.get("prefill_chunk", 0)
    if chunk:
        todo[f"context_paged {n}x{chunk}"] = lambda: \
            progs.context_paged.lower(
                lps, sds((n, chunk, d), bf16), pools, sds((n, chunk), i32),
                sds((n,), i32), sds((n, mb), i32))
    else:
        width = args.width or max(T.grid(trf["prompt"]))
        todo[f"prefill {n}x{width}"] = lambda: progs.prefill.lower(
            lps, sds((n, width, d), bf16), caches((n, max_len)),
            sds((n, width), i32), None, sds((n, width), i32), None,
            sds((n,), i32))
    for name, lower in todo.items():
        t0 = time.monotonic()
        m = lower().compile().memory_analysis()
        print(f"{name}: arguments {m.argument_size_in_bytes} B, outputs "
              f"{m.output_size_in_bytes} B, temporaries "
              f"{m.temp_size_in_bytes} B, aliased "
              f"{m.alias_size_in_bytes} B per device; compiled in "
              f"{time.monotonic() - t0:.1f} s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
