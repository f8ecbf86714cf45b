"""Open-loop documents over four devices, rehearsed on four virtual CPU
devices (``test_bench_docs.py`` runs this in a process of its own, since
the device count is fixed when JAX starts).

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 bench/tests/four_devices_docs.py OUT_DIR

Adds the ``tiny4.docs_open`` cell to a checkout of the benchmark, the way
a later change adds one: ``tiny4`` over one tensor-parallel stage on all
four devices, prompts in 16-token chunks arriving on an open loop, in a
pool too small for every slot, so that slots are preempted and computed
again. Runs it as served and with the exchange between the devices left
out (``four_devices.leave_out_exchange``), degraded features failing the
run as in ``run.py``. Prints one JSON line per run: the result line, the
serve's preemptions, the tokens they threw away and
``preempt_recompute_share``.
"""
import json
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent), str(HERE.parent.parent / "src")]

WORKLOAD = "tiny4.docs_open"


def add_cell(root: Path) -> None:
    """The cell's traffic and settings as new files, its entry in
    BENCHMARK.json, and its name in the lists of the metrics the
    documents cell reports."""
    data = HERE / "data"
    shutil.copy(data / "tiny_docs_open.json",
                root / "bench" / "traffic" / "tiny_docs_open.json")
    shutil.copy(data / "tiny_docs_open.cell.json",
                root / "bench" / "cells" / f"{WORKLOAD}.json")
    path = root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["workloads"].append({"name": WORKLOAD, "config": "tiny4",
                               "traffic": "tiny_docs_open", "chips": 4,
                               "why": "tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "granite-8b-4chip.docs" in m.get("workloads", []):
            m["workloads"].append(WORKLOAD)
    path.write_text(json.dumps(bench, indent=1))


def main(out: str) -> int:
    import jax
    import checkout
    from four_devices import leave_out_exchange, tp4_plan
    from harness import cell
    from harness.spec import Spec
    assert len(jax.devices()) == 4, jax.devices()
    root = checkout.make_root(Path(out))
    add_cell(root)
    spec = Spec(root)
    cell.make_plan = tp4_plan
    cell.fail_on_degraded_features()
    serve, runs = cell.serve, []

    def kept(*args, **kw):
        runs.append(serve(*args, **kw))
        return runs[-1]
    cell.serve = kept
    share = spec.reader("preempt_recompute_share.online")
    for name, fault in (("served", None), ("exchange", leave_out_exchange)):
        res = cell.run_cell(spec, WORKLOAD, 2 ** 31 + 3, 1.0, False,
                            jax.devices(), time.monotonic(), fault=fault)
        stats = runs[-1].stats
        print(json.dumps({
            "run": name, **res, "preemptions": stats.preemptions,
            "recomputed_tokens": stats.recomputed_tokens,
            "preempt_recompute_share": share(runs[-1])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
