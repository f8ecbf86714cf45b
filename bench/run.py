#!/usr/bin/env python3
"""The benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload granite-8b-1chip.chat --seed 7 \\
        --seconds 30 --trace 0

Run from the root of a checkout. Builds the cell's deployment from the
seed, warms up every shape its traffic uses (set-up), serves the traffic
for ``--seconds`` on the wall clock, and checks the served tokens against
the plain float32 reference. With ``--trace 0`` it reports the cell's
end-to-end metrics; with ``--trace 1`` its per-layer metrics, from the
same kind of run with the profiler on for the window's last seconds.

Prints its progress, then as the last line of standard output one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
(``breakdown`` when traced) and last ``compared``, each number of the
correctness check with its limit, which also ends standard error. Exits
non-zero, printing no result, where JAX finds no TPU or fewer chips than
the cell asks for.
"""
import time

T_START = time.monotonic()      # set-up is timed from process start

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default="", choices=("", "int8"),
                    help="for setting the correctness limit, not part of a "
                         "benchmark run: put the reference with int8 "
                         "weights in the program's place, so that the run "
                         "is judged on its tokens")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from harness.spec import Spec
    spec = Spec(ROOT)
    chips = spec.workload(args.workload)["chips"]

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"{args.workload} needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import configure_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"compile cache: {configure_compile_cache()}", flush=True)

    from harness import cell
    cell.fail_on_degraded_features()
    result = cell.run_cell(spec, args.workload, args.seed, args.seconds,
                           bool(args.trace), devices[:chips], T_START,
                           control=args.control)
    for name, v in result["compared"].items():
        print(f"compared {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
