#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest rate whose backlog does
not grow over a window.

    python3 bench/sweep.py --workload granite-8b-1chip.chat --seed 11 \\
        --seconds 30 --rates 1 1.5 2 2.5 3 [--set n_slots=16]

Builds and warms the cell's deployment once, then serves the cell's
traffic at each rate in turn, each for one window, and prints one line per
rate: requests due, the backlog (due but not yet admitted) at the middle
and at the end of the window, the median queue wait in the window's first
and last third, and the tails of time to first token and of the gap
between tokens. A backlog that grows from the middle to the end, or a
last-third wait well above the first, is past the knee. The cell's rate is
then set by hand, in its traffic file, at about four fifths of the knee.
Needs the chip the cell asks for.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def backlog(reqs, t: float) -> int:
    return sum(1 for r in reqs if r.arrival <= t
               and (r.start_time is None or r.start_time > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="a cell setting in place of the cell file's, "
                         "e.g. --set n_slots=16")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from harness import cell
    from harness.spec import Spec
    from harness.stats import percentile
    spec = Spec(BENCH.parent)
    chips = spec.workload(args.workload)["chips"]
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"{args.workload} needs {chips} TPU chip(s)", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import configure_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    configure_compile_cache()
    cell.fail_on_degraded_features()
    overrides = {k: json.loads(v) for k, v in
                 (kv.split("=", 1) for kv in args.set)}
    dep = cell.Deployment(spec, args.workload, args.seed, devices[:chips],
                          overrides=overrides)
    if dep.traffic["loop"] != "open":
        print("the sweep is for open-loop traffic", file=sys.stderr)
        return 2
    print(f"set-up: {time.monotonic() - T_START:.3f} s", flush=True)
    s = args.seconds
    for rate in args.rates:
        trf = dict(dep.traffic, rate_per_s=rate)
        run = cell.serve(dep, trf, args.seed, s)
        e2e = cell.end_to_end(run)
        thirds = [[r.start_time - r.arrival for r in run.requests
                   if r.start_time is not None
                   and k * s / 3 <= r.arrival < (k + 1) * s / 3]
                  for k in (0, 2)]
        print(f"rate {rate!r}/s: {len(run.attempted)} due, "
              f"{len(run.failed)} failed, backlog {backlog(run.requests, s / 2)}"
              f" at {s / 2:.0f} s and {backlog(run.requests, s)} at {s:.0f} s, "
              f"median queue wait {float(np.median(thirds[0] or [0])):.3f} s "
              f"(first third) {float(np.median(thirds[1] or [0])):.3f} s "
              f"(last third), ttft p95 {e2e['ttft_p95_ms']:.1f} ms, "
              f"tpot p95 {e2e['tpot_p95_ms']:.2f} ms, output "
              f"{e2e['output_tok_s']:.1f} tok/s, queue wait p95 "
              f"{1e3 * percentile(thirds[1] or [0], 95):.1f} ms",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
