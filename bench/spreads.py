#!/usr/bin/env python3
"""Median and spread of each metric over runs of one cell.

    python3 bench/spreads.py RUN_OUTPUT...

Each argument is the standard output of one run of ``bench/run.py``; its
last line is the result. Prints, per metric, the number of runs, the
median, and the spread: the distance between the first and third
quartile as ``statistics.quantiles(values, n=4)`` gives them, over the
median. A bound is set from the widest spread of two sets of runs.
"""
import json
import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main(paths) -> int:
    from harness.stats import spread
    values = {}
    for path in paths:
        line = Path(path).read_text().strip().splitlines()[-1]
        for name, m in json.loads(line)["metrics"].items():
            values.setdefault(name, []).append(m["value"])
    for name, v in values.items():
        print(f"{name}: {len(v)} runs, median {statistics.median(v)!r}, "
              f"spread {spread(v) if len(v) > 1 else float('nan')!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
