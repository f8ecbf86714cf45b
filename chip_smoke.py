#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU, at published widths.

    python chip_smoke.py              # one chip: granite-8b, 16 of 36 layers
    python chip_smoke.py --chips 4    # four chips: full depth, three layouts
    python chip_smoke.py --kvsan      # ... under the KVSAN page sanitizer

Runs in one process. Prints its phases, then one JSON line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``;
exits non-zero, printing no result, when JAX finds no TPU or any phase
fails. The phases live in ``src/repro/launch/smoke.py``.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.launch.smoke import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
