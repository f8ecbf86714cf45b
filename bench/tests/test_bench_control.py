"""The control, at a size a test run holds: served at the configuration's
bf16 the ``small`` cell is correct; with the reference at int8 weights put
in the program's place at the same positions, the run is not. On the CPU,
at a fixed seed, these numbers repeat exactly; the limits and readings at
the cells' own size are in PERF.md."""
import time

import jax
import pytest

from checkout import make_root, set_limits
from harness import cell
from harness.spec import Spec

SEED = 2 ** 31 + 21


@pytest.mark.parametrize("number,limit", [("worst_logit_gap", 0.015),
                                          ("mean_logit_gap", 2.0e-4)])
@pytest.mark.parametrize("control", ["", "int8"])
def test_served_passes_and_the_reference_at_int8_fails(tmp_path, control,
                                                       number, limit):
    """Served 0.0101 worst and 1.25e-4 mean; the control 0.0263 and
    3.86e-4."""
    root = make_root(tmp_path)
    set_limits(root, "small.chat", {number: limit})
    res = cell.run_cell(Spec(root), "small.chat", SEED, 1.0, False,
                        jax.devices()[:1], time.monotonic(), control=control)
    got = res["compared"]
    assert res["correct"] is (not control)
    assert 0 < got[number]["value"] < got[number]["limit"] == limit
    if control:
        assert got[f"control_{number}"]["value"] > limit
    else:
        assert f"control_{number}" not in got
