"""The documents cell's path rehearsed on four virtual CPU devices:
open-loop prompts in chunks over one TP-4 stage, in a pool too small for
every slot (``four_devices_docs.py``, in a process of its own)."""
import json
import os
import subprocess
import sys

import pytest

from checkout import BENCH


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(BENCH / "tests" /
                                            "four_devices_docs.py"),
                        str(tmp_path_factory.mktemp("docs"))], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return {r["run"]: r for r in map(json.loads, [
        line for line in p.stdout.splitlines() if line.startswith("{")])}


def test_preempted_and_recomputed_requests_agree_with_the_reference(runs):
    """Slots are preempted and computed again; every finished request
    (the check takes them all) agrees with the float32 reference, and the
    share of recomputed tokens reads above 0."""
    served = runs["served"]
    assert served["correct"] is True
    assert served["failed"] == 0 and served["attempted"] > 0
    assert served["device"]["count"] == 4
    assert set(served["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms",
                                      "setup_s"}
    assert served["preemptions"] > 0 and served["recomputed_tokens"] > 0
    assert served["preempt_recompute_share"] > 0


def test_the_exchange_left_out_is_not_correct(runs):
    fault = runs["exchange"]
    assert fault["correct"] is False
    gap = fault["compared"]["worst_logit_gap"]
    assert gap["value"] > 10 * gap["limit"]
