"""A rehearsal of the benchmark on the CPU at a tiny size: the files found by
name, the token stamps, the window's accounting, the metric arithmetic, the
result line, the correctness check and the faults it has to catch, the
four-device path on virtual devices, and the refusal to report without a
TPU."""
import hashlib
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from checkout import BENCH, make_root, set_limits
from harness import cell, check
from harness.spec import Spec

SEED = 2 ** 31 + 7
KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]


def digest(path: Path) -> str:
    return hashlib.sha256(path.read_bytes()).hexdigest()


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(scope="module")
def spec(root):
    return Spec(root)


@pytest.fixture(scope="module")
def chat(spec):
    dep = cell.Deployment(spec, "tiny.chat", SEED, jax.devices()[:1])
    run = cell.serve(dep, dep.traffic, SEED, 1.5)
    return dep, run


def test_new_files_are_found_by_name_and_no_file_is_edited(root, spec):
    """make_root adds configurations, traffic, cells and workloads the way
    a later change would; every file the benchmark had is unchanged."""
    for f in BENCH.rglob("*"):
        rel = f.relative_to(BENCH)
        if f.is_file() and rel.parts[0] not in ("tests", "__pycache__") \
                and "__pycache__" not in rel.parts:
            assert digest(root / "bench" / rel) == digest(f), rel
    old = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    new = spec.bench
    for key in ("configs", "workloads"):
        assert new[key][:len(old[key])] == old[key]
    assert spec.config("tiny")["hidden_size"] == 64
    assert spec.traffic("tiny_chat")["loop"] == "open"
    assert spec.cell("tiny4.docs")["n_slots"] == 4
    assert [m["name"] for m in spec.end_to_end("tiny.chat")] == \
        ["ttft_p95_ms", "tpot_p95_ms", "setup_s"]
    assert [m["name"] for m in spec.end_to_end("tiny4.docs")] == \
        ["output_tok_s", "setup_s"]
    for m in spec.per_layer("tiny.chat") + spec.per_layer("tiny.docs"):
        assert callable(spec.reader(m["name"]))


def test_every_per_layer_metric_has_a_reader():
    spec = Spec(BENCH.parent)
    for m in spec.bench["per_layer"]:
        assert callable(spec.reader(m["name"])), m["name"]
    for w in spec.bench["workloads"]:
        assert spec.config(w["config"]) and spec.traffic(w["traffic"])
        assert spec.cell(w["name"])["limits"]


def test_each_served_token_is_stamped_once_after_its_request_was_due(chat):
    dep, run = chat
    done = [r for r in run.requests if cell.full(r)]
    assert len(done) >= len(run.attempted) - 2
    for r in done:
        t = run.recorder.token_times(r.rid, r.max_new_tokens)
        assert t is not None, r.rid
        # the engine's last decode of a request computes logits for one
        # token more, which it drops: stamped, never counted
        assert set(run.recorder.tokens[r.rid]) <= set(
            range(r.max_new_tokens + 1))
        assert np.all(np.diff(t) > 0)
        assert t[0] >= r.start_time >= r.arrival


def test_window_accounting(chat):
    dep, run = chat
    due = [r for r in run.requests if r.arrival < run.seconds]
    assert len(run.attempted) == len(due) == len(run.requests)
    assert run.failed == []
    assert run.compiles == []              # warm-up left nothing to compile
    kinds = {k.kind for k in run.calls}
    assert kinds == {"insert", "decode"}
    # each prompt is inserted once (nothing was preempted)
    inserts = [k for k in run.recorder.calls if k.kind == "insert"]
    assert run.stats.preemptions == 0
    assert sum(k.tokens for k in inserts) == sum(
        len(r.prompt) for r in run.requests)
    assert all(k.t0 < run.seconds for k in run.calls)


def test_metric_arithmetic(chat, spec):
    dep, run = chat
    e2e = cell.end_to_end(run)
    ttft = [run.recorder.token_times(r.rid, r.max_new_tokens)[0]
            - r.arrival for r in run.attempted]
    assert e2e["ttft_p95_ms"] == pytest.approx(
        1e3 * np.percentile(ttft, 95))
    assert 0 < e2e["tpot_p95_ms"] < e2e["ttft_p95_ms"] * 100
    got = {m["name"]: spec.reader(m["name"])(run)
           for m in spec.per_layer("tiny.chat")}
    decodes = [k for k in run.calls if k.kind == "decode"]
    assert got["decode_step_ms.online"] == pytest.approx(
        1e3 * np.mean([k.t1 - k.t0 for k in decodes]))
    assert got["compiles_in_window.online"] == 0
    assert got["queue_wait_p95_ms"] >= 0
    assert got["prefill_ms_per_ktok.online"] > 0
    # no device trace and no peaks on the CPU: those readers find nothing
    for name in ("device_idle_share.online", "decode_hbm_roofline.online",
                 "decode_mfu.online"):
        assert got[name] is None


def test_served_tokens_agree_with_the_reference(chat):
    dep, run = chat
    sample = check.sample(run.requests, SEED, dep.cell["check"])
    longest = max(len(r.output) for r in run.requests if cell.full(r))
    assert len(sample[0].output) == longest
    v = check.compare(dep.config, SEED, sample, jax.devices()[:1],
                      dep.cell)
    assert v["correct"], v["summary"]
    assert v["positions"] >= dep.cell["check"]["tokens"]


def test_gap_reads_how_far_below_the_best_a_token_lies():
    ref = np.array([[0.0, 2.0, 1.0], [3.0, -1.0, 0.5]])
    assert check.gaps(ref, np.array([1, 2])).tolist() == [0.0, 2.5]


def test_result_line_of_a_run(spec):
    res = cell.run_cell(spec, "tiny.docs", SEED, 1.0, False,
                        jax.devices()[:1], time.monotonic())
    assert list(res) == KEYS
    assert res["correct"] is True
    assert set(res["metrics"]) == {"output_tok_s", "setup_s"}
    assert all(set(m) == {"value", "unit"} for m in res["metrics"].values())
    assert res["metrics"]["output_tok_s"]["value"] > 0
    assert res["device"] == {"platform": "cpu", "kind": "cpu",
                             "count": len(jax.devices())}
    assert res["compared"]["worst_logit_gap"]["limit"] == 0.001
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("number", ["worst_logit_gap", "mean_logit_gap"])
def test_an_altered_token_is_not_correct(root, tmp_path, number):
    sys.path.insert(0, str(Path(__file__).parent))
    from four_devices import alter_tokens
    if number != "worst_logit_gap":
        root = make_root(tmp_path)
        set_limits(root, "tiny.chat", {number: 0.001})
    res = cell.run_cell(Spec(root), "tiny.chat", SEED, 1.0, False,
                        jax.devices()[:1], time.monotonic(),
                        fault=alter_tokens)
    assert res["correct"] is False
    gap = res["compared"][number]
    assert gap["value"] > 100 * gap["limit"]


def test_four_devices_served_and_faults(tmp_path):
    """The four-chip path on four virtual devices: served as planned it is
    correct; with the exchange between devices left out, or a token
    altered, it is not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(BENCH / "tests" /
                                            "four_devices.py"),
                        str(tmp_path)], env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    runs = {r["run"]: r for r in map(json.loads, [
        line for line in p.stdout.splitlines() if line.startswith("{")])}
    assert runs["served"]["correct"] is True
    assert runs["served"]["device"]["count"] == 4
    assert runs["exchange"]["correct"] is False
    assert runs["token"]["correct"] is False


@pytest.mark.parametrize("where", ["repo", "benchmark_only"])
def test_no_result_without_a_tpu(tmp_path, where):
    cwd = BENCH.parent
    if where == "benchmark_only":
        import shutil
        cwd = tmp_path / "checkout"
        shutil.copytree(BENCH, cwd / "bench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(BENCH.parent / "BENCHMARK.json", cwd)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "granite-8b-1chip.chat", "--seed", str(SEED),
                        "--seconds", "1", "--trace", "0"], cwd=cwd,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr
