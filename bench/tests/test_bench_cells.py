"""The cells' configurations pinned to the program, on the CPU: each
file's sizes are the program's published model, the scheduler still plans
the deployment the file states, and the page pool it is given is the one
PERF.md quotes. A change to any of them then shows here first."""
import json
import types

import pytest

from checkout import BENCH
from harness import cell
from harness.spec import Spec

# the bytes a TPU v5e chip's runtime lets a process hold
# (``memory_stats()["bytes_limit"]``, read on the chip)
V5E_BYTES_LIMIT = 16_909_336_064


def chip(limit=V5E_BYTES_LIMIT):
    return types.SimpleNamespace(
        memory_stats=lambda: {"bytes_limit": limit})


@pytest.fixture(scope="module")
def spec():
    return Spec(BENCH.parent)


def deployment(spec, workload):
    w = spec.workload(workload)
    c = spec.config(w["config"])
    cfg = cell.model_config(c)
    sv = cell.serving_config(cfg, c, spec.traffic(w["traffic"]),
                             spec.cell(workload))
    return c, cfg, sv


def test_the_whole_model_is_the_published_one(spec):
    from repro.configs.granite_8b import CONFIG
    c, cfg, _ = deployment(spec, "granite-8b-4chip.docs")
    assert c["reduced"] == {}
    assert c["num_hidden_layers"] == c["published"]["num_hidden_layers"]
    for key in ("num_layers", "d_model", "num_heads", "num_kv_heads",
                "d_ff", "vocab_size", "head_dim_", "dtype"):
        assert getattr(cfg, key) == getattr(CONFIG, key), key
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = next(x for x in bench["configs"]
                 if x["name"] == "granite-8b-4chip")
    assert entry["reduced"] == []


@pytest.mark.parametrize("workload,rate,describe,chips", [
    ("granite-8b-1chip.chat", None, "[1] layers=[16]", 1),
    ("granite-8b-4chip.docs", None, "[4] layers=[36]", 4),
    ("granite-8b-4chip.docs", 0.6, "[2] layers=[36]; [2] layers=[36]", 4)])
def test_the_scheduler_plans_the_stated_deployment(spec, workload, rate,
                                                   describe, chips):
    """One TP-4 stage over the whole 2x2 host for the documents cell. The
    plan follows the traffic's rate: at 0.6 req/s the scheduler plans two
    TP-2 replicas instead, so the cell's rate stays below that."""
    w = spec.workload(workload)
    c = spec.config(w["config"])
    cfg = cell.model_config(c)
    trf = spec.traffic(w["traffic"])
    if rate is not None:
        trf = dict(trf, rate_per_s=rate)
    sv = cell.serving_config(cfg, c, trf, spec.cell(workload))
    plan = cell.make_plan(cfg, sv, [chip()] * chips)
    assert plan.describe() == describe


def test_documents_pool_holds_every_slot_full(spec):
    """At the chip's limit, after the replicated embedding and head
    (805,314,560 B), a quarter of every layer (36 x 109,056,000 B) and the
    cell's reserve, the pool would hold 18,951 blocks of 16 tokens at
    36,864 B a token; it is capped at every slot full, 16 slots x 17,424
    tokens in blocks of 16 plus the null block."""
    workload = "granite-8b-4chip.docs"
    c, cfg, sv = deployment(spec, workload)
    devices = [chip()] * 4
    plan = cell.make_plan(cfg, sv, devices)
    cellf = spec.cell(workload)
    assert sv.max_len() == 17_424
    blocks = cell.pool_blocks(c, plan, devices, sv, cellf)
    assert blocks == [16 * 17_424 // 16 + 1] == [17_425]
    # the reserve is what keeps the pool at every slot full, and no more
    weights = 36 * 109_056_000 + 805_314_560
    spare = V5E_BYTES_LIMIT - cellf["reserve_bytes"] - weights
    assert spare // (16 * 36_864) == 18_951
