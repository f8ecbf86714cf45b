"""DeploymentPlan / ReplicaSpec: the typed plan surface replacing
SearchResult's parallel lists. Covers dimension None-ness semantics,
diff/apply round-trips (property-tested where hypothesis is available),
the deprecated SearchResult property shim, and the ServingConfig
argv/json round-trips."""
import argparse
import dataclasses
import json
import warnings

import numpy as np
import pytest

from _hyp import given, settings, st  # noqa: F401 (skips when absent)

from repro.core.genetic import SearchResult
from repro.core.plan import (Assignment, DeploymentPlan, PipelinePlan,
                             ReplicaSpec, StagePlan)
from repro.serving.config import ServingConfig


def _pipe(devs, layers=4):
    return PipelinePlan([StagePlan(list(devs), layers)],
                        cost=0.1, bottleneck=0.1)


def _asg(*groups):
    return Assignment([_pipe(g) for g in groups])


# ---------------------------------------------------------------------------
# Dimension semantics
# ---------------------------------------------------------------------------

def test_from_search_preserves_noneness():
    asg = _asg([0, 1], [2, 3])
    plan = DeploymentPlan.from_search(asg)
    assert plan.num_replicas == 2
    # un-searched dimensions stay None, exactly like the old Optional
    # parallel lists
    assert plan.roles is None and plan.spec_ks is None
    assert plan.kv_dtypes is None and plan.host_blocks is None

    plan2 = DeploymentPlan.from_search(asg, roles=["prefill", "decode"],
                                       spec_ks=[2, 0])
    assert plan2.roles == ["prefill", "decode"]
    assert plan2.spec_ks == [2, 0]
    assert plan2.kv_dtypes is None          # still not searched
    assert plan2.dims == frozenset({"roles", "spec"})


def test_replica_key_is_device_set():
    r = ReplicaSpec(pipeline=_pipe([3, 1]))
    assert r.key == frozenset({1, 3})
    assert r.device_ids == [3, 1]


def test_assignment_round_trip():
    asg = _asg([0, 1], [2], [3, 4, 5])
    plan = DeploymentPlan.from_search(asg)
    got = plan.assignment
    assert [p.device_ids for p in got.pipelines] == \
        [p.device_ids for p in asg.pipelines]


# ---------------------------------------------------------------------------
# diff / apply
# ---------------------------------------------------------------------------

def _mk_plan(groups, roles=None):
    return DeploymentPlan.from_search(_asg(*groups), roles=roles)


def test_diff_empty_on_identical():
    a = _mk_plan([[0, 1], [2, 3]])
    d = a.diff(_mk_plan([[0, 1], [2, 3]]))
    assert d.is_empty


def test_diff_detects_add_remove_change():
    a = _mk_plan([[0, 1], [2, 3]], roles=["both", "both"])
    b = _mk_plan([[0, 1], [4, 5]], roles=["prefill", "decode"])
    d = a.diff(b)
    assert {tuple(sorted(r.key)) for r in d.removed} == {(2, 3)}
    assert {tuple(sorted(r.key)) for r in d.added} == {(4, 5)}
    # replica {0,1} survives but its role changed
    assert len(d.changed) == 1
    old, new = d.changed[0]
    assert old.key == new.key == frozenset({0, 1})
    assert old.role == "both" and new.role == "prefill"


def test_apply_round_trip_deterministic():
    rng = np.random.RandomState(7)
    for _ in range(50):
        n_dev = rng.randint(4, 12)
        devs = list(range(n_dev))
        rng.shuffle(devs)

        def cut(ds):
            groups, i = [], 0
            while i < len(ds):
                k = rng.randint(1, 4)
                groups.append(ds[i:i + k])
                i += k
            return groups

        ga = cut(devs)[:rng.randint(1, 5)]
        gb = cut(devs)[:rng.randint(1, 5)]
        roles_a = [rng.choice(["both", "prefill", "decode"]) for _ in ga]
        roles_b = [rng.choice(["both", "prefill", "decode"]) for _ in gb]
        a = _mk_plan(ga, roles=roles_a)
        b = _mk_plan(gb, roles=roles_b)
        assert a.apply(a.diff(b)).canonical() == b.canonical()
        assert b.apply(b.diff(a)).canonical() == a.canonical()


@settings(max_examples=60, deadline=None)
@given(st.data())
def test_apply_round_trip_property(data):
    def plan(tag):
        n = data.draw(st.integers(1, 4), label=f"{tag}_replicas")
        groups, base = [], 0
        for i in range(n):
            k = data.draw(st.integers(1, 3), label=f"{tag}_width{i}")
            groups.append(list(range(base, base + k)))
            base += k
        roles = [data.draw(st.sampled_from(["both", "prefill", "decode"]),
                           label=f"{tag}_role{i}") for i in range(n)]
        return _mk_plan(groups, roles=roles)

    a, b = plan("a"), plan("b")
    assert a.apply(a.diff(b)).canonical() == b.canonical()


def test_diff_describe_mentions_changes():
    a = _mk_plan([[0, 1]], roles=["both"])
    b = _mk_plan([[0, 1], [2]], roles=["prefill", "decode"])
    txt = a.diff(b).describe()
    assert "+[" in txt and "->" in txt


# ---------------------------------------------------------------------------
# SearchResult deprecation shim
# ---------------------------------------------------------------------------

def _result(**dims):
    plan = DeploymentPlan.from_search(_asg([0, 1], [2, 3]), **dims)
    return SearchResult(plan=plan, attainment=1.0, history=[], evaluations=0)


def test_search_result_plan_is_primary():
    res = _result(roles=["prefill", "decode"])
    assert res.plan.roles == ["prefill", "decode"]
    # .assignment is NOT deprecated (it's the serving surface)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert res.assignment.num_replicas == 2


@pytest.mark.parametrize("name,value", [
    ("roles", ["prefill", "decode"]),
    ("spec_ks", [3, 0]),
    ("kv_dtypes", ["int8", None]),
    ("host_blocks", [4, 0]),
])
def test_search_result_deprecated_properties(name, value):
    res = _result(**{name: value})
    with pytest.warns(DeprecationWarning, match=name):
        assert getattr(res, name) == value
    # None-ness preserved for un-searched dimensions
    bare = _result()
    with pytest.warns(DeprecationWarning):
        assert getattr(bare, name) is None


# ---------------------------------------------------------------------------
# ServingConfig round-trips
# ---------------------------------------------------------------------------

def test_serving_config_argv_round_trip():
    cfg = ServingConfig(arch="granite-8b", reduced=True, rate=7.5,
                        policy="static", prefix_caching=True,
                        kvsan=True, kv_dtype="search", spec_decode=True,
                        spec_k=3, route_seed=11, host_mem_gb=2.0,
                        shared_prefix=16, disaggregate=True)
    assert ServingConfig.parse(cfg.to_args()) == cfg
    assert ServingConfig.parse([]) == ServingConfig()


def test_serving_config_json_round_trip():
    cfg = ServingConfig(arch="llama2-70b", block_size=32, kv_dtype="fp8",
                        prefill_chunk=64)
    assert ServingConfig.from_json(cfg.to_json()) == cfg


def test_serving_config_every_field_is_a_flag():
    ap = argparse.ArgumentParser()
    ServingConfig.add_args(ap)
    flags = {a.dest for a in ap._actions if a.dest != "help"}
    assert flags == {f.name for f in dataclasses.fields(ServingConfig)}


def test_normalized_gates_paged_features():
    bad = ServingConfig(disaggregate=True, spec_decode=True,
                        kv_dtype="fp8", host_mem_gb=1.0,
                        cluster_prefix=True, prefix_hit_rate=0.5)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        ok = bad.normalized()
    # the one gate left: the host tier and the cluster directory need
    # prefix caching; every paged feature else passes through
    assert len(w) == 1 and "prefix caching" in str(w[0].message)
    assert ok.host_mem_gb == 0.0 and not ok.cluster_prefix
    assert ok.disaggregate and ok.spec_decode
    assert ok.kv_dtype == "fp8" and ok.prefix_hit_rate == 0.5
    with_prefix = dataclasses.replace(bad, prefix_caching=True)
    assert with_prefix.normalized() == with_prefix
    # idempotent: a consistent config passes through silently
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert ok.normalized() == ok


def test_max_len_rounds_to_blocks():
    cfg = ServingConfig(prompt_len=10, out_len=5, block_size=16)
    assert cfg.max_len() == 32                # 10 + 8 + 5, whole pages
    assert ServingConfig(prompt_len=10, out_len=5,
                         block_size=8).max_len() == 24
    assert ServingConfig(prompt_len=11, out_len=5,
                         block_size=8).max_len() == 24


def test_paged_is_the_only_cache_layout():
    """The continuous engine is always paged: ``ServingConfig`` takes
    ``cache_layout="paged"`` only, from the constructor and from JSON,
    and ``Router``/``InferenceEngine`` take no layout at all."""
    import inspect
    from repro.serving.engine import InferenceEngine
    from repro.serving.router import Router
    assert ServingConfig().cache_layout == "paged"
    assert ServingConfig(cache_layout="paged") == ServingConfig()
    for gone in ("contiguous", ""):
        with pytest.raises(ValueError, match="PagedPipelineBatcher"):
            ServingConfig(cache_layout=gone)
        doc = json.loads(ServingConfig().to_json())
        doc["cache_layout"] = gone
        with pytest.raises(ValueError, match="PagedPipelineBatcher"):
            ServingConfig.from_json(json.dumps(doc))
    for cls in (Router, InferenceEngine):
        assert "cache_layout" not in inspect.signature(cls).parameters


def test_guard_layers_pins_both_ends():
    cfg = ServingConfig(kv_guard_layers=2)
    assert cfg.guard_layers(8) == [0, 1, 6, 7]
    assert cfg.guard_layers(2) == [0, 1]      # clamped to half the stack
    assert ServingConfig().guard_layers(8) == []
