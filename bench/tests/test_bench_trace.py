"""The reduction of a profiler trace to device metrics: on events made by
hand, and on a small trace recorded on a TPU v5e by ``record_trace.py``."""
from pathlib import Path

import pytest

from harness import trace

RECORDED = Path(__file__).parent / "data" / "tpu_trace.xplane.pb"
MS = 1_000_000                     # ns


def events():
    """Two chips. The window is [0, 100) ms: decode#0 [0, 40), insert#1
    [50, 90); decode#2 after the window. Chip 0 is busy [5, 35) (two
    overlapping ops) and [60, 80); chip 1 [10, 30) and [55, 85)."""
    return {
        "chips": {
            "/device:TPU:0": {
                "ops": [("fusion.1", 5 * MS, 20 * MS),
                        ("dot.2", 15 * MS, 20 * MS),
                        ("fusion.1", 60 * MS, 20 * MS),
                        ("late", 120 * MS, 5 * MS)],
                "modules": [("jit_decode", 5 * MS, 30 * MS),
                            ("jit_insert", 55 * MS, 40 * MS)]},
            "/device:TPU:1": {
                "ops": [("fusion.1", 10 * MS, 20 * MS),
                        ("dot.2", 55 * MS, 30 * MS)],
                "modules": [("jit_decode", 5 * MS, 30 * MS),
                            ("jit_insert", 55 * MS, 40 * MS)]},
        },
        "annots": [("bench", "decode", 0, 0, 40 * MS),
                   ("bench", "insert", 1, 50 * MS, 100 * MS),
                   ("bench_after", "decode", 2, 110 * MS, 130 * MS)],
    }


def test_busy_is_the_union_of_op_intervals_averaged_over_chips():
    r = trace.reduce(events())
    assert r["chips"] == 2
    assert r["window_s"] == pytest.approx(0.100)
    # chip 0: 30 + 20 ms; chip 1: 20 + 30 ms
    assert r["busy_s"] == pytest.approx(0.050)
    assert r["call_busy_s"][0] == pytest.approx((0.030 + 0.020) / 2)
    assert r["call_busy_s"][1] == pytest.approx((0.020 + 0.030) / 2)
    assert 2 not in r["call_busy_s"]


def test_programs_and_ops_are_ranked_by_device_time():
    r = trace.reduce(events())
    assert dict(r["programs"]) == {"jit_insert": pytest.approx(0.040),
                                   "jit_decode": pytest.approx(0.030)}
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx((0.040 + 0.020) / 2)
    assert "late" not in ops                  # after the window


def test_idle_gaps_are_named_by_what_the_host_was_doing():
    r = trace.reduce(events())
    gaps = dict(r["idle_gaps"])
    # chip 0 idle: [0,5) decode, [35,40) decode, [40,50) loop, [50,60)
    # insert, [80,100) insert; chip 1: [0,10) decode, [30,40) decode,
    # [40,50) loop, [50,55) insert, [85,100) insert
    assert gaps["decode"] == pytest.approx((0.010 + 0.020) / 2)
    assert gaps["serve_loop"] == pytest.approx(0.010)
    assert gaps["insert"] == pytest.approx((0.030 + 0.020) / 2)
    total = sum(gaps.values())
    assert total == pytest.approx(r["window_s"] - r["busy_s"])


def shifted(e, ns):
    return {"annots": e["annots"], "chips": {
        name: {k: [(n, s + ns, d) for n, s, d in v] for k, v in c.items()}
        for name, c in e["chips"].items()}}


@pytest.mark.parametrize("skew_ms", [-1.2, 0.7])
def test_the_device_clock_is_put_on_the_host_clock(skew_ms):
    """A device clock that runs ahead or behind the host's by a constant
    is found again, and the figures do not change."""
    r0 = trace.reduce(events())
    r = trace.reduce(shifted(events(), skew_ms * MS))
    assert r["clock_offsets_s"] == [pytest.approx(-skew_ms / 1e3, abs=2e-5)
                                    ] * 2
    for k in ("busy_s", "window_s"):
        assert r[k] == pytest.approx(r0[k], rel=1e-3)
    assert r["call_busy_s"] == pytest.approx(r0["call_busy_s"], rel=1e-3)


def test_nothing_to_read_gives_none():
    e = events()
    e["annots"] = [a for a in e["annots"] if a[0] != "bench"]
    assert trace.reduce(e) is None
    assert trace.reduce({"chips": {}, "annots": events()["annots"]}) is None


def test_recorded_v5e_trace():
    """Six calls inside the window, one after it, on one v5e chip: the
    device was busy in every call, idle between calls while the host
    slept, and the large program took most of the device time."""
    r = trace.reduce(trace.load(str(RECORDED)))
    assert r["chips"] == 1
    # the v5e's device clock lags the host's by about a millisecond
    assert 0.5e-3 < r["clock_offsets_s"][0] < 3e-3
    assert set(r["call_busy_s"]) == set(range(6))
    assert all(v > 0 for v in r["call_busy_s"].values())
    assert 0 < r["busy_s"] < r["window_s"]
    gaps = dict(r["idle_gaps"])
    assert gaps["serve_loop"] > 0.004          # five host sleeps of 2 ms
    names = [p for p, _ in r["programs"]]
    assert "large" in names[0]
