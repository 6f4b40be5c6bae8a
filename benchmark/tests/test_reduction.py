"""The trace reduction on a hand-built interval set and on one small
recorded TPU trace (`data/small_tpu_trace.xplane.pb`, made by
`record_trace.py` on a v5e: three runs of one small program, the host
asleep 5 ms after each)."""

import os

import pytest

from lib import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small_tpu_trace.xplane.pb")


def hand_built():
    ops0 = [("while", 100, 400), ("fusion.1", 100, 100),
            ("conv.2", 250, 100), ("copy.3", 600, 100)]
    modules0 = [("jit_step(123)", 100, 400), ("jit_other(5)", 600, 100)]
    host = [("bench.slice", 0, 1000), ("bench.train", 60, 430),
            ("PjitFunction(step)", 70, 30), ("host.sleep", 510, 80),
            ("bench.train", 590, 300)]
    return {"/device:TPU:0": {"XLA Ops": ops0, "XLA Modules": modules0,
                              "Steps": [("0", 0, 1000)]},
            "/host:CPU": {"main": host}}


def test_union_clip_gaps():
    merged = trace.union([(5, 9), (1, 3), (2, 4), (9, 9), (8, 12)])
    assert merged == [(1, 4), (5, 12)]
    assert trace.total(merged) == 10
    assert trace.clip(merged, 2, 6) == [(2, 4), (5, 6)]
    assert trace.gaps(trace.clip(merged, 0, 20), 0, 20) == [
        (0, 1), (4, 5), (12, 20)]
    assert trace.gaps([], 3, 7) == [(3, 7)]


def test_self_times_take_nested_ops_out():
    got = dict(trace.self_times([("w", 0, 100), ("a", 10, 30),
                                 ("b", 30, 50), ("c", 35, 40),
                                 ("x", 120, 130)]))
    want = {"w": 60, "a": 20, "b": 15, "c": 5, "x": 10}
    assert got == {k: v / 1e9 for k, v in want.items()}


def test_short_op():
    line = ("%fusion.5 = bf16[64,9]{1,0:T(8,128)(2,1)} fusion(bf16[9]{0} "
            "%p), kind=kLoop")
    assert trace.short_op(line) == "fusion.5 bf16[64,9]"
    assert trace.short_op("%f.2 = (f32[]{:T(128)}, u8[4]{0}) fusion()") \
        == "f.2 f32[]"
    assert trace.short_op("conv.2") == "conv.2"


def test_hand_built_one_chip():
    r = trace.reduce(hand_built())
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(1000e-9)
    # [100,500] with overlapping ops inside, and [600,700]
    assert r["busy_s"] == pytest.approx(500e-9)
    ops = dict(r["device_ops"])
    assert ops == pytest.approx({
        "jit_step/while": 200e-9, "jit_step/fusion.1": 100e-9,
        "jit_step/conv.2": 100e-9, "jit_other/copy.3": 100e-9})
    assert sum(ops.values()) == pytest.approx(r["busy_s"])
    gaps = dict(r["idle_gaps"])
    assert gaps == pytest.approx({
        "outside bench.* | nothing traced": 100e-9,   # [0,100]
        "outside bench.* | host.sleep": 100e-9,       # [500,600]
        "bench.train | nothing traced": 300e-9})      # [700,1000]
    assert r["busy_s"] + sum(gaps.values()) == pytest.approx(r["window_s"])
    assert r["longest_gap_s"] == pytest.approx(300e-9)


def test_hand_built_two_chips_mean_and_window_clip():
    planes = hand_built()
    # The second chip runs one op that straddles the window's end.
    planes["/device:TPU:1"] = {"XLA Ops": [("fusion.9", 800, 400)],
                               "XLA Modules": [("jit_step(123)", 800, 400)]}
    r = trace.reduce(planes)
    assert r["chips"] == 2
    assert r["busy_s_per_chip"] == pytest.approx([500e-9, 200e-9])
    assert r["busy_s"] == pytest.approx(350e-9)
    assert dict(r["device_ops"])["jit_step/fusion.9"] == pytest.approx(
        100e-9)  # 200 ns inside the window, a chip's share of two


def test_no_device_op_is_nothing():
    planes = hand_built()
    del planes["/device:TPU:0"]
    assert trace.reduce(planes) is None


def test_recorded_trace():
    planes = trace.load(DATA)
    r = trace.reduce(planes)
    assert r is not None and r["chips"] == 1
    spans = [e for e in trace.host_events(planes) if e[0] == "bench.train"]
    assert len(spans) == 3
    assert 0 < r["busy_s"] < r["window_s"]
    gaps = dict(r["idle_gaps"])
    assert r["busy_s"] + sum(gaps.values()) == pytest.approx(r["window_s"])
    # The host slept 5 ms after each run: that is where the chip idled.
    asleep = sum(v for k, v in gaps.items() if "host.sleep" in k)
    assert asleep >= 0.010 and asleep > 0.5 * sum(gaps.values())
    assert r["device_ops"] and all(
        name.startswith("jit_") for name, _ in r["device_ops"])
    assert sum(s for _, s in r["device_ops"]) <= r["busy_s"] * 1.0001
