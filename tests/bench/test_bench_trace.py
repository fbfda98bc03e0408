"""The reduction from a profiler trace to busy time, kernel time, the top
device operations and the idle gaps by host span."""
import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
import trace_reduce as TR


def synthetic():
    # one device: ops overlap (a fused pair) and leave three gaps; the
    # host: a window around a step, a submit and a wait
    return {
        "device": [[("fusion.1", 100, 50), ("paged_decode_attention.3", 200,
                                             100),
                    ("fusion.22", 250, 100), ("copy.7", 700, 50),
                    ("fusion.5", 2000, 10)]],          # after the window
        "host": [("bench.window", 0, 1000), ("bench.step", 90, 400),
                 ("bench.submit", 600, 100), ("bench.wait", 800, 150)],
    }


def test_union_merges_overlaps():
    assert TR.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]


def test_synthetic_reduction():
    tr = synthetic()
    lo, hi = TR.window_of(tr, "bench.window")
    assert (lo, hi) == (0, 1000)
    red = TR.reduce(tr, lo, hi, ["paged_decode_attention"])
    # busy: [100,150) + [200,350) + [700,750) = 250 ns
    assert red["window_s"] == pytest.approx(1e-6)
    assert red["busy_s"] == pytest.approx(250e-9)
    assert red["kernel_s"]["paged_decode_attention"] == pytest.approx(1e-7)
    ops = dict(red["device_ops"])
    assert ops["fusion"] == pytest.approx(150e-9) and "copy" in ops
    # idle: [0,100) step 10 window 90; [150,200) step 50; [350,700) step
    # 140, submit 100, window 110; [750,1000) wait 150, window 100
    idle = {k.split(" ")[0]: v for k, v in red["idle_gaps"]}
    assert idle["bench.step"] == pytest.approx(200e-9)
    assert idle["bench.submit"] == pytest.approx(100e-9)
    assert idle["bench.wait"] == pytest.approx(150e-9)
    assert idle["bench.window"] == pytest.approx(300e-9)
    assert sum(idle.values()) + red["busy_s"] == pytest.approx(1e-6)


def test_two_devices_are_averaged():
    tr = synthetic()
    tr["device"].append([("fusion.9", 0, 1000)])
    red = TR.reduce(tr, 0, 1000, ["paged_decode_attention"])
    assert red["busy_s"] == pytest.approx((250e-9 + 1000e-9) / 2)
    assert red["kernel_s"]["paged_decode_attention"] == pytest.approx(5e-8)


def test_op_family():
    assert TR.op_family("fusion.123") == "fusion"
    assert TR.op_family("paged_decode_attention") == "paged_decode_attention"
    hlo = ("%copy.61 = bf16[32,353,64,5,64]{4,3,2,1,0} copy(bf16[32,353,64,"
           "5,64] %paged_decode_attention.14)")
    assert TR.op_family(hlo) == "copy" and TR.op_name(hlo) == "copy.61"


def test_nested_ops_count_self_time_and_kernels_by_own_name():
    # a while loop around a kernel and a copy that reads the kernel's
    # output: the loop keeps only its own time, the copy is no kernel
    tr = {"device": [[("%while.3 = (s32[]) while(%t)", 0, 100),
                      ("%paged_decode_attention.1 = bf16[2] custom-call()",
                       10, 30),
                      ("%copy.2 = bf16[2] copy(%paged_decode_attention.1)",
                       50, 20)]],
          "host": [("bench.window", 0, 100)]}
    red = TR.reduce(tr, 0, 100, ["paged_decode_attention"])
    ops = dict(red["device_ops"])
    assert ops["while"] == pytest.approx(50e-9)
    assert ops["copy"] == pytest.approx(20e-9)
    assert red["kernel_s"]["paged_decode_attention"] == pytest.approx(30e-9)
    assert red["busy_s"] == pytest.approx(100e-9)


def test_recorded_chip_trace():
    # 60 ms of smollm360m.agent-prefix traced on a TPU v5e: the device
    # is busy nearly throughout, the page-walk kernel is found by name,
    # and the scan's while loop holds the rest
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "agent_prefix_trace.json")
    with open(path) as f:
        raw = json.load(f)
    tr = {"device": [[tuple(e) for e in d] for d in raw["device"]],
          "host": [tuple(e) for e in raw["host"]]}
    lo, hi = TR.window_of(tr, "bench.window")
    red = TR.reduce(tr, lo, hi, ["paged_decode_attention"])
    assert red["window_s"] == pytest.approx(0.06)
    assert 0.9 * red["window_s"] < red["busy_s"] <= red["window_s"]
    k = red["kernel_s"]["paged_decode_attention"]
    assert 0 < k < red["busy_s"]
    ops = dict(red["device_ops"])
    assert ops["paged_decode_attention"] == pytest.approx(k)
    assert sum(ops.values()) <= red["busy_s"] * (1 + 1e-9)
    idle = sum(v for _, v in red["idle_gaps"])
    assert idle + red["busy_s"] == pytest.approx(red["window_s"])
    assert red["idle_gaps"][0][0].startswith("bench.step")
