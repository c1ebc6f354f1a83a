"""The yardstick's arithmetic: the trace reduction on a hand-made trace,
and the needed-work functions against numbers worked out by hand."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark import trace_reduce, work  # noqa: E402


def _trace():
    # on the device: op A 2000-5000, the kernel 4000-7000 (overlaps A), a
    # gap 7000-9000 before the second program, op C 9000-10000; the
    # traced stretch lasted 10000 ns on the host clock, so 2000 ns of
    # idle lie before the first and after the last op
    return {
        "/host:CPU": {"python": [("unrelated", 0.0, 20000.0)]},
        "/device:TPU:0": {
            "XLA Ops": [("fusion.1", 2000.0, 3000.0),
                        ("hist_kernel", 4000.0, 3000.0),
                        ("copy.2", 9000.0, 1000.0)],
            "XLA Modules": [("jit_step", 2000.0, 5000.0),
                            ("jit_score", 9000.0, 1000.0)]},
    }


def test_union_counts_overlap_once():
    assert trace_reduce.union_ns([(0, 10), (5, 15), (20, 30)]) == 25
    assert trace_reduce.union_ns([]) == 0
    assert trace_reduce.union_ns([(3, 4), (0, 10)]) == 10


def test_short_name_keeps_the_instruction_name():
    assert trace_reduce.short_name(
        "%fusion.14 = bf16[1024,56,56,256]{3,0,2,1} fusion(...)") \
        == "fusion.14"
    assert trace_reduce.short_name("jit_run") == "jit_run"


def test_reduce_busy_idle_kernel_exactly():
    got = trace_reduce.reduce(_trace(), window_s=10000e-9,
                              kernel_pattern="hist")
    assert got["window_s"] == pytest.approx(10000e-9)
    assert got["busy_s"] == pytest.approx(6000e-9)       # 2000-7000, 9000-10000
    assert got["idle_share"] == pytest.approx(0.4)
    assert got["kernel_s"] == pytest.approx(3000e-9)
    assert got["kernel_calls"] == 1
    assert got["kernel_s"] / got["busy_s"] == pytest.approx(0.5)
    ops = dict(got["device_ops"])
    assert ops == {"fusion.1": pytest.approx(3000e-9),
                   "hist_kernel": pytest.approx(3000e-9),
                   "copy.2": pytest.approx(1000e-9)}
    gaps = dict(got["idle_gaps"])
    assert gaps["before jit_score"] == pytest.approx(2000e-9)
    assert gaps["(before the first and after the last op)"] \
        == pytest.approx(2000e-9)
    assert sum(gaps.values()) == pytest.approx(
        got["window_s"] - got["busy_s"])
    assert got["longest_gap_s"] == pytest.approx(2000e-9)


def test_a_gap_inside_a_program_is_named_for_it():
    trace = _trace()
    trace["/device:TPU:0"]["XLA Modules"] = [("jit_step", 2000.0, 8000.0)]
    gaps = dict(trace_reduce.reduce(trace, window_s=10000e-9)["idle_gaps"])
    assert gaps["inside jit_step"] == pytest.approx(2000e-9)


def test_reduce_returns_nothing_without_a_device():
    host_only = {"/host:CPU": _trace()["/host:CPU"]}
    assert trace_reduce.reduce(host_only, window_s=1.0) is None
    assert trace_reduce.reduce(_trace(), window_s=0.0) is None


def test_layer_readers_return_nothing_on_an_empty_trace():
    from benchmark import run
    for name in ("device_idle.fit", "device_idle.featurize",
                 "hist_kernel_share"):
        reader = run._load_module("layer_metrics", name)
        assert reader.read({"trace": None}) is None
        assert reader.read({"trace": {"busy_s": 0.0, "kernel_calls": 0,
                                      "idle_share": 1.0}}) is None


def test_resnet50_flops_by_hand():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "resnet50-imagenet.json")) as f:
        cfg = json.load(f)
    # multiply-accumulates, stage by stage at 224x224 (stride on the 3x3)
    macs = 112 * 112 * 49 * 3 * 64
    s = 56 * 56
    macs += s * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256) \
        + 2 * s * (256 * 64 + 9 * 64 * 64 + 64 * 256)
    for (h_in, h, c_in, mid, n) in ((56, 28, 256, 128, 4),
                                    (28, 14, 512, 256, 6),
                                    (14, 7, 1024, 512, 3)):
        out = 4 * mid
        macs += (h_in * h_in * c_in * mid + h * h * 9 * mid * mid
                 + h * h * mid * out + h * h * c_in * out)
        macs += (n - 1) * h * h * (out * mid + 9 * mid * mid + mid * out)
    assert macs == 4_087_136_256
    assert work.resnet_forward_flops(cfg, head=False) == 2 * macs
    # with the classifier: torchvision's published 4.09 GMACs
    assert work.resnet_forward_flops(cfg, head=True) == 2 * 4_089_184_256


def test_gbdt_byte_floor_by_hand():
    # 10.5M rows x (28 bin bytes + 16) = 462 MB an iteration
    assert work.gbdt_iteration_min_bytes(10_500_000, 28) == 462_000_000
    assert work.gbdt_iteration_min_bytes(1, 0) == 16


def test_peaks_known_kind_and_unknown_is_an_error():
    assert work.peaks("TPU v5 lite")["flops_per_s_bf16"] == 197e12
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")
