"""The reduction from a trace to numbers: on a synthetic trace with known
answers, and on a small trace recorded on the chip (tests/data)."""
import gzip
import json
import os

import pytest

from benchmarks.trace import kernels, reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# times in ns.  Device 0: a `while` (a scanned stack) from 100 to 900 that
# holds two operations, then one more at 1000; device 1 is busy throughout
SYNTHETIC = {
    "devices": {
        "/device:TPU:0": [["while.1", 100, 800], ["fusion.1", 100, 300],
                          ["all-reduce.2 all-reduce f32[8]", 450, 350], ["fusion.3", 1000, 100],
                          ["fusion.1", 2000, 50]],      # outside the window
        "/device:TPU:1": [["fusion.9", 0, 1200]],
    },
    "host": [["bench::window", 0, 1200],
             ["generation::ragged_step", 50, 900],
             ["generation::sample", 905, 60],
             ["generation::sample", 1500, 10]],
}


def test_window_is_the_benchmarks_own_span():
    assert reduce.window(SYNTHETIC) == (0, 1200)
    bare = {"devices": SYNTHETIC["devices"], "host": []}
    assert reduce.window(bare) == (0, 2050)


def test_busy_is_the_union_not_the_sum():
    dev0 = SYNTHETIC["devices"]["/device:TPU:0"]
    assert reduce.busy_intervals(dev0, 0, 1200) == [[100, 900], [1000, 1100]]
    assert reduce.busy_ns(dev0, 0, 1200) == 900      # the sum would be 1550
    assert reduce.busy_ns(dev0, 500, 1050) == 450    # clipped to the window


def test_self_time_takes_children_off_their_parent():
    ops = reduce.self_times(SYNTHETIC["devices"]["/device:TPU:0"], 0, 1200)
    assert ops == {"while.1": [150, 1], "fusion.1": [300, 1],
                   "all-reduce.2 all-reduce f32[8]": [350, 1], "fusion.3": [100, 1]}
    assert sum(ns for ns, _ in ops.values()) == 900  # == the busy union


def test_gaps_go_to_the_innermost_host_span_open_in_them():
    gaps = reduce.idle_gaps(SYNTHETIC["devices"]["/device:TPU:0"],
                            SYNTHETIC["host"], 0, 1200)
    assert gaps == [(0, 100, "generation::ragged_step"),
                    (900, 1000, "generation::sample"),
                    (1100, 1200, "(no span)")]


def test_reduce_and_breakdown():
    red = reduce.reduce(SYNTHETIC)
    assert red["window_s"] == pytest.approx(1200e-9)
    # averaged over the two devices: (900 + 1200) / 2
    assert red["busy_s"] == pytest.approx(1050e-9)
    assert red["devices"] == 2
    assert red["spans"] == {
        "generation::ragged_step": [pytest.approx(900e-9)],
        "generation::sample": [pytest.approx(60e-9)]}
    seconds, calls = reduce.op_seconds(red, reduce.is_collective)
    assert (seconds, calls) == (pytest.approx(350e-9), 1)
    b = reduce.breakdown(red, top=2)
    assert [n for n, _ in b["device_ops"]] == ["all-reduce.2 all-reduce f32[8]", "fusion.1"]
    assert b["idle_gaps"][0][1] == pytest.approx(100e-9)
    assert len(b["idle_gaps"]) == 2


def test_a_trace_with_no_device_operation_is_refused():
    with pytest.raises(ValueError):
        reduce.reduce({"devices": {}, "host": SYNTHETIC["host"]})


FLASH_FORWARD_HLO = (
    '%closed_call.14 = (f32[96,1024,64]{2,1,0:T(8,128)}, f32[96,1024,1]'
    '{2,1,0:T(8,128)}) custom-call(f32[96,1024,64]{2,1,0:T(8,128)S(1)} '
    '%bitcast.1181, f32[96,1024,64]{2,1,0:T(8,128)} %bitcast.1184, '
    'f32[96,1024,64]{2,1,0:T(8,128)} %bitcast.1186, f32[1,1,1024]'
    '{2,1,0:T(1,128)S(1)} %broadcast_in_dim.409), custom_call_target='
    '"tpu_custom_call", operand_layout_constraints={f32[96,1024,64]{2,1,0}}')
FLASH_BACKWARD_HLO = (
    '%checkpoint.28 = f32[96,1024,64]{2,1,0:T(8,128)} custom-call('
    'f32[96,1024,64]{2,1,0:T(8,128)S(1)} %bitcast.1172, f32[96,1024,64]'
    '{2,1,0:T(8,128)S(1)} %bitcast.1168, f32[96,1024,1]{2,1,0:T(8,128)} '
    '%pallas_call.59, f32[96,1024,1]{2,1,0:T(8,128)} %copy.295, '
    'f32[96,1024,64]{2,1,0:T(8,128)} %bitcast.1175, f32[96,1024,64]'
    '{2,1,0:T(8,128)} %bitcast.1178, f32[1,1,1024]{2,1,0:T(1,128)S(1)} '
    '%copy-done.126), custom_call_target="tpu_custom_call", '
    'operand_layout_constraints={f32[96,1024,64]{2,1,0}}')


def test_kernels_are_told_apart_as_a_chip_trace_showed_them():
    # the flash kernel's calls as GPT-2's step showed them on a v5e (PR 22)
    assert kernels.short_name(FLASH_FORWARD_HLO) == kernels.FLASH_FORWARD
    assert kernels.short_name(FLASH_BACKWARD_HLO) == kernels.FLASH_BACKWARD
    assert kernels.short_name(
        "%fusion.251 = f32[17,50272]{1,0:T(8,128)S(1)} fusion(f32[4096,50272]"
        "{0,1:T(8,128)} %flat_120_.1), kind=kOutput, calls=%fused.324"
    ) == "fusion fusion f32[17,50272]"
    assert kernels.short_name(
        "%all-reduce-start.3 = (f32[8]{0}, f32[8]{0}) all-reduce-start("
        "f32[8]{0} %x), replica_groups={}") \
        == "all-reduce-start all-reduce-start (f32[8], f32[8])"
    assert reduce.is_collective(
        "all-reduce-start all-reduce-start (f32[8], f32[8])")
    assert not reduce.is_collective("fusion fusion f32[17,50272]")


def test_recorded_chip_trace():
    """350 ms of `opt-6.7b-d8.decode-closed` on a TPU v5 lite: three engine
    steps of 8 layers and the start of a fourth."""
    with gzip.open(os.path.join(DATA, "decode_closed_v5e.trace.json.gz")) \
            as f:
        raw = json.load(f)
    trace = {"devices": {k: [[kernels.short_name(n), a, d] for n, a, d in v]
                         for k, v in raw["devices"].items()},
             "host": raw["host"]}
    red = reduce.reduce(trace)
    assert red["window_s"] == pytest.approx(0.35)
    assert red["busy_s"] == pytest.approx(0.339199633)
    seconds, calls = reduce.op_seconds(red, kernels.is_ragged)
    assert calls == 25 and seconds == pytest.approx(0.258635788)
    # nothing else in the serving step is a Pallas call
    assert not any(n.startswith("pallas:") and n != kernels.RAGGED
                   for n in red["ops"])
    # leaves only: the self times add up to the busy union
    assert sum(s for s, _ in red["ops"].values()) == pytest.approx(
        red["busy_s"])
    assert red["spans"]["generation::ragged_step"][:2] == [
        pytest.approx(0.112137961), pytest.approx(0.112237652)]
    # the device waits while the engine packs and samples inside its step
    assert red["gaps"]["generation::ragged_step"] == pytest.approx(
        0.010800316)
    b = reduce.breakdown(red)
    assert b["device_ops"][0] == [kernels.RAGGED, pytest.approx(0.258635788)]
    # the 16 whole-pool transposes of every step, under one name
    assert b["device_ops"][1] == [
        "copy_bitcast_fusion fusion f32[32,1280,16,128]",
        pytest.approx(0.05025822)]
    assert red["ops"][b["device_ops"][1][0]][1] == 3 * 16 + 2
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
