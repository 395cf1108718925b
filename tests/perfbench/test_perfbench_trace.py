"""The trace reduction on a small recorded trace: busy and idle share,
per-operation sums and the naming of idle gaps come out as worked out by
hand from the events in ``data/recorded_trace.json``."""

import json
from pathlib import Path

import pytest

from perfbench.layer_metrics import conv_roofline
from perfbench.lib import trace

DOC = json.loads((Path(__file__).parent / "data"
                  / "recorded_trace.json").read_text())


def test_window_busy_and_idle_by_hand():
    got = trace.reduce(DOC)
    # the harness's own span: starts at 200,000 ns, lasts 2,780,750 ns
    assert got.window_s == pytest.approx(2_780_750e-9)
    # five clusters of device operations, none overlapping another:
    first = 12 + 12 + 12 + 12 + 2 + 11 + 11 + 277 + 316 + 1539
    conv = 13 + 6 + 1287 + 1761 + 14045 + 541 + 3 + 2 + 41365 + 26665 \
        + 3398 + 28624
    busy = first + 621 + 596 + conv + 38891
    assert busy == 160_022
    assert got.devices == 1
    assert got.busy_s == pytest.approx(busy * 1e-9)
    assert got.idle_share == pytest.approx(1 - 160_022 / 2_780_750)


def test_operation_sums_by_hand():
    ops = trace.reduce(DOC).op_seconds
    # the same instruction ran twice in the window: 596 ns and 38,891 ns
    assert ops["convert_element_type.1 convert"] == pytest.approx(39_487e-9)
    assert ops["copy-done copy-done"] == pytest.approx((277 + 3) * 1e-9)
    name = "convolution_select_fusion fusion kOutput 256x8x33x64 3x3x3x64"
    assert ops[name] == pytest.approx(41_365e-9)
    assert trace.reduce(DOC).top_ops(2) == [
        [name, pytest.approx(41_365e-9)],
        ["convert_element_type.1 convert", pytest.approx(39_487e-9)]]
    assert sum(ops.values()) == pytest.approx(160_022e-9)


def test_idle_gaps_are_named_by_the_innermost_host_span():
    gaps = trace.reduce(DOC).gaps
    # gaps under 100 us are not listed: the 82,880 ns before the first
    # operation and the few-ns seams inside a cluster
    assert [round(s * 1e9) for s, _ in gaps] == [
        1_199_379 - 285_794, 1_780_750 - 1_200_000, 2_415_907 - 1_781_346,
        2_696_392 - 2_533_628, 2_980_750 - 2_735_283]
    assert [name for _, name in gaps] == [
        # middle at 742,586 ns: inside PjitFunction(_uniform) > DevicePut on
        # the Python thread and AllocateRawBuffer on main, but innermost
        # (39,760 ns) is the worker enqueueing the program
        "pjrt-tpu-tasks: DoEnqueueProgram",
        # middle at 1,490,375 ns: main waits 276,109 ns for device memory
        "main: DeferredTpuAllocator::Allocate",
        # middle at 2,098,626 ns: main prepares the next launch
        "main: CommonPjRtLoadedExecutable::ExecutePrepare",
        # middle at 2,615,010 ns: the completion thread, 38,730 ns
        "futex-default-SDomainT: tpu::System::Execute=>Done",
        # middle at 2,858,016 ns: no span covers it; the host is in Python
        "host: no span (Python)"]
    top = trace.reduce(DOC).top_gaps(2)
    assert top[0] == ["pjrt-tpu-tasks: DoEnqueueProgram",
                      pytest.approx(913_585e-9)]


def test_a_trace_without_the_window_span_or_device_is_refused():
    hostless = {"planes": [p for p in DOC["planes"]
                           if not p["name"].startswith("/host:")]}
    with pytest.raises(SystemExit):
        trace.reduce(hostless)
    no_device = {"planes": [p for p in DOC["planes"]
                            if p["name"].startswith("/host:")]}
    got = trace.reduce(no_device)
    assert got.busy_s == 0.0 and got.devices == 0


@pytest.mark.parametrize("text,want", [
    ("%copy-done.93 = f32[256]{0:T(256)S(1)} copy-done((f32[256]{0:T(256)"
     "S(1)}, f32[256]{0:T(256)}, u32[]{:S(2)}) %copy-start.93)",
     "copy-done.93 copy-done"),
    ("%fusion.768 = bf16[32,256,256,64]{3,0,2,1:T(8,128)(2,1)} fusion(bf16"
     "[32,256,256,64]{3,0,2,1:T(8,128)(2,1)} %copy.1004, f32[3,3,64,64]"
     "{3,2,1,0:T(8,128)S(1)} %copy-done.36), kind=kOutput, calls=%fused",
     "fusion.768 fusion kOutput 32x256x256x64 32x256x256x64 3x3x64x64"),
    ("%while.4 = (s32[]{:T(128)}, f32[1]{0:T(128)}) while((s32[], f32[1]) "
     "%tuple), condition=%c, body=%b", "while.4 while"),
    ("perfbench.window", "perfbench.window"),
])
def test_operation_names(text, want):
    assert trace.op_name(text) == want


def test_which_operations_hold_a_convolution():
    seg = dict(in_channels=3, num_classes=1, base_features=64, bilinear=True)
    kernels = conv_roofline.kernel_shapes(seg, 256)
    assert (3, 3, 64, 64) in kernels and (3, 3, 512, 1024) in kernels
    assert (1, 1, 1, 64) in kernels and (2, 2, 512, 1024) not in kernels
    tconv = conv_roofline.kernel_shapes(dict(seg, bilinear=False), 256)
    assert (2, 2, 512, 1024) in tconv
    held = lambda name: conv_roofline.holds_conv(name, kernels)  # noqa: E731
    assert held("fusion.768 fusion kOutput 32x256x256x64 3x3x64x64")
    # the kernel's gradient sees the same dimensions in another order
    assert held("multiply_add_fusion.367 fusion kOutput 3x3x128x64 "
                "32x256x256x128")
    assert held("conv3x3_grad_weights.76 custom-call")
    assert held("convolution.5 convolution")
    assert held("convolution_select_fusion fusion kOutput 256x8x33x64")
    # the bilinear interpolation is an output fusion too, but no kernel's
    assert not held("fusion.12 fusion kOutput 32x256x256x64 32x128x256x64")
    assert not held("copy.995 copy") and not held("fusion.787 fusion")
    assert conv_roofline.conv_seconds(trace.reduce(DOC).op_seconds,
                                      kernels) == pytest.approx(41_365e-9)
