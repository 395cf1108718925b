"""The benchmark's operation and byte counts against numbers derived by
hand for both decoders, so that ``conv_roofline`` and ``step_mfu`` cannot
pass 100% because a count was stale or belonged to the other configuration."""

import json
import types
from pathlib import Path

import pytest

from perfbench.layer_metrics import step_mfu
from perfbench.lib import flops, spec, trace

ROOT = Path(__file__).resolve().parents[2]

SEG = dict(in_channels=3, num_classes=1, base_features=64, bilinear=True)
TCONV = dict(SEG, bilinear=False)
# one 3x3 convolution 64->64 at 256x256: 2 * 65536 * 9 * 64 * 64
U = 2 * 65536 * 9 * 64 * 64
FIRST = 2 * 65536 * 9 * 3 * 64          # 3 -> 64 at 256x256
HEAD = 2 * 65536 * 64 * 1               # 1x1, 64 -> 1
T = 2 * 1024 * 1024 * 512               # each 2x2 stride-2 up-convolution


def test_forward_flops_by_hand():
    # bilinear: encoder FIRST + U, three levels of U/2 + U, bottleneck
    # U/4 + U/4 (512 -> 512 at 16x16); decoder three levels of 2U + U/2
    # (concatenated input, halved mid width) and the last 2U + U
    seg = FIRST + U + 3 * (U // 2 + U) + U // 2 + 3 * (2 * U + U // 2) \
        + (2 * U + U) + HEAD
    assert seg == 79_960_211_456
    assert flops.forward_flops(SEG, 256) == seg
    # transposed: bottleneck U/2 + U (512 -> 1024 -> 1024); each decoder
    # level one up-convolution T and 2U + U
    tconv = FIRST + U + 3 * (U // 2 + U) + (U // 2 + U) \
        + 4 * (T + 2 * U + U) + HEAD
    assert tconv == 96_334_774_272
    assert flops.forward_flops(TCONV, 256) == tconv


def test_layer_lists_differ_where_the_decoders_do():
    seg = {ly["name"]: ly for ly in flops.conv_layers(SEG, 256)}
    tconv = {ly["name"]: ly for ly in flops.conv_layers(TCONV, 256)}
    assert len(seg) == 19 and len(tconv) == 23
    assert seg["enc4.conv1"]["cout"] == 512
    assert tconv["enc4.conv1"]["cout"] == 1024
    assert seg["dec0.conv0"] == dict(name="dec0.conv0", hw=32, hw_in=32,
                                     taps=9, cin=1024, cout=512)
    assert tconv["dec0.tconv"] == dict(name="dec0.tconv", hw=32, hw_in=16,
                                       taps=1, cin=1024, cout=512)


def test_train_step_counts_three_passes_but_two_for_the_first():
    for model in (SEG, TCONV):
        fwd = flops.forward_flops(model, 256, 32)
        assert flops.step_flops(model, 256, 32) == 3 * fwd - 32 * FIRST
        assert flops.step_flops(model, 256, 32, train=False) == fwd


def test_bytes_and_floor_by_hand():
    first = flops.conv_layers(SEG, 256)[0]
    # bf16: read 32*256*256*3, write 32*256*256*64, read 9*3*64 weights
    assert flops.pass_bytes(first, 32) == 2 * (32 * 65536 * 67 + 9 * 3 * 64)
    up = flops.conv_layers(TCONV, 256)[10]
    assert up["name"] == "dec0.tconv"
    assert flops.pass_bytes(up, 1) == 2 * (256 * 1024 + 1024 * 512
                                           + 4 * 1024 * 512)
    peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    floor = flops.step_floor_seconds(SEG, 256, 32, peaks)
    compute_only = flops.step_flops(SEG, 256, 32) / 197e12
    # memory-bound first and last layers lift the floor over the FLOP time
    assert compute_only < floor < 1.1 * compute_only
    assert floor == pytest.approx(0.040536, rel=1e-3)


@pytest.mark.parametrize("config,forward", [("seg", 79_960_211_456),
                                            ("unet-tconv", 96_334_774_272)])
def test_model_flops_of_a_window_by_hand(config, forward):
    """The window of the resident traffic at 50 s: 12 epochs of 26 steps and
    of 7 validation batches (205 rows, the tail filled), batch 32."""
    bench = spec.Bench(ROOT)
    retrain = bench.driver("retrain")
    body, traffic = bench.config(config), bench.traffic("retrain-resident")
    want = 312 * 32 * (3 * forward - FIRST) + 84 * 32 * forward
    assert retrain.model_flops(body, 32, 312, 84) == pytest.approx(
        want, rel=1e-12)
    # ... and the driver's counters state that window
    job = types.SimpleNamespace(
        cell=types.SimpleNamespace(config=body, traffic=traffic),
        base_cfg=types.SimpleNamespace(batch_size=32, validation_split=0.2,
                                       seed=7),
        window_epochs=retrain.window_epochs(traffic, 50))
    got = retrain.counters(job, {"optimizer_steps": 312,
                                 "train_phase_s": 40.0}, 48.0)
    assert got["eval_batches"] == 84 and got["attempted"] == 312
    assert got["model_flops"] == retrain.model_flops(body, 32, 312, 84)


def test_step_mfu_divides_the_drivers_operations_by_busy_time_and_peak():
    doc = json.loads((Path(__file__).parent / "data"
                      / "recorded_trace.json").read_text())
    summary = trace.reduce(doc)         # one device plane, 160,022 ns busy
    peaks = spec.Bench(ROOT).peaks("TPU v5 lite")

    def read(**kw):
        ctx = dict(trace=summary, peaks=peaks,
                   counters={"model_flops": 1.5e10})
        return step_mfu.read(types.SimpleNamespace(**{**ctx, **kw}))

    assert read() == pytest.approx(100 * 1.5e10 / (160_022e-9 * 197e12))
    assert 0 < read() < 100
    # nothing to read: no trace, no peaks (a CPU run), a driver that states
    # no operations, a trace in which the device never ran
    assert read(trace=None) is None and read(peaks=None) is None
    assert read(counters={}) is None
    idle = types.SimpleNamespace(busy_s=0.0, devices=0)
    assert read(trace=idle) is None
    # four chips: the window's operations over four chips' busy seconds
    four = types.SimpleNamespace(busy_s=summary.busy_s, devices=4)
    assert read(trace=four) == pytest.approx(read() / 4)
