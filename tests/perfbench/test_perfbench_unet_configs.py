"""What only a U-Net's configuration can be asked: its plain reference
builds the decoder that the configuration states. One case for each
configuration of ``BENCHMARK.json`` whose own file says ``"family":
"unet"``; a configuration of another family brings its own such test."""

from pathlib import Path

import pytest

from perfbench.lib import spec

ROOT = Path(__file__).resolve().parents[2]


def _unets():
    bench = spec.Bench(ROOT)
    return [c["name"] for c in bench.doc["configs"]
            if bench.config(c["name"])["family"] == "unet"]


@pytest.mark.parametrize("name", _unets())
def test_the_reference_builds_the_decoder_the_configuration_states(name):
    bench = spec.Bench(ROOT)
    model = bench.config(name)["model"]
    shapes = bench.reference(name).param_shapes(model)
    assert any("ConvTranspose" in k for k in shapes) != model["bilinear"]
