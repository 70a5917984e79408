"""The MLP kind's FLOP count and the table of peaks."""

import json
from pathlib import Path

import pytest

from bench import flops, harness

ROOT = Path(__file__).resolve().parents[2]


@pytest.mark.parametrize("name", ["pancreas-mlp", "gemini-mlp"])
def test_mlp_flops_are_six_times_the_weights(name):
    conf = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())
    biases = sum(conf["model"]["sizes"][1:])
    work = harness.load_kind(conf).train_flops_per_example(conf, {})
    assert work == 6 * (conf["params"] - biases)


def test_v5e_peaks_have_a_source():
    peak = flops.peak("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["source"] == "Google Cloud TPU v5e documentation"


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        flops.peak("cpu")
