"""Count functions against the program's own arithmetic, and the peaks."""

import json

import pytest

from harness import counts, weights
from harness.manifest import BENCH


@pytest.mark.parametrize("name", ["mistral-7b-v0.3-int8", "deepseek-llm-7b-int8"])
def test_matmul_params_match_the_program(name):
    from tpumlops.models.llama import LlamaConfig, matmul_param_count

    model = json.loads((BENCH / "configs" / f"{name}.json").read_text())["model"]
    g = weights.geometry(model)
    assert counts.Shapes.of(model).matmul_params == matmul_param_count(LlamaConfig(**g))


def test_published_sizes():
    m = json.loads((BENCH / "configs" / "mistral-7b-v0.3-int8.json").read_text())["model"]
    s = counts.Shapes.of(m)
    assert s.matmul_params == 32 * (2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336) + 4096 * 32768
    assert s.kv_bytes_per_position == 128 * 1024
    d = json.loads((BENCH / "configs" / "deepseek-llm-7b-int8.json").read_text())["model"]
    assert counts.Shapes.of(d).kv_bytes_per_position == 480 * 1024


def test_unknown_device_kind_raises():
    with pytest.raises(counts.UnknownDeviceKind):
        counts.peaks_for("TPU v9 imaginary")
    assert counts.peaks_for("TPU v5 lite").hbm_bytes_per_s == 819e9


def test_roofline_says_which_bound():
    s = counts.Shapes.of(json.loads(
        (BENCH / "configs" / "mistral-7b-v0.3-int8.json").read_text())["model"])
    p = counts.peaks_for("TPU v5 lite")
    ms, bound = counts.roofline_ms(*s.decode_step(8, 8 * 300), p)
    assert bound == "bytes" and 8.0 < ms < 12.0
    ms, bound = counts.roofline_ms(*s.prefill_chunk(512, 512), p)
    assert bound == "flops"
