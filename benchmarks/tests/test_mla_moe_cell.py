"""The latent-attention, sparse-expert configuration: its count functions
against hand arithmetic (ISSUE 27's table), its file against the catalog
row, and the whole command at a tiny `model` on the CPU in a throw-away
copy (the cell's own traffic shape: closed loop, chunked prompts), with
the control ending `correct: false`."""

import json
import math
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from harness import manifest
from harness.manifest import BENCH, PKG, ROOT

CONFIG = BENCH / "configs" / "joyai-llm-flash-bf16.json"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")

TINY_MODEL = {
    "model_type": "tiny_mla_moe", "hidden_size": 128, "num_hidden_layers": 3,
    "num_attention_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
    "intermediate_size": 256, "moe_intermediate_size": 64,
    "n_routed_experts": 16, "n_shared_experts": 1, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "scoring_func": "sigmoid", "n_group": 1,
    "topk_group": 1, "rope_interleave": True, "vocab_size": 512,
    "max_position_embeddings": 128, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6,
}


@pytest.fixture(scope="module")
def counts():
    cell = manifest.load_cell("joyai-flash-docs-saturated")
    return manifest.load_reference(cell).shapes(cell.model)


def test_counts_are_the_issues_table(counts):
    c = counts
    assert c.attn_params == (2048 * 1536 + 1536 * 32 * 192 + 2048 * 576
                             + 512 * 32 * 256 + 4096 * 2048) == 26_345_472
    assert c.expert_params == 3 * 2048 * 768 == 4_718_592
    assert c.attn_params + c.dense_ffn_params == 70_385_664
    expert_layer = c.attn_params + 257 * c.expert_params + 2048 * 256
    assert expert_layer == 1_239_547_904
    assert c.total_params == 70_385_664 + 4 * expert_layer + 2 * 129280 * 2048
    assert c.total_params == 5_558_108_160  # 11.116e9 bytes in bf16
    assert c.active_layer_params == 70_385_664 + 4 * (
        c.attn_params + 2048 * 256 + 9 * c.expert_params)
    assert c.cache_bytes_per_position == 5 * 1152 and c.vocab == 129280


def test_bytes_follow_the_experts_a_call_reaches(counts):
    c = counts
    # One token reaches 8 experts a layer, a 512-token chunk all but none.
    assert c.experts_hit(1) == pytest.approx(8.0)
    assert c.experts_hit(8) == pytest.approx(256 * (1 - (1 - 8 / 256) ** 8))
    assert 57 < c.experts_hit(8) < 58 and c.experts_hit(512) > 255.99
    assert c.routed_bytes(512) == pytest.approx(4 * 256 * 9_437_184, rel=1e-6)
    unrouted = 2 * (70_385_664 + 4 * (26_345_472 + 2048 * 256 + 4_718_592))
    assert c.unrouted_layer_bytes == unrouted
    flops, nbytes = c.prefill_chunk(512, 512)
    assert nbytes == pytest.approx(
        unrouted + c.routed_bytes(512) + 5 * 1152 * 1024 + 2 * 2048 * 512)
    keys = 512 * 512 + 512 * 513 / 2
    assert flops == pytest.approx(
        2 * c.active_layer_params * 512 + 5 * 2 * 32 * (192 + 128) * keys)
    # The 512-token chunk is bound by the stream: ~10 GB at 819 GB/s.
    assert 12.0e-3 < nbytes / 819e9 < 13.0e-3 and flops / 197e12 < nbytes / 819e9
    flops, nbytes = c.decode_step(8, 8 * 1500)
    head = 2 * 129280 * 2048
    assert nbytes == pytest.approx(
        unrouted + head + c.routed_bytes(8) + 5 * 1152 * (8 * 1500 + 8) + 2 * 2048 * 8)
    assert flops == pytest.approx(
        2 * (c.active_layer_params + 129280 * 2048) * 8
        + 5 * 2 * 32 * 320 * 8 * 1500)
    assert c.prompt_flops(1024) > 1024 * c.token_flops(0) - 1024 * 2 * 129280 * 2048


def test_the_file_is_the_catalog_row_but_for_the_two_cuts():
    body = json.loads(CONFIG.read_text())
    model = body["model"]
    assert body["reduced"] == ["num_hidden_layers", "max_position_embeddings"]
    assert body["published"] == {"num_hidden_layers": 40,
                                 "max_position_embeddings": 131072}
    assert (model["num_hidden_layers"], model["max_position_embeddings"]) == (5, 2048)
    # The published keys stand at the file's top level too, unchanged.
    assert all(body[k] == v for k, v in model.items())
    assert body["serving"]["tpu"]["prefillChunk"] == 512
    assert body["serving"]["tpu"]["quantize"] == "none"
    if not CATALOG.is_file():
        pytest.skip("no catalog beside the model-configs guide here")
    row = next(json.loads(l) for l in CATALOG.read_text().splitlines()
               if json.loads(l)["name"] == "JoyAI-LLM-Flash")
    assert body["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if model.get(k, "absent") != v}
    assert differ == set(body["reduced"])
    assert {k: row["config"][k] for k in differ} == body["published"]


def test_the_trimmed_mean_tells_many_small_gaps_from_a_few_wide_ones():
    """What `mean_logit_gap` is in this reference: a few tokens thrown far
    (a flipped top-k choice) read 0, every fourth token moved a little (a
    model a precision below) reads what those displacements sum to."""
    import numpy as np

    from references import mla_moe_decoder as ref

    assert ref.TRIM == 0.15 and list(ref.CONTROLS.values()) == [127, 7]
    few_wide = np.array([0.0] * 88 + [0.6] * 12)
    many_small = np.array([0.0] * 70 + [0.1] * 30)
    a, b = ref.readings(few_wide), ref.readings(many_small)
    assert a["mean_logit_gap"] == 0.0 and a["max_logit_gap"] == 0.6
    assert a["all_mean_logit_gap"] == pytest.approx(0.072)
    assert b["mean_logit_gap"] == pytest.approx(15 * 0.1 / 85)
    assert b["all_mean_logit_gap"] == pytest.approx(0.03)
    assert b["all_mean_logit_gap"] < a["all_mean_logit_gap"]  # the plain mean says the reverse
    # The limit of the cell lies between the two kinds.
    limit = manifest.load_cell("joyai-flash-docs-saturated").load["limits"]["mean_logit_gap"]
    assert a["mean_logit_gap"] < limit < b["mean_logit_gap"]


def test_the_manifest_holds_every_rule_but_the_accepted_tests_own_width_regex(monkeypatch):
    """`test_manifest.py::test_manifest_meets_the_contract` fails since this
    configuration came: its width regex has `hidden`, so it reads
    `num_hidden_layers` (a depth, the contract's own example of a reduced
    key) as a width.  Only a `benchmark` PR may edit that file (ROADMAP
    R-B11: `hidden` -> `hidden_size`).  Until then this runs the same
    test with that one word repaired, so every other rule stays checked
    for every cell."""
    import re
    import types

    import test_manifest

    def search(pattern, key):
        assert "(hidden|" in pattern  # the one place that test searches
        return re.search(pattern.replace("(hidden|", "(hidden_size|"), key)

    with pytest.raises(AssertionError):
        test_manifest.test_manifest_meets_the_contract()
    monkeypatch.setattr(test_manifest, "re", types.SimpleNamespace(
        search=search, split=re.split, compile=re.compile))
    test_manifest.test_manifest_meets_the_contract()
    assert search(r"(hidden|intermediate|_dim$|_rank$|head_dim)", "hidden_size")


def make_tree(dst: Path) -> Path:
    """A throw-away copy with the configuration at a tiny `model` under a
    docs-shaped mix (closed loop, prompts of several chunks), joined to
    every metric the real cell reports."""
    shutil.copytree(BENCH, dst / "benchmarks",
                    ignore=shutil.ignore_patterns(".cache", ".work", "__pycache__",
                                                  ".pytest_cache", ".export"))
    for name in (PKG, "tpumlops"):
        os.symlink(ROOT / name, dst / name)
    b = dst / "benchmarks"
    # At this toy's size rounding to int8 flips next to nothing (the int8
    # model agrees with the float32 one at 93-96 % of the positions, at the
    # published widths at ~71 %: PERF.md 6), so the copy puts the int4
    # control first.  What is rehearsed is the way from `--control 1` to
    # `correct: false`; that the int8 control ends there at the published
    # widths is the chip's reading (the cell's `notes`).
    ref = b / "references" / "mla_moe_decoder.py"
    first = 'CONTROLS = {"control": 127, "control_int4": 7}'
    assert ref.read_text().count(first) == 1
    ref.write_text(ref.read_text().replace(
        first, 'CONTROLS = {"control": 7, "control_int8": 127}'))
    real = json.loads(CONFIG.read_text())
    tpu = dict(real["serving"]["tpu"], maxSlots=4, maxBatchSize=4, prefillChunk=16,
               observability={"traceRing": 64})
    (b / "configs" / "tiny.json").write_text(json.dumps({
        "source": "benchmarks/tests: a toy for the CPU walk, never a cell",
        "model": TINY_MODEL, "reduced": [], "assumed": [],
        "reference": real["reference"],
        "serving": {"model_name": "tiny", "topology": "v5e-1", "tpu": tpu},
        "trace_programs": real["trace_programs"]}))
    (b / "traffic" / "tinymix.json").write_text(json.dumps({
        "loop": "closed", "order": "seeded", "warm_s": 1,
        "prompt_tokens": {"dist": "uniform", "min": 20, "max": 72},
        "answer_tokens": {"dist": "uniform", "min": 8, "max": 16},
        "draw_seed": 7, "check_sample": 8}))
    # Between what this toy reads served in bf16 (widest 0.000-0.085 over
    # seven runs on the CPU, trimmed mean 0) and its int4 control (widest
    # 0.141-0.209; its trimmed mean 0.0003-0.012 decides nothing here).
    (b / "cells" / "tiny-cell.json").write_text(json.dumps({
        "clients": 6, "limits": {"max_logit_gap": 0.12, "mean_logit_gap": 0.005}}))
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny", "source": "none", "file":
                         "benchmarks/configs/tiny.json", "reduced": [], "why": "test"})
    m["workloads"].append({"name": "tiny-cell", "config": "tiny", "traffic":
                           "tinymix", "chips": 1, "why": "test"})
    for e in m["end_to_end"] + m["per_layer"]:
        if "joyai-flash-docs-saturated" in (e.get("workloads") or []):
            e["workloads"].append("tiny-cell")
    (dst / "BENCHMARK.json").write_text(json.dumps(m))
    return dst


def run(tree, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "tiny-cell",
         "--seconds", "4", "--rehearse-cpu", *extra],
        cwd=tree, env=env, capture_output=True, text=True, timeout=900)
    return p.returncode, p.stdout.strip().splitlines(), p.stderr


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return make_tree(tmp_path_factory.mktemp("mla_moe"))


def test_the_cell_runs_traced_at_tiny_size(tree):
    rc, out, err = run(tree, "--seed", str(2**31 + 27), "--trace", "1")
    assert rc == 0, err[-3000:]
    res = json.loads(out[-1])
    assert res["correct"] is True and res["failed"] == 0, out[-1]
    assert res["attempted"] > 20
    m = res["metrics"]
    # Chunks of 16 tokens x top-4 over 16 experts: ~4 a hit expert (short
    # last chunks read lower); a step's 4 rows x top-4 hit ~11 of 16.
    assert 2.0 < m["moe_tokens_per_expert.prefill"]["value"] <= 4.6
    assert 1.0 <= m["moe_tokens_per_expert.decode"]["value"] < 2.0
    for name in ("prefill_tick_ms", "decode_tick_ms.saturated",
                 "loop_period_ms.saturated", "prefill_tokens_per_s"):
        assert m[name]["value"] > 0
    # No device in the trace: shares of a roofline are left out, never 0.
    assert not any("roofline" in k or "mfu" in k for k in m)
    assert res["checks"]["compiles_in_window"]["value"] == 0


def test_the_control_in_the_programs_place_is_not_correct(tree):
    rc, out, err = run(tree, "--seed", "6", "--trace", "0", "--control", "1")
    assert rc == 0, err[-3000:]
    res = json.loads(out[-1])
    assert res["correct"] is False, out[-1]
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    c = res["checks"]
    line = next(l for l in out if l.startswith("reference over"))
    ref = json.loads(line.split(": ", 1)[1])
    # The control's readings stand where the program's would, and one of
    # the two is past its limit; the program's own lie inside both.
    assert ref["control_levels"] == 7 and ref["control_int8_levels"] == 127
    for name in ("max_logit_gap", "mean_logit_gap"):
        assert c[name]["value"] == ref["control_" + name]
        assert ref[name] <= c[name]["limit"]
    assert any(c[n]["value"] > c[n]["limit"] for n in ("max_logit_gap", "mean_logit_gap"))
    # The mean is trimmed: the widest TRIM of the gaps are left out of it.
    assert ref["control_mean_logit_gap"] < ref["control_all_mean_logit_gap"]


def test_a_program_without_the_flavor_fails_before_the_artifact(tree, tmp_path):
    """What the parent commit does on this cell: the writer asks the
    program's registry for the flavor first, so the run ends in seconds
    with a non-zero exit and no result line."""
    (tmp_path / "sitecustomize.py").write_text(
        "import sys\n"
        "if '--make-artifact' in sys.argv:\n"
        "    from tpumlops.models import registry\n"
        "    registry._BUILDERS.pop('mla-moe-generate', None)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(tmp_path))
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "tiny-cell",
         "--seconds", "4", "--rehearse-cpu", "--seed", "1", "--trace", "0"],
        cwd=tree, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not any(l.startswith("{") for l in p.stdout.splitlines())
    assert "mla-moe-generate" in p.stderr
