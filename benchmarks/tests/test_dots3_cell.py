"""The indexed / sliding / expert-share configuration
(`dots3-note-prev-bf16`): its count functions against hand arithmetic
(ISSUE 33's table), its file against the catalog row, its two readers
against a scrape, and the whole command at a tiny `model` on the CPU in a
throw-away copy (the cell's own traffic shape: closed loop, prompts of
several chunks, several times the kept keys and the window), with the
control ending `correct: false` and a program from before layer kinds
failing before the artifact."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from harness import manifest, prom
from harness.manifest import BENCH, PKG, ROOT

CELL = "dots3-note-docs-long-saturated"
CONFIG = BENCH / "configs" / "dots3-note-prev-bf16.json"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
F, S = "full_attention", "sliding_attention"

# Every mechanism bites: prompts of 20-72 against 16 kept keys and a
# window of 9 (a ring of 16 rows), 16 routed experts of which 4 are held.
TINY_MODEL = {
    "model_type": "tiny_dots3_note", "hidden_size": 128, "num_hidden_layers": 5,
    "layer_types": [F, F, S, S, S, F, S], "num_attention_heads": 4,
    "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 32,
    "qk_rope_head_dim": 16, "v_head_dim": 32, "rope_theta": 10000.0,
    "sliding_window_size": 9, "swa_num_attention_heads": 2, "swa_q_lora_rank": 48,
    "swa_kv_lora_rank": 64, "swa_qk_nope_head_dim": 48, "swa_qk_rope_head_dim": 16,
    "swa_v_head_dim": 32, "swa_rope_theta": 500.0,
    "index_n_heads": 4, "index_head_dim": 32, "index_topk": 16,
    "attention_gate_type": "headwise", "swa_attention_gate_type": "headwise",
    "apply_mla_qkv_lora_rescale": True,
    "intermediate_size": 256, "moe_intermediate_size": 64,
    "n_routed_experts": 4, "router_experts": 16, "local_expert_start": 4,
    "n_shared_experts": 1, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "routed_scaling_factor": 1.0,
    "norm_topk_prob": True, "scoring_func": "sigmoid", "vocab_size": 512,
    "max_position_embeddings": 128, "rms_norm_eps": 1e-5,
}


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


@pytest.fixture(scope="module")
def counts(cell):
    return manifest.load_reference(cell).shapes(cell.model)


def test_counts_are_the_issues_table(counts):
    c = counts
    full = (5120 * 1024 + 1024 * 128 * 192 + 5120 * 576 + 512 * 128 * 256
            + 16384 * 5120 + 5120 * 128 + 1024 * 64 * 128 + 5120 * 128 + 5120 * 64)
    swa = (5120 * 1024 + 1024 * 64 * 256 + 5120 * 1088 + 1024 * 64 * 320
           + 8192 * 5120 + 5120 * 64)
    assert c.attn_params("full") == full == 144_048_128  # 0.288e9 bytes
    assert c.attn_params("swa") == swa == 90_832_896  # 0.182e9
    assert c.dense_ffn_params == 3 * 5120 * 13824 == 212_336_640  # 0.425e9
    assert c.expert_params == 3 * 5120 * 1536 == 23_592_960
    expert_layer = 33 * c.expert_params + 5120 * 256
    assert expert_layer == 779_878_400  # 1.560e9
    assert 2 * c.head_params == 2 * 19008 * 5120 == 194_641_920  # 0.389e9
    assert c.total_params == (2 * full + 3 * swa + c.dense_ffn_params
                              + 4 * expert_layer + 2 * c.head_params)
    # ISSUE 33: "8.18e9 bytes = 47.6 % of 16 GiB" (the table's rounded rows
    # sum to 8.176e9).
    assert 2 * c.total_params == 8_174_174_208
    assert round(100 * 2 * c.total_params / 2**34, 1) == 47.6
    # A position: two full layers of latent 512 + RoPE key 64 + index key 128.
    assert c.cache_bytes_per_position == 2 * (64 + 512 + 128) * 2 == 2816
    assert c.ring_row_bytes == (1024 + 64) * 2 == 2176 and c.vocab == 19008
    # Of a token's 8 choices among 256, one is held here on average.
    assert c.chosen_here == 1.0
    assert c.active_layer_params == (2 * full + 3 * swa + c.dense_ffn_params + 4 * (
        5120 * 256 + 2 * c.expert_params))


def test_counts_reckon_the_least_work(counts):
    c = counts
    # Held experts a layer reads: 32 (1 - (1 - 1/32)^n).
    assert c.experts_hit(1) == pytest.approx(1.0)
    assert c.experts_hit(8) == pytest.approx(32 * (1 - (31 / 32) ** 8))
    assert c.experts_hit(512) > 31.99
    assert c.routed_bytes(512) == pytest.approx(4 * 32 * 2 * 23_592_960, rel=1e-6)
    # sum of min(t + 1, cap) over positions.
    assert c._capped(0, 4096, 2048) == 2048 * 2049 / 2 + 2048 * 2048
    assert c._capped(6144, 512, 2048) == 512 * 2048
    assert c._capped(100, 50, float("inf")) == sum(range(101, 151))
    # A decode step at context 6144: every row scores 6145 index keys, keeps
    # 2048 latent rows and reads a window of 513 ring rows, a layer.
    flops, read = c.attention(6144, 1)
    assert read == 2 * (256 * 6145 + 1152 * 2048) + 3 * 2176 * 513
    index = 2 * 64 * 129 * 6145
    absorbed_full = 2 * 128 * (2 * 512 + 64) * 2048 + 2 * 512 * 128 * 256
    absorbed_swa = 2 * 64 * (2 * 1024 + 64) * 513 + 2 * 1024 * 64 * 320
    assert flops == pytest.approx(2 * (absorbed_full + index) + 3 * absorbed_swa)
    # A chunk of 512 at offset 6144: expanded is the cheaper core (keys and
    # values of at most 2048 distinct positions made once for 512 queries).
    flops, read = c.attention(6144, 512)
    pairs = 512 * 2048
    expanded = 2 * 128 * 320 * pairs + 2 * 512 * 128 * 256 * 2048
    assert expanded < 2 * 128 * 1088 * pairs
    pairs_win = 512 * 513
    expanded_swa = 2 * 64 * 384 * pairs_win + 2 * 1024 * 64 * 320 * 1024
    scored = sum(range(6145, 6657))
    assert flops == pytest.approx(
        2 * (expanded + 2 * 64 * 129 * scored) + 3 * expanded_swa)
    # The chunk is bound by its stream: 6.0e9 bytes of held experts.
    f, b = c.prefill_chunk(512, 6144)
    assert 6.0e9 < c.routed_bytes(512) < 6.1e9 and b / 819e9 > f / 197e12
    assert 9.3e-3 < b / 819e9 < 9.8e-3  # 7.81e9 bytes: 9.54 ms at 819 GB/s


def test_the_file_is_the_catalog_row_but_for_the_four_cuts():
    body = json.loads(CONFIG.read_text())
    model = body["model"]
    assert body["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size", "max_position_embeddings"]
    assert body["published"] == {
        "num_hidden_layers": 46, "n_routed_experts": 256, "vocab_size": 152064,
        "max_position_embeddings": 524288}
    assert [model[k] for k in body["reduced"]] == [5, 32, 19008, 8704]
    # The router keeps its published width; the deployment is written out.
    assert (model["router_experts"], model["local_expert_start"]) == (256, 0)
    assert body["deployment"].startswith("8 chips share each layer")
    assert all(body[k] == v for k, v in model.items())
    assert model["max_position_embeddings"] % body["serving"]["tpu"]["prefillChunk"] == 0
    assert body["serving"]["tpu"]["prefillChunk"] == 512
    assert body["serving"]["tpu"]["maxSlots"] == 8
    for key in ("assumed", "departures", "precision", "own_keys"):
        assert body[key]
    # Every published width stands as published.
    widths = {"hidden_size": 5120, "num_attention_heads": 128,
              "swa_num_attention_heads": 64, "qk_nope_head_dim": 128,
              "swa_qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 128,
              "q_lora_rank": 1024, "kv_lora_rank": 512, "swa_q_lora_rank": 1024,
              "swa_kv_lora_rank": 1024, "index_n_heads": 64, "index_head_dim": 128,
              "index_topk": 2048, "sliding_window_size": 513,
              "intermediate_size": 13824, "moe_intermediate_size": 1536,
              "num_experts_per_tok": 8, "routed_scaling_factor": 1}
    assert {k: model[k] for k in widths} == widths
    if not CATALOG.is_file():
        pytest.skip("no catalog beside the model-configs guide here")
    row = next(json.loads(l) for l in CATALOG.read_text().splitlines()
               if json.loads(l)["name"] == "dots3-note-prev")
    assert body["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if model.get(k, "absent") != v}
    assert differ == set(body["reduced"])
    assert {k: row["config"][k] for k in differ} == body["published"]


def test_the_mix_and_the_cell_are_what_the_issue_names(cell):
    mix = cell.mix
    assert (mix["loop"], mix["order"], mix["warm_s"]) == ("closed", "seeded", 10)
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 4096, "max": 8192}
    assert mix["answer_tokens"] == {"dist": "uniform", "min": 16, "max": 64}
    assert mix["check_sample"] >= 4 and mix["draw_seed"] == 33004
    assert cell.load["clients"] == 16 and cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {"dsa_selected_share.prefill", "dsa_selected_share.decode",
            "prefill_roofline", "decode_roofline.saturated", "mfu_pct.prefill",
            "moe_tokens_per_expert.prefill"} <= names and len(names) == 15


@pytest.mark.parametrize("program,want", [("prefill", 37.5), ("decode", 25.0)])
def test_the_readers_read_a_scrape_and_nothing_from_a_program_without_the_counters(
        program, want):
    from types import SimpleNamespace

    reader = manifest.load_layer_metric(f"dsa_selected_share.{program}")
    series = ('tpumlops_dsa_keys_{kind}_total{{deployment_name="d",program="{p}"}} {v}\n')
    scrape = lambda scale: prom.parse("".join(
        series.format(kind=kind, p=p, v=scale * v)
        for kind, p, v in (("scored", "prefill", 800), ("selected", "prefill", 300),
                           ("scored", "decode", 400), ("selected", "decode", 100))))
    ctx = SimpleNamespace(before=scrape(1), after=scrape(3))
    assert reader.compute(ctx) == pytest.approx(want)
    # The parent's /metrics: no such family, so the line leaves the metric out.
    other = prom.parse('tpumlops_prefill_tokens_total{deployment_name="d"} 5\n')
    assert reader.compute(SimpleNamespace(before=other, after=other)) is None


def test_the_manifest_holds_every_rule_but_the_accepted_tests_own_width_regex(monkeypatch):
    """As `test_mla_moe_cell.py`'s: `test_manifest.py`'s width regex reads
    the `hidden` in `num_hidden_layers` (a depth) as a width; with that
    one word repaired every rule of it holds for every entry, this
    configuration's four reduced keys, its cell and its two metrics
    included."""
    import re
    import types

    import test_manifest

    def search(pattern, key):
        assert "(hidden|" in pattern
        return re.search(pattern.replace("(hidden|", "(hidden_size|"), key)

    monkeypatch.setattr(test_manifest, "re", types.SimpleNamespace(
        search=search, split=re.split, compile=re.compile))
    test_manifest.test_manifest_meets_the_contract()
    m = manifest.load_manifest()
    assert [c["name"] for c in m["configs"]][-1] == "dots3-note-prev-bf16"
    assert [w["name"] for w in m["workloads"]][-1] == CELL
    assert [p["name"] for p in m["per_layer"]][-2:] == [
        "dsa_selected_share.prefill", "dsa_selected_share.decode"]


def make_tree(dst: Path) -> Path:
    """A throw-away copy with the configuration at a tiny `model` under a
    docs-long-shaped mix, joined to every metric the real cell reports."""
    shutil.copytree(BENCH, dst / "benchmarks",
                    ignore=shutil.ignore_patterns(".cache", ".work", "__pycache__",
                                                  ".pytest_cache", ".export"))
    for name in (PKG, "tpumlops"):
        os.symlink(ROOT / name, dst / name)
    b = dst / "benchmarks"
    # At this toy's size rounding to int8 flips next to nothing, so the
    # copy puts the int4 control first, as `test_mla_moe_cell.py` does:
    # what is rehearsed is the way from `--control 1` to `correct: false`;
    # that the int8 control ends there at the published widths is the
    # chip's reading (the cell's `notes`).
    ref = b / "references" / "dots3_note_decoder.py"
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
    # Between what this toy reads served in bf16 and its int4 control
    # (readings in the test below).
    (b / "cells" / "tiny-cell.json").write_text(json.dumps({
        "clients": 6, "limits": {"max_logit_gap": 0.12, "mean_logit_gap": 0.005}}))
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny", "source": "none", "file":
                         "benchmarks/configs/tiny.json", "reduced": [], "why": "test"})
    m["workloads"].append({"name": "tiny-cell", "config": "tiny", "traffic":
                           "tinymix", "chips": 1, "why": "test"})
    for e in m["end_to_end"] + m["per_layer"]:
        if CELL in (e.get("workloads") or []):
            e["workloads"].append("tiny-cell")
    (dst / "BENCHMARK.json").write_text(json.dumps(m))
    return dst


def run(tree, *extra, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.pop("JAX_COMPILATION_CACHE_DIR", None)
    e.update(env or {})
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "tiny-cell",
         "--seconds", "4", "--rehearse-cpu", *extra],
        cwd=tree, env=e, capture_output=True, text=True, timeout=900)
    return p.returncode, p.stdout.strip().splitlines(), p.stderr


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return make_tree(tmp_path_factory.mktemp("dots3"))


def test_the_cell_runs_traced_at_tiny_size(tree):
    rc, out, err = run(tree, "--seed", str(2**31 + 33), "--trace", "1")
    assert rc == 0, err[-3000:]
    res = json.loads(out[-1])
    assert res["correct"] is True and res["failed"] == 0, out[-1]
    assert res["attempted"] > 20
    m = res["metrics"]
    # Prompts of 20-72 against 16 kept keys: (16 P - 128) / (P^2 / 2) over
    # the draw; a step at context 30-85 keeps 16 of it.
    assert 25.0 < m["dsa_selected_share.prefill"]["value"] < 75.0
    assert 15.0 < m["dsa_selected_share.decode"]["value"] < 60.0
    # 4 of 16 experts held, top-4: a chunk of 16 tokens lands ~16
    # assignments on them, ~4 a hit expert; a step's 4 rows ~1.3.
    assert 2.0 < m["moe_tokens_per_expert.prefill"]["value"] <= 6.0
    assert 1.0 <= m["moe_tokens_per_expert.decode"]["value"] < 2.5
    for name in ("prefill_tick_ms", "decode_tick_ms.saturated",
                 "loop_period_ms.saturated", "prefill_tokens_per_s"):
        assert m[name]["value"] > 0
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
    assert ref["control_levels"] == 7 and ref["control_int8_levels"] == 127
    for name in ("max_logit_gap", "mean_logit_gap"):
        assert c[name]["value"] == ref["control_" + name]
        assert ref[name] <= c[name]["limit"]
    assert any(c[n]["value"] > c[n]["limit"] for n in ("max_logit_gap", "mean_logit_gap"))


def test_a_program_from_before_layer_kinds_fails_before_the_artifact(tree, tmp_path):
    """What the parent commit does on this cell: it knows the flavor, and
    would load this artifact as a plain latent-attention model (its
    loader drops config keys it does not know), so the writer asks the
    program's config class for the keys first and the run ends in seconds
    with a non-zero exit and no result line."""
    (tmp_path / "sitecustomize.py").write_text(
        "import sys\n"
        "if '--make-artifact' in sys.argv:\n"
        "    from tpumlops.models import mla_moe\n"
        "    for key in ('layer_types', 'index_topk', 'n_local_experts'):\n"
        "        mla_moe.MlaMoeConfig.__dataclass_fields__.pop(key)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(tmp_path))
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "tiny-cell",
         "--seconds", "4", "--rehearse-cpu", "--seed", "1", "--trace", "0"],
        cwd=tree, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not any(l.startswith("{") for l in p.stdout.splitlines())
    assert "does not know" in p.stderr and "layer_types" in p.stderr
