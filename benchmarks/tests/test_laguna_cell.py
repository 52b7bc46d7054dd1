"""The window configuration (`laguna-s-2.1-bf16`): its count functions
against hand arithmetic (to the parameter), its file
against the catalog row, its two readers against recorded scrapes, its
manifest entries against the rules, and the whole command at a tiny
`model` on the CPU in a throw-away copy (the cell's own traffic shape:
closed loop, prompts of several chunks and several windows), with both
controls (int8's stand-in and the dropped window) ending `correct: false`
and a program from before the window keys failing before the artifact."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from harness import manifest, prom
from harness.context import Context
from harness.manifest import BENCH, PKG, ROOT

CELL = "laguna-s-docs-long-saturated"
CONFIG = BENCH / "configs" / "laguna-s-2.1-bf16.json"
# The published-config catalog (JSON lines of name, source_url, config).
CATALOG = Path(os.environ.get("MODEL_CATALOG", "architectures.jsonl"))
DATA = Path(__file__).resolve().parent / "data"
READERS = ("attn_pairs_used_pct.prefill", "attn_pairs_used_pct.decode.saturated")
F, S = "full_attention", "sliding_attention"

# Every mechanism bites: prompts of 20-72 in chunks of 16 against a window
# of 16 (a ring of 16 rows: prompts of up to four rings), two periods of a
# full layer and three sliding ones with 4 and 6 query heads over 2 KV
# heads, YaRN's correction range inside the full layers' 8 rotary pairs,
# a dense layer 0, 16 routed experts of which 4 are held.
TINY_MODEL = {
    "model_type": "tiny_laguna", "vocab_size": 512, "hidden_size": 128,
    "intermediate_size": 192, "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "max_position_embeddings": 128,
    "attention_bias": False, "rms_norm_eps": 1e-6, "num_experts": 4,
    "router_experts": 16, "local_expert_start": 4, "num_experts_per_tok": 4,
    "moe_intermediate_size": 64, "shared_expert_intermediate_size": 64,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head", "sliding_window": 16,
    "rope_parameters": {
        F: {"rope_theta": 10000, "rope_type": "yarn", "factor": 4,
            "original_max_position_embeddings": 2048, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.1386294361119891,
            "partial_rotary_factor": 0.5},
        S: {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}},
    "layer_types": [F, S, S, S] * 2, "mlp_layer_types": ["dense"] + ["sparse"] * 7,
    "gating_types": ["per_head"] * 8, "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [4, 6, 6, 6] * 2,
    "moe_router_logit_softcapping": 0, "moe_apply_router_weight_on_input": False,
}


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


@pytest.fixture(scope="module")
def counts(cell):
    return manifest.load_reference(cell).shapes(cell.model)


def test_counts_are_the_arithmetic_to_the_parameter(counts):
    c = counts
    assert c.layers == {"full": 2, "sliding": 6, "dense": 1, "moe": 7}
    q48, q72 = 3072 * 48 * 128, 3072 * 72 * 128
    assert q48 == 18_874_368 and 3072 * 1024 == 3_145_728
    full = q48 + 2 * 3_145_728 + q48 + 3072 * 48
    assert c.attn_params(F) == full == 44_187_648
    sliding = q72 + 2 * 3_145_728 + q72 + 3072 * 72
    assert c.attn_params(S) == sliding == 63_135_744
    assert c.dense_params == 3 * 3072 * 12288 == 113_246_208
    assert c.router_params == 786_432 and c.shared_params == c.expert_params == 9_437_184
    assert c.layer_params(0) == full + 113_246_208 + 6144 == 157_440_000
    assert c.layer_params(1) - 32 * c.expert_params == sliding + 786_432 + 9_437_184 + 6144 \
        == 73_365_504
    assert c.layer_params(4) - 32 * c.expert_params == 54_417_408
    assert 7 * 32 * c.expert_params == 2_113_929_216
    assert 2 * c.head_params + 3072 == 2 * 12544 * 3072 + 3072 == 77_073_408
    assert c.total_params == (157_440_000 + 6 * 73_365_504 + 54_417_408
                              + 2_113_929_216 + 77_073_408) == 2_843_053_056
    # "5,686,106,112 B = 33.1 % of 16 GiB"
    assert 2 * c.total_params == 5_686_106_112
    assert round(100 * 2 * c.total_params / 2**34, 1) == 33.1
    # The cache at 8 slots: the full layers' rows, and the rings (the
    # window's 512 rows; without them six layers of rows a position).
    assert 8 * 8704 * c.cache_bytes_per_position == 570_425_344
    assert c.ring_bytes == 6 * 4096 * 512  # K and V, 8 KV heads x 128, bf16
    assert 8 * 6 * 2 * 8704 * 1024 * 2 == 1_711_276_032  # "1.71e9"
    # Of a token's 10 choices among 256, 1.25 are held here on average.
    assert c.chosen_here == 1.25 and c.vocab == 12544


def test_counts_reckon_the_least_work(counts):
    c = counts
    assert c.experts_hit(1) == pytest.approx(1.25)
    assert c.experts_hit(512) > 31.99
    # A chunk at the mean attended length: the projections' 477 GFLOP, the
    # dense layer's 116, the shared experts' 68, the routed 85.
    per = lambda n: 2 * 512 * n / 1e9
    assert round(per(2 * (44_187_648 - 3072 * 48) + 6 * (63_135_744 - 3072 * 72))) == 477
    assert round(per(2 * 44_187_648 + 6 * 63_135_744)) == 478  # the gates' 1 GFLOP in
    assert round(per(113_246_208)) == 116 and round(per(7 * 9_437_184)) == 68
    assert round(per(7 * 1.25 * 9_437_184)) == 85
    # Attention: the full layers every earlier position (4 D a query head
    # a pair), the sliding layers at most 512 of them.
    flops, read = c.attention(3400, 512)
    full = 4 * 128 * 48 * 2 * (512 * 3400 + 512 * 513 / 2)
    slide = 4 * 128 * 72 * 6 * 512 * 512
    assert flops == pytest.approx(full + slide)
    assert round(full / 1e9) == 92 and round(slide / 1e9) == 58
    assert read == 2 * 4096 * 3912 + 6 * 4096 * (511 + 512)
    f, b = c.prefill_chunk(512, 3400)
    assert b == pytest.approx(c.unrouted_layer_bytes + c.routed_bytes(512) + read
                              + 2 * 3072 * 512)
    assert 4.2e9 < c.routed_bytes(512) < 4.24e9  # "4.23e9 of held experts"
    # "The least time is ~7 ms, set by bytes."
    assert b / 819e9 > f / 197e12 and 6.5e-3 < b / 819e9 < 7.2e-3
    # A step of 8 rows: the window caps the sliding layers' keys.
    f, b = c.decode_step(8, 8 * 6144)
    assert f == pytest.approx(8 * (2 * (c.active_layer_params + c.head_params)
                                   + 4 * 128 * (48 * 2 * 6145 + 72 * 6 * 512)))
    assert c.token_flops(6144) == pytest.approx(f / 8)
    assert c.prompt_flops(4096) == pytest.approx(
        2 * c.active_layer_params * 4096 + 2 * c.head_params + c.attention(0, 4096)[0])


def test_the_file_is_the_catalog_row_but_for_the_four_cuts():
    body = json.loads(CONFIG.read_text())
    model = body["model"]
    assert body["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size", "max_position_embeddings"]
    assert body["published"] == {
        "num_hidden_layers": 48, "num_experts": 256, "vocab_size": 100352,
        "max_position_embeddings": 1048576}
    assert [model[k] for k in body["reduced"]] == [8, 32, 12544, 8704]
    assert (model["router_experts"], model["local_expert_start"]) == (256, 0)
    assert body["deployment"].startswith("8 chips share each layer")
    assert all(body[k] == v for k, v in model.items())
    tpu = body["serving"]["tpu"]
    assert model["max_position_embeddings"] % tpu["prefillChunk"] == 0
    assert (tpu["prefillChunk"], tpu["maxSlots"], tpu["maxBatchSize"],
            tpu["quantize"]) == (512, 8, 8, "none")
    for key in ("assumed", "departures", "precision", "own_keys"):
        assert body[key]
    # The first eight entries of the tables are two whole periods.
    assert model["layer_types"][:8] == [F, S, S, S] * 2
    assert model["num_attention_heads_per_layer"][:8] == [48, 72, 72, 72] * 2
    widths = {"hidden_size": 3072, "head_dim": 128, "num_attention_heads": 48,
              "num_key_value_heads": 8, "sliding_window": 512,
              "intermediate_size": 12288, "moe_intermediate_size": 1024,
              "shared_expert_intermediate_size": 1024, "num_experts_per_tok": 10}
    assert {k: model[k] for k in widths} == widths
    if not CATALOG.is_file():
        pytest.skip("MODEL_CATALOG names no catalog file")
    row = next(json.loads(l) for l in CATALOG.read_text().splitlines()
               if json.loads(l)["name"] == "Laguna-S-2.1")
    assert body["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if model.get(k, "absent") != v}
    assert differ == set(body["reduced"])
    assert {k: row["config"][k] for k in differ} == body["published"]


def test_the_mix_and_the_cell_are_as_declared(cell):
    mix = cell.mix
    assert cell.mix_name == "docs-long" and cell.chips == 1
    assert (mix["loop"], mix["order"], mix["warm_s"]) == ("closed", "seeded", 10)
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 4096, "max": 8192}
    assert mix["answer_tokens"] == {"dist": "uniform", "min": 16, "max": 64}
    assert cell.load["clients"] == 16
    assert set(cell.load["limits"]) == {"max_logit_gap", "mean_logit_gap"}
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    # What the Qwen3-Next cell reports, less its state metric, and two more.
    theirs = {m["name"] for m in manifest.load_cell(
        "qwen3-next-docs-long-saturated").per_layer}
    assert names == (theirs - {"gdn_tokens_per_state_pass.prefill"}) | set(READERS)
    assert len(names) == 18


def _ctx(before, after):
    return Context(None, 42.0, [], before, after, None, None, None)


def test_the_readers_read_recorded_scrapes():
    """Over the window the prefill chunks multiplied 8000 full-layer pairs
    and 24000 sliding-layer ones for real queries, of which 7000 and 12000
    lie inside the masks: 19000 / 32000; the steps 4000 and 1500, of
    which 3000 and 1200: 4200 / 5500.  The split by kind is noted."""
    before = prom.parse((DATA / "attn_pairs_before.prom").read_text())
    after = prom.parse((DATA / "attn_pairs_after.prom").read_text())
    for name, value, split in (
        (READERS[0], 100 * 19000 / 32000, "full 87.50 % of 8000, sliding 50.00 % of 2.4e+04"),
        (READERS[1], 100 * 4200 / 5500, "full 75.00 % of 4000, sliding 80.00 % of 1500"),
    ):
        ctx = _ctx(before, after)
        assert manifest.load_layer_metric(name).compute(ctx) == pytest.approx(value)
        assert ctx.notes == [f"attn_pairs_used_pct.{name.split('.', 1)[1]}: {split}"]
        # The parent's /metrics, or a window with no such call: nothing.
        other = {k: v for k, v in after.items() if not k[0].startswith("tpumlops_attn_pairs")}
        assert manifest.load_layer_metric(name).compute(_ctx(other, other)) is None
        assert manifest.load_layer_metric(name).compute(_ctx(after, after)) is None


def test_the_manifest_holds_every_rule_but_the_accepted_tests_own_width_regex(monkeypatch):
    """As `test_qwen3_next_cell.py`'s: `test_manifest.py`'s width regex
    reads the `hidden` in `num_hidden_layers` (a depth) as a width; with
    that one word repaired every rule of it holds for every entry.  The
    new entries stand behind what was there (membership, not the last
    place: later entries append behind them)."""
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
    for key, earlier, mine in (
        ("configs", "qwen3-next-80b-a3b-bf16", "laguna-s-2.1-bf16"),
        ("workloads", "qwen3-next-docs-long-saturated", CELL),
        ("per_layer", "starved_before_chunk_ms.saturated", READERS[0]),
        ("per_layer", READERS[0], READERS[1]),
    ):
        names = [e["name"] for e in m[key]]
        assert names.index(mine) > names.index(earlier)
    by_name = {e["name"]: e for e in m["per_layer"] + m["end_to_end"]}
    for name in READERS:
        e = by_name[name]
        assert (e["unit"], e["better"], e["source"], e["layer"], e["moves"],
                e["workloads"]) == ("%", "higher", "program_counter",
                                    "engine programs", "tokens_per_s", [CELL])
    # Appended to every list the saturated expert cells share, behind them.
    for name in ("tokens_per_s", "prefill_tick_ms", "decode_tick_ms.saturated",
                 "prefill_device_ms", "decode_device_ms.saturated", "prefill_roofline",
                 "decode_roofline.saturated", "mfu_pct.prefill", "device_idle_pct.prefill",
                 "loop_period_ms.saturated", "loop_host_ms.saturated",
                 "prefill_tokens_per_s", "moe_tokens_per_expert.prefill",
                 "moe_tokens_per_expert.decode", "device_starved_pct.saturated",
                 "starved_before_step_ms.saturated", "starved_before_chunk_ms.saturated"):
        cells = by_name[name]["workloads"]
        assert cells.index(CELL) > cells.index("qwen3-next-docs-long-saturated")


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
    # copy puts the int4 control in its place, as `test_qwen3_next_cell.py`
    # does: what is rehearsed is the way from `--control 1` to `correct:
    # false` through BOTH controls; that they end there at the published
    # widths is the chip's reading (the cell's `notes`).
    ref = b / "references" / "laguna_decoder.py"
    text = ref.read_text()
    old = 'CONTROLS = {"control_int8": 127, "control_int4": 7}'
    assert text.count(old) == 1
    ref.write_text(text.replace(old, 'CONTROLS = {"control_int8": 7, "control_int4": 3}'))
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
    # Between what this toy reads served in bf16 and its controls
    # (readings in the tests below).
    (b / "cells" / "tiny-cell.json").write_text(json.dumps({
        "clients": 6, "limits": {"max_logit_gap": LIMITS[0], "mean_logit_gap": LIMITS[1]}}))
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


LIMITS = (0.12, 0.005)


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
    return make_tree(tmp_path_factory.mktemp("laguna"))


def test_the_cell_runs_traced_at_tiny_size(tree):
    rc, out, err = run(tree, "--seed", str(2**31 + 39), "--trace", "1")
    assert rc == 0, err[-3000:]
    res = json.loads(out[-1])
    assert res["correct"] is True and res["failed"] == 0, out[-1]
    assert res["attempted"] > 10
    m = res["metrics"]
    # Prompts of 20-72 in chunks of 16 against a window of 16: a sliding
    # layer's chunk multiplies 15 + 16 keys a query for at most 16 in the
    # mask; a full layer's the whole capacity of 128 (one block at this
    # size) for ~37 on average: ~29 % in all.
    assert 20.0 < m["attn_pairs_used_pct.prefill"]["value"] < 40.0
    assert 0.0 < m["attn_pairs_used_pct.decode.saturated"]["value"] < 100.0
    assert 1.0 <= m["moe_tokens_per_expert.decode"]["value"] < 2.5
    for name in ("prefill_tick_ms", "decode_tick_ms.saturated",
                 "loop_period_ms.saturated", "prefill_tokens_per_s"):
        assert m[name]["value"] > 0
    assert not any("roofline" in k or "mfu" in k or "gdn" in k for k in m)
    assert res["checks"]["compiles_in_window"]["value"] == 0
    notes = [l for l in out if l.strip().startswith("attn_pairs_used_pct.")]
    assert len(notes) == 2 and all("full" in l and "sliding" in l for l in notes)


def test_both_controls_in_the_programs_place_are_not_correct(tree):
    rc, out, err = run(tree, "--seed", "6", "--trace", "0", "--control", "1")
    assert rc == 0, err[-3000:]
    res = json.loads(out[-1])
    assert res["correct"] is False, out[-1]
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    c = res["checks"]
    line = next(l for l in out if l.startswith("reference over"))
    ref = json.loads(line.split(": ", 1)[1])
    assert ref["control_int8_levels"] == 7 and ref["control_int4_levels"] == 3
    limits = {n: c[n]["limit"] for n in ("max_logit_gap", "mean_logit_gap")}
    for name, limit in limits.items():
        # What run.py compares is the smaller of the two controls' readings.
        assert c[name]["value"] == ref["control_" + name] == min(
            ref["control_int8_" + name], ref["control_window_" + name])
        assert ref[name] <= limit
    # Each control alone is not correct by the cell's limits, and the
    # check that fails for both at once is what ends the run; the
    # YaRN-dropped reading is logged beside them.
    for control in ("control_int8_", "control_window_"):
        assert any(ref[control + n] > limits[n] for n in limits), control
    assert any(c[n]["value"] > limits[n] for n in limits)
    assert "control_yarn_mean_logit_gap" in ref
    # The weights alone rounded, logged beside the int8 control (both
    # operands rounded), not compared.
    assert ref["control_int8w_levels"] == 7 and "control_int8w_mean_logit_gap" in ref


def test_a_program_from_before_the_window_keys_fails_before_the_artifact(tree, tmp_path):
    """What the parent commit does on this cell: its config of the flavor
    does not know the window keys, so the artifact's writer ends the run
    in seconds with a non-zero exit and no result line."""
    (tmp_path / "sitecustomize.py").write_text(
        "import sys\n"
        "if '--make-artifact' in sys.argv:\n"
        "    import dataclasses\n"
        "    from tpumlops.models import gdn_moe\n"
        "    from tpumlops.server import loader\n"
        "    keep = [(f.name, f.type, dataclasses.field(default=f.default))\n"
        "            for f in dataclasses.fields(gdn_moe.GdnMoeConfig)\n"
        "            if f.name not in ('layer_types', 'sliding_window')]\n"
        "    Before = dataclasses.make_dataclass('Before', keep)\n"
        "    loader._build_config = lambda flavor, cfg: Before()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(tmp_path))
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "tiny-cell",
         "--seconds", "4", "--rehearse-cpu", "--seed", "1", "--trace", "0"],
        cwd=tree, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not any(l.startswith("{") for l in p.stdout.splitlines())
    assert "cannot run model_type 'laguna'" in p.stderr
