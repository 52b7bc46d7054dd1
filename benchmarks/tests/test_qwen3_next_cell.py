"""The linear-attention configuration (`qwen3-next-80b-a3b-bf16`): its
count functions against hand arithmetic (ISSUE 35's table, to the
parameter), its file against the catalog row, its reader against a
scrape, and the whole command at a tiny `model` on the CPU in a throw-away
copy (the cell's own traffic shape: closed loop, prompts of several
chunks), with both controls (int8's stand-in and the dropped state)
ending `correct: false` and a program from before the family failing
before the artifact."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from harness import manifest, prom
from harness.manifest import BENCH, PKG, ROOT

CELL = "qwen3-next-docs-long-saturated"
CONFIG = BENCH / "configs" / "qwen3-next-80b-a3b-bf16.json"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")

# Every mechanism bites: prompts of 20-72 in chunks of 16 (a state carried
# over 2-5 chunk boundaries, a padded last chunk), one period of three
# linear layers and a full one twice, 16 routed experts of which 4 are held.
TINY_MODEL = {
    "model_type": "tiny_qwen3_next", "hidden_size": 128, "num_hidden_layers": 8,
    "full_attention_interval": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "partial_rotary_factor": 0.5,
    "rope_theta": 10000.0, "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 32, "linear_value_head_dim": 32,
    "linear_conv_kernel_dim": 4, "moe_intermediate_size": 64,
    "shared_expert_intermediate_size": 64, "num_experts": 4, "router_experts": 16,
    "local_expert_start": 4, "num_experts_per_tok": 4, "norm_topk_prob": True,
    "decoder_sparse_step": 1, "mlp_only_layers": [], "hidden_act": "silu",
    "vocab_size": 512, "max_position_embeddings": 128, "rms_norm_eps": 1e-6,
}


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


@pytest.fixture(scope="module")
def counts(cell):
    return manifest.load_reference(cell).shapes(cell.model)


def test_counts_are_the_issues_table_to_the_parameter(counts):
    c = counts
    linear = 2048 * 12288 + 2048 * 64 + 4 * 8192 + 64 + 128 + 4096 * 2048
    assert c.linear_mixer_params == linear == 33_718_464
    full = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 512
    assert c.full_mixer_params == full == 27_263_488
    assert c.router_params == 2048 * 512 == 1_048_576
    assert c.expert_params == 3 * 2048 * 512 == 3_145_728
    block = 1_048_576 + 3_145_728 + 2048 + 4096
    assert c.unrouted_block_params == block == 4_200_448
    assert 128 * c.expert_params == 402_653_184
    assert c.layers == {"full": 2, "linear": 6, "moe": 8}
    assert c.layer_params == 6 * linear + 2 * full + 8 * (block + 402_653_184)
    assert c.layer_params == 3_511_666_816
    assert 2 * c.head_params + 2048 == 2 * 37984 * 2048 + 2048 == 155_584_512
    assert c.total_params == 3_667_251_328
    # "7,334,502,656 bytes = 42.7 % of 16 GiB"
    assert 2 * c.total_params == 7_334_502_656
    assert round(100 * 2 * c.total_params / 2**34, 1) == 42.7
    # A position: two full layers of K and V, 2 KV heads x 256, bf16; a
    # slot's state: six linear layers of S (2 MiB, float32) and 3 x 8192.
    assert c.cache_bytes_per_position == 2 * 2 * 512 * 2 == 4096
    assert c.state_bytes == 6 * (2_097_152 + 49_152) == 12_877_824
    assert 8 * 8704 * c.cache_bytes_per_position == 285_212_672  # ISSUE: 0.285e9
    assert 8 * c.state_bytes == 103_022_592  # ISSUE: 0.103e9
    # Of a token's 10 choices among 512, 2.5 are held here on average.
    assert c.chosen_here == 2.5 and c.vocab == 37984


def test_counts_reckon_the_least_work(counts):
    c = counts
    # Held experts a layer reads: 128 (1 - (1 - 10/512)^n).
    assert c.experts_hit(1) == pytest.approx(2.5)
    assert c.experts_hit(8) == pytest.approx(128 * (1 - (502 / 512) ** 8))
    assert c.experts_hit(512) > 127.99
    # The stream of a chunk: "6.44e9 bytes = 7.9 ms at 819 GB/s".
    assert c.routed_bytes(512) == pytest.approx(8 * 128 * 2 * 3_145_728, rel=1e-4)
    assert 6.44e9 < c.routed_bytes(512) < 6.45e9
    # The rule, a token: the convolution's 2 x 4 x 8192 and three products
    # of 128 x 128 in each of 32 heads, in six layers.
    assert c.rule_flops == 6 * (2 * 4 * 8192 + 6 * 32 * 128 * 128)
    # Attention: every earlier position and its own, 4 x 4096 a pair a layer.
    flops, read = c.attention(6144, 1)
    assert flops == 2 * 4 * 4096 * 6145 and read == 4096 * 6145
    flops, read = c.attention(6144, 512)
    assert flops == 2 * 4 * 4096 * sum(range(6145, 6657))
    assert read == 4096 * 6656
    # A chunk: bound by its stream; the state is read and written once
    # whatever the chunk brings (12.9 MB twice against 6.4 GB).
    f, b = c.prefill_chunk(512, 6144)
    assert b / 819e9 > f / 197e12
    assert b == pytest.approx(
        c.unrouted_layer_bytes + c.routed_bytes(512) + 4096 * 6656
        + 2 * 12_877_824 + 2 * 2048 * 512)
    assert 8.6e-3 < b / 819e9 < 8.7e-3  # 7.08e9 bytes: 8.64 ms at 819 GB/s
    # A step of 8 rows at context 6144: ~19 experts a layer, 25 MB of rows
    # and 26 MB of state a row.
    f, b = c.decode_step(8, 8 * 6144)
    assert b == pytest.approx(
        c.unrouted_layer_bytes + 2 * c.head_params + c.routed_bytes(8)
        + 8 * (4096 * 6145 + 2 * 12_877_824) + 2 * 2048 * 8)
    assert f == pytest.approx(
        8 * (2 * (c.active_layer_params + c.head_params) + c.rule_flops
             + 2 * 4 * 4096 * 6145))
    assert c.token_flops(6144) == pytest.approx(f / 8)
    assert c.prompt_flops(4096) == pytest.approx(
        (2 * c.active_layer_params + c.rule_flops) * 4096 + 2 * c.head_params
        + 2 * 4 * 4096 * 4096 * 4097 / 2)


def test_the_file_is_the_catalog_row_but_for_the_four_cuts():
    body = json.loads(CONFIG.read_text())
    model = body["model"]
    assert body["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size", "max_position_embeddings"]
    assert body["published"] == {
        "num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936,
        "max_position_embeddings": 262144}
    assert [model[k] for k in body["reduced"]] == [8, 128, 37984, 8704]
    # The router keeps its published width; the deployment is written out.
    assert (model["router_experts"], model["local_expert_start"]) == (512, 0)
    assert body["deployment"].startswith("4 chips share each layer")
    assert all(body[k] == v for k, v in model.items())
    tpu = body["serving"]["tpu"]
    assert model["max_position_embeddings"] % tpu["prefillChunk"] == 0
    assert (tpu["prefillChunk"], tpu["maxSlots"], tpu["maxBatchSize"],
            tpu["quantize"]) == (512, 8, 8, "none")
    assert tpu["observability"] == {"traceRing": 256, "deviceTelemetry": True}
    for key in ("assumed", "departures", "precision", "own_keys"):
        assert body[key]
    assert any("A_log" in a and "0.5" in a and "0.999" in a for a in body["assumed"])
    # Every published width stands as published.
    widths = {"hidden_size": 2048, "num_attention_heads": 16,
              "num_key_value_heads": 2, "head_dim": 256,
              "partial_rotary_factor": 0.25, "linear_num_key_heads": 16,
              "linear_num_value_heads": 32, "linear_key_head_dim": 128,
              "linear_value_head_dim": 128, "linear_conv_kernel_dim": 4,
              "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
              "num_experts_per_tok": 10, "full_attention_interval": 4}
    assert {k: model[k] for k in widths} == widths
    if not CATALOG.is_file():
        pytest.skip("no catalog beside the model-configs guide here")
    row = next(json.loads(l) for l in CATALOG.read_text().splitlines()
               if json.loads(l)["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert body["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if model.get(k, "absent") != v}
    assert differ == set(body["reduced"])
    assert {k: row["config"][k] for k in differ} == body["published"]


def test_the_mix_and_the_cell_are_what_the_issue_names(cell):
    mix = cell.mix
    assert cell.mix_name == "docs-long"
    assert (mix["loop"], mix["order"], mix["warm_s"]) == ("closed", "seeded", 10)
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 4096, "max": 8192}
    assert mix["answer_tokens"] == {"dist": "uniform", "min": 16, "max": 64}
    assert mix["check_sample"] == 8
    assert cell.load["clients"] == 16 and cell.chips == 1
    assert set(cell.load["limits"]) == {"max_logit_gap", "mean_logit_gap"}
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    # What the dots3-note cell reports, less its two dsa_* shares, and one more.
    theirs = {m["name"] for m in manifest.load_cell(
        "dots3-note-docs-long-saturated").per_layer}
    assert names == (theirs - {"dsa_selected_share.prefill", "dsa_selected_share.decode"}
                     ) | {"gdn_tokens_per_state_pass.prefill"}
    assert len(names) == 14


def test_the_reader_reads_a_scrape_and_nothing_from_a_program_without_the_counters():
    from types import SimpleNamespace

    reader = manifest.load_layer_metric("gdn_tokens_per_state_pass.prefill")
    series = ('tpumlops_gdn_{kind}_total{{deployment_name="d",program="{p}"}} {v}\n')
    scrape = lambda scale: prom.parse("".join(
        series.format(kind=kind, p=p, v=scale * v)
        for kind, p, v in (("tokens", "prefill", 6 * 4900), ("state_passes", "prefill", 60),
                           ("tokens", "decode", 420), ("state_passes", "decode", 420))))
    ctx = SimpleNamespace(before=scrape(1), after=scrape(3))
    assert reader.compute(ctx) == pytest.approx(490.0)
    # The parent's /metrics: no such family, so the line leaves the metric out.
    other = prom.parse('tpumlops_prefill_tokens_total{deployment_name="d"} 5\n')
    assert reader.compute(SimpleNamespace(before=other, after=other)) is None


def test_the_manifest_holds_every_rule_but_the_accepted_tests_own_width_regex(monkeypatch):
    """As `test_dots3_cell.py`'s: `test_manifest.py`'s width regex reads
    the `hidden` in `num_hidden_layers` (a depth) as a width; with that
    one word repaired every rule of it holds for every entry, this
    configuration's four reduced keys, its cell and its metric
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
    # Appended behind what was there (not "the last entry": the next
    # configuration is appended behind this one, and the accepted
    # `test_dots3_cell.py` pins `[-1]` and fails from this PR on; PERF.md 7).
    m = manifest.load_manifest()
    for key, earlier, mine in (
        ("configs", "dots3-note-prev-bf16", "qwen3-next-80b-a3b-bf16"),
        ("workloads", "dots3-note-docs-long-saturated", CELL),
        ("per_layer", "dsa_selected_share.decode", "gdn_tokens_per_state_pass.prefill"),
    ):
        names = [e["name"] for e in m[key]]
        assert names.index(mine) > names.index(earlier)


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
    # copy puts the int4 control in its place, as `test_dots3_cell.py`
    # does, and the dropped-state control's chunk is the toy's own 16:
    # what is rehearsed is the way from `--control 1` to `correct: false`
    # through BOTH controls; that they end there at the published widths
    # is the chip's reading (the cell's `notes`).
    ref = b / "references" / "qwen3_next_decoder.py"
    text = ref.read_text()
    for old, new in (
        ('CONTROLS = {"control_int8": 127, "control_int4": 7}',
         'CONTROLS = {"control_int8": 7, "control_int4": 3}'),
        ("STATE_CHUNK = 512", "STATE_CHUNK = 16"),
    ):
        assert text.count(old) == 1
        text = text.replace(old, new)
    ref.write_text(text)
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
    return make_tree(tmp_path_factory.mktemp("qwen3next"))


def test_the_cell_runs_traced_at_tiny_size(tree):
    rc, out, err = run(tree, "--seed", str(2**31 + 35), "--trace", "1")
    assert rc == 0, err[-3000:]
    res = json.loads(out[-1])
    assert res["correct"] is True and res["failed"] == 0, out[-1]
    assert res["attempted"] > 10
    m = res["metrics"]
    # Prompts of 20-72 in chunks of 16: 46 tokens over 3.4 passes a prompt.
    assert 10.0 < m["gdn_tokens_per_state_pass.prefill"]["value"] <= 16.0
    # 4 of 16 experts held, top-4: a chunk of 16 tokens lands ~16
    # assignments on them, ~4 a hit expert; a step's 4 rows ~1.3.
    assert 2.0 < m["moe_tokens_per_expert.prefill"]["value"] <= 6.0
    assert 1.0 <= m["moe_tokens_per_expert.decode"]["value"] < 2.5
    for name in ("prefill_tick_ms", "decode_tick_ms.saturated",
                 "loop_period_ms.saturated", "prefill_tokens_per_s"):
        assert m[name]["value"] > 0
    assert not any("roofline" in k or "mfu" in k or "dsa" in k for k in m)
    assert res["checks"]["compiles_in_window"]["value"] == 0


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
            ref["control_int8_" + name], ref["control_state_" + name])
        assert ref[name] <= limit
    # Each control alone is not correct by the cell's limits, and the
    # check that fails for both at once is what ends the run.
    for control in ("control_int8_", "control_state_"):
        assert any(ref[control + n] > limits[n] for n in limits), control
    assert any(c[n]["value"] > limits[n] for n in limits)


def test_a_program_from_before_the_family_fails_before_the_artifact(tree, tmp_path):
    """What the parent commit does on this cell: it does not know the
    flavor, so the artifact's writer ends the run in seconds with a
    non-zero exit and no result line."""
    (tmp_path / "sitecustomize.py").write_text(
        "import sys\n"
        "if '--make-artifact' in sys.argv:\n"
        "    from tpumlops.models import registry\n"
        "    registry._BUILDERS.pop('gdn-moe-generate')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(tmp_path))
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "tiny-cell",
         "--seconds", "4", "--rehearse-cpu", "--seed", "1", "--trace", "0"],
        cwd=tree, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not any(l.startswith("{") for l in p.stdout.splitlines())
    assert "unknown model flavor 'gdn-moe-generate'" in p.stderr
