"""A throw-away copy of the benchmark with one more cell, mix,
configuration and per-layer metric, added as files and manifest entries
only: what a later PR is allowed to do."""

from __future__ import annotations

import json
import os
import shutil
from pathlib import Path

from harness.manifest import BENCH, PKG, ROOT

TINY_CONFIG = {
    "source": "benchmarks/tests: a toy for the CPU walk, never a cell",
    "model": {
        "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 4,
        "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 512,
        "max_position_embeddings": 128, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-5,
    },
    "reduced": [], "assumed": [], "reference": "dense_decoder",
    "serving": {
        "model_name": "tiny", "topology": "v5e-1",
        "tpu": {"meshShape": {"dp": 1, "tp": 1}, "quantize": "int8",
                "maxSlots": 4, "maxBatchSize": 4, "prefillChunk": 16,
                "observability": {"traceRing": 64}},
    },
    "trace_programs": {"decode": ["decode"], "prefill": ["prefill"]},
}
TINY_MIX = {
    "loop": "open", "arrivals": "poisson", "warm_s": 1,
    "prompt_tokens": {"dist": "uniform", "min": 4, "max": 40},
    "answer_tokens": {"dist": "uniform", "min": 8, "max": 16},
    "draw_seed": 7, "check_sample": 8,
}
TINY_METRIC = '''"""Requests of the window, counted by a reader added as a file."""


def compute(ctx):
    return float(len([r for r in ctx.records if 0 <= r.start < ctx.seconds]))
'''


def make_tree(dst: Path, loop: str = "open") -> Path:
    dst = Path(dst)
    shutil.copytree(BENCH, dst / "benchmarks",
                    ignore=shutil.ignore_patterns(".cache", ".work", "__pycache__",
                                                  ".pytest_cache", ".export"))
    for name in (PKG, "tpumlops"):
        os.symlink(ROOT / name, dst / name)
    b = dst / "benchmarks"
    (b / "configs" / "tiny.json").write_text(json.dumps(TINY_CONFIG))
    mix = dict(TINY_MIX, loop=loop)
    (b / "traffic" / "tinymix.json").write_text(json.dumps(mix))
    load = {"rate_rps": 6.0} if loop == "open" else {"clients": 6}
    # Between what this toy reads served (widest 0.002-0.013, mean
    # 0.00002-0.0003 over seven seeds, CPU) and its int4 control (widest
    # 0.17-0.25, mean 0.018-0.044 on six of them).
    load["limits"] = {"max_logit_gap": 0.03, "mean_logit_gap": 0.003}
    (b / "cells" / "tiny-cell.json").write_text(json.dumps(load))
    (b / "layer_metrics" / "window_requests.py").write_text(TINY_METRIC)
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny", "source": "none", "file":
                         "benchmarks/configs/tiny.json", "reduced": [], "why": "test"})
    m["workloads"].append({"name": "tiny-cell", "config": "tiny", "traffic":
                           "tinymix", "chips": 1, "why": "test"})
    e2e = "tpot_p90_ms" if loop == "open" else "tokens_per_s"
    for e in m["end_to_end"] + m["per_layer"]:
        if e["name"] == e2e or e.get("moves") == e2e:
            e["workloads"].append("tiny-cell")
    m["per_layer"].append({"name": "window_requests", "unit": "requests",
                           "better": "higher", "source": "host_clock",
                           "layer": "load generator (benchmark)", "moves": e2e,
                           "workloads": ["tiny-cell"]})
    (dst / "BENCHMARK.json").write_text(json.dumps(m))
    return dst
