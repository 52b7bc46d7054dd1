"""Driver-contract tests for bench.py's final stdout line.

Round 3's official record was lost because the final JSON line outgrew
the driver's ~2 KB stdout tail capture (BENCH_r03.json "parsed": null).
These tests pin the contract: ``compact_line`` must keep the headline
(BERT p99 / MFU / vs_baseline) and stay under the byte budget even when
every secondary bench returns its fattest possible payload — ladders,
prose notes, multi-line error strings with ANSI escapes.
"""

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import bench  # noqa: E402


def _fat_full_record() -> dict:
    """A record modeled on the ACTUAL round-3 output that broke parsing:
    full slot ladders, long notes, and a raw compiler error with
    embedded ANSI escape sequences."""
    ansi_error = (
        "JaxRuntimeError: RESOURCE_EXHAUSTED: XLA:TPU compile permanent "
        "error. Ran out of memory in memory space hbm. Used 17.2G of\n"
        "[2m2026-07-31T04:27:22.482386Z[0m [33m W"
        + "x" * 400
    )
    ladder_1p35 = {
        str(s): {
            "tok_per_s": 2240.5 - s,
            "ms_per_step": 14.28,
            "hbm_gb_per_s": 335.3,
            "bw_util": 0.409,
        }
        for s in (8, 16, 32, 64)
    }
    return {
        "metric": "bert_base_b32_s128_p99_batch_latency_per_chip",
        "value": 4.31,
        "unit": "ms",
        "vs_baseline": 104.3,
        "p50_ms": 3.55,
        "numerics": "int8 acts+weights on the MXU s8 path, tanh-GELU "
                    "(the int8 serving default; bf16 erf comparison in "
                    "bf16_p99_ms)",
        "parity_vs_bf16_erf": {"max_abs_logit_diff": 0.031},
        "bf16_p99_ms": 7.31,
        "throughput_seq_per_s": 9014.1,
        "tflops": 41.3,
        "mfu_vs_s8_peak": 0.105,
        "bf16_tflops": 24.4,
        "bf16_mfu": 0.124,
        "baseline_cpu_p99_ms": 449.5,
        "vs_gpu_baseline": {"t4_int8": 2.2, "a100": 0.46},
        "hardware": "TPU v5e (1 chip)",
        "secondary": {
            "time_to_100pct_traffic": {
                "measured_s": 5.43,
                "policy_floor_s": 4.2,
                "operator_overhead_s": 1.23,
                "step_interval_s": 0.5,
                "ref_floor_same_policy_s": 480,
                "traffic_split": "native router (smooth WRR), gate on "
                                 "its live histograms",
                "overhead_breakdown_ms": {
                    "alias_resolve": 101.9, "apply": 55.2, "gate": 40.1,
                    "metrics": 230.8, "status": 60.0,
                    "reconcile_steps_total": 600.1, "other": 112.1,
                },
            },
            "iris_sklearn_linear": {"p50_us": 28.1, "batch": 32},
            "xgboost_forest": {
                "p50_us": 79.0, "trees": 200, "batch": 256,
                "eval_form": "gemm",
            },
            "resnet50": {
                "ladder": {
                    "8": {"p50_ms": 5.0, "img_per_s": 1601.0,
                          "tflops": 6.6, "mfu": 0.033},
                    "32": {"p50_ms": 11.4, "img_per_s": 2801.2,
                           "tflops": 11.5, "mfu": 0.058},
                    "128": {"p50_ms": 38.6, "img_per_s": 3313.7,
                            "tflops": 13.6, "mfu": 0.069},
                },
                "p50_ms": 38.6, "img_per_s": 3313.7, "tflops": 13.6,
                "mfu": 0.069,
                "vs_gpu_baseline": {"t4_int8_mlperf": 0.59,
                                    "a100_int8_mlperf": 0.09},
            },
            "llama_1p35b_decode": {
                "device_tok_per_s": 2240.5,
                "ms_per_step": 14.28,
                "slots": 32,
                "slot_ladder": ladder_1p35,
                "bw_util_at_best": 0.409,
                "params_b": 1.35,
                "numerics": "int8 weights + int8 kv + windowed decode "
                            "(window=512)",
                "int8kv_parity_vs_bf16kv": {
                    "teacher_forced_steps": 26,
                    "max_rel_logit_err": 0.0087,
                    "argmax_agreement": 1.0,
                },
                "note": "engine-loop tok/s is not reported by this "
                        "scenario: the device loop is the chip number, "
                        "the engine loop adds a host read per tick, and "
                        "the gap between the two (once measured at 70.7 "
                        "against 787.6 tok/s) is ROADMAP S2's to size.",
            },
            "serve_path_http": {
                "direct": {"p50_ms": 201.4, "p99_ms": 249.1,
                           "requests": 96},
                "via_router": {"p50_ms": 201.8, "p99_ms": 273.0,
                               "requests": 96},
                "router_overhead_p50_ms": 0.37,
                "server_observed_mean_ms": 208.73,
                "server_queue_mean_ms": 87.28,
                "server_device_run_mean_ms": 109.48,
                "server_overhead_ms": 11.97,
                "clients": 8,
                "batch_per_request": 1,
                "numerics": "int8",
                "note": "absolutes include the host's HTTP and batching "
                        "path, which dominates them at one sequence per "
                        "request; the compute floor is the headline "
                        "per-batch latency. router_overhead is the "
                        "paired, order-independent signal "
                        "here.",
            },
            "llama_7b_decode": {
                "device_tok_per_s": 663.5,
                "ms_per_step": 24.11,
                "slots": 16,
                "slot_ladder": {
                    "8": {"tok_per_s": 488.5, "ms_per_step": 16.4,
                          "hbm_gb_per_s": 488.5, "bw_util": 0.596},
                    "16": {"tok_per_s": 663.5, "ms_per_step": 24.11,
                           "hbm_gb_per_s": 377.0, "bw_util": 0.46},
                    "32": {"error": ansi_error},
                },
                "bw_util_at_best": 0.46,
                "params_b": 6.74,
                "weight_bytes_gib": 6.4,
                "load_s": 545.9,
                "numerics": "int8 weights + int8 kv + windowed decode "
                            "(window=512)",
                "vs_gpu_baseline": {"a100_80g_fp16_vllm": 0.35},
            },
        },
    }


def test_compact_line_fits_driver_tail():
    out = json.dumps(bench.compact_line(_fat_full_record()))
    assert len(out) <= bench.COMPACT_BUDGET_BYTES, len(out)
    parsed = json.loads(out)  # round-trips
    # Driver contract keys survive compaction.
    assert parsed["metric"] == "bert_base_b32_s128_p99_batch_latency_per_chip"
    assert parsed["value"] == 4.31
    assert parsed["unit"] == "ms"
    assert parsed["vs_baseline"] == 104.3
    # The round-3 loss: BERT p99 and MFU must be ON the parsed line.
    assert parsed["mfu_vs_s8_peak"] == 0.105
    assert parsed["p50_ms"] == 3.55


def test_compact_line_keeps_secondary_headlines():
    parsed = bench.compact_line(_fat_full_record())
    sec = parsed["secondary"]
    assert sec["llama_7b_decode"]["device_tok_per_s"] == 663.5
    assert sec["llama_7b_decode"]["load_s"] == 545.9
    assert sec["llama_1p35b_decode"]["device_tok_per_s"] == 2240.5
    assert sec["time_to_100pct_traffic"]["measured_s"] == 5.43
    assert sec["serve_path_http"]["server_queue_mean_ms"] == 87.28
    # Ladders and notes are detail-file material, not headline material.
    assert "slot_ladder" not in sec["llama_7b_decode"]
    assert "note" not in sec["llama_1p35b_decode"]
    assert parsed["detail"] == "BENCH_DETAIL.json"


def test_compact_line_sanitizes_error_entries():
    full = _fat_full_record()
    full["secondary"]["llama_7b_decode"] = {
        "error": "timeout after 900s (compile never returned)\n"
                 "[2mtrace[0m " + "y" * 500,
    }
    full["secondary"]["resnet50"] = {"skipped": "wall budget 2400s spent"}
    parsed = bench.compact_line(full)
    err = parsed["secondary"]["llama_7b_decode"]["error"]
    assert len(err) <= 80
    assert "" not in err and "\n" not in err
    assert parsed["secondary"]["resnet50"]["skipped"].startswith("wall budget")


def test_compact_line_sheds_to_budget_without_losing_contract():
    full = _fat_full_record()
    # Adversarial: a secondary with a huge allowlisted value set.
    full["secondary"]["llama_7b_decode"]["vs_gpu_per_gbps"] = 0.88
    full["notes_blob"] = "z" * 5000  # unknown top-level key, not shed-able
    # Unknown top-level keys ride along unless shedding must remove known
    # optional ones; the contract keys must always survive.
    parsed = bench.compact_line(full)
    for k in ("metric", "value", "unit", "vs_baseline"):
        assert k in parsed


_STUB_MAIN = r'''
import sys, time
sys.path.insert(0, {repo!r})
import bench
# The contract under test is the stdout line, not a measurement: this
# CPU test states the device the stubs pretend to have run on.
bench._require_accelerator = lambda: {{
    "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
bench.bench_bert = lambda: {{
    "int8": {{50: 0.004, 99: 0.0045}}, "bf16": {{50: 0.007, 99: 0.0075}},
    "parity": {{"argmax_agreement": 1.0, "max_logit_delta": 0.03}},
    "tflops_int8": 88.0, "tflops_bf16": 44.0,
    "mfu_int8": 0.22, "mfu_bf16": 0.22,
}}
bench.bench_torch_cpu = lambda iters=3: {{50: 0.4, 99: 0.45}}
def fast():
    return {{"p50_us": 10.0}}
def slow():
    time.sleep(120)
for name in ("bench_time_to_100", "bench_iris"):
    setattr(bench, name, fast)
for name in ("bench_xgboost", "bench_resnet", "bench_prefix_cache",
             "bench_speculative", "bench_multistep",
             "bench_superstep", "bench_tensor_parallel",
             "bench_long_context", "bench_packed_prefill",
             "bench_observability", "bench_device_telemetry",
             "bench_admission_control", "bench_cold_start",
             "bench_disaggregated", "bench_chaos", "bench_multi_model",
             "bench_fleet_trace", "bench_priority_preemption",
             "bench_llama_decode", "bench_serve_path",
             "bench_llama_7b_decode"):
    setattr(bench, name, {tail_fn})
bench.main()
'''


def test_sigterm_mid_bench_still_emits_parseable_record(tmp_path):
    """The round-4 failure mode: an external kill mid-secondaries must
    leave (a) a parseable headline line on stdout and (b) a current
    BENCH_DETAIL.json containing every completed secondary.  SIGTERM is
    what both ``timeout(1)`` and the driver deliver first."""
    import os
    import signal
    import subprocess
    import time as _time

    detail = tmp_path / "detail.json"
    env = dict(os.environ, BENCH_DETAIL_PATH=str(detail))
    code = _STUB_MAIN.format(
        repo=str(Path(__file__).resolve().parent.parent), tail_fn="slow"
    )
    proc = subprocess.Popen(
        [sys.executable, "-c", code], stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, text=True, env=env, cwd=tmp_path,
    )
    try:
        # Wait for the early emission (headline + fast secondaries), then
        # kill while a slow secondary is "running".
        deadline = _time.monotonic() + 30
        while _time.monotonic() < deadline and not detail.exists():
            _time.sleep(0.1)
        _time.sleep(1.0)
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
    parsed = None
    for line in reversed([l for l in out.splitlines() if l.strip()]):
        try:
            parsed = json.loads(line)
            break
        except json.JSONDecodeError:
            continue
    assert parsed is not None, out
    assert parsed["metric"] == "bert_base_b32_s128_p99_batch_latency_per_chip"
    assert parsed["value"] == 4.5
    assert parsed["mfu_vs_s8_peak"] == 0.22
    full = json.loads(detail.read_text())
    # Completed secondaries survive; the in-flight one reads skipped/None.
    assert full["secondary"]["time_to_100pct_traffic"] == {"p50_us": 10.0}
    assert full["secondary"]["iris_sklearn_linear"] == {"p50_us": 10.0}


def test_early_emission_precedes_secondaries(tmp_path):
    """stdout must carry a parseable headline BEFORE any secondary runs
    (first emission), and a final line after: >= 2 parseable lines on a
    clean run."""
    import os
    import subprocess

    detail = tmp_path / "detail.json"
    env = dict(os.environ, BENCH_DETAIL_PATH=str(detail))
    code = _STUB_MAIN.format(
        repo=str(Path(__file__).resolve().parent.parent), tail_fn="fast"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=60, cwd=tmp_path,
    )
    parseable = []
    for line in proc.stdout.splitlines():
        try:
            parseable.append(json.loads(line))
        except json.JSONDecodeError:
            continue
    assert len(parseable) >= 2, proc.stdout
    # First emission: headline present, secondaries pending (null).
    assert parseable[0]["value"] == 4.5
    assert all(v is None for v in parseable[0]["secondary"].values())
    # Final emission: all secondaries filled in.
    assert all(v is not None for v in parseable[-1]["secondary"].values())
    assert parseable[-1]["secondary"]["llama_7b_decode"] == {"p50_us": 10.0}


def _run_bench_cli(*args):
    import os
    import subprocess

    return subprocess.run(
        [sys.executable, "bench.py", *args],
        capture_output=True, text=True, timeout=60,
        cwd=str(Path(__file__).resolve().parent.parent),
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )


def test_unknown_scenario_exits_with_one_line_error():
    """A typo'd scenario name must exit 2 with ONE line naming the valid
    set — not a KeyError traceback."""
    proc = _run_bench_cli("no_such_scenario", "--dry-run")
    assert proc.returncode == 2, (proc.returncode, proc.stderr)
    err_lines = [l for l in proc.stderr.splitlines() if l.strip()]
    assert len(err_lines) == 1, proc.stderr
    assert "no_such_scenario" in err_lines[0]
    assert "packed_prefill_serving" in err_lines[0]  # the valid set
    assert "Traceback" not in proc.stderr


def test_dry_run_prints_packed_prefill_schema():
    """``--dry-run`` must print the scenario schema contract as one JSON
    line without touching a device (make verify runs exactly this)."""
    proc = _run_bench_cli("packed_prefill_serving", "--dry-run")
    assert proc.returncode == 0, proc.stderr
    parsed = json.loads(proc.stdout.strip().splitlines()[-1])
    assert parsed["dry_run"] is True
    schema = parsed["scenarios"]["packed_prefill_serving"]
    for key in (
        "serial_ttft_p50_ms", "serial_ttft_p99_ms", "serial_chunk_calls",
        "packed_ttft_p50_ms", "packed_ttft_p99_ms", "packed_chunk_calls",
        "ttft_p50_speedup", "chunk_call_reduction", "batch_fill_mean",
    ):
        assert key in schema, key


def test_packed_prefill_schema_covers_compact_keys():
    """Schema drift guard: every key the driver line keeps for a
    scenario must be part of that scenario's published schema — a
    renamed field would otherwise silently vanish from the headline."""
    for name, keys in bench._COMPACT_KEYS.items():
        schema = bench.SCENARIO_SCHEMAS.get(name)
        if schema is None:
            continue
        missing = set(keys) - set(schema)
        assert not missing, (name, missing)
    # The new scenario is covered by both contracts.
    assert "packed_prefill_serving" in bench.SCENARIO_SCHEMAS
    assert "packed_prefill_serving" in bench._COMPACT_KEYS
    assert "packed_prefill_serving" in {name for name, _ in bench.SCENARIOS}
    # Every registry entry resolves to a real bench function.
    for _name, attr in bench.SCENARIOS:
        assert callable(getattr(bench, attr)), attr


def test_compact_line_keeps_packed_prefill_headline():
    full = _fat_full_record()
    full["secondary"]["packed_prefill_serving"] = {
        "requests": 8, "prompt_tokens": 512, "prefill_chunk": 128,
        "prefill_batch": 8,
        "serial_ttft_p50_ms": 1768.8, "serial_ttft_p99_ms": 2924.8,
        "serial_chunk_calls": 32,
        "packed_ttft_p50_ms": 1265.1, "packed_ttft_p99_ms": 1265.6,
        "packed_chunk_calls": 4, "ttft_p50_speedup": 1.4,
        "chunk_call_reduction": 8.0, "batch_fill_mean": 8.0,
        "token_agreement": 1.0,
        "note": "x" * 300,
    }
    parsed = bench.compact_line(full)
    sec = parsed["secondary"]["packed_prefill_serving"]
    assert sec["chunk_call_reduction"] == 8.0
    assert sec["serial_chunk_calls"] == 32
    assert "note" not in sec
    assert len(json.dumps(bench.compact_line(full))) <= bench.COMPACT_BUDGET_BYTES


def test_scan_delta_donated_carry_aliases_in_place():
    """The donated carry must alias into the scan loop state.

    XLA expresses donation as input->output buffer pairs; round 4 found
    the timed region returning only the probe ys, which left the donated
    multi-GiB KV cache nothing to alias into ("Some donated buffers were
    not usable") — the cache lived twice and the 7B 32-slot fit argument
    was void.  Pin: donate_carry produces zero donation warnings.
    """
    import warnings

    import jax.numpy as jnp

    def step(p, c):
        c2 = c * p + 1e-6
        return c2, c2[0, 0]

    def carry_at(i):
        return jnp.ones((128, 128), jnp.float32) * (1.0 + 1e-5 * i)

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        try:
            bench._scan_delta_timed(
                step, carry_at, runs=3, n1=2, n2=6,
                params=jnp.float32(1.0), donate_carry=True,
            )
        except RuntimeError:
            # The anti-elision timing guards can fire on a sub-ms CPU
            # workload; the donation warning (what this test pins) is
            # emitted at trace time, before any timing check.
            pass
    bad = [w for w in caught if "donated" in str(w.message).lower()]
    assert not bad, f"donation failed to alias: {bad[0].message}"
