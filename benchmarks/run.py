#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run: seeded artifact -> `python -m tpumlops.server` child on the chip
-> a few seconds of the mix unmeasured -> the measured window over HTTP
`/generate` streams -> SIGTERM -> the reference over a sample of what was
served -> the last line.  The parent stays off jax while the child holds
the chip.  See benchmarks/README.md.

`--rehearse-cpu` walks the same path at whatever size the cell's
configuration has on the CPU (for benchmarks/tests; never a fallback).
`--control 1` puts the int4 control in the program's place in the
comparison: the same checks then have to print `correct: false` (the
builder's tool for setting the limits; the driver's runs never ask for it).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
import traceback
from pathlib import Path

T_START = time.perf_counter()

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(ROOT))

from harness import counts, loadgen, manifest, metrics, prom  # noqa: E402
from harness import server as srv  # noqa: E402
from harness.context import Context  # noqa: E402

TRACE_S = 2.5  # length of the profiler capture, at the window's end


class RunFailure(Exception):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


def cache_dir() -> str:
    """Where compiled programs live: where the machine says, else one
    fixed ignored path inside the checkout (the path is part of the key)."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    d = BENCH / ".cache" / "jax"
    d.mkdir(parents=True, exist_ok=True)
    return str(d)


def make_artifact(work: Path, cell, seed: int) -> str:
    uri = str(work / "model")
    t0 = time.monotonic()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = str(ROOT) + os.pathsep + env.get("PYTHONPATH", "")
    subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--make-artifact", uri,
         "--workload", cell.name, "--seed", str(seed)],
        env=env, check=True, timeout=900,
    )
    size = sum(f.stat().st_size for f in Path(uri).iterdir())
    say(f"seeded artifact: {size / 2**30:.2f} GiB bf16 in "
        f"{time.monotonic() - t0:.1f}s")
    return uri


def compile_counts(samples: dict, dev: dict | None) -> dict:
    out = {
        "compilations_total": prom.total(samples, "tpumlops_compilations_total"),
        "cache_misses": prom.total(samples, "tpumlops_compile_cache_misses"),
    }
    out["observatory_compiles"] = srv.compile_totals(dev).get("compiles", 0)
    return out


def drive(child: srv.ServerChild, plan: loadgen.Plan, trace: bool) -> tuple:
    """The warm seconds, the window and the drain; returns the driver and
    what the hooks scraped."""
    got: dict = {}

    async def scrape(key, driver):
        async with driver.session.get(child.base + "/metrics") as r:
            samples = prom.parse((await r.read()).decode())
        async with driver.session.get(child.base + "/debug/device") as r:
            device = json.loads(await r.read()) if r.status == 200 else None
        got[key] = {"metrics": samples, "device": device}

    async def profile(driver):
        t0 = driver.now()
        async with driver.session.post(
            child.base + "/debug/profile", json={"duration_s": TRACE_S}
        ) as r:
            got["profile"] = {"status": r.status, "body": await r.json(),
                              "from": t0, "to": driver.now()}

    # A capture ends in seconds of `stop_trace` on the server's event loop,
    # during which no token leaves it: so the capture is the window's last
    # seconds, the counters are read just before it, and the stall falls
    # into the drain.
    counted = max(1.0, plan.seconds - TRACE_S - 0.5) if trace else plan.seconds
    hooks = [(0.0, lambda d: scrape("before", d)),
             (counted, lambda d: scrape("after", d))]
    if trace:
        hooks.append((counted + 0.1, profile))
    driver = loadgen.Driver(child.generate_url, plan, {"at": hooks})
    driver.run()
    return driver, got, counted


def pick_sample(records: list, n: int, seed: int) -> list:
    """The longest finished request of the window and n-1 more, drawn
    from the seed."""
    import numpy as np

    done = [r for r in records if r.complete]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + r.max_new, -r.idx))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 11])
    pick = rng.permutation(len(rest))[: max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def padded_sizes(mix: dict) -> tuple[int, int]:
    """(positions, answers) every sample is padded to: one reference
    program per mix, whatever the seed drew."""
    answers = int(mix["answer_tokens"]["max"])
    seq = int(mix["prompt_tokens"]["max"]) + answers
    return -(-seq // 128) * 128, answers


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=[0, 1])
    ap.add_argument("--control", type=int, default=0, choices=[0, 1])
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--records", help="also write the window's records here (JSON)")
    ap.add_argument("--make-artifact", help=argparse.SUPPRESS)
    args = ap.parse_args()

    if not (ROOT / manifest.PKG).is_dir():
        print(f"{ROOT} holds the benchmark but not the {manifest.PKG} "
              "package: nothing to measure", file=sys.stderr)
        return 2
    try:
        cell = manifest.load_cell(args.workload, ROOT)
    except manifest.ManifestError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if args.make_artifact:
        manifest.load_reference(cell, ROOT).write_artifact(
            args.make_artifact, cell.model, args.seed)
        return 0

    platform = "cpu" if args.rehearse_cpu else "tpu"
    if os.environ.get("JAX_PLATFORMS", "").lower() == "cpu" and not args.rehearse_cpu:
        print("error: JAX_PLATFORMS=cpu: no accelerator to measure on "
              "(--rehearse-cpu is the explicit CPU walk, never a fallback)",
              file=sys.stderr)
        return 3
    seconds = float(args.seconds if args.seconds is not None
                    else manifest.load_manifest(ROOT)["run_seconds"])
    work = Path(tempfile.mkdtemp(prefix="tpumlops-bench-"))
    profiles = work / "profile"  # this run's captures and nobody else's
    child = None
    try:
        say(f"cell {cell.name}: config {cell.config_name}, mix {cell.mix_name}, "
            f"load {({k: v for k, v in cell.load.items() if k in ('rate_rps', 'clients')})}, "
            f"seed {args.seed}, {seconds:g}s, trace {args.trace}")
        # Imports no jax until asked to compare: the chip is the child's.
        reference = manifest.load_reference(cell, ROOT)
        shapes = reference.shapes(cell.model)
        plan = loadgen.build_plan(
            cell.mix, cell.load, shapes.vocab,
            int(cell.model["max_position_embeddings"]), args.seed, seconds)
        uri = make_artifact(work, cell, args.seed)
        cdir = cache_dir()
        child = srv.ServerChild(uri, cell.config["serving"], cdir,
                                work / "server.log", platform, profiles, ROOT)
        child.start()
        at_ready = srv.compile_totals(child.device())
        say(f"server ready in {child.boot_s:.1f}s; compile cache {cdir}; "
            f"since start: {({k: v for k, v in at_ready.items() if k != 'warmup'})} "
            f"warm-up {at_ready.get('warmup')}")

        driver, got, counted = drive(child, plan, bool(args.trace))
        setup_s = driver.t_zero - T_START
        dev_after = child.device()
        drain_s = child.terminate()
        child = None
        say(f"SIGTERM -> exit 0 in {drain_s:.1f}s")
        shutil.rmtree(work / "model", ignore_errors=True)  # before the reference needs the RAM

        records = driver.records
        win = metrics.measured(records, seconds)
        failed = [r for r in win if not r.complete]
        mismatch = [r for r in win if r.complete and r.final_ids != r.tokens]
        e2e_all = metrics.end_to_end(records, seconds)
        say(f"window: attempted {len(win)}, failed {len(failed)}, "
            f"all records {len(records)}; lengths drawn: prompt "
            f"{metrics.percentile([r.prompt_len for r in win], 50)} median / "
            f"{max((r.prompt_len for r in win), default=0)} max, answer "
            f"{metrics.percentile([r.max_new for r in win], 50)} median / "
            f"{max((r.max_new for r in win), default=0)} max")
        for r in failed[:5]:
            say(f"  failed request {r.idx}: {r.error} after {len(r.tokens)}/{r.max_new} tokens")
        say("timelines: " + json.dumps(e2e_all))
        say(f"generator lateness ms: {json.dumps(loadgen.lateness_ms(driver))}")
        before, after = got.get("before"), got.get("after")
        if before is None or after is None:
            raise RunFailure("the window's scrapes of /metrics did not happen")
        c0 = compile_counts(before["metrics"], before["device"])
        c1 = compile_counts(after["metrics"], after["device"])
        compiles_in_window = max(c1[k] - c0[k] for k in c0)
        say(f"compiles inside the window: {compiles_in_window} ({c0} -> {c1})")
        say("server histograms over the window [mean, n]: "
            + json.dumps(prom.window_means(before["metrics"], after["metrics"])))
        hbm = (dev_after or {}).get("hbm") or {}
        measured_mem = hbm.get("measured") or {}
        say(f"HBM: bytes_in_use {measured_mem.get('bytes_in_use')}, peak "
            f"{measured_mem.get('peak_bytes_in_use')}, ledger "
            f"{hbm.get('device_total_bytes')} {hbm.get('components')}")
        if args.records:
            Path(args.records).parent.mkdir(parents=True, exist_ok=True)
            Path(args.records).write_text(json.dumps([
                {k: v for k, v in vars(r).items() if k != "prompt_ids"}
                for r in records]))

        # -- the chip is free: the parent may touch jax now ------------------
        os.environ["JAX_PLATFORMS"] = platform
        import jax

        jax.config.update("jax_compilation_cache_dir", cdir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        devs = jax.devices()
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs),
                  "memory_peak_bytes": int(measured_mem.get("peak_bytes_in_use") or 0)}
        if device["platform"] == "cpu" and not args.rehearse_cpu:
            raise RunFailure("jax found no accelerator")
        if device["count"] < cell.chips:
            raise RunFailure(f"cell needs {cell.chips} chips, jax sees {device['count']}")

        sample = pick_sample(win, int(cell.mix.get("check_sample", 6)), args.seed)
        seq, answers = padded_sizes(cell.mix)
        t0 = time.monotonic()
        ref = (reference.compare(
            cell.model, args.seed, [(r.prompt_ids, r.tokens) for r in sample],
            seq, answers, control=bool(args.control)) if sample else {})
        say(f"reference over {len(sample)} requests in "
            f"{time.monotonic() - t0:.1f}s: {json.dumps(ref)}")

        summary = None
        if args.trace:
            from harness import trace as tr

            prof = got.get("profile") or {}
            say(f"profile: {prof}")
            found = tr.find_xplane(profiles)
            if len(found) != 1:
                raise RunFailure(f"one capture was asked for, {profiles} holds "
                                 f"{[str(f) for f in found]}")
            xplane = found[0]
            t0 = time.monotonic()
            flat = tr.read_xplane(xplane)
            summary = tr.reduce(flat, cell.config.get("trace_programs") or {})
            say(f"trace {xplane} ({xplane.stat().st_size / 2**20:.1f} MiB) "
                f"reduced in {time.monotonic() - t0:.1f}s: window "
                f"{summary.window_s:.3f}s busy {summary.busy_s:.3f}s on "
                f"{summary.devices} device(s)")
            for name, sec, n in summary.modules[:12]:
                say(f"  program {name}: {sec:.4f}s over {n} executions")
            dump = os.environ.get("BENCH_TRACE_DUMP")
            if dump:
                keep = {"planes": [
                    {"name": p["name"], "lines": [
                        {"name": l["name"], "events": l["events"][:4000]}
                        for l in p["lines"]]} for p in flat["planes"]],
                    "span_ns": flat["span_ns"]}
                Path(dump).parent.mkdir(parents=True, exist_ok=True)
                Path(dump).write_text(json.dumps(keep))

        values: dict[str, float] = {}
        if args.trace:
            peaks = None if device["platform"] == "cpu" else counts.peaks_for(device["kind"])
            # Per-layer readers see the part of the window before the capture.
            ctx = Context(cell, counted, records, before["metrics"],
                          after["metrics"], summary, shapes, peaks)
            for m in cell.per_layer:
                v = manifest.load_layer_metric(m["name"], ROOT).compute(ctx)
                if v is not None:
                    values[m["name"]] = float(v)
            for note in ctx.notes:
                say("  " + note)
            units = {m["name"]: m["unit"] for m in cell.per_layer}
            if summary is not None and summary.devices:
                device["busy_s"] = summary.busy_s
                device["window_s"] = summary.window_s
        else:
            for m in cell.end_to_end:
                values[m["name"]] = setup_s if m["name"] == "setup_s" else e2e_all[m["name"]]
            units = {m["name"]: m["unit"] for m in cell.end_to_end}
        say(f"setup_s {setup_s:.3f}: process start to the window's start, "
            f"{plan.warm_s:g}s of the mix unmeasured included")

        limits = cell.load.get("limits") or {}
        # `--control 1`: the control's readings stand where the program's
        # would, under the same names, through the same comparison.
        who = "control_" if args.control else ""
        checks = [
            ["requests_incomplete", len(failed), 0],
            ["stream_mismatch", len(mismatch), 0],
            ["compiles_in_window", compiles_in_window, 0],
            ["max_logit_gap", ref.get(who + "max_logit_gap"), limits.get("max_logit_gap")],
            ["mean_logit_gap", ref.get(who + "mean_logit_gap"), limits.get("mean_logit_gap")],
        ]
        ok = all(value is not None and limit is not None and value <= limit
                 for _name, value, limit in checks)
        result = {
            "correct": bool(ok), "attempted": len(win), "failed": len(failed),
            "metrics": {k: {"value": v, "unit": units[k]} for k, v in values.items()},
            "device": device,
        }
        if summary is not None:
            result["breakdown"] = {"device_ops": summary.device_ops,
                                   "idle_gaps": summary.idle_gaps}
        if args.control:
            result["compared"] = "the int4 control in the program's place"
        result["checks"] = {n: {"value": v, "limit": l} for n, v, l in checks}
        sys.stdout.flush()
        for name, value, limit in checks:
            print(f"check {name}: value {value} limit {limit}", file=sys.stderr)
        print(f"correct: {ok}" + (" (the int4 control in the program's place)"
                                  if args.control else ""), file=sys.stderr, flush=True)
        print(json.dumps(result), flush=True)
        return 0
    except Exception as e:  # the boundary: report, clean up, exit non-zero
        traceback.print_exc()
        print(f"FAILED: {type(e).__name__}: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        if child is not None:
            try:
                sys.stderr.write(child.log_tail(60) + "\n")
            finally:
                child.kill()
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
