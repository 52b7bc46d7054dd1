#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py                 # one TPU chip (what the driver runs)
    python chip_smoke.py --layers 32     # same, Llama-2-7B at full depth
    python chip_smoke.py --chips 4       # ONLY the multi-chip path + its tp=1 twin
    python chip_smoke.py --rehearse-cpu  # tiny sizes on the CPU, control flow only

One-chip run, in this order so the chip always belongs to ONE process (the
parent stays off jax while a child holds the chip; each child has exited
before the next starts; the parent imports jax only after the last child):

  A. serve-llm — ``python -m tpumlops.server`` with exactly the container
     args and JAX_PLATFORMS the operator's manifest builder emits for a TPU
     pod, on Llama-2-7B at full WIDTH (depth is --layers; weights random
     from --seed, int8).  Health, a handful of /generate requests, /metrics,
     /debug/device (HBM ledger vs device.memory_stats()), SIGTERM -> rc 0.
     Then the same child a second time: the warm-up sweep must come from
     the persistent compile cache.
  B. rollout — in-process: two seeded BERT-base int8 versions behind the
     compiled native router, OperatorRuntime over SyncingKube/FakeRegistry
     with the router's live histograms as the gate's metrics; flip the
     alias v1 -> v2; the CR must reach Stable at 100 % on v2.

The LAST stdout line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``
with the device as jax reports it.  Any failed phase -> ``"ok": false`` and
a non-zero exit.  Without an accelerator the script fails in seconds, before
any model is made; ``--rehearse-cpu`` is the explicit (never automatic) way
to walk the whole script at tiny size on the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time
import traceback
import urllib.error
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent
PKG = "research_and_development_of_kubernetes_operator_for_machine_learning_pipelines_tpu"

# Llama-2-7B widths (BASELINE.json flagship); depth is --layers.
LLAMA_7B = dict(vocab_size=32000, hidden_size=4096, num_heads=32,
                num_kv_heads=32, intermediate_size=11008, max_seq=1024)
# --rehearse-cpu only: 4 KV heads so tp=4 divides them.
LLAMA_TINY = dict(vocab_size=512, hidden_size=128, num_heads=4,
                  num_kv_heads=4, intermediate_size=256, max_seq=256)
DEFAULT_LAYERS = 8
PREFILL_CHUNK = 128
LEDGER_TOLERANCE_PCT = 10.0  # docs/OBSERVABILITY.md: the ledger's e2e gate
BOOT_TIMEOUT_S = 900.0  # a cold full-width warm-up sweep is minutes
MODEL, NS = "llama2-7b", "models"

_children: list[subprocess.Popen] = []


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)
    say(f"    ok: {msg}")


def http(url: str, body: dict | None = None, timeout: float = 120.0):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data,
        headers={"Content-Type": "application/json"} if data else {},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


# ---------------------------------------------------------------------------
# Device probe (a child: the parent must not touch jax yet)
# ---------------------------------------------------------------------------


def probe_device(env: dict) -> dict:
    """What jax sees, asked by a child that exits before anyone else
    needs the chip."""
    code = (
        "import json, jax; d = jax.devices(); print(json.dumps({'platform': "
        "d[0].platform, 'kind': d[0].device_kind, 'count': len(d)}))"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=180,
    )
    if out.returncode != 0:
        raise SmokeFailure(
            "jax found no usable device: " + out.stderr.strip()[-600:]
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# Seeded artifacts
# ---------------------------------------------------------------------------


def make_llama_artifact(path: str, layers: int, seed: int, tiny: bool) -> None:
    """Child mode (JAX_PLATFORMS=cpu, numpy + ml_dtypes only): random bf16
    weights at the given geometry through ``save_native_model``."""
    from concurrent.futures import ThreadPoolExecutor

    import ml_dtypes
    import numpy as np

    sys.path.insert(0, str(ROOT))
    from tpumlops.server.loader import save_native_model

    g = dict(LLAMA_TINY if tiny else LLAMA_7B)
    H, I, V = g["hidden_size"], g["intermediate_size"], g["vocab_size"]
    kvd = H // g["num_heads"] * g["num_kv_heads"]
    bf16 = ml_dtypes.bfloat16
    layer_shapes = {
        "q": (H, H), "k": (H, kvd), "v": (H, kvd), "o": (H, H),
        "gate": (H, I), "up": (H, I), "down": (I, H),
    }
    params = {
        "embed": np.empty((V, H), bf16),
        "lm_head": np.empty((H, V), bf16),
        "final_norm": np.ones((H,), bf16),
        "layers": {
            **{k: np.empty((layers, *s), bf16) for k, s in layer_shapes.items()},
            "attn_norm": np.ones((layers, H), bf16),
            "mlp_norm": np.ones((layers, H), bf16),
        },
    }
    # One independent stream per (leaf, layer) so threads can fill them
    # in any order and the artifact is still a function of the seed.
    jobs = [("embed", None), ("lm_head", None)] + [
        (k, l) for k in layer_shapes for l in range(layers)
    ]

    def fill(job_index: int) -> None:
        name, l = jobs[job_index]
        out = params[name] if l is None else params["layers"][name][l]
        rng = np.random.default_rng([seed, job_index])
        flat = out.reshape(-1)
        step = 1 << 22
        for i in range(0, flat.size, step):
            n = min(step, flat.size - i)
            flat[i:i + n] = (
                rng.standard_normal(n, dtype=np.float32) * 0.02
            ).astype(bf16)

    with ThreadPoolExecutor(max_workers=min(12, os.cpu_count() or 1)) as ex:
        list(ex.map(fill, range(len(jobs))))
    save_native_model(path, "llama-generate", params,
                      config={**g, "num_layers": layers})


def bert_request_body(cfg, batch: int, seq: int, seed: int) -> bytes:
    import numpy as np

    ids = np.random.default_rng(seed).integers(0, cfg.vocab_size, (batch, seq))
    return json.dumps({"inputs": [
        {"name": "input_ids", "shape": [batch, seq], "datatype": "INT32",
         "data": ids.ravel().tolist()},
        {"name": "attention_mask", "shape": [batch, seq],
         "datatype": "INT32", "data": [1] * (batch * seq)},
    ]}).encode()


# ---------------------------------------------------------------------------
# Phase A: the server process, as the manifests start it
# ---------------------------------------------------------------------------


class ServerChild:
    """``python -m tpumlops.server`` with the builder's own container args."""

    def __init__(self, uri: str, mesh: dict, topology: str, cache_dir: str,
                 log: Path, rehearse: bool, extra_env: dict | None = None):
        from tpumlops.clients.localplane import free_port
        from tpumlops.operator.builder import build_deployment
        from tpumlops.utils.config import OperatorConfig

        cfg = OperatorConfig.from_spec({
            "modelName": MODEL, "modelAlias": "prod", "backend": "tpu",
            "tpu": {
                "tpuTopology": topology, "meshShape": mesh,
                "maxBatchSize": 8, "quantize": "int8",
                "prefillChunk": PREFILL_CHUNK,
                # The ledger cross-check needs a device that reports
                # memory and has a peaks row; the CPU has neither.
                "observability": {"traceRing": 256,
                                  "deviceTelemetry": not rehearse},
                "compileCacheDir": cache_dir,
            },
        })
        sd = build_deployment(MODEL, NS, "chip-smoke", cfg, "1", uri, 100)
        container = sd["spec"]["predictors"][0]["componentSpecs"][0][
            "spec"]["containers"][0]
        self.port = free_port()
        self.cmd = [sys.executable, "-m", "tpumlops.server", *container["args"],
                    "--host", "127.0.0.1", "--port", str(self.port),
                    "--metrics-port", "0", "--drain-s", "0.5"]
        env = dict(os.environ)
        # The pod's env, minus TPU_TOPOLOGY (a GKE slice label, not a
        # machine fact: this host's runtime describes itself).
        for e in container["env"]:
            if e["name"] != "TPU_TOPOLOGY" and "value" in e:
                env[e["name"]] = e["value"]
        if rehearse:
            env["JAX_PLATFORMS"] = "cpu"
        env.update(extra_env or {})
        env["PYTHONPATH"] = str(ROOT) + os.pathsep + env.get("PYTHONPATH", "")
        self.env, self.log = env, log
        self.base = f"http://127.0.0.1:{self.port}"
        self.proc: subprocess.Popen | None = None
        self.boot_s = 0.0

    def start(self, timeout: float) -> None:
        if "jax" in sys.modules:
            raise SmokeFailure(
                "the parent has imported jax: it may hold the chip this "
                "child needs (one process per chip)"
            )
        say(f"    $ JAX_PLATFORMS={self.env['JAX_PLATFORMS']} "
            f"JAX_COMPILATION_CACHE_DIR={self.env['JAX_COMPILATION_CACHE_DIR']}"
            f" {' '.join(self.cmd[1:])}")
        t0 = time.monotonic()
        with open(self.log, "w") as fh:
            self.proc = subprocess.Popen(
                self.cmd, cwd=ROOT, env=self.env, stdout=fh,
                stderr=subprocess.STDOUT,
            )
        _children.append(self.proc)
        while time.monotonic() - t0 < timeout:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"server exited rc={self.proc.returncode} before "
                    f"readiness\n{self.log_tail()}"
                )
            try:
                if http(self.base + "/v2/health/ready", timeout=2)[0] == 200:
                    self.boot_s = time.monotonic() - t0
                    return
            except OSError:
                pass
            time.sleep(0.25)
        raise SmokeFailure(
            f"server not ready after {timeout:.0f}s\n{self.log_tail()}"
        )

    def log_tail(self, n: int = 40) -> str:
        lines = self.log.read_text(errors="replace").splitlines()
        return "\n".join("      | " + line for line in lines[-n:])

    def generate(self, prompt: list[int], max_new: int) -> list[int]:
        code, raw = http(
            f"{self.base}/v2/models/{MODEL}/generate",
            {"prompt_ids": prompt, "max_new_tokens": max_new},
        )
        if code != 200:
            raise SmokeFailure(f"/generate -> {code}: {raw[:300]!r}")
        data = json.loads(raw)["outputs"][0]["data"]
        if len(data) != max_new:
            raise SmokeFailure(
                f"asked {max_new} tokens, got {len(data)}: {data}"
            )
        return data

    def generate_sse(self, prompt: list[int], max_new: int) -> list[int]:
        req = urllib.request.Request(
            f"{self.base}/v2/models/{MODEL}/generate",
            data=json.dumps({"prompt_ids": prompt, "max_new_tokens": max_new,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as resp:
            if resp.status != 200:
                raise SmokeFailure(f"SSE /generate -> {resp.status}")
            events = [json.loads(line[5:]) for line in
                      resp.read().decode().splitlines()
                      if line.startswith("data:")]
        streamed = [e["token"] for e in events if "token" in e]
        final = [e for e in events if e.get("done")]
        if not final or final[0]["output_ids"] != streamed:
            raise SmokeFailure(f"SSE stream inconsistent: {events}")
        if len(streamed) != max_new:
            raise SmokeFailure(f"SSE asked {max_new}, streamed {streamed}")
        return streamed

    def device(self) -> dict | None:
        code, raw = http(self.base + "/debug/device")
        return json.loads(raw) if code == 200 else None

    def metrics(self) -> dict:
        from tpumlops.clients.router import parse_prometheus_text

        code, raw = http(self.base + "/metrics")
        if code != 200:
            raise SmokeFailure(f"/metrics -> {code}")
        return parse_prometheus_text(raw.decode())

    def terminate(self, grace: float = 60.0) -> None:
        """SIGTERM, as kubelet does; the drain must finish by itself."""
        t0 = time.monotonic()
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=grace)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            raise SmokeFailure(
                f"server still alive {grace:.0f}s after SIGTERM\n"
                f"{self.log_tail()}"
            ) from None
        say(f"    SIGTERM -> rc {rc} in {time.monotonic() - t0:.1f}s")
        if rc != 0:
            raise SmokeFailure(f"server exit code {rc}\n{self.log_tail()}")
        check("drain grace" not in self.log.read_text(errors="replace"),
              "drained without the 'drain grace expired' warning")


def compile_totals(dev: dict | None) -> dict:
    """Backend compiles / persistent-cache outcomes so far.  With device
    telemetry on, per-op from the compile observatory; without it (CPU
    rehearsal) nothing attributes them and only readiness is checked."""
    if dev is None:
        return {}
    ops = dev["compile"]["ops"]
    return {
        "compiles": sum(o["compiles"] for o in ops.values()),
        "hits": sum(o["cache_hits"] for o in ops.values()),
        "misses": sum(o["cache_misses"] for o in ops.values()),
        "by_op": ops,
        "warmup": dev["compile"]["warmup"],
    }


def smoke_prompts(vocab: int, seed: int) -> dict[str, list[int]]:
    import numpy as np

    rng = np.random.default_rng([seed, 99])
    draw = lambda n: rng.integers(1, vocab, n).tolist()
    return {
        "short": draw(5),
        "chunk_crossing": draw(PREFILL_CHUNK + 72),  # 2 chunks
        "pair_a": draw(40), "pair_b": draw(70),
        "stream": draw(9),
    }


def drive_requests(srv: ServerChild, prompts: dict) -> dict[str, list[int]]:
    out = {"short": srv.generate(prompts["short"], 16)}
    say(f"    short prompt (5 tok) -> {out['short']}")
    out["chunk_crossing"] = srv.generate(prompts["chunk_crossing"], 8)
    say(f"    {len(prompts['chunk_crossing'])}-token prompt (crosses the "
        f"{PREFILL_CHUNK}-token prefill chunk) -> {out['chunk_crossing']}")
    pair: dict = {}

    def one(key):
        try:
            pair[key] = srv.generate(prompts[key], 12)
        except BaseException as e:  # re-raised on the main thread
            pair[key] = e

    threads = [threading.Thread(target=one, args=(k,))
               for k in ("pair_a", "pair_b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for k, v in pair.items():
        if isinstance(v, BaseException):
            raise v
        out[k] = v
    say(f"    two concurrent requests -> {out['pair_a']} / {out['pair_b']}")
    out["stream"] = srv.generate_sse(prompts["stream"], 8)
    say(f"    SSE stream -> {out['stream']}")
    again = srv.generate(prompts["short"], 16)
    check(again == out["short"], "repeat of the first prompt returns "
          "identical greedy tokens")
    return out


def phase_a(work: Path, args, cache_dir: str) -> None:
    say(f"[A] serve-llm: Llama-2-7B widths, {args.layers} of 32 layers, int8 "
        f"weights, seed {args.seed}" + (" (TINY rehearsal geometry)"
                                        if args.rehearse_cpu else ""))
    from tpumlops.utils.compile_cache import cache_entry_count

    cache_entries = lambda: cache_entry_count(cache_dir)
    uri = make_artifact(work, args)
    geom = LLAMA_TINY if args.rehearse_cpu else LLAMA_7B
    prompts = smoke_prompts(geom["vocab_size"], args.seed)
    first: dict = {}
    for boot in (1, 2):
        say(f"  boot {boot} ({'cold' if boot == 1 else 'same command again'})"
            f": compile cache {cache_dir} holds {cache_entries()} "
            "entries")
        entries_before = cache_entries()
        srv = ServerChild(uri, {"dp": 1, "tp": 1}, "v5e-1", cache_dir,
                          work / f"server_boot{boot}.log", args.rehearse_cpu)
        srv.start(timeout=BOOT_TIMEOUT_S)
        at_ready = compile_totals(srv.device())
        say(f"    ready in {srv.boot_s:.1f}s")
        if at_ready:
            w = at_ready["warmup"]
            say(f"    warm-up sweep: {w.get('compiles')} compiles, "
                f"{w.get('seconds', 0):.1f}s of XLA, wall {w.get('wall_s')}s, "
                f"ops {w.get('ops')}")
            say(f"    since process start: backend compiles "
                f"{at_ready['compiles']}, persistent-cache hits "
                f"{at_ready['hits']}, misses {at_ready['misses']}")
        if boot == 1:
            first = drive_requests(srv, prompts)
        else:
            again = srv.generate(prompts["short"], 16)
            check(again == first["short"],
                  "boot 2 answers the first prompt with boot 1's tokens")
        samples = srv.metrics()
        ticks = {dict(labels).get("kind"): v for (n, labels), v in
                 samples.items() if n == "tpumlops_tick_seconds_count"}
        say(f"    tpumlops_tick_seconds counts by kind: {ticks}")
        check(sum(ticks.values()) > 0, "engine ticks were recorded")
        dev1 = srv.device()
        after = compile_totals(dev1)
        if after:
            check(after["compiles"] == at_ready["compiles"]
                  and after["misses"] == at_ready["misses"],
                  "zero compiles after readiness (every shape was warmed)")
            hbm = dev1["hbm"]
            m = hbm.get("measured")
            check(m is not None and m.get("bytes_in_use"),
                  "device.memory_stats() reports bytes_in_use")
            say(f"    HBM ledger {hbm['device_total_bytes'] / 2**30:.3f} GiB "
                f"{hbm['components']} vs memory_stats bytes_in_use "
                f"{m['bytes_in_use'] / 2**30:.3f} GiB (peak "
                f"{m.get('peak_bytes_in_use', 0) / 2**30:.3f} GiB): "
                f"ledger_vs_measured {hbm['ledger_vs_measured_pct']}%")
            check(abs(hbm["ledger_vs_measured_pct"]) <= LEDGER_TOLERANCE_PCT,
                  f"analytic ledger within {LEDGER_TOLERANCE_PCT}% of the "
                  "device's own count")
            check(dev1["peaks"]["source"] == "detected",
                  f"peaks row detected for {dev1['peaks']['device']}")
            if boot == 1:
                check(cache_entries() > entries_before
                      or at_ready["hits"] > 0,
                      "boot 1 wrote entries into the compile cache")
            else:
                for op, rec in sorted(at_ready["by_op"].items()):
                    if rec["cache_misses"]:
                        say(f"    MISS on boot 2: op={op} misses="
                            f"{rec['cache_misses']} compiles={rec['compiles']}"
                            " — every executable is persisted regardless of "
                            "size or compile time, so a miss means this "
                            "program's cache key changed between two boots "
                            "of one command")
                check(at_ready["hits"] > 0,
                      "boot 2 was served by persistent-cache hits")
                check(at_ready["misses"] == 0,
                      "no persistent-cache miss on boot 2")
        elif boot == 2:
            check(cache_entries() > 0,
                  "compile cache holds entries (rehearsal: no observatory)")
        srv.terminate()


def make_artifact(work: Path, args) -> str:
    uri = str(work / "llama")
    t0 = time.monotonic()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, str(ROOT / "chip_smoke.py"), "--make-llama", uri,
           "--layers", str(args.layers), "--seed", str(args.seed)]
    if args.rehearse_cpu:
        cmd.append("--rehearse-cpu")
    subprocess.run(cmd, env=env, check=True, timeout=900)
    size = sum(f.stat().st_size for f in Path(uri).iterdir())
    say(f"  seeded artifact: {size / 2**30:.2f} GiB bf16 in "
        f"{time.monotonic() - t0:.1f}s (numpy + ml_dtypes, no jax device)")
    return uri


# ---------------------------------------------------------------------------
# --chips 4: the multi-chip path and what it is compared with
# ---------------------------------------------------------------------------


def phase_multichip(work: Path, args, cache_dir: str, probe: dict) -> None:
    check(probe["count"] >= 4, f"four devices visible (jax sees {probe['count']})")
    say(f"[M] multi-chip: Llama-2-7B widths, {args.layers} layers, one seeded "
        "artifact served at tp=4, then at tp=1")
    uri = make_artifact(work, args)
    geom = LLAMA_TINY if args.rehearse_cpu else LLAMA_7B
    prompts = smoke_prompts(geom["vocab_size"], args.seed)
    asks = [("short", 6), ("chunk_crossing", 6), ("pair_a", 6),
            ("pair_b", 6), ("stream", 6)]
    extra = ({"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
             if args.rehearse_cpu else None)
    meshes = [("tp4", {"dp": 1, "tp": 4}, "v5e-4"),
              ("tp1", {"dp": 1, "tp": 1}, "v5e-1")]
    tokens: dict[str, dict] = {}
    for name, mesh, topology in meshes:
        say(f"  {name}: meshShape {mesh}")
        srv = ServerChild(uri, mesh, topology, cache_dir,
                          work / f"server_{name}.log", args.rehearse_cpu, extra)
        srv.start(timeout=BOOT_TIMEOUT_S)
        say(f"    ready in {srv.boot_s:.1f}s")
        tokens[name] = {k: srv.generate(prompts[k], n) for k, n in asks}
        say(f"    tokens: {tokens[name]}")
        dev = srv.device()
        if dev is not None and name != "tp1":
            hbm = dev["hbm"]
            per_dev = hbm["measured"]["per_device_bytes_in_use"]
            total = hbm["device_total_bytes"]
            say(f"    placement: ledger total {total / 2**30:.3f} GiB, "
                f"expected per chip "
                f"{hbm.get('per_chip', {}).get('total', 0) / 2**30:.3f} GiB")
            for i, b in enumerate(per_dev):
                say(f"      device {i}: bytes_in_use {b / 2**30:.3f} GiB "
                    f"({100.0 * b / total:.1f}% of the ledger total)")
            check(len(per_dev) == 4 and min(per_dev) > 0,
                  "all four devices hold part of the model")
            check(max(per_dev) <= total / 3,
                  "no device holds more than a third of weights + cache")
            # Observation, not a check: every chip holds ~0.1 GiB the
            # ledger does not count, so the one-chip tolerance does not
            # carry over to the sum at shallow depth (PERF.md, PR 21).
            say(f"    ledger vs summed memory_stats: "
                f"{hbm['ledger_vs_measured_pct']}%")
        srv.terminate()
    ref = tokens["tp1"]
    for name in tokens:
        if name == "tp1":
            continue
        same = total = 0
        for k, _n in asks:
            check(tokens[name][k][0] == ref[k][0],
                  f"{name} vs tp1: first token identical on prompt {k!r}")
            same += sum(a == b for a, b in zip(tokens[name][k], ref[k]))
            total += len(ref[k])
        say(f"    {name} vs tp1 token agreement {same}/{total} = "
            f"{same / total:.3f}")
        check(same / total >= 0.85, f"{name} token agreement >= 0.85 "
              "(bf16 near-ties flip argmax chains; exact parity is the f64 "
              "test suite's job)")


# ---------------------------------------------------------------------------
# Phase B: the canary rollout, in-process (the parent takes the chip now)
# ---------------------------------------------------------------------------


def phase_b(work: Path, args, cache_dir: str) -> None:
    say("[B] rollout: BERT-base int8, b32/s128 requests, native router, "
        "operator on live histograms" + (" (TINY rehearsal geometry)"
                                         if args.rehearse_cpu else ""))
    if shutil.which("g++") is None:
        raise SmokeFailure(
            "g++ not found: the native router (native/router.cc) cannot be "
            "built on this machine, so the rollout cannot run"
        )
    # Built from the committed source into the work dir: nothing outside
    # the checkout (a ~/.cache binary from another tree) is trusted.
    os.environ["TPUMLOPS_CACHE"] = str(work / "native")
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    from tpumlops.clients.base import ObjectRef
    from tpumlops.clients.fakes import FakeRegistry
    from tpumlops.clients.localplane import (
        SyncingKube, TrafficGenerator, free_port, relaxed_gate_spec,
        start_model_server,
    )
    from tpumlops.clients.router import (
        RouterMetricsSource, RouterProcess, RouterSync, build_router,
    )
    from tpumlops.models import bert
    from tpumlops.operator.runtime import OperatorRuntime
    from tpumlops.server.loader import save_native_model
    from tpumlops.utils.clock import SystemClock
    from tpumlops.utils.compile_cache import (
        counters_snapshot, enable_persistent_compile_cache,
    )
    from tpumlops.utils.config import TpuSpec

    check(enable_persistent_compile_cache(cache_dir),
          f"persistent compile cache enabled at {cache_dir}")
    t0 = time.monotonic()
    binary = build_router()
    say(f"    native router built from native/router.cc in "
        f"{time.monotonic() - t0:.1f}s -> {binary}")

    cfg = bert.BertConfig.tiny() if args.rehearse_cpu else bert.BertConfig.base()
    batch, seq = 32, 128
    fields = ("vocab_size", "hidden_size", "num_layers", "num_heads",
              "intermediate_size", "max_position_embeddings", "num_labels")
    handles, ports = [], {}
    router = rt = None
    gens = []
    try:
        for version in ("1", "2"):
            t0 = time.monotonic()
            params = bert.init(jax.random.key(args.seed + int(version)), cfg)
            art = str(work / f"bert-v{version}")
            save_native_model(
                art, "bert-classifier", params,
                config={f: getattr(cfg, f) for f in fields},
                builder_kwargs={"seq_len": seq, "seq_buckets": False},
            )
            del params
            port = free_port()
            handles.append(start_model_server(
                art, f"v{version}", port, model_name="bert", namespace=NS,
                tpu=TpuSpec.from_spec({
                    "meshShape": {"tp": 1}, "maxBatchSize": batch,
                    "maxBatchDelayMs": 2, "quantize": "int8",
                }),
                ready_timeout_s=BOOT_TIMEOUT_S,
            ))
            ports[f"v{version}"] = port
            say(f"    bert v{version} (seed {args.seed + int(version)}) "
                f"serving in {time.monotonic() - t0:.1f}s; "
                f"compile counters {counters_snapshot()}")

        router = RouterProcess(port=free_port(), backends={},
                               namespace=NS).start()
        kube = SyncingKube(RouterSync(
            router.admin, lambda pred: ("127.0.0.1", ports[pred])))
        registry = FakeRegistry()
        registry.register("bert", "1", "mlflow-artifacts:/1/aaa/artifacts/model")
        registry.set_alias("bert", "prod", "1")
        rt = OperatorRuntime(kube, registry,
                             metrics=RouterMetricsSource(router.admin),
                             clock=SystemClock(), sync_interval_s=0.05)
        ref = ObjectRef(namespace=NS, name="bert", group="mlflow.nizepart.com",
                        version="v1alpha1", plural="mlflowmodels")
        spec = relaxed_gate_spec(stepInterval=0.5)
        spec.update(modelName="bert", observability={"historyLimit": 48})
        kube.create(ref, {"spec": spec})
        threading.Thread(target=rt.serve, daemon=True).start()

        def status() -> dict:
            return kube.get(ref).get("status") or {}

        def wait_for(pred, timeout, what):
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                if pred():
                    return
                time.sleep(0.05)
            raise SmokeFailure(f"timed out waiting for {what}: {status()}")

        wait_for(lambda: status().get("phase") == "Stable", 60, "v1 Stable")
        check(router.admin.get_weights() == {"v1": 100}, "v1 Stable at 100 %")
        body = bert_request_body(cfg, batch, seq, args.seed)
        for _ in range(2):
            gen = TrafficGenerator(router.port, model_name="bert", body=body)
            gen.__enter__()
            gens.append(gen)
        sent = lambda: sum(g.sent for g in gens)
        errors = lambda: sum(g.errors for g in gens)
        wait_for(lambda: sent() - errors() > 50, 120, "baseline traffic on v1")

        registry.register("bert", "2", "mlflow-artifacts:/1/bbb/artifacts/model")
        registry.set_alias("bert", "prod", "2")
        t_flip = time.monotonic()
        wait_for(lambda: status().get("phase") == "Stable"
                 and status().get("currentModelVersion") == "2",
                 240, "promotion of v2 to Stable")
        t100 = time.monotonic() - t_flip
        for gen in gens:
            gen.__exit__()
        time.sleep(0.3)  # let in-flight requests land in the counters
        st = status()
        say(f"    alias flip -> Stable on v2 at 100 %: time-to-100% {t100:.2f}s"
            f" (policy: 25 % steps, stepInterval 0.5s); requests sent "
            f"{sent()}, failed {errors()}")
        check(st.get("trafficCurrent") == 100
              and router.admin.get_weights() == {"v2": 100},
              "CR Stable, router sends 100 % to v2, v1 removed")
        gates = [r for r in st.get("history") or [] if r.get("kind") == "gate"]
        say(f"    status.history gate records: {len(gates)}; last "
            f"{json.dumps(gates[-1]) if gates else None}")
        check(len(gates) > 0, "the gate judged real router histograms")
        check("PromotionComplete" in kube.event_reasons(),
              "PromotionComplete event emitted")
        check(errors() == 0, "zero failed requests during the rollout")
        say(f"    compile counters after rollout: {counters_snapshot()}")
    finally:
        for gen in gens:
            gen.__exit__()
        if rt is not None:
            rt.stop()
        if router is not None:
            router.stop()
        for h in handles:
            h.stop()


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--layers", type=int, default=DEFAULT_LAYERS,
                    help="Llama depth (widths are never cut; 32 = full "
                    f"Llama-2-7B; default {DEFAULT_LAYERS})")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run ONLY the tp=4 server and its tp=1 twin")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="walk the whole script at TINY size on the CPU "
                    "(explicit; never a fallback)")
    ap.add_argument("--make-llama", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.make_llama:
        make_llama_artifact(args.make_llama, args.layers, args.seed,
                            args.rehearse_cpu)
        return 0

    device = None
    t_start = time.monotonic()
    work = ROOT / ".smoke_work"  # gitignored; artifacts, logs, router
    try:
        if not (ROOT / PKG).is_dir():
            raise SmokeFailure(
                f"{ROOT} holds chip_smoke.py but not the {PKG} package: "
                "nothing to smoke"
            )
        sys.path.insert(0, str(ROOT))
        env = dict(os.environ)
        if args.rehearse_cpu:
            env["JAX_PLATFORMS"] = "cpu"
            if args.chips == 4:
                env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        if env.get("JAX_PLATFORMS", "").lower() == "cpu" and not args.rehearse_cpu:
            raise SmokeFailure(
                "JAX_PLATFORMS=cpu: no accelerator to smoke (the CPU walk is "
                "--rehearse-cpu, never a fallback)"
            )
        probe = probe_device(env)
        say(f"jax sees {probe['count']} x {probe['kind']} ({probe['platform']})")
        if probe["platform"] == "cpu" and not args.rehearse_cpu:
            raise SmokeFailure("jax found no accelerator (platform cpu)")
        from tpumlops.utils.compile_cache import resolve_compile_cache_dir

        cache_dir = resolve_compile_cache_dir()
        if work.exists():
            shutil.rmtree(work)
        work.mkdir(parents=True)
        if args.chips == 4:
            t0 = time.monotonic()
            phase_multichip(work, args, cache_dir, probe)
            say(f"[M] passed in {time.monotonic() - t0:.1f}s")
        else:
            t0 = time.monotonic()
            phase_a(work, args, cache_dir)
            say(f"[A] passed in {time.monotonic() - t0:.1f}s")
            t0 = time.monotonic()
            phase_b(work, args, cache_dir)
            say(f"[B] passed in {time.monotonic() - t0:.1f}s")
        if args.chips == 4:
            device = probe_device(env)  # every child has exited
        else:
            import jax  # the parent holds the device since phase B

            d = jax.devices()
            device = {"platform": d[0].platform, "kind": d[0].device_kind,
                      "count": len(d)}
        result = {"ok": True, "device": device}
        if args.rehearse_cpu:
            result["rehearsal"] = True
        rc = 0
    except Exception as e:  # the boundary: report, clean up, exit non-zero
        traceback.print_exc()
        say(f"FAILED: {e}")
        result = {"ok": False, "error": f"{type(e).__name__}: {e}"[:500],
                  "device": device}
        rc = 1
    finally:
        for p in _children:
            if p.poll() is None:
                p.kill()
                p.wait()
        if work.exists():
            # Server logs outlive the work dir (chiprun brings this back).
            logs = ROOT / "chiprun_out" / "smoke_logs"
            logs.mkdir(parents=True, exist_ok=True)
            for f in work.glob("*.log"):
                shutil.copy(f, logs / f.name)
            shutil.rmtree(work, ignore_errors=True)
    say(f"total {time.monotonic() - t_start:.1f}s")
    print(json.dumps(result), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
