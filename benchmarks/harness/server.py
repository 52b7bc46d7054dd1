"""The system under test as its manifests start it: one
`python -m tpumlops.server` child holding the chip, driven over HTTP.

Copied from `chip_smoke.py`'s `ServerChild` (proven on the chip in PR 21),
with the serving spec read from the configuration's file.  The child is
entered through `serve.py`, which runs the program's `__main__` with the
profiler's captures placed under `profile_root` (see there)."""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

from .manifest import ROOT

NAMESPACE = "bench"
BOOT_TIMEOUT_S = 1100.0  # a cold full-width warm-up sweep is minutes


class ServerFailure(Exception):
    pass


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


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class ServerChild:
    def __init__(self, uri: str, serving: dict, cache_dir: str, log: Path,
                 platform: str, profile_root: Path, root: Path = ROOT):
        from tpumlops.operator.builder import build_deployment
        from tpumlops.utils.config import OperatorConfig

        self.model_name = serving["model_name"]
        tpu = dict(serving["tpu"])
        tpu["tpuTopology"] = serving["topology"]
        tpu["compileCacheDir"] = cache_dir
        if platform == "cpu":
            # The CPU reports no memory and has no peaks row.
            obs = dict(tpu.get("observability") or {})
            obs["deviceTelemetry"] = False
            tpu["observability"] = obs
        cfg = OperatorConfig.from_spec({
            "modelName": self.model_name, "modelAlias": "prod",
            "backend": "tpu", "tpu": tpu,
        })
        sd = build_deployment(self.model_name, NAMESPACE, "bench", cfg, "1", uri, 100)
        container = sd["spec"]["predictors"][0]["componentSpecs"][0][
            "spec"]["containers"][0]
        self.port = free_port()
        self.cmd = [sys.executable, str(Path(__file__).with_name("serve.py")),
                    *container["args"],
                    "--host", "127.0.0.1", "--port", str(self.port),
                    "--metrics-port", "0", "--drain-s", "0.5"]
        env = dict(os.environ)
        for e in container["env"]:
            if e["name"] != "TPU_TOPOLOGY" and "value" in e:
                env[e["name"]] = e["value"]
        env["JAX_PLATFORMS"] = platform
        env["BENCH_PROFILE_ROOT"] = str(profile_root)
        env["PYTHONPATH"] = str(root) + os.pathsep + env.get("PYTHONPATH", "")
        self.env, self.log, self.root = env, log, root
        self.base = f"http://127.0.0.1:{self.port}"
        self.proc: subprocess.Popen | None = None
        self.boot_s = 0.0

    def start(self, timeout: float = BOOT_TIMEOUT_S) -> None:
        if "jax" in sys.modules:
            raise ServerFailure(
                "the parent has imported jax: it may hold the chip this "
                "child needs (one process per chip)"
            )
        t0 = time.monotonic()
        with open(self.log, "w") as fh:
            self.proc = subprocess.Popen(
                self.cmd, cwd=self.root, env=self.env, stdout=fh,
                stderr=subprocess.STDOUT,
            )
        while time.monotonic() - t0 < timeout:
            if self.proc.poll() is not None:
                raise ServerFailure(
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
        raise ServerFailure(f"server not ready after {timeout:.0f}s\n{self.log_tail()}")

    def log_tail(self, n: int = 40) -> str:
        try:
            lines = self.log.read_text(errors="replace").splitlines()
        except OSError:
            return "      | (no log)"
        return "\n".join("      | " + line for line in lines[-n:])

    @property
    def generate_url(self) -> str:
        return f"{self.base}/v2/models/{self.model_name}/generate"

    def device(self) -> dict | None:
        code, raw = http(self.base + "/debug/device")
        return json.loads(raw) if code == 200 else None

    def terminate(self, grace: float = 60.0) -> float:
        """SIGTERM, as kubelet does; the drain must finish by itself."""
        t0 = time.monotonic()
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=grace)
        except subprocess.TimeoutExpired:
            self.kill()
            raise ServerFailure(
                f"server still alive {grace:.0f}s after SIGTERM\n{self.log_tail()}"
            ) from None
        if rc != 0:
            raise ServerFailure(f"server exit code {rc}\n{self.log_tail()}")
        return time.monotonic() - t0

    def kill(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()


def compile_totals(dev: dict | None) -> dict:
    """Backend compiles and persistent-cache outcomes so far, from the
    compile observatory of `/debug/device` (empty without telemetry)."""
    if dev is None:
        return {}
    ops = dev["compile"]["ops"]
    return {
        "compiles": sum(o["compiles"] for o in ops.values()),
        "hits": sum(o["cache_hits"] for o in ops.values()),
        "misses": sum(o["cache_misses"] for o in ops.values()),
        "warmup": dev["compile"]["warmup"],
    }
