"""chip_smoke.py's contract off the chip, and the compile-cache resolver.

The smoke itself only means something on a TPU (the builder runs it
through the chip tool); here: without an accelerator and without the
explicit rehearsal option it must fail fast, before any model exists,
with a parseable last line — never fall back to the CPU.
"""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from tpumlops.utils import compile_cache as cc

REPO = Path(__file__).resolve().parent.parent


def _run_smoke(cwd: Path, script: Path, env_extra: dict):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(env_extra)
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, str(script)], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120,
    )
    return proc, time.monotonic() - t0


def test_without_accelerator_fails_fast_with_ok_false():
    proc, wall = _run_smoke(REPO, REPO / "chip_smoke.py",
                            {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert wall < 30, f"took {wall:.1f}s: must fail before loading a model"
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert "rehearse-cpu" in last["error"]  # names the explicit way
    assert not (REPO / ".smoke_work").exists()  # nothing was made


def test_alone_in_a_directory_fails_plainly(tmp_path):
    """A directory holding chip_smoke.py and nothing else of the repo."""
    script = tmp_path / "chip_smoke.py"
    script.write_bytes((REPO / "chip_smoke.py").read_bytes())
    proc, _ = _run_smoke(tmp_path, script, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and "package" in last["error"]


@pytest.mark.parametrize(
    "env, requested, expected",
    [
        # placed from outside: the variable wins over flag and default
        ("/x/cache", None, "/x/cache"),
        ("/x/cache", "/x/cache", "/x/cache"),
        ("/x/cache", "/other", "/x/cache"),
        # unset: the flag, else the one fixed in-checkout path
        (None, "/flag/dir", "/flag/dir"),
        (None, None, str(REPO / ".jax_compile_cache")),
        # an EMPTY variable is unset (pods with compileCacheDir: null)
        ("", None, str(REPO / ".jax_compile_cache")),
        # explicit "" = no persistent cache, whatever the variable says
        ("/x/cache", "", ""),
        (None, "", ""),
    ],
)
def test_compile_cache_resolver(monkeypatch, env, requested, expected):
    if env is None:
        monkeypatch.delenv(cc.CACHE_DIR_ENV, raising=False)
    else:
        monkeypatch.setenv(cc.CACHE_DIR_ENV, env)
    assert cc.resolve_compile_cache_dir(requested) == expected


def test_default_cache_dir_is_fixed_and_gitignored():
    """No pid, time or tempdir in the path (the path is part of jax's
    cache key), and git never commits what lands there."""
    assert cc.DEFAULT_CACHE_DIR == str(REPO / ".jax_compile_cache")
    assert ".jax_compile_cache/" in (REPO / ".gitignore").read_text().split()
