"""The whole command at tiny size on the CPU, in a throw-away copy that
adds a cell, a mix, a configuration and a per-layer metric as files: no
harness file is touched.  Also the control (int4 in the program's place
must come out of the same comparison as not correct) and a run with the
timed path broken underneath (a served token altered where it is
produced)."""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from tree import make_tree

FAULT = '''
import os, sys
if os.environ.get("BENCH_FAULT") == "token" and "harness/serve.py" in " ".join(sys.orig_argv):
    from tpumlops.server import generation as g

    _orig = g.GenerationEngine._record_token
    _n = [0]

    def _bad(self, slot_idx, token, t=None):
        _n[0] += 1
        if not self._in_warmup and _n[0] % 7 == 0:
            token = (int(token) + 1) % int(self._cfg.vocab_size)
        return _orig(self, slot_idx, token, t)

    g.GenerationEngine._record_token = _bad
'''


def run(tree, *extra, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.pop("JAX_COMPILATION_CACHE_DIR", None)
    e.update(env or {})
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "tiny-cell",
         "--seconds", "4", *extra],
        cwd=tree, env=e, capture_output=True, text=True, timeout=600)
    err = "\n".join(l for l in p.stderr.splitlines() if "cpu_aot_loader" not in l)
    return p.returncode, p.stdout.strip().splitlines(), err


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return make_tree(tmp_path_factory.mktemp("open"))


def test_added_cell_runs_traced(tree, tmp_path):
    t0 = time.time()
    rc, out, err = run(tree, "--seed", str(2**31 + 17), "--trace", "1",
                       "--rehearse-cpu", env={"TMPDIR": str(tmp_path)})
    assert rc == 0, err[-3000:]
    # The capture went under the run's own work directory (in $TMPDIR, gone
    # at exit), not to the program's fixed /tmp/tpumlops-profile.
    shared = Path("/tmp/tpumlops-profile")
    assert not shared.is_dir() or not [
        d for d in shared.glob("tiny-*") if d.stat().st_mtime >= t0 - 1]
    assert not list(tmp_path.iterdir())
    res = json.loads(out[-1])
    assert list(res)[-1] == "checks" and res["correct"] is True, out[-1]
    assert res["attempted"] > 10 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    m = res["metrics"]
    # The reader added as a file was found; end-to-end names stay out of a traced line.
    # (it sees the window up to the capture, so fewer than `attempted`).
    assert 0 < m["window_requests"]["value"] <= res["attempted"]
    assert "decode_tick_ms" in m and "tpot_p90_ms" not in m
    # No device in the trace: shares of a roofline are left out, never 0.
    assert "decode_roofline" not in m and "device_idle_pct.decode" not in m
    # Every number compared is on stderr's last lines beside its limit.
    tail = err.splitlines()[-8:]
    assert any(l.startswith("check max_logit_gap: value") for l in tail), tail
    c = res["checks"]
    for name in ("max_logit_gap", "mean_logit_gap"):
        assert c[name]["value"] <= c[name]["limit"], c
    # Nothing is left in the tree but the compile cache.
    left = {p.name for p in (tree / "benchmarks").iterdir()} - {
        "run.py", "sweep.py", "harness", "configs", "traffic", "cells",
        "layer_metrics", "references", "tests", "README.md", ".gitignore",
        ".cache"}
    assert not left, left


def test_control_in_the_programs_place_is_not_correct(tree):
    rc, out, err = run(tree, "--seed", "6", "--trace", "0", "--control", "1",
                       "--rehearse-cpu")
    assert rc == 0, err[-3000:]
    res = json.loads(out[-1])
    # Through the harness's own `correct`, not a comparison made here.
    assert res["correct"] is False, out[-1]
    assert err.splitlines()[-1].startswith("correct: False")
    c = res["checks"]
    assert all(c[n]["value"] == 0 for n in
               ("requests_incomplete", "stream_mismatch", "compiles_in_window"))
    assert c["mean_logit_gap"]["value"] > c["mean_logit_gap"]["limit"]
    assert c["max_logit_gap"]["value"] > c["max_logit_gap"]["limit"]


def test_altered_token_is_not_correct(tree, tmp_path):
    (tmp_path / "sitecustomize.py").write_text(FAULT)
    rc, out, err = run(tree, "--seed", "5", "--trace", "0", "--rehearse-cpu",
                       env={"BENCH_FAULT": "token", "PYTHONPATH": str(tmp_path)})
    assert rc == 0, err[-3000:]
    res = json.loads(out[-1])
    assert res["correct"] is False, out[-1]
    c = res["checks"]["max_logit_gap"]
    assert c["value"] > c["limit"]
    assert set(res["metrics"]) == {"tpot_p90_ms", "setup_s"}


def test_closed_loop_cell(tmp_path):
    t = make_tree(tmp_path / "closed", loop="closed")
    rc, out, err = run(t, "--seed", "9", "--trace", "0", "--rehearse-cpu")
    assert rc == 0, err[-3000:]
    res = json.loads(out[-1])
    assert res["correct"] is True, out[-1]
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    assert res["metrics"]["tokens_per_s"]["value"] > 0


def test_no_accelerator_fails_before_measuring(tree):
    rc, out, err = run(tree, "--seed", "1", "--trace", "0")
    assert rc != 0 and not any(l.startswith("{") for l in out)


def test_alone_in_a_directory_fails(tmp_path):
    t = make_tree(tmp_path / "alone")
    for name in os.listdir(t):
        if name not in ("BENCHMARK.json", "benchmarks"):
            os.unlink(t / name)
    rc, out, err = run(t, "--seed", "1", "--trace", "0", "--rehearse-cpu")
    assert rc != 0 and not any(l.startswith("{") for l in out)
