"""The server child's entry places the profiler's captures under the
run's own directory, whatever path the program names."""

import os

import jax.profiler

from harness import serve


def test_captures_are_rerooted_under_the_runs_directory(tmp_path, monkeypatch):
    asked = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, *a, **k: asked.append((d, a, k)))
    root = tmp_path / "profile"
    serve.place_profiles(str(root))
    jax.profiler.start_trace("/tmp/tpumlops-profile/m-1700000000")
    jax.profiler.start_trace(str(root / "already" / "inside"), create_perfetto_link=False)
    jax.profiler.start_trace("relative/dir")
    assert asked[0] == (str(root / "tmp/tpumlops-profile/m-1700000000"), (), {})
    assert asked[1] == (str(root / "already" / "inside"), (), {"create_perfetto_link": False})
    assert asked[2][0] == str(root) + os.path.abspath("relative/dir")
    assert all(d.startswith(str(root) + os.sep) for d, _a, _k in asked)
