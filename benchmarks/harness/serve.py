"""The server child's entry: `python -m tpumlops.server <args>`, with the
profiler's captures placed inside the run's work directory.

The program's `POST /debug/profile` writes to the fixed path
`/tmp/tpumlops-profile/<model>-<second>` and then deletes older captures
there: two checkouts measured side by side would meet in it.  The
benchmark may not change the program, so this entry re-roots whatever
directory `jax.profiler.start_trace` is handed under `BENCH_PROFILE_ROOT`
(a directory of this run alone, under `$TMPDIR`) and then runs the
program's own `__main__` unchanged.  A directory already inside that root
is left as it is, so a program that learns to place its captures needs no
change here.  Nothing else of jax or of the program is touched.
"""

import os
import runpy
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
# This file's directory holds `trace.py`, `server.py`...: keep them from
# shadowing the standard library in the server's process.
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]


def place_profiles(root: str) -> None:
    import jax.profiler

    root = os.path.abspath(root)
    real = jax.profiler.start_trace

    def start_trace(log_dir, *args, **kwargs):
        d = os.path.abspath(os.fspath(log_dir))
        if os.path.commonpath([d, root]) != root:
            d = os.path.join(root, d.lstrip(os.sep))
        return real(d, *args, **kwargs)

    jax.profiler.start_trace = start_trace


if __name__ == "__main__":
    place_profiles(os.environ["BENCH_PROFILE_ROOT"])
    runpy.run_module("tpumlops.server", run_name="__main__", alter_sys=True)
