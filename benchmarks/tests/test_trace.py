"""The reduction from trace to busy time, per-program time and the
breakdown: on a hand-made trace, and on a small one recorded on the chip."""

import json
from pathlib import Path

from harness import trace as tr

DATA = Path(__file__).resolve().parent / "data"
PROGRAMS = {"decode": ["jit__decode"], "prefill": ["jit__prefill"]}


def flat(ops, mods, span):
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": tr.MODULES_LINE, "events": mods},
        {"name": tr.OPS_LINE, "events": ops}]}], "span_ns": span}


def test_hand_made_trace():
    ms = 1e6
    mods = [["jit__decode_greedy(1)", 0 * ms, 10 * ms],
            ["jit__prefill_one_chunk(2)", 14 * ms, 6 * ms],
            ["jit__decode_greedy(3)", 25 * ms, 10 * ms]]
    ops = [["%while.1 = (s32[]) while(...)", 0 * ms, 10 * ms],
           ["%fusion.2 = bf16[8,4096]{1,0} fusion(...)", 1 * ms, 4 * ms],  # nested
           ["%fusion.9 = bf16[128,4096]{1,0} fusion(...)", 14 * ms, 6 * ms],
           ["%while.1 = (s32[]) while(...)", 25 * ms, 10 * ms]]
    s = tr.reduce(flat(ops, mods, [0.0, 40 * ms]), PROGRAMS)
    assert s.devices == 1 and abs(s.window_s - 0.040) < 1e-12
    assert abs(s.busy_s - 0.026) < 1e-12  # nested ops are not counted twice
    assert s.programs["decode"] == {"seconds": 0.020, "executions": 2}
    assert s.programs["prefill"]["executions"] == 1
    assert s.device_ops[0] == ["%while.1", 0.020]
    assert ["%fusion.2 bf16[8,4096]", 0.004] in s.device_ops
    gaps = dict(map(tuple, s.idle_gaps))
    assert abs(gaps["before jit__prefill_one_chunk"] - 0.004) < 1e-12
    assert abs(gaps["before jit__decode_greedy"] - 0.005) < 1e-12


def test_no_device_plane_reads_nothing():
    s = tr.reduce({"planes": [], "span_ns": [0.0, 1e9]}, PROGRAMS)
    assert s.devices == 0 and s.busy_s == 0.0 and not s.programs


def test_recorded_trace():
    fx = json.loads((DATA / "trace_v5e_decode.json").read_text())
    s = tr.reduce(fx, PROGRAMS)
    assert s.devices == 1 and abs(s.window_s - 2.815480022) < 1e-9
    assert s.programs["decode"]["executions"] == 74
    assert abs(s.programs["decode"]["seconds"] - 1.913204936) < 1e-9
    assert s.programs["prefill"] == {"seconds": 0.223003036, "executions": 13}
    # Busy time against a brute-force raster of the same events (1 us cells).
    ops = fx["planes"][0]["lines"][1]["events"]
    lo = min(e[1] for e in ops)
    cells = set()
    for _n, st, du in ops:
        cells.update(range(int((st - lo) // 1000), int((st + du - lo) // 1000)))
    assert abs(s.busy_s - len(cells) * 1e-6) < 3e-4
    assert s.device_ops[0][0] == "%while.3" and len(s.device_ops) <= 10
    assert all(len(n) <= 96 for n, _ in s.device_ops)
