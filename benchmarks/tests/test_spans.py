"""Span arithmetic on two synthetic scrapes of `/metrics`."""

from harness import prom, spans
from harness.context import Context

IDENT = 'deployment_name="d",namespace="n",predictor_name="p"'


def scrape(rows: dict[str, tuple[int, float, float]], prefill_tokens=None) -> dict:
    """{span: (count, total_s, self_s)} -> parsed exposition text."""
    lines = []
    for name, (n, total, own) in rows.items():
        lab = "{" + IDENT + f',span="{name}"' + "}"
        lines.append(f"tpumlops_spans_total{lab} {n}")
        lines.append(f"tpumlops_span_seconds_total{lab} {total}")
        lines.append(f"tpumlops_span_self_seconds_total{lab} {own}")
    if prefill_tokens is not None:
        lines.append(f"tpumlops_prefill_tokens_total{{{IDENT}}} {prefill_tokens}")
    return prom.parse("\n".join(lines))


# 100 steps in the window: a pass of 30 ms, of which 25 ms blocked on the
# decode read-back, 1 ms on a prefill sync, 3.5 ms in the other phases and
# 0.5 ms in no phase; 2 s waiting for traffic besides.  The first scrape
# holds what warm-up left.
BEFORE = scrape({
    "engine.iteration": (10, 1.0, 0.1),
    "engine.wait_work": (2, 0.5, 0.5),
    "engine.decode_readback": (10, 0.3, 0.3),
    "engine.decode_dispatch": (10, 0.05, 0.05),
}, prefill_tokens=1000)
AFTER = scrape({
    "engine.iteration": (112, 1.0 + 3.0 + 2.0, 0.1 + 0.05),
    "engine.wait_work": (4, 0.5 + 2.0, 0.5 + 2.0),
    "engine.admit": (102, 0.1 + 2.0, 0.1),
    "engine.prefill_dispatch": (10, 0.02, 0.02),
    "engine.prefill_sync": (10, 0.1, 0.1),
    "engine.decode_assemble": (100, 0.03, 0.03),
    "engine.decode_dispatch": (110, 0.05 + 0.08, 0.05 + 0.08),
    "engine.decode_readback": (110, 0.3 + 2.5, 0.3 + 2.5),
    "engine.emit": (100, 0.07, 0.07),
    "engine.journal": (100, 0.05, 0.05),
}, prefill_tokens=1000 + 45000)


def test_period_host_and_cover():
    d = spans.deltas(BEFORE, AFTER)
    assert d["engine.decode_readback"]["n"] == 100
    assert abs(spans.loop_period_ms(d) - 30.0) < 1e-9
    assert abs(spans.loop_host_ms(d) - 4.0) < 1e-9  # 30 - 25 - 1
    assert abs(spans.covered_pct(d) - 99.0) < 1e-9  # 0.05 s of 5.0 s in no phase
    line = spans.table(d)
    assert "decode_readback 25.000" in line and "uninstrumented 0.500" in line
    assert "period 30.000, host 4.000" in line


def test_a_program_without_the_counters_reads_nothing():
    old = prom.parse('tpumlops_tick_seconds_count{kind="decode"} 5')
    assert spans.deltas(old, old) is None
    assert spans.deltas(BEFORE, BEFORE) is None  # and a window with no step


def test_readers_note_the_table_once_and_read_the_prefill_counter():
    from harness import manifest

    ctx = Context(None, 45.0, [], BEFORE, AFTER, None, None, None)
    period = manifest.load_layer_metric("loop_period_ms").compute(ctx)
    host = manifest.load_layer_metric("loop_host_ms.saturated").compute(ctx)
    assert abs(period - 30.0) < 1e-9 and abs(host - 4.0) < 1e-9
    assert len(ctx.notes) == 1 and ctx.notes[0].startswith("engine loop")
    rate = manifest.load_layer_metric("prefill_tokens_per_s").compute(ctx)
    assert abs(rate - 1000.0) < 1e-9
    bare = Context(None, 45.0, [], {}, {}, None, None, None)
    for name in ("loop_period_ms", "loop_period_ms.saturated", "loop_host_ms",
                 "loop_host_ms.saturated", "prefill_tokens_per_s"):
        assert manifest.load_layer_metric(name).compute(bare) is None
    assert bare.notes == []
