"""Prometheus metrics with Seldon-executor-compatible identity.

The promotion gate queries exactly these series (``mlflow_operator.py``):

- ``seldon_api_executor_client_requests_seconds`` histogram — p95 latency
  (``:367``), mean latency Δsum/Δcount (``:393-404``), request count (``:407``);
- ``seldon_api_executor_server_requests_seconds_count`` with a ``code``
  label — error counting via ``code!="200"`` (``:375``) and a ``service``
  label for feedback requests (``:410``);

all keyed by ``{deployment_name, predictor_name, namespace}`` (``:367``).
Emitting the same names and labels means the reference's PromQL — and our
gate, which preserves it — works against this server unmodified (SURVEY §7
hard part 4: metric identity).

Beyond gate compatibility the server exports first-party TPU series
(``tpumlops_*``): batch sizes, queue latency, compile counts.
"""

from __future__ import annotations

from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    generate_latest,
)
from prometheus_client.core import CounterMetricFamily

from ..utils.tracing import Tracer

# Latency SLOs live in the 1ms-10s range on TPU; buckets chosen to resolve
# p95/p99 there.
_LATENCY_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
)


class _SpanCollector:
    """``tpumlops_span_*`` and ``tpumlops_device_starved_*``: the
    tracer's per-name stats and the engine's starvation account
    (``tracer.account("device_starved")``) rendered when ``/metrics`` is
    scraped, so neither costs the hot path a prometheus call."""

    def __init__(self, tracer: Tracer, identity: dict[str, str]):
        self._tracer = tracer
        self._identity = identity

    def collect(self):
        labels = [*self._identity, "span"]
        seconds = CounterMetricFamily(
            "tpumlops_span_seconds",
            "Host time inside spans of this name (children included)",
            labels=labels,
        )
        self_seconds = CounterMetricFamily(
            "tpumlops_span_self_seconds",
            "Host time inside spans of this name that no child span "
            "on the same thread covered",
            labels=labels,
        )
        count = CounterMetricFamily(
            "tpumlops_spans", "Spans of this name closed", labels=labels
        )
        for name, s in sorted(self._tracer.stats().items()):
            values = [*self._identity.values(), name]
            seconds.add_metric(values, s.total_s)
            self_seconds.add_metric(values, s.self_s)
            count.add_metric(values, s.count)
        return [seconds, self_seconds, count, *self._starved()]

    def _starved(self):
        ident = list(self._identity.values())
        seconds = CounterMetricFamily(
            "tpumlops_device_starved_seconds",
            "Engine-thread time between seeing the last dispatched tick "
            "program end and handing the device the next, by the kind of "
            "program dispatched at the interval's end; time waiting for "
            "traffic (engine.wait_work) is in no interval",
            labels=[*self._identity, "before"],
        )
        intervals = CounterMetricFamily(
            "tpumlops_device_starved_intervals",
            "Such intervals closed, by the kind of program dispatched at "
            "their end",
            labels=[*self._identity, "before"],
        )
        by_span = CounterMetricFamily(
            "tpumlops_device_starved_by_span_seconds",
            "The same seconds by the engine.* span whose self time covered "
            "them: what the host was doing while the chip had nothing",
            labels=[*self._identity, "span"],
        )
        account = self._tracer.account("device_starved")
        for before, (s, n) in sorted(account.by_label.copy().items()):
            seconds.add_metric([*ident, before], s)
            intervals.add_metric([*ident, before], n)
        for name, s in sorted(account.by_span.copy().items()):
            by_span.add_metric([*ident, name], s)
        return [seconds, intervals, by_span]


class ServerMetrics:
    def __init__(
        self,
        deployment_name: str,
        predictor_name: str,
        namespace: str,
        device_telemetry: bool = False,
    ):
        self.registry = CollectorRegistry()
        self.identity = {
            "deployment_name": deployment_name,
            "predictor_name": predictor_name,
            "namespace": namespace,
        }
        ident_labels = list(self.identity)
        # The server's one tracer (utils/tracing.py): the engine loop's
        # ``engine.*`` spans, with the profiler sink.  Read by
        # ``/debug/spans`` and by the collector below.
        self.tracer = Tracer(profiler=True)
        self.spans = _SpanCollector(self.tracer, self.identity)
        self.registry.register(self.spans)

        self.client_requests = Histogram(
            "seldon_api_executor_client_requests_seconds",
            "Inference request latency (gate-compatible identity)",
            ident_labels,
            buckets=_LATENCY_BUCKETS,
            registry=self.registry,
        )
        # Histogram, NOT Counter: the gate's PromQL reads the ``_count``
        # series (``seldon_api_executor_server_requests_seconds_count``,
        # mlflow_operator.py:375,:383,:410); a Counter would export
        # ``_total`` and every error query would silently read 0 through
        # the ``or on() vector(0)`` fallback.
        self.server_requests = Histogram(
            "seldon_api_executor_server_requests_seconds",
            "Request durations by HTTP code (gate queries _count with code!='200')",
            ident_labels + ["code", "service"],
            buckets=_LATENCY_BUCKETS,
            registry=self.registry,
        )
        self.batch_size = Histogram(
            "tpumlops_batch_size",
            "Dynamic-batcher batch sizes",
            ident_labels,
            buckets=(1, 2, 4, 8, 16, 32, 64, 128),
            registry=self.registry,
        )
        self.queue_seconds = Histogram(
            "tpumlops_queue_seconds",
            "Time requests spend in the batching queue",
            ident_labels,
            buckets=_LATENCY_BUCKETS,
            registry=self.registry,
        )
        # Device dispatch wall per batch: with queue_seconds and the
        # request histogram this decomposes server-observed latency into
        # queue wait + device run + server overhead (JSON, HTTP, glue);
        # the overhead term does not depend on the device (VERDICT r2 #7).
        self.batch_run_seconds = Histogram(
            "tpumlops_batch_run_seconds",
            "run_batch (device dispatch) wall time per executed batch",
            ident_labels,
            buckets=_LATENCY_BUCKETS,
            registry=self.registry,
        )
        # Pipelined mode only: time a dispatched batch waited behind its
        # predecessor's device run before its own materialize began.
        # Without this term the wait pools into the residual "overhead"
        # (total - queue - run), misreading pipeline occupancy as server
        # glue cost.
        self.pipeline_wait_seconds = Histogram(
            "tpumlops_pipeline_wait_seconds",
            "Wait behind the previous in-flight batch before materialize",
            ident_labels,
            buckets=_LATENCY_BUCKETS,
            registry=self.registry,
        )
        self.compilations = Counter(
            "tpumlops_compilations_total",
            "XLA compilations triggered (by bucket signature)",
            ident_labels,
            registry=self.registry,
        )
        self.generated_tokens = Counter(
            "tpumlops_generated_tokens_total",
            "Tokens produced by the continuous-batching generation engine",
            ident_labels,
            registry=self.registry,
        )
        self.decode_batch = Histogram(
            "tpumlops_decode_batch_size",
            "Active slots per continuous-batching decode step",
            ident_labels,
            buckets=(1, 2, 4, 8, 16, 32, 64),
            registry=self.registry,
        )
        # Prefix KV cache (server/prefix_cache.py): the promotion gate's
        # operator can watch hit rate / cached-token volume per predictor
        # to judge whether a canary inherits the production prefix mix.
        self.prefill_tokens = Counter(
            "tpumlops_prefill_tokens_total",
            "Real (unpadded) prompt tokens whose K/V a prefill dispatch "
            "wrote; cached-prefix tokens count in "
            "tpumlops_prefix_cache_cached_tokens instead",
            ident_labels,
            registry=self.registry,
        )
        # How often an admission's chunk went out behind the pass's
        # decode step, before anything was read back: ahead / (ahead +
        # in_turn) is the share of chunks the chip did not idle in front of.
        self.prefill_dispatch = Counter(
            "tpumlops_prefill_dispatch_total",
            "Prefill chunk programs of the single-admission path by where "
            "the engine dispatched them: ahead (right behind the pass's "
            "decode step, before its read-back) or in_turn (in the admit "
            "phase)",
            ident_labels + ["when"],
            registry=self.registry,
        )
        # How often a plain decode step went out behind the one before it,
        # before that one was read back: ahead / (ahead + in_turn) is the
        # share of steps the chip did not idle in front of.
        self.decode_dispatch = Counter(
            "tpumlops_decode_dispatch_total",
            "Plain decode step programs by where the engine dispatched "
            "them: ahead (behind the step still in flight, before its "
            "read-back) or in_turn (with no step in flight)",
            ident_labels + ["when"],
            registry=self.registry,
        )
        self.prefill_key_blocks = Counter(
            "tpumlops_prefill_key_blocks_total",
            "Key blocks of the cache's capacity, summed over the "
            "full-attention layers of a family whose prefill core walks "
            "the written ones: those a dispatched chunk multiplied "
            "(walked) and those it did not reach (skipped); host "
            "arithmetic from the chunk's offset",
            ident_labels + ["kind"],
            registry=self.registry,
        )
        # What the attention cores of a family that says so multiply
        # against what its causal and window masks keep, by program and
        # layer kind: attended / computed is the share of the products
        # that count.
        self.attn_pairs_computed = Counter(
            "tpumlops_attn_pairs_computed_total",
            "(query, key) pairs the attention cores multiplied for real "
            "query rows, a pair a layer summed over the layers of the kind; "
            "host arithmetic from the call's shapes, trip count and "
            "lengths at its dispatch",
            ident_labels + ["program", "kind"],
            registry=self.registry,
        )
        self.attn_pairs_attended = Counter(
            "tpumlops_attn_pairs_attended_total",
            "Of those pairs, the ones inside the causal and window masks",
            ident_labels + ["program", "kind"],
            registry=self.registry,
        )
        # Routed-expert traffic of a sparse-expert family, by program
        # (prefill | decode): assignments / activations is the mean
        # number of tokens an expert that was read got to work on;
        # assignments / (row_tile_visits x row_tile_rows) is how full the
        # row tiles are that the grouped matmuls multiply.
        self.moe_assignments = Counter(
            "tpumlops_moe_assignments_total",
            "(token, expert) pairs routed to an expert this replica "
            "holds (every pair where it holds them all: real tokens x "
            "experts per token x expert layers), counted on the device",
            ident_labels + ["program"],
            registry=self.registry,
        )
        self.moe_assignments_routed_away = Counter(
            "tpumlops_moe_assignments_routed_away_total",
            "(token, expert) pairs routed to an expert held elsewhere "
            "(an expert share): left out of this replica's result",
            ident_labels + ["program"],
            registry=self.registry,
        )
        # Indexed sparse attention: selected / scored is how sparse the
        # traffic made the attention of the full-attention layers.
        self.dsa_keys_scored = Counter(
            "tpumlops_dsa_keys_scored_total",
            "Cached positions the indexers scored, summed over real "
            "query rows and indexed layers (the finite index scores, "
            "counted on the device)",
            ident_labels + ["program"],
            registry=self.registry,
        )
        self.dsa_keys_selected = Counter(
            "tpumlops_dsa_keys_selected_total",
            "Cached positions the selection kept for the softmax, "
            "counted on the device from what it kept (min(position + 1, "
            "index_topk) a query a layer while it works)",
            ident_labels + ["program"],
            registry=self.registry,
        )
        # Recurrent state of a linear-attention family: tokens / passes is
        # how many real tokens a pass over a row's state folds into it.
        self.gdn_tokens = Counter(
            "tpumlops_gdn_tokens_total",
            "Real tokens folded into a recurrent state, summed over the "
            "linear-attention layers, counted on the device from the "
            "call's validity mask",
            ident_labels + ["program"],
            registry=self.registry,
        )
        self.gdn_state_passes = Counter(
            "tpumlops_gdn_state_passes_total",
            "Rows whose recurrent state a program call read and wrote, "
            "summed over the linear-attention layers, counted on the "
            "device from the call's validity mask",
            ident_labels + ["program"],
            registry=self.registry,
        )
        self.cache_state_bytes = Gauge(
            "tpumlops_cache_state_bytes",
            "Bytes of recurrent state one cache slot holds whatever its "
            "length (a linear-attention family: the float32 state and "
            "the convolution's carried rows of every such layer), beside "
            "the bytes a position of the HBM ledger's cache row",
            ident_labels,
            registry=self.registry,
        )
        self.moe_expert_activations = Counter(
            "tpumlops_moe_expert_activations_total",
            "(program call, layer, expert) triples in which the expert "
            "got at least one real token, counted on the device",
            ident_labels + ["program"],
            registry=self.registry,
        )
        self.moe_row_tile_visits = Counter(
            "tpumlops_moe_row_tile_visits_total",
            "(program call, layer, expert, row tile) visits of the grouped "
            "matmuls' schedule (the three matmuls of a layer share it), "
            "counted on the device from the group sizes",
            ident_labels + ["program"],
            registry=self.registry,
        )
        self.moe_row_tile_rows = Gauge(
            "tpumlops_moe_row_tile_rows",
            "Rows a visit multiplies in the program's last call: static, "
            "from the call's token copies and expert count",
            ident_labels + ["program"],
            registry=self.registry,
        )
        # The engine thread stamps each streamed token as it hands it to
        # the event loop; the SSE writer observes now - stamp after the
        # event's write returns.  With the engine.* spans' maxima this
        # tells an event-loop stall from an engine stall.
        self.emit_lag = Histogram(
            "tpumlops_emit_lag_seconds",
            "Engine on_token stamp to the SSE event's write returning",
            ident_labels,
            buckets=_LATENCY_BUCKETS,
            registry=self.registry,
        )
        self.prefix_cache_hits = Counter(
            "tpumlops_prefix_cache_hits",
            "Admissions that reused a radix-cached prompt prefix",
            ident_labels,
            registry=self.registry,
        )
        self.prefix_cache_cached_tokens = Counter(
            "tpumlops_prefix_cache_cached_tokens",
            "Prompt tokens served from the prefix KV cache (prefill skipped)",
            ident_labels,
            registry=self.registry,
        )
        self.prefix_cache_evictions = Counter(
            "tpumlops_prefix_cache_evictions",
            "Prefix-cache chunks evicted under the byte budget (LRU)",
            ident_labels,
            registry=self.registry,
        )
        # Second-tier (host-RAM) prefix cache (prefixCache.l2BudgetMB):
        # chunks the first tier evicted that were caught, re-promoted,
        # or aged out of the L2 pool.  Registered unconditionally like
        # the L1 family — children appear only when the tier is on.
        self.prefix_cache_l2_hits = Counter(
            "tpumlops_prefix_cache_l2_hits",
            "Radix-walk misses served by the second-tier host-RAM pool "
            "(chunk promoted back into the tree)",
            ident_labels,
            registry=self.registry,
        )
        self.prefix_cache_l2_spills = Counter(
            "tpumlops_prefix_cache_l2_spills",
            "First-tier evictions caught by the second-tier pool",
            ident_labels,
            registry=self.registry,
        )
        self.prefix_cache_l2_evictions = Counter(
            "tpumlops_prefix_cache_l2_evictions",
            "Chunks aged out of the second-tier pool (LRU byte budget)",
            ident_labels,
            registry=self.registry,
        )
        # Engine occupancy telemetry (fed per decode tick from the
        # engine's on_step callback): lets the operator correlate
        # speculative acceptance — and every other per-tick rate — with
        # batch occupancy and admission backlog.
        self.engine_active_slots = Gauge(
            "tpumlops_engine_active_slots",
            "Occupied decode slots at the most recent engine tick",
            ident_labels,
            registry=self.registry,
        )
        self.engine_queue_depth = Gauge(
            "tpumlops_engine_queue_depth",
            "Requests queued but NOT yet admitted (excludes in-flight "
            "admissions — see tpumlops_engine_admitting)",
            ident_labels,
            registry=self.registry,
        )
        # Separate from queue depth so saturation alerts (queue grows)
        # and admission-latency alerts (admissions in flight pile up
        # behind long prefills) stop conflating the two populations.
        self.engine_admitting = Gauge(
            "tpumlops_engine_admitting",
            "Admissions mid-prefill (dequeued, no first token yet)",
            ident_labels,
            registry=self.registry,
        )
        # Packed multi-admission prefill (server/generation.py
        # prefillBatch): real chunks per batched prefill call.  Mean
        # fill near 1 under light load is expected; under bursts it
        # should track min(concurrent admissions, prefillBatch) — a
        # flat 1 under load means packing is not engaging.
        self.prefill_batch_fill = Histogram(
            "tpumlops_prefill_batch_fill",
            "Admission chunks packed into one batched prefill call",
            ident_labels,
            buckets=(1, 2, 4, 8, 16, 32, 64),
            registry=self.registry,
        )
        self.admission_wait_ms = Histogram(
            "tpumlops_admission_wait_ms",
            "Milliseconds a request waited in the queue before its "
            "admission began",
            ident_labels,
            buckets=(0.5, 1, 2.5, 5, 10, 25, 50, 100, 250, 500, 1000,
                     2500, 5000, 10000),
            registry=self.registry,
        )
        self.ttft_seconds = Histogram(
            "tpumlops_ttft_seconds",
            "Submit-to-first-token latency per generation request",
            ident_labels,
            buckets=_LATENCY_BUCKETS,
            registry=self.registry,
        )
        # Per-request latency decomposition (with ttft_seconds): ITL is
        # the steady-state token cadence a streaming client feels —
        # tick_seconds{kind="decode"} measures the device tick, ITL the
        # request (a tick serves many slots; a slot skips ticks while
        # its admission peer prefills).
        self.itl_seconds = Histogram(
            "tpumlops_itl_seconds",
            "Inter-token latency: wall between consecutive tokens of one "
            "request (first token excluded — that is TTFT)",
            ident_labels,
            buckets=_LATENCY_BUCKETS,
            registry=self.registry,
        )
        self.request_tokens = Histogram(
            "tpumlops_request_tokens",
            "Tokens generated per finished request (includes cancelled "
            "requests' partial output)",
            ident_labels,
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096),
            registry=self.registry,
        )
        # Engine tick wall by kind: the aggregate view of the flight
        # recorder's per-tick journal (server/flight_recorder.py) — a
        # decode-cadence regression shows up as the decode kind's
        # distribution shifting while packed-prefill's fattens.  A
        # "multistep" tick covers K decode steps (decodeSteps), so read
        # its wall against tokens, not against single-step decode ticks.
        self.tick_seconds = Histogram(
            "tpumlops_tick_seconds",
            "Engine tick wall time by kind "
            "(decode/verify/multistep/prefill/packed-prefill/seed); "
            "prefill/seed walls are dispatch-only unless the flight "
            "recorder is on (traceRing > 0), which syncs them to cover "
            "device time",
            ident_labels + ["kind"],
            buckets=_LATENCY_BUCKETS,
            registry=self.registry,
        )
        # Engine device dispatches by op: with generated_tokens this is
        # the amortization series of record — dispatches-per-token is
        # what the fused multi-step path (decodeSteps) collapses by ~K,
        # and what prefix-cache/speculative/packed-prefill each already
        # cut on their own axes.  One increment per journaled engine
        # tick (a multi-chunk seed op counts once).  Registered
        # UNCONDITIONALLY like the spec_* families (the series is
        # meaningful for every serving mode, fused or not) — the
        # decodeSteps:1 byte-identity contract covers the engine loop,
        # tick records, and label VALUES (no op="multistep" children
        # ever appear at K=1), not the family's presence; the inventory
        # is pinned in tests/test_metrics_contract.py.
        self.engine_dispatches = Counter(
            "tpumlops_engine_dispatches",
            "Engine device dispatches by tick kind (decode/verify/"
            "multistep/prefill/packed-prefill/seed)",
            ident_labels + ["op"],
            registry=self.registry,
        )
        # Self-speculative decoding (server/speculative.py): proposed vs
        # accepted draft tokens, plus per-verify distributions.  The
        # counters give the exact acceptance rate over any window
        # (rate(accepted)/rate(proposed)); the histograms show its shape
        # — a healthy repetitive workload piles acceptance at the draft
        # cap, adversarial text piles it at 0.
        self.spec_proposed_tokens = Counter(
            "tpumlops_spec_proposed_tokens",
            "Draft tokens proposed by the n-gram speculative drafter",
            ident_labels,
            registry=self.registry,
        )
        self.spec_accepted_tokens = Counter(
            "tpumlops_spec_accepted_tokens",
            "Draft tokens accepted by greedy verification",
            ident_labels,
            registry=self.registry,
        )
        self.spec_accepted_len = Histogram(
            "tpumlops_spec_accepted_len",
            "Accepted draft length per (slot, verify)",
            ident_labels,
            # Top finite bucket matches the draftTokens ceiling (64) so
            # high-draft tunings keep a readable distribution shape.
            buckets=(0, 1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64),
            registry=self.registry,
        )
        self.spec_acceptance_rate = Histogram(
            "tpumlops_spec_acceptance_rate",
            "accepted/proposed per (slot, verify)",
            ident_labels,
            buckets=(0.0, 0.25, 0.5, 0.75, 0.999, 1.0),
            registry=self.registry,
        )
        # Admission control (server/generation.py admission_queue_budget
        # + the drain protocol): requests refused at the door with
        # 429 + Retry-After.  reason="budget" = queued estimated tokens
        # over budget; reason="draining" = scale-down / shutdown drain
        # in progress.  The autoscaler watches this family to confirm
        # shed (not silence) is what a saturated replica produces.
        self.shed = Counter(
            "tpumlops_engine_shed",
            "Generation requests shed at admission (HTTP 429)",
            ident_labels + ["reason"],
            registry=self.registry,
        )
        # Mid-decode preemption (spec.tpu.preemption + spec.sloClass):
        # evictions of lower-class slots to admit higher-class work and
        # the matching restores.  event="evict" | "restore"; restores
        # lag evictions only while the preempted record waits in its
        # class queue, so evict-minus-restore is live preempted backlog.
        self.preempt = Counter(
            "tpumlops_engine_preempt",
            "Slot preemption events (evict = KV written back through "
            "the prefix cache and slot reclaimed; restore = sequence "
            "re-admitted with no lost work)",
            ident_labels + ["event"],
            registry=self.registry,
        )
        # Model-load stage breakdown (server/loader.py load_stats): the
        # bench has measured disk/transfer/quantize/shard for rounds —
        # this makes it a first-party series so a cold-start regression
        # shows on dashboards, not just in bench JSON.  stage="restore"
        # is the snapshot fast path (server/snapshot.py); "total" the
        # load wall.  Registered unconditionally like engine_dispatches:
        # children appear only when a load observes them, and the
        # inventory is pinned in tests/test_metrics_contract.py.
        self.model_load_seconds = Gauge(
            "tpumlops_model_load_seconds",
            "Most recent model load's stage breakdown "
            "(disk/transfer/quantize/shard, or restore for a snapshot "
            "restore; total = wall)",
            ident_labels + ["stage"],
            registry=self.registry,
        )
        # Scale-to-zero cold start ladder (wake -> restore -> compile ->
        # first_token): stamped once per boot/attach so the whole
        # CR-at-zero -> first-token path is observable per stage.
        self.cold_start_seconds = Gauge(
            "tpumlops_cold_start_seconds",
            "Cold-start stage walls of the most recent boot/attach "
            "(wake/load/restore/compile/first_token/total)",
            ident_labels + ["stage"],
            registry=self.registry,
        )
        # Device telemetry layer (server/device_telemetry.py), registered
        # ONLY when spec.tpu.observability.deviceTelemetry is on: even an
        # unobserved labeled family adds HELP/TYPE lines to the
        # exposition, and the disabled contract is byte-for-byte.
        self.device_hbm_bytes = None
        self.device_mfu = None
        self.device_hbm_bw_util = None
        self.engine_collective_seconds = None
        self.compile_seconds = None
        self.compile_cache_hits = None
        self.compile_cache_misses = None
        if device_telemetry:
            self.device_hbm_bytes = Gauge(
                "tpumlops_device_hbm_bytes",
                "Analytic HBM ledger: bytes held on device by component "
                "(weights_<dtype>, kv_cache, sampling_state, total)",
                ident_labels + ["component"],
                registry=self.registry,
            )
            self.device_mfu = Gauge(
                "tpumlops_device_mfu",
                "Model FLOPs utilization of the most recent engine tick "
                "of each kind (analytic cost model / device peak)",
                ident_labels + ["kind"],
                registry=self.registry,
            )
            self.device_hbm_bw_util = Gauge(
                "tpumlops_device_hbm_bw_util",
                "HBM bandwidth utilization of the most recent engine "
                "tick of each kind (analytic bytes / device peak)",
                ident_labels + ["kind"],
                registry=self.registry,
            )
            self.engine_collective_seconds = Counter(
                "tpumlops_engine_collective_seconds",
                "Estimated ICI collective wall seconds per engine "
                "dispatch at tp > 1, by op (all_reduce = the Megatron "
                "o/down psum pair per layer, all_gather = the vocab-"
                "sharded logits gather), from the analytic cost model",
                ident_labels + ["op"],
                registry=self.registry,
            )
            self.compile_seconds = Counter(
                "tpumlops_compile_seconds",
                "XLA backend-compile wall seconds attributed to the "
                "engine op that triggered the compilation",
                ident_labels + ["op"],
                registry=self.registry,
            )
            self.compile_cache_hits = Counter(
                "tpumlops_compile_cache_hits",
                "Persistent compile-cache hits (compile requests served "
                "by deserializing a cached executable)",
                ident_labels,
                registry=self.registry,
            )
            self.compile_cache_misses = Counter(
                "tpumlops_compile_cache_misses",
                "Persistent compile-cache misses (full XLA compilations)",
                ident_labels,
                registry=self.registry,
            )
        self.ready = Gauge(
            "tpumlops_model_ready",
            "1 once the model is loaded and warmed",
            ident_labels,
            registry=self.registry,
        )
        # First-party reward telemetry for the Seldon feedback API
        # (``/api/v1.0/feedback``); the gate-visible count lives in
        # ``server_requests{service="feedback"}`` (``:410-415``).  A
        # Gauge, not a Counter: rewards are arbitrary floats (negative =
        # penalty signal) and the sum must not silently drop them.
        self.feedback_reward = Gauge(
            "tpumlops_feedback_reward_total",
            "Running sum of rewards posted to the feedback endpoint "
            "(may decrease: negative rewards are penalties)",
            ident_labels,
            registry=self.registry,
        )
        # Failure containment (PR 13).  Watchdog families sit at 0 until
        # --watchdog-deadline-s arms the monitor; the poison counters
        # back the always-on quarantine (a prompt whose admission
        # crashed the engine twice is refused with a typed 422).
        self.watchdog_stalls = Counter(
            "tpumlops_engine_watchdog_stalls_total",
            "Scheduler ticks that exceeded the watchdog deadline "
            "(each flips /readyz unready and journals a watchdog event)",
            ident_labels,
            registry=self.registry,
        )
        self.watchdog_tick_age = Gauge(
            "tpumlops_engine_watchdog_last_tick_age_seconds",
            "Age of the scheduler's last heartbeat as seen by the "
            "watchdog monitor (0 while disarmed; climbs during a stall)",
            ident_labels,
            registry=self.registry,
        )
        self.poison_quarantined = Counter(
            "tpumlops_engine_poison_quarantined_total",
            "Prompt fingerprints quarantined after repeated "
            "admission/prefill crashes",
            ident_labels,
            registry=self.registry,
        )
        self.poison_rejected = Counter(
            "tpumlops_engine_poison_rejected_total",
            "Submissions refused (typed 422) because their prompt "
            "fingerprint is quarantined",
            ident_labels,
            registry=self.registry,
        )

    # -- recording helpers ---------------------------------------------------

    def observe_request(self, seconds: float, code: int = 200, service: str = "predictions"):
        # client_requests feeds the gate's latency percentiles
        # (``:367-372``) — inference traffic only; feedback posts land in
        # server_requests under their own ``service`` label so the
        # feedback count query (``:410-415``) sees them without skewing
        # the latency gate.
        if service == "predictions":
            self.client_requests.labels(**self.identity).observe(seconds)
        self.server_requests.labels(
            **self.identity, code=str(code), service=service
        ).observe(seconds)

    def observe_feedback_reward(self, reward: float):
        self.feedback_reward.labels(**self.identity).inc(reward)

    def observe_batch(
        self,
        size: int,
        queue_seconds: float,
        run_seconds: float = 0.0,
        pipeline_wait_seconds: float = 0.0,
    ):
        self.batch_size.labels(**self.identity).observe(size)
        self.queue_seconds.labels(**self.identity).observe(queue_seconds)
        self.batch_run_seconds.labels(**self.identity).observe(run_seconds)
        self.pipeline_wait_seconds.labels(**self.identity).observe(
            pipeline_wait_seconds
        )

    def observe_decode_step(
        self,
        active_slots: int,
        seconds: float,
        queue_depth: int = 0,
        admitting: int = 0,
    ):
        # active_slots == 0 is the engine's idle heartbeat: refresh the
        # occupancy gauges but keep the batch-size histogram tick-only
        # (the step's wall is tpumlops_tick_seconds{kind="decode"}).
        if active_slots > 0:
            self.decode_batch.labels(**self.identity).observe(active_slots)
        self.engine_active_slots.labels(**self.identity).set(active_slots)
        self.engine_queue_depth.labels(**self.identity).set(queue_depth)
        self.engine_admitting.labels(**self.identity).set(admitting)

    def inc_watchdog_stall(self):
        self.watchdog_stalls.labels(**self.identity).inc()

    def set_watchdog_tick_age(self, seconds: float):
        self.watchdog_tick_age.labels(**self.identity).set(seconds)

    def inc_poison(self, action: str):
        """``action``: "quarantined" (fingerprint crossed the crash
        threshold) or "rejected" (a submit refused with the typed 422)."""
        if action == "quarantined":
            self.poison_quarantined.labels(**self.identity).inc()
        else:
            self.poison_rejected.labels(**self.identity).inc()

    def inc_shed(self, reason: str):
        self.shed.labels(**self.identity, reason=reason).inc()

    def inc_preempt(self, event: str):
        """``event``: "evict" (slot reclaimed, KV parked in the prefix
        cache) or "restore" (preempted sequence re-admitted)."""
        self.preempt.labels(**self.identity, event=event).inc()

    def observe_prefill_batch(self, fill: int):
        self.prefill_batch_fill.labels(**self.identity).observe(fill)

    def observe_admission_wait(self, seconds: float):
        self.admission_wait_ms.labels(**self.identity).observe(seconds * 1000)

    def observe_ttft(self, seconds: float):
        self.ttft_seconds.labels(**self.identity).observe(seconds)

    def observe_itl(self, seconds: float):
        self.itl_seconds.labels(**self.identity).observe(seconds)

    def observe_request_tokens(self, n: int):
        self.request_tokens.labels(**self.identity).observe(n)

    def observe_tick(self, kind: str, seconds: float):
        self.tick_seconds.labels(**self.identity, kind=kind).observe(seconds)

    def inc_dispatch(self, op: str):
        self.engine_dispatches.labels(**self.identity, op=op).inc()

    def observe_speculative(self, proposed: int, accepted: int):
        self.spec_proposed_tokens.labels(**self.identity).inc(proposed)
        self.spec_accepted_tokens.labels(**self.identity).inc(accepted)
        self.spec_accepted_len.labels(**self.identity).observe(accepted)
        if proposed > 0:
            self.spec_acceptance_rate.labels(**self.identity).observe(
                accepted / proposed
            )

    def observe_prefix_hit(self, cached_tokens: int):
        self.prefix_cache_hits.labels(**self.identity).inc()
        self.prefix_cache_cached_tokens.labels(**self.identity).inc(
            cached_tokens
        )

    def inc_prefill_tokens(self, n: int):
        self.prefill_tokens.labels(**self.identity).inc(n)

    def inc_prefill_dispatch(self, when: str):
        self.prefill_dispatch.labels(**self.identity, when=when).inc()

    def inc_decode_dispatch(self, when: str):
        self.decode_dispatch.labels(**self.identity, when=when).inc()

    def inc_prefill_key_blocks(self, walked: int, skipped: int):
        self.prefill_key_blocks.labels(**self.identity, kind="walked").inc(walked)
        self.prefill_key_blocks.labels(**self.identity, kind="skipped").inc(skipped)

    def inc_attn_pairs(self, program: str, pairs: dict):
        """``pairs``: ``{kind: (computed, attended)}`` of one call."""
        short = {"full_attention": "full", "sliding_attention": "sliding"}
        for kind, (computed, attended) in pairs.items():
            labels = dict(self.identity, program=program, kind=short.get(kind, kind))
            self.attn_pairs_computed.labels(**labels).inc(computed)
            self.attn_pairs_attended.labels(**labels).inc(attended)

    def inc_moe(self, program: str, counts: dict, routed: int, row_tile: int):
        """One call of a routed family's ``program``: ``counts`` is what
        the device counted, by the family's ``COUNTS`` names; ``routed``
        every (token, expert) pair of the call's real tokens, wherever
        the expert is held."""
        labels = dict(self.identity, program=program)
        local = counts["local_assignments"]
        self.moe_assignments.labels(**labels).inc(local)
        self.moe_assignments_routed_away.labels(**labels).inc(routed - local)
        self.moe_expert_activations.labels(**labels).inc(counts["experts_hit"])
        self.moe_row_tile_visits.labels(**labels).inc(counts["row_tile_visits"])
        self.moe_row_tile_rows.labels(**labels).set(row_tile)
        # What only some families count: an indexer's keys, a recurrent
        # state's tokens and passes.
        for name, counter in (
            ("dsa_keys_scored", self.dsa_keys_scored),
            ("dsa_keys_selected", self.dsa_keys_selected),
            ("gdn_tokens", self.gdn_tokens),
            ("gdn_state_passes", self.gdn_state_passes),
        ):
            if name in counts:
                counter.labels(**labels).inc(counts[name])

    def set_cache_state_bytes(self, nbytes: int):
        self.cache_state_bytes.labels(**self.identity).set(nbytes)

    def observe_emit_lag(self, seconds: float):
        self.emit_lag.labels(**self.identity).observe(seconds)

    def inc_prefix_evictions(self, n: int = 1):
        self.prefix_cache_evictions.labels(**self.identity).inc(n)

    def inc_prefix_l2(self, kind: str):
        counter = {
            "hit": self.prefix_cache_l2_hits,
            "spill": self.prefix_cache_l2_spills,
            "evict": self.prefix_cache_l2_evictions,
        }.get(kind)
        if counter is not None:
            counter.labels(**self.identity).inc()

    # -- device telemetry (families exist only with deviceTelemetry on) ------

    def observe_hbm_component(self, component: str, nbytes: int):
        if self.device_hbm_bytes is not None:
            self.device_hbm_bytes.labels(
                **self.identity, component=component
            ).set(nbytes)

    def observe_collective(self, op: str, seconds: float):
        if self.engine_collective_seconds is not None:
            self.engine_collective_seconds.labels(
                **self.identity, op=op
            ).inc(seconds)

    def observe_device_util(self, kind: str, mfu: float, bw_util: float):
        if self.device_mfu is not None:
            self.device_mfu.labels(**self.identity, kind=kind).set(mfu)
            self.device_hbm_bw_util.labels(**self.identity, kind=kind).set(
                bw_util
            )

    def observe_compile(self, op: str, seconds: float):
        if self.compile_seconds is not None:
            self.compile_seconds.labels(**self.identity, op=op).inc(seconds)

    def observe_compile_cache(self, hit: bool):
        if self.compile_cache_hits is not None:
            (self.compile_cache_hits if hit else self.compile_cache_misses
             ).labels(**self.identity).inc()

    _LOAD_STAGES = {
        "disk_s": "disk",
        "transfer_s": "transfer",
        "quantize_s": "quantize",
        "shard_s": "shard",
        "restore_s": "restore",
        "wall_s": "total",
    }

    def observe_model_load(self, stats: dict):
        """Export a loader ``load_stats`` breakdown (stage keys absent
        from the stats simply don't materialize children)."""
        for key, stage in self._LOAD_STAGES.items():
            if stats.get(key) is not None:
                self.model_load_seconds.labels(
                    **self.identity, stage=stage
                ).set(float(stats[key]))

    def observe_cold_start(self, stage: str, seconds: float):
        self.cold_start_seconds.labels(**self.identity, stage=stage).set(
            max(0.0, float(seconds))
        )

    def inc_generated_tokens(self, n: int = 1):
        # Separate from observe_decode_step: the first token of every
        # sequence comes from prefill, not a decode tick.
        self.generated_tokens.labels(**self.identity).inc(n)

    def exposition(self) -> bytes:
        return generate_latest(self.registry)
