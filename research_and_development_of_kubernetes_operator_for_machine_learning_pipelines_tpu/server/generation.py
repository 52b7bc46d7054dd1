"""Continuous-batching text-generation engine (baseline config 4).

The reference serves every model as stateless request/response through
Seldon's ``MLFLOW_SERVER`` (``mlflow_operator.py:198``) — it has no notion
of autoregressive decoding.  A TPU data plane serving Llama-class models
needs one: without cross-request batching, each decode step is a batch-1
matmul that leaves the MXU ~idle, and chip utilization collapses.

Design (vLLM-style scheduling, TPU-static shapes):

- The engine owns a :class:`~..models.llama.RaggedKVCache` with a fixed
  number of batch rows ("slots").  Every device computation has a static
  shape — slot count, cache capacity, and prefill bucket lengths are all
  fixed at compile time, so XLA compiles each program exactly once.
- A new request is right-padded to a power-of-two bucket, prefilled as
  batch 1, and its K/V inserted into a free slot (one fused+donated jit
  per bucket).  Padding beyond the real length is progressively
  overwritten by decode writes before it can ever be attended — see
  ``decode_ragged``'s slot-reuse note.
- Every scheduler tick runs ONE batched decode step over all slots at
  their own positions (``lengths`` is per-row).  Requests join and leave
  between ticks; a slot frees as soon as its request finishes, and the
  next queued request takes it — no barrier on batch completion
  ("continuous batching").
- Inactive slots still compute (the MXU does not care) and advance
  nothing; their sampled tokens are discarded host-side (and their cache
  writes DROP — an inactive row may belong to a packed admission
  mid-prefill).
- Packed multi-admission prefill (``prefill_batch`` > 1): a queue of
  in-flight admissions each reserves a cache row, and every engine tick
  up to ``prefill_batch`` of their next prompt chunks run as ONE batched
  call — the per-chunk HBM weight stream is paid once per tick instead
  of once per admission, which is what holds TTFT through a cold-start
  burst or traffic ramp.  ``prefill_token_budget`` caps the packed work
  per tick so decode cadence survives long-prompt bursts.

The big cache buffers are donated through both jitted programs, so steady
state allocates no new HBM per token.  Greedy decoding only — matching
``llama.generate_greedy`` exactly (tested in float64, where no backend
fast-math can blur the comparison).
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Callable, Sequence

import numpy as np

_log = logging.getLogger("tpumlops.generation")


class EngineShutdown(RuntimeError):
    """The engine shut down before this request's admission completed.

    Raised into the futures of queued (not-yet-admitted) and mid-prefill
    requests at shutdown, so callers get a clear error instead of a bare
    ``CancelledError`` (or a hang on a future nobody will resolve)."""


class EngineOverloaded(RuntimeError):
    """Admission shed: the request was refused at the door.

    Raised SYNCHRONOUSLY from :meth:`GenerationEngine.submit` /
    :meth:`GenerationEngine.reserve_admission` — nothing was enqueued —
    either because accepting the request would push the estimated tokens
    (prompt + max_new) of queued-but-unadmitted work past the admission
    budget, or because the engine is draining for shutdown/scale-down.
    The HTTP layer maps it to ``429`` with a ``Retry-After`` header so
    clients (and the router) retry on another replica; shed is the
    loss-free pressure valve that keeps admitted requests' TTFT bounded
    while the autoscaler boots more capacity.
    """

    def __init__(self, message: str, reason: str = "budget",
                 retry_after_s: int = 1, slo_class: str | None = None):
        super().__init__(message)
        # "budget" | "draining" | "class_<name>" (per-class threshold)
        self.reason = reason
        self.retry_after_s = int(retry_after_s)
        # The shed request's SLO class (None when classes are unarmed):
        # rides the 429 body so dashboards and clients can tell
        # best-effort load-shedding from real overload.
        self.slo_class = slo_class


class PoisonRequest(ValueError):
    """The request's prompt fingerprint is quarantined.

    A request whose admission/prefill crashed the engine twice (same
    prompt hash both times) is rejected SYNCHRONOUSLY from
    :meth:`GenerationEngine.submit` instead of being given a third shot
    at crash-looping the replica — every crash fails ALL in-flight
    sequences and reallocates device state, so one poison prompt
    retried by a well-meaning client would take the whole replica's
    traffic down with it on every attempt.  The HTTP layer maps this to
    a typed ``422`` (the request is unprocessable HERE AND EVERYWHERE —
    a retry on another replica would crash it too, so no Retry-After).
    """

    def __init__(self, fingerprint: str, crashes: int):
        super().__init__(
            f"prompt quarantined: admission crashed the engine {crashes} "
            f"times (fingerprint {fingerprint})"
        )
        self.fingerprint = fingerprint
        self.crashes = int(crashes)


def _safe_resolve(fut: Future, value) -> None:
    """set_result tolerating a concurrent client-side cancel (TOCTOU: the
    cancelled() check and set_result are not atomic across threads)."""
    try:
        fut.set_result(value)
    except Exception:  # InvalidStateError: client cancelled in the gap
        pass


def _safe_fail(fut: Future, exc: Exception) -> None:
    try:
        fut.set_exception(exc)
    except Exception:
        pass

class _Wake:
    """Queue sentinel that only unblocks the scheduler's idle wait (a
    control op arrived); carries no request and must never be confused
    with the None shutdown sentinel."""


_WAKE = _Wake()

# SLO priority classes (spec.sloClass / per-request "slo_class").
# Higher priority drains first from the admission queue; under
# preemption a waiting higher-class request may evict a lower-class
# slot at a tick boundary.  Order below is priority DESCENDING.
SLO_CLASSES = ("interactive", "batch", "best-effort")
_CLASS_PRIORITY = {name: i for i, name in enumerate(reversed(SLO_CLASSES))}
# Fraction of the admission budget each class may fill before ITS
# submissions shed (reason "class_<name>"): lower classes give up queue
# room early so the headroom stays available to interactive traffic.
_CLASS_BUDGET_FACTOR = {"interactive": 1.0, "batch": 0.75,
                        "best-effort": 0.5}

_MIN_BUCKET = 16


def prefill_bucket(length: int, capacity: int) -> int:
    """Power-of-two prompt bucket (>= _MIN_BUCKET, <= cache capacity)."""
    from .batching import next_bucket

    return min(max(_MIN_BUCKET, next_bucket(length, capacity)), capacity)


def decode_window_bucket(length: int, capacity: int) -> int:
    """Attention-window bucket: smallest of {2^k, 3*2^(k-1)} >= length.

    Decode attention cost is LINEAR in the attended window W at the
    G=1 MXU matvec floor (docs/PERF.md round 5), so pure power-of-two
    buckets overpay up to 2x just under each boundary (serving at
    position 260 attends 512).  The 1.5x intermediate steps (96, 192,
    384, 768, ...) cap the overshoot at 33% for one more compiled
    variant per octave — measured on chip at 1.35B/32 slots, window
    384 vs 512 is 1.085x the step rate (15.10 -> 13.92 ms/step; the
    weight-stream constant dilutes the linear attention term)."""
    w = prefill_bucket(length, capacity)
    # The 3/4 step applies only to an UNCAPPED power-of-two bucket: when
    # next_bucket was clamped to a non-power capacity, 3*(w//4) is an
    # arbitrary value the warmup enumeration never compiles, and a lazy
    # compile on the scheduler thread is exactly what buckets prevent.
    if length > 0 and w >= 2 * _MIN_BUCKET and w & (w - 1) == 0:
        threeq = 3 * (w // 4)
        if length <= threeq:
            return threeq
    return w


def decode_window_buckets(capacity: int) -> list[int]:
    """Every window :func:`decode_window_bucket` can return, ascending —
    the warmup sweep compiles exactly this set (pinned by an exhaustive
    reachability test over power and non-power capacities)."""
    out = {min(capacity, _MIN_BUCKET)}
    b = _MIN_BUCKET
    while b < capacity:
        out.add(b)
        # 3*(b//2) is reachable only when the NEXT power of two (2b) is
        # itself an admissible uncapped bucket.
        if 2 * b <= capacity:
            out.add(3 * (b // 2))
        b *= 2
    out.add(capacity)
    return sorted(out)


def superstep_window(
    decode_hi: int, other_hi: int, steps: int, capacity: int
) -> int:
    """Window pre-pick for a MIXED-role unified super-step dispatch.

    One static window serves every row of the tick, so it must cover
    each role's WORST case: a decode row at next-write position
    ``decode_hi`` attends up to ``decode_hi + steps - 1`` by the last
    fused iteration (the scan cannot grow the window mid-flight —
    exactly ``_step_fused``'s bound), while a verify row at length L or
    a prefill row committing at offset O attends strictly below L / O
    (``other_hi`` is the max of those).  Taking the bucket of the max
    keeps a K-step decode row and a long verify/prefill row sharing one
    dispatch both inside their worst-case window (pinned exhaustively
    in tests/test_multistep.py)."""
    need = max(1, decode_hi + (steps - 1) if decode_hi > 0 else 1, other_hi)
    return decode_window_bucket(min(need, capacity), capacity)


@dataclass
class _Slot:
    future: Future
    remaining: int  # new tokens still to produce
    eos_id: int | None
    sampling: bool = False  # temperature > 0 (selects the decode variant)
    on_token: Callable[[int], None] | None = None  # streaming callback
    prompt_len: int = 0  # for the decode attention window (host mirror)
    generated: list[int] = field(default_factory=list)
    t_start: float = 0.0
    # Self-speculative decoding (engine speculative config only): an
    # incrementally-appended history buffer holding prompt + generated
    # tokens (the drafter context, built WITHOUT a per-tick
    # re-concatenation — at long context that copy would be serial
    # scheduler-thread work ahead of every dispatch; the prompt alone is
    # ``history[:prompt_len]``), and the slot's adaptive draft budget.
    # Both None when speculation is disabled.
    history: np.ndarray | None = None  # int64 [capacity]; valid: [:hist_len]
    hist_len: int = 0
    draft: "object | None" = None  # speculative.DraftState
    # Request tracing (flight_recorder.RequestTrace | None): per-request
    # timing the HTTP layer returns under ``"debug": true`` and logs on
    # completion.  None (direct engine callers, warmup) = no bookkeeping.
    request_id: str = ""
    trace: "object | None" = None
    t_last_token: float = 0.0  # previous token's wall (inter-token latency)
    # SLO class / preemption state (defaults when classes are unarmed —
    # the armed engine records the class, and under preemption also the
    # prompt and sampling params so an evicted slot can be rebuilt
    # exactly on restore).
    slo_class: str = "interactive"
    prompt: np.ndarray | None = None
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0


@dataclass(eq=False)  # identity semantics: list membership/removal must
# never field-compare (numpy prompt arrays make == a broadcast, not a bool)
class _PrefillProgress:
    """A chunked admission in flight.

    Single-admission mode (``prefillBatch`` 1, the default) holds at
    most one of these and threads a batch-1 scratch cache through the
    engine's ``_seq_state``; packed mode holds a queue of them, each
    with a RESERVED cache row (``slot``) its chunks are written into
    directly.

    ``chunks`` covers only the UNCACHED suffix when a radix-cached
    prefix was found at admission (``cached_tokens`` > 0): the prefix's
    K/V is seeded straight into the sequence cache (``cached_kv``, one
    host pair per chunk) and never re-prefilled.

    ``ahead``: chunk ``next_idx - 1`` went out behind the last pass's
    decode step (``GenerationEngine._send_chunk_ahead``), so the coming
    pass's admit phase dispatches no other."""

    req: _Request
    chunks: list  # padded [1, C] int32 arrays (uncached suffix only)
    next_idx: int = 0
    ahead: bool = False
    cached_tokens: int = 0
    cached_kv: list = field(default_factory=list)
    seeded: bool = False
    slot: int = -1  # packed mode: reserved cache row (-1 = scratch path)


@dataclass(eq=False)
class _OpenTick:
    """A prefill-side program (seed, chunk, insert, fused admit,
    sp-prefill, packed chunks) that has been dispatched and whose tick is
    not journaled yet: the engine thread waits for it at the pass's next
    blocking read, behind whatever it dispatched meanwhile
    (``GenerationEngine._close_ticks``); a chunk sent ahead, behind the
    pass's step, at the next pass's."""

    kind: str
    t0: float  # perf_counter before its dispatch
    # An output of the program that nothing dispatched before the wait
    # donates (a donated buffer cannot be waited on).
    wait_on: object
    owners: tuple  # the _Requests it serves: a device error fails these
    fields: dict  # _record_tick's keyword fields
    # The ``_moe_pending`` entries its program noted: computed, and so
    # readable without a wait, only once the tick is closed.
    counts: list = field(default_factory=list)


class _TickFailed(RuntimeError):
    """The wait for an open tick raised, or the dispatch of a chunk sent
    ahead (``__cause__`` holds the error): the failure is that
    admission's, wherever the loop took the wait."""

    def __init__(self, kind: str, owners: tuple, at: str = "wait"):
        super().__init__(f"{kind} tick failed at its {at}")
        self.owners = owners  # the _Requests the program served


@dataclass
class _Request:
    prompt: np.ndarray  # int32 [L]
    max_new_tokens: int
    eos_id: int | None
    future: Future
    temperature: float = 0.0  # <= 0: greedy
    top_k: int = 0  # <= 0: disabled
    top_p: float = 1.0  # >= 1: disabled
    seed: int | None = None  # None: engine-assigned (boot-nonce fold_in)
    on_token: Callable[[int], None] | None = None  # streaming callback
    t_submit: float = 0.0  # perf_counter at submit (admission-wait / TTFT)
    request_id: str = ""  # inbound X-Request-Id / traceparent (or generated)
    trace: "object | None" = None  # flight_recorder.RequestTrace | None
    # Estimated tokens (prompt + max_new) this request holds against the
    # admission budget while queued; released exactly once at dequeue
    # (0 = nothing reserved, e.g. budget disabled).
    est_tokens: int = 0
    slo_class: str = "interactive"


@dataclass(eq=False)  # identity semantics (numpy fields)
class _Preempted:
    """An evicted mid-decode sequence awaiting re-admission.

    Everything a restore needs to resume the sequence EXACTLY where the
    eviction cut it: the committed K/V chunks (host copies — the radix
    cache holds the full-chunk ones too, but an interleaved admission
    may evict them before restore), the PRNG carry, the pending
    not-yet-fed token, and the slot bookkeeping.  Queued at the FRONT
    of its class deque so an evicted sequence re-admits before newer
    work of its own class — no starvation pile-up behind the flood that
    evicted it."""

    future: Future
    remaining: int
    eos_id: int | None
    sampling: bool
    on_token: Callable[[int], None] | None
    prompt: np.ndarray
    generated: list[int]
    t_start: float
    request_id: str
    trace: "object | None"
    slo_class: str
    temperature: float
    top_k: int
    top_p: float
    key_data: np.ndarray  # PRNG carry at eviction (jax.random.key_data)
    chunks: list  # host (k, v) pairs covering hist, chunk-strided
    hist: int  # committed cache positions (prompt + generated - 1)
    history: np.ndarray | None  # speculative drafter context
    hist_len: int
    draft: "object | None"
    # Queue-protocol shims: a _Preempted rides the class deques next to
    # _Request items, and the admission loop's reservation-release and
    # wait-metric paths read these (0 = nothing reserved / no metric).
    est_tokens: int = 0
    t_submit: float = 0.0


class GenerationEngine:
    """Schedules concurrent generation requests onto one ragged KV cache.

    ``submit`` is thread-safe and returns a ``concurrent.futures.Future``
    resolving to the generated token ids (``np.ndarray[int32]``); the
    aiohttp handler awaits it via ``asyncio.wrap_future``.  All JAX work
    happens on the single scheduler thread.
    """

    def __init__(
        self,
        params,
        cfg,
        *,
        max_slots: int = 4,
        dtype=None,
        eos_id: int | None = None,
        on_step: Callable[[int, float, int, int], None] | None = None,
        on_tokens: Callable[[int], None] | None = None,
        channel=None,
        kv_quant: bool = False,
        prefill_chunk: int | None = None,
        prefix_cache=None,  # PrefixCacheConfig | None
        on_prefix_hit: Callable[[int], None] | None = None,
        on_prefix_evict: Callable[[], None] | None = None,
        on_prefix_l2: Callable[[str], None] | None = None,
        speculative=None,  # speculative.SpeculativeConfig | None
        on_spec: Callable[[int, int], None] | None = None,
        prefill_batch: int = 1,
        prefill_token_budget: int = 0,
        on_prefill_batch: Callable[[int], None] | None = None,
        on_admission_wait: Callable[[float], None] | None = None,
        on_ttft: Callable[[float], None] | None = None,
        on_itl: Callable[[float], None] | None = None,
        on_request_tokens: Callable[[int], None] | None = None,
        on_tick: Callable[[str, float], None] | None = None,
        recorder=None,  # flight_recorder.FlightRecorder | None
        admission_queue_budget: int = 0,
        on_shed: Callable[[str], None] | None = None,
        telemetry=None,  # device_telemetry.DeviceTelemetry | None
        decode_steps: int = 1,
        unified_step: bool = False,
        on_dispatch: Callable[[str], None] | None = None,
        watchdog=None,  # watchdog.EngineWatchdog | None (leader-side)
        on_poison: Callable[[str], None] | None = None,
        mesh_shape=None,  # {"dp": N, "sp": N, "tp": N} | None
        sp_prefill_threshold: int = 1024,
        slo_class: str | None = None,  # default class for submissions
        preemption: bool = False,  # mid-decode eviction of lower classes
        on_preempt: Callable[[str], None] | None = None,  # "evict"|"restore"
        on_prefill_tokens: Callable[[int], None] | None = None,
        tracer=None,  # utils.tracing.Tracer | None (the server shares its own)
        family=None,  # the causal-LM family's module (None: models.llama)
        on_moe: Callable[[str, dict, int, int], None] | None = None,
        # "ahead"|"in_turn": one a chunk program of the single-admission path,
        on_prefill_dispatch: Callable[[str], None] | None = None,
        on_decode_dispatch: Callable[[str], None] | None = None,  # one a step
        on_key_blocks: Callable[[int, int], None] | None = None,  # walked, skipped
    ):
        import jax
        import jax.numpy as jnp

        from ..models import llama
        from ..utils.config import validate_serving_for_family

        # The model is reached through its family's module (the
        # predictor's ``causal_lm["family"]``): cache tuples, ``forward``,
        # ``prefill``, ``decode_ragged``, ``insert_sequence`` under llama's
        # names.  What a family's programs lack is refused HERE, typed and
        # naming the mechanism, before any device state exists.
        lm = llama if family is None else family
        self._lm = lm
        validate_serving_for_family(
            lm.FLAVOR,
            lm.UNSUPPORTED,
            quantize="int8kv" if kv_quant else "none",
            mesh_shape=mesh_shape,
            multihost=channel is not None,
            speculative=speculative is not None and speculative.enabled,
            prefix_cache=prefix_cache is not None and prefix_cache.enabled,
            prefill_batch=prefill_batch,
            decode_steps=decode_steps,
            unified_step=unified_step,
            preemption=preemption,
        )
        # A family with routed experts pads a prompt chunk with an id it
        # does not route, and its programs return, behind llama's outputs,
        # its ``COUNTS`` as one int32 vector: the experts that got a real
        # token, the row-tile visits of the grouped matmuls, the
        # assignments that landed on an expert held here, the positions
        # an indexer scored and kept.
        # ``_moe_pending`` holds (program, real tokens, token rows, device
        # counts) until a read-back the loop makes anyway; a chunk's lie
        # with its open tick until that is closed.
        self._pad_id = lm.PAD_ID
        self._on_moe = on_moe
        # A family whose prefill core walks the written key blocks of its
        # capacity says how many a chunk walks and skips
        # (``prefill_key_blocks``): host arithmetic at the dispatch.
        self._key_blocks = getattr(lm, "prefill_key_blocks", None)
        self._on_key_blocks = on_key_blocks
        self._moe_pending: list = []
        self._params = params
        self._cfg = cfg
        self._eos_default = eos_id
        # (active_slots, step_seconds, queue_depth, admitting) per
        # decode/verify tick — queue_depth is QUEUED-BUT-UNADMITTED only;
        # admitting counts in-flight (mid-prefill) admissions.
        self._on_step = on_step
        self._on_tokens = on_tokens  # (n,) per token delivered to a client
        # multihost.UnitChannel: leader broadcasts every device call so
        # follower processes replay it in lockstep (None = single-host).
        self._channel = channel
        self._in_warmup = False  # suppress metrics/counters during warmup
        self.max_slots = int(max_slots)
        self.capacity = int(cfg.max_seq)
        dtype = dtype or jnp.bfloat16
        self._dtype = dtype
        self._kv_quant = bool(kv_quant)
        # Serving mesh (spec.tpu.meshShape).  None or a product-1 shape
        # — the default — arms NOTHING: no mesh object, no sharding
        # handles, and every jit below compiles exactly the
        # single-device program it always did (pinned byte-for-byte in
        # tests/test_tensor_parallel.py).  Three axes light up:
        #
        # - tp > 1: params arrive pre-sharded (loader) over the same
        #   device prefix this mesh covers, the KV cache shards its
        #   heads axis, sampling state replicates, and every program
        #   compiles with EXPLICIT output shardings so K/V commits, the
        #   on-device sampling chain, and donated buffers stay sharded
        #   across ticks — no per-tick gather.
        # - dp > 1: the ragged cache ALSO shards its row (batch) axis —
        #   each dp shard holds max_slots/dp rows, weights replicate
        #   over dp, and GSPMD partitions every batched program on the
        #   row axis.  Slot bookkeeping stays host-side and identical
        #   (sampling state replicates), so replay op count is
        #   unchanged; _free_slot spreads admissions across the row
        #   blocks so shards fill evenly.
        # - sp > 1: long prompts (>= sp_prefill_threshold tokens, cold
        #   prefix) prefill in ONE ring-attention pass with the
        #   sequence axis split over sp (models.llama.prefill_ring),
        #   then insert through the existing scratch path.
        #
        # pp/ep stay rejected: no pipeline or expert machinery exists.
        self._mesh = None
        self._shard_rep = self._shard_kv = self._shard_seq = None
        self._dp = 1
        self._sp = 1
        self._sp_threshold = int(sp_prefill_threshold)
        if mesh_shape:
            from ..models import partition

            if partition.mesh_device_count(mesh_shape) > 1:
                bad = {
                    a: int(n) for a, n in dict(mesh_shape).items()
                    if a not in ("dp", "sp", "tp") and int(n) > 1
                }
                if bad:
                    raise ValueError(
                        "the generation engine shards over dp/sp/tp "
                        f"only; meshShape axes {bad} must be 1 (no "
                        "pipeline or expert parallelism exists here)"
                    )
                # Typed rejects BEFORE any device state: an indivisible
                # axis would otherwise surface as an opaque XLA shape
                # error at the first warmup dispatch.
                partition.validate_llama_mesh(cfg, mesh_shape)
                dp = partition.dp_degree(mesh_shape)
                sp = partition.sp_degree(mesh_shape)
                if dp > 1 and self.max_slots % dp != 0:
                    raise ValueError(
                        f"meshShape dp={dp} does not divide maxSlots "
                        f"{self.max_slots}: the ragged cache's row axis "
                        "shards over dp in equal blocks"
                    )
                if sp > 1 and (
                    sp & (sp - 1) != 0 or sp > _MIN_BUCKET
                ):
                    raise ValueError(
                        f"meshShape sp={sp} must be a power of two <= "
                        f"{_MIN_BUCKET} so every prefill bucket divides "
                        "evenly across the ring"
                    )
                self._dp = dp
                self._sp = sp
                self._mesh = partition.build_serving_mesh(mesh_shape)
                (
                    self._shard_rep,
                    self._shard_kv,
                    self._shard_seq,
                ) = partition.engine_state_shardings(
                    self._mesh, self._kv_quant
                )
        # Chunked prefill: split prompts into fixed-size chunks so (a) one
        # compiled program serves every prompt length and (b) the scheduler
        # interleaves a decode tick between chunks — a long prompt no
        # longer stalls in-flight streams' token cadence for its whole
        # prefill.  None = whole-prompt bucketed prefill (fused, fastest
        # time-to-first-token when nothing else is decoding).
        self._prefill_chunk_size = int(prefill_chunk) if prefill_chunk else None
        # Radix prefix KV cache (cross-request prompt reuse).  The reuse
        # unit IS the prefill chunk, so enabling the cache enables chunked
        # prefill at ``chunk_tokens`` when prefillChunk is unset; when both
        # are set they must agree — a mismatched reuse unit would make
        # cached chunk boundaries fall mid-prefill-chunk.
        self._prefix_cache = None
        self._on_prefix_hit = on_prefix_hit
        self._on_prefix_evict = on_prefix_evict
        prefix_enabled = prefix_cache is not None and prefix_cache.enabled
        if prefix_enabled:
            ct = int(prefix_cache.chunk_tokens)
            if ct <= 0:
                raise ValueError(
                    f"prefixCache.chunkTokens must be positive, got {ct}"
                )
            if self._prefill_chunk_size is None:
                self._prefill_chunk_size = ct
            elif self._prefill_chunk_size != ct:
                raise ValueError(
                    f"prefixCache.chunkTokens {ct} must equal prefillChunk "
                    f"{self._prefill_chunk_size}: the prefill chunk is the "
                    "prefix reuse unit"
                )
        if self._prefill_chunk_size is not None:
            C = self._prefill_chunk_size
            if C <= 0:
                raise ValueError(f"prefill_chunk must be positive, got {C}")
            if self.capacity % C != 0:
                # Padding the last chunk must never spill past capacity
                # (clamped cache writes would silently corrupt the prompt).
                raise ValueError(
                    f"prefill_chunk {C} must divide KV capacity "
                    f"{self.capacity}"
                )
        # Packed multi-admission prefill: up to prefill_batch in-flight
        # admissions' next chunks run as ONE batched forward per tick —
        # the per-chunk weight stream amortizes across admissions the
        # way PR 2's verify amortized decode.  1 (the default) keeps the
        # single-admission pipeline byte-for-byte.
        self._prefill_batch = 1 if prefill_batch is None else int(prefill_batch)
        if self._prefill_batch < 1:
            raise ValueError(
                f"prefill_batch must be >= 1, got {prefill_batch}"
            )
        if self._prefill_batch > 1 and self._prefill_chunk_size is None:
            raise ValueError(
                "prefill_batch > 1 requires chunked prefill: set "
                "prefillChunk (or enable prefixCache, which implies it)"
            )
        # More concurrent admissions than cache rows cannot exist.
        self._prefill_batch = min(self._prefill_batch, self.max_slots)
        self._prefill_token_budget = int(prefill_token_budget or 0)
        if self._prefill_token_budget < 0:
            raise ValueError(
                "prefill_token_budget must be >= 0, got "
                f"{prefill_token_budget}"
            )
        self._packed = self._prefill_batch > 1
        self._on_prefill_batch = on_prefill_batch
        self._on_admission_wait = on_admission_wait
        self._on_ttft = on_ttft
        # Per-request cadence metrics + engine flight recorder.  recorder
        # None (the default) keeps the scheduler loop byte-for-byte: every
        # hook below is guarded, nothing is allocated per tick.
        self._on_itl = on_itl
        self._on_request_tokens = on_request_tokens
        self._on_tick = on_tick
        self._recorder = recorder
        # Device telemetry (HBM ledger + compile observatory + per-tick
        # MFU/bandwidth; spec.tpu.observability.deviceTelemetry).  None
        # — the default — wraps nothing and computes nothing per tick.
        self._telemetry = telemetry
        # JAX dispatch is async: a prefill-side call returns before the
        # device finishes.  The engine thread waits for it at the pass's
        # next blocking read (``_close_ticks``), behind the decode step it
        # dispatched meanwhile.  Each pass leaves one program queued behind
        # its read-back: the admission's next chunk (``_send_chunk_ahead``)
        # or else the next decode step (``_ahead``, dispatched from the
        # device-resident outputs of the step before it and read back
        # behind the step after it), so the chip does not idle through the
        # read-back, the emission and the next admit phase.  A tick's wall
        # runs to the stamp of its wait, in completion order, and does not
        # absorb another's device time.  No option arms or disarms this:
        # watching the engine (recorder, telemetry) does not change it.
        self._unseen = 0  # tick programs dispatched and not yet seen to end
        self._on_prefill_dispatch = on_prefill_dispatch
        self._on_decode_dispatch = on_decode_dispatch
        self._on_prefix_l2 = on_prefix_l2
        if prefix_enabled:
            from .prefix_cache import RadixPrefixCache

            self._prefix_cache = RadixPrefixCache(
                budget_bytes=int(prefix_cache.budget_bytes),
                chunk_tokens=self._prefill_chunk_size,
                on_evict=self._note_prefix_evict,
                l2_budget_bytes=int(
                    getattr(prefix_cache, "l2_budget_bytes", 0) or 0
                ),
                on_l2_event=self._note_prefix_l2,
            )
        # SLO priority classes + mid-decode preemption.  Both default
        # off, and off keeps the scheduler byte-for-byte: no class
        # deques exist, _dequeue IS queue.get, no slot records extra
        # state.  Classes arm when either a default class is configured
        # or preemption is on (preemption needs class ordering to pick
        # victims).
        if slo_class is not None and slo_class not in SLO_CLASSES:
            raise ValueError(
                f"slo_class must be one of {SLO_CLASSES}, got "
                f"{slo_class!r}"
            )
        self._slo_default = slo_class
        self._classes = slo_class is not None or bool(preemption)
        self._class_queues: "dict[str, object] | None" = None
        if self._classes:
            from collections import deque

            self._class_queues = {name: deque() for name in SLO_CLASSES}
        self._preemption = bool(preemption)
        if self._preemption and self._prefix_cache is None:
            # Evicted K/V is written back THROUGH the radix cache (and
            # restore re-seeds through the same chunk layout), so
            # preemption without it has nowhere loss-free to park work.
            raise ValueError(
                "preemption requires the radix prefix cache "
                "(prefixCache.enabled): evicted slots write their K/V "
                "back through it and restore from the same chunks"
            )
        self._on_preempt = on_preempt
        self.preemptions = 0
        self.preempt_restores = 0
        # Tokens a preempted sequence had to RE-generate after restore —
        # zero by construction (the pending token and PRNG carry travel
        # with the eviction record); the bench gate pins it there.
        self.preempt_recomputed_tokens = 0
        # Self-speculative n-gram decoding: disabled (None) = byte-for-byte
        # the plain single-token tick.  Enabled: greedy-only ticks draft up
        # to draft_tokens continuations per slot from the slot's own
        # history and verify them in ONE batched forward (_verify below);
        # any tick with a sampling slot falls back to the plain step —
        # exact acceptance is a greedy-argmax rule.
        self._spec = None
        self._spec_chain: tuple[int, ...] = ()
        self._on_spec = on_spec
        if speculative is not None and speculative.enabled:
            from .speculative import draft_chain

            dt = int(speculative.draft_tokens)
            if dt < 1:
                raise ValueError(
                    f"speculative.draftTokens must be >= 1, got {dt}"
                )
            if not (1 <= int(speculative.ngram_min) <= int(speculative.ngram_max)):
                raise ValueError(
                    "speculative ngram bounds must satisfy "
                    f"1 <= ngramMin <= ngramMax, got "
                    f"[{speculative.ngram_min}, {speculative.ngram_max}]"
                )
            self._spec = speculative
            self._spec_chain = draft_chain(dt)
        # Fused multi-step decode (spec.tpu.decodeSteps): K decode
        # iterations per dispatch as a lax.scan with an on-device
        # sampling chain and EOS latch, paired with lag-1 asynchronous
        # token readback (the scheduler dispatches tick N+1 before
        # blocking on tick N's token block).  1 — the default — keeps
        # the single-step tick loop byte-for-byte: no fused program is
        # built, swept, or consulted.
        self._decode_steps = 1 if decode_steps is None else int(decode_steps)
        if not (1 <= self._decode_steps <= 16):
            raise ValueError(
                f"decode_steps must be in [1, 16], got {decode_steps}"
            )
        self._fused = self._decode_steps > 1
        # Unified ragged super-step (spec.tpu.unifiedStep): ONE program
        # per tick processes a mixed batch of packed-prefill chunk
        # commits, fused-K decode rows, and speculative verify rows —
        # driven by per-row role/offset/budget tensors — so the warmup
        # sweep compiles one variant per (window-bucket x sampling-mode)
        # instead of the decode x verify-chain x multistep x packed-B_p
        # cross-product.  False — the default — builds nothing and keeps
        # the legacy split-program engine byte-for-byte.
        self._unified = bool(unified_step)
        # Static block width of the unified program: wide enough for the
        # largest verify chain (draft_tokens + 1) and the prefill chunk,
        # 1 when neither feature is on.  One width -> one compiled shape.
        sw = 1
        if self._spec is not None:
            sw = max(sw, int(self._spec.draft_tokens) + 1)
        if self._packed:
            sw = max(sw, int(self._prefill_chunk_size))
        self._super_width = sw
        self._on_dispatch = on_dispatch
        self._on_prefill_tokens = on_prefill_tokens
        # Host-time spans of the scheduler loop (``engine.*``: a root a pass
        # of ``_loop``, nine phases under it) and the starvation account.
        from ..utils.tracing import Tracer

        self.tracer = tracer if tracer is not None else Tracer(profiler=True)
        self._span = self.tracer.span
        self._starved = self.tracer.account("device_starved")
        # Scheduler-loop watchdog (server/watchdog.py): None — the
        # default — keeps the loop byte-for-byte (every beat below is
        # guarded).  Leader-side only, like the recorder: followers
        # block inside replayed collectives by design and the leader's
        # exit tears the unit down.
        self._watchdog = watchdog
        # An IDLE scheduler blocks in queue.get and beats only once per
        # poll — a deadline below the poll interval would read every
        # quiet second as a stall (readiness flapping, spurious journal
        # events, and with a short grace an exit loop on a healthy idle
        # pod).  Halve the idle poll under the deadline so idle beats
        # always land in time.
        self._idle_poll_s = (
            min(1.0, watchdog.deadline_s / 2.0)
            if watchdog is not None else 1.0
        )
        if watchdog is not None:
            # The engine owns the slot truth; the server owns the
            # readiness/metrics callbacks.  Unconditional: a warm-pool
            # attach/replace hands the SAME watchdog to its new engine,
            # and the inventory must follow.
            watchdog.slot_inventory = self._slot_inventory
        # Poison-request quarantine: prompt fingerprints whose
        # admission/prefill crashed the engine, and the ones past the
        # crash threshold that submit now refuses with a typed 422.
        # Always on — it only changes behavior on the Nth crash of a
        # prompt that already took every in-flight request down twice.
        self._poison_counts: dict[str, int] = {}
        self._quarantined: dict[str, int] = {}
        self._poison_lock = threading.Lock()
        self._on_poison = on_poison  # fed "quarantined" | "rejected"
        self.poison_quarantined_total = 0
        self.poison_rejected_total = 0
        # Engine device dispatches by tick kind (the amortization series:
        # a fused K-step tick is ONE dispatch where the plain loop paid
        # K) — mirrored to tpumlops_engine_dispatches_total{op} via
        # on_dispatch; tests/test_multistep.py counts it.
        self.dispatches_total: dict[str, int] = {}
        self._reset_device_state()

        # Sharding handles for the program signatures below: ``rep`` =
        # replicated (tokens, lengths, keys, sampling params, logits
        # read-backs), ``kvsh`` = the ragged cache repr (heads axis on
        # tp; a (values, scales) pair under int8kv), ``seqsh`` = the
        # batch-1 prefill scratch.  All None without a mesh.
        rep, kvsh, seqsh = self._shard_rep, self._shard_kv, self._shard_seq

        def jit_sharded(fn, donate_argnums=(), static_argnums=(),
                        out_shardings=None):
            """``jax.jit`` with EXPLICIT output shardings when the tp
            mesh is armed — jax.jit with out_shardings IS pjit
            (jax.shard_map stays the escape
            hatch for manually-partitioned kernels; the engine programs
            are GSPMD-partitioned, input shardings propagate from the
            committed param/cache arrays).  Without a mesh this is
            byte-for-byte the plain jax.jit call it replaces: no
            out_shardings kwarg is even passed."""
            kw = {}
            if donate_argnums:
                kw["donate_argnums"] = donate_argnums
            if static_argnums:
                kw["static_argnums"] = static_argnums
            if self._mesh is not None and out_shardings is not None:
                kw["out_shardings"] = out_shardings
            return jax.jit(fn, **kw)

        def make_cache(k, v, lengths):
            """k/v are arrays (bf16 cache) or (values, scales) pairs."""
            if self._kv_quant:
                return llama.QuantRaggedKVCache(k[0], k[1], v[0], v[1], lengths)
            return lm.RaggedKVCache(k, v, lengths)

        def cache_repr(cache):
            if self._kv_quant:
                return (cache.k8, cache.k_scale), (cache.v8, cache.v_scale)
            return cache.k, cache.v

        def _decode(
            params, toks, k, v, lengths, active, keys, temps, tks, tps, window
        ):
            from ..models.sampling import sample_logits, split_keys

            cache = make_cache(k, v, lengths)
            logits, cache, *aux = lm.decode_ragged(
                params, toks, cache, cfg, active=active, dtype=dtype,
                window=window,
            )
            with jax.named_scope("sample"):
                keys2, use = split_keys(keys)
                nxt = sample_logits(logits[:, -1, :], use, temps, tks, tps)
                # Finished slots keep their last token so their rows stay
                # inert.
                toks2 = jnp.where(active, nxt, toks[:, 0])[:, None]
            ck, cv = cache_repr(cache)
            return (toks2, ck, cv, cache.lengths, keys2, *aux)

        # ``window`` is static: one compiled program per power-of-two bucket
        # of the longest active sequence (short traffic stops paying
        # full-capacity cache reads — decode's dominant HBM term).
        self._decode = jit_sharded(
            _decode, donate_argnums=(2, 3), static_argnums=(10,),
            out_shardings=(rep, kvsh, kvsh, rep, rep) if rep else None,
        )

        def _decode_greedy(params, toks, k, v, lengths, active, window):
            # Hot path when every occupied slot is greedy (the default):
            # plain argmax — no full-vocab sort/softmax/categorical work.
            cache = make_cache(k, v, lengths)
            logits, cache, *aux = lm.decode_ragged(
                params, toks, cache, cfg, active=active, dtype=dtype,
                window=window,
            )
            with jax.named_scope("sample"):
                nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
                toks2 = jnp.where(active, nxt, toks[:, 0])[:, None]
            ck, cv = cache_repr(cache)
            return (toks2, ck, cv, cache.lengths, *aux)

        self._decode_greedy = jit_sharded(
            _decode_greedy, donate_argnums=(2, 3), static_argnums=(6,),
            out_shardings=(rep, kvsh, kvsh, rep) if rep else None,
        )

        def _verify(params, toks, k, v, lengths, active, draft_len, window):
            # Self-speculative verify: toks [B, S] (col 0 = pending token,
            # cols 1.. = draft, padded past draft_len).  ONE forward
            # scores all S positions per slot; acceptance is exact greedy
            # argmax, so emitted tokens are bit-identical to S sequential
            # _decode_greedy steps — but the weight tree streams from HBM
            # once instead of up to S times.  Rejected K/V writes roll
            # back by PER-ROW LENGTH TRUNCATION: lengths advance only by
            # accepted+1, and positions at/beyond the truncated length
            # are never attended before being overwritten (the same
            # invariant that makes slot reuse safe).  One compiled
            # variant per (S, window); K/V donated like _decode.
            from ..models.sampling import speculative_accept

            cache = make_cache(k, v, lengths)
            logits, cache = llama.verify_ragged(
                params, toks, cache, cfg, dtype=dtype, window=window,
                active=active,
            )
            with jax.named_scope("sample"):
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, S]
                accepted, nxt = speculative_accept(toks, greedy, draft_len)
                toks2 = jnp.where(active, nxt, toks[:, 0])[:, None]
            advance = jnp.where(active, accepted + 1, 0).astype(jnp.int32)
            ck, cv = cache_repr(cache)
            return toks2, ck, cv, cache.lengths + advance, greedy, accepted

        self._verify = jit_sharded(
            _verify, donate_argnums=(2, 3), static_argnums=(7,),
            out_shardings=(rep, kvsh, kvsh, rep, rep, rep) if rep else None,
        )

        def _multistep_sampling(
            params, toks, k, v, lengths, active, remaining, eos_ids,
            keys, temps, tks, tps, window, steps,
        ):
            # Fused K-step decode, sampling variant: the scan body is the
            # SAME decode forward as _decode with the on-device sampling
            # chain advancing every row's key once per step — exactly the
            # step-by-step key discipline, so seeded sampling is
            # token-for-token reproducible against K sequential ticks.
            from ..models.sampling import sample_chain_step

            cache = make_cache(k, v, lengths)

            @jax.named_scope("sample")
            def sample(logits, carry):
                return sample_chain_step(logits, carry, temps, tks, tps)

            tok_block, valid, toks2, cache, active2, remaining2, keys2 = (
                llama.decode_multistep(
                    params, toks, cache, cfg, active, remaining, eos_ids,
                    steps, sample, sample_carry=keys, dtype=dtype,
                    window=window,
                )
            )
            ck, cv = cache_repr(cache)
            return (
                tok_block, valid, toks2, ck, cv, cache.lengths,
                active2, remaining2, keys2,
            )

        def _multistep_greedy(
            params, toks, k, v, lengths, active, remaining, eos_ids,
            window, steps,
        ):
            # Greedy variant: plain argmax per step (no sort/softmax/key
            # work), mirroring _decode_greedy.
            cache = make_cache(k, v, lengths)

            @jax.named_scope("sample")
            def sample(logits, carry):
                return carry, jnp.argmax(logits, axis=-1).astype(jnp.int32)

            tok_block, valid, toks2, cache, active2, remaining2, _ = (
                llama.decode_multistep(
                    params, toks, cache, cfg, active, remaining, eos_ids,
                    steps, sample, sample_carry=None, dtype=dtype,
                    window=window,
                )
            )
            ck, cv = cache_repr(cache)
            return (
                tok_block, valid, toks2, ck, cv, cache.lengths,
                active2, remaining2,
            )

        if self._fused and not self._unified:
            # One compiled variant per (K, window) pair, like _verify's
            # (S, window) grid; K is fixed per deployment so the warmup
            # sweep is |window buckets| x 2 variants.  The unified
            # engine never builds these: its K steps run inside the
            # super-step program.
            self._multistep = jit_sharded(
                _multistep_sampling, donate_argnums=(2, 3),
                static_argnums=(12, 13),
                out_shardings=(
                    (rep, rep, rep, kvsh, kvsh, rep, rep, rep, rep)
                    if rep else None
                ),
            )
            self._multistep_greedy = jit_sharded(
                _multistep_greedy, donate_argnums=(2, 3),
                static_argnums=(8, 9),
                out_shardings=(
                    (rep, rep, rep, kvsh, kvsh, rep, rep, rep)
                    if rep else None
                ),
            )

        def _prefill_insert(
            params, ids, k, v, lengths, toks, slot, actual_len,
            keys, temps, tks, tps, slot_key, temp, tk, tp,
        ):
            from ..models.sampling import sample_logits

            logits, seq, *aux = lm.prefill(params, ids, cfg, dtype=dtype)
            cache = lm.insert_sequence(
                make_cache(k, v, lengths), seq, slot, actual_len
            )
            # Install the slot's sampling state, then draw the first token
            # with the same per-slot key discipline decode uses.
            carry, use = jax.random.split(slot_key)
            keys2 = keys.at[slot].set(carry)
            temps2 = temps.at[slot].set(temp)
            tks2 = tks.at[slot].set(tk)
            tps2 = tps.at[slot].set(tp)
            row = logits[0, actual_len - 1][None]
            first = sample_logits(
                row, use[None], temp[None], tk[None], tp[None]
            )[0]
            toks2 = toks.at[slot, 0].set(first)
            ck, cv = cache_repr(cache)
            return (
                ck, cv, cache.lengths, toks2,
                keys2, temps2, tks2, tps2, first, *aux,
            )

        # One compiled program per prompt bucket (jit caches by ids shape).
        self._prefill_insert = jit_sharded(
            _prefill_insert, donate_argnums=(2, 3),
            out_shardings=(
                (kvsh, kvsh, rep, rep, rep, rep, rep, rep, rep)
                if rep else None
            ),
        )

        def _prefill_one_chunk(params, ids, sk, sv, slen):
            seq = lm.KVCache(sk, sv, slen)
            logits, seq, *aux = lm.forward(params, ids, seq, cfg, dtype=dtype)
            return (logits[0], seq.k, seq.v, seq.length, *aux)

        self._prefill_one_chunk = jit_sharded(
            _prefill_one_chunk, donate_argnums=(2, 3),
            out_shardings=(rep, seqsh, seqsh, rep) if rep else None,
        )

        from jax.lax import dynamic_slice as lax_ds
        from jax.lax import dynamic_update_slice as lax_dus

        def _seed_chunk(sk, sv, ck, cv, start):
            # Prefix-cache hit: copy one cached chunk's K/V into the
            # in-progress sequence cache at its absolute offset.  ``start``
            # is traced, the chunk shape is fixed — ONE compiled program
            # serves every cached chunk at every offset (vs a forward pass
            # per chunk on the cold path).
            z = jnp.int32(0)
            sk = lax_dus(sk, ck.astype(sk.dtype), (z, z, start, z, z))
            sv = lax_dus(sv, cv.astype(sv.dtype), (z, z, start, z, z))
            return sk, sv

        self._seed_chunk = jit_sharded(
            _seed_chunk, donate_argnums=(0, 1),
            out_shardings=(seqsh, seqsh) if rep else None,
        )

        def _read_chunk(sk, sv, start):
            # Prefix-cache write-back: pull one freshly prefilled chunk's
            # K/V slice off the device.  Traced ``start`` -> one program.
            C = self._prefill_chunk_size
            z = jnp.int32(0)
            size = (sk.shape[0], sk.shape[1], C, sk.shape[3], sk.shape[4])
            return (
                lax_ds(sk, (z, z, start, z, z), size),
                lax_ds(sv, (z, z, start, z, z), size),
            )

        # Chunk read-backs feed the HOST radix cache: replicated outputs
        # (one all-gather at prefill rate, never per tick).
        self._read_chunk = jit_sharded(
            _read_chunk, out_shardings=(rep, rep) if rep else None
        )

        def _insert_only(
            last_logits, k, v, lengths, toks, slot, actual_len,
            keys, temps, tks, tps, slot_key, temp, tk, tp, sk, sv, last_idx,
        ):
            from ..models.sampling import sample_logits

            seq = lm.KVCache(sk, sv, jnp.zeros((), jnp.int32))
            cache = lm.insert_sequence(
                make_cache(k, v, lengths), seq, slot, actual_len
            )
            carry, use = jax.random.split(slot_key)
            keys2 = keys.at[slot].set(carry)
            temps2 = temps.at[slot].set(temp)
            tks2 = tks.at[slot].set(tk)
            tps2 = tps.at[slot].set(tp)
            row = last_logits[last_idx][None]
            first = sample_logits(
                row, use[None], temp[None], tk[None], tp[None]
            )[0]
            toks2 = toks.at[slot, 0].set(first)
            ck, cv = cache_repr(cache)
            return (
                ck, cv, cache.lengths, toks2,
                keys2, temps2, tks2, tps2, first,
            )

        self._insert_only = jit_sharded(
            _insert_only, donate_argnums=(1, 2),
            out_shardings=(
                (kvsh, kvsh, rep, rep, rep, rep, rep, rep, rep)
                if rep else None
            ),
        )

        # Sequence-parallel prefill: the whole padded prompt in ONE
        # ring-attention pass with the sequence split over sp (one
        # compiled variant per prompt bucket >= the threshold's bucket).
        # Stacked K/V lands in the donated seq scratch at origin, and
        # only the last REAL row's logits [1, V] cross the replicated
        # boundary — the insert then rides the existing _insert_only
        # path with last_idx = 0.
        if self._sp > 1:
            sp_mesh = self._mesh

            def _prefill_sp(params, ids, sk, sv, last_idx):
                logits, k_all, v_all = llama.prefill_ring(
                    params, ids, cfg, mesh=sp_mesh, last_idx=last_idx,
                    dtype=dtype,
                )
                z = jnp.int32(0)
                sk = lax_dus(sk, k_all.astype(sk.dtype), (z, z, z, z, z))
                sv = lax_dus(sv, v_all.astype(sv.dtype), (z, z, z, z, z))
                return logits, sk, sv

            self._prefill_sp = jit_sharded(
                _prefill_sp, donate_argnums=(2, 3),
                out_shardings=(rep, seqsh, seqsh) if rep else None,
            )
        else:
            self._prefill_sp = None

        max_slots_static = self.max_slots

        def _prefill_chunks_batched(
            params, ids, k, v, lengths, toks, keys, temps, tks, tps,
            slots, offsets, last_pos, final_lens,
            slot_keys, r_temps, r_tks, r_tps,
        ):
            # Packed admission: B_p in-flight admissions' next chunks in
            # ONE forward (llama.prefill_chunks_ragged), plus the
            # finalize step for rows whose chunk completes the prompt
            # (last_pos >= 0): install the slot's sampling state and
            # sample the first token — the per-sequence _insert_only
            # discipline, batched.  Non-final (and pad) rows scatter to
            # the out-of-range slot index and drop.  One compiled
            # variant per power-of-two B_p bucket (the ids shape).
            from ..models.sampling import sample_logits, split_keys

            cache = make_cache(k, v, lengths)
            logits, cache = llama.prefill_chunks_ragged(
                params, ids, cache, slots, offsets, cfg, dtype=dtype
            )
            is_final = last_pos >= 0
            row = jnp.take_along_axis(
                logits, jnp.maximum(last_pos, 0)[:, None, None], axis=1
            )[:, 0]  # [B_p, vocab]
            carry, use = split_keys(slot_keys)
            firsts = sample_logits(row, use, r_temps, r_tks, r_tps)
            tgt = jnp.where(is_final, slots, jnp.int32(max_slots_static))
            kd = jax.random.key_data(keys)
            keys2 = jax.random.wrap_key_data(
                kd.at[tgt].set(jax.random.key_data(carry), mode="drop")
            )
            temps2 = temps.at[tgt].set(r_temps, mode="drop")
            tks2 = tks.at[tgt].set(r_tks, mode="drop")
            tps2 = tps.at[tgt].set(r_tps, mode="drop")
            lengths2 = cache.lengths.at[tgt].set(final_lens, mode="drop")
            toks2 = toks.at[tgt, 0].set(firsts, mode="drop")
            ck, cv = cache_repr(cache)
            return ck, cv, lengths2, toks2, keys2, temps2, tks2, tps2, firsts

        self._prefill_chunks = jit_sharded(
            _prefill_chunks_batched, donate_argnums=(2, 3),
            out_shardings=(
                (kvsh, kvsh, rep, rep, rep, rep, rep, rep, rep)
                if rep else None
            ),
        )

        def _seed_chunk_slot(k, v, ck, cv, slot, start):
            # Packed-mode prefix-cache hit: copy one cached chunk's K/V
            # straight into the reserved cache row at its absolute
            # offset (the scratch-path _seed_chunk, retargeted at a slot
            # of the ragged cache).  ck/cv arrive [L, 1, C, NKV, D] — the
            # radix cache's storage layout and the ragged cache's own, so
            # entries stay interchangeable between modes.
            at = (jnp.int32(0), slot, start, jnp.int32(0), jnp.int32(0))
            if self._kv_quant:
                from ..models.llama import _quant_kv

                k8, ksc = _quant_kv(ck.astype(dtype))
                v8, vsc = _quant_kv(cv.astype(dtype))
                kb, ks = k
                vb, vs = v
                return (
                    (lax_dus(kb, k8, at), lax_dus(ks, ksc, at)),
                    (lax_dus(vb, v8, at), lax_dus(vs, vsc, at)),
                )
            return (
                lax_dus(k, ck.astype(k.dtype), at),
                lax_dus(v, cv.astype(v.dtype), at),
            )

        self._seed_slot = jit_sharded(
            _seed_chunk_slot, donate_argnums=(0, 1),
            out_shardings=(kvsh, kvsh) if rep else None,
        )

        def _read_chunk_slot(k, v, slot, start):
            # Packed-mode prefix-cache write-back: pull one freshly
            # prefilled chunk's K/V off the reserved cache row, in the
            # radix cache's storage layout (the cache's own).  An
            # int8kv cache dequantizes on the way out — lossless round
            # trip: re-quantizing q8*scale reproduces q8 and scale
            # exactly (the per-head max is preserved).
            C = self._prefill_chunk_size
            z = jnp.int32(0)

            def pull(buf):
                nl, _b, _t, nkv, width = buf.shape
                return lax_ds(buf, (z, slot, start, z, z), (nl, 1, C, nkv, width))

            if self._kv_quant:
                kb, ks = k
                vb, vs = v
                ck = pull(kb).astype(dtype) * pull(ks)
                cv = pull(vb).astype(dtype) * pull(vs)
            else:
                ck, cv = pull(k), pull(v)
            return ck.astype(dtype), cv.astype(dtype)

        self._read_slot = jit_sharded(
            _read_chunk_slot, out_shardings=(rep, rep) if rep else None
        )

        def _insert_restore(
            lengths, toks, keys, temps, tks, tps,
            slot, length, pending, slot_key, temp, tk, tp,
        ):
            # Preemption restore: re-install an evicted sequence's slot
            # bookkeeping after its K/V chunks were re-seeded.  The
            # mirror of _insert_only's finalize step with two deliberate
            # differences that make restore+resume token-for-token
            # identical to never having been evicted: the PRNG carry is
            # installed AS CAPTURED (no split — the split already
            # happened in the sequence's own history), and no token is
            # sampled (the pending token was sampled before eviction
            # and travels with the record).  Touches no cache buffers.
            lengths2 = lengths.at[slot].set(length)
            toks2 = toks.at[slot, 0].set(pending)
            kd = jax.random.key_data(keys)
            keys2 = jax.random.wrap_key_data(
                kd.at[slot].set(jax.random.key_data(slot_key))
            )
            temps2 = temps.at[slot].set(temp)
            tks2 = tks.at[slot].set(tk)
            tps2 = tps.at[slot].set(tp)
            return lengths2, toks2, keys2, temps2, tks2, tps2

        self._insert_restore = jit_sharded(
            _insert_restore,
            out_shardings=(
                (rep, rep, rep, rep, rep, rep) if rep else None
            ),
        )

        def _superstep(
            params, ids, k, v, lengths, toks, keys, temps, tks, tps,
            roles, offsets, counts, draft_len, act_in, remaining, eos_in,
            last_pos, final_lens, slot_keys, r_temps, r_tks, r_tps,
            window, steps, sampling,
        ):
            # The whole tick as ONE program: mixed decode/verify/prefill
            # rows through llama.super_step_ragged, then the packed
            # finalize step (rows whose chunk completes the prompt
            # install their sampling state and sample the first token —
            # _prefill_chunks_batched's tail, reading the same wide
            # logits).  ``sampling`` is static like window/steps: the
            # greedy variant compiles without the chain-sampling work
            # but keeps the full signature (finalize still installs
            # per-request sampling state), so the warmup sweep is
            # |window buckets| x 2 — full stop.
            from ..models.sampling import (
                sample_chain_step, sample_logits, split_keys,
            )

            cache = make_cache(k, v, lengths)
            if sampling:
                @jax.named_scope("sample")
                def sample(lg, carry):
                    return sample_chain_step(lg, carry, temps, tks, tps)

                carry0 = keys
            else:
                @jax.named_scope("sample")
                def sample(lg, carry):
                    return carry, jnp.argmax(lg, axis=-1).astype(jnp.int32)

                carry0 = None
            (
                logits, tok_block, valid, greedy, accepted,
                toks2, cache, _act2, _rem2, carry2,
            ) = llama.super_step_ragged(
                params, ids, cache, cfg,
                roles=roles, offsets=offsets, counts=counts,
                draft_len=draft_len, active=act_in, remaining=remaining,
                eos_ids=eos_in, steps=steps, sample_fn=sample,
                sample_carry=carry0, dtype=dtype, window=window,
            )
            keys_run = carry2 if sampling else keys
            is_final = last_pos >= 0
            row = jnp.take_along_axis(
                logits, jnp.maximum(last_pos, 0)[:, None, None], axis=1
            )[:, 0]  # [B, vocab]
            f_carry, use = split_keys(slot_keys)
            firsts = sample_logits(row, use, r_temps, r_tks, r_tps)
            tgt = jnp.where(
                is_final,
                jnp.arange(max_slots_static, dtype=jnp.int32),
                jnp.int32(max_slots_static),
            )
            kd = jax.random.key_data(keys_run)
            keys2 = jax.random.wrap_key_data(
                kd.at[tgt].set(jax.random.key_data(f_carry), mode="drop")
            )
            temps2 = temps.at[tgt].set(r_temps, mode="drop")
            tks2 = tks.at[tgt].set(r_tks, mode="drop")
            tps2 = tps.at[tgt].set(r_tps, mode="drop")
            lengths2 = cache.lengths.at[tgt].set(final_lens, mode="drop")
            toks3 = toks2.at[tgt, 0].set(firsts, mode="drop")
            ck, cv = cache_repr(cache)
            return (
                tok_block, valid, greedy, accepted, firsts,
                toks3, ck, cv, lengths2, keys2, temps2, tks2, tps2,
            )

        if self._unified:
            self._superstep = jit_sharded(
                _superstep, donate_argnums=(2, 3),
                static_argnums=(23, 24, 25),
                out_shardings=(
                    (rep, rep, rep, rep, rep, rep, kvsh, kvsh,
                     rep, rep, rep, rep, rep)
                    if rep else None
                ),
            )

        if telemetry is not None:
            # Compile observatory: every engine jit dispatch is wrapped so
            # XLA compilations attribute to the op that triggered them
            # (decode buckets x verify variants x prefill B_p buckets x
            # seed ops).  The wrapper is a thread-local set/unset around
            # the call — no per-dispatch device work.
            obs = telemetry.observatory
            self._decode = obs.wrap_jit("decode", self._decode)
            self._decode_greedy = obs.wrap_jit("decode", self._decode_greedy)
            self._verify = obs.wrap_jit("verify", self._verify)
            if self._fused and not self._unified:
                self._multistep = obs.wrap_jit("multistep", self._multistep)
                self._multistep_greedy = obs.wrap_jit(
                    "multistep", self._multistep_greedy
                )
            if self._unified:
                self._superstep = obs.wrap_jit("superstep", self._superstep)
            self._prefill_insert = obs.wrap_jit("prefill", self._prefill_insert)
            self._prefill_one_chunk = obs.wrap_jit(
                "prefill", self._prefill_one_chunk
            )
            self._insert_only = obs.wrap_jit("prefill", self._insert_only)
            if self._prefill_sp is not None:
                self._prefill_sp = obs.wrap_jit(
                    "sp-prefill", self._prefill_sp
                )
            self._prefill_chunks = obs.wrap_jit(
                "packed-prefill", self._prefill_chunks
            )
            self._seed_chunk = obs.wrap_jit("seed", self._seed_chunk)
            self._read_chunk = obs.wrap_jit("seed", self._read_chunk)
            self._seed_slot = obs.wrap_jit("seed", self._seed_slot)
            self._read_slot = obs.wrap_jit("seed", self._read_slot)
            prefix_budget = (
                int(prefix_cache.budget_bytes) if prefix_enabled else 0
            )
            telemetry.attach_model(
                params, cfg, self.max_slots,
                kv_quant=self._kv_quant,
                dtype_bytes=jnp.dtype(dtype).itemsize,
                prefix_cache_budget_bytes=prefix_budget,
                mesh_shape=mesh_shape,
                family=family,
            )

        self._slots: list[_Slot | None] = [None] * self.max_slots
        self._pending: list[_PrefillProgress] = []
        # Packed mode: cache rows reserved by in-flight admissions (their
        # chunks are being written there; decode must not hand them out).
        self._reserved: set[int] = set()
        # Single-admission chunked-prefill scratch (leader and follower
        # both thread the in-progress sequence cache through here; it is
        # what serializes that mode to one admission at a time — packed
        # mode writes straight into reserved cache rows and never uses it).
        self._seq_state = None  # (last_logits, seq_k, seq_v, seq_len)
        # Engine-assigned sampling keys: fold a per-boot nonce so unseeded
        # requests never collide with the user-visible seed space (and never
        # replay the same streams after a pod restart).  NOT reset by
        # _reset_device_state: streams stay distinct across a recovery.
        import os as _os

        self._boot_key = jax.random.key(int.from_bytes(_os.urandom(7), "little"))
        self._seed_counter = 0
        # Constant pad-row key material for packed calls, computed ONCE:
        # rebuilding it per tick would put a device dispatch + D2H sync
        # on the scheduler thread ahead of every packed dispatch.
        self._zero_kd = np.asarray(jax.random.key_data(jax.random.key(0)))
        self._queue: queue.Queue[_Request | None] = queue.Queue()
        # Control operations (KV export/import, fleet introspection):
        # closures any thread may enqueue that MUST run on the scheduler
        # thread — the radix prefix cache and slot truth are
        # single-threaded by design.  Drained at the top of every
        # admission phase; one empty get_nowait per tick when idle.
        self._control_ops: queue.Queue = queue.Queue()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        # Admission control (the data-plane half of the autoscaling
        # subsystem): a token-denominated bound on queued-but-unadmitted
        # work.  0 (default) = unbounded, the old admission behavior
        # byte-for-byte — submit still takes the lock, but only to read
        # a flag that is always False.
        self._admission_budget = int(admission_queue_budget or 0)
        if self._admission_budget < 0:
            raise ValueError(
                "admission_queue_budget must be >= 0, got "
                f"{admission_queue_budget}"
            )
        self._adm_lock = threading.Lock()
        self._queued_est_tokens = 0
        # Per-model fairness ledger (multiplexed warm pool): estimated
        # tokens outstanding per attached-model id, HTTP-request scoped
        # (reserved in reserve_admission, returned by
        # release_model_admission when the carrying request finishes).
        # Empty — and every branch reading it dead — unless a caller
        # passes model=, so single-model admission is byte-identical.
        self._model_est: dict[str, int] = {}
        self._inflight_reqs = 0  # submitted futures not yet done
        self._draining = False
        self._on_shed = on_shed
        self.shed_total = 0  # sheds by any reason (metrics mirror)
        self.tokens_generated = 0
        # Prefix-cache observability (read by tests/test_prefix_cache.py
        # and the Prometheus hookups in app.make_gen_engine).
        self.prefix_hits = 0
        self.prefix_cached_tokens = 0
        self.prefix_evictions = 0
        self.prefill_chunks_dispatched = 0
        # Real (unpadded) prompt tokens whose K/V a prefill dispatch
        # wrote; seeded (cached) tokens are prefix_cached_tokens'.
        self.prefill_tokens = 0
        # Weight-streaming prefill dispatches (fused prefills, serial
        # chunk forwards, packed batched calls each count 1):
        # tests/test_packed_prefill.py reads the packed-vs-serial drop
        # here — every dispatch avoided is a full HBM weight stream
        # the admissions shared instead of re-paying.
        self.prefill_forwards = 0
        # Speculative/fused observability (read by tests/test_speculative.py
        # and tests/test_multistep.py):
        # decode_forwards counts every decode/verify/multistep DISPATCH,
        # decode_tokens every token those dispatches emitted.  In the
        # single-step loop a dispatch is one weight stream and the ratio
        # is exactly 1/(active slots); speculative acceptance drives it
        # lower per weight stream, while a fused K-step dispatch streams
        # the weights K times under ONE dispatch — so this ratio is the
        # per-DISPATCH amortization (host overhead), not
        # weight-streams-per-token, once decodeSteps > 1.
        self.decode_forwards = 0
        self.decode_tokens = 0
        self.spec_verify_ticks = 0
        self.spec_proposed_tokens = 0
        self.spec_accepted_tokens = 0

    def _reset_device_state(self) -> None:
        """(Re)allocate the KV cache and token buffers.

        Also the recovery path after a failed jitted step: donation has
        already invalidated the old buffers, so continuing with them would
        raise "Array has been deleted" on every subsequent request."""
        import jax
        import jax.numpy as jnp

        from ..models import llama

        if getattr(self, "_kv_quant", False):
            cache = llama.QuantRaggedKVCache.create(self._cfg, self.max_slots)
            self._cache_k = (cache.k8, cache.k_scale)
            self._cache_v = (cache.v8, cache.v_scale)
        else:
            cache = self._lm.RaggedKVCache.create(
                self._cfg, self.max_slots, self._dtype
            )
            self._cache_k, self._cache_v = cache.k, cache.v
        self._lengths = cache.lengths
        self._tokens = jnp.zeros((self.max_slots, 1), jnp.int32)
        # Per-slot sampling state (arrays so one compiled decode serves any
        # mix of greedy and sampled requests).
        self._keys = jax.random.split(jax.random.key(0), self.max_slots)
        self._temps = jnp.zeros((self.max_slots,), jnp.float32)
        self._topk = jnp.zeros((self.max_slots,), jnp.int32)
        self._topp = jnp.ones((self.max_slots,), jnp.float32)
        if getattr(self, "_mesh", None) is not None:
            # Commit the state to its mesh shardings up front (cache
            # heads on tp, everything else replicated): the programs'
            # explicit out shardings keep them there, so donation reuses
            # the sharded buffers and no tick ever re-lays-out.
            self._cache_k = jax.device_put(self._cache_k, self._shard_kv)
            self._cache_v = jax.device_put(self._cache_v, self._shard_kv)
            put = lambda x: jax.device_put(x, self._shard_rep)
            self._lengths = put(self._lengths)
            self._tokens = put(self._tokens)
            self._keys = put(self._keys)
            self._temps = put(self._temps)
            self._topk = put(self._topk)
            self._topp = put(self._topp)
        # Fused-decode chain state (device-resident active mask / budgets
        # / EOS ids): valid only WITHIN one fused burst — every burst
        # re-seeds it from host slot truth, so a recovery reset needs no
        # special handling beyond dropping the stale references.
        self._ms_active = None
        self._ms_remaining = None
        self._ms_eos = None
        self._moe_pending = []  # device scalars of the programs just lost
        # Prefill-side programs dispatched and not waited for yet, the step
        # in flight (lost with the rest), the last completion seen.
        self._open_ticks: list[_OpenTick] = []
        self._ahead: _StepOut | None = None
        self._done_at = 0.0

    def _put_seq(self, buf):
        """Commit a fresh batch-1 prefill scratch buffer to the seq-cache
        sharding (no-op without a mesh)."""
        if self._mesh is None:
            return buf
        import jax

        return jax.device_put(buf, self._shard_seq)

    # -- lifecycle -----------------------------------------------------------

    def start(self, warmup: bool = True) -> None:
        if warmup:
            self._warmup()
        if self._watchdog is not None:
            # Arm AFTER warmup: the compile sweep legitimately blocks
            # far past any sane tick deadline.
            self._watchdog.arm()
            self._watchdog.start()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="generation-scheduler"
        )
        self._thread.start()

    def _beat(self, kind: str | None = None) -> None:
        """One scheduler heartbeat (no-op without a watchdog — the
        default keeps the loop byte-for-byte)."""
        if self._watchdog is not None:
            self._watchdog.beat(kind)

    def _slot_inventory(self) -> list:
        """Best-effort in-flight snapshot for the watchdog's stall event
        (called from the MONITOR thread while the scheduler is wedged —
        reads race its last mutation by design; the watchdog tolerates
        raises)."""
        inv = []
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            inv.append({
                "slot": i,
                "request_id": slot.request_id,
                "prompt_len": int(slot.prompt_len),
                "generated": len(slot.generated),
                "remaining": int(slot.remaining),
            })
        for prog in list(self._pending):
            inv.append({
                "slot": int(getattr(prog, "slot", -1)),
                "request_id": prog.req.request_id,
                "prompt_len": int(prog.req.prompt.size),
                "generated": 0,
                "remaining": int(prog.req.max_new_tokens),
                "admitting": True,
            })
        return inv

    def _warmup(self) -> None:
        """Compile every decode program before readiness, so no live request
        pays an XLA compile (the persistent compile cache makes this
        near-instant on a warm node).

        "Every" means both decode variants (greedy / sampling) at EVERY
        power-of-two attention-window bucket up to capacity — window is a
        static jit arg, so each bucket is its own executable and a lazily
        compiled one would stall the single scheduler thread (and every
        in-flight stream) for seconds the first time traffic crosses a
        bucket boundary."""
        import jax
        import jax.numpy as jnp

        t0 = time.perf_counter()
        self._in_warmup = True
        if self._telemetry is not None:
            # Compile observatory: the sweep's compiles/seconds roll up
            # into a warmup report, warned about when they exceed the
            # readiness budget (the kubelet's probe window).
            self._telemetry.observatory.begin_warmup()
        try:
            if self._prefix_cache is not None:
                # Compile the prefix-cache seed (dispatched: followers
                # must compile it too) and the leader-side chunk read-back
                # before readiness — a lazy compile on the first warm
                # admission would stall the scheduler thread.  Runs FIRST:
                # the admissions below dispatch fresh-chunk + insert ops
                # that drop the seeded scratch buffers on every host.
                C = self._prefill_chunk_size
                shape = (
                    self._cfg.num_layers, 1, C,
                    self._cfg.num_kv_heads, self._cfg.head_dim,
                )
                zk = np.asarray(jnp.zeros(shape, self._dtype))
                if self._packed:
                    # Packed mode seeds/reads the reserved cache row
                    # directly — different executables than the scratch
                    # path (zeros into row 0 == the freshly allocated
                    # state, so nothing to clean up after).
                    self._dispatch_seed_slot([(zk, zk)], 0, C)
                    self._read_slot(
                        self._cache_k, self._cache_v,
                        jnp.int32(0), jnp.int32(0),
                    )
                else:
                    self._dispatch_seed([(zk, zk)], C)
                    _, sk, sv, _slen = self._seq_state
                    self._read_chunk(sk, sv, jnp.int32(0))
                if self._preemption:
                    # Evict/restore path: the slot-targeted read/seed
                    # pair (packed mode compiled them above) plus the
                    # restore finalize — all dispatched or leader-cheap,
                    # so the first live eviction never compiles on the
                    # scheduler thread.
                    if not self._packed:
                        self._dispatch_seed_slot([(zk, zk)], 0, C)
                        self._read_slot(
                            self._cache_k, self._cache_v,
                            jnp.int32(0), jnp.int32(0),
                        )
                    self._dispatch_restore(
                        0, C, 1, np.asarray(jax.random.key_data(
                            jax.random.key(0))),
                        0.0, 0, 1.0,
                    )
            if self._packed and not self._unified:
                # Packed-prefill variants: one executable per B_p bucket
                # (the ids shape is what jit caches on).  Dispatched, not
                # raw: followers of a multihost unit must compile the
                # same buckets.  The fully parked batch shares the live
                # path's construction site, so warmed shapes cannot
                # drift from what _packed_tick dispatches.  The unified
                # engine has no packed program: chunks ride the
                # super-step variants swept below.
                for bucket in self._pack_buckets():
                    self._dispatch_chunks(*self._parked_batch(bucket))
            self._admit_now(
                _Request(
                    prompt=np.array([1], np.int32),
                    max_new_tokens=2,
                    eos_id=None,
                    future=Future(),
                )
            )
            self._step()  # greedy decode variant, smallest window
            self._slots = [None] * self.max_slots
            self._admit_now(
                _Request(
                    prompt=np.array([1], np.int32),
                    max_new_tokens=2,
                    eos_id=None,
                    future=Future(),
                    temperature=1.0,
                    seed=0,
                )
            )
            self._step()  # sampling decode variant, smallest window
            # Remaining window buckets, both variants, on inert state
            # (active all-False advances nothing; warmup resets state
            # after).  Dispatched, not raw: followers of a multihost unit
            # must compile the same buckets or the first bucket crossing
            # stalls the whole slice.
            inactive = np.zeros((self.max_slots,), bool)
            smallest = decode_window_bucket(1, self.capacity)
            if self._unified:
                # THE K-fold collapse: one super-step variant per
                # (window bucket x sampling mode) covers what the split
                # engine sweeps as decode x 2 + verify x |chain| +
                # multistep x 2 + packed B_p buckets.  Every window is
                # swept (the dummy admits above may land on a larger
                # bucket when decode_steps pushes length + K - 1 over
                # the smallest); re-dispatching a compiled variant is a
                # jit cache hit.  Parked batches (all-idle roles, zero
                # counts) advance nothing, exactly like the inactive
                # decode sweeps.
                for window in decode_window_buckets(self.capacity):
                    self._dispatch_superstep(
                        *self._parked_superstep(), window, False
                    )
                    self._dispatch_superstep(
                        *self._parked_superstep(), window, True
                    )
            else:
                for window in decode_window_buckets(self.capacity):
                    if window == smallest:
                        continue  # both variants already compiled above
                    self._dispatch_step(inactive, window, False)
                    self._dispatch_step(inactive, window, True)
            if self._spec is not None and not self._unified:
                # Verify variants: one executable per (draft length,
                # window) pair — draft lengths are capped to the halving
                # chain so this sweep stays |chain| x |buckets|, not
                # draftTokens x |buckets|.  Dispatched (not raw): lazy
                # compiles on a follower would stall the whole slice at
                # the first live verify.
                zero_draft = np.zeros((self.max_slots,), np.int32)
                for window in decode_window_buckets(self.capacity):
                    for s_draft in self._spec_chain:
                        toks = np.zeros(
                            (self.max_slots, s_draft + 1), np.int32
                        )
                        self._dispatch_verify(
                            toks, inactive, zero_draft, window
                        )
            if self._fused and not self._unified:
                # Fused multi-step variants: one executable per
                # (K, window) pair, both token rules — K is fixed per
                # deployment so the sweep is |buckets| x 2.  Dispatched,
                # not raw: followers must compile the same variants or
                # the first live fused tick stalls the whole slice.
                # All-inactive, zero-budget rows advance nothing.
                zero_rem = np.zeros((self.max_slots,), np.int32)
                no_eos = np.full((self.max_slots,), -1, np.int32)
                for window in decode_window_buckets(self.capacity):
                    self._dispatch_multistep(
                        inactive, zero_rem, no_eos, window, False
                    )
                    self._dispatch_multistep(
                        inactive, zero_rem, no_eos, window, True
                    )
            # Fused-prefill buckets: each power-of-two prompt bucket is its
            # own executable (the padded ids shape is static), so admit one
            # dummy prompt per bucket — otherwise the first live request at
            # a larger bucket pays the XLA compile on the single scheduler
            # thread and stalls every in-flight stream.  Chunked prefill
            # runs one fixed-size program per chunk; no sweep needed there.
            if self._prefill_chunk_size is None:
                bucket = _MIN_BUCKET
                while bucket < self.capacity:
                    bucket = min(bucket * 2, self.capacity)
                    # max_new_tokens=1 resolves at admission, so the slot
                    # frees itself inside _admit — no cleanup needed.
                    self._admit_now(
                        _Request(
                            prompt=np.ones((bucket,), np.int32),
                            max_new_tokens=1,
                            eos_id=None,
                            future=Future(),
                        )
                    )
            if self._sp > 1 and self._sp_threshold <= self.capacity:
                # sp ring-prefill variants: one executable per power-of-
                # two prompt bucket at or above the routing threshold
                # (plus the [1, V] insert variant, shared across
                # buckets).  Dispatched via _admit_now -> _admit_sp so
                # followers of a multihost unit compile the same ring
                # programs.  The prompt length >= threshold guarantees
                # the sp route fires regardless of chunked/fused mode.
                bucket = prefill_bucket(self._sp_threshold, self.capacity)
                while True:
                    self._admit_now(
                        _Request(
                            prompt=np.ones((bucket,), np.int32),
                            max_new_tokens=1,
                            eos_id=None,
                            future=Future(),
                        )
                    )
                    if bucket >= self.capacity:
                        break
                    bucket = min(bucket * 2, self.capacity)
        finally:
            self._in_warmup = False
            if self._telemetry is not None:
                self._telemetry.observatory.end_warmup()
        # Reset state so warmup tokens never leak into a real response.
        slot = self._slots[0]
        if slot is not None:
            slot.future.cancel()
        self._slots = [None] * self.max_slots
        _log.info("generation warmup in %.1fs", time.perf_counter() - t0)

    def shutdown(self) -> None:
        if self._watchdog is not None:
            # Disarm BEFORE the join: teardown legitimately stops
            # beating, and an escalation mid-shutdown would turn a clean
            # drain into an os._exit.
            self._watchdog.disarm()
            self._watchdog.stop()
        self._stop.set()
        self._queue.put(None)  # unblock the scheduler
        if self._thread is not None:
            self._thread.join(timeout=30)
        for prog in self._pending:
            # A chunked admission in flight is in neither the queue nor a
            # slot; fail it LOUDLY or its client awaits forever.
            self._abort_trace(prog.req.trace, "shutdown")
            if not prog.req.future.done():
                _safe_fail(
                    prog.req.future,
                    EngineShutdown(
                        "engine shut down mid-prefill; retry on another "
                        "replica"
                    ),
                )
        self._pending = []
        self._reserved.clear()
        self._seq_state = None
        for slot in self._slots:
            if slot is not None and not slot.future.done():
                self._abort_trace(slot.trace, "shutdown")
                slot.future.cancel()
        while True:
            try:
                fn_fut = self._control_ops.get_nowait()
            except queue.Empty:
                break
            _safe_fail(
                fn_fut[1],
                EngineShutdown("engine shut down before the control op ran"),
            )
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            if isinstance(req, _Wake):
                continue
            if req is not None:
                self._release_queued(req)
            if req is not None and not req.future.done():
                # Queued-but-unadmitted: a clear EngineShutdown beats a
                # bare CancelledError — callers can distinguish "the
                # server is going away, retry elsewhere" from a client-
                # side cancel.
                self._abort_trace(req.trace, "shutdown")
                _safe_fail(
                    req.future,
                    EngineShutdown(
                        "engine shut down before admission; retry on "
                        "another replica"
                    ),
                )
        if self._class_queues is not None:
            # Class deques hold dequeued-but-unadmitted requests AND
            # evicted sequences awaiting restore — fail both loudly.
            for dq in self._class_queues.values():
                while dq:
                    item = dq.popleft()
                    if isinstance(item, _Request):
                        self._release_queued(item)
                    if not item.future.done():
                        self._abort_trace(item.trace, "shutdown")
                        _safe_fail(
                            item.future,
                            EngineShutdown(
                                "engine shut down before admission; "
                                "retry on another replica"
                            ),
                        )

    def _abort_trace(self, trace, reason: str) -> None:
        """Finish a request trace off the normal token path (shutdown /
        engine failure) so its span still closes in the recorder."""
        if trace is None:
            return
        trace.finish(reason)
        if self._recorder is not None:
            self._recorder.event(trace.request_id, "finish", slot=trace.slot)
            self._recorder.complete(trace)

    # -- admission control / drain (client-facing) ---------------------------

    def reserve_admission(
        self,
        est_tokens: int,
        slo_class: str | None = None,
        model: str | None = None,
    ) -> None:
        """Reserve queue room for ``est_tokens`` or shed.

        Raises :class:`EngineOverloaded` when the engine is draining, or
        when the reservation would push queued-but-unadmitted estimated
        tokens past the admission budget; otherwise the tokens are
        counted (released exactly once when the scheduler dequeues the
        carrying request).  Callers batching several prompts into one
        HTTP request reserve the TOTAL up front, so a request is
        admitted whole or shed whole — never half-admitted with
        siblings generating into abandoned futures.

        With SLO classes armed, each class sheds at its own fraction of
        the budget (``_CLASS_BUDGET_FACTOR``): a best-effort request
        refused at half-full queue sheds with reason
        ``class_best-effort`` — distinguishable on dashboards from the
        full-budget ``budget`` overload interactive traffic hits.

        ``model`` (multiplexed warm pool: the model id the router
        addressed) arms per-model fairness: with two or more models
        holding outstanding work, each is bounded by an equal SHARE of
        the budget instead of the whole budget — a flooded hot model
        sheds with reason ``model_budget`` at its share while a tail
        model with nothing outstanding is still admitted, so the shared
        queue cannot starve cold models.  The caller returns the
        reservation via :meth:`release_model_admission` when the
        carrying HTTP request finishes.
        """
        cls = None
        if self._classes:
            cls = slo_class or self._slo_default or "interactive"
        with self._adm_lock:
            if self._draining:
                self._note_shed("draining")
                raise EngineOverloaded(
                    "engine is draining; retry on another replica",
                    reason="draining",
                    retry_after_s=1,
                    slo_class=cls,
                )
            budget = self._admission_budget
            eff_budget, reason = budget, "budget"
            if cls is not None and budget:
                factor = _CLASS_BUDGET_FACTOR.get(cls, 1.0)
                if factor < 1.0:
                    eff_budget = int(budget * factor)
                    reason = f"class_{cls}"
            fair_share = None
            if eff_budget and model is not None:
                active = {m for m, v in self._model_est.items() if v > 0}
                active.add(model)
                if len(active) >= 2:
                    # Two or more models contending: this model's bound
                    # becomes budget/n INSTEAD of the global backlog
                    # check below — the global check would let a hot
                    # model's backlog shed the tail model's first
                    # request, the exact starvation fairness exists to
                    # prevent.
                    fair_share = max(1, eff_budget // len(active))
                    mine = self._model_est.get(model, 0)
                    if mine > 0 and mine + est_tokens > fair_share:
                        self._note_shed("model_budget")
                        raise EngineOverloaded(
                            f"model {model!r} admission share full: "
                            f"{mine} estimated tokens outstanding + "
                            f"{est_tokens} requested > share "
                            f"{fair_share} ({eff_budget} budget / "
                            f"{len(active)} active models); retry "
                            "after the share drains",
                            reason="model_budget",
                            retry_after_s=1,
                            slo_class=cls,
                        )
            # The budget bounds the BACKLOG, not request size: with the
            # queue empty, any request validate() allowed is admitted —
            # otherwise a single request whose estimate alone exceeds
            # the budget would shed identically on every replica, a
            # deterministic fleet-wide 429 outage for work the engine
            # could run directly.
            if (
                fair_share is None
                and eff_budget
                and self._queued_est_tokens > 0
                and self._queued_est_tokens + est_tokens > eff_budget
            ):
                self._note_shed(reason)
                raise EngineOverloaded(
                    f"admission queue full: {self._queued_est_tokens} "
                    f"estimated tokens queued + {est_tokens} requested "
                    f"> budget {eff_budget}; retry on another replica",
                    reason=reason,
                    retry_after_s=1,
                    slo_class=cls,
                )
            self._queued_est_tokens += est_tokens
            if model is not None:
                self._model_est[model] = (
                    self._model_est.get(model, 0) + est_tokens
                )

    def _note_shed(self, reason: str) -> None:
        # _adm_lock held: counter mutations stay consistent with the
        # decision that produced them.
        self.shed_total += 1
        if self._on_shed is not None:
            self._on_shed(reason)

    def release_model_admission(self, model: str | None, est_tokens: int) -> None:
        """Return a per-model fairness reservation (HTTP-request scoped
        counterpart of the ``model=`` arm of :meth:`reserve_admission`)."""
        if not model or not est_tokens:
            return
        with self._adm_lock:
            left = self._model_est.get(model, 0) - est_tokens
            if left > 0:
                self._model_est[model] = left
            else:
                self._model_est.pop(model, None)

    def _release_queued(self, req: _Request) -> None:
        """Return a dequeued request's reservation (idempotent)."""
        if req.est_tokens:
            with self._adm_lock:
                self._queued_est_tokens -= req.est_tokens
            req.est_tokens = 0

    def begin_drain(self) -> None:
        """Stop admissions: every later submit sheds with 429-mapped
        :class:`EngineOverloaded`; already-queued and in-flight
        sequences run to completion (that is what makes the drain
        lossless).  The scheduler loop keeps ticking until
        :meth:`shutdown`."""
        with self._adm_lock:
            self._draining = True

    def cancel_drain(self) -> None:
        """Reopen admissions (an operator cancelled the drain); nothing
        in flight was disturbed, so this is just the flag."""
        with self._adm_lock:
            self._draining = False

    @property
    def draining(self) -> bool:
        return self._draining

    def inflight(self) -> int:
        """Submitted sequences whose futures are not yet done.

        Counted at the future boundary, NOT by summing queue + pending +
        slots: a request being moved between those structures on the
        scheduler thread would transiently vanish from a structural sum,
        and a drain waiter hitting that gap would tear the server down
        with work in flight — the one request the drain exists to save.
        """
        with self._adm_lock:
            return self._inflight_reqs

    def drained(self) -> bool:
        return self._draining and self.inflight() == 0

    # -- client API ----------------------------------------------------------

    def validate(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: int,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        seed: int | None = None,
    ) -> np.ndarray:
        """Check a request without admitting it; returns the int32 prompt.

        Callers batching several prompts into one HTTP request validate ALL
        of them first, so a bad one rejects the request before any sibling
        has been admitted and left generating into an abandoned future.
        """
        try:
            # int64 first: ids >= 2**31 would raise OverflowError straight
            # from an int32 asarray, and that escaped to clients as a 500.
            prompt = np.asarray(prompt_ids, np.int64).reshape(-1)
        except (OverflowError, ValueError, TypeError) as e:
            raise ValueError(f"prompt ids must be integers: {e}") from None
        if prompt.size == 0:
            raise ValueError("empty prompt")
        vocab = int(getattr(self._cfg, "vocab_size", 0))
        if int(prompt.min()) < 0 or (vocab and int(prompt.max()) >= vocab):
            # Out-of-range ids would silently clamp in jnp.take and return
            # garbage completions as 200s; reject at the door instead.
            raise ValueError(
                f"prompt ids must be in [0, {vocab}), got range "
                f"[{int(prompt.min())}, {int(prompt.max())}]"
            )
        prompt = prompt.astype(np.int32)
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        total = prompt.size + max_new_tokens
        if total > self.capacity:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new_tokens}) "
                f"= {total} exceeds KV-cache capacity {self.capacity}"
            )
        if not (0.0 <= float(temperature) <= 100.0):
            raise ValueError(f"temperature must be in [0, 100], got {temperature}")
        if not (0 <= int(top_k) < 2**31):
            # top_k is lowered to jnp.int32 in _admit; an out-of-range value
            # passing validation would raise OverflowError inside the jitted
            # step and _fail_all_and_recover would kill every in-flight
            # request over one malformed one.
            raise ValueError(f"top_k must be in [0, 2**31), got {top_k}")
        if not (0.0 < float(top_p) <= 1.0):
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if seed is not None and not (0 <= int(seed) < 2**63):
            # jax.random.key takes an int64; reject before admission so one
            # bad request can't poison the scheduler for everyone else.
            raise ValueError(f"seed must be in [0, 2**63), got {seed}")
        # Poison quarantine: a prompt whose admission crashed the engine
        # twice is refused at the door (typed 422 upstream) instead of
        # getting a third shot at crash-looping the replica.  The dict
        # gate keeps the hot path hash-free until a crash ever happens.
        if self._quarantined:
            fp = self._fingerprint(prompt)
            with self._poison_lock:
                crashes = self._quarantined.get(fp)
            if crashes is not None:
                self.poison_rejected_total += 1
                if self._on_poison is not None:
                    self._on_poison("rejected")
                raise PoisonRequest(fp, crashes)
        return prompt

    # -- poison-request quarantine -------------------------------------------

    # Crashes of the same prompt fingerprint before submits refuse it:
    # the first crash could be anything (device wedge, OOM race), the
    # second with every OTHER request meanwhile fine is the prompt.
    POISON_CRASH_THRESHOLD = 2

    @staticmethod
    def _fingerprint(prompt: np.ndarray) -> str:
        import hashlib

        return hashlib.sha256(
            np.ascontiguousarray(prompt, np.int64).tobytes()
        ).hexdigest()[:16]

    def _note_admission_crash(self, reqs) -> None:
        """Attribute an admission/prefill crash to the implicated
        request(s) by prompt fingerprint; quarantine at the threshold.

        Called from the scheduler thread's crash handlers only — decode
        crashes are NOT attributed (every slot was in flight; blaming
        any of them would quarantine innocents).  In packed mode all
        batched admissions are implicated: the poison one accumulates
        toward the threshold on every retry while innocents' counts
        only grow if they keep co-batching with it."""
        for req in reqs:
            if req is None:
                continue
            try:
                fp = self._fingerprint(req.prompt)
            except Exception:
                continue
            newly = False
            with self._poison_lock:
                n = self._poison_counts.get(fp, 0) + 1
                self._poison_counts[fp] = n
                if n >= self.POISON_CRASH_THRESHOLD and fp not in self._quarantined:
                    self._quarantined[fp] = n
                    newly = True
            if newly:
                self.poison_quarantined_total += 1
                _log.error(
                    "poison quarantine: prompt fingerprint %s crashed "
                    "admission %d times; further submits are refused "
                    "with a typed 422",
                    fp, n,
                )
                if self._on_poison is not None:
                    self._on_poison("quarantined")
                if self._recorder is not None:
                    self._recorder.event(
                        req.request_id or "", "poison-quarantine",
                        fingerprint=fp, crashes=n,
                    )

    def submit(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: int,
        eos_id: int | None = None,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        seed: int | None = None,
        on_token: Callable[[int], None] | None = None,
        request_id: str = "",
        trace=None,  # flight_recorder.RequestTrace | None
        est_reserved: bool = False,
        slo_class: str | None = None,
    ) -> Future:
        prompt = self.validate(
            prompt_ids, max_new_tokens, temperature, top_k, top_p, seed
        )
        # Per-request class overrides the engine default (one engine
        # serves mixed traffic); meaningless when classes are unarmed.
        if slo_class is not None and slo_class not in SLO_CLASSES:
            raise ValueError(
                f"slo_class must be one of {SLO_CLASSES}, got "
                f"{slo_class!r}"
            )
        cls = slo_class or self._slo_default or "interactive"
        # Admission control: shed BEFORE anything is enqueued (429 at
        # the door, never a half-admitted request).  est_reserved=True
        # means the caller already took the whole multi-prompt request's
        # reservation through reserve_admission.
        est = int(prompt.size) + int(max_new_tokens)
        if not est_reserved:
            self.reserve_admission(est, slo_class=cls)
        fut: Future = Future()
        # None means "use the engine default"; 0 is a legitimate eos token.
        eos = self._eos_default if eos_id is None else eos_id
        t_submit = time.perf_counter()
        if trace is not None:
            trace.t_submit = t_submit
            trace.prompt_tokens = int(prompt.size)
            if not trace.request_id:
                trace.request_id = request_id
            if self._recorder is not None:
                self._recorder.event(trace.request_id, "enqueued")
        with self._adm_lock:
            self._inflight_reqs += 1
        fut.add_done_callback(self._note_request_done)
        self._queue.put(
            _Request(
                prompt,
                int(max_new_tokens),
                eos,
                fut,
                temperature=float(temperature),
                top_k=int(top_k),
                top_p=float(top_p),
                seed=seed,
                on_token=on_token,
                t_submit=t_submit,
                request_id=request_id,
                trace=trace,
                # Always the reservation size: every submit reserved
                # (itself or via the caller's batch reserve_admission),
                # and the dequeue-side release must mirror it exactly.
                est_tokens=est,
                slo_class=cls,
            )
        )
        return fut

    def _note_request_done(self, _fut: Future) -> None:
        # Fires exactly once per submitted future (result, exception, or
        # cancel) — the drain waiter's in-flight count lives here.
        with self._adm_lock:
            self._inflight_reqs -= 1

    def generate(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: int,
        eos_id: int | None = None,
        timeout: float | None = 120.0,
        **sampling,
    ) -> np.ndarray:
        """Blocking convenience wrapper around :meth:`submit`."""
        return self.submit(
            prompt_ids, max_new_tokens, eos_id, **sampling
        ).result(timeout)

    # -- scheduler -----------------------------------------------------------

    def _free_slot(self) -> int | None:
        free = [
            i for i, s in enumerate(self._slots)
            if s is None and i not in self._reserved
        ]
        if not free:
            return None
        if self._dp <= 1:
            return free[0]
        # dp > 1: the cache's row axis shards in contiguous blocks of
        # max_slots/dp, so slot index // rows IS the dp shard.  Admit
        # into the least-loaded shard (ties -> lowest index) — filling
        # slots 0..k-1 first would park every active row on shard 0 and
        # idle the rest of the dp axis.
        rows = self.max_slots // self._dp

        def shard_load(shard: int) -> int:
            return sum(
                1 for i in range(shard * rows, (shard + 1) * rows)
                if self._slots[i] is not None or i in self._reserved
            )

        return min(free, key=lambda i: (shard_load(i // rows), i))

    # -- SLO classes / preemption --------------------------------------------

    def _queued_work(self) -> bool:
        """True when any submission waits — transport queue OR class
        deques (a drained-but-unadmitted request must still break a
        fused burst / keep the fused-prefill gate closed)."""
        if not self._queue.empty():
            return True
        return self._class_queues is not None and any(
            self._class_queues[name] for name in SLO_CLASSES
        )

    def _drain_to_classes(self) -> None:
        """Route every immediately available submission from the
        transport queue into its class deque (classes armed only).  The
        None shutdown sentinel and _Wake are pushed back for the
        blocking path — they must be observed in the admission loop,
        not swallowed here."""
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                return
            if item is None or isinstance(item, _Wake):
                self._queue.put(item)
                return
            self._class_queues[item.slo_class].append(item)

    def _dequeue(self, block: bool, timeout: float):
        """``self._queue.get`` with class priority.

        Unarmed classes make this EXACTLY the plain ``get`` call it
        replaces.  Armed: drain the transport queue into the per-class
        deques and pop the highest class first (FIFO within a class;
        evicted sequences re-enter at the front of theirs), falling
        back to a blocking get only when every deque is empty."""
        if self._class_queues is None:
            return self._queue.get(block=block, timeout=timeout)
        self._drain_to_classes()
        for name in SLO_CLASSES:
            dq = self._class_queues[name]
            if dq:
                return dq.popleft()
        item = self._queue.get(block=block, timeout=timeout)
        if item is None or isinstance(item, _Wake):
            return item
        # A burst may have landed while we blocked: route through the
        # deques so it is admitted in class order, not arrival order.
        self._class_queues[item.slo_class].append(item)
        self._drain_to_classes()
        for name in SLO_CLASSES:
            dq = self._class_queues[name]
            if dq:
                return dq.popleft()
        raise AssertionError("unreachable: item was just enqueued")

    def _maybe_preempt(self) -> None:
        """Tick-boundary preemption: when a strictly higher-class
        request waits with no free slot, evict the lowest-class active
        slot (least progress, then lowest index breaks ties) so the
        waiting work admits next iteration.  At most one eviction per
        scheduler iteration — preemption tracks demand, it never
        flushes the batch."""
        if self._free_slot() is not None:
            return
        self._drain_to_classes()
        waiting = next(
            (n for n in SLO_CLASSES if self._class_queues[n]), None
        )
        if waiting is None:
            return
        wprio = _CLASS_PRIORITY[waiting]
        victim = None
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            prio = _CLASS_PRIORITY.get(slot.slo_class, wprio)
            if prio >= wprio:
                continue
            key = (prio, slot.prompt_len + len(slot.generated), i)
            if victim is None or key < victim[0]:
                victim = (key, i)
        if victim is not None:
            # The eviction reads the cache: no tick open under it (and a
            # chunk that failed at its wait has emptied the slots).
            self._settle_ticks()
            if self._slots[victim[1]] is not None:
                self._evict_slot(victim[1])

    def _evict_slot(self, idx: int) -> None:
        """Evict one active slot at a tick boundary, losing no work.

        The committed K/V (positions 0..hist-1, where hist = prompt +
        generated - 1: the pending token has not been fed yet) is read
        off the device with the SAME slot-chunk program the prefix
        cache uses, so full chunks re-enter the radix cache — siblings
        reuse them, and restore re-seeds from whichever tier they
        landed in.  Host copies of every chunk ride the eviction record
        as the fallback (interleaved admissions may evict the cache
        entries before restore).  The PRNG carry and the pending token
        complete the record: restore resumes the sequence exactly.

        Leader-only, NO broadcast: ``_read_slot`` is a pure read (no
        donation), followers' device state is untouched, and the
        freed slot's stale rows are exactly the normal slot-reuse case
        every program already tolerates."""
        import jax
        import jax.numpy as jnp

        slot = self._slots[idx]
        assert slot is not None and slot.prompt is not None
        t0 = time.perf_counter()
        self._beat("preempt")
        C = self._prefill_chunk_size
        hist = slot.prompt_len + len(slot.generated) - 1
        full = np.empty((hist,), np.int32)
        full[: slot.prompt_len] = slot.prompt
        if len(slot.generated) > 1:
            full[slot.prompt_len:] = np.asarray(
                slot.generated[:-1], np.int32
            )
        n_chunks = -(-hist // C)
        chunks = []
        for ci in range(n_chunks):
            ck, cv = self._read_slot(
                self._cache_k, self._cache_v,
                jnp.int32(idx), jnp.int32(ci * C),
            )
            chunks.append((np.asarray(ck), np.asarray(cv)))
        # Whole-token chunks only: the tail chunk holds garbage past
        # hist and must never be shared under a token-byte key.
        for ci in range(hist // C):
            if not self._prefix_cache.has_chunk(full, ci):
                if not self._prefix_cache.insert_chunk(
                    full, ci, *chunks[ci]
                ):
                    break  # parent path evicted mid-insert: stop here
        key_data = np.asarray(jax.random.key_data(self._keys))[idx].copy()
        rec = _Preempted(
            future=slot.future,
            remaining=slot.remaining,
            eos_id=slot.eos_id,
            sampling=slot.sampling,
            on_token=slot.on_token,
            prompt=slot.prompt,
            generated=slot.generated,
            t_start=slot.t_start,
            request_id=slot.request_id,
            trace=slot.trace,
            slo_class=slot.slo_class,
            temperature=slot.temperature,
            top_k=slot.top_k,
            top_p=slot.top_p,
            key_data=key_data,
            chunks=chunks,
            hist=hist,
            history=slot.history,
            hist_len=slot.hist_len,
            draft=slot.draft,
        )
        self._slots[idx] = None
        # FRONT of its own class: the evicted sequence outranks newer
        # work of the same class on re-admission.
        self._class_queues[slot.slo_class].appendleft(rec)
        self.preemptions += 1
        if not self._in_warmup:
            self._record_tick(
                "preempt-evict", t0, time.perf_counter() - t0,
                active_slots=sum(s is not None for s in self._slots),
                tokens=hist,
            )
            self._trace_event(slot.trace, "preempt-evict", slot=idx)
            if self._on_preempt is not None:
                self._on_preempt("evict")

    def _admit_restore(self, rec: _Preempted) -> None:
        """Re-admit an evicted sequence into a free slot, resuming it
        token-for-token where the eviction cut it.

        K/V re-seeds through the radix cache where its chunks survived
        (counting prefix hits — an L2-spilled chunk promotes on the
        way), falling back to the record's host copies; then ONE
        restore dispatch re-installs the slot's lengths row, pending
        token, sampling params, and the PRNG carry AS CAPTURED.  No
        token is re-generated: ``preempt_recomputed_tokens`` stays 0
        by construction."""
        slot_idx = self._free_slot()
        assert slot_idx is not None
        t0 = time.perf_counter()
        self._beat("restore")
        C = self._prefill_chunk_size
        hist = rec.hist
        full = np.empty((hist,), np.int32)
        pl = int(rec.prompt.size)
        full[:pl] = rec.prompt
        if hist > pl:
            full[pl:] = np.asarray(
                rec.generated[: hist - pl], np.int32
            )
        matched, cached = self._prefix_cache.lookup(full)
        matched = min(matched, (hist // C) * C)
        seed_chunks = list(cached[: matched // C])
        seed_chunks.extend(rec.chunks[matched // C:])
        with self._span("engine.prefill_dispatch"):
            self._dispatch_seed_slot(seed_chunks, slot_idx, hist)
        if matched:
            self.prefix_hits += 1
            self.prefix_cached_tokens += matched
            if not self._in_warmup and self._on_prefix_hit is not None:
                self._on_prefix_hit(matched)
        with self._span("engine.prefill_dispatch"):
            self._dispatch_restore(
                slot_idx, hist, int(rec.generated[-1]), rec.key_data,
                rec.temperature, rec.top_k, rec.top_p,
            )
        self._slots[slot_idx] = _Slot(
            future=rec.future,
            remaining=rec.remaining,
            eos_id=rec.eos_id,
            sampling=rec.sampling,
            on_token=rec.on_token,
            prompt_len=pl,
            generated=rec.generated,
            t_start=rec.t_start,
            history=rec.history,
            hist_len=rec.hist_len,
            draft=rec.draft,
            request_id=rec.request_id,
            trace=rec.trace,
            slo_class=rec.slo_class,
            prompt=rec.prompt,
            temperature=rec.temperature,
            top_k=rec.top_k,
            top_p=rec.top_p,
        )
        self.preempt_restores += 1
        if not self._in_warmup:
            with self._span("engine.journal"):
                self._record_tick(
                    "preempt-restore", t0, time.perf_counter() - t0,
                    active_slots=sum(s is not None for s in self._slots),
                    tokens=hist,
                    cost=self._cost_seed(hist),
                )
                self._trace_event(
                    rec.trace, "preempt-restore", slot=slot_idx
                )
                if self._on_preempt is not None:
                    self._on_preempt("restore")

    def _dispatch_restore(
        self, slot, length, pending, key_data, temp, tk, tp
    ):
        """Broadcast (multihost) then run the restore finalize — a
        stateful GEN op: every host re-installs the same slot
        bookkeeping or later replayed ticks diverge."""
        if self._channel is None:
            self._device_restore(
                slot, length, pending, key_data, temp, tk, tp
            )
            return
        from .multihost import OP_GEN_RESTORE, encode_message

        payload = encode_message(
            OP_GEN_RESTORE,
            {
                "slot": int(slot),
                "length": int(length),
                "pending": int(pending),
                "key_data": np.asarray(key_data),
                "temp": float(temp),
                "tk": int(tk),
                "tp": float(tp),
            },
        )
        self._channel.run(
            payload,
            lambda: self._device_restore(
                slot, length, pending, key_data, temp, tk, tp
            ),
        )

    def _device_restore(
        self, slot, length, pending, key_data, temp, tk, tp
    ):
        import jax
        import jax.numpy as jnp

        slot_key = jax.random.wrap_key_data(jnp.asarray(key_data))
        (
            self._lengths,
            self._tokens,
            self._keys,
            self._temps,
            self._topk,
            self._topp,
        ) = self._insert_restore(
            self._lengths,
            self._tokens,
            self._keys,
            self._temps,
            self._topk,
            self._topp,
            jnp.int32(slot),
            jnp.int32(length),
            jnp.int32(pending),
            slot_key,
            jnp.float32(temp),
            jnp.int32(tk),
            jnp.float32(tp),
        )

    def replay_restore(
        self, slot, length, pending, key_data, temp, tk, tp
    ) -> None:
        """Follower side of :meth:`_dispatch_restore`."""
        self._device_restore(
            int(slot), int(length), int(pending), np.asarray(key_data),
            float(temp), int(tk), float(tp),
        )

    def _admit(self, req: _Request) -> None:
        import jax

        slot_idx = self._free_slot()
        assert slot_idx is not None
        L = int(req.prompt.size)
        bucket = prefill_bucket(L, self.capacity)
        ids = np.full((1, bucket), self._pad_id, np.int32)
        ids[0, :L] = req.prompt

        # Engine-assigned keys are distinct per request and disjoint from
        # any user-specified jax.random.key(seed) stream (see _slot_key_for).
        slot_key = self._slot_key_for(req)
        span = self._span
        t0 = time.perf_counter()
        self._beat("admit")
        with span("engine.prefill_dispatch"):
            first = self._dispatch_admit(
                ids, slot_idx, L, slot_key, req.temperature, req.top_k, req.top_p,
            )
            self._dispatched("prefill")
        if not self._in_warmup:
            self.prefill_forwards += 1
            self._note_prefill_tokens(L)
            self._open_tick(
                "prefill", t0, first, (req,),
                active_slots=sum(s is not None for s in self._slots),
                batch_fill=1, tokens=1,
                cost=self._cost_prefill(1, bucket),
            )
        if req.trace is not None:
            req.trace.slot = slot_idx
            req.trace.prefill_chunks += 1  # fused: the whole prompt at once
        slot = _Slot(
            future=req.future,
            remaining=req.max_new_tokens,
            eos_id=req.eos_id,
            sampling=req.temperature > 0,
            on_token=req.on_token,
            prompt_len=L,
            t_start=t0,
            request_id=req.request_id,
            trace=req.trace,
            **self._spec_slot_state(req),
            **self._class_slot_state(req),
        )
        self._slots[slot_idx] = slot
        self._emit_first(slot_idx, req, first)

    def _open_tick(
        self, kind: str, t0: float, wait_on, owners=(),
        noted: int | None = None, **fields
    ) -> None:
        """Register a prefill-side program just dispatched.  Nothing waits here:
        :meth:`_close_ticks` journals it (``fields`` are :meth:`_record_tick`'s) at
        the next blocking read.  ``noted`` is the length ``_moe_pending`` had before
        the dispatch: what the program noted since is the tick's until it closes."""
        counts = [] if noted is None else self._moe_pending[noted:]
        if counts:
            del self._moe_pending[noted:]
        self._open_ticks.append(
            _OpenTick(kind, t0, wait_on, tuple(owners), fields, counts)
        )

    def _close_ticks(
        self, behind: str | None = None, first: int | None = None
    ) -> None:
        """A blocking point: read back the step in flight, the oldest program
        out, then wait for the open ticks in device order and journal each
        with the wall the host saw for it alone.
        ``behind`` is the heartbeat kind of a decode-side dispatch already
        queued behind them, None where nothing is.  ``first`` closes only
        that many, the oldest: a step leaves the chunk it sent ahead open.
        Every wait is ``engine.prefill_sync`` (the benchmark's
        ``loop_host_ms`` takes that span as time blocked on the device,
        wherever the loop takes it).  A tick's device error raises
        :class:`_TickFailed`, the step in flight's :class:`_StepFailed`."""
        self._read_ahead()
        if not self._open_ticks:
            return
        import jax

        span = self._span
        n = len(self._open_ticks) if first is None else first
        ticks, self._open_ticks = self._open_ticks[:n], self._open_ticks[n:]
        for tick in ticks:
            self._beat(tick.kind)
            try:
                with span("engine.prefill_sync"):
                    jax.block_until_ready(tick.wait_on)
            except Exception as exc:
                self._open_ticks = []
                raise _TickFailed(tick.kind, tick.owners) from exc
            self._moe_pending.extend(tick.counts)
            start, wall = self._tick_done(tick.t0)
            with span("engine.journal"):
                self._record_tick(tick.kind, start, wall, **tick.fields)
        if behind:
            self._beat(behind)

    def _tick_done(self, t0: float) -> tuple[float, float]:
        """Stamp a completion the engine thread has just seen; returns the tick's
        (start, wall).  The device runs programs in dispatch order, so a program
        dispatched at ``t0`` cannot have started before the one ahead of it was
        seen to end: walls taken in completion order never overlap, and a pass's
        walls sum to no more than the pass.  Was it the last program out, the chip
        has nothing left that the host knows of: an interval of the starvation
        account opens, and :meth:`_dispatched` closes it."""
        now = time.perf_counter()
        start = max(t0, self._done_at)
        self._done_at = now
        if self._unseen:  # 0 through a warm-up sweep: nothing is counted
            self._unseen -= 1
            if not self._unseen:
                self._starved.open()
        return start, now - start

    def _record_tick(
        self, kind: str, t0: float, wall_s: float, *,
        active_slots: int = 0, batch_fill: int = 0, tokens: int = 0,
        spec_accepted: int = 0, cost=None, steps: int = 0,
        roles: dict | None = None,
    ) -> None:
        """Journal one engine device dispatch (tick-kind metric + flight recorder
        + the dispatches-by-op counter).  Callers skip warmup themselves; every
        sink is optional and the default costs one dict update + branch per tick.

        ``cost`` is the tick's analytic ``(flops, hbm_bytes)`` (device
        telemetry only, None otherwise): joined with the wall into MFU /
        bandwidth utilization — gauges plus extra recorder-tick fields.
        ``steps`` > 0 marks a fused multi-step tick (K scan iterations
        in the one dispatch this record covers); ``roles`` is a unified
        super-step tick's per-row role breakdown ({prefill, decode,
        verify} counts in the one dispatch)."""
        self.dispatches_total[kind] = self.dispatches_total.get(kind, 0) + 1
        if self._on_dispatch is not None:
            self._on_dispatch(kind)
        util = None
        if self._telemetry is not None and cost is not None:
            util = self._telemetry.tick_util(kind, wall_s, *cost)
        if self._on_tick is not None:
            self._on_tick(kind, wall_s)
        if self._recorder is not None:
            self._recorder.tick(
                kind, t0, wall_s,
                active_slots=active_slots,
                queue_depth=self._queue.qsize(),
                batch_fill=batch_fill,
                tokens=tokens,
                spec_accepted=spec_accepted,
                util=util,
                steps=steps,
                roles=roles,
            )

    def _cost_decode(self, window: int, s: int = 1, steps: int = 1):
        """Analytic (flops, bytes) of one decode/verify tick — the
        program computes EVERY cache row (inactive rows too; the MXU
        does not care), so the cost counts ``max_slots``.  ``steps`` > 1
        scales for a fused multi-step tick: K scan iterations each pay
        the full weight stream and (conservatively, at the pre-picked
        window) the cache read."""
        if self._telemetry is None or self._telemetry.cost is None:
            return None
        flops, nbytes = self._telemetry.cost.decode(self.max_slots, window, s)
        if steps > 1:
            flops, nbytes = flops * steps, nbytes * steps
        return flops, nbytes

    def _cost_superstep(self, window: int, s: int, steps: int):
        """Analytic (flops, bytes) of one unified super-step dispatch:
        the S-wide forward plus ``steps - 1`` single-token fused
        iterations, all at the pre-picked window."""
        if self._telemetry is None or self._telemetry.cost is None:
            return None
        return self._telemetry.cost.superstep(
            self.max_slots, window, s, steps
        )

    def _cost_prefill(self, rows: int, chunk: int, attended=None):
        if self._telemetry is None or self._telemetry.cost is None:
            return None
        return self._telemetry.cost.prefill(rows, chunk, attended)

    def _cost_seed(self, tokens: int):
        if self._telemetry is None or self._telemetry.cost is None:
            return None
        return self._telemetry.cost.seed(tokens)

    def _trace_event(self, trace, name: str, slot: int = -1) -> None:
        if (
            self._recorder is not None
            and trace is not None
            and not self._in_warmup
        ):
            self._recorder.event(trace.request_id, name, slot=slot)

    def _note_ttft(self, req: _Request) -> None:
        """First token produced for ``req``: record submit->token wall."""
        if self._in_warmup or req.t_submit <= 0.0:
            return
        if req.trace is not None:
            req.trace.t_first = time.perf_counter()
            self._trace_event(req.trace, "first_token", slot=req.trace.slot)
        if self._on_ttft is not None:
            self._on_ttft(time.perf_counter() - req.t_submit)

    def _emit_first(self, slot_idx: int, req: _Request, first) -> None:
        """A fresh admission's first token.  The host needs its value
        now (to stream it, and to know whether the slot is done already),
        so this is a blocking point: the open ticks (the last chunk, sent
        in turn or ahead, and the insert) close here, then TTFT, the read
        and the emission."""
        self._close_ticks()
        self._note_ttft(req)
        with self._span("engine.prefill_sync"):
            token = int(first)
            self._read_experts()
        with self._span("engine.emit"):
            self._record_token(slot_idx, token)

    def _note_experts(self, program: str, tokens: int, rows: int, aux) -> None:
        """A routed family's program call over ``rows`` token rows, of
        which ``tokens`` were real; ``aux`` holds its on-device counts
        (the family's ``COUNTS``).  Kept as a device value until
        :meth:`_read_experts`."""
        if aux and self._on_moe is not None and not self._in_warmup:
            self._moe_pending.append((program, tokens, rows, aux[0]))

    def _read_experts(self, pending: list | None = None) -> None:
        """Hand counts to ``on_moe(program, counts, routed, row_tile)``:
        the family's ``COUNTS`` by name, every (token, expert) pair of the
        call's real tokens, the grouped matmuls' row tile.  ``pending`` is
        a step's own, at its read-back (a program dispatched behind the
        step may have noted its own since); None takes ``_moe_pending``
        where the loop has just read a program's result back: a chunk's
        come here only when its tick is closed (a chunk sent ahead is
        queued BEHIND the step whose tokens were just read), so every
        count here is already computed: no synchronisation of its own."""
        if pending is None:
            pending, self._moe_pending = self._moe_pending, []
        for program, tokens, rows, counts in pending:
            self._on_moe(
                program,
                dict(zip(self._lm.COUNTS, np.asarray(counts).tolist())),
                self._lm.routed_assignments(self._cfg, tokens),
                self._lm.moe_row_tile(self._cfg, rows),
            )

    def _note_prefill_tokens(self, n: int) -> None:
        """``n`` real prompt tokens' K/V written by a prefill dispatch."""
        self.prefill_tokens += n
        if self._on_prefill_tokens is not None:
            self._on_prefill_tokens(n)

    def _note_admission_wait(self, req: _Request) -> None:
        """``req`` left the submission queue and its admission began."""
        if self._in_warmup or req.t_submit <= 0.0:
            return
        if req.trace is not None:
            req.trace.t_admit = time.perf_counter()
            self._trace_event(req.trace, "admission")
        if self._on_admission_wait is not None:
            self._on_admission_wait(time.perf_counter() - req.t_submit)

    def _spec_slot_state(self, req: _Request) -> dict:
        """Per-slot speculative state (empty when speculation is off)."""
        if self._spec is None:
            return {}
        from .speculative import DraftState

        # validate() caps prompt + max_new_tokens at capacity, so the
        # buffer never overflows; generated tokens append in
        # _record_token.
        history = np.empty((self.capacity,), np.int64)
        L = int(req.prompt.size)
        history[:L] = req.prompt
        return {
            "history": history,
            "hist_len": L,
            "draft": DraftState(
                self._spec.draft_tokens, adaptive=self._spec.adaptive
            ),
        }

    def _class_slot_state(self, req: _Request) -> dict:
        """Per-slot SLO-class / preemption state (empty when classes are
        unarmed — default _Slot fields keep the old layout exactly)."""
        if not self._classes:
            return {}
        out: dict = {"slo_class": req.slo_class}
        if self._preemption:
            # Eviction needs the prompt (to key the radix write-back and
            # rebuild the committed token sequence) and the sampling
            # params (to refill the slot's device rows on restore —
            # another admission may reuse the row meanwhile).
            out.update(
                prompt=req.prompt,
                temperature=req.temperature,
                top_k=req.top_k,
                top_p=req.top_p,
            )
        return out

    def _admit_now(self, req: _Request) -> None:
        """Synchronous admission (warmup): runs the whole chunked pipeline
        at once when chunking is enabled, else the fused path."""
        if self._sp_eligible(req):
            # Warmup prompts are cold by construction — same routing the
            # live admission phases apply.
            self._admit_sp(req)
            return
        if self._prefill_chunk_size is None:
            self._admit(req)
            return
        prog = self._make_progress(req)
        if self._packed:
            slot = self._free_slot()
            assert slot is not None
            prog.slot = slot
            self._reserved.add(slot)
        self._pending.append(prog)
        while prog in self._pending:
            if self._packed:
                # Unified engine: packed chunks ride the super-step
                # dispatch — there is no separate packed program to run.
                if self._unified:
                    self._super_tick()
                else:
                    self._packed_tick()
            else:
                self._chunk_tick()

    def _dispatch_admit(self, ids, slot_idx, L, slot_key, temp, tk, tp):
        """Broadcast (multihost) then run the prefill+insert device call."""
        import jax

        if self._channel is None:
            return self._device_admit(ids, slot_idx, L, slot_key, temp, tk, tp)
        from .multihost import OP_GEN_ADMIT, encode_message

        payload = encode_message(
            OP_GEN_ADMIT,
            {
                "ids": ids,
                "slot": int(slot_idx),
                "length": int(L),
                # typed keys don't pickle portably; ship the raw key data
                "key_data": np.asarray(jax.random.key_data(slot_key)),
                "temp": float(temp),
                "tk": int(tk),
                "tp": float(tp),
            },
        )
        return self._channel.run(
            payload,
            lambda: self._device_admit(ids, slot_idx, L, slot_key, temp, tk, tp),
        )

    def _device_admit(self, ids, slot_idx, L, slot_key, temp, tk, tp):
        import jax.numpy as jnp

        (
            self._cache_k,
            self._cache_v,
            self._lengths,
            self._tokens,
            self._keys,
            self._temps,
            self._topk,
            self._topp,
            first,
            *aux,
        ) = self._prefill_insert(
            self._params,
            jnp.asarray(ids),
            self._cache_k,
            self._cache_v,
            self._lengths,
            self._tokens,
            jnp.int32(slot_idx),
            jnp.int32(L),
            self._keys,
            self._temps,
            self._topk,
            self._topp,
            slot_key,
            jnp.float32(temp),
            jnp.int32(tk),
            jnp.float32(tp),
        )
        self._note_experts("prefill", int(L), ids.size, aux)
        return first

    def replay_admit(self, ids, slot, length, key_data, temp, tk, tp) -> None:
        """Follower side of :meth:`_dispatch_admit` (multihost lockstep)."""
        import jax

        slot_key = jax.random.wrap_key_data(np.asarray(key_data))
        self._device_admit(ids, slot, length, slot_key, temp, tk, tp)

    def replay_step(self, active, window, sampling) -> None:
        """Follower side of a decode tick (multihost lockstep)."""
        self._device_step(np.asarray(active), int(window), bool(sampling))

    # -- chunked prefill (one compiled chunk shape; decode interleaves) ------

    def _split_chunks(self, prompt: np.ndarray) -> list:
        C = self._prefill_chunk_size
        L = int(prompt.size)
        n = -(-L // C)
        padded = np.full((n * C,), self._pad_id, np.int32)
        padded[:L] = prompt
        return [padded[i * C : (i + 1) * C][None, :] for i in range(n)]

    def _make_progress(self, req: _Request) -> _PrefillProgress:
        """Chunked-admission plan: longest radix-cached prefix (to seed)
        plus the uncached suffix (to prefill).  Warmup prompts never
        consult or populate the cache."""
        cached_tokens, cached_kv = 0, []
        if self._prefix_cache is not None and not self._in_warmup:
            cached_tokens, cached_kv = self._prefix_cache.lookup(req.prompt)
        if req.trace is not None:
            req.trace.cached_tokens = cached_tokens
        return _PrefillProgress(
            req=req,
            chunks=self._split_chunks(req.prompt[cached_tokens:]),
            cached_tokens=cached_tokens,
            cached_kv=cached_kv,
        )

    def _note_prefix_evict(self, nbytes: int) -> None:
        self.prefix_evictions += 1
        if self._on_prefix_evict is not None and not self._in_warmup:
            self._on_prefix_evict()

    def _note_prefix_l2(self, kind: str) -> None:
        """Second-tier prefix-cache event (``hit``/``spill``/``evict``)
        — mirrored to the tpumlops_prefix_cache_l2_* counters."""
        if self._on_prefix_l2 is not None and not self._in_warmup:
            self._on_prefix_l2(kind)

    # -- KV handoff (disaggregated prefill/decode fleets) --------------------

    def run_control(self, fn: Callable[[], object]) -> Future:
        """Run ``fn`` on the scheduler thread at the next admission phase
        (thread-safe); returns a Future with its result.  Control ops
        never occupy a cache slot and run even when every slot is busy —
        they exist for state that is single-threaded by design (the
        radix prefix cache, slot truth)."""
        fut: Future = Future()
        if self._stop.is_set():
            # Shut down (or shutting down): the scheduler will never pop
            # this op — fail typed NOW instead of letting the caller
            # block out its timeout.
            _safe_fail(
                fut,
                EngineShutdown("engine shut down before the control op ran"),
            )
            return fut
        self._control_ops.put((fn, fut))
        self._queue.put(_WAKE)  # unblock an idle scheduler promptly
        if self._stop.is_set():
            # Raced stop(): its queue drain may already have missed this
            # op, so drain ourselves.  Both drains use get_nowait and
            # _safe_fail is idempotent, so double-draining is harmless.
            while True:
                try:
                    _fn2, fut2 = self._control_ops.get_nowait()
                except queue.Empty:
                    break
                _safe_fail(
                    fut2,
                    EngineShutdown(
                        "engine shut down before the control op ran"
                    ),
                )
        return fut

    def _drain_control_ops(self) -> None:
        while True:
            try:
                fn, fut = self._control_ops.get_nowait()
            except queue.Empty:
                return
            self._settle_ticks()  # an op may read device state
            try:
                _safe_resolve(fut, fn())
            except Exception as exc:
                _safe_fail(fut, exc)

    def _require_prefix_cache(self):
        if self._prefix_cache is None:
            raise RuntimeError(
                "KV handoff requires the radix prefix cache: enable "
                "spec.tpu.prefixCache (--prefix-cache 1)"
            )
        return self._prefix_cache

    def exportable_prefix_tokens(self, prompt: np.ndarray) -> int:
        """Whole-chunk token count of ``prompt`` a handoff can cover
        (the radix lookup's strict cap below the prompt length)."""
        cache = self._require_prefix_cache()
        C = cache.chunk_tokens
        return ((int(np.asarray(prompt).size) - 1) // C) * C

    def export_prefix_kv(
        self, prompt: np.ndarray, timeout: float | None = 60.0
    ) -> tuple[int, list]:
        """Committed prefix K/V of ``prompt`` as host chunk pairs —
        ``(matched_tokens, [(k, v), ...])`` in radix storage layout.
        Thread-safe: the lookup (an LRU-touching radix walk) runs as a
        control op on the scheduler thread; the returned host arrays are
        immutable snapshots safe to serialize from any thread."""
        cache = self._require_prefix_cache()
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        return self.run_control(lambda: cache.lookup(prompt)).result(timeout)

    def import_prefix_kv(
        self,
        prompt: np.ndarray,
        chunks: list,
        timeout: float | None = 60.0,
    ) -> int:
        """Install handed-off prefix chunks into the radix cache; returns
        the tokens now covered.  Runs on the scheduler thread and
        journals one ``kv-import`` tick so a relayed request is
        reconstructable from ``/debug/trace`` — the import is the tick
        between the router's handoff and the request's seed."""
        cache = self._require_prefix_cache()
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        C = cache.chunk_tokens
        if len(chunks) * C > prompt.size:
            raise ValueError(
                f"{len(chunks)} chunks of {C} tokens exceed the "
                f"{prompt.size}-token prompt"
            )

        def op() -> int:
            t0 = time.perf_counter()
            installed = 0
            for idx, (k, v) in enumerate(chunks):
                if not cache.insert_chunk(prompt, idx, k, v):
                    break  # parent path evicted mid-walk: stop cleanly
                installed += 1
            self._record_tick(
                "kv-import", t0, time.perf_counter() - t0,
                active_slots=sum(s is not None for s in self._slots),
                batch_fill=installed, tokens=installed * C,
            )
            return installed * C

        return int(self.run_control(op).result(timeout))

    def _chunk_to_cache(self, prog: _PrefillProgress) -> int | None:
        """Where chunk ``prog.next_idx`` starts in its prompt if
        prefilling it owes the radix cache a write-back, else None:
        full real-token chunks only (a padded tail carries pad-garbage
        K/V that must never be reused), and ``has_chunk`` skips chunks
        already cached (the steady state for shared-prefix traffic)."""
        if self._prefix_cache is None or self._in_warmup:
            return None
        C = self._prefill_chunk_size
        prompt = prog.req.prompt
        start = prog.cached_tokens + prog.next_idx * C
        if start + C > prompt.size or self._prefix_cache.has_chunk(prompt, start // C):
            return None
        return start

    def _cache_chunk(self, prog: _PrefillProgress, start: int) -> None:
        """Write the chunk just prefilled, which starts at ``start``
        (:meth:`_chunk_to_cache`), back into the radix cache —
        leader-side only (the scheduler thread).

        The ``np.asarray`` is a blocking point: the scheduler waits for
        the chunk's forward pass (its open tick closes first) before
        dispatching the next decode tick, so it is paid at most ONCE per
        unique chunk, and a chunk that owes it is never sent ahead."""
        import jax.numpy as jnp

        chunk_idx = start // self._prefill_chunk_size
        _, sk, sv, _slen = self._seq_state
        ck, cv = self._read_chunk(sk, sv, jnp.int32(start))
        self._close_ticks()
        with self._span("engine.prefill_sync"):
            ck, cv = np.asarray(ck), np.asarray(cv)
        self._prefix_cache.insert_chunk(prog.req.prompt, chunk_idx, ck, cv)

    def _dispatch_chunk(self, ids: np.ndarray, fresh: bool) -> None:
        if self._channel is None:
            self._device_chunk(ids, fresh)
            return
        from .multihost import OP_GEN_CHUNK, encode_message

        payload = encode_message(OP_GEN_CHUNK, {"ids": ids, "fresh": bool(fresh)})
        self._channel.run(payload, lambda: self._device_chunk(ids, fresh))

    def _device_chunk(self, ids: np.ndarray, fresh: bool) -> None:
        import jax.numpy as jnp

        if fresh:
            seq = self._lm.KVCache.create(self._cfg, 1, self._dtype)
            sk0, sv0 = self._put_seq(seq.k), self._put_seq(seq.v)
            self._seq_state = (None, sk0, sv0, seq.length)
        _, sk, sv, slen = self._seq_state
        logits0, sk, sv, slen, *aux = self._prefill_one_chunk(
            self._params, jnp.asarray(ids), sk, sv, slen
        )
        self._seq_state = (logits0, sk, sv, slen)
        self._note_experts("prefill", int((ids >= 0).sum()), ids.size, aux)

    def replay_chunk(self, ids, fresh) -> None:
        self._device_chunk(np.asarray(ids), bool(fresh))

    def _dispatch_seed(self, cached_kv: list, length: int) -> None:
        """Broadcast (multihost) then seed the sequence cache from the
        radix-cached prefix chunks.  The payload carries the host K/V so
        followers stay in lockstep without their own cache.

        Known multihost cost: the payload scales with the cached prefix
        (MBs at large geometries) and rides the serialized unit channel,
        where chunk ops are ~KBs.  Follower-local cache replicas (replay
        the write-back index instead of the bytes; eviction is already
        deterministic) would shrink the seed op to a scalar — future
        work, single-host serving is unaffected."""
        if self._channel is None:
            self._device_seed(cached_kv, length)
            return
        from .multihost import OP_GEN_SEED, encode_message

        payload = encode_message(
            OP_GEN_SEED,
            {
                "ks": [np.asarray(k) for k, _ in cached_kv],
                "vs": [np.asarray(v) for _, v in cached_kv],
                "length": int(length),
            },
        )
        self._channel.run(payload, lambda: self._device_seed(cached_kv, length))

    def _device_seed(self, cached_kv: list, length: int) -> None:
        import jax.numpy as jnp

        seq = self._lm.KVCache.create(self._cfg, 1, self._dtype)
        sk, sv = self._put_seq(seq.k), self._put_seq(seq.v)
        C = self._prefill_chunk_size
        off = 0
        for ck, cv in cached_kv:
            sk, sv = self._seed_chunk(
                sk, sv, jnp.asarray(ck), jnp.asarray(cv), jnp.int32(off)
            )
            off += C
        # No last_logits yet: at least one real suffix chunk ALWAYS follows
        # (lookup caps the match strictly below the prompt length), and its
        # prefill provides the logits the insert samples from.
        self._seq_state = (None, sk, sv, jnp.asarray(int(length), jnp.int32))

    def replay_seed(self, ks, vs, length) -> None:
        """Follower side of :meth:`_dispatch_seed` (multihost lockstep)."""
        self._device_seed(list(zip(ks, vs)), int(length))

    def _dispatch_insert(self, slot_idx, L, slot_key, temp, tk, tp, last_idx):
        import jax

        if self._channel is None:
            return self._device_insert(
                slot_idx, L, slot_key, temp, tk, tp, last_idx
            )
        from .multihost import OP_GEN_INSERT, encode_message

        payload = encode_message(
            OP_GEN_INSERT,
            {
                "slot": int(slot_idx),
                "length": int(L),
                "key_data": np.asarray(jax.random.key_data(slot_key)),
                "temp": float(temp),
                "tk": int(tk),
                "tp": float(tp),
                "last_idx": int(last_idx),
            },
        )
        return self._channel.run(
            payload,
            lambda: self._device_insert(
                slot_idx, L, slot_key, temp, tk, tp, last_idx
            ),
        )

    def _device_insert(self, slot_idx, L, slot_key, temp, tk, tp, last_idx):
        import jax.numpy as jnp

        last_logits, sk, sv, _slen = self._seq_state
        self._seq_state = None
        (
            self._cache_k,
            self._cache_v,
            self._lengths,
            self._tokens,
            self._keys,
            self._temps,
            self._topk,
            self._topp,
            first,
        ) = self._insert_only(
            last_logits,
            self._cache_k,
            self._cache_v,
            self._lengths,
            self._tokens,
            jnp.int32(slot_idx),
            jnp.int32(L),
            self._keys,
            self._temps,
            self._topk,
            self._topp,
            slot_key,
            jnp.float32(temp),
            jnp.int32(tk),
            jnp.float32(tp),
            sk,
            sv,
            jnp.int32(last_idx),
        )
        return first

    def replay_insert(self, slot, length, key_data, temp, tk, tp, last_idx):
        import jax

        slot_key = jax.random.wrap_key_data(np.asarray(key_data))
        self._device_insert(slot, length, slot_key, temp, tk, tp, last_idx)

    # -- sequence-parallel prefill (meshShape sp > 1) ------------------------

    def _sp_eligible(self, req: _Request) -> bool:
        """Long cold prompts ride the ring: one sequence-parallel pass
        instead of L/C serial chunk forwards.  Short prompts and warm
        prefixes keep their existing paths — a radix-cached prefix
        already skips the prefill the ring would parallelize."""
        return (
            self._sp > 1
            and int(req.prompt.size) >= self._sp_threshold
        )

    def _admit_sp(self, req: _Request) -> None:
        """Admit ``req`` through the sequence-parallel prefill: one ring
        pass over the bucket-padded prompt, prefix-cache write-back of
        its full chunks, then the standard scratch insert."""
        slot_idx = self._free_slot()
        assert slot_idx is not None
        L = int(req.prompt.size)
        bucket = prefill_bucket(L, self.capacity)
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :L] = req.prompt
        span = self._span
        self._beat("prefill")
        ts = time.perf_counter()
        with span("engine.prefill_dispatch"):
            self._dispatch_sp_prefill(ids, L)
            self._dispatched("sp-prefill")
        if not self._in_warmup:
            self.prefill_forwards += 1
            self._note_prefill_tokens(L)
            self._open_tick(
                "sp-prefill", ts, self._seq_state[0], (req,),
                active_slots=sum(s is not None for s in self._slots),
                batch_fill=1,
                cost=self._cost_sp_prefill(bucket),
            )
            self._trace_event(req.trace, "sp_prefill")
        self._cache_sp_chunks(req)
        slot_key = self._slot_key_for(req)
        t0 = time.perf_counter()
        # The ring pass already selected the final real row: last_idx 0
        # indexes the [1, V] logits it returned.
        with span("engine.prefill_dispatch"):
            first = self._dispatch_insert(
                slot_idx, L, slot_key, req.temperature, req.top_k, req.top_p,
                last_idx=0,
            )
            self._dispatched("insert")
        if not self._in_warmup:
            self._open_tick(
                "prefill", t0, first, (req,),
                active_slots=sum(s is not None for s in self._slots),
                batch_fill=1, tokens=1,
            )
        if req.trace is not None:
            req.trace.slot = slot_idx
        self._slots[slot_idx] = _Slot(
            future=req.future,
            remaining=req.max_new_tokens,
            eos_id=req.eos_id,
            sampling=req.temperature > 0,
            on_token=req.on_token,
            prompt_len=L,
            t_start=t0,
            request_id=req.request_id,
            trace=req.trace,
            **self._spec_slot_state(req),
            **self._class_slot_state(req),
        )
        self._emit_first(slot_idx, req, first)

    def _cache_sp_chunks(self, req: _Request) -> None:
        """Radix write-back after a ring prefill: every FULL chunk of the
        prompt (pad-garbage tails never enter the cache), read from the
        freshly filled scratch — future shared-prefix requests seed from
        these exactly as if the chunked path had prefilled them."""
        if self._prefix_cache is None or self._in_warmup:
            return
        import jax.numpy as jnp

        C = self._prefill_chunk_size
        if C is None:
            return
        L = int(req.prompt.size)
        _, sk, sv, _slen = self._seq_state
        for chunk_idx in range(L // C):
            if self._prefix_cache.has_chunk(req.prompt, chunk_idx):
                continue
            self._close_ticks()  # the reads below wait for the ring pass
            ck, cv = self._read_chunk(sk, sv, jnp.int32(chunk_idx * C))
            self._prefix_cache.insert_chunk(
                req.prompt, chunk_idx, np.asarray(ck), np.asarray(cv)
            )

    def _dispatch_sp_prefill(self, ids: np.ndarray, length: int) -> None:
        if self._channel is None:
            self._device_sp_prefill(ids, length)
            return
        from .multihost import OP_GEN_SP_PREFILL, encode_message

        payload = encode_message(
            OP_GEN_SP_PREFILL, {"ids": ids, "length": int(length)}
        )
        self._channel.run(
            payload, lambda: self._device_sp_prefill(ids, length)
        )

    def _device_sp_prefill(self, ids: np.ndarray, length: int) -> None:
        import jax.numpy as jnp

        from ..models import llama

        seq = llama.KVCache.create(self._cfg, 1, self._dtype)
        sk0, sv0 = self._put_seq(seq.k), self._put_seq(seq.v)
        last_row, sk, sv = self._prefill_sp(
            self._params, jnp.asarray(ids), sk0, sv0,
            jnp.int32(int(length) - 1),
        )
        self._seq_state = (
            last_row, sk, sv, jnp.asarray(int(length), jnp.int32)
        )

    def replay_sp_prefill(self, ids, length) -> None:
        """Follower side of :meth:`_dispatch_sp_prefill` (lockstep)."""
        self._device_sp_prefill(np.asarray(ids), int(length))

    def _cost_sp_prefill(self, tokens: int):
        if self._telemetry is None or self._telemetry.cost is None:
            return None
        return self._telemetry.cost.sp_prefill(tokens)

    # -- packed multi-admission prefill (prefillBatch > 1) -------------------

    def _pack_buckets(self) -> list[int]:
        """Power-of-two B_p buckets up to ``prefill_batch`` (which caps
        the set even when it is not itself a power of two), ascending —
        one compiled packed-call variant each, all swept at warmup."""
        out, b = [], 1
        while b < self._prefill_batch:
            out.append(b)
            b *= 2
        out.append(self._prefill_batch)
        return out

    def _pack_bucket(self, n: int) -> int:
        for b in self._pack_buckets():
            if b >= n:
                return b
        return self._prefill_batch

    def _parked_batch(self, bucket: int) -> tuple:
        """A fully PARKED packed-call argument set — (ids, slots, offsets, last_pos,
        final_lens, key_data, temps, tks, tps) where every row writes nothing (offset ==
        capacity drops), finalizes nothing (last_pos == -1), and carries neutral
        sampling params. The warmup bucket sweep dispatches it as-is;
        :meth:`_packed_tick` overwrites rows ``[0, n)`` with the real admissions — ONE
        construction site, so the warmed shapes can never drift from the live call's.
        Pad slots are pairwise distinct (and their parked positions start at capacity,
        so equality with a REAL row's reserved slot cannot collide index tuples — see
        llama._commit_chunk_at's unique-indices contract)."""
        C = self._prefill_chunk_size
        return (
            np.zeros((bucket, C), np.int32),
            np.arange(bucket, dtype=np.int32),
            np.full((bucket,), self.capacity, np.int32),
            np.full((bucket,), -1, np.int32),
            np.zeros((bucket,), np.int32),
            np.broadcast_to(
                self._zero_kd, (bucket,) + self._zero_kd.shape
            ).copy(),
            np.zeros((bucket,), np.float32),
            np.zeros((bucket,), np.int32),
            np.ones((bucket,), np.float32),
        )

    def _packed_tick(self) -> None:
        """Advance up to ``prefill_batch`` in-flight admissions by one chunk each — ONE
        batched device call (plus one seed op per admission entering with a radix-cached
        prefix).  The token-budget knob caps the chunks packed per tick, Sarathi-style:
        decode ticks interleave every tick regardless, so bounding prefill work per tick
        bounds the decode-cadence jitter long prompts can inject."""
        self._beat("packed-prefill")
        C = self._prefill_chunk_size
        max_chunks = self._prefill_batch
        if self._prefill_token_budget:
            max_chunks = min(
                max_chunks, max(1, self._prefill_token_budget // C)
            )
        take = self._pending[:max_chunks]
        chunk_progs = []
        for prog in take:
            if prog.cached_tokens and not prog.seeded:
                # Cached-prefix hit: seed the radix K/V straight into the
                # reserved cache row; those tokens never re-prefill.
                self._seed_reserved_row(
                    prog, sum(s is not None for s in self._slots)
                )
            else:
                chunk_progs.append(prog)
        if not chunk_progs:
            return
        import jax

        span = self._span
        n = len(chunk_progs)
        bucket = self._pack_bucket(n)
        (
            ids, slots, offsets, last_pos, final_lens,
            key_data, r_temps, r_tks, r_tps,
        ) = self._parked_batch(bucket)
        for i, prog in enumerate(chunk_progs):
            req = prog.req
            ids[i] = prog.chunks[prog.next_idx][0]
            slots[i] = prog.slot
            offsets[i] = prog.cached_tokens + prog.next_idx * C
            if prog.next_idx == len(prog.chunks) - 1:
                L = int(req.prompt.size)
                last_pos[i] = (L - 1) - int(offsets[i])
                final_lens[i] = L
                r_temps[i] = req.temperature
                r_tks[i] = req.top_k
                r_tps[i] = req.top_p
                key_data[i] = np.asarray(
                    jax.random.key_data(self._slot_key_for(req))
                )
        t0 = time.perf_counter()
        with span("engine.prefill_dispatch"):
            firsts = self._dispatch_chunks(
                ids, slots, offsets, last_pos, final_lens,
                key_data, r_temps, r_tks, r_tps,
            )
        if not self._in_warmup:
            self.prefill_chunks_dispatched += n
            self.prefill_forwards += 1
            self._note_prefill_tokens(self._chunk_tokens(chunk_progs))
            if self._on_prefill_batch is not None:
                self._on_prefill_batch(n)
            finals = sum(
                1 for prog in chunk_progs
                if prog.next_idx == len(prog.chunks) - 1
            )
            # The compiled program computes every row of the B_p bucket (parked
            # pad rows included); the mean attended span is over the REAL chunks'.
            attended = (
                sum(float(offsets[i]) for i in range(n)) / n + C / 2
            )
            # ``_device_chunks`` has read the first tokens back: the
            # call is over, and its tick closes at once.
            self._open_tick(
                "packed-prefill", t0, None, [p.req for p in chunk_progs],
                active_slots=sum(s is not None for s in self._slots),
                batch_fill=n, tokens=finals,
                cost=self._cost_prefill(bucket, C, attended=attended),
            )
            self._close_ticks()
        for i, prog in enumerate(chunk_progs):
            if prog.req.trace is not None:
                prog.req.trace.slot = prog.slot
                prog.req.trace.prefill_chunks += 1
                self._trace_event(
                    prog.req.trace, "prefill_chunk", slot=prog.slot
                )
            self._maybe_cache_chunk_slot(prog)
            prog.next_idx += 1
            if prog.next_idx < len(prog.chunks):
                continue
            # Final chunk landed: the packed call already installed the
            # slot's device state and sampled its first token.
            self._pending.remove(prog)
            self._reserved.discard(prog.slot)
            req = prog.req
            self._slots[prog.slot] = _Slot(
                future=req.future,
                remaining=req.max_new_tokens,
                eos_id=req.eos_id,
                sampling=req.temperature > 0,
                on_token=req.on_token,
                prompt_len=int(req.prompt.size),
                t_start=t0,
                request_id=req.request_id,
                trace=req.trace,
                **self._spec_slot_state(req),
                **self._class_slot_state(req),
            )
            self._emit_first(prog.slot, req, firsts[i])

    def _seed_reserved_row(self, prog: _PrefillProgress, active: int) -> None:
        """Packed-mode cached-prefix hit: seed the radix K/V straight
        into the admission's reserved cache row (its own op: a radix
        copy is not a forward); those tokens never re-prefill."""
        span = self._span
        ts = time.perf_counter()
        with span("engine.prefill_dispatch"):
            self._dispatch_seed_slot(
                prog.cached_kv, prog.slot, prog.cached_tokens
            )
            self._dispatched("seed")
        prog.seeded = True
        prog.cached_kv = []
        self.prefix_hits += 1
        self.prefix_cached_tokens += prog.cached_tokens
        if self._in_warmup:
            return
        if self._on_prefix_hit is not None:
            self._on_prefix_hit(prog.cached_tokens)
        # Waited for at once: the seed's only outputs are the cache
        # buffers, which the very next program donates.
        self._open_tick(
            "seed", ts, self._cache_k, (prog.req,),
            active_slots=active, batch_fill=1,
            cost=self._cost_seed(prog.cached_tokens),
        )
        self._close_ticks()
        self._trace_event(prog.req.trace, "seed", slot=prog.slot)

    def _chunk_tokens(self, chunk_progs: list) -> int:
        """Real prompt tokens in the next chunk of each admission."""
        C = self._prefill_chunk_size
        return sum(
            min(C, int(p.req.prompt.size) - p.cached_tokens - p.next_idx * C)
            for p in chunk_progs
        )

    def _maybe_cache_chunk_slot(self, prog: _PrefillProgress) -> None:
        """Packed-mode prefix write-back: like :meth:`_maybe_cache_chunk`
        but the freshly prefilled chunk is read from the reserved cache
        row, not the batch-1 scratch."""
        if self._prefix_cache is None or self._in_warmup:
            return
        import jax.numpy as jnp

        C = self._prefill_chunk_size
        L = int(prog.req.prompt.size)
        start = prog.cached_tokens + prog.next_idx * C
        if start + C > L:
            return
        chunk_idx = start // C
        if self._prefix_cache.has_chunk(prog.req.prompt, chunk_idx):
            return
        ck, cv = self._read_slot(
            self._cache_k, self._cache_v,
            jnp.int32(prog.slot), jnp.int32(start),
        )
        self._prefix_cache.insert_chunk(
            prog.req.prompt, chunk_idx, np.asarray(ck), np.asarray(cv)
        )

    def _dispatch_chunks(
        self, ids, slots, offsets, last_pos, final_lens,
        key_data, r_temps, r_tks, r_tps,
    ):
        """Broadcast (multihost) then run the packed prefill call."""
        args = (
            ids, slots, offsets, last_pos, final_lens,
            key_data, r_temps, r_tks, r_tps,
        )
        if self._channel is None:
            return self._device_chunks(*args)
        from .multihost import OP_GEN_CHUNKS, encode_message

        payload = encode_message(
            OP_GEN_CHUNKS,
            {
                "ids": ids,
                "slots": slots,
                "offsets": offsets,
                "last_pos": last_pos,
                "final_lens": final_lens,
                "key_data": key_data,
                "temps": r_temps,
                "tks": r_tks,
                "tps": r_tps,
            },
        )
        return self._channel.run(payload, lambda: self._device_chunks(*args))

    def _device_chunks(
        self, ids, slots, offsets, last_pos, final_lens,
        key_data, r_temps, r_tks, r_tps,
    ):
        import jax
        import jax.numpy as jnp

        slot_keys = jax.random.wrap_key_data(jnp.asarray(key_data))
        (
            self._cache_k,
            self._cache_v,
            self._lengths,
            self._tokens,
            self._keys,
            self._temps,
            self._topk,
            self._topp,
            firsts,
        ) = self._prefill_chunks(
            self._params,
            jnp.asarray(ids),
            self._cache_k,
            self._cache_v,
            self._lengths,
            self._tokens,
            self._keys,
            self._temps,
            self._topk,
            self._topp,
            jnp.asarray(slots),
            jnp.asarray(offsets),
            jnp.asarray(last_pos),
            jnp.asarray(final_lens),
            slot_keys,
            jnp.asarray(r_temps),
            jnp.asarray(r_tks),
            jnp.asarray(r_tps),
        )
        self._dispatched("packed-prefill")
        with self._span("engine.prefill_sync"):
            return np.asarray(firsts)

    def replay_chunks(
        self, ids, slots, offsets, last_pos, final_lens,
        key_data, temps, tks, tps,
    ) -> None:
        """Follower side of :meth:`_dispatch_chunks` (multihost lockstep)."""
        self._device_chunks(
            np.asarray(ids), np.asarray(slots), np.asarray(offsets),
            np.asarray(last_pos), np.asarray(final_lens),
            np.asarray(key_data), np.asarray(temps), np.asarray(tks),
            np.asarray(tps),
        )

    def _dispatch_seed_slot(self, cached_kv: list, slot: int, length: int):
        """Broadcast (multihost) then seed a reserved cache row from the
        radix-cached prefix chunks (packed-mode sibling of
        :meth:`_dispatch_seed`; same payload-size caveat)."""
        if self._channel is None:
            self._device_seed_slot(cached_kv, slot, length)
            return
        from .multihost import OP_GEN_SEED_SLOT, encode_message

        payload = encode_message(
            OP_GEN_SEED_SLOT,
            {
                "ks": [np.asarray(k) for k, _ in cached_kv],
                "vs": [np.asarray(v) for _, v in cached_kv],
                "slot": int(slot),
                "length": int(length),
            },
        )
        self._channel.run(
            payload, lambda: self._device_seed_slot(cached_kv, slot, length)
        )

    def _device_seed_slot(self, cached_kv: list, slot: int, length: int):
        import jax.numpy as jnp

        C = self._prefill_chunk_size
        off = 0
        for ck, cv in cached_kv:
            self._cache_k, self._cache_v = self._seed_slot(
                self._cache_k, self._cache_v,
                jnp.asarray(ck), jnp.asarray(cv),
                jnp.int32(slot), jnp.int32(off),
            )
            off += C

    def replay_seed_slot(self, ks, vs, slot, length) -> None:
        """Follower side of :meth:`_dispatch_seed_slot`."""
        self._device_seed_slot(list(zip(ks, vs)), int(slot), int(length))

    def _slot_key_for(self, req: _Request):
        import jax

        if req.seed is None:
            self._seed_counter += 1
            return jax.random.fold_in(self._boot_key, self._seed_counter)
        return jax.random.key(int(req.seed))

    def _chunk_tick(self) -> None:
        """Advance the in-flight chunked admission by ONE device op (a prefix-cache seed
        or one prefill chunk), unless its next chunk went out behind the last pass's
        step already (:meth:`_send_chunk_ahead`); once every chunk is dispatched,
        install the sequence into its slot.  Single-admission mode only (the batch-1
        scratch cache serializes admissions); packed mode advances through
        :meth:`_packed_tick`."""
        assert self._pending
        span = self._span
        self._beat("prefill")
        prog = self._pending[0]
        if prog.ahead:
            prog.ahead = False  # this pass's chunk is on the chip already
        elif prog.cached_tokens and not prog.seeded:
            # Cached-prefix hit: one seed op copies the radix-cached K/V
            # into a fresh sequence cache — those tokens never re-prefill.
            ts = time.perf_counter()
            with span("engine.prefill_dispatch"):
                self._dispatch_seed(prog.cached_kv, prog.cached_tokens)
                self._dispatched("seed")
            prog.seeded = True
            prog.cached_kv = []  # host copies handed off; free the refs
            self.prefix_hits += 1
            self.prefix_cached_tokens += prog.cached_tokens
            if not self._in_warmup:
                if self._on_prefix_hit is not None:
                    self._on_prefix_hit(prog.cached_tokens)
                # The seeded scratch itself: the next chunk donates it,
                # so that chunk is never sent ahead of this tick's close.
                self._open_tick(
                    "seed", ts, self._seq_state[1], (prog.req,),
                    active_slots=sum(s is not None for s in self._slots),
                    batch_fill=1,
                    cost=self._cost_seed(prog.cached_tokens),
                )
                self._trace_event(prog.req.trace, "seed")
            return  # suffix chunks start next tick (decode cadence kept)
        else:
            start = self._chunk_to_cache(prog)
            self._dispatch_next_chunk(prog, "in_turn")
            if start is not None:
                self._cache_chunk(prog, start)
        if prog.next_idx == len(prog.chunks):
            self._finish_admission(prog)

    def _dispatch_next_chunk(self, prog: _PrefillProgress, when: str) -> None:
        """Dispatch ``prog``'s next prefill chunk into the batch-1
        scratch and open its tick.  ``when``: the admit phase sent it
        ``in_turn``, or the step sent it ``ahead``."""
        ids = prog.chunks[prog.next_idx]
        C = self._prefill_chunk_size
        offset = prog.cached_tokens + prog.next_idx * C
        noted = len(self._moe_pending)
        ts = time.perf_counter()
        with self._span("engine.prefill_dispatch"):
            self._dispatch_chunk(
                ids, fresh=prog.next_idx == 0 and not prog.seeded
            )
            self._dispatched("chunk")
        if not self._in_warmup:
            self.prefill_chunks_dispatched += 1
            self.prefill_forwards += 1
            self._note_prefill_tokens(self._chunk_tokens([prog]))
            if self._on_prefill_dispatch is not None:
                self._on_prefill_dispatch(when)
            if self._key_blocks is not None and self._on_key_blocks is not None:
                self._on_key_blocks(*self._key_blocks(self._cfg, offset, C))
            # The chunk's logits: the next chunk donates the scratch, not
            # these.
            self._open_tick(
                "prefill", ts, self._seq_state[0], (prog.req,), noted=noted,
                active_slots=sum(s is not None for s in self._slots),
                batch_fill=1,
                cost=self._cost_prefill(1, C, attended=offset + C / 2),
            )
        if prog.req.trace is not None:
            prog.req.trace.prefill_chunks += 1
            self._trace_event(prog.req.trace, "prefill_chunk")
        prog.next_idx += 1

    def _finish_admission(self, prog: _PrefillProgress) -> None:
        """Every chunk of ``prog`` is dispatched: insert the scratch into
        a free slot and emit the first token (a blocking point: the last
        chunk's tick, sent in turn or ahead, closes there)."""
        req = prog.req
        C = self._prefill_chunk_size
        self._pending.pop(0)
        slot_idx = self._free_slot()
        assert slot_idx is not None  # reserved by the admission policy
        L = int(req.prompt.size)
        slot_key = self._slot_key_for(req)
        t0 = time.perf_counter()
        with self._span("engine.prefill_dispatch"):
            first = self._dispatch_insert(
                slot_idx, L, slot_key, req.temperature, req.top_k, req.top_p,
                last_idx=(L - 1) - prog.cached_tokens
                - C * (len(prog.chunks) - 1),
            )
            self._dispatched("insert")
        if not self._in_warmup:
            self._open_tick(
                "prefill", t0, first, (req,),
                active_slots=sum(s is not None for s in self._slots),
                batch_fill=1, tokens=1,
            )
        if req.trace is not None:
            req.trace.slot = slot_idx
        self._slots[slot_idx] = _Slot(
            future=req.future,
            remaining=req.max_new_tokens,
            eos_id=req.eos_id,
            sampling=req.temperature > 0,
            on_token=req.on_token,
            prompt_len=L,
            t_start=t0,
            request_id=req.request_id,
            trace=req.trace,
            **self._spec_slot_state(req),
            **self._class_slot_state(req),
        )
        self._emit_first(slot_idx, req, first)

    def replay_reset(self) -> None:
        """Follower side of :meth:`_fail_all_and_recover`'s device reset."""
        self._reset_device_state()

    def _record_token(
        self, slot_idx: int, token: int, t: float | None = None
    ) -> None:
        """Credit one emitted token to a slot.  ``t`` overrides the token's wall
        timestamp (fused multi-step harvests reconstruct per-token instants across the
        tick wall — K tokens landing on one perf_counter() read would zero every ITL
        observation and stack the Perfetto token instants on one point)."""
        slot = self._slots[slot_idx]
        assert slot is not None
        if slot.future.cancelled():
            # Client gone (stream disconnect / shutdown): free the slot
            # instead of decoding tokens nobody will read.
            self._finish_trace(slot, "cancelled")
            self._slots[slot_idx] = None
            return
        slot.generated.append(token)
        if slot.history is not None and slot.hist_len < slot.history.size:
            slot.history[slot.hist_len] = token
            slot.hist_len += 1
        slot.remaining -= 1
        if not self._in_warmup:
            now = time.perf_counter() if t is None else t
            if slot.t_last_token > 0.0 and self._on_itl is not None:
                self._on_itl(now - slot.t_last_token)
            slot.t_last_token = now
            if slot.trace is not None:
                slot.trace.note_token(now)
            self.tokens_generated += 1
            if self._on_tokens is not None:
                self._on_tokens(1)
            if slot.on_token is not None:
                try:
                    slot.on_token(token)
                except Exception:
                    # ONE line, then disarm: a broken streaming client
                    # would otherwise log a full stack per token at
                    # decode rate for the rest of the request.
                    _log.exception(
                        "on_token callback failed; disabling streaming "
                        "callback for this request"
                    )
                    slot.on_token = None
        done = slot.remaining <= 0 or (
            slot.eos_id is not None and token == slot.eos_id
        )
        if done:
            reason = (
                "eos"
                if slot.eos_id is not None and token == slot.eos_id
                else "length"
            )
            self._finish_trace(slot, reason)
            _safe_resolve(slot.future, np.asarray(slot.generated, np.int32))
            self._slots[slot_idx] = None

    def _finish_trace(self, slot: _Slot, reason: str) -> None:
        """Close a slot's request trace: finish reason, completion event,
        per-request token-count histogram, and hand the trace to the
        flight recorder's completed-request ring."""
        if self._in_warmup:
            return
        if self._on_request_tokens is not None:
            self._on_request_tokens(len(slot.generated))
        if slot.trace is None:
            return
        slot.trace.finish(reason)
        self._trace_event(slot.trace, "finish", slot=slot.trace.slot)
        if self._recorder is not None:
            self._recorder.complete(slot.trace)

    def _step(self) -> None:
        """One batched decode tick.

        The plain step goes out behind the step still in flight, over the
        rows :meth:`_step_rows` picks, from the device-resident tokens,
        lengths and cache that step left; the one in flight is then read
        back behind it (:meth:`_close_ticks`), and the new one stays out
        across the end of the pass unless a chunk went out behind it.

        A draft+verify pass (speculation on, every slot greedy), a fused
        multi-step burst (a tick that owes nothing else) and the unified
        super-step start from exact slot truth: the step in flight is read
        back first."""
        if self._unified or self._spec is not None or self._fused:
            self._read_ahead()
        if self._unified:
            self._super_tick()
            return
        span = self._span
        with span("engine.decode_assemble"):
            # The step's rows, attention window and token rule.
            active_np, window, sampling = self._step_rows()
            drafts = None
            if (
                active_np.any()
                and self._spec is not None
                and not sampling
                and not self._in_warmup
            ):
                drafts = self._collect_drafts()
        if not active_np.any():
            if self._ahead is not None:
                self._read_ahead()  # the last step out: no row needs more
                return
            # Still report occupancy: without this the gauges freeze at
            # their last busy values and an idle server reads as loaded.
            # (observe_decode_step skips its histograms at 0 active.)
            if self._on_step is not None and not self._in_warmup:
                with span("engine.journal"):
                    self._on_step(
                        0, 0.0, self._queue.qsize(), len(self._pending)
                    )
            return
        if drafts is not None and any(drafts):
            # Speculative slots fall back to verify ticks (a draft in
            # hand amortizes the weight stream by acceptance, which a
            # fixed-K scan cannot beat on draftable text); ticks with
            # no drafts anywhere fuse below like plain traffic.
            self._verify_tick(active_np, window, drafts)
            return
        if (
            self._fused
            and not self._in_warmup
            and not self._pending
            and not self._queued_work()
        ):
            # Fused multi-step decode engages only when the scheduler
            # owes nothing else: no queued request waiting on a slot a
            # K-step tick would hold for K tokens, no admission
            # mid-prefill whose chunk cadence a fused tick would stall.
            self._step_fused(active_np, sampling)
            return
        # The step's on-device counts are the ones noted from here on:
        # they travel with it to its own read-back.
        noted = len(self._moe_pending)
        t0 = time.perf_counter()
        self._beat("decode")
        with span("engine.decode_dispatch"):
            self._dispatch_step(active_np, window, sampling)
            self._dispatched("decode")
            step = self._step_out(t0, active_np, window, noted)
        # The admission's next chunk goes behind the step.  Then wait in the
        # device's order: the step in flight, what went before this one, and
        # this one only where a chunk is behind it (one program stays out).
        before = len(self._open_ticks)
        self._send_chunk_ahead()
        self._close_ticks(behind="decode", first=before)
        if self._open_ticks or self._in_warmup:
            self._read_step(step)  # the warm-up sweep sends none ahead
        else:
            self._ahead = step

    def _read_ahead(self) -> None:
        """Read back the step in flight, if one is (a failure is its own)."""
        step, self._ahead = self._ahead, None
        try:
            if step is not None:
                self._read_step(step)
        except Exception as exc:  # and what went behind it: lost, unblamed
            self._open_ticks = []
            raise _StepFailed("the decode step in flight failed") from exc

    def _note_tick(
        self, active_np, t0: float, wall: float, kind: str = "decode",
        tokens: int = 0, spec_accepted: int = 0, cost=None,
    ) -> None:
        """Journal a decode or verify tick; ``t0`` and ``wall`` are
        :meth:`_tick_done`'s, taken where its tokens were read back."""
        if self._in_warmup:
            return
        self.decode_forwards += 1
        self._record_tick(
            kind, t0, wall,
            active_slots=int(active_np.sum()),
            tokens=tokens, spec_accepted=spec_accepted, cost=cost,
        )
        if self._on_step is not None:
            # queue depth counts QUEUED-BUT-UNADMITTED requests only; the
            # in-flight admission count rides separately so saturation
            # and admission-latency alerts stop conflating the two.
            self._on_step(
                int(active_np.sum()),
                wall,
                self._queue.qsize(),
                len(self._pending),
            )

    # -- fused multi-step decode (decodeSteps > 1) ---------------------------

    def _step_fused(self, active_np, sampling: bool) -> None:
        """A fused-decode BURST with lag-1 asynchronous readback.

        Each iteration dispatches ONE jitted program that runs K decode
        steps as a ``lax.scan`` (on-device sampling feeds each step's
        token into the next; an on-device EOS latch freezes finished
        rows mid-scan), then harvests the PREVIOUS dispatch's token
        block — so the host-side work of tick N (sync, SSE emission,
        recorder feed) overlaps tick N+1's device execution and the
        dispatch bubble between ticks disappears.  Chained dispatches
        pass NO host arrays: the active mask, per-row budgets, tokens,
        keys, lengths, and the donated cache buffers all stay device-
        resident between ticks.

        Host knowledge therefore lags the device by one tick: slot
        bookkeeping is exact through tick N-1 when tick N+1 is
        dispatched.  Only two decisions need host state — whether to
        keep the burst going, and the attention window — and both use
        conservative bounds (a row can advance at most K per tick), so a
        mid-scan EOS costs at most one trailing all-inactive dispatch,
        never a wrong result.  The burst exits with every harvest
        drained: the scheduler never leaves ``_step`` holding un-synced
        tokens, so admission and shutdown paths see exact slot truth."""
        K = self._decode_steps
        B = self.max_slots
        span = self._span
        with span("engine.decode_assemble"):
            # Burst-entry device inputs from exact host slot truth.
            remaining = np.zeros((B,), np.int32)
            eos_ids = np.full((B,), -1, np.int32)  # -1: no EOS (ids are >= 0)
            hi = np.zeros((B,), np.int64)  # per-row next-write position bound
            rem_hi = np.zeros((B,), np.int64)  # per-row emit-budget bound
            for i, slot in enumerate(self._slots):
                if slot is None:
                    continue
                remaining[i] = slot.remaining
                if slot.eos_id is not None:
                    eos_ids[i] = slot.eos_id
                hi[i] = slot.prompt_len + len(slot.generated)
                rem_hi[i] = slot.remaining
        pending = None  # (tok_block_dev, valid_dev, t0, window)
        start = True
        while True:
            # Pre-pick the window for length + K: the scan cannot grow
            # it mid-flight, and the LAST step attends positions up to
            # needed + K - 1 (satellite: a row crossing a bucket edge
            # inside K steps must already be covered).
            with span("engine.decode_assemble"):
                needed_hi = int(
                    max(
                        hi[i]
                        for i in range(B)
                        if self._slots[i] is not None and rem_hi[i] > 0
                    )
                )
                window = decode_window_bucket(
                    min(needed_hi + K - 1, self.capacity), self.capacity
                )
            t0 = time.perf_counter()
            self._beat("multistep")
            with span("engine.decode_dispatch"):
                tok_block, valid = self._dispatch_multistep(
                    active_np if start else None,
                    remaining if start else None,
                    eos_ids if start else None,
                    window, sampling,
                )
                self._dispatched("multistep")
            with span("engine.decode_assemble"):
                for i in range(B):
                    emit = min(int(rem_hi[i]), K)
                    hi[i] += emit
                    rem_hi[i] -= emit
            start = False
            if pending is not None:
                # Lag-1: tick N+1 is in flight; block on tick N now.
                self._harvest_fused(*pending)
            pending = (tok_block, valid, t0, window)
            with span("engine.decode_assemble"):
                may_be_active = any(
                    self._slots[i] is not None and rem_hi[i] > 0
                    for i in range(B)
                )
                # Speculative fallback is PER TICK: the harvest above
                # refreshed slot histories, and a draft in hand beats a
                # fixed-K scan on draftable text — end the burst so the
                # next _step runs the verify path.
                done = (
                    not may_be_active
                    or self._stop.is_set()
                    or bool(self._pending)
                    or self._queued_work()
                    or (
                        self._spec is not None
                        and not sampling
                        and any(self._collect_drafts())
                    )
                )
            if done:
                break
        if pending is not None:
            self._harvest_fused(*pending)

    def _harvest_fused(self, tok_block_dev, valid_dev, t0, window) -> None:
        """Block on one fused tick's outputs and credit its tokens.

        ``valid[i]`` counts the scan steps row ``i`` was active for — token columns
        at/after it are frozen copies the latch never emitted (and whose K/V was never
        committed: the in-scan active gate parks those writes, so no host-side
        truncation is needed). Per-token timestamps are reconstructed by spacing the
        row's valid tokens across the tick wall (clamped monotone against the row's
        previous token): K tokens on one instant would zero every ITL observation and
        stack the Perfetto instants."""
        self._close_ticks(behind="multistep")
        with self._span("engine.decode_readback"):
            toks = np.asarray(tok_block_dev)  # the deferred device sync
            valid = np.asarray(valid_dev)
        t0, wall = self._tick_done(t0)
        end = t0 + wall
        K = self._decode_steps
        active_slots = int((valid > 0).sum())
        total = int(valid.sum())
        self.decode_forwards += 1
        with self._span("engine.journal"):
            self._record_tick(
                "multistep", t0, wall,
                active_slots=active_slots, tokens=total, steps=K,
                cost=self._cost_decode(window, steps=K),
            )
            if self._on_step is not None:
                self._on_step(
                    active_slots, wall,
                    self._queue.qsize(), len(self._pending),
                )
        with self._span("engine.emit"):
            for i in range(self.max_slots):
                n = int(valid[i])
                if n <= 0 or self._slots[i] is None:
                    continue
                base = max(t0, self._slots[i].t_last_token)
                spread = max(end - base, 0.0)
                for j in range(n):
                    self._record_token(
                        i, int(toks[i, j]), t=base + spread * (j + 1) / n
                    )
                    self.decode_tokens += 1
                    if self._slots[i] is None:
                        break  # finished (eos/length) or cancelled

    def _dispatch_multistep(self, active_np, remaining, eos_ids, window,
                            sampling):
        """Broadcast (multihost) then run one fused K-step decode.

        ``active_np``/``remaining``/``eos_ids`` are host arrays on the
        first tick of a burst and ``None`` on chained ticks — chained
        state (mask, budgets, EOS ids) lives on device from the previous
        fused tick, on followers exactly as on the leader."""
        if self._channel is None:
            return self._device_multistep(
                active_np, remaining, eos_ids, window, sampling
            )
        from .multihost import OP_GEN_MULTISTEP, encode_message

        payload = encode_message(
            OP_GEN_MULTISTEP,
            {
                "active": active_np,
                "remaining": remaining,
                "eos_ids": eos_ids,
                "window": int(window),
                "sampling": bool(sampling),
            },
        )
        return self._channel.run(
            payload,
            lambda: self._device_multistep(
                active_np, remaining, eos_ids, window, sampling
            ),
        )

    def _device_multistep(self, active_np, remaining, eos_ids, window,
                          sampling):
        import jax.numpy as jnp

        if active_np is None:
            act, rem, eos = self._ms_active, self._ms_remaining, self._ms_eos
        else:
            act = jnp.asarray(np.asarray(active_np, bool))
            rem = jnp.asarray(np.asarray(remaining, np.int32))
            eos = jnp.asarray(np.asarray(eos_ids, np.int32))
            self._ms_eos = eos
        if sampling:
            (
                tok_block, valid, self._tokens,
                self._cache_k, self._cache_v, self._lengths,
                self._ms_active, self._ms_remaining, self._keys,
            ) = self._multistep(
                self._params, self._tokens,
                self._cache_k, self._cache_v, self._lengths,
                act, rem, eos,
                self._keys, self._temps, self._topk, self._topp,
                int(window), self._decode_steps,
            )
        else:
            (
                tok_block, valid, self._tokens,
                self._cache_k, self._cache_v, self._lengths,
                self._ms_active, self._ms_remaining,
            ) = self._multistep_greedy(
                self._params, self._tokens,
                self._cache_k, self._cache_v, self._lengths,
                act, rem, eos,
                int(window), self._decode_steps,
            )
        return tok_block, valid

    def replay_multistep(self, active, remaining, eos_ids, window,
                         sampling) -> None:
        """Follower side of a fused multi-step tick (multihost lockstep).
        ``active`` None = chained tick: the follower's own device-resident
        chain state (maintained by its previous replay) is used, exactly
        as on the leader."""
        self._device_multistep(
            None if active is None else np.asarray(active),
            None if remaining is None else np.asarray(remaining),
            None if eos_ids is None else np.asarray(eos_ids),
            int(window), bool(sampling),
        )

    # -- unified ragged super-step (unifiedStep) -----------------------------

    def _parked_superstep(self) -> tuple:
        """A fully PARKED unified-dispatch argument set: every row idle
        (zero counts park all K/V writes, inactive rows emit nothing,
        ``last_pos == -1`` finalizes nothing) with neutral sampling
        params.  The warmup window sweep dispatches it as-is;
        :meth:`_super_tick` overwrites rows with the tick's real roles —
        ONE construction site, so warmed shapes can never drift from
        the live call's (the `_parked_batch` discipline)."""
        B, S = self.max_slots, self._super_width
        return (
            np.zeros((B, S), np.int32),   # ids
            np.zeros((B,), np.int32),     # roles (all ROLE_IDLE)
            np.zeros((B,), np.int32),     # offsets
            np.zeros((B,), np.int32),     # counts
            np.zeros((B,), np.int32),     # draft_len
            np.zeros((B,), bool),         # active
            np.zeros((B,), np.int32),     # remaining
            np.full((B,), -1, np.int32),  # eos_ids
            np.full((B,), -1, np.int32),  # last_pos
            np.zeros((B,), np.int32),     # final_lens
            np.broadcast_to(
                self._zero_kd, (B,) + self._zero_kd.shape
            ).copy(),                     # key_data
            np.zeros((B,), np.float32),   # r_temps
            np.zeros((B,), np.int32),     # r_tks
            np.ones((B,), np.float32),    # r_tps
        )

    def _super_tick(self) -> None:
        """ONE dispatch per tick: assemble every occupied slot (decode
        or, on an all-greedy tick with drafts in hand, verify) and up to
        the packed budget of pending admissions' next chunks (prefill)
        into per-row role/offset/budget tensors, run the unified
        super-step program, and harvest all three roles' results from
        the one readback.  This is `_step` + `_verify_tick` +
        `_packed_tick` + `_step_fused` collapsed: the split engine's
        per-tick-kind programs (and their warmup cross-product)
        disappear, and prefill chunks interleave with decode inside the
        dispatch instead of between dispatches."""
        import jax

        from ..models import llama

        B = self.max_slots
        occupied = np.array([s is not None for s in self._slots])
        # Packed-admission chunk work riding this tick (seeds stay their
        # own op: a radix copy is not a forward).
        chunk_progs: list = []
        if self._packed and self._pending:
            C = self._prefill_chunk_size
            max_chunks = self._prefill_batch
            if self._prefill_token_budget:
                max_chunks = min(
                    max_chunks, max(1, self._prefill_token_budget // C)
                )
            for prog in self._pending[:max_chunks]:
                if prog.cached_tokens and not prog.seeded:
                    self._seed_reserved_row(prog, int(occupied.sum()))
                else:
                    chunk_progs.append(prog)
        span = self._span
        if not occupied.any() and not chunk_progs:
            # Still report occupancy: without this the gauges freeze at
            # their last busy values and an idle server reads as loaded.
            if self._on_step is not None and not self._in_warmup:
                with span("engine.journal"):
                    self._on_step(
                        0, 0.0, self._queue.qsize(), len(self._pending)
                    )
            return
        self._beat("superstep")
        with span("engine.decode_assemble"):
            K = self._decode_steps
            sampling = any(s is not None and s.sampling for s in self._slots)
            drafts: list[list[int]] = [[] for _ in range(B)]
            if (
                self._spec is not None
                and not sampling
                and not self._in_warmup
                and occupied.any()
            ):
                drafts = self._collect_drafts()
            (
                ids, roles, offsets, counts, draft_len, active, remaining,
                eos_ids, last_pos, final_lens, key_data, r_temps, r_tks, r_tps,
            ) = self._parked_superstep()
            decode_hi = other_hi = 0
            n_dec = n_ver = 0
            for i, slot in enumerate(self._slots):
                if slot is None:
                    continue
                pos = slot.prompt_len + len(slot.generated)
                ids[i, 0] = slot.generated[-1]  # pending (emitted, unfed) token
                active[i] = True
                d = drafts[i]
                if d:
                    roles[i] = llama.ROLE_VERIFY
                    ids[i, 1 : 1 + len(d)] = d
                    draft_len[i] = len(d)
                    counts[i] = len(d) + 1
                    other_hi = max(other_hi, pos)
                    n_ver += 1
                else:
                    roles[i] = llama.ROLE_DECODE
                    counts[i] = 1
                    remaining[i] = slot.remaining
                    if slot.eos_id is not None:
                        eos_ids[i] = slot.eos_id
                    decode_hi = max(decode_hi, pos)
                    n_dec += 1
            C = self._prefill_chunk_size
            for prog in chunk_progs:
                i, req = prog.slot, prog.req
                roles[i] = llama.ROLE_PREFILL
                off = prog.cached_tokens + prog.next_idx * C
                offsets[i] = off
                counts[i] = C
                ids[i, :C] = prog.chunks[prog.next_idx][0]
                other_hi = max(other_hi, off)
                if prog.next_idx == len(prog.chunks) - 1:
                    L = int(req.prompt.size)
                    last_pos[i] = (L - 1) - off
                    final_lens[i] = L
                    r_temps[i] = req.temperature
                    r_tks[i] = req.top_k
                    r_tps[i] = req.top_p
                    key_data[i] = np.asarray(
                        jax.random.key_data(self._slot_key_for(req))
                    )
            window = superstep_window(decode_hi, other_hi, K, self.capacity)
        n_pre = len(chunk_progs)
        t0 = time.perf_counter()
        with span("engine.decode_dispatch"):  # the read-back nests in it
            tok_block, valid, greedy, accepted, firsts = (
                self._dispatch_superstep(
                    ids, roles, offsets, counts, draft_len, active,
                    remaining, eos_ids, last_pos, final_lens, key_data,
                    r_temps, r_tks, r_tps, window, sampling,
                )
            )
        t0, wall = self._tick_done(t0)
        end = t0 + wall
        finals = sum(
            1 for prog in chunk_progs
            if prog.next_idx == len(prog.chunks) - 1
        )
        acc_total = int(accepted[occupied].sum()) if n_ver else 0
        if not self._in_warmup:
            self.decode_forwards += 1
            if n_pre:
                self.prefill_chunks_dispatched += n_pre
                self.prefill_forwards += 1
                self._note_prefill_tokens(self._chunk_tokens(chunk_progs))
                if self._on_prefill_batch is not None:
                    self._on_prefill_batch(n_pre)
            if n_ver:
                self.spec_verify_ticks += 1
            with span("engine.journal"):
                self._record_tick(
                    "superstep", t0, wall,
                    active_slots=int(occupied.sum()),
                    batch_fill=n_pre,
                    tokens=int(valid.sum()) + n_ver + acc_total + finals,
                    spec_accepted=acc_total,
                    steps=K,
                    cost=self._cost_superstep(window, self._super_width, K),
                    roles={"prefill": n_pre, "decode": n_dec, "verify": n_ver},
                )
                if self._on_step is not None:
                    self._on_step(
                        int(occupied.sum()), wall,
                        self._queue.qsize(), len(self._pending),
                    )
        # Prefill harvest: the _packed_tick bookkeeping, minus the
        # dispatch it no longer owns.
        with span("engine.admit"):
            for i, prog in enumerate(chunk_progs):
                if prog.req.trace is not None:
                    prog.req.trace.slot = prog.slot
                    prog.req.trace.prefill_chunks += 1
                    self._trace_event(
                        prog.req.trace, "prefill_chunk", slot=prog.slot
                    )
                self._maybe_cache_chunk_slot(prog)
                prog.next_idx += 1
                if prog.next_idx < len(prog.chunks):
                    continue
                self._pending.remove(prog)
                self._reserved.discard(prog.slot)
                req = prog.req
                self._slots[prog.slot] = _Slot(
                    future=req.future,
                    remaining=req.max_new_tokens,
                    eos_id=req.eos_id,
                    sampling=req.temperature > 0,
                    on_token=req.on_token,
                    prompt_len=int(req.prompt.size),
                    t_start=t0,
                    request_id=req.request_id,
                    trace=req.trace,
                    **self._spec_slot_state(req),
                    **self._class_slot_state(req),
                )
                self._emit_first(prog.slot, req, firsts[prog.slot])
        # Decode/verify harvest from the same readback.
        with span("engine.emit"):
            for i in range(B):
                if not occupied[i] or self._slots[i] is None:
                    continue
                slot = self._slots[i]
                if roles[i] == llama.ROLE_VERIFY:
                    n_prop, n_acc = int(draft_len[i]), int(accepted[i])
                    if slot.draft is not None:
                        slot.draft.observe(n_prop, n_acc)
                    if n_prop and not self._in_warmup:
                        self.spec_proposed_tokens += n_prop
                        self.spec_accepted_tokens += n_acc
                        if slot.trace is not None:
                            slot.trace.spec_proposed += n_prop
                            slot.trace.spec_accepted += n_acc
                        if self._on_spec is not None:
                            self._on_spec(n_prop, n_acc)
                    # Emit the accepted draft prefix plus the bonus token;
                    # stop early if the slot finishes (eos/budget/cancel).
                    for j in range(n_acc + 1):
                        self._record_token(i, int(greedy[i, j]))
                        if not self._in_warmup:
                            self.decode_tokens += 1
                        if self._slots[i] is None:
                            break
                else:
                    n = int(valid[i])
                    if n <= 0:
                        continue
                    # Per-token timestamps spaced across the tick wall (the
                    # _harvest_fused discipline): K tokens on one instant
                    # would zero every ITL observation.
                    base = max(t0, slot.t_last_token)
                    spread = max(end - base, 0.0)
                    for j in range(n):
                        self._record_token(
                            i, int(tok_block[i, j]), t=base + spread * (j + 1) / n
                        )
                        if not self._in_warmup:
                            self.decode_tokens += 1
                        if self._slots[i] is None:
                            break

    def _dispatch_superstep(
        self, ids, roles, offsets, counts, draft_len, active, remaining,
        eos_ids, last_pos, final_lens, key_data, r_temps, r_tks, r_tps,
        window, sampling,
    ):
        """Broadcast (multihost) then run one unified super-step tick.
        Unlike the fused multistep burst, every input is a HOST array
        (the assembler rebuilds role truth each tick), so the replay
        payload is self-contained — followers keep no chained device
        state for this op."""
        args = (
            ids, roles, offsets, counts, draft_len, active, remaining,
            eos_ids, last_pos, final_lens, key_data, r_temps, r_tks, r_tps,
            window, sampling,
        )
        if self._channel is None:
            return self._device_superstep(*args)
        from .multihost import OP_GEN_SUPERSTEP, encode_message

        payload = encode_message(
            OP_GEN_SUPERSTEP,
            {
                "ids": ids,
                "roles": roles,
                "offsets": offsets,
                "counts": counts,
                "draft_len": draft_len,
                "active": active,
                "remaining": remaining,
                "eos_ids": eos_ids,
                "last_pos": last_pos,
                "final_lens": final_lens,
                "key_data": key_data,
                "temps": r_temps,
                "tks": r_tks,
                "tps": r_tps,
                "window": int(window),
                "sampling": bool(sampling),
            },
        )
        return self._channel.run(
            payload, lambda: self._device_superstep(*args)
        )

    def _device_superstep(
        self, ids, roles, offsets, counts, draft_len, active, remaining,
        eos_ids, last_pos, final_lens, key_data, r_temps, r_tks, r_tps,
        window, sampling,
    ):
        import jax
        import jax.numpy as jnp

        slot_keys = jax.random.wrap_key_data(jnp.asarray(key_data))
        (
            tok_block,
            valid,
            greedy,
            accepted,
            firsts,
            self._tokens,
            self._cache_k,
            self._cache_v,
            self._lengths,
            self._keys,
            self._temps,
            self._topk,
            self._topp,
        ) = self._superstep(
            self._params,
            jnp.asarray(ids),
            self._cache_k,
            self._cache_v,
            self._lengths,
            self._tokens,
            self._keys,
            self._temps,
            self._topk,
            self._topp,
            jnp.asarray(roles),
            jnp.asarray(offsets),
            jnp.asarray(counts),
            jnp.asarray(draft_len),
            jnp.asarray(active),
            jnp.asarray(remaining),
            jnp.asarray(eos_ids),
            jnp.asarray(last_pos),
            jnp.asarray(final_lens),
            slot_keys,
            jnp.asarray(r_temps),
            jnp.asarray(r_tks),
            jnp.asarray(r_tps),
            int(window),
            self._decode_steps,
            bool(sampling),
        )
        self._queued_behind("superstep")
        with self._span("engine.decode_readback"):
            return (
                np.asarray(tok_block), np.asarray(valid), np.asarray(greedy),
                np.asarray(accepted), np.asarray(firsts),
            )

    def replay_superstep(
        self, ids, roles, offsets, counts, draft_len, active, remaining,
        eos_ids, last_pos, final_lens, key_data, temps, tks, tps,
        window, sampling,
    ) -> None:
        """Follower side of a unified super-step tick (multihost
        lockstep).  Every input arrives in the payload; no device-
        resident chain state is consulted."""
        self._device_superstep(
            np.asarray(ids), np.asarray(roles), np.asarray(offsets),
            np.asarray(counts), np.asarray(draft_len), np.asarray(active),
            np.asarray(remaining), np.asarray(eos_ids),
            np.asarray(last_pos), np.asarray(final_lens),
            np.asarray(key_data), np.asarray(temps), np.asarray(tks),
            np.asarray(tps), int(window), bool(sampling),
        )

    # -- self-speculative decoding (n-gram draft + batched verify) -----------

    def _collect_drafts(self) -> list[list[int]]:
        """Per-slot draft proposals for this tick (``[]`` = no draft).

        The budget is the slot's adaptive draft length capped at
        ``remaining - 1``: acceptance emits up to budget+1 tokens and a
        slot must never be asked to emit past its request."""
        drafts: list[list[int]] = []
        for slot in self._slots:
            if slot is None or slot.draft is None:
                drafts.append([])
                continue
            budget = min(slot.draft.budget(), slot.remaining - 1)
            if budget < 1:
                drafts.append([])
                continue
            drafts.append(self._propose(slot, budget))
        return drafts

    def _propose(self, slot: _Slot, budget: int) -> list[int]:
        """N-gram ("prompt lookup") draft from the slot's own history.
        Separate method so tests can swap in an oracle drafter."""
        from .speculative import propose_ngram

        return propose_ngram(
            slot.history[: slot.hist_len], budget,
            self._spec.ngram_min, self._spec.ngram_max,
        )

    def _verify_tick(self, active_np, window: int, drafts) -> None:
        """One draft+verify pass: k+1 positions per slot under ONE weight
        stream; per-slot greedy acceptance decides how many emit."""
        from .speculative import pad_to_chain

        span = self._span
        with span("engine.decode_assemble"):
            s_draft = pad_to_chain(
                max(len(d) for d in drafts), self._spec_chain
            )
            toks = np.zeros((self.max_slots, s_draft + 1), np.int32)
            draft_len = np.zeros((self.max_slots,), np.int32)
            for i, slot in enumerate(self._slots):
                if slot is None:
                    continue
                toks[i, 0] = slot.generated[-1]  # pending (unfed) token
                d = drafts[i]
                toks[i, 1 : 1 + len(d)] = d
                draft_len[i] = len(d)
        t0 = time.perf_counter()
        self._beat("verify")
        with span("engine.decode_dispatch"):  # the read-back nests in it
            greedy, accepted = self._dispatch_verify(
                toks, active_np, draft_len, window
            )
        done = self._tick_done(t0)
        with span("engine.journal"):
            acc_total = int(np.asarray(accepted)[active_np].sum())
            self._note_tick(
                active_np, *done, kind="verify",
                tokens=int(active_np.sum()) + acc_total,
                spec_accepted=acc_total,
                cost=self._cost_decode(window, s_draft + 1),
            )
        if not self._in_warmup:
            self.spec_verify_ticks += 1
        with span("engine.emit"):
            for i, was_active in enumerate(active_np):
                if not was_active or self._slots[i] is None:
                    continue
                slot = self._slots[i]
                n_prop, n_acc = int(draft_len[i]), int(accepted[i])
                if slot.draft is not None:
                    slot.draft.observe(n_prop, n_acc)
                if n_prop and not self._in_warmup:
                    self.spec_proposed_tokens += n_prop
                    self.spec_accepted_tokens += n_acc
                    if slot.trace is not None:
                        slot.trace.spec_proposed += n_prop
                        slot.trace.spec_accepted += n_acc
                    if self._on_spec is not None:
                        self._on_spec(n_prop, n_acc)
                # Emit the accepted draft prefix plus the bonus token;
                # stop early if the slot finishes (eos / budget) or
                # cancels.
                for j in range(n_acc + 1):
                    self._record_token(i, int(greedy[i, j]))
                    if not self._in_warmup:
                        self.decode_tokens += 1
                    if self._slots[i] is None:
                        break

    def _dispatch_verify(self, toks, active_np, draft_len, window):
        if self._channel is None:
            return self._device_verify(toks, active_np, draft_len, window)
        from .multihost import OP_GEN_VERIFY, encode_message

        payload = encode_message(
            OP_GEN_VERIFY,
            {
                "toks": toks,
                "active": active_np,
                "draft_len": draft_len,
                "window": int(window),
            },
        )
        return self._channel.run(
            payload,
            lambda: self._device_verify(toks, active_np, draft_len, window),
        )

    def _device_verify(self, toks, active_np, draft_len, window):
        import jax.numpy as jnp

        (
            self._tokens,
            self._cache_k,
            self._cache_v,
            self._lengths,
            greedy,
            accepted,
        ) = self._verify(
            self._params,
            jnp.asarray(toks),
            self._cache_k,
            self._cache_v,
            self._lengths,
            jnp.asarray(active_np),
            jnp.asarray(draft_len),
            int(window),
        )
        self._queued_behind("verify")
        with self._span("engine.decode_readback"):
            return np.asarray(greedy), np.asarray(accepted)

    def replay_verify(self, toks, active, draft_len, window) -> None:
        """Follower side of a verify tick (multihost lockstep)."""
        self._device_verify(
            np.asarray(toks), np.asarray(active),
            np.asarray(draft_len), int(window),
        )

    def _dispatch_step(self, active_np, window, sampling) -> None:
        if self._channel is None:
            self._device_step(active_np, window, sampling)
            return
        from .multihost import OP_GEN_STEP, encode_message

        payload = encode_message(
            OP_GEN_STEP,
            {"active": active_np, "window": int(window), "sampling": bool(sampling)},
        )
        self._channel.run(
            payload, lambda: self._device_step(active_np, window, sampling)
        )

    def _device_step(self, active_np, window, sampling) -> None:
        import jax.numpy as jnp

        if sampling:
            (
                self._tokens,
                self._cache_k,
                self._cache_v,
                self._lengths,
                self._keys,
                *aux,
            ) = self._decode(
                self._params,
                self._tokens,
                self._cache_k,
                self._cache_v,
                self._lengths,
                jnp.asarray(active_np),
                self._keys,
                self._temps,
                self._topk,
                self._topp,
                window,
            )
        else:
            (
                self._tokens,
                self._cache_k,
                self._cache_v,
                self._lengths,
                *aux,
            ) = self._decode_greedy(
                self._params,
                self._tokens,
                self._cache_k,
                self._cache_v,
                self._lengths,
                jnp.asarray(active_np),
                window,
            )
        self._note_experts("decode", int(np.sum(active_np)), len(active_np), aux)

    # -- the step in flight ----------------------------------------------------
    # A plain step goes out from the outputs of the one before it, which stay
    # on the device (tokens and lengths are not donated, K/V are), so the host
    # can dispatch step n+1 before it reads step n back: it reads step n from
    # the token array it held before that dispatch.  Host slot truth lags one
    # step meanwhile; each slot's known budget says which rows step n+1 needs.

    def _step_rows(self) -> tuple:
        """The rows of the step about to go out, its attention window and
        its token rule.  A row is in while its known budget exceeds the
        steps still un-read for it (one where the step in flight was
        computed for this very slot): a row that step finishes by length
        gets no step past it, one it finishes on EOS gets one whose token
        nobody reads.  The window is the bucket of the furthest next write
        position of the rows in, the step in flight counted."""
        out = self._ahead.slots if self._ahead else (None,) * self.max_slots
        active = np.zeros((self.max_slots,), bool)
        needed, sampling = 0, False
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            unread = int(out[i] is slot)
            if slot.remaining <= unread:
                continue
            active[i] = True
            needed = max(needed, slot.prompt_len + len(slot.generated) + unread)
            sampling = sampling or slot.sampling
        return active, decode_window_bucket(needed, self.capacity), sampling

    def _step_out(self, t0: float, active_np, window: int, noted: int):
        """The step just dispatched, as its read-back will need it: its own
        token array, the slot each row was computed for and the counts it
        noted (moved off ``_moe_pending``: converting them before the step
        is read back would wait for it)."""
        if not self._in_warmup and self._on_decode_dispatch is not None:
            self._on_decode_dispatch(
                "in_turn" if self._ahead is None else "ahead"
            )
        counts = self._moe_pending[noted:]
        del self._moe_pending[noted:]
        slots = tuple(
            s if on else None for s, on in zip(self._slots, active_np)
        )
        return _StepOut(t0, self._tokens, slots, window, counts)

    def _read_step(self, step: _StepOut) -> None:
        """Read a step back, journal the rows it emits, emit them.  A row's
        token goes to the slot it was computed for and to no other: a slot
        that finished meanwhile may hold a new admission already, whose
        insert reset the row behind this step on the device."""
        span = self._span
        self._beat("decode")
        with span("engine.decode_readback"):
            toks = np.asarray(step.tokens)[:, 0]
            done = self._tick_done(step.t0)
            self._read_experts(step.counts)
        with span("engine.journal"):
            live = np.array([
                s is not None and s is self._slots[i]
                for i, s in enumerate(step.slots)
            ])
            self._note_tick(
                live, *done, tokens=int(live.sum()),
                cost=self._cost_decode(step.window),
            )
        with span("engine.emit"):
            for i in np.flatnonzero(live):
                self._record_token(int(i), int(toks[i]))
                if not self._in_warmup:
                    self.decode_tokens += 1

    # -- the starvation account ------------------------------------------------
    # When the chip had nothing to run, what it was waiting to be given and
    # what the host was doing meanwhile: ``tracer.account("device_starved")``.
    # An interval opens where the engine thread sees the last tick program it
    # dispatched end (``_tick_done``) and closes where the call that hands the
    # device the next one returns, under that program's kind (a dispatch onto
    # an idle chip is the host's time too).  It is the host's view: a
    # completion is seen when the thread blocks on it or next looks, so the
    # account can only under-count.  Programs that are no ticks (the scratch's
    # zero-fills, an admission's eager scalars) end no interval: they are the
    # host work it charges.  Always on; a warm-up sweep counts nothing.

    def _dispatched(self, kind: str) -> None:
        """The device has just been handed a tick program: one more is
        out, and the starved interval, if one is open, ends before it.
        Waiting for traffic is no part of it; what no phase covered is
        the root's."""
        if self._in_warmup:
            return
        if self._starved.mark is not None:
            self._starved.close(kind, "engine.iteration", ("engine.wait_work",))
        self._unseen += 1

    def _queued_behind(self, kind: str) -> None:
        """A decode-side program whose read-back follows at once (its
        dispatch span holds both) is out: wait for what went before it."""
        self._dispatched(kind)
        self._close_ticks(behind=kind)

    def _send_chunk_ahead(self) -> None:
        """Dispatch the in-flight admission's next chunk right behind the
        step just dispatched, before the pass reads anything back.  The
        chunk reads and writes only the batch-1 scratch and needs nothing
        the step produces, so the chip goes on to it while the host reads
        the step back, emits, journals and runs the next admit phase,
        which then sends no other (``prog.ahead``): the device's order
        stays chunk, step, chunk, step.

        One rule on what the engine observes: an admission whose first
        chunk is out (a fresh one's waits for the slot a read-back
        frees; a seed's tick waits on the scratch this would donate) and
        that has a chunk left, no other program of it un-waited but the
        one this pass sent, and no prefix-cache write-back to read the
        chunk back at once.  A dispatch error is the admission's."""
        if self._packed or not self._pending:
            return
        prog = self._pending[0]
        if (
            not 0 < prog.next_idx < len(prog.chunks)
            or len(self._open_ticks) > 1
            or self._chunk_to_cache(prog) is not None
        ):
            return
        try:
            self._dispatch_next_chunk(prog, "ahead")
        except Exception as exc:
            raise _TickFailed("prefill", (prog.req,), at="dispatch") from exc
        prog.ahead = True

    def _settle_ticks(self) -> None:
        """Read back the step in flight and close the open ticks where the
        admit phase is about to read device state (a control op, an
        eviction, a packed chunk) or to wait for traffic, outside
        ``_loop``'s handler: a wait that fails there is its admission's,
        or the step's, all the same."""
        try:
            self._close_ticks()
        except _TickFailed as failed:
            self._admission_failed(failed.owners, failed)
        except Exception:
            _log.exception("decode step failed")
            self._fail_all_and_recover()

    def _loop(self) -> None:
        span = self._span
        while not self._stop.is_set():
            # One root span a pass; the phases under it (``engine.admit``
            # here, the rest at their sites) cover it, so its self time
            # is the uninstrumented remainder.
            with span("engine.iteration"):
                # Heartbeat: the idle stamp is overwritten by the dispatch
                # sites below just before they block on a device call, so
                # a wedged tick is attributed to its kind, not to "idle".
                self._beat("idle")
                with span("engine.admit"):
                    alive = self._admit_phase()
                if not alive:
                    break  # shutdown sentinel
                try:
                    self._step()
                    # A pass that read nothing back (no slot active)
                    # waits for its chunk here.  One program stays out
                    # across the end of a pass, and is waited for behind
                    # the next step's dispatch: a chunk sent ahead, else
                    # the step in flight.
                    if self._ahead is None and not (
                        self._pending and self._pending[0].ahead
                    ):
                        self._close_ticks()
                except _TickFailed as failed:
                    self._admission_failed(failed.owners, failed)
                except Exception:
                    _log.exception("decode step failed")
                    self._fail_all_and_recover()
        try:
            self._close_ticks()  # shutdown with a step or a chunk in flight
        except Exception:
            _log.exception("a program in flight failed at shutdown")

    def _dequeue_or_wait(self, block: bool):
        """:meth:`_dequeue`; blocking (no slot active, nothing pending)
        is waiting for traffic, not host cost: ``engine.wait_work``."""
        if not block:
            return self._dequeue(False, self._idle_poll_s)
        self._settle_ticks()  # nothing stays out across a wait for traffic
        with self._span("engine.wait_work"):
            return self._dequeue(True, self._idle_poll_s)

    def _admit_phase(self) -> bool:
        """Admission work for one scheduler iteration.

        Fused mode drains every free slot; single-admission chunked mode
        advances the in-flight admission by ONE chunk unless the last
        pass's step sent that chunk ahead already (or starts a new
        one); packed mode tops up the admission queue (one reserved cache
        row each) and advances up to ``prefill_batch`` of them with ONE
        batched call.  In every mode the decode tick that follows is
        never more than one prefill tick away — in-flight streams keep
        their token cadence under long prompts.  Returns False on the
        shutdown sentinel."""
        self._drain_control_ops()
        if self._preemption:
            self._maybe_preempt()
        if self._packed:
            return self._admit_phase_packed()
        if self._pending:
            prog = self._pending[0]  # _chunk_tick pops it on finish
            try:
                self._chunk_tick()
            except Exception as exc:
                self._admission_failed([prog.req], exc)
            return True
        while self._free_slot() is not None:
            try:
                idle = all(s is None for s in self._slots)
                req = self._dequeue_or_wait(idle)
            except queue.Empty:
                break
            if isinstance(req, _Wake):
                self._drain_control_ops()
                continue
            if req is not None:
                self._release_queued(req)  # left the admission queue
            if req is None or self._stop.is_set():
                # A real request dequeued during shutdown is in neither
                # the queue nor a slot — fail it here or its client
                # awaits a future nobody will ever resolve.
                if req is not None and not req.future.done():
                    _safe_fail(
                        req.future,
                        EngineShutdown(
                            "engine shut down before admission; retry on "
                            "another replica"
                        ),
                    )
                return False
            if isinstance(req, _Preempted):
                # An evicted sequence re-admits straight into the free
                # slot the loop condition guarantees — no prefill.
                try:
                    self._admit_restore(req)
                except Exception as exc:
                    _log.exception("preemption restore failed")
                    if not req.future.done():
                        self._abort_trace(req.trace, "error")
                        _safe_fail(req.future, exc)
                    self._fail_all_and_recover()
                continue
            self._note_admission_wait(req)
            if self._prefill_chunk_size is not None:
                prog = self._make_progress(req)
                if self._sp_eligible(req) and not prog.cached_tokens:
                    # Long cold prompt: one ring pass now instead of
                    # queuing L/C serial chunk ticks.  Warm prefixes
                    # keep the seed + suffix-chunk path — the cache
                    # already skips the work sp would parallelize.
                    try:
                        self._admit_sp(req)
                    except Exception as exc:
                        self._admission_failed([req], exc)
                    continue
                self._pending.append(prog)
                return True  # first chunk runs next iteration's admit phase
            try:
                if self._sp_eligible(req):
                    self._admit_sp(req)
                else:
                    self._admit(req)
            except Exception as exc:  # keep the scheduler alive
                self._admission_failed([req], exc)
        return True

    def _admit_phase_packed(self) -> bool:
        """Packed-mode admission: top up the in-flight queue (each new
        admission reserves a free cache row), then advance up to
        ``prefill_batch`` admissions with one batched call."""
        popped = False
        while True:
            slot = self._free_slot()
            if slot is None:
                break
            idle = not self._pending and all(s is None for s in self._slots)
            try:
                req = self._dequeue_or_wait(idle and not popped)
            except queue.Empty:
                break
            if isinstance(req, _Wake):
                self._drain_control_ops()
                continue
            if req is not None:
                self._release_queued(req)  # left the admission queue
            if req is None or self._stop.is_set():
                if req is not None and not req.future.done():
                    _safe_fail(
                        req.future,
                        EngineShutdown(
                            "engine shut down before admission; retry on "
                            "another replica"
                        ),
                    )
                return False
            if isinstance(req, _Preempted):
                try:
                    self._admit_restore(req)
                except Exception as exc:
                    _log.exception("preemption restore failed")
                    if not req.future.done():
                        self._abort_trace(req.trace, "error")
                        _safe_fail(req.future, exc)
                    self._fail_all_and_recover()
                popped = True
                continue
            self._note_admission_wait(req)
            prog = self._make_progress(req)
            if self._sp_eligible(req) and not prog.cached_tokens:
                # Long cold prompt: the ring pass (batch-1 scratch, no
                # reserved row needed) beats packing its L/C chunks
                # into the batched program one budget at a time.
                try:
                    self._admit_sp(req)
                except Exception as exc:
                    self._admission_failed([req], exc)
                popped = True
                continue
            prog.slot = slot
            self._reserved.add(slot)
            self._pending.append(prog)
            popped = True
        if not self._pending:
            return True
        if self._unified:
            # Chunks ride the NEXT super-step dispatch (_super_tick
            # consumes up to the packed budget of pending admissions as
            # prefill rows); a failure there runs _loop's recovery,
            # which fails pending packed admissions too.
            return True
        # A packed call reads its first tokens back at once: the step in
        # flight is read first, so each wall stays its own program's.
        self._settle_ticks()
        try:
            self._packed_tick()
        except Exception as exc:
            self._admission_failed([p.req for p in self._pending], exc)
        return True

    def _admission_failed(self, reqs, exc: Exception) -> None:
        """A prefill-side program of ``reqs``' admission raised, at its
        dispatch or at the wait for it, wherever the loop took that wait
        (a :class:`_TickFailed` names the tick's own requests): count the
        crash against their prompts, fail their futures with the device
        error, drop their progress and recover the device state.  Where the
        step in flight failed at the admission's blocking point
        (:class:`_StepFailed`), the requests are lost with the slots and
        their prompts are not blamed: decode crashes are not attributed."""
        self._drop_admission(reqs, exc)
        self._open_ticks = []  # that admission's: lost with the device state
        self._fail_all_and_recover()

    def _drop_admission(self, reqs, exc: Exception) -> None:
        """:meth:`_admission_failed` less the recovery of the device
        state (which may be what found the failure)."""
        if isinstance(exc, _StepFailed):
            exc = exc.__cause__
            _log.error("decode step failed under an admission", exc_info=exc)
        else:
            if isinstance(exc, _TickFailed):
                reqs, exc = exc.owners or reqs, exc.__cause__
            _log.error(
                "admission failed: a prefill-side program raised", exc_info=exc
            )
            self._note_admission_crash(reqs)
        failed = {id(req) for req in reqs}
        self._pending = [p for p in self._pending if id(p.req) not in failed]
        self._seq_state = None  # the scratch is that admission's
        for req in reqs:
            if not req.future.done():
                _safe_fail(req.future, exc)

    def _fail_all_and_recover(self) -> None:
        """Fail every in-flight sequence and reallocate device state.

        A failed jitted call poisons all slots (their K/V history is part of
        the donated buffers), and donation has ALREADY invalidated those
        buffers — reusing them would raise "Array has been deleted" on every
        later request, bricking the engine while /ready stays green.  Fresh
        buffers restore service for subsequent requests.  A chunk sent
        ahead of the failure is waited for first: journaled if it ran (the
        scratch is not the slots'), its admission failed with the rest if
        it did not.  The step in flight is lost with the slots it serves."""
        self._ahead = None
        try:
            self._close_ticks()
        except _TickFailed as failed:
            self._drop_admission(failed.owners, failed)
        # What was out is lost with the device state: nothing is unseen,
        # and no interval runs from a completion that never came.
        self._unseen = 0
        self._starved.drop()
        for i, slot in enumerate(self._slots):
            if slot is not None and not slot.future.done():
                self._abort_trace(slot.trace, "error")
                _safe_fail(
                    slot.future,
                    RuntimeError("generation step failed; see server log"),
                )
            self._slots[i] = None
        if self._packed:
            # Packed admissions prefill STRAIGHT into the donated cache
            # rows, so the reset below destroys their half-written
            # prompts (single-mode admissions live in the untouched
            # batch-1 scratch and survive).  Fail them — continuing over
            # zeroed K/V would stream corrupted completions as 200s.
            for prog in self._pending:
                if not prog.req.future.done():
                    self._abort_trace(prog.req.trace, "error")
                    _safe_fail(
                        prog.req.future,
                        RuntimeError(
                            "generation step failed; see server log"
                        ),
                    )
            self._pending = []
            self._reserved.clear()
        if self._channel is not None:
            # Followers replayed the op that just failed here; their buffers
            # are invalidated (or their state now diverges).  Broadcast the
            # reset so every host drops to the same fresh state — otherwise
            # each subsequent replayed step runs with disagreeing
            # lengths/cache shards and silently corrupts tokens.
            from .multihost import OP_GEN_RESET, encode_message

            try:
                self._channel.run(
                    encode_message(OP_GEN_RESET, {}),
                    self._reset_device_state,
                )
                return
            except Exception:
                _log.exception("broadcasting gen reset failed")
        try:
            self._reset_device_state()
        except Exception:
            _log.exception("device state reallocation failed")


@dataclass(eq=False)
class _StepOut:
    """A plain decode step dispatched and not read back yet
    (``GenerationEngine._ahead`` while it stays out across a pass)."""

    t0: float  # perf_counter before its dispatch
    # Its own output tokens: the step after it reads them on the device,
    # and does not donate them.
    tokens: object
    slots: tuple  # the _Slot each row was computed for, None where inactive
    window: int
    counts: list  # the ``_moe_pending`` entries it noted, until its read-back


class _StepFailed(RuntimeError):
    """The read-back of the decode step in flight raised (``__cause__``
    holds the error), wherever the pass took it: at a blocking point of an
    admission too.  The failure is the step's, and no prompt is blamed
    (``GenerationEngine._drop_admission``)."""
