"""Device telemetry: HBM ledger, compile observatory, cost model + MFU.

Every perf PR so far justified itself with hand-derived "weight streams
per token" arithmetic; this module makes the hardware story a measured,
served surface instead of a code comment.  Three parts:

- **HBM ledger** — an analytic byte ledger of what the serving process
  holds on device (weight tree by dtype, KV cache incl. the int8kv
  layout's scale planes, per-slot sampling state) cross-checked against
  ``device.memory_stats()`` where the platform provides it.  Served at
  ``GET /debug/device``, exported as ``tpumlops_device_hbm_bytes
  {component}``, and stamped into the model-capacity startup log line
  (``server/loader.py`` emits that line even with telemetry off).
- **Compile observatory** — wraps every engine jit dispatch so each XLA
  compilation is attributed to the op that triggered it (decode buckets,
  verify variants, prefill B_p buckets, seed ops), with wall time and
  persistent-cache hit/miss from ``utils/compile_cache``'s jax
  monitoring hooks.  One structured ``tpumlops.compile`` log line per
  compilation; ``tpumlops_compile_seconds_total{op}`` and
  ``tpumlops_compile_cache_{hits,misses}_total`` series; a warning when
  the warmup sweep exceeds the readiness budget (cold-start is a
  first-class serving cost — "Breaking the Ice", PAPERS.md).
- **Cost model + utilization** — analytic per-program FLOPs / HBM-bytes
  estimates for the llama serving programs, joined with flight-recorder
  tick walls into per-tick-kind MFU and HBM-bandwidth utilization.  The
  ENGINE path is analytic by design: its programs are jit-dispatched
  with donated buffers, so there is no compiled object in hand and an
  AOT re-lower just to ask XLA's opinion would double every compile.
  :func:`cost_from_analysis` is the adapter for contexts that DO hold a
  ``Compiled`` (scripts, notebooks, AOT tooling — ``lower().compile()
  .cost_analysis()``), and the test suite uses it to cross-check the
  analytic numbers against XLA's own count.  Exposed surfaces:
  ``mfu`` / ``hbm_bw_util`` fields on recorder ticks, Perfetto counter
  tracks in ``/debug/trace``, and ``tpumlops_device_{mfu,hbm_bw_util}
  {kind}`` gauges.

Error bars (documented in docs/OBSERVABILITY.md): the analytic FLOPs
count is exact for the matmul tree and counts the attention einsums at
the full padded window, so MFU is a lower bound on "useful" utilization
by at most the padding fraction; HBM bytes assume each weight byte and
each attended cache byte streams exactly once (XLA re-reads under
fusion-decline pathologies, so bw_util can read > 1 of the *model*
while still < 1 of the wire — values are clamped to (0, 1]).

``spec.tpu.observability.deviceTelemetry`` (CRD -> config -> builder
``--device-telemetry`` -> server CLI) gates the whole layer; off — the
default — constructs nothing and every payload stays byte-for-byte.
"""

from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass, field

_log = logging.getLogger("tpumlops.device_telemetry")
_compile_log = logging.getLogger("tpumlops.compile")

# Warmup sweep budget before a warning fires: the builder's readiness
# probe window is initialDelay 10 + period 5 x failureThreshold 60 =
# 310 s; a sweep past ~300 s risks the kubelet killing the pod
# mid-compile (SURVEY §7 hard part 3).
READINESS_BUDGET_S = 300.0


# ---------------------------------------------------------------------------
# Device facts (peaks the utilization ratios divide by)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class DevicePeaks:
    """Peak rates the utilization ratios are read against.

    ``chips`` is how many chips the numbers cover: the cost model and
    ledger count the WHOLE (possibly sharded) model, so the peaks must
    cover the whole device set holding it — a tp=8 mesh divides by 8x
    the per-chip roofline, or every ratio reads 8x high and clamps."""

    kind: str  # the DEVICE_PEAKS row's name
    flops_per_s: float  # dense bf16 peak
    hbm_bytes_per_s: float
    hbm_bytes: int  # HBM capacity
    source: str  # "detected" (table row) | whatever a caller's own peaks say
    chips: int = 1
    int8_ops_per_s: float = 0.0  # dense s8 peak (0 = not stated)
    # Per-chip ICI bandwidth the collective-wall estimates divide by
    # (rough order-of-magnitude constants, marked per ``source`` like
    # the rooflines; stays PER-CHIP under scaled() — a ring all-reduce's
    # wall is set by one link, not the aggregate).
    ici_bytes_per_s: float = 2e11

    def scaled(self, chips: int) -> "DevicePeaks":
        import dataclasses

        n = max(1, int(chips))
        return dataclasses.replace(
            self,
            flops_per_s=self.flops_per_s * n,
            int8_ops_per_s=self.int8_ops_per_s * n,
            hbm_bytes_per_s=self.hbm_bytes_per_s * n,
            hbm_bytes=self.hbm_bytes * n,
            chips=n,
        )


def param_device_count(params) -> int:
    """Devices the param tree is actually sharded over (1 for the
    default unsharded tree, even when more devices are visible)."""
    try:
        import jax

        leaf = jax.tree.leaves(params)[0]
        return max(1, len(leaf.sharding.device_set))
    except Exception:
        return 1


class UnknownDeviceKind(LookupError):
    """``device_kind`` has no row in :data:`DEVICE_PEAKS`.  A utilization
    ratio against another part's roofline is not a measurement, so an
    unlisted device is an error, never a default; callers that want
    ratios on such a device (CPU tests) pass ``peaks=`` themselves."""

    def __init__(self, kind: str):
        self.kind = kind
        super().__init__(
            f"no peak rates known for device kind {kind!r} "
            f"(known: {sorted(DEVICE_PEAKS)}); add a row with its source "
            "to DEVICE_PEAKS or pass peaks= explicitly"
        )


# THE peaks table, per chip, keyed by the exact
# ``jax.Device.device_kind`` (a v5e reports "TPU v5 lite").  Source:
# Google Cloud TPU documentation, "TPU v5e" / "TPU v4" system
# architecture pages.
DEVICE_PEAKS = {
    "TPU v5 lite": DevicePeaks(
        "tpu-v5e", 197e12, 819e9, 16 * 2**30, "detected",
        int8_ops_per_s=394e12,
    ),
    "TPU v4": DevicePeaks(
        "tpu-v4", 275e12, 1228e9, 32 * 2**30, "detected",
        int8_ops_per_s=275e12,
    ),
}


def peaks_for(device_kind: str) -> DevicePeaks:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise UnknownDeviceKind(device_kind) from None


def detect_peaks() -> DevicePeaks:
    """Peaks of the first visible device; :class:`UnknownDeviceKind`
    when it is not in the table."""
    import jax

    return peaks_for(jax.devices()[0].device_kind)


def measured_memory() -> dict | None:
    """``device.memory_stats()`` summed over the ADDRESSABLE devices
    (TPU/GPU runtimes report it; CPU returns None).  ``devices`` counts
    how many reported — on a multi-host unit each process sees only its
    local chips, so the ledger cross-check scales by the addressable
    fraction (see :meth:`HbmLedger.snapshot`).  ``per_device_bytes_in_use``
    keeps the addends of the ``bytes_in_use`` sum, in device order: the
    sum alone cannot show a sharded model that landed on one chip."""
    import jax

    totals: dict = {}
    per_device: list[int] = []
    for dev in jax.local_devices():
        stats = dev.memory_stats()
        if not stats:
            continue
        per_device.append(int(stats.get("bytes_in_use", 0)))
        for k, v in stats.items():
            if isinstance(v, (int, float)):
                totals[k] = totals.get(k, 0) + int(v)
    if not per_device:
        return None
    totals["devices"] = len(per_device)
    totals["per_device_bytes_in_use"] = per_device
    return totals


# ---------------------------------------------------------------------------
# HBM ledger
# ---------------------------------------------------------------------------


def weights_bytes_by_dtype(params, per_chip: bool = False) -> dict[str, int]:
    """Parameter bytes grouped by dtype as stored (int8 leaves count
    1 byte/elem; their f32 scale planes land under float32).

    ``per_chip=True`` counts what ONE device holds — exact, via each
    leaf's shard shape: a tp-sharded matrix counts 1/tp of its bytes,
    a replicated norm counts whole on every chip."""
    import jax

    out: dict[str, int] = {}
    for leaf in jax.tree.leaves(params):
        name = str(leaf.dtype)
        if per_chip:
            try:
                from ..models.partition import shard_bytes

                nbytes = shard_bytes(leaf)
            except Exception:  # host arrays / exotic shardings
                nbytes = int(leaf.size) * leaf.dtype.itemsize
        else:
            nbytes = int(leaf.size) * leaf.dtype.itemsize
        out[name] = out.get(name, 0) + nbytes
    return out


def kv_cache_bytes_per_row(
    cfg, kv_quant: bool, dtype_bytes: int = 2, tp: int = 1, family=None
) -> int:
    """Bytes one cache row (slot at full ``max_seq``) holds: k + v across
    all layers, plus the int8kv layout's per-(pos, head) f32 scales.
    ``tp`` > 1 gives the PER-CHIP row (the heads axis is what shards, so
    each chip holds num_kv_heads/tp of every row).  ``family``: the
    causal-LM family's module where it is not the dense one whose
    arithmetic this is (the predictor's ``causal_lm["family"]``); it then
    answers for its own cache (``kv_row_bytes``)."""
    if family is not None:
        return family.kv_row_bytes(cfg, dtype_bytes)
    heads = cfg.num_kv_heads // max(1, int(tp))
    elems = cfg.num_layers * heads * cfg.max_seq * cfg.head_dim
    if kv_quant:
        # int8 values + f32 scale per head_dim group, for k and v each.
        return 2 * (elems + (elems // cfg.head_dim) * 4)
    return 2 * elems * dtype_bytes


def sampling_state_bytes(max_slots: int) -> int:
    """Engine per-slot device state outside the cache: token buffer
    (int32), PRNG keys (2x uint32), temps/topk/topp (4 B each)."""
    return max_slots * (4 + 8 + 4 + 4 + 4)


@dataclass
class HbmLedger:
    """Analytic device-byte ledger, cross-checkable against
    ``memory_stats()``.  ``components`` are on-device; ``host_components``
    (the prefix cache's host-RAM budget) ride along for the capacity
    story but never count toward the device total."""

    components: dict[str, int] = field(default_factory=dict)
    host_components: dict[str, int] = field(default_factory=dict)
    kv_bytes_per_row: int = 0
    max_slots: int = 0
    # tp > 1: what ONE chip holds of each component (weights exact via
    # shard shapes, kv/sampling analytic) — the per-chip view the
    # tpumlops_device_hbm_bytes{component="*_per_chip"} gauges export.
    per_chip: dict[str, int] = field(default_factory=dict)
    chips: int = 1

    def device_total(self) -> int:
        return sum(self.components.values())

    def max_cache_rows(self, hbm_bytes: int) -> int:
        """Full-capacity KV rows that fit beside the weights — the
        capacity number the autoscaler/operator plans against."""
        if self.kv_bytes_per_row <= 0:
            return 0
        spare = hbm_bytes - sum(
            v for k, v in self.components.items() if not k.startswith("kv_")
        )
        return max(0, spare // self.kv_bytes_per_row)

    def snapshot(self, peaks: DevicePeaks | None = None) -> dict:
        peaks = peaks or detect_peaks()
        measured = measured_memory()
        out = {
            "components": dict(self.components),
            "host_components": dict(self.host_components),
            "device_total_bytes": self.device_total(),
            "kv_bytes_per_row": self.kv_bytes_per_row,
            "max_slots": self.max_slots,
            "hbm_capacity_bytes": peaks.hbm_bytes,
            "hbm_source": peaks.source,
            "max_cache_rows": self.max_cache_rows(peaks.hbm_bytes),
            "measured": measured,
        }
        if self.per_chip:
            out["per_chip"] = dict(self.per_chip)
            out["chips"] = self.chips
        if measured and measured.get("bytes_in_use"):
            # Multi-host: this process addresses only its local chips,
            # which hold addressable/total of the sharded model — scale
            # the ledger to what THESE chips should hold before
            # comparing.
            frac = min(1.0, measured["devices"] / max(1, peaks.chips))
            expected = self.device_total() * frac
            out["ledger_vs_measured_pct"] = round(
                100.0 * (expected - measured["bytes_in_use"])
                / max(1, measured["bytes_in_use"]),
                1,
            )
        return out


def build_hbm_ledger(
    params,
    cfg,
    max_slots: int,
    kv_quant: bool = False,
    dtype_bytes: int = 2,
    prefix_cache_budget_bytes: int = 0,
    tp: int = 1,
    dp: int = 1,
    family=None,
) -> HbmLedger:
    dp = max(1, int(dp))
    ledger = HbmLedger(
        kv_bytes_per_row=kv_cache_bytes_per_row(
            cfg, kv_quant, dtype_bytes, family=family
        ),
        max_slots=int(max_slots),
        chips=max(1, int(tp)) * dp,
    )
    if family is not None:
        # Sparse experts: the routed experts (all resident, a few read a
        # token) apart from the weights every token streams.
        ledger.components["weights_routed_experts"] = sum(
            weights_bytes_by_dtype(family.routed_expert_leaves(params)).values()
        )
        params = _without_routed_experts(params)
    for dtype, nbytes in weights_bytes_by_dtype(params).items():
        ledger.components[f"weights_{dtype}"] = nbytes
    ledger.components["kv_cache"] = ledger.kv_bytes_per_row * int(max_slots)
    if hasattr(family, "state_row_bytes"):
        # A slot's recurrent state is a constant beside its bytes a
        # position: its own line, out of the rows'.
        state = family.state_row_bytes(cfg, dtype_bytes) * int(max_slots)
        ledger.components["cache_state"] = state
        ledger.components["kv_cache"] -= state
    ledger.components["sampling_state"] = sampling_state_bytes(max_slots)
    if prefix_cache_budget_bytes:
        ledger.host_components["prefix_cache_budget"] = int(
            prefix_cache_budget_bytes
        )
    if tp > 1 or dp > 1:
        for dtype, nbytes in weights_bytes_by_dtype(
            params, per_chip=True
        ).items():
            ledger.per_chip[f"weights_{dtype}"] = nbytes
        row_chip = kv_cache_bytes_per_row(cfg, kv_quant, dtype_bytes, tp=tp)
        ledger.per_chip["kv_bytes_per_row"] = row_chip
        # dp shards the ROW axis: one chip holds max_slots/dp rows (of
        # its tp heads-shard of each).
        ledger.per_chip["kv_cache"] = row_chip * (int(max_slots) // dp)
        # Sampling state replicates: every chip holds the whole thing.
        ledger.per_chip["sampling_state"] = sampling_state_bytes(max_slots)
        ledger.per_chip["total"] = sum(
            v for k, v in ledger.per_chip.items()
            if k != "kv_bytes_per_row"
        )
    return ledger


def _without_routed_experts(params) -> dict:
    layers = [{k: v for k, v in lp.items() if k != "experts"}
              for lp in params["layers"]]
    return {**params, "layers": layers}


def capacity_log_line(params, cfg, kv_quant: bool,
                      peaks: DevicePeaks | None = None, family=None) -> str:
    """The model-capacity startup line ``server/loader.py`` stamps (even
    with telemetry off): weights by dtype, KV bytes/row, max cache rows.
    HBM covers the device set the params are sharded over.  On a device
    kind with no :data:`DEVICE_PEAKS` row the line says so instead of
    pricing rows against another part's HBM."""
    n_chips = param_device_count(params)
    by_dtype = weights_bytes_by_dtype(params)
    total = sum(by_dtype.values())
    per_row = kv_cache_bytes_per_row(cfg, kv_quant, family=family)
    try:
        peaks = (peaks or detect_peaks()).scaled(n_chips)
    except UnknownDeviceKind as e:
        capacity = (
            f"max cache rows not computed (device kind {e.kind!r} has no "
            "peaks row)"
        )
    else:
        spare = peaks.hbm_bytes - total
        rows = max(0, spare // per_row) if per_row else 0
        chips = f" x{peaks.chips}" if peaks.chips > 1 else ""
        capacity = (
            f"max cache rows {rows} "
            f"(hbm {peaks.hbm_bytes / 2**30:.1f} GiB "
            f"{peaks.source} {peaks.kind}{chips})"
        )
    dtypes = ", ".join(
        f"{k}={v / 2**20:.1f}MiB" for k, v in sorted(by_dtype.items())
    )
    per_chip = ""
    if n_chips > 1:
        # The tp view: what ONE chip actually holds (weights exact via
        # shard shapes, KV row = heads/tp) — the number that fits or
        # OOMs on the hardware.
        chip_w = sum(weights_bytes_by_dtype(params, per_chip=True).values())
        chip_row = kv_cache_bytes_per_row(cfg, kv_quant, tp=n_chips)
        per_chip = (
            f", per-chip weights {chip_w / 2**20:.1f} MiB "
            f"kv {chip_row} B/row"
        )
    sparse = ""
    if family is not None:
        active, held = family.param_counts(cfg)
        sparse = f", params active {active} of {held}"
    return (
        f"model capacity: weights {total / 2**20:.1f} MiB ({dtypes}), "
        f"kv {per_row} B/row (max_seq {cfg.max_seq}"
        f"{', int8kv' if kv_quant else ''}), "
        f"{capacity}{per_chip}{sparse}"
    )


# ---------------------------------------------------------------------------
# Compile observatory
# ---------------------------------------------------------------------------


class CompileObservatory:
    """Attributes every XLA compilation to the engine op that triggered
    it.

    The engine wraps each jitted callable with :meth:`wrap_jit`; the
    wrapper pins the op name in a thread-local for the duration of the
    call, and ``utils/compile_cache``'s jax monitoring hooks deliver
    (compile wall, cache hit/miss) events back through :meth:`on_event`
    — compiles are synchronous inside the triggering dispatch, so the
    attribution is exact.  Each compilation logs one structured
    ``tpumlops.compile`` line (from ``utils/compile_cache``, which asks
    this observatory for the current op)."""

    MAX_EVENTS = 256

    def __init__(self, readiness_budget_s: float = READINESS_BUDGET_S):
        self._lock = threading.Lock()
        self._tls = threading.local()
        self.readiness_budget_s = float(readiness_budget_s)
        # op -> {"compiles", "seconds", "cache_hits", "cache_misses"}
        self.ops: dict[str, dict] = {}
        self.events: list[dict] = []  # newest-last, bounded
        self._in_warmup = False
        self.warmup: dict = {}
        self._on_compile = None  # (op, seconds) -> None (metrics hookup)
        self._on_cache = None  # (hit: bool) -> None

    # -- wiring ---------------------------------------------------------------

    def install(self) -> None:
        """Register with utils/compile_cache's monitoring hooks (idempotent
        there); safe to call before any jit."""
        from ..utils.compile_cache import install_compile_listeners

        install_compile_listeners(observatory=self)

    def set_metrics_hooks(self, on_compile=None, on_cache=None) -> None:
        self._on_compile = on_compile
        self._on_cache = on_cache

    def wrap_jit(self, op: str, fn):
        """Wrap a jitted callable so compiles inside it attribute to
        ``op``.  Transparent otherwise — same args, same returns."""

        def wrapped(*args, **kwargs):
            prev = getattr(self._tls, "op", None)
            self._tls.op = op
            try:
                return fn(*args, **kwargs)
            finally:
                self._tls.op = prev

        wrapped.__name__ = f"observed_{op}"
        return wrapped

    def current_op(self) -> str:
        return getattr(self._tls, "op", None) or "other"

    # -- event sinks (called from utils/compile_cache's listeners) -----------

    def on_event(self, kind: str, seconds: float = 0.0) -> None:
        """``kind``: "compile" (with backend wall) or "cache_hit" /
        "cache_miss" (persistent-cache outcome of the compile request)."""
        op = self.current_op()
        with self._lock:
            rec = self.ops.setdefault(
                op,
                {"compiles": 0, "seconds": 0.0,
                 "cache_hits": 0, "cache_misses": 0},
            )
            if kind == "compile":
                rec["compiles"] += 1
                rec["seconds"] += seconds
                self.events.append(
                    {"op": op, "seconds": round(seconds, 4),
                     "ts": time.time(), "warmup": self._in_warmup}
                )
                del self.events[: -self.MAX_EVENTS]
                if self._in_warmup:
                    self.warmup["compiles"] = self.warmup.get("compiles", 0) + 1
                    self.warmup["seconds"] = (
                        self.warmup.get("seconds", 0.0) + seconds
                    )
            elif kind == "cache_hit":
                rec["cache_hits"] += 1
            elif kind == "cache_miss":
                rec["cache_misses"] += 1
        if kind == "compile" and self._on_compile is not None:
            self._on_compile(op, seconds)
        elif kind in ("cache_hit", "cache_miss") and self._on_cache is not None:
            self._on_cache(kind == "cache_hit")

    # -- warmup sweep ---------------------------------------------------------

    def begin_warmup(self) -> None:
        with self._lock:
            self._in_warmup = True
            self.warmup = {"compiles": 0, "seconds": 0.0}
            # Per-op compile counts at sweep start, so end_warmup can
            # report the variant INVENTORY the sweep itself compiled —
            # not lifetime totals polluted by pre-warmup seeds.
            self._warmup_baseline = {
                op: rec["compiles"] for op, rec in self.ops.items()
            }
            self._t_warmup = time.perf_counter()

    def end_warmup(self) -> dict:
        with self._lock:
            self._in_warmup = False
            self.warmup["wall_s"] = round(
                time.perf_counter() - getattr(self, "_t_warmup", 0.0), 2
            )
            baseline = getattr(self, "_warmup_baseline", {})
            inventory = {
                op: rec["compiles"] - baseline.get(op, 0)
                for op, rec in sorted(self.ops.items())
                if rec["compiles"] - baseline.get(op, 0) > 0
            }
            self.warmup["ops"] = inventory
            report = dict(self.warmup)
            report["ops"] = dict(inventory)
        inv = (
            " ".join(f"{op}={n}" for op, n in report["ops"].items()) or "-"
        )
        if report["wall_s"] > self.readiness_budget_s:
            _log.warning(
                "warmup sweep took %.1fs (> readiness budget %.0fs): "
                "%d compiles [%s], %.1fs of XLA work — the kubelet may "
                "kill this pod mid-compile; pre-seed the persistent "
                "compile cache or raise the readiness window",
                report["wall_s"], self.readiness_budget_s,
                report["compiles"], inv, report["seconds"],
            )
        else:
            # The variant inventory in one structured line: the op ×
            # count breakdown makes a program-space regression (or the
            # unified engine's K-fold collapse) visible without diffing
            # gauge snapshots.
            _compile_log.info(
                "warmup sweep done compiles=%d compile_s=%.2f "
                "wall_s=%.2f ops=[%s]",
                report["compiles"], report["seconds"],
                report["wall_s"], inv,
            )
        return report

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "ops": {k: dict(v) for k, v in self.ops.items()},
                "events": [dict(e) for e in self.events],
                "warmup": dict(self.warmup),
                "readiness_budget_s": self.readiness_budget_s,
            }


# ---------------------------------------------------------------------------
# Cost model
# ---------------------------------------------------------------------------


def cost_from_analysis(analysis) -> tuple[float, float] | None:
    """Parse an XLA ``Compiled.cost_analysis()`` payload into
    ``(flops, hbm_bytes)`` (jax returns a dict, or a 1-list of dicts on
    older versions).  For callers that hold a compiled object — scripts
    / AOT tooling / the cross-check test — NOT the engine hot path,
    which is analytic by design (its programs are jit-dispatched with
    donated buffers; see the module docstring)."""
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else None
    if not isinstance(analysis, dict):
        return None
    flops = float(analysis.get("flops", 0.0))
    nbytes = float(analysis.get("bytes accessed", 0.0))
    if flops <= 0.0 and nbytes <= 0.0:
        return None
    return flops, nbytes


@dataclass(frozen=True)
class LlamaCostModel:
    """Analytic per-program FLOPs / HBM-bytes for the llama serving
    programs.  ``matmul_params`` is the weight-matrix element count (the
    2-flops-per-param term); ``weight_bytes`` the tree as stored (int8
    leaves 1 B) — every program streams it once."""

    matmul_params: int
    weight_bytes: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    kv_elem_bytes: float  # bytes per cache element incl. scale overhead
    # Tensor-parallel collective geometry (tp == 1 -> no collectives):
    # hidden/vocab size the per-layer all-reduces and the logits
    # all-gather move, in the serving activation dtype.
    tp: int = 1
    hidden_size: int = 0
    vocab_size: int = 0
    act_bytes: int = 2
    # Batch (row) and sequence parallel degrees — dp shards the cache's
    # row axis (no extra collectives: weights replicate and the logits
    # all-gather already covers the replicated read-back); sp adds the
    # ring-permute K/V rotation costed in :meth:`ring_bytes`.
    dp: int = 1
    sp: int = 1

    @classmethod
    def for_model(cls, params, cfg, kv_quant: bool = False,
                  dtype_bytes: int = 2,
                  mesh_shape=None) -> "LlamaCostModel":
        import jax

        from ..models.llama import matmul_param_count

        wbytes = sum(
            int(leaf.size) * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(params)
        )
        hd = cfg.head_dim
        kv_eb = 1 + 4.0 / hd if kv_quant else float(dtype_bytes)
        # Prefer the declared mesh: under dp the params REPLICATE over
        # dp*tp devices, so the sharded-device count alone would
        # over-report tp by the dp factor.
        if mesh_shape:
            tp = max(1, int(dict(mesh_shape).get("tp", 1)))
            dp = max(1, int(dict(mesh_shape).get("dp", 1)))
            sp = max(1, int(dict(mesh_shape).get("sp", 1)))
        else:
            tp, dp, sp = param_device_count(params), 1, 1
        return cls(
            matmul_params=matmul_param_count(cfg),
            weight_bytes=wbytes,
            num_layers=cfg.num_layers,
            num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads,
            head_dim=hd,
            kv_elem_bytes=kv_eb,
            tp=tp,
            hidden_size=int(getattr(cfg, "hidden_size", 0)),
            vocab_size=int(getattr(cfg, "vocab_size", 0)),
            act_bytes=int(dtype_bytes),
            dp=dp,
            sp=sp,
        )

    def collective_bytes(self, rows: int, s: int = 1) -> dict[str, float]:
        """Per-device ICI bytes one forward dispatch moves at tp > 1:

        - ``all_reduce`` — the Megatron pair: 2 psums per layer (after
          the o and down projections) of the ``[rows*s, hidden]``
          activation block; a ring all-reduce moves ``2(tp-1)/tp`` of
          the block per device;
        - ``all_gather`` — the vocab-sharded lm_head product gathered
          for replicated token/logit outputs: ``(tp-1)/tp`` of
          ``[rows*s, vocab]`` f32 once per dispatch.

        Empty at tp == 1 (no collectives exist to estimate)."""
        if self.tp <= 1:
            return {}
        tokens = float(rows) * float(s)
        block = tokens * self.hidden_size * self.act_bytes
        ar = 2.0 * self.num_layers * block * 2.0 * (self.tp - 1) / self.tp
        ag = tokens * self.vocab_size * 4.0 * (self.tp - 1) / self.tp
        return {"all_reduce": ar, "all_gather": ag}

    def _kv_bytes(self, rows: int, positions: float) -> float:
        """k+v cache traffic for ``rows`` rows over ``positions`` each."""
        return (
            2.0 * rows * positions * self.num_layers * self.num_kv_heads
            * self.head_dim * self.kv_elem_bytes
        )

    def decode(self, rows: int, window: int, s: int = 1
               ) -> tuple[float, float]:
        """One decode (``s=1``) or verify (``s`` positions/row) tick over
        ``rows`` cache rows attending ``window`` positions."""
        flops = 2.0 * self.matmul_params * rows * s
        flops += 4.0 * rows * s * window * self.num_heads * self.head_dim
        nbytes = self.weight_bytes + self._kv_bytes(rows, window)
        nbytes += self._kv_bytes(rows, s)  # fresh K/V written
        return flops, nbytes

    def prefill(self, rows: int, chunk: int, attended: float | None = None
                ) -> tuple[float, float]:
        """One prefill call: ``rows`` rows of ``chunk`` tokens each,
        attending ``attended`` mean positions (defaults to the causal
        mean over the chunk itself)."""
        if attended is None:
            attended = chunk / 2.0
        flops = 2.0 * self.matmul_params * rows * chunk
        flops += 4.0 * rows * chunk * attended * self.num_heads * self.head_dim
        nbytes = self.weight_bytes + self._kv_bytes(rows, chunk)
        nbytes += self._kv_bytes(rows, max(0.0, attended - chunk / 2.0))
        return flops, nbytes

    def superstep(self, rows: int, window: int, s: int, steps: int
                  ) -> tuple[float, float]:
        """One unified super-step dispatch: the wide ragged forward
        (``s`` positions/row — the verify-chain / prefill-chunk width)
        plus ``steps - 1`` chained single-position decode iterations
        under the same dispatch.  A composition of :meth:`decode`, so
        the unified engine's cost stays consistent with the split
        programs it replaces."""
        flops, nbytes = self.decode(rows, window, s)
        if steps > 1:
            f1, b1 = self.decode(rows, window, 1)
            flops += (steps - 1) * f1
            nbytes += (steps - 1) * b1
        return flops, nbytes

    def seed(self, tokens: int) -> tuple[float, float]:
        """Prefix-cache seed: a pure K/V copy — read + write, no flops."""
        return 0.0, 2.0 * self._kv_bytes(1, tokens)

    def sp_prefill(self, tokens: int) -> tuple[float, float]:
        """One ring-attention prefill pass over a ``tokens``-long padded
        prompt: same total flops/bytes as a fused prefill of the whole
        prompt (the ring changes WHERE the S x S work runs — S/sp per
        device — not how much exists)."""
        return self.prefill(1, tokens)

    def ring_bytes(self, tokens: int) -> dict[str, float]:
        """Per-device ICI bytes the sp ring rotation moves in one
        prefill pass: each device forwards its K/V shard ``sp - 1``
        times per layer (k and v each, [1, S/sp, NKV, D] blocks).
        Empty at sp == 1 — no ring exists to estimate."""
        if self.sp <= 1:
            return {}
        shard = float(tokens) / self.sp
        per_layer = (
            2.0 * shard * self.num_kv_heads * self.head_dim * self.act_bytes
        )
        return {
            "ring_permute": per_layer * self.num_layers * (self.sp - 1)
        }


# ---------------------------------------------------------------------------
# Facade the server wires together
# ---------------------------------------------------------------------------


class DeviceTelemetry:
    """One object per server process: ledger + observatory + cost model.

    Constructed only when ``spec.tpu.observability.deviceTelemetry`` is
    on; ``None`` everywhere otherwise, so the disabled path allocates
    nothing and every existing payload stays byte-for-byte."""

    def __init__(self, metrics=None,
                 readiness_budget_s: float = READINESS_BUDGET_S,
                 peaks: DevicePeaks | None = None):
        # Per-chip until attach_model scales to the param-holding device
        # set; _chip_peaks keeps the pristine base so a rebind/re-attach
        # can never compound the scaling.
        self._chip_peaks = peaks or detect_peaks()
        self.peaks = self._chip_peaks
        self.observatory = CompileObservatory(readiness_budget_s)
        self.observatory.install()
        self.ledger: HbmLedger | None = None
        self.cost = None  # LlamaCostModel, or the family's own cost model
        # {"active", "total"} where the family tells them apart.
        self.param_counts: dict | None = None
        self._metrics = None
        # Last computed utilization per tick kind (the /debug/device
        # mirror of the gauges).  Written by the engine scheduler
        # thread, read by the /debug/device executor thread — the lock
        # covers the first-tick-of-a-new-kind insert racing a snapshot
        # iteration.
        self._util_lock = threading.Lock()
        self.last_util: dict[str, dict] = {}
        if metrics is not None:
            self.bind_metrics(metrics)

    def bind_metrics(self, metrics) -> None:
        """Hook the Prometheus families (present only when the registry
        was built with ``device_telemetry=True``)."""
        if getattr(metrics, "device_hbm_bytes", None) is None:
            return
        self._metrics = metrics
        self.observatory.set_metrics_hooks(
            on_compile=metrics.observe_compile,
            on_cache=metrics.observe_compile_cache,
        )

    def attach_model(self, params, cfg, max_slots: int,
                     kv_quant: bool = False, dtype_bytes: int = 2,
                     prefix_cache_budget_bytes: int = 0,
                     mesh_shape=None, family=None) -> None:
        """Build the ledger + cost model once the engine geometry is
        known; exports the per-component HBM gauges.  Peaks scale to the
        device set actually holding the params (the cost model and
        ledger count the whole sharded model).  ``mesh_shape`` (when
        the engine runs one) disambiguates the axes: params replicated
        over a dp axis span dp*tp devices, which the sharded-device
        count alone would misread as tp."""
        if mesh_shape:
            tp = max(1, int(dict(mesh_shape).get("tp", 1)))
            dp = max(1, int(dict(mesh_shape).get("dp", 1)))
            chips = 1
            for v in dict(mesh_shape).values():
                chips *= max(1, int(v))
        else:
            tp, dp = param_device_count(params), 1
            chips = tp
        self.peaks = self._chip_peaks.scaled(chips)
        self.ledger = build_hbm_ledger(
            params, cfg, max_slots, kv_quant=kv_quant,
            dtype_bytes=dtype_bytes,
            prefix_cache_budget_bytes=prefix_cache_budget_bytes,
            tp=tp, dp=dp, family=family,
        )
        if family is not None:
            self.cost = family.cost_model(params, cfg, dtype_bytes)
            active, total = family.param_counts(cfg)
            self.param_counts = {"active": active, "total": total}
        else:
            self.cost = LlamaCostModel.for_model(
                params, cfg, kv_quant=kv_quant, dtype_bytes=dtype_bytes,
                mesh_shape=mesh_shape,
            )
        if self._metrics is not None:
            for comp, nbytes in self.ledger.components.items():
                self._metrics.observe_hbm_component(comp, nbytes)
            self._metrics.observe_hbm_component(
                "total", self.ledger.device_total()
            )
            # tp > 1: the per-chip view rides the same family under
            # ``<component>_per_chip`` label values — what ONE chip
            # holds, which is what fits-or-OOMs on the hardware.
            for comp, nbytes in self.ledger.per_chip.items():
                if comp == "kv_bytes_per_row":
                    continue
                self._metrics.observe_hbm_component(
                    f"{comp}_per_chip", nbytes
                )

    def tick_util(self, kind: str, wall_s: float, flops: float,
                  hbm_bytes: float) -> dict:
        """Join one tick's wall with its program cost: MFU and HBM-BW
        utilization, clamped to (0, 1] (see the module docstring's error
        bars).  Returns the dict merged onto the recorder tick."""
        wall = max(wall_s, 1e-9)
        mfu = min(1.0, flops / wall / self.peaks.flops_per_s)
        bw = min(1.0, hbm_bytes / wall / self.peaks.hbm_bytes_per_s)
        # 3 significant digits, NOT fixed decimals: a CPU dev tick's
        # 4e-7 MFU must stay > 0 (the in-(0,1] contract), and a real
        # chip's 0.41 needs no more precision.
        util = {
            "mfu": float(f"{mfu:.3g}") if flops > 0 else 0.0,
            "hbm_bw_util": float(f"{bw:.3g}"),
        }
        if (
            self.cost is not None
            and (self.cost.tp > 1 or kind == "sp-prefill")
            and kind in ("decode", "verify", "multistep", "prefill",
                         "packed-prefill", "superstep", "sp-prefill")
        ):
            # Analytic collective walls at tp > 1: one dispatch's ICI
            # traffic over the per-chip link rate, split by op — the
            # tpumlops_engine_collective_seconds{op} feed.  The token
            # count is recovered from the tick's own flops (flops ~=
            # 2 x matmul_params x tokens), so a fused K-step scan, an
            # S-position verify, and a packed chunk call all count
            # their full per-dispatch traffic, not one token-row's.
            tokens = flops / max(1.0, 2.0 * self.cost.matmul_params)
            coll = self.cost.collective_bytes(tokens)
            if kind == "sp-prefill":
                # The ring rotation is the sp axis's collective wall —
                # per-layer K/V shard forwards, costed per device.
                coll = dict(coll)
                coll.update(self.cost.ring_bytes(tokens))
            total_coll = 0.0
            for op, nbytes in coll.items():
                secs = nbytes / self.peaks.ici_bytes_per_s
                total_coll += secs
                if self._metrics is not None:
                    self._metrics.observe_collective(op, secs)
            util["collective_s"] = float(f"{total_coll:.3g}")
        with self._util_lock:
            self.last_util[kind] = util
        if self._metrics is not None:
            self._metrics.observe_device_util(kind, mfu, bw)
        return util

    def snapshot(self) -> dict:
        """The ``GET /debug/device`` payload."""
        with self._util_lock:
            utilization = {k: dict(v) for k, v in self.last_util.items()}
        out = {
            "peaks": {
                "device": self.peaks.kind,
                "source": self.peaks.source,
                "chips": self.peaks.chips,
                "flops_per_s": self.peaks.flops_per_s,
                "hbm_bytes_per_s": self.peaks.hbm_bytes_per_s,
                "hbm_bytes": self.peaks.hbm_bytes,
            },
            "hbm": self.ledger.snapshot(self.peaks) if self.ledger else None,
            "utilization": utilization,
            "compile": self.observatory.snapshot(),
        }
        if self.param_counts is not None:
            # What a token multiplies through against what the chip holds.
            out["params"] = dict(self.param_counts)
        return out
