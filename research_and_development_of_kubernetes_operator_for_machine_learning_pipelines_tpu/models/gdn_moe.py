"""Decoder of GQA layers, Gated DeltaNet layers or GQA window layers
beside full GQA layers, and a sparse-expert FFN.

Two configurations of one family, the layer kinds read from a table
(``layer_types``; by default a full layer every
``full_attention_interval``, linear ones between):

- The Qwen3-Next block: pre-norm with zero-centred RMSNorms (``rms(x) (1
  + w)``); ``full_attention_interval - 1`` linear-attention layers (Gated
  DeltaNet: a short causal convolution, then the gated delta rule on a
  recurrent state a value head) for every layer of gated softmax
  attention (GQA, per-head q/k norms, rotary on part of the head, a
  sigmoid gate on every number of the heads' outputs); every FFN routed
  experts (softmax scores, top-k renormalised) beside one sigmoid-gated
  shared expert; untied head.  Reference:
  ``benchmarks/references/qwen3_next_decoder.py``.
- The Laguna block: plain RMSNorms (``rms(x) w``); full GQA layers and
  sliding-window GQA layers (``sliding_attention``: the query's own
  position and the ``sliding_window - 1`` before it) with their own
  query-head counts and rotary (``cfg.view(kind)``: the full kind YaRN on
  part of the head, the sliding kind plain RoPE on all of it), no q/k
  norms, a sigmoid gate a head (``attention_gate: headwise``) from the
  normed layer input; leading SwiGLU layers (``mlp_only_layers``), then
  softmax-routed experts with a routed scaling factor beside an ungated
  shared expert.  Reference: ``benchmarks/references/laguna_decoder.py``.

What the serving engine needs of a causal-LM family is here under the
names ``models/llama.py`` gives them, as in ``models/mla_moe.py``:
``KVCache`` / ``RaggedKVCache`` (the donated pair stays ``(k, v)``, each
a dict of buffers by kind, below), ``forward``, ``prefill``,
``decode_ragged``, ``insert_sequence``, ``generate_greedy``.  ``forward``
and ``decode_ragged`` return one value more than llama's: int32 ``[5]``
(``COUNTS``): the expert layer's three counts as ``mla_moe.moe_ffn``
gives them, the real tokens folded into a recurrent state and the rows
whose state the call read and wrote, both times the linear layers, which
the engine turns into the ``tpumlops_moe_*`` and ``tpumlops_gdn_*``
counters.

Design decisions:

- Two kinds of state live in one cache.  A full layer holds ROWS INDEXED
  BY POSITION, ``k["key"]`` / ``v["value"]`` ``[B, T, kv_heads *
  head_dim]`` (the KV heads side by side on the lanes: one buffer a
  layer, no size-1 axis, the findings of ``mla_moe.py``'s cache).  A
  linear layer holds a STATE THAT IS NO FUNCTION OF A POSITION:
  ``v["state"]`` float32 ``[B, value_heads, key_dim, value_dim]`` (``S``
  of the delta rule) and ``k["conv"]`` ``[B, kernel - 1, channels]`` (the
  convolution's last input rows), read and written whole by every chunk
  and every step.  A sliding layer holds its last ``ring_rows`` ROWS ON
  A RING, ``k["ring_key"]`` / ``v["ring_value"]`` ``[B, ring, kv_heads *
  head_dim]``, a row at ``position mod ring`` (``mla_moe.py``'s ring at
  GQA's widths): a slot's rows whatever its length.  Every buffer is
  donated through every program; the scratch sequence carries the state
  and the ring from chunk to chunk and the insert copies them as they
  stand.
- The delta rule in two forms that agree.  A token does ``S <- exp(g) S``,
  ``d = beta (v - S^T k)``, ``S <- S + k d^T``, ``o = S^T q``.  A step
  does exactly that (``_delta_step``).  A chunk (``_delta_chunks``) cuts
  its tokens into sub-chunks of ``SUB_CHUNK`` = 64 and, in each, solves
  the 64 tokens' mutual corrections at once: with ``G`` the running sum
  of ``g`` and ``L[i, j] = beta_i (k_i . k_j) exp(G_i - G_j)`` below the
  diagonal, ``T = (I + L)^-1`` turns the tokens' own ``beta v`` and
  ``beta k exp(G)`` into what they write given the state before the
  sub-chunk, so the state is touched once a sub-chunk by matrix products
  (the WY form of the published chunked rule).  ``(I + L)^-1`` is the
  product ``(I + M)(I + M^2)(I + M^4)...`` of ``M = -L`` (nilpotent: six
  squarings reach 64), matrix products on the MXU where a triangular
  solve would walk rows.  All of it float32 at ``highest`` precision: a
  bf16 pass in a product into ``S`` compounds over thousands of tokens.
- Padding leaves the state alone: a padded row has ``beta = 0``, ``g =
  0`` (it writes nothing and decays nothing), and the convolution's
  carried rows are the last ``kernel - 1`` REAL rows of the call, not
  its last slots (padding trails a row's real tokens: the engine pads
  behind).  A slot that is not live in a step keeps its state.
- The full layers' prefill attends in key blocks with a running maximum
  and sum (``_gqa_blocks``; the trip count follows what is written), a
  decode step a strict window plus the position in flight, rows
  committed after the loop: ``mla_moe.py``'s contracts at GQA's shapes.
  A sliding layer's chunk attends the ring's ``ring_rows`` rows before
  it, in position order, and its own rows, masked by absolute positions
  (the ring's oldest row is outside every query's window: a ring of 512
  and a chunk of 512 make two whole key blocks), and the ring takes the
  chunk's rows when the layer has read it; its step reads the whole
  ring.  On the TPU both kinds' prefill core is one Pallas kernel
  (``ops/gqa_prefill_attention.py``: a key block's scores stay in VMEM,
  the rows read as they lie), the einsum body off it and where no tiling
  fits.
- The expert layer is ``mla_moe.moe_ffn``, shared with that family: one
  grouped matmul, one share arithmetic (``n_local_experts`` from
  ``local_expert_start`` of a router over all ``n_routed_experts``), one
  set of counts.  This family asks it for softmax scores, no selection
  bias, the routed scaling factor, and a gate on the shared expert where
  the configuration has one.
- Which (query, key) pairs the attention cores multiply and how many of
  them the causal and window masks keep (``attn_pairs``) is host
  arithmetic from a call's shapes and lengths, at its dispatch.
- Layers are a list of per-layer trees and the loop is unrolled, as in
  ``mla_moe.py`` and for its reason (the grouped matmul's operand must
  be a whole buffer).
- Not here (``UNSUPPORTED``, refused typed): what ``mla_moe.py`` lacks,
  and, for a reason of each kind's own, every mechanism that takes
  cached state to be rows that are a pure function of a token prefix,
  every position's row kept: the radix prefix cache and preemption,
  speculative rollback, KV transfer.  The multi-token-prediction module
  is not loaded.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, replace
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.gqa_prefill_attention import gqa_prefill_attention
from .common import rms_norm
from .llama import _attended_window, _embed, _qmatmul
from .mla_moe import (
    _commit_row,
    _key_block,
    _key_tile,
    _layer_plan,
    _layer_rows,
    _ring_bias,
    _ring_rows,
    _ring_slots,
    _swiglu,
    _tree_bytes,
    greedy_scan,
    moe_ffn,
    routed_expert_leaves,  # noqa: F401  (the HBM ledger asks the family)
)
from ..ops.grouped_matmul import row_tile


FLAVOR = "gdn-moe-generate"  # registry / artifact name of this family
PAD_ID = -1  # padding rows of a prompt chunk: ids < 0 touch no state
_STATE = ("the recurrent state of a linear-attention layer is no function "
          "of a position: ")
_RING = ("; a sliding-window layer's ring keeps only a slot's last window "
         "of rows: ")
UNSUPPORTED = {
    "quantize": "int8 weights or an int8 cache",
    "mesh": "sharding over more than one chip (no expert, tensor, data or "
            "sequence-parallel path, no ring prefill)",
    "speculative": "speculative decoding (" + _STATE + "truncating a row's "
                   "length does not undo the rejected tokens' updates" + _RING
                   + "a rejected token's row has overwritten the one a "
                   "window back)",
    "prefix_cache": "the radix prefix cache and preemption (" + _STATE
                    + "reuse and resume need a snapshot of it at a chunk "
                    "boundary, not a copy of rows" + _RING + "a prefix "
                    "reused or resumed past the window needs rows it has "
                    "overwritten)",
    "prefill_batch": "packed multi-admission prefill",
    "decode_steps": "the fused multi-step decode program",
    "unified_step": "the unified super-step program",
    "kv_transfer": "KV transfer between prefill and decode replicas ("
                   + _STATE + "the wire carries rows" + _RING + "the wire "
                   "carries every position's row)",
}

LINEAR, FULL, SLIDING = "linear_attention", "full_attention", "sliding_attention"
GATES = ("elementwise", "headwise")  # a sigmoid a number / a head of the output
NORMS = ("zero_centred", "plain")  # rms(x) (1 + w) / rms(x) w
# What the int32 vector holds that ``forward`` and ``decode_ragged`` return
# behind llama's outputs, summed over layers.
COUNTS = ("experts_hit", "row_tile_visits", "local_assignments",
          "gdn_tokens", "gdn_state_passes")
SUB_CHUNK = 64  # tokens whose mutual corrections the chunked rule solves at once
_HI = lax.Precision.HIGHEST


@dataclass(frozen=True)
class GdnMoeConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    full_attention_interval: int = 4  # layer l is full where (l + 1) % this == 0
    # The full-attention layers.
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10_000_000.0
    # The linear-attention layers.
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    # The expert layer: routed experts held here (0: all of them) of a
    # router over ``n_routed_experts``, as ``mla_moe.MlaMoeConfig``'s.
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    n_routed_experts: int = 512
    num_experts_per_tok: int = 10
    n_local_experts: int = 0
    local_expert_start: int = 0
    max_seq: int = 262144
    rms_eps: float = 1e-6
    # What ``mla_moe.route`` reads: softmax scores, renormalised, times
    # ``routed_scaling_factor``.
    scoring_func: str = "softmax"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # Layer kinds, one a layer (``()``: the interval rule above), and the
    # sliding kind's window (the query's own position and the
    # ``sliding_window - 1`` before it), query heads and rotary (0: the
    # full kind's).
    layer_types: tuple = ()
    sliding_window: int = 0
    swa_num_heads: int = 0
    swa_rope_theta: float = 0.0
    swa_partial_rotary_factor: float = 1.0
    # The full kind's rotary frequencies: "default", or "yarn" with its
    # factor, original context, the two betas and the scale of cos / sin.
    rope_type: str = "default"
    rope_factor: float = 1.0
    rope_original_max_position: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_attention_factor: float = 1.0
    # Variants of the block: the output gate (``GATES``), the RMSNorm
    # (``NORMS``), per-head q/k norms, a sigmoid gate on the shared
    # expert, and leading layers whose FFN is a SwiGLU of
    # ``intermediate_size`` instead of experts.
    attention_gate: str = "elementwise"
    norm: str = "zero_centred"
    qk_norm: bool = True
    shared_expert_gate: bool = True
    mlp_only_layers: tuple = ()
    intermediate_size: int = 0

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "mlp_only_layers", tuple(self.mlp_only_layers))
        for key, only, what in (
            ("scoring_func", ("softmax",), "sigmoid router scores"),
            ("norm_topk_prob", (True,), "un-normalised routing weights"),
            ("attention_gate", GATES, "an output gate of another form"),
            ("norm", NORMS, "an RMSNorm of another form"),
            ("rope_type", ("default", "yarn"), "a rotary of another kind"),
        ):
            if getattr(self, key) not in only:
                raise ValueError(
                    f"{key}={getattr(self, key)!r}: {what} is not "
                    f"implemented for this family (only {key} in {only!r})"
                )
        if self.layer_types:
            unknown = [k for k in self.layer_types if k not in (LINEAR, FULL, SLIDING)]
            if unknown or len(self.layer_types) != self.num_layers:
                raise ValueError(
                    f"layer_types must name {LINEAR!r}, {FULL!r} or "
                    f"{SLIDING!r} once a layer ({self.num_layers}): got "
                    f"{len(self.layer_types)} entries, unknown kinds {unknown}"
                )
        if not self.full_layers:
            raise ValueError(
                f"num_layers {self.num_layers} holds no full-attention layer "
                f"at full_attention_interval {self.full_attention_interval}: "
                "the cache's capacity is the full layers' row"
            )
        if self.sliding_layers and self.sliding_window < 1:
            raise ValueError("sliding_attention layers need a sliding_window")
        if self.routed_scaling_factor <= 0:
            raise ValueError(
                f"routed_scaling_factor {self.routed_scaling_factor} must be > 0")
        if not all(0 <= l < self.num_layers for l in self.mlp_only_layers) or (
            self.mlp_only_layers and self.intermediate_size < 1
        ):
            raise ValueError(
                f"mlp_only_layers {self.mlp_only_layers} must be layers of "
                f"the {self.num_layers} and need an intermediate_size"
            )
        if self.rope_type == "yarn" and not (
            self.rope_factor > 1 and self.rope_original_max_position > 0
        ):
            raise ValueError(
                "rope_type 'yarn' needs rope_factor > 1 and "
                "rope_original_max_position"
            )
        if not 1 <= self.num_experts_per_tok <= self.n_routed_experts:
            raise ValueError(
                f"num_experts_per_tok {self.num_experts_per_tok} outside "
                f"[1, n_routed_experts {self.n_routed_experts}]"
            )
        if self.num_heads % self.num_kv_heads or (
            self.linear_num_value_heads % self.linear_num_key_heads
        ) or (self.swa_num_heads % self.num_kv_heads):
            raise ValueError(
                "query heads must be a multiple of KV heads "
                f"({self.num_heads}, {self.swa_num_heads} / "
                f"{self.num_kv_heads}) and value heads of key heads "
                f"({self.linear_num_value_heads} / "
                f"{self.linear_num_key_heads})"
            )
        for kind, factor in ((FULL, self.partial_rotary_factor),
                             (SLIDING, self.swa_partial_rotary_factor)):
            rotary = int(self.head_dim * factor)
            if kind in self.kinds and (rotary % 2 or not 0 < rotary <= self.head_dim):
                raise ValueError(
                    f"partial_rotary_factor {factor} of head_dim "
                    f"{self.head_dim} must give an even number of rotated "
                    f"dims ({kind}): RoPE rotates pairs"
                )
        if self.linear_conv_kernel_dim < 2:
            raise ValueError(
                f"linear_conv_kernel_dim {self.linear_conv_kernel_dim}: the "
                "cache holds the kernel's last kernel - 1 input rows"
            )
        if not (
            0 <= self.local_expert_start
            and 0 <= self.n_local_experts
            and self.local_expert_start + self.local_experts
            <= self.n_routed_experts
        ):
            raise ValueError(
                f"the expert share [{self.local_expert_start}, "
                f"+{self.n_local_experts}) lies outside the router's "
                f"{self.n_routed_experts} experts"
            )

    @property
    def kinds(self) -> tuple:
        """Each layer's kind."""
        if self.layer_types:
            return self.layer_types
        return tuple(
            FULL if (l + 1) % self.full_attention_interval == 0 else LINEAR
            for l in range(self.num_layers))

    @property
    def full_layers(self) -> tuple:
        return tuple(l for l, k in enumerate(self.kinds) if k == FULL)

    @property
    def linear_layers(self) -> tuple:
        return tuple(l for l, k in enumerate(self.kinds) if k == LINEAR)

    @property
    def sliding_layers(self) -> tuple:
        return tuple(l for l, k in enumerate(self.kinds) if k == SLIDING)

    @property
    def attention_kinds(self) -> tuple:
        """The attention kinds present, in the order layers first show
        them (a tuple, never a set: a traced program's text is a compile
        cache key)."""
        return tuple(k for k in dict.fromkeys(self.kinds) if k != LINEAR)

    @property
    def ring_rows(self) -> int:
        """Rows of a sliding layer's ring (``mla_moe``'s rule)."""
        return _ring_rows(self.sliding_window)

    def view(self, kind: str) -> "GdnMoeConfig":
        """This config with ``kind``'s query heads and rotary under the
        plain names (the full and linear kinds: the config itself)."""
        return _kind_view(self, kind)

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def kv_width(self) -> int:
        """Numbers a position holds in a full layer's K (or V) buffer."""
        return self.num_kv_heads * self.head_dim

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: ``q || k || v``."""
        return 2 * self.key_dim + self.value_dim

    @property
    def local_experts(self) -> int:
        """Routed experts whose matrices this chip holds."""
        return self.n_local_experts or self.n_routed_experts

    @property
    def num_moe_layers(self) -> int:
        return self.num_layers - len(self.mlp_only_layers)

    @classmethod
    def tiny(cls, **kw) -> "GdnMoeConfig":
        defaults = dict(
            vocab_size=256, hidden_size=64, num_layers=4, num_heads=4,
            num_kv_heads=2, head_dim=16, partial_rotary_factor=0.5,
            rope_theta=10000.0, linear_num_key_heads=2,
            linear_num_value_heads=4, linear_key_head_dim=16,
            linear_value_head_dim=16, moe_intermediate_size=32,
            shared_expert_intermediate_size=32, n_routed_experts=8,
            num_experts_per_tok=2, max_seq=64,
        )
        defaults.update(kw)
        return cls(**defaults)


@functools.lru_cache(maxsize=None)
def _kind_view(cfg: GdnMoeConfig, kind: str) -> GdnMoeConfig:
    if kind != SLIDING:
        return cfg
    return replace(
        cfg,
        num_heads=cfg.swa_num_heads or cfg.num_heads,
        rope_theta=cfg.swa_rope_theta or cfg.rope_theta,
        partial_rotary_factor=cfg.swa_partial_rotary_factor,
        rope_type="default",
    )


def _mixer_params(cfg: GdnMoeConfig, kind: str) -> int:
    """Weight-matrix elements of one ``kind`` layer's token mixer (the
    convolution's kernel among them; norms, ``A_log``, ``dt_bias`` not)."""
    h = cfg.hidden_size
    if kind != LINEAR:
        c = cfg.view(kind)
        q = c.num_heads * c.head_dim
        # The elementwise gate is the query projection's other half; the
        # headwise one a number a head.
        gate = q if c.attention_gate == "elementwise" else c.num_heads
        return h * (q + gate) + 2 * h * c.kv_width + q * h
    return (h * (2 * cfg.key_dim + 2 * cfg.value_dim)
            + h * 2 * cfg.linear_num_value_heads
            + cfg.linear_conv_kernel_dim * cfg.conv_dim
            + cfg.value_dim * h)


def param_counts(cfg: GdnMoeConfig) -> tuple[int, int]:
    """``(active, total)`` weight-matrix elements: what one token
    multiplies through in a forward pass (its chosen routed experts as
    far as they are held here, the shared one and its gate, the router,
    the leading SwiGLUs, the head) and what the tree holds (embedding
    included).  The cost model's two terms."""
    h = cfg.hidden_size
    mixers = sum(_mixer_params(cfg, kind) for kind in cfg.kinds)
    expert = 3 * h * cfg.moe_intermediate_size
    shared = 3 * h * cfg.shared_expert_intermediate_size + h * cfg.shared_expert_gate
    router = h * cfg.n_routed_experts
    dense = len(cfg.mlp_only_layers) * 3 * h * cfg.intermediate_size
    head = h * cfg.vocab_size
    chosen_here = (cfg.num_experts_per_tok * cfg.local_experts
                   // cfg.n_routed_experts)
    active = mixers + dense + head + cfg.num_moe_layers * (
        router + shared + expert * chosen_here)
    total = mixers + dense + 2 * head + cfg.num_moe_layers * (
        router + shared + expert * cfg.local_experts)
    return active, total


def routed_assignments(cfg: GdnMoeConfig, tokens: int) -> int:
    """(token, expert) pairs ``tokens`` real tokens make in one forward
    pass, wherever the expert is held."""
    return int(tokens) * cfg.num_experts_per_tok * cfg.num_moe_layers


def moe_row_tile(cfg: GdnMoeConfig, tokens: int) -> int:
    """Rows a visit of the grouped matmuls multiplies in a program call
    over ``tokens`` token rows (padding included: the shape is static)."""
    return row_tile(int(tokens) * cfg.num_experts_per_tok, cfg.local_experts)


def state_row_bytes(cfg: GdnMoeConfig, dtype_bytes: int = 2) -> int:
    """Bytes of recurrent state one slot holds, whatever its length: a
    linear layer's ``S`` in float32 and its convolution's carried rows."""
    s = (cfg.linear_num_value_heads * cfg.linear_key_head_dim
         * cfg.linear_value_head_dim * 4)
    tail = (cfg.linear_conv_kernel_dim - 1) * cfg.conv_dim * dtype_bytes
    return len(cfg.linear_layers) * (s + tail)


def ring_row_bytes(cfg: GdnMoeConfig, dtype_bytes: int = 2) -> int:
    """Bytes of ring one slot holds, whatever its length: a sliding
    layer's K and V at ``ring_rows`` rows."""
    return len(cfg.sliding_layers) * cfg.ring_rows * 2 * cfg.kv_width * dtype_bytes


def kv_row_bytes(cfg: GdnMoeConfig, dtype_bytes: int = 2) -> int:
    """Bytes one cache row (a slot at full ``max_seq``) holds: the full
    layers' K and V a position, and the linear layers' state and the
    sliding layers' rings, constants a slot (``state_row_bytes``,
    ``ring_row_bytes``)."""
    rows = len(cfg.full_layers) * cfg.max_seq * 2 * cfg.kv_width * dtype_bytes
    return (rows + state_row_bytes(cfg, dtype_bytes)
            + ring_row_bytes(cfg, dtype_bytes))


def _pairs_to(first: int, count: int, most: int = 0) -> int:
    """(query, key) pairs inside the causal mask of ``count`` queries at
    positions ``first ..``: a query at ``t`` attends ``t + 1`` positions,
    at most ``most`` (0: no window)."""
    last = first + count  # one past the last query's own count of keys
    if not most or last <= most:
        return count * first + count * (count + 1) // 2
    full = max(0, last - max(first, most - 1))  # queries that see a whole window
    return _pairs_to(first, count - full) + full * most


def attn_pairs(cfg: GdnMoeConfig, program: str, first: list, count: list,
               width: int) -> dict:
    """``{kind: (computed, attended)}`` over the layers of each attention
    kind of one program call, host arithmetic from its shapes: the
    (query, key) pairs the attention cores multiply for real query rows,
    and those inside the causal and window masks (a pair a layer, every
    head of it).  Row ``r`` brings ``count[r]`` real queries from
    position ``first[r]``.  ``program`` "prefill": one call of ``width``
    query slots; a full layer walks the key blocks of its capacity that
    hold a written position, a sliding one the ring's ``ring_rows`` rows
    before the chunk and the chunk's own.  "decode": ``width`` is the
    step's window bucket; a full layer reads it and the position in
    flight for every row, a sliding one its whole ring and the position
    in flight.  For ``tpumlops_attn_pairs_{computed,attended}_total``."""
    out = {}
    for kind in cfg.attention_kinds:
        layers = len(cfg.full_layers if kind == FULL else cfg.sliding_layers)
        most = cfg.sliding_window if kind == SLIDING else 0
        computed = attended = 0
        for f, n in zip(first, count):
            f, n = int(f), int(n)
            if n <= 0:
                continue
            if program == "decode":
                keys = (width if kind == FULL else cfg.ring_rows) + 1
            elif kind == SLIDING:
                keys = cfg.ring_rows + width
            else:
                kb = _key_block(cfg.max_seq)
                keys = cfg.max_seq if kb == cfg.max_seq else -(-(f + width) // kb) * kb
            computed += n * keys
            attended += _pairs_to(f, n, most)
        out[kind] = (layers * computed, layers * attended)
    return out


@dataclass(frozen=True)
class CostModel:
    """Analytic per-program FLOPs / HBM-bytes of this family's serving
    programs, for the device telemetry's per-tick utilization
    (``mla_moe.CostModel``'s contract).  FLOPs from the ACTIVE parameters,
    the full layers' attended pairs and the delta rule's products a
    token; bytes from the weights every call streams, the distinct held
    experts ``tokens`` tokens are expected to reach under uniform
    routing, the full layers' rows as far as attended and every row's
    state read and written once a call."""

    active_params: int
    total_params: int
    unrouted_bytes: int  # everything a call streams whatever it routes
    expert_bytes: int  # one routed expert's three matrices
    moe_layers: int
    n_routed_experts: int
    local_experts: int
    experts_per_tok: int
    full_layers: int
    pair_flops: int  # heads x 2 head_dim: flops/2 a (query, key) pair a layer
    kv_pos_bytes: float  # K + V bytes a position a full layer
    rule_flops: float  # the delta rule's flops a token, all linear layers
    state_bytes: float  # ``state_row_bytes``
    sliding_layers: int = 0
    swa_pair_flops: int = 0  # ``pair_flops`` at the sliding kind's heads
    window: int = 0  # most positions a sliding layer's query attends
    tp: int = 1  # no mesh exists for this family

    def _routed_bytes(self, tokens: float) -> float:
        miss = (1.0 - self.experts_per_tok / self.n_routed_experts) ** max(
            0.0, tokens)
        return self.moe_layers * self.local_experts * (1.0 - miss) * self.expert_bytes

    def _call(self, rows: int, tokens: int, attended: float) -> tuple[float, float]:
        n = rows * tokens
        flops = (2.0 * self.active_params * n + self.rule_flops * n
                 + 2.0 * n * attended * self.full_layers * self.pair_flops)
        nbytes = (self.unrouted_bytes + self._routed_bytes(n)
                  + rows * (self.full_layers * self.kv_pos_bytes
                            * (attended + tokens) + 2.0 * self.state_bytes))
        if self.sliding_layers:
            near = min(attended, self.window)
            flops += 2.0 * n * near * self.sliding_layers * self.swa_pair_flops
            nbytes += rows * self.sliding_layers * self.kv_pos_bytes * (near + tokens)
        return flops, nbytes

    def decode(self, rows: int, window: int, s: int = 1) -> tuple[float, float]:
        return self._call(rows, s, window)

    def prefill(self, rows: int, chunk: int, attended: float | None = None
                ) -> tuple[float, float]:
        return self._call(rows, chunk, chunk / 2.0 if attended is None else attended)


def cost_model(params: dict, cfg: GdnMoeConfig, dtype_bytes: int = 2) -> CostModel:
    active, total = param_counts(cfg)
    routed = _tree_bytes(routed_expert_leaves(params))
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    return CostModel(
        active_params=active,
        total_params=total,
        unrouted_bytes=_tree_bytes(params) - routed,
        expert_bytes=routed // max(1, cfg.num_moe_layers * cfg.local_experts),
        moe_layers=cfg.num_moe_layers,
        n_routed_experts=cfg.n_routed_experts,
        local_experts=cfg.local_experts,
        experts_per_tok=cfg.num_experts_per_tok,
        full_layers=len(cfg.full_layers),
        pair_flops=cfg.num_heads * 2 * cfg.head_dim,
        kv_pos_bytes=float(2 * cfg.kv_width * dtype_bytes),
        # S^T k, k d^T and S^T q: three products of key_dim x value_dim a head.
        rule_flops=float(len(cfg.linear_layers) * cfg.linear_num_value_heads
                         * 6 * dk * dv),
        state_bytes=float(state_row_bytes(cfg, dtype_bytes)),
        sliding_layers=len(cfg.sliding_layers),
        swa_pair_flops=cfg.view(SLIDING).num_heads * 2 * cfg.head_dim,
        window=cfg.sliding_window,
    )


# ---------------------------------------------------------------------------
# The cache: rows a position beside a state a slot
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _cache_buffers(cfg: GdnMoeConfig, batch: int, dtype) -> tuple[dict, dict]:
    """The zeroed ``(k, v)`` dicts of a cache of ``batch`` rows, a tuple of
    one buffer a layer under each name.  Jitted: every admission makes a
    fresh scratch, and a buffer a layer made eagerly is a host dispatch a
    buffer with the chip idle (``mla_moe._cache_buffers``'s finding)."""
    rows = (batch, cfg.max_seq, cfg.kv_width)
    tail = (batch, cfg.linear_conv_kernel_dim - 1, cfg.conv_dim)
    state = (batch, cfg.linear_num_value_heads, cfg.linear_key_head_dim,
             cfg.linear_value_head_dim)
    full, linear = cfg.full_layers, cfg.linear_layers
    k = {"key": tuple(jnp.zeros(rows, dtype) for _ in full),
         "conv": tuple(jnp.zeros(tail, dtype) for _ in linear)}
    v = {"value": tuple(jnp.zeros(rows, dtype) for _ in full),
         "state": tuple(jnp.zeros(state, jnp.float32) for _ in linear)}
    if cfg.sliding_layers:
        ring = (batch, cfg.ring_rows, cfg.kv_width)
        k["ring_key"] = tuple(jnp.zeros(ring, dtype) for _ in cfg.sliding_layers)
        v["ring_value"] = tuple(jnp.zeros(ring, dtype) for _ in cfg.sliding_layers)
    return k, v


class KVCache(NamedTuple):
    """The prefill scratch: ``k`` and ``v`` the dicts of buffers by kind
    (the module's docstring), one scalar length shared by the batch.  The
    linear layers' state rides here from chunk to chunk."""

    k: dict
    v: dict
    length: jax.Array

    @classmethod
    def create(cls, cfg: GdnMoeConfig, batch: int, dtype=jnp.bfloat16) -> "KVCache":
        k, v = _cache_buffers(cfg, batch, dtype)
        return cls(k=k, v=v, length=jnp.zeros((), jnp.int32))

    @property
    def capacity(self) -> int:
        return self.k["key"][0].shape[1]


class RaggedKVCache(NamedTuple):
    """The slot cache with per-row lengths, in the scratch's layout: the
    engine donates ``k`` and ``v``, every buffer of both kinds, through
    every program."""

    # "key" / "value" [B,T,kv] a full layer, "ring_key" / "ring_value"
    # [B,ring,kv] a sliding one, "conv" [B,kernel-1,channels] and "state"
    # f32 [B,Hv,dk,dv] a linear one.
    k: dict
    v: dict
    lengths: jax.Array  # int32 [B]

    @classmethod
    def create(
        cls, cfg: GdnMoeConfig, batch: int, dtype=jnp.bfloat16
    ) -> "RaggedKVCache":
        k, v = _cache_buffers(cfg, batch, dtype)
        return cls(k, v, jnp.zeros((batch,), jnp.int32))

    @property
    def capacity(self) -> int:
        return self.k["key"][0].shape[1]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def decay_log_a(cfg: GdnMoeConfig) -> jax.Array:
    """``A_log`` a value head, float32, seeded so that memory matters: with
    ``dt_bias`` 0 a token's decay ``exp(g) = exp(-exp(A_log) softplus(a))``
    spans about 0.5 (the first head) to 0.999 (the last) at ``a = 0``,
    geometric in between.  The published initialiser (``A ~ U(0, 16)``,
    ``dt_bias`` 1) forgets the state within a token for most heads, and a
    dropped state would be invisible to any comparison."""
    n = cfg.linear_num_value_heads
    rate = jnp.exp(jnp.linspace(math.log(-math.log(0.5)),
                                math.log(-math.log(0.999)), n))
    return jnp.log(rate / math.log(2.0)).astype(jnp.float32)


def init(key: jax.Array, cfg: GdnMoeConfig, dtype=jnp.float32) -> dict:
    """N(0, 0.02) matrices; zero-centred norm weights N(0, 0.02) (so ``1
    + w`` is exercised), plain ones 1; the gated norm's weight 1,
    ``A_log`` from ``decay_log_a`` and ``dt_bias`` 0 (both float32
    whatever ``dtype``).  ``layers`` is a list of per-layer trees: a
    linear layer's ``qkvz``, ``ba``, ``conv`` ``[kernel, channels]``,
    ``A_log``, ``dt_bias``, ``gdn_norm`` and ``o``; a full or sliding
    layer's ``q`` (query and, elementwise, gate a head), ``k``, ``v``,
    the headwise gate ``attn_gate`` [H, heads], ``q_norm`` and ``k_norm``
    where the config has them, and ``o``, at its kind's heads; a leading
    dense layer's SwiGLU ``gate``, ``up``, ``down``; every other layer's
    ``router``, ``experts`` (those held here, stacked on an expert axis),
    the shared expert's three matrices and, where the config has it, its
    gate ``shared_expert_gate``."""
    h = cfg.hidden_size
    e, held = cfg.n_routed_experts, cfg.local_experts
    im, ims = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
    keys = iter(jax.random.split(key, 3 + 16 * cfg.num_layers))

    def normal(shape, dt=dtype):
        return (0.02 * jax.random.normal(next(keys), shape, jnp.float32)).astype(dt)

    def norm():
        return normal((h,)) if cfg.norm == "zero_centred" else jnp.ones((h,), dtype)

    def layer(l, kind):
        lp = {"attn_norm": norm(), "ffn_norm": norm()}
        if kind != LINEAR:
            c = cfg.view(kind)
            q = c.num_heads * c.head_dim
            if c.attention_gate == "elementwise":
                lp.update(q=normal((h, 2 * q)), k=normal((h, c.kv_width)),
                          v=normal((h, c.kv_width)))
            else:
                lp.update(q=normal((h, q)), k=normal((h, c.kv_width)),
                          v=normal((h, c.kv_width)),
                          attn_gate=normal((h, c.num_heads)))
            if c.qk_norm:
                lp.update(q_norm=normal((c.head_dim,)), k_norm=normal((c.head_dim,)))
            lp["o"] = normal((q, h))
        else:
            lp.update(
                qkvz=normal((h, 2 * cfg.key_dim + 2 * cfg.value_dim)),
                ba=normal((h, 2 * cfg.linear_num_value_heads)),
                conv=normal((cfg.linear_conv_kernel_dim, cfg.conv_dim)),
                A_log=decay_log_a(cfg),
                dt_bias=jnp.zeros((cfg.linear_num_value_heads,), jnp.float32),
                gdn_norm=jnp.ones((cfg.linear_value_head_dim,), dtype),
                o=normal((cfg.value_dim, h)))
        if l in cfg.mlp_only_layers:
            i = cfg.intermediate_size
            lp.update(gate=normal((h, i)), up=normal((h, i)), down=normal((i, h)))
            return lp
        lp.update(
            router=normal((h, e)),
            experts={"gate": normal((held, h, im)), "up": normal((held, h, im)),
                     "down": normal((held, im, h))},
            shared_gate=normal((h, ims)), shared_up=normal((h, ims)),
            shared_down=normal((ims, h)))
        if cfg.shared_expert_gate:
            lp["shared_expert_gate"] = normal((h, 1))
        return lp

    return {
        "embed": normal((cfg.vocab_size, h)),
        "layers": [layer(l, kind) for l, kind in enumerate(cfg.kinds)],
        "final_norm": norm(),
        "lm_head": normal((h, cfg.vocab_size)),
    }


# ---------------------------------------------------------------------------
# Layer pieces
# ---------------------------------------------------------------------------


def _norm(x, w, cfg):
    """The config's RMSNorm: zero-centred ``rms(x) (1 + w)`` or plain
    ``rms(x) w``."""
    if cfg.norm == "plain":
        return rms_norm(x, w, cfg.rms_eps)
    return rms_norm(x, 1.0 + w.astype(jnp.float32), cfg.rms_eps)


def _head(params, x, cfg):
    """Final norm and lm_head: logits in float32."""
    with jax.named_scope("head"):
        return _qmatmul(_norm(x, params["final_norm"], cfg), params["lm_head"])


def yarn_correction_range(cfg: GdnMoeConfig) -> tuple[int, int]:
    """YaRN's ``[low, high]`` of rotary pair indices: the pairs that turn
    more than ``rope_beta_fast`` times over the original context keep
    their frequency, those under ``rope_beta_slow`` turns are divided by
    ``rope_factor``, a linear ramp between.  ``floor`` / ``ceil`` of
    ``d ln(L / (2 pi beta)) / (2 ln theta)`` at the rotary dims ``d``."""
    d, theta = cfg.rotary_dim, cfg.rope_theta
    at = lambda beta: d * math.log(
        cfg.rope_original_max_position / (beta * 2 * math.pi)) / (2 * math.log(theta))
    return (max(math.floor(at(cfg.rope_beta_fast)), 0),
            min(math.ceil(at(cfg.rope_beta_slow)), d - 1))


def yarn_inv_freq(cfg: GdnMoeConfig) -> np.ndarray:
    """YaRN's inverse frequencies ``[rotary/2]`` (float32): pair ``i``'s
    ``theta^(-2i/d)`` blended with it over ``rope_factor`` by ``r_i = 1 -
    clamp((i - low) / (high - low), 0, 1)``, ``r_i`` of the original
    frequency.  No function of a position: every position's angle
    changes."""
    d = cfg.rotary_dim
    low, high = yarn_correction_range(cfg)
    base = cfg.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp
    return (base / cfg.rope_factor * (1.0 - keep) + base * keep).astype(np.float32)


def rope_cos_sin(positions: jax.Array, cfg: GdnMoeConfig):
    """cos/sin ``[..., rotary/2]`` (float32) for ``positions`` ``[...]``;
    under YaRN both scaled by ``rope_attention_factor``."""
    d = cfg.rotary_dim
    if cfg.rope_type == "yarn":
        with jax.named_scope("rope"):
            ang = positions.astype(jnp.float32)[..., None] * yarn_inv_freq(cfg)
            scale = jnp.float32(cfg.rope_attention_factor)
            return jnp.cos(ang) * scale, jnp.sin(ang) * scale
    inv_freq = 1.0 / (cfg.rope_theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate the half-split pairs ``(i, i + rotary/2)`` of the first
    ``2 * cos.shape[-1]`` dims of ``x``'s last axis, the rest passing;
    ``cos``/``sin`` broadcast against one half."""
    half = cos.shape[-1]
    xf = x.astype(jnp.float32)
    a, b, rest = xf[..., :half], xf[..., half:2 * half], xf[..., 2 * half:]
    out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], axis=-1)
    return out.astype(x.dtype)


def _attn_qkv(xn, lp, cos, sin, cfg):
    """Normed ``xn`` [B,S,H] of a full or sliding layer (``cfg`` its
    kind's view) -> rotated ``q`` [B,S,KV,R,D] (R query heads a KV head),
    the elementwise gates [B,S,NH*D] (None for a headwise gate), and the
    position's cache rows ``k`` / ``v`` [B,S,KV*D] (``k`` normed where
    the config norms it, and rotated); ``cos``/``sin`` [B or 1, S,
    rotary/2]."""
    b, s, _h = xn.shape
    nh, nkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    qk_norm = ((lambda x, w: _norm(x, lp[w], cfg)) if cfg.qk_norm
               else (lambda x, _w: x))
    with jax.named_scope("layer.attn_qkv"):
        if cfg.attention_gate == "elementwise":
            qg = _qmatmul(xn, lp["q"]).astype(xn.dtype).reshape(b, s, nh, 2 * d)
            q, gate = qg[..., :d], qg[..., d:]
        else:
            q = _qmatmul(xn, lp["q"]).astype(xn.dtype).reshape(b, s, nh, d)
            gate = None
        k = _qmatmul(xn, lp["k"]).astype(xn.dtype).reshape(b, s, nkv, d)
        v = _qmatmul(xn, lp["v"]).astype(xn.dtype)
        q = apply_rope(qk_norm(q, "q_norm"), cos[:, :, None], sin[:, :, None])
        k = apply_rope(qk_norm(k, "k_norm"), cos[:, :, None], sin[:, :, None])
        return (q.reshape(b, s, nkv, nh // nkv, d),
                None if gate is None else gate.reshape(b, s, nh * d),
                k.reshape(b, s, nkv * d), v)


def _gqa_blocks(q, keys, values, start, written, key_start=0, window=0):
    """Causal attention of ``S`` queries ``q`` [B,S,KV,R,D] at positions
    ``start ..`` over the first ``written`` of ``T`` keys ``keys`` /
    ``values`` [B,T,KV*D] as cached, at positions ``key_start ..`` (ints
    or traced scalars; no query sees a key past ``written``).  A full
    layer's keys are its cache rows (``key_start`` 0); a sliding layer's
    are its ring's ``ring_rows`` rows in position order, the positions
    before the chunk (negative where none was written; the oldest is
    outside every query's window), then the chunk's own, and with a
    ``window`` a query sees a key at a position ``>= 0`` inside the last
    ``window`` positions up to its own.  On the TPU the fused core of
    ``ops/gqa_prefill_attention.py`` at the shapes its tiles take; else,
    and off it, the einsum body here: a key block at a time with a running
    maximum and sum (one block, a plain softmax, up to
    ``mla_moe.ONE_PASS`` positions).  Returns ctx [B,S,NH*D]."""
    b, s, nkv, r, d = q.shape
    dt = q.dtype
    t = keys.shape[1]
    kb = _key_block(t)
    scale = 1.0 / math.sqrt(d)

    def einsums(q, keys, values, start, written, key_start):
        qp = (start + jnp.arange(s))[:, None]

        def scores_of(j):
            lo = j * kb
            k = _layer_rows(keys, lo, kb).astype(dt).reshape(b, kb, nkv, d)
            v = _layer_rows(values, lo, kb).astype(dt).reshape(b, kb, nkv, d)
            sc = jnp.einsum("bqgrd,bkgd->bgrqk", q, k,
                            preferred_element_type=jnp.float32) * scale
            kp = (key_start + lo + jnp.arange(kb))[None, :]
            see = kp <= qp  # [S, kb]
            if window:
                see &= (kp >= 0) & (qp - kp < window)
            return sc, see, v

        if kb == t:
            sc, see, v = scores_of(0)
            probs = jax.nn.softmax(jnp.where(see, sc, -1e9), axis=-1).astype(dt)
            ctx = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)
            return ctx.reshape(b, s, nkv * r * d)

        low = jnp.float32(-1e30)

        def step(j, carry):
            top, total, acc = carry
            sc, see, v = scores_of(j)
            top2 = jnp.maximum(top, jnp.max(jnp.where(see, sc, low), axis=-1))
            p = jnp.where(see, jnp.exp(sc - top2[..., None]), 0.0)
            keep = jnp.exp(top - top2)
            acc = acc * keep[..., None] + jnp.einsum(
                "bgrqk,bkgd->bgrqd", p.astype(dt), v,
                preferred_element_type=jnp.float32)
            return top2, total * keep + p.sum(-1), acc

        _top, total, acc = lax.fori_loop(
            0, (written + kb - 1) // kb, step,
            (jnp.full((b, nkv, r, s), low), jnp.zeros((b, nkv, r, s), jnp.float32),
             jnp.zeros((b, nkv, r, s, d), jnp.float32)))
        ctx = acc / jnp.maximum(total, 1e-30)[..., None]
        return ctx.transpose(0, 3, 1, 2, 4).reshape(b, s, nkv * r * d).astype(dt)

    with jax.named_scope("layer.attn_core"):
        return gqa_prefill_attention(
            q, keys, values, start, written, key_start, window=window,
            key_block=_key_tile(t), scale=scale, fallback=einsums)


def _gqa_step(q, k_new, v_new, ck, cv, mask_bias):
    """Single-token attention, the cache read-only: ``ck`` / ``cv``
    [B,W,KV*D] the attended rows under the STRICT ``mask_bias`` [B,1,W]
    (only positions before the current one), the current position
    attended through the in-flight ``k_new`` / ``v_new`` [B,1,KV*D] (its
    row is written after the layer loop).  ``q`` [B,1,KV,R,D] ->
    ctx [B,1,NH*D].
    The rows are multiplied AS THEY LIE, every KV head's numbers side by
    side on the lanes: a query head's vector is laid into its KV head's
    lanes of a ``KV*D``-wide row of zeros, and of the ``KV*D``-wide
    context a head keeps its own KV head's lanes.  Twice the products of
    a step that waits for the rows' bytes anyway; with the KV heads as a
    batch axis of the product the chip's compiler relaid the whole window
    head-major first, a copy of every K and V buffer every step (seen in
    the compile for a described v5e)."""
    b, _s, nkv, r, d = q.shape
    dt = q.dtype
    with jax.named_scope("layer.attn_core"):
        scale = 1.0 / math.sqrt(d)
        own = jnp.eye(nkv, dtype=dt)[None, :, None, :, None]  # [1,KV,1,KV,1]
        wide = (q[:, 0, :, :, None, :] * own).reshape(b, nkv * r, nkv * d)
        score = lambda keys: jnp.einsum(
            "bhc,bkc->bhk", wide, keys.astype(dt),
            preferred_element_type=jnp.float32) * scale
        full = jnp.concatenate([score(ck) + mask_bias, score(k_new)], axis=-1)
        probs = jax.nn.softmax(full, axis=-1).astype(dt)
        ctx = jnp.einsum(
            "bhk,bkc->bhc", probs[..., :-1], cv.astype(dt),
            preferred_element_type=jnp.float32,
        ) + probs[..., -1:].astype(jnp.float32) * v_new.astype(jnp.float32)
        ctx = (ctx.reshape(b, nkv, r, nkv, d) * own.astype(jnp.float32)).sum(3)
        return ctx.astype(dt).reshape(b, 1, nkv * r * d)


def _attn_out(x, ctx, gate, xn, lp, cfg):
    """The heads' outputs ``ctx`` [B,S,NH*D] gated, through ``W_o`` onto
    the residual: elementwise, each number by the sigmoid of the query
    projection's other half ``gate``; headwise, each head by the sigmoid
    of the normed layer input ``xn`` through ``attn_gate``."""
    with jax.named_scope("layer.attn_gate"):
        if cfg.attention_gate == "headwise":
            g = jax.nn.sigmoid(_qmatmul(xn, lp["attn_gate"]).astype(jnp.float32))
            b, s, _ = ctx.shape
            ctx = (ctx.reshape(b, s, cfg.num_heads, cfg.head_dim)
                   * g[..., None].astype(ctx.dtype)).reshape(ctx.shape)
        else:
            ctx = ctx * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(ctx.dtype)
    with jax.named_scope("layer.attn_out"):
        return x + _qmatmul(ctx, lp["o"]).astype(x.dtype)


def _gdn_in(xn, lp, cfg):
    """Normed ``xn`` [B,S,H] through a linear layer's two projections:
    ``mixed`` [B,S,channels] (``q || k || v``, the convolution's input),
    ``z`` [B,S,Hv,dv] and float32 ``b``, ``a`` [B,S,Hv].  The published
    layout: a key head's ``[q | k | v of its value heads | z of them]``
    side by side in ``qkvz``, its ``[b | a]`` of them in ``ba``."""
    b_, s, _h = xn.shape
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    r = hv // hk
    with jax.named_scope("layer.gdn_in"):
        qkvz = _qmatmul(xn, lp["qkvz"]).astype(xn.dtype)
        qkvz = qkvz.reshape(b_, s, hk, 2 * dk + 2 * r * dv)
        q, k, v, z = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
        ba = _qmatmul(xn, lp["ba"]).reshape(b_, s, hk, 2 * r)
        mixed = jnp.concatenate(
            [q.reshape(b_, s, hk * dk), k.reshape(b_, s, hk * dk),
             v.reshape(b_, s, hv * dv)], axis=-1)
        return (mixed, z.reshape(b_, s, hv, dv),
                ba[..., :r].reshape(b_, s, hv), ba[..., r:].reshape(b_, s, hv))


def _gdn_conv(mixed, tail, lp):
    """The causal depthwise convolution of ``mixed`` [B,S,C] behind the
    carried ``tail`` [B,kernel-1,C] (the rows before the call), then
    SiLU.  Returns ``(u [B,S,C], rows [B,kernel-1+S,C])``: the carried
    rows and the call's, of which ``_conv_tail`` keeps the next tail."""
    w = lp["conv"].astype(jnp.float32)  # [kernel, C]
    s = mixed.shape[1]
    with jax.named_scope("layer.gdn_conv"):
        rows = jnp.concatenate([tail.astype(mixed.dtype), mixed], axis=1)
        u = sum(rows[:, j:j + s].astype(jnp.float32) * w[j]
                for j in range(w.shape[0]))
        return jax.nn.silu(u).astype(mixed.dtype), rows


def _conv_tail(rows, n_real, tail):
    """The next carried rows: the last ``kernel - 1`` of the rows carried
    plus the call's first ``n_real`` [B] rows, so padding behind the real
    rows never enters them (and a row with none keeps its tail)."""
    return jax.vmap(
        lambda r, n: lax.dynamic_slice_in_dim(r, n, tail.shape[1], 0)
    )(rows, n_real.astype(jnp.int32)).astype(tail.dtype)


def _gdn_gates(u, b, a, valid, lp, cfg):
    """From the convolved ``u`` [B,S,C] and the raw ``b``, ``a`` [B,S,Hv]:
    float32 ``q``, ``k`` [B,S,Hv,dk] (L2-normalised a head, ``q`` scaled,
    a key head repeated for its value heads), ``v`` [B,S,Hv,dv], the
    log-decay ``g`` and the write strength ``beta`` [B,S,Hv], both 0 on
    rows that are not ``valid`` [B,S]."""
    b_, s, _c = u.shape
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    uf = u.astype(jnp.float32)
    q, k, v = jnp.split(uf, [hk * dk, 2 * hk * dk], axis=-1)
    unit = lambda x: x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
    q = unit(q.reshape(b_, s, hk, dk)) * (dk ** -0.5)
    k = unit(k.reshape(b_, s, hk, dk))
    q, k = (jnp.repeat(x, hv // hk, axis=2) for x in (q, k))
    real = valid[..., None]
    beta = jnp.where(real, jax.nn.sigmoid(b), 0.0)
    g = jnp.where(real, -jnp.exp(lp["A_log"].astype(jnp.float32))
                  * jax.nn.softplus(a + lp["dt_bias"].astype(jnp.float32)), 0.0)
    return q, k, v.reshape(b_, s, hv, dv), g, beta


def _delta_step(q, k, v, g, beta, state):
    """The gated delta rule, one token a row: ``q``, ``k`` [B,Hv,dk],
    ``v`` [B,Hv,dv], ``g``, ``beta`` [B,Hv], ``state`` [B,Hv,dk,dv], all
    float32.  Returns ``(o [B,Hv,dv], state)``.  ``beta = 0`` and ``g =
    0`` return the state as it came."""
    state = state * jnp.exp(g)[..., None, None]
    read = jnp.einsum("bhkv,bhk->bhv", state, k, precision=_HI)
    delta = (v - read) * beta[..., None]
    state = state + k[..., :, None] * delta[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", state, q, precision=_HI), state


def _delta_chunks(q, k, v, g, beta, state):
    """The gated delta rule over ``S`` tokens a row, in sub-chunks of
    ``SUB_CHUNK`` (the module's docstring): ``q``, ``k`` [B,S,Hv,dk],
    ``v`` [B,S,Hv,dv], ``g``, ``beta`` [B,S,Hv], ``state`` [B,Hv,dk,dv],
    all float32.  Returns ``(o [B,S,Hv,dv], state)``: what
    ``_delta_step`` gives token by token."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    c = min(SUB_CHUNK, s)
    n = -(-s // c)
    pad = n * c - s  # rows of beta = g = 0 behind the last token: no-ops

    def cut(x):  # [B,S,H,...] -> [N,B,H,C,...]
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape(b, n, c, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    q, k, v, g, beta = (cut(x) for x in (q, k, v, g, beta))
    mm = lambda spec, x, y: jnp.einsum(spec, x, y, precision=_HI)
    gc = jnp.cumsum(g, axis=-1)  # [N,B,H,C]
    at = jnp.arange(c)
    lower, strict = at[:, None] >= at[None, :], at[:, None] > at[None, :]
    # exp(G_i - G_j) for i >= j (<= 1); the other side is never read.
    decay = jnp.where(lower, jnp.exp(jnp.minimum(
        gc[..., :, None] - gc[..., None, :], 0.0)), 0.0)
    kb, vb = k * beta[..., None], v * beta[..., None]
    m = jnp.where(strict, -mm("nbhik,nbhjk->nbhij", kb, k) * decay, 0.0)
    # (I - M)^-1 = (I + M)(I + M^2)(I + M^4)...: M^c = 0.
    eye = jnp.eye(c, dtype=jnp.float32)
    t, power = eye + m, m
    for _ in range(max(0, (c - 1).bit_length() - 1)):
        power = mm("nbhij,nbhjk->nbhik", power, power)
        t = mm("nbhij,nbhjk->nbhik", t, eye + power)
    writes = mm("nbhij,nbhjv->nbhiv", t, vb)  # given an empty state
    reads = mm("nbhij,nbhjk->nbhik", t, kb * jnp.exp(gc)[..., None])
    within = mm("nbhik,nbhjk->nbhij", q, k) * decay

    def sub_chunk(state, xs):
        q_i, k_i, gc_i, writes_i, reads_i, within_i = xs
        new = writes_i - mm("bhik,bhkv->bhiv", reads_i, state)
        o = (mm("bhik,bhkv->bhiv", q_i * jnp.exp(gc_i)[..., None], state)
             + mm("bhij,bhjv->bhiv", within_i, new))
        last = gc_i[..., -1:]
        state = state * jnp.exp(last)[..., None] + mm(
            "bhik,bhiv->bhkv", k_i * jnp.exp(last - gc_i)[..., None], new)
        return state, o

    state, o = lax.scan(sub_chunk, state, (q, k, gc, writes, reads, within))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)  # [N,B,H,C,dv] -> [B,N,C,H,dv]
    return o.reshape(b, n * c, h, dv)[:, :s], state


def _gdn_out(x, o, z, lp, cfg):
    """The rule's output ``o`` [B,S,Hv,dv] through the gated norm
    (``rms(o) w silu(z)`` over a value head's numbers) and ``W_o`` onto
    the residual."""
    b, s = x.shape[:2]
    with jax.named_scope("layer.gdn_out"):
        y = rms_norm(o, lp["gdn_norm"], cfg.rms_eps).astype(jnp.float32)
        y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
        return x + _qmatmul(y.reshape(b, s, cfg.value_dim), lp["o"]).astype(x.dtype)


def _ffn(x, lp, valid, cfg):
    """A layer's FFN with its residual: the expert block
    (``mla_moe.moe_ffn``) where the layer carries a router, a SwiGLU
    where it is one of ``mlp_only_layers``; ``valid`` bool [B, S] marks
    the real tokens.  Returns ``(x, counts)``."""
    b, s, h = x.shape
    if "router" not in lp:
        with jax.named_scope("layer.mlp"):
            y = _swiglu(_norm(x, lp["ffn_norm"], cfg), lp["gate"], lp["up"], lp["down"])
            return x + y.astype(x.dtype), jnp.zeros((3,), jnp.int32)
    xn = _norm(x, lp["ffn_norm"], cfg).reshape(b * s, h)
    y, counts = moe_ffn(xn, lp, valid.reshape(b * s), cfg)
    return x + y.reshape(b, s, h).astype(x.dtype), counts


# ---------------------------------------------------------------------------
# Forward over a shared-start cache (prefill, chunked prefill, /infer)
# ---------------------------------------------------------------------------


def forward(
    params: dict,
    input_ids: jax.Array,
    cache: KVCache,
    cfg: GdnMoeConfig,
    dtype=jnp.bfloat16,
):
    """Run ``input_ids`` [B,S] through the model starting at
    ``cache.length``; ids < 0 are padding behind a row's real tokens
    (embedded as id 0, not routed, folded into no state, written to no
    ring).  A full layer writes its rows, then attends the blocks of its
    cache written so far; a sliding layer attends its ring's ``ring_rows``
    rows and the chunk's own, then its ring takes the chunk's rows in one
    drop-scatter; a linear layer reads its convolution tail and its
    state, runs the chunked rule, and writes both back.
    Returns ``(logits [B,S,vocab] float32, cache, counts)`` (``counts``
    int32 ``[len(COUNTS)]``, summed over layers)."""
    b, s = input_ids.shape
    if s > cfg.max_seq:
        raise ValueError(
            f"sequence chunk of {s} tokens exceeds KV-cache capacity "
            f"max_seq={cfg.max_seq}"
        )
    start = cache.length
    valid = input_ids >= 0
    n_real = valid.sum(-1)
    x = _embed(params, jnp.maximum(input_ids, 0), dtype)
    positions = start + jnp.arange(s)
    ropes = {kind: rope_cos_sin(positions[None], cfg.view(kind))  # [1, S, rotary/2]
             for kind in cfg.attention_kinds}
    z = jnp.zeros((), jnp.int32)
    k = {name: list(bufs) for name, bufs in cache.k.items()}
    v = {name: list(bufs) for name, bufs in cache.v.items()}
    counts = jnp.zeros((3,), jnp.int32)
    put = lambda buf, new: lax.dynamic_update_slice(
        buf, new.astype(buf.dtype), (z, start, z))
    window, ring = cfg.sliding_window, cfg.ring_rows
    if cfg.sliding_layers:
        ring_at = _ring_slots(valid, positions, ring)
        # The ring's slots of the ``ring`` positions before the chunk, in
        # position order (a ring of 512 rows and a chunk of 512: two key
        # blocks of 512).
        before = (start - ring + jnp.arange(ring)) % ring
    for (kind, i), lp in zip(_layer_plan(cfg), params["layers"]):
        kc = cfg.view(kind)
        xn = _norm(x, lp["attn_norm"], cfg)
        if kind == FULL:
            q, gate, k_new, v_new = _attn_qkv(xn, lp, *ropes[kind], kc)
            with jax.named_scope("kv_commit"):
                k["key"][i] = put(k["key"][i], k_new)
                v["value"][i] = put(v["value"][i], v_new)
            ctx = _gqa_blocks(q, k["key"][i], v["value"][i], start, start + s)
            x = _attn_out(x, ctx, gate, xn, lp, kc)
        elif kind == SLIDING:
            q, gate, k_new, v_new = _attn_qkv(xn, lp, *ropes[kind], kc)
            take = lambda buf: jnp.take(buf, before, axis=1).astype(k_new.dtype)
            ctx = _gqa_blocks(
                q, jnp.concatenate([take(k["ring_key"][i]), k_new], axis=1),
                jnp.concatenate([take(v["ring_value"][i]), v_new], axis=1),
                start, ring + s, key_start=start - ring, window=window)
            rows = jnp.arange(b)[:, None]
            with jax.named_scope("ring_commit"):
                k["ring_key"][i] = k["ring_key"][i].at[rows, ring_at].set(
                    k_new.astype(k["ring_key"][i].dtype), mode="drop")
                v["ring_value"][i] = v["ring_value"][i].at[rows, ring_at].set(
                    v_new.astype(v["ring_value"][i].dtype), mode="drop")
            x = _attn_out(x, ctx, gate, xn, lp, kc)
        else:
            mixed, zg, b_raw, a_raw = _gdn_in(xn, lp, cfg)
            u, rows = _gdn_conv(mixed, k["conv"][i], lp)
            with jax.named_scope("layer.gdn_scan"):
                o, state = _delta_chunks(
                    *_gdn_gates(u, b_raw, a_raw, valid, lp, cfg), v["state"][i])
            with jax.named_scope("state_commit"):
                k["conv"][i] = _conv_tail(rows, n_real, k["conv"][i])
                v["state"][i] = state
            x = _gdn_out(x, o.astype(dtype), zg, lp, cfg)
        x, layer_counts = _ffn(x, lp, valid, cfg)
        counts = counts + layer_counts
    linear = len(cfg.linear_layers)
    counts = jnp.concatenate([counts, jnp.stack(
        [valid.sum() * linear, jnp.any(valid, axis=-1).sum() * linear]
    ).astype(jnp.int32)])
    done = lambda bufs: {name: tuple(layers) for name, layers in bufs.items()}
    return _head(params, x, cfg), KVCache(done(k), done(v), start + s), counts


def prefill(params, input_ids, cfg, dtype=jnp.bfloat16):
    cache = KVCache.create(cfg, input_ids.shape[0], dtype)
    return forward(params, input_ids, cache, cfg, dtype)


def generate_greedy(
    params: dict,
    prompt_ids: jax.Array,
    num_new_tokens: int,
    cfg: GdnMoeConfig,
    dtype=jnp.bfloat16,
) -> jax.Array:
    """Greedy generation with a scanned decode loop (the ``/infer``
    path)."""
    return greedy_scan(forward, KVCache.create, params, prompt_ids,
                       num_new_tokens, cfg, dtype)


# ---------------------------------------------------------------------------
# Continuous batching (per-row positions)
# ---------------------------------------------------------------------------


def decode_ragged(
    params: dict,
    token_ids: jax.Array,
    cache: RaggedKVCache,
    cfg: GdnMoeConfig,
    active: jax.Array | None = None,
    dtype=jnp.bfloat16,
    window: int | None = None,
):
    """One decode step where every batch row is at its OWN position
    (``llama.decode_ragged``'s contract: a full layer's strict mask over
    the static ``window``, the current position attended in flight, its
    new row committed by one drop-scatter a buffer after the loop).  A
    sliding layer reads its whole ring, each row's position told from the
    row's length, and its new row lands at ``length mod ring``.  A linear
    layer runs the recurrence once on its state and shifts its
    convolution tail by the token.  An inactive row is neither written
    nor advanced, not routed, and keeps its state, its tail and its ring.
    Returns ``(logits [B,1,vocab] float32, cache, counts)`` (as
    ``forward``'s)."""
    b, s = token_ids.shape
    if s != 1:
        raise ValueError(f"decode_ragged is single-token: got chunk of {s}")
    lengths = cache.lengths
    live = jnp.ones((b,), bool) if active is None else active
    x = _embed(params, token_ids, dtype)
    ropes = {kind: rope_cos_sin(lengths[:, None], cfg.view(kind))  # [B, 1, rotary/2]
             for kind in cfg.attention_kinds}
    window = _attended_window(cache, window)
    before = jnp.arange(window)[None, :] < lengths[:, None]  # [B, W]
    mask_bias = jnp.where(before, 0.0, -1e9).astype(jnp.float32)[:, None]
    if cfg.sliding_layers:
        ring_bias = _ring_bias(lengths, cfg.ring_rows, cfg.sliding_window)
    real = live[:, None]
    news = {name: [] for name in (*cache.k, *cache.v)}
    counts = jnp.zeros((3,), jnp.int32)
    for (kind, i), lp in zip(_layer_plan(cfg), params["layers"]):
        kc = cfg.view(kind)
        xn = _norm(x, lp["attn_norm"], cfg)
        if kind == FULL:
            q, gate, k_new, v_new = _attn_qkv(xn, lp, *ropes[kind], kc)
            ck = _layer_rows(cache.k["key"][i], 0, window)
            cv = _layer_rows(cache.v["value"][i], 0, window)
            ctx = _gqa_step(q, k_new, v_new, ck, cv, mask_bias)
            x = _attn_out(x, ctx, gate, xn, lp, kc)
            news["key"].append(k_new)
            news["value"].append(v_new)
        elif kind == SLIDING:
            q, gate, k_new, v_new = _attn_qkv(xn, lp, *ropes[kind], kc)
            ctx = _gqa_step(q, k_new, v_new, cache.k["ring_key"][i],
                            cache.v["ring_value"][i], ring_bias)
            x = _attn_out(x, ctx, gate, xn, lp, kc)
            news["ring_key"].append(k_new)
            news["ring_value"].append(v_new)
        else:
            mixed, zg, b_raw, a_raw = _gdn_in(xn, lp, cfg)
            u, rows = _gdn_conv(mixed, cache.k["conv"][i], lp)
            with jax.named_scope("layer.gdn_scan"):
                qf, kf, vf, g, beta = _gdn_gates(u, b_raw, a_raw, real, lp, cfg)
                o, state = _delta_step(qf[:, 0], kf[:, 0], vf[:, 0], g[:, 0],
                                       beta[:, 0], cache.v["state"][i])
            with jax.named_scope("state_commit"):
                # A live row's tail shifts by its token; one that is not
                # live keeps its tail and its state (the rule at beta = g
                # = 0 already returned the state it got).
                keep = live[:, None, None]
                tail = cache.k["conv"][i]
                news["conv"].append(
                    jnp.where(keep, rows[:, 1:].astype(tail.dtype), tail))
                news["state"].append(
                    jnp.where(keep[..., None], state, cache.v["state"][i]))
            x = _gdn_out(x, o[:, None].astype(dtype), zg, lp, cfg)
        x, layer_counts = _ffn(x, lp, real, cfg)
        counts = counts + layer_counts
    logits = _head(params, x, cfg)

    commit = lambda name, at: tuple(
        _commit_row(buf, new[:, 0], at)
        for buf, new in zip((cache.k | cache.v)[name], news[name]))
    at = jnp.where(live, lengths, cache.capacity)
    with jax.named_scope("kv_commit"):
        keys, values = commit("key", at), commit("value", at)
    k_out = {"key": keys, "conv": tuple(news["conv"])}
    v_out = {"value": values, "state": tuple(news["state"])}
    if cfg.sliding_layers:
        at = jnp.where(live, lengths % cfg.ring_rows, cfg.ring_rows)
        with jax.named_scope("ring_commit"):
            k_out["ring_key"] = commit("ring_key", at)
            v_out["ring_value"] = commit("ring_value", at)
    linear = len(cfg.linear_layers)
    counts = jnp.concatenate(
        [counts, jnp.stack([live.sum() * linear] * 2).astype(jnp.int32)])
    return (
        logits,
        RaggedKVCache(k_out, v_out, lengths + live.astype(jnp.int32)),
        counts,
    )


@jax.named_scope("kv_commit")
def insert_sequence(
    cache: RaggedKVCache, seq: KVCache, slot: jax.Array, length: jax.Array
) -> RaggedKVCache:
    """Install a prefilled single-sequence scratch into batch row ``slot``
    (``llama.insert_sequence`` for this cache): a full layer's rows, a
    sliding layer's ring (the scratch wrote it at ``position mod ring``
    too), and a linear layer's state and tail as they stand.  ``length`` is the real
    token count; padding rows behind it are overwritten by decode before
    they can be attended, and no padding ever reached a state."""
    slot = jnp.asarray(slot, jnp.int32)
    z = jnp.zeros((), jnp.int32)

    def put(kinds, rows):
        return {name: tuple(
            lax.dynamic_update_slice(
                buf, row.astype(buf.dtype), (slot,) + (z,) * (buf.ndim - 1))
            for buf, row in zip(bufs, rows[name])) for name, bufs in kinds.items()}

    return RaggedKVCache(
        put(cache.k, seq.k), put(cache.v, seq.v),
        cache.lengths.at[slot].set(jnp.asarray(length, jnp.int32)),
    )
