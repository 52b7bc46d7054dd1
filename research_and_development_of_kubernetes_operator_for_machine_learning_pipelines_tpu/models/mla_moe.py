"""Decoder with latent (MLA) attention and a sparse-expert FFN.

The DeepSeek-V3 block: pre-RMSNorm; attention through a low-rank query
and a compressed key/value latent (one RoPE key head shared by all query
heads); ``first_k_dense_replace`` leading SwiGLU layers, then layers of
routed experts (sigmoid scores, a selection-only bias, top-k,
renormalised and scaled weights) beside shared experts; untied head.
The plain float32 references this is tested against are
``benchmarks/references/mla_moe_decoder.py`` (every layer full attention,
every expert held) and ``benchmarks/references/dots3_note_decoder.py``
(layers of two kinds, an indexer, a gate, an expert share).

What the serving engine needs of a causal-LM family is here under the
names ``models/llama.py`` gives them, so ``server/generation.py`` reaches
either through one handle: ``KVCache`` / ``RaggedKVCache`` (the donated
pair stays ``(k, v)``, each a dict of buffers by row kind, below),
``forward``, ``prefill``, ``decode_ragged``, ``insert_sequence``,
``generate_greedy``.  ``forward`` and ``decode_ragged`` return one value
more than llama's: int32 ``[5]`` (``COUNTS``): the (layer, expert) pairs
that got at least one real token, the (layer, expert, row tile) visits
the grouped matmuls made, the (token, expert) assignments that landed on
an expert held here, the positions the indexer scored and the positions
it kept, which the engine turns into the ``tpumlops_moe_*`` and
``tpumlops_dsa_*`` counters.

Design decisions:

- The cache holds what the published model caches: the latent after its
  norm and the RoPE key after rotation, 576 numbers a position a layer.
  Prefill expands ``[k_nope | v] = c W_kvb`` over the attended positions;
  decode absorbs ``W_kvb`` into the query and the context (``q_nope W_uk``
  scores the latent itself, ``P c`` is expanded by ``W_uv`` after): the
  same mathematics, and a step reads 576 numbers a position, not 8192.
- The cache is a dict of ROW KINDS, one position-major buffer a layer
  of the kind, every buffer donated through every program: ``k["rope"]``
  the RoPE keys ``[B, T, LANES]`` and ``v["latent"]`` the latents ``[B,
  T, kv_lora_rank]`` of the full-attention layers, ``k["index"]`` their
  indexers' keys ``[B, T, index_head_dim]``, ``k["ring_rope"]`` /
  ``v["ring_latent"]`` ``[B, ring, *]`` the sliding layers' rings,
  written at ``position mod ring``.  A model of one layer kind and no
  indexer holds the first pair alone.  A buffer a layer and no head axis
  (one key head serves every query head): stacked over layers, or with a
  size-1 head axis, the chip's compiler held the donated buffer in the
  layout the commit's scatter likes (layers beside the row's numbers)
  and read it in another, a whole-buffer copy in and out of every step.
  A RoPE key's 64 numbers lie in a row of the chip's 128 lanes
  (``LANES``, zeros behind them): a 64-wide buffer the compiler holds
  transposed, positions on the lanes, and relays whole around every
  commit; laid beside its latent in one row of 576 numbers, no multiple
  of 128 either, every window read was transposed.
- Experts are ``ops.grouped_matmul`` over token copies sorted by expert:
  on the TPU a Pallas kernel whose row tile follows from the static
  (token copies, experts) of the call, 128 rows at a 512-token chunk's
  4096 copies over 256 experts and 16 at a decode step's 64, which
  visits only the (expert, row tile) pairs that share a row and reads
  each expert's matrix once; off it ``jax.lax.ragged_dot``.  XLA's own
  lowering of ``ragged_dot`` tiles 512 rows at 4096 copies and walks
  every group: a chunk multiplied 512-row tiles for the ~16 rows an
  expert gets, 2.4 ms a matmul against a 0.98 ms stream (PERF.md §5,
  PR 27), and a step walked 256 groups to reach ~57.  No capacity
  factor, no dropped token.
- Layers are a LIST of per-layer trees and the layer loop is unrolled,
  where llama stacks and scans: the grouped matmul is a custom call whose
  operand must be a whole buffer, so a dynamic slice of experts stacked
  over layers is copied first (0.8 GB a matrix at the published widths,
  three a layer, every step: seen in the compile for a described v5e).
  Depth costs program size and compile time here.
- Padding is not routed: token ids < 0 mark padding rows of a prompt
  chunk, ``active`` marks the live rows of a decode step.  A padded row
  would stream experts for nothing and count as traffic it is not.
- Router matmul, sigmoid and top-k in float32 at ``highest`` precision: a
  near-tie that flips a choice moves a token's logits more than any
  rounding of a matrix does.
- RoPE rotates the pairs ``(2i, 2i+1)`` where they lie
  (``rope_interleave``); the published code permutes to half-split order
  first, which gives the same dot products.
- Layers of two kinds (``layer_types``): full attention, with an
  optional indexer that scores every earlier position
  (``sum_h w_h relu(q_h . k)``) and keeps the ``index_topk`` best for
  the softmax, and sliding-window attention at its own widths
  (``swa_*``) over a ring of rows.  ``cfg.view(kind)`` is the config
  with that kind's widths under the plain names, so one set of layer
  pieces serves both.  A headwise sigmoid gate on the heads' outputs
  and a rescale of the two latents are options of either kind.
- Prefill attends in key blocks (``_attn_blocks``): a full layer walks
  the blocks of its cache written so far (a dynamic trip count: no
  ``[heads, chunk, capacity]`` scores), masked by the indexer's
  selection where it bites; the selection is exact and is
  ``lax.top_k``'s without its sort: the k-th largest score found by
  bisection on the float's ordered integer image, a tie at it going to
  the lower position.  On the TPU the core of either layer kind is
  ``ops.prefill_attention``, a Pallas kernel in which a key block's
  float32 scores never leave VMEM (XLA wrote and re-read 134 MB of them
  a block, PERF.md 5), at every shape its tiles take (a chunk, a bucket
  of 16 queries or more over key blocks of whole lanes); elsewhere, and
  off the TPU, the einsum body: blocks with a running maximum and sum,
  or, over a capacity of at most ``ONE_PASS`` positions, one block and a
  plain softmax.  Decode selects with ``lax.top_k`` and gathers the kept
  rows.
- An expert layer may hold a SHARE of the routed experts
  (``n_local_experts`` from ``local_expert_start``): the router scores
  all ``n_routed_experts``, assignments to experts held elsewhere sort
  behind every group as padding does, and what those experts would add
  is left out.  Nothing stands in for the other chips or the exchange.
- Not here (``UNSUPPORTED``, refused typed): int8 weights or cache,
  a mesh beyond one chip, verify / multi-step / packed / super-step
  programs, the multi-token-prediction module (not loaded: it adds no
  term to the next-token logits).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.grouped_matmul import grouped_matmul, row_tile, row_tile_schedule
from ..ops.prefill_attention import prefill_attention
from .common import rms_norm
from .llama import _attended_window, _embed, _head, _qmatmul


FLAVOR = "mla-moe-generate"  # registry / artifact name of this family
PAD_ID = -1  # padding rows of a prompt chunk: ids < 0 are not routed
# The serving mechanisms these programs lack, each with the words its typed
# rejection uses (``utils.config.validate_serving_for_family`` maps the
# ``spec.tpu`` knobs onto these keys).  What is here: the cache tuples,
# chunked and fused prefill, single-step decode and the insert, in bf16 on
# one chip.
UNSUPPORTED = {
    "quantize": "int8 weights or an int8 cache",
    "mesh": "sharding over more than one chip (no expert, tensor, data or "
            "sequence-parallel path, no ring prefill)",
    "speculative": "speculative decoding (no verify program, no drafter)",
    "prefix_cache": "the radix prefix cache over latent cache rows (and "
                    "preemption, which parks evicted rows in it)",
    "prefill_batch": "packed multi-admission prefill",
    "decode_steps": "the fused multi-step decode program",
    "unified_step": "the unified super-step program",
    "kv_transfer": "KV transfer between prefill and decode replicas",
}


FULL, SLIDING = "full_attention", "sliding_attention"  # ``layer_types`` values
# What the int32 vector holds that ``forward`` and ``decode_ragged`` return
# behind llama's outputs, summed over layers.
COUNTS = ("experts_hit", "row_tile_visits", "local_assignments",
          "dsa_keys_scored", "dsa_keys_selected")
LANES = 128  # a RoPE key's cache row is padded to this many numbers
KEY_BLOCK = 512  # key positions a block of ``_attn_blocks`` attends
ONE_PASS = 2048  # key positions at or under which attention is one block
INDEX_NORM_EPS = 1e-6  # the indexer's key LayerNorm


@dataclass(frozen=True)
class MlaMoeConfig:
    vocab_size: int = 129280
    hidden_size: int = 2048
    num_layers: int = 40
    num_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 7168
    moe_intermediate_size: int = 768
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 2.5
    max_seq: int = 131072
    rope_theta: float = 32_000_000.0
    rms_eps: float = 1e-6
    # Variants of the published block of which ONE value is implemented;
    # an artifact that states another is refused, not served as this one.
    n_group: int = 1
    topk_group: int = 1
    scoring_func: str = "sigmoid"
    norm_topk_prob: bool = True
    # Layer kinds, one a layer (``()``: every layer full attention), and
    # the sliding kind's own attention widths and window (the query's own
    # position and the ``sliding_window - 1`` before it).
    layer_types: tuple = ()
    sliding_window: int = 0
    swa_num_heads: int = 0
    swa_q_lora_rank: int = 0
    swa_kv_lora_rank: int = 0
    swa_qk_nope_head_dim: int = 0
    swa_qk_rope_head_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 0.0
    # The full kind's indexer (0 heads: none, every earlier position is
    # attended) and how many positions it keeps.
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    attention_gate: str = "none"  # | "headwise": sigmoid gate a head, before W_o
    lora_rescale: bool = False  # sqrt(hidden / rank) on the two normed latents
    # The expert share: routed experts held here (0: all of them) and the
    # first one's index.  The router's width stays ``n_routed_experts``.
    n_local_experts: int = 0
    local_expert_start: int = 0

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        for key, only, what in (
            ("n_group", 1, "group-limited routing"),
            ("topk_group", 1, "group-limited routing"),
            ("scoring_func", "sigmoid", "softmax router scores"),
            ("norm_topk_prob", True, "un-normalised routing weights"),
        ):
            if getattr(self, key) != only:
                raise ValueError(
                    f"{key}={getattr(self, key)!r}: {what} is not "
                    f"implemented (only {key}={only!r})"
                )
        if not 0 <= self.first_k_dense_replace <= self.num_layers:
            raise ValueError(
                f"first_k_dense_replace {self.first_k_dense_replace} outside "
                f"[0, num_layers {self.num_layers}]"
            )
        if not 1 <= self.num_experts_per_tok <= self.n_routed_experts:
            raise ValueError(
                f"num_experts_per_tok {self.num_experts_per_tok} outside "
                f"[1, n_routed_experts {self.n_routed_experts}]"
            )
        if self.qk_rope_head_dim % 2 or self.swa_qk_rope_head_dim % 2:
            raise ValueError(
                f"qk_rope_head_dim {self.qk_rope_head_dim} / "
                f"swa_qk_rope_head_dim {self.swa_qk_rope_head_dim} must be "
                "even: RoPE rotates pairs"
            )
        if self.layer_types:
            unknown = set(self.layer_types) - {FULL, SLIDING}
            if unknown or len(self.layer_types) != self.num_layers:
                raise ValueError(
                    f"layer_types must name {FULL!r} or {SLIDING!r} once a "
                    f"layer ({self.num_layers}): got {len(self.layer_types)} "
                    f"entries, unknown kinds {sorted(unknown)}"
                )
        if not self.full_layers:
            raise ValueError(
                "a model of sliding-window layers alone is not implemented "
                "(the cache's capacity is the full layers' row)"
            )
        if self.sliding_layers and not (
            self.sliding_window >= 1 and self.swa_num_heads
            and self.swa_q_lora_rank and self.swa_kv_lora_rank
            and self.swa_qk_rope_head_dim and self.swa_v_head_dim
            and self.swa_rope_theta > 0
        ):
            raise ValueError(
                "sliding_attention layers need sliding_window and every "
                "swa_* attention width"
            )
        if self.index_n_heads and not (
            self.index_topk >= 1
            and self.index_head_dim >= self.qk_rope_head_dim
        ):
            raise ValueError(
                f"an indexer of {self.index_n_heads} heads needs index_topk "
                f">= 1 and index_head_dim >= qk_rope_head_dim (RoPE turns "
                f"its first {self.qk_rope_head_dim} dims)"
            )
        if self.attention_gate not in ("none", "headwise"):
            raise ValueError(
                f"attention_gate={self.attention_gate!r}: only 'none' and "
                "'headwise' are implemented"
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
    def num_dense_layers(self) -> int:
        return self.first_k_dense_replace

    @property
    def num_moe_layers(self) -> int:
        return self.num_layers - self.first_k_dense_replace

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def kinds(self) -> tuple:
        """Each layer's kind."""
        return self.layer_types or (FULL,) * self.num_layers

    @property
    def full_layers(self) -> tuple:
        return tuple(l for l, k in enumerate(self.kinds) if k == FULL)

    @property
    def sliding_layers(self) -> tuple:
        return tuple(l for l, k in enumerate(self.kinds) if k == SLIDING)

    @property
    def indexed(self) -> bool:
        return self.index_n_heads > 0

    @property
    def local_experts(self) -> int:
        """Routed experts whose matrices this chip holds."""
        return self.n_local_experts or self.n_routed_experts

    @property
    def ring_rows(self) -> int:
        """Rows of a sliding layer's ring: the window, rounded up to the
        layout's tile of positions (128; 8 under that)."""
        tile = 128 if self.sliding_window >= 128 else 8
        return -(-self.sliding_window // tile) * tile

    def view(self, kind: str) -> "MlaMoeConfig":
        """This config with ``kind``'s attention widths under the plain
        names (and no indexer for the sliding kind)."""
        return _kind_view(self, kind)

    @classmethod
    def tiny(cls, **kw) -> "MlaMoeConfig":
        defaults = dict(
            vocab_size=256, hidden_size=64, num_layers=3, num_heads=4,
            q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
            moe_intermediate_size=32, n_routed_experts=8, n_shared_experts=1,
            num_experts_per_tok=2, first_k_dense_replace=1, max_seq=64,
            rope_theta=10000.0,
        )
        defaults.update(kw)
        return cls(**defaults)


@functools.lru_cache(maxsize=None)
def _kind_view(cfg: MlaMoeConfig, kind: str) -> MlaMoeConfig:
    if kind == FULL:
        return cfg
    return dataclasses.replace(
        cfg,
        num_heads=cfg.swa_num_heads,
        q_lora_rank=cfg.swa_q_lora_rank,
        kv_lora_rank=cfg.swa_kv_lora_rank,
        qk_nope_head_dim=cfg.swa_qk_nope_head_dim,
        qk_rope_head_dim=cfg.swa_qk_rope_head_dim,
        v_head_dim=cfg.swa_v_head_dim,
        rope_theta=cfg.swa_rope_theta,
        index_n_heads=0,
    )


def _layer_plan(cfg) -> list:
    """``(kind, index among the layers of its kind)`` of every layer of a
    config with ``kinds``: the index is the layer's row in its kind's
    cache buffers."""
    seen: dict = {}
    plan = []
    for kind in cfg.kinds:
        plan.append((kind, seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return plan


def _attn_params(cfg: MlaMoeConfig, kind: str) -> int:
    """Weight-matrix elements of one ``kind`` layer's attention."""
    c = cfg.view(kind)
    h, nh = c.hidden_size, c.num_heads
    n = (
        h * c.q_lora_rank
        + c.q_lora_rank * nh * c.qk_head_dim
        + h * (c.kv_lora_rank + c.qk_rope_head_dim)
        + c.kv_lora_rank * nh * (c.qk_nope_head_dim + c.v_head_dim)
        + nh * c.v_head_dim * h
    )
    if c.attention_gate == "headwise":
        n += h * nh
    if c.indexed:
        n += (c.q_lora_rank * c.index_n_heads * c.index_head_dim
              + h * c.index_head_dim + h * c.index_n_heads)
    return n


def param_counts(cfg: MlaMoeConfig) -> tuple[int, int]:
    """``(active, total)`` weight-matrix elements: what one token
    multiplies through in a forward pass (its chosen routed experts as
    far as they are held here, the shared ones, the router, the head) and
    what the tree holds (embedding included).  The cost model's two
    terms."""
    h = cfg.hidden_size
    attn = [_attn_params(cfg, kind) for kind in cfg.kinds]
    expert = 3 * h * cfg.moe_intermediate_size
    router = h * cfg.n_routed_experts
    dense_ffn = cfg.num_dense_layers * 3 * h * cfg.intermediate_size
    head = h * cfg.vocab_size
    chosen_here = (cfg.num_experts_per_tok * cfg.local_experts
                   // cfg.n_routed_experts)
    active = sum(attn) + dense_ffn + head + cfg.num_moe_layers * (
        router + expert * (chosen_here + cfg.n_shared_experts)
    )
    total = sum(attn) + dense_ffn + 2 * head + cfg.num_moe_layers * (
        router + expert * (cfg.local_experts + cfg.n_shared_experts)
    )
    return active, total


def routed_assignments(cfg: MlaMoeConfig, tokens: int) -> int:
    """(token, expert) pairs ``tokens`` real tokens make in one forward
    pass, wherever the expert is held: ``tpumlops_moe_assignments_total``
    plus ``tpumlops_moe_assignments_routed_away_total``."""
    return int(tokens) * cfg.num_experts_per_tok * cfg.num_moe_layers


def moe_row_tile(cfg: MlaMoeConfig, tokens: int) -> int:
    """Rows a visit of the grouped matmuls multiplies in a program call
    over ``tokens`` token rows (padding included: the shape is static)."""
    return row_tile(int(tokens) * cfg.num_experts_per_tok, cfg.local_experts)


def _row_widths(cfg: MlaMoeConfig) -> dict:
    """Numbers a position holds in each cache buffer, by buffer name."""
    out = {"rope": max(LANES, cfg.qk_rope_head_dim), "latent": cfg.kv_lora_rank}
    if cfg.indexed:
        out["index"] = cfg.index_head_dim
    if cfg.sliding_layers:
        s = cfg.view(SLIDING)
        out["ring_rope"] = max(LANES, s.qk_rope_head_dim)
        out["ring_latent"] = s.kv_lora_rank
    return out


def kv_row_bytes(cfg: MlaMoeConfig, dtype_bytes: int = 2) -> int:
    """Bytes one cache row (a slot at full ``max_seq``) holds: a full
    layer's latent, RoPE key (padded to ``LANES``) and index key a
    position, a sliding layer's latent and RoPE key a ring row, whatever
    the head count."""
    w = _row_widths(cfg)
    full = len(cfg.full_layers) * cfg.max_seq * (
        w["rope"] + w["latent"] + w.get("index", 0))
    ring = len(cfg.sliding_layers) * cfg.ring_rows * (
        w.get("ring_rope", 0) + w.get("ring_latent", 0))
    return (full + ring) * dtype_bytes


def routed_expert_leaves(params: dict) -> list:
    """The routed experts' matrices, a tree a layer that has them: all
    resident, a few read a token, so the HBM ledger counts them apart."""
    return [lp["experts"] for lp in params["layers"] if "experts" in lp]


def _tree_bytes(tree) -> int:
    return sum(int(leaf.size) * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(tree))


@dataclass(frozen=True)
class CostModel:
    """Analytic per-program FLOPs / HBM-bytes of this family's serving
    programs, for the device telemetry's per-tick utilization.  FLOPs
    come from the ACTIVE parameters (the chosen routed experts held here,
    the shared ones, the router, the head); bytes from the weights every
    call streams plus the distinct held experts ``tokens`` tokens are
    expected to reach under uniform routing, ``E_here (1 - (1 - k/E)^
    tokens)`` a layer, plus the cache rows read and written: a full
    layer's as far as attended (the kept ``index_topk`` where an indexer
    selects, and the index keys over the whole window), a sliding
    layer's window."""

    active_params: int
    total_params: int
    unrouted_bytes: int  # everything a call streams whatever it routes
    expert_bytes: int  # one routed expert's three matrices
    moe_layers: int
    n_routed_experts: int
    local_experts: int
    experts_per_tok: int
    # A tuple a layer kind present: (layers, heads x (qk_nope + qk_rope +
    # v): flops a (query, key) pair a layer, cache bytes a position a
    # layer, most keys a query attends or 0 for no limit).
    attn: tuple
    # The indexer: (layers, flops a scored key a layer, key bytes).
    index: tuple = (0, 0, 0)
    tp: int = 1  # no mesh exists for this family

    def _routed_bytes(self, tokens: float) -> float:
        miss = (1.0 - self.experts_per_tok / self.n_routed_experts) ** max(
            0.0, tokens)
        return self.moe_layers * self.local_experts * (1.0 - miss) * self.expert_bytes

    def _attended(self, tokens: float, attended: float) -> tuple[float, float]:
        """(flops, cache bytes read) of ``tokens`` queries that each see
        ``attended`` earlier positions."""
        flops = nbytes = 0.0
        for layers, pair_flops, row_bytes, most in self.attn:
            keys = min(attended, most) if most else attended
            flops += 2.0 * tokens * keys * layers * pair_flops
            nbytes += layers * row_bytes * keys
        layers, key_flops, key_bytes = self.index
        flops += tokens * attended * layers * key_flops
        nbytes += layers * key_bytes * attended
        return flops, nbytes

    @property
    def _row_write_bytes(self) -> float:
        return (sum(layers * row for layers, _f, row, _m in self.attn)
                + self.index[0] * self.index[2])

    def decode(self, rows: int, window: int, s: int = 1
               ) -> tuple[float, float]:
        flops, read = self._attended(rows * s, window)
        flops += 2.0 * self.active_params * rows * s
        nbytes = (self.unrouted_bytes + self._routed_bytes(rows * s)
                  + rows * (read + s * self._row_write_bytes))
        return flops, nbytes

    def prefill(self, rows: int, chunk: int, attended: float | None = None
                ) -> tuple[float, float]:
        if attended is None:
            attended = chunk / 2.0
        flops, read = self._attended(rows * chunk, attended)
        flops += 2.0 * self.active_params * rows * chunk
        nbytes = (self.unrouted_bytes + self._routed_bytes(rows * chunk)
                  + rows * (read + chunk * self._row_write_bytes))
        return flops, nbytes


def cost_model(params: dict, cfg: MlaMoeConfig, dtype_bytes: int = 2) -> CostModel:
    active, total = param_counts(cfg)
    routed = _tree_bytes(routed_expert_leaves(params))
    attn = []
    for kind, layers, most in (
        (FULL, len(cfg.full_layers), cfg.index_topk if cfg.indexed else 0),
        (SLIDING, len(cfg.sliding_layers), cfg.sliding_window),
    ):
        if layers:
            c = cfg.view(kind)
            attn.append((
                layers, c.num_heads * (c.qk_head_dim + c.v_head_dim),
                float((c.kv_lora_rank + c.qk_rope_head_dim) * dtype_bytes),
                most,
            ))
    index = (0, 0, 0)
    if cfg.indexed:
        index = (len(cfg.full_layers),
                 2.0 * cfg.index_n_heads * cfg.index_head_dim,
                 float(cfg.index_head_dim * dtype_bytes))
    return CostModel(
        active_params=active,
        total_params=total,
        unrouted_bytes=_tree_bytes(params) - routed,
        expert_bytes=routed // max(1, cfg.num_moe_layers * cfg.local_experts),
        moe_layers=cfg.num_moe_layers,
        n_routed_experts=cfg.n_routed_experts,
        local_experts=cfg.local_experts,
        experts_per_tok=cfg.num_experts_per_tok,
        attn=tuple(attn),
        index=index,
    )


def _layer_rows(buf: jax.Array, first, rows: int) -> jax.Array:
    """``buf[:, first:first + rows]`` of a layer's cache buffer ``[B, T,
    D]`` as ONE dynamic slice ``[B, rows, D]``, sized by what is read and
    not by the capacity (``llama._layer_window`` for this layout)."""
    b, _t, d = buf.shape
    z = jnp.zeros((), jnp.int32)
    return lax.dynamic_slice(
        buf, (z, jnp.asarray(first, jnp.int32), z), (b, rows, d))


def _commit_row(buf: jax.Array, new: jax.Array, at: jax.Array) -> jax.Array:
    """Write row ``b``'s ``new[b]`` [D] at position ``at[b]`` of a layer's
    buffer ``[B, T, D]``, in place; ``at[b] == T`` drops the write
    (``llama._commit_rows`` for this layout)."""
    rows = jnp.arange(buf.shape[0])
    return buf.at[rows, at].set(
        new.astype(buf.dtype), mode="drop", unique_indices=True)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _cache_buffers(cfg: MlaMoeConfig, batch: int, dtype) -> tuple[dict, dict]:
    """The zeroed ``(k, v)`` dicts of a cache of ``batch`` rows, a tuple
    of one buffer a layer under each name: the full layers' over
    ``max_seq`` positions, the sliding layers' over their ring.  Jitted:
    every admission makes a fresh scratch, and a buffer a layer made
    eagerly is a host dispatch a buffer with the chip idle (ten of them
    cost a JoyAI chunk 1.2 ms of `engine.prefill_dispatch`, PERF.md 6)."""
    widths = _row_widths(cfg)

    def bufs(*names):
        out = {}
        for name in names:
            if name in widths:
                ring = name.startswith("ring")
                layers = cfg.sliding_layers if ring else cfg.full_layers
                shape = (batch, cfg.ring_rows if ring else cfg.max_seq, widths[name])
                out[name] = tuple(jnp.zeros(shape, dtype) for _ in layers)
        return out

    return bufs("rope", "index", "ring_rope"), bufs("latent", "ring_latent")


class KVCache(NamedTuple):
    """The prefill scratch: ``k`` and ``v`` the dicts of buffers by row
    kind (the module's docstring), one scalar length shared by the batch
    (llama's ``KVCache`` at other widths).  A sliding layer's rows lie at
    ``position mod ring`` here as in the slot cache, so the insert copies
    the ring as it stands."""

    k: dict
    v: dict
    length: jax.Array

    @classmethod
    def create(cls, cfg: MlaMoeConfig, batch: int, dtype=jnp.bfloat16) -> "KVCache":
        k, v = _cache_buffers(cfg, batch, dtype)
        return cls(k=k, v=v, length=jnp.zeros((), jnp.int32))

    @property
    def capacity(self) -> int:
        return self.v["latent"][0].shape[1]


class RaggedKVCache(NamedTuple):
    """The slot cache with per-row lengths, in the scratch's layout
    (llama's ``RaggedKVCache`` at other widths): the engine donates
    ``k`` and ``v``, every buffer of every row kind, through every
    program."""

    k: dict  # a layer: "rope" [B,T,rope], "index" [B,T,di], "ring_rope" [B,R,rope]
    v: dict  # a layer: "latent" [B,T,rank], "ring_latent" [B,R,rank]
    lengths: jax.Array  # int32 [B]

    @classmethod
    def create(
        cls, cfg: MlaMoeConfig, batch: int, dtype=jnp.bfloat16
    ) -> "RaggedKVCache":
        k, v = _cache_buffers(cfg, batch, dtype)
        return cls(k, v, jnp.zeros((batch,), jnp.int32))

    @property
    def capacity(self) -> int:
        return self.v["latent"][0].shape[1]

    def layer_window(self, layer, window: int):
        """Full layer ``layer``'s (its index among the full layers) first
        ``window`` positions of every slot: RoPE keys ``[B, window, rope]``
        and latents ``[B, window, rank]``."""
        return (_layer_rows(self.k["rope"][layer], 0, window),
                _layer_rows(self.v["latent"][layer], 0, window))


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init(key: jax.Array, cfg: MlaMoeConfig, dtype=jnp.float32) -> dict:
    """N(0, 0.02) matrices, norms 1, a small seeded router bias (float32
    whatever ``dtype``: it is added to float32 scores).  ``layers`` is a
    list of per-layer trees: attention at the layer's kind's widths (a
    gated layer adds ``attn_gate``, an indexed one ``idx_q_b``,
    ``idx_k`` with its LayerNorm ``idx_k_norm`` / ``idx_k_bias``, and
    ``idx_w``); the leading ones carry a SwiGLU (``gate``, ``up``,
    ``down``), the rest a router over every routed expert, the
    ``experts`` held here stacked on an expert axis, and the shared
    experts."""
    h, e = cfg.hidden_size, cfg.n_routed_experts
    i, im = cfg.intermediate_size, cfg.moe_intermediate_size
    ims = im * cfg.n_shared_experts
    keys = iter(jax.random.split(key, 2 + 13 * cfg.num_layers))

    def normal(shape, dt=dtype, k=None):
        k = next(keys) if k is None else k
        return (0.02 * jax.random.normal(k, shape, jnp.float32)).astype(dt)

    def layer(l):
        c = cfg.view(cfg.kinds[l])
        nh, qr, kvr = c.num_heads, c.q_lora_rank, c.kv_lora_rank
        lp = {
            "attn_norm": jnp.ones((h,), dtype),
            "q_a": normal((h, qr)),
            "q_norm": jnp.ones((qr,), dtype),
            "q_b": normal((qr, nh * c.qk_head_dim)),
            "kv_a": normal((h, kvr + c.qk_rope_head_dim)),
            "kv_norm": jnp.ones((kvr,), dtype),
            "kv_b": normal((kvr, nh * (c.qk_nope_head_dim + c.v_head_dim))),
            "o": normal((nh * c.v_head_dim, h)),
            "ffn_norm": jnp.ones((h,), dtype),
        }
        if l < cfg.num_dense_layers:
            lp.update(gate=normal((h, i)), up=normal((h, i)), down=normal((i, h)))
        else:
            held = cfg.local_experts
            lp.update(
                router=normal((h, e)),
                router_bias=normal((e,), jnp.float32),
                experts={
                    "gate": normal((held, h, im)),
                    "up": normal((held, h, im)),
                    "down": normal((held, im, h)),
                },
                shared_gate=normal((h, ims)),
                shared_up=normal((h, ims)),
                shared_down=normal((ims, h)),
            )
        # The variants' leaves draw from keys of their own, so a tree
        # without them is the one it always was.
        extra = iter(jax.random.split(jax.random.fold_in(key, 1000 + l), 4))
        if c.attention_gate == "headwise":
            lp["attn_gate"] = normal((h, nh), k=next(extra))
        if c.indexed:
            hi, di = c.index_n_heads, c.index_head_dim
            lp.update(
                idx_q_b=normal((qr, hi * di), k=next(extra)),
                idx_k=normal((h, di), k=next(extra)),
                idx_k_norm=jnp.ones((di,), dtype),
                idx_k_bias=jnp.zeros((di,), dtype),
                idx_w=normal((h, hi), k=next(extra)),
            )
        return lp

    return {
        "embed": normal((cfg.vocab_size, h)),
        "layers": [layer(l) for l in range(cfg.num_layers)],
        "final_norm": jnp.ones((h,), dtype),
        "lm_head": normal((h, cfg.vocab_size)),
    }


# ---------------------------------------------------------------------------
# RoPE on interleaved pairs
# ---------------------------------------------------------------------------


def rope_cos_sin(positions: jax.Array, cfg: MlaMoeConfig):
    """cos/sin ``[..., rope/2]`` (float32) for ``positions`` ``[...]``."""
    d = cfg.qk_rope_head_dim
    inv_freq = 1.0 / (cfg.rope_theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate the pairs ``(2i, 2i+1)`` of ``x``'s last axis; ``cos``/``sin``
    broadcast against ``x[..., ::2]``."""
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# Layer pieces (``cfg`` is the layer's kind's view)
# ---------------------------------------------------------------------------


def _latent(x, norm, rank, cfg):
    """A latent after its RMSNorm, rescaled where the config says so."""
    y = rms_norm(x, norm, cfg.rms_eps)
    if cfg.lora_rescale:
        y = y * jnp.asarray(math.sqrt(cfg.hidden_size / rank), y.dtype)
    return y


def _mla_q(xn, lp, cos, sin, cfg):
    """Normed ``xn`` [B,S,H] -> ``q_nope`` [B,S,NH,nope], ``q_rope``
    [B,S,NH,rope] (rotated) and the query latent ``cq`` [B,S,q_rank] they
    come from; ``cos``/``sin`` [B or 1, S, rope/2]."""
    b, s, _h = xn.shape
    with jax.named_scope("layer.mla_q"):
        cq = _latent(_qmatmul(xn, lp["q_a"]).astype(xn.dtype), lp["q_norm"],
                     cfg.q_lora_rank, cfg)
        q = _qmatmul(cq, lp["q_b"]).astype(xn.dtype)
        q = q.reshape(b, s, cfg.num_heads, cfg.qk_head_dim)
        q_nope, q_rope = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
        return q_nope, apply_rope(q_rope, cos[:, :, None], sin[:, :, None]), cq


def _mla_kv(xn, lp, cos, sin, cfg):
    """Normed ``xn`` [B,S,H] -> what the cache holds of these positions:
    the RoPE key ``kr`` [B,S,rope] and the normalised latent ``c``
    [B,S,rank]."""
    with jax.named_scope("layer.mla_kv"):
        ckr = _qmatmul(xn, lp["kv_a"]).astype(xn.dtype)
        c, kr = jnp.split(ckr, [cfg.kv_lora_rank], axis=-1)
        return apply_rope(kr, cos, sin), _latent(c, lp["kv_norm"],
                                                 cfg.kv_lora_rank, cfg)


def _to_lanes(x):
    """``x`` [..., n] zero-padded to ``LANES`` numbers: a RoPE key as its
    cache row holds it, or a query's RoPE part to score such rows."""
    short = LANES - x.shape[-1]
    return x if short <= 0 else jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, short)])


def _kv_b(lp, cfg, dtype):
    """``W_kvb`` as ``[rank, NH, nope + v]``."""
    return lp["kv_b"].astype(dtype).reshape(
        cfg.kv_lora_rank, cfg.num_heads, cfg.qk_nope_head_dim + cfg.v_head_dim
    )


def _index_qkw(xn, cq, lp, cos, sin, cfg):
    """The indexer's projections of normed ``xn`` [B,S,H] and the query
    latent ``cq``: queries ``qi`` [B,S,Hi,Di] and the key ``ki`` [B,S,Di]
    (LayerNorm'd; the layer's RoPE on the first ``rope`` dims of both) and
    the heads' weights ``wi`` [B,S,Hi] float32."""
    b, s, _h = xn.shape
    rope = cfg.qk_rope_head_dim
    with jax.named_scope("layer.dsa_index"):
        qi = _qmatmul(cq, lp["idx_q_b"]).astype(xn.dtype)
        qi = qi.reshape(b, s, cfg.index_n_heads, cfg.index_head_dim)
        kf = _qmatmul(xn, lp["idx_k"]).astype(jnp.float32)
        kf = kf - kf.mean(-1, keepdims=True)
        kf = kf * lax.rsqrt(jnp.mean(kf * kf, -1, keepdims=True) + INDEX_NORM_EPS)
        ki = (kf * lp["idx_k_norm"].astype(jnp.float32)
              + lp["idx_k_bias"].astype(jnp.float32)).astype(xn.dtype)
        qi = jnp.concatenate(
            [apply_rope(qi[..., :rope], cos[:, :, None], sin[:, :, None]),
             qi[..., rope:]], axis=-1)
        ki = jnp.concatenate(
            [apply_rope(ki[..., :rope], cos, sin), ki[..., rope:]], axis=-1)
        wi = _qmatmul(xn, lp["idx_w"]).astype(jnp.float32)
        return qi, ki, wi


def _index_scores(qi, wi, ki):
    """``I = sum_h w_h relu(q_h . k)``: ``qi`` [B,S,Hi,Di], ``wi`` [B,S,Hi],
    keys ``ki`` [B,K,Di] -> [B,S,K] float32 (bf16 operands, float32
    accumulation)."""
    dots = jnp.einsum("bqhd,bkd->bqhk", qi, ki.astype(qi.dtype),
                      preferred_element_type=jnp.float32)
    return jnp.einsum("bqhk,bqh->bqk", jax.nn.relu(dots), wi)


def _kth_largest(x: jax.Array, k: int) -> jax.Array:
    """The ``k``-th largest of ``x``'s last axis (float32, no NaN; -inf
    where fewer than ``k`` entries are finite), EXACT: bisection, a bit a
    pass, on the unsigned integer whose order is the float's.  32 counts
    over the axis where a sort of it would cost a hundred."""
    bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    # Order-preserving image: flip every bit of a negative, the sign bit
    # of a non-negative.
    u = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    def bit(i, found):
        cand = found | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(u >= cand[..., None], axis=-1) >= k
        return jnp.where(enough, cand, found)

    found = lax.fori_loop(0, 32, bit, jnp.zeros(x.shape[:-1], jnp.uint32))
    back = jnp.where(found >> 31 == 1, found & jnp.uint32((1 << 31) - 1), ~found)
    return lax.bitcast_convert_type(back, jnp.float32)


def _top_mask(x: jax.Array, k: int) -> jax.Array:
    """Which entries of ``x``'s last axis are its ``k`` largest (every
    finite one where fewer are finite), bool like ``x``: exactly what
    ``lax.top_k`` picks, a tie at the ``k``-th value going to the lower
    index, without its sort.  Everything above the ``k``-th largest
    value, and of the entries equal to it the first few, their last
    index found by a second bisection."""
    kth = _kth_largest(x, k)[..., None]
    above, tie = x > kth, (x == kth) & (x > -jnp.inf)
    need = k - jnp.sum(above, axis=-1)  # ties still to take, >= 1 where any
    at = jnp.arange(x.shape[-1], dtype=jnp.int32)
    nbits = max(1, (x.shape[-1] - 1).bit_length())

    def bit(i, last):
        # The largest index with fewer than ``need`` ties before it.
        cand = last | (jnp.int32(1) << (nbits - 1 - i))
        few = jnp.sum(tie & (at < cand[..., None]), axis=-1) < need
        return jnp.where(few, cand, last)

    last = lax.fori_loop(0, nbits, bit, jnp.zeros(x.shape[:-1], jnp.int32))
    return above | (tie & (at <= last[..., None]))


def _key_block(keys: int) -> int:
    """Key positions a block of ``_attn_blocks``'s einsum body holds: all
    of them up to ``ONE_PASS``, else the largest divisor of ``keys`` at
    or under ``KEY_BLOCK`` (the engine keeps a capacity a multiple of the
    prefill chunk, so a divisor of that size exists where it matters)."""
    if keys <= ONE_PASS:
        return keys
    return _key_tile(keys)


def _key_tile(keys: int) -> int:
    """The largest divisor of ``keys`` at or under ``KEY_BLOCK``: the
    keys a grid step of the fused core attends, whatever ``ONE_PASS``
    says (the kernel walks the written blocks of any capacity)."""
    return max(d for d in range(1, KEY_BLOCK + 1) if keys % d == 0)


def prefill_key_blocks(cfg: MlaMoeConfig, start: int, tokens: int) -> tuple[int, int]:
    """``(walked, skipped)``: of the capacity's key blocks, summed over
    the full-attention layers, those a prefill call of ``tokens`` rows
    from position ``start`` multiplies (the ones that hold a written
    position) and those it does not reach.  Host arithmetic for
    ``tpumlops_prefill_key_blocks_total``; a sliding layer attends its
    window whole and has no capacity to skip."""
    kb = _key_tile(cfg.max_seq)
    walked = min(-(-(int(start) + int(tokens)) // kb), cfg.max_seq // kb)
    layers = len(cfg.full_layers)
    return layers * walked, layers * (cfg.max_seq // kb - walked)


def _attn_blocks(q_nope, q_rope, keys_kr, keys_c, sees, written, lp, cfg):
    """Attention of ``S`` queries over the first ``written`` of ``T`` key
    positions (an int or a traced scalar: no query sees a later one),
    keys and values expanded from the latent a block at a time:
    ``keys_kr`` [B,T,LANES] the RoPE keys as cached, ``keys_c``
    [B,T,rank] the latents, ``sees`` bool [B or 1, S, T] which keys each
    query sees (at least one).  On the TPU the fused core of
    ``ops/prefill_attention.py`` at the shapes its tiles take; else, and
    off it, the einsum body here: one block is a plain softmax, more
    keep a running maximum and sum.  Returns ctx [B,S,NH*v]."""
    b, s = q_nope.shape[:2]
    dt = q_nope.dtype
    nh, vd = cfg.num_heads, cfg.v_head_dim
    t = keys_c.shape[1]
    kb = _key_block(t)
    z = jnp.zeros((), jnp.int32)

    def einsums(q_nope, q_rope, keys_kr, keys_c, w_kvb, sees, written):
        w_kvb = _kv_b({"kv_b": w_kvb}, cfg, dt)

        def scores_of(j):
            lo = j * kb
            kr = _layer_rows(keys_kr, lo, kb)[..., :cfg.qk_rope_head_dim]
            c = _layer_rows(keys_c, lo, kb)
            see = lax.dynamic_slice(sees, (z, z, lo), (*sees.shape[:2], kb))
            kv = jnp.einsum(
                "btc,cnd->btnd", c.astype(dt), w_kvb,
                preferred_element_type=jnp.float32,
            ).astype(dt)
            k_nope, v = jnp.split(kv, [cfg.qk_nope_head_dim], axis=-1)
            sc = jnp.einsum(
                "bqnd,bknd->bnqk", q_nope, k_nope, preferred_element_type=jnp.float32
            ) + jnp.einsum(
                "bqnd,bkd->bnqk", q_rope, kr.astype(dt),
                preferred_element_type=jnp.float32,
            )
            return sc * scale, see[:, None], v

        if kb == t:
            sc, see, v = scores_of(0)
            probs = jax.nn.softmax(jnp.where(see, sc, -1e9), axis=-1).astype(dt)
            ctx = jnp.einsum("bnqk,bknd->bqnd", probs, v)
            return ctx.reshape(b, s, nh * vd)

        low = jnp.float32(-1e30)

        def step(j, carry):
            top, total, acc = carry
            sc, see, v = scores_of(j)
            top2 = jnp.maximum(top, jnp.max(jnp.where(see, sc, low), axis=-1))
            p = jnp.where(see, jnp.exp(sc - top2[..., None]), 0.0)
            keep = jnp.exp(top - top2)
            acc = acc * keep[..., None] + jnp.einsum(
                "bnqk,bknd->bnqd", p.astype(dt), v,
                preferred_element_type=jnp.float32,
            )
            return top2, total * keep + p.sum(-1), acc

        _top, total, acc = lax.fori_loop(
            0, (written + kb - 1) // kb, step,
            (jnp.full((b, nh, s), low), jnp.zeros((b, nh, s), jnp.float32),
             jnp.zeros((b, nh, s, vd), jnp.float32)),
        )
        ctx = acc / jnp.maximum(total, 1e-30)[..., None]
        return ctx.transpose(0, 2, 1, 3).reshape(b, s, nh * vd).astype(dt)

    scale = 1.0 / math.sqrt(cfg.qk_head_dim)
    with jax.named_scope("layer.attn_core"):
        return prefill_attention(
            q_nope, q_rope, keys_kr, keys_c, lp["kv_b"], sees, written,
            key_block=_key_tile(t), scale=scale, fallback=einsums)


def _attn_absorbed(q_nope, q_rope, kr_new, c_new, ck, cv, mask_bias, lp, cfg,
                   new_bias=None):
    """Single-token attention with ``W_kvb`` absorbed and the cache
    read-only: ``ck`` [B,W,LANES] / ``cv`` [B,W,rank] are the attended
    rows, ``mask_bias`` [B,1,W] is STRICT (only positions before the
    current one), and the current position is attended through the exact
    in-flight ``kr_new`` [B,1,LANES] / ``c_new`` [B,1,rank] (its cache row is written
    after the layer loop, as in ``llama._block_decode_deferred``);
    ``new_bias`` [B,1,1] masks it where a selection left it out."""
    b = q_nope.shape[0]
    dt = q_nope.dtype
    with jax.named_scope("layer.attn_core"):
        w_uk, w_uv = jnp.split(_kv_b(lp, cfg, dt), [cfg.qk_nope_head_dim], axis=-1)
        q_lat = jnp.einsum(
            "bnd,cnd->bnc", q_nope[:, 0], w_uk, preferred_element_type=jnp.float32
        ).astype(dt)
        qr = _to_lanes(q_rope[:, 0])

        def score(lat, kr):  # [B,K,rank], [B,K,rope] -> [B,NH,K]
            return jnp.einsum(
                "bnc,bkc->bnk", q_lat, lat.astype(dt),
                preferred_element_type=jnp.float32,
            ) + jnp.einsum(
                "bnr,bkr->bnk", qr, kr.astype(dt),
                preferred_element_type=jnp.float32,
            )

        scale = 1.0 / math.sqrt(cfg.qk_head_dim)
        own = score(c_new, kr_new) * scale
        if new_bias is not None:
            own = own + new_bias
        full = jnp.concatenate([score(cv, ck) * scale + mask_bias, own], axis=-1)
        probs = jax.nn.softmax(full, axis=-1).astype(dt)
        ctx_lat = jnp.einsum(
            "bnk,bkc->bnc", probs[..., :-1], cv.astype(dt),
            preferred_element_type=jnp.float32,
        ) + probs[..., -1:].astype(jnp.float32) * c_new.astype(jnp.float32)
        ctx = jnp.einsum(
            "bnc,cnd->bnd", ctx_lat.astype(dt), w_uv,
            preferred_element_type=jnp.float32,
        ).astype(dt)
        return ctx.reshape(b, 1, cfg.num_heads * cfg.v_head_dim)


def _attn_out(x, ctx, xn, lp, cfg):
    """The heads' outputs ``ctx`` [B,S,NH*v], gated a head by a sigmoid
    of the layer's normed input where the config says so, through
    ``W_o`` onto the residual."""
    if cfg.attention_gate == "headwise":
        with jax.named_scope("layer.attn_gate"):
            g = jax.nn.sigmoid(_qmatmul(xn, lp["attn_gate"]).astype(jnp.float32))
            b, s, _ = ctx.shape
            ctx = (ctx.reshape(b, s, cfg.num_heads, cfg.v_head_dim)
                   * g[..., None].astype(ctx.dtype)).reshape(ctx.shape)
    with jax.named_scope("layer.attn_out"):
        return x + _qmatmul(ctx, lp["o"]).astype(x.dtype)


def _swiglu(xn, gate, up, down):
    act = jax.nn.silu(_qmatmul(xn, gate)) * _qmatmul(xn, up)
    return _qmatmul(act.astype(xn.dtype), down)


def route(xn, router, bias, cfg):
    """Chosen experts ``[N, k]`` (int32) and their weights ``[N, k]``
    (float32) for normed tokens ``xn`` [N, H]: scores by
    ``cfg.scoring_func`` (a sigmoid an expert, or a softmax over all of
    them), ``bias`` (None: no selection bias) picks and does not weigh,
    weights renormalised over the chosen and scaled.  All in float32 at
    ``highest`` precision."""
    logits = jnp.matmul(
        xn.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )
    scores = (jax.nn.softmax(logits, axis=-1) if cfg.scoring_func == "softmax"
              else jax.nn.sigmoid(logits))
    pick = scores if bias is None else scores + bias.astype(jnp.float32)
    _, idx = lax.top_k(pick, cfg.num_experts_per_tok)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weights = chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
    return idx, weights * cfg.routed_scaling_factor


@functools.partial(jax.jit, static_argnums=3)
def moe_ffn(xn, lp, valid, cfg):
    """The routed + shared expert FFN of normed tokens ``xn`` [N, H], of
    this family and of ``models/gdn_moe.py`` (``cfg`` is either's: what is
    read of it is the share, ``num_experts_per_tok`` and ``route``'s
    keys; a tree without ``router_bias`` is routed without one, one with
    ``shared_expert_gate`` gates its shared experts);
    ``valid`` bool [N] marks the real ones (padding is not routed and
    yields the shared experts' output alone, which nobody reads).  Of a
    token's chosen experts only those held here (``cfg.local_experts``
    from ``cfg.local_expert_start``) are computed: the rest add nothing.
    Returns ``(y [N, H] float32, counts int32 [3])``: the held experts
    that got a real token, the row-tile visits of the grouped matmuls'
    schedule, the assignments that landed here.
    Jitted, so the expert layers of every serving program share one trace
    and each program lowers the block once: a cached boot re-traces all
    36 programs, and that, not XLA, is what its warm-up waits for."""
    n, h = xn.shape
    e, k = cfg.local_experts, cfg.num_experts_per_tok
    with jax.named_scope("layer.moe_router"):
        idx, weights = route(xn, lp["router"], lp.get("router_bias"), cfg)
    with jax.named_scope("layer.moe_experts"):
        # Token copies sorted by held expert; padding and the assignments
        # routed away sort behind every group (expert id E) and belong to
        # none.
        here = idx - cfg.local_expert_start
        held = valid[:, None] & (here >= 0) & (here < e)
        flat = jnp.where(held, here, e).reshape(n * k)
        order = jnp.argsort(flat)
        sizes = jnp.zeros((e + 1,), jnp.int32).at[flat].add(1)[:e]
        xs = xn[order // k]
        ex = lp["experts"]
        # One schedule of (expert, row tile) visits for the three matmuls.
        plan = row_tile_schedule(sizes, n * k, row_tile(n * k, e))
        act = jax.nn.silu(
            grouped_matmul(xs, ex["gate"].astype(xn.dtype), sizes, plan)
        ) * grouped_matmul(xs, ex["up"].astype(xn.dtype), sizes, plan)
        ys = grouped_matmul(act.astype(xn.dtype), ex["down"].astype(xn.dtype),
                            sizes, plan)
        # Rows behind the last group are whatever the grouped matmul left.
        landed = sizes.sum()
        ys = jnp.where((jnp.arange(n * k) < landed)[:, None], ys, 0.0)
        routed = jnp.einsum(
            "nkh,nk->nh", ys[jnp.argsort(order)].reshape(n, k, h), weights
        )
        counts = jnp.stack(
            [jnp.sum(sizes > 0).astype(jnp.int32), plan.visits, landed])
    with jax.named_scope("layer.moe_shared"):
        shared = _swiglu(xn, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
        if "shared_expert_gate" in lp:  # one sigmoid a token on what they add
            shared = shared * jax.nn.sigmoid(_qmatmul(xn, lp["shared_expert_gate"]))
    return routed + shared, counts


def _ffn(x, lp, valid, cfg):
    """A layer's FFN with its residual: SwiGLU where the layer carries
    one, experts where it carries a router.  ``valid`` bool [B, S] marks
    the real tokens.  Returns ``(x, counts)`` (as ``moe_ffn``'s)."""
    b, s, h = x.shape
    if "router" not in lp:
        with jax.named_scope("layer.mlp"):
            xn = rms_norm(x, lp["ffn_norm"], cfg.rms_eps)
            y = _swiglu(xn, lp["gate"], lp["up"], lp["down"])
            return x + y.astype(x.dtype), jnp.zeros((3,), jnp.int32)
    xn = rms_norm(x, lp["ffn_norm"], cfg.rms_eps).reshape(b * s, h)
    y, counts = moe_ffn(xn, lp, valid.reshape(b * s), cfg)
    return x + y.reshape(b, s, h).astype(x.dtype), counts


def _dsa_all_kept(positions, real):
    """int32 [2] (keys scored, keys kept) of an indexed layer where fewer
    positions exist than ``index_topk``, so the program scores nothing
    and the equations keep every position up to a ``real`` query's own:
    that many, twice.  Where a selection runs, ``_dsa_select`` and
    ``_dsa_pick`` count what it scored and kept."""
    seen = jnp.where(real, positions.astype(jnp.int32) + 1, 0).sum()
    return jnp.stack([seen, seen])


# ---------------------------------------------------------------------------
# Forward over a shared-start cache (prefill, chunked prefill, /infer)
# ---------------------------------------------------------------------------


def forward(
    params: dict,
    input_ids: jax.Array,
    cache: KVCache,
    cfg: MlaMoeConfig,
    dtype=jnp.bfloat16,
):
    """Run ``input_ids`` [B,S] through the model starting at
    ``cache.length``; ids < 0 are padding (embedded as id 0, not routed,
    not written to a ring).  A full layer writes its rows, then attends
    the blocks of its cache written so far, the indexer's selection among
    them where the capacity exceeds ``index_topk``; a sliding layer
    attends its ring's last ``window - 1`` rows and the chunk's own,
    then its ring takes the chunk's rows in one drop-scatter.
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
    x = _embed(params, jnp.maximum(input_ids, 0), dtype)
    positions = start + jnp.arange(s)
    ropes = {kind: rope_cos_sin(positions[None], cfg.view(kind))  # [1, S, rope/2]
             for kind in dict.fromkeys(cfg.kinds)}
    capacity = cache.capacity
    kb = _key_block(capacity)
    # Blocks of the capacity that hold a written position; static where
    # the capacity is one block.
    n_blocks = 1 if kb == capacity else (start + s + kb - 1) // kb
    z = jnp.zeros((), jnp.int32)
    k = {name: list(bufs) for name, bufs in cache.k.items()}
    v = {name: list(bufs) for name, bufs in cache.v.items()}
    window, ring = cfg.sliding_window, cfg.ring_rows
    if cfg.sliding_layers:
        # A row's last ``ring`` real tokens land at their position mod
        # ring; padding, and a token that ``ring`` later real ones of this
        # chunk would overwrite, are dropped (index = ring).  Counted in
        # real tokens, not slots: a padded bucket may be longer than the
        # ring.
        real = valid.astype(jnp.int32)
        later = real.sum(-1, keepdims=True) - jnp.cumsum(real, axis=-1)
        fresh = valid & (later < ring)
        ring_at = jnp.where(fresh, positions[None, :] % ring, ring)  # [B, S]
    counts, dsa = jnp.zeros((3,), jnp.int32), jnp.zeros((2,), jnp.int32)
    for (kind, i), lp in zip(_layer_plan(cfg), params["layers"]):
        kc = cfg.view(kind)
        cos, sin = ropes[kind]
        xn = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        q_nope, q_rope, cq = _mla_q(xn, lp, cos, sin, kc)
        kr, c = _mla_kv(xn, lp, cos, sin, kc)
        if kind == FULL:
            put = lambda buf, new: lax.dynamic_update_slice(
                buf, new.astype(buf.dtype), (z, start, z))
            with jax.named_scope("kv_commit"):
                k["rope"][i] = put(k["rope"][i], _to_lanes(kr))
                v["latent"][i] = put(v["latent"][i], c)
            kept = None
            if kc.indexed:
                qi, ki, wi = _index_qkw(xn, cq, lp, cos, sin, kc)
                with jax.named_scope("kv_commit"):
                    k["index"][i] = put(k["index"][i], ki)
                if capacity > kc.index_topk:
                    kept, picked = _dsa_select(qi, wi, k["index"][i], positions,
                                               valid, n_blocks, kb, kc)
                else:
                    picked = _dsa_all_kept(positions[None, :], valid)
                dsa = dsa + picked

            sees = (jnp.arange(capacity)[None, :] <= positions[:, None])[None]
            if kept is not None:
                sees = sees & kept
            ctx = _attn_blocks(q_nope, q_rope, k["rope"][i], v["latent"][i],
                               sees, start + s, lp, kc)
        else:
            # The ring's rows of the window - 1 positions before the
            # chunk, then the chunk's own.
            before = start - (window - 1) + jnp.arange(window - 1)
            take = lambda bufs: jnp.take(bufs[i], before % ring, axis=1)
            keys_kr = jnp.concatenate(
                [take(k["ring_rope"]).astype(kr.dtype), _to_lanes(kr)], axis=1)
            keys_c = jnp.concatenate(
                [take(v["ring_latent"]).astype(c.dtype), c], axis=1)
            key_pos = jnp.concatenate([before, positions])
            kp, qp = key_pos[None, :], positions[:, None]
            sees = ((kp >= 0) & (kp <= qp) & (qp - kp < window))[None]
            ctx = _attn_blocks(q_nope, q_rope, keys_kr, keys_c, sees,
                               window - 1 + s, lp, kc)
            # The ring takes the chunk's rows when the layer has read it.
            rows = jnp.arange(b)[:, None]
            with jax.named_scope("kv_commit"):
                k["ring_rope"][i] = k["ring_rope"][i].at[rows, ring_at].set(
                    _to_lanes(kr).astype(k["ring_rope"][i].dtype), mode="drop")
                v["ring_latent"][i] = v["ring_latent"][i].at[rows, ring_at].set(
                    c.astype(v["ring_latent"][i].dtype), mode="drop")
        x, layer_counts = _ffn(_attn_out(x, ctx, xn, lp, kc), lp, valid, cfg)
        counts = counts + layer_counts
    counts = jnp.concatenate([counts, dsa])
    done = lambda bufs: {name: tuple(layers) for name, layers in bufs.items()}
    return _head(params, x, cfg), KVCache(done(k), done(v), start + s), counts


def _dsa_select(qi, wi, index_buf, positions, valid, n_blocks, kb, cfg):
    """Which cached positions each query keeps, bool [B,S,T]: the index
    scores of every written position at or before the query's own, a key
    block at a time, then the query's ``index_topk`` largest (all of
    them where it has fewer).  Beside it int32 [2]: the scores computed
    and the positions kept, counted from those tensors over the
    ``valid`` [B,S] queries."""
    b, s = qi.shape[:2]
    t = index_buf.shape[1]
    z = jnp.zeros((), jnp.int32)
    with jax.named_scope("layer.dsa_index"):
        def step(j, scores):
            lo = j * kb
            ki = _layer_rows(index_buf, lo, kb)
            sees = (lo + jnp.arange(kb))[None, :] <= positions[:, None]
            blk = jnp.where(sees[None], _index_scores(qi, wi, ki), -jnp.inf)
            return lax.dynamic_update_slice(scores, blk, (z, z, lo))

        scores = lax.fori_loop(
            0, n_blocks, step, jnp.full((b, s, t), -jnp.inf, jnp.float32))
    with jax.named_scope("layer.dsa_select"):
        kept = _top_mask(scores, cfg.index_topk)
        row = valid[..., None]
        return kept, jnp.stack(
            [jnp.sum((scores > -jnp.inf) & row), jnp.sum(kept & row)]
        ).astype(jnp.int32)


def prefill(params, input_ids, cfg, dtype=jnp.bfloat16):
    cache = KVCache.create(cfg, input_ids.shape[0], dtype)
    return forward(params, input_ids, cache, cfg, dtype)


def greedy_scan(forward, cache_of, params, prompt_ids, num_new_tokens, cfg, dtype):
    """Greedy generation with a scanned decode loop over a family's
    ``forward`` and scratch ``cache_of(cfg, batch, dtype)``, the cache
    sized to what this call can reach (this family's and
    ``models/gdn_moe.py``'s ``/infer`` path)."""
    total = prompt_ids.shape[1] + num_new_tokens
    if total > cfg.max_seq:
        raise ValueError(
            f"prompt ({prompt_ids.shape[1]}) + new tokens ({num_new_tokens}) "
            f"= {total} exceeds KV-cache capacity max_seq={cfg.max_seq}"
        )
    cfg = dataclasses.replace(cfg, max_seq=min(cfg.max_seq, -(-total // 8) * 8))
    cache = cache_of(cfg, prompt_ids.shape[0], dtype)
    logits, cache, _ = forward(params, prompt_ids, cache, cfg, dtype)
    next_tok = jnp.argmax(logits[:, -1:, :], axis=-1)

    def body(carry, _):
        tok, cache = carry
        logits, cache, _ = forward(params, tok, cache, cfg, dtype)
        return (jnp.argmax(logits[:, -1:, :], axis=-1), cache), tok

    _, toks = lax.scan(body, (next_tok, cache), None, length=num_new_tokens)
    return jnp.moveaxis(toks[..., 0], 0, 1)


def generate_greedy(
    params: dict,
    prompt_ids: jax.Array,
    num_new_tokens: int,
    cfg: MlaMoeConfig,
    dtype=jnp.bfloat16,
) -> jax.Array:
    """Greedy generation with a scanned decode loop (the ``/infer``
    path)."""
    return greedy_scan(forward, KVCache.create, params, prompt_ids,
                       num_new_tokens, cfg, dtype)


# ---------------------------------------------------------------------------
# Continuous batching (per-row positions)
# ---------------------------------------------------------------------------


def _dsa_pick(qi, wi, ki_new, ki_win, before, live, cfg):
    """A decode step's selection: the index scores of the window's
    positions before each row's own (``before`` bool [B,W]) and of the
    in-flight one, the ``index_topk`` largest kept.  Returns their window
    indices [B,k] (int32, clipped into the window), an additive mask
    [B,1,k] for those that are no real pick, the in-flight position's
    [B,1,1], and int32 [2]: the scores computed and the picks that are
    real, over the ``live`` [B] rows."""
    w = ki_win.shape[1]
    with jax.named_scope("layer.dsa_index"):
        past = jnp.where(before, _index_scores(qi, wi, ki_win)[:, 0], -jnp.inf)
        own = _index_scores(qi, wi, ki_new)[:, 0]  # [B, 1]
    with jax.named_scope("layer.dsa_select"):
        vals, idx = lax.top_k(jnp.concatenate([past, own], axis=-1), cfg.index_topk)
        real = vals > -jnp.inf
        own_kept = jnp.any(real & (idx == w), axis=-1)
        bias = jnp.where(real & (idx < w), 0.0, -1e9).astype(jnp.float32)
        new_bias = jnp.where(own_kept, 0.0, -1e9).astype(jnp.float32)
        row = live[:, None]
        picked = jnp.stack(
            [jnp.sum(before & row) + jnp.sum(live), jnp.sum(real & row)]
        ).astype(jnp.int32)
        return (jnp.minimum(idx, w - 1), bias[:, None],
                new_bias[:, None, None], picked)


def decode_ragged(
    params: dict,
    token_ids: jax.Array,
    cache: RaggedKVCache,
    cfg: MlaMoeConfig,
    active: jax.Array | None = None,
    dtype=jnp.bfloat16,
    window: int | None = None,
):
    """One decode step where every batch row is at its OWN position
    (``llama.decode_ragged``'s contract: strict mask over the static
    ``window``, the current position attended in flight, every layer's new
    row committed by one drop-scatter a buffer after the loop, inactive
    rows neither written nor advanced).  Inactive rows are not routed
    either.  An indexed layer scores the window's index keys and, where
    the window holds more than ``index_topk`` positions, gathers the kept
    rows alone; a sliding layer reads its ring, each row's position told
    from the row's length.
    Returns ``(logits [B,1,vocab] float32, cache, counts)`` (as
    ``forward``'s)."""
    b, s = token_ids.shape
    if s != 1:
        raise ValueError(f"decode_ragged is single-token: got chunk of {s}")
    lengths = cache.lengths
    live = jnp.ones((b,), bool) if active is None else active
    x = _embed(params, token_ids, dtype)
    ropes = {kind: rope_cos_sin(lengths[:, None], cfg.view(kind))  # [B, 1, rope/2]
             for kind in dict.fromkeys(cfg.kinds)}
    window = _attended_window(cache, window)
    before = jnp.arange(window)[None, :] < lengths[:, None]  # [B, W]
    mask_bias = jnp.where(before, 0.0, -1e9).astype(jnp.float32)[:, None]
    if cfg.sliding_layers:
        # Ring row j holds the last position before the row's own that is
        # j mod ring; it is attended while inside the window.
        ring = cfg.ring_rows
        last = lengths[:, None] - 1
        held = last - (last - jnp.arange(ring)[None, :]) % ring  # [B, ring]
        in_window = (held >= 0) & (lengths[:, None] - held < cfg.sliding_window)
        ring_bias = jnp.where(in_window, 0.0, -1e9).astype(jnp.float32)[:, None]

    news = {name: [] for name in (*cache.k, *cache.v)}
    counts, dsa = jnp.zeros((3,), jnp.int32), jnp.zeros((2,), jnp.int32)
    for (kind, i), lp in zip(_layer_plan(cfg), params["layers"]):
        kc = cfg.view(kind)
        cos, sin = ropes[kind]
        xn = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        q_nope, q_rope, cq = _mla_q(xn, lp, cos, sin, kc)
        kr, c = _mla_kv(xn, lp, cos, sin, kc)
        if kind == FULL:
            ck, cv = cache.layer_window(i, window)
            bias, new_bias = mask_bias, None
            if kc.indexed:
                qi, ki, wi = _index_qkw(xn, cq, lp, cos, sin, kc)
                news["index"].append(ki)
                if window + 1 > kc.index_topk:
                    ki_win = _layer_rows(cache.k["index"][i], 0, window)
                    idx, bias, new_bias, picked = _dsa_pick(
                        qi, wi, ki, ki_win, before, live, kc)
                    ck = jnp.take_along_axis(ck, idx[..., None], axis=1)
                    cv = jnp.take_along_axis(cv, idx[..., None], axis=1)
                else:
                    picked = _dsa_all_kept(lengths, live)
                dsa = dsa + picked
            kr = _to_lanes(kr)
            ctx = _attn_absorbed(q_nope, q_rope, kr, c, ck, cv, bias, lp, kc,
                                 new_bias)
            news["rope"].append(kr)
            news["latent"].append(c)
        else:
            ck, cv = cache.k["ring_rope"][i], cache.v["ring_latent"][i]  # the whole ring
            kr = _to_lanes(kr)
            ctx = _attn_absorbed(q_nope, q_rope, kr, c, ck, cv, ring_bias, lp, kc)
            news["ring_rope"].append(kr)
            news["ring_latent"].append(c)
        x, layer_counts = _ffn(_attn_out(x, ctx, xn, lp, kc), lp, live[:, None], cfg)
        counts = counts + layer_counts
    logits = _head(params, x, cfg)

    def commit(name, bufs):
        rows = bufs[0].shape[1]
        at = lengths % rows if name.startswith("ring") else lengths
        at = jnp.where(live, at, rows)
        with jax.named_scope("kv_commit"):
            return tuple(_commit_row(buf, new[:, 0], at)
                         for buf, new in zip(bufs, news[name]))

    counts = jnp.concatenate([counts, dsa])
    return (
        logits,
        RaggedKVCache(
            {name: commit(name, buf) for name, buf in cache.k.items()},
            {name: commit(name, buf) for name, buf in cache.v.items()},
            lengths + live.astype(jnp.int32),
        ),
        counts,
    )


@jax.named_scope("kv_commit")
def insert_sequence(
    cache: RaggedKVCache, seq: KVCache, slot: jax.Array, length: jax.Array
) -> RaggedKVCache:
    """Install a prefilled single-sequence scratch into batch row ``slot``
    (``llama.insert_sequence`` for this cache), every buffer of every row
    kind (a ring as it stands: the scratch wrote it at ``position mod
    ring`` too): ``length`` is the real token count; padding behind it is
    overwritten by decode before it can be attended."""
    slot = jnp.asarray(slot, jnp.int32)
    z = jnp.zeros((), jnp.int32)
    at = (slot, z, z)

    def put(kinds, rows):
        return {name: tuple(
            lax.dynamic_update_slice(buf, row.astype(buf.dtype), at)
            for buf, row in zip(bufs, rows[name])) for name, bufs in kinds.items()}

    return RaggedKVCache(
        put(cache.k, seq.k), put(cache.v, seq.v),
        cache.lengths.at[slot].set(jnp.asarray(length, jnp.int32)),
    )
