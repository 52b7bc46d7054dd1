"""Decoder of Gated DeltaNet layers with a gated full-attention layer
every few, and a sparse-expert FFN in every layer.

The Qwen3-Next block: pre-norm with zero-centred RMSNorms (``rms(x) (1 +
w)``); ``full_attention_interval - 1`` linear-attention layers (Gated
DeltaNet: a short causal convolution, then the gated delta rule on a
recurrent state a value head) for every layer of gated softmax
attention (GQA, per-head q/k norms, rotary on part of the head, a
sigmoid gate on the heads' outputs); every FFN routed experts (softmax
scores, top-k renormalised) beside one sigmoid-gated shared expert;
untied head.  The plain float32 reference this is tested against is
``benchmarks/references/qwen3_next_decoder.py``.

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
  and every step.  Every buffer is donated through every program; the
  scratch sequence carries the state from chunk to chunk and the insert
  copies it as it stands.
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
- The expert layer is ``mla_moe.moe_ffn``, shared with that family: one
  grouped matmul, one share arithmetic (``n_local_experts`` from
  ``local_expert_start`` of a router over all ``n_routed_experts``), one
  set of counts.  This family asks it for softmax scores, no selection
  bias and a gate on the shared expert.
- Layers are a list of per-layer trees and the loop is unrolled, as in
  ``mla_moe.py`` and for its reason (the grouped matmul's operand must
  be a whole buffer).
- Not here (``UNSUPPORTED``, refused typed): what ``mla_moe.py`` lacks,
  and, for a reason of its own, every mechanism that takes cached state
  to be rows that are a pure function of a token prefix: the radix
  prefix cache and preemption, speculative rollback, KV transfer.  The
  multi-token-prediction module is not loaded.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from .common import rms_norm
from .llama import _attended_window, _embed, _qmatmul
from .mla_moe import (
    _commit_row,
    _key_block,
    _layer_plan,
    _layer_rows,
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
UNSUPPORTED = {
    "quantize": "int8 weights or an int8 cache",
    "mesh": "sharding over more than one chip (no expert, tensor, data or "
            "sequence-parallel path, no ring prefill)",
    "speculative": "speculative decoding (" + _STATE + "truncating a row's "
                   "length does not undo the rejected tokens' updates)",
    "prefix_cache": "the radix prefix cache and preemption (" + _STATE
                    + "reuse and resume need a snapshot of it at a chunk "
                    "boundary, not a copy of rows)",
    "prefill_batch": "packed multi-admission prefill",
    "decode_steps": "the fused multi-step decode program",
    "unified_step": "the unified super-step program",
    "kv_transfer": "KV transfer between prefill and decode replicas ("
                   + _STATE + "the wire carries rows)",
}

LINEAR, FULL = "linear_attention", "full_attention"
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
    # What ``mla_moe.route`` reads, fixed for this family.
    scoring_func: str = "softmax"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0

    def __post_init__(self):
        for key, only, what in (
            ("scoring_func", "softmax", "sigmoid router scores"),
            ("norm_topk_prob", True, "un-normalised routing weights"),
            ("routed_scaling_factor", 1.0, "a scaling of the routing weights"),
        ):
            if getattr(self, key) != only:
                raise ValueError(
                    f"{key}={getattr(self, key)!r}: {what} is not "
                    f"implemented for this family (only {key}={only!r})"
                )
        if not self.full_layers:
            raise ValueError(
                f"num_layers {self.num_layers} holds no full-attention layer "
                f"at full_attention_interval {self.full_attention_interval}: "
                "the cache's capacity is the full layers' row"
            )
        if not 1 <= self.num_experts_per_tok <= self.n_routed_experts:
            raise ValueError(
                f"num_experts_per_tok {self.num_experts_per_tok} outside "
                f"[1, n_routed_experts {self.n_routed_experts}]"
            )
        if self.num_heads % self.num_kv_heads or (
            self.linear_num_value_heads % self.linear_num_key_heads
        ):
            raise ValueError(
                "query heads must be a multiple of KV heads "
                f"({self.num_heads} / {self.num_kv_heads}) and value heads "
                f"of key heads ({self.linear_num_value_heads} / "
                f"{self.linear_num_key_heads})"
            )
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(
                f"partial_rotary_factor {self.partial_rotary_factor} of "
                f"head_dim {self.head_dim} must give an even number of "
                "rotated dims: RoPE rotates pairs"
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
        return self.num_layers

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


def _mixer_params(cfg: GdnMoeConfig, kind: str) -> int:
    """Weight-matrix elements of one ``kind`` layer's token mixer (the
    convolution's kernel among them; norms, ``A_log``, ``dt_bias`` not)."""
    h = cfg.hidden_size
    if kind == FULL:
        q = cfg.num_heads * cfg.head_dim
        return h * 2 * q + 2 * h * cfg.kv_width + q * h
    return (h * (2 * cfg.key_dim + 2 * cfg.value_dim)
            + h * 2 * cfg.linear_num_value_heads
            + cfg.linear_conv_kernel_dim * cfg.conv_dim
            + cfg.value_dim * h)


def param_counts(cfg: GdnMoeConfig) -> tuple[int, int]:
    """``(active, total)`` weight-matrix elements: what one token
    multiplies through in a forward pass (its chosen routed experts as
    far as they are held here, the shared one and its gate, the router,
    the head) and what the tree holds (embedding included).  The cost
    model's two terms."""
    h = cfg.hidden_size
    mixers = sum(_mixer_params(cfg, kind) for kind in cfg.kinds)
    expert = 3 * h * cfg.moe_intermediate_size
    shared = 3 * h * cfg.shared_expert_intermediate_size + h
    router = h * cfg.n_routed_experts
    head = h * cfg.vocab_size
    chosen_here = (cfg.num_experts_per_tok * cfg.local_experts
                   // cfg.n_routed_experts)
    active = mixers + head + cfg.num_layers * (
        router + shared + expert * chosen_here)
    total = mixers + 2 * head + cfg.num_layers * (
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


def kv_row_bytes(cfg: GdnMoeConfig, dtype_bytes: int = 2) -> int:
    """Bytes one cache row (a slot at full ``max_seq``) holds: the full
    layers' K and V a position, and the linear layers' state, a constant
    a slot (``state_row_bytes``)."""
    rows = len(cfg.full_layers) * cfg.max_seq * 2 * cfg.kv_width * dtype_bytes
    return rows + state_row_bytes(cfg, dtype_bytes)


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

    k: dict  # "key" [B,T,kv] a full layer, "conv" [B,kernel-1,channels] a linear one
    v: dict  # "value" [B,T,kv] a full layer, "state" f32 [B,Hv,dk,dv] a linear one
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
    """N(0, 0.02) matrices and zero-centred norm weights (so ``1 + w`` is
    exercised), the gated norm's weight 1, ``A_log`` from
    ``decay_log_a`` and ``dt_bias`` 0 (both float32 whatever ``dtype``).
    ``layers`` is a list of per-layer trees: a linear layer's ``qkvz``,
    ``ba``, ``conv`` ``[kernel, channels]``, ``A_log``, ``dt_bias``,
    ``gdn_norm`` and ``o``; a full layer's ``q`` (query and gate a head),
    ``k``, ``v``, ``q_norm``, ``k_norm`` and ``o``; every layer's
    ``router``, ``experts`` (those held here, stacked on an expert axis),
    the shared expert's three matrices and its gate ``shared_expert_gate``."""
    h = cfg.hidden_size
    e, held = cfg.n_routed_experts, cfg.local_experts
    im, ims = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
    keys = iter(jax.random.split(key, 3 + 16 * cfg.num_layers))

    def normal(shape, dt=dtype):
        return (0.02 * jax.random.normal(next(keys), shape, jnp.float32)).astype(dt)

    def layer(kind):
        lp = {"attn_norm": normal((h,)), "ffn_norm": normal((h,))}
        if kind == FULL:
            q = cfg.num_heads * cfg.head_dim
            lp.update(
                q=normal((h, 2 * q)), k=normal((h, cfg.kv_width)),
                v=normal((h, cfg.kv_width)), q_norm=normal((cfg.head_dim,)),
                k_norm=normal((cfg.head_dim,)), o=normal((q, h)))
        else:
            lp.update(
                qkvz=normal((h, 2 * cfg.key_dim + 2 * cfg.value_dim)),
                ba=normal((h, 2 * cfg.linear_num_value_heads)),
                conv=normal((cfg.linear_conv_kernel_dim, cfg.conv_dim)),
                A_log=decay_log_a(cfg),
                dt_bias=jnp.zeros((cfg.linear_num_value_heads,), jnp.float32),
                gdn_norm=jnp.ones((cfg.linear_value_head_dim,), dtype),
                o=normal((cfg.value_dim, h)))
        lp.update(
            router=normal((h, e)),
            experts={"gate": normal((held, h, im)), "up": normal((held, h, im)),
                     "down": normal((held, im, h))},
            shared_gate=normal((h, ims)), shared_up=normal((h, ims)),
            shared_down=normal((ims, h)), shared_expert_gate=normal((h, 1)))
        return lp

    return {
        "embed": normal((cfg.vocab_size, h)),
        "layers": [layer(kind) for kind in cfg.kinds],
        "final_norm": normal((h,)),
        "lm_head": normal((h, cfg.vocab_size)),
    }


# ---------------------------------------------------------------------------
# Layer pieces
# ---------------------------------------------------------------------------


def _znorm(x, w, eps):
    """The zero-centred RMSNorm: ``rms(x) (1 + w)``."""
    return rms_norm(x, 1.0 + w.astype(jnp.float32), eps)


def _head(params, x, cfg):
    """Final norm and lm_head: logits in float32."""
    with jax.named_scope("head"):
        return _qmatmul(_znorm(x, params["final_norm"], cfg.rms_eps),
                        params["lm_head"])


def rope_cos_sin(positions: jax.Array, cfg: GdnMoeConfig):
    """cos/sin ``[..., rotary/2]`` (float32) for ``positions`` ``[...]``."""
    d = cfg.rotary_dim
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
    """Normed ``xn`` [B,S,H] of a full layer -> rotated ``q``
    [B,S,KV,R,D] (R query heads a KV head), the gates [B,S,NH*D], and the
    position's cache rows ``k`` / ``v`` [B,S,KV*D] (``k`` normed and
    rotated); ``cos``/``sin`` [B or 1, S, rotary/2]."""
    b, s, _h = xn.shape
    nh, nkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    with jax.named_scope("layer.attn_qkv"):
        qg = _qmatmul(xn, lp["q"]).astype(xn.dtype).reshape(b, s, nh, 2 * d)
        q, gate = qg[..., :d], qg[..., d:]
        k = _qmatmul(xn, lp["k"]).astype(xn.dtype).reshape(b, s, nkv, d)
        v = _qmatmul(xn, lp["v"]).astype(xn.dtype)
        q = apply_rope(_znorm(q, lp["q_norm"], cfg.rms_eps),
                       cos[:, :, None], sin[:, :, None])
        k = apply_rope(_znorm(k, lp["k_norm"], cfg.rms_eps),
                       cos[:, :, None], sin[:, :, None])
        return (q.reshape(b, s, nkv, nh // nkv, d), gate.reshape(b, s, nh * d),
                k.reshape(b, s, nkv * d), v)


def _gqa_blocks(q, keys, values, positions, written):
    """Causal attention of ``S`` queries ``q`` [B,S,KV,R,D] at
    ``positions`` [S] over the first ``written`` of the ``T`` cached
    positions (a traced scalar: no query sees a later one), ``keys`` /
    ``values`` [B,T,KV*D] as cached, a key block at a time with a
    running maximum and sum (one block, a plain softmax, up to
    ``mla_moe.ONE_PASS`` positions).  Returns ctx [B,S,NH*D]."""
    b, s, nkv, r, d = q.shape
    dt = q.dtype
    t = keys.shape[1]
    kb = _key_block(t)
    scale = 1.0 / math.sqrt(d)

    def scores_of(j):
        lo = j * kb
        k = _layer_rows(keys, lo, kb).astype(dt).reshape(b, kb, nkv, d)
        v = _layer_rows(values, lo, kb).astype(dt).reshape(b, kb, nkv, d)
        sc = jnp.einsum("bqgrd,bkgd->bgrqk", q, k,
                        preferred_element_type=jnp.float32) * scale
        see = (lo + jnp.arange(kb))[None, :] <= positions[:, None]  # [S, kb]
        return sc, see, v

    with jax.named_scope("layer.attn_core"):
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


def _attn_out(x, ctx, gate, lp):
    """The heads' outputs ``ctx`` [B,S,NH*D], each number gated by the
    sigmoid of the query projection's other half, through ``W_o`` onto
    the residual."""
    with jax.named_scope("layer.attn_gate"):
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
    """A layer's expert FFN with its residual (``mla_moe.moe_ffn``);
    ``valid`` bool [B, S] marks the real tokens.  Returns ``(x, counts)``."""
    b, s, h = x.shape
    xn = _znorm(x, lp["ffn_norm"], cfg.rms_eps).reshape(b * s, h)
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
    (embedded as id 0, not routed, folded into no state).  A full layer
    writes its rows, then attends the blocks of its cache written so far;
    a linear layer reads its convolution tail and its state, runs the
    chunked rule, and writes both back.
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
    cos, sin = rope_cos_sin(positions[None], cfg)  # [1, S, rotary/2]
    z = jnp.zeros((), jnp.int32)
    k = {name: list(bufs) for name, bufs in cache.k.items()}
    v = {name: list(bufs) for name, bufs in cache.v.items()}
    counts = jnp.zeros((3,), jnp.int32)
    put = lambda buf, new: lax.dynamic_update_slice(
        buf, new.astype(buf.dtype), (z, start, z))
    for (kind, i), lp in zip(_layer_plan(cfg), params["layers"]):
        xn = _znorm(x, lp["attn_norm"], cfg.rms_eps)
        if kind == FULL:
            q, gate, k_new, v_new = _attn_qkv(xn, lp, cos, sin, cfg)
            with jax.named_scope("kv_commit"):
                k["key"][i] = put(k["key"][i], k_new)
                v["value"][i] = put(v["value"][i], v_new)
            ctx = _gqa_blocks(q, k["key"][i], v["value"][i], positions, start + s)
            x = _attn_out(x, ctx, gate, lp)
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
    linear layer runs the recurrence once on its state and shifts its
    convolution tail by the token.  An inactive row is neither written
    nor advanced, not routed, and keeps its state and its tail.
    Returns ``(logits [B,1,vocab] float32, cache, counts)`` (as
    ``forward``'s)."""
    b, s = token_ids.shape
    if s != 1:
        raise ValueError(f"decode_ragged is single-token: got chunk of {s}")
    lengths = cache.lengths
    live = jnp.ones((b,), bool) if active is None else active
    x = _embed(params, token_ids, dtype)
    cos, sin = rope_cos_sin(lengths[:, None], cfg)  # [B, 1, rotary/2]
    window = _attended_window(cache, window)
    before = jnp.arange(window)[None, :] < lengths[:, None]  # [B, W]
    mask_bias = jnp.where(before, 0.0, -1e9).astype(jnp.float32)[:, None]
    real = live[:, None]
    news = {name: [] for name in (*cache.k, *cache.v)}
    counts = jnp.zeros((3,), jnp.int32)
    for (kind, i), lp in zip(_layer_plan(cfg), params["layers"]):
        xn = _znorm(x, lp["attn_norm"], cfg.rms_eps)
        if kind == FULL:
            q, gate, k_new, v_new = _attn_qkv(xn, lp, cos, sin, cfg)
            ck = _layer_rows(cache.k["key"][i], 0, window)
            cv = _layer_rows(cache.v["value"][i], 0, window)
            ctx = _gqa_step(q, k_new, v_new, ck, cv, mask_bias)
            x = _attn_out(x, ctx, gate, lp)
            news["key"].append(k_new)
            news["value"].append(v_new)
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

    at = jnp.where(live, lengths, cache.capacity)
    with jax.named_scope("kv_commit"):
        commit = lambda name, bufs: tuple(
            _commit_row(buf, new[:, 0], at) for buf, new in zip(bufs, news[name]))
        keys, values = commit("key", cache.k["key"]), commit("value", cache.v["value"])
    linear = len(cfg.linear_layers)
    counts = jnp.concatenate(
        [counts, jnp.stack([live.sum() * linear] * 2).astype(jnp.int32)])
    return (
        logits,
        RaggedKVCache(
            {"key": keys, "conv": tuple(news["conv"])},
            {"value": values, "state": tuple(news["state"])},
            lengths + live.astype(jnp.int32),
        ),
        counts,
    )


@jax.named_scope("kv_commit")
def insert_sequence(
    cache: RaggedKVCache, seq: KVCache, slot: jax.Array, length: jax.Array
) -> RaggedKVCache:
    """Install a prefilled single-sequence scratch into batch row ``slot``
    (``llama.insert_sequence`` for this cache): a full layer's rows, and a
    linear layer's state and tail as they stand.  ``length`` is the real
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
