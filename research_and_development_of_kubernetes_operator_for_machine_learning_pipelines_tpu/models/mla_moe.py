"""Decoder with latent (MLA) attention and a sparse-expert FFN.

The DeepSeek-V3 block: pre-RMSNorm; attention through a low-rank query
and a compressed key/value latent (one RoPE key head shared by all query
heads); ``first_k_dense_replace`` leading SwiGLU layers, then layers of
routed experts (sigmoid scores, a selection-only bias, top-k,
renormalised and scaled weights) beside shared experts; untied head.
The plain float32 reference this is tested against is
``benchmarks/references/mla_moe_decoder.py``.

What the serving engine needs of a causal-LM family is here under the
names ``models/llama.py`` gives them, so ``server/generation.py`` reaches
either through one handle: ``KVCache`` / ``RaggedKVCache`` (the donated
pair stays ``(k, v)``: ``k`` the RoPE key ``[L, B, T, 1, rope]``, ``v``
the normalised latent ``[L, B, T, 1, kv_lora_rank]``, position-major),
``forward``, ``prefill``, ``decode_ragged``, ``insert_sequence``,
``generate_greedy``.  ``forward`` and ``decode_ragged`` return one value
more than llama's: int32 ``[2]`` (the ``counts`` below), the (layer, expert)
pairs that got at least one real token and the (layer, expert, row tile)
visits the grouped matmuls made, which the engine turns into
``tpumlops_moe_expert_activations_total`` and
``tpumlops_moe_row_tile_visits_total``.

Design decisions:

- The cache holds what the published model caches: the latent after its
  norm and the RoPE key after rotation, 576 numbers a position a layer.
  Prefill expands ``[k_nope | v] = c W_kvb`` over the attended positions;
  decode absorbs ``W_kvb`` into the query and the context (``q_nope W_uk``
  scores the latent itself, ``P c`` is expanded by ``W_uv`` after): the
  same mathematics, and a step reads 576 numbers a position, not 8192.
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
- Not here (``UNSUPPORTED``, refused typed): int8 weights or cache,
  a mesh beyond one chip, verify / multi-step / packed / super-step
  programs, the multi-token-prediction module (not loaded: it adds no
  term to the next-token logits).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.grouped_matmul import grouped_matmul, row_tile, row_tile_schedule
from .common import rms_norm
from .llama import _attended_window, _commit_rows, _embed, _head, _layer_window, _qmatmul


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

    def __post_init__(self):
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
        if self.qk_rope_head_dim % 2:
            raise ValueError(
                f"qk_rope_head_dim {self.qk_rope_head_dim} must be even: "
                "RoPE rotates pairs"
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


def param_counts(cfg: MlaMoeConfig) -> tuple[int, int]:
    """``(active, total)`` weight-matrix elements: what one token
    multiplies through in a forward pass (the chosen routed experts, the
    shared ones, the router, the head) and what the tree holds (embedding
    included).  The cost model's two terms."""
    h, nh = cfg.hidden_size, cfg.num_heads
    attn = (
        h * cfg.q_lora_rank
        + cfg.q_lora_rank * nh * cfg.qk_head_dim
        + h * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
        + cfg.kv_lora_rank * nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)
        + nh * cfg.v_head_dim * h
    )
    expert = 3 * h * cfg.moe_intermediate_size
    router = h * cfg.n_routed_experts
    dense = cfg.num_dense_layers * (attn + 3 * h * cfg.intermediate_size)
    head = h * cfg.vocab_size
    active = dense + head + cfg.num_moe_layers * (
        attn + router + expert * (cfg.num_experts_per_tok + cfg.n_shared_experts)
    )
    total = dense + 2 * head + cfg.num_moe_layers * (
        attn + router + expert * (cfg.n_routed_experts + cfg.n_shared_experts)
    )
    return active, total


def routed_assignments(cfg: MlaMoeConfig, tokens: int) -> int:
    """(token, expert) pairs ``tokens`` real tokens make in one forward
    pass: what ``tpumlops_moe_assignments_total`` counts."""
    return int(tokens) * cfg.num_experts_per_tok * cfg.num_moe_layers


def moe_row_tile(cfg: MlaMoeConfig, tokens: int) -> int:
    """Rows a visit of the grouped matmuls multiplies in a program call
    over ``tokens`` token rows (padding included: the shape is static)."""
    return row_tile(int(tokens) * cfg.num_experts_per_tok, cfg.n_routed_experts)


def kv_row_bytes(cfg: MlaMoeConfig, dtype_bytes: int = 2) -> int:
    """Bytes one cache row (a slot at full ``max_seq``) holds: one
    normalised latent and one RoPE key a position a layer, whatever the
    head count."""
    width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    return cfg.num_layers * cfg.max_seq * width * dtype_bytes


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
    come from the ACTIVE parameters (the chosen routed experts, the
    shared ones, the router, the head); bytes from the weights every call
    streams plus the distinct routed experts ``tokens`` tokens are
    expected to reach under uniform routing, ``E (1 - (1 - k/E)^tokens)``
    a layer, plus the latent cache rows read and written."""

    active_params: int
    total_params: int
    unrouted_bytes: int  # everything a call streams whatever it routes
    expert_bytes: int  # one routed expert's three matrices
    moe_layers: int
    n_routed_experts: int
    experts_per_tok: int
    num_layers: int
    num_heads: int
    score_width: int  # qk_nope + qk_rope + v: flops a (query, key) pair
    cache_row_bytes: float  # one position, one layer
    tp: int = 1  # no mesh exists for this family

    def _routed_bytes(self, tokens: float) -> float:
        e = self.n_routed_experts
        hit = e * (1.0 - (1.0 - self.experts_per_tok / e) ** max(0.0, tokens))
        return self.moe_layers * hit * self.expert_bytes

    def _cost(self, tokens: float, attended: float) -> tuple[float, float]:
        flops = 2.0 * self.active_params * tokens
        flops += 2.0 * tokens * attended * self.num_layers * (
            self.num_heads * self.score_width
        )
        nbytes = self.unrouted_bytes + self._routed_bytes(tokens)
        return flops, nbytes

    def decode(self, rows: int, window: int, s: int = 1
               ) -> tuple[float, float]:
        flops, nbytes = self._cost(rows * s, window)
        nbytes += self.num_layers * self.cache_row_bytes * rows * (window + s)
        return flops, nbytes

    def prefill(self, rows: int, chunk: int, attended: float | None = None
                ) -> tuple[float, float]:
        if attended is None:
            attended = chunk / 2.0
        flops, nbytes = self._cost(rows * chunk, attended)
        nbytes += self.num_layers * self.cache_row_bytes * rows * (
            chunk + max(0.0, attended - chunk / 2.0)
        )
        return flops, nbytes


def cost_model(params: dict, cfg: MlaMoeConfig, dtype_bytes: int = 2) -> CostModel:
    active, total = param_counts(cfg)
    routed = _tree_bytes(routed_expert_leaves(params))
    return CostModel(
        active_params=active,
        total_params=total,
        unrouted_bytes=_tree_bytes(params) - routed,
        expert_bytes=routed // max(1, cfg.num_moe_layers * cfg.n_routed_experts),
        moe_layers=cfg.num_moe_layers,
        n_routed_experts=cfg.n_routed_experts,
        experts_per_tok=cfg.num_experts_per_tok,
        num_layers=cfg.num_layers,
        num_heads=cfg.num_heads,
        score_width=cfg.qk_head_dim + cfg.v_head_dim,
        cache_row_bytes=float(
            (cfg.kv_lora_rank + cfg.qk_rope_head_dim) * dtype_bytes
        ),
    )


class KVCache(NamedTuple):
    """The prefill scratch: ``k`` the RoPE key ``[L, B, T, 1, rope]``, ``v``
    the normalised latent ``[L, B, T, 1, kv_lora_rank]``, one scalar
    length shared by the batch (llama's ``KVCache`` at other widths)."""

    k: jax.Array
    v: jax.Array
    length: jax.Array

    @classmethod
    def create(cls, cfg: MlaMoeConfig, batch: int, dtype=jnp.bfloat16) -> "KVCache":
        lead = (cfg.num_layers, batch, cfg.max_seq, 1)
        return cls(
            k=jnp.zeros(lead + (cfg.qk_rope_head_dim,), dtype),
            v=jnp.zeros(lead + (cfg.kv_lora_rank,), dtype),
            length=jnp.zeros((), jnp.int32),
        )


class RaggedKVCache(NamedTuple):
    """The slot cache with per-row lengths, in the scratch's layout
    (llama's ``RaggedKVCache`` at other widths): the engine donates
    ``k`` and ``v`` through every program."""

    k: jax.Array  # [L, B, T, 1, rope]
    v: jax.Array  # [L, B, T, 1, kv_lora_rank]
    lengths: jax.Array  # int32 [B]

    @classmethod
    def create(
        cls, cfg: MlaMoeConfig, batch: int, dtype=jnp.bfloat16
    ) -> "RaggedKVCache":
        seq = KVCache.create(cfg, batch, dtype)
        return cls(seq.k, seq.v, jnp.zeros((batch,), jnp.int32))

    @property
    def capacity(self) -> int:
        return self.k.shape[2]

    def layer_window(self, layer, window: int):
        """Layer ``layer``'s first ``window`` positions of every slot:
        RoPE keys ``[B, window, rope]`` and latents ``[B, window, rank]``."""
        return (
            _layer_window(self.k, layer, window)[:, :, 0],
            _layer_window(self.v, layer, window)[:, :, 0],
        )


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init(key: jax.Array, cfg: MlaMoeConfig, dtype=jnp.float32) -> dict:
    """N(0, 0.02) matrices, norms 1, a small seeded router bias (float32
    whatever ``dtype``: it is added to float32 scores).  ``layers`` is a
    list of per-layer trees: the leading ones carry a SwiGLU (``gate``,
    ``up``, ``down``), the rest a router, ``experts`` stacked on an expert
    axis, and the shared experts."""
    h, nh, e = cfg.hidden_size, cfg.num_heads, cfg.n_routed_experts
    i, im = cfg.intermediate_size, cfg.moe_intermediate_size
    ims = im * cfg.n_shared_experts
    qr, kvr, rope = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    keys = iter(jax.random.split(key, 2 + 13 * cfg.num_layers))

    def normal(shape, dt=dtype):
        return (0.02 * jax.random.normal(next(keys), shape, jnp.float32)).astype(dt)

    def layer(l):
        lp = {
            "attn_norm": jnp.ones((h,), dtype),
            "q_a": normal((h, qr)),
            "q_norm": jnp.ones((qr,), dtype),
            "q_b": normal((qr, nh * cfg.qk_head_dim)),
            "kv_a": normal((h, kvr + rope)),
            "kv_norm": jnp.ones((kvr,), dtype),
            "kv_b": normal((kvr, nh * (cfg.qk_nope_head_dim + cfg.v_head_dim))),
            "o": normal((nh * cfg.v_head_dim, h)),
            "ffn_norm": jnp.ones((h,), dtype),
        }
        if l < cfg.num_dense_layers:
            lp.update(gate=normal((h, i)), up=normal((h, i)), down=normal((i, h)))
        else:
            lp.update(
                router=normal((h, e)),
                router_bias=normal((e,), jnp.float32),
                experts={
                    "gate": normal((e, h, im)),
                    "up": normal((e, h, im)),
                    "down": normal((e, im, h)),
                },
                shared_gate=normal((h, ims)),
                shared_up=normal((h, ims)),
                shared_down=normal((ims, h)),
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
# Layer pieces
# ---------------------------------------------------------------------------


def _mla_q(xn, lp, cos, sin, cfg):
    """Normed ``xn`` [B,S,H] -> ``q_nope`` [B,S,NH,nope], ``q_rope``
    [B,S,NH,rope] (rotated); ``cos``/``sin`` [B or 1, S, rope/2]."""
    b, s, _h = xn.shape
    with jax.named_scope("layer.mla_q"):
        cq = rms_norm(_qmatmul(xn, lp["q_a"]).astype(xn.dtype), lp["q_norm"], cfg.rms_eps)
        q = _qmatmul(cq, lp["q_b"]).astype(xn.dtype)
        q = q.reshape(b, s, cfg.num_heads, cfg.qk_head_dim)
        q_nope, q_rope = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
        return q_nope, apply_rope(q_rope, cos[:, :, None], sin[:, :, None])


def _mla_kv(xn, lp, cos, sin, cfg):
    """Normed ``xn`` [B,S,H] -> what the cache holds of these positions:
    the RoPE key ``kr`` [B,S,rope] and the normalised latent ``c``
    [B,S,rank]."""
    with jax.named_scope("layer.mla_kv"):
        ckr = _qmatmul(xn, lp["kv_a"]).astype(xn.dtype)
        c, kr = jnp.split(ckr, [cfg.kv_lora_rank], axis=-1)
        return apply_rope(kr, cos, sin), rms_norm(c, lp["kv_norm"], cfg.rms_eps)


def _kv_b(lp, cfg, dtype):
    """``W_kvb`` as ``[rank, NH, nope + v]``."""
    return lp["kv_b"].astype(dtype).reshape(
        cfg.kv_lora_rank, cfg.num_heads, cfg.qk_nope_head_dim + cfg.v_head_dim
    )


def _attn_expanded(q_nope, q_rope, kr_all, c_all, mask_bias, lp, cfg):
    """Attention over ``T`` cached positions with keys and values expanded
    from the latent: ``kr_all`` [B,T,rope], ``c_all`` [B,T,rank],
    ``mask_bias`` [1,1,S,T].  Returns ctx [B,S,NH*v]."""
    b, s = q_nope.shape[:2]
    dt = q_nope.dtype
    with jax.named_scope("layer.attn_core"):
        kv = jnp.einsum(
            "btc,cnd->btnd", c_all.astype(dt), _kv_b(lp, cfg, dt),
            preferred_element_type=jnp.float32,
        ).astype(dt)
        k_nope, v = jnp.split(kv, [cfg.qk_nope_head_dim], axis=-1)
        scores = jnp.einsum(
            "bqnd,bknd->bnqk", q_nope, k_nope, preferred_element_type=jnp.float32
        ) + jnp.einsum(
            "bqnd,bkd->bnqk", q_rope, kr_all.astype(dt),
            preferred_element_type=jnp.float32,
        )
        scores = scores / math.sqrt(cfg.qk_head_dim) + mask_bias
        probs = jax.nn.softmax(scores, axis=-1).astype(dt)
        ctx = jnp.einsum("bnqk,bknd->bqnd", probs, v)
        return ctx.reshape(b, s, cfg.num_heads * cfg.v_head_dim)


def _attn_absorbed(q_nope, q_rope, kr_new, c_new, ck, cv, mask_bias, lp, cfg):
    """Single-token attention with ``W_kvb`` absorbed and the cache
    read-only: ``ck`` [B,W,rope] / ``cv`` [B,W,rank] are the attended
    window, ``mask_bias`` [B,1,W] is STRICT (``key_pos < position``), and
    the current position is attended through the exact in-flight
    ``kr_new`` / ``c_new`` [B,1,*] (its cache row is written after the
    layer loop, as in ``llama._block_decode_deferred``)."""
    b = q_nope.shape[0]
    dt = q_nope.dtype
    with jax.named_scope("layer.attn_core"):
        w_uk, w_uv = jnp.split(_kv_b(lp, cfg, dt), [cfg.qk_nope_head_dim], axis=-1)
        q_lat = jnp.einsum(
            "bnd,cnd->bnc", q_nope[:, 0], w_uk, preferred_element_type=jnp.float32
        ).astype(dt)
        qr = q_rope[:, 0]

        def score(lat, kr):  # [B,K,rank], [B,K,rope] -> [B,NH,K]
            return jnp.einsum(
                "bnc,bkc->bnk", q_lat, lat.astype(dt),
                preferred_element_type=jnp.float32,
            ) + jnp.einsum(
                "bnr,bkr->bnk", qr, kr.astype(dt),
                preferred_element_type=jnp.float32,
            )

        scale = 1.0 / math.sqrt(cfg.qk_head_dim)
        full = jnp.concatenate(
            [score(cv, ck) * scale + mask_bias, score(c_new, kr_new) * scale],
            axis=-1,
        )
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


def _attn_out(x, ctx, lp):
    with jax.named_scope("layer.attn_out"):
        return x + _qmatmul(ctx, lp["o"]).astype(x.dtype)


def _swiglu(xn, gate, up, down):
    act = jax.nn.silu(_qmatmul(xn, gate)) * _qmatmul(xn, up)
    return _qmatmul(act.astype(xn.dtype), down)


def route(xn, router, bias, cfg):
    """Chosen experts ``[N, k]`` (int32) and their weights ``[N, k]``
    (float32) for normed tokens ``xn`` [N, H]: sigmoid scores, the bias
    picks and does not weigh, weights renormalised over the chosen and
    scaled.  All in float32 at ``highest`` precision."""
    scores = jax.nn.sigmoid(
        jnp.matmul(
            xn.astype(jnp.float32), router.astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
        )
    )
    _, idx = lax.top_k(scores + bias.astype(jnp.float32), cfg.num_experts_per_tok)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weights = chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
    return idx, weights * cfg.routed_scaling_factor


@functools.partial(jax.jit, static_argnums=3)
def moe_ffn(xn, lp, valid, cfg):
    """The routed + shared expert FFN of normed tokens ``xn`` [N, H];
    ``valid`` bool [N] marks the real ones (padding is not routed and
    yields the shared experts' output alone, which nobody reads).
    Returns ``(y [N, H] float32, counts int32 [2])``: the experts that got
    a real token and the row-tile visits of the grouped matmuls' schedule.
    Jitted, so the expert layers of every serving program share one trace
    and each program lowers the block once: a cached boot re-traces all
    36 programs, and that, not XLA, is what its warm-up waits for."""
    n, h = xn.shape
    e, k = cfg.n_routed_experts, cfg.num_experts_per_tok
    with jax.named_scope("layer.moe_router"):
        idx, weights = route(xn, lp["router"], lp["router_bias"], cfg)
    with jax.named_scope("layer.moe_experts"):
        # Token copies sorted by expert; padding sorts behind every group
        # (expert id E) and belongs to none.
        flat = jnp.where(valid[:, None], idx, e).reshape(n * k)
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
        ys = jnp.where((jnp.arange(n * k) < sizes.sum())[:, None], ys, 0.0)
        routed = jnp.einsum(
            "nkh,nk->nh", ys[jnp.argsort(order)].reshape(n, k, h), weights
        )
        counts = jnp.stack([jnp.sum(sizes > 0).astype(jnp.int32), plan.visits])
    with jax.named_scope("layer.moe_shared"):
        shared = _swiglu(xn, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
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
            return x + y.astype(x.dtype), jnp.zeros((2,), jnp.int32)
    xn = rms_norm(x, lp["ffn_norm"], cfg.rms_eps).reshape(b * s, h)
    y, counts = moe_ffn(xn, lp, valid.reshape(b * s), cfg)
    return x + y.reshape(b, s, h).astype(x.dtype), counts


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
    ``cache.length``; ids < 0 are padding (embedded as id 0, not routed).
    Returns ``(logits [B,S,vocab] float32, cache, counts)`` (``counts``
    int32 [2]: ``moe_ffn``'s, summed over layers)."""
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
    cos, sin = rope_cos_sin(positions[None], cfg)  # [1, S, rope/2]
    capacity = cache.k.shape[2]
    visible = jnp.arange(capacity)[None, :] <= positions[:, None]  # [S, T]
    mask_bias = jnp.where(visible, 0.0, -1e9).astype(jnp.float32)[None, None]
    z = jnp.zeros((), jnp.int32)
    ck, cv = cache.k, cache.v
    counts = jnp.zeros((2,), jnp.int32)
    for l, lp in enumerate(params["layers"]):
        xn = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        q_nope, q_rope = _mla_q(xn, lp, cos, sin, cfg)
        kr, c = _mla_kv(xn, lp, cos, sin, cfg)
        with jax.named_scope("kv_commit"):
            at = (jnp.int32(l), z, start, z, z)
            ck = lax.dynamic_update_slice(ck, kr[None, :, :, None].astype(ck.dtype), at)
            cv = lax.dynamic_update_slice(cv, c[None, :, :, None].astype(cv.dtype), at)
        ctx = _attn_expanded(q_nope, q_rope, ck[l, :, :, 0], cv[l, :, :, 0],
                             mask_bias, lp, cfg)
        x, layer_counts = _ffn(_attn_out(x, ctx, lp), lp, valid, cfg)
        counts = counts + layer_counts
    return _head(params, x, cfg), KVCache(ck, cv, start + s), counts


def prefill(params, input_ids, cfg, dtype=jnp.bfloat16):
    cache = KVCache.create(cfg, input_ids.shape[0], dtype)
    return forward(params, input_ids, cache, cfg, dtype)


def generate_greedy(
    params: dict,
    prompt_ids: jax.Array,
    num_new_tokens: int,
    cfg: MlaMoeConfig,
    dtype=jnp.bfloat16,
) -> jax.Array:
    """Greedy generation with a scanned decode loop (the ``/infer``
    path), the cache sized to what this call can reach."""
    import dataclasses

    total = prompt_ids.shape[1] + num_new_tokens
    if total > cfg.max_seq:
        raise ValueError(
            f"prompt ({prompt_ids.shape[1]}) + new tokens ({num_new_tokens}) "
            f"= {total} exceeds KV-cache capacity max_seq={cfg.max_seq}"
        )
    cfg = dataclasses.replace(cfg, max_seq=min(cfg.max_seq, -(-total // 8) * 8))
    logits, cache, _ = prefill(params, prompt_ids, cfg, dtype)
    next_tok = jnp.argmax(logits[:, -1:, :], axis=-1)

    def body(carry, _):
        tok, cache = carry
        logits, cache, _ = forward(params, tok, cache, cfg, dtype)
        return (jnp.argmax(logits[:, -1:, :], axis=-1), cache), tok

    _, toks = lax.scan(body, (next_tok, cache), None, length=num_new_tokens)
    return jnp.moveaxis(toks[..., 0], 0, 1)


# ---------------------------------------------------------------------------
# Continuous batching (per-row positions)
# ---------------------------------------------------------------------------


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
    row committed by one drop-scatter after the loop, inactive rows
    neither written nor advanced).  Inactive rows are not routed either.
    Returns ``(logits [B,1,vocab] float32, cache, counts)`` (as
    ``forward``'s)."""
    b, s = token_ids.shape
    if s != 1:
        raise ValueError(f"decode_ragged is single-token: got chunk of {s}")
    lengths = cache.lengths
    live = jnp.ones((b,), bool) if active is None else active
    x = _embed(params, token_ids, dtype)
    cos, sin = rope_cos_sin(lengths[:, None], cfg)  # [B, 1, rope/2]
    window = _attended_window(cache, window)
    before = jnp.arange(window)[None, :] < lengths[:, None]  # [B, W]
    mask_bias = jnp.where(before, 0.0, -1e9).astype(jnp.float32)[:, None]

    k_news, v_news = [], []
    counts = jnp.zeros((2,), jnp.int32)
    for l, lp in enumerate(params["layers"]):
        xn = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        q_nope, q_rope = _mla_q(xn, lp, cos, sin, cfg)
        kr, c = _mla_kv(xn, lp, cos, sin, cfg)
        ck, cv = cache.layer_window(l, window)
        ctx = _attn_absorbed(q_nope, q_rope, kr, c, ck, cv, mask_bias, lp, cfg)
        x, layer_counts = _ffn(_attn_out(x, ctx, lp), lp, live[:, None], cfg)
        counts = counts + layer_counts
        k_news.append(kr)
        v_news.append(c)
    k_news, v_news = jnp.stack(k_news), jnp.stack(v_news)  # [L, B, 1, *]
    logits = _head(params, x, cfg)
    write_pos = jnp.where(live, lengths, jnp.int32(cache.capacity))
    return (
        logits,
        RaggedKVCache(
            _commit_rows(cache.k, k_news, write_pos),
            _commit_rows(cache.v, v_news, write_pos),
            lengths + live.astype(jnp.int32),
        ),
        counts,
    )


@jax.named_scope("kv_commit")
def insert_sequence(
    cache: RaggedKVCache, seq: KVCache, slot: jax.Array, length: jax.Array
) -> RaggedKVCache:
    """Install a prefilled single-sequence scratch into batch row ``slot``
    (``llama.insert_sequence`` for this cache): ``length`` is the real
    token count; padding behind it is overwritten by decode before it can
    be attended."""
    slot = jnp.asarray(slot, jnp.int32)
    z = jnp.zeros((), jnp.int32)
    at = (z, slot, z, z, z)
    return RaggedKVCache(
        lax.dynamic_update_slice(cache.k, seq.k.astype(cache.k.dtype), at),
        lax.dynamic_update_slice(cache.v, seq.v.astype(cache.v.dtype), at),
        cache.lengths.at[slot].set(jnp.asarray(length, jnp.int32)),
    )
