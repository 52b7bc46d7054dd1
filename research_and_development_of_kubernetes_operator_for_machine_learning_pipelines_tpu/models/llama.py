"""Llama-2 decoder (baseline config 4: text-gen, TP-sharded across v5e-8).

Pure-JAX implementation matching HuggingFace ``LlamaForCausalLM`` semantics
(weight-copy parity test in ``tests/test_models_llama.py``): pre-RMSNorm,
rotate-half RoPE, grouped-query attention, SwiGLU MLP, untied LM head.

TPU-first design decisions:

- layer params are STACKED on a leading axis and consumed by ``lax.scan`` —
  one compiled block instead of ``n_layers`` unrolled copies, keeping
  compile times flat as depth grows;
- a fixed-capacity KV cache (``max_seq``) with a dynamic write index keeps
  every shape static under ``jit`` (no data-dependent shapes, SURVEY §7);
- logical axes put heads/kv_heads/mlp/vocab on the ``tp`` mesh axis
  (Megatron split) so a v5e-8 mesh shards Llama-2-7B ~0.9 GiB/chip in bf16;
  XLA inserts the ICI all-reduces at the o/down projections.

The reference has no model code (SURVEY §2.3); this is the rebuild's
long-context/distributed first-class citizen.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from .common import rms_norm
from .quantization import dequantize_tensor, is_quantized


# What the serving engine reads off a causal-LM family's module besides its
# programs (``models/mla_moe.py`` declares the same names).
FLAVOR = "llama-generate"  # registry / artifact name of this family
PAD_ID = 0  # the id a short prompt chunk is padded with
UNSUPPORTED: dict[str, str] = {}  # serving mechanisms these programs lack


def _mat(w, dtype):
    """Weight leaf -> matmul operand: raw array or int8 {"q8","scale"}.

    Prefer :func:`_qmatmul` on the hot paths — materializing the
    dequantized operand risks XLA writing a full-precision weight copy
    to HBM when the fusion heuristics decline (round-4 profile: a
    "weights-only" decode step cost 3-4x the int8 stream floor).
    """
    return dequantize_tensor(w, dtype) if is_quantized(w) else w.astype(dtype)


def _qmatmul(x, w):
    """``x @ dequantize(w)`` with the scale applied to the OUTPUT.

    The int8 scheme's scale is per-output-channel (``axis=-2`` reduce,
    shape ``[..., 1, out]``), so ``x @ (q8 * scale) == (x @ q8) * scale``
    exactly — the multiply moves from the ``[in, out]`` weight matrix to
    the ``[rows, out]`` result.  That guarantees the GEMM's HBM read is
    the RAW int8 buffer with only a convert on the operand (a fusion XLA
    performs reliably), instead of relying on it fusing a broadcast
    multiply — when that fusion declines, a bf16 copy of every weight
    matrix hits HBM and decode pays ~3x the weight traffic (round-4
    profile).  int8 values are exact in bf16,
    and the f32 scale multiplies the f32 accumulator, so numerics are at
    least as good as dequantize-then-matmul.
    """
    if is_quantized(w):
        y = jnp.matmul(
            x, w["q8"].astype(x.dtype), preferred_element_type=jnp.float32
        )
        return y * w["scale"].astype(jnp.float32)
    return jnp.matmul(x, w.astype(x.dtype), preferred_element_type=jnp.float32)


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    intermediate_size: int = 11008
    max_seq: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @classmethod
    def llama2_7b(cls, **kw) -> "LlamaConfig":
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        defaults = dict(
            vocab_size=256,
            hidden_size=64,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            intermediate_size=128,
            max_seq=64,
        )
        defaults.update(kw)
        return cls(**defaults)


def matmul_param_count(cfg: LlamaConfig) -> int:
    """Weight-matrix elements one token-position multiplies through in a
    forward pass: q/k/v/o projections, the SwiGLU MLP triple, and the
    untied LM head (embedding lookups move bytes, not FLOPs).  The
    device-telemetry cost model's dominant term — 2 FLOPs per element
    per position — kept HERE so it can never drift from the layer
    geometry it describes."""
    h, i, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    per_layer = (
        h * nh * hd          # q
        + 2 * h * nkv * hd   # k, v
        + nh * hd * h        # o
        + 3 * h * i          # gate, up, down
    )
    return cfg.num_layers * per_layer + h * v


class KVCache(NamedTuple):
    """Static-shape KV cache: (layers, batch, max_seq, kv_heads, head_dim).

    Capacity is fixed at creation (``max_seq``); ``forward`` rejects chunks
    larger than capacity and ``generate_greedy`` rejects prompt+new-token
    totals beyond it.  Writing past capacity via repeated ``decode_step``
    calls is undefined (dynamic_update_slice clamps) — callers track
    ``length`` against capacity (the server engine does).
    """

    k: jax.Array
    v: jax.Array
    length: jax.Array  # int32 scalar: number of valid positions

    @classmethod
    def create(cls, cfg: LlamaConfig, batch: int, dtype=jnp.bfloat16) -> "KVCache":
        shape = (cfg.num_layers, batch, cfg.max_seq, cfg.num_kv_heads, cfg.head_dim)
        return cls(
            k=jnp.zeros(shape, dtype),
            v=jnp.zeros(shape, dtype),
            length=jnp.zeros((), jnp.int32),
        )


def _layer_window(buf: jax.Array, layer, window: int) -> jax.Array:
    """``buf[layer, :, :window]`` of a ragged-cache buffer ``[L, B, T, NKV,
    *]`` as ONE dynamic slice, so a layer walk's per-layer read is
    ``[B, window, NKV, *]`` — sized by the attended window, not by the
    capacity (an index then a ``[:, :window]`` copies the whole-``T``
    slab first; tests/test_tpu_compile.py pins the window-sized read)."""
    _l, b, _t, n, d = buf.shape
    z = jnp.zeros((), jnp.int32)
    return lax.dynamic_slice(
        buf, (jnp.asarray(layer, jnp.int32), z, z, z, z), (1, b, window, n, d)
    )[0]


class RaggedKVCache(NamedTuple):
    """Multi-slot KV cache with PER-ROW lengths (continuous batching).

    Shapes match :class:`KVCache` — k/v ``[L, B, T, NKV, D]`` — but
    ``lengths`` is int32 ``[B]``: each batch row ("slot") sits at its own
    sequence position, so requests that arrived at different times decode
    together in one static-shape batched step (``decode_ragged``).  The
    server's :class:`~..server.generation.GenerationEngine` owns slot
    assignment; this type is the pure-JAX state it schedules over.

    Position-major is the layout the decode program computes in (a
    commit writes whole ``[NKV, D]`` planes at one position), so the
    donated buffers alias through it with no relayout copy
    (tests/test_tpu_compile.py::test_ragged_programs_leave_the_cache_in_place).
    The shape is made HERE and nowhere else: callers ask ``capacity`` and
    ``layer_window``, never a ``shape[i]``.
    """

    k: jax.Array  # [L, B, T, NKV, D]
    v: jax.Array
    lengths: jax.Array  # int32 [B]: valid positions per slot

    @classmethod
    def create(
        cls, cfg: LlamaConfig, batch: int, dtype=jnp.bfloat16
    ) -> "RaggedKVCache":
        shape = (cfg.num_layers, batch, cfg.max_seq, cfg.num_kv_heads, cfg.head_dim)
        return cls(
            k=jnp.zeros(shape, dtype),
            v=jnp.zeros(shape, dtype),
            lengths=jnp.zeros((batch,), jnp.int32),
        )

    @property
    def capacity(self) -> int:
        """Positions a slot can hold (the static ``T``)."""
        return self.k.shape[2]

    def layer_window(self, layer, window: int):
        """Layer ``layer``'s first ``window`` positions of every slot:
        ``(k, v)``, each ``[B, window, NKV, D]``."""
        return (
            _layer_window(self.k, layer, window),
            _layer_window(self.v, layer, window),
        )


class QuantRaggedKVCache(NamedTuple):
    """Int8 variant of :class:`RaggedKVCache` (KV-cache quantization).

    Decode streams the whole attended cache window every step; at long
    context that traffic dwarfs the (already int8-able) weights, so the
    cache itself is the next HBM lever.  K/V are stored int8 with a
    per-(layer, row, position, head) scale over the ``head_dim`` axis —
    written once when the position is produced and consumed WITHOUT a
    dequantized copy (scales factor out of the attention einsums; see
    ``_block_decode_deferred``).  With the round-3 deferred-write decode
    (v5e chip, 1.35B shape, int8 weights, window=512) the int8 cache is
    part of the 1938 tok/s @ 8 slots / 2240 @ 16 ladder (docs/PERF.md);
    numerics are held beside the full-precision cache by
    ``tests/test_quantization.py``.  Opt-in: ``spec.tpu.quantize: int8kv``.
    """

    k8: jax.Array  # int8 [L, B, T, NKV, D], RaggedKVCache's layout
    k_scale: jax.Array  # f32 [L, B, T, NKV, 1]
    v8: jax.Array
    v_scale: jax.Array
    lengths: jax.Array  # int32 [B]

    @classmethod
    def create(cls, cfg: LlamaConfig, batch: int) -> "QuantRaggedKVCache":
        shape = (cfg.num_layers, batch, cfg.max_seq, cfg.num_kv_heads, cfg.head_dim)
        sshape = shape[:-1] + (1,)
        return cls(
            k8=jnp.zeros(shape, jnp.int8),
            k_scale=jnp.zeros(sshape, jnp.float32),
            v8=jnp.zeros(shape, jnp.int8),
            v_scale=jnp.zeros(sshape, jnp.float32),
            lengths=jnp.zeros((batch,), jnp.int32),
        )

    @property
    def capacity(self) -> int:
        return self.k8.shape[2]

    def layer_window(self, layer, window: int):
        """``((k8, k_scale), (v8, v_scale))`` of layer ``layer``, each
        buffer ``[B, window, NKV, *]``."""
        return (
            (
                _layer_window(self.k8, layer, window),
                _layer_window(self.k_scale, layer, window),
            ),
            (
                _layer_window(self.v8, layer, window),
                _layer_window(self.v_scale, layer, window),
            ),
        )


def _quant_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-(…, head) int8 over the trailing head_dim axis."""
    from .quantization import quantize_tensor

    q = quantize_tensor(x, axis=-1)
    return q["q8"], q["scale"]


# ---------------------------------------------------------------------------
# Init / torch import
# ---------------------------------------------------------------------------


def init(key: jax.Array, cfg: LlamaConfig, dtype=jnp.float32) -> dict:
    h, i, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    L = cfg.num_layers
    keys = jax.random.split(key, 9)
    std = 0.02

    def normal(k, shape):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    return {
        "embed": normal(keys[0], (v, h)),
        "layers": {
            "attn_norm": jnp.ones((L, h), dtype),
            "q": normal(keys[1], (L, h, nh * hd)),
            "k": normal(keys[2], (L, h, nkv * hd)),
            "v": normal(keys[3], (L, h, nkv * hd)),
            "o": normal(keys[4], (L, nh * hd, h)),
            "mlp_norm": jnp.ones((L, h), dtype),
            "gate": normal(keys[5], (L, h, i)),
            "up": normal(keys[6], (L, h, i)),
            "down": normal(keys[7], (L, i, h)),
        },
        "final_norm": jnp.ones((h,), dtype),
        "lm_head": normal(keys[8], (h, v)),
    }


def from_torch(torch_model, cfg: LlamaConfig) -> dict:
    """Convert a HuggingFace ``LlamaForCausalLM`` state dict."""
    import numpy as np

    sd = {k: v.detach().cpu().float().numpy() for k, v in torch_model.state_dict().items()}

    def stack(fmt: str, transpose: bool = False):
        mats = [sd[fmt.format(i)] for i in range(cfg.num_layers)]
        if transpose:
            mats = [m.T for m in mats]
        return jnp.asarray(np.stack(mats, axis=0))

    return {
        "embed": jnp.asarray(sd["model.embed_tokens.weight"]),
        "layers": {
            "attn_norm": stack("model.layers.{}.input_layernorm.weight"),
            "q": stack("model.layers.{}.self_attn.q_proj.weight", transpose=True),
            "k": stack("model.layers.{}.self_attn.k_proj.weight", transpose=True),
            "v": stack("model.layers.{}.self_attn.v_proj.weight", transpose=True),
            "o": stack("model.layers.{}.self_attn.o_proj.weight", transpose=True),
            "mlp_norm": stack("model.layers.{}.post_attention_layernorm.weight"),
            "gate": stack("model.layers.{}.mlp.gate_proj.weight", transpose=True),
            "up": stack("model.layers.{}.mlp.up_proj.weight", transpose=True),
            "down": stack("model.layers.{}.mlp.down_proj.weight", transpose=True),
        },
        "final_norm": jnp.asarray(sd["model.norm.weight"]),
        "lm_head": jnp.asarray(sd["lm_head.weight"].T),
    }


# ---------------------------------------------------------------------------
# RoPE (HF rotate-half convention)
# ---------------------------------------------------------------------------


def rope_cos_sin(positions: jax.Array, cfg: LlamaConfig, dtype=jnp.float32):
    """cos/sin tables for ``positions`` [S] (or [B, S]) -> [..., head_dim]."""
    hd = cfg.head_dim
    inv_freq = 1.0 / (
        cfg.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    )
    freqs = positions.astype(jnp.float32)[..., None] * inv_freq  # [..., hd/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # [..., hd]
    return jnp.cos(emb).astype(dtype), jnp.sin(emb).astype(dtype)


def _rotate_half(x: jax.Array) -> jax.Array:
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: [B, S, N, D]; cos/sin: [S, D] (shared) or [B, S, D] (per-row)."""
    if cos.ndim == 2:
        c = cos[None, :, None, :]
        s = sin[None, :, None, :]
    else:
        c = cos[:, :, None, :]
        s = sin[:, :, None, :]
    return (x * c + _rotate_half(x) * s).astype(x.dtype)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _attn_qkv(x, lp, cos, sin, cfg):
    """A layer's attention prologue: norm, the three projections, RoPE.
    ``x`` [B,S,H] -> q [B,S,NH,D], k and v [B,S,NKV,D]."""
    b, s, _h = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    with jax.named_scope("layer.attn_qkv"):
        xn = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        q = _qmatmul(xn, lp["q"])
        k = _qmatmul(xn, lp["k"])
        v = _qmatmul(xn, lp["v"])
        q = q.astype(x.dtype).reshape(b, s, nh, hd)
        k = k.astype(x.dtype).reshape(b, s, nkv, hd)
        v = v.astype(x.dtype).reshape(b, s, nkv, hd)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def _attn_out_mlp(x, ctx, lp, cfg):
    """A layer's epilogue: the attention output projection and the
    SwiGLU MLP, each with its residual.  ``ctx`` is [B,S,NH*D]."""
    with jax.named_scope("layer.attn_out"):
        attn_out = _qmatmul(ctx, lp["o"]).astype(x.dtype)
        x = x + attn_out
    with jax.named_scope("layer.mlp"):
        xn = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
        gate = _qmatmul(xn, lp["gate"])
        up = _qmatmul(xn, lp["up"])
        act = jax.nn.silu(gate) * up
        down = _qmatmul(act.astype(x.dtype), lp["down"]).astype(x.dtype)
        return x + down


def _embed(params, token_ids, dtype):
    with jax.named_scope("embed"):
        return jnp.take(params["embed"], token_ids, axis=0).astype(dtype)


def _head(params, x, cfg):
    """Final norm and lm_head: logits in float32."""
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        return _qmatmul(x, params["lm_head"])


def _scale_over_keys(scale: jax.Array) -> jax.Array:
    """Per-(position, head) int8 scales ``[B, K, NKV, 1]`` -> ``[B, NKV,
    1, 1, K]``: broadcast over the ``bngqk`` scores' (group, query) axes."""
    return jnp.moveaxis(scale[..., 0], 1, 2)[:, :, None, None, :]


def _block(
    x: jax.Array,
    lp: dict,
    cache_k: jax.Array,
    cache_v: jax.Array,
    start: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    mask_bias: jax.Array,
    cfg: LlamaConfig,
    window: int | None = None,
):
    """One decoder layer over a fixed-capacity cache.

    x: [B,S,H]; cache_k/v: [B,max_seq,NKV,D]; start: scalar write offset
    shared by the batch (prefill / chunked prefill).  Per-row ragged
    decode does NOT come through here — see _block_decode_deferred.

    ``window`` (static) restricts ATTENTION to cache positions
    ``[0, window)`` while writes still land in the full buffer — decode's
    HBM floor is dominated by streaming the cache, so reading only a
    bucket that covers every row's current position instead of the full
    static capacity cuts that traffic proportionally.  Callers guarantee
    ``start + s <= window`` for every attended row; ``mask_bias``'s key
    axis must already be ``window``-sized.
    Returns (y, new_cache_k, new_cache_v).
    """
    b, s, h = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    q, k, v = _attn_qkv(x, lp, cos, sin, cfg)

    # Write this chunk's K/V into the cache at [start : start+s].
    # A quantized cache layer arrives as pairs (values int8, scales): the
    # chunk is quantized per-(position, head) at write time and dequantized
    # on the (fused) read path — KV-cache HBM traffic halves.
    quant_cache = isinstance(cache_k, tuple)

    def _write_all(buffers_and_vals):
        # Scalar start only: ragged (per-row) decode writes do not come
        # through here — decode_ragged defers them and commits all layers
        # with one scatter after its scan (see _block_decode_deferred).
        out = []
        z = jnp.zeros((), start.dtype) if hasattr(start, "dtype") else 0
        for buf, vals in buffers_and_vals:
            out.append(
                lax.dynamic_update_slice(
                    buf, vals.astype(buf.dtype), (z, start, z, z)
                )
            )
        return out

    with jax.named_scope("kv_commit"):
        if quant_cache:
            k8, ks = cache_k
            v8, vs = cache_v
            kq, kqs = _quant_kv(k)
            vq, vqs = _quant_kv(v)
            k8, ks, v8, vs = _write_all([(k8, kq), (ks, kqs), (v8, vq), (vs, vqs)])
            cache_k = (k8, ks)
            cache_v = (v8, vs)
        else:
            cache_k, cache_v = _write_all([(cache_k, k), (cache_v, v)])

    with jax.named_scope("layer.attn_core"):
        # GQA via grouped einsum: q reshaped to [B,S,NKV,G,D] contracts directly
        # against the [B,T,NKV,D] cache — no materialized repeat of K/V to all
        # query heads (that broadcast would dominate HBM traffic at decode).
        group = nh // nkv
        qg = q.reshape(b, s, nkv, group, hd)
        if quant_cache:
            # The per-(position, head) scales are CONSTANT over the contracted
            # head_dim axis, so they factor OUT of both einsums: contract the
            # raw int8 cache (the int8->bf16 convert fuses into the operand
            # read like the weight path) and fold K's scale into the scores,
            # V's into the probabilities.  A naive dequantize-then-einsum
            # materializes a full bf16 copy of the cache window per step —
            # measured SLOWER than the bf16 cache it was meant to beat.
            k8, ks = cache_k
            v8, vs = cache_v
            if window is not None:
                k8, ks = k8[:, :window], ks[:, :window]
                v8, vs = v8[:, :window], vs[:, :window]
            scores = jnp.einsum(
                "bqngd,bknd->bngqk",
                qg,
                k8.astype(x.dtype),
                preferred_element_type=jnp.float32,
            ) / jnp.sqrt(jnp.float32(hd))
            scores = scores * _scale_over_keys(ks)
            scores = scores + mask_bias[:, None]
            probs = jax.nn.softmax(scores, axis=-1)
            probs = (probs * _scale_over_keys(vs)).astype(x.dtype)
            ctx = jnp.einsum(
                "bngqk,bknd->bqngd", probs, v8.astype(x.dtype)
            ).reshape(b, s, nh * hd)
        else:
            kk = cache_k if window is None else cache_k[:, :window]
            vv = cache_v if window is None else cache_v[:, :window]
            kk = kk.astype(x.dtype)
            vv = vv.astype(x.dtype)

            scores = jnp.einsum(
                "bqngd,bknd->bngqk", qg, kk, preferred_element_type=jnp.float32
            ) / jnp.sqrt(jnp.float32(hd))
            scores = scores + mask_bias[:, None]  # [B or 1, 1, 1, S, T]
            probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
            ctx = jnp.einsum("bngqk,bknd->bqngd", probs, vv).reshape(b, s, nh * hd)
    return _attn_out_mlp(x, ctx, lp, cfg), cache_k, cache_v


def _block_decode_deferred(
    x: jax.Array,
    lp: dict,
    cache_k,
    cache_v,
    cos: jax.Array,
    sin: jax.Array,
    mask_bias: jax.Array,
    cfg: LlamaConfig,
):
    """One decoder layer for single-token ragged decode with the cache
    READ-ONLY: returns ``(y, k_new, v_new)`` instead of an updated cache.

    ``cache_k``/``cache_v`` are the layer's attended window
    ``[B, W, NKV, D]`` (``cache.layer_window``; ``(values, scales)``
    pairs under int8kv) and ``mask_bias`` is ``[B, 1, 1, W]``.

    Why: if the layer scan carried an updated cache, the update would ride
    the scan's stacked outputs and XLA materializes that as a full cache
    read + write every step — traffic linear in slots that capped 1.35B
    decode at ~1000 tok/s (round-3 probe: the write path cost 11.7 ms of
    a 17 ms step at 32 slots).  Deferring the write means the scan emits
    only each layer's tiny ``[B,1,NKV,D]`` row and :func:`decode_ragged`
    commits every layer with ONE scatter after the scan, leaving the big
    buffers untouched through the jit body.

    The current token is attended via an exact bf16 self-term concatenated
    before the softmax — ``mask_bias`` must therefore be STRICT
    (``key_pos < position``): the current position's cache row is
    stale/unwritten by design.  On the quant-cache path this also skips a
    quantize round-trip for the newest token (slightly better numerics).
    """
    b, s, h = x.shape  # s == 1 by contract
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    q, k, v = _attn_qkv(x, lp, cos, sin, cfg)

    group = nh // nkv
    qg = q.reshape(b, s, nkv, group, hd)
    quant_cache = isinstance(cache_k, tuple)
    # Plain XLA, no kernel: at G = 1 each head's dot is a one-row matvec at
    # the MXU's 8-sublane floor; on the chip this chain won at every slot
    # count, and a fused window passes 16 MiB of scoped VMEM at 7B geometry.
    with jax.named_scope("layer.attn_core"):
        if quant_cache:
            k8, ks = cache_k
            v8, vs = cache_v
            scores = jnp.einsum(
                "bqngd,bknd->bngqk",
                qg,
                k8.astype(x.dtype),
                preferred_element_type=jnp.float32,
            ) / jnp.sqrt(jnp.float32(hd))
            scores = scores * _scale_over_keys(ks)
        else:
            scores = jnp.einsum(
                "bqngd,bknd->bngqk",
                qg,
                cache_k.astype(x.dtype),
                preferred_element_type=jnp.float32,
            ) / jnp.sqrt(jnp.float32(hd))
        scores = scores + mask_bias[:, None]

        # Exact self-term for the current (not-yet-written) position.
        score_self = (
            jnp.einsum("bqngd,bqnd->bngq", qg, k, preferred_element_type=jnp.float32)
            / jnp.sqrt(jnp.float32(hd))
        )[..., None]
        full = jnp.concatenate([scores, score_self], axis=-1)
        probs = jax.nn.softmax(full, axis=-1)
        probs_cache, prob_self = probs[..., :-1], probs[..., -1:]

        if quant_cache:
            probs_cache = (probs_cache * _scale_over_keys(vs)).astype(x.dtype)
            ctx = jnp.einsum("bngqk,bknd->bqngd", probs_cache, v8.astype(x.dtype))
        else:
            ctx = jnp.einsum(
                "bngqk,bknd->bqngd",
                probs_cache.astype(x.dtype),
                cache_v.astype(x.dtype),
            )
        ctx = ctx + jnp.einsum(
            "bngqk,bknd->bqngd", prob_self.astype(x.dtype), v
        )
        ctx = ctx.reshape(b, s, nh * hd)

    return _attn_out_mlp(x, ctx, lp, cfg), k, v


def forward(
    params: dict,
    input_ids: jax.Array,
    cache: KVCache,
    cfg: LlamaConfig,
    dtype=jnp.bfloat16,
) -> tuple[jax.Array, KVCache]:
    """Run ``input_ids`` [B,S] through the model starting at ``cache.length``.

    Works for both prefill (S = prompt length, cache.length = 0) and decode
    (S = 1).  Returns (logits [B,S,vocab] float32, updated cache).
    """
    b, s = input_ids.shape
    if s > cfg.max_seq:
        raise ValueError(
            f"sequence chunk of {s} tokens exceeds KV-cache capacity "
            f"max_seq={cfg.max_seq}"
        )
    start = cache.length
    x = _embed(params, input_ids, dtype)

    positions = start + jnp.arange(s)
    cos, sin = rope_cos_sin(positions, cfg, jnp.float32)

    # Additive mask over the full cache buffer T=max_seq:
    # query at absolute position p attends keys with pos <= p (and only
    # positions already written).
    key_pos = jnp.arange(cfg.max_seq)
    valid = key_pos[None, :] <= positions[:, None]  # [S, T]
    mask_bias = jnp.where(valid, 0.0, -1e9).astype(jnp.float32)[None, None, :, :]

    def scan_body(carry, layer_inputs):
        x = carry
        lp, ck, cv = layer_inputs
        y, ck2, cv2 = _block(x, lp, ck, cv, start, cos, sin, mask_bias, cfg)
        return y, (ck2, cv2)

    x, (new_k, new_v) = lax.scan(
        scan_body, x, (params["layers"], cache.k, cache.v)
    )
    logits = _head(params, x, cfg)
    new_cache = KVCache(k=new_k, v=new_v, length=start + s)
    return logits, new_cache


def prefill(params, input_ids, cfg, dtype=jnp.bfloat16):
    cache = KVCache.create(cfg, input_ids.shape[0], dtype)
    return forward(params, input_ids, cache, cfg, dtype)


def decode_step(params, token_ids, cache, cfg, dtype=jnp.bfloat16):
    """One greedy decode step: token_ids [B,1] -> (logits [B,1,V], cache)."""
    return forward(params, token_ids, cache, cfg, dtype)


def _ring_block(x, lp, cos, sin, cfg, mesh, axis_name):
    """One decoder layer with ring attention over an ``sp``-sharded
    sequence (long-prompt prefill; no cache read — the prompt IS the
    context).  x: [B,S,H] with S sharded over ``axis_name``.  Returns
    (y, k, v) where k/v are this layer's [B,S,NKV,D] cache rows (k
    rope'd, exactly what :func:`_block` writes)."""
    b, s, h = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    xn = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
    q = _qmatmul(xn, lp["q"]).astype(x.dtype).reshape(b, s, nh, hd)
    k = _qmatmul(xn, lp["k"]).astype(x.dtype).reshape(b, s, nkv, hd)
    v = _qmatmul(xn, lp["v"]).astype(x.dtype).reshape(b, s, nkv, hd)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    # The ring kernel contracts [B,H,S,D] blocks with matching head
    # counts — GQA groups are repeated here (an S/n-local broadcast per
    # ring step, not the full-sequence repeat the decode path avoids).
    group = nh // nkv
    kf = jnp.repeat(k, group, axis=2) if group > 1 else k
    vf = jnp.repeat(v, group, axis=2) if group > 1 else v
    from ..ops.ring_attention import ring_attention_sharded

    ctx = ring_attention_sharded(
        q.transpose(0, 2, 1, 3),
        kf.transpose(0, 2, 1, 3),
        vf.transpose(0, 2, 1, 3),
        mesh,
        causal=True,
        axis_name=axis_name,
    )
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, nh * hd)
    x = x + _qmatmul(ctx, lp["o"]).astype(x.dtype)

    xn = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
    act = jax.nn.silu(_qmatmul(xn, lp["gate"])) * _qmatmul(xn, lp["up"])
    down = _qmatmul(act.astype(x.dtype), lp["down"]).astype(x.dtype)
    return x + down, k, v


def prefill_ring(
    params: dict,
    input_ids: jax.Array,
    cfg: LlamaConfig,
    *,
    mesh,
    last_idx: jax.Array,
    dtype=jnp.bfloat16,
    axis_name: str = "sp",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Sequence-parallel prefill: the whole (padded) prompt in ONE pass
    with the sequence axis sharded over ``axis_name`` and exact ring
    attention (``ops.ring_attention``) in place of the dense S x S
    score matrix.

    input_ids: [1, S] padded to a bucket divisible by the sp degree;
    ``last_idx`` (traced) selects the final REAL row so only a [1, V]
    logits slice crosses the replicated boundary — never [S, V].
    Returns ``(last_logits [1,V], k_all, v_all)`` with k_all/v_all
    stacked [L, 1, S, NKV, D], the position-major seq-scratch layout
    :func:`insert_sequence` consumes.  Pad rows carry garbage K/V
    exactly like the padded chunked path — insert length caps reads.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    b, s = input_ids.shape
    x = _embed(params, input_ids, dtype)
    # Pin activations seq-sharded so the per-token work (norms, MLP,
    # projections) partitions over sp too, not just the attention.
    seq_sharded = NamedSharding(mesh, PartitionSpec(None, axis_name, None))
    x = lax.with_sharding_constraint(x, seq_sharded)

    positions = jnp.arange(s)
    cos, sin = rope_cos_sin(positions, cfg, jnp.float32)

    def scan_body(carry, lp):
        y, k, v = _ring_block(carry, lp, cos, sin, cfg, mesh, axis_name)
        return y, (k, v)

    x, (k_all, v_all) = lax.scan(scan_body, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    last = lax.dynamic_slice_in_dim(x, last_idx, 1, axis=1)  # [1,1,H]
    logits = _qmatmul(last[:, 0], params["lm_head"])  # [1, V]
    return logits, k_all, v_all


def generate_greedy(
    params: dict,
    prompt_ids: jax.Array,
    num_new_tokens: int,
    cfg: LlamaConfig,
    dtype=jnp.bfloat16,
) -> jax.Array:
    """Greedy generation with a scanned decode loop (jit-friendly)."""
    total = prompt_ids.shape[1] + num_new_tokens
    if total > cfg.max_seq:
        raise ValueError(
            f"prompt ({prompt_ids.shape[1]}) + new tokens ({num_new_tokens}) "
            f"= {total} exceeds KV-cache capacity max_seq={cfg.max_seq}"
        )
    # Size the cache to what THIS call can reach, not to ``max_seq``:
    # the loop carries the whole cache, and at Llama-2-7B depth a batch-8
    # call at max_seq 1024 asked the chip to reserve 9 GiB for positions
    # it could never write (80 of 1024 were reachable) — beside the
    # serving engine's own cache that does not load.
    cfg = dataclasses.replace(cfg, max_seq=min(cfg.max_seq, -(-total // 8) * 8))
    logits, cache = prefill(params, prompt_ids, cfg, dtype)
    next_tok = jnp.argmax(logits[:, -1:, :], axis=-1)

    def body(carry, _):
        tok, cache = carry
        logits, cache = decode_step(params, tok, cache, cfg, dtype)
        nxt = jnp.argmax(logits[:, -1:, :], axis=-1)
        return (nxt, cache), tok

    (_, _), toks = lax.scan(body, (next_tok, cache), None, length=num_new_tokens)
    # toks: [num_new, B, 1] -> [B, num_new]
    return jnp.moveaxis(toks[..., 0], 0, 1)


# ---------------------------------------------------------------------------
# Continuous batching primitives (per-row positions)
# ---------------------------------------------------------------------------


def _attended_window(cache, window: int | None) -> int:
    """The static attended prefix: ``window`` clamped to the capacity."""
    return cache.capacity if window is None else min(int(window), cache.capacity)


def _walk_layers(params, cache, x, cfg, window, block, rows=None):
    """The layer loop every ragged program shares, the cache READ-ONLY:
    ``block(x, lp, ck, cv) -> (y, k_new, v_new)`` per layer, where
    ``ck``/``cv`` are ``cache.layer_window(l, window)`` (gathered to
    ``rows`` when the batch is a subset of the slots) and ``k_new`` /
    ``v_new`` are the layer's fresh ``[B, S, NKV, D]`` rows.  Returns
    ``(x, k_news, v_news)`` with the rows stacked ``[L, B, S, NKV, D]``
    for ONE commit after the loop.

    A ``fori_loop`` over dynamic slices of the ORIGINAL buffers, not a
    ``lax.scan`` with the cache as xs: packing multi-GiB buffers into a
    scan's xs can make XLA copy them into loop state each step.
    """
    b, s, _h = x.shape
    acc_k = jnp.zeros(
        (cfg.num_layers, b, s, cfg.num_kv_heads, cfg.head_dim), x.dtype
    )

    def layer_body(l, carry):
        x, acc_k, acc_v = carry
        lp = jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, l, axis=0, keepdims=False),
            params["layers"],
        )
        ck, cv = cache.layer_window(l, window)
        if rows is not None:
            ck, cv = jax.tree.map(lambda a: a[rows], (ck, cv))
        y, k_new, v_new = block(x, lp, ck, cv)
        acc_k = lax.dynamic_update_slice_in_dim(
            acc_k, k_new[None].astype(acc_k.dtype), l, axis=0
        )
        acc_v = lax.dynamic_update_slice_in_dim(
            acc_v, v_new[None].astype(acc_v.dtype), l, axis=0
        )
        return y, acc_k, acc_v

    return lax.fori_loop(
        0, cfg.num_layers, layer_body, (x, acc_k, jnp.zeros_like(acc_k))
    )


def decode_ragged(
    params: dict,
    token_ids: jax.Array,
    cache: "RaggedKVCache | QuantRaggedKVCache",
    cfg: LlamaConfig,
    active: jax.Array | None = None,
    dtype=jnp.bfloat16,
    window: int | None = None,
):
    """One decode step where every batch row is at its OWN position.

    token_ids ``[B, 1]``; each row i writes K/V at ``cache.lengths[i]`` and
    attends keys ``0..lengths[i]``.  ``active`` (bool ``[B]``) gates the
    length advance so finished/empty slots don't creep toward capacity;
    their rows still compute (static shapes — the MXU does not care) and
    their outputs are ignored by the scheduler.

    Slot-reuse safety: a reused slot's stale K/V beyond the new sequence's
    current position is never attended — the cache mask is STRICT
    (``key_pos < p``), every position ``< p`` has been rewritten by the
    new occupant's prefill insert or a prior decode step's commit, and
    position ``p`` itself is attended through the exact in-flight
    self-term (never read from the cache this step; its row is written
    by the post-scan scatter for the NEXT step to read).

    ``window`` (STATIC int) bounds the attended cache prefix: callers pass
    a power-of-two bucket ``> max(lengths of active rows)`` so each window
    value compiles once but short sequences stop paying full-capacity
    cache reads.  Writes are unaffected (full buffer).  Measured on a v5e
    chip (1.35B shape, 8 slots at position 256, capacity 1024):
    window=512 is 1.11x over full-capacity in bf16, and composes with
    int8 weights to 1.24x (625 -> 772 tok/s).

    Returns (logits ``[B, 1, vocab]`` float32, cache with advanced lengths).
    """
    b, s = token_ids.shape
    if s != 1:
        raise ValueError(f"decode_ragged is single-token: got chunk of {s}")
    quant = isinstance(cache, QuantRaggedKVCache)
    lengths = cache.lengths
    x = _embed(params, token_ids, dtype)

    positions = lengths[:, None]  # [B, 1]
    cos, sin = rope_cos_sin(positions, cfg, jnp.float32)  # [B, 1, head_dim]

    window = _attended_window(cache, window)
    key_pos = jnp.arange(window)
    # STRICT mask: the current position is attended via the exact
    # self-term inside _block_decode_deferred, not read back from the
    # cache (which stays read-only through the layer scan — see that
    # function's docstring for the traffic argument).
    valid = key_pos[None, None, :] < positions[:, :, None]  # [B, 1, W]
    mask_bias = jnp.where(valid, 0.0, -1e9).astype(jnp.float32)[:, None]  # [B,1,1,W]

    x, k_news, v_news = _walk_layers(
        params, cache, x, cfg, window,
        lambda x, lp, ck, cv: _block_decode_deferred(
            x, lp, ck, cv, cos, sin, mask_bias, cfg
        ),
    )
    return _finish_decode(
        params, x, k_news[:, :, 0], v_news[:, :, 0], cache, lengths, active,
        quant, cfg,
    )


def decode_multistep(
    params: dict,
    token_ids: jax.Array,
    cache: "RaggedKVCache | QuantRaggedKVCache",
    cfg: LlamaConfig,
    active: jax.Array,
    remaining: jax.Array,
    eos_ids: jax.Array,
    steps: int,
    sample_fn,
    sample_carry=None,
    dtype=jnp.bfloat16,
    window: int | None = None,
):
    """``steps`` (K) decode iterations in ONE program: a ``lax.scan``
    whose body is the existing single-step :func:`decode_ragged` forward
    plus an on-device sampling chain — each step's sampled token feeds
    the next step's embedding lookup without a host round trip, so one
    dispatch (and one blocking readback, which the engine further defers
    by a tick) serves K tokens per row.

    ``token_ids`` int32 ``[B, 1]`` is each row's pending token (last
    emitted, not yet fed); ``active`` bool ``[B]``; ``remaining`` int32
    ``[B]`` is each row's token budget (new tokens it may still emit);
    ``eos_ids`` int32 ``[B]`` is each row's stop token with ``-1`` for
    "no EOS" (token ids are non-negative, so -1 never matches).

    ``sample_fn(logits [B, V], carry) -> (carry, next [B])`` is the
    per-step token rule: greedy passes ``lambda l, c: (c, argmax(l))``
    with ``sample_carry=None``; sampling passes
    :func:`~.sampling.sample_chain_step` closed over the per-row
    temperature/top-k/top-p arrays with ``sample_carry`` = the per-row
    key batch — the carry threads through the scan so every step splits
    keys exactly like a step-by-step sampling tick.

    The EOS latch lives INSIDE the scan: a row that samples its EOS (or
    exhausts ``remaining``) drops out of ``active`` for the rest of the
    scan, so its lengths stop advancing and its K/V writes park
    (``decode_ragged``'s ``active`` gate) — over-run work is bounded by
    K and nothing past EOS is ever committed, so the host needs no K/V
    truncation, only to ignore token columns at/after ``valid[i]``.

    ``window`` (STATIC) must cover the LAST step's attended positions:
    callers pass a bucket ``>= max(lengths of active rows) + steps - 1``
    (the scan cannot grow the window mid-flight — one compiled variant
    per (steps, window) pair).

    Returns ``(tok_block [B, steps], valid [B], toks [B, 1], cache,
    active_out, remaining_out, carry_out)``: ``tok_block[i, j]`` is real
    for ``j < valid[i]`` (frozen last-token copies after), ``valid[i]``
    counts steps row ``i`` was active for, and the trailing outputs are
    the device-resident state the engine chains into the NEXT fused
    dispatch without a host sync (lag-1 readback).
    """
    def body(carry, _):
        toks, cache, act, rem, sc = carry
        logits, cache = decode_ragged(
            params, toks, cache, cfg, active=act, dtype=dtype, window=window
        )
        sc, nxt = sample_fn(logits[:, -1, :], sc)
        nxt = jnp.where(act, nxt.astype(jnp.int32), toks[:, 0])
        emitted = act
        rem = rem - act.astype(jnp.int32)
        act = act & (nxt != eos_ids) & (rem > 0)
        return (nxt[:, None], cache, act, rem, sc), (nxt, emitted)

    carry0 = (token_ids, cache, active, remaining, sample_carry)
    (toks, cache, active, remaining, sample_carry), (tok_seq, emit_seq) = (
        lax.scan(body, carry0, None, length=steps)
    )
    tok_block = jnp.moveaxis(tok_seq, 0, 1)  # [steps, B] -> [B, steps]
    valid = jnp.sum(emit_seq.astype(jnp.int32), axis=0)
    return tok_block, valid, toks, cache, active, remaining, sample_carry


def _block_verify_deferred(
    x: jax.Array,
    lp: dict,
    cache_k,
    cache_v,
    cos: jax.Array,
    sin: jax.Array,
    mask_bias: jax.Array,
    chunk_bias: jax.Array,
    cfg: LlamaConfig,
):
    """One decoder layer for MULTI-token ragged verify with the cache
    READ-ONLY: ``x`` is ``[B, S, H]`` where row ``i``'s S tokens sit at
    positions ``lengths[i] .. lengths[i]+S-1``.  Returns ``(y, k_new,
    v_new)`` with the chunk's fresh K/V ``[B, S, NKV, D]`` — the caller
    commits every layer with one scatter pass after the scan, exactly
    like :func:`_block_decode_deferred` (whose S == 1 case this
    generalizes; see that docstring for the deferred-write traffic
    argument).  ``cache_k``/``cache_v`` are the layer's attended window
    ``[B, W, NKV, D]``, as there.

    Attention decomposes into two exact terms: the cache window (strict
    mask ``key_pos < lengths[i]`` — no chunk position has been written
    yet) and an in-chunk causal term over the S fresh K/V rows
    (``chunk_bias``: key j attends query q iff ``j <= q``), joined in
    one softmax.  This is what verifies k draft tokens under ONE weight
    stream instead of k sequential decode steps.
    """
    b, s, h = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    q, k, v = _attn_qkv(x, lp, cos, sin, cfg)

    with jax.named_scope("layer.attn_core"):
        group = nh // nkv
        qg = q.reshape(b, s, nkv, group, hd)
        quant_cache = isinstance(cache_k, tuple)
        if quant_cache:
            k8, ks = cache_k
            v8, vs = cache_v
            scores = jnp.einsum(
                "bqngd,bknd->bngqk",
                qg,
                k8.astype(x.dtype),
                preferred_element_type=jnp.float32,
            ) / jnp.sqrt(jnp.float32(hd))
            scores = scores * _scale_over_keys(ks)
        else:
            scores = jnp.einsum(
                "bqngd,bknd->bngqk",
                qg,
                cache_k.astype(x.dtype),
                preferred_element_type=jnp.float32,
            ) / jnp.sqrt(jnp.float32(hd))
        scores = scores + mask_bias[:, None]  # [B,1,1,W] -> over (n, g, q)

        # In-chunk causal scores over the fresh (not-yet-written) K rows.
        # Only the SELF position (j == q) may use the exact full-precision
        # term — that mirrors _block_decode_deferred, where the current
        # token is attended in-flight.  Every EARLIER chunk position was, on
        # the sequential path, already committed to the cache before being
        # attended — on the int8 cache that means a quantize round-trip —
        # so the chunk term must read those positions through the same
        # round-trip (raw int8 contraction, scales folded out, exactly like
        # the cache-window term above) or verify logits diverge from plain
        # int8kv decode by the QUANTIZATION error, not mere reduction
        # rounding, and near-tie argmaxes break token parity.
        score_self = jnp.einsum(
            "bqngd,bjnd->bngqj", qg, k, preferred_element_type=jnp.float32
        ) / jnp.sqrt(jnp.float32(hd))
        if quant_cache:
            k8c, kscc = _quant_kv(k)  # [B,S,NKV,D] / [B,S,NKV,1]
            score_rt = jnp.einsum(
                "bqngd,bjnd->bngqj",
                qg,
                k8c.astype(x.dtype),
                preferred_element_type=jnp.float32,
            ) / jnp.sqrt(jnp.float32(hd))
            score_rt = score_rt * _scale_over_keys(kscc)
            eye = jnp.eye(s, dtype=bool)[None, None, None]
            score_chunk = jnp.where(eye, score_self, score_rt)
        else:
            score_chunk = score_self
        score_chunk = score_chunk + chunk_bias  # [1,1,1,S,S]
        full = jnp.concatenate([scores, score_chunk], axis=-1)
        probs = jax.nn.softmax(full, axis=-1)
        probs_cache, probs_chunk = probs[..., :-s], probs[..., -s:]

        if quant_cache:
            probs_cache = (probs_cache * _scale_over_keys(vs)).astype(x.dtype)
            ctx = jnp.einsum("bngqk,bknd->bqngd", probs_cache, v8.astype(x.dtype))
            # Chunk V: self row full-precision, earlier rows through the
            # int8 round-trip (scales folded into the probabilities, like
            # the cache-window term).
            v8c, vscc = _quant_kv(v)
            vscale_c = _scale_over_keys(vscc)
            eyef = eye.astype(probs.dtype)
            ctx = ctx + jnp.einsum(
                "bngqj,bjnd->bqngd", (probs_chunk * eyef).astype(x.dtype), v
            )
            ctx = ctx + jnp.einsum(
                "bngqj,bjnd->bqngd",
                (probs_chunk * (1.0 - eyef) * vscale_c).astype(x.dtype),
                v8c.astype(x.dtype),
            )
        else:
            ctx = jnp.einsum(
                "bngqk,bknd->bqngd",
                probs_cache.astype(x.dtype),
                cache_v.astype(x.dtype),
            )
            ctx = ctx + jnp.einsum(
                "bngqj,bjnd->bqngd", probs_chunk.astype(x.dtype), v
            )
        ctx = ctx.reshape(b, s, nh * hd)

    return _attn_out_mlp(x, ctx, lp, cfg), k, v


def verify_ragged(
    params: dict,
    token_ids: jax.Array,
    cache: "RaggedKVCache | QuantRaggedKVCache",
    cfg: LlamaConfig,
    dtype=jnp.bfloat16,
    window: int | None = None,
    active: jax.Array | None = None,
):
    """Score S tokens per slot in ONE forward (self-speculative verify).

    ``token_ids`` is ``[B, S]``: row ``i``'s column 0 is the slot's last
    emitted (pending) token and columns ``1..S-1`` are drafted
    continuations; position ``j`` occupies absolute position
    ``lengths[i] + j``.  Returns ``(logits [B, S, vocab] float32, cache)``
    with every chunk position's K/V committed but ``lengths`` UNCHANGED —
    the caller advances each row by its accepted count + 1, which IS the
    rollback of rejected writes: positions at or beyond the truncated
    length are never attended (the cache mask is strict) and are
    overwritten by later writes before the sequence reaches them — the
    same invariant that makes slot reuse safe (see :func:`decode_ragged`).

    One compiled variant per (S, window) pair; S = 1 degenerates to a
    single-token decode step (the engine uses :func:`decode_ragged`
    there — this path exists for the draft lengths).

    ``active`` (bool ``[B]`` or None) parks inactive rows' K/V writes
    (see :func:`_commit_chunk`): an inactive slot may be mid-packed-
    prefill and its rows belong to the admission path this tick.
    """
    b, s = token_ids.shape
    quant = isinstance(cache, QuantRaggedKVCache)
    lengths = cache.lengths
    x = _embed(params, token_ids, dtype)

    positions = lengths[:, None] + jnp.arange(s)[None, :]  # [B, S]
    cos, sin = rope_cos_sin(positions, cfg, jnp.float32)  # [B, S, head_dim]

    window = _attended_window(cache, window)
    key_pos = jnp.arange(window)
    # STRICT cache mask shared by every chunk query: no chunk position has
    # been written yet, so all of them see exactly key_pos < lengths[i];
    # positions lengths[i]..lengths[i]+q-1 are the chunk's own earlier
    # tokens, attended through the exact in-chunk term.
    valid = key_pos[None, :] < lengths[:, None]  # [B, W]
    mask_bias = jnp.where(valid, 0.0, -1e9).astype(jnp.float32)[:, None, None]
    qpos = jnp.arange(s)
    chunk_causal = qpos[:, None] >= qpos[None, :]  # key j <= query q
    chunk_bias = jnp.where(chunk_causal, 0.0, -1e9).astype(jnp.float32)[
        None, None, None
    ]

    x, k_news, v_news = _walk_layers(
        params, cache, x, cfg, window,
        lambda x, lp, ck, cv: _block_verify_deferred(
            x, lp, ck, cv, cos, sin, mask_bias, chunk_bias, cfg
        ),
    )
    logits = _head(params, x, cfg)
    return logits, _commit_chunk(cache, k_news, v_news, lengths, quant, active)


@jax.named_scope("kv_commit")
def _commit_chunk(cache, k_news, v_news, lengths, quant, active=None):
    """Commit a verify chunk's K/V: row ``b``'s token ``j`` lands at
    position ``lengths[b] + j``, ONE batched drop-scatter per buffer
    over the ``[B, S]`` index grid — sequential per-``j`` passes would
    re-pay the scatter's full-buffer walk S times (the round-5 commit
    measurements put one pass at ~3.8 ms at the 1.35B/32-slot shape),
    taxing exactly the tick speculation exists to accelerate.
    ``lengths`` is returned UNCHANGED: acceptance decides the advance.

    ``active`` (bool [B] or None) parks INACTIVE rows' writes at
    capacity so the drop-mode scatter discards them: an empty slot may
    be mid-packed-prefill (its K/V written by the admission path, not
    this tick), and the old always-write garbage row would corrupt it.
    """
    b, s = k_news.shape[1:3]
    write_base = lengths
    if active is not None:
        write_base = jnp.where(active, lengths, jnp.int32(cache.capacity))
    pos = write_base[:, None] + jnp.arange(s)[None, :]
    return _commit_at(cache, k_news, v_news, jnp.arange(b), pos, quant)


def _commit_at(cache, k_news, v_news, rows, pos, quant):
    """Write chunk rows ``k_news``/``v_news`` ``[L, B_c, S, NKV, D]`` at
    cache ``(rows[b], pos[b, j])`` — ``rows`` ``[B_c]``, ``pos`` ``[B_c,
    S]`` — with ONE batched drop-scatter per buffer and ``lengths``
    unchanged.  The advanced indices sit on the buffers' adjacent (row,
    position) axes, so the updates are the rows as they stand.  The
    (row, position) tuples must be pairwise distinct
    (``unique_indices``); positions at or past capacity drop, never
    clamp."""

    def commit(buf, vals):
        return buf.at[:, rows[:, None], pos].set(
            vals.astype(buf.dtype), mode="drop", unique_indices=True
        )

    if quant:
        kq, kqs = _quant_kv(k_news)
        vq, vqs = _quant_kv(v_news)
        return QuantRaggedKVCache(
            commit(cache.k8, kq),
            commit(cache.k_scale, kqs),
            commit(cache.v8, vq),
            commit(cache.v_scale, vqs),
            cache.lengths,
        )
    return RaggedKVCache(
        commit(cache.k, k_news), commit(cache.v, v_news), cache.lengths
    )


def prefill_chunks_ragged(
    params: dict,
    token_ids: jax.Array,
    cache: "RaggedKVCache | QuantRaggedKVCache",
    slots: jax.Array,
    offsets: jax.Array,
    cfg: LlamaConfig,
    dtype=jnp.bfloat16,
):
    """Packed multi-admission prefill: one forward for ``B_p`` sequences'
    next prompt chunks under ONE weight stream.

    ``token_ids`` is ``[B_p, C]``: row ``b`` is the next uncached chunk
    of an in-flight admission whose K/V lives in cache row ``slots[b]``
    and whose ``offsets[b]`` tokens (earlier chunks and/or a radix-cached
    prefix) are already written there; chunk position ``j`` occupies
    absolute position ``offsets[b] + j``.  This is :func:`verify_ragged`
    with a per-row cache-row indirection: the attention decomposes into
    the strict cache window (``key_pos < offsets[b]``, gathered from row
    ``slots[b]``) and the exact in-chunk causal term, joined in one
    softmax — so serial chunked prefill (B_p sequential batch-1 chunk
    forwards, each streaming the full weight tree) collapses to one
    forward whose weight stream is amortized across all B_p admissions.

    Rows may be PARKED by passing ``offsets[b] == capacity``: the commit
    scatter drops their writes (``mode="drop"``) and their logits are
    garbage the caller ignores — that is how a packed call padded up to
    a power-of-two B_p bucket keeps every shape static.

    Returns ``(logits [B_p, C, vocab] float32, cache)`` with each real
    row's chunk K/V committed at ``(slots[b], offsets[b] + j)`` by one
    batched drop-scatter per buffer and ``lengths`` UNCHANGED — the
    engine's finalize step sets a slot's length when its LAST chunk
    lands (until then the row stays inactive and decode ticks park
    their writes for it; see :func:`_finish_decode`).
    """
    b, s = token_ids.shape
    quant = isinstance(cache, QuantRaggedKVCache)
    x = _embed(params, token_ids, dtype)

    positions = offsets[:, None] + jnp.arange(s)[None, :]  # [B_p, C]
    cos, sin = rope_cos_sin(positions, cfg, jnp.float32)

    capacity = cache.capacity
    key_pos = jnp.arange(capacity)
    # STRICT cache mask, exactly verify_ragged's: no chunk position has
    # been written yet, so every chunk query sees key_pos < offsets[b];
    # in-chunk positions are attended through the exact causal term.
    valid = key_pos[None, :] < offsets[:, None]  # [B_p, T]
    mask_bias = jnp.where(valid, 0.0, -1e9).astype(jnp.float32)[:, None, None]
    qpos = jnp.arange(s)
    chunk_causal = qpos[:, None] >= qpos[None, :]
    chunk_bias = jnp.where(chunk_causal, 0.0, -1e9).astype(jnp.float32)[
        None, None, None
    ]

    # Gather the B_p admissions' cache rows out of the full slot batch:
    # the compute (and the weight stream it amortizes) scales with the
    # B_p bucket, not max_slots.
    x, k_news, v_news = _walk_layers(
        params, cache, x, cfg, capacity,
        lambda x, lp, ck, cv: _block_verify_deferred(
            x, lp, ck, cv, cos, sin, mask_bias, chunk_bias, cfg
        ),
        rows=slots,
    )
    logits = _head(params, x, cfg)
    return logits, _commit_chunk_at(cache, k_news, v_news, slots, offsets, quant)


@jax.named_scope("kv_commit")
def _commit_chunk_at(cache, k_news, v_news, slots, offsets, quant):
    """Commit a packed prefill chunk's K/V: row ``b``'s token ``j`` lands
    at ``(slots[b], offsets[b] + j)`` — :func:`_commit_chunk` with a
    per-row cache-row indirection.  Parked rows (``offsets[b] ==
    capacity``) drop every write.  ``unique_indices`` contract — the
    (slot, position) tuples must be pairwise distinct, which holds when
    (a) REAL rows carry distinct slots (the engine reserves one cache
    row per admission) with in-range positions, and (b) PARKED rows
    carry slots distinct from each other (their positions start at
    ``capacity``, so they cannot collide with a real row's tuple even
    on an equal slot value)."""
    pos = offsets[:, None] + jnp.arange(k_news.shape[2])[None, :]
    return _commit_at(cache, k_news, v_news, slots, pos, quant)


# Per-row roles for the unified super-step (super_step_ragged): what each
# batch row is doing inside ONE dispatch.  IDLE rows park every write.
ROLE_IDLE = 0
ROLE_DECODE = 1
ROLE_VERIFY = 2
ROLE_PREFILL = 3


@jax.named_scope("kv_commit")
def _commit_block_at(cache, k_news, v_news, base, counts, quant):
    """Commit a super-step chunk's K/V with PER-POSITION parking: row
    ``b``'s token ``j`` lands at ``base[b] + j`` when ``j < counts[b]``
    and parks past capacity otherwise — :func:`_commit_chunk` whose park
    granularity is a column, not a whole row, because one super-step row
    commits 1 (decode), ``draft_len+1`` (verify) or ``C`` (prefill)
    columns out of the same static-width block.

    ``unique_indices`` contract: rows are pairwise distinct, a row's
    valid positions ``base[b]..base[b]+counts[b]-1`` are strictly
    increasing and bounded by ``capacity + S - 1`` (drop-scatter spill),
    and its parked positions start at ``capacity + S`` — the two ranges
    cannot collide, so every (row, position) tuple stays distinct."""
    b, s = k_news.shape[1:3]
    j = jnp.arange(s)[None, :]
    pos = jnp.where(
        j < counts[:, None],
        base[:, None] + j,
        jnp.int32(cache.capacity + s) + j,
    )
    return _commit_at(cache, k_news, v_news, jnp.arange(b), pos, quant)


def super_step_ragged(
    params: dict,
    token_block: jax.Array,
    cache: "RaggedKVCache | QuantRaggedKVCache",
    cfg: LlamaConfig,
    *,
    roles: jax.Array,
    offsets: jax.Array,
    counts: jax.Array,
    draft_len: jax.Array,
    active: jax.Array,
    remaining: jax.Array,
    eos_ids: jax.Array,
    steps: int,
    sample_fn,
    sample_carry=None,
    dtype=jnp.bfloat16,
    window: int | None = None,
):
    """ONE dispatch advancing a ragged batch of MIXED roles: per row,
    a packed-prefill chunk commit (``ROLE_PREFILL``), a fused-K decode
    step with the on-device sampling chain (``ROLE_DECODE``), or a
    speculative verify (``ROLE_VERIFY``) — the engine's whole tick as a
    single program, so the compile/warmup space collapses from the
    (decode + verify-chain + multistep + packed-B_p) cross-product to
    one variant per (window, sampling-mode).

    ``token_block`` int32 ``[B, S]``: column 0 is a decode/verify row's
    pending token (last emitted, unfed) or a prefill row's first chunk
    token; verify rows carry their draft in columns ``1..draft_len``;
    prefill rows carry their chunk in columns ``0..C-1``; everything
    past ``counts[b]`` is padding.  ``offsets`` is a prefill row's
    absolute chunk write base (other roles read their cache length);
    ``counts`` is how many leading block columns really commit (0 parks
    the row — see :func:`_commit_block_at`); ``active`` gates emission
    and length advance exactly like the split programs.

    The wide forward IS :func:`verify_ragged`'s: a strict cache mask
    (``key_pos < base[b]``) joined with the exact in-chunk causal term
    in one softmax, so column 0 of a decode row is the same class of
    computation as a plain decode step (int8kv included — see
    :func:`_block_verify_deferred`), and a verify row's columns match
    :func:`verify_ragged` column-for-column.  After the wide step,
    decode rows run ``steps - 1`` more fused iterations through
    :func:`decode_multistep` — same EOS/budget latch, same per-step key
    split, so seeded sampling stays token-for-token reproducible
    against the split programs.

    ``window`` (STATIC) must cover every row's worst case: a decode
    row's ``length + steps - 1``, a verify row's ``length``, a prefill
    row's ``offset`` (see the engine's ``superstep_window`` pre-pick).

    Returns ``(logits [B, S, vocab] f32, tok_block [B, steps], valid
    [B], greedy [B, S], accepted [B], toks [B, 1], cache, active_out,
    remaining_out, carry_out)``: ``logits``/``greedy``/``accepted``
    serve the verify and prefill-finalize consumers; ``tok_block`` /
    ``valid`` are the decode rows' emissions (column layout of
    :func:`decode_multistep`); ``lengths`` advance on-device by each
    decode row's emitted count and each verify row's ``accepted + 1``
    (prefill rows advance at finalize, engine-side, exactly like the
    packed path)."""
    from .sampling import speculative_accept

    b, s = token_block.shape
    quant = isinstance(cache, QuantRaggedKVCache)
    lengths = cache.lengths
    window = _attended_window(cache, window)

    is_dec = roles == ROLE_DECODE
    is_ver = roles == ROLE_VERIFY
    is_pre = roles == ROLE_PREFILL
    # Write/read base per row: a prefill row sits at its chunk offset
    # (its length stays 0 until finalize), every other role at its
    # cache length — the one indirection that lets three programs share
    # a forward.
    base = jnp.where(is_pre, offsets, lengths).astype(jnp.int32)

    x = _embed(params, token_block, dtype)
    positions = base[:, None] + jnp.arange(s)[None, :]  # [B, S]
    cos, sin = rope_cos_sin(positions, cfg, jnp.float32)

    key_pos = jnp.arange(window)
    # STRICT cache mask (verify_ragged's): no block position has been
    # written yet, so every query sees exactly key_pos < base[b]; the
    # block's own earlier columns are attended via the exact in-chunk
    # causal term.
    valid_mask = key_pos[None, :] < base[:, None]  # [B, W]
    mask_bias = jnp.where(valid_mask, 0.0, -1e9).astype(jnp.float32)[
        :, None, None
    ]
    qpos = jnp.arange(s)
    chunk_causal = qpos[:, None] >= qpos[None, :]
    chunk_bias = jnp.where(chunk_causal, 0.0, -1e9).astype(jnp.float32)[
        None, None, None
    ]

    x, k_news, v_news = _walk_layers(
        params, cache, x, cfg, window,
        lambda x, lp, ck, cv: _block_verify_deferred(
            x, lp, ck, cv, cos, sin, mask_bias, chunk_bias, cfg
        ),
    )
    logits = _head(params, x, cfg)  # [B, S, vocab] f32

    cache = _commit_block_at(cache, k_news, v_news, base, counts, quant)

    # Verify consumers: exact greedy acceptance over the wide logits —
    # columns past a row's draft_len are capped out by the per-row
    # budget inside speculative_accept, so the static S padding never
    # changes the accepted count.
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, S]
    accepted, nxt_v = speculative_accept(token_block, greedy, draft_len)
    ver_act = is_ver & active
    accepted = jnp.where(ver_act, accepted, 0)

    # Decode rows' step 1 of K: sample column 0 under the same rule and
    # latch order as decode_multistep's scan body.
    act_dec = active & is_dec
    carry, sampled = sample_fn(logits[:, 0, :], sample_carry)
    nxt_d = jnp.where(act_dec, sampled.astype(jnp.int32), token_block[:, 0])
    valid0 = act_dec.astype(jnp.int32)
    remaining1 = remaining - valid0
    act1 = act_dec & (nxt_d != eos_ids) & (remaining1 > 0)

    lengths1 = lengths + valid0 + jnp.where(ver_act, accepted + 1, 0)
    cache = cache._replace(lengths=lengths1)

    toks1 = jnp.where(ver_act, nxt_v, nxt_d)[:, None]
    if steps > 1:
        (
            tok_rest, valid_rest, toks2, cache, act2, rem2, carry,
        ) = decode_multistep(
            params, toks1, cache, cfg, act1, remaining1, eos_ids,
            steps - 1, sample_fn, sample_carry=carry, dtype=dtype,
            window=window,
        )
        tok_block_out = jnp.concatenate([nxt_d[:, None], tok_rest], axis=1)
        valid = valid0 + valid_rest
    else:
        tok_block_out = nxt_d[:, None]
        valid = valid0
        toks2, act2, rem2 = toks1, act1, remaining1

    return (
        logits, tok_block_out, valid, greedy, accepted,
        toks2, cache, act2, rem2, carry,
    )


def _finish_decode(params, x, k_news, v_news, cache, lengths, active, quant, cfg):
    """Shared decode tail: final norm, lm_head, and the cache commit.

    ``k_news``/``v_news`` are ``[L, B, NKV, D]`` — every layer's new
    token row, committed with one write pass (see ``_commit_rows``).
    """
    b = x.shape[0]
    logits = _head(params, x, cfg)
    advance = (
        jnp.ones((b,), jnp.int32) if active is None else active.astype(jnp.int32)
    )
    # Inactive rows write NOTHING (positions parked at capacity, dropped
    # by the scatter): an empty slot may be mid-packed-prefill, and its
    # rows are being written by the admission path — the old
    # always-write garbage token would corrupt the prefilled prompt.
    write_pos = lengths
    if active is not None:
        write_pos = jnp.where(active, lengths, jnp.int32(cache.capacity))
    if quant:
        kq, kqs = _quant_kv(k_news)
        vq, vqs = _quant_kv(v_news)
        return logits, QuantRaggedKVCache(
            _commit_rows(cache.k8, kq, write_pos),
            _commit_rows(cache.k_scale, kqs, write_pos),
            _commit_rows(cache.v8, vq, write_pos),
            _commit_rows(cache.v_scale, vqs, write_pos),
            lengths + advance,
        )
    return logits, RaggedKVCache(
        _commit_rows(cache.k, k_news.astype(cache.k.dtype), write_pos),
        _commit_rows(cache.v, v_news.astype(cache.v.dtype), write_pos),
        lengths + advance,
    )


@jax.named_scope("kv_commit")
def _commit_rows(buf: jax.Array, vals: jax.Array, lengths: jax.Array) -> jax.Array:
    """Write row ``b``'s new K/V at its own position, in place.

    ``buf`` is ``[L, B, T, NKV, ...]``, ``vals`` ``[L, B, NKV, ...]``; row
    ``b`` writes at position ``lengths[b]``, and a row parked at capacity
    (``lengths[b] == T``) must be DROPPED, never clamped onto its last
    real position: one batched scatter with drop semantics.  A position
    is a whole ``[NKV, ...]`` plane of the buffer, so the donated buffer
    is updated where it lies — no relayout of the cache around the write
    (tests/test_tpu_compile.py::test_ragged_programs_leave_the_cache_in_place)."""
    rows = jnp.arange(buf.shape[1])
    return buf.at[:, rows, lengths].set(
        vals.astype(buf.dtype), mode="drop", unique_indices=True
    )


@jax.named_scope("kv_commit")
def insert_sequence(
    cache: "RaggedKVCache | QuantRaggedKVCache",
    seq: KVCache,
    slot: jax.Array,
    length: jax.Array,
):
    """Install a prefilled single-sequence cache into batch row ``slot``.

    ``seq`` comes from :func:`prefill` with batch 1 (k/v ``[L,1,Tp,...]``,
    ``Tp <= capacity``); ``length`` is the sequence's REAL token count —
    prompt padding beyond it was written by prefill but is progressively
    overwritten by decode steps before it can ever be attended (see
    ``decode_ragged``).  ``slot``/``length`` may be traced values, so one
    compiled insert serves every slot.
    """
    slot = jnp.asarray(slot, jnp.int32)
    z = jnp.zeros((), jnp.int32)
    lengths = cache.lengths.at[slot].set(jnp.asarray(length, jnp.int32))
    # prefill's KVCache [L, 1, Tp, NKV, D] is the ragged cache's layout
    # with one row: the sequence lands as it stands.
    at = (z, slot, z, z, z)
    if isinstance(cache, QuantRaggedKVCache):
        k8, ks = _quant_kv(seq.k)
        v8, vs = _quant_kv(seq.v)
        ins = lambda buf, vals: lax.dynamic_update_slice(
            buf, vals.astype(buf.dtype), at
        )
        return QuantRaggedKVCache(
            ins(cache.k8, k8),
            ins(cache.k_scale, ks),
            ins(cache.v8, v8),
            ins(cache.v_scale, vs),
            lengths,
        )
    k = lax.dynamic_update_slice(cache.k, seq.k.astype(cache.k.dtype), at)
    v = lax.dynamic_update_slice(cache.v, seq.v.astype(cache.v.dtype), at)
    return RaggedKVCache(k, v, lengths)


# ---------------------------------------------------------------------------
# Sharding
# ---------------------------------------------------------------------------


def param_logical_axes(cfg: LlamaConfig | None = None) -> dict:
    """Logical axes (leading ``None`` on stacked layer params = scan axis)."""
    return {
        "embed": ("vocab", "embed"),
        "layers": {
            "attn_norm": (None, "embed"),
            "q": (None, "embed", "heads"),
            "k": (None, "embed", "kv_heads"),
            "v": (None, "embed", "kv_heads"),
            "o": (None, "heads", "embed"),
            "mlp_norm": (None, "embed"),
            "gate": (None, "embed", "mlp"),
            "up": (None, "embed", "mlp"),
            "down": (None, "mlp", "embed"),
        },
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


def cache_logical_axes() -> KVCache:
    """Sharding for the KV cache: kv_heads on tp, batch on dp."""
    return KVCache(
        k=(None, "batch", None, "kv_heads", "head_dim"),
        v=(None, "batch", None, "kv_heads", "head_dim"),
        length=None,
    )
