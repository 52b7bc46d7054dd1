"""The plain reference of decoders with latent (MLA) attention and a
sparse-expert FFN (the DeepSeek-V3 block: one leading dense layer, then
routed + shared experts), its seeded artifact, its count functions, and
the comparison that decides `correct`.  A configuration names it
(`"reference": "mla_moe_decoder"`); run.py asks it three things:
`write_artifact(path, model, seed)`, `shapes(model)`, `compare(...)`.

The published layer equations in straightforward `jax.numpy`, float32 at
`highest` precision, no cache, no batching, no absorption of `W_kvb`, on
weights it makes itself from the seed.  It imports nothing of the
program (the artifact writer alone asks the program's loader for its
metadata files, as `harness/weights.py` does).  Layer by layer, one
expert at a time, so a 2.5 GB expert layer never stands in float32.

Per layer, residual `h`, `x = RMSNorm(h)` before each sub-layer:

- MLA: `cq = RMSNorm(x W_qa)`; `q = cq W_qb` -> heads x (nope | rope);
  `[c | kr] = x W_kva`; `c = RMSNorm(c)`; `kr` is one head shared by all;
  RoPE on `q_rope` and `kr`; `[k_nope | v] = c W_kvb`; scores
  `(q_nope k_nope + q_rope kr) / sqrt(nope + rope)`, causal softmax,
  `h += (P v) W_o`.
- FFN, the first `first_k_dense_replace` layers: SwiGLU.
- FFN after them: `s = sigmoid(x W_r)`; the top-k of `s + b` are chosen
  (`b` picks, it does not weigh); `w = s[chosen] / (sum + 1e-20) *
  routed_scaling_factor`; `h += sum_k w_k E_k(x) + E_shared(x)`.
- Final RMSNorm, untied head.

Departures from the published model, also in the configuration's file:

- RoPE pairing: `rope_interleave: true` pairs dimensions `(2i, 2i+1)`;
  this reference rotates those pairs where they lie.  The published code
  first permutes them into half-split order and rotates there, which
  gives the same dot products.
- The multi-token-prediction module (`num_nextn_predict_layers`) is not
  made or loaded: it adds no term to the next-token logits.
- `e_score_correction_bias` (`b`) is trained in the published model; here
  it is seeded, N(0, BIAS_STD): small against the scores' spread, so it
  decides some choices and not all.
- `n_group = topk_group = 1` (no group limit), sigmoid scores and
  `norm_topk_prob` are what the one published configuration has; other
  values are refused.

Compared: for every served token of the sampled requests, how far its
reference logit lies below the reference's best at that position, given
the served prefix (teacher forcing).  Greedy serving only.  Two numbers
go to run.py's two checks: `max_logit_gap`, the widest gap, and
`mean_logit_gap`, here a TRIMMED mean: the mean over the `1 - TRIM` share
of the tokens whose gaps are smallest.  Why trimmed (PERF.md 6, PR 27):
a top-k choice that flips on a near-tie between bfloat16 and float32
moves that token's logits by more than rounding every weight to int8
does, so a handful of such tokens carry the plain mean (`all_mean_logit_gap`
in the log) and it cannot tell the served path from an int8 model.  What
int8 does differently is move EVERY token a little: the served path puts
the reference's first choice first at ~90 % of the positions, an int8
model at ~71 %.  With the widest 15 % left out the served path reads 0
or nearly, the int8 model what its many small displacements sum to.

A control is the same reference with every matrix (experts, router and
head included) rounded to a few levels a side, symmetric, per output
channel: at each position, the gap of the token that model puts first.
The first of `CONTROLS` (int8, the precision next below the
configuration's bfloat16) stands in the program's place under
`--control 1`; int4 is read in the same run and logged beside it."""

from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from harness import weights

FLAVOR = "mla-moe-generate"  # the program's name for this architecture
BIAS_STD = 0.02  # of the seeded router bias (assumed)
CONTROLS = {"control": 127, "control_int4": 7}  # levels a side: int8, int4
TRIM = 0.15  # the share of the tokens, those with the widest gaps, left out of the mean


# ---------------------------------------------------------------------------
# Geometry and leaves
# ---------------------------------------------------------------------------


def geometry(model: dict) -> dict:
    """The program's artifact config from the published config's keys."""
    fixed = {"n_group": 1, "topk_group": 1, "scoring_func": "sigmoid",
             "norm_topk_prob": True, "rope_interleave": True,
             "moe_layer_freq": 1, "rope_scaling": None,
             "tie_word_embeddings": False, "attention_bias": False}
    for key, want in fixed.items():
        if key in model and model[key] != want:
            raise ValueError(
                f"{key}={model[key]!r}: this reference implements {want!r} only")
    return {
        "vocab_size": int(model["vocab_size"]),
        "hidden_size": int(model["hidden_size"]),
        "num_layers": int(model["num_hidden_layers"]),
        "num_heads": int(model["num_attention_heads"]),
        "q_lora_rank": int(model["q_lora_rank"]),
        "kv_lora_rank": int(model["kv_lora_rank"]),
        "qk_nope_head_dim": int(model["qk_nope_head_dim"]),
        "qk_rope_head_dim": int(model["qk_rope_head_dim"]),
        "v_head_dim": int(model["v_head_dim"]),
        "intermediate_size": int(model["intermediate_size"]),
        "moe_intermediate_size": int(model["moe_intermediate_size"]),
        "n_routed_experts": int(model["n_routed_experts"]),
        "n_shared_experts": int(model["n_shared_experts"]),
        "num_experts_per_tok": int(model["num_experts_per_tok"]),
        "first_k_dense_replace": int(model["first_k_dense_replace"]),
        "routed_scaling_factor": float(model["routed_scaling_factor"]),
        "max_seq": int(model["max_position_embeddings"]),
        "rope_theta": float(model["rope_theta"]),
        "rms_eps": float(model["rms_norm_eps"]),
        "n_group": int(model.get("n_group", 1)),
        "topk_group": int(model.get("topk_group", 1)),
        "scoring_func": str(model.get("scoring_func", "sigmoid")),
        "norm_topk_prob": bool(model.get("norm_topk_prob", True)),
    }


# Matrices of a layer, by kind: every layer has the attention's; the
# leading layers a dense SwiGLU, the rest the router, the routed experts
# (three matrices stacked on an expert axis) and the shared experts.
ATTN_MATS = ("q_a", "q_b", "kv_a", "kv_b", "o")
DENSE_MATS = ("gate", "up", "down")
MOE_MATS = ("router", "gate", "up", "down",
            "shared_gate", "shared_up", "shared_down")
_STREAMS = ("embed", "lm_head") + tuple(
    f"{grp}.{m}" for grp, mats in
    (("attn", ATTN_MATS), ("dense", DENSE_MATS), ("moe", MOE_MATS))
    for m in mats) + ("moe.router_bias",)


def mat_shapes(g: dict) -> dict[str, tuple[int, ...]]:
    """Shape of one layer's slice of every matrix leaf, `group.name`."""
    h, nh = g["hidden_size"], g["num_heads"]
    nope, rope, vd = g["qk_nope_head_dim"], g["qk_rope_head_dim"], g["v_head_dim"]
    qr, kvr = g["q_lora_rank"], g["kv_lora_rank"]
    i, im, e = g["intermediate_size"], g["moe_intermediate_size"], g["n_routed_experts"]
    ims = im * g["n_shared_experts"]
    return {
        "attn.q_a": (h, qr), "attn.q_b": (qr, nh * (nope + rope)),
        "attn.kv_a": (h, kvr + rope), "attn.kv_b": (kvr, nh * (nope + vd)),
        "attn.o": (nh * vd, h),
        "dense.gate": (h, i), "dense.up": (h, i), "dense.down": (i, h),
        "moe.router": (h, e),
        "moe.gate": (e, h, im), "moe.up": (e, h, im), "moe.down": (e, im, h),
        "moe.shared_gate": (h, ims), "moe.shared_up": (h, ims),
        "moe.shared_down": (ims, h),
    }


def group_layers(g: dict) -> dict[str, int]:
    dense = min(g["first_k_dense_replace"], g["num_layers"])
    return {"attn": g["num_layers"], "dense": dense,
            "moe": g["num_layers"] - dense}


def layer_mats(g: dict, l: int) -> list[tuple[str, int]]:
    """(leaf, unit) of every matrix of global layer `l`: the unit is the
    layer's index within its kind, which seeds the leaf's streams."""
    dense = group_layers(g)["dense"]
    kind, unit, mats = (("dense", l, DENSE_MATS) if l < dense
                        else ("moe", l - dense, MOE_MATS))
    return ([(f"attn.{m}", l) for m in ATTN_MATS]
            + [(f"{kind}.{m}", unit) for m in mats])


def fill_unit(out: np.ndarray, seed: int, name: str, unit: int, ex) -> None:
    """Fill `out` (one layer's slice of leaf `name`, or a whole unstacked
    leaf) with N(0, STD) bfloat16 picked from `weights.normal_table()`:
    chunk `c` of (leaf, unit) is a stream of its own, so the artifact's
    writer and the reference fill in any order and agree."""
    table = weights.normal_table()
    flat = out.reshape(-1).view(np.uint16)
    leaf = _STREAMS.index(name)

    def chunk(c: int) -> None:
        part = flat[c * weights.CHUNK:(c + 1) * weights.CHUNK]
        rng = np.random.default_rng([int(seed), 27, leaf, unit + 1, c])
        step = 1 << 18
        for i in range(0, part.size, step):
            n = min(step, part.size - i)
            part[i:i + n] = table[rng.integers(0, 65536, n, dtype=np.uint16)]

    list(ex.map(chunk, range(-(-flat.size // weights.CHUNK))))


def router_bias(g: dict, seed: int, moe_layer: int) -> np.ndarray:
    rng = np.random.default_rng(
        [int(seed), 27, _STREAMS.index("moe.router_bias"), moe_layer + 1])
    return (BIAS_STD * rng.standard_normal(g["n_routed_experts"])).astype(np.float32)


# ---------------------------------------------------------------------------
# The artifact
# ---------------------------------------------------------------------------


def write_artifact(path: str, model: dict, seed: int) -> None:
    """Child mode of run.py: the seeded bf16 artifact in the program's own
    layout.  A program that does not know the flavor fails here, before
    gigabytes are written and read back."""
    from pathlib import Path

    from tpumlops.models import registry
    from tpumlops.server import loader

    registry.get_builder(FLAVOR)
    g = geometry(model)
    loader.save_native_model(path, FLAVOR, {}, config=g)
    stream_npz(str(Path(path) / "params.npz"), seed, g, loader._SEP)


def artifact_key(name: str, layer: int, sep: str) -> str:
    """The program's tree: `layers` is a list of per-layer trees, a routed
    expert's matrices lie under `experts`."""
    group, mat = name.split(".")
    inner = f"experts{sep}{mat}" if group == "moe" and mat in DENSE_MATS else mat
    return f"layers{sep}#{layer}{sep}{inner}"


def stream_npz(path: str, seed: int, g: dict, sep: str) -> None:
    """The whole bf16 tree (norms are 1, the router bias float32) as
    numpy's own `.npz`, a matrix at a time: one is filled while the one
    before is written."""
    import zipfile

    import ml_dtypes

    bf16 = np.dtype(ml_dtypes.bfloat16)
    h = g["hidden_size"]
    src_shapes = mat_shapes(g)
    dense_layers = group_layers(g)["dense"]
    small: dict[str, np.ndarray] = {"final_norm": np.ones((h,), bf16)}
    mats = [("embed", "embed", -1, (g["vocab_size"], h)),
            ("lm_head", "lm_head", -1, (h, g["vocab_size"]))]
    for l in range(g["num_layers"]):
        for norm, width in (("attn_norm", h), ("ffn_norm", h),
                            ("q_norm", g["q_lora_rank"]),
                            ("kv_norm", g["kv_lora_rank"])):
            small[f"layers{sep}#{l}{sep}{norm}"] = np.ones((width,), bf16)
        if l >= dense_layers:
            small[f"layers{sep}#{l}{sep}router_bias"] = router_bias(
                g, seed, l - dense_layers)
        mats += [(artifact_key(n, l, sep), n, u, src_shapes[n])
                 for n, u in layer_mats(g, l)]
    weights.normal_table()

    def header(fp, arr_shape, dtype):
        np.lib.format.write_array_header_1_0(fp, {
            "descr": np.lib.format.dtype_to_descr(np.dtype(dtype)),
            "fortran_order": False, "shape": tuple(arr_shape)})

    # Two buffers of the largest matrix, filled in turn.
    largest = max(math.prod(shape) for _k, _n, _u, shape in mats)
    bufs = [np.empty(largest, bf16) for _ in range(2)]

    with zipfile.ZipFile(path, "w", zipfile.ZIP_STORED, allowZip64=True) as zf, \
            ThreadPoolExecutor(max_workers=weights.threads()) as ex, \
            ThreadPoolExecutor(max_workers=1) as ahead:
        for key, arr in small.items():
            with zf.open(key + ".npy", "w", force_zip64=True) as fp:
                header(fp, arr.shape, arr.dtype)
                fp.write(arr.tobytes())

        def make(i):
            _key, name, unit, shape = mats[i]
            buf = bufs[i % 2][:math.prod(shape)]
            fill_unit(buf, seed, name, unit, ex)
            return buf

        nxt = ahead.submit(make, 0)
        for i, (key, _name, _unit, shape) in enumerate(mats):
            buf = nxt.result()
            if i + 1 < len(mats):
                nxt = ahead.submit(make, i + 1)
            with zf.open(key + ".npy", "w", force_zip64=True) as fp:
                header(fp, shape, bf16)
                fp.write(memoryview(buf.view(np.uint16)).cast("B"))


# ---------------------------------------------------------------------------
# Counts: what the algorithm needs, from the shapes alone
# ---------------------------------------------------------------------------


class Counts:
    """Operations and bytes of this architecture's programs.  Flops come
    from the *active* parameters (attention as computed un-absorbed, the
    chosen routed experts and the shared ones, the router, the head);
    bytes from the weights a call must read: everything but the routed
    experts once, and of those the expected distinct ones under uniform
    routing (which seeded weights give), `E (1 - (1 - k/E)^n)` a layer for
    `n` real tokens, plus the latent cache as far as attended (bf16).
    These counts do not read the program's counters and do not follow how
    the program computes.  (A plain class: run.py loads this file outside
    `sys.modules`, where a dataclass cannot be made.)"""

    def __init__(self, g: dict):
        self.g = g

    @property
    def vocab(self) -> int:
        return self.g["vocab_size"]

    @property
    def layers(self) -> dict[str, int]:
        return group_layers(self.g)

    @property
    def attn_params(self) -> int:
        """One layer's attention matrices (q_a, q_b, kv_a, kv_b, o)."""
        s = mat_shapes(self.g)
        return sum(math.prod(s[f"attn.{m}"]) for m in ATTN_MATS)

    @property
    def dense_ffn_params(self) -> int:
        return 3 * self.g["hidden_size"] * self.g["intermediate_size"]

    @property
    def expert_params(self) -> int:
        """One routed expert (also one shared expert: the same width)."""
        return 3 * self.g["hidden_size"] * self.g["moe_intermediate_size"]

    @property
    def router_params(self) -> int:
        return self.g["hidden_size"] * self.g["n_routed_experts"]

    @property
    def head_params(self) -> int:
        return self.g["hidden_size"] * self.g["vocab_size"]

    @property
    def active_layer_params(self) -> int:
        """Matrix elements one token multiplies through in the layers."""
        n = self.layers
        moe = (self.attn_params + self.router_params + self.expert_params
               * (self.g["num_experts_per_tok"] + self.g["n_shared_experts"]))
        return n["dense"] * (self.attn_params + self.dense_ffn_params) + n["moe"] * moe

    @property
    def total_params(self) -> int:
        """Every parameter held (norms and the router bias left out)."""
        n = self.layers
        moe = (self.attn_params + self.router_params + self.expert_params
               * (self.g["n_routed_experts"] + self.g["n_shared_experts"]))
        return (n["dense"] * (self.attn_params + self.dense_ffn_params)
                + n["moe"] * moe + 2 * self.head_params)

    @property
    def unrouted_layer_bytes(self) -> int:
        """bf16 bytes of every layer matrix but the routed experts."""
        n = self.layers
        moe = (self.attn_params + self.router_params
               + self.expert_params * self.g["n_shared_experts"])
        return 2 * (n["dense"] * (self.attn_params + self.dense_ffn_params)
                    + n["moe"] * moe)

    @property
    def cache_bytes_per_position(self) -> int:
        """The latent row: normalised latent and RoPE key, every layer."""
        return 2 * self.g["num_layers"] * (
            self.g["kv_lora_rank"] + self.g["qk_rope_head_dim"])

    def experts_hit(self, tokens: float) -> float:
        """Expected distinct routed experts a layer reads for `tokens`."""
        e, k = self.g["n_routed_experts"], self.g["num_experts_per_tok"]
        return e * (1.0 - (1.0 - k / e) ** max(0.0, float(tokens)))

    def routed_bytes(self, tokens: float) -> float:
        return self.layers["moe"] * self.experts_hit(tokens) * 2 * self.expert_params

    def attn_flops(self, keys_total: float) -> float:
        """QK^T over nope+rope and PV over v, summed over query positions."""
        per_key = 2.0 * self.g["num_heads"] * (
            self.g["qk_nope_head_dim"] + self.g["qk_rope_head_dim"]
            + self.g["v_head_dim"])
        return self.g["num_layers"] * per_key * keys_total

    def decode_step(self, batch: float, ctx_sum: float) -> tuple[float, float]:
        flops = (2.0 * (self.active_layer_params + self.head_params) * batch
                 + self.attn_flops(ctx_sum))
        nbytes = (self.unrouted_layer_bytes + 2 * self.head_params
                  + self.routed_bytes(batch)
                  + self.cache_bytes_per_position * (ctx_sum + batch)
                  + 2.0 * self.g["hidden_size"] * batch)
        return flops, nbytes

    def prefill_chunk(self, chunk: float, offset: float) -> tuple[float, float]:
        """The head is needed once a request; it is in `prompt_flops`."""
        keys = chunk * offset + chunk * (chunk + 1) / 2.0
        flops = 2.0 * self.active_layer_params * chunk + self.attn_flops(keys)
        nbytes = (self.unrouted_layer_bytes + self.routed_bytes(chunk)
                  + self.cache_bytes_per_position * (offset + chunk)
                  + 2.0 * self.g["hidden_size"] * chunk)
        return flops, nbytes

    def prompt_flops(self, prompt_len: int) -> float:
        keys = prompt_len * (prompt_len + 1) / 2.0
        return (2.0 * self.active_layer_params * prompt_len
                + 2.0 * self.head_params + self.attn_flops(keys))

    def token_flops(self, ctx: int) -> float:
        return (2.0 * (self.active_layer_params + self.head_params)
                + self.attn_flops(ctx))


def shapes(model: dict) -> Counts:
    return Counts(geometry(model))


# ---------------------------------------------------------------------------
# The forward pass
# ---------------------------------------------------------------------------


def _fake_quant(jnp, w, levels: int):
    """Round to `levels` a side, symmetric, per output channel (last axis)."""
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / levels
    return jnp.clip(jnp.round(w / scale), -levels, levels) * scale


def build(g: dict, seq: int, levels: int | None = None):
    """The reference's functions for rows of `seq` positions: `attention`,
    `dense_ffn`, `moe_ffn` (each `x` [R, S, H] float32 and one layer's
    weights as stored -> `x`), `route`, `head`, `gaps`.  With `levels`,
    every matrix is rounded first (the control)."""
    from types import SimpleNamespace

    import jax
    import jax.numpy as jnp

    nh = g["num_heads"]
    nope, rope, vd = g["qk_nope_head_dim"], g["qk_rope_head_dim"], g["v_head_dim"]
    kvr, eps = g["kv_lora_rank"], g["rms_eps"]
    top_k, scaling = g["num_experts_per_tok"], g["routed_scaling_factor"]

    def mat(w):
        w = w.astype(jnp.float32)
        return w if levels is None else _fake_quant(jnp, w, levels)

    def rms(x):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)

    pos = jnp.arange(seq, dtype=jnp.float32)
    inv = 1.0 / (g["rope_theta"] ** (jnp.arange(0, rope, 2, dtype=jnp.float32) / rope))
    ang = pos[:, None] * inv[None, :]  # [S, rope/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    causal = jnp.tril(jnp.ones((seq, seq), bool))

    def rotate(x):
        """RoPE on pairs (2i, 2i+1) of the last axis; x [S, ..., rope]."""
        shape = x.shape
        x = x.reshape(*shape[:-1], rope // 2, 2)
        c = cos.reshape(seq, *([1] * (x.ndim - 3)), rope // 2)
        s = sin.reshape(seq, *([1] * (x.ndim - 3)), rope // 2)
        even, odd = x[..., 0], x[..., 1]
        return jnp.stack([even * c - odd * s, even * s + odd * c], -1).reshape(shape)

    def swiglu(x, gate, up, down):
        return (jax.nn.silu(x @ gate) * (x @ up)) @ down

    def attend_row(h, w):  # h [S, H]
        x = rms(h)
        q = (rms(x @ w["q_a"]) @ w["q_b"]).reshape(seq, nh, nope + rope)
        q_nope, q_rope = q[..., :nope], rotate(q[..., nope:])
        ckr = x @ w["kv_a"]
        c, kr = rms(ckr[:, :kvr]), rotate(ckr[:, kvr:])
        kv = (c @ w["kv_b"]).reshape(seq, nh, nope + vd)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        sc = (jnp.einsum("qnd,knd->nqk", q_nope, k_nope)
              + jnp.einsum("qnd,kd->nqk", q_rope, kr)) / math.sqrt(nope + rope)
        sc = jnp.where(causal[None], sc, -jnp.inf)
        ctx = jnp.einsum("nqk,knd->qnd", jax.nn.softmax(sc, -1), v)
        return h + ctx.reshape(seq, nh * vd) @ w["o"]

    @jax.jit
    def attention(x, w):
        w = {k: mat(v) for k, v in w.items()}
        return jax.lax.map(lambda row: attend_row(row, w), x)

    @jax.jit
    def dense_ffn(x, w):
        return x + swiglu(rms(x), mat(w["gate"]), mat(w["up"]), mat(w["down"]))

    def route(x, router, bias):
        """Chosen experts [T, k] and their weights, for normed x [T, H]."""
        s = jax.nn.sigmoid(x @ router)
        _, idx = jax.lax.top_k(s + bias, top_k)
        chosen = jnp.take_along_axis(s, idx, axis=-1)
        return idx, chosen / (chosen.sum(-1, keepdims=True) + 1e-20) * scaling

    @jax.jit
    def moe_ffn(x, w):
        r, s, h = x.shape
        xn = rms(x).reshape(r * s, h)
        idx, wts = route(xn, mat(w["router"]), w["router_bias"])
        # The routing matrix [T, E]: a token's weight for each expert, 0
        # where it was not chosen.  Every expert then sees every token.
        dense = jnp.zeros((r * s, g["n_routed_experts"]), jnp.float32)
        dense = dense.at[jnp.arange(r * s)[:, None], idx].set(wts)

        def one_expert(acc, ew):
            gate, up, down, col = ew
            return acc + col[:, None] * swiglu(xn, mat(gate), mat(up), mat(down)), None

        routed, _ = jax.lax.scan(
            one_expert, jnp.zeros_like(xn),
            (w["gate"], w["up"], w["down"], dense.T))
        shared = swiglu(xn, mat(w["shared_gate"]), mat(w["shared_up"]),
                        mat(w["shared_down"]))
        return x + (routed + shared).reshape(r, s, h)

    @jax.jit
    def head(x, idx, lm_head):  # x [R,S,H], idx [R,A] -> logits [R,A,V]
        picked = jnp.take_along_axis(rms(x), idx[..., None], axis=1)
        return picked @ mat(lm_head)

    @jax.jit
    def gaps(logits, tokens):
        best = jnp.max(logits, axis=-1)
        mine = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
        return best - mine, jnp.argmax(logits, axis=-1)

    return SimpleNamespace(attention=attention, dense_ffn=dense_ffn,
                           moe_ffn=moe_ffn, route=route, head=head, gaps=gaps)


class LayerSource:
    """One layer's weights at a time, made on the host from the seed while
    the device works on the layer before."""

    def __init__(self, g: dict, seed: int, ex):
        import ml_dtypes

        self.g, self.seed, self.ex = g, seed, ex
        self.bf16 = np.dtype(ml_dtypes.bfloat16)
        self.shapes = mat_shapes(g)
        self.dense_layers = group_layers(g)["dense"]

    def layer(self, l: int) -> dict[str, np.ndarray]:
        out = {}
        for name, unit in layer_mats(self.g, l):
            buf = np.empty(self.shapes[name], self.bf16)
            fill_unit(buf, self.seed, name, unit, self.ex)
            out[name] = buf
        if l >= self.dense_layers:
            out["moe.router_bias"] = router_bias(self.g, self.seed, l - self.dense_layers)
        return out

    def whole(self, name: str) -> np.ndarray:
        shape = ((self.g["vocab_size"], self.g["hidden_size"]) if name == "embed"
                 else (self.g["hidden_size"], self.g["vocab_size"]))
        buf = np.empty(shape, self.bf16)
        fill_unit(buf, self.seed, name, -1, self.ex)
        return buf


def forward(g: dict, seed: int, toks: np.ndarray, idx: np.ndarray,
            levels: int | None = None):
    """Logits [R, A, V] at positions `idx` [R, A] of token rows `toks`
    [R, S]: the whole forward pass, a layer at a time."""
    import jax
    import jax.numpy as jnp

    ref = build(g, toks.shape[1], levels)
    weights.normal_table()
    with ThreadPoolExecutor(max_workers=weights.threads()) as ex, \
            ThreadPoolExecutor(max_workers=1) as ahead:
        src = LayerSource(g, seed, ex)
        embed = jnp.asarray(src.whole("embed"))
        x = embed[jnp.asarray(toks)].astype(jnp.float32)
        del embed
        nxt = ahead.submit(src.layer, 0)
        for l in range(g["num_layers"]):
            host = nxt.result()
            if l + 1 < g["num_layers"]:
                nxt = ahead.submit(src.layer, l + 1)
            w = {k.split(".", 1)[1]: jnp.array(v) for k, v in host.items()}
            del host
            x = ref.attention(x, {k: w.pop(k) for k in ATTN_MATS})
            x = ref.dense_ffn(x, w) if l < src.dense_layers else ref.moe_ffn(x, w)
            jax.block_until_ready(x)
            del w
        lm_head = jnp.asarray(src.whole("lm_head"))
    return ref.head(x, jnp.asarray(idx), lm_head)


def readings(gap: np.ndarray) -> dict:
    """The numbers compared, of one model's gaps at the served positions."""
    gap = np.sort(gap)
    kept = gap[: max(1, round(len(gap) * (1.0 - TRIM)))]
    return {"max_logit_gap": float(gap[-1]),
            "mean_logit_gap": float(kept.mean()),
            "all_mean_logit_gap": float(gap.mean())}


def compare(model: dict, seed: int, rows: list[tuple[list[int], list[int]]],
            seq: int, answers: int, control: bool = False) -> dict:
    """`rows`: (prompt ids, served tokens) of each sampled request.  `seq`
    and `answers` are the padded sizes (fixed per mix, so one compile)."""
    import time

    import jax
    import jax.numpy as jnp

    jax.config.update("jax_default_matmul_precision", "highest")
    g = geometry(model)
    R = len(rows)
    toks = np.zeros((R, seq), np.int32)
    idx = np.zeros((R, answers), np.int32)
    served = np.zeros((R, answers), np.int32)
    valid = np.zeros((R, answers), bool)
    for r, (prompt, out) in enumerate(rows):
        full = list(prompt) + list(out)
        if len(full) > seq or len(out) > answers:
            raise ValueError(f"row {r} ({len(prompt)}+{len(out)}) exceeds ({seq},{answers})")
        toks[r, :len(full)] = full
        n = len(out)
        idx[r, :n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
        served[r, :n] = out
        valid[r, :n] = True
    gaps = build(g, seq).gaps

    t_all = time.perf_counter()
    logits = forward(g, seed, toks, idx)
    gap, ref_best = gaps(logits, jnp.asarray(served))
    gap, ref_best = np.asarray(gap), np.asarray(ref_best)
    out = {
        "rows": R,
        "served_tokens": int(valid.sum()),
        **readings(gap[valid]),
        "argmax_agreement": float((ref_best == served)[valid].mean()),
    }
    for name, levels in CONTROLS.items() if control else ():
        first_c = jnp.argmax(forward(g, seed, toks, idx, levels), axis=-1)
        gap_c = np.asarray(gaps(logits, first_c)[0])
        out[name + "_levels"] = levels
        out.update({f"{name}_{k}": v for k, v in readings(gap_c[valid]).items()})
        out[name + "_argmax_agreement"] = float(
            (np.asarray(first_c) == ref_best)[valid].mean())
    out["seconds"] = round(time.perf_counter() - t_all, 2)
    return out
