"""The plain reference of the Laguna decoder (`model_type: laguna`): GQA
layers of two kinds, full attention with YaRN on half the head and
sliding-window attention with plain RoPE on all of it, each kind with its
own count of query heads beside 8 KV heads; a sigmoid gate a head; one
leading dense layer, then softmax-routed experts (top-k renormalised and
scaled) of which THIS chip holds a share, beside an ungated shared
expert.  With its seeded artifact, its count functions and the
comparison that decides `correct`.  A configuration names it
(`"reference": "laguna_decoder"`); run.py asks it three things:
`write_artifact(path, model, seed)`, `shapes(model)`, `compare(...)`.

The layer equations in straightforward `jax.numpy`, float32 at `highest`
precision, no cache, no batching, no kernels: the window is a mask over
the whole sequence, YaRN's frequencies are written here from the formula,
on weights it makes itself from the seed.  It imports nothing of the
program (the artifact writer alone asks the program's loader for its
metadata files and checks that the program's config of the flavor knows
every key it writes).  A layer's weights at a time, a row at a time,
attention a block of 128 queries at a time, one expert at a time, so a
row of 8320 positions fits.

With `rms(x) = x / sqrt(mean(x^2) + eps)`, residual `h`, per layer `l` of
kind `k` (`layer_types`), `H_k` query heads (`num_attention_heads_per_
layer`), `KV` key/value heads of `D`, query head `i` reading KV head
`floor(i / (H_k / KV))`; `t` a query's position, `s` a key's:

- `x = rms(h) w_1`; `q = x W_q`, `k = x W_k`, `v = x W_v` (no bias, no q/k
  norm).
- Rotary on half-split pairs (`(i, i + R/2)` of the first `R` dims):
  full: `R = partial_rotary_factor D`, `inv_freq_i = (1 - r_i)
  theta^(-2i/R) / factor + r_i theta^(-2i/R)`, `r_i = 1 - clamp((i -
  low) / (high - low), 0, 1)`, `low = floor(c(beta_fast))`, `high =
  ceil(c(beta_slow))`, `c(beta) = R ln(L0 / (2 pi beta)) / (2 ln theta)`
  (`L0` the original context); cos and sin times `attention_factor`.
  Sliding: `R = D`, `inv_freq_i = theta^(-2i/R)`.
- `o_i(t) = sum_s softmax_s(q_i(t).k(s) / sqrt(D)) v(s)` over `s <= t`
  (full) or `t - window < s <= t` (sliding).
- `o_i <- sigmoid(x w_gate,i) o_i`; `h += [o_1 .. o_H] W_o`.
- `x = rms(h) w_2`; `mlp_only_layers`: `h += SwiGLU(x)` of
  `intermediate_size`; else `p = softmax(x W_r)` over all
  `router_experts`; the top-k of `p`, `w = routed_scaling_factor p /
  sum_topk p`; `h += sum_{k held here} w_k E_k(x) + E_shared(x)`: what
  the experts held elsewhere would add is left out.
- `rms(h) w_f`, untied head over the vocabulary slice (a smaller
  vocabulary).

Departures and assumptions are in the configuration's file.

Compared: as `qwen3_next_decoder.py` (served tokens teacher-forced; the
gap by which a served token's reference logit lies below the
reference's best; `max_logit_gap` the widest, `mean_logit_gap` their
mean over every served token, with no trim: at these widths more than
85 % of the int8 control's gaps are 0 too, so the trimmed mean of the
other references reads 0 for int8 and cannot tell it from bf16).  Controls
(`--control 1`), each in the program's place: the reference computed in
int8 as an int8 matrix unit takes its operands, every weight matrix
rounded to 127 levels a side per output channel and every activation it
multiplies per row (int4 logged beside it, and the weights alone rounded
to int8 as `control_int8w_*`), and the reference itself with the WINDOW
DROPPED (the sliding layers attend every earlier position: what a program
that ignores the window computes).  The readings under `control_` are, a
check, the SMALLER of the int8 control's and the window-dropped
control's, so the one `correct: false` of a `--control 1` run holds for
both; each control's own are logged as `control_int8_*` and
`control_window_*`, and the reference with plain RoPE in its full layers
(YaRN dropped) beside them as `control_yarn_*`."""

from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from harness import weights

FLAVOR = "gdn-moe-generate"  # the program's name for this family
CONTROLS = {"control_int8": 127, "control_int4": 7}  # levels a side
FULL, SLIDING = "full_attention", "sliding_attention"
QUERY_BLOCK = 128


# ---------------------------------------------------------------------------
# Geometry and leaves
# ---------------------------------------------------------------------------


def geometry(model: dict) -> dict:
    """The program's artifact config from the published config's keys:
    the first `num_hidden_layers` entries of the per-layer tables."""
    fixed = {"attention_bias": False, "norm_topk_prob": True,
             "decoder_sparse_step": 1, "tie_word_embeddings": False,
             "gating": "per-head", "moe_router_logit_softcapping": 0,
             "moe_apply_router_weight_on_input": False}
    for key, want in fixed.items():
        if key in model and model[key] != want:
            raise ValueError(
                f"{key}={model[key]!r}: this reference implements {want!r} only")
    n = int(model["num_hidden_layers"])
    kinds = list(model["layer_types"][:n])
    heads = {}
    for kind, h in zip(kinds, model["num_attention_heads_per_layer"][:n]):
        if heads.setdefault(kind, int(h)) != int(h):
            raise ValueError(f"{kind} layers of more than one head count")
    dense = [l for l in model["mlp_only_layers"] if l < n]
    mlp = ["dense" if l in dense else "sparse" for l in range(n)]
    if list(model["mlp_layer_types"][:n]) != mlp or set(
            model["gating_types"][:n]) != {"per_head"}:
        raise ValueError("mlp_layer_types / gating_types disagree with "
                         "mlp_only_layers / gating")
    rope = model["rope_parameters"]
    full, slide = rope[FULL], rope[SLIDING]
    if full["rope_type"] != "yarn" or slide["rope_type"] != "default":
        raise ValueError("this reference implements YaRN full layers and "
                         "default-RoPE sliding layers only")
    return {
        "vocab_size": int(model["vocab_size"]),
        "hidden_size": int(model["hidden_size"]),
        "num_layers": n,
        "layer_types": kinds,
        "num_heads": heads[FULL],
        "swa_num_heads": heads.get(SLIDING, heads[FULL]),
        "num_kv_heads": int(model["num_key_value_heads"]),
        "head_dim": int(model["head_dim"]),
        "sliding_window": int(model["sliding_window"]),
        "rope_theta": float(full["rope_theta"]),
        "partial_rotary_factor": float(full["partial_rotary_factor"]),
        "rope_type": "yarn",
        "rope_factor": float(full["factor"]),
        "rope_original_max_position": int(full["original_max_position_embeddings"]),
        "rope_beta_fast": float(full["beta_fast"]),
        "rope_beta_slow": float(full["beta_slow"]),
        "rope_attention_factor": float(full["attention_factor"]),
        "swa_rope_theta": float(slide["rope_theta"]),
        "swa_partial_rotary_factor": float(slide["partial_rotary_factor"]),
        "attention_gate": "headwise",
        "norm": "plain",
        "qk_norm": False,
        "shared_expert_gate": False,
        "mlp_only_layers": dense,
        "intermediate_size": int(model["intermediate_size"]),
        "moe_intermediate_size": int(model["moe_intermediate_size"]),
        "shared_expert_intermediate_size": int(
            model["shared_expert_intermediate_size"]),
        # The router's width is the published count; the file's
        # `num_experts` is how many of them this chip holds.
        "n_routed_experts": int(model["router_experts"]),
        "n_local_experts": int(model["num_experts"]),
        "local_expert_start": int(model["local_expert_start"]),
        "num_experts_per_tok": int(model["num_experts_per_tok"]),
        "routed_scaling_factor": float(model["moe_routed_scaling_factor"]),
        "max_seq": int(model["max_position_embeddings"]),
        "rms_eps": float(model["rms_norm_eps"]),
    }


def kinds(g: dict) -> list[str]:
    return list(g["layer_types"])


def heads(g: dict, kind: str) -> int:
    return g["num_heads"] if kind == FULL else g["swa_num_heads"]


def rotary(g: dict, kind: str) -> int:
    factor = g["partial_rotary_factor"] if kind == FULL else g["swa_partial_rotary_factor"]
    return int(g["head_dim"] * factor)


def yarn_range(g: dict) -> tuple[int, int]:
    """`[low, high]`: `floor(c(beta_fast))`, `ceil(c(beta_slow))`, `c(beta)
    = R ln(L0 / (2 pi beta)) / (2 ln theta)`, held inside `[0, R - 1]` (the
    published code's clamp; [9, 18] at the published widths, inside)."""
    r, theta, l0 = rotary(g, FULL), g["rope_theta"], g["rope_original_max_position"]
    c = lambda beta: r * math.log(l0 / (2 * math.pi * beta)) / (2 * math.log(theta))
    return (max(math.floor(c(g["rope_beta_fast"])), 0),
            min(math.ceil(c(g["rope_beta_slow"])), r - 1))


def inv_freq(g: dict, kind: str, yarn: bool = True) -> np.ndarray:
    """A kind's inverse frequencies [R/2], float64, and the scale of its
    cos / sin (YaRN's `attention_factor`; 1 for plain RoPE)."""
    r = rotary(g, kind)
    theta = g["rope_theta"] if kind == FULL else g["swa_rope_theta"]
    base = theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    if kind != FULL or not yarn:
        return base, 1.0
    low, high = yarn_range(g)
    keep = 1.0 - np.clip((np.arange(r // 2) - low) / (high - low), 0.0, 1.0)
    return ((1.0 - keep) * base / g["rope_factor"] + keep * base,
            g["rope_attention_factor"])


# Leaves of a layer, by group: `attn` (its kind's heads), `mlp` the leading
# dense SwiGLU, `moe` the expert block (the held experts' three matrices
# stacked on an expert axis).  Matrices N(0, STD) picked from
# `weights.normal_table()`; every norm weight 1.
ATTN_MATS = ("q", "k", "v", "attn_gate", "o")
MLP_MATS = ("gate", "up", "down")
MOE_MATS = ("router", "gate", "up", "down", "shared_gate", "shared_up",
            "shared_down")
EXPERT_MATS = ("gate", "up", "down")
_STREAMS = ("embed", "lm_head") + tuple(
    f"{grp}.{m}" for grp, mats in
    (("attn", ATTN_MATS), ("mlp", MLP_MATS), ("moe", MOE_MATS)) for m in mats)


def mat_shapes(g: dict, kind: str) -> dict[str, tuple[int, ...]]:
    """Shape of one `kind` layer's slice of every matrix leaf, `group.name`."""
    h, d, nkv = g["hidden_size"], g["head_dim"], g["num_kv_heads"]
    nh = heads(g, kind)
    i, im, ims = (g["intermediate_size"], g["moe_intermediate_size"],
                  g["shared_expert_intermediate_size"])
    held = g["n_local_experts"]
    return {
        "attn.q": (h, nh * d), "attn.k": (h, nkv * d), "attn.v": (h, nkv * d),
        "attn.attn_gate": (h, nh), "attn.o": (nh * d, h),
        "mlp.gate": (h, i), "mlp.up": (h, i), "mlp.down": (i, h),
        "moe.router": (h, g["n_routed_experts"]),
        "moe.gate": (held, h, im), "moe.up": (held, h, im),
        "moe.down": (held, im, h),
        "moe.shared_gate": (h, ims), "moe.shared_up": (h, ims),
        "moe.shared_down": (ims, h),
    }


def layer_mats(g: dict, l: int) -> list[str]:
    """Every matrix leaf of layer `l` (its index seeds the leaf's streams)."""
    ffn = (("mlp", MLP_MATS) if l in g["mlp_only_layers"] else ("moe", MOE_MATS))
    return [f"attn.{m}" for m in ATTN_MATS] + [f"{ffn[0]}.{m}" for m in ffn[1]]


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
        rng = np.random.default_rng([int(seed), 39, leaf, unit + 1, c])
        step = 1 << 18
        for i in range(0, part.size, step):
            n = min(step, part.size - i)
            part[i:i + n] = table[rng.integers(0, 65536, n, dtype=np.uint16)]

    list(ex.map(chunk, range(-(-flat.size // weights.CHUNK))))


# ---------------------------------------------------------------------------
# The artifact
# ---------------------------------------------------------------------------


def write_artifact(path: str, model: dict, seed: int) -> None:
    """Child mode of run.py: the seeded bf16 artifact in the program's own
    layout.  A program that does not know the flavor, or whose config of
    it does not know this model's keys (model_type laguna: layer kinds by
    table, a sliding kind on a ring, YaRN, a headwise gate), fails here in
    seconds, before gigabytes are written and read back as another
    model."""
    from pathlib import Path

    from tpumlops.models import registry
    from tpumlops.server import loader

    registry.get_builder(FLAVOR)
    g = geometry(model)
    known = loader._build_config(FLAVOR, {}).__dataclass_fields__
    missing = sorted(set(g) - set(known))
    if missing:
        raise ValueError(
            f"this program's {FLAVOR} config does not know {missing}: it "
            "cannot run model_type 'laguna'")
    loader.save_native_model(path, FLAVOR, {}, config=g)
    stream_npz(str(Path(path) / "params.npz"), seed, g, loader._SEP)


def artifact_key(name: str, layer: int, sep: str) -> str:
    """The program's tree: `layers` is a list of per-layer trees, a routed
    expert's matrices lie under `experts`."""
    group, mat = name.split(".")
    inner = f"experts{sep}{mat}" if group == "moe" and mat in EXPERT_MATS else mat
    return f"layers{sep}#{layer}{sep}{inner}"


def stream_npz(path: str, seed: int, g: dict, sep: str) -> None:
    """The whole tree as numpy's own `.npz`, a matrix at a time: one is
    filled while the one before is written."""
    import zipfile

    import ml_dtypes

    bf16 = np.dtype(ml_dtypes.bfloat16)
    h = g["hidden_size"]
    ones = np.ones((h,), bf16)
    small = {"final_norm": ones}
    mats = [("embed", "embed", -1, (g["vocab_size"], h)),
            ("lm_head", "lm_head", -1, (h, g["vocab_size"]))]
    for l, kind in enumerate(kinds(g)):
        small[f"layers{sep}#{l}{sep}attn_norm"] = ones
        small[f"layers{sep}#{l}{sep}ffn_norm"] = ones
        shapes = mat_shapes(g, kind)
        mats += [(artifact_key(n, l, sep), n, l, shapes[n]) for n in layer_mats(g, l)]
    weights.normal_table()

    def header(fp, arr_shape, dtype):
        np.lib.format.write_array_header_1_0(fp, {
            "descr": np.lib.format.dtype_to_descr(np.dtype(dtype)),
            "fortran_order": False, "shape": tuple(arr_shape)})

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
# Counts: the least any implementation must do, from the shapes alone
# ---------------------------------------------------------------------------


def pairs_to(first: float, count: float, most: float = 0) -> float:
    """(query, key) pairs of `count` queries at positions `first ..`, a
    query at `t` attending `t + 1` positions, at most `most` (0: all)."""
    last = first + count
    if not most or last <= most:
        return count * first + count * (count + 1) / 2.0
    whole = last - max(first, most - 1)
    return pairs_to(first, count - whole) + whole * most


class Counts:
    """Operations and bytes of this architecture's programs: the LEAST
    work any implementation of the equations must do, so a share of a
    roofline cannot pass 100 % because the program chose another form.

    - A full layer: its projections and gate, and `4 D` a query head an
      attended (query, key) pair, every earlier position attended; K and
      V rows read as far as attended, bf16.
    - A sliding layer: the same at its own head count, a query attending
      at most `sliding_window` positions, rows read as far as the window.
    - The leading dense layer's SwiGLU; every other layer's router, the
      ungated shared expert, the expected held experts of a token's top-k;
      of the experts held here the expected distinct ones are read, `E_here
      (1 - (1 - k/E)^n)` a layer for `n` real tokens under the uniform ids
      seeded weights give.
    - The head over the vocabulary slice, once a request in prefill
      (`prompt_flops`), a step in decode.

    These counts do not read the program's counters.  (A plain class:
    run.py loads this file outside `sys.modules`, where a dataclass cannot
    be made.)"""

    def __init__(self, g: dict):
        self.g = g

    @property
    def vocab(self) -> int:
        return self.g["vocab_size"]

    @property
    def layers(self) -> dict[str, int]:
        k = kinds(self.g)
        dense = len(self.g["mlp_only_layers"])
        return {"full": k.count(FULL), "sliding": k.count(SLIDING),
                "dense": dense, "moe": self.g["num_layers"] - dense}

    def attn_params(self, kind: str) -> int:
        """A layer's attention, every matrix: `W_q`, `W_k`, `W_v`, the
        gate, `W_o` at the kind's head count."""
        s = mat_shapes(self.g, kind)
        return sum(math.prod(s[f"attn.{m}"]) for m in ATTN_MATS)

    @property
    def dense_params(self) -> int:
        return 3 * self.g["hidden_size"] * self.g["intermediate_size"]

    @property
    def expert_params(self) -> int:
        """One routed expert (also the shared expert: the same width)."""
        return 3 * self.g["hidden_size"] * self.g["moe_intermediate_size"]

    @property
    def router_params(self) -> int:
        return self.g["hidden_size"] * self.g["n_routed_experts"]

    @property
    def shared_params(self) -> int:
        return 3 * self.g["hidden_size"] * self.g["shared_expert_intermediate_size"]

    @property
    def norm_params(self) -> int:
        """A layer's two RMSNorm weights."""
        return 2 * self.g["hidden_size"]

    @property
    def head_params(self) -> int:
        return self.g["hidden_size"] * self.g["vocab_size"]

    def layer_params(self, l: int) -> int:
        """Every parameter of layer `l` held here."""
        g = self.g
        n = self.attn_params(kinds(g)[l]) + self.norm_params
        if l in g["mlp_only_layers"]:
            return n + self.dense_params
        return (n + self.router_params + self.shared_params
                + g["n_local_experts"] * self.expert_params)

    @property
    def total_params(self) -> int:
        """Every parameter held: layers, embedding, head, final norm."""
        g = self.g
        return (sum(self.layer_params(l) for l in range(g["num_layers"]))
                + 2 * self.head_params + g["hidden_size"])

    @property
    def chosen_here(self) -> float:
        """Expected experts of a token's top-k that are held here."""
        g = self.g
        return g["num_experts_per_tok"] * g["n_local_experts"] / g["n_routed_experts"]

    @property
    def active_layer_params(self) -> float:
        """Matrix elements one token multiplies through in the layers."""
        n = self.layers
        return (n["full"] * self.attn_params(FULL)
                + n["sliding"] * self.attn_params(SLIDING)
                + n["dense"] * self.dense_params
                + n["moe"] * (self.router_params + self.shared_params
                              + self.chosen_here * self.expert_params))

    @property
    def unrouted_layer_bytes(self) -> int:
        """bf16 bytes of every layer leaf but the routed experts."""
        g = self.g
        routed = self.layers["moe"] * g["n_local_experts"] * self.expert_params
        return 2 * (sum(self.layer_params(l) for l in range(g["num_layers"])) - routed)

    # -- the cache (bf16 rows) ------------------------------------------------

    @property
    def kv_bytes(self) -> int:
        """K and V of one position in one layer, bf16."""
        return 2 * self.g["num_kv_heads"] * self.g["head_dim"] * 2

    @property
    def cache_bytes_per_position(self) -> int:
        """What a context-long position holds: the full layers' K and V."""
        return self.layers["full"] * self.kv_bytes

    @property
    def ring_bytes(self) -> int:
        """What a slot holds whatever its length: the sliding layers' K and
        V of the window's positions."""
        return self.layers["sliding"] * self.kv_bytes * self.g["sliding_window"]

    def experts_hit(self, tokens: float) -> float:
        """Expected distinct HELD experts a layer reads for `tokens`."""
        g = self.g
        p = g["num_experts_per_tok"] / g["n_routed_experts"]
        return g["n_local_experts"] * (1.0 - (1.0 - p) ** max(0.0, float(tokens)))

    def routed_bytes(self, tokens: float) -> float:
        return self.layers["moe"] * self.experts_hit(tokens) * 2 * self.expert_params

    def attention(self, first: float, count: float) -> tuple[float, float]:
        """(flops, cache bytes read) of `count` consecutive queries from
        position `first`, processed together: a full layer's every
        earlier position and its own, a sliding layer's last window."""
        g, n = self.g, self.layers
        d, w = g["head_dim"], g["sliding_window"]
        flops = 4.0 * d * (n["full"] * g["num_heads"] * pairs_to(first, count)
                           + n["sliding"] * g["swa_num_heads"]
                           * pairs_to(first, count, w))
        read = (self.cache_bytes_per_position * (first + count)
                + n["sliding"] * self.kv_bytes * (min(first, w - 1) + count))
        return flops, read

    # -- the programs ---------------------------------------------------------

    def decode_step(self, batch: float, ctx_sum: float) -> tuple[float, float]:
        mean = ctx_sum / max(batch, 1e-9)
        a_flops, a_read = self.attention(mean, 1.0)
        flops = 2.0 * (self.active_layer_params + self.head_params) * batch + a_flops * batch
        nbytes = (self.unrouted_layer_bytes + 2 * self.head_params
                  + self.routed_bytes(batch) + batch * a_read
                  + 2.0 * self.g["hidden_size"] * batch)
        return flops, nbytes

    def prefill_chunk(self, chunk: float, offset: float) -> tuple[float, float]:
        """The head is needed once a request; it is in `prompt_flops`."""
        a_flops, a_read = self.attention(offset, chunk)
        flops = 2.0 * self.active_layer_params * chunk + a_flops
        nbytes = (self.unrouted_layer_bytes + self.routed_bytes(chunk) + a_read
                  + 2.0 * self.g["hidden_size"] * chunk)
        return flops, nbytes

    def prompt_flops(self, prompt_len: int) -> float:
        return (2.0 * self.active_layer_params * prompt_len + 2.0 * self.head_params
                + self.attention(0, prompt_len)[0])

    def token_flops(self, ctx: int) -> float:
        return (2.0 * (self.active_layer_params + self.head_params)
                + self.attention(ctx, 1)[0])


def shapes(model: dict) -> Counts:
    return Counts(geometry(model))


# ---------------------------------------------------------------------------
# The forward pass
# ---------------------------------------------------------------------------


def _fake_quant(jnp, w, levels: int, axis: int = -2):
    """Round to `levels` a side, symmetric: a matrix per output channel
    (the reduced axis -2), an activation per row (`axis=-1`)."""
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / levels
    return jnp.clip(jnp.round(w / scale), -levels, levels) * scale


def build(g: dict, seq: int, levels: int | None = None, window: bool = True,
          yarn: bool = True, weights_only: bool = False):
    """The reference's functions for rows of `seq` positions: `attention`
    (a kind's), `mlp`, `moe_ffn` (each `x` [R, S, H] float32 and one
    layer's weights as stored -> `x`), `rope`, `route`, `head`, `gaps`.
    With `levels`, both operands of every product with a weight matrix
    are rounded first, the matrix per output channel and the activation
    per row, as an int8 matrix unit takes them (the int8 control), or
    with `weights_only` the matrices alone;
    `window=False` lets the sliding layers attend every earlier position,
    `yarn=False` gives the full layers plain RoPE (the two mechanism
    controls)."""
    from types import SimpleNamespace

    import jax
    import jax.numpy as jnp

    eps, d, nkv = g["rms_eps"], g["head_dim"], g["num_kv_heads"]
    top_k, scale = g["num_experts_per_tok"], g["routed_scaling_factor"]
    start, held = g["local_expert_start"], g["n_local_experts"]
    qb = QUERY_BLOCK if seq % QUERY_BLOCK == 0 else seq
    pos = jnp.arange(seq)

    def mat(w):
        w = w.astype(jnp.float32)
        return w if levels is None else _fake_quant(jnp, w, levels)

    def act(x):
        if levels is None or weights_only:
            return x
        return _fake_quant(jnp, x, levels, axis=-1)

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w

    def rope(x, kind):
        """Rotary on the half-split pairs of the first R dims of x [S,
        heads, D]; the rest passes."""
        freq, amp = inv_freq(g, kind, yarn)
        half = freq.shape[0]
        ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(freq, jnp.float32)[None, :]
        c = (jnp.cos(ang) * amp)[:, None, :]
        s = (jnp.sin(ang) * amp)[:, None, :]
        a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
        return jnp.concatenate([a * c - b * s, b * c + a * s, rest], axis=-1)

    def swiglu(x, gate, up, down):
        x = act(x)
        return act(jax.nn.silu(x @ gate) * (x @ up)) @ down

    def attend_row(h, w, kind):  # h [S, H]
        nh = heads(g, kind)
        most = g["sliding_window"] if kind == SLIDING and window else seq + 1
        x = act(rms(h, w["attn_norm"].astype(jnp.float32)))
        q = rope((x @ mat(w["q"])).reshape(seq, nh, d), kind)
        k = rope((x @ mat(w["k"])).reshape(seq, nkv, d), kind)
        v = (x @ mat(w["v"])).reshape(seq, nkv, d)
        q = q.reshape(seq, nkv, nh // nkv, d)

        def block(q0):
            qs = jax.lax.dynamic_slice_in_dim(q, q0, qb, 0)
            t = (q0 + jnp.arange(qb))[:, None]
            sc = jnp.einsum("qgrd,kgd->grqk", qs, k) / math.sqrt(d)
            see = (pos[None, :] <= t) & (t - pos[None, :] < most)
            sc = jnp.where(see[None, None], sc, -jnp.inf)
            return jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(sc, -1), v)

        o = jax.lax.map(block, jnp.arange(0, seq, qb)).reshape(seq, nh, d)
        gate = jax.nn.sigmoid(x @ mat(w["attn_gate"]))  # [S, heads]
        return h + act((o * gate[..., None]).reshape(seq, nh * d)) @ mat(w["o"])

    attention = {
        kind: jax.jit(lambda x, w, kind=kind: jax.lax.map(
            lambda row: attend_row(row, w, kind), x))
        for kind in (FULL, SLIDING)}

    @jax.jit
    def mlp(x, w):
        xn = rms(x, w["ffn_norm"].astype(jnp.float32))
        return x + swiglu(xn, mat(w["gate"]), mat(w["up"]), mat(w["down"]))

    def route(x, router):
        """Chosen experts [T, k] of ALL the routed experts and their
        weights, for normed x [T, H]."""
        p = jax.nn.softmax(act(x) @ router, axis=-1)
        chosen, idx = jax.lax.top_k(p, top_k)
        return idx, scale * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)

    @jax.jit
    def moe_ffn(x, w):
        rows, s, h = x.shape
        xn = rms(x, w["ffn_norm"].astype(jnp.float32)).reshape(rows * s, h)
        idx, wts = route(xn, mat(w["router"]))
        # The routing matrix [T, E]: a token's weight for each expert, 0
        # where it was not chosen; the held experts' columns are what is
        # computed here, each held expert seeing every token.
        dense = jnp.zeros((rows * s, g["n_routed_experts"]), jnp.float32)
        dense = dense.at[jnp.arange(rows * s)[:, None], idx].set(wts)
        here = dense[:, start:start + held]

        def one_expert(acc, ew):
            gate, up, down, col = ew
            return acc + col[:, None] * swiglu(xn, mat(gate), mat(up), mat(down)), None

        routed, _ = jax.lax.scan(
            one_expert, jnp.zeros_like(xn),
            (w["gate"], w["up"], w["down"], here.T))
        shared = swiglu(xn, mat(w["shared_gate"]), mat(w["shared_up"]),
                        mat(w["shared_down"]))
        return x + (routed + shared).reshape(rows, s, h)

    @jax.jit
    def head(x, idx, final_norm, lm_head):  # x [R,S,H], idx [R,A] -> logits [R,A,V]
        picked = jnp.take_along_axis(x, idx[..., None], axis=1)
        return act(rms(picked, final_norm.astype(jnp.float32))) @ mat(lm_head)

    @jax.jit
    def gaps(logits, tokens):
        best = jnp.max(logits, axis=-1)
        mine = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
        return best - mine, jnp.argmax(logits, axis=-1)

    return SimpleNamespace(attention=attention, mlp=mlp, moe_ffn=moe_ffn,
                           rope=rope, route=route, head=head, gaps=gaps)


class LayerSource:
    """One layer's weights at a time, made on the host from the seed while
    the device works on the layer before."""

    def __init__(self, g: dict, seed: int, ex):
        import ml_dtypes

        self.g, self.seed, self.ex = g, seed, ex
        self.bf16 = np.dtype(ml_dtypes.bfloat16)

    def layer(self, l: int) -> dict[str, np.ndarray]:
        """`attn.*` and `mlp.*` / `moe.*` leaves of layer `l`, the layer
        norms with the sub-layer that applies them."""
        shapes = mat_shapes(self.g, kinds(self.g)[l])
        out = {}
        for name in layer_mats(self.g, l):
            buf = np.empty(shapes[name], self.bf16)
            fill_unit(buf, self.seed, name, l, self.ex)
            out[name] = buf
        ones = np.ones((self.g["hidden_size"],), self.bf16)
        out["attn.attn_norm"] = ones
        out[("mlp" if l in self.g["mlp_only_layers"] else "moe") + ".ffn_norm"] = ones
        return out

    def whole(self, name: str) -> np.ndarray:
        h, vocab = self.g["hidden_size"], self.g["vocab_size"]
        if name == "final_norm":
            return np.ones((h,), self.bf16)
        buf = np.empty((vocab, h) if name == "embed" else (h, vocab), self.bf16)
        fill_unit(buf, self.seed, name, -1, self.ex)
        return buf


def forward(g: dict, seed: int, toks: np.ndarray, idx: np.ndarray,
            levels: int | None = None, window: bool = True, yarn: bool = True,
            weights_only: bool = False):
    """Logits [R, A, V] at positions `idx` [R, A] of token rows `toks`
    [R, S]: the whole forward pass, a layer at a time."""
    import jax
    import jax.numpy as jnp

    ref = build(g, toks.shape[1], levels, window, yarn, weights_only)
    weights.normal_table()
    with ThreadPoolExecutor(max_workers=weights.threads()) as ex, \
            ThreadPoolExecutor(max_workers=1) as ahead:
        src = LayerSource(g, seed, ex)
        embed = jnp.asarray(src.whole("embed"))
        x = embed[jnp.asarray(toks)].astype(jnp.float32)
        del embed
        nxt = ahead.submit(src.layer, 0)
        for l, kind in enumerate(kinds(g)):
            host = nxt.result()
            if l + 1 < g["num_layers"]:
                nxt = ahead.submit(src.layer, l + 1)
            part = lambda p: {k.split(".", 1)[1]: jnp.array(v)
                              for k, v in host.items() if k.startswith(p)}
            attn, ffn = part("attn."), part("mlp." if l in g["mlp_only_layers"] else "moe.")
            del host
            x = ref.attention[kind](x, attn)
            x = ref.mlp(x, ffn) if l in g["mlp_only_layers"] else ref.moe_ffn(x, ffn)
            jax.block_until_ready(x)
            del attn, ffn
        final_norm = jnp.asarray(src.whole("final_norm"))
        lm_head = jnp.asarray(src.whole("lm_head"))
    return ref.head(x, jnp.asarray(idx), final_norm, lm_head)


def readings(gap: np.ndarray) -> dict:
    """The numbers compared, of one model's gaps at the served positions."""
    return {"max_logit_gap": float(gap.max()),
            "mean_logit_gap": float(gap.mean()),
            # The gaps' quantiles at 50, 55, .. 95 %: what a limit is set
            # from (the log carries them; nothing compares them).
            "gap_quantiles": [round(float(np.quantile(gap, q / 100.0)), 5)
                              for q in range(50, 100, 5)]}


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
    if control:
        runs = {name: {"levels": levels} for name, levels in CONTROLS.items()}
        runs["control_int8w"] = {"levels": CONTROLS["control_int8"], "weights_only": True}
        runs["control_window"] = {"window": False}
        runs["control_yarn"] = {"yarn": False}
        for name, kw in runs.items():
            first_c = jnp.argmax(forward(g, seed, toks, idx, **kw), axis=-1)
            gap_c = np.asarray(gaps(logits, first_c)[0])
            if "levels" in kw:
                out[name + "_levels"] = kw["levels"]
            out.update({f"{name}_{k}": v for k, v in readings(gap_c[valid]).items()})
            out[name + "_argmax_agreement"] = float(
                (np.asarray(first_c) == ref_best)[valid].mean())
        # What run.py compares: a check, the smaller of the two controls'.
        for check in ("max_logit_gap", "mean_logit_gap"):
            out["control_" + check] = min(
                out[f"control_int8_{check}"], out[f"control_window_{check}"])
    out["seconds"] = round(time.perf_counter() - t_all, 2)
    return out
