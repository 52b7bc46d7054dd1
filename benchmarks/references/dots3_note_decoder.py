"""The plain reference of the dots3-note decoder (text path): layers of
two kinds, full latent attention with an indexer that keeps the
`index_topk` best earlier positions, and sliding-window latent attention
at widths of its own; a headwise gate and a rescale of the latents in
both; one leading dense layer, then routed + shared experts of which THIS
chip holds a share.  With its seeded artifact, its count functions and
the comparison that decides `correct`.  A configuration names it
(`"reference": "dots3_note_decoder"`); run.py asks it three things:
`write_artifact(path, model, seed)`, `shapes(model)`, `compare(...)`.

The layer equations in straightforward `jax.numpy`, float32 at `highest`
precision, no cache, no batching, no kernels, exact top-k (`lax.top_k`
over every earlier position), on weights it makes itself from the seed.
It imports nothing of the program (the artifact writer alone asks the
program's loader for its metadata files and checks that the program
knows layer kinds, as `mla_moe_decoder.py` checks the flavor).  A layer's
weights at a time, attention a block of 128 queries at a time, one expert
at a time, so a row of 8320 positions fits.

Per layer, residual `h`, `x = RMSNorm(h)` (eps `rms_norm_eps`) before
each sub-layer; `t` a query's position, `s` a key's:

- Full attention (H heads, nope | rope, v, ranks q / kv, theta):
  `c_q = a_q RMSNorm(x W_qa)`; `[q_nope | q_rope] = c_q W_qb`;
  `[c | kr] = x W_kva`; `c = a_kv RMSNorm(c)`; RoPE on `q_rope`, `kr`
  (one key head for all); `[k_nope | v] = c W_kvb`.
  Indexer: `qI_h = c_q WI_q,h`, `kI = LayerNorm(x WI_k)` (weight, bias,
  eps 1e-6), RoPE on the first `rope` dims of both, `w = x WI_w`;
  `I(t, s) = sum_h w_h(t) relu(qI_h(t) . kI(s))`; `S_t` = the `index_topk`
  positions `s <= t` of largest `I` (all of them while `t + 1 <=
  index_topk`; `lax.top_k` breaks a tie by the lower position).
  `o_h(t) = sum_{s in S_t} softmax_s((q_nope.k_nope + q_rope.kr) /
  sqrt(nope + rope)) v_h(s)`; `g = sigmoid(x W_g)`, a number a head;
  `h += [g_h o_h]_h W_o`.
- Sliding attention (`swa_*` widths): the same without the indexer,
  `S_t = {s : 0 <= t - s < sliding_window_size}`.
- `a_q = sqrt(hidden / q_rank)`, `a_kv = sqrt(hidden / kv_rank)` at the
  layer's own ranks (`apply_mla_qkv_lora_rescale`).
- FFN, the first `first_k_dense_replace` layers: SwiGLU.
- FFN after them: `sc = sigmoid(x W_r)` over all `router_experts`; the
  top-k of `sc + b` are chosen; `w = sc[chosen] / (sum + 1e-20) *
  routed_scaling_factor`; `h += sum_{k held here} w_k E_k(x) +
  E_shared(x)`: what the experts held elsewhere would add is left out.
- Final RMSNorm, untied head over the vocabulary slice (a smaller
  vocabulary).

Departures and assumptions are in the configuration's file.

Compared: as `mla_moe_decoder.py` (served tokens teacher-forced; the gap
by which a served token's reference logit lies below the reference's
best; `max_logit_gap` the widest, `mean_logit_gap` the TRIMMED mean, the
widest `TRIM` of the gaps left out).  A flipped 2048th key (a near-tie
of two index scores between bfloat16 and float32) swaps one key of 2048
in one layer's softmax for its neighbour in rank: both carry the
smallest weights the indexer gives, and the logits move by less than a
flipped top-8 expert does; both kinds of flip land in the trimmed 15 %.
Controls: every matrix rounded to a few levels a side per output
channel; int8 first (`--control 1`), int4 logged beside it."""

from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from harness import weights

FLAVOR = "mla-moe-generate"  # the program's name for this family
BIAS_STD = 0.02  # of the seeded router bias (assumed)
CONTROLS = {"control": 127, "control_int4": 7}  # levels a side: int8, int4
TRIM = 0.15  # the share of the tokens, those with the widest gaps, left out of the mean
INDEX_NORM_EPS = 1e-6
FULL, SLIDING = "full_attention", "sliding_attention"
QUERY_BLOCK = 128


# ---------------------------------------------------------------------------
# Geometry and leaves
# ---------------------------------------------------------------------------


def geometry(model: dict) -> dict:
    """The program's artifact config from the published config's keys."""
    fixed = {"scoring_func": "sigmoid", "norm_topk_prob": True,
             "moe_layer_freq": 1, "rope_scaling": None,
             "tie_word_embeddings": False, "attention_bias": False,
             "attention_gate_type": "headwise",
             "swa_attention_gate_type": "headwise"}
    for key, want in fixed.items():
        if key in model and model[key] != want:
            raise ValueError(
                f"{key}={model[key]!r}: this reference implements {want!r} only")
    layers = int(model["num_hidden_layers"])
    kinds = [str(k) for k in model["layer_types"][:layers]]
    if len(kinds) != layers or set(kinds) - {FULL, SLIDING}:
        raise ValueError(f"layer_types gives no kind for each of {layers} layers")
    return {
        "vocab_size": int(model["vocab_size"]),
        "hidden_size": int(model["hidden_size"]),
        "num_layers": layers,
        "layer_types": kinds,
        "num_heads": int(model["num_attention_heads"]),
        "q_lora_rank": int(model["q_lora_rank"]),
        "kv_lora_rank": int(model["kv_lora_rank"]),
        "qk_nope_head_dim": int(model["qk_nope_head_dim"]),
        "qk_rope_head_dim": int(model["qk_rope_head_dim"]),
        "v_head_dim": int(model["v_head_dim"]),
        "rope_theta": float(model["rope_theta"]),
        "sliding_window": int(model["sliding_window_size"]),
        "swa_num_heads": int(model["swa_num_attention_heads"]),
        "swa_q_lora_rank": int(model["swa_q_lora_rank"]),
        "swa_kv_lora_rank": int(model["swa_kv_lora_rank"]),
        "swa_qk_nope_head_dim": int(model["swa_qk_nope_head_dim"]),
        "swa_qk_rope_head_dim": int(model["swa_qk_rope_head_dim"]),
        "swa_v_head_dim": int(model["swa_v_head_dim"]),
        "swa_rope_theta": float(model["swa_rope_theta"]),
        "index_n_heads": int(model["index_n_heads"]),
        "index_head_dim": int(model["index_head_dim"]),
        "index_topk": int(model["index_topk"]),
        "attention_gate": "headwise",
        "lora_rescale": bool(model["apply_mla_qkv_lora_rescale"]),
        "intermediate_size": int(model["intermediate_size"]),
        "moe_intermediate_size": int(model["moe_intermediate_size"]),
        # The router's width is the published count; the file's
        # `n_routed_experts` is how many of them this chip holds.
        "n_routed_experts": int(model["router_experts"]),
        "n_local_experts": int(model["n_routed_experts"]),
        "local_expert_start": int(model["local_expert_start"]),
        "n_shared_experts": int(model["n_shared_experts"]),
        "num_experts_per_tok": int(model["num_experts_per_tok"]),
        "first_k_dense_replace": int(model["first_k_dense_replace"]),
        "routed_scaling_factor": float(model["routed_scaling_factor"]),
        "max_seq": int(model["max_position_embeddings"]),
        "rms_eps": float(model["rms_norm_eps"]),
        "scoring_func": "sigmoid",
        "norm_topk_prob": True,
    }


def attn_dims(g: dict, kind: str) -> dict:
    """One layer kind's attention widths under plain names."""
    p = "" if kind == FULL else "swa_"
    return {
        "heads": g[p + "num_heads"], "q_rank": g[p + "q_lora_rank"],
        "kv_rank": g[p + "kv_lora_rank"], "nope": g[p + "qk_nope_head_dim"],
        "rope": g[p + "qk_rope_head_dim"], "v": g[p + "v_head_dim"],
        "theta": g[p + "rope_theta"],
    }


# Matrices of a layer, by group: `full` / `swa` the attention of the
# layer's kind (`idx_*` the indexer's, `attn_gate` the headwise gate), the
# leading layers a dense SwiGLU, the rest the router over every routed
# expert, the held experts' three matrices stacked on an expert axis, and
# the shared experts.
SWA_MATS = ("q_a", "q_b", "kv_a", "kv_b", "o", "attn_gate")
FULL_MATS = SWA_MATS + ("idx_q_b", "idx_k", "idx_w")
DENSE_MATS = ("gate", "up", "down")
MOE_MATS = ("router", "gate", "up", "down",
            "shared_gate", "shared_up", "shared_down")
_STREAMS = ("embed", "lm_head") + tuple(
    f"{grp}.{m}" for grp, mats in
    (("full", FULL_MATS), ("swa", SWA_MATS), ("dense", DENSE_MATS),
     ("moe", MOE_MATS)) for m in mats) + ("moe.router_bias",)


def mat_shapes(g: dict) -> dict[str, tuple[int, ...]]:
    """Shape of one layer's slice of every matrix leaf, `group.name`."""
    h = g["hidden_size"]
    i, im = g["intermediate_size"], g["moe_intermediate_size"]
    ims = im * g["n_shared_experts"]
    held = g["n_local_experts"]
    out = {
        "dense.gate": (h, i), "dense.up": (h, i), "dense.down": (i, h),
        "moe.router": (h, g["n_routed_experts"]),
        "moe.gate": (held, h, im), "moe.up": (held, h, im),
        "moe.down": (held, im, h),
        "moe.shared_gate": (h, ims), "moe.shared_up": (h, ims),
        "moe.shared_down": (ims, h),
    }
    for grp, kind in (("full", FULL), ("swa", SLIDING)):
        d = attn_dims(g, kind)
        nh = d["heads"]
        out.update({
            f"{grp}.q_a": (h, d["q_rank"]),
            f"{grp}.q_b": (d["q_rank"], nh * (d["nope"] + d["rope"])),
            f"{grp}.kv_a": (h, d["kv_rank"] + d["rope"]),
            f"{grp}.kv_b": (d["kv_rank"], nh * (d["nope"] + d["v"])),
            f"{grp}.o": (nh * d["v"], h),
            f"{grp}.attn_gate": (h, nh),
        })
    hi, di = g["index_n_heads"], g["index_head_dim"]
    out.update({"full.idx_q_b": (g["q_lora_rank"], hi * di),
                "full.idx_k": (h, di), "full.idx_w": (h, hi)})
    return out


def group_layers(g: dict) -> dict[str, int]:
    dense = min(g["first_k_dense_replace"], g["num_layers"])
    full = sum(k == FULL for k in g["layer_types"])
    return {"full": full, "swa": g["num_layers"] - full, "dense": dense,
            "moe": g["num_layers"] - dense}


def layer_mats(g: dict, l: int) -> list[tuple[str, int]]:
    """(leaf, unit) of every matrix of global layer `l`: the unit is the
    layer's index within its group, which seeds the leaf's streams."""
    dense = group_layers(g)["dense"]
    kind = g["layer_types"][l]
    grp, mats = ("full", FULL_MATS) if kind == FULL else ("swa", SWA_MATS)
    unit = sum(k == kind for k in g["layer_types"][:l])
    ffn, ffn_unit, ffn_mats = (("dense", l, DENSE_MATS) if l < dense
                               else ("moe", l - dense, MOE_MATS))
    return ([(f"{grp}.{m}", unit) for m in mats]
            + [(f"{ffn}.{m}", ffn_unit) for m in ffn_mats])


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
        rng = np.random.default_rng([int(seed), 33, leaf, unit + 1, c])
        step = 1 << 18
        for i in range(0, part.size, step):
            n = min(step, part.size - i)
            part[i:i + n] = table[rng.integers(0, 65536, n, dtype=np.uint16)]

    list(ex.map(chunk, range(-(-flat.size // weights.CHUNK))))


def router_bias(g: dict, seed: int, moe_layer: int) -> np.ndarray:
    rng = np.random.default_rng(
        [int(seed), 33, _STREAMS.index("moe.router_bias"), moe_layer + 1])
    return (BIAS_STD * rng.standard_normal(g["n_routed_experts"])).astype(np.float32)


# ---------------------------------------------------------------------------
# The artifact
# ---------------------------------------------------------------------------


def write_artifact(path: str, model: dict, seed: int) -> None:
    """Child mode of run.py: the seeded bf16 artifact in the program's own
    layout.  A program that does not know the flavor, or whose config of
    it knows no layer kinds, fails here, before gigabytes are written and
    read back as another model."""
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
            "cannot run a model of two layer kinds with an indexer")
    loader.save_native_model(path, FLAVOR, {}, config=g)
    stream_npz(str(Path(path) / "params.npz"), seed, g, loader._SEP)


def artifact_key(name: str, layer: int, sep: str) -> str:
    """The program's tree: `layers` is a list of per-layer trees, a routed
    expert's matrices lie under `experts`."""
    group, mat = name.split(".")
    inner = f"experts{sep}{mat}" if group == "moe" and mat in DENSE_MATS else mat
    return f"layers{sep}#{layer}{sep}{inner}"


def stream_npz(path: str, seed: int, g: dict, sep: str) -> None:
    """The whole bf16 tree (norms are 1, the indexer's key bias 0, the
    router bias float32) as numpy's own `.npz`, a matrix at a time: one is
    filled while the one before is written."""
    import zipfile

    import ml_dtypes

    bf16 = np.dtype(ml_dtypes.bfloat16)
    h = g["hidden_size"]
    src_shapes = mat_shapes(g)
    dense_layers = group_layers(g)["dense"]
    small: dict[str, np.ndarray] = {"final_norm": np.ones((h,), bf16)}
    mats = [("embed", "embed", -1, (g["vocab_size"], h)),
            ("lm_head", "lm_head", -1, (h, g["vocab_size"]))]
    for l, kind in enumerate(g["layer_types"]):
        d = attn_dims(g, kind)
        norms = [("attn_norm", h), ("ffn_norm", h), ("q_norm", d["q_rank"]),
                 ("kv_norm", d["kv_rank"])]
        if kind == FULL:
            norms.append(("idx_k_norm", g["index_head_dim"]))
            small[f"layers{sep}#{l}{sep}idx_k_bias"] = np.zeros(
                (g["index_head_dim"],), bf16)
        for norm, width in norms:
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
# Counts: the least any implementation must do, from the shapes alone
# ---------------------------------------------------------------------------


class Counts:
    """Operations and bytes of this architecture's programs: the LEAST
    work any implementation of the equations must do, so a share of a
    roofline cannot pass 100 % because the program chose another form.

    - A full layer's softmax core attends `min(t + 1, index_topk)` keys a
      query, in the cheaper of two forms: expanded (keys and values made
      from the latent once a distinct key, then heads x (nope + rope + v)
      a pair) or absorbed (`W_kvb` folded into query and context once a
      query, then heads x (2 rank + rope) a pair).
    - The indexer scores every one of the `t + 1` positions: index heads x
      index dim a pair.
    - A sliding layer attends `min(t + 1, window)` keys a query.
    - Bytes: every matrix outside the routed experts once a call; of the
      experts held here the expected distinct ones, `E_here (1 - (1 -
      k/E)^n)` a layer for `n` real tokens: a token's `k` distinct choices
      of `E` hit a given held expert with probability `k/E` (8/256 = 1/32:
      `32 (1 - (1 - 1/32)^n)`), independently over tokens under the
      uniform ids seeded weights give; the cache by row kind as far as it
      must be read (index keys over the whole context, latent rows of the
      kept positions, the ring's window) and the new rows written, bf16.
    - `decode_step` is given the sum of the rows' contexts and needs
      `min(context, cap)` a row: it takes every row at the mean context,
      which is exact wherever every context lies on one side of each cap
      (this mix: every context is past 4096 > 2048 > 513).

    These counts do not read the program's counters.  (A plain class:
    run.py loads this file outside `sys.modules`, where a dataclass cannot
    be made.)"""

    def __init__(self, g: dict):
        self.g = g
        self.full = attn_dims(g, FULL)
        self.swa = attn_dims(g, SLIDING)

    @property
    def vocab(self) -> int:
        return self.g["vocab_size"]

    @property
    def layers(self) -> dict[str, int]:
        return group_layers(self.g)

    def attn_params(self, grp: str) -> int:
        """One layer's attention matrices of group `full` or `swa`."""
        s = mat_shapes(self.g)
        return sum(math.prod(s[f"{grp}.{m}"])
                   for m in (FULL_MATS if grp == "full" else SWA_MATS))

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
    def attn_layer_params(self) -> int:
        n = self.layers
        return n["full"] * self.attn_params("full") + n["swa"] * self.attn_params("swa")

    @property
    def chosen_here(self) -> float:
        """Expected experts of a token's top-k that are held here."""
        g = self.g
        return g["num_experts_per_tok"] * g["n_local_experts"] / g["n_routed_experts"]

    @property
    def active_layer_params(self) -> float:
        """Matrix elements one token multiplies through in the layers."""
        n = self.layers
        moe = self.router_params + self.expert_params * (
            self.chosen_here + self.g["n_shared_experts"])
        return (self.attn_layer_params + n["dense"] * self.dense_ffn_params
                + n["moe"] * moe)

    @property
    def total_params(self) -> int:
        """Every parameter held (norms and biases left out)."""
        n = self.layers
        moe = self.router_params + self.expert_params * (
            self.g["n_local_experts"] + self.g["n_shared_experts"])
        return (self.attn_layer_params + n["dense"] * self.dense_ffn_params
                + n["moe"] * moe + 2 * self.head_params)

    @property
    def unrouted_layer_bytes(self) -> int:
        """bf16 bytes of every layer matrix but the routed experts."""
        n = self.layers
        moe = self.router_params + self.expert_params * self.g["n_shared_experts"]
        return 2 * (self.attn_layer_params + n["dense"] * self.dense_ffn_params
                    + n["moe"] * moe)

    # -- the cache, by row kind (bf16) ------------------------------------

    @property
    def full_row_bytes(self) -> int:
        """A position of ONE full layer: latent + RoPE key + index key."""
        return 2 * (self.full["kv_rank"] + self.full["rope"] + self.g["index_head_dim"])

    @property
    def index_key_bytes(self) -> int:
        return 2 * self.g["index_head_dim"]

    @property
    def latent_row_bytes(self) -> int:
        return 2 * (self.full["kv_rank"] + self.full["rope"])

    @property
    def ring_row_bytes(self) -> int:
        return 2 * (self.swa["kv_rank"] + self.swa["rope"])

    @property
    def cache_bytes_per_position(self) -> int:
        """What a context-long position holds: the full layers' rows (a
        sliding layer holds a ring, not a row a position)."""
        return self.layers["full"] * self.full_row_bytes

    def experts_hit(self, tokens: float) -> float:
        """Expected distinct HELD experts a layer reads for `tokens`."""
        g = self.g
        p = g["num_experts_per_tok"] / g["n_routed_experts"]
        return g["n_local_experts"] * (1.0 - (1.0 - p) ** max(0.0, float(tokens)))

    def routed_bytes(self, tokens: float) -> float:
        return self.layers["moe"] * self.experts_hit(tokens) * 2 * self.expert_params

    # -- attention ----------------------------------------------------------

    @staticmethod
    def _core_flops(d: dict, queries: float, pairs: float, distinct: float) -> float:
        """The cheaper form of one layer's softmax core over `pairs`
        (query, key) pairs of `queries` queries and `distinct` keys."""
        nh = d["heads"]
        expanded = (2.0 * nh * (d["nope"] + d["rope"] + d["v"]) * pairs
                    + 2.0 * d["kv_rank"] * nh * (d["nope"] + d["v"]) * distinct)
        absorbed = (2.0 * nh * (2 * d["kv_rank"] + d["rope"]) * pairs
                    + 2.0 * d["kv_rank"] * nh * (d["nope"] + d["v"]) * queries)
        return min(expanded, absorbed)

    @staticmethod
    def _capped(first: float, count: float, cap: float) -> float:
        """sum of min(t + 1, cap) over the `count` positions from `first`."""
        count = max(0.0, float(count))
        whole = min(max(cap - first, 0.0), count)  # positions with t + 1 <= cap
        rest = count - whole
        return whole * (2.0 * first + whole + 1.0) / 2.0 + (rest * cap if rest else 0.0)

    def attention(self, first: float, count: float) -> tuple[float, float]:
        """(flops, cache bytes read) of `count` consecutive queries from
        position `first`, processed together, over every layer."""
        g, n = self.g, self.layers
        last = first + count
        pairs_all = self._capped(first, count, float("inf"))
        pairs_top = self._capped(first, count, g["index_topk"])
        pairs_win = self._capped(first, count, g["sliding_window"])
        flops = n["full"] * (
            self._core_flops(self.full, count, pairs_top, min(last, g["index_topk"]))
            + 2.0 * g["index_n_heads"] * (g["index_head_dim"] + 1) * pairs_all)
        flops += n["swa"] * self._core_flops(
            self.swa, count, pairs_win, min(last, g["sliding_window"] - 1 + count))
        read = n["full"] * (self.index_key_bytes * last
                            + self.latent_row_bytes * min(last, g["index_topk"]))
        read += n["swa"] * self.ring_row_bytes * min(
            last, g["sliding_window"] - 1 + count)
        return flops, read

    @property
    def new_row_bytes(self) -> int:
        n = self.layers
        return n["full"] * self.full_row_bytes + n["swa"] * self.ring_row_bytes

    # -- the programs -----------------------------------------------------

    def decode_step(self, batch: float, ctx_sum: float) -> tuple[float, float]:
        mean = ctx_sum / max(batch, 1e-9)
        a_flops, a_read = self.attention(mean, 1.0)
        flops = (2.0 * (self.active_layer_params + self.head_params) * batch
                 + a_flops * batch)
        nbytes = (self.unrouted_layer_bytes + 2 * self.head_params
                  + self.routed_bytes(batch)
                  + batch * (a_read + self.new_row_bytes)
                  + 2.0 * self.g["hidden_size"] * batch)
        return flops, nbytes

    def prefill_chunk(self, chunk: float, offset: float) -> tuple[float, float]:
        """The head is needed once a request; it is in `prompt_flops`."""
        a_flops, a_read = self.attention(offset, chunk)
        flops = 2.0 * self.active_layer_params * chunk + a_flops
        nbytes = (self.unrouted_layer_bytes + self.routed_bytes(chunk)
                  + a_read + chunk * self.new_row_bytes
                  + 2.0 * self.g["hidden_size"] * chunk)
        return flops, nbytes

    def prompt_flops(self, prompt_len: int) -> float:
        return (2.0 * self.active_layer_params * prompt_len
                + 2.0 * self.head_params + self.attention(0, prompt_len)[0])

    def token_flops(self, ctx: int) -> float:
        return (2.0 * (self.active_layer_params + self.head_params)
                + self.attention(ctx, 1)[0])


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
    """The reference's functions for rows of `seq` positions:
    `attention[kind]`, `dense_ffn`, `moe_ffn` (each `x` [R, S, H] float32
    and one layer's weights as stored -> `x`), `route`, `select`, `head`,
    `gaps`.  With `levels`, every matrix is rounded first (the control)."""
    from types import SimpleNamespace

    import jax
    import jax.numpy as jnp

    eps = g["rms_eps"]
    hidden = g["hidden_size"]
    top_k, scaling = g["num_experts_per_tok"], g["routed_scaling_factor"]
    start, held = g["local_expert_start"], g["n_local_experts"]
    hi, di, keep = g["index_n_heads"], g["index_head_dim"], g["index_topk"]
    window = g["sliding_window"]
    qb = QUERY_BLOCK if seq % QUERY_BLOCK == 0 else seq

    def mat(w):
        w = w.astype(jnp.float32)
        return w if levels is None else _fake_quant(jnp, w, levels)

    def rms(x):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)

    pos = jnp.arange(seq)

    def rotate(x, theta):
        """RoPE on pairs (2i, 2i+1) of the last axis; x [S, ..., rope]."""
        rope = x.shape[-1]
        inv = 1.0 / (theta ** (jnp.arange(0, rope, 2, dtype=jnp.float32) / rope))
        ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
        shape = x.shape
        x = x.reshape(*shape[:-1], rope // 2, 2)
        c = jnp.cos(ang).reshape(seq, *([1] * (x.ndim - 3)), rope // 2)
        s = jnp.sin(ang).reshape(seq, *([1] * (x.ndim - 3)), rope // 2)
        even, odd = x[..., 0], x[..., 1]
        return jnp.stack([even * c - odd * s, even * s + odd * c], -1).reshape(shape)

    def swiglu(x, gate, up, down):
        return (jax.nn.silu(x @ gate) * (x @ up)) @ down

    def select(x, cq, w, theta):
        """Which positions each query keeps, bool [S, S] (row t: S_t)."""
        rope = g["qk_rope_head_dim"]
        qi = (cq @ w["idx_q_b"]).reshape(seq, hi, di)
        ki = x @ w["idx_k"]
        ki = ki - ki.mean(-1, keepdims=True)
        ki = ki * jax.lax.rsqrt(jnp.mean(ki * ki, -1, keepdims=True) + INDEX_NORM_EPS)
        ki = ki * w["idx_k_norm"] + w["idx_k_bias"]
        qi = jnp.concatenate([rotate(qi[..., :rope], theta), qi[..., rope:]], -1)
        ki = jnp.concatenate([rotate(ki[..., :rope], theta), ki[..., rope:]], -1)
        wi = x @ w["idx_w"]  # [S, hi]

        def block(q0):
            qs = jax.lax.dynamic_slice_in_dim(qi, q0, qb, 0)
            ws = jax.lax.dynamic_slice_in_dim(wi, q0, qb, 0)
            score = jnp.einsum("qhk,qh->qk",
                               jax.nn.relu(jnp.einsum("qhd,kd->qhk", qs, ki)), ws)
            t = q0 + jnp.arange(qb)
            score = jnp.where(pos[None, :] <= t[:, None], score, -jnp.inf)
            if seq <= keep:
                return score > -jnp.inf
            vals, idx = jax.lax.top_k(score, keep)
            kept = jnp.zeros((qb, seq), bool).at[jnp.arange(qb)[:, None], idx].set(
                vals > -jnp.inf)
            return kept

        return jax.lax.map(block, jnp.arange(0, seq, qb)).reshape(seq, seq)

    def attend_row(h, w, kind):  # h [S, H]
        d = attn_dims(g, kind)
        nh, nope, rope, vd = d["heads"], d["nope"], d["rope"], d["v"]
        a_q = math.sqrt(hidden / d["q_rank"]) if g["lora_rescale"] else 1.0
        a_kv = math.sqrt(hidden / d["kv_rank"]) if g["lora_rescale"] else 1.0
        x = rms(h)
        cq = a_q * rms(x @ w["q_a"])
        q = (cq @ w["q_b"]).reshape(seq, nh, nope + rope)
        q_nope, q_rope = q[..., :nope], rotate(q[..., nope:], d["theta"])
        ckr = x @ w["kv_a"]
        c, kr = a_kv * rms(ckr[:, :d["kv_rank"]]), rotate(ckr[:, d["kv_rank"]:], d["theta"])
        kv = (c @ w["kv_b"]).reshape(seq, nh, nope + vd)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        if kind == FULL:
            kept = select(x, cq, w, d["theta"])
        else:
            gap = pos[:, None] - pos[None, :]
            kept = (gap >= 0) & (gap < window)

        def block(q0):
            qn = jax.lax.dynamic_slice_in_dim(q_nope, q0, qb, 0)
            qr = jax.lax.dynamic_slice_in_dim(q_rope, q0, qb, 0)
            sees = jax.lax.dynamic_slice_in_dim(kept, q0, qb, 0)
            sc = (jnp.einsum("qnd,knd->nqk", qn, k_nope)
                  + jnp.einsum("qnd,kd->nqk", qr, kr)) / math.sqrt(nope + rope)
            sc = jnp.where(sees[None], sc, -jnp.inf)
            return jnp.einsum("nqk,knd->qnd", jax.nn.softmax(sc, -1), v)

        ctx = jax.lax.map(block, jnp.arange(0, seq, qb)).reshape(seq, nh, vd)
        gate = jax.nn.sigmoid(x @ w["attn_gate"])  # [S, nh]
        return h + (ctx * gate[..., None]).reshape(seq, nh * vd) @ w["o"]

    def attention_of(kind):
        @jax.jit
        def attention(x, w):
            w = {k: (v.astype(jnp.float32) if k in ("idx_k_norm", "idx_k_bias")
                     else mat(v)) for k, v in w.items()}
            return jax.lax.map(lambda row: attend_row(row, w, kind), x)
        return attention

    @jax.jit
    def dense_ffn(x, w):
        return x + swiglu(rms(x), mat(w["gate"]), mat(w["up"]), mat(w["down"]))

    def route(x, router, bias):
        """Chosen experts [T, k] of ALL the routed experts and their
        weights, for normed x [T, H]."""
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
        # where it was not chosen; the held experts' columns are what is
        # computed here, each held expert seeing every token.
        dense = jnp.zeros((r * s, g["n_routed_experts"]), jnp.float32)
        dense = dense.at[jnp.arange(r * s)[:, None], idx].set(wts)
        here = dense[:, start:start + held]

        def one_expert(acc, ew):
            gate, up, down, col = ew
            return acc + col[:, None] * swiglu(xn, mat(gate), mat(up), mat(down)), None

        routed, _ = jax.lax.scan(
            one_expert, jnp.zeros_like(xn),
            (w["gate"], w["up"], w["down"], here.T))
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

    return SimpleNamespace(
        attention={FULL: attention_of(FULL), SLIDING: attention_of(SLIDING)},
        dense_ffn=dense_ffn, moe_ffn=moe_ffn, route=route, select=select,
        head=head, gaps=gaps)


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
        if self.g["layer_types"][l] == FULL:
            di = self.g["index_head_dim"]
            out["full.idx_k_norm"] = np.ones((di,), np.float32)
            out["full.idx_k_bias"] = np.zeros((di,), np.float32)
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
        for l, kind in enumerate(g["layer_types"]):
            host = nxt.result()
            if l + 1 < g["num_layers"]:
                nxt = ahead.submit(src.layer, l + 1)
            attn = {k.split(".", 1)[1]: jnp.array(v) for k, v in host.items()
                    if k.split(".")[0] in ("full", "swa")}
            ffn = {k.split(".", 1)[1]: jnp.array(v) for k, v in host.items()
                   if k.split(".")[0] in ("dense", "moe")}
            del host
            x = ref.attention[kind](x, attn)
            x = ref.dense_ffn(x, ffn) if l < src.dense_layers else ref.moe_ffn(x, ffn)
            jax.block_until_ready(x)
            del attn, ffn
        lm_head = jnp.asarray(src.whole("lm_head"))
    return ref.head(x, jnp.asarray(idx), lm_head)


def readings(gap: np.ndarray) -> dict:
    """The numbers compared, of one model's gaps at the served positions."""
    gap = np.sort(gap)
    kept = gap[: max(1, round(len(gap) * (1.0 - TRIM)))]
    return {"max_logit_gap": float(gap[-1]),
            "mean_logit_gap": float(kept.mean()),
            "all_mean_logit_gap": float(gap.mean()),
            # The gaps' quantiles at 50, 55, .. 95 %: what a limit or a
            # trim is set from (the log carries them; nothing compares them).
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
    for name, levels in CONTROLS.items() if control else ():
        first_c = jnp.argmax(forward(g, seed, toks, idx, levels), axis=-1)
        gap_c = np.asarray(gaps(logits, first_c)[0])
        out[name + "_levels"] = levels
        out.update({f"{name}_{k}": v for k, v in readings(gap_c[valid]).items()})
        out[name + "_argmax_agreement"] = float(
            (np.asarray(first_c) == ref_best)[valid].mean())
    out["seconds"] = round(time.perf_counter() - t_all, 2)
    return out
