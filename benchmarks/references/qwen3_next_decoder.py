"""The plain reference of the Qwen3-Next decoder: Gated DeltaNet layers
with a gated full-attention layer every `full_attention_interval`, and in
every layer softmax-routed experts, of which THIS chip holds a share,
beside one sigmoid-gated shared expert.  With its seeded artifact, its
count functions and the comparison that decides `correct`.  A
configuration names it (`"reference": "qwen3_next_decoder"`); run.py asks
it three things: `write_artifact(path, model, seed)`, `shapes(model)`,
`compare(...)`.

The layer equations in straightforward `jax.numpy`, float32 at `highest`
precision, no cache, no batching, no kernels, no chunked form: the delta
rule is the recurrence TOKEN BY TOKEN (`lax.scan` over positions), the
convolution four shifted products, attention a softmax over the whole
causal prefix, on weights it makes itself from the seed.  It imports
nothing of the program (the artifact writer alone asks the program's
loader for its metadata files and that the program knows the flavor).  A
layer's weights at a time, a row at a time, attention a block of 128
queries at a time, one expert at a time, so a row of 8320 positions fits.

With `rms(x) = x / sqrt(mean(x^2) + eps)`, residual `h`, per layer:

- Norms: layer, final, `q_norm`, `k_norm` are zero-centred, `rms(x) (1 +
  w)`; the DeltaNet output norm is `rms(o) w silu(z)` over a value
  head's numbers.
- Linear layer (`Hk` key heads, `Hv` value heads of `dk`, `dv`; `r = Hv /
  Hk`): `x = norm(h)`; `x W_qkvz` laid out a key head as `[q dk | k dk |
  v r dv | z r dv]`, `x W_ba` a key head as `[b r | a r]`; `m = q || k ||
  v` (all heads' q, then k, then v); `u_t = silu(sum_j c_j * m_{t-K+1+j})`,
  `j < K` (causal, depthwise, no bias, zeros before the sequence);
  `beta = sigmoid(b)`, `g = -exp(A_log) softplus(a + dt_bias)` a value
  head; `q`, `k` of `u` L2-normalised a head (`x rsqrt(sum x^2 + 1e-6)`),
  `q` times `dk^-0.5`, a key head serving its `r` value heads; a value
  head's state `S` [dk, dv] from zero: `S <- exp(g_t) S`; `d = beta_t (v_t
  - S^T k_t)`; `S <- S + k_t d^T`; `o_t = S^T q_t`.  `h += (rms(o) w
  silu(z)) W_o`.
- Full layer (`H` query heads, `KV` key/value heads of `D`): `x W_q` a
  head `[query D | gate D]`; `q = q_norm(query)`, `k = k_norm(x W_k)`, `v
  = x W_v`; rotary on the first `partial_rotary_factor D` dims,
  half-split pairs; causal softmax at `D^-0.5`, a KV head serving `H / KV`
  query heads; `h += (o sigmoid(gate)) W_o`.
- Expert block, every layer: `x = norm(h)`; `p = softmax(x W_r)` over all
  `router_experts` in float32; the top-k of `p`, weights renormalised
  over them; `h += sum_{k held here} w_k E_k(x) + sigmoid(x w_sg)
  E_shared(x)`: what the experts held elsewhere would add is left out.
- Final norm, untied head over the vocabulary slice (a smaller vocabulary).

Departures and assumptions are in the configuration's file.

Compared: as `dots3_note_decoder.py` (served tokens teacher-forced; the
gap by which a served token's reference logit lies below the reference's
best; `max_logit_gap` the widest, `mean_logit_gap` the TRIMMED mean, the
widest `TRIM` of the gaps left out).  Controls (`--control 1`), each in
the program's place: every matrix rounded to 127 levels a side per
output channel (int8; int4 logged beside it), and the reference itself
with the linear layers' CARRIED STATE DROPPED at one chunk boundary (`S`
and the convolution's earlier rows zeroed at the start of the prompt's
last `STATE_CHUNK` tokens: what a program that loses its scratch between
two chunks computes).  The readings that stand under `control_` are, a
check, the SMALLER of the int8 control's and the dropped-state
control's, so the one `correct: false` of a `--control 1` run holds for
both; each control's own are logged as `control_int8_*` and
`control_state_*`."""

from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from harness import weights

FLAVOR = "gdn-moe-generate"  # the program's name for this family
NORM_STD = 0.02  # of the seeded zero-centred norm weights (assumed)
CONTROLS = {"control_int8": 127, "control_int4": 7}  # levels a side
TRIM = 0.15  # the share of the tokens, those with the widest gaps, left out of the mean
STATE_CHUNK = 512  # the dropped-state control's chunk (the cell's prefillChunk)
LINEAR, FULL = "linear_attention", "full_attention"
QUERY_BLOCK = 128
DECAY_SPAN = (0.5, 0.999)  # a token's decay exp(g) at a + dt_bias = 0: first, last head


# ---------------------------------------------------------------------------
# Geometry and leaves
# ---------------------------------------------------------------------------


def geometry(model: dict) -> dict:
    """The program's artifact config from the published config's keys."""
    fixed = {"norm_topk_prob": True, "decoder_sparse_step": 1,
             "mlp_only_layers": [], "rope_scaling": None,
             "tie_word_embeddings": False, "use_sliding_window": False,
             "hidden_act": "silu"}
    for key, want in fixed.items():
        if key in model and model[key] != want:
            raise ValueError(
                f"{key}={model[key]!r}: this reference implements {want!r} only")
    return {
        "vocab_size": int(model["vocab_size"]),
        "hidden_size": int(model["hidden_size"]),
        "num_layers": int(model["num_hidden_layers"]),
        "full_attention_interval": int(model["full_attention_interval"]),
        "num_heads": int(model["num_attention_heads"]),
        "num_kv_heads": int(model["num_key_value_heads"]),
        "head_dim": int(model["head_dim"]),
        "partial_rotary_factor": float(model["partial_rotary_factor"]),
        "rope_theta": float(model["rope_theta"]),
        "linear_num_key_heads": int(model["linear_num_key_heads"]),
        "linear_num_value_heads": int(model["linear_num_value_heads"]),
        "linear_key_head_dim": int(model["linear_key_head_dim"]),
        "linear_value_head_dim": int(model["linear_value_head_dim"]),
        "linear_conv_kernel_dim": int(model["linear_conv_kernel_dim"]),
        "moe_intermediate_size": int(model["moe_intermediate_size"]),
        "shared_expert_intermediate_size": int(
            model["shared_expert_intermediate_size"]),
        # The router's width is the published count; the file's
        # `num_experts` is how many of them this chip holds.
        "n_routed_experts": int(model["router_experts"]),
        "n_local_experts": int(model["num_experts"]),
        "local_expert_start": int(model["local_expert_start"]),
        "num_experts_per_tok": int(model["num_experts_per_tok"]),
        "max_seq": int(model["max_position_embeddings"]),
        "rms_eps": float(model["rms_norm_eps"]),
    }


def kinds(g: dict) -> list[str]:
    return [FULL if (l + 1) % g["full_attention_interval"] == 0 else LINEAR
            for l in range(g["num_layers"])]


def dims(g: dict) -> dict:
    hk, hv = g["linear_num_key_heads"], g["linear_num_value_heads"]
    dk, dv = g["linear_key_head_dim"], g["linear_value_head_dim"]
    return {"hk": hk, "hv": hv, "dk": dk, "dv": dv, "r": hv // hk,
            "key_dim": hk * dk, "value_dim": hv * dv,
            "conv_dim": 2 * hk * dk + hv * dv,
            "kernel": g["linear_conv_kernel_dim"],
            "q_dim": g["num_heads"] * g["head_dim"],
            "kv_dim": g["num_kv_heads"] * g["head_dim"],
            "rotary": int(g["head_dim"] * g["partial_rotary_factor"])}


# Leaves of a layer, by group: `gdn` / `attn` the token mixer of the
# layer's kind, `moe` the expert block of every layer (the held experts'
# three matrices stacked on an expert axis).  Matrices are N(0, STD) picked
# from `weights.normal_table()`; `*_norm` vectors (zero-centred) N(0,
# NORM_STD) from a generator of their own.
GDN_MATS = ("qkvz", "ba", "conv", "o")
ATTN_MATS = ("q", "k", "v", "o")
MOE_MATS = ("router", "gate", "up", "down", "shared_gate", "shared_up",
            "shared_down", "shared_expert_gate")
EXPERT_MATS = ("gate", "up", "down")
_STREAMS = ("embed", "lm_head") + tuple(
    f"{grp}.{m}" for grp, mats in
    (("gdn", GDN_MATS), ("attn", ATTN_MATS), ("moe", MOE_MATS)) for m in mats
) + ("final_norm", "attn_norm", "ffn_norm", "q_norm", "k_norm")


def mat_shapes(g: dict) -> dict[str, tuple[int, ...]]:
    """Shape of one layer's slice of every matrix leaf, `group.name`."""
    h, d = g["hidden_size"], dims(g)
    im, ims = g["moe_intermediate_size"], g["shared_expert_intermediate_size"]
    held = g["n_local_experts"]
    return {
        "gdn.qkvz": (h, 2 * d["key_dim"] + 2 * d["value_dim"]),
        "gdn.ba": (h, 2 * d["hv"]),
        "gdn.conv": (d["kernel"], d["conv_dim"]),
        "gdn.o": (d["value_dim"], h),
        "attn.q": (h, 2 * d["q_dim"]), "attn.k": (h, d["kv_dim"]),
        "attn.v": (h, d["kv_dim"]), "attn.o": (d["q_dim"], h),
        "moe.router": (h, g["n_routed_experts"]),
        "moe.gate": (held, h, im), "moe.up": (held, h, im),
        "moe.down": (held, im, h),
        "moe.shared_gate": (h, ims), "moe.shared_up": (h, ims),
        "moe.shared_down": (ims, h), "moe.shared_expert_gate": (h, 1),
    }


def layer_mats(g: dict, l: int) -> list[tuple[str, int]]:
    """(leaf, unit) of every matrix of global layer `l`: the unit is the
    layer's index within its group, which seeds the leaf's streams."""
    kind = kinds(g)[l]
    grp, mats = ("attn", ATTN_MATS) if kind == FULL else ("gdn", GDN_MATS)
    unit = sum(k == kind for k in kinds(g)[:l])
    return ([(f"{grp}.{m}", unit) for m in mats]
            + [(f"moe.{m}", l) for m in MOE_MATS])


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
        rng = np.random.default_rng([int(seed), 35, leaf, unit + 1, c])
        step = 1 << 18
        for i in range(0, part.size, step):
            n = min(step, part.size - i)
            part[i:i + n] = table[rng.integers(0, 65536, n, dtype=np.uint16)]

    list(ex.map(chunk, range(-(-flat.size // weights.CHUNK))))


def norm_weight(seed: int, name: str, unit: int, width: int) -> np.ndarray:
    """A zero-centred norm's weight `w` (`1 + w` multiplies), bfloat16."""
    import ml_dtypes

    rng = np.random.default_rng([int(seed), 35, _STREAMS.index(name), unit + 1])
    return (NORM_STD * rng.standard_normal(width)).astype(
        np.float32).astype(ml_dtypes.bfloat16)


def decay_log_a(g: dict) -> np.ndarray:
    """`A_log` a value head, float32: with `dt_bias` 0 a token's decay
    `exp(g) = exp(-exp(A_log) softplus(a))` runs geometrically from
    `DECAY_SPAN[0]` (head 0) to `DECAY_SPAN[1]` (the last) at `a = 0`, so
    a state that is dropped or stale moves the logits (the configuration's
    `assumed`; the program's `gdn_moe.decay_log_a` is the same rule)."""
    lo, hi = (math.log(-math.log(x)) for x in DECAY_SPAN)
    rate = np.exp(np.linspace(lo, hi, g["linear_num_value_heads"]))
    return np.log(rate / math.log(2.0)).astype(np.float32)


def small_leaves(g: dict, seed: int, l: int) -> dict[str, np.ndarray]:
    """The vectors of layer `l`, by the program's leaf names."""
    import ml_dtypes

    h, d = g["hidden_size"], dims(g)
    out = {"attn_norm": norm_weight(seed, "attn_norm", l, h),
           "ffn_norm": norm_weight(seed, "ffn_norm", l, h)}
    if kinds(g)[l] == FULL:
        out["q_norm"] = norm_weight(seed, "q_norm", l, g["head_dim"])
        out["k_norm"] = norm_weight(seed, "k_norm", l, g["head_dim"])
    else:
        out["A_log"] = decay_log_a(g)
        out["dt_bias"] = np.zeros((d["hv"],), np.float32)
        out["gdn_norm"] = np.ones((d["dv"],), ml_dtypes.bfloat16)
    return out


# ---------------------------------------------------------------------------
# The artifact
# ---------------------------------------------------------------------------


def write_artifact(path: str, model: dict, seed: int) -> None:
    """Child mode of run.py: the seeded bf16 artifact in the program's own
    layout.  A program that does not know the flavor fails here, in
    seconds, before gigabytes are written."""
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
    inner = f"experts{sep}{mat}" if group == "moe" and mat in EXPERT_MATS else mat
    return f"layers{sep}#{layer}{sep}{inner}"


def stream_npz(path: str, seed: int, g: dict, sep: str) -> None:
    """The whole tree as numpy's own `.npz`, a matrix at a time: one is
    filled while the one before is written."""
    import zipfile

    import ml_dtypes

    bf16 = np.dtype(ml_dtypes.bfloat16)
    h = g["hidden_size"]
    src_shapes = mat_shapes(g)
    small = {"final_norm": norm_weight(seed, "final_norm", -1, h)}
    mats = [("embed", "embed", -1, (g["vocab_size"], h)),
            ("lm_head", "lm_head", -1, (h, g["vocab_size"]))]
    for l in range(g["num_layers"]):
        small.update({f"layers{sep}#{l}{sep}{k}": v
                      for k, v in small_leaves(g, seed, l).items()})
        mats += [(artifact_key(n, l, sep), n, u, src_shapes[n])
                 for n, u in layer_mats(g, l)]
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


class Counts:
    """Operations and bytes of this architecture's programs: the LEAST
    work any implementation of the equations must do, so a share of a
    roofline cannot pass 100 % because the program chose another form.

    - A linear layer: its projections, the convolution (`2 K` a channel a
      token) and the delta rule in its cheapest form, the recurrence: three
      products of `dk x dv` a value head a token (`S^T k`, `k d^T`, `S^T
      q`), `6 dk dv`.  (The chunked form spends more on purpose: it buys
      matrix products for the walk.)  Its state is read and written ONCE a
      call a row (`S` float32, the convolution's `K - 1` rows bf16).
    - A full layer: projections, and `4 D` a head an attended (query, key)
      pair, every earlier position attended; K and V rows read as far as
      attended, bf16.
    - The expert block: router, the gated shared expert, the expected
      held experts of a token's top-k; of the experts held here the
      expected distinct ones are read, `E_here (1 - (1 - k/E)^n)` a layer
      for `n` real tokens under the uniform ids seeded weights give.
    - The head over the vocabulary slice, once a request in prefill
      (`prompt_flops`), a step in decode.

    These counts do not read the program's counters.  (A plain class:
    run.py loads this file outside `sys.modules`, where a dataclass cannot
    be made.)"""

    def __init__(self, g: dict):
        self.g = g
        self.d = dims(g)

    @property
    def vocab(self) -> int:
        return self.g["vocab_size"]

    @property
    def layers(self) -> dict[str, int]:
        full = sum(k == FULL for k in kinds(self.g))
        return {"full": full, "linear": self.g["num_layers"] - full,
                "moe": self.g["num_layers"]}

    def _group(self, grp: str, mats) -> int:
        s = mat_shapes(self.g)
        return sum(math.prod(s[f"{grp}.{m}"]) for m in mats)

    @property
    def linear_mixer_params(self) -> int:
        """A linear layer's mixer, every leaf: the two projections, the
        convolution's kernel, `A_log`, `dt_bias`, the gated norm, `W_o`."""
        return self._group("gdn", GDN_MATS) + 2 * self.d["hv"] + self.d["dv"]

    @property
    def full_mixer_params(self) -> int:
        """A full layer's mixer, every leaf: `W_q`, `W_k`, `W_v`, `W_o`
        and the two per-head norms."""
        return self._group("attn", ATTN_MATS) + 2 * self.g["head_dim"]

    @property
    def expert_params(self) -> int:
        """One routed expert (also the shared expert: the same width)."""
        return 3 * self.g["hidden_size"] * self.g["moe_intermediate_size"]

    @property
    def router_params(self) -> int:
        return self.g["hidden_size"] * self.g["n_routed_experts"]

    @property
    def unrouted_block_params(self) -> int:
        """A layer outside its mixer and its routed experts: router,
        shared expert, its gate, the two layer norms."""
        h = self.g["hidden_size"]
        return (self.router_params
                + 3 * h * self.g["shared_expert_intermediate_size"] + h + 2 * h)

    @property
    def head_params(self) -> int:
        return self.g["hidden_size"] * self.g["vocab_size"]

    @property
    def mixer_params(self) -> int:
        n = self.layers
        return (n["linear"] * self.linear_mixer_params
                + n["full"] * self.full_mixer_params)

    @property
    def layer_params(self) -> int:
        """Every parameter of the layers held here."""
        return self.mixer_params + self.layers["moe"] * (
            self.unrouted_block_params
            + self.g["n_local_experts"] * self.expert_params)

    @property
    def total_params(self) -> int:
        """Every parameter held: layers, embedding, head, final norm."""
        return self.layer_params + 2 * self.head_params + self.g["hidden_size"]

    @property
    def chosen_here(self) -> float:
        """Expected experts of a token's top-k that are held here."""
        g = self.g
        return g["num_experts_per_tok"] * g["n_local_experts"] / g["n_routed_experts"]

    @property
    def active_layer_params(self) -> float:
        """Matrix elements one token multiplies through in the layers
        (the convolution's kernel is counted with the rule's flops)."""
        n, d = self.layers, self.d
        conv = d["kernel"] * d["conv_dim"]
        vectors = 2 * self.g["hidden_size"]
        return (n["linear"] * (self._group("gdn", GDN_MATS) - conv)
                + n["full"] * self._group("attn", ATTN_MATS)
                + n["moe"] * (self.unrouted_block_params - vectors
                              + self.chosen_here * self.expert_params))

    @property
    def rule_flops(self) -> float:
        """A token's convolution and delta rule, over the linear layers."""
        d = self.d
        return self.layers["linear"] * (
            2.0 * d["kernel"] * d["conv_dim"] + 6.0 * d["hv"] * d["dk"] * d["dv"])

    @property
    def unrouted_layer_bytes(self) -> int:
        """bf16 bytes of every layer leaf but the routed experts."""
        return 2 * (self.mixer_params
                    + self.layers["moe"] * self.unrouted_block_params)

    # -- the cache (rows bf16, state float32) ---------------------------------

    @property
    def cache_bytes_per_position(self) -> int:
        """What a context-long position holds: the full layers' K and V."""
        return self.layers["full"] * 2 * self.d["kv_dim"] * 2

    @property
    def state_bytes(self) -> int:
        """What a slot holds whatever its length: the linear layers' `S`
        in float32 and their convolutions' `K - 1` rows in bf16."""
        d = self.d
        return self.layers["linear"] * (
            d["hv"] * d["dk"] * d["dv"] * 4 + (d["kernel"] - 1) * d["conv_dim"] * 2)

    def experts_hit(self, tokens: float) -> float:
        """Expected distinct HELD experts a layer reads for `tokens`."""
        g = self.g
        p = g["num_experts_per_tok"] / g["n_routed_experts"]
        return g["n_local_experts"] * (1.0 - (1.0 - p) ** max(0.0, float(tokens)))

    def routed_bytes(self, tokens: float) -> float:
        return self.layers["moe"] * self.experts_hit(tokens) * 2 * self.expert_params

    def attention(self, first: float, count: float) -> tuple[float, float]:
        """(flops, cache bytes read) of `count` consecutive queries from
        position `first`, processed together, over the full layers: every
        query attends its own and every earlier position."""
        pairs = count * first + count * (count + 1) / 2.0
        flops = self.layers["full"] * 4.0 * self.d["q_dim"] * pairs
        return flops, self.cache_bytes_per_position * (first + count)

    # -- the programs ---------------------------------------------------------

    def decode_step(self, batch: float, ctx_sum: float) -> tuple[float, float]:
        mean = ctx_sum / max(batch, 1e-9)
        a_flops, a_read = self.attention(mean, 1.0)
        flops = (2.0 * (self.active_layer_params + self.head_params) * batch
                 + (self.rule_flops + a_flops) * batch)
        nbytes = (self.unrouted_layer_bytes + 2 * self.head_params
                  + self.routed_bytes(batch)
                  + batch * (a_read + 2 * self.state_bytes)
                  + 2.0 * self.g["hidden_size"] * batch)
        return flops, nbytes

    def prefill_chunk(self, chunk: float, offset: float) -> tuple[float, float]:
        """The head is needed once a request; it is in `prompt_flops`."""
        a_flops, a_read = self.attention(offset, chunk)
        flops = (2.0 * self.active_layer_params + self.rule_flops) * chunk + a_flops
        nbytes = (self.unrouted_layer_bytes + self.routed_bytes(chunk)
                  + a_read + 2 * self.state_bytes
                  + 2.0 * self.g["hidden_size"] * chunk)
        return flops, nbytes

    def prompt_flops(self, prompt_len: int) -> float:
        return ((2.0 * self.active_layer_params + self.rule_flops) * prompt_len
                + 2.0 * self.head_params + self.attention(0, prompt_len)[0])

    def token_flops(self, ctx: int) -> float:
        return (2.0 * (self.active_layer_params + self.head_params)
                + self.rule_flops + self.attention(ctx, 1)[0])


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
    """The reference's functions for rows of `seq` positions: `linear`,
    `attention`, `moe_ffn` (each `x` [R, S, H] float32 and one layer's
    weights as stored -> `x`; `linear` also takes `drop_at` [R], the
    position before which a row's carried state is dropped, `seq` for
    never), `delta_rule`, `route`, `head`, `gaps`.  With `levels`, every
    matrix is rounded first (the control)."""
    from types import SimpleNamespace

    import jax
    import jax.numpy as jnp

    eps = g["rms_eps"]
    d = dims(g)
    hk, hv, dk, dv, r, kernel = d["hk"], d["hv"], d["dk"], d["dv"], d["r"], d["kernel"]
    nh, nkv, hd, rotary = g["num_heads"], g["num_kv_heads"], g["head_dim"], d["rotary"]
    top_k = g["num_experts_per_tok"]
    start, held = g["local_expert_start"], g["n_local_experts"]
    qb = QUERY_BLOCK if seq % QUERY_BLOCK == 0 else seq
    vectors = ("attn_norm", "ffn_norm", "q_norm", "k_norm", "A_log", "dt_bias",
               "gdn_norm")

    def mat(w):
        w = w.astype(jnp.float32)
        return w if levels is None else _fake_quant(jnp, w, levels)

    def prepared(w):
        return {k: (v.astype(jnp.float32) if k in vectors else mat(v))
                for k, v in w.items()}

    def rms(x):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)

    def znorm(x, w):
        return rms(x) * (1.0 + w)

    pos = jnp.arange(seq)

    def rotate(x):
        """Rotary on the half-split pairs of the first `rotary` dims of
        x [S, heads, D]; the rest passes."""
        half = rotary // 2
        inv = 1.0 / (g["rope_theta"] ** (jnp.arange(0, rotary, 2, dtype=jnp.float32)
                                         / rotary))
        ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
        c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        a, b, rest = x[..., :half], x[..., half:rotary], x[..., rotary:]
        return jnp.concatenate([a * c - b * s, b * c + a * s, rest], axis=-1)

    def swiglu(x, gate, up, down):
        return (jax.nn.silu(x @ gate) * (x @ up)) @ down

    def delta_rule(q, k, v, gl, beta, drop_at):
        """The recurrence, token by token: q, k [S,Hv,dk], v [S,Hv,dv],
        gl, beta [S,Hv] -> o [S,Hv,dv].  At `drop_at` the state is zeroed
        before the token is taken in."""
        def token(state, xs):
            q_t, k_t, v_t, g_t, b_t, t = xs
            state = jnp.where(t == drop_at, 0.0, state)
            state = state * jnp.exp(g_t)[:, None, None]
            delta = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
            state = state + k_t[:, :, None] * delta[:, None, :]
            return state, jnp.einsum("hkv,hk->hv", state, q_t)

        _, o = jax.lax.scan(token, jnp.zeros((hv, dk, dv), jnp.float32),
                            (q, k, v, gl, beta, pos))
        return o

    def linear_row(h, drop_at, w):  # h [S, H]
        x = znorm(h, w["attn_norm"])
        qkvz = (x @ w["qkvz"]).reshape(seq, hk, 2 * dk + 2 * r * dv)
        q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
        v = qkvz[..., 2 * dk:2 * dk + r * dv]
        z = qkvz[..., 2 * dk + r * dv:].reshape(seq, hv, dv)
        ba = (x @ w["ba"]).reshape(seq, hk, 2 * r)
        b, a = ba[..., :r].reshape(seq, hv), ba[..., r:].reshape(seq, hv)
        m = jnp.concatenate([q.reshape(seq, hk * dk), k.reshape(seq, hk * dk),
                             v.reshape(seq, hv * dv)], axis=-1)
        # Four shifted products: row t takes m[t - (kernel - 1) + j] under
        # c_j, zeros before the sequence (and, in the dropped-state control,
        # before `drop_at` for the rows at and after it).
        u = jnp.zeros_like(m)
        for j in range(kernel):
            back = kernel - 1 - j
            src = pos - back
            shifted = jnp.roll(m, back, axis=0)
            seen = (src >= 0) & ~((pos >= drop_at) & (src < drop_at))
            u = u + jnp.where(seen[:, None], shifted, 0.0) * w["conv"][j]
        u = jax.nn.silu(u)
        unit = lambda y: y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)
        q = unit(u[:, :hk * dk].reshape(seq, hk, dk)) * dk ** -0.5
        k = unit(u[:, hk * dk:2 * hk * dk].reshape(seq, hk, dk))
        q, k = jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1)
        v = u[:, 2 * hk * dk:].reshape(seq, hv, dv)
        beta = jax.nn.sigmoid(b)
        gl = -jnp.exp(w["A_log"]) * jax.nn.softplus(a + w["dt_bias"])
        o = delta_rule(q, k, v, gl, beta, drop_at)
        y = rms(o) * w["gdn_norm"] * jax.nn.silu(z)
        return h + y.reshape(seq, hv * dv) @ w["o"]

    @jax.jit
    def linear(x, w, drop_at):
        w = prepared(w)
        return jax.lax.map(lambda row: linear_row(row[0], row[1], w), (x, drop_at))

    def attend_row(h, w):  # h [S, H]
        x = znorm(h, w["attn_norm"])
        qg = (x @ w["q"]).reshape(seq, nh, 2 * hd)
        q, gate = qg[..., :hd], qg[..., hd:]
        k = (x @ w["k"]).reshape(seq, nkv, hd)
        v = (x @ w["v"]).reshape(seq, nkv, hd)
        q, k = rotate(znorm(q, w["q_norm"])), rotate(znorm(k, w["k_norm"]))
        q = q.reshape(seq, nkv, nh // nkv, hd)

        def block(q0):
            qs = jax.lax.dynamic_slice_in_dim(q, q0, qb, 0)
            t = q0 + jnp.arange(qb)
            sc = jnp.einsum("qgrd,kgd->grqk", qs, k) / math.sqrt(hd)
            sc = jnp.where((pos[None, :] <= t[:, None])[None, None], sc, -jnp.inf)
            return jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(sc, -1), v)

        ctx = jax.lax.map(block, jnp.arange(0, seq, qb)).reshape(seq, nh, hd)
        return h + (ctx * jax.nn.sigmoid(gate)).reshape(seq, nh * hd) @ w["o"]

    @jax.jit
    def attention(x, w):
        w = prepared(w)
        return jax.lax.map(lambda row: attend_row(row, w), x)

    def route(x, router):
        """Chosen experts [T, k] of ALL the routed experts and their
        weights, for normed x [T, H]."""
        p = jax.nn.softmax(x @ router, axis=-1)
        chosen, idx = jax.lax.top_k(p, top_k)
        return idx, chosen / (chosen.sum(-1, keepdims=True) + 1e-20)

    @jax.jit
    def moe_ffn(x, w):
        rows, s, h = x.shape
        xn = znorm(x, w["ffn_norm"].astype(jnp.float32)).reshape(rows * s, h)
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
        shared = shared * jax.nn.sigmoid(xn @ mat(w["shared_expert_gate"]))
        return x + (routed + shared).reshape(rows, s, h)

    @jax.jit
    def head(x, idx, final_norm, lm_head):  # x [R,S,H], idx [R,A] -> logits [R,A,V]
        picked = jnp.take_along_axis(x, idx[..., None], axis=1)
        return znorm(picked, final_norm.astype(jnp.float32)) @ mat(lm_head)

    @jax.jit
    def gaps(logits, tokens):
        best = jnp.max(logits, axis=-1)
        mine = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
        return best - mine, jnp.argmax(logits, axis=-1)

    return SimpleNamespace(linear=linear, attention=attention, moe_ffn=moe_ffn,
                           delta_rule=delta_rule, route=route, head=head, gaps=gaps)


class LayerSource:
    """One layer's weights at a time, made on the host from the seed while
    the device works on the layer before."""

    def __init__(self, g: dict, seed: int, ex):
        import ml_dtypes

        self.g, self.seed, self.ex = g, seed, ex
        self.bf16 = np.dtype(ml_dtypes.bfloat16)
        self.shapes = mat_shapes(g)

    def layer(self, l: int) -> dict[str, np.ndarray]:
        """`mixer.*` and `moe.*` leaves of layer `l`, the layer norms with
        the sub-layer that applies them."""
        out = {}
        for name, unit in layer_mats(self.g, l):
            buf = np.empty(self.shapes[name], self.bf16)
            fill_unit(buf, self.seed, name, unit, self.ex)
            grp, leaf = name.split(".")
            out[("moe." if grp == "moe" else "mixer.") + leaf] = buf
        for leaf, vec in small_leaves(self.g, self.seed, l).items():
            out[("moe." if leaf == "ffn_norm" else "mixer.") + leaf] = vec
        return out

    def whole(self, name: str) -> np.ndarray:
        h, vocab = self.g["hidden_size"], self.g["vocab_size"]
        if name == "final_norm":
            return norm_weight(self.seed, name, -1, h)
        buf = np.empty((vocab, h) if name == "embed" else (h, vocab), self.bf16)
        fill_unit(buf, self.seed, name, -1, self.ex)
        return buf


def forward(g: dict, seed: int, toks: np.ndarray, idx: np.ndarray,
            levels: int | None = None, drop_at: np.ndarray | None = None):
    """Logits [R, A, V] at positions `idx` [R, A] of token rows `toks`
    [R, S]: the whole forward pass, a layer at a time.  `drop_at` [R]:
    the dropped-state control's position a row (None: the reference)."""
    import jax
    import jax.numpy as jnp

    seq = toks.shape[1]
    ref = build(g, seq, levels)
    never = np.full((toks.shape[0],), seq, np.int32)
    drop = jnp.asarray(never if drop_at is None else drop_at, jnp.int32)
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
            mixer, ffn = part("mixer."), part("moe.")
            del host
            x = (ref.attention(x, mixer) if kind == FULL
                 else ref.linear(x, mixer, drop))
            x = ref.moe_ffn(x, ffn)
            jax.block_until_ready(x)
            del mixer, ffn
        final_norm = jnp.asarray(src.whole("final_norm"))
        lm_head = jnp.asarray(src.whole("lm_head"))
    return ref.head(x, jnp.asarray(idx), final_norm, lm_head)


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
    drop_at = np.zeros((R,), np.int32)
    for r, (prompt, out) in enumerate(rows):
        full = list(prompt) + list(out)
        if len(full) > seq or len(out) > answers:
            raise ValueError(f"row {r} ({len(prompt)}+{len(out)}) exceeds ({seq},{answers})")
        toks[r, :len(full)] = full
        n = len(out)
        idx[r, :n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
        served[r, :n] = out
        valid[r, :n] = True
        # The start of the prompt's last chunk (a prompt of one chunk: 0,
        # where nothing is carried yet and nothing is dropped).
        drop_at[r] = (len(prompt) - 1) // STATE_CHUNK * STATE_CHUNK
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
        runs["control_state"] = {"drop_at": drop_at}
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
                out[f"control_int8_{check}"], out[f"control_state_{check}"])
    out["seconds"] = round(time.perf_counter() - t_all, 2)
    return out
