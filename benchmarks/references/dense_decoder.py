"""The plain reference of dense pre-norm decoders (Llama-shaped: GQA,
SwiGLU, rotate-half RoPE, untied head), and the comparison that decides
`correct`.  A configuration names its reference (`"reference"` in its
file); run.py finds `references/<name>.py` and asks it three things:
`write_artifact(path, model, seed)`, `shapes(model)` (the count functions)
and `compare(...)`.

The published layer equations in straightforward `jax.numpy`, float32 at
`highest` precision, no cache, no batching tricks, on weights it makes
itself from the seed (harness/weights.py): pre-RMSNorm, rotate-half RoPE,
grouped-query attention, SwiGLU, untied head.  It imports nothing of the
program.  Layer by layer, so a 7B model in float32 fits beside its
activations.

Compared: for every served token of the sampled requests, how far its
reference logit lies below the reference's best at that position, given
the served prefix (teacher forcing).  Greedy serving only.

The control is the same reference with every matrix rounded to int4
(symmetric, per output channel): at each position, the gap of the token
the int4 model puts first."""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from harness import counts, weights
from harness.weights import write_artifact  # noqa: F401  (part of the interface)


def shapes(model: dict) -> counts.Shapes:
    return counts.Shapes.of(model)


def _fake_quant(jnp, w, levels: int):
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / levels
    return jnp.clip(jnp.round(w / scale), -levels, levels) * scale


def _build(g: dict, seq: int):
    import jax
    import jax.numpy as jnp

    H, nh, nkv = g["hidden_size"], g["num_heads"], g["num_kv_heads"]
    hd = H // nh
    group = nh // nkv
    eps = g["rms_eps"]

    def rms(x):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)

    pos = jnp.arange(seq, dtype=jnp.float32)
    inv = 1.0 / (g["rope_theta"] ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    causal = jnp.tril(jnp.ones((seq, seq), bool))

    def rope(x):
        x1, x2 = jnp.split(x, 2, axis=-1)
        return x * cos + jnp.concatenate([-x2, x1], -1) * sin

    def one_row(x, w):  # x [S, H]
        xn = rms(x)[None]
        q = rope((xn @ w["q"]).reshape(1, seq, nh, hd))[0]
        k = rope((xn @ w["k"]).reshape(1, seq, nkv, hd))[0]
        v = (xn @ w["v"]).reshape(seq, nkv, hd)
        qg = q.reshape(seq, nkv, group, hd)
        sc = jnp.einsum("qngd,knd->ngqk", qg, k) / jnp.sqrt(jnp.float32(hd))
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        ctx = jnp.einsum("ngqk,knd->qngd", jax.nn.softmax(sc, -1), v)
        x = x + ctx.reshape(seq, nh * hd) @ w["o"]
        xn = rms(x)
        return x + (jax.nn.silu(xn @ w["gate"]) * (xn @ w["up"])) @ w["down"]

    @jax.jit
    def layer(x, w):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        return jax.lax.map(lambda row: one_row(row, w), x)

    @jax.jit
    def layer_int4(x, w):
        w = {k: _fake_quant(jnp, v.astype(jnp.float32), 7) for k, v in w.items()}
        return jax.lax.map(lambda row: one_row(row, w), x)

    @jax.jit
    def head(x, idx, lm_head):  # x [R,S,H], idx [R,A] -> logits [R,A,V]
        picked = jnp.take_along_axis(rms(x), idx[..., None], axis=1)
        return picked @ lm_head.astype(jnp.float32)

    @jax.jit
    def head_int4(x, idx, lm_head):
        picked = jnp.take_along_axis(rms(x), idx[..., None], axis=1)
        return picked @ _fake_quant(jnp, lm_head.astype(jnp.float32), 7)

    @jax.jit
    def gaps(logits, tokens):
        best = jnp.max(logits, axis=-1)
        mine = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
        return best - mine, jnp.argmax(logits, axis=-1)

    return layer, layer_int4, head, head_int4, gaps


def compare(model: dict, seed: int, rows: list[tuple[list[int], list[int]]],
            seq: int, answers: int, control: bool = False) -> dict:
    """`rows`: (prompt ids, served tokens) of each sampled request.  `seq`
    and `answers` are the padded sizes (fixed per mix, so one compile)."""
    import time

    import jax
    import jax.numpy as jnp
    import ml_dtypes

    jax.config.update("jax_default_matmul_precision", "highest")
    g = weights.geometry(model)
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
    layer, layer_int4, head, head_int4, gaps = _build(g, seq)

    clock = {"host_wait_s": 0.0, "to_device_s": 0.0}
    t_all = time.perf_counter()
    L = g["num_layers"]
    ahead = 3  # layers made on the host while the device works on this one
    weights.normal_table()
    with ThreadPoolExecutor(max_workers=weights.threads()) as ex:

        def whole(name: str):
            buf = np.empty(weights.leaf_shape(g, name), ml_dtypes.bfloat16)
            list(ex.map(lambda a: weights.fill_chunk(*a),
                        weights.fill_jobs(buf, seed, name, None)))
            return jnp.asarray(buf)

        # Host buffers are reused: in this sandbox a fresh page costs more
        # than the numbers that fill it.
        pool = [
            {k: np.empty(s, ml_dtypes.bfloat16)
             for k, s in weights.mat_shapes(g).items()}
            for _ in range(min(ahead, L))
        ]

        def submit(l: int, bufs: dict) -> list:
            return [ex.submit(weights.fill_chunk, *a) for k in weights.LAYER_MATS
                    for a in weights.fill_jobs(bufs[k], seed, k, l)]

        embed = whole("embed")
        x = embed[jnp.asarray(toks)].astype(jnp.float32)
        del embed
        xc = x if control else None
        pending = {l: (pool[l], submit(l, pool[l])) for l in range(len(pool))}
        for l in range(L):
            bufs, futs = pending.pop(l)
            t0 = time.perf_counter()
            for f in futs:
                f.result()
            t1 = time.perf_counter()
            w = {k: jnp.array(v) for k, v in bufs.items()}  # a copy: the CPU backend would alias
            jax.block_until_ready(w)  # the host buffers are free again
            clock["host_wait_s"] += t1 - t0
            clock["to_device_s"] += time.perf_counter() - t1
            if l + len(pool) < L:
                pending[l + len(pool)] = (bufs, submit(l + len(pool), bufs))
            x = layer(x, w)
            if control:
                xc = layer_int4(xc, w)
            del w
        lm_head = whole("lm_head")
    jidx, jserved = jnp.asarray(idx), jnp.asarray(served)
    logits = head(x, jidx, lm_head)
    gap, ref_best = gaps(logits, jserved)
    gap, ref_best = np.asarray(gap), np.asarray(ref_best)
    clock["total_s"] = time.perf_counter() - t_all
    out = {
        "seconds": {k: round(v, 2) for k, v in clock.items()},
        "rows": R,
        "served_tokens": int(valid.sum()),
        "max_logit_gap": float(gap[valid].max()),
        "mean_logit_gap": float(gap[valid].mean()),
        "argmax_agreement": float((ref_best == served)[valid].mean()),
    }
    if control:
        logits_c = head_int4(xc, jidx, lm_head)
        first_c = jnp.argmax(logits_c, axis=-1)
        gap_c, _ = gaps(logits, first_c)
        gap_c = np.asarray(gap_c)
        out["control_max_logit_gap"] = float(gap_c[valid].max())
        out["control_mean_logit_gap"] = float(gap_c[valid].mean())
        out["control_argmax_agreement"] = float(
            (np.asarray(first_c) == ref_best)[valid].mean())
    return out
