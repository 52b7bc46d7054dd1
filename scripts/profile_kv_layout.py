"""KV-cache layout + commit-strategy microbench (VERDICT r4 item #2).

profile_decode.py showed two superlinear-cost components at high slot
counts: the post-scan scatter commit (14 ms at 64 slots — consistent
with XLA copying the cache buffers instead of writing in place) and
KV-window read marginal bandwidth decaying 612 -> 300 GB/s.  This bench
isolates both on raw buffers at 1.35B geometry, no model code:

commit strategies (write one [L,B,N,D] row-set at per-row positions):
- ``scatter``  — ``buf.at[:, rows, lengths].set(vals)`` (production);
- ``dus_loop`` — ``fori_loop`` over rows of per-row
  ``dynamic_update_slice`` (classic in-place pattern);
- ``same_pos`` — single ``dynamic_update_slice`` at one shared position
  (in-place upper bound; not ragged-correct, a bound only).

read/attention layouts (score einsum over the 512-window):
- ``bknd`` — cache stored [B, W, NKV, D], einsum "bqngd,bknd->bngqk"
  (production: position-major, head minor);
- ``bnkd`` — cache stored [B, NKV, W, D], einsum "bqngd,bnkd->bngqk"
  (head-major: the dot's natural operand layout — if production pays a
  materialized transpose, this variant shows the gap).

Run on the chip: ``python scripts/profile_kv_layout.py``.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


L, NKV, D, T, W = 24, 16, 128, 768, 512
GROUP = 1  # 1.35B is MHA: num_heads == num_kv_heads


def main() -> None:
    import bench
    from bench import _scan_delta_timed

    jax = bench._setup_jax()
    import jax.numpy as jnp
    from jax import lax

    results: dict = {}

    # 8-slot commits are in-place and sub-noise (the
    # delta collapses to zero — itself the answer); measure where the
    # model-level profile saw the superlinear cost.
    for slots in (32, 64):
        entry: dict = {}
        k8 = jnp.zeros((L, slots, T, NKV, D), jnp.int8)
        new_rows = jnp.ones((L, slots, NKV, D), jnp.int8)
        lengths0 = jnp.full((slots,), 256, jnp.int32)
        rows = jnp.arange(slots)

        # -- commit strategies (the buffer rides the scan carry, so each
        # iteration's write is a real loop-carried dependency) ----------
        def run_commit(kind) -> float:
            def step(carry):
                buf, lengths = carry
                # The written values depend on the PREVIOUS iteration's
                # write (dynamic-index read), and the probe reads THIS
                # iteration's write: the chain cannot be DCE'd or
                # scatter-forwarded (indices are traced values).
                prev = lax.dynamic_index_in_dim(
                    buf, lengths[0] - 1, axis=2, keepdims=False
                )[0, 0, 0, 0]
                vals = new_rows + prev
                if kind == "scatter":
                    buf = buf.at[:, rows, lengths].set(vals)
                elif kind == "dus_loop":
                    def body(i, b):
                        return lax.dynamic_update_slice(
                            b,
                            vals[:, i][:, None, None],
                            (0, i, lengths[i], 0, 0),
                        )
                    buf = lax.fori_loop(0, slots, body, buf)
                elif kind == "same_pos":
                    buf = lax.dynamic_update_slice(
                        buf,
                        vals[:, :, None],
                        (0, 0, lengths[0], 0, 0),
                    )
                probe = lax.dynamic_index_in_dim(
                    buf, lengths[0], axis=2, keepdims=False
                )[0, 0, 0, 0].astype(jnp.int32)
                lengths = lengths + 1
                return (buf, lengths), probe

            p = _scan_delta_timed(
                step, lambda i: (k8, lengths0 + i % 3), n1=8, n2=40
            )
            return p[50]

        for kind in ("scatter", "dus_loop", "same_pos"):
            try:
                entry[f"commit_{kind}_ms"] = round(run_commit(kind) * 1e3, 3)
            except RuntimeError as e:  # below the noise floor
                entry[f"commit_{kind}_ms"] = f"sub-noise ({e})"[:60]
        print(f"COMMIT {slots}: {json.dumps(entry)}", flush=True)

        # -- read/attention layouts --------------------------------------
        q = jnp.ones((slots, 1, NKV, GROUP, D), jnp.bfloat16)

        def run_read(layout) -> float:
            # Non-constant cache values: the probe (a reduction over the
            # scores) must differ across varied-q calls or the replay
            # detector rejects every sample.
            n_elem = slots * W * NKV * D
            data = (jnp.arange(n_elem, dtype=jnp.int32) % 251 - 125).astype(
                jnp.int8
            )
            if layout == "bknd":
                cache = data.reshape(slots, W, NKV, D)
                eq = "bqngd,bknd->bngqk"
            else:
                cache = data.reshape(slots, NKV, W, D)
                eq = "bqngd,bnkd->bngqk"

            def step(cache_arg, carry):
                qq, probe = carry
                scores = jnp.einsum(
                    eq, qq, cache_arg.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32,
                )
                # MAX, not sum: sum(einsum(q, K)) is linear in K, so XLA
                # rewrites it to einsum(q, sum(K)) and hoists the entire
                # cache read out of the loop as loop-invariant — the
                # "collapsed to zero" runs.  max cannot commute through
                # the contraction.
                s = jnp.max(jnp.abs(scores))
                # Feed the score back through q with a non-foldable tiny
                # multiplier: keeps a true data dependency between scan
                # iterations (mul-by-zero would constant-fold away).
                qq = qq + (s * jnp.float32(1e-30)).astype(jnp.bfloat16)
                return (qq, s), s

            # 0.125 * i: exactly representable in bf16 and >= one ulp at
            # 1.0 — a sub-ulp perturbation (e.g. 0.001*i) rounds away and
            # every carry would hold the identical input.
            p = _scan_delta_timed(
                step, lambda i: (q + jnp.bfloat16(0.125) * i, jnp.float32(0)),
                n1=32, n2=160, params=cache,
            )
            return p[50]

        for layout in ("bknd", "bnkd"):
            entry[f"read_{layout}_us"] = round(run_read(layout) * 1e6, 1)
        kv_bytes = slots * W * NKV * D
        entry["read_bknd_gbps"] = round(
            kv_bytes / (entry["read_bknd_us"] / 1e6) / 1e9, 1
        )
        entry["read_bnkd_gbps"] = round(
            kv_bytes / (entry["read_bnkd_us"] / 1e6) / 1e9, 1
        )

        results[str(slots)] = entry
        print(f"LAYOUT {slots}: {json.dumps(entry)}", flush=True)

    print(json.dumps(results))


if __name__ == "__main__":
    main()
