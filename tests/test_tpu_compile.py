"""Compile-only tests against a DESCRIBED TPU v5e (no chip attached).

The chip's compiler is installed beside jax; it compiles for a topology
that is described, not present.  That shows what interpret mode and the
CPU backend cannot: Mosaic lowering of the two Pallas kernels (tiling, VMEM),
HBM fit of the full-width serving programs, and what the chip's compiler
does with their donated caches.  Nothing runs, so these
say nothing about results or times — a pass here is not a chip run.

Rules this file keeps (on-chip-measurement guide §2): the topology is
described inside a module-scoped fixture that skips when it cannot be —
never at import; every compile happens in the test's own process; the
persistent compile cache is off around them (an entry compiled for a
described device cannot be read back); all such tests live in THIS file,
so exactly one xdist worker loads the TPU library.
"""

import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tpumlops.models import llama
from tpumlops.models.quantization import quantize_llama

# Llama-2-7B: the widths chip_smoke.py serves.
H, L, NH, NKV, INTER, VOCAB = 4096, 32, 32, 32, 11008, 32000
HD = H // NH
HBM = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prior)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    """Shape tree -> the same shapes placed on the described device."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree,
    )


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _cfg(max_seq=1024):
    return llama.LlamaConfig(
        vocab_size=VOCAB, hidden_size=H, num_layers=L, num_heads=NH,
        num_kv_heads=NKV, intermediate_size=INTER, max_seq=max_seq,
    )


def _int8_params(cfg):
    return jax.eval_shape(
        lambda: quantize_llama(
            llama.init(jax.random.key(0), cfg, dtype=jnp.bfloat16)
        )
    )


def _resident_bytes(compiled):
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes + m.temp_size_in_bytes
        - m.alias_size_in_bytes
    )


# ---------------------------------------------------------------------------
# Full-width serving programs: do they fit one chip's HBM?
# ---------------------------------------------------------------------------


def test_int8_decode_step_fits_one_chip_with_cache_donated(one_chip):
    """THE flagship step: Llama-2-7B int8 weights + int8 KV, 16 slots x
    1024, cache donated.  The chip's compiler credits the donation
    (alias > 0), and what stays resident is under 16 GiB."""
    cfg = _cfg()
    slots = 16
    params = _on(one_chip, _int8_params(cfg))
    cache = _on(
        one_chip,
        jax.eval_shape(lambda: llama.QuantRaggedKVCache.create(cfg, slots)),
    )

    def step(params, toks, cache, active):
        logits, cache = llama.decode_ragged(
            params, toks, cache, cfg, active=active, window=cfg.max_seq
        )
        return jnp.argmax(logits[:, -1], axis=-1), cache

    compiled = jax.jit(step, donate_argnums=(2,)).lower(
        params,
        _sds(one_chip, (slots, 1), jnp.int32),
        cache,
        _sds(one_chip, (slots,), jnp.bool_),
    ).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes > 2**30, "cache donation not credited"
    assert _resident_bytes(compiled) < HBM


def _bf16_cache(cfg, slots, one_chip):
    return _on(
        one_chip,
        jax.eval_shape(lambda: llama.RaggedKVCache.create(cfg, slots)),
    )


@pytest.mark.parametrize(
    "program", ["multistep", "verify", "packed_prefill", "superstep"]
)
def test_engine_programs_fit_one_chip_at_smoke_geometry(one_chip, program):
    """The engine's other step programs at what chip_smoke.py's server
    runs: int8 weights, bf16 KV, 8 slots x 1024, chunk 128."""
    cfg = _cfg()
    slots, chunk, steps = 8, 128, 4
    params = _on(one_chip, _int8_params(cfg))
    cache = _bf16_cache(cfg, slots, one_chip)
    s = functools.partial(_sds, one_chip)
    i32, b = jnp.int32, jnp.bool_

    def greedy(logits, carry):
        return carry, jnp.argmax(logits, axis=-1).astype(jnp.int32)

    if program == "multistep":
        def fn(params, toks, cache, active, remaining, eos):
            return llama.decode_multistep(
                params, toks, cache, cfg, active, remaining, eos, steps,
                greedy, window=cfg.max_seq,
            )
        args = (s((slots, 1), i32), cache, s((slots,), b),
                s((slots,), i32), s((slots,), i32))
    elif program == "verify":
        def fn(params, toks, cache, active):
            return llama.verify_ragged(
                params, toks, cache, cfg, window=cfg.max_seq, active=active
            )
        args = (s((slots, 5), i32), cache, s((slots,), b))
    elif program == "packed_prefill":
        def fn(params, toks, cache, rows, offsets):
            return llama.prefill_chunks_ragged(
                params, toks, cache, rows, offsets, cfg
            )
        args = (s((4, chunk), i32), cache, s((4,), i32), s((4,), i32))
    else:
        def fn(params, block, cache, roles, offsets, counts, draft, active,
               remaining, eos):
            return llama.super_step_ragged(
                params, block, cache, cfg, roles=roles, offsets=offsets,
                counts=counts, draft_len=draft, active=active,
                remaining=remaining, eos_ids=eos, steps=steps,
                sample_fn=greedy, window=cfg.max_seq,
            )
        vec = s((slots,), i32)
        args = (s((slots, chunk), i32), cache, vec, vec, vec, vec,
                s((slots,), b), vec, vec)

    compiled = jax.jit(fn, donate_argnums=(2,)).lower(params, *args).compile()
    assert _resident_bytes(compiled) < HBM


# The benchmark's two configurations (benchmarks/configs/*.json) and a
# decode window below each capacity, as the engine's buckets give.
_CELL_GEOMETRY = {
    "mistral": (
        dict(vocab_size=32768, hidden_size=4096, num_layers=32, num_heads=32,
             num_kv_heads=8, intermediate_size=14336, max_seq=2048,
             rope_theta=1e6),
        1024,
    ),
    "deepseek": (
        dict(vocab_size=102400, hidden_size=4096, num_layers=30, num_heads=32,
             num_kv_heads=32, intermediate_size=11008, max_seq=1024),
        768,
    ),
}

_HLO_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\("
)


def _array_instructions(hlo_text):
    """(name, dims, opcode) of every array-typed instruction."""
    for line in hlo_text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if m:
            name, dims, opcode = m.groups()
            yield name, [int(d) for d in dims.split(",") if d], opcode


def _kernel_calls(hlo_text, kernel):
    """The lines of the program's custom calls of the repo's Pallas
    kernel named ``kernel``."""
    return [l for l in hlo_text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in l and kernel in l]


def _assert_no_float32_scores(hlo_text, heads, queries):
    """No instruction of the program is a float32 ``[heads, queries,
    keys]`` tensor (under any batch axis): the scores of the prefill core
    stay in the kernel's VMEM."""
    for line in hlo_text.splitlines():
        m = re.match(r"^\s*(?:ROOT )?%?([\w.\-]+) = f32\[([\d,]*)\]", line)
        if m:
            dims = [int(d) for d in m.group(2).split(",") if d]
            dims = [d for d in dims if d != 1]
            assert not (len(dims) == 3 and dims[0] in heads and dims[1] == queries), (
                f"{m.group(1)}: float32 scores {dims} in HBM")


def _assert_buffer_left_in_place(compiled, buffer_elements, cfg, windowed):
    """No ``copy``/``transpose`` of a whole ``[L, B, T, NKV, D]`` buffer of
    ``buffer_elements`` and, where the program takes a window, no
    capacity-sized slab of one layer of it either."""
    slab_elements = buffer_elements // cfg.num_layers
    for name, dims, opcode in _array_instructions(compiled.as_text()):
        n = math.prod(dims)
        relayout = opcode in ("copy", "copy-start", "transpose") or (
            opcode == "fusion" and ("copy" in name or "transpose" in name)
        )
        assert not (relayout and n == buffer_elements), (
            f"{name}: a whole-cache {opcode} of {dims}"
        )
        if windowed:
            assert not (
                cfg.max_seq in dims and slab_elements <= n < buffer_elements
            ), f"{name}: a capacity-sized slab {dims} from {opcode}"


def _ragged_program(program, cfg, window, slots, one_chip):
    """``(fn, args)``: the engine's jit bodies over the ragged cache
    (server/generation.py), ``k``/``v`` at argument positions 2 and 3."""
    from tpumlops.models.sampling import sample_logits, split_keys

    s = functools.partial(_sds, one_chip)
    i32, f32, b = jnp.int32, jnp.float32, jnp.bool_
    vec = s((slots,), i32)
    toks, active = s((slots, 1), i32), s((slots,), b)
    chunk, steps = 128, 4

    def greedy(logits, carry):
        return carry, jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def cache_of(k, v, lengths):
        return llama.RaggedKVCache(k, v, lengths)

    if program == "decode_greedy":
        def fn(params, toks, k, v, lengths, active):
            logits, c = llama.decode_ragged(
                params, toks, cache_of(k, v, lengths), cfg, active=active,
                window=window,
            )
            nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(i32)
            return jnp.where(active, nxt, toks[:, 0])[:, None], c.k, c.v, c.lengths
        args = (toks, active)
    elif program == "decode_sampling":
        def fn(params, toks, k, v, lengths, active, keys, temps, tks, tps):
            logits, c = llama.decode_ragged(
                params, toks, cache_of(k, v, lengths), cfg, active=active,
                window=window,
            )
            keys2, use = split_keys(keys)
            nxt = sample_logits(logits[:, -1, :], use, temps, tks, tps)
            toks2 = jnp.where(active, nxt, toks[:, 0])[:, None]
            return toks2, c.k, c.v, c.lengths, keys2
        keys = _on(one_chip, jax.eval_shape(
            lambda: jax.random.split(jax.random.key(0), slots)
        ))
        args = (toks, active, keys, s((slots,), f32), vec, s((slots,), f32))
    elif program == "multistep":
        def fn(params, toks, k, v, lengths, active, remaining, eos):
            out = llama.decode_multistep(
                params, toks, cache_of(k, v, lengths), cfg, active,
                remaining, eos, steps, greedy, window=window,
            )
            c = out[3]
            return out[:3] + (c.k, c.v, c.lengths) + out[4:6]
        args = (toks, active, vec, vec)
    elif program == "verify":
        def fn(params, toks, k, v, lengths, active):
            logits, c = llama.verify_ragged(
                params, toks, cache_of(k, v, lengths), cfg, window=window,
                active=active,
            )
            return jnp.argmax(logits, axis=-1), c.k, c.v
        args = (s((slots, 5), i32), active)
    elif program == "packed_prefill":
        def fn(params, toks, k, v, lengths, rows, offsets):
            logits, c = llama.prefill_chunks_ragged(
                params, toks, cache_of(k, v, lengths), rows, offsets, cfg
            )
            return jnp.argmax(logits, axis=-1), c.k, c.v
        args = (s((4, chunk), i32), s((4,), i32), s((4,), i32))
    else:
        def fn(params, block, k, v, lengths, roles, offsets, counts, draft,
               active, remaining, eos):
            out = llama.super_step_ragged(
                params, block, cache_of(k, v, lengths), cfg, roles=roles,
                offsets=offsets, counts=counts, draft_len=draft,
                active=active, remaining=remaining, eos_ids=eos, steps=steps,
                sample_fn=greedy, window=window,
            )
            c = out[6]
            return out[1:6] + (c.k, c.v, c.lengths) + out[7:9]
        args = (s((slots, chunk), i32), vec, vec, vec, vec, active, vec, vec)
    return fn, args


@pytest.mark.parametrize("geometry", sorted(_CELL_GEOMETRY))
@pytest.mark.parametrize(
    "program",
    ["decode_greedy", "decode_sampling", "multistep", "verify",
     "packed_prefill", "superstep"],
)
def test_ragged_programs_leave_the_cache_in_place(one_chip, geometry, program):
    """The donated ``[L, B, T, NKV, D]`` buffers are the layout the
    programs compute in: the chip's compiler aliases both through every
    ragged program with no cache-shaped ``copy``/``transpose`` (with the
    cache head-major it put four around every step, half the step's
    device time), and the layer loop reads a window-sized slab, not a
    capacity-sized one.  The two chunk programs keep only the copy and
    alias rules: packed prefill attends the whole capacity by contract,
    and both carry ``[B, chunk, ...]`` float32 temporaries past 64 MiB."""
    kw, window = _CELL_GEOMETRY[geometry]
    cfg = llama.LlamaConfig(**kw)
    slots = 8
    windowed = program not in ("packed_prefill", "superstep")
    params = _on(one_chip, _int8_params(cfg))
    cache = _bf16_cache(cfg, slots, one_chip)
    fn, args = _ragged_program(program, cfg, window, slots, one_chip)
    compiled = jax.jit(fn, donate_argnums=(2, 3)).lower(
        params, args[0], cache.k, cache.v, cache.lengths, *args[1:]
    ).compile()

    buffer_elements = math.prod(cache.k.shape)
    buffer_bytes = buffer_elements * cache.k.dtype.itemsize
    _assert_buffer_left_in_place(compiled, buffer_elements, cfg, windowed)
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 2 * buffer_bytes, "k/v donation not credited"
    assert m.temp_size_in_bytes < (64 * 2**20 if windowed else buffer_bytes)


@pytest.mark.parametrize("geometry", sorted(_CELL_GEOMETRY))
def test_int8kv_decode_step_leaves_the_cache_values_in_place(one_chip, geometry):
    """The same for ``spec.tpu.quantize: int8kv``: the greedy decode step
    over ``QuantRaggedKVCache`` aliases all four donated buffers, copies
    neither int8 value buffer whole, and reads a window-sized slab of
    them a layer.  Not held, and so not asserted: the four float32 scale
    planes ``[L, B, T, NKV, 1]`` (16-30 MiB each) are relaid whole on the
    way in and out of every step (PERF.md section 7; no cell runs int8kv)."""
    kw, window = _CELL_GEOMETRY[geometry]
    cfg = llama.LlamaConfig(**kw)
    slots = 8
    params = _on(one_chip, _int8_params(cfg))
    cache = _on(
        one_chip,
        jax.eval_shape(lambda: llama.QuantRaggedKVCache.create(cfg, slots)),
    )

    def fn(params, toks, k8, ks, v8, vs, lengths, active):
        logits, c = llama.decode_ragged(
            params, toks, llama.QuantRaggedKVCache(k8, ks, v8, vs, lengths),
            cfg, active=active, window=window,
        )
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return (jnp.where(active, nxt, toks[:, 0])[:, None],
                c.k8, c.k_scale, c.v8, c.v_scale, c.lengths)

    compiled = jax.jit(fn, donate_argnums=(2, 3, 4, 5)).lower(
        params, _sds(one_chip, (slots, 1), jnp.int32), cache.k8,
        cache.k_scale, cache.v8, cache.v_scale, cache.lengths,
        _sds(one_chip, (slots,), jnp.bool_),
    ).compile()

    values = math.prod(cache.k8.shape)  # int8: elements are bytes
    _assert_buffer_left_in_place(compiled, values, cfg, windowed=True)
    scales = math.prod(cache.k_scale.shape) * cache.k_scale.dtype.itemsize
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 2 * (values + scales), "donation not credited"
    assert m.temp_size_in_bytes < values


def test_batch_generate_program_does_not_reserve_a_full_capacity_cache(one_chip):
    """The ``/infer`` path of an LLM (``generate_greedy``, warmed per
    batch bucket beside the serving engine).  Its loop carries a KV
    cache; sized to ``max_seq`` that was 9 GiB of temporaries at batch 8
    and the full-depth server could not load it on the chip (5.3 GiB
    were free).  Sized to the 80 positions the call can reach, the
    program's temporaries are under 2 GiB."""
    cfg = _cfg()
    params = _on(one_chip, _int8_params(cfg))
    compiled = jax.jit(
        lambda p, ids: llama.generate_greedy(p, ids, 64, cfg)
    ).lower(params, _sds(one_chip, (8, 16), jnp.int32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5 * 2**30


def test_tp4_decode_step_partitions_over_the_four_chip_host(topo):
    """What ``chip_smoke.py --chips 4`` serves: the int8 decode step on a
    {tp: 4} mesh over the host's four chips.  The compiler must be able
    to partition it (collectives inserted), and what ONE device holds is
    about a quarter of the one-chip program — not the whole model."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from tpumlops.models import partition

    cfg = _cfg()
    slots = 8
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("dp", "tp"))
    rep, kvsh, _seq = partition.engine_state_shardings(mesh, kv_quant=False)
    shapes = _int8_params(cfg)
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, partition.llama_param_shardings(shapes, mesh),
    )
    cache_shape = jax.eval_shape(
        lambda: llama.RaggedKVCache.create(cfg, slots)
    )
    cache = llama.RaggedKVCache(
        jax.ShapeDtypeStruct(cache_shape.k.shape, cache_shape.k.dtype,
                             sharding=kvsh),
        jax.ShapeDtypeStruct(cache_shape.v.shape, cache_shape.v.dtype,
                             sharding=kvsh),
        jax.ShapeDtypeStruct(cache_shape.lengths.shape, jnp.int32,
                             sharding=rep),
    )

    def step(params, toks, cache, active):
        logits, cache = llama.decode_ragged(
            params, toks, cache, cfg, active=active, window=cfg.max_seq
        )
        return jnp.argmax(logits[:, -1], axis=-1), cache

    compiled = jax.jit(step, donate_argnums=(2,)).lower(
        params,
        jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=rep),
        cache,
        jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=rep),
    ).compile()
    assert "all-reduce" in compiled.as_text()
    per_device = compiled.memory_analysis().argument_size_in_bytes
    whole = sum(
        int(np.prod(s.shape)) * s.dtype.itemsize
        for s in jax.tree.leaves((shapes, cache_shape))
    )
    # A quarter of weights + cache, plus the replicated norms/scales.
    assert whole / 4 <= per_device < whole / 3, (per_device, whole)


@pytest.mark.parametrize("program", ["decode", "prefill_chunk"])
def test_sparse_expert_programs_read_the_experts_where_they_lie(one_chip, program):
    """The latent-attention, sparse-expert family at the benchmark's
    published widths (5 of 40 layers, 8 slots x 2048, chunk 512): both
    serving programs fit the chip beside 11.1 GB of bf16 weights, the
    donated cache aliases, every expert matmul is the grouped-matmul kernel,
    and NO expert tensor is copied — stacked over layers, a dynamic slice
    of [256, 2048, 768] was materialised for each custom-call operand,
    0.8 GB three times a layer every step; the per-layer tree avoids it."""
    from tpumlops.models import mla_moe

    cfg = mla_moe.MlaMoeConfig(num_layers=5, max_seq=2048)
    params = _on(one_chip, jax.eval_shape(
        lambda: mla_moe.init(jax.random.key(0), cfg, jnp.bfloat16)))
    if program == "decode":
        cache = _on(one_chip, jax.eval_shape(
            lambda: mla_moe.RaggedKVCache.create(cfg, 8)))

        def fn(params, toks, k, v, lengths, active):
            logits, c, counts = mla_moe.decode_ragged(
                params, toks, mla_moe.RaggedKVCache(k, v, lengths), cfg,
                active=active, window=1536)
            return jnp.argmax(logits[:, -1], -1), c.k, c.v, c.lengths, counts

        args = (params, _sds(one_chip, (8, 1), jnp.int32), cache.k, cache.v,
                cache.lengths, _sds(one_chip, (8,), jnp.bool_))
    else:
        seq = _on(one_chip, jax.eval_shape(lambda: mla_moe.KVCache.create(cfg, 1)))

        def fn(params, ids, sk, sv, slen):
            logits, s, counts = mla_moe.forward(
                params, ids, mla_moe.KVCache(sk, sv, slen), cfg)
            return logits[0], s.k, s.v, s.length, counts

        args = (params, _sds(one_chip, (1, 512), jnp.int32), seq.k, seq.v,
                seq.length)
    compiled = jax.jit(fn, donate_argnums=(2, 3)).lower(*args).compile()
    mem = compiled.memory_analysis()
    weights = 2 * mla_moe.param_counts(cfg)[1]
    assert weights == 11_116_216_320
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 0.8 * HBM
    # The largest expert tensor is 805 MB: a copy of one would show here.
    assert mem.temp_size_in_bytes < 400 * 2**20, mem.temp_size_in_bytes
    cache_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(args[2:4]))
    assert mem.alias_size_in_bytes >= cache_bytes
    text = compiled.as_text()
    # PERF.md 7.8(f), closed in PR 33: no buffer of the latent cache (one
    # a layer) is copied or relaid whole on the way in or out.
    # (The step's slot cache: a chunk's [512, 2048] activations have the
    # element count of the batch-1 scratch's [1, 2048, 512] latents.)
    whole = {math.prod(a.shape) for a in jax.tree.leaves(args[2:4])}
    for name, dims, opcode in _array_instructions(text if program == "decode" else ""):
        relayout = opcode in ("copy", "copy-start", "transpose") or (
            opcode == "fusion" and ("copy" in name or "transpose" in name))
        assert not (relayout and math.prod(dims) in whole), (
            f"{name}: a whole-buffer {opcode} of {dims}")
    # Three grouped matmuls in each of the four expert layers, each the
    # repo's own kernel (ops/grouped_matmul.py) under the expert layer's
    # scope, and none left to XLA's 512-row lowering of ragged_dot.
    calls = _kernel_calls(text, "grouped_matmul")
    assert len(calls) == 12
    assert all("layer.moe_experts" in l for l in calls)
    assert "ragged-dot" not in text
    assert not re.search(r"bf16\[256,(2048,768|768,2048)\]\S* copy\(", text)
    # The chunk's softmax core is the fused kernel, once a layer, and no
    # float32 [32, 512, keys] scores are left in the program (the one-pass
    # softmax held [32, 512, 2048], 134 MB); a step has no such core.
    cores = _kernel_calls(text, "prefill_attention")
    assert len(cores) == (5 if program == "prefill_chunk" else 0)
    assert all("layer.attn_core" in l for l in cores)
    assert len(calls) + len(cores) == text.count('custom_call_target="tpu_custom_call"')
    if program == "prefill_chunk":
        _assert_no_float32_scores(text, heads={32}, queries=512)


@pytest.mark.parametrize("rows", [4096, 64, 8])
@pytest.mark.parametrize("k,n", [(2048, 768), (768, 2048)])
def test_grouped_matmul_lowers_at_the_published_expert_widths(one_chip, rows, k, n):
    """Mosaic takes the kernel at a 512-token chunk's 4096 token copies, a
    decode step's 64 and one token's 8, over 256 experts of 2048 x 768
    (gate, up) and 768 x 2048 (down), with a whole expert matrix a block:
    inside the 16 MiB of scoped VMEM, or the compile raises."""
    from tpumlops.ops import grouped_matmul as gm

    compiled = jax.jit(gm.grouped_matmul).lower(
        _sds(one_chip, (rows, k), jnp.bfloat16),
        _sds(one_chip, (256, k, n), jnp.bfloat16),
        _sds(one_chip, (256,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ragged-dot" not in text
    tm = gm.row_tile(rows, 256)
    assert gm._col_tile(k, n, tm, 2) == n  # the matrix is not split
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def _dots3_cfg():
    """``benchmarks/configs/dots3-note-prev-bf16.json`` as the program
    reads it: published widths, 5 of 46 layers (full, full, sliding x 3),
    32 of 256 experts, 19008 of 152064 vocabulary rows, 8704 positions."""
    from tpumlops.models import mla_moe

    f, s = mla_moe.FULL, mla_moe.SLIDING
    return mla_moe.MlaMoeConfig(
        vocab_size=19008, hidden_size=5120, num_layers=5, num_heads=128,
        q_lora_rank=1024, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, intermediate_size=13824,
        moe_intermediate_size=1536, n_routed_experts=256, n_shared_experts=1,
        num_experts_per_tok=8, first_k_dense_replace=1,
        routed_scaling_factor=1.0, max_seq=8704, rope_theta=8e7, rms_eps=1e-5,
        layer_types=(f, f, s, s, s), sliding_window=513, swa_num_heads=64,
        swa_q_lora_rank=1024, swa_kv_lora_rank=1024, swa_qk_nope_head_dim=192,
        swa_qk_rope_head_dim=64, swa_v_head_dim=128, swa_rope_theta=5e4,
        index_n_heads=64, index_head_dim=128, index_topk=2048,
        attention_gate="headwise", lora_rescale=True,
        n_local_experts=32, local_expert_start=0,
    )


@pytest.mark.parametrize("program", ["decode", "prefill_chunk"])
def test_two_kind_programs_leave_every_row_kind_in_place(one_chip, program):
    """The indexed / sliding configuration at the benchmark's published
    widths (8 slots x 8704 positions, chunk 512): the decode step at its
    widest window and the prefill chunk fit the chip beside 8.18 GB of
    bf16 weights; every donated cache buffer of every row kind (the full
    layers' RoPE key, latent and index key, the sliding layers' ring)
    aliases and none is copied or relaid whole; the chunk program holds no
    ``[heads, chunk, capacity]`` float32 scores (2.2 GB at 128 heads) nor
    anything that size; the expert matmuls are the kernel at 32 groups."""
    from tpumlops.models import mla_moe

    cfg = _dots3_cfg()
    params = _on(one_chip, jax.eval_shape(
        lambda: mla_moe.init(jax.random.key(0), cfg, jnp.bfloat16)))
    if program == "decode":
        cache = _on(one_chip, jax.eval_shape(
            lambda: mla_moe.RaggedKVCache.create(cfg, 8)))

        def fn(params, toks, k, v, lengths, active):
            logits, c, counts = mla_moe.decode_ragged(
                params, toks, mla_moe.RaggedKVCache(k, v, lengths), cfg,
                active=active, window=8704)
            return jnp.argmax(logits[:, -1], -1), c.k, c.v, c.lengths, counts

        args = (params, _sds(one_chip, (8, 1), jnp.int32), cache.k, cache.v,
                cache.lengths, _sds(one_chip, (8,), jnp.bool_))
    else:
        seq = _on(one_chip, jax.eval_shape(lambda: mla_moe.KVCache.create(cfg, 1)))

        def fn(params, ids, sk, sv, slen):
            logits, s, counts = mla_moe.forward(
                params, ids, mla_moe.KVCache(sk, sv, slen), cfg)
            return logits[0], s.k, s.v, s.length, counts

        args = (params, _sds(one_chip, (1, 512), jnp.int32), seq.k, seq.v,
                seq.length)
    compiled = jax.jit(fn, donate_argnums=(2, 3)).lower(*args).compile()
    mem = compiled.memory_analysis()
    weights = 2 * mla_moe.param_counts(cfg)[1]
    assert weights == 8_174_174_208  # ISSUE 33's table: 8.18e9 bytes (47.6 % of 16 GiB)
    # A position of the two full layers: latent 512 + RoPE key 64 (held
    # in a row of 128 lanes) + index key 128, bf16; three rings of 640.
    assert mla_moe.kv_row_bytes(cfg) == 8704 * 2 * 2 * (512 + 128 + 128) + 3 * 640 * 2 * (1024 + 128)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 0.8 * HBM
    buffers = jax.tree.leaves(args[2:4])
    assert len(buffers) == 2 * 3 + 3 * 2  # a layer: rope, index, latent; ring rope, ring latent
    cache_bytes = sum(a.size * a.dtype.itemsize for a in buffers)
    assert mem.alias_size_in_bytes >= cache_bytes, "a row kind's donation not credited"
    # 128 heads x 512 queries x 8704 positions of float32 scores would be
    # 2.28 GB; a key block's are 134 MB, and a few live at once.
    assert mem.temp_size_in_bytes < 1.5 * 2**30, mem.temp_size_in_bytes
    whole = {math.prod(a.shape) for a in buffers}
    for name, dims, opcode in _array_instructions(compiled.as_text()):
        relayout = opcode in ("copy", "copy-start", "transpose") or (
            opcode == "fusion" and ("copy" in name or "transpose" in name))
        assert not (relayout and math.prod(dims) in whole), (
            f"{name}: a whole-buffer {opcode} of {dims}")
        assert not (opcode != "parameter" and 8704 in dims
                    and math.prod(dims) >= 128 * 512 * 8704), (
            f"{name}: {dims} is a capacity-wide score tensor")
    text = compiled.as_text()
    calls = _kernel_calls(text, "grouped_matmul")
    assert len(calls) == 12
    assert all("layer.moe_experts" in l for l in calls)
    # The chunk's softmax core of either layer kind is the fused kernel
    # (two full layers, three sliding), and neither kind's float32
    # [heads, 512, keys] scores are left in the program.
    cores = _kernel_calls(text, "prefill_attention")
    assert len(cores) == (5 if program == "prefill_chunk" else 0)
    assert all("layer.attn_core" in l for l in cores)
    assert len(calls) + len(cores) == text.count('custom_call_target="tpu_custom_call"')
    if program == "prefill_chunk":
        _assert_no_float32_scores(text, heads={128, 64}, queries=512)
    for scope in ("layer.dsa_index", "layer.dsa_select", "layer.attn_gate",
                  "layer.attn_core"):
        assert scope in text, scope


CORE_GEOMETRIES = {
    # name: (heads, nope, rope, v, rank, keys): the three layer geometries
    # of the latent family's two configurations, a 512-token chunk.
    "dots3_note_full": (128, 128, 64, 128, 512, 8704),
    "dots3_note_sliding": (64, 192, 64, 128, 1024, 1024),
    "joyai_flash": (32, 128, 64, 128, 512, 2048),
}


@pytest.mark.parametrize("geometry", sorted(CORE_GEOMETRIES))
def test_prefill_attention_lowers_at_the_published_widths(one_chip, geometry):
    """Mosaic takes the fused prefill core at the published head counts
    and widths of all three layer geometries, 512 queries over key blocks
    of 512 with a traced block count, at the head group ``heads_per_step``
    picks: inside the 16 MiB of scoped VMEM, or the compile raises.  The
    program around it holds nothing the size of a score tensor."""
    from tpumlops.models import mla_moe
    from tpumlops.ops import prefill_attention as pa

    nh, nope, rope, v, rank, keys = CORE_GEOMETRIES[geometry]
    tiles = pa.tiles_for(512, keys, nh, nope, v, rank, mla_moe.LANES, 512, 2)
    assert tiles is not None and tiles.queries == tiles.keys == 512
    assert tiles.heads == (2 if geometry == "dots3_note_sliding" else 4)
    assert tiles.nope % 128 == 0

    cfg = mla_moe.MlaMoeConfig.tiny(
        num_heads=nh, qk_nope_head_dim=nope, qk_rope_head_dim=rope,
        v_head_dim=v, kv_lora_rank=rank)

    def core(q_nope, q_rope, kr, c, w, sees, written):
        return mla_moe._attn_blocks(
            q_nope, q_rope, kr, c, sees, written, {"kv_b": w}, cfg)

    bf = jnp.bfloat16
    compiled = jax.jit(core).lower(
        _sds(one_chip, (1, 512, nh, nope), bf), _sds(one_chip, (1, 512, nh, rope), bf),
        _sds(one_chip, (1, keys, mla_moe.LANES), bf), _sds(one_chip, (1, keys, rank), bf),
        _sds(one_chip, (rank, nh * (nope + v)), bf),
        _sds(one_chip, (1, 512, keys), jnp.bool_), _sds(one_chip, (), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert len(_kernel_calls(text, "prefill_attention")) == 1
    _assert_no_float32_scores(text, heads={nh}, queries=512)
    # Beside the kernel: the queries laid out a head beside the next (and
    # the sliding kind's padded weights), tens of MB, not a score tensor's
    # 134 MB.
    assert compiled.memory_analysis().temp_size_in_bytes < 100 * 2**20


def _qwen3_next_cfg():
    """``benchmarks/configs/qwen3-next-80b-a3b-bf16.json`` as the program
    runs it: 8 of 48 layers, 128 of 512 experts held, 37984 of 151936
    vocabulary rows, 8704 positions; every width as published."""
    from tpumlops.models import gdn_moe

    return gdn_moe.GdnMoeConfig(
        vocab_size=37984, num_layers=8, n_local_experts=128, max_seq=8704)


@pytest.mark.parametrize("program", ["decode", "prefill_chunk"])
def test_state_programs_leave_rows_and_state_in_place(one_chip, program):
    """The linear-attention configuration at the benchmark's published
    widths (8 slots x 8704 positions, chunk 512): the decode step at its
    widest window and the prefill chunk fit the chip beside 7.33 GB of
    bf16 weights; every donated buffer of both kinds (the two full
    layers' K and V rows, the six linear layers' float32 state and
    convolution tail) aliases, so the state is updated in place, and none
    is copied or relaid whole; the chunk holds no ``[heads, chunk,
    capacity]`` float32 scores; the expert matmuls are the kernel at 128
    groups."""
    from tpumlops.models import gdn_moe

    cfg = _qwen3_next_cfg()
    params = _on(one_chip, jax.eval_shape(
        lambda: gdn_moe.init(jax.random.key(0), cfg, jnp.bfloat16)))
    if program == "decode":
        cache = _on(one_chip, jax.eval_shape(
            lambda: gdn_moe.RaggedKVCache.create(cfg, 8)))

        def fn(params, toks, k, v, lengths, active):
            logits, c, counts = gdn_moe.decode_ragged(
                params, toks, gdn_moe.RaggedKVCache(k, v, lengths), cfg,
                active=active, window=8704)
            return jnp.argmax(logits[:, -1], -1), c.k, c.v, c.lengths, counts

        args = (params, _sds(one_chip, (8, 1), jnp.int32), cache.k, cache.v,
                cache.lengths, _sds(one_chip, (8,), jnp.bool_))
    else:
        seq = _on(one_chip, jax.eval_shape(lambda: gdn_moe.KVCache.create(cfg, 1)))

        def fn(params, ids, sk, sv, slen):
            logits, s, counts = gdn_moe.forward(
                params, ids, gdn_moe.KVCache(sk, sv, slen), cfg)
            return logits[0], s.k, s.v, s.length, counts

        args = (params, _sds(one_chip, (1, 512), jnp.int32), seq.k, seq.v,
                seq.length)
    compiled = jax.jit(fn, donate_argnums=(2, 3)).lower(*args).compile()
    mem = compiled.memory_analysis()
    leaves = sum(math.prod(a.shape) for a in jax.tree.leaves(params))
    assert leaves == 3_667_251_328  # ISSUE 35's table: 7,334,502,656 bytes, 42.7 % of 16 GiB
    assert gdn_moe.param_counts(cfg)[1] == leaves - (
        6 * (64 + 128) + 2 * 512 + 8 * 4096 + 2048)  # the vectors it leaves out
    # A slot: two full layers' K and V of 512 numbers a position, six
    # linear layers' float32 state (2 MiB) and three rows of 8192.
    state = 6 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)
    assert gdn_moe.state_row_bytes(cfg) == state == 12_877_824
    assert gdn_moe.kv_row_bytes(cfg) == 2 * 8704 * 2 * 512 * 2 + state
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 0.8 * HBM
    buffers = jax.tree.leaves(args[2:4])
    assert len(buffers) == 2 * 2 + 6 * 2  # a full layer: key, value; a linear one: conv, state
    cache_bytes = sum(a.size * a.dtype.itemsize for a in buffers)
    assert mem.alias_size_in_bytes >= cache_bytes, "a buffer's donation not credited"
    assert mem.temp_size_in_bytes < 1.5 * 2**30, mem.temp_size_in_bytes
    # The rows of either program, and the slots' state (16 MiB a layer; the
    # batch-1 scratch's 2 MiB is the carry of the chunked rule's loop).
    whole = {math.prod(a.shape) for a in buffers if a.ndim == 3 and a.shape[1] > 8}
    if program == "decode":
        whole |= {math.prod(a.shape) for a in buffers if a.ndim == 4}
    for name, dims, opcode in _array_instructions(compiled.as_text()):
        relayout = opcode in ("copy", "copy-start", "transpose") or (
            opcode == "fusion" and ("copy" in name or "transpose" in name))
        assert not (relayout and math.prod(dims) in whole), (
            f"{name}: a whole-buffer {opcode} of {dims}")
        assert not (opcode != "parameter" and 8704 in dims
                    and math.prod(dims) >= 16 * 512 * 8704), (
            f"{name}: {dims} is a capacity-wide score tensor")
    text = compiled.as_text()
    calls = _kernel_calls(text, "grouped_matmul")
    assert len(calls) == 24  # three a layer, eight layers
    assert all("layer.moe_experts" in l for l in calls)
    assert "ragged-dot" not in text
    assert len(calls) == text.count('custom_call_target="tpu_custom_call"')
    for scope in ("layer.gdn_in", "layer.gdn_conv", "layer.gdn_scan",
                  "layer.gdn_out", "state_commit", "kv_commit", "layer.attn_qkv",
                  "layer.attn_core", "layer.attn_gate", "layer.attn_out",
                  "layer.moe_router", "layer.moe_experts", "layer.moe_shared"):
        assert scope in text, scope


def _laguna_cfg():
    """``benchmarks/configs/laguna-s-2.1-bf16.json`` as the program runs
    it: 8 of 48 layers (full + dense, sliding x 3, full, sliding x 3), 32
    of 256 experts held, 12544 of 100352 vocabulary rows, 8704 positions;
    every width as published."""
    import json
    import sys
    from pathlib import Path

    from tpumlops.models import gdn_moe

    bench = Path(__file__).resolve().parents[1] / "benchmarks"
    if str(bench) not in sys.path:
        sys.path.append(str(bench))
    from references import laguna_decoder

    model = json.loads((bench / "configs" / "laguna-s-2.1-bf16.json").read_text())["model"]
    return gdn_moe.GdnMoeConfig(**laguna_decoder.geometry(model))


@pytest.mark.parametrize("program", ["decode", "prefill_chunk"])
def test_window_programs_leave_rows_and_ring_in_place(one_chip, program):
    """The window configuration at the benchmark's published widths (8
    slots x 8704 positions, chunk 512): the decode step at its widest
    window and the prefill chunk fit the chip beside 5.69 GB of bf16
    weights; every donated buffer (the two full layers' K and V rows, the
    six sliding layers' rings of 512 rows) aliases and none is copied or
    relaid whole; the chunk holds no ``[heads, chunk, capacity]`` float32
    scores; the expert matmuls are the kernel at 32 groups in the seven
    expert layers."""
    from tpumlops.models import gdn_moe

    cfg = _laguna_cfg()
    assert cfg.kinds == (gdn_moe.FULL,) + (gdn_moe.SLIDING,) * 3 + (
        gdn_moe.FULL,) + (gdn_moe.SLIDING,) * 3
    params = _on(one_chip, jax.eval_shape(
        lambda: gdn_moe.init(jax.random.key(0), cfg, jnp.bfloat16)))
    if program == "decode":
        cache = _on(one_chip, jax.eval_shape(
            lambda: gdn_moe.RaggedKVCache.create(cfg, 8)))

        def fn(params, toks, k, v, lengths, active):
            logits, c, counts = gdn_moe.decode_ragged(
                params, toks, gdn_moe.RaggedKVCache(k, v, lengths), cfg,
                active=active, window=8704)
            return jnp.argmax(logits[:, -1], -1), c.k, c.v, c.lengths, counts

        args = (params, _sds(one_chip, (8, 1), jnp.int32), cache.k, cache.v,
                cache.lengths, _sds(one_chip, (8,), jnp.bool_))
    else:
        seq = _on(one_chip, jax.eval_shape(lambda: gdn_moe.KVCache.create(cfg, 1)))

        def fn(params, ids, sk, sv, slen):
            logits, s, counts = gdn_moe.forward(
                params, ids, gdn_moe.KVCache(sk, sv, slen), cfg)
            return logits[0], s.k, s.v, s.length, counts

        args = (params, _sds(one_chip, (1, 512), jnp.int32), seq.k, seq.v,
                seq.length)
    compiled = jax.jit(fn, donate_argnums=(2, 3)).lower(*args).compile()
    mem = compiled.memory_analysis()
    leaves = sum(math.prod(a.shape) for a in jax.tree.leaves(params))
    assert leaves == 2_843_053_056  # 5,686,106,112 bytes, 33.1 % of 16 GiB
    assert gdn_moe.param_counts(cfg)[1] == leaves - (8 * 2 * 3072 + 3072)  # the norms
    # A slot: two full layers' K and V of 1024 numbers a position, and six
    # rings of 512 rows of the same.
    assert gdn_moe.ring_row_bytes(cfg) == 6 * 512 * 2 * 1024 * 2
    assert gdn_moe.kv_row_bytes(cfg) == 2 * 8704 * 2 * 1024 * 2 + 6 * 512 * 2 * 1024 * 2
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 0.8 * HBM
    buffers = jax.tree.leaves(args[2:4])
    assert len(buffers) == 2 * 2 + 6 * 2  # a full layer: key, value; a sliding one: its ring's
    cache_bytes = sum(a.size * a.dtype.itemsize for a in buffers)
    assert mem.alias_size_in_bytes >= cache_bytes, "a buffer's donation not credited"
    assert mem.temp_size_in_bytes < 1.5 * 2**30, mem.temp_size_in_bytes
    # The slots' rows and rings; in the chunk the scratch's rows (its ring
    # of [1, 512, 1024] has the element count of the chunk's own K or V
    # rows, which the projections lay out as they like).
    whole = {math.prod(a.shape) for a in buffers
             if program == "decode" or a.shape[1] > cfg.ring_rows}
    for name, dims, opcode in _array_instructions(compiled.as_text()):
        relayout = opcode in ("copy", "copy-start", "transpose") or (
            opcode == "fusion" and ("copy" in name or "transpose" in name))
        assert not (relayout and math.prod(dims) in whole), (
            f"{name}: a whole-buffer {opcode} of {dims}")
        assert not (opcode != "parameter" and 8704 in dims
                    and math.prod(dims) >= 48 * 512 * 8704), (
            f"{name}: {dims} is a capacity-wide score tensor")
    text = compiled.as_text()
    calls = _kernel_calls(text, "grouped_matmul")
    assert len(calls) == 21  # three a layer, seven expert layers
    assert all("layer.moe_experts" in l for l in calls)
    assert "ragged-dot" not in text
    assert len(calls) == text.count('custom_call_target="tpu_custom_call"')
    for scope in ("ring_commit", "kv_commit", "rope", "layer.attn_qkv",
                  "layer.attn_core", "layer.attn_gate", "layer.attn_out",
                  "layer.mlp", "layer.moe_router", "layer.moe_experts",
                  "layer.moe_shared"):
        assert scope in text, scope
