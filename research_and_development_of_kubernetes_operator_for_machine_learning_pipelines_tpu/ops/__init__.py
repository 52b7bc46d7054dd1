"""TPU kernels and collective attention with XLA fallbacks.

The reference has no compute kernels at all (its data plane is Seldon's
generic container); these are the ops of the rebuild's first-party data
plane that plain XLA does not give:

- ``ring_attention``   — sequence parallelism over the ``sp`` mesh axis:
  KV blocks rotate around the ICI ring while each device keeps only its
  sequence shard (long-context prefill, called by ``models/llama.py``).
- ``grouped_matmul``   — the sparse-expert FFN's matmuls over token copies
  sorted by expert: a row tile that follows from the call's static shapes,
  only the (expert, row tile) pairs that share a row visited, each
  expert's matrix read once (import it from ``ops.grouped_matmul``, whose
  module also holds the tile choice and the visit schedule).  Off the TPU
  it is ``lax.ragged_dot``, which is also its oracle in tests (the kernel
  runs in interpret mode on CPU).
- ``prefill_attention`` — the softmax core of the latent-attention
  family's prefill: keys and values expanded from the latent, scores,
  mask, running maximum, sum and accumulator of a key block in VMEM, only
  the written key blocks walked (``ops.prefill_attention``).  Off the TPU,
  and at shapes its tiles do not take, it is the caller's einsum body,
  its oracle in tests.  These two are the Pallas kernels on a family's
  default path.
"""

from .ring_attention import ring_attention, ring_attention_sharded

__all__ = [
    "ring_attention",
    "ring_attention_sharded",
]
