"""TPU kernels (Pallas) with XLA fallbacks.

The reference has no compute kernels at all (its data plane is Seldon's
generic container); these are the hot ops of the rebuild's first-party
data plane:

- ``flash_attention`` — blockwise online-softmax attention: O(S) memory
  instead of the O(S^2) score matrix, VMEM-resident tiles feeding the MXU.
- ``rmsnorm``          — fused normalize+scale in one VMEM pass.
- ``ring_attention``   — sequence parallelism over the ``sp`` mesh axis:
  KV blocks rotate around the ICI ring while each device keeps only its
  sequence shard (long-context serving).
- ``grouped_matmul``   — the sparse-expert FFN's matmuls over token copies
  sorted by expert: a row tile that follows from the call's static shapes,
  only the (expert, row tile) pairs that share a row visited, each
  expert's matrix read once (the one kernel on a family's default path;
  import it from ``ops.grouped_matmul``, whose module also holds the tile
  choice and the visit schedule).

Every op has a pure-XLA reference implementation used as fallback off-TPU
and as the numerical oracle in tests (kernels run in interpret mode on CPU).
"""

from .flash_attention import flash_attention, attention_reference
from .rmsnorm import rmsnorm, rmsnorm_reference
from .ring_attention import ring_attention, ring_attention_sharded

__all__ = [
    "flash_attention",
    "attention_reference",
    "rmsnorm",
    "rmsnorm_reference",
    "ring_attention",
    "ring_attention_sharded",
]
