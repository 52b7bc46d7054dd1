"""Multi-host initialization (DCN) for multi-host TPU slices.

A v5e predictor larger than one host (e.g. v5e-16) runs as N pods that must
form one JAX process group before any collective can cross hosts.  In the
manifests each pod gets ``TPU_WORKER_HOSTNAMES``/coordinator env from the
GKE TPU webhook; here we translate that into ``jax.distributed.initialize``.

Single-host (or test/CPU) processes are a no-op, so the same server code
runs everywhere.
"""

from __future__ import annotations

import logging
import os

_log = logging.getLogger(__name__)

_initialized = False


def configure_cpu_rehearsal(num_local_devices: int = 1) -> None:
    """Rehearse the multi-host (DCN) path on CPU processes.

    Selects the CPU backend and its cross-process collectives
    implementation (Gloo) so ``maybe_initialize_distributed`` can form a
    REAL ``jax.distributed`` group between OS processes on one machine:
    after it, ``jax.device_count() > jax.local_device_count()`` and
    ``psum``/``all_gather`` genuinely cross process boundaries — the same
    code path a v5e multi-host slice takes over DCN, minus the TPU
    transport.  Must run before the group forms; it drops any
    already-created backends because the caller (pytest's conftest,
    say) may have initialized a different platform or device count.

    Proven by ``tests/test_distributed_group.py``: two processes, one
    coordinator, a cross-process ``psum`` with bitwise-checked results on
    both ranks (SURVEY §2.3 distributed-comm-backend obligation).
    """
    import jax
    from jax.extend import backend

    # Clear BEFORE the device-count update: with a backend already
    # live, jax_num_cpu_devices raises "config should be updated before
    # backends are initialized".
    jax.config.update("jax_platforms", "cpu")
    backend.clear_backends()
    jax.config.update("jax_num_cpu_devices", num_local_devices)
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def maybe_initialize_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> bool:
    """Initialize DCN process group if (and only if) multi-host env is set.

    Resolution order: explicit args > environment
    (``JAX_COORDINATOR_ADDRESS``/``JAX_NUM_PROCESSES``/``JAX_PROCESS_ID``,
    or the GKE TPU defaults that jax reads natively).  Returns True when
    ``jax.distributed.initialize`` was called.
    """
    global _initialized
    if _initialized:
        return True
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    )
    env_np = os.environ.get("JAX_NUM_PROCESSES")
    env_pid = os.environ.get("JAX_PROCESS_ID")
    if num_processes is None and env_np is not None:
        num_processes = int(env_np)
    if process_id is None and env_pid is not None:
        process_id = int(env_pid)

    if not coordinator_address or not num_processes or num_processes <= 1:
        _log.debug("single-process JAX (no coordinator configured)")
        return False

    import jax

    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    _initialized = True
    _log.info(
        "jax.distributed initialized: %d processes, this is process %s",
        num_processes,
        process_id,
    )
    return True
