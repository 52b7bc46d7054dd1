"""Pre-baked weight snapshots: the device-resident tree on disk, restorable
with zero transform work.

Why: a 7B cold load reads 12.55 GiB of bf16 from disk only to quantize
it down to 6.4 GiB of int8 on device (seconds: not measured on today's
code).  Both λScale and "Breaking
the Ice" (PAPERS.md) locate the scale-to-zero win in the same place:
stop re-deriving the device state on every boot.  A snapshot is the
*exact post-shard, post-quantize* param tree — q8/scale planes included —
written once after the first successful load, so a restore is a straight
disk→device stream: ~2x fewer bytes read than the bf16 artifact and no
``quantize_s`` / reshard stage at all.

Layout (one directory per snapshot)::

    <dir>/<content_hash>/
        SNAPSHOT.json     # manifest: format version, identity, leaf index
        chunk-00000.bin   # concatenated raw leaf bytes (bounded size)
        chunk-00001.bin
        ...

The manifest indexes every leaf as ``(file, offset, nbytes, dtype, shape,
crc32)``; leaves are never split across chunk files, so a restore can
stream file-by-file with a reader thread while the consumer transfers the
previous leaves to the device (same overlap discipline as
``loader._stream_native_params``, minus the transform work).

Tensor-parallel trees (``meshShape`` tp > 1) extend a leaf entry with a
SHARD axis: a partitioned leaf carries ``spec`` (its PartitionSpec as
data) and ``shards`` — one ``(file, offset, nbytes, crc32, start,
shape)`` record per device shard, each written from that device's own
buffer.  A restore rebuilds the mesh from the manifest identity's
``mesh_shape`` and device-puts each shard straight onto its device
(``jax.make_array_from_single_device_arrays``), so at no point does the
full tree — or even a full sharded leaf, beyond the one being assembled
— materialize on one host.  Replicated leaves (norms, scales of
row-split matrices) keep the flat single-copy layout, so a ``tp: 1``
snapshot's manifest and chunks are byte-for-byte the pre-tp format.

The shard plan is spec-driven, not axis-named: a ``{dp: N}`` mesh (PR 17)
replicates every weight leaf over dp while tp still splits heads, and the
plan's slice-start dedup writes each DISTINCT shard block exactly once —
a dp x tp tree snapshots the same bytes as the tp-only tree, and restore
reassembles against whatever mesh ``identity.mesh_shape`` names (dp/sp
axes included) because ``devices_indices_map`` carries the full
placement.  No dp/sp-specific code exists here; the geometry tests in
``tests/test_data_parallel.py`` pin that property.

Identity and invalidation: the snapshot is keyed by a content hash of
``(model version/uri, quantize mode, mesh shape, format version)``.  Any
mismatch — a new model version, a different quantize mode, a resharded
mesh, a format bump — makes the hash differ, so the restore path simply
misses and the caller falls back to the cold load (which then re-bakes).
Corruption (truncated chunk, CRC mismatch, malformed manifest) raises the
typed :class:`SnapshotError` instead of serving garbage weights.
"""

from __future__ import annotations

import binascii
import hashlib
import json
import logging
import os
import shutil
import tempfile
import time
from pathlib import Path
from typing import Any

import numpy as np

_log = logging.getLogger(__name__)

# Bump when the on-disk layout changes; a version mismatch is an ordinary
# cache miss (cold load + re-bake), never an error.
FORMAT_VERSION = 1

MANIFEST_NAME = "SNAPSHOT.json"

# Leaves are packed into chunk files of at most this many bytes (a leaf
# larger than the bound gets its own file).  Bounded chunks keep restore
# read-ahead and CRC verification incremental instead of one giant file.
DEFAULT_CHUNK_BYTES = 256 * 2**20


class SnapshotError(Exception):
    """Typed failure of a snapshot read: corrupt/truncated chunk, CRC
    mismatch, malformed manifest.  Callers treat it as 'this snapshot is
    unusable' and fall back to the cold load path."""


class SnapshotMismatch(SnapshotError):
    """The snapshot on disk was baked for a different identity (model
    version, quantize mode, mesh) or format version — a cache miss, not
    corruption."""


# ---------------------------------------------------------------------------
# Identity
# ---------------------------------------------------------------------------


def snapshot_identity(
    model_uri: str, quantize: str | None, mesh_shape: dict | None
) -> dict[str, Any]:
    """The invalidation key, as data: everything that changes the device
    tree a load produces."""
    return {
        "model_uri": str(model_uri),
        "quantize": quantize or "none",
        "mesh_shape": {k: int(v) for k, v in sorted((mesh_shape or {}).items())},
        "format_version": FORMAT_VERSION,
    }


def content_hash(identity: dict[str, Any]) -> str:
    """Stable short hash of an identity dict (sorted-key JSON, sha256)."""
    blob = json.dumps(identity, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def snapshot_path_for(snapshot_dir: str | Path, model_uri: str) -> Path:
    """Deterministic snapshot location for a model artifact — the operator
    computes the same path to record ``status.snapshot.uri`` on a parked
    CR without ever touching the data plane.

    Keyed by the model URI ONLY (a new model version is a new URI, so it
    bakes beside the old); the quantize/mesh half of the identity lives
    in the manifest's content hash, so flipping those knobs hits the
    same location, mismatches, falls back to the cold load, and re-bakes
    in place — stale state can never be restored, only replaced."""
    tag = hashlib.sha256(str(model_uri).encode()).hexdigest()[:16]
    return Path(snapshot_dir) / tag


# ---------------------------------------------------------------------------
# dtype round-trip (numpy has no native bf16; ml_dtypes supplies it)
# ---------------------------------------------------------------------------


def _dtype_from_name(name: str) -> np.dtype:
    if name == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


def _leaf_to_numpy(leaf: Any) -> np.ndarray:
    """Device array -> host ndarray with its dtype intact (bf16 stays
    bf16 — the whole point is writing the device-resident bytes)."""
    arr = np.asarray(leaf)
    return np.ascontiguousarray(arr)


def _spec_to_data(spec) -> list:
    """PartitionSpec -> JSON-serializable form (axis name, list of
    names, or None per dimension)."""
    out = []
    for p in spec:
        if p is None:
            out.append(None)
        elif isinstance(p, (tuple, list)):
            out.append([str(a) for a in p])
        else:
            out.append(str(p))
    return out


def _spec_from_data(data) -> "Any":
    from jax.sharding import PartitionSpec

    return PartitionSpec(
        *[tuple(p) if isinstance(p, list) else p for p in data]
    )


def _shard_plan(leaf: Any):
    """``None`` for a single-device/replicated leaf (flat layout), else
    ``(spec_data, [(starts, shard_ndarray), ...])`` for a partitioned
    one — each shard the bytes ONE device holds, deduplicated by slice
    start (partial replication writes each distinct block once)."""
    sharding = getattr(leaf, "sharding", None)
    if sharding is None:
        return None
    try:
        from jax.sharding import NamedSharding

        if not isinstance(sharding, NamedSharding):
            return None
        if len(sharding.device_set) <= 1 or sharding.is_fully_replicated:
            return None
    except Exception:  # pragma: no cover - exotic sharding types
        return None
    seen: dict[tuple, np.ndarray] = {}
    for s in leaf.addressable_shards:
        starts = tuple(int(sl.start or 0) for sl in s.index)
        if starts not in seen:
            seen[starts] = np.ascontiguousarray(np.asarray(s.data))
    return _spec_to_data(sharding.spec), sorted(seen.items())


# ---------------------------------------------------------------------------
# Writing
# ---------------------------------------------------------------------------


def write_snapshot(
    snapshot_dir: str | Path,
    params: Any,
    *,
    identity: dict[str, Any],
    flavor: str,
    config: dict | None = None,
    builder_kwargs: dict | None = None,
    chunk_bytes: int = DEFAULT_CHUNK_BYTES,
) -> Path:
    """Write the device tree as a restorable snapshot; returns its path.

    Atomic: everything is staged in a temp directory next to the target
    and renamed into place, so a crash mid-write can never leave a
    half-snapshot that a later restore would trust (restores also verify
    per-leaf CRCs, but the rename makes the common case clean).  Writing
    over an existing snapshot of the same model URI replaces it whole.
    """
    from .loader import _flatten  # one flattening scheme, spelled once

    target = snapshot_path_for(snapshot_dir, identity["model_uri"])
    target.parent.mkdir(parents=True, exist_ok=True)
    t0 = time.perf_counter()
    # convert=False: leaves keep their device placement so _shard_plan
    # can see a partitioned leaf's sharding and write it per-shard.
    flat = _flatten(params, convert=False)
    staging = Path(
        tempfile.mkdtemp(prefix=".snapshot-", dir=str(target.parent))
    )
    try:
        leaves = []
        state = {
            "idx": -1,
            "f": None,
            # force a fresh chunk on first blob
            "used": chunk_bytes + 1,
            "total": 0,
        }

        def emit(raw: bytes) -> tuple[str, int]:
            """Append one blob to the current (or a fresh) chunk file;
            returns its (file, offset)."""
            if state["used"] + len(raw) > chunk_bytes and state["used"] > 0:
                if state["f"] is not None:
                    state["f"].close()
                state["idx"] += 1
                state["f"] = open(
                    staging / f"chunk-{state['idx']:05d}.bin", "wb"
                )
                state["used"] = 0
            off = state["used"]
            state["f"].write(raw)
            state["used"] += len(raw)
            state["total"] += len(raw)
            return f"chunk-{state['idx']:05d}.bin", off

        try:
            for key in sorted(flat):
                plan = _shard_plan(flat[key])
                if plan is None:
                    # Flat layout — byte-for-byte the pre-tp format for
                    # every single-device/replicated leaf.
                    arr = _leaf_to_numpy(flat[key])
                    raw = arr.tobytes()
                    fname, off = emit(raw)
                    leaves.append(
                        {
                            "key": key,
                            "dtype": arr.dtype.name,
                            "shape": list(arr.shape),
                            "file": fname,
                            "offset": off,
                            "nbytes": len(raw),
                            "crc32": binascii.crc32(raw) & 0xFFFFFFFF,
                        }
                    )
                    continue
                spec_data, shards = plan
                entry = {
                    "key": key,
                    "dtype": shards[0][1].dtype.name,
                    "shape": list(flat[key].shape),
                    "spec": spec_data,
                    "shards": [],
                }
                for starts, sarr in shards:
                    raw = sarr.tobytes()
                    fname, off = emit(raw)
                    entry["shards"].append(
                        {
                            "file": fname,
                            "offset": off,
                            "nbytes": len(raw),
                            "crc32": binascii.crc32(raw) & 0xFFFFFFFF,
                            "start": list(starts),
                            "shape": list(sarr.shape),
                        }
                    )
                leaves.append(entry)
        finally:
            if state["f"] is not None:
                state["f"].close()
        total = state["total"]
        manifest = {
            "format_version": FORMAT_VERSION,
            "identity": identity,
            "content_hash": content_hash(identity),
            "flavor": flavor,
            "config": config or {},
            "builder_kwargs": builder_kwargs or {},
            "total_bytes": total,
            "leaves": leaves,
        }
        (staging / MANIFEST_NAME).write_text(json.dumps(manifest, indent=1))
        if target.exists():
            shutil.rmtree(target)
        os.replace(staging, target)
    except BaseException:
        shutil.rmtree(staging, ignore_errors=True)
        raise
    _log.info(
        "wrote snapshot %s: %d leaves, %.2f GiB in %.1fs",
        target,
        len(leaves),
        total / 2**30,
        time.perf_counter() - t0,
    )
    return target


# ---------------------------------------------------------------------------
# Reading
# ---------------------------------------------------------------------------


def read_manifest(path: str | Path) -> dict[str, Any]:
    """Parse + structurally validate a snapshot manifest.  Raises
    :class:`SnapshotError` on anything malformed."""
    mf = Path(path) / MANIFEST_NAME
    if not mf.exists():
        raise SnapshotError(f"no {MANIFEST_NAME} in {path}")
    try:
        manifest = json.loads(mf.read_text())
    except (OSError, json.JSONDecodeError) as e:
        raise SnapshotError(f"unreadable snapshot manifest {mf}: {e}") from e
    if not isinstance(manifest, dict) or not isinstance(
        manifest.get("leaves"), list
    ):
        raise SnapshotError(f"malformed snapshot manifest {mf}")
    return manifest


def check_identity(manifest: dict, identity: dict[str, Any]) -> None:
    """Raise :class:`SnapshotMismatch` unless the manifest was baked for
    exactly this identity (format version rides inside the identity)."""
    if int(manifest.get("format_version", -1)) != FORMAT_VERSION:
        raise SnapshotMismatch(
            f"snapshot format v{manifest.get('format_version')} != "
            f"v{FORMAT_VERSION}"
        )
    if manifest.get("content_hash") != content_hash(identity):
        raise SnapshotMismatch(
            "snapshot identity mismatch: baked for "
            f"{manifest.get('identity')}, requested {identity}"
        )


def load_snapshot(
    path: str | Path,
    *,
    identity: dict[str, Any] | None = None,
    stats: dict | None = None,
    to_device: bool = True,
) -> tuple[Any, dict[str, Any]]:
    """Restore ``(params, manifest)`` from a snapshot directory.

    Streams leaf-by-leaf with a reader thread so disk read overlaps the
    host→device transfer (the restore is pure I/O: no quantize, no
    reshard — the bytes on disk ARE the device layout).  Each leaf's CRC
    is verified before its bytes are trusted; a truncated chunk or CRC
    mismatch raises :class:`SnapshotError`.  When ``identity`` is given,
    a mismatch raises :class:`SnapshotMismatch` BEFORE any data is read.

    ``stats`` (optional dict) is filled with ``restore_s`` / ``disk_s`` /
    ``transfer_s`` / ``read_gib`` so a slow restore says which stage was
    slow — same shape the cold path's ``load_stats`` uses.

    Per-shard leaves (a tp > 1 bake) restore WITHOUT ever assembling the
    full leaf on host: the mesh is rebuilt from the manifest identity's
    ``mesh_shape`` and each shard device-puts straight onto its device
    (``jax.make_array_from_single_device_arrays``).  Restoring a
    sharded snapshot onto a process with too few devices raises
    :class:`SnapshotError` (the caller cold-loads).
    """
    import queue as _queue
    import threading

    from .loader import _unflatten

    path = Path(path)
    manifest = read_manifest(path)
    if identity is not None:
        check_identity(manifest, identity)

    # Flatten leaves into one read plan: a flat leaf is one record, a
    # sharded leaf one record per shard (written contiguously, so the
    # reader stays sequential per chunk file).
    records: list[dict] = []
    sharded = False
    for leaf in manifest["leaves"]:
        if "shards" in leaf:
            sharded = True
            for i, srec in enumerate(leaf["shards"]):
                records.append(
                    {
                        **srec,
                        "key": leaf["key"],
                        "dtype": leaf["dtype"],
                        "leaf": leaf,
                        "last_shard": i == len(leaf["shards"]) - 1,
                    }
                )
        else:
            records.append({**leaf, "leaf": None})

    mesh = None
    if sharded and to_device:
        mesh_shape = (manifest.get("identity") or {}).get("mesh_shape") or {}
        try:
            from ..models.partition import build_serving_mesh

            mesh = build_serving_mesh(mesh_shape)
        except Exception as e:
            # MISMATCH, not corruption: the snapshot is valid, THIS
            # process just cannot host its mesh (fewer visible devices —
            # a CPU debug run, a degraded slice).  SnapshotError here
            # would make the loader quarantine a perfectly good bake
            # over an environmental condition.
            raise SnapshotMismatch(
                f"sharded snapshot needs mesh {mesh_shape}, which this "
                f"process cannot build: {e}"
            ) from e

    t_wall = time.perf_counter()
    timing = {"disk_s": 0.0, "transfer_s": 0.0, "read_bytes": 0}
    q: _queue.Queue = _queue.Queue(maxsize=4)
    reader_error: list[BaseException] = []
    abort = threading.Event()

    def reader() -> None:
        open_file = None
        open_name = None
        try:
            for rec in records:
                if abort.is_set():
                    return
                t0 = time.perf_counter()
                if rec["file"] != open_name:
                    if open_file is not None:
                        open_file.close()
                    fpath = path / rec["file"]
                    if not fpath.exists():
                        raise SnapshotError(
                            f"snapshot chunk {rec['file']} missing in {path}"
                        )
                    open_file = open(fpath, "rb")
                    open_name = rec["file"]
                open_file.seek(rec["offset"])
                raw = open_file.read(rec["nbytes"])
                if len(raw) != rec["nbytes"]:
                    raise SnapshotError(
                        f"snapshot chunk {rec['file']} truncated at leaf "
                        f"{rec['key']!r}: wanted {rec['nbytes']} bytes, "
                        f"got {len(raw)}"
                    )
                if (binascii.crc32(raw) & 0xFFFFFFFF) != rec["crc32"]:
                    raise SnapshotError(
                        f"snapshot leaf {rec['key']!r} failed CRC in "
                        f"{rec['file']}"
                    )
                arr = np.frombuffer(
                    raw, dtype=_dtype_from_name(rec["dtype"])
                ).reshape(rec["shape"])
                timing["disk_s"] += time.perf_counter() - t0
                timing["read_bytes"] += rec["nbytes"]
                q.put((rec, arr))
        except BaseException as e:
            reader_error.append(e)
        finally:
            if open_file is not None:
                open_file.close()
            q.put(None)

    rthread = threading.Thread(
        target=reader, daemon=True, name="snapshot-reader"
    )
    rthread.start()

    leaves: dict[str, Any] = {}
    pending: dict[str, dict[tuple, np.ndarray]] = {}
    try:
        if to_device:
            import jax
            import jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec

            rep = (
                NamedSharding(mesh, PartitionSpec())
                if mesh is not None else None
            )

        def place_flat(arr):
            if not to_device:
                return arr
            # Replicated leaves of a sharded tree commit to the mesh so
            # the engine programs see one consistent device set.
            return jnp.asarray(arr) if rep is None else jax.device_put(
                arr, rep
            )

        def assemble(leaf, shard_map):
            shape = tuple(leaf["shape"])
            if not to_device:
                full = np.zeros(shape, _dtype_from_name(leaf["dtype"]))
                for starts, arr in shard_map.items():
                    idx = tuple(
                        slice(st, st + n)
                        for st, n in zip(starts, arr.shape)
                    )
                    full[idx] = arr
                return full
            sh = NamedSharding(mesh, _spec_from_data(leaf["spec"]))
            bufs = []
            for dev, idx in sh.devices_indices_map(shape).items():
                starts = tuple(int(sl.start or 0) for sl in idx)
                arr = shard_map.get(starts)
                if arr is None:
                    raise SnapshotError(
                        f"snapshot leaf {leaf['key']!r} has no shard at "
                        f"offset {starts} for mesh placement"
                    )
                bufs.append(jax.device_put(arr, dev))
            return jax.make_array_from_single_device_arrays(
                shape, sh, bufs
            )

        while True:
            item = q.get()
            if item is None:
                break
            rec, arr = item
            t0 = time.perf_counter()
            if rec["leaf"] is None:
                leaves[rec["key"]] = place_flat(arr)
            else:
                acc = pending.setdefault(rec["key"], {})
                acc[tuple(rec["start"])] = arr
                if rec["last_shard"]:
                    leaves[rec["key"]] = assemble(rec["leaf"], acc)
                    del pending[rec["key"]]
            timing["transfer_s"] += time.perf_counter() - t0
    except BaseException:
        # Same reader-unwedging contract as _stream_native_params: a
        # consumer failure must not strand the reader on the bounded put.
        abort.set()
        while True:
            try:
                if q.get_nowait() is None:
                    break
            except _queue.Empty:
                if not rthread.is_alive():
                    break
                time.sleep(0.01)
        raise
    if reader_error:
        err = reader_error[0]
        if isinstance(err, SnapshotError):
            raise err
        raise SnapshotError(f"snapshot read failed: {err}") from err
    if stats is not None:
        stats.update(
            restore_s=round(time.perf_counter() - t_wall, 3),
            disk_s=round(timing["disk_s"], 3),
            transfer_s=round(timing["transfer_s"], 3),
            read_gib=round(timing["read_bytes"] / 2**30, 3),
        )
    return _unflatten(leaves), manifest
