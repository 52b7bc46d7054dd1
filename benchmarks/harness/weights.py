"""Seeded weights: the artifact the server loads and the reference's own
copy come from this one generator, a pure function of (seed, leaf, layer):
N(0, 0.02) as both published configs initialise, in bfloat16.

numpy + ml_dtypes only, so the process that writes the artifact never
touches jax (one process per chip)."""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

STD = 0.02  # initializer_range of both published configs
LAYER_MATS = ("q", "k", "v", "o", "gate", "up", "down")


def geometry(model: dict) -> dict:
    """The program's artifact config from the published config's keys."""
    return {
        "vocab_size": int(model["vocab_size"]),
        "hidden_size": int(model["hidden_size"]),
        "num_layers": int(model["num_hidden_layers"]),
        "num_heads": int(model["num_attention_heads"]),
        "num_kv_heads": int(model["num_key_value_heads"]),
        "intermediate_size": int(model["intermediate_size"]),
        "max_seq": int(model["max_position_embeddings"]),
        "rope_theta": float(model["rope_theta"]),
        "rms_eps": float(model["rms_norm_eps"]),
    }


def mat_shapes(g: dict) -> dict[str, tuple[int, int]]:
    h, i = g["hidden_size"], g["intermediate_size"]
    hd = h // g["num_heads"]
    kvd, qd = hd * g["num_kv_heads"], hd * g["num_heads"]
    return {
        "q": (h, qd), "k": (h, kvd), "v": (h, kvd), "o": (qd, h),
        "gate": (h, i), "up": (h, i), "down": (i, h),
    }


_NAMES = ("embed", "lm_head") + LAYER_MATS
CHUNK = 1 << 22  # elements of one independent random stream
_STEP = 1 << 18  # numpy calls long enough to run beside each other (the
#                  GIL), temporaries small enough to be reused, not mapped anew

_TABLE: np.ndarray | None = None


def normal_table() -> np.ndarray:
    """The 65536 quantiles of N(0, STD) at (i + 0.5) / 65536, rounded to
    bfloat16 (as bit patterns: numpy moves uint16 fast).  A weight is one
    of them picked by 16 random bits: bfloat16 has no more values than
    that, and picking is several times faster than float32 normals, which
    every run pays twice (artifact and reference)."""
    global _TABLE
    if _TABLE is None:
        import ml_dtypes
        from statistics import NormalDist

        inv = NormalDist(0.0, STD).inv_cdf
        _TABLE = np.array(
            [inv((i + 0.5) / 65536.0) for i in range(65536)], np.float32
        ).astype(ml_dtypes.bfloat16).view(np.uint16)
    return _TABLE


def leaf_shape(g: dict, name: str) -> tuple[int, int]:
    if name == "embed":
        return (g["vocab_size"], g["hidden_size"])
    if name == "lm_head":
        return (g["hidden_size"], g["vocab_size"])
    return mat_shapes(g)[name]


def _fill(flat: np.ndarray, seed: int, name: str, layer: int | None, c: int) -> None:
    table = normal_table()
    rng = np.random.default_rng(
        [int(seed), _NAMES.index(name), 0 if layer is None else layer + 1, c])
    for i in range(0, flat.size, _STEP):
        n = min(_STEP, flat.size - i)
        flat[i:i + n] = table[rng.integers(0, 65536, n, dtype=np.uint16)]


def fill_chunk(out: np.ndarray, seed: int, name: str, layer: int | None,
               c: int) -> None:
    """Chunk `c` of one (leaf, layer): a stream of its own, so threads
    may fill chunks in any order and the tree is still a function of the
    seed."""
    _fill(out.reshape(-1).view(np.uint16)[c * CHUNK:(c + 1) * CHUNK],
          seed, name, layer, c)


def fill_jobs(out: np.ndarray, seed: int, name: str, layer: int | None) -> list:
    """The argument tuples of `fill_chunk` that fill `out`."""
    return [(out, seed, name, layer, c) for c in range(-(-out.size // CHUNK))]


def threads() -> int:
    return min(12, os.cpu_count() or 1)


def stream_npz(path: str, seed: int, g: dict, sep: str) -> None:
    """The whole bf16 tree in the program's stacked layout (norms are 1),
    written as numpy's own `.npz` (stored, not compressed) without ever
    holding it: threads fill a few reused chunk buffers ahead of one writer.  A 7B tree is 13.5 GiB, and
    in this sandbox touching that much fresh memory costs more than
    making and writing the numbers."""
    import queue
    import zipfile

    import ml_dtypes

    bf16 = np.dtype(ml_dtypes.bfloat16)
    L, H = g["num_layers"], g["hidden_size"]
    ones = {"final_norm": (H,), f"layers{sep}attn_norm": (L, H),
            f"layers{sep}mlp_norm": (L, H)}
    mats = [("embed", "embed", None, leaf_shape(g, "embed")),
            ("lm_head", "lm_head", None, leaf_shape(g, "lm_head"))]
    mats += [(f"layers{sep}{k}", k, L, s) for k, s in mat_shapes(g).items()]
    normal_table()
    n_threads = threads()
    pool: queue.Queue = queue.Queue()
    for _ in range(2 * n_threads + 2):
        pool.put(np.empty(CHUNK, np.uint16))

    def job(name, layer, c, n):
        buf = pool.get()
        _fill(buf[:n], seed, name, layer, c)
        return buf, n

    def header(fp, shape):
        np.lib.format.write_array_header_1_0(fp, {
            "descr": np.lib.format.dtype_to_descr(bf16),
            "fortran_order": False, "shape": tuple(shape)})

    with zipfile.ZipFile(path, "w", zipfile.ZIP_STORED, allowZip64=True) as zf, \
            ThreadPoolExecutor(max_workers=n_threads) as ex:
        for key, shape in ones.items():
            with zf.open(key + ".npy", "w", force_zip64=True) as fp:
                header(fp, shape)
                fp.write(np.ones(shape, bf16).tobytes())
        for key, name, layers, shape in mats:
            size = shape[0] * shape[1]
            todo = [(name, l, c, min(CHUNK, size - c * CHUNK))
                    for l in ([None] if layers is None else range(layers))
                    for c in range(-(-size // CHUNK))]
            full = shape if layers is None else (layers, *shape)
            with zf.open(key + ".npy", "w", force_zip64=True) as fp:
                header(fp, full)
                ahead: list = []
                it = iter(todo)
                # Never more jobs in flight than buffers, or the pool deadlocks.
                for _ in range(n_threads + 1):
                    nxt = next(it, None)
                    if nxt is not None:
                        ahead.append(ex.submit(job, *nxt))
                while ahead:
                    buf, n = ahead.pop(0).result()
                    fp.write(memoryview(buf[:n]).cast("B"))
                    pool.put(buf)
                    nxt = next(it, None)
                    if nxt is not None:
                        ahead.append(ex.submit(job, *nxt))


def write_artifact(path: str, model: dict, seed: int) -> None:
    """Child mode of run.py: the seeded artifact in the program's own
    layout, through the program's own writer."""
    from pathlib import Path

    from tpumlops.server import loader

    g = geometry(model)
    # The program's writer for the metadata (config.json, MLmodel) and the
    # key scheme; the arrays themselves are streamed in numpy's format.
    loader.save_native_model(path, "llama-generate", {}, config=g)
    stream_npz(str(Path(path) / "params.npz"), seed, g, loader._SEP)
