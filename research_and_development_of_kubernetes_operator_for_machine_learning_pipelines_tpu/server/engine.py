"""Inference engine: jit compilation, warmup, and dispatch for a Predictor.

TPU cold-start is the canary killer (SURVEY §7 hard part 3): the first
request on a fresh predictor would otherwise pay tens of seconds of XLA
compile and instantly fail the latency gate.  The engine therefore:

- jits jittable predictors once per input-shape signature;
- *warms up* every batch bucket (1, 2, 4, ... max_batch) at startup using
  the flavor's ``example_input`` builder, so steady-state traffic only ever
  hits cached executables;
- honors ``JAX_COMPILATION_CACHE_DIR`` (set by the manifest builder) so
  even process restarts skip recompiles.

Non-jittable (pyfunc) predictors dispatch to the host callable directly —
same interface, same metrics, different tier.
"""

from __future__ import annotations

import functools
import logging
import threading
import time
from typing import Any, Callable, Mapping

import numpy as np

from ..models.registry import Predictor

_log = logging.getLogger(__name__)


def warmup_buckets(max_batch_size: int) -> list[int]:
    """Batch buckets to pre-compile: powers of two up to the cap, plus the
    cap itself when it isn't one (``next_bucket()`` clamps there, so a
    non-power-of-two cap is a servable bucket and must be warmed too)."""
    buckets = []
    b = 1
    while b <= max_batch_size:
        buckets.append(b)
        b <<= 1
    if buckets[-1] != max_batch_size:
        buckets.append(max_batch_size)
    return buckets


class InferenceEngine:
    def __init__(
        self,
        predictor: Predictor,
        max_batch_size: int = 32,
        on_compile: Callable[[], None] | None = None,
        warmup_full_grid: bool = False,
    ):
        self.predictor = predictor
        self.max_batch_size = int(max_batch_size)
        # Latency-sensitive deployments (CRD spec.tpu.warmupFullGrid) warm
        # the full batch x length grid: with a cold persistent compile
        # cache, an interior bucket (e.g. batch 4 at a non-base length)
        # otherwise pays its XLA compile on first live traffic.
        self.warmup_full_grid = bool(warmup_full_grid)
        self._on_compile = on_compile
        self._seen_signatures: set[tuple] = set()
        self._lock = threading.Lock()
        if predictor.jittable:
            import jax

            if predictor.apply is not None:
                # Weights ride as a jit ARGUMENT (see Predictor.apply):
                # never captured into the program as constants.
                apply_jit = jax.jit(
                    lambda params, inputs: self._call(
                        functools.partial(self.predictor.apply, params),
                        inputs,
                    )
                )
                self._jitted = lambda inputs: apply_jit(
                    self.predictor.params, inputs
                )
            else:
                self._jitted = jax.jit(self._call_predict)
        else:
            self._jitted = None

    # -- calling conventions -------------------------------------------------

    @staticmethod
    def _call(fn, inputs: Mapping[str, Any]):
        """Single input -> positional call; several -> keyword call."""
        if len(inputs) == 1:
            (value,) = inputs.values()
            return fn(value)
        return fn(**inputs)

    def _call_predict(self, inputs: Mapping[str, Any]):
        return self._call(self.predictor.predict, inputs)

    @staticmethod
    def _signature(inputs: Mapping[str, np.ndarray]) -> tuple:
        return tuple(sorted((k, v.shape, str(v.dtype)) for k, v in inputs.items()))

    # -- public API ----------------------------------------------------------

    @property
    def wants_warmup(self) -> bool:
        """True when warmup would actually compile something (jittable
        predictor with an example-input builder)."""
        return self._jitted is not None and self.predictor.example_input is not None

    def predict(self, inputs: Mapping[str, np.ndarray]) -> Any:
        """Run one already-batched input dict; returns numpy outputs."""
        return self.materialize(self.predict_async(inputs))

    def predict_async(self, inputs: Mapping[str, np.ndarray]) -> Any:
        """Dispatch one already-batched input dict WITHOUT materializing.

        Under ``jit``, XLA dispatch is asynchronous: the returned device
        arrays are promises, so the caller can overlap forming/dispatching
        the NEXT batch with this one's device execution (the
        ``DynamicBatcher``'s pipelined mode).  Pair with
        :meth:`materialize`, which blocks until the device is done.  On
        the non-jittable (pyfunc) tier the call runs synchronously here —
        ``materialize`` is then a cheap identity walk.
        """
        sig = self._signature(inputs)
        with self._lock:
            new_sig = sig not in self._seen_signatures
            if new_sig:
                self._seen_signatures.add(sig)
        if new_sig:
            if self._on_compile:
                self._on_compile()
            _log.info("new input signature %s (compiling)", sig)
        if self._jitted is not None:
            return self._jitted(dict(inputs))
        return self._call_predict(inputs)

    def materialize(self, out: Any) -> Any:
        """Block until ``out``'s device computation finishes; numpy it."""
        return _to_numpy(out)

    def warmup(
        self,
        buckets: list[int] | None = None,
        predict: Callable[[Mapping[str, np.ndarray]], Any] | None = None,
    ) -> float:
        """Compile every batch bucket ahead of traffic; returns seconds spent.

        ``predict`` overrides the dispatch path (the multi-host wrapper
        passes its broadcasting predict so followers warm the same buckets)
        while bucket policy and example building stay in this one place."""
        if not self.wants_warmup:
            return 0.0
        if buckets is None:
            buckets = warmup_buckets(self.max_batch_size)
        predict = predict or self.predict
        t0 = time.perf_counter()
        n_shapes = 0
        for b in buckets:
            ex = self.predictor.example_input(b)
            if not isinstance(ex, Mapping):
                ex = {"x": ex}
            predict(ex)
            n_shapes += 1
        # Sequence-bucketed predictors: also warm the LENGTH buckets at
        # the batch-grid edges (batch 1 and max).  The full batch x length
        # grid would be |buckets|^2 cold compiles; the edges cover lone
        # requests and saturated batches, and the persistent compile
        # cache fills the interior once, fleet-wide.  warmup_full_grid
        # opts into the whole grid for deployments that cannot afford a
        # single cold-cache first-hit compile stall.
        seq_pad = getattr(self.predictor, "seq_pad", None)
        if seq_pad:
            axis = int(seq_pad.get("axis", 1))
            max_len = int(seq_pad.get("max_len") or 0)
            example = self.predictor.example_input(1)
            pad_names = [
                k
                for k in (seq_pad.get("pad_values") or {})
                if isinstance(example, Mapping) and k in example
            ]
            if pad_names and max_len:

                def at_length(b: int, length: int) -> dict:
                    ex = self.predictor.example_input(b)
                    idx = np.zeros(length, np.intp)  # repeat position 0
                    return {
                        k: (np.take(v, idx, axis=axis) if k in pad_names else v)
                        for k, v in ex.items()
                    }

                from .batching import seq_buckets

                base_len = example[pad_names[0]].shape[axis]
                grid_batches = (
                    buckets if self.warmup_full_grid else (1, self.max_batch_size)
                )
                for length in seq_buckets(seq_pad):
                    if length == base_len:
                        continue  # base length covered above
                    for b in grid_batches:
                        predict(at_length(b, length))
                        n_shapes += 1
        dt = time.perf_counter() - t0
        _log.info("warmup compiled %d shapes in %.1fs", n_shapes, dt)
        return dt


def _to_numpy(out: Any) -> Any:
    if isinstance(out, (tuple, list)):
        return tuple(_to_numpy(o) for o in out)
    if isinstance(out, dict):
        return {k: _to_numpy(v) for k, v in out.items()}
    return np.asarray(out)
