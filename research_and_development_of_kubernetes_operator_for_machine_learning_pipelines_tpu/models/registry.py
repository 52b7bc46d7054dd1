"""Model-family registry: flavor name -> Predictor builder.

The server's loader resolves an MLflow artifact to a *flavor* (sklearn,
forest, bert, llama, resnet, pyfunc, ...) and asks this registry to build a
``Predictor`` — the one interface the data plane serves:

- ``predict``   — batched callable; a pure jittable JAX function for native
  flavors, a host-side Python callable for the pyfunc fallback tier;
- ``jittable``  — selects the engine path (jit+warmup vs host thread pool);
- ``example_input`` — builds a representative batch for warmup compilation
  so the first real request never pays the XLA compile (SURVEY §7 hard
  part 3, TPU cold-start).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable

import numpy as np


@dataclass
class Predictor:
    name: str
    predict: Callable[..., Any]
    jittable: bool = True
    example_input: Callable[[int], Any] | None = None  # batch_size -> inputs
    metadata: dict = field(default_factory=dict)
    # Causal-LM handles ({"params", "cfg", "eos_id"?, "family"?}) for
    # flavors that support autoregressive decoding: the server builds a
    # continuous-batching GenerationEngine from these and exposes
    # /generate.  ``family`` is the module the engine reaches the model
    # through (cache tuples, forward, decode_ragged...); absent: llama.
    causal_lm: dict | None = None
    # Declarative sequence bucketing (server/batching.apply_seq_pad):
    # collapses variable request lengths into power-of-two buckets so the
    # batcher can merge them and XLA compiles log-many shapes.  Only for
    # models whose padding is exact (masked attention, pooled outputs).
    seq_pad: dict | None = None
    # Weights as a jit ARGUMENT.  ``apply(params, *inputs)`` is
    # ``predict(*inputs)`` with the weight tree passed explicitly; the
    # engine jits THAT.  A jitted closure bakes every captured array
    # into the program as a constant: at Llama-2-7B width each batch
    # bucket then carries gigabytes of weights through the compiler (the
    # first chip run died at the host's 40 GiB), the executable cannot be
    # shared between two versions of one architecture, and the persistent
    # cache stores the weights once per bucket.  Flavors whose captured
    # state is small (tabular) leave both None.
    params: Any = None
    apply: Callable[..., Any] | None = None


_BUILDERS: dict[str, Callable[..., Predictor]] = {}


def register(flavor: str):
    def deco(fn: Callable[..., Predictor]):
        _BUILDERS[flavor] = fn
        return fn

    return deco


def get_builder(flavor: str) -> Callable[..., Predictor]:
    try:
        return _BUILDERS[flavor]
    except KeyError:
        raise KeyError(
            f"unknown model flavor {flavor!r}; registered: {sorted(_BUILDERS)}"
        ) from None


def list_flavors() -> list[str]:
    return sorted(_BUILDERS)


# ---------------------------------------------------------------------------
# Built-in flavors
# ---------------------------------------------------------------------------


@register("sklearn-linear")
def _build_sklearn_linear(model: Any, **_kw) -> Predictor:
    from . import linear

    params, cfg = linear.from_sklearn(model)
    n_feat = cfg.n_features

    def predict(x):
        return linear.predict(params, x, cfg)

    return Predictor(
        name="sklearn-linear",
        predict=predict,
        jittable=True,
        example_input=lambda b: np.zeros((b, n_feat), np.float32),
        metadata={"n_features": n_feat, "n_classes": cfg.n_classes},
    )


@register("sklearn-forest")
def _build_sklearn_forest(model: Any, **_kw) -> Predictor:
    from . import tabular

    trees = tabular.from_sklearn_forest(model)
    n_feat = int(model.n_features_in_)
    predict, form = tabular.lower_forest(trees)

    return Predictor(
        name="sklearn-forest",
        predict=predict,
        jittable=True,
        example_input=lambda b: np.zeros((b, n_feat), np.float32),
        metadata={"n_trees": int(trees.feature.shape[0]), "eval_form": form},
    )


@register("xgboost")
def _build_xgboost(model: Any, **_kw) -> Predictor:
    """``model`` is a parsed xgboost JSON dict (or a live Booster).

    Fully TPU-native (baseline config 1): the forest is lowered to the
    MXU matmul form when it fits the budget (tabular.GemmForest; ~11x
    the gather traversal on v5e), else to the flattened gather program
    shared with sklearn forests.  The objective picks the output
    transform: sigmoid for ``binary:*``, softmax/argmax over per-class
    margins for ``multi:*``, identity for regression.  Matches xgboost's
    ``predict`` output shapes: probabilities [B, K] for softprob, class
    ids [B] for softmax.
    """
    from . import tabular

    if isinstance(model, (dict, str, bytes)):
        trees, objective = tabular.from_xgboost_json(model)
    else:
        trees, objective = tabular.from_xgboost(model)
    margins, form = tabular.lower_forest(trees)

    if objective.startswith("binary:"):
        def predict(x):
            import jax

            return jax.nn.sigmoid(margins(x))
    elif objective == "multi:softprob":
        def predict(x):
            import jax

            return jax.nn.softmax(margins(x), axis=-1)
    elif objective == "multi:softmax":
        def predict(x):
            import jax.numpy as jnp

            return jnp.argmax(margins(x), axis=-1).astype(jnp.float32)
    else:
        predict = margins

    n_feat = trees.n_features or int(trees.feature.max()) + 1
    return Predictor(
        name="xgboost",
        predict=predict,
        jittable=True,
        example_input=lambda b: np.zeros((b, n_feat), np.float32),
        metadata={
            "n_trees": int(trees.feature.shape[0]),
            "n_features": n_feat,
            "objective": objective,
            "n_classes": trees.n_groups,
            "eval_form": form,
        },
    )


@register("pyfunc")
def _build_pyfunc(model: Any, **_kw) -> Predictor:
    from .tabular import PyFuncPredictor

    wrapped = model if isinstance(model, PyFuncPredictor) else PyFuncPredictor(
        model.predict if hasattr(model, "predict") else model
    )
    return Predictor(name="pyfunc", predict=wrapped, jittable=False)


@register("bert-classifier")
def _build_bert(
    params: Any,
    cfg: Any = None,
    seq_len: int = 128,
    seq_buckets: bool = True,
    **_kw,
) -> Predictor:
    from . import bert

    cfg = cfg or bert.BertConfig.base()

    def apply(params, input_ids, attention_mask=None, token_type_ids=None):
        import jax.numpy as jnp

        return bert.classify(
            params,
            input_ids,
            attention_mask,
            token_type_ids,
            cfg=cfg,
            dtype=jnp.bfloat16,
        )

    predict = functools.partial(apply, params)

    def example(b):
        return {
            "input_ids": np.ones((b, seq_len), np.int32),
            "attention_mask": np.ones((b, seq_len), np.int32),
        }

    return Predictor(
        name="bert-classifier",
        predict=predict,
        params=params,
        apply=apply,
        jittable=True,
        example_input=example,
        metadata={
            "seq_len": seq_len,
            "num_labels": cfg.num_labels,
            "hidden_act": cfg.hidden_act,
        },
        # Padding is exact for classification: the attention mask (0 on
        # padded keys) removes them from every softmax, and the CLS
        # pooling position is unaffected.  A request without a mask gets
        # one synthesized BEFORE padding, or the padded ids would be
        # attended.
        # seq_buckets=False pins the model to fixed-length traffic (no
        # length ladder warmed or served) — for controlled benches and
        # pipelines that always send one length.
        seq_pad=None
        if not seq_buckets
        else {
            "axis": 1,
            "pad_values": {
                "input_ids": 0,
                "attention_mask": 0,
                "token_type_ids": 0,
            },
            "synthesize": {"attention_mask": 1},
            "min_bucket": 16,
            "max_len": cfg.max_position_embeddings,
        },
    )


@register("resnet-classifier")
def _build_resnet(params: Any, cfg: Any = None, image_size: int = 224, **_kw) -> Predictor:
    from . import resnet

    cfg = cfg or resnet.ResNetConfig.resnet50()

    def apply(params, images):
        return resnet.forward(params, images, cfg)

    return Predictor(
        name="resnet-classifier",
        predict=functools.partial(apply, params),
        params=params,
        apply=apply,
        jittable=True,
        example_input=lambda b: np.zeros((b, image_size, image_size, 3), np.float32),
        metadata={"image_size": image_size, "num_classes": cfg.num_classes},
    )


def _causal_lm_predictor(
    module, name: str, params: Any, cfg: Any, max_new_tokens: int,
    eos_id: int | None, **handle,
) -> Predictor:
    """A causal-LM family's Predictor: ``module.generate_greedy`` behind
    the batch predict path, and the handles the generation engine takes
    (``handle``: what the family adds to them)."""
    # The batch predict path pairs a fixed example prompt length with a
    # fixed generation budget; both must fit the KV-cache capacity.
    example_len = min(16, cfg.max_seq // 4)
    max_new_tokens = min(max_new_tokens, cfg.max_seq - example_len)

    def apply(params, prompt_ids):
        return module.generate_greedy(params, prompt_ids, max_new_tokens, cfg)

    return Predictor(
        name=name,
        predict=functools.partial(apply, params),
        params=params,
        apply=apply,
        jittable=True,
        example_input=lambda b: np.ones((b, example_len), np.int32),
        metadata={"max_new_tokens": max_new_tokens, "max_seq": cfg.max_seq},
        causal_lm={"params": params, "cfg": cfg, "eos_id": eos_id, **handle},
    )


@register("llama-generate")
def _build_llama(
    params: Any,
    cfg: Any,
    max_new_tokens: int = 64,
    eos_id: int | None = None,
    **_kw,
) -> Predictor:
    from . import llama

    return _causal_lm_predictor(
        llama, "llama-generate", params, cfg, max_new_tokens, eos_id
    )


@register("mla-moe-generate")
def _build_mla_moe(
    params: Any,
    cfg: Any,
    max_new_tokens: int = 64,
    eos_id: int | None = None,
    **_kw,
) -> Predictor:
    from . import mla_moe

    return _causal_lm_predictor(
        mla_moe, mla_moe.FLAVOR, params, cfg, max_new_tokens, eos_id,
        family=mla_moe,
    )


@register("gdn-moe-generate")
def _build_gdn_moe(
    params: Any,
    cfg: Any,
    max_new_tokens: int = 64,
    eos_id: int | None = None,
    **_kw,
) -> Predictor:
    from . import gdn_moe

    return _causal_lm_predictor(
        gdn_moe, gdn_moe.FLAVOR, params, cfg, max_new_tokens, eos_id,
        family=gdn_moe,
    )
