"""TPU-native ML deployment framework.

A ground-up rebuild of the capabilities of the reference MLflow->Seldon
Kubernetes operator (see SURVEY.md), designed TPU-first:

- ``operator``  -- the control plane: a level-triggered reconciler that watches
  ``MlflowModel`` custom resources, resolves MLflow registry aliases to model
  versions, and runs metric-gated canary rollouts with resumable promotion
  state and rollback-on-SLO-breach.  (Reference behavior:
  ``mlflow_operator.py:26-361``; rebuilt as a state machine, not a poll loop.)
- ``server``    -- the data plane the reference outsourced to Seldon's
  ``MLFLOW_SERVER`` image: a first-party JAX/XLA inference server that
  jit/pjit-compiles model predict functions and serves the V2 (kfserving)
  protocol from TPU node pools, exporting Seldon-compatible Prometheus
  metrics.
- ``models``    -- the model zoo backing the baseline configs: linear/iris,
  tabular, ResNet-50, BERT-base, Llama-2 (tensor-parallel over v5e-8).
- ``ops``       -- ring attention over the ``sp`` mesh axis and the
  sparse-expert grouped matmul (Pallas on the TPU, XLA off it).
- ``parallel``  -- device meshes, sharding rules, collectives, multi-host
  initialization.
- ``clients``   -- protocol interfaces + real REST clients + in-memory fakes
  for Kubernetes, the MLflow registry, and Prometheus.

Import as::

    import research_and_development_of_kubernetes_operator_for_machine_learning_pipelines_tpu as rdko
    # or the short alias
    import tpumlops
"""

__version__ = "0.1.0"

# Subpackages are imported lazily so that the pure control-plane core can be
# used without pulling in jax (and vice versa).
_SUBPACKAGES = (
    "operator",
    "clients",
    "server",
    "models",
    "ops",
    "parallel",
    "utils",
)


def __getattr__(name):
    if name in _SUBPACKAGES:
        import importlib

        mod = importlib.import_module(f".{name}", __name__)
        globals()[name] = mod
        return mod
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + list(_SUBPACKAGES))
