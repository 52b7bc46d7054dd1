"""Aux subsystems: tracing spans, orbax checkpoint round-trip, manifests."""

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import yaml

import tpumlops
from tpumlops.utils import checkpoint
from tpumlops.utils.tracing import Tracer

PKG_DIR = Path(tpumlops.__file__).parent


def test_tracer_records_spans():
    tr = Tracer()
    with tr.span("reconcile"):
        pass
    with tr.span("reconcile"):
        pass
    with tr.span("gate"):
        pass
    stats = tr.stats()
    assert stats["reconcile"].count == 2
    assert stats["gate"].count == 1
    assert stats["reconcile"].total_s >= stats["reconcile"].max_s > 0.0


def test_tracer_stats_is_a_snapshot_not_a_live_view():
    """``stats()`` hands out copies: a caller holding one must never
    see it move (or read a torn record: count bumped, total_s not yet)."""
    tr = Tracer()
    with tr.span("x"):
        pass
    snap = tr.stats()["x"]
    count0, total0 = snap.count, snap.total_s
    with tr.span("x"):
        pass
    assert snap.count == count0
    assert snap.total_s == total0
    assert tr.stats()["x"].count == count0 + 1


def test_tracer_as_dict_is_json_ready():
    import json

    tr = Tracer()
    with tr.span("gate"):
        pass
    d = json.loads(json.dumps(tr.as_dict()))
    assert d["gate"]["count"] == 1
    assert set(d["gate"]) == {"count", "total_s", "self_s", "mean_ms", "max_ms"}


def test_json_log_format_carries_request_id():
    import io
    import json
    import logging

    from tpumlops.utils.logging import JsonFormatter

    stream = io.StringIO()
    handler = logging.StreamHandler(stream)
    handler.setFormatter(JsonFormatter())
    log = logging.getLogger("tpumlops.test.jsonfmt")
    log.addHandler(handler)
    log.setLevel(logging.INFO)
    log.propagate = False
    try:
        log.info("generate done tokens=%d", 7, extra={"request_id": "rid-9"})
        log.warning("no id attached")
    finally:
        log.removeHandler(handler)
    lines = [json.loads(ln) for ln in stream.getvalue().splitlines()]
    assert lines[0]["message"] == "generate done tokens=7"
    assert lines[0]["request_id"] == "rid-9"
    assert lines[0]["level"] == "INFO"
    assert lines[0]["logger"] == "tpumlops.test.jsonfmt"
    assert "request_id" not in lines[1]


def test_operator_metrics_listener_serves_debug_spans():
    """The operator's --metrics-port listener serves /metrics AND
    /debug/spans (the GLOBAL_TRACER stats, same shape as the server)."""
    import json
    import urllib.request

    from tpumlops.operator.telemetry import OperatorTelemetry
    from tpumlops.utils.tracing import GLOBAL_TRACER

    telemetry = OperatorTelemetry()
    telemetry.set_resource_count(3)
    httpd = telemetry.serve(0, addr="127.0.0.1")  # port 0: OS-assigned
    port = httpd.server_address[1]
    try:
        with GLOBAL_TRACER.span("operator-listener-probe"):
            pass
        base = f"http://127.0.0.1:{port}"
        metrics = urllib.request.urlopen(base + "/metrics", timeout=5).read()
        assert b"tpumlops_operator_resources 3.0" in metrics
        spans = json.loads(
            urllib.request.urlopen(base + "/debug/spans", timeout=5).read()
        )["spans"]
        assert spans["operator-listener-probe"]["count"] >= 1
        assert spans["operator-listener-probe"]["self_s"] >= 0.0
        try:
            urllib.request.urlopen(base + "/nope", timeout=5)
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        httpd.shutdown()


def test_checkpoint_roundtrip(tmp_path):
    tree = {
        "layer": {"w": jnp.arange(12.0).reshape(3, 4), "b": jnp.ones((4,))},
        "step": jnp.asarray(7),
    }
    checkpoint.save(tmp_path / "ckpt", tree)
    restored = checkpoint.restore(tmp_path / "ckpt")
    np.testing.assert_array_equal(restored["layer"]["w"], tree["layer"]["w"])
    np.testing.assert_array_equal(restored["step"], tree["step"])


def test_checkpoint_restore_with_sharding_template(tmp_path):
    from jax.sharding import NamedSharding, PartitionSpec

    from tpumlops.parallel import build_mesh

    tree = {"w": jnp.arange(32.0).reshape(8, 4)}
    checkpoint.save(tmp_path / "ckpt", tree)
    mesh = build_mesh({"tp": 8})
    template = {
        "w": jax.ShapeDtypeStruct(
            (8, 4), jnp.float32, sharding=NamedSharding(mesh, PartitionSpec("tp", None))
        )
    }
    restored = checkpoint.restore(tmp_path / "ckpt", template)
    assert restored["w"].sharding.spec == PartitionSpec("tp", None)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(tree["w"]))


def test_checkpoint_manager_versioned_save_restore_and_gc(tmp_path):
    mgr = checkpoint.CheckpointManager(tmp_path / "ckpts", max_to_keep=2)
    assert mgr.latest_step() is None
    for step in (1, 2, 3):
        mgr.save(step, {"w": jnp.full((4,), float(step))},
                 tags={"version": f"v{step}"})
    # keep-N GC: step 1 is gone, 2 and 3 remain.
    assert mgr.steps() == [2, 3]
    assert mgr.latest_step() == 3
    np.testing.assert_array_equal(
        mgr.restore()["w"], jnp.full((4,), 3.0)
    )
    np.testing.assert_array_equal(
        mgr.restore(step=2)["w"], jnp.full((4,), 2.0)
    )
    assert mgr.metadata(3)["tags"] == {"version": "v3"}
    # monotonic-step guard: silent clobbering refused.
    import pytest

    with pytest.raises(FileExistsError):
        mgr.save(3, {"w": jnp.zeros((4,))})
    mgr.save(3, {"w": jnp.full((4,), 30.0)}, overwrite=True)
    np.testing.assert_array_equal(mgr.restore()["w"], jnp.full((4,), 30.0))


def test_checkpoint_manager_torn_save_is_invisible(tmp_path):
    """A crash mid-save must never surface as a restorable step: only
    directories carrying the COMMITTED marker are listed."""
    mgr = checkpoint.CheckpointManager(tmp_path / "ckpts", max_to_keep=None)
    mgr.save(1, {"w": jnp.ones((2,))})
    # Simulate a torn save: step dir exists, marker absent.
    torn = mgr._step_dir(2)
    torn.mkdir(parents=True)
    (torn / "params").mkdir()
    assert mgr.steps() == [1]
    assert mgr.latest_step() == 1
    import pytest

    with pytest.raises(FileNotFoundError):
        mgr.restore(step=2)
    # The next save of step 2 clears the wreckage and commits cleanly.
    mgr.save(2, {"w": jnp.full((2,), 2.0)})
    assert mgr.steps() == [1, 2]


def test_checkpoint_manager_async_save(tmp_path):
    mgr = checkpoint.CheckpointManager(tmp_path / "ckpts")
    handle = mgr.save_async(5, {"w": jnp.arange(8.0)}, tags={"async": True})
    handle.wait(timeout=60)
    assert handle.done()
    assert mgr.latest_step() == 5
    np.testing.assert_array_equal(mgr.restore()["w"], jnp.arange(8.0))
    # Failure surfaces through wait(), not silently.
    bad = mgr.save_async(5, {"w": jnp.zeros(1)})  # step exists
    import pytest

    with pytest.raises(FileExistsError):
        bad.wait(timeout=60)


def test_manifests_are_valid_yaml_with_expected_fields():
    crd = list(yaml.safe_load_all((PKG_DIR / "deploy" / "crd.yaml").read_text()))[0]
    assert crd["spec"]["group"] == "mlflow.nizepart.com"
    assert crd["spec"]["names"]["shortNames"] == ["mlflowm"]
    version = crd["spec"]["versions"][0]
    spec_props = version["schema"]["openAPIV3Schema"]["properties"]["spec"]["properties"]
    # Reference spec fields (crd.yaml:17-25) ...
    for f in ("modelName", "modelAlias", "monitoringInterval", "minioSecret"):
        assert f in spec_props, f
    # ... plus the north-star TPU additions.
    assert spec_props["backend"]["enum"] == ["seldon", "tpu"]
    assert "tpuTopology" in spec_props["tpu"]["properties"]
    assert "meshShape" in spec_props["tpu"]["properties"]
    status_props = version["schema"]["openAPIV3Schema"]["properties"]["status"]["properties"]
    for f in ("currentModelVersion", "previousModelVersion", "error",
              "phase", "trafficCurrent", "heldVersion"):
        assert f in status_props, f
    assert version["subresources"] == {"status": {}}

    rbac_docs = list(yaml.safe_load_all((PKG_DIR / "deploy" / "rbac.yaml").read_text()))
    kinds = [d["kind"] for d in rbac_docs]
    assert kinds == ["ServiceAccount", "ClusterRole", "ClusterRoleBinding"]
    rules = rbac_docs[1]["rules"]
    resources = {r for rule in rules for r in rule["resources"]}
    assert {"mlflowmodels", "mlflowmodels/status", "seldondeployments",
            "events", "secrets", "nodes"} <= resources

    dep = list(yaml.safe_load_all(
        (PKG_DIR / "deploy" / "operator-deployment.yaml").read_text()
    ))[0]
    container = dep["spec"]["template"]["spec"]["containers"][0]
    assert container["envFrom"][0]["secretRef"]["name"] == "mlflow-creds"


# ---------------------------------------------------------------------------
# Persistent XLA compilation cache (SURVEY §7 hard part 3)
# ---------------------------------------------------------------------------


def test_compile_cache_persists_small_executables(tmp_path, monkeypatch):
    from tpumlops.utils.compile_cache import (
        cache_entry_count,
        enable_persistent_compile_cache,
    )

    d = str(tmp_path / "xla")
    assert enable_persistent_compile_cache(d)
    try:
        # Canary-sized computation: compiles in far under JAX's default 1 s
        # persistence floor — persisted anyway because we zero the floors.
        f = jax.jit(lambda x: jnp.tanh(x @ x.T).sum())
        f(jnp.ones((16, 16), jnp.float32)).block_until_ready()
        assert cache_entry_count(d) >= 1
    finally:
        jax.config.update("jax_compilation_cache_dir", None)


def test_compile_cache_disabled_or_unwritable_is_nonfatal(tmp_path):
    from tpumlops.utils.compile_cache import enable_persistent_compile_cache

    assert enable_persistent_compile_cache(None) is False
    assert enable_persistent_compile_cache("") is False
    blocked = tmp_path / "blocked"
    blocked.write_text("a file, not a dir")
    assert enable_persistent_compile_cache(str(blocked)) is False


def test_tpu_pod_mounts_node_local_compile_cache():
    from tests.test_builder import cfg, two_version_manifest

    config = cfg(
        backend="tpu", tpu={"tpuTopology": "v5e-8", "meshShape": {"dp": 1, "tp": 8}}
    )
    sd = two_version_manifest(config)
    pod = sd["spec"]["predictors"][1]["componentSpecs"][0]["spec"]
    container = pod["containers"][0]
    args = " ".join(container["args"])
    assert "--compile-cache-dir /tmp/jax_compile_cache" in args
    (mount,) = container["volumeMounts"]
    assert mount["mountPath"] == "/tmp/jax_compile_cache"
    (vol,) = pod["volumes"]
    assert vol["name"] == mount["name"] == "xla-cache"
    # hostPath so the cache outlives the pod (canary reschedule = warm start).
    assert vol["hostPath"]["type"] == "DirectoryOrCreate"


def test_operator_entrypoint_help():
    """``python -m tpumlops.operator`` must run through the short alias
    (runpy needs a get_code-capable loader for __main__ submodules)."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-m", "tpumlops.operator", "--help"],
        capture_output=True,
        text=True,
        timeout=60,
        cwd=str(PKG_DIR.parent),
    )
    assert out.returncode == 0, out.stderr
    assert "--metrics-port" in out.stdout


def test_example_crs_parse_through_operator_config():
    """The shipped example CRs must round-trip through the real spec parser
    (a drifting example is worse than none)."""
    from tpumlops.utils.config import OperatorConfig

    for name in ("iris-seldon.yaml", "llama-tpu.yaml"):
        doc = yaml.safe_load((PKG_DIR / "deploy" / "examples" / name).read_text())
        cfg = OperatorConfig.from_spec(doc["spec"])
        assert cfg.model_name
    # The long-context example: sp mesh + threshold must land (and pass
    # the reconcile-time sp/prefillChunk/chip checks).
    lc = OperatorConfig.from_spec(yaml.safe_load(
        (PKG_DIR / "deploy" / "examples" / "llama-longcontext.yaml")
        .read_text()
    )["spec"])
    assert lc.tpu.mesh_shape == {"sp": 4, "tp": 4}
    assert lc.tpu.sp_prefill_threshold == 8192
    # Field names must really land (unknown keys silently default!).
    assert cfg.backend == "tpu"
    assert cfg.tpu.quantize == "int8kv"
    assert cfg.tpu.prefill_chunk == 256
    assert cfg.tpu.mesh_shape == {"dp": 1, "tp": 8}
    assert cfg.thresholds.min_sample_count == 50
    assert cfg.thresholds.error_rate_floor == 0.005
    assert cfg.canary.rollback_on_failure is True
    assert cfg.canary.warmup_requests == 20
    assert cfg.canary.attempt_delay_s == 10


def test_checkpoint_manager_overwrite_crash_keeps_predecessor(tmp_path, monkeypatch):
    """overwrite=True must not destroy the committed predecessor before
    the replacement's data is on disk: a crash during the (potentially
    multi-minute) orbax write would otherwise lose BOTH versions of the
    step — the durability story the COMMITTED marker exists to provide."""
    import pytest

    mgr = checkpoint.CheckpointManager(tmp_path / "ckpts", max_to_keep=None)
    mgr.save(3, {"w": jnp.full((4,), 3.0)})

    def boom(path, tree):
        raise RuntimeError("simulated crash mid-save")

    monkeypatch.setattr(checkpoint, "save", boom)
    with pytest.raises(RuntimeError, match="simulated crash"):
        mgr.save(3, {"w": jnp.full((4,), 99.0)}, overwrite=True)
    monkeypatch.undo()

    # The predecessor is still committed and restorable, bit-for-bit.
    assert mgr.steps() == [3]
    restored = mgr.restore(step=3)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.full((4,), 3.0))

    # And a successful overwrite replaces it cleanly afterwards.
    mgr.save(3, {"w": jnp.full((4,), 7.0)}, overwrite=True)
    restored = mgr.restore(step=3)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.full((4,), 7.0))
    assert not list((tmp_path / "ckpts").glob(".replaced_*"))


def test_checkpoint_manager_interrupted_swap_recovers_predecessor(tmp_path, monkeypatch):
    """Crash BETWEEN renaming the predecessor away and committing its
    replacement leaves the only committed copy under .replaced_*.  A
    retried save must restore it before attempting the new write — and a
    second failure must still leave the step restorable."""
    import pytest

    mgr = checkpoint.CheckpointManager(tmp_path / "ckpts", max_to_keep=None)
    mgr.save(5, {"w": jnp.full((3,), 5.0)})

    # Simulate the crash window: predecessor renamed away, replacement
    # data present but never committed.
    final = mgr._step_dir(5)
    final.rename(tmp_path / "ckpts" / ".replaced_step_00000005")
    final.mkdir()
    (final / "params").mkdir()
    assert mgr.steps() == []  # the step is invisible mid-window...

    def boom(path, tree):
        raise RuntimeError("second crash")

    monkeypatch.setattr(checkpoint, "save", boom)
    with pytest.raises(RuntimeError, match="second crash"):
        mgr.save(5, {"w": jnp.zeros((3,))}, overwrite=True)
    monkeypatch.undo()

    # ...but the retry recovered the predecessor before the new write,
    # so the second failure cost nothing.
    assert mgr.steps() == [5]
    restored = mgr.restore(step=5)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.full((3,), 5.0))

    # A clean retry then replaces it for real.
    mgr.save(5, {"w": jnp.full((3,), 6.0)}, overwrite=True)
    np.testing.assert_array_equal(
        np.asarray(mgr.restore(step=5)["w"]), np.full((3,), 6.0)
    )


def test_checkpoint_manager_marker_is_atomic(tmp_path):
    """The COMMITTED marker is published via temp+rename: no observable
    state may have a marker that exists but does not parse."""
    mgr = checkpoint.CheckpointManager(tmp_path / "ckpts")
    mgr.save(1, {"w": jnp.ones((2,))}, tags={"k": "v"})
    assert mgr.metadata(1)["tags"] == {"k": "v"}
    # A torn temp marker (crash mid-write) is invisible to listing.
    torn = mgr._step_dir(2)
    torn.mkdir(parents=True)
    (torn / "params").mkdir()
    (torn / "COMMITTED.tmp").write_text('{"truncat')
    assert mgr.steps() == [1]


def test_checkpoint_manager_open_recovers_interrupted_swap(tmp_path):
    """A NEW manager over a root holding an interrupted overwrite swap
    must surface the parked predecessor immediately — recovery cannot
    wait for a same-step save() that may never come (steps are
    monotonic), and the .replaced_ copy must not leak."""
    mgr = checkpoint.CheckpointManager(tmp_path / "ckpts", max_to_keep=None)
    mgr.save(9, {"w": jnp.full((2,), 9.0)})
    final = mgr._step_dir(9)
    final.rename(tmp_path / "ckpts" / ".replaced_step_00000009")
    final.mkdir()
    (final / "params").mkdir()  # uncommitted replacement wreckage

    fresh = checkpoint.CheckpointManager(tmp_path / "ckpts", max_to_keep=None)
    assert fresh.steps() == [9]
    np.testing.assert_array_equal(
        np.asarray(fresh.restore(step=9)["w"]), np.full((2,), 9.0)
    )
    assert not list((tmp_path / "ckpts").glob(".replaced_*"))
