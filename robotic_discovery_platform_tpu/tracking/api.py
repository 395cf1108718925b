"""MLflow-shaped tracking API over the file store.

Drop-in for the subset of the MLflow surface the reference exercises:
``set_tracking_uri`` / ``set_experiment`` / ``start_run`` / ``log_params`` /
``log_metric`` (reference: scripts/train_segmenter.py:112-129,183-191),
model logging + registration (:195-207), ``MlflowClient.get_latest_versions``
and ``set_registered_model_alias`` (reference: workflows/
retraining_pipeline.py:50-74), and ``load_model("models:/Name/latest" |
"models:/Name@alias" | "models:/Name/3")`` (reference: services/
vision_analysis/server.py:81-82 plus README.md:147's documented staging-alias
intent).

Model artifacts are Flax variable trees serialized with
``flax.serialization`` plus a JSON model config, so a registry entry is
self-describing: ``load_model`` rebuilds the Flax module and returns
``(model, variables)``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import re
import threading
from pathlib import Path
from types import SimpleNamespace

from robotic_discovery_platform_tpu.tracking.store import FileStore
from robotic_discovery_platform_tpu.utils.config import from_dict

_DEFAULT_URI = "file:ml/mlruns"

# Process-global like real MLflow (the gRPC server's worker threads must see
# the URI the main thread configured); guarded for concurrent mutation.
_state = SimpleNamespace(
    uri=_DEFAULT_URI, store=None, experiment_id="0", active_run=None
)
_state_lock = threading.Lock()


def _globals():
    return _state


def set_tracking_uri(uri: str) -> None:
    with _state_lock:
        _state.uri = uri
        _state.store = None


def get_tracking_uri() -> str:
    return _globals().uri


def _make_store(uri: str):
    """URI-scheme backend selection: the dependency-free FileStore by
    default; for tracking-server URIs, the mlflow-client adapter
    (tracking/mlflow_backend.py) when the ``mlflow`` extra is installed,
    else the dependency-free REST client (tracking/rest_backend.py).
    ``mlflow+<uri>`` forces the client adapter, ``mlflow-rest+http(s)://``
    forces the REST client."""
    scheme = uri.split(":", 1)[0]
    if uri.startswith("mlflow-rest+"):
        from robotic_discovery_platform_tpu.tracking.rest_backend import (
            RestMlflowStore)

        return RestMlflowStore(uri[len("mlflow-rest+"):])
    if scheme in ("http", "https") or uri.startswith(("databricks", "mlflow+")):
        bare = uri[len("mlflow+"):] if uri.startswith("mlflow+") else uri
        try:
            from robotic_discovery_platform_tpu.tracking.mlflow_backend import (
                MlflowStore)

            return MlflowStore(bare)
        except ImportError:
            if scheme not in ("http", "https"):
                raise  # databricks/mlflow+file etc. need the real client
            from robotic_discovery_platform_tpu.tracking.rest_backend import (
                RestMlflowStore)

            return RestMlflowStore(bare)
    return FileStore(uri)


def _store() -> FileStore:
    with _state_lock:
        if _state.store is None:
            _state.store = _make_store(_state.uri)
        return _state.store


def set_experiment(name: str) -> str:
    g = _globals()
    g.experiment_id = _store().get_or_create_experiment(name)
    return g.experiment_id


class ActiveRun:
    """Mimics ``mlflow.ActiveRun``: has ``.info.run_id``."""

    class _Info:
        def __init__(self, run_id: str):
            self.run_id = run_id

    def __init__(self, run_id: str):
        self.info = self._Info(run_id)


@contextlib.contextmanager
def start_run(run_name: str | None = None):
    g = _globals()
    run_id = _store().create_run(g.experiment_id, run_name)
    g.active_run = ActiveRun(run_id)
    try:
        yield g.active_run
        _store().end_run(run_id, "FINISHED")
    except Exception:
        _store().end_run(run_id, "FAILED")
        raise
    finally:
        g.active_run = None


def active_run() -> ActiveRun | None:
    return _globals().active_run


def _require_run() -> str:
    run = active_run()
    if run is None:
        raise RuntimeError("no active run; wrap calls in tracking.start_run()")
    return run.info.run_id


def log_params(params: dict) -> None:
    _store().log_params(_require_run(), params)


def log_param(key: str, value) -> None:
    log_params({key: value})


def log_metric(key: str, value: float, step: int | None = None) -> None:
    _store().log_metric(_require_run(), key, value, step)


def log_metrics(metrics: dict, step: int | None = None) -> None:
    for k, v in metrics.items():
        log_metric(k, v, step)


def get_metric_history(run_id: str, key: str) -> list[dict]:
    return _store().get_metric_history(run_id, key)


# ---------------------------------------------------------------------------
# Model logging / registry
# ---------------------------------------------------------------------------

_MODEL_CONFIG_FILE = "model_config.json"
_MODEL_WEIGHTS_FILE = "variables.msgpack"
#: the training task that built the model (``training/tasks.py``); an
#: artifact without the file is the segmenter's, as all were before tasks
_MODEL_FAMILY_FILE = "model_family.txt"
#: weights above this many bytes are written as one raw ``.npy`` file a leaf
#: under ``variables/`` instead of one msgpack blob: flax's ``to_bytes``
#: builds the blob in memory at 0.3 GB/s, which for 2.6 GB of parameters
#: made the registry write the longest phase of a retraining job
_LEAF_FILES_ABOVE = 1024**3
_MODEL_LEAVES_DIR = "variables"


def save_model(variables, model_cfg, path: Path) -> None:
    """Write a self-describing model artifact directory: the configuration,
    the task that trains it (found from the configuration's type) and the
    weights."""
    from flax import serialization

    from robotic_discovery_platform_tpu.training import checkpoint, tasks

    path = Path(path)
    path.mkdir(parents=True, exist_ok=True)
    (path / _MODEL_CONFIG_FILE).write_text(
        json.dumps(dataclasses.asdict(model_cfg), indent=2)
    )
    (path / _MODEL_FAMILY_FILE).write_text(tasks.task_for(model_cfg).name)
    if checkpoint.are_leaf_files(variables):
        # the weights as a streamed checkpoint's files (the best candidate
        # of a state too large to hold twice): linked, not copied
        checkpoint.link_leaves(path / _MODEL_LEAVES_DIR, variables)
    elif checkpoint.tree_bytes(variables) > _LEAF_FILES_ABOVE:
        checkpoint.write_leaves(path / _MODEL_LEAVES_DIR, variables)
    else:
        (path / _MODEL_WEIGHTS_FILE).write_bytes(
            serialization.to_bytes(variables))


def load_model_dir(path: Path):
    """Load (model, variables) from an artifact directory; the model is
    built by the task the artifact names."""
    from flax import serialization

    from robotic_discovery_platform_tpu.training import tasks

    path = Path(path)
    family = path / _MODEL_FAMILY_FILE
    task = tasks.task_named(
        family.read_text().strip() if family.is_file() else tasks.UNET.name)
    cfg = from_dict(task.config_type,
                    json.loads((path / _MODEL_CONFIG_FILE).read_text()))
    model = task.build(cfg)
    if (path / _MODEL_LEAVES_DIR).is_dir():
        from robotic_discovery_platform_tpu.training import checkpoint

        variables = checkpoint.read_leaves(
            path / _MODEL_LEAVES_DIR, task.template(model))
    else:
        variables = serialization.from_bytes(
            task.template(model), (path / _MODEL_WEIGHTS_FILE).read_bytes()
        )
    return model, variables


def log_model(variables, model_cfg, artifact_path: str = "model",
              registered_model_name: str | None = None) -> int | None:
    """Save the model under the active run's artifacts and optionally register
    a new version (the reference's ``mlflow.pytorch.log_model(...,
    registered_model_name=...)`` flow, train_segmenter.py:200-206).

    Returns the new registry version when registered.
    """
    run_id = _require_run()
    store = _store()
    dest = store.artifact_dir(run_id) / artifact_path
    save_model(variables, model_cfg, dest)
    # remote backends (MlflowStore) stage locally, then upload to the run
    if hasattr(store, "publish_artifacts"):
        store.publish_artifacts(run_id, dest)
    if registered_model_name is None:
        return None
    return store.create_model_version(registered_model_name, run_id, dest)


_MODEL_URI = re.compile(
    r"^models:/(?P<name>[^/@]+)(?:/(?P<version>latest|\d+)|@(?P<alias>[\w-]+))?$"
)


def store_for(tracking_uri: str):
    """A store instance SCOPED to ``tracking_uri``, without touching the
    process-global tracking state. Background threads (the serving
    hot-reload poller) must use this: ``set_tracking_uri`` from a thread
    would silently re-point every other component's tracking mid-run."""
    return _make_store(tracking_uri)


def resolve_model_uri(uri: str, store=None) -> Path:
    """models:/Name/latest | models:/Name/3 | models:/Name@staging -> path.

    ``store`` defaults to the process-global one; pass ``store_for(uri)``
    for a scoped lookup.
    """
    m = _MODEL_URI.match(uri)
    if not m:
        raise ValueError(f"unsupported model uri: {uri!r}")
    name = m.group("name")
    store = _store() if store is None else store
    if m.group("alias"):
        version = store.get_alias(name, m.group("alias"))
        if version is None:
            raise KeyError(f"model {name!r} has no alias {m.group('alias')!r}")
    elif m.group("version") and m.group("version") != "latest":
        version = int(m.group("version"))
    else:
        version = store.latest_version(name)["version"]
    return store.version_path(name, version)


def load_model(uri: str, store=None):
    """Load (model, variables) from a ``models:/`` uri or a plain path."""
    if uri.startswith("models:/"):
        return load_model_dir(resolve_model_uri(uri, store=store))
    return load_model_dir(Path(uri))


class ModelVersionInfo:
    """Mimics mlflow's ModelVersion for the fields the reference touches
    (retraining_pipeline.py:60-66: ``.version``)."""

    def __init__(self, name: str, version: int, run_id: str | None):
        self.name = name
        self.version = version
        self.run_id = run_id


class Client:
    """Registry client with the reference's MlflowClient call shapes."""

    def get_latest_versions(self, name: str, stages=None) -> list[ModelVersionInfo]:
        """MLflow semantics: latest version per requested stage. A version's
        stage is "None" until transitioned (the reference promotes via the
        *alias* flow, retraining_pipeline.py:69-75, so stages stay "None"
        unless a version record carries an explicit ``stage`` field)."""
        if stages is None:
            v = _store().latest_version(name)
            return [ModelVersionInfo(name, v["version"], v.get("run_id"))]
        versions = _store().list_model_versions(name)
        if not versions:
            raise KeyError(f"registered model {name!r} has no versions")
        out = []
        for stage in stages:
            staged = [v for v in versions if v.get("stage", "None") == stage]
            if staged:
                v = max(staged, key=lambda v: v["version"])
                out.append(ModelVersionInfo(name, v["version"], v.get("run_id")))
        return out

    def set_registered_model_alias(self, name: str, alias: str, version) -> None:
        _store().set_alias(name, alias, int(version))

    def get_model_version_by_alias(self, name: str, alias: str) -> ModelVersionInfo:
        version = _store().get_alias(name, alias)
        if version is None:
            raise KeyError(f"model {name!r} has no alias {alias!r}")
        return ModelVersionInfo(name, version, None)

    def list_versions(self, name: str) -> list[dict]:
        return _store().list_model_versions(name)
