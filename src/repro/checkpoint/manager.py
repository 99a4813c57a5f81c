"""Fault-tolerant checkpointing.

Properties needed at 1000-node scale, implemented here:

  * **atomic** — write to ``<dir>.tmp`` then ``os.rename`` (a crashed save
    can never corrupt the latest checkpoint; a half-written tmp dir is
    ignored and garbage-collected)
  * **keep-k** — bounded disk usage, oldest checkpoints pruned
  * **async** — a background thread serializes, the train loop keeps going
    (device→host copy happens synchronously, serialization doesn't block)
  * **resumable** — ``latest_step`` + deterministic data pipeline ⇒ bitwise
    replay after restart (tested in tests/test_checkpoint.py)
  * **elastic** — checkpoints store *global* arrays; ``restore`` re-shards
    onto whatever mesh/sharding the restoring job passes (different device
    count than the saving job — node-failure recovery path)

Format: one ``.npz`` per checkpoint (pytree flattened with stable key paths)
plus a small JSON manifest.  On multi-host deployments each host would write
its address-space shards; on this single-process container the host holds all
shards, which exercises the same code path.
"""
from __future__ import annotations

import json
import os
import queue
import shutil
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["CheckpointManager", "save_policy", "restore_policy",
           "policy_manifest", "policy_feature_config"]


def _jsonable(obj):
    """Manifest sanitizer: numpy scalars/arrays → plain Python.

    Corpus-run manifests carry sampler RNG state, per-graph bests and
    bucket partitions assembled from numpy — ``json.dump`` would otherwise
    crash on the first ``np.int64`` deep inside.
    """
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, np.ndarray):
        return _jsonable(obj.tolist())
    if isinstance(obj, np.generic):
        return obj.item()
    return obj


def _flatten(tree) -> Dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = jax.tree_util.keystr(path, simple=True, separator="/")
        arr = np.asarray(leaf)
        if arr.dtype == jnp.bfloat16:
            # npz has no bf16: widen losslessly; restore casts back via the
            # target tree's dtype.
            arr = arr.astype(np.float32)
        flat[key] = arr
    return flat


def _unflatten(tree_like, flat: Dict[str, np.ndarray]):
    paths = jax.tree_util.tree_flatten_with_path(tree_like)[0]
    leaves = []
    for path, leaf in paths:
        key = jax.tree_util.keystr(path, simple=True, separator="/")
        if key not in flat:
            raise KeyError(f"checkpoint missing leaf {key!r}")
        arr = flat[key]
        leaves.append(arr)
    treedef = jax.tree_util.tree_structure(tree_like)
    return jax.tree_util.tree_unflatten(treedef, leaves)


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3,
                 async_save: bool = False):
        self.directory = directory
        self.keep = keep
        self.async_save = async_save
        os.makedirs(directory, exist_ok=True)
        self._gc_tmp()
        self._q: "queue.Queue" = queue.Queue()
        self._worker: Optional[threading.Thread] = None
        self._errors: List[BaseException] = []
        if async_save:
            self._worker = threading.Thread(target=self._drain, daemon=True)
            self._worker.start()

    # ------------------------------------------------------------- internal
    def _gc_tmp(self) -> None:
        for name in os.listdir(self.directory):
            if name.endswith(".tmp"):
                shutil.rmtree(os.path.join(self.directory, name),
                              ignore_errors=True)

    def _step_dir(self, step: int) -> str:
        return os.path.join(self.directory, f"step_{step:010d}")

    def _drain(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            try:
                self._write(*item)
            except BaseException as e:   # surfaced on next wait()
                self._errors.append(e)
            finally:
                self._q.task_done()

    def _write(self, step: int, flat: Dict[str, np.ndarray],
               meta: Dict) -> None:
        final = self._step_dir(step)
        tmp = final + ".tmp"
        os.makedirs(tmp, exist_ok=True)
        np.savez(os.path.join(tmp, "state.npz"), **flat)
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(_jsonable({"step": step, "time": time.time(), **meta}),
                      f)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)
        self._prune()

    def _prune(self) -> None:
        steps = self.all_steps()
        for s in steps[:-self.keep]:
            shutil.rmtree(self._step_dir(s), ignore_errors=True)

    # ---------------------------------------------------------------- API
    def all_steps(self) -> List[int]:
        steps = []
        for name in os.listdir(self.directory):
            if name.startswith("step_") and not name.endswith(".tmp"):
                if os.path.exists(os.path.join(self.directory, name,
                                               "manifest.json")):
                    steps.append(int(name[5:]))
        return sorted(steps)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def save(self, step: int, tree: Any, meta: Optional[Dict] = None) -> None:
        # device→host copy is synchronous (consistent snapshot); file IO is
        # async when enabled.
        flat = _flatten(tree)
        if self.async_save:
            self._q.put((step, flat, meta or {}))
        else:
            self._write(step, flat, meta or {})

    def wait(self) -> None:
        """Block until queued async saves land; re-raise their errors."""
        if self.async_save:
            self._q.join()
        if self._errors:
            raise self._errors.pop()

    def restore(self, step: int, tree_like: Any,
                shardings: Optional[Any] = None) -> Any:
        """Restore into the structure of ``tree_like``.

        ``shardings``: optional matching tree of ``jax.sharding.Sharding`` —
        arrays are placed with those shardings (elastic re-shard path).
        """
        path = os.path.join(self._step_dir(step), "state.npz")
        with np.load(path) as data:
            flat = {k: data[k] for k in data.files}
        tree = _unflatten(tree_like, flat)

        def place(x, like, sh=None):
            dtype = like.dtype if hasattr(like, "dtype") else None
            arr = jnp.asarray(x, dtype)
            if sh is not None:
                arr = jax.device_put(arr, sh)
            return arr

        if shardings is not None:
            return jax.tree.map(place, tree, tree_like, shardings)
        return jax.tree.map(lambda x, l: place(x, l), tree, tree_like)

    def manifest(self, step: int) -> Dict:
        with open(os.path.join(self._step_dir(step), "manifest.json")) as f:
            return json.load(f)

    def close(self) -> None:
        if self._worker is not None:
            self._q.put(None)
            self._worker.join(timeout=10)
            self._worker = None


# --------------------------------------------------------------------------
# Shared-policy checkpoints (multi-graph training).
#
# A cross-graph policy is only usable on a new graph if that graph is
# featurized with the *same* shared vocabularies the policy was trained on,
# so the feature layout rides along in the checkpoint manifest (it is small,
# JSON-serializable, and the thing people forget to persist).
# --------------------------------------------------------------------------


def _feature_config_to_meta(feature_config) -> Optional[Dict]:
    if feature_config is None:
        return None
    import dataclasses
    d = dataclasses.asdict(feature_config)
    return {k: (list(v) if isinstance(v, tuple) else v) for k, v in d.items()}


def _feature_config_from_meta(meta: Optional[Dict]):
    if not meta:
        return None
    from ..core.features import FeatureConfig
    kw = dict(meta)
    for key in ("op_vocab", "in_deg_vocab", "out_deg_vocab"):
        if kw.get(key) is not None:
            kw[key] = tuple(kw[key])
    return FeatureConfig(**kw)


def save_policy(directory: str, params: Any, *, step: int = 0,
                feature_config=None, meta: Optional[Dict] = None,
                keep: int = 3) -> None:
    """Atomically save a (shared) policy pytree + its feature layout."""
    mgr = CheckpointManager(directory, keep=keep)
    full_meta = dict(meta or {})
    fc = _feature_config_to_meta(feature_config)
    if fc is not None:
        full_meta["feature_config"] = fc
    try:
        mgr.save(step, params, full_meta)
    finally:
        mgr.close()


def policy_manifest(directory: str, step: Optional[int] = None) -> Dict:
    """The manifest of a ``save_policy`` checkpoint (training config, the
    simulation engine that produced the rewards, feature layout, ...)."""
    mgr = CheckpointManager(directory)
    try:
        if step is None:
            step = mgr.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {directory!r}")
        return mgr.manifest(step)
    finally:
        mgr.close()


def policy_feature_config(directory: str, step: Optional[int] = None):
    """The feature layout a ``save_policy`` checkpoint was trained with
    (``None`` when the save recorded none) — readable *without* a parameter
    tree, so warm-start paths can featurize and validate new graphs before
    deciding to restore.
    """
    return _feature_config_from_meta(
        policy_manifest(directory, step).get("feature_config"))


def restore_policy(directory: str, params_like: Any,
                   step: Optional[int] = None, *,
                   graphs: Optional[Any] = None):
    """→ (params, feature_config, step, manifest) from a ``save_policy``
    checkpoint.

    ``params_like`` supplies the pytree structure/dtypes (e.g. a freshly
    ``init()``-ed parameter tree of the same architecture).  ``manifest`` is
    the full manifest dict (training config, reward engine, ...), already
    loaded — callers should read it from here rather than re-opening the
    directory via :func:`policy_manifest`.

    ``graphs`` — the graphs the restored policy is about to run on.  When
    given, the saved feature vocabularies are validated against them
    (:func:`repro.core.features.check_feature_compat`): an op type missing
    from the saved ``op_vocab`` raises — naming the mismatched types —
    instead of silently encoding all-zero / mis-aligned one-hot columns
    that would corrupt fine-tuning.
    """
    mgr = CheckpointManager(directory)
    try:
        if step is None:
            step = mgr.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {directory!r}")
        manifest = mgr.manifest(step)
        feature_config = _feature_config_from_meta(
            manifest.get("feature_config"))
        if graphs is not None:
            from ..core.features import check_feature_compat
            if feature_config is None:
                raise ValueError(
                    f"checkpoint {directory!r} records no feature_config; "
                    f"cannot validate it against the given graphs")
            check_feature_compat(feature_config, graphs)
        params = mgr.restore(step, params_like)
    finally:
        mgr.close()
    return params, feature_config, step, manifest
