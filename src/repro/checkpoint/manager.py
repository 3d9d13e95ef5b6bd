"""Fault-tolerant checkpointing.

Guarantees:
  * **atomicity** — writes go to ``<dir>/tmp.<step>`` and are renamed to
    ``<dir>/step_<n>`` only after fsync; a crash mid-save never corrupts
    the latest checkpoint,
  * **asynchrony** — ``save_async`` snapshots device arrays to host then
    writes on a background thread; training continues,
  * **elasticity** — the manifest records leaf paths/shapes/dtypes and the
    logical PartitionSpec; ``restore`` re-shards onto ANY mesh (different
    device count / topology), which is the elastic-scaling and
    failed-node-replacement path,
  * **retention** — keep_last_k garbage collection.

On a real multi-host pod each host writes only the shards it owns
(addressable_shards); in this single-process container that degenerates to
full arrays, same code path.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
from typing import Optional

import jax
import numpy as np

__all__ = ["save_checkpoint", "restore_checkpoint", "CheckpointManager"]

_NATIVE_DTYPES = {
    "float16", "float32", "float64", "int8", "int16", "int32", "int64",
    "uint8", "uint16", "uint32", "uint64", "bool",
}


def _leaf_paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    names = ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
             for path, _ in flat]
    return names, [l for _, l in flat], treedef


def save_checkpoint(directory: str, step: int, tree, extra: Optional[dict] = None):
    """Synchronous atomic save."""
    os.makedirs(directory, exist_ok=True)
    tmp = os.path.join(directory, f"tmp.{step}.{os.getpid()}")
    final = os.path.join(directory, f"step_{step:08d}")
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    names, leaves, _ = _leaf_paths(tree)
    manifest = {"step": step, "leaves": [], "extra": extra or {}}
    for i, (name, leaf) in enumerate(zip(names, leaves)):
        arr = np.asarray(jax.device_get(leaf))
        fname = f"leaf_{i:05d}.npy"
        raw = arr.dtype.name not in _NATIVE_DTYPES
        if raw:  # bf16/fp8 etc: store raw bytes, keep logical dtype in meta
            np.save(os.path.join(tmp, fname),
                    np.ascontiguousarray(arr).view(np.uint8))
        else:
            np.save(os.path.join(tmp, fname), arr)
        spec = ""
        shd = getattr(leaf, "sharding", None)
        if shd is not None and hasattr(shd, "spec"):
            spec = str(shd.spec)
        manifest["leaves"].append(
            {"name": name, "file": fname, "shape": list(arr.shape),
             "dtype": str(arr.dtype), "raw": raw, "spec": spec}
        )
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    return final


def latest_step(directory: str) -> Optional[int]:
    if not os.path.isdir(directory):
        return None
    steps = [
        int(d.split("_")[1]) for d in os.listdir(directory) if d.startswith("step_")
    ]
    return max(steps) if steps else None


def restore_checkpoint(directory: str, tree_like, step: Optional[int] = None,
                       shardings=None):
    """Restore into the structure of ``tree_like``.

    ``shardings``: optional pytree (or flat list) of NamedSharding for the
    *current* mesh — arrays are re-sharded on load (elastic restore).
    Returns (tree, step, extra).
    """
    step = step if step is not None else latest_step(directory)
    if step is None:
        raise FileNotFoundError(f"no checkpoint in {directory}")
    d = os.path.join(directory, f"step_{step:08d}")
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    names, leaves, treedef = _leaf_paths(tree_like)
    by_name = {l["name"]: l for l in manifest["leaves"]}
    shard_flat = None
    if shardings is not None:
        shard_flat = jax.tree.leaves(
            shardings, is_leaf=lambda x: isinstance(x, jax.sharding.Sharding)
        )
    out = []
    for i, (name, like) in enumerate(zip(names, leaves)):
        meta = by_name[name]
        arr = np.load(os.path.join(d, meta["file"]))
        if meta.get("raw"):
            import ml_dtypes  # noqa: F401  (registers bf16/fp8 dtypes)

            dt = np.dtype(meta["dtype"])
            arr = arr.reshape(-1).view(dt).reshape(meta["shape"])
        elif hasattr(like, "dtype"):
            arr = arr.astype(like.dtype)
        if shard_flat is not None:
            arr = jax.device_put(arr, shard_flat[i])
        out.append(arr)
    return treedef.unflatten(out), step, manifest.get("extra", {})


class CheckpointManager:
    """Async, retained, atomic checkpoints + elastic restore."""

    def __init__(self, directory: str, keep_last_k: int = 3):
        self.directory = directory
        self.keep = keep_last_k
        self._thread: Optional[threading.Thread] = None

    def save_async(self, step: int, tree, extra: Optional[dict] = None):
        self.wait()  # one in-flight save
        host_tree = jax.tree.map(lambda l: np.asarray(jax.device_get(l)), tree)

        def work():
            save_checkpoint(self.directory, step, host_tree, extra)
            self._gc()

        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()

    def save(self, step: int, tree, extra: Optional[dict] = None):
        self.wait()
        save_checkpoint(self.directory, step, tree, extra)
        self._gc()

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def restore(self, tree_like, step=None, shardings=None):
        self.wait()
        return restore_checkpoint(self.directory, tree_like, step, shardings)

    def latest_step(self):
        return latest_step(self.directory)

    def _gc(self):
        steps = sorted(
            int(d.split("_")[1])
            for d in os.listdir(self.directory)
            if d.startswith("step_")
        )
        for s in steps[: -self.keep] if self.keep else []:
            shutil.rmtree(os.path.join(self.directory, f"step_{s:08d}"))
