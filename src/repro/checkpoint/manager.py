"""Checkpoint manager: atomic, integrity-checked, keep-k, re-mesh restore.

Fault-tolerance contract (DESIGN.md §4):
  * writes go to ``<dir>/tmp.step_N`` and are renamed atomically — a
    preempted writer can never corrupt the latest valid checkpoint;
  * every array records a SHA-256 digest in the manifest; loads verify;
  * ``latest`` resolution scans valid manifests (not a symlink), so a
    torn write is skipped automatically on restart;
  * arrays are stored logically (full shapes) — restore reshards onto
    *whatever mesh is active* (elastic shrink/grow across restarts);
  * optimizer state / data step / rng all live in the same tree, so
    resume is exact.

On a real multi-host pod each process would write its owned shards
(process-local `.npz` + shared manifest); this container is single-host,
so arrays are written whole — the formats and the restore path are the
same (recorded as a scale note in DESIGN.md).
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np


def _tree_to_flat(tree) -> dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        flat[name] = np.asarray(leaf)
    return flat


def save_checkpoint(ckpt_dir: str, step: int, tree: Any, metadata: Optional[dict] = None) -> str:
    os.makedirs(ckpt_dir, exist_ok=True)
    tmp = os.path.join(ckpt_dir, f"tmp.step_{step:08d}")
    final = os.path.join(ckpt_dir, f"step_{step:08d}")
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)

    flat = _tree_to_flat(tree)
    np.savez(os.path.join(tmp, "arrays.npz"), **flat)
    digests = {k: hashlib.sha256(v.tobytes()).hexdigest() for k, v in flat.items()}
    ts = jax.tree_util.tree_structure(tree)
    try:  # proto is stable across versions but rejects user-defined nodes
        treedef_hex, treedef_kind = ts.serialize_using_proto().hex(), "proto"
    except ValueError:  # e.g. NamedTuple optimizer states -> pickle
        import pickle

        treedef_hex, treedef_kind = pickle.dumps(ts).hex(), "pickle"
    manifest = {
        "step": step,
        "arrays": {k: {"shape": list(v.shape), "dtype": str(v.dtype), "sha256": digests[k]} for k, v in flat.items()},
        "treedef": treedef_hex,
        "treedef_kind": treedef_kind,
        "metadata": metadata or {},
    }
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)  # atomic publish
    return final


def _valid_steps(ckpt_dir: str) -> list[int]:
    if not os.path.isdir(ckpt_dir):
        return []
    steps = []
    for name in os.listdir(ckpt_dir):
        if name.startswith("step_") and os.path.exists(os.path.join(ckpt_dir, name, "manifest.json")):
            try:
                steps.append(int(name.split("_")[1]))
            except ValueError:
                continue
    return sorted(steps)


def load_checkpoint(
    ckpt_dir: str,
    step: Optional[int] = None,
    sharding_fn: Optional[Callable[[str, tuple], Any]] = None,
    verify: bool = True,
) -> tuple[int, Any]:
    """Load latest (or given) step.  ``sharding_fn(name, shape)`` may
    return a Sharding to place each array directly onto the active mesh
    (the elastic re-mesh path); None keeps host arrays."""
    steps = _valid_steps(ckpt_dir)
    if not steps:
        raise FileNotFoundError(f"no valid checkpoints in {ckpt_dir}")
    step = steps[-1] if step is None else step
    path = os.path.join(ckpt_dir, f"step_{step:08d}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    leaves = []
    names = list(manifest["arrays"].keys())
    try:
        data = np.load(os.path.join(path, "arrays.npz"))
        arrays = {name: data[name] for name in names}
    except Exception as e:  # torn/corrupt archive -> uniform IOError
        raise IOError(f"checkpoint corruption reading {path}: {e}") from e
    for name in names:
        arr = arrays[name]
        if verify:
            digest = hashlib.sha256(arr.tobytes()).hexdigest()
            if digest != manifest["arrays"][name]["sha256"]:
                raise IOError(f"checkpoint corruption: {name} digest mismatch")
        if sharding_fn is not None:
            sh = sharding_fn(name, arr.shape)
            leaves.append(jax.device_put(arr, sh) if sh is not None else jnp.asarray(arr))
        else:
            leaves.append(jnp.asarray(arr))
    if manifest.get("treedef_kind", "proto") == "pickle":
        import pickle

        treedef = pickle.loads(bytes.fromhex(manifest["treedef"]))
    else:
        from jaxlib._jax import pytree

        treedef = pytree.PyTreeDef.deserialize_using_proto(
            jax.tree_util.default_registry, bytes.fromhex(manifest["treedef"]))
    return step, jax.tree_util.tree_unflatten(treedef, leaves)


class CheckpointManager:
    """Keep-k rotation + auto-resume + preemption-safe cadence."""

    def __init__(self, ckpt_dir: str, keep: int = 3, every_steps: int = 100):
        self.ckpt_dir = ckpt_dir
        self.keep = keep
        self.every_steps = every_steps

    def maybe_save(self, step: int, tree: Any, metadata: Optional[dict] = None, force: bool = False):
        if not force and (step % self.every_steps != 0):
            return None
        path = save_checkpoint(self.ckpt_dir, step, tree, metadata)
        for old in _valid_steps(self.ckpt_dir)[: -self.keep]:
            shutil.rmtree(os.path.join(self.ckpt_dir, f"step_{old:08d}"), ignore_errors=True)
        return path

    def restore_or_none(self, sharding_fn=None):
        try:
            return load_checkpoint(self.ckpt_dir, sharding_fn=sharding_fn)
        except FileNotFoundError:
            return None

    def latest_step(self) -> Optional[int]:
        steps = _valid_steps(self.ckpt_dir)
        return steps[-1] if steps else None
