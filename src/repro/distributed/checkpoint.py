"""Fault-tolerant checkpointing: sharded arrays, atomic manifest, restore.

Layout on disk:

    <dir>/step_000123/
        manifest.json        tree structure + per-leaf shape/dtype/spec
        leaf_00000.npy ...   one file per pytree leaf (host-gathered)
    <dir>/LATEST             atomic pointer (rename) to the newest step

Writes go to ``step_X.tmp/`` and are renamed into place only after the
manifest lands, so a crash mid-write can never corrupt the restore path —
the previous checkpoint stays LATEST.  Router/bandit state (plain dict of
numpy arrays) rides the same machinery as model/optimizer pytrees.

On a real multi-host pod each host writes its local shards and the manifest
carries the PartitionSpecs for resharded restore; on this single-host
container arrays are host-gathered (they are either small or test-sized).
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import shutil
import tempfile
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro import compat

MANIFEST = "manifest.json"
LATEST = "LATEST"


def _flatten(tree: Any) -> Tuple[List[Tuple[str, Any]], Any]:
    leaves, treedef = jax.tree.flatten_with_path(tree)
    return [(compat.path_str(path), leaf) for path, leaf in leaves], treedef


def save(directory: str, step: int, tree: Any,
         extra: Optional[Dict[str, Any]] = None) -> pathlib.Path:
    """Atomically write ``tree`` as checkpoint ``step``; returns final path."""
    root = pathlib.Path(directory)
    root.mkdir(parents=True, exist_ok=True)
    final = root / f"step_{step:09d}"
    tmp = root / f"step_{step:09d}.tmp"
    if tmp.exists():
        shutil.rmtree(tmp)
    tmp.mkdir()

    named, _ = _flatten(tree)
    entries = []
    for i, (name, leaf) in enumerate(named):
        arr = np.asarray(leaf)
        fname = f"leaf_{i:05d}.npy"
        dtype = str(arr.dtype)
        if arr.dtype.kind not in "biufc":     # bf16/fp8: store as raw bits
            arr = arr.view(np.uint16 if arr.dtype.itemsize == 2
                           else np.uint8)
        np.save(tmp / fname, arr)
        entries.append({"name": name, "file": fname,
                        "shape": list(arr.shape), "dtype": dtype})
    manifest = {"step": step, "leaves": entries, "extra": extra or {}}
    # manifest written last inside tmp, then a single atomic rename
    (tmp / MANIFEST).write_text(json.dumps(manifest, indent=1))
    if final.exists():
        shutil.rmtree(final)
    os.replace(tmp, final)
    _point_latest(root, final.name)
    return final


def _point_latest(root: pathlib.Path, name: str) -> None:
    fd, tmppath = tempfile.mkstemp(dir=root)
    with os.fdopen(fd, "w") as f:
        f.write(name)
    os.replace(tmppath, root / LATEST)


def latest_step(directory: str) -> Optional[int]:
    root = pathlib.Path(directory)
    ptr = root / LATEST
    if not ptr.exists():
        return None
    name = ptr.read_text().strip()
    if not (root / name / MANIFEST).exists():
        return None
    return int(name.split("_")[1])


def restore(directory: str, like: Any, step: Optional[int] = None,
            shardings: Any = None) -> Tuple[Any, Dict[str, Any]]:
    """Restore into the structure of ``like``; reshard if shardings given.

    Returns (tree, extra).  Missing leaves raise — a checkpoint must match
    the model it restores (elastic re-meshing changes shardings, never the
    tree structure).
    """
    root = pathlib.Path(directory)
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {directory}")
    path = root / f"step_{step:09d}"
    manifest = json.loads((path / MANIFEST).read_text())
    by_name = {e["name"]: e for e in manifest["leaves"]}

    named, treedef = _flatten(like)
    flat_shardings = None
    if shardings is not None:
        flat_shardings = [s for _, s in _flatten(shardings)[0]]
    out = []
    for i, (name, leaf) in enumerate(named):
        e = by_name.get(name)
        if e is None:
            raise KeyError(f"checkpoint {path} missing leaf {name!r}")
        arr = np.load(path / e["file"])
        if str(arr.dtype) != e["dtype"]:      # raw-bits storage (bf16/fp8)
            import ml_dtypes
            arr = arr.view(getattr(ml_dtypes, e["dtype"]))
        if tuple(arr.shape) != tuple(leaf.shape):
            raise ValueError(f"leaf {name!r}: checkpoint shape {arr.shape} "
                             f"!= model shape {tuple(leaf.shape)}")
        if flat_shardings is not None:
            out.append(jax.device_put(arr, flat_shardings[i]))
        else:
            out.append(jax.numpy.asarray(arr))
    tree = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(like), out)
    return tree, manifest.get("extra", {})


def prune(directory: str, keep: int = 3) -> None:
    """Retain the newest ``keep`` checkpoints (never the LATEST target)."""
    root = pathlib.Path(directory)
    steps = sorted(p for p in root.glob("step_*") if p.is_dir()
                   and not p.name.endswith(".tmp"))
    for p in steps[:-keep]:
        shutil.rmtree(p)
