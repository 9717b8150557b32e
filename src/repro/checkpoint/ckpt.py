"""Atomic checkpoint save/restore for sharded pytrees.

Two-phase protocol: leaves are written into ``step_N.tmp/`` (one .npy per
leaf keyed by its tree path + this host's process index), fsynced, a
manifest (step, config hash, leaf index, tree structure) is written LAST,
and the directory is atomically renamed to ``step_N/``. A crash at any
point leaves either a complete checkpoint or an ignorable ``.tmp`` — the
restore path only ever sees manifests of complete checkpoints, and boots
from the newest one (torn checkpoints are skipped, older complete ones are
used instead: the restart path after a node failure).

On a real multi-host cluster each host writes only its addressable shards
(shard-per-host layout); this container is single-host so leaves are whole.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
from pathlib import Path

import jax
import numpy as np

from repro.obs import REGISTRY, span


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


def config_fingerprint(obj) -> str:
    return hashlib.sha256(repr(obj).encode()).hexdigest()[:16]


def save(root: str | os.PathLike, step: int, tree, config_hash: str = "",
         process_index: int | None = None) -> Path:
    """Write ``tree`` as checkpoint ``step`` under ``root`` (the two-phase
    protocol above). Returns the published directory."""
    with span("ckpt.write") as sp:
        final, leaves, nbytes = _save(root, step, tree, config_hash,
                                      process_index)
        sp.note(leaves=leaves, bytes=nbytes)
    REGISTRY.counter("checkpoint_bytes_total").inc(nbytes)
    return final


def _save(root, step, tree, config_hash, process_index):
    root = Path(root)
    root.mkdir(parents=True, exist_ok=True)
    pidx = jax.process_index() if process_index is None else process_index
    final = root / f"step_{step:08d}"
    tmp = root / f"step_{step:08d}.tmp"
    if tmp.exists():
        shutil.rmtree(tmp)
    tmp.mkdir()
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    index = []
    nbytes = 0  # leaf files' bytes
    for path, leaf in leaves:
        key = _path_str(path)
        fname = f"{key.replace('/', '.')}.p{pidx}.npy"
        arr = np.asarray(leaf)
        with open(tmp / fname, "wb") as f:
            np.save(f, arr)
            f.flush()
            os.fsync(f.fileno())
            nbytes += f.tell()
        index.append({"key": key, "file": fname, "shape": list(arr.shape),
                      "dtype": str(arr.dtype)})
    manifest = {"step": step, "config_hash": config_hash,
                "process_index": pidx, "leaves": index,
                "treedef": jax.tree_util.tree_structure(tree).__repr__()}
    mpath = tmp / "MANIFEST.json"
    with open(mpath, "w") as f:
        json.dump(manifest, f, indent=1)
        f.flush()
        os.fsync(f.fileno())
    if final.exists():
        shutil.rmtree(final)
    os.rename(tmp, final)  # atomic publish
    return final, len(index), nbytes


def latest_step(root: str | os.PathLike) -> int | None:
    root = Path(root)
    if not root.exists():
        return None
    steps = []
    for d in root.iterdir():
        if d.is_dir() and d.name.startswith("step_") \
                and not d.name.endswith(".tmp") \
                and (d / "MANIFEST.json").exists():
            steps.append(int(d.name.split("_")[1]))
    return max(steps) if steps else None


def read_leaf(root: str | os.PathLike, key: str, step: int | None = None,
              default=None):
    """Read one leaf of a complete checkpoint by its tree-path key,
    without materializing the rest of the tree. Used by the wire server's
    boot recovery to fetch transport-layer leaves (``wire/last_seq``)
    that ride in the session checkpoint but are not part of the session's
    ``load_state_dict`` contract. Returns ``default`` when the key (or
    any complete checkpoint) is absent."""
    root = Path(root)
    if step is None:
        step = latest_step(root)
    if step is None:
        return default
    d = root / f"step_{step:08d}"
    manifest = json.loads((d / "MANIFEST.json").read_text())
    for e in manifest["leaves"]:
        if e["key"] == key:
            return np.load(d / e["file"])
    return default


def prune(root: str | os.PathLike, keep: int = 2) -> int:
    """Delete all but the newest ``keep`` complete checkpoints under
    ``root`` (plus any torn ``.tmp`` debris). A daemon checkpointing
    every committed window would otherwise grow the store without bound.
    Returns directories removed."""
    root = Path(root)
    if not root.exists():
        return 0
    removed = 0
    complete = []
    for d in root.iterdir():
        if not d.is_dir():
            continue
        if d.name.endswith(".tmp"):
            shutil.rmtree(d, ignore_errors=True)
            removed += 1
        elif d.name.startswith("step_") and (d / "MANIFEST.json").exists():
            complete.append(d)
    complete.sort(key=lambda d: int(d.name.split("_")[1]))
    for d in complete[:-keep] if keep else complete:
        shutil.rmtree(d, ignore_errors=True)
        removed += 1
    return removed


def restore(root: str | os.PathLike, tree_like, step: int | None = None,
            config_hash: str = "", process_index: int | None = None):
    """Load into the structure of ``tree_like`` (arrays or SDS). Returns
    (tree, step). Raises FileNotFoundError if no complete checkpoint."""
    root = Path(root)
    if step is None:
        step = latest_step(root)
    if step is None:
        raise FileNotFoundError(f"no complete checkpoint under {root}")
    pidx = jax.process_index() if process_index is None else process_index
    d = root / f"step_{step:08d}"
    manifest = json.loads((d / "MANIFEST.json").read_text())
    if config_hash and manifest["config_hash"] \
            and manifest["config_hash"] != config_hash:
        raise ValueError(
            f"checkpoint config hash {manifest['config_hash']} != "
            f"{config_hash} — refusing to restore a different model")
    by_key = {e["key"]: e for e in manifest["leaves"]}
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree_like)
    out = []
    for path, leaf in leaves:
        key = _path_str(path)
        e = by_key[key]
        arr = np.load(d / e["file"].replace(f".p{manifest['process_index']}",
                                            f".p{pidx}"))
        out.append(arr)
    return jax.tree_util.tree_unflatten(treedef, out), step
