"""Sharding-aware pytree checkpointing (numpy .npz + JSON manifest).

Leaves are gathered to host, stored flat by tree path; the manifest records
tree structure, dtypes and the logical PartitionSpec of each leaf so a
restore onto a different mesh re-shards correctly.  No external deps.

Writes are atomic: everything is staged into a temp sibling directory,
fsynced, and ``os.replace``d into place — a crash mid-save leaves either
the previous checkpoint or none, never a truncated one.  Loads raise
``CheckpointCorruptError`` (with the offending path) on missing or
truncated ``arrays.npz``/``manifest.json`` instead of an opaque
``np.load``/JSON traceback.
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile
import zipfile

import jax
import numpy as np


class CheckpointCorruptError(RuntimeError):
    """A checkpoint directory is missing, truncated, or inconsistent."""


def _flatten_with_paths(tree):
    flat, treedef = jax.tree.flatten_with_path(tree)
    out = {}
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        out[key] = leaf
    return out, treedef


def _fsync_dir(path):
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    except OSError:  # pragma: no cover - some filesystems reject dir fsync
        pass
    finally:
        os.close(fd)


def save_checkpoint(path, tree, step=0, pspecs=None, extra=None):
    path = os.fspath(path)
    parent = os.path.dirname(os.path.abspath(path)) or "."
    os.makedirs(parent, exist_ok=True)
    flat, _ = _flatten_with_paths(tree)
    arrays = {k: np.asarray(jax.device_get(v)) for k, v in flat.items()}
    manifest = {
        "step": int(step),
        "keys": sorted(arrays.keys()),
        "dtypes": {k: str(v.dtype) for k, v in arrays.items()},
        "shapes": {k: list(v.shape) for k, v in arrays.items()},
        "extra": extra or {},
    }
    if pspecs is not None:
        flat_specs, _ = _flatten_with_paths(pspecs)
        manifest["pspecs"] = {k: str(v) for k, v in flat_specs.items()}
    tmp = tempfile.mkdtemp(prefix=os.path.basename(path) + ".tmp.",
                           dir=parent)
    try:
        for name, writer in (
            ("arrays.npz", lambda f: np.savez(f, **arrays)),
            ("manifest.json", lambda f: json.dump(manifest, f, indent=1)),
        ):
            mode = "wb" if name.endswith(".npz") else "w"
            with open(os.path.join(tmp, name), mode) as f:
                writer(f)
                f.flush()
                os.fsync(f.fileno())
        _fsync_dir(tmp)
        if os.path.isdir(path):
            # os.replace cannot clobber a non-empty dir; swap via a
            # doomed sibling so the target transition stays atomic.
            doomed = tempfile.mkdtemp(prefix=os.path.basename(path)
                                      + ".old.", dir=parent)
            os.replace(path, os.path.join(doomed, "prev"))
            os.replace(tmp, path)
            shutil.rmtree(doomed, ignore_errors=True)
        else:
            os.replace(tmp, path)
        _fsync_dir(parent)
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise


def _read_manifest(path):
    mpath = os.path.join(path, "manifest.json")
    if not os.path.exists(mpath):
        raise CheckpointCorruptError(f"missing manifest: {mpath}")
    try:
        with open(mpath) as f:
            manifest = json.load(f)
    except (json.JSONDecodeError, OSError) as e:
        raise CheckpointCorruptError(
            f"unreadable/truncated manifest: {mpath} ({e})") from e
    if "keys" not in manifest:
        raise CheckpointCorruptError(f"manifest missing 'keys': {mpath}")
    return manifest


def _read_arrays(path, manifest):
    apath = os.path.join(path, "arrays.npz")
    if not os.path.exists(apath):
        raise CheckpointCorruptError(f"missing arrays: {apath}")
    try:
        data = np.load(apath)
    except (zipfile.BadZipFile, OSError, ValueError) as e:
        raise CheckpointCorruptError(
            f"unreadable/truncated arrays: {apath} ({e})") from e
    missing = [k for k in manifest["keys"] if k not in data.files]
    if missing:
        raise CheckpointCorruptError(
            f"arrays.npz missing leaves {missing[:4]}"
            f"{'...' if len(missing) > 4 else ''}: {apath}")
    return data


def load_checkpoint(path, like_tree=None, shardings=None):
    """Restore a pytree.  ``like_tree`` (a template with the same structure)
    keys the placement; with ``shardings`` a matching tree of NamedShardings
    each leaf is placed sharded via jax.device_put."""
    path = os.fspath(path)
    manifest = _read_manifest(path)
    data = _read_arrays(path, manifest)
    if like_tree is None:
        return {k: data[k] for k in manifest["keys"]}, manifest
    flat, treedef = _flatten_with_paths(like_tree)
    sflat = None
    if shardings is not None:
        sflat, _ = _flatten_with_paths(shardings)
    leaves = {}
    for k in flat:
        if k not in data.files:
            raise CheckpointCorruptError(
                f"checkpoint at {path} lacks leaf '{k}' of like_tree")
        arr = data[k]
        if sflat is not None:
            arr = jax.device_put(arr, sflat[k])
        leaves[k] = arr
    # dict insertion order == tree flatten order
    restored = jax.tree.unflatten(
        jax.tree.structure(like_tree), [leaves[k] for k in flat]
    )
    return restored, manifest
