"""Fault-tolerant checkpointing: atomic, keep-last-k, async, mesh-elastic.

Layout: ``<dir>/step_<N>/`` holding ``arrays.npz`` (leaf-path -> numpy) and
``manifest.json``.  Writes go to ``step_<N>.tmp`` then ``os.replace`` — a
crash mid-save never corrupts the latest checkpoint, and ``latest_step``
only ever sees fully-renamed directories (the restart path after a node
failure).  Both files (and the directory entries) are fsynced before the
rename, so the atomicity holds across power loss, not just process death.

Integrity: the manifest stores a crc32 per leaf (computed over the raw
row-major bytes).  ``load`` re-hashes every leaf it reads and refuses
silently-corrupted arrays; ``verify_step`` / ``latest_verifiable_step`` let
restart paths walk back past a torn or bit-flipped newest step to the most
recent checkpoint that still verifies (DESIGN.md §16).

Checkpoints are *mesh-free*: leaves are stored as full (unsharded) numpy
arrays keyed by their tree path, so a job can restart on a different device
count / mesh shape — ``load`` takes target shardings and ``device_put``s each
leaf accordingly (elastic scaling).  At real multi-pod scale the same layout
would be written shard-wise per host; the single-process container writes the
fused array (noted in DESIGN.md).
"""
from __future__ import annotations

import json
import os
import re
import shutil
import threading
import zipfile
import zlib
from typing import Any

import jax
import numpy as np

from .. import obs

_SEP = "/"


def _leaf_crc(arr: np.ndarray) -> int:
    """crc32 of the leaf's row-major bytes (dtype/shape live next to it in
    the manifest, so bytes alone pin the value)."""
    return zlib.crc32(np.ascontiguousarray(arr).tobytes()) & 0xFFFFFFFF


def _fsync_path(path: str) -> None:
    """fsync a file or directory by path (directory fsync commits the
    rename/creation of its entries on POSIX)."""
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _flatten(tree) -> dict[str, Any]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = _SEP.join(
            str(p.key) if hasattr(p, "key") else str(p.idx) if hasattr(p, "idx")
            else str(p.name) for p in path)
        flat[key] = leaf
    return flat


def save(ckpt_dir: str, step: int, tree, *, keep_last: int = 3,
         metadata: dict | None = None) -> str:
    """Atomic synchronous save; returns the final directory path.

    One ``ckpt.save`` span (``repro.obs``) over ``ckpt.sync`` (waiting for
    the tree's device arrays to be computed), ``ckpt.copy`` (device to
    host) and ``ckpt.write`` (everything on disk); the last two carry the
    tree's ``bytes``.
    """
    with obs.span("ckpt.save"):
        with obs.span("ckpt.sync"):
            jax.block_until_ready(tree)
        with obs.span("ckpt.copy") as copy:
            flat = {k: np.asarray(v) for k, v in _flatten(tree).items()}
            nbytes = sum(v.nbytes for v in flat.values())
            copy.set(bytes=nbytes)
        with obs.span("ckpt.write", bytes=nbytes):
            return _write(ckpt_dir, step, flat, keep_last, metadata)


def _write(ckpt_dir: str, step: int, flat: dict, keep_last: int,
           metadata: dict | None) -> str:
    os.makedirs(ckpt_dir, exist_ok=True)
    final = os.path.join(ckpt_dir, f"step_{step:08d}")
    tmp = final + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    arrays_path = os.path.join(tmp, "arrays.npz")
    np.savez(arrays_path, **flat)
    manifest = {
        "step": step,
        "leaves": {k: {"shape": list(v.shape), "dtype": str(v.dtype),
                       "crc32": _leaf_crc(v)}
                   for k, v in flat.items()},
        "metadata": metadata or {},
    }
    manifest_path = os.path.join(tmp, "manifest.json")
    with open(manifest_path, "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())
    # fsync file contents and the tmp dir entries BEFORE the rename, then
    # the parent dir AFTER — a power cut leaves either the old state or the
    # complete new one, never a renamed-but-empty directory.
    _fsync_path(arrays_path)
    _fsync_path(tmp)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.replace(tmp, final)
    _fsync_path(ckpt_dir)
    _cleanup(ckpt_dir, keep_last)
    return final


def save_async(ckpt_dir: str, step: int, tree, **kw) -> threading.Thread:
    """Snapshot to host memory now, write in a background thread."""
    host_tree = jax.tree.map(lambda x: np.asarray(x), tree)
    t = threading.Thread(target=save, args=(ckpt_dir, step, host_tree),
                         kwargs=kw, daemon=True)
    t.start()
    return t


def _cleanup(ckpt_dir: str, keep_last: int) -> None:
    steps = sorted(all_steps(ckpt_dir))
    for s in steps[:-keep_last] if keep_last else []:
        shutil.rmtree(os.path.join(ckpt_dir, f"step_{s:08d}"), ignore_errors=True)


def all_steps(ckpt_dir: str) -> list[int]:
    if not os.path.isdir(ckpt_dir):
        return []
    out = []
    for name in os.listdir(ckpt_dir):
        m = re.fullmatch(r"step_(\d+)", name)
        if m:
            out.append(int(m.group(1)))
    return sorted(out)


def latest_step(ckpt_dir: str) -> int | None:
    steps = all_steps(ckpt_dir)
    return steps[-1] if steps else None


def load_metadata(ckpt_dir: str, step: int) -> dict:
    """The ``metadata`` dict passed to ``save`` for this step.

    Consumers that resume from *inside* a logical unit of work store their
    cursor here — e.g. the streaming trainers save ``{"epoch", "next_chunk"}``
    so a mid-epoch restart replays the exact remaining chunk sequence.

    Raises ``ValueError`` (never a raw traceback type) when the step has no
    manifest or the manifest is corrupt — by the atomic-rename contract a
    fully-written checkpoint always has one, so either means the directory
    is not a checkpoint this library wrote.
    """
    path = os.path.join(ckpt_dir, f"step_{step:08d}", "manifest.json")
    try:
        with open(path) as f:
            return json.load(f).get("metadata", {})
    except FileNotFoundError:
        raise ValueError(
            f"{ckpt_dir}: step {step} has no manifest ({path} missing) — "
            "not a checkpoint written by repro.checkpoint") from None
    except json.JSONDecodeError as e:
        raise ValueError(
            f"{ckpt_dir}: step {step} manifest is corrupt ({e}) — "
            "the checkpoint directory was tampered with or truncated "
            "outside the atomic-rename path") from None


def load(ckpt_dir: str, step: int, target_tree, *, shardings=None):
    """Restore into the structure of ``target_tree``.

    ``shardings``: optional tree (matching target) of NamedSharding — leaves
    are device_put with them, enabling restore onto a different mesh than the
    one that saved (elastic restart).
    """
    path = os.path.join(ckpt_dir, f"step_{step:08d}", "arrays.npz")
    try:
        with np.load(path) as z:
            stored = {k: z[k] for k in z.files}
    except FileNotFoundError:
        raise ValueError(
            f"{ckpt_dir}: step {step} has no arrays.npz — not a complete "
            "checkpoint (atomic saves always write one)") from None
    except (ValueError, OSError, EOFError, zipfile.BadZipFile) as e:
        # truncated / corrupt zip
        raise ValueError(
            f"{ckpt_dir}: step {step} arrays.npz is unreadable ({e}) — "
            "truncated or corrupt tree") from None
    keys = list(_flatten(target_tree).keys())
    missing = [k for k in keys if k not in stored]
    if missing:
        raise ValueError(
            f"{ckpt_dir}: step {step} checkpoint is missing leaves "
            f"{missing[:5]} — truncated tree or a different state layout")
    _check_crcs(ckpt_dir, step, stored)
    leaves, treedef = jax.tree_util.tree_flatten(target_tree)
    flat_shardings = (jax.tree_util.tree_flatten(shardings)[0]
                      if shardings is not None else [None] * len(leaves))
    new_leaves = []
    for key, ref, shd in zip(keys, leaves, flat_shardings):
        arr = stored[key]
        if tuple(arr.shape) != tuple(ref.shape):
            raise ValueError(f"{key}: shape {arr.shape} != target {ref.shape}")
        arr = arr.astype(ref.dtype)
        new_leaves.append(jax.device_put(arr, shd) if shd is not None
                          else jax.numpy.asarray(arr))
    return jax.tree_util.tree_unflatten(treedef, new_leaves)


def _check_crcs(ckpt_dir: str, step: int, stored: dict[str, np.ndarray]
                ) -> None:
    """Verify stored leaves against the manifest's per-leaf crc32.

    Checkpoints written before checksums existed (no ``crc32`` key) pass
    unchecked — backward compatible.  A missing or corrupt manifest, or any
    crc mismatch, raises ``ValueError``.
    """
    path = os.path.join(ckpt_dir, f"step_{step:08d}", "manifest.json")
    try:
        with open(path) as f:
            leaves = json.load(f).get("leaves", {})
    except FileNotFoundError:
        raise ValueError(
            f"{ckpt_dir}: step {step} has no manifest ({path} missing) — "
            "not a checkpoint written by repro.checkpoint") from None
    except json.JSONDecodeError as e:
        raise ValueError(
            f"{ckpt_dir}: step {step} manifest is corrupt ({e})") from None
    for key, arr in stored.items():
        spec = leaves.get(key)
        if spec is None or "crc32" not in spec:
            continue   # pre-checksum checkpoint, or extra leaf — skip
        got = _leaf_crc(arr)
        if got != int(spec["crc32"]):
            raise ValueError(
                f"{ckpt_dir}: step {step} leaf {key!r} fails its checksum "
                f"(crc32 {got:#010x} != manifest {int(spec['crc32']):#010x})"
                " — silent corruption, refuse to restore")


def verify_step(ckpt_dir: str, step: int) -> None:
    """Full integrity check of one step: readable manifest, readable
    arrays.npz, every manifest leaf present with the recorded shape/dtype,
    and (when recorded) a matching crc32.  Raises ``ValueError`` naming the
    first problem; returns None when the step verifies."""
    step_dir = os.path.join(ckpt_dir, f"step_{step:08d}")
    path = os.path.join(step_dir, "manifest.json")
    try:
        with open(path) as f:
            leaves = json.load(f).get("leaves", {})
    except FileNotFoundError:
        raise ValueError(
            f"{ckpt_dir}: step {step} has no manifest — torn write") from None
    except json.JSONDecodeError as e:
        raise ValueError(
            f"{ckpt_dir}: step {step} manifest is corrupt ({e})") from None
    arrays = os.path.join(step_dir, "arrays.npz")
    try:
        with np.load(arrays) as z:
            stored = {k: z[k] for k in z.files}
    except FileNotFoundError:
        raise ValueError(
            f"{ckpt_dir}: step {step} has no arrays.npz — torn write"
        ) from None
    except (ValueError, OSError, EOFError, zipfile.BadZipFile) as e:
        raise ValueError(
            f"{ckpt_dir}: step {step} arrays.npz is unreadable ({e})"
        ) from None
    for key, spec in leaves.items():
        if key not in stored:
            raise ValueError(
                f"{ckpt_dir}: step {step} is missing leaf {key!r} — "
                "truncated tree")
        arr = stored[key]
        if list(arr.shape) != list(spec["shape"]):
            raise ValueError(
                f"{ckpt_dir}: step {step} leaf {key!r} shape "
                f"{list(arr.shape)} != manifest {spec['shape']}")
        if str(arr.dtype) != spec["dtype"]:
            raise ValueError(
                f"{ckpt_dir}: step {step} leaf {key!r} dtype {arr.dtype} "
                f"!= manifest {spec['dtype']}")
    _check_crcs(ckpt_dir, step, stored)


def latest_verifiable_step(ckpt_dir: str) -> int | None:
    """Newest step that passes ``verify_step``, walking back past torn or
    corrupt steps (a crash mid-save, or bit rot on the newest checkpoint,
    must not strand the restart path).  None when no step verifies."""
    for step in reversed(all_steps(ckpt_dir)):
        try:
            verify_step(ckpt_dir, step)
        except ValueError:
            continue
        return step
    return None


def restore_latest(ckpt_dir: str, target_tree, *, shardings=None):
    steps = all_steps(ckpt_dir)
    if not steps:
        return None, None
    step = latest_verifiable_step(ckpt_dir)
    if step is None:
        raise ValueError(
            f"{ckpt_dir}: checkpoint steps {steps} exist but none verify — "
            "refusing to restore from corrupt state")
    return step, load(ckpt_dir, step, target_tree, shardings=shardings)
