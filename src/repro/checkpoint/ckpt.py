"""Checkpointing: atomic, digest-verified, async-capable npz shards.

Layout:  <dir>/step_<N>/host<h>.npz  +  <dir>/step_<N>/MANIFEST.json
Writes go to ``.tmp-`` paths first and are renamed only after fsync — a
killed writer never corrupts the latest checkpoint (restart reads the newest
*complete* manifest). ``CheckpointManager`` keeps the last ``keep`` steps and
can overlap saves with training via a writer thread (async=True).

Restore supports **elastic topology change**: a D-PSGD state saved with
n_nodes=N can be restored onto M != N nodes (`reshape_nodes`): surviving
node rows are kept, new rows are filled by the node-axis mean — the natural
D-PSGD warm start after failure/scale events (runtime.fault re-solves W).
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import threading
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

__all__ = ["save", "restore", "CheckpointManager", "reshape_nodes",
           "compact_nodes", "expand_nodes"]


def _flatten(state: PyTree) -> tuple[list[np.ndarray], Any]:
    leaves, treedef = jax.tree.flatten(state)
    return [np.asarray(l) for l in leaves], treedef


def save(directory: str, step: int, state: PyTree, host: int = 0) -> str:
    """Atomic save; returns the checkpoint path."""
    leaves, _ = _flatten(state)
    step_dir = os.path.join(directory, f"step_{step:08d}")
    os.makedirs(step_dir, exist_ok=True)
    tmp = os.path.join(step_dir, f".tmp-host{host}.npz")
    final = os.path.join(step_dir, f"host{host}.npz")
    arrays = {f"leaf_{i}": l for i, l in enumerate(leaves)}
    with open(tmp, "wb") as f:
        np.savez(f, **arrays)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, final)

    digest = hashlib.sha256()
    for l in leaves:
        digest.update(np.ascontiguousarray(l).tobytes()[:4096])
    manifest = {"step": step, "n_leaves": len(leaves),
                "digest": digest.hexdigest(),
                "shapes": [list(l.shape) for l in leaves],
                "dtypes": [str(l.dtype) for l in leaves]}
    mtmp = os.path.join(step_dir, ".tmp-MANIFEST.json")
    with open(mtmp, "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(mtmp, os.path.join(step_dir, "MANIFEST.json"))
    return step_dir


def latest_step(directory: str) -> Optional[int]:
    if not os.path.isdir(directory):
        return None
    steps = []
    for name in os.listdir(directory):
        if name.startswith("step_") and os.path.exists(
                os.path.join(directory, name, "MANIFEST.json")):
            steps.append(int(name.split("_")[1]))
    return max(steps) if steps else None


def restore(directory: str, like: PyTree, step: Optional[int] = None,
            host: int = 0) -> tuple[PyTree, int]:
    """Restore into the structure of ``like``; returns (state, step)."""
    step = latest_step(directory) if step is None else step
    if step is None:
        raise FileNotFoundError(f"no complete checkpoint under {directory}")
    step_dir = os.path.join(directory, f"step_{step:08d}")
    with open(os.path.join(step_dir, "MANIFEST.json")) as f:
        manifest = json.load(f)
    data = np.load(os.path.join(step_dir, f"host{host}.npz"))
    leaves_like, treedef = jax.tree.flatten(like)
    if manifest["n_leaves"] != len(leaves_like):
        raise ValueError(
            f"checkpoint has {manifest['n_leaves']} leaves, expected {len(leaves_like)}")
    leaves = [jnp.asarray(data[f"leaf_{i}"]) for i in range(len(leaves_like))]
    digest = hashlib.sha256()
    for l in leaves:
        digest.update(np.ascontiguousarray(np.asarray(l)).tobytes()[:4096])
    if digest.hexdigest() != manifest["digest"]:
        raise ValueError(f"checkpoint digest mismatch at step {step}")
    return jax.tree.unflatten(treedef, leaves), step


def _node_width(state: PyTree, what: str) -> int:
    """Shared leading node-axis width of the non-scalar leaves (scalar
    leaves — step counters and the like — carry no node axis and pass
    through every elastic transform untouched). Pytree-general: any leaf
    structure works as long as the node axis leads. Raises on disagreeing
    leading dims; returns 0 when every leaf is scalar."""
    from ..core.dpsgd import node_axis_size
    return node_axis_size(state, what, allow_scalar=True)


def reshape_nodes(state: PyTree, survivors: list[int], n_new: int) -> PyTree:
    """Elastic restore: keep surviving node rows, fill the rest with the
    survivor mean (leading axis = node axis on every leaf of params/opt)."""
    width = _node_width(state, "reshape_nodes state")
    surv = np.asarray(survivors, dtype=np.int64)
    if width and surv.size and int(surv.max()) >= width:
        raise ValueError(
            f"survivor index {int(surv.max())} out of range for the state's "
            f"node axis of {width}")

    def fix(leaf):
        if leaf.ndim == 0:
            return leaf
        kept = leaf[np.asarray(survivors)]
        if n_new <= kept.shape[0]:
            return kept[:n_new]
        # compute the warm-start mean on host: XLA's on-device reduction can
        # drift ~20 float32 ulps from numpy's pairwise sum on near-cancelling
        # rows, which breaks bit-for-bit agreement across hosts replaying the
        # same elastic event
        kept_np = np.asarray(kept)
        fill = jnp.asarray(kept_np.mean(axis=0, keepdims=True)
                           .astype(kept_np.dtype))
        extra = jnp.broadcast_to(fill, (n_new - kept.shape[0], *kept.shape[1:]))
        return jnp.concatenate([kept, extra], axis=0)
    return jax.tree.map(fix, state)


def compact_nodes(state: PyTree, live: np.ndarray) -> PyTree:
    """Masked fixed-width state -> compacted state: keep live node rows, in
    original-id order. The inverse (for live rows) of ``expand_nodes``; used
    to checkpoint or hand off the result of the masked scan path
    (``sim.batch``) in the same layout the per-round driver produces.
    Pytree-general: any leaf structure (flat CNN arrays, nested transformer
    blocks) compacts the same way — the only contract is the leading node
    axis, validated against ``live``'s width so a ragged or transposed
    state fails loudly instead of gathering the wrong axis."""
    live = np.asarray(live, dtype=bool)
    width = _node_width(state, "compact_nodes state")
    if width and width != live.size:
        raise ValueError(
            f"state node axis is {width} but live mask has {live.size} "
            "entries")
    idx = np.flatnonzero(live)
    if idx.size == width:
        # every node live: a gather would only copy (and, on a mesh, may
        # gather the sharded node axis onto every device)
        return state
    return jax.tree.map(
        lambda leaf: leaf if leaf.ndim == 0 else leaf[idx], state)


def expand_nodes(state: PyTree, survivors: list[int], n_total: int) -> PyTree:
    """Compacted state -> masked fixed-width state: scatter node row ``k`` to
    row ``survivors[k]`` of an ``n_total``-wide state; the remaining (dead)
    rows are filled with the survivor mean, matching the ``reshape_nodes``
    warm start (host-side mean for bit-identical replay across hosts). Dead
    rows are inert under ``dpsgd_masked_step`` — the fill only matters if a
    node is later revived. Pytree-general with the same validated
    node-axis contract as ``compact_nodes``."""
    survivors = np.asarray(survivors, dtype=np.int64)
    width = _node_width(state, "expand_nodes state")
    if width and width != survivors.size:
        raise ValueError(
            f"compacted state node axis is {width} but {survivors.size} "
            "survivor slots were given")
    if survivors.size and int(survivors.max()) >= n_total:
        raise ValueError(
            f"survivor index {int(survivors.max())} out of range for "
            f"n_total={n_total}")

    def fix(leaf):
        if leaf.ndim == 0:
            return leaf
        leaf_np = np.asarray(leaf)
        out = np.empty((n_total, *leaf_np.shape[1:]), dtype=leaf_np.dtype)
        out[:] = leaf_np.mean(axis=0, keepdims=True).astype(leaf_np.dtype)
        out[survivors] = leaf_np
        return jnp.asarray(out)

    return jax.tree.map(fix, state)


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3, async_save: bool = True):
        self.directory = directory
        self.keep = keep
        self.async_save = async_save
        self._thread: Optional[threading.Thread] = None

    def save(self, step: int, state: PyTree, host: int = 0):
        state = jax.tree.map(np.asarray, state)  # snapshot off-device
        if self._thread is not None:
            self._thread.join()

        def _do():
            save(self.directory, step, state, host)
            self._gc()

        if self.async_save:
            self._thread = threading.Thread(target=_do, daemon=True)
            self._thread.start()
        else:
            _do()

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def restore_latest(self, like: PyTree, host: int = 0):
        return restore(self.directory, like, host=host)

    def _gc(self):
        if not os.path.isdir(self.directory):
            return
        steps = sorted(
            int(n.split("_")[1]) for n in os.listdir(self.directory)
            if n.startswith("step_") and os.path.exists(
                os.path.join(self.directory, n, "MANIFEST.json")))
        for s in steps[: -self.keep]:
            shutil.rmtree(os.path.join(self.directory, f"step_{s:08d}"),
                          ignore_errors=True)
