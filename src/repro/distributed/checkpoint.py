"""Sharded checkpointing: save/restore with resharding, async writes,
atomic commits, retention. The restart path of the fault-tolerance story
(distributed/fault.py) builds on restore-with-resharding: a checkpoint
written on one mesh restores onto any other mesh (elastic re-mesh).

Layout:
  <dir>/step_<N>.tmp/      while writing
  <dir>/step_<N>/          after atomic rename (os.replace)
      manifest.json        treedef, shapes, dtypes, step, wall time
      leaf_<i>.npy         one file per pytree leaf (device_get'ed)
"""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.spans import span


def _tree_paths(tree) -> List[str]:
    paths = []
    for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]:
        paths.append(jax.tree_util.keystr(path))
    return paths


def save_checkpoint(ckpt_dir: str, step: int, state: Any,
                    blocking: bool = True) -> str:
    """Write `state` (any pytree of arrays) atomically. Returns final path.

    blocking=False snapshots to host memory synchronously (cheap) and
    writes files on a daemon thread (compute continues) — the standard
    async-checkpoint pattern.
    """
    base = pathlib.Path(ckpt_dir)
    base.mkdir(parents=True, exist_ok=True)
    tmp = base / f"step_{step}.tmp"
    final = base / f"step_{step}"
    leaves, treedef = jax.tree.flatten(state)
    with span("store.fetch") as fetch_span:
        host_leaves = [np.asarray(jax.device_get(leaf)) for leaf in leaves]
        fetch_span.set_metadata(bytes=sum(leaf.nbytes for leaf in host_leaves))
    manifest = {
        "step": step,
        "time": time.time(),
        "paths": _tree_paths(state),
        "shapes": [list(leaf.shape) for leaf in host_leaves],
        "dtypes": [str(leaf.dtype) for leaf in host_leaves],
        "treedef": str(treedef),
    }

    def write():
        with span("store.write",
                  bytes=sum(leaf.nbytes for leaf in host_leaves)):
            if tmp.exists():
                shutil.rmtree(tmp)
            tmp.mkdir(parents=True)
            for i, leaf in enumerate(host_leaves):
                np.save(tmp / f"leaf_{i}.npy", leaf)
            (tmp / "manifest.json").write_text(json.dumps(manifest))
            if final.exists():
                shutil.rmtree(final)
            os.replace(tmp, final)

    if blocking:
        write()
    else:
        t = threading.Thread(target=write, daemon=True)
        t.start()
        _ASYNC_THREADS.append(t)
    return str(final)


_ASYNC_THREADS: List[threading.Thread] = []


def wait_for_async_saves():
    for t in _ASYNC_THREADS:
        t.join()
    _ASYNC_THREADS.clear()


def latest_step(ckpt_dir: str) -> Optional[int]:
    base = pathlib.Path(ckpt_dir)
    if not base.exists():
        return None
    steps = []
    for p in base.iterdir():
        if p.is_dir() and p.name.startswith("step_") and \
                not p.name.endswith(".tmp") and \
                (p / "manifest.json").exists():
            steps.append(int(p.name[5:]))
    return max(steps) if steps else None


def read_manifest(ckpt_dir: str, step: Optional[int] = None) -> Dict:
    """Manifest (paths/shapes/dtypes) of a checkpoint without loading leaves.

    Lets callers that only persisted a flat dict of arrays (e.g. the
    repro.serve FittedModel artifact) rebuild a `state_like` skeleton for
    restore_checkpoint from the checkpoint itself.
    """
    step = step if step is not None else latest_step(ckpt_dir)
    if step is None:
        raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
    path = pathlib.Path(ckpt_dir) / f"step_{step}"
    return json.loads((path / "manifest.json").read_text())


def restore_checkpoint(ckpt_dir: str, state_like: Any,
                       step: Optional[int] = None, mesh=None,
                       pspecs: Any = None) -> Tuple[Any, int]:
    """Restore into the structure of `state_like`.

    With (mesh, pspecs) the leaves are device_put with NamedShardings —
    this is how a checkpoint written on a 512-chip mesh restores onto a
    shrunken mesh after failures (elastic re-mesh).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    step = step if step is not None else latest_step(ckpt_dir)
    if step is None:
        raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
    path = pathlib.Path(ckpt_dir) / f"step_{step}"
    manifest = json.loads((path / "manifest.json").read_text())
    leaves_like, treedef = jax.tree.flatten(state_like)
    n = len(manifest["shapes"])
    if n != len(leaves_like):
        raise ValueError(f"checkpoint has {n} leaves, expected "
                         f"{len(leaves_like)}")
    out = []
    spec_leaves = (jax.tree.flatten(
        pspecs, is_leaf=lambda x: isinstance(x, P))[0]
        if pspecs is not None else [None] * n)
    for i, (like, spec) in enumerate(zip(leaves_like, spec_leaves)):
        arr = np.load(path / f"leaf_{i}.npy")
        if list(arr.shape) != list(like.shape):
            raise ValueError(f"leaf {i}: shape {arr.shape} != "
                             f"{like.shape}")
        a = jnp.asarray(arr, dtype=like.dtype)
        if mesh is not None and spec is not None:
            a = jax.device_put(a, NamedSharding(mesh, spec))
        out.append(a)
    return jax.tree.unflatten(treedef, out), step


class CheckpointManager:
    """Interval + retention policy around save/restore."""

    def __init__(self, ckpt_dir: str, save_every: int = 100,
                 keep: int = 3, async_saves: bool = True):
        self.dir = ckpt_dir
        self.save_every = save_every
        self.keep = keep
        self.async_saves = async_saves

    def maybe_save(self, step: int, state: Any) -> Optional[str]:
        if step % self.save_every:
            return None
        path = save_checkpoint(self.dir, step, state,
                               blocking=not self.async_saves)
        self._gc()
        return path

    def _gc(self):
        base = pathlib.Path(self.dir)
        steps = sorted(int(p.name[5:]) for p in base.iterdir()
                       if p.is_dir() and p.name.startswith("step_")
                       and not p.name.endswith(".tmp"))
        for s in steps[:-self.keep]:
            shutil.rmtree(base / f"step_{s}", ignore_errors=True)

    def restore_latest(self, state_like, mesh=None, pspecs=None):
        return restore_checkpoint(self.dir, state_like, mesh=mesh,
                                  pspecs=pspecs)
