"""Step-atomic sharded checkpointing with elastic restore.

Layout (one directory per step, atomically renamed into place):

    <root>/step_000120/
        manifest.json      # tree structure, shapes, dtypes, step, wall time
        leaf_00000.npy ...# one file per pytree leaf (bf16 stored as u16)

Guarantees exercised by tests:
  * atomicity: a crash mid-save never corrupts the latest checkpoint
    (tmp dir + os.replace);
  * restart: restore() returns a state tree identical to what was saved;
  * elasticity: restore(sharding=...) re-lays the arrays out on a
    *different* mesh than the one that saved them (full-array files are
    mesh-agnostic; per-shard streaming is the documented scale-up path);
  * retention: keep_last_k garbage-collects old steps, never the newest.
"""
from __future__ import annotations

import json
import os
import shutil
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


def _path_str(path) -> str:
    out = []
    for k in path:
        out.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/".join(out)


class CheckpointManager:
    def __init__(self, root: str, keep_last_k: int = 3):
        self.root = root
        self.keep = keep_last_k
        os.makedirs(root, exist_ok=True)

    # ------------------------------------------------------------- save
    def save(self, step: int, state: Any) -> str:
        leaves, treedef = jax.tree.flatten(state)
        paths = [_path_str(p) for p, _ in jax.tree.flatten_with_path(state)[0]]
        tmp = os.path.join(self.root, f".tmp_step_{step:06d}_{os.getpid()}")
        final = os.path.join(self.root, f"step_{step:06d}")
        os.makedirs(tmp, exist_ok=True)
        manifest: Dict[str, Any] = {
            "step": step, "time": time.time(), "leaves": []}
        for i, (leaf, path) in enumerate(zip(leaves, paths)):
            arr = np.asarray(jax.device_get(leaf))
            dtype = str(arr.dtype)
            if dtype == "bfloat16":
                arr = arr.view(np.uint16)
            fname = f"leaf_{i:05d}.npy"
            np.save(os.path.join(tmp, fname), arr, allow_pickle=False)
            manifest["leaves"].append(
                {"path": path, "file": fname, "dtype": dtype,
                 "shape": list(arr.shape)})
        manifest["treedef"] = jax.tree_util.tree_structure(state).__repr__()
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.replace(tmp, final)          # atomic publish
        self._gc()
        return final

    # ---------------------------------------------------------- restore
    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def all_steps(self) -> List[int]:
        out = []
        for d in os.listdir(self.root):
            if d.startswith("step_") and os.path.exists(
                    os.path.join(self.root, d, "manifest.json")):
                out.append(int(d.split("_")[1]))
        return sorted(out)

    def restore(self, like: Any, step: Optional[int] = None,
                sharding: Any = None) -> Any:
        """Restore into the structure of ``like``.

        ``sharding``: optional pytree (matching ``like``) of NamedShardings —
        pass shardings built on the *current* mesh for elastic restore.
        """
        step = self.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {self.root}")
        d = os.path.join(self.root, f"step_{step:06d}")
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)
        leaves_like, treedef = jax.tree.flatten(like)
        if len(manifest["leaves"]) != len(leaves_like):
            raise ValueError("checkpoint/like structure mismatch: "
                             f"{len(manifest['leaves'])} vs {len(leaves_like)}")
        shard_leaves = (jax.tree.leaves(sharding) if sharding is not None
                        else [None] * len(leaves_like))
        out = []
        for rec, leaf_like, sh in zip(manifest["leaves"], leaves_like,
                                      shard_leaves):
            arr = np.load(os.path.join(d, rec["file"]), allow_pickle=False)
            if rec["dtype"] == "bfloat16":
                arr = arr.view(jnp.bfloat16.dtype)
            if sh is not None:
                out.append(jax.device_put(arr, sh))
            else:
                out.append(jnp.asarray(arr))
        return jax.tree.unflatten(treedef, out)

    # --------------------------------------------------------------- gc
    def _gc(self):
        steps = self.all_steps()
        for s in steps[:-self.keep] if self.keep else []:
            shutil.rmtree(os.path.join(self.root, f"step_{s:06d}"),
                          ignore_errors=True)
