"""Sharded, asynchronous, atomic checkpointing (fault-tolerance substrate).

Layout (one directory per step):

    <root>/step_000123/
        manifest.json        # treedef, shapes, dtypes, step, mesh, config
        leaf_00000.npy ...   # one file per pytree leaf (full array)
    <root>/step_000123.COMMITTED   # atomic commit marker (written last)

Design points for 1000+ node deployments (documented in DESIGN.md §5):
  * **atomic commit**: readers only consume directories with a COMMITTED
    marker, so a preempted writer never corrupts the restore path;
  * **async save**: the host thread snapshots device arrays (device_get) and
    hands serialisation to a background thread — the training loop resumes
    immediately after the snapshot;
  * **restore with resharding**: arrays are loaded and device_put against
    the *current* mesh's NamedShardings, so a 512-chip checkpoint restores
    onto a 256-chip elastic fallback mesh unchanged (shard shapes are
    re-derived from the specs, not stored);
  * on multi-controller deployments each host writes only the leaves it
    owns (``process_index`` filter); in this single-process container that
    set is all leaves.
"""

from __future__ import annotations

import json
import os
import queue
import shutil
import threading
from typing import Any, Optional

import jax
import numpy as np

PyTree = Any

_COMMIT_SUFFIX = ".COMMITTED"


def _step_dir(root: str, step: int) -> str:
    return os.path.join(root, f"step_{step:09d}")


class Checkpointer:
    def __init__(self, root: str, keep: int = 3, async_save: bool = True):
        self.root = root
        self.keep = keep
        self.async_save = async_save
        os.makedirs(root, exist_ok=True)
        self._q: "queue.Queue" = queue.Queue()
        self._worker: Optional[threading.Thread] = None
        self._last_error: Optional[BaseException] = None
        if async_save:
            self._worker = threading.Thread(target=self._drain, daemon=True)
            self._worker.start()

    # -- write path ---------------------------------------------------------

    def save(self, step: int, tree: PyTree, extra: Optional[dict] = None,
             timestamp: Optional[float] = None):
        """Snapshot + (a)synchronously persist. Returns after the snapshot:
        device buffers may be donated/overwritten immediately.

        ``timestamp`` is caller-injected wall time for the manifest's
        ``time`` field; the default ``None`` omits the field entirely, so
        identical trees produce bytes-identical checkpoints (the manifest
        is part of the repo's determinism contract — see DET002 in
        docs/static-analysis.md)."""
        host_tree = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)
        if self.async_save:
            self._q.put((step, host_tree, extra or {}, timestamp))
        else:
            self._write(step, host_tree, extra or {}, timestamp)

    def wait(self):
        """Block until all queued saves are durable (tests / shutdown)."""
        self._q.join()
        if self._last_error:
            raise self._last_error

    def _drain(self):
        while True:
            step, tree, extra, timestamp = self._q.get()
            try:
                self._write(step, tree, extra, timestamp)
            except BaseException as e:  # surfaced on wait()
                self._last_error = e
            finally:
                self._q.task_done()

    def _write(self, step: int, host_tree: PyTree, extra: dict,
               timestamp: Optional[float] = None):
        d = _step_dir(self.root, step)
        tmp = d + ".tmp"
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        leaves, treedef = jax.tree.flatten(host_tree)
        manifest = {
            "step": step,
            "treedef": jax.tree_util.tree_structure(host_tree).serialize_using_proto().hex(),
            "num_leaves": len(leaves),
            "shapes": [list(l.shape) for l in leaves],
            "dtypes": [str(l.dtype) for l in leaves],
            "extra": extra,
        }
        if timestamp is not None:
            manifest["time"] = float(timestamp)
        for i, leaf in enumerate(leaves):
            np.save(os.path.join(tmp, f"leaf_{i:05d}.npy"), leaf,
                    allow_pickle=False)
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        if os.path.exists(d):
            shutil.rmtree(d)
        os.rename(tmp, d)
        with open(d + _COMMIT_SUFFIX, "w") as f:
            f.write(str(step))
        self._gc()

    def _gc(self):
        steps = self.committed_steps()
        for s in steps[: -self.keep] if self.keep else []:
            shutil.rmtree(_step_dir(self.root, s), ignore_errors=True)
            try:
                os.remove(_step_dir(self.root, s) + _COMMIT_SUFFIX)
            except FileNotFoundError:
                pass

    # -- read path -----------------------------------------------------------

    def committed_steps(self) -> "list[int]":
        out = []
        for name in os.listdir(self.root):
            if name.endswith(_COMMIT_SUFFIX):
                out.append(int(name[len("step_"):-len(_COMMIT_SUFFIX)]))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.committed_steps()
        return steps[-1] if steps else None

    def restore(self, step: Optional[int] = None, shardings: PyTree = None,
                template: PyTree = None) -> "tuple[int, PyTree, dict]":
        """Load a committed checkpoint.

        Args:
          step: specific step (default: latest committed).
          shardings: optional NamedSharding tree — arrays are device_put
            against it (resharding onto the current mesh).
          template: pytree with the expected structure (required); the
            loaded leaves are unflattened into it.
        Returns (step, tree, extra).
        """
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no committed checkpoints under {self.root}")
        d = _step_dir(self.root, step)
        if not os.path.exists(d + _COMMIT_SUFFIX):
            raise FileNotFoundError(f"checkpoint step {step} not committed")
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)
        if template is None:
            raise ValueError("restore requires a template pytree")
        leaves = [
            np.load(os.path.join(d, f"leaf_{i:05d}.npy"))
            for i in range(manifest["num_leaves"])
        ]
        _, expect_def = jax.tree.flatten(template)
        tree = jax.tree.unflatten(expect_def, leaves)
        if shardings is not None:
            tree = jax.tree.map(
                lambda x, s: jax.device_put(x, s), tree, shardings)
        return step, tree, manifest.get("extra", {})
