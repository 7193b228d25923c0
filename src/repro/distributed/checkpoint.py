"""Sharded, versioned, atomic checkpoints with async write + retention.

Layout:   <dir>/step_<n>/manifest.json + arrays.npz       (committed)
          <dir>/step_<n>.tmp.<pid>/...                    (in flight)

* **Atomic commit**: everything is written into a tmp dir, fsync'd, then
  os.rename'd — a crash never leaves a half-readable step visible.
* **Async**: ``save_async`` snapshots to host memory (device_get) on the
  caller thread — the cheap part — and runs serialization on a background
  thread so the train loop is not blocked by disk.
* **Elastic restore**: the manifest stores *logical axes* per leaf, not
  device assignments; ``restore`` re-resolves shardings against whatever
  mesh is active (a checkpoint written on (2,16,16) restores onto (16,16)
  or (8,16) — tested in tests/test_checkpoint.py).
* **Retention**: keep the most recent ``keep`` steps, delete older.

Data cursor convention: train loops store {"step": int} metadata; the data
pipeline (repro.data.lm) is stateless given the step, so restore resumes
the exact stream position.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.observe.spans import bind as _bind, span as _span

SEP = "/"


def _flatten(tree) -> dict[str, Any]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = SEP.join(_path_str(p) for p in path)
        flat[key] = leaf
    return flat


def _path_str(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return str(p.idx)
    if hasattr(p, "name"):
        return str(p.name)
    return str(p)


def _unflatten_into(template, flat: dict[str, Any]):
    paths = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for path, tmpl in paths[0]:
        key = SEP.join(_path_str(p) for p in path)
        if key not in flat:
            raise KeyError(f"checkpoint missing leaf {key!r}")
        leaves.append(flat[key])
    return jax.tree_util.tree_unflatten(paths[1], leaves)


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3, faults=None):
        self.dir = directory
        self.keep = keep
        # fault-injection hook (repro.distributed.faults.FaultPlan): fires
        # the "checkpoint.pre_rename" site inside the crash window — after
        # the fsync'd temp write, before the atomic rename
        self.faults = faults
        os.makedirs(directory, exist_ok=True)
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    # -- write ---------------------------------------------------------------

    def save(self, step: int, tree, metadata: Optional[dict] = None) -> str:
        """Synchronous checkpoint."""
        host = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)
        return self._write(step, host, metadata or {})

    def save_async(self, step: int, tree, metadata: Optional[dict] = None):
        """Snapshot now, serialize in the background."""
        self.wait()                      # one in flight at a time
        host = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)
        md = dict(metadata or {})

        def run():
            try:
                self._write(step, host, md)
            except BaseException as e:     # pragma: no cover
                self._error = e

        self._thread = threading.Thread(target=_bind(run), daemon=True)
        self._thread.start()

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def _write(self, step: int, host_tree, metadata: dict) -> str:
        with _span("checkpoint.commit", step=step):
            return self._write_inner(step, host_tree, metadata)

    def _write_inner(self, step: int, host_tree, metadata: dict) -> str:
        final = os.path.join(self.dir, f"step_{step:010d}")
        tmp = final + f".tmp.{os.getpid()}"
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        flat = _flatten(host_tree)
        # numpy cannot natively persist ml_dtypes (bfloat16 etc.); store a
        # same-width unsigned view and record the true dtype in the manifest
        savable = {}
        dtypes = {}
        for k, v in flat.items():
            arr = np.asarray(v)
            dtypes[k] = str(arr.dtype)
            if arr.dtype.kind not in "fiub" or str(arr.dtype) == "bfloat16":
                arr = arr.view(_uint_of_width(arr.dtype.itemsize))
            savable[k] = arr
        np.savez(os.path.join(tmp, "arrays.npz"), **savable)
        manifest = {
            "step": step,
            "metadata": metadata,
            "leaves": {k: {"shape": list(np.shape(v)),
                           "dtype": dtypes[k]}
                       for k, v in flat.items()},
            "format": 1,
        }
        mpath = os.path.join(tmp, "manifest.json")
        with open(mpath, "w") as f:
            json.dump(manifest, f)
            f.flush()
            os.fsync(f.fileno())
        if self.faults is not None:
            # the crash window: a kill here leaves an orphaned tmp dir and
            # must NOT disturb the previously committed step
            self.faults.site("checkpoint.pre_rename", step=step)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)
        self._gc()
        return final

    def _gc(self):
        steps = self.all_steps()
        for s in steps[:-self.keep] if self.keep > 0 else []:
            shutil.rmtree(os.path.join(self.dir, f"step_{s:010d}"),
                          ignore_errors=True)
        # orphaned in-flight dirs left by a writer killed inside the crash
        # window. Safe under the manager's one-write-in-flight discipline
        # (_gc only runs after our own rename committed, so any tmp dir
        # still present belongs to a dead writer); concurrent unmanaged
        # writers to the same directory are not supported.
        for name in os.listdir(self.dir):
            if name.startswith("step_") and ".tmp." in name:
                shutil.rmtree(os.path.join(self.dir, name),
                              ignore_errors=True)

    # -- read ----------------------------------------------------------------

    def all_steps(self) -> list[int]:
        out = []
        for name in os.listdir(self.dir):
            if name.startswith("step_") and ".tmp." not in name:
                try:
                    out.append(int(name[5:]))
                except ValueError:
                    pass
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def metadata(self, step: Optional[int] = None) -> dict:
        step = self.latest_step() if step is None else step
        path = os.path.join(self.dir, f"step_{step:010d}", "manifest.json")
        with open(path) as f:
            return json.load(f)

    def restore(self, template, step: Optional[int] = None,
                shardings=None):
        """Restore into the structure of ``template`` (arrays or
        ShapeDtypeStructs). If ``shardings`` (matching pytree of
        NamedShardings) is given, leaves are device_put accordingly —
        this is the elastic-resharding path: the mesh inside the
        shardings can differ from the mesh at save time.
        """
        step = self.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {self.dir}")
        d = os.path.join(self.dir, f"step_{step:010d}")
        manifest = self.metadata(step)
        with np.load(os.path.join(d, "arrays.npz")) as z:
            flat = {}
            for k in z.files:
                arr = z[k]
                true_dtype = manifest["leaves"][k]["dtype"]
                if str(arr.dtype) != true_dtype:
                    import ml_dtypes
                    arr = arr.view(np.dtype(getattr(
                        ml_dtypes, true_dtype, true_dtype)))
                flat[k] = arr
        tree = _unflatten_into(template, flat)
        # dtype-cast to the template's dtypes (bf16 is stored as its view)
        def cast(t, x):
            want = t.dtype if hasattr(t, "dtype") else None
            arr = jnp.asarray(x)
            return arr.astype(want) if want is not None else arr
        tree = jax.tree.map(cast, template, tree)
        if shardings is not None:
            tree = jax.tree.map(
                lambda x, s: jax.device_put(x, s), tree, shardings)
        return tree


def _uint_of_width(itemsize: int):
    return {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}[itemsize]
