"""Fault-tolerant checkpointing: atomic, async, elastic.

Layout: <dir>/step_<k>/  one .npy per leaf (path-keyed) + manifest.json.
  - ATOMIC: written into step_<k>.tmp then os.replace'd — a crash mid-save
    never corrupts the latest checkpoint;
  - ASYNC: `save(..., background=True)` snapshots to host memory and writes
    from a thread, keeping serialization off the training critical path
    (straggler mitigation for slow filesystems);
  - ELASTIC: restore() takes target shardings — a checkpoint written under
    one mesh restores under any other mesh/device count (each host reads
    the full leaf and device_put's its shard; at real multi-host scale the
    same manifest supports slice reads via np.load(mmap_mode)).
"""
from __future__ import annotations

import json
import os
import shutil
import threading
from typing import Any, Optional

import jax
import numpy as np

_SEP = "::"


def _flatten(tree):
    flat, treedef = jax.tree.flatten_with_path(tree)
    items = {}
    for path, leaf in flat:
        key = _SEP.join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        items[key] = leaf
    return items, treedef


def latest_step(ckpt_dir: str) -> Optional[int]:
    if not os.path.isdir(ckpt_dir):
        return None
    steps = [int(d.split("_")[1]) for d in os.listdir(ckpt_dir)
             if d.startswith("step_") and not d.endswith(".tmp")]
    return max(steps) if steps else None


def _write(ckpt_dir: str, step: int, host_items: dict, meta: dict,
           keep_last: int):
    final = os.path.join(ckpt_dir, f"step_{step}")
    tmp = final + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp, exist_ok=True)
    manifest = {"step": step, "meta": meta, "leaves": {}}
    # Leaf files are numbered, not hash-named: `hash(str)` is salted per
    # process (PYTHONHASHSEED) and 32-bit-truncated hashes can collide,
    # silently aliasing two leaves. Restore resolves names through the
    # manifest, so old hash-named checkpoints keep loading.
    for i, (key, arr) in enumerate(host_items.items()):
        fname = f"{i:05d}.npy"
        np.save(os.path.join(tmp, fname), arr)
        manifest["leaves"][key] = {
            "file": fname, "shape": list(arr.shape), "dtype": str(arr.dtype)}
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.replace(tmp, final)
    # GC old checkpoints
    steps = sorted(int(d.split("_")[1]) for d in os.listdir(ckpt_dir)
                   if d.startswith("step_") and not d.endswith(".tmp"))
    for s in steps[:-keep_last]:
        shutil.rmtree(os.path.join(ckpt_dir, f"step_{s}"), ignore_errors=True)


class Checkpointer:
    def __init__(self, ckpt_dir: str, keep_last: int = 3):
        self.dir = ckpt_dir
        self.keep_last = keep_last
        self._thread: Optional[threading.Thread] = None
        os.makedirs(ckpt_dir, exist_ok=True)

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def save(self, step: int, tree: Any, meta: Optional[dict] = None,
             background: bool = True):
        self.wait()  # at most one in-flight save
        items, _ = _flatten(tree)
        # Snapshot to host memory synchronously (cheap), write async.
        host_items = {}
        for k, v in items.items():
            if hasattr(v, "dtype") and v.dtype == jax.numpy.bfloat16:
                host_items[k] = np.asarray(v.astype(jax.numpy.float32))
                host_items[k] = host_items[k].astype("float32")
            else:
                host_items[k] = np.asarray(v)
        args = (self.dir, step, host_items, meta or {}, self.keep_last)
        if background:
            self._thread = threading.Thread(target=_write, args=args,
                                            daemon=True)
            self._thread.start()
        else:
            _write(*args)

    def restore(self, like: Any, step: Optional[int] = None,
                shardings: Any = None):
        """Restore into the structure (and dtypes) of `like`.

        `shardings` (optional, same tree structure) resharding onto any
        mesh — elastic restart across device counts."""
        step = step if step is not None else latest_step(self.dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {self.dir}")
        d = os.path.join(self.dir, f"step_{step}")
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)
        items, treedef = _flatten(like)
        shard_items = (_flatten(shardings)[0] if shardings is not None
                       else {k: None for k in items})
        out = {}
        for key, ref in items.items():
            entry = manifest["leaves"][key]
            arr = np.load(os.path.join(d, entry["file"]))
            dtype = getattr(ref, "dtype", arr.dtype)
            arr = arr.astype(dtype)
            sh = shard_items.get(key)
            out[key] = (jax.device_put(arr, sh) if sh is not None
                        else jax.numpy.asarray(arr))
        leaves = [out[k] for k in items.keys()]
        return jax.tree.unflatten(treedef, leaves), step, manifest["meta"]

    def maybe_restore(self, like: Any, step: Optional[int] = None,
                      shardings: Any = None):
        """`restore`, but None instead of raising when no checkpoint
        exists — the resume-or-start idiom of long-running MD drivers:

            got = ckpt.maybe_restore(sim.state._asdict())
            if got is not None: ...
        """
        if (step if step is not None else latest_step(self.dir)) is None:
            return None
        return self.restore(like, step=step, shardings=shardings)
