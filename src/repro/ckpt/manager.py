"""Checkpointing over BuffetFS: sharded, async, atomic, elastic.

Layout per step:

    /ckpt/<run>/step_00000100/part_000/<leaf-path>.npy   (many smallish files)
    /ckpt/<run>/step_00000100/MANIFEST                   (written LAST)

Semantics:

* **Atomic commit** — readers only trust steps whose MANIFEST exists and
  whose checksums verify; MANIFEST is written after every shard file, so a
  crashed save is simply invisible (no torn checkpoints).
* **Async save** — `save(..., block=False)` snapshots arrays to host memory
  and writes on a background thread: the train step never waits on
  durability (the BuffetFS deferral insight applied to checkpoints).
* **Elastic restore** — arrays are split over `parts` along axis 0 at save
  time; restore reassembles regardless of the current world size, so a job
  can restart on a different host count (elastic scaling) and re-shard via
  its own `device_put`.
* **Fault tolerance** — shard files carry crc32s recorded in the manifest;
  `restore` verifies them, and `latest_step` skips uncommitted/corrupt steps.
"""
from __future__ import annotations

import io
import json
import threading
import zlib
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import ml_dtypes  # registers bfloat16/f8 numpy dtypes (np.dtype("bfloat16"))
import numpy as np

from .. import obs
from ..core.blib import BLib

try:  # tree utilities without requiring jax at import time for pure-data users
    import jax
    _tree_flatten = lambda t: jax.tree_util.tree_flatten_with_path(t)
    _keystr = lambda kp: jax.tree_util.keystr(kp)
except Exception:  # pragma: no cover
    jax = None


def _leaf_name(keypath) -> str:
    s = _keystr(keypath)
    return s.replace("/", "_").replace("'", "").replace("[", ".").replace("]", "") \
            .replace(" ", "").strip(".")


@dataclass
class Manifest:
    step: int
    parts: int
    leaves: List[Dict[str, Any]]  # {name, shape, dtype, files: [{path, crc}]}
    extra: Dict[str, Any] = field(default_factory=dict)

    def to_bytes(self) -> bytes:
        return json.dumps({"step": self.step, "parts": self.parts,
                           "leaves": self.leaves, "extra": self.extra}).encode()

    @staticmethod
    def from_bytes(b: bytes) -> "Manifest":
        d = json.loads(b.decode())
        return Manifest(**d)


class CheckpointManager:
    def __init__(self, lib: BLib, run: str = "run0", *, base: str = "/ckpt",
                 parts: int = 4, keep_last: int = 3) -> None:
        self.lib = lib
        self.base = f"{base}/{run}"
        self.parts = parts
        self.keep_last = keep_last
        self.lib.makedirs(self.base)
        self._inflight: Optional[threading.Thread] = None
        self._save_lock = threading.Lock()

    # ------------------------------------------------------------------
    def _step_dir(self, step: int) -> str:
        return f"{self.base}/step_{step:08d}"

    @staticmethod
    def _np_bytes(arr: np.ndarray) -> bytes:
        buf = io.BytesIO()
        np.save(buf, arr, allow_pickle=False)
        return buf.getvalue()

    def _write_tree(self, step: int, tree: Any, extra: Dict[str, Any],
                    cause: Optional[int] = None) -> None:
        with obs.span("ckpt.write", cause=cause, step=step):
            sdir = self._step_dir(step)
            self.lib.makedirs(sdir)
            flat, _ = _tree_flatten(tree)
            leaves_meta: List[Dict[str, Any]] = []
            for kp, leaf in flat:
                arr = np.asarray(leaf)
                name = _leaf_name(kp)
                nparts = (self.parts if arr.ndim > 0 and arr.shape[0] >= self.parts
                          else 1)
                chunks = (np.array_split(arr, nparts, axis=0) if nparts > 1
                          else [arr])
                files = []
                for pi, chunk in enumerate(chunks):
                    pdir = f"{sdir}/part_{pi:03d}"
                    self.lib.makedirs(pdir)
                    path = f"{pdir}/{name}.npy"
                    with obs.span("ckpt.serialize"):
                        blob = self._np_bytes(chunk)
                    self.lib.write_file(path, blob)
                    with obs.span("ckpt.crc"):
                        crc = zlib.crc32(blob)
                    files.append({"path": path, "crc": crc})
                leaves_meta.append({"name": name, "shape": list(arr.shape),
                                    "dtype": str(arr.dtype), "files": files})
            man = Manifest(step=step, parts=self.parts, leaves=leaves_meta,
                           extra=extra)
            with obs.span("ckpt.commit"):
                self.lib.write_file(f"{sdir}/MANIFEST", man.to_bytes())
            with obs.span("ckpt.gc"):
                self._gc()

    # ------------------------------------------------------------------
    def save(self, step: int, tree: Any, *, extra: Optional[Dict[str, Any]] = None,
             block: bool = True) -> None:
        extra = extra or {}
        with obs.span("ckpt.save", step=step) as sid:
            # snapshot to host memory NOW (cheap on CPU; device->host on
            # TPU), so async writing races with nothing
            with obs.span("ckpt.snapshot"):
                snap = jax.tree_util.tree_map(lambda x: np.array(x), tree)
            if block:
                with self._save_lock:
                    self._write_tree(step, snap, extra)
                return
            self.wait()
            self._inflight = threading.Thread(
                target=lambda: self._write_tree(step, snap, extra, cause=sid),
                name="ckpt-writer", daemon=True)
            self._inflight.start()

    def wait(self) -> None:
        with obs.span("ckpt.wait"):
            if self._inflight is not None:
                self._inflight.join()
                self._inflight = None

    # ------------------------------------------------------------------
    def steps(self) -> List[int]:
        try:
            names = self.lib.listdir(self.base)
        except OSError:
            return []
        out = []
        for n in names:
            if n.startswith("step_"):
                sdir = f"{self.base}/{n}"
                if self.lib.exists(f"{sdir}/MANIFEST"):
                    out.append(int(n[5:]))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        s = self.steps()
        return s[-1] if s else None

    def manifest(self, step: int) -> Manifest:
        return Manifest.from_bytes(self.lib.read_file(f"{self._step_dir(step)}/MANIFEST"))

    def restore(self, step: Optional[int] = None, *, like: Any = None
                ) -> Tuple[int, Any]:
        """Reassemble the checkpoint (elastically: any current world size).

        If `like` is given (arrays or `ShapeDtypeStruct`s), the restored flat
        leaves are re-packed into its treedef (shapes checked leaf-by-leaf,
        dtypes cast to the leaf's)."""
        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError("no committed checkpoint")
        with obs.span("ckpt.restore", step=step):
            return step, self._read_tree(step, like)

    def _read_tree(self, step: int, like: Any) -> Any:
        man = self.manifest(step)
        by_name: Dict[str, np.ndarray] = {}
        for lm in man.leaves:
            parts = []
            for f in lm["files"]:
                blob = self.lib.read_file(f["path"])
                with obs.span("ckpt.verify"):
                    if zlib.crc32(blob) != f["crc"]:
                        raise IOError(f"checksum mismatch in {f['path']}")
                with obs.span("ckpt.decode"):
                    part = np.load(io.BytesIO(blob), allow_pickle=False)
                    if part.dtype.kind == "V":
                        # custom dtypes (bfloat16, f8) round-trip through
                        # .npy as raw void records; re-view with the
                        # manifest dtype
                        part = part.view(np.dtype(lm["dtype"]))
                parts.append(part)
            with obs.span("ckpt.assemble"):
                arr = np.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
                arr = arr.reshape(lm["shape"]).astype(np.dtype(lm["dtype"]))
            by_name[lm["name"]] = arr
        if like is None:
            return by_name
        flat, treedef = _tree_flatten(like)
        leaves = []
        with obs.span("ckpt.assemble"):
            for kp, leaf in flat:
                name = _leaf_name(kp)
                if name not in by_name:
                    raise KeyError(f"checkpoint missing leaf {name}")
                arr = by_name[name]
                # read shape/dtype only: `like` may be device arrays or shapes
                if tuple(arr.shape) != tuple(leaf.shape):
                    raise ValueError(
                        f"{name}: ckpt shape {arr.shape} != {leaf.shape}")
                leaves.append(arr.astype(leaf.dtype, copy=False))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    # ------------------------------------------------------------------
    def _gc(self) -> None:
        steps = self.steps()
        for s in steps[: -self.keep_last] if self.keep_last else []:
            sdir = self._step_dir(s)
            try:
                # delete manifest first => step becomes invisible atomically
                self.lib.unlink(f"{sdir}/MANIFEST")
                for f in list(self.lib.walk_files(sdir)):
                    self.lib.unlink(f)
            except OSError:
                pass
