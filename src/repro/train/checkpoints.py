"""Fault-tolerant, datalake-versioned checkpoints with elastic restore.

Checkpoints are ACAI filesets ("<run>-ckpt" versions), written through a
transactional upload session (a crashed save never becomes a visible
version) with provenance edges from the training job. Restore reshards onto
ANY mesh: arrays are saved unsharded-logical (global shape) and re-placed
with the target mesh's NamedShardings — elastic scaling across restarts.

A checkpoint is two files. ``state.bin`` holds every leaf's bytes, C order
and in its own dtype, one after another in the state's flattening order;
``manifest.json`` holds the step, the extra fields and the ``layout``: per
leaf its ``key``, ``dtype`` name, ``shape`` and byte ``offset`` in
``state.bin``. The save streams the leaves' own host buffers into the data
lake, so the host holds one copy of the state. Checkpoints written before
this layout hold ``state.npz`` instead, and restore reads them too.
"""
from __future__ import annotations

import io
import json
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.core.acai import AcaiProject
from repro.core.trace import span


def _flatten(tree) -> dict[str, Any]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        flat[key] = leaf
    return flat


def _unflatten_like(template, flat: dict[str, Any], cast: bool = False):
    paths = jax.tree_util.tree_flatten_with_path(template)[0]
    leaves = []
    for path, tmpl_leaf in paths:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        leaf = flat[key]
        if cast and hasattr(tmpl_leaf, "dtype"):
            leaf = np.asarray(leaf).astype(tmpl_leaf.dtype)
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(template), leaves)


def _fetch(flat: dict[str, Any]) -> dict[str, np.ndarray]:
    """Every leaf on the host: all device-to-host copies start at once."""
    for leaf in flat.values():
        if isinstance(leaf, jax.Array):
            leaf.copy_to_host_async()
    return {k: np.asarray(v) for k, v in flat.items()}


def _layout(arrays: dict[str, np.ndarray]) -> list[dict]:
    """Each leaf's key, dtype, shape and byte offset in ``state.bin``."""
    out, offset = [], 0
    for key, arr in arrays.items():
        out.append({"key": key, "dtype": arr.dtype.name,
                    "shape": list(arr.shape), "offset": offset})
        offset += arr.nbytes
    return out


def _read_layout(raw: bytes, layout: list[dict]) -> dict[str, np.ndarray]:
    return {e["key"]: np.frombuffer(
        raw, jnp.dtype(e["dtype"]), count=math.prod(e["shape"]),
        offset=e["offset"]).reshape(e["shape"]) for e in layout}


class CheckpointManager:
    def __init__(self, project: AcaiProject, run_name: str,
                 keep: int = 3):
        self.project = project
        self.run = run_name
        self.keep = keep

    @property
    def fileset(self) -> str:
        return f"{self.run}-ckpt"

    # ------------------------------------------------------------------
    def save(self, step: int, params, opt_state=None,
             extra: Optional[dict] = None, job_id: Optional[str] = None,
             input_fileset: Optional[str] = None) -> str:
        with span("ckpt/save"):
            state = {"params": params}
            if opt_state is not None:
                state["opt"] = opt_state
            flat = _flatten(state)
            with span("ckpt/fetch"):     # device-to-host copy of each leaf
                arrays = _fetch(flat)
            with span("ckpt/encode"):
                manifest = {"step": step, "extra": extra or {},
                            "layout": _layout(arrays)}
            storage = self.project.storage
            paths = [f"/{self.fileset}/state.bin",
                     f"/{self.fileset}/manifest.json"]
            with span("lake/put"):
                sid = storage.begin_session(paths, creator="trainer")
                put = storage.session_put_stream(sid, paths[0], (
                    np.ascontiguousarray(a).reshape(-1).view(np.uint8)
                    for a in arrays.values()))
                del arrays
                storage.session_put(sid, paths[1],
                                    json.dumps(manifest).encode())
                fvs = storage.commit_session(sid)
                fsv = self.project.filesets.create(
                    self.fileset, [f"{fv.path}@{fv.version}" for fv in fvs],
                    creator="trainer")
                self.project.metadata.register(
                    fsv.ref, kind="checkpoint", step=step, run=self.run,
                    bytes=put.size, hash_wait_s=put.hash_wait_s,
                    **(extra or {}))
            if job_id is not None:
                src = None
                if input_fileset:
                    src = self.project.filesets.resolve(input_fileset).ref
                self.project.provenance.add_job_edge(src=src, dst=fsv.ref,
                                                     job_id=job_id)
            return fsv.ref

    # ------------------------------------------------------------------
    def latest_step(self) -> Optional[int]:
        if not self.project.filesets.exists(self.fileset):
            return None
        ref = self.project.filesets.resolve(self.fileset).ref
        return self.project.metadata.get(ref).get("step")

    def restore(self, template, *, version: Optional[int] = None,
                mesh=None, specs=None):
        """Rebuild ``template``-shaped state. With (mesh, specs) the arrays
        are placed sharded on the target mesh — any device count (elastic).
        Returns (state, step)."""
        ref = self.fileset if version is None else \
            f"{self.fileset}:{version}"
        fsv = self.project.filesets.resolve(ref)
        storage = self.project.storage

        def read(name: str) -> bytes:
            path = f"/{self.fileset}/{name}"
            return storage._get_blob(
                storage.resolve(path, fsv.files[path]).blob)

        man = json.loads(read("manifest.json"))
        if f"/{self.fileset}/state.npz" in fsv.files:    # the old layout
            npz = np.load(io.BytesIO(read("state.npz")))
            flat = {k: npz[k] for k in npz.files}
        else:
            flat = _read_layout(read("state.bin"), man["layout"])
        state = _unflatten_like(template, flat, cast=True)
        if mesh is not None and specs is not None:
            flat_spec = _flatten(specs)
            placed = {}
            for key, arr in _flatten(state).items():
                spec = flat_spec.get(key)
                if spec is not None:
                    placed[key] = jax.device_put(
                        arr, NamedSharding(mesh, spec))
                else:
                    placed[key] = jnp.asarray(arr)
            state = _unflatten_like(template, placed)
        else:
            state = jax.tree.map(jnp.asarray, state)
        return state, man["step"]
