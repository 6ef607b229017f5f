"""Fault-tolerant checkpointing: atomic, device-count-agnostic, async.

Layout (one directory per step):

    ckpt_dir/step_000123.tmp-<nonce>/   # written first
        manifest.json                   # tree structure + dtypes + widths
        arrays.npz                      # one entry per leaf (host arrays)
    ckpt_dir/step_000123/               # atomic os.replace when complete

Design points for 1000+ node operation:
  * **Atomicity** — a checkpoint is visible iff its directory was
    os.replace()'d into place; readers never see partial state. A crash
    mid-write leaves only a .tmp dir that the next run garbage-collects.
  * **Elasticity** — leaves are saved *unsharded* (gathered to host), so a
    restart may use any mesh shape/device count; the launcher re-shards on
    restore. (At real 100B scale this becomes per-shard files + a gather
    manifest; the manifest format already carries per-leaf metadata.)
  * **Async** — save() can snapshot-to-host synchronously and write in a
    background thread, keeping the step loop running.
  * **Packed state passes through untouched** — PackedTensor payloads are
    uint32 leaves + static aux recorded in the manifest, so checkpoints of
    compressed state are bits/32 the size of f32 checkpoints, exactly the
    paper's footprint claim applied to persistence. Each packed entry
    names its word layout (``bitpack.LAYOUT``); restore refuses a payload
    packed in another layout instead of decoding it with the wrong
    shifts.
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
import shutil
import tempfile
import threading
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro import compat

from repro.core import bitpack
from repro.core.tensor_store import PackedTensor, is_packed


def _flatten(tree) -> Tuple[List[Tuple[str, Any]], Any]:
    flat, treedef = compat.tree_flatten_with_path(
        tree, is_leaf=is_packed
    )
    out = []
    for path, leaf in flat:
        key = "/".join(
            str(getattr(p, "key", getattr(p, "idx", p))) for p in path
        )
        out.append((key, leaf))
    return out, treedef


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3):
        self.directory = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._gc_tmp()
        self._thread: Optional[threading.Thread] = None

    # -- public ---------------------------------------------------------------
    def save(self, step: int, tree: Any, blocking: bool = True,
             plan: Any = None) -> str:
        """Snapshot to host now; write (a)synchronously; return final path.

        ``plan`` (a ``core.compress.CompressionPlan`` or None) rides in
        the manifest — packed-master training checkpoints persist the
        ``(packed codes, masters, plan)`` triple, and the plan is what
        lets a resumed run re-encode updated masters at the same widths
        without re-tuning."""
        host_tree = compat.tree_map(
            lambda l: np.asarray(jax.device_get(l)), tree, is_leaf=is_packed
        ) if not _tree_has_packed(tree) else _device_get_packed(tree)
        final = self._step_dir(step)
        if blocking:
            self._write(step, host_tree, final, plan)
        else:
            self.wait()
            self._thread = threading.Thread(
                target=self._write, args=(step, host_tree, final, plan),
                daemon=True,
            )
            self._thread.start()
        return final

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def all_steps(self) -> List[int]:
        out = []
        for name in os.listdir(self.directory):
            m = re.fullmatch(r"step_(\d+)", name)
            if m:
                out.append(int(m.group(1)))
        return sorted(out)

    def restore(self, step: Optional[int] = None,
                with_plan: bool = False) -> Tuple:
        """Load (step, tree of host numpy arrays / PackedTensors) — or
        (step, tree, plan) with ``with_plan``, where plan is the
        ``CompressionPlan`` the checkpoint was saved with (None when the
        run was not packed-master)."""
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {self.directory}")
        d = self._step_dir(step)
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)
        arrays = np.load(os.path.join(d, "arrays.npz"))
        leaves = []
        for entry in manifest["leaves"]:
            arr = arrays[entry["key"]]
            if entry.get("packed") and entry.get("layout") != bitpack.LAYOUT:
                raise ValueError(
                    f"checkpoint {d}: packed leaf {entry['key']!r} uses word "
                    f"layout {entry.get('layout', 'group-of-32')!r}, this "
                    f"build reads {bitpack.LAYOUT!r}; re-pack it from the "
                    "dense values")
            if entry.get("packed"):
                leaves.append(PackedTensor(
                    data=arr,
                    bits=entry["bits"],
                    kind=entry["kind"],
                    signed=entry["signed"],
                    logical_shape=tuple(entry["logical_shape"]),
                    out_dtype=np.dtype(entry["out_dtype"]),
                ))
            else:
                leaves.append(arr)
        treedef = compat.tree_structure(
            json.loads(manifest["treedef_json"]),
            is_leaf=lambda x: x is None,
        )
        tree = compat.tree_unflatten(treedef, leaves)
        if with_plan:
            return step, tree, _plan_from_jsonable(manifest.get("plan"))
        return step, tree

    # -- internals --------------------------------------------------------------
    def _step_dir(self, step: int) -> str:
        return os.path.join(self.directory, f"step_{step:06d}")

    def _write(self, step: int, host_tree: Any, final: str,
               plan: Any = None) -> None:
        tmp = tempfile.mkdtemp(
            prefix=f"step_{step:06d}.tmp-", dir=self.directory
        )
        flat, treedef = _flatten(host_tree)
        leaves_meta = []
        payload = {}
        for key, leaf in flat:
            if is_packed(leaf):
                payload[key] = np.asarray(leaf.data)
                leaves_meta.append({
                    "key": key, "packed": True, "layout": bitpack.LAYOUT,
                    "bits": leaf.bits,
                    "kind": leaf.kind, "signed": leaf.signed,
                    "logical_shape": list(leaf.logical_shape),
                    "out_dtype": np.dtype(leaf.out_dtype).name,
                })
            else:
                payload[key] = np.asarray(leaf)
                leaves_meta.append({"key": key, "packed": False})
        skeleton = compat.tree_map(
            lambda _: None, host_tree, is_leaf=is_packed
        )
        manifest = {
            "step": step,
            "leaves": leaves_meta,
            "treedef_json": json.dumps(_to_jsonable(skeleton)),
            "plan": _plan_to_jsonable(plan),
        }
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        np.savez(os.path.join(tmp, "arrays.npz"), **payload)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.replace(tmp, final)
        self._gc_old()

    def _gc_old(self) -> None:
        steps = self.all_steps()
        for s in steps[: -self.keep]:
            shutil.rmtree(self._step_dir(s), ignore_errors=True)

    def _gc_tmp(self) -> None:
        for name in os.listdir(self.directory):
            if ".tmp-" in name:
                shutil.rmtree(
                    os.path.join(self.directory, name), ignore_errors=True
                )


def _tree_has_packed(tree) -> bool:
    return any(
        is_packed(l)
        for l in compat.tree_leaves(tree, is_leaf=is_packed)
    )


def _device_get_packed(tree):
    def get(l):
        if is_packed(l):
            return dataclasses.replace(
                l, data=np.asarray(jax.device_get(l.data))
            )
        return np.asarray(jax.device_get(l))
    return compat.tree_map(get, tree, is_leaf=is_packed)


def _to_jsonable(tree):
    if isinstance(tree, dict):
        return {k: _to_jsonable(v) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return [_to_jsonable(v) for v in tree]
    return None


def _plan_to_jsonable(plan) -> Optional[Dict[str, Any]]:
    """CompressionPlan -> manifest entry: the shared plan-file codec
    (``CompressionPlan.to_jsonable``), so a manifest plan and a
    ``--save-plan`` file are the same schema."""
    if plan is None:
        return None
    return plan.to_jsonable()


def _plan_from_jsonable(entry):
    if entry is None:
        return None
    from repro.core.compress import CompressionPlan
    # from_jsonable tolerates the pre-codec manifests (no "version" key)
    return CompressionPlan.from_jsonable(entry)
