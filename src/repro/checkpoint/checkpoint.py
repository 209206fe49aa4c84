"""Sharded, atomic, elastic-restorable checkpointing.

Layout:
    <dir>/step_<k>/
        manifest.json        -- step, leaf paths, shapes, dtypes, meta
        <leaf-path>.npy      -- one file per pytree leaf (global arrays)
        _COMMITTED           -- written last; restore ignores dirs without it

Atomicity: write into ``step_<k>.tmp`` then rename -- a crash mid-write
never corrupts the latest checkpoint (restart resumes from the previous
committed step).  ``async_save`` runs the serialization on a background
thread so the train loop overlaps I/O with compute.

Integrity: the manifest records a sha256 per leaf file and the
``_COMMITTED`` marker records the manifest's own sha256, so damage
*after* commit (torn disk write, truncation, bit rot -- the failure the
rename cannot defend against) is detected, not silently restored.
:func:`validate_checkpoint` checks one step directory;
:func:`restore` validates before loading and falls back to the newest
earlier step that verifies, moving damaged directories aside to
``step_<k>.corrupt`` (the quarantine discipline of
:mod:`repro.tuning.cache`).  Checkpoints written before checksums
existed validate by file presence alone.

Elasticity: leaves are stored as GLOBAL arrays, so a restart with a
different mesh / dp size (or a different param_mode) just reshards on
load.  The zero1 flat optimizer buffers depend on (dp, tp); on an elastic
resize they are re-initialized (Adam moments warm up in ~b2 horizon) --
recorded in the manifest so the trainer can log it.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import threading
from typing import Any, Dict, List, Optional, Tuple

import jax

import numpy as np


def _sha256(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 20), b""):
            h.update(block)
    return h.hexdigest()


def _leaf_paths(tree) -> Dict[str, Any]:
    flat = jax.tree.flatten_with_path(tree)[0]
    out = {}
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        out[key] = leaf
    return out


def save(ckpt_dir: str, step: int, trees: Dict[str, Any],
         meta: Optional[Dict] = None) -> str:
    """Synchronous checkpoint of named pytrees (e.g. params, opt_state)."""
    final = os.path.join(ckpt_dir, f"step_{step:08d}")
    tmp = final + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp, exist_ok=True)
    manifest = {"step": step, "trees": {}, "meta": meta or {}}
    for name, tree in trees.items():
        leaves = _leaf_paths(tree)
        manifest["trees"][name] = {}
        for key, leaf in leaves.items():
            arr = np.asarray(jax.device_get(leaf))
            fn = f"{name}__{key.replace('/', '__')}.npy"
            fpath = os.path.join(tmp, fn)
            np.save(fpath, arr)
            manifest["trees"][name][key] = {
                "file": fn, "shape": list(arr.shape), "dtype": str(arr.dtype),
                "sha256": _sha256(fpath)}
    mpath = os.path.join(tmp, "manifest.json")
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    # the commit marker carries the manifest's digest: a torn or tampered
    # manifest is then as detectable as a torn leaf file
    with open(os.path.join(tmp, "_COMMITTED"), "w") as f:
        f.write(_sha256(mpath))
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    return final


class AsyncCheckpointer:
    """Background-thread checkpointing; at most one save in flight."""

    def __init__(self, ckpt_dir: str, keep: int = 3):
        self.ckpt_dir = ckpt_dir
        self.keep = keep
        self._thread: Optional[threading.Thread] = None

    def save(self, step: int, trees: Dict[str, Any],
             meta: Optional[Dict] = None):
        self.wait()
        # device_get on the caller thread (consistent snapshot), IO async
        host_trees = jax.tree.map(lambda x: np.asarray(jax.device_get(x)),
                                  trees)

        def _run():
            save(self.ckpt_dir, step, host_trees, meta)
            _gc(self.ckpt_dir, self.keep)

        self._thread = threading.Thread(target=_run, daemon=True)
        self._thread.start()

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None


def _gc(ckpt_dir: str, keep: int):
    steps = sorted(latest_steps(ckpt_dir))
    for s in steps[:-keep]:
        shutil.rmtree(os.path.join(ckpt_dir, f"step_{s:08d}"),
                      ignore_errors=True)


def latest_steps(ckpt_dir: str, validate: bool = False) -> List[int]:
    """Committed checkpoint steps, ascending.

    ``validate=True`` additionally verifies each step's content
    checksums (:func:`validate_checkpoint`) and drops -- without
    quarantining -- the ones that fail; the default keeps listing cheap
    (one marker stat per step).
    """
    if not os.path.isdir(ckpt_dir):
        return []
    out = []
    for d in os.listdir(ckpt_dir):
        if d.startswith("step_") and not d.endswith(".tmp") and \
                not d.endswith(".corrupt") and \
                os.path.exists(os.path.join(ckpt_dir, d, "_COMMITTED")):
            step = int(d.split("_")[1].split(".")[0])
            if validate and not validate_checkpoint(os.path.join(ckpt_dir, d)):
                continue
            out.append(step)
    return sorted(out)


def validate_checkpoint(step_dir: str) -> bool:
    """True iff a committed checkpoint directory verifies end to end.

    Checks, in order: the ``_COMMITTED`` marker exists; the manifest
    parses and (when the marker carries a digest -- legacy markers hold
    ``ok``) hashes to what the marker recorded at commit time; every
    leaf file exists and (when the manifest recorded one) matches its
    sha256.  Any failure -- torn write, truncation, bit flip, missing
    file -- returns False; nothing is modified.
    """
    marker = os.path.join(step_dir, "_COMMITTED")
    mpath = os.path.join(step_dir, "manifest.json")
    try:
        with open(marker) as f:
            committed = f.read().strip()
        if len(committed) == 64:  # digest marker (legacy markers hold "ok")
            if _sha256(mpath) != committed:
                return False
        with open(mpath) as f:
            manifest = json.load(f)
        for name, leaves in manifest["trees"].items():
            for key, ent in leaves.items():
                fpath = os.path.join(step_dir, ent["file"])
                if not os.path.exists(fpath):
                    return False
                want = ent.get("sha256")
                if want is not None and _sha256(fpath) != want:
                    return False
    except (OSError, ValueError, KeyError, AttributeError):
        return False
    return True


def _quarantine(step_dir: str) -> None:
    """Move a damaged checkpoint aside to ``<dir>.corrupt`` so it stops
    shadowing older restorable steps (mirrors the tuning cache's
    corrupt-file discipline).  Best effort: a failure to move never
    masks the original corruption."""
    dst = step_dir + ".corrupt"
    try:
        if os.path.exists(dst):
            shutil.rmtree(dst, ignore_errors=True)
        os.replace(step_dir, dst)
    except OSError:
        pass


def restore(ckpt_dir: str, like: Dict[str, Any],
            step: Optional[int] = None) -> Tuple[int, Dict[str, Any]]:
    """Load named pytrees, reshaping into the structure of ``like``.

    A tree whose leaf set does not match what was stored (elastic resize
    of zero1 buffers) is returned as its ``like`` value unchanged, with a
    note in the returned meta.

    Every candidate step is checksum-validated first.  With ``step``
    given, a damaged checkpoint raises ``ValueError`` (the caller asked
    for that step specifically); without it, damaged steps are
    quarantined to ``step_<k>.corrupt`` and restore falls back to the
    newest earlier step that verifies.
    """
    steps = latest_steps(ckpt_dir)
    if not steps:
        raise FileNotFoundError(f"no committed checkpoints in {ckpt_dir}")
    if step is not None:
        d = os.path.join(ckpt_dir, f"step_{step:08d}")
        if not validate_checkpoint(d):
            raise ValueError(
                f"checkpoint step {step} in {ckpt_dir} failed validation")
    else:
        d = None
        for s in reversed(steps):
            cand = os.path.join(ckpt_dir, f"step_{s:08d}")
            if validate_checkpoint(cand):
                step, d = s, cand
                break
            _quarantine(cand)
        if d is None:
            raise FileNotFoundError(
                f"no checkpoint in {ckpt_dir} passed validation "
                f"(all {len(steps)} quarantined)")
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    out = {}
    for name, tree in like.items():
        want = _leaf_paths(tree)
        have = manifest["trees"].get(name, {})
        if set(want) != set(have) or any(
                list(np.shape(want[k])) != have[k]["shape"] for k in want):
            out[name] = tree            # incompatible layout: keep fresh
            continue
        loaded = {k: np.load(os.path.join(d, have[k]["file"]))
                  for k in want}
        flat, treedef = jax.tree.flatten_with_path(tree)
        leaves = []
        for path, leaf in flat:
            key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                           for p in path)
            leaves.append(loaded[key].astype(have[key]["dtype"]))
        out[name] = jax.tree.unflatten(jax.tree.structure(tree), leaves)
    return step, out
