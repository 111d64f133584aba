"""Fault-tolerant checkpointing: atomic, sharded-friendly, elastic-restore.

Design (DESIGN.md §5):
  * save: every array leaf -> .npy under a temp dir; metadata (step, tree
    structure, user extras) -> JSON; atomic publish via directory rename.
    A crashed writer can never corrupt the latest checkpoint.
  * restore: host-side load + device_put against the *current* mesh's
    shardings — the device count may differ from the writer's (elastic
    restart after node failure); re-sharding happens at placement time.
  * async: ``save_async`` snapshots to host memory synchronously (cheap)
    and writes to disk on a background thread, overlapping I/O with the
    next training steps.
  * retention: ``keep`` newest checkpoints are retained, older ones pruned.

Combined with the deterministic data pipeline (batch = f(seed, step)), a
restore needs only (params, opt_state, step) to resume bit-identically.
"""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import threading
import warnings
import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np


class CheckpointCorrupt(RuntimeError):
    """A checkpoint directory failed verification: missing or unparsable
    manifest, unreadable array leaf, or a per-leaf checksum mismatch."""


def _flatten_with_paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        key = "/".join(
            str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p))))
            for p in path)
        out.append((key, leaf))
    return out, treedef


def save(ckpt_dir: str, step: int, tree: Any,
         extras: Optional[Dict] = None, keep: int = 3) -> str:
    """Synchronous atomic checkpoint save.  Returns the published path."""
    base = pathlib.Path(ckpt_dir)
    base.mkdir(parents=True, exist_ok=True)
    tmp = base / f".tmp_step_{step:010d}_{os.getpid()}"
    final = base / f"step_{step:010d}"
    if tmp.exists():
        shutil.rmtree(tmp)
    tmp.mkdir()
    leaves, _ = _flatten_with_paths(tree)
    manifest = {"step": step, "extras": extras or {}, "leaves": []}
    for i, (key, leaf) in enumerate(leaves):
        arr = np.asarray(leaf)
        dtype_name = str(arr.dtype)
        if dtype_name == "bfloat16":  # numpy can't round-trip bf16: widen
            arr = arr.astype(np.float32)
        fname = f"leaf_{i:05d}.npy"
        np.save(tmp / fname, arr)
        manifest["leaves"].append(
            {"key": key, "file": fname, "shape": list(arr.shape),
             "dtype": dtype_name,
             # per-leaf content checksum: restore verifies it so a torn
             # write or storage-level corruption is detected, not loaded
             "crc32": zlib.crc32(np.ascontiguousarray(arr).tobytes())})
    (tmp / "manifest.json").write_text(json.dumps(manifest))
    if final.exists():
        shutil.rmtree(final)
    tmp.rename(final)  # atomic publish
    _prune(base, keep)
    return str(final)


def _prune(base: pathlib.Path, keep: int):
    steps = sorted(p for p in base.iterdir()
                   if p.is_dir() and p.name.startswith("step_"))
    for p in steps[:-keep] if keep else []:
        shutil.rmtree(p, ignore_errors=True)


def _sweep_stale_tmp(base: pathlib.Path) -> List[str]:
    """Remove ``.tmp_step_*_<pid>`` dirs whose writer process is dead — a
    crashed writer's half-written temp dir otherwise lingers forever (the
    atomic-rename protocol never publishes it, but it wastes storage and
    confuses humans).  Temp dirs of live pids (a concurrent writer) are
    left alone."""
    removed = []
    if not base.exists():
        return removed
    for p in base.glob(".tmp_step_*"):
        if not p.is_dir():
            continue
        pid_s = p.name.rsplit("_", 1)[-1]
        if not pid_s.isdigit():
            continue
        pid = int(pid_s)
        alive = pid == os.getpid()
        if not alive:
            try:
                os.kill(pid, 0)
                alive = True
            except ProcessLookupError:
                alive = False
            except PermissionError:  # exists, owned by someone else
                alive = True
            except OSError:
                alive = False
        if not alive:
            shutil.rmtree(p, ignore_errors=True)
            removed.append(str(p))
    return removed


class AsyncCheckpointer:
    """Snapshot-to-host synchronously, write-to-disk on a worker thread.

    A background write that fails does not vanish: the exception is
    recorded and re-raised from the next :meth:`wait` or :meth:`save` —
    otherwise a run could march on for hours believing it has checkpoints
    it does not.  Construction sweeps stale temp dirs left by dead
    writers (see :func:`_sweep_stale_tmp`).
    """

    def __init__(self, ckpt_dir: str, keep: int = 3):
        self.ckpt_dir = ckpt_dir
        self.keep = keep
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        self.last_path: Optional[str] = None
        self.swept = _sweep_stale_tmp(pathlib.Path(ckpt_dir))

    def save(self, step: int, tree: Any, extras: Optional[Dict] = None):
        self.wait()
        host_tree = jax.tree_util.tree_map(np.asarray, tree)  # snapshot

        def work():
            try:
                self.last_path = save(self.ckpt_dir, step, host_tree,
                                      extras, self.keep)
            except BaseException as e:  # noqa: BLE001 - recorded, re-raised
                self._error = e

        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()

    def save_abm(self, step: int, engine, state,
                 extras: Optional[Dict] = None):
        """Async variant of :func:`save_abm`: the mesh-independent logical
        snapshot (flatten + histogram + host gather) runs synchronously —
        it must see the state *now* — and only the disk write overlaps
        with subsequent steps."""
        self.wait()
        tree, merged = _abm_snapshot(engine, state, extras)
        host_tree = jax.tree_util.tree_map(np.asarray, tree)

        def work():
            try:
                self.last_path = save(self.ckpt_dir, step, host_tree,
                                      merged, self.keep)
            except BaseException as e:  # noqa: BLE001 - recorded, re-raised
                self._error = e

        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()

    def wait(self) -> Optional[str]:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err
        return self.last_path


def _delta_meta(cfg) -> Optional[Dict]:
    """JSON-able record of an engine's aura-codec config (None if absent)."""
    if cfg is None:
        return None
    return {
        "enabled": bool(cfg.enabled),
        "qdtype": np.dtype(cfg.qdtype).name,
        "refresh_interval": int(cfg.refresh_interval),
        "scale": None if cfg.scale is None else float(cfg.scale),
    }


def _abm_snapshot(engine, state, extras: Optional[Dict] = None
                  ) -> Tuple[Dict, Dict]:
    """Build the logical (mesh-independent) checkpoint tree + extras for
    an ABM state — shared by the sync :func:`save_abm` and the async
    :meth:`AsyncCheckpointer.save_abm`."""
    from repro.core.reshard import flatten_state, occupancy_histogram

    flat = flatten_state(engine.geom, state)
    hist = occupancy_histogram(engine.geom, state)
    tree = {
        "positions": flat.positions,
        "attrs": {k: np.asarray(v) for k, v in sorted(flat.attrs.items())},
        "gid_counters": flat.gid_counters,
        "base_key": flat.base_key,
        "histogram": hist,
    }
    geom = engine.geom
    abm_meta = {
        "it": int(flat.it),
        "dropped_total": int(flat.dropped_total),
        "cell_size": float(geom.cell_size),
        "ndim": int(geom.ndim),
        "global_cells": list(geom.global_cells),
        "cap": int(geom.cap),
        # per-axis boundary list (legacy checkpoints stored one string;
        # Domain normalizes either form on restore)
        "boundary": list(geom.boundary),
        "box_factor": int(geom.box_factor),
        "dt": float(engine.dt),
        "attr_names": sorted(flat.attrs),
        # uneven-ownership provenance: the live cut positions (cells) and
        # the ownership mode a restore should re-cut with.  Restore never
        # reuses the cuts verbatim — the device count may differ — it cuts
        # a FRESH plan from the stored histogram (elastic_restore_abm);
        # legacy checkpoints without these keys restore as "equal".
        "partition": ([list(c) for c in geom.partition.cuts]
                      if geom.uneven else None),
        "ownership": "rcb" if geom.uneven else "equal",
        # aura-codec provenance: restore re-applies the same delta config
        # by default so a recovery replay stays bit-exact with the
        # checkpointed run (the quantized closed loop is part of the
        # dynamics once enabled).  Legacy checkpoints without the key
        # restore with the codec off, as before.
        "delta": _delta_meta(getattr(engine, "delta_cfg", None)),
    }
    return tree, {"abm": abm_meta, **(extras or {})}


def save_abm(ckpt_dir: str, step: int, engine, state,
             extras: Optional[Dict] = None, keep: int = 3) -> str:
    """Checkpoint an ABM :class:`SimState` *logically*: the flattened live
    agents plus the engine carry (iteration, spawn counters, RNG root) and
    the occupancy histogram.

    Storing the flattened form instead of the sharded SoA makes the
    checkpoint mesh-independent — restore is a re-shard whose target mesh is
    chosen from the stored histogram (elastic.elastic_restore_abm), so a
    run can resume on any surviving device count.
    """
    tree, merged = _abm_snapshot(engine, state, extras)
    return save(ckpt_dir, step, tree, extras=merged, keep=keep)


def _step_dirs(base: pathlib.Path) -> List[pathlib.Path]:
    out = []
    for p in base.iterdir():
        if not (p.is_dir() and p.name.startswith("step_")):
            continue
        suffix = p.name.split("_", 1)[1]
        if suffix.isdigit():
            out.append(p)
    return sorted(out)


def _load_verified(path: pathlib.Path) -> Tuple[Dict, List[np.ndarray]]:
    """Load (manifest, arrays) from one checkpoint dir, verifying per-leaf
    checksums when present.  Raises :class:`CheckpointCorrupt` on any
    missing/unparsable manifest, unreadable leaf, or checksum mismatch."""
    mpath = path / "manifest.json"
    if not mpath.exists():
        raise CheckpointCorrupt(f"{path}: missing manifest.json")
    try:
        manifest = json.loads(mpath.read_text())
        leaves = manifest["leaves"]
    except (ValueError, KeyError, TypeError) as e:
        raise CheckpointCorrupt(
            f"{path}: unparsable manifest.json ({e})") from e
    arrays = []
    for leaf in leaves:
        try:
            arr = np.load(path / leaf["file"])
        except Exception as e:  # torn/truncated/missing .npy
            raise CheckpointCorrupt(
                f"{path}: unreadable leaf {leaf.get('file')} "
                f"[{leaf.get('key')}] ({e})") from e
        want = leaf.get("crc32")  # absent on legacy checkpoints
        if want is not None:
            got = zlib.crc32(np.ascontiguousarray(arr).tobytes())
            if got != want:
                raise CheckpointCorrupt(
                    f"{path}: checksum mismatch on leaf "
                    f"{leaf['file']} [{leaf.get('key')}] "
                    f"(crc32 {got:#010x} != manifest {want:#010x})")
        arrays.append(arr)
    return manifest, arrays


def latest_step(ckpt_dir: str) -> Optional[int]:
    """Newest *plausibly usable* checkpoint step: dirs without a parsable
    ``manifest.json`` are skipped with a warning (a torn write past the
    atomic rename, or external corruption) instead of crashing the
    restore path.  Content checksums are verified at :func:`restore`."""
    base = pathlib.Path(ckpt_dir)
    if not base.exists():
        return None
    for p in reversed(_step_dirs(base)):
        try:
            json.loads((p / "manifest.json").read_text())
        except (OSError, ValueError) as e:
            warnings.warn(
                f"skipping checkpoint {p.name} in {ckpt_dir}: "
                f"missing/corrupt manifest.json ({e})", stacklevel=2)
            continue
        return int(p.name.split("_", 1)[1])
    return None


def restore(ckpt_dir: str, step: Optional[int] = None,
            like: Any = None, shardings: Any = None
            ) -> Tuple[int, Any, Dict]:
    """Restore a checkpoint.

    Args:
      like: a pytree with the same structure (e.g. abstract params) used to
        rebuild the tree; if None, returns a flat {key: array} dict.
      shardings: optional matching pytree of NamedSharding for elastic
        placement on the current (possibly different-sized) mesh.

    With ``step=None`` the newest checkpoint that passes full verification
    (manifest parses, every leaf loads, checksums match) is used —
    corrupt ones are skipped newest-to-oldest with a warning naming the
    skipped dir.  An explicit ``step`` that fails verification raises
    :class:`CheckpointCorrupt`.
    """
    base = pathlib.Path(ckpt_dir)
    if step is not None:
        manifest, arrays = _load_verified(base / f"step_{step:010d}")
    else:
        if not base.exists():
            raise FileNotFoundError(f"no checkpoints in {ckpt_dir}")
        manifest = arrays = None
        for path in reversed(_step_dirs(base)):
            try:
                manifest, arrays = _load_verified(path)
                break
            except CheckpointCorrupt as e:
                warnings.warn(
                    f"skipping corrupt checkpoint {path.name}: {e}",
                    stacklevel=2)
        if manifest is None:
            raise FileNotFoundError(
                f"no usable checkpoints in {ckpt_dir} (all candidates "
                "failed verification)")

    if like is None:
        flat = {leaf["key"]: arr
                for leaf, arr in zip(manifest["leaves"], arrays)}
        return manifest["step"], flat, manifest["extras"]

    leaves, treedef = jax.tree_util.tree_flatten(like)
    assert len(leaves) == len(arrays), (
        f"checkpoint has {len(arrays)} leaves, tree expects {len(leaves)}")
    def cast(a, l):
        # on the host: a sharded leaf goes from here straight to its
        # devices, never whole onto the default one
        return np.asarray(a).astype(l.dtype)

    if shardings is not None:
        shard_leaves = jax.tree_util.tree_leaves(
            shardings, is_leaf=lambda x: isinstance(
                x, jax.sharding.Sharding))
        placed = [jax.device_put(cast(a, l), s)
                  for a, l, s in zip(arrays, leaves, shard_leaves)]
    else:
        placed = [jax.numpy.asarray(cast(a, l))
                  for a, l in zip(arrays, leaves)]
    return (manifest["step"],
            jax.tree_util.tree_unflatten(treedef, placed),
            manifest["extras"])
