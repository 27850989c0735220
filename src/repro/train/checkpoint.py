"""Checkpointing: atomic, checksummed, async, shard-aware.

Layout (one directory per step):
    <dir>/step_000100/
        manifest.json       tree structure, shapes, dtypes, shard info, sha256
        arrays.npz          leaf data (full mode)  or
        shard_<k>.npz       per-host shard data (sharded mode)
    <dir>/LATEST            text file: last complete step directory name

Guarantees a 1000-node deployment needs:
  * atomicity — writes land in a tmp dir, fsynced, then renamed; LATEST is
    updated last, so a crash mid-save never corrupts the restore point,
  * integrity — per-file sha256 in the manifest, verified on restore,
  * async — ``save_async`` snapshots to host memory synchronously (cheap)
    and writes in a background thread so the train loop never blocks on IO,
  * retention — keep_last N; concurrent writers are serialized through a
    lock and ``_gc`` never deletes a step a pending writer is producing.

Sharded mode (``format: "sharded"``): ``shard_<k>.npz`` holds exactly
model-shard ``k``'s slice of every leaf; ``manifest["shard_info"]`` maps
each key to its slicing rule (``distributed.tp.Segments`` JSON, or
``"replicated"``), so ``restore`` can reassemble the full tree bit-exactly
and ``tp.load_sharded_params`` can device_put shards pre-partitioned.
Sharded checkpoints are produced offline by ``scripts/checkpoint_converter``.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import tempfile
import threading
from typing import Any, Optional

import jax
import ml_dtypes
import numpy as np

# npz cannot round-trip ml_dtypes (bfloat16 & co): store raw uint8 views and
# reinterpret on restore using the manifest dtype.
_EXT_DTYPES = {
    "bfloat16": ml_dtypes.bfloat16,
    "float8_e4m3fn": ml_dtypes.float8_e4m3fn,
    "float8_e5m2": ml_dtypes.float8_e5m2,
}


def _to_storable(arr: np.ndarray) -> np.ndarray:
    if arr.dtype.name in _EXT_DTYPES:
        return arr.view(np.uint8)
    return arr


def _from_storable(arr: np.ndarray, dtype_name: str) -> np.ndarray:
    if dtype_name in _EXT_DTYPES:
        return arr.view(_EXT_DTYPES[dtype_name])
    return arr


def _flatten(tree) -> tuple[list[tuple[str, Any]], Any]:
    flat, treedef = jax.tree.flatten_with_path(tree)
    items = []
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        items.append((key, leaf))
    return items, treedef


def _sha256(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def save(ckpt_dir: str, state, step: int, *, keep_last: int = 3) -> str:
    """Synchronous atomic save.  Returns the checkpoint path."""
    items, _ = _flatten(state)
    host = {k: np.asarray(v) for k, v in items}
    return _write(ckpt_dir, {"arrays.npz": host}, step, keep_last)


def save_sharded(ckpt_dir: str, shards: list[dict], step: int, *,
                 shard_info: dict, keep_last: int = 3) -> str:
    """Write a ``format: "sharded"`` checkpoint from per-shard flat dicts.

    ``shards[k]`` maps checkpoint key -> shard ``k``'s (already sliced)
    host array; ``shard_info`` maps each key to its slicing rule
    (``Segments.to_json()`` or ``"replicated"``).  Keys and local shapes
    must agree across shards — slicing is always even."""
    shards = [{k: np.asarray(v) for k, v in s.items()} for s in shards]
    keys = sorted(shards[0].keys())
    for m, s in enumerate(shards[1:], start=1):
        if sorted(s.keys()) != keys:
            raise ValueError(f"shard {m} keys differ from shard 0")
    files = {f"shard_{m}.npz": s for m, s in enumerate(shards)}
    extra = {"format": "sharded", "num_shards": len(shards),
             "shard_info": dict(shard_info)}
    return _write(ckpt_dir, files, step, keep_last, extra=extra)


# Concurrent writers (two save_async calls, or save_async racing a sync
# save) must not interleave the final rename / LATEST update / gc sweep,
# and gc must never collect a step another writer is still producing.
_LOCK = threading.Lock()
_PENDING: list[threading.Thread] = []
_IN_FLIGHT: set[tuple[str, str]] = set()   # (abs ckpt_dir, step dir name)


def save_async(ckpt_dir: str, state, step: int, *, keep_last: int = 3
               ) -> threading.Thread:
    """Snapshot to host now, write in the background."""
    items, _ = _flatten(state)
    host = {k: np.asarray(v) for k, v in items}  # device->host copy (sync)
    t = threading.Thread(
        target=_write, args=(ckpt_dir, {"arrays.npz": host}, step, keep_last),
        daemon=True)
    with _LOCK:
        _PENDING.append(t)
    t.start()
    return t


def wait_pending():
    with _LOCK:
        pending = list(_PENDING)
    for t in pending:
        t.join()
        with _LOCK:
            if t in _PENDING:
                _PENDING.remove(t)


def _write(ckpt_dir: str, files: dict[str, dict], step: int, keep_last: int,
           *, extra: Optional[dict] = None) -> str:
    os.makedirs(ckpt_dir, exist_ok=True)
    name = f"step_{step:08d}"
    final = os.path.join(ckpt_dir, name)
    token = (os.path.abspath(ckpt_dir), name)
    with _LOCK:
        _IN_FLIGHT.add(token)
    tmp = tempfile.mkdtemp(prefix=f".tmp_{name}_", dir=ckpt_dir)
    try:
        try:
            host = files["arrays.npz"] if "arrays.npz" in files \
                else files["shard_0.npz"]
            sha = {}
            for fname, data in files.items():
                path = os.path.join(tmp, fname)
                np.savez(path, **{k.replace("/", "__"): _to_storable(v)
                                  for k, v in data.items()})
                sha[fname] = _sha256(path)
            manifest = {
                "step": step,
                "keys": sorted(host.keys()),
                # sharded mode: per-shard local shapes (even split, so all
                # shards agree); full mode: the global shapes
                "shapes": {k: list(v.shape) for k, v in host.items()},
                "dtypes": {k: str(v.dtype) for k, v in host.items()},
                "sha256": sha,
                "format": "full",
            }
            if extra:
                manifest.update(extra)
            with open(os.path.join(tmp, "manifest.json"), "w") as f:
                json.dump(manifest, f, indent=1)
                f.flush()
                os.fsync(f.fileno())
            with _LOCK:
                if os.path.exists(final):
                    shutil.rmtree(final)
                os.rename(tmp, final)
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise
        with _LOCK:
            latest = os.path.join(ckpt_dir, "LATEST")
            current = ""
            if os.path.exists(latest):
                with open(latest) as f:
                    current = f.read().strip()
            # a slow writer for an *older* step finishing after a newer one
            # must not move LATEST backwards (names sort: zero-padded)
            if name >= current:
                with open(latest + ".tmp", "w") as f:
                    f.write(name)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(latest + ".tmp", latest)
            _gc(ckpt_dir, keep_last)
    finally:
        with _LOCK:
            _IN_FLIGHT.discard(token)
    return final


def _gc(ckpt_dir: str, keep_last: int):
    """Drop all but the newest ``keep_last`` steps.  Caller holds _LOCK;
    steps another writer is still producing are never collected."""
    busy = {n for d, n in _IN_FLIGHT if d == os.path.abspath(ckpt_dir)}
    steps = sorted(d for d in os.listdir(ckpt_dir) if d.startswith("step_"))
    for d in steps[:-keep_last] if keep_last > 0 else []:
        if d in busy:
            continue
        shutil.rmtree(os.path.join(ckpt_dir, d), ignore_errors=True)


def latest_step(ckpt_dir: str) -> Optional[int]:
    latest = os.path.join(ckpt_dir, "LATEST")
    if not os.path.exists(latest):
        return None
    with open(latest) as f:
        return int(f.read().strip().split("_")[1])


def _read_manifest(ckpt_dir: str, step: Optional[int]) -> tuple[dict, str]:
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
    path = os.path.join(ckpt_dir, f"step_{step:08d}")
    with open(os.path.join(path, "manifest.json")) as f:
        return json.load(f), path


def _load_npz(path: str, manifest: dict) -> dict[str, np.ndarray]:
    """Load one checkpoint npz into {key: array}, closing the file."""
    out = {}
    with np.load(path) as data:
        for key in manifest["keys"]:
            out[key] = _from_storable(data[key.replace("/", "__")],
                                      manifest["dtypes"][key])
    return out


def read_sharded(ckpt_dir: str, *, step: Optional[int] = None,
                 verify: bool = True) -> tuple[dict, list[dict]]:
    """Read a sharded checkpoint as (manifest, per-shard flat dicts).

    Shard ``k``'s dict holds only its local slices — nothing is
    concatenated here (that is the point of the format)."""
    manifest, path = _read_manifest(ckpt_dir, step)
    if manifest.get("format") != "sharded":
        raise ValueError(f"checkpoint at {path} has format "
                         f"'{manifest.get('format')}', expected 'sharded'")
    shards = []
    for m in range(int(manifest["num_shards"])):
        fname = f"shard_{m}.npz"
        fpath = os.path.join(path, fname)
        if verify:
            got = _sha256(fpath)
            want = manifest["sha256"][fname]
            if got != want:
                raise IOError(f"checksum mismatch in {fpath}: "
                              f"{got} != {want}")
        shards.append(_load_npz(fpath, manifest))
    return manifest, shards


def _reassemble(manifest: dict, shards: list[dict]) -> dict[str, np.ndarray]:
    """Full flat state from per-shard slices (bit-exact inverse of the
    converter's slicing, driven purely by the manifest's shard_info)."""
    from repro.distributed.tp import Segments
    info = manifest["shard_info"]
    full = {}
    for key in manifest["keys"]:
        rule = Segments.from_json(info.get(key, "replicated"))
        full[key] = (shards[0][key] if rule is None
                     else rule.unslice([s[key] for s in shards]))
    return full


def _load_flat(ckpt_dir: str, step: Optional[int], verify: bool
               ) -> tuple[dict, dict[str, np.ndarray]]:
    manifest, path = _read_manifest(ckpt_dir, step)
    if manifest.get("format") == "sharded":
        manifest, shards = read_sharded(ckpt_dir, step=manifest["step"],
                                        verify=verify)
        return manifest, _reassemble(manifest, shards)
    arrays_path = os.path.join(path, "arrays.npz")
    if verify:
        got = _sha256(arrays_path)
        want = manifest["sha256"]["arrays.npz"]
        if got != want:
            raise IOError(f"checksum mismatch in {arrays_path}: "
                          f"{got} != {want}")
    return manifest, _load_npz(arrays_path, manifest)


def restore(ckpt_dir: str, state_like, *, step: Optional[int] = None,
            verify: bool = True):
    """Restore into the structure of ``state_like`` (shapes validated).

    Returns (state, step).  state_like may hold arrays or ShapeDtypeStructs.
    Sharded checkpoints are reassembled to the full tree bit-exactly."""
    manifest, flat = _load_flat(ckpt_dir, step, verify)
    items, treedef = _flatten(state_like)
    leaves = []
    for key, like in items:
        arr = flat[key]
        assert tuple(arr.shape) == tuple(like.shape), (key, arr.shape,
                                                       like.shape)
        leaves.append(arr.astype(like.dtype))
    return jax.tree.unflatten(treedef, leaves), manifest["step"]


def load_params(ckpt_dir: str, *, step: Optional[int] = None,
                verify: bool = True):
    """Restore without a ``state_like``: rebuild the nested dict tree from
    the manifest keys alone, re-wrapping ``QuantizedTensor`` leaves.

    A key group ``<stem>/0`` (int8) + ``<stem>/1`` (float scale)
    [+ ``<stem>/2`` act scale] is exactly how ``_flatten`` serializes a
    QuantizedTensor, so detection is unambiguous for dict-shaped models.
    Returns (tree, step) with numpy leaves (stored dtypes preserved)."""
    manifest, flat = _load_flat(ckpt_dir, step, verify)
    from repro.quant.core import QuantizedTensor
    keys = set(flat)
    tree: dict = {}
    consumed: set[str] = set()

    def insert(key: str, leaf):
        node = tree
        parts = key.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf

    for key in sorted(keys):
        if key in consumed:
            continue
        stem, _, child = key.rpartition("/")
        if (child == "0" and stem and flat[key].dtype == np.int8
                and stem + "/1" in keys):
            q, scale = flat[stem + "/0"], flat[stem + "/1"]
            act = flat.get(stem + "/2")
            consumed.update(k for k in (stem + "/0", stem + "/1", stem + "/2")
                            if k in keys)
            insert(stem, QuantizedTensor(
                q=q, scale=scale,
                # -1 (not ndim-1): stays channel-last when a lax.scan over
                # the block stack peels the leading payload dim
                axis=-1 if scale.ndim else None,
                act_scale=act))
        else:
            insert(key, flat[key])
    return tree, manifest["step"]
