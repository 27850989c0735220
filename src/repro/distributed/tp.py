"""Tensor parallelism over the mesh's ``model`` axis.

The logical-axis GSPMD rules in :mod:`repro.distributed.sharding` let the
compiler shard *training* graphs; serving wants the Megatron layout made
explicit instead: column-parallel ``wi``/``wi_gate``/``wq``/``wk``/``wv``
(weights sliced on the output feature dim, no collective), row-parallel
``wo``/``out_proj`` (sliced on the input dim, one ``psum`` after), a
vocab-parallel embedding, and Mamba-2 ``in_proj``/head-vector slicing.
This module holds the three pieces every layer shares:

* **runtime context** — :func:`axis_ctx` marks, at trace time inside a
  ``shard_map`` body, which mesh axis carries the model shards; layer code
  asks :func:`axis`/:func:`extent` and calls :func:`psum`/:func:`pmax`/
  :func:`all_gather_last`.  With no context every helper is the identity,
  so unsharded engines run the exact same layer code.

* **slicing plan** — :func:`build_plan` walks a model's (axes, shapes)
  trees and, *through the same logical->mesh rules ``logical_spec``
  uses*, assigns each parameter leaf a :class:`Segments` slicing rule (or
  ``None`` = replicated).  ``Segments`` covers the plain one-dim shard and
  the segment-packed Mamba projections (z/x sharded, B/C replicated, dt
  sharded — one mechanism, invertible, JSON-serializable into checkpoint
  manifests).

* **placement** — :func:`partition_params` slices a replicated tree onto
  the mesh (counted ``tp.load.replicated_slice``);
  :func:`load_sharded_params` builds the same device layout straight from
  a ``format: "sharded"`` checkpoint (counted ``tp.load.pre_partitioned``)
  without ever materializing a full weight on any device — asserted, not
  assumed.  :class:`repro.quant.QuantizedTensor` leaves slice payload and
  per-channel scales along the same axis.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.quant import core as qcore

# ===================================================== runtime context ====
# Set (lexically, at trace time) inside shard_map bodies; model layers read
# it to decide whether a psum/pmax/all_gather is needed.  Deliberately NOT
# the GSPMD ShardingContext: that one drives compiler constraints, this one
# drives explicit collectives.
_TP_AXIS: Optional[str] = None
_TP_EXTENT: int = 1


@contextlib.contextmanager
def axis_ctx(name: str, n: int):
    """Scope a tensor-parallel axis: ``with tp.axis_ctx("model", 2): ...``."""
    global _TP_AXIS, _TP_EXTENT
    prev = (_TP_AXIS, _TP_EXTENT)
    _TP_AXIS, _TP_EXTENT = (name, int(n)) if n > 1 else (None, 1)
    try:
        yield
    finally:
        _TP_AXIS, _TP_EXTENT = prev


def axis() -> Optional[str]:
    """The active TP mesh-axis name, or None outside a TP region."""
    return _TP_AXIS


def extent() -> int:
    """Number of model shards (1 outside a TP region)."""
    return _TP_EXTENT


def index():
    """This shard's position along the TP axis (traced value)."""
    return jax.lax.axis_index(_TP_AXIS)


def psum(x):
    return jax.lax.psum(x, _TP_AXIS) if _TP_AXIS is not None else x


def pmax(x):
    return jax.lax.pmax(x, _TP_AXIS) if _TP_AXIS is not None else x


def all_gather_last(x):
    """Concatenate shards along the last dim (ascending shard order)."""
    if _TP_AXIS is None:
        return x
    return jax.lax.all_gather(x, _TP_AXIS, axis=x.ndim - 1, tiled=True)


# ======================================================== slicing rules ===
@dataclasses.dataclass(frozen=True)
class Segments:
    """Slicing rule for one parameter dim made of packed segments.

    ``parts`` is ``((width, sharded), ...)`` covering ``dim`` end to end.
    A plain column/row shard is one ``(width, True)`` part; the Mamba-2
    ``in_proj`` output dim is ``[z x B C dt]`` with z/x/dt sharded by heads
    and the single-group B/C replicated on every shard.  ``slice`` and
    ``unslice`` are exact inverses, so the offline checkpoint converter
    and ``restore`` share one layout definition.
    """
    dim: int
    parts: tuple[tuple[int, bool], ...]

    @classmethod
    def plain(cls, dim: int, width: int) -> "Segments":
        return cls(dim=dim, parts=((width, True),))

    def local_width(self, n: int) -> int:
        return sum(w // n if sh else w for w, sh in self.parts)

    def _index(self, arr_ndim: int, lo: int, hi: int):
        d = self.dim % arr_ndim
        return (slice(None),) * d + (slice(lo, hi),)

    def validate(self, shape, n: int, name: str = "?") -> None:
        d = self.dim % len(shape)
        total = sum(w for w, _ in self.parts)
        if shape[d] != total:
            raise ValueError(
                f"{name}: dim {d} has {shape[d]} features, slicing rule "
                f"covers {total}")
        for w, sh in self.parts:
            if sh and w % n:
                raise ValueError(
                    f"{name}: segment of width {w} not divisible by "
                    f"tp={n}")

    def slice(self, arr, i: int, n: int):
        """Shard ``i`` of ``n`` (works on numpy and jax arrays)."""
        segs, off = [], 0
        for w, sh in self.parts:
            if sh:
                lw = w // n
                lo = off + i * lw
                segs.append(arr[self._index(arr.ndim, lo, lo + lw)])
            else:
                segs.append(arr[self._index(arr.ndim, off, off + w)])
            off += w
        if len(segs) == 1:
            return segs[0]
        xp = np if isinstance(arr, np.ndarray) else jnp
        return xp.concatenate(segs, axis=self.dim % arr.ndim)

    def unslice(self, shards):
        """Reassemble the full array from per-shard locals (bit-exact)."""
        n = len(shards)
        xp = np if isinstance(shards[0], np.ndarray) else jnp
        d = self.dim % shards[0].ndim
        segs, off = [], 0
        for w, sh in self.parts:
            if sh:
                lw = w // n
                segs.extend(s[self._index(s.ndim, off, off + lw)]
                            for s in shards)
                off += lw
            else:
                segs.append(shards[0][self._index(shards[0].ndim,
                                                  off, off + w)])
                off += w
        if len(segs) == 1:
            return segs[0]
        return xp.concatenate(segs, axis=d)

    def to_json(self):
        return {"dim": self.dim, "parts": [[w, bool(sh)]
                                           for w, sh in self.parts]}

    @classmethod
    def from_json(cls, obj) -> Optional["Segments"]:
        if obj is None or obj == "replicated":
            return None
        return cls(dim=int(obj["dim"]),
                   parts=tuple((int(w), bool(sh)) for w, sh in obj["parts"]))


def rule_to_json(rule: Optional[Segments]):
    return "replicated" if rule is None else rule.to_json()


def scale_rule(rule: Optional[Segments], payload_ndim: int
               ) -> Optional[Segments]:
    """Slicing rule for a QuantizedTensor's per-channel ``scale``.

    Scales run along the payload's *last* axis: column-parallel weights
    (sliced on the last dim) slice their scales identically; row-parallel
    weights (sliced on an input dim) replicate them.  ``dim=-1`` covers
    both the plain ``(C,)`` scale and the stacked ``(*stack, C)`` one."""
    if rule is None or rule.dim % payload_ndim != payload_ndim - 1:
        return None
    return Segments(dim=-1, parts=rule.parts)


# ========================================================== plan builder ==
def _flatten_with_keys(tree, is_leaf=None):
    flat, treedef = jax.tree.flatten_with_path(tree, is_leaf=is_leaf)
    items = []
    for path, leaf in flat:
        names = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        items.append(("/".join(names), names, leaf))
    return items, treedef


def _maps_to(rules: dict, logical: Optional[str], tp_axis: str) -> bool:
    if not logical:
        return False
    mapped = rules.get(logical)
    if mapped is None:
        return False
    mapped = (mapped,) if isinstance(mapped, str) else tuple(mapped)
    return tp_axis in mapped


# segment layouts of the Mamba-2 packed projections (see models/mamba2.py):
#   in_proj out dim  = [z (di) | x (di) | B (ds) | C (ds) | dt (nh)]
#   conv_w/conv_b    = [x (di) | B (ds) | C (ds)]
# z/x/dt shard with the heads; the single-group B/C stay on every shard.
def _mamba_segments(key: str, cfg) -> Optional[list[tuple[int, bool]]]:
    di, ds, nh = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_heads
    if key == "in_proj":
        return [(di, True), (di, True), (ds, False), (ds, False), (nh, True)]
    if key in ("conv_w", "conv_b"):
        return [(di, True), (ds, False), (ds, False)]
    return None


@dataclasses.dataclass(frozen=True)
class Plan:
    """Per-leaf slicing rules for one (model config, tp degree) pair."""
    tp: int
    axis: str
    rules: Any                            # pytree: Segments | None per leaf
    flat: dict[str, Optional[Segments]]   # checkpoint-key -> rule

    def flat_json(self) -> dict:
        return {k: rule_to_json(r) for k, r in self.flat.items()}


def default_tp_rules() -> dict[str, Any]:
    """Logical->mesh mapping used when no mesh is at hand (the offline
    converter); mirrors ``sharding.default_rules`` for the model axis."""
    return {"vocab": "model", "heads": "model", "kv_heads": "model",
            "mlp": "model", "ssm_inner": "model", "ssm_heads": "model"}


def build_plan(axes_tree, shapes_tree, *, cfg, tp: int, axis: str = "model",
               rules: Optional[dict] = None) -> Plan:
    """Assign every parameter leaf a slicing rule (or None = replicated).

    ``axes_tree``/``shapes_tree`` come from ``model.abstract_params(cfg)``;
    ``rules`` is the logical->mesh mapping (``sharding.default_rules(mesh)``
    at serve time, :func:`default_tp_rules` offline) — the *same* table
    ``logical_spec`` concretizes, so GSPMD and explicit TP cannot drift.

    Strict divisibility: a model-mapped dim that ``tp`` does not divide is
    an error naming the parameter — except the vocab, which falls back to a
    replicated embedding (the unembed all-gather is then a no-op).
    """
    tp = int(tp)
    if tp < 1:
        raise ValueError(f"tp={tp}")
    rules = default_tp_rules() if rules is None else rules

    # config-level divisibility first: these produce clearer errors than
    # the per-leaf width check (e.g. kv_dim may divide while kv_heads
    # do not — the decode reshape would then mix heads across shards)
    problems = []
    has_attn = any(s.mixer == "attn" for s in cfg.block_pattern)
    has_mamba = any(s.mixer == "mamba" for s in cfg.block_pattern)
    if tp > 1 and has_attn:
        if cfg.num_heads % tp:
            problems.append(f"num_heads={cfg.num_heads}")
        if cfg.num_kv_heads % tp:
            problems.append(f"num_kv_heads={cfg.num_kv_heads}")
    if tp > 1 and cfg.d_ff % tp and any(s.ff for s in cfg.block_pattern):
        problems.append(f"d_ff={cfg.d_ff}")
    if tp > 1 and has_mamba and cfg.ssm_heads % tp:
        problems.append(f"ssm_heads={cfg.ssm_heads}")
    if problems:
        raise ValueError(
            f"model '{cfg.name}' cannot shard over tp={tp}: "
            + ", ".join(problems) + " not divisible")

    shape_items, treedef = _flatten_with_keys(shapes_tree)
    axes_items, _ = _flatten_with_keys(
        axes_tree, is_leaf=lambda x: isinstance(x, tuple))
    axes_by_key = {k: leaf for k, _, leaf in axes_items}

    flat: dict[str, Optional[Segments]] = {}
    leaves = []
    for key, names, like in shape_items:
        rule = _leaf_rule(names, tuple(like.shape), axes_by_key.get(key),
                          cfg, tp, axis, rules)
        if rule is not None:
            rule.validate(tuple(like.shape), tp, name=key)
        flat[key] = rule
        leaves.append(rule)
    return Plan(tp=tp, axis=axis,
                rules=jax.tree_util.tree_unflatten(treedef, leaves),
                flat=flat)


def _leaf_rule(names, shape, axes, cfg, tp, tp_axis, rules
               ) -> Optional[Segments]:
    if tp == 1:
        return None
    # MoE experts stay replicated under TP: expert parallelism already
    # covers them on the data axis, and moe() computes with full weights
    if "moe" in names:
        return None
    key = names[-1] if names else ""
    if "mamba" in names:
        segs = _mamba_segments(key, cfg)
        if segs is not None:
            return Segments(dim=len(shape) - 1, parts=tuple(segs))
    if axes is None:
        return None
    for i, logical in enumerate(axes):
        if not _maps_to(rules, logical, tp_axis):
            continue
        if shape[i] % tp:
            if logical == "vocab":
                return None  # replicated-embedding fallback (odd vocabs)
            raise ValueError(
                f"{'/'.join(names)}: dim {i} ({logical}={shape[i]}) not "
                f"divisible by tp={tp}")
        return Segments.plain(i, shape[i])
    return None


def _pspec(rule: Optional[Segments], axis: str, ndim: Optional[int] = None
           ) -> P:
    if rule is None:
        return P()
    d = rule.dim if rule.dim >= 0 else rule.dim % ndim
    return P(*([None] * d + [axis]))


def param_pspecs(plan: Plan, params):
    """PartitionSpec tree for shard_map in_specs, mirroring ``params``.

    QuantizedTensor leaves become spec-QTs (same treedef, same static
    ``axis``) whose children carry the payload/scale/act-scale specs."""
    def one(rule, leaf):
        if qcore.is_quantized(leaf):
            return qcore.QuantizedTensor(
                q=_pspec(rule, plan.axis, leaf.q.ndim),
                scale=_pspec(scale_rule(rule, leaf.q.ndim), plan.axis,
                             jnp.ndim(leaf.scale)),
                axis=leaf.axis,
                act_scale=None if leaf.act_scale is None else P())
        return _pspec(rule, plan.axis, jnp.ndim(leaf))

    return _map_with_rules(plan, params, one)


# ============================================================ placement ===
def _record(key: str) -> None:
    from repro.kernels import fabric
    fabric.record(key)


def _replicate(x, mesh):
    return jax.device_put(jnp.asarray(x), NamedSharding(mesh, P()))


def _put_sharded(locals_, mesh, dim: int, axis: str):
    """Per-shard host arrays -> one global jax.Array, sharded on ``dim``.

    Built via ``make_array_from_callback`` so each device receives exactly
    its local block — the full (packed) array never exists on any device,
    and the trailing assert turns that claim into a hard failure."""
    n = len(locals_)
    l0 = locals_[0]
    dim = dim % l0.ndim
    lw = l0.shape[dim]
    gshape = list(l0.shape)
    gshape[dim] = lw * n
    sharding = NamedSharding(mesh, P(*([None] * dim + [axis])))

    def cb(idx):
        start = idx[dim].start or 0
        return locals_[start // lw]

    arr = jax.make_array_from_callback(tuple(gshape), sharding, cb)
    for s in arr.addressable_shards:
        assert s.data.shape[dim] == lw, (
            f"device {s.device} holds {s.data.shape[dim]} of "
            f"{gshape[dim]} rows — full weight materialized")
    return arr


def _place(rule: Optional[Segments], full, mesh, tp, axis, counter):
    if rule is None:
        return _replicate(full, mesh)
    arr = np.asarray(full)
    locals_ = [np.ascontiguousarray(rule.slice(arr, m, tp))
               for m in range(tp)]
    _record(counter)
    return _put_sharded(locals_, mesh, rule.dim, axis)


def _map_with_rules(plan: Plan, params, fn):
    return jax.tree_util.tree_map(fn, plan.rules, params,
                                  is_leaf=lambda x: x is None or
                                  isinstance(x, Segments))


def partition_params(params, mesh, plan: Plan):
    """Slice a fully-replicated params tree onto the mesh (host-side).

    This is the migration path (and the fresh-init path): the full weight
    exists once on host, gets sliced, and each device receives only its
    shard.  Counted ``tp.load.replicated_slice`` per sharded leaf —
    pre-partitioned checkpoint loads count ``tp.load.pre_partitioned``
    instead, which is how tests prove which path served the weights."""
    tp, ax = plan.tp, plan.axis

    def one(rule, leaf):
        if qcore.is_quantized(leaf):
            q = _place(rule, np.asarray(leaf.q), mesh, tp, ax,
                       "tp.load.replicated_slice")
            s = _place(scale_rule(rule, leaf.q.ndim), np.asarray(leaf.scale),
                       mesh, tp, ax, "tp.load.replicated_slice")
            act = (None if leaf.act_scale is None
                   else _replicate(np.asarray(leaf.act_scale), mesh))
            return qcore.QuantizedTensor(q=q, scale=s, axis=leaf.axis,
                                         act_scale=act)
        return _place(rule, leaf, mesh, tp, ax, "tp.load.replicated_slice")

    return _map_with_rules(plan, params, one)


def load_sharded_params(ckpt_dir: str, mesh, plan: Plan, *,
                        step: Optional[int] = None):
    """Pre-partitioned load from a ``format: "sharded"`` checkpoint.

    Each ``shard_<k>.npz`` holds exactly shard ``k``'s slices (payload AND
    per-channel scales already cut by the offline converter), so the load
    is read -> device_put per shard: no host- or device-side concatenation
    of a full weight ever happens.  The manifest's per-key ``shard_info``
    must match ``plan`` — a checkpoint converted for a different tp degree
    or layout is rejected, not silently re-sliced."""
    from repro.train import checkpoint as ck
    manifest, shards = ck.read_sharded(ckpt_dir, step=step)
    tp, ax = plan.tp, plan.axis
    if int(manifest["num_shards"]) != tp:
        raise ValueError(
            f"checkpoint has {manifest['num_shards']} shards, mesh wants "
            f"tp={tp} — re-run the converter for this mesh")
    shard_info = manifest["shard_info"]

    def rule_for(key: str, want: Optional[Segments]) -> Optional[Segments]:
        got = Segments.from_json(shard_info.get(key, "replicated"))
        if rule_to_json(got) != rule_to_json(want):
            raise ValueError(
                f"{key}: checkpoint sliced as {rule_to_json(got)}, plan "
                f"wants {rule_to_json(want)} — re-shard the checkpoint")
        return got

    def put(key: str, want: Optional[Segments]):
        rule = rule_for(key, want)
        if rule is None:
            _record("tp.load.replicated")
            return _replicate(shards[0][key], mesh)
        _record("tp.load.pre_partitioned")
        return _put_sharded([shards[m][key] for m in range(tp)], mesh,
                            rule.dim, ax)

    keys = set(manifest["keys"])
    tree: dict = {}
    for stem, want in plan.flat.items():
        if stem in keys:
            leaf = put(stem, want)
        elif stem + "/0" in keys:  # QuantizedTensor children (q, scale[, act])
            qs = manifest["shapes"][stem + "/0"]
            leaf = qcore.QuantizedTensor(
                q=put(stem + "/0", want),
                scale=put(stem + "/1", scale_rule(want, len(qs))),
                # -1 (not ndim-1): scanning the block stack peels a leading
                # dim off the payload, and axis must stay channel-last
                axis=(-1 if len(manifest["shapes"][stem + "/1"]) else None),
                act_scale=(put(stem + "/2", None)
                           if stem + "/2" in keys else None))
        else:
            raise KeyError(f"checkpoint is missing parameter '{stem}'")
        node = tree
        parts = stem.split("/")
        for name in parts[:-1]:
            node = node.setdefault(name, {})
        node[parts[-1]] = leaf
    return tree


def shard_state(flat: dict, plan: Plan, *, prefix: str = ""
                ) -> tuple[list[dict], dict]:
    """Slice a flat {checkpoint_key: np.ndarray} state into per-shard flat
    dicts + the manifest ``shard_info`` — the converter's core.

    Keys resolve against ``plan.flat`` directly, or with ``prefix/``
    stripped (checkpoints that wrap params under e.g. ``params/``).
    QuantizedTensor children (``<stem>/0`` payload, ``/1`` scales, ``/2``
    act scale) slice per the stem's rule: payload as the float weight
    would, per-channel scales along the same axis, act scale replicated.
    Unknown keys (optimizer state, step counters) replicate."""
    def stem_rule(key: str):
        cand = [key]
        if prefix and key.startswith(prefix + "/"):
            cand.append(key[len(prefix) + 1:])
        for k in cand:
            if k in plan.flat:
                return plan.flat[k], "leaf"
            base, _, child = k.rpartition("/")
            if child in ("0", "1", "2") and base in plan.flat:
                return plan.flat[base], child
        return None, "unknown"

    shards: list[dict] = [dict() for _ in range(plan.tp)]
    info: dict = {}
    for key, arr in flat.items():
        arr = np.asarray(arr)
        rule, kind = stem_rule(key)
        if kind == "1":
            # per-channel scale: slice along dim 0 iff the payload's rule
            # shards its last dim (scale axis == payload last axis)
            payload = flat.get(key[:-1] + "0")
            pnd = payload.ndim if payload is not None else arr.ndim + 1
            rule = scale_rule(rule, pnd)
        elif kind == "2" or kind == "unknown":
            rule = None  # act scale / optimizer state / counters: replicate
        if rule is not None and (arr.ndim == 0 or arr.shape[
                rule.dim % arr.ndim] != sum(w for w, _ in rule.parts)):
            rule = None  # per-tensor scale / mismatched aux leaf: replicate
        info[key] = rule_to_json(rule)
        for m in range(plan.tp):
            shards[m][key] = (arr if rule is None
                              else np.ascontiguousarray(
                                  rule.slice(arr, m, plan.tp)))
    return shards, info
