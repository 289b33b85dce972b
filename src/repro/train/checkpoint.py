"""Fault-tolerant checkpointing.

Format: one directory per step, containing ``leaves.npz`` (every pytree leaf
keyed by its tree path) + ``manifest.json`` (step, leaf index, dtypes).
Writes are atomic (tmp dir + rename), ``keep_last`` old steps are pruned,
and ``latest_step`` scans the directory so restart-after-crash needs no
bookkeeping.

Because leaves are stored as *full logical arrays* keyed by path (not by
device shard), restore is **elastic**: the same checkpoint can be loaded
onto any mesh shape / sharding — restore takes a template pytree (built with
``jax.eval_shape``) and optional per-leaf shardings and device_puts
accordingly.  8-bit optimizer states are stored as their uint8 codes +
f32 absmax, so checkpoints are ~4x smaller than fp32-state checkpoints —
the paper's memory saving carried through to the storage/restore path.

Auxiliary optimizer state rides along unchanged: the percentile-clipping
gnorm history (``OptState.gnorm_vec``) is an ordinary f32 leaf, so a
restored run resumes with the exact clipping statistics it left with
(tests/test_checkpoint.py round-trips it).  ``None`` leaves (e.g. the
history when clipping is off) are recorded in the manifest and restored
as ``None``.

Bit-packed sub-byte states (``PackedCodes``, DESIGN.md §9) are stored as
their packed uint8 words with a ``"packed": {"bits", "n_codes", "layout"}``
manifest annotation; restore validates the annotation against the
template's static format (a 4-bit checkpoint cannot silently load as 5-bit
— same byte count, different codes; bytes packed under another layout than
``lowbit.PACK_LAYOUT``, or with no layout tag, are refused with a
``FormatError``) and re-wraps the array.  Because packing is a
per-block layout detail and the full logical array is stored, packed
leaves stay elastic: the same checkpoint restores onto any mesh.

Pooled optimizer states (``OptimConfig.pooled``, DESIGN.md §10) are stored
**per-leaf**: ``save`` slices every arena back into the per-leaf canonical
layout (``blockopt.unpool_state``) before writing, and ``restore``
reassembles arenas to match the template (``blockopt.repool_like``).  The
on-disk format is therefore independent of the pooling flag — per-leaf
checkpoints restore into pooled states and vice versa, on any mesh.

Partitioned (ZeRO-1) states (``OptimConfig.partition``, DESIGN.md §12)
add nothing on disk: the ``ArenaPartition`` is static arena aux metadata
that ``unpool_state`` drops on save and ``repool_like`` reattaches from
the restore template, so partitioned ↔ pooled ↔ per-leaf interchange is
elastic in all six directions and across shard counts
(tests/test_partition.py interchange matrix).
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile
from typing import Any, Optional

import jax
import numpy as np

from repro.core.lowbit import PACK_LAYOUT, PackedCodes
from repro.errors import FormatError

Pytree = Any


def _is_packed(x) -> bool:
    return isinstance(x, PackedCodes)


def _canonical(tree: Pytree) -> Pytree:
    """Per-leaf canonical view of every OptState in the tree (identity for
    trees without pooled optimizer states)."""
    from repro.core.optim import blockopt
    return blockopt.map_opt_states(tree, blockopt.unpool_state)


def _repool(tree: Pytree, template: Pytree) -> Pytree:
    """Reassemble pooled arenas to match ``template`` (identity when the
    template has no pooled optimizer states)."""
    from repro.core.optim import blockopt
    return blockopt.zip_opt_states(tree, template, blockopt.repool_like)


def _check_no_orphan_pooled(tree: Pytree) -> None:
    """Pooled containers outside an OptState cannot be canonicalized (the
    arena and its per-leaf nodes live on sibling OptState fields), so e.g.
    saving ``state.leaves`` alone would silently drop every quantized
    statistic.  Fail loudly instead."""
    from repro.core.optim import base as optim_base
    pooled = (optim_base.PooledQuantLeaf, optim_base.Pool32Leaf,
              optim_base.QuantArena, optim_base.Pool32Arena)
    flat = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: _is_packed(x) or isinstance(x, pooled))[0]
    bad = [jax.tree_util.keystr(p) for p, l in flat if isinstance(l, pooled)]
    if bad:
        raise ValueError(
            f"cannot checkpoint pooled optimizer containers outside their "
            f"OptState (their arena/per-leaf halves live on sibling "
            f"fields): {bad[:5]}{'...' if len(bad) > 5 else ''} — save the "
            f"whole OptState (or unpool_state it) instead")


def _leaf_paths(tree) -> list[tuple[str, Any]]:
    flat = jax.tree_util.tree_flatten_with_path(tree, is_leaf=_is_packed)[0]
    out = []
    for path, leaf in flat:
        key = jax.tree_util.keystr(path)
        out.append((key, leaf))
    return out


def save(ckpt_dir: str, step: int, tree: Pytree, *, keep_last: int = 3) -> str:
    """Atomically write checkpoint for ``step``. Returns the final path."""
    tree = _canonical(tree)
    _check_no_orphan_pooled(tree)
    os.makedirs(ckpt_dir, exist_ok=True)
    final = os.path.join(ckpt_dir, f"step_{step:010d}")
    tmp = tempfile.mkdtemp(dir=ckpt_dir, prefix=".tmp_")
    try:
        arrays, index = {}, []
        for i, (key, leaf) in enumerate(_leaf_paths(tree)):
            if leaf is None:
                index.append({"key": key, "none": True})
                continue
            name = f"a{i}"
            entry = {"key": key, "name": name}
            if _is_packed(leaf):
                entry["packed"] = {"bits": leaf.bits, "n_codes": leaf.n_codes,
                                   "layout": PACK_LAYOUT}
                leaf = leaf.packed
            arrays[name] = np.asarray(jax.device_get(leaf))
            entry.update(dtype=str(arrays[name].dtype),
                         shape=list(arrays[name].shape))
            index.append(entry)
        np.savez(os.path.join(tmp, "leaves.npz"), **arrays)
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump({"step": step, "index": index}, f)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    _prune(ckpt_dir, keep_last)
    return final


def _prune(ckpt_dir: str, keep_last: int):
    steps = sorted(all_steps(ckpt_dir))
    for s in steps[:-keep_last] if keep_last > 0 else []:
        shutil.rmtree(os.path.join(ckpt_dir, f"step_{s:010d}"),
                      ignore_errors=True)


def all_steps(ckpt_dir: str) -> list[int]:
    if not os.path.isdir(ckpt_dir):
        return []
    out = []
    for d in os.listdir(ckpt_dir):
        if d.startswith("step_"):
            try:
                out.append(int(d[5:]))
            except ValueError:
                pass
    return sorted(out)


def latest_step(ckpt_dir: str) -> Optional[int]:
    steps = all_steps(ckpt_dir)
    return steps[-1] if steps else None


def restore(ckpt_dir: str, step: int, template: Pytree,
            shardings: Optional[Pytree] = None) -> Pytree:
    """Load ``step`` into the structure of ``template`` (values ignored; may
    be ShapeDtypeStructs from jax.eval_shape).  ``shardings``: optional
    tree of jax.sharding.Sharding matching ``template`` for elastic
    placement; ``None`` entries (at any leaf) mean default placement, and a
    shardings tree whose structure does not match the template is an
    error."""
    path = os.path.join(ckpt_dir, f"step_{step:010d}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    data = np.load(os.path.join(path, "leaves.npz"))
    by_key, meta_by_key = {}, {}
    for ent in manifest["index"]:
        by_key[ent["key"]] = None if ent.get("none") else data[ent["name"]]
        meta_by_key[ent["key"]] = ent

    # Checkpoints are stored in the per-leaf canonical layout; load into
    # the per-leaf view of the template, then repool to its real layout.
    pl_template = _canonical(template)
    _check_no_orphan_pooled(pl_template)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        pl_template, is_leaf=_is_packed)
    leaves = []
    for p, tmpl in flat:
        key = jax.tree_util.keystr(p)
        if key not in by_key:
            raise KeyError(f"checkpoint missing leaf {key}")
        arr = by_key[key]
        if arr is None:
            leaves.append(None)
            continue
        packed_tmpl = tmpl if _is_packed(tmpl) else None
        saved = meta_by_key[key].get("packed")
        if packed_tmpl is not None:
            # Packedness must agree in both directions: packed bytes and
            # plain codes can share a shape without sharing a meaning.
            if saved is None:
                raise ValueError(
                    f"{key}: template expects {packed_tmpl.bits}-bit packed "
                    f"codes; checkpoint stores a plain array")
            if (saved["bits"] != packed_tmpl.bits or
                    saved["n_codes"] != packed_tmpl.n_codes):
                raise ValueError(
                    f"{key}: checkpoint packs {saved['bits']}-bit x "
                    f"{saved['n_codes']} codes; template expects "
                    f"{packed_tmpl.bits}-bit x {packed_tmpl.n_codes}")
            if saved.get("layout") != PACK_LAYOUT:
                raise FormatError(
                    f"{key}: checkpoint packs codes in layout "
                    f"{saved.get('layout')!r}; this code unpacks "
                    f"{PACK_LAYOUT!r} only (the bytes would decode to "
                    f"scrambled codes)")
            tmpl = packed_tmpl.packed
        elif saved is not None:
            raise ValueError(
                f"{key}: checkpoint stores packed {saved['bits']}-bit codes; "
                f"template expects a plain array")
        want = tuple(tmpl.shape) if hasattr(tmpl, "shape") else None
        if want is not None and tuple(arr.shape) != want:
            raise ValueError(f"{key}: checkpoint shape {arr.shape} != "
                             f"template {want}")
        if packed_tmpl is not None:
            arr = PackedCodes(arr, packed_tmpl.bits, packed_tmpl.n_codes)
        leaves.append(arr)
    tree = jax.tree_util.tree_unflatten(treedef, leaves)
    tree = _repool(tree, template)
    if shardings is None:
        return jax.device_put(tree)

    # Flatten the shardings with the *output* treedef (is_leaf aware and
    # None-preserving): tree_leaves(shardings) would silently drop None
    # entries and mis-zip everything after the first one.
    out_flat, out_treedef = jax.tree_util.tree_flatten(tree,
                                                       is_leaf=_is_packed)
    try:
        shard_flat = out_treedef.flatten_up_to(shardings)
    except ValueError as e:
        raise ValueError(
            f"shardings tree structure does not match the restore "
            f"template: {e}") from e
    placed = [jax.device_put(x) if shd is None else jax.device_put(x, shd)
              for x, shd in zip(out_flat, shard_flat)]
    return jax.tree_util.tree_unflatten(out_treedef, placed)
