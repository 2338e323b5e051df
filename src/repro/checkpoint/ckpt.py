"""Sharded checkpointing with atomic commits, async writes and auto-resume.

Fault-tolerance contract (DESIGN.md §5):
  * writes go to ``<dir>/tmp_<step>`` and are atomically renamed to
    ``<dir>/step_<step>`` — a crash mid-write never corrupts the latest
    checkpoint;
  * ``restore_checkpoint`` picks the newest *committed* step, so a training
    job restarted after a node failure resumes from the last good state;
  * ``AsyncCheckpointer`` offloads serialization to a worker thread so the
    TPU step loop is not blocked (device→host copy happens synchronously,
    the file I/O does not);
  * arrays are stored per-leaf as ``.npy`` plus a JSON manifest of the tree
    structure — on restore with a *different mesh*, leaves are re-sharded by
    ``distributed/elastic.py`` (elastic scaling);
  * mem-shard layout (docs/sharding.md): a state saved under a
    ``mem_shard.memory_mesh`` context carries its memory/usage leaves in
    the slot-sharded layout (N + shards rows, one scratch row per shard).
    ``save_checkpoint(..., mem_layout=(num_slots, shards))`` records that
    layout in the manifest; on restore, a migratable leaf whose row count
    differs from the template is re-laid-out on the host
    (``mem_shard.np_relayout``) to the template's shard count (derived as
    ``template_rows - num_slots``) — so save-on-mesh-A / restore-on-mesh-B
    (or on a single device) round-trips bit-exactly on the logical rows;
  * scratch-row migration shim: checkpoints written before the persistent
    (B, N+1, W) memory layout (core/types.py) predate the manifest
    ``format`` field (now 2) and hold (B, N, W)/(B, N) memory and usage
    leaves. On restore of such a **format-1 (markerless)** checkpoint,
    when the template expects exactly one more row on axis 1 and the leaf
    is named memory/last_access/usage, the loaded leaf is padded with the
    scratch-row init (zeros for float memory, int32 max for the usage
    table) — everything else restores bit-exactly. Later-format
    checkpoints are restored strictly (shapes must match), and any other
    mismatch raises — so a config change (head count, slot count —
    including `num_slots` N→N+1, which would be shape-indistinguishable
    from the legacy layout) cannot masquerade as a layout migration;
  * LSH-index re-layout (docs/sharding.md): the ownership-partitioned ANN
    index (ANNState — buckets/cursor) stores *layout-local* ring
    placements, so a cross-mesh restore re-partitions the two leaves
    together on the host (`mem_shard.np_relayout_ann`; the remap needs
    the recorded ``mem_layout``'s num_slots or a declared
    ``expect_num_slots`` to resolve slot ownership). Pre-format-3
    checkpoints carry the un-partitioned index shapes and migrate by a
    pure reshape (P=1 axis inserted) first.
"""
from __future__ import annotations

import json
import os
import queue
import shutil
import threading

import jax
import numpy as np


def _flatten_with_paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    paths = ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in path) for path, _ in flat]
    leaves = [leaf for _, leaf in flat]
    return paths, leaves, treedef


# Manifest format: 1 (implicit — no field) predates the scratch-row layout;
# 2 = scratch-row era (un-partitioned LSH index); 3 = ownership-partitioned
# LSH index (ANNState grew a partition axis); 4 = int8 quantized memory era
# (states may carry a per-row `mem_scale` leaf next to an int8 `memory`
# leaf). Each shape-based migration shim applies only to checkpoints
# written *before* the format that introduced its layout: once a checkpoint
# carries the marker, its shapes are authoritative and any mismatch is a
# config error. The mem-dtype migration (float↔int8 memory, below) is
# *dtype*-driven, not format-gated — the leaf dtypes in the manifest are
# unambiguous in every format.
MANIFEST_FORMAT = 4


def save_checkpoint(directory: str, step: int, tree,
                    mem_layout: tuple = None) -> str:
    """Blocking atomic save. Returns the committed path.

    ``mem_layout=(num_slots, shards)`` records the mem-shard layout of the
    tree's memory/usage leaves (module docstring) so a restore on a
    different mesh can re-lay them out. An optional third element — the
    2D mesh's data degree, as `mem_shard.ckpt_layout()` now produces —
    is recorded as provenance under ``"data"``; it never affects restore
    (the data degree is placement, not row layout), and manifests without
    it restore identically. When omitted, the active
    `mem_shard.memory_mesh` context (if any, on the *calling* thread) is
    recorded automatically — so every save made under the mesh-native path
    stays cross-mesh restorable, whichever code path wrote it."""
    if mem_layout is None:
        from repro.distributed import mem_shard
        mem_layout = mem_shard.ckpt_layout()
    os.makedirs(directory, exist_ok=True)
    tmp = os.path.join(directory, f"tmp_{step}")
    final = os.path.join(directory, f"step_{step}")
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    paths, leaves, _ = _flatten_with_paths(tree)
    manifest = {"step": step, "format": MANIFEST_FORMAT, "leaves": []}
    if mem_layout is not None:
        num_slots, shards = mem_layout[0], mem_layout[1]
        manifest["mem_layout"] = {"num_slots": int(num_slots),
                                  "shards": int(shards)}
        if len(mem_layout) > 2:
            manifest["mem_layout"]["data"] = int(mem_layout[2])
    for i, (p, leaf) in enumerate(zip(paths, leaves)):
        arr = np.asarray(jax.device_get(leaf))
        np.save(os.path.join(tmp, f"leaf_{i}.npy"), arr)
        manifest["leaves"].append({"path": p, "file": f"leaf_{i}.npy",
                                   "dtype": str(arr.dtype),
                                   "shape": list(arr.shape)})
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)          # atomic commit
    return final


def latest_step(directory: str):
    if not os.path.isdir(directory):
        return None
    steps = []
    for name in os.listdir(directory):
        if name.startswith("step_"):
            if os.path.exists(os.path.join(directory, name, "manifest.json")):
                steps.append(int(name.split("_")[1]))
    return max(steps) if steps else None


# Leaves the scratch-row migration / mem-shard re-layout shims may touch:
# the memory buffer and the usage table, addressed by their field name (the
# last component of the manifest path). The set is `core.types.SLOT_LEAVES`
# — the same single source the live layout transforms in
# distributed/mem_shard.py key on, so the checkpoint path and the in-memory
# path cannot drift apart. Any other leaf with a shape mismatch still
# raises — a head-count or slot-count config change must not be silently
# "migrated".
from repro.core.types import ANN_LEAVES as _ANN_LEAVES
from repro.core.types import SLOT_LEAVES as _MIGRATABLE_LEAVES


def _migrate_scratch_row(arr: np.ndarray, want_shape) -> np.ndarray:
    """Legacy-layout shim: pad a (B, N, ...) leaf to the (B, N+1, ...)
    scratch-row layout the template expects. The scratch row is initialized
    the way `init_state` does: 0 for float memory, int32 max (`LA_SCRATCH`)
    for integer usage tables. Returns `arr` unchanged when shapes already
    match; raises on any other mismatch."""
    want = tuple(want_shape)
    if arr.shape == want:
        return arr
    legacy = (arr.ndim >= 2 and len(want) == arr.ndim
              and want[0] == arr.shape[0]
              and want[1] == arr.shape[1] + 1
              and want[2:] == arr.shape[2:])
    if not legacy:
        raise ValueError(
            f"checkpoint leaf shape {arr.shape} does not match template "
            f"{want} and is not a legacy (one fewer row on axis 1) layout")
    from repro.core.types import LA_SCRATCH
    pad = [(0, 0)] * arr.ndim
    pad[1] = (0, 1)
    fill = LA_SCRATCH if np.issubdtype(arr.dtype, np.integer) else 0
    return np.pad(arr, pad, constant_values=fill)


def _np_quantize_rows(arr: np.ndarray):
    """Host-side numpy twin of `core.quant.quantize_rows`, kept in sync
    (tested against it in tests/test_int8_memory.py): per-row symmetric
    int8 along the last axis, ``scale = max|row| / 127`` exactly — no
    epsilon, so all-zero rows carry scale 0.0 and dequantize to exact
    zeros. `np.rint` and `jnp.round` are both round-half-to-even."""
    xf = np.asarray(arr, np.float32)
    scale = (np.max(np.abs(xf), axis=-1) / np.float32(127.0)).astype(
        np.float32)
    safe = np.where(scale > 0, scale, np.float32(1.0))
    q = np.clip(np.rint(xf / safe[..., None]), -127, 127).astype(np.int8)
    return q, scale


def _np_dequantize_rows(q: np.ndarray, scale: np.ndarray) -> np.ndarray:
    return q.astype(np.float32) * scale.astype(np.float32)[..., None]


def _scale_path(mem_path: str) -> str:
    """Manifest path of the `mem_scale` leaf next to a `memory` leaf —
    same container, so same rendering (".memory" → ".mem_scale",
    "memory" → "mem_scale")."""
    prefix, _, last = mem_path.rpartition("/")
    dot = "." if last.startswith(".") else ""
    return (prefix + "/" if prefix else "") + dot + "mem_scale"


def _migrate_ann_axis(arr: np.ndarray, name: str) -> np.ndarray:
    """Pre-format-3 shim: the un-partitioned LSH index stored buckets as
    (B, T, nb, bucket_size) and cursor as (B, T, nb); the partitioned
    layout (format 3) inserts a P=1 ownership axis — a pure reshape."""
    if name == "buckets" and arr.ndim == 4:
        return arr[:, :, :, None, :]
    if name == "cursor" and arr.ndim == 3:
        return arr[..., None]
    return arr


def _relayout_ann_group(group: dict, num_slots: int, parent: str):
    """Re-partition a deferred (buckets, cursor) pair to the template's
    partition count via `mem_shard.np_relayout_ann` — the two leaves must
    be remapped *together* (ring order lives in the cursor). Validates
    that everything except the partitioning matches the template: a
    bucket-size / table-count config change must keep raising."""
    from repro.distributed.mem_shard import np_relayout_ann
    if set(group) != {"buckets", "cursor"}:
        raise ValueError(
            f"checkpoint ANN leaves under {parent!r} cannot be re-laid-out:"
            f" need both buckets and cursor to change partition count "
            f"together — a lone mismatch is a config change, not a mesh "
            f"change")
    _, barr, btmpl, _ = group["buckets"]
    _, carr, ctmpl, _ = group["cursor"]
    bt = tuple(btmpl.shape)
    ok = (barr.ndim == 5 and len(bt) == 5
          and barr.shape[:3] == bt[:3]
          and barr.shape[3] * barr.shape[4] == bt[3] * bt[4]
          and carr.shape == barr.shape[:4]
          and tuple(ctmpl.shape) == bt[:4])
    if not ok:
        raise ValueError(
            f"checkpoint ANN leaves under {parent!r} have shapes "
            f"{barr.shape}/{carr.shape}; templates {bt}/"
            f"{tuple(ctmpl.shape)} are not a pure partition-count change "
            f"(batch/tables/buckets/capacity must match)")
    return np_relayout_ann(barr, carr, num_slots, bt[3])


def _relayout_mem_shard(arr: np.ndarray, want_shape, layout: dict,
                        path: str) -> np.ndarray:
    """Mem-shard layout shim: re-lay-out a slot-sharded memory/usage leaf
    (manifest-recorded ``mem_layout``) to the shard count the template's
    row dimension implies (``template_rows - num_slots``; 1 = canonical
    single-device layout). Only the recorded layout is trusted — shapes
    alone cannot distinguish a mesh change from a slot-count config change,
    which must keep raising."""
    from repro.distributed.mem_shard import np_relayout
    want = tuple(want_shape)
    N, s_from = int(layout["num_slots"]), int(layout["shards"])
    s_to = want[1] - N if len(want) >= 2 else 0
    ok = (arr.ndim == len(want) and arr.ndim >= 2
          and want[0] == arr.shape[0] and want[2:] == arr.shape[2:]
          and arr.shape[1] == N + s_from
          and s_to >= 1 and N % s_to == 0)
    if not ok:
        raise ValueError(
            f"checkpoint leaf {path!r} has shape {arr.shape} under recorded "
            f"mem_layout (num_slots={N}, shards={s_from}); template shape "
            f"{want} is not a valid re-layout target (rows must be "
            f"num_slots + shards for some shard count dividing num_slots)")
    return np_relayout(arr, N, s_from, s_to)


def restore_checkpoint(directory: str, template, step: int = None,
                       shardings=None, fill_missing: bool = False,
                       expect_num_slots: int = None):
    """Restore into the structure of `template`. `shardings` (optional pytree
    of NamedShardings) re-shards each leaf — this is how elastic re-scaling
    restores onto a different mesh. Legacy pre-scratch-row checkpoints are
    migrated leaf-by-leaf (`_migrate_scratch_row`).

    ``fill_missing=True`` matches checkpoint leaves to template leaves *by
    manifest path* and keeps the template's value for any path absent from
    the checkpoint — how legacy checkpoints (saved before the train-loop
    state rode along, e.g. params/opt-only trees) load unchanged into the
    extended {params, opt, carry, loop} template. Every leaf the checkpoint
    *does* carry must still match a template path — an unknown leaf raises,
    so a renamed field cannot be silently dropped.

    ``expect_num_slots`` pins the memory size the caller's config declares:
    a checkpoint whose recorded ``mem_layout`` disagrees raises instead of
    re-laying-out. Without it, a slot-count config change whose new row
    count *happens* to parse as a valid re-layout of the recorded
    num_slots (e.g. N: 64 → 65 reads as 64 + 2 shards) cannot be told
    apart from a mesh change by shapes alone — callers that know their
    config (the streaming trainer does) should always pass it."""
    if step is None:
        step = latest_step(directory)
        if step is None:
            return None, None
    path = os.path.join(directory, f"step_{step}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    t_paths, t_leaves, treedef = _flatten_with_paths(template)
    ck_by_path = {e["path"]: e for e in manifest["leaves"]}

    def _leaf_name(p):
        return p.rsplit("/", 1)[-1].lstrip(".")

    def _consumed_scale(p):
        # A checkpoint `mem_scale` leaf with no template counterpart is
        # consumed by dequantizing its sibling int8 memory leaf into a
        # float template leaf — not an unknown/renamed field.
        if _leaf_name(p) != "mem_scale":
            return False
        prefix, _, last = p.rpartition("/")
        mp = ((prefix + "/" if prefix else "")
              + ("." if last.startswith(".") else "") + "memory")
        me = ck_by_path.get(mp)
        return (me is not None and me["dtype"] == "int8"
                and mp in set(t_paths))

    if fill_missing:
        unknown = {p for p in set(ck_by_path) - set(t_paths)
                   if not _consumed_scale(p)}
        if unknown:
            raise ValueError(
                f"checkpoint leaves {sorted(unknown)} have no counterpart "
                f"in the template — not a pure leaf-subset checkpoint")
        entries = [ck_by_path.get(p) for p in t_paths]
    elif len(t_leaves) != len(manifest["leaves"]):
        # The only structural drift allowed outside fill_missing is the
        # `mem_scale` leaf appearing (float→int8 template) or disappearing
        # (int8→float template) next to a migrating memory leaf.
        extra_t = [p for p in t_paths if p not in ck_by_path]
        extra_c = [p for p in ck_by_path if p not in set(t_paths)]
        if not all(_leaf_name(p) == "mem_scale" for p in extra_t + extra_c):
            raise AssertionError("checkpoint/template structure mismatch")
        entries = [ck_by_path.get(p) for p in t_paths]
    else:
        entries = manifest["leaves"]
    leaves = []
    s_leaves = (jax.tree.leaves(shardings, is_leaf=lambda x: x is None)
                if shardings is not None else [None] * len(t_leaves))
    fmt = manifest.get("format", 1)
    migratable = fmt < 2             # pre-scratch-row era
    mem_layout = manifest.get("mem_layout")
    if (expect_num_slots is not None and mem_layout is not None
            and int(mem_layout["num_slots"]) != int(expect_num_slots)):
        raise ValueError(
            f"checkpoint was saved with num_slots="
            f"{mem_layout['num_slots']}, caller expects {expect_num_slots} "
            f"— a slot-count config change cannot be restored as a mesh "
            f"re-layout")
    # LSH-index (buckets, cursor) pairs whose partition count must change:
    # re-laid-out *together* after the loop (ring order lives in the
    # cursor). parent path -> {leaf name: (slot, arr, tmpl, sharding)}.
    ann_pending: dict = {}
    # float→int8 mem-dtype migration: scales produced by quantizing a float
    # memory leaf fill the template's `mem_scale` leaf. Flatten order is
    # container-dependent (dicts sort keys, so "mem_scale" can precede
    # "memory"), so the consumer slot is deferred and patched after the
    # loop, like the ANN pairs. template scale path -> host scale array /
    # -> (leaf slot, sharding).
    scale_pending: dict = {}
    scale_slots: dict = {}
    t_by_path = dict(zip(t_paths, t_leaves))
    for entry, t_path, tmpl, sh in zip(entries, t_paths, t_leaves, s_leaves):
        if entry is None:
            if _leaf_name(t_path) == "mem_scale":
                prefix, _, last = t_path.rpartition("/")
                mp = ((prefix + "/" if prefix else "")
                      + ("." if last.startswith(".") else "") + "memory")
                me, mt = ck_by_path.get(mp), t_by_path.get(mp)
                if (me is not None and mt is not None
                        and np.dtype(getattr(mt, "dtype", None)) == np.int8
                        and np.issubdtype(np.dtype(me["dtype"]),
                                          np.floating)):
                    scale_slots[t_path] = (len(leaves), sh)
                    leaves.append(None)          # patched after the loop
                    continue
            if not fill_missing:
                raise ValueError(
                    f"template leaf {t_path!r} is absent from the "
                    f"checkpoint and is not a mem-dtype migration target")
            # fill_missing: keep the template value
            leaves.append(jax.device_put(tmpl, sh) if sh is not None
                          else jax.numpy.asarray(tmpl))
            continue
        arr = np.load(os.path.join(path, entry["file"]))
        if arr.dtype.kind == "V":
            # .npy does not keep an ml_dtypes type: bfloat16 reads back
            # as raw 2-byte records. The manifest names the dtype.
            arr = arr.view(jax.numpy.dtype(entry["dtype"]))
        if hasattr(tmpl, "shape") and arr.shape != tuple(tmpl.shape):
            # Path components render as ".memory" (GetAttrKey) or "memory"
            # (dict key) depending on the container — compare field names.
            leaf_name = entry["path"].rsplit("/", 1)[-1].lstrip(".")
            if leaf_name in _ANN_LEAVES:
                if fmt < 3:
                    # Pre-partitioned index: insert the P=1 axis first.
                    arr = _migrate_ann_axis(arr, leaf_name)
                if arr.shape != tuple(tmpl.shape):
                    # Partition-count change (cross-mesh restore): defer
                    # for the paired re-layout. Pinning num_slots needs
                    # the recorded mem_layout or the caller's declaration.
                    if mem_layout is not None:
                        n = int(mem_layout["num_slots"])
                    elif expect_num_slots is not None:
                        n = int(expect_num_slots)
                    else:
                        raise ValueError(
                            f"checkpoint leaf {entry['path']!r} has shape "
                            f"{arr.shape}, template expects "
                            f"{tuple(tmpl.shape)} — re-partitioning the "
                            f"LSH index needs the ownership rule's "
                            f"num_slots (a recorded mem_layout, or "
                            f"expect_num_slots=)")
                    parent = entry["path"].rsplit("/", 1)[0]
                    ann_pending.setdefault(parent, {"num_slots": n})[
                        leaf_name] = (len(leaves), arr, tmpl, sh)
                    leaves.append(None)          # patched after the loop
                    continue
            elif leaf_name in _MIGRATABLE_LEAVES and mem_layout is not None:
                # Cross-mesh restore: re-layout to the template's shard
                # count (manifest records the saved layout).
                arr = _relayout_mem_shard(arr, tmpl.shape, mem_layout,
                                          entry["path"])
            elif (leaf_name in _MIGRATABLE_LEAVES
                  and expect_num_slots is not None and arr.ndim >= 2
                  and arr.shape[1] == int(expect_num_slots) + 1):
                # Pre-mem-layout checkpoint upgrading onto a mesh: the
                # manifest records no layout, but the caller's declared
                # num_slots pins it — rows == N+1 is unambiguously the
                # canonical (1-shard) layout for that config, so the
                # re-layout to the template's shard count is safe. Without
                # expect_num_slots the mismatch keeps raising below.
                arr = _relayout_mem_shard(
                    arr, tmpl.shape,
                    {"num_slots": int(expect_num_slots), "shards": 1},
                    entry["path"])
            elif migratable and leaf_name in _MIGRATABLE_LEAVES:
                arr = _migrate_scratch_row(arr, tmpl.shape)
            else:
                raise ValueError(
                    f"checkpoint leaf {entry['path']!r} has shape "
                    f"{arr.shape}, template expects {tuple(tmpl.shape)} — "
                    f"scratch-row migration applies only to pre-format-2 "
                    f"checkpoints, mem-shard/LSH-index re-layout only to "
                    f"checkpoints with a recorded mem_layout (or a "
                    f"declared expect_num_slots), and only to "
                    f"{sorted(_MIGRATABLE_LEAVES | _ANN_LEAVES)} leaves")
        # ---- mem-dtype migration (float ↔ int8 memory rows) ----
        # Runs after the shape shims, so a cross-mesh re-layout and a
        # storage-dtype change compose in one restore. Dtype-driven, not
        # format-gated: the manifest dtypes are unambiguous.
        tdt = getattr(tmpl, "dtype", None)
        if tdt is not None and arr.dtype != np.dtype(tdt):
            leaf_name = _leaf_name(entry["path"])
            if (leaf_name == "memory" and np.dtype(tdt) == np.int8
                    and np.issubdtype(arr.dtype, np.floating)):
                # float checkpoint → int8 template: quantize host-side;
                # the derived scales fill the template's mem_scale leaf.
                arr, s = _np_quantize_rows(arr)
                scale_pending[_scale_path(t_path)] = s
            elif (leaf_name == "memory" and arr.dtype == np.int8
                    and np.issubdtype(np.dtype(tdt), np.floating)):
                # int8 checkpoint → float template: dequantize against the
                # sibling mem_scale leaf (re-laid-out with its memory leaf
                # on a cross-mesh restore).
                sp = _scale_path(entry["path"])
                se = ck_by_path.get(sp)
                if se is None:
                    raise ValueError(
                        f"checkpoint leaf {entry['path']!r} is int8 but "
                        f"carries no sibling {sp!r} scale leaf — cannot "
                        f"dequantize into a float template")
                scale = np.load(os.path.join(path, se["file"]))
                if scale.shape != arr.shape[:-1]:
                    if mem_layout is None:
                        raise ValueError(
                            f"checkpoint scale leaf {sp!r} shape "
                            f"{scale.shape} does not match its memory leaf "
                            f"{arr.shape} and no mem_layout is recorded")
                    scale = _relayout_mem_shard(scale, arr.shape[:-1],
                                                mem_layout, sp)
                arr = _np_dequantize_rows(arr, scale).astype(tdt)
            elif (leaf_name == "memory"
                    and np.issubdtype(arr.dtype, np.floating)
                    and np.issubdtype(np.dtype(tdt), np.floating)):
                # float → float storage-dtype change (f32 ↔ bf16).
                arr = arr.astype(tdt)
        if sh is not None:
            leaves.append(jax.device_put(arr, sh))
        else:
            leaves.append(jax.numpy.asarray(arr))
    for parent, group in ann_pending.items():
        n = group.pop("num_slots")
        out_b, out_c = _relayout_ann_group(group, n, parent)
        for name, out in (("buckets", out_b), ("cursor", out_c)):
            slot, _, _, sh = group[name]
            leaves[slot] = (jax.device_put(out, sh) if sh is not None
                            else jax.numpy.asarray(out))
    for sp, (slot, sh) in scale_slots.items():
        s = scale_pending.pop(sp, None)
        if s is None:
            raise ValueError(
                f"template leaf {sp!r} expected a quantization scale from "
                f"its sibling memory leaf, but none was produced")
        leaves[slot] = (jax.device_put(s, sh) if sh is not None
                        else jax.numpy.asarray(s))
    return jax.tree.unflatten(treedef, leaves), step


class AsyncCheckpointer:
    """Background-thread checkpoint writer (non-blocking step loop)."""

    def __init__(self, directory: str, keep: int = 3, mem_layout: tuple = None):
        self.directory = directory
        self.keep = keep
        self.mem_layout = mem_layout
        self._q: queue.Queue = queue.Queue(maxsize=2)
        self._worker = threading.Thread(target=self._run, daemon=True)
        self._worker.start()
        self.errors: list = []

    def save(self, step: int, tree):
        # Device→host copy happens here (synchronous, cheap vs step time);
        # file I/O happens on the worker. The memory_mesh layout is
        # captured HERE, on the calling thread, at every save — the worker
        # thread has no thread-local context, and a checkpointer is often
        # constructed before the mesh context is entered; capturing at
        # construction (or not at all) would silently drop the layout and
        # leave the checkpoint unrestorable onto any other mesh shape.
        mem_layout = self.mem_layout
        if mem_layout is None:
            from repro.distributed import mem_shard
            mem_layout = mem_shard.ckpt_layout()
        host_tree = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)
        self._q.put((step, host_tree, mem_layout))

    def _run(self):
        while True:
            item = self._q.get()
            if item is None:
                return
            step, tree, mem_layout = item
            try:
                save_checkpoint(self.directory, step, tree,
                                mem_layout=mem_layout)
                self._gc()
            except Exception as e:  # noqa: BLE001
                self.errors.append(e)

    def _gc(self):
        steps = sorted(
            int(n.split("_")[1]) for n in os.listdir(self.directory)
            if n.startswith("step_"))
        for s in steps[:-self.keep]:
            shutil.rmtree(os.path.join(self.directory, f"step_{s}"),
                          ignore_errors=True)

    def wait(self):
        self._q.join() if False else None
        while not self._q.empty():
            import time
            time.sleep(0.05)

    def close(self):
        self._q.put(None)
        self._worker.join(timeout=10)
