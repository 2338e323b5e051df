"""Mesh-native sparse memory: `shard_map` read/write over slot-sharded memory.

The GSPMD route for the sparse memory ops is a trap at scale: a dynamically
indexed gather/scatter on a memory sharded over slots lowers to a per-step
all-gather of the full (B, N, W) buffer — O(B·N·W) collective traffic that
silently erases the paper's O(K·W) asymptotics. This module provides the
mesh-native alternative: the memory shards over a mesh axis ("model") *by
slots*, every O(N) sweep runs shard-locally through the ordinary kernel
backend dispatch (`repro.kernels.ops` — ref/pallas stay untouched inside
each shard), and the only cross-shard traffic is

  * top-K / LRA selection: shard-local top-K over the local rows, then an
    all-gather of (B, K) scores+indices and a replicated K-merge —
    O(B·H·K) per step;
  * reads of the K winning rows: each shard contributes the rows it owns
    (others masked to zero) and a psum assembles the full (B, H, K, W)
    words on every shard — O(B·H·K·W) per step;
  * writes: none. (index, value) pairs route to their owning shard by
    masking — each shard scatters only what it owns; non-owned entries
    land on the shard's scratch row with zero weight.

Per-step collective traffic is therefore O(B·K·W), never O(B·N·W)
(asserted against the compiled HLO by benchmarks/bench_shard.py).

On a 2D (data × model) mesh the batch dimension additionally shards over
the data axes (``memory_mesh(..., data_axes=...)``): every state leaf and
batch-leading operand splits its B rows across data replicas, the
shard_map bodies run on the local batch block, and all of the collectives
above still name only the model axis — B above becomes B_local = B/data,
and the data axes carry zero memory-path collective traffic (the HLO guard
asserts this). The slot layout is identical on every replica, so the data
degree is pure placement: re-laying a state across data degrees is a
`device_put`, never a row remap (distributed/elastic.py).

Sharded scratch-row layout
--------------------------
The canonical single-device layout is a (B, N+1, W) buffer with one
write-scratch row at N (core/types.py). N+1 is indivisible by any useful
mesh axis, so the sharded layout gives **every shard its own scratch row**:

    (B, N + S, W)  =  S blocks of (local_n + 1) rows,
    block s = [rows s·local_n .. (s+1)·local_n) , shard-s scratch row]

with local_n = N/S. Total rows N+S = S·(local_n+1) divide the S-way axis
exactly, each shard-local block is itself a valid (B, local_n+1, W)
scratch-row buffer, and the existing kernels run on it unchanged with
``valid_n=local_n`` / ``scratch_row=local_n``. The canonical layout is the
S=1 special case. Indices stay *global* (in [0, N)) everywhere outside the
shard bodies; row g lives on shard g // local_n at local row g % local_n.

Activation
----------
    with mem_shard.memory_mesh(mesh, num_slots=N):
        state = cell.init_state(batch)          # built in the sharded layout
        ...jit / grad / scan as usual...

The context is trace-time static. `repro.kernels.ops` and
`repro.core.addressing` detect a buffer in the active context's sharded
layout by shape and route through the `shard_map` paths below; everything
else (canonical or legacy buffers, no context) takes the ordinary path.
See docs/sharding.md.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import warnings
from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.types import (ANN_LEAVES, LA_SCRATCH, SCRATCH_ROWS,
                              SLOT_LEAVES)
from repro.kernels import ops as _ops


# --------------------------------------------------------------------------
# Context
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MemShardCtx:
    """Active slot-sharding of the sparse memory: N logical slots split into
    `shards` contiguous blocks over mesh axis `axis`, one scratch row per
    shard (module docstring). `data_axes`/`data_degree` describe the
    orthogonal data-parallel axes the *batch* dimension shards over in a 2D
    (data × model) mesh: the slot layout is identical on every data replica
    — the data degree is pure placement, never a row-layout parameter."""

    mesh: Mesh
    axis: str
    num_slots: int
    shards: int
    data_axes: tuple = ()
    data_degree: int = 1

    @property
    def local_n(self) -> int:
        return self.num_slots // self.shards

    @property
    def sharded_rows(self) -> int:
        """Row count of a buffer in this context's sharded layout."""
        return self.num_slots + self.shards * SCRATCH_ROWS


class _Ctx(threading.local):
    def __init__(self):
        self.ctx: Optional[MemShardCtx] = None


_CTX = _Ctx()


@contextlib.contextmanager
def memory_mesh(mesh: Mesh, num_slots: int, axis: str = "model",
                data_axes: tuple = ("pod", "data")):
    """Activate mesh-native sparse memory for `num_slots` slots sharded over
    `axis` (falling back to 1 shard when the mesh lacks the axis — the S=1
    layout is the canonical single-scratch-row buffer, so everything keeps
    working, just unsharded). `data_axes` names the orthogonal
    data-parallel axes of a 2D (data × model) mesh: axes actually present
    shard the *batch* dimension of every memory operand and state leaf,
    composing data parallelism with slot sharding (pass ``data_axes=()``
    for a replicated batch on a 2D mesh)."""
    shards = int(mesh.shape[axis]) if axis in mesh.axis_names else 1
    if num_slots % shards:
        raise ValueError(
            f"num_slots={num_slots} not divisible by the {shards}-way "
            f"{axis!r} mesh axis — slot sharding needs equal blocks")
    data_axes = tuple(a for a in data_axes
                      if a != axis and a in mesh.axis_names)
    degree = 1
    for a in data_axes:
        degree *= int(mesh.shape[a])
    if degree == 1:
        data_axes = ()
    ctx = MemShardCtx(mesh=mesh, axis=axis, num_slots=num_slots,
                      shards=shards, data_axes=data_axes, data_degree=degree)
    old = _CTX.ctx
    _CTX.ctx = ctx
    try:
        yield ctx
    finally:
        _CTX.ctx = old


def current() -> Optional[MemShardCtx]:
    return _CTX.ctx


def route_ctx(buf_rows: int) -> Optional[MemShardCtx]:
    """The active context, iff a buffer with `buf_rows` rows is in its
    sharded layout and the layout is actually distributed (S > 1; the S=1
    layout is canonical and takes the ordinary kernel path)."""
    ctx = _CTX.ctx
    if ctx is not None and ctx.shards > 1 and buf_rows == ctx.sharded_rows:
        return ctx
    return None


def default_shards(num_slots: int) -> int:
    """Shard count `init_state` should build for: the active context's,
    when it matches this memory size."""
    ctx = _CTX.ctx
    if ctx is not None and ctx.num_slots == num_slots:
        return ctx.shards
    return 1


def init_layout(num_slots: int, mem_shards: Optional[int], *bufs):
    """Apply the shard layout to freshly-initialized canonical buffers —
    the single `init_state` helper shared by SAM, the SDNC, and the LM
    memory layer. Resolves the shard count (explicit ``mem_shards`` beats
    the active context's default) and re-layouts each buffer when actually
    sharded; S=1 returns the canonical buffers unchanged."""
    shards = default_shards(num_slots) if mem_shards is None else mem_shards
    if shards > 1:
        bufs = tuple(to_shard_layout(b, num_slots, shards) for b in bufs)
    return bufs if len(bufs) != 1 else bufs[0]


class MemLayout(NamedTuple):
    """Resolved layout of a memory/usage buffer, as the step functions
    consume it: `valid_n`/`scratch_row` for the ordinary kernel dispatch
    (None on the mesh route, which derives its own local values)."""

    kind: str                       # "mesh" | "canonical" | "legacy"
    valid_n: Optional[int]
    scratch_row: Optional[int]
    ctx: Optional[MemShardCtx]


def memory_layout(num_slots: int, buf_rows: int) -> MemLayout:
    """Classify a buffer with `buf_rows` rows for a logical memory of
    `num_slots` slots. Raises on an unrecognized row count — a sharded
    buffer used outside its `memory_mesh` context must fail loudly, not
    sweep the per-shard scratch rows as if they were logical slots."""
    ctx = route_ctx(buf_rows)
    if ctx is not None and ctx.num_slots == num_slots:
        return MemLayout("mesh", None, None, ctx)
    if buf_rows == num_slots + SCRATCH_ROWS:
        return MemLayout("canonical", num_slots, num_slots, None)
    if buf_rows == num_slots:
        return MemLayout("legacy", None, None, None)
    raise ValueError(
        f"memory buffer with {buf_rows} rows matches no known layout for "
        f"num_slots={num_slots}: expected {num_slots} (legacy), "
        f"{num_slots + SCRATCH_ROWS} (canonical scratch-row), or an active "
        f"mem_shard.memory_mesh() context whose sharded layout has "
        f"N + shards rows")


# --------------------------------------------------------------------------
# Layout conversion (canonical (B, N+1, ...) <-> sharded (B, N+S, ...))
# --------------------------------------------------------------------------

def _fill_value(dtype) -> int:
    """Scratch-row fill: `LA_SCRATCH` for int32 usage tables, 0 for
    everything else. Keyed on the itemsize, not bare integer-ness: int8
    *memory* rows (mem_dtype="int8") are integer leaves too, and
    LA_SCRATCH does not even fit in them."""
    dt = jnp.dtype(dtype)
    return LA_SCRATCH if (jnp.issubdtype(dt, jnp.integer)
                          and dt.itemsize >= 4) else 0


def to_shard_layout(x, num_slots: int, shards: int):
    """Re-layout a canonical (B, N+1, ...) — or legacy (B, N, ...) — buffer
    into the (B, N+S, ...) sharded layout. Scratch rows are (re)initialized
    (0 for float memory, `LA_SCRATCH` for integer usage tables): scratch
    contents are meaningless by contract, so none are preserved."""
    N, S = num_slots, shards
    B, tail = x.shape[0], x.shape[2:]
    blocks = x[:, :N].reshape((B, S, N // S) + tail)
    fill = jnp.full((B, S, SCRATCH_ROWS) + tail, _fill_value(x.dtype),
                    x.dtype)
    return jnp.concatenate([blocks, fill], axis=2).reshape(
        (B, N + S * SCRATCH_ROWS) + tail)


def from_shard_layout(x, num_slots: int, shards: int):
    """Inverse of `to_shard_layout`: back to the canonical (B, N+1, ...)
    layout (scratch row freshly initialized)."""
    N, S = num_slots, shards
    B, tail = x.shape[0], x.shape[2:]
    blocks = x.reshape((B, S, N // S + SCRATCH_ROWS) + tail)
    logical = blocks[:, :, :N // S].reshape((B, N) + tail)
    fill = jnp.full((B, SCRATCH_ROWS) + tail, _fill_value(x.dtype), x.dtype)
    return jnp.concatenate([logical, fill], axis=1)


def np_relayout(arr: np.ndarray, num_slots: int, from_shards: int,
                to_shards: int) -> np.ndarray:
    """Host-side (numpy) layout conversion between shard counts — the
    checkpoint restore path (checkpoint/ckpt.py) re-layouts saved memory
    leaves with this, so a checkpoint saved on mesh A restores on mesh B
    (or on a single device: to_shards=1 is the canonical layout)."""
    N = num_slots
    for s in (from_shards, to_shards):
        if s < 1 or N % s:
            raise ValueError(f"invalid shard count {s} for num_slots={N}")
    B, tail = arr.shape[0], arr.shape[2:]
    fill = LA_SCRATCH if (np.issubdtype(arr.dtype, np.integer)
                          and arr.dtype.itemsize >= 4) else 0
    blocks = arr.reshape((B, from_shards, N // from_shards + SCRATCH_ROWS)
                         + tail)
    logical = blocks[:, :, :N // from_shards].reshape((B, N) + tail)
    out_blocks = logical.reshape((B, to_shards, N // to_shards) + tail)
    pad = np.full((B, to_shards, SCRATCH_ROWS) + tail, fill, arr.dtype)
    return np.concatenate([out_blocks, pad], axis=2).reshape(
        (B, N + to_shards * SCRATCH_ROWS) + tail)


def np_relayout_ann(buckets: np.ndarray, cursor: np.ndarray, num_slots: int,
                    to_partitions: int):
    """Host-side (numpy) re-partitioning of an LSH index between ownership
    partition counts — the checkpoint restore path's ANN counterpart of
    `np_relayout` (save on mesh A, restore on mesh B / single device).

    Bucket contents are *global* slot indices but their placement is
    layout-local (which sub-ring a slot sits in, and where its ring
    cursor points, depend on the partition count), so a partition-count
    change cannot be a reshape: every entry is re-routed to its new
    owner's sub-ring. The deterministic remap rule: per (batch, table,
    bucket), entries are drained oldest→newest from each old sub-ring, old
    partitions visited in ascending order, and re-inserted in that order
    into the new sub-rings — when a new sub-ring overflows its depth
    d = bucket_size/P, the oldest drained entries drop first, exactly the
    ring-overwrite semantics a live rebuild would apply. Total per-bucket
    capacity (bucket_size = P·d) is preserved and merging partitions only
    *grows* per-owner capacity, so S→1 (and the S→1→S round trip) loses
    nothing; any move that shrinks a sub-ring below its entry count —
    1→S included — drops the oldest entries of the overfull sub-rings
    (documented, tested in tests/test_mesh_parity.py and
    tests/test_checkpoint_layout.py).

    Python-loop implementation over (B, T, n_buckets) — restore is a rare,
    host-side path; sizes are a few thousand buckets."""
    B, T, nb, p_from, d_from = buckets.shape
    cap = p_from * d_from
    if cap % to_partitions or num_slots % to_partitions:
        raise ValueError(
            f"cannot re-partition LSH index to P={to_partitions}: bucket "
            f"capacity {cap} and num_slots={num_slots} must both divide")
    d_to = cap // to_partitions
    blk = num_slots // to_partitions
    out_b = np.full((B, T, nb, to_partitions, d_to), -1, np.int32)
    out_c = np.zeros((B, T, nb, to_partitions), np.int32)
    for b in range(B):
        for t in range(T):
            for k in range(nb):
                drained = [[] for _ in range(to_partitions)]
                for p in range(p_from):
                    cur = int(cursor[b, t, k, p])
                    for j in range(d_from):       # oldest → newest
                        e = int(buckets[b, t, k, p, (cur + j) % d_from])
                        if e >= 0:
                            drained[e // blk].append(e)
                for p, seq in enumerate(drained):
                    seq = seq[-d_to:]             # overflow: oldest drop
                    out_b[b, t, k, p, :len(seq)] = seq
                    out_c[b, t, k, p] = len(seq) % d_to
    return out_b, out_c


# Layout transforms and sharding specs key on the *field name and dim
# position* of the slot leaves (`core.types.SLOT_LEAVES` — the same single
# set the checkpoint migration shims trust), never on a bare size match: a
# controller hidden width that happens to equal N+1 (or a segment count
# equal to N+S) must not be mistaken for a memory buffer.

def _leaf_name(path) -> str:
    if not path:
        return ""
    k = path[-1]
    return str(getattr(k, "name", getattr(k, "key", getattr(k, "idx", k))))


def _slot_dim(name: str, leaf) -> Optional[int]:
    """Dim index of the sharding axis for a named state leaf: -2 for the
    memory buffer ((..., rows, W)) and the ANN bucket table
    ((..., P, d)), -1 for the usage table ((..., rows)) and the ANN cursor
    ((..., P)). None for anything that is not a slot-dimension leaf
    (`SLOT_LEAVES` / `ANN_LEAVES`)."""
    if name not in SLOT_LEAVES and name not in ANN_LEAVES:
        return None
    if not hasattr(leaf, "ndim"):
        return None
    if name in ("memory", "buckets"):
        return leaf.ndim - 2 if leaf.ndim >= 2 else None
    return leaf.ndim - 1 if leaf.ndim >= 1 else None


def _leaf_extent(ctx: MemShardCtx, name: str) -> int:
    """Size the sharding dim of a named leaf must have in this context's
    layout: N + S rows for memory/usage, S partitions for the ANN index."""
    return ctx.shards if name in ANN_LEAVES else ctx.sharded_rows


def _map_slot_leaves(tree, fn):
    """tree_map that hands `fn(name, dim, leaf)` only the named slot leaves
    (dim = their sharding axis); everything else passes through
    `fn(name, None, leaf)`."""
    def visit(path, leaf):
        name = _leaf_name(path)
        return fn(name, _slot_dim(name, leaf), leaf)
    return jax.tree_util.tree_map_with_path(visit, tree)


def to_shard_state(tree, ctx: Optional[MemShardCtx] = None):
    """Re-layout the named slot-dimension leaves (memory / last_access /
    usage, identified by field name + dim position) of a recurrent-state
    tree into the active context's sharded layout. Everything else
    (controller state, indices, the SDNC's (B, N, K_L) link matrices —
    replicated by design) passes through."""
    ctx = ctx or current()
    if ctx is None or ctx.shards == 1:
        return tree
    canon = ctx.num_slots + SCRATCH_ROWS

    def conv(name, dim, leaf):
        if (name in ANN_LEAVES or dim is None or dim != 1
                or leaf.shape[dim] != canon):
            return leaf
        return to_shard_layout(leaf, ctx.num_slots, ctx.shards)
    return _map_slot_leaves(tree, conv)


def from_shard_state(tree, ctx: Optional[MemShardCtx] = None):
    """Inverse of `to_shard_state` (back to the canonical layout)."""
    ctx = ctx or current()
    if ctx is None or ctx.shards == 1:
        return tree

    # The ANN index is NOT converted: its partition count is *semantic*
    # (it determines per-bucket sub-ring depths and hence candidate sets),
    # not mere placement — re-partitioning an index is a remap/rebuild
    # (`np_relayout_ann`, or `ann_build` on the new layout), never a
    # reshape.
    def conv(name, dim, leaf):
        if (name in ANN_LEAVES or dim is None or dim != 1
                or leaf.shape[dim] != ctx.sharded_rows):
            return leaf
        return from_shard_layout(leaf, ctx.num_slots, ctx.shards)
    return _map_slot_leaves(tree, conv)


def relayout_state(tree, num_slots: int, new_shards: int):
    """Convert the named slot-dimension leaves between shard counts,
    inferring the current count from the row dimension (rows = N + S).
    Elastic scaling uses this to move a recurrent carry onto a mesh with a
    different model degree (distributed/elastic.py). ANN index
    (buckets, cursor) pairs are re-partitioned to `new_shards` as well —
    on the host, via `np_relayout_ann`, since their partition count is
    semantic, not mere placement — so an LSH-mode carry keeps the
    mesh-native index path after a scale event instead of silently
    falling back to the replicated-index read. An index whose bucket
    capacity cannot take `new_shards` partitions is left as-is with a
    warning (that fallback is correct, just replicated)."""
    def conv(name, dim, leaf):
        if name in ANN_LEAVES or dim is None or dim != 1:
            return leaf
        s_from = leaf.shape[dim] - num_slots
        if s_from < 1 or num_slots % s_from or s_from == new_shards:
            return leaf
        x = from_shard_layout(jnp.asarray(leaf), num_slots, s_from)
        return to_shard_layout(x, num_slots, new_shards)
    return _relayout_ann_leaves(_map_slot_leaves(tree, conv), num_slots,
                                new_shards)


def _relayout_ann_leaves(tree, num_slots: int, to_partitions: int):
    """Re-partition every sibling (buckets, cursor) ANN pair of `tree` to
    `to_partitions` (host-side `np_relayout_ann` — the two leaves move
    together because ring order lives in the cursor). Pairs already at the
    target count, non-index decoys (wrong rank), and indivisible
    capacities (warned) pass through."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    leaves = [leaf for _, leaf in flat]
    groups: dict = {}
    for i, (path, leaf) in enumerate(flat):
        name = _leaf_name(path)
        if name in ANN_LEAVES and hasattr(leaf, "ndim"):
            groups.setdefault(tuple(str(k) for k in path[:-1]), {})[name] \
                = (i, leaf)
    for parent, g in groups.items():
        if set(g) != {"buckets", "cursor"}:
            continue
        bi, b = g["buckets"]
        ci, c = g["cursor"]
        if (b.ndim != 5 or c.ndim != 4 or b.shape[:4] != c.shape
                or b.shape[-2] == to_partitions):
            continue
        cap = b.shape[-2] * b.shape[-1]
        if to_partitions < 1 or cap % to_partitions \
                or num_slots % to_partitions:
            warnings.warn(
                f"LSH index at {'/'.join(parent)} (P={b.shape[-2]}, "
                f"bucket capacity {cap}) cannot re-partition to "
                f"{to_partitions} — leaving it as-is (reads fall back to "
                f"the replicated-index path)", UserWarning, stacklevel=3)
            continue
        nb, nc = np_relayout_ann(np.asarray(jax.device_get(b)),
                                 np.asarray(jax.device_get(c)),
                                 num_slots, to_partitions)
        leaves[bi], leaves[ci] = jnp.asarray(nb), jnp.asarray(nc)
    return jax.tree_util.tree_unflatten(treedef, leaves)


# --------------------------------------------------------------------------
# State specs ("shard-consistent state specs" for jit/device_put/constraints)
# --------------------------------------------------------------------------

def _data_entry(ctx: MemShardCtx):
    """The PartitionSpec entry for a data-sharded batch dim (a single axis
    name, or the axis tuple when the batch spans several data axes)."""
    return ctx.data_axes if len(ctx.data_axes) > 1 else ctx.data_axes[0]


def leaf_spec(ctx: MemShardCtx, dim: Optional[int], shape,
              extent: Optional[int] = None) -> P:
    """PartitionSpec placing the mesh axis on `dim` — the sharding axis a
    named slot leaf resolved to via `_slot_dim` (works for live state
    leaves and for engine-stacked versions of them, e.g. the chunked
    unroll's (S_seg, B, N+S, W) boundary-checkpoint stack, whose rows dim
    is still ndim-2, or a stacked (S_seg, B, T, nb, P, d) ANN bucket
    table). ``extent`` is the size the dim must have to shard (default:
    the sharded row count; the ANN leaves pass the shard count). Anything
    else — including a slot leaf whose dim size does not match the
    context's layout — is explicitly replicated.

    Under a 2D (data × model) context, the leaf's batch dim — a fixed
    offset from `dim`: rows dim − 1 for memory/usage leaves, partition
    dim − 3 for the (B, T, nb, P[, d]) ANN leaves, so stacked variants
    resolve correctly too — additionally shards over the data axes
    whenever its size divides the data degree."""
    if extent is None:
        extent = ctx.sharded_rows
    if dim is None or shape[dim] != extent:
        return P()
    entries = [ctx.axis if i == dim else None for i in range(len(shape))]
    bdim = dim - (3 if extent == ctx.shards else 1)
    if (ctx.data_degree > 1 and bdim >= 0
            and shape[bdim] % ctx.data_degree == 0):
        entries[bdim] = _data_entry(ctx)
    return P(*entries)


def state_shardings(tree, ctx: Optional[MemShardCtx] = None):
    """NamedSharding pytree for a state tree: slot-sharded memory/usage
    leaves and ownership-partitioned ANN index leaves (by field name + dim
    position) on the mesh axis, everything else replicated. None without
    an active (distributed) context."""
    ctx = ctx or current()
    if ctx is None or ctx.shards == 1:
        return None

    def spec(name, dim, leaf):
        if dim is None:
            # Live (batch-leading) non-slot leaves follow the batch onto
            # the data axes in a 2D context; scalars (step counters) and
            # indivisible batches stay replicated. This helper is for
            # *live* states — stacked (T, B, ...) trees go through
            # `constrain_state`, which leaves non-slot leaves to GSPMD.
            if (ctx.data_degree > 1 and getattr(leaf, "ndim", 0) >= 1
                    and leaf.shape[0] % ctx.data_degree == 0):
                return P(_data_entry(ctx))
            return P()
        return leaf_spec(ctx, dim, leaf.shape, _leaf_extent(ctx, name))

    return _map_slot_leaves(tree, lambda name, dim, leaf: NamedSharding(
        ctx.mesh, spec(name, dim, leaf)))


def constrain_state(tree):
    """`with_sharding_constraint` every leaf per `leaf_spec` — sharded
    memory rows (and ANN index partitions) on the mesh axis, explicit
    replication elsewhere (this is what keeps the chunked engine's
    O(C·K·W) delta stacks replicated and its dense boundary checkpoints —
    the ANN state riding along — sharded like the live state). No-op
    without an active distributed context.

    Under a 2D (data × model) context the non-slot leaves pass through
    *unconstrained* instead: their batch dim position is ambiguous (dim 0
    live, dim 1 stacked), and pinning them to explicit replication would
    force a data-axis all-gather of batch-sharded activations — GSPMD
    propagates their placement from the operands. Slot leaves keep their
    full (batch over data, rows/partitions over model) constraint, which
    `leaf_spec` resolves for live and stacked shapes alike."""
    ctx = current()
    if ctx is None or ctx.shards == 1:
        return tree

    def visit(name, dim, leaf):
        if dim is None and ctx.data_degree > 1:
            return leaf
        return jax.lax.with_sharding_constraint(
            leaf, NamedSharding(ctx.mesh,
                                leaf_spec(ctx, dim, leaf.shape,
                                          _leaf_extent(ctx, name))))
    return _map_slot_leaves(tree, visit)


def place_state(tree, ctx: Optional[MemShardCtx] = None):
    """`device_put` a state tree with its shard-consistent shardings (no-op
    without an active distributed context)."""
    sh = state_shardings(tree, ctx)
    return tree if sh is None else jax.device_put(tree, sh)


def ckpt_layout(ctx: Optional[MemShardCtx] = None):
    """(num_slots, shards, data_degree) to record in a checkpoint manifest,
    or None. Only the first two determine the row layout; the data degree
    is recorded for provenance (placement at save time) — restore accepts
    2-tuples from older callers unchanged."""
    ctx = ctx or current()
    return None if ctx is None else (ctx.num_slots, ctx.shards,
                                     ctx.data_degree)


# --------------------------------------------------------------------------
# shard_map bodies
# --------------------------------------------------------------------------
#
# Conventions: `mem`/`la` enter sharded over ctx.axis on the row dimension;
# every other operand (queries, indices, weights, step) is replicated over
# the model axis. In a 2D (data × model) context every batch-leading
# operand — memory buffers and queries/indices/weights alike — additionally
# shards its batch dim over the data axes (`_bentry`), so the bodies run on
# the local batch block and *every* collective below still names only
# ctx.axis: the data axes carry zero memory-path collective traffic by
# construction (asserted against the compiled HLO by
# benchmarks/bench_shard.py). Indices crossing the boundary are global;
# inside a body, shard s owns global rows [s·local_n, (s+1)·local_n) and
# its local scratch row is local_n. Inner kernel calls use the caller's
# ``backend`` untouched, with valid_n/scratch_row = local_n — exactly the
# canonical dispatch, one shard at a time.

def _smap(ctx, body, in_specs, out_specs):
    return jax.shard_map(body, mesh=ctx.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _bentry(ctx, batch: int):
    """PartitionSpec entry for a batch dim of `batch` rows: the data axes
    when the context has them and they divide the batch, else None (a
    replicated batch — the 1D behavior, and the graceful fallback for an
    odd batch on a 2D mesh)."""
    if ctx.data_degree > 1 and batch % ctx.data_degree == 0:
        return _data_entry(ctx)
    return None


def _bspec(be) -> P:
    """Spec for a model-replicated, batch-leading operand (queries,
    indices, weights): batch over the data axes, everything else
    replicated. `P()` when the batch itself is replicated."""
    return P() if be is None else P(be)


def _step_spec(be, step, batch: int) -> P:
    """Spec for a step counter that is either a scalar (training: one
    global step) or a (B, 1) per-lane vector (serving:
    `init_memory_states(per_lane_step=True)`): the vector form follows the
    batch onto the data axes, the scalar stays replicated."""
    if getattr(step, "ndim", 0) >= 1 and step.shape[0] == batch:
        return _bspec(be)
    return P()


def _mem_spec(ctx, be=None) -> P:
    return P(be, ctx.axis, None)


def _vec_spec(ctx, be=None) -> P:
    return P(be, ctx.axis)


def _concat_shards(x, axis_name: str):
    """all_gather a (..., K) per-shard tensor into (..., S·K), shard-major —
    so position order equals (shard, local rank) order, which is global-
    index order for ties (each shard owns a contiguous ascending index
    block and ranks ties by ascending index)."""
    g = jax.lax.all_gather(x, axis_name)          # (S, ..., K)
    g = jnp.moveaxis(g, 0, -2)                    # (..., S, K)
    return g.reshape(g.shape[:-2] + (g.shape[-2] * g.shape[-1],))


def _own_local(ctx, idx, s):
    """(own mask, local index) for global indices on shard s; non-owned
    entries route to the shard's scratch row."""
    own = (idx // ctx.local_n) == s
    lidx = jnp.where(own, idx - s * ctx.local_n, ctx.local_n)
    return own, lidx


def topk_read_sharded(ctx: MemShardCtx, q, mem, k: int, *, backend=None,
                      block_n: int = 512):
    """Mesh-native `ops.topk_read`: shard-local top-K over the local rows,
    then a (B, H, K) score+index all-gather and a replicated K-merge.
    Exactly matches the global oracle including tie order (see
    `_concat_shards`). Returns (vals, idx) with *global* indices,
    replicated."""
    if k > ctx.local_n:
        raise ValueError(
            f"top-{k} read needs K <= N/shards = {ctx.local_n} candidates "
            f"per shard")

    def body(q, mem_l):
        vals, lidx = _ops.topk_read(q, mem_l, k, backend=backend,
                                    block_n=block_n, valid_n=ctx.local_n)
        s = jax.lax.axis_index(ctx.axis)
        gidx = lidx + s * ctx.local_n
        av = _concat_shards(vals, ctx.axis)               # (B, H, S·K)
        ai = _concat_shards(gidx, ctx.axis)
        mvals, pos = jax.lax.top_k(av, k)
        return mvals, jnp.take_along_axis(ai, pos, axis=-1)

    be = _bentry(ctx, mem.shape[0])
    return _smap(ctx, body, (_bspec(be), _mem_spec(ctx, be)),
                 (_bspec(be), _bspec(be)))(q, mem)


def lra_topn_sharded(ctx: MemShardCtx, la, n: int, *, backend=None):
    """Mesh-native `ops.lra_topn`: shard-local LRA top-n (kernel dispatch,
    scratch entry excluded by valid_n), then an (B, n) staleness+index
    all-gather and a replicated merge. Global indices, replicated."""
    if n > ctx.local_n:
        raise ValueError(
            f"LRA top-{n} needs n <= N/shards = {ctx.local_n} per shard")

    def body(la_l):
        lidx = _ops.lra_topn(la_l, n, backend=backend, valid_n=ctx.local_n)
        lv = jnp.take_along_axis(la_l, lidx, axis=1)
        s = jax.lax.axis_index(ctx.axis)
        av = _concat_shards(lv, ctx.axis)                 # (B, S·n)
        ai = _concat_shards(lidx + s * ctx.local_n, ctx.axis)
        _, pos = jax.lax.top_k(-av, n)
        return jnp.take_along_axis(ai, pos, axis=-1)

    be = _bentry(ctx, la.shape[0])
    return _smap(ctx, body, (_vec_spec(ctx, be),), _bspec(be))(la)


def usage_argmin_sharded(ctx: MemShardCtx, la, *, backend=None):
    return lra_topn_sharded(ctx, la, 1, backend=backend)[:, 0]


def gather_rows_sharded(ctx: MemShardCtx, mem, idx):
    """Mesh-native row gather: each shard gathers the rows it owns (others
    masked to zero) and a psum assembles the replicated (B, J, W) result —
    O(B·J·W) collective, independent of N. Differentiable: the transpose
    scatters cotangents back into the owning shard only."""

    def body(mem_l, idx):
        s = jax.lax.axis_index(ctx.axis)
        own, lidx = _own_local(ctx, idx, s)
        b = jnp.arange(mem_l.shape[0])[:, None]
        rows = mem_l[b, lidx]
        # zeros_like, not the literal 0.0: int8 rows (mem_dtype="int8")
        # must mask and psum in their own dtype (exactly one shard owns
        # each row, so the int sum never overflows).
        masked = jnp.where(own[..., None], rows, jnp.zeros_like(rows))
        return jax.lax.psum(masked, ctx.axis)

    be = _bentry(ctx, mem.shape[0])
    return _smap(ctx, body, (_mem_spec(ctx, be), _bspec(be)),
                 _bspec(be))(mem, idx)


def scatter_rows_sharded(ctx: MemShardCtx, mem, idx, rows, mode: str, *,
                         backend=None, mem_scale=None, rows_scale=None):
    """Mesh-native `ops.scatter_rows`: no collective at all — each shard
    scatters the (index, row) pairs it owns through the ordinary kernel
    dispatch (scratch_row=local_n); non-owned pairs land on the shard's
    scratch row ('add' with the row masked to zero, so the scratch row and
    its cotangent stay clean; 'set' values are irrelevant there by the
    scratch contract). With ``mem_scale`` (int8 storage) the scale leaf
    shards with the rows and the result is (mem', mem_scale')."""

    if mem_scale is not None:
        # rows_scale enters as an explicit (replicated) operand — shard_map
        # bodies must not close over traced arrays. A None rows_scale rides
        # along as a zero-width dummy.
        rs = rows_scale if rows_scale is not None \
            else jnp.zeros(idx.shape[:1] + (0,), jnp.float32)

        def body_q(mem_l, scale_l, idx, rows, rs):
            s = jax.lax.axis_index(ctx.axis)
            own, lidx = _own_local(ctx, idx, s)
            r = rows
            if mode == "add":
                r = jnp.where(own[..., None], r, jnp.zeros_like(r))
            return _ops.scatter_rows(mem_l, lidx, r, mode=mode,
                                     backend=backend,
                                     scratch_row=ctx.local_n,
                                     mem_scale=scale_l,
                                     rows_scale=rs if rs.shape[-1] else None)

        be = _bentry(ctx, mem.shape[0])
        return _smap(ctx, body_q,
                     (_mem_spec(ctx, be), _vec_spec(ctx, be), _bspec(be),
                      _bspec(be), _bspec(be)),
                     (_mem_spec(ctx, be), _vec_spec(ctx, be)))(
                         mem, mem_scale, idx, rows, rs)

    def body(mem_l, idx, rows):
        s = jax.lax.axis_index(ctx.axis)
        own, lidx = _own_local(ctx, idx, s)
        if mode == "add":
            rows = jnp.where(own[..., None], rows, 0.0)
        return _ops.scatter_rows(mem_l, lidx, rows, mode=mode,
                                 backend=backend, scratch_row=ctx.local_n)

    be = _bentry(ctx, mem.shape[0])
    return _smap(ctx, body, (_mem_spec(ctx, be), _bspec(be), _bspec(be)),
                 _mem_spec(ctx, be))(mem, idx, rows)


def sparse_write_update_sharded(ctx: MemShardCtx, mem, la, write_idx,
                                write_w, a, lra_idx, step, *, delta: float,
                                backend=None, mem_scale=None):
    """Mesh-native fused SAM write: writes route to their owning shard by
    masking (weight zeroed elsewhere), the LRA erase routes the same way,
    and each shard runs the ordinary fused kernel on its local block — no
    collective in the forward pass. The usage stamp is shard-local too
    (zero-weight non-owned entries never exceed delta; the scratch entry is
    pinned at LA_SCRATCH and scatter-max can never lower it). With
    ``mem_scale`` (int8 storage) the scale leaf shards with the rows —
    each shard re-quantizes its owned rows locally — and the result is
    (mem', la', mem_scale'). A zero-weight non-owned contribution leaves
    the row's accumulated f32 value unchanged, and `core.quant`'s
    round-trip is the identity on its own output (the max entry always
    re-quantizes to ±127), so non-owning shards do not drift their copy —
    they never store one anyway."""

    if mem_scale is not None:
        def body_q(mem_l, la_l, scale_l, widx, ww, a, lra, step):
            s = jax.lax.axis_index(ctx.axis)
            own_w, l_widx = _own_local(ctx, widx, s)
            l_ww = jnp.where(own_w, ww, 0.0)
            _, l_lra = _own_local(ctx, lra, s)
            return _ops.sparse_write_update(
                mem_l, la_l, l_widx, l_ww, a, l_lra, step, delta=delta,
                backend=backend, scratch_row=ctx.local_n,
                mem_scale=scale_l)

        be = _bentry(ctx, mem.shape[0])
        sspec = _step_spec(be, step, mem.shape[0])
        return _smap(ctx, body_q,
                     (_mem_spec(ctx, be), _vec_spec(ctx, be),
                      _vec_spec(ctx, be), _bspec(be), _bspec(be),
                      _bspec(be), _bspec(be), sspec),
                     (_mem_spec(ctx, be), _vec_spec(ctx, be),
                      _vec_spec(ctx, be)))(
                         mem, la, mem_scale, write_idx, write_w, a,
                         lra_idx, step)

    def body(mem_l, la_l, widx, ww, a, lra, step):
        s = jax.lax.axis_index(ctx.axis)
        own_w, l_widx = _own_local(ctx, widx, s)
        l_ww = jnp.where(own_w, ww, 0.0)
        _, l_lra = _own_local(ctx, lra, s)
        return _ops.sparse_write_update(
            mem_l, la_l, l_widx, l_ww, a, l_lra, step, delta=delta,
            backend=backend, scratch_row=ctx.local_n)

    be = _bentry(ctx, mem.shape[0])
    sspec = _step_spec(be, step, mem.shape[0])
    return _smap(ctx, body,
                 (_mem_spec(ctx, be), _vec_spec(ctx, be), _bspec(be),
                  _bspec(be), _bspec(be), _bspec(be), sspec),
                 (_mem_spec(ctx, be), _vec_spec(ctx, be)))(
                     mem, la, write_idx, write_w, a, lra_idx, step)


# --------------------------------------------------------------------------
# Sharded LSH index (ANN) ops — the bucket tables shard by slot ownership
# --------------------------------------------------------------------------
#
# The index layout is `core.ann`'s ownership-partitioned ANNState with
# P == ctx.shards: buckets (B, T, nb, S, d), cursor (B, T, nb, S), sharded
# over the partition dimension — each device holds only the sub-rings
# covering the slots it owns (1/S of the index). Inserts are collective-
# free: a shard hashes the rows it stores locally and scatters only owned
# indices (non-owned scatters route out of bounds and drop — the bucket-
# table analogue of the scratch-row trick). Queries hash shard-local,
# re-rank the local candidates against the *local* memory block (every
# local candidate is an owned slot), and merge per-shard top-K sets through
# the same O(B·K) score+index all-gather the exact-read path uses.

def _ann_specs(ctx, be=None):
    """(buckets, cursor) PartitionSpecs: partition dim on the mesh axis,
    batch dim on the data axes when active."""
    return (P(be, None, None, ctx.axis, None),
            P(be, None, None, ctx.axis))


def ann_insert_sharded(ctx: MemShardCtx, planes, state, idx, mem, cfg):
    """Mesh-native `ann.ann_insert`: no collective at all. Each shard reads
    the rows it owns from its local memory block (non-owned indices resolve
    to the scratch row, whose hash is discarded), hashes them, and inserts
    the owned indices into its local sub-rings; rank/cursor sequencing
    counts only owned same-bucket pairs — exactly the (bucket, owner)
    grouping of the canonical partitioned insert, one owner at a time."""
    from repro.core import ann as ann_lib
    T = cfg.lsh_tables

    def body(planes, buckets_l, cursor_l, idx, mem_l):
        B = idx.shape[0]
        d = buckets_l.shape[-1]
        s = jax.lax.axis_index(ctx.axis)
        own, lidx = _own_local(ctx, idx, s)
        rows = mem_l[jnp.arange(B)[:, None], lidx]            # (B, J, W)
        if jnp.issubdtype(rows.dtype, jnp.integer):
            # int8 storage: hash the raw rows upcast to f32 — projection
            # signs are invariant to the positive per-row dequant scale.
            rows = rows.astype(jnp.float32)
        ids = ann_lib.lsh_hash(planes, rows, backend=cfg.backend)  # (B,J,T)
        b = jnp.arange(B)[:, None, None]
        t = jnp.arange(T)[None, None, :]
        # Owned entries form one ownership group (this shard); non-owned
        # entries group with nothing, so they neither rank nor count —
        # the same (bucket, owner) sequencing as the canonical insert,
        # restricted to one owner (ann.ring_ranks is the single source).
        rank, count = ann_lib.ring_ranks(
            ids, own[:, :, None] & own[:, None, :])
        cur = cursor_l[b, t, ids, 0]                          # (B, J, T)
        # Non-owned entries scatter out of bounds and drop.
        pos = jnp.where(own[..., None], (cur + rank) % d, d)
        buckets = buckets_l.at[b, t, ids, 0, pos].set(
            jnp.broadcast_to(idx[:, :, None], ids.shape), mode="drop")
        bid = jnp.where(own[..., None], ids, buckets_l.shape[2])
        cursor = cursor_l.at[b, t, bid, 0].set((cur + count) % d,
                                               mode="drop")
        return buckets, cursor

    be = _bentry(ctx, mem.shape[0])
    bspec, cspec = _ann_specs(ctx, be)
    buckets, cursor = _smap(
        ctx, body, (P(), bspec, cspec, _bspec(be), _mem_spec(ctx, be)),
        (bspec, cspec))(planes, state.buckets, state.cursor, idx, mem)
    return type(state)(buckets=buckets, cursor=cursor)


def lsh_candidate_topk_sharded(ctx: MemShardCtx, planes, state, q, mem,
                               extra_idx, k: int, cfg, mem_scale=None):
    """Mesh-native LSH candidate selection: each shard hashes the
    (replicated) queries, gathers its local sub-rings' candidates plus the
    owned entries of `extra_idx` (the freshly written rows), re-ranks them
    against its local memory block, takes a local top-K, and the per-shard
    (B, H, K) score+index sets merge through the existing all-gather +
    replicated K-merge — O(B·H·K) collective, independent of N and of the
    bucket-table size. Candidate order (local sub-rings, then owned
    extras, shard-major) equals the canonical `ann.ann_candidates` array's
    position order, so top-K tie-breaking matches the single-device path
    exactly. Returns (B, H, K) *signed* global indices (-1 = no valid
    candidate), replicated."""
    from repro.core import addressing as addr_lib
    from repro.core import ann as ann_lib
    T = cfg.lsh_tables
    d = state.buckets.shape[-1]
    c_local = T * d + extra_idx.shape[-1]
    if k > c_local:
        raise ValueError(
            f"top-{k} LSH read needs K <= per-shard candidates "
            f"{c_local} (= tables*bucket_size/shards + write rows)")

    def body(planes, q, mem_l, buckets_l, widx, scale_l):
        B, H, _ = q.shape
        s = jax.lax.axis_index(ctx.axis)
        ids = ann_lib.lsh_hash(planes, q, backend=cfg.backend)  # (B, H, T)
        b = jnp.arange(B)[:, None, None]
        t = jnp.arange(T)[None, None, :]
        cl = buckets_l[b, t, ids, 0].reshape(B, H, T * d)
        own = (widx // ctx.local_n) == s                        # (B, J)
        extra = jnp.where(own, widx, -1)[:, None, :]
        extra = jnp.broadcast_to(extra, (B, H, widx.shape[-1]))
        cand = jnp.concatenate([cl, extra], axis=-1)            # (B,H,C_l)
        # Local dedup == global dedup: ownership blocks are disjoint.
        cand = addr_lib._dedup(cand)
        lidx = jnp.where(cand >= 0, cand - s * ctx.local_n, ctx.local_n)
        rows = mem_l[jnp.arange(B)[:, None, None], lidx]        # (B,H,C_l,W)
        if jnp.issubdtype(rows.dtype, jnp.integer):
            rows = rows.astype(jnp.float32)
            if scale_l.shape[-1]:
                # Re-rank on *dequantized* rows: scale-invariant in exact
                # arithmetic, but the fused candidate kernel ranks on
                # in-VMEM dequantized values — matching its fp
                # tie-breaking keeps the mesh selection bit-consistent
                # with the single-device reference.
                rows = rows * scale_l[jnp.arange(B)[:, None, None],
                                      lidx][..., None]
        sims = addr_lib._rerank(jax.lax.stop_gradient(q),
                                jax.lax.stop_gradient(rows))
        sims = jnp.where(cand < 0, addr_lib._NEG, sims)
        vals, pos = jax.lax.top_k(sims, k)
        gidx = jnp.take_along_axis(cand, pos, axis=-1)
        av = _concat_shards(vals, ctx.axis)                     # (B, H, S·K)
        ai = _concat_shards(gidx, ctx.axis)
        _, mpos = jax.lax.top_k(av, k)
        return jnp.take_along_axis(ai, mpos, axis=-1)

    be = _bentry(ctx, mem.shape[0])
    bspec, _ = _ann_specs(ctx, be)
    if mem_scale is None:
        # Zero-width dummy keeps the operand list (and specs) static —
        # the scale branch in `body` folds away on `scale_l.shape[-1]`.
        mem_scale = jnp.zeros(mem.shape[:1] + (0,), jnp.float32)
        sspec = _bspec(be)
    else:
        sspec = _vec_spec(ctx, be)
    return _smap(ctx, body,
                 (P(), _bspec(be), _mem_spec(ctx, be), bspec, _bspec(be),
                  sspec),
                 _bspec(be))(planes, q, mem, state.buckets, extra_idx,
                             mem_scale)


def ann_build_sharded(ctx: MemShardCtx, planes, memory, cfg, *,
                      chunk: int | None = None):
    """Mesh-native `ann.ann_build`: each shard bulk-inserts the rows it
    owns into its local sub-table — **no** canonical all-gather of the
    O(N·W) memory, no collective at all (each shard's insert sequence over
    its owned slots in ascending order is exactly the canonical build's
    sequence restricted to that owner, so the result equals the canonical
    P-partitioned build bit-for-bit)."""
    from repro.core import ann as ann_lib
    from repro.core.types import ANNState
    nb = 2 ** cfg.lsh_bits
    T = cfg.lsh_tables
    d = cfg.lsh_bucket_size // ctx.shards

    def body(planes, mem_l):
        B = mem_l.shape[0]
        s = jax.lax.axis_index(ctx.axis)
        n_l = ctx.local_n
        state = ANNState(
            buckets=jnp.full((B, T, nb, 1, d), -1, jnp.int32),
            cursor=jnp.zeros((B, T, nb, 1), jnp.int32))
        J = max(1, min(chunk or d, n_l, d))

        def insert_chunk(st, lidx):                           # lidx: (J,)
            rows_j = jnp.take(mem_l, lidx, axis=1)            # (B, J, W)
            gidx = jnp.broadcast_to((lidx + s * n_l)[None],
                                    (B, lidx.shape[0]))
            return ann_lib.ann_insert(planes, st, gidx, rows_j, cfg), None

        n_full = n_l // J
        main = jnp.arange(n_full * J, dtype=jnp.int32).reshape(n_full, J)
        state, _ = jax.lax.scan(insert_chunk, state, main)
        if n_l % J:
            state, _ = insert_chunk(
                state, jnp.arange(n_full * J, n_l, dtype=jnp.int32))
        return state.buckets, state.cursor

    be = _bentry(ctx, memory.shape[0])
    bspec, cspec = _ann_specs(ctx, be)
    buckets, cursor = _smap(ctx, body, (P(), _mem_spec(ctx, be)),
                            (bspec, cspec))(planes, memory)
    return ANNState(buckets=buckets, cursor=cursor)


def update_last_access_sharded(ctx: MemShardCtx, la, idx, w, step,
                               delta: float):
    """Mesh-native read-side usage stamp (`addressing.update_last_access`):
    shard-local scatter-max at the owned indices; non-owned entries route to
    the pinned scratch entry, where max(LA_SCRATCH, step) is a no-op."""

    def body(la_l, idx, w, step):
        s = jax.lax.axis_index(ctx.axis)
        _, lidx = _own_local(ctx, idx, s)
        b = jnp.arange(la_l.shape[0])[:, None]
        upd = jnp.where(w > delta, step, la_l[b, lidx])
        return la_l.at[b, lidx].max(upd)

    # `step` enters as an explicit operand, not a closure: the per-lane
    # (B, 1) serving form must shard with the batch in a 2D context.
    be = _bentry(ctx, la.shape[0])
    step = jnp.asarray(step)
    return _smap(ctx, body,
                 (_vec_spec(ctx, be), _bspec(be), _bspec(be),
                  _step_spec(be, step, la.shape[0])),
                 _vec_spec(ctx, be))(la, idx, w, step)
