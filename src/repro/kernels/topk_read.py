"""Pallas TPU kernel: tiled content-based top-K addressing (SAM §3.1).

The hot spot of the exact ("linear index") SAM read is the similarity sweep
q·Mᵀ over N memory rows. On TPU we stream M through VMEM in (block_n, W)
tiles — one sweep per batch row serves all H query heads — compute cosine
similarities on the MXU as an (H, block_n) matrix, and merge each tile into
a running per-head top-K held in VMEM scratch. The selection is masked
reductions over `broadcasted_iota` (no sort, no dynamic vector indexing),
so the kernel emits the final top-K directly: there is no host-side merge.

The merge is gated (`sweep_tile`): a row enters only if it beats its
head's running K-th value strictly — at an equal value the running entry,
with its lower index, wins `lax.top_k`'s tie — so a tile runs
min(K, most entrants of any head) insertions, none where no head has an
entrant; and a tile of zero rows (they score exactly 0) is not scored
once every head's K-th value is at least 0. Per tile: the DMA and a
max-abs pass over the tile always; the two similarity matmuls and a few
reductions for a tile with a nonzero row; one reduction pair and (fused
read) one one-hot row pick per insertion. A served memory, zero past its
written rows, scores and merges only its first tiles. The result is
bit-identical to scoring and merging every tile K times.

Grid: (B, N/block_n), sequential over tiles. Block shapes follow the TPU
rule that a block's last two dims divide (8, 128) or equal the array's:
queries (H, W), memory tiles (block_n, W), outputs (H, K).

The sweep helpers here (`reset_topk`, `sweep_tile`, `sorted_topk`) are
shared with the fused read (`kernels/fused_read.py`), so both kernels
tie-break identically to `jax.lax.top_k`: value descending, then lowest
index.

Scratch-row layout: with ``valid_n=N`` the memory may carry extra scratch
rows past N (the persistent (B, N+1, W) buffer, docs/memory-model.md); the
grid tiles cover exactly rows [0, N), so the scratch row is never swept —
no slice of the big buffer is needed to exclude it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CONSUMED = -3e30           # below any cosine sim and the -1e9 validity mask
_HIGHEST = jax.lax.Precision.HIGHEST


def _dot_nt(a, b):
    """(M, W) x (R, W) -> (M, R) in f32 at full precision."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _dot(a, b):
    """(M, R) x (R, W) -> (M, W) in f32 at full precision (exact for the
    one-hot row pick below)."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def reset_topk(vals_ref, idx_ref):
    """Empty running top-K: every slot at CONSUMED, with distinct negative
    sentinel indices so that each of the first K insertions evicts a slot
    of its own."""
    vals_ref[...] = jnp.full(vals_ref.shape, CONSUMED, jnp.float32)
    idx_ref[...] = -1 - jax.lax.broadcasted_iota(jnp.int32, idx_ref.shape, 1)


def sweep_tile(q, m, base, vals_ref, idx_ref, *, scale=None, rows_ref=None):
    """Score one memory tile against every head's query and merge it into
    the running per-head top-K held in VMEM scratch.

    q: (H, W); m: (block_n, W) f32; scale: optional (1, block_n) per-row
    int8 dequantization scales; ``base`` the global index of the tile's
    first row. vals_ref/idx_ref: (H, K) the running top-K as an unordered
    set, holding only indices below ``base`` (`reset_topk` makes it empty,
    `sorted_topk` orders it). With ``rows_ref`` (K, H, W) each entering
    row is picked out of the tile in VMEM into its slot (a one-hot matmul),
    so the fused read never gathers from HBM.

    Similarities are the oracle's ``normalize(q) · normalize(m * scale)``,
    computed on row sums so the per-row factors stay lane-major, at
    HIGHEST in f32. A row enters only if it beats its head's running K-th
    value strictly: an equal value carries a higher index than every
    running entry and would lose `lax.top_k`'s tie. Two gates skip work
    that cannot change the top-K:

    * zero tile: a tile of zero rows scores exactly 0 on every head, so
      where every head's K-th value is at least 0 (once the top-K is full
      of non-negative scores) it is not scored at all.
    * entrants: otherwise the tile's entrants are inserted best first
      (value desc, index asc), each evicting its head's worst entry (the
      lowest value, of equal values the highest index: the one `top_k`
      ranks last) — min(K, most entrants of any head) insertions, none
      where no head has an entrant."""
    H, bn = q.shape[0], m.shape[0]
    K = vals_ref.shape[1]
    kth = jnp.min(vals_ref[...], axis=1, keepdims=True)             # (H, 1)

    @pl.when((jnp.max(jnp.abs(m)) > 0) | (jnp.min(kth) < 0))
    def _score():
        qf = q.astype(jnp.float32)
        qn = qf * jax.lax.rsqrt(jnp.sum(qf * qf, axis=-1, keepdims=True)
                                + 1e-6)
        dot = _dot_nt(qn, m)                                        # (H, bn)
        sq = _dot_nt(jnp.ones((1, m.shape[1]), jnp.float32), m * m)  # (1, bn)
        if scale is not None:
            dot = dot * scale
            sq = sq * (scale * scale)
        sims = dot * jax.lax.rsqrt(sq + 1e-6)
        enter = sims > kth
        steps = jnp.minimum(
            jnp.max(jnp.sum(enter.astype(jnp.int32), axis=1, keepdims=True)),
            K)
        lane = jax.lax.broadcasted_iota(jnp.int32, (H, bn), 1)
        kpos = jax.lax.broadcasted_iota(jnp.int32, (H, K), 1)

        def insert(_, s):
            vt = jnp.max(s, axis=1, keepdims=True)                  # (H, 1)
            jt = jnp.min(jnp.where(s == vt, lane, bn), axis=1, keepdims=True)
            vals, idx = vals_ref[...], idx_ref[...]
            vmin = jnp.min(vals, axis=1, keepdims=True)
            worst = vals == vmin
            drop = jnp.max(jnp.where(worst, idx, -K - 1), axis=1,
                           keepdims=True)
            hot = worst & (idx == drop) & (vt > vmin)
            vals_ref[...] = jnp.where(hot, vt, vals)
            idx_ref[...] = jnp.where(hot, base + jt, idx)
            if rows_ref is not None:
                pick = lane == jt
                row = _dot(pick.astype(jnp.float32), m)              # (H, W)
                if scale is not None:
                    row = row * jnp.sum(jnp.where(pick, scale, 0.0), axis=1,
                                        keepdims=True)
                slot = jnp.min(jnp.where(hot, kpos, K), axis=1, keepdims=True)
                for k in range(K):
                    rows_ref[k] = jnp.where(slot == k, row, rows_ref[k])
            return jnp.where(lane == jt, CONSUMED, s)

        jax.lax.fori_loop(0, steps, insert, jnp.where(enter, sims, CONSUMED))


def sorted_topk(vals, idx, rows=None):
    """Order a running top-K set (H, K) by (value desc, index asc), the
    order of `lax.top_k`. Returns (vals, idx), plus the rows reordered
    alike when ``rows`` (a length-K list of (H, W)) is given."""
    H, K = vals.shape
    kpos = jax.lax.broadcasted_iota(jnp.int32, (H, K), 1)
    out_v = jnp.full((H, K), CONSUMED, jnp.float32)
    out_i = jnp.zeros((H, K), jnp.int32)
    out_r = []
    for i in range(K):
        vmax = jnp.max(vals, axis=1, keepdims=True)
        imin = jnp.min(jnp.where(vals == vmax, idx, jnp.iinfo(jnp.int32).max),
                       axis=1, keepdims=True)
        slot = jnp.min(jnp.where((vals == vmax) & (idx == imin), kpos, K),
                       axis=1, keepdims=True)
        out_v = jnp.where(kpos == i, vmax, out_v)
        out_i = jnp.where(kpos == i, imin, out_i)
        if rows is not None:
            row = rows[0]
            for k in range(1, K):
                row = jnp.where(slot == k, rows[k], row)
            out_r.append(row)
        vals = jnp.where(kpos == slot, CONSUMED, vals)
    if rows is None:
        return out_v, out_i
    return out_v, out_i, out_r


def _kernel(q_ref, m_ref, vals_ref, idx_ref, vals_s, idx_s, *, block_n: int,
            tiles: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        reset_topk(vals_s, idx_s)

    sweep_tile(q_ref[...], m_ref[...].astype(jnp.float32), t * block_n,
               vals_s, idx_s)

    @pl.when(t == tiles - 1)
    def _emit():
        vals_ref[...], idx_ref[...] = sorted_topk(vals_s[...], idx_s[...])


@functools.partial(jax.jit, static_argnames=("k", "block_n", "interpret",
                                             "valid_n"))
def topk_read(q: jax.Array, mem: jax.Array, *, k: int, block_n: int = 512,
              interpret: bool = False, valid_n: Optional[int] = None):
    """q: (B, H, W), mem: (B, N, W) -> (vals, idx) each (B, H, K), cosine
    similarity, descending. ``valid_n`` restricts the sweep to the first
    `valid_n` rows (scratch-row layout: mem is (B, N+1, W), valid_n=N)."""
    B, H, W = q.shape
    _, N, _ = mem.shape
    N = N if valid_n is None else valid_n
    assert N % block_n == 0, (N, block_n)
    assert block_n >= k, (block_n, k)
    tiles = N // block_n
    out_spec = pl.BlockSpec((None, H, k), lambda b, t: (b, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, block_n=block_n, tiles=tiles),
        grid=(B, tiles),
        in_specs=[
            pl.BlockSpec((None, H, W), lambda b, t: (b, 0, 0)),
            pl.BlockSpec((None, block_n, W), lambda b, t: (b, t, 0)),
        ],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, k), jnp.float32),
            jax.ShapeDtypeStruct((B, H, k), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((H, k), jnp.float32),
                        pltpu.VMEM((H, k), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="topk_read",
    )(q, mem)
