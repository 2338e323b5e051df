"""Pallas TPU kernels: the fused one-dispatch SAM read (§3.1 / §3.5).

The composed sparse read is 3–4 dispatches per decode step: a similarity
sweep (`topk_read`), a `lax.top_k` merge, a row gather, and the re-rank /
softmax / weighted-sum tail — each materializing an intermediate in HBM.
These kernels collapse the whole read into **one** `pallas_call`:

* `fused_read_sweep` — the exact ("linear index") read. Grid
  (B, N/block_n), sequential over tiles: each tile computes the cosine
  similarities of all H heads on the MXU, merges them into a running
  per-head top-K in VMEM scratch (values, indices, and the raw candidate
  *rows*, so no second gather pass ever touches HBM — the selection
  helpers are shared with `kernels/topk_read.py`), and the final tile
  orders the top-K and applies key strength, softmax, and the weighted
  sum in-register. HBM traffic is one O(N·W) memory stream per batch row,
  shared by its H heads — the intermediates (sims, top-K merge buffers,
  gathered rows) never exist outside VMEM. The merge is gated
  (`topk_read.sweep_tile`): only rows that beat their head's running K-th
  value strictly enter, so a tile with no entrant does no merge work, and
  a tile of zero rows is not scored once every head's K-th value is at
  least 0; exact, bit for bit.
  ``block_n`` defaults to `sweep_block`, derived from N and W.

* `fused_read_candidates` — the ANN-mode read over a pre-deduped signed
  candidate set from the LSH index. The candidate ids are scalar-
  prefetched (they *must* exist before kernel launch — they drive the
  memory block's index map), so the hash + bucket/ring probe + dedup stay
  outside; everything after (candidate sims → top-K re-rank → softmax →
  weighted gather) is one pass with grid (B·H, C) — **independent of N**.
  Invalid candidates (id < 0: cold bucket slot or dedup'd duplicate) ride
  through with weight exactly 0, matching
  `addressing.finish_candidate_read`'s validity contract.

Both kernels compute in f32 regardless of the memory dtype: bf16 rows are
upcast tile-by-tile in VMEM, and int8 rows (``mem_scale=`` given) are
dequantized in VMEM against their per-row f32 scale — the scaled-read
half of the compressed-memory story: the HBM stream is the quantized
rows plus one scalar per row (~4x less traffic than f32 at W=32). They
tie-break identically to `jax.lax.top_k` (value descending,
then lowest index / candidate position), and return (read, weights,
signed indices). Selection is non-differentiable by construction;
`kernels/ops.py` wraps both in a residual-light `jax.custom_vjp` whose
backward re-derives the differentiable tail (`ref.sparse_read_tail`) from
the recorded indices — gradients match the composed path exactly.

Scratch-row layout: `fused_read_sweep` takes ``valid_n=N`` so the grid
tiles cover exactly rows [0, N) of the persistent (B, N+1, W) buffer —
the write-scratch row is never swept. The candidate kernel needs nothing:
candidate ids are always < N.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.topk_read import (CONSUMED, reset_topk, sorted_topk,
                                     sweep_tile)

_NEG = -1e9                # finish_candidate_read's invalid-selection mask
_IMAX = jnp.iinfo(jnp.int32).max
# VMEM for a sweep tile's f32 working copy: rows of every storage dtype are
# upcast to f32 in VMEM, and the one-hot row picks and the HIGHEST-precision
# scoring hold several such copies, so the f32 tile bounds the block (an
# 8,192 x 128 f32 tile, 4 MiB, does not fit the v5e's scoped VMEM). A row
# takes at least 128 lanes of VMEM, whatever its width.
_TILE_F32_BYTES = 1 << 20
# The merge gate skips whole tiles, so a sweep keeps this many at least.
_MIN_TILES = 16


def sweep_block(n: int, w: int) -> int:
    """Rows per tile of the exact sweep over ``n`` rows of width ``w``:
    512, doubled while the tile still divides ``n``, its f32 working copy
    stays within `_TILE_F32_BYTES` and ``n`` still spans `_MIN_TILES`
    tiles; at most ``n``. Larger tiles cut the grid's fixed cost per step;
    smaller ones let the gate skip more of a memory that is zero past its
    written rows, and cost less per insertion (2,048 rows at N=65,536,
    W=128)."""
    bn = 512
    row_bytes = max(w, 128) * 4
    while (n % (2 * bn) == 0 and 2 * bn * row_bytes <= _TILE_F32_BYTES
           and n // (2 * bn) >= _MIN_TILES):
        bn *= 2
    return min(bn, n)


def _norm_row(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x) + 1e-6)


def _take_row(mat, j):
    """Row `j` (traced) of a VMEM-resident (R, W) value via a one-hot
    matvec — Mosaic-friendly where a dynamic-start slice is not."""
    hot = (jnp.arange(mat.shape[0]) == j).astype(jnp.float32)
    return jnp.dot(hot, mat, preferred_element_type=jnp.float32)


def _softmax_tail(vals, valid, beta):
    """The read-weight tail, numerically identical to
    `addressing.finish_candidate_read`: scaled sims masked to -1e9 where
    invalid, softmax, invalid weights zeroed, renormalized."""
    sel = jnp.where(valid, vals * beta, _NEG)
    e = jnp.exp(sel - jnp.max(sel, axis=-1, keepdims=True))
    w = e / jnp.sum(e, axis=-1, keepdims=True)
    w = jnp.where(valid, w, 0.0)
    return w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-6)


# --------------------------------------------------------------------------
# Exact read: one sequential sweep, running top-K + rows in scratch
# --------------------------------------------------------------------------

def _sweep_kernel(q_ref, m_ref, beta_ref, *rest, k: int, block_n: int,
                  tiles: int, quantized: bool):
    if quantized:
        s_ref, read_ref, w_ref, idx_ref, vals_s, idx_s, rows_s = rest
        scale = s_ref[...]
    else:
        read_ref, w_ref, idx_ref, vals_s, idx_s, rows_s = rest
        scale = None
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        reset_topk(vals_s, idx_s)
        rows_s[...] = jnp.zeros(rows_s.shape, jnp.float32)

    # In-VMEM upcast/dequantization: the HBM stream stays in the storage
    # dtype (int8 rows + one f32 scale per row, ~4x less than f32 rows).
    sweep_tile(q_ref[...], m_ref[...].astype(jnp.float32), t * block_n,
               vals_s, idx_s, scale=scale, rows_ref=rows_s)

    @pl.when(t == tiles - 1)
    def _emit():
        vals, idx, rows = sorted_topk(vals_s[...], idx_s[...],
                                      [rows_s[i] for i in range(k)])
        # Exact selections are always valid (every swept row is real).
        w = _softmax_tail(vals, True, beta_ref[...])
        read = w[:, 0:1] * rows[0]
        for i in range(1, k):
            read = read + w[:, i:i + 1] * rows[i]
        read_ref[...] = read
        w_ref[...] = w
        idx_ref[...] = idx


@functools.partial(jax.jit, static_argnames=("k", "block_n", "interpret",
                                             "valid_n"))
def fused_read_sweep(q: jax.Array, mem: jax.Array, beta: jax.Array, *,
                     k: int, block_n: Optional[int] = None,
                     interpret: bool = False,
                     valid_n: Optional[int] = None,
                     mem_scale: Optional[jax.Array] = None):
    """q: (B, H, W), mem: (B, N, W), beta: (B, H) -> (read (B, H, W) f32,
    weights (B, H, K) f32, indices (B, H, K) int32). One kernel dispatch;
    numerically matches `ref.fused_read_ref` (= the composed
    topk_read → finish_candidate_read path). ``valid_n`` restricts the
    sweep to rows [0, valid_n) of a scratch-row buffer. ``mem_scale``
    (B, N) marks int8 rows: each tile's rows are dequantized in VMEM
    (``row * scale``) — still one dispatch, the HBM stream drops to int8
    rows plus one f32 scalar per row. ``block_n`` defaults to
    `sweep_block`'s."""
    B, H, W = q.shape
    N = mem.shape[1] if valid_n is None else valid_n
    block_n = sweep_block(N, W) if block_n is None else block_n
    assert N % block_n == 0, (N, block_n)
    assert block_n >= k, (block_n, k)
    tiles = N // block_n
    quantized = mem_scale is not None

    head_spec = lambda last: pl.BlockSpec((None, H, last),  # noqa: E731
                                          lambda b, t: (b, 0, 0))
    in_specs = [head_spec(W),
                pl.BlockSpec((None, block_n, W), lambda b, t: (b, t, 0)),
                head_spec(1)]
    operands = [q, mem, beta.reshape(B, H, 1).astype(jnp.float32)]
    if quantized:
        # (B, 1, rows): a lane-major (1, block_n) scale row per tile.
        in_specs.append(pl.BlockSpec((None, 1, block_n),
                                     lambda b, t: (b, 0, t)))
        operands.append(mem_scale.astype(jnp.float32)[:, None, :])

    return pl.pallas_call(
        functools.partial(_sweep_kernel, k=k, block_n=block_n, tiles=tiles,
                          quantized=quantized),
        grid=(B, tiles),
        in_specs=in_specs,
        out_specs=[head_spec(W), head_spec(k), head_spec(k)],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, W), jnp.float32),
            jax.ShapeDtypeStruct((B, H, k), jnp.float32),
            jax.ShapeDtypeStruct((B, H, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((H, k), jnp.float32),
            pltpu.VMEM((H, k), jnp.int32),
            pltpu.VMEM((k, H, W), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="fused_read_sweep",
    )(*operands)


# --------------------------------------------------------------------------
# ANN read: scalar-prefetched candidates, grid independent of N
# --------------------------------------------------------------------------

def _cand_kernel(cc_ref, cs_ref, q_ref, beta_ref, m_ref, *rest,
                 k: int, C: int, quantized: bool):
    if quantized:
        s_ref, read_ref, w_ref, idx_ref, vals_s, pos_s, sig_s, rows_s = rest
    else:
        s_ref = None
        read_ref, w_ref, idx_ref, vals_s, pos_s, sig_s, rows_s = rest
    bh = pl.program_id(0)
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        vals_s[0, :] = jnp.full((k,), CONSUMED, jnp.float32)
        # Distinct descending sentinels: the first K insertions each evict
        # a different empty slot (eviction picks the max-pos minimum).
        pos_s[0, :] = _IMAX - jnp.arange(k, dtype=jnp.int32)
        sig_s[0, :] = jnp.full((k,), -1, jnp.int32)
        rows_s[:, :] = jnp.zeros(rows_s.shape, jnp.float32)

    row = m_ref[0, 0, :].astype(jnp.float32)
    if quantized:
        # Per-candidate dequantization: the scale block map follows the
        # same prefetched clamped id as the row block, so one int8 row and
        # one f32 scalar move per candidate — still a single dispatch.
        row = row * s_ref[0, 0]
    qn = _norm_row(q_ref[0, :].astype(jnp.float32))
    sim = jnp.dot(row, qn, preferred_element_type=jnp.float32) \
        * jax.lax.rsqrt(jnp.sum(row * row) + 1e-6)
    sig = cs_ref[bh, c]
    sim = jnp.where(sig < 0, _NEG, sim)

    # Running top-K under (value desc, position asc): candidate `c` enters
    # iff it strictly beats the current minimum (a tie keeps the earlier
    # position, as `lax.top_k` would), evicting the max-position slot among
    # the equal minima (the one `top_k` would drop).
    cv = vals_s[0, :]
    vmin = jnp.min(cv)
    slot = jnp.argmax(jnp.where(cv == vmin, pos_s[0, :], -1))
    hot = (jnp.arange(k) == slot) & (sim > vmin)
    vals_s[0, :] = jnp.where(hot, sim, cv)
    pos_s[0, :] = jnp.where(hot, c, pos_s[0, :])
    sig_s[0, :] = jnp.where(hot, sig, sig_s[0, :])
    rows_s[:, :] = jnp.where(hot[:, None], row[None, :], rows_s[:, :])

    @pl.when(c == C - 1)
    def _emit():
        cv = vals_s[0, :]
        cp = pos_s[0, :]
        ov, osig, orows = [], [], []
        for _ in range(k):
            vmax = jnp.max(cv)
            j = jnp.argmin(jnp.where(cv == vmax, cp, _IMAX))
            ov.append(cv[j])
            osig.append(sig_s[0, j])
            orows.append(_take_row(rows_s[:, :], j))
            cv = cv.at[j].set(CONSUMED)
            cp = cp.at[j].set(_IMAX)
        vals = jnp.stack(ov)
        sig = jnp.stack(osig)
        rows = jnp.stack(orows)
        w = _softmax_tail(vals, sig >= 0, beta_ref[0, 0])
        read_ref[0, :] = jnp.dot(w, rows,
                                 preferred_element_type=jnp.float32)
        w_ref[0, :] = w
        idx_ref[0, :] = sig


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def fused_read_candidates(q: jax.Array, mem: jax.Array, beta: jax.Array,
                          cand_idx: jax.Array, *, k: int,
                          interpret: bool = False,
                          mem_scale: Optional[jax.Array] = None):
    """ANN-mode fused read. q: (B, H, W), mem: (B, N, W), beta: (B, H),
    cand_idx: (B, H, C) *signed, pre-deduped* candidate ids (-1 = invalid).
    Returns (read (B, H, W) f32, weights (B, H, K) f32, signed indices
    (B, H, K) int32) — numerically matches `ref.fused_read_candidates_ref`
    (= select_candidates → finish_candidate_read on deduped candidates).
    Grid is (B·H, C): independent of N. Requires C >= k. ``mem_scale``
    (B, N) marks int8 rows: the per-candidate scale is fetched through the
    same prefetched block map as the row and applied in VMEM."""
    B, H, W = q.shape
    C = cand_idx.shape[-1]
    assert C >= k, (C, k)
    qf = q.reshape(B * H, W)
    bf = beta.reshape(B * H, 1).astype(jnp.float32)
    cs = cand_idx.reshape(B * H, C).astype(jnp.int32)
    cc = jnp.maximum(cs, 0)          # clamped: drives the mem block map
    quantized = mem_scale is not None

    in_specs = [
        pl.BlockSpec((1, W), lambda bh, c, *_: (bh, 0)),
        pl.BlockSpec((1, 1), lambda bh, c, *_: (bh, 0)),
        pl.BlockSpec((1, 1, W), lambda bh, c, cc, _cs: (bh // H, cc[bh, c], 0)),
    ]
    operands = [qf, bf, mem]
    if quantized:
        in_specs.append(
            pl.BlockSpec((1, 1), lambda bh, c, cc, _cs: (bh // H, cc[bh, c])))
        operands.append(mem_scale.astype(jnp.float32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,       # clamped ids, signed ids
        grid=(B * H, C),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, W), lambda bh, c, *_: (bh, 0)),
            pl.BlockSpec((1, k), lambda bh, c, *_: (bh, 0)),
            pl.BlockSpec((1, k), lambda bh, c, *_: (bh, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, k), jnp.float32),
            pltpu.VMEM((1, k), jnp.int32),
            pltpu.VMEM((1, k), jnp.int32),
            pltpu.VMEM((k, W), jnp.float32),
        ],
    )
    read, w, idx = pl.pallas_call(
        functools.partial(_cand_kernel, k=k, C=C, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B * H, W), jnp.float32),
            jax.ShapeDtypeStruct((B * H, k), jnp.float32),
            jax.ShapeDtypeStruct((B * H, k), jnp.int32),
        ],
        interpret=interpret,
        name="fused_read_candidates",
    )(cc, cs, *operands)
    return (read.reshape(B, H, W), w.reshape(B, H, k),
            idx.reshape(B, H, k))
