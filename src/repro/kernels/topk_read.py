"""Pallas TPU kernel: tiled content-based top-K addressing (SAM §3.1).

The hot spot of the exact ("linear index") SAM read is the similarity sweep
q·Mᵀ over N memory rows. On TPU we stream M through VMEM in (block_n, W)
tiles — one sweep per batch row serves all H query heads — compute cosine
similarities on the MXU as an (H, block_n) matrix, and merge each tile into
a running per-head top-K held in VMEM scratch. The selection is K masked
reductions over `broadcasted_iota` (no sort, no dynamic vector indexing),
so the kernel emits the final top-K directly: there is no host-side merge.

Grid: (B, N/block_n), sequential over tiles. Block shapes follow the TPU
rule that a block's last two dims divide (8, 128) or equal the array's:
queries (H, W), memory tiles (block_n, W), outputs (H, K).

The sweep helpers here (`sims_tile`, `merge_topk`) are shared with the
fused read (`kernels/fused_read.py`), so both kernels tie-break
identically to `jax.lax.top_k`: value descending, then lowest index.

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


def sims_tile(q, m, scale=None):
    """Cosine similarities of one memory tile against every head's query.

    q: (H, W), m: (block_n, W), scale: optional (1, block_n) per-row int8
    dequantization scales -> (H, block_n) f32. Algebraically the oracle's
    ``normalize(q) · normalize(m * scale)``, computed on row sums so the
    per-row factors stay lane-major."""
    q = q.astype(jnp.float32)
    m = m.astype(jnp.float32)
    qn = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6)
    dot = _dot_nt(qn, m)                                        # (H, bn)
    sq = _dot_nt(jnp.ones((1, m.shape[1]), jnp.float32), m * m)  # (1, bn)
    if scale is not None:
        dot = dot * scale
        sq = sq * (scale * scale)
    return dot * jax.lax.rsqrt(sq + 1e-6)


def merge_topk(sims, base, vals, idx, *, m=None, scale=None, rows=None):
    """Merge one tile into a running per-head top-K.

    sims: (H, block_n) this tile's similarities; ``base`` the global index
    of its first row. vals/idx: (H, K) the running top-K, sorted by (value
    desc, index asc) and holding only indices below ``base``. Returns the
    merged (vals, idx) — plus the merged candidate rows when ``rows``
    (a length-K list of (H, W) f32) and the tile ``m`` are given: the
    winning rows are picked out of VMEM with a one-hot matmul, so the
    fused read never gathers from HBM.

    Ties keep `lax.top_k`'s order: the running entries win ties against
    the tile (they carry lower indices), and within either side the
    lowest index wins."""
    H, bn = sims.shape
    K = vals.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (H, bn), 1)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (H, K), 1)
    new_v = jnp.full((H, K), CONSUMED, jnp.float32)
    new_i = jnp.zeros((H, K), jnp.int32)
    new_r = []
    for i in range(K):
        vs = jnp.max(vals, axis=1, keepdims=True)                   # (H, 1)
        vt = jnp.max(sims, axis=1, keepdims=True)
        take_s = vs >= vt
        ps = jnp.min(jnp.where(vals == vs, kpos, K), axis=1, keepdims=True)
        jt = jnp.min(jnp.where(sims == vt, lane, bn), axis=1, keepdims=True)
        is_ = jnp.sum(jnp.where(kpos == ps, idx, 0), axis=1, keepdims=True)
        new_v = jnp.where(kpos == i, jnp.where(take_s, vs, vt), new_v)
        new_i = jnp.where(kpos == i, jnp.where(take_s, is_, base + jt), new_i)
        if rows is not None:
            hot = lane == jt
            row_t = _dot(hot.astype(jnp.float32), m)                # (H, W)
            if scale is not None:
                row_t = row_t * jnp.sum(jnp.where(hot, scale, 0.0), axis=1,
                                        keepdims=True)
            row_s = rows[0]
            for k in range(1, K):
                row_s = jnp.where(ps == k, rows[k], row_s)
            new_r.append(jnp.where(take_s, row_s, row_t))
        vals = jnp.where(take_s & (kpos == ps), CONSUMED, vals)
        sims = jnp.where(~take_s & (lane == jt), CONSUMED, sims)
    if rows is None:
        return new_v, new_i
    return new_v, new_i, new_r


def _kernel(q_ref, m_ref, vals_ref, idx_ref, vals_s, idx_s, *, block_n: int,
            tiles: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        vals_s[...] = jnp.full(vals_s.shape, CONSUMED, jnp.float32)
        idx_s[...] = jnp.zeros(idx_s.shape, jnp.int32)

    sims = sims_tile(q_ref[...], m_ref[...])
    vals_s[...], idx_s[...] = merge_topk(sims, t * block_n, vals_s[...],
                                         idx_s[...])

    @pl.when(t == tiles - 1)
    def _emit():
        vals_ref[...] = vals_s[...]
        idx_ref[...] = idx_s[...]


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
