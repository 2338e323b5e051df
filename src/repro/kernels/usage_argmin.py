"""Pallas TPU kernels: least-recently-accessed slots (SAM §3.2, eq. 6).

`usage_argmin` streams the (N,) last-access array through VMEM tiles keeping
a running (min, argmin) across the sequential grid — the TPU-native
replacement for the paper's circular-linked-list LRA ring.

`lra_topn` generalizes it to the n least-recently-accessed slots (SAM needs
one LRA row per head): each grid step takes a (B, block_n) tile of the
usage table — every batch row at once — and merges it into a running n
smallest held in VMEM scratch (n masked reductions, n = num_heads ≤ 8), so
the final n come out of the one dispatch. Both tie-break toward the lowest
index, matching the `jax.lax.top_k` reference.

Scratch-row layout: with ``valid_n=N`` the usage table may carry a scratch
entry past N ((B, N+1), pinned to int32 max — docs/memory-model.md); the
grid tiles cover exactly rows [0, N), so the scratch entry is never swept."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(u_ref, idx_ref, val_ref, *, block_n: int):
    t = pl.program_id(1)
    u = u_ref[0, :].astype(jnp.float32)
    j = jnp.argmin(u)
    v = u[j]
    idx = (t * block_n + j).astype(jnp.int32)

    @pl.when(t == 0)
    def _():
        idx_ref[0, 0] = idx
        val_ref[0, 0] = v

    @pl.when(t > 0)
    def _():
        better = v < val_ref[0, 0]
        idx_ref[0, 0] = jnp.where(better, idx, idx_ref[0, 0])
        val_ref[0, 0] = jnp.where(better, v, val_ref[0, 0])


@functools.partial(jax.jit, static_argnames=("block_n", "interpret",
                                             "valid_n"))
def usage_argmin(last_access: jax.Array, *, block_n: int = 1024,
                 interpret: bool = False, valid_n: Optional[int] = None):
    """last_access: (B, N) -> (B,) int32 index of the minimum over the first
    `valid_n` rows (default: all)."""
    B, N = last_access.shape
    N = N if valid_n is None else valid_n
    bn = min(block_n, N)
    assert N % bn == 0, (N, bn)
    idx, _ = pl.pallas_call(
        functools.partial(_kernel, block_n=bn),
        grid=(B, N // bn),
        in_specs=[pl.BlockSpec((1, bn), lambda b, t: (b, t))],
        out_specs=[pl.BlockSpec((1, 1), lambda b, t: (b, 0)),
                   pl.BlockSpec((1, 1), lambda b, t: (b, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, 1), jnp.int32),
                   jax.ShapeDtypeStruct((B, 1), jnp.float32)],
        interpret=interpret,
        name="usage_argmin",
    )(last_access)
    return idx[:, 0]


_INT_MAX = jnp.iinfo(jnp.int32).max


def _topn_kernel(u_ref, idx_ref, vals_s, idx_s, *, n: int, block_n: int,
                 tiles: int):
    """One (B, block_n) tile of the usage table, every batch row at once
    (rows on sublanes): merge the tile into the running n smallest
    (value, index) pairs with n masked reductions over `broadcasted_iota`.
    The running entries carry lower indices than the tile, so they win
    value ties; consumed entries become (INT_MAX, INT_MAX), which loses to
    every real entry."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        vals_s[...] = jnp.full(vals_s.shape, _INT_MAX, jnp.int32)
        idx_s[...] = jnp.full(idx_s.shape, _INT_MAX, jnp.int32)

    u = u_ref[...].astype(jnp.int32)                            # (B, bn)
    B = u.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (B, block_n), 1)
    npos = jax.lax.broadcasted_iota(jnp.int32, (B, n), 1)
    avail = lane >= 0
    sv, si = vals_s[...], idx_s[...]
    new_v = jnp.full((B, n), _INT_MAX, jnp.int32)
    new_i = jnp.full((B, n), _INT_MAX, jnp.int32)
    for i in range(n):
        vt = jnp.min(jnp.where(avail, u, _INT_MAX), axis=1, keepdims=True)
        jt = jnp.min(jnp.where(avail & (u == vt), lane, block_n), axis=1,
                     keepdims=True)
        it = t * block_n + jt
        vs = jnp.min(sv, axis=1, keepdims=True)
        ps = jnp.min(jnp.where(sv == vs, npos, n), axis=1, keepdims=True)
        is_ = jnp.min(jnp.where(npos == ps, si, _INT_MAX), axis=1,
                      keepdims=True)
        take_s = (vs < vt) | ((vs == vt) & (is_ < it))
        new_v = jnp.where(npos == i, jnp.where(take_s, vs, vt), new_v)
        new_i = jnp.where(npos == i, jnp.where(take_s, is_, it), new_i)
        used = take_s & (npos == ps)
        sv = jnp.where(used, _INT_MAX, sv)
        si = jnp.where(used, _INT_MAX, si)
        avail = avail & ~(~take_s & (lane == jt))
    vals_s[...] = new_v
    idx_s[...] = new_i

    @pl.when(t == tiles - 1)
    def _emit():
        idx_ref[...] = idx_s[...]


@functools.partial(jax.jit, static_argnames=("n", "block_n", "interpret",
                                             "valid_n"))
def lra_topn(last_access: jax.Array, *, n: int, block_n: int = 1024,
             interpret: bool = False, valid_n: Optional[int] = None):
    """last_access: (B, N) -> (B, n) int32 indices of the n smallest entries
    over the first `valid_n` rows (default: all), ascending by
    (value, index) — identical to `lra_topn_ref`. One dispatch: the
    per-tile candidates merge in VMEM, so no host-side sort follows."""
    B, N = last_access.shape
    N = N if valid_n is None else valid_n
    bn = min(block_n, N)
    assert N % bn == 0, (N, bn)
    assert n <= bn, (n, bn)
    tiles = N // bn
    return pl.pallas_call(
        functools.partial(_topn_kernel, n=n, block_n=bn, tiles=tiles),
        grid=(tiles,),
        in_specs=[pl.BlockSpec((B, bn), lambda t: (0, t))],
        out_specs=pl.BlockSpec((B, n), lambda t: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((B, n), jnp.int32),
                        pltpu.VMEM((B, n), jnp.int32)],
        interpret=interpret,
        name="lra_topn",
    )(last_access.astype(jnp.int32))
