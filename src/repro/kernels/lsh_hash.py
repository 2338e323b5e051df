"""Pallas TPU kernel: LSH signature hashing (SAM §3.5, TPU-adapted ANN).

Computes bucket ids for a batch of vectors against T tables of `bits` random
hyperplanes: one (rows_tile, W) × (W, T·bits) MXU matmul per grid step, sign
bits packed into integers with a power-of-two dot — no data-dependent control
flow, so it vectorizes across the whole write/query batch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, planes_ref, out_ref, *, bits: int, tables: int):
    x = x_ref[...]                                  # (R, W)
    p = planes_ref[...]                             # (T*bits, W)
    proj = jnp.dot(x, p.T, preferred_element_type=jnp.float32)  # (R, T*bits)
    b = (proj > 0).astype(jnp.float32).reshape(x.shape[0], tables, bits)
    weights = (2.0 ** jnp.arange(bits)).astype(jnp.float32)
    ids = jnp.einsum("rtb,b->rt", b, weights)
    out_ref[...] = ids.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def lsh_hash(x: jax.Array, planes: jax.Array, *, block_r: int = 256,
             interpret: bool = False):
    """x: (R, W), planes: (T, bits, W) -> bucket ids (R, T) int32."""
    R, W = x.shape
    T, bits, _ = planes.shape
    pad = (-R) % block_r
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    Rp = xp.shape[0]
    out = pl.pallas_call(
        functools.partial(_kernel, bits=bits, tables=T),
        grid=(Rp // block_r,),
        in_specs=[
            pl.BlockSpec((block_r, W), lambda r: (r, 0)),
            pl.BlockSpec((T * bits, W), lambda r: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_r, T), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, T), jnp.int32),
        interpret=interpret,
        name="lsh_hash",
    )(xp, planes.reshape(T * bits, W))
    return out[:R]
