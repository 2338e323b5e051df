"""Pallas TPU kernel: sparse row scatter (SAM §3.2 write path).

The SAM write touches H·(K+1) rows of a large (N, W) memory. A dense
XLA scatter materializes index tensors in HBM; here each grid step uses
scalar-prefetched row indices to map a (1, W) memory block directly, so the
write is J · W bytes of traffic — O(1) in N, the paper's claim.

Sequential grid semantics on TPU make duplicate indices well-defined:
'add' accumulates, 'set' takes the last write.

Uses ``input_output_aliasing`` so the memory buffer is updated in place —
the functional-JAX analogue of the paper's in-place write + rollback.
Duplicate 'add' indices are pre-combined into their first occurrence and
the leftovers parked on a scratch row; with ``scratch_row=N`` that row is
row N of the caller's persistent (B, N+1, W) buffer (no pad/slice —
docs/memory-model.md), otherwise a transient padded row is used.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, mem_ref, rows_ref, out_ref, *, mode: str):
    del idx_ref  # only used by the index maps
    if mode == "add":
        out_ref[...] = mem_ref[...] + rows_ref[...]
    else:
        out_ref[...] = rows_ref[...]


def first_occurrence(idx: jax.Array) -> jax.Array:
    """(B, J) bool mask: True where idx[b, j] is the first occurrence of its
    value along j. O(J²) pairwise compare — J is H·(K+1) ≈ 20. Shared by
    every kernel that needs unique row ownership under in/out aliasing
    (here and kernels/sparse_write.py)."""
    eq = idx[:, :, None] == idx[:, None, :]                      # (B,J,J)
    return jnp.argmax(eq, axis=-1) == jnp.arange(idx.shape[-1])


def _combine_duplicates(idx: jax.Array, rows: jax.Array, dummy: int):
    """Sum rows sharing an index into the first occurrence; redirect the
    remaining duplicates to a dummy slot."""
    eq = idx[:, :, None] == idx[:, None, :]                      # (B,J,J)
    first = first_occurrence(idx)
    combined = jnp.einsum("bjk,bkw->bjw", eq.astype(rows.dtype), rows)
    rows = jnp.where(first[..., None], combined, 0.0)
    idx = jnp.where(first, idx, dummy)
    return idx, rows


@functools.partial(jax.jit, static_argnames=("mode", "interpret",
                                             "scratch_row"))
def scatter_rows(mem: jax.Array, idx: jax.Array, rows: jax.Array,
                 *, mode: str = "add", interpret: bool = False,
                 scratch_row: Optional[int] = None):
    """mem: (B, N, W), idx: (B, J) int32, rows: (B, J, W) -> updated memory.

    'add' accumulates duplicate indices; 'set' takes the last write. With
    ``scratch_row=N`` the memory is the persistent (B, N+1, W) scratch-row
    buffer and 'add' parks duplicates on row N in place (no pad/slice)."""
    B, N, W = mem.shape
    _, J = idx.shape
    rows = rows.astype(mem.dtype)   # one rounding per update under bf16 rows
    if mode == "add":
        # Read-modify-write of a freshly written block would see stale data
        # under in/out aliasing, so make the touched row set unique first.
        if scratch_row is not None:
            assert scratch_row == N - 1, (scratch_row, mem.shape)
            idx, rows = _combine_duplicates(idx, rows, dummy=scratch_row)
            return _scatter_unique(mem, idx, rows, mode=mode,
                                   interpret=interpret)
        mem = jnp.pad(mem, ((0, 0), (0, 1), (0, 0)))
        idx, rows = _combine_duplicates(idx, rows, dummy=N)
        out = _scatter_unique(mem, idx, rows, mode=mode, interpret=interpret)
        return out[:, :N]
    return _scatter_unique(mem, idx, rows, mode=mode, interpret=interpret)


def _scatter_unique(mem: jax.Array, idx: jax.Array, rows: jax.Array,
                    *, mode: str, interpret: bool):
    B, N, W = mem.shape
    _, J = idx.shape

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, J),
        in_specs=[
            pl.BlockSpec((1, 1, W), lambda b, j, idx_ref: (b, idx_ref[b, j], 0)),
            pl.BlockSpec((1, 1, W), lambda b, j, idx_ref: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, W),
                               lambda b, j, idx_ref: (b, idx_ref[b, j], 0)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, mode=mode),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(mem.shape, mem.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
        name="scatter_rows",
    )(idx, mem, rows)
