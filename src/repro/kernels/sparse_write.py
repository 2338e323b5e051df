"""Pallas TPU kernel: fused SAM write + usage update (§3.2, eqs. 3/5/6).

One SAM step's write side is, unfused, 3–4 separate dispatches:

  1. scatter-set zeros into the LRA rows        (R_t erase)
  2. materialize the (B, J, W) outer product w^W a^T in HBM
  3. scatter-add it into the memory             (A_t)
  4. scatter-max the last-access table          (U^(2) usage)

This kernel does steps 1–3 in a single pass over the J = H·(K+1) touched
rows. Each grid step (b, u) owns one *unique* touched row: it DMAs the
row's tile-aligned window (8 f32 / 16 bf16 / 32 int8 rows — the TPU block
rule forbids a one-row block) from the HBM buffer into VMEM, zeroes the row
if it is an erase target, accumulates every matching write's
w_j · a_{head(j)} contribution on the fly (the outer product never exists
in HBM), and DMAs the window back. Step 4 is the oracle's own O(J)
max-scatter on the usage table, applied in place by XLA. HBM traffic is
O(J·W) — independent of N, the paper's headline property.

Duplicate handling — the persistent scratch-row contract: each row must be
updated by exactly one grid step, so duplicate indices are redirected to a
**scratch row** and the first occurrence accumulates *all* matching
contributions (the kernel's inner loop matches on row id, not on position);
the parked grid steps do nothing. With ``scratch_row=N`` the caller carries
the memory as a persistent (B, N+1, W) buffer (`SAMState`,
docs/memory-model.md) whose row N *is* the scratch row: the kernel updates
the buffer in place and no write index ever equals N, so the scratch row
is never touched. Nothing is padded or sliced — the compiled step stays
O(J·W). Without ``scratch_row`` (legacy callers holding
a (B, N, W) memory) the wrapper still pads a transient row N and slices it
back off, an O(N·W) copy per call kept only for layout migration and the
`benchmarks/bench_kernels.py` legacy-vs-scratch comparison.

Gradients: `pallas_call` has no VJP; `kernels/ops.py` wraps this in a
`jax.custom_vjp` whose backward is closed-form (gather of the output
cotangent), so the fused path is usable inside `jax.grad` — required by
both the naive unroll and the rollback BPTT replay.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import quantize_rows
from repro.kernels.scatter_rows import first_occurrence


def _as_lane_step(step: jax.Array, batch: int) -> jax.Array:
    """Normalize the usage-stamp step to a (B,) int32 vector.

    Accepts the () scalar the recurrent cores carry, or the (B,)/(B, 1)
    per-lane counters the continuous-batching engine carries (one session
    step per lane — `models/lm.init_memory_states(per_lane_step=True)`)."""
    step = jnp.asarray(step).astype(jnp.int32)
    if step.ndim == 0:
        return jnp.broadcast_to(step, (batch,))
    flat = step.reshape(-1)
    if flat.shape[0] != batch:
        raise ValueError(
            f"per-lane step must have one entry per batch row: got shape "
            f"{step.shape} for batch {batch}")
    return flat


def _tile_rows(dtype) -> int:
    """Rows of one native TPU tile (8 sublanes of 32-bit words): 8 for f32,
    16 for bf16, 32 for int8 — the aligned window a row DMA moves."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def _load_window(out_hbm, buf, sem, b, row):
    """DMA the tile-aligned window of rows holding `row` (batch row `b`)
    into VMEM; returns its start and the one-hot row mask over it."""
    T, rows = buf.shape[0], out_hbm.shape[1]
    if ((rows - 2) // T + 1) * T <= rows:
        # The aligned window of every non-scratch row fits in the buffer:
        # packed dtypes (bf16, int8) can only DMA tile-aligned windows.
        r0 = pl.multiple_of((row // T) * T, T)
    else:
        r0 = jnp.minimum((row // T) * T, rows - T)
    cp = pltpu.make_async_copy(out_hbm.at[b, pl.ds(r0, T), :], buf,
                               sem.at[0])
    cp.start()
    cp.wait()
    sel = jax.lax.broadcasted_iota(jnp.int32, buf.shape, 0) == row - r0
    return r0, sel


def _store_window(out_hbm, buf, sem, b, r0):
    cp = pltpu.make_async_copy(buf, out_hbm.at[b, pl.ds(r0, buf.shape[0]), :],
                               sem.at[1])
    cp.start()
    cp.wait()


def _kernel(uidx_ref, widx_ref, erase_ref, w_ref, mem_hbm, a_ref, out_hbm,
            buf, sem, *, J: int, kp1: int, prod_dtype):
    """Grid step (b, u) owns the unique row ``uidx[b, u]``; duplicate lanes
    (parked on the scratch row) do nothing. The row's aligned window is
    read from and written back to the aliased output buffer — the input
    ref is the same HBM buffer and only carries the alias — so a later
    step that shares the window sees this one's update."""
    del mem_hbm
    b = pl.program_id(0)
    u = pl.program_id(1)
    row = uidx_ref[b, u]

    @pl.when(row == widx_ref[b, u])
    def _owner():
        r0, sel = _load_window(out_hbm, buf, sem, b, row)
        dt = buf.dtype
        old = jnp.sum(jnp.where(sel, buf[...].astype(jnp.float32), 0.0),
                      axis=0, keepdims=True)                    # (1, W)
        acc = jnp.where(erase_ref[b, u] > 0, 0.0, old)
        for j in range(J):                 # J ≈ 36, statically unrolled
            wj = jnp.where(widx_ref[b, j] == row, w_ref[b, j], 0.0)
            # One rounding of w·a to the product dtype, then of each slot
            # update to the storage dtype — the oracle's scatter-add.
            c = (wj * a_ref[pl.ds(j // kp1, 1), :]).astype(prod_dtype)
            acc = (acc + c.astype(dt).astype(jnp.float32)).astype(dt) \
                .astype(jnp.float32)
        buf[...] = jnp.where(sel, acc.astype(dt), buf[...])
        _store_window(out_hbm, buf, sem, b, r0)


def _kernel_q(uidx_ref, widx_ref, erase_ref, w_ref, old_s_ref, mem_hbm,
              a_ref, out_hbm, new_s_ref, buf, sem, *, J: int, kp1: int):
    """Int8 variant: dequantize the owned row against its f32 scale,
    accumulate every matching write's contribution in f32, re-quantize
    **once** (`core.quant.quantize_rows`), and emit the new int8 row and
    its scale — the read-modify-write touches only the J owned rows.
    Parked duplicate lanes re-emit their scratch row's old scale, so the
    caller's scale scatter leaves that row's bits unchanged."""
    del mem_hbm
    b = pl.program_id(0)
    u = pl.program_id(1)
    row = uidx_ref[b, u]
    new_s_ref[b, u] = old_s_ref[b, u]

    @pl.when(row == widx_ref[b, u])
    def _owner():
        r0, sel = _load_window(out_hbm, buf, sem, b, row)
        old = jnp.sum(jnp.where(sel, buf[...].astype(jnp.float32), 0.0),
                      axis=0, keepdims=True) * old_s_ref[b, u]
        acc = jnp.where(erase_ref[b, u] > 0, 0.0, old)
        for j in range(J):                 # J ≈ 36, statically unrolled
            wj = jnp.where(widx_ref[b, j] == row, w_ref[b, j], 0.0)
            acc = acc + wj * a_ref[pl.ds(j // kp1, 1), :]
        new_q, new_s = quantize_rows(acc)  # one rounding per touched row
        buf[...] = jnp.where(sel, new_q, buf[...])
        new_s_ref[b, u] = jnp.max(new_s)
        _store_window(out_hbm, buf, sem, b, r0)


@functools.partial(jax.jit,
                   static_argnames=("delta", "interpret", "scratch_row"))
def sparse_write_update(mem: jax.Array, last_access: jax.Array,
                        write_idx: jax.Array, write_w: jax.Array,
                        a: jax.Array, lra_idx: jax.Array, step: jax.Array,
                        *, delta: float, interpret: bool = False,
                        scratch_row: Optional[int] = None,
                        mem_scale: Optional[jax.Array] = None):
    """Fused erase + outer-product scatter-add + usage update.

    Scratch-row layout (``scratch_row=N``): mem: (B, N+1, W);
    last_access: (B, N+1) int32 — row N is the persistent write-scratch row
    (never referenced by any index argument). Returns (mem', last_access')
    in the same padded shapes, with row N untouched. Legacy layout
    (``scratch_row=None``): mem: (B, N, W); a transient scratch row is
    padded on and sliced back off (O(N·W) per call).

    write_idx: (B, J) int32, J = H·(K+1); write_w: (B, J); a: (B, H, W);
    lra_idx: (B, H) int32; step: () int32, or a per-batch-row (B,)/(B, 1)
    vector (the continuous-batching engine stamps each lane with its own
    session step). All indices < N. Numerically matches
    `ref.sparse_write_update_ref` (duplicates accumulate; usage takes the
    max over step and the previous value wherever weight > delta). The
    row update is the kernel; the O(J) usage max-scatter is the oracle's
    own expression, applied to the buffer in place.

    Precondition: every lra_idx row must also appear in write_idx — only
    write_idx rows get grid steps, so an LRA row outside the write set
    would not be erased (the reference erases unconditionally). SAM's
    write plan guarantees this by construction: the LRA slot is the last
    of each head's K+1 write rows (`write_plan`, eq. 5).

    Int8 storage (``mem_scale`` (B, rows) f32 given): the owned rows are
    dequantized, updated in f32, and re-quantized once in the same pass
    (`_kernel_q`); returns (mem', last_access', mem_scale'). Numerically
    matches `ref.sparse_write_update_q_ref`.
    """
    B, rows, W = mem.shape
    _, J = write_idx.shape
    H = a.shape[1]
    kp1 = J // H
    assert kp1 * H == J, (J, H)
    quantized = mem_scale is not None

    if scratch_row is None:
        # Legacy layout: transient scratch row N, padded on / sliced off.
        mem_p = jnp.pad(mem, ((0, 0), (0, 1), (0, 0)))
        scale_p = None if not quantized else jnp.pad(mem_scale,
                                                     ((0, 0), (0, 1)))
        dummy = rows
    else:
        assert scratch_row == rows - 1 == last_access.shape[1] - 1, \
            (scratch_row, mem.shape, last_access.shape)
        mem_p, scale_p, dummy = mem, mem_scale, scratch_row

    # Unique-first row ownership: duplicates are parked on the scratch row.
    write_idx = write_idx.astype(jnp.int32)
    first = first_occurrence(write_idx)
    uidx = jnp.where(first, write_idx, dummy).astype(jnp.int32)
    erase = (uidx[:, :, None] == lra_idx[:, None, :]).any(-1).astype(jnp.int32)
    bidx = jnp.arange(B)[:, None]

    window = min(_tile_rows(mem.dtype), mem_p.shape[1])
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    a_spec = pl.BlockSpec((None, H, W), lambda b, u, *_: (b, 0, 0))
    scratch = [pltpu.VMEM((window, W), mem.dtype),
               pltpu.SemaphoreType.DMA((2,))]
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))
    prefetch = [uidx, write_idx, erase, write_w.astype(jnp.float32)]

    if quantized:
        old_s = scale_p[bidx, uidx]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,   # uidx, write_idx, erase, write_w, old_s
            grid=(B, J),
            in_specs=[any_spec, a_spec],
            out_specs=[any_spec, pl.BlockSpec(memory_space=pltpu.SMEM)],
            scratch_shapes=scratch,
        )
        out_mem, new_s = pl.pallas_call(
            functools.partial(_kernel_q, J=J, kp1=kp1),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(mem_p.shape, mem.dtype),
                       jax.ShapeDtypeStruct((B, J), jnp.float32)],
            input_output_aliases={5: 0},
            compiler_params=params,
            interpret=interpret,
            name="sparse_write_update",
        )(*prefetch, old_s, mem_p, a.astype(jnp.float32))
        out_scale = scale_p.at[bidx, uidx].set(new_s.astype(scale_p.dtype))
    else:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,   # uidx, write_idx, erase, write_w
            grid=(B, J),
            in_specs=[any_spec, a_spec],
            out_specs=any_spec,
            scratch_shapes=scratch,
        )
        out_mem = pl.pallas_call(
            functools.partial(_kernel, J=J, kp1=kp1,
                              prod_dtype=jnp.result_type(write_w, a)),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(mem_p.shape, mem.dtype),
            input_output_aliases={4: 0},
            compiler_params=params,
            interpret=interpret,
            name="sparse_write_update",
        )(*prefetch, mem_p, a.astype(jnp.float32))

    # Usage (U^(2)): the oracle's max-scatter over the J written slots.
    step = _as_lane_step(step, B)[:, None]
    upd = jnp.where(write_w > delta, step, last_access[bidx, write_idx])
    out_la = last_access.at[bidx, write_idx].max(upd)
    if scratch_row is None:
        out_mem = out_mem[:, :rows]
        if quantized:
            return out_mem, out_la, out_scale[:, :rows]
        return out_mem, out_la
    if quantized:
        return out_mem, out_la, out_scale
    return out_mem, out_la
