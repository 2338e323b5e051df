"""Pure-jnp oracles for every Pallas kernel (the correctness references).

Scratch-row layout: the mutating oracles (`scatter_rows_ref`,
`sparse_write_update_ref`) are layout-agnostic — they only touch rows named
by their index arguments, so handing them the persistent (B, N+1, W)
scratch-row buffer (docs/memory-model.md) leaves row N bit-identical. The
sweep oracles (`topk_read_ref`, `usage_argmin_ref`, `lra_topn_ref`) scan
every row they are given; `kernels/ops.py` slices the logical [0, N) view
off a padded buffer before calling them (``valid_n=``), which XLA fuses
into the O(N·W) sweep these oracles already perform."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quant import dequantize_rows, quantize_rows


def _deq_view(mem: jax.Array, mem_scale):
    """f32 view of a memory buffer: plain upcast for f32/bf16, per-row
    dequantization when an int8 buffer's scale leaf is provided. The
    oracle-side twin of the fused kernels' in-VMEM dequant."""
    if mem_scale is None:
        return mem.astype(jnp.float32)
    return dequantize_rows(mem, mem_scale)


def topk_read_ref(q: jax.Array, mem: jax.Array, k: int):
    """Content-based top-K addressing oracle.

    q: (B, H, W), mem: (B, N, W) -> (vals (B,H,K), idx (B,H,K)) by cosine
    similarity (descending)."""
    qn = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
    mn = mem * jax.lax.rsqrt(jnp.sum(mem * mem, -1, keepdims=True) + 1e-6)
    sims = jnp.einsum("bhw,bnw->bhn", qn, mn)
    return jax.lax.top_k(sims, k)


def sparse_read_tail(q: jax.Array, mem: jax.Array, beta: jax.Array,
                     idx: jax.Array, mem_scale=None):
    """Differentiable tail of a sparse read from recorded signed indices —
    the jnp twin of `core.addressing.finish_candidate_read` (kept here so
    the fused-read custom-VJPs in `kernels/ops.py` can re-derive gradients
    without a circular import).

    q: (B, H, W), mem: (B, N, W), beta: (B, H), idx: (B, H, K) signed
    (-1 = invalid: clamped for the gather, weight exactly 0). Rows are
    upcast to f32 before the re-rank (bf16 memory storage reads at f32);
    with ``mem_scale`` (B, N) the rows are int8 and the gathered words are
    dequantized ``row * scale`` — the scale gather is differentiable, so
    the int8 path's exact scale gradients come out of plain autodiff.
    Returns (read (B, H, K->W weighted sum), weights (B, H, K))."""
    valid = idx >= 0
    b = jnp.arange(mem.shape[0])[:, None, None]
    words = mem[b, jnp.maximum(idx, 0)].astype(jnp.float32)   # (B, H, K, W)
    if mem_scale is not None:
        words = words * mem_scale[b, jnp.maximum(idx, 0)][..., None]
    qn = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
    wn = words * jax.lax.rsqrt(jnp.sum(words * words, -1, keepdims=True)
                               + 1e-6)
    sel = jnp.einsum("bhw,bhkw->bhk", qn, wn) * beta[..., None]
    sel = jnp.where(valid, sel, -1e9)
    w = jax.nn.softmax(sel, axis=-1)
    w = jnp.where(valid, w, 0.0)
    w = w / jnp.maximum(w.sum(axis=-1, keepdims=True), 1e-6)
    read = jnp.einsum("bhk,bhkw->bhw", w, words)
    return read, w


def fused_read_ref(q: jax.Array, mem: jax.Array, beta: jax.Array, k: int,
                   valid_n=None, mem_scale=None):
    """Oracle for the fused exact read: the composed
    topk_read → finish_candidate_read path in one call. The selection sweep
    runs on a stop-gradient f32 view of rows [0, valid_n) — dequantized
    when ``mem_scale`` marks int8 storage; the tail gathers from the full
    (differentiable) memory. Returns
    (read (B,H,W), weights (B,H,K), indices (B,H,K) int32)."""
    mv = mem if valid_n is None else mem[:, :valid_n]
    sv = None if mem_scale is None else mem_scale[:, :mv.shape[1]]
    _, idx = topk_read_ref(
        jax.lax.stop_gradient(q).astype(jnp.float32),
        jax.lax.stop_gradient(_deq_view(mv, sv)), k)
    read, w = sparse_read_tail(q, mem, beta, idx, mem_scale=mem_scale)
    return read, w, idx


def sweep_merge_steps(q, mem, k: int, block_n: int, valid_n=None,
                      mem_scale=None):
    """NumPy count of the exact sweep's work, lane by lane, as
    `topk_read.sweep_tile` gates it (a measuring aid; no step calls it).

    q: (B, H, W), mem: (B, N, W) (int8 rows with ``mem_scale`` (B, N)),
    swept over rows [0, valid_n) in tiles of ``block_n``. Returns
    (scored, merged, insertions), each (B,) int: the tiles scored (a
    nonzero row, or a head whose running K-th value is below 0), the tiles
    where some head has an entrant (a score strictly above its running
    K-th value), and the insertions those tiles run (min(K, most entrants
    of any head), summed). Scores are the kernel's formula in float64, so
    a count can differ from the kernel's only at a near-tie."""
    q = np.asarray(q, np.float64)
    mem = np.asarray(mem, np.float64)
    B, H, _ = q.shape
    n = mem.shape[1] if valid_n is None else valid_n
    scale = np.ones((B, n)) if mem_scale is None \
        else np.asarray(mem_scale, np.float64)[:, :n]
    qn = q / np.sqrt(np.sum(q * q, -1, keepdims=True) + 1e-6)
    counts = np.zeros((3, B), np.int64)
    for b in range(B):
        top = np.full((H, k), -np.inf)                    # running top-K
        for t in range(0, n, block_n):
            m, s = mem[b, t:t + block_n], scale[b, t:t + block_n]
            if not m.any() and top.min() >= 0:
                continue
            counts[0, b] += 1
            sims = (qn[b] @ m.T) * s / np.sqrt(np.sum(m * m, -1) * s * s
                                               + 1e-6)
            steps = min(k, int((sims > top[:, -1:]).sum(1).max()))
            counts[1, b] += steps > 0
            counts[2, b] += steps
            top = -np.sort(-np.concatenate([top, sims], 1), 1)[:, :k]
    return tuple(counts)


def fused_read_candidates_ref(q: jax.Array, mem: jax.Array, beta: jax.Array,
                              k: int, cand_idx: jax.Array, mem_scale=None):
    """Oracle for the fused ANN read: re-rank a *pre-deduped* signed
    candidate set (B, H, C), keep the top-K by (sim desc, position asc),
    then the shared tail. Invalid candidates (-1) re-rank at -1e9 —
    selectable only when fewer than K valid candidates exist, and then
    with exactly zero weight. ``mem_scale`` marks int8 rows (dequantized
    per candidate). Returns (read, weights, signed idx)."""
    b = jnp.arange(mem.shape[0])[:, None, None]
    cand = jax.lax.stop_gradient(mem)[b, jnp.maximum(cand_idx, 0)]
    cand = cand.astype(jnp.float32)                           # (B, H, C, W)
    if mem_scale is not None:
        cs = jax.lax.stop_gradient(mem_scale)[
            jnp.arange(mem.shape[0])[:, None, None], jnp.maximum(cand_idx, 0)]
        cand = cand * cs[..., None]
    qs = jax.lax.stop_gradient(q).astype(jnp.float32)
    qn = qs * jax.lax.rsqrt(jnp.sum(qs * qs, -1, keepdims=True) + 1e-6)
    cn = cand * jax.lax.rsqrt(jnp.sum(cand * cand, -1, keepdims=True) + 1e-6)
    sims = jnp.einsum("bhw,bhcw->bhc", qn, cn)
    sims = jnp.where(cand_idx < 0, -1e9, sims)
    _, pos = jax.lax.top_k(sims, k)
    idx = jnp.take_along_axis(cand_idx, pos, axis=-1)         # (B, H, K)
    read, w = sparse_read_tail(q, mem, beta, idx, mem_scale=mem_scale)
    return read, w, idx


def scatter_rows_ref(mem: jax.Array, idx: jax.Array, rows: jax.Array,
                     mode: str = "add"):
    """mem: (B,N,W), idx: (B,J), rows: (B,J,W). Sequential semantics for
    duplicate indices in 'set' mode (later j wins) — made explicit below
    because XLA's scatter-set order for conflicting updates is otherwise
    implementation-defined across platforms."""
    b = jnp.arange(mem.shape[0])[:, None]
    rows = rows.astype(mem.dtype)
    if mode == "add":
        return mem.at[b, idx].add(rows)
    # Replace every duplicate's row with its last occurrence's row, so the
    # scatter writes identical values regardless of XLA's update order.
    J = idx.shape[1]
    eq = idx[:, :, None] == idx[:, None, :]                  # (B, J, J)
    last = jnp.argmax(jnp.where(eq, jnp.arange(J)[None, None, :], -1), -1)
    rows = jnp.take_along_axis(rows, last[..., None], axis=1)
    return mem.at[b, idx].set(rows)


def lsh_hash_ref(x: jax.Array, planes: jax.Array):
    """x: (..., W), planes: (T, bits, W) -> bucket ids (..., T) int32."""
    proj = jnp.einsum("...w,tbw->...tb", x, planes)
    bits = (proj > 0).astype(jnp.int32)
    weights = 2 ** jnp.arange(planes.shape[1], dtype=jnp.int32)
    return (bits * weights).sum(axis=-1)


def usage_argmin_ref(last_access: jax.Array):
    """last_access: (B, N) -> LRA index per batch (B,) int32 (lowest index
    wins ties)."""
    return jnp.argmin(last_access, axis=-1).astype(jnp.int32)


def lra_topn_ref(last_access: jax.Array, n: int):
    """last_access: (B, N) -> the n least-recently-accessed slot indices per
    batch, (B, n) int32, most stale first. Ties break toward the lowest
    index (top_k stability)."""
    _, idx = jax.lax.top_k(-last_access, n)
    return idx.astype(jnp.int32)


def sparse_write_update_ref(mem: jax.Array, last_access: jax.Array,
                            write_idx: jax.Array, write_w: jax.Array,
                            a: jax.Array, lra_idx: jax.Array,
                            step: jax.Array, delta: float):
    """Oracle for the fused SAM write (erase + outer-product add + usage).

    mem: (B, N, W); last_access: (B, N) int32; write_idx: (B, J) int32 with
    J = H·(K+1); write_w: (B, J); a: (B, H, W) write words (head of column j
    is j // (K+1)); lra_idx: (B, H) rows to erase; step: () int32 or a
    per-batch-row (B,)/(B, 1) vector (per-lane session steps, the serving
    engine's layout). Also accepts scratch-row buffers ((B, N+1, W)/
    (B, N+1), indices < N): the scatter updates below never reach row N,
    so it passes through untouched.

    Semantics (matching `sam_step`'s unfused sequence exactly):
      1. mem[b, lra_idx]   = 0                       (R_t erase, eq. 6)
      2. mem[b, write_idx] += write_w · a            (A_t = w^W a^T, eq. 3/5;
                                                      duplicates accumulate)
      3. last_access[b, i]  = max(last_access, step) where any write with
                              weight > delta touched i (U^(2), §3.2)
    """
    B, H, W = a.shape
    J = write_idx.shape[1]
    kp1 = J // H
    b = jnp.arange(B)[:, None]
    mem = mem.at[b, lra_idx].set(jnp.zeros((B, lra_idx.shape[1], W), mem.dtype))
    add_rows = (write_w.reshape(B, H, kp1)[..., None]
                * a[:, :, None, :]).reshape(B, J, W)
    # One rounding per slot update under bf16 storage (scatter updates must
    # match the operand dtype; f32 memory is unaffected).
    mem = mem.at[b, write_idx].add(add_rows.astype(mem.dtype))
    upd = jnp.where(write_w > delta, step, last_access[b, write_idx])
    la = last_access.at[b, write_idx].max(upd)
    return mem, la


def _lane_step(step: jax.Array, batch: int) -> jax.Array:
    """Usage-stamp step as a broadcastable shape: () stays scalar, per-lane
    (B,)/(B, 1) vectors become (B, 1) — the jnp twin of the Pallas
    kernel's `_as_lane_step`."""
    step = jnp.asarray(step)
    return step if step.ndim == 0 else step.reshape(batch, 1)


def sparse_write_update_q_ref(mem: jax.Array, mem_scale: jax.Array,
                              last_access: jax.Array, write_idx: jax.Array,
                              write_w: jax.Array, a: jax.Array,
                              lra_idx: jax.Array, step: jax.Array,
                              delta: float):
    """Oracle for the fused SAM write under int8 memory storage.

    mem: (B, N, W) int8 rows; mem_scale: (B, N) f32 per-row scales; the
    other arguments match `sparse_write_update_ref`. Semantics: dequantize
    the touched rows only, apply the erase + w^W a^T accumulation in f32
    (duplicates accumulate into the same row), then re-quantize each
    touched row **once** (`core.quant.quantize_rows`) and scatter the new
    (int8 row, f32 scale) pair back. Untouched rows keep their exact bits.
    Returns (mem', last_access', mem_scale').

    Precondition (shared with the fused Pallas kernel): every lra_idx row
    also appears in write_idx — SAM's write plan puts the LRA slot in each
    head's K+1 columns, so erase-only rows do not exist.

    Gradients: the int8 scatter is non-differentiable, but the new scales
    are plain jnp (`max|row| / 127`), so autodiff carries exact
    magnitude-channel gradients to ``write_w``/``a`` and through the old
    scales — the straight-through scheme of docs/memory-model.md. No
    custom VJP is needed on this reference path."""
    B, H, W = a.shape
    J = write_idx.shape[1]
    kp1 = J // H
    b = jnp.arange(B)[:, None]
    old_q = mem[b, write_idx]                                 # (B, J, W) int8
    old_s = mem_scale[b, write_idx]                           # (B, J)
    old_f = old_q.astype(jnp.float32) * old_s[..., None]
    erased = (write_idx[:, :, None] == lra_idx[:, None, :]).any(-1)
    base = jnp.where(erased[..., None], 0.0, old_f)
    add = (write_w.reshape(B, H, kp1)[..., None]
           * a[:, :, None, :]).reshape(B, J, W).astype(jnp.float32)
    # Each column j rebuilds its *whole* target row: sum every column that
    # lands on the same slot, so duplicates produce identical rows and the
    # scatter-set below is order-independent (cf. `scatter_rows_ref`).
    eq = (write_idx[:, :, None] == write_idx[:, None, :]).astype(jnp.float32)
    new_f = base + jnp.einsum("bjk,bkw->bjw", eq, add)
    new_q, new_s = quantize_rows(new_f)                       # one rounding
    mem = mem.at[b, write_idx].set(new_q)
    mem_scale = mem_scale.at[b, write_idx].set(new_s)
    upd = jnp.where(write_w > delta, _lane_step(step, B),
                    last_access[b, write_idx])
    la = last_access.at[b, write_idx].max(upd)
    return mem, la, mem_scale


def scatter_rows_q_ref(mem: jax.Array, mem_scale: jax.Array, idx: jax.Array,
                       rows: jax.Array, rows_scale=None, mode: str = "add"):
    """`scatter_rows_ref` for int8 memory: every touched row is rebuilt in
    f32 and re-quantized once; untouched rows keep their exact bits.
    Returns (mem', mem_scale').

    'set' with int8 ``rows`` + ``rows_scale``: a bit-exact restore (the
    rollback path scatters recorded pre-write (row, scale) pairs; last
    duplicate wins, like `scatter_rows_ref`). 'set' with float rows:
    quantize then scatter. 'add': dequantize the target rows, accumulate
    every duplicate's contribution, re-quantize once."""
    b = jnp.arange(mem.shape[0])[:, None]
    J = idx.shape[1]
    if mode == "set":
        if rows.dtype == jnp.int8:
            assert rows_scale is not None, \
                "int8 'set' rows need their recorded scales"
            q, s = rows, rows_scale.astype(mem_scale.dtype)
        else:
            q, s = quantize_rows(rows)
        # Last duplicate wins, made order-independent as in scatter_rows_ref.
        eq = idx[:, :, None] == idx[:, None, :]
        last = jnp.argmax(jnp.where(eq, jnp.arange(J)[None, None, :], -1), -1)
        q = jnp.take_along_axis(q, last[..., None], axis=1)
        s = jnp.take_along_axis(s, last, axis=1)
        return mem.at[b, idx].set(q), mem_scale.at[b, idx].set(s)
    old_f = mem[b, idx].astype(jnp.float32) * mem_scale[b, idx][..., None]
    eq = (idx[:, :, None] == idx[:, None, :]).astype(jnp.float32)
    new_f = old_f + jnp.einsum("bjk,bkw->bjw", eq,
                               rows.astype(jnp.float32))
    q, s = quantize_rows(new_f)
    return mem.at[b, idx].set(q), mem_scale.at[b, idx].set(s)
