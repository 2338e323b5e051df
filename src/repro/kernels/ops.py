"""Backend-dispatched public wrappers for the kernel suite.

Every op takes ``backend=`` (a name, a :class:`~repro.kernels.registry.
KernelBackend`, or None → `REPRO_KERNEL_BACKEND` env var → the platform
default, ``"pallas"`` on a TPU and ``"ref"`` elsewhere) and
routes to that backend's implementation, falling back to the pure-jnp
oracles in `kernels/ref.py`. The backend choice is trace-time static.

Shape-fallback rule (documented contract, covered by tests): the Pallas
``topk_read``, ``lra_topn`` and ``usage_argmin`` tile the N axis, so when
N is not divisible by the (clamped) block size — or the input dtype is
unsupported (float ``lra_topn``) — the op uses the reference
implementation instead of failing: results are identical, only the
execution path differs. The served shapes always tile; `chip_smoke.py`
proves it on the chip by finding the kernels in the compiled step.
``scatter_rows``, ``lsh_hash`` and ``sparse_write_update`` have no shape
restrictions.

Scratch-row layout (docs/memory-model.md): the sweep ops take ``valid_n=``
to restrict the scan to the logical rows [0, valid_n) of a persistent
(B, N+1, ...) buffer, and the mutating ops take ``scratch_row=`` to park
duplicate write indices on the in-state scratch row instead of padding a
transient one (the retired O(N·W) pad/slice path, kept only for
``scratch_row=None`` legacy callers). On the reference fallback ``valid_n``
is applied as a slice — fused by XLA into the O(N·W) oracle sweep it
already performs. Divisibility checks use ``valid_n``, so the padded buffer
(N+1 rows) keeps the kernel path whenever the logical N qualifies.

Backend ``overrides`` written before these keywords existed keep working:
the dispatch inspects the override's signature and, when it cannot accept
the keyword, adapts instead — sweep ops hand the override the sliced
[0, valid_n) view (correct, at the cost of an O(N) slice per call), and
mutating ops simply drop ``scratch_row`` (safe: the oracle contract says
an implementation touches only the rows its indices name, so the padded
buffer's row N passes through untouched). Overrides that do accept the
keywords get them whenever the caller sets them.

Gradients: the Pallas kernels have no VJP of their own, so the mutating ops
(`scatter_rows`, `sparse_write_update`) are wrapped in closed-form
`jax.custom_vjp` rules here — both the naive SAM unroll and the rollback
BPTT replay differentiate through them. The selection ops (`topk_read`,
`lra_topn`, `usage_argmin`, `lsh_hash`) return integers or are used under
`stop_gradient` and need no rule.

Mesh-native route (docs/sharding.md): under an active
`repro.distributed.mem_shard.memory_mesh` context, a buffer in the
context's slot-sharded layout (N + shards rows, one scratch row per shard)
routes through the `shard_map` implementations in `distributed/mem_shard.py`
*before* any backend dispatch — inside each shard the op re-enters this
module with the same ``backend`` and the shard-local
``valid_n``/``scratch_row``, so ref/pallas backends and custom overrides
run untouched per shard. The route is keyed on the row count, which only
matches the whole-buffer shape (a shard-local block has N/S + 1 rows, never
N + S), so the inner dispatch cannot recurse.
"""
from __future__ import annotations

import functools
import inspect

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.fused_read import \
    fused_read_candidates as fused_read_cand_pallas
from repro.kernels.fused_read import fused_read_sweep as fused_read_pallas
from repro.kernels.fused_read import sweep_block
from repro.kernels.lsh_hash import lsh_hash as lsh_hash_pallas
from repro.kernels.registry import BackendSpec, resolve
from repro.kernels.scatter_rows import scatter_rows as scatter_rows_pallas
from repro.kernels.sparse_write import \
    sparse_write_update as sparse_write_pallas
from repro.kernels.topk_read import topk_read as topk_read_pallas
from repro.kernels.usage_argmin import lra_topn as lra_topn_pallas
from repro.kernels.usage_argmin import usage_argmin as usage_argmin_pallas


def _zero_ct(x):
    """Zero cotangent with the dtype JAX expects (float0 for ints)."""
    if jnp.issubdtype(x.dtype, jnp.floating):
        return jnp.zeros_like(x)
    return np.zeros(x.shape, dtype=jax.dtypes.float0)


def detach_int(x):
    """Detach an integer array from the autodiff tracer chain.

    `lax.stop_gradient` is an identity short-circuit for ints, so an int32
    output of a `custom_vjp` still carries a (float0) tangent tracer — and
    JAX's integer scatter-max JVP rule downstream is broken (it mixes f32
    normalizers into an int select). `bitwise_or` has a `defjvp_zero` rule,
    so ``x | 0`` produces the plain primal with a symbolic-zero tangent."""
    return jnp.bitwise_or(x, jnp.zeros((), x.dtype))


_detach_int = detach_int


# --------------------------------------------------------------------------
# Selection ops (no gradients needed)
# --------------------------------------------------------------------------

def _accepts_kw(fn, name: str) -> bool:
    """True when `fn` can take keyword `name` (explicitly or via **kwargs).
    Unintrospectable callables are assumed to accept it."""
    try:
        params = inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return True
    return name in params or any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values())


def _opt_kw(**kw):
    """Keyword dict with the None-valued entries dropped (overrides only see
    the layout keywords when the caller actually uses them)."""
    return {k: v for k, v in kw.items() if v is not None}


def _mesh_route(buf_rows: int):
    """The active mem-shard context when `buf_rows` matches its sharded
    layout (module docstring), else None. Imported lazily: mem_shard
    imports this module for the shard-local inner dispatch."""
    from repro.distributed import mem_shard
    return mem_shard.route_ctx(buf_rows)


def topk_read(q, mem, k: int, *, backend: BackendSpec = None,
              block_n: int = 512, valid_n: int = None):
    """q: (B,H,W), mem: (B,N,W) -> (vals, idx) each (B,H,k), cosine
    similarity descending. ``valid_n`` restricts the sweep to the logical
    rows [0, valid_n) (scratch-row layout)."""
    if (ctx := _mesh_route(mem.shape[1])) is not None:
        from repro.distributed import mem_shard
        if valid_n is not None:
            raise ValueError("valid_n is meaningless on a slot-sharded "
                             "buffer: the mesh route derives its own "
                             "shard-local valid_n")
        return mem_shard.topk_read_sharded(ctx, q, mem, k, backend=backend,
                                           block_n=block_n)
    be = resolve(backend)
    if (impl := be.impl("topk_read")) is not None:
        if valid_n is not None and not _accepts_kw(impl, "valid_n"):
            return impl(q, mem[:, :valid_n], k, block_n=block_n)
        return impl(q, mem, k, block_n=block_n, **_opt_kw(valid_n=valid_n))
    nv = mem.shape[1] if valid_n is None else valid_n
    bn = min(block_n, nv)
    if be.use_pallas and nv % bn == 0:
        return topk_read_pallas(q, mem, k=k, block_n=bn,
                                interpret=be.interpret, valid_n=valid_n)
    m = mem if valid_n is None else mem[:, :valid_n]
    return ref.topk_read_ref(q, m, k)


def lsh_hash(x, planes, *, backend: BackendSpec = None):
    """x: (..., W), planes: (T, bits, W) -> bucket ids (..., T) int32."""
    be = resolve(backend)
    if (impl := be.impl("lsh_hash")) is not None:
        return impl(x, planes)
    if be.use_pallas:
        shape = x.shape
        out = lsh_hash_pallas(x.reshape(-1, shape[-1]), planes,
                              interpret=be.interpret)
        return out.reshape(shape[:-1] + (planes.shape[0],))
    return ref.lsh_hash_ref(x, planes)


def usage_argmin(last_access, *, backend: BackendSpec = None,
                 block_n: int = 1024, valid_n: int = None):
    """last_access: (B, N) -> (B,) int32 argmin (lowest index on ties) over
    the logical rows [0, valid_n) (default: all)."""
    if (ctx := _mesh_route(last_access.shape[1])) is not None:
        from repro.distributed import mem_shard
        if valid_n is not None:
            raise ValueError("valid_n is meaningless on a slot-sharded "
                             "buffer: the mesh route derives its own "
                             "shard-local valid_n")
        return mem_shard.usage_argmin_sharded(ctx, last_access,
                                              backend=backend)
    be = resolve(backend)
    if (impl := be.impl("usage_argmin")) is not None:
        if valid_n is not None and not _accepts_kw(impl, "valid_n"):
            return impl(last_access[:, :valid_n])
        return impl(last_access, **_opt_kw(valid_n=valid_n))
    nv = last_access.shape[1] if valid_n is None else valid_n
    bn = min(block_n, nv)
    if be.use_pallas and nv % bn == 0:
        return usage_argmin_pallas(last_access, block_n=bn,
                                   interpret=be.interpret, valid_n=valid_n)
    la = last_access if valid_n is None else last_access[:, :valid_n]
    return ref.usage_argmin_ref(la)


def lra_topn(last_access, n: int, *, backend: BackendSpec = None,
             block_n: int = 1024, valid_n: int = None):
    """last_access: (B, N) -> (B, n) int32 least-recently-accessed rows
    among the logical rows [0, valid_n) (default: all), most stale first
    (ties toward the lowest index)."""
    if (ctx := _mesh_route(last_access.shape[1])) is not None:
        from repro.distributed import mem_shard
        if valid_n is not None:
            raise ValueError("valid_n is meaningless on a slot-sharded "
                             "buffer: the mesh route derives its own "
                             "shard-local valid_n")
        return mem_shard.lra_topn_sharded(ctx, last_access, n,
                                          backend=backend)
    be = resolve(backend)
    if (impl := be.impl("lra_topn")) is not None:
        if valid_n is not None and not _accepts_kw(impl, "valid_n"):
            return impl(last_access[:, :valid_n], n)
        return impl(last_access, n, **_opt_kw(valid_n=valid_n))
    nv = last_access.shape[1] if valid_n is None else valid_n
    bn = min(block_n, nv)
    # Integer inputs only on the kernel path: the tiled kernel compares in
    # int32, and float usage tables (e.g. DAM's U^(1)) would silently
    # truncate — those fall back to the exact reference.
    if (be.use_pallas and jnp.issubdtype(last_access.dtype, jnp.integer)
            and nv % bn == 0 and n <= bn):
        return lra_topn_pallas(last_access, n=n, block_n=bn,
                               interpret=be.interpret, valid_n=valid_n)
    la = last_access if valid_n is None else last_access[:, :valid_n]
    return ref.lra_topn_ref(la, n)


# --------------------------------------------------------------------------
# Fused one-dispatch SAM read (differentiable)
# --------------------------------------------------------------------------

def fused_read(q, mem, beta, k: int, *, cand_idx=None,
               backend: BackendSpec = None, block_n: int = None,
               valid_n: int = None, mem_scale=None):
    """The whole sparse read in one kernel dispatch. q: (B, H, W),
    mem: (B, N, W), beta: (B, H) -> (read (B, H, W) f32, weights (B, H, K),
    signed indices (B, H, K) int32).

    With ``cand_idx=None``: the exact read — similarity sweep over rows
    [0, valid_n), top-K, softmax tail fused (`fused_read_sweep`). With
    ``cand_idx`` (B, H, C) *signed, pre-deduped* LSH candidates: the
    ANN-mode read with grid independent of N (`fused_read_candidates`).
    Selection is non-differentiable; read/weights carry the composed
    path's exact gradients (custom VJP re-derives `ref.sparse_read_tail`
    from the recorded indices). The exact sweep's rows per tile are
    ``block_n`` clamped to N, or by default `fused_read.sweep_block`'s,
    derived from N and W. Falls back to the jnp oracle when N is not
    divisible by the block size (exact) or C < k (ANN) — identical
    results, composed execution.

    Int8 memory storage: ``mem_scale`` (B, N) f32 per-row scales mark int8
    rows. Both Pallas kernels dequantize **inside** the (still single)
    dispatch; gradients flow to q/beta exactly and to the scales through
    the dequantized gather (the rows themselves are integer: float0 —
    docs/memory-model.md, "storage dtype ladder"). Backend ``overrides``
    that predate ``mem_scale`` are bypassed for int8 buffers (they would
    misread raw quantized rows); the built-in kernels/oracle run instead.

    Slot-sharded buffers (`mem_shard.memory_mesh`) have no fused route:
    the caller (core/addressing.py) keeps the composed
    shard_map path there."""
    if _mesh_route(mem.shape[1]) is not None:
        raise ValueError(
            "fused_read has no slot-sharded route; use the composed "
            "topk_read/gather path (core.addressing falls back to it "
            "under an active memory_mesh)")
    be = resolve(backend)
    impl = be.impl("fused_read")
    if impl is not None and mem_scale is not None \
            and not _accepts_kw(impl, "mem_scale"):
        impl = None                      # pre-int8 override: use built-ins
    if impl is not None:
        kw = _opt_kw(mem_scale=mem_scale)
        if valid_n is not None and not _accepts_kw(impl, "valid_n"):
            out = impl(q, mem[:, :valid_n], beta, k, cand_idx=cand_idx,
                       **_opt_kw(block_n=block_n), **kw)
        else:
            out = impl(q, mem, beta, k, cand_idx=cand_idx,
                       **_opt_kw(block_n=block_n, valid_n=valid_n,
                                 mem_scale=mem_scale))
        read, w, idx = out
        return read, w, _detach_int(idx)
    if cand_idx is not None:
        if be.use_pallas and cand_idx.shape[-1] >= k:
            if mem_scale is not None:
                out = _fused_read_cand_q_vjp(q, mem, mem_scale, beta,
                                             cand_idx, k, be.interpret)
            else:
                out = _fused_read_cand_vjp(q, mem, beta, cand_idx, k,
                                           be.interpret)
        else:
            out = ref.fused_read_candidates_ref(q, mem, beta, k, cand_idx,
                                                mem_scale=mem_scale)
        read, w, idx = out
        return read, w, _detach_int(idx)
    if be.impl("topk_read") is not None:
        # Partial backend: it accelerates the composed sweep but has no
        # fused read — honor its override by composing (identical results,
        # composed execution; the docs/kernels.md extension contract). An
        # int8 buffer hands the override a dequantized f32 sweep view (the
        # override predates quantized rows).
        mv = mem if mem_scale is None \
            else ref._deq_view(mem, mem_scale)
        _, idx = topk_read(jax.lax.stop_gradient(q),
                           jax.lax.stop_gradient(mv), k, backend=be,
                           valid_n=valid_n, **_opt_kw(block_n=block_n))
        read, w = ref.sparse_read_tail(q, mem, beta, idx,
                                       mem_scale=mem_scale)
        return read, w, _detach_int(idx)
    nv = mem.shape[1] if valid_n is None else valid_n
    bn = sweep_block(nv, mem.shape[2]) if block_n is None \
        else min(block_n, nv)
    if be.use_pallas and nv % bn == 0 and bn >= k:
        if mem_scale is not None:
            out = _fused_read_sweep_q_vjp(q, mem, mem_scale, beta, k, bn,
                                          be.interpret, valid_n)
        else:
            out = _fused_read_sweep_vjp(q, mem, beta, k, bn, be.interpret,
                                        valid_n)
    else:
        out = ref.fused_read_ref(q, mem, beta, k, valid_n=valid_n,
                                 mem_scale=mem_scale)
    read, w, idx = out
    return read, w, _detach_int(idx)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _fused_read_sweep_vjp(q, mem, beta, k, block_n, interpret, valid_n):
    return fused_read_pallas(q, mem, beta, k=k, block_n=block_n,
                             interpret=interpret, valid_n=valid_n)


def _fused_read_sweep_fwd(q, mem, beta, k, block_n, interpret, valid_n):
    out = _fused_read_sweep_vjp(q, mem, beta, k, block_n, interpret, valid_n)
    return out, (q, mem, beta, out[2])


def _fused_read_sweep_bwd(k, block_n, interpret, valid_n, res, ct):
    q, mem, beta, idx = res
    g_read, g_w, _ = ct                               # idx is int: float0 ct
    # Selection (idx) is non-differentiable; everything after it is exactly
    # the composed path's tail, so its VJP *is* the composed gradient.
    _, vjp_fn = jax.vjp(
        lambda q_, m_, b_: ref.sparse_read_tail(q_, m_, b_, idx),
        q, mem, beta)
    return vjp_fn((g_read, g_w))


_fused_read_sweep_vjp.defvjp(_fused_read_sweep_fwd, _fused_read_sweep_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fused_read_cand_vjp(q, mem, beta, cand_idx, k, interpret):
    return fused_read_cand_pallas(q, mem, beta, cand_idx, k=k,
                                  interpret=interpret)


def _fused_read_cand_fwd(q, mem, beta, cand_idx, k, interpret):
    out = _fused_read_cand_vjp(q, mem, beta, cand_idx, k, interpret)
    return out, (q, mem, beta, cand_idx, out[2])


def _fused_read_cand_bwd(k, interpret, res, ct):
    q, mem, beta, cand_idx, idx = res
    g_read, g_w, _ = ct
    _, vjp_fn = jax.vjp(
        lambda q_, m_, b_: ref.sparse_read_tail(q_, m_, b_, idx),
        q, mem, beta)
    g_q, g_mem, g_beta = vjp_fn((g_read, g_w))
    return g_q, g_mem, g_beta, _zero_ct(cand_idx)


_fused_read_cand_vjp.defvjp(_fused_read_cand_fwd, _fused_read_cand_bwd)


# Int8 variants: same kernels with the per-row scale operand. The memory
# argument is integer, so its cotangent is float0 (the direction channel is
# straight-through-truncated — docs/memory-model.md); the f32 scale leaf
# gets the exact gradient of the dequantized gather via the ref tail.

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _fused_read_sweep_q_vjp(q, mem, mem_scale, beta, k, block_n, interpret,
                            valid_n):
    return fused_read_pallas(q, mem, beta, k=k, block_n=block_n,
                             interpret=interpret, valid_n=valid_n,
                             mem_scale=mem_scale)


def _fused_read_sweep_q_fwd(q, mem, mem_scale, beta, k, block_n, interpret,
                            valid_n):
    out = _fused_read_sweep_q_vjp(q, mem, mem_scale, beta, k, block_n,
                                  interpret, valid_n)
    return out, (q, mem, mem_scale, beta, out[2])


def _fused_read_sweep_q_bwd(k, block_n, interpret, valid_n, res, ct):
    q, mem, mem_scale, beta, idx = res
    g_read, g_w, _ = ct                               # idx is int: float0 ct
    _, vjp_fn = jax.vjp(
        lambda q_, s_, b_: ref.sparse_read_tail(q_, mem, b_, idx,
                                                mem_scale=s_),
        q, mem_scale, beta)
    g_q, g_s, g_beta = vjp_fn((g_read, g_w))
    return g_q, _zero_ct(mem), g_s, g_beta


_fused_read_sweep_q_vjp.defvjp(_fused_read_sweep_q_fwd,
                               _fused_read_sweep_q_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _fused_read_cand_q_vjp(q, mem, mem_scale, beta, cand_idx, k, interpret):
    return fused_read_cand_pallas(q, mem, beta, cand_idx, k=k,
                                  interpret=interpret, mem_scale=mem_scale)


def _fused_read_cand_q_fwd(q, mem, mem_scale, beta, cand_idx, k, interpret):
    out = _fused_read_cand_q_vjp(q, mem, mem_scale, beta, cand_idx, k,
                                 interpret)
    return out, (q, mem, mem_scale, beta, cand_idx, out[2])


def _fused_read_cand_q_bwd(k, interpret, res, ct):
    q, mem, mem_scale, beta, cand_idx, idx = res
    g_read, g_w, _ = ct
    _, vjp_fn = jax.vjp(
        lambda q_, s_, b_: ref.sparse_read_tail(q_, mem, b_, idx,
                                                mem_scale=s_),
        q, mem_scale, beta)
    g_q, g_s, g_beta = vjp_fn((g_read, g_w))
    return g_q, _zero_ct(mem), g_s, g_beta, _zero_ct(cand_idx)


_fused_read_cand_q_vjp.defvjp(_fused_read_cand_q_fwd, _fused_read_cand_q_bwd)


# --------------------------------------------------------------------------
# scatter_rows (differentiable)
# --------------------------------------------------------------------------

def scatter_rows(mem, idx, rows, mode: str = "add", *,
                 backend: BackendSpec = None, scratch_row: int = None,
                 mem_scale=None, rows_scale=None):
    """mem: (B,N,W), idx: (B,J) int32, rows: (B,J,W) -> updated memory.

    'add' accumulates duplicate indices; 'set' takes the last write
    (sequential semantics, j ascending). ``scratch_row=N`` marks a
    persistent (B, N+1, W) scratch-row buffer: 'add' parks duplicates on
    row N in place instead of padding a transient row.

    Int8 storage (``mem_scale`` (B, N) f32 given): routes to
    `ref.scatter_rows_q_ref` and returns (mem', mem_scale'). With int8
    ``rows`` + ``rows_scale``, 'set' restores the recorded (row, scale)
    bits exactly (rollback); float rows are re-quantized — once per
    target row ('add' accumulates all duplicates in f32 first). The jnp
    oracle is plainly differentiable (scale/value gradients via autodiff;
    the int8 leaves carry float0), so no Pallas variant or custom VJP is
    needed — scatter traffic is O(J·W) either way."""
    if (ctx := _mesh_route(mem.shape[1])) is not None:
        from repro.distributed import mem_shard
        if scratch_row is not None:
            raise ValueError("scratch_row is meaningless on a slot-sharded "
                             "buffer: each shard parks on its own local "
                             "scratch row")
        return mem_shard.scatter_rows_sharded(
            ctx, mem, idx, rows, mode, backend=backend,
            **_opt_kw(mem_scale=mem_scale, rows_scale=rows_scale))
    if mem_scale is not None:
        return ref.scatter_rows_q_ref(mem, mem_scale, idx, rows,
                                      rows_scale=rows_scale, mode=mode)
    # Cast OUTSIDE the custom_vjp below: the astype's transpose then
    # converts the (bf16) memory cotangent back to the caller's rows dtype;
    # casting inside would leak a bf16 cotangent against an f32 primal.
    rows = rows.astype(mem.dtype)
    be = resolve(backend)
    if (impl := be.impl("scatter_rows")) is not None:
        if scratch_row is not None and not _accepts_kw(impl, "scratch_row"):
            # Oracle contract: only indexed rows are touched, so the padded
            # buffer's scratch row passes through an old-signature override.
            return impl(mem, idx, rows, mode=mode)
        return impl(mem, idx, rows, mode=mode,
                    **_opt_kw(scratch_row=scratch_row))
    if be.use_pallas:
        return _scatter_rows_vjp(mem, idx, rows, mode, be.interpret,
                                 scratch_row)
    # The jnp oracle is layout-agnostic: indices stay below the scratch row.
    return ref.scatter_rows_ref(mem, idx, rows, mode)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _scatter_rows_vjp(mem, idx, rows, mode, interpret, scratch_row):
    return scatter_rows_pallas(mem, idx, rows, mode=mode, interpret=interpret,
                               scratch_row=scratch_row)


def _scatter_rows_fwd(mem, idx, rows, mode, interpret, scratch_row):
    return _scatter_rows_vjp(mem, idx, rows, mode, interpret, scratch_row), idx


def _scatter_rows_bwd(mode, interpret, scratch_row, idx, g):
    B, J = idx.shape
    b = jnp.arange(B)[:, None]
    g_gather = g[b, idx]                              # (B, J, W)
    if mode == "add":
        return g, _zero_ct(idx), g_gather
    # 'set': overwritten rows receive no cotangent; among duplicates only
    # the last write survives the primal, so only it gets the cotangent.
    g_mem = g.at[b, idx].set(0.0)
    later_same = (idx[:, :, None] == idx[:, None, :]) \
        & (jnp.arange(J)[None, :] > jnp.arange(J)[:, None])[None]
    is_last = ~later_same.any(-1)                     # (B, J)
    return g_mem, _zero_ct(idx), jnp.where(is_last[..., None], g_gather, 0.0)


_scatter_rows_vjp.defvjp(_scatter_rows_fwd, _scatter_rows_bwd)


# --------------------------------------------------------------------------
# Fused SAM write + usage update (differentiable)
# --------------------------------------------------------------------------

def sparse_write_update(mem, last_access, write_idx, write_w, a, lra_idx,
                        step, *, delta: float, backend: BackendSpec = None,
                        scratch_row: int = None, mem_scale=None):
    """Fused LRA erase + scatter-add of w^W a^T + last-access update.

    See `ref.sparse_write_update_ref` for the exact contract. Returns
    (mem', last_access'). ``scratch_row=N`` marks the persistent
    (B, N+1, W)/(B, N+1) scratch-row layout — the Pallas path then runs
    with no pad/slice around the kernel (row N is a fixed point of the
    update; the jnp oracle never touches it because every index is < N).
    The usage output is non-differentiable (the paper passes no gradients
    through U^(2)) and is explicitly detached so downstream integer scatter
    ops never see a tangent tracer.

    Int8 storage (``mem_scale`` (B, rows) f32 given): the touched rows are
    dequantized, updated, and re-quantized once in the same pass
    (`kernels/sparse_write._kernel_q` / `ref.sparse_write_update_q_ref`);
    returns (mem', last_access', mem_scale'). Gradients: mem'/la' are
    integer (float0 — straight-through truncation through the stored
    rows); mem_scale' carries exact autodiff gradients to mem_scale,
    write_w, and a (the Pallas path's custom VJP re-runs the jnp oracle's
    scale output under `jax.vjp`). Backend overrides that predate
    ``mem_scale`` are bypassed for int8 buffers."""
    if (ctx := _mesh_route(mem.shape[1])) is not None:
        from repro.distributed import mem_shard
        if scratch_row is not None:
            raise ValueError("scratch_row is meaningless on a slot-sharded "
                             "buffer: each shard parks on its own local "
                             "scratch row")
        if mem_scale is not None:
            mem_out, la_out, scale_out = \
                mem_shard.sparse_write_update_sharded(
                    ctx, mem, last_access, write_idx, write_w, a, lra_idx,
                    step, delta=delta, backend=backend, mem_scale=mem_scale)
            return mem_out, _detach_int(la_out), scale_out
        mem_out, la_out = mem_shard.sparse_write_update_sharded(
            ctx, mem, last_access, write_idx, write_w, a, lra_idx, step,
            delta=delta, backend=backend)
        return mem_out, _detach_int(la_out)
    be = resolve(backend)
    if mem_scale is not None:
        impl = be.impl("sparse_write_update")
        if impl is not None and _accepts_kw(impl, "mem_scale"):
            out = impl(mem, last_access, write_idx, write_w, a, lra_idx,
                       step, delta=delta, mem_scale=mem_scale,
                       **_opt_kw(scratch_row=scratch_row))
        elif be.use_pallas:
            out = _sparse_write_q_vjp(mem, last_access, mem_scale,
                                      write_idx, write_w, a, lra_idx, step,
                                      delta, be.interpret, scratch_row)
        else:
            out = ref.sparse_write_update_q_ref(mem, mem_scale, last_access,
                                                write_idx, write_w, a,
                                                lra_idx, step, delta)
        mem_out, la_out, scale_out = out
        return mem_out, _detach_int(la_out), scale_out
    if (impl := be.impl("sparse_write_update")) is not None:
        if scratch_row is not None and not _accepts_kw(impl, "scratch_row"):
            out = impl(mem, last_access, write_idx, write_w, a, lra_idx,
                       step, delta=delta)
        else:
            out = impl(mem, last_access, write_idx, write_w, a, lra_idx,
                       step, delta=delta, **_opt_kw(scratch_row=scratch_row))
    elif be.use_pallas:
        out = _sparse_write_vjp(mem, last_access, write_idx, write_w, a,
                                lra_idx, step, delta, be.interpret,
                                scratch_row)
    else:
        out = ref.sparse_write_update_ref(mem, last_access, write_idx,
                                          write_w, a, lra_idx, step, delta)
    mem_out, la_out = out
    return mem_out, _detach_int(la_out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _sparse_write_vjp(mem, last_access, write_idx, write_w, a, lra_idx,
                      step, delta, interpret, scratch_row):
    return sparse_write_pallas(mem, last_access, write_idx, write_w, a,
                               lra_idx, step, delta=delta,
                               interpret=interpret, scratch_row=scratch_row)


def _sparse_write_fwd(mem, last_access, write_idx, write_w, a, lra_idx,
                      step, delta, interpret, scratch_row):
    out = _sparse_write_vjp(mem, last_access, write_idx, write_w, a,
                            lra_idx, step, delta, interpret, scratch_row)
    return out, (last_access, write_idx, a, write_w, lra_idx, step)


def _sparse_write_bwd(delta, interpret, scratch_row, res, ct):
    last_access, write_idx, a, write_w, lra_idx, step = res
    g_mem_out, _ = ct                                 # la' is int: float0 ct
    B, H, W = a.shape
    J = write_idx.shape[1]
    kp1 = J // H
    b = jnp.arange(B)[:, None]
    # mem' rows: erased rows lose their mem dependence, all others identity.
    g_mem = g_mem_out.at[b, lra_idx].set(0.0)
    # w_j and a_h see the output cotangent at their target rows; duplicates
    # each read the same row (the primal sums their contributions).
    g_rows = g_mem_out[b, write_idx]                  # (B, J, W)
    a_per_j = jnp.repeat(a, kp1, axis=1)              # (B, J, W)
    g_w = (g_rows * a_per_j).sum(-1)                  # (B, J)
    g_a = (write_w.reshape(B, H, kp1)[..., None]
           * g_rows.reshape(B, H, kp1, W)).sum(2)     # (B, H, W)
    return (g_mem, _zero_ct(last_access), _zero_ct(write_idx), g_w, g_a,
            _zero_ct(lra_idx), _zero_ct(step))


_sparse_write_vjp.defvjp(_sparse_write_fwd, _sparse_write_bwd)


# Int8 variant. Outputs: mem' (int8) and la' (int32) carry float0
# cotangents — only the f32 mem_scale' output is differentiable. Its
# backward re-runs the jnp oracle's scale output under `jax.vjp`, which
# yields the exact gradients to (mem_scale, write_w, a): the scale of a
# touched row is max|new_f|/127 with new_f = dequant(old) [unless erased]
# + accumulated w_j·a_h, so the magnitude channel trains while the stored
# direction bits are straight-through-truncated (docs/memory-model.md).

@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def _sparse_write_q_vjp(mem, last_access, mem_scale, write_idx, write_w, a,
                        lra_idx, step, delta, interpret, scratch_row):
    return sparse_write_pallas(mem, last_access, write_idx, write_w, a,
                               lra_idx, step, delta=delta,
                               interpret=interpret, scratch_row=scratch_row,
                               mem_scale=mem_scale)


def _sparse_write_q_fwd(mem, last_access, mem_scale, write_idx, write_w, a,
                        lra_idx, step, delta, interpret, scratch_row):
    out = _sparse_write_q_vjp(mem, last_access, mem_scale, write_idx,
                              write_w, a, lra_idx, step, delta, interpret,
                              scratch_row)
    return out, (mem, last_access, mem_scale, write_idx, write_w, a,
                 lra_idx, step)


def _sparse_write_q_bwd(delta, interpret, scratch_row, res, ct):
    mem, last_access, mem_scale, write_idx, write_w, a, lra_idx, step = res
    _, _, g_scale_out = ct                # mem'/la' are int: float0 cts
    _, vjp_fn = jax.vjp(
        lambda s_, w_, a_: ref.sparse_write_update_q_ref(
            mem, s_, last_access, write_idx, w_, a_, lra_idx, step,
            delta)[2],
        mem_scale, write_w, a)
    g_s, g_w, g_a = vjp_fn(g_scale_out)
    return (_zero_ct(mem), _zero_ct(last_access), g_s, _zero_ct(write_idx),
            g_w, g_a, _zero_ct(lra_idx), _zero_ct(step))


_sparse_write_q_vjp.defvjp(_sparse_write_q_fwd, _sparse_write_q_bwd)
