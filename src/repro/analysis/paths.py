"""The contract zoo: every hot path's complexity contract, declared.

Each builder returns a `measure.Target` for one (sizes, backend) cell;
the `Contract` around it declares the asymptotic envelope the paper's
O(K·W) story requires of that entry point, plus the structural facts
(dispatch counts, kernel names, collective fingerprints, lints,
donation) that pin the path's *shape*, not just its totals.

Organization mirrors the claims:

* **SAM read** — the LSH candidate read is flat in N; the exact read is
  declared-linear (the similarity sweep is inherently O(N·W) — the paper
  point is that serving uses the ANN path); on the Pallas backends the
  exact read is ONE `fused_read_sweep` dispatch with no top_k/sort, and the
  composed control must trip that detector.
* **Fused write** — the scratch-row layout stages no O(N·W) pad/slice
  copy of the buffer (`scratch_copy` lint); the legacy layout on the
  pallas path is the positive control that the lint can fire.
* **Decode step** — a full `sam_step` in LSH mode at serving shapes is
  flat in N on flops and HBM; the LM decode step is declared-O(N) on
  the ref backend (exact read) and top_k-free on pallas; donated step
  functions must keep their carries aliased.
* **Sharded paths** (8 forced host devices) — mesh-native step, sharded
  LSH step/insert, sharded `ann_build`, and the 2D (data × model) step
  move flat collective bytes with no near-full-buffer collective; the
  GSPMD legacy route is the positive control whose collective bytes
  MUST grow with N.

Positive controls carry ``expect_trip=True``: they pass only by
failing, which keeps every detector in this file honest.

Shape policy: read/step contracts use serving-scale words (W=128) —
at toy W the fixed controller traffic hides the N-dependence this suite
exists to bound. Mesh contracts reuse benchmarks/bench_shard.py's small
shapes: collective *bytes* there are exact layout facts at any W.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.analysis.contracts import Contract, register
from repro.analysis.measure import Target
from repro.core import addressing as addr
from repro.core import ann as ann_lib
from repro.core import sam as sam_lib
from repro.core import unroll as unroll_lib
from repro.core.cell import SAMCell
from repro.core.types import ControllerConfig, MemoryConfig
from repro.distributed import mem_shard
from repro.kernels import ops
from repro.launch.mesh import make_mesh

# ---------------------------------------------------------------------------
# Serving-scale shapes for the single-device read/step contracts.
# ---------------------------------------------------------------------------

_B, _H, _W, _K, _D = 2, 4, 128, 8, 32
_CTL = ControllerConfig(_D, 64, _D)
_SIZES = {"B": _B, "H": _H, "W": _W, "K": _K}


def _mem_cfg(n: int, backend: str, *, ann: str = "exact",
             mem_dtype=None) -> MemoryConfig:
    kw = {}
    if ann == "lsh":
        kw = dict(ann="lsh", lsh_tables=4, lsh_bits=6, lsh_bucket_size=32)
    if mem_dtype is not None:
        kw["mem_dtype"] = mem_dtype
    return MemoryConfig(num_slots=n, word_size=_W, num_heads=_H, k=_K,
                        backend=backend, **kw)


def _read_case(n: int, *, dtype=jnp.float32, scratch: bool = False):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    rows = n + 1 if scratch else n
    q = jax.random.normal(ks[0], (_B, _H, _W))
    mem = jax.random.normal(ks[1], (_B, rows, _W)).astype(dtype)
    beta = jax.random.uniform(ks[2], (_B, _H), minval=1.0, maxval=3.0)
    return q, mem, beta


def _read_meminfo(n: int, *, buf_rows=None, word=_W, batch=_B, itemsize=4):
    return {"num_slots": n, "buf_rows": n if buf_rows is None else buf_rows,
            "word_size": word, "buffer_bytes": batch * n * word * itemsize}


# ---------------------------------------------------------------------------
# SAM read
# ---------------------------------------------------------------------------

def _build_sam_read(sizes, backend):
    """The LSH-mode candidate read: re-rank a fixed-size candidate set
    against the buffer — K·W work however many slots exist."""
    n, c = sizes["N"], sizes["C"]
    q, mem, beta = _read_case(n)
    cand = jax.random.randint(jax.random.PRNGKey(7), (_B, _H, c), 0, n)

    def fn(q, mem, beta, cand):
        sr, _ = addr.select_and_read_candidates(q, mem, beta, _K, cand,
                                                backend=backend)
        return sr

    return Target(fn=fn, args=(q, mem, beta, cand),
                  meminfo=_read_meminfo(n))


@register
def sam_read():
    return Contract(
        name="sam_read", build=_build_sam_read,
        sizes={**_SIZES, "C": 128},
        backends=("ref", "pallas-interpret"),
        notes="LSH candidate read: flat in N on every resource "
              "(flops/hbm judged on ref; dispatch profile everywhere).")


def _build_sam_read_exact(sizes, backend):
    n = sizes["N"]
    q, buf, beta = _read_case(n, scratch=True)

    def fn(q, buf, beta):
        return addr.sparse_read_exact(q, buf, beta, _K, backend=backend,
                                      valid_n=n)

    return Target(fn=fn, args=(q, buf, beta),
                  meminfo=_read_meminfo(n, buf_rows=n + 1))


@register
def sam_read_exact():
    return Contract(
        name="sam_read_exact", build=_build_sam_read_exact,
        sizes=dict(_SIZES),
        flops="O(B*H*N*W)", hbm="O(B*N*W)",
        backends=("ref", "pallas-interpret"),
        notes="The exact read's similarity sweep is inherently linear in "
              "N — declared so. Anything superlinear (or a stray O(N^2) "
              "materialization) trips this contract.")


@register
def sam_read_exact_kernel():
    return Contract(
        name="sam_read_exact_kernel", build=_build_sam_read_exact,
        sizes=dict(_SIZES), points=(256, 1024), quick_points=None,
        dispatches={"pallas_call": 1, "top_k": 0, "sort": 0},
        kernels={"fused_read_sweep": 1},
        backends=("pallas-interpret",),
        notes="On the Pallas backend the exact read is ONE fused "
              "fused_read_sweep dispatch: no top_k, no sort "
              "(tests/test_fused_read.py's acceptance guard).")


def _build_composed_read(sizes, backend):
    n = sizes["N"]
    q, mem, beta = _read_case(n)

    def fn(q, mem, beta):
        sims = addr.cosine_sim(
            jax.lax.stop_gradient(q),
            jax.lax.stop_gradient(mem).astype(jnp.float32))
        _, idx = jax.lax.top_k(sims, _K)
        return addr.finish_candidate_read(q, mem, beta, idx)

    return Target(fn=fn, args=(q, mem, beta), meminfo=_read_meminfo(n))


@register
def composed_read_control():
    return Contract(
        name="composed_read_control", build=_build_composed_read,
        sizes=dict(_SIZES), points=(256, 1024), quick_points=None,
        dispatches={"top_k": 0},
        backends=("ref",), expect_trip=True,
        notes="Positive control: the pre-fusion composed read stages a "
              "top_k, so the top_k==0 detector MUST fire on it.")


# ---------------------------------------------------------------------------
# bf16 storage: reads must not widen the whole buffer
# ---------------------------------------------------------------------------

def _build_bf16_read(sizes, backend):
    n = sizes["N"]
    q, mem, beta = _read_case(n, dtype=jnp.bfloat16)

    def fn(q, mem, beta):
        return ops.fused_read(q, mem, beta, _K, backend=backend)

    return Target(fn=fn, args=(q, mem, beta),
                  meminfo=_read_meminfo(n, itemsize=2))


@register
def read_bf16_no_widening():
    return Contract(
        name="read_bf16_no_widening", build=_build_bf16_read,
        sizes=dict(_SIZES), points=(256, 1024), quick_points=None,
        lints=("dtype_widening",),
        backends=("pallas-interpret",),
        notes="bf16 storage on the fused kernel: rows upcast in-VMEM, so "
              "the lowered module has no full-buffer bf16->f32 convert.")


@register
def read_bf16_ref_control():
    return Contract(
        name="read_bf16_ref_control", build=_build_bf16_read,
        sizes=dict(_SIZES), points=(256, 1024), quick_points=None,
        lints=("dtype_widening",),
        backends=("ref",), expect_trip=True,
        notes="Positive control: the ref oracle upcasts the whole buffer "
              "to f32 before its sweep (_deq_view), so the dtype-widening "
              "lint MUST fire on it.")


# ---------------------------------------------------------------------------
# Fused write (scratch-row layout) + legacy positive control
# ---------------------------------------------------------------------------

def _write_target(sizes, backend, *, scratch: bool):
    n = sizes["N"]
    j = _H * (_K + 1)
    rows = n + 1 if scratch else n
    mem = jnp.zeros((_B, rows, _W))
    last = jnp.zeros((_B, rows), jnp.int32)
    widx = (jnp.arange(j, dtype=jnp.int32)[None].repeat(_B, 0) * 3) % n
    lra = widx.reshape(_B, _H, _K + 1)[..., -1]
    ww = jnp.full((_B, j), 0.1)
    a = jnp.ones((_B, _H, _W))

    def fn(mem, last, ww, a):
        return ops.sparse_write_update(
            mem, last, widx, ww, a, lra, jnp.int32(1), delta=0.005,
            backend=backend, scratch_row=n if scratch else None)

    # The buffer is donated exactly as the serving step donates its state
    # — without donation XLA guards the in-place scatter with a defensive
    # full-buffer copy, which is real O(N·W) traffic but not this path's.
    return Target(fn=fn, args=(mem, last, ww, a), donate_argnums=(0, 1),
                  meminfo=_read_meminfo(n, buf_rows=rows))


def _build_fused_write(sizes, backend):
    return _write_target(sizes, backend, scratch=True)


def _build_legacy_write(sizes, backend):
    return _write_target(sizes, backend, scratch=False)


@register
def fused_write():
    return Contract(
        name="fused_write", build=_build_fused_write,
        sizes=dict(_SIZES),
        donate=True,
        lints=("scratch_copy",),
        backends=("ref", "pallas-interpret"),
        notes="Scratch-row layout: the write updates K rows in place — "
              "flat flops/hbm in N and no full-buffer pad/slice/gather "
              "in the lowered module (PR-2 contract, generalized).")


@register
def fused_write_legacy():
    return Contract(
        name="fused_write_legacy", build=_build_legacy_write,
        sizes=dict(_SIZES), points=(256, 1024), quick_points=None,
        lints=("scratch_copy",),
        backends=("pallas-interpret",), expect_trip=True,
        notes="Positive control: the legacy (B,N,W) layout on the pallas "
              "path pads the buffer to N+1 rows and slices it back every "
              "write — the scratch_copy lint MUST fire on it.")


# ---------------------------------------------------------------------------
# Decode step: a full sam_step in LSH (serving) mode
# ---------------------------------------------------------------------------

def _build_decode_step_sam(sizes, backend):
    n = sizes["N"]
    cfg = sam_lib.SAMConfig(_mem_cfg(n, backend, ann="lsh"), _CTL)
    params = sam_lib.init_params(jax.random.PRNGKey(0), cfg)
    state = sam_lib.init_state(_B, cfg)
    x = jnp.zeros((_B, _D))

    def fn(p, s, x):
        return sam_lib.sam_step(p, cfg, s, x)

    # State donated like the serving engine's carried state — without it
    # XLA guards the in-place memory update with a full-buffer copy.
    return Target(fn=fn, args=(params, state, x), donate_argnums=(1,),
                  meminfo=_read_meminfo(n, buf_rows=state.memory.shape[1]))


@register
def decode_step_sam():
    return Contract(
        name="decode_step_sam", build=_build_decode_step_sam,
        # All points multi-tile: the LRA kernel tiles N in 1024-row blocks,
        # and the degenerate single-tile lowering (N <= 1024) elides the
        # final top-K slice over per-tile winners, which would read as a
        # dispatch-profile drift. From 2048 up the two-stage reduction
        # shape is identical at every point.
        points=(2048, 4096, 8192), quick_points=(2048, 4096),
        sizes=dict(_SIZES),
        donate=True,
        backends=("ref", "pallas-interpret"),
        notes="The headline claim at serving shapes: one LSH-mode "
              "sam_step (read + write + index insert) is flat in N on "
              "flops and HBM (judged on ref) and keeps an N-independent "
              "dispatch profile on every backend (swept over multi-tile "
              "N only; see points).")


# ---------------------------------------------------------------------------
# LM decode step (reduced config) + donation contracts
# ---------------------------------------------------------------------------

def _lm_cfg(n: int, backend: str):
    from repro.configs import get_config, reduced
    cfg = reduced(get_config("h2o_danube_3_4b_sam"))
    return dataclasses.replace(cfg, memory=dataclasses.replace(
        cfg.memory, num_slots=n, backend=backend))


def _lm_case(n: int, backend: str, *, tokens: int = 1):
    from repro.models import lm
    cfg = _lm_cfg(n, backend)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    cache = lm.init_cache(cfg, _B, 16, per_lane_pos=True)
    mem = lm.init_memory_states(cfg, _B, per_lane_step=True)
    tok = jnp.ones((_B, tokens), jnp.int32)
    return cfg, params, cache, mem, tok


def _build_lm_decode(sizes, backend):
    from repro.models import lm
    n = sizes["N"]
    cfg, params, cache, mem, tok = _lm_case(n, backend)

    def fn(p, c, m, t):
        return lm.decode_step(p, cfg, c, t, mem_states=m)

    return Target(fn=fn, args=(params, cache, mem, tok),
                  meminfo=_read_meminfo(n, buf_rows=n + 1, word=16))


@register
def lm_decode_step():
    return Contract(
        name="lm_decode_step", build=_build_lm_decode,
        sweep="N", points=(64, 256, 1024), quick_points=(64, 256),
        flops="O(N)", hbm="O(N)",
        backends=("ref",),
        notes="Reduced-config LM decode step on the ref backend (exact "
              "read): at worst linear in N. A stray O(N^2) "
              "materialization anywhere in the decode path trips this.")


@register
def lm_decode_no_topk():
    return Contract(
        name="lm_decode_no_topk", build=_build_lm_decode,
        points=(64,), quick_points=None,
        dispatches={"top_k": 0},
        backends=("pallas-interpret",),
        notes="End-to-end serving guard: a decode step on the Pallas "
              "memory backend contains no top_k at all — every read is "
              "the fused kernel.")


@register
def lm_decode_ref_control():
    return Contract(
        name="lm_decode_ref_control", build=_build_lm_decode,
        points=(64,), quick_points=None,
        dispatches={"top_k": 0},
        backends=("ref",), expect_trip=True,
        notes="Positive control: the ref decode step stages top_k, so "
              "the top_k==0 detector MUST fire on it.")


def _build_decode_scan_donated(sizes, backend):
    from repro.models import lm
    n = sizes["N"]
    cfg, params, cache, mem, tok = _lm_case(n, backend, tokens=4)

    def fn(p, c, m, t):
        out = lm.decode_scan(p, cfg, c, t, mem_states=m)
        return out[1:]          # (new_cache, new_mem): the carried state

    return Target(fn=fn, args=(params, cache, mem, tok),
                  donate_argnums=(1, 2),
                  meminfo=_read_meminfo(n, buf_rows=n + 1, word=16))


@register
def decode_scan_donated():
    return Contract(
        name="decode_scan_donated", build=_build_decode_scan_donated,
        points=(64,), quick_points=None,
        donate=True, backends=("ref",),
        notes="Prefill scan with donated cache+memory: the aliased "
              "entry-parameter bytes must cover every donated carry — a "
              "dropped donation doubles resident serving state.")


def _build_engine_step_donated(sizes, backend):
    from repro.launch.engine.stepfn import make_engine_step
    n = sizes["N"]
    cfg, params, cache, mem, tok = _lm_case(n, backend)
    step = make_engine_step(cfg)
    greedy = jnp.ones((_B,), bool)
    seeds = jnp.zeros((_B,), jnp.int32)
    counters = jnp.zeros((_B,), jnp.int32)

    return Target(fn=step, args=(params, cache, mem, tok, greedy, seeds,
                                 counters),
                  donate_argnums=(1, 2),
                  meminfo=_read_meminfo(n, buf_rows=n + 1, word=16))


@register
def engine_step_donated():
    return Contract(
        name="engine_step_donated", build=_build_engine_step_donated,
        points=(64,), quick_points=None,
        donate=True, backends=("ref",),
        notes="The serving engine's jitted step: cache and memory states "
              "donated and actually aliased in the compiled module.")


# ---------------------------------------------------------------------------
# Chunked-unroll backward: O(T) end to end, structure flat in T
# ---------------------------------------------------------------------------

def _build_unroll_backward(sizes, backend):
    t = sizes["T"]
    cfg = sam_lib.SAMConfig(
        MemoryConfig(num_slots=32, word_size=8, num_heads=2, k=2,
                     backend=backend),
        ControllerConfig(8, 24, 6))
    cell = SAMCell(cfg)
    params = sam_lib.init_params(jax.random.PRNGKey(0), cfg)
    state0 = sam_lib.init_state(_B, cfg)
    xs = jax.random.normal(jax.random.PRNGKey(1), (t, _B, 8))

    def fn(p, s, xs):
        def loss(pp):
            _, ys = unroll_lib.unroll(cell, pp, s, xs, mode="chunked",
                                      chunk=8)
            return (ys ** 2).sum()
        return jax.grad(loss)(p)

    return Target(fn=fn, args=(params, state0, xs),
                  meminfo={"num_slots": 32, "buf_rows": 33, "word_size": 8,
                           "buffer_bytes": _B * 32 * 8 * 4})


@register
def unroll_backward_chunked():
    return Contract(
        name="unroll_backward_chunked", build=_build_unroll_backward,
        sweep="T", points=(32, 64, 128), quick_points=(32, 64),
        sizes={},
        flops="O(T)", hbm="O(T)",
        backends=("ref",),
        notes="Chunked-BPTT backward: linear in sequence length with a "
              "T-independent program structure (segments live in scan "
              "trip counts, not staged ops).")


# ---------------------------------------------------------------------------
# Sharded paths (8 forced host devices; bench_shard's small shapes)
# ---------------------------------------------------------------------------

_MB, _MW, _MH, _MK, _MD = 2, 16, 2, 4, 6
_MCTL = ControllerConfig(_MD, 16, _MD)
_MSHARDS = 8


def _mesh_cfg(n: int, *, ann: str = "exact") -> sam_lib.SAMConfig:
    kw = {}
    if ann == "lsh":
        kw = dict(ann="lsh", lsh_tables=4, lsh_bits=6, lsh_bucket_size=32)
    return sam_lib.SAMConfig(
        MemoryConfig(num_slots=n, word_size=_MW, num_heads=_MH, k=_MK, **kw),
        _MCTL)


def _mesh1d():
    return make_mesh((_MSHARDS,), ("model",))


def _mesh_meminfo(n: int, *, batch=_MB):
    return {"num_slots": n, "buf_rows": n + _MSHARDS, "word_size": _MW,
            "buffer_bytes": batch * n * _MW * 4}


def _build_mesh_step(sizes, backend, *, ann="exact"):
    n = sizes["N"]
    cfg = _mesh_cfg(n, ann=ann)
    mesh = _mesh1d()
    with mem_shard.memory_mesh(mesh, n):
        params = sam_lib.init_params(jax.random.PRNGKey(0), cfg)
        state = mem_shard.place_state(sam_lib.init_state(_MB, cfg))

    def fn(p, s, x):
        return sam_lib.sam_step(p, cfg, s, x)

    return Target(fn=fn, args=(params, state, jnp.zeros((_MB, _MD))),
                  context=lambda: mem_shard.memory_mesh(mesh, n),
                  meminfo=_mesh_meminfo(n))


@register
def mesh_step():
    return Contract(
        name="mesh_step",
        build=lambda s, b: _build_mesh_step(s, b),
        sizes={"B": _MB, "H": _MH, "W": _MW, "K": _MK},
        flops="O(B*H*N*W)", hbm="O(B*N*W)",
        lints=("full_buffer_collective",),
        devices=_MSHARDS,
        notes="Slot-sharded sam_step (exact read): shard-local compute is "
              "declared-linear (the similarity sweep), but collective "
              "bytes stay flat in N (the O(B·K·W) score all-gather + "
              "winner-row psum) with no single collective near the full "
              "buffer — the scale-out contract.")


@register
def lsh_step_sharded():
    return Contract(
        name="lsh_step_sharded",
        build=lambda s, b: _build_mesh_step(s, b, ann="lsh"),
        sizes={"B": _MB, "H": _MH, "W": _MW, "K": _MK},
        hbm="O(B*N)",
        lints=("full_buffer_collective",),
        devices=_MSHARDS,
        notes="Sharded-index LSH step (ownership-partitioned bucket "
              "tables, collective-free insert): flops flat in N, HBM "
              "bounded by the O(B·N) usage/LRU vectors (word-free — no "
              "N·W term), and collective bytes flat in N.")


def _build_gspmd_control(sizes, backend):
    from jax.sharding import NamedSharding, PartitionSpec as P
    n = sizes["N"]
    cfg = _mesh_cfg(n)
    mesh = _mesh1d()
    params = sam_lib.init_params(jax.random.PRNGKey(0), cfg)
    s = sam_lib.init_state(_MB, cfg)
    s = s._replace(memory=s.memory[:, :n], last_access=s.last_access[:, :n])
    sh = jax.tree.map(lambda l: NamedSharding(mesh, P()), s)
    sh = sh._replace(memory=NamedSharding(mesh, P(None, "model", None)),
                     last_access=NamedSharding(mesh, P(None, "model")))

    def fn(p, st, x):
        return sam_lib.sam_step(p, cfg, st, x)

    return Target(fn=fn, args=(params, jax.device_put(s, sh),
                               jnp.zeros((_MB, _MD))),
                  meminfo=_mesh_meminfo(n))


@register
def gspmd_control():
    return Contract(
        name="gspmd_control", build=_build_gspmd_control,
        sizes={"B": _MB, "W": _MW, "K": _MK},
        devices=_MSHARDS, expect_trip=True,
        notes="Positive control: the retired legacy-layout-through-GSPMD "
              "route — its dynamically-indexed sweep forces O(N) "
              "collective terms, so the flat-collective-bytes check MUST "
              "fire on it.")


def _build_lsh_insert_sharded(sizes, backend):
    n = sizes["N"]
    cfg = _mesh_cfg(n, ann="lsh")
    mesh = _mesh1d()
    with mem_shard.memory_mesh(mesh, n):
        ctx = mem_shard.current()
        state = mem_shard.place_state(sam_lib.init_state(_MB, cfg))
    planes = ann_lib.lsh_planes(jax.random.PRNGKey(0), cfg.memory)
    j = _MH * (_MK + 1)
    idx = (jnp.arange(j, dtype=jnp.int32)[None].repeat(_MB, 0) * 5) % n

    def fn(planes, ann_state, idx, memv):
        return mem_shard.ann_insert_sharded(ctx, planes, ann_state, idx,
                                            memv, cfg.memory)

    return Target(fn=fn, args=(planes, state.ann, idx, state.memory),
                  context=lambda: mem_shard.memory_mesh(mesh, n),
                  meminfo=_mesh_meminfo(n))


@register
def lsh_insert_sharded():
    return Contract(
        name="lsh_insert_sharded", build=_build_lsh_insert_sharded,
        sizes={"B": _MB, "W": _MW, "K": _MK},
        lints=("full_buffer_collective",),
        devices=_MSHARDS,
        notes="The sharded LSH insert alone: each shard hashes only the "
              "rows it owns — flat (in fact zero) collective bytes "
              "however many slots the index covers.")


def _build_ann_build_sharded(sizes, backend):
    n = sizes["N"]
    cfg = _mesh_cfg(n, ann="lsh")
    mesh = _mesh1d()
    with mem_shard.memory_mesh(mesh, n):
        planes = ann_lib.lsh_planes(jax.random.PRNGKey(0), cfg.memory)
        state = mem_shard.place_state(sam_lib.init_state(_MB, cfg))

    def fn(p, m):
        return ann_lib.ann_build(p, m, cfg.memory)

    return Target(fn=fn, args=(planes, state.memory),
                  context=lambda: mem_shard.memory_mesh(mesh, n),
                  meminfo=_mesh_meminfo(n))


@register
def ann_build_sharded():
    return Contract(
        name="ann_build_sharded", build=_build_ann_build_sharded,
        sizes={"B": _MB, "W": _MW, "K": _MK},
        flops="O(B*N*W)", hbm="O(B*N*W)",
        lints=("full_buffer_collective",),
        devices=_MSHARDS,
        notes="ann_build on a slot-sharded buffer: hashing every row is "
              "declared-linear, but the build compiles shard-local — no "
              "collective anywhere near the O(N·W) memory.")


def _build_mesh2d_step(sizes, backend):
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    n, gb = sizes["N"], sizes["B"]
    cfg = sam_lib.SAMConfig(
        MemoryConfig(num_slots=n, word_size=_MW, num_heads=_MH, k=_MK),
        _MCTL)
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:8]).reshape(2, 4), ("data", "model"))

    def ctx_factory():
        return mem_shard.memory_mesh(mesh, n, data_axes=("pod", "data"))

    with ctx_factory():
        ctx = mem_shard.current()
        params = sam_lib.init_params(jax.random.PRNGKey(0), cfg)
        state = mem_shard.place_state(sam_lib.init_state(gb, cfg))
        xspec = P("data") if ctx.data_degree > 1 else P()
        x = jax.device_put(jnp.zeros((gb, _MD)), NamedSharding(mesh, xspec))

    def fn(p, s, x):
        return sam_lib.sam_step(p, cfg, s, x)

    return Target(fn=fn, args=(params, state, x), context=ctx_factory,
                  meminfo=_mesh_meminfo(n, batch=gb))


@register
def mesh2d_step():
    return Contract(
        name="mesh2d_step", build=_build_mesh2d_step,
        sizes={"B": 2 * _MB, "H": _MH, "W": _MW, "K": _MK},
        flops="O(B*H*N*W)", hbm="O(B*N*W)",
        group_sizes=(4,),
        lints=("full_buffer_collective",),
        devices=_MSHARDS,
        notes="2D (data × model) composition on a (2,4) mesh: per-device "
              "collective bytes flat in N and every collective grouped "
              "on the model axis only (group size == model degree == 4) "
              "— zero data-axis traffic on the memory path.")
