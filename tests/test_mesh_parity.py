"""Mesh-native sparse memory parity: single device vs an 8-way slot-sharded
mesh (docs/sharding.md).

These tests need 8 devices; the tier-1 driver in tests/test_sharding_optim.py
(and the CI mesh lane) runs this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``. Covered:

  * SAM and SDNC forward, gradient, and chunked-rollback BPTT match the
    single-device reference to 1e-5 on every unroll mode (exact-read and
    LSH candidate reads, the LSH bucket tables sharded by slot ownership
    with the final index asserted bit-exactly);
  * the compiled sharded step's HLO contains no full-memory collective —
    per-step collective bytes are independent of N (the GSPMD slot-sharded
    path, the positive control, scales with N); the sharded-LSH step
    additionally compiles no full-bucket-table collective, and `ann_build`
    on a sharded buffer compiles with no O(N·W) all-gather;
  * a checkpoint saved on mesh A (8-way) restores on mesh B (4-way) and on
    a single device, bit-exact on the logical rows; the LSH index
    re-partitions with its per-bucket candidate sets preserved;
  * the streaming trainer under a mesh reproduces the single-device loss
    trajectory exactly;
  * int8 quantized memory (mem_dtype="int8") on the mesh: sharded parity
    with bit-exact stored rows, and a mesh session spilled through the
    serving SessionStore restores bit-identically (docs/memory-model.md).
"""
import functools
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import dnc as dnc_lib
from repro.core import sam as sam_lib
from repro.core import unroll as unroll_lib
from repro.core.cell import SAMCell, SDNCCell
from repro.core.types import ControllerConfig, MemoryConfig
from repro.distributed import mem_shard
from repro.launch.mesh import make_mesh

# The HLO collective guard reuses the bench helpers (single source for the
# O(K-not-N) guard — benchmarks/bench_shard.py); `python -m pytest` puts
# the repo root on sys.path, a bare `pytest` may not.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(run via the driver in tests/test_sharding_optim.py)")

N, W, H, K, B, T, D = 64, 8, 2, 2, 2, 6, 6
CTL = ControllerConfig(D, 16, D)
TOL = 1e-5


def _mesh8():
    return make_mesh((8,), ("model",))


def _mesh24():
    return make_mesh((2, 4), ("data", "model"))


@functools.lru_cache(maxsize=None)
def _cell(kind: str):
    mem = MemoryConfig(num_slots=N, word_size=W, num_heads=H, k=K,
                       ann="lsh" if kind.endswith("_lsh") else "exact",
                       mem_dtype="int8" if "int8" in kind else "float32",
                       lsh_tables=2, lsh_bits=3, lsh_bucket_size=8)
    if kind.startswith("sdnc"):
        return SDNCCell(dnc_lib.DNCConfig(mem, CTL, k_l=4, sparse=True))
    return SAMCell(sam_lib.SAMConfig(mem, CTL))


def _init_state(cell, kind: str):
    """Single-device state with the mesh run's *index semantics*: the LSH
    index's ownership partitioning (P=8 sub-rings per bucket) determines
    candidate sets, so the reference must carry the same partitioning —
    unsharded — for parity to be meaningful. The memory layout itself is
    pure placement and stays canonical here."""
    if kind.endswith("_lsh"):
        return cell.init_state(B, ann_partitions=8)
    return cell.init_state(B)


def _xs():
    return jax.random.normal(jax.random.PRNGKey(1), (T, B, D))


def _loss(cell, params, state, mode, chunk):
    st, ys = unroll_lib.unroll(cell, params, state, _xs(), mode=mode,
                               chunk=chunk)
    return (ys ** 2).sum(), (st, ys)


@functools.lru_cache(maxsize=None)
def _reference(kind: str, mode: str, chunk):
    """Single-device forward + grad (computed outside any mesh context)."""
    cell = _cell(kind)
    params = cell.init_params(jax.random.PRNGKey(0))
    (_, (st, ys)), g = jax.value_and_grad(_loss, argnums=1, has_aux=True)(
        cell, params, _init_state(cell, kind), mode, chunk)
    return params, st, ys, g


def _assert_state_matches(canon, ref):
    """Compare a mesh-run final state (converted back to the canonical
    layout) against the single-device reference: logical slot rows exactly
    where sharding cannot perturb them, 1e-5 elsewhere. Scratch rows are
    excluded — their contents are meaningless by contract."""
    for got, want in zip(jax.tree.leaves(canon), jax.tree.leaves(ref)):
        g, w = np.asarray(got), np.asarray(want)
        if g.ndim >= 2 and g.shape[1] == N + 1:
            g, w = g[:, :N], w[:, :N]
        if np.issubdtype(g.dtype, np.integer):
            np.testing.assert_array_equal(g, w)
        else:
            np.testing.assert_allclose(g, w, atol=TOL, rtol=0)


MODES = [("naive", None), ("sparse", None), ("chunked", 3)]


@pytest.mark.parametrize("kind", ["sam", "sdnc", "sam_lsh", "sdnc_lsh",
                                  "sam_int8", "sam_int8_lsh"])
@pytest.mark.parametrize("mode,chunk", MODES, ids=[m for m, _ in MODES])
def test_forward_grad_bptt_parity(kind, mode, chunk):
    """SAM and SDNC, exact and LSH reads: the mesh run (memory slot-sharded,
    LSH bucket tables sharded by slot ownership) matches the single-device
    reference at 1e-5 on outputs, final state, and gradients — the LSH
    kinds additionally assert the final ANN index (buckets *and* cursors)
    bit-exactly, which pins the collective-free sharded insert to the
    canonical partitioned insert. The int8 kinds run the quantized storage
    path on the mesh: the int8 memory leaf is integer, so the state
    comparison is *bit-exact* on the stored rows (and the f32 mem_scale
    column shards/compares alongside them)."""
    cell = _cell(kind)
    params, ref_st, ref_ys, ref_g = _reference(kind, mode, chunk)
    with mem_shard.memory_mesh(_mesh8(), N):
        state = mem_shard.place_state(_init_state(cell, kind))
        assert state.memory.shape[1] == N + 8          # sharded layout
        if kind.endswith("_lsh"):
            assert state.ann.buckets.shape[-2] == 8    # sharded index
            assert state.ann.buckets.addressable_shards[0].data.nbytes \
                == state.ann.buckets.nbytes // 8       # 1/S per device
        f = jax.jit(functools.partial(
            jax.value_and_grad(_loss, argnums=1, has_aux=True),
            cell, mode=mode, chunk=chunk))
        (_, (st, ys)), g = f(params, state)
        canon = mem_shard.from_shard_state(st)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(ref_ys),
                               atol=TOL, rtol=0)
    _assert_state_matches(canon, ref_st)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(ref_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=TOL, rtol=0)


# --------------------------------------------------------------------------
# HLO guard: collective traffic O(K), never the full memory buffer
# --------------------------------------------------------------------------

def test_step_hlo_collectives_scale_with_k_not_n():
    """Single source for the guard: the compile helpers live in
    benchmarks/bench_shard.py and the verdict machinery in repro.analysis
    (the `full_buffer_collective` lint, recorded per compile, and the
    shared growth fit) — the same checks the `mesh_step`/`gspmd_control`
    contracts sweep."""
    from benchmarks import bench_shard
    mesh = _mesh8()
    ns = [256, 1024]
    mesh_recs = [bench_shard.compile_mesh_step(mesh, n) for n in ns]
    ctrl_recs = [bench_shard.compile_gspmd_control(mesh, n) for n in ns]
    # No collective anywhere near the full (B, N, W) memory buffer.
    for rec in mesh_recs:
        assert rec["full_buffer_offenses"] == [], rec["full_buffer_offenses"]
    # Mesh-native traffic is independent of N (pure K/H/W terms)...
    fit = bench_shard._flat_in("N", ns,
                               [r["bytes_total"] for r in mesh_recs])
    assert fit.ok, f"mesh collective bytes grew ~N^{fit.exponent:.2f}"
    # ...while the GSPMD control grows with N (positive control: the guard
    # would catch a regression that silently reintroduces dense traffic).
    ctrl_fit = bench_shard._flat_in("N", ns,
                                    [r["bytes_total"] for r in ctrl_recs])
    assert not ctrl_fit.ok, "positive control stayed flat — guard is dead"
    assert mesh_recs[-1]["bytes_total"] < ctrl_recs[-1]["bytes_total"] / 4


def test_lsh_step_hlo_no_bucket_table_collective():
    """Sharded-LSH step guard: no collective anywhere near the full bucket
    table (or the memory buffer) — the lint runs against the tighter of
    the two inside the compile helper — traffic flat in N, and strictly
    below the replicated-index positive control (whose read psum-gathers
    the full O(C·W) candidate rows); per-device bucket-table bytes drop
    by exactly the shard factor."""
    from benchmarks import bench_shard
    mesh = _mesh8()
    small = bench_shard.compile_mesh_step_lsh(mesh, 256)
    big = bench_shard.compile_mesh_step_lsh(mesh, 1024)
    repl = bench_shard.compile_mesh_step_lsh(mesh, 1024, index_partitions=1)
    assert big["full_buffer_offenses"] == [], big["full_buffer_offenses"]
    fit = bench_shard._flat_in("N", [256, 1024],
                               [small["bytes_total"], big["bytes_total"]])
    assert fit.ok, f"sharded-LSH bytes grew ~N^{fit.exponent:.2f}"
    assert big["bytes_total"] < repl["bytes_total"] / 2
    assert repl["bucket_table_bytes_per_device"] \
        == big["bucket_table_bytes_per_device"] * 8


def test_ann_build_sharded_compiles_without_canonical_allgather():
    """`ann_build` on a slot-sharded buffer rebuilds shard-local: the
    compiled HLO moves no collective anywhere near the O(N·W) memory (the
    pre-shard rebuild all-gathered the whole buffer back to canonical
    form) — the `full_buffer_collective` lint verdict recorded by the
    compile helper."""
    from benchmarks import bench_shard
    rec = bench_shard.compile_lsh_build(_mesh8(), 1024)
    assert rec["full_buffer_offenses"] == [], rec["full_buffer_offenses"]


# --------------------------------------------------------------------------
# Checkpoint: save on mesh A, restore on mesh B / single device
# --------------------------------------------------------------------------

def test_checkpoint_cross_mesh_roundtrip(tmp_path):
    from repro.checkpoint import ckpt as ckpt_lib
    cfg = sam_lib.SAMConfig(
        MemoryConfig(num_slots=N, word_size=W, num_heads=H, k=K), CTL)
    logical = jnp.arange(B * N * W, dtype=jnp.float32).reshape(B, N, W)
    with mem_shard.memory_mesh(_mesh8(), N):
        s8 = sam_lib.init_state(B, cfg)
        s8 = s8._replace(memory=mem_shard.to_shard_layout(logical, N, 8))
        ckpt_lib.save_checkpoint(str(tmp_path), 1, {"carry": s8},
                                 mem_layout=mem_shard.ckpt_layout())
    # Restore onto a 4-way model mesh: rows re-layout 64+8 -> 64+4.
    with mem_shard.memory_mesh(_mesh24(), N):
        tmpl = {"carry": sam_lib.init_state(B, cfg)}
        restored, _ = ckpt_lib.restore_checkpoint(str(tmp_path), tmpl)
        assert restored["carry"].memory.shape[1] == N + 4
        canon4 = mem_shard.from_shard_state(restored["carry"])
    np.testing.assert_array_equal(np.asarray(canon4.memory[:, :N]),
                                  np.asarray(logical))
    # Restore onto a single device (canonical layout).
    tmpl1 = {"carry": sam_lib.init_state(B, cfg)}
    r1, _ = ckpt_lib.restore_checkpoint(str(tmp_path), tmpl1)
    assert r1["carry"].memory.shape[1] == N + 1
    np.testing.assert_array_equal(np.asarray(r1["carry"].memory[:, :N]),
                                  np.asarray(logical))


def test_checkpoint_layout_autorecorded_under_context(tmp_path):
    """A save made under the memory_mesh context records mem_layout even
    when the caller does not pass it (AsyncCheckpointer/fault-tolerance
    path), so the canonical restore still round-trips; a sharded state
    saved *outside* any context has no recorded layout and the shape
    mismatch stays a loud config error."""
    from repro.checkpoint import ckpt as ckpt_lib
    cfg = sam_lib.SAMConfig(
        MemoryConfig(num_slots=N, word_size=W, num_heads=H, k=K), CTL)
    with mem_shard.memory_mesh(_mesh8(), N):
        s8 = sam_lib.init_state(B, cfg)
        ckpt_lib.save_checkpoint(str(tmp_path / "a"), 1, {"carry": s8})
    tmpl = {"carry": sam_lib.init_state(B, cfg)}                   # canonical
    restored, _ = ckpt_lib.restore_checkpoint(str(tmp_path / "a"), tmpl)
    assert restored["carry"].memory.shape[1] == N + 1
    ckpt_lib.save_checkpoint(str(tmp_path / "b"), 1, {"carry": s8})
    with pytest.raises(ValueError, match="mem_layout"):
        ckpt_lib.restore_checkpoint(str(tmp_path / "b"), tmpl)


def test_pre_mesh_checkpoint_upgrades_with_declared_slots(tmp_path):
    """A checkpoint saved before mesh support (canonical layout, no
    recorded mem_layout) restores onto a mesh template when the caller
    declares num_slots — rows == N+1 pins the layout unambiguously. With
    no declaration the mismatch stays a loud error."""
    from repro.checkpoint import ckpt as ckpt_lib
    cfg = sam_lib.SAMConfig(
        MemoryConfig(num_slots=N, word_size=W, num_heads=H, k=K), CTL)
    s1 = sam_lib.init_state(B, cfg)                    # canonical, no ctx
    logical = jnp.arange(B * N * W, dtype=jnp.float32).reshape(B, N, W)
    s1 = s1._replace(memory=s1.memory.at[:, :N].set(logical))
    ckpt_lib.save_checkpoint(str(tmp_path), 1, {"carry": s1})
    with mem_shard.memory_mesh(_mesh8(), N):
        tmpl = {"carry": sam_lib.init_state(B, cfg)}   # sharded template
        with pytest.raises(ValueError, match="mem_layout"):
            ckpt_lib.restore_checkpoint(str(tmp_path), tmpl)
        restored, _ = ckpt_lib.restore_checkpoint(str(tmp_path), tmpl,
                                                  expect_num_slots=N)
        assert restored["carry"].memory.shape[1] == N + 8
        canon = mem_shard.from_shard_state(restored["carry"])
    np.testing.assert_array_equal(np.asarray(canon.memory[:, :N]),
                                  np.asarray(logical))


def _bucket_entry_sets(ann):
    """Multiset of valid entries per (batch, table, bucket), partition-
    agnostic — the candidate sets queries see."""
    b = np.asarray(ann.buckets)
    B_, T_, nb = b.shape[:3]
    return [[sorted(int(e) for e in b[i, t, k].ravel() if e >= 0)
             for k in range(nb)] for i in range(B_) for t in range(T_)]


def test_checkpoint_ann_index_relayout(tmp_path):
    """Bucket contents are layout-local ring placements, so a cross-mesh
    restore re-partitions the (buckets, cursor) pair together: save the
    LSH index populated on the 8-way mesh, restore onto a 4-way mesh and
    a single device — the per-bucket candidate sets are preserved exactly
    (total per-bucket capacity is partition-invariant), and the restored
    index keeps working (cursors consistent)."""
    from repro.checkpoint import ckpt as ckpt_lib
    cell = _cell("sam_lsh")
    cfg = cell.cfg
    params = cell.init_params(jax.random.PRNGKey(0))
    with mem_shard.memory_mesh(_mesh8(), N):
        state = mem_shard.place_state(cell.init_state(B, ann_partitions=8))
        step = jax.jit(functools.partial(sam_lib.sam_step, params, cfg))
        for x in _xs():                        # populate the index
            state, _ = step(state, x)
        saved_sets = _bucket_entry_sets(state.ann)
        ckpt_lib.save_checkpoint(str(tmp_path), 1, {"carry": state})
    # 4-way restore: buckets (B, T, nb, 8, 1) -> (B, T, nb, 4, 2).
    with mem_shard.memory_mesh(_mesh24(), N):
        tmpl = {"carry": cell.init_state(B)}
        restored, _ = ckpt_lib.restore_checkpoint(str(tmp_path), tmpl)
        ann4 = restored["carry"].ann
        assert ann4.buckets.shape[-2:] == (4, 2)
        assert _bucket_entry_sets(ann4) == saved_sets
        # Ownership rule holds after the remap: every entry sits in the
        # sub-ring of its owner.
        b4 = np.asarray(ann4.buckets)
        part = np.arange(4)[None, None, None, :, None]
        assert bool(((b4 < 0) | (b4 // (N // 4) == part)).all())
    # Single-device restore (canonical P=1 full-depth rings).
    tmpl1 = {"carry": cell.init_state(B)}
    r1, _ = ckpt_lib.restore_checkpoint(str(tmp_path), tmpl1)
    ann1 = r1["carry"].ann
    assert ann1.buckets.shape[-2:] == (1, 8)
    assert _bucket_entry_sets(ann1) == saved_sets
    # The restored single-device state keeps stepping (cursor consistent).
    s1 = r1["carry"]
    s1, _ = sam_lib.sam_step(params, cfg, s1, _xs()[0])
    assert bool(jnp.isfinite(s1.read.words).all())


# --------------------------------------------------------------------------
# Serving sessions: int8 memory evicts/restores bit-exactly off a mesh
# --------------------------------------------------------------------------

def test_session_store_int8_mesh_roundtrip(tmp_path):
    """A mesh-sharded int8 session spilled through the SessionStore (which
    canonicalizes to shards=1 on `put`) restores bit-identically to the
    canonical form of the live state: the int8 row bits, the f32 mem_scale
    column, and the usage table move through relayout/spill/restore with
    no de/re-quantization anywhere."""
    from repro.launch.engine.sessions import SessionStore
    cell = _cell("sam_int8")
    params = cell.init_params(jax.random.PRNGKey(0))
    with mem_shard.memory_mesh(_mesh8(), N):
        state = mem_shard.place_state(_init_state(cell, "sam_int8"))
        step = jax.jit(functools.partial(sam_lib.sam_step, params, cell.cfg))
        for x in _xs():
            state, _ = step(state, x)
        assert state.memory.dtype == jnp.int8
        canon = mem_shard.from_shard_state(state)
        store = SessionStore(num_slots=N, capacity=1,
                             spill_dir=str(tmp_path))
        store.put("u", state._asdict())
        store.put("v", {"x": np.zeros(2)})     # force "u" onto disk
        assert store.spills == 1
        back = store.take("u")
    for got, want in zip(jax.tree.leaves(back),
                         jax.tree.leaves(canon._asdict())):
        g, w = np.asarray(got), np.asarray(want)
        if g.ndim >= 2 and g.shape[1] == N + 1:
            g, w = g[:, :N], w[:, :N]
        np.testing.assert_array_equal(g, w)


# --------------------------------------------------------------------------
# Streaming trainer under a mesh
# --------------------------------------------------------------------------

def test_streaming_trainer_mesh_matches_single_device():
    from repro.core.training import ModelSpec, train_task_streaming
    spec = ModelSpec("sam",
                     MemoryConfig(num_slots=N, word_size=W, num_heads=1, k=2),
                     ControllerConfig(10, 16, 8), bptt_chunk=4)
    kw = dict(episodes=1, chunk=8, batch=2, level=2, max_level=4, bits=8,
              seed=0, stop_after_chunks=2)
    _, h_single = train_task_streaming(spec, "copy", **kw)
    _, h_mesh = train_task_streaming(spec, "copy", mesh=_mesh8(), **kw)
    assert len(h_single) == len(h_mesh) == 2
    for a, b in zip(h_single, h_mesh):
        assert abs(a["loss"] - b["loss"]) < TOL, (a, b)
        assert abs(a["err"] - b["err"]) < TOL, (a, b)
