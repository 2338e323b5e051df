"""Sharding rules resolution, optimizers, ANN index, SAM memory layer,
mem-shard layout plumbing, and the forced-8-device mesh parity driver."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import ann as ann_lib
from repro.core.types import LA_SCRATCH, MemoryConfig
from repro.distributed import mem_shard
from repro.distributed.sharding import logical_spec, mesh_rules, shard
from repro.launch.mesh import make_mesh
from repro.optim import optimizers as opt


def test_logical_spec_resolution():
    mesh = make_mesh((1,), ("data",))
    spec = logical_spec(("batch", "seq"), (8, 128), mesh)
    assert spec == P(("data",), None) or spec == P("data", None)


def test_logical_spec_drops_nondividing_axes():
    mesh = make_mesh((1,), ("model",))
    # vocab 7 not divisible by ... 1 divides everything; use size-1 mesh but
    # simulate with a fake: divisibility logic is in _resolve.
    from repro.distributed.sharding import _resolve
    class FakeMesh:
        axis_names = ("model",)
        shape = {"model": 16}
    assert _resolve("heads", FakeMesh(), 8) is None or True
    # 8 heads on 16-way model axis: cannot divide -> dropped
    assert _resolve("heads", FakeMesh(), 8) is None
    assert _resolve("heads", FakeMesh(), 32) == "model"


def test_shard_noop_without_mesh():
    x = jnp.ones((4, 4))
    assert shard(x, "batch", "embed") is x


def test_adamw_decreases_quadratic():
    params = {"w": jnp.array([5.0, -3.0])}
    state = opt.adamw_init(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, state = opt.adamw_update(params, grads, state, lr=0.05,
                                         weight_decay=0.0)
    assert float(jnp.abs(params["w"]).max()) < 0.5


def test_rmsprop_decreases_quadratic():
    params = {"w": jnp.array([5.0, -3.0])}
    state = opt.rmsprop_init(params)
    for _ in range(300):
        grads = {"w": 2 * params["w"]}
        params, state = opt.rmsprop_update(params, grads, state, lr=0.02)
    assert float(jnp.abs(params["w"]).max()) < 0.5


def test_clip_by_global_norm():
    g = {"a": jnp.ones((3,)) * 10}
    clipped, norm = opt.clip_by_global_norm(g, 1.0)
    total = jnp.sqrt(sum(jnp.sum(x ** 2) for x in jax.tree.leaves(clipped)))
    np.testing.assert_allclose(float(total), 1.0, rtol=1e-5)


def test_cosine_schedule_shape():
    lr0 = opt.cosine_schedule(jnp.int32(0), base_lr=1.0, warmup=10, total=100)
    lr_mid = opt.cosine_schedule(jnp.int32(10), base_lr=1.0, warmup=10,
                                 total=100)
    lr_end = opt.cosine_schedule(jnp.int32(100), base_lr=1.0, warmup=10,
                                 total=100)
    assert float(lr0) == 0.0
    assert float(lr_mid) == pytest.approx(1.0)
    assert float(lr_end) == pytest.approx(0.0, abs=1e-6)


# --------------------------- mem_slots rule gate ---------------------------

class _FakeModelMesh:
    axis_names = ("model",)
    shape = {"model": 16}


def test_mem_slots_replicates_without_mesh_native_ctx():
    """The old rule handed a scratch-row buffer's slot dim to GSPMD; now
    mem_slots resolves to replication (with a one-time warning) unless the
    mesh-native path is active — for any dim size, divisible or not."""
    import repro.distributed.sharding as sh_mod
    sh_mod._MEM_SLOTS_WARNED = False
    with pytest.warns(UserWarning, match="mem_slots"):
        # 1025 = N+1 scratch-row buffer: indivisible by 16.
        spec = logical_spec(("batch", "mem_slots", "mem_word"),
                            (8, 1025, 32), _FakeModelMesh())
    assert spec[1] is None
    # Divisible dim: still replicated (GSPMD sharding of the slot dim is
    # what reintroduced the full-memory all-gather).
    spec = logical_spec(("batch", "mem_slots", "mem_word"),
                        (8, 1024, 32), _FakeModelMesh())
    assert spec[1] is None


def test_mem_slots_shards_under_memory_mesh():
    with mem_shard.memory_mesh(_FakeModelMesh(), 1024):
        spec = logical_spec(("batch", "mem_slots", "mem_word"),
                            (8, 1024 + 16, 32), _FakeModelMesh())
        assert spec[1] == "model"
        # A non-matching dim (canonical buffer) still replicates.
        spec = logical_spec(("batch", "mem_slots", "mem_word"),
                            (8, 1025, 32), _FakeModelMesh())
        assert spec[1] is None


# ------------------------ mem-shard layout round-trip ------------------------

def test_shard_layout_roundtrip():
    N, S = 12, 4
    mem = jnp.arange(2 * (N + 1) * 3, dtype=jnp.float32).reshape(2, N + 1, 3)
    la = jnp.arange(2 * (N + 1), dtype=jnp.int32).reshape(2, N + 1)
    smem = mem_shard.to_shard_layout(mem, N, S)
    sla = mem_shard.to_shard_layout(la, N, S)
    assert smem.shape == (2, N + S, 3) and sla.shape == (2, N + S)
    # Per-shard scratch rows carry the init fill.
    blocks = sla.reshape(2, S, N // S + 1)
    assert bool((blocks[:, :, -1] == LA_SCRATCH).all())
    back = mem_shard.from_shard_layout(smem, N, S)
    np.testing.assert_array_equal(np.asarray(back[:, :N]),
                                  np.asarray(mem[:, :N]))
    # Canonical scratch row is re-initialized, not preserved.
    assert float(jnp.abs(back[:, N]).sum()) == 0.0


def test_relayout_state_infers_current_shards():
    from repro.distributed.elastic import relayout_memory_state
    N = 12
    mem = jnp.arange(2 * (N + 1) * 3, dtype=jnp.float32).reshape(2, N + 1, 3)
    tree = {"memory": mem_shard.to_shard_layout(mem, N, 4),
            "ctrl": jnp.ones((2, 5))}
    out = relayout_memory_state(tree, N, 2)
    assert out["memory"].shape == (2, N + 2, 3)
    assert out["ctrl"].shape == (2, 5)                 # untouched
    np.testing.assert_array_equal(
        np.asarray(mem_shard.from_shard_layout(out["memory"], N, 2)[:, :N]),
        np.asarray(mem[:, :N]))


def test_relayout_state_repartitions_ann_index():
    """An elastic scale event must carry the LSH index to the new shard
    count (else every later step silently falls back to the replicated-
    index read): relayout_memory_state re-partitions sibling
    (buckets, cursor) pairs, preserving the per-bucket entry sets when
    capacity allows, and warns + passes through when it does not."""
    from repro.distributed.elastic import relayout_memory_state
    N = 16
    cfg = MemoryConfig(num_slots=N, word_size=8, ann="lsh", lsh_tables=2,
                       lsh_bits=3, lsh_bucket_size=8)
    planes = ann_lib.lsh_planes(jax.random.PRNGKey(0), cfg)
    mem = jax.random.normal(jax.random.PRNGKey(1), (2, N, 8))
    ann8 = ann_lib.ann_build(planes, mem, cfg, partitions=8)
    tree = {"memory": mem_shard.to_shard_layout(
                jnp.zeros((2, N + 1, 3)), N, 8),
            "ann": {"buckets": ann8.buckets, "cursor": ann8.cursor}}
    out = relayout_memory_state(tree, N, 2)
    assert out["memory"].shape == (2, N + 2, 3)
    assert out["ann"]["buckets"].shape[-2:] == (2, 4)
    # Capacity per owner grew (8 sub-rings of 1 -> 2 of 4): sets preserved.
    def sets(b):
        b = np.asarray(b)
        return [sorted(int(e) for e in b[i, t, k].ravel() if e >= 0)
                for i in range(2) for t in range(2) for k in range(8)]
    assert sets(out["ann"]["buckets"]) == sets(ann8.buckets)
    # Indivisible target: warn, leave the pair untouched.
    with pytest.warns(UserWarning, match="re-partition"):
        out3 = relayout_memory_state(
            {"ann": {"buckets": ann8.buckets, "cursor": ann8.cursor}}, N, 3)
    assert out3["ann"]["buckets"].shape == ann8.buckets.shape


def test_np_relayout_rejects_bad_shards():
    arr = np.zeros((2, 13, 3), np.float32)
    with pytest.raises(ValueError):
        mem_shard.np_relayout(arr, 12, 1, 5)           # 5 does not divide 12


def test_layout_transforms_match_by_name_not_shape():
    """Slot-leaf detection keys on field name + dim position: a controller
    leaf whose width coincides with a valid layout row count must pass
    through untouched."""
    with mem_shard.memory_mesh(_FakeModelMesh(), 64):     # 16 shards
        tree = {"memory": jnp.zeros((2, 65, 4)), "ctrl": jnp.zeros((2, 65))}
        out = mem_shard.to_shard_state(tree)
        assert out["memory"].shape == (2, 64 + 16, 4)
        assert out["ctrl"].shape == (2, 65)               # not a slot leaf
    from repro.distributed.elastic import relayout_memory_state
    tree = {"memory": mem_shard.to_shard_layout(jnp.zeros((2, 65, 3)), 64, 8),
            "ctrl": jnp.zeros((2, 72))}                   # 72 = 64 + 8: decoy
    out = relayout_memory_state(tree, 64, 2)
    assert out["memory"].shape == (2, 66, 3)
    assert out["ctrl"].shape == (2, 72)                   # untouched


def test_leaf_spec_targets_slot_rows_dim():
    """The sharding spec lands on the slot-rows axis resolved from the
    field name, even when another dim (segment count, batch) coincides
    with the sharded row count."""
    ctx = mem_shard.MemShardCtx(mesh=None, axis="model", num_slots=64,
                                shards=8)                 # sharded_rows=72
    # Stacked boundary checkpoint with 72 segments: rows dim is ndim-2.
    assert mem_shard.leaf_spec(ctx, 2, (72, 2, 72, 8)) \
        == P(None, None, "model", None)
    # Non-slot leaves replicate no matter their shape.
    assert mem_shard.leaf_spec(ctx, None, (72, 2, 72, 8)) == P()


def test_ckpt_restore_pins_expected_num_slots(tmp_path):
    """N: 64 -> 65 makes the canonical template rows (66) parse as a valid
    re-layout of the recorded layout (64 + 2 shards); expect_num_slots is
    the guard that keeps a config change from masquerading as one."""
    from repro.checkpoint import ckpt as ckpt_lib
    tree = {"memory": np.zeros((2, 72, 3), np.float32)}   # 64 + 8 shards
    ckpt_lib.save_checkpoint(str(tmp_path), 1, tree, mem_layout=(64, 8))
    tmpl = {"memory": jnp.zeros((2, 66, 3))}              # N=65 canonical
    with pytest.raises(ValueError, match="config change"):
        ckpt_lib.restore_checkpoint(str(tmp_path), tmpl, expect_num_slots=65)


# ----------------------------- elastic rescale -----------------------------

def test_rescale_batch_keeps_per_device_batch():
    from repro.distributed.elastic import rescale_batch
    assert rescale_batch(32, 4, 8) == 64
    assert rescale_batch(32, 8, 2) == 8


def test_rescale_batch_rejects_nondividing_layout():
    """A global batch that never divided the old data degree must raise:
    the old floor-division fallback silently changed the per-device batch,
    desyncing the streaming trainer's chunk cursor on a scale event."""
    from repro.distributed.elastic import rescale_batch
    with pytest.raises(ValueError, match="chunk cursor"):
        rescale_batch(30, 4, 8)
    with pytest.raises(ValueError, match="chunk cursor"):
        rescale_batch(2, 4, 8)                         # old degree > batch
    with pytest.raises(ValueError):
        rescale_batch(8, 0, 4)


# ------------------- forced-8-device mesh parity (driver) -------------------

@pytest.mark.skipif(jax.device_count() >= 8,
                    reason="8 devices visible: tests/test_mesh_parity.py "
                           "runs natively in this session")
@pytest.mark.skipif(bool(os.environ.get("REPRO_SKIP_MESH_DRIVER")),
                    reason="a dedicated forced-8-device mesh lane runs "
                           "tests/test_mesh_parity.py (CI)")
def test_mesh_parity_suite_on_forced_host_mesh():
    """Tier-1 acceptance driver: run the single-device vs 8-way mesh parity
    suite (tests/test_mesh_parity.py) in a subprocess with a forced
    8-device host platform — forward, grad, and chunked-rollback BPTT for
    SAM and SDNC at 1e-5, the no-full-memory-collective HLO guard, and the
    cross-mesh checkpoint round-trip."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q",
         os.path.join(os.path.dirname(__file__), "test_mesh_parity.py")],
        env=env, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, \
        f"mesh parity suite failed:\n{proc.stdout[-4000:]}\n{proc.stderr[-2000:]}"


# ------------------------------- ANN index -------------------------------

def test_ann_insert_query_recall(rng_key):
    cfg = MemoryConfig(num_slots=128, word_size=16, lsh_tables=8, lsh_bits=4,
                       lsh_bucket_size=16, ann="lsh")
    planes = ann_lib.lsh_planes(rng_key, cfg)
    mem = jax.random.normal(jax.random.PRNGKey(1), (1, 128, 16))
    state = ann_lib.ann_build(planes, mem, cfg)
    # querying with an exact stored row must return its index as candidate
    hits = 0
    for i in range(0, 128, 8):
        q = mem[:, i][:, None, :]                      # (1,1,W)
        cands = ann_lib.ann_query(planes, state, q, cfg)
        hits += int(i in np.asarray(cands[0, 0]).tolist())
    assert hits >= 14, f"recall too low: {hits}/16"


def test_ann_build_chunked_matches_sequential(rng_key):
    """The vectorized (batched-insert) rebuild is exactly equivalent to
    N sequential single-slot inserts, including when the chunk size does
    not divide N (the remainder call)."""
    cfg = MemoryConfig(num_slots=10, word_size=8, lsh_tables=2, lsh_bits=3,
                       lsh_bucket_size=4, ann="lsh")
    planes = ann_lib.lsh_planes(rng_key, cfg)
    mem = jax.random.normal(jax.random.PRNGKey(2), (2, 10, 8))
    ref = ann_lib.ann_build(planes, mem, cfg, chunk=1)    # sequential
    # 3 → remainder call; 10 > bucket_size → clamped to 4 (exactness
    # precondition), still equivalent.
    for chunk in (3, 4, 10, None):
        got = ann_lib.ann_build(planes, mem, cfg, chunk=chunk)
        assert np.array_equal(np.asarray(ref.buckets), np.asarray(got.buckets))
        assert np.array_equal(np.asarray(ref.cursor), np.asarray(got.cursor))


def test_ann_insert_updates_bucket(rng_key):
    cfg = MemoryConfig(num_slots=8, word_size=8, lsh_tables=2, lsh_bits=3,
                       lsh_bucket_size=4, ann="lsh")
    planes = ann_lib.lsh_planes(rng_key, cfg)
    state = ann_lib.ann_init(1, cfg)
    row = jax.random.normal(rng_key, (1, 1, 8))
    state = ann_lib.ann_insert(planes, state, jnp.array([[5]], jnp.int32),
                               row, cfg)
    cands = ann_lib.ann_query(planes, state, row, cfg)
    assert 5 in np.asarray(cands[0, 0]).tolist()


# ---------------------------- SAM memory layer ----------------------------

def test_memory_layer_reads_what_it_wrote(rng_key):
    from repro.configs import get_config, reduced
    from repro.models import sam_layer
    cfg = reduced(get_config("starcoder2_7b_sam"))
    p = jax.tree.map(
        lambda d: d.initialize(rng_key, jnp.float32),
        sam_layer.memory_defs(cfg),
        is_leaf=lambda x: hasattr(x, "initialize"))
    state = sam_layer.init_memory_state(cfg, 2)
    x = jax.random.normal(rng_key, (2, 64, cfg.d_model))
    y, state2 = sam_layer.memory_layer_seq(p, cfg, x, state, segment=32)
    assert y.shape == x.shape
    assert bool(jnp.isfinite(y).all())
    assert int(state2.step) == 2                      # two segments
    # memory was written
    assert float(jnp.abs(state2.memory).sum()) > 0.0
