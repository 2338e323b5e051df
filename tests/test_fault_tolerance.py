"""Checkpoint/restart, transient-failure retry, straggler detection, and
elastic re-sharding."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (latest_step, restore_checkpoint,
                              save_checkpoint)
from repro.distributed.elastic import rescale_batch, reshard_tree
from repro.launch.mesh import make_mesh
from repro.distributed.fault_tolerance import (ResilientLoop, StragglerPolicy,
                                               TransientError)


def _tree():
    return {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.zeros((3,)),
            "nested": {"c": jnp.ones((4,), jnp.int32)}}


def test_checkpoint_roundtrip(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 7, t)
    assert latest_step(str(tmp_path)) == 7
    restored, step = restore_checkpoint(str(tmp_path), t)
    assert step == 7
    jax.tree.map(lambda a, b: np.testing.assert_allclose(np.asarray(a),
                                                         np.asarray(b)),
                 t, restored)


def test_checkpoint_atomicity(tmp_path):
    """A stale tmp_ dir (simulated crash mid-write) is never restored."""
    t = _tree()
    save_checkpoint(str(tmp_path), 1, t)
    os.makedirs(tmp_path / "tmp_2")          # crashed partial write
    (tmp_path / "tmp_2" / "leaf_0.npy").write_bytes(b"garbage")
    assert latest_step(str(tmp_path)) == 1


def test_resilient_loop_retries_transient(tmp_path):
    calls = {"n": 0, "failures": 0}

    def flaky_hook(step):
        if step == 3 and calls["failures"] < 2:
            calls["failures"] += 1
            raise TransientError("simulated preemption")

    def step_fn(state, batch):
        calls["n"] += 1
        return state + 1, {"loss": float(state)}

    loop = ResilientLoop(step_fn, str(tmp_path), ckpt_every=2,
                         failure_hook=flaky_hook)
    batches = iter(lambda: 0, 1)
    state, log = loop.run(jnp.zeros(()), batches, 0, 6)
    assert int(state) == 6
    assert calls["failures"] == 2            # retried through both failures


def test_resilient_loop_resume(tmp_path):
    def step_fn(state, batch):
        return state + 1, {}

    loop = ResilientLoop(step_fn, str(tmp_path), ckpt_every=2)
    batches = iter(lambda: 0, 1)
    state, _ = loop.run(jnp.zeros(()), batches, 0, 5)
    loop._ckpt.close()
    # fresh loop resumes from the persisted step
    loop2 = ResilientLoop(step_fn, str(tmp_path), ckpt_every=2)
    restored, start = loop2.restore_or(jnp.zeros(()))
    assert start > 0
    assert int(restored) == start - 1 + 1 or int(restored) >= 0


def test_straggler_policy_detects_slow_steps():
    p = StragglerPolicy(deadline_factor=2.0, max_slow_steps=2)
    for _ in range(10):
        assert p.observe(0.1) == "ok"
    assert p.observe(1.0) == "slow"
    assert p.observe(1.0) == "reshard"


def test_straggler_policy_rebaselines_after_reshard():
    """Regression: the 'reshard' transition must reset the *timing window*,
    not just the slow-step streak. The post-reshard mesh has a different
    nominal step time; against the stale pre-reshard median every step of
    the new regime reads as slow and the policy re-triggers a reshard
    within `max_slow_steps` observations — an infinite reshard loop."""
    p = StragglerPolicy(deadline_factor=2.0, max_slow_steps=2)
    for _ in range(10):
        assert p.observe(0.1) == "ok"
    assert p.observe(1.0) == "slow"
    assert p.observe(1.0) == "reshard"
    # 1.0s is the new normal. With the stale 0.1s median this would read
    # "slow", "reshard" again; after the re-baseline it never escalates
    # (the first 7 steps are observation-only, then the median is 1.0).
    assert all(p.observe(1.0) == "ok" for _ in range(10))
    # The detector still works after re-baselining.
    assert p.observe(5.0) == "slow"


def test_make_mesh_for_warns_on_degree_mismatch():
    """`make_mesh_for` is best-effort: when the requested model degree
    does not fit the device count it halves down — and must say so loudly,
    because the model degree is the memory slot-sharding degree (a silent
    change re-layouts every memory buffer on the next elastic event)."""
    from repro.launch.mesh import make_mesh_for
    with pytest.warns(UserWarning, match="requested model_parallel=16"):
        mesh = make_mesh_for(jax.device_count(), 16 * jax.device_count())
    assert "model" in mesh.axis_names
    # An exact fit never warns.
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        make_mesh_for(jax.device_count(), jax.device_count())


def test_rescale_to_mesh_relayouts_memory_state():
    """The one-call live scale event: a sharded-layout memory tree moves
    onto a new mesh with its slot rows re-laid-out to the mesh's model
    degree (1 here) and every leaf re-placed — logical rows bit-exact."""
    from repro.distributed.elastic import rescale_to_mesh
    from repro.distributed.mem_shard import to_shard_layout
    n = 8
    logical = jnp.arange(2 * n * 4, dtype=jnp.float32).reshape(2, n, 4)
    tree = {"memory": to_shard_layout(logical, n, 4),   # 4-shard layout
            "w": jnp.ones((4, 4))}
    axes = {"memory": (None, "mem_slots", "mem_word"),
            "w": ("batch", "embed")}
    mesh = make_mesh((1, 1), ("data", "model"))
    out = rescale_to_mesh(tree, axes, mesh, num_slots=n)
    assert out["memory"].shape == (2, n + 1, 4)         # canonical layout
    np.testing.assert_array_equal(np.asarray(out["memory"][:, :n]),
                                  np.asarray(logical))


def test_elastic_reshard_single_device():
    mesh = make_mesh((1,), ("data",))
    tree = {"w": jnp.ones((4, 4))}
    axes = {"w": ("batch", "embed")}
    out = reshard_tree(tree, axes, mesh)
    np.testing.assert_allclose(np.asarray(out["w"]), 1.0)


def test_rescale_batch():
    assert rescale_batch(256, 16, 8) == 128
    assert rescale_batch(256, 16, 32) == 512
