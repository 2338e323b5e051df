"""Property tests (hypothesis) for the serving session store
(launch/engine/sessions.py): arbitrary interleavings of put (evict) /
take (restore) across users — with LRU disk spill through the checkpoint
machinery and canonicalizing re-layout from any source shard layout —
round-trip every memory / usage / ANN-index leaf **bit-exactly**. The
store must behave like a plain dict composed with the canonical
re-layout; nothing about ordering, spill, restore, or the ``.npy``
round trip may perturb a single bit.

Also here: the cold-session guard (a brand-new user yields None — and a
freshly initialized state, cold LSH index included, is bit-identical to a
pristine init: no state leaks between users through the store; regression
guard for the phantom-read class), and a forced-8-device lane exercising
the same round trip for states living sharded on a real mesh
(subprocess driver, mirroring the mesh parity lane).

Example budget: 20 examples per property (CI tier-1); the nightly job
raises it via ``REPRO_HYPOTHESIS_PROFILE=nightly`` (200).
"""
import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# Only the interleaving property needs hypothesis; the deterministic
# lanes (spill counts, cold sessions, the mesh round trips) must keep
# running in containers without it.
try:
    from hypothesis import given, settings, strategies as st
    settings.register_profile("ci", max_examples=20, deadline=None)
    settings.register_profile("nightly", max_examples=200, deadline=None)
    settings.load_profile(os.environ.get("REPRO_HYPOTHESIS_PROFILE", "ci"))
except ImportError:                                       # pragma: no cover
    given = settings = st = None

from repro.core import sam as sam_lib  # noqa: E402
from repro.core.types import ControllerConfig, MemoryConfig  # noqa: E402
from repro.distributed import elastic, mem_shard  # noqa: E402
from repro.launch.engine import SessionStore  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

pytestmark = pytest.mark.slow

B, N, W, H, K, D = 1, 16, 8, 2, 2, 6


def _cfg(ann=None):
    return sam_lib.SAMConfig(
        MemoryConfig(num_slots=N, word_size=W, num_heads=H, k=K, ann=ann,
                     lsh_tables=2, lsh_bits=3, lsh_bucket_size=8),
        ControllerConfig(D, 16, D))


def _evolved_state(cfg, seed: int, steps: int):
    """A canonical-layout SAMState after `steps` real SAM steps."""
    params = sam_lib.init_params(jax.random.PRNGKey(seed), cfg)
    state = sam_lib.init_state(B, cfg, params=params)
    for i in range(steps):
        x = jax.random.normal(jax.random.fold_in(
            jax.random.PRNGKey(seed + 1), i), (B, D))
        state = sam_lib.sam_step(params, cfg, state, x)[0]
    return state


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


def _assert_tree_bits(a, b, msg=""):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.shape == y.shape and x.dtype == y.dtype, msg
        assert (x == y).all() or (np.isnan(x) & np.isnan(y)).all(), msg


# ------------------------- interleaving property -------------------------

@pytest.mark.skipif(st is None, reason="needs hypothesis")
@(given(data=st.data()) if st is not None else (lambda f: f))
def test_put_take_interleavings_round_trip_bit_exact(data):
    """The store == dict + canonical re-layout, under arbitrary op
    interleavings, per-user source shard layouts (1/2/4 — mesh-lane
    evictions hand the store sharded-layout trees), an LSH index riding
    in the state, and forced LRU disk spill (capacity=1)."""
    cfg = _cfg(ann="lsh")
    n_users = data.draw(st.integers(1, 3), label="n_users")
    capacity = data.draw(st.sampled_from([None, 1]), label="capacity")

    users = {}
    for u in range(n_users):
        steps = data.draw(st.integers(0, 3), label=f"steps_{u}")
        shards = data.draw(st.sampled_from([1, 2, 4]), label=f"shards_{u}")
        state = _evolved_state(cfg, seed=u, steps=steps)
        tree = elastic.relayout_memory_state(state, N, shards)
        # Reference: what a correct store must hand back — the same tree
        # canonicalized, untouched by storage.
        ref = jax.tree.map(np.asarray,
                           elastic.relayout_memory_state(tree, N, 1))
        users[f"u{u}"] = (tree, ref)

    ops = data.draw(st.lists(
        st.tuples(st.sampled_from(["put", "take"]),
                  st.integers(0, n_users - 1)),
        min_size=1, max_size=12), label="ops")

    with tempfile.TemporaryDirectory() as tmp:
        store = SessionStore(num_slots=N, capacity=capacity,
                             spill_dir=os.path.join(tmp, "spill"))
        model = {}                           # the dict the store must match
        for op, u in ops:
            user = f"u{u}"
            tree, ref = users[user]
            if op == "put":
                store.put(user, tree)
                model[user] = ref
            else:
                got = store.take(user)
                if user not in model:
                    assert got is None       # cold user: nothing to restore
                else:
                    _assert_tree_bits(got, model.pop(user),
                                      f"user {user} leaf mismatch")
                assert user not in store
        for user, ref in model.items():      # drain whatever is left
            _assert_tree_bits(store.take(user), ref,
                              f"user {user} leaf mismatch at drain")
        if capacity == 1 and len(model) > 1:
            assert store.spills > 0          # LRU spill actually exercised


# --------------------------- deterministic lanes --------------------------

def test_spill_and_restore_counts():
    cfg = _cfg(ann="lsh")
    s0, s1 = (_evolved_state(cfg, seed=s, steps=2) for s in (0, 1))
    with tempfile.TemporaryDirectory() as tmp:
        store = SessionStore(num_slots=N, capacity=1,
                             spill_dir=os.path.join(tmp, "spill"))
        store.put("a", s0)
        store.put("b", s1)                   # a spills to disk
        assert store.spills == 1 and "a" in store
        got = store.take("a")                # restored via ckpt machinery
        assert store.restores == 1
        _assert_tree_bits(got, jax.tree.map(
            np.asarray, elastic.relayout_memory_state(s0, N, 1)))


def test_spilled_bfloat16_leaves_come_back_as_bfloat16():
    """A bf16 KV cache (the serving compute dtype) spilled to `.npy` reads
    back as raw 2-byte records unless the manifest's dtype is applied."""
    kv = jnp.arange(24, dtype=jnp.bfloat16).reshape(2, 3, 4) / 7
    with tempfile.TemporaryDirectory() as tmp:
        store = SessionStore(capacity=1, spill_dir=tmp)
        assert store.put("a", {"k": kv}) == kv.nbytes
        store.put("b", {"k": kv + 1})        # a spills to disk
        got = store.take("a")["k"]
    assert store.restores == 1
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got), np.asarray(kv))


def test_capacity_requires_spill_dir():
    with pytest.raises(ValueError):
        SessionStore(num_slots=N, capacity=2)


def test_cold_session_is_fresh_zero_state():
    """A user never stored yields None, and a fresh init afterwards is
    bit-identical to a pristine init — populated neighbours (LSH buckets
    included) cannot leak into a cold session through the store."""
    cfg = _cfg(ann="lsh")
    params = sam_lib.init_params(jax.random.PRNGKey(0), cfg)
    pristine = jax.tree.map(np.asarray, sam_lib.init_state(B, cfg,
                                                           params=params))
    store = SessionStore(num_slots=N)
    store.put("warm", _evolved_state(cfg, seed=0, steps=3))
    assert store.take("cold-user") is None
    fresh = sam_lib.init_state(B, cfg, params=params)
    _assert_tree_bits(fresh, pristine, "cold init was perturbed")
    assert (np.asarray(fresh.ann.buckets) == -1).all()   # cold LSH index
    assert (np.asarray(fresh.ann.cursor) == 0).all()


# ----------------------------- mesh lane ---------------------------------

@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 devices (forced host lane runs the "
                           "driver below)")
def test_mesh_state_round_trip_bit_exact():
    """A state living slot-sharded on a real 8-way mesh: evict into the
    store (canonicalize + host move), take it back, re-lay-out to the
    mesh — every logical row, usage entry, and ANN leaf bit-exact against
    the pre-eviction state."""
    mesh = make_mesh((8,), ("model",))
    cfg = _cfg(ann="lsh")
    with mem_shard.memory_mesh(mesh, N):
        params = sam_lib.init_params(jax.random.PRNGKey(0), cfg)
        state = mem_shard.place_state(sam_lib.init_state(B, cfg,
                                                         params=params))
        for i in range(3):
            x = jax.random.normal(jax.random.PRNGKey(10 + i), (B, D))
            state = sam_lib.sam_step(params, cfg, state, x)[0]

        store = SessionStore(num_slots=N)
        store.put("u", state)
        back = elastic.relayout_memory_state(store.take("u"), N, 8)
        # Compare in canonical layout: logical rows must round-trip
        # (scratch rows are reinitialized by contract).
        _assert_tree_bits(
            elastic.relayout_memory_state(back, N, 1),
            jax.tree.map(np.asarray, elastic.relayout_memory_state(
                state, N, 1)),
            "mesh state round trip")


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 devices (forced host lane runs the "
                           "driver below)")
def test_mesh_state_data_degree_change_bit_exact():
    """A session living on a 2D (2, 4) data×model mesh — batch genuinely
    sharded over the data axis — evicts into the store and restores onto
    a (4, 2) mesh (model degree 4 → 2 re-layouts the slot rows; the data
    degree change is pure placement) and onto a single device, every
    logical leaf bit-exact. `rescale_batch` covers the same event's batch
    arithmetic: per-device batch stays fixed across the degree change."""
    b2 = 2                                    # divisible by the data degree
    mesh24 = make_mesh((2, 4), ("data", "model"))
    mesh42 = make_mesh((4, 2), ("data", "model"))
    cfg = _cfg(ann="lsh")
    params = sam_lib.init_params(jax.random.PRNGKey(0), cfg)
    store = SessionStore(num_slots=N)
    with mem_shard.memory_mesh(mesh24, N):
        ctx = mem_shard.current()
        assert ctx.shards == 4 and ctx.data_degree == 2
        state = mem_shard.place_state(sam_lib.init_state(b2, cfg,
                                                         params=params))
        assert "data" in str(state.memory.sharding.spec[0])  # 2D for real
        for i in range(3):
            x = jax.random.normal(jax.random.PRNGKey(20 + i), (b2, D))
            state = sam_lib.sam_step(params, cfg, state, x)[0]
        canon = jax.tree.map(np.asarray,
                             elastic.relayout_memory_state(state, N, 1))
        store.put("u", state)
    with mem_shard.memory_mesh(mesh42, N):
        ctx = mem_shard.current()
        assert ctx.shards == 2 and ctx.data_degree == 4
        back = mem_shard.place_state(
            elastic.relayout_memory_state(store.peek("u"), N, 2))
        assert back.memory.shape[1] == N + 2          # 2-shard layout
        _assert_tree_bits(elastic.relayout_memory_state(back, N, 1), canon,
                          "(2,4) -> (4,2) restore")
        # The restored session keeps stepping on the new mesh (batch 2
        # does not divide data degree 4, so placement replicates the
        # batch dim — a layout, never a correctness, decision).
        x = jax.random.normal(jax.random.PRNGKey(99), (b2, D))
        nxt = sam_lib.sam_step(params, cfg, back, x)[0]
        assert bool(jnp.isfinite(nxt.read.words).all())
    # Single-device restore: the stored canonical form, bit-exact.
    _assert_tree_bits(store.take("u"), canon, "(2,4) -> single-device")
    # Batch arithmetic of the same event: per-device batch stays fixed.
    assert elastic.rescale_batch(2 * b2, 2, 4) == 4 * b2
    assert elastic.rescale_batch(2 * b2, 2, 1) == b2


@pytest.mark.skipif(jax.device_count() >= 8,
                    reason="8 devices visible: the mesh variant runs "
                           "natively in this session")
@pytest.mark.skipif(bool(os.environ.get("REPRO_SKIP_MESH_DRIVER")),
                    reason="a dedicated forced-8-device mesh lane runs "
                           "this file (CI)")
def test_session_store_on_forced_host_mesh():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q",
         os.path.join(os.path.dirname(__file__), "test_session_store.py"),
         "-k", "mesh_state"],
        env=env, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, \
        f"mesh session round trip failed:\n{proc.stdout[-4000:]}\n" \
        f"{proc.stderr[-2000:]}"
