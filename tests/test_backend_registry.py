"""Backend registry semantics plus the end-to-end acceptance parity:
`sam_step`/`sam_unroll` on the "pallas-interpret" backend must match the
"ref" backend within 1e-5."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sam as sam_lib
from repro.core.unroll import sam_unroll_sparse_bptt
from repro.core.types import ControllerConfig, MemoryConfig
from repro.kernels import ops, ref, registry


# ------------------------------- registry ---------------------------------

def test_resolve_default_is_ref():
    assert registry.resolve(None).name == "ref"
    assert registry.resolve("ref") is registry.resolve(None)


def test_resolve_env_var(monkeypatch):
    monkeypatch.setenv(registry.ENV_VAR, "pallas-interpret")
    be = registry.resolve(None)
    assert be.name == "pallas-interpret" and be.use_pallas and be.interpret


def test_platform_default_is_pallas_on_tpu(monkeypatch):
    """With no backend named, a TPU resolves to the compiled kernels and
    every other platform to the oracle — never to the interpreter."""
    monkeypatch.delenv(registry.ENV_VAR, raising=False)
    monkeypatch.setattr(registry.jax, "default_backend", lambda: "tpu")
    be = registry.resolve(None)
    assert be.name == "pallas" and be.use_pallas and not be.interpret
    monkeypatch.setattr(registry.jax, "default_backend", lambda: "cpu")
    assert registry.resolve(None).name == "ref"


def test_resolve_passthrough_instance():
    be = registry.get("pallas")
    assert registry.resolve(be) is be
    assert be.use_pallas and not be.interpret


def test_unknown_backend_raises_with_listing():
    with pytest.raises(KeyError, match="nope.*available"):
        registry.resolve("nope")


def test_builtins_cannot_be_silently_replaced():
    with pytest.raises(ValueError, match="already registered"):
        registry.register(registry.KernelBackend(name="ref"))
    with pytest.raises(ValueError, match="built-in"):
        registry.unregister("pallas")


def test_custom_backend_override_is_dispatched():
    """A registered backend's per-op override wins over flags and oracle —
    the documented extension point (docs/kernels.md)."""
    calls = []

    def my_argmin(last_access):
        calls.append(last_access.shape)
        return ref.usage_argmin_ref(last_access)

    be = registry.register(registry.KernelBackend(
        name="custom-test", overrides={"usage_argmin": my_argmin}))
    try:
        u = jnp.array([[3, 1, 2]], jnp.int32)
        out = ops.usage_argmin(u, backend="custom-test")
        assert int(out[0]) == 1 and calls == [(1, 3)]
        # Ops without an override fall back to the oracle.
        v, i = ops.topk_read(jnp.ones((1, 1, 4)), jnp.ones((1, 8, 4)), 2,
                             backend=be)
        assert i.shape == (1, 1, 2)
    finally:
        registry.unregister("custom-test")


# --------------------------- end-to-end parity ----------------------------

CTL = ControllerConfig(input_size=8, hidden_size=24, output_size=6)


def _cfg(backend, ann="exact"):
    mem = MemoryConfig(num_slots=64, word_size=8, num_heads=2, k=2, ann=ann,
                       lsh_tables=2, lsh_bits=4, lsh_bucket_size=8,
                       backend=backend)
    return sam_lib.SAMConfig(mem, CTL)


def _run(backend, ann, T=4, B=2):
    cfg = _cfg(backend, ann)
    key = jax.random.PRNGKey(0)
    params = sam_lib.init_params(key, cfg)
    state = sam_lib.init_state(B, cfg)
    xs = jax.random.normal(jax.random.PRNGKey(1), (T, B, 8))
    stateT, ys = sam_lib.sam_unroll(params, cfg, state, xs)
    return stateT, ys


@pytest.mark.parametrize("ann", ["exact", "lsh"])
def test_sam_unroll_backend_parity(ann):
    """Acceptance: sam_step/sam_unroll end-to-end on backend
    "pallas-interpret" match "ref" within 1e-5 (exact and LSH modes)."""
    s_ref, y_ref = _run("ref", ann)
    s_pal, y_pal = _run("pallas-interpret", ann)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_pal.memory),
                               np.asarray(s_ref.memory), atol=1e-5)
    assert np.array_equal(np.asarray(s_pal.last_access),
                          np.asarray(s_ref.last_access))
    assert np.array_equal(np.asarray(s_pal.read.indices),
                          np.asarray(s_ref.read.indices))


def test_sam_step_backend_parity_single_step():
    cfg_r, cfg_p = _cfg("ref"), _cfg("pallas-interpret")
    key = jax.random.PRNGKey(2)
    params = sam_lib.init_params(key, cfg_r)
    state = sam_lib.init_state(2, cfg_r)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8))
    s1, y1, d1 = sam_lib.sam_step(params, cfg_r, state, x, collect_deltas=True)
    s2, y2, d2 = sam_lib.sam_step(params, cfg_p, state, x, collect_deltas=True)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y1), atol=1e-5)
    assert np.array_equal(np.asarray(d2.write_idx), np.asarray(d1.write_idx))
    np.testing.assert_allclose(np.asarray(d2.old_rows),
                               np.asarray(d1.old_rows), atol=1e-5)


def test_sam_grads_backend_parity():
    """Gradients through the naive unroll agree across backends — exercises
    the custom VJPs of the fused write on the production path."""
    def grads(backend):
        cfg = _cfg(backend)
        key = jax.random.PRNGKey(4)
        params = sam_lib.init_params(key, cfg)
        state = sam_lib.init_state(2, cfg)
        xs = jax.random.normal(jax.random.PRNGKey(5), (3, 2, 8))
        return jax.grad(lambda p: (sam_lib.sam_unroll(p, cfg, state, xs)[1]
                                   ** 2).sum())(params)

    g_ref, g_pal = grads("ref"), grads("pallas-interpret")
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=2e-4, rtol=1e-3), g_ref, g_pal)


def test_sparse_bptt_on_pallas_backend():
    """The rollback BPTT must run and match the naive unroll's gradients on
    the pallas-interpret backend (replay + rollback both dispatch)."""
    cfg = _cfg("pallas-interpret")
    key = jax.random.PRNGKey(6)
    params = sam_lib.init_params(key, cfg)
    state = sam_lib.init_state(2, cfg)
    xs = jax.random.normal(jax.random.PRNGKey(7), (3, 2, 8))

    g1 = jax.grad(lambda p: (sam_lib.sam_unroll(p, cfg, state, xs)[1]
                             ** 2).sum())(params)
    g2 = jax.grad(lambda p: (sam_unroll_sparse_bptt(p, cfg, state, xs)[1]
                             ** 2).sum())(params)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=2e-4, rtol=1e-3), g1, g2)


def test_memory_config_backend_field_is_static():
    cfg = MemoryConfig(backend="pallas-interpret")
    assert dataclasses.asdict(cfg)["backend"] == "pallas-interpret"
    hash(cfg)   # frozen + hashable, safe as a static jit argument


# -------------- scratch-row layout: ref vs pallas parity sweep --------------
#
# The persistent (B, N+1, W) layout (docs/memory-model.md) must be
# observationally identical across backends — forward, `jax.grad`, and the
# rollback-BPTT restore — including the configurations that exercise the
# silent-fallback paths (block-divisibility, float-dtype `lra_topn`).

SWEEP = [
    # (num_slots, word_size, heads, k, T, B). All configs stay on the
    # kernel path end-to-end: `sam_step` never overrides block_n, so the
    # clamp to min(block_n, N) always divides. The fallback paths are
    # exercised at the ops level below, where block_n can be forced.
    (64, 8, 2, 2, 4, 2),
    (80, 8, 2, 4, 3, 1),
    (48, 16, 4, 2, 3, 2),
]


def _sweep_cfg(backend, shape):
    n, w, h, k, _, _ = shape
    mem = MemoryConfig(num_slots=n, word_size=w, num_heads=h, k=k,
                      backend=backend)
    return sam_lib.SAMConfig(mem, CTL)


@pytest.mark.parametrize("shape", SWEEP,
                         ids=[f"N{s[0]}W{s[1]}H{s[2]}K{s[3]}" for s in SWEEP])
def test_layout_parity_forward_grad_bptt(shape):
    """Forward outputs/state (1e-5), naive-unroll grads, and rollback-BPTT
    grads agree between "ref" and "pallas-interpret" on the padded layout."""
    *_, T, B = shape

    def run(backend):
        cfg = _sweep_cfg(backend, shape)
        params = sam_lib.init_params(jax.random.PRNGKey(0), cfg)
        state = sam_lib.init_state(B, cfg)
        assert state.memory.shape[1] == cfg.memory.num_slots + 1
        xs = jax.random.normal(jax.random.PRNGKey(1), (T, B, 8))
        stateT, ys = sam_lib.sam_unroll(params, cfg, state, xs)
        g = jax.grad(lambda p: (sam_lib.sam_unroll(p, cfg, state, xs)[1]
                                ** 2).sum())(params)
        gb = jax.grad(lambda p: (sam_unroll_sparse_bptt(p, cfg, state, xs)[1]
                                 ** 2).sum())(params)
        return stateT, ys, g, gb

    s_ref, y_ref, g_ref, gb_ref = run("ref")
    s_pal, y_pal, g_pal, gb_pal = run("pallas-interpret")
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_pal.memory),
                               np.asarray(s_ref.memory), atol=1e-5)
    assert np.array_equal(np.asarray(s_pal.last_access),
                          np.asarray(s_ref.last_access))
    for ga, gb in ((g_ref, g_pal), (gb_ref, gb_pal)):
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, rtol=1e-3), ga, gb)
    # The rollback restore itself: BPTT grads also match the naive unroll.
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=2e-4, rtol=1e-3), g_pal, gb_pal)


@pytest.mark.parametrize("block_n,expect_kernel", [(32, True), (40, False)])
def test_layout_parity_block_divisibility_fallback(block_n, expect_kernel,
                                                   monkeypatch):
    """ops-level sweep on the padded layout: divisibility is checked against
    the *logical* N (so N=64 at block 32 stays on the kernel path despite
    the 65-row buffer), and a non-divisible block silently falls back to
    the sliced reference with identical results. The execution path is
    asserted by spying on the dispatch targets — results alone can't
    distinguish them (they must agree by contract)."""
    N, W, H, K = 64, 8, 2, 4
    calls = {"kernel": 0, "oracle": 0}
    real_kernel, real_oracle = ops.topk_read_pallas, ops.ref.topk_read_ref

    def spy_kernel(*a, **kw):
        calls["kernel"] += 1
        return real_kernel(*a, **kw)

    def spy_oracle(*a, **kw):
        calls["oracle"] += 1
        return real_oracle(*a, **kw)

    monkeypatch.setattr(ops, "topk_read_pallas", spy_kernel)
    monkeypatch.setattr(ops.ref, "topk_read_ref", spy_oracle)

    mem = jax.random.normal(jax.random.PRNGKey(0), (1, N + 1, W))
    mem = mem.at[:, N].set(1e3)          # garbage scratch: must never win
    q = jax.random.normal(jax.random.PRNGKey(1), (1, H, W))
    v_ref, i_ref = ops.topk_read(q, mem, K, backend="ref", valid_n=N)
    assert calls == {"kernel": 0, "oracle": 1}
    v_pal, i_pal = ops.topk_read(q, mem, K, backend="pallas-interpret",
                                 block_n=block_n, valid_n=N)
    assert calls["kernel"] == (1 if expect_kernel else 0)
    assert calls["oracle"] == (1 if expect_kernel else 2)
    assert np.array_equal(np.sort(np.asarray(i_pal)), np.sort(np.asarray(i_ref)))
    np.testing.assert_allclose(np.sort(np.asarray(v_pal)),
                               np.sort(np.asarray(v_ref)), atol=1e-5)
    assert int(np.asarray(i_pal).max()) < N


def test_layout_parity_float_dtype_fallback():
    """Float usage tables (DAM's U^(1)) take the reference path for
    `lra_topn` even on a pallas backend — with valid_n the slice happens
    before the oracle, so a float garbage scratch entry can never win."""
    N, H = 48, 4
    la = jax.random.uniform(jax.random.PRNGKey(0), (2, N + 1)) * 10.0
    la = la.at[:, N].set(-1e9)           # would win the argmin if swept
    out_ref = ops.lra_topn(la, H, backend="ref", valid_n=N)
    out_pal = ops.lra_topn(la, H, backend="pallas-interpret", valid_n=N)
    assert np.array_equal(np.asarray(out_ref), np.asarray(out_pal))
    assert int(np.asarray(out_pal).max()) < N


def test_old_signature_override_works_on_padded_layout():
    """A custom backend registered with the pre-scratch-row override
    signatures must keep working now that the padded layout is the default
    state: sweep overrides get the sliced logical view, mutating overrides
    run without `scratch_row` (docs/kernels.md 'Adding a backend')."""
    seen = {}

    def old_topk(q, mem, k, *, block_n=512):
        seen["topk_n"] = mem.shape[1]
        return ref.topk_read_ref(q, mem, k)

    def old_write(mem, last, widx, ww, a, lra, step, *, delta):
        seen["write_rows"] = mem.shape[1]
        return ref.sparse_write_update_ref(mem, last, widx, ww, a, lra,
                                           step, delta)

    registry.register(registry.KernelBackend(
        name="old-sig-test",
        overrides={"topk_read": old_topk, "sparse_write_update": old_write}))
    try:
        cfg = _cfg("old-sig-test")
        params = sam_lib.init_params(jax.random.PRNGKey(0), cfg)
        state = sam_lib.init_state(2, cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8))
        _, y = sam_lib.sam_step(params, cfg, state, x)
        assert bool(jnp.isfinite(y).all())
        N = cfg.memory.num_slots
        assert seen["topk_n"] == N          # sweep saw the sliced view
        assert seen["write_rows"] == N + 1  # mutating op saw the full buffer
        # Parity with the ref backend on the same padded state.
        _, y_ref = sam_lib.sam_step(params, _cfg("ref"), state, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=1e-6)
    finally:
        registry.unregister("old-sig-test")


def test_layout_parity_checkpoint_restore_roundtrip(tmp_path):
    """A padded state saved on one backend restores and continues on the
    other with identical outputs (the layout is backend-independent)."""
    from repro.checkpoint.ckpt import restore_checkpoint, save_checkpoint
    cfg_r, cfg_p = _cfg("ref"), _cfg("pallas-interpret")
    params = sam_lib.init_params(jax.random.PRNGKey(0), cfg_r)
    state = sam_lib.init_state(2, cfg_r)
    xs = jax.random.normal(jax.random.PRNGKey(1), (3, 2, 8))
    mid, _ = sam_lib.sam_unroll(params, cfg_r, state, xs)
    save_checkpoint(str(tmp_path), 1, mid)
    restored, _ = restore_checkpoint(str(tmp_path), mid)
    x2 = jax.random.normal(jax.random.PRNGKey(2), (2, 8))
    _, y_ref = sam_lib.sam_step(params, cfg_r, restored, x2)
    _, y_pal = sam_lib.sam_step(params, cfg_p, restored, x2)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               atol=1e-5)
