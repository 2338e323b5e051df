"""Fused one-dispatch SAM read (kernels/fused_read.py via ops.fused_read):
forward and gradient parity with the composed topk_read → re-rank → softmax
→ gather path, candidate-mode validity (duplicates, cold index), the
scratch-row/valid_n contract, bf16 storage, and the structural guard that
the exact read really is ONE kernel dispatch on the Pallas backends (with
the composed path as the positive control)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import addressing as addr
from repro.kernels import ops

BACKENDS = ["ref", "pallas-interpret"]


def _case(key, B=2, H=3, N=64, W=16):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, W))
    mem = jax.random.normal(ks[1], (B, N, W))
    beta = jax.random.uniform(ks[2], (B, H), minval=1.0, maxval=3.0)
    return q, mem, beta


def _composed(q, mem, beta, k, valid_n=None):
    """The pre-fusion exact read: top_k over cosine sims under
    stop_gradient, then the differentiable tail."""
    mv = mem if valid_n is None else mem[:, :valid_n]
    sims = addr.cosine_sim(jax.lax.stop_gradient(q),
                           jax.lax.stop_gradient(mv).astype(jnp.float32))
    _, idx = jax.lax.top_k(sims, k)
    return addr.finish_candidate_read(q, mem, beta, idx)


# ----------------------------- exact read ---------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_exact_forward_matches_composed(backend):
    q, mem, beta, k = *_case(jax.random.PRNGKey(0)), 4
    read, w, idx = ops.fused_read(q, mem, beta, k, backend=backend)
    want = _composed(q, mem, beta, k)
    assert np.array_equal(np.asarray(idx), np.asarray(want.indices))
    np.testing.assert_allclose(np.asarray(w), np.asarray(want.weights),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(read), np.asarray(want.words),
                               atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_exact_gradients_match_composed(backend):
    q, mem, beta, k = *_case(jax.random.PRNGKey(1)), 4
    tr = jax.random.normal(jax.random.PRNGKey(2), q.shape)
    tw = jax.random.normal(jax.random.PRNGKey(3), (*beta.shape, k))

    def loss_fused(args):
        read, w, _ = ops.fused_read(*args, k, backend=backend)
        return (read * tr).sum() + (w * tw).sum()

    def loss_composed(args):
        r = _composed(*args, k)
        return (r.words * tr).sum() + (r.weights * tw).sum()

    g_f = jax.grad(loss_fused)((q, mem, beta))
    g_c = jax.grad(loss_composed)((q, mem, beta))
    for gf, gc in zip(g_f, g_c):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gc), atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_valid_n_never_selects_scratch_row(backend):
    """A scratch-row buffer with garbage on row N: valid_n must keep the
    sweep off it — indices < N, outputs equal to the logical-rows read,
    and exactly zero gradient into the scratch row."""
    q, mem, beta, k = *_case(jax.random.PRNGKey(4)), 4
    B, N, W = mem.shape
    # Scratch row deliberately query-aligned: it would win every top-K.
    buf = jnp.concatenate([mem, 1e3 * q[:, :1, :]], axis=1)
    read, w, idx = ops.fused_read(q, buf, beta, k, backend=backend,
                                  valid_n=N)
    assert (np.asarray(idx) < N).all()
    want = _composed(q, mem, beta, k)
    assert np.array_equal(np.asarray(idx), np.asarray(want.indices))
    np.testing.assert_allclose(np.asarray(read), np.asarray(want.words),
                               atol=1e-5)

    g = jax.grad(lambda m: ops.fused_read(q, m, beta, k, backend=backend,
                                          valid_n=N)[0].sum())(buf)
    assert (np.asarray(g)[:, N] == 0).all()


def test_exact_duplicate_rows_tie_break_like_top_k():
    """Identical memory rows: the fused sweep must keep `lax.top_k`'s tie
    order (lowest index first) so pallas and ref agree exactly."""
    q, mem, beta, k = *_case(jax.random.PRNGKey(5), N=32), 4
    mem = mem.at[:, 10].set(mem[:, 3]).at[:, 21].set(mem[:, 3])
    _, _, i_ref = ops.fused_read(q, mem, beta, k, backend="ref")
    _, _, i_pal = ops.fused_read(q, mem, beta, k,
                                 backend="pallas-interpret")
    assert np.array_equal(np.asarray(i_ref), np.asarray(i_pal))


# --------------------------- candidate read -------------------------------

def _cand_case(key, B=2, H=2, N=64, W=16, C=12):
    q, mem, beta = _case(key, B=B, H=H, N=N, W=W)
    cand = jax.random.randint(jax.random.PRNGKey(99), (B, H, C), 0, N)
    cand = cand.at[:, :, 3].set(cand[:, :, 0])       # duplicate
    cand = cand.at[:, :, 5].set(-1)                  # cold bucket slot
    return q, mem, beta, cand


@pytest.mark.parametrize("backend", BACKENDS)
def test_candidates_match_composed(backend):
    q, mem, beta, cand = _cand_case(jax.random.PRNGKey(6))
    k = 4
    sr, sel = addr.select_and_read_candidates(q, mem, beta, k, cand,
                                              backend=backend)
    want_sel = addr.select_candidates(q, mem, k, cand)
    want = addr.finish_candidate_read(q, mem, beta, want_sel)
    assert np.array_equal(np.asarray(sel), np.asarray(want_sel))
    assert np.array_equal(np.asarray(sr.indices), np.asarray(want.indices))
    np.testing.assert_allclose(np.asarray(sr.weights),
                               np.asarray(want.weights), atol=1e-5)
    np.testing.assert_allclose(np.asarray(sr.words),
                               np.asarray(want.words), atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_cold_candidate_index_reads_zero_with_zero_grad(backend):
    """All candidates invalid (a cold LSH index): weight exactly 0, read
    exactly 0, and no gradient leaks into row 0 through the clamp."""
    q, mem, beta, _ = _cand_case(jax.random.PRNGKey(7))
    cand = jnp.full((2, 2, 12), -1, jnp.int32)
    read, w, sel = ops.fused_read(q, mem, beta, 4, cand_idx=cand,
                                  backend=backend)
    assert (np.asarray(w) == 0).all()
    assert (np.asarray(read) == 0).all()
    assert (np.asarray(sel) < 0).all()
    g = jax.grad(lambda m: ops.fused_read(q, m, beta, 4, cand_idx=cand,
                                          backend=backend)[0].sum())(mem)
    assert (np.asarray(g) == 0).all()


@pytest.mark.parametrize("backend", BACKENDS)
def test_candidate_gradients_match_composed(backend):
    q, mem, beta, cand = _cand_case(jax.random.PRNGKey(8))
    k = 4

    def loss_fused(args):
        sr, _ = addr.select_and_read_candidates(*args, k, cand,
                                                backend=backend)
        return (sr.words ** 2).sum() + sr.weights.sum()

    def loss_composed(args):
        sel = addr.select_candidates(args[0], args[1], k, cand)
        r = addr.finish_candidate_read(*args, sel)
        return (r.words ** 2).sum() + r.weights.sum()

    g_f = jax.grad(loss_fused)((q, mem, beta))
    g_c = jax.grad(loss_composed)((q, mem, beta))
    for gf, gc in zip(g_f, g_c):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gc), atol=1e-5)


# ------------------------------ bf16 rows ---------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_bf16_memory_reads_close_to_f32(backend):
    """bf16 storage (MemoryConfig.mem_dtype): the read upcasts rows to f32,
    so outputs stay f32 and track the f32-storage read to bf16 precision."""
    q, mem, beta, k = *_case(jax.random.PRNGKey(9)), 4
    r32, w32, _ = ops.fused_read(q, mem, beta, k, backend=backend)
    r16, w16, _ = ops.fused_read(q, mem.astype(jnp.bfloat16), beta, k,
                                 backend=backend)
    assert r16.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(r16), np.asarray(r32), atol=0.05)
    np.testing.assert_allclose(np.asarray(w16), np.asarray(w32), atol=0.05)


# ------------------------- structural dispatch guard ----------------------
# The dispatch fingerprints (one pallas_call, zero top_k/sort, the
# `fused_read_sweep` name) are declared on contracts in repro.analysis.paths;
# these tests run them through the shared checker so the guard and the
# sweep share one source of truth. Each pairs with a ref/composed positive
# control that passes only by tripping.

def _run(name):
    from repro.analysis import all_contracts, run_contract
    report = run_contract(all_contracts()[name], quick=True)
    detail = {b: r.get("failures", []) for b, r in report["backends"].items()}
    return report, detail


def test_exact_read_is_one_kernel_dispatch():
    """The acceptance guard: on the Pallas backend the exact read traces to
    exactly one pallas_call (the `fused_read_sweep`) and NO top_k/sort; the
    composed/ref path (the positive control) contains a top_k."""
    report, detail = _run("sam_read_exact_kernel")
    assert report["ok"], detail
    ctrl, cdetail = _run("composed_read_control")
    assert ctrl["ok"], ("composed-read control never tripped", cdetail)


def test_decode_step_read_has_no_topk_on_pallas():
    """End-to-end: a serving decode step on the Pallas memory backend
    contains no top_k at all — the read is the fused kernel. (`sort` still
    appears: the LRA top-n's host-side tile merge, write path, is a
    lexsort.) The ref backend is the positive control."""
    report, detail = _run("lm_decode_no_topk")
    assert report["ok"], detail
    ctrl, cdetail = _run("lm_decode_ref_control")
    assert ctrl["ok"], ("ref decode control never tripped", cdetail)


# ------------------------------- mesh lane --------------------------------

@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 devices (forced host lane runs the "
                           "driver below)")
def test_fused_read_mesh_fallback_matches_single_device():
    """Slot-sharded buffers have no fused route: sparse_read_exact must
    fall back to the composed shard_map path and still agree with the
    single-device fused read."""
    from repro.distributed import mem_shard
    from repro.launch.mesh import make_memory_mesh

    B, H, N, W, k = 2, 2, 64, 16, 4
    q, mem, beta = _case(jax.random.PRNGKey(11), B=B, H=H, N=N, W=W)
    want = addr.sparse_read_exact(q, jnp.pad(mem, ((0, 0), (0, 1), (0, 0))),
                                  beta, k, backend="pallas-interpret",
                                  valid_n=N)
    mesh = make_memory_mesh(8)
    with mem_shard.memory_mesh(mesh, N):
        buf = mem_shard.to_shard_layout(mem, N, 8)
        got = addr.sparse_read_exact(q, buf, beta, k,
                                     backend="pallas-interpret")
    assert np.array_equal(np.asarray(got.indices), np.asarray(want.indices))
    np.testing.assert_allclose(np.asarray(got.words), np.asarray(want.words),
                               atol=1e-5)


@pytest.mark.skipif(jax.device_count() >= 8,
                    reason="8 devices visible: the mesh variant runs "
                           "natively in this session")
@pytest.mark.skipif(bool(os.environ.get("REPRO_SKIP_MESH_DRIVER")),
                    reason="a dedicated forced-8-device mesh lane runs "
                           "this file (CI)")
def test_fused_read_on_forced_host_mesh():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q",
         os.path.join(os.path.dirname(__file__), "test_fused_read.py"),
         "-k", "mesh_fallback"],
        env=env, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, \
        f"mesh fused-read failed:\n{proc.stdout[-4000:]}\n{proc.stderr[-2000:]}"
