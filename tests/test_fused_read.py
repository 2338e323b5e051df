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


# ------------------------ gated merge: exactness --------------------------
# The sweep merges a tile only where its rows enter a head's running top-K
# (kernels/topk_read.py::sweep_tile). Each case below must give what
# `lax.top_k` gives over the kernel's own scores, bit for bit: the indices,
# the weights of the softmax tail over the selected scores, and the read.

SWEEP_K, SWEEP_BN, SWEEP_N = 8, 128, 1024


def _sweep_case(name, key, B=2, H=4, N=SWEEP_N, W=16):
    """(q, mem (B, N+1, W) with garbage on the scratch row N, beta)."""
    kq, km, kb, kn = jax.random.split(key, 4)
    q = np.asarray(jax.random.normal(kq, (B, H, W)))
    mem = np.asarray(jax.random.normal(km, (B, N + 1, W))).copy()
    if name == "zero_tail":                # most tiles hold only zero rows
        mem[:, 40:N] = 0.0
    elif name == "late_entrants":          # full memory, winners at the end
        mem[:, N - 3] = q[:, 0]
        mem[:, N - 70] = q[:, 1]
        mem[:, N - SWEEP_BN - 5] = q[:, 2]
    elif name == "crowded_tile":           # > K entrants in one tile
        near = q[:, :1] + 0.05 * np.asarray(
            jax.random.normal(kn, (B, 3 * SWEEP_K, W)))
        mem[:, 5 * SWEEP_BN + 7:5 * SWEEP_BN + 7 + 3 * SWEEP_K] = near
    elif name == "ties":                   # later duplicates must lose
        mem[:, 2 * SWEEP_BN + 11] = q[:, 0]
        mem[:, 2 * SWEEP_BN + 12:2 * SWEEP_BN + 12 + SWEEP_K] = q[:, 0, None]
        mem[:, 6 * SWEEP_BN:6 * SWEEP_BN + 2 * SWEEP_K] = q[:, 0, None]
        mem[:, 3 * SWEEP_BN + 1:3 * SWEEP_BN + 40] = mem[:, 1:40]
        mem[:, N - SWEEP_BN:N] = mem[:, 0:SWEEP_BN]
    elif name == "all_negative":           # zero rows win every head
        q = np.abs(q)
        mem[:, :N] = np.where(np.arange(N)[:, None] % 37 == 0,
                              -np.abs(mem[:, :N]), 0.0)
    mem[:, N] = 1e3 * q[:, 0]              # the scratch row: never swept
    beta = jax.random.uniform(kb, (B, H), minval=1.0, maxval=3.0)
    return jnp.asarray(q), mem, beta


def _stored(mem, dtype):
    """Rows as stored in `dtype` (+ per-row scales for int8) and the f32
    rows they dequantize to."""
    if dtype == "int8":
        from repro.core.quant import dequantize_rows, quantize_rows
        rows, scale = quantize_rows(jnp.asarray(mem))
        return rows, scale, dequantize_rows(rows, scale)
    rows = jnp.asarray(mem).astype(dtype)
    return rows, None, rows.astype(jnp.float32)


def _kernel_scores(q, rows, scale, n):
    """The sweep's scores over rows [0, n), tile by tile and lane by lane,
    in the kernel's own formula and ops (normalize(q) · m over the row
    norm at HIGHEST, the int8 scale applied), jitted as the interpreted
    kernel is."""
    from repro.kernels.topk_read import _dot_nt

    @jax.jit
    def tile(q, m, s):
        qn = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
        m = m.astype(jnp.float32)
        dot = _dot_nt(qn, m)
        sq = _dot_nt(jnp.ones((1, m.shape[1]), jnp.float32), m * m)
        if s is not None:
            dot, sq = dot * s, sq * (s * s)
        return dot * jax.lax.rsqrt(sq + 1e-6)

    return jnp.stack([jnp.concatenate(
        [tile(q[b], rows[b, t:t + SWEEP_BN],
              None if scale is None else scale[b, None, t:t + SWEEP_BN])
         for t in range(0, n, SWEEP_BN)], -1) for b in range(q.shape[0])])


SWEEP_CASES = ["zero_tail", "late_entrants", "crowded_tile", "ties",
               "all_negative"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("case", SWEEP_CASES)
def test_gated_sweep_is_exact(case, dtype):
    from repro.kernels import ref
    from repro.kernels.fused_read import _softmax_tail, fused_read_sweep
    from repro.kernels.topk_read import topk_read

    q, mem, beta = _sweep_case(case, jax.random.PRNGKey(
        SWEEP_CASES.index(case)))
    rows, scale, deq = _stored(mem, dtype)
    N, K = SWEEP_N, SWEEP_K
    read, w, idx = fused_read_sweep(q, rows, beta, k=K, block_n=SWEEP_BN,
                                    interpret=True, valid_n=N,
                                    mem_scale=scale)

    # The oracle: lax.top_k over the kernel's own scores, then the tail.
    vals, want_idx = jax.lax.top_k(_kernel_scores(q, rows, scale, N), K)
    @jax.jit
    @jax.vmap
    def tail(v, b, picked):                 # the kernel's emit, per lane
        w = _softmax_tail(v, True, b[:, None])
        read = w[:, 0:1] * picked[:, 0]
        for i in range(1, K):
            read = read + w[:, i:i + 1] * picked[:, i]
        return read, w

    want_read, want_w = tail(vals, beta, jnp.take_along_axis(
        deq[:, None, :N], want_idx[..., None], 2))
    assert np.array_equal(np.asarray(idx), np.asarray(want_idx))
    assert np.array_equal(np.asarray(w), np.asarray(want_w))
    assert np.array_equal(np.asarray(read), np.asarray(want_read))

    # ... which is the composed read's selection (its own rounding).
    r_read, r_w, r_idx = ref.fused_read_ref(q, rows, beta, K, valid_n=N,
                                            mem_scale=scale)
    assert np.array_equal(np.asarray(idx), np.asarray(r_idx))
    np.testing.assert_allclose(np.asarray(w), np.asarray(r_w), atol=1e-5)
    np.testing.assert_allclose(np.asarray(read), np.asarray(r_read),
                               atol=1e-5)

    if scale is None:                      # topk_read shares the merge
        tv, ti = topk_read(q, rows, k=K, block_n=SWEEP_BN, interpret=True,
                           valid_n=N)
        assert np.array_equal(np.asarray(ti), np.asarray(want_idx))
        assert np.array_equal(np.asarray(tv), np.asarray(vals))

    scored, merged, inserted = ref.sweep_merge_steps(
        q, rows if scale is None else rows.astype(jnp.float32), K, SWEEP_BN,
        valid_n=N, mem_scale=scale)
    assert (merged <= scored).all() and (inserted <= K * merged).all()
    assert (merged >= 1).all() and (inserted >= K).all()  # tile 0 fills
    if case in ("zero_tail", "all_negative"):
        # Past the first tile of zero rows nothing can enter any head.
        assert (merged <= 2).all(), merged
    if case == "zero_tail":
        assert (scored <= 2).all(), scored   # zero tiles are not scored
    if case == "crowded_tile":
        assert (inserted >= 2 * K).all(), inserted


def test_sweep_merge_steps_counts_a_full_memory():
    """The counter on a full random memory: every tile is scored, and a
    K-wide merge runs only while the top-K is filling."""
    from repro.kernels import ref
    q, mem, _ = _sweep_case("full", jax.random.PRNGKey(5))
    scored, merged, inserted = ref.sweep_merge_steps(q, mem, SWEEP_K,
                                                     SWEEP_BN, valid_n=SWEEP_N)
    tiles = SWEEP_N // SWEEP_BN
    assert (scored == tiles).all()
    assert (merged <= tiles).all() and (inserted < SWEEP_K * tiles).all()


@pytest.mark.parametrize("n,w,block", [
    (65536, 128, 2048),      # the served memory: 32 tiles of 1 MiB f32
    (2 ** 20, 32, 2048),     # narrow rows still take 128 lanes of VMEM
    (16384, 128, 1024),      # kept at 16 tiles for the gate to skip
    (2560, 128, 512),        # 1,024 does not divide: the old block
    (64, 8, 64),             # a tiny memory is one tile
])
def test_sweep_block_is_derived_from_n_and_w(n, w, block):
    from repro.kernels.fused_read import sweep_block
    assert sweep_block(n, w) == block


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
