"""2D (data × model) mesh parity: batch sharding composed with slot
sharding (docs/sharding.md §2D mesh).

These tests need 16 devices — a (2, 8) mesh with a real data axis over the
batch *and* the 8-way slot-sharded memory path; the tier-1 driver at the
bottom of this file (and the CI 2D mesh lane) runs the suite under
``XLA_FLAGS=--xla_force_host_platform_device_count=16``. Covered:

  * SAM and SDNC forward, gradient, and chunked BPTT on the (2, 8) mesh
    match the single-device reference to 1e-5 — exact and LSH candidate
    reads — with the batch dimension genuinely sharded over the data axis
    (asserted on the placed state's sharding spec);
  * the compiled 2D step runs **zero collectives on the data axis**: every
    replica group in its HLO has exactly ``model`` participants
    (`hlo_cost.collective_groups`, the same guard bench_shard asserts on
    its own 2D sweep);
  * a live leave/join elastic event on the serving engine — replicas 2 on
    the (2, 8) mesh, down to 1 on a (1, 8) submesh mid-request, back up —
    preserves the in-flight session bit-exactly and continues the token
    stream without restarting the episode.
"""
import functools
import os
import subprocess
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

# bench_shard provides the 2D compile helpers (single source for the HLO
# guard); `python -m pytest` puts the repo root on sys.path, bare `pytest`
# may not.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

pytestmark = pytest.mark.skipif(
    jax.device_count() < 16,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=16 "
           "(run via the driver at the bottom of this file)")

N, W, H, K, B, T, D = 64, 8, 2, 2, 2, 6, 6
CTL = ControllerConfig(D, 16, D)
TOL = 1e-5


def _mesh28():
    return make_mesh((2, 8), ("data", "model"))


def _mesh18():
    """A (1, 8) submesh over the first 8 devices — the post-leave world."""
    return jax.sharding.Mesh(
        np.array(jax.devices()[:8]).reshape(1, 8), ("data", "model"))


@functools.lru_cache(maxsize=None)
def _cell(kind: str):
    mem = MemoryConfig(num_slots=N, word_size=W, num_heads=H, k=K,
                       ann="lsh" if kind.endswith("_lsh") else "exact",
                       lsh_tables=2, lsh_bits=3, lsh_bucket_size=8)
    if kind.startswith("sdnc"):
        return SDNCCell(dnc_lib.DNCConfig(mem, CTL, k_l=4, sparse=True))
    return SAMCell(sam_lib.SAMConfig(mem, CTL))


def _init_state(cell, kind: str):
    """Single-device reference state with the mesh run's index semantics
    (see tests/test_mesh_parity.py): the LSH ownership partitioning
    determines candidate sets, so the reference carries P=8 unsharded."""
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
    cell = _cell(kind)
    params = cell.init_params(jax.random.PRNGKey(0))
    (_, (st, ys)), g = jax.value_and_grad(_loss, argnums=1, has_aux=True)(
        cell, params, _init_state(cell, kind), mode, chunk)
    return params, st, ys, g


def _assert_state_matches(canon, ref):
    for got, want in zip(jax.tree.leaves(canon), jax.tree.leaves(ref)):
        g, w = np.asarray(got), np.asarray(want)
        if g.ndim >= 2 and g.shape[1] == N + 1:
            g, w = g[:, :N], w[:, :N]
        if np.issubdtype(g.dtype, np.integer):
            np.testing.assert_array_equal(g, w)
        else:
            np.testing.assert_allclose(g, w, atol=TOL, rtol=0)


MODES = [("naive", None), ("chunked", 3)]


@pytest.mark.parametrize("kind", ["sam", "sdnc", "sam_lsh", "sdnc_lsh"])
@pytest.mark.parametrize("mode,chunk", MODES, ids=[m for m, _ in MODES])
def test_forward_grad_bptt_parity_2d(kind, mode, chunk):
    """The (2, 8) run — batch over "data", slot rows over "model" — matches
    the single-device reference at 1e-5 on outputs, final state, and
    gradients. The placed state must be *genuinely* 2D: its memory leaf's
    spec names the data entry on the batch dim and the model axis on the
    row dim, so the parity is exercising the composed layout and not a
    silently-replicated batch."""
    cell = _cell(kind)
    params, ref_st, ref_ys, ref_g = _reference(kind, mode, chunk)
    with mem_shard.memory_mesh(_mesh28(), N):
        ctx = mem_shard.current()
        assert ctx.shards == 8 and ctx.data_degree == 2
        state = mem_shard.place_state(_init_state(cell, kind))
        assert state.memory.shape[1] == N + 8          # slot-sharded layout
        spec = state.memory.sharding.spec
        assert spec[1] == "model" and spec[0] is not None \
            and "data" in ((spec[0],) if isinstance(spec[0], str)
                           else tuple(spec[0]))        # batch over data
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
# HLO guard: zero data-axis collectives on the memory path
# --------------------------------------------------------------------------

def test_step_hlo_zero_data_axis_collectives():
    """Every collective in the compiled (2, 8) step groups on the model
    axis only — 8 participants per replica group, never 2 (data) or 16
    (global) — and the per-device traffic is flat in N and in global B
    (bench_shard asserts the same on its own sweep; the helpers are the
    single source)."""
    from benchmarks import bench_shard
    mesh = _mesh28()
    small = bench_shard.compile_mesh_step_2d(mesh, 256, 2 * bench_shard.B)
    big = bench_shard.compile_mesh_step_2d(mesh, 1024, 2 * bench_shard.B)
    for rec in (small, big):
        assert rec["data_degree"] == 2
        assert rec["collective_group_sizes"] == [8], \
            f"non-model-axis collectives: groups " \
            f"{rec['collective_group_sizes']}"
        assert rec["full_buffer_offenses"] == [], rec["full_buffer_offenses"]
    fit = bench_shard._flat_in("N", [256, 1024],
                               [small["bytes_total"], big["bytes_total"]])
    assert fit.ok, f"2D collective bytes grew ~N^{fit.exponent:.2f}"
    # Flat in global B per device: the replicated-batch control on the
    # same mesh pays ~2x what the batch-sharded step pays.
    repl = bench_shard.compile_mesh_step_2d(mesh, 1024, 2 * bench_shard.B,
                                            data_parallel=False)
    assert repl["bytes_total"] >= big["bytes_total"] * 1.7


# --------------------------------------------------------------------------
# Serving: live leave/join elastic events
# --------------------------------------------------------------------------

def _mem_equal(a, b):
    for sa, sb in zip(a, b):
        for name in sa._fields:
            f, s = np.asarray(getattr(sa, name)), np.asarray(getattr(sb, name))
            if f.shape != s.shape or not (f == s).all():
                return False, name
    return True, None


def test_serve_live_leave_join_preserves_sessions():
    """A replica-leave mid-request (mesh (2,8) → (1,8), replicas 2 → 1)
    parks every in-flight session through the ordinary eviction path and
    resumes it on the shrunk engine; a later re-join (back to (2,8))
    serves the same user again from the preserved session. Token streams
    and the final stored session are bit-identical to an uninterrupted
    two-request run on the (2,8) mesh — no episode restart anywhere."""
    from repro.configs import get_config, reduced
    from repro.launch.engine import Request, ServeEngine
    cfg = reduced(get_config("h2o_danube_3_4b_sam"))
    P1, P2 = [3, 7, 11, 2], [5]
    u = dict(user="u", greedy=False, sample_seed=42)
    noise = lambda: Request(user="noise", prompt=[9, 9], max_new_tokens=6,
                            greedy=False, sample_seed=7)

    # Reference: both requests served uninterrupted on the (2, 8) mesh.
    with ServeEngine(cfg, lanes=4, max_len=64, mesh=_mesh28()) as ref:
        assert ref.replicas == 2              # defaulted to the data degree
        r1 = ref.run([Request(prompt=P1, max_new_tokens=8, **u), noise()])
        tok_ref = [r for r in r1 if r["user"] == "u"][0]["tokens"]
        r2 = ref.run([Request(prompt=P2, max_new_tokens=4, **u)])
        tok_ref2 = r2[0]["tokens"]
        sess_ref = ref.sessions.take("u")

    # Live run: the leave event fires mid-decode of the first request.
    with ServeEngine(cfg, lanes=4, max_len=64, mesh=_mesh28()) as eng:
        eng.submit(Request(prompt=P1, max_new_tokens=8, **u))
        eng.submit(noise())
        done = []
        for _ in range(6):                    # prefill + a few decode steps
            done.extend(eng.step())
        assert any(r.user == "u" for r in eng.scheduler.active.values())
        eng.rescale(mesh=_mesh18())           # leave: one replica remains
        assert eng.replicas == 1 and eng.lanes == 2
        while eng.scheduler.has_work:         # finish on the shrunk engine
            done.extend(eng.step())
        tok_live = [r for r in done if r["user"] == "u"][0]["tokens"]
        eng.rescale(mesh=_mesh28())           # join: back to two replicas
        assert eng.replicas == 2 and eng.lanes == 4
        r2 = eng.run([Request(prompt=P2, max_new_tokens=4, **u)])
        tok_live2 = r2[0]["tokens"]
        sess_live = eng.sessions.take("u")

    assert tok_live == tok_ref                # continuation, not restart
    assert tok_live2 == tok_ref2
    ok, leaf = _mem_equal(sess_ref["mem"], sess_live["mem"])
    assert ok, f"memory leaf {leaf!r} diverged across the leave/join"
    assert int(sess_ref["pos"][0]) == int(sess_live["pos"][0])
    assert sess_ref["counter"] == sess_live["counter"]


# --------------------------------------------------------------------------
# Tier-1 driver: force a 16-device host platform in a subprocess
# --------------------------------------------------------------------------

@pytest.mark.skipif(jax.device_count() >= 16,
                    reason="16 devices visible: the suite runs natively in "
                           "this session")
@pytest.mark.skipif(bool(os.environ.get("REPRO_SKIP_MESH_DRIVER")),
                    reason="a dedicated forced-16-device 2D mesh lane runs "
                           "this file (CI)")
def test_mesh2d_parity_suite_on_forced_host_mesh():
    """Driver: re-run this file in a subprocess with a forced 16-device
    host platform (XLA flag must precede jax import, hence the
    subprocess)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=16")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q",
         os.path.join(os.path.dirname(__file__), "test_mesh2d_parity.py"),
         "-k", "not forced_host"],
        env=env, capture_output=True, text=True, timeout=1800)
    assert proc.returncode == 0, \
        f"2D mesh parity failed:\n{proc.stdout[-4000:]}\n{proc.stderr[-2000:]}"
