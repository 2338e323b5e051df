"""Compile the served Pallas kernels for a TPU v5e that is described, not
attached: the Mosaic compiler refuses here what the chip would refuse
(block shapes off the (8, 128) tiling, unlowerable in-kernel idioms, too
much VMEM), at no chip time.

Shapes are the served widths — B=8 lanes, H=4 heads, N=65,536 slots plus
the scratch row, W=128, K=8 — and, for the kernels the slot-sharded path
runs per shard, the shard-local rows of a 4-chip mesh (N/4 + 1). Each
compiled program must hold the kernel as a TPU custom call under its
name. The topology is described inside a fixture (never at import: only
one process at a time may load the TPU library), and the persistent
compilation cache is off around these compiles (a program compiled for a
described device cannot be read back without one).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops  # noqa: F401  (import order: ops first)
from repro.kernels.fused_read import fused_read_sweep
from repro.kernels.sparse_write import sparse_write_update
from repro.kernels.topk_read import topk_read
from repro.kernels.usage_argmin import lra_topn

B, H, N, W, K = 8, 4, 65536, 128, 8
J = H * (K + 1)
DELTA = 0.005


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _read(n, dtype):
    """fused_read_sweep over an (B, n+1, W) buffer of `dtype` (int8 rows
    carry their per-row f32 scales)."""
    def shapes(s):
        out = [s((B, H, W), jnp.float32), s((B, n + 1, W), dtype),
               s((B, H), jnp.float32)]
        if dtype == jnp.int8:
            out.append(s((B, n + 1), jnp.float32))
        return out

    def fn(q, m, beta, scale=None):
        return fused_read_sweep(q, m, beta, k=K, valid_n=n, mem_scale=scale)
    return "fused_read_sweep", fn, shapes


def _write(n, dtype):
    """sparse_write_update in place on an (B, n+1, W) buffer of `dtype`
    with per-lane steps, as the engine calls it."""
    def shapes(s):
        out = [s((B, n + 1, W), dtype), s((B, n + 1), jnp.int32),
               s((B, J), jnp.int32), s((B, J), jnp.float32),
               s((B, H, W), jnp.float32), s((B, H), jnp.int32),
               s((B, 1), jnp.int32)]
        if dtype == jnp.int8:
            out.append(s((B, n + 1), jnp.float32))
        return out

    def fn(mem, la, widx, ww, a, lra, step, scale=None):
        return sparse_write_update(mem, la, widx, ww, a, lra, step,
                                   delta=DELTA, scratch_row=n,
                                   mem_scale=scale)
    return "sparse_write_update", fn, shapes


def _lra(n):
    def fn(la):
        return lra_topn(la, n=H, valid_n=n)
    return "lra_topn", fn, lambda s: [s((B, n + 1), jnp.int32)]


def _topk(n):
    def fn(q, m):
        return topk_read(q, m, k=K, valid_n=n)
    return "topk_read", fn, lambda s: [s((B, H, W), jnp.float32),
                                       s((B, n + 1, W), jnp.float32)]


SHARD = N // 4
CASES = {
    "fused_read_sweep-f32": _read(N, jnp.float32),
    "fused_read_sweep-bf16": _read(N, jnp.bfloat16),
    "fused_read_sweep-int8": _read(N, jnp.int8),
    "sparse_write_update-f32": _write(N, jnp.float32),
    "sparse_write_update-bf16": _write(N, jnp.bfloat16),
    "sparse_write_update-int8": _write(N, jnp.int8),
    "lra_topn": _lra(N),
    "topk_read": _topk(N),
    "shard-topk_read": _topk(SHARD),
    "shard-lra_topn": _lra(SHARD),
    "shard-sparse_write_update": _write(SHARD, jnp.float32),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_compile_cache):
    name, fn, shapes = CASES[case]
    args = shapes(lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                         sharding=one_chip))
    compiled = jax.jit(fn).lower(*args).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert any(f"%{name}" in line for line in calls), (case, calls)
