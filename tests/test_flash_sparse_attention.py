"""Flash-attention Pallas kernel (interpret mode) and SAM-style sparse
top-K block decode: correctness vs dense references."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.models.attention import (attn_defs, gqa_decode, gqa_decode_sparse)
from repro.models.config import ModelConfig
from repro.models.layers import init_from_defs


def naive(q, k, v):
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, S, Hkv, G, D)
    s = jnp.einsum("bqhgd,bkhd->bqhgk", qg, k) * D ** -0.5
    pos = jnp.arange(S)
    s = jnp.where((pos[:, None] >= pos[None, :])[None, :, None, None, :],
                  s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bqhgk,bkhd->bqhgd", p, v).reshape(B, S, H, D)


@pytest.mark.parametrize("B,S,H,Hkv,D,qb,kb", [
    (1, 64, 2, 1, 16, 16, 16),
    (2, 128, 4, 2, 32, 32, 64),
    (1, 128, 8, 8, 16, 64, 32),
])
def test_flash_attention_sweep(B, S, H, Hkv, D, qb, kb, rng_key):
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    out = flash_attention(q, k, v, q_block=qb, kv_block=kb, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(naive(q, k, v)),
                               atol=2e-5)


def test_flash_attention_bf16(rng_key):
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (1, 64, 2, 16)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 64, 2, 16)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 64, 2, 16)).astype(jnp.bfloat16)
    out = flash_attention(q, k, v, q_block=32, kv_block=32, interpret=True)
    ref = naive(q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), atol=5e-2)


def _cfg(**kw):
    return ModelConfig(name="t", num_layers=1, d_model=32, num_heads=4,
                       num_kv_heads=2, head_dim=8, d_ff=64, vocab_size=64,
                       **kw)


def test_sparse_decode_full_blocks_equals_dense(rng_key):
    cfg = _cfg(sparse_decode_blocks=4, sparse_decode_block=4)
    params = init_from_defs(rng_key, attn_defs(cfg), jnp.float32)
    B, S = 2, 16
    x = jax.random.normal(rng_key, (B, S, 32))
    kc = jnp.zeros((B, S, 2, 8)); vc = jnp.zeros_like(kc)
    kc2 = jnp.zeros_like(kc); vc2 = jnp.zeros_like(kc)
    ksum = jnp.zeros((B, 4, 2, 8))
    for t in range(S):
        o1, kc, vc = gqa_decode(params, cfg, x[:, t:t + 1], kc, vc,
                                jnp.int32(t))
        o2, kc2, vc2, ksum = gqa_decode_sparse(
            params, cfg, x[:, t:t + 1], kc2, vc2, ksum, jnp.int32(t))
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


def test_sparse_decode_selects_relevant_block(rng_key):
    """With K=1 extra block, the query must attend to the block whose keys
    match it — SAM's content-addressing property on the KV cache."""
    cfg = _cfg(sparse_decode_blocks=2, sparse_decode_block=4,
               rope_theta=1e9)      # ~no rotation, keep content similarity
    params = init_from_defs(rng_key, attn_defs(cfg), jnp.float32)
    B, S = 1, 16
    x = jax.random.normal(rng_key, (B, S, 32))
    kc = jnp.zeros((B, S, 2, 8)); vc = jnp.zeros_like(kc)
    ksum = jnp.zeros((B, 4, 2, 8))
    outs = []
    for t in range(S):
        o, kc, vc, ksum = gqa_decode_sparse(
            params, cfg, x[:, t:t + 1], kc, vc, ksum, jnp.int32(t))
        outs.append(o)
    assert all(bool(jnp.isfinite(o).all()) for o in outs)


def test_lm_decode_with_sparse_blocks(rng_key):
    """End-to-end decode_step with the sparse-decode cache entry."""
    from repro.configs import get_config, reduced
    from repro.models import lm
    cfg = dataclasses.replace(reduced(get_config("yi_34b")),
                              sparse_decode_blocks=2,
                              sparse_decode_block=8)
    params = lm.init_params(rng_key, cfg)
    cache = lm.init_cache(cfg, 2, 32)
    assert "ksum" in cache
    logits, cache = lm.decode_step(params, cfg, cache,
                                   jnp.ones((2, 1), jnp.int32))
    assert bool(jnp.isfinite(logits).all())
