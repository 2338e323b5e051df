"""Pallas TPU kernel: causal GQA flash attention.

The §Roofline analysis showed the XLA chunked-attention path materializes
every (q_block × kv_block) score tile to HBM — ~half the memory term of
attention-heavy train/prefill cells. This kernel keeps the running softmax
statistics and the output accumulator in VMEM scratch across the sequential
kv-block grid dimension, so score tiles never leave VMEM: HBM traffic drops
from O(S²) to O(S·D) per head (§Perf iteration A2).

Grid: (B·H, nq, nk), nk innermost (sequential). Causal blocks with
ik > iq are skipped with @pl.when — the same triangular schedule as the
jnp path's pair list. Block shapes default to (512, 512)×head_dim ≤128,
a ≤1.6 MB f32 working set per tile — comfortably inside the ~16 MB VMEM
with double-buffered k/v streams.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            q_block: int, kv_block: int, nk: int, scale: float):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    # last kv block a query block attends to (q_block and kv_block may differ)
    last_k = jnp.minimum(((iq + 1) * q_block - 1) // kv_block, nk - 1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ik <= last_k)  # causal triangular schedule
    def _compute():
        q = q_ref[0, :, :].astype(jnp.float32)           # (qb, D)
        k = k_ref[0, :, :].astype(jnp.float32)           # (kb, D)
        v = v_ref[0, :, :].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

        pos_q = iq * q_block + jax.lax.broadcasted_iota(
            jnp.int32, (q_block, kv_block), 0)
        pos_k = ik * kv_block + jax.lax.broadcasted_iota(
            jnp.int32, (q_block, kv_block), 1)
        s = jnp.where(pos_q >= pos_k, s, _NEG)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == last_k)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0, :, :] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("q_block", "kv_block",
                                             "interpret"))
def flash_attention(q, k, v, *, q_block: int = 512, kv_block: int = 512,
                    interpret: bool = False):
    """Causal GQA flash attention.

    q: (B, S, H, D); k, v: (B, S, Hkv, D) -> (B, S, H, D)."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    q_block = min(q_block, S)
    kv_block = min(kv_block, S)
    assert S % q_block == 0 and S % kv_block == 0
    nq, nk = S // q_block, S // kv_block
    scale = D ** -0.5

    qf = jnp.moveaxis(q, 2, 1).reshape(B * H, S, D)
    kf = jnp.moveaxis(k, 2, 1).reshape(B * Hkv, S, D)
    vf = jnp.moveaxis(v, 2, 1).reshape(B * Hkv, S, D)

    def kv_index(bh, iq, ik):
        b = bh // H
        h = bh % H
        return (b * Hkv + h // G, ik, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, q_block=q_block, kv_block=kv_block,
                          nk=nk, scale=scale),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, q_block, D), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, kv_block, D), kv_index),
            pl.BlockSpec((1, kv_block, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, q_block, D),
                               lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)
    return jnp.moveaxis(out.reshape(B, H, S, D), 1, 2)
