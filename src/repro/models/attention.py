"""Attention for the LM zoo: chunked (flash-style) training/prefill
attention with GQA / sliding-window / prefix-LM masking, single-token decode
against a KV cache (ring-buffered for SWA), and DeepSeek-V2 MLA with both
naive and absorbed decode paths.

The chunked implementation scans over a *static pair list* of
(q_block, kv_block) tiles. Causal skipping, windows and prefix-LM all reduce
to choosing which pairs appear in the list, so the baseline (full rectangle)
and the optimized (triangular) schedule share one code path — this is the
§Perf "compute term" lever for attention-dominated shapes."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import current_mesh, shard
from repro.models.config import MLAConfig, ModelConfig
from repro.models.layers import pdef, peinsum, rope

_NEG = -1e30


def _batch_sharded_attention(cfg: ModelConfig) -> bool:
    """True when the head count cannot shard over the model axis — the
    attention core would silently replicate 16×. Re-sharding the batch over
    (pod, data, model) for the attention region trades two all-to-alls per
    layer for a model-axis-factor compute reduction (§Perf A1)."""
    mesh = current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return False
    return cfg.padded_heads % mesh.shape["model"] != 0


# --------------------------------------------------------------------------
# Parameter defs
# --------------------------------------------------------------------------

def attn_defs(cfg: ModelConfig):
    d, H, Hkv, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "wq_down": pdef((d, m.q_lora), ("embed", None)),
            "q_norm": pdef((m.q_lora,), (None,), init="zeros"),
            "wq_up": pdef((m.q_lora, H, m.nope_head_dim + m.rope_head_dim),
                          (None, "heads", "head_dim")),
            "wkv_down": pdef((d, m.kv_lora + m.rope_head_dim),
                             ("embed", "kv_lora")),
            "kv_norm": pdef((m.kv_lora,), (None,), init="zeros"),
            "wk_up": pdef((m.kv_lora, H, m.nope_head_dim),
                          ("kv_lora", "heads", "head_dim")),
            "wv_up": pdef((m.kv_lora, H, m.v_head_dim),
                          ("kv_lora", "heads", "head_dim")),
            "wo": pdef((H, m.v_head_dim, d), ("heads", "head_dim", "embed")),
        }
    Hp = cfg.padded_heads    # dead pad heads: zero-init, masked, untrained
    return {
        "wq": pdef((d, Hp, Dh), ("embed", "heads", "head_dim")),
        "wk": pdef((d, Hkv, Dh), ("embed", "kv_heads", "head_dim")),
        "wv": pdef((d, Hkv, Dh), ("embed", "kv_heads", "head_dim")),
        "wo": pdef((Hp, Dh, d), ("heads", "head_dim", "embed")),
    }


def _head_mask(cfg: ModelConfig, dtype):
    """(H_pad,) 1/0 mask of real heads; groups are padded contiguously so
    the GQA head→kv-head mapping is preserved."""
    if cfg.pad_head_groups is None:
        return None
    G = cfg.q_heads_per_kv
    Gp = cfg.pad_head_groups
    valid = (jnp.arange(Gp) < G)
    return jnp.tile(valid, cfg.num_kv_heads).astype(dtype)


# --------------------------------------------------------------------------
# Pair-list chunked attention
# --------------------------------------------------------------------------

def _pair_list(nq: int, nk: int, *, causal: bool, skip: bool,
               window_blocks: Optional[int], prefix_blocks: int):
    """Static (q_block, kv_block) schedule. Last pair of each q block flushes."""
    pairs = []
    for i in range(nq):
        for j in range(nk):
            if skip and causal and j > i:
                if j >= prefix_blocks:
                    continue
            if skip and window_blocks is not None and i - j > window_blocks \
                    and j >= prefix_blocks:
                continue
            pairs.append((i, j))
    # mark flush points (last kv block for a given q block)
    flush = [k + 1 == len(pairs) or pairs[k + 1][0] != i
             for k, (i, _) in enumerate(pairs)]
    return pairs, flush


def chunked_attention(q, k, v, *, q_block: int, kv_block: int,
                      causal: bool = True, window: Optional[int] = None,
                      prefix_len: int = 0, q_offset: int = 0,
                      causal_skip: bool = True):
    """q: (B, Sq, H, D); k, v: (B, Skv, Hkv, D) -> (B, Sq, H, D).

    Online-softmax over a static tile schedule. `q_offset` shifts query
    positions (for prefill continuation)."""
    B, Sq, H, D = q.shape
    _, Skv, Hkv, Dv = v.shape
    G = H // Hkv
    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Skv)
    assert Sq % q_block == 0 and Skv % kv_block == 0
    nq, nk = Sq // q_block, Skv // kv_block
    scale = D ** -0.5

    qb = q.reshape(B, nq, q_block, Hkv, G, D)
    kb = k.reshape(B, nk, kv_block, Hkv, D)
    vb = v.reshape(B, nk, kv_block, Hkv, Dv)

    wb = None if window is None else max(1, -(-window // kv_block))
    pairs, flush = _pair_list(nq, nk, causal=causal, skip=causal_skip,
                              window_blocks=wb,
                              prefix_blocks=-(-prefix_len // kv_block) if prefix_len else 0)
    pair_arr = jnp.asarray(pairs, jnp.int32)           # (P, 2)
    flush_arr = jnp.asarray(flush)                     # (P,)

    out = jnp.zeros((B, nq, q_block, Hkv, G, Dv), jnp.float32)
    m0 = jnp.full((B, q_block, Hkv, G), _NEG, jnp.float32)
    l0 = jnp.zeros((B, q_block, Hkv, G), jnp.float32)
    acc0 = jnp.zeros((B, q_block, Hkv, G, Dv), jnp.float32)

    def body(carry, step):
        out, m, l, acc = carry
        (qi, kj), do_flush = step
        qc = jax.lax.dynamic_index_in_dim(qb, qi, 1, keepdims=False)
        kc = jax.lax.dynamic_index_in_dim(kb, kj, 1, keepdims=False)
        vc = jax.lax.dynamic_index_in_dim(vb, kj, 1, keepdims=False)
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qc, kc,
                       preferred_element_type=jnp.float32) * scale
        pos_q = q_offset + qi * q_block + jnp.arange(q_block)
        pos_k = kj * kv_block + jnp.arange(kv_block)
        mask = jnp.ones((q_block, kv_block), bool)
        if causal:
            mask = pos_q[:, None] >= pos_k[None, :]
        if window is not None:
            mask &= (pos_q[:, None] - pos_k[None, :]) < window
        if prefix_len:
            mask |= pos_k[None, :] < prefix_len
        s = jnp.where(mask[None, :, None, None, :], s, _NEG)

        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqhgk,bkhd->bqhgd", p, vc, preferred_element_type=jnp.float32)

        norm = acc_new / jnp.maximum(l_new[..., None], 1e-20)
        prev = jax.lax.dynamic_index_in_dim(out, qi, 1, keepdims=False)
        out = jax.lax.dynamic_update_index_in_dim(
            out, jnp.where(do_flush, norm, prev), qi, 1)
        # Reset running stats after a flush (next step starts a new q block).
        m_next = jnp.where(do_flush, m0, m_new)
        l_next = jnp.where(do_flush, l0, l_new)
        acc_next = jnp.where(do_flush, acc0, acc_new)
        return (out, m_next, l_next, acc_next), None

    (out, _, _, _), _ = jax.lax.scan(body, (out, m0, l0, acc0),
                                     (pair_arr, flush_arr))
    return out.reshape(B, Sq, H, Dv).astype(q.dtype)


# --------------------------------------------------------------------------
# GQA attention (train / prefill / decode)
# --------------------------------------------------------------------------

def gqa_forward(params, cfg: ModelConfig, x, positions):
    """x: (B, S, d) -> (B, S, d). Training/prefill path."""
    q = peinsum("bsd,dhk->bshk", x, params["wq"])
    k = peinsum("bsd,dhk->bshk", x, params["wk"])
    v = peinsum("bsd,dhk->bshk", x, params["wv"])
    batch_ax = "attn_batch" if _batch_sharded_attention(cfg) else "batch"
    q = shard(q, batch_ax, "seq", "heads", None)
    k = shard(k, batch_ax, "seq", "kv_heads", None)
    v = shard(v, batch_ax, "seq", "kv_heads", None)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    o = chunked_attention(q, k, v, q_block=cfg.q_block, kv_block=cfg.kv_block,
                          causal=True, window=cfg.window,
                          prefix_len=cfg.prefix_lm,
                          causal_skip=cfg.causal_skip)
    mask = _head_mask(cfg, o.dtype)
    if mask is not None:
        o = o * mask[None, None, :, None]
    o = shard(o, batch_ax, "seq", "heads", None)
    out = peinsum("bshk,hkd->bsd", o, params["wo"])
    return shard(out, "batch", "seq", "embed")


def gqa_decode(params, cfg: ModelConfig, x, k_cache, v_cache, pos):
    """x: (B, 1, d); caches (B, Smax, Hkv, D) (ring buffer when SWA).

    ``pos`` is () for a lockstep batch, or (B,) per-lane positions — the
    continuous-batching engine (launch/engine) admits sequences mid-decode,
    so each lane runs at its own offset (its own rope phase, cache slot,
    and validity horizon); rows never mix, so a lane's output is invariant
    to its neighbours.

    Returns (out, k_cache, v_cache)."""
    B = x.shape[0]
    Smax = k_cache.shape[1]
    pos = jnp.asarray(pos)
    q = peinsum("bsd,dhk->bshk", x, params["wq"])
    k = peinsum("bsd,dhk->bshk", x, params["wk"])
    v = peinsum("bsd,dhk->bshk", x, params["wv"])
    ppos = pos[None, None] if pos.ndim == 0 else pos[:, None]
    q = rope(q, ppos, cfg.rope_theta)
    k = rope(k, ppos, cfg.rope_theta)
    slot = pos % Smax if cfg.window is not None else pos
    if pos.ndim == 0:
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k.astype(k_cache.dtype), slot, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v.astype(v_cache.dtype), slot, axis=1)
    else:
        b = jnp.arange(B)
        k_cache = k_cache.at[b, slot].set(k[:, 0].astype(k_cache.dtype))
        v_cache = v_cache.at[b, slot].set(v[:, 0].astype(v_cache.dtype))

    H, Hkv = cfg.padded_heads, cfg.num_kv_heads
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, -1)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache.astype(qg.dtype),
                   preferred_element_type=jnp.float32) * (q.shape[-1] ** -0.5)
    s = shard(s, "batch", "kv_heads", None, "kv_seq")
    idx = jnp.arange(Smax)
    if cfg.window is not None:
        valid = (idx <= slot[..., None]) | (pos[..., None] >= Smax)
    else:
        valid = idx <= pos[..., None]              # () -> (Smax); (B,) -> (B,Smax)
    valid = jnp.broadcast_to(valid, (B, Smax))
    s = jnp.where(valid[:, None, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(p.dtype),
                   preferred_element_type=jnp.float32)
    o = o.reshape(B, 1, H, -1).astype(x.dtype)
    mask = _head_mask(cfg, o.dtype)
    if mask is not None:
        o = o * mask[None, None, :, None]
    return peinsum("bshk,hkd->bsd", o, params["wo"]), k_cache, v_cache


def gqa_decode_sparse(params, cfg: ModelConfig, x, k_cache, v_cache,
                      ksum, pos):
    """SAM-style sparse top-K decode attention (beyond-paper §Perf C2).

    The paper's core insight — content-based reads need only touch the
    top-K most similar memory rows (§3.1) — applied to the KV cache: score
    the query against per-block key centroids, select the top-K blocks per
    kv head, and run exact attention over just those blocks (the current
    block is always included, mirroring SAM's always-write-recent rule).
    HBM traffic per step drops from O(S·D) to O(K·bs·D + (S/bs)·D).

    ksum: (B, nb, Hkv, D) running per-block key sums, updated incrementally.
    Returns (out, k_cache, v_cache, ksum)."""
    B = x.shape[0]
    Smax = k_cache.shape[1]
    bs = cfg.sparse_decode_block
    nb = Smax // bs
    kb = min(cfg.sparse_decode_blocks, nb)
    H, Hkv = cfg.padded_heads, cfg.num_kv_heads
    G = H // Hkv
    D = cfg.head_dim

    q = peinsum("bsd,dhk->bshk", x, params["wq"])
    k = peinsum("bsd,dhk->bshk", x, params["wk"])
    v = peinsum("bsd,dhk->bshk", x, params["wv"])
    q = rope(q, pos[None, None], cfg.rope_theta)
    k = rope(k, pos[None, None], cfg.rope_theta)
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        k_cache, k.astype(k_cache.dtype), pos, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        v_cache, v.astype(v_cache.dtype), pos, axis=1)
    # incremental centroid update for the written block
    blk = pos // bs
    upd = ksum[jnp.arange(B), blk] + k[:, 0].astype(ksum.dtype)  # (B,Hkv,D)
    ksum = ksum.at[jnp.arange(B), blk].set(upd)

    qg = q.reshape(B, Hkv, G, D)
    # block scores: sum over q-head group (shared block set per kv head)
    counts = jnp.clip(
        (pos + 1) - jnp.arange(nb) * bs, 0, bs).astype(qg.dtype)  # (nb,)
    cent = ksum.astype(qg.dtype) / jnp.maximum(counts, 1.0)[None, :, None,
                                                            None]
    bscore = jnp.einsum("bhgd,bnhd->bhn", qg, cent)               # (B,Hkv,nb)
    valid_blk = jnp.arange(nb) <= blk
    bscore = jnp.where(valid_blk[None, None, :], bscore, _NEG)
    # always include the current block
    bscore = bscore + 1e9 * (jnp.arange(nb)[None, None, :] == blk)
    _, top_blk = jax.lax.top_k(bscore, kb)                        # (B,Hkv,kb)

    # gather the selected blocks
    pos_sel = (top_blk[..., None] * bs
               + jnp.arange(bs)[None, None, None, :]).reshape(B, Hkv, kb * bs)
    bi = jnp.arange(B)[:, None, None]
    hi = jnp.arange(Hkv)[None, :, None]
    k_sel = k_cache[bi, pos_sel, hi].astype(qg.dtype)    # (B,Hkv,P,D)
    v_sel = v_cache[bi, pos_sel, hi].astype(qg.dtype)

    s = jnp.einsum("bhgd,bhpd->bhgp", qg, k_sel) * (D ** -0.5)
    ok = pos_sel <= pos
    s = jnp.where(ok[:, :, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgp,bhpd->bhgd", p, v_sel)
    o = o.reshape(B, 1, H, D).astype(x.dtype)
    mask = _head_mask(cfg, o.dtype)
    if mask is not None:
        o = o * mask[None, None, :, None]
    out = peinsum("bshk,hkd->bsd", o, params["wo"])
    return out, k_cache, v_cache, ksum


def _sparse_read_local(qg, k_loc, v_loc, ksum_loc, pos, shard_idx, *,
                       bs: int, kb_local: int, D: int):
    """Per-shard SAM-style sparse read over the local KV partition.

    Runs inside shard_map: this shard owns S_local contiguous positions
    starting at shard_idx·S_local. Selects its local top-K blocks by
    centroid score and returns flash-combinable partials (acc, m, l)."""
    B, Hkv, G, _ = qg.shape
    S_local = k_loc.shape[1]
    nb_local = S_local // bs
    start = shard_idx * S_local

    blk_global = pos // bs
    counts = jnp.clip((pos + 1) - (start + jnp.arange(nb_local) * bs),
                      0, bs).astype(qg.dtype)
    cent = ksum_loc.astype(qg.dtype) / jnp.maximum(counts, 1.0)[None, :,
                                                                None, None]
    bscore = jnp.einsum("bhgd,bnhd->bhn", qg, cent)
    local_blk_ids = start // bs + jnp.arange(nb_local)
    valid_blk = local_blk_ids <= blk_global
    bscore = jnp.where(valid_blk[None, None, :], bscore, _NEG)
    bscore = bscore + 1e9 * (local_blk_ids[None, None, :] == blk_global)
    _, top_blk = jax.lax.top_k(bscore, kb_local)            # (B,Hkv,kb)

    pos_sel = (top_blk[..., None] * bs
               + jnp.arange(bs)[None, None, None, :]).reshape(B, Hkv, -1)
    bi = jnp.arange(B)[:, None, None]
    hi = jnp.arange(Hkv)[None, :, None]
    k_sel = k_loc[bi, pos_sel, hi].astype(qg.dtype)         # local gather
    v_sel = v_loc[bi, pos_sel, hi].astype(qg.dtype)

    s = jnp.einsum("bhgd,bhpd->bhgp", qg, k_sel) * (D ** -0.5)
    ok = (start + pos_sel) <= pos
    # also mask blocks that were invalid (selected only as filler)
    blk_ok = jnp.take_along_axis(valid_blk[None, None, :], top_blk, axis=-1)
    ok = ok & jnp.repeat(blk_ok, bs, axis=-1)
    s = jnp.where(ok[:, :, None, :], s, _NEG)
    m = s.max(axis=-1)                                      # (B,Hkv,G)
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)
    acc = jnp.einsum("bhgp,bhpd->bhgd", p, v_sel)
    return acc, m, l


def gqa_decode_sparse_sharded(params, cfg: ModelConfig, x, k_cache, v_cache,
                              ksum, pos):
    """Distributed SAM-style sparse decode: the KV cache shards its sequence
    dim over `model`; each shard runs the content-based top-K search over
    its own partition (exactly how SAM's ANN shards at scale) and partial
    softmax states merge with one tiny all-reduce — no cache resharding.
    (The naive cross-shard gather version is kept for single-device tests;
    GSPMD lowers it by replicating the cache — refuted in §Perf C1.)"""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import current_mesh, logical_spec

    mesh = current_mesh()
    B = x.shape[0]
    Smax = k_cache.shape[1]
    bs = cfg.sparse_decode_block
    H, Hkv = cfg.padded_heads, cfg.num_kv_heads
    G = H // Hkv
    D = cfg.head_dim
    model_size = mesh.shape["model"]
    kb_local = max(1, cfg.sparse_decode_blocks // model_size)

    q = peinsum("bsd,dhk->bshk", x, params["wq"])
    k = peinsum("bsd,dhk->bshk", x, params["wk"])
    v = peinsum("bsd,dhk->bshk", x, params["wv"])
    q = rope(q, pos[None, None], cfg.rope_theta)
    k = rope(k, pos[None, None], cfg.rope_theta)
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        k_cache, k.astype(k_cache.dtype), pos, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        v_cache, v.astype(v_cache.dtype), pos, axis=1)
    blk = pos // bs
    upd = ksum[jnp.arange(B), blk] + k[:, 0].astype(ksum.dtype)
    ksum = ksum.at[jnp.arange(B), blk].set(upd)

    qg = q.reshape(B, Hkv, G, D)
    batch_ax = logical_spec(("batch",), (B,), mesh)[0]
    cache_spec = P(batch_ax, "model", None, None)
    q_spec = P(batch_ax, None, None, None)

    def local(qg_l, k_l, v_l, ks_l, pos_l):
        shard_idx = jax.lax.axis_index("model")
        acc, m, l = _sparse_read_local(qg_l, k_l, v_l, ks_l, pos_l,
                                       shard_idx, bs=bs, kb_local=kb_local,
                                       D=D)
        # flash-style cross-shard softmax merge (tiny collective)
        m_glob = jax.lax.pmax(m, "model")
        corr = jnp.exp(m - m_glob)
        acc = jax.lax.psum(acc * corr[..., None], "model")
        l = jax.lax.psum(l * corr, "model")
        return acc / jnp.maximum(l, 1e-20)[..., None]

    o = jax.shard_map(local, mesh=mesh,
                      in_specs=(q_spec, cache_spec, cache_spec, cache_spec,
                                P()),
                      out_specs=q_spec,
                      check_vma=False)(qg, k_cache, v_cache, ksum, pos)
    o = o.reshape(B, 1, H, D).astype(x.dtype)
    mask = _head_mask(cfg, o.dtype)
    if mask is not None:
        o = o * mask[None, None, :, None]
    out = peinsum("bshk,hkd->bsd", o, params["wo"])
    return out, k_cache, v_cache, ksum


# --------------------------------------------------------------------------
# MLA (DeepSeek-V2)
# --------------------------------------------------------------------------

def _mla_qkv(params, cfg: ModelConfig, x, positions):
    m = cfg.mla
    from repro.models.layers import rms_norm
    ql = rms_norm(x @ params["wq_down"], params["q_norm"], cfg.norm_eps)
    q = peinsum("bsl,lhk->bshk", ql, params["wq_up"])
    q_nope, q_rope = q[..., :m.nope_head_dim], q[..., m.nope_head_dim:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)

    ckv = x @ params["wkv_down"]
    c, k_rope = ckv[..., :m.kv_lora], ckv[..., m.kv_lora:]
    c = rms_norm(c, params["kv_norm"], cfg.norm_eps)
    k_rope = rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    return q_nope, q_rope, c, k_rope


def mla_forward(params, cfg: ModelConfig, x, positions):
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    q_nope, q_rope, c, k_rope = _mla_qkv(params, cfg, x, positions)
    k_nope = peinsum("bsl,lhk->bshk", c, params["wk_up"])
    v = peinsum("bsl,lhk->bshk", c, params["wv_up"])
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (B, S, H, m.rope_head_dim))], axis=-1)
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "heads", None)
    o = chunked_attention(q, k, v, q_block=cfg.q_block, kv_block=cfg.kv_block,
                          causal=True, causal_skip=cfg.causal_skip)
    out = peinsum("bshk,hkd->bsd", o, params["wo"])
    return shard(out, "batch", "seq", "embed")


def mla_decode(params, cfg: ModelConfig, x, ckv_cache, pos):
    """Absorbed MLA decode: attention runs in the (kv_lora + rope) latent
    space, the cache stores only the compressed ckv (B, Smax, kv_lora+rope).

    The naive alternative up-projects the whole cache per step — that is the
    baseline the MLA paper (and ours, §Perf) improves on."""
    m = cfg.mla
    B = x.shape[0]
    Smax = ckv_cache.shape[1]
    H = cfg.num_heads
    pos = jnp.asarray(pos)
    ppos = pos[None, None] if pos.ndim == 0 else pos[:, None]
    q_nope, q_rope, c, k_rope = _mla_qkv(params, cfg, x, ppos)
    new = jnp.concatenate([c, k_rope], axis=-1)
    if pos.ndim == 0:
        ckv_cache = jax.lax.dynamic_update_slice_in_dim(
            ckv_cache, new.astype(ckv_cache.dtype), pos, axis=1)
    else:                       # per-lane positions (continuous batching)
        ckv_cache = ckv_cache.at[jnp.arange(B), pos].set(
            new[:, 0].astype(ckv_cache.dtype))
    cache = ckv_cache.astype(x.dtype)
    c_all, kr_all = cache[..., :m.kv_lora], cache[..., m.kv_lora:]

    # Absorb: q_eff = q_nope @ wk_upᵀ  → score against the latent directly.
    q_eff = peinsum("bshk,lhk->bshl", q_nope, params["wk_up"])  # (B,1,H,L)
    s_nope = jnp.einsum("bshl,btl->bhst", q_eff, c_all,
                        preferred_element_type=jnp.float32)
    s_rope = jnp.einsum("bshk,btk->bhst", q_rope, kr_all,
                        preferred_element_type=jnp.float32)
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    s = (s_nope + s_rope) * scale
    s = shard(s, "batch", "heads", None, "kv_seq")
    valid = jnp.broadcast_to(jnp.arange(Smax) <= pos[..., None], (B, Smax))
    s = jnp.where(valid[:, None, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhst,btl->bshl", p, c_all,
                       preferred_element_type=jnp.float32).astype(x.dtype)
    o = peinsum("bshl,lhk->bshk", o_lat, params["wv_up"])
    out = peinsum("bshk,hkd->bsd", o, params["wo"])
    return out, ckv_cache
