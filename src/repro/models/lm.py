"""Top-level LM: embeddings → scanned blocks (± SAM memory layers) → loss,
plus prefill/decode for serving. One implementation drives all 10 assigned
architectures (config-selected)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import named_sharding, shard
from repro.models import sam_layer
from repro.models import transformer as tfm
from repro.models.config import ModelConfig
from repro.models.layers import (ParamDef, abstract_from_defs,
                                 axes_from_defs, embed_apply, embed_defs,
                                 init_from_defs, pdef, rms_norm, stack_defs)

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


# --------------------------------------------------------------------------
# Parameter tree
# --------------------------------------------------------------------------

def _n_dense_layers(cfg: ModelConfig) -> int:
    return cfg.moe.num_dense_layers if cfg.moe is not None else 0


def param_defs(cfg: ModelConfig):
    n_dense = _n_dense_layers(cfg)
    n_scan = cfg.num_layers - n_dense
    defs = {
        "embed": embed_defs(cfg),
        "blocks": stack_defs(tfm.block_defs(cfg), n_scan),
        "final_norm": pdef((cfg.d_model,), (None,), init="zeros"),
    }
    if n_dense:
        defs["dense_blocks"] = stack_defs(
            tfm.block_defs(cfg, moe_layer=False), n_dense)
    if not cfg.tie_embeddings:
        defs["lm_head"] = pdef((cfg.d_model, cfg.vocab_size),
                               ("embed", "vocab"))
    if cfg.memory is not None:
        n_groups = max(1, cfg.num_layers // cfg.memory.every_n_layers)
        defs["memory"] = stack_defs(sam_layer.memory_defs(cfg), n_groups)
    return defs


def init_params(key, cfg: ModelConfig, *, dtype: Optional[str] = None,
                mesh=None):
    """Random weights from ``key``, stored in ``dtype`` (default
    ``cfg.param_dtype``). Serving passes the compute dtype: a model that
    keeps no optimizer state holds no f32 copy and casts nothing per step.
    ``mesh`` places every leaf by its logical axes as it is made."""
    defs = param_defs(cfg)
    shardings = None
    if mesh is not None:
        shardings = jax.tree.map(
            lambda d: named_sharding(mesh, d.axes, d.shape), defs,
            is_leaf=lambda x: isinstance(x, ParamDef))
    return init_from_defs(key, defs, _DTYPES[dtype or cfg.param_dtype],
                          shardings)


def abstract_params(cfg: ModelConfig):
    return abstract_from_defs(param_defs(cfg), _DTYPES[cfg.param_dtype])


def param_axes(cfg: ModelConfig):
    return axes_from_defs(param_defs(cfg))


def _cast(params, cfg: ModelConfig):
    cd = _DTYPES[cfg.compute_dtype]
    return jax.tree.map(
        lambda x: x.astype(cd) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        params)


# --------------------------------------------------------------------------
# Forward (training / prefill)
# --------------------------------------------------------------------------

def _embed_inputs(params, cfg: ModelConfig, batch):
    """Token + (stubbed) modality-frontend embeddings -> (B, S, d), positions."""
    cd = _DTYPES[cfg.compute_dtype]
    parts = []
    if cfg.frontend == "audio":
        # EnCodec frame embeddings provided by the (stubbed) frontend.
        parts.append(batch["frame_embeds"].astype(cd))
    else:
        if cfg.frontend == "vision" and cfg.frontend_len:
            parts.append(batch["patch_embeds"].astype(cd))
        parts.append(embed_apply(params["embed"], batch["tokens"], cd)
                     * (cfg.d_model ** 0.5))
    x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    x = shard(x, "batch", "seq", "embed")
    positions = jnp.arange(x.shape[1])[None, :]
    return x, positions


def _scan_blocks(params, cfg: ModelConfig, x, positions):
    """Scan the stacked blocks; returns (x, total_aux)."""
    n_dense = _n_dense_layers(cfg)

    def run_stack(x, stacked, moe_layer):
        def body(carry, layer_params):
            h, aux = carry
            blk = functools.partial(tfm.block_forward, cfg=cfg,
                                    positions=positions, moe_layer=moe_layer)
            if cfg.remat:
                rem = jax.checkpoint(
                    lambda p, hh: blk(p, x=hh),
                    policy=jax.checkpoint_policies.nothing_saveable)
                h, a = rem(layer_params, h)
            else:
                h, a = blk(layer_params, x=h)
            return (h, aux + a), None
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   stacked)
        return x, aux

    aux_total = jnp.zeros((), jnp.float32)
    if n_dense:
        x, aux = run_stack(x, _cast(params["dense_blocks"], cfg), False)
        aux_total += aux

    if cfg.memory is None:
        x, aux = run_stack(x, _cast(params["blocks"], cfg), None)
        aux_total += aux
        return x, aux_total

    # SAM-augmented: split the stack into groups, one memory access per group.
    n_scan = cfg.num_layers - n_dense
    n_groups = max(1, cfg.num_layers // cfg.memory.every_n_layers)
    per = n_scan // n_groups
    mem_state = sam_layer.init_memory_state(cfg, x.shape[0])
    blocks = _cast(params["blocks"], cfg)
    mem_params = _cast(params["memory"], cfg)
    for g in range(n_groups):
        sl = jax.tree.map(
            lambda t: jax.lax.slice_in_dim(t, g * per, (g + 1) * per, axis=0),
            blocks)
        x, aux = run_stack(x, sl, None)
        aux_total += aux
        mp = jax.tree.map(lambda t: t[g], mem_params)
        # Segment length + unroll mode come from cfg.memory: the group loop
        # trains through the sparse-rollback engine (core/unroll.py).
        x, mem_state = sam_layer.memory_layer_seq(mp, cfg, x, mem_state)
    return x, aux_total


def forward(params, cfg: ModelConfig, batch):
    """Returns final-layer hidden states (B, S, d) and aux loss."""
    x, positions = _embed_inputs(params, cfg, batch)
    x, aux = _scan_blocks(params, cfg, x, positions)
    x = rms_norm(x, _cast(params["final_norm"], cfg), cfg.norm_eps)
    return x, aux


def _head_weight(params, cfg: ModelConfig):
    cd = _DTYPES[cfg.compute_dtype]
    if cfg.tie_embeddings:
        return params["embed"]["tok"].astype(cd).T
    return params["lm_head"].astype(cd)


def chunked_ce(head_w, hidden, targets, mask, chunk: int):
    """Cross-entropy without materializing full (B, S, V) logits."""
    B, S, d = hidden.shape
    chunk = min(chunk, S)
    if S % chunk:                    # pad to a chunk multiple, mask the tail
        pad = chunk - S % chunk
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
        S += pad
    n = S // chunk
    h = jnp.moveaxis(hidden.reshape(B, n, chunk, d), 1, 0)
    t = jnp.moveaxis(targets.reshape(B, n, chunk), 1, 0)
    m = jnp.moveaxis(mask.reshape(B, n, chunk), 1, 0)

    def body(carry, xs):
        tot, cnt = carry
        hc, tc, mc = xs
        logits = (hc @ head_w).astype(jnp.float32)
        logits = shard(logits, "batch", "seq", "vocab")
        lse = jax.nn.logsumexp(logits, axis=-1)
        b = jnp.arange(B)[:, None]
        s = jnp.arange(chunk)[None, :]
        picked = logits[b, s, tc]
        ce = (lse - picked) * mc
        return (tot + ce.sum(), cnt + mc.sum()), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32),
                                        jnp.zeros((), jnp.float32)),
                                 (h, t, m))
    return tot / jnp.maximum(cnt, 1.0)


def loss_fn(params, cfg: ModelConfig, batch):
    hidden, aux = forward(params, cfg, batch)
    targets = batch["targets"]
    S_t = targets.shape[1]
    hidden = hidden[:, -S_t:]          # frontend prefix predicts nothing
    mask = batch.get("mask", jnp.ones_like(targets, jnp.float32))
    ce = chunked_ce(_head_weight(params, cfg), hidden, targets, mask,
                    cfg.loss_chunk)
    return ce + aux, {"ce": ce, "aux": aux}


# --------------------------------------------------------------------------
# Serving: prefill + decode
# --------------------------------------------------------------------------

def cache_shapes(cfg: ModelConfig, batch: int, max_len: int):
    per_layer = tfm.layer_cache_shapes(cfg, batch, max_len)
    return {k: (cfg.num_layers,) + v for k, v in per_layer.items()}


def cache_axes(cfg: ModelConfig):
    per_layer = tfm.cache_logical_axes(cfg)
    return {**{k: ("layers",) + v for k, v in per_layer.items()},
            "pos": ()}


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               per_lane_pos: bool = False):
    """``per_lane_pos=True`` carries ``pos`` as a (B,) vector instead of a
    scalar — the continuous-batching engine (launch/engine) admits lanes
    mid-decode, so every lane runs at its own position."""
    cd = _DTYPES[cfg.compute_dtype]
    shapes = cache_shapes(cfg, batch, max_len)
    cache = {k: jnp.zeros(v, jnp.float32 if k in ("wkv", "ssm") else cd)
             for k, v in shapes.items()}
    cache["pos"] = jnp.zeros((batch,) if per_lane_pos else (), jnp.int32)
    return cache


def init_memory_states(cfg: ModelConfig, batch: int, *,
                       per_lane_step: bool = False):
    """Per-group decode-time memory: a tuple of `sam_layer.MemoryState`
    (one per memory group, matching the stacked ``params['memory']``).

    ``per_lane_step=True`` carries the SAM step counter as a (B, 1) vector
    so every lane stamps usage with its *own* session step — a session
    evicted and later restored into a different lane (launch/engine) then
    reproduces the uninterrupted run's usage table bit-for-bit. Every
    kernel backend's fused write stamps the vector step per batch row,
    so per-lane serving runs on any backend."""
    if cfg.memory is None:
        return None
    n_groups = max(1, cfg.num_layers // cfg.memory.every_n_layers)
    states = []
    for _ in range(n_groups):
        st = sam_layer.init_memory_state(cfg, batch)
        if per_lane_step:
            st = st._replace(step=jnp.zeros((batch, 1), jnp.int32))
        states.append(st)
    return tuple(states)


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int):
    cd = _DTYPES[cfg.compute_dtype]
    shapes = cache_shapes(cfg, batch, max_len)
    out = {k: jax.ShapeDtypeStruct(
        v, jnp.float32 if k in ("wkv", "ssm") else cd)
        for k, v in shapes.items()}
    out["pos"] = jax.ShapeDtypeStruct((), jnp.int32)
    return out


def decode_step(params, cfg: ModelConfig, cache, tokens, mem_states=None):
    """tokens: (B, 1) int32 (or (B, 1, d) frame embeds for audio frontends).

    ``cache['pos']`` is () for a lockstep batch or (B,) per-lane positions
    (continuous batching — launch/engine). ``mem_states`` (a tuple of
    per-group `sam_layer.MemoryState`, see `init_memory_states`) enables
    SAM-augmented decode: the scanned stack splits into memory groups
    exactly like the training forward (`_scan_blocks`), and after each
    group's blocks the token's hidden state performs one SAM read+write
    (decode segment = 1 token). Every memory op is per-batch-row, so a
    lane's memory trajectory is independent of its neighbours — the
    property the serving engine's evict/restore determinism rests on.

    Returns (logits (B, 1, V), new_cache) — plus new_mem_states when
    ``mem_states`` was given."""
    cd = _DTYPES[cfg.compute_dtype]
    pos = cache["pos"]
    if jnp.ndim(pos) and cfg.sparse_decode_blocks is not None:
        raise NotImplementedError(
            "per-lane decode positions are not supported with "
            "sparse_decode_blocks (the block-centroid ring assumes a "
            "lockstep position)")
    if cfg.frontend == "audio":
        x = tokens.astype(cd)
    else:
        x = embed_apply(params["embed"], tokens, cd) * (cfg.d_model ** 0.5)
    x = shard(x, "batch", None, "embed")

    n_dense = _n_dense_layers(cfg)
    layer_cache = {k: v for k, v in cache.items() if k != "pos"}

    def body(x, xs):
        layer_params, cache_l = xs
        x, new_cache_l = tfm.block_decode(layer_params, cfg, x, cache_l, pos)
        return x, new_cache_l

    blocks = _cast(params["blocks"], cfg)
    if n_dense:
        # Dense leading layers consume the first cache slices.
        dense_cache = jax.tree.map(lambda t: t[:n_dense], layer_cache)
        scan_cache = jax.tree.map(lambda t: t[n_dense:], layer_cache)
        db = _cast(params["dense_blocks"], cfg)
        for i in range(n_dense):
            dp = jax.tree.map(lambda t: t[i], db)
            dc = jax.tree.map(lambda t: t[i], dense_cache)
            x, nc = tfm.block_decode(dp, cfg, x, dc, pos, moe_layer=False)
            dense_cache = jax.tree.map(
                lambda full, new: full.at[i].set(new), dense_cache, nc)
    else:
        dense_cache = None
        scan_cache = layer_cache

    new_mem = None
    if mem_states is not None:
        if cfg.memory is None:
            raise ValueError("mem_states passed but cfg.memory is None")
        n_scan = cfg.num_layers - n_dense
        n_groups = len(mem_states)
        per = n_scan // n_groups
        mem_params = _cast(params["memory"], cfg)

        # A group's layers are indexed out of the full stacks inside the
        # loop, and the cache is updated in place: a static slice of the
        # stacked weights per group would copy them (on a 16 GB chip the
        # copies of a 4B model's weights do not fit beside the weights).
        def layer_body(carry, i):
            x, cache_all = carry
            at = lambda t: jax.lax.dynamic_index_in_dim(t, i, 0, False)  # noqa: E731
            x, new_l = tfm.block_decode(jax.tree.map(at, blocks), cfg, x,
                                        jax.tree.map(at, cache_all), pos)
            cache_all = jax.tree.map(
                lambda t, n: jax.lax.dynamic_update_index_in_dim(t, n, i, 0),
                cache_all, new_l)
            return (x, cache_all), None

        new_mem = []
        new_scan_cache = scan_cache
        for g in range(n_groups):
            (x, new_scan_cache), _ = jax.lax.scan(
                layer_body, (x, new_scan_cache),
                jnp.arange(g * per, (g + 1) * per))
            mp = jax.tree.map(lambda t: t[g], mem_params)
            st, out = sam_layer.memory_access(mp, cfg, x[:, 0],
                                              mem_states[g])
            new_mem.append(st)
            x = x + out[:, None, :].astype(x.dtype)
    else:
        x, new_scan_cache = jax.lax.scan(body, x, (blocks, scan_cache))

    if dense_cache is not None:
        new_cache = jax.tree.map(
            lambda a, b: jnp.concatenate([a, b], axis=0),
            dense_cache, new_scan_cache)
    else:
        new_cache = new_scan_cache

    x = rms_norm(x, _cast(params["final_norm"], cfg), cfg.norm_eps)
    logits = x @ _head_weight(params, cfg)
    logits = shard(logits, "batch", None, "vocab")
    new_cache["pos"] = pos + 1
    if mem_states is not None:
        return logits, new_cache, tuple(new_mem)
    return logits, new_cache


def decode_scan(params, cfg: ModelConfig, cache, tokens, mem_states=None):
    """Consume T tokens under **one** `lax.scan` of `decode_step` — one XLA
    dispatch for the whole stretch instead of one Python dispatch per
    token. tokens: (B, T) int32, or (B, T, d) frame embeds for audio
    frontends. Callers jit this with the cache (and memory states) donated
    so the scan carry updates in place.

    Returns (logits (B, 1, V) of the *last* position, new_cache) — plus
    new_mem_states when ``mem_states`` was given. Numerics are the scanned
    composition of `decode_step`, so per-lane positions / per-lane memory
    steps ride through untouched (the serving engine scans prefill
    stretches with this; `launch/serve.py` scans whole generations)."""
    B = tokens.shape[0]
    xs = jnp.moveaxis(tokens, 1, 0)
    xs = xs[:, :, None] if xs.ndim == 2 else xs[:, :, None, :]
    logits0 = jnp.zeros((B, 1, cfg.vocab_size), _DTYPES[cfg.compute_dtype])

    def body(carry, x):
        cache, mem, _ = carry
        if mem is None:
            logits, cache = decode_step(params, cfg, cache, x)
        else:
            logits, cache, mem = decode_step(params, cfg, cache, x,
                                             mem_states=mem)
        return (cache, mem, logits), None

    (cache, mem, logits), _ = jax.lax.scan(
        body, (cache, mem_states, logits0), xs)
    if mem_states is not None:
        return logits, cache, mem
    return logits, cache


def prefill(params, cfg: ModelConfig, batch, max_len: Optional[int] = None):
    """Run the full-sequence forward and (for roofline purposes) return the
    last-position logits. Cache population for chunked prefill→decode
    handoff is exercised in tests at small scale via repeated decode_step."""
    hidden, _ = forward(params, cfg, batch)
    logits = hidden[:, -1:] @ _head_weight(params, cfg)
    return shard(logits, "batch", None, "vocab")
