"""Common layers + the ParamDef single-source-of-truth parameter system.

Every parameter is declared once as ``pdef(shape, logical_axes, init)``;
from the same declaration we derive real initialization, abstract
ShapeDtypeStructs (for the no-allocation dry-run) and the logical-axis tree
used by the sharding rules."""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: tuple
    axes: tuple                 # logical axis names, len == len(shape)
    init: str = "normal"        # normal | zeros | ones | small
    scale: Optional[float] = None

    def initialize(self, key, dtype):
        if self.init == "zeros":
            return jnp.zeros(self.shape, dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, dtype)
        fan_in = self.shape[0] if len(self.shape) > 1 else self.shape[-1]
        scale = self.scale if self.scale is not None else fan_in ** -0.5
        return (jax.random.normal(key, self.shape) * scale).astype(dtype)


def pdef(shape, axes, init="normal", scale=None) -> ParamDef:
    assert len(shape) == len(axes), (shape, axes)
    return ParamDef(tuple(shape), tuple(axes), init, scale)


def _is_def(x):
    return isinstance(x, ParamDef)


def stack_defs(defs, num: int):
    """Add a leading scanned-layers dim to every ParamDef in a tree."""
    return jax.tree.map(
        lambda d: ParamDef((num,) + d.shape, ("layers",) + d.axes, d.init,
                           d.scale),
        defs, is_leaf=_is_def)


@functools.lru_cache(maxsize=None)
def _leaf_init(d: ParamDef, dtype, sharding):
    """One leaf's jitted initializer: the f32 draw, the scale and the cast
    fuse on the device, so a bf16 leaf never exists in f32 as a whole, and
    with ``sharding`` each device makes only its own shard."""
    return jax.jit(lambda k: d.initialize(k, dtype), out_shardings=sharding)


def init_from_defs(key, defs, dtype, shardings=None):
    """Initialize every ParamDef in ``defs`` from ``key``, leaf by leaf.
    ``shardings`` (a matching tree of shardings) places each leaf as it is
    made."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=_is_def)
    keys = jax.random.split(key, len(leaves))
    shards = ([None] * len(leaves) if shardings is None
              else treedef.flatten_up_to(shardings))
    return jax.tree.unflatten(
        treedef, [_leaf_init(d, dtype, s)(k)
                  for d, k, s in zip(leaves, keys, shards)])


def abstract_from_defs(defs, dtype):
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, dtype), defs, is_leaf=_is_def)


def axes_from_defs(defs):
    return jax.tree.map(lambda d: d.axes, defs, is_leaf=_is_def)


# ----------------------------- layer math --------------------------------

def peinsum(spec, *ops):
    """einsum whose HLO dot emits the input dtype directly (TPU MXU still
    accumulates f32 internally for bf16). Without this, bf16 dots emit f32
    and GSPMD places the tensor-parallel partial-sum all-reduce *before* the
    bf16 convert — doubling collective + intermediate HBM traffic
    (§Perf A3: all-reduce volume halved fleet-wide)."""
    return jnp.einsum(spec, *ops, preferred_element_type=ops[0].dtype)


def rms_norm(x, scale, eps: float = 1e-5):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def rope(x, positions, theta: float):
    """x: (..., S, H, D) rotary embedding at `positions` (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freq       # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def mlp_defs(cfg, d_in: int, d_hidden: int, gated: bool):
    d = {"w1": pdef((d_in, d_hidden), ("embed", "ff")),
         "w2": pdef((d_hidden, d_in), ("ff", "embed"))}
    if gated:
        d["w3"] = pdef((d_in, d_hidden), ("embed", "ff"))
    return d


def mlp_apply(params, x, act: str):
    h = peinsum("bsd,df->bsf", x, params["w1"])
    h = shard(h, "batch", "seq", "ff")
    if "w3" in params:                       # gated: silu (llama) / geglu (gemma)
        gate = jax.nn.silu(h) if act == "silu" else jax.nn.gelu(h)
        h = gate * peinsum("bsd,df->bsf", x, params["w3"])
    else:
        h = jax.nn.gelu(h)
    out = peinsum("bsf,fd->bsd", h, params["w2"])
    return shard(out, "batch", "seq", "embed")


def embed_defs(cfg):
    # Dedicated logical axes: sharding the vocab dim over `model` forces the
    # SPMD partitioner into an involuntary full rematerialization on the
    # token gather (observed in the baseline dry-run). The default rules
    # shard the table's *embedding* dim instead, so gathers stay local and
    # the output lands pre-sharded on the embed axis (§Perf iteration B1).
    return {"tok": pdef((cfg.vocab_size, cfg.d_model),
                        ("vocab_table", "embed_table"), scale=1.0)}


def embed_apply(params, tokens, compute_dtype):
    out = jnp.take(params["tok"].astype(compute_dtype), tokens, axis=0)
    return shard(out, "batch", "seq", "embed")


def logits_apply(head_w, x):
    """x: (B, S, d), head_w: (d, V) -> (B, S, V)."""
    out = x @ head_w
    return shard(out, "batch", "seq", "vocab")
