"""Seeded weights of the SAM-augmented decoder, made by the benchmark.

The harness hands these to the program under test and the plain reference
(`reference/model.py`) makes the same values again from the seed, one layer
at a time, so the reference takes no weight from the program. Every leaf is
a normal draw from ``fold_in(fold_in(key(seed), leaf), index)`` times the
leaf's scale, rounded to the served dtype; a stacked leaf draws each layer
(or memory group) from its own index, so one layer can be made alone.

`LEAVES` names every leaf of the served parameter tree by its path, with
its shape, its stacking and its scale. Nothing here imports the program.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp


def leaves(model: dict, memory: dict) -> dict:
    """path -> (stack, per-index shape, scale), where ``stack`` is
    ``"layers"``, ``"groups"`` or None (one unstacked leaf)."""
    d, H, Hkv, D = (model["d_model"], model["num_heads"],
                    model["num_kv_heads"], model["head_dim"])
    F, V = model["d_ff"], model["vocab_size"]
    M, W = memory["num_heads"], memory["word_size"]
    return {
        "embed/tok": (None, (V, d), 1.0),
        "blocks/ln1": ("layers", (d,), 0.1),
        "blocks/ln2": ("layers", (d,), 0.1),
        "blocks/attn/wq": ("layers", (d, H, D), d ** -0.5),
        "blocks/attn/wk": ("layers", (d, Hkv, D), d ** -0.5),
        "blocks/attn/wv": ("layers", (d, Hkv, D), d ** -0.5),
        "blocks/attn/wo": ("layers", (H, D, d), (H * D) ** -0.5),
        "blocks/mlp/w1": ("layers", (d, F), d ** -0.5),
        "blocks/mlp/w3": ("layers", (d, F), d ** -0.5),
        "blocks/mlp/w2": ("layers", (F, d), F ** -0.5),
        "final_norm": (None, (d,), 0.1),
        "lm_head": (None, (d, V), d ** -0.5),
        "memory/wq": ("groups", (d, M, W), d ** -0.5),
        "memory/wa": ("groups", (d, M, W), d ** -0.5),
        # The program's init draws the read-out at 0.02, which at the served
        # width (H·W = 512) makes the read about 0.45 of the residual's
        # scale; this keeps that share at every width.
        "memory/wr": ("groups", (M, W, d), 0.02 * (512 / (M * W)) ** 0.5),
        # The residual stream the memory reads is not normalised (its rows
        # have a norm near d); 1/d keeps the gate logits near unit scale.
        "memory/gates": ("groups", (d, M, 3), 1.0 / d),
    }


def num_groups(model: dict, memory: dict) -> int:
    return max(1, model["num_layers"] // memory["every_n_layers"])


def stack_size(stack, model: dict, memory: dict) -> int:
    return model["num_layers"] if stack == "layers" else \
        num_groups(model, memory)


def seed_words(seed: int):
    """The seed as two 32-bit words: seeds may pass what 32 bits hold."""
    return (jnp.uint32(seed % 2 ** 32), jnp.uint32((seed >> 32) % 2 ** 32))


def _leaf_key(words, path: str):
    lo, hi = words
    base = jax.random.fold_in(jax.random.key(lo), hi)
    return jax.random.fold_in(base, zlib.crc32(path.encode()))


@functools.partial(jax.jit, static_argnames=("shape", "scale", "dtype"))
def _draw(key, index, *, shape, scale, dtype):
    x = jax.random.normal(jax.random.fold_in(key, index), shape, jnp.float32)
    return (x * scale).astype(dtype)


def one(seed: int, path: str, spec, index: int = 0, dtype=jnp.bfloat16):
    """One leaf, or one layer / group of a stacked leaf."""
    _, shape, scale = spec
    return _draw(_leaf_key(seed_words(seed), path), index, shape=shape,
                 scale=scale, dtype=dtype)


def tree(words, model: dict, memory: dict, dtype=jnp.bfloat16):
    """Every leaf, stacked ones with their leading index, as a flat
    path -> array dict, from the seed's `seed_words`. Meant to run under
    one `jax.jit`, with the words traced so that one program serves every
    seed."""
    out = {}
    for path, spec in leaves(model, memory).items():
        stack, shape, scale = spec
        key = _leaf_key(words, path)
        if stack is None:
            out[path] = _draw(key, 0, shape=shape, scale=scale, dtype=dtype)
        else:
            n = stack_size(stack, model, memory)
            out[path] = jax.vmap(
                lambda i, key=key, shape=shape, scale=scale: _draw(
                    key, i, shape=shape, scale=scale, dtype=dtype))(
                jnp.arange(n))
    return out
