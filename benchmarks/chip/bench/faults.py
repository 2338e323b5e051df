"""Faults planted under the timed path, to see `correct` come out false.

`plant(engine, fault)` wraps the engine's jitted step:

- ``memory_unchanged``: the step returns the SAM memory state it was
  given, so no write ever lands;
- ``half_batch``: the second half of the lanes is left out: their tokens
  are the first half's and their KV cache and memory keep their state;
- ``token_altered``: lane 0's token is replaced by the next token id
  where the step produces it (it is served and fed back).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

FAULTS = ("memory_unchanged", "half_batch", "token_altered")


def plant(eng, fault: str) -> None:
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
    step = eng._step_fn

    def broken(params, cache, mem, *ins):
        old_cache = jax.tree.map(jnp.copy, cache)
        old_mem = jax.tree.map(jnp.copy, mem)
        tok, logits, cache, mem = step(params, cache, mem, *ins)
        if fault == "memory_unchanged":
            mem = old_mem
        elif fault == "half_batch":
            h = tok.shape[0] // 2
            tok = tok.at[h:].set(tok[:h])

            def keep(new, old, axis):
                return jnp.concatenate(
                    [jax.lax.slice_in_dim(new, 0, h, axis=axis),
                     jax.lax.slice_in_dim(old, h, None, axis=axis)], axis)

            cache = {k: keep(v, old_cache[k], 0 if k == "pos" else 1)
                     for k, v in cache.items()}
            mem = jax.tree.map(lambda n, o: keep(n, o, 0), mem, old_mem)
        else:
            tok = tok.at[0].set((tok[0] + 1) % logits.shape[-1])
        return tok, logits, cache, mem

    eng._step_fn = broken
