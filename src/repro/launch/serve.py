"""Serving drivers.

Two modes share this CLI:

* **static batch** (`serve`, the original driver): prefill a lockstep
  batch of prompts, then decode greedy or sampled — kept as the simple
  reference path and for throughput spot checks;
* **continuous batching** (`serve_continuous`, ``--continuous``): the
  engine package (launch/engine/) — FIFO admission over fixed lanes,
  mid-decode evict/refill, and persistent per-user memory sessions
  (docs/serving.md). `benchmarks/bench_serve.py` drives this mode under a
  Poisson arrival workload.

``mesh=`` (or ``--mesh-model N``) serves under a mesh from
`launch/mesh.py`: logical-axis rules activate for the transformer stack
and, for SAM-augmented archs, the external memory runs the mesh-native
slot-sharded path (`mem_shard.memory_mesh`, docs/sharding.md)."""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced as reduce_cfg
from repro.distributed import mem_shard
from repro.distributed.sharding import current_mesh, mesh_rules
from repro.launch import compile_cache
from repro.models import lm


def serve(arch: str, *, batch: int = 4, prompt_len: int = 32,
          gen_len: int = 32, max_len: int = 128, use_reduced: bool = True,
          seed: int = 0, greedy: bool = True, mesh=None):
    cfg = get_config(arch)
    if use_reduced:
        cfg = reduce_cfg(cfg)
    with contextlib.ExitStack() as stack:
        if mesh is not None:
            stack.enter_context(mesh_rules(mesh))
            if cfg.memory is not None:
                stack.enter_context(mem_shard.memory_mesh(
                    mesh, cfg.memory.num_slots))
        return _serve(cfg, batch=batch, prompt_len=prompt_len,
                      gen_len=gen_len, max_len=max_len, seed=seed,
                      greedy=greedy)


def _select(logits, greedy: bool, key):
    """Next-token selection for the static-batch driver: argmax, or
    temperature-1 categorical when ``greedy=False``."""
    if greedy:
        return jnp.argmax(logits[:, -1], axis=-1)
    return jax.random.categorical(key, logits[:, -1].astype(jnp.float32))


def _serve(cfg, *, batch, prompt_len, gen_len, max_len, seed, greedy=True):
    key = jax.random.PRNGKey(seed)
    params = lm.init_params(key, cfg, dtype=cfg.compute_dtype,
                            mesh=current_mesh())

    cache = lm.init_cache(cfg, batch, max_len)
    if cfg.frontend == "audio":
        prompt = jax.random.normal(key, (batch, prompt_len, cfg.d_model))
    else:
        prompt = jax.random.randint(key, (batch, prompt_len), 1,
                                    cfg.vocab_size)

    # Prefill: the whole prompt under one scanned dispatch (lm.decode_scan)
    # with the cache donated — no per-token Python round trip.
    prefill_fn = jax.jit(lambda p, c, xs: lm.decode_scan(p, cfg, c, xs),
                         donate_argnums=(1,))
    t0 = time.time()
    logits, cache = prefill_fn(params, cache, prompt)
    # JAX dispatch is async: without blocking on the result the stopwatch
    # measures enqueue time, not compute, inflating the throughput numbers.
    jax.block_until_ready(logits)
    prefill_t = time.time() - t0

    sample_key = jax.random.fold_in(key, 1)

    def decode_loop(params, cache, tok0):
        """The whole generation under one `lax.scan`: step, select, feed
        back — the same select-key schedule the per-token loop used
        (token i sampled with fold_in(sample_key, i))."""
        def body(carry, i):
            cache, tok = carry
            if cfg.frontend == "audio":
                step_in = jax.nn.one_hot(tok, cfg.d_model)[:, None]
            else:
                step_in = tok[:, None]
            logits, cache = lm.decode_step(params, cfg, cache, step_in)
            nxt = _select(logits, greedy, jax.random.fold_in(sample_key, i))
            return (cache, nxt), nxt

        (cache, _), toks = jax.lax.scan(body, (cache, tok0),
                                        jnp.arange(gen_len))
        return cache, jnp.moveaxis(toks, 0, 1)          # (B, gen_len)

    decode_fn = jax.jit(decode_loop, donate_argnums=(1,))
    tok0 = _select(logits, greedy, sample_key)
    t0 = time.time()
    cache, tokens = decode_fn(params, cache, tok0)
    jax.block_until_ready(tokens)    # same async-dispatch pitfall as above
    decode_t = time.time() - t0
    return {
        "tokens": tokens,
        "prefill_s": prefill_t,
        "decode_s": decode_t,
        "decode_tok_per_s": batch * gen_len / max(decode_t, 1e-9),
    }


def serve_continuous(arch: str, *, lanes: int = 4, requests: int = 8,
                     prompt_len: int = 8, gen_len: int = 16,
                     max_len: int = 128, use_reduced: bool = True,
                     seed: int = 0, greedy: bool = True, mesh=None):
    """Serve `requests` synthetic single-request users through the
    continuous-batching engine and report aggregate throughput and the
    engine's counters (`ServeEngine.stats`)."""
    import numpy as np
    from repro.launch.engine import Request, ServeEngine

    cfg = get_config(arch)
    if use_reduced:
        cfg = reduce_cfg(cfg)
    rng = np.random.default_rng(seed)
    with ServeEngine(cfg, lanes=lanes, max_len=max_len, param_seed=seed,
                     mesh=mesh) as eng:
        t0 = time.time()
        results = eng.run([
            Request(user=f"user{i}",
                    prompt=rng.integers(1, cfg.vocab_size,
                                        prompt_len).tolist(),
                    max_new_tokens=gen_len, greedy=greedy, sample_seed=i)
            for i in range(requests)])
        wall = time.time() - t0
        stats = dataclasses.asdict(eng.stats)
    total = sum(len(r["tokens"]) for r in results)
    return {
        "results": results,
        "wall_s": wall,
        "steps": stats["steps"],
        "stats": stats,
        "tok_per_s": total / max(wall, 1e-9),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="hymba_1_5b")
    ap.add_argument("--batch", type=int, default=4,
                    help="static-batch size / engine lane count")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--sample", action="store_true",
                    help="categorical sampling instead of argmax")
    ap.add_argument("--continuous", action="store_true",
                    help="serve through the continuous-batching engine "
                         "(launch/engine) instead of the static batch")
    ap.add_argument("--requests", type=int, default=8,
                    help="request count for --continuous")
    ap.add_argument("--mesh-model", type=int, default=0,
                    help="serve under a (data, model) mesh with this model-"
                         "parallel degree (0 = no mesh); SAM-augmented "
                         "archs then run the mesh-native memory path")
    args = ap.parse_args()
    compile_cache.enable()
    mesh = None
    if args.mesh_model:
        from repro.launch.mesh import make_memory_mesh
        mesh = make_memory_mesh(args.mesh_model)
    if args.continuous:
        res = serve_continuous(args.arch, lanes=args.batch,
                               requests=args.requests,
                               prompt_len=args.prompt_len,
                               gen_len=args.gen_len,
                               greedy=not args.sample, mesh=mesh)
        stats = " ".join(f"{k}={v}" for k, v in res["stats"].items())
        print(f"served {len(res['results'])} requests in {res['steps']} "
              f"steps; {res['tok_per_s']:.1f} tok/s; {stats}")
    else:
        res = serve(args.arch, batch=args.batch, prompt_len=args.prompt_len,
                    gen_len=args.gen_len, greedy=not args.sample, mesh=mesh)
        print(f"generated {res['tokens'].shape} tokens; "
              f"prefill {res['prefill_s']:.2f}s, "
              f"decode {res['decode_tok_per_s']:.1f} tok/s")


if __name__ == "__main__":
    main()
