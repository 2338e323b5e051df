"""End-to-end LM training driver with fault tolerance.

Usage (examples/quickstart.py wraps this):
    PYTHONPATH=src python -m repro.launch.train --arch yi_34b --reduced \
        --steps 50 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, reduced as reduce_cfg
from repro.data.tokens import lm_token_batches
from repro.distributed.fault_tolerance import ResilientLoop
from repro.distributed.sharding import mesh_rules
from repro.launch import compile_cache
from repro.launch.steps import make_train_step
from repro.launch.specs import concrete_batch
from repro.models import lm
from repro.optim import optimizers as opt


def train(arch: str, *, steps: int = 50, batch: int = 8, seq: int = 256,
          lr: float = 3e-4, use_reduced: bool = True, ckpt_dir: str = None,
          ckpt_every: int = 20, mesh=None, log_every: int = 10,
          seed: int = 0, accum: int = 1):
    cfg = get_config(arch)
    if use_reduced:
        cfg = reduce_cfg(cfg)
    key = jax.random.PRNGKey(seed)
    params = lm.init_params(key, cfg)
    opt_state = opt.adamw_init(params)
    step_fn = make_train_step(cfg, lr=lr, accum=accum, total_steps=steps)

    ctx = mesh_rules(mesh) if mesh is not None else _null_ctx()
    with ctx:
        jitted = jax.jit(step_fn, donate_argnums=(0, 1))

        def wrapped(state, batch_):
            params, opt_state = state
            params, opt_state, metrics = jitted(params, opt_state, batch_)
            return (params, opt_state), metrics

        if cfg.frontend is None:
            gen = lm_token_batches(cfg.vocab_size, batch, seq)
            batches = (jax.tree.map(jax.numpy.asarray, b)
                       for b, _ in gen)
        else:
            def _gen():
                k = key
                while True:
                    k, sub = jax.random.split(k)
                    yield concrete_batch(sub, cfg, batch, seq)
            batches = _gen()

        state = (params, opt_state)
        if ckpt_dir:
            loop = ResilientLoop(wrapped, ckpt_dir, ckpt_every=ckpt_every)
            state, start = loop.restore_or(state)
            state, log = loop.run(state, batches, start, steps,
                                  log_every=log_every)
            return state, log
        log = []
        t0 = time.time()
        for i in range(steps):
            state, metrics = wrapped(state, next(batches))
            if i % log_every == 0:
                m = {k: float(v) for k, v in metrics.items()}
                log.append((i, m))
                print(f"step {i:5d} loss={m['loss']:.4f} "
                      f"lr={m['lr']:.2e} ({time.time()-t0:.1f}s)")
        return state, log


class _null_ctx:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi_34b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--full", action="store_true",
                    help="use the full published config (needs a pod!)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--accum", type=int, default=1)
    args = ap.parse_args()
    compile_cache.enable()
    train(args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
          lr=args.lr, use_reduced=not args.full, ckpt_dir=args.ckpt_dir,
          accum=args.accum)


if __name__ == "__main__":
    main()
