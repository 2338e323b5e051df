#!/usr/bin/env python3
"""Smoke test of the served SAM-augmented LM on a TPU, end to end.

    python chip_smoke.py               # one chip: phases (a), (b), (c)
    python chip_smoke.py --chips 4     # four chips: the slot-sharded path only

One process runs every phase: a chip belongs to one process at a time.

(a) Device: platform, kind and count as JAX reports them. Without a TPU
    the script exits non-zero and prints no result.
(b) Kernel parity at the served shapes (B=8 lanes, H=4, N=65,536 + 1
    scratch row, W=128, K=8): one SAM write then read — `lra_topn`,
    `sparse_write_update`, `fused_read` — on the compiled Pallas kernels
    against the jnp oracle at "highest" matmul precision (on a TPU the
    default f32 matmul runs bf16 passes, and near-ties would then pick
    other slots). Indices and usage must match exactly, floats within
    ATOL + RTOL·|oracle|.
(c) The engine at full width: `ServeEngine` over the full
    `h2o_danube_3_4b_sam` config (24 layers, d=3840, 6 memory groups of
    65,536 x 128), random weights from ``--seed``, 8 lanes, 12 requests of
    64 prompt + 32 new tokens, greedy and sampled, 4 of them returning
    users whose sessions are evicted and restored. The compiled step must
    dispatch the served kernels as TPU custom calls under their names.

With ``--chips 4`` the engine runs under `make_memory_mesh(4)` (memory
slot-sharded over the four chips, weights placed by their logical axes)
and the memory write+read on the slot-sharded layout is compared with the
single-device result for the same inputs.

``--rehearse`` runs the same phases on the CPU at a tiny size with the
Pallas interpreter (``JAX_PLATFORMS=cpu``; add
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` for ``--chips 4``).

The last line of stdout is ``{"ok": true, "device": {...}}``; any failed
check raises and exits non-zero.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import time
from collections import Counter

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "h2o_danube_3_4b_sam"
LANES, MAX_LEN = 8, 512
# Kernels the compiled engine step must dispatch. The slot-sharded path
# sweeps with `topk_read` per shard (the fused read has no mesh route).
SERVED_KERNELS = ("fused_read_sweep", "lra_topn", "sparse_write_update")
SHARDED_KERNELS = ("topk_read", "lra_topn", "sparse_write_update")
# Float tolerance of the kernel-vs-oracle and sharded-vs-single checks.
ATOL, RTOL = 1e-4, 1e-3


def log(msg: str) -> None:
    print(msg, flush=True)


class Failures(list):
    def check(self, ok: bool, what: str) -> None:
        log(f"  {'ok  ' if ok else 'FAIL'} {what}")
        if not ok:
            self.append(what)

    def raise_if_any(self, phase: str) -> None:
        if self:
            raise SystemExit(f"chip_smoke: {phase} failed: {list(self)}")


# ---------------------------------------------------------------- (a) ---

def device_info(jax, chips: int, rehearse: bool) -> dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu" and not rehearse:
        raise SystemExit(f"chip_smoke: no TPU found; JAX sees "
                         f"{info['count']} {info['platform']} device(s)")
    if info["count"] < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} "
                         f"devices, JAX sees {info['count']}")
    log(f"(a) device: {info}")
    return info


# ---------------------------------------------------------------- (b) ---

def memory_inputs(m, batch: int, seed: int):
    """One SAM step's operands, made from ``seed`` on the device: memory
    (B, N+1, W) with a zero scratch row, a usage table full of ties (and
    the scratch entry pinned), previous read slots, write weights on both
    sides of delta, write words, queries and key strengths."""
    import jax
    import jax.numpy as jnp
    from repro.core.types import LA_SCRATCH

    N, W, H, K = m.num_slots, m.word_size, m.num_heads, m.k
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    mem = jax.random.normal(ks[0], (batch, N + 1, W), jnp.float32)
    la = jax.random.randint(ks[1], (batch, N + 1), 0, 1000, jnp.int32)
    read_idx = jax.random.randint(ks[2], (batch, H, K), 0, N, jnp.int32)
    return dict(
        mem=mem.at[:, N].set(0.0),
        la=la.at[:, N].set(LA_SCRATCH),
        # The last slot, beside the scratch row, and a duplicate.
        read_idx=read_idx.at[:, 0, 0].set(N - 1).at[:, 1, 0].set(N - 1),
        ww=jax.random.uniform(ks[3], (batch, H * (K + 1)), maxval=4 * m.delta),
        a=jax.random.normal(ks[4], (batch, H, W)),
        q=jax.random.normal(ks[5], (batch, H, W)),
        beta=1.0 + 9.0 * jax.random.uniform(ks[6], (batch, H)),
        step=1000 + jnp.arange(batch, dtype=jnp.int32)[:, None])


def memory_step(mem, la, read_idx, ww, a, q, beta, step, *, backend,
                num_slots: int, delta: float):
    """One SAM write then read through the memory ops the served layer
    calls (`sam_layer.memory_access`): LRA slots, the fused write to
    {previously read ∪ LRA}, then the exact top-K read. The layout (and so
    the single-device kernels or the slot-sharded route) follows the
    buffer's row count, as in the served path."""
    import jax.numpy as jnp
    from repro.core import addressing as addr
    from repro.distributed import mem_shard

    B, H, K = read_idx.shape
    lay = mem_shard.memory_layout(num_slots, mem.shape[1])
    lra = addr.least_recently_accessed(la, H, backend=backend,
                                       valid_n=lay.valid_n)
    widx = jnp.concatenate([read_idx, lra[..., None]], -1).reshape(B, -1)
    mem, la = addr.sparse_write_update(mem, la, widx, ww, a, lra, step,
                                       delta, backend=backend,
                                       scratch_row=lay.scratch_row)
    read = addr.sparse_read_exact(q, mem, beta, K, backend=backend,
                                  valid_n=lay.valid_n)
    return dict(lra=lra, mem=mem, la=la, idx=read.indices, w=read.weights,
                words=read.words)


EXACT = ("lra", "la", "idx")


def compare(fails: Failures, got: dict, want: dict, label: str) -> None:
    import jax.numpy as jnp
    for k in want:
        g, w = got[k], want[k]
        if k in EXACT:
            bad = int(jnp.sum(g != w))
            fails.check(bad == 0, f"{label} {k} {tuple(w.shape)}: "
                                  f"{bad} entries differ")
        else:
            err = float(jnp.max(jnp.abs(g - w)))
            ok = bool(jnp.all(jnp.abs(g - w) <= ATOL + RTOL * jnp.abs(w)))
            fails.check(ok, f"{label} {k} {tuple(w.shape)}: max abs err "
                            f"{err:.3e}")


def kernel_parity(m, kernel_backend: str, seed: int) -> None:
    import jax
    from repro.kernels import registry

    log(f"(b) kernel parity: backend {kernel_backend!r} vs 'ref' at B="
        f"{LANES} H={m.num_heads} N={m.num_slots}+1 W={m.word_size} "
        f"K={m.k}; tolerance {ATOL} + {RTOL}*|ref|")
    step = jax.jit(memory_step, static_argnames=("backend", "num_slots",
                                                 "delta"))
    x = memory_inputs(m, LANES, seed)
    kw = dict(num_slots=m.num_slots, delta=m.delta)
    with jax.default_matmul_precision("highest"):
        want = step(**x, backend="ref", **kw)
    got = step(**x, backend=kernel_backend, **kw)
    fails = Failures()
    fails.check(registry.resolve(kernel_backend).use_pallas,
                f"{kernel_backend!r} runs the Pallas kernels")
    compare(fails, got, want, "pallas/ref")
    fails.raise_if_any("kernel parity")


def sharded_parity(m, mesh, seed: int) -> None:
    """The memory write+read on the slot-sharded layout against the
    single-device result for the same inputs (both on the platform's
    default backend, at "highest" precision)."""
    import jax
    from repro.distributed import mem_shard

    shards = int(mesh.shape["model"])
    log(f"(b) sharded parity: {shards} slot shards vs one device at B="
        f"{LANES} H={m.num_heads} N={m.num_slots} W={m.word_size} K={m.k}")
    step = jax.jit(memory_step, static_argnames=("backend", "num_slots",
                                                 "delta"))
    x = memory_inputs(m, LANES, seed)
    kw = dict(backend=None, num_slots=m.num_slots, delta=m.delta)
    fails = Failures()
    with jax.default_matmul_precision("highest"):
        want = step(**x, **kw)
        with mem_shard.memory_mesh(mesh, m.num_slots) as ctx:
            buf = mem_shard.place_state(mem_shard.to_shard_state(
                {"memory": x["mem"], "last_access": x["la"]}, ctx), ctx)
            for name, leaf in buf.items():
                fails.check(_spans(leaf, shards),
                            f"{name} {leaf.shape} split over {shards} devices")
            got = step(**{**x, "mem": buf["memory"],
                          "la": buf["last_access"]}, **kw)
            got.update(mem_shard.from_shard_state(
                {"memory": got["mem"], "last_access": got["la"]}, ctx))
    got["mem"], got["la"] = got.pop("memory"), got.pop("last_access")
    compare(fails, got, want, "sharded/single")
    fails.raise_if_any("sharded parity")


# ---------------------------------------------------------------- (c) ---

def custom_call_names(hlo_text: str) -> Counter:
    """Kernel names of the TPU custom calls in a compiled HLO module (an
    instruction is named after its kernel, plus a ``.N`` suffix)."""
    names = Counter()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.match(r"\s*(?:ROOT\s+)?%?([\w.-]+?)(?:\.\d+)?\s*=", line)
            names[m.group(1) if m else "<unparsed>"] += 1
    return names


def _spans(leaf, n: int) -> bool:
    """True when `leaf` is split over `n` devices (not replicated)."""
    sh = leaf.sharding
    return (len(sh.device_set) == n
            and sh.shard_shape(leaf.shape) != tuple(leaf.shape))


def placement_checks(fails: Failures, eng, n: int) -> None:
    import jax
    for g, st in enumerate(eng.mem):
        for name in ("memory", "last_access"):
            leaf = getattr(st, name)
            fails.check(_spans(leaf, n), f"group {g} {name} {leaf.shape} "
                                         f"split over {n} devices")
    leaves = jax.tree.leaves(eng.params)
    total = sum(x.nbytes for x in leaves)
    per_dev = Counter()
    for x in leaves:
        for s in x.addressable_shards:
            per_dev[s.device.id] += s.data.nbytes
    fails.check(all(len(x.sharding.device_set) == n for x in leaves),
                f"every weight leaf placed on all {n} devices")
    fails.check(max(per_dev.values()) < 0.5 * total,
                f"weights split: {dict(per_dev)} bytes per device of "
                f"{total} in all")


def requests(cfg, prompt_len: int, new_tokens: int, seed: int):
    """8 first-time users, then 4 of them returning (their second request
    queues behind the first wave and restores the evicted session)."""
    import numpy as np
    from repro.launch.engine import Request
    rng = np.random.default_rng(seed)
    users = [f"user{i}" for i in range(8)] + [f"user{i}" for i in range(4)]
    return [Request(user=u, prompt=rng.integers(1, cfg.vocab_size,
                                                prompt_len).tolist(),
                    max_new_tokens=new_tokens, greedy=i % 2 == 0,
                    sample_seed=i)
            for i, u in enumerate(users)]


def engine_phase(cfg, *, prompt_len: int, new_tokens: int, seed: int,
                 mesh=None, kernels=SERVED_KERNELS, on_tpu: bool) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels.introspect import count_jaxpr, kernel_names
    from repro.launch.engine import ServeEngine

    m = cfg.memory
    n_dev = 1 if mesh is None else mesh.size
    log(f"(c) engine: {cfg.name} L={cfg.num_layers} d={cfg.d_model} "
        f"heads={cfg.num_heads}/{cfg.num_kv_heads} memory "
        f"{cfg.num_layers // m.every_n_layers} x {m.num_slots} x "
        f"{m.word_size} K={m.k}; lanes={LANES} max_len={MAX_LEN} "
        f"devices={n_dev}")
    fails = Failures()
    reqs = requests(cfg, prompt_len, new_tokens, seed)
    t0 = time.time()
    with ServeEngine(cfg, lanes=LANES, max_len=MAX_LEN, param_seed=seed,
                     mesh=mesh) as eng:
        jax.block_until_ready((eng.params, eng.mem))
        log(f"  init_s {time.time() - t0:.3f}")
        if mesh is not None:
            placement_checks(fails, eng, n_dev)

        traced = eng.trace_step()
        t0 = time.time()
        compiled = traced.lower().compile()
        log(f"  compile_s {time.time() - t0:.3f}")
        log(f"  step memory_analysis: {compiled.memory_analysis()}")
        if on_tpu:
            names = custom_call_names(compiled.as_text())
            log(f"  compiled step custom calls: {dict(names)}")
        else:           # interpret mode lowers kernels to plain HLO ops
            names = kernel_names(count_jaxpr(traced.jaxpr))
            log(f"  step jaxpr kernels: {dict(names)}")
        for k in kernels:
            fails.check(names[k] > 0, f"compiled step dispatches {k}")

        for r in reqs:
            eng.submit(r)
        results, finite = [], True
        t0 = time.time()
        while eng.scheduler.has_work:
            results.extend(eng.step())
            finite &= bool(jnp.isfinite(eng.last_logits).all())
        jax.block_until_ready(eng.mem)
        wall = time.time() - t0
        log(f"  wall_s {wall:.3f} steps {eng.steps} requests "
            f"{len(results)}")

        fails.check(len(results) == len(reqs),
                    f"{len(results)} of {len(reqs)} requests finished")
        fails.check(all(len(r["tokens"]) == new_tokens for r in results),
                    f"every request returned {new_tokens} tokens")
        fails.check(all(0 <= t < cfg.vocab_size
                        for r in results for t in r["tokens"]),
                    f"every token in [0, {cfg.vocab_size})")
        fails.check(finite, "logits finite on every step")
        per_req = prompt_len + new_tokens - 1
        counters = {u: int(eng.sessions.peek(u)["counter"])
                    for u in sorted({r.user for r in reqs})}
        returning = {r.user for r in reqs[8:]}
        fails.check(all(c == per_req * (2 if u in returning else 1)
                        for u, c in counters.items()),
                    f"returning users resumed their sessions "
                    f"(token counters {counters})")
        if mesh is not None:
            placement_checks(fails, eng, n_dev)
    stats = jax.devices()[0].memory_stats() or {}
    log(f"  peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')}"
        f" bytes_limit {stats.get('bytes_limit', 'not reported')}")
    fails.raise_if_any("engine")


# ---------------------------------------------------------------- main ---

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the slot-sharded path on four chips")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny CPU rehearsal with the Pallas interpreter")
    args = ap.parse_args(argv)

    import jax
    info = device_info(jax, args.chips, args.rehearse)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import get_config, reduced
    from repro.kernels import registry
    from repro.launch import compile_cache
    from repro.launch.mesh import make_memory_mesh
    log(f"  compile cache: {compile_cache.enable()}")

    cfg = get_config(ARCH)
    if args.rehearse:
        cfg = reduced(cfg)
        cfg = dataclasses.replace(cfg, memory=dataclasses.replace(
            cfg.memory, backend="pallas-interpret"))
        prompt_len, new_tokens = 6, 3
    else:
        prompt_len, new_tokens = 64, 32
    kernel_backend = registry.resolve(cfg.memory.backend).name
    log(f"  memory backend: {cfg.memory.backend!r} resolves to "
        f"{kernel_backend!r}")
    if not args.rehearse and kernel_backend != "pallas":
        raise SystemExit("chip_smoke: the served path must resolve to the "
                         "compiled 'pallas' kernels on a TPU")
    on_tpu = info["platform"] == "tpu"

    if args.chips == 4:
        mesh = make_memory_mesh(4)
        sharded_parity(cfg.memory, mesh, args.seed)
        engine_phase(cfg, prompt_len=prompt_len, new_tokens=new_tokens,
                     seed=args.seed, mesh=mesh, kernels=SHARDED_KERNELS,
                     on_tpu=on_tpu)
    else:
        kernel_parity(cfg.memory, kernel_backend, args.seed)
        engine_phase(cfg, prompt_len=prompt_len, new_tokens=new_tokens,
                     seed=args.seed, on_tpu=on_tpu)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
