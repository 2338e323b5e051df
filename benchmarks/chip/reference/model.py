"""Plain float32 reference of the SAM-augmented decoder as it is served.

It follows decode semantics: every token of a conversation passes through
every block, and after each group of ``every_n_layers`` blocks the token's
hidden state makes one SAM write then one SAM read (Rae et al. 2016, §3.1,
§3.2) against its conversation's own memory, whose read is added into the
residual. The blocks are those of H2O-Danube3 (Llama/Mistral style): RMS
norm with a ``1 + scale`` gain, grouped-query attention with rotary
positions (half-split), a sliding window, and a SiLU-gated MLP.

It is computed layer by layer over the whole conversation: a block's
attention over all positions with a causal (and window) mask gives what
decoding through a KV cache gives, and the memory of a group is scanned
token by token, since each token's access depends on the previous one.
Weights come from `bench.weights` for the seed, one layer at a time, as
the served dtype's values held in float32; every matmul runs at "highest"
precision. Nothing of the program is imported.

``quantize="fp8"`` is the control: the same computation with the weight
matmuls in float8, the step below the bfloat16 the configuration serves:
every weight matrix rounded to e4m3 under one absmax scale per matrix, and
every activation operand of those matmuls under one scale per token.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import weights as W

_EPS = 1e-6          # the SAM read's row normalisation


def _quantize_fp8(w):
    """Round to float8 e4m3 under one absmax scale."""
    s = jnp.maximum(jnp.max(jnp.abs(w)), 1e-30) / 448.0
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _fp8_activations(x):
    """A matmul's activation operand in float8: one absmax scale per row
    (token), as an fp8 matmul takes it."""
    s = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def rope(x, pos, theta):
    """x: (B, T, h, D) at positions pos (T,)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("theta", "eps", "window", "fp8"))
def block(x, w, *, theta, eps, window, fp8=False):
    """One decoder block over a whole (B, T, d) sequence; returns it and
    the block's keys and values (B, T, Hkv, D), as a KV cache holds them.
    With ``fp8`` the activation operand of every weight matmul is rounded
    to float8."""
    B, T, _ = x.shape
    pos = jnp.arange(T)
    mm = _fp8_activations if fp8 else (lambda a: a)
    h = mm(rms_norm(x, w["ln1"], eps))
    q = rope(jnp.einsum("btd,dhk->bthk", h, w["wq"]), pos, theta)
    k = rope(jnp.einsum("btd,dhk->bthk", h, w["wk"]), pos, theta)
    v = jnp.einsum("btd,dhk->bthk", h, w["wv"])
    Hkv, D = k.shape[2], k.shape[3]
    qg = q.reshape(B, T, Hkv, -1, D)
    s = jnp.einsum("btjgd,bsjd->bjgts", qg, k) * D ** -0.5
    causal = (pos[None, :] <= pos[:, None]) & \
        (pos[None, :] > pos[:, None] - window)
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bjgts,bsjd->btjgd", jax.nn.softmax(s, -1), v)
    x = x + jnp.einsum("bthk,hkd->btd", mm(o.reshape(B, T, -1, D)), w["wo"])
    h = mm(rms_norm(x, w["ln2"], eps))
    f = jax.nn.silu(h @ w["w1"]) * (h @ w["w3"])
    return x + mm(f) @ w["w2"], (k, v)


def top_k(x, k: int, block: int = 128):
    """`jax.lax.top_k` over the last axis, exact, in two stages: the top
    k of each block of ``block`` entries, then the top k of those. Ties
    go to the lower index, as in one stage."""
    n = x.shape[-1]
    if n % block or n // block <= 1:
        return jax.lax.top_k(x, k)
    xb = x.reshape(x.shape[:-1] + (n // block, block))
    v, i = jax.lax.top_k(xb, k)                       # (..., n/block, k)
    i = i + (jnp.arange(n // block) * block)[:, None]
    flat = x.shape[:-1] + (-1,)
    v2, j = jax.lax.top_k(v.reshape(flat), k)
    return v2, jnp.take_along_axis(i.reshape(flat), j, -1)


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + _EPS)


def memory_access(w, x, st, step, live, *, k, delta, quant=None):
    """One SAM write then read for token states x (B, d); `st` is
    (memory (B, N+1, W), inverse row norms (B, N+1), last_access (B, N+1),
    read_idx (B, H, K), read_w (B, H, K)). Row N of the memory is a trash
    row that only rows past their last token (``live`` false) write to,
    so their state stays as it was. The inverse norms are those of
    `_unit`, kept for the touched rows only."""
    mem, inv, la, ridx, rw = st
    B, N = x.shape[0], mem.shape[1] - 1
    b = jnp.arange(B)[:, None]
    mm = quant or (lambda a: a)
    xq = mm(x)
    q = jnp.einsum("bd,dhw->bhw", xq, w["wq"])
    a = jnp.einsum("bd,dhw->bhw", xq, w["wa"])
    g = jax.nn.sigmoid(jnp.einsum("bd,dhg->bhg", xq, w["gates"]))
    alpha, gamma, beta = g[..., 0], g[..., 1], 1.0 + 9.0 * g[..., 2]
    H = q.shape[1]
    on = live[:, None]
    # Write (eq. 5): the previously read slots and the least recently
    # accessed slot of each head, which is erased first (eq. 6).
    _, lra = top_k(-la[:, :N], H)                                # (B, H)
    widx = jnp.concatenate([ridx, lra[..., None]], -1)           # (B,H,K+1)
    ww = jnp.concatenate([(alpha * gamma)[..., None] * rw,
                          (alpha * (1 - gamma))[..., None]], -1)
    mem = mem.at[b, jnp.where(on, lra, N)].set(0.0)
    add = ww[..., None] * a[:, :, None, :]                       # (B,H,K+1,W)
    widx = jnp.where(on, widx.reshape(B, -1), N)
    ww = ww.reshape(B, -1)
    mem = mem.at[b, widx].add(add.reshape(B, -1, a.shape[-1]))
    la = la.at[b, widx].max(jnp.where(ww > delta, step, la[b, widx]))
    rows = mem[b, widx]                                          # (B, J, W)
    inv = inv.at[b, widx].set(
        jax.lax.rsqrt(jnp.sum(rows * rows, -1) + _EPS))
    # Read (§3.1): the K most similar slots by cosine, softmax over them.
    sims = jnp.einsum("bhw,bnw->bhn", _unit(q), mem[:, :N]) * \
        inv[:, None, :N]
    _, idx = top_k(sims, k)                                      # (B, H, K)
    words = mem[jnp.arange(B)[:, None, None], idx]               # (B,H,K,W)
    sel = jnp.einsum("bhw,bhkw->bhk", _unit(q), _unit(words)) * beta[..., None]
    new_rw = jax.nn.softmax(sel, -1)
    read = jnp.einsum("bhk,bhkw->bhw", new_rw, words)
    fi = jnp.where(on, idx.reshape(B, -1), N)
    fw = new_rw.reshape(B, -1)
    la = la.at[b, fi].max(jnp.where(fw > delta, step, la[b, fi]))
    out = jnp.einsum("bhw,hwd->bd", mm(read), w["wr"])
    keep = live[:, None, None]
    return (mem, inv, la, jnp.where(keep, idx, ridx),
            jnp.where(keep, new_rw, rw)), out


@functools.partial(jax.jit, static_argnames=("slots", "k", "delta", "fp8"))
def memory_group(x, w, lengths, *, slots, k, delta, fp8=False):
    """Scan one group's memory over the sequence: returns x with each
    token's read added, and each row's state after its last token
    (memory and usage without the trash row)."""
    B, T, _ = x.shape
    H, Wd = w["wq"].shape[1], w["wq"].shape[2]
    la0 = jnp.concatenate([-jnp.arange(slots, dtype=jnp.int32),
                           jnp.full((1,), 2 ** 31 - 1, jnp.int32)])
    st = (jnp.zeros((B, slots + 1, Wd), jnp.float32),
          jnp.full((B, slots + 1), _EPS ** -0.5, jnp.float32),
          jnp.broadcast_to(la0, (B, slots + 1)),
          jnp.zeros((B, H, k), jnp.int32), jnp.zeros((B, H, k), jnp.float32))
    quant = _fp8_activations if fp8 else None

    def body(st, xs):
        t, xt = xs
        return memory_access(w, xt, st, t + 1, t < lengths, k=k,
                             delta=delta, quant=quant)

    st, outs = jax.lax.scan(body, st, (jnp.arange(T), jnp.moveaxis(x, 1, 0)))
    mem, la = st[0][:, :slots], st[2][:, :slots]
    return x + jnp.moveaxis(outs, 0, 1), (mem, la)


@jax.jit
def _kv_readings(kv_ref, kv_other, lengths):
    """Per row: the norm of the reference's keys and values over the
    row's positions, and the norm of the other model's difference."""
    T = kv_ref[0].shape[1]
    live = (jnp.arange(T)[None, :] < lengths[:, None])[..., None, None]
    ref = sum(jnp.sum(jnp.where(live, r, 0.0) ** 2, (1, 2, 3)) for r in kv_ref)
    diff = sum(jnp.sum(jnp.where(live, o.astype(jnp.float32) - r, 0.0) ** 2,
                       (1, 2, 3)) for r, o in zip(kv_ref, kv_other))
    return dict(norm_ref=jnp.sqrt(ref), norm_diff=jnp.sqrt(diff))


def _bf16_share(m):
    """Per row: the share of the nonzero entries that bfloat16 holds
    exactly (values computed in float32 rarely are): those whose low 16
    bits are zero. Read from the bits, since XLA may drop a round trip
    through bfloat16 as excess precision."""
    nz = m != 0
    low = jax.lax.bitcast_convert_type(m, jnp.uint32) & jnp.uint32(0xFFFF)
    exact = nz & (low == 0)
    return jnp.sum(exact, (1, 2)) / jnp.maximum(jnp.sum(nz, (1, 2)), 1)


@jax.jit
def _memory_readings(mem_ref, la_ref, mem_prog, la_prog):
    """Per row: the norms of the two memories, the norm of their
    difference, how many usage entries differ, and each memory's share
    of entries on the bfloat16 grid."""
    n_ref = jnp.sqrt(jnp.sum(mem_ref * mem_ref, (1, 2)))
    n_prog = jnp.sqrt(jnp.sum(mem_prog * mem_prog, (1, 2)))
    diff = mem_prog - mem_ref
    return dict(norm_ref=n_ref, norm_prog=n_prog,
                norm_diff=jnp.sqrt(jnp.sum(diff * diff, (1, 2))),
                usage_diff=jnp.sum(la_ref != la_prog, 1),
                bf16_ref=_bf16_share(mem_ref), bf16_prog=_bf16_share(mem_prog))


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, scale, head, lookups, *, eps, fp8=False):
    h = rms_norm(x, scale, eps)
    logits = (_fp8_activations(h) if fp8 else h) @ head          # (B, T, V)
    best = jnp.max(logits, -1)
    top = jnp.argmax(logits, -1).astype(jnp.int32)
    picked = [jnp.take_along_axis(logits, jnp.maximum(ix, 0)[..., None],
                                  -1)[..., 0] for ix in lookups]
    return best, top, picked


class Reference:
    """The served model for one seed, in float32 (or the fp8 control)."""

    def __init__(self, model: dict, memory: dict, seed: int, *,
                 quantize: str | None = None):
        if quantize not in (None, "fp8"):
            raise ValueError(f"unknown control precision {quantize!r}")
        self.model, self.memory, self.seed = model, memory, seed
        self.quantize = quantize
        # The weights as served: their values in the served dtype.
        self.served_dtype = jnp.dtype(model["compute_dtype"])
        self.specs = W.leaves(model, memory)

    def weight(self, path: str, index: int = 0):
        w = W.one(self.seed, path, self.specs[path], index,
                  self.served_dtype).astype(jnp.float32)
        if self.quantize == "fp8" and w.ndim >= 2:
            w = _quantize_fp8(w)
        return w

    def _layer(self, i: int) -> dict:
        names = ("ln1", "ln2", "attn/wq", "attn/wk", "attn/wv", "attn/wo",
                 "mlp/w1", "mlp/w2", "mlp/w3")
        return {n.split("/")[-1]: self.weight(f"blocks/{n}", i) for n in names}

    def run(self, tokens, lengths, lookups=(), others=None,
            others_kv=None, keep_memory=False, keep_kv=False):
        """Run the conversations ``tokens`` (B, T) int32, row b valid for
        its first ``lengths[b]`` positions.

        Returns ``best`` (B, T), the largest logit at each position,
        ``top`` (B, T), its token, and ``picked``: for each (B, T) index
        array in ``lookups``, the logit of that token (entries < 0 read
        token 0 and are to be ignored). ``others`` maps a name to a
        function of the group index that gives another model's memory
        after each row's last token, (memory (B, N, W), last_access
        (B, N)); ``memory[name]`` then holds, per group, the readings of
        `_memory_readings` against this reference's. With ``keep_memory``,
        ``state`` holds this reference's (memory, last_access) per group,
        on the host. ``others_kv`` maps a name to a function of the layer
        index that gives another model's (keys, values), each (B, T, Hkv,
        D); ``kv[name]`` then holds, per layer, the readings of
        `_kv_readings`. With ``keep_kv``, ``kv_state`` holds this
        reference's per layer, on the host."""
        m, mm = self.model, self.memory
        tokens = jnp.asarray(tokens, jnp.int32)
        lengths = jnp.asarray(lengths, jnp.int32)
        T = tokens.shape[1]
        window = int(m.get("window") or T)
        groups = W.num_groups(m, mm)
        per = m["num_layers"] // groups
        readings = {name: [] for name in others or {}}
        kv_readings = {name: [] for name in others_kv or {}}
        kept, kept_kv = [], []
        fp8 = self.quantize == "fp8"
        with jax.default_matmul_precision("highest"):
            x = self.weight("embed/tok")[tokens] * m["d_model"] ** 0.5
            for g in range(groups):
                for i in range(g * per, (g + 1) * per):
                    x, kv = block(x, self._layer(i),
                                  theta=float(m["rope_theta"]),
                                  eps=float(m["norm_eps"]), window=window,
                                  fp8=fp8)
                    for name, get in (others_kv or {}).items():
                        kv_readings[name].append(jax.device_get(
                            _kv_readings(kv, get(i), lengths)))
                    if keep_kv:
                        kept_kv.append(jax.device_get(kv))
                    del kv
                wm = {n: self.weight(f"memory/{n}", g)
                      for n in ("wq", "wa", "wr", "gates")}
                x, (mem, la) = memory_group(
                    x, wm, lengths, slots=mm["num_slots"], k=mm["k"],
                    delta=float(mm["delta"]), fp8=fp8)
                for name, get in (others or {}).items():
                    mem_o, la_o = get(g)
                    readings[name].append(jax.device_get(_memory_readings(
                        mem, la, jnp.asarray(mem_o, jnp.float32),
                        jnp.asarray(la_o, jnp.int32))))
                if keep_memory:
                    kept.append(jax.device_get((mem, la)))
                del mem, la
            best, top, picked = _head(
                x, self.weight("final_norm"), self.weight("lm_head"),
                [jnp.asarray(ix, jnp.int32) for ix in lookups],
                eps=float(m["norm_eps"]), fp8=fp8)
        return dict(best=jax.device_get(best), top=jax.device_get(top),
                    picked=[jax.device_get(p) for p in picked],
                    memory=readings, state=kept, kv=kv_readings,
                    kv_state=kept_kv)
