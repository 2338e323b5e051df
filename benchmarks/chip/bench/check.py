"""The comparison that decides `correct`.

After the window, a sample of the requests the engine finished (the
finished turn with the longest conversation behind it, one request from
every lane that served one, and the requests the traffic marked
``sampled``, in an order drawn from the seed) is replayed
through the plain float32 reference (`reference/model.py`): every turn
of the request's conversation, prompts and served tokens, as one
uninterrupted sequence, so later turns also check the session the engine
evicted and restored. Which of its readings are compared, and against
what limit, is the cell's `limits/<workload>.json`; PERF.md says why.

- ``logit_gap``: the widest gap, over every served token of the sample,
  by which the reference's logit of the served token lies below the
  reference's best logit at that position (the served tokens are greedy);
  beside it the mean and 90th-percentile gap and the share of served
  tokens that are not the reference's first;
- ``kv_gap``: the worst relative difference between the keys and values
  the engine stored for a conversation and the reference's, over the
  layers before the first memory access (``kv_gap_read``: the first layer
  after it; ``kv_gap_all``: all layers);
- ``memory_norm_gap``, ``memory_diff``, ``usage_entries_differ``: each
  group's memory after the conversation's last write against the
  reference's: the gap of their norms, the norm of their difference (both
  over the reference's norm), and the usage entries that differ;
- ``memory_bf16_share``: the largest share, over the sample's
  conversations and the groups, of the engine's nonzero memory entries
  that bfloat16 holds exactly (``memory_bf16_share_ref``: the
  reference's): the precision the memory is held at.

The control (``control=True``) is the reference in float8 put in the
program's place, read the same way against the reference.
"""
from __future__ import annotations

import numpy as np

from reference.model import Reference


def sample(finished: list, rows: int, seed: int) -> list:
    """At most ``rows`` of the captured requests: the longest one; then,
    in an order drawn from the seed, each one that ran in a lane the
    sample does not cover yet; then the others in that order. With at
    least as many rows as lanes, every lane that served a captured
    request is in the sample."""
    if not finished:
        return []
    longest = max(finished, key=lambda s: s["positions"])
    rest = [s for s in finished if s is not longest]
    order = np.random.default_rng([seed, 3]).permutation(len(rest))
    rest = [rest[i] for i in order]
    chosen, covered = [longest], set(longest["lanes"])
    for s in rest:
        if len(chosen) < rows and not s["lanes"] <= covered:
            chosen.append(s)
            covered |= s["lanes"]
    for s in rest:
        if len(chosen) < rows and all(s is not c for c in chosen):
            chosen.append(s)
    return chosen


def conversation(turns: list) -> tuple[list, list]:
    """The token stream a conversation fed the engine, and at each of its
    positions the served token the engine predicted there (-1 where it
    predicted none): the last token of a turn is served but never fed."""
    stream, target = [], []
    for prompt, served in turns:
        stream += list(prompt) + list(served[:-1])
        target += [-1] * (len(prompt) - 1) + list(served)
    return stream, target


def compare(samples: list, model: dict, memory: dict, seed: int, *,
            rows: int, max_len: int, control: bool = False) -> dict:
    """Readings of the sample against the reference (see module doc).
    The reference runs on as many rows as the sample holds, rounded up to
    a power of two (at most ``rows``), each ``max_len`` long."""
    B, T = min(rows, 1 << max(0, len(samples) - 1).bit_length()), max_len
    tokens = np.zeros((B, T), np.int32)
    target = np.full((B, T), -1, np.int32)
    lengths = np.ones(B, np.int32)
    for b, s in enumerate(samples):
        stream, tgt = conversation(s["turns"])
        if len(stream) > T:
            raise ValueError(f"a conversation of {len(stream)} positions "
                             f"passes max_len={T}")
        tokens[b, :len(stream)] = stream
        target[b, :len(tgt)] = tgt
        lengths[b] = len(stream)
    N, Wd = memory["num_slots"], memory["word_size"]

    def program_kv(i):
        k = np.zeros((B, T) + samples[0]["kv"][0][0].shape[1:], np.float32)
        v = np.zeros_like(k)
        for b, s in enumerate(samples):
            n = s["kv"][i][0].shape[0]
            k[b, :n], v[b, :n] = s["kv"][i]
        return k, v

    def program(g):
        mem = np.zeros((B, N, Wd), np.float32)
        la = np.zeros((B, N), np.int32)
        for b, s in enumerate(samples):
            mem[b], la[b] = s["memory"][g]
        return mem, la

    ref = Reference(model, memory, seed)
    lookups, others = [target], {"program": program}
    others_kv = {"program": program_kv}
    if control:
        ctl = Reference(model, memory, seed, quantize="fp8").run(
            tokens, lengths, keep_memory=True, keep_kv=True)
        lookups.append(ctl["top"])
        others["control"] = lambda g: ctl["state"][g]
        others_kv["control"] = lambda i: ctl["kv_state"][i]
    out = ref.run(tokens, lengths, lookups=lookups, others=others,
                  others_kv=others_kv)
    per = model["num_layers"] // len(out["memory"]["program"])
    served = target >= 0
    served[len(samples):] = False
    gaps = out["best"] - out["picked"][0]
    readings = dict(
        served_tokens=int(served.sum()), conversations=len(samples),
        **_gap_numbers(gaps[served]),
        **_kv_numbers(out["kv"]["program"], len(samples), per),
        **_memory_numbers(out["memory"]["program"], len(samples)))
    if control:
        cg = out["best"] - out["picked"][1]
        readings["control"] = dict(
            **_gap_numbers(cg[served]),
            **_kv_numbers(out["kv"]["control"], len(samples), per),
            **_memory_numbers(out["memory"]["control"], len(samples)))
    return readings


def _gap_numbers(g) -> dict:
    return dict(logit_gap=float(g.max()), gap_mean=float(g.mean()),
                gap_p90=float(np.quantile(g, 0.9)),
                top_mismatch=float(np.mean(g > 0)))


def _kv_numbers(per_layer: list, n: int, per: int) -> dict:
    """The worst relative gap of the keys and values, over the layers
    before the first memory access, and over all layers."""
    gaps = [np.max(r["norm_diff"][:n] / np.maximum(r["norm_ref"][:n], 1e-30))
            for r in per_layer]
    return dict(kv_gap=float(max(gaps[:per])), kv_gap_read=float(gaps[per]),
                kv_gap_all=float(max(gaps)),
                kv_gap_by_layer=[round(float(g), 5) for g in gaps])


def _memory_numbers(per_group: list, n: int) -> dict:
    norm_gap, diff = 0.0, 0.0
    for r in per_group:
        ref = np.maximum(r["norm_ref"][:n], 1e-30)
        norm_gap = max(norm_gap, float(np.max(
            np.abs(r["norm_prog"][:n] - r["norm_ref"][:n]) / ref)))
        diff = max(diff, float(np.max(r["norm_diff"][:n] / ref)))
    usage = max(int(np.max(r["usage_diff"][:n])) for r in per_group)
    return dict(memory_norm_gap=norm_gap, memory_diff=diff,
                usage_entries_differ=usage,
                memory_bf16_share=max(float(np.max(r["bf16_prog"][:n]))
                                      for r in per_group),
                memory_bf16_share_ref=max(float(np.max(r["bf16_ref"][:n]))
                                          for r in per_group))


def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """Each compared number beside its limit; correct when every number
    is at or under its limit."""
    checks = {name: {"value": readings[name], "limit": lim["limit"]}
              for name, lim in limits["compare"].items()}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
