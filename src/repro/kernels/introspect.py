"""Structural dispatch counting over jaxprs.

The fused-read acceptance criterion ("a decode step's SAM read is one
kernel dispatch") is asserted *structurally*: trace the function with
`jax.make_jaxpr` — no compile, no TPU needed, safe on CPU even for
``backend="pallas"`` — and count primitives. `pallas_call` is opaque (its
inner jaxpr is the kernel body, not extra dispatches), every other
primitive's sub-jaxprs (scan/while/cond/pjit bodies) are walked
recursively. Each `pallas_call` is additionally counted under a
``"pallas_call:<kernel name>"`` key so contracts can assert *which*
kernel dispatched, not just how many (see `repro.analysis`). Used by
`tests/test_fused_read.py` (fused = 1 pallas_call + 0 sort/top_k, with
the composed path as positive control), `repro.analysis.measure`, and
`benchmarks/bench_kernels.py`'s decode-step rows.
"""
from __future__ import annotations

import collections

import jax


def count_primitives(fn, *args, **kwargs) -> collections.Counter:
    """Trace ``fn(*args, **kwargs)`` and count every primitive equation,
    recursing into sub-jaxprs (except inside `pallas_call`: one kernel is
    one dispatch, whatever its body stages).

    Keyword arguments are passed straight through to the traced call —
    they are *call* kwargs, not `make_jaxpr` options. Each pallas_call
    also increments a ``"pallas_call:<name>"`` entry naming the kernel.
    """
    if kwargs:
        jaxpr = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    else:
        jaxpr = jax.make_jaxpr(fn)(*args)
    return count_jaxpr(jaxpr)


def count_jaxpr(jaxpr) -> collections.Counter:
    """`count_primitives` of an already traced (closed) jaxpr."""
    counts: collections.Counter = collections.Counter()
    _walk(jaxpr.jaxpr, counts)
    return counts


def kernel_names(counts: collections.Counter) -> collections.Counter:
    """The per-kernel slice of a `count_primitives` result: a Counter
    mapping kernel name -> dispatch count, dropping the ``pallas_call:``
    prefix."""
    out: collections.Counter = collections.Counter()
    for key, n in counts.items():
        if key.startswith("pallas_call:"):
            out[key.split(":", 1)[1]] += n
    return out


def _pallas_kernel_name(params) -> str:
    """The kernel name a pallas_call eqn carries: every kernel in this repo
    passes ``name=`` to `pl.pallas_call`, so an unnamed one reads as
    ``"<unknown>"``."""
    return params.get("name") or "<unknown>"


def _walk(jaxpr, counts) -> None:
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] += 1
        if eqn.primitive.name == "pallas_call":
            counts["pallas_call:" + _pallas_kernel_name(eqn.params)] += 1
            continue
        for sub in _sub_jaxprs(eqn.params):
            _walk(sub, counts)


def _sub_jaxprs(params):
    """Yield every inner jaxpr in an eqn's params (duck-typed: closed
    jaxprs carry ``.jaxpr``, open ones carry ``.eqns`` directly)."""
    for v in params.values():
        vs = v if isinstance(v, (list, tuple)) else [v]
        for item in vs:
            if hasattr(item, "jaxpr"):
                yield item.jaxpr
            elif hasattr(item, "eqns"):
                yield item
