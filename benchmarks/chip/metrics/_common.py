"""Shared arithmetic of the per-layer readers: the step program's events
and the names of the memory kernels. Each reader is a file of its own
with ``read(trace, window, cell)``; a reader that finds nothing to read
returns None."""
from __future__ import annotations

import pathlib
import re
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from bench.trace import base_name, busy_ns  # noqa: E402,F401

STEP_MODULE = re.compile(r"^jit_step(\b|\()")
READ_KERNELS = ("fused_read_sweep",)
SAM_KERNELS = READ_KERNELS + ("lra_topn", "sparse_write_update")


def idle_share(trace):
    """Share of the traced window in which no operation ran on the first
    device, percent: 1 - (union of its op intervals) / window."""
    t0, t1 = trace["window"]
    ops = trace["devices"][0]["ops"]
    if not ops or t1 <= t0:
        return None
    return 100.0 * (1.0 - busy_ns(ops, t0, t1) / (t1 - t0))


def clipped(events, window):
    t0, t1 = window
    return sum(max(0, min(s + d, t1) - max(s, t0)) for _, s, d in events)


def step_events(trace):
    """The device events of the engine's jitted step on the first device,
    wholly inside the traced window."""
    t0, t1 = trace["window"]
    return [e for e in trace["devices"][0]["modules"]
            if STEP_MODULE.match(e[0]) and e[1] >= t0 and e[1] + e[2] <= t1]


def ops_named(trace, names):
    return [e for e in trace["devices"][0]["ops"]
            if base_name(e[0]) in names]


def per_step_ms(trace, events):
    """Device time of `events` in the traced window per engine step, ms;
    None when the window holds no step."""
    steps = len(step_events(trace))
    if not steps:
        return None
    return clipped(events, trace["window"]) / steps / 1e6
