"""Capture a profiler trace of part of the window and reduce it.

`Tracer` starts JAX's profiler a while into the window and stops it a
few seconds later; `load` reads the `.xplane.pb` it wrote into plain
lists of events, which the per-layer readers (`metrics/*.py`) take:

    {"window": (t0_ns, t1_ns),             # the traced stretch
     "devices": [{"ops": [(name, start_ns, dur_ns), ...],
                  "modules": [(name, start_ns, dur_ns), ...]}, ...],
     "host": [(name, start_ns, dur_ns), ...]}   # the harness's spans

Devices are in order of their id; the readers use the first one. Only
events that overlap the traced stretch are kept.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
import time

import jax

WINDOW_SPAN = "bench.traced"
HOST_SPANS = ("bench.step", "bench.traced", "engine.admit", "engine.evict")


class Tracer:
    """Call with seconds into the window after every step."""

    def __init__(self, begin_s: float, length_s: float):
        self.begin_s, self.length_s = begin_s, length_s
        self.dir = None
        self.span = None
        self.t0 = self.t1 = None      # host clock of the traced stretch
        self.done = False

    def __call__(self, elapsed: float) -> None:
        if self.done:
            return
        if self.dir is None and elapsed >= self.begin_s:
            self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
            jax.profiler.start_trace(self.dir)
            self.t0 = time.perf_counter()
            self.span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            self.span.__enter__()
        elif self.dir is not None and \
                elapsed >= self.begin_s + self.length_s:
            self.stop()

    def stop(self) -> None:
        if self.dir is None or self.done:
            return
        self.t1 = time.perf_counter()
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.done = True

    def load(self) -> dict:
        try:
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not files:
                raise RuntimeError("the profiler wrote no trace")
            return load(files[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


_DEVICE = re.compile(r"^/device:[A-Z]+:(\d+)$")
# A TPU trace names an op event by its HLO text, "%fused_read_sweep.6 =
# (...) custom-call(...)"; keep the instruction's name.
_HLO_NAME = re.compile(r"^\s*%?([^\s=]+)")
# Ops that hold other ops (their events span their body's events).
CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    m = _HLO_NAME.match(event_name)
    return m.group(1) if m else event_name


def load(path: str) -> dict:
    """Reduce an ``.xplane.pb`` file to the event lists described above."""
    data = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [(op_name(e.name) if key == "ops" else e.name,
                                 int(e.start_ns), int(e.duration_ns))
                                for e in line.events]
            devices[int(m.group(1))] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, int(e.start_ns), int(e.duration_ns))
                         for e in line.events if e.name in HOST_SPANS]
    if not devices:
        raise RuntimeError("the trace holds no device plane")
    spans = [e for e in host if e[0] == WINDOW_SPAN]
    if not spans:
        raise RuntimeError("the trace has no span of the traced window")
    t0 = spans[0][1]
    t1 = t0 + spans[0][2]
    keep = lambda evs: [e for e in evs if e[1] < t1 and e[1] + e[2] > t0]  # noqa: E731
    return {"window": (t0, t1),
            "devices": [{k: keep(v) for k, v in devices[i].items()}
                        for i in sorted(devices)],
            "host": keep(host)}


def busy_ns(events, t0: int, t1: int) -> int:
    """Length of the union of the events' intervals, clipped to [t0, t1]."""
    total, cur_s, cur_e = 0, None, None
    for _, s, d in sorted(events, key=lambda e: e[1]):
        s, e = max(s, t0), min(s + d, t1)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def base_name(op: str) -> str:
    """An HLO op's name without its ``.N`` suffix."""
    return re.sub(r"(\.\d+)+$", "", op)


def breakdown(trace: dict, top: int = 10) -> dict:
    """The device ops of the first device that took most time, by op name
    without its ``.N`` suffix (ops that hold other ops left out), and the
    longest idle gaps, each named by the host span it fell in."""
    t0, t1 = trace["window"]
    dev = trace["devices"][0]
    by_op = {}
    for name, s, d in dev["ops"]:
        b = base_name(name)
        if b not in CONTAINERS:
            by_op[b] = by_op.get(b, 0) + min(s + d, t1) - max(s, t0)
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gaps, last = [], t0
    for _, s, d in sorted(dev["ops"], key=lambda e: e[1]):
        if s > last:
            gaps.append((last, s))
        last = max(last, s + d)
    if last < t1:
        gaps.append((last, t1))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    named = []
    for a, b in gaps:
        mid = (a + b) // 2
        inside = [h for h in trace["host"] if h[0] != WINDOW_SPAN
                  and h[1] <= mid < h[1] + h[2]]
        # The innermost span: the shortest one that holds the gap's middle.
        what = min(inside, key=lambda h: h[2])[0] if inside else "host idle"
        named.append([what, (b - a) / 1e9])
    return {"device_ops": [[n, v / 1e9] for n, v in ops],
            "idle_gaps": named}
