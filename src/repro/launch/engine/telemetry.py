"""What the serving engine tells an observer: profiler spans and counters.

Spans are `jax.profiler.TraceAnnotation`s, so they land in the profiler's
own trace, on the same clock as the device planes: under
``jax.profiler.trace(dir)`` every idle stretch of the device lines up with
what the host was doing then. With no profiler running a span costs one
to three microseconds of host time (a Xeon host). A span's arguments come
from host state only, never from a device array, so recording them never
waits on the device.

The spans, parent to children (docs/serving.md, "Observing the engine"):

    serve.step (step_num, lanes)
      serve.admit (req, lane, warm)
        serve.restore -> session.relayout, session.insert     (warm)
        serve.reset -> session.insert                         (cold)
        session.unspill                 (a spilled session read from disk)
      serve.hop (n)                     (only when the prefill hop runs)
      serve.dispatch                    (step inputs to device + the call)
      serve.wait                        (host blocked on the sampled tokens)
      serve.evict (req, lane)
        session.slice
        session.put -> session.relayout, session.to_host, session.spill

`EngineStats` holds the engine's counters: plain host integers, for an
operator who runs no profiler.
"""
from __future__ import annotations

import dataclasses

import jax

SPANS = (
    "serve.step", "serve.admit", "serve.restore", "serve.reset", "serve.hop",
    "serve.dispatch", "serve.wait", "serve.evict",
    "session.put", "session.unspill", "session.relayout", "session.to_host",
    "session.insert", "session.slice", "session.spill",
)


def span(name: str, **args):
    """A profiler span named ``name`` with host-side ``args``.

    ``serve.step`` is a `jax.profiler.StepTraceAnnotation` (its number in
    ``step_num``), so profiler tools that group by step see engine steps."""
    if name == "serve.step":
        return jax.profiler.StepTraceAnnotation(name, **args)
    return jax.profiler.TraceAnnotation(name, **args)


def tree_nbytes(tree) -> int:
    """Bytes held by a tree's array leaves, from their shapes (no sync)."""
    return sum(int(getattr(x, "nbytes", 0)) for x in jax.tree.leaves(tree))


@dataclasses.dataclass
class EngineStats:
    """Counters of one `ServeEngine`, since it was built.

    ``bytes_to_host`` sums the sessions copied to the host at eviction,
    ``bytes_to_device`` the stored sessions copied back into a lane at a
    warm admission (a cold admission inserts a template already on the
    device). Disk spills and restores are the `SessionStore`'s own
    ``spills`` and ``restores``."""
    steps: int = 0              # tokens every lane advanced (a hop counts n)
    hop_dispatches: int = 0     # prefill hops dispatched
    admits_cold: int = 0        # requests admitted with a fresh session
    admits_warm: int = 0        # requests admitted with a stored session
    evictions: int = 0          # lanes snapshotted into the session store
    bytes_to_host: int = 0
    bytes_to_device: int = 0
