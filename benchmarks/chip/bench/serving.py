"""Drive the program's `ServeEngine` through a timed window.

The harness submits the traffic's requests on their schedule and calls
``ServeEngine.step`` in a loop, so the window drives the engine's own
admit / restore, jitted decode step and evict. It keeps its own clock:
a token is counted as delivered when the step that made it returns, and
time to first token runs from the request's *scheduled* send time.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time

import jax
import numpy as np

from bench import traffic as traffic_lib
from bench import weights as weights_lib


def program_engine_class(prefill_hop: bool = True):
    """`ServeEngine` with the benchmark's weights, host spans around the
    calls into each layer, a record of the lane each request was admitted
    to, and, where the configuration turns it off (``prefill_hop``), the
    prefill hop off.

    The hop (`ServeEngine._prefill_scan_hop`) jits a (lanes, n) block of
    prompt tokens, so each new n compiles; n follows the prompts and the
    timing of the run, so its programs cannot all be warmed up and its
    compiles would land inside the window. With it off every token goes
    through the engine's one-token step, as it does whenever any lane
    decodes or the queue holds work."""
    from repro.launch.engine import ServeEngine

    class BenchEngine(ServeEngine):
        def __init__(self, cfg, *, make_params, **kw):
            self._make_params = make_params
            self.lane_of = {}                     # request id -> lane
            super().__init__(cfg, **kw)

        def _init_params(self):
            self.params = self._make_params(self.cfg)

        def _admit_lane(self, lane, req):
            self.lane_of[req.id] = lane
            with jax.profiler.TraceAnnotation("engine.admit"):
                super()._admit_lane(lane, req)

        def _evict_lane(self, lane):
            with jax.profiler.TraceAnnotation("engine.evict"):
                super()._evict_lane(lane)

        if not prefill_hop:
            def _prefill_scan_hop(self):
                return None

    return BenchEngine


def params_maker(seed: int, model: dict, memory: dict):
    """A ``make_params(cfg)`` for `BenchEngine`: the benchmark's seeded
    weights in one jitted call on the device, in the program's parameter
    tree."""
    from repro.models import lm
    from repro.models.layers import ParamDef

    def make(cfg):
        defs = lm.param_defs(cfg)
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            defs, is_leaf=lambda x: isinstance(x, ParamDef))
        paths = ["/".join(str(k.key) for k in kp) for kp, _ in flat]
        specs = weights_lib.leaves(model, memory)
        if sorted(paths) != sorted(specs):
            raise ValueError(f"the program's parameters {sorted(paths)} are "
                             f"not the benchmark's {sorted(specs)}")
        for path, (_, d) in zip(paths, flat):
            stack, shape, _ = specs[path]
            n = () if stack is None else (
                weights_lib.stack_size(stack, model, memory),)
            if tuple(d.shape) != n + tuple(shape):
                raise ValueError(f"{path}: program shape {d.shape}, "
                                 f"benchmark shape {n + tuple(shape)}")

        def build(words):
            t = weights_lib.tree(words, model, memory,
                                 np.dtype(cfg.compute_dtype))
            return [t[p] for p in paths]

        leaves = jax.jit(build)(weights_lib.seed_words(seed))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return make


@dataclasses.dataclass
class Record:
    """One request as the harness saw it."""
    item: traffic_lib.Item
    req: object
    sent: float                       # when it was due (absolute)
    fed: int = 0                      # prompt + generated tokens counted
    lane: int | None = None           # the lane that served it
    token_times: list = dataclasses.field(default_factory=list)
    tokens: list | None = None
    finish: float | None = None


@dataclasses.dataclass
class Window:
    start: float
    end: float = 0.0
    tokens: int = 0                   # prompt tokens consumed + generated
    steps: list = dataclasses.field(default_factory=list)
    finished: list = dataclasses.field(default_factory=list)
    submitted: int = 0
    in_flight: int = 0
    late_s: float = 0.0               # worst lateness of a submission
    compiles: int = 0
    records: list = dataclasses.field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Step:
    """One engine step: when it ended, the tokens it consumed, the lanes
    that ran and the attention context they saw."""
    end: float
    tokens: int
    lanes: int
    context: int


class CompileCounter:
    """Counts XLA compiles (and compile-cache loads) as JAX reports them."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.count += 1


def make_request(item: traffic_lib.Item):
    from repro.launch.engine import Request
    return Request(user=item.conv, prompt=list(item.prompt),
                   max_new_tokens=item.max_new, greedy=True,
                   sample_seed=item.index)


class LoadRunner:
    """Runs windows of traffic through one engine."""

    def __init__(self, eng, on_finish=None):
        self.eng = eng
        self.on_finish = on_finish or (lambda rec: None)
        self.history = collections.defaultdict(list)   # conv -> turns
        self.lanes = collections.defaultdict(set)      # conv -> lanes

    def _submit(self, item, sent, live):
        req = self.eng.submit(make_request(item))
        live[req.id] = Record(item=item, req=req, sent=sent)

    def _account(self, live, now, win):
        tokens = 0
        for rec in live.values():
            r = rec.req
            fed = r.prefill_done + r.generated
            tokens += fed - rec.fed
            rec.fed = fed
            rec.token_times.extend([now] * (r.generated
                                            - len(rec.token_times)))
        win.tokens += tokens
        return tokens

    def _finish(self, res, live, now, win):
        rec = live.pop(res["id"])
        rec.tokens, rec.finish = res["tokens"], now
        rec.lane = self.eng.lane_of.pop(res["id"])
        it = rec.item
        self.history[it.conv].append((list(it.prompt), list(res["tokens"])))
        self.lanes[it.conv].add(rec.lane)
        win.finished.append(rec)
        self.on_finish(rec)
        if it.last_turn:
            self.eng.sessions.take(it.conv)
            self.history.pop(it.conv, None)
            self.lanes.pop(it.conv, None)

    def run(self, *, seconds: float, items=None, clients=None,
            counter: CompileCounter | None = None, tracer=None) -> Window:
        """One window: ``items`` (open loop, with send times) or
        ``clients`` (closed loop, one list per client). ``tracer`` is
        called with (now - start) after every step; it may start or stop
        the profiler."""
        eng = self.eng
        live: dict[int, Record] = {}
        start = time.perf_counter()
        win = Window(start=start)
        c0 = counter.count if counter else 0
        pending = collections.deque(items or [])
        queues = [collections.deque(c) for c in clients or []]
        for q in queues:
            self._submit(q.popleft(), start, live)
            win.submitted += 1
        while True:
            now = time.perf_counter()
            if now - start >= seconds:
                break
            while pending and pending[0].send <= now - start:
                it = pending.popleft()
                win.late_s = max(win.late_s, now - start - it.send)
                self._submit(it, start + it.send, live)
                win.submitted += 1
            if not eng.scheduler.has_work:
                nxt = pending[0].send if pending else seconds
                time.sleep(max(0.0, min(nxt, seconds) - (now - start)))
                continue
            with jax.profiler.TraceAnnotation("bench.step"):
                results = eng.step()
            now = time.perf_counter()
            ran = [live[r.id] for r in eng.scheduler.active.values()]
            ran += [live[r["id"]] for r in results]
            # Each lane that ran fed one token at its conversation's next
            # position and attended to everything up to it.
            context = sum(rec.item.start_pos + rec.req.prefill_done
                          + max(rec.req.generated - 1, 0) for rec in ran)
            tokens = self._account(live, now, win)
            win.steps.append(Step(now, tokens, len(ran), context))
            for res in results:
                client = live[res["id"]].item.client
                self._finish(res, live, now, win)
                if client is not None and queues[client]:
                    self._submit(queues[client].popleft(), now, live)
                    win.submitted += 1
            if tracer is not None:
                tracer(now - start)
        win.end = max(time.perf_counter(), start + seconds) \
            if not win.steps else max(win.steps[-1].end, start + seconds)
        win.in_flight = len(live)
        win.records = win.finished + list(live.values())
        win.compiles = (counter.count - c0) if counter else 0
        self._live = live
        return win

    def drain(self) -> None:
        """Finish whatever is queued or running (off the clock), and drop
        every session."""
        eng = self.eng
        live = getattr(self, "_live", {})
        while eng.scheduler.has_work:
            for res in eng.step():
                live.pop(res["id"], None)
                eng.lane_of.pop(res["id"], None)
        for user in list(eng.sessions.users):
            eng.sessions.take(user)
        self.history.clear()
        self.lanes.clear()


def warm_up(eng, lanes: int, vocab: int) -> None:
    """Every program the window runs: a cold insert into each lane, the
    step, an evict from each lane, and a restore into each lane."""
    from repro.launch.engine import Request
    rng = np.random.default_rng(0)
    for _ in range(2):
        for i in range(lanes):
            eng.submit(Request(user=f"__warm{i}",
                               prompt=rng.integers(1, vocab, 2).tolist(),
                               max_new_tokens=2))
        while eng.scheduler.has_work:
            eng.step()
    for i in range(lanes):
        eng.sessions.take(f"__warm{i}")
    eng.lane_of.clear()
    jax.block_until_ready((eng.cache, eng.mem))


def nearest_rank(values, p: float):
    """The p-th percentile by nearest rank: the smallest value with at
    least p% of the values at or below it. None for no values."""
    if not len(values):
        return None
    v = np.sort(np.asarray(values, np.float64))
    return float(v[max(0, math.ceil(p / 100.0 * len(v)) - 1)])


def end_to_end(win: Window) -> dict:
    """tok_s over the whole window, and the latency samples: the time to
    first token of every request whose first token came in the window,
    from its scheduled send time, and every gap between two output tokens
    of one request."""
    ttft = [(r.token_times[0] - r.sent) * 1e3
            for r in win.records if r.token_times]
    gaps = []
    for r in win.records:
        t = r.token_times
        gaps += [(b - a) * 1e3 for a, b in zip(t, t[1:])]
    return dict(tok_s=win.tokens / win.seconds, ttft_ms=ttft, itl_ms=gaps)
