"""The continuous-batching serving engine.

A `ServeEngine` owns a fixed number of batch *lanes* (the device batch
dimension), one compiled step function (launch/engine/stepfn.py), and a
`SessionStore` of per-user persistent state. Each `step()`:

1. admits queued requests into free lanes (scheduler, FIFO) — a lane
   freed by an eviction is refillable on the same step;
2. runs one jitted decode step for the whole batch (every lane advances:
   prompt token while prefilling, else its previously emitted token);
3. updates per-request progress and evicts finished lanes, snapshotting
   each finished user's session (KV-cache rows + position + SAM memory
   states + token counter) into the session store.

A user's next request *resumes* their session: the stored KV cache,
position, and memory state re-enter whichever lane the scheduler picks,
and decode continues as if never interrupted. Sessions are stored in the
canonical single-shard memory layout and re-laid-out to the live mesh's
shard count on admission (`elastic.relayout_memory_state` — the same
cross-mesh machinery a checkpoint restore uses), so a session saved by a
single-device engine restores into a mesh engine and vice versa. Row
indices (`read_idx`) need no conversion: they are *global* slot ids in
[0, N) under every layout (the mem_shard module contract).

Every step and every session move records a profiler span, and the
engine keeps host counters in `stats` (launch/engine/telemetry.py).

Determinism contract (tested in tests/test_serve_engine.py): every decode
and memory op is per-batch-row and sampling keys derive from
(request seed, session token counter) only, so a request's token stream
and final memory state are bit-identical whether it ran uninterrupted or
was evicted and restored across engine instances, whatever lanes it
landed in and whoever its batch neighbours were.
"""
from __future__ import annotations

import contextlib
import time
import warnings
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.distributed import elastic, mem_shard
from repro.distributed.sharding import mesh_rules
from repro.models import lm
from repro.launch.engine.scheduler import Request, Scheduler
from repro.launch.engine.sessions import SessionStore
from repro.launch.engine.telemetry import EngineStats, span, tree_nbytes
from repro.launch.engine.stepfn import (make_engine_step, make_lane_insert,
                                        make_prefill_scan)


class ServeEngine:
    """Continuous-batching server for one model over `lanes` batch lanes.

    ``mesh=`` serves under a (data, model) mesh: logical-axis sharding
    rules activate for the transformer stack and, for SAM-augmented
    archs, the slot-sharded mesh-native memory path
    (`mem_shard.memory_mesh` — on a 2D mesh the lane/batch dimension
    additionally shards over the data axes). Use as a context manager (or
    call ``close()``) so the mesh contexts unwind.

    ``replicas`` makes the engine multi-replica: lanes split into equal
    per-replica pools and the scheduler keeps session-to-replica affinity
    (launch/engine/scheduler.py). It defaults to the mesh's data degree —
    one serving replica per data shard, so a replica's lane pool is
    exactly the batch block that data shard holds — or 1 without a mesh
    (replicas are a host-side scheduling concept, so a single-device
    engine can run many). `rescale()` is the live join/leave event.

    ``session_capacity``/``spill_dir`` bound the in-RAM session store
    with LRU disk spill (launch/engine/sessions.py).
    """

    def __init__(self, cfg, *, lanes: int = 4, max_len: int = 128,
                 param_seed: int = 0, mesh=None,
                 replicas: Optional[int] = None,
                 session_capacity: Optional[int] = None,
                 spill_dir: Optional[str] = None,
                 session_store: Optional[SessionStore] = None):
        if cfg.frontend == "audio":
            raise NotImplementedError(
                "the serving engine feeds token ids, not audio frames")
        self.cfg = cfg
        self.lanes = lanes
        self.max_len = max_len
        self.mesh = mesh
        self._stack = contextlib.ExitStack()
        self._enter_mesh(mesh)
        self.replicas = self._resolve_replicas(lanes, mesh, replicas)

        self.param_seed = param_seed
        self._init_params()
        self._build_batch(lanes)

        self.scheduler = Scheduler(lanes, replicas=self.replicas)
        self.sessions = session_store if session_store is not None else \
            SessionStore(
                num_slots=cfg.memory.num_slots if cfg.memory else None,
                capacity=session_capacity, spill_dir=spill_dir)
        self._out: dict[int, list] = {}             # request id -> tokens
        self.stats = EngineStats()

    def _init_params(self) -> None:
        """Random weights from ``param_seed`` in the compute dtype (serving
        keeps no optimizer state, so there is no f32 master copy and no
        per-step cast), placed on the live mesh by their logical axes."""
        self.params = lm.init_params(jax.random.PRNGKey(self.param_seed),
                                     self.cfg, dtype=self.cfg.compute_dtype,
                                     mesh=self.mesh)

    def _enter_mesh(self, mesh) -> None:
        if mesh is not None:
            self._stack.enter_context(mesh_rules(mesh))
            if self.cfg.memory is not None:
                self._stack.enter_context(
                    mem_shard.memory_mesh(mesh, self.cfg.memory.num_slots))

    @staticmethod
    def _mesh_data_degree(mesh) -> int:
        d = 1
        if mesh is not None:
            for a in ("pod", "data"):
                if a in mesh.axis_names:
                    d *= int(mesh.shape[a])
        return d

    def _resolve_replicas(self, lanes: int, mesh,
                          replicas: Optional[int]) -> int:
        if replicas is None:
            d = self._mesh_data_degree(mesh)
            if d > 1 and lanes % d:
                warnings.warn(
                    f"mesh data degree {d} does not divide lanes={lanes} — "
                    f"serving single-replica (pass lanes divisible by the "
                    f"data degree, or an explicit replicas=)",
                    UserWarning, stacklevel=3)
                return 1
            return d
        if replicas < 1 or lanes % replicas:
            raise ValueError(
                f"lanes={lanes} must split evenly over replicas={replicas}")
        return replicas

    def _build_batch(self, lanes: int) -> None:
        """(Re)build everything whose shape carries the lane count: the
        batched device state, the jitted step functions (fresh, so no jit
        cache entry traced under a previous mesh context can leak into the
        new one), and the host-side per-lane registers."""
        cfg = self.cfg
        self.lanes = lanes
        self.cache = lm.init_cache(cfg, lanes, self.max_len,
                                   per_lane_pos=True)
        # Memory leaves are born in the live shard layout; place them on
        # the mesh too (slot-sharded), not on the default device.
        self.mem = mem_shard.place_state(
            lm.init_memory_states(cfg, lanes, per_lane_step=True))
        self._step_fn = make_engine_step(cfg)
        self._prefill_fn = make_prefill_scan(cfg)
        self._insert_fn = make_lane_insert(cfg)
        # Cold-session template, built once (inside the mesh contexts, so
        # memory leaves are born in the live layout): admission inserts it
        # with the same single jitted dispatch a warm restore uses.
        self._fresh_cache = {k: jnp.zeros_like(v[:, :1])
                             for k, v in self.cache.items() if k != "pos"}
        self._zero_pos = jnp.zeros((1,), jnp.int32)
        self._fresh_mem = None if self.mem is None else \
            lm.init_memory_states(cfg, 1, per_lane_step=True)

        # Host-side per-lane registers (what the next jitted step consumes).
        self._feed = np.zeros(lanes, np.int32)      # next input token
        self._greedy = np.ones(lanes, bool)
        self._seeds = np.zeros(lanes, np.int32)
        self._counters = np.zeros(lanes, np.int32)  # session token counters

    # -- lifecycle ---------------------------------------------------------

    def close(self):
        self._stack.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    @property
    def _live_shards(self) -> int:
        ctx = mem_shard.current()
        if ctx is not None and self.cfg.memory is not None \
                and ctx.num_slots == self.cfg.memory.num_slots:
            return ctx.shards
        return 1

    @property
    def steps(self) -> int:
        return self.stats.steps

    # -- request API -------------------------------------------------------

    def submit(self, req: Request) -> Request:
        if not req.prompt:
            raise ValueError("a request needs at least one prompt token")
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if req.arrival == 0.0:
            req.arrival = time.time()
        return self.scheduler.submit(req)

    def step(self) -> list:
        """Advance the batch one token; returns results of any requests
        that finished this step (possibly empty)."""
        with span("serve.step", step_num=self.stats.steps,
                  lanes=len(self.scheduler.active)):
            return self._step()

    def _step(self) -> list:
        for lane, req in self.scheduler.admit():
            self._admit_lane(lane, req)
        if not self.scheduler.active:
            return []
        self._prefill_scan_hop()

        with span("serve.dispatch"):
            next_tok, logits, self.cache, self.mem = self._step_fn(
                self.params, self.cache, self.mem, *self._step_inputs())
        self.last_logits = logits     # (lanes, V); tests probe neighbours
        # Block on the sampled tokens: the tail-latency numbers the bench
        # records must measure compute, not JAX's async dispatch queue.
        with span("serve.wait"):
            toks = np.asarray(next_tok)
        now = time.time()
        self.stats.steps += 1

        finished = []
        for lane in sorted(self.scheduler.active):
            req = self.scheduler.active[lane]
            self._counters[lane] += 1
            if req.prefilling:
                req.prefill_done += 1
                if req.prefilling:            # more prompt to feed
                    self._feed[lane] = req.prompt[req.prefill_done]
                    continue
                req.first_token_time = now    # last prompt token consumed:
            req.generated += 1                # this step's output counts
            self._out[req.id].append(int(toks[lane]))
            self._feed[lane] = toks[lane]
            if req.done:
                req.finish_time = now
                self._evict_lane(lane)
                finished.append(self._result(req))
        return finished

    def _step_inputs(self):
        """The per-lane host registers as the step's device inputs."""
        return (jnp.asarray(self._feed[:, None]), jnp.asarray(self._greedy),
                jnp.asarray(self._seeds), jnp.asarray(self._counters))

    def trace_step(self):
        """The engine step traced for the live batch state (a
        `jax.stages.Traced`): its ``.jaxpr``, and ``.lower().compile()``
        for an ahead-of-time compile whose program can be read (which
        kernels it dispatches, its memory analysis)."""
        return self._step_fn.trace(self.params, self.cache, self.mem,
                                   *self._step_inputs())

    def run(self, requests=None) -> list:
        """Submit `requests` (optional) and step until the queue and all
        lanes drain; returns results in completion order."""
        for r in requests or []:
            self.submit(r)
        results = []
        while self.scheduler.has_work:
            results.extend(self.step())
        return results

    # -- elastic scale events ----------------------------------------------

    _KEEP = object()      # rescale sentinel: "keep the current mesh"

    def rescale(self, *, replicas: Optional[int] = None, mesh=_KEEP,
                lanes: Optional[int] = None) -> None:
        """Live join/leave elastic event: change the replica count (and
        optionally the mesh) **without restarting any episode**.

        Every in-flight request is parked through the ordinary eviction
        path — its lane snapshots into the `SessionStore` in the canonical
        layout, exactly like a finished request — the device batch is
        rebuilt at the new lane count under the new mesh contexts, and the
        parked requests re-enter the queue (in submission order, ahead of
        the waiting backlog) with their progress intact. Re-admission
        restores each session with `elastic.relayout_memory_state` to the
        new live shard count, so the determinism contract (module
        docstring) makes the continuation bit-exact: the token streams and
        final memory states are identical to an uninterrupted run.

        ``lanes`` defaults to keeping the per-replica lane count fixed —
        a replica joining/leaving adds/removes its lane pool. ``replicas``
        defaults to the (new) mesh's data degree, like the constructor."""
        per_replica = self.lanes // self.replicas
        inflight = [self.scheduler.active[lane]
                    for lane in sorted(self.scheduler.active)]
        inflight.sort(key=lambda r: r.id)
        for lane in sorted(self.scheduler.active):
            self._evict_lane(lane)
        queued = list(self.scheduler.queue)
        old = self.scheduler

        if mesh is not ServeEngine._KEEP:
            self.mesh = mesh
            self._stack.close()
            self._stack = contextlib.ExitStack()
            self._enter_mesh(mesh)
            self._init_params()
        if replicas is None:
            replicas = self._mesh_data_degree(self.mesh)
        if lanes is None:
            lanes = per_replica * replicas
        self.replicas = self._resolve_replicas(lanes, self.mesh, replicas)
        self._build_batch(lanes)

        sched = Scheduler(lanes, replicas=self.replicas)
        sched._ids = old._ids         # request ids stay globally unique
        sched.affinity = {u: r for u, r in old.affinity.items()
                          if r < self.replicas}
        for req in inflight:
            sched.queue.append(req)
        for req in queued:
            sched.queue.append(req)
        self.scheduler = sched

    # -- lane <-> session movement ----------------------------------------

    def _admit_lane(self, lane: int, req: Request) -> None:
        warm = req.user in self.sessions
        with span("serve.admit", req=req.id, lane=lane, warm=warm):
            self._admit(lane, req)
        if warm:
            self.stats.admits_warm += 1
        else:
            self.stats.admits_cold += 1

    def _admit(self, lane: int, req: Request) -> None:
        # Validate against the *stored* session before taking it: a
        # rejected request must leave the session in the store and hand
        # the lane back to the scheduler — previously `take` had already
        # removed the session and the raise left the lane occupied with
        # no way to free it. The budget counts only the *remaining* prompt
        # and generation, so a request resuming after a rescale (progress
        # already in `pos`) is not double-counted.
        sess = self.sessions.peek(req.user)
        pos = 0 if sess is None else int(np.asarray(sess["pos"])[0])
        need = (len(req.prompt) - req.prefill_done
                + req.max_new_tokens - req.generated)
        if pos + need > self.max_len and self.cfg.window is None:
            self.scheduler.evict(lane)
            raise ValueError(
                f"user {req.user!r}: session at position {pos} cannot fit "
                f"{len(req.prompt)} prompt + {req.max_new_tokens} new "
                f"tokens in max_len={self.max_len}")
        sess = self.sessions.take(req.user)
        if sess is None:
            self._reset_lane(lane)
        else:
            self._restore_lane(lane, sess)
        # A fresh request feeds its first prompt token; one resuming after
        # a rescale feeds wherever it stopped — the next prompt token, or
        # mid-generation the last token it emitted.
        self._out.setdefault(req.id, [])
        self._feed[lane] = (req.prompt[req.prefill_done] if req.prefilling
                            else self._out[req.id][-1])
        self._greedy[lane] = req.greedy
        self._seeds[lane] = req.sample_seed

    def _reset_lane(self, lane: int) -> None:
        """Cold session: zero KV rows, position 0, fresh memory state —
        including a cold (empty) ANN index for cells that carry one. One
        jitted dispatch (`make_lane_insert`), not one per state leaf."""
        with span("serve.reset"), span("session.insert"):
            self.cache, self.mem = self._insert_fn(
                self.cache, self.mem, lane, self._fresh_cache,
                self._zero_pos, self._fresh_mem)
        self._counters[lane] = 0

    def _restore_lane(self, lane: int, sess) -> None:
        """Warm session: re-lay the canonical-layout session out to the
        live shard count and insert it into `lane` — one jitted dispatch,
        like the cold reset."""
        with span("serve.restore"):
            mem = None
            if self.mem is not None:
                with span("session.relayout"):
                    mem = elastic.relayout_memory_state(
                        sess["mem"], self.cfg.memory.num_slots,
                        self._live_shards)
            with span("session.insert"):
                self.cache, self.mem = self._insert_fn(
                    self.cache, self.mem, lane, sess["cache"],
                    jnp.asarray(sess["pos"]), mem)
        self._counters[lane] = int(sess["counter"])
        self.stats.bytes_to_device += tree_nbytes(
            (sess["cache"], sess["pos"], mem))

    def _prefill_scan_hop(self) -> None:
        """Scan the shared mid-prompt stretch in one dispatch.

        Fires only when the queue is drained and *every* active request is
        still prefilling, and stops one token short of the shortest
        remaining prompt — so every emission boundary (last prompt token,
        first sampled token, `first_token_time`, logits bookkeeping) stays
        on the ordinary 1-token step path. Continuous batching is
        untouched: the hop replaces exactly n ordinary steps with one
        `lax.scan` dispatch (`make_prefill_scan`) and advances `steps`,
        counters, and prompt cursors by the same n."""
        reqs = self.scheduler.active
        if self.scheduler.queue or not reqs:
            return
        if any(not r.prefilling for r in reqs.values()):
            return
        n = min(len(r.prompt) - r.prefill_done for r in reqs.values()) - 1
        if n < 1:
            return
        feed = np.zeros((self.lanes, n), np.int32)
        for lane, r in reqs.items():
            feed[lane] = r.prompt[r.prefill_done:r.prefill_done + n]
        with span("serve.hop", n=n):
            self.cache, self.mem = self._prefill_fn(
                self.params, self.cache, self.mem, jnp.asarray(feed))
        self.stats.hop_dispatches += 1
        self.stats.steps += n
        for lane, r in reqs.items():
            self._counters[lane] += n
            r.prefill_done += n
            self._feed[lane] = r.prompt[r.prefill_done]

    def _evict_lane(self, lane: int) -> None:
        req = self.scheduler.evict(lane)
        with span("serve.evict", req=req.id, lane=lane):
            self._evict(lane, req)
        self.stats.evictions += 1

    def _evict(self, lane: int, req: Request) -> None:
        with span("session.slice"):
            sess = {
                "cache": {k: v[:, lane:lane + 1]
                          for k, v in self.cache.items() if k != "pos"},
                "pos": self.cache["pos"][lane:lane + 1],
                "counter": int(self._counters[lane]),
            }
            if self.mem is not None:
                # No index remap needed: row indices (read_idx) are
                # *global* slot ids in [0, N) under every layout (mem_shard
                # module contract) — only the memory/usage buffers are
                # re-laid-out.
                sess["mem"] = tuple(
                    jax.tree.map(lambda t: t[lane:lane + 1], st)
                    for st in self.mem)
        self.stats.bytes_to_host += self.sessions.put(req.user, sess)

    def _result(self, req: Request) -> dict:
        return {
            "id": req.id,
            "user": req.user,
            "tokens": self._out.pop(req.id),
            "prompt_len": len(req.prompt),
            "arrival": req.arrival,
            "first_token_time": req.first_token_time,
            "finish_time": req.finish_time,
        }
