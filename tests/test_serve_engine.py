"""Continuous-batching serving engine (launch/engine): scheduler unit
tests, engine e2e coverage (greedy + sampled, lane churn, cold-session
admission), and the evict/restore determinism contract — a user served
across two engine instances with an evict + session-store restore in
between produces bit-identical memory state and identical tokens to an
uninterrupted decode. The mesh-marked variants run the same contract on
an 8-way forced host mesh (driver subprocess, mirroring the mesh parity
lane)."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.launch.engine import Request, Scheduler, ServeEngine, SessionStore

ARCH = "h2o_danube_3_4b_sam"


def _cfg():
    return reduced(get_config(ARCH))


# ----------------------------- scheduler ---------------------------------

def _reqs(n, user=None, **kw):
    kw.setdefault("prompt", [1])
    kw.setdefault("max_new_tokens", 1)
    return [Request(user=user or f"u{i}", **kw) for i in range(n)]


def test_scheduler_fifo_admission_order():
    s = Scheduler(lanes=2)
    reqs = _reqs(5)
    for r in reqs:
        s.submit(r)
    admitted = s.admit()
    assert [(l, r.user) for l, r in admitted] == [(0, "u0"), (1, "u1")]
    assert s.admit() == []                   # batch full
    assert s.free_lanes == 0
    s.evict(0)
    assert s.free_lanes == 1
    # The freed lane refills with the *next* submission, same step.
    assert [(l, r.user) for l, r in s.admit()] == [(0, "u2")]


def test_scheduler_reuses_lowest_freed_lane():
    s = Scheduler(lanes=3)
    for r in _reqs(3):
        s.submit(r)
    s.admit()
    s.evict(2)
    s.evict(0)
    for u in ("v0", "v1"):
        s.submit(Request(user=u, prompt=[2], max_new_tokens=1))
    lanes = [l for l, _ in s.admit()]
    assert lanes == [0, 2]                   # deterministic, lowest first


def test_scheduler_no_starvation_under_full_batch():
    """Under a persistently full batch, every request is eventually served
    and (distinct users) in exactly submission order."""
    s = Scheduler(lanes=2)
    for r in _reqs(20):
        s.submit(r)
    served = []
    for _ in range(100):
        for lane, req in s.admit():
            served.append(req.user)
        for lane in list(s.active):
            s.evict(lane)                    # each request takes one "step"
        if not s.has_work:
            break
    assert served == [f"u{i}" for i in range(20)]


def test_scheduler_holds_back_active_user():
    """A request for a user already live in a lane is deferred (one live
    lane per user), later users may overtake it, and the deferred request
    admits as soon as the user's lane frees."""
    s = Scheduler(lanes=2)
    a1, a2 = Request("a", [1], 1), Request("a", [2], 1)
    b, c = Request("b", [1], 1), Request("c", [1], 1)
    for r in (a1, a2, b, c):
        s.submit(r)
    admitted = s.admit()
    assert [(l, r.user) for l, r in admitted] == [(0, "a"), (1, "b")]
    s.evict(1)                               # b done; a still active
    assert [(l, r.user) for l, r in s.admit()] == [(1, "c")]  # c overtakes a2
    s.evict(0)                               # a's first request done
    s.evict(1)
    admitted = s.admit()
    assert [(l, r.prompt) for l, r in admitted] == [(0, [2])]  # a2 at last


def test_scheduler_replica_pools_and_affinity():
    """Multi-replica lane pools: eviction records the user's replica, a
    returning user's request prefers a free lane in that replica's pool,
    and falls back to the lowest free lane anywhere when the pool is
    full. FIFO admission over requests is unchanged."""
    with pytest.raises(ValueError, match="split evenly"):
        Scheduler(lanes=5, replicas=2)
    s = Scheduler(lanes=4, replicas=2)
    assert s.lanes_per_replica == 2
    for r in _reqs(4):
        s.submit(r)
    s.admit()
    s.evict(2)                                # u2 lived in replica 1
    assert s.affinity["u2"] == 1
    s.evict(0)                                # u0 lived in replica 0
    # u2 returns: lane 0 is the lowest free lane, but affinity steers the
    # request into replica 1's pool (lane 2).
    s.submit(Request(user="u2", prompt=[1], max_new_tokens=1))
    assert [(l, r.user) for l, r in s.admit()] == [(2, "u2")]
    # Replica-1 pool now full again; a second replica-1-affine user falls
    # back to the lowest free lane anywhere (lane 0, replica 0).
    s.affinity["u9"] = 1
    s.submit(Request(user="u9", prompt=[1], max_new_tokens=1))
    assert [(l, r.user) for l, r in s.admit()] == [(0, "u9")]


# ----------------------------- engine e2e --------------------------------

def test_engine_greedy_and_sampled_modes():
    cfg = _cfg()
    def run(greedy, seed):
        with ServeEngine(cfg, lanes=2, max_len=64) as eng:
            return eng.run([Request(user="u", prompt=[3, 7], max_new_tokens=4,
                                    greedy=greedy, sample_seed=seed)]
                           )[0]["tokens"]
    g1, g2 = run(True, 0), run(True, 0)
    s1, s2 = run(False, 1), run(False, 1)
    s3 = run(False, 2)
    assert g1 == g2 and s1 == s2             # both modes deterministic
    assert len(s1) == 4
    assert s1 != g1 or s3 != g1              # sampling actually samples


def test_engine_refills_lane_on_finish_step():
    """3 equal-length requests over 2 lanes: the third admits the moment a
    lane frees, so total steps = 2 waves, not 3."""
    cfg = _cfg()
    with ServeEngine(cfg, lanes=2, max_len=64) as eng:
        res = eng.run(_reqs(3, prompt=[2, 3], max_new_tokens=2))
    assert len(res) == 3
    # 3 steps per request (the last prompt step emits the first token);
    # 2 back-to-back waves = 6. A refill delayed by even one step -> 7.
    assert eng.steps == 6


def test_cold_session_mid_batch_is_fresh_and_isolated():
    """A brand-new user admitted into a lane another user just vacated
    must start from zero state (no phantom reads of the previous
    occupant's memory) and must not perturb a neighbour lane's decode:
    the long-running neighbour's tokens match a churn-free run, and the
    cold user's tokens match the same user served alone in a fresh
    engine."""
    cfg = _cfg()
    long_req = lambda: Request(user="long", prompt=[5, 9], max_new_tokens=10,
                               greedy=True)
    # Reference: the long user alone, no churn.
    with ServeEngine(cfg, lanes=2, max_len=64) as eng:
        ref_long = eng.run([long_req()])[0]["tokens"]
    # Reference: the cold user alone in a fresh engine (lane 1 empty).
    cold_req = lambda: Request(user="cold", prompt=[11], max_new_tokens=3,
                               greedy=True)
    with ServeEngine(cfg, lanes=2, max_len=64) as eng:
        ref_cold = eng.run([cold_req()])[0]["tokens"]
    # Churn run: lane 1 serves two other users, then the cold user lands
    # in the dirty lane while "long" is still mid-decode in lane 0.
    with ServeEngine(cfg, lanes=2, max_len=64) as eng:
        res = eng.run([long_req(),
                       Request(user="x", prompt=[4, 4], max_new_tokens=2),
                       Request(user="y", prompt=[8], max_new_tokens=2),
                       cold_req()])
    by_user = {r["user"]: r["tokens"] for r in res}
    assert by_user["long"] == ref_long       # neighbour unperturbed
    assert by_user["cold"] == ref_cold       # fresh zero state, no leaks


# ----------------------- evict/restore determinism -----------------------

def _mem_equal(a, b):
    for sa, sb in zip(a, b):
        for name in sa._fields:
            f, s = np.asarray(getattr(sa, name)), np.asarray(getattr(sb, name))
            if f.shape != s.shape or not (f == s).all():
                return False, name
    return True, None


def _determinism_roundtrip(mesh=None, cfg=None):
    """Serve user "u" (sampled) 8 tokens uninterrupted vs 4 + 4 across two
    engine instances sharing a SessionStore, with different neighbours and
    lanes each time. Returns both token streams and both final sessions."""
    cfg = cfg if cfg is not None else _cfg()
    P = [3, 7, 11, 2]
    u = dict(user="u", greedy=False, sample_seed=42)

    with ServeEngine(cfg, lanes=3, max_len=64, mesh=mesh) as e1:
        full = e1.run([Request(prompt=P, max_new_tokens=8, **u),
                       Request(user="noise", prompt=[9, 9], max_new_tokens=6,
                               greedy=False, sample_seed=7)])
        tok_full = [r for r in full if r["user"] == "u"][0]["tokens"]
        sess_full = e1.sessions.take("u")

    store = SessionStore(num_slots=cfg.memory.num_slots)
    with ServeEngine(cfg, lanes=3, max_len=64, mesh=mesh,
                     session_store=store) as a:
        r1 = a.run([Request(prompt=P, max_new_tokens=4, **u)])
    t4 = r1[0]["tokens"][-1]
    with ServeEngine(cfg, lanes=3, max_len=64, mesh=mesh,
                     session_store=store) as b:
        b.submit(Request(user="other", prompt=[1, 2, 3], max_new_tokens=9,
                         greedy=False, sample_seed=5))  # takes lane 0 first
        r2 = b.run([Request(prompt=[t4], max_new_tokens=4, **u)])
        tok_split = (r1[0]["tokens"]
                     + [r for r in r2 if r["user"] == "u"][0]["tokens"])
        sess_split = b.sessions.take("u")
    return tok_full, sess_full, tok_split, sess_split


def _assert_roundtrip_deterministic(mesh=None, cfg=None):
    tok_full, sess_full, tok_split, sess_split = _determinism_roundtrip(
        mesh, cfg)
    assert tok_full == tok_split
    ok, leaf = _mem_equal(sess_full["mem"], sess_split["mem"])
    assert ok, f"memory leaf {leaf!r} diverged across evict/restore"
    assert int(sess_full["pos"][0]) == int(sess_split["pos"][0])
    assert sess_full["counter"] == sess_split["counter"]


def test_evict_restore_determinism_single_device():
    _assert_roundtrip_deterministic(mesh=None)


def test_evict_restore_determinism_pallas_backend():
    """The engine on a Pallas-backed memory config (regression: it used to
    refuse anything but the ref backend because the fused write kernel
    could not take per-lane session steps). Same bit-exact evict/restore
    contract, now through the fused kernels."""
    import dataclasses
    cfg = _cfg()
    cfg = dataclasses.replace(cfg, memory=dataclasses.replace(
        cfg.memory, backend="pallas-interpret"))
    _assert_roundtrip_deterministic(cfg=cfg)


def test_rejected_request_keeps_session_and_lane():
    """Admission rejection (session + prompt + budget exceeds max_len) must
    be loss-free: the stored session survives untouched and the lane goes
    back to the scheduler. Regression: `take` ran before validation, so a
    rejected request silently destroyed the user's session and leaked the
    lane (it stayed occupied with no way to free it)."""
    import dataclasses
    cfg = dataclasses.replace(_cfg(), window=None)
    with ServeEngine(cfg, lanes=2, max_len=16) as eng:
        eng.run([Request(user="u", prompt=[3, 7], max_new_tokens=4,
                         greedy=True)])
        pos_before = int(np.asarray(eng.sessions.peek("u")["pos"])[0])
        eng.submit(Request(user="u", prompt=[5], max_new_tokens=16))
        with pytest.raises(ValueError, match="cannot fit"):
            eng.run()
        assert "u" in eng.sessions          # session not consumed
        assert int(np.asarray(eng.sessions.peek("u")["pos"])[0]) == pos_before
        assert eng.scheduler.free_lanes == 2  # lane returned, refillable
        res = eng.run([Request(user="u", prompt=[2], max_new_tokens=2,
                               greedy=True)])
        assert len(res) == 1 and len(res[0]["tokens"]) == 2


def test_engine_live_rescale_is_bit_exact():
    """`rescale()` without a mesh: shrink a 2-replica engine to 1 replica
    mid-decode (parking the in-flight sampled request through the session
    store), then grow back to 2 replicas and serve a follow-up. Token
    streams and the final stored session are bit-identical to an
    uninterrupted run — the live scale event is invisible to every user.
    Request ids keep counting across the rebuild (no reuse)."""
    cfg = _cfg()
    P1, P2 = [3, 7, 11, 2], [5]
    u = dict(user="u", greedy=False, sample_seed=42)
    noise = lambda: Request(user="noise", prompt=[9, 9], max_new_tokens=6,
                            greedy=False, sample_seed=7)

    with ServeEngine(cfg, lanes=4, max_len=64, replicas=2) as ref:
        r1 = ref.run([Request(prompt=P1, max_new_tokens=8, **u), noise()])
        tok_ref = [r for r in r1 if r["user"] == "u"][0]["tokens"]
        tok_ref2 = ref.run([Request(prompt=P2, max_new_tokens=4, **u)]
                           )[0]["tokens"]
        sess_ref = ref.sessions.take("u")

    with ServeEngine(cfg, lanes=4, max_len=64, replicas=2) as eng:
        eng.submit(Request(prompt=P1, max_new_tokens=8, **u))
        eng.submit(noise())
        done = []
        for _ in range(6):                    # prefill + a few decode steps
            done.extend(eng.step())
        assert any(r.user == "u" for r in eng.scheduler.active.values())
        eng.rescale(replicas=1)               # leave
        assert eng.replicas == 1 and eng.lanes == 2
        while eng.scheduler.has_work:
            done.extend(eng.step())
        tok_live = [r for r in done if r["user"] == "u"][0]["tokens"]
        eng.rescale(replicas=2, lanes=4)      # join
        follow = eng.submit(Request(prompt=P2, max_new_tokens=4, **u))
        assert follow.id > max(r["id"] for r in done)   # ids never reused
        tok_live2 = eng.run()[0]["tokens"]
        sess_live = eng.sessions.take("u")

    assert tok_live == tok_ref
    assert tok_live2 == tok_ref2
    ok, leaf = _mem_equal(sess_ref["mem"], sess_live["mem"])
    assert ok, f"memory leaf {leaf!r} diverged across the rescale"
    assert int(sess_ref["pos"][0]) == int(sess_live["pos"][0])
    assert sess_ref["counter"] == sess_live["counter"]


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 devices (forced host lane runs the "
                           "driver below)")
def test_evict_restore_determinism_mesh():
    from repro.launch.mesh import make_memory_mesh
    _assert_roundtrip_deterministic(mesh=make_memory_mesh(8))


@pytest.mark.skipif(jax.device_count() >= 8,
                    reason="8 devices visible: the mesh variant runs "
                           "natively in this session")
@pytest.mark.skipif(bool(os.environ.get("REPRO_SKIP_MESH_DRIVER")),
                    reason="a dedicated forced-8-device mesh lane runs "
                           "this file (CI)")
def test_serve_determinism_on_forced_host_mesh():
    """Driver: re-run this file's mesh-marked determinism test in a
    subprocess with a forced 8-device host platform (the slot-sharded
    mesh-native memory path under the engine)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q",
         os.path.join(os.path.dirname(__file__), "test_serve_engine.py"),
         "-k", "determinism_mesh"],
        env=env, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, \
        f"mesh determinism failed:\n{proc.stdout[-4000:]}\n{proc.stderr[-2000:]}"


# --------------------------- legacy driver -------------------------------

def test_legacy_serve_threads_greedy_flag():
    """`serve(greedy=...)` reaches the decode loop (regression: the flag
    was accepted and dropped). Greedy runs are reproducible; sampling
    draws a different stream."""
    from repro.launch.serve import serve
    kw = dict(batch=2, prompt_len=3, gen_len=4, max_len=16, seed=0)
    g1 = np.asarray(serve("h2o_danube_3_4b", greedy=True, **kw)["tokens"])
    g2 = np.asarray(serve("h2o_danube_3_4b", greedy=True, **kw)["tokens"])
    s1 = np.asarray(serve("h2o_danube_3_4b", greedy=False, **kw)["tokens"])
    s2 = np.asarray(serve("h2o_danube_3_4b", greedy=False, **kw)["tokens"])
    assert g1.shape == s1.shape == (2, 4)
    assert (g1 == g2).all() and (s1 == s2).all()
    assert (g1 != s1).any(), "sampled decode returned the argmax stream"


def test_serve_continuous_entrypoint():
    from repro.launch.serve import serve_continuous
    res = serve_continuous(ARCH, lanes=2, requests=3, prompt_len=2,
                           gen_len=2, max_len=32)
    assert len(res["results"]) == 3
    assert all(len(r["tokens"]) == 2 for r in res["results"])
    assert res["tok_per_s"] > 0
    st = res["stats"]
    assert (st["admits_cold"], st["admits_warm"], st["evictions"]) == (3, 0, 3)
    assert st["steps"] == res["steps"] and st["bytes_to_host"] > 0
