"""The program's host spans and counters, under the benchmark's engine.

A tiny engine serves one user a cold turn and then a warm turn under the
profiler, with one hot session allowed, so that the warm turn's session
comes back from disk. The trace holds every span the program names, each
inside its parent, carrying the request's id; the engine's counters agree
with what moved; the harness's own spans still wrap the program's."""
import glob
import os

import jax
import pytest

import chipbench_tiny as tiny
from bench import cell, serving
from repro.launch.engine import Request, telemetry

# Each span's parents: where the engine records it. The test also calls
# the store directly once, inside its own span ("test.park").
PARENTS = {
    "serve.admit": ("serve.step",), "serve.hop": ("serve.step",),
    "serve.dispatch": ("serve.step",), "serve.wait": ("serve.step",),
    "serve.evict": ("serve.step",),
    "serve.restore": ("serve.admit",), "serve.reset": ("serve.admit",),
    "session.unspill": ("serve.admit",),
    "session.relayout": ("serve.restore", "session.put"),
    "session.insert": ("serve.restore", "serve.reset"),
    "session.slice": ("serve.evict",), "session.put": ("serve.evict",),
    "session.to_host": ("session.put",), "session.spill": ("session.put",),
}
PARK = "test.park"


def _events(directory):
    path, = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in telemetry.SPANS + (PARK, "engine.admit",
                                                    "engine.evict"):
                        s = int(e.start_ns)
                        out.append((e.name, s, s + int(e.duration_ns),
                                    dict(e.stats)))
    return out


def _within(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    cfg_spec = tiny.config(lanes=2)
    cfg = cell.program_config(cfg_spec)
    Engine = serving.program_engine_class(prefill_hop=True)
    store = str(tmp_path_factory.mktemp("spill"))
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    eng = Engine(cfg, make_params=serving.params_maker(
        2 ** 31 + 3, cfg_spec["model"], cfg_spec["memory"]),
        lanes=2, max_len=64, session_capacity=1, spill_dir=store)
    sizes = []
    with jax.profiler.trace(trace_dir):
        cold = eng.submit(Request(user="u", prompt=[5, 6, 7, 8],
                                  max_new_tokens=3))
        eng.run()
        sizes.append(telemetry.tree_nbytes(eng.sessions.peek("u")))
        with jax.profiler.TraceAnnotation(PARK):
            # A second session pushes the user's out of memory, to disk.
            eng.sessions.put("parked", eng.sessions.peek("u"))
            eng.sessions.take("parked")
        warm = eng.submit(Request(user="u", prompt=[9, 10, 11],
                                  max_new_tokens=2))
        eng.run()
        sizes.append(telemetry.tree_nbytes(eng.sessions.peek("u")))
    yield dict(eng=eng, events=_events(trace_dir), cold=cold, warm=warm,
               sizes=sizes)
    eng.close()


def test_every_span_is_recorded_inside_its_parent(served):
    evs = served["events"]
    names = {e[0] for e in evs}
    assert set(telemetry.SPANS) <= names
    assert set(PARENTS) | {"serve.step"} == set(telemetry.SPANS)
    parks = [e for e in evs if e[0] == PARK]
    for e in evs:
        if e[0] not in PARENTS:
            continue
        if e[0] == "session.put" and any(_within(e, p) for p in parks):
            continue                      # the test's own call to the store
        assert any(_within(e, p) for p in evs if p[0] in PARENTS[e[0]]), e
    # The harness's spans still wrap the program's: its overrides run.
    for mine, harness in (("serve.admit", "engine.admit"),
                          ("serve.evict", "engine.evict")):
        for e in (e for e in evs if e[0] == mine):
            assert any(_within(e, h) for h in evs if h[0] == harness)


def test_spans_carry_the_request_and_lane(served):
    evs = served["events"]
    cold, warm = served["cold"].id, served["warm"].id
    admits = {e[3]["req"]: e[3] for e in evs if e[0] == "serve.admit"}
    assert admits[cold]["warm"] == 0 and admits[warm]["warm"] == 1
    evicts = {e[3]["req"]: e[3] for e in evs if e[0] == "serve.evict"}
    assert set(evicts) == {cold, warm}
    assert evicts[warm]["lane"] == admits[warm]["lane"]
    # The warm turn's restore and unspill lie in the admission of its id.
    warm_admit = next(e for e in evs if e[0] == "serve.admit"
                      and e[3]["req"] == warm)
    for name in ("serve.restore", "session.unspill"):
        assert [e for e in evs if e[0] == name
                and _within(e, warm_admit)]
    steps = [e[3]["step_num"] for e in evs if e[0] == "serve.step"]
    assert steps == sorted(steps) and steps[0] == 0


def test_counters_agree_with_what_moved(served):
    eng, sizes = served["eng"], served["sizes"]
    st = eng.stats
    assert (st.admits_cold, st.admits_warm, st.evictions) == (1, 1, 2)
    assert st.bytes_to_host == sum(sizes)
    # The warm turn brought back the first session less its host counter.
    assert 0 < st.bytes_to_device < sizes[0]
    assert sizes[0] - st.bytes_to_device <= 8
    # Each turn hops over its prompt less one token, then steps once per
    # token it generates.
    assert st.hop_dispatches == 2
    assert st.steps == eng.steps == (3 + 3) + (2 + 2)
    assert (eng.sessions.spills, eng.sessions.restores) == (1, 1)


def test_the_harness_hop_off_records_no_hop(tmp_path):
    """The benchmark's configuration turns the prefill hop off by
    overriding `_prefill_scan_hop`: no `serve.hop` span and no hop
    dispatch, every prompt token through the one-token step."""
    cfg_spec = tiny.config(lanes=2)
    Engine = serving.program_engine_class(prefill_hop=False)
    eng = Engine(cell.program_config(cfg_spec),
                 make_params=serving.params_maker(
                     2 ** 31 + 5, cfg_spec["model"], cfg_spec["memory"]),
                 lanes=2, max_len=64)
    with jax.profiler.trace(str(tmp_path)):
        eng.submit(Request(user="u", prompt=[5, 6, 7, 8], max_new_tokens=3))
        eng.run()
    names = [e[0] for e in _events(str(tmp_path))]
    eng.close()
    assert "serve.hop" not in names
    assert names.count("serve.step") == eng.stats.steps == 4 + 3 - 1
    assert (eng.stats.hop_dispatches, eng.stats.admits_cold,
            eng.stats.evictions) == (0, 1, 1)
