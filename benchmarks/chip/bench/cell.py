"""One run of one cell: set-up, the window, the trace, the check."""
from __future__ import annotations

import dataclasses
import gc
import json
import time

import jax

from bench import check, serving, spec, traffic
from bench.trace import Tracer, breakdown, busy_ns


class NoChip(Exception):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def device_info(chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX sees {info['count']} {info['platform']} "
                     f"device(s)")
    if info["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{info['count']}")
    return info


def program_config(cfg_spec: dict):
    """The program's configuration for a config file, checked against the
    sizes the file states (which the reference reads)."""
    from repro.configs import get_config
    prog = cfg_spec["program"]
    cfg = get_config(prog["arch"])
    cfg = dataclasses.replace(
        cfg, **prog.get("overrides", {}),
        memory=dataclasses.replace(cfg.memory, **prog.get("memory", {})))
    for group, obj in (("model", cfg), ("memory", cfg.memory)):
        for key, want in cfg_spec[group].items():
            got = getattr(obj, key)
            if got != want:
                raise spec.SpecError(f"{cfg_spec['name']}: the program's "
                                     f"{group}.{key} is {got!r}, the file "
                                     f"states {want!r}")
    return cfg


def peak_bytes() -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(*, bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, control: bool = False,
             require_tpu: bool = True, cfg_spec: dict | None = None,
             mix: dict | None = None, limits: dict | None = None,
             engine_hook=None, dump_trace: str | None = None) -> dict:
    """Run one cell and return its result line as a dict.

    ``cfg_spec``/``mix``/``limits`` replace the files the workload names
    (tests run a tiny cell this way); ``engine_hook(engine)`` may alter
    the engine before its warm-up (the fault tests break the timed path
    with it)."""
    wl = spec.workload(bench, workload)
    cfg_spec = cfg_spec or spec.config(wl["config"])
    mix = mix or spec.traffic(wl["traffic"])
    chips = wl["chips"]
    if chips != 1:
        raise spec.SpecError(f"{workload}: this harness runs cells on one "
                             f"chip; a cell on {chips} has no mesh here")
    info = device_info(chips, require_tpu)
    limits = limits or spec.limits(workload)
    names = [m["name"] for m in spec.metrics_for(bench, workload, trace)]
    peaks = spec.peaks(info["kind"]) if trace else None

    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cfg = program_config(cfg_spec)
    model, memory = cfg_spec["model"], cfg_spec["memory"]
    lanes, max_len = cfg_spec["lanes"], mix["max_len"]

    counter = serving.CompileCounter()
    Engine = serving.program_engine_class(cfg_spec["prefill_hop"])
    eng = Engine(cfg, make_params=serving.params_maker(seed, model, memory),
                 lanes=lanes, max_len=max_len)
    if engine_hook is not None:
        engine_hook(eng)
    serving.warm_up(eng, lanes, model["vocab_size"])

    captured, longest = [], [{"positions": -1}]
    covered = set()               # lanes of the captured conversations
    N = memory["num_slots"]

    def capture(rec, runner):
        it = rec.item
        positions = it.start_pos + it.positions
        is_longest = positions > longest[0]["positions"]
        new_lane = rec.lane not in covered
        if not (it.sampled or is_longest or new_lane):
            return
        sess = eng.sessions.peek(it.conv)
        s = {"turns": list(runner.history[it.conv]), "positions": positions,
             "lanes": set(runner.lanes[it.conv]),
             "memory": [(st.memory[0, :N], st.last_access[0, :N])
                        for st in sess["mem"]],
             "kv": [(sess["cache"]["k"][i, 0, :positions],
                     sess["cache"]["v"][i, 0, :positions])
                    for i in range(model["num_layers"])]}
        if it.sampled or new_lane:
            captured.append(s)
            covered.update(s["lanes"])
        if is_longest:
            longest[0] = s

    runner = serving.LoadRunner(eng)
    runner.on_finish = lambda rec: capture(rec, runner)
    vocab = model["vocab_size"]
    if mix["loop"] == "open":
        load = dict(items=traffic.open_loop(mix, seed, seconds, vocab))
    else:
        clients = lanes if mix["clients"] == "lanes" else mix["clients"]
        load = dict(clients=traffic.closed_loop(mix, seed, vocab, clients))
    tracer = None
    if trace:
        length = min(mix["trace_seconds"], seconds / 2)
        tracer = Tracer((seconds - length) / 2, length)
    setup_s = time.time() - t_start
    win = runner.run(seconds=seconds, counter=counter, tracer=tracer, **load)
    if tracer is not None:
        tracer.stop()
    device = dict(info, memory_peak_bytes=peak_bytes())

    # Free the program's state before the reference runs on the chip.
    eng.close()
    eng.params = eng.cache = eng.mem = None
    del eng, runner
    gc.collect()

    # A request cannot fail alone: an error in the engine ends the run.
    result = {"correct": False, "attempted": len(win.finished), "failed": 0}
    e2e = serving.end_to_end(win)
    metrics = {}
    if trace:
        tr = tracer.load()
        if dump_trace:
            with open(dump_trace, "w") as f:
                json.dump(tr, f)
        t0, t1 = tracer.t0, tracer.t1
        steps = [s for s in win.steps if t0 < s.end <= t1]
        window = dict(seconds=t1 - t0, steps=len(steps),
                      tokens=sum(s.tokens for s in steps),
                      lane_steps=sum(s.lanes for s in steps),
                      context=sum(s.context for s in steps),
                      compiles=win.compiles)
        cell = dict(model=model, memory=memory, chips=chips, lanes=lanes,
                    peaks=peaks)
        for m in spec.metrics_for(bench, workload, True):
            v = spec.reader(m["name"])(tr, window, cell)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        w0, w1 = tr["window"]
        busy = [busy_ns(d["ops"], w0, w1) for d in tr["devices"][:chips]]
        device.update(busy_s=sum(busy) / len(busy) / 1e9,
                      window_s=(w1 - w0) / 1e9)
        result["breakdown"] = breakdown(tr)
    else:
        values = dict(tok_s=e2e["tok_s"], setup_s=setup_s,
                      itl_p50_ms=serving.nearest_rank(e2e["itl_ms"], 50),
                      itl_p99_ms=serving.nearest_rank(e2e["itl_ms"], 99))
        for m in spec.metrics_for(bench, workload, False):
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device

    extra = [] if longest[0]["positions"] < 0 or any(
        s is longest[0] for s in captured) else [longest[0]]
    samples = check.sample(captured + extra, mix["check_rows"], seed)
    served_lanes = sorted({r.lane for r in win.finished})
    t_check = time.time()
    readings = check.compare(samples, model, memory, seed,
                             rows=mix["check_rows"], max_len=max_len,
                             control=control)
    readings["seconds"] = time.time() - t_check
    readings["lanes"] = sorted(set().union(*(s["lanes"] for s in samples)))
    ok, checks = check.judge(readings, limits)
    missing = [n for n in names if n not in metrics]
    result.update(
        correct=ok and bool(samples) and bool(checks),
        window={"seconds": win.seconds, "submitted": win.submitted,
                "finished": len(win.finished), "in_flight": win.in_flight,
                "steps": len(win.steps), "tokens": win.tokens,
                "compiles": win.compiles, "late_s": win.late_s,
                "lanes": served_lanes,
                "first_tokens": len(e2e["ttft_ms"]),
                "ttft_p50_ms": serving.nearest_rank(e2e["ttft_ms"], 50),
                "gaps": len(e2e["itl_ms"]), "metrics_missing": missing},
        readings=readings, checks=checks)
    return result
