#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate at which
the queue does not grow over the window. One process, one engine, one
window per rate (rates in rising order):

    python3 benchmarks/chip/tools/sweep.py --workload danube_sam.chat \
        --rates 1,1.5,2,2.5,3 --seconds 30 --seed 5

Prints, per rate, tokens/s, the 90th percentile of time to first token,
the requests left queued or running at the close, and the backlog's trend
(requests waiting at the end of each third of the window).
"""
import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args()
    import jax
    from bench import cell, serving, spec, traffic
    bench = spec.benchmark()
    wl = spec.workload(bench, args.workload)
    cfg_spec, mix = spec.config(wl["config"]), spec.traffic(wl["traffic"])
    print(cell.device_info(wl["chips"], True), flush=True)
    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cfg = cell.program_config(cfg_spec)
    Engine = serving.program_engine_class(cfg_spec["prefill_hop"])
    eng = Engine(cfg, make_params=serving.params_maker(
        args.seed, cfg_spec["model"], cfg_spec["memory"]),
        lanes=cfg_spec["lanes"], max_len=mix["max_len"])
    serving.warm_up(eng, cfg_spec["lanes"], cfg_spec["model"]["vocab_size"])
    runner = serving.LoadRunner(eng)
    backlog = []

    def watch(elapsed):
        if len(backlog) < 3 and elapsed >= (len(backlog) + 1) * \
                args.seconds / 3 - 1e-9:
            backlog.append(len(eng.scheduler.queue)
                           + len(eng.scheduler.active))

    for rate in [float(r) for r in args.rates.split(",")]:
        m = dict(mix, rate_per_s=rate)
        items = traffic.open_loop(m, args.seed, args.seconds,
                                  cfg_spec["model"]["vocab_size"])
        backlog.clear()
        t0 = time.time()
        win = runner.run(seconds=args.seconds, items=items, tracer=watch)
        e2e = serving.end_to_end(win)
        print(json.dumps({
            "rate": rate, "offered": len(items), "finished":
            len(win.finished), "in_flight": win.in_flight,
            "backlog_thirds": backlog, "tok_s": e2e["tok_s"],
            "ttft_p50_ms": serving.nearest_rank(e2e["ttft_ms"], 50),
            "ttft_p90_ms": serving.nearest_rank(e2e["ttft_ms"], 90),
            "itl_p50_ms": serving.nearest_rank(e2e["itl_ms"], 50),
            "itl_p95_ms": serving.nearest_rank(e2e["itl_ms"], 95),
            "steps": len(win.steps), "wall": time.time() - t0}),
            flush=True)
        runner.drain()
    return 0


if __name__ == "__main__":
    sys.exit(main())
