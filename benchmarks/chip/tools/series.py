#!/usr/bin/env python3
"""Run one cell several times, one process per run, one after another.

    python3 benchmarks/chip/tools/series.py --workload danube_sam.chat \
        --seeds 11,12,13 --seconds 30 --trace 0 [--control 1] --out DIR

Each run is ``benchmarks/chip/run.py`` in a process of its own (the
parent never touches JAX, so the child holds the chip). Each run's
result line and the end of its standard error go to ``DIR/<tag>.jsonl``;
a summary line per run is printed.
"""
import argparse
import json
import os
import pathlib
import subprocess
import sys
import time

HERE = pathlib.Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--control", default="0")
    ap.add_argument("--out", required=True)
    ap.add_argument("--tag", default="")
    ap.add_argument("--timeout", type=float, default=1200)
    ap.add_argument("--dump-trace", default="",
                    help="directory for the reduced traces of traced runs")
    args = ap.parse_args()
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    tag = args.tag or f"{args.workload}.t{args.trace}"
    traces = args.trace.split(",")
    for i, seed in enumerate(args.seeds.split(",")):
        trace = traces[i % len(traces)]
        cmd = [sys.executable, str(HERE / "run.py"), "--workload",
               args.workload, "--seed", seed, "--seconds",
               str(args.seconds), "--trace", trace,
               "--control", args.control]
        if args.dump_trace and trace == "1":
            cmd += ["--dump-trace",
                    os.path.join(args.dump_trace, f"trace_{seed}.json")]
        t0 = time.time()
        try:
            p = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=args.timeout)
            rc, so, se = p.returncode, p.stdout, p.stderr
        except subprocess.TimeoutExpired as e:
            rc, so, se = 124, e.stdout or "", e.stderr or ""
            so = so.decode() if isinstance(so, bytes) else so
            se = se.decode() if isinstance(se, bytes) else se
        wall = time.time() - t0
        lines = [l for l in so.strip().splitlines() if l.startswith("{")]
        result = json.loads(lines[-1]) if lines else None
        rec = {"seed": seed, "trace": trace, "rc": rc, "wall_s": wall,
               "result": result, "stderr_tail": se[-6000:]}
        with open(out / f"{tag}.jsonl", "a") as f:
            f.write(json.dumps(rec) + "\n")
        if result:
            m = {k: round(v["value"], 4) for k, v in
                 result["metrics"].items()}
            ch = {k: round(v["value"], 5) for k, v in
                  result["checks"].items()}
            print(json.dumps({"seed": seed, "trace": trace, "rc": rc,
                              "wall": round(wall, 1),
                              "correct": result["correct"], "m": m,
                              "checks": ch,
                              "win": result.get("window")}), flush=True)
        else:
            print(json.dumps({"seed": seed, "rc": rc, "wall": round(wall, 1),
                              "err": se[-3000:]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
