#!/usr/bin/env python3
"""Read a cell's compared numbers with a fault planted under the timed
path (`bench/faults.py`), or with the program's own lower-precision
memory in place (``memory_bf16``: `mem_dtype` bfloat16, the step below the
float32 the configuration states), or as it is (``sound``), one process
per run:

    python3 benchmarks/chip/tools/fault_runs.py --workload danube_sam.chat \
        --runs half_batch:5,6 --runs memory_bf16:7 --seconds 51

Prints each run's readings and checks as one JSON line.
"""
import argparse
import json
import pathlib
import subprocess
import sys
import time

HERE = pathlib.Path(__file__).resolve().parents[1]

# The program's own paths at a precision below the configuration's.
CONTROLS = {"memory_bf16": {"mem_dtype": "bfloat16"}}


def one(workload: str, plant: str, seed: int, seconds: float) -> dict:
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(HERE.parents[1] / "src"))
    from bench import cell, faults, spec
    bench = spec.benchmark()
    cfg_spec, hook = None, None
    if plant == "sound":
        pass
    elif plant in CONTROLS:
        cfg_spec = spec.config(spec.workload(bench, workload)["config"])
        cfg_spec["program"]["memory"].update(CONTROLS[plant])
        cfg_spec["memory"].update(CONTROLS[plant])
    else:
        hook = lambda eng: faults.plant(eng, plant)  # noqa: E731
    r = cell.run_cell(bench=bench, workload=workload, seed=seed,
                      seconds=seconds, trace=False, t_start=time.time(),
                      cfg_spec=cfg_spec, engine_hook=hook)
    return {"plant": plant, "seed": seed, "correct": r["correct"],
            "window": r["window"], "checks": r["checks"],
            "readings": r["readings"]}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", action="append", required=True,
                    help="<fault or control>:<seed>,<seed>...")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--one", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.one:
        plant, seed = args.runs[0].split(":")
        print(json.dumps(one(args.workload, plant, int(seed),
                             args.seconds)), flush=True)
        return 0
    for spec_ in args.runs:
        plant, seeds = spec_.split(":")
        for seed in seeds.split(","):
            p = subprocess.run(
                [sys.executable, __file__, "--one", "--workload",
                 args.workload, "--runs", f"{plant}:{seed}", "--seconds",
                 str(args.seconds)], capture_output=True, text=True,
                timeout=1200)
            lines = [ln for ln in p.stdout.splitlines()
                     if ln.startswith("{")]
            print(lines[-1] if lines else json.dumps(
                {"plant": plant, "seed": seed, "rc": p.returncode,
                 "err": p.stderr[-2000:]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
