#!/usr/bin/env python3
"""The chip benchmark of the SAM-augmented LM serving engine.

    python3 benchmarks/chip/run.py --workload danube_sam.chat --seed 7 \
        --seconds 30 --trace 0

Runs one cell of `BENCHMARK.json` (at the root of the checkout) on the
machine it is started on: builds the program's `ServeEngine` with weights
from the seed, warms up every program the window runs, drives the cell's
traffic for ``--seconds``, and checks what the engine served against the
plain float32 reference (`reference/model.py`). With ``--trace 0`` it
reports the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics, read from a profiler trace of part of the window.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced); its last key, ``checks``, holds each compared number beside its
limit, which the last lines of standard error repeat. Without a TPU, or
with fewer chips than the cell asks for, it exits 2 and prints no result.

``--control 1`` also runs the fp8 control in the reference's place and
prints its readings (a measuring aid; the benchmark's runs do not use it).
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default=None,
                    help="write the reduced trace of a traced run here")
    args = ap.parse_args(argv)

    from bench import spec
    bench = spec.benchmark()
    bad = spec.check_names(bench)
    if bad:
        print(f"BENCHMARK.json: {bad}", file=sys.stderr)
        return 2
    from bench import cell
    try:
        result = cell.run_cell(bench=bench, workload=args.workload,
                               seed=args.seed, seconds=args.seconds,
                               trace=bool(args.trace), t_start=T_START,
                               control=bool(args.control),
                               dump_trace=args.dump_trace)
    except cell.NoChip as e:
        print(f"chip benchmark: {e}", file=sys.stderr)
        return 2
    checks = result.pop("checks")
    for k, v in result["readings"].items():
        print(f"reading {k}: {v}", file=sys.stderr)
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
