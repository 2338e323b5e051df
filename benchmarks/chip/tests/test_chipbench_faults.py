"""A whole run of a tiny cell on the CPU, past the look for a chip: sound,
`correct` comes out true; with the timed path broken underneath
(`bench/faults.py`), or with the program's memory held below the float32
the configuration states, false. The exchange between chips, the fourth
fault a cell can have, does not exist on one chip.
"""
import time

import pytest

import chipbench_tiny as tiny
from bench import cell, check, faults, spec

# The tiny cell's own limits, set as the cell's are (PERF.md): sound runs
# of it read a KV gap near 0.004 (the fp8 control 0.05), a logit gap up
# to 0.09 (the faults 0.23 and more), a memory norm gap near 0.015 (a
# memory left unchanged 1) and 0.6-2.6% of the memory's entries on the
# bfloat16 grid (a memory held in bfloat16 or int8: all).
LIMITS = {"compare": {"kv_gap": {"limit": 0.02},
                      "logit_gap": {"limit": 0.15},
                      "memory_norm_gap": {"limit": 0.1},
                      "memory_bf16_share": {"limit": 0.25}}}


def _run(fault=None, seed=2 ** 31 + 5, mem_dtype=None):
    mix = dict(tiny.chat_mix(), rate_per_s=6.0)
    cfg = tiny.config(lanes=2)
    if mem_dtype is not None:
        cfg["program"]["memory"]["mem_dtype"] = mem_dtype
        cfg["memory"]["mem_dtype"] = mem_dtype
    return cell.run_cell(
        bench=spec.benchmark(), workload="danube_sam.chat", seed=seed,
        seconds=4, trace=False, t_start=time.time(), require_tpu=False,
        cfg_spec=cfg, mix=mix, limits=LIMITS,
        engine_hook=None if fault is None else
        (lambda eng: faults.plant(eng, fault)))


def test_sound_run_is_correct():
    r = _run()
    assert r["correct"], r["checks"]
    assert r["attempted"] > 5 and r["failed"] == 0
    assert r["window"]["compiles"] == 0
    assert set(r["metrics"]) == {"itl_p50_ms", "itl_p99_ms", "setup_s"}
    assert list(r)[-1] == "checks"
    # Every lane that served a request is in the sample.
    assert r["readings"]["lanes"] == r["window"]["lanes"] == [0, 1]


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_broken_step_is_not_correct(fault):
    r = _run(fault)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("mem_dtype", ["bfloat16", "int8"])
def test_memory_below_float32_is_not_correct(mem_dtype):
    """The program's own lower-precision memory paths: the control of the
    SAM layer's stored precision."""
    r = _run(mem_dtype=mem_dtype)
    assert not r["correct"], r["checks"]
    assert r["checks"]["memory_bf16_share"]["value"] == 1.0


def _captured(lanes, positions):
    return {"lanes": set(lanes), "positions": positions}


def test_sample_covers_every_lane_with_the_longest_first():
    got = [_captured([0], 9), _captured([0], 5), _captured([1, 2], 3),
           _captured([3], 4), _captured([2], 7), _captured([0, 3], 12)]
    for seed in range(5):
        s = check.sample(got, 3, seed)
        assert s[0]["positions"] == 12 and len(s) == 3
        assert set().union(*(x["lanes"] for x in s)) == {0, 1, 2, 3}
    assert len(check.sample(got, 8, 0)) == len(got)


def test_no_chip_no_result(capsys):
    import run
    rc = run.main(["--workload", "danube_sam.chat", "--seed", "1",
                   "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out == ""
