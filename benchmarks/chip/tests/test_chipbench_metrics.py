"""The per-layer readers on a small trace, and the end-to-end arithmetic:
percentiles over all requests, tokens over the whole window, and a stall
that moves the tail."""
import json
import pathlib

import pytest

import chipbench_tiny  # noqa: F401
from bench import serving, spec, traffic
from bench.trace import base_name, breakdown, busy_ns

DATA = pathlib.Path(__file__).resolve().parent / "data"
MS = 1_000_000


def synthetic():
    """Two steps of 10 ms in a 30 ms window: each step runs a read kernel
    (2 ms), the write (1 ms), a copy (1 ms), an all-gather (0.5 ms), then
    after 0.5 ms idle a fusion (5 ms)."""
    ops, modules = [], []
    for k in range(2):
        t = 5 * MS + k * 12 * MS
        modules.append(("jit_step", t, 10 * MS))
        ops += [("fused_read_sweep.3", t, 2 * MS),
                ("sparse_write_update.1", t + 2 * MS, 1 * MS),
                ("copy.17", t + 3 * MS, 1 * MS),
                ("all-gather-start.2", t + 4 * MS, MS // 2),
                ("fusion.9", t + 5 * MS, 5 * MS)]
    host = [("bench.step", 4 * MS, 12 * MS), ("engine.evict", 15 * MS,
                                               2 * MS),
            ("bench.step", 16 * MS, 12 * MS)]
    return {"window": (0, 30 * MS), "devices": [{"ops": ops,
                                                 "modules": modules}],
            "host": host}


CELL = {"model": chipbench_tiny.MODEL, "memory": chipbench_tiny.MEMORY,
        "chips": 1, "lanes": 4,
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
WINDOW = {"seconds": 0.03, "steps": 2, "tokens": 9, "lane_steps": 8,
          "context": 100, "compiles": 0}


def read(name, trace=None, window=WINDOW, cell=CELL):
    return spec.reader(name)(trace or synthetic(), window, cell)


def test_idle_and_step_time():
    for name in ("idle_pct.itl_p99", "idle_pct.tok_s"):
        assert read(name) == pytest.approx(100 * (1 - 19 / 30))
    assert read("step_device_ms") == pytest.approx(10.0)
    assert read("compiles_in_window.itl_p99") == 0
    assert read("compiles_in_window.tok_s") == 0


def test_op_times_per_step():
    assert read("xla_copy_ms") == pytest.approx(1.0)
    assert read("sam_kernels_ms") == pytest.approx(3.0)


def test_read_roofline_counts_every_lane_and_group():
    from importlib import import_module
    nbytes, flops = import_module("sam_read_roofline").read_work(CELL)
    groups = CELL["model"]["num_layers"] // CELL["memory"]["every_n_layers"]
    assert nbytes == 4 * groups * 128 * 16 * 4
    want = 100 * 2 * nbytes / 819e9 / 4e-3
    assert read("sam_read_roofline") == pytest.approx(want)


def test_mfu_counts_weights_attention_and_sweep():
    from importlib import import_module
    fixed, per_ctx = import_module("mfu_pct").token_flops(
        CELL["model"], CELL["memory"])
    want = 100 * (fixed * 8 + per_ctx * 100) / (0.03 * 197e12)
    assert read("mfu_pct") == pytest.approx(want)
    assert read("mfu_pct", window=dict(WINDOW, lane_steps=0)) is None


def test_readers_find_nothing_to_read():
    empty = {"window": (0, 30 * MS), "devices": [{"ops": [],
                                                  "modules": []}],
             "host": []}
    for name in ("idle_pct.tok_s", "step_device_ms", "xla_copy_ms",
                 "sam_kernels_ms", "sam_read_roofline"):
        assert read(name, trace=empty) is None


def test_busy_union_and_breakdown():
    tr = synthetic()
    assert busy_ns(tr["devices"][0]["ops"], 0, 30 * MS) == 19 * MS
    bd = breakdown(tr)
    assert bd["device_ops"][0] == ["fusion", 0.01]
    # Gaps: 0-5 ms and 27-30 ms hold no host span; 15-17 ms falls in the
    # evict, the innermost span there; two 0.5 ms gaps inside steps.
    assert bd["idle_gaps"] == [["host idle", 0.005], ["host idle", 0.003],
                               ["engine.evict", 0.002],
                               ["bench.step", 0.0005],
                               ["bench.step", 0.0005]]


def recorded():
    path = DATA / "trace_danube_sam_chat.json"
    if not path.exists():
        pytest.skip("no recorded trace")
    return json.loads(path.read_text())


def test_readers_on_a_recorded_chip_trace():
    rec = recorded()
    cell = dict(rec["cell"], peaks=spec.peaks("TPU v5 lite"))
    got = {m: spec.reader(m)(rec["trace"], rec["window"], cell)
           for m in rec["expect"]}
    assert got == pytest.approx(rec["expect"])
    assert 0 < got["sam_read_roofline"] <= 100
    assert 0 <= got["idle_pct.itl_p99"] < 100
    # Three engine steps, each with one call of every SAM kernel per
    # memory group (6 groups).
    ops = [base_name(n) for n, _, _ in rec["trace"]["devices"][0]["ops"]]
    for k in ("fused_read_sweep", "lra_topn", "sparse_write_update"):
        assert ops.count(k) == 3 * 6
    assert got["sam_kernels_ms"] < got["step_device_ms"]


def _window(token_times, sends, seconds):
    win = serving.Window(start=0.0, end=seconds)
    for k, (times, sent) in enumerate(zip(token_times, sends)):
        it = traffic.Item(index=k, user="u", conv=f"u.c{k}", start_pos=0,
                          prompt=[1], max_new=len(times))
        rec = serving.Record(item=it, req=None, sent=sent,
                             token_times=list(times))
        win.records.append(rec)
    return win


def test_percentiles_cover_every_request():
    vals = list(range(1, 101))
    assert serving.nearest_rank(vals, 90) == 90
    assert serving.nearest_rank(vals, 95) == 95
    assert serving.nearest_rank([5.0], 95) == 5.0
    assert serving.nearest_rank([], 95) is None
    win = _window([[0.1 * i + 0.05] for i in range(20)],
                  [0.1 * i for i in range(20)], 3.0)
    e2e = serving.end_to_end(win)
    assert len(e2e["ttft_ms"]) == 20
    assert serving.nearest_rank(e2e["ttft_ms"], 90) == pytest.approx(50.0)


def test_tokens_per_second_over_the_whole_window():
    win = _window([[0.1, 0.2]], [0.0], 4.0)
    win.tokens = 400
    assert serving.end_to_end(win)["tok_s"] == pytest.approx(100.0)


def test_a_stall_moves_the_tail():
    steady = [[0.02 * k for k in range(1, 101)] for _ in range(4)]
    stalled = [list(t) for t in steady]
    for t in stalled:                  # a 0.5 s stall after token 50
        for k in range(50, 100):
            t[k] += 0.5
    # The stall shows in few gaps, so it moves the tail by its share.
    a = serving.end_to_end(_window(steady, [0] * 4, 3.0))["itl_ms"]
    b = serving.end_to_end(_window(stalled, [0] * 4, 3.0))["itl_ms"]
    assert serving.nearest_rank(a, 95) == pytest.approx(20.0)
    assert serving.nearest_rank(b, 99) == pytest.approx(520.0)
    assert serving.nearest_rank(b, 100) > serving.nearest_rank(a, 100)
