"""Roofline share of the SAM read, percent: the least time the chip
needs for the read's work over the device time of the kernels that
implement it (`fused_read_sweep`).

The work of one engine step, counted the same whatever implements it:
every lane sweeps all N rows of every group's memory, W words at the
stored dtype, and scores them against H queries
(2·H·W operations a row, plus 2·W for the row's norm). The least time is
the greater of bytes over the HBM peak and operations over the bf16 peak.
"""
import sys
import pathlib

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
from _common import READ_KERNELS, clipped, ops_named, step_events  # noqa: E402

BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


def read_work(cell) -> tuple[float, float]:
    """(bytes, operations) of one step's read."""
    m, mem = cell["model"], cell["memory"]
    groups = max(1, m["num_layers"] // mem["every_n_layers"])
    rows = cell["lanes"] * groups * mem["num_slots"]
    W, H = mem["word_size"], mem["num_heads"]
    per_row = W * BYTES[mem["mem_dtype"]]
    if mem["mem_dtype"] == "int8":
        per_row += 4                  # the row's f32 scale
    return rows * per_row, rows * (2 * H * W + 2 * W)


def read(trace, window, cell):
    ops = ops_named(trace, READ_KERNELS)
    steps = len(step_events(trace))
    if not ops or not steps:
        return None
    kernel_s = clipped(ops, trace["window"]) / 1e9
    nbytes, flops = read_work(cell)
    least = steps * max(nbytes / cell["peaks"]["hbm_bytes_per_s"],
                        flops / cell["peaks"]["bf16_flops_per_s"])
    return 100.0 * least / kernel_s
