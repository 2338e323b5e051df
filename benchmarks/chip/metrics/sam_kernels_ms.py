"""Device time per engine step of the SAM memory kernels on the first
device, ms: `fused_read_sweep`, `lra_topn` and `sparse_write_update`."""
import sys
import pathlib

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
from _common import SAM_KERNELS, ops_named, per_step_ms  # noqa: E402


def read(trace, window, cell):
    ops = ops_named(trace, SAM_KERNELS)
    if not ops:
        return None
    return per_step_ms(trace, ops)
