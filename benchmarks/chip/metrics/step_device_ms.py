"""Device time of one engine step: the mean duration of the jitted
step's (`jit_step`) module events on the first device, ms."""
import sys
import pathlib

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
from _common import step_events  # noqa: E402


def read(trace, window, cell):
    ev = step_events(trace)
    if not ev:
        return None
    return sum(d for _, _, d in ev) / len(ev) / 1e6
