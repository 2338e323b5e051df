"""Device time per engine step of XLA copy ops on the first device, ms:
the relayouts of the (lanes, N+1, W) memory around the kernels among
them. 0 when the window holds steps and no copy."""
import sys
import pathlib

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
from _common import base_name, per_step_ms  # noqa: E402


def read(trace, window, cell):
    ops = [e for e in trace["devices"][0]["ops"]
           if base_name(e[0]).split("-")[0] == "copy"]
    return per_step_ms(trace, ops)
