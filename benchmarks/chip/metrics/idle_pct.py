"""Share of the traced window in which no operation ran on the first
device, percent. Read as `idle_pct.<moves>`, named by the end-to-end
metric it moves in the cells that list it: in a closed loop every idle
stretch is throughput lost (`tok_s`); below the knee of an open loop the
stalls of session moves set the tail (`itl_p99_ms`)."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
from _common import idle_share  # noqa: E402


def read(trace, window, cell):
    return idle_share(trace)
