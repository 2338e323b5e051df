"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. The dry-run entry point sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import;
tests and benchmarks see the real single device."""
from __future__ import annotations

import warnings

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """`jax.make_mesh` with Auto axes. The model code places arrays through
    sharding constraints and GSPMD propagation (distributed/sharding.py),
    which Explicit axes — `jax.make_mesh`'s default — reject."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_for(devices: int, model_parallel: int = 16):
    """Elastic-scaling helper: best-effort (data, model) mesh for an
    arbitrary device count (used by distributed/elastic.py). When the
    requested model degree does not fit — it exceeds the device count, or
    does not divide it — the degree is halved down until it does, and a
    loud warning reports requested-vs-actual: the model degree is the
    memory slot-sharding degree, so an elastic rescale that silently lands
    on a different one re-layouts every memory buffer (or quietly disables
    the sharding at model=1)."""
    model = min(model_parallel, devices)
    while devices % model:
        model //= 2
    if model != model_parallel:
        warnings.warn(
            f"make_mesh_for: requested model_parallel={model_parallel} "
            f"does not fit {devices} devices — building a "
            f"(data={devices // model}, model={model}) mesh instead. The "
            f"memory slot-sharding degree follows the model axis: an "
            f"elastic rescale onto this mesh re-layouts memory state to "
            f"{model} shard(s), not {model_parallel}.",
            UserWarning, stacklevel=2)
    return make_mesh((devices // model, model), ("data", "model"))


def make_memory_mesh(model_parallel: int = None):
    """Mesh for the mesh-native sparse memory path (docs/sharding.md): all
    visible devices on a (data, model) grid, model axis as large as
    divisibility allows (default: every device — memory capacity, not
    controller width, is the scaling axis). On a forced host platform
    (XLA_FLAGS=--xla_force_host_platform_device_count=8) this is the
    8-device validation mesh the parity tests and benchmarks run on.

    An *explicit* ``model_parallel`` must divide the device count: the
    caller asked for that degree, and silently halving it down (what the
    best-effort `make_mesh_for` does for elastic scaling) could quietly
    disable the memory sharding altogether."""
    n = jax.device_count()
    if model_parallel and n % model_parallel:
        raise ValueError(
            f"model_parallel={model_parallel} does not divide the "
            f"{n} visible devices — pick a divisor (or omit it to use "
            f"all devices on the model axis)")
    return make_mesh_for(n, model_parallel if model_parallel else n)


# TPU v5e hardware constants (per chip) for the roofline analysis.
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW = 50e9                     # B/s per link
