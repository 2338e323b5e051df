"""repro.analysis: envelope grammar/fitting units, lint passes on synthetic
and real lowered modules, the measurement layer, the dead-module report,
and the auto-collected complexity-contract suite (``-m analysis`` selects
the contract runs; the sharded contracts get a forced-8-device subprocess
driver exactly like tests/test_fused_read.py's mesh lane)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import deadmods
from repro.analysis import lints as lints_mod
from repro.analysis.checker import run_contract
from repro.analysis.contracts import all_contracts
from repro.analysis.envelope import (check_growth, fit_exponent,
                                     parse_envelope)
from repro.analysis.measure import Measurement, Target, measure

# ----------------------------- envelope ------------------------------------


def test_parse_envelope_products_and_sums():
    e = parse_envelope("O(B*K*W + N^2)")
    assert e.predict({"B": 2, "K": 8, "W": 128, "N": 10}) == 2148.0
    assert e.depends_on("N") and e.depends_on("K")
    assert not e.depends_on("T")
    # The O(...) wrapper is optional; integers are constant factors.
    assert parse_envelope("2*N").predict({"N": 5}) == 5.0
    assert parse_envelope("O(1)").predict({}) == 1.0


def test_parse_envelope_rejects_garbage():
    with pytest.raises(ValueError):
        parse_envelope("O(N**2)")
    with pytest.raises(ValueError):
        parse_envelope("O(N + )")
    with pytest.raises(KeyError):
        parse_envelope("O(N*W)").predict({"N": 4})   # W undeclared


def test_fit_exponent_power_laws():
    xs = [256, 1024, 4096]
    assert fit_exponent(xs, [x ** 2 for x in xs]) == pytest.approx(2.0)
    assert fit_exponent(xs, [7.0, 7.0, 7.0]) == pytest.approx(0.0)
    # Zero measurements clamp to one unit: absent resources fit flat.
    assert fit_exponent(xs, [0.0, 0.0, 0.0]) == pytest.approx(0.0)
    with pytest.raises(ValueError):
        fit_exponent([4, 4], [1.0, 2.0])


def test_check_growth_envelope_is_upper_bound():
    xs = [256, 1024]
    sizes = [{"N": x, "W": 8} for x in xs]
    flat = [100.0, 101.0]
    linear = [100.0, 400.0]
    assert check_growth("hbm", None, xs, sizes, flat, 0.1).ok
    assert not check_growth("hbm", None, xs, sizes, linear, 0.1).ok
    assert check_growth("hbm", "O(N*W)", xs, sizes, linear, 0.1).ok
    # Sub-envelope growth passes: the envelope bounds, it doesn't equate.
    assert check_growth("hbm", "O(N*W)", xs, sizes, flat, 0.1).ok


# ------------------------- introspect / measure -----------------------------


def test_count_primitives_kwargs_and_kernel_names():
    from repro.kernels import ops
    from repro.kernels.introspect import count_primitives, kernel_names

    # kwargs are call kwargs (the dead branch this suite fixed).
    counts = count_primitives(lambda x, scale=1.0: x * scale,
                              jnp.ones((4,)), scale=2.0)
    assert counts["mul"] == 1

    q = jnp.ones((1, 2, 16))
    mem = jnp.ones((1, 32, 16))
    beta = jnp.ones((1, 2))
    fused = count_primitives(
        lambda *a: ops.fused_read(*a, 4, backend="pallas-interpret"),
        q, mem, beta)
    assert fused["pallas_call"] == 1
    assert kernel_names(fused) == {"fused_read_sweep": 1}


def test_measure_flops_and_donation_fingerprint():
    def f(state, x):
        return state + x @ x

    state = jnp.ones((64, 64))
    x = jnp.ones((64, 64))
    m = measure(Target(fn=f, args=(state, x), donate_argnums=(0,)))
    assert m.flops >= 2 * 64 ** 3 * 0.9
    assert 0 in m.aliased_params
    assert m.entry_param_bytes[0] == 64 * 64 * 4
    assert m.dispatches.get("dot_general", 0) == 1
    assert m.group_sizes == []          # no collectives on one device


# ------------------------------- lints --------------------------------------


def _meas(**kw):
    base = dict(flops=0.0, bytes=0.0, param_bytes=0.0, hbm=0.0, coll={},
                coll_bytes=0.0, coll_moved=0.0, coll_count=0.0,
                group_sizes=[], dispatches={}, kernels={},
                aliased_params=[], entry_param_bytes={}, hlo_text="",
                stablehlo_text="")
    base.update(kw)
    return Measurement(**base)


_MEMINFO = {"num_slots": 64, "buf_rows": 65, "word_size": 8,
            "buffer_bytes": 2 * 64 * 8 * 4}


def test_scratch_copy_lint_fires_on_pad_and_sliceback():
    dirty = "\n".join([
        "%0 = stablehlo.pad %arg0 : tensor<2x64x8xf32> -> tensor<2x65x8xf32>",
        "%1 = stablehlo.slice %0 : tensor<2x65x8xf32> -> tensor<2x64x8xf32>",
    ])
    offenses = lints_mod.scratch_copy(_meas(stablehlo_text=dirty), _MEMINFO)
    assert len(offenses) == 2
    # The hot path itself stays legal: K-row gathers FROM the buffer, a
    # K-row dynamic_slice, and the in-place dynamic_update.
    clean = "\n".join([
        "%0 = stablehlo.gather %arg0 : tensor<2x64x8xf32> -> tensor<2x4x8xf32>",
        "%1 = stablehlo.dynamic_slice %arg0 : tensor<2x64x8xf32> -> tensor<2x4x8xf32>",
        "%2 = stablehlo.dynamic_update_slice %arg0, %u : tensor<2x65x8xf32>",
    ])
    assert lints_mod.scratch_copy(_meas(stablehlo_text=clean), _MEMINFO) == []


def test_dtype_widening_lint():
    dirty = ("%0 = stablehlo.convert %arg0 : tensor<2x64x8xbf16> -> "
             "tensor<2x64x8xf32>")
    assert lints_mod.dtype_widening(_meas(stablehlo_text=dirty), _MEMINFO)
    rows_ok = ("%0 = stablehlo.convert %g : tensor<2x4x8xbf16> -> "
               "tensor<2x4x8xf32>")
    assert lints_mod.dtype_widening(_meas(stablehlo_text=rows_ok),
                                    _MEMINFO) == []


def test_full_buffer_collective_lint():
    buf = _MEMINFO["buffer_bytes"]
    big = _meas(coll={"all-gather": {"count": 1, "bytes": buf, "moved": buf}})
    small = _meas(coll={"all-gather": {"count": 4, "bytes": 256.0,
                                       "moved": 256.0}})
    assert lints_mod.full_buffer_collective(big, _MEMINFO)
    assert lints_mod.full_buffer_collective(small, _MEMINFO) == []


def test_donation_lint_coverage():
    m = _meas(aliased_params=[0, 2], entry_param_bytes={0: 4096, 1: 64,
                                                        2: 2048})
    ok = dict(_MEMINFO, donated_bytes=6144)
    short = dict(_MEMINFO, donated_bytes=8192)
    assert lints_mod.donation(m, ok) == []
    assert lints_mod.donation(m, short)
    assert lints_mod.donation(m, _MEMINFO) == []   # nothing declared donated


# ---------------------------- dead modules ----------------------------------


def test_dead_module_report():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    rep = deadmods.report(src)
    assert rep["reachable"] > 40
    # The configs architecture zoo is importlib-loaded: dynamic, not dead.
    assert any(m.startswith("repro.configs.") for m in rep["dynamic"])
    assert not any(m.startswith("repro.configs.") for m in rep["dead"])
    # Core path modules must be reachable from the launch CLIs.
    for mod in ("repro.core.sam", "repro.kernels.ops",
                "repro.launch.hlo_cost", "repro.analysis.checker"):
        assert mod not in rep["dead"] and mod not in rep["dynamic"], mod
    assert "unreachable" in deadmods.format_report(rep) or \
        rep["dead"] == [] == rep["dynamic"]


# ------------------------- the contract suite -------------------------------

_TIER1 = sorted(n for n, c in all_contracts().items()
                if c.tier1 and c.devices <= jax.device_count())
_SHARDED = sorted(n for n, c in all_contracts().items()
                  if c.tier1 and c.devices > jax.device_count())


@pytest.mark.analysis
@pytest.mark.parametrize("name", _TIER1)
def test_contract(name):
    report = run_contract(all_contracts()[name], quick=True)
    if report["ok"] is None:
        pytest.skip(report["skipped"])
    detail = {b: r.get("failures", []) for b, r in report["backends"].items()}
    if report["expect_trip"]:
        assert report["ok"], (
            f"positive control {name} never tripped a detector", detail)
    else:
        assert report["ok"], (name, detail)


@pytest.mark.analysis
@pytest.mark.skipif(not _SHARDED,
                    reason="all contracts runnable in this session")
@pytest.mark.skipif(bool(os.environ.get("REPRO_SKIP_MESH_DRIVER")),
                    reason="a dedicated forced-8-device analysis lane runs "
                           "the sharded contracts (CI)")
def test_sharded_contracts_on_forced_host_mesh():
    """Driver: run the device-gated contracts in a subprocess that forces
    8 host devices (the CLI sets XLA_FLAGS before importing jax)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    out = os.path.join("/tmp", "ANALYSIS_mesh_driver.json")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--sweep", "--quick",
         "--force-devices", "8", "--only", *_SHARDED, "--out", out],
        env=env, capture_output=True, text=True, timeout=3000)
    assert proc.returncode == 0, \
        f"sharded contracts failed:\n{proc.stdout[-4000:]}\n" \
        f"{proc.stderr[-2000:]}"
