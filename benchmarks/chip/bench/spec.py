"""What a run is made of, found by name.

`BENCHMARK.json` at the root of the checkout names the cells. Everything
that belongs to one configuration, one traffic mix, one cell's limits or
one per-layer metric is a file of its own under this directory, found from
the name alone, so a later change adds a cell or a metric by adding files:

    configs/<config>.json     sizes of the model, its deployment, lanes
    traffic/<mix>.json        parameters of the one traffic generator
    limits/<workload>.json    the limits of the numbers `correct` compares
    metrics/<metric>.py       a reader with ``read(trace, window, cell)``;
                              a metric ``<quantity>.<moves>``, one quantity
                              split by the end-to-end metric it moves, is
                              read by ``metrics/<quantity>.py``
    peaks.json                published peaks, keyed by ``device_kind``
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import re

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


class SpecError(Exception):
    """A file of the benchmark is missing or malformed."""


def _json(path: pathlib.Path):
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing {path}") from None


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def check_names(bench: dict) -> list[str]:
    """Names and units outside the allowed characters, as messages."""
    bad = []
    metrics = bench["end_to_end"] + bench["per_layer"]
    for kind, items in (("config", bench["configs"]),
                        ("workload", bench["workloads"]),
                        ("metric", metrics)):
        for it in items:
            if not NAME.match(it["name"]):
                bad.append(f"{kind} name {it['name']!r}")
    for w in bench["workloads"]:
        for key in ("config", "traffic"):
            if not NAME.match(w[key]):
                bad.append(f"workload {w['name']} {key} {w[key]!r}")
    for c in bench["configs"]:
        bad += [f"config {c['name']} reduced key {k!r}"
                for k in c["reduced"] if not NAME.match(k)]
    for m in metrics:
        if not UNIT.match(m["unit"]):
            bad.append(f"metric {m['name']} unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"metric {m['name']} better {m['better']!r}")
    return bad


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str, here: pathlib.Path = HERE) -> dict:
    return _json(here / "configs" / f"{name}.json")


def traffic(name: str, here: pathlib.Path = HERE) -> dict:
    return _json(here / "traffic" / f"{name}.json")


def limits(workload_name: str, here: pathlib.Path = HERE) -> dict:
    return _json(here / "limits" / f"{workload_name}.json")


def peaks(device_kind: str, here: pathlib.Path = HERE) -> dict:
    table = _json(here / "peaks.json")["devices"]
    if device_kind not in table:
        raise SpecError(f"no published peaks for device kind "
                        f"{device_kind!r} in peaks.json")
    return table[device_kind]


def metrics_for(bench: dict, workload_name: str, trace: bool) -> list[dict]:
    """The metrics a run of this cell reports: with ``trace`` its
    per-layer metrics, otherwise its end-to-end ones. A metric with a
    ``workloads`` key belongs only to the cells it lists."""
    pool = bench["per_layer" if trace else "end_to_end"]
    return [m for m in pool
            if workload_name in m.get("workloads", [workload_name])]


def reader(metric_name: str, here: pathlib.Path = HERE):
    """The ``read(trace, window, cell)`` function of a per-layer metric,
    from ``metrics/<metric_name>.py``, or for ``<quantity>.<moves>`` from
    ``metrics/<quantity>.py``."""
    name = metric_name
    path = here / "metrics" / f"{name}.py"
    if not path.exists() and "." in name:
        name = name.split(".")[0]
        path = here / "metrics" / f"{name}.py"
    if not path.exists():
        raise SpecError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
