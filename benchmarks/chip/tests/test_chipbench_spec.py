"""BENCHMARK.json and the files it names: names, units, discovery."""
import json
import shutil

import pytest

import chipbench_tiny  # noqa: F401  (puts the benchmark on sys.path)
from bench import spec


@pytest.fixture(scope="module")
def bench():
    return spec.benchmark()


def test_names_and_units_follow_the_rules(bench):
    assert spec.check_names(bench) == []


@pytest.mark.parametrize("field,value", [
    ("name", "has space"), ("name", "a/b"), ("name", "x" * 65),
    ("name", ".lead"), ("unit", "tokens per s"), ("unit", "x" * 17),
    ("unit", "µs"), ("better", "up")])
def test_name_rules_refuse(bench, field, value):
    bad = json.loads(json.dumps(bench))
    bad["end_to_end"][0][field] = value
    assert spec.check_names(bad)


def test_every_cell_finds_its_files(bench):
    for wl in bench["workloads"]:
        cfg = spec.config(wl["config"])
        assert cfg["name"] == wl["config"]
        assert cfg["chips"] == wl["chips"]
        mix = spec.traffic(wl["traffic"])
        assert mix["loop"] in ("open", "closed")
        assert spec.limits(wl["name"])["compare"]
        assert spec.metrics_for(bench, wl["name"], False)
        assert spec.metrics_for(bench, wl["name"], True)
    for m in bench["per_layer"]:
        assert callable(spec.reader(m["name"]))


def test_metrics_move_reported_metrics(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    assert "setup_s" in e2e
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", cells):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", cells)
    for w in cells:
        reported = [m["name"] for m in spec.metrics_for(bench, w, False)]
        assert "setup_s" in reported and len(reported) >= 2


def test_unknown_device_kind_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v99 imaginary")
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_a_new_cell_and_metric_are_found_by_file_name(tmp_path, bench):
    """A configuration, a traffic mix, limits and a metric added as new
    files are found without an edit to any file that was there."""
    here = tmp_path / "chip"
    shutil.copytree(spec.HERE, here, ignore=shutil.ignore_patterns(
        "tests", "__pycache__", ".out"))
    cfg = spec.config("danube_sam", here)
    (here / "configs" / "danube_other.json").write_text(
        json.dumps(dict(cfg, name="danube_other")))
    (here / "traffic" / "burst.json").write_text(
        json.dumps(dict(spec.traffic("chat", here), rate_per_s=9.0)))
    (here / "limits" / "danube_other.burst.json").write_text(
        json.dumps({"compare": {"logit_gap": {"limit": 0.5}}}))
    (here / "metrics" / "steps_traced.py").write_text(
        "def read(trace, window, cell):\n    return window['steps']\n")
    assert spec.config("danube_other", here)["name"] == "danube_other"
    assert spec.traffic("burst", here)["rate_per_s"] == 9.0
    assert spec.limits("danube_other.burst", here)["compare"]
    assert spec.reader("steps_traced", here)(None, {"steps": 7}, None) == 7
    with pytest.raises(spec.SpecError):
        spec.reader("absent_metric", here)


def test_a_split_metric_is_read_by_its_quantity():
    """`<quantity>.<moves>` names one quantity split by the end-to-end
    metric it moves; one reader serves every split."""
    trace = {"window": (0, 10), "devices": [{"ops": [("fusion.1", 2, 4)]}]}
    for name in ("idle_pct", "idle_pct.itl_p99", "idle_pct.tok_s"):
        assert spec.reader(name)(trace, None, None) == pytest.approx(60.0)
    with pytest.raises(spec.SpecError):
        spec.reader("absent_metric.tok_s")
