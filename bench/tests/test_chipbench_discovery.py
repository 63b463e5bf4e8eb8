"""A cell, a traffic mix and a per-layer metric added only as files and
``BENCHMARK.json`` entries are found by name; an unknown name fails
clearly."""
import json
import shutil
from pathlib import Path

import pytest

from bench import generators
from bench.run import run
from bench.spec import REPO, Bench


def _tree(tmp_path: Path) -> Path:
    """A benchmark root holding only what a later change would add: one
    configuration, one mix, one metric and the reference they need."""
    b = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics", "references"):
        (b / d).mkdir(parents=True)
    shutil.copy(REPO / "bench" / "references" / "heat3d.py", b / "references")
    (b / "configs" / "heat_tiny.json").write_text(json.dumps(
        {"program": "heat3d", "dtype": "float32", "Nk": 3, "Nj": 9, "Ni": 128,
         "limits": {"rel_err": 1e-5}}))
    (b / "traffic" / "sweep_again.json").write_text(json.dumps({"generator": "sweep"}))
    (b / "metrics" / "sweeps_done.py").write_text(
        "def read(ctx):\n    return ctx.counters['sweeps']\n")
    (b / "metrics" / "silent.py").write_text("def read(ctx):\n    return None\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "heat_tiny", "file": "bench/configs/heat_tiny.json"}],
        "workloads": [{"name": "heat_tiny.sweep_again", "config": "heat_tiny",
                       "traffic": "sweep_again", "chips": 1},
                      {"name": "heat_tiny.nowhere", "config": "heat_tiny",
                       "traffic": "nowhere", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"},
                       {"name": "sweep_ms", "unit": "ms",
                        "workloads": ["heat_tiny.sweep_again"]}],
        "per_layer": [{"name": "sweeps_done", "unit": "sweeps",
                       "workloads": ["heat_tiny.sweep_again"]},
                      {"name": "silent", "unit": "%"}]}))
    return tmp_path


def test_added_files_are_found_by_name_and_run(tmp_path):
    bench = Bench(_tree(tmp_path))
    cell = bench.cell("heat_tiny.sweep_again")
    assert cell.config["Nj"] == 9 and cell.traffic == {"generator": "sweep"}
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "sweep_ms"]
    assert bench.reader("sweeps_done")(type("C", (), {"counters": {"sweeps": 3}})) == 3
    result, _ = run("heat_tiny.sweep_again", 5, 0.2, True, bench=bench, require_chip=False)
    assert result["correct"]
    # a reader that finds nothing leaves its metric out of the line
    assert result["metrics"]["sweeps_done"]["value"] == result["attempted"]
    assert "silent" not in result["metrics"]
    result, _ = run("heat_tiny.sweep_again", 5, 0.2, False, bench=bench, require_chip=False)
    assert set(result["metrics"]) == {"setup_s", "sweep_ms"}


def test_unknown_names_fail_clearly(tmp_path):
    bench = Bench(_tree(tmp_path))
    with pytest.raises(ValueError, match=r"unknown workload 'nope'.*heat_tiny.sweep_again"):
        bench.cell("nope")
    with pytest.raises(ValueError, match="unknown traffic mix 'nowhere'"):
        bench.cell("heat_tiny.nowhere")
    with pytest.raises(ValueError, match="unknown configuration 'other'"):
        bench.config("other")
    with pytest.raises(ValueError, match="unknown metric 'gone'"):
        bench.reader("gone")
    with pytest.raises(ValueError, match="unknown reference 'cosmo'"):
        bench.reference("cosmo")
    with pytest.raises(ValueError, match="unknown generator 'nope'"):
        generators.load("nope")


def test_committed_benchmark_names_resolve():
    bench = Bench()
    for w in bench.spec["workloads"]:
        cell = bench.cell(w["name"])
        bench.reference(cell.config["program"])
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in bench.spec["per_layer"]:
        assert callable(bench.reader(m["name"]))
