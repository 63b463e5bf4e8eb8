"""The comparison that decides ``correct``: sound runs pass it, and the
control (the plain reference one precision lower, in the program's
place) fails it, at sizes the CPU's Pallas interpreter runs."""
import pytest

from bench.control import control_errors
from bench.tests.small import SmallBench, run_small

CELLS = ["cosmo1_hdiff.sweep", "heat3d_7pt.sweep", "heat3d_7pt.serve"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limit(cell):
    bench = SmallBench()
    limit = bench.cell(cell).config["limits"]["rel_err"]
    for seed, err in control_errors(cell, [1, 2, 2**31 + 3], bench):
        assert err > 10 * limit, (seed, err)


@pytest.mark.parametrize("cell", ["cosmo1_hdiff.sweep", "heat3d_7pt.sweep"])
def test_sound_sweep_run_is_correct(cell):
    result, notes = run_small(cell)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 2 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "sweep_ms"}
    assert list(result)[-1] == "checks"
    assert result["checks"]["rel_err"]["value"] <= result["checks"]["rel_err"]["limit"]
    assert any(line.startswith("grid_steps_per_sweep") for line in notes)


def test_sound_traced_sweep_run_reports_its_per_layer_metrics():
    result, notes = run_small("heat3d_7pt.sweep", trace=True)
    assert result["correct"], result["checks"]
    # off the chip no device plane is traced: only the host-clock metric
    assert set(result["metrics"]) == {"compile_s"}
    assert result["device"]["window_s"] > 0
    assert any(line.startswith("xla_fusion_baseline_sweep_ms") for line in notes)
    assert "compiles_in_window 0" in notes
