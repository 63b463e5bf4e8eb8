"""Set-up split into phases (``bench/setup_phases.py``) on a small sweep
run off the chip: every phase is named, the spans cover the set-up, and
the harness is left as it was."""
import time

import pytest

from bench.generators import sweep
from bench.setup_phases import PHASES, measure
from bench.tests.small import SmallBench


@pytest.mark.parametrize("cell", ["cosmo1_hdiff.sweep", "heat3d_7pt.sweep"])
def test_small_sweep_setup_is_named_by_phase(cell):
    wrapped = (sweep._chained, sweep._pace, sweep.Generator.__init__)
    result, notes = measure(cell, 2**31 + 11, 0.2, bench=SmallBench(),
                            require_chip=False, t_start_ns=time.perf_counter_ns())
    assert result["correct"]
    phases = result["phases"]
    for metric in PHASES:
        assert phases[metric] > 0
    assert phases["setup_covered_pct"] >= 95
    assert set(phases["spans"]) >= {"bench.setup.runtime", "bench.setup.program",
                                    "bench.setup.inputs", "hfav.compile_program",
                                    "bench.setup.lower", "bench.setup.warmup"}
    assert phases["parts"]["bench.setup.xla_compile"] > 0
    assert phases["parts"]["hfav.build_call"] > 0
    assert phases["counters"]["hfav.grid_steps"] > 0
    assert sum(phases["spans"].values()) <= result["metrics"]["setup_s"]["value"]
    assert any(line.startswith("setup_covered_pct ") for line in notes)
    assert (sweep._chained, sweep._pace, sweep.Generator.__init__) == wrapped
