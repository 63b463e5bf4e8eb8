"""Least bytes, grid steps and the peaks table."""
import json

import pytest

from bench import counts
from bench.spec import REPO, Bench


def _config(name):
    return json.loads((REPO / "bench" / "configs" / f"{name}.json").read_text())


def _program(config):
    from repro.core.programs import ALL_PROGRAMS

    return ALL_PROGRAMS[config["program"]]()


@pytest.mark.parametrize("config, least, steps", [
    # 80*774*1158 read + 80*770*1154 written, 4 bytes each; 80 * 774 rows
    ("cosmo1_hdiff", 571_159_040, 61_920),
    # 512^3 read + 510^3 written, 4 bytes each; 512 * 512 rows
    ("heat3d_7pt", 1_067_474_912, 262_144),
    ("heat3d_7pt_256", (256**3 + 254**3) * 4, 256 * 256),
])
def test_least_bytes_and_grid_steps_match_hand_counts(config, least, steps):
    from repro.core import compile_program

    cfg = _config(config)
    prog = _program(cfg)
    sizes = {k: cfg[k] for k in ("Nk", "Nj", "Ni")}
    assert counts.least_bytes(prog, sizes, 4) == least
    assert counts.grid_steps(compile_program(prog).kernel_plan, sizes) == steps


def test_input_shapes_follow_axiom_extents():
    prog = _program(_config("cosmo1_hdiff"))
    assert counts.input_shapes(prog, {"Nk": 80, "Nj": 774, "Ni": 1158}) == {
        "u": (80, 774, 1158)}


def test_peaks_table_has_v5e_and_refuses_unknown_kinds():
    peaks = Bench().peaks("TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["bf16_flops_per_s"] == 197e12
    assert "TPU v5e" in peaks["source"]
    with pytest.raises(ValueError, match="not in the peaks table"):
        Bench().peaks("cpu")
