"""The NPB MG class C cell (``npb_mg_c_resid.sweep``) cut to a size the
CPU's Pallas interpreter runs in seconds: the cell resolves, its least
bytes at 514^3 are the hand count, the reference agrees with the
program, the control fails the limit, and a run whose timed path is
broken is not correct."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core
from bench import counts
from bench.check import rel_err
from bench.control import control_errors
from bench.generators import random_arrays
from bench.run import run
from bench.spec import Bench
from bench.tests.small import SmallBench

CELL = "npb_mg_c_resid.sweep"
#: Rows off the sublane tile and lanes off the lane tile, as at 514^3.
SIZES = {"npb_mg_c_resid": {"Nk": 6, "Nj": 9, "Ni": 140}}


class MgSmallBench(SmallBench):
    """:class:`SmallBench` with the MG configuration cut to :data:`SIZES`."""

    def config(self, name):
        if name in SIZES:
            return dict(Bench.config(self, name), **SIZES[name])
        return super().config(name)


def _run(seconds: float = 0.3, seed: int = 2**31 + 41):
    return run(CELL, seed, seconds, False, bench=MgSmallBench(), require_chip=False,
               t_start=time.perf_counter())


def test_cell_resolves_by_name():
    cell = Bench().cell(CELL)
    assert cell.config["program"] == "mg_resid" and cell.chips == 1
    assert (cell.config["Nk"], cell.config["Nj"], cell.config["Ni"]) == (514, 514, 514)
    assert cell.config["reduced"] == [] and cell.config["limits"] == {"rel_err": 1e-4}
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "sweep_ms"}
    assert {m["name"] for m in cell.per_layer} == {
        "compile_s", "kernel_ms.sweep", "hbm_roofline_pct.sweep", "glue_ms.sweep",
        "idle_pct.sweep"}
    assert callable(Bench().reference("mg_resid"))


def test_least_bytes_at_514_cubed():
    """``u`` and ``v`` at 514^3 read once, ``r``'s 512^3 interior and the
    two norms written once, 4 bytes each; 514 planes of 515 row steps."""
    from repro.core import compile_program
    from repro.core.programs import mg_resid_program

    prog = mg_resid_program()
    sizes = {"Nk": 514, "Nj": 514, "Ni": 514}
    assert counts.least_bytes(prog, sizes, 4) == (2 * 514**3 + 512**3 + 2) * 4 \
        == 1_623_244_872
    assert counts.grid_steps(compile_program(prog).kernel_plan, sizes) == 514 * 515


def test_reference_agrees_with_the_program():
    from repro.core import compile_program
    from repro.core.programs import mg_resid_program

    prog = mg_resid_program()
    shapes = counts.input_shapes(prog, SIZES["npb_mg_c_resid"])
    inputs = random_arrays(shapes, jnp.float32, 2**33 + 7)
    got = jax.jit(lambda a: compile_program(prog).fn(**a))(inputs)
    want = Bench().reference("mg_resid")(inputs, jnp.float32)
    assert set(got) == set(want) == {"r", "rnm2", "rnmu"}
    # the same equations summed in another order: float32 rounding only
    assert rel_err(got, want) <= 1e-6
    np.testing.assert_array_equal(np.asarray(want["r"])[0], 0.0)


def test_sound_run_is_correct():
    result, notes = _run()
    assert result["correct"], result["checks"]
    assert result["attempted"] > 2 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "sweep_ms"}
    assert result["checks"]["rel_err"]["value"] <= 1e-6
    assert "least_bytes_per_sweep 75944" in notes


def test_control_fails_the_limit():
    bench = MgSmallBench()
    limit = bench.cell(CELL).config["limits"]["rel_err"]
    for seed, err in control_errors(CELL, [1, 2, 2**31 + 3], bench):
        assert err > 10 * limit, (seed, err)


def _broken(fault):
    real = repro.core.compile_program

    class Broken:
        def __init__(self, gen):
            self.gen = gen

        def fn(self, **arrays):
            return fault(self.gen.fn(**arrays), arrays)

    return lambda *args, **kwargs: Broken(real(*args, **kwargs))


@pytest.mark.parametrize("fault", [
    # one residual altered where it is produced
    lambda out, a: dict(out, r=out["r"].at[3, 4, 70].add(0.01)),
    # the root-mean-square norm off by a thousandth
    lambda out, a: dict(out, rnm2=out["rnm2"] * 1.001),
    # the largest |r| taken of v, as though A u were left out
    lambda out, a: dict(out, rnmu=jnp.max(jnp.abs(a["v"][1:-1, 1:-1, 1:-1]))),
], ids=["r_altered", "rnm2_off", "rnmu_of_v"])
def test_broken_sweep_is_not_correct(monkeypatch, fault):
    monkeypatch.setattr(repro.core, "compile_program", _broken(fault))
    result, _ = _run(seconds=0.2)
    assert not result["correct"]
    assert result["checks"]["rel_err"]["value"] > result["checks"]["rel_err"]["limit"]
