"""The Hydro2D cell (``hydro2d_8k.sweep_state``) cut to a size the CPU's
Pallas interpreter runs in seconds: a sound run is correct, the control
fails the limit, and a run whose timed path is broken is not correct."""
import time

import jax.numpy as jnp
import numpy as np
import pytest

import repro.core
from bench.control import control_errors
from bench.generators.sweep_state import physical_state
from bench.run import run
from bench.spec import Bench
from bench.tests.small import SmallBench

CELL = "hydro2d_8k.sweep_state"
#: Rows off the sublane tile and lanes off the lane tile, as at 8196^2.
SIZES = {"hydro2d_8k": {"Nj": 21, "Ni": 140}}


class HydroSmallBench(SmallBench):
    """:class:`SmallBench` with the Hydro2D configuration cut to :data:`SIZES`."""

    def config(self, name):
        if name in SIZES:
            return dict(Bench.config(self, name), **SIZES[name])
        return super().config(name)


def _run(seconds: float = 0.3, seed: int = 2**31 + 29):
    return run(CELL, seed, seconds, False, bench=HydroSmallBench(), require_chip=False,
               t_start=time.perf_counter())


def test_sound_run_is_correct():
    result, notes = _run()
    assert result["correct"], result["checks"]
    assert result["attempted"] > 2 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "sweep_ms"}
    assert result["checks"]["rel_err"]["value"] <= 1e-6
    assert any(line.startswith("least_bytes_per_sweep") for line in notes)


def test_control_fails_the_limit():
    bench = HydroSmallBench()
    limit = bench.cell(CELL).config["limits"]["rel_err"]
    for seed, err in control_errors(CELL, [1, 2, 2**31 + 3], bench):
        assert err > 10 * limit, (seed, err)


def test_state_is_physical_and_follows_the_seed():
    state = Bench().config("hydro2d_8k")["state"]
    a = physical_state((21, 140), state, jnp.float32, 2**33 + 5)
    b = physical_state((21, 140), state, jnp.float32, 2**33 + 5)
    c = physical_state((21, 140), state, jnp.float32, 5)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
    assert not np.array_equal(np.asarray(a["rho"]), np.asarray(c["rho"]))
    rho = np.asarray(a["rho"])
    u, v = np.asarray(a["mu"]) / rho, np.asarray(a["mv"]) / rho
    p = 0.4 * (np.asarray(a["en"]) - 0.5 * rho * (u * u + v * v))
    assert rho.min() > 0 and p.min() > 0


def _broken(fault):
    real = repro.core.compile_program

    class Broken:
        def __init__(self, gen):
            self.gen = gen

        def fn(self, **arrays):
            return fault(self.gen.fn(**arrays), arrays)

    return lambda *args, **kwargs: Broken(real(*args, **kwargs))


@pytest.mark.parametrize("fault", [
    # the step returns its state unchanged
    lambda out, a: {f"{k}_new": a[k] for k in ("rho", "mu", "mv", "en")},
    # one answer altered where it is produced
    lambda out, a: dict(out, en_new=out["en_new"].at[7, 50].add(0.01)),
], ids=["state_unchanged", "answer_altered"])
def test_broken_step_is_not_correct(monkeypatch, fault):
    monkeypatch.setattr(repro.core, "compile_program", _broken(fault))
    result, _ = _run(seconds=0.2)
    assert not result["correct"]
    assert result["checks"]["rel_err"]["value"] > result["checks"]["rel_err"]["limit"]
