"""Whole sweep runs off the chip whose timed path is broken underneath:
each must come out not correct."""
import jax.numpy as jnp
import pytest

import repro.core
from bench.tests.small import run_small


def _broken(fault):
    real = repro.core.compile_program

    class Broken:
        def __init__(self, gen):
            self.gen = gen

        def fn(self, **arrays):
            return {k: fault(v, arrays) for k, v in self.gen.fn(**arrays).items()}

    return lambda *args, **kwargs: Broken(real(*args, **kwargs))


@pytest.mark.parametrize("fault", [
    lambda out, a: a["u"],                     # the sweep returns its state unchanged
    lambda out, a: out.at[0, 3, 5].add(1.0),   # an answer altered where it is produced
    lambda out, a: out.at[out.shape[0] // 2:].set(0.0),  # half the levels left out
], ids=["state_unchanged", "answer_altered", "half_left_out"])
@pytest.mark.parametrize("cell", ["cosmo1_hdiff.sweep", "heat3d_7pt.sweep"])
def test_broken_sweep_is_not_correct(monkeypatch, cell, fault):
    monkeypatch.setattr(repro.core, "compile_program", _broken(fault))
    result, _ = run_small(cell, seconds=0.2)
    assert not result["correct"]
    assert result["checks"]["rel_err"]["value"] > result["checks"]["rel_err"]["limit"]


def test_nan_answer_is_not_correct(monkeypatch):
    monkeypatch.setattr(repro.core, "compile_program",
                        _broken(lambda out, a: out.at[1, 2, 3].set(jnp.nan)))
    result, _ = run_small("heat3d_7pt.sweep", seconds=0.2)
    assert not result["correct"] and result["checks"]["rel_err"]["value"] == "nan"
