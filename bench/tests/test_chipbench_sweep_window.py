"""The sweep window off the chip: sweeps chained through a donated output,
the sampled sweep's output kept out of the chain, every sweep sent
waited for."""
import jax.numpy as jnp
import pytest

from bench.generators.sweep import _buffer, _chained, _run


def _double():
    inputs = {"u": jnp.arange(24.0).reshape(2, 3, 4)}
    step, shapes = _chained(lambda u: {"v": u * 2.0}, inputs)
    return step, shapes, inputs


@pytest.mark.parametrize("ahead", [1, 3, 1000])
def test_window_keeps_the_sampled_output_and_the_last(ahead):
    step, shapes, inputs = _double()
    n, window_s, outs = _run(step, inputs, _buffer(shapes), _buffer(shapes), 0.05,
                             ahead, 1, "test.window")
    assert n > 2 and window_s >= 0.05
    assert len(outs) == 2
    for out in outs:  # a donated output would have been deleted
        assert not out["v"].is_deleted()
        assert (out["v"] == inputs["u"] * 2.0).all()


def test_window_shorter_than_the_sample_keeps_the_last_only():
    step, shapes, inputs = _double()
    n, _, outs = _run(step, inputs, _buffer(shapes), _buffer(shapes), 0.0, 2, 10**9,
                      "test.window")
    assert n == 1 and len(outs) == 1
    assert (outs[0]["v"] == inputs["u"] * 2.0).all()
