"""Whole open-loop runs off the chip: a sound one, and ones whose served
answers are broken underneath, which must come out not correct."""
import numpy as np
import pytest

import repro.serve.plans as plans
from bench.tests.small import run_small, small_batches


def test_sound_serve_run_is_correct(monkeypatch):
    small_batches(monkeypatch)
    result, notes = run_small("heat3d_7pt.serve", seconds=1.0)
    assert result["correct"], result["checks"]
    assert result["attempted"] == 20 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "serve_p95_ms"}
    assert result["metrics"]["serve_p95_ms"]["value"] > 0
    assert any(line.startswith("generator_late_ms") for line in notes)


def _broken(fault):
    real = plans.compile_batched

    def compile_batched(*args, **kwargs):
        gen = real(*args, **kwargs)
        fn = gen.fn

        def broken(stacked):
            return {k: fault(np.array(v), stacked) for k, v in fn(stacked).items()}

        gen.fn = broken
        return gen

    return compile_batched


def _half_batch_left_out(out, stacked):
    out[out.shape[0] // 2:] = 0.0
    return out


def _answer_altered(out, stacked):
    out[:, 2, 3, 5] += 1.0
    return out


def _state_unchanged(out, stacked):
    return np.array(stacked["u"])


@pytest.mark.parametrize("fault", [_half_batch_left_out, _answer_altered, _state_unchanged])
def test_broken_answers_are_not_correct(monkeypatch, fault):
    small_batches(monkeypatch)
    monkeypatch.setattr(plans, "compile_batched", _broken(fault))
    result, _ = run_small("heat3d_7pt.serve_sat", seconds=1.0)
    assert not result["correct"]
    assert result["checks"]["rel_err"]["value"] > result["checks"]["rel_err"]["limit"]
