"""Hydro2D (HFAV paper section 5.4): one fused x-then-y Godunov step of
the 2-D Euler equations, through the default ``compile_program`` path.

The fused step is compared with the benchmark's plain reference
(``bench/references/hydro2d.py``, written from the equations), with the
unfused schedule of the same rule bodies and with the pure-JAX plan
interpreter, on seeded physical states at small ragged sizes.  Its plan
is checked by PlanCheck and its VMEM need by the model that picks the
row tile and the scoped limit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.generators.sweep_state import physical_state
from bench.spec import Bench
from repro import trace
from repro.core import compile_program
from repro.core.plancheck import (DEFAULT_VMEM_BUDGET, LANE, body_values,
                                  call_vmem, check_plan, has_errors, row_tile,
                                  scoped_vmem_limit)
from repro.core.programs import hydro2d_program
from repro.core.unfused import build_unfused

#: The benchmark configuration's state parameters (bench/configs/hydro2d_8k.json).
STATE = {"gamma": 1.4, "log_rho_sd": 0.25, "vel_sd": 0.5, "log_p_sd": 0.25}
SEEDS = [3, 2**31 + 17]
#: Float32 rounding through ten Riemann iterations of square roots and
#: divisions: the fused step and another schedule or implementation of
#: the same equations round differently, by a few ulps of the data.
#: Measured at most 2.4e-7 on these states; the reference in bfloat16
#: errs by 1e-2 and more (bench/tests/test_chipbench_hydro2d.py).
REL_ERR = 2e-6


@pytest.fixture(scope="module")
def fused():
    return compile_program(hydro2d_program())


@pytest.fixture(scope="module")
def call(fused):
    (c,) = fused.kernel_plan.calls
    return c


def _rel_err(got: dict, want: dict) -> float:
    assert set(got) == set(want)
    errs = []
    for k in want:
        g, w = np.asarray(got[k], np.float64), np.asarray(want[k], np.float64)
        assert g.shape == w.shape, k
        errs.append(np.max(np.abs(g - w) / (np.abs(w) + np.mean(np.abs(w)))))
    return max(errs)


@pytest.fixture(scope="module")
def others():
    """The plain reference, the unfused schedule and the pure-JAX
    interpreter, each jitted once."""
    reference = Bench().reference("hydro2d")
    return {
        "reference": lambda a: reference(a, jnp.float32),
        "unfused": jax.jit(lambda a: build_unfused(hydro2d_program()).fn(**a)),
        "interp_jax": jax.jit(lambda a: compile_program(
            hydro2d_program(), backend="interp_jax").fn(**a)),
    }


@pytest.mark.parametrize("nj,ni,against", [
    (20, 140, ("reference",)),
    (37, 260, ("reference", "unfused", "interp_jax")),
])
def test_fused_step_matches_reference_unfused_and_interp_jax(fused, others, nj, ni,
                                                            against):
    """The default path (the Pallas interpreter off the chip) against the
    plain reference, the unfused schedule and the pure-JAX interpreter,
    each within :data:`REL_ERR`, on two seeded states."""
    step = jax.jit(lambda a: fused.fn(**a))
    for seed in SEEDS:
        state = physical_state((nj, ni), STATE, jnp.float32, seed)
        got = step(state)
        for name in against:
            assert _rel_err(got, others[name](state)) <= REL_ERR, (name, seed)
        for k, v in got.items():  # a zero two-cell border, as in the reference
            v = np.asarray(v)
            assert not v[:2].any() and not v[-2:].any() and not v[:, :2].any() \
                and not v[:, -2:].any(), k


def test_uniform_state_stays_uniform(fused):
    """Every face of a uniform state carries the same flux, so the step
    leaves each interior cell as it was, exactly."""
    values = {"rho": 1.3, "mu": 0.2, "mv": -0.1, "en": 2.5}
    got = fused.fn(**{k: jnp.full((20, 140), v, jnp.float32) for k, v in values.items()})
    for k, v in values.items():
        np.testing.assert_array_equal(np.asarray(got[f"{k}_new"])[2:-2, 2:-2],
                                      np.full((16, 136), v, np.float32))


def test_plan_is_one_nest_with_no_planchecker_error(fused, call):
    """Both passes fuse into one nest, and PlanCheck finds no error: every
    same-step (``local``) read sits at its producer's lead."""
    assert len(fused.kernel_plan.calls) == 1
    diags = check_plan(fused.kernel_plan)
    assert not has_errors(diags), [str(d) for d in diags if d.severity == "error"]
    leads = {str(t): s.lead for s in call.steps for ts in s.writes
             for kind, t in ts if kind == "local"}
    local_reads = [(rd.src[6:], rd.j_off) for s in call.steps for rd in s.reads
                   if rd.src.startswith("local:")]
    assert local_reads
    assert all(j_off == leads[name] for name, j_off in local_reads)


def test_row_tiled_steps_match_jax_bit_for_bit(fused):
    """R-row steps (one 40-row tile, and three of 16 with the last
    ragged) make the ``jax`` backend's outputs bit for bit, both
    compiled with XLA's CPU back end unoptimized, so that neither fuses
    a multiply and an add into one rounding."""
    opts = {"xla_backend_optimization_level": 0}

    def run(fn, a):
        return jax.jit(lambda x: fn(**x)).lower(a).compile(opts)(a)

    state = physical_state((37, 140), STATE, jnp.float32, 5)
    want = run(compile_program(hydro2d_program(), backend="jax").fn, state)
    with trace.recording() as rec:
        got = run(fused.fn, state)
    assert rec.counters["hfav.row_tile"] == 40
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]), err_msg=k)


def test_body_values_count_the_riemann_step(call):
    """The liveness count over the fused steps: the x pass's Riemann
    step holds the most, 25 row values (its eight face-state reads, the
    solver's temporaries and its four fluxes)."""
    assert body_values(call) == 25
    assert [s.op for s in call.steps][:3] == ["primx", "tracex", "riemannx"]


@pytest.mark.parametrize("n,rows,limit", [
    # 1028^2: the step bodies push R down to 40 under the default limit
    (1028, 40, None),
    # 8196^2: the smallest tile, 8 rows, needs a raised limit
    (8196, 8, 29434880),
])
def test_call_vmem_counts_the_body(call, n, rows, limit):
    """``call_vmem``'s body term is the body values at R rows by the
    padded lanes; with it the row tile and the scoped limit follow."""
    lanes = -(-n // LANE) * LANE
    R = row_tile(call, n, n, 4, False)
    assert R == rows
    report = call_vmem(call, n, n, 4, False, rows=R)
    assert report["body"] == 25 * R * lanes * 4
    assert scoped_vmem_limit(report["total"]) == limit
    without = report["total"] - report["body"]
    if limit is None:  # the body term is what keeps R from the next tile
        bigger = call_vmem(call, n, n, 4, False, rows=R + 8)
        assert bigger["total"] + bigger["total"] // 4 > DEFAULT_VMEM_BUDGET
        assert without + without // 4 <= DEFAULT_VMEM_BUDGET


def test_vmem_counters_per_traced_call(fused):
    """``hfav.vmem_need_bytes`` and ``hfav.vmem_limit_bytes`` are the
    model's need at the chosen R and the scoped limit passed, added once
    per traced stencil call; at 8196^2 the limit is raised."""
    (c,) = fused.kernel_plan.calls
    for n, limit in [(260, 0), (8196, 29434880)]:
        shapes = {k: jax.ShapeDtypeStruct((n, n), jnp.float32)
                  for k in ("rho", "mu", "mv", "en")}
        with trace.recording() as rec:
            jax.eval_shape(lambda a: fused.fn(**a), shapes)
        need = call_vmem(c, n, n, 4, False)["total"]
        assert rec.counters["hfav.vmem_need_bytes"] == need
        assert rec.counters["hfav.vmem_limit_bytes"] == limit
        (build,) = rec.named("hfav.build_call")
        assert build.attrs["vmem_need_bytes"] == need
        assert build.attrs["vmem_limit_bytes"] == limit
