"""NPB MG's finest-level residual and its norms (``resid`` and
``norm2u3`` of ``mg.f``) through the default ``compile_program`` path.

The program is compared with a float64 NumPy evaluation of ``mg.f``'s
loops, written here, through the Pallas interpreter, the pure-JAX plan
interpreter and the unfused schedule, on seeded inputs at small sizes
whose rows and lanes are off the (8, 128) tile, as 514 is.  Its plan is
one call: a plane window on ``u``, two carried accumulators, one row a
grid step.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import trace
from repro.core import compile_program, tiling
from repro.core.programs import MG_C_POINTS, mg_resid_program
from repro.core.unfused import build_unfused

#: (Nk, Nj, Ni): 9 and 17 rows end in a partial 8-row block; 140 and 258
#: lanes in a partial 128-lane tile (258 = 2 * 128 + 2, as 514 = 4 * 128 + 2)
SHAPES = [(6, 9, 140), (7, 17, 258)]
BACKENDS = ("pallas", "interp_jax", "unfused")
#: ``r`` in float32 against float64: 22 terms of up to 8/3 |u| each, so
#: a few float32 ulps of about 10 (the inputs are standard normal).
R_ATOL = 2e-5
#: ``rnm2`` sums a few thousand float32 squares in an order each backend
#: picks, then takes a square root: a relative error of a few 1e-7, far
#: under this.  ``rnmu`` is a largest |r|, exact up to r's own rounding.
RNM2_RTOL = 1e-5
RNMU_RTOL = 1e-6


def _mg_f64(u, v):
    """``resid`` and ``norm2u3`` as ``mg.f`` loops them, in float64:
    for each plane ``i3`` and row ``i2`` the partial sums ``u1`` and
    ``u2`` over every lane ``i1``, then ``r`` over the interior lanes."""
    u = np.asarray(u, np.float64)
    v = np.asarray(v, np.float64)
    a0, a2, a3 = -8.0 / 3.0, 1.0 / 6.0, 1.0 / 12.0
    n3, n2, _ = u.shape
    r = np.zeros_like(u)
    for i3 in range(1, n3 - 1):
        for i2 in range(1, n2 - 1):
            u1 = (u[i3, i2 - 1] + u[i3, i2 + 1]
                  + u[i3 - 1, i2] + u[i3 + 1, i2])
            u2 = (u[i3 - 1, i2 - 1] + u[i3 - 1, i2 + 1]
                  + u[i3 + 1, i2 - 1] + u[i3 + 1, i2 + 1])
            r[i3, i2, 1:-1] = (v[i3, i2, 1:-1] - a0 * u[i3, i2, 1:-1]
                               - a2 * (u2[1:-1] + u1[:-2] + u1[2:])
                               - a3 * (u2[:-2] + u2[2:]))
    inner = r[1:-1, 1:-1, 1:-1]
    return {"r": r, "rnm2": np.sqrt(np.sum(inner * inner) / MG_C_POINTS),
            "rnmu": np.max(np.abs(inner))}


def _inputs(shape, seed):
    rng = np.random.default_rng(seed)
    return {k: jnp.asarray(rng.standard_normal(shape), jnp.float32)
            for k in ("u", "v")}


@pytest.fixture(scope="module")
def runs():
    """``{shape: (inputs, {backend: outputs})}``, each backend jitted."""
    prog = mg_resid_program()
    fns = {b: compile_program(prog, backend=b).fn for b in BACKENDS[:2]}
    fns["unfused"] = build_unfused(prog).fn
    out = {}
    for n, shape in enumerate(SHAPES):
        arrs = _inputs(shape, 2**31 + n)
        out[shape] = arrs, {b: jax.jit(lambda a, f=f: f(**a))(arrs)
                            for b, f in fns.items()}
    return out


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_matches_mg_f_in_float64(runs, shape, backend):
    arrs, got = runs[shape]
    want = _mg_f64(arrs["u"], arrs["v"])
    out = got[backend]
    assert out["r"].shape == shape and out["rnm2"].shape == ()
    np.testing.assert_allclose(np.asarray(out["r"]), want["r"], rtol=0,
                               atol=R_ATOL)
    np.testing.assert_allclose(float(out["rnm2"]), want["rnm2"], rtol=RNM2_RTOL)
    np.testing.assert_allclose(float(out["rnmu"]), want["rnmu"], rtol=RNMU_RTOL)


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_r_is_bit_identical_across_backends(runs, shape):
    """The three schedules call the same rule body on the same operands,
    so ``r`` agrees to the bit; its halo is the goal's fill, 0."""
    _, got = runs[shape]
    r = np.asarray(got["pallas"]["r"])
    for b in BACKENDS[1:]:
        np.testing.assert_array_equal(np.asarray(got[b]["r"]), r)
    halo = np.ones(shape, bool)
    halo[1:-1, 1:-1, 1:-1] = False
    assert (r[halo] == 0.0).all()


def test_plan_is_one_call_with_a_plane_window_and_two_accumulators():
    gen = compile_program(mg_resid_program(), backend="pallas")
    (call,) = gen.kernel_plan.calls
    assert {i.name for i in call.inputs if i.plane} == {"u"}
    assert {i.name for i in call.inputs} == {"u", "v"}
    assert len(call.accs) == 2
    assert [o.kind for o in call.outputs] == ["external", "acc", "acc"]
    assert [h.op for h in call.host_post] == ["rnm2"]
    # carried accumulators keep one row a grid step, at the cell's size too
    assert tiling.row_tile(call, 17, 258, 4) == 1
    t = tiling.call_tiling(call, (514, 514, 514), 4)
    assert (t.rows, t.seats.steps, t.vmem_need, t.vmem_limit) == (
        1, 514 * 515, 4_767_744, None)


def test_trace_counts_the_accumulators_and_their_lane_folds():
    gen = compile_program(mg_resid_program(), backend="pallas")
    arrs = _inputs(SHAPES[0], 5)
    with trace.recording() as rec:
        jax.make_jaxpr(lambda a: gen.fn(**a))(arrs)
    assert rec.counters["hfav.accumulators"] == 2
    assert rec.counters["hfav.outputs_lane_folded"] == 2
    assert rec.counters["hfav.row_tile"] == 1
    assert rec.counters["hfav.outputs_seated"] == 1
