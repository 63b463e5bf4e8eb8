"""The Pallas kernel writes each external goal at its seat.

An ``external`` output of a stencil call (a goal) comes back from the
interpreter's ``build_call`` goal-shaped, ``(*N_outer, Nj, Ni)``, with
the fill (0.0) in every row, plane and lane outside the goal, so the host
half hands it on as it is: the traced program holds no trim (``slice``)
or re-seat (``pad``/``scatter``) around the ``pallas_call``.  The R-row
path writes each goal block one grid step late from a VMEM carry buffer;
the one-row paths (BlockSpec rows and ``double_buffer``) write each row
at its goal row in a revisited 8-row block.  Rows are ragged against
both tiles, and the outputs equal the ``interp_jax`` interpreter's and
the plain unfused reference's.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _interp_utils import arrays_for, sizes_for
from repro import trace
from repro.core import compile_program
from repro.core import plancheck
from repro.core.interpreters import execute_plan
from repro.core.plancheck import row_tile
from repro.core.programs import ALL_PROGRAMS
from repro.core.unfused import build_unfused

#: program -> (outputs the kernel seats, outputs the host trims)
COUNTS = {"cosmo": (1, 0), "heat3d": (1, 0), "hydro2d": (4, 0),
          "heat3d_residual_norm": (1, 1)}

#: mode -> (cap of R, Nj, double_buffer): R-row steps over 37 rows (two
#: 16-row steps and a ragged 5-row one), and one row a step over 21 rows
#: (two 8-row blocks and a ragged 5-row one), through BlockSpecs or the
#: explicit DMA pipeline
MODES = {"rows": (16, 37, False), "one_row": (1, 21, False),
         "double_buffer": (None, 21, True)}

CASES = [pytest.param(name, mode, id=f"{name}-{mode}")
         for name in ("cosmo", "heat3d", "hydro2d") for mode in MODES] + [
    # an accumulator keeps R = 1 beside a seated goal
    pytest.param("heat3d_residual_norm", "rows", id="heat3d_residual_norm")]

#: ops that trim or re-seat an array outside the kernel
GLUE = {"pad", "slice", "dynamic_slice", "dynamic_update_slice", "scatter"}


def _goal_mask(call, oi, shape):
    """Where the goal of ``call.outputs[oi]`` lies in its seated array."""
    out = call.outputs[oi]
    *outer, nj, ni = shape
    (step,) = [s for s in call.steps
               if any(("out", oi) in t for t in s.writes)]
    mask = np.zeros(shape, bool)
    seat = tuple(slice(out.outer_lo[d], outer[d] + out.outer_hi[d])
                 for d in range(len(outer)))
    lanes = slice(step.out_col0, step.out_col0 + ni + step.out_w_off)
    mask[seat + (slice(out.j_lo, nj + out.j_hi), lanes)] = True
    return mask


@pytest.mark.parametrize("name,mode", CASES)
def test_external_outputs_are_seated_by_the_kernel(monkeypatch, name, mode):
    cap, nj, double_buffer = MODES[mode]
    if cap is not None:
        monkeypatch.setattr(plancheck, "ROW_TILE_CAP", cap)
    prog = ALL_PROGRAMS[name]()
    gen = compile_program(prog, backend="pallas", double_buffer=double_buffer,
                          use_cache=False)
    kp = gen.kernel_plan
    (call,) = [c for c in kp.calls if c.has_grid]
    dims = {"i": 20, "j": nj, "k": 5}
    arrs = arrays_for(kp, np.random.default_rng(7), dims)
    if name == "hydro2d":  # a positive density and pressure
        arrs = {k: jnp.abs(v) + 0.5 for k, v in arrs.items()}
    sizes = sizes_for(kp, dims)
    ni = sizes["Ni"]
    R = row_tile(call, nj, ni, 4, double_buffer)
    assert (R > 1) == (mode == "rows" and not call.accs)

    with trace.recording() as rec:
        jaxpr = jax.make_jaxpr(lambda a: gen.fn(**a))(arrs)
    prims = {e.primitive.name for e in jaxpr.eqns}
    assert "pallas_call" in prims
    if not COUNTS[name][1]:  # no output the host trims: no glue at all
        assert not prims & GLUE
    assert (rec.counters["hfav.outputs_seated"],
            rec.counters["hfav.outputs_trimmed"]) == COUNTS[name]

    (kernel,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    got = gen.fn(**arrs)
    want = build_unfused(prog).fn(**arrs)
    other = execute_plan(kp, interpreter="interp_jax")(**arrs)
    store_of = {var: store for store, var in kp.goal_outputs}
    seated = [oi for oi, o in enumerate(call.outputs) if o.kind == "external"]
    assert len(seated) == COUNTS[name][0]
    for oi in seated:
        store = store_of[call.outputs[oi].name]
        arr = np.asarray(got[store])
        # the kernel's own output is the goal-shaped array
        assert kernel.outvars[oi].aval.shape == arr.shape == want[store].shape
        halo = ~_goal_mask(call, oi, arr.shape)
        assert halo.any() and (arr[halo] == 0.0).all()
        assert (np.asarray(other[store])[halo] == 0.0).all()
    for store in want:
        np.testing.assert_allclose(np.asarray(got[store]),
                                   np.asarray(other[store]),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got[store]),
                                   np.asarray(want[store]),
                                   rtol=1e-5, atol=1e-5)
