"""R-row grid steps of the Pallas stencil interpreter.

Each grid step of ``kernels/stencil2d`` computes a tile of R rows
(:func:`repro.core.tiling.row_tile`), R following from the plan, the
dtype and the shape.  Every output element is made by the same
arithmetic as with one row a step, so the interpreter's outputs equal
the ``jax`` backend's bit for bit, with rows ragged against R.  Calls
with accumulators keep one row a step, and a cap of 1 on R
(``tiling.ROW_TILE_CAP``) puts every call on the one-row path.  The
tiling (:func:`repro.core.tiling.call_tiling`: the VMEM model and the
seated grid) and the ``hfav.grid_steps``/``hfav.row_tile`` counters are
checked against the grid, blocks and scratch that ``build_call``
builds, for every grid call of every program.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _interp_utils import DIM, arrays_for
from repro import trace
from repro.core import compile_program, tiling
from repro.core.interpreters import execute_plan
from repro.core.plan import GridDim
from repro.core.programs import ALL_PROGRAMS
from repro.core.tiling import (LANE, body_values, call_tiling, call_vmem,
                               row_geometry, row_tile, scoped_vmem_limit,
                               seat_geometry)
from repro.kernels.stencil2d import build_call

#: (rows Nj, cap of R): rows below one tile; one ragged 40-row tile;
#: three 16-row steps, the last ragged; one row a step, at both row
#: counts.
ROWS = [(7, None), (37, None), (37, 16), (7, 1), (37, 1)]

#: The programs compared bit for bit below.  hydro2d's step bodies are
#: long enough that XLA's CPU compiler fuses a multiply and an add into
#: one rounding in one schedule and not in the other; tests/test_hydro2d.py
#: compares its R-row steps bit for bit with the compiler's optimizations
#: off.
BIT_EXACT = sorted(set(ALL_PROGRAMS) - {"hydro2d"})


def _bits(got: dict, want: dict, tag: str) -> None:
    assert set(want) <= set(got), tag
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]),
                                      err_msg=f"{tag}:{k}")


@pytest.mark.parametrize("nj,cap", ROWS, ids=[f"nj{n}-cap{c}" for n, c in ROWS])
@pytest.mark.parametrize("name", BIT_EXACT)
def test_row_tiled_pallas_matches_jax_bit_for_bit(name, nj, cap, monkeypatch):
    """R-row steps give the ``jax`` backend's outputs bit for bit, and
    so does the one-row path (calls with accumulators, or a cap of 1),
    which runs one row a step (``hfav.row_tile``)."""
    if cap is not None:
        monkeypatch.setattr(tiling, "ROW_TILE_CAP", cap)
    prog = ALL_PROGRAMS[name]()
    gen = compile_program(prog, backend="pallas", use_cache=False)
    arrs = arrays_for(gen.kernel_plan, np.random.default_rng(11), dict(DIM, j=nj))
    with trace.recording() as rec:
        got = gen.fn(**arrs)
    _bits(got, compile_program(prog, backend="jax").fn(**arrs),
          f"{name}/nj={nj}/cap={cap}")
    tiles = {s.attrs["call"]: s.attrs["row_tile"]
             for s in rec.named("hfav.build_call")}
    grid_calls = [c for c in gen.kernel_plan.calls if c.has_grid]
    assert set(tiles) == {c.name for c in grid_calls}
    assert rec.counters["hfav.row_tile"] == sum(tiles.values())
    for c in grid_calls:
        if c.accs or cap == 1:
            assert tiles[c.name] == 1, c.name
        else:
            assert tiles[c.name] == min(cap or tiling.ROW_TILE_CAP,
                                        -(-nj // 8) * 8), c.name


@pytest.mark.parametrize("shift", [1, 3])
@pytest.mark.parametrize("name", ["laplace5", "heat3d"])
def test_row_tile_absorbs_an_input_start_off_the_tile(name, shift):
    """A grid that starts ``shift`` rows earlier puts each input's first
    row, ``x_lo + lead - j_lo``, off the row tile: the block index map
    brings the input in that many rows ahead (a rolling window keeps as
    many more rows), and the answer is the same."""
    prog = ALL_PROGRAMS[name]()
    kplan = compile_program(prog, backend="pallas").kernel_plan
    (call,) = kplan.calls
    row = call.grid[-1]
    early = dataclasses.replace(call, grid=call.grid[:-1] + (
        GridDim(row.dim, row.lo - shift, row.hi_off),))
    arrs = arrays_for(kplan, np.random.default_rng(5), dict(DIM, j=37))
    nj = 37
    R = row_tile(early, nj, DIM["i"], 4)
    (ispec,) = early.inputs
    assert (early.x_lo + ispec.lead - ispec.j_lo) % R == R - shift
    assert row_geometry(early, nj, R, 4).first_row[ispec.name] == 0
    got = execute_plan(dataclasses.replace(kplan, calls=(early,)),
                       interpreter="pallas")(**arrs)
    _bits(got, compile_program(prog, backend="jax").fn(**arrs), name)


# ---------------------------------------------------------------------------
# The VMEM model and the counters against what build_call builds
# ---------------------------------------------------------------------------

def _built(call, sizes):
    """The grid, the stream blocks and the scratch of one built call, as
    the traced ``pallas_call`` holds them."""
    fn, _ = build_call(call, sizes, jnp.float32, interpret=True)
    *outer, nj, ni = sizes
    args = []
    for i in call.inputs:
        if i.scalar:
            args.append(jax.ShapeDtypeStruct((1, 1), jnp.float32))
            continue
        lead = [outer[d] + (i.outer_his or (0,) * i.n_outer)[k]
                - (i.outer_los or (0,) * i.n_outer)[k]
                for k, d in enumerate(range(len(outer) - i.n_outer, len(outer)))]
        args.append(jax.ShapeDtypeStruct(
            (*lead, nj + i.j_hi - i.j_lo, ni + i.i_hi - i.i_lo), jnp.float32))
    (eqn,) = [e for e in jax.make_jaxpr(fn)(*args).eqns
              if e.primitive.name == "pallas_call"]
    gm = eqn.params["grid_mapping"]
    blocks = [tuple(getattr(b, "block_size", b) for b in bm.block_shape)
              for bm in gm.block_mappings if str(bm.block_aval).startswith("Ref{")]
    scratch = [v.aval for v in eqn.params["jaxpr"].invars[-gm.num_scratch_operands:]]
    vmem = [tuple(a.shape) for a in scratch if str(a).startswith("Ref<vmem>")]
    return tuple(gm.grid), blocks, vmem


def _bytes(shape) -> int:
    """VMEM bytes of one f32 buffer: rows padded to 8, lanes to 128."""
    *lead, rows, lanes = shape
    return math.prod(lead) * -(-rows // 8) * 8 * -(-lanes // LANE) * LANE * 4


def _grid_calls(kplan, dims):
    """Every grid call of ``kplan`` with its ``(*outer, Nj, Ni)`` at the
    loop dims ``dims``."""
    sym = dict(kplan.dim_sizes)
    size = {sym[d]: n for d, n in dims.items() if d in sym}
    return [(c, tuple(size[sym[g.dim]] for g in c.grid) + (size[sym[c.vec_dim]],))
            for c in kplan.calls if c.has_grid]


#: (program, Nj, cap of R): every program at rows below one tile and at
#: a ragged 40-row tile, and the one-row path
MIRROR = [(n, nj, None) for n in sorted(ALL_PROGRAMS) for nj in (7, 37)] \
    + [("laplace5", 37, 1)]


@pytest.mark.parametrize("name,nj,cap", MIRROR,
                         ids=[f"{n}-nj{j}-cap{c}" for n, j, c in MIRROR])
def test_call_vmem_mirrors_built_blocks_and_scratch(name, nj, cap, monkeypatch):
    """For every grid call, ``call_tiling`` is what ``build_call``
    builds: the seated grid, and a VMEM need that counts two buffers of
    every stream block (R rows, or the 8-row groups of one-row steps)
    and every VMEM scratch buffer (windows of ``R + stages - 1`` rows,
    plane windows with their margins, accumulators, seat carry
    buffers), plus the step bodies' values of R rows (8 for one-row
    steps) by ``Ni`` lanes."""
    if cap is not None:
        monkeypatch.setattr(tiling, "ROW_TILE_CAP", cap)
    kplan = compile_program(ALL_PROGRAMS[name](), backend="pallas").kernel_plan
    for call, sizes in _grid_calls(kplan, dict(DIM, j=nj, i=130)):
        *outer, nj, ni = sizes
        t = call_tiling(call, sizes, 4)
        R = t.rows
        assert R == (1 if cap == 1 else row_tile(call, nj, ni, 4)), call.name
        grid, blocks, vmem = _built(call, sizes)
        assert t.seats == seat_geometry(call, outer, nj, R, 4)
        assert grid == (t.seats.steps,), call.name
        assert t.seats.radix[-1] == -(-(nj + call.x_hi_off - call.x_lo) // R)
        assert t.geometry == row_geometry(call, nj, R, 4)
        for w in call.windows:
            if not w.plane:  # the kept rows, rounded up to the tile for R > 1
                keep = w.stages - 1
                assert t.geometry.height[w.name] == \
                    R + (keep if R == 1 else -(-keep // 8) * 8)
        want = sum(2 * _bytes(b) for b in blocks) + sum(_bytes(s) for s in vmem)
        report = call_vmem(call, nj, ni, 4)
        assert report["body"] == _bytes((body_values(call) * max(R, 8), ni))
        assert report["total"] == want + report["body"] == t.vmem_need, call.name
        assert t.vmem_limit == scoped_vmem_limit(t.vmem_need)


def test_vmem_limit_forces_the_row_tile_down(monkeypatch):
    """Three resident 1024x1024 planes leave too little of the default
    scoped VMEM limit for the largest row tile: R drops to the largest
    one that fits, and the built blocks are R rows."""
    monkeypatch.setattr(tiling, "ROW_TILE_CAP", 128)
    (call,) = compile_program(ALL_PROGRAMS["heat3d"](), backend="pallas").kernel_plan.calls
    R = row_tile(call, 1024, 1024, 4)
    assert 8 <= R < 128
    assert scoped_vmem_limit(call_vmem(call, 1024, 1024, 4, rows=R)["total"]) is None
    assert scoped_vmem_limit(call_vmem(call, 1024, 1024, 4, rows=R + 8)["total"])
    grid, blocks, vmem = _built(call, (4, 1024, 1024))
    # four planes of 1024 // R row steps, and one step after the last
    # that writes the last goal block
    assert grid == (4 * (1024 // R) + 1,)
    assert all(b[-2] == R for b in blocks)
    want = sum(2 * _bytes(b) for b in blocks) + sum(_bytes(s) for s in vmem)
    report = call_vmem(call, 1024, 1024, 4)
    assert report["total"] == want + report["body"]


@pytest.mark.parametrize("name", sorted(ALL_PROGRAMS))
def test_grid_step_counters_match_the_built_grid(name):
    """For every grid call, ``hfav.grid_steps`` is the built grid's
    steps and ``hfav.row_tile`` the R chosen, both read from the one
    ``call_tiling`` that ``build_call`` builds from: row-window plans
    (cosmo), plane-window plans (heat3d), accumulators and split
    schedules of several calls."""
    prog = ALL_PROGRAMS[name]()
    gen = compile_program(prog, backend="pallas", use_cache=False)
    dims = dict(DIM, j=150)
    arrs = arrays_for(gen.kernel_plan, np.random.default_rng(0), dims)
    with trace.recording() as rec:
        jaxpr = jax.make_jaxpr(lambda a: gen.fn(**a))(arrs)
    grids = [e.params["grid_mapping"].grid for e in jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    spans = rec.named("hfav.build_call")
    calls = _grid_calls(gen.kernel_plan, dims)
    assert len(grids) == len(spans) == len(calls)
    assert rec.counters["hfav.grid_steps"] == sum(math.prod(g) for g in grids)
    assert rec.counters["hfav.row_tile"] == sum(s.attrs["row_tile"] for s in spans)
    for (call, sizes), grid, sp in zip(calls, grids, spans):
        t = call_tiling(call, sizes, 4)
        assert sp.attrs["call"] == call.name
        assert grid == (t.seats.steps,) == (sp.attrs["grid_steps"],)
        assert sp.attrs["row_tile"] == t.rows
        assert t.seats.radix[-1] == -(-(sizes[-2] + call.x_hi_off - call.x_lo) // t.rows)
