"""Lifted Pallas-executor restrictions, each validated against the
unfused oracle in interpret mode:

* outer grids (``n_outer >= 1``, including the 4-D ``(l, k, j, i)``
  pyramid with a rolling buffer carried on a 3-D grid);
* k-tiled reductions (carried VMEM accumulator across outer tiles) and
  per-outer-tile reductions (output keeps the outer dims);
* outer-dim stencil halos (``u[k-1][j][i]`` reads) served by multi-plane
  VMEM windows carried across the outer grid, including on grids with
  two outer dims and with the non-exact outer extents halos induce;
* **producer plane windows** — same-nest *produced* variables read at
  plane offsets (``st(u[k-1])`` where ``st`` is computed in the nest):
  the producer runs its plane-dim lead ahead of the outer grid and
  keeps whole planes resident in VMEM;
* **halo'd reductions** — plane windows and carried accumulators fused
  in one nest (``heat3d_residual_norm``);
* reductions keeping the row dim (``rsum[j]``) and reductions keeping a
  strict leading subset of the outer dims (``(l, k, j, i) -> out[l]``) —
  on both backends;
* cross-row (j-offset) reads of same-nest materialized variables;
* each of these with R rows a grid step and with one (R capped at 1).

Plus regression tests pinning the *remaining* restrictions to the
improved ``PallasUnsupported`` messages (the table in docs/BACKENDS.md)
and the streamed-input DMA origin fix (window shape and grid range must
come from the same extent frame).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Generated, PallasGenerated, PallasUnsupported,
                        Program, axiom, clear_compile_cache, compile_program,
                        goal, kernel, register_pallas_split_win, tiling)
from repro.core.engine import PALLAS_SPLIT_WINS
from repro.core.programs import (advect4d_halo_program, cosmo_program,
                                 energy3d_program, heat3d_program,
                                 heat3d_residual_norm_program,
                                 heat3d_stage_program,
                                 plane_sum_program, pyramid4d_program,
                                 row_sum_program, smooth_norm_program,
                                 subset_sum_program)
from repro.core.unfused import build_unfused


@pytest.fixture(autouse=True)
def _fresh_cache():
    clear_compile_cache()
    yield
    clear_compile_cache()


@pytest.fixture(params=["rows", "one_row"])
def streaming(request, monkeypatch):
    """R rows a grid step (``rows``), or one (``one_row``: R capped at
    1, the path that accumulator calls take)."""
    if request.param == "one_row":
        monkeypatch.setattr(tiling, "ROW_TILE_CAP", 1)
    return request.param


def _u(rng, shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


LIFTED = [
    # (program builder, output name, input shape, restriction exercised)
    (pyramid4d_program, "edge", (2, 3, 9, 40), "outer-grid n_outer=2"),
    (cosmo_program, "unew", (3, 10, 70), "outer-grid n_outer=1"),
    (energy3d_program, "energy", (3, 7, 33), "k-tiled carried reduction"),
    (plane_sum_program, "colsum", (4, 6, 20), "per-outer-tile reduction"),
    (smooth_norm_program, "nflux", (9, 30), "cross-row materialized read"),
    (heat3d_program, "heat", (4, 7, 24), "k-halo plane window"),
    (advect4d_halo_program, "adv", (2, 4, 6, 20), "plane window, 2 outer dims"),
    (row_sum_program, "rsum", (7, 21), "row-kept reduction"),
    (subset_sum_program, "lsum", (3, 4, 5, 16), "subset-outer reduction"),
    (heat3d_stage_program, "heat", (5, 7, 24), "producer plane window"),
    (heat3d_residual_norm_program, "rnorm", (5, 7, 24), "halo'd reduction"),
]


@pytest.mark.parametrize("build,out,shape,_why", LIFTED,
                         ids=[c[3] for c in LIFTED])
def test_lifted_restriction_matches_oracle(rng, build, out, shape, _why,
                                           streaming):
    prog = build()
    gen = compile_program(prog, backend="pallas")
    assert isinstance(gen, PallasGenerated)
    u = _u(rng, shape)
    got = gen.fn(u=u)[out]
    want = build_unfused(prog).fn(u=u)[out]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def _broadcast_coeff_program():
    """A 2-D coefficient field on a (k, j, i) grid: the streamed input
    `c` carries only the (j, i) suffix (InputPlan.n_outer=0 on an
    n_outer=1 grid) and broadcasts over k."""
    k_mul = kernel(
        "damp",
        inputs=[
            ("a", "u?[k?][j?][i?]"),
            ("b", "u?[k?][j?+1][i?]"),
            ("c", "c[j?][i?]"),
        ],
        outputs=[("o", "damped(u?[k?][j?][i?])")],
        fn=lambda a, b, c: (a + b) * c,
    )
    return Program(
        rules=[k_mul],
        axioms=[
            axiom("u[k?][j?][i?]", k="Nk", j="Nj", i="Ni"),
            axiom("c[j?][i?]", j="Nj", i="Ni"),
        ],
        goals=[goal("damped(u[k][j][i])", store_as="damped",
                    k=("Nk", 0, 0), j=("Nj", 0, -1), i=("Ni", 0, 0))],
        loop_order=("k", "j", "i"),
    )


def test_broadcast_suffix_input_matches_oracle(rng, streaming):
    """Streamed inputs over a dim *suffix* broadcast across the leading
    outer grid dims, R rows or one row a grid step."""
    prog = _broadcast_coeff_program()
    gen = compile_program(prog, backend="pallas")
    (ispec_u, ispec_c) = [i for i in gen.call.inputs if not i.scalar]
    assert {ispec_u.name: ispec_u.n_outer,
            ispec_c.name: ispec_c.n_outer} == {"u": 1, "c": 0}
    u, c = _u(rng, (3, 8, 33)), _u(rng, (8, 33))
    got = gen.fn(u=u, c=c)["damped"]
    want = build_unfused(prog).fn(u=u, c=c)["damped"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_outer_grid_plan_shape():
    """pyramid4d maps both outer identifiers onto leading grid dims and
    carries the blur in a 3-row rolling window."""
    gen = compile_program(pyramid4d_program(), backend="pallas")
    assert gen.call.n_outer == 2
    assert [(w.name, w.stages) for w in gen.call.windows] == [("b_blur_u", 3)]


def test_ktiled_reduction_plan():
    """energy3d: one carried accumulator on a (k, j) grid."""
    gen = compile_program(energy3d_program(), backend="pallas")
    (acc,) = gen.call.accs
    assert gen.call.n_outer == 1 and not acc.per_outer


def test_per_outer_reduction_plan():
    """plane_sum: the accumulator re-initializes per k-tile."""
    gen = compile_program(plane_sum_program(), backend="pallas")
    (acc,) = gen.call.accs
    assert acc.per_outer


def test_cross_row_read_gets_rolling_window():
    """smooth_norm: the materialized flux is ALSO served in-nest from a
    2-stage rolling window (rows j and j-1)."""
    gen = compile_program(smooth_norm_program(), backend="pallas")
    assert len(gen.calls) == 2
    assert [(w.name, w.stages) for w in gen.calls[0].windows] \
        == [("b_flux_u", 2)]


def test_heat3d_plane_window_plan():
    """heat3d: the k +/- 1 reads give the streamed input a 3-plane VMEM
    window with a one-tile plane lead, and the k grid dim gains one
    warm-up tile (outer_lo = -1) to prime it."""
    gen = compile_program(heat3d_program(), backend="pallas")
    call = gen.call
    (ispec,) = call.inputs
    assert (ispec.p_stages, ispec.p_lead) == (3, 1) and ispec.plane
    assert call.n_outer == 1
    assert call.outer_lo == (-1,) and call.outer_hi_off == (-1,)


def test_advect4d_plane_window_on_two_outer_dims():
    """advect4d_halo: the plane window rides the *last* outer grid dim
    (k) while l stays an exact leading grid dim."""
    gen = compile_program(advect4d_halo_program(), backend="pallas")
    call = gen.call
    (ispec,) = call.inputs
    assert call.n_outer == 2
    assert (ispec.p_stages, ispec.p_lead) == (3, 1)
    assert call.outer_lo == (0, -1) and call.outer_hi_off == (0, -1)


def test_producer_plane_window_plan():
    """heat3d_stage: the same-nest produced intermediate gets a 3-plane
    producer window with the stage kernel running one tile ahead, and —
    consumed only in-nest — is never materialized to HBM."""
    gen = compile_program(heat3d_stage_program(), backend="pallas")
    call = gen.call
    (w,) = call.windows
    assert w.plane and (w.p_stages, w.p_lead) == (3, 1)
    # the producer's step runs at plane lead 1, row lead 1
    stage = next(s for s in call.steps if s.op == "stage")
    assert stage.writes == ((("buf", "b_st_u"),),)
    assert stage.lead == 1
    # only the goal is an output: the intermediate skipped HBM entirely
    assert [o.name for o in call.outputs] == ["heat_u"]
    # the consumer reads planes -1/0/+1 out of the window
    heat = next(s for s in call.steps if s.op == "heat7")
    assert sorted({r.p_off for r in heat.reads}) == [-1, 0, 1]


def test_halo_reduction_plan():
    """heat3d_residual_norm: one nest holds the plane-window input, the
    terminal heat field, its same-step residual consumer, and the
    carried accumulator whose combines are predicated off the window's
    warm-up tiles."""
    gen = compile_program(heat3d_residual_norm_program(), backend="pallas")
    (call,) = gen.calls
    (ispec,) = call.inputs
    assert ispec.plane and ispec.p_stages == 3
    (acc,) = call.accs
    assert not acc.per_outer
    red = next(s for s in call.steps if s.acc is not None)
    assert red.valid_outer == ((1, -1),)
    kinds = sorted(o.kind for o in call.outputs)
    assert kinds == ["acc", "external"]


def test_subset_outer_reduction_plan():
    """subset_sum: the accumulator keeps the leading-prefix outer dim l
    (n_kept=1 of a 2-outer grid) and re-initializes per l tile."""
    gen = compile_program(subset_sum_program(), backend="pallas")
    (acc,) = gen.call.accs
    assert gen.call.n_outer == 2
    assert acc.n_kept == 1 and acc.per_outer


def test_row_kept_reduction_plan():
    """row_sum: no carried accumulator at all — each grid step emits one
    identity-padded partial row, lane-reduced on the host."""
    gen = compile_program(row_sum_program(), backend="pallas")
    assert not gen.call.accs
    (out,) = gen.call.outputs
    assert out.acc is None and out.fill == 0.0
    assert out.kind == "acc_rows" and out.reduce_idx is not None


REDUCTION_SHAPES = [
    (plane_sum_program, "colsum", (4, 6, 20)),
    (row_sum_program, "rsum", (7, 21)),
    (subset_sum_program, "lsum", (3, 4, 5, 16)),
]


@pytest.mark.parametrize("build,out,shape", REDUCTION_SHAPES,
                         ids=[c[0].__name__ for c in REDUCTION_SHAPES])
def test_kept_dim_reductions_on_jax_backend(rng, build, out, shape):
    """The JAX emitter covers every kept-dim reduction shape (no
    more 'neither backend' rows): per-cell accumulator arrays, masked
    in-place combines, lane-reduced returns."""
    prog = build()
    gen = compile_program(prog, backend="jax")
    assert isinstance(gen, Generated)
    u = _u(rng, shape)
    got = gen.fn(u)[out]
    want = build_unfused(prog).fn(u=u)[out]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=1e-4)


def test_row_kept_reduction_with_outer_dims(rng, monkeypatch):
    """A (k, j)-keeping i-reduction on a 3-D grid: acc_rows output with
    outer trimming, R rows or one row a grid step, and on the JAX
    backend."""
    k_sum = kernel("psum", [("x", "u[k?][j?][i]")],
                   [("acc", "psum(u[k?][j?])")],
                   fn=lambda acc, x: acc + x, kind="reduce", init=0.0)
    prog = Program(
        rules=[k_sum],
        axioms=[axiom("u[k?][j?][i?]", k="Nk", j="Nj", i="Ni")],
        goals=[goal("psum(u[k][j])", store_as="psum",
                    k=("Nk", 0, 0), j=("Nj", 0, 0))],
        loop_order=("k", "j", "i"),
        name="psum_rows",
    )
    u = _u(rng, (3, 6, 17))
    want = build_unfused(prog).fn(u=u)["psum"]
    gen = compile_program(prog, backend="pallas")
    for cap in (tiling.ROW_TILE_CAP, 1):
        monkeypatch.setattr(tiling, "ROW_TILE_CAP", cap)
        got = gen.fn(u=u)["psum"]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-4, rtol=1e-4)
    got_j = compile_program(prog, backend="jax").fn(u)["psum"]
    np.testing.assert_allclose(np.asarray(got_j), np.asarray(want),
                               atol=2e-4, rtol=1e-4)


def _same_nest_koff_program():
    """A staged k-difference: diff reads st at k-1 AND k while st is
    produced in the same nest — formerly the last outer-dim restriction
    ('only streamed inputs get plane windows'), now served by a
    2-plane producer window at plane lead 0."""
    k_a = kernel("stage", [("a", "u?[k?][j?][i?]")],
                 [("o", "st(u?[k?][j?][i?])")], fn=lambda a: 2.0 * a)
    k_b = kernel("diff", [("m", "st(u?[k?-1][j?][i?])"),
                          ("c", "st(u?[k?][j?][i?])")],
                 [("o", "d(u?[k?][j?][i?])")], fn=lambda m, c: c - m)
    return Program(
        rules=[k_a, k_b],
        axioms=[axiom("u[k?][j?][i?]", k="Nk", j="Nj", i="Ni")],
        goals=[goal("d(u[k][j][i])", store_as="d",
                    k=("Nk", 1, 0), j=("Nj", 0, 0), i=("Ni", 0, 0))],
        loop_order=("k", "j", "i"),
        name="same_nest_koff",
    )


def test_same_nest_plane_offset_lifted(rng, streaming):
    """The producer-plane-window lift: a same-nest variable read at a
    backward plane offset compiles on the stencil interpreter (2 planes
    resident, producer lead 0) and matches the oracle."""
    prog = _same_nest_koff_program()
    gen = compile_program(prog, backend="pallas")
    assert isinstance(gen, PallasGenerated)
    (w,) = gen.call.windows
    assert w.plane and (w.p_stages, w.p_lead) == (2, 0)
    u = _u(rng, (4, 5, 12))
    got = gen.fn(u=u)["d"]
    want = build_unfused(prog).fn(u=u)["d"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_auto_routes_producer_plane_window_to_pallas():
    """With the last outer-dim restriction gone, auto now routes
    same-nest plane-offset programs to the stencil interpreter."""
    gen = compile_program(_same_nest_koff_program(), backend="auto")
    assert isinstance(gen, PallasGenerated)
    gen2 = compile_program(heat3d_stage_program(), backend="auto")
    assert isinstance(gen2, PallasGenerated)


def _cross_call_halo_program():
    """A materialized intermediate consumed at k +/- 1 in a *later*
    nest: the cross-call streamed input gets the plane window (its
    origins come from the variable extent, not axiom extents)."""
    rules = [
        kernel("fx", [("a", "u?[k?][j?][i?]"), ("b", "u?[k?][j?][i?+1]")],
               [("f", "fx(u?[k?][j?][i?])")], fn=lambda a, b: b - a),
        kernel("nrm", [("x", "fx(u[k][j][i])")], [("acc", "n2(u)")],
               fn=lambda acc, x: acc + x * x, kind="reduce", init=0.0),
        kernel("inv", [("n", "n2(u?)")], [("r", "inv(u?)")],
               fn=lambda n: 1.0 / jnp.sqrt(n + 1e-30)),
        kernel("sm", [("m", "fx(u?[k?-1][j?][i?])"),
                      ("p", "fx(u?[k?+1][j?][i?])"),
                      ("c", "fx(u?[k?][j?][i?])"), ("s", "inv(u?)")],
               [("o", "sm(u?[k?][j?][i?])")],
               fn=lambda m, p, c, s: (m + p + c) * s),
    ]
    return Program(
        rules=rules,
        axioms=[axiom("u[k?][j?][i?]", k="Nk", j="Nj", i="Ni")],
        goals=[goal("sm(u[k][j][i])", store_as="sm",
                    k=("Nk", 1, -1), j=("Nj", 0, 0), i=("Ni", 0, -1))],
        loop_order=("k", "j", "i"),
        name="cross_call_halo",
    )


def test_cross_call_materialized_plane_window(rng, streaming):
    """Plane windows also serve cross-call *materialized* inputs: fx is
    produced by nest 0 (which the reduction splits off), then streamed
    into nest 1 with a 3-plane window and one k warm-up tile."""
    prog = _cross_call_halo_program()
    gen = compile_program(prog, backend="pallas")
    assert len(gen.calls) == 2
    (fx_in,) = [i for i in gen.calls[1].inputs if not i.scalar]
    assert fx_in.name == "fx_u" and (fx_in.p_stages, fx_in.p_lead) == (3, 1)
    assert gen.calls[1].outer_lo == (-1,)
    u = _u(rng, (5, 6, 16))
    got = gen.fn(u=u)["sm"]
    want = build_unfused(prog).fn(u=u)["sm"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=1e-4)


def _narrowed_axiom_program():
    """The DMA-origin regression shape: the axiom's row extent is
    *narrowed* (array rows cover [1, Nj-1) of the iteration space), and
    a j+1 read forces a streaming lead — the fetched window and the grid
    range must agree on the array's origin frame."""
    k = kernel(
        "ridge",
        inputs=[("a", "u?[j?][i?]"), ("b", "u?[j?+1][i?]")],
        outputs=[("o", "ridge(u?[j?][i?])")],
        fn=lambda a, b: b - 2.0 * a,
    )
    return Program(
        rules=[k],
        axioms=[axiom("u[j?][i?]", j=("Nj", 1, -1), i="Ni")],
        goals=[goal("ridge(u[j][i])", store_as="ridge",
                    j=("Nj", 1, -2), i=("Ni", 0, 0))],
        loop_order=("j", "i"),
        name="ridge",
    )


def test_narrowed_axiom_stream_origin(rng, streaming):
    """Regression: the planner used to size the fetched window from the
    axiom extents but the grid range from the variable extent — a
    narrowed axiom row extent misaligned the stream.  Both now come
    from the same frame."""
    prog = _narrowed_axiom_program()
    gen = compile_program(prog, backend="pallas")
    (ispec,) = gen.call.inputs
    assert (ispec.j_lo, ispec.j_hi) == (1, -1)
    # grid start = array origin minus the streaming lead: rows stream
    # from the first array row, not from before it
    assert gen.call.x_lo == ispec.j_lo - ispec.lead
    u = _u(rng, (9, 16))  # Nj=11 positions, rows cover [1, 10)
    got = gen.fn(u=u)["ridge"]
    want = build_unfused(prog).fn(u=u)["ridge"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_auto_routes_single_nest_reduction_to_pallas(rng):
    """The auto routing table shrank: single-nest reductions now go to
    the stencil executor."""
    gen = compile_program(energy3d_program(), backend="auto")
    assert isinstance(gen, PallasGenerated)


def test_auto_split_schedule_routing():
    """Split schedules default to JAX but route to Pallas once the
    program is registered as a measured win."""
    prog = smooth_norm_program()
    assert isinstance(compile_program(prog, backend="auto"), Generated)
    try:
        register_pallas_split_win(prog.name)
        # the stale cached auto->JAX entry must have been invalidated
        gen = compile_program(prog, backend="auto")
        assert isinstance(gen, PallasGenerated)
    finally:
        PALLAS_SPLIT_WINS.discard(prog.name)
    # the default program name would reroute every anonymous program
    with pytest.raises(ValueError, match="default program name"):
        register_pallas_split_win("program")


# ---------------------------------------------------------------------------
# Remaining restrictions: each must raise naming the offending
# variable/dim (regression for the plan.py validate-pass messages)
# ---------------------------------------------------------------------------

def test_loop_order_too_short_message():
    k = kernel("id1", [("a", "u?[i?]")], [("o", "v(u?[i?])")], fn=lambda a: a)
    prog = Program(
        rules=[k],
        axioms=[axiom("u[i?]", i="Ni")],
        goals=[goal("v(u[i])", store_as="v", i=("Ni", 0, 0))],
        loop_order=("i",),
    )
    with pytest.raises(PallasUnsupported, match=r"loop order .* \(row, vector\)"):
        compile_program(prog, backend="pallas")


def test_offset_beyond_plane_dim_message():
    """Stencil offsets in an outer dim *other than* the plane dim stay
    unsupported: only the outer identifier adjacent to the row dim gets
    a plane window."""
    k = kernel(
        "lshift",
        [("a", "u?[l?-1][k?][j?][i?]"), ("c", "u?[l?][k?][j?][i?]")],
        [("o", "v(u?[l?][k?][j?][i?])")],
        fn=lambda a, c: c - a,
    )
    prog = Program(
        rules=[k],
        axioms=[axiom("u[l?][k?][j?][i?]", l="Nl", k="Nk", j="Nj", i="Ni")],
        goals=[goal("v(u[l][k][j][i])", store_as="v",
                    l=("Nl", 1, 0), k=("Nk", 0, 0), j=("Nj", 0, 0),
                    i=("Ni", 0, 0))],
        loop_order=("l", "k", "j", "i"),
    )
    with pytest.raises(PallasUnsupported,
                       match=r"outer dim 'l'.*innermost three dims"):
        compile_program(prog, backend="pallas")
    # auto degrades gracefully to the JAX backend
    assert isinstance(compile_program(prog, backend="auto"), Generated)


def test_same_nest_nonplane_lead_message(rng):
    """A same-nest variable read at a *positive* offset in a non-plane
    outer dim would need the producer to lead a dim with no window
    (volume windows): the planner refuses, the JAX backend covers."""
    k_a = kernel("stage", [("a", "u?[l?][k?][j?][i?]")],
                 [("o", "st(u?[l?][k?][j?][i?])")], fn=lambda a: 2.0 * a)
    k_b = kernel("diff", [("m", "st(u?[l?+1][k?][j?][i?])"),
                          ("c", "st(u?[l?][k?][j?][i?])")],
                 [("o", "d(u?[l?][k?][j?][i?])")], fn=lambda m, c: c - m)
    prog = Program(
        rules=[k_a, k_b],
        axioms=[axiom("u[l?][k?][j?][i?]", l="Nl", k="Nk", j="Nj", i="Ni")],
        goals=[goal("d(u[l][k][j][i])", store_as="d",
                    l=("Nl", 0, -1), k=("Nk", 0, 0), j=("Nj", 0, 0),
                    i=("Ni", 0, 0))],
        loop_order=("l", "k", "j", "i"),
        name="same_nest_loff",
    )
    with pytest.raises(PallasUnsupported,
                       match=r"ahead in outer dim 'l'.*volume windows"):
        compile_program(prog, backend="pallas")
    # auto degrades gracefully AND the JAX compilation is correct
    gen = compile_program(prog, backend="auto")
    assert isinstance(gen, Generated)
    u = _u(rng, (3, 4, 5, 12))
    got = gen.fn(u)["d"]
    want = build_unfused(prog).fn(u=u)["d"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_row_kept_reduction_reducing_outer_dim_message(rng):
    """A row-kept reduction that also folds an outer dim would need a
    per-row accumulator carried across tiles — unsupported on the
    executor, covered by the JAX backend."""
    k_sum = kernel("colsum", [("x", "u[k][j?][i]")],
                   [("acc", "rsum(u[j?])")],
                   fn=lambda acc, x: acc + x, kind="reduce", init=0.0)
    prog = Program(
        rules=[k_sum],
        axioms=[axiom("u[k?][j?][i?]", k="Nk", j="Nj", i="Ni")],
        goals=[goal("rsum(u[j])", store_as="rsum", j=("Nj", 0, 0))],
        loop_order=("k", "j", "i"),
        name="rowsum_over_k",
    )
    with pytest.raises(PallasUnsupported,
                       match=r"keeps the row dim 'j' while reducing"):
        compile_program(prog, backend="pallas")
    gen = compile_program(prog, backend="auto")
    assert isinstance(gen, Generated)
    u = _u(rng, (3, 6, 14))
    got = gen.fn(u)["rsum"]
    want = build_unfused(prog).fn(u=u)["rsum"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=1e-4)


def test_row_kept_reduction_negative_row_origin_message(rng):
    """A row-kept reduction whose reduced i extent starts below 0 cannot
    seat its partial row in the Ni-wide output: the planner must raise
    (so auto degrades to JAX) instead of crashing at call time."""
    k_sum = kernel("nsum", [("x", "u[j?][i]")], [("acc", "nsum(u[j?])")],
                   fn=lambda acc, x: acc + x, kind="reduce", init=0.0)
    prog = Program(
        rules=[k_sum],
        axioms=[axiom("u[j?][i?]", j="Nj", i=("Ni", -1, 0))],
        goals=[goal("nsum(u[j])", store_as="nsum", j=("Nj", 0, 0))],
        loop_order=("j", "i"),
        name="nsum_neg",
    )
    with pytest.raises(PallasUnsupported,
                       match=r"partial-accumulator row .* outside"):
        compile_program(prog, backend="pallas")
    gen = compile_program(prog, backend="auto")
    assert isinstance(gen, Generated)
    u = _u(rng, (5, 12))  # rows cover i in [-1, 11)
    got = gen.fn(u)["nsum"]
    want = np.asarray(u).sum(axis=1)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4, rtol=1e-4)


def test_non_prefix_kept_outer_subset_message(rng):
    """A reduction keeping a non-*prefix* subset of outer dims (out[k]
    on an (l, k) grid) would interleave accumulator lifetimes across
    tiles — unsupported on the executor, covered by the JAX backend."""
    k_sum = kernel("ksum", [("x", "u[l][k?][j][i]")],
                   [("acc", "ksum(u[k?])")],
                   fn=lambda acc, x: acc + x, kind="reduce", init=0.0)
    prog = Program(
        rules=[k_sum],
        axioms=[axiom("u[l?][k?][j?][i?]", l="Nl", k="Nk", j="Nj", i="Ni")],
        goals=[goal("ksum(u[k])", store_as="ksum", k=("Nk", 0, 0))],
        loop_order=("l", "k", "j", "i"),
        name="ksum_nonprefix",
    )
    with pytest.raises(PallasUnsupported,
                       match=r"keeps outer dims \('k',\).*leading prefix"):
        compile_program(prog, backend="pallas")
    gen = compile_program(prog, backend="auto")
    assert isinstance(gen, Generated)
    u = _u(rng, (2, 3, 4, 10))
    got = gen.fn(u)["ksum"]
    want = build_unfused(prog).fn(u=u)["ksum"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=1e-4)


def test_row_variable_crossing_split_message():
    """1-D row variables still cannot cross a stencil-call boundary; the
    message names the variable and the suffix rule."""
    k_col = kernel("colmax", [("x", "u[j][i?]")], [("acc", "cmax(u[i?])")],
                   fn=lambda acc, x: jnp.maximum(acc, x), kind="reduce",
                   init=-1e30)
    k_use = kernel("scale", [("a", "u?[j?][i?]"), ("m", "cmax(u?[i?])")],
                   [("o", "scaled(u?[j?][i?])")], fn=lambda a, m: a / (m + 2e30))
    prog = Program(
        rules=[k_col, k_use],
        axioms=[axiom("u[j?][i?]", j="Nj", i="Ni")],
        goals=[goal("scaled(u[j][i])", store_as="scaled",
                    j=("Nj", 0, 0), i=("Ni", 0, 0))],
        loop_order=("j", "i"),
    )
    with pytest.raises(PallasUnsupported, match=r"cross-call read of vector "
                                                r"accumulator cmax_u"):
        compile_program(prog, backend="pallas")
