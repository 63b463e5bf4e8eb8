"""A fused nest run in the dim order the device keeps its arrays in
(repro.core.dimorder): the program transform, the loop order read off
the device's layouts, and the host half that hands the permuted
program transposed views.  The layout query is patched to report what a
TPU v5e reports for cosmo1's field, ``(1, 0, 2)``; off the TPU every
layout is row-major."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro import trace
from repro.core import clear_compile_cache, compile_program, dimorder
from repro.core import plan_cache_size
from repro.core.programs import ALL_PROGRAMS, cosmo_program

#: cosmo at a ragged size: 21 rows and 150 lanes fill no (8, 128) tile
SHAPE = (16, 21, 150)


@pytest.fixture
def fresh_cache():
    clear_compile_cache()
    yield
    clear_compile_cache()


def _report(m2m):
    """A layout query that reports ``m2m`` for every 3-dim array and
    row-major for the rest."""
    def query(shape, dtype):
        return tuple(m2m) if len(shape) == 3 else tuple(range(len(shape)))
    return query


def _field(shape=SHAPE, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


@pytest.mark.parametrize("backend", ["pallas", "interp_jax"])
def test_permuted_cosmo_matches_unpermuted_bit_for_bit(fresh_cache, backend,
                                                       monkeypatch):
    gen = compile_program(cosmo_program(), backend=backend)
    u = _field()
    want = gen.fn(u=u)["unew"]
    monkeypatch.setattr(dimorder, "device_major_to_minor", _report((1, 0, 2)))
    with trace.recording() as rec:
        got = gen.fn(u=u)["unew"]
    assert rec.counters["hfav.nest_permuted"] == 1
    # the k dim's 16 rows are the permuted nest's row dim, one tile
    assert rec.counters["hfav.row_tile"] == 16
    assert got.shape == want.shape == SHAPE
    assert bool((got == want).all())


@pytest.mark.parametrize("m2m", [(0, 1, 2), (0, 2, 1), (2, 1, 0)],
                         ids=["row-major", "lane-moved", "reversed"])
def test_no_permutation_keeps_the_plan_and_its_cache_key(fresh_cache, m2m,
                                                        monkeypatch):
    """A row-major report, or one whose order would move the lane dim,
    runs the program's own plan: nothing more is planned or cached."""
    gen = compile_program(cosmo_program(), backend="pallas")
    kplan, before = gen.kernel_plan, plan_cache_size()
    monkeypatch.setattr(dimorder, "device_major_to_minor", _report(m2m))
    with trace.recording() as rec:
        gen.fn(u=_field())
    assert rec.counters["hfav.nest_permuted"] == 0
    assert "hfav.plan_cache.miss" not in rec.counters
    assert plan_cache_size() == before
    assert gen.kernel_plan is kplan
    assert compile_program(cosmo_program(), backend="pallas") is gen


def test_permuted_plan_is_cached_beside_the_programs_plan(fresh_cache,
                                                          monkeypatch):
    """The permuted plan is built on first need, once, under its own key."""
    gen = compile_program(cosmo_program(), backend="pallas")
    before = plan_cache_size()
    monkeypatch.setattr(dimorder, "device_major_to_minor", _report((1, 0, 2)))
    with trace.recording() as rec:
        gen.fn(u=_field())
        gen.fn(u=_field(seed=1))
    assert rec.counters["hfav.plan_cache.miss"] == 1
    assert plan_cache_size() == before + 1


def test_permuted_nest_that_would_run_one_row_keeps_the_parent(
        fresh_cache, monkeypatch):
    """Where the permuted plan would run one row a grid step and the
    program's own runs more, the call keeps the program's own plan."""
    gen = compile_program(cosmo_program(), backend="pallas")
    u = _field()
    want = gen.fn(u=u)["unew"]
    rows = dimorder._rows
    monkeypatch.setattr(
        dimorder, "_rows",
        lambda kp, sizes, dtype: [1] if kp.loop_order[0] == "j"
        else rows(kp, sizes, dtype))
    monkeypatch.setattr(dimorder, "device_major_to_minor", _report((1, 0, 2)))
    with trace.recording() as rec:
        got = gen.fn(u=u)["unew"]
    assert rec.counters["hfav.nest_permuted"] == 0
    assert bool((got == want).all())


def test_device_loop_order_follows_the_layout(monkeypatch):
    kplan = compile_program(cosmo_program(), backend="pallas").kernel_plan
    u = _field()
    for m2m, want in [((1, 0, 2), ("j", "k", "i")), ((0, 1, 2), None),
                      ((0, 2, 1), None), ((2, 0, 1), None)]:
        monkeypatch.setattr(dimorder, "device_major_to_minor", _report(m2m))
        assert dimorder.device_loop_order(kplan, {"u": u}) == want, m2m


def test_device_loop_order_refuses_inputs_that_disagree(monkeypatch):
    """Hydro2D's four fields: one kept column-major, three row-major."""
    kplan = compile_program(ALL_PROGRAMS["hydro2d"](),
                            backend="pallas").kernel_plan
    arrays = {ax.array: jnp.zeros((12, 12)) for ax in kplan.axioms}
    arrays[kplan.axioms[0].array] = jnp.zeros((12, 13))

    def query(shape, dtype):
        return (1, 0) if shape == (12, 13) else (0, 1)
    monkeypatch.setattr(dimorder, "device_major_to_minor", query)
    assert dimorder.device_loop_order(kplan, arrays) is None


@pytest.mark.parametrize("name", sorted(
    n for n, b in ALL_PROGRAMS.items() if len(b().loop_order) >= 3))
def test_permute_program_round_trips(name):
    """Permute, then permute back under the old order: the program again."""
    prog = ALL_PROGRAMS[name]()
    order = tuple(prog.loop_order)
    new = (order[1], order[0]) + order[2:]
    perm = dimorder.permute_program(prog, new)
    assert perm.loop_order == new and perm.name == prog.name
    assert perm != prog
    assert dimorder.permute_program(perm, order) == prog


def test_permute_program_reorders_every_term():
    perm = dimorder.permute_program(cosmo_program(), ("j", "k", "i"))
    assert str(perm.axioms[0].term) == "u[j?][k?][i?]"
    assert str(perm.goals[0].term) == "unew(u[j][k][i])"
    ulap = perm.rules[0]
    assert [str(p.pattern) for p in ulap.inputs][0] == "u?[j?-1][k?][i?]"
    assert perm.goals[0].extents == cosmo_program().goals[0].extents


def test_permute_program_refuses_what_it_cannot_permute():
    prog = cosmo_program()
    with pytest.raises(ValueError, match="no permutation"):
        dimorder.permute_program(prog, ("j", "i"))
    swapped = dimorder.permute_program(prog, ("j", "k", "i"))
    swapped.loop_order = ("k", "j", "i")
    with pytest.raises(ValueError, match="cannot be permuted"):
        dimorder.permute_program(swapped, ("j", "k", "i"))


def test_off_the_tpu_every_layout_is_row_major():
    assert dimorder.device_major_to_minor((80, 774, 1158),
                                          jnp.float32) == (0, 1, 2)
