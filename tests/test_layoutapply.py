"""LayoutApply (repro.core.layoutapply): the plan->plan pass executing
VecScan's layout hints, and its engine/interpreter wiring.

Covers, in order: the corpus-wide conformance sweep (auto-mode
transformed plans execute bit-identically to the untransformed plan on
every layout-aware interpreter, at two row counts, and are
*refused* by capability-gated interpreters), force mode with
tolerances, one unit test per handled hint kind on hand-built plans
(including the strided-reads-become-executable DLT path), the
engine-level cache-key hygiene (two apply modes never share a compile
cache entry; the disk plan cache stores only untransformed plans), and
the explain() applied-vs-advisory rendering.
"""
import json
import pathlib

import numpy as np
import pytest

from _interp_utils import DIM, arrays_for, sizes_for
from repro.core import (KernelPlan, apply_layout, clear_compile_cache,
                        compile_cache_size, compile_program, explain)
from repro.core.interpreters import (PlanUnsupported, _lane_permute,
                                     execute_plan, get_interpreter,
                                     registered_interpreters)
from repro.core.layoutapply import (APPLY_LAYOUT_ENV, EXACT_HINTS,
                                    HANDLED_HINTS, resolve_apply_mode)
from repro.core.plan import (AxiomPlan, CallPlan, GridDim, InputPlan,
                             LanePass, LayoutHint, OutputPlan, ReadPlan,
                             StepPlan)
from repro.core.plancheck import check_plan, has_errors
from repro.core.programs import ALL_PROGRAMS
from repro.core.tiling import LANE

GOLDEN_DIR = pathlib.Path(__file__).resolve().parent / "goldens" / "plans"
GOLDENS = sorted(p.stem for p in GOLDEN_DIR.glob("*.json"))
INTERPRETERS = registered_interpreters()


@pytest.fixture(autouse=True)
def _fresh_cache():
    clear_compile_cache()
    yield
    clear_compile_cache()


def _golden(name: str) -> KernelPlan:
    return KernelPlan.from_dict(
        json.loads((GOLDEN_DIR / f"{name}.json").read_text()))


def _run(kplan, interp, rng_seed=7, dims=DIM):
    arrs = arrays_for(kplan, np.random.default_rng(rng_seed), dims)
    return {k: np.asarray(v) for k, v in
            execute_plan(kplan, interpreter=interp)(**arrs).items()}


# ---------------------------------------------------------------------------
# The conformance sweep: transformed == untransformed, whole corpus,
# every registered interpreter, rows below one tile and a ragged R-row tile
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nj", [DIM["j"], 37])
@pytest.mark.parametrize("name", GOLDENS)
@pytest.mark.parametrize("interp", INTERPRETERS)
def test_auto_transform_conformance(interp, name, nj):
    """Auto mode is bit-exact: on every golden whose hints apply, a
    layout-aware interpreter must produce bit-identical outputs for
    the transformed and untransformed plan; an interpreter without the
    new capabilities must refuse the transformed plan with the typed
    PlanUnsupported rather than miscompile."""
    kplan = _golden(name)
    dims = dict(DIM, j=nj)
    res = apply_layout(kplan, mode="auto", sizes=sizes_for(kplan, dims))
    if not res.applied:
        pytest.skip("no exact hint applies to this plan")
    assert res.plan.cache_key() != kplan.cache_key()
    assert not has_errors(check_plan(res.plan))
    if not res.plan.features() <= get_interpreter(interp).capabilities:
        with pytest.raises(PlanUnsupported):
            execute_plan(res.plan, interpreter=interp)
        return
    want = _run(kplan, interp, dims=dims)
    got = _run(res.plan, interp, dims=dims)
    assert set(got) == set(want)
    for k in want:
        assert np.array_equal(got[k], want[k]), (name, k)


@pytest.mark.parametrize("name", GOLDENS)
def test_force_transform_allclose(name):
    """Force mode adds the reassociating rewrites (acc_lane_block) —
    the bar drops from bit-identical to allclose, but the transformed
    plan must still validate, lint clean, and execute on the
    layout-aware interpreter for the *whole* corpus."""
    kplan = _golden(name)
    res = apply_layout(kplan, mode="force", sizes=sizes_for(kplan))
    assert not has_errors(check_plan(res.plan))
    want = _run(kplan, "interp_jax")
    got = _run(res.plan, "interp_jax")
    for k in want:
        np.testing.assert_allclose(got[k], want[k], atol=2e-4, rtol=1e-3,
                                   err_msg=f"{name}:{k}")


def test_corpus_exercises_every_exact_hint_kind():
    """The golden corpus is a meaningful testbed: across all 15 plans,
    force mode actually applies every exact hint kind plus the
    acc_lane_block pre-fold (layout_transform has no strided golden —
    the hand-built test below covers it)."""
    applied = set()
    for name in GOLDENS:
        kplan = _golden(name)
        res = apply_layout(kplan, mode="force", sizes=sizes_for(kplan))
        applied |= {k for k, _, _ in res.applied}
    assert "shift_reuse" in applied
    assert "acc_lane_block" in applied


# ---------------------------------------------------------------------------
# Per-hint unit tests
# ---------------------------------------------------------------------------

def test_shift_reuse_builds_carried_vector():
    """laplace5: the 5 reads of in_cell (j_off -1..1, col0 0..2)
    collapse into one carried-vector slot — carry spans the j chain,
    the widened load covers the col union, and the rewritten reads
    keep every coordinate except src."""
    kplan = _golden("laplace5")
    res = apply_layout(kplan, mode="auto")
    assert res.applied == (("shift_reuse", "laplace5_n0", "in_cell"),)
    (call,) = res.plan.calls
    (v,) = call.vloads
    assert (v.name, v.src) == ("cell", "in_cell")
    assert v.j_off == 1 and v.carry == 2  # rows j+1 .. j-1 carried
    assert v.col0 == 0 and v.w_off == 0   # col union [0, ni)
    old = [rd for s in kplan.calls[0].steps for rd in s.reads]
    new = [rd for s in call.steps for rd in s.reads]
    assert all(rd.src == "vec:cell" for rd in new)
    for o, n in zip(old, new):
        assert (o.j_off, o.col0, o.w_off, o.p_off) == \
            (n.j_off, n.col0, n.w_off, n.p_off)


def test_shift_reuse_absorbs_rider_groups():
    """heat3d: once the p=0 chain reuses rows, the single-load groups
    at p=+-1 ride along as carry-0 registers — every in_u access then
    flows through the register file (none left for the plane window),
    and the transformed plan still validates and checks clean."""
    kplan = _golden("heat3d")
    res = apply_layout(kplan, mode="auto")
    assert ("shift_reuse", "heat3d_n0", "in_u") in res.applied
    (call,) = res.plan.calls
    by_name = {v.name: v for v in call.vloads}
    assert by_name["u_p0"].carry == 2       # the reuse chain proper
    assert by_name["u_p-1"].carry == 0      # riders: one load, no
    assert by_name["u_p1"].carry == 0       # history to carry
    assert by_name["u_p-1"].p_off == -1 and by_name["u_p1"].p_off == 1
    assert not any(rd.src == "in_u"
                   for s in call.steps for rd in s.reads)
    assert not has_errors(check_plan(res.plan))


def _hand_plan(call, *, i_hi=2, layout_hints=()):
    """A minimal executable one-call plan over u[Nj, Ni + i_hi]."""
    return KernelPlan(
        program="hand",
        loop_order=("j", "i"),
        dim_sizes=(("i", "Ni"), ("j", "Nj")),
        axioms=(AxiomPlan("u", ("j", "i"),
                          (("j", "Nj", 0, 0), ("i", "Ni", 0, i_hi))),),
        goal_outputs=(("v", "v"),),
        calls=(call,),
        layout_hints=tuple(layout_hints),
    ).validate()


def test_realign_origin_pads_window():
    """A window whose loads all sit off-lane gains align_pad seating
    the lowest origin on a lane boundary — and executes bit-identically
    (every access shifts by the same physical pad)."""
    call = CallPlan(
        name="hand_n0",
        grid=(GridDim("j", 0, 0),),
        vec_dim="i",
        inputs=(InputPlan("u", i_hi=2),),
        steps=(StepPlan("add2", 0,
                        (ReadPlan("in_u", 0, 1, 0), ReadPlan("in_u", 0, 2, 0)),
                        ((("out", 0),),), 0),),
        outputs=(OutputPlan("v", kind="external"),),
        fns=(lambda a, b: a + b,),
    )
    kplan = _hand_plan(call, layout_hints=[
        LayoutHint("realign_origin", "hand_n0", "in_u")])
    res = apply_layout(kplan, mode="force")
    assert res.applied == (("realign_origin", "hand_n0", "in_u"),)
    (ispec,) = res.plan.calls[0].inputs
    assert ispec.align_pad == LANE - 1  # lowest origin was col 1
    want = _run(kplan, "interp_jax")
    got = _run(res.plan, "interp_jax")
    assert np.array_equal(got["v"], want["v"])
    # and the numbers are what the stencil says
    u = arrays_for(kplan, np.random.default_rng(7))["u"]
    ref = np.asarray(u)[:, 1:-1] + np.asarray(u)[:, 2:]
    np.testing.assert_allclose(got["v"], ref, atol=2e-4, rtol=1e-3)


def test_realign_origin_skips_aligned_anchor():
    """With an aligned (col 0) load in the group, re-origining buys
    nothing and the pass must decline."""
    kplan = _golden("laplace5")  # reads at col0 0..2: col 0 is aligned
    res = apply_layout(kplan, mode="force")
    assert any(k == "realign_origin" and "aligned anchor" in why
               for k, _, _, why in res.skipped)


def test_layout_transform_makes_strided_plan_executable():
    """The size-specialized DLT: a uniformly 2-strided plan is outside
    interp_jax's capabilities, but after the de-interleave pre-pass the
    reads are unit-stride and the plan runs — matching the hand-written
    numpy semantics of the original strided access."""
    call = CallPlan(
        name="sv_n0",
        grid=(GridDim("j", 0, 0),),
        vec_dim="i",
        inputs=(InputPlan("u"),),
        steps=(StepPlan("pairsum", 0,
                        (ReadPlan("in_u", 0, 0, -2, 0, 2),
                         ReadPlan("in_u", 0, 1, -2, 0, 2)),
                        ((("out", 0),),), 0, out_w_off=-11),),
        outputs=(OutputPlan("v", kind="external"),),
        fns=(lambda a, b: a + b,),
    )
    kplan = _hand_plan(call, i_hi=0, layout_hints=[
        LayoutHint("layout_transform", "sv_n0", "in_u")])
    assert "strided_reads" in kplan.features()
    with pytest.raises(PlanUnsupported):
        execute_plan(kplan, interpreter="interp_jax")

    sizes = sizes_for(kplan)  # Ni=20: window 20, half-lanes of 10
    res = apply_layout(kplan, mode="force", sizes=sizes)
    assert res.applied == (("layout_transform", "sv_n0", "in_u"),)
    assert res.plan.pre_passes == (LanePass("u", 2, 20),)
    reads = res.plan.calls[0].steps[0].reads
    assert [(rd.col0, rd.w_off, rd.i_stride) for rd in reads] == \
        [(0, -11, 1), (10, -11, 1)]
    assert "strided_reads" not in res.plan.features()

    got = _run(res.plan, "interp_jax")
    u = np.asarray(arrays_for(kplan, np.random.default_rng(7))["u"])
    ref = np.zeros_like(u)  # 9 written cols, the rest output fill
    ref[:, :9] = u[:, 0:18:2] + u[:, 1:18:2]
    np.testing.assert_allclose(got["v"], ref, atol=2e-4, rtol=1e-3)


def test_layout_transform_output_inverse_post_pass():
    """A hint on an external output appends the *inverse* re-interleave
    as a post-pass on the assembled goal, and mode auto refuses it
    (not bit-exact)."""
    call = CallPlan(
        name="hand_n0",
        grid=(GridDim("j", 0, 0),),
        vec_dim="i",
        inputs=(InputPlan("u", i_hi=2),),
        steps=(StepPlan("add2", 0,
                        (ReadPlan("in_u", 0, 1, 0), ReadPlan("in_u", 0, 2, 0)),
                        ((("out", 0),),), 0),),
        outputs=(OutputPlan("v", kind="external"),),
        fns=(lambda a, b: a + b,),
    )
    hint = LayoutHint("layout_transform", "hand_n0", "v",
                      params=(("stride", 2),))
    kplan = _hand_plan(call, layout_hints=[hint])
    auto = apply_layout(kplan, mode="auto", sizes=sizes_for(kplan))
    assert any(k == "layout_transform" and "force mode only" in why
               for k, _, _, why in auto.skipped)
    res = apply_layout(kplan, mode="force", sizes=sizes_for(kplan))
    assert res.applied == (("layout_transform", "hand_n0", "v"),)
    assert res.plan.post_passes == (LanePass("v", 2, 20),)
    want = _run(kplan, "interp_jax")["v"]
    got = _run(res.plan, "interp_jax")["v"]
    import jax.numpy as jnp
    seated = np.asarray(_lane_permute(jnp.asarray(want),
                                      LanePass("v", 2, 20), inverse=True))
    assert np.array_equal(got, seated)


def test_lane_permute_round_trips():
    """The runtime permutation and its inverse compose to identity for
    every divisor stride."""
    import jax.numpy as jnp
    x = jnp.asarray(np.arange(48, dtype=np.float32).reshape(2, 24))
    for s in (2, 3, 4, 6):
        p = LanePass("x", s, 24)
        y = _lane_permute(x, p)
        assert not np.array_equal(np.asarray(y), np.asarray(x))
        assert np.array_equal(
            np.asarray(_lane_permute(y, p, inverse=True)), np.asarray(x))


def test_acc_lane_block_prefolds_row_reduction():
    """row_sum: force mode gives the acc_rows output a lane-wide device
    pre-fold; the reassociated reduction agrees within tolerance."""
    kplan = _golden("row_sum")
    res = apply_layout(kplan, mode="force", sizes=sizes_for(kplan))
    assert ("acc_lane_block", res.applied[0][1], "rsum_u") in res.applied
    out = next(o for c in res.plan.calls for o in c.outputs
               if o.name == "rsum_u")
    assert out.lane_block == LANE
    want = _run(kplan, "interp_jax")
    got = _run(res.plan, "interp_jax")
    for k in want:
        np.testing.assert_allclose(got[k], want[k], atol=2e-4, rtol=1e-3)


def test_off_mode_and_env_resolution(monkeypatch):
    """Mode "off" is a true no-op (same object back); the env default
    resolves through REPRO_APPLY_LAYOUT; junk modes raise."""
    kplan = _golden("laplace5")
    assert apply_layout(kplan, mode="off").plan is kplan
    monkeypatch.delenv(APPLY_LAYOUT_ENV, raising=False)
    assert resolve_apply_mode(None) == "off"
    monkeypatch.setenv(APPLY_LAYOUT_ENV, "auto")
    assert resolve_apply_mode(None) == "auto"
    assert resolve_apply_mode("force") == "force"
    with pytest.raises(ValueError, match="apply_layout"):
        resolve_apply_mode("always")
    assert set(EXACT_HINTS) < set(HANDLED_HINTS)


# ---------------------------------------------------------------------------
# Engine wiring: the apply_layout knob, cache-key hygiene, explain
# ---------------------------------------------------------------------------

def test_compile_program_modes_split_the_cache():
    """Same program, two apply modes: two compile-cache entries, both
    correct, and the transformed plan only under the mode that asked
    for it."""
    prog = ALL_PROGRAMS["laplace5"]()
    g_off = compile_program(prog, backend="interp_jax", apply_layout="off")
    g_auto = compile_program(prog, backend="interp_jax", apply_layout="auto")
    assert compile_cache_size() == 2
    assert not g_off.kernel_plan.applied_layout
    assert g_auto.kernel_plan.applied_layout
    arrs = arrays_for(g_off.kernel_plan, np.random.default_rng(3))
    r0, r1 = g_off.fn(**arrs), g_auto.fn(**arrs)
    for k in r0:
        assert np.array_equal(np.asarray(r0[k]), np.asarray(r1[k]))


def test_disk_cache_stores_untransformed_plan(tmp_path):
    """The on-disk plan cache must hold the *untransformed* plan so a
    warm load under a different mode (or a future pass version) is
    never poisoned by a previously-applied layout."""
    prog = ALL_PROGRAMS["laplace5"]()
    g = compile_program(prog, backend="interp_jax", apply_layout="auto",
                        plan_cache_dir=str(tmp_path))
    assert g.kernel_plan.applied_layout
    (entry,) = tmp_path.glob("*.json")
    stored = json.loads(entry.read_text())["plan"]
    assert stored["applied_layout"] == []

    clear_compile_cache()
    g_off = compile_program(prog, backend="interp_jax", apply_layout="off",
                            plan_cache_dir=str(tmp_path))
    assert not g_off.kernel_plan.applied_layout
    clear_compile_cache()
    g_auto = compile_program(prog, backend="interp_jax", apply_layout="auto",
                             plan_cache_dir=str(tmp_path))
    assert g_auto.kernel_plan.applied_layout
    arrs = arrays_for(g_off.kernel_plan, np.random.default_rng(5))
    r0, r1 = g_off.fn(**arrs), g_auto.fn(**arrs)
    for k in r0:
        assert np.array_equal(np.asarray(r0[k]), np.asarray(r1[k]))


def test_non_layout_aware_backend_normalizes_mode():
    """For a backend that isn't layout-aware the mode is normalized to
    "off" in the compile key — asking for auto neither transforms the
    plan nor splits the cache."""
    prog = ALL_PROGRAMS["laplace5"]()
    g0 = compile_program(prog, backend="pallas", interpret=True,
                         apply_layout="off")
    g1 = compile_program(prog, backend="pallas", interpret=True,
                         apply_layout="auto")
    assert compile_cache_size() == 1
    assert g1 is g0
    assert not g1.kernel_plan.applied_layout


def test_explain_renders_applied_vs_advisory():
    txt = explain(ALL_PROGRAMS["laplace5"](), verbose=True,
                  apply_layout="auto", dim_sizes={"Ni": 256, "Nj": 96})
    assert "--- layout apply ---" in txt
    assert "apply mode: auto" in txt
    assert "applied  shift_reuse" in txt
    assert "redundant-load ratio" in txt
    off = explain(ALL_PROGRAMS["laplace5"](), verbose=True,
                  apply_layout="off")
    assert "every hint stays advisory" in off
