"""The KernelPlan IR seam: planner purity and determinism, golden-plan
snapshots, plan-level compile-cache behavior, IR validation, and the
interpreter running hand-built plans with no engine in sight."""
import json
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

from _interp_utils import arrays_for
from repro.core import (KernelPlan, clear_compile_cache, compile_program,
                        plan_pallas)
from repro.core.dataflow import build_dataflow
from repro.core.engine import plan_cache_size
from repro.core.fusion import fuse_inest_dag
from repro.core.infer import infer
from repro.core.interpreters import execute_plan as registry_execute_plan
from repro.core.interpreters import registered_interpreters
from repro.core.plan import (CallPlan, GridDim, InputPlan, OutputPlan,
                             PallasUnsupported, ReadPlan, StepPlan)
from repro.core.programs import (ALL_PROGRAMS, heat3d_program,
                                 heat3d_stage_program, laplace5_program,
                                 normalization_program)
from repro.core.reuse import analyze_storage
from repro.core.rules import Program, axiom, goal, kernel
from repro.core.unfused import build_unfused

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _fresh_cache():
    clear_compile_cache()
    yield
    clear_compile_cache()


def _plan(program) -> KernelPlan:
    idag = infer(program)
    plan = analyze_storage(fuse_inest_dag(build_dataflow(idag)))
    return plan_pallas(plan, idag)


# ---------------------------------------------------------------------------
# Golden-plan snapshots: the planner's output is a stable contract
# ---------------------------------------------------------------------------

GOLDEN_LAPLACE = """\
kernel plan: laplace5
  loop order: (j, i)
  call laplace5_n0: grid j=[-1, Nj-1)
    input cell: rows[0,+0] cols[0,+0] lead=1 stages=3
    step laplace5 @lead 0: reads [in_cell[j-1], in_cell[j+0], in_cell[j+1], \
in_cell[j+0], in_cell[j+0]] -> out:0
    out laplace_cell: external lead=0 rows[1,-1]
  goals: lap<-laplace_cell"""

GOLDEN_HEAT3D = """\
kernel plan: heat3d
  loop order: (k, j, i)
  call heat3d_n0: grid k=[-1, Nk-1) x j=[-1, Nj-1)
    input u: rows[0,+0] cols[0,+0] lead=1 stages=3 plane_window=3 p_lead=1
    step heat7 @lead 0: reads [in_u[p-1 j+0], in_u[p+1 j+0], in_u[j-1], \
in_u[j+1], in_u[j+0], in_u[j+0], in_u[j+0]] -> out:0
    out heat_u: external lead=0 rows[1,-1]
  goals: heat<-heat_u"""


def test_golden_plan_laplace5():
    assert _plan(laplace5_program()).render() == GOLDEN_LAPLACE


def test_golden_plan_heat3d():
    assert _plan(heat3d_program()).render() == GOLDEN_HEAT3D


GOLDEN_DIR = ROOT / "tests" / "goldens" / "plans"


def test_golden_corpus_covers_every_program():
    """One golden file per ALL_PROGRAMS entry, and no strays."""
    assert {p.stem for p in GOLDEN_DIR.glob("*.json")} == set(ALL_PROGRAMS)


@pytest.mark.parametrize("name", sorted(ALL_PROGRAMS))
def test_golden_plan_corpus(name):
    """Re-plan every program and diff its full serialized form against
    the checked-in golden: any planner drift becomes a reviewable
    golden-file change (regenerate deliberately via
    ``scripts/warm_cache.py --goldens``), and the golden itself must
    deserialize into a validating, cache-key-identical plan — the
    corpus doubles as a round-trip fixture."""
    kplan = _plan(ALL_PROGRAMS[name]())
    path = GOLDEN_DIR / f"{name}.json"
    got = json.loads(json.dumps(kplan.to_dict()))
    want = json.loads(path.read_text())
    assert got == want, (
        f"planner drift for {name!r}: if intended, regenerate the "
        f"corpus with scripts/warm_cache.py --goldens")
    restored = KernelPlan.from_dict(want).validate()
    assert restored == kplan
    assert restored.cache_key() == kplan.cache_key()


@pytest.mark.parametrize("interp", registered_interpreters())
@pytest.mark.parametrize("name", sorted(ALL_PROGRAMS))
def test_golden_corpus_executes_on_every_interpreter(name, interp):
    """The checked-in serialized corpus is executable on every
    registered plan interpreter and agrees with the unfused reference —
    the goldens pin not just the planner's output but the portability
    of the IR across executors."""
    kplan = KernelPlan.from_dict(
        json.loads((GOLDEN_DIR / f"{name}.json").read_text()))
    rng = np.random.default_rng(11)
    arrs = arrays_for(kplan, rng)
    got = registry_execute_plan(kplan, interpreter=interp)(**arrs)
    ref = build_unfused(ALL_PROGRAMS[name]()).fn(**arrs)
    for k in ref:
        np.testing.assert_allclose(
            np.asarray(got[k]), np.asarray(ref[k]), atol=2e-4, rtol=1e-3,
            err_msg=f"{interp}/{name}:{k}")


def test_plan_is_serializable():
    """to_json round-trips through the json module and never leaks
    callables (the IR is declarative; fns travel in a side table)."""
    for build in (laplace5_program, heat3d_stage_program,
                  normalization_program):
        blob = _plan(build()).to_json()
        data = json.loads(blob)
        assert data["program"] == build().name
        assert "fns" not in blob


# ---------------------------------------------------------------------------
# Determinism and structural identity
# ---------------------------------------------------------------------------

def test_plan_determinism_and_structural_equality():
    """Same program (rebuilt from scratch, fresh lambdas) -> structurally
    equal, equal-hash plans: callables sit outside structural identity."""
    for build in (laplace5_program, heat3d_program, heat3d_stage_program,
                  normalization_program):
        p1, p2 = _plan(build()), _plan(build())
        assert p1 == p2, build.__name__
        assert hash(p1) == hash(p2)
        assert p1.render() == p2.render()


def _scaled_program(c, name="scaled_plan"):
    k = kernel("scalep", [("a", "u?[j?][i?]")], [("o", "sp(u?[j?][i?])")],
               fn=lambda a: a * c)
    return Program(
        rules=[k],
        axioms=[axiom("u[j?][i?]", j="Nj", i="Ni")],
        goals=[goal("sp(u[j][i])", store_as="sp",
                    j=("Nj", 0, 0), i=("Ni", 0, 0))],
        loop_order=("j", "i"),
        name=name,
    )


def test_cache_key_distinguishes_closures():
    """Two programs lowering to structurally equal plans whose kernels
    close over different values must NOT share a cache key (behavioral
    identity rides in via fn_key)."""
    p2, p3 = _plan(_scaled_program(2.0)), _plan(_scaled_program(3.0))
    assert p2 == p3  # structural equality ignores the callables...
    assert p2.cache_key() != p3.cache_key()  # ...the cache key does not


def test_plan_inequality_distinct_cache_entries():
    """Structurally different plans occupy distinct plan-cache entries
    (and behaviorally different same-structure plans too)."""
    assert plan_cache_size() == 0
    compile_program(_scaled_program(2.0), backend="pallas")
    assert plan_cache_size() == 1
    # same structure, same closure: plan-level hit
    compile_program(_scaled_program(2.0), backend="pallas")
    assert plan_cache_size() == 1
    # same structure, different closure value: distinct entry
    compile_program(_scaled_program(3.0), backend="pallas")
    assert plan_cache_size() == 2
    # different structure: distinct entry
    compile_program(laplace5_program(), backend="pallas")
    assert plan_cache_size() == 3
    # different execution flags: distinct entry for the same plan
    compile_program(laplace5_program(), backend="pallas",
                    double_buffer=True)
    assert plan_cache_size() == 4


def test_plan_cache_correctness_across_closures():
    """The distinct entries must also *behave* distinctly."""
    u = jnp.ones((4, 6), jnp.float32)
    o2 = compile_program(_scaled_program(2.0), backend="pallas").fn(u=u)["sp"]
    o3 = compile_program(_scaled_program(3.0), backend="pallas").fn(u=u)["sp"]
    assert float(np.asarray(o2)[0, 0]) == 2.0
    assert float(np.asarray(o3)[0, 0]) == 3.0


# ---------------------------------------------------------------------------
# validate(): the IR re-checks the planner's restriction pass
# ---------------------------------------------------------------------------

def _manual_call(**overrides) -> CallPlan:
    base = dict(
        name="manual_n0",
        grid=(GridDim("j", 0, 0),),
        vec_dim="i",
        inputs=(InputPlan("u"),),
        steps=(StepPlan("dbl", 0, (ReadPlan("in_u", 0, 0, 0),),
                        ((("out", 0),),), 0),),
        outputs=(OutputPlan("v", kind="external"),),
        fns=(lambda a: 2.0 * a,),
    )
    base.update(overrides)
    return CallPlan(**base)


def _manual_plan(call: CallPlan) -> KernelPlan:
    return KernelPlan(
        program="manual",
        loop_order=("j", "i"),
        dim_sizes=(("i", "Ni"), ("j", "Nj")),
        axioms=(),
        goal_outputs=(("v", "v"),),
        calls=(call,),
    )


def test_validate_rejects_unresolved_read():
    call = _manual_call(steps=(StepPlan("dbl", 0,
                                        (ReadPlan("in_ghost", 0, 0, 0),),
                                        ((("out", 0),),), 0),))
    with pytest.raises(ValueError, match="unresolved source"):
        _manual_plan(call).validate()


def test_validate_rejects_negative_output_span():
    call = _manual_call(outputs=(OutputPlan("v", kind="external",
                                            i_lo=-1),))
    with pytest.raises(PallasUnsupported, match="outside the Ni-wide"):
        _manual_plan(call).validate()


def test_validate_rejects_plane_read_without_window():
    call = _manual_call(steps=(StepPlan("dbl", 0,
                                        (ReadPlan("in_u", 0, 0, 0, p_off=1),),
                                        ((("out", 0),),), 0),))
    with pytest.raises(PallasUnsupported, match="no plane window"):
        _manual_plan(call).validate()


def test_validate_short_loop_order():
    plan = KernelPlan(program="m", loop_order=("i",), dim_sizes=(("i", "Ni"),),
                      axioms=(), goal_outputs=(), calls=())
    with pytest.raises(PallasUnsupported, match="row, vector"):
        plan.validate()


# ---------------------------------------------------------------------------
# Interpreter isolation: a hand-built plan runs with no engine involved
# ---------------------------------------------------------------------------

def test_interpreter_executes_handbuilt_plan():
    """kernels/stencil2d is a pure interpreter: a CallPlan written by
    hand (no Program, no inference, no fusion) builds and runs."""
    from repro.kernels.stencil2d import build_call

    call = _manual_call()
    _manual_plan(call).validate()
    fn, steps_j = build_call(call, (5, 8), jnp.float32, interpret=True)
    u = jnp.arange(40, dtype=jnp.float32).reshape(5, 8)
    seated = fn(u)
    # an external output comes back goal-shaped, not padded to a tile
    assert steps_j == 5 and seated.shape == (5, 8)
    np.testing.assert_allclose(np.asarray(seated), 2.0 * np.asarray(u))


def test_quickstart_plan_dump_doctest():
    """examples/quickstart.py demonstrates explain(verbose=True); its
    plan_dump doctest pins the rendered output so it cannot rot."""
    import doctest
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "quickstart_example", ROOT / "examples" / "quickstart.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    results = doctest.testmod(mod, verbose=False)
    assert results.attempted >= 1
    assert results.failed == 0


def test_planner_contains_no_raise_sites():
    """The planner delegates every restriction to the plan.py validate
    pass: codegen_pallas.py itself raises no PallasUnsupported (only
    the IR module owns raise sites, per scripts/check_docs.sh)."""
    src = (ROOT / "src/repro/core/codegen_pallas.py").read_text()
    assert "raise PallasUnsupported" not in src
