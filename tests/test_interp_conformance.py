"""Cross-interpreter conformance harness.

Every interpreter in the registry (:mod:`repro.core.interpreters`) must
execute every program in the corpus, at rows below one sublane tile and
at a ragged R-row tile, and agree with the unfused reference evaluator —
the paper's correctness bar for "one kernel description, many
executable forms".  New interpreters are
covered by registering; no test edits required.

Also pins the registry contract itself: unknown backends fail with a
listing of what *is* registered, capability-rejected plans raise the
typed :class:`~repro.core.interpreters.PlanUnsupported`, and a
serialized golden plan re-links into every registered interpreter.
"""
import json
import pathlib

import numpy as np
import pytest

from _interp_utils import DIM, arrays_for
from repro.core import KernelPlan, compile_program
from repro.core.interpreters import (InterpreterSpec, PlanUnsupported,
                                     execute_plan, get_interpreter,
                                     register_interpreter,
                                     registered_interpreters,
                                     unregister_interpreter)
from repro.core.programs import ALL_PROGRAMS
from repro.core.unfused import build_unfused

GOLDEN_DIR = pathlib.Path(__file__).resolve().parent / "goldens" / "plans"

INTERPRETERS = registered_interpreters()

# The oracle is interpreter-independent; build each program's evaluator
# once for the whole module.
_ORACLE: dict = {}


def _oracle(name):
    if name not in _ORACLE:
        _ORACLE[name] = build_unfused(ALL_PROGRAMS[name]()).fn
    return _ORACLE[name]


def _assert_conforms(got: dict, ref: dict, tag: str) -> None:
    assert set(ref) <= set(got), tag
    for k in ref:
        np.testing.assert_allclose(
            np.asarray(got[k]), np.asarray(ref[k]),
            atol=2e-4, rtol=1e-3, err_msg=f"{tag}:{k}")


# ---------------------------------------------------------------------------
# The sweep: interpreter x program x row count vs the unfused oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nj", [DIM["j"], 37])
@pytest.mark.parametrize("name", sorted(ALL_PROGRAMS))
@pytest.mark.parametrize("interp", INTERPRETERS)
def test_conformance_vs_unfused(interp, name, nj):
    gen = compile_program(ALL_PROGRAMS[name](), backend=interp)
    assert gen.interpreter == interp
    rng = np.random.default_rng(7)
    arrs = arrays_for(gen.kernel_plan, rng, dict(DIM, j=nj))
    _assert_conforms(gen.fn(**arrs), _oracle(name)(**arrs),
                     f"{interp}/{name}/nj={nj}")


# ---------------------------------------------------------------------------
# Registry contract
# ---------------------------------------------------------------------------

def test_registry_lists_builtin_interpreters():
    assert {"pallas", "interp_jax"} <= set(INTERPRETERS)
    pallas = get_interpreter("pallas")
    assert pallas.flags == frozenset({"interpret"})
    # a pure-JAX interpreter has no streaming modes to honor
    assert get_interpreter("interp_jax").flags == frozenset()


def test_unknown_backend_lists_registered_interpreters():
    with pytest.raises(ValueError, match="registered interpreter"):
        compile_program(ALL_PROGRAMS["laplace5"](), backend="cuda")
    with pytest.raises(ValueError, match="interp_jax"):
        get_interpreter("nope")


def test_capability_rejected_plan_raises_typed_error():
    """A plan whose feature set exceeds the interpreter's declared
    capabilities is refused with the typed PlanUnsupported — at
    compile_program dispatch, not deep inside a build."""
    register_interpreter(InterpreterSpec(
        name="_test_tiny", build_call=lambda *a, **k: None,
        capabilities=frozenset({"lane_reduce"}), flags=frozenset(),
        description="capability-starved test double"))
    try:
        with pytest.raises(PlanUnsupported, match="outside interpreter"):
            compile_program(ALL_PROGRAMS["heat3d"](), backend="_test_tiny",
                            use_cache=False)
    finally:
        unregister_interpreter("_test_tiny")


def test_register_rejects_unknown_capability_tags():
    with pytest.raises(ValueError, match="unknown capability"):
        register_interpreter(InterpreterSpec(
            name="_test_bad", build_call=lambda *a, **k: None,
            capabilities=frozenset({"warp_pipelining"})))
    assert "_test_bad" not in registered_interpreters()


# ---------------------------------------------------------------------------
# One serialized plan, every interpreter (the AOT-cache re-link path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("interp", INTERPRETERS)
def test_golden_plan_executes_on_every_interpreter(interp):
    """A checked-in serialized KernelPlan (the on-disk cache format)
    deserializes and runs on each registered interpreter — the plan IR
    is the portable artifact, the interpreter a late binding."""
    kplan = KernelPlan.from_dict(
        json.loads((GOLDEN_DIR / "heat3d.json").read_text()))
    rng = np.random.default_rng(3)
    arrs = arrays_for(kplan, rng)
    got = execute_plan(kplan, interpreter=interp)(**arrs)
    _assert_conforms(got, _oracle("heat3d")(**arrs), f"golden/{interp}")
