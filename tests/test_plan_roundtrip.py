"""Plan serialization round-trip properties: random small stencil-chain
programs -> plan -> dict -> JSON -> plan survives with equal
cache_key(), equal render(), and *identical* executor output vs the
original plan, in both streaming modes (interpret=True).

The deterministic seeded legs always run; when hypothesis is installed
(requirements-dev.txt) a property version widens the seed space.
"""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from _progen import build_chain_program, random_chain, unregister_chain
from repro.core import KernelPlan, PlanSerializationError, plan_pallas
from repro.core.dataflow import build_dataflow
from repro.core.fusion import fuse_inest_dag
from repro.core.infer import infer
from repro.core.reuse import analyze_storage

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # the seeded legs below still run
    HAVE_HYPOTHESIS = False


def _plan(program) -> KernelPlan:
    idag = infer(program)
    return plan_pallas(analyze_storage(fuse_inest_dag(build_dataflow(idag))),
                       idag)


def _roundtrip(kplan: KernelPlan) -> KernelPlan:
    """plan -> to_dict -> JSON text -> from_dict."""
    return KernelPlan.from_dict(json.loads(json.dumps(kplan.to_dict())))


def check_serialization_roundtrip(seed: int) -> None:
    """Structural property: the round-tripped plan is equal, renders
    identically, shares the compile-cache key, and re-validates."""
    desc = random_chain(seed)
    name = f"rt_{seed}"
    prog = build_chain_program(desc, name=name, register=True)
    try:
        kplan = _plan(prog)
        kplan2 = _roundtrip(kplan)
        assert kplan2 == kplan, desc
        assert kplan2.render() == kplan.render(), desc
        assert kplan2.cache_key() == kplan.cache_key(), desc
        kplan2.validate()
    finally:
        unregister_chain(name)


@pytest.mark.parametrize("seed", range(12))
def test_roundtrip_structural(seed):
    """Seeded structural round-trips (run regardless of hypothesis)."""
    check_serialization_roundtrip(seed)


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(st.integers(0, 2 ** 31 - 1))
    def test_roundtrip_structural_property(seed):
        """Hypothesis widening of the structural round-trip property."""
        check_serialization_roundtrip(seed)


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("cap", [None, 1], ids=["rows", "one_row"])
def test_roundtrip_executor_output_identical(seed, cap, monkeypatch):
    """The deserialized plan *executes* bit-identically to the original
    (same re-linked callables, same IR, same interpreter), with R rows
    a grid step and with one (R capped at 1)."""
    from repro.core import tiling
    from repro.core.interpreters import execute_plan

    if cap is not None:
        monkeypatch.setattr(tiling, "ROW_TILE_CAP", cap)
    desc = random_chain(seed)
    name = f"rtx_{seed}"
    prog = build_chain_program(desc, name=name, register=True)
    try:
        kplan = _plan(prog)
        kplan2 = _roundtrip(kplan)
        rng = np.random.default_rng(seed)
        u = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
        got1 = execute_plan(kplan, interpret=True)(u=u)["out"]
        got2 = execute_plan(kplan2, interpret=True)(u=u)["out"]
        np.testing.assert_array_equal(np.asarray(got1), np.asarray(got2))
    finally:
        unregister_chain(name)


def test_unregistered_closure_not_serializable():
    """A chain whose weight-closures were never registered must refuse
    to serialize with a clear error (not silently drop callables)."""
    desc = random_chain(0)
    prog = build_chain_program(desc, name="rt_unreg", register=False)
    kplan = _plan(prog)
    with pytest.raises(PlanSerializationError, match="no stable identity"):
        kplan.to_dict()


def test_registered_key_survives_process_restart_shape():
    """Deserialization resolves registered keys through the *current*
    table: dropping the key breaks from_dict with a clear error, and
    re-registering (as a fresh process would at import time) repairs
    it."""
    desc = random_chain(3)
    prog = build_chain_program(desc, name="rt_relink", register=True)
    try:
        blob = json.dumps(_plan(prog).to_dict())
    finally:
        unregister_chain("rt_relink")
    with pytest.raises(PlanSerializationError, match="not registered"):
        KernelPlan.from_dict(json.loads(blob))
    prog2 = build_chain_program(desc, name="rt_relink", register=True)
    try:
        kplan = KernelPlan.from_dict(json.loads(blob))
        assert kplan == _plan(prog2)
    finally:
        unregister_chain("rt_relink")


def test_schema_version_mismatch_rejected():
    """A payload from another schema version must not half-load."""
    desc = random_chain(1)
    prog = build_chain_program(desc, name="rt_schema", register=True)
    try:
        d = _plan(prog).to_dict()
    finally:
        unregister_chain("rt_schema")
    d["schema"] = 9999
    with pytest.raises(PlanSerializationError, match="schema version"):
        KernelPlan.from_dict(d)


def test_with_init_spec_roundtrip():
    """Row-kept reductions wrap their combine in acc_init_wrap; the
    wrapper serializes as a with_init spec and rebuilds behaviorally
    identically."""
    from repro.core.programs import row_sum_program

    kplan = _plan(row_sum_program())
    blob = json.dumps(kplan.to_dict())
    specs = [s for c in json.loads(blob)["calls"] for s in c["fns"]]
    assert any(s["kind"] == "with_init" for s in specs)
    kplan2 = KernelPlan.from_dict(json.loads(blob))
    assert kplan2.cache_key() == kplan.cache_key()
