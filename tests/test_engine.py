"""Engine behaviour pinned to the paper's worked examples:

* Laplace fuses to one nest (Fig. 2 pipeline);
* normalization fuses to exactly TWO nests with the reduction's finalize
  in the first nest's epilogue and the flux intermediate materialized
  across the split (§5.2: "five to two");
* COSMO fuses to one nest with 2-row rolling buffers for the Laplacian
  and y-flux (tighter than the paper's 3+2 thanks to exact leads);
* hydro fuses all seven kernels into one nest with zero materialized
  intermediates (§5.4);
* inference errors: multiple producers, unreachable goals.
"""
import pytest

from repro.core import (InferenceError, Program, analyze_storage, axiom,
                        build_dataflow, fuse_inest_dag, goal, infer, kernel)
from repro.core.programs import (cosmo_program, hydro1d_program,
                                 laplace5_program, normalization_program)
from repro.core.reuse import reuse_graph, reuse_order


def pipeline(prog):
    idag = infer(prog)
    dag = build_dataflow(idag)
    sched = fuse_inest_dag(dag)
    plan = analyze_storage(sched)
    return idag, dag, sched, plan


def test_laplace_single_nest():
    idag, dag, sched, plan = pipeline(laplace5_program())
    assert sched.n_toplevel() == 1
    # 5 loads grouped into one callsite group
    loads = [g for g in dag.groups if g.kind == "load"]
    assert len(loads) == 1 and len(loads[0].instances) == 5


def test_normalization_two_nests_and_split():
    idag, dag, sched, plan = pipeline(normalization_program())
    assert sched.n_toplevel() == 2, "reduction->broadcast must split"
    # finalize (norm_root) fused into the FIRST nest's epilogue
    first = sched.nests[0]
    eplg = first.phase_groups("epilogue")
    by_id = {g.gid: g for g in dag.groups}
    assert any(by_id[g].name == "norm_root" for g in eplg)
    # flux crosses the split -> materialized in full
    kinds = {p.name: p.kind for p in plan.vars.values()}
    assert kinds["flux_u"] == "full"
    assert kinds["fluxsq_u"] == "row"  # consumed in-nest only


def test_cosmo_rolling_buffers():
    _, _, sched, plan = pipeline(cosmo_program())
    assert sched.n_toplevel() == 1
    kinds = {p.name: (p.kind, p.stages) for p in plan.vars.values()}
    assert kinds["ulap_u"] == ("rolling", 2)
    assert kinds["fy_u"] == ("rolling", 2)
    assert kinds["fx_u"][0] == "row"


def test_hydro_full_fusion_zero_intermediates():
    _, dag, sched, plan = pipeline(hydro1d_program())
    assert sched.n_toplevel() == 1
    for p in plan.vars.values():
        assert p.kind in ("external_in", "external_out", "row"), p.name


def test_reuse_order_matches_paper_fig8():
    # 5-point stencil, (j, i) progression: first touch (j+1,i), last (j-1,i)
    offsets = {(0, 0), (1, 0), (-1, 0), (0, 1), (0, -1)}
    order = reuse_order(("j", "i"), offsets, ("j", "i"))
    assert order == [(1, 0), (0, 1), (0, 0), (0, -1), (-1, 0)]
    verts, edges, path = reuse_graph(("j", "i"), offsets, ("j", "i"))
    # transitive tournament: the longest path covers all vertices in order
    assert path == order and len(edges) == 10


def test_single_producer_violation():
    k1 = kernel("k1", [("a", "u[i?]")], [("o", "v(u[i?])")], fn=lambda a: a)
    k2 = kernel("k2", [("a", "u[i?]")], [("o", "v(u[i?])")], fn=lambda a: a)
    prog = Program(
        rules=[k1, k2],
        axioms=[axiom("u[i?]", i="Ni")],
        goals=[goal("v(u[i])", i=("Ni", 0, 0))],
        loop_order=("i",),
    )
    with pytest.raises(InferenceError):
        infer(prog)


def test_unreachable_goal():
    prog = Program(
        rules=[],
        axioms=[axiom("u[i?]", i="Ni")],
        goals=[goal("w(u[i])", i=("Ni", 0, 0))],
        loop_order=("i",),
    )
    with pytest.raises(InferenceError):
        infer(prog)


def test_topo_merge_unorderable_bodies_raises():
    """_topo_merge_bodies must refuse bodies with a mutual (cyclic)
    dependency instead of emitting an arbitrary order."""
    from repro.core.dataflow import DataflowDAG, Group
    from repro.core.fusion import Unfusable, _topo_merge_bodies
    from repro.core.inest import Body

    prog = Program(rules=[], axioms=[], goals=[], loop_order=("i",))
    g1 = Group(gid=1, kind="kernel", rule=None, instances=[])
    g2 = Group(gid=2, kind="kernel", rule=None, instances=[])
    dag = DataflowDAG(prog, [g1, g2], {}, {(1, 2), (2, 1)})
    dag._succ = {1: {2}, 2: {1}}
    dag._pred = {1: {2}, 2: {1}}
    with pytest.raises(Unfusable):
        _topo_merge_bodies(dag, Body([1]), Body([2]))


def _direct_reduction_consumer_program():
    """sq -> reduce -> scale, where scale ALSO reads sq's output: the
    broadcast consumes the accumulator directly (no 0-dim finalize)."""
    k_sq = kernel("sq", [("a", "u?[j?][i?]")], [("o", "sq(u?[j?][i?])")],
                  fn=lambda a: a * a)
    k_tot = kernel("tot", [("x", "sq(u[j][i])")], [("t", "tot(u)")],
                   fn=lambda acc, x: acc + x, kind="reduce", init=0.0)
    k_scale = kernel(
        "scale", [("s", "sq(u?[j?][i?])"), ("t", "tot(u?)")],
        [("o", "scaled(u?[j?][i?])")], fn=lambda s, t: s / (t + 1.0))
    return Program(
        rules=[k_sq, k_tot, k_scale],
        axioms=[axiom("u[j?][i?]", j="Nj", i="Ni")],
        goals=[goal("scaled(u[j][i])", store_as="scaled",
                    j=("Nj", 0, 0), i=("Ni", 0, 0))],
        loop_order=("j", "i"),
    )


def test_barred_vertex_cut_on_direct_reduction_consumer():
    """The accumulator-consumer split (Fig. 6): `scale` cannot share the
    reduced j-loop, and the store — reachable from the failed candidate —
    must be *barred* into the second nest rather than fused upstream."""
    idag, dag, sched, plan = pipeline(_direct_reduction_consumer_program())
    assert sched.n_toplevel() == 2
    by_id = {g.gid: g for g in dag.groups}
    first = {by_id[g].name for g in sched.nests[0].groups()}
    second = {by_id[g].name for g in sched.nests[1].groups()}
    assert {"sq", "tot"} <= first and "scale" not in first
    assert {"scale", "store"} <= second
    # sq's output crosses the split and must be materialized
    kinds = {p.name: p.kind for p in plan.vars.values()}
    assert kinds["sq_u"] == "full"


def test_direct_reduction_consumer_matches_unfused(rng):
    """Regression: before the split fix the fused nest read a *partial*
    accumulator and produced wrong values."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import compile_program
    from repro.core.unfused import build_unfused

    prog = _direct_reduction_consumer_program()
    gen = compile_program(prog, backend="jax", use_cache=False)
    u = jnp.asarray(rng.standard_normal((6, 7)), jnp.float32)
    got = gen.fn(u)["scaled"]
    want = build_unfused(prog).fn(u=u)["scaled"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_compile_cache_hits_rebuilt_lambdas():
    """Structurally identical programs whose kernels are *rebuilt*
    lambdas (fresh function objects, same code) must share compiled
    artifacts: the signature keys kernel callables on their code
    object, not object identity."""
    from repro.core import clear_compile_cache, compile_program
    from repro.core.engine import compile_cache_size

    def build():
        k = kernel("sq2", [("a", "u?[j?][i?]")], [("o", "sq2(u?[j?][i?])")],
                   fn=lambda a: a * a)
        return Program(
            rules=[k],
            axioms=[axiom("u[j?][i?]", j="Nj", i="Ni")],
            goals=[goal("sq2(u[j][i])", store_as="sq2",
                        j=("Nj", 0, 0), i=("Ni", 0, 0))],
            loop_order=("j", "i"),
            name="sq2",
        )

    clear_compile_cache()
    try:
        g1 = compile_program(build(), backend="jax")
        assert compile_program(build(), backend="jax") is g1
        assert compile_cache_size() == 1
    finally:
        clear_compile_cache()


def test_compile_cache_distinguishes_closures():
    """Lambdas sharing a code object but closing over different values
    behave differently and must NOT share a cache entry."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import clear_compile_cache, compile_program

    def build(c):
        k = kernel("scale_c", [("a", "u?[j?][i?]")],
                   [("o", "sc(u?[j?][i?])")], fn=lambda a: a * c)
        return Program(
            rules=[k],
            axioms=[axiom("u[j?][i?]", j="Nj", i="Ni")],
            goals=[goal("sc(u[j][i])", store_as="sc",
                        j=("Nj", 0, 0), i=("Ni", 0, 0))],
            loop_order=("j", "i"),
            name="sc",
        )

    def build_kw(c):
        def scale(a, *, f=c):  # keyword-only default, not in __defaults__
            return a * f

        k = kernel("scale_kw", [("a", "u?[j?][i?]")],
                   [("o", "sk(u?[j?][i?])")], fn=scale)
        return Program(
            rules=[k],
            axioms=[axiom("u[j?][i?]", j="Nj", i="Ni")],
            goals=[goal("sk(u[j][i])", store_as="sk",
                        j=("Nj", 0, 0), i=("Ni", 0, 0))],
            loop_order=("j", "i"),
            name="sk",
        )

    clear_compile_cache()
    try:
        u = jnp.ones((3, 4), jnp.float32)
        o2 = compile_program(build(2.0), backend="jax").fn(u)["sc"]
        o3 = compile_program(build(3.0), backend="jax").fn(u)["sc"]
        assert np.asarray(o2)[0, 0] == 2.0 and np.asarray(o3)[0, 0] == 3.0
        k2 = compile_program(build_kw(2.0), backend="jax").fn(u)["sk"]
        k3 = compile_program(build_kw(3.0), backend="jax").fn(u)["sk"]
        assert np.asarray(k2)[0, 0] == 2.0 and np.asarray(k3)[0, 0] == 3.0
    finally:
        clear_compile_cache()


def test_compile_cache_distinguishes_bound_methods():
    """Bound methods share module/qualname/code/closure across
    instances: the receiver must be part of the signature or the cache
    returns the wrong instance's kernel."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import clear_compile_cache, compile_program

    class Scaler:
        def __init__(self, c):
            self.c = c

        def apply(self, a):
            return a * self.c

    def build(scaler):
        k = kernel("scale_m", [("a", "u?[j?][i?]")],
                   [("o", "sm(u?[j?][i?])")], fn=scaler.apply)
        return Program(
            rules=[k],
            axioms=[axiom("u[j?][i?]", j="Nj", i="Ni")],
            goals=[goal("sm(u[j][i])", store_as="sm",
                        j=("Nj", 0, 0), i=("Ni", 0, 0))],
            loop_order=("j", "i"),
            name="sm",
        )

    clear_compile_cache()
    try:
        u = jnp.ones((3, 4), jnp.float32)
        o2 = compile_program(build(Scaler(2.0)), backend="jax").fn(u)["sm"]
        o3 = compile_program(build(Scaler(3.0)), backend="jax").fn(u)["sm"]
        assert np.asarray(o2)[0, 0] == 2.0 and np.asarray(o3)[0, 0] == 3.0
    finally:
        clear_compile_cache()


def _plan_cache_prog(c, name):
    k = kernel("scale_lru", [("a", "u?[j?][i?]")],
               [("o", "sl(u?[j?][i?])")], fn=lambda a: a * c)
    return Program(
        rules=[k],
        axioms=[axiom("u[j?][i?]", j="Nj", i="Ni")],
        goals=[goal("sl(u[j][i])", store_as="sl",
                    j=("Nj", 0, 0), i=("Ni", 0, 0))],
        loop_order=("j", "i"),
        name=name,
    )


def test_plan_cache_lru_eviction():
    """The in-memory plan-level compile cache is LRU-bounded: entries
    beyond the cap are evicted oldest-first, recently-hit entries
    survive, and lowering the cap evicts immediately."""
    from repro.core import (clear_compile_cache, compile_program,
                            plan_cache_cap, set_plan_cache_cap)
    from repro.core import engine
    from repro.core.engine import plan_cache_size

    progs = [_plan_cache_prog(float(c), f"lru_{c}") for c in (2, 3, 4)]
    clear_compile_cache()
    old = set_plan_cache_cap(2)
    try:
        assert plan_cache_cap() == 2
        g0 = compile_program(progs[0], backend="pallas")
        compile_program(progs[1], backend="pallas")
        assert plan_cache_size() == 2
        # hit prog 0 so prog 1 becomes the LRU victim
        engine._CACHE.clear()  # bypass the signature-level L1
        assert compile_program(progs[0], backend="pallas") is g0
        compile_program(progs[2], backend="pallas")
        assert plan_cache_size() == 2
        # prog 0 survived (recently used): plan-level hit, same object
        engine._CACHE.clear()
        assert compile_program(progs[0], backend="pallas") is g0
        # prog 1 was evicted: recompiling yields a fresh artifact
        g1b = compile_program(progs[1], backend="pallas")
        engine._CACHE.clear()
        assert compile_program(progs[1], backend="pallas") is g1b
        # lowering the cap evicts down to the bound immediately
        set_plan_cache_cap(1)
        assert plan_cache_size() == 1
    finally:
        set_plan_cache_cap(old)
        clear_compile_cache()


def test_plan_cache_isolated_per_interpreter():
    """Two interpreters compiling the SAME program must never collide in
    the plan-level cache: the key carries the interpreter name, so each
    gets its own executor artifact tagged with its own name."""
    from repro.core import clear_compile_cache, compile_program
    from repro.core import engine
    from repro.core.engine import plan_cache_size

    prog = _plan_cache_prog(2.0, "iso_interp")
    clear_compile_cache()
    try:
        gp = compile_program(prog, backend="pallas")
        gj = compile_program(prog, backend="interp_jax")
        assert plan_cache_size() == 2
        assert gp is not gj
        assert gp.interpreter == "pallas"
        assert gj.interpreter == "interp_jax"
        # each backend hits its OWN entry, not the other's
        engine._CACHE.clear()  # bypass the signature-level L1
        assert compile_program(prog, backend="pallas") is gp
        assert compile_program(prog, backend="interp_jax") is gj
        # flags an interpreter does not honor are normalized out of its
        # key: a pure-JAX compile with double_buffer=True is the same
        # cache entry, while pallas (which honors the flag) is not
        engine._CACHE.clear()
        assert compile_program(prog, backend="interp_jax",
                               double_buffer=True) is gj
        assert compile_program(prog, backend="pallas",
                               double_buffer=True) is not gp
    finally:
        clear_compile_cache()


def test_interpret_default_resolves_from_backend(monkeypatch):
    """``interpret=None`` means interpret mode exactly off the TPU, and
    the caches key on the resolved value: on the CPU the default and an
    explicit ``True`` are one entry, ``False`` is another."""
    from repro.core import clear_compile_cache, compile_program
    from repro.core import interpreters
    from repro.core.interpreters import resolve_interpret

    assert resolve_interpret(None) is True  # the test suite runs on CPU
    assert resolve_interpret(False) is False
    monkeypatch.setattr(interpreters.jax, "default_backend", lambda: "tpu")
    assert resolve_interpret(None) is False
    assert resolve_interpret(True) is True
    monkeypatch.undo()

    prog = _plan_cache_prog(3.0, "interp_default")
    clear_compile_cache()
    try:
        g = compile_program(prog, backend="pallas")
        assert compile_program(prog, backend="pallas", interpret=True) is g
        assert compile_program(prog, backend="pallas",
                               interpret=False) is not g
    finally:
        clear_compile_cache()


def test_plan_cache_lru_evicts_across_interpreters():
    """LRU eviction treats per-interpreter entries as ordinary
    citizens: filling the cap with a second interpreter's entries
    evicts the first interpreter's stale ones, and a re-compile then
    yields a fresh artifact."""
    from repro.core import (clear_compile_cache, compile_program,
                            set_plan_cache_cap)
    from repro.core import engine

    prog = _plan_cache_prog(3.0, "lru_interp")
    clear_compile_cache()
    old = set_plan_cache_cap(2)
    try:
        gp = compile_program(prog, backend="pallas")
        gj = compile_program(prog, backend="interp_jax")
        # pallas is now the LRU victim: one more distinct entry (a new
        # pallas flag combination) evicts it
        compile_program(prog, backend="pallas", double_buffer=True)
        engine._CACHE.clear()
        assert compile_program(prog, backend="interp_jax") is gj
        assert compile_program(prog, backend="pallas") is not gp
    finally:
        set_plan_cache_cap(old)
        clear_compile_cache()


def test_plan_cache_cap_validation():
    """A cap below 1 is rejected; the setter returns the previous cap."""
    import pytest as _pytest

    from repro.core import plan_cache_cap, set_plan_cache_cap

    cur = plan_cache_cap()
    with _pytest.raises(ValueError, match=">= 1"):
        set_plan_cache_cap(0)
    assert plan_cache_cap() == cur
    prev = set_plan_cache_cap(cur)
    assert prev == cur


def test_explain_matches_compile_program_routing():
    """explain() routes through the same probe as compile_program —
    including split-win registration and non-default flags."""
    from repro.core import (Generated, PallasGenerated, compile_program,
                            explain, register_pallas_split_win)
    from repro.core.engine import PALLAS_SPLIT_WINS, clear_compile_cache
    from repro.core.programs import smooth_norm_program

    prog = smooth_norm_program()
    clear_compile_cache()
    try:
        assert "auto backend: jax" in explain(prog)
        assert isinstance(compile_program(prog, backend="auto"), Generated)
        register_pallas_split_win(prog.name)
        # both the report and the compilation flip together, for every
        # flag combination
        assert "auto backend: pallas" in explain(prog, double_buffer=True)
        gen = compile_program(prog, backend="auto", double_buffer=True)
        assert isinstance(gen, PallasGenerated)
    finally:
        PALLAS_SPLIT_WINS.discard(prog.name)
        clear_compile_cache()


def test_demand_exceeding_availability_raises():
    # goal wants the full range but the kernel needs i+1 halo from an
    # axiom that only covers [0, N)
    k = kernel("shift", [("a", "u[i?+1]")], [("o", "v(u[i?])")], fn=lambda a: a)
    prog = Program(
        rules=[k],
        axioms=[axiom("u[i?]", i="Ni")],
        goals=[goal("v(u[i])", i=("Ni", 0, 0))],
        loop_order=("i",),
    )
    idag = infer(prog)
    with pytest.raises(ValueError, match="exceeds"):
        build_dataflow(idag)
