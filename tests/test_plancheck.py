"""The PlanCheck static analyzer (repro.core.plancheck): golden-corpus
lint sweep, hazard-injection cases proving every diagnostic code fires,
the VMEM footprint model, the engine's ``check_plans``/``dim_sizes``
wiring, the lint CLI, the warm-cache refusal gate, the interpreter's
hazard guards, and the plan-cache env default + cross-process lock."""
import dataclasses
import importlib.util
import json
import pathlib
import subprocess
import sys
import warnings

import jax.numpy as jnp
import pytest

from repro.core import (KernelPlan, PlanCache, PlanCheckError,
                        PlanCheckWarning, check_plan, clear_compile_cache,
                        compile_program, explain, has_errors,
                        sizes_from_arrays, vmem_bytes, vmem_report)
from repro.core.codegen_jax import Generated
from repro.core.engine import _emit_plan
from repro.core.plancheck import (DEFAULT_VMEM_BUDGET, Diagnostic,
                                  call_vmem, resolve_check_mode, vmem_budget)
from repro.core.programs import ALL_PROGRAMS, heat3d_program

ROOT = pathlib.Path(__file__).resolve().parent.parent
GOLDEN_DIR = ROOT / "tests" / "goldens" / "plans"


@pytest.fixture(autouse=True)
def _fresh_cache():
    clear_compile_cache()
    yield
    clear_compile_cache()


def load_golden(name: str) -> KernelPlan:
    return KernelPlan.from_dict(
        json.loads((GOLDEN_DIR / f"{name}.json").read_text()))


def mutate_call(kplan: KernelPlan, ci: int = 0, **over) -> KernelPlan:
    """Rebuild ``kplan`` with call ``ci`` mutated (the hazard-injection
    harness: every mutation below models a corruption an autotuner or
    hand edit could introduce)."""
    calls = list(kplan.calls)
    calls[ci] = dataclasses.replace(calls[ci], **over)
    return dataclasses.replace(kplan, calls=tuple(calls))


def codes(kplan: KernelPlan, **kw) -> set:
    return {d.code for d in check_plan(kplan, **kw)}


# ---------------------------------------------------------------------------
# Golden-corpus sweep: every checked-in plan is hazard-free
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(ALL_PROGRAMS))
def test_golden_corpus_lints_clean(name):
    """Zero diagnostics — not even warnings — on every golden plan:
    the analyzer's inequalities are exact on the full capability
    matrix (plane windows, producer planes, reductions, locals,
    multi-call chains)."""
    assert check_plan(load_golden(name)) == []


def test_golden_corpus_is_complete():
    assert {p.stem for p in GOLDEN_DIR.glob("*.json")} == set(ALL_PROGRAMS)


# ---------------------------------------------------------------------------
# Hazard injection: each diagnostic code fires on a minimal bad plan
# ---------------------------------------------------------------------------

def test_pc000_unresolved_read_source():
    kp = load_golden("heat3d")
    c = kp.calls[0]
    step = dataclasses.replace(
        c.steps[0],
        reads=(dataclasses.replace(c.steps[0].reads[0], src="in_ghost"),))
    assert codes(mutate_call(kp, steps=(step,))) == {"PC000"}


def test_pc001_reordered_steps():
    """Swapping the first two steps of the hydro1d local chain makes a
    consumer read its local before the producing step runs."""
    kp = load_golden("hydro1d")
    c = kp.calls[0]
    bad = mutate_call(kp, steps=(c.steps[1], c.steps[0]) + c.steps[2:])
    got = check_plan(bad)
    assert has_errors(got)
    assert {d.code for d in got} == {"PC001"}


def test_pc002_shrunk_plane_window():
    """heat3d reads planes p-1..p+1; a 2-plane window cannot hold the
    oldest one (the mod-slot arithmetic would alias it)."""
    kp = load_golden("heat3d")
    i0 = dataclasses.replace(kp.calls[0].inputs[0], p_stages=2)
    assert codes(mutate_call(kp, inputs=(i0,))) == {"PC002"}


def test_pc002_shrunk_rolling_window():
    """cosmo's lead-2 stream needs 3 resident rows; 1 stage aliases."""
    kp = load_golden("cosmo")
    i0 = dataclasses.replace(kp.calls[0].inputs[0], stages=1)
    assert codes(mutate_call(kp, inputs=(i0,))) == {"PC002"}


def test_pc003_vmem_over_budget():
    kp = load_golden("heat3d")
    sizes = {"Nk": 8, "Nj": 10, "Ni": 200}
    diags = check_plan(kp, sizes=sizes, budget=1024)
    assert {d.code for d in diags} == {"PC003"}
    assert not has_errors(diags)  # budget findings are warnings
    assert check_plan(kp, sizes=sizes) == []  # default budget: clean


def test_pc004_dead_cross_call_output():
    """Dropping one laplace_pair goal orphans its call output."""
    kp = load_golden("laplace_pair")
    bad = dataclasses.replace(kp, goal_outputs=(kp.goal_outputs[0],))
    diags = check_plan(bad)
    assert {d.code for d in diags} == {"PC004"}
    assert not has_errors(diags)


def test_pc005_dropped_lead():
    """Zeroing heat3d's stream lead leaves the j+1/p+1 reads pointing
    ahead of anything the DMA has landed."""
    kp = load_golden("heat3d")
    i0 = dataclasses.replace(kp.calls[0].inputs[0], lead=0, p_lead=0)
    assert codes(mutate_call(kp, inputs=(i0,))) == {"PC005"}


def test_pc006_trim_outside_device_buffer():
    kp = load_golden("heat3d")
    o0 = dataclasses.replace(kp.calls[0].outputs[0], j_lo=-2)
    got = codes(mutate_call(kp, outputs=(o0,)))
    assert "PC006" in got


def test_pc007_idle_accumulator():
    """An accumulator no step combines and no output emits is a dead
    reduction (both findings fire)."""
    kp = load_golden("subset_sum")
    c = kp.calls[0]
    accs = c.accs + (dataclasses.replace(c.accs[0], name="a_phantom_u"),)
    diags = check_plan(mutate_call(kp, accs=accs))
    assert [d.code for d in diags] == ["PC007", "PC007"]
    assert all(d.var == "a_phantom_u" for d in diags)


def test_diagnostic_str_carries_code_nest_and_var():
    d = Diagnostic("PC002", "error", "in_u", "heat3d_n0", "missing halo")
    assert str(d) == "PC002 error [heat3d_n0] in_u: missing halo"


# ---------------------------------------------------------------------------
# The VMEM footprint model
# ---------------------------------------------------------------------------

def test_sizes_from_arrays_matches_runtime_resolution():
    kp = load_golden("heat3d")
    assert sizes_from_arrays(kp, {"u": (8, 10, 200)}) == \
        {"Nk": 8, "Nj": 10, "Ni": 200}


def test_vmem_bytes_mirrors_scratch_shapes():
    """heat3d's only scratch is the 3-plane input window.  At Nj=10 each
    grid step computes a 16-row tile (10 rows rounded up to the tile),
    so a plane holds 24 rows: an 8-row top margin for the first step's
    j-1 read and the step's aligned 24-row read span; x pad(200->256)
    lanes x 4 B.  The input streams two 10-row blocks (padded to 16)
    and the goal-shaped output two 10-row blocks (padded to 16).  The
    output's carry buffer holds the step's 16 rows below 7 pad rows
    (its goal rows start one row into the step's tile): 24 rows.  The
    step body holds at most 8 values of 16 x 256 (its seven reads and
    one sum)."""
    kp = load_golden("heat3d")
    sizes = {"Nk": 8, "Nj": 10, "Ni": 200}
    block = 2 * 16 * 256 * 4
    body = 8 * 16 * 256 * 4
    carry = 24 * 256 * 4
    assert vmem_bytes(kp, sizes) == \
        3 * 24 * 256 * 4 + 2 * block + carry + body
    rep = vmem_report(kp, sizes)
    assert rep["heat3d_n0"]["in_u"] == 73728
    assert rep["heat3d_n0"]["blk_u"] == block
    assert rep["heat3d_n0"]["out_heat_u"] == block
    assert rep["heat3d_n0"]["seat_heat_u"] == carry
    assert rep["heat3d_n0"]["body"] == body
    assert rep["heat3d_n0"]["total"] == 294912


def test_vmem_bytes_double_buffer_adds_staging():
    """The explicit two-slot DMA staging takes the place of the
    pipeline's two input blocks, at the same size, for the one-row grid
    steps that ``double_buffer`` keeps (R-row steps stream R-row
    blocks instead)."""
    kp = load_golden("cosmo")
    sizes = sizes_from_arrays(kp, {"u": (4, 12, 100)})
    plain = vmem_report(kp, sizes)["cosmo_n0"]
    dbuf = vmem_report(kp, sizes, double_buffer=True)["cosmo_n0"]
    one_row = call_vmem(kp.calls[0], 12, 100, 4, False, rows=1)
    assert dbuf["dma_u"] == one_row["blk_u"] == 2 * 8 * 128 * 4
    assert plain["blk_u"] == 2 * 16 * 128 * 4
    assert "blk_u" not in dbuf and "dma_u" not in plain
    assert dbuf["total"] == one_row["total"]


def test_vmem_budget_resolution(monkeypatch):
    assert vmem_budget(None) == DEFAULT_VMEM_BUDGET
    assert vmem_budget(4096) == 4096
    monkeypatch.setenv("REPRO_VMEM_BUDGET_BYTES", "8192")
    assert vmem_budget(None) == 8192


# ---------------------------------------------------------------------------
# Engine wiring: check_plans modes, dim_sizes, auto VMEM routing
# ---------------------------------------------------------------------------

def test_resolve_check_mode(monkeypatch):
    assert resolve_check_mode(None) == "warn"
    assert resolve_check_mode("off") == "off"
    monkeypatch.setenv("REPRO_CHECK_PLANS", "error")
    assert resolve_check_mode(None) == "error"
    with pytest.raises(ValueError, match="check_plans"):
        resolve_check_mode("loud")


def test_compile_clean_under_error_mode():
    gen = compile_program(heat3d_program(), backend="pallas",
                          check_plans="error", use_cache=False)
    u = jnp.ones((4, 6, 140), jnp.float32)
    assert gen.fn(u=u)["heat"].shape == (4, 6, 140)


def _hazard_plan() -> KernelPlan:
    kp = load_golden("heat3d")
    i0 = dataclasses.replace(kp.calls[0].inputs[0], lead=0, p_lead=0)
    return mutate_call(kp, inputs=(i0,))


def test_emit_plan_error_mode_rejects_hazard():
    with pytest.raises(PlanCheckError) as ei:
        _emit_plan(_hazard_plan(), None, interpreter="pallas",
                   dtype=jnp.float32, interpret=True,
                   double_buffer=False, use_cache=False, check="error")
    assert any(d.code == "PC005" for d in ei.value.diagnostics)


def test_emit_plan_warn_mode_warns_then_off_is_silent():
    # warn: the hazard surfaces as PlanCheckWarning (the interpreter
    # build itself is stopped earlier by the kernel guard, so catch
    # either outcome after the warning is recorded)
    with pytest.warns(PlanCheckWarning, match="PC005"):
        try:
            _emit_plan(_hazard_plan(), None, interpreter="pallas",
                       dtype=jnp.float32,
                       interpret=True, double_buffer=False,
                       use_cache=False, check="warn")
        except ValueError:
            pass
    # off: no PlanCheckWarning at all
    with warnings.catch_warnings():
        warnings.simplefilter("error", PlanCheckWarning)
        try:
            _emit_plan(_hazard_plan(), None, interpreter="pallas",
                       dtype=jnp.float32,
                       interpret=True, double_buffer=False,
                       use_cache=False, check="off")
        except ValueError:
            pass


def test_auto_routes_to_jax_when_over_vmem_budget(monkeypatch):
    sizes = {"Nk": 8, "Nj": 10, "Ni": 200}
    gen = compile_program(heat3d_program(), backend="auto",
                          dim_sizes=sizes, use_cache=False)
    assert not isinstance(gen, Generated)  # fits: stencil executor
    monkeypatch.setenv("REPRO_VMEM_BUDGET_BYTES", "1024")
    gen = compile_program(heat3d_program(), backend="auto",
                          dim_sizes=sizes, use_cache=False)
    assert isinstance(gen, Generated)  # over budget: JAX fallback


def test_dim_sizes_keys_the_compile_cache():
    compile_program(heat3d_program(), backend="auto")
    compile_program(heat3d_program(), backend="auto",
                    dim_sizes={"Nk": 8, "Nj": 10, "Ni": 200})
    from repro.core import compile_cache_size
    assert compile_cache_size() == 2


def test_explain_verbose_renders_vmem():
    out = explain(heat3d_program(), verbose=True,
                  dim_sizes={"Nk": 8, "Nj": 10, "Ni": 200})
    assert "--- vmem estimate ---" in out
    assert "in_u: 3 x sub(Nj+0+m) x pad(Ni+0) x 4B" in out
    assert "seat heat_u: sub(R+p) x pad(Ni+0) x 4B where R > 1" in out
    assert "294912 B resident" in out


# ---------------------------------------------------------------------------
# The interpreter's own hazard guards (analyzer claims, asserted)
# ---------------------------------------------------------------------------

def test_build_call_rejects_aliased_window_read():
    from repro.kernels.stencil2d import build_call
    kp = _hazard_plan()
    with pytest.raises(ValueError, match="PlanCheck"):
        build_call(kp.calls[0], (8, 10, 200), jnp.float32, interpret=True)


def test_build_call_rejects_local_read_before_write():
    from repro.kernels.stencil2d import build_call
    kp = load_golden("hydro1d")
    c = kp.calls[0]
    bad = mutate_call(kp, steps=(c.steps[1], c.steps[0]) + c.steps[2:])
    with pytest.raises(ValueError, match="PC001"):
        build_call(bad.calls[0], (12, 200), jnp.float32, interpret=True)


# ---------------------------------------------------------------------------
# The lint CLI
# ---------------------------------------------------------------------------

def _run_lint(*args):
    return subprocess.run(
        [sys.executable, str(ROOT / "scripts" / "plan_lint.py"), *args],
        capture_output=True, text=True, cwd=ROOT)


@pytest.mark.slow
def test_pc008_interpreter_capability_mismatch():
    """check_plan(interpreter=...) is the static twin of the registry's
    build-time capability gate: each plan feature outside the target
    interpreter's declared set becomes one PC008 error."""
    from repro.core.interpreters import (InterpreterSpec,
                                         register_interpreter,
                                         unregister_interpreter)

    kp = load_golden("heat3d")
    # both built-ins declare full capabilities: no PC008
    assert "PC008" not in codes(kp, interpreter="pallas")
    assert "PC008" not in codes(kp, interpreter="interp_jax")
    register_interpreter(InterpreterSpec(
        name="_pc008_tiny", build_call=lambda *a, **k: None,
        capabilities=frozenset(), flags=frozenset()))
    try:
        diags = [d for d in check_plan(kp, interpreter="_pc008_tiny")
                 if d.code == "PC008"]
        assert diags and all(d.severity == "error" for d in diags)
        assert {d.var for d in diags} == kp.features()
    finally:
        unregister_interpreter("_pc008_tiny")


def test_cli_goldens_exit_zero():
    res = _run_lint(str(GOLDEN_DIR), "-q")
    assert res.returncode == 0, res.stdout + res.stderr
    assert "16 target(s), 0 error(s), 0 warning(s)" in res.stdout


@pytest.mark.slow
def test_cli_flags_corrupt_file_and_hazard_plan(tmp_path):
    corrupt = tmp_path / "corrupt.json"
    corrupt.write_text("{not json")
    hazard = tmp_path / "hazard.json"
    hazard.write_text(json.dumps(_hazard_plan().to_dict()))
    res = _run_lint(str(corrupt), str(hazard))
    assert res.returncode == 1
    assert "PC000" in res.stdout
    assert "PC005" in res.stdout


# ---------------------------------------------------------------------------
# Plan-cache env default, write locking, warm-cache refusal
# ---------------------------------------------------------------------------

def test_plan_cache_dir_env_default(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_PLAN_CACHE_DIR", str(tmp_path))
    compile_program(heat3d_program(), backend="pallas", use_cache=False)
    assert len(list(tmp_path.glob("*.json"))) == 1


def test_plan_cache_put_takes_the_write_lock(tmp_path):
    cache = PlanCache(tmp_path)
    assert cache.put("deadbeef", load_golden("laplace5"))
    assert (tmp_path / ".lock").exists()
    # the lock file never counts against the entry bound
    assert len(cache) == 1


def test_plan_cache_eviction_respects_bound_under_lock(tmp_path):
    cache = PlanCache(tmp_path, max_entries=3)
    kp = load_golden("laplace5")
    for k in "abcdef":
        cache.put(k * 8, kp)
    assert len(cache) == 3


@pytest.mark.slow
def test_warm_cache_refuses_hazard_plans(tmp_path, monkeypatch):
    """The warm-cache gate: a planner (or future autotuner) emitting a
    hazardous plan must not poison the shared cache directory."""
    spec = importlib.util.spec_from_file_location(
        "warm_cache_under_test", ROOT / "scripts" / "warm_cache.py")
    wc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(wc)
    monkeypatch.setattr(
        wc, "ALL_PROGRAMS", {"bad": heat3d_program})
    monkeypatch.setattr(
        wc, "plan_program",
        lambda build: (build(), _hazard_plan()))
    rc = wc.main(["--cache-dir", str(tmp_path)])
    assert rc == 1
    assert len(list(tmp_path.glob("*.json"))) == 0  # nothing persisted
