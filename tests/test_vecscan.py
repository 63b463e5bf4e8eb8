"""The VecScan vectorization analyzer (repro.core.vecscan): total
access-pattern classification over the golden corpus, the hand-built
cases behind every access class and PV diagnostic, the redundant-load
ratio model against worked numbers, LayoutHint attachment + plan
serialization round-trip, and the engine/CLI wiring (vec_report=,
explain, the backend="auto" tiebreaker, plan_lint --vec)."""
import dataclasses
import json
import os
import pathlib
import subprocess
import sys

import pytest

from repro.core import (KernelPlan, VecReport, attach_layout_hints,
                        auto_vec_reject, clear_compile_cache,
                        compile_program, explain, render_vec, scan_plan)
from repro.core.codegen_jax import Generated
from repro.core.codegen_pallas import PallasGenerated
from repro.core.plan import (CallPlan, GridDim, InputPlan, LayoutHint,
                             OutputPlan, ReadPlan, StepPlan)
from repro.core.programs import heat3d_program, laplace5_program
from repro.core.vecscan import (AUTO_RATIO_ENV, OCCUPANCY_ENV,
                                PV004_OCCUPANCY)

ROOT = pathlib.Path(__file__).resolve().parent.parent
GOLDEN_DIR = ROOT / "tests" / "goldens" / "plans"


@pytest.fixture(autouse=True)
def _fresh_cache():
    clear_compile_cache()
    yield
    clear_compile_cache()


def _call(**overrides) -> CallPlan:
    base = dict(
        name="vec_n0",
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


def _plan(call: CallPlan) -> KernelPlan:
    return KernelPlan(
        program="vec",
        loop_order=("j", "i"),
        dim_sizes=(("i", "Ni"), ("j", "Nj")),
        axioms=(),
        goal_outputs=(("v", "v"),),
        calls=(call,),
    )


def _laplace_kplan() -> KernelPlan:
    return compile_program(laplace5_program(), backend="pallas",
                           interpret=True).kernel_plan


def _read_classes(rep: VecReport) -> list:
    return [s.cls for s in rep.sites if s.kind == "read"]


def _codes(rep: VecReport) -> set:
    return {d.code for d in rep.diagnostics}


# ---------------------------------------------------------------------------
# Acceptance gate: every read site in every golden plan classifies
# ---------------------------------------------------------------------------

def test_golden_corpus_classifies_totally():
    goldens = sorted(GOLDEN_DIR.glob("*.json"))
    assert len(goldens) == 16
    for path in goldens:
        kp = KernelPlan.from_dict(json.loads(path.read_text()))
        rep = scan_plan(kp)
        assert rep.sites, path.name
        assert rep.class_counts()["unknown"] == 0, path.name
        assert "PV000" not in _codes(rep), path.name


# ---------------------------------------------------------------------------
# The classifier, one hand-built case per access class
# ---------------------------------------------------------------------------

def test_aligned_and_broadcast():
    call = _call(
        inputs=(InputPlan("u"), InputPlan("s", scalar=True)),
        steps=(StepPlan("f", 0, (ReadPlan("in_u", 0, 0, 0),
                                 ReadPlan("scalar:s", 0, 0, 0)),
                        ((("out", 0),),), 0),),
    )
    rep = scan_plan(_plan(call))
    assert _read_classes(rep) == ["aligned", "broadcast"]
    assert not rep.diagnostics


def test_shifted_lane_crossing_read():
    # resident [0, Ni+1); origin 1 is contained but not lane-aligned
    call = _call(
        inputs=(InputPlan("u", i_hi=1),),
        steps=(StepPlan("f", 0, (ReadPlan("in_u", 0, 1, 0),),
                        ((("out", 0),),), 0),),
    )
    rep = scan_plan(_plan(call))
    assert _read_classes(rep) == ["shifted"]
    # a lone shifted read is an unaligned row group
    assert _codes(rep) == {"PV002"}
    assert [h.kind for h in rep.hints] == ["realign_origin"]


def test_strided_read():
    call = _call(
        steps=(StepPlan("f", 0, (ReadPlan("in_u", 0, 0, 0, i_stride=2),),
                        ((("out", 0),),), 0),),
    )
    rep = scan_plan(_plan(call))
    assert _read_classes(rep) == ["strided"]
    assert "PV006" in _codes(rep)
    assert any(h.kind == "layout_transform" for h in rep.hints)


def test_gather_span_not_resident():
    # w_off=1 overruns the [0, Ni+0) resident span: per-lane gather
    call = _call(
        steps=(StepPlan("f", 0, (ReadPlan("in_u", 0, 0, 1),),
                        ((("out", 0),),), 0),),
    )
    rep = scan_plan(_plan(call))
    assert _read_classes(rep) == ["gather"]
    assert "PV001" in _codes(rep)
    assert any(h.kind == "layout_transform" for h in rep.hints)


def test_unknown_source_is_pv000_error():
    call = _call(
        steps=(StepPlan("f", 0, (ReadPlan("in_ghost", 0, 0, 0),),
                        ((("out", 0),),), 0),),
    )
    rep = scan_plan(_plan(call))
    assert _read_classes(rep) == ["unknown"]
    assert any(d.code == "PV000" and d.severity == "error"
               for d in rep.diagnostics)


# ---------------------------------------------------------------------------
# The efficiency model
# ---------------------------------------------------------------------------

def test_pv005_overlapping_loads_and_ratio():
    # two overlapping contiguous reads of one resident row: loaded
    # spans 2*Ni+1 elements, unique Ni+1 -> asymptotic ratio 2.0
    call = _call(
        inputs=(InputPlan("u", i_hi=1),),
        steps=(StepPlan("f", 0, (ReadPlan("in_u", 0, 0, 0),
                                 ReadPlan("in_u", 0, 1, 0)),
                        ((("out", 0),),), 0),),
    )
    rep = scan_plan(_plan(call))
    assert "PV005" in _codes(rep)
    assert any(h.kind == "shift_reuse" for h in rep.hints)
    (sv,) = rep.steps
    assert (sv.n_reads, sv.n_groups) == (2, 1)
    assert rep.redundant_load_ratio == pytest.approx(2.0)


def test_laplace5_ratio_matches_hand_count():
    """5 reads of width Ni-2 over 3 resident rows: asymptotically 5/3.
    Exactly: loaded 5(Ni-2); unique is Ni-2 for the j-1 and j+1 rows
    plus Ni for the j+0 row (three reads at origins 0/1/2 overlap into
    one Ni-wide span) = 3Ni-4."""
    kp = _laplace_kplan()
    rep = scan_plan(kp)
    assert rep.redundant_load_ratio == pytest.approx(5 / 3)
    ni = 256
    crep = scan_plan(kp, sizes={"Nj": 96, "Ni": ni})
    assert crep.ni == ni
    assert crep.redundant_load_ratio == pytest.approx(
        (5 * ni - 10) / (3 * ni - 4))
    assert crep.bytes_moved == (5 * ni - 10) * 4
    assert crep.bytes_needed == (3 * ni - 4) * 4
    # 2 unaligned row groups (j-1 and j+1 rows) + the overlapping-load
    # finding; full lane occupancy at Ni=256
    codes = sorted(d.code for d in crep.diagnostics)
    assert codes == ["PV002", "PV002", "PV005"]
    assert crep.lane_occupancy == pytest.approx(1.0)


def test_laplace5_window_reuse():
    (w,) = scan_plan(_laplace_kplan()).windows
    assert (w.name, w.stages, w.reuse, w.slack) == ("in_cell", 3, 3, 0)


def test_pv003_acc_rows_output():
    call = _call(outputs=(OutputPlan("r", kind="acc_rows"),))
    rep = scan_plan(_plan(call))
    assert "PV003" in _codes(rep)
    assert any(h.kind == "acc_lane_block" for h in rep.hints)


def test_pv004_lane_padding_waste():
    ni = 8  # width 8 of a 128-lane padded row: occupancy 1/16
    rep = scan_plan(_plan(_call()), sizes={"Ni": ni})
    assert rep.lane_occupancy == pytest.approx(ni / 128)
    assert rep.lane_occupancy < PV004_OCCUPANCY
    assert "PV004" in _codes(rep)


# ---------------------------------------------------------------------------
# VecReport structure
# ---------------------------------------------------------------------------

def test_report_to_dict_is_json_native():
    rep = scan_plan(_laplace_kplan(), sizes={"Nj": 96, "Ni": 256})
    blob = json.dumps(rep.to_dict(), sort_keys=True)
    back = json.loads(blob)
    assert back["program"] == "laplace5"
    assert back["redundant_load_ratio"] == rep.redundant_load_ratio
    summary = rep.summary()
    assert set(summary) == {"vec_redundant_load_ratio",
                            "vec_lane_occupancy", "vec_bytes_moved",
                            "vec_bytes_needed", "vec_classes",
                            "vec_diagnostics"}
    assert summary["vec_classes"] == {"aligned": 2, "shifted": 4}
    assert render_vec(rep) == rep.render()


# ---------------------------------------------------------------------------
# LayoutHints: attachment, identity, serialization round-trip
# ---------------------------------------------------------------------------

def test_planner_attaches_layout_hints():
    kp = _laplace_kplan()
    assert {h.kind for h in kp.layout_hints} == {"realign_origin",
                                                 "shift_reuse"}


def test_hints_do_not_split_caches_but_serialize():
    kp = _laplace_kplan()
    bare = dataclasses.replace(kp, layout_hints=())
    assert kp == bare  # compare=False: identity unchanged
    assert kp.cache_key() == bare.cache_key()
    back = KernelPlan.from_dict(json.loads(json.dumps(kp.to_dict())))
    assert back.layout_hints == kp.layout_hints
    for h in back.layout_hints:
        assert isinstance(h, LayoutHint)
        assert LayoutHint.from_dict(h.to_dict()) == h


def test_attach_layout_hints_noop_without_findings():
    kp = _plan(_call())  # one aligned read: nothing to recommend
    assert attach_layout_hints(kp) is kp


# ---------------------------------------------------------------------------
# Engine wiring: vec_report=, explain, the auto tiebreaker
# ---------------------------------------------------------------------------

def test_compile_program_vec_report_kwarg():
    prog = laplace5_program()
    gen = compile_program(prog, backend="pallas", interpret=True,
                          vec_report=True)
    assert isinstance(gen.vec_report, VecReport)
    assert gen.vec_report.program == "laplace5"
    clear_compile_cache()
    assert compile_program(prog, backend="pallas",
                           interpret=True).vec_report is None


def test_explain_verbose_renders_vectorization():
    out = explain(heat3d_program(), verbose=True)
    assert "--- vectorization ---" in out
    assert "redundant-load ratio" in out


def test_auto_vec_reject_occupancy_floor(monkeypatch):
    kp = _laplace_kplan()
    sizes = {"Nj": 96, "Ni": 256}
    monkeypatch.delenv(OCCUPANCY_ENV, raising=False)
    monkeypatch.delenv(AUTO_RATIO_ENV, raising=False)
    assert auto_vec_reject(kp, sizes) is None  # occupancy 1.0
    monkeypatch.setenv(OCCUPANCY_ENV, "1.01")
    assert "lane occupancy" in auto_vec_reject(kp, sizes)


def test_auto_vec_reject_ratio_ceiling(monkeypatch):
    kp = _laplace_kplan()
    sizes = {"Nj": 96, "Ni": 256}
    monkeypatch.delenv(OCCUPANCY_ENV, raising=False)
    monkeypatch.setenv(AUTO_RATIO_ENV, "1.5")  # laplace5 models ~1.66
    assert "redundant-load ratio" in auto_vec_reject(kp, sizes)
    monkeypatch.setenv(AUTO_RATIO_ENV, "2.0")
    assert auto_vec_reject(kp, sizes) is None


def test_auto_routing_consults_the_tiebreaker(monkeypatch):
    """backend="auto" + dim_sizes routes to JAX when the vec model
    rejects, and to Pallas otherwise — same program, same sizes."""
    prog = laplace5_program()
    sizes = {"Nj": 24, "Ni": 96}
    monkeypatch.delenv(AUTO_RATIO_ENV, raising=False)
    monkeypatch.delenv(OCCUPANCY_ENV, raising=False)
    gen = compile_program(prog, backend="auto", interpret=True,
                          dim_sizes=sizes)
    assert isinstance(gen, PallasGenerated)
    clear_compile_cache()
    monkeypatch.setenv(OCCUPANCY_ENV, "1.01")  # nothing can pass
    gen = compile_program(prog, backend="auto", interpret=True,
                          dim_sizes=sizes)
    assert isinstance(gen, Generated)


# ---------------------------------------------------------------------------
# The lint CLI under --vec
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_plan_lint_vec_json_over_goldens():
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run(
        [sys.executable, str(ROOT / "scripts" / "plan_lint.py"),
         str(GOLDEN_DIR), "--vec", "--format", "json"],
        capture_output=True, text=True, cwd=ROOT, env=env)
    assert out.returncode == 0, out.stderr
    records = [json.loads(line) for line in out.stdout.splitlines()]
    assert len(records) == 16
    baseline = json.loads(
        (ROOT / "tests" / "goldens" /
         "vec_lint_baseline.json").read_text())["errors"]
    for r in records:
        assert r["errors"] == 0
        assert "vec" in r and "vec_redundant_load_ratio" in r["vec"]
        assert baseline[pathlib.Path(r["target"]).name] == 0
