"""One benchmark leg per lifted Pallas-executor restriction.

Each leg times ``backend="pallas"`` against the unfused oracle value
(which it must match) and reports the JAX-backend time for the same
program as its in-row baseline:

* ``pyramid4d``  — outer grids (two loop dims flattened onto leading
  Pallas grid dims, blur contracted to a 3-row rolling buffer);
* ``energy3d``   — k-tiled reduction (carried VMEM accumulator across
  every outer tile of the (k, j) grid);
* ``plane_sum``  — per-outer-tile reduction (output keeps k);
* ``smooth_norm`` — cross-row read of a same-nest materialized variable
  (served from a rolling VMEM window);
* ``cosmo_dbuf`` — double-buffered input DMA (explicit two-slot
  async-copy pipeline) vs the BlockSpec-streamed cosmo leg;
* ``heat3d``     — outer-dim stencil halo (``u[k-1]``/``u[k+1]`` reads
  served from a 3-plane VMEM window carried across the k grid);
* ``heat3d_dbuf`` — the same plane window fed by the double-buffered
  DMA pipeline;
* ``heat3d_stage`` — a *producer plane window*: the same-nest
  pre-smooth stage runs one tile ahead, its planes resident in VMEM,
  never materialized to HBM;
* ``heat3d_residual_norm`` — a halo'd reduction: plane-window input
  plus a carried accumulator fused in one nest;
* ``row_sum``    — row-kept reduction (per-step partial-accumulator
  rows, lane-reduced on the host);
* ``subset_sum`` — reduction keeping a leading subset of outer dims
  (accumulator re-initialized per kept-prefix tile).

The suite also sweeps the **plan-interpreter registry**
(``interpreters`` legs): every registered interpreter
(:mod:`repro.core.interpreters` — Pallas-interpret, the pure-JAX plan
interpreter, future registrations) runs laplace5, heat3d, and cosmo
against the legacy fused-JAX emitter baseline, so the overhead of
interpreting the declarative KernelPlan vs executing emitted source
is tracked per PR.  Every **layout-aware** interpreter additionally
runs a ``*_layout`` leg: the same program compiled with
``apply_layout="auto"`` (the LayoutApply pass,
:mod:`repro.core.layoutapply`), cross-checked bit-identical against
the untransformed leg, timed, and recorded beside the *post-transform*
re-run of the vectorization analyzer — so the transformed-vs-
untransformed throughput delta and the analyzer's predicted
redundant-load drop land in the same ``BENCH_<pr>.json`` record.

The suite also times the **AOT plan cache** (``plan_cache`` legs):
cold-plan compiles (full analysis pipeline + planner) against
warm-cache compiles (the serialized plan loaded from disk, analysis
skipped entirely) for the laplace5 and heat3d programs — the
"decide ahead of time, replay cheaply" claim in wall-clock form.

Every Pallas leg also records the vectorization analyzer's summary
(:func:`repro.core.vecscan.scan_plan` at the leg's concrete shape —
predicted redundant-load ratio, lane occupancy, modeled bytes moved
vs needed) beside the measured wall time, so the static model's
predictions can be compared against reality PR over PR
(``scripts/bench_trend.py`` prints that trajectory).

Off-TPU the legs run in interpret mode on bounded sizes (the grid
unrolls at trace time); on a TPU they compile for the chip.  Feed measured split-schedule wins back into
``repro.core.engine.register_pallas_split_win`` so ``backend="auto"``
routes them to the stencil executor.

Run directly for the machine-readable trajectory record::

    PYTHONPATH=src python -m benchmarks.lifted --json

(`scripts/bench.sh` wraps this and writes ``BENCH_<pr>.json`` so every
PR leaves a perf baseline the next one can regress against.)
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from typing import Optional

import jax
import numpy as np

from repro.core import (clear_compile_cache, compile_program, scan_plan,
                        sizes_from_arrays, vmem_bytes)
from repro.core.codegen_jax import CodegenError
from repro.core.interpreters import resolve_interpret
from repro.core.programs import (cosmo_program, energy3d_program,
                                 heat3d_program,
                                 heat3d_residual_norm_program,
                                 heat3d_stage_program, laplace5_program,
                                 plane_sum_program, pyramid4d_program,
                                 row_sum_program, smooth_norm_program,
                                 subset_sum_program)
from repro.core.unfused import build_unfused

from .common import mk, time_fn, time_pair

# interpret mode unrolls the grid at trace time: keep row counts bounded
CASES = [
    ("pyramid4d", pyramid4d_program, "edge", (2, 2, 24, 128), False),
    ("energy3d", energy3d_program, "energy", (4, 32, 256), False),
    ("plane_sum", plane_sum_program, "colsum", (4, 32, 256), False),
    ("smooth_norm", smooth_norm_program, "nflux", (96, 256), False),
    ("cosmo_dbuf", cosmo_program, "unew", (4, 48, 256), True),
    ("heat3d", heat3d_program, "heat", (6, 32, 256), False),
    ("heat3d_dbuf", heat3d_program, "heat", (6, 32, 256), True),
    ("heat3d_stage", heat3d_stage_program, "heat", (6, 32, 256), False),
    ("heat3d_residual_norm", heat3d_residual_norm_program, "rnorm",
     (6, 32, 256), False),
    ("row_sum", row_sum_program, "rsum", (96, 256), False),
    ("subset_sum", subset_sum_program, "lsum", (3, 4, 24, 256), False),
]


def run(interpret: Optional[bool] = None):
    interpret = resolve_interpret(interpret)
    rng = np.random.default_rng(7)
    rows = []
    for name, build, out, shape, dbuf in CASES:
        prog = build()
        u = mk(rng, shape)
        ref = build_unfused(prog).fn(u=u)[out]
        gen = compile_program(prog, backend="pallas", interpret=interpret,
                              double_buffer=dbuf)
        pallas_fn = jax.jit(lambda u, _g=gen: _g.fn(u=u)[out])
        t_p, got = time_fn(pallas_fn, u)
        assert np.allclose(np.asarray(got), np.asarray(ref),
                           atol=1e-4, rtol=1e-4), name
        jax_us = None
        try:
            gen_j = compile_program(prog, backend="jax")
            jax_fn = jax.jit(lambda u, _g=gen_j: _g.fn(u)[out])
            t_j, got_j = time_fn(jax_fn, u)
            assert np.allclose(np.asarray(got_j), np.asarray(ref),
                               atol=1e-4, rtol=1e-4), name
            jax_us = t_j * 1e6
            base = f"jax_us={jax_us:.0f};"
        except CodegenError:
            base = "jax_us=n/a;"  # defensive: both backends cover every leg
        cells = int(np.prod(shape))
        # the static analyzer's resident-VMEM estimate for this leg's
        # concrete shape (peak across nests; mirrors build_call scratch)
        kplan = gen.kernel_plan
        sizes = sizes_from_arrays(kplan, {"u": shape})
        vmem = vmem_bytes(kplan, sizes, dtype_bytes=4, double_buffer=dbuf)
        # the vectorization analyzer's prediction for the same concrete
        # shape, recorded beside the measured wall time so the model
        # can be judged against reality PR over PR
        vsum = scan_plan(kplan, sizes=sizes).summary()
        rows.append({
            "name": f"lifted_{name}_{'x'.join(map(str, shape))}",
            "us_per_call": t_p * 1e6,
            "derived": (
                f"backend=pallas;interpret={interpret};"
                f"double_buffer={dbuf};{base}"
                f"Mcells_s={cells / t_p / 1e6:.0f};vmem_B={vmem};"
                f"vec_ratio={vsum['vec_redundant_load_ratio']:.2f}"
            ),
            # structured fields for the --json trajectory record
            "backend": "pallas",
            "interpret": interpret,
            "double_buffer": dbuf,
            "jax_us_per_call": jax_us,
            "mcells_per_s": cells / t_p / 1e6,
            "vmem_bytes": vmem,
            **vsum,
        })
    return rows


INTERP_CASES = [
    ("laplace5", laplace5_program, "cell", "lap", (96, 256)),
    ("heat3d", heat3d_program, "u", "heat", (6, 32, 256)),
    ("cosmo", cosmo_program, "u", "unew", (4, 48, 256)),
]


def run_interpreters(interpret: Optional[bool] = None):
    """Per-interpreter legs: every registered plan interpreter runs the
    same program, timed against the legacy fused-JAX emitter
    (``backend="jax"``) as the in-suite baseline — the cost of
    executing the declarative KernelPlan instead of emitted source.
    New registrations get a leg automatically; layout-aware ones also
    get a ``*_layout`` leg with the LayoutApply pass on (auto mode),
    bit-identity-checked against their untransformed leg and recorded
    with the post-transform analyzer summary."""
    interpret = resolve_interpret(interpret)
    from repro.core.interpreters import (get_interpreter,
                                         registered_interpreters)

    rng = np.random.default_rng(11)
    legs = []
    for case, build, arg, out, shape in INTERP_CASES:
        prog = build()
        u = mk(rng, shape)
        cells = int(np.prod(shape))
        ref = build_unfused(prog).fn(**{arg: u})[out]
        gen_e = compile_program(prog, backend="jax")
        emit_fn = jax.jit(lambda u, _g=gen_e: _g.fn(u)[out])
        t_e, got = time_fn(emit_fn, u)
        assert np.allclose(np.asarray(got), np.asarray(ref),
                           atol=1e-4, rtol=1e-4), f"{case}/jax_emitter"
        legs.append({"name": f"interp_{case}_jax_emitter",
                     "interpreter": "jax_emitter",
                     "us_per_call": t_e * 1e6,
                     "mcells_per_s": cells / t_e / 1e6,
                     "vs_jax_emitter": 1.0})
        for name in registered_interpreters():
            gen = compile_program(prog, backend=name, interpret=interpret)
            fn = jax.jit(lambda u, _g=gen, _a=arg: _g.fn(**{_a: u})[out])
            # the transformed leg: same program through LayoutApply,
            # same inputs, bit-identical outputs required — timed
            # interleaved with the untransformed leg so the reported
            # vs_untransformed ratio is robust to clock drift
            lgen = None
            if get_interpreter(name).layout_aware:
                cand = compile_program(prog, backend=name,
                                       interpret=interpret,
                                       apply_layout="auto")
                if cand.kernel_plan.applied_layout:
                    lgen = cand  # auto mode applied: measure the pair
            if lgen is None:
                t, got = time_fn(fn, u)
            else:
                lfn = jax.jit(
                    lambda u, _g=lgen, _a=arg: _g.fn(**{_a: u})[out])
                t, t_l, got, got_l = time_pair(fn, lfn, u)
            assert np.allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-4, rtol=1e-4), f"{case}/{name}"
            kplan = gen.kernel_plan
            vsum = scan_plan(
                kplan, sizes=sizes_from_arrays(kplan, {arg: shape})
            ).summary()
            legs.append({"name": f"interp_{case}_{name}",
                         "interpreter": name,
                         "us_per_call": t * 1e6,
                         "mcells_per_s": cells / t / 1e6,
                         "vs_jax_emitter": t / t_e,
                         **vsum})
            if lgen is None:
                continue
            assert np.array_equal(np.asarray(got_l), np.asarray(got)), \
                f"{case}/{name}+layout: not bit-identical"
            lplan = lgen.kernel_plan
            lsum = scan_plan(
                lplan, sizes=sizes_from_arrays(lplan, {arg: shape})
            ).summary()
            legs.append({"name": f"interp_{case}_{name}_layout",
                         "interpreter": name,
                         "apply_layout": "auto",
                         "applied": [f"{k}:{tgt}" for k, _, tgt
                                     in lplan.applied_layout],
                         "us_per_call": t_l * 1e6,
                         "mcells_per_s": cells / t_l / 1e6,
                         "vs_jax_emitter": t_l / t_e,
                         "vs_untransformed": t_l / t,
                         **lsum})
    return legs


PLAN_CACHE_CASES = [("laplace5", laplace5_program),
                    ("heat3d", heat3d_program)]


def run_plan_cache(repeats: int = 5):
    """Time cold-plan vs warm-cache compiles (best of ``repeats``).

    Cold runs the whole pipeline — inference, dataflow, fusion, storage
    analysis, planning — plus interpreter construction; warm loads the
    serialized plan from a pre-warmed on-disk cache and builds the
    interpreter straight from the IR.  In-memory caches are cleared
    before every sample so each timing is a genuine fresh-process
    stand-in."""
    legs = []
    for name, build in PLAN_CACHE_CASES:
        prog = build()
        with tempfile.TemporaryDirectory() as d:
            def once(**kw):
                clear_compile_cache()
                t0 = time.perf_counter()
                compile_program(prog, backend="pallas", **kw)
                return time.perf_counter() - t0

            cold = min(once() for _ in range(repeats))
            once(plan_cache_dir=d)  # warm the disk entry
            warm = min(once(plan_cache_dir=d) for _ in range(repeats))
        legs.append({
            "name": f"plan_cache_{name}",
            "cold_plan_ms": cold * 1e3,
            "warm_cache_ms": warm * 1e3,
            "speedup": cold / warm if warm > 0 else float("inf"),
        })
        clear_compile_cache()
    return legs


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="Time one leg per lifted Pallas restriction.")
    ap.add_argument("--json", action="store_true",
                    help="emit a machine-readable record (per-leg wall "
                         "time + backend) instead of the CSV rows")
    args = ap.parse_args(argv)
    interpret = resolve_interpret(None)
    rows = run(interpret=interpret)
    interp_legs = run_interpreters(interpret=interpret)
    cache_legs = run_plan_cache()
    if args.json:
        legs = [{k: r[k] for k in ("name", "us_per_call", "backend",
                                   "interpret", "double_buffer",
                                   "jax_us_per_call", "mcells_per_s",
                                   "vmem_bytes",
                                   "vec_redundant_load_ratio",
                                   "vec_lane_occupancy",
                                   "vec_bytes_moved", "vec_bytes_needed",
                                   "vec_classes", "vec_diagnostics")}
                for r in rows]
        # environment stamp: perf numbers are only comparable across
        # PRs when the runtime that produced them is auditable
        import jaxlib
        import platform
        json.dump({"suite": "lifted",
                   "interpret": interpret,
                   "env": {"jax": jax.__version__,
                           "jaxlib": jaxlib.__version__,
                           "python": platform.python_version()},
                   "legs": legs,
                   "interpreters": interp_legs,
                   "plan_cache": cache_legs}, sys.stdout, indent=1)
        sys.stdout.write("\n")
        return
    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
    for leg in interp_legs:
        extra = (f";vs_untransformed={leg['vs_untransformed']:.2f}x"
                 if "vs_untransformed" in leg else "")
        print(f"{leg['name']},{leg['us_per_call']:.1f},"
              f"interpreter={leg['interpreter']};"
              f"Mcells_s={leg['mcells_per_s']:.0f};"
              f"vs_jax_emitter={leg['vs_jax_emitter']:.2f}x{extra}")
    for leg in cache_legs:
        print(f"{leg['name']},cold_plan_ms={leg['cold_plan_ms']:.2f},"
              f"warm_cache_ms={leg['warm_cache_ms']:.2f},"
              f"speedup={leg['speedup']:.1f}x")


if __name__ == "__main__":
    main()
