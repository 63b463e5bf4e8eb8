"""Smoke test of the HFAV stencil compiler on one TPU chip.

Drives the main path once through the entry points a user calls, at
deployment grid sizes, and checks every result against the plain
reference (one ``jax.jit`` of :func:`repro.core.unfused.build_unfused`):

* library path: ``compile_program(prog, backend="pallas")`` with no
  ``interpret`` argument, on COSMO's 80-level 1024x1024 grid, a 512^3
  heat3d, an 8192x8192 normalization, an 8192x8192 hydro1d and a
  4100x4100 laplace5.  Each jitted program must hold a Mosaic kernel
  (``tpu_custom_call``);
* serving path: a ``PlanServe(..., backend="pallas")`` answers a few
  requests each for laplace5 at 2048x2048 and heat3d at 256^3.

Run from the repository root, on a machine with a TPU::

    python chip_smoke.py

It prints the device first and one line per phase.  The times are one
warm call each, a smoke time and not a benchmark.  It exits non-zero
when JAX finds no TPU or any phase fails; on success the last line is
``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

SEED = 0
#: Tolerance on |got - ref| / (|ref| + mean|ref|), element by element.
#: The kernel bodies are the same functions as the reference's, so only
#: summation order and division rounding differ (~1e-6 in f32), while a
#: misplaced row or lane errs by the data's own magnitude.
RTOL = 1e-4

#: (program, {size symbol: int}) for the library path
LIBRARY = (
    ("cosmo", {"Nk": 80, "Nj": 1024, "Ni": 1024}),
    ("heat3d", {"Nk": 512, "Nj": 512, "Ni": 512}),
    ("normalization", {"Nj": 8192, "Ni": 8192}),
    ("hydro1d", {"Nj": 8192, "Ni": 8192}),
    # rows and lanes that are not multiples of the (8, 128) tile: the
    # last row block and lane tile are partial
    ("laplace5", {"Nj": 4100, "Ni": 4100}),
)

#: (program, {size symbol: int}, requests) for the serving path
SERVING = (
    ("laplace5", {"Nj": 2048, "Ni": 2048}, 4),
    ("heat3d", {"Nk": 256, "Nj": 256, "Ni": 256}, 4),
)


def _shapes(prog, sizes: dict) -> dict:
    """Input shapes from the program's axiom extents: ``size + hi - lo``
    along each dim."""
    shapes = {}
    for ax in prog.axioms:
        exts = [ax.extents[d.rstrip("?")] for d in ax.term.ref.dims]
        shapes[ax.term.ref.name] = tuple(sizes[e.size] + e.hi - e.lo
                                         for e in exts)
    return shapes


def _errors(got: dict, want: dict) -> tuple[float, float]:
    """(max|got - ref|, max |got - ref| / (|ref| + mean|ref|)) over
    every goal store; raises when the second exceeds :data:`RTOL`."""
    import numpy as np
    err = rel = 0.0
    for k, ref in want.items():
        g = np.asarray(got[k], np.float64)
        r = np.asarray(ref, np.float64)
        if g.shape != r.shape:
            raise AssertionError(f"{k}: shape {g.shape} != {r.shape}")
        d = np.abs(g - r)
        err = max(err, float(d.max()))
        rel = max(rel, float((d / (np.abs(r) + np.abs(r).mean())).max()))
    if not rel <= RTOL:
        raise AssertionError(f"relative error {rel!r} > tolerance {RTOL}")
    return err, rel


def library_phase(name: str, sizes: dict) -> str:
    import jax

    from repro import trace
    from repro.core import compile_program
    from repro.core.programs import ALL_PROGRAMS
    from repro.core.unfused import build_unfused

    prog = ALL_PROGRAMS[name]()
    gen = compile_program(prog, backend="pallas")
    shapes = _shapes(prog, sizes)
    keys = jax.random.split(jax.random.key(SEED), len(shapes))
    arrays = {n: jax.random.normal(k, s, jax.numpy.float32)
              for k, (n, s) in zip(keys, sorted(shapes.items()))}
    t0 = time.perf_counter()
    with trace.recording() as rec:
        compiled = jax.jit(lambda a: gen.fn(**a)).lower(arrays).compile()
    compile_s = time.perf_counter() - t0
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError("no tpu_custom_call in the compiled program")
    got = jax.block_until_ready(compiled(arrays))
    t0 = time.perf_counter()
    got = jax.block_until_ready(compiled(arrays))
    warm_s = time.perf_counter() - t0
    want = jax.block_until_ready(
        jax.jit(lambda a: build_unfused(prog).fn(**a))(arrays))
    err, rel = _errors(got, want)
    return (f"shape={shapes} grid_steps={rec.counters['hfav.grid_steps']}"
            f" row_tiles={[b.attrs['row_tile'] for b in rec.named('hfav.build_call')]}"
            f" compile_s={compile_s!r} smoke_warm_call_s={warm_s!r}"
            f" max_err={err!r} rel_err={rel!r} tol={RTOL}"
            f" tpu_custom_call=True")


def serving_phase(name: str, sizes: dict, n_requests: int) -> str:
    import jax
    import numpy as np

    from repro.core.programs import ALL_PROGRAMS
    from repro.core.unfused import build_unfused
    from repro.serve.plans import PlanServe

    prog = ALL_PROGRAMS[name]()
    shapes = _shapes(prog, sizes)
    rng = np.random.default_rng(SEED)
    requests = [{n: rng.standard_normal(s, dtype=np.float32)
                 for n, s in sorted(shapes.items())}
                for _ in range(n_requests)]
    ref = jax.jit(lambda a: build_unfused(prog).fn(**a))
    with PlanServe({name: prog}, backend="pallas",
                   max_batch=n_requests) as srv:
        t0 = time.perf_counter()
        srv.prefill(name, sizes, batch=n_requests)
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        tickets = [srv.submit(name, a) for a in requests]
        answers = [t.result(600) for t in tickets]
        wall_s = time.perf_counter() - t0
        snap = srv.metrics.snapshot()
    errs = [_errors(got, jax.device_get(ref(a)))
            for a, got in zip(requests, answers)]
    return (f"shape={shapes} requests={n_requests}"
            f" batch_sizes={sorted({t.stats['batch_size'] for t in tickets})}"
            f" compile_s={compile_s!r} smoke_wall_s={wall_s!r}"
            f" compiles={snap['compiles']['count']}"
            f" max_err={max(e for e, _ in errs)!r}"
            f" rel_err={max(r for _, r in errs)!r} tol={RTOL}")


def main() -> int:
    import jax

    from repro.jaxcache import enable_compile_cache

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device {json.dumps(device)}", flush=True)
    if device["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU; this check runs only on the "
              "chip", file=sys.stderr)
        return 2
    print(f"compile cache {enable_compile_cache()}", flush=True)
    phases = [(f"library {n}", library_phase, (n, s)) for n, s in LIBRARY]
    phases += [(f"serving {n}", serving_phase, (n, s, r))
               for n, s, r in SERVING]
    failed = []
    for label, fn, args in phases:
        try:
            print(f"ok   {label}: {fn(*args)}", flush=True)
        except Exception as err:  # every phase runs; failures are counted
            failed.append(label)
            print(f"FAIL {label}: {type(err).__name__}: {err}", flush=True)
    if failed:
        print(f"chip_smoke: {len(failed)} phase(s) failed: {failed}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
