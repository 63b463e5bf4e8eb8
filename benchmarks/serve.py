"""Load test for PlanServe: batched vs one-at-a-time throughput, and
cold vs warm worker start over a shared on-disk plan cache.

Two experiment families, each on at least two programs (laplace5 and
heat3d by default):

* **serial vs batched** — the same fixed-size request stream served by
  a ``max_batch=1`` engine one request at a time, then by a
  ``max_batch=16`` engine with every request submitted up front (the
  micro-batcher coalesces them).  Reported per leg: requests/s and
  p50/p99 request latency (ms).  Batching must win: one vmapped call
  amortizes dispatch and jit-call overhead that the serial loop pays
  per request.
* **cold vs warm worker start** — a spawned ServeWorker against an
  empty cache dir (plans from scratch, persists them) and a second
  worker against the now-warm dir (loads the serialized plan, skips
  the analysis pipeline).  Reported per leg: time to first result,
  compile wall-clock, disk-hit count, plus steady-state requests/s
  and p50/p99 once warm.

A TPU belongs to one process at a time, so this process never opens a
JAX backend: the in-process legs run in one spawned child, which exits
before the worker legs spawn theirs.

::

    PYTHONPATH=src python -m benchmarks.serve --json

The ``--json`` record (``{"suite": "serve", "serving": [...]}``) is
merged into ``BENCH_<pr>.json`` by ``scripts/bench.sh``; read the
trajectory with ``scripts/bench_trend.py``.
"""
from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import sys
import tempfile
import time

import numpy as np

#: (program name, request sizes) pairs the load test serves.
PROGRAMS = (
    ("laplace5", {"Nj": 48, "Ni": 128}),
    ("heat3d", {"Nk": 6, "Nj": 24, "Ni": 96}),
)


def _request_arrays(name: str, sizes: dict, rng) -> dict:
    """One request's input arrays (axiom shapes from extent contracts)."""
    from repro.core.programs import ALL_PROGRAMS
    prog = ALL_PROGRAMS[name]()
    arrays = {}
    for ax in prog.axioms:
        shape = []
        for d in ax.term.ref.dims:
            e = ax.extents[d[:-1] if d.endswith("?") else d]
            shape.append(sizes[e.size] + e.hi - e.lo)
        arrays[ax.term.ref.name] = rng.standard_normal(
            tuple(shape)).astype(np.float32)
    return arrays


def _latency_stats(lat_ms: list) -> dict:
    v = np.asarray(lat_ms, np.float64)
    return {"p50_ms": float(np.percentile(v, 50)),
            "p99_ms": float(np.percentile(v, 99))}


def _throughput_leg(name: str, sizes: dict, *, mode: str, n_requests: int,
                    backend: str) -> dict:
    """Serve ``n_requests`` fixed-size requests serially (max_batch=1,
    one at a time) or batched (max_batch=16, submit-all-then-wait) and
    report requests/s + latency percentiles."""
    from repro.core import clear_compile_cache
    from repro.core.programs import ALL_PROGRAMS
    from repro.serve.plans import PlanServe
    clear_compile_cache()
    rng = np.random.default_rng(11)
    requests = [_request_arrays(name, sizes, rng) for _ in range(n_requests)]
    max_batch = 16 if mode == "batched" else 1
    with PlanServe({name: ALL_PROGRAMS[name]()}, backend=backend,
                   max_batch=max_batch, max_wait_ms=2.0) as srv:
        srv.prefill(name, sizes, batch=max_batch)
        t0 = time.perf_counter()
        if mode == "batched":
            tickets = [srv.submit(name, a) for a in requests]
            for t in tickets:
                t.result(300)
        else:
            tickets = []
            for a in requests:
                t = srv.submit(name, a)
                t.result(300)
                tickets.append(t)
        wall = time.perf_counter() - t0
    lat = [t.stats["latency_ms"] for t in tickets]
    sizes_tag = "x".join(f"{k}{v}" for k, v in sorted(sizes.items()))
    return {"name": f"{name}@{sizes_tag}:{mode}", "program": name,
            "mode": mode, "backend": backend, "requests": n_requests,
            "requests_per_s": n_requests / wall,
            "batch_size_mean": float(np.mean(
                [t.stats["batch_size"] for t in tickets])),
            **_latency_stats(lat)}


def _worker_leg(name: str, sizes: dict, *, mode: str, cache_dir,
                n_requests: int, backend: str) -> dict:
    """Start one spawned worker against ``cache_dir`` (cold: empty;
    warm: pre-filled by the cold run), time the first result (includes
    the bucket compile), then a steady-state request run."""
    from repro.serve.workers import ServeWorker
    rng = np.random.default_rng(13)
    requests = [_request_arrays(name, sizes, rng)
                for _ in range(n_requests)]
    t0 = time.perf_counter()
    with ServeWorker([name], cache_dir=cache_dir, backend=backend,
                     max_wait_ms=1.0) as w:
        w.serve(name, requests[0])
        first_ms = (time.perf_counter() - t0) * 1e3
        t1 = time.perf_counter()
        for a in requests:
            w.serve(name, a)
        wall = time.perf_counter() - t1
        snap = w.metrics()
    sizes_tag = "x".join(f"{k}{v}" for k, v in sorted(sizes.items()))
    return {"name": f"{name}@{sizes_tag}:worker_{mode}", "program": name,
            "mode": mode, "backend": backend, "requests": n_requests,
            "first_result_ms": first_ms,
            "requests_per_s": n_requests / wall,
            "p50_ms": snap["latency_ms"]["p50"],
            "p99_ms": snap["latency_ms"]["p99"],
            "compile_ms": snap["compiles"]["total_ms"],
            "disk_hits": snap["compiles"]["disk_hits"]}


def _throughput_legs(n_requests: int, backend: str) -> list:
    """Serial and batched legs for every program, in this process."""
    from repro.jaxcache import enable_compile_cache
    enable_compile_cache()
    legs = []
    for name, sizes in PROGRAMS:
        serial = _throughput_leg(name, sizes, mode="serial",
                                 n_requests=n_requests, backend=backend)
        batched = _throughput_leg(name, sizes, mode="batched",
                                  n_requests=n_requests, backend=backend)
        batched["vs_serial"] = (batched["requests_per_s"]
                                / serial["requests_per_s"])
        legs += [serial, batched]
    return legs


def run(n_requests: int = 64, backend: str = "interp_jax") -> list:
    """All serving legs: serial/batched per program in one spawned
    child, then cold/warm worker starts per program over one shared
    cache dir each."""
    with mp.get_context("spawn").Pool(1) as pool:
        legs = pool.apply(_throughput_legs, (n_requests, backend))
        pool.close()
        pool.join()
    for name, sizes in PROGRAMS:
        with tempfile.TemporaryDirectory() as d:
            cold = _worker_leg(name, sizes, mode="cold", cache_dir=d,
                               n_requests=max(8, n_requests // 8),
                               backend=backend)
            warm = _worker_leg(name, sizes, mode="warm", cache_dir=d,
                               n_requests=max(8, n_requests // 8),
                               backend=backend)
            warm["first_result_speedup"] = (cold["first_result_ms"]
                                            / warm["first_result_ms"])
            legs += [cold, warm]
    return legs


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="PlanServe load test: batched vs serial, cold vs "
                    "warm worker start.")
    ap.add_argument("--json", action="store_true",
                    help="emit the BENCH record section on stdout")
    ap.add_argument("--requests", type=int, default=64,
                    help="requests per throughput leg (default 64)")
    ap.add_argument("--backend", default="interp_jax",
                    help="vmap-safe serving backend (default interp_jax)")
    args = ap.parse_args(argv)

    legs = run(n_requests=args.requests, backend=args.backend)
    if args.json:
        import platform

        import jax
        import jaxlib
        json.dump({"suite": "serve",
                   "env": {"jax": jax.__version__,
                           "jaxlib": jaxlib.__version__,
                           "python": platform.python_version()},
                   "serving": legs}, sys.stdout, indent=1)
        sys.stdout.write("\n")
        return
    for leg in legs:
        extra = ""
        if "vs_serial" in leg:
            extra = f",vs_serial={leg['vs_serial']:.2f}x"
        if "first_result_ms" in leg:
            extra = (f",first_result_ms={leg['first_result_ms']:.0f}"
                     f",disk_hits={leg['disk_hits']}")
        print(f"{leg['name']},rps={leg['requests_per_s']:.1f},"
              f"p50_ms={leg['p50_ms']:.2f},p99_ms={leg['p99_ms']:.2f}"
              f"{extra}")


if __name__ == "__main__":
    main()
