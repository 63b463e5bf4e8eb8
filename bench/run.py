"""Run one cell of ``BENCHMARK.json`` once, on the chip, and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (JAX start, inputs from the seed, plan and compile from the
persistent cache, warm-up of every shape the window uses) is timed from
the start of this process to the first timed call.  The window then runs
for ``--seconds``.  With ``--trace 0`` the result carries the cell's
end-to-end metrics; with ``--trace 1`` the window runs under the
profiler and the result carries the cell's per-layer metrics, each read
by ``bench/metrics/<metric>.py``.  After the window the program's state
is freed and its answers are compared with the plain reference of the
configuration (:mod:`bench.check`).

The last line of standard output is one JSON object; the numbers
compared, each beside its limit, close it (``checks``) and are also the
last lines of standard error.  Exits 2, printing no result, where JAX
finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parent.parent


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Context:
    """What a per-layer metric's reader may read."""

    setup: dict          # the generator's set-up timings, e.g. compile_s
    counters: dict       # the generator's counts from the window
    trace: object        # bench.trace.Summary of the traced window
    least_bytes: int     # the program's least HBM bytes per call
    peaks: dict          # the device's row of bench/peaks.json


class CompileCounter:
    """Counts JAX's tracing and compile events while ``armed``."""

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, _secs: float, **_kw) -> None:
        if self.armed and name.startswith("/jax/core/compile/"):
            self.count += 1


def _number(x):
    """``x`` as JSON can hold it: a non-finite reading becomes its name."""
    return x if isinstance(x, (int, float)) and math.isfinite(x) else str(x)


def run(name: str, seed: int, seconds: float, trace: bool, *, bench=None,
        require_chip: bool = True, t_start: float = T_START):
    """One run of cell ``name``; returns ``(result, notes)``.  Off the
    chip (``require_chip=False``, for tests) no device check, peaks or
    persistent cache apply."""
    import jax

    from bench import generators
    from bench import trace as tr
    from bench.check import verdict
    from bench.spec import Bench

    bench = bench or Bench()
    cell = bench.cell(name)
    devices = jax.devices()
    dev = devices[0]
    peaks = None
    if require_chip:
        if dev.platform != "tpu" or len(devices) < cell.chips:
            raise NoChip(f"cell {name!r} needs {cell.chips} TPU chip(s); JAX found "
                         f"{len(devices)} {dev.platform} device(s)")
        peaks = bench.peaks(dev.device_kind)
        from repro.jaxcache import enable_compile_cache
        enable_compile_cache()
    compiles = CompileCounter()
    generator = generators.load(cell.traffic["generator"])(cell, seed, bench)
    setup = generator.setup()
    setup_s = time.perf_counter() - t_start
    tmp = tempfile.TemporaryDirectory(prefix="bench_trace_") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tmp.name, profiler_options=opts)
    compiles.armed = True
    e2e, attempted, failed, counters = generator.window(seconds)
    compiles.armed = False
    if trace:
        jax.profiler.stop_trace()
    used = devices[:cell.chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used)
    generator.release()
    readings = dict(generator.check(), failed=failed)
    correct, checks = verdict(readings, dict(cell.config["limits"], failed=0))
    notes = generator.notes(trace) + [f"compiles_in_window {compiles.count}",
                                      f"peak_bytes_in_use {peak}"]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if trace:
        summary = tr.reduce(tr.load(tr.find_xplane(tmp.name)))
        tmp.cleanup()
        ctx = Context(setup=setup, counters=counters, trace=summary,
                      least_bytes=generator.least_bytes(), peaks=peaks)
        metrics = {}
        for m in cell.per_layer:
            value = bench.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result.update(metrics=metrics, device=device, breakdown=tr.breakdown(summary))
    else:
        values = dict(e2e, setup_s=setup_s)
        result.update(metrics={m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                               for m in cell.end_to_end}, device=device)
    result["checks"] = {k: {"value": _number(v["value"]), "limit": v["limit"]}
                        for k, v in checks.items()}
    return result, notes


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        result, notes = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as err:
        print(f"bench: {err}", file=sys.stderr)
        return 2
    for line in notes:
        print(line, flush=True)
    for k, v in result["checks"].items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    here = Path(__file__).resolve().parent
    sys.path[:] = [str(REPO), str(REPO / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != here]
    sys.exit(main())
