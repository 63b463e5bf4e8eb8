"""Split one run's set-up into phases, on the program's own spans.

    python3 bench/setup_phases.py --workload <name> --seed <n> [--seconds <s>]

Runs cell ``name`` once as ``bench/run.py --trace 0`` does, inside a
:func:`repro.trace.recording` opened at the start of this process, and
names where its ``setup_s`` went.  ``bench/run.py`` and the sweep
generator carry no set-up spans of their own, so for the length of the
run the generator's set-up steps are wrapped in spans here:

* ``bench.setup.runtime``: process start until ``jax.devices()`` has
  returned and the persistent compilation cache is on;
* ``bench.setup.program``: the generator's construction (the program,
  its sizes, the reference);
* ``bench.setup.inputs``: the seeded field, made on the device;
* ``bench.setup.lower``: ``_chained``, tracing and lowering the sweep
  step, the program's ``hfav.build_call`` inside, and holding
  ``bench.setup.xla_compile`` (``Lowered.compile``: Mosaic and XLA, or
  a load from the persistent cache);
* ``bench.setup.warmup``: the output buffers and the paced sweeps.

``hfav.compile_program`` and its children are the program's own spans.
Prints a ``setup_span <name> <seconds>`` line per top-level span,
``setup_part <name> <seconds>`` per nested one, ``setup_covered_pct``
(top-level spans over ``setup_s``), ``counter <name> <n>`` per counter
(``jax.cache_hits`` and ``jax.cache_misses`` are the persistent cache's)
and last one JSON line: the run's result with ``phases`` added, which
holds ``runtime_start_s``, ``plan_s`` (``hfav.compile_program``) and
``xla_compile_s`` (``bench.setup.lower``).  Exits 2 without a TPU, as
``bench/run.py`` does.
"""
from __future__ import annotations

import time

T_START_NS = time.perf_counter_ns()

import argparse  # noqa: E402
import collections  # noqa: E402
import functools  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from contextlib import contextmanager  # noqa: E402
from pathlib import Path  # noqa: E402

#: The three phases a set-up metric would read, and the span each sums.
PHASES = {"runtime_start_s": "bench.setup.runtime",
          "plan_s": "hfav.compile_program",
          "xla_compile_s": "bench.setup.lower"}


@contextmanager
def _spans_around_setup():
    """Wrap the sweep generator's set-up steps in spans, and put them
    back after."""
    import jax.stages

    from bench.generators import sweep
    from repro.trace import span

    def spanned(fn, name):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner

    targets = [(sweep.Generator, "__init__", "bench.setup.program"),
               (sweep.Generator, "make_inputs", "bench.setup.inputs"),
               (sweep, "_chained", "bench.setup.lower"),
               (jax.stages.Lowered, "compile", "bench.setup.xla_compile"),
               (sweep, "_buffer", "bench.setup.warmup"),
               (sweep, "_pace", "bench.setup.warmup")]
    saved = [(owner, attr, getattr(owner, attr)) for owner, attr, _ in targets]
    for owner, attr, name in targets:
        setattr(owner, attr, spanned(getattr(owner, attr), name))
    try:
        yield
    finally:
        for owner, attr, fn in saved:
            setattr(owner, attr, fn)


def measure(name: str, seed: int, seconds: float, *, bench=None,
            require_chip: bool = True, t_start_ns: int = T_START_NS):
    """One recorded run of cell ``name``; returns ``(result, notes)``,
    the result carrying ``phases``."""
    import jax

    from bench.run import run
    from repro import trace

    with trace.recording() as rec:
        jax.devices()
        if require_chip:
            from repro.jaxcache import enable_compile_cache
            enable_compile_cache()
        rec.add("bench.setup.runtime", t_start_ns, time.perf_counter_ns())
        with _spans_around_setup():
            result, notes = run(name, seed, seconds, False, bench=bench,
                                require_chip=require_chip, t_start=t_start_ns / 1e9)
    setup_s = result["metrics"]["setup_s"]["value"]
    setup_end = t_start_ns + setup_s * 1e9
    roots = [s for s in rec.roots() if s.end_ns <= setup_end]
    top = collections.Counter()
    for s in roots:
        top[s.name] += s.seconds
    parts = {n: rec.total_s(n) for n in sorted({s.name for s in rec.spans if s.parent})}
    covered = 100.0 * sum(top.values()) / setup_s
    notes = notes + [f"setup_span {k} {v}" for k, v in top.items()]
    notes += [f"setup_part {k} {v}" for k, v in parts.items()]
    notes += [f"setup_covered_pct {covered}"]
    notes += [f"counter {k} {v}" for k, v in sorted(rec.counters.items())]
    phases = {metric: sum(s.seconds for s in roots if s.name == span_name)
              for metric, span_name in PHASES.items()}
    result["phases"] = dict(phases, setup_covered_pct=covered, spans=dict(top),
                            parts=parts, counters=dict(rec.counters))
    return result, notes


def main(argv=None) -> int:
    from bench.run import NoChip

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=1.0)
    args = p.parse_args(argv)
    try:
        result, notes = measure(args.workload, args.seed, args.seconds)
    except NoChip as err:
        print(f"bench: {err}", file=sys.stderr)
        return 2
    for line in notes:
        print(line, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    here = Path(__file__).resolve().parent
    repo = here.parent
    sys.path[:] = [str(repo), str(repo / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != here]
    sys.exit(main())
