"""Find the knee of an open-loop serving cell: the highest offered rate
the server sustains without a growing queue.

    python3 bench/knee.py --workload heat3d_7pt.serve --seconds 20 --rates 5 6 7 8 9 10

Runs the cell's own generator once per rate, in one process on one chip,
and prints per rate the offered and completed requests per second, the
requests still unanswered when the window closed, the latency tail and
the mean batch.  A rate is sustained when the window completes at least
95 % of what it offered and leaves at most two batches unanswered.  The
traffic files of the serving cells hold rates set from one such sweep
(``PERF.md``); the benchmark's own runs never search for a rate.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
from pathlib import Path


def sweep_rates(name: str, rates, seconds: float, seed: int) -> list:
    import numpy as np

    from bench import generators, openloop
    from bench.spec import Bench
    from repro.jaxcache import enable_compile_cache

    enable_compile_cache()
    bench = Bench()
    cell = bench.cell(name)
    rows = []
    for rate in rates:
        c = dataclasses.replace(cell, traffic=dict(cell.traffic, rate_rps=rate))
        generator = generators.load(c.traffic["generator"])(c, seed, bench)
        generator.setup()
        e2e, attempted, failed, counters = generator.window(seconds)
        generator.release()
        sizes = [s["batch_size"] for s in counters["stats"]]
        rows.append({"offered_rps": rate, "completed_rps": e2e["serve_rps"],
                     "unanswered_at_end": generator.backlog, "failed": failed,
                     "p50_ms": openloop.percentile(generator.latency_ms, 50),
                     "p95_ms": e2e["serve_p95_ms"],
                     "mean_batch": float(np.mean(sizes)) if sizes else 0.0,
                     "sustained": e2e["serve_rps"] >= 0.95 * rate
                     and generator.backlog <= 2 * generator.srv.max_batch})
        print(rows[-1], flush=True)
    return rows


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)
    rows = sweep_rates(args.workload, args.rates, args.seconds, args.seed)
    ok = [r["offered_rps"] for r in rows if r["sustained"]]
    print(f"knee {max(ok) if ok else 'below the lowest rate'} req/s", flush=True)
    return 0


if __name__ == "__main__":
    here = Path(__file__).resolve().parent
    sys.path[:] = [str(here.parent), str(here.parent / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != here]
    sys.exit(main())
