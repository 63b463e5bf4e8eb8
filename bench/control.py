"""The control of a cell's comparison: its plain reference computed one
precision lower (bfloat16 for a float32 configuration) put in the
program's place, on the inputs the cell makes from each seed.

    python3 bench/control.py --workload <name> --seeds 1 2 3

Prints, per seed, the ``rel_err`` that :mod:`bench.check` would read
for the control, beside the configuration's limit; a sound limit lies
below every one of them.  Not run by the benchmark's own runs.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

#: The precision one step below each configuration dtype.
LOWER = {"float32": "bfloat16"}


def control_errors(name: str, seeds, bench=None) -> list:
    """``[(seed, rel_err of the lower-precision reference)]``."""
    import jax.numpy as jnp

    from bench import generators
    from bench.check import rel_err, worst
    from bench.spec import Bench

    bench = bench or Bench()
    cell = bench.cell(name)
    ref = bench.reference(cell.config["program"])
    dtype = jnp.dtype(cell.config["dtype"])
    lower = jnp.dtype(LOWER[dtype.name])
    out = []
    for seed in seeds:
        generator = generators.load(cell.traffic["generator"])(cell, seed, bench)
        errs = []
        for inputs in generator.make_inputs():
            inputs = {k: jnp.asarray(v) for k, v in inputs.items()}
            errs.append(rel_err(ref(inputs, lower), ref(inputs, dtype)))
        out.append((seed, worst(errs)))
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    from bench.spec import Bench

    bench = Bench()
    limit = bench.cell(args.workload).config["limits"]["rel_err"]
    for seed, err in control_errors(args.workload, args.seeds, bench):
        print(f"control {args.workload} seed {seed} rel_err {err!r} limit {limit}", flush=True)
    return 0


if __name__ == "__main__":
    here = Path(__file__).resolve().parent
    sys.path[:] = [str(here.parent), str(here.parent / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != here]
    sys.exit(main())
