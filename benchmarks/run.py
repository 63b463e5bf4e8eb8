"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV:
  normalization  — paper Fig. 12 (§5.2)
  cosmo          — paper Fig. 11 (§5.3)
  hydro          — paper Fig. 13 (§5.4)
  kernels        — HFAV contraction applied to LM hot paths (DESIGN.md §5)
  lifted         — one leg per lifted Pallas restriction (docs/BACKENDS.md)
"""
from __future__ import annotations

import sys


def main() -> None:
    from repro.jaxcache import enable_compile_cache

    from . import cosmo, hydro, kernels_bench, lifted, normalization

    enable_compile_cache()

    suites = [
        ("normalization", normalization.run),
        ("cosmo", cosmo.run),
        ("hydro", hydro.run),
        ("kernels", kernels_bench.run),
        ("lifted", lifted.run),
    ]
    only = sys.argv[1] if len(sys.argv) > 1 else None
    print("name,us_per_call,derived")
    for name, fn in suites:
        if only and only != name:
            continue
        for row in fn():
            print(f"{row['name']},{row['us_per_call']:.1f},{row['derived']}")


if __name__ == "__main__":
    main()
