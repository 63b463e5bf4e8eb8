"""The benchmark's cells cut to sizes that the CPU's Pallas interpreter
runs in seconds, for tests that drive a whole run off the chip."""
from bench.spec import REPO, Bench

#: Grid sizes per configuration: ragged lanes (not a multiple of 128)
#: and rows (not a multiple of 8) as at the real sizes.
SIZES = {"cosmo1_hdiff": {"Nk": 2, "Nj": 13, "Ni": 133},
         "heat3d_7pt": {"Nk": 5, "Nj": 11, "Ni": 130},
         "heat3d_7pt_256": {"Nk": 4, "Nj": 9, "Ni": 130}}


#: The open-loop serving cells, run by the tests whether or not
#: ``BENCHMARK.json`` lists them yet.
SERVING = {
    "configs": [{"name": "heat3d_7pt_256", "file": "bench/configs/heat3d_7pt_256.json"}],
    "workloads": [{"name": f"heat3d_7pt.{t}", "config": "heat3d_7pt_256", "traffic": t,
                   "chips": 1} for t in ("serve", "serve_sat")],
    "end_to_end": [{"name": "serve_p95_ms", "unit": "ms", "workloads": ["heat3d_7pt.serve"]},
                   {"name": "serve_rps", "unit": "req/s",
                    "workloads": ["heat3d_7pt.serve_sat"]}],
}


class SmallBench(Bench):
    """``BENCHMARK.json`` as committed plus :data:`SERVING`, with every grid
    cut to :data:`SIZES` and open-loop rates raised so a one-second window
    holds some requests."""

    def __init__(self, root=REPO):
        super().__init__(root)
        for key, entries in SERVING.items():
            known = {e["name"] for e in self.spec[key]}
            self.spec[key] += [e for e in entries if e["name"] not in known]

    def config(self, name):
        return dict(super().config(name), **SIZES[name])

    def traffic(self, name):
        t = super().traffic(name)
        return dict(t, rate_rps=20.0) if "rate_rps" in t else t


def small_batches(monkeypatch, max_batch: int = 4) -> None:
    """Cap PlanServe's default batch so set-up warms fewer batch widths."""
    from repro.serve.plans import PlanServe

    monkeypatch.setitem(PlanServe.__init__.__kwdefaults__, "max_batch", max_batch)


def run_small(cell: str, seed: int = 2**31 + 11, trace: bool = False, seconds: float = 0.5):
    """One whole run of ``cell`` off the chip; returns ``(result, notes)``."""
    import time

    from bench.run import run

    return run(cell, seed, seconds, trace, bench=SmallBench(), require_chip=False,
               t_start=time.perf_counter())
