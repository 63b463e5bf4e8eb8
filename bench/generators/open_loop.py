"""Open-loop serving through ``PlanServe``: independent clients each
submit one grid and wait for its answer.

Requests are due on the schedule of :func:`bench.openloop.schedule` at
``rate_rps``, whatever the server does; each is timed from when it was
due to when its ticket resolved.  Request arrays come from a seeded pool
of host numpy arrays, as users submit them.  A sample of the answers,
drawn from the seed before the window, is kept (copied out of the
batch) and compared with the reference once the window has closed.

Traffic parameters: ``rate_rps``, ``backend`` (PlanServe's), ``pool``
(distinct request arrays) and ``sample`` (answers compared).
"""
from __future__ import annotations

import queue
import threading
import time

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from bench import counts, openloop
from bench.generators import program_sizes
from bench.trace import WINDOW

#: Seconds past the window's close that the benchmark waits for answers.
DRAIN_SECONDS = 60.0


class Generator:
    def __init__(self, cell, seed: int, bench):
        from repro.core.programs import ALL_PROGRAMS

        self.seed = seed % (1 << 64)
        self.name = cell.config["program"]
        self.prog = ALL_PROGRAMS[self.name]()
        self.sizes = program_sizes(self.prog, cell.config)
        self.dtype = jnp.dtype(cell.config["dtype"])
        self.reference = bench.reference(self.name)
        t = cell.traffic
        self.rate, self.backend = float(t["rate_rps"]), t["backend"]
        self.n_pool, self.n_sample = int(t["pool"]), int(t["sample"])

    def make_inputs(self) -> list:
        """The seeded pool of host request arrays."""
        rng = np.random.default_rng([self.seed, 0])
        shapes = counts.input_shapes(self.prog, self.sizes)
        return [{n: rng.standard_normal(s, dtype=np.float32).astype(self.dtype, copy=False)
                 for n, s in sorted(shapes.items())} for _ in range(self.n_pool)]

    def setup(self) -> dict:
        from repro.serve.plans import PlanServe

        self.pool = self.make_inputs()
        self.srv = PlanServe({self.name: self.prog}, backend=self.backend)
        t0 = time.perf_counter()
        batch = 1
        while True:  # every batch-slot width the batcher can pick
            self.srv.prefill(self.name, self.sizes, batch=batch)
            if batch >= self.srv.max_batch:
                break
            batch = min(2 * batch, self.srv.max_batch)
        return {"compile_s": time.perf_counter() - t0}

    def window(self, seconds: float):
        due = openloop.schedule(self.rate, seconds, self.seed)
        n = len(due)
        rng = np.random.default_rng([self.seed, 1])
        self.which = rng.integers(self.n_pool, size=n)
        sample = set(rng.choice(n, size=min(self.n_sample, n), replace=False).tolist())
        done = np.full(n, np.nan)
        service_s = np.full(n, np.nan)
        submitted = np.full(n, np.nan)
        self.answers, self.stats, self.errors = {}, [], []
        tickets: queue.Queue = queue.Queue()

        def collect():
            while (item := tickets.get()) is not None:
                k, ticket = item
                try:
                    out = ticket.result()
                except Exception as err:  # a failed request counts as missing
                    self.errors.append(f"{type(err).__name__}: {err}")
                    continue
                done[k] = time.perf_counter()
                service_s[k] = (ticket.stats["latency_ms"] - ticket.stats["queue_wait_ms"]) / 1e3
                self.stats.append(ticket.stats)
                if k in sample:
                    self.answers[k] = {s: np.array(a) for s, a in out.items()}

        collector = threading.Thread(target=collect, name="bench-collector")
        collector.start()
        with TraceAnnotation(WINDOW):
            t0 = time.perf_counter()
            for k in range(n):
                delay = t0 + due[k] - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                with TraceAnnotation("bench.submit"):
                    ticket = self.srv.submit(self.name, self.pool[self.which[k]])
                submitted[k] = time.perf_counter()
                tickets.put((k, ticket))
            delay = t0 + seconds - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            t_end = time.perf_counter()
        tickets.put(None)
        with TraceAnnotation("bench.drain"):
            collector.join(DRAIN_SECONDS)
            gave_up = time.perf_counter()
            if collector.is_alive():  # fail what is still queued
                self.srv.close()
                collector.join()
        self.failed = int(np.sum(np.isnan(done)))
        self.late_ms = (submitted - (t0 + due)) * 1e3
        self.window_s = t_end - t0
        self.backlog = int(np.sum(~(done <= t_end)))
        lat = openloop.latencies_ms(t0 + due, done, gave_up)
        e2e = {"serve_p95_ms": openloop.percentile(lat, 95),
               "serve_rps": openloop.served(done, service_s, t_end) / self.window_s}
        self.latency_ms = lat
        return e2e, n, self.failed, {"stats": self.stats}

    def release(self) -> None:
        self.srv.close()

    def check(self) -> dict:
        from bench.check import rel_err, worst

        refs = {}
        errs = []
        for k, got in sorted(self.answers.items()):
            p = int(self.which[k])
            if p not in refs:
                refs[p] = self.reference({n: jnp.asarray(a) for n, a in self.pool[p].items()},
                                         self.dtype)
            errs.append(rel_err(got, refs[p]))
        return {"rel_err": worst(errs)}

    def notes(self, trace: bool) -> list:
        sizes = [s["batch_size"] for s in self.stats]
        lines = [f"offered_rps {self.rate} requests {len(self.which)} window_s {self.window_s}",
                 f"generator_late_ms p50 {np.percentile(self.late_ms, 50)} "
                 f"p95 {np.percentile(self.late_ms, 95)} max {np.max(self.late_ms)}",
                 f"latency_ms p50 {openloop.percentile(self.latency_ms, 50)} "
                 f"p95 {openloop.percentile(self.latency_ms, 95)} "
                 f"max {np.max(self.latency_ms)}",
                 f"unanswered_at_window_end {self.backlog}",
                 f"batch_size mean {np.mean(sizes) if sizes else 0} "
                 f"max {max(sizes, default=0)}"]
        lines += [f"request_error {e}" for e in self.errors[:3]]
        return lines

    def least_bytes(self) -> int:
        return counts.least_bytes(self.prog, self.sizes, self.dtype.itemsize)
