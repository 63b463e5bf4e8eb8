"""Back-to-back fused sweeps of one grid through ``compile_program``.

The program is compiled with its default options, as a user calls it,
and jitted once around its host half.  Sweeps run as a solver's time
loop runs them: each is dispatched as soon as the host can, and the host
waits only for the sweep ``ahead_s`` seconds of work behind the newest,
so a stall of the host shorter than that leaves the chip fed.  (The TPU
runtime itself holds at most 32 executions in flight and blocks a
dispatch beyond them, so there the queue is the lesser of the two.)  Each
sweep's output buffer is donated to the next sweep, which writes its
output there, so the queue holds one output however deep it is; each
sweep also returns one element of its output, a marker that is ready
when the sweep is done and that the host waits on.  When the window's
time is up nothing more is sent, every sweep sent is waited for, and the
clock is read after that wait.  Every sweep reads the same seeded field,
so every sweep's answer is the same and any of them can be checked.

Traffic parameters: ``ahead_s``, the seconds of sweeps dispatched ahead
of the one waited for (default :data:`AHEAD_S`).
"""
from __future__ import annotations

import collections
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from bench import counts
from bench.generators import program_sizes, random_arrays
from bench.trace import WINDOW

#: Sweeps timed for the XLA-fusion baseline printed by a traced run.
BASELINE_SECONDS = 2.0
#: Seconds of sweeps dispatched ahead of the one the host waits for.
AHEAD_S = 4.0
#: Sweeps timed in set-up to turn ``ahead_s`` into a number of sweeps.
PACE_SWEEPS = 3


def _chained(fn, inputs):
    """``fn(**inputs)`` compiled as one link of a chain:
    ``(inputs, previous output) -> (output, marker)``, the previous
    output donated to this one; and the shapes of the output."""
    def link(arrays, _previous):
        out = fn(**arrays)
        leaf = jax.tree.leaves(out)[0]
        return out, leaf[tuple(n // 2 for n in leaf.shape)]

    shapes = jax.eval_shape(lambda a: fn(**a), inputs)
    step = jax.jit(link, donate_argnums=1, keep_unused=True).lower(inputs, shapes).compile()
    return step, shapes


def _buffer(shapes):
    """A fresh output buffer of ``shapes`` for a chain to donate."""
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


def _pace(step, inputs, chain):
    """Seconds per sweep of ``PACE_SWEEPS`` back-to-back sweeps, and the
    chain's newest output."""
    t0 = time.perf_counter()
    for _ in range(PACE_SWEEPS):
        chain, mark = step(inputs, chain)
    jax.block_until_ready(mark)
    return (time.perf_counter() - t0) / PACE_SWEEPS, chain


def _run(step, inputs, chain, spare, seconds: float, ahead: int, keep: int, span: str):
    """Sweeps of ``step`` for ``seconds``, ``ahead`` sweeps dispatched
    beyond the one waited for; returns (sweeps, window seconds, the
    outputs of sweep ``keep`` and of the last sweep).  Sweep ``keep``'s
    output is kept out of the chain: the sweep after it writes to
    ``spare`` instead."""
    marks = collections.deque()
    kept = None
    n = 0
    with TraceAnnotation(span):
        t0 = time.perf_counter()
        while True:
            with TraceAnnotation("bench.dispatch"):
                out, mark = step(inputs, chain)
            if n == keep:
                kept, chain = out, spare
            else:
                chain = out
            n += 1
            marks.append(mark)
            if len(marks) > ahead:
                with TraceAnnotation("bench.block"):
                    jax.block_until_ready(marks.popleft())
            if time.perf_counter() - t0 >= seconds:
                break
        with TraceAnnotation("bench.block"):
            jax.block_until_ready(marks[-1])
        t1 = time.perf_counter()
    return n, t1 - t0, [o for o in (kept, out) if o is not None]


class Generator:
    def __init__(self, cell, seed: int, bench):
        from repro.core.programs import ALL_PROGRAMS

        self.seed = seed
        self.prog = ALL_PROGRAMS[cell.config["program"]]()
        self.sizes = program_sizes(self.prog, cell.config)
        self.dtype = jnp.dtype(cell.config["dtype"])
        self.reference = bench.reference(cell.config["program"])
        self.itemsize = self.dtype.itemsize
        self.ahead_s = float(cell.traffic.get("ahead_s", AHEAD_S))

    def make_inputs(self) -> list:
        """The one seeded field every sweep reads, made on the device."""
        return [random_arrays(counts.input_shapes(self.prog, self.sizes), self.dtype,
                              self.seed)]

    def setup(self) -> dict:
        from repro.core import compile_program

        self.inputs, = self.make_inputs()
        t0 = time.perf_counter()
        self.gen = compile_program(self.prog, dtype=self.dtype)
        self.step, shapes = _chained(self.gen.fn, self.inputs)
        compile_s = time.perf_counter() - t0
        self.spare = _buffer(shapes)
        self.chain = _buffer(shapes)
        for _ in range(2):
            sweep_s, self.chain = _pace(self.step, self.inputs, self.chain)
        self.ahead = max(1, math.ceil(self.ahead_s / sweep_s))
        return {"compile_s": compile_s}

    def window(self, seconds: float):
        keep = int(np.random.default_rng([self.seed % (1 << 64), 1]).integers(8))
        n, window_s, self.outputs = _run(self.step, self.inputs, self.chain, self.spare,
                                         seconds, self.ahead, keep, WINDOW)
        return {"sweep_ms": window_s * 1e3 / n}, n, 0, {"sweeps": n}

    def release(self) -> None:
        del self.step, self.chain, self.spare

    def check(self) -> dict:
        from bench.check import rel_err, worst

        want = self.reference(self.inputs, self.dtype)
        return {"rel_err": worst(rel_err(got, want) for got in self.outputs)}

    def notes(self, trace: bool) -> list:
        kplan = getattr(self.gen, "kernel_plan", None)
        lines = [f"grid_steps_per_sweep {counts.grid_steps(kplan, self.sizes) if kplan else 'none'}",
                 f"least_bytes_per_sweep {self.least_bytes()}",
                 f"sweeps_ahead {self.ahead} (ahead_s {self.ahead_s})"]
        if trace:
            from repro.core.unfused import build_unfused

            base = build_unfused(self.prog)
            fn, shapes = _chained(base.fn, self.inputs)
            sweep_s, chain = _pace(fn, self.inputs, _buffer(shapes))
            ahead = max(1, math.ceil(self.ahead_s / sweep_s))
            n, s, _ = _run(fn, self.inputs, chain, None, BASELINE_SECONDS, ahead, -1,
                           "bench.baseline")
            lines.append(f"xla_fusion_baseline_sweep_ms {s * 1e3 / n} over {n} sweeps "
                         f"(jax.jit of build_unfused, same inputs)")
        return lines

    def least_bytes(self) -> int:
        return counts.least_bytes(self.prog, self.sizes, self.itemsize)
