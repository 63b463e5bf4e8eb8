"""Multi-process PlanServe workers sharing one on-disk plan cache.

Each worker is a spawned child process running its own
:class:`~repro.serve.plans.PlanServe` (its own jit caches, its own
batcher thread) and answering requests over a pipe.  All workers point
at the *same* ``cache_dir``: the first worker to plan a program
persists the :class:`~repro.core.plan.KernelPlan` through
:mod:`repro.core.plancache` (fcntl write locking keeps concurrent
fills/evictions sane), and every later worker — or a later cold start
of the whole pool — compiles warm, skipping the analysis pipeline.
This is the measured cold-vs-warm worker-start leg of
``benchmarks/serve.py``.

Programs cross the process boundary *by name* (resolved against
:data:`repro.core.programs.ALL_PROGRAMS` inside the child), because
kernel rule callables are not reliably picklable; the spawn context is
used unconditionally so workers never inherit a forked JAX runtime.

A TPU belongs to one process at a time, and a process that opens the
TPU backend takes every chip of its host.  So a worker is never started
from a process that holds the TPU, and a pool of more than one worker on
a TPU host is refused with an error instead of leaving the later
workers to fail or hang on the chip.
"""
from __future__ import annotations

import multiprocessing as mp
import os
import pathlib
import sys
from typing import Optional


def _ensure_child_pythonpath() -> None:
    """Make sure spawned children can ``import repro``: prepend this
    source tree's root to ``PYTHONPATH`` if it is not already on it
    (spawn re-imports modules from scratch and only inherits the
    environment, not the parent's ``sys.path`` mutations)."""
    src = str(pathlib.Path(__file__).resolve().parents[2])
    parts = os.environ.get("PYTHONPATH", "").split(os.pathsep)
    if src not in parts:
        os.environ["PYTHONPATH"] = os.pathsep.join(
            [src] + [p for p in parts if p])


def _holds_tpu() -> bool:
    """Whether this process has opened JAX's TPU backend (and with it
    every chip of the host).  A process that has not imported JAX, or
    has not initialized a backend, holds nothing."""
    if "jax" not in sys.modules:
        return False
    import jax
    from jax._src import xla_bridge
    return xla_bridge.backends_are_initialized() \
        and jax.default_backend() == "tpu"


def _worker_main(conn, program_names, backend, cache_dir, quantum,
                 max_batch, max_wait_ms) -> None:
    """Child entry point: build a PlanServe over the named programs and
    answer ``("serve", name, arrays)`` / ``("metrics",)`` / ``("stop",)``
    messages until stopped.  Every reply is a ``(tag, payload)`` pair;
    request failures reply ``("error", message)`` instead of killing
    the worker.  The ready message reports the pid and the devices the
    child opened."""
    import traceback

    import jax

    from repro.core.programs import ALL_PROGRAMS
    from repro.jaxcache import enable_compile_cache
    from repro.serve.plans import PlanServe
    try:
        enable_compile_cache()
        progs = {n: ALL_PROGRAMS[n]() for n in program_names}
        with PlanServe(progs, backend=backend, plan_cache_dir=cache_dir,
                       quantum=quantum, max_batch=max_batch,
                       max_wait_ms=max_wait_ms) as srv:
            conn.send(("ready", {"pid": os.getpid(),
                                 "platform": jax.default_backend(),
                                 "device_count": jax.device_count()}))
            while True:
                msg = conn.recv()
                if msg[0] == "stop":
                    conn.send(("stopped", srv.metrics.snapshot()))
                    return
                if msg[0] == "metrics":
                    conn.send(("metrics", srv.metrics.snapshot()))
                elif msg[0] == "serve":
                    _, name, arrays = msg
                    try:
                        conn.send(("ok", srv.serve(name, arrays,
                                                   timeout=300)))
                    except Exception as err:
                        conn.send(("error",
                                   f"{type(err).__name__}: {err}"))
                else:
                    conn.send(("error", f"unknown command {msg[0]!r}"))
    except Exception:
        conn.send(("fatal", traceback.format_exc()))


class ServeWorker:
    """One spawned serving process.  ``serve``/``metrics`` are
    synchronous request/reply over the pipe; ``close`` stops the child
    and returns its final metrics snapshot.  ``platform`` and
    ``device_count`` are what the child's JAX opened."""

    def __init__(self, program_names, *, backend: str = "interp_jax",
                 cache_dir=None, quantum: int = 32, max_batch: int = 16,
                 max_wait_ms: float = 2.0):
        if _holds_tpu():
            raise RuntimeError(
                "this process holds the TPU, so a spawned worker could "
                "not open it: start ServeWorkers before touching JAX in "
                "the parent, or serve in-process with PlanServe")
        _ensure_child_pythonpath()
        ctx = mp.get_context("spawn")
        self._conn, child = ctx.Pipe()
        self._proc = ctx.Process(
            target=_worker_main,
            args=(child, list(program_names), backend,
                  str(cache_dir) if cache_dir is not None else None,
                  quantum, max_batch, max_wait_ms),
            daemon=True)
        self._proc.start()
        child.close()
        tag, payload = self._conn.recv()
        if tag != "ready":
            raise RuntimeError(f"worker failed to start: {payload}")
        self.pid = payload["pid"]
        self.platform = payload["platform"]
        self.device_count = payload["device_count"]

    def _rpc(self, *msg):
        self._conn.send(msg)
        tag, payload = self._conn.recv()
        if tag in ("error", "fatal"):
            raise RuntimeError(payload)
        return payload

    def serve(self, name: str, arrays: dict) -> dict:
        """Run one request in the worker, returning ``{store: array}``."""
        return self._rpc("serve", name, arrays)

    def metrics(self) -> dict:
        """The worker's live :class:`~repro.serve.plans.ServeMetrics`
        snapshot."""
        return self._rpc("metrics")

    def close(self) -> Optional[dict]:
        """Stop the worker (idempotent) and return its final metrics
        snapshot (``None`` if it already died)."""
        if self._proc is None:
            return None
        snap = None
        try:
            snap = self._rpc("stop")
        except (RuntimeError, EOFError, OSError):
            pass
        self._proc.join(timeout=30)
        if self._proc.is_alive():
            self._proc.terminate()
            self._proc.join(timeout=10)
        self._conn.close()
        self._proc = None
        return snap

    def __enter__(self) -> "ServeWorker":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class WorkerPool:
    """``n`` ServeWorkers over one shared cache dir, with round-robin
    request dispatch.  ``close`` returns every worker's final metrics
    snapshot (the benchmark aggregates compile/disk-hit counts across
    the pool).

    The first worker starts alone.  If it opened a TPU, it holds every
    chip of the host, so a pool of more than one worker is refused with
    a ``RuntimeError`` before any other worker starts."""

    def __init__(self, n: int, program_names, **kwargs):
        if n < 1:
            raise ValueError(f"need at least one worker, got {n}")
        first = ServeWorker(program_names, **kwargs)
        if n > 1 and first.platform == "tpu":
            first.close()
            raise RuntimeError(
                f"WorkerPool(n={n}) on a TPU host: worker 0 holds all "
                f"{first.device_count} chip(s) of the host, so no other "
                f"worker could open one; run one worker per TPU host")
        self.workers = [first] + [ServeWorker(program_names, **kwargs)
                                  for _ in range(n - 1)]
        self._next = 0

    def serve(self, name: str, arrays: dict) -> dict:
        """Dispatch one request to the next worker (round-robin)."""
        w = self.workers[self._next % len(self.workers)]
        self._next += 1
        return w.serve(name, arrays)

    def metrics(self) -> list:
        """Live metrics snapshots, one per worker."""
        return [w.metrics() for w in self.workers]

    def close(self) -> list:
        """Stop every worker; returns their final metrics snapshots."""
        return [w.close() for w in self.workers]

    def __enter__(self) -> "WorkerPool":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
