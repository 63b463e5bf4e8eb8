"""Reduce a profiler trace to device busy time, kernel time and idle gaps.

The JAX profiler writes an XSpace (``*.xplane.pb``): one plane per
device and one for the host, each with lines of timed events on one
clock.  On a TPU the device plane ``/device:TPU:<n>`` holds a line
``XLA Ops`` with one event per operation that ran, named by the
operation's HLO text (``%hfav_cosmo_n0.1 = f32[...] custom-call(...)``);
:func:`op_name` keeps the instruction's name.  The benchmark's own
spans (``jax.profiler.TraceAnnotation``) sit on the host plane; the
span :data:`WINDOW` marks the measured window.
"""
from __future__ import annotations

import collections
import gzip
from dataclasses import dataclass, field
from pathlib import Path

#: The benchmark's span around the measured window.
WINDOW = "bench.window"
#: Prefix of the benchmark's own host spans (dispatch, block, submit, ...).
SPAN_PREFIX = "bench."
#: The device line whose events are the operations that ran.
OPS_LINE = "XLA Ops"
#: What an operation's name holds where it is a stencil kernel: they are
#: named ``hfav_<call>``, and ``vmap_hfav_<call>`` when batched.
KERNEL = "hfav_"
#: Entries of each list of a ``breakdown``.
TOP = 10


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Summary:
    """A traced window, reduced.  Times in seconds; device times are
    averaged over the device planes."""

    window_s: float
    busy_s: float
    kernel_s: float
    glue_s: float
    devices: int
    ops: dict = field(default_factory=dict)   # op name -> seconds
    gaps: list = field(default_factory=list)  # [(host activity, seconds)], longest first

    def idle_pct(self):
        """Percent of the window in which no operation ran on the device;
        None where the trace holds no device operation."""
        if self.busy_s <= 0 or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)


def find_xplane(log_dir) -> Path:
    """The one ``*.xplane.pb`` that a trace into ``log_dir`` wrote."""
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if len(found) != 1:
        raise ValueError(f"expected one xplane.pb under {log_dir}, found {found}")
    return found[0]


def load(path) -> list:
    """Every event of an ``.xplane.pb`` (or ``.xplane.pb.gz``) file."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.suffix == ".gz":
        data = ProfileData.from_serialized_xspace(gzip.decompress(path.read_bytes()))
    else:
        data = ProfileData.from_file(str(path))
    return [Event(pl.name, ln.name, ev.name, float(ev.start_ns), float(ev.duration_ns))
            for pl in data.planes for ln in pl.lines for ev in ln.events]


def op_name(event_name: str) -> str:
    """``hfav_cosmo_n0.1`` of ``%hfav_cosmo_n0.1 = f32[...] custom-call(...)``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _union(intervals) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _is_device(plane: str) -> bool:
    return plane.startswith("/device:") and not plane.startswith("/device:CUSTOM")


def reduce(events) -> Summary:
    """Busy union, kernel and glue time of the device operations inside
    the :data:`WINDOW` span, and the :data:`TOP` longest idle gaps, each
    named by the host event that covers most of it.  An operation whose name holds
    :data:`KERNEL` is kernel time; every other operation is glue."""
    spans = [e for e in events if e.name == WINDOW and not _is_device(e.plane)]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW!r} span in the trace, found {len(spans)}")
    w0, w1 = spans[0].start_ns, spans[0].end_ns
    per_plane = collections.defaultdict(list)
    for e in events:
        if _is_device(e.plane) and e.line == OPS_LINE and e.end_ns > w0 and e.start_ns < w1:
            per_plane[e.plane].append((max(e.start_ns, w0), min(e.end_ns, w1),
                                       op_name(e.name)))
    n_dev = max(1, len(per_plane))
    busy = kernel_ns = glue_ns = 0.0
    ops = collections.Counter()
    gaps = []
    for plane_ops in per_plane.values():
        merged = _union((s, e) for s, e, _ in plane_ops)
        busy += sum(e - s for s, e in merged)
        for s, e, name in plane_ops:
            ops[name] += (e - s) / n_dev
            if KERNEL in name:
                kernel_ns += e - s
            else:
                glue_ns += e - s
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    host = [e for e in events if not _is_device(e.plane) and e.name != WINDOW
            and e.dur_ns > 0]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [(_host_activity(host, s, e), (e - s) / 1e9) for s, e in gaps[:TOP]]
    return Summary(window_s=(w1 - w0) / 1e9, busy_s=busy / n_dev / 1e9,
                   kernel_s=kernel_ns / n_dev / 1e9, glue_s=glue_ns / n_dev / 1e9,
                   devices=len(per_plane),
                   ops={k: v / 1e9 for k, v in ops.most_common()}, gaps=named)


def _host_activity(host, s: float, e: float) -> str:
    """What the host did during ``[s, e)``: the host event that overlaps
    it most, the benchmark's own span by its name and any other event as
    ``host:<name>``; ``"unattributed"`` where none does."""
    best, name = 0.0, "unattributed"
    for ev in host:
        o = min(e, ev.end_ns) - max(s, ev.start_ns)
        if o > best:
            best = o
            name = ev.name if ev.name.startswith(SPAN_PREFIX) else f"host:{ev.name}"
    return name


def breakdown(summary: Summary) -> dict:
    """The ``breakdown`` of a result line: the device operations that
    took most time and the longest idle gaps, in seconds."""
    return {"device_ops": [[k, v] for k, v in list(summary.ops.items())[:TOP]],
            "idle_gaps": [[k, v] for k, v in summary.gaps[:TOP]]}
