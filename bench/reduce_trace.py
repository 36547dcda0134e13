"""Reduction of a JAX profiler trace to the benchmark's device numbers.

A trace is read into flat :class:`Event` records (plane, line, name,
start, duration, stats); everything else works on those, so the tests
can build traces by hand.  Device operations are the events on the
``XLA Ops`` line of each ``/device:TPU:<n>`` plane; host spans are the
benchmark's own ``jax.profiler.TraceAnnotation`` events on the host
plane.  Times are nanoseconds on the trace's clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
#: the benchmark's span around the measured window
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: tuple = ()          # ((key, value), ...) as the trace gives them

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def op(self) -> str:
        """The HLO instruction's name without its ``%`` and numeric
        suffix: a device operation's event is named by the instruction's
        text, ``%backproject_pallas.2 = f32[...] custom-call(...), ...``."""
        return re.sub(r"\.\d+$", "", self.name.split(" = ", 1)[0].lstrip("%"))

    def is_kernel(self, name: str) -> bool:
        """Whether this is a Mosaic kernel's custom call named ``name``
        (the program's ``pallas_call`` wrapper)."""
        return (self.op == name
                and 'custom_call_target="tpu_custom_call"' in self.name)


def find_xspace(log_dir: str) -> str:
    """The ``.xplane.pb`` file a ``jax.profiler`` trace wrote under
    ``log_dir``; raises when there is none or more than one."""
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def load_events(path: str) -> list[Event]:
    """Every event of the device planes and the host plane."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    events = []
    for plane in data.planes:
        if not (DEVICE_PLANE.match(plane.name) or plane.name == HOST_PLANE):
            continue
        for line in plane.lines:
            for ev in line.events:
                events.append(Event(plane.name, line.name, ev.name,
                                    ev.start_ns, ev.duration_ns,
                                    tuple(ev.stats)))
    return events


def window(events: list[Event]) -> tuple[float, float]:
    """(start, end) of the benchmark's window span."""
    spans = [e for e in events if e.plane == HOST_PLANE
             and e.name == WINDOW_SPAN]
    if len(spans) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN!r} span in the "
                           f"trace, found {len(spans)}")
    return spans[0].start_ns, spans[0].end_ns


def device_ops(events: list[Event]) -> dict[str, list[Event]]:
    """Device plane name -> its operations, by start time."""
    out: dict[str, list[Event]] = {}
    for e in events:
        if DEVICE_PLANE.match(e.plane) and e.line == OPS_LINE:
            out.setdefault(e.plane, []).append(e)
    for ops in out.values():
        ops.sort(key=lambda e: e.start_ns)
    return out


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of (start, end) intervals inside [lo, hi]."""
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def union(intervals) -> list[tuple[float, float]]:
    """Disjoint, sorted union of (start, end) intervals."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi] that no interval of the union ``busy``
    covers."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def busy_ns(events: list[Event], lo: float, hi: float) -> float:
    """Mean over the devices of the time inside [lo, hi] in which some
    operation ran."""
    per = [sum(e - s for s, e in union(clip(((o.start_ns, o.end_ns)
                                             for o in ops), lo, hi)))
           for ops in device_ops(events).values()]
    if not per:
        raise RuntimeError("the trace holds no device operation")
    return sum(per) / len(per)


def op_seconds(events: list[Event], lo: float, hi: float,
               kernel: str | None = None) -> float:
    """Summed duration inside [lo, hi], over every device, of the
    device operations, or of the Mosaic kernel ``kernel``'s calls."""
    return sum(e - s for ops in device_ops(events).values()
               for s, e in clip(((o.start_ns, o.end_ns) for o in ops
                                 if kernel is None or o.is_kernel(kernel)),
                                lo, hi)) * 1e-9


def kernel_calls(events: list[Event], lo: float, hi: float,
                 kernel: str) -> int:
    """Calls of the Mosaic kernel ``kernel`` wholly inside [lo, hi]."""
    return sum(1 for ops in device_ops(events).values() for o in ops
               if o.is_kernel(kernel) and lo <= o.start_ns and o.end_ns <= hi)


def top_ops(events: list[Event], lo: float, hi: float, n: int = 10
            ) -> list[list]:
    """[[name, seconds], ...]: the device operations (summed by
    instruction name, :attr:`Event.op`) that took the most time inside
    [lo, hi]."""
    total: dict[str, float] = {}
    for ops in device_ops(events).values():
        for o in ops:
            for s, e in clip([(o.start_ns, o.end_ns)], lo, hi):
                total[o.op] = total.get(o.op, 0.0) + (e - s) * 1e-9
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])
            [:n]]


def idle_by_host_span(events: list[Event], lo: float, hi: float,
                      n: int = 10) -> list[list]:
    """[[span, seconds], ...]: device idle time inside [lo, hi], summed
    by the innermost benchmark span open on the host at the time
    ("none" where none was open), on the first device's timeline."""
    planes = device_ops(events)
    if not planes:
        raise RuntimeError("the trace holds no device operation")
    first = planes[sorted(planes)[0]]
    idle = gaps(union(clip(((o.start_ns, o.end_ns) for o in first),
                           lo, hi)), lo, hi)
    spans = [(e.start_ns, e.end_ns, e.name) for e in events
             if e.plane == HOST_PLANE and e.name != WINDOW_SPAN
             and e.name.startswith(("bench.", "client."))]
    total: dict[str, float] = {}
    for gs, ge in idle:
        cuts = sorted({gs, ge} | {t for s, e, _ in spans for t in (s, e)
                                   if gs < t < ge})
        for a, b in zip(cuts, cuts[1:]):
            open_ = [(s, name) for s, e, name in spans if s <= a and e >= b]
            name = max(open_)[1] if open_ else "none"
            total[name] = total.get(name, 0.0) + (b - a) * 1e-9
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])
            [:n]]
