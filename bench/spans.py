"""The program's own spans in a traced run.

The program mirrors each live span of its traces (``repro.obs.trace``)
into JAX's profiler, on the device trace's clock, from whichever thread
opened it.  Such an event sits on the host plane, on its thread's line,
and carries the stats ``trace_id``, ``span_id`` and ``parent_id``
besides the span's scalar attrs; the benchmark's own annotations
(``bench.*``, ``client.*``) carry none.  Times are nanoseconds on the
trace's clock.  A reader returns None where the run holds no span of
the name: an untraced run, or a program that does not record it.
"""
from __future__ import annotations

import reduce_trace


def stat(event, key: str):
    """The value of one of ``event``'s stats, or None."""
    return dict(event.stats).get(key)


def program_spans(run) -> list:
    """The program's spans, from every host line, that overlap the
    traced window."""
    if run.events is None:
        return []
    lo, hi = run.trace_window
    return [e for e in run.events if e.plane == reduce_trace.HOST_PLANE
            and stat(e, "trace_id") is not None
            and e.end_ns > lo and e.start_ns < hi]


def _matcher(name):
    """A predicate over span names: ``name`` itself when callable, else
    equality with it."""
    return name if callable(name) else (lambda n: n == name)


def self_ns(span, spans, lo: float, hi: float) -> float:
    """The part of ``span`` inside [lo, hi] that none of its child spans
    (those of ``spans`` whose ``parent_id`` is its ``span_id``) covers."""
    (a, b), = reduce_trace.clip([(span.start_ns, span.end_ns)], lo, hi)
    sid = stat(span, "span_id")
    children = reduce_trace.clip(
        ((s.start_ns, s.end_ns) for s in spans
         if stat(s, "parent_id") == sid), a, b)
    return (b - a) - sum(e - s for s, e in reduce_trace.union(children))


def per_request(run, name, *, self_time: bool = False) -> float | None:
    """Seconds a completed request spent in the spans named ``name`` (or
    whose name passes ``name``, a predicate), clipped to the window, or
    with ``self_time`` in their self time; None when there are none."""
    spans = program_spans(run)
    match = _matcher(name)
    chosen = [s for s in spans if match(s.name)]
    if not chosen or not run.done:
        return None
    lo, hi = run.trace_window
    if self_time:
        total = sum(self_ns(s, spans, lo, hi) for s in chosen)
    else:
        total = sum(e - s for s, e in reduce_trace.clip(
            ((s.start_ns, s.end_ns) for s in chosen), lo, hi))
    return total * 1e-9 / len(run.done)


def stat_sum(run, name, key: str) -> float | None:
    """The sum of the stat ``key`` over the spans named ``name`` (or
    whose name passes it) that overlap the window; None when there are
    none."""
    match = _matcher(name)
    values = [stat(s, key) for s in program_spans(run) if match(s.name)]
    return sum(values) if values else None


def idle_by_span(run, n: int = 10) -> list[list]:
    """[[span, seconds], ...]: device idle time inside the window,
    summed by the innermost program span open on the host at the time
    (the latest started, on any thread; "none" where none was open), on
    the first device's timeline."""
    lo, hi = run.trace_window
    planes = reduce_trace.device_ops(run.events)
    first = planes[sorted(planes)[0]]
    idle = reduce_trace.gaps(reduce_trace.union(reduce_trace.clip(
        ((o.start_ns, o.end_ns) for o in first), lo, hi)), lo, hi)
    spans = [(s.start_ns, s.end_ns, s.name) for s in program_spans(run)]
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
