"""Median host time per decode step during which the device has nothing
to run.

For each ``serve:decode_step`` span that begins in the window: its
``serve:step:prepare``, ``serve:step:launch`` and ``serve:step:commit``
phases (``serve:step:sync``, which waits for the device, is left out),
plus the ``serve:schedule`` span that ends last before the step begins,
less the ``serve:admit`` and ``serve:wait_arrival`` spans inside it.
Spans pair by time containment.  A program whose steps carry no phase
spans reads nothing.
"""
import bisect
import statistics

HOST_PHASES = ("serve:step:prepare", "serve:step:launch",
               "serve:step:commit")
NOT_HOST = ("serve:admit", "serve:wait_arrival")


def _named(spans, names):
    return sorted((s for s in spans if s.name in names), key=lambda s: s.t0)


def _inside(sorted_spans, starts, outer):
    """The spans of ``sorted_spans`` (by start, ``starts``) that lie
    within ``outer``."""
    i = bisect.bisect_left(starts, outer.t0)
    out = []
    while i < len(sorted_spans) and sorted_spans[i].t0 < outer.t1:
        if sorted_spans[i].t1 <= outer.t1:
            out.append(sorted_spans[i])
        i += 1
    return out


def read(ctx):
    t0, t1 = ctx["window"]
    spans = ctx["spans"]
    steps = [s for s in spans if s.name == "serve:decode_step"
             and t0 <= s.t0 < t1]
    phases = _named(spans, HOST_PHASES)
    phase_t0 = [s.t0 for s in phases]
    scheds = sorted((s for s in spans if s.name == "serve:schedule"),
                    key=lambda s: s.t1)
    sched_t1 = [s.t1 for s in scheds]
    nested = _named(spans, NOT_HOST)
    nested_t0 = [s.t0 for s in nested]
    host = []
    for step in steps:
        own = _inside(phases, phase_t0, step)
        if not own:
            continue
        secs = sum(s.duration_s for s in own)
        j = bisect.bisect_right(sched_t1, step.t0) - 1
        if j >= 0:
            sch = scheds[j]
            secs += sch.duration_s - sum(
                s.duration_s for s in _inside(nested, nested_t0, sch))
        host.append(secs)
    return 1e3 * statistics.median(host) if host else None
