"""The paged-attention kernel's share of its roofline in the decode step.

For each decode step whose ``serve:decode_step`` span began inside the
traced stretch: the least time of its kernel calls (bench/paged.py), the
larger of their FLOPs over peak FLOP/s and their bytes over peak HBM
bandwidth, for the cached positions of the rows it served (token ``j >=
1`` of a request came from a step whose kernel read ``prompt + j - 1``
positions).  The mean of those, over the mean device self time of the
kernel's operations (``<paged_step program>:paged_attention.<n>``, one
per layer) per decode program in the trace.  The two counts may differ
by one at the stretch's edges; more and there is no reading.

The engine's counters say which path ran: no reading, with the reason
on standard error, where the program counts no kernel steps
(``serve_stats{stat=paged_kernel_steps}``), where some of its decode
steps took the jnp gather, or where the kernel is not among the
operations the reduced trace lists.
"""
import bisect
import sys

from bench import devtrace, flops, paged

PROGRAM = "paged_step"
KERNEL = "paged_attention"


def _why(msg):
    print(f"paged_attention_roofline: {msg}", file=sys.stderr)


def _counters():
    """The engine's counters as the metrics registry re-expresses them,
    or {} where the registry has none."""
    from repro import obs

    snap = obs.collect().get("serve_stats")
    return {lbl["stat"]: v for lbl, v in snap["samples"]} if snap else {}


def read(ctx):
    tr, (t0, t1) = ctx["trace"], ctx["traced"]
    if tr is None or ctx["peak"] is None:
        return None
    c = _counters()
    _why(f"engine counters {c}")
    steps = c.get("paged_kernel_steps")
    if not steps or steps != c.get("decode_steps"):
        _why(f"{steps} of {c.get('decode_steps')} decode steps ran the "
             "kernel")
        return None
    ops = [s for name, s in tr["device_ops"]
           if PROGRAM in name.split(":")[0]
           and name.split(":", 1)[-1].startswith(KERNEL)]
    spans = [s for s in ctx["spans"] if s.name == "serve:decode_step"
             and t0 <= s.t0 < t1]
    _, n = devtrace.program_seconds(tr, PROGRAM)
    if not ops or not spans or not n or abs(n - len(spans)) > 1:
        _why(f"{len(ops)} kernel operations among the trace's listed "
             f"operations, {n} decode programs, {len(spans)} decode spans")
        return None
    toks = sorted((t, len(r.prompt) + j - 1) for r in ctx["served"]
                  for j, t in enumerate(r.stamps) if j >= 1)
    keys = [t for t, _ in toks]
    least = 0.0
    for s in spans:
        cached = [p for _, p in toks[bisect.bisect_left(keys, s.t0):
                                     bisect.bisect_right(keys, s.t1)]]
        if cached:
            least += flops.least_time_s(
                *paged.decode_step(ctx["spec"], sum(cached), len(cached)),
                ctx["peak"])[0]
    return 100.0 * (least / len(spans)) / (sum(ops) / n)
