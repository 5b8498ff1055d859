"""The whole decode step's share of the chip's roofline.

For each decode step whose ``serve:decode_step`` span began inside the
traced stretch: the least time the step could take, the larger of its
FLOPs over peak FLOP/s and its bytes over peak HBM bandwidth
(bench/flops.py: every weight read once, each live slot's keys and values
read once).  The mean of those, over the mean device time of the decode
programs in the trace (the engine's jitted ``paged_step``); the two
counts may differ by one at the stretch's edges, more and there is no
reading.  Decode is bound by bandwidth, so this is a roofline share; it
is the whole step's share of the chip's peak, hence ``mfu``.
"""
import bisect
import sys

from bench import devtrace, flops

PROGRAM = "paged_step"


def read(ctx):
    tr, (t0, t1) = ctx["trace"], ctx["traced"]
    if tr is None or ctx["peak"] is None:
        return None
    spans = [s for s in ctx["spans"] if s.name == "serve:decode_step"
             and t0 <= s.t0 < t1]
    dev_s, n = devtrace.program_seconds(tr, PROGRAM)
    if not spans or not n or abs(n - len(spans)) > 1:
        print(f"mfu.decode: {n} decode programs in the trace, "
              f"{len(spans)} decode spans in the stretch", file=sys.stderr)
        return None
    # token j >= 1 of a request came from a step that attended over
    # prompt + j positions
    toks = sorted((t, len(r.prompt) + j) for r in ctx["served"]
                  for j, t in enumerate(r.stamps) if j >= 1)
    keys = [t for t, _ in toks]
    least = 0.0
    for s in spans:
        lo = bisect.bisect_left(keys, s.t0)
        hi = bisect.bisect_right(keys, s.t1)
        ctxs = [c for _, c in toks[lo:hi]]
        if ctxs:
            least += flops.least_time_s(*flops.decode_step(ctx["spec"], ctxs),
                                        ctx["peak"])[0]
    return 100.0 * (least / len(spans)) / (dev_s / n)
