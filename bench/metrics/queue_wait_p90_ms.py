"""90th percentile of the engine's ``serve:queue`` spans that end in the
window: each admitted request's wait from when it was due to the start
of its admission.  A program that records no such span reads nothing."""
from bench import latency


def read(ctx):
    t0, t1 = ctx["window"]
    d = [s.duration_s for s in ctx["spans"]
         if s.name == "serve:queue" and t0 <= s.t1 <= t1]
    return 1e3 * latency.percentile(d, 90) if d else None
