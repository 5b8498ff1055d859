"""Median host time of the engine's ``serve:admit`` spans in the window:
a request's prefill, its page write and the first token's readback."""
import statistics


def read(ctx):
    d = [s.duration_s for s in ctx["spans"] if s.name == "serve:admit"]
    return 1e3 * statistics.median(d) if d else None
