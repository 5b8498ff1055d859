"""Median host time of the engine's ``serve:decode_step`` spans in the
window: one batched decode step, ending after the sampled tokens reach
the host."""
import statistics


def read(ctx):
    d = [s.duration_s for s in ctx["spans"] if s.name == "serve:decode_step"]
    return 1e3 * statistics.median(d) if d else None
