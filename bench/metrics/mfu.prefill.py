"""The whole prefill step's share of the chip's peak bf16 FLOP/s.

FLOPs: what each prefill in the traced stretch needs for its real prompt
tokens (bench/flops.py: every layer's matmuls, causal attention, the
head at one position).  Time: the device time of the prefill programs in
the trace (the engine's jitted ``step_fn``), bucket padding included.
Both are taken per prefill, as means over the stretch: the prefills whose
``serve:prefill`` span began in it, and the prefill programs the trace
holds.  The two counts may differ by one at the stretch's edges; more
than that and there is no reading.
"""
import bisect
import sys

from bench import devtrace, flops

PROGRAM = "step_fn"


def read(ctx):
    tr, (t0, t1) = ctx["trace"], ctx["traced"]
    if tr is None or ctx["peak"] is None:
        return None
    spans = [s for s in ctx["spans"] if s.name.startswith("serve:prefill:")
             and t0 <= s.t0 < t1]
    dev_s, n = devtrace.program_seconds(tr, PROGRAM)
    if not spans or not n or abs(n - len(spans)) > 1:
        print(f"mfu.prefill: {n} prefill programs in the trace, "
              f"{len(spans)} prefill spans in the stretch", file=sys.stderr)
        return None
    # each prefill's request is the one whose first token came next
    firsts = sorted((r.stamps[0], len(r.prompt)) for r in ctx["served"]
                    if r.stamps)
    keys = [f for f, _ in firsts]
    need = sum(flops.prefill_flops(
        ctx["spec"], firsts[bisect.bisect_left(keys, s.t1)][1])
        for s in spans)
    return 100.0 * (need / len(spans)) / (dev_s / n * ctx["peak"][
        "bf16_flops"])
