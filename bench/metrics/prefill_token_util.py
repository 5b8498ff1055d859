"""Real prompt tokens over bucket tokens, summed over the window's
admissions: the share of prefill work the bucket ladder does not pad."""


def read(ctx):
    end = ctx["window"][1]
    adm = [r for r in ctx["served"]
           if r.stamps and r.stamps[0] <= end and r.bucket]
    bucket = sum(r.bucket for r in adm)
    return 100.0 * sum(len(r.prompt) for r in adm) / bucket if bucket \
        else None
