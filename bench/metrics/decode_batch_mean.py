"""Tokens decoded per decode step over the window: the mean number of
live slots a step serves (the engine's own ``stats`` counters)."""


def read(ctx):
    b, a = ctx["stats"]["before"], ctx["stats"]["after"]
    steps = a["decode_steps"] - b["decode_steps"]
    return (a["tokens"] - b["tokens"]) / steps if steps else None
