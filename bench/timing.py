"""One measured window of the program's own ``ServeEngine.run``.

The engine is driven as it is: its admission, bucket ladder, paged KV
cache and batched decode.  The benchmark only hands each request an
output list of its own (``Stamps``), whose ``append`` records the host
time of every token.  Once the window's end has passed, the next append
raises ``WindowClosed``, which ends ``run`` without draining: requests
still running count as attempted and unfinished.  The same hook starts
and stops the profiler at fixed points of a traced run.

A request's first token never raises: it is appended inside admission,
where an exception would leave a slot active with no token to decode
from.  The window closes at the next decode step instead, which the
engine leaves consistent (each slot either took its token and advanced,
or did neither).  So ``run`` can be called again after the window: where
no request finished inside it, the engine serves on, unmeasured, until
one does (an answer that comes late is late, not wrong), so that the
correctness check has finished requests to compare.
"""
from __future__ import annotations

import dataclasses
import math
import time

import numpy as np


class WindowClosed(Exception):
    """The measured window ended while the engine was serving."""


class Window:
    """The window's clock, with actions due at fixed offsets into it."""

    def __init__(self, seconds: float,
                 actions: list[tuple[float, object]] | None = None):
        self.seconds = seconds
        self.t_start = math.inf
        self.t_end = math.inf
        self.t_stop = math.inf      # when serving is cut (t_end, or later)
        self._offsets = sorted(actions or [], key=lambda a: a[0])
        self._due: list[tuple[float, object]] = []

    def open(self) -> None:
        self.t_start = time.perf_counter()
        self.t_end = self.t_stop = self.t_start + self.seconds
        self._due = [(self.t_start + off, fn) for off, fn in self._offsets]

    def tick(self, now: float, first: bool) -> None:
        while self._due and now >= self._due[0][0]:
            self._due.pop(0)[1]()
        if now > self.t_stop and not first:
            raise WindowClosed


class Stamps(list):
    """A request's output tokens, with the host time of each in
    ``stamps``."""

    def __init__(self, window: Window):
        super().__init__()
        self.window = window
        self.stamps: list[float] = []

    def append(self, token) -> None:
        now = time.perf_counter()
        self.window.tick(now, first=not self.stamps)
        self.stamps.append(now)
        super().append(token)


@dataclasses.dataclass
class Served:
    """What one request of the window came to."""
    rid: int
    prompt: np.ndarray
    bucket: int
    t_due: float
    stamps: list[float]
    tokens: list[int]
    done: bool


def make_requests(prompts, outs, window: Window):
    from repro.launch.serve import Request

    return [Request(i, p, o, out=Stamps(window))
            for i, (p, o) in enumerate(zip(prompts, outs))]


# how long the engine may serve on after the window for a first finished
# request, in steps of DRAIN_STEP_S
DRAIN_S, DRAIN_STEP_S = 40.0, 2.0


def serve_window(eng, reqs, arrivals, window: Window) -> tuple[list[Served],
                                                                dict]:
    """Open the window and serve ``reqs`` through ``eng.run`` until the
    window closes (or everything is served), then on where nothing
    finished.  Returns each request's record and the engine's counters
    at the window's start and end."""
    before = _stats(eng)
    window.open()
    try:
        eng.run(reqs, {}, arrivals=arrivals)
    except WindowClosed:
        pass
    after = _stats(eng)
    drained = 0.0
    while not any(r.done for r in reqs) and drained < DRAIN_S:
        window.t_stop = time.perf_counter() + DRAIN_STEP_S
        drained += DRAIN_STEP_S
        try:
            eng.run([], {})
        except WindowClosed:
            pass
    # run() set each t_arrival: when the open loop made it due, or the
    # window's start for a batch
    served = [Served(r.rid, r.prompt, r.bucket, r.t_arrival,
                     list(r.out.stamps), list(r.out), r.done)
              for r in reqs]
    return served, {"before": before, "after": after, "drained_s": drained}


def _stats(eng) -> dict:
    return {k: eng.stats[k] for k in ("prefills", "decode_steps", "tokens",
                                      "replans")}
