"""End-to-end arithmetic over the host's per-token timestamps.

Each request carries ``t_due`` (when the open loop made it due; t=0 of
the window for a batch) and ``stamps``, the host time of each output
token, taken when the engine appended it.  All three metrics are taken
over every request and every gap of the window, never as medians of
chunks:

* TTFT: first stamp minus due time; a request due in the window with no
  first token by its end enters with its wait so far (censored at the
  end), so a growing queue raises the tail instead of hiding it.
* ITL: every gap between consecutive stamps of one request, pooled; the
  95th and 99th percentiles.
* output rate: every token stamped inside the window over its length.
"""
from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (linear interpolation between ranks)."""
    v = np.asarray(values, dtype=np.float64)
    if v.size == 0:
        raise ValueError("percentile of no values")
    return float(np.percentile(v, q))


def ttfts(reqs, t_end: float) -> list[float]:
    """Seconds from due to first token, for each request due by
    ``t_end``; censored at ``t_end`` where no token came."""
    out = []
    for r in reqs:
        if r.t_due > t_end:
            continue
        first = r.stamps[0] if r.stamps else t_end
        out.append(min(first, t_end) - r.t_due)
    return out


def itls(reqs, t_end: float = np.inf) -> list[float]:
    """Every gap between consecutive output tokens of one request, both
    stamped by ``t_end``."""
    gaps: list[float] = []
    for r in reqs:
        s = np.asarray(r.stamps, dtype=np.float64)
        gaps.extend(np.diff(s[s <= t_end]).tolist())
    return gaps


def tokens_in(reqs, t_start: float, t_end: float) -> int:
    return sum(sum(1 for t in r.stamps if t_start <= t <= t_end)
               for r in reqs)


def end_to_end(reqs, t_start: float, t_end: float) -> dict[str, float]:
    """ttft_p90_ms, itl_p95_ms, itl_p99_ms and out_tok_s of one window
    (each cell reports those BENCHMARK.json names for it)."""
    window = t_end - t_start
    out = {"out_tok_s": tokens_in(reqs, t_start, t_end) / window}
    gaps = itls(reqs, t_end)
    if gaps:
        out["itl_p95_ms"] = 1e3 * percentile(gaps, 95)
        out["itl_p99_ms"] = 1e3 * percentile(gaps, 99)
    firsts = ttfts(reqs, t_end)
    if firsts:
        out["ttft_p90_ms"] = 1e3 * percentile(firsts, 90)
    return out
