"""Whether what the timed path served is right, by the float32 reference.

After the window a sample of the requests the engine finished, drawn from
the seed and always holding the longest, is run through the reference:
each prompt with its served tokens, once.  Every served token is greedy,
so the reference's logit for it should sit at the top, short of it only
by rounding.  The number compared is the widest gap by which a served
token's reference logit lies below the reference's best.  The first
served token of a request comes from the engine's prefill (the planned
executors at the request's bucket), the rest from its paged decode step
(at the window's slot count), so both paths are covered.
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from bench import reference

MAX_SAMPLE = 32


def sample(served, seed: int, min_tokens: int) -> list:
    """Finished requests: the longest first, then others in an order
    drawn from ``seed``, until ``min_tokens`` served tokens are held."""
    done = [r for r in served if r.done]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.tokens), r.rid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng(seed).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if n >= min_tokens or len(out) >= MAX_SAMPLE:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def _rows_len(n: int) -> int:
    r = 1
    while r < n:
        r *= 2
    return r


def _inputs(r, rows_len: int):
    plen, n = len(r.prompt), len(r.tokens)
    seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
    tokens = np.zeros(reference.padded_len(len(seq)), np.int32)
    tokens[:len(seq)] = seq
    rows = np.full(rows_len, plen - 1, np.int32)
    rows[:n] = np.arange(plen - 1, plen - 1 + n)
    return jnp.asarray(tokens), jnp.asarray(rows), n


def gaps(spec, w, reqs, rows_len: int, *, control: bool = False
         ) -> dict:
    """Per request, the widest gap below the reference's best of the
    served tokens; with ``control`` also of the tokens the float8 control
    puts first at the same positions."""
    rows_len = _rows_len(rows_len)
    t0 = time.perf_counter()
    served, ctrl = [], []
    for r in reqs:
        tokens, rows, n = _inputs(r, rows_len)
        ref = reference.logits_at(spec, w, tokens, rows)
        best = jnp.max(ref, -1)
        got = jnp.take_along_axis(
            ref, jnp.asarray(np.resize(np.asarray(r.tokens, np.int32),
                                       rows_len))[:, None], -1)[:, 0]
        served.append(float(jnp.max((best - got)[:n])))
        if control:
            low = reference.logits_at(spec, w, tokens, rows, quant=True)
            pick = jnp.argmax(low, -1)
            lowgot = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
            ctrl.append(float(jnp.max((best - lowgot)[:n])))
    out = {"max_logit_gap": max(served) if served else None,
           "per_request": served, "tokens": sum(len(r.tokens) for r in reqs),
           "requests": len(reqs), "reference_s": time.perf_counter() - t0}
    if control:
        out["control_max_logit_gap"] = max(ctrl) if ctrl else None
        out["control_per_request"] = ctrl
    return out
