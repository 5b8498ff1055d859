"""Operations and bytes that serving a dense decoder needs, from its sizes.

These are what the work requires, not what a program happens to do:
prefill counts the real prompt tokens (no bucket padding), attention
only over the causal pairs, and the output head at the one position whose
logits are served; a decode step reads every weight once and each live
slot's keys and values once.  Norms, activations, RoPE and softmax are
left out: they are under a percent of the matmuls at these widths.
"""
from __future__ import annotations

from bench.spec import Spec

BYTES = 2        # bf16


def layer_matmul_flops(spec: Spec, t: int) -> int:
    """Projections and MLP of one layer over ``t`` tokens."""
    d = spec.d
    proj = d * (spec.q_width + 2 * spec.kv_width) + spec.q_width * d
    mlp = d * spec.ff * (3 if spec.gated else 2)
    return 2 * t * (proj + mlp)


def attention_flops(spec: Spec, t: int, causal: bool = True) -> int:
    """q k^T and p v of one layer over a ``t``-token sequence: the pairs
    (i, j <= i) where causal, all t * t otherwise."""
    pairs = t * (t + 1) // 2 if causal else t * t
    return 4 * spec.heads * spec.head_dim * pairs


def head_flops(spec: Spec, rows: int = 1) -> int:
    return 2 * spec.d * spec.vocab * rows


def prefill_flops(spec: Spec, t: int) -> int:
    """One prompt of ``t`` real tokens: every layer, and the head once."""
    return (spec.layers * (layer_matmul_flops(spec, t)
                           + attention_flops(spec, t))
            + head_flops(spec))


def weight_bytes(spec: Spec) -> int:
    """Every weight a decode step streams: all layers, the final norm and
    the head (the embedding table is read only at the live tokens' rows,
    counted in ``decode_step``)."""
    top = spec.top_shapes()
    n = spec.n_params() - top["embed"][0] * top["embed"][1]
    return BYTES * n


def decode_step(spec: Spec, contexts: list[int]) -> tuple[int, int]:
    """(FLOPs, bytes) of one decode step whose live slots attend over
    ``contexts`` positions each (the new token's included)."""
    n = len(contexts)
    kv = sum(contexts)
    flops = (spec.layers * (layer_matmul_flops(spec, n)
                            + 4 * spec.heads * spec.head_dim * kv)
             + head_flops(spec, n))
    nbytes = (weight_bytes(spec) + BYTES * spec.d * n
              + kv * spec.kv_bytes_per_token())
    return flops, nbytes


def least_time_s(flops: int, nbytes: int, peak: dict) -> tuple[float, str]:
    """The roofline's least time and which bound sets it."""
    tc = flops / peak["bf16_flops"]
    tb = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tb else (tb, "bandwidth")
