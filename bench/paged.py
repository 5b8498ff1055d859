"""Operations and bytes the paged-attention kernel needs in one decode
step, from the model's sizes and the slots' cached positions.

The kernel reads each slot's cached keys and values where they lie in
the page pool and computes each live row's attention state over them:
per layer, every query head scores against its own KV head's positions
(``q k^T`` and ``p v``).  What it must move: the keys and values of the
cached positions, every live row's bf16 query in, and its f32 state out
(the unnormalized output, the running max and the running sum of each
head).  Whole pages, and the kernel's whole chunks of pages, are more
than that and count against it.
"""
from __future__ import annotations

from bench.flops import BYTES
from bench.spec import Spec

STATE_BYTES = 4          # f32


def decode_step(spec: Spec, positions: int, rows: int) -> tuple[int, int]:
    """(FLOPs, bytes) of one decode step's paged attention over every
    layer: ``rows`` live rows with ``positions`` cached positions in
    all."""
    flops = spec.layers * 4 * spec.heads * spec.head_dim * positions
    row = spec.heads * (spec.head_dim * (BYTES + STATE_BYTES)
                        + 2 * STATE_BYTES)
    nbytes = positions * spec.kv_bytes_per_token() + spec.layers * rows * row
    return flops, nbytes
