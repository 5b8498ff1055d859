"""Baseline tiled GEMM Pallas kernel (the layer-per-layer building block).

Grid (m, n, k) with k innermost; fp32 VMEM accumulator (FTL kernel-policy:
``contract_accumulate``).  Block shapes come from an FTL ``TilePlan`` — see
ops.py — or are passed explicitly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gemm_kernel(x_ref, w_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "vmem_limit_bytes",
                     "interpret"),
)
def gemm(
    x: jax.Array,            # (M, K)
    w: jax.Array,            # (K, N)
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    vmem_limit_bytes: int,
    interpret: bool = False,
) -> jax.Array:
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    if m % block_m or n % block_n or k % block_k:
        raise ValueError(f"blocks must divide dims: {(m, n, k)} vs "
                         f"{(block_m, block_n, block_k)}")
    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        _gemm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        name="gemm",
        interpret=interpret,
    )(x, w)
