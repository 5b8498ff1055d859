"""GEMM + activation — the paper's exact fused benchmark op.

Fuses ``act(x @ w + b)`` into one Pallas kernel: the pre-activation tensor
lives only as a VMEM accumulator tile and never reaches HBM (on Siracusa:
never reaches L2/L3).  Grid (m, n, k), k innermost, fp32 accumulator,
activation applied as the epilogue of the final k step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import act_fn


def _make_kernel(act: str, has_bias: bool):
    fn = act_fn(act)

    def kernel(*refs):
        if has_bias:
            x_ref, w_ref, b_ref, o_ref, acc_ref = refs
        else:
            x_ref, w_ref, o_ref, acc_ref = refs

        @pl.when(pl.program_id(2) == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(
            x_ref[...], w_ref[...], preferred_element_type=jnp.float32
        )

        @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
        def _epilogue():
            h = acc_ref[...]
            if has_bias:
                h = h + b_ref[...].astype(jnp.float32)
            o_ref[...] = fn(h).astype(o_ref.dtype)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("act", "block_m", "block_n", "block_k",
                     "vmem_limit_bytes", "interpret"),
)
def gemm_act(
    x: jax.Array,              # (M, K)
    w: jax.Array,              # (K, N)
    b: jax.Array | None = None,  # (N,)
    *,
    act: str = "gelu",
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    vmem_limit_bytes: int,
    interpret: bool = False,
) -> jax.Array:
    m, k = x.shape
    _, n = w.shape
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    if m % block_m or n % block_n or k % block_k:
        raise ValueError("blocks must divide dims")
    grid = (m // block_m, n // block_n, k // block_k)

    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
    ]
    args = [x, w]
    if b is not None:
        in_specs.append(pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)))
        args.append(b.reshape(1, n))

    return pl.pallas_call(
        _make_kernel(act, b is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        name="gemm_gelu",
        interpret=interpret,
    )(*args)
