"""Fused-Tiled MLP — the paper's flagship fusion, extended to the FULL MLP.

One Pallas kernel computes ``y = act(x@w1 + b1)[⊙ (x@wg)] @ w2 + b2`` with
the (M, d_ff) hidden tensor living only as a (block_m, block_f) VMEM tile.
Dataflow (FTL kernel-policy constraints — the solver is told these):

  * K (d_model in)  : whole  — gemm1 is computed output-stationary per tile;
  * N (d_model out) : whole  — the y tile accumulates across F in fp32 VMEM;
  * grid (m, f), f innermost — contraction of gemm2 accumulates in VMEM, so
    y is written to HBM exactly once (cost.py's model of this kernel).

Block sizes come from the FTL solver (ops.py); the kernel asserts the
solver's VMEM accounting by construction (block shapes == plan tiles).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import act_fn


def _make_kernel(act: str, gated: bool, has_b1: bool, has_b2: bool):
    fn = act_fn(act)

    def kernel(*refs):
        refs = list(refs)
        x_ref = refs.pop(0)
        w1_ref = refs.pop(0)
        wg_ref = refs.pop(0) if gated else None
        w2_ref = refs.pop(0)
        b1_ref = refs.pop(0) if has_b1 else None
        b2_ref = refs.pop(0) if has_b2 else None
        o_ref = refs.pop(0)
        acc_ref = refs.pop(0)

        f = pl.program_id(1)
        nf = pl.num_programs(1)

        @pl.when(f == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        h = jnp.dot(x_ref[...], w1_ref[...], preferred_element_type=jnp.float32)
        if has_b1:
            h = h + b1_ref[...].astype(jnp.float32)
        h = fn(h)
        if gated:
            h = h * jnp.dot(
                x_ref[...], wg_ref[...], preferred_element_type=jnp.float32
            )
        # The hidden tile is consumed immediately — never leaves VMEM.
        acc_ref[...] += jnp.dot(
            h.astype(x_ref.dtype), w2_ref[...], preferred_element_type=jnp.float32
        )

        @pl.when(f == nf - 1)
        def _flush():
            y = acc_ref[...]
            if has_b2:
                y = y + b2_ref[...].astype(jnp.float32)
            o_ref[...] = y.astype(o_ref.dtype)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("act", "block_m", "block_f", "vmem_limit_bytes",
                     "interpret"),
)
def fused_mlp(
    x: jax.Array,                 # (M, K)
    w1: jax.Array,                # (K, F)
    w2: jax.Array,                # (F, N)
    wg: jax.Array | None = None,  # (K, F) — gate (SwiGLU-style)
    b1: jax.Array | None = None,  # (F,)
    b2: jax.Array | None = None,  # (N,)
    *,
    act: str = "gelu",
    block_m: int = 256,
    block_f: int = 512,
    vmem_limit_bytes: int,
    interpret: bool = False,
) -> jax.Array:
    m, k = x.shape
    kf, f = w1.shape
    f2, n = w2.shape
    assert k == kf and f == f2, (x.shape, w1.shape, w2.shape)
    block_m = min(block_m, m)
    block_f = min(block_f, f)
    if m % block_m or f % block_f:
        raise ValueError(f"blocks must divide dims: M={m}%{block_m}, F={f}%{block_f}")
    grid = (m // block_m, f // block_f)

    gated = wg is not None
    has_b1 = b1 is not None
    has_b2 = b2 is not None

    in_specs = [
        pl.BlockSpec((block_m, k), lambda i, j: (i, 0)),
        pl.BlockSpec((k, block_f), lambda i, j: (0, j)),
    ]
    args = [x, w1]
    if gated:
        in_specs.append(pl.BlockSpec((k, block_f), lambda i, j: (0, j)))
        args.append(wg)
    in_specs.append(pl.BlockSpec((block_f, n), lambda i, j: (j, 0)))
    args.append(w2)
    if has_b1:
        in_specs.append(pl.BlockSpec((1, block_f), lambda i, j: (0, j)))
        args.append(b1.reshape(1, f))
    if has_b2:
        in_specs.append(pl.BlockSpec((1, n), lambda i, j: (0, 0)))
        args.append(b2.reshape(1, n))

    return pl.pallas_call(
        _make_kernel(act, gated, has_b1, has_b2),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, n), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        name="fused_mlp",
        interpret=interpret,
    )(*args)
