"""Fused-tiled attention (flash-style) with GQA, causal and local-window
masking.

Attention is the second FTL instance in this framework (DESIGN.md §5): the
(Tq, Tk) score matrix is the intermediate fused away; the online-softmax
rescale is the kernel-policy that lets the Tk contraction tile with a VMEM
accumulator.  Grid (batch*heads, q_tiles, kv_tiles), kv innermost.

Numerics: masking uses a large negative constant (not -inf) and explicit
zero-guards so fully-masked rows (local windows) produce zeros, matching
ref.attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _make_kernel(*, causal: bool, window: int | None, scale: float,
                 block_q: int, block_k: int, q_offset: int):
    def kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref):
        iq = pl.program_id(1)
        jk = pl.program_id(2)
        nk = pl.num_programs(2)

        @pl.when(jk == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, _NEG)
            l_ref[...] = jnp.zeros_like(l_ref)

        q = q_ref[0].astype(jnp.float32)          # (bq, dh)
        k = k_ref[0].astype(jnp.float32)          # (bk, dh)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

        qpos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0) + q_offset
        kpos = jk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), dtype=jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, _NEG)

        m_prev = m_ref[...]                        # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        # guard: rows with nothing unmasked stay at _NEG -> p = 0
        p = jnp.where(s > _NEG / 2, jnp.exp(s - m_new), 0.0)
        alpha = jnp.where(m_prev > _NEG / 2, jnp.exp(m_prev - m_new), 0.0)

        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

        @pl.when(jk == nk - 1)
        def _flush():
            l = l_ref[...]
            o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(
                o_ref.dtype
            )

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "q_offset", "block_q", "block_k",
        "vmem_limit_bytes", "interpret"
    ),
)
def flash_attention(
    q: jax.Array,   # (B, Hq, Tq, Dh)
    k: jax.Array,   # (B, Hk, Tk, Dh)
    v: jax.Array,   # (B, Hk, Tk, Dh)
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
    block_q: int = 256,
    block_k: int = 256,
    vmem_limit_bytes: int,
    interpret: bool = False,
) -> jax.Array:
    b, hq, tq, dh = q.shape
    _, hk, tk, _ = k.shape
    assert hq % hk == 0
    group = hq // hk
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    if tq % block_q or tk % block_k:
        raise ValueError(f"blocks must divide seq lens {(tq, tk)}")
    scale = dh ** -0.5

    qf = q.reshape(b * hq, tq, dh)
    kf = k.reshape(b * hk, tk, dh)
    vf = v.reshape(b * hk, tk, dh)

    grid = (b * hq, tq // block_q, tk // block_k)

    def kv_index(bh, iq, jk):
        # map flat q-head index -> flat kv-head index (GQA)
        bb = bh // hq
        h = bh % hq
        return (bb * hk + h // group, jk, 0)

    out = pl.pallas_call(
        _make_kernel(
            causal=causal, window=window, scale=scale,
            block_q=block_q, block_k=block_k, q_offset=q_offset,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda bh, iq, jk: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, dh), kv_index),
            pl.BlockSpec((1, block_k, dh), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda bh, iq, jk: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, tq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, dh), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        name="flash_attention",
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, hq, tq, dh)
