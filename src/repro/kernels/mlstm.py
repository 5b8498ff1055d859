"""mLSTM matrix-memory recurrence (xLSTM) as a fused-tiled Pallas kernel.

State per head: C (Dh×Dh) matrix memory, n (Dh) normalizer, m scalar
stabilizer — all VMEM-resident scratch carried across time chunks (grid dim,
innermost).  The (T × Dh × Dh) state trajectory that a layer-per-layer
schedule would materialize never exists: only h_t streams out.  This is the
paper's fusion argument applied to a recurrence instead of a GEMM chain.

Grid (B*H, t_chunks).  Within a chunk the recurrence is evaluated in its
chunkwise-parallel form — the per-step stabilized update unrolled over the
chunk, which is exact algebra, not an approximation: with F_t the running
sum of log forget gates inside the chunk and (C_0, n_0, m_0) the state
entering it,

  m_t = F_t + max(m_0, max_{s<=t} (i_s - F_s))
  C_t q = exp(F_t + m_0 - m_t) C_0 q + sum_{s<=t} exp(F_t - F_s + i_s - m_t)
          (k_s . q) v_s

so a chunk is four matmuls on the MXU plus (chunk × chunk) elementwise
gate algebra, and every exponent is <= 0 by construction of m_t.  The
gates arrive as (1, chunk) lane rows; their per-step column forms are
taken with a diagonal mask, which keeps every load aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30
_NT = (((1,), (1,)), ((), ()))      # x @ y.T
_TN = (((0,), (0,)), ((), ()))      # x.T @ y


def _make_kernel(scale: float):
    def kernel(q_ref, k_ref, v_ref, i_ref, f_ref, h_ref, C_ref, n_ref, m_ref):
        tc = pl.program_id(1)

        @pl.when(tc == 0)
        def _init():
            C_ref[...] = jnp.zeros_like(C_ref)
            n_ref[...] = jnp.zeros_like(n_ref)
            m_ref[...] = jnp.zeros_like(m_ref)

        f32 = jnp.float32
        q = q_ref[0].astype(f32) * scale              # (T, Dh)
        k = k_ref[0].astype(f32)
        v = v_ref[0].astype(f32)
        t = q.shape[0]
        row = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
        causal = col <= row
        eye = col == row

        i_row = i_ref[0].astype(f32)                  # (1, T)
        logf_row = jax.nn.log_sigmoid(f_ref[0].astype(f32))
        # F_t = sum_{s<=t} logf_s as a column; i_t as a column
        F = jnp.sum(jnp.where(causal, logf_row, 0.0), axis=1, keepdims=True)
        i_col = jnp.sum(jnp.where(eye, i_row, 0.0), axis=1, keepdims=True)
        g_col = i_col - F                             # (T, 1)
        g_row = jnp.sum(jnp.where(eye, g_col, 0.0), axis=0, keepdims=True)

        m0 = m_ref[...]                               # (1, 1)
        gmax = jnp.max(jnp.where(causal, g_row, _NEG), axis=1, keepdims=True)
        m = F + jnp.maximum(m0, gmax)                 # (T, 1)
        a = jnp.exp(F + m0 - m)                       # decay of the entry state
        w = jnp.where(causal, jnp.exp((F - m) + g_row), 0.0)   # (T, T)

        s = jax.lax.dot_general(q, k, _NT, preferred_element_type=f32)
        ws = w * s
        C0 = C_ref[...]
        n0 = n_ref[...]                               # (1, Dh)
        num = a * jax.lax.dot_general(q, C0, _NT, preferred_element_type=f32)
        num = num + jnp.dot(ws, v, preferred_element_type=f32)
        nq = a * jnp.sum(q * n0, axis=1, keepdims=True)
        nq = nq + jnp.sum(ws, axis=1, keepdims=True)
        den = jnp.maximum(jnp.abs(nq), jnp.exp(-m))
        h_ref[0] = (num / den).astype(h_ref.dtype)

        # state leaving the chunk = the step-(T-1) state
        m_last = m[t - 1:, :]
        # the last step's decay as a (1, Dh) row, reduced out of the
        # lane-broadcast column: Mosaic cannot broadcast a (1, 1) value
        # along both axes of C at once
        last = jax.lax.broadcasted_iota(jnp.int32, q.shape, 0) == t - 1
        a_last = jnp.sum(jnp.where(last, a, 0.0), axis=0, keepdims=True)
        w_last = jnp.exp(g_col + (F[t - 1:, :] - m_last))   # (T, 1)
        C_ref[...] = a_last * C0 + jax.lax.dot_general(
            v * w_last, k, _TN, preferred_element_type=f32)
        n_ref[...] = a_last * n0 + jnp.sum(k * w_last, axis=0, keepdims=True)
        m_ref[...] = m_last

    return kernel


@functools.partial(jax.jit, static_argnames=("block_t", "vmem_limit_bytes",
                                             "interpret"))
def mlstm_scan(
    q: jax.Array,      # (B, H, T, Dh)
    k: jax.Array,
    v: jax.Array,
    i_pre: jax.Array,  # (B, H, T)
    f_pre: jax.Array,  # (B, H, T)
    *,
    block_t: int = 128,
    vmem_limit_bytes: int,
    interpret: bool = False,
) -> jax.Array:
    b, h, t, dh = q.shape
    block_t = min(block_t, t)
    if t % block_t:
        raise ValueError(f"block_t must divide T={t}")
    scale = dh ** -0.5

    qf = q.reshape(b * h, t, dh)
    kf = k.reshape(b * h, t, dh)
    vf = v.reshape(b * h, t, dh)
    # gates as (1, T) lane rows: a (B*H, T) array blocked (1, block_t)
    # would put a 1-row block on the sublane axis, which Mosaic refuses
    if_ = i_pre.reshape(b * h, 1, t)
    ff = f_pre.reshape(b * h, 1, t)

    grid = (b * h, t // block_t)
    out = pl.pallas_call(
        _make_kernel(scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, dh), lambda bh, tt: (bh, tt, 0)),
            pl.BlockSpec((1, block_t, dh), lambda bh, tt: (bh, tt, 0)),
            pl.BlockSpec((1, block_t, dh), lambda bh, tt: (bh, tt, 0)),
            pl.BlockSpec((1, 1, block_t), lambda bh, tt: (bh, 0, tt)),
            pl.BlockSpec((1, 1, block_t), lambda bh, tt: (bh, 0, tt)),
        ],
        out_specs=pl.BlockSpec((1, block_t, dh), lambda bh, tt: (bh, tt, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, t, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((dh, dh), jnp.float32),
            pltpu.VMEM((1, dh), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        name="mlstm",
        interpret=interpret,
    )(qf, kf, vf, if_, ff)
    return out.reshape(b, h, t, dh)
