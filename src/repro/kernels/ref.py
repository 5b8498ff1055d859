"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth the kernels are validated
against (tests/test_kernels.py sweeps shapes & dtypes with allclose).
They are also the layer-per-layer *execution* baseline: e.g. ``mlp`` here
materializes the hidden tensor exactly like the paper's unfused schedule.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

_ACTS = {
    "gelu": partial(jax.nn.gelu, approximate=True),
    "gelu_exact": partial(jax.nn.gelu, approximate=False),
    "silu": jax.nn.silu,
    "relu": jax.nn.relu,
    "identity": lambda x: x,
}


def act_fn(name: str):
    return _ACTS[name]


# ---------------------------------------------------------------------------
# GEMM family
# ---------------------------------------------------------------------------

def gemm(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.matmul(
        x, w, preferred_element_type=jnp.float32
    ).astype(x.dtype)


def gemm_act(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array | None = None,
    *,
    act: str = "gelu",
) -> jax.Array:
    """The paper's benchmark op: ``act(x @ w + b)``."""
    h = jnp.matmul(x, w, preferred_element_type=jnp.float32)
    if b is not None:
        h = h + b.astype(h.dtype)
    return act_fn(act)(h).astype(x.dtype)


def mlp(
    x: jax.Array,
    w1: jax.Array,
    w2: jax.Array,
    wg: jax.Array | None = None,
    b1: jax.Array | None = None,
    b2: jax.Array | None = None,
    *,
    act: str = "gelu",
) -> jax.Array:
    """Layer-per-layer MLP (materializes the hidden tensor)."""
    h = jnp.matmul(x, w1, preferred_element_type=jnp.float32)
    if b1 is not None:
        h = h + b1.astype(h.dtype)
    h = act_fn(act)(h)
    if wg is not None:
        h = h * jnp.matmul(x, wg, preferred_element_type=jnp.float32)
    y = jnp.matmul(h.astype(x.dtype), w2, preferred_element_type=jnp.float32)
    if b2 is not None:
        y = y + b2.astype(y.dtype)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (supports GQA + causal + local window)
# ---------------------------------------------------------------------------

def attention(
    q: jax.Array,   # (B, Hq, Tq, Dh)
    k: jax.Array,   # (B, Hk, Tk, Dh)
    v: jax.Array,   # (B, Hk, Tk, Dh)
    *,
    causal: bool = True,
    window: int | None = None,     # local attention window (recurrentgemma)
    q_offset: int = 0,             # absolute position of q[0] (decode)
) -> jax.Array:
    b, hq, tq, dh = q.shape
    hk = k.shape[1]
    assert hq % hk == 0, (hq, hk)
    group = hq // hk
    # GQA via reshape (no materialized jnp.repeat of K/V); f32 accumulation
    # via preferred_element_type, not input casts (which would materialize
    # f32 copies of Q/K/V — measured in the dry-run, see §Perf).
    qg = q.reshape(b, hk, group, tq, dh)
    scale = dh ** -0.5
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    qpos = jnp.arange(tq)[:, None] + q_offset
    kpos = jnp.arange(k.shape[2])[None, :]
    mask = jnp.ones((tq, k.shape[2]), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    # rows fully masked (can happen with windows) -> zeros, not NaN
    p = jnp.where(jnp.isnan(p), 0.0, p)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, hq, tq, dh).astype(q.dtype)


def paged_attention(
    q: jax.Array,          # (B, Hq, Dh)
    k_pages: jax.Array,    # (P, bs, Hk, Dh)
    v_pages: jax.Array,    # (P, bs, Hk, Dh)
    blocks: jax.Array,     # (B, W) int32 page of each row's w-th block
    lengths: jax.Array,    # (B,) int32 cached positions of each row
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Row ``b``'s attention state over its cached positions ``<
    lengths[b]``, read from pages ``blocks[b]``: ``(acc (B, Hq, Dh), m (B,
    Hq), l (B, Hq))``, all f32, with ``acc / l`` the attention output and
    ``m`` the max score (-1e30 where a row has no position).

    One ``take`` reads a row's pages as ``(W * bs * Hk, Dh)``: key ``c``
    is position ``c // Hk`` of KV head ``c % Hk``.  Every query head
    scores against every key, and a mask keeps its own head, so the
    masked scores weigh exactly zero and the keys need no relayout."""
    b, hq, dh = q.shape
    hk = k_pages.shape[2]

    def read(pages):
        return jnp.take(pages, blocks, axis=0).reshape(b, -1, dh)

    k, v = read(k_pages), read(v_pages)
    key = jnp.arange(k.shape[1])
    own = (key[None, None] % hk == (jnp.arange(hq) // (hq // hk))[None, :,
                                                                  None]) \
        & (key[None, None] // hk < lengths[:, None, None])   # (B, Hq, keys)
    s = jnp.einsum("bqd,bcd->bqc", q, k,
                   preferred_element_type=jnp.float32) * dh ** -0.5
    m = jnp.where(own, s, -1e30).max(-1)
    p = jnp.where(own, jnp.exp(s - m[..., None]), 0.0)
    acc = jnp.einsum("bqc,bcd->bqd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return acc, m, p.sum(-1)


def attention_blockwise(
    q: jax.Array,   # (B, Hq, Tq, Dh)
    k: jax.Array,   # (B, Hk, Tk, Dh)
    v: jax.Array,   # (B, Hk, Tk, Dh)
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
    block_k: int = 1024,
) -> jax.Array:
    """FTL-scheduled attention on the XLA path: ``lax.scan`` over KV blocks
    with an online softmax, so the (Tq, Tk) score matrix exists only as a
    (Tq, block_k) tile — the same schedule the Pallas flash kernel runs,
    executed by XLA (executor_xla.py's role, applied to attention).

    Numerically identical to :func:`attention` (same fp32 accumulation);
    peak memory drops from O(Tq·Tk) to O(Tq·block_k) per head.  §Perf
    measures the effect on the compiled dry-run.
    """
    b, hq, tq, dh = q.shape
    hk, tk = k.shape[1], k.shape[2]
    group = hq // hk
    if tk % block_k:
        block_k = tk            # fall back to one block
    nblk = tk // block_k
    qg = q.reshape(b, hk, group, tq, dh)
    scale = dh ** -0.5
    qpos = jnp.arange(tq) + q_offset

    kb = jnp.moveaxis(k.reshape(b, hk, nblk, block_k, dh), 2, 0)
    vb = jnp.moveaxis(v.reshape(b, hk, nblk, block_k, dh), 2, 0)

    def body(carry, blk):
        acc, m_run, l_run, j = carry
        kj, vj = blk
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kj,
                       preferred_element_type=jnp.float32) * scale
        kpos = j * block_k + jnp.arange(block_k)
        mask = jnp.ones((tq, block_k), bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(mask[None, None, None], s, -1e30)
        m_new = jnp.maximum(m_run, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)
        alpha = jnp.exp(m_run - m_new)
        l_new = l_run * alpha + p.sum(-1)
        pv = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(vj.dtype), vj,
                        preferred_element_type=jnp.float32)
        acc = acc * alpha[..., None] + pv
        return (acc, m_new, l_new, j + 1), None

    acc0 = jnp.zeros((b, hk, group, tq, dh), jnp.float32)
    m0 = jnp.full((b, hk, group, tq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, hk, group, tq), jnp.float32)
    (acc, _, l, _), _ = jax.lax.scan(
        body, (acc0, m0, l0, jnp.int32(0)), (kb, vb))
    out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    return out.reshape(b, hq, tq, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# RG-LRU (Griffin / RecurrentGemma) — gated linear recurrence
# ---------------------------------------------------------------------------

def rg_lru_scan(
    x: jax.Array,   # (B, T, D) gated input u_t (already multiplied by input gate)
    a: jax.Array,   # (B, T, D) per-step decay in (0, 1)
    h0: jax.Array | None = None,   # (B, D) initial state
) -> tuple[jax.Array, jax.Array]:
    """h_t = a_t * h_{t-1} + x_t ;  returns (all h, final h)."""
    b, t, d = x.shape
    if h0 is None:
        h0 = jnp.zeros((b, d), jnp.float32)

    def step(h, inp):
        xt, at = inp
        h = at * h + xt
        return h, h

    xs = (
        jnp.moveaxis(x.astype(jnp.float32), 1, 0),
        jnp.moveaxis(a.astype(jnp.float32), 1, 0),
    )
    hT, hs = jax.lax.scan(step, h0.astype(jnp.float32), xs)
    return jnp.moveaxis(hs, 0, 1).astype(x.dtype), hT


# ---------------------------------------------------------------------------
# mLSTM (xLSTM) — matrix-memory recurrence, stabilized
# ---------------------------------------------------------------------------

def mlstm_scan(
    q: jax.Array,   # (B, H, T, Dh)
    k: jax.Array,   # (B, H, T, Dh)
    v: jax.Array,   # (B, H, T, Dh)
    i_pre: jax.Array,   # (B, H, T) input-gate preactivation
    f_pre: jax.Array,   # (B, H, T) forget-gate preactivation
    *,
    return_state: bool = False,
):
    """Stabilized mLSTM recurrence (xLSTM eqs. 19-27).

    C_t = f'_t C_{t-1} + i'_t v_t k_tᵀ ;  n_t = f'_t n_{t-1} + i'_t k_t
    h_t = C_t q̃_t / max(|n_tᵀ q̃_t|, exp(-m_t))      with log-space stabilizer m.
    """
    b, h, t, dh = q.shape
    scale = dh ** -0.5

    def head_scan(qh, kh, vh, ih, fh):
        def step(carry, inp):
            C, n, m = carry
            qt, kt, vt, it, ft = inp
            logf = jax.nn.log_sigmoid(ft)
            m_new = jnp.maximum(logf + m, it)
            i_ = jnp.exp(it - m_new)
            f_ = jnp.exp(logf + m - m_new)
            C = f_ * C + i_ * jnp.outer(vt, kt)
            n = f_ * n + i_ * kt
            qs = qt * scale
            num = C @ qs
            den = jnp.maximum(jnp.abs(jnp.dot(n, qs)), jnp.exp(-m_new))
            return (C, n, m_new), num / den

        C0 = jnp.zeros((dh, dh), jnp.float32)
        n0 = jnp.zeros((dh,), jnp.float32)
        m0 = jnp.float32(0.0)
        carry, hs = jax.lax.scan(
            step,
            (C0, n0, m0),
            (
                qh.astype(jnp.float32),
                kh.astype(jnp.float32),
                vh.astype(jnp.float32),
                ih.astype(jnp.float32),
                fh.astype(jnp.float32),
            ),
        )
        return hs, carry

    fn = jax.vmap(jax.vmap(head_scan))
    out, (C, n, m) = fn(q, k, v, i_pre, f_pre)
    if return_state:
        return out.astype(q.dtype), {"C": C, "n": n, "m": m}
    return out.astype(q.dtype)


def mlstm_scan_chunked(
    q: jax.Array,       # (B, H, T, Dh)
    k: jax.Array,
    v: jax.Array,
    i_pre: jax.Array,   # (B, H, T)
    f_pre: jax.Array,
    *,
    chunk: int = 256,
    return_state: bool = False,
):
    """mLSTM with time-chunked rematerialization (§Perf lever).

    The plain scan's backward pass saves the (Dh×Dh) matrix memory at
    EVERY step — O(T·Dh²) bytes (xlstm-1.3b @4k: ~64 GiB/device).  Here
    the outer scan carries state across chunks and the inner per-chunk
    scan is ``jax.checkpoint``-ed, so only chunk boundaries are saved:
    O(T/chunk·Dh²), recomputing inside chunks on the backward pass.
    Bit-identical forward to :func:`mlstm_scan`.
    """
    b, h, t, dh = q.shape
    while t % chunk:
        chunk //= 2
    nc = t // chunk
    scale = dh ** -0.5

    def chunk_body(carry, inp):
        C, n, m = carry
        qc, kc, vc, ic, fc = inp        # (chunk, Dh)/(chunk,)

        def step(cr, xs):
            Ci, ni, mi = cr
            qt, kt, vt, it, ft = xs
            logf = jax.nn.log_sigmoid(ft)
            m_new = jnp.maximum(logf + mi, it)
            i_ = jnp.exp(it - m_new)
            f_ = jnp.exp(logf + mi - m_new)
            Ci = f_ * Ci + i_ * jnp.outer(vt, kt)
            ni = f_ * ni + i_ * kt
            qs = qt * scale
            num = Ci @ qs
            den = jnp.maximum(jnp.abs(jnp.dot(ni, qs)), jnp.exp(-m_new))
            return (Ci, ni, m_new), num / den

        return jax.lax.scan(step, (C, n, m), (qc, kc, vc, ic, fc))

    chunk_body = jax.checkpoint(
        chunk_body, policy=jax.checkpoint_policies.nothing_saveable)

    def head_scan(qh, kh, vh, ih, fh):
        def resh(x):
            return x.reshape(nc, chunk, *x.shape[1:])
        carry0 = (jnp.zeros((dh, dh), jnp.float32),
                  jnp.zeros((dh,), jnp.float32), jnp.float32(0.0))
        carry, hs = jax.lax.scan(
            chunk_body, carry0,
            (resh(qh.astype(jnp.float32)), resh(kh.astype(jnp.float32)),
             resh(vh.astype(jnp.float32)), resh(ih.astype(jnp.float32)),
             resh(fh.astype(jnp.float32))))
        return hs.reshape(t, dh), carry

    out, (C, n, m) = jax.vmap(jax.vmap(head_scan))(q, k, v, i_pre, f_pre)
    if return_state:
        return out.astype(q.dtype), {"C": C, "n": n, "m": m}
    return out.astype(q.dtype)
