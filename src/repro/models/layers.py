"""Shared layers for the architecture zoo: norms, RoPE, GQA attention
(training + cached decode, full/local/cross), and the MLP with FTL as a
first-class execution mode.

Parameters are plain nested dicts of jnp arrays; every layer is a pure
function ``f(cfg, params, x, ...)`` so the zoo composes under pjit/remat
without a framework dependency.
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.ftl import registry
from repro.distributed.act_sharding import constrain
from repro.kernels import ops

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def init_linear(key, d_in: int, d_out: int, *, bias: bool, dtype,
                scale: float | None = None) -> Params:
    scale = scale if scale is not None else d_in ** -0.5
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale
               ).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p: Params, x: jax.Array) -> jax.Array:
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def init_norm(d: int, kind: str, dtype) -> Params:
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def norm(p: Params, x: jax.Array, kind: str, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    else:
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.mean((xf - mu) ** 2, -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32)
    if "bias" in p:
        y = y + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, Dh); positions: (S,) or (B, S)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        ang = positions.astype(jnp.float32)[None, :, None] * freqs[None, None]
    else:
        ang = positions.astype(jnp.float32)[:, :, None] * freqs[None, None]
    cos = jnp.cos(ang)[:, :, None, :]     # (B, S, 1, half)
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def init_attention(cfg, key, *, cross: bool = False) -> Params:
    d, h, hk, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    return {
        "wq": init_linear(ks[0], d, h * dh, bias=cfg.qkv_bias, dtype=dt),
        "wk": init_linear(ks[1], d, hk * dh, bias=cfg.qkv_bias, dtype=dt),
        "wv": init_linear(ks[2], d, hk * dh, bias=cfg.qkv_bias, dtype=dt),
        "wo": init_linear(ks[3], h * dh, d, bias=cfg.mlp_bias, dtype=dt,
                          scale=(h * dh) ** -0.5 / math.sqrt(2 * cfg.n_layers)),
    }


def _split_heads(x: jax.Array, n: int) -> jax.Array:
    b, s, _ = x.shape
    return x.reshape(b, s, n, -1)


def attention_layer(
    cfg,
    p: Params,
    x: jax.Array,                    # (B, S, D)
    *,
    positions: jax.Array,            # (S,)
    causal: bool = True,
    window: int | None = None,
    kv_source: jax.Array | None = None,   # cross-attention context (B, Sk, D)
    use_rope: bool = True,
) -> jax.Array:
    """Full-sequence attention (training / prefill)."""
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = _split_heads(linear(p["wq"], x), h)
    src = x if kv_source is None else kv_source
    k = _split_heads(linear(p["wk"], src), hk)
    v = _split_heads(linear(p["wv"], src), hk)
    if use_rope and kv_source is None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    q = constrain(q.transpose(0, 2, 1, 3), "heads_q")
    k = constrain(k.transpose(0, 2, 1, 3), "heads_kv")
    v = constrain(v.transpose(0, 2, 1, 3), "heads_kv")
    o = ops.attention(
        q, k, v,
        causal=causal and kv_source is None,
        window=window,
        backend="ref" if jax.default_backend() != "tpu" else "auto",
    )
    o = o.transpose(0, 2, 1, 3).reshape(x.shape[0], x.shape[1], h * dh)
    return linear(p["wo"], o)


def attention_prefill(
    cfg,
    p: Params,
    x: jax.Array,
    *,
    positions: jax.Array,
    causal: bool = True,
    window: int | None = None,
    kv_source: jax.Array | None = None,
    use_rope: bool = True,
    pad_to: int | None = None,
    plan=None,                       # registry.BlockPlan | None
) -> tuple[jax.Array, Params]:
    """Full-sequence attention that also returns the decode cache.

    Cache layout (B, S, Hk, Dh); for local windows a ring buffer of the
    last ``window`` positions keyed by ``pos % window``.  ``pad_to``
    right-pads the cache seq dim so decode steps can append in place
    (decode DUS clamps out-of-range starts — an unpadded cache would
    silently corrupt its last slot).

    ``plan`` (self-attention only) runs the projections and the attention
    core through the plan's gemm/attention bindings, as ``run_block``
    does, so serving prefill executes what ``plan_report`` resolves.
    """
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    if plan is not None:
        from repro.core.ftl import executor_block  # lazy: no cycle
        m, dt = x.shape[1], str(x.dtype)
        gemm_ex = executor_block.resolve_gemm(plan, cfg.ftl_mode, m, dt)
        attend = executor_block.resolve_attention(
            plan, cfg.ftl_mode, m, dt).run

        def proj(pp, xx):
            return executor_block.project(gemm_ex, xx, pp)
    else:
        proj = linear
        attend = functools.partial(
            ops.attention,
            backend="ref" if jax.default_backend() != "tpu" else "auto")
    q = _split_heads(proj(p["wq"], x), h)
    src = x if kv_source is None else kv_source
    k = _split_heads(proj(p["wk"], src), hk)
    v = _split_heads(proj(p["wv"], src), hk)
    if use_rope and kv_source is None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    o = attend(
        constrain(q.transpose(0, 2, 1, 3), "heads_q"),
        constrain(k.transpose(0, 2, 1, 3), "heads_kv"),
        constrain(v.transpose(0, 2, 1, 3), "heads_kv"),
        causal=causal and kv_source is None,
        window=window,
    )
    o = o.transpose(0, 2, 1, 3).reshape(x.shape[0], x.shape[1], h * dh)
    out = proj(p["wo"], o)
    s = k.shape[1]
    if window is not None and s >= window:
        tail_k, tail_v = k[:, -window:], v[:, -window:]
        tail_pos = positions[-window:] % window
        order = jnp.argsort(tail_pos)
        cache = {"k": tail_k[:, order], "v": tail_v[:, order]}
    else:
        cache = {"k": k, "v": v}
        target = pad_to
        if window is not None:
            # ring buffer must be exactly window-sized for decode
            target = window
        if target is not None and target > s:
            pad = [(0, 0), (0, target - s), (0, 0), (0, 0)]
            cache = {kk: jnp.pad(vv, pad) for kk, vv in cache.items()}
    return out, cache


def masked_decode_attention(
    q: jax.Array,        # (B, H, 1, Dh)
    k: jax.Array,        # (B, S, Hk, Dh)
    v: jax.Array,        # (B, S, Hk, Dh)
    mask: jax.Array,     # (S,) or (B, S) bool — valid cache slots
) -> jax.Array:
    b, hq = q.shape[0], q.shape[1]
    hk = k.shape[2]
    group = hq // hk
    dh = q.shape[-1]
    # keep K/V in storage dtype; accumulate in f32 via the MXU's
    # preferred_element_type — casting inputs would materialize f32 copies
    # of the whole cache (measured: 2.8 GB/step hoisted converts, §Perf).
    qg = q.reshape(b, hk, group, dh)
    kf = k.transpose(0, 2, 1, 3)                       # (B, Hk, S, Dh)
    vf = v.transpose(0, 2, 1, 3)
    s = jnp.einsum("bhgd,bhsd->bhgs", qg, kf,
                   preferred_element_type=jnp.float32) * dh ** -0.5
    # (B, S) masks carry per-row validity (continuous batching decodes
    # slots at mixed sequence lengths); (S,) is the uniform-length case
    mvalid = mask[None, None, None, :] if mask.ndim == 1 \
        else mask[:, None, None, :]
    s = jnp.where(mvalid, s, -1e30)
    pmax = s.max(-1, keepdims=True)
    e = jnp.exp(s - pmax)
    o = jnp.einsum("bhgs,bhsd->bhgd", e.astype(v.dtype), vf,
                   preferred_element_type=jnp.float32)
    o = o / e.sum(-1, keepdims=True)
    return o.reshape(b, hq, 1, dh).astype(q.dtype)


def _decode_qkv(cfg, p: Params, x: jax.Array, pos: jax.Array, *,
                use_rope: bool = True):
    """One token per row projected to q ``(B, 1, H, Dh)`` and the new k, v
    ``(B, 1, Hk, Dh)``; q and k roped at the row's absolute position
    (``pos`` () or ``(B,)``)."""
    q = _split_heads(linear(p["wq"], x), cfg.n_heads)
    k = _split_heads(linear(p["wk"], x), cfg.n_kv_heads)
    v = _split_heads(linear(p["wv"], x), cfg.n_kv_heads)
    if use_rope:
        # (B, 1) positions rope each row at its own absolute position
        posv = pos[:, None] if pos.ndim == 1 else pos[None]
        q = rope(q, posv, cfg.rope_theta)
        k = rope(k, posv, cfg.rope_theta)
    return q, k, v


def attention_decode(
    cfg,
    p: Params,
    x: jax.Array,                  # (B, 1, D)
    cache: Params,                 # {"k": (B, S, Hk, Dh), "v": ..., ["cross_k"/"cross_v"]}
    pos: jax.Array,                # () or (B,) int32 — absolute position(s)
    *,
    window: int | None = None,
    cross: bool = False,
    use_rope: bool = True,
) -> tuple[jax.Array, Params]:
    """One-token decode with KV cache (full or ring-buffered local).

    ``pos`` may be a scalar (every row appends at the same position — the
    uniform-length path) or a ``(B,)`` vector for continuous batching at
    mixed sequence lengths: each row writes its new KV at its own
    position and masks its own prefix."""
    h, dh = cfg.n_heads, cfg.resolved_head_dim
    b = x.shape[0]

    if cross:
        # cross-attention: K/V precomputed at prefill, no rope, no update
        q = _split_heads(linear(p["wq"], x), h)      # (B, 1, H, Dh)
        k, v = cache["k"], cache["v"]
        mask = jnp.ones((k.shape[1],), bool)
        o = masked_decode_attention(q.transpose(0, 2, 1, 3), k, v, mask)
        o = o.transpose(0, 2, 1, 3).reshape(b, 1, h * dh)
        return linear(p["wo"], o), cache

    pos = jnp.asarray(pos)
    vec = pos.ndim == 1
    q, k_new, v_new = _decode_qkv(cfg, p, x, pos, use_rope=use_rope)

    def _dus_rows(full, upd, starts):
        # per-row dynamic update: row i writes its (1, Hk, Dh) slice at
        # its own seq position starts[i]
        return jax.vmap(
            lambda f, u, s: jax.lax.dynamic_update_slice_in_dim(f, u, s, 0)
        )(full, upd, starts)

    s_max = cache["k"].shape[1]
    if window is not None and s_max == window:
        # ring buffer: slot j holds the latest position p ≤ pos with p%W==j
        slot = pos % window
        if vec:
            k = _dus_rows(cache["k"], k_new, slot)
            v = _dus_rows(cache["v"], v_new, slot)
            j = jnp.arange(window)
            mask = (pos[:, None] - ((pos[:, None] - j[None, :]) % window)
                    ) >= 0
        else:
            k = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k_new, slot, 1)
            v = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v_new, slot, 1)
            j = jnp.arange(window)
            slot_pos = pos - ((pos - j) % window)
            mask = slot_pos >= 0
    else:
        if vec:
            k = _dus_rows(cache["k"], k_new, pos)
            v = _dus_rows(cache["v"], v_new, pos)
            kpos = jnp.arange(s_max)
            mask = kpos[None, :] <= pos[:, None]
            if window is not None:
                mask &= kpos[None, :] > pos[:, None] - window
        else:
            k = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k_new, pos, 1)
            v = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v_new, pos, 1)
            kpos = jnp.arange(s_max)
            mask = kpos <= pos
            if window is not None:
                mask &= kpos > pos - window
    k = constrain(k, "kv_cache")
    v = constrain(v, "kv_cache")
    o = masked_decode_attention(q.transpose(0, 2, 1, 3), k, v, mask)
    o = o.transpose(0, 2, 1, 3).reshape(b, 1, h * dh)
    return linear(p["wo"], o), {"k": k, "v": v}


def paged_decode_attention(
    cfg,
    p: Params,
    x: jax.Array,                  # (B, 1, D)
    pages: Params,                 # {"k", "v"}: (P, bs, Hk, Dh) page pool
    blocks: jax.Array,             # (B, W) int32 pages of each row, in order
    pos: jax.Array,                # (B,) int32 absolute positions
) -> tuple[jax.Array, Params]:
    """One-token full-attention decode that reads a page pool in place.

    Row ``b`` attends to its cached positions ``< pos[b]``, read from
    pages ``blocks[b]`` where they lie (``ops.paged_attention``), and to
    its own new key, which enters the softmax directly: nothing is
    written here.  The arithmetic is :func:`masked_decode_attention`'s:
    bf16 inputs, f32 scores, a max-subtracted softmax accumulated in f32.
    Returns the output ``(B, 1, D)`` and the new token's ``{"k", "v"}:
    (B, Hk, Dh)`` for the caller to store."""
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    b = blocks.shape[0]
    q, k_new, v_new = _decode_qkv(cfg, p, x, pos)
    q, k_new, v_new = q[:, 0], k_new[:, 0], v_new[:, 0]
    acc, m, l = ops.paged_attention(q, pages["k"], pages["v"], blocks, pos)
    kv_head = jnp.arange(h) // (h // hk)            # of each query head
    kn, vn = k_new[:, kv_head], v_new[:, kv_head]   # (B, H, Dh)
    s_new = jnp.einsum("bqd,bqd->bq", q, kn,
                       preferred_element_type=jnp.float32) * dh ** -0.5
    m_all = jnp.maximum(m, s_new)
    alpha, e_new = jnp.exp(m - m_all), jnp.exp(s_new - m_all)
    o = (alpha[..., None] * acc
         + (e_new.astype(vn.dtype).astype(jnp.float32)[..., None]
            * vn.astype(jnp.float32)))
    o = o / (alpha * l + e_new)[..., None]
    o = o.astype(q.dtype).reshape(b, 1, h * dh)
    return linear(p["wo"], o), {"k": k_new, "v": v_new}


def init_kv_cache(cfg, batch: int, seq: int, dtype, *, window: int | None = None
                  ) -> Params:
    hk, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    s = min(seq, window) if window is not None else seq
    return {
        "k": jnp.zeros((batch, s, hk, dh), dtype),
        "v": jnp.zeros((batch, s, hk, dh), dtype),
    }


# ---------------------------------------------------------------------------
# MLP — FTL integration point (DESIGN.md §5)
# ---------------------------------------------------------------------------

def init_mlp(cfg, key, d_ff: int | None = None) -> Params:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 3)
    p = {
        "w1": init_linear(ks[0], d, f, bias=cfg.mlp_bias, dtype=dt),
        "w2": init_linear(ks[1], f, d, bias=cfg.mlp_bias, dtype=dt,
                          scale=f ** -0.5 / math.sqrt(2 * cfg.n_layers)),
    }
    if cfg.mlp_gated:
        p["wg"] = init_linear(ks[2], d, f, bias=False, dtype=dt)
    return p


def mlp_layer(cfg, p: Params, x: jax.Array, *, ftl_mode: str | None = None,
              plan=None) -> jax.Array:
    """MLP dispatched through the FTL executor registry.

    off   — layer-per-layer jnp: the hidden tensor is materialized (XLA
            fuses the activation epilogue but not GEMM→GEMM).  Baseline.
    fused — the fused_mlp Pallas kernel (FTL plan → BlockSpecs).
    scan  — portable FTL schedule via lax.scan token tiling.
    auto  — plan-driven: the fusion partitioner's chosen schedule picks
            the executor (Pallas fused kernel on TPU, scan executor for a
            fused/partial schedule elsewhere, baseline when the planner
            rejects fusion).

    ``plan`` (a :class:`~repro.core.ftl.registry.BlockPlan`) makes the
    plan's own MLP binding authoritative under 'auto' — the serving path
    threads its phase-specific prefill/decode plans here so the MLP runs
    through the executor the plan bound (requalified at the runtime
    shape), instead of re-planning an MLP-only graph.  Override modes
    ('off'/'fused'/'scan') keep their meaning either way.
    """
    mode = ftl_mode if ftl_mode is not None else cfg.ftl_mode
    wg = p.get("wg", {}).get("w")
    b1 = p["w1"].get("b")
    b2 = p["w2"].get("b")
    w1, w2 = p["w1"]["w"], p["w2"]["w"]
    if plan is not None:
        from repro.core.ftl import executor_block  # lazy: no cycle
        exe = executor_block.resolve_mlp(
            plan, mode, x.shape[-2], str(x.dtype),
            d_model=w1.shape[0], d_ff=w1.shape[1], gated=wg is not None,
        )
    else:
        exe = registry.mlp_executor(
            mode,
            m=x.shape[-2], d_model=w1.shape[0], d_ff=w1.shape[1],
            dtype=str(x.dtype), gated=wg is not None, act=cfg.mlp_act,
        )
    return exe.run(x, w1, w2, wg, b1, b2, act=cfg.mlp_act)


# ---------------------------------------------------------------------------
# whole-block execution — BlockPlan as the execution authority
# ---------------------------------------------------------------------------

def block_layer(
    cfg,
    p: Params,
    x: jax.Array,                    # (B, S, D)
    *,
    positions: jax.Array,
    plan=None,                       # registry.BlockPlan | None
    causal: bool = True,
    window: int | None = None,
    use_rope: bool = True,
) -> jax.Array:
    """One pre-norm attention+MLP block, plan-driven when ``plan`` is set.

    With a :class:`~repro.core.ftl.registry.BlockPlan` this replaces the
    hand-sequenced attention+MLP calls: ``registry.run_block`` walks the
    planned segments and dispatches each to its bound executor, falling
    back per segment when a binding does not qualify at runtime.  With
    ``plan=None`` it is the layer-per-layer reference path (the baseline
    the equivalence tests and benchmarks compare against).
    """
    if plan is not None:
        # the caller's cfg stays authoritative for the execution mode even
        # if the plan was made from a differently-moded config
        return registry.run_block(
            plan, p, x, positions=positions, causal=causal, window=window,
            use_rope=use_rope, ftl_mode=cfg.ftl_mode)
    h = norm(p["ln1"], x, cfg.norm)
    o = attention_layer(cfg, p["attn"], h, positions=positions,
                        causal=causal, window=window, use_rope=use_rope)
    x = constrain(x + o, "residual")
    if "mlp" in p:
        h = norm(p["ln2"], x, cfg.norm)
        x = constrain(x + mlp_layer(cfg, p["mlp"], h), "residual")
    return x
