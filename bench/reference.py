"""Float32 reference forward of the dense decoders the configurations
describe, written from the published equations and independent of the
program under test.

One block kind covers both configurations: pre-norm attention and MLP
with a residual around each.

  norm     RMSNorm, or LayerNorm with a bias (``spec.norm``), eps
           ``spec.eps``, then a learned scale
  attention  q, k, v projections (with biases where ``attn_bias``),
           half-split RoPE (HF ``rotate_half``) at ``spec.theta``, causal
           softmax(q k^T / sqrt(head_dim)) v with ``heads / kv_heads``
           query heads sharing each key/value head (GQA; MQA at one),
           output projection (with a bias where ``attn_bias``)
  MLP      w2(act(w1 x + b1) * (wg x)) + b2 where gated (w1 is HF's
           gate_proj, wg its up_proj, w2 its down_proj), else
           w2(act(w1 x + b1)) + b2 (GPT-BigCode's c_fc and c_proj);
           act is SiLU or the tanh-approximated GELU
  head     final norm, then an untied (d, vocab) projection

Everything is computed in float32 at the highest matmul precision, one
layer's weights upcast at a time, attention in blocks of query rows and
the head in blocks of the vocabulary, so the reference fits beside the
bf16 weights on the chip.

``quant=True`` is the correctness check's control: the same forward with
every matmul input rounded to float8 (e4m3, scaled per row or column by
its largest magnitude) - the step below the bf16 the configurations
state, which a later change might be tempted to take.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.spec import Spec

QBLOCK = 512          # query rows per attention block
VOCAB_BLOCKS = 8      # head computed in this many vocabulary slices
F8_MAX = 448.0        # largest finite float8_e4m3fn


def _q8(x: jax.Array, axis: int) -> jax.Array:
    """``x`` rounded to float8 e4m3, scaled along ``axis`` by its largest
    magnitude (the contraction axis of the matmul it feeds)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, quant: bool):
    if quant:
        a, b = _q8(a, -1), _q8(b, 0)
    return a @ b


def _norm(spec: Spec, x, scale, bias):
    if spec.norm == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + spec.eps)
    else:
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + spec.eps)
    y = y * scale
    return y if bias is None else y + bias


def _act(spec: Spec, x):
    if spec.act == "silu":
        return x / (1.0 + jnp.exp(-x))
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))


def _rope(spec: Spec, x, positions):
    """x: (T, heads, head_dim) rotated at ``positions`` (T,)."""
    half = spec.head_dim // 2
    freqs = spec.theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(spec: Spec, q, k, v, quant: bool):
    """Causal attention; q (T, H, dh), k and v (T, Hk, dh) -> (T, H*dh)."""
    t = q.shape[0]
    hk, dh = spec.kv_heads, spec.head_dim
    g = spec.heads // hk
    qg = q.reshape(t, hk, g, dh)
    if quant:
        k, v = _q8(k, -1), _q8(v, 0)
    keys = jnp.arange(t)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qg, i * QBLOCK, QBLOCK, 0)
        if quant:
            qb = _q8(qb, -1)
        s = jnp.einsum("qkgd,tkd->kgqt", qb, k) / math.sqrt(dh)
        rows = i * QBLOCK + jnp.arange(QBLOCK)
        s = jnp.where(keys[None, :] <= rows[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if quant:
            p = _q8(p, -1)
        o = jnp.einsum("kgqt,tkd->qkgd", p, v)
        return o.reshape(QBLOCK, spec.q_width)

    return jax.lax.map(block, jnp.arange(t // QBLOCK)).reshape(
        t, spec.q_width)


def _layer(spec: Spec, x, p, positions, quant: bool):
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    t = x.shape[0]

    def lin(h, w, b=None):
        y = _mm(h, p[w], quant)
        return y if b is None or b not in p else y + p[b]

    h = _norm(spec, x, p["ln1_scale"], p.get("ln1_bias"))
    q = _rope(spec, lin(h, "wq", "bq").reshape(t, spec.heads, -1),
              positions)
    k = _rope(spec, lin(h, "wk", "bk").reshape(t, spec.kv_heads, -1),
              positions)
    v = lin(h, "wv", "bv").reshape(t, spec.kv_heads, -1)
    x = x + lin(_attention(spec, q, k, v, quant), "wo", "bo")
    h = _norm(spec, x, p["ln2_scale"], p.get("ln2_bias"))
    u = _act(spec, lin(h, "w1", "b1"))
    if spec.gated:
        u = u * lin(h, "wg")
    return x + lin(u, "w2", "b2")


@functools.partial(jax.jit, static_argnames=("spec", "quant"))
def logits_at(spec: Spec, w: dict, tokens: jax.Array, rows: jax.Array,
              quant: bool = False) -> jax.Array:
    """Float32 logits (len(rows), vocab) at positions ``rows`` of the
    sequence ``tokens`` (T,), T a multiple of ``QBLOCK`` (pad the end:
    attention is causal, so padding changes no earlier position)."""
    layer_keys = sorted(spec.layer_shapes())
    positions = jnp.arange(tokens.shape[0])
    with jax.default_matmul_precision("highest"):
        x = w["embed"][tokens].astype(jnp.float32)

        def body(x, p):
            return _layer(spec, x, p, positions, quant), None

        x, _ = jax.lax.scan(body, x, {k: w[k] for k in layer_keys})
        x = _norm(spec, x[rows], w["final_scale"].astype(jnp.float32),
                  w["final_bias"].astype(jnp.float32)
                  if "final_bias" in w else None)
        head = w["lm_head"]
        c = spec.vocab // VOCAB_BLOCKS
        parts = jax.lax.map(
            lambda i: _mm(x, jax.lax.dynamic_slice_in_dim(
                head, i * c, c, axis=1).astype(jnp.float32), quant),
            jnp.arange(VOCAB_BLOCKS))
    return parts.transpose(1, 0, 2).reshape(rows.shape[0], -1)


def padded_len(n: int) -> int:
    """The length a sequence of ``n`` tokens is padded to: the next
    power-of-two multiple of ``QBLOCK``, so a few compiled shapes serve
    every request."""
    t = QBLOCK
    while t < n:
        t *= 2
    return t
