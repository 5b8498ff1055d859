"""Model assembly for the architecture zoo.

One module builds every assigned architecture from the shared blocks:

  dense   — embed → scan[attn + MLP] → norm → lm_head
  moe     — MLP replaced by capacity-routed MoE (+ aux loss)
  vlm     — every ``cross_attn_every``-th layer cross-attends to stub
            image embeddings (llama-3.2-vision)
  ssm     — xLSTM: mLSTM blocks with every ``slstm_every``-th an sLSTM;
            no separate MLP (projections live inside the block)
  hybrid  — recurrentgemma: (rec, rec, local-attn) pattern + MLP each layer
  audio   — whisper: encoder (bidirectional attn over stub frame
            embeddings) + decoder (causal self-attn + cross-attn)

Compile-efficiency: layers are grouped into *pattern periods* (dense: 1
layer; vlm: 5; hybrid: 3; ssm: 8).  Params of each position-in-period are
stacked across periods and the stack is consumed by ``lax.scan`` — the HLO
contains one period body regardless of depth (38..100 layers), which keeps
the 512-device dry-run compiles tractable.  Layers that do not fill a whole
period (recurrentgemma: 38 = 12×3 + 2) are applied unrolled after the scan.

Everything is a pure function of (cfg, params, inputs) so the same code
runs under pjit, remat, eval_shape (dry-run) and CPU smoke tests.
"""
from __future__ import annotations

import functools
import logging
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.ftl import InfeasibleError, graph
from repro.core.ftl import registry as ftl_registry
from repro.distributed.act_sharding import constrain
from repro.models import recurrent
from repro.models.layers import (
    attention_decode,
    attention_layer,
    attention_prefill,
    block_layer,
    init_attention,
    init_kv_cache,
    init_linear,
    init_mlp,
    init_norm,
    linear,
    mlp_layer,
    norm,
    paged_decode_attention,
)
from repro.models.moe import init_moe, moe_layer

Params = dict[str, Any]

log = logging.getLogger(__name__)

# kinds whose mixer handles its own input norm (recurrent blocks do)
_SELF_NORMED = {"mlstm", "slstm", "rec"}
# kinds that keep a decode cache of KV type
_KV_KINDS = {"attn", "local", "cross"}


# ===========================================================================
# per-layer init
# ===========================================================================

def _init_layer(cfg, key, kind: str) -> Params:
    """One transformer/recurrent layer of mixer ``kind`` (+ MLP/MoE)."""
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    p: Params = {"kind_": kind}
    if kind == "attn" or kind == "local":
        p["ln1"] = init_norm(cfg.d_model, cfg.norm, dt)
        p["attn"] = init_attention(cfg, ks[0])
    elif kind == "cross":
        p["ln1"] = init_norm(cfg.d_model, cfg.norm, dt)
        p["attn"] = init_attention(cfg, ks[0], cross=True)
        # gating scalar per llama-3.2 cross-attn layers
        p["xgate"] = jnp.zeros((1,), jnp.float32)
    elif kind == "mlstm":
        p["mix"] = recurrent.init_mlstm_block(cfg, ks[0])
    elif kind == "slstm":
        p["mix"] = recurrent.init_slstm_block(cfg, ks[0])
    elif kind == "rec":
        p["mix"] = recurrent.init_rec_block(cfg, ks[0])
    else:  # pragma: no cover
        raise ValueError(f"unknown block kind {kind!r}")

    if cfg.is_moe:
        p["ln2"] = init_norm(cfg.d_model, cfg.norm, dt)
        p["moe"] = init_moe(cfg, ks[1])
    elif cfg.d_ff:
        p["ln2"] = init_norm(cfg.d_model, cfg.norm, dt)
        p["mlp"] = init_mlp(cfg, ks[1])
    return p


def _strip_kind(p: Params) -> Params:
    return {k: v for k, v in p.items() if k != "kind_"}


# ===========================================================================
# per-layer apply (train / full-sequence forward)
# ===========================================================================

def _apply_mixer(cfg, p: Params, kind: str, x, *, positions, ctx):
    if kind in ("attn", "local"):
        h = norm(p["ln1"], x, cfg.norm)
        window = cfg.local_window if kind == "local" else None
        return attention_layer(cfg, p["attn"], h, positions=positions,
                               causal=True, window=window)
    if kind == "cross":
        h = norm(p["ln1"], x, cfg.norm)
        o = attention_layer(cfg, p["attn"], h, positions=positions,
                            causal=False, kv_source=ctx, use_rope=False)
        return jnp.tanh(p["xgate"]).astype(x.dtype) * o
    if kind == "mlstm":
        return recurrent.mlstm_block(cfg, p["mix"], x)
    if kind == "slstm":
        return recurrent.slstm_block(cfg, p["mix"], x)
    if kind == "rec":
        return recurrent.rec_block(cfg, p["mix"], x)
    raise ValueError(kind)


def _apply_ffn(cfg, p: Params, x, plan=None):
    """Returns (delta, aux).  ``plan`` routes the MLP through its
    BlockPlan binding (serving's phase-split plans); None re-resolves."""
    if "moe" in p:
        h = norm(p["ln2"], x, cfg.norm)
        y, aux = moe_layer(cfg, p["moe"], h)
        return y, aux
    if "mlp" in p:
        h = norm(p["ln2"], x, cfg.norm)
        return mlp_layer(cfg, p["mlp"], h, plan=plan), jnp.float32(0.0)
    return jnp.zeros_like(x), jnp.float32(0.0)


def _apply_layer(cfg, p: Params, kind: str, x, *, positions, ctx, plan=None):
    """Pre-norm residual layer.  Returns (x, aux)."""
    if plan is not None and kind in ("attn", "local") and "mlp" in p:
        # BlockPlan-driven execution: the planned segments (QKV/output
        # projections, attention core, MLP) dispatch through their bound
        # executors; norms and residuals are stitched by run_block.
        window = cfg.local_window if kind == "local" else None
        x = block_layer(cfg, p, x, positions=positions, plan=plan,
                        window=window)
        return x, jnp.float32(0.0)
    x = x + _apply_mixer(cfg, p, kind, x, positions=positions, ctx=ctx)
    x = constrain(x, "residual")
    d, aux = _apply_ffn(cfg, p, x)
    x = constrain(x + d, "residual")
    return x, aux


# ===========================================================================
# period/stack machinery
# ===========================================================================

def period_kinds(cfg) -> list[str]:
    """Mixer kinds of the positions inside one pattern period."""
    if cfg.family == "hybrid":
        return list(cfg.block_pattern)
    if cfg.family == "vlm" and cfg.cross_attn_every:
        k = cfg.cross_attn_every
        return ["attn"] * (k - 1) + ["cross"]
    if cfg.family == "ssm":
        if cfg.slstm_every:
            k = cfg.slstm_every
            return ["mlstm"] * (k - 1) + ["slstm"]
        return ["mlstm"]
    return ["attn"]


def _layer_split(cfg) -> tuple[list[str], int, list[str]]:
    """(period kinds, n_full_periods, remainder kinds)."""
    kinds = period_kinds(cfg)
    per = len(kinds)
    n_full = cfg.n_layers // per
    rem = cfg.n_layers % per
    return kinds, n_full, kinds[:rem]


def _init_stack(cfg, key, kinds: list[str], n: int) -> Params:
    """Stacked params: one entry per position-in-period, leaves (n, ...)."""
    keys = jax.random.split(key, n)

    def one(k):
        ks = jax.random.split(k, len(kinds))
        return {f"pos{i}": _strip_kind(_init_layer(cfg, ks[i], kinds[i]))
                for i in range(len(kinds))}

    return jax.vmap(one)(keys)


def _scan_layers(cfg, stack: Params, kinds: list[str], x, *, positions, ctx,
                 plan=None):
    """lax.scan over periods; returns (x, aux_sum)."""

    def body(carry, pp):
        h, aux = carry
        for i, kind in enumerate(kinds):
            h, a = _apply_layer(cfg, pp[f"pos{i}"], kind, h,
                                positions=positions, ctx=ctx, plan=plan)
            aux = aux + a
        return (h, aux), None

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable
        )
    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), stack)
    return x, aux


def plan_or_none(cfg, **kwargs):
    """``registry.plan_block(cfg, **kwargs)``, or None where "no plan" is
    the planner's documented outcome: the config has no plannable block
    (pure recurrent stacks), or no tile assignment fits the target
    (``InfeasibleError``, logged).  Any other planning error propagates —
    a model that should have a plan never silently runs without one.

    The model's one way in to a block plan: ``forward`` and the serving
    engine both plan through it, and ``registry.plan_block`` holds the
    only cache (keyed on the resolved target, the autotune config and
    the phase, among the shapes)."""
    if not graph.has_plannable_block(cfg):
        return None
    try:
        return ftl_registry.plan_block(cfg, **kwargs)
    except InfeasibleError as e:
        log.warning("no FTL plan for %s (%s): %s", cfg.name, kwargs, e)
        return None


# ---------------------------------------------------------------------------
# serving: the prefill bucket ladder
# ---------------------------------------------------------------------------

# The prefill bucket ladder: prompts are padded up to the next rung so the
# number of distinct prefill plans (and jit compilations) is bounded by
# the ladder length, not by the number of distinct prompt lengths.
PREFILL_BUCKETS: tuple[int, ...] = (8, 16, 32, 64, 128, 256, 512, 1024,
                                    2048, 4096)


def bucket_m(m: int, buckets: tuple[int, ...] = PREFILL_BUCKETS) -> int:
    """Smallest bucket ≥ ``m``.  Raises when ``m`` exceeds the ladder —
    serving must reject (or truncate) prompts longer than its max bucket
    rather than silently compiling an unbounded set of shapes."""
    if m <= 0:
        raise ValueError(f"bucket_m needs m >= 1, got {m}")
    for b in buckets:
        if b >= m:
            return b
    raise ValueError(
        f"m={m} exceeds the largest prefill bucket {max(buckets)}")


# ===========================================================================
# embeddings
# ===========================================================================

def _init_embed(cfg, key) -> Params:
    dt = jnp.dtype(cfg.dtype)
    p = {"tok": (jax.random.normal(key, (cfg.vocab_size, cfg.d_model),
                                   jnp.float32) * cfg.d_model ** -0.5
                 ).astype(dt)}
    return p


def _embed(cfg, p: Params, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["tok"], tokens, axis=0)


def _sinusoid(seq: int, d: int, offset=0) -> jax.Array:
    """Whisper-style sinusoidal positions (computed, never stored)."""
    pos = jnp.arange(seq)[:, None] + offset
    div = jnp.exp(-jnp.log(10000.0) * jnp.arange(0, d, 2) / d)
    ang = pos * div[None]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _unembed(cfg, params: Params, x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["tok"].T
    else:
        logits = linear(params["lm_head"], x)
    return constrain(logits, "logits")


# ===========================================================================
# public API — decoder-only families
# ===========================================================================

@functools.partial(jax.jit, static_argnums=0)
def init_params(cfg, key) -> Params:
    """Full parameter tree (works under jax.eval_shape for the dry-run).

    Jitted with ``cfg`` static, so the tree is built on the device and
    each leaf is written straight in its dtype: the compiler fuses every
    float32 draw into its cast, and no float32 copy of a stacked weight
    ever exists (eagerly, yi-6b's (32, 4096, 11008) MLP stack alone would
    take 5.8 GB in float32 on a 16 GB chip)."""
    if cfg.is_encoder_decoder:
        return _init_params_encdec(cfg, key)
    ks = jax.random.split(key, 5)
    kinds, n_full, rem_kinds = _layer_split(cfg)
    params: Params = {
        "embed": _init_embed(cfg, ks[0]),
        "layers": _init_stack(cfg, ks[1], kinds, n_full),
        "final_norm": init_norm(cfg.d_model, cfg.norm, jnp.dtype(cfg.dtype)),
    }
    if rem_kinds:
        rks = jax.random.split(ks[2], len(rem_kinds))
        params["rem"] = {
            f"rem{i}": _strip_kind(_init_layer(cfg, rks[i], k))
            for i, k in enumerate(rem_kinds)
        }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_linear(
            ks[3], cfg.d_model, cfg.vocab_size, bias=False,
            dtype=jnp.dtype(cfg.dtype))
    return params


def forward(cfg, params: Params, batch: dict[str, jax.Array]
            ) -> tuple[jax.Array, jax.Array]:
    """Training/eval forward: ``batch['tokens']`` (B, S) → (logits, aux).

    Extra inputs: ``image_embeds`` (vlm), ``frames`` (audio).
    """
    if cfg.is_encoder_decoder:
        return _forward_encdec(cfg, params, batch)
    tokens = batch["tokens"]
    b, s = tokens.shape
    positions = jnp.arange(s)
    ctx = batch.get("image_embeds")
    kinds, _, rem_kinds = _layer_split(cfg)

    x = constrain(_embed(cfg, params["embed"], tokens), "residual")
    # ftl_mode='off' is the full escape hatch (run_block would pin the
    # baseline executors anyway, so skipping the solver at trace time
    # gives the same graph), and MoE FFNs route rather than chain
    plan = (None if cfg.ftl_mode == "off" or cfg.is_moe
            else plan_or_none(cfg, m=s, dtype=cfg.dtype))
    x, aux = _scan_layers(cfg, params["layers"], kinds, x,
                          positions=positions, ctx=ctx, plan=plan)
    for i, kind in enumerate(rem_kinds):
        x, a = _apply_layer(cfg, params["rem"][f"rem{i}"], kind, x,
                            positions=positions, ctx=ctx, plan=plan)
        aux = aux + a
    x = norm(params["final_norm"], x, cfg.norm)
    return _unembed(cfg, params, x), aux


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------

def _layer_prefill(cfg, p: Params, kind: str, x, *, positions, ctx,
                   max_seq: int | None = None, plan=None):
    """Returns (x, cache)."""
    if kind in ("attn", "local"):
        h = norm(p["ln1"], x, cfg.norm)
        window = cfg.local_window if kind == "local" else None
        o, cache = attention_prefill(cfg, p["attn"], h, positions=positions,
                                     causal=True, window=window,
                                     pad_to=max_seq, plan=plan)
        x = x + o
    elif kind == "cross":
        h = norm(p["ln1"], x, cfg.norm)
        o, cache = attention_prefill(cfg, p["attn"], h, positions=positions,
                                     causal=False, kv_source=ctx,
                                     use_rope=False)
        x = x + jnp.tanh(p["xgate"]).astype(x.dtype) * o
    elif kind == "mlstm":
        o, st = recurrent.mlstm_block(cfg, p["mix"], x, return_state=True)
        cache = {"C": st["C"], "n": st["n"], "m": st["m"]}
        x = x + o
    elif kind == "slstm":
        o, cache = recurrent.slstm_block(cfg, p["mix"], x, return_state=True)
        x = x + o
    elif kind == "rec":
        o, cache = recurrent.rec_block(cfg, p["mix"], x, return_state=True)
        x = x + o
    else:
        raise ValueError(kind)
    d, _ = _apply_ffn(cfg, p, x, plan=plan)
    return constrain(x + d, "residual"), cache


def _layer_decode(cfg, p: Params, kind: str, x, cache: Params, pos,
                  plan=None):
    """One-token step.  Returns (x, new_cache)."""
    if kind in ("attn", "local"):
        h = norm(p["ln1"], x, cfg.norm)
        window = cfg.local_window if kind == "local" else None
        o, cache = attention_decode(cfg, p["attn"], h, cache, pos,
                                    window=window)
        x = x + o
    elif kind == "cross":
        h = norm(p["ln1"], x, cfg.norm)
        o, cache = attention_decode(cfg, p["attn"], h, cache, pos, cross=True)
        x = x + jnp.tanh(p["xgate"]).astype(x.dtype) * o
    elif kind == "mlstm":
        o, cache = recurrent.mlstm_block_decode(cfg, p["mix"], x, cache)
        x = x + o
    elif kind == "slstm":
        o, cache = recurrent.slstm_block_decode(cfg, p["mix"], x, cache)
        x = x + o
    elif kind == "rec":
        o, cache = recurrent.rec_block_decode(cfg, p["mix"], x, cache)
        x = x + o
    else:
        raise ValueError(kind)
    d, _ = _apply_ffn(cfg, p, x, plan=plan)
    return constrain(x + d, "residual"), cache


def _init_layer_cache(cfg, kind: str, batch: int, seq: int, *,
                      ctx_len: int = 0) -> Params:
    dt = jnp.dtype(cfg.dtype)
    if kind == "attn":
        return init_kv_cache(cfg, batch, seq, dt)
    if kind == "local":
        return init_kv_cache(cfg, batch, seq, dt, window=cfg.local_window)
    if kind == "cross":
        hk, dh = cfg.n_kv_heads, cfg.resolved_head_dim
        return {"k": jnp.zeros((batch, ctx_len, hk, dh), dt),
                "v": jnp.zeros((batch, ctx_len, hk, dh), dt)}
    if kind == "mlstm":
        return recurrent.init_mlstm_state(cfg, batch)
    if kind == "slstm":
        return recurrent.init_slstm_state(cfg, batch)
    if kind == "rec":
        return recurrent.init_rec_state(cfg, batch)
    raise ValueError(kind)


def init_cache(cfg, batch: int, seq: int) -> Params:
    """Zero decode state for a ``seq``-long context (dry-run decode entry).

    Mirrors init_params' stack structure so pjit shardings line up.
    """
    if cfg.is_encoder_decoder:
        return _init_cache_encdec(cfg, batch, seq)
    kinds, n_full, rem_kinds = _layer_split(cfg)

    def one(_):
        return {f"pos{i}": _init_layer_cache(cfg, k, batch, seq,
                                             ctx_len=cfg.n_image_tokens)
                for i, k in enumerate(kinds)}

    cache: Params = {"layers": jax.vmap(one)(jnp.arange(n_full))}
    if rem_kinds:
        cache["rem"] = {
            f"rem{i}": _init_layer_cache(cfg, k, batch, seq,
                                         ctx_len=cfg.n_image_tokens)
            for i, k in enumerate(rem_kinds)
        }
    return cache


def prefill(cfg, params: Params, batch: dict[str, jax.Array],
            max_seq: int | None = None, *, plan=None,
            last_pos: jax.Array | None = None) -> tuple[jax.Array, Params]:
    """Process the full prompt; returns (last-token logits, decode cache).

    ``max_seq`` right-pads KV caches so subsequent decode steps append in
    place (required whenever decoding continues past the prompt).

    ``plan`` threads a (bucketed) prefill BlockPlan into every layer's
    MLP dispatch — the serving path's plan-cache entry for this shape.
    ``last_pos`` (traced scalar) returns the logits at that token index
    instead of the final one: bucketed serving right-pads prompts up to
    the bucket, so the prompt's true last token sits at
    ``len(prompt) - 1``, not at ``bucket - 1``."""
    if cfg.is_encoder_decoder:
        return _prefill_encdec(cfg, params, batch, max_seq,
                               last_pos=last_pos)
    tokens = batch["tokens"]
    s = tokens.shape[1]
    positions = jnp.arange(s)
    ctx = batch.get("image_embeds")
    kinds, _, rem_kinds = _layer_split(cfg)

    x = constrain(_embed(cfg, params["embed"], tokens), "residual")

    def body(h, pp):
        caches = {}
        for i, kind in enumerate(kinds):
            h, c = _layer_prefill(cfg, pp[f"pos{i}"], kind, h,
                                  positions=positions, ctx=ctx,
                                  max_seq=max_seq, plan=plan)
            caches[f"pos{i}"] = c
        return h, caches

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable
        )
    x, layer_caches = jax.lax.scan(body, x, params["layers"])
    cache: Params = {"layers": layer_caches}
    if rem_kinds:
        cache["rem"] = {}
        for i, kind in enumerate(rem_kinds):
            x, c = _layer_prefill(cfg, params["rem"][f"rem{i}"], kind, x,
                                  positions=positions, ctx=ctx,
                                  max_seq=max_seq, plan=plan)
            cache["rem"][f"rem{i}"] = c
    x = norm(params["final_norm"], x, cfg.norm)
    logits = _unembed(cfg, params, _last_tokens(x, last_pos))
    return logits, cache


def _last_tokens(x: jax.Array, last_pos: jax.Array | None) -> jax.Array:
    """(B, S, D) → (B, 1, D) at ``last_pos`` (None → the final position)."""
    if last_pos is None:
        return x[:, -1:]
    return jax.lax.dynamic_slice_in_dim(x, last_pos, 1, axis=1)


def decode_step(cfg, params: Params, token: jax.Array, cache: Params,
                pos: jax.Array, *, plan=None) -> tuple[jax.Array, Params]:
    """One decode step: ``token`` (B, 1) + cache @ ``pos`` → (logits, cache).

    ``pos`` is a scalar (uniform batch) or a ``(B,)`` vector — continuous
    batching decodes slots at mixed sequence lengths, each row appending
    and masking at its own position (encoder-decoder configs are
    scalar-only: their sinusoidal offset is uniform).  ``plan`` threads
    the m=1 decode BlockPlan into every layer's MLP dispatch."""
    if cfg.is_encoder_decoder:
        return _decode_encdec(cfg, params, token, cache, pos)
    kinds, _, rem_kinds = _layer_split(cfg)
    x = constrain(_embed(cfg, params["embed"], token), "residual")

    def body(h, inp):
        pp, cc = inp
        new = {}
        for i, kind in enumerate(kinds):
            h, c = _layer_decode(cfg, pp[f"pos{i}"], kind, h,
                                 cc[f"pos{i}"], pos, plan=plan)
            new[f"pos{i}"] = c
        return h, new

    x, layer_caches = jax.lax.scan(
        body, x, (params["layers"], cache["layers"]))
    new_cache: Params = {"layers": layer_caches}
    if rem_kinds:
        new_cache["rem"] = {}
        for i, kind in enumerate(rem_kinds):
            x, c = _layer_decode(cfg, params["rem"][f"rem{i}"], kind, x,
                                 cache["rem"][f"rem{i}"], pos, plan=plan)
            new_cache["rem"][f"rem{i}"] = c
    x = norm(params["final_norm"], x, cfg.norm)
    return _unembed(cfg, params, x), new_cache


def _layer_decode_paged(cfg, p: Params, x, pages: Params, blocks, pos,
                        plan=None):
    """One full-attention layer's step over its pages.  Returns (x, the
    new token's {"k", "v"})."""
    h = norm(p["ln1"], x, cfg.norm)
    o, new = paged_decode_attention(cfg, p["attn"], h, pages, blocks, pos)
    x = x + o
    d, _ = _apply_ffn(cfg, p, x, plan=plan)
    return constrain(x + d, "residual"), new


def decode_step_paged(cfg, params: Params, token: jax.Array, pool: Params,
                      tables: jax.Array, pos: jax.Array, *, plan=None
                      ) -> tuple[jax.Array, Params]:
    """One decode step that reads a paged KV pool in place.

    ``pool`` is ``init_cache(cfg, n_pages, bs)``, its batch axis the page
    id: stacked leaves ``(L, n_pages, bs, Hk, Dh)``, remainder leaves
    ``(n_pages, bs, Hk, Dh)``.  ``tables`` ``(B, W)`` holds each row's
    page ids, ``pos`` ``(B,)`` its position; pure-'attn' decoder-only
    configs only.  Each layer reads its own pages inside the layer scan.
    Returns the logits and the step's new K/V in the pool's tree: stacked
    leaves ``(L, B, Hk, Dh)``, remainder ``(B, Hk, Dh)``; storing them is
    the caller's."""
    kinds, n_full, rem_kinds = _layer_split(cfg)
    x = constrain(_embed(cfg, params["embed"], token), "residual")
    n_pages = jax.tree.leaves(pool)[0].shape[-4]
    # (L, n_pages, ...) -> (L * n_pages, ...), a free reshape: layer l's
    # page j is row l * n_pages + j, so one take reads a layer's pages
    stacked = jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]),
                           pool["layers"])

    def body(h, inp):
        pp, layer = inp
        new = {}
        for i in range(len(kinds)):
            h, new[f"pos{i}"] = _layer_decode_paged(
                cfg, pp[f"pos{i}"], h, stacked[f"pos{i}"],
                tables + layer * n_pages, pos, plan=plan)
        return h, new

    x, new_layers = jax.lax.scan(
        body, x, (params["layers"], jnp.arange(n_full)))
    new_kv: Params = {"layers": new_layers}
    if rem_kinds:
        new_kv["rem"] = {}
        for i in range(len(rem_kinds)):
            x, new_kv["rem"][f"rem{i}"] = _layer_decode_paged(
                cfg, params["rem"][f"rem{i}"], x, pool["rem"][f"rem{i}"],
                tables, pos, plan=plan)
    x = norm(params["final_norm"], x, cfg.norm)
    return _unembed(cfg, params, x), new_kv


# ===========================================================================
# encoder-decoder (whisper)
# ===========================================================================
#
# The conv frontend is a stub per the task spec: inputs are precomputed
# frame embeddings (B, encoder_seq, d_model).  Positions are sinusoidal for
# both stacks (whisper's decoder uses a learned table capped at 448; the
# assigned 4k/32k decoder cells are exercised mechanically with sinusoids —
# documented in DESIGN.md §7).

def _init_dec_layer(cfg, key) -> Params:
    """Decoder layer: self-attn + cross-attn + MLP."""
    ks = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.dtype)
    p = _init_layer(cfg, ks[0], "attn")      # ln1 + self-attn (+ln2/mlp)
    p["lnx"] = init_norm(cfg.d_model, cfg.norm, dt)
    p["xattn"] = init_attention(cfg, ks[1], cross=True)
    return p


def _init_params_encdec(cfg, key) -> Params:
    ks = jax.random.split(key, 6)
    dt = jnp.dtype(cfg.dtype)

    def enc_one(k):
        return {"pos0": _strip_kind(_init_layer(cfg, k, "attn"))}

    def dec_one(k):
        return {"pos0": _strip_kind(_init_dec_layer(cfg, k))}

    return {
        "embed": _init_embed(cfg, ks[0]),
        "enc_layers": jax.vmap(enc_one)(
            jax.random.split(ks[1], cfg.n_encoder_layers)),
        "enc_norm": init_norm(cfg.d_model, cfg.norm, dt),
        "layers": jax.vmap(dec_one)(jax.random.split(ks[2], cfg.n_layers)),
        "final_norm": init_norm(cfg.d_model, cfg.norm, dt),
        "lm_head": init_linear(ks[3], cfg.d_model, cfg.vocab_size,
                               bias=False, dtype=dt),
    }


def _encode(cfg, params: Params, frames: jax.Array) -> jax.Array:
    """frames: (B, F, D) stub embeddings → encoder output (B, F, D)."""
    s = frames.shape[1]
    positions = jnp.arange(s)
    x = frames + _sinusoid(s, cfg.d_model).astype(frames.dtype)[None]
    x = constrain(x, "residual")

    def body(h, pp):
        p = pp["pos0"]
        hh = norm(p["ln1"], h, cfg.norm)
        h = h + attention_layer(cfg, p["attn"], hh, positions=positions,
                                causal=False, use_rope=False)
        d, _ = _apply_ffn(cfg, p, h)
        return constrain(h + d, "residual"), None

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable
        )
    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return norm(params["enc_norm"], x, cfg.norm)


def _dec_layer_full(cfg, p: Params, x, enc_out, positions):
    h = norm(p["ln1"], x, cfg.norm)
    x = x + attention_layer(cfg, p["attn"], h, positions=positions,
                            causal=True, use_rope=False)
    h = norm(p["lnx"], x, cfg.norm)
    x = x + attention_layer(cfg, p["xattn"], h, positions=positions,
                            causal=False, kv_source=enc_out, use_rope=False)
    d, _ = _apply_ffn(cfg, p, x)
    return constrain(x + d, "residual")


def _forward_encdec(cfg, params: Params, batch) -> tuple[jax.Array, jax.Array]:
    enc_out = _encode(cfg, params, batch["frames"])
    tokens = batch["tokens"]
    s = tokens.shape[1]
    positions = jnp.arange(s)
    x = _embed(cfg, params["embed"], tokens)
    x = x + _sinusoid(s, cfg.d_model).astype(x.dtype)[None]
    x = constrain(x, "residual")

    def body(h, pp):
        return _dec_layer_full(cfg, pp["pos0"], h, enc_out, positions), None

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable
        )
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = norm(params["final_norm"], x, cfg.norm)
    return _unembed(cfg, params, x), jnp.float32(0.0)


def _init_cache_encdec(cfg, batch: int, seq: int) -> Params:
    dt = jnp.dtype(cfg.dtype)
    hk, dh = cfg.n_kv_heads, cfg.resolved_head_dim

    def one(_):
        return {"pos0": {
            "self": init_kv_cache(cfg, batch, seq, dt),
            "cross": {"k": jnp.zeros((batch, cfg.encoder_seq, hk, dh), dt),
                      "v": jnp.zeros((batch, cfg.encoder_seq, hk, dh), dt)},
        }}

    return {"layers": jax.vmap(one)(jnp.arange(cfg.n_layers))}


def _prefill_encdec(cfg, params: Params, batch, max_seq: int | None = None,
                    *, last_pos: jax.Array | None = None
                    ) -> tuple[jax.Array, Params]:
    enc_out = _encode(cfg, params, batch["frames"])
    tokens = batch["tokens"]
    s = tokens.shape[1]
    positions = jnp.arange(s)
    x = _embed(cfg, params["embed"], tokens)
    x = x + _sinusoid(s, cfg.d_model).astype(x.dtype)[None]

    def body(h, pp):
        p = pp["pos0"]
        hh = norm(p["ln1"], h, cfg.norm)
        o, self_c = attention_prefill(cfg, p["attn"], hh,
                                      positions=positions, causal=True,
                                      use_rope=False, pad_to=max_seq)
        h = h + o
        hh = norm(p["lnx"], h, cfg.norm)
        o, cross_c = attention_prefill(cfg, p["xattn"], hh,
                                       positions=positions, causal=False,
                                       kv_source=enc_out, use_rope=False)
        h = h + o
        d, _ = _apply_ffn(cfg, p, h)
        return constrain(h + d, "residual"), {
            "pos0": {"self": self_c, "cross": cross_c}}

    x, caches = jax.lax.scan(body, x, params["layers"])
    x = norm(params["final_norm"], x, cfg.norm)
    return _unembed(cfg, params, _last_tokens(x, last_pos)), {
        "layers": caches}


def _decode_encdec(cfg, params: Params, token, cache, pos
                   ) -> tuple[jax.Array, Params]:
    x = _embed(cfg, params["embed"], token)
    x = x + _sinusoid(1, cfg.d_model, offset=pos).astype(x.dtype)[None]

    def body(h, inp):
        pp, cc = inp
        p, c = pp["pos0"], cc["pos0"]
        hh = norm(p["ln1"], h, cfg.norm)
        o, self_c = attention_decode(cfg, p["attn"], hh, c["self"], pos,
                                     use_rope=False)
        h = h + o
        hh = norm(p["lnx"], h, cfg.norm)
        o, _ = attention_decode(cfg, p["xattn"], hh, c["cross"], pos,
                                cross=True)
        h = h + o
        d, _ = _apply_ffn(cfg, p, h)
        return h + d, {"pos0": {"self": self_c, "cross": c["cross"]}}

    x, caches = jax.lax.scan(body, x, (params["layers"], cache["layers"]))
    x = norm(params["final_norm"], x, cfg.norm)
    return _unembed(cfg, params, x), {"layers": caches}


# ===========================================================================
# shape-level helpers (dry-run / tests)
# ===========================================================================

def param_shapes(cfg) -> Params:
    """Parameter ShapeDtypeStructs without allocating (dry-run entry)."""
    return jax.eval_shape(
        functools.partial(init_params, cfg), jax.random.PRNGKey(0))


def count_params(cfg) -> int:
    import math

    shapes = param_shapes(cfg)
    return sum(math.prod(l.shape) for l in jax.tree.leaves(shapes))
