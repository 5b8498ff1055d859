"""Plan-driven execution of a whole transformer block.

:func:`run_block` is the runtime counterpart of ``registry.plan_block``:
it walks the planned segments in chain order and dispatches each one to
its bound executor — the GEMM executors for the QKV/output projections,
flash attention (Pallas on TPU, the jnp oracle elsewhere) for the
attention core, and the fused/partial/scan MLP executors for the MLP —
stitching the pre-norm residual structure (norms + residual adds) between
segments exactly like the hand-sequenced ``models/layers.py`` path.

Fallback contract: every binding is *requalified* at run time against the
actual platform and shapes (``ExecContext``).  A plan made on TPU runs
unchanged on CPU because each disqualified binding falls back, per
segment, to the highest-priority executor that does qualify — the XLA
reference path in the worst case.  Numerics: with every stage on its
reference executor the output is bitwise identical to the layer-per-layer
path; planned executors (scan tiling, Pallas kernels) agree within fp32
tolerance (pinned by ``tests/test_block_exec.py``).

Model-side imports (norm/rope live in ``repro.models.layers``) are lazy so
the planning half of ``repro.core.ftl`` stays importable on its own.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro import obs

from . import registry

# runtime-fallback telemetry: how often a plan's binding had to be
# rebound at run time, and how often the rebind landed on an XLA
# reference executor (the worst-case fallback the contract promises)
_C_REQUALIFIED = obs.counter(
    "ftl_requalified_total",
    "bindings rebound at run time (plan executor no longer qualified)",
    ("kind",))
_C_XLA_FALLBACK = obs.counter(
    "ftl_xla_fallback_total",
    "runtime rebinds that landed on an XLA reference executor", ("kind",))
# which executor each stage actually ran: one count per run call, i.e.
# per trace under jax.jit — the ground truth behind resolved_executors()
_C_DISPATCH = obs.counter(
    "ftl_dispatch_total",
    "stage executor runs (per trace under jit)",
    ("phase", "kind", "executor"))


def _runtime_ctx(
    plan: registry.BlockPlan,
    kind: str,
    schedule: str,
    m: int,
    dtype: str,
) -> registry.ExecContext:
    cfg = plan.cfg
    return registry.ExecContext(
        kind=kind,
        platform=registry.platform(),
        schedule=schedule,
        m=m,
        d_model=cfg.d_model,
        d_ff=cfg.moe_d_ff if cfg.is_moe else cfg.d_ff,
        dtype=dtype,
        gated=cfg.mlp_gated,
        act=cfg.mlp_act,
        target=plan.target,
        head_dim=cfg.resolved_head_dim,
        # requalification keeps the plan's regime: a decode plan must not
        # rebind a Pallas kernel just because the runtime shape probe ran
        phase=plan.phase,
    )


def _sub_schedule(plan: registry.BlockPlan, kind: str) -> str:
    if kind == "attention":
        sched = plan.attention_schedule
    elif kind == "mlp":
        sched = plan.mlp_schedule
    else:
        sched = plan.schedule
    if sched == "none":
        # the plan has no ops of this kind (e.g. an MLP-only block graph
        # asked for its attention stage): the attention core is always
        # executable fused (flash streams KV); the MLP conservatively
        # falls back to the layer-per-layer baseline
        return "fused" if kind == "attention" else "unfused"
    return sched


def _stage_executor(
    plan: registry.BlockPlan,
    kind: str,
    ctx: registry.ExecContext,
) -> registry.Executor:
    """The plan's bound executor for ``kind``, or the runtime fallback.

    All bindings of one kind share a single executor (qualification used
    the sub-chain schedule at plan time), so the first binding decides;
    when it no longer qualifies — planned on another platform, shapes
    changed — ``registry.find`` rebinds the best qualifying executor.
    """
    bound = None
    for b in plan.bindings:
        if b.kind == kind:
            ex = registry.get(b.executor)
            if ex.qualifies(ctx):
                return ex
            bound = b.executor
            break
    fb = registry.find(kind, ctx)
    if bound is not None and fb.name != bound:
        _C_REQUALIFIED.labels(kind=kind).inc()
        if fb.backend == "xla":
            _C_XLA_FALLBACK.labels(kind=kind).inc()
    return fb


def _counted_run(run, labels: dict[str, str], *args, **kwargs):
    _C_DISPATCH.labels(**labels).inc()
    return run(*args, **kwargs)


def _bind_target(ex: registry.Executor, plan) -> registry.Executor:
    """Pin the executor to the plan's own target: every run function (the
    Pallas kernels' block-size planning, the scan executors' token-tile
    choice) must price itself against the machine the plan was made for,
    not whatever the process default happens to be at run time.  Each
    run is counted on ``ftl_dispatch_total``."""
    labels = {"phase": plan.phase, "kind": ex.kind, "executor": ex.name}
    return dataclasses.replace(
        ex,
        run=functools.partial(_counted_run, ex.run, labels,
                              target=plan.target),
    )


def _resolve_gemm(plan, mode, m, dtype) -> registry.Executor:
    if mode == "off":
        return _bind_target(registry.get("xla_gemm"), plan)
    ctx = _runtime_ctx(plan, "gemm", plan.schedule, m, dtype)
    return _bind_target(_stage_executor(plan, "gemm", ctx), plan)


def _resolve_attention(plan, mode, m, dtype) -> registry.Executor:
    if mode == "off":
        # the baseline attention path was backend='auto': flash on TPU,
        # the jnp oracle elsewhere — exactly what a 'fused' qualification
        # resolves to
        ctx = _runtime_ctx(plan, "attention", "fused", m, dtype)
        return _bind_target(registry.find("attention", ctx), plan)
    ctx = _runtime_ctx(
        plan,
        "attention",
        _sub_schedule(plan, "attention"),
        m,
        dtype,
    )
    return _bind_target(_stage_executor(plan, "attention", ctx), plan)


def _resolve_mlp(
    plan,
    mode,
    m,
    dtype,
    *,
    d_model=None,
    d_ff=None,
    gated=None,
) -> registry.Executor:
    cfg = plan.cfg
    if mode in ("off", "fused", "scan"):
        # explicit override modes keep their historical meaning; the plan
        # stays authoritative only for 'auto'
        if d_model is None:
            d_model = cfg.d_model
        if d_ff is None:
            d_ff = cfg.moe_d_ff if cfg.is_moe else cfg.d_ff
        if gated is None:
            gated = cfg.mlp_gated
        ex = registry.mlp_executor(
            mode,
            m=m,
            d_model=d_model,
            d_ff=d_ff,
            dtype=dtype,
            gated=gated,
            act=cfg.mlp_act,
            target=plan.target,
        )
        return _bind_target(ex, plan)
    ctx = _runtime_ctx(plan, "mlp", _sub_schedule(plan, "mlp"), m, dtype)
    return _bind_target(_stage_executor(plan, "mlp", ctx), plan)


# public names for the per-stage resolvers: the serving path
# (models.layers' prefill attention and mlp_layer with plan=) dispatches
# its stages through the plan's bindings exactly as run_block would,
# without running run_block
resolve_mlp = _resolve_mlp
resolve_attention = _resolve_attention
resolve_gemm = _resolve_gemm


def resolved_executors(
    plan: registry.BlockPlan,
    *,
    m: int | None = None,
    dtype: str | None = None,
) -> dict[str, str]:
    """Executor names :func:`run_block` would dispatch to right now.

    Reporting/diagnostics hook (serve stats, benchmarks): resolves each
    stage exactly as :func:`run_block` does — honoring ``cfg.ftl_mode``
    and requalifying the plan's bindings against the current platform at
    shape ``m``/``dtype`` (defaulting to the planned ones) — without
    executing anything.
    """
    m = m if m is not None else plan.m
    dtype = dtype or plan.dtype
    mode = plan.cfg.ftl_mode
    return {
        "gemm": _resolve_gemm(plan, mode, m, dtype).name,
        "attention": _resolve_attention(plan, mode, m, dtype).name,
        "mlp": _resolve_mlp(plan, mode, m, dtype).name,
    }


def project(ex: registry.Executor, x, p: dict[str, Any]):
    """One planned projection GEMM (``linear`` routed through a binding)."""
    w = p["w"]
    if ex.backend == "pallas":
        # the Pallas GEMM kernel is 2-D; flatten leading dims around it
        *lead, k = x.shape
        y = ex.run(x.reshape(-1, k), w).reshape(*lead, w.shape[1])
    else:
        y = ex.run(x, w)
    if "b" in p:
        y = y + p["b"]
    return y


def run_block(
    plan: registry.BlockPlan,
    params: dict[str, Any],
    x,  # (B, S, D)
    *,
    positions=None,  # (S,) — defaults to arange(S)
    causal: bool = True,
    window: int | None = None,
    use_rope: bool = True,
    ftl_mode: str | None = None,  # overrides plan.cfg.ftl_mode
):
    """Execute one pre-norm transformer block per its :class:`BlockPlan`.

    ``params`` is one layer's parameter dict from ``models/model.py``
    (``ln1``/``attn``/``ln2``/``mlp``).  Stages present in ``params`` but
    absent from the plan (e.g. local attention of a hybrid config whose
    plannable block is MLP-only) execute through the runtime-fallback
    executor for their kind, so the block always runs end to end.

    ``cfg.ftl_mode`` (overridable per call via ``ftl_mode=``) keeps its
    pre-plan meaning as the escape hatch: with
    ``'off'`` every stage is pinned to the executors the hand-sequenced
    baseline used (plain XLA projections, unfused MLP, the platform's
    default attention kernel), so the compute graph is exactly the
    pre-plan one; ``'fused'``/``'scan'`` force that MLP executor; any
    other mode (``'auto'``) makes the plan's bindings authoritative.
    """
    from repro.distributed.act_sharding import constrain  # lazy: no cycle
    from repro.models import layers as L  # lazy: no cycle

    cfg = plan.cfg
    b, s, _ = x.shape
    dtype = str(x.dtype)
    mode = ftl_mode if ftl_mode is not None else cfg.ftl_mode

    # Each stage runs under a named scope carrying the *resolved*
    # executor (``seg:attn:<executor>`` / ``seg:mlp:<executor>``): the
    # compiled operations keep it in their metadata, so a device trace
    # names the planned segment each operation belongs to.
    if "attn" in params:
        nh, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
        if positions is None:
            positions = jnp.arange(s)
        gemm_ex = _resolve_gemm(plan, mode, s, dtype)
        attn_ex = _resolve_attention(plan, mode, s, dtype)
        with jax.named_scope(f"seg:attn:{attn_ex.name}"):
            ap = params["attn"]
            h = L.norm(params["ln1"], x, cfg.norm)
            q = L._split_heads(project(gemm_ex, h, ap["wq"]), nh)
            k = L._split_heads(project(gemm_ex, h, ap["wk"]), hk)
            v = L._split_heads(project(gemm_ex, h, ap["wv"]), hk)
            if use_rope:
                q = L.rope(q, positions, cfg.rope_theta)
                k = L.rope(k, positions, cfg.rope_theta)
            q = constrain(q.transpose(0, 2, 1, 3), "heads_q")
            k = constrain(k.transpose(0, 2, 1, 3), "heads_kv")
            v = constrain(v.transpose(0, 2, 1, 3), "heads_kv")
            o = attn_ex.run(q, k, v, causal=causal, window=window)
            o = o.transpose(0, 2, 1, 3).reshape(b, s, nh * dh)
            x = constrain(x + project(gemm_ex, o, ap["wo"]), "residual")

    if "mlp" in params:
        mp = params["mlp"]
        w1, w2 = mp["w1"]["w"], mp["w2"]["w"]
        mlp_ex = _resolve_mlp(
            plan,
            mode,
            s,
            dtype,
            d_model=w1.shape[0],
            d_ff=w1.shape[1],
            gated=mp.get("wg", {}).get("w") is not None,
        )
        with jax.named_scope(f"seg:mlp:{mlp_ex.name}"):
            wg = mp.get("wg", {}).get("w")
            h = L.norm(params["ln2"], x, cfg.norm)
            y = mlp_ex.run(
                h,
                w1,
                w2,
                wg,
                mp["w1"].get("b"),
                mp["w2"].get("b"),
                act=cfg.mlp_act,
            )
            x = constrain(x + y, "residual")

    return x
