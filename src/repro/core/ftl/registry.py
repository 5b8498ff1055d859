"""Executor registry: planned fusion groups → concrete implementations.

The planner (graph → partition) decides *what* to fuse; this module
decides *who runs it*.  Every executor advertises the pattern it
implements (``kind``), its backend, and a qualification predicate over an
:class:`ExecContext`; ``find`` returns the highest-priority qualifying
executor.  Consumers get two entry points:

* :func:`plan_block` — plan a whole transformer block for a config and
  bind every planned segment to an executor (the one API
  ``models/layers.py``, ``launch/*`` and the benchmarks consume).
* :func:`mlp_executor` — resolve an MLP execution callable for a given
  ``ftl_mode``; ``'auto'`` is plan-driven: the partitioner's chosen
  schedule selects between the Pallas fused kernel, the portable scan
  executor, and the layer-per-layer baseline.

Adding a new layer kind = one IR builder (graph.py) + one registry entry
here — no per-consumer wiring.

Kernel imports are lazy (inside the run functions) so the planning side
of ``repro.core.ftl`` stays importable without pulling in Pallas.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Mapping

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import hw as hwlib

from . import executor_xla, graph, partition
from .partition import ChainPlan
from .solver import InfeasibleError, solve

_C_PLAN_BLOCK = obs.counter(
    "ftl_plan_block_total", "plan_block calls", ("phase",))


# ---------------------------------------------------------------------------
# registry core
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ExecContext:
    """Everything an executor needs to decide whether it qualifies."""

    kind: str                    # 'mlp' | 'attention' | 'gemm'
    platform: str                # 'tpu' | 'cpu' | 'gpu'
    schedule: str                # 'fused' | 'partial' | 'unfused'
    m: int = 0
    d_model: int = 0
    d_ff: int = 0
    dtype: str = "bfloat16"
    gated: bool = False
    act: str = "gelu"
    target: hwlib.Target | None = None   # the plan's memory hierarchy
    head_dim: int = 0            # attention kernels' footprint probe
    # 'prefill' (full-sequence, compute-heavy) vs 'decode' (m=1 against a
    # cache, memory-bound).  Decode shapes never fill an MXU lane tile, so
    # the Pallas kernels disqualify themselves there and the registry
    # binds the XLA executors instead (decode-shape qualification).
    phase: str = "prefill"


def _vmem_class(target: hwlib.Target | None) -> bool:
    """Capacity-class fallback for *shape-less* contexts: can the
    target's fast level plausibly host a Pallas double-buffered
    pipeline at all?  When the context carries shapes, qualification
    uses the kernel's actual tile footprint instead
    (:func:`_mlp_kernel_fits` / :func:`_attention_kernel_fits`)."""
    return target is None or target.fast.capacity_bytes >= 4 * (1 << 20)


@functools.lru_cache(maxsize=1024)
def _mlp_kernel_footprint_fits(m: int, d_model: int, d_ff: int, dtype: str,
                               gated: bool, act: str,
                               target: hwlib.Target) -> bool:
    """True when the fused-MLP Pallas kernel's own dataflow (K and N
    whole — weight panels resident, M/F tiled) has a tile assignment
    whose double-buffered footprint fits the target's fast level: the
    same solve ``ops.plan_mlp_blocks`` runs to pick the kernel's block
    sizes, so an executor qualifies iff its kernel is actually
    plannable at this shape on this machine."""
    g = graph.mlp_graph(m=m, d_model=d_model, d_ff=d_ff, dtype=dtype,
                        gated=gated, act=act)
    try:
        solve(g.group(0, g.n_ops), target=target,
              whole_dims=frozenset({"K", "N"}))
        return True
    except InfeasibleError:
        return False


@functools.lru_cache(maxsize=1024)
def _partial_mlp_footprint_fits(m: int, d_model: int, d_ff: int,
                                dtype: str, act: str,
                                target: hwlib.Target) -> bool:
    """The *partial* Pallas path runs two separate kernels (gemm_act for
    the up projection, gemm for the down projection), so each GEMM needs
    only its own weight panel resident — probe them independently
    (matching ``ops.plan_gemm_blocks``), not the fused whole-K/N solve."""
    try:
        solve(graph.gemm_act_graph(m=m, k=d_model, n=d_ff, dtype=dtype,
                                   act=act).group(0, 2), target=target)
        solve(graph.gemm_chain_graph(m=m, dims_kn=[d_ff, d_model],
                                     dtype=dtype).group(0, 1),
              target=target)
        return True
    except InfeasibleError:
        return False


@functools.lru_cache(maxsize=1024)
def _attention_kernel_footprint_fits(m: int, head_dim: int, dtype: str,
                                     target: hwlib.Target) -> bool:
    """Flash-attention analogue: head dim whole (the kernel's online
    softmax streams Tk), q/k tiles solved against the fast level."""
    g = graph.attention_graph(q_len=m, kv_len=m, head_dim=head_dim,
                              dtype=dtype)
    try:
        partition.plan_fixed(g, (), target=target)
        return True
    except InfeasibleError:
        return False


def _mlp_kernel_fits(c: ExecContext) -> bool:
    """Per-target Pallas MLP qualification (ROADMAP item): the kernel's
    *actual tile footprint* at the context's shapes must be plannable on
    the target — a weight panel that cannot fit the fast level
    disqualifies the kernel no matter how roomy the capacity class says
    the scratchpad is.  The VMEM-class floor stays as a conjunct: the
    Pallas pipeline machinery itself needs TPU-VMEM-scale headroom, and
    a plan made for a KiB-scale scratchpad must not bind these kernels
    even when its (tiny) tiles would technically fit."""
    if c.target is None:
        return True
    if not _vmem_class(c.target):
        return False
    if not (c.m and c.d_model and c.d_ff):
        return True
    return _mlp_kernel_footprint_fits(c.m, c.d_model, c.d_ff, c.dtype,
                                      c.gated, c.act, c.target)


def _partial_mlp_kernel_fits(c: ExecContext) -> bool:
    """Footprint probe for the partial Pallas MLP: per-GEMM, since its
    kernels run sequentially and never co-reside both weight panels."""
    if c.target is None:
        return True
    if not _vmem_class(c.target):
        return False
    if not (c.m and c.d_model and c.d_ff):
        return True
    return _partial_mlp_footprint_fits(c.m, c.d_model, c.d_ff, c.dtype,
                                       c.act, c.target)


def _attention_kernel_fits(c: ExecContext) -> bool:
    if c.target is None:
        return True
    if not _vmem_class(c.target):
        return False
    if not (c.m and c.head_dim):
        return True
    return _attention_kernel_footprint_fits(c.m, c.head_dim, c.dtype,
                                            c.target)


@dataclasses.dataclass(frozen=True)
class Executor:
    """A registered implementation of one planned-group pattern."""

    name: str
    kind: str
    backend: str                 # 'pallas' | 'xla'
    priority: int
    qualifies: Callable[[ExecContext], bool]
    run: Callable | None = None


_REGISTRY: dict[str, Executor] = {}


def register(ex: Executor, *, override: bool = False) -> Executor:
    if ex.name in _REGISTRY and not override:
        raise ValueError(f"executor {ex.name!r} already registered")
    _REGISTRY[ex.name] = ex
    return ex


def get(name: str) -> Executor:
    return _REGISTRY[name]


def executors(kind: str | None = None) -> list[Executor]:
    exs = [e for e in _REGISTRY.values() if kind is None or e.kind == kind]
    return sorted(exs, key=lambda e: -e.priority)


def find(kind: str, ctx: ExecContext) -> Executor:
    """Highest-priority executor of ``kind`` that qualifies for ``ctx``.

    Raises :class:`LookupError` spelling out the full qualification
    context and every executor that was considered (name, backend,
    priority), so a failed binding is diagnosable from the message alone.
    """
    considered = executors(kind)
    for ex in considered:
        if ex.qualifies(ctx):
            return ex
    fields = ", ".join(
        f"{f.name}={getattr(ctx, f.name)!r}"
        for f in dataclasses.fields(ctx)
    )
    tried = ", ".join(
        f"{e.name} (backend={e.backend}, priority={e.priority})"
        for e in considered
    ) or "<none registered for this kind>"
    raise LookupError(
        f"no executor of kind={kind!r} qualifies for "
        f"ExecContext({fields}); considered in priority order: {tried}"
    )


def platform() -> str:
    return jax.default_backend()


# ---------------------------------------------------------------------------
# built-in MLP executors
# ---------------------------------------------------------------------------

def _run_pallas_fused_mlp(x, w1, w2, wg, b1, b2, *, act, target=None):
    from repro.kernels import ops  # lazy: Pallas stack
    return ops.fused_mlp(x, w1, w2, wg, b1, b2, act=act, backend="pallas",
                         target=target)


def _run_pallas_partial_mlp(x, w1, w2, wg, b1, b2, *, act, target=None):
    """Partial schedule on the Pallas kernels: the paper's fused
    GEMM+activation kernel for the up projection, a plain GEMM kernel for
    the down projection (non-gated only — the gated epilogue has no
    dedicated kernel yet)."""
    from repro.kernels import ops
    *lead, m, k = x.shape
    xf = x.reshape(-1, k)
    h = ops.gemm_act(xf, w1, b1, act=act, backend="pallas", target=target)
    y = ops.gemm(h, w2, backend="pallas", target=target)
    if b2 is not None:
        y = y + b2
    return y.reshape(*lead, m, w2.shape[1])


@functools.lru_cache(maxsize=512)
def _scan_tile(m: int, d_model: int, d_ff: int, dtype: str, gated: bool,
               act: str, target: hwlib.Target) -> int:
    """Token-tile for the scan executor from its own kernel policy: the
    scan tiles M only, so K/F/N stay whole and the solver picks the
    largest M tile that fits the target's fast level.  Falls back to a
    power-of-two divisor when even the smallest tile does not fit (XLA
    will still run — the budget is a planning target, not a hard limit on
    this backend)."""
    g = graph.mlp_graph(m=m, d_model=d_model, d_ff=d_ff, dtype=dtype,
                        gated=gated, act=act)
    try:
        plan = solve(g.group(0, g.n_ops), target=target,
                     whole_dims=frozenset({"K", "F", "N"}))
        return plan.tile("M")
    except InfeasibleError:
        for cand in (1024, 512, 256, 128):
            if m % cand == 0 and cand < m:
                return cand
        return m


def _run_xla_scan_mlp(x, w1, w2, wg, b1, b2, *, act, target=None):
    m = x.shape[-2]
    tile = _scan_tile(m, w1.shape[0], w1.shape[1], str(x.dtype),
                      wg is not None, act,
                      target if target is not None
                      else hwlib.default_target())
    return executor_xla.mlp_scan(x, w1, w2, wg, b1, b2, act=act, tile_m=tile)


def _run_xla_partial_mlp(x, w1, w2, wg, b1, b2, *, act, target=None):
    m = x.shape[-2]
    tile = _scan_tile(m, w1.shape[0], w1.shape[1], str(x.dtype),
                      wg is not None, act,
                      target if target is not None
                      else hwlib.default_target())
    return executor_xla.mlp_partial_scan(x, w1, w2, wg, b1, b2, act=act,
                                         tile_m=tile)


def _run_xla_unfused_mlp(x, w1, w2, wg, b1, b2, *, act, target=None):
    from repro.distributed.act_sharding import constrain  # lazy: no cycle
    from repro.kernels import ref
    h = x @ w1
    if b1 is not None:
        h = h + b1
    h = ref.act_fn(act)(h.astype(jnp.float32)).astype(x.dtype)
    if wg is not None:
        h = h * (x @ wg)
    h = constrain(h, "ffn_hidden")
    y = h @ w2
    if b2 is not None:
        y = y + b2
    return y


def _run_pallas_attention(q, k, v, *, target=None, **kw):
    from repro.kernels import ops
    return ops.attention(q, k, v, backend="pallas", target=target, **kw)


def _run_ref_attention(q, k, v, *, target=None, **kw):
    from repro.kernels import ops
    return ops.attention(q, k, v, backend="ref", target=target, **kw)


def _run_pallas_gemm(x, w, *, target=None):
    from repro.kernels import ops
    return ops.gemm(x, w, backend="pallas", target=target)


def _run_xla_gemm(x, w, *, target=None):
    return x @ w


register(Executor(
    name="pallas_fused_mlp", kind="mlp", backend="pallas", priority=100,
    qualifies=lambda c: (c.platform == "tpu" and c.schedule == "fused"
                         and c.phase != "decode" and _mlp_kernel_fits(c)),
    run=_run_pallas_fused_mlp))
register(Executor(
    name="pallas_partial_mlp", kind="mlp", backend="pallas", priority=90,
    qualifies=lambda c: (c.platform == "tpu" and c.schedule == "partial"
                         and c.phase != "decode"
                         and not c.gated and _partial_mlp_kernel_fits(c)),
    run=_run_pallas_partial_mlp))
register(Executor(
    name="xla_scan_mlp", kind="mlp", backend="xla", priority=50,
    qualifies=lambda c: c.schedule == "fused",
    run=_run_xla_scan_mlp))
register(Executor(
    name="xla_partial_scan_mlp", kind="mlp", backend="xla", priority=40,
    qualifies=lambda c: c.schedule == "partial",
    run=_run_xla_partial_mlp))
register(Executor(
    name="xla_unfused_mlp", kind="mlp", backend="xla", priority=10,
    qualifies=lambda c: True,
    run=_run_xla_unfused_mlp))
register(Executor(
    name="pallas_flash_attention", kind="attention", backend="pallas",
    priority=100,
    qualifies=lambda c: (c.platform == "tpu" and c.schedule != "unfused"
                         and c.phase != "decode"
                         and _attention_kernel_fits(c)),
    run=_run_pallas_attention))
register(Executor(
    name="xla_ref_attention", kind="attention", backend="xla", priority=10,
    qualifies=lambda c: True,
    run=_run_ref_attention))
register(Executor(
    name="pallas_gemm", kind="gemm", backend="pallas", priority=100,
    qualifies=lambda c: c.platform == "tpu" and c.phase != "decode",
    run=_run_pallas_gemm))
register(Executor(
    name="xla_gemm", kind="gemm", backend="xla", priority=10,
    qualifies=lambda c: True,
    run=_run_xla_gemm))


# ---------------------------------------------------------------------------
# block-level planning: the one API every consumer goes through
# ---------------------------------------------------------------------------

def _segment_kind(seg: partition.Segment) -> str:
    names = seg.op_names()
    if any(n.startswith("attn.") for n in names):
        return "attention"
    if any(n.startswith("mlp.") or n.startswith("gemm") for n in names):
        return "mlp" if any(n.startswith("mlp.") for n in names) else "gemm"
    return "gemm"


@dataclasses.dataclass(frozen=True)
class GroupBinding:
    segment: partition.Segment
    kind: str
    executor: str


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    """A planned transformer block with per-segment executor bindings.

    Carries the config, planning shape and memory-hierarchy target it was
    made for so :func:`run_block` can execute it (and requalify bindings)
    without any side-channel state.
    """

    chain: ChainPlan
    bindings: tuple[GroupBinding, ...]
    platform: str
    cfg: object = None
    m: int = 0
    dtype: str = ""
    # set when the plan came out of the DES-scored autotuner
    # (repro.tune.TuneResult); the chain above is then the tuned chain
    # and chain.target may be a depth-modified variant of the request's
    # target.
    tune: object = None
    # serving regime the plan was made for: 'prefill' (full-sequence) or
    # 'decode' (m=1 against a cache).  Part of every plan-cache key; the
    # bindings were qualified with this phase in their ExecContext.
    phase: str = "prefill"

    @property
    def target(self) -> hwlib.Target:
        return self.chain.target

    @property
    def graph(self) -> graph.OpGraph:
        return self.chain.graph

    @property
    def schedule(self) -> str:
        return self.chain.schedule

    @property
    def traffic_bytes(self) -> int:
        return self.chain.traffic_bytes

    @property
    def per_level_traffic(self) -> dict[str, int]:
        return self.chain.per_level_traffic

    def _sub_schedule(self, prefix: str) -> str:
        ops = [op.name for op in self.graph.ops
               if op.name.startswith(prefix)]
        segs = [s for s in self.chain.segments
                if any(n.startswith(prefix) for n in s.op_names())]
        if not ops or not segs:
            return "none"
        if len(segs) == 1:
            return "fused"
        if len(segs) == len(ops):
            return "unfused"
        return "partial"

    @property
    def mlp_schedule(self) -> str:
        return self._sub_schedule("mlp.")

    @property
    def attention_schedule(self) -> str:
        return self._sub_schedule("attn.")

    def summary(self) -> str:
        lines = [self.chain.summary(),
                 f"  executors ({self.platform}, planned for "
                 f"{self.target.name}):"]
        for b in self.bindings:
            lines.append(
                f"    [{b.segment.lo}:{b.segment.hi}] {b.kind:9s} -> "
                f"{b.executor}"
            )
        return "\n".join(lines)


def _freeze(d: Mapping[str, int] | None):
    return tuple(sorted(d.items())) if d else None


@functools.lru_cache(maxsize=128)
def _plan_block_cached(cfg, m: int, dtype: str | None,
                       target: hwlib.Target, sharded: tuple | None,
                       plat: str, residual: bool,
                       autotune=None, phase: str = "prefill") -> BlockPlan:
    g = graph.block_graph(cfg, m=m, dtype=dtype, residual=residual)
    sharded_d = dict(sharded) if sharded else None
    tune_result = None
    if autotune is not None:
        from repro.tune import autotune_chain  # lazy: pulls in repro.sim
        tune_result = autotune_chain(g, target=target, config=autotune,
                                     sharded_sizes=sharded_d)
        chain = tune_result.chain
        # bindings qualify against the tuned hierarchy (possibly
        # depth-modified), the one the chain was scored on
        target = chain.target
    else:
        chain = partition.plan_chain(g, target=target,
                                     sharded_sizes=sharded_d)
    shell = BlockPlan(chain=chain, bindings=(), platform=plat, cfg=cfg,
                      m=m, dtype=dtype or cfg.dtype, phase=phase)
    sub = {"mlp": shell.mlp_schedule, "attention": shell.attention_schedule}
    bindings = []
    for seg in chain.segments:
        kind = _segment_kind(seg)
        # qualification uses the sub-chain's own fusion state: a split
        # attention core must not bind to the flash kernel, etc.
        sched = sub.get(kind, chain.schedule)
        sched = chain.schedule if sched == "none" else sched
        ctx = ExecContext(
            kind=kind, platform=plat, schedule=sched,
            m=m, d_model=cfg.d_model,
            d_ff=cfg.moe_d_ff if cfg.is_moe else cfg.d_ff,
            dtype=dtype or cfg.dtype, gated=cfg.mlp_gated, act=cfg.mlp_act,
            target=target, head_dim=cfg.resolved_head_dim, phase=phase)
        bindings.append(GroupBinding(segment=seg, kind=kind,
                                     executor=find(kind, ctx).name))
    return BlockPlan(chain=chain, bindings=tuple(bindings), platform=plat,
                     cfg=cfg, m=m, dtype=dtype or cfg.dtype,
                     tune=tune_result, phase=phase)


def plan_block(
    cfg,
    *,
    m: int,
    dtype: str | None = None,
    target: hwlib.Target | None = None,
    sharded_sizes: Mapping[str, int] | None = None,
    residual: bool = True,
    autotune=None,
    phase: str = "prefill",
) -> BlockPlan:
    """Plan one transformer block of ``cfg`` at ``m`` tokens on ``target``
    (None → the default target) and bind every planned fusion group to the
    best qualifying executor.

    ``autotune`` (a :class:`repro.tune.AutotuneConfig`) swaps the analytic
    argmin for the simulator-scored search: the returned plan's chain is
    the DES-runtime-optimal candidate (simulated runtime ≤ the analytic
    plan's, by construction) and ``BlockPlan.tune`` carries the full
    :class:`~repro.tune.TuneResult`.  The config is part of the plan
    cache key — tuned and untuned plans never alias.

    ``phase`` ('prefill' | 'decode') runs the same partition DP at the
    regime's own shape: decode plans (``m=1`` against a cache) are
    memory-bound, so the max(compute, transfer) objective generally picks
    different cuts than prefill, and their bindings never qualify the
    Pallas kernels (decode-shape qualification).  Phase is part of the
    plan-cache key — a decode plan and a prefill plan for the same shapes
    never alias."""
    if phase not in ("prefill", "decode"):
        raise ValueError(f"phase must be 'prefill' or 'decode', "
                         f"got {phase!r}")
    target = target if target is not None else hwlib.default_target()
    _C_PLAN_BLOCK.labels(phase=phase).inc()
    with obs.span(f"plan_block:{phase}", "planner"):
        return _plan_block_cached(cfg, m, dtype, target,
                                  _freeze(sharded_sizes), platform(),
                                  residual, autotune, phase)


# ---------------------------------------------------------------------------
# MLP mode resolution for models/layers.py
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1024)
def _mlp_executor_cached(mode: str, m: int, d_model: int, d_ff: int,
                         dtype: str, gated: bool, act: str,
                         target: hwlib.Target, plat: str) -> Executor:
    if mode == "off":
        ex = get("xla_unfused_mlp")
    elif mode == "fused":
        # explicit request for the Pallas kernel (interpret mode off-TPU)
        ex = get("pallas_fused_mlp")
    elif mode == "scan":
        ex = get("xla_scan_mlp")
    elif mode == "auto":
        g = graph.mlp_graph(m=m, d_model=d_model, d_ff=d_ff, dtype=dtype,
                            gated=gated, act=act)
        try:
            schedule = partition.plan_chain(g, target=target).schedule
        except InfeasibleError:
            schedule = "unfused"
        ctx = ExecContext(kind="mlp", platform=plat, schedule=schedule,
                          m=m, d_model=d_model, d_ff=d_ff, dtype=dtype,
                          gated=gated, act=act, target=target)
        ex = find("mlp", ctx)
    else:
        raise ValueError(f"unknown ftl_mode {mode!r}")
    # run under the target the schedule was resolved with, not whatever the
    # process default happens to be at run time (affects the scan
    # executors' token-tile choice)
    return dataclasses.replace(
        ex, run=functools.partial(ex.run, target=target))


def mlp_executor(
    mode: str,
    *,
    m: int,
    d_model: int,
    d_ff: int,
    dtype: str,
    gated: bool,
    act: str,
    target: hwlib.Target | None = None,
) -> Executor:
    """Resolve the MLP executor for ``ftl_mode`` at the given shapes on
    ``target`` (None → the default target).

    ``'auto'`` is plan-driven: the fusion partitioner's chosen schedule
    picks the implementation (Pallas fused kernel on TPU, scan executor
    for a fused/partial schedule elsewhere, layer-per-layer baseline when
    the planner rejects fusion)."""
    target = target if target is not None else hwlib.default_target()
    return _mlp_executor_cached(mode, m, d_model, d_ff, dtype, gated, act,
                                target, platform())


# ---------------------------------------------------------------------------
# planner-cache registry: one ledger over every memoized planning entry
# ---------------------------------------------------------------------------

_PLAN_CACHES: dict[str, Callable] = {}


def register_plan_cache(name: str, fn: Callable) -> Callable:
    """Enroll an ``lru_cache``-wrapped planner in the plan-cache ledger.

    Higher layers (``repro.core.ftl``'s shims, ``repro.tune``) self-register
    at import, so :func:`plan_cache_stats` covers every *imported*
    planner cache without this module depending on them.  Returns ``fn``
    so the call composes as a decorator-style tail."""
    _PLAN_CACHES[name] = fn
    return fn


def plan_cache_stats() -> dict[str, dict[str, int]]:
    """Hit/miss/size counters for every registered planner cache —
    surfaced by ``ServeEngine.plan_report()`` so a serving run can show
    its plans came from cache, not replanning."""
    return {
        name: {
            "hits": info.hits,
            "misses": info.misses,
            "size": info.currsize,
            "maxsize": info.maxsize,
        }
        for name, fn in sorted(_PLAN_CACHES.items())
        for info in (fn.cache_info(),)
    }


def clear_plan_caches() -> None:
    """Drop every registered planner cache (tests; target registry
    edits that would otherwise serve stale plans)."""
    for fn in _PLAN_CACHES.values():
        fn.cache_clear()


for _fn in (_mlp_kernel_footprint_fits, _partial_mlp_footprint_fits,
            _attention_kernel_footprint_fits, _scan_tile,
            _plan_block_cached, _mlp_executor_cached):
    register_plan_cache(f"registry.{_fn.__name__}", _fn)
for _fn in (partition._plan_chain_cached, partition._plan_chain_top_k_cached):
    register_plan_cache(f"partition.{_fn.__name__}", _fn)
del _fn


def _collect_plan_caches(reg) -> None:
    """Pull-style re-expression of the PR-8 plan-cache ledger on the
    metrics registry: :func:`plan_cache_stats` stays the canonical
    bookkeeping (lru_cache's own counters), re-read at scrape time as
    gauges — never double-counted on the hot path, and automatically in
    sync with :func:`clear_plan_caches` resets."""
    g_hits = reg.gauge("ftl_plan_cache_hits",
                       "plan-cache hits (ledger snapshot)", ("cache",))
    g_miss = reg.gauge("ftl_plan_cache_misses",
                       "plan-cache misses (ledger snapshot)", ("cache",))
    g_size = reg.gauge("ftl_plan_cache_size",
                       "plan-cache entries (ledger snapshot)", ("cache",))
    for name, row in plan_cache_stats().items():
        g_hits.labels(cache=name).set(row["hits"])
        g_miss.labels(cache=name).set(row["misses"])
        g_size.labels(cache=name).set(row["size"])


obs.register_collector(_collect_plan_caches)


# ---------------------------------------------------------------------------
# block execution: walk the plan, dispatch every segment
# ---------------------------------------------------------------------------

def run_block(plan: BlockPlan, params, x, **kwargs):
    """Execute one transformer block through its :class:`BlockPlan`.

    Walks the planned segments in order and dispatches each one to its
    bound executor (Pallas flash attention / fused MLP kernels on TPU,
    the XLA scan executors elsewhere), stitching the norms and residual
    adds between segments.  Bindings are requalified against the runtime
    shapes/platform; a binding that no longer qualifies falls back,
    per segment, to the best qualifying (ultimately XLA reference)
    executor.  See :mod:`repro.core.ftl.executor_block`.
    """
    from . import executor_block  # lazy: keeps planning importable alone
    return executor_block.run_block(plan, params, x, **kwargs)
