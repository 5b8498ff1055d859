"""Measurement records + wall-clock harness for target calibration.

Every planning decision in the stack is priced by :class:`~repro.core.hw.
Target` constants that were, until now, hand-typed presets.  This module
measures what this host actually does — isolated GEMM / elementwise
microbenchmarks, DMA-proxy copies at several working-set sizes, and
whole-block ref-vs-plan wall-clock in the ``bench_block`` style — and
records each run as a :class:`Measurement`: the observed seconds next to
the *model features* the roofline prices it with (per-level bytes and
transfer counts, per-kind FLOPs).

A measurement is deliberately self-contained: :func:`modeled_measurement_s`
re-prices it on any :class:`Target` through the repo's one shared formula
(``Target.compute_time_by_kind`` / ``Target.transfer_time`` composed by
``hw.modeled_runtime``), so the fitter (:mod:`repro.calib.fit`) and the
drift gate never restate the cost model.

Feature attribution uses the *base* target's level structure
(``Target.assign_homes`` over the same footprints the cost model would
see).  Calibration never changes capacities or level names — only
bandwidth / DMA-setup / FLOP-rate constants — so features extracted
against the base stay valid for the calibrated target.

Timing discipline: one untimed compile call, then ``warmup`` timed-path
iterations (plan-cache and dispatch cost must not land in the first
sample — the bench_block skew this PR also fixes), then ``min`` over
``repeats``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Iterable, Mapping, Sequence

from repro.core import hw as hwlib

# branch hints: which side of the roofline max() a microbenchmark was
# designed to isolate.  The fitter only fits hinted single-segment
# measurements; unhinted ones (whole blocks) are validation-only.
COMPUTE = "compute"
TRANSFER = "transfer"

DEFAULT_REPEATS = 5
DEFAULT_WARMUP = 1


def _freeze(d: Mapping) -> tuple:
    return tuple(sorted(d.items()))


@dataclasses.dataclass(frozen=True)
class SegmentFeatures:
    """Roofline features of one sequential segment of a measured run.

    Mirrors exactly what :meth:`Target.transfer_time` and
    :meth:`Target.compute_time_by_kind` consume, so re-pricing a
    measurement on a candidate target is a pure lookup — no re-planning,
    no shape knowledge."""

    flops_by_kind: tuple[tuple[str, float], ...] = ()
    bytes_by_level: tuple[tuple[str, int], ...] = ()
    transfers_by_level: tuple[tuple[str, int], ...] = ()
    repeat: int = 1

    def compute_s(self, target: hwlib.Target) -> float:
        return target.compute_time_by_kind(dict(self.flops_by_kind))

    def transfer_s(self, target: hwlib.Target) -> float:
        return target.transfer_time(dict(self.bytes_by_level),
                                    dict(self.transfers_by_level))

    def modeled_s(self, target: hwlib.Target) -> float:
        """``hw.modeled_runtime`` of this segment — the one shared
        overlap rule, times the segment's multiplicity."""
        return self.repeat * hwlib.modeled_runtime(
            self.compute_s(target), self.transfer_s(target))


@dataclasses.dataclass(frozen=True)
class Measurement:
    """One wall-clock observation plus the features that model it.

    ``branch`` is the microbenchmark's design hint (:data:`COMPUTE` /
    :data:`TRANSFER`): which side of the roofline ``max`` the run was
    built to isolate, hence which linear subsystem of the fit its row
    belongs to.  ``None`` (whole-block measurements) means the
    measurement only validates the fit — mixed segments cannot be
    attributed to a single branch."""

    name: str
    kind: str                    # gemm | elementwise | dma | block
    measured_s: float
    segments: tuple[SegmentFeatures, ...]
    branch: str | None = None
    meta: tuple[tuple[str, float | int | str], ...] = ()

    def __post_init__(self):
        if self.measured_s <= 0:
            raise ValueError(
                f"measurement {self.name}: measured_s must be positive, "
                f"got {self.measured_s}")
        if self.branch not in (None, COMPUTE, TRANSFER):
            raise ValueError(
                f"measurement {self.name}: unknown branch {self.branch!r}")
        if not self.segments:
            raise ValueError(f"measurement {self.name}: no segments")


def modeled_measurement_s(target: hwlib.Target, m: Measurement) -> float:
    """Modeled seconds of ``m`` on ``target``: segments run sequentially,
    each overlapping its own DMA — ``Σ_seg max(compute, transfer)``,
    the same objective ``ChainPlan.modeled_runtime_s`` sums."""
    return sum(seg.modeled_s(target) for seg in m.segments)


# ---------------------------------------------------------------------------
# feature extraction
# ---------------------------------------------------------------------------

def features_from_chain(chain) -> tuple[SegmentFeatures, ...]:
    """Per-segment roofline features of a planned chain (``ChainPlan`` or
    a ``BlockPlan`` via ``.chain``) — what a whole-block wall-clock
    measurement is modeled with."""
    chain = getattr(chain, "chain", chain)
    feats = []
    for seg in chain.segments:
        rep = seg.repeat
        flops: dict[str, float] = {}
        for oc in seg.plan.report.op_compute:
            # effective FLOPs: rate-discount by MXU lane utilization the
            # same way compute_costs prices the op
            flops[oc.kind] = flops.get(oc.kind, 0.0) \
                + oc.flops / oc.utilization
        feats.append(SegmentFeatures(
            flops_by_kind=_freeze(flops),
            bytes_by_level=_freeze(seg.plan.report.per_level_traffic),
            transfers_by_level=_freeze(seg.plan.report.per_level_transfers),
            repeat=rep,
        ))
    return tuple(feats)


def _streamed_features(
    base: hwlib.Target,
    footprints: Mapping[str, int],
    flops_by_kind: Mapping[str, float],
) -> SegmentFeatures:
    """Single-block features: every tensor moved exactly once between its
    home backing level and the fast memory (the min-traffic bound), homes
    assigned by the *base* structure exactly as the cost model would."""
    homes = base.assign_homes(dict(footprints))
    by_level: dict[str, int] = {}
    n_level: dict[str, int] = {}
    for name, b in footprints.items():
        lv = homes[name].name
        by_level[lv] = by_level.get(lv, 0) + int(b)
        n_level[lv] = n_level.get(lv, 0) + 1
    return SegmentFeatures(
        flops_by_kind=_freeze(dict(flops_by_kind)),
        bytes_by_level=_freeze(by_level),
        transfers_by_level=_freeze(n_level),
    )


# ---------------------------------------------------------------------------
# wall-clock harness
# ---------------------------------------------------------------------------

def wallclock_s(fn: Callable, *args, repeats: int = DEFAULT_REPEATS,
                warmup: int = DEFAULT_WARMUP) -> float:
    """``min`` wall-clock seconds of ``fn(*args)`` over ``repeats`` timed
    iterations, after one untimed compile call plus ``warmup`` timed-path
    iterations (dispatch/plan-cache cost stays out of the samples)."""
    out = fn(*args)
    _block(out)
    for _ in range(warmup):
        _block(fn(*args))
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        _block(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _block(out):
    if hasattr(out, "block_until_ready"):
        out.block_until_ready()
    elif isinstance(out, (tuple, list)):
        for o in out:
            _block(o)
    return out


# ---------------------------------------------------------------------------
# microbenchmarks
# ---------------------------------------------------------------------------

def measure_gemms(
    shapes: Iterable[tuple[int, int, int]],
    *,
    base: hwlib.Target | None = None,
    repeats: int = DEFAULT_REPEATS,
    warmup: int = DEFAULT_WARMUP,
) -> list[Measurement]:
    """Isolated f32 GEMMs at several (m, k, n): the compute-branch rows
    that pin the effective ``gemm`` FLOP/s of this host."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref

    base = base if base is not None else hwlib.default_target()
    fn = jax.jit(ref.gemm)
    out = []
    for m, k, n in shapes:
        key = jax.random.PRNGKey(m + k + n)
        x = jax.random.normal(key, (m, k), jnp.float32)
        w = jax.random.normal(key, (k, n), jnp.float32)
        secs = wallclock_s(fn, x, w, repeats=repeats, warmup=warmup)
        feats = _streamed_features(
            base,
            {"x": 4 * m * k, "w": 4 * k * n, "y": 4 * m * n},
            {"gemm": 2.0 * m * k * n},
        )
        out.append(Measurement(
            name=f"gemm_m{m}_k{k}_n{n}", kind="gemm", measured_s=secs,
            segments=(feats,), branch=COMPUTE,
            meta=(("m", m), ("k", k), ("n", n)),
        ))
    return out


def measure_elementwise(
    sizes: Iterable[int],
    *,
    act: str = "gelu",
    base: hwlib.Target | None = None,
    repeats: int = DEFAULT_REPEATS,
    warmup: int = DEFAULT_WARMUP,
) -> list[Measurement]:
    """Isolated activation sweeps: the rows that pin the effective
    ``elementwise`` rate (the planner prices an elementwise op at one
    FLOP per output element — ``flops_per_macs=1`` — so the fitted rate
    absorbs the real per-element cost of the activation)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref

    base = base if base is not None else hwlib.default_target()
    fn = jax.jit(ref.act_fn(act))
    out = []
    for n in sizes:
        x = jax.random.normal(jax.random.PRNGKey(n % (1 << 30)),
                              (n,), jnp.float32)
        secs = wallclock_s(fn, x, repeats=repeats, warmup=warmup)
        feats = _streamed_features(
            base, {"x": 4 * n, "y": 4 * n}, {"elementwise": float(n)})
        out.append(Measurement(
            name=f"{act}_n{n}", kind="elementwise", measured_s=secs,
            segments=(feats,), branch=COMPUTE, meta=(("n", n),),
        ))
    return out


def measure_dma_proxy(
    sizes_bytes: Iterable[int],
    *,
    base: hwlib.Target | None = None,
    repeats: int = DEFAULT_REPEATS,
    warmup: int = DEFAULT_WARMUP,
) -> list[Measurement]:
    """Copy-through sweeps at several working-set sizes: the
    transfer-branch rows that pin effective per-level bandwidth and DMA
    setup.  Each run reads + writes its buffer once (``x + 1``: the
    cheapest op XLA will not elide); sizes straddling the base target's
    level capacities land on different home levels via the same
    first-fit the cost model uses, which is what makes per-level
    constants identifiable from one host."""
    import jax
    import jax.numpy as jnp

    base = base if base is not None else hwlib.default_target()
    fn = jax.jit(lambda x: x + jnp.float32(1.0))
    out = []
    for b in sizes_bytes:
        n = max(1, int(b) // 4)
        x = jax.random.normal(jax.random.PRNGKey(n % (1 << 30)),
                              (n,), jnp.float32)
        secs = wallclock_s(fn, x, repeats=repeats, warmup=warmup)
        feats = _streamed_features(
            base, {"src": 4 * n, "dst": 4 * n}, {"elementwise": float(n)})
        out.append(Measurement(
            name=f"dma_{4 * n}B", kind="dma", measured_s=secs,
            segments=(feats,), branch=TRANSFER, meta=(("bytes", 4 * n),),
        ))
    return out


def microbench_sweep(
    *,
    base: hwlib.Target | None = None,
    gemm_shapes: Sequence[tuple[int, int, int]] = (
        (256, 256, 256), (512, 512, 512), (1024, 512, 1024),
    ),
    elementwise_sizes: Sequence[int] = (1 << 20, 1 << 22, 1 << 23),
    dma_sizes: Sequence[int] = (1 << 21, 1 << 23, 1 << 25, 1 << 26),
    repeats: int = DEFAULT_REPEATS,
    warmup: int = DEFAULT_WARMUP,
) -> list[Measurement]:
    """The standard isolated-microbenchmark sweep the fitter consumes:
    GEMMs + activations (compute branch) and copy-throughs at sizes
    straddling the backing-level capacities (transfer branch)."""
    base = base if base is not None else hwlib.default_target()
    ms = measure_gemms(gemm_shapes, base=base, repeats=repeats,
                       warmup=warmup)
    ms += measure_elementwise(elementwise_sizes, base=base,
                              repeats=repeats, warmup=warmup)
    ms += measure_dma_proxy(dma_sizes, base=base, repeats=repeats,
                            warmup=warmup)
    return ms


# ---------------------------------------------------------------------------
# whole-block validation measurements (bench_block-style ref vs plan)
# ---------------------------------------------------------------------------

def measure_block(
    arch: str,
    m: int,
    *,
    base: hwlib.Target | None = None,
    repeats: int = DEFAULT_REPEATS,
    warmup: int = DEFAULT_WARMUP,
) -> list[Measurement]:
    """Whole-transformer-block wall-clock, reference (all-unfused
    features) and plan-driven (planned-chain features) — the held-out
    measurements the drift gate validates the fitted constants against.
    Mirrors ``benchmarks/bench_block.exec_rows`` at reduced config."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.core.ftl import partition, registry
    from repro.models import layers

    base = base if base is not None else hwlib.default_target()
    cfg = configs.get_config(arch).reduced()
    cfg = _dc.replace(cfg, dtype="float32", remat=False)
    cfg_auto = _dc.replace(cfg, ftl_mode="auto")
    cfg_off = _dc.replace(cfg, ftl_mode="off")
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 2)
    dt = jnp.dtype(cfg.dtype)
    params = {
        "ln1": layers.init_norm(cfg.d_model, cfg.norm, dt),
        "attn": layers.init_attention(cfg, ks[0]),
        "ln2": layers.init_norm(cfg.d_model, cfg.norm, dt),
        "mlp": layers.init_mlp(cfg, ks[1]),
    }
    plan = registry.plan_block(cfg_auto, m=m, dtype="float32", target=base)
    positions = jnp.arange(m)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, m, cfg.d_model),
                          jnp.float32)

    def plan_fn(xx):
        return registry.run_block(plan, params, xx, positions=positions)

    def ref_fn(xx):
        return layers.block_layer(cfg_off, params, xx, positions=positions)

    plan_s = wallclock_s(jax.jit(plan_fn), x, repeats=repeats,
                         warmup=warmup)
    ref_s = wallclock_s(jax.jit(ref_fn), x, repeats=repeats,
                        warmup=warmup)
    unfused = partition.plan_fixed(plan.graph,
                                   partition.all_cuts(plan.graph),
                                   target=base)
    return [
        Measurement(
            name=f"block_{arch}_m{m}_plan", kind="block",
            measured_s=plan_s, segments=features_from_chain(plan),
            meta=(("arch", arch), ("m", m), ("schedule", plan.schedule)),
        ),
        Measurement(
            name=f"block_{arch}_m{m}_ref", kind="block",
            measured_s=ref_s, segments=features_from_chain(unfused),
            meta=(("arch", arch), ("m", m), ("schedule", "unfused")),
        ),
    ]


__all__ = [
    "COMPUTE", "TRANSFER", "SegmentFeatures", "Measurement",
    "modeled_measurement_s", "features_from_chain", "wallclock_s",
    "measure_gemms", "measure_elementwise", "measure_dma_proxy",
    "microbench_sweep", "measure_block",
]
