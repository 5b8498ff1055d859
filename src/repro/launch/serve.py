"""Serving driver: continuous batching on planned schedules.

A real serving loop on top of the FTL planning stack:

* **Paged KV cache** — pure-'attn' decoder-only configs back their cache
  with fixed-size sequence blocks allocated per slot
  (:mod:`repro.launch.kv_cache`); pages are allocated on demand as a
  slot's position grows and freed on eviction, so admission control can
  queue requests under memory pressure.  Other families (local windows,
  recurrent state, cross caches, enc-dec) keep the dense per-slot cache.
* **Mixed sequence lengths** — each slot decodes at its *own* position
  (vector ``pos`` through ``model.decode_step``): admission prefills at
  the request's bucketed length, decode appends per slot, eviction on
  EOS/max-len refills the slot from the queue.
* **Plan cache** — serving plans are keyed ``(cfg, bucketed m, dtype,
  target, phase)``.  Prompts bucket through the
  :data:`repro.models.model.PREFILL_BUCKETS` ladder (ahead-of-time
  warmed), so steady state replans exactly zero times; the CI bench
  gates on that.
* **Split prefill/decode plans** — decode plans at ``m=1`` run through
  the same partition DP as prefill; memory-bound, they generally pick
  different cuts (pinned on ``rv32_npu``), and their bindings never
  qualify the Pallas kernels (decode-shape qualification).  Serve logs
  ``resolved_executors`` for *both* regimes, mirroring train.

CPU demo (open-loop arrivals + decode-plan timeline)::

  python -m repro.launch.serve --arch yi-6b --reduced --requests 8 \\
      --max-new 32 --arrival-rate 4 --trace /tmp/decode_trace.json
"""
from __future__ import annotations

import argparse
import dataclasses
import time
import weakref
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as obslib
from repro.configs import get_config
from repro.core import hw
from repro.core.ftl import registry as ftl_registry
from repro.kernels import ops
from repro.kernels.paged_attention import block_size_for
from repro.launch import kv_cache as KV
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.train import steps as S

# how often an obs-enabled engine samples a decode step into the drift
# monitor (report-only rows; whole-block rows come from
# execute_block_plan and are the ones benches gate on)
_DRIFT_SAMPLE_EVERY = 16


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # (S,) int32
    max_new: int
    out: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    arrival_s: float = 0.0       # open-loop arrival offset from run start
    bucket: int = 0              # prefill bucket the prompt landed in
    t_arrival: float = 0.0       # absolute times (perf_counter)
    t_admitted: float = 0.0
    t_done: float = 0.0

    @property
    def latency_s(self) -> float:
        """Arrival → completion, including queueing for a slot."""
        return self.t_done - self.t_arrival


class PlanCache:
    """Serving plan cache keyed ``(cfg, bucketed m, dtype, target, phase)``.

    A thin counting wrapper over :func:`repro.models.model.serve_plan`:
    ``warmup`` pre-plans the whole prefill bucket ladder plus the decode
    plan, after which every lookup must hit — ``misses_after_warmup`` is
    the CI gate's "zero replans during steady-state decode" counter.
    """

    def __init__(self, cfg, *, dtype: str, target: hw.Target,
                 buckets: tuple[int, ...]):
        self.cfg = cfg
        self.dtype = dtype
        self.target = target
        self.buckets = tuple(buckets)
        self._plans: dict[tuple, object] = {}
        self.hits = 0
        self.misses = 0
        self.warmed = False
        self.misses_after_warmup: list[tuple[str, int]] = []
        # a global cache clear must not leave this wrapper claiming
        # hits/warmth for plans the clear just dropped
        ftl_registry.register_counter_reset(self)

    def get(self, m: int, phase: str):
        """(bucketed m, BlockPlan-or-None) for one lookup."""
        mb = 1 if phase == "decode" else M.bucket_m(m, self.buckets)
        key = (self.cfg, mb, self.dtype, self.target, phase)
        if key in self._plans:
            self.hits += 1
            return mb, self._plans[key]
        self.misses += 1
        if self.warmed:
            self.misses_after_warmup.append((phase, mb))
        _, plan = M.serve_plan(self.cfg, m=mb, dtype=self.dtype,
                               target=self.target, phase=phase,
                               buckets=self.buckets)
        self._plans[key] = plan
        return mb, plan

    def warmup(self) -> None:
        for b in self.buckets:
            self.get(b, "prefill")
        self.get(1, "decode")
        self.warmed = True

    def counters(self) -> dict:
        return {
            "plans": len(self._plans),
            "hits": self.hits,
            "misses": self.misses,
            "misses_after_warmup": len(self.misses_after_warmup),
        }

    def reset_counters(self) -> None:
        """Back to the just-constructed state — called by
        ``registry.clear_plan_caches``.  The held plans are dropped too
        (they were built by the caches the clear invalidated), so the
        next lookup genuinely replans and the counters say so."""
        self._plans.clear()
        self.hits = 0
        self.misses = 0
        self.warmed = False
        self.misses_after_warmup.clear()


def _default_buckets(max_seq: int, block_size: int) -> tuple[int, ...]:
    rungs = [b for b in M.PREFILL_BUCKETS if b <= max_seq]
    if not rungs or rungs[-1] < max_seq:
        rungs.append(max_seq)
    rungs = [b for b in rungs if b % block_size == 0] or [max_seq]
    return tuple(rungs)


def make_paged_step(cfg, plan):
    """The engine's jitted paged decode step ``(params, pool, tables, tok,
    pos, wblk, woff) -> (logits, pool)``: each layer reads its pages in
    place (``model.decode_step_paged``) and the new tokens are written
    once, into the donated pool (``kv_cache.write_tokens``)."""
    def paged_step(params, pool, tables, tok, pos, wblk, woff):
        logits, new = M.decode_step_paged(cfg, params, tok, pool, tables,
                                          pos, plan=plan)
        return logits, KV.write_tokens(pool, new, wblk, woff)

    return jax.jit(paged_step, donate_argnums=1)


class ServeEngine:
    """Fixed-slot continuous batching engine (single host).

    ``target`` picks the planning preset (None → the process default);
    ``block_size`` is the paged-KV page length (None → the shortest whose
    pages fill the paged-attention kernel's tiles, ``block_size_for``: 8
    positions for GQA, 16 for MQA in bf16); ``paged=False`` forces the
    dense per-slot cache, ``kv_blocks`` shrinks the physical pool below
    ``slots * max_seq / block_size`` to exercise admission control.

    Besides the step counts, ``stats`` counts the decode steps whose
    attention ran the Pallas paged kernel (``paged_kernel_steps``)."""

    def __init__(self, cfg, params, *, batch_slots: int, max_seq: int,
                 eos_id: int = 1, target: hw.Target | None = None,
                 block_size: int | None = None,
                 paged: bool | None = None,
                 kv_blocks: int | None = None,
                 buckets: tuple[int, ...] | None = None,
                 obs: bool = False,
                 drift_target: hw.Target | None = None,
                 drift_band: tuple[float, float] | None = None):
        self.cfg = cfg
        self.params = params
        self.slots = batch_slots
        self.max_seq = max_seq
        self.eos = eos_id
        self.target = target if target is not None else hw.default_target()
        if block_size is None:
            block_size = block_size_for(cfg.n_kv_heads, cfg.dtype)
        self.block_size = block_size
        self.buckets = (tuple(buckets) if buckets is not None
                        else _default_buckets(max_seq, block_size))
        if any(b > max_seq for b in self.buckets):
            raise ValueError(f"bucket ladder {self.buckets} exceeds "
                             f"max_seq={max_seq}")
        self.active: list[Request | None] = [None] * batch_slots
        self.pos = np.zeros(batch_slots, np.int32)
        # mixed-length decode needs per-slot positions; enc-dec keeps the
        # scalar path (uniform sinusoidal offset)
        self._vector_pos = not cfg.is_encoder_decoder

        self.paged = (KV.paged_supported(cfg) if paged is None else paged)
        if self.paged and not KV.paged_supported(cfg):
            raise ValueError(f"{cfg.name!r} cannot use the paged KV cache")
        if self.paged:
            if max_seq % block_size:
                raise ValueError(f"max_seq={max_seq} must be a multiple "
                                 f"of block_size={block_size}")
            if any(b % block_size for b in self.buckets):
                raise ValueError(
                    f"every prefill bucket must be a multiple of "
                    f"block_size={block_size}, got {self.buckets}")
            self.kv = KV.PagedKVCache(cfg, slots=batch_slots,
                                      max_seq=max_seq,
                                      block_size=block_size,
                                      num_blocks=kv_blocks)
            self.cache = None
        else:
            self.kv = None
            self.cache = M.init_cache(cfg, batch_slots, max_seq)
        # whether the paged step's attention is the Pallas kernel (decided
        # as ops.paged_attention decides it when the step is traced)
        self.paged_kernel = self.paged and ops.paged_kernel(
            block_size, cfg.n_kv_heads, cfg.dtype)

        # AOT warmup of the bucket ladder + the decode plan: after this,
        # steady state never plans again (the bench gate).
        self.plans = PlanCache(cfg, dtype=cfg.dtype, target=self.target,
                               buckets=self.buckets)
        self.plans.warmup()
        _, self.decode_plan = self.plans.get(1, "decode")
        _, self.block_plan = self.plans.get(self.buckets[-1], "prefill")
        self.decode_fn = self._build_decode(self.decode_plan)
        self._decode_fn_plan = self.decode_plan
        self._prefill_fns: dict[int, Any] = {}

        self.stats = {
            "prefills": 0, "decode_steps": 0, "tokens": 0,
            "replans": 0, "paged_inplace_steps": 0,
            "paged_kernel_steps": 0,
            "bucket_admissions": {},
            "ftl_schedule": (self.block_plan.schedule
                             if self.block_plan else "n/a"),
            "ftl_target": self.target.name,
            "block_exec": "n/a",
        }
        ftl_registry.register_counter_reset(self)

        # telemetry (repro.obs): span recording + per-step gauges + the
        # online drift monitor, all opt-in — a bare engine pays nothing
        # but its counters, read only when the registry is collected.
        self.obs = bool(obs)
        self.drift = None
        self._register_collector()
        if self.obs:
            obslib.enable()
            self.drift = obslib.DriftMonitor(
                target=drift_target if drift_target is not None
                else self.target,
                **({"band": drift_band} if drift_band else {}))
            self._g_active = obslib.gauge(
                "serve_active_slots", "slots currently decoding")
            self._g_queue = obslib.gauge(
                "serve_queue_depth", "requests waiting for a slot")
            self._g_kv_free = obslib.gauge(
                "serve_kv_free_blocks", "paged-KV free physical blocks")
            self._g_kv_occ = obslib.gauge(
                "serve_kv_page_occupancy",
                "fraction of the paged-KV pool in use")
            self._c_evict = obslib.counter(
                "serve_evictions_total", "slots freed (EOS/max-len)")
            self._h_step = obslib.histogram(
                "serve_decode_step_seconds", "wall-clock per decode step")

    def _register_collector(self) -> None:
        """Re-express ``stats`` (every engine) and ``plan_report()``
        (obs-enabled engines) on the metrics registry at collect time.
        Weakly bound: a dead engine's collector is a no-op, never a
        leak."""
        ref = weakref.ref(self)

        def _collect(reg) -> None:
            eng = ref()
            if eng is None:
                return
            g_stat = reg.gauge("serve_stats",
                               "ServeEngine.stats re-expressed", ("stat",))
            for k in ("prefills", "decode_steps", "tokens", "replans",
                      "paged_inplace_steps", "paged_kernel_steps"):
                g_stat.labels(stat=k).set(eng.stats[k])
            if not eng.obs:
                return
            g_pc = reg.gauge("serve_plan_cache",
                             "serving PlanCache counters", ("field",))
            for k, v in eng.plans.counters().items():
                g_pc.labels(field=k).set(v)
            rep = eng.plan_report()
            g_plan = reg.gauge(
                "serve_plan_segments",
                "planned segments per serving regime (0 = no plan)",
                ("phase", "schedule"))
            for phase in ("prefill", "decode"):
                e = rep[phase]
                if e is not None:
                    g_plan.labels(phase=phase, schedule=e["schedule"]) \
                        .set(len(e["cuts"]) + 1)
            reg.gauge("serve_decode_differs_from_prefill",
                      "1 when the decode DP picked different cuts") \
                .set(float(rep["decode_differs_from_prefill"]))

        obslib.register_collector(_collect)

    def reset_counters(self) -> None:
        """Called by ``registry.clear_plan_caches``: the decode-replan
        counter tracks misses of the (just-reset) plan cache, so it must
        restart with it or ``plan_report`` would blame post-clear replans
        on steady-state serving."""
        self.stats["replans"] = 0

    # ------------------------------------------------------------------
    # plan-aware step builders
    # ------------------------------------------------------------------
    def _build_decode(self, plan):
        if self.paged:
            return make_paged_step(self.cfg, plan)
        return jax.jit(S.make_decode_step(self.cfg, None, plan=plan))

    def prefill_fn(self, bucket: int, plan):
        """The engine's jitted prefill step for ``bucket`` (built once);
        ``decode_fn`` is its jitted decode step."""
        fn = self._prefill_fns.get(bucket)
        if fn is None:
            # paged caches splice page-aligned bucket-length caches; the
            # dense path right-pads to max_seq at splice time instead
            fn = jax.jit(S.make_prefill_step(self.cfg, None, plan=plan))
            self._prefill_fns[bucket] = fn
        return fn

    def plan_report(self) -> dict:
        """Resolved executors + cuts for *both* serving regimes (mirrors
        what train logs for its single shape)."""
        from repro.core.ftl import executor_block

        def entry(plan, m):
            if plan is None:
                return None
            return {
                "m": m,
                "schedule": plan.schedule,
                "cuts": list(plan.chain.cuts()),
                "executors": executor_block.resolved_executors(plan, m=m),
            }

        pre = entry(self.block_plan, self.buckets[-1])
        dec = entry(self.decode_plan, 1)
        return {
            "target": self.target.name,
            "buckets": list(self.buckets),
            "prefill": pre,
            "decode": dec,
            "decode_differs_from_prefill": bool(
                pre and dec and pre["cuts"] != dec["cuts"]),
            # every memoized planner the serving path leans on — shows
            # the plans above came out of cache, not replanning
            "plan_caches": ftl_registry.plan_cache_stats(),
        }

    def warmup_compile(self, extras: dict[str, Any] | None = None) -> None:
        """Compile every bucket's prefill step and the decode step ahead
        of time, so open-loop latency percentiles measure serving, not
        XLA compiles.  Engine state is untouched but for the paged
        pool's scratch page, which the warm-up decode step writes."""
        extras = extras or {}
        for b in self.buckets:
            _, plan = self.plans.get(b, "prefill")
            fn = self.prefill_fn(b, plan)
            batch = {"tokens": jnp.zeros((1, b), jnp.int32), **extras}
            fn(self.params, batch, jnp.int32(b - 1))[0].block_until_ready()
        tok = jnp.zeros((self.slots, 1), jnp.int32)
        if self.paged:
            pos = jnp.zeros((self.slots,), jnp.int32)
            zero = jnp.zeros((self.slots,), jnp.int32)
            # every slot writes the scratch page; the pool is donated
            out = self.decode_fn(self.params, self.kv.pool,
                                 self.kv.table_array(), tok, pos, zero,
                                 zero)
            self.kv.pool = out[1]
        else:
            pos = (jnp.zeros((self.slots,), jnp.int32) if self._vector_pos
                   else jnp.int32(0))
            out = self.decode_fn(self.params, self.cache, tok, pos)
        out[0].block_until_ready()

    # ------------------------------------------------------------------
    def execute_block_plan(self):
        """Run the stored prefill BlockPlan for real at the serving shape.

        Executes one transformer block of the engine's own parameters
        through ``registry.run_block`` on a (1, max_seq, d_model)
        activation — the steady-state prefill shape regime.  This is
        where every binding is requalified on the serving host
        (per-segment fallback), and it prices the plan in wall-clock
        terms instead of only reporting modeled traffic.  Records the
        resolved executors and timing in ``stats``; returns the stats
        entry (None when the model has no plan or no plannable layer).
        """
        if self.block_plan is None:
            return None
        p, kind = self._first_block_params()
        if p is None or ("attn" not in p and "mlp" not in p):
            return None
        from repro.core.ftl import executor_block
        from repro.core.ftl import registry as ftl_registry
        cfg = self.cfg
        window = cfg.local_window if kind == "local" else None
        x = jax.random.normal(
            jax.random.PRNGKey(0), (1, self.max_seq, cfg.d_model)
        ).astype(cfg.dtype)
        positions = jnp.arange(self.max_seq)
        # the layer's weights are an argument, not a closure constant the
        # compiler would copy into the executable
        run = jax.jit(lambda pp, xx: ftl_registry.run_block(
            self.block_plan, pp, xx, positions=positions, window=window))
        run(p, x).block_until_ready()           # compile
        t0 = time.perf_counter()
        with obslib.span("serve:block_exec", "exec"):
            y = run(p, x)
            y.block_until_ready()
        dt = time.perf_counter() - t0
        entry = {
            "ms": round(1e3 * dt, 3),
            "executors": executor_block.resolved_executors(
                self.block_plan, m=self.max_seq, dtype=str(x.dtype)),
            "finite": bool(jnp.isfinite(y).all()),
        }
        self.stats["block_exec"] = entry
        if self.drift is not None:
            # the gated drift feed: a whole planned block, wall-clocked
            # at the serving shape — the same regime bench_calibrate's
            # block rows measure
            entry["drift_ratio"] = self.drift.observe_chain(
                self.block_plan, dt, name="block_exec", kind="block")
        return entry

    def _first_block_params(self):
        """(params, mixer kind) of the first plan-executable layer.

        Prefers a full attention(+MLP) layer; hybrid configs whose leading
        positions are recurrent fall back to any MLP-bearing one (the plan
        is MLP-only there and run_block executes just that stage).
        Returns (None, None) when no layer can execute the plan.
        """
        kinds, n_full, rem_kinds = M._layer_split(self.cfg)
        if n_full:
            pool = [(k, f"pos{i}") for i, k in enumerate(kinds)]

            def get(key):
                # slice only this position's subtree, not the whole stack
                return jax.tree.map(lambda a: a[0],
                                    self.params["layers"][key])
        elif rem_kinds:
            pool = [(k, f"rem{i}") for i, k in enumerate(rem_kinds)]

            def get(key):
                return self.params["rem"][key]
        else:
            return None, None
        for kind, key in pool:
            if kind in ("attn", "local"):
                return get(key), kind
        # no attention layer: any MLP-bearing layer can run the
        # (MLP-only) plan
        if bool(self.cfg.d_ff) and not self.cfg.is_moe:
            kind, key = pool[0]
            return get(key), kind
        return None, None

    # ------------------------------------------------------------------
    def _admit(self, req: Request, slot: int, extras: dict[str, Any]
               ) -> bool:
        """Prefill one request at its bucketed length and splice its
        cache into the slot.  Returns False (admitting nothing) when the
        paged pool cannot cover the bucket — the request stays queued."""
        plen = len(req.prompt)
        if plen > self.buckets[-1]:
            raise ValueError(f"request {req.rid}: prompt of {plen} tokens "
                             f"exceeds the largest bucket "
                             f"{self.buckets[-1]}")
        t_admit = time.perf_counter()
        obslib.begin("serve:admit", "serve")
        try:
            bucket, plan = self.plans.get(plen, "prefill")
            req.bucket = bucket
            if self.paged and not self.kv.allocate(slot, bucket):
                return False

            padded = np.zeros(bucket, np.int32)
            padded[:plen] = req.prompt
            batch = {"tokens": jnp.asarray(padded)[None], **extras}
            fn = self.prefill_fn(bucket, plan)
            # bucket padding is on the right; the prompt's real last token
            # sits at plen-1 and decode overwrites the pad KV in place
            with obslib.span(f"serve:prefill:m{bucket}", "serve"):
                logits, cache1 = fn(self.params, batch, jnp.int32(plen - 1))
                logits.block_until_ready()

            if self.paged:
                # dispatch only: the page write runs on the device after
                with obslib.span("serve:admit:pages", "serve"):
                    self.kv.write_prefill(slot, cache1, bucket)
            else:
                def splice(path, full, one):
                    """Insert request-batch-1 state into this slot of the
                    batch cache, padding the request's seq dims up to the
                    engine max.

                    The batch axis is structural, not inferred from
                    extents (slot-count 1 made every axis look like
                    batch): stacked 'layers' caches carry a leading layer
                    dim → batch is axis 1; remainder/unstacked caches →
                    axis 0."""
                    names = [str(k.key) for k in path
                             if isinstance(k, jax.tree_util.DictKey)]
                    ax = 1 if names and names[0] == "layers" else 0
                    if one.shape[ax + 1:] != full.shape[ax + 1:]:
                        pads = [(0, 0)] * one.ndim
                        for d in range(ax + 1, one.ndim):
                            pads[d] = (0, full.shape[d] - one.shape[d])
                        one = jnp.pad(one, pads)
                    return _dus_axis(full, jnp.take(one, 0, axis=ax), slot,
                                     ax)

                self.cache = jax.tree_util.tree_map_with_path(
                    splice, self.cache, cache1)

            self.active[slot] = req
            self.pos[slot] = plen
            with obslib.span("serve:admit:first_token", "serve"):
                first = int(jnp.argmax(logits[0, -1]))
            req.out.append(first)
            req.t_admitted = time.perf_counter()
            self.stats["prefills"] += 1
            adm = self.stats["bucket_admissions"]
            adm[bucket] = adm.get(bucket, 0) + 1
            # the request's wait: from due to this admission's start
            obslib.record("serve:queue", "serve", req.t_arrival, t_admit)
            return True
        finally:
            obslib.end()  # serve:admit

    # ------------------------------------------------------------------
    def _evict(self, slot: int) -> None:
        with obslib.span("serve:evict", "serve"):
            self.active[slot] = None
            self.pos[slot] = 0
            if self.paged:
                self.kv.release(slot)
        if self.obs:
            self._c_evict.inc()

    def step(self):
        """One batched decode step for all active slots (each at its own
        position).

        With recording on, four spans tile ``serve:decode_step``:
        ``prepare`` (plan lookup, slot scan, page allocation, host
        arrays), ``launch`` (the jitted step's dispatch), ``sync`` (the
        sampling dispatch and the tokens' readback, which waits for the
        device) and ``commit`` (token bookkeeping)."""
        t_step = time.perf_counter() if self.obs else 0.0
        obslib.begin("serve:decode_step", "serve")
        obslib.begin("serve:step:prepare", "serve")
        try:
            # steady-state plan lookup: after warmup this always hits; a
            # miss (or a changed plan object) would force a re-jit —
            # counted as a replan, and gated to zero in bench_serve
            _, plan = self.plans.get(1, "decode")
            if plan is not self._decode_fn_plan:
                self.decode_fn = self._build_decode(plan)
                self._decode_fn_plan = plan
                self.decode_plan = plan
                self.stats["replans"] += 1

            tok = np.zeros((self.slots, 1), np.int32)
            live = np.zeros(self.slots, bool)
            for i, r in enumerate(self.active):
                if r is not None and not r.done:
                    tok[i, 0] = r.out[-1]
                    live[i] = True

            if self.paged:
                wblk = np.zeros(self.slots, np.int32)
                woff = np.zeros(self.slots, np.int32)
                for i in range(self.slots):
                    if live[i]:
                        if not self.kv.allocate(i, int(self.pos[i]) + 1):
                            raise RuntimeError(
                                f"KV pool exhausted growing slot {i} at "
                                f"pos {int(self.pos[i])} "
                                f"({self.kv.free_blocks} free blocks)")
                        wblk[i], woff[i] = self.kv.write_coords(
                            i, int(self.pos[i]))
                    # dead slots keep (0, 0): the scratch page
                args = (self.params, self.kv.pool, self.kv.table_array(),
                        jnp.asarray(tok), jnp.asarray(self.pos),
                        jnp.asarray(wblk), jnp.asarray(woff))
                obslib.switch("serve:step:launch", "serve")
                logits, self.kv.pool = self.decode_fn(*args)
            else:
                if self._vector_pos:
                    pos = jnp.asarray(self.pos)
                else:
                    pos = jnp.int32(int(max(
                        (self.pos[i] for i, r in enumerate(self.active)
                         if r is not None), default=0)))
                args = (self.params, self.cache, jnp.asarray(tok), pos)
                obslib.switch("serve:step:launch", "serve")
                logits, self.cache = self.decode_fn(*args)

            obslib.switch("serve:step:sync", "serve")
            nxt = np.asarray(jnp.argmax(logits[:, 0], -1))
            obslib.switch("serve:step:commit", "serve")
            now = time.perf_counter()
            for i, r in enumerate(self.active):
                if r is None or r.done:
                    continue
                t = int(nxt[i])
                r.out.append(t)
                self.pos[i] += 1
                self.stats["tokens"] += 1
                if t == self.eos or len(r.out) >= r.max_new \
                        or self.pos[i] >= self.max_seq - 1:
                    r.done = True
                    r.t_done = now
            self.stats["decode_steps"] += 1
            if self.paged:
                self.stats["paged_inplace_steps"] += 1
            if self.paged_kernel:
                self.stats["paged_kernel_steps"] += 1
        finally:
            obslib.end()  # the open serve:step:* phase
            obslib.end()  # serve:decode_step
        if self.obs:
            self._observe_step(time.perf_counter() - t_step)

    def _observe_step(self, dt: float) -> None:
        """Per-step gauges + a sampled drift row (obs-enabled engines)."""
        self._h_step.observe(dt)
        self._g_active.set(sum(1 for r in self.active
                               if r is not None and not r.done))
        if self.paged:
            free = self.kv.free_blocks
            self._g_kv_free.set(free)
            self._g_kv_occ.set(1.0 - free / max(self.kv.num_blocks, 1))
        if (self.drift is not None and self.decode_plan is not None
                and self.stats["decode_steps"] % _DRIFT_SAMPLE_EVERY == 0):
            # report-only row: a decode step runs the per-block plan
            # n_layers times (plus head/dispatch the model never charges
            # the block plan for), so scale the modeled side to match.
            # Whole-block rows from execute_block_plan are the gated ones.
            self.drift.observe_chain(
                self.decode_plan, dt, name="decode_step", kind="decode",
                scale=max(self.cfg.n_layers, 1))

    def run(self, requests: list[Request], extras: dict[str, Any],
            arrivals: list[float] | None = None):
        """Serve ``requests`` to completion.

        ``arrivals`` (seconds from run start, one per request, sorted)
        switches to an open-loop arrival process: request *i* only
        becomes admissible once its arrival time has passed, and
        ``Request.latency_s`` measures arrival → completion including
        queueing.  None keeps the closed-loop behavior (everything
        arrives at t=0)."""
        if arrivals is not None:
            if len(arrivals) != len(requests):
                raise ValueError("one arrival time per request")
            for r, a in zip(requests, arrivals):
                r.arrival_s = float(a)
        t0 = time.perf_counter()
        for r in requests:
            r.t_arrival = t0 + r.arrival_s
        queue = list(requests)
        done: list[Request] = []
        while queue or any(r is not None for r in self.active):
            # host work between decode steps: evictions, admissions and
            # the wait for an arrival
            obslib.begin("serve:schedule", "serve")
            try:
                now = time.perf_counter()
                if self.obs:
                    self._g_queue.set(len(queue))
                admitted_any = False
                for i in range(self.slots):
                    r = self.active[i]
                    if r is not None and r.done:
                        done.append(r)
                        self._evict(i)
                    if (self.active[i] is None and queue
                            and queue[0].t_arrival <= now):
                        if self._admit(queue[0], i, extras):
                            queue.pop(0)
                            admitted_any = True
                        else:
                            break   # paged pool full: wait for evictions
                have_live = any(r is not None and not r.done
                                for r in self.active)
                if not have_live:
                    if admitted_any:
                        continue
                    if queue:
                        # from the same clock read as the admission test:
                        # a head due after `now` waits, never deadlocks
                        wait = queue[0].t_arrival - now
                        if wait > 0:
                            with obslib.span("serve:wait_arrival", "serve"):
                                time.sleep(min(wait, 0.05))
                            continue
                        if all(r is None for r in self.active):
                            # head request arrived but cannot be admitted
                            # and nothing is running to free pages
                            raise RuntimeError(
                                "deadlock: KV pool too small to admit "
                                f"request {queue[0].rid} with every slot "
                                "empty")
                    continue
            finally:
                obslib.end()  # serve:schedule
            self.step()
        return done


def _dus_axis(full, val, idx, ax):
    return jax.lax.dynamic_update_index_in_dim(full, val, idx, ax)


def poisson_arrivals(n: int, rate_per_s: float, seed: int = 0
                     ) -> list[float]:
    """Cumulative exponential inter-arrival times (open-loop process)."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / max(rate_per_s, 1e-9), size=n)
    return list(np.cumsum(gaps))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--target", default=None,
                    help="planning target preset (default: auto-detect)")
    ap.add_argument("--ftl-mode", default=None,
                    choices=["off", "fused", "scan", "auto"],
                    help="executor mode (default: the config's); 'auto' "
                    "runs the planned bindings, Pallas kernels on TPU")
    ap.add_argument("--block-size", type=int, default=None,
                    help="paged-KV page length in tokens (default: the "
                    "shortest whose pages fill the paged-attention "
                    "kernel's tiles)")
    ap.add_argument("--dense-kv", action="store_true",
                    help="force the dense per-slot cache")
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="open-loop Poisson arrival rate (req/s); "
                    "default: all requests arrive at t=0")
    ap.add_argument("--trace", default=None,
                    help="write a Chrome-tracing timeline of the decode "
                    "plan's simulated schedule to this path (with --obs: "
                    "the merged live+modeled timeline, written post-run)")
    ap.add_argument("--obs", action="store_true",
                    help="enable runtime telemetry (spans, gauges, the "
                    "online drift monitor)")
    ap.add_argument("--obs-trace", default=None,
                    help="write the merged live+modeled Perfetto "
                    "timeline to this path after the run (implies --obs)")
    ap.add_argument("--obs-metrics", default=None,
                    help="write a Prometheus text exposition of the "
                    "metrics registry to this path after the run "
                    "(implies --obs)")
    args = ap.parse_args()
    if args.obs_trace or args.obs_metrics:
        args.obs = True
    if args.trace and args.obs_trace and args.trace == args.obs_trace:
        ap.error("--trace and --obs-trace point at the same path "
                 f"({args.trace}); they would silently overwrite each "
                 "other — give them distinct paths")

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.ftl_mode:
        cfg = dataclasses.replace(cfg, ftl_mode=args.ftl_mode)
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    extras: dict[str, Any] = {}
    if cfg.family == "vlm":
        extras["image_embeds"] = jnp.zeros(
            (1, cfg.n_image_tokens, cfg.d_model), cfg.dtype)
    if cfg.is_encoder_decoder:
        extras["frames"] = jnp.zeros(
            (1, cfg.encoder_seq, cfg.d_model), cfg.dtype)

    rng = np.random.default_rng(args.seed)
    # mixed prompt lengths exercise the bucket ladder + per-slot decode
    lens = rng.integers(max(1, args.prompt_len // 2), args.prompt_len + 1,
                        size=args.requests)
    reqs = [Request(i, rng.integers(2, cfg.vocab_size,
                                    size=int(lens[i])).astype(np.int32),
                    args.max_new)
            for i in range(args.requests)]
    target = hw.get_target(args.target) if args.target else None
    eng = ServeEngine(cfg, params, batch_slots=args.slots,
                      max_seq=args.max_seq, target=target,
                      block_size=args.block_size,
                      paged=False if args.dense_kv else None,
                      obs=args.obs)
    report = eng.plan_report()
    print(f"FTL serving plans on {report['target']} "
          f"(buckets {report['buckets']}, "
          f"{'paged' if eng.paged else 'dense'} KV):")
    for phase in ("prefill", "decode"):
        e = report[phase]
        if e is None:
            print(f"  {phase}: no plannable block")
            continue
        print(f"  {phase} @ m={e['m']}: schedule={e['schedule']} "
              f"cuts={e['cuts']} executors={e['executors']}")
    if report["decode_differs_from_prefill"]:
        print("  decode cuts differ from prefill (memory-bound m=1 DP)")
    hot = {n: s for n, s in report["plan_caches"].items()
           if s["hits"] or s["misses"]}
    for n, s in hot.items():
        print(f"  plan cache {n}: {s['hits']} hits / {s['misses']} misses "
              f"({s['size']}/{s['maxsize']} entries)")
    if eng.block_plan is not None:
        exec_stats = eng.execute_block_plan()
        if exec_stats is not None:
            print(f"block plan executed @ m={args.max_seq}: "
                  f"{exec_stats['ms']} ms, executors "
                  f"{exec_stats['executors']}")
    if args.trace and not args.obs:
        # modeled-only timeline (pre-run: it needs no live spans); with
        # --obs the trace is written post-run as the merged live+modeled
        # view instead
        from repro.sim import write_chrome_trace
        plan = eng.decode_plan or eng.block_plan
        if plan is not None:
            write_chrome_trace(plan, args.trace)
            print(f"decode-plan timeline written to {args.trace}")

    eng.warmup_compile(extras)
    arrivals = (poisson_arrivals(args.requests, args.arrival_rate,
                                 args.seed)
                if args.arrival_rate else None)
    t0 = time.time()
    done = eng.run(reqs, extras, arrivals=arrivals)
    dt = time.time() - t0
    lat = sorted(r.latency_s for r in done)
    p50 = lat[len(lat) // 2] if lat else 0.0
    p99 = lat[min(len(lat) - 1, int(0.99 * len(lat)))] if lat else 0.0
    print(f"served {len(done)} requests, {eng.stats['tokens']} tokens "
          f"in {dt:.1f}s ({eng.stats['tokens']/max(dt,1e-9):.1f} tok/s); "
          f"{eng.stats['decode_steps']} decode steps, "
          f"{eng.stats['prefills']} prefills, "
          f"p50 {1e3*p50:.0f} ms / p99 {1e3*p99:.0f} ms")
    pc = eng.plans.counters()
    print(f"plan cache: {pc['plans']} plans, {pc['hits']} hits, "
          f"{pc['misses']} misses ({pc['misses_after_warmup']} after "
          f"warmup), {eng.stats['replans']} decode replans")
    for r in done[:3]:
        print(f"  req {r.rid}: {len(r.out)} tokens: {r.out[:10]}...")

    if args.obs:
        if eng.drift is not None and eng.drift.n_observed:
            st = eng.drift.status()
            print(f"drift monitor on {st['target']}: geomean "
                  f"modeled/measured {st['geomean_ratio']:.3f} "
                  f"({'in' if st['in_band'] else 'OUT OF'} band "
                  f"{tuple(st['band'])}, {st['n_observed']} observations)")
        plan = eng.decode_plan or eng.block_plan
        for path in (args.obs_trace, args.trace):
            if path:
                obslib.write_merged_trace(path, chain=plan)
                print(f"merged live+modeled timeline written to {path}")
        if args.obs_metrics:
            obslib.write_prometheus(args.obs_metrics)
            print(f"Prometheus metrics written to {args.obs_metrics}")


if __name__ == "__main__":
    main()
