#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the chip this process finds.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (``setup_s``, from process start to the window): the device check,
the cell's weights drawn from the seed on the device, a ``ServeEngine``
over the rungs of its bucket ladder the window's prompts reach, and
every shape compiled or read from the persistent compile cache
(``served.start``).  Then the
window: the cell's traffic served through ``ServeEngine.run`` for
``--seconds``.  After it, the float32 reference checks a sample of what
was served.  With ``--trace 0`` the result carries the cell's end-to-end
metrics; with ``--trace 1`` its per-layer metrics, read from the
engine's spans and counters and from a device trace of the cell's
``trace_s`` seconds from 30% into the window.

Earlier lines of standard output: one JSON ``info`` object (set-up parts,
executors that ran, compiles inside the window, counts).  Last lines of
standard error: each number the correctness check compared, with its
limit.  Last line of standard output: the result object.  Anything but a
TPU, fewer chips than the cell asks for, or a device kind missing from
``peaks.json`` exits non-zero with no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import gc  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
if sys.path and pathlib.Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path.pop(0)          # the bench's modules import as ``bench.*``
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import (correctness, devtrace, latency, lookup, served,  # noqa: E402
                   timing)

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"
BLOCK_SIZE = 8            # the engine's default page length
SPAN_CAPACITY = 1 << 17
# the traced stretch of a --trace 1 window starts at this share of the
# window and lasts the cell's ``trace_s`` (cut at the window's end)
TRACE_FROM = 0.3
_COMPILE_EVENTS = {"/jax/core/compile/backend_compile_duration": "compiled",
                   "/jax/compilation_cache/cache_retrieval_time_sec":
                   "cache_loads"}


class NoChip(RuntimeError):
    """The process found no device the benchmark may measure."""


def peaks() -> dict:
    with open(PEAKS) as f:
        return json.load(f)


def device_check(chips: int) -> dict:
    """The device JAX runs on; a TPU with at least ``chips`` chips whose
    kind ``peaks.json`` knows, or ``NoChip``."""
    import jax

    devs = jax.devices()
    dev = devs[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}
    if dev.platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {info}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    if dev.device_kind not in peaks():
        raise NoChip(f"no peaks for device kind {dev.device_kind!r} in "
                     f"{PEAKS.name}")
    return info


def _log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


class CompileCount:
    """Backend compiles and persistent-cache loads, counted by kind as
    JAX reports them."""

    def __init__(self):
        import jax

        self.n = dict.fromkeys(_COMPILE_EVENTS.values(), 0)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.n[_COMPILE_EVENTS[event]] += 1


def _executors() -> dict[str, float]:
    from repro import obs

    snap = obs.collect().get("ftl_dispatch_total", {"samples": []})
    return {f"{lbl['phase']}/{lbl['kind']}/{lbl['executor']}": v
            for lbl, v in snap["samples"] if v}


def _share_spanning_admission(served_reqs, t_end: float) -> float | None:
    """Share of the window's pooled inter-token gaps inside which some
    request got its first token (an admission's prefill ran in that
    gap)."""
    firsts = sorted(r.stamps[0] for r in served_reqs if r.stamps)
    n = hit = 0
    for r in served_reqs:
        st = [t for t in r.stamps if t <= t_end]
        for a, b in zip(st, st[1:]):
            n += 1
            i = bisect.bisect_right(firsts, a)
            hit += i < len(firsts) and firsts[i] < b
    return hit / n if n else None


class Profiler:
    """The JAX profiler over a stretch of the window, into a temporary
    directory."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.t0 = self.t1 = None

    def start(self) -> None:
        import jax

        jax.profiler.start_trace(self.dir)
        self.t0 = time.perf_counter()

    def stop(self) -> None:
        import jax

        if self.t0 is not None and self.t1 is None:
            self.t1 = time.perf_counter()
            jax.profiler.stop_trace()

    def read(self) -> dict:
        try:
            return devtrace.load(devtrace.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: pathlib.Path = lookup.ROOT, check_device=device_check,
             keep_trace: str | None = None) -> tuple[dict, dict, dict]:
    """(result, info, checks) of one run of ``workload``."""
    import jax

    cell = lookup.cell(workload, root)
    chips = cell["workload"]["chips"]
    t = time.perf_counter()
    setup = {"import_s": t - T_PROCESS}
    device = check_device(chips)
    setup["device_s"] = time.perf_counter() - t
    compiles = CompileCount()
    peak = peaks().get(device["kind"])

    traffic, params = cell["traffic"], cell["params"]
    t = time.perf_counter()
    prompts, outs, arrivals = cell_traffic(cell, seconds, seed)
    setup["traffic_s"] = time.perf_counter() - t
    system = served.start(cell, seed, map(len, prompts), BLOCK_SIZE)
    spec, w, eng = system.spec, system.w, system.eng
    setup.update(system.setup)

    prof = Profiler() if trace else None
    actions = []
    if prof is not None:
        from repro import obs
        obs.enable(SPAN_CAPACITY)
        a = TRACE_FROM * seconds
        actions = [(a, prof.start),
                   (a + min(params["trace_s"], (1 - TRACE_FROM) * seconds),
                    prof.stop)]
    window = timing.Window(seconds, actions)
    reqs = timing.make_requests(prompts, outs, window)
    executors = _executors()
    n_compiles = dict(compiles.n)
    try:
        served_reqs, stats = timing.serve_window(eng, reqs, arrivals,
                                                 window)
    finally:
        if prof is not None:
            prof.stop()
    compiles_in_window = sum(compiles.n.values()) - sum(n_compiles.values())
    setup_s = window.t_start - T_PROCESS
    mem = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = mem.get("peak_bytes_in_use")

    e2e = latency.end_to_end(served_reqs, window.t_start, window.t_end)
    e2e["setup_s"] = setup_s
    if prof is not None:
        metrics, breakdown = _per_layer(cell, prof, spec, peak, served_reqs,
                                        stats, window, device, keep_trace,
                                        root)
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"] if m["name"] in e2e}
        breakdown = None

    # the program's state goes before the reference runs
    eng_buckets = eng.buckets
    eng.kv.pool = None
    del eng, reqs, system
    gc.collect()

    sample = correctness.sample(served_reqs, seed,
                                params["sample_tokens"])
    got = correctness.gaps(spec, w, sample, traffic["output_len"]["max"])
    limit = params["limits"]["max_logit_gap"]
    gap = got["max_logit_gap"]
    correct = gap is not None and math.isfinite(gap) and gap <= limit
    checks = {"max_logit_gap": {"value": gap, "limit": limit}}

    due = [r for r in served_reqs if r.t_due <= window.t_end]
    attempted = (len(due) if arrivals is not None
                 else sum(1 for r in served_reqs
                          if r.stamps and r.stamps[0] <= window.t_end))
    result = {"correct": bool(correct), "attempted": attempted, "failed": 0,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    info = {
        "workload": workload, "seed": seed, "seconds": seconds,
        "trace": trace, "setup": setup, "setup_s": setup_s,
        "slots": params["slots"], "buckets": list(eng_buckets),
        "max_seq": traffic["max_seq"], "rate_per_s": params.get("rate_per_s"),
        "requests": len(served_reqs), "due": len(due),
        "admitted": sum(1 for r in served_reqs
                        if r.stamps and r.stamps[0] <= window.t_end),
        "finished": sum(1 for r in served_reqs if r.done),
        "finished_in_window": sum(1 for r in served_reqs
                                  if r.done and r.stamps[-1] <= window.t_end),
        "drained_s": stats["drained_s"],
        "tokens_in_window": latency.tokens_in(served_reqs, window.t_start,
                                              window.t_end),
        "engine_stats": stats, "executors": executors,
        "compiles_in_window": compiles_in_window,
        "compiles_in_setup": n_compiles,
        "memory_stats": mem,
        "itl_share_spanning_admission":
            _share_spanning_admission(served_reqs, window.t_end),
        "end_to_end": e2e,
        "itl_ms_at": _at(latency.itls(served_reqs, window.t_end),
                         (50, 90, 95, 99)),
        "ttft_ms_at": _at(latency.ttfts(served_reqs, window.t_end),
                          (50, 90, 95)),
        "slowest_firsts": _slowest_firsts(served_reqs, window),
        "longest_gaps": _longest_gaps(served_reqs, window),
        "check": got,
    }
    return result, info, checks


def _slowest_firsts(served_reqs, window, n: int = 8) -> list:
    """[due s, TTFT ms, prompt length, bucket] of the ``n`` requests due
    in the window that waited longest for their first token."""
    due = [r for r in served_reqs if r.t_due <= window.t_end]
    wait = {r.rid: min(r.stamps[0] if r.stamps else window.t_end,
                       window.t_end) - r.t_due for r in due}
    due.sort(key=lambda r: -wait[r.rid])
    return [[r.t_due - window.t_start, 1e3 * wait[r.rid], len(r.prompt),
             r.bucket] for r in due[:n]]


def _longest_gaps(served_reqs, window, n: int = 8) -> list:
    """[start s, ms, request] of the ``n`` longest gaps between
    consecutive tokens of one request inside the window."""
    gaps = [(a - window.t_start, 1e3 * (b - a), r.rid)
            for r in served_reqs
            for a, b in zip(r.stamps, r.stamps[1:]) if b <= window.t_end]
    return [list(g) for g in sorted(gaps, key=lambda g: -g[1])[:n]]


def _at(values, qs) -> dict[int, float] | None:
    """Percentiles ``qs`` of ``values`` in ms, for the info line."""
    if not values:
        return None
    return {q: 1e3 * latency.percentile(values, q) for q in qs}


def cell_traffic(cell: dict, seconds: float, seed: int,
                 rate_per_s: float | None = None):
    """(prompts, output lengths, arrivals) of a window of ``cell``, at
    its own rate unless ``rate_per_s`` is given."""
    from bench import traffic as gen

    params = cell["params"]
    return gen.generate(cell["traffic"], seconds=seconds,
                        slots=params["slots"],
                        rate_per_s=rate_per_s or params.get("rate_per_s"),
                        seed=seed, vocab=cell["config"]["vocab_size"])


def _per_layer(cell, prof, spec, peak, served_reqs, stats, window, device,
               keep_trace, root):
    from repro import obs

    spans = obs.recorder().drain()
    obs.disable()
    reduced = None
    if prof.t1 is not None:
        events = prof.read()
        if keep_trace:
            with gzip.open(keep_trace, "wt") as f:
                json.dump(events, f)
        reduced = devtrace.reduce(events)
        _log(f"trace lines: {reduced['lines']}; programs: "
             f"{ {n: v['n'] for n, v in reduced['programs'].items()} }")
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = prof.t1 - prof.t0
    ctx = {"spec": spec, "peak": peak, "served": served_reqs,
           "spans": spans, "stats": stats,
           "window": (window.t_start, window.t_end), "trace": reduced,
           "traced": (prof.t0, prof.t1), "device": device}
    if prof.t1 is not None:
        inside = {}
        for s in spans:
            if prof.t0 <= s.t0 < prof.t1:
                inside[s.name] = inside.get(s.name, 0) + 1
        _log(f"spans begun in the traced stretch: {inside}")
    metrics = {}
    for m in cell["per_layer"]:
        v = lookup.metric_reader(m["name"], root)(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    breakdown = None
    if reduced is not None:
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}
    return metrics, breakdown


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also write the traced run's device events "
                    "(JSON, gzip) to this path")
    args = ap.parse_args(argv)
    try:
        result, info, checks = run_cell(args.workload, args.seed,
                                        args.seconds, bool(args.trace),
                                        keep_trace=args.keep_trace)
    except NoChip as e:
        _log(f"bench: {e}")
        return 2
    print(json.dumps({"info": info}), flush=True)
    for name, c in checks.items():
        _log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
