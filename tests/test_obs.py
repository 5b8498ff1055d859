"""Unified runtime telemetry (repro.obs): span ring buffer, metrics
registry + Prometheus exposition, merged live/modeled Chrome traces —
plus the serving/planner integration pins (plan_report key schema, the
full plan-cache ledger)."""
import dataclasses
import threading

import pytest

from repro import obs
from repro.obs.spans import SpanRecorder


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_recorder_nesting_and_order():
    rec = SpanRecorder(capacity=16)
    rec.begin("outer", "t")
    rec.begin("inner", "t")
    rec.end()
    rec.end()
    rows = rec.snapshot()
    # inner ends first, so it commits first
    assert [s.name for s in rows] == ["inner", "outer"]
    assert rows[0].depth == 1 and rows[1].depth == 0
    assert all(s.t1 >= s.t0 for s in rows)
    inner, outer = rows
    assert outer.t0 <= inner.t0 and inner.t1 <= outer.t1


def test_span_recorder_ring_overflow_counts_dropped():
    rec = SpanRecorder(capacity=4)
    for i in range(10):
        with rec.span(f"s{i}", "t"):
            pass
    rows = rec.snapshot()
    assert len(rows) == 4
    assert [s.name for s in rows] == ["s6", "s7", "s8", "s9"]
    assert rec.dropped == 6


def test_span_recorder_drain_resets():
    rec = SpanRecorder(capacity=8)
    with rec.span("a", "t"):
        pass
    assert len(rec.drain()) == 1
    assert len(rec) == 0 and rec.snapshot() == []


def test_span_recorder_unbalanced_end_is_safe():
    rec = SpanRecorder(capacity=8)
    rec.end()                    # underflow: no-op, no exception
    assert rec.snapshot() == []


def test_spans_per_thread_ids():
    rec = SpanRecorder(capacity=16)

    def work():
        with rec.span("worker", "t"):
            pass

    t = threading.Thread(target=work)
    with rec.span("main", "t"):
        t.start()
        t.join()
    tids = {s.name: s.tid for s in rec.snapshot()}
    assert tids["worker"] != tids["main"]


def test_module_level_span_respects_enable():
    obs.disable()
    try:
        with obs.span("ignored", "t"):
            pass
        assert obs.recorder() is None
        rec = obs.enable(capacity=8)
        with obs.span("kept", "t"):
            pass
        assert [s.name for s in rec.snapshot()] == ["kept"]
        # disabling mid-span must not unbalance: the cm pinned `rec`
        with obs.span("pinned", "t"):
            obs.disable()
        assert "pinned" in [s.name for s in rec.snapshot()]
    finally:
        obs.enable()


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_counter_labels_and_monotonicity():
    reg = obs.MetricsRegistry()
    c = reg.counter("hits_total", "h", ("kind",))
    c.labels(kind="a").inc()
    c.labels(kind="a").inc(2)
    c.labels(kind="b").inc()
    samples = dict((tuple(sorted(lbl.items())), v)
                   for lbl, v in reg.collect()["hits_total"]["samples"])
    assert samples[(("kind", "a"),)] == 3
    assert samples[(("kind", "b"),)] == 1
    with pytest.raises(ValueError):
        c.labels(kind="a").inc(-1)


def test_gauge_set_inc_dec():
    reg = obs.MetricsRegistry()
    g = reg.gauge("depth", "d")
    g.set(5)
    g.inc()
    g.dec(2)
    ((_, v),) = reg.collect()["depth"]["samples"]
    assert v == 4


def test_histogram_cumulative_buckets_sum_count():
    reg = obs.MetricsRegistry()
    h = reg.histogram("lat", "l", buckets=(0.1, 1.0, float("inf")))
    for x in (0.05, 0.5, 0.5, 3.0):
        h.observe(x)
    rows = {}
    for lbl, v in reg.collect()["lat"]["samples"]:
        if "le" in lbl:
            rows[lbl["le"]] = v
        elif "__count__" in lbl:
            rows["count"] = v
        elif "__sum__" in lbl:
            rows["sum"] = v
    assert rows["0.1"] == 1          # cumulative
    assert rows["1.0"] == 3
    assert rows["+Inf"] == 4
    assert rows["count"] == 4 and rows["sum"] == pytest.approx(4.05)
    text = obs.prometheus_text(reg)
    assert 'lat_bucket{le="1.0"} 3' in text
    assert "lat_sum" in text and "lat_count 4" in text


def test_registry_rejects_type_and_label_conflicts():
    reg = obs.MetricsRegistry()
    reg.counter("x_total", "x")
    with pytest.raises(ValueError):
        reg.gauge("x_total", "x")
    with pytest.raises(ValueError):
        reg.counter("x_total", "x", ("k",))


def test_registry_reset_zeroes_but_keeps_registrations():
    reg = obs.MetricsRegistry()
    c = reg.counter("n_total", "n")
    c.inc(7)
    reg.reset()
    ((_, v),) = reg.collect()["n_total"]["samples"]
    assert v == 0
    assert reg.counter("n_total", "n") is c


def test_prometheus_text_exposition_shape():
    reg = obs.MetricsRegistry()
    reg.counter("req_total", "requests served", ("code",)) \
       .labels(code="200").inc(3)
    text = obs.prometheus_text(reg)
    assert "# HELP req_total requests served" in text
    assert "# TYPE req_total counter" in text
    assert 'req_total{code="200"} 3' in text


# ---------------------------------------------------------------------------
# merged trace + planner/serving integration (jax below this line)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def setup():
    import jax

    from repro import configs
    from repro.models import model as M

    cfg = dataclasses.replace(configs.get_config("llama3.2-3b").reduced(),
                              remat=False)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_merged_trace_has_modeled_and_live_pids(setup):
    from repro.core.ftl import registry

    cfg, _ = setup
    plan = registry.plan_block(cfg, m=32)
    rec = SpanRecorder(capacity=16)
    with rec.span("live_work", "t"):
        pass
    trace = obs.merged_chrome_trace(spans=rec, chain=plan)
    pids = {e["pid"] for e in trace["traceEvents"] if e.get("ph") == "X"}
    assert pids == {0, 1}
    live = [e for e in trace["traceEvents"]
            if e.get("pid") == 1 and e.get("ph") == "X"]
    assert [e["name"] for e in live] == ["live_work"]
    assert "metrics" in trace["otherData"]


def test_plan_cache_gauges_follow_clear(setup):
    from repro.core.ftl import clear_plan_caches, registry

    cfg, _ = setup
    registry.plan_block(cfg, m=32)
    snap = obs.collect()["ftl_plan_cache_size"]["samples"]
    assert any(v > 0 for _, v in snap)
    # the ledger reset empties every cache; the gauges must follow on
    # the next collect — while monotone counters (plan_block calls) keep
    # counting across the reset
    before = sum(v for _, v
                 in obs.collect()["ftl_plan_block_total"]["samples"])
    clear_plan_caches()
    snap = obs.collect()["ftl_plan_cache_size"]["samples"]
    assert all(v == 0 for _, v in snap)
    registry.plan_block(cfg, m=32)
    after = sum(v for _, v
                in obs.collect()["ftl_plan_block_total"]["samples"])
    assert after == before + 1


def test_plan_cache_stats_covers_every_memoized_planner(setup):
    """The full ledger: all 11 plan caches across the planning stack."""
    import repro.tune.autotune  # noqa: F401  — registers the tune cache
    from repro.core.ftl import plan_cache_stats

    stats = plan_cache_stats()
    assert sorted(stats) == [
        "ftl._plan_attention_cached",
        "ftl._plan_mlp_cached",
        "partition._plan_chain_cached",
        "partition._plan_chain_top_k_cached",
        "registry._attention_kernel_footprint_fits",
        "registry._mlp_executor_cached",
        "registry._mlp_kernel_footprint_fits",
        "registry._partial_mlp_footprint_fits",
        "registry._plan_block_cached",
        "registry._scan_tile",
        "tune._autotune_cached",
    ]
    for name, s in stats.items():
        assert {"hits", "misses", "size", "maxsize"} <= set(s), name


def test_serve_engine_obs_spans_gauges_and_report_schema(setup):
    import numpy as np

    from repro.launch.serve import Request, ServeEngine

    cfg, params = setup
    # fresh full-size buffer: an earlier test may have left a tiny one
    obs.disable()
    obs.enable(capacity=1024)
    rng = np.random.default_rng(0)
    eng = ServeEngine(cfg, params, batch_slots=2, max_seq=32, eos_id=-1)
    reqs = [Request(i, rng.integers(2, cfg.vocab_size, size=6)
                    .astype(np.int32), 3) for i in range(2)]
    eng.run(list(reqs), {})

    names = {s.name for s in obs.recorder().snapshot()}
    assert "serve:decode_step" in names
    assert "serve:admit" in names
    assert any(n.startswith("serve:prefill:m") for n in names)

    # the engine's counters on the registry: the five serve_stats labels
    # the benchmark's metrics read, at the engine's own values
    got = {lbl["stat"]: v for lbl, v
           in obs.collect()["serve_stats"]["samples"]}
    assert set(got) == {"prefills", "decode_steps", "tokens", "replans",
                        "paged_kernel_steps"}
    assert got == {k: eng.stats[k] for k in got}
    assert got["prefills"] == 2 and got["decode_steps"] >= 2

    # plan_report key schema pin (the serving dashboard contract)
    report = eng.plan_report()
    assert set(report) == {"target", "buckets", "prefill", "decode",
                           "decode_differs_from_prefill", "plan_caches"}
    for regime in ("prefill", "decode"):
        entry = report[regime]
        assert set(entry) == {"m", "schedule", "cuts", "executors"}
        assert set(entry["executors"]) == {"gemm", "attention", "mlp"}
    assert isinstance(report["decode_differs_from_prefill"], bool)


def test_monitor_metrics_emit(tmp_path):
    from repro.runtime.monitor import HeartbeatMonitor, StragglerMonitor

    def _val(name):
        ((_, v),) = obs.collect()[name]["samples"]
        return v

    flagged0 = _val("train_straggler_flagged_total")
    mon = StragglerMonitor(threshold=1e-9, warmup=0)
    mon.start_step()
    mon.end_step(0)                 # first step seeds the EMA, no flag
    mon.start_step()
    stat = mon.end_step(1)          # threshold ~0: certainly flagged
    assert stat.flagged
    assert _val("train_straggler_flagged_total") == flagged0 + 1
    assert _val("train_step_seconds") == stat.seconds

    stamps0 = _val("train_heartbeat_stamps_total")
    hb = HeartbeatMonitor(str(tmp_path), 0, timeout=1e6)
    hb.stamp()
    assert hb.stale_peers() == []
    assert _val("train_heartbeat_stamps_total") == stamps0 + 1
    assert _val("train_heartbeat_oldest_age_seconds") >= 0


# ---------------------------------------------------------------------------
# phase spans inside the serving engine, mirrored into the JAX profiler
# ---------------------------------------------------------------------------

_STEP_PHASES = ("serve:step:prepare", "serve:step:launch",
                "serve:step:sync", "serve:step:commit")


def test_switch_tiles_and_record_commits_an_interval():
    rec = SpanRecorder(capacity=16)
    rec.begin("outer", "t")
    rec.begin("a", "t")
    rec.switch("b", "t")
    rec.end()
    rec.record("waited", "t", 1.0, 2.5)
    rec.end()
    rows = {s.name: s for s in rec.snapshot()}
    assert rows["a"].t1 == rows["b"].t0
    assert rows["a"].depth == rows["b"].depth == 1
    assert (rows["waited"].t0, rows["waited"].t1) == (1.0, 2.5)
    assert rows["outer"].t0 <= rows["a"].t0 and rows["b"].t1 <= \
        rows["outer"].t1


def _tiny_engine(setup, **kw):
    from repro.launch.serve import ServeEngine

    cfg, params = setup
    return ServeEngine(cfg, params, batch_slots=2, max_seq=32, eos_id=-1,
                       **kw)


def _prompts(setup, n, seed=0):
    import numpy as np

    from repro.launch.serve import Request

    cfg, _ = setup
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(2, cfg.vocab_size, size=6)
                    .astype(np.int32), 4) for i in range(n)]


def _fresh_recorder():
    obs.disable()
    return obs.enable(capacity=4096)


def test_step_phases_tile_each_decode_step(setup):
    eng = _tiny_engine(setup)
    rec = _fresh_recorder()
    try:
        eng.run(_prompts(setup, 3), {})
        rows = rec.snapshot()
    finally:
        obs.enable()
    steps = [s for s in rows if s.name == "serve:decode_step"]
    assert steps
    for step in steps:
        inside = [s for s in rows if s.name.startswith("serve:step:")
                  and step.t0 <= s.t0 and s.t1 <= step.t1]
        assert tuple(s.name for s in inside) == _STEP_PHASES
        for a, b in zip(inside, inside[1:]):
            assert a.t1 == b.t0              # no gap between phases
        assert sum(s.duration_s for s in inside) == pytest.approx(
            step.duration_s, rel=0.05)
    # the loop between steps is spanned, and no new name can be taken
    # for a step or a prefill by the span counts that read those
    names = {s.name for s in rows}
    assert "serve:schedule" in names
    assert all(n == "serve:decode_step" or n in _STEP_PHASES
               or not n.startswith("serve:decode_step") for n in names)


def test_one_queue_span_per_admission_from_its_arrival(setup):
    eng = _tiny_engine(setup)
    eng.run(_prompts(setup, 1), {})       # compile outside the timing
    reqs = _prompts(setup, 4, seed=1)
    rec = _fresh_recorder()
    try:
        # the last arrives after the first three have finished
        done = eng.run(list(reqs), {}, arrivals=[0.0, 0.001, 0.002, 0.5])
        rows = rec.snapshot()
    finally:
        obs.enable()
    assert len(done) == 4
    queue = [s for s in rows if s.name == "serve:queue"]
    admits = [s for s in rows if s.name == "serve:admit"]
    assert len(queue) == len(admits) == 4
    assert sorted(s.t0 for s in queue) == sorted(r.t_arrival for r in reqs)
    for q in queue:
        # it ends where its admission begins (two clock reads apart)
        assert min(a.t0 - q.t1 for a in admits if a.t0 >= q.t1) < 0.05
    assert any(s.name == "serve:wait_arrival" for s in rows)
    assert {"serve:admit:pages", "serve:admit:first_token"} <= {
        s.name for s in rows}


class _StopAfter(list):
    """A request's output list that raises once it holds ``n`` tokens, as
    a caller that ends serving from inside a token append does."""

    def __init__(self, n):
        super().__init__()
        self.n = n

    def append(self, token):
        if len(self) >= self.n:
            raise KeyboardInterrupt("stop")
        super().append(token)


def test_an_exception_from_a_token_append_leaves_the_stack_balanced(setup):
    eng = _tiny_engine(setup)
    req = _prompts(setup, 1)[0]
    req.out = _StopAfter(2)
    rec = _fresh_recorder()
    try:
        with pytest.raises(KeyboardInterrupt):
            eng.run([req], {})
        rec.begin("probe", "t")
        rec.end()
        rows = rec.snapshot()
    finally:
        obs.enable()
    assert rows[-1].name == "probe" and rows[-1].depth == 0
    # the step that raised closed its phase and itself
    assert rows[-2].name == "serve:decode_step"
    assert rows[-3].name == "serve:step:commit"


class _CountingAnnotation:
    """Stands in for jax.profiler.TraceAnnotation."""
    made: list = []

    def __init__(self, name):
        self.name, self.open = name, False
        _CountingAnnotation.made.append(self)

    def __enter__(self):
        self.open = True
        return self

    def __exit__(self, *exc):
        self.open = False


def test_spans_mirror_into_profiler_annotations_only_while_on(
        setup, monkeypatch):
    import jax.profiler

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    _CountingAnnotation.made = []
    eng = _tiny_engine(setup)
    obs.disable()
    try:
        eng.run(_prompts(setup, 2), {})
        assert obs.recorder() is None and _CountingAnnotation.made == []
        rec = obs.enable(capacity=4096)
        eng.run(_prompts(setup, 2), {})
        rows = rec.snapshot()
    finally:
        obs.disable()
        obs.enable()
    made = _CountingAnnotation.made
    assert made and not any(a.open for a in made)
    # every bracketed span has its annotation; a queue wait has none
    assert sorted(a.name for a in made) == sorted(
        s.name for s in rows if s.name != "serve:queue")


def test_importing_obs_pulls_no_jax():
    import os
    import pathlib
    import subprocess
    import sys

    src = pathlib.Path(__file__).resolve().parent.parent / "src"
    env = dict(os.environ, PYTHONPATH=str(src))
    code = ("import sys, repro.obs; "
            "sys.exit('jax' in sys.modules)")
    assert subprocess.run([sys.executable, "-c", code], env=env,
                          timeout=120).returncode == 0
