"""repro.tune test harness: the tuned-never-worse invariant (tuned
simulated runtime ≤ analytic-best simulated runtime on every preset ×
workload, hypothesis-fuzzed over shapes), determinism (no RNG: same
inputs → the identical chosen plan), the k-best shortlist's exactness
pins (entry 0 == the argmin ``solve``/``plan_chain`` return), the
plan-cache key regression (a tuned plan never aliases the analytic plan
for the same shapes), and the Chrome-trace export."""
import dataclasses
import json

import pytest

from repro import configs, sim
from repro.core import hw
from repro.core.ftl import graph, partition, registry, solver
from repro.tune import AutotuneConfig, autotune_chain, tile_ladder
from repro.tune.autotune import _Search

PRESETS = list(hw.presets())
PRESET_IDS = [t.name for t in PRESETS]

# small shapes + tight budget: each search is a few dozen replays
FAST = AutotuneConfig(top_k_partitions=2, top_k_tiles=2, beam_width=3,
                      max_rounds=2, max_sims=64)


def _paper_op(m=256, k=768, n=3072, dtype="int8"):
    return graph.gemm_act_graph(m=m, k=k, n=n, dtype=dtype)


def _zoo_block(m=32):
    cfg = dataclasses.replace(configs.get_config("llama3.2-3b").reduced(),
                              dtype="float32", remat=False, ftl_mode="auto")
    return cfg, graph.block_graph(cfg, m=m, dtype="float32")


# ---------------------------------------------------------------------------
# analytic shortlist: k-best extensions stay exact at k=1 / entry 0
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("target", PRESETS, ids=PRESET_IDS)
def test_solve_top_k_entry0_is_solve(target):
    g = _paper_op()
    group = g.group(0, g.n_ops)
    best = solver.solve(group, target=target)
    top = solver.solve_top_k(group, target=target, k=3)
    assert 1 <= len(top) <= 3
    assert top[0].tiles == best.tiles
    # ranked: analytically non-decreasing modeled runtime
    times = [hw.round_time(p.modeled_runtime_s) for p in top]
    assert times == sorted(times)
    # distinct assignments
    assert len({tuple(sorted(p.tiles.items())) for p in top}) == len(top)


@pytest.mark.parametrize("target", PRESETS, ids=PRESET_IDS)
def test_plan_chain_top_k_entry0_is_plan_chain(target):
    _, g = _zoo_block()
    best = partition.plan_chain(g, target=target)
    top = partition.plan_chain_top_k(g, target=target, k=3)
    assert top[0].cuts() == best.cuts()
    assert top[0].modeled_runtime_s == best.modeled_runtime_s
    times = [hw.round_time(c.modeled_runtime_s) for c in top]
    assert times == sorted(times)
    assert len({c.cuts() for c in top}) == len(top)


def test_top_k_rejects_bad_k():
    g = _paper_op()
    with pytest.raises(ValueError):
        solver.solve_top_k(g.group(0, g.n_ops), k=0)
    with pytest.raises(ValueError):
        partition.plan_chain_top_k(g, k=0)


def test_tile_ladder_adds_aligned_midpoints():
    g = _paper_op()
    plan = solver.solve(g.group(0, g.n_ops), target=hw.TPU_V5E)
    for d, c in plan.constraints.items():
        ladder = tile_ladder(c)
        assert set(c.candidates) <= set(ladder)
        assert all(x % max(c.alignment, 1) == 0 for x in ladder)
        assert list(ladder) == sorted(ladder)
        if len(c.candidates) == 1:
            assert ladder == c.candidates


# ---------------------------------------------------------------------------
# the invariant: tuned simulated runtime <= analytic-best simulated runtime
# ---------------------------------------------------------------------------

def _check_never_worse(g, target, config=FAST):
    res = autotune_chain(g, target=target, config=config)
    baseline = sim.simulate_chain(
        sim.lower_chain(partition.plan_chain(g, target=target))).runtime_s
    assert baseline == pytest.approx(res.baseline_sim_runtime_s, rel=1e-12)
    assert (hw.round_time(res.sim_runtime_s)
            <= hw.round_time(res.baseline_sim_runtime_s))
    assert res.improved == (hw.round_time(res.sim_runtime_s)
                            < hw.round_time(res.baseline_sim_runtime_s))
    # the winning chain replays to exactly the reported runtime
    replay = sim.simulate_chain(sim.lower_chain(res.chain)).runtime_s
    assert replay == pytest.approx(res.sim_runtime_s, rel=1e-12)
    return res


@pytest.mark.parametrize("target", PRESETS, ids=PRESET_IDS)
def test_tuned_never_worse_paper_op(target):
    _check_never_worse(_paper_op(), target)


@pytest.mark.parametrize("target", PRESETS, ids=PRESET_IDS)
def test_tuned_never_worse_zoo_block(target):
    _, g = _zoo_block()
    _check_never_worse(g, target)


def test_tuner_improves_somewhere():
    """The strict half of the CI gate: across the presets the DES-scored
    search must beat the analytic argmin at least once (fill/drain
    stalls, depth headroom and analytic near-ties guarantee slack)."""
    assert any(_check_never_worse(_paper_op(), t).improved for t in PRESETS)


try:
    import hypothesis.strategies as st
    from hypothesis import given, settings

    dim = st.sampled_from([128, 256, 512])

    @settings(max_examples=8, deadline=None)
    @given(m=dim, k=dim, n=dim)
    def test_tuned_never_worse_fuzz(m, k, n):
        tiny = AutotuneConfig(top_k_partitions=2, top_k_tiles=2,
                              beam_width=2, max_rounds=1, max_sims=24)
        _check_never_worse(_paper_op(m=m, k=k, n=n),
                           hw.get_target("rv32_l1_l2"), config=tiny)
except ImportError:  # pragma: no cover - hypothesis optional locally
    pass


# ---------------------------------------------------------------------------
# determinism: no RNG anywhere — same inputs, same chosen plan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("target", PRESETS, ids=PRESET_IDS)
def test_autotune_is_deterministic(target):
    g = _paper_op()
    # two fresh searches, bypassing the lru cache
    a = _Search(g, target, FAST, None).run()
    b = _Search(g, target, FAST, None).run()
    assert a.sim_runtime_s == b.sim_runtime_s
    assert a.n_scored == b.n_scored
    assert a.chain.target.name == b.chain.target.name
    assert a.chain.cuts() == b.chain.cuts()
    for sa, sb in zip(a.chain.segments, b.chain.segments):
        assert sa.plan.tiles == sb.plan.tiles
        assert sa.plan.report.op_compute == sb.plan.report.op_compute
    # and the cached entry point returns one object for one key
    assert autotune_chain(g, target=target, config=FAST) is \
        autotune_chain(g, target=target, config=FAST)


def test_autotune_config_validates():
    with pytest.raises(ValueError):
        AutotuneConfig(top_k_tiles=0)
    with pytest.raises(ValueError):
        AutotuneConfig(beam_width=0)
    with pytest.raises(ValueError):
        AutotuneConfig(max_sims=0)
    with pytest.raises(ValueError):
        AutotuneConfig(depth_candidates=(0, 2))


def test_tuned_candidates_respect_budget_and_capacity():
    """Every feasible scored candidate fits the (possibly re-depthed)
    fast level, and the replay budget is honored."""
    g = _paper_op()
    s = _Search(g, hw.get_target("cpu_cache"), FAST, None)
    res = s.run()
    assert s.n_scored <= FAST.max_sims
    assert res.n_feasible <= res.n_scored
    for _, runtime, chain in s.scored.values():
        if runtime is None:
            continue
        for seg in chain.segments:
            assert seg.plan.report.vmem_bytes <= chain.target.fast_capacity


# ---------------------------------------------------------------------------
# regression: plan caches key on the autotune config
# ---------------------------------------------------------------------------

def test_model_block_plan_cache_keys_autotune():
    """Mirror of test_model_block_plan_cache_keys_target: requesting a
    DES-tuned plan must never serve the cached analytic plan (or vice
    versa) for the same (cfg, m, dtype, target)."""
    from repro.models import model as M
    cfg, _ = _zoo_block()
    t = hw.TPU_V5E
    plan_plain = M.plan_or_none(cfg, m=32, dtype="float32", target=t)
    assert plan_plain is not None
    assert plan_plain.tune is None
    plan_tuned = registry.plan_block(cfg, m=32, dtype="float32", target=t,
                                     autotune=FAST)
    assert plan_tuned is not None
    assert plan_tuned is not plan_plain
    assert plan_tuned.tune is not None
    assert plan_tuned.tune.config == FAST
    assert (hw.round_time(plan_tuned.tune.sim_runtime_s)
            <= hw.round_time(plan_tuned.tune.baseline_sim_runtime_s))
    # a different tuning config is a different key too
    other = dataclasses.replace(FAST, max_sims=32)
    plan_other = registry.plan_block(cfg, m=32, dtype="float32", target=t,
                                     autotune=other)
    assert plan_other is not plan_tuned
    # and the untuned entry is still served untouched
    assert M.plan_or_none(cfg, m=32, dtype="float32",
                          target=t) is plan_plain


def test_registry_plan_block_binds_tuned_chain():
    """plan_block(autotune=...) must bind executors against the tuned
    chain's (possibly depth-modified) target, not the request's."""
    cfg, _ = _zoo_block()
    bp = registry.plan_block(cfg, m=32, dtype="float32", target=hw.TPU_V5E,
                             autotune=FAST)
    assert bp.tune is not None
    assert bp.chain is bp.tune.chain
    assert bp.target == bp.chain.target
    assert len(bp.bindings) == len(bp.chain.segments)


# ---------------------------------------------------------------------------
# Chrome-trace export
# ---------------------------------------------------------------------------

def test_chrome_trace_structure(tmp_path):
    g = _paper_op()
    chain = partition.plan_chain(g, target=hw.get_target("rv32_npu"))
    trace = sim.to_chrome_trace(chain)
    json.dumps(trace)                       # serializable as-is
    evs = trace["traceEvents"]
    names = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert "dma" in names
    assert {"engine:npu", "engine:cluster"} <= names
    xs = [e for e in evs if e["ph"] == "X"]
    assert xs
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert e["cat"] in ("dma", "engine")
    # one complete-event per schedule event, laid out per track
    lowered = sim.lower_chain(chain)
    assert len(xs) == sum(len(s.events) for s, _ in lowered)
    # round-trips through the file writer
    out = tmp_path / "trace.json"
    sim.write_chrome_trace(chain, out)
    assert json.loads(out.read_text())["traceEvents"]


def test_bench_autotune_writes_wellformed_json(tmp_path, monkeypatch):
    bench = pytest.importorskip("benchmarks.bench_autotune")
    monkeypatch.setenv("BENCH_SMOKE", "1")
    monkeypatch.chdir(tmp_path)
    bench.main()
    data = json.loads((tmp_path / "BENCH_autotune.json").read_text())
    assert data["smoke"] is True
    assert {t["target"] for t in data["targets"]} == set(PRESET_IDS)
    rows = [t["paper_op"] for t in data["targets"]] + data["zoo_block"]
    for r in rows:
        assert r["gate_tuned_ok"]
        assert r["tuned_sim_ms"] > 0
    assert any(r["improved"] for r in rows)
