"""Mesh-aware planning: per-port transfer model, interconnect spill
exclusion, collective capture, multi-port DES overlap, autotune on
collective graphs, and the plan-cache ledger.

Single-chip bit-identity is the load-bearing invariant: with one DMA
port in play the max-over-ports transfer model must degenerate to the
old Σ-over-levels model exactly (every pre-mesh golden value in
tests/test_targets.py / test_objective.py doubles as a regression on
this), and ``capture_block`` at mesh_size=1 must return the plain
``block_graph`` unchanged.
"""
import pytest

from repro.configs import get_config
from repro.core import hw
from repro.core.ftl import partition
from repro.core.ftl.graph import (CollectiveNode, OpGraph, block_graph,
                                  collective)
from repro.core.ftl.ir import Dim, Role, TensorSpec
from repro.distributed import mesh_capture as mc
from repro.sim import lower_chain, simulate_chain

CFG = get_config("llama3.2-3b").reduced()


# ---------------------------------------------------------------------------
# per-port transfer model
# ---------------------------------------------------------------------------

def test_single_port_max_degenerates_to_sum():
    """With every level on the default port, max-over-ports IS the old
    Σ-over-levels serialization — single-chip plans stay bit-identical."""
    t = hw.get_target("rv32_npu")
    assert all(lv.dma_port == "dma" for lv in t.backing)
    by = {lv.name: 1 << 20 for lv in t.backing}
    tr = {lv.name: 4 for lv in t.backing}
    assert t.transfer_time(by, tr) == t.transfer_time_serialized(by, tr)


def test_multi_port_transfer_is_max_over_ports():
    t = hw.get_target("tpu_v5e")
    hbm = next(lv for lv in t.backing if lv.name == "hbm")
    ici = next(lv for lv in t.backing if lv.name == "ici")
    assert ici.dma_port == "ici" and hbm.dma_port == "dma"
    by = {"hbm": 8 << 20, "ici": 64 << 20}
    tr = {"hbm": 2, "ici": 3}
    per = t.transfer_time_by_port(by, tr)
    assert set(per) == {"dma", "ici"}
    assert t.transfer_time(by, tr) == pytest.approx(max(per.values()))
    assert t.transfer_time_serialized(by, tr) == pytest.approx(
        sum(per.values()))
    # with only default-port traffic the two agree (bit-identity leg)
    assert t.transfer_time({"hbm": 8 << 20}, {"hbm": 2}) == \
        t.transfer_time_serialized({"hbm": 8 << 20}, {"hbm": 2})


def test_interconnect_classification_and_presets():
    tpu = hw.get_target("tpu_v5e")
    assert tpu.interconnect is not None
    assert tpu.interconnect.name == "ici"
    assert tpu.interconnect.is_interconnect
    assert not tpu.fast.is_interconnect
    mesh = hw.get_target("rv32_mesh")
    assert mesh.interconnect.name == "noc"
    assert mesh.interconnect.dma_port == "noc"
    assert hw.get_target("rv32_npu").interconnect is None


def test_spill_never_lands_on_interconnect():
    """Regression: the ici level's 1<<50 sentinel capacity must never
    win the first-fit — an hbm-overflowing tensor spills to hbm, not to
    the interconnect."""
    t = hw.get_target("tpu_v5e")
    hbm = next(lv for lv in t.backing if lv.name == "hbm")
    too_big = {"w": hbm.capacity_bytes * 2, "x": 1 << 10}
    homes = t.assign_homes(too_big)
    assert homes["w"].name == "hbm"
    assert all(not lv.is_interconnect for lv in homes.values())
    # same on the rv32 mesh preset: spills land on l3, never the noc
    m = hw.get_target("rv32_mesh")
    deepest_mem = [lv for lv in m.backing if not lv.is_interconnect][-1]
    homes = m.assign_homes({"w": deepest_mem.capacity_bytes * 2})
    assert homes["w"].name == deepest_mem.name


# ---------------------------------------------------------------------------
# CollectiveNode + capture
# ---------------------------------------------------------------------------

def _sharded_graph(m=128, n=2):
    return mc.capture_block(CFG, m=m, mesh_size=n)


def test_capture_mesh1_is_plain_block_graph():
    assert mc.capture_block(CFG, m=128, mesh_size=1) == \
        block_graph(CFG, m=128)


def test_capture_inserts_all_reduces():
    g = _sharded_graph()
    colls = [op for op in g.ops if isinstance(op, CollectiveNode)]
    assert [c.comm for c in colls] == ["all_reduce", "all_reduce"]
    assert {c.name for c in colls} == {"comm.proj.wo", "comm.mlp.gemm2"}
    # consumers downstream read the reduced tensor, not the partial
    names = [op.name for op in g.ops]
    red = next(op for op in g.ops if op.name == "comm.proj.wo")
    assert red.output.name == red.inputs[0].name + "_red"
    for op in g.ops[names.index("comm.proj.wo") + 1:]:
        assert red.inputs[0].name not in {t.name for t in op.inputs}


def test_collective_ring_formulas():
    g = _sharded_graph(n=4)
    sizes = {d.name: d.size for d in g.dims}
    red = next(op for op in g.ops if op.name == "comm.proj.wo")
    payload = red.inputs[0].bytes_full(sizes)
    # ring all-reduce: 2 phases x (n-1)/n of the payload, (n-1) msgs each
    assert red.comm_bytes(sizes) == 2 * payload * 3 // 4
    assert red.comm_transfers(sizes) == 2 * 3
    # builder sanity: all_gather prices the (bigger) output
    sz = {"m": 32, "d": 16}
    x = TensorSpec("x", ("m", "d"), "float32", Role.INPUT)
    out = TensorSpec("xg", ("m", "d"), "float32", Role.OUTPUT)
    ag = collective("ag", "all_gather", x, out, mesh_size=4)
    assert ag.comm_bytes(sz) == out.bytes_full(sz) * 3 // 4
    assert ag.mesh_size == 4 and Dim("m", 32).size == 32
    with pytest.raises(ValueError):
        collective("bad", "all_to_nowhere", x, out, mesh_size=2)


def test_shard_spec_divisibility():
    assert mc.shard_spec(CFG, 1).any is False
    s2 = mc.shard_spec(CFG, 2)
    assert s2.heads and s2.d_ff
    # a mesh that divides d_ff but not the kv heads shards only the MLP
    big = mc.shard_spec(CFG, 8)
    assert not big.heads and big.d_ff
    assert big.any


def test_strip_and_map_cuts_roundtrip():
    g = _sharded_graph()
    stripped = mc.strip_collectives(g)
    assert not any(isinstance(op, CollectiveNode) for op in stripped.ops)
    assert stripped.n_ops == g.n_ops - 2
    cuts = mc.map_cuts(g, stripped, partition.all_cuts(stripped))
    # every mapped cut is a valid boundary of the full graph
    assert all(0 < c < g.n_ops for c in cuts)
    assert mc.strip_collectives(stripped) is stripped


# ---------------------------------------------------------------------------
# planning with collectives
# ---------------------------------------------------------------------------

def test_plan_prices_collectives_on_interconnect_port():
    g = _sharded_graph()
    p = partition.plan_chain(g, target=hw.get_target("tpu_v5e"))
    colls = [cc for s in p.segments for cc in s.plan.report.collectives]
    assert len(colls) == 2
    assert all(cc.level == "ici" for cc in colls)
    assert all(cc.comm == "all_reduce" for cc in colls)
    # the all-reduced partial is produced in-segment when fused with its
    # producer; the cost report records the dependency for the DES
    for cc in colls:
        if cc.producer:
            assert not cc.pre


def test_plan_collectives_require_interconnect():
    g = _sharded_graph()
    with pytest.raises(ValueError, match="interconnect"):
        partition.plan_chain(g, target=hw.get_target("rv32_npu"))


def test_blind_plan_same_graph_different_knowledge():
    g = _sharded_graph(m=1024)
    t = hw.get_target("rv32_mesh")
    aware = partition.plan_chain(g, target=t)
    blind = mc.plan_collective_blind(g, target=t)
    # both plan the FULL graph (collectives priced in both reports) —
    # only the cut decision was made blind
    assert blind.graph == g
    assert sum(len(s.plan.report.collectives)
               for s in blind.segments) == 2
    # the aware DP must never model worse than the blind one
    assert aware.modeled_runtime_s <= blind.modeled_runtime_s + 1e-12


# ---------------------------------------------------------------------------
# multi-port DES
# ---------------------------------------------------------------------------

def test_comm_chunks_sum_to_analytic_totals():
    g = _sharded_graph(m=512)
    p = partition.plan_chain(g, target=hw.get_target("rv32_mesh"))
    lowered = lower_chain(p)
    assert len(lowered) == len(p.segments)
    seen = 0
    for (sched, _rep), seg in zip(lowered, p.segments):
        by_op: dict[str, int] = {}
        setups: dict[str, int] = {}
        for e in sched.comm_events():
            by_op[e.op] = by_op.get(e.op, 0) + e.bytes
            setups[e.op] = setups.get(e.op, 0) + e.setups
        for cc in seg.plan.report.collectives:
            assert by_op[cc.name] == cc.bytes
            assert setups[cc.name] == cc.transfers
            seen += 1
    assert seen == 2


def test_multi_port_sim_never_loses_to_shared_port():
    for preset in ("tpu_v5e", "rv32_mesh"):
        t = hw.get_target(preset)
        g = _sharded_graph(m=512)
        p = partition.plan_chain(g, target=t)
        lowered = lower_chain(p)
        split = simulate_chain(lowered)
        shared = simulate_chain(lowered, share_ports=True)
        assert split.runtime_s <= shared.runtime_s + 1e-12
        # the interconnect port shows up as its own busy track
        key = f"dma:{t.interconnect.dma_port}"
        assert key in split.busy_s and split.busy_s[key] > 0
        assert key not in shared.busy_s
        # the DES only ever adds real serialization over the roofline
        assert split.runtime_s >= split.analytic_runtime_s * (1 - 1e-9)


def test_chrome_trace_has_collective_track():
    from repro.sim import to_chrome_trace
    g = _sharded_graph(m=256)
    p = partition.plan_chain(g, target=hw.get_target("tpu_v5e"))
    tr = to_chrome_trace(p)
    tracks = {e["args"]["name"] for e in tr["traceEvents"]
              if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert "dma:ici" in tracks
    comm = [e for e in tr["traceEvents"]
            if e.get("ph") == "X" and e["name"].startswith("all_reduce:")]
    assert comm and all(e["cat"] == "dma" for e in comm)


# ---------------------------------------------------------------------------
# autotune accepts collective graphs
# ---------------------------------------------------------------------------

def test_autotune_on_collective_graph():
    from repro.tune import autotune_chain
    g = _sharded_graph(m=256)
    res = autotune_chain(g, target=hw.get_target("rv32_mesh"))
    assert res.sim_runtime_s <= res.baseline_sim_runtime_s + 1e-12
    colls = [cc for s in res.chain.segments
             for cc in s.plan.report.collectives]
    assert len(colls) == 2


# ---------------------------------------------------------------------------
# plan-cache ledger
# ---------------------------------------------------------------------------

def test_plan_cache_stats_and_clear():
    from repro.core.ftl import clear_plan_caches, plan_cache_stats
    stats = plan_cache_stats()
    for name in ("partition._plan_chain_cached",
                 "registry._plan_block_cached"):
        assert name in stats, sorted(stats)
    before = plan_cache_stats()["partition._plan_chain_cached"]["misses"]
    g = block_graph(CFG, m=96)
    partition.plan_chain(g, target=hw.get_target("tpu_v5e"))
    mid = plan_cache_stats()["partition._plan_chain_cached"]
    assert mid["misses"] == before + 1
    partition.plan_chain(g, target=hw.get_target("tpu_v5e"))
    after = plan_cache_stats()["partition._plan_chain_cached"]
    assert after["hits"] == mid["hits"] + 1
    clear_plan_caches()
    cleared = plan_cache_stats()
    assert all(s["size"] == 0 for s in cleared.values())


def test_graph_exports():
    from repro.core.ftl import graph as graph_mod
    assert "CollectiveNode" in graph_mod.__all__
    assert "collective" in graph_mod.__all__
    assert isinstance(_sharded_graph(), OpGraph)
