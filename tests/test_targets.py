"""Memory-hierarchy Target tests: preset validity, planner monotonicity
in fast-level capacity, cross-preset feasibility on the zoo configs, the
paper's qualitative result on the Siracusa-like preset, the plan-cache
target-keying regression, and target-aware executor qualification."""
import dataclasses

import pytest

from repro import configs
from repro.core import ftl, hw
from repro.core.ftl import graph, partition, registry
from repro.core.ftl.solver import InfeasibleError

KB, MB = 1 << 10, 1 << 20


# a single-backing-level target with zero DMA setup: the modeled-time
# objective reduces to traffic/bw, so traffic-vs-capacity monotonicity is
# exact (with setup cost, a bigger scratchpad may legitimately trade a
# few bytes for far fewer transfers)
def _flat(budget: int) -> hw.Target:
    return hw.Target(
        name=f"flat@{budget}",
        levels=(hw.MemoryLevel("fast", budget, 1e12),
                hw.MemoryLevel("back", 1 << 50, 100e9)),
        flops=1e12,
    )


# ---------------------------------------------------------------------------
# Target construction / presets
# ---------------------------------------------------------------------------

class TestTargetBasics:
    def test_presets_well_formed(self):
        for t in hw.presets():
            assert len(t.levels) >= 2
            assert t.fast is t.levels[0]
            assert t.fast_capacity == t.levels[0].capacity_bytes
            caps = [lv.capacity_bytes for lv in t.levels]
            assert caps == sorted(caps)
        assert {"tpu_v5e", "cpu_cache", "rv32_l1_l2"} <= set(hw.PRESETS)

    def test_rv32_preset_is_two_backing_levels(self):
        t = hw.get_target("rv32_l1_l2")
        assert [lv.name for lv in t.levels] == ["l1", "l2", "l3"]
        assert t.fast_capacity == 256 * KB

    def test_needs_backing_level(self):
        with pytest.raises(ValueError, match="backing"):
            hw.Target(name="x",
                      levels=(hw.MemoryLevel("only", 1 * MB, 1e9),),
                      flops=1e9)

    def test_rejects_shrinking_capacities(self):
        with pytest.raises(ValueError, match="smaller"):
            hw.Target(name="x",
                      levels=(hw.MemoryLevel("fast", 2 * MB, 1e9),
                              hw.MemoryLevel("back", 1 * MB, 1e9)),
                      flops=1e9)

    def test_with_fast_capacity(self):
        t = hw.TPU_V5E.with_fast_capacity(8 * MB)
        assert t.fast_capacity == 8 * MB
        assert t != hw.TPU_V5E              # distinct plan-cache key
        assert hash(t) != hash(hw.TPU_V5E)

    def test_default_target_override(self):
        # no override: auto-detected from the process's JAX devices
        # (cpu_cache on the CPU-only test host)
        detected = hw.detect_target()
        assert hw.default_target() == detected
        try:
            hw.set_default_target("rv32_l1_l2")
            assert hw.default_target().name == "rv32_l1_l2"
        finally:
            hw.set_default_target(None)
        assert hw.default_target() == detected

    def test_assign_homes_spills_big_tensors_deeper(self):
        t = hw.get_target("rv32_l1_l2")
        homes = t.assign_homes({"small": 512 * KB, "big": 9 * MB})
        assert homes["small"].name == "l2"
        assert homes["big"].name == "l3"     # exceeds free L2 -> spill


# ---------------------------------------------------------------------------
# target auto-detection from the JAX device list
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _FakeDev:
    platform: str
    device_kind: str = ""


class TestDetectTarget:
    def test_cpu_host_maps_to_cache_blocked_preset(self):
        assert hw.detect_target([_FakeDev("cpu", "cpu")]) is hw.CPU_CACHE

    def test_tpu_v5e_maps_to_preset(self):
        for kind in ("TPU v5 lite", "TPU v5e"):
            assert hw.detect_target([_FakeDev("tpu", kind)]) is hw.TPU_V5E

    def test_tpu_generations_scale_flops(self):
        v4 = hw.detect_target([_FakeDev("tpu", "TPU v4")])
        v5p = hw.detect_target([_FakeDev("tpu", "TPU v5p")])
        v6 = hw.detect_target([_FakeDev("tpu", "TPU v6 lite")])
        assert v4.name == "tpu_v4" and v4.flops == 275e12
        assert v5p.name == "tpu_v5p" and v5p.flops > v4.flops
        assert v6.name == "tpu_v6e" and v6.flops > v5p.flops
        # all well-formed planning targets (fast + backing, DMA-fed VMEM)
        for t in (v4, v5p, v6):
            assert t.fast.name == "vmem" and t.fast.buffer_depth == 2
            assert len(t.levels) == 3

    def test_unknown_platform_falls_back_to_v5e(self):
        """No fallback any more: a platform without a described hierarchy
        (and an empty device list) raises instead of planning for v5e."""
        with pytest.raises(ValueError, match="platform 'gpu'"):
            hw.detect_target([_FakeDev("gpu", "NVIDIA H100")])
        with pytest.raises(ValueError):
            hw.detect_target([])

    def test_default_target_uses_detection(self, monkeypatch):
        """default_target resolution: set_default_target override, then
        FTL_TARGET, then the (memoized) device detection."""
        detected = hw.detect_target([_FakeDev("tpu", "TPU v4")])
        monkeypatch.setattr(hw, "_RESOLVED", {None: detected})
        monkeypatch.setattr(hw, "_DEFAULT", [None])
        monkeypatch.delenv("FTL_TARGET", raising=False)
        assert hw.default_target().name == "tpu_v4"
        monkeypatch.setenv("FTL_TARGET", "rv32_l1_l2")
        assert hw.default_target().name == "rv32_l1_l2"
        hw.set_default_target("cpu_cache")
        try:
            assert hw.default_target().name == "cpu_cache"
        finally:
            hw.set_default_target(None)

    def test_detection_memoized_once(self, monkeypatch):
        calls = []

        def fake_detect(devices=None):
            calls.append(1)
            return hw.CPU_CACHE

        monkeypatch.setattr(hw, "_RESOLVED", {})
        monkeypatch.setattr(hw, "detect_target", fake_detect)
        monkeypatch.delenv("FTL_TARGET", raising=False)
        hw.default_target()
        hw.default_target()
        assert len(calls) == 1

    def test_env_flip_mid_process_takes_effect(self, monkeypatch):
        """Regression: the resolution memo must be keyed by the env
        state — flipping FTL_TARGET after the first lookup (or clearing
        it back to detection) must change the answer, not be shadowed by
        the first memoized resolution."""
        monkeypatch.setattr(hw, "_RESOLVED", {})
        monkeypatch.setattr(hw, "_DEFAULT", [None])
        monkeypatch.setattr(hw, "detect_target",
                            lambda devices=None: hw.CPU_CACHE)
        monkeypatch.delenv("FTL_TARGET", raising=False)
        assert hw.default_target() is hw.CPU_CACHE   # memoizes detection
        monkeypatch.setenv("FTL_TARGET", "rv32_npu")
        assert hw.default_target().name == "rv32_npu"
        monkeypatch.setenv("FTL_TARGET", "tpu_v5e")
        assert hw.default_target().name == "tpu_v5e"
        monkeypatch.delenv("FTL_TARGET")
        assert hw.default_target() is hw.CPU_CACHE   # back to detection
        # set_default_target clears the memo: a later un-override
        # re-resolves rather than serving the pre-override memo entry
        hw.set_default_target("rv32_l1_l2")
        try:
            assert hw.default_target().name == "rv32_l1_l2"
        finally:
            hw.set_default_target(None)
        assert hw.default_target() is hw.CPU_CACHE


# ---------------------------------------------------------------------------
# engines: per-op-kind compute rates
# ---------------------------------------------------------------------------

class TestEngines:
    def test_rv32_npu_preset(self):
        t = hw.get_target("rv32_npu")
        assert [lv.name for lv in t.levels] == ["l1", "l2", "l3"]
        assert {e.name for e in t.engines} == {"npu", "cluster"}
        assert t.engine_rate("gemm") == ("npu", 128e9)
        assert t.engine_rate("elementwise") == ("cluster", 0.3e9)

    def test_engineless_target_runs_everything_on_core(self):
        assert hw.TPU_V5E.engine_rate("gemm") == ("core", hw.TPU_V5E.flops)
        assert hw.TPU_V5E.compute_time_by_kind({"gemm": 2e12, "x": 1e12}) \
            == hw.TPU_V5E.compute_time_s(3e12)

    def test_engines_overlap_one_engine_serializes(self):
        t = hw.get_target("rv32_npu")
        mix = {"gemm": 128e9, "elementwise": 0.3e9}
        # one second of work per engine: overlapped => 1 s, not 2
        assert t.compute_time_by_kind(mix) == pytest.approx(1.0)
        times = t.engine_times(mix)
        assert times["npu"] == pytest.approx(1.0)
        assert times["cluster"] == pytest.approx(1.0)

    def test_unroutable_kind_raises_without_catch_all(self):
        t = dataclasses.replace(
            hw.RV32_NPU,
            engines=(hw.Engine("npu", (("gemm", 128e9),)),))
        with pytest.raises(ValueError, match="catch-all"):
            t.engine_rate("elementwise")

    def test_engines_survive_derived_targets(self):
        t = hw.RV32_NPU.with_fast_capacity(512 * KB).with_buffer_depth(3)
        assert {e.name for e in t.engines} == {"npu", "cluster"}

    def test_hw_profiles_collapse_onto_engines(self):
        """The benchmark profiles' macs/ew split is the shared Engine
        model now: NPU profiles overlap the two kinds, cluster-only
        profiles serialize them on one engine."""
        from benchmarks import hw_profiles as hp
        npu = hp.SIRACUSA_NPU.target()
        clu = hp.SIRACUSA_CLUSTER.target()
        mix = {"gemm": 2.0 * 64e9, "elementwise": 0.3e9}   # 1 s each
        assert npu.compute_time_by_kind(mix) == pytest.approx(1.0)
        mix_c = {"gemm": 2.0 * 3e9, "elementwise": 0.3e9}
        assert clu.compute_time_by_kind(mix_c) == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# property: solved traffic monotone non-increasing in fast capacity
# ---------------------------------------------------------------------------

BUDGET_LADDER = (1 * MB, 2 * MB, 8 * MB, 32 * MB, 96 * MB)


def _monotone_check(m, k, n, lo, hi):
    g = lambda: ftl.fusion.mlp(m=m, d_model=k, d_ff=n, fuse=True)  # noqa
    try:
        t_lo = ftl.solve(g(), target=_flat(lo)).traffic_bytes
    except InfeasibleError:
        return
    t_hi = ftl.solve(g(), target=_flat(hi)).traffic_bytes
    assert t_hi <= t_lo


@pytest.mark.parametrize("m,k,n", [(512, 256, 1024), (3072, 768, 3072),
                                   (2048, 2048, 2048)])
def test_traffic_monotone_in_fast_capacity(m, k, n):
    """Growing the fast level never increases the solved traffic: the
    feasible tile set only grows with capacity and the (zero-setup)
    objective is traffic-proportional.  Deterministic ladder sweep; the
    hypothesis variant below fuzzes shapes when hypothesis is installed."""
    for lo, hi in zip(BUDGET_LADDER, BUDGET_LADDER[1:]):
        _monotone_check(m, k, n, lo, hi)


try:
    import hypothesis.strategies as st
    from hypothesis import given, settings

    dim = st.sampled_from([256, 512, 768, 1024, 2048])
    budget = st.sampled_from(BUDGET_LADDER)

    @settings(max_examples=30, deadline=None)
    @given(m=dim, k=dim, n=dim, b1=budget, b2=budget)
    def test_traffic_monotone_in_fast_capacity_fuzz(m, k, n, b1, b2):
        _monotone_check(m, k, n, min(b1, b2), max(b1, b2))
except ImportError:  # pragma: no cover - hypothesis optional locally
    pass


# ---------------------------------------------------------------------------
# every preset plans the zoo configs test_block_exec executes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch",
                         ["llama3.2-3b", "granite-20b", "recurrentgemma-9b"])
@pytest.mark.parametrize("target", list(hw.presets()),
                         ids=lambda t: t.name)
def test_presets_feasible_on_zoo_configs(arch, target):
    cfg = dataclasses.replace(configs.get_config(arch).reduced(),
                              dtype="float32", remat=False, ftl_mode="auto")
    bp = registry.plan_block(cfg, m=32, dtype="float32", target=target)
    assert bp.target == target
    assert bp.traffic_bytes > 0
    # per-level accounting covers the whole boundary traffic
    assert sum(bp.per_level_traffic.values()) == bp.traffic_bytes


# ---------------------------------------------------------------------------
# the paper's qualitative result on the Siracusa-like hierarchy
# ---------------------------------------------------------------------------

def test_rv32_fused_moves_less_backing_traffic_than_unfused():
    """ViT-MLP GEMM→GeLU (the paper's Fig. 3 op, int8) on rv32_l1_l2:
    the fused segment must move fewer backing-store bytes than the
    layer-per-layer schedule — the paper's core claim."""
    t = hw.get_target("rv32_l1_l2")
    g = graph.gemm_act_graph(m=3072, k=768, n=3072, dtype="int8")
    fused = partition.plan_fixed(g, (), target=t)
    unfused = partition.plan_fixed(g, partition.all_cuts(g), target=t)
    assert fused.traffic_bytes < unfused.traffic_bytes
    assert fused.transfer_time_s < unfused.transfer_time_s
    # and the DP agrees fusion is the right schedule on this machine
    assert partition.plan_chain(g, target=t).schedule == "fused"


def test_full_mlp_segment_on_rv32_beats_unfused_when_feasible():
    """The whole (reduced-size) MLP chain on the 256 KiB L1: whatever the
    DP picks must not exceed the unfused schedule's backing traffic."""
    t = hw.get_target("rv32_l1_l2")
    g = graph.mlp_graph(m=512, d_model=256, d_ff=1024, dtype="int8")
    chain = partition.plan_chain(g, target=t)
    unfused = partition.plan_fixed(g, partition.all_cuts(g), target=t)
    assert chain.traffic_bytes <= unfused.traffic_bytes


# ---------------------------------------------------------------------------
# regression: the block plan cache is keyed by target
# ---------------------------------------------------------------------------

def test_model_block_plan_cache_keys_target():
    """Changing the planning target (default or explicit) must never serve
    a stale cached plan made for a different hierarchy."""
    from repro.models import model as M
    cfg = dataclasses.replace(configs.get_config("llama3.2-3b").reduced(),
                              dtype="float32", remat=False, ftl_mode="auto")
    plan_default = M.plan_or_none(cfg, m=32, dtype="float32")
    assert plan_default is not None
    assert plan_default.target == hw.default_target()
    # explicit target: distinct plan object for a distinct machine
    plan_rv32 = M.plan_or_none(cfg, m=32, dtype="float32",
                               target=hw.get_target("rv32_l1_l2"))
    assert plan_rv32 is not None
    assert plan_rv32.target.name == "rv32_l1_l2"
    assert plan_rv32 is not plan_default
    # default-target switch reaches the cache key too
    try:
        hw.set_default_target("rv32_l1_l2")
        plan_switched = M.plan_or_none(cfg, m=32, dtype="float32")
        assert plan_switched is not None
        assert plan_switched.target.name == "rv32_l1_l2"
        assert plan_switched is not plan_default
    finally:
        hw.set_default_target(None)
    # and with the default restored, the original plan is served again
    assert M.plan_or_none(cfg, m=32, dtype="float32") is plan_default


# ---------------------------------------------------------------------------
# target-aware executor qualification
# ---------------------------------------------------------------------------

class TestTargetQualification:
    def test_pallas_requires_vmem_class_target(self):
        """A plan made for a KiB-scale scratchpad must not bind the Pallas
        kernels even on a TPU host — its tiles assume another machine.
        (Shape-less contexts fall back to the capacity-class check.)"""
        ctx = registry.ExecContext(kind="mlp", platform="tpu",
                                   schedule="fused",
                                   target=hw.get_target("rv32_l1_l2"))
        assert registry.find("mlp", ctx).name == "xla_scan_mlp"
        ctx = registry.ExecContext(kind="mlp", platform="tpu",
                                   schedule="fused", target=hw.TPU_V5E)
        assert registry.find("mlp", ctx).name == "pallas_fused_mlp"

    def test_pallas_mlp_qualifies_by_kernel_tile_footprint(self):
        """With shapes in the context, qualification solves the kernel's
        own dataflow (K/N whole) on the plan target: a weight panel that
        cannot fit the fast level disqualifies the kernel even on a
        VMEM-class target — where the old capacity-class check would
        have happily bound it."""
        small_vmem = hw.TPU_V5E.with_fast_capacity(8 * MB)
        assert registry._vmem_class(small_vmem)       # old check: fine
        big = registry.ExecContext(
            kind="mlp", platform="tpu", schedule="fused",
            m=8192, d_model=8192, d_ff=32768, dtype="bfloat16",
            target=small_vmem)
        # w1 alone is 8192*32768*2 B = 512 MiB >> 8 MiB: must fall back
        assert registry.find("mlp", big).name == "xla_scan_mlp"
        ok = registry.ExecContext(
            kind="mlp", platform="tpu", schedule="fused",
            m=4096, d_model=256, d_ff=1024, dtype="bfloat16",
            target=small_vmem)
        assert registry.find("mlp", ok).name == "pallas_fused_mlp"
        # and the rv32 scratchpad fails the footprint probe with shapes
        rv = registry.ExecContext(
            kind="mlp", platform="tpu", schedule="fused",
            m=4096, d_model=768, d_ff=3072, dtype="bfloat16",
            target=hw.get_target("rv32_l1_l2"))
        assert registry.find("mlp", rv).name == "xla_scan_mlp"

    def test_partial_mlp_probes_per_gemm_footprint(self):
        """The partial Pallas path runs its two GEMM kernels
        sequentially, one weight panel each: shapes whose *fused*
        whole-K/N solve cannot fit must still qualify the partial
        executor when each GEMM alone is plannable."""
        small_vmem = hw.TPU_V5E.with_fast_capacity(8 * MB)
        ctx = registry.ExecContext(
            kind="mlp", platform="tpu", schedule="partial",
            m=4096, d_model=16384, d_ff=16384, dtype="bfloat16",
            target=small_vmem)
        # fused probe fails (whole-K weight columns alone overflow)...
        assert not registry._mlp_kernel_footprint_fits(
            4096, 16384, 16384, "bfloat16", False, "gelu", small_vmem)
        # ...but the per-GEMM partial probe qualifies the kernel
        assert registry.find("mlp", ctx).name == "pallas_partial_mlp"

    def test_kernel_block_planning_survives_cpu_default_target(self):
        """ops.plan_*_blocks with target=None must not solve against the
        auto-detected cpu_cache default (whose 1 MiB fast level cannot
        hold the kernels' weight panels): a non-VMEM-class process
        default falls back to the TPU preset."""
        from repro.kernels import ops
        assert ops._kernel_target(None).fast.capacity_bytes >= 4 * MB
        try:
            hw.set_default_target("cpu_cache")
            assert ops._kernel_target(None) is hw.TPU_V5E
            assert ops.plan_mlp_blocks(
                4096, 768, 3072, "bfloat16", False, "gelu") == \
                ops.plan_mlp_blocks(4096, 768, 3072, "bfloat16", False,
                                    "gelu", target=hw.TPU_V5E)
            hw.set_default_target("rv32_npu")
            assert ops._kernel_target(None) is hw.TPU_V5E
            hw.set_default_target("tpu_v5e")
            assert ops._kernel_target(None) is hw.TPU_V5E
        finally:
            hw.set_default_target(None)

    def test_pallas_attention_qualifies_by_kernel_tile_footprint(self):
        rv = registry.ExecContext(
            kind="attention", platform="tpu", schedule="fused",
            m=4096, head_dim=128, dtype="bfloat16",
            target=hw.get_target("rv32_l1_l2"))
        assert registry.find("attention", rv).name == "xla_ref_attention"
        tpu = registry.ExecContext(
            kind="attention", platform="tpu", schedule="fused",
            m=4096, head_dim=128, dtype="bfloat16", target=hw.TPU_V5E)
        assert registry.find("attention", tpu).name == \
            "pallas_flash_attention"

    def test_plan_block_context_carries_head_dim(self):
        cfg = dataclasses.replace(configs.get_config("llama3.2-3b")
                                  .reduced(),
                                  dtype="float32", remat=False,
                                  ftl_mode="auto")
        plan = registry.plan_block(cfg, m=32, dtype="float32",
                                   target=hw.TPU_V5E)
        # requalification context exposes the head dim for the probe
        from repro.core.ftl import executor_block as eb
        ctx = eb._runtime_ctx(plan, "attention", "fused", 32, "float32")
        assert ctx.head_dim == cfg.resolved_head_dim

    def test_run_block_executors_bound_to_plan_target(self):
        """Every resolved stage executor must run pinned to the plan's own
        target — the Pallas kernels' block-size planning and the scan
        executors' token tile would otherwise silently re-plan against
        whatever the process default is at run time."""
        from repro.core.ftl import executor_block as eb
        cfg = dataclasses.replace(
            configs.get_config("llama3.2-3b").reduced(),
            dtype="float32", remat=False, ftl_mode="auto")
        plan = registry.plan_block(cfg, m=32, dtype="float32",
                                   target=hw.get_target("rv32_l1_l2"))
        for resolver in (eb._resolve_gemm, eb._resolve_attention,
                         eb._resolve_mlp):
            ex = resolver(plan, "auto", 32, "float32")
            assert ex.run.keywords["target"] == plan.target, resolver

    def test_with_fast_capacity_drops_outgrown_backing_levels(self):
        t = hw.get_target("rv32_l1_l2").with_fast_capacity(8 * MB)
        # the 2 MiB L2 cannot back an 8 MiB scratchpad: dropped, spill
        # reprices at L3; the deepest level is always kept
        assert [lv.name for lv in t.levels] == ["l1", "l3"]

    def test_roofline_hw_derives_from_same_target(self):
        from repro.roofline.analysis import DEFAULT_HW, HW
        rebuilt = HW.from_target(hw.TPU_V5E)
        assert rebuilt == DEFAULT_HW
        assert rebuilt.vmem_bytes == hw.TPU_V5E.fast_capacity
        assert rebuilt.hbm_bw == hw.TPU_V5E.levels[1].bw_bytes_per_s
        assert rebuilt.peak_flops == hw.TPU_V5E.flops
        assert rebuilt.target_name == "tpu_v5e"
