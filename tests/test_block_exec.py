"""BlockPlan-driven execution (registry.run_block) vs the layer-per-layer
reference path: numerical equivalence in fp32 on CPU across gated/ungated
MLPs, causal/non-causal attention and multiple zoo configs; runtime
requalification fallback; the enriched registry.find diagnostics; and the
bench_block artifact shape."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.ftl import executor_block, registry
from repro.models import layers
from repro.models import model as M

TOL = dict(rtol=2e-5, atol=2e-5)


def _fp32(arch, **over):
    return dataclasses.replace(configs.get_config(arch).reduced(),
                               dtype="float32", remat=False, **over)


def _layer_params(cfg, seed=0):
    # the single-block param builder lives with the benchmark so the
    # equivalence tests exercise exactly the params the bench times
    bench_block = pytest.importorskip("benchmarks.bench_block")
    return bench_block._layer_params(cfg, jax.random.PRNGKey(seed))


def _x(cfg, m=32, b=2, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (b, m, cfg.d_model), jnp.float32)


# ---------------------------------------------------------------------------
# numerical equivalence: plan-driven == layer-per-layer
# ---------------------------------------------------------------------------

class TestRunBlockEquivalence:
    # two zoo configs with opposite MLP/norm conventions: llama3.2-3b is
    # gated-silu/rmsnorm/no-bias, granite-20b is plain-gelu/layernorm
    # with qkv+mlp biases
    @pytest.mark.parametrize("arch", ["llama3.2-3b", "granite-20b"])
    @pytest.mark.parametrize("gated", [False, True])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, arch, gated, causal):
        cfg = _fp32(arch, mlp_gated=gated)
        p = _layer_params(cfg)
        x = _x(cfg)
        pos = jnp.arange(x.shape[1])
        plan = registry.plan_block(
            dataclasses.replace(cfg, ftl_mode="auto"),
            m=x.shape[1], dtype="float32")
        y_plan = registry.run_block(plan, p, x, positions=pos,
                                    causal=causal)
        y_ref = layers.block_layer(cfg, p, x, positions=pos, plan=None,
                                   causal=causal)
        np.testing.assert_allclose(y_plan, y_ref, **TOL)

    def test_matches_under_jit(self):
        cfg = _fp32("llama3.2-3b")
        p = _layer_params(cfg)
        x = _x(cfg)
        pos = jnp.arange(x.shape[1])
        plan = registry.plan_block(
            dataclasses.replace(cfg, ftl_mode="auto"),
            m=x.shape[1], dtype="float32")
        y_jit = jax.jit(
            lambda xx: registry.run_block(plan, p, xx, positions=pos))(x)
        y_ref = layers.block_layer(cfg, p, x, positions=pos, plan=None)
        np.testing.assert_allclose(y_jit, y_ref, **TOL)

    def test_stale_tpu_bindings_fall_back_per_segment(self):
        """A plan whose bindings were made on TPU must requalify at run
        time and fall back to the XLA executors segment by segment."""
        cfg = _fp32("llama3.2-3b", ftl_mode="auto")
        p = _layer_params(cfg)
        x = _x(cfg)
        pos = jnp.arange(x.shape[1])
        plan = registry.plan_block(cfg, m=x.shape[1], dtype="float32")
        pallas = {"gemm": "pallas_gemm",
                  "attention": "pallas_flash_attention",
                  "mlp": "pallas_fused_mlp"}
        stale = dataclasses.replace(
            plan,
            platform="tpu",
            bindings=tuple(dataclasses.replace(b, executor=pallas[b.kind])
                           for b in plan.bindings))
        y = registry.run_block(stale, p, x, positions=pos)
        y_ref = layers.block_layer(
            dataclasses.replace(cfg, ftl_mode="off"), p, x,
            positions=pos, plan=None)
        np.testing.assert_allclose(y, y_ref, **TOL)
        execs = executor_block.resolved_executors(stale, dtype="float32")
        assert all(not name.startswith("pallas") for name in execs.values())

    def test_ftl_mode_off_pins_baseline_executors(self):
        """ftl_mode='off' is the full escape hatch: even with (stale)
        Pallas bindings in the plan, every stage runs the baseline
        executors and the output matches the hand-sequenced path."""
        cfg = _fp32("llama3.2-3b", ftl_mode="off")
        p = _layer_params(cfg)
        x = _x(cfg)
        pos = jnp.arange(x.shape[1])
        plan = registry.plan_block(cfg, m=x.shape[1], dtype="float32")
        pallas = {"gemm": "pallas_gemm",
                  "attention": "pallas_flash_attention",
                  "mlp": "pallas_fused_mlp"}
        stale = dataclasses.replace(
            plan,
            platform="tpu",
            bindings=tuple(dataclasses.replace(b, executor=pallas[b.kind])
                           for b in plan.bindings))
        y = registry.run_block(stale, p, x, positions=pos)
        y_ref = layers.block_layer(cfg, p, x, positions=pos, plan=None)
        np.testing.assert_allclose(y, y_ref, **TOL)

    def test_mlp_only_plan_runs_local_attention_fallback(self):
        """Hybrid config: the plannable block is MLP-only (leading 'rec'
        kind); run_block must still execute the local-attention stage via
        the runtime-fallback executor, matching the reference."""
        cfg = _fp32("recurrentgemma-9b", ftl_mode="auto")
        p = _layer_params(cfg)
        x = _x(cfg, m=64)
        pos = jnp.arange(64)
        plan = registry.plan_block(cfg, m=64, dtype="float32")
        assert plan.attention_schedule == "none"
        y = registry.run_block(plan, p, x, positions=pos,
                               window=cfg.local_window)
        y_ref = layers.block_layer(cfg, p, x, positions=pos, plan=None,
                                   window=cfg.local_window)
        np.testing.assert_allclose(y, y_ref, **TOL)


# ---------------------------------------------------------------------------
# forward: the plan path is the execution authority, and it matches the
# hand-sequenced path end to end
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["llama3.2-3b", "recurrentgemma-9b"])
def test_forward_plan_vs_handsequenced(arch, monkeypatch):
    cfg = _fp32(arch, ftl_mode="auto")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.arange(2 * 16).reshape(2, 16) % cfg.vocab_size}
    assert M.plan_or_none(cfg, m=16, dtype=cfg.dtype) is not None
    y_plan, _ = M.forward(cfg, params, batch)
    monkeypatch.setattr(M, "plan_or_none", lambda *a, **k: None)
    y_ref, _ = M.forward(cfg, params, batch)
    np.testing.assert_allclose(y_plan, y_ref, **TOL)


def test_forward_skips_planning_when_ftl_off(monkeypatch):
    """ftl_mode='off' is the zero-cost escape hatch: no plan is built
    (no trace-time solver work) and the hand-sequenced path runs."""
    cfg = _fp32("llama3.2-3b")
    assert cfg.ftl_mode == "off"

    def no_planning(*a, **k):
        raise AssertionError("forward planned under ftl_mode='off'")

    monkeypatch.setattr(registry, "plan_block", no_planning)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.arange(2 * 16).reshape(2, 16) % cfg.vocab_size}
    y, _ = M.forward(cfg, params, batch)
    assert np.isfinite(np.asarray(y)).all()


@pytest.mark.parametrize("ftl_mode", ["off", "auto"])
def test_forward_asks_for_a_plan_only_when_planning_is_on(ftl_mode,
                                                          monkeypatch):
    """The ftl_mode gate sits at forward's call site: under 'off' it
    asks for no plan, under 'auto' for one, at m = the sequence
    length."""
    cfg = _fp32("llama3.2-3b", ftl_mode=ftl_mode)
    asked = []
    plan_or_none = M.plan_or_none

    def spy(c, **kw):
        asked.append(kw)
        return plan_or_none(c, **kw)

    monkeypatch.setattr(M, "plan_or_none", spy)
    params = jax.eval_shape(M.init_params, cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 24), jnp.int32)}
    jax.eval_shape(lambda p, b: M.forward(cfg, p, b), params, batch)
    want = [] if ftl_mode == "off" else [24]
    assert [kw["m"] for kw in asked] == want


def test_serve_engine_executes_block_plan():
    from repro.launch.serve import ServeEngine
    cfg = _fp32("llama3.2-3b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, batch_slots=1, max_seq=32)
    entry = eng.execute_block_plan()
    assert entry is not None
    assert entry["finite"]
    assert entry["ms"] > 0
    assert set(entry["executors"]) == {"gemm", "attention", "mlp"}
    # default ftl_mode='off' must report the baseline executors it ran,
    # not the plan's bindings
    assert entry["executors"]["mlp"] == "xla_unfused_mlp"
    assert eng.stats["block_exec"] is entry


def test_serve_engine_executes_block_plan_hybrid():
    """Hybrid configs (leading 'rec' positions) still execute their
    stored plan through the first local-attention layer."""
    from repro.launch.serve import ServeEngine
    cfg = _fp32("recurrentgemma-9b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, batch_slots=1, max_seq=32)
    entry = eng.execute_block_plan()
    assert entry is not None
    assert entry["finite"]


# ---------------------------------------------------------------------------
# registry.find diagnostics (satellite fix)
# ---------------------------------------------------------------------------

class TestFindDiagnostics:
    def test_unknown_kind_message_carries_context(self):
        ctx = registry.ExecContext(kind="conv", platform="cpu",
                                   schedule="fused", m=128)
        with pytest.raises(LookupError) as ei:
            registry.find("conv", ctx)
        msg = str(ei.value)
        assert "kind='conv'" in msg
        assert "platform='cpu'" in msg
        assert "schedule='fused'" in msg
        assert "m=128" in msg
        assert "none registered" in msg

    def test_message_lists_considered_executors(self):
        ex = registry.Executor(name="never_qualifies_test", kind="testkind",
                               backend="xla", priority=7,
                               qualifies=lambda c: False)
        registry.register(ex)
        try:
            ctx = registry.ExecContext(kind="testkind", platform="cpu",
                                       schedule="fused")
            with pytest.raises(LookupError) as ei:
                registry.find("testkind", ctx)
            assert "never_qualifies_test (backend=xla, priority=7)" in \
                str(ei.value)
        finally:
            del registry._REGISTRY["never_qualifies_test"]


# ---------------------------------------------------------------------------
# bench_block artifact (consumed by the CI bench-smoke job)
# ---------------------------------------------------------------------------

def test_bench_block_writes_wellformed_json(tmp_path, monkeypatch):
    bench_block = pytest.importorskip("benchmarks.bench_block")
    # knob overrides resolve at call time (None = BENCH_SMOKE default)
    monkeypatch.setattr(bench_block, "ARCHS", ("llama3.2-3b",))
    monkeypatch.setattr(bench_block, "EXEC_TOKENS", (32,))
    monkeypatch.setattr(bench_block, "MODEL_TOKENS", 128)
    monkeypatch.setattr(bench_block, "ITERS", 1)
    monkeypatch.chdir(tmp_path)
    bench_block.main()
    data = json.loads((tmp_path / "BENCH_block.json").read_text())
    assert data["measured"] and data["modeled_traffic"]
    for row in data["measured"]:
        assert {"arch", "m", "schedule", "executors", "ref_ms",
                "plan_ms"} <= set(row)
        assert row["ref_ms"] > 0 and row["plan_ms"] > 0
    for row in data["modeled_traffic"]:
        assert {"arch", "m", "schedule", "plan_MiB"} <= set(row)
