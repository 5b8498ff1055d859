"""granite's layout through the harness on the CPU, at a tiny size: MQA
(one KV head under four query heads), an un-gated tanh-GELU MLP and
attention with biases, LayerNorm.  Prefill, then paged decode through
the engine's own page length (16 positions for one bf16 KV head),
checked against the float32 reference; and the paged-attention
roofline reader on a hand-made trace and counters."""
import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from bench import flops, lookup, paged, run, served
from bench.tests import tiny
from bench.tests.test_bench_flops import SPECS

CONFIG = dict(tiny.CONFIG, name="tiny-mqa",
              source="a CPU test size of the granite-20b layout",
              num_key_value_heads=1, hidden_act="gelu_pytorch_tanh",
              mlp_gated=False, attention_bias=True, mlp_bias=True,
              norm="layernorm")
# prompts of 9 or more: the harness's ladder of 8-position rungs then
# reaches none the engine's 16-position pages cannot hold
TRAFFIC = dict(tiny.TRAFFIC, prompt_len=dict(tiny.TRAFFIC["prompt_len"],
                                             min=9))


@pytest.fixture
def root(tmp_path):
    # a test writes nothing to the checkout's compile cache
    keep = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    jax.config.update("jax_enable_compilation_cache", False)
    yield tiny.make_root(tmp_path, config=CONFIG, traffic=TRAFFIC)
    for k, v in keep.items():
        jax.config.update(k, v)


def _run(root, seed=2**31 + 15):
    return run.run_cell("tiny.open", seed, 2.0, False, root=root,
                        check_device=tiny.cpu_device)


def _engines(monkeypatch, alter=None):
    """The engines the harness builds, each passed through ``alter``."""
    built = []
    build = served.build_engine

    def keep(*a, **k):
        eng = build(*a, **k)
        if alter is not None:
            alter(eng)
        built.append(eng)
        return eng

    monkeypatch.setattr(served, "build_engine", keep)
    return built


def test_mqa_gelu_layernorm_serves_correctly_through_16_position_pages(
        root, monkeypatch):
    built = _engines(monkeypatch)
    result, info, checks = _run(root)
    (eng,) = built
    assert eng.paged and eng.block_size == 16
    assert eng.cfg.n_kv_heads == 1 and eng.cfg.mlp_act == "gelu"
    assert result["correct"] is True
    assert checks["max_logit_gap"]["value"] <= tiny.CELL["limits"][
        "max_logit_gap"]
    assert info["finished"] > 0 and info["compiles_in_window"] == 0
    assert info["engine_stats"]["after"]["decode_steps"] > 0
    assert any(k.startswith("prefill/") for k in info["executors"])


def _roll(fn):
    """``fn`` with the logits it returns shifted one id along the
    vocabulary: every token it produces is altered."""
    @functools.wraps(fn)
    def wrapped(*a, **k):
        logits, rest = fn(*a, **k)
        return jnp.roll(logits, 1, axis=-1), rest
    return wrapped


@pytest.mark.parametrize("where", ["prefill", "decode"])
def test_an_altered_token_is_not_correct(root, monkeypatch, where):
    def alter(eng):
        if where == "decode":
            eng.decode_fn = _roll(eng.decode_fn)
        else:
            get = eng.prefill_fn
            eng.prefill_fn = lambda b, plan: _roll(get(b, plan))

    _engines(monkeypatch, alter)
    result, _, checks = _run(root)
    assert result["correct"] is False
    assert checks["max_logit_gap"]["value"] > checks["max_logit_gap"][
        "limit"]


# ---------------------------------------------------------------------------
# the paged-attention kernel's roofline share
# ---------------------------------------------------------------------------

MQA = SPECS[1]
PEAK = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}


def test_paged_counts_by_hand():
    # 2 layers, 4 query heads over 1 KV head of 16
    f, b = paged.decode_step(MQA, positions=48, rows=3)
    assert f == 2 * 4 * 4 * 16 * 48
    assert b == 48 * (2 * 2 * 16 * 2) + 2 * 3 * 4 * (16 * 6 + 8)


def _span(name, t0, t1):
    return SimpleNamespace(name=name, t0=t0, t1=t1, duration_s=t1 - t0)


def _ctx(ops, programs=3):
    # request a (prompt 100) decodes in the steps at 1.4 and 1.6, b
    # (prompt 300) in the one at 2.5; the kernel holds 0.006 s over the
    # three decode programs
    served = [SimpleNamespace(prompt=[0] * 100, stamps=[1.25, 1.45, 1.65]),
              SimpleNamespace(prompt=[0] * 300, stamps=[2.35, 2.55])]
    spans = [_span("serve:decode_step", t, t + 0.1) for t in (1.4, 1.6,
                                                               2.5)]
    return {"spec": MQA, "peak": PEAK, "served": served, "spans": spans,
            "traced": (0.5, 3.0),
            "trace": {"device_ops": ops,
                      "programs": {"jit_paged_step": {"s": 0.3,
                                                      "n": programs}}}}


def _counters(monkeypatch, **stats):
    from repro import obs

    snap = {"serve_stats": {"samples": [({"stat": k}, v)
                                        for k, v in stats.items()]}}
    monkeypatch.setattr(obs, "collect", lambda: snap)


def _read(ctx):
    return lookup.metric_reader("paged_attention_roofline")(ctx)


KERNEL_OPS = [["jit_paged_step:fusion.3", 0.2],
              ["jit_paged_step:paged_attention.6", 0.006],
              ["jit_step_fn:gemm.1", 0.1]]


def test_roofline_is_least_time_of_the_cached_positions_over_kernel_time(
        monkeypatch):
    _counters(monkeypatch, decode_steps=10, paged_kernel_steps=10)
    # each step read one row's cached positions: 100, 101 and 300
    least = [flops.least_time_s(*paged.decode_step(MQA, c, 1), PEAK)
             for c in (100, 101, 300)]
    assert {bound for _, bound in least} == {"bandwidth"}
    assert _read(_ctx(KERNEL_OPS)) == pytest.approx(
        100.0 * (sum(t for t, _ in least) / 3) / (0.006 / 3))


@pytest.mark.parametrize("stats,ops,programs", [
    # a program with no such counters (the gather-only engine)
    ({"decode_steps": 10}, KERNEL_OPS, 3),
    # decode steps that took the jnp gather
    ({"decode_steps": 10, "paged_kernel_steps": 0}, KERNEL_OPS, 3),
    ({"decode_steps": 10, "paged_kernel_steps": 9}, KERNEL_OPS, 3),
    # the kernel fell out of the trace's listed operations
    ({"decode_steps": 10, "paged_kernel_steps": 10}, KERNEL_OPS[:1], 3),
    # the trace's decode programs and the stretch's steps disagree
    ({"decode_steps": 10, "paged_kernel_steps": 10}, KERNEL_OPS, 6),
])
def test_roofline_reads_nothing_without_counters_or_a_kernel(
        monkeypatch, stats, ops, programs):
    _counters(monkeypatch, **stats)
    assert _read(_ctx(ops, programs)) is None


def test_roofline_reads_nothing_without_a_trace(monkeypatch):
    _counters(monkeypatch, decode_steps=10, paged_kernel_steps=10)
    ctx = _ctx(KERNEL_OPS)
    ctx["trace"] = None
    assert _read(ctx) is None
