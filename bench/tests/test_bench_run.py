"""A whole run of the harness on the CPU at a tiny size, with the look
for a chip skipped: it serves through ServeEngine.run, reports its
metrics, and the correctness check passes on the sound program, fails
when a served token is altered where it is produced, and fails on the
float8 control."""
import functools

import jax
import jax.numpy as jnp
import pytest

from bench import correctness, run, served
from bench.tests import tiny


@pytest.fixture
def root(tmp_path):
    # a test writes nothing to the checkout's compile cache
    keep = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    jax.config.update("jax_enable_compilation_cache", False)
    yield tiny.make_root(tmp_path)
    for k, v in keep.items():
        jax.config.update(k, v)


def _run(root, seed=2**31 + 11, trace=False):
    return run.run_cell("tiny.open", seed, 2.0, trace, root=root,
                        check_device=tiny.cpu_device)


def test_sound_run_is_correct_and_reports_its_metrics(root):
    result, info, checks = _run(root)
    assert result["correct"] is True
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"ttft_p90_ms", "itl_p95_ms",
                                      "itl_p99_ms", "out_tok_s", "setup_s"}
    assert result["attempted"] == info["due"] == info["requests"] > 20
    assert info["compiles_in_window"] == 0
    # fewer tokens finished than the sample asks for: it takes them all
    assert info["check"]["requests"] == min(info["finished"],
                                            correctness.MAX_SAMPLE)
    assert checks["max_logit_gap"]["value"] <= tiny.CELL["limits"][
        "max_logit_gap"]
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
    build = served.build_engine

    def broken(*a, **k):
        eng = build(*a, **k)
        if where == "decode":
            eng.decode_fn = _roll(eng.decode_fn)
        else:
            get = eng.prefill_fn
            eng.prefill_fn = lambda b, plan: _roll(get(b, plan))
        return eng

    monkeypatch.setattr(served, "build_engine", broken)
    result, _, checks = _run(root)
    assert result["correct"] is False
    assert checks["max_logit_gap"]["value"] > checks["max_logit_gap"][
        "limit"]


def test_the_float8_control_is_not_correct(root, monkeypatch):
    """The reference computed in float8 in the program's place: at the
    same positions, the token it puts first lies further below the
    float32 reference's best than the limit allows."""
    gaps = correctness.gaps
    monkeypatch.setattr(correctness, "gaps",
                        functools.partial(gaps, control=True))
    _, info, checks = _run(root, seed=5)
    limit = checks["max_logit_gap"]["limit"]
    assert info["check"]["max_logit_gap"] <= limit
    assert info["check"]["control_max_logit_gap"] > limit


def test_a_window_no_request_outlives_serves_on_until_one_finishes(
        root, monkeypatch):
    """A batch whose outputs outlast the window: the engine serves on,
    unmeasured, until a request finishes, and the tokens after the
    window's end count in no metric."""
    from bench import timing
    from bench.tests import tiny as t

    monkeypatch.setattr(timing, "DRAIN_STEP_S", 0.5)
    cell = dict(t.CELL, rate_per_s=None, slots=2, sample_tokens=8)
    traffic = dict(t.TRAFFIC, arrivals="batch", steps_per_s_bound=50,
                   output_len={"dist": "uniform", "min": 100, "max": 110},
                   prompt_len={"dist": "uniform", "min": 8, "max": 16},
                   max_seq=128)
    root = tiny.make_root(root.parent / "batch", traffic=traffic, cell=cell)
    result, info, _ = run.run_cell("tiny.open", 3, 0.05, False, root=root,
                                   check_device=tiny.cpu_device)
    assert info["finished_in_window"] == 0 and info["finished"] >= 1
    assert info["drained_s"] > 0
    assert result["correct"] is True
    assert 0 < info["tokens_in_window"] < info["check"]["tokens"]
