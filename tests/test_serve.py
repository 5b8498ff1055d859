"""Serving-engine integration tests: continuous batching, cache splicing,
greedy parity with the raw model loop."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch.serve import Request, ServeEngine
from repro.models import model as M


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(configs.get_config("llama3.2-3b").reduced(),
                              remat=False)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def greedy_reference(cfg, params, prompt, n_new):
    """Raw prefill+decode greedy loop (no engine)."""
    toks = jnp.asarray(prompt)[None]
    logits, cache = M.prefill(cfg, params, {"tokens": toks},
                              max_seq=len(prompt) + n_new + 1)
    out = [int(jnp.argmax(logits[0, -1]))]
    pos = len(prompt)
    for _ in range(n_new - 1):
        tok = jnp.asarray([[out[-1]]], jnp.int32)
        logits, cache = M.decode_step(cfg, params, tok, cache,
                                      jnp.int32(pos))
        out.append(int(jnp.argmax(logits[0, 0])))
        pos += 1
    return out


def test_engine_single_request_matches_reference(setup):
    cfg, params = setup
    rng = np.random.default_rng(0)
    prompt = rng.integers(2, cfg.vocab_size, size=8).astype(np.int32)
    n_new = 6
    ref = greedy_reference(cfg, params, prompt, n_new)

    eng = ServeEngine(cfg, params, batch_slots=1, max_seq=32,
                      eos_id=-1)  # never EOS
    done = eng.run([Request(0, prompt, n_new)], {})
    assert done[0].out[:n_new] == ref


def test_engine_serves_more_requests_than_slots(setup):
    cfg, params = setup
    rng = np.random.default_rng(1)
    reqs = [Request(i, rng.integers(2, cfg.vocab_size, size=6)
                    .astype(np.int32), 4) for i in range(5)]
    eng = ServeEngine(cfg, params, batch_slots=2, max_seq=32, eos_id=-1)
    done = eng.run(list(reqs), {})
    assert sorted(r.rid for r in done) == [0, 1, 2, 3, 4]
    assert all(len(r.out) == 4 for r in done)
    assert eng.stats["prefills"] == 5


def test_engine_batched_equals_single(setup):
    """Tokens produced with 2 concurrent slots == served alone."""
    cfg, params = setup
    rng = np.random.default_rng(2)
    prompts = [rng.integers(2, cfg.vocab_size, size=8).astype(np.int32)
               for _ in range(2)]

    solo = []
    for i, pr in enumerate(prompts):
        eng = ServeEngine(cfg, params, batch_slots=1, max_seq=32, eos_id=-1)
        solo.append(eng.run([Request(i, pr, 4)], {})[0].out)

    eng = ServeEngine(cfg, params, batch_slots=2, max_seq=32, eos_id=-1)
    done = eng.run([Request(i, pr, 4) for i, pr in enumerate(prompts)], {})
    by_rid = {r.rid: r.out for r in done}
    assert by_rid[0] == solo[0]
    assert by_rid[1] == solo[1]


# ---------------------------------------------------------------------------
# continuous batching on planned schedules: mixed lengths, paged KV,
# bucket ladder, split prefill/decode plans
# ---------------------------------------------------------------------------

from repro.core import hw                                   # noqa: E402
from repro.core.ftl import registry as ftl_registry          # noqa: E402
from repro.launch import kv_cache as KV                      # noqa: E402
from repro.launch.serve import poisson_arrivals              # noqa: E402


def test_engine_mixed_lengths_match_reference(setup):
    """Two slots at different positions (5- and 11-token prompts) decode
    together; each must match its solo no-engine greedy loop — the
    per-slot position vector plus bucket padding at work."""
    cfg, params = setup
    rng = np.random.default_rng(3)
    prompts = [rng.integers(2, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 11)]
    n_new = 5
    refs = [greedy_reference(cfg, params, p, n_new) for p in prompts]

    eng = ServeEngine(cfg, params, batch_slots=2, max_seq=32, eos_id=-1)
    done = eng.run([Request(i, p, n_new) for i, p in enumerate(prompts)],
                   {})
    by_rid = {r.rid: r.out for r in done}
    assert by_rid[0][:n_new] == refs[0]
    assert by_rid[1][:n_new] == refs[1]
    # the two prompts landed in different prefill buckets
    assert sorted(eng.stats["bucket_admissions"]) == [8, 16]


def test_paged_equals_dense(setup):
    """The paged KV cache (block pool + tables + gather/scatter) is a
    pure layout change: token streams must match the dense cache."""
    cfg, params = setup
    assert KV.paged_supported(cfg)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(2, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 11, 8, 3)]
    reqs = lambda: [Request(i, p, 4) for i, p in enumerate(prompts)]  # noqa: E731

    eng_p = ServeEngine(cfg, params, batch_slots=2, max_seq=32,
                        block_size=8, eos_id=-1)
    assert eng_p.paged
    out_p = {r.rid: r.out for r in eng_p.run(reqs(), {})}
    eng_d = ServeEngine(cfg, params, batch_slots=2, max_seq=32,
                        paged=False, eos_id=-1)
    out_d = {r.rid: r.out for r in eng_d.run(reqs(), {})}
    assert out_p == out_d


def test_eviction_returns_pages_and_refills(setup):
    """EOS/max-len eviction frees a slot *and* its pages; queued requests
    refill the slot and the pool drains back to full when idle."""
    cfg, params = setup
    rng = np.random.default_rng(5)
    reqs = [Request(i, rng.integers(2, cfg.vocab_size, size=6 + 3 * i)
                    .astype(np.int32), 3) for i in range(5)]
    eng = ServeEngine(cfg, params, batch_slots=2, max_seq=32,
                      block_size=8, eos_id=-1)
    total = eng.kv.free_blocks
    done = eng.run(reqs, {})
    assert sorted(r.rid for r in done) == [0, 1, 2, 3, 4]
    assert eng.stats["prefills"] == 5
    assert all(r is None for r in eng.active)
    assert eng.kv.free_blocks == total          # every page returned


def test_kv_admission_control_under_pressure(setup):
    """A pool too small for every slot at once defers admission instead
    of corrupting state; all requests still finish."""
    cfg, params = setup
    rng = np.random.default_rng(6)
    reqs = [Request(i, rng.integers(2, cfg.vocab_size, size=10)
                    .astype(np.int32), 3) for i in range(4)]
    # 3 slots x 4 blocks/slot = 12 wanted; give 6 -> at most ~2 active
    eng = ServeEngine(cfg, params, batch_slots=3, max_seq=32,
                      block_size=8, kv_blocks=6, eos_id=-1)
    done = eng.run(reqs, {})
    assert sorted(r.rid for r in done) == [0, 1, 2, 3]
    assert eng.kv.free_blocks == 6


def test_open_loop_arrivals_and_latency(setup):
    """Open-loop arrivals: requests are only admissible after their
    arrival time, and latency covers queueing (monotone stamps)."""
    cfg, params = setup
    rng = np.random.default_rng(7)
    reqs = [Request(i, rng.integers(2, cfg.vocab_size, size=6)
                    .astype(np.int32), 3) for i in range(4)]
    arr = poisson_arrivals(4, 100.0, seed=1)
    assert arr == sorted(arr) and len(arr) == 4
    eng = ServeEngine(cfg, params, batch_slots=2, max_seq=32, eos_id=-1)
    done = eng.run(reqs, {}, arrivals=arr)
    assert len(done) == 4
    for r in done:
        assert r.t_admitted >= r.t_arrival
        assert r.t_done > r.t_admitted
        assert r.latency_s > 0


def test_arrival_between_clock_reads_waits_and_admits(setup, monkeypatch):
    """An idle engine whose queue head becomes due between the loop's
    admission test and its wait (clock 100.009 -> 100.011 across an
    arrival due at 100.01) waits for it and admits it, instead of
    reporting a deadlock."""
    from types import SimpleNamespace

    from repro.launch import serve

    cfg, params = setup
    eng = ServeEngine(cfg, params, batch_slots=1, max_seq=32, eos_id=-1)
    reads = iter([100.0, 100.009, 100.011])
    last = [100.011]

    def clock():
        # the scripted reads, then 1 ms a read
        last[0] = next(reads, last[0] + 1e-3)
        return last[0]

    monkeypatch.setattr(serve, "time", SimpleNamespace(
        perf_counter=clock, sleep=lambda s: None))
    req = Request(0, np.arange(2, 8, dtype=np.int32), 3)
    done = eng.run([req], {}, arrivals=[0.01])
    assert [r.rid for r in done] == [0] and len(req.out) == 3
    assert req.t_arrival == pytest.approx(100.01)
    assert req.t_admitted >= 100.011


def test_decode_plan_differs_from_prefill_on_rv32_npu():
    """The m=1 decode shape runs through the same partition DP and, being
    memory-bound, picks different cuts than prefill on the NPU-equipped
    RISC-V hierarchy — the split-plan tentpole, pinned."""
    cfg = configs.get_config("llama3.2-3b").reduced()
    tgt = hw.get_target("rv32_npu")
    pre = M.plan_or_none(cfg, m=64, dtype=cfg.dtype, target=tgt,
                         phase="prefill")
    dec = M.plan_or_none(cfg, m=1, dtype=cfg.dtype, target=tgt,
                         phase="decode")
    assert pre is not None and dec is not None
    assert pre.phase == "prefill" and dec.phase == "decode"
    assert pre.m == 64 and dec.m == 1
    assert pre.chain.cuts() != dec.chain.cuts()


def test_serve_plan_cache_keys_bucket_ladder():
    """Serving plans are keyed (cfg, bucketed m, dtype, target, phase):
    requests inside one bucket share a plan object, bucket/phase/target
    changes never serve a stale plan (mirrors the forward plan's target
    and autotune key regressions)."""
    cfg = configs.get_config("llama3.2-3b").reduced()
    assert M.bucket_m(1) == 8 and M.bucket_m(8) == 8
    assert M.bucket_m(9) == 16 and M.bucket_m(16) == 16
    with pytest.raises(ValueError):
        M.bucket_m(0)
    with pytest.raises(ValueError):
        M.bucket_m(M.PREFILL_BUCKETS[-1] + 1)

    tgt = hw.get_target("cpu_cache")

    def plan(m, phase="prefill", target=tgt):
        mb = 1 if phase == "decode" else M.bucket_m(m)
        return mb, M.plan_or_none(cfg, m=mb, dtype=cfg.dtype,
                                  target=target, phase=phase)

    m10, m16 = plan(10), plan(16)
    assert m10[0] == m16[0] == 16
    assert m10[1] is m16[1]                     # same bucket -> same plan
    m17 = plan(17)
    assert m17[0] == 32 and m17[1] is not m16[1]
    # decode is its own key at m=1 regardless of the requested m
    d = plan(16, phase="decode")
    assert d[0] == 1 and d[1] is not m16[1] and d[1].phase == "decode"
    # a different hierarchy never reuses the cpu_cache plan
    other = plan(16, target=hw.get_target("rv32_l1_l2"))
    assert other[1] is not m16[1]


def test_decode_phase_disqualifies_pallas():
    """Decode-shape qualification: at phase='decode' (m=1) the Pallas
    kernels drop out even on a TPU-class context and the registry binds
    the XLA executors; the identical prefill context keeps Pallas."""
    tgt = hw.get_target("tpu_v5e")

    def names(phase, m):
        mk = lambda kind, **kw: ftl_registry.ExecContext(    # noqa: E731
            kind=kind, platform="tpu", schedule="fused", m=m,
            d_model=768, d_ff=3072, dtype="bfloat16", target=tgt,
            phase=phase, **kw)
        return (ftl_registry.find("mlp", mk("mlp")).name,
                ftl_registry.find("attention",
                                  mk("attention", head_dim=64)).name,
                ftl_registry.find("gemm", mk("gemm")).name)

    assert names("prefill", 512) == ("pallas_fused_mlp",
                                     "pallas_flash_attention",
                                     "pallas_gemm")
    assert all(n.startswith("xla_") for n in names("decode", 1))
    with pytest.raises(ValueError):
        ftl_registry.plan_block(configs.get_config("llama3.2-3b").reduced(),
                                m=1, phase="bogus")


def test_zero_replans_and_both_phase_executors(setup, monkeypatch):
    """Once built, the engine never plans again: with the planner made
    to raise, prompts landing in several rungs of the ladder are traced,
    compiled and served, and the engine still reports resolved executors
    for BOTH serving regimes, mirroring what train logs for its one
    shape."""
    cfg, params = setup
    rng = np.random.default_rng(8)
    reqs = [Request(i, rng.integers(2, cfg.vocab_size, size=4 + 5 * i)
                    .astype(np.int32), 4) for i in range(6)]
    eng = ServeEngine(cfg, params, batch_slots=3, max_seq=32, eos_id=-1)

    def no_planning(*a, **k):
        raise AssertionError("the engine planned after construction")

    monkeypatch.setattr(ftl_registry, "plan_block", no_planning)
    done = eng.run(reqs, {})
    assert sorted(r.rid for r in done) == list(range(6))
    assert len({r.bucket for r in done}) >= 2
    assert set(eng.prefill_plans) == set(eng.buckets)
    assert eng.stats["replans"] == 0

    report = eng.plan_report()
    for phase in ("prefill", "decode"):
        entry = report[phase]
        assert entry is not None
        assert set(entry["executors"]) == {"gemm", "attention", "mlp"}
    assert report["prefill"]["m"] == max(eng.buckets)
    assert report["decode"]["m"] == 1


@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_engine_plans_are_the_planner_cache_entries(setup, phase):
    """The engine holds the planner's own cached plans: each phase's
    plan *is* what ``M.plan_or_none`` returns for the same key, so one
    cache serves the engine and everyone else."""
    cfg, params = setup
    eng = ServeEngine(cfg, params, batch_slots=1, max_seq=32, eos_id=-1)
    plans = (eng.prefill_plans if phase == "prefill"
             else {1: eng.decode_plan})
    assert eng.block_plan is eng.prefill_plans[eng.buckets[-1]]
    for m, plan in plans.items():
        assert plan is not None and plan.phase == phase and plan.m == m
        assert plan is M.plan_or_none(cfg, m=m, dtype=cfg.dtype,
                                      target=eng.target, phase=phase)


# ---------------------------------------------------------------------------
# the paged decode step reads the pool in place and writes only the new
# tokens
# ---------------------------------------------------------------------------

# (KV heads, dtype) over the reduced llama's 4 query heads: GQA groups of
# two, MQA, and GQA in the serving precision
_PAGED_CASES = {"gqa": (2, "float32"), "mqa": (1, "float32"),
                "gqa_bf16": (2, "bfloat16")}
_PAGED_ATOL = {"float32": 1e-4, "bfloat16": 6.25e-2}


@pytest.fixture(scope="module", params=sorted(_PAGED_CASES))
def paged_case(request):
    kv_heads, dtype = _PAGED_CASES[request.param]
    cfg = dataclasses.replace(configs.get_config("llama3.2-3b").reduced(),
                              remat=False, n_kv_heads=kv_heads, dtype=dtype)
    return cfg, M.init_params(cfg, jax.random.PRNGKey(1))


def _one_paged_step(cfg, params, prompt_lens: dict[int, int], slots: int,
                    block_size: int | None = 8):
    """Prefill each ``slot: prompt length`` of ``prompt_lens`` into a
    paged engine's pool (pages of ``block_size``, None: the engine's
    pick) and into a dense per-slot cache (the same prefill cache in
    both), then run one decode step each way; slots left out are dead.
    Returns (paged logits, dense logits, dense cache after the step, pool
    before, pool after, positions, write coordinates)."""
    max_seq = 32
    eng = ServeEngine(cfg, params, batch_slots=slots, max_seq=max_seq,
                      block_size=block_size, eos_id=-1)
    rng = np.random.default_rng(12)
    dense = M.init_cache(cfg, slots, max_seq)
    tok = np.zeros((slots, 1), np.int32)
    pos = np.zeros(slots, np.int32)
    wblk = np.zeros(slots, np.int32)
    woff = np.zeros(slots, np.int32)
    for i, n in prompt_lens.items():
        prompt = rng.integers(2, cfg.vocab_size, size=n).astype(np.int32)
        bucket = M.bucket_m(n, eng.buckets)
        padded = np.zeros(bucket, np.int32)
        padded[:n] = prompt
        _, cache1 = eng.prefill_fn(bucket, eng.prefill_plans[bucket])(
            params, {"tokens": jnp.asarray(padded)[None]}, jnp.int32(n - 1))
        eng.kv.write_prefill(i, cache1, bucket)
        dense = jax.tree.map(
            lambda d, c: d.at[:, i, :bucket].set(c[:, 0]), dense, cache1)
        tok[i, 0] = rng.integers(2, cfg.vocab_size)
        pos[i] = n
        assert eng.kv.allocate(i, n + 1)
        wblk[i], woff[i] = eng.kv.write_coords(i, n)
    before = jax.tree.map(np.asarray, eng.kv.pool)
    logits, pool = eng.decode_fn(
        params, eng.kv.pool, eng.kv.table_array(), jnp.asarray(tok),
        jnp.asarray(pos), jnp.asarray(wblk), jnp.asarray(woff))
    after = jax.tree.map(np.asarray, pool)
    dlogits, dense = M.decode_step(cfg, params, jnp.asarray(tok), dense,
                                   jnp.asarray(pos))
    return (np.asarray(logits, np.float32), np.asarray(dlogits, np.float32),
            dense, before, after, pos, (wblk, woff))


# slots 0, 1, 3 at positions 5, 8 (this step opens the slot's second
# page) and 13; slot 2 dead
_MIXED = {0: 5, 1: 8, 3: 13}


def test_paged_inplace_step_matches_dense_logits(paged_case):
    """One step of the in-place paged decode against the dense per-slot
    path on the same prefill caches: the live slots' logits agree and
    pick the same tokens, for GQA and MQA, in float32 and bfloat16."""
    cfg, params = paged_case
    logits, dlogits, _, _, _, _, _ = _one_paged_step(cfg, params, _MIXED, 4)
    live = sorted(_MIXED)
    atol = _PAGED_ATOL[cfg.dtype]
    np.testing.assert_allclose(logits[live], dlogits[live], atol=atol,
                               rtol=0)
    assert (logits[live, 0].argmax(-1) == dlogits[live, 0].argmax(-1)).all()
    assert np.isfinite(logits).all()      # the dead slot too


def test_paged_inplace_step_writes_only_the_new_tokens(paged_case):
    """After one step each live slot's new K/V sits at its (block,
    offset), equal to what the dense path appended at its position;
    every other row of every mapped page is bitwise unchanged, and the
    dead slot touched nothing but the scratch page 0."""
    cfg, params = paged_case
    _, _, dense, before, after, pos, (wblk, woff) = _one_paged_step(
        cfg, params, _MIXED, 4)
    live = sorted(_MIXED)
    assert wblk[2] == 0 and woff[2] == 0
    assert woff[1] == 0 and wblk[1] not in (0, wblk[0], wblk[3])
    written = {(int(wblk[i]), int(woff[i])) for i in live}
    atol = _PAGED_ATOL[cfg.dtype]
    for name in ("k", "v"):
        b, a = before["layers"]["pos0"][name], after["layers"]["pos0"][name]
        d = np.asarray(dense["layers"]["pos0"][name], np.float32)
        assert a.shape == b.shape            # (L, NB+1, bs, Hk, Dh)
        changed = (a != b).any(axis=(0, 3, 4))              # (NB+1, bs)
        for blk, off in zip(*np.nonzero(changed)):
            assert blk == 0 or (blk, off) in written, (name, blk, off)
        for i in live:
            got = a[:, wblk[i], woff[i]].astype(np.float32)     # (L, Hk, Dh)
            np.testing.assert_allclose(got, d[:, i, pos[i]], atol=atol,
                                       rtol=0)


def test_paged_engine_counts_inplace_steps(setup):
    """A paged engine counts a kernel step for every decode step whose
    attention ran the paged kernel (none here: the jnp gather on the
    CPU); the dense per-slot engine does not page."""
    cfg, params = setup
    rng = np.random.default_rng(13)
    reqs = lambda: [Request(i, rng.integers(2, cfg.vocab_size, size=5 + i)  # noqa: E731
                            .astype(np.int32), 4) for i in range(3)]
    eng = ServeEngine(cfg, params, batch_slots=2, max_seq=32, eos_id=-1)
    eng.run(reqs(), {})
    assert eng.paged and eng.stats["decode_steps"] > 0
    assert eng.stats["paged_kernel_steps"] == (
        eng.stats["decode_steps"] if eng.paged_kernel else 0)
    eng_d = ServeEngine(cfg, params, batch_slots=2, max_seq=32,
                        paged=False, eos_id=-1)
    eng_d.run(reqs(), {})
    assert eng_d.stats["decode_steps"] > 0
    assert eng_d.paged is False


# ---------------------------------------------------------------------------
# the page length follows the paged-attention kernel's tiles, and the
# engine counts the steps and pages the kernel reads
# ---------------------------------------------------------------------------

from repro.kernels import ops                                # noqa: E402
from repro.kernels import paged_attention as PA              # noqa: E402


@pytest.mark.parametrize("arch,dtype,block_size", [
    ("yi-6b", "bfloat16", 8),          # GQA: 8 positions x 4 heads fill
    ("granite-20b", "bfloat16", 16),   # MQA: a bf16 tile is 16 rows
    ("granite-20b", "float32", 8),     # an f32 tile is 8
])
def test_engine_picks_the_shortest_page_that_fills_the_kernels_tiles(
        arch, dtype, block_size):
    cfg = dataclasses.replace(configs.get_config(arch).reduced(),
                              remat=False, dtype=dtype)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, batch_slots=2, max_seq=32, eos_id=-1)
    assert eng.block_size == eng.kv.block_size == block_size
    assert PA.supported(block_size, cfg.n_kv_heads, dtype)
    # an explicit page length still wins
    eng = ServeEngine(cfg, params, batch_slots=2, max_seq=32, eos_id=-1,
                      block_size=32)
    assert eng.kv.block_size == 32


@pytest.fixture(scope="module")
def granite_bf16():
    """granite's block at a CPU size: MQA, un-gated GELU MLP and
    attention with biases, LayerNorm, in the serving precision."""
    cfg = dataclasses.replace(configs.get_config("granite-20b").reduced(),
                              remat=False, dtype="bfloat16")
    assert cfg.n_kv_heads == 1 and not cfg.mlp_gated and cfg.mlp_bias
    assert cfg.norm == "layernorm" and cfg.mlp_act == "gelu"
    return cfg, M.init_params(cfg, jax.random.PRNGKey(2))


def test_mqa_gelu_layernorm_engine_matches_dense_at_16_token_pages(
        granite_bf16):
    """The engine's own page length for MQA in bf16 (16 positions): one
    paged step against the dense per-slot path, with slots inside their
    first page, opening their second (position 16) and deep in it, and
    a dead slot."""
    cfg, params = granite_bf16
    lens = {0: 5, 1: 16, 3: 27}
    logits, dlogits, _, _, _, _, (wblk, woff) = _one_paged_step(
        cfg, params, lens, 4, block_size=None)
    assert woff[1] == 0 and woff[3] == 11        # 16-position pages
    live = sorted(lens)
    np.testing.assert_allclose(logits[live], dlogits[live],
                               atol=_PAGED_ATOL["bfloat16"], rtol=0)
    assert (logits[live, 0].argmax(-1) == dlogits[live, 0].argmax(-1)).all()


def test_paged_engine_counts_kernel_steps_and_page_positions(
        granite_bf16, monkeypatch):
    """With the Pallas kernel on the step's path (interpret mode here),
    every decode step counts as a kernel step, at the engine's
    16-position pages for MQA; off it (the jnp gather on the CPU) the
    counter stays at 0."""
    cfg, params = granite_bf16

    def reqs():
        rng = np.random.default_rng(14)
        return [Request(i, rng.integers(2, cfg.vocab_size, size=n)
                        .astype(np.int32), 4) for i, n in enumerate((5, 20))]

    eng = ServeEngine(cfg, params, batch_slots=2, max_seq=64, eos_id=-1)
    assert not eng.paged_kernel
    eng.run(reqs(), {})
    assert eng.stats["decode_steps"] == 3
    assert eng.stats["paged_kernel_steps"] == 0

    monkeypatch.setattr(ops, "_resolve", lambda backend: "pallas")
    eng = ServeEngine(cfg, params, batch_slots=2, max_seq=64, eos_id=-1)
    assert eng.paged_kernel and eng.block_size == 16
    done = {r.rid: r.out for r in eng.run(reqs(), {})}
    # both admitted at once, then three steps of the kernel
    assert eng.stats["decode_steps"] == eng.stats["paged_kernel_steps"] == 3
    from repro import obs
    got = {lbl["stat"]: v for lbl, v in obs.collect()["serve_stats"][
        "samples"]}
    assert got["paged_kernel_steps"] == got["decode_steps"] == 3
    assert all(len(out) == 4 for out in done.values())


# ---------------------------------------------------------------------------
# the allocator hands out pages in runs the kernel copies in one piece
# ---------------------------------------------------------------------------

def _is_run(pages) -> bool:
    return bool((np.diff(pages) == 1).all())


def test_slot_entering_a_run_takes_a_whole_aligned_run(setup):
    """In the default pool a slot's first page and the first page past
    each run take a whole run of consecutive pages into the run's table
    entries; growth inside the run allocates nothing; release returns
    every page."""
    cfg, _ = setup
    kv = KV.PagedKVCache(cfg, slots=2, max_seq=256, block_size=8)
    run = kv.run
    assert run == PA.pages_per_copy(kv.blocks_per_slot) == 16
    start = kv.free_blocks
    assert start == kv.num_blocks == 2 * 32

    assert kv.allocate(0, 3)                     # one position: a run
    assert kv.n_alloc[0] == run and _is_run(kv.tables[0, :run])
    assert (kv.tables[0, run:] == 0).all()
    assert kv.free_blocks == start - run
    before = kv.tables.copy()
    for n in (8, 9, 100, run * 8):               # inside the first run
        assert kv.can_allocate(0, n) and kv.allocate(0, n)
    assert (kv.tables == before).all() and kv.free_blocks == start - run

    assert kv.allocate(1, 50)
    assert kv.allocate(0, run * 8 + 1)           # entry 16: the next run
    assert _is_run(kv.tables[0, run:2 * run])
    assert _is_run(kv.tables[1, :run])
    assert kv.free_blocks == start - 3 * run
    # no page is handed out twice
    held = np.concatenate([kv.tables[0, :2 * run], kv.tables[1, :run]])
    assert len(set(held.tolist())) == 3 * run and 0 not in held

    kv.release(0)
    kv.release(1)
    assert kv.free_blocks == start
    assert (kv.tables == 0).all() and (kv.n_alloc == 0).all()
    # the runs came back whole: the next slot gets one again
    assert kv.allocate(1, 1) and _is_run(kv.tables[1, :run])


@pytest.mark.parametrize("extra", [0, 1, 16])
def test_pool_of_every_slot_at_max_seq_is_all_runs(setup, extra):
    """A pool at or above the default size fills every slot to max_seq
    in runs, with no page left over that a slot could need."""
    cfg, _ = setup
    kv = KV.PagedKVCache(cfg, slots=3, max_seq=256, block_size=8,
                         num_blocks=3 * 32 + extra)
    assert kv.run == 16
    for slot in range(3):
        for n in range(1, 257, 8):
            assert kv.allocate(slot, n)
        assert kv.n_alloc[slot] == 32
        assert all(_is_run(kv.tables[slot, r:r + 16]) for r in (0, 16))
    assert len(set(kv.tables.ravel().tolist())) == 3 * 32
    assert kv.free_blocks == 16 * (extra // 16)


def test_pressured_pool_hands_out_single_pages(setup):
    """A pool smaller than every slot at max_seq hands out one page at a
    time, as many as each slot covers: two slots hold 2 and 8 pages of a
    32-page pool, and the first still grows to 17 pages past position
    128.  Pages in runs would have taken the whole pool for the two."""
    cfg, _ = setup
    kv = KV.PagedKVCache(cfg, slots=2, max_seq=256, block_size=8,
                         num_blocks=32)
    assert kv.run == 1
    assert kv.allocate(0, 16) and kv.n_alloc[0] == 2
    assert kv.allocate(1, 64) and kv.n_alloc[1] == 8
    assert kv.free_blocks == 22
    for n in range(17, 130):
        assert kv.allocate(0, n)
    assert kv.n_alloc[0] == 17 and kv.free_blocks == 7
    assert not kv.can_allocate(1, 8 * 24)        # 16 more: refused
    assert not kv.allocate(1, 8 * 24) and kv.n_alloc[1] == 8
    kv.release(0)
    kv.release(1)
    assert kv.free_blocks == 32 and (kv.tables == 0).all()


def test_pressured_pool_admits_every_slot_it_can_hold(setup):
    """Under admission control each slot holds only the pages it covers:
    three 2-page prompts share a 6-page pool at once, and a fourth slot
    waits for a page to come free."""
    cfg, params = setup
    rng = np.random.default_rng(16)
    reqs = [Request(i, rng.integers(2, cfg.vocab_size, size=10)
                    .astype(np.int32), 3) for i in range(4)]
    eng = ServeEngine(cfg, params, batch_slots=4, max_seq=32,
                      block_size=8, kv_blocks=6, eos_id=-1)
    assert eng.kv.run == 1
    for slot, req in enumerate(reqs[:3]):
        assert eng._admit(req, slot, {})
    assert eng.kv.free_blocks == 0
    assert not eng._admit(reqs[3], 3, {})
