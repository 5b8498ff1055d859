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
    _, pre = M.serve_plan(cfg, m=64, target=tgt, phase="prefill")
    _, dec = M.serve_plan(cfg, m=1, target=tgt, phase="decode")
    assert pre is not None and dec is not None
    assert pre.phase == "prefill" and dec.phase == "decode"
    assert pre.m == 64 and dec.m == 1
    assert pre.chain.cuts() != dec.chain.cuts()


def test_serve_plan_cache_keys_bucket_ladder():
    """serve_plan is keyed (cfg, bucketed m, dtype, target, phase):
    requests inside one bucket share a plan object, bucket/phase/target
    changes never serve a stale plan (mirrors the _block_plan target and
    autotune key regressions)."""
    cfg = configs.get_config("llama3.2-3b").reduced()
    assert M.bucket_m(1) == 8 and M.bucket_m(8) == 8
    assert M.bucket_m(9) == 16 and M.bucket_m(16) == 16
    with pytest.raises(ValueError):
        M.bucket_m(0)
    with pytest.raises(ValueError):
        M.bucket_m(M.PREFILL_BUCKETS[-1] + 1)

    tgt = hw.get_target("cpu_cache")
    m10 = M.serve_plan(cfg, m=10, target=tgt, phase="prefill")
    m16 = M.serve_plan(cfg, m=16, target=tgt, phase="prefill")
    assert m10[0] == m16[0] == 16
    assert m10[1] is m16[1]                     # same bucket -> same plan
    m17 = M.serve_plan(cfg, m=17, target=tgt, phase="prefill")
    assert m17[0] == 32 and m17[1] is not m16[1]
    # decode is its own key at m=1 regardless of the requested m
    d = M.serve_plan(cfg, m=16, target=tgt, phase="decode")
    assert d[0] == 1 and d[1] is not m16[1] and d[1].phase == "decode"
    # a different hierarchy never reuses the cpu_cache plan
    other = M.serve_plan(cfg, m=16, target=hw.get_target("rv32_l1_l2"),
                         phase="prefill")
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


def test_zero_replans_and_both_phase_executors(setup):
    """Steady-state decode never replans (100% plan-cache hits after
    warmup) and the engine reports resolved executors for BOTH serving
    regimes, mirroring what train logs for its one shape."""
    cfg, params = setup
    rng = np.random.default_rng(8)
    reqs = [Request(i, rng.integers(2, cfg.vocab_size, size=4 + 5 * i)
                    .astype(np.int32), 4) for i in range(6)]
    eng = ServeEngine(cfg, params, batch_slots=3, max_seq=32, eos_id=-1)
    eng.warmup_compile()
    warm_misses = eng.plans.counters()["misses"]
    eng.run(reqs, {})
    after = eng.plans.counters()
    assert eng.stats["replans"] == 0
    assert after["misses"] == warm_misses
    assert after["misses_after_warmup"] == 0
    assert after["hits"] > 0

    report = eng.plan_report()
    for phase in ("prefill", "decode"):
        entry = report[phase]
        assert entry is not None
        assert set(entry["executors"]) == {"gemm", "attention", "mlp"}
    assert report["prefill"]["m"] == max(eng.buckets)
    assert report["decode"]["m"] == 1


def test_clear_plan_caches_resets_serve_counters(setup):
    """Regression: ``registry.clear_plan_caches()`` used to drop the 13
    lru caches but leave the engine's PlanCache counters and replan stat
    standing, so ``plan_report`` claimed reuse of plans the clear had
    invalidated.  A clear must reset hits/misses/warmth/replans with the
    caches — and the engine must still serve afterwards (replanning,
    and saying so)."""
    cfg, params = setup
    rng = np.random.default_rng(9)
    eng = ServeEngine(cfg, params, batch_slots=1, max_seq=32, eos_id=-1)
    assert eng.plans.counters()["plans"] > 0    # warmed at construction
    assert eng.plans.warmed

    ftl_registry.clear_plan_caches()
    c = eng.plans.counters()
    assert c == {"plans": 0, "hits": 0, "misses": 0,
                 "misses_after_warmup": 0}
    assert not eng.plans.warmed
    assert eng.stats["replans"] == 0
    # the ledger itself reset too
    for stats in ftl_registry.plan_cache_stats().values():
        assert stats["hits"] == 0 and stats["misses"] == 0

    # serving after a clear replans cleanly: fresh plan objects, honest
    # miss counters (not misses_after_warmup — warmth was reset too)
    prompt = rng.integers(2, cfg.vocab_size, size=6).astype(np.int32)
    done = eng.run([Request(0, prompt, 3)], {})
    assert len(done) == 1 and len(done[0].out) == 3
    c = eng.plans.counters()
    assert c["plans"] > 0 and c["misses"] > 0
    assert c["misses_after_warmup"] == 0


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
        bucket, plan = eng.plans.get(n, "prefill")
        padded = np.zeros(bucket, np.int32)
        padded[:n] = prompt
        _, cache1 = eng.prefill_fn(bucket, plan)(
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
    """Every decode step of a paged engine runs the in-place step; the
    dense per-slot engine runs none."""
    cfg, params = setup
    rng = np.random.default_rng(13)
    reqs = lambda: [Request(i, rng.integers(2, cfg.vocab_size, size=5 + i)  # noqa: E731
                            .astype(np.int32), 4) for i in range(3)]
    eng = ServeEngine(cfg, params, batch_slots=2, max_seq=32, eos_id=-1)
    eng.run(reqs(), {})
    assert eng.paged and eng.stats["decode_steps"] > 0
    assert eng.stats["paged_inplace_steps"] == eng.stats["decode_steps"]
    eng_d = ServeEngine(cfg, params, batch_slots=2, max_seq=32,
                        paged=False, eos_id=-1)
    eng_d.run(reqs(), {})
    assert eng_d.stats["decode_steps"] > 0
    assert eng_d.stats["paged_inplace_steps"] == 0


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
