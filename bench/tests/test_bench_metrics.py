"""The per-layer readers on a hand-made run: spans, counters, requests
and a reduced trace whose every number is known."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench import flops, lookup
from bench.tests.test_bench_flops import SMALL

PEAK = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}


def _span(name, t0, t1):
    return SimpleNamespace(name=name, t0=t0, t1=t1, duration_s=t1 - t0)


def _req(plen, bucket, stamps):
    return SimpleNamespace(prompt=np.zeros(plen, np.int32), bucket=bucket,
                           stamps=list(stamps))


def _ctx(trace=True):
    # two requests: prefills at [1, 1.2] and [2, 2.3], then decode steps
    a = _req(100, 128, [1.25, 1.45, 1.65])
    b = _req(300, 512, [2.35, 2.55])
    spans = [_span("serve:admit", 1.0, 1.25), _span("serve:prefill:m128",
                                                     1.0, 1.2),
             _span("serve:decode_step", 1.4, 1.5),
             _span("serve:decode_step", 1.6, 1.7),
             _span("serve:admit", 2.0, 2.35), _span("serve:prefill:m512",
                                                     2.0, 2.3),
             _span("serve:decode_step", 2.5, 2.6)]
    programs = {"jit_step_fn": {"s": 0.4, "n": 2},
                "jit_paged_step": {"s": 0.24, "n": 3}}
    return {"spec": SMALL, "peak": PEAK, "served": [a, b], "spans": spans,
            "stats": {"before": {"tokens": 10, "decode_steps": 5},
                      "after": {"tokens": 16, "decode_steps": 8}},
            "trace": {"programs": programs} if trace else None,
            "traced": (0.5, 3.0), "window": (0.0, 3.0),
            "device": {"busy_s": 2.0, "window_s": 2.5}}


def _read(name, ctx):
    return lookup.metric_reader(name)(ctx)


def test_span_and_counter_readers():
    ctx = _ctx()
    assert _read("admit_ms", ctx) == pytest.approx(300.0)
    assert _read("decode_step_ms", ctx) == pytest.approx(100.0)
    assert _read("prefill_token_util", ctx) == pytest.approx(
        100.0 * 400 / 640)
    assert _read("decode_batch_mean", ctx) == pytest.approx(2.0)
    assert _read("device_idle_share", ctx) == pytest.approx(20.0)


def test_mfu_prefill_counts_real_tokens_over_program_time():
    need = flops.prefill_flops(SMALL, 100) + flops.prefill_flops(SMALL, 300)
    assert _read("mfu.prefill", _ctx()) == pytest.approx(
        100.0 * need / (0.4 * 1e12))


def test_mfu_decode_is_least_time_over_program_time():
    # steps: request a at context 101 and 102, request b at 301
    least = sum(flops.least_time_s(*flops.decode_step(SMALL, [c]), PEAK)[0]
                for c in (101, 102, 301))
    assert _read("mfu.decode", _ctx()) == pytest.approx(
        100.0 * (least / 3) / (0.24 / 3))


def test_trace_readers_read_nothing_without_a_trace_or_a_match():
    assert _read("mfu.prefill", _ctx(trace=False)) is None
    assert _read("mfu.decode", _ctx(trace=False)) is None
    assert _read("device_idle_share", _ctx(trace=False)) is None
    ctx = _ctx()
    ctx["trace"]["programs"]["jit_paged_step"]["n"] = 6
    assert _read("mfu.decode", ctx) is None
