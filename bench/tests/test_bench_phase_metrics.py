"""The readers of the engine's phase spans on hand-made spans whose every
number is known: host time per decode step and the queue's wait."""
from types import SimpleNamespace

import pytest

from bench import lookup


def _span(name, t0, t1):
    return SimpleNamespace(name=name, t0=t0, t1=t1, duration_s=t1 - t0)


def _step(t0, prepare, launch, sync, commit):
    """A decode step at ``t0`` tiled by its four phases (seconds each)."""
    out, t = [], t0
    for name, d in (("prepare", prepare), ("launch", launch),
                    ("sync", sync), ("commit", commit)):
        out.append(_span(f"serve:step:{name}", t, t + d))
        t += d
    return [_span("serve:decode_step", t0, t)] + out


def _spans():
    # step 1: schedule [0.9, 1.0] holds an admission of 0.06 s, so 0.04 s
    # of it is host work; phases 0.002 + 0.001 + (sync) + 0.003 host
    # step 2: schedule [1.2, 1.21] holds nothing
    # step 3: schedule [1.5, 1.8] is mostly a wait for an arrival
    spans = [_span("serve:schedule", 0.9, 1.0),
             _span("serve:admit", 0.92, 0.98),
             _span("serve:prefill:m128", 0.92, 0.97)]
    spans += _step(1.0, 0.002, 0.001, 0.04, 0.003)
    spans += [_span("serve:schedule", 1.2, 1.21)]
    spans += _step(1.21, 0.004, 0.001, 0.04, 0.005)
    spans += [_span("serve:schedule", 1.5, 1.8),
              _span("serve:wait_arrival", 1.5, 1.79)]
    spans += _step(1.8, 0.001, 0.001, 0.04, 0.001)
    spans += [_span("serve:queue", 0.5, 0.92), _span("serve:queue", 1.0, 1.1),
              _span("serve:queue", 1.2, 1.23), _span("serve:queue", 1.7, 1.9),
              _span("serve:queue", 2.5, 3.5)]
    return spans


def _ctx(spans, window=(0.95, 3.0)):
    return {"spans": spans, "window": window}


def _read(name, ctx):
    return lookup.metric_reader(name)(ctx)


def test_step_host_ms_is_the_median_of_host_phases_and_schedule():
    # per step: 0.006 + 0.04, 0.010 + 0.01, 0.003 + 0.01
    assert _read("step_host_ms", _ctx(_spans())) == pytest.approx(20.0)


def test_step_host_ms_counts_steps_begun_in_the_window():
    # only the last step (1.8) begins in [1.5, 3.0]
    assert _read("step_host_ms", _ctx(_spans(), (1.5, 3.0))) == \
        pytest.approx(13.0)


def test_queue_wait_p90_ms_reads_the_waits_that_end_in_the_window():
    # waits of 420 (ends before the window's start), 100, 30, 200 and
    # 1000 ms (ends after its end): the middle three count, and the 90th
    # percentile of 30, 100 and 200 lies 0.8 of the way from 100 to 200
    want = 100 + 0.8 * (200 - 100)
    assert _read("queue_wait_p90_ms", _ctx(_spans(), (0.95, 3.0))) == \
        pytest.approx(want)


def test_phase_readers_read_nothing_from_a_program_without_phases():
    """The spans a program without phase spans records: a step and an
    admission, with no phase, schedule or queue span."""
    old = [_span("serve:admit", 1.0, 1.2), _span("serve:prefill:m64", 1.0,
                                                  1.15),
           _span("serve:decode_step", 1.3, 1.35)]
    assert _read("step_host_ms", _ctx(old, (0.0, 2.0))) is None
    assert _read("queue_wait_p90_ms", _ctx(old, (0.0, 2.0))) is None
    assert _read("step_host_ms", _ctx([], (0.0, 2.0))) is None
