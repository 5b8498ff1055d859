"""End-to-end arithmetic: percentiles over every request and every gap,
censored first tokens, and the rate over the whole window."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench import latency


def _req(t_due, stamps):
    return SimpleNamespace(t_due=t_due, stamps=list(stamps))


def test_percentiles_pool_every_gap_not_chunk_medians():
    # one request with many short gaps, one with few long ones
    a = _req(0.0, np.arange(0, 10.0, 0.1))
    b = _req(0.0, [0.0, 2.0, 4.0, 6.0])
    gaps = latency.itls([a, b])
    assert len(gaps) == 99 + 3
    assert latency.percentile(gaps, 95) == pytest.approx(
        np.percentile(gaps, 95))
    assert latency.percentile(gaps, 50) == pytest.approx(0.1)


def test_ttft_censors_requests_with_no_token_at_the_end():
    reqs = [_req(1.0, [1.5, 1.6]), _req(2.0, []), _req(9.0, []),
            _req(11.0, [])]
    # the last is due after the end and does not count
    assert latency.ttfts(reqs, t_end=10.0) == pytest.approx([0.5, 8.0, 1.0])


def test_rate_and_gaps_count_only_the_window():
    # the stamp at 10.5 came after the window, while the engine served on
    reqs = [_req(0.0, [0.5, 1.0, 1.5]), _req(0.0, [2.0, 10.5])]
    out = latency.end_to_end(reqs, t_start=0.0, t_end=10.0)
    assert out["out_tok_s"] == pytest.approx(4 / 10.0)
    assert latency.itls(reqs, t_end=10.0) == pytest.approx([0.5, 0.5])
    assert out["itl_p95_ms"] == out["itl_p99_ms"] == pytest.approx(500.0)
    assert out["ttft_p90_ms"] == pytest.approx(1e3 * np.percentile(
        [0.5, 2.0], 90))


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        latency.percentile([], 90)
