"""The traffic generator: deterministic per seed, within its bounds, the
same work for every seed, and one declared Poisson sample of arrivals
and lengths for an open loop, due inside the window."""
import numpy as np
import pytest

from bench import lookup, traffic

MIXES = ["prefill_open", "decode_batch"]


def _gen(mix, seed, seconds=20.0, slots=4, rate=2.0):
    return traffic.generate(lookup.traffic(mix), seconds=seconds,
                            slots=slots, rate_per_s=rate, seed=seed,
                            vocab=1000)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    a, b = _gen(mix, 2**31 + 5), _gen(mix, 2**31 + 5)
    assert all(np.array_equal(x, y) for x, y in zip(a[0], b[0]))
    assert a[1:] == b[1:]
    c = _gen(mix, 7)
    assert not all(np.array_equal(x, y) for x, y in zip(a[0], c[0]))


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_keep_their_bounds(mix):
    t = lookup.traffic(mix)
    prompts, outs, _ = _gen(mix, 3)
    plen = [len(p) for p in prompts]
    assert t["prompt_len"]["min"] <= min(plen)
    assert max(plen) <= t["prompt_len"]["max"]
    assert t["output_len"]["min"] <= min(outs)
    assert max(outs) <= t["output_len"]["max"]
    assert max(plen) + t["output_len"]["max"] <= t["max_seq"]
    assert all(p.min() >= 2 and p.max() < 1000 for p in prompts)


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_offers_the_same_work(mix):
    a, b = _gen(mix, 1), _gen(mix, 2)
    assert sorted(map(len, a[0])) == sorted(map(len, b[0]))
    assert sorted(a[1]) == sorted(b[1])
    if lookup.traffic(mix)["arrivals"] == "batch":
        # the same sizes in another order
        assert [len(p) for p in a[0]] != [len(p) for p in b[0]]
    else:
        # the same sample: arrivals and lengths, request by request
        assert [len(p) for p in a[0]] == [len(p) for p in b[0]]
        assert a[1:] == b[1:]


def test_open_loop_is_one_poisson_sample_due_inside_the_window():
    t = lookup.traffic("prefill_open")
    plens, outs, arr = traffic.open_schedule(t, seconds=400.0,
                                             rate_per_s=2.0)
    assert all(0 < x < y for x, y in zip(arr, arr[1:]))
    assert arr[-1] < 400.0
    # about rate x seconds requests (Poisson: 800 +- 4 sigma)
    assert abs(len(arr) - 800) < 4 * 800 ** 0.5
    gaps = np.diff(np.concatenate([[0.0], arr]))
    assert 0.45 < gaps.mean() < 0.55
    # exponential gaps: the spread equals the mean, not a stratified one
    assert 0.85 < gaps.std() / gaps.mean() < 1.15
    assert np.median(plens) == pytest.approx(t["prompt_len"]["median"],
                                             rel=0.1)


def test_open_loop_sample_is_fixed_by_its_schedule_seed():
    t = lookup.traffic("prefill_open")
    short = traffic.open_schedule(t, seconds=20.0, rate_per_s=1.0)
    long = traffic.open_schedule(t, seconds=40.0, rate_per_s=1.0)
    fast = traffic.open_schedule(t, seconds=20.0, rate_per_s=2.0)
    n = len(short[0])
    # a longer window serves the same requests first
    for x, y in zip(short, long):
        np.testing.assert_array_equal(x, y[:n])
    # a doubled rate serves the same sample in half the time
    np.testing.assert_allclose(fast[2], long[2][:len(fast[2])] / 2)
    np.testing.assert_array_equal(fast[0], long[0][:len(fast[0])])
    other = traffic.open_schedule(dict(t, schedule_seed=1), seconds=20.0,
                                  rate_per_s=1.0)
    assert list(other[0]) != list(short[0])


def test_lognormal_median_and_batch_outlasts_the_window():
    u = (np.arange(1001) + 0.5) / 1001
    spec = {"dist": "lognormal", "median": 1024, "sigma": 0.6,
            "min": 1, "max": 10**6}
    assert abs(np.median(traffic.lengths(spec, u)) - 1024) <= 1
    t = lookup.traffic("decode_batch")
    prompts, outs, arr = _gen("decode_batch", 5, seconds=40.0, slots=10)
    assert arr is None
    # each slot's queue holds more output than the window can decode at
    # the bound on steps a second
    assert sum(outs) / 10 > 40.0 * t["steps_per_s_bound"]
