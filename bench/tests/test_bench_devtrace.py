"""The trace reduction: busy union, idle share, device time per program,
top operations and the longest gaps, on synthetic events and on a
stretch of a trace recorded on the chip."""
import gzip
import json
import pathlib

import pytest

from bench import devtrace

DATA = pathlib.Path(__file__).parent / "data"


def _events(ops, mods):
    return {"planes": {"/device:TPU:0": {devtrace.OPS_LINE: ops,
                                         devtrace.MODULES_LINE: mods}}}


def test_union_merges_overlaps():
    assert devtrace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
        (0, 3), (5, 8)]


def test_reduce_busy_programs_ops_and_gaps():
    ns = 1e9
    # a loop with a fusion nested in its body, then two more programs
    ops = [["%while.1 = (s32[]) while(...)", 0, 1.5 * ns],
           ["%fusion.2 = bf16[8] fusion(...)", 0.2 * ns, 0.8 * ns],
           ["%fusion.2 = bf16[8] fusion(...)", 4 * ns, 1 * ns],
           ["%dot.3 = f32[2] dot(...)", 9 * ns, 0.5 * ns]]
    mods = [["jit_step_fn(123)", 0, 1.5 * ns],
            ["jit_paged_step(9)", 4 * ns, 1 * ns],
            ["jit_paged_step(9)", 9 * ns, 0.5 * ns]]
    r = devtrace.reduce(_events(ops, mods))
    assert r["busy_s"] == pytest.approx(1.5 + 1 + 0.5)
    assert r["programs"]["jit_step_fn"] == {"s": pytest.approx(1.5), "n": 1}
    assert devtrace.program_seconds(r, "paged_step") == (
        pytest.approx(1.5), 2)
    assert dict(r["device_ops"]) == {
        "jit_paged_step:fusion.2": pytest.approx(1.0),
        "jit_step_fn:fusion.2": pytest.approx(0.8),
        "jit_step_fn:while.1": pytest.approx(0.7),
        "jit_paged_step:dot.3": pytest.approx(0.5)}
    assert r["idle_gaps"] == [["after jit_paged_step", pytest.approx(4.0)],
                              ["after jit_step_fn", pytest.approx(2.5)]]


def test_reduce_refuses_a_trace_without_a_device():
    with pytest.raises(ValueError):
        devtrace.reduce({"planes": {}})


def test_program_names_lose_their_ids():
    assert devtrace.program_name("jit_paged_step(1234)") == "jit_paged_step"
    assert devtrace.program_name("jit__write_pages.7") == "jit__write_pages"


def _recorded():
    with gzip.open(DATA / "yi6b_prefill_open_trace.json.gz", "rt") as f:
        return json.load(f)


def test_recorded_chip_trace():
    """0.45 s of a yi6b.prefill_open trace (TPU v5 lite): one prefill
    and five decode steps of the engine, with the small programs of
    admission and sampling between them."""
    ev = _recorded()
    r = devtrace.reduce(ev)
    a, b = ev["stretch_ns"]
    assert 0 < r["busy_s"] <= (b - a) * 1e-9
    assert devtrace.program_seconds(r, "step_fn")[1] == 1
    s, n = devtrace.program_seconds(r, "paged_step")
    assert n == 5 and 0.03 < s / n < 0.06
    # self times partition the busy time: nested ops are not counted twice
    ops = ev["planes"]["/device:TPU:0"][devtrace.OPS_LINE]
    total = sum(d for _, d, _ in devtrace._self_times(ops))
    assert total == pytest.approx(r["busy_s"], rel=1e-6)
    assert all(":" in n and " " not in n for n, _ in r["device_ops"])
    idle = 1 - r["busy_s"] / ((b - a) * 1e-9)
    assert 0.0 < idle < 0.2
