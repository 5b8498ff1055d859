#!/usr/bin/env python3
"""Readings a cell's fixed numbers are set from, taken on the chip in one
process (set-up is paid once, the engine is reused).  Not run by the
benchmark's own runs.

  python3 bench/calibrate.py knee --workload <cell> --rates 1,1.5,2 --seconds 30
      Serves the cell's open-loop mix at each rate for a window and prints
      its tails, its output rate and the backlog left at the window's
      end: the knee is the highest rate with no growing backlog, and the
      cell's rate_per_s sits at about four fifths of it.

  python3 bench/calibrate.py limits --workload <cell> --seeds 1,2,3 \
      --control-seeds 1,2,3 --seconds 51
      For each seed, new weights and token ids, a window of the cell's
      own traffic, and the correctness check's reading of what was
      served: the program's readings set the lower end of the limit.  On
      the control seeds the float8 control is read at the same positions
      too: its readings set the upper end.

Each reading is one JSON line on standard output.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
if sys.path and pathlib.Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path.pop(0)
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import correctness, latency, lookup, served, timing  # noqa: E402
from bench import run as bench_run  # noqa: E402


def _ints(s: str) -> list[int]:
    return [int(x) for x in s.split(",") if x]


def _emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


class Cell:
    """One engine for a cell, set up as a timed run sets it up
    (``served.start``), whose weights can be drawn anew."""

    def __init__(self, workload: str, seed: int, seconds: float,
                 rates: list[float] | None = None,
                 root: pathlib.Path = lookup.ROOT):
        self.cell = lookup.cell(workload, root)
        bench_run.device_check(self.cell["workload"]["chips"])
        self.params = self.cell["params"]
        # every bucket any of the windows to come reaches
        lens = [len(p) for rate in rates or [None]
                for p in bench_run.cell_traffic(self.cell, seconds, seed,
                                                rate)[0]]
        self.system = served.start(self.cell, seed, lens,
                                   bench_run.BLOCK_SIZE)

    def window(self, seed: int, seconds: float, rate: float | None = None):
        prompts, outs, arrivals = bench_run.cell_traffic(self.cell, seconds,
                                                         seed, rate)
        win = timing.Window(seconds)
        reqs = timing.make_requests(prompts, outs, win)
        got, stats = timing.serve_window(self.system.eng, reqs, arrivals,
                                         win)
        served.free_slots(self.system.eng)
        return got, stats, win


def knee(args) -> None:
    rates = [float(r) for r in args.rates.split(",")]
    c = Cell(args.workload, args.seed, args.seconds, rates)
    for rate in rates:
        got, stats, win = c.window(args.seed, args.seconds, rate)
        e2e = latency.end_to_end(got, win.t_start, win.t_end)
        due = [r for r in got if r.t_due <= win.t_end]
        _emit({"rate_per_s": rate, **e2e, "due": len(due),
               "admitted": sum(1 for r in due if r.stamps),
               "finished": sum(1 for r in due if r.done),
               "backlog_at_end": sum(1 for r in due if not r.stamps),
               "decode_steps": stats["after"]["decode_steps"]
               - stats["before"]["decode_steps"]})


def limits(args) -> None:
    seeds = _ints(args.seeds)
    controls = set(_ints(args.control_seeds))
    c = Cell(args.workload, seeds[0], args.seconds)
    rows = c.cell["traffic"]["output_len"]["max"]
    for i, seed in enumerate(seeds):
        if i:
            served.reseed(c.system, seed)
        got, _, _ = c.window(seed, args.seconds)
        t = time.perf_counter()
        sample = correctness.sample(got, seed, c.params["sample_tokens"])
        rec = correctness.gaps(c.system.spec, c.system.w, sample, rows,
                               control=seed in controls)
        rec.update(seed=seed, finished=sum(1 for r in got if r.done),
                   check_s=time.perf_counter() - t)
        _emit(rec)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    k = sub.add_parser("knee")
    k.add_argument("--rates", required=True)
    lim = sub.add_parser("limits")
    lim.add_argument("--seeds", required=True)
    lim.add_argument("--control-seeds", default="")
    for p in (k, lim):
        p.add_argument("--workload", required=True)
        p.add_argument("--seconds", type=float, required=True)
        p.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    try:
        (knee if args.cmd == "knee" else limits)(args)
    except bench_run.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
