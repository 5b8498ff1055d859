"""Benchmark harness: one module per paper figure/claim + the roofline
table.  ``python -m benchmarks.run`` prints everything as CSV sections and
writes ``BENCH_sections.json`` (per-section status/timings, uploaded by
the CI bench-smoke job next to ``BENCH_block.json``).  ``BENCH_SMOKE=1``
runs every section on tiny shapes."""
from __future__ import annotations

import json
import sys
import time

from ._smoke import smoke

SECTIONS_OUT = "BENCH_sections.json"


def _write_status(results: list[dict]) -> None:
    with open(SECTIONS_OUT, "w") as f:
        json.dump({
            "smoke": smoke(),
            "sections": results,
        }, f, indent=2)


def main() -> None:
    from . import (bench_attention, bench_autotune, bench_block,
                   bench_calibrate, bench_mesh, bench_paper_mlp,
                   bench_roofline, bench_schedule, bench_solver,
                   bench_targets, bench_tpu_mlp)

    sections = [
        ("targets: per-level traffic across memory hierarchies + gate",
         bench_targets.main),
        ("schedule-sim: tile-level DES replay vs analytic roofline + gate",
         bench_schedule.main),
        ("autotune: DES-scored search vs analytic argmin + gate",
         bench_autotune.main),
        ("paper-fig3: ViT MLP layer-per-layer vs FTL (Siracusa profiles)",
         bench_paper_mlp.main),
        ("ftl-at-scale: fused-vs-unfused MLP per assigned arch (TPU v5e)",
         bench_tpu_mlp.main),
        ("ftl-attention: fused-tiled attention traffic", bench_attention.main),
        ("ftl-solver: branch-and-bound performance", bench_solver.main),
        ("block-exec: layer-per-layer vs BlockPlan-driven whole block",
         bench_block.main),
        ("mesh: collective-aware 1->N scaling + multi-port overlap + gate",
         bench_mesh.main),
        ("calibrate: fitted Target constants + modeled-vs-measured "
         "drift gate", bench_calibrate.main),
        ("roofline: dry-run artifacts (per arch x shape x mesh)",
         bench_roofline.main),
    ]
    results: list[dict] = []
    for title, fn in sections:
        print(f"\n### {title}")
        t0 = time.time()
        try:
            fn()
        except Exception as e:                  # noqa: BLE001
            print(f"FAILED: {type(e).__name__}: {e}")
            results.append({"section": title, "ok": False,
                            "error": f"{type(e).__name__}: {e}"})
            _write_status(results)
            raise
        dt = time.time() - t0
        results.append({"section": title, "ok": True,
                        "seconds": round(dt, 1)})
        print(f"# section took {dt:.1f}s", file=sys.stderr)
    _write_status(results)


if __name__ == "__main__":
    main()
