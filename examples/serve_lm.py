"""Serving example: continuous-batching engine over a reduced model.

Admits a queue of prompt requests into fixed decode slots, prefills each
at its bucketed length (paged KV cache when the config supports it),
then decodes all active slots together — each at its own position.
Prefill and decode run on *separate* FTL plans (the memory-bound m=1
decode DP generally picks different cuts), all planned once when the
engine is built.

Run:  PYTHONPATH=src python examples/serve_lm.py

Extra flags pass straight through to ``repro.launch.serve``:

  --arrival-rate 8         open-loop Poisson arrivals at 8 req/s
                           (default: everything arrives at t=0)
  --trace decode.json      Chrome-tracing timeline of the decode plan's
                           simulated schedule (load in Perfetto or
                           chrome://tracing); with --obs it becomes the
                           merged live+modeled timeline, written post-run
  --obs                    record the engine's lifecycle spans
  --obs-trace live.json    merged live+modeled Perfetto timeline
                           (implies --obs; must differ from --trace —
                           the same path is rejected, not overwritten)
  --obs-metrics serve.prom Prometheus text exposition of the metrics
                           registry (implies --obs)
  --target rv32_npu        plan for a specific memory-hierarchy preset
  --block-size 16          paged-KV page length; --dense-kv disables
                           paging
"""
import sys

from repro.launch.serve import main

if __name__ == "__main__":
    sys.argv = [sys.argv[0], "--arch", "llama3.2-3b", "--reduced",
                "--requests", "6", "--slots", "3", "--prompt-len", "12",
                "--max-new", "12", "--max-seq", "64"] + sys.argv[1:]
    main()
