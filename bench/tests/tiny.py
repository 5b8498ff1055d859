"""A tiny cell for running the harness on the CPU: the bench directory
copied into a temporary root, with a 2-layer configuration, a short mix
and a cell of their own added beside the real ones."""
from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parent.parent

CONFIG = {
    "name": "tiny", "source": "a CPU test size of the yi-6b layout",
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "vocab_size": 512, "hidden_act": "silu", "mlp_gated": True,
    "attention_bias": False, "mlp_bias": False, "norm": "rmsnorm",
    "norm_eps": 1e-6, "rope_theta": 10000.0, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16",
}
TRAFFIC = {
    "arrivals": "poisson", "schedule_seed": 0,
    "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                   "min": 8, "max": 100},
    "output_len": {"dist": "uniform", "min": 4, "max": 8},
    "max_seq": 128,
}
CELL = {"slots": 3, "rate_per_s": 20.0, "sample_tokens": 400, "trace_s": 1,
        "limits": {"max_logit_gap": 0.1}}


def make_root(tmp: pathlib.Path, config: dict = CONFIG,
              traffic: dict = TRAFFIC, cell: dict = CELL) -> pathlib.Path:
    """A root holding a copy of bench/ and a BENCHMARK.json whose only
    cell is ``tiny.open``."""
    shutil.copytree(BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for sub, name, body in (("configs", config["name"], config),
                            ("traffic", "tiny_open", traffic),
                            ("cells", "tiny.open", cell)):
        (tmp / "bench" / sub / f"{name}.json").write_text(json.dumps(body))
    bm = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bm["workloads"] = [{"name": "tiny.open", "config": config["name"],
                        "traffic": "tiny_open", "chips": 1, "why": "test"}]
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.open"]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bm))
    return tmp


def cpu_device(chips: int) -> dict:
    """Stands in for run.device_check: the harness's look for a chip."""
    import jax

    return {"platform": jax.devices()[0].platform,
            "kind": jax.devices()[0].device_kind, "count": 1}
