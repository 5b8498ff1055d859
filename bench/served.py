"""The system under test: the program's ``ServeEngine`` built from a
configuration file, fed the benchmark's own weights.

This is the only module of the benchmark that knows the program's
configuration record and parameter layout.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from bench import weights
from bench.spec import Spec

# the cells run the FTL-planned path, the one this repository exists for
FTL_MODE = "auto"
# the program's names for the configuration files' activations
_PROGRAM_ACTS = {"silu": "silu", "gelu_pytorch_tanh": "gelu"}
# an end-of-sequence id no vocabulary produces: every output runs to the
# length the traffic drew for it
NO_EOS = -1


def model_config(conf: dict):
    """The program's ModelConfig for configuration file ``conf``."""
    from repro.configs.base import ModelConfig

    spec = Spec.from_config(conf)
    if spec.attn_bias != spec.mlp_bias:
        raise ValueError(f"{conf['name']}: the program ties the attention "
                         "output bias to mlp_bias; configurations must "
                         "give attention_bias == mlp_bias")
    return ModelConfig(
        name=conf["name"], family="dense", n_layers=spec.layers,
        d_model=spec.d, n_heads=spec.heads, n_kv_heads=spec.kv_heads,
        d_ff=spec.ff, vocab_size=spec.vocab, head_dim=spec.head_dim,
        mlp_act=_PROGRAM_ACTS[spec.act], mlp_gated=spec.gated,
        mlp_bias=spec.mlp_bias, qkv_bias=spec.attn_bias, norm=spec.norm,
        rope_theta=spec.theta, tie_embeddings=False, dtype=spec.dtype,
        ftl_mode=FTL_MODE)


def program_params(spec: Spec, w: dict) -> dict:
    """The benchmark's weights ``w`` nested as the program's parameter
    tree (the same arrays, no copy)."""
    def lin(name, bias=None):
        p = {"w": w[name]}
        if bias is not None and bias in w:
            p["b"] = w[bias]
        return p

    def norm(prefix):
        p = {"scale": w[f"{prefix}_scale"]}
        if f"{prefix}_bias" in w:
            p["bias"] = w[f"{prefix}_bias"]
        return p

    mlp = {"w1": lin("w1", "b1"), "w2": lin("w2", "b2")}
    if spec.gated:
        mlp["wg"] = lin("wg")
    layer = {
        "ln1": norm("ln1"), "ln2": norm("ln2"), "mlp": mlp,
        "attn": {"wq": lin("wq", "bq"), "wk": lin("wk", "bk"),
                 "wv": lin("wv", "bv"), "wo": lin("wo", "bo")},
    }
    return {"embed": {"tok": w["embed"]}, "layers": {"pos0": layer},
            "final_norm": norm("final"), "lm_head": {"w": w["lm_head"]}}


def reached_buckets(prompt_lens, max_seq: int, block_size: int
                    ) -> tuple[int, ...]:
    """The rungs of the engine's default bucket ladder that the prompts
    of lengths ``prompt_lens`` land in: each prompt lands in the same
    rung as on the whole ladder, and no other rung is compiled."""
    from repro.launch.serve import _default_buckets
    from repro.models.model import bucket_m

    ladder = _default_buckets(max_seq, block_size)
    return tuple(sorted({bucket_m(int(n), ladder) for n in prompt_lens}))


def build_engine(cfg, params, *, slots: int, max_seq: int,
                 buckets: tuple[int, ...]):
    from repro.launch.serve import ServeEngine

    return ServeEngine(cfg, params, batch_slots=slots, max_seq=max_seq,
                       eos_id=NO_EOS, buckets=buckets)


@dataclasses.dataclass
class System:
    """The system under test, set up for one cell: the benchmark's
    weights and the engine that serves with them."""
    spec: Spec
    w: dict
    eng: object
    setup: dict[str, float]     # seconds of each part of set-up


def start(cell: dict, seed: int, prompt_lens, block_size: int) -> System:
    """Everything a cell's window needs, set up the same way for the
    timed runs and for the readings limits and rates are set from:
    weights drawn from ``seed`` on the device, a ``ServeEngine`` over the
    bucket rungs ``prompt_lens`` reach, and every shape it will use
    compiled or read from the persistent compile cache."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import Request

    enable_compile_cache()
    # small programs too: every run after the first reads all of them back
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    conf, traffic, params = cell["config"], cell["traffic"], cell["params"]
    spec = Spec.from_config(conf)
    setup = {}
    t = time.perf_counter()
    w = weights.make(spec, weights.key_of(seed))
    jax.block_until_ready(w)
    setup["weights_s"] = time.perf_counter() - t
    t = time.perf_counter()
    buckets = reached_buckets(prompt_lens, traffic["max_seq"], block_size)
    eng = build_engine(model_config(conf), program_params(spec, w),
                       slots=params["slots"], max_seq=traffic["max_seq"],
                       buckets=buckets)
    setup["engine_s"] = time.perf_counter() - t
    setup.update(warm(eng, lambda rid, p, n: Request(rid, p, n)))
    return System(spec, w, eng, setup)


def reseed(system: System, seed: int) -> None:
    """New weights from ``seed`` in the engine ``system`` holds, with the
    old ones freed first."""
    import jax

    system.eng.params = system.w = None
    gc.collect()
    system.w = weights.make(system.spec, weights.key_of(seed))
    jax.block_until_ready(system.w)
    system.eng.params = program_params(system.spec, system.w)


def warm(eng, make_request) -> dict[str, float]:
    """Compile every shape the window will use: each bucket's prefill and
    the decode step (``warmup_compile``), then one request per bucket
    through ``eng.run`` itself, which also loads the page writer for each
    page count and the small host-side programs of admission and
    sampling.  Returns the seconds each part took."""
    t0 = time.perf_counter()
    eng.warmup_compile()
    t1 = time.perf_counter()
    rng = np.random.default_rng(0)
    # a prompt that fills its bucket, short of max_seq by room to decode
    sizes = [min(b, eng.max_seq - 8) for b in eng.buckets]
    reqs = [make_request(-1 - i, rng.integers(2, eng.cfg.vocab_size, size=n)
                         .astype(np.int32), 3)
            for i, n in enumerate(sizes)]
    eng.run(reqs, {})
    t2 = time.perf_counter()
    return {"compile_s": t1 - t0, "warm_run_s": t2 - t1}


def free_slots(eng) -> None:
    """Evict whatever a window left in the engine's slots (a window ends
    by an exception in the middle of serving)."""
    for i, r in enumerate(eng.active):
        if r is not None:
            eng._evict(i)
