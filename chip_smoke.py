#!/usr/bin/env python3
"""Smoke run of the serving main path on one TPU chip: yi-6b at full width.

Everything runs in this one process (a chip belongs to one process at a
time).  Each phase prints one JSON line:

  device   platform, kind, count and HBM limit — anything but a TPU fails;
  params   yi-6b's bf16 weights built on the device from ``--seed``;
  serve    per executor mode: a ServeEngine (4 slots, max_seq 2048,
           buckets 128/2048) compiles its steps (set-up time) and serves
           6 requests of mixed prompt lengths; the executors its plans
           resolve must be the ones that ran, with no runtime rebind,
           and the compiled prefill must contain a Pallas kernel;
  correct  per mode, one request's logits from the engine's own prefill
           and decode steps against a float32 reference forward;
  block    ``execute_block_plan()`` at m=max_seq in the planned mode.

``--chips 4`` runs only the sharded-training check instead: 3 train
steps of a depth-cut yi-6b on a 2x2 ("data", "model") mesh, with its
Pallas kernels run per shard, compared with the same steps on one chip.

The last line is ``{"ok": true, "device": {...}}`` only when every phase
passed; any failure exits non-zero.  Run: ``python3 chip_smoke.py``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import pathlib
import sys
import time
import traceback

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import obs  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.ftl import executor_block  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import Request, ServeEngine  # noqa: E402
from repro.models import model as M  # noqa: E402

ARCH = "yi-6b"
# 'off' is the hand-sequenced baseline, 'auto' the FTL-planned bindings
# (yi-6b's plan splits its MLP, so 'auto' runs the XLA partial MLP), and
# 'fused' forces the paper's fused-MLP Pallas kernel under the same plan
MODES = ("off", "auto", "fused")
PLANNED_MODE = "auto"

# Served (bf16 weights and activations, f32 accumulation) against the
# float32 reference: per generated position, the L2 error of the logit
# vector relative to the reference's L2 norm.  Rounding each matmul input
# to bf16 (unit roundoff 2**-9) compounds over 32 residual layers to
# about a percent of the logits; 5% leaves room for that, while a wrong
# mask, RoPE position or KV page changes the attention output itself and
# moves the logits by a large fraction of their norm.
LOGIT_RTOL = 0.05

# the --chips 4 check trains a yi-6b cut to 2 layers: its train state
# (bf16 weights, float32 Adam moments: 10 bytes a parameter, 8.7e9 bytes
# in all) leaves the one-chip reference step room in 16 GB for gradients
# and activations, and each further layer would add 1.73e9 bytes of state
# while testing nothing the second layer does not (the layer scan already
# runs more than once)
TRAIN_LAYERS = 2
# every stage of the train step through the plan's executors, the MLP
# through the fused Pallas kernel: on the mesh each kernel runs per shard
TRAIN_MODE = "fused"

# sharded vs one-chip training losses: the same bf16 program partitioned
# over 4 chips sums in another order, which moves a loss of ~11 in its
# third significant digit at most
LOSS_RTOL = 5e-3


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def device_info() -> dict:
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()),
            "bytes_limit": stats.get("bytes_limit")}


def _peak_bytes() -> int | None:
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def build_params(cfg, seed: int) -> tuple[dict, dict]:
    """yi-6b's parameter tree built on the device (``init_params`` is
    jitted, so every leaf is written straight in bf16)."""
    t0 = time.perf_counter()
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    return params, {
        "phase": "params", "arch": cfg.name, "n_layers": cfg.n_layers,
        "d_model": cfg.d_model, "dtype": cfg.dtype,
        "bytes": sum(x.nbytes for x in jax.tree.leaves(params)),
        "peak_bytes_in_use": _peak_bytes(),
        "setup_s": time.perf_counter() - t0,
    }


def make_requests(cfg, buckets, n: int, max_new: int, seed: int
                  ) -> list[Request]:
    """``n`` requests, alternating between the smallest and the largest
    bucket, with prompt lengths drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    lo, hi = buckets[0], buckets[-1]
    reqs = []
    for i in range(n):
        plen = int(rng.integers(max(1, lo // 2), lo + 1) if i % 2 == 0
                   else rng.integers(lo + 1, hi - max_new))
        prompt = rng.integers(2, cfg.vocab_size, size=plen).astype(np.int32)
        reqs.append(Request(i, prompt, max_new))
    return reqs


_COUNTERS = ("ftl_dispatch_total", "ftl_requalified_total",
             "ftl_xla_fallback_total")


def _counts() -> dict[str, dict[tuple, float]]:
    """The executor counters, keyed by their sorted label items."""
    snap = obs.collect()
    return {name: {tuple(sorted(lbl.items())): v
                   for lbl, v in snap[name]["samples"]}
            for name in _COUNTERS}


def _delta(before: dict, after: dict, name: str) -> list[tuple[dict, float]]:
    return [(dict(key), v - before[name].get(key, 0.0))
            for key, v in after[name].items()]


def _ran(before: dict, after: dict) -> tuple[dict, dict]:
    """Between two counter snapshots: the executors dispatched per phase
    and stage kind, and the count of each kind of runtime rebind."""
    ran: dict[str, dict[str, set[str]]] = {}
    for lbl, v in _delta(before, after, "ftl_dispatch_total"):
        if v > 0:
            ran.setdefault(lbl["phase"], {}).setdefault(
                lbl["kind"], set()).add(lbl["executor"])
    rebinds = {name: sum(v for _, v in _delta(before, after, name))
               for name in _COUNTERS[1:]}
    return ran, rebinds


def _sorted(ran: dict) -> dict:
    return {ph: {k: sorted(v) for k, v in kinds.items()}
            for ph, kinds in ran.items()}


def _expected_prefill(eng) -> dict[str, set[str]]:
    """Per stage kind, the executors every bucket's prefill plan binds."""
    want: dict[str, set[str]] = {}
    for b, plan in eng.prefill_plans.items():
        for kind, name in executor_block.resolved_executors(
                plan, m=b).items():
            want.setdefault(kind, set()).add(name)
    return want


def serve_mode(cfg, params, mode: str, *, slots: int, max_seq: int,
               buckets: tuple[int, ...], n_requests: int, max_new: int,
               seed: int, on_tpu: bool = True) -> tuple[ServeEngine, dict]:
    """Build, compile and run one ServeEngine in ``mode``; check that the
    executors its plans resolve are the ones that ran."""
    cfg = dataclasses.replace(cfg, ftl_mode=mode)
    before = _counts()
    t0 = time.perf_counter()
    eng = ServeEngine(cfg, params, batch_slots=slots, max_seq=max_seq,
                      buckets=buckets)
    plan_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    eng.warmup_compile()
    compile_s = time.perf_counter() - t0

    reqs = make_requests(cfg, eng.buckets, n_requests, max_new, seed)
    t0 = time.perf_counter()
    done = eng.run(reqs, {})
    wall_s = time.perf_counter() - t0
    if len(done) != n_requests:
        raise RuntimeError(f"{mode}: served {len(done)}/{n_requests}")

    rep = eng.plan_report()
    ran, rebinds = _ran(before, _counts())
    want = _expected_prefill(eng)
    if ran.get("prefill") != want:
        raise AssertionError(f"{mode}: prefill ran {ran.get('prefill')}, "
                             f"its plans resolve {want}")
    if any(rebinds.values()):
        raise AssertionError(f"{mode}: runtime rebinds {rebinds}")
    if on_tpu:
        # what each mode must run on a TPU, whatever the planner chose
        must = {"attention": "pallas_flash_attention"}
        if mode == "off":
            must.update(gemm="xla_gemm", mlp="xla_unfused_mlp")
        if mode == "fused":
            must["mlp"] = "pallas_fused_mlp"
        for kind, name in must.items():
            if want[kind] != {name}:
                raise AssertionError(
                    f"{mode}: prefill {kind} resolved {want[kind]}, "
                    f"expected {name}")
        b = eng.buckets[-1]
        hlo = eng.prefill_fn(b, eng.prefill_plans[b]).lower(
            params, {"tokens": jnp.zeros((1, b), jnp.int32)},
            jnp.int32(b - 1)).compile().as_text()
        if "tpu_custom_call" not in hlo:
            raise AssertionError(f"{mode}: no Pallas kernel in the "
                                 f"compiled m={b} prefill")

    return eng, {
        "phase": "serve", "mode": mode, "slots": slots, "max_seq": max_seq,
        "buckets": list(eng.buckets), "requests": len(done),
        "prompt_lens": [len(r.prompt) for r in reqs],
        "tokens_served": sum(len(r.out) for r in done),
        "wall_s": wall_s, "plan_s": plan_s, "compile_s": compile_s,
        "executors": {"prefill": rep["prefill"]["executors"],
                      "decode": rep["decode"]["executors"]},
        "ran": _sorted(ran), "rebinds": rebinds, "peak_bytes_in_use": _peak_bytes(),
    }


def served_logits(eng: ServeEngine, prompt: np.ndarray, n_new: int
                  ) -> tuple[np.ndarray, list[int]]:
    """Greedy-decode ``prompt`` in slot 0 through the engine's own jitted
    prefill and paged decode steps; returns the float32 logits of every
    generated position (n_new, vocab) and the generated tokens."""
    plen = len(prompt)
    bucket = M.bucket_m(plen, eng.buckets)
    padded = np.zeros(bucket, np.int32)
    padded[:plen] = prompt
    logits, cache1 = eng.prefill_fn(bucket, eng.prefill_plans[bucket])(
        eng.params, {"tokens": jnp.asarray(padded)[None]},
        jnp.int32(plen - 1))
    eng.kv.write_prefill(0, cache1, bucket)
    rows = [logits[0, -1]]
    toks = [int(jnp.argmax(rows[-1]))]
    zeros = np.zeros(eng.slots, np.int32)
    for pos in range(plen, plen + n_new - 1):
        eng.kv.allocate(0, pos + 1)
        wblk, woff = eng.kv.write_coords(0, pos)
        tok, posv, wb, wo = (zeros.copy() for _ in range(4))
        tok[0], posv[0], wb[0], wo[0] = toks[-1], pos, wblk, woff
        logits, eng.kv.pool = eng.decode_fn(
            eng.params, eng.kv.pool, eng.kv.table_array(),
            jnp.asarray(tok)[:, None], jnp.asarray(posv), jnp.asarray(wb),
            jnp.asarray(wo))
        rows.append(logits[0, 0])
        toks.append(int(jnp.argmax(rows[-1])))
    eng.kv.release(0)
    return np.asarray(jnp.stack(rows).astype(jnp.float32)), toks


@functools.partial(jax.jit, static_argnums=0)
def reference_logits(cfg, params, tokens, positions):
    """Float32 logits of a plain forward over ``tokens`` (T,) at
    ``positions`` (T,): RMSNorm, half-split RoPE, GQA attention and the
    gated MLP (``repro.kernels.ref``), computed at the highest matmul
    precision.  One layer's weights are upcast at a time inside the
    scan, and the head one vocab chunk at a time, so the reference fits
    next to the bf16 model on the chip."""
    if not (cfg.family == "dense" and cfg.norm == "rmsnorm"
            and cfg.mlp_gated and not (cfg.qkv_bias or cfg.mlp_bias
                                       or cfg.tie_embeddings)):
        raise ValueError(f"no reference forward for {cfg.name}")
    f32 = jnp.float32
    t = tokens.shape[0]
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    half = dh // 2
    freqs = cfg.rope_theta ** (-jnp.arange(half, dtype=f32) / half)
    ang = positions.astype(f32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)

    def rms(x, scale):
        return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
                * scale.astype(f32))

    def rope(x):                      # (T, heads, dh)
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)

    def layer(x, p):
        p = jax.tree.map(lambda a: a.astype(f32), p)
        a, m = p["attn"], p["mlp"]
        y = rms(x, p["ln1"]["scale"])
        q = rope((y @ a["wq"]["w"]).reshape(t, h, dh))
        k = rope((y @ a["wk"]["w"]).reshape(t, hk, dh))
        v = (y @ a["wv"]["w"]).reshape(t, hk, dh)
        o = ref.attention(q.transpose(1, 0, 2)[None],
                          k.transpose(1, 0, 2)[None],
                          v.transpose(1, 0, 2)[None], causal=True)[0]
        x = x + o.transpose(1, 0, 2).reshape(t, h * dh) @ a["wo"]["w"]
        y = rms(x, p["ln2"]["scale"])
        return x + ref.mlp(y, m["w1"]["w"], m["w2"]["w"], m["wg"]["w"],
                           act=cfg.mlp_act), None

    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tok"][tokens].astype(f32)
        x, _ = jax.lax.scan(layer, x, params["layers"]["pos0"])
        x = rms(x, params["final_norm"]["scale"])
        w = params["lm_head"]["w"]
        n_chunks = 8 if w.shape[1] % 8 == 0 else 1
        c = w.shape[1] // n_chunks
        chunks = jax.lax.map(
            lambda i: x @ jax.lax.dynamic_slice_in_dim(
                w, i * c, c, axis=1).astype(f32),
            jnp.arange(n_chunks))
    return chunks.transpose(1, 0, 2).reshape(t, -1)


def rel_err(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per-row L2 error of ``got`` relative to ``want``'s L2 norm."""
    return (np.linalg.norm(got - want, axis=-1)
            / np.linalg.norm(want, axis=-1))


def check_logits(eng: ServeEngine, cfg, mode: str, *, prompt_len: int,
                 n_new: int, seed: int) -> dict:
    """One request's served logits against the float32 reference
    (``cfg`` is the mode-independent config, so every mode shares one
    compiled reference)."""
    rng = np.random.default_rng(seed + 1)
    prompt = rng.integers(2, cfg.vocab_size, size=prompt_len
                          ).astype(np.int32)
    got, toks = served_logits(eng, prompt, n_new)
    seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
    pos = np.arange(len(seq), dtype=np.int32)
    want = np.asarray(reference_logits(cfg, eng.params, jnp.asarray(seq),
                                       jnp.asarray(pos)))[prompt_len - 1:]
    err = rel_err(got, want)
    # the same comparison against a reference whose generated tokens sit
    # one position late: what a decode RoPE/cache-position bug would serve
    late = pos + (pos >= prompt_len)
    want_late = np.asarray(reference_logits(
        cfg, eng.params, jnp.asarray(seq), jnp.asarray(late)))[prompt_len - 1:]
    late_err = float(rel_err(got, want_late)[1:].max())
    rec = {
        "phase": "correct", "mode": mode, "prompt_len": prompt_len,
        "new_tokens": n_new, "max_rel_err": float(err.max()),
        "rel_err": [float(e) for e in err], "tolerance": LOGIT_RTOL,
        "late_position_rel_err": late_err,
        "token_agreement": float(np.mean(np.argmax(want, -1) == toks)),
    }
    if not np.isfinite(got).all() or err.max() > LOGIT_RTOL:
        raise AssertionError(f"{mode}: served logits off the reference "
                             f"(max rel err {err.max():.4g} > {LOGIT_RTOL})")
    if late_err <= LOGIT_RTOL:
        raise AssertionError(f"{mode}: the tolerance {LOGIT_RTOL} does not "
                             f"reject a one-position slip ({late_err:.4g})")
    return rec


def block_phase(eng: ServeEngine) -> dict:
    entry = eng.execute_block_plan()
    if entry is None or not entry["finite"]:
        raise AssertionError(f"execute_block_plan: {entry}")
    return {"phase": "block", "mode": eng.cfg.ftl_mode, "m": eng.max_seq,
            "executors": entry["executors"], "finite": entry["finite"],
            "ms": entry["ms"]}


def run_one_chip(args) -> None:
    cfg = get_config(ARCH)
    params, rec = build_params(cfg, args.seed)
    emit(rec)
    for mode in MODES:
        eng, rec = serve_mode(
            cfg, params, mode, slots=4, max_seq=2048, buckets=(128, 2048),
            n_requests=6, max_new=16, seed=args.seed)
        emit(rec)
        emit(check_logits(eng, cfg, mode, prompt_len=1000, n_new=16,
                          seed=args.seed))
        if mode == PLANNED_MODE:
            emit(block_phase(eng))
        del eng


def train_parity(cfg, *, devices, n_layers: int, batch: int, seq: int,
                 steps: int, seed: int, on_tpu: bool = True) -> dict:
    """``steps`` train steps in ``TRAIN_MODE`` on a 2x2 ("data", "model")
    mesh of ``devices`` against the same steps on ``devices[0]`` alone;
    both must dispatch the same executors, with no runtime rebind."""
    from jax.sharding import Mesh

    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.optim import OptConfig
    from repro.train import steps as S

    cfg = dataclasses.replace(cfg, n_layers=n_layers, ftl_mode=TRAIN_MODE)
    opt = OptConfig(peak_lr=1e-3, warmup_steps=1, decay_steps=steps)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                  global_batch=batch, seq_len=seq,
                                  seed=seed, kind="random"))
    batches = [{k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
               for i in range(steps)]
    key = jax.random.PRNGKey(seed)
    init = functools.partial(S.init_train_state, cfg)

    def run(step, state, batch_sharding):
        """Compile ``step`` (a jitted train step) and take every step with
        it; returns the losses, what ran, the rebinds and the HLO."""
        placed = [jax.device_put(b, batch_sharding) for b in batches]
        before = _counts()
        compiled = step.lower(state, placed[0]).compile()
        losses = []
        for b in placed:
            state, m = compiled(state, b)
            losses.append(float(m["loss"]))
        return state, losses, *_ran(before, _counts()), compiled.as_text()

    one = jax.sharding.SingleDeviceSharding(devices[0])
    t0 = time.perf_counter()
    state = jax.jit(init, out_shardings=one)(key)
    state, single, ran_one, rebinds_one, _ = run(
        jax.jit(S.make_train_step(cfg, None, opt), donate_argnums=0), state,
        one)
    single_s = time.perf_counter() - t0
    one_chip_peak = _peak_bytes()
    del state

    mesh = Mesh(np.asarray(devices[:4]).reshape(2, 2), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    batch_sds = {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}
    in_sh, out_sh = S.train_step_shardings(
        cfg, mesh, S.train_state_shapes(cfg), batch_sds)
    t0 = time.perf_counter()
    state = jax.jit(init, out_shardings=in_sh[0])(key)
    step = jax.jit(S.make_train_step(cfg, mesh, opt), in_shardings=in_sh,
                   out_shardings=out_sh, donate_argnums=0)
    state, sharded, ran, rebinds, hlo = run(step, state, in_sh[1])
    sharded_s = time.perf_counter() - t0
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in devices[:4]]
    state_bytes = sum(x.nbytes for x in jax.tree.leaves(state))
    err = [abs(a - b) / abs(b) for a, b in zip(sharded, single)]
    rec = {"phase": "train_parity", "arch": cfg.name, "n_layers": n_layers,
           "layers_cut_from": get_config(ARCH).n_layers, "mesh": [2, 2],
           "mode": TRAIN_MODE, "batch": batch, "seq": seq,
           "losses_sharded": sharded, "losses_one_chip": single,
           "max_rel_err": max(err), "tolerance": LOSS_RTOL,
           "ran_sharded": _sorted(ran), "ran_one_chip": _sorted(ran_one),
           "rebinds": {k: rebinds[k] + rebinds_one[k] for k in rebinds},
           "state_bytes": state_bytes, "bytes_in_use": in_use,
           "one_chip_peak_bytes": one_chip_peak, "one_chip_s": single_s,
           "sharded_s": sharded_s}
    if max(err) > LOSS_RTOL or not np.isfinite(sharded).all():
        raise AssertionError(f"sharded losses {sharded} vs one-chip "
                             f"{single}")
    if ran != ran_one or any(rec["rebinds"].values()):
        raise AssertionError(f"sharded step ran {rec['ran_sharded']}, one "
                             f"chip {rec['ran_one_chip']}; runtime rebinds "
                             f"{rec['rebinds']}")
    if on_tpu:
        kernels = {name for kinds in ran.values() for names in kinds.values()
                   for name in names if name.startswith("pallas_")}
        if not {"pallas_flash_attention", "pallas_fused_mlp"} <= kernels:
            raise AssertionError(f"sharded step ran {rec['ran_sharded']}")
        if "tpu_custom_call" not in hlo:
            raise AssertionError("no Pallas kernel in the compiled sharded "
                                 "train step")
        if None in in_use or max(in_use) > 0.5 * state_bytes:
            raise AssertionError(f"state not spread over the mesh: {in_use} "
                                 f"bytes in use for {state_bytes} of state")
    return rec


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-training check")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = device_info()
    emit({"phase": "device", **dev})
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"]}
    if dev["platform"] != "tpu":
        emit({"ok": False, "error": "JAX found no TPU", "device": device})
        return 1
    if dev["count"] < args.chips:
        emit({"ok": False, "error": f"{args.chips} chips asked, "
              f"{dev['count']} found", "device": device})
        return 1
    try:
        emit({"phase": "compile_cache", "dir": enable_compile_cache()})
        if args.chips == 4:
            emit(train_parity(get_config(ARCH), devices=jax.devices(),
                              n_layers=TRAIN_LAYERS, batch=8, seq=512,
                              steps=3, seed=args.seed))
        else:
            run_one_chip(args)
    except Exception as e:  # any phase failing fails the run
        traceback.print_exc()
        emit({"ok": False, "error": repr(e)[:500], "device": device})
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
