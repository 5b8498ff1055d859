"""Training driver.

Single-host it runs real steps on whatever devices exist (CPU smoke:
``--arch yi-6b --reduced``); on a pod slice the same code path pjits over
the production mesh.  Fault tolerance wiring: auto-resume from the latest
checkpoint, async saves every N steps, SIGTERM-preemption checkpointing,
straggler flagging — all via runtime.TrainLoop.

Examples
--------
CPU end-to-end (reduced config, synthetic bigram data)::

  python -m repro.launch.train --arch yi-6b --reduced --steps 100 \\
      --batch 8 --seq 128 --ckpt-dir /tmp/ck

Production (pod slice)::

  python -m repro.launch.train --arch qwen2-72b --steps 10000 \\
      --batch 256 --seq 4096 --mesh 16x16 --ckpt-dir gs://...
"""
from __future__ import annotations

import argparse
import dataclasses
import logging

import jax
import jax.numpy as jnp

from repro import obs as obslib
from repro.configs import get_config
from repro.core.ftl import executor_block
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.optim import OptConfig
from repro.runtime import LoopConfig, TrainLoop
from repro.runtime.monitor import HeartbeatMonitor
from repro.train import steps as S


def build(args):
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.ftl_mode:
        cfg = dataclasses.replace(cfg, ftl_mode=args.ftl_mode)

    # graph-level FTL plan of one block at the training token count.
    # This is not just a report: model.forward resolves the same cached
    # plan (per cfg/m/dtype) and executes every block through
    # registry.run_block, so the schedule logged here is the schedule the
    # train step actually runs.
    bp = M.plan_or_none(cfg, m=args.seq)
    if bp is None:
        logging.info("FTL block plan unavailable (layer-per-layer path)")
    else:
        execs = executor_block.resolved_executors(bp, m=args.seq)
        state = ("executed by every forward block"
                 if cfg.ftl_mode != "off" else
                 "report only — ftl_mode='off' runs the baseline; pass "
                 "--ftl-mode auto to execute it")
        logging.info("FTL block plan (m=%d, target=%s, %s):\n%s\n"
                     "  runtime executors: %s",
                     args.seq, bp.target.name, state, bp.summary(), execs)

    mesh = None
    in_sh = out_sh = None
    state = S.init_train_state(cfg, jax.random.PRNGKey(args.seed))
    opt = OptConfig(peak_lr=args.lr, warmup_steps=args.warmup,
                    decay_steps=args.steps)
    step = S.make_train_step(cfg, mesh, opt, accum=args.accum,
                             compress=args.compress)
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split("x"))
        axes = ("pod", "data", "model")[-len(shape):]
        mesh = make_mesh(shape, axes)
        state_sds = jax.eval_shape(lambda: state)
        batch_sds = {"tokens": jax.ShapeDtypeStruct(
            (args.batch, args.seq), jnp.int32)}
        step = S.make_train_step(cfg, mesh, opt, accum=args.accum,
                                 compress=args.compress)
        in_sh, out_sh = S.train_step_shardings(cfg, mesh, state_sds,
                                               batch_sds)
        sspec = in_sh[0]
        state = jax.device_put(state, sspec)
        jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
    else:
        jitted = jax.jit(step)

    data = SyntheticLM(DataConfig(
        vocab_size=cfg.vocab_size, global_batch=args.batch,
        seq_len=args.seq, seed=args.seed, kind=args.data))

    # liveness: stamp a heartbeat at the top of every step (make_batch is
    # the first per-step call) so peers on a shared filesystem can spot a
    # hung process even when on_metrics only fires every log_every steps
    hb = (HeartbeatMonitor(args.heartbeat_dir, jax.process_index())
          if getattr(args, "heartbeat_dir", None) else None)

    def make_batch(i: int):
        if hb is not None:
            hb.stamp()
        return {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}

    loop = TrainLoop(
        LoopConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                   ckpt_every=args.ckpt_every, log_every=args.log_every),
        jitted, make_batch, state,
        state_shardings=in_sh[0] if in_sh else None,
        on_metrics=lambda s, m: print(
            f"step {s:6d} loss {m.get('loss', float('nan')):.4f} "
            f"gnorm {m.get('grad_norm', 0):.3f} lr {m.get('lr', 0):.2e}",
            flush=True),
    )
    loop.block_plan = bp          # surfaced for tooling/tests
    loop.heartbeat = hb
    return loop


def main() -> None:
    logging.basicConfig(level=logging.INFO)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data", default="bigram", choices=["bigram", "random"])
    ap.add_argument("--mesh", default=None, help="e.g. 16x16")
    ap.add_argument("--ftl-mode", default=None,
                    choices=["off", "fused", "scan", "auto"])
    ap.add_argument("--compress", action="store_true",
                    help="int8 error-feedback gradient compression")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--obs", action="store_true",
                    help="runtime telemetry: train_step spans + straggler/"
                         "heartbeat metrics on the repro.obs registry")
    ap.add_argument("--obs-trace", default=None,
                    help="merged live+modeled Chrome-tracing JSON "
                         "(implies --obs)")
    ap.add_argument("--obs-metrics", default=None,
                    help="Prometheus text exposition written post-run "
                         "(implies --obs)")
    ap.add_argument("--heartbeat-dir", default=None,
                    help="shared dir for per-process heartbeat stamps")
    args = ap.parse_args()
    if args.obs_trace or args.obs_metrics:
        args.obs = True
    if args.obs:
        obslib.enable()
    enable_compile_cache()

    loop = build(args)
    loop.run()
    if loop.metrics_log:
        last = loop.metrics_log[-1]
        print(f"final: step {last['step']} loss {last.get('loss'):.4f}")

    # straggler summary: TrainLoop's monitor flagged these live (and the
    # obs registry carries the counters); echo them so a smoke run shows
    # the wiring without scraping
    flagged = loop.monitor.flagged_steps
    if flagged:
        worst = max(flagged, key=lambda s: s.seconds)
        print(f"stragglers: {len(flagged)} flagged step(s), worst "
              f"step {worst.step} at {worst.seconds:.3f}s "
              f"(ema {loop.monitor.ema:.3f}s)")
    elif args.obs:
        print(f"stragglers: none flagged over {len(loop.monitor.history)} "
              f"steps (ema {loop.monitor.ema:.3f}s)"
              if loop.monitor.ema is not None else "stragglers: no steps ran")

    if args.obs_trace:
        obslib.write_merged_trace(args.obs_trace, chain=loop.block_plan)
        print(f"wrote merged trace to {args.obs_trace}")
    if args.obs_metrics:
        obslib.write_prometheus(args.obs_metrics)
        print(f"wrote metrics to {args.obs_metrics}")


if __name__ == "__main__":
    main()
