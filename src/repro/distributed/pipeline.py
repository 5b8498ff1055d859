"""GPipe-style pipeline parallelism over a ``pipe`` mesh axis (opt-in).

For deployments where TP×FSDP leaves too little per-device memory (very
deep models at small world sizes), layers split into S stages placed on the
``pipe`` axis; microbatches stream through with ``lax.ppermute`` rotations.
Classic GPipe schedule: S + M - 1 ticks for M microbatches, bubble fraction
(S-1)/(S+M-1).

Implemented with shard_map: every device runs its stage each tick, then
activations rotate one stage forward.  Finished microbatches accumulate on
the last stage; a final psum broadcasts them (all other stages contribute
zeros).  Self-contained — used by tests and launch/train.py ``--pipeline``;
the production layout for the assigned cells is TP×FSDP (DESIGN.md §6).
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


Params = dict[str, Any]


def stage_params(params_per_layer: list[Params], n_stages: int) -> Params:
    """Stack per-layer param trees into (S, layers_per_stage, ...) leaves."""
    n = len(params_per_layer)
    assert n % n_stages == 0, (n, n_stages)
    per = n // n_stages
    stages = []
    for s in range(n_stages):
        chunk = params_per_layer[s * per:(s + 1) * per]
        stages.append(jax.tree.map(lambda *xs: jnp.stack(xs), *chunk))
    return jax.tree.map(lambda *xs: jnp.stack(xs), *stages)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_stages + n_micro - 1)


def pipeline_forward(
    stage_fn: Callable[[Params, jax.Array], jax.Array],
    staged_params: Params,      # leaves (S, per_stage, ...), sharded over pipe
    x: jax.Array,               # (M, micro_batch, ...) microbatched input
    *,
    mesh: Mesh,
    axis: str = "pipe",
) -> jax.Array:
    """Run the GPipe schedule; returns (M, micro_batch, ...) outputs.

    ``stage_fn(stage_params, act) -> act`` applies one stage's layers;
    activations must keep a fixed shape across stages.
    """
    n_stages = mesh.shape[axis]
    m = x.shape[0]

    def body(params, xs):
        params = jax.tree.map(lambda l: l[0], params)   # drop stage dim
        stage = jax.lax.axis_index(axis)
        queue = jax.lax.all_gather(xs, axis, tiled=True)    # (M, mb, ...)
        fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        zero = jnp.zeros_like(queue[0])
        out0 = jnp.zeros_like(queue)

        def tick(t, carry):
            cur, out = carry
            feed = jax.lax.dynamic_index_in_dim(
                queue, jnp.clip(t, 0, m - 1), keepdims=False)
            cur = jnp.where(stage == 0, feed, cur)
            y = stage_fn(params, cur)
            done = t - (n_stages - 1)
            write = (stage == n_stages - 1) & (done >= 0)
            out = jnp.where(
                write,
                jax.lax.dynamic_update_index_in_dim(
                    out, y, jnp.clip(done, 0, m - 1), 0),
                out)
            cur = jax.lax.ppermute(y, axis, fwd)
            return cur, out

        _, out = jax.lax.fori_loop(0, m + n_stages - 1, tick, (zero, out0))
        return jax.lax.psum(out, axis)   # only the last stage wrote

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), staged_params), P(axis)),
        out_specs=P(),
    )
    return fn(staged_params, x)
