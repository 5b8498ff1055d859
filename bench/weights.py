"""Random weights drawn from ``--seed`` on the device, in one jitted call.

The benchmark makes the weights itself, so the reference takes nothing
the program made; ``served.program_params`` hands the same arrays to the
program in its own layout.  Each leaf is drawn in float32 and cast inside
the one program, so the compiler fuses the cast and no float32 copy of a
stacked weight is ever held.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.spec import Spec


def key_of(seed: int) -> jax.Array:
    """A PRNG key that keeps all of a seed up to 64 bits (PRNGKey alone
    drops the bits above 32)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32),
                              (seed >> 32) % 2**32)


def _scale(name: str, shape, spec: Spec) -> tuple[str, float]:
    """(kind, scale) of one leaf: the embedding N(0, 1/d), matrices
    N(0, 1/fan_in), output
    projections also over sqrt(2 * layers); norm scales 1 + N(0, 0.1^2);
    biases N(0, 0.02^2)."""
    if "scale" in name:
        return "one_plus", 0.1
    if name.startswith("b") or name.endswith("_bias"):
        return "normal", 0.02
    if name == "embed":
        return "normal", spec.d ** -0.5
    s = shape[0] ** -0.5
    if name in ("wo", "w2"):
        s /= math.sqrt(2 * spec.layers)
    return "normal", s


@functools.partial(jax.jit, static_argnums=0)
def make(spec: Spec, key: jax.Array) -> dict[str, jax.Array]:
    dt = jnp.dtype(spec.dtype)
    shapes = {k: (spec.layers, *s) for k, s in spec.layer_shapes().items()}
    shapes.update(spec.top_shapes())
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        kind, s = _scale(name, shape if name in spec.top_shapes()
                         else shape[1:], spec)
        x = jax.random.normal(k, shape, jnp.float32) * s
        if kind == "one_plus":
            x = x + 1.0
        out[name] = x.astype(dt)
    return out
