"""Activation-sharding hook.

Model code calls ``constrain(x, kind)`` at layer boundaries; a sharding
*policy* (installed by the train/serve step builders via ``use_policy``)
maps the semantic kind to a ``with_sharding_constraint``.  Outside any
policy (CPU smoke tests, examples) it is a no-op, keeping model code
mesh-agnostic.

Kinds used by the model zoo:
  residual    (B, S, D)      ffn_hidden (B, S, F)      logits   (B, S, V)
  heads_q     (B, H, S, Dh)  heads_kv   (B, Hk, S, Dh) kv_cache (B, S, Hk, Dh)
  moe_buf     (E, C, D)      moe_hidden (E, C, F)      rec_state (B, D)
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Callable

import jax

Policy = Callable[[jax.Array, str], jax.Array]

_POLICY: contextvars.ContextVar[Policy | None] = contextvars.ContextVar(
    "repro_act_sharding_policy", default=None
)


@contextlib.contextmanager
def use_policy(policy: Policy | None):
    token = _POLICY.set(policy)
    try:
        yield
    finally:
        _POLICY.reset(token)


def mesh() -> jax.sharding.Mesh | None:
    """The mesh the code being traced is partitioned over: the installed
    policy's ``mesh`` attribute, None outside any policy.  Ops XLA cannot
    partition (the Pallas kernels) run per shard of it; a policy that
    does not name its mesh leaves them no way to, so that raises."""
    policy = _POLICY.get()
    if policy is None:
        return None
    m = getattr(policy, "mesh", None)
    if m is None:
        raise ValueError("the installed sharding policy has no mesh: "
                         "per-shard kernels cannot be placed")
    return m


def constrain(x: jax.Array, kind: str) -> jax.Array:
    policy = _POLICY.get()
    if policy is None:
        return x
    return policy(x, kind)
