"""The FLOP and byte counts against the benchmark's own reference: every
matmul the reference traces, counted from its jaxpr."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops, reference, weights
from bench.spec import Spec

SMALL = Spec(layers=2, d=64, heads=4, kv_heads=2, head_dim=16, ff=96,
             vocab=512, act="silu", gated=True, attn_bias=False,
             mlp_bias=False, norm="rmsnorm", eps=1e-6, theta=1e4)
SPECS = [SMALL, dataclasses.replace(
    SMALL, kv_heads=1, gated=False, attn_bias=True, mlp_bias=True,
    act="gelu_pytorch_tanh", norm="layernorm")]


def dot_flops(jaxpr) -> int:
    """2 x output elements x contracted size of every dot_general,
    multiplied through the trip counts of scans (and lax.map)."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (ca, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            k = int(np.prod([lhs[i] for i in ca]))
            total += 2 * int(np.prod(eqn.outvars[0].aval.shape)) * k
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n = eqn.params.get("length", 1) if eqn.primitive.name == "scan" \
                else 1
            total += n * dot_flops(sub)
    return total


def _weights(spec):
    return weights.make(spec, weights.key_of(0))


@pytest.mark.parametrize("spec", SPECS, ids=["gqa_gated", "mqa_gelu"])
def test_layer_matmuls_match_the_reference(spec):
    t = reference.QBLOCK
    w = _weights(spec)
    p = {k: w[k][0] for k in spec.layer_shapes()}
    x = jnp.zeros((t, spec.d), jnp.float32)
    jx = jax.make_jaxpr(lambda x, p: reference._layer(
        spec, x, p, jnp.arange(t), False))(x, p)
    # the reference attends densely under a mask; the count needs only
    # the causal pairs
    assert dot_flops(jx.jaxpr) == (flops.layer_matmul_flops(spec, t)
                                   + flops.attention_flops(spec, t, False))


@pytest.mark.parametrize("spec", SPECS, ids=["gqa_gated", "mqa_gelu"])
def test_prefill_counts_the_head_once(spec):
    """Logits at one row: every layer's matmuls plus the head once, as
    the prefill count has it (with dense attention in the reference)."""
    t = reference.QBLOCK
    w = _weights(spec)

    def traced(rows):
        return dot_flops(jax.make_jaxpr(lambda w, tok, r: reference.logits_at(
            spec, w, tok, r))(w, jnp.zeros(t, jnp.int32),
                              jnp.zeros(rows, jnp.int32)).jaxpr)

    dense = spec.layers * (flops.attention_flops(spec, t, False)
                           - flops.attention_flops(spec, t))
    assert traced(1) == flops.prefill_flops(spec, t) + dense
    # the head is the only part that grows with the rows read out
    assert traced(3) - traced(1) == 2 * flops.head_flops(spec)


def test_decode_bytes_and_bound():
    spec = SMALL
    f, b = flops.decode_step(spec, [10, 20])
    w = _weights(spec)
    nbytes = sum(v.nbytes for k, v in w.items() if k != "embed")
    assert b == nbytes + 2 * 64 * 2 + 30 * spec.kv_bytes_per_token()
    assert f == (spec.layers * (flops.layer_matmul_flops(spec, 2)
                                + 4 * 4 * 16 * 30) + flops.head_flops(spec, 2))
    peak = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
    t, bound = flops.least_time_s(f, b, peak)
    assert bound == "bandwidth" and t == pytest.approx(b / 1e9)
