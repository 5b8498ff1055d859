"""The benchmark's weights in the program's layout, and the program's
forward on them against the benchmark's reference, for both block kinds
the configurations use (GQA with a gated SiLU MLP and RMSNorm; MQA with
an un-gated GELU MLP, biases and LayerNorm)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, served, weights
from bench.tests.test_bench_flops import SPECS


def _conf(spec):
    return {"name": "t", "num_hidden_layers": spec.layers,
            "hidden_size": spec.d, "num_attention_heads": spec.heads,
            "num_key_value_heads": spec.kv_heads, "head_dim": spec.head_dim,
            "intermediate_size": spec.ff, "vocab_size": spec.vocab,
            "hidden_act": spec.act, "mlp_gated": spec.gated,
            "attention_bias": spec.attn_bias, "mlp_bias": spec.mlp_bias,
            "norm": spec.norm, "norm_eps": spec.eps,
            "rope_theta": spec.theta, "torch_dtype": "float32"}


@pytest.mark.parametrize("spec", SPECS, ids=["gqa_gated", "mqa_gelu"])
def test_program_layout_and_logits_match_the_reference(spec):
    from repro.models import model as M

    spec = dataclasses.replace(spec, dtype="float32")
    cfg = served.model_config(_conf(spec))
    w = weights.make(spec, weights.key_of(3))
    params = served.program_params(spec, w)
    want = jax.eval_shape(lambda k: M.init_params(cfg, k),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert jax.tree.leaves(got) == jax.tree.leaves(want)

    t = 40
    tokens = np.random.default_rng(0).integers(2, spec.vocab, t)
    logits, _ = M.prefill(cfg, params,
                          {"tokens": jnp.asarray(tokens, jnp.int32)[None]})
    pad = np.zeros(reference.QBLOCK, np.int32)
    pad[:t] = tokens
    ref = reference.logits_at(spec, w, jnp.asarray(pad),
                              jnp.asarray([t - 1], jnp.int32))
    np.testing.assert_allclose(np.asarray(logits[0, -1]),
                               np.asarray(ref[0]), rtol=2e-3, atol=2e-3)


def test_unequal_attention_and_mlp_biases_are_refused():
    spec = SPECS[1]
    conf = dict(_conf(spec), attention_bias=False)
    with pytest.raises(ValueError):
        served.model_config(conf)
