"""Sharding-rule unit tests — pure PartitionSpec logic, no devices needed.

Uses an abstract mesh-shaped stand-in so the 16×16 production rules are
testable on a 1-device box."""
import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro import configs
from repro.distributed.sharding import (
    _div, axis_size, batch_pspecs, cache_pspecs, dp_axes, param_pspecs)
from repro.models import model as M

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_div_guards_divisibility():
    assert _div(MESH, 64000, "model") == "model"
    assert _div(MESH, 51865, "model") is None       # whisper vocab: odd
    assert _div(MESH, 1, ("pod", "data")) is None


def test_dp_axes():
    assert dp_axes(MESH) == ("data",)
    assert dp_axes(MESH3) == ("pod", "data")
    assert axis_size(MESH3, ("pod", "data")) == 32


@pytest.mark.parametrize("arch", list(configs.ARCHS))
def test_param_specs_valid_for_all_archs(arch):
    """Every leaf gets a spec with rank == leaf rank and sharded dims
    divisible by their axis product (GSPMD hard requirement)."""
    cfg = configs.get_config(arch)
    shapes = M.param_shapes(cfg)
    specs = param_pspecs(shapes, MESH, cfg)
    flat_s = jax.tree_util.tree_leaves_with_path(shapes)
    flat_p = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_s) == len(flat_p)
    for (path, leaf), spec in zip(flat_s, flat_p):
        assert len(spec) <= len(leaf.shape), (path, spec, leaf.shape)
        for dim, ax in zip(leaf.shape, spec):
            if ax is None:
                continue
            assert dim % axis_size(MESH, ax) == 0, (path, spec, leaf.shape)


def test_whisper_vocab_not_sharded():
    cfg = configs.get_config("whisper-base")
    shapes = M.param_shapes(cfg)
    specs = param_pspecs(shapes, MESH, cfg)
    assert specs["embed"]["tok"][0] is None         # 51865 % 16 != 0
    assert specs["lm_head"]["w"][1] is None


def test_dense_2d_layout():
    """FSDP on d_model, TP on heads/d_ff; transposed for the output mats."""
    cfg = configs.get_config("qwen2-72b")
    specs = param_pspecs(M.param_shapes(cfg), MESH, cfg)
    lyr = specs["layers"]["pos0"]
    assert lyr["attn"]["wq"]["w"] == P(None, "data", "model")
    assert lyr["attn"]["wo"]["w"] == P(None, "model", "data")
    assert lyr["mlp"]["w1"]["w"] == P(None, "data", "model")
    assert lyr["mlp"]["w2"]["w"] == P(None, "model", "data")
    assert lyr["ln1"]["scale"] == P(None, None)


def test_moe_expert_parallel_when_divisible():
    cfg = configs.get_config("moonshot-v1-16b-a3b")        # E=64
    specs = param_pspecs(M.param_shapes(cfg), MESH, cfg)
    moe = specs["layers"]["pos0"]["moe"]
    assert moe["w1"] == P(None, "model", "data", None)     # EP
    cfg2 = configs.get_config("qwen2-moe-a2.7b")           # E=60
    specs2 = param_pspecs(M.param_shapes(cfg2), MESH, cfg2)
    moe2 = specs2["layers"]["pos0"]["moe"]
    assert moe2["w1"] == P(None, None, "data", "model")    # TP inside expert
    assert moe2["w2"] == P(None, None, "model", "data")


def test_multipod_pod_axis_is_pure_dp():
    """Params must NOT shard over 'pod' (pod-replicated, DESIGN.md §6)."""
    cfg = configs.get_config("yi-6b")
    specs = param_pspecs(M.param_shapes(cfg), MESH3, cfg)
    for spec in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)):
        for ax in spec:
            axes = (ax,) if isinstance(ax, str) else (ax or ())
            assert "pod" not in axes
    # but the batch DOES shard over pod
    import jax.numpy as jnp
    b = batch_pspecs({"tokens": jax.ShapeDtypeStruct((256, 4096),
                                                     jnp.int32)}, MESH3)
    assert b["tokens"][0] == ("pod", "data")


def test_cache_specs_shard_seq_over_model():
    cfg = configs.get_config("qwen2-72b")
    cache = jax.eval_shape(lambda: M.init_cache(cfg, 128, 32768))
    specs = cache_pspecs(cache, MESH, cfg)
    kspec = specs["layers"]["pos0"]["k"]
    assert kspec == P(None, "data", "model", None, None)


def test_cache_specs_batch1_not_sharded():
    cfg = configs.get_config("xlstm-1.3b")
    cache = jax.eval_shape(lambda: M.init_cache(cfg, 1, 524288))
    specs = cache_pspecs(cache, MESH, cfg)
    for spec in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)):
        if len(spec) >= 2:
            assert spec[1] is None or spec[1] == "model"   # B=1 → no dp
