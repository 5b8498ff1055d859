"""The Pallas kernels compile for a TPU v5e at real model widths.

Interpret mode (``tests/test_kernels.py``) checks the numerics but not
what the chip's compiler accepts: scoped-VMEM limits, block alignment,
vector layouts.  These tests lower every kernel at the block sizes the
FTL planner picks for yi-6b (and the recurrent kernels at the widths of
recurrentgemma-9b and xlstm-1.3b) and compile it against a described
``v5e:2x2`` topology — the chip's compiler, with no chip attached.

The engine's paged decode step compiles whole, at yi-6b widths and at
granite-20b's (MQA, 48 query heads over one KV head, 16-token pages)
with two layers, to check what its compiled program does with the KV
pool.

The planned kernels compile under a limit of the planner's own footprint
estimate plus ``hw.PALLAS_VMEM_HEADROOM``, tighter than the target's
``vmem_limit_bytes`` they ask for in use: Mosaic's internal scratch comes
on top of the tiles the planner counts (about 8.5 MiB for the fused MLP
at m=4096), and the headroom is what must cover it.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler library, and each test
worker imports every test file.
"""
import dataclasses
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import ftl, hw
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_mlp import fused_mlp
from repro.kernels.gemm import gemm
from repro.kernels.gemm_gelu import gemm_act
from repro.kernels.mlstm import mlstm_scan
from repro.kernels.paged_attention import block_size_for, paged_attention
from repro.kernels.rg_lru import rg_lru_scan

TARGET = hw.TPU_V5E
YI = get_config("yi-6b")
GRANITE = get_config("granite-20b")
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _limit(plan) -> int:
    """Scoped-VMEM limit for a planned kernel: the planner's footprint
    estimate plus the Pallas headroom (at most ``vmem_limit_bytes``)."""
    return plan.vmem_bytes + hw.PALLAS_VMEM_HEADROOM


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("m", [512, 4096])
def test_fused_mlp_compiles_at_yi6b_width(one_chip, m):
    d, f = YI.d_model, YI.d_ff
    bm, bf = ops.plan_mlp_blocks(m, d, f, "bfloat16", True, YI.mlp_act,
                                 TARGET)
    plan = ftl.solve(ftl.fusion.mlp(m=m, d_model=d, d_ff=f, dtype="bfloat16",
                                    gated=True, act=YI.mlp_act, fuse=True),
                     target=TARGET, whole_dims=frozenset({"K", "N"}))
    assert (plan.tile("M"), plan.tile("F")) == (bm, bf)
    _compile(lambda x, w1, w2, wg: fused_mlp(
        x, w1, w2, wg, act=YI.mlp_act, block_m=bm, block_f=bf,
        vmem_limit_bytes=_limit(plan)),
        one_chip, ((m, d), BF16), ((d, f), BF16), ((f, d), BF16),
        ((d, f), BF16))


@pytest.mark.parametrize("t", [2048, 4096])
def test_flash_attention_compiles_at_yi6b_width(one_chip, t):
    hq, hk, dh = YI.n_heads, YI.n_kv_heads, YI.resolved_head_dim
    bq, bk = ops.plan_attention_blocks(t, t, dh, "bfloat16", TARGET)
    plan = ftl.plan_fixed(ftl.attention_graph(q_len=t, kv_len=t, head_dim=dh,
                                              dtype="bfloat16"),
                          (), target=TARGET).segments[0].plan
    _compile(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=bq, block_k=bk,
        vmem_limit_bytes=_limit(plan)),
        one_chip, ((1, hq, t, dh), BF16), ((1, hk, t, dh), BF16),
        ((1, hk, t, dh), BF16))


def test_gemm_compiles_at_yi6b_attention_projection(one_chip):
    m, d, n = 2048, YI.d_model, YI.d_model
    bm, bn, bk = ops.plan_gemm_blocks(m, d, n, "bfloat16", None, TARGET)
    plan = ftl.solve(ftl.fusion.gemm_chain(m=m, dims_kn=[d, n],
                                           dtype="bfloat16"), target=TARGET)
    _compile(lambda x, w: gemm(
        x, w, block_m=bm, block_n=bn, block_k=bk,
        vmem_limit_bytes=_limit(plan)),
        one_chip, ((m, d), BF16), ((d, n), BF16))


def test_gemm_act_compiles_at_yi6b_up_projection(one_chip):
    m, d, f = 2048, YI.d_model, YI.d_ff
    bm, bn, bk = ops.plan_gemm_blocks(m, d, f, "bfloat16", YI.mlp_act,
                                      TARGET)
    plan = ftl.solve(ftl.fusion.gemm_act(m=m, k=d, n=f, dtype="bfloat16",
                                         act=YI.mlp_act), target=TARGET)
    _compile(lambda x, w: gemm_act(
        x, w, act=YI.mlp_act, block_m=bm, block_n=bn, block_k=bk,
        vmem_limit_bytes=_limit(plan)),
        one_chip, ((m, d), BF16), ((d, f), BF16))


def test_rg_lru_compiles_at_recurrentgemma9b_width(one_chip):
    w = get_config("recurrentgemma-9b").lru_width
    shape = ((1, 2048, w), BF16)
    _compile(lambda x, a: rg_lru_scan(
        x, a, vmem_limit_bytes=TARGET.vmem_limit_bytes),
        one_chip, shape, shape)


def test_mlstm_compiles_at_xlstm13b_width(one_chip):
    cfg = get_config("xlstm-1.3b")
    h = cfg.n_heads
    dh = cfg.xlstm_expand * cfg.d_model // h
    qkv = ((1, h, 2048, dh), BF16)
    gate = ((1, h, 2048), jnp.float32)
    _compile(lambda q, k, v, i, f: mlstm_scan(
        q, k, v, i, f, vmem_limit_bytes=TARGET.vmem_limit_bytes),
        one_chip, qkv, qkv, qkv, gate, gate)


def _hlo_arrays(text: str):
    """(opcode, dims) of every array-shaped instruction of an HLO dump."""
    pat = re.compile(r"^\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* ([\w-]+)\(")
    for line in text.splitlines():
        m = pat.match(line)
        if m:
            yield m.group(2), tuple(int(d) for d in m.group(1).split(",")
                                    if d)


def _compile_paged_attention(cfg, sharding, slots, max_seq):
    hq, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    bs = block_size_for(hk, BF16)
    n_pages = slots * max_seq // bs + 1
    _compile(lambda q, k, v, blocks, lens: paged_attention(
        q, k, v, blocks, lens, vmem_limit_bytes=TARGET.vmem_limit_bytes),
        sharding, ((slots, hq, dh), BF16), ((n_pages, bs, hk, dh), BF16),
        ((n_pages, bs, hk, dh), BF16), ((slots, max_seq // bs), jnp.int32),
        ((slots,), jnp.int32))


@pytest.mark.parametrize("slots,max_seq", [(10, 2048), (4, 4096)])
def test_paged_attention_compiles_at_yi6b_width(one_chip, slots, max_seq):
    assert block_size_for(YI.n_kv_heads, BF16) == 8
    _compile_paged_attention(YI, one_chip, slots, max_seq)


@pytest.mark.parametrize("slots,max_seq", [(256, 2048), (16, 4096)])
def test_paged_attention_compiles_at_granite20b_width(one_chip, slots,
                                                      max_seq):
    """48 query heads over one KV head, 16-token pages, at the slots and
    contexts of granite's decode-batch and prefill-open cells."""
    assert block_size_for(GRANITE.n_kv_heads, BF16) == 16
    _compile_paged_attention(GRANITE, one_chip, slots, max_seq)


def test_paged_decode_step_reads_the_pool_in_place(one_chip, monkeypatch):
    """The engine's paged decode step at yi-6b widths (2 layers, 10 slots
    at max_seq 2048, 8-token pages), traced as on the chip, runs the
    paged-attention kernel and takes the pool donated and in place:
    every pool byte aliases an output, no copy is as large as a pool
    leaf, and no instruction holds a dense per-slot view of the cache."""
    _check_paged_step(YI, one_chip, monkeypatch, slots=10, max_seq=2048)


def test_paged_decode_step_reads_the_pool_in_place_at_granite20b_width(
        one_chip, monkeypatch):
    """The same at granite-20b widths (MQA 48/1, GELU MLP with biases,
    LayerNorm; 2 layers, 256 slots at max_seq 2048, 16-token pages)."""
    _check_paged_step(GRANITE, one_chip, monkeypatch, slots=256,
                      max_seq=2048)


def _check_paged_step(base, one_chip, monkeypatch, *, slots, max_seq):
    from repro.launch.serve import make_paged_step
    from repro.models import model as M

    # the step picks the kernel by the backend it is traced for
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    cfg = dataclasses.replace(base, n_layers=2, ftl_mode="auto")
    bs = block_size_for(cfg.n_kv_heads, cfg.dtype)
    _, plan = M.serve_plan(cfg, m=1, dtype=cfg.dtype, target=TARGET,
                           phase="decode")

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.eval_shape(M.init_params, cfg, jax.random.PRNGKey(0))
    pool = jax.eval_shape(functools.partial(
        M.init_cache, cfg, slots * max_seq // bs + 1, bs))
    ints = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
            for s in ((slots, max_seq // bs), (slots, 1), (slots,),
                      (slots,), (slots,))]
    compiled = make_paged_step(cfg, plan).lower(
        jax.tree.map(spec, params), jax.tree.map(spec, pool),
        *ints).compile()

    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    assert "%paged_attention" in text
    leaves = jax.tree.leaves(pool)
    assert compiled.memory_analysis().alias_size_in_bytes == sum(
        a.size * a.dtype.itemsize for a in leaves)
    leaf_size = min(a.size for a in leaves)
    hk, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    dense = (slots, max_seq, hk, dh)
    dense_size = cfg.n_layers * math.prod(dense)
    for op, dims in _hlo_arrays(text):
        if op.startswith("copy"):
            assert math.prod(dims) < leaf_size, (op, dims)
        assert dims[-4:] != dense, (op, dims)
        assert math.prod(dims) != dense_size, (op, dims)
