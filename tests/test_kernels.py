"""Pallas kernel validation: interpret-mode vs the pure-jnp oracles,
swept over shapes, dtypes and block sizes."""
import functools
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hw
from repro.kernels import flash_attention as _fa, fused_mlp as _fm, gemm as _g
from repro.kernels import gemm_gelu as _ga, mlstm as _ml, ref, rg_lru as _rg
from repro.kernels import paged_attention as _pa
from util import check, run_with_devices

# the kernels take the scoped-VMEM limit of the target they were planned
# for; these tests plan for the repo's serving target
_LIMIT = dict(vmem_limit_bytes=hw.TPU_V5E.vmem_limit_bytes)
flash_attention = functools.partial(_fa.flash_attention, **_LIMIT)
fused_mlp = functools.partial(_fm.fused_mlp, **_LIMIT)
gemm = functools.partial(_g.gemm, **_LIMIT)
gemm_act = functools.partial(_ga.gemm_act, **_LIMIT)
mlstm_scan = functools.partial(_ml.mlstm_scan, **_LIMIT)
rg_lru_scan = functools.partial(_rg.rg_lru_scan, **_LIMIT)
paged_attention = functools.partial(_pa.paged_attention, **_LIMIT)


def rnd(key, shape, dtype, scale=1.0):
    x = jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)
    return (x * scale).astype(dtype)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


DTYPES = [jnp.float32, jnp.bfloat16]


# ---------------------------------------------------------------------------
# GEMM family
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (256, 128, 256, 128, 128, 128),
    (512, 512, 256, 256, 128, 256),
    (128, 384, 640, 128, 128, 128),
])
def test_gemm_matches_ref(m, k, n, bm, bn, bk, dtype):
    x, w = rnd(0, (m, k), dtype, 0.1), rnd(1, (k, n), dtype, 0.1)
    out = gemm(x, w, block_m=bm, block_n=bn, block_k=bk, interpret=True)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.gemm(x, w).astype(jnp.float32),
                               **tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("act", ["gelu", "relu", "silu"])
@pytest.mark.parametrize("bias", [False, True])
def test_gemm_act_matches_ref(act, bias, dtype):
    """The paper's exact benchmark op (GEMM + activation fused)."""
    m, k, n = 256, 384, 512
    x, w = rnd(0, (m, k), dtype, 0.1), rnd(1, (k, n), dtype, 0.1)
    b = rnd(2, (n,), dtype) if bias else None
    out = gemm_act(x, w, b, act=act, block_m=128, block_n=128, block_k=128,
                   interpret=True)
    expect = ref.gemm_act(x, w, b, act=act)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               expect.astype(jnp.float32), **tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("gated", [False, True])
@pytest.mark.parametrize("m,k,f,n,bm,bf", [
    (256, 128, 512, 128, 128, 256),
    (512, 256, 1024, 256, 256, 256),
])
def test_fused_mlp_matches_ref(m, k, f, n, bm, bf, gated, dtype):
    """The FTL flagship: full MLP in one kernel, hidden never leaves VMEM."""
    x = rnd(0, (m, k), dtype, 0.1)
    w1 = rnd(1, (k, f), dtype, 0.05)
    w2 = rnd(2, (f, n), dtype, 0.05)
    wg = rnd(3, (k, f), dtype, 0.05) if gated else None
    out = fused_mlp(x, w1, w2, wg, act="gelu", block_m=bm, block_f=bf,
                    interpret=True)
    expect = ref.mlp(x, w1, w2, wg, act="gelu")
    np.testing.assert_allclose(out.astype(jnp.float32),
                               expect.astype(jnp.float32), **tol(dtype))


def test_fused_mlp_with_biases():
    m, k, f, n = 256, 128, 512, 128
    x = rnd(0, (m, k), jnp.float32, 0.1)
    w1, w2 = rnd(1, (k, f), jnp.float32, 0.05), rnd(2, (f, n), jnp.float32, 0.05)
    b1, b2 = rnd(3, (f,), jnp.float32), rnd(4, (n,), jnp.float32)
    out = fused_mlp(x, w1, w2, None, b1, b2, act="gelu",
                    block_m=128, block_f=256, interpret=True)
    expect = ref.mlp(x, w1, w2, None, b1, b2, act="gelu")
    np.testing.assert_allclose(out, expect, rtol=2e-5, atol=2e-5)


def test_fused_mlp_rejects_nondividing_blocks():
    x = rnd(0, (100, 128), jnp.float32)
    w1, w2 = rnd(1, (128, 512), jnp.float32), rnd(2, (512, 128), jnp.float32)
    with pytest.raises(ValueError):
        fused_mlp(x, w1, w2, block_m=64, block_f=256, interpret=True)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("hq,hk", [(4, 4), (8, 2), (4, 1)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_gqa(hq, hk, causal, dtype):
    b, t, dh = 2, 256, 64
    q = rnd(0, (b, hq, t, dh), dtype)
    k = rnd(1, (b, hk, t, dh), dtype)
    v = rnd(2, (b, hk, t, dh), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128,
                          interpret=True)
    expect = ref.attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               expect.astype(jnp.float32),
                               rtol=3e-2 if dtype == jnp.bfloat16 else 2e-5,
                               atol=3e-2 if dtype == jnp.bfloat16 else 2e-5)


def test_flash_attention_local_window():
    b, h, t, dh = 1, 2, 512, 64
    q = rnd(0, (b, h, t, dh), jnp.float32)
    k = rnd(1, (b, h, t, dh), jnp.float32)
    v = rnd(2, (b, h, t, dh), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=128,
                          block_q=128, block_k=128, interpret=True)
    expect = ref.attention(q, k, v, causal=True, window=128)
    np.testing.assert_allclose(out, expect, rtol=2e-5, atol=2e-5)


def test_flash_attention_cross_q_offset():
    """decode-style: q block at an offset into the kv sequence."""
    b, h, dh = 1, 2, 64
    q = rnd(0, (b, h, 128, dh), jnp.float32)
    k = rnd(1, (b, h, 512, dh), jnp.float32)
    v = rnd(2, (b, h, 512, dh), jnp.float32)
    out = flash_attention(q, k, v, causal=True, q_offset=384,
                          block_q=128, block_k=128, interpret=True)
    expect = ref.attention(q, k, v, causal=True, q_offset=384)
    np.testing.assert_allclose(out, expect, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("hq,hk,bs", [(8, 4, 8), (4, 2, 8), (4, 1, 16)])
def test_paged_attention_matches_ref(hq, hk, bs, dtype):
    """Rows with nothing cached, part of a page, several chunks and the
    whole table, over pages scattered through the pool in no order."""
    width, dh = 32, 128
    lengths = jnp.array([0, 5, 8 * bs + 3, width * bs], jnp.int32)
    b = lengths.shape[0]
    n_pages = b * width + 1
    assert _pa.supported(bs, hk, dtype)
    blocks = jax.random.permutation(jax.random.PRNGKey(3), n_pages)[
        :b * width].reshape(b, width).astype(jnp.int32)
    q = rnd(0, (b, hq, dh), dtype)
    k = rnd(1, (n_pages, bs, hk, dh), dtype)
    v = rnd(2, (n_pages, bs, hk, dh), dtype)
    acc, m, l = paged_attention(q, k, v, blocks, lengths, interpret=True)
    racc, rm, rl = ref.paged_attention(q, k, v, blocks, lengths)
    t = tol(dtype)
    live = lengths > 0
    np.testing.assert_allclose(m[live], rm[live], **t)
    np.testing.assert_allclose((acc / l[..., None])[live],
                               (racc / rl[..., None])[live], **t)
    # the dead row holds nothing: no weight, no output
    assert float(l[0].max()) == 0.0 and float(abs(acc[0]).max()) == 0.0


@pytest.mark.parametrize("dtype", DTYPES)
def test_paged_attention_mqa_at_the_engines_page_length(dtype):
    """granite's 48 query heads over one KV head, at the page length the
    serving engine picks for MQA (16 positions in bf16, 8 in f32), over
    a table as wide as a 2048-position slot: slots at mixed lengths, one
    within its first page, one ending a page, one over several chunks,
    one full, and a dead slot between them."""
    hq, hk, dh = 48, 1, 128
    bs = _pa.block_size_for(hk, dtype)
    assert bs == (16 if dtype == jnp.bfloat16 else 8)
    assert _pa.supported(bs, hk, dtype)
    width = 2048 // bs
    lengths = jnp.array([7, 2 * bs, 0, 37 * bs + 5, width * bs], jnp.int32)
    b = lengths.shape[0]
    n_pages = b * width + 1
    blocks = jax.random.permutation(jax.random.PRNGKey(5), n_pages)[
        :b * width].reshape(b, width).astype(jnp.int32)
    q = rnd(0, (b, hq, dh), dtype)
    k = rnd(1, (n_pages, bs, hk, dh), dtype)
    v = rnd(2, (n_pages, bs, hk, dh), dtype)
    acc, m, l = paged_attention(q, k, v, blocks, lengths, interpret=True)
    racc, rm, rl = ref.paged_attention(q, k, v, blocks, lengths)
    t = tol(dtype)
    live = lengths > 0
    np.testing.assert_allclose(m[live], rm[live], **t)
    np.testing.assert_allclose((acc / l[..., None])[live],
                               (racc / rl[..., None])[live], **t)
    assert float(l[2].max()) == 0.0 and float(abs(acc[2]).max()) == 0.0


@pytest.mark.parametrize("layout", ["runs", "one_chunk_broken"])
@pytest.mark.parametrize("hq,hk,bs", [(8, 4, 8), (48, 1, 16)])
def test_paged_attention_over_tables_in_runs(hq, hk, bs, layout):
    """Tables as the allocator lays them out: each chunk of the table one
    run of consecutive pages (copied whole), the runs in no order through
    the pool; and the same with one page in the middle of a live chunk
    swapped for a page elsewhere, which that chunk must copy page by
    page.  Rows end mid-run, on a run's edge and at the full table, with
    a dead row."""
    dtype, width, dh = jnp.bfloat16, 32, 128
    chunk = _pa.pages_per_copy(width)
    assert chunk == 16
    lengths = jnp.array([5 * bs + 3, 0, chunk * bs, (chunk + 7) * bs + 2,
                         width * bs], jnp.int32)
    b = lengths.shape[0]
    runs = b * width // chunk
    n_pages = 1 + runs * chunk + 1      # a scratch page first, a spare last
    first = 1 + chunk * np.random.default_rng(0).permutation(runs)
    blocks = (first.reshape(b, width // chunk, 1)
              + np.arange(chunk)).reshape(b, width)
    blocks[1] = 0                       # the dead row points at scratch
    if layout == "one_chunk_broken":
        blocks[3, chunk + 4] = n_pages - 1
    blocks = jnp.asarray(blocks, jnp.int32)
    q = rnd(0, (b, hq, dh), dtype)
    k = rnd(1, (n_pages, bs, hk, dh), dtype)
    v = rnd(2, (n_pages, bs, hk, dh), dtype)
    acc, m, l = paged_attention(q, k, v, blocks, lengths, interpret=True)
    racc, rm, rl = ref.paged_attention(q, k, v, blocks, lengths)
    t = tol(dtype)
    live = lengths > 0
    np.testing.assert_allclose(m[live], rm[live], **t)
    np.testing.assert_allclose((acc / l[..., None])[live],
                               (racc / rl[..., None])[live], **t)
    assert float(l[1].max()) == 0.0 and float(abs(acc[1]).max()) == 0.0


# ---------------------------------------------------------------------------
# recurrent kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bt,bd", [(64, 128), (256, 256)])
def test_rg_lru_matches_ref(bt, bd):
    b, t, d = 2, 256, 256
    x = rnd(0, (b, t, d), jnp.float32, 0.5)
    a = jax.nn.sigmoid(rnd(1, (b, t, d), jnp.float32)) * 0.2 + 0.79
    h, hT = rg_lru_scan(x, a, block_t=bt, block_d=bd, interpret=True)
    h_ref, hT_ref = ref.rg_lru_scan(x, a)
    np.testing.assert_allclose(h, h_ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(hT, hT_ref, rtol=1e-4, atol=1e-4)


def test_rg_lru_carries_initial_state():
    b, t, d = 1, 128, 128
    x = rnd(0, (b, t, d), jnp.float32, 0.5)
    a = jnp.full((b, t, d), 0.9, jnp.float32)
    h0 = jnp.ones((b, d), jnp.float32)
    h, _ = rg_lru_scan(x, a, h0, block_t=64, block_d=128, interpret=True)
    h_ref, _ = ref.rg_lru_scan(x, a, h0)
    np.testing.assert_allclose(h, h_ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("block_t", [64, 128])
def test_mlstm_matches_ref(block_t):
    b, h, t, dh = 1, 2, 128, 64
    q = rnd(0, (b, h, t, dh), jnp.float32, 0.3)
    k = rnd(1, (b, h, t, dh), jnp.float32, 0.3)
    v = rnd(2, (b, h, t, dh), jnp.float32, 0.3)
    i_pre = rnd(3, (b, h, t), jnp.float32)
    f_pre = rnd(4, (b, h, t), jnp.float32) + 3.0
    out = mlstm_scan(q, k, v, i_pre, f_pre, block_t=block_t, interpret=True)
    expect = ref.mlstm_scan(q, k, v, i_pre, f_pre)
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-4)


def test_mlstm_state_continuity_across_chunks():
    """Chunked kernel must be bit-consistent with the one-chunk kernel."""
    b, h, t, dh = 1, 1, 256, 64
    q = rnd(0, (b, h, t, dh), jnp.float32, 0.3)
    k = rnd(1, (b, h, t, dh), jnp.float32, 0.3)
    v = rnd(2, (b, h, t, dh), jnp.float32, 0.3)
    ip = rnd(3, (b, h, t), jnp.float32)
    fp = rnd(4, (b, h, t), jnp.float32) + 3.0
    one = mlstm_scan(q, k, v, ip, fp, block_t=256, interpret=True)
    many = mlstm_scan(q, k, v, ip, fp, block_t=64, interpret=True)
    np.testing.assert_allclose(one, many, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# FTL-planned dispatch (ops.py)
# ---------------------------------------------------------------------------

def test_ops_plan_blocks_are_legal():
    # the Pallas kernels plan against the TPU target explicitly: the
    # auto-detected process default is the cache-blocked CPU preset on
    # the test host, whose 1 MiB fast level cannot hold these kernels'
    # whole-K/N weight panels
    from repro.core import hw
    from repro.kernels import ops
    bm, bf = ops.plan_mlp_blocks(4096, 768, 3072, "bfloat16", False, "gelu",
                                 target=hw.TPU_V5E)
    assert 4096 % bm == 0 and 3072 % bf == 0
    bq, bk = ops.plan_attention_blocks(4096, 4096, 128, "bfloat16",
                                       target=hw.TPU_V5E)
    assert 4096 % bq == 0 and 4096 % bk == 0



def _ops_case(op, batch=1):
    """(op's Pallas path, its jnp reference path, arguments); ``batch``
    sizes the leading dim of the attention and recurrent operands."""
    from repro.kernels import ops
    f32 = jnp.float32
    x, w = rnd(0, (128, 128), f32, 0.1), rnd(1, (128, 256), f32, 0.1)
    w2, wg = rnd(2, (256, 128), f32, 0.1), rnd(3, (128, 256), f32, 0.1)
    q = rnd(4, (batch, 4, 128, 64), f32)
    k, v = rnd(5, (batch, 2, 128, 64), f32), rnd(6, (batch, 2, 128, 64), f32)
    u = rnd(7, (batch, 64, 256), f32)
    a = jax.nn.sigmoid(rnd(8, (batch, 64, 256), f32))
    mq = [rnd(9 + i, (batch, 2, 64, 32), f32, 0.5) for i in range(3)]
    gates = [rnd(12, (batch, 2, 64), f32),
             rnd(13, (batch, 2, 64), f32) + 2.0]
    fn, args, kw = {
        "gemm": (ops.gemm, (x, w), {}),
        "gemm_act": (ops.gemm_act, (x, w), dict(act="silu")),
        "fused_mlp": (ops.fused_mlp, (x, w, w2, wg), dict(act="silu")),
        "attention": (ops.attention, (q, k, v), dict(causal=True)),
        "rg_lru": (lambda *xs, backend: ops.rg_lru(*xs, backend=backend)[0],
                   (u, a), {}),
        "mlstm": (ops.mlstm, (*mq, *gates), {}),
    }[op]
    return (functools.partial(fn, backend="pallas", **kw),
            functools.partial(fn, backend="ref", **kw), args)


@pytest.mark.parametrize("op", ["gemm", "gemm_act", "fused_mlp",
                                "attention", "rg_lru", "mlstm"])
def test_pallas_ops_differentiate_like_reference(op):
    """A pallas_call has no derivative of its own: the kernel path of each
    op must give the gradient of its jnp reference (a TPU train step runs
    the kernels forward)."""
    pallas, reference, args = _ops_case(op)
    argnums = tuple(range(len(args)))

    def grads(fn):
        return jax.grad(lambda *xs: jnp.sum(jnp.sin(fn(*xs))), argnums)(
            *args)

    for g, r in zip(grads(pallas), grads(reference)):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5)


_OPS = ["gemm", "gemm_act", "fused_mlp", "attention", "rg_lru", "mlstm"]


@pytest.fixture(scope="module")
def per_shard():
    """Each op's Pallas path (interpret mode) inside a program partitioned
    over a 2x2 ("data", "model") mesh of four host devices, against the
    same path on one device: max abs difference of its value and
    gradients, and whether the kernel ran under shard_map."""
    proc = run_with_devices(f"""
import json, sys
sys.path.insert(0, {str(pathlib.Path(__file__).parent)!r})
import jax, jax.numpy as jnp, numpy as np
from test_kernels import _ops_case
from repro.distributed import act_sharding
from repro.distributed.sharding import make_activation_policy
from repro.launch.mesh import make_mesh

def program(pallas, n):
    # a fresh function per trace: jit must not reuse the other's trace
    def both(*xs):
        loss = lambda *ys: jnp.sum(jnp.sin(pallas(*ys)))
        return pallas(*xs), jax.grad(loss, tuple(range(n)))(*xs)
    return both

mesh = make_mesh((2, 2), ("data", "model"))
out = {{}}
for op in {_OPS!r}:
    pallas, _, args = _ops_case(op, batch=2)
    want = jax.jit(program(pallas, len(args)))(*args)
    with act_sharding.use_policy(make_activation_policy(mesh, None)):
        sharded = program(pallas, len(args))
        got = jax.jit(sharded)(*args)
        text = str(jax.make_jaxpr(sharded)(*args))
    out[op] = dict(
        err=max(float(np.max(np.abs(np.asarray(g) - np.asarray(w))))
                for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want))),
        per_shard="shard_map" in text)
print(json.dumps(out))
""", n_devices=4)
    check(proc)
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("op", _OPS)
def test_pallas_ops_run_per_shard_on_a_mesh(per_shard, op):
    """XLA cannot partition a Mosaic kernel: in a program partitioned over
    a mesh each Pallas op runs once per shard, and its value and
    gradients match the unpartitioned kernel."""
    assert per_shard[op]["per_shard"], per_shard[op]
    assert per_shard[op]["err"] < 1e-4, per_shard[op]


def test_pallas_ops_refuse_a_policy_without_mesh(monkeypatch):
    """'auto' stays on the kernels inside a partitioned program; a policy
    that names no mesh cannot place them and raises, it never rebinds."""
    from repro.distributed import act_sharding
    from repro.kernels import ops
    pallas, _, args = _ops_case("gemm")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with act_sharding.use_policy(lambda x, kind: x):
        assert ops._resolve("auto") == "pallas"
        with pytest.raises(ValueError, match="no mesh"):
            pallas(*args)
