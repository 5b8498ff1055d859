"""Public kernel API: FTL-planned, backend-dispatching wrappers.

Every op here:
  * asks the FTL solver for block sizes (kernel-policy constraints of the
    specific Pallas dataflow are passed as ``whole_dims``),
  * runs the Pallas kernel on TPU, or in interpret mode elsewhere — in a
    program partitioned over a mesh, once per shard (``_per_shard``),
  * can be forced onto the jnp reference path (``backend='ref'``) — that is
    the layer-per-layer baseline used across benchmarks.

The plan lookup is cached (static shapes → static schedule, like Deeploy).
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import ftl
from repro.core import hw as hwlib
from repro.distributed import act_sharding, sharding as shd

from . import ref as _ref
from .flash_attention import flash_attention as _flash
from .fused_mlp import fused_mlp as _fused_mlp
from .gemm import gemm as _gemm
from .gemm_gelu import gemm_act as _gemm_act
from .mlstm import mlstm_scan as _mlstm
from . import paged_attention as _paged
from .rg_lru import rg_lru_scan as _rg_lru

Backend = Literal["auto", "pallas", "ref"]


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _resolve(backend: Backend) -> str:
    if backend == "auto":
        # Pallas on TPU; the jnp path elsewhere (interpret mode is for
        # validation, not production CPU execution)
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return backend


def _with_ref_grad(kernel, reference):
    """``kernel`` with the gradient of ``reference``, its jnp oracle.

    These Pallas kernels have no backward pass, and JAX cannot derive one
    through a ``pallas_call``, so a train step on the TPU differentiates
    the reference at the same inputs instead: the kernel runs forward, the
    oracle is recomputed for the VJP.  The backward therefore costs what
    the reference costs: for attention it builds the whole (Tq, Tk) score
    matrix, O(T^2) memory, where the forward kernel streams it."""
    @jax.custom_vjp
    def f(*args):
        return kernel(*args)

    def fwd(*args):
        return kernel(*args), args

    def bwd(args, g):
        return jax.vjp(reference, *args)[1](g)

    f.defvjp(fwd, bwd)
    return f


# ---------------------------------------------------------------------------
# planned block sizes
# ---------------------------------------------------------------------------

def _kernel_target(target: hwlib.Target | None) -> hwlib.Target:
    """Planning target for the Pallas TPU kernels' block sizes.

    An explicit target wins.  ``None`` resolves through the process
    default *only when* that default is a VMEM-class machine: the
    auto-detected default on a CPU host is the cache-blocked
    ``cpu_cache`` preset, whose 1 MiB fast level cannot hold these
    kernels' whole-K/N weight panels — planning TPU kernels against it
    would raise ``InfeasibleError`` (or pick nonsense blocks) in
    interpret mode.  Such hosts plan the kernels for :data:`TPU_V5E`.
    """
    if target is not None:
        return target
    default = hwlib.default_target()
    if default.fast.capacity_bytes >= 4 * (1 << 20):
        return default
    return hwlib.TPU_V5E


@functools.lru_cache(maxsize=512)
def _plan_mlp_blocks(m: int, k: int, f: int, dtype: str, gated: bool,
                     act: str, target: hwlib.Target) -> tuple[int, int]:
    group = ftl.fusion.mlp(
        m=m, d_model=k, d_ff=f, dtype=dtype, gated=gated, act=act, fuse=True
    )
    plan = ftl.solve(group, target=target, whole_dims=frozenset({"K", "N"}))
    return plan.tile("M"), plan.tile("F")


def plan_mlp_blocks(
    m: int, k: int, f: int, dtype: str, gated: bool, act: str,
    target: hwlib.Target | None = None,
) -> tuple[int, int]:
    """(block_m, block_f) for the fused_mlp kernel from the FTL solver."""
    return _plan_mlp_blocks(m, k, f, dtype, gated, act,
                            _kernel_target(target))


@functools.lru_cache(maxsize=512)
def _plan_gemm_blocks(m: int, k: int, n: int, dtype: str, act: str | None,
                      target: hwlib.Target) -> tuple[int, int, int]:
    if act is None:
        group = ftl.fusion.gemm_chain(m=m, dims_kn=[k, n], dtype=dtype)
    else:
        group = ftl.fusion.gemm_act(m=m, k=k, n=n, dtype=dtype, act=act)
    plan = ftl.solve(group, target=target)
    dims = plan.tiles
    bm = dims.get("M", m)
    bk = dims.get("K", dims.get("K0", k))
    bn = dims.get("F", dims.get("K1", n))
    return bm, bn, bk


def plan_gemm_blocks(
    m: int, k: int, n: int, dtype: str, act: str | None,
    target: hwlib.Target | None = None,
) -> tuple[int, int, int]:
    """(block_m, block_n, block_k) for gemm / gemm_act kernels."""
    return _plan_gemm_blocks(m, k, n, dtype, act, _kernel_target(target))


@functools.lru_cache(maxsize=512)
def _plan_attention_blocks(tq: int, tk: int, dh: int, dtype: str,
                           target: hwlib.Target) -> tuple[int, int]:
    g = ftl.attention_graph(q_len=tq, kv_len=tk, head_dim=dh, dtype=dtype)
    plan = ftl.plan_fixed(g, (), target=target).segments[0].plan
    bq = plan.tile("Tq")
    bk = min(plan.tile("Tk"), max(512, bq))
    while tk % bk:
        bk //= 2
    return bq, max(bk, 1)


def plan_attention_blocks(
    tq: int, tk: int, dh: int, dtype: str,
    target: hwlib.Target | None = None,
) -> tuple[int, int]:
    """(block_q, block_k) for flash attention; Tk is re-tiled if the solver
    kept it whole (its VMEM model allows a whole-row S tile; the kernel
    streams Tk for the online softmax)."""
    return _plan_attention_blocks(tq, tk, dh, dtype,
                                  _kernel_target(target))


# ---------------------------------------------------------------------------
# per-shard placement
# ---------------------------------------------------------------------------

def _dp(mesh: Mesh, n: int):
    """The mesh's data axes for a dim of size ``n``, if it divides."""
    return shd._div(mesh, n, shd.dp_axes(mesh)) or None


def _tp(mesh: Mesh, n: int):
    """The ``model`` axis for a dim of size ``n``, if it divides."""
    return shd._div(mesh, n, "model") if "model" in mesh.axis_names else None


def _per_shard(body, mesh: Mesh, args, in_specs, out_specs):
    """``body(*args)`` once per shard of ``mesh``.

    XLA cannot partition a Mosaic kernel, so inside a program partitioned
    over a mesh (``act_sharding.mesh()``) each Pallas kernel runs under
    ``jax.shard_map`` on the local blocks ``in_specs`` cut out of its
    operands, and plans its block sizes for those local shapes.  ``None``
    arguments (absent biases, states) pass through as ``None``."""
    given = [i for i, a in enumerate(args) if a is not None]

    def local(*xs):
        full = [None] * len(args)
        for i, x in zip(given, xs):
            full[i] = x
        return body(*full)

    return jax.shard_map(
        local, mesh=mesh, in_specs=tuple(in_specs[i] for i in given),
        out_specs=out_specs, check_vma=False)(*(args[i] for i in given))


# ---------------------------------------------------------------------------
# ops
# ---------------------------------------------------------------------------

def gemm(x, w, *, backend: Backend = "auto",
         target: hwlib.Target | None = None):
    if _resolve(backend) == "ref":
        return _ref.gemm(x, w)

    def body(x, w):
        bm, bn, bk = plan_gemm_blocks(x.shape[0], x.shape[1], w.shape[1],
                                      str(x.dtype), None, target)
        kernel = functools.partial(
            _gemm, block_m=bm, block_n=bn, block_k=bk,
            vmem_limit_bytes=_kernel_target(target).vmem_limit_bytes,
            interpret=_interpret())
        return _with_ref_grad(kernel, _ref.gemm)(x, w)

    mesh = act_sharding.mesh()
    if mesh is None:
        return body(x, w)
    rows, cols = _dp(mesh, x.shape[0]), _tp(mesh, w.shape[1])
    return _per_shard(body, mesh, (x, w), (P(rows, None), P(None, cols)),
                      P(rows, cols))


def gemm_act(x, w, b=None, *, act: str = "gelu", backend: Backend = "auto",
             target: hwlib.Target | None = None):
    """The paper's benchmark op."""
    if _resolve(backend) == "ref":
        return _ref.gemm_act(x, w, b, act=act)

    def body(x, w, b):
        bm, bn, bk = plan_gemm_blocks(x.shape[0], x.shape[1], w.shape[1],
                                      str(x.dtype), act, target)
        kernel = functools.partial(
            _gemm_act, act=act, block_m=bm, block_n=bn, block_k=bk,
            vmem_limit_bytes=_kernel_target(target).vmem_limit_bytes,
            interpret=_interpret())
        return _with_ref_grad(
            kernel, functools.partial(_ref.gemm_act, act=act))(x, w, b)

    mesh = act_sharding.mesh()
    if mesh is None:
        return body(x, w, b)
    rows, cols = _dp(mesh, x.shape[0]), _tp(mesh, w.shape[1])
    return _per_shard(body, mesh, (x, w, b),
                      (P(rows, None), P(None, cols), P(cols)), P(rows, cols))


def fused_mlp(x, w1, w2, wg=None, b1=None, b2=None, *, act: str = "gelu",
              backend: Backend = "auto",
              target: hwlib.Target | None = None):
    """Full fused MLP; x may have leading batch dims (flattened internally)."""
    if _resolve(backend) == "ref":
        return _ref.mlp(x, w1, w2, wg, b1, b2, act=act)
    *lead, m, k = x.shape
    xf = x.reshape(-1, k)

    def body(xf, w1, w2, wg, b1, b2):
        bm, bf = plan_mlp_blocks(xf.shape[0], k, w1.shape[1], str(x.dtype),
                                 wg is not None, act, target)
        kernel = functools.partial(
            _fused_mlp, act=act, block_m=bm, block_f=bf,
            vmem_limit_bytes=_kernel_target(target).vmem_limit_bytes,
            interpret=_interpret())
        return _with_ref_grad(kernel, functools.partial(_ref.mlp, act=act))(
            xf, w1, w2, wg, b1, b2)

    mesh = act_sharding.mesh()
    if mesh is None:
        y = body(xf, w1, w2, wg, b1, b2)
    else:
        # d_ff split over 'model': each shard's slice of w2 gives a partial
        # sum of the output, stacked on a leading axis and added up here
        # (b2 once, after the sum)
        rows, f = _dp(mesh, xf.shape[0]), _tp(mesh, w1.shape[1])
        parts = _per_shard(
            lambda *a: body(*a, None)[None], mesh, (xf, w1, w2, wg, b1),
            (P(rows, None), P(None, f), P(f, None), P(None, f), P(f)),
            P(f, rows, None))
        y = parts.sum(0, dtype=jnp.float32).astype(x.dtype)
        if b2 is not None:
            y = y + b2
    return y.reshape(*lead, m, w2.shape[1])


# XLA-path attention schedule: 'naive' materializes the (Tq, Tk) scores
# (the layer-per-layer baseline); 'blockwise' runs the FTL schedule via
# lax.scan (ref.attention_blockwise) above the length threshold.  §Perf
# toggles this to measure the fused-tiled schedule's effect on the
# compiled dry-run.
_XLA_ATTN = {"mode": "naive", "min_len": 2048}


def set_xla_attention(mode: str, *, min_len: int = 2048) -> None:
    assert mode in ("naive", "blockwise"), mode
    _XLA_ATTN["mode"] = mode
    _XLA_ATTN["min_len"] = min_len


def attention(q, k, v, *, causal: bool = True, window: int | None = None,
              q_offset: int = 0, backend: Backend = "auto",
              target: hwlib.Target | None = None):
    if _resolve(backend) == "ref":
        tk = k.shape[2]
        if _XLA_ATTN["mode"] == "blockwise" and tk >= _XLA_ATTN["min_len"]:
            _, bk = plan_attention_blocks(q.shape[2], tk, q.shape[3],
                                          str(q.dtype), target)
            return _ref.attention_blockwise(
                q, k, v, causal=causal, window=window, q_offset=q_offset,
                block_k=max(bk, 1024))
        return _ref.attention(q, k, v, causal=causal, window=window,
                              q_offset=q_offset)

    def body(q, k, v):
        bq, bk = plan_attention_blocks(q.shape[2], k.shape[2], q.shape[3],
                                       str(q.dtype), target)
        kernel = functools.partial(
            _flash, causal=causal, window=window, q_offset=q_offset,
            block_q=bq, block_k=bk,
            vmem_limit_bytes=_kernel_target(target).vmem_limit_bytes,
            interpret=_interpret())
        reference = functools.partial(_ref.attention, causal=causal,
                                      window=window, q_offset=q_offset)
        return _with_ref_grad(kernel, reference)(q, k, v)

    mesh = act_sharding.mesh()
    if mesh is None:
        return body(q, k, v)
    # heads split over 'model' only when the KV heads divide (then the
    # query heads do too, and each shard keeps whole GQA groups)
    spec = P(_dp(mesh, q.shape[0]), _tp(mesh, k.shape[1]), None, None)
    return _per_shard(body, mesh, (q, k, v), (spec,) * 3, spec)


def paged_kernel(block_size: int, kv_heads: int, dtype,
                 backend: Backend = "auto") -> bool:
    """Whether :func:`paged_attention` runs the Pallas kernel over pages
    of ``block_size`` positions of ``kv_heads`` heads in ``dtype``."""
    return _resolve(backend) != "ref" and _paged.supported(
        block_size, kv_heads, dtype)


def paged_attention(q, k_pages, v_pages, blocks, lengths, *,
                    backend: Backend = "auto",
                    target: hwlib.Target | None = None):
    """Decode attention over KV pages read in place: ``(acc, m, l)`` of
    each row's cached positions (``ref.paged_attention``).  The Pallas
    kernel where the pages fit its tiles (``paged_kernel``), the jnp
    gather otherwise."""
    bs, hk = k_pages.shape[1], k_pages.shape[2]
    if not paged_kernel(bs, hk, k_pages.dtype, backend):
        return _ref.paged_attention(q, k_pages, v_pages, blocks, lengths)
    return _paged.paged_attention(
        q, k_pages, v_pages, blocks, lengths,
        vmem_limit_bytes=_kernel_target(target).vmem_limit_bytes,
        interpret=_interpret())


def rg_lru(x, a, h0=None, *, backend: Backend = "auto"):
    if _resolve(backend) == "ref":
        return _ref.rg_lru_scan(x, a, h0)

    def body(x, a, h0):
        kernel = functools.partial(
            _rg_lru, vmem_limit_bytes=_kernel_target(None).vmem_limit_bytes,
            interpret=_interpret())
        return _with_ref_grad(kernel, _ref.rg_lru_scan)(x, a, h0)

    mesh = act_sharding.mesh()
    if mesh is None:
        return body(x, a, h0)
    rows, feats = _dp(mesh, x.shape[0]), _tp(mesh, x.shape[2])
    seq, state = P(rows, None, feats), P(rows, feats)
    return _per_shard(body, mesh, (x, a, h0), (seq, seq, state),
                      (seq, state))


def mlstm(q, k, v, i_pre, f_pre, *, backend: Backend = "auto",
          return_state: bool = False):
    if return_state:
        # prefill handoff needs the final (C, n, m); the scan ref provides it
        # (kernel extension tracked as a §Perf item).
        return _ref.mlstm_scan(q, k, v, i_pre, f_pre, return_state=True)
    if _resolve(backend) == "ref":
        return _ref.mlstm_scan(q, k, v, i_pre, f_pre)

    def body(q, k, v, i_pre, f_pre):
        kernel = functools.partial(
            _mlstm, vmem_limit_bytes=_kernel_target(None).vmem_limit_bytes,
            interpret=_interpret())
        return _with_ref_grad(kernel, _ref.mlstm_scan)(q, k, v, i_pre, f_pre)

    mesh = act_sharding.mesh()
    if mesh is None:
        return body(q, k, v, i_pre, f_pre)
    rows, heads = _dp(mesh, q.shape[0]), _tp(mesh, q.shape[1])
    seq, gate = P(rows, heads, None, None), P(rows, heads, None)
    return _per_shard(body, mesh, (q, k, v, i_pre, f_pre),
                      (seq, seq, seq, gate, gate), seq)
