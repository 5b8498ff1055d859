"""Paged decode attention: one query token per row against KV pages read
in place from the pool through the block table.

The pages stay where they are in HBM.  Grid step ``b`` copies into VMEM
only the chunks of pages that hold row ``b``'s cached positions (``<
lengths[b]``), after starting the copies of row ``b + 1``, so the copies
stream back to back while each row computes.  A chunk whose table entries
are consecutive pages (the runs ``launch.kv_cache`` hands out) moves in
one K and one V copy; any other chunk moves page by page.  A page holds
``bs`` positions of all ``Hk`` KV heads, seen as ``bs * Hk`` rows of
``Dh`` (row ``s * Hk + h``): every query head scores against every row
in one MXU contraction, and a mask keeps its own head at the row's
cached positions (``Hk`` times the score FLOPs, which decode has to
spare), so no page is relaid.

The result is the unnormalized online-softmax state ``(acc, m, l)``
(``ref.paged_attention``'s contract), so the caller can merge keys the
pool does not hold yet: decode's new token.  Numerics: bf16 inputs, f32
scores, max-subtracted softmax accumulated in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30
_LANES = 128
# pages per compute chunk: the largest power of two up to this that
# divides the table width
_MAX_CHUNK = 16


def pages_per_copy(width: int) -> int:
    """Pages of one chunk of a ``width``-page table: the longest run of
    consecutive pages the kernel moves in one copy, and the run length
    the allocator hands a slot (16 at the tables of every cell)."""
    c = _MAX_CHUNK
    while width % c:
        c //= 2
    return c


def _sublanes(dtype) -> int:
    """Rows of one VMEM tile of ``dtype``: 8 of 32 bits, packed."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def supported(block_size: int, kv_heads: int, dtype) -> bool:
    """Whether a page's ``block_size * kv_heads`` rows fill whole VMEM
    tiles of ``dtype`` and the head index is a bit field (a power-of-two
    ``kv_heads``): the shapes this kernel runs."""
    return (block_size * kv_heads) % _sublanes(dtype) == 0 and \
        kv_heads & (kv_heads - 1) == 0


def block_size_for(kv_heads: int, dtype) -> int:
    """The shortest page, a multiple of 8 positions, whose ``block_size *
    kv_heads`` rows fill whole VMEM tiles of ``dtype``: 8 for GQA with 2
    or more KV heads, 16 for one KV head in bf16."""
    bs = 8
    while (bs * kv_heads) % _sublanes(dtype):
        bs += 8
    return bs


def _make_kernel(*, bs: int, hk: int, group: int, chunk: int, scale: float):
    rows = bs * hk                      # rows of one page
    shift = hk.bit_length() - 1         # row -> position: row >> shift

    def kernel(pages_ref, lens_ref, q_ref, k_hbm, v_hbm,
               acc_ref, m_ref, l_ref, k_buf, v_buf, sems):
        b = pl.program_id(0)
        dh = q_ref.shape[-1]
        pairs = ((k_hbm, k_buf), (v_hbm, v_buf))

        def n_chunks(r):
            return (lens_ref[r] + chunk * bs - 1) // (chunk * bs)

        def copies(r, slot, c, act):
            """``act`` (start or wait) on each copy of row ``r``'s chunk
            ``c``: one K and one V copy of the whole chunk where its table
            entries are consecutive pages, else one of each per page.
            Start and wait read the same entries, so take the same
            branch."""
            w0 = c * chunk
            first = pages_ref[r, w0]

            def run():
                for i, (hbm, buf) in enumerate(pairs):
                    act(pltpu.make_async_copy(
                        hbm.at[pl.ds(first, chunk)],
                        buf.at[slot, pl.ds(w0, chunk)], sems.at[i, slot, c]))

            def pages():
                for j in range(chunk):
                    page = pages_ref[r, w0 + j]
                    for i, (hbm, buf) in enumerate(pairs):
                        act(pltpu.make_async_copy(
                            hbm.at[page], buf.at[slot, w0 + j],
                            sems.at[i, slot, c]))

            if chunk == 1:
                run()
                return
            whole = functools.reduce(jnp.logical_and, [
                pages_ref[r, w0 + j] == first + j for j in range(1, chunk)])
            pl.when(whole)(run)
            pl.when(jnp.logical_not(whole))(pages)

        def start(r, slot):
            def body(c, carry):
                copies(r, slot, c, lambda d: d.start())
                return carry
            jax.lax.fori_loop(0, n_chunks(r), body, 0)

        @pl.when(b == 0)
        def _():
            start(0, 0)

        @pl.when(b + 1 < pl.num_programs(0))
        def _():
            start(b + 1, (b + 1) % 2)

        slot = b % 2
        q = q_ref[0]                                       # (Hq, Dh)
        hq = q.shape[0]
        shape = (hq, chunk * rows)
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        own = (col & (hk - 1)) == (
            jax.lax.broadcasted_iota(jnp.int32, shape, 0) // group)
        pos = col >> shift

        def body(c, carry):
            m, l, acc = carry
            copies(b, slot, c, lambda d: d.wait())
            k = k_buf[slot, pl.ds(c * chunk, chunk)].reshape(-1, dh)
            v = v_buf[slot, pl.ds(c * chunk, chunk)].reshape(-1, dh)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            valid = own & (c * chunk * bs + pos < lens_ref[b])
            s = jnp.where(valid, s, _NEG)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m - m_new)
            l = alpha * l + p.sum(axis=-1, keepdims=True)
            acc = alpha * acc + jnp.dot(p.astype(v.dtype), v,
                                        preferred_element_type=jnp.float32)
            return m_new, l, acc

        m, l, acc = jax.lax.fori_loop(
            0, n_chunks(b), body,
            (jnp.full((hq, 1), _NEG, jnp.float32),
             jnp.zeros((hq, 1), jnp.float32),
             jnp.zeros((hq, dh), jnp.float32)))
        acc_ref[0] = acc
        m_ref[0] = jnp.broadcast_to(m, (hq, _LANES))
        l_ref[0] = jnp.broadcast_to(l, (hq, _LANES))

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("vmem_limit_bytes", "interpret"))
def paged_attention(
    q: jax.Array,          # (B, Hq, Dh)
    k_pages: jax.Array,    # (P, bs, Hk, Dh)
    v_pages: jax.Array,    # (P, bs, Hk, Dh)
    blocks: jax.Array,     # (B, W) int32 page of each row's w-th block
    lengths: jax.Array,    # (B,) int32 cached positions of each row
    *,
    vmem_limit_bytes: int,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``(acc (B, Hq, Dh), m (B, Hq), l (B, Hq))``, all f32: row ``b``'s
    attention state over its positions ``< lengths[b]``."""
    b, hq, dh = q.shape
    n_pages, bs, hk, _ = k_pages.shape
    width = blocks.shape[1]
    chunk = pages_per_copy(width)
    rows = bs * hk
    kernel = _make_kernel(bs=bs, hk=hk, group=hq // hk, chunk=chunk,
                          scale=dh ** -0.5)
    state = jax.ShapeDtypeStruct
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, hq, dh), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
                pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            ],
            out_specs=[
                pl.BlockSpec((1, hq, dh), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((1, hq, _LANES), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((1, hq, _LANES), lambda i, *_: (i, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, width, rows, dh), k_pages.dtype),
                pltpu.VMEM((2, width, rows, dh), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2, width // chunk)),
            ]),
        out_shape=[state((b, hq, dh), jnp.float32),
                   state((b, hq, _LANES), jnp.float32),
                   state((b, hq, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit_bytes),
        name="paged_attention",
        interpret=interpret,
    )(blocks, lengths, q, k_pages.reshape(n_pages, rows, dh),
      v_pages.reshape(n_pages, rows, dh))
    return acc, m[..., 0], l[..., 0]
