"""RG-LRU gated linear recurrence (Griffin / RecurrentGemma) as a
fused-tiled Pallas kernel.

``h_t = a_t * h_{t-1} + x_t`` over time, per channel.  The FTL view: time is
chunked (grid dim, innermost) and channels tiled; the recurrent state is the
VMEM-resident intermediate carried across time chunks — the full (B, T, D)
state trajectory streams out, but the *carry* never bounces through HBM
between chunks (the layer-per-layer analogue would run chunk-sized scans and
materialize the carry in HBM each time).

Grid (B, d_tiles, t_chunks), t innermost; state scratch (1, block_d) f32.
Time steps are walked one sublane tile at a time (8 rows of f32, 16 of
bf16): the tile is loaded at an aligned offset, its rows are stepped in
registers, and the finished rows are stored back as one aligned tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rows_per_tile(dtype) -> int:
    return 8 * 4 // jnp.dtype(dtype).itemsize


def _kernel(x_ref, a_ref, h0_ref, h_ref, hT_ref, state_ref):
    tc = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(tc == 0)
    def _init():
        state_ref[...] = h0_ref[0].astype(jnp.float32)

    block_t, block_d = x_ref.shape[1], x_ref.shape[2]
    rows = _rows_per_tile(x_ref.dtype)
    row_id = jax.lax.broadcasted_iota(jnp.int32, (rows, block_d), 0)

    def tile(i, h):
        r = pl.multiple_of(i * rows, rows)
        xs = x_ref[0, pl.ds(r, rows), :].astype(jnp.float32)
        as_ = a_ref[0, pl.ds(r, rows), :].astype(jnp.float32)
        out = jnp.zeros((rows, block_d), jnp.float32)
        for j in range(rows):
            h = as_[j:j + 1] * h + xs[j:j + 1]
            out = jnp.where(row_id == j, h, out)
        h_ref[0, pl.ds(r, rows), :] = out.astype(h_ref.dtype)
        return h

    state_ref[...] = jax.lax.fori_loop(0, block_t // rows, tile,
                                       state_ref[...])

    @pl.when(tc == nt - 1)
    def _final():
        hT_ref[0] = state_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("block_t", "block_d", "vmem_limit_bytes", "interpret")
)
def rg_lru_scan(
    x: jax.Array,    # (B, T, D) pre-gated input
    a: jax.Array,    # (B, T, D) decay gates in (0, 1)
    h0: jax.Array | None = None,   # (B, D)
    *,
    block_t: int = 256,
    block_d: int = 512,
    vmem_limit_bytes: int,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    b, t, d = x.shape
    if h0 is None:
        h0 = jnp.zeros((b, d), jnp.float32)
    block_t = min(block_t, t)
    block_d = min(block_d, d)
    if t % block_t or d % block_d:
        raise ValueError(f"blocks must divide dims {(t, d)}")
    if block_t % _rows_per_tile(x.dtype):
        raise ValueError(f"block_t={block_t} must be a multiple of "
                         f"{_rows_per_tile(x.dtype)} for {x.dtype}")
    grid = (b, d // block_d, t // block_t)

    # the (B, D) state travels as (B, 1, D): a 1-row block of a (B, D)
    # array would sit on the sublane axis, which Mosaic refuses
    h, hT = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, block_d), lambda bb, dd, tt: (bb, tt, dd)),
            pl.BlockSpec((1, block_t, block_d), lambda bb, dd, tt: (bb, tt, dd)),
            pl.BlockSpec((1, 1, block_d), lambda bb, dd, tt: (bb, 0, dd)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, block_d), lambda bb, dd, tt: (bb, tt, dd)),
            pl.BlockSpec((1, 1, block_d), lambda bb, dd, tt: (bb, 0, dd)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, d), x.dtype),
            jax.ShapeDtypeStruct((b, 1, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        name="rg_lru",
        interpret=interpret,
    )(x, a, h0.astype(jnp.float32).reshape(b, 1, d))
    return h, hT.reshape(b, d)
