"""Paged (blocked) KV cache for the continuous-batching serving engine.

The cache is backed by fixed-size sequence blocks ("pages") allocated
per slot instead of one dense ``(slots, max_seq, ...)`` tensor per layer:

* The physical pool is ``models.model.init_cache(cfg, num_blocks + 1,
  block_size)`` — the *batch* axis of every cache leaf plays the physical
  block index, so the pool reuses the model's exact cache structure
  (stacked ``layers`` leaves ``(L, NB+1, bs, Hk, Dh)``, remainder leaves
  ``(NB+1, bs, Hk, Dh)``).  Physical block 0 is a reserved scratch page:
  unmapped table entries and inactive slots point there, so a stray
  write can never corrupt a mapped page.
* Each slot owns a block table row (host-side numpy, ``(slots,
  blocks_per_slot)`` int32 of physical block ids).  Pages are allocated
  on demand as a slot's position crosses a block boundary and returned
  to the free list on eviction — the continuous-batching scheduler's
  admission control can therefore run the pool smaller than
  ``slots * blocks_per_slot`` and queue requests under memory pressure.
* A decode step (``launch.serve.make_paged_step``) reads the pool in
  place: ``models.model.decode_step_paged`` hands each layer its pages
  through the block table inside the layer scan, and
  ``kernels.ops.paged_attention`` reads them where they lie (on the TPU
  a Pallas kernel copies each slot's live pages into VMEM; elsewhere a
  jnp ``take``).  The new token enters the softmax directly, and the
  step returns only the new tokens' K/V, which :func:`write_tokens`
  stores at their ``(block, offset)`` with one row scatter per leaf into
  the donated pool.  No dense per-slot view is built and no leaf of the
  pool is copied.

Paging requires every decode-cache leaf to be a full-attention KV tensor
with the model's uniform ``(batch, seq, Hk, Dh)`` layout —
:func:`paged_supported` gates it to pure-``attn`` decoder-only configs
(ring-buffered local windows, recurrent states and cross caches keep the
dense per-slot path in the engine).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M


def paged_supported(cfg) -> bool:
    """True when every decode-cache leaf is a plain full-attention KV
    tensor (pure-'attn' decoder-only stacks)."""
    if cfg.is_encoder_decoder:
        return False
    kinds, _, rem_kinds = M._layer_split(cfg)
    return all(k == "attn" for k in [*kinds, *rem_kinds])


def _pages(leaf, blocks: jax.Array) -> jax.Array:
    """Rows of physical blocks ``blocks`` in a pool leaf viewed as ``(... *
    (NB+1), bs, Hk, Dh)`` (a free reshape: only leading dims merge):
    ``l * (NB+1) + block`` for each layer ``l`` of a stacked leaf, which
    adds a leading layer dim."""
    lead = leaf.shape[:-4]
    layer = jnp.arange(math.prod(lead)).reshape(lead + (1,) * blocks.ndim)
    return layer * leaf.shape[-4] + blocks


def write_tokens(pool, new, wblk: jax.Array, woff: jax.Array):
    """Store each slot's new KV token at its physical (block, offset).

    ``new`` mirrors the pool's tree (``models.model.decode_step_paged``):
    stacked leaves ``(L, slots, Hk, Dh)``, remainder ``(slots, Hk, Dh)``;
    ``wblk``/``woff`` ``(slots,)``, with inactive slots at the scratch page
    (block 0).  Each leaf is viewed as token rows ``(... * bs, Hk, Dh)``
    and written by one row scatter, which a donated pool takes in place.
    """
    def w(leaf, tok):
        rows = _pages(leaf, wblk) * leaf.shape[-3] + woff
        flat = leaf.reshape(-1, *leaf.shape[-2:]).at[rows.reshape(-1)].set(
            tok.reshape(-1, *tok.shape[-2:]), mode="promise_in_bounds")
        return flat.reshape(leaf.shape)

    return jax.tree.map(w, pool, new)


@functools.partial(jax.jit, donate_argnums=0)
def _write_pages(pool, cache1, blocks: jax.Array):
    """Write one request's prefill cache (batch-1, seq = n_pages * bs)
    into its allocated pages of the donated pool, one token row at a
    time as :func:`write_tokens` does (jitted; retraces per page
    count)."""
    def s(leaf, c):
        bs = leaf.shape[-3]
        rows = _pages(leaf, blocks[:, None]) * bs + jnp.arange(bs)
        flat = leaf.reshape(-1, *leaf.shape[-2:]).at[rows.reshape(-1)].set(
            c.reshape(-1, *c.shape[-2:]), mode="promise_in_bounds")
        return flat.reshape(leaf.shape)

    return jax.tree.map(s, pool, cache1)


class PagedKVCache:
    """Block-pool KV cache with per-slot page tables (single host).

    ``num_blocks`` bounds the physical pool (default: enough for every
    slot at ``max_seq``, i.e. no admission pressure); one extra scratch
    page is always added on top.  All table/free-list bookkeeping is
    host-side numpy — only the pool itself lives on device.
    """

    def __init__(self, cfg, *, slots: int, max_seq: int, block_size: int,
                 num_blocks: int | None = None):
        if not paged_supported(cfg):
            raise ValueError(
                "paged KV cache needs a pure-'attn' decoder-only config; "
                f"{cfg.name!r} has other cache kinds")
        if max_seq % block_size:
            raise ValueError(
                f"max_seq={max_seq} must be a multiple of "
                f"block_size={block_size}")
        self.cfg = cfg
        self.slots = slots
        self.max_seq = max_seq
        self.block_size = block_size
        self.blocks_per_slot = max_seq // block_size
        self.num_blocks = (num_blocks if num_blocks is not None
                           else slots * self.blocks_per_slot)
        if self.num_blocks < self.blocks_per_slot:
            raise ValueError(
                f"pool of {self.num_blocks} blocks cannot hold even one "
                f"slot at max_seq ({self.blocks_per_slot} blocks)")
        # +1: physical block 0 is the reserved scratch page
        self.pool = M.init_cache(cfg, self.num_blocks + 1, block_size)
        self.tables = np.zeros((slots, self.blocks_per_slot), np.int32)
        self.n_alloc = np.zeros(slots, np.int32)
        self._free = list(range(self.num_blocks, 0, -1))

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def blocks_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.block_size)

    def can_allocate(self, slot: int, n_tokens: int) -> bool:
        need = self.blocks_for(n_tokens) - int(self.n_alloc[slot])
        return need <= len(self._free)

    def allocate(self, slot: int, n_tokens: int) -> bool:
        """Grow ``slot`` to cover ``n_tokens`` positions.  Returns False
        (allocating nothing) when the free list cannot cover the growth —
        the scheduler's admission-control signal."""
        need = self.blocks_for(n_tokens)
        have = int(self.n_alloc[slot])
        if need <= have:
            return True
        if need > self.blocks_per_slot:
            raise ValueError(
                f"slot {slot}: {n_tokens} tokens exceed max_seq "
                f"{self.max_seq}")
        if need - have > len(self._free):
            return False
        for j in range(have, need):
            self.tables[slot, j] = self._free.pop()
        self.n_alloc[slot] = need
        return True

    def release(self, slot: int) -> None:
        """Return the slot's pages to the free list (eviction)."""
        for j in range(int(self.n_alloc[slot])):
            self._free.append(int(self.tables[slot, j]))
        self.tables[slot, :] = 0
        self.n_alloc[slot] = 0

    def table_array(self) -> jax.Array:
        return jnp.asarray(self.tables)

    def write_coords(self, slot: int, pos: int) -> tuple[int, int]:
        """Physical (block, offset) of dense position ``pos`` in ``slot``."""
        j = pos // self.block_size
        return int(self.tables[slot, j]), pos % self.block_size

    def write_prefill(self, slot: int, cache1, n_tokens: int) -> None:
        """Splice one request's prefill cache (batch 1, seq a multiple of
        ``block_size``) into the slot's pages, allocating them first.
        The caller has already checked/established capacity via
        :meth:`allocate`."""
        if not self.allocate(slot, n_tokens):
            raise RuntimeError(
                f"KV pool exhausted admitting into slot {slot} "
                f"({self.free_blocks} free blocks)")
        nb = self.blocks_for(n_tokens)
        blocks = jnp.asarray(self.tables[slot, :nb])
        self.pool = _write_pages(self.pool, cache1, blocks)
