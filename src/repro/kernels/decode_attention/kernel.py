"""Pallas TPU multi-position decode attention (flash-style, query-tiled).

The query-tile BlockSpec of this kernel IS the M_attn granularity of the
NFP principle: q rows are padded to ``q_block`` (selected by
``core.granularity.select_q_block``) before launch, so physical work is
quantized exactly like FlashAttention's kBlockM / FlashInfer's CTA_TILE_Q
(paper App. F) — re-derived for the TPU memory hierarchy: the q tile and
one (k_block, head_dim) KV tile live in VMEM, accumulation runs in f32
VREGs, and the scores matmul maps onto the MXU with M = g*q_block.

RAGGED PER-SLOT DECODE: ``cache_lens`` is a (b,) scalar-prefetch vector —
one committed-cache length per batch row.  This is the layout the
continuous-batching scheduler serves: every slot decodes at its own
sequence position through ONE quantized kernel launch (the FlashInfer
CTA-tile regime of paper App. F).  Each row masks its own query/kv
positions, and a per-row kv-tile upper bound ``cdiv(len_b + n, k_block)``
lets short slots SKIP kv tiles beyond their filled length: the pl.when
guard elides the tile's compute, and the K/V BlockSpec index map clamps
skipped steps to the row's last useful tile so the pipelining machinery
elides their DMA too (unchanged block index => no copy) — granularity
slack becomes observable per row (``ops.slack_report`` models exactly
this skip rule).  Aligned rows (a scalar broadcast to (b,)) reduce to the
old single-length behaviour bit-for-bit.

Layout (prepared by ops.py):
  q: (b, kv_heads, g, n_pad, dh)   g = query heads per KV head (GQA)
  k: (b, kv_heads, s_pad, dh)
  v: (b, kv_heads, s_pad, dh)
  cache_lens: (b,) i32 scalar-prefetch (positions already committed,
              per batch row; the n new positions sit at len_b .. len_b+n-1)
Output:
  o: (b, kv_heads, g, n_pad, dh)
Grid: (b, kv_heads, n_q_tiles, n_kv_tiles) — kv tiles innermost, online
softmax state in VMEM scratch persists across kv tiles.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(cache_lens_ref, q_ref, k_ref, v_ref, o_ref,
                 m_ref, l_ref, acc_ref, *,
                 q_block: int, k_block: int, g: int, scale: float,
                 window: Optional[int], n_kv_tiles: int, n_logical: int):
    ib = pl.program_id(0)
    iq = pl.program_id(2)
    ij = pl.program_id(3)

    @pl.when(ij == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cache_len = cache_lens_ref[ib]

    # --- per-row kv-tile bounds (the ragged fast path) ---------------------
    # Upper: this row's cache holds cache_len + n_logical committed/new
    # positions, and within this q tile nothing past the tile's last query
    # (causal diagonal) is visible either; tiles at/after the smaller
    # boundary hold nothing the mask would keep — skipping them is free.
    row_kv_end = cache_len + jnp.minimum(n_logical, (iq + 1) * q_block)
    useful = ij * k_block < row_kv_end
    if window is not None:
        # Lower: the smallest q position in this q tile is
        # cache_len + iq*q_block; kv tiles wholly below its window are
        # invisible to every row of the tile.
        lo_visible = cache_len + iq * q_block - window + 1
        useful &= ij * k_block + k_block - 1 >= lo_visible

    @pl.when(useful)
    def _compute():
        rows = g * q_block
        q = q_ref[0, 0].reshape(rows, q_ref.shape[-1]).astype(jnp.float32)
        # dense layout blocks are (1, 1, kb, dh); paged pool blocks are
        # (1, kb, dh) — flatten either to the (kb, dh) tile
        k = k_ref[...].reshape(k_block, k_ref.shape[-1]).astype(jnp.float32)
        v = v_ref[...].reshape(k_block, v_ref.shape[-1]).astype(jnp.float32)

        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (rows, kb)

        # --- causal / window / validity mask -------------------------------
        row_ids = jax.lax.broadcasted_iota(jnp.int32, (rows, k_block), 0)
        q_off = row_ids % q_block                            # row -> q index
        q_pos = cache_len + iq * q_block + q_off
        kv_pos = (ij * k_block
                  + jax.lax.broadcasted_iota(jnp.int32, (rows, k_block), 1))
        mask = kv_pos <= q_pos
        if window is not None:
            mask &= kv_pos > (q_pos - window)
        scores = jnp.where(mask, scores, NEG_INF)

        # --- online softmax ------------------------------------------------
        m_prev = m_ref[...]
        m_cur = jnp.max(scores, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = (alpha * acc_ref[...]
                        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                              preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ij == n_kv_tiles - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        out = (acc_ref[...] / l).reshape(g, q_block, acc_ref.shape[-1])
        o_ref[0, 0] = out.astype(o_ref.dtype)


def decode_attention_pallas(q, k, v, cache_lens, *, q_block: int,
                            k_block: int, scale: float,
                            window: Optional[int] = None,
                            n_logical: Optional[int] = None,
                            interpret: bool = False):
    """q: (b, kv, g, n_pad, dh); k/v: (b, kv, s_pad, dh); cache_lens: (b,) i32.

    ``n_logical`` is the un-padded query count (defaults to n_pad): row b's
    filled kv length is cache_lens[b] + n_logical, the per-row tile bound.
    """
    b, kv, g, n_pad, dh = q.shape
    s_pad = k.shape[2]
    n_q_tiles = n_pad // q_block
    n_kv_tiles = s_pad // k_block
    grid = (b, kv, n_q_tiles, n_kv_tiles)

    n_log = n_pad if n_logical is None else n_logical
    kernel = functools.partial(
        _attn_kernel, q_block=q_block, k_block=k_block, g=g, scale=scale,
        window=window, n_kv_tiles=n_kv_tiles, n_logical=n_log)

    def kv_index(ib, ik, iq, ij, lens_ref):
        # Clamp the kv block index to the row's useful-tile range (mirrors
        # the kernel's `useful` bounds, upper AND window lower): skipped
        # grid steps then revisit an already-resident block, and Pallas
        # elides the DMA when the block index is unchanged — so the ragged
        # skip saves HBM traffic, not just MXU work.  The fetched-but-
        # skipped content is never read (the pl.when guard), so the clamp
        # target is free to choose.
        last = jnp.maximum(
            (lens_ref[ib] + jnp.minimum(n_log, (iq + 1) * q_block)
             + k_block - 1) // k_block - 1, 0)
        idx = jnp.minimum(ij, last)
        if window is not None:
            first = jnp.maximum(
                (lens_ref[ib] + iq * q_block - window + 1) // k_block, 0)
            idx = jnp.maximum(idx, jnp.minimum(first, last))
        return (ib, ik, idx, 0)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, q_block, dh),
                             lambda ib, ik, iq, ij, *_: (ib, ik, 0, iq, 0)),
                pl.BlockSpec((1, 1, k_block, dh), kv_index),
                pl.BlockSpec((1, 1, k_block, dh), kv_index),
            ],
            out_specs=pl.BlockSpec((1, 1, g, q_block, dh),
                                   lambda ib, ik, iq, ij, *_: (ib, ik, 0, iq, 0)),
            scratch_shapes=[
                pltpu.VMEM((g * q_block, 1), jnp.float32),   # running max
                pltpu.VMEM((g * q_block, 1), jnp.float32),   # running sum
                pltpu.VMEM((g * q_block, dh), jnp.float32),  # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, n_pad, dh), q.dtype),
        interpret=interpret,
        name="decode_attention_ragged",
    )(cache_lens, q, k, v)


def decode_attention_paged_pallas(q, k_pool, v_pool, cache_lens,
                                  block_tables, *, q_block: int,
                                  block_size: int, scale: float,
                                  window: Optional[int] = None,
                                  n_logical: Optional[int] = None,
                                  interpret: bool = False):
    """Block-table-indexed variant: the KV cache is a GLOBAL paged pool.

    q: (b, kv, g, n_pad, dh); k_pool/v_pool: (kv, n_phys*block_size, dh)
    — the refcounted block pool flattened along the position axis, one
    physical page per kv tile (the page size IS this launch's k_block);
    cache_lens: (b,) i32; block_tables: (b, max_blocks) i32 mapping row
    b's LOGICAL kv tile ij to a physical page.

    This is the (b,) ``cache_lens`` scalar-prefetch machinery
    generalized one step: a SECOND prefetch operand carries the block
    tables, and the K/V BlockSpec index map — the same per-row
    useful-tile clamp as the ragged dense kernel — returns
    ``bt[ib, clamp(ij)]`` instead of ``clamp(ij)``, so the DMA engine
    walks each row's (arbitrarily fragmented) page list while the
    in-kernel masks keep operating in LOGICAL positions.  The tile-skip
    rule (and therefore ``ops.slack_report``) is unchanged: a skipped
    grid step revisits the row's last useful page, and Pallas elides
    the copy when the physical page index is unchanged.  Rows whose
    table entries point at the trailing trash page (inactive slots)
    read junk that the causal mask zeroes out exactly.
    """
    b, kv, g, n_pad, dh = q.shape
    n_q_tiles = n_pad // q_block
    n_kv_tiles = block_tables.shape[1]
    grid = (b, kv, n_q_tiles, n_kv_tiles)

    n_log = n_pad if n_logical is None else n_logical
    kernel = functools.partial(
        _attn_kernel, q_block=q_block, k_block=block_size, g=g, scale=scale,
        window=window, n_kv_tiles=n_kv_tiles, n_logical=n_log)

    def paged_kernel(lens_ref, bt_ref, *refs, **kw):
        # the block tables only steer the index maps; the kernel body is
        # the ragged kernel unchanged (it masks in logical positions)
        del bt_ref
        return kernel(lens_ref, *refs, **kw)

    def kv_index(ib, ik, iq, ij, lens_ref, bt_ref):
        # identical useful-range clamp to the dense ragged kernel, then
        # mapped through the row's block table: logical tile -> physical
        # page.  Entries inside the clamp range are always valid pages
        # (allocated, or the trash page for inactive rows).
        last = jnp.maximum(
            (lens_ref[ib] + jnp.minimum(n_log, (iq + 1) * q_block)
             + block_size - 1) // block_size - 1, 0)
        idx = jnp.minimum(ij, last)
        if window is not None:
            first = jnp.maximum(
                (lens_ref[ib] + iq * q_block - window + 1) // block_size, 0)
            idx = jnp.maximum(idx, jnp.minimum(first, last))
        return (ik, bt_ref[ib, idx], 0)

    return pl.pallas_call(
        paged_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, q_block, dh),
                             lambda ib, ik, iq, ij, *_: (ib, ik, 0, iq, 0)),
                pl.BlockSpec((1, block_size, dh), kv_index),
                pl.BlockSpec((1, block_size, dh), kv_index),
            ],
            out_specs=pl.BlockSpec((1, 1, g, q_block, dh),
                                   lambda ib, ik, iq, ij, *_: (ib, ik, 0, iq, 0)),
            scratch_shapes=[
                pltpu.VMEM((g * q_block, 1), jnp.float32),   # running max
                pltpu.VMEM((g * q_block, 1), jnp.float32),   # running sum
                pltpu.VMEM((g * q_block, dh), jnp.float32),  # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, n_pad, dh), q.dtype),
        interpret=interpret,
        name="decode_attention_paged",
    )(cache_lens, block_tables, q, k_pool, v_pool)
