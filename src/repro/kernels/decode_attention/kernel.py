"""Pallas TPU multi-position decode attention (flash-style, query-tiled).

The query-tile BlockSpec of this kernel IS the M_attn granularity of the
NFP principle: q rows are padded to ``q_block`` (selected by
``core.granularity.select_q_block``) before launch, so physical work is
quantized exactly like FlashAttention's kBlockM / FlashInfer's CTA_TILE_Q
(paper App. F) — re-derived for the TPU memory hierarchy: the q tile and
one (k_block, head_dim) KV tile of each of ``hps`` KV heads live in
VMEM, accumulation runs in f32 VREGs, and the scores matmul maps onto
the MXU with M = g*q_block, batched over the heads.

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

HEADS PER STEP: a grid step carries ``hps`` KV heads (``ops.heads_per_step``
picks the largest divisor of kv_heads whose blocks fit VMEM), so one
step moves a kv tile of every head it carries and the per-step overhead
is paid kv_heads/hps times a tile, not kv_heads times.  The heads axis
is a batch axis of both matmuls; the mask and the tile-skip rule depend
on the row, the q tile and the kv tile alone, so every head of a step
shares them, and each head's arithmetic is that of a one-head step.

Layout (prepared by ops.py):
  q: (b, kv_heads, g, n_pad, dh)   g = query heads per KV head (GQA)
  k: (b, kv_heads, s_pad, dh)
  v: (b, kv_heads, s_pad, dh)
  cache_lens: (b,) i32 scalar-prefetch (positions already committed,
              per batch row; the n new positions sit at len_b .. len_b+n-1)
Output:
  o: (b, kv_heads, g, n_pad, dh)
Grid: (b, kv_heads/hps, n_q_tiles, n_kv_tiles) — kv tiles innermost,
online softmax state of the step's hps heads in VMEM scratch persists
across kv tiles.  Blocks: q and o (1, hps, g, q_block, dh), k and v
(1, hps, k_block, dh).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _visible_end(cache_len, q_end, n_logical: int, block: Optional[int]):
    """Exclusive end of the kv positions the first ``q_end`` queries of a
    row see: causal, their last position + 1; block-causal (``block``),
    the end of the last one's block, but never past the row's written
    positions (``cache_len + n_logical``).  A width of at most one
    query tile gives ``cache_len + n_logical`` under either mask."""
    end = cache_len + q_end
    if block is None:
        return end
    return jnp.minimum(cache_len + n_logical,
                       ((end - 1) // block + 1) * block)


def _useful_tile(cache_len, iq, ij, *, q_block: int, k_block: int,
                 n_logical: int, window: Optional[int],
                 block: Optional[int]):
    """The kv tile grid step (row, ``iq``, ``ij``) reads: ``ij`` clamped
    to the row's useful-tile range (the kernel's ``useful`` bounds, upper
    AND window lower).  Skipped grid steps then revisit an already-
    resident block, and Pallas elides the DMA when the block index is
    unchanged — so the ragged skip saves HBM traffic, not just MXU work.
    The fetched-but-skipped content is never read (the pl.when guard),
    so the clamp target is free to choose."""
    end = _visible_end(cache_len, jnp.minimum(n_logical, (iq + 1) * q_block),
                       n_logical, block)
    last = jnp.maximum((end + k_block - 1) // k_block - 1, 0)
    idx = jnp.minimum(ij, last)
    if window is not None:
        first = jnp.maximum(
            (cache_len + iq * q_block - window + 1) // k_block, 0)
        idx = jnp.maximum(idx, jnp.minimum(first, last))
    return idx


def _attn_kernel(cache_lens_ref, q_ref, k_ref, v_ref, o_ref,
                 m_ref, l_ref, acc_ref, *,
                 q_block: int, k_block: int, g: int, scale: float,
                 window: Optional[int], n_kv_tiles: int, n_logical: int,
                 kv_lanes: bool = False, block: Optional[int] = None):
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
    # (causal diagonal; block-causal, its block's end) is visible either;
    # tiles at/after the smaller boundary hold nothing the mask would
    # keep — skipping them is free.
    row_kv_end = _visible_end(cache_len,
                              jnp.minimum(n_logical, (iq + 1) * q_block),
                              n_logical, block)
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
        hps, dh = q_ref.shape[1], q_ref.shape[-1]
        q = q_ref[0].reshape(hps, rows, dh).astype(jnp.float32)
        # dense layout blocks are (1, hps, kb, dh); paged pool pages hold
        # their positions on the lanes, (1, hps, 1, dh, kb): flatten either
        # to its (hps, 2-D tile) and contract over dh wherever it sits,
        # the heads a batch axis of both matmuls
        tile = (hps, dh, k_block) if kv_lanes else (hps, k_block, dh)
        d_axis = 1 if kv_lanes else 2
        k = k_ref[...].reshape(tile).astype(jnp.float32)
        v = v_ref[...].reshape(tile).astype(jnp.float32)

        scores = jax.lax.dot_general(
            q, k, (((2,), (d_axis,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # (hps, rows, kb)

        # --- causal / block-causal / window / validity mask ----------------
        # one (rows, kb) mask, the same for every head of the step
        row_ids = jax.lax.broadcasted_iota(jnp.int32, (rows, k_block), 0)
        q_off = row_ids % q_block                            # row -> q index
        q_pos = cache_len + iq * q_block + q_off
        kv_pos = (ij * k_block
                  + jax.lax.broadcasted_iota(jnp.int32, (rows, k_block), 1))
        if block is None:
            mask = kv_pos <= q_pos
        else:
            mask = ((kv_pos < (q_pos // block + 1) * block)
                    & (kv_pos < cache_len + n_logical))
        if window is not None:
            mask &= kv_pos > (q_pos - window)
        scores = jnp.where(mask[None], scores, NEG_INF)

        # --- online softmax ------------------------------------------------
        m_prev = m_ref[...]
        m_cur = jnp.max(scores, axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=2, keepdims=True)
        acc_ref[...] = (alpha * acc_ref[...]
                        + jax.lax.dot_general(
                            p, v, (((2,), (3 - d_axis,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ij == n_kv_tiles - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        out = (acc_ref[...] / l).reshape(o_ref.shape[1:])
        o_ref[0] = out.astype(o_ref.dtype)


def _scratch(hps: int, rows: int, dh: int):
    """Online-softmax state of one grid step's ``hps`` heads."""
    return [pltpu.VMEM((hps, rows, 1), jnp.float32),     # running max
            pltpu.VMEM((hps, rows, 1), jnp.float32),     # running sum
            pltpu.VMEM((hps, rows, dh), jnp.float32)]    # accumulator


def decode_attention_pallas(q, k, v, cache_lens, *, q_block: int,
                            k_block: int, scale: float, heads_per_step: int,
                            window: Optional[int] = None,
                            n_logical: Optional[int] = None,
                            block: Optional[int] = None,
                            interpret: bool = False):
    """q: (b, kv, g, n_pad, dh); k/v: (b, kv, s_pad, dh); cache_lens: (b,) i32.

    ``n_logical`` is the un-padded query count (defaults to n_pad): row b's
    filled kv length is cache_lens[b] + n_logical, the per-row tile bound.
    ``block``: block-causal masking (``AttentionSpec.diffusion_block``).
    ``heads_per_step`` (a divisor of kv, ``ops.heads_per_step``): the KV
    heads one grid step carries; grid (b, kv/hps, q tiles, kv tiles).
    """
    b, kv, g, n_pad, dh = q.shape
    hps = heads_per_step
    s_pad = k.shape[2]
    n_q_tiles = n_pad // q_block
    n_kv_tiles = s_pad // k_block
    grid = (b, kv // hps, n_q_tiles, n_kv_tiles)

    n_log = n_pad if n_logical is None else n_logical
    kernel = functools.partial(
        _attn_kernel, q_block=q_block, k_block=k_block, g=g, scale=scale,
        window=window, n_kv_tiles=n_kv_tiles, n_logical=n_log, block=block)

    def kv_index(ib, ik, iq, ij, lens_ref):
        return (ib, ik, _useful_tile(lens_ref[ib], iq, ij, q_block=q_block,
                                     k_block=k_block, n_logical=n_log,
                                     window=window, block=block), 0)

    qo_spec = pl.BlockSpec((1, hps, g, q_block, dh),
                           lambda ib, ik, iq, ij, *_: (ib, ik, 0, iq, 0))
    kv_spec = pl.BlockSpec((1, hps, k_block, dh), kv_index)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[qo_spec, kv_spec, kv_spec],
            out_specs=qo_spec,
            scratch_shapes=_scratch(hps, g * q_block, dh),
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, n_pad, dh), q.dtype),
        interpret=interpret,
        name="decode_attention_ragged",
    )(cache_lens, q, k, v)


def decode_attention_paged_pallas(q, k_pool, v_pool, cache_lens,
                                  block_tables, layer, *, q_block: int,
                                  scale: float, heads_per_step: int,
                                  window: Optional[int] = None,
                                  n_logical: Optional[int] = None,
                                  block: Optional[int] = None,
                                  interpret: bool = False):
    """Block-table-indexed variant: the KV cache is a GLOBAL paged pool.

    q: (b, kv, g, n_pad, dh); k_pool/v_pool: (layers, kv, n_phys, dh,
    block_size) — every layer's refcounted block pool, one physical
    page per kv tile (the page size IS this launch's k_block), each page
    holding its positions on the lanes;
    cache_lens: (b,) i32; block_tables: (b, max_blocks) i32 mapping row
    b's LOGICAL kv tile ij to a physical page; layer: (1,) i32, the
    layer whose pages this launch reads; ``heads_per_step`` (hps, a
    divisor of kv, ``ops.heads_per_step``): the KV heads one step carries.

    Grid (b, kv/hps, n_q_tiles, max_blocks).  Step (ib, ik, iq, ij)
    reads q and writes o as (1, hps, g, q_block, dh) blocks — the query
    tile iq of KV heads ik*hps .. ik*hps+hps-1 — and reads K and V as
    (1, hps, 1, dh, block_size) blocks: one page of each of those heads,
    a strided copy of hps contiguous (dh, block_size) chunks, as
    ``paged_kv_write`` reads them.  With hps = kv one step reads a page
    of every head.

    This is the (b,) ``cache_lens`` scalar-prefetch machinery
    generalized: further prefetch operands carry the block tables and
    the layer, and the K/V BlockSpec index map — the same per-row
    useful-tile clamp as the ragged dense kernel — returns
    ``(layer, ik, bt[ib, clamp(ij)])`` instead of ``clamp(ij)``, so the
    DMA engine walks each row's (arbitrarily fragmented) page list in
    the stacked pool while the in-kernel masks keep operating in LOGICAL
    positions.  The tile-skip rule (and therefore ``ops.slack_report``)
    is unchanged: a skipped grid step revisits the row's last useful
    page, and Pallas elides the copy when the page index is unchanged.
    Each executed (row, head, page) tile is still read once and each
    (row, head, q tile) query and output once.  Rows whose table entries
    point at the trailing trash page (inactive slots) read junk that the
    causal mask zeroes out exactly.
    """
    b, kv, g, n_pad, dh = q.shape
    hps = heads_per_step
    block_size = k_pool.shape[4]
    n_q_tiles = n_pad // q_block
    n_kv_tiles = block_tables.shape[1]
    grid = (b, kv // hps, n_q_tiles, n_kv_tiles)

    n_log = n_pad if n_logical is None else n_logical
    kernel = functools.partial(
        _attn_kernel, q_block=q_block, k_block=block_size, g=g, scale=scale,
        window=window, n_kv_tiles=n_kv_tiles, n_logical=n_log,
        kv_lanes=True, block=block)

    def paged_kernel(lens_ref, bt_ref, layer_ref, *refs, **kw):
        # the block tables and the layer only steer the index maps; the
        # kernel body is the ragged kernel's (it masks in logical
        # positions), fed pages with their positions on the lanes
        del bt_ref, layer_ref
        return kernel(lens_ref, *refs, **kw)

    def kv_index(ib, ik, iq, ij, lens_ref, bt_ref, layer_ref):
        # the dense kernel's useful-range clamp, then mapped through the
        # row's block table: logical tile -> physical page.  Entries
        # inside the clamp range are always valid pages (allocated, or
        # the trash page for inactive rows).
        idx = _useful_tile(lens_ref[ib], iq, ij, q_block=q_block,
                           k_block=block_size, n_logical=n_log,
                           window=window, block=block)
        return (layer_ref[0], ik, bt_ref[ib, idx], 0, 0)

    qo_spec = pl.BlockSpec((1, hps, g, q_block, dh),
                           lambda ib, ik, iq, ij, *_: (ib, ik, 0, iq, 0))
    kv_spec = pl.BlockSpec((1, hps, 1, dh, block_size), kv_index)
    return pl.pallas_call(
        paged_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[qo_spec, kv_spec, kv_spec],
            out_specs=qo_spec,
            scratch_shapes=_scratch(hps, g * q_block, dh),
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, n_pad, dh), q.dtype),
        interpret=interpret,
        name="decode_attention_paged",
    )(cache_lens, block_tables, layer, q, k_pool, v_pool)


def paged_kv_write_pallas(k_cols, v_cols, k_pool, v_pool, layer, pages,
                          starts, *, interpret: bool = False):
    """Write each row's new K/V positions into the stacked paged pool, in
    place: the outputs alias ``k_pool`` and ``v_pool``.

    k_cols/v_cols: (b, kv, dh, n) — row b's n new positions, one column
    each; k_pool/v_pool: (layers, kv, n_phys, dh, bs); layer: (1,) i32;
    pages: (b, p) i32, the pages of row b's logical blocks from the
    first new position's on (the last page repeated past the last new
    position); starts: (b,) i32, the first new position's offset in its
    page.

    Grid (b, p): step (ib, j) reads page ``pages[ib, j]`` of every kv
    head, sets the lanes row ib's new positions fall on and writes the
    page back — one read and one write of each page the new positions
    touch, for all heads and positions at once.  Live pages are written
    by their owning row alone; rows that share the trash page may lose
    each other's writes there, which nothing reads.
    """
    _, kv, _, dh, bs = k_pool.shape
    b, n_pages = pages.shape
    n = k_cols.shape[-1]

    def kernel(layer_ref, pages_ref, starts_ref, kc_ref, vc_ref, kin_ref,
               vin_ref, kout_ref, vout_ref):
        ib, j = pl.program_id(0), pl.program_id(1)
        start = starts_ref[ib]
        # steps past the row's last page see that page again (its index
        # unchanged, so not fetched again) and write it again, the same
        jb = jnp.minimum(j, (start + n - 1) // bs)
        # lane l of this page holds new position l + jb*bs - start: a
        # one-hot product places the columns exactly
        shift = start - jb * bs
        src = jax.lax.broadcasted_iota(jnp.int32, (n, bs), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (n, bs), 1)
        onehot = (lane - shift == src).astype(kc_ref.dtype)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1) - shift
        hit = (lane >= 0) & (lane < n)
        for c_ref, i_ref, o_ref in ((kc_ref, kin_ref, kout_ref),
                                    (vc_ref, vin_ref, vout_ref)):
            placed = jnp.dot(c_ref[...].reshape(kv * dh, n), onehot,
                             preferred_element_type=jnp.float32)
            page = i_ref[...].reshape(kv * dh, bs)
            o_ref[...] = jnp.where(hit, placed.astype(page.dtype),
                                   page).reshape(o_ref.shape)

    def page_index(ib, j, layer_ref, pages_ref, starts_ref):
        return (layer_ref[0], 0, pages_ref[ib, j], 0, 0)

    page_spec = pl.BlockSpec((1, kv, 1, dh, bs), page_index)
    cols_spec = pl.BlockSpec((1, kv, dh, n), lambda ib, j, *_: (ib, 0, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, n_pages),
            in_specs=[cols_spec, cols_spec, page_spec, page_spec],
            out_specs=[page_spec, page_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        input_output_aliases={5: 0, 6: 1},
        interpret=interpret,
        name="paged_kv_write",
    )(layer, pages, starts, k_cols, v_cols, k_pool, v_pool)
