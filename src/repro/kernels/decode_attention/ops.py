"""jit'd wrappers: layout prep + query-tile padding (the M_attn mechanism).

``decode_attention_ragged`` is the kernel entry the serving scheduler
uses: ``cache_lens`` is a (b,) vector of per-slot committed lengths, so
mixed-length slots share ONE quantized kernel launch.  The logical N
query rows are padded up to the selected q_block before launch — physical
work therefore changes only at tile boundaries (paper Eq. 33-34), which
is exactly the granularity the NFP predictor reads from
``core.granularity``.  ``decode_attention`` keeps the original aligned
(scalar ``total_len``) signature and is a broadcast of the ragged path.

``decode_attention_paged`` serves the scheduler's PAGED cache: K/V live
in a global refcounted block pool and a (b, max_blocks) block table
(second scalar-prefetch operand) maps each row's logical kv tile to a
physical page — the page size is that launch's k_block, so paging slots
straight into the same tile-skip machinery.  ``paged_kv_write`` writes
a forward's new K/V into that pool in place, a page at a time.

``heads_per_step`` picks, from shapes alone, how many KV heads one grid
step of either launch carries: the largest divisor of kv_heads whose
blocks and scratch fit ``VMEM_BUDGET``.

``slack_report`` models the kernel's physical work for one forward in
plain numpy — useful vs padded query rows, executed vs grid kv tiles
under the kernel's per-row skip rule, and the grid steps a launch runs
— so serving telemetry can place MEASURED per-step granularity slack
next to the ``core.nfp`` prediction.
The same rule covers the paged launch (pass ``k_block=block_size`` and
the block-table-covered ``s_max``): tile skipping is decided in logical
positions, independent of which physical page a tile maps to.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.granularity import cdiv, round_up, select_q_block
from repro.kernels.decode_attention.kernel import (
    decode_attention_paged_pallas, decode_attention_pallas,
    paged_kv_write_pallas)

K_BLOCK = 128
# VMEM a grid step's blocks and scratch may take: the v5e's scoped
# default is 16 MiB, and the rest is the compiler's (f32 copies of the
# q, K and V tiles, the mask)
VMEM_BUDGET = 12 * 2 ** 20


def _vmem_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM a (rows, cols) array takes in (sublane, 128-lane) tiles."""
    sublanes = 8 * max(1, 4 // itemsize)
    return round_up(rows, sublanes) * round_up(cols, 128) * itemsize


def heads_per_step(kv_heads: int, g: int, q_block: int, head_dim: int,
                   k_block: int, itemsize: int) -> int:
    """KV heads one grid step of the decode-attention launches carries:
    the largest divisor of ``kv_heads`` whose per-step working set fits
    ``VMEM_BUDGET``.  A head's share: its double-buffered q and output
    blocks (g*q_block x head_dim) and K and V tiles (k_block x head_dim,
    either orientation), at ``itemsize`` bytes, plus in float32 the
    running max and sum, the accumulator, and the scores and
    probabilities (g*q_block x k_block)."""
    rows = g * q_block
    tile = max(_vmem_bytes(k_block, head_dim, itemsize),
               _vmem_bytes(head_dim, k_block, itemsize))
    per_head = (2 * 2 * _vmem_bytes(rows, head_dim, itemsize)   # q, out
                + 2 * 2 * tile                                   # K, V
                + 2 * _vmem_bytes(rows, 1, 4)                    # max, sum
                + _vmem_bytes(rows, head_dim, 4)                 # acc
                + 2 * _vmem_bytes(rows, k_block, 4))             # s, p
    fits = [d for d in range(1, kv_heads + 1)
            if kv_heads % d == 0 and d * per_head <= VMEM_BUDGET]
    return max(fits, default=1)


@functools.partial(jax.jit, static_argnames=("window", "q_block_override",
                                             "k_block", "block", "interpret"))
def decode_attention_ragged(q, k_cache, v_cache, cache_lens, *,
                            window: Optional[int] = None,
                            q_block_override: Optional[int] = None,
                            k_block: int = K_BLOCK,
                            block: Optional[int] = None,
                            interpret: Optional[bool] = None):
    """q: (b, n, h, dh); k/v_cache: (b, s, kv, dh); cache_lens: (b,) i32.

    Row b's N query positions sit at cache_lens[b] .. cache_lens[b]+N-1
    (their K/V already written into the cache at those offsets).  A scalar
    ``cache_lens`` broadcasts to the aligned case.  Returns (b, n, h, dh).

    interpret=None (the default) compiles the kernel on TPU and runs the
    Pallas interpreter elsewhere (CPU validation), so engine/scheduler
    callers need no threading; pass True/False to force either.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, n, h, dh = q.shape
    s = k_cache.shape[1]
    kv = k_cache.shape[2]
    g = h // kv
    q_block = q_block_override or select_q_block(n, dh)
    n_pad = round_up(n, q_block)
    s_pad = round_up(s, k_block)
    scale = 1.0 / (dh ** 0.5)

    qk = q.reshape(b, n, kv, g, dh).transpose(0, 2, 3, 1, 4)   # (b,kv,g,n,dh)
    qk = jnp.pad(qk, ((0, 0), (0, 0), (0, 0), (0, n_pad - n), (0, 0)))
    kk = jnp.pad(k_cache.transpose(0, 2, 1, 3),
                 ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))
    vk = jnp.pad(v_cache.transpose(0, 2, 1, 3),
                 ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))
    lens = jnp.broadcast_to(
        jnp.asarray(cache_lens, jnp.int32).reshape(-1), (b,))

    hps = heads_per_step(kv, g, q_block, dh, k_block,
                         max(q.dtype.itemsize, k_cache.dtype.itemsize))
    o = decode_attention_pallas(qk, kk, vk, lens, q_block=q_block,
                                k_block=k_block, scale=scale,
                                heads_per_step=hps, window=window,
                                n_logical=n, block=block,
                                interpret=interpret)
    return o[:, :, :, :n].transpose(0, 3, 1, 2, 4).reshape(b, n, h, dh)


@functools.partial(jax.jit, static_argnames=("window", "q_block_override",
                                             "block", "interpret"))
def decode_attention_paged(q, k_pool, v_pool, cache_lens, block_tables,
                           layer, *, window: Optional[int] = None,
                           q_block_override: Optional[int] = None,
                           block: Optional[int] = None,
                           interpret: Optional[bool] = None):
    """Paged-pool kernel entry the scheduler's paged cache serves.

    q: (b, n, h, dh); k_pool/v_pool: (layers, kv, n_phys, dh, bs) — the
    global refcounted block pool of every layer (``serving.paged``), as
    the engine stores it, whose page size ``bs`` becomes this launch's
    kv tile (k_block); layer: this launch's index into the pool's first
    axis (a traced scalar); cache_lens: (b,) committed lengths;
    block_tables: (b, max_blocks) i32 logical->physical page map per row
    (unassigned entries point at the trailing trash page).

    Row b's N query positions sit at cache_lens[b] .. cache_lens[b]+N-1
    in LOGICAL positions; their K/V must already be written into the
    pool at the pages the table names.  The pool goes to the kernel
    untouched: no slice, transpose or copy of it.  ``block`` makes the
    mask block-causal (``AttentionSpec.diffusion_block``), reading no
    position past the n written ones.  Returns (b, n, h, dh).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, n, h, dh = q.shape
    kv = k_pool.shape[1]
    g = h // kv
    q_block = q_block_override or select_q_block(n, dh)
    n_pad = round_up(n, q_block)
    scale = 1.0 / (dh ** 0.5)

    qk = q.reshape(b, n, kv, g, dh).transpose(0, 2, 3, 1, 4)   # (b,kv,g,n,dh)
    qk = jnp.pad(qk, ((0, 0), (0, 0), (0, 0), (0, n_pad - n), (0, 0)))
    lens = jnp.broadcast_to(
        jnp.asarray(cache_lens, jnp.int32).reshape(-1), (b,))
    bt = jnp.asarray(block_tables, jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    hps = heads_per_step(kv, g, q_block, dh, k_pool.shape[4],
                         max(q.dtype.itemsize, k_pool.dtype.itemsize))
    o = decode_attention_paged_pallas(qk, k_pool, v_pool, lens, bt, layer,
                                      q_block=q_block, scale=scale,
                                      heads_per_step=hps, window=window,
                                      n_logical=n, block=block,
                                      interpret=interpret)
    return o[:, :, :, :n].transpose(0, 3, 1, 2, 4).reshape(b, n, h, dh)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_kv_write(k_pool, v_pool, k, v, layer, pages, starts, *,
                   interpret: Optional[bool] = None):
    """Write a forward's new K/V into the stacked paged pool in place.

    k_pool/v_pool: (layers, kv, n_phys, dh, bs); k/v: (b, n, kv, dh), row
    b's n new positions, the first at offset ``starts[b]`` of page
    ``pages[b, 0]``; pages: (b, p) the pages of the row's consecutive
    logical blocks from there, p at least the blocks the n positions
    span, entries past the last of them repeating its page; layer: the
    pool's layer to write (a traced scalar).  Returns the
    updated (k_pool, v_pool), the same buffers where the caller's are
    not needed after the call (a scan carry, a donated argument).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def cols(x):                   # (b, n, kv, dh) -> (b, kv, dh, n)
        return x.astype(k_pool.dtype).transpose(0, 2, 3, 1)

    k_pool, v_pool = paged_kv_write_pallas(
        cols(k), cols(v), k_pool, v_pool,
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.asarray(pages, jnp.int32), jnp.asarray(starts, jnp.int32),
        interpret=interpret)
    return k_pool, v_pool


@functools.partial(jax.jit, static_argnames=("window", "q_block_override",
                                             "interpret"))
def decode_attention(q, k_cache, v_cache, total_len, *,
                     window: Optional[int] = None,
                     q_block_override: Optional[int] = None,
                     interpret: Optional[bool] = None):
    """Aligned-rows entry: q: (b, n, h, dh); total_len = cache_len + n
    (scalar, every row at the same position).  See decode_attention_ragged.
    """
    n = q.shape[1]
    cache_len = jnp.asarray(total_len - n, jnp.int32).reshape(())
    return decode_attention_ragged(
        q, k_cache, v_cache, cache_len, window=window,
        q_block_override=q_block_override, interpret=interpret)


def slack_report(n: int, cache_lens, s_max: int, *,
                 head_dim: int = 128,
                 q_block: Optional[int] = None,
                 k_block: int = K_BLOCK,
                 window: Optional[int] = None,
                 block: Optional[int] = None,
                 active=None,
                 n_heads: int = 1,
                 kv_heads: int = 1,
                 itemsize: int = 2) -> Dict[str, float]:
    """Model one ragged decode forward's physical work (tiles per kv head,
    grid steps per launch).

    Mirrors the kernel's skip rule exactly: for batch row b and q tile iq,
    kv tile ij executes iff
        ij*k_block < len_b + min(n, (iq+1)*q_block)              (upper)
        and, with a window, ij*k_block + k_block - 1 >=
            len_b + iq*q_block - window + 1                      (lower)
    (block-causal, ``block``: the upper end is that of the last query's
    block, capped at len_b + n — the same for n <= q_block).

    Args:
      n:          logical query positions per row this forward.
      cache_lens: (b,) committed lengths (the scheduler's slot_lens).
      s_max:      allocated cache length (sets the full kv grid).
      active:     optional (b,) bool — rows carrying real requests.  Rows
                  outside it still execute (the kernel runs the whole
                  batch) but count as pure slack.
      n_heads, kv_heads, itemsize: the launch's head layout and the bytes
                  of its q/K/V elements, which set ``heads_per_step``.

    Returns a dict:
      rows_logical / rows_physical / row_utilization   — query-row padding
      kv_tiles_useful    — executed tiles on active rows (ideal work)
      kv_tiles_executed  — tiles the ragged kernel runs (after skips)
      kv_tiles_grid      — tiles a non-ragged scalar-length kernel runs
      kv_tile_utilization = useful / executed
      kv_tiles_skipped    = grid - executed (the ragged win)
      heads_per_step     — KV heads one grid step carries (hps)
      grid_steps         — steps one launch (one layer) runs:
                           b x q_tiles x kv_tiles x kv_heads / hps
    """
    lens = np.asarray(cache_lens, np.int64).ravel()
    b = lens.size
    act = (np.ones(b, bool) if active is None
           else np.asarray(active, bool).ravel())
    qb = q_block or select_q_block(n, head_dim)
    n_pad = round_up(n, qb)
    n_q_tiles = n_pad // qb
    s_pad = round_up(s_max, k_block)
    n_kv_tiles = s_pad // k_block

    executed = 0
    useful = 0
    for bi in range(b):
        for iq in range(n_q_tiles):
            hi = lens[bi] + min(n, (iq + 1) * qb)        # kv end (exclusive)
            if block is not None:
                hi = min(lens[bi] + n, cdiv(int(hi), block) * block)
            tiles = min(n_kv_tiles, cdiv(int(hi), k_block))
            lo_tile = 0
            if window is not None:
                # first tile whose last kv position reaches lo_visible —
                # same floor-div the kernel's kv_index clamp uses
                lo_visible = lens[bi] + iq * qb - window + 1
                lo_tile = max(0, int(lo_visible) // k_block)
            t = max(0, tiles - lo_tile)
            executed += t
            if act[bi]:
                useful += t

    rows_logical = int(act.sum()) * n
    rows_physical = b * n_pad
    grid = b * n_q_tiles * n_kv_tiles
    hps = heads_per_step(kv_heads, n_heads // kv_heads, qb, head_dim,
                         k_block, itemsize)
    return {
        "n": n, "q_block": qb, "k_block": k_block,
        "rows_logical": rows_logical,
        "rows_physical": rows_physical,
        "row_utilization": rows_logical / max(rows_physical, 1),
        "kv_tiles_useful": useful,
        "kv_tiles_executed": executed,
        "kv_tiles_grid": grid,
        "kv_tile_utilization": useful / max(executed, 1),
        "kv_tiles_skipped": grid - executed,
        "heads_per_step": hps,
        "grid_steps": grid * (kv_heads // hps),
    }
