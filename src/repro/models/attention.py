"""Attention variants: GQA (covers MHA/MQA), sliding-window GQA, and MLA.

Three execution modes:
  - "full":   self-attention over the whole sequence (train / prefill).
  - "decode": multi-position decode forward — the paper's Eq. 2: N new
              positions attend to a pre-filled KV cache + each other.
  - "cross":  encoder-decoder cross attention (whisper).

The decode path can route the attention core through the Pallas
query-tiled kernel (``repro.kernels.decode_attention``) whose q-block IS
the M_attn granularity of the NFP principle; the default XLA path is the
semantically identical reference.  The kernel serves BOTH cache layouts:
a scalar ``cache_len`` (single-request drivers, aligned rows) and a (b,)
vector (the scheduler's slotted cache) go through the same ragged
entry — per-row lengths ride the kernel's scalar-prefetch lane, so
mixed-length slots share one quantized launch.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.arch import AttentionSpec
from repro.models.layers import _init, apply_rope, init_rmsnorm, rmsnorm

Array = jax.Array


# ===========================================================================
# Parameter init
# ===========================================================================

def init_attention(key, d_model: int, a: AttentionSpec, dtype=jnp.bfloat16) -> Dict:
    ks = jax.random.split(key, 8)
    if a.kind == "mla":
        qk_h = a.qk_nope_head_dim + a.qk_rope_head_dim
        return {
            "wq_a": _init(ks[0], (d_model, a.q_lora_rank), dtype=dtype),
            "q_norm": init_rmsnorm(a.q_lora_rank, dtype),
            "wq_b": _init(ks[1], (a.q_lora_rank, a.n_heads * qk_h), dtype=dtype),
            "wkv_a": _init(ks[2], (d_model, a.kv_lora_rank + a.qk_rope_head_dim),
                           dtype=dtype),
            "kv_norm": init_rmsnorm(a.kv_lora_rank, dtype),
            "wkv_b": _init(ks[3], (a.kv_lora_rank,
                                   a.n_heads * (a.qk_nope_head_dim + a.v_head_dim)),
                           dtype=dtype),
            "wo": _init(ks[4], (a.n_heads * a.v_head_dim, d_model), dtype=dtype),
        }
    p = {
        "wq": _init(ks[0], (d_model, a.n_heads * a.head_dim), dtype=dtype),
        "wk": _init(ks[1], (d_model, a.n_kv_heads * a.head_dim), dtype=dtype),
        "wv": _init(ks[2], (d_model, a.n_kv_heads * a.head_dim), dtype=dtype),
        "wo": _init(ks[3], (a.n_heads * a.head_dim, d_model), dtype=dtype),
    }
    if a.qk_norm_eps is not None:
        p["q_norm"] = init_rmsnorm(a.head_dim, dtype)
        p["k_norm"] = init_rmsnorm(a.head_dim, dtype)
    return p


def init_kv_cache(batch: int, max_len: int, a: AttentionSpec,
                  dtype=jnp.bfloat16) -> Dict:
    """Pre-allocated decode cache (paper App. C.1.3 discipline)."""
    if a.kind == "mla":
        return {
            "latent": jnp.zeros((batch, max_len, a.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, max_len, a.qk_rope_head_dim), dtype),
        }
    return {
        "k": jnp.zeros((batch, max_len, a.n_kv_heads, a.head_dim), dtype),
        "v": jnp.zeros((batch, max_len, a.n_kv_heads, a.head_dim), dtype),
    }


def init_paged_kv_cache(n_phys: int, block_size: int, a: AttentionSpec,
                        dtype=jnp.bfloat16) -> Dict:
    """One layer of the paged decode cache: a GLOBAL pool of ``n_phys``
    blocks of ``block_size`` positions, shared by all slots through
    per-slot block tables (``serving.paged.BlockManager``).  The last
    block is the write-dump page unattached table entries point at.

    A page holds its positions on the minor axis, (d, block): GQA/MHA
    K and V are (kv, n_phys, head_dim, block), MLA leaves (n_phys, d,
    block).  With a 128-position page that minor axis fills the TPU's
    128 lanes, so the device stores the pool row-major — the layout the
    paged kernel's DMA reads page by page — and never pads a head_dim
    such as 80 or 64 up to the lanes."""
    if a.kind == "mla":
        return {
            "latent": jnp.zeros((n_phys, a.kv_lora_rank, block_size), dtype),
            "k_rope": jnp.zeros((n_phys, a.qk_rope_head_dim, block_size),
                                dtype),
        }
    return {
        "k": jnp.zeros((a.n_kv_heads, n_phys, a.head_dim, block_size), dtype),
        "v": jnp.zeros((a.n_kv_heads, n_phys, a.head_dim, block_size), dtype),
    }


# ===========================================================================
# Attention cores
# ===========================================================================

def _gqa_core(q: Array, k: Array, v: Array, mask: Array, scale: float) -> Array:
    """q: (b,sq,h,dh)  k/v: (b,sk,kv,dh)  mask: (b,sq,sk) bool -> (b,sq,h,dh).

    Grouped without materializing repeated KV heads.
    """
    b, sq, h, dh = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, dh)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32) * scale
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    ctx = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return ctx.reshape(b, sq, h, dh)


def _row_offsets(cache_len, batch: int) -> Array:
    """Per-row cache lengths: a scalar ``cache_len`` (every row at the
    same position — the single-request drivers) or a (b,) vector (the
    scheduler's slotted cache, each slot at its own length)."""
    cl = jnp.asarray(cache_len, jnp.int32)
    if cl.ndim == 0:
        return jnp.full((batch,), cl, jnp.int32)
    return cl


def _update_rows(cache: Array, new: Array, offsets: Array) -> Array:
    """Write ``new`` (b, n, ...) into ``cache`` (b, s, ...) at per-row
    sequence offsets (vmapped dynamic_update_slice)."""
    def one(c, x, off):
        start = (off,) + (0,) * (c.ndim - 1)
        return jax.lax.dynamic_update_slice(c, x, start)
    return jax.vmap(one)(cache, new, offsets)


def _paged_slots(block_tables: Array, pos: Array, n_phys: int,
                 block_size: int) -> Tuple[Array, Array]:
    """Pool (page, offset) of per-row logical positions (b, n).
    Positions past the table's coverage — e.g. junk rows of a
    width-bucketed batched forward on an inactive slot — fall through to
    the trailing trash page, never a live block."""
    b, max_blocks = block_tables.shape
    blk_idx = jnp.clip(pos // block_size, 0, max_blocks - 1)
    page = jnp.take_along_axis(block_tables, blk_idx, axis=1)
    page = jnp.where(pos < max_blocks * block_size, page, n_phys - 1)
    return page, pos % block_size


def _pool_geometry(pool: Array) -> Tuple[int, int]:
    """(n_phys, block) of a stacked paged pool leaf, (layers, [kv,]
    n_phys, d, block)."""
    return pool.shape[-3], pool.shape[-1]


def _paged_write(pool: Array, layer, new: Array, page: Array,
                 off: Array) -> Array:
    """Write new entries (b, n, [kv,] d) into layer ``layer`` of a
    stacked pool leaf at (page, offset) slots (b, n) — the XLA path's
    write (the Pallas path writes with ``paged_kv_write``).  Live-block
    destinations are disjoint by construction (writes require
    refcount-1 ownership; see ``serving.paged``); only trash-page slots
    may collide, where the winner is irrelevant."""
    if pool.ndim == 5:             # (layers, kv, n_phys, dh, bs)
        heads = jnp.arange(pool.shape[1], dtype=jnp.int32)
        at = (layer, heads, page[..., None], slice(None), off[..., None])
    else:                          # (layers, n_phys, d, bs)
        at = (layer, page, slice(None), off)
    return pool.at[at].set(new.astype(pool.dtype))


def _paged_gather(pool: Array, layer, block_tables: Array) -> Array:
    """Materialize each row's VIRTUAL contiguous cache from one layer
    of the stacked pool: + (b, max_blocks) -> (b, max_blocks*bs, ...).
    The XLA reference path for paged decode — the Pallas path never
    materializes this, its DMA index map walks the table instead."""
    b = block_tables.shape[0]
    pages = pool[(layer,) + (slice(None),) * (pool.ndim - 4)
                 + (block_tables,)]                # (b, mb, [kv,] d, bs)
    pages = jnp.moveaxis(pages, -1, 2)             # (b, mb, bs, [kv,] d)
    return pages.reshape((b, -1) + pages.shape[3:])


def _causal_mask(q_pos: Array, kv_pos: Array,
                 window: Optional[int] = None,
                 kv_valid: Optional[Array] = None,
                 block: Optional[int] = None) -> Array:
    """q_pos: (b,sq) kv_pos: (b,sk) -> (b,sq,sk) bool.  With ``block``
    the mask is block-causal: q sees every kv below the end of its own
    block (``AttentionSpec.diffusion_block``)."""
    if block is None:
        m = kv_pos[:, None, :] <= q_pos[:, :, None]
    else:
        m = kv_pos[:, None, :] < (q_pos[:, :, None] // block + 1) * block
    if window is not None:
        m &= kv_pos[:, None, :] > (q_pos[:, :, None] - window)
    if kv_valid is not None:
        m &= kv_valid[:, None, :]
    return m


# ===========================================================================
# GQA / SWA
# ===========================================================================

def _qkv(params, a: AttentionSpec, x: Array, pos: Array, theta: float
         ) -> Tuple[Array, Array, Array]:
    """q (b,s,h,dh), k and v (b,s,kv,dh) of x at positions ``pos``: the
    projections, the per-head q/k RMSNorm where the block has one, then
    rotary on q and k."""
    b, s, _ = x.shape
    q = (x @ params["wq"]).reshape(b, s, a.n_heads, a.head_dim)
    k = (x @ params["wk"]).reshape(b, s, a.n_kv_heads, a.head_dim)
    v = (x @ params["wv"]).reshape(b, s, a.n_kv_heads, a.head_dim)
    if a.qk_norm_eps is not None:
        q = rmsnorm(params["q_norm"], q, a.qk_norm_eps)
        k = rmsnorm(params["k_norm"], k, a.qk_norm_eps)
    return apply_rope(q, pos, theta), apply_rope(k, pos, theta), v


def _decode_mask(a: AttentionSpec, q_pos: Array, kv_pos: Array,
                 written: Array) -> Array:
    """The decode forwards' mask; ``written`` (b,) is each row's end of
    written positions, past which a block-causal row must not read (its
    block may reach beyond the positions this forward wrote)."""
    window = a.window if a.kind == "swa" else None
    if a.diffusion_block is None:
        return _causal_mask(q_pos, kv_pos, window)
    return _causal_mask(q_pos, kv_pos, window,
                        kv_valid=kv_pos < written[:, None],
                        block=a.diffusion_block)


def gqa_full(params, a: AttentionSpec, x: Array, positions: Array,
             theta: float, build_cache: Optional[Dict] = None,
             cache_len: int = 0, causal: bool = True,
             ) -> Tuple[Array, Optional[Dict]]:
    """Self-attention over x (train / prefill).  Optionally fills a cache."""
    b, s, d = x.shape
    q, k, v = _qkv(params, a, x, positions, theta)
    window = a.window if a.kind == "swa" else None
    if causal:
        mask = _causal_mask(positions, positions, window,
                            block=a.diffusion_block)
    else:
        mask = jnp.ones((b, s, s), bool)
    scale = 1.0 / (a.head_dim ** 0.5)
    ctx = _gqa_core(q, k, v, mask, scale)
    out = ctx.reshape(b, s, -1) @ params["wo"]
    new_cache = None
    if build_cache is not None:
        new_cache = {
            "k": jax.lax.dynamic_update_slice(
                build_cache["k"], k, (0, cache_len, 0, 0)),
            "v": jax.lax.dynamic_update_slice(
                build_cache["v"], v, (0, cache_len, 0, 0)),
        }
    return out, new_cache


def gqa_decode(params, a: AttentionSpec, x: Array, cache: Dict,
               cache_len, theta: float,
               use_kernel: bool = False) -> Tuple[Array, Dict]:
    """Multi-position decode forward: N new positions vs cache (Eq. 2).

    ``cache_len`` may be a scalar (all rows aligned) or a (b,) vector
    (scheduler-slotted cache: each batch row decodes at its own length).
    """
    b, n, d = x.shape
    s_max = cache["k"].shape[1]
    per_row = jnp.ndim(cache_len) > 0
    offsets = _row_offsets(cache_len, b)
    q_pos = offsets[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]  # (b,n)
    q, k, v = _qkv(params, a, x, q_pos, theta)
    if per_row:
        k_cache = _update_rows(cache["k"], k, offsets)
        v_cache = _update_rows(cache["v"], v, offsets)
    else:
        k_cache = jax.lax.dynamic_update_slice(cache["k"], k,
                                               (0, cache_len, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(cache["v"], v,
                                               (0, cache_len, 0, 0))
    kv_pos = jnp.broadcast_to(jnp.arange(s_max, dtype=jnp.int32)[None, :],
                              (b, s_max))
    window = a.window if a.kind == "swa" else None
    scale = 1.0 / (a.head_dim ** 0.5)
    if use_kernel:
        # Ragged per-slot fast path: the (b,) offsets vector goes straight
        # into the kernel's scalar-prefetch lane, so scheduler-slotted
        # batches (each row at its own length) share one quantized launch;
        # the scalar case is the same kernel with aligned rows.
        from repro.kernels.decode_attention.ops import decode_attention_ragged
        ctx = decode_attention_ragged(q, k_cache, v_cache, offsets,
                                      window=window,
                                      block=a.diffusion_block)
    else:
        mask = _decode_mask(a, q_pos, kv_pos, offsets + n)
        ctx = _gqa_core(q, k_cache, v_cache, mask, scale)
    out = ctx.reshape(b, n, -1) @ params["wo"]
    return out, {"k": k_cache, "v": v_cache}


def gqa_decode_paged(params, a: AttentionSpec, x: Array, pool: Dict,
                     cache_len, block_tables: Array, layer, theta: float,
                     use_kernel: bool = False) -> Tuple[Array, Dict]:
    """Paged multi-position decode: the cache is a global block pool
    (``init_paged_kv_cache``) indexed through per-row block tables.

    ``pool`` holds every layer's pages, stacked (layers, kv, n_phys, dh,
    block), and ``layer`` says which are this layer's: the N new
    positions' K/V are written straight into them, and attention runs
    over each row's virtual cache (gathered for the XLA path; walked by
    the block-table DMA index map on the Pallas path) with identical
    math to ``gqa_decode``.  Junk rows of a batched forward write to the
    trash page, so a live block is only ever written by the slot that
    owns it.  Returns the output and the updated stacked pool.
    """
    b, n, d = x.shape
    n_phys, bs = _pool_geometry(pool["k"])
    offsets = _row_offsets(cache_len, b)
    q_pos = offsets[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]
    q, k, v = _qkv(params, a, x, q_pos, theta)
    bt = jnp.asarray(block_tables, jnp.int32)
    window = a.window if a.kind == "swa" else None
    scale = 1.0 / (a.head_dim ** 0.5)
    if use_kernel:
        from repro.kernels.decode_attention.ops import (
            decode_attention_paged, paged_kv_write)
        k_pool, v_pool = pool["k"], pool["v"]
        # at most 128 new positions a launch: the write kernel holds
        # them, for every head, in VMEM
        for c0 in range(0, n, 128):
            m = min(n, c0 + 128) - c0
            first = offsets + c0
            # the pages of each row's blocks from its first new position
            # on, as many as m positions can span
            blocks = first[:, None] // bs + jnp.arange(
                (m - 1) // bs + 2, dtype=jnp.int32)[None, :]
            blocks = jnp.minimum(blocks, (first[:, None] + m - 1) // bs)
            pages, _ = _paged_slots(bt, blocks * bs, n_phys, bs)
            # static slices of the traced forward: no compile of their own
            k_pool, v_pool = paged_kv_write(
                k_pool, v_pool,
                k[:, c0:c0 + m], v[:, c0:c0 + m],  # analysis: allow-recompile-hazard
                layer, pages, first % bs)
        ctx = decode_attention_paged(q, k_pool, v_pool, offsets, bt, layer,
                                     window=window, block=a.diffusion_block)
    else:
        page, off = _paged_slots(bt, q_pos, n_phys, bs)
        k_pool = _paged_write(pool["k"], layer, k, page, off)
        v_pool = _paged_write(pool["v"], layer, v, page, off)
        k_virt = _paged_gather(k_pool, layer, bt)
        v_virt = _paged_gather(v_pool, layer, bt)
        s_virt = k_virt.shape[1]
        kv_pos = jnp.broadcast_to(
            jnp.arange(s_virt, dtype=jnp.int32)[None, :], (b, s_virt))
        mask = _decode_mask(a, q_pos, kv_pos, offsets + n)
        ctx = _gqa_core(q, k_virt, v_virt, mask, scale)
    out = ctx.reshape(b, n, -1) @ params["wo"]
    return out, {"k": k_pool, "v": v_pool}


def gqa_decode_ring(params, a: AttentionSpec, x: Array, cache: Dict,
                    cache_len, theta: float) -> Tuple[Array, Dict]:
    """Sliding-window decode over a RING buffer of size W_buf >= window+N.

    Global position p lives in slot p % W_buf; the slot's current content
    is the LARGEST written position congruent to the slot index, which is
    computable from (slot, total_written) without storing positions:
        p_s = s + W_buf * ((L_tot - 1 - s) // W_buf)   if L_tot > 0.
    Memory: O(window) instead of O(sequence) — 128x smaller for
    mixtral long_500k (window 4096 vs 524k cache).
    """
    b, n, d = x.shape
    w_buf = cache["k"].shape[1]
    q_pos = cache_len + jnp.arange(n, dtype=jnp.int32)[None, :]
    q_pos = jnp.broadcast_to(q_pos, (b, n))
    q, k, v = _qkv(params, a, x, q_pos, theta)
    slots = (cache_len + jnp.arange(n, dtype=jnp.int32)) % w_buf
    k_cache = cache["k"].at[:, slots].set(k)
    v_cache = cache["v"].at[:, slots].set(v)
    # position currently stored in each slot (after the writes above)
    l_tot = cache_len + n
    s_idx = jnp.arange(w_buf, dtype=jnp.int32)
    p_s = s_idx + w_buf * ((l_tot - 1 - s_idx) // w_buf)
    p_s = jnp.where(l_tot > 0, p_s, -1)
    kv_pos = jnp.broadcast_to(p_s[None, :], (b, w_buf))
    window = a.window or w_buf
    mask = _causal_mask(q_pos, kv_pos, window,
                        kv_valid=kv_pos >= 0)
    scale = 1.0 / (a.head_dim ** 0.5)
    ctx = _gqa_core(q, k_cache, v_cache, mask, scale)
    out = ctx.reshape(b, n, -1) @ params["wo"]
    return out, {"k": k_cache, "v": v_cache}


def cross_attention(params, a: AttentionSpec, x: Array,
                    enc_k: Array, enc_v: Array) -> Array:
    """Whisper decoder cross-attn: kv precomputed from encoder memory."""
    b, n, d = x.shape
    q = (x @ params["wq"]).reshape(b, n, a.n_heads, a.head_dim)
    mask = jnp.ones((b, n, enc_k.shape[1]), bool)
    scale = 1.0 / (a.head_dim ** 0.5)
    ctx = _gqa_core(q, enc_k, enc_v, mask, scale)
    return ctx.reshape(b, n, -1) @ params["wo"]


def encode_cross_kv(params, a: AttentionSpec, memory: Array) -> Tuple[Array, Array]:
    b, s, d = memory.shape
    k = (memory @ params["wk"]).reshape(b, s, a.n_kv_heads, a.head_dim)
    v = (memory @ params["wv"]).reshape(b, s, a.n_kv_heads, a.head_dim)
    return k, v


# ===========================================================================
# MLA (MiniCPM3 / DeepSeek-style multi-head latent attention)
# ===========================================================================

def _mla_q(params, a: AttentionSpec, x: Array, q_pos: Array, theta: float):
    b, n, _ = x.shape
    qk_h = a.qk_nope_head_dim + a.qk_rope_head_dim
    q = rmsnorm(params["q_norm"], x @ params["wq_a"]) @ params["wq_b"]
    q = q.reshape(b, n, a.n_heads, qk_h)
    q_nope = q[..., : a.qk_nope_head_dim]
    q_rope = apply_rope(q[..., a.qk_nope_head_dim:], q_pos, theta)
    return q_nope, q_rope


def _mla_latent(params, a: AttentionSpec, x: Array, pos: Array, theta: float):
    kv = x @ params["wkv_a"]
    latent = rmsnorm(params["kv_norm"], kv[..., : a.kv_lora_rank])
    k_rope = kv[..., a.kv_lora_rank:]
    # shared-rope key: rotate as a single "head"
    k_rope = apply_rope(k_rope[..., None, :], pos, theta)[..., 0, :]
    return latent, k_rope


def mla_full(params, a: AttentionSpec, x: Array, positions: Array,
             theta: float, build_cache: Optional[Dict] = None,
             cache_len: int = 0) -> Tuple[Array, Optional[Dict]]:
    """Non-absorbed MLA for train/prefill: decompress K/V and run GQA-style."""
    b, s, _ = x.shape
    q_nope, q_rope = _mla_q(params, a, x, positions, theta)
    latent, k_rope = _mla_latent(params, a, x, positions, theta)
    wkv_b = params["wkv_b"].reshape(a.kv_lora_rank, a.n_heads,
                                    a.qk_nope_head_dim + a.v_head_dim)
    kv = jnp.einsum("bsl,lhd->bshd", latent, wkv_b)
    k_nope = kv[..., : a.qk_nope_head_dim]
    v = kv[..., a.qk_nope_head_dim:]
    scale = 1.0 / ((a.qk_nope_head_dim + a.qk_rope_head_dim) ** 0.5)
    mask = _causal_mask(positions, positions)
    scores = (jnp.einsum("bqhd,bshd->bhqs", q_nope, k_nope)
              + jnp.einsum("bqhd,bsd->bhqs", q_rope, k_rope))
    scores = scores.astype(jnp.float32) * scale
    scores = jnp.where(mask[:, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhqs,bshd->bqhd", probs, v)
    out = ctx.reshape(b, s, -1) @ params["wo"]
    new_cache = None
    if build_cache is not None:
        new_cache = {
            "latent": jax.lax.dynamic_update_slice(
                build_cache["latent"], latent, (0, cache_len, 0)),
            "k_rope": jax.lax.dynamic_update_slice(
                build_cache["k_rope"], k_rope, (0, cache_len, 0)),
        }
    return out, new_cache


def mla_decode(params, a: AttentionSpec, x: Array, cache: Dict,
               cache_len, theta: float) -> Tuple[Array, Dict]:
    """Absorbed MLA decode: scores computed directly against the latent
    cache (KV traffic = latent bytes — the d_latent term in the NFP model)."""
    b, n, _ = x.shape
    s_max = cache["latent"].shape[1]
    per_row = jnp.ndim(cache_len) > 0
    offsets = _row_offsets(cache_len, b)
    q_pos = offsets[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]
    q_nope, q_rope = _mla_q(params, a, x, q_pos, theta)
    latent_new, k_rope_new = _mla_latent(params, a, x, q_pos, theta)
    if per_row:
        latent = _update_rows(cache["latent"], latent_new, offsets)
        k_rope = _update_rows(cache["k_rope"], k_rope_new, offsets)
    else:
        latent = jax.lax.dynamic_update_slice(cache["latent"], latent_new,
                                              (0, cache_len, 0))
        k_rope = jax.lax.dynamic_update_slice(cache["k_rope"], k_rope_new,
                                              (0, cache_len, 0))
    wkv_b = params["wkv_b"].reshape(a.kv_lora_rank, a.n_heads,
                                    a.qk_nope_head_dim + a.v_head_dim)
    wk = wkv_b[..., : a.qk_nope_head_dim]           # (lora, h, d_nope)
    wv = wkv_b[..., a.qk_nope_head_dim:]            # (lora, h, d_v)
    # absorb the key decompression into the query
    q_lat = jnp.einsum("bqhd,lhd->bqhl", q_nope, wk)
    scores = (jnp.einsum("bqhl,bsl->bhqs", q_lat, latent)
              + jnp.einsum("bqhd,bsd->bhqs", q_rope, k_rope))
    scale = 1.0 / ((a.qk_nope_head_dim + a.qk_rope_head_dim) ** 0.5)
    kv_pos = jnp.broadcast_to(jnp.arange(s_max, dtype=jnp.int32)[None, :],
                              (b, s_max))
    mask = _causal_mask(q_pos, kv_pos)
    scores = jnp.where(mask[:, None, :, :], scores.astype(jnp.float32) * scale,
                       -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    ctx_lat = jnp.einsum("bhqs,bsl->bqhl", probs, latent)
    ctx = jnp.einsum("bqhl,lhd->bqhd", ctx_lat, wv)
    out = ctx.reshape(b, n, -1) @ params["wo"]
    return out, {"latent": latent, "k_rope": k_rope}


def mla_decode_paged(params, a: AttentionSpec, x: Array, pool: Dict,
                     cache_len, block_tables: Array, layer, theta: float
                     ) -> Tuple[Array, Dict]:
    """Absorbed MLA decode over the stacked paged latent pool (layers,
    n_phys, d, block), writing and reading layer ``layer`` (XLA path
    only — the Pallas kernel serves GQA/SWA geometries, as in the dense
    case)."""
    b, n, _ = x.shape
    n_phys, bs = _pool_geometry(pool["latent"])
    offsets = _row_offsets(cache_len, b)
    q_pos = offsets[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]
    q_nope, q_rope = _mla_q(params, a, x, q_pos, theta)
    latent_new, k_rope_new = _mla_latent(params, a, x, q_pos, theta)
    bt = jnp.asarray(block_tables, jnp.int32)
    page, off = _paged_slots(bt, q_pos, n_phys, bs)
    latent_pool = _paged_write(pool["latent"], layer, latent_new, page, off)
    k_rope_pool = _paged_write(pool["k_rope"], layer, k_rope_new, page, off)
    latent = _paged_gather(latent_pool, layer, bt)
    k_rope = _paged_gather(k_rope_pool, layer, bt)
    s_virt = latent.shape[1]
    wkv_b = params["wkv_b"].reshape(a.kv_lora_rank, a.n_heads,
                                    a.qk_nope_head_dim + a.v_head_dim)
    wk = wkv_b[..., : a.qk_nope_head_dim]
    wv = wkv_b[..., a.qk_nope_head_dim:]
    q_lat = jnp.einsum("bqhd,lhd->bqhl", q_nope, wk)
    scores = (jnp.einsum("bqhl,bsl->bhqs", q_lat, latent)
              + jnp.einsum("bqhd,bsd->bhqs", q_rope, k_rope))
    scale = 1.0 / ((a.qk_nope_head_dim + a.qk_rope_head_dim) ** 0.5)
    kv_pos = jnp.broadcast_to(jnp.arange(s_virt, dtype=jnp.int32)[None, :],
                              (b, s_virt))
    mask = _causal_mask(q_pos, kv_pos)
    scores = jnp.where(mask[:, None, :, :], scores.astype(jnp.float32) * scale,
                       -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    ctx_lat = jnp.einsum("bhqs,bsl->bqhl", probs, latent)
    ctx = jnp.einsum("bqhl,lhd->bqhd", ctx_lat, wv)
    out = ctx.reshape(b, n, -1) @ params["wo"]
    return out, {"latent": latent_pool, "k_rope": k_rope_pool}


# ===========================================================================
# Dispatch
# ===========================================================================

def attention_full(params, a: AttentionSpec, x, positions, theta,
                   build_cache=None, cache_len: int = 0, causal: bool = True):
    if a.kind == "mla":
        return mla_full(params, a, x, positions, theta, build_cache, cache_len)
    return gqa_full(params, a, x, positions, theta, build_cache, cache_len,
                    causal)


def attention_decode(params, a: AttentionSpec, x, cache, cache_len, theta,
                     use_kernel: bool = False, swa_ring: bool = False,
                     block_tables=None, layer=None):
    """Decode-mode attention.  With ``block_tables``, ``cache`` is the
    whole stacked paged pool and ``layer`` this layer's index in it."""
    if block_tables is not None:
        if a.kind == "mla":
            return mla_decode_paged(params, a, x, cache, cache_len,
                                    block_tables, layer, theta)
        return gqa_decode_paged(params, a, x, cache, cache_len, block_tables,
                                layer, theta, use_kernel)
    if a.kind == "mla":
        return mla_decode(params, a, x, cache, cache_len, theta)
    if swa_ring and a.kind == "swa":
        return gqa_decode_ring(params, a, x, cache, cache_len, theta)
    return gqa_decode(params, a, x, cache, cache_len, theta, use_kernel)
