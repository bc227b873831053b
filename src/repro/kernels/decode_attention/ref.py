"""Pure-jnp oracle for multi-position decode attention (aligned + ragged)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def decode_attention_ref(q, k_cache, v_cache, cache_len, *,
                         window: Optional[int] = None,
                         block: Optional[int] = None):
    """q: (b, n, h, dh); k/v_cache: (b, s, kv, dh); cache_len: scalar or (b,).

    The N query positions of row b sit at global positions
    cache_len[b] .. cache_len[b]+N-1 (their K/V already written into the
    cache).  A scalar ``cache_len`` is the aligned case; a (b,) vector is
    the scheduler's ragged per-slot case.  ``block``: block-causal — a
    query sees every position of its own block, but none past the N
    written ones.  Returns (b, n, h, dh).
    """
    b, n, h, dh = q.shape
    s = k_cache.shape[1]
    kv = k_cache.shape[2]
    g = h // kv
    scale = 1.0 / (dh ** 0.5)
    lens = jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32).reshape(-1), (b,))
    q_pos = lens[:, None] + jnp.arange(n, dtype=jnp.int32)[None]     # (b, n)
    kv_pos = jnp.arange(s, dtype=jnp.int32)                          # (s,)
    if block is None:
        mask = kv_pos[None, None, :] <= q_pos[:, :, None]            # (b,n,s)
    else:
        mask = ((kv_pos[None, None, :] < (q_pos[:, :, None] // block + 1)
                 * block)
                & (kv_pos[None, None, :] < (lens + n)[:, None, None]))
    if window is not None:
        mask &= kv_pos[None, None, :] > (q_pos[:, :, None] - window)
    qg = q.reshape(b, n, kv, g, dh)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_cache)
    scores = scores.astype(jnp.float32) * scale
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    ctx = jnp.einsum("bkgqs,bskd->bqkgd", probs, v_cache)
    return ctx.reshape(b, n, h, dh)
