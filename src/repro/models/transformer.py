"""Composable decoder / encoder-decoder assembly for all 10 assigned archs.

Layers are grouped into *segments* of identical kind (attn / ssm / hybrid)
and each segment is executed with ``jax.lax.scan`` over stacked per-layer
parameters — the HLO contains one layer body per segment regardless of
depth, which keeps multi-pod dry-run compiles tractable and lets XLA
overlap per-layer collectives with the next iteration's compute.

Modes:
  train   — full causal self-attention, no cache, returns logits (+aux).
  prefill — same math, fills the pre-allocated decode cache.
  decode  — the paper's multi-position decode forward (Eq. 2): N new
            positions against a cache of length ``cache_len``.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.arch import (LAYER_ATTN, LAYER_HYBRID, LAYER_SSM, ArchConfig)
from repro.models.attention import (attention_decode, attention_full,
                                    cross_attention, encode_cross_kv,
                                    init_attention, init_kv_cache,
                                    init_paged_kv_cache)
from repro.models.layers import (embed, init_embedding, init_lm_head,
                                 init_mlp, init_rmsnorm, lm_head, mlp,
                                 rmsnorm, unembed_tied)
from repro.models.mamba import (init_mamba1, init_mamba1_state, init_mamba2,
                                init_mamba2_state, mamba1_block, mamba2_block)
from repro.models.moe import init_moe, moe_ffn

Array = jax.Array


# ===========================================================================
# Segments
# ===========================================================================

def make_segments(cfg: ArchConfig) -> List[Tuple[str, int]]:
    """Group the layer pattern into runs of identical kind."""
    segs: List[Tuple[str, int]] = []
    for kind in cfg.pattern():
        if segs and segs[-1][0] == kind:
            segs[-1] = (kind, segs[-1][1] + 1)
        else:
            segs.append((kind, 1))
    return segs


def _tree_stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


# ===========================================================================
# Init
# ===========================================================================

def _init_layer(key, cfg: ArchConfig, kind: str, dtype) -> Dict:
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    p: Dict = {"ln1": init_rmsnorm(d, dtype)}
    if kind == LAYER_ATTN:
        p["attn"] = init_attention(ks[0], d, cfg.attention, dtype)
        p["ln2"] = init_rmsnorm(d, dtype)
        if cfg.ffn.kind == "moe":
            p["ffn"] = init_moe(ks[1], d, cfg.ffn, dtype)
        elif cfg.ffn.kind == "dense":
            p["ffn"] = init_mlp(ks[1], d, cfg.ffn.d_ff, cfg.ffn.activation,
                                dtype)
        if cfg.encoder is not None:  # whisper decoder layer: cross-attn
            p["ln_cross"] = init_rmsnorm(d, dtype)
            p["cross"] = init_attention(ks[2], d, cfg.attention, dtype)
    elif kind == LAYER_SSM:
        init_fn = init_mamba1 if cfg.ssm.kind == "mamba1" else init_mamba2
        p["ssm"] = init_fn(ks[0], d, cfg.ssm, dtype)
    elif kind == LAYER_HYBRID:
        init_fn = init_mamba1 if cfg.ssm.kind == "mamba1" else init_mamba2
        p["ssm"] = init_fn(ks[0], d, cfg.ssm, dtype)
        p["ln_shared"] = init_rmsnorm(d, dtype)
    return p


def init_model(key, cfg: ArchConfig, dtype=jnp.bfloat16) -> Dict:
    keys = jax.random.split(key, cfg.n_layers + 8)
    params: Dict = {
        "embed": init_embedding(keys[-1], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": init_rmsnorm(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_lm_head(keys[-2], cfg.d_model,
                                         cfg.vocab_size, dtype)
    segs, li = [], 0
    for kind, count in make_segments(cfg):
        layers = [_init_layer(keys[li + i], cfg, kind, dtype)
                  for i in range(count)]
        li += count
        segs.append(_tree_stack(layers))
    params["segments"] = segs
    if cfg.shared_attention:
        params["shared_attn"] = {
            "attn": init_attention(keys[-3], cfg.d_model, cfg.attention,
                                   dtype),
            "ln2": init_rmsnorm(cfg.d_model, dtype),
            "ffn": init_mlp(keys[-4], cfg.d_model,
                            cfg.ffn.d_ff or 4 * cfg.d_model,
                            cfg.ffn.activation, dtype),
        }
    if cfg.encoder is not None:
        enc_layers = []
        for i in range(cfg.encoder.n_layers):
            k = jax.random.fold_in(keys[-5], i)
            ks = jax.random.split(k, 2)
            enc_layers.append({
                "ln1": init_rmsnorm(cfg.d_model, dtype),
                "attn": init_attention(ks[0], cfg.d_model, cfg.attention,
                                       dtype),
                "ln2": init_rmsnorm(cfg.d_model, dtype),
                "ffn": init_mlp(ks[1], cfg.d_model, cfg.ffn.d_ff,
                                cfg.ffn.activation, dtype),
            })
        params["encoder"] = {
            "layers": _tree_stack(enc_layers),
            "final_norm": init_rmsnorm(cfg.d_model, dtype),
        }
    return params


def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, swa_ring: bool = False,
               ring_headroom: int = 128) -> Dict:
    """Pre-allocated decode state for every segment (App. C.1.3 discipline).

    swa_ring: sliding-window archs allocate an O(window) RING buffer
    (window + ring_headroom decode positions, 16-aligned) instead of
    O(max_len) — pair with forward(..., swa_ring=True)."""
    attn_len = max_len
    if (swa_ring and cfg.attention is not None
            and cfg.attention.kind == "swa" and cfg.attention.window):
        ring = ((cfg.attention.window + ring_headroom + 15) // 16) * 16
        attn_len = min(max_len, ring)
    segs = []
    for kind, count in make_segments(cfg):
        if kind == LAYER_ATTN:
            c = [init_kv_cache(batch, attn_len, cfg.attention, dtype)
                 for _ in range(count)]
            segs.append(_tree_stack(c))
        elif kind == LAYER_SSM:
            fn = (init_mamba1_state if cfg.ssm.kind == "mamba1"
                  else init_mamba2_state)
            segs.append(_tree_stack([fn(batch, cfg.d_model, cfg.ssm)
                                     for _ in range(count)]))
        else:  # hybrid: ssm state + shared-attn kv cache
            fn = (init_mamba1_state if cfg.ssm.kind == "mamba1"
                  else init_mamba2_state)
            c = [{"ssm_state": fn(batch, cfg.d_model, cfg.ssm),
                  "attn": init_kv_cache(batch, max_len, cfg.attention, dtype)}
                 for _ in range(count)]
            segs.append(_tree_stack(c))
    return {"segments": segs}


def init_paged_cache(cfg: ArchConfig, n_phys: int, block_size: int,
                     dtype=jnp.bfloat16) -> Dict:
    """Paged decode state: every attention layer shares ONE logical
    block layout (the per-slot block tables in ``serving.paged``), each
    layer owning its own pages of a stacked pool — GQA/MHA K and V
    (layers, kv, n_phys, head_dim, block_size), MLA (layers, n_phys, d,
    block_size).  Paging covers KV caches only — recurrent (SSM/hybrid)
    state and encoder memory have no sequence axis to page, so those
    archs keep the dense cache (``DecodeEngine`` rejects them in paged
    mode)."""
    segs = []
    for kind, count in make_segments(cfg):
        if kind != LAYER_ATTN:
            raise ValueError("paged KV cache supports attention-only "
                             f"architectures; {cfg.name} has a {kind} segment")
        c = [init_paged_kv_cache(n_phys, block_size, cfg.attention, dtype)
             for _ in range(count)]
        segs.append(_tree_stack(c))
    return {"segments": segs}


# ===========================================================================
# Layer bodies
# ===========================================================================

def _ffn_apply(lp, cfg: ArchConfig, h: Array, routing_override):
    if cfg.ffn.kind == "moe":
        out, aux = moe_ffn(lp["ffn"], cfg.ffn, h,
                           routing_override=routing_override)
        return out, aux
    if cfg.ffn.kind == "dense":
        return mlp(lp["ffn"], h, cfg.ffn.activation), jnp.zeros((), jnp.float32)
    return jnp.zeros_like(h), jnp.zeros((), jnp.float32)


def _attn_layer(lp, cfg: ArchConfig, x: Array, positions, cache, cache_len,
                mode: str, use_kernel: bool, routing_override,
                memory: Optional[Array], swa_ring: bool = False,
                block_tables=None, layer=None):
    # named scopes are path components of every op's ``tf_op`` in a
    # profiler trace: device time splits by layer part with no flag
    with jax.named_scope("attention"):
        h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
        if mode == "decode":
            att, new_cache = attention_decode(lp["attn"], cfg.attention, h,
                                              cache, cache_len, cfg.rope_theta,
                                              use_kernel, swa_ring,
                                              block_tables=block_tables,
                                              layer=layer)
        else:
            att, new_cache = attention_full(lp["attn"], cfg.attention, h,
                                            positions, cfg.rope_theta,
                                            build_cache=cache, cache_len=0)
        x = x + att
        if memory is not None and "cross" in lp:
            hc = rmsnorm(lp["ln_cross"], x, cfg.norm_eps)
            ck, cv = encode_cross_kv(lp["cross"], cfg.attention, memory)
            x = x + cross_attention(lp["cross"], cfg.attention, hc, ck, cv)
    with jax.named_scope("ffn"):
        h2 = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        ff, aux = _ffn_apply(lp, cfg, h2, routing_override)
        x = x + ff
    return x, new_cache, aux


def _ssm_layer(lp, cfg: ArchConfig, x: Array, state, use_kernel: bool):
    h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
    block = mamba1_block if cfg.ssm.kind == "mamba1" else mamba2_block
    if cfg.ssm.kind == "mamba1":
        out, new_state = block(lp["ssm"], cfg.ssm, h, state, use_kernel)
    else:
        out, new_state = block(lp["ssm"], cfg.ssm, h, state)
    return x + out, new_state


def _hybrid_layer(lp, shared, cfg: ArchConfig, x: Array, positions, cache,
                  cache_len, mode: str, use_kernel: bool):
    ssm_state = None if cache is None else cache["ssm_state"]
    x, new_ssm = _ssm_layer(lp, cfg, x, ssm_state, use_kernel)
    # shared attention block (zamba2-style: one param set reused)
    h = rmsnorm(lp["ln_shared"], x, cfg.norm_eps)
    attn_cache = None if cache is None else cache["attn"]
    if mode == "decode":
        att, new_attn = attention_decode(shared["attn"], cfg.attention, h,
                                         attn_cache, cache_len,
                                         cfg.rope_theta, use_kernel)
    else:
        att, new_attn = attention_full(shared["attn"], cfg.attention, h,
                                       positions, cfg.rope_theta,
                                       build_cache=attn_cache, cache_len=0)
    x = x + att
    h2 = rmsnorm(shared["ln2"], x, cfg.norm_eps)
    x = x + mlp(shared["ffn"], h2, cfg.ffn.activation)
    if cache is None:
        return x, None
    return x, {"ssm_state": new_ssm, "attn": new_attn}


# ===========================================================================
# Forward
# ===========================================================================

def _sinusoidal(positions: Array, d: int) -> Array:
    half = d // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def encode(params, cfg: ArchConfig, frames: Array) -> Array:
    """Whisper-style encoder over stub frame embeddings (b, F, d)."""
    b, f, d = frames.shape
    pos = jnp.broadcast_to(jnp.arange(f, dtype=jnp.int32)[None], (b, f))
    x = (frames.astype(jnp.float32) + _sinusoidal(pos, d)).astype(frames.dtype)
    ep = params["encoder"]

    def body(carry, lp):
        x = carry
        h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
        att, _ = attention_full(lp["attn"], cfg.attention, h, pos,
                                cfg.rope_theta, causal=False)
        x = x + att
        h2 = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        return x + mlp(lp["ffn"], h2, cfg.ffn.activation), 0.0

    x, _ = jax.lax.scan(body, x, ep["layers"])
    return rmsnorm(ep["final_norm"], x, cfg.norm_eps)


def forward(params, cfg: ArchConfig, inputs: Dict, *, mode: str = "train",
            cache: Optional[Dict] = None, cache_len=0,
            use_kernel: bool = False, routing_override=None,
            remat=False, swa_ring: bool = False, block_tables=None,
            last: Optional[Array] = None,
            ) -> Tuple[Array, Optional[Dict], Array, Array]:
    """Returns (logits, new_cache, moe_aux_loss, hidden).

    ``last`` (b,) i32 keeps one position of each row: logits and hidden
    come back (b, 1, ...) at those positions, and the output head runs
    on them alone (an admission needs only each prompt's last logits).

    ``block_tables`` (b, max_blocks) i32 switches decode-mode attention
    onto the PAGED cache path: ``cache`` must then be an
    ``init_paged_cache`` pool and ``cache_len`` a (b,) per-slot length
    vector (``serving.paged`` owns the table bookkeeping).  The layer
    scan then carries the stacked pool instead of slicing it per layer:
    each layer writes its new positions into the carry and attention
    reads its pages there, so the pool is never copied, and under a
    caller that donates it the returned pool is the same buffer.

    ``hidden`` is the final-norm output (b, s, d) — the representation
    the LM head (and any auxiliary head bank, e.g. MTP) reads.  Serving
    threads it out so multi-token-prediction proposals consume the real
    last hidden state rather than an embedding-row proxy.

    inputs: {"tokens": (b,s) i32} or {"embeds": (b,s,d)}; whisper adds
    {"frames": (b,F,d)} (stub frontend output).

    remat: False / True / float fraction in (0,1) — fractional remat
    checkpoints only the first ceil(frac*L) layers of each segment and
    saves the rest's activations (perf iteration #3: cuts the recompute
    flops multiplier from 4x toward 3x where memory allows).
    """
    if "embeds" in inputs:
        x = inputs["embeds"]
    else:
        x = embed(params["embed"], inputs["tokens"])
    b, s = x.shape[0], x.shape[1]

    memory = None
    if cfg.encoder is not None:
        memory = encode(params, cfg, inputs["frames"])
        pos0 = jnp.asarray(cache_len if mode == "decode" else 0, jnp.int32)
        if pos0.ndim == 1:                       # per-slot cache lengths
            pos0 = pos0[:, None]
        tok_pos = pos0 + jnp.arange(s, dtype=jnp.int32)[None]
        x = (x.astype(jnp.float32)
             + _sinusoidal(jnp.broadcast_to(tok_pos, (b, s)), cfg.d_model)
             ).astype(x.dtype)

    if mode == "decode":
        positions = None
    else:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                                     (b, s))

    shared = params.get("shared_attn")
    aux_total = jnp.zeros((), jnp.float32)
    new_segments = []
    segments = make_segments(cfg)
    for si, (kind, count) in enumerate(segments):
        sp = params["segments"][si]
        seg_cache = None if cache is None else cache["segments"][si]

        if kind == LAYER_ATTN and block_tables is not None:
            def body(carry, inp):
                (x, pool), (lp, layer) = carry, inp
                y, pool, aux = _attn_layer(lp, cfg, x, positions, pool,
                                           cache_len, mode, use_kernel,
                                           routing_override, memory,
                                           swa_ring, block_tables, layer)
                return (y, pool), aux
        elif kind == LAYER_ATTN:
            def body(x, inp, _kind=kind):
                lp, lc = inp
                y, nc, aux = _attn_layer(lp, cfg, x, positions, lc, cache_len,
                                         mode, use_kernel, routing_override,
                                         memory, swa_ring, block_tables)
                return y, (nc, aux)
        elif kind == LAYER_SSM:
            def body(x, inp, _kind=kind):
                lp, lc = inp
                y, ns = _ssm_layer(lp, cfg, x, lc, use_kernel)
                return y, (ns, jnp.zeros((), jnp.float32))
        else:
            def body(x, inp, _kind=kind):
                lp, lc = inp
                y, nc = _hybrid_layer(lp, shared, cfg, x, positions, lc,
                                      cache_len, mode, use_kernel)
                return y, (nc, jnp.zeros((), jnp.float32))

        frac = (1.0 if remat is True else
                0.0 if remat is False else float(remat))

        # each scan over the stacked layers runs under "layers": its own
        # slicing and updating of stacked weights and cache is the part of
        # that scope outside "attention" and "ffn"
        if cache is None:
            # scan without cache: feed layer params only
            def body_nc(x, lp, _body=body):
                y, (nc, aux) = _body(x, (lp, None))
                return y, aux
            n_re = int(round(frac * count))
            aux_parts = []
            if n_re > 0:
                sp_re = (jax.tree.map(lambda a: a[:n_re], sp)
                         if n_re < count else sp)
                with jax.named_scope("layers"):
                    x, a1 = jax.lax.scan(jax.checkpoint(body_nc), x, sp_re)
                aux_parts.append(a1)
            if n_re < count:
                sp_pl = (jax.tree.map(lambda a: a[n_re:], sp)
                         if n_re > 0 else sp)
                with jax.named_scope("layers"):
                    x, a2 = jax.lax.scan(body_nc, x, sp_pl)
                aux_parts.append(a2)
            auxs = jnp.concatenate([jnp.atleast_1d(a) for a in aux_parts])
            new_segments.append(None)
        else:
            if frac > 0:
                body = jax.checkpoint(body)
            with jax.named_scope("layers"):
                if block_tables is not None:
                    layer_ids = jnp.arange(count, dtype=jnp.int32)
                    (x, ncs), auxs = jax.lax.scan(body, (x, seg_cache),
                                                  (sp, layer_ids))
                else:
                    x, (ncs, auxs) = jax.lax.scan(body, x, (sp, seg_cache))
            new_segments.append(ncs)
        aux_total = aux_total + jnp.sum(auxs)

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if last is not None:
        x = jnp.take_along_axis(x, last[:, None, None], axis=1)
    if cfg.tie_embeddings:
        logits = unembed_tied(params["embed"], x)
    else:
        logits = lm_head(params["lm_head"], x)
    new_cache = None if cache is None else {"segments": new_segments}
    return logits, new_cache, aux_total, x
