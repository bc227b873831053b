"""Mixture-of-Experts FFN: top-k routing + grouped GEMM (ragged_dot).

Dispatch pipeline (paper Sec. 3.3: dispatch -> expert FFN -> weighted
combine):
  1. router logits -> top-k expert ids + renormalized weights,
  2. token-expert pairs sorted by expert id (contiguous expert groups),
  3. grouped GEMM over expert groups via ``jax.lax.ragged_dot`` —
     the XLA-native analogue of the fused MoE kernels the paper inspects.
     The Pallas path (``repro.kernels.moe_ffn``) additionally pads each
     group to the ``token_block`` granularity — the M_moe mechanism.
  4. weighted scatter-add combine (eta = 2 accesses, Eq. 17).

Controlled routing (paper App. C.3.1) is supported via
``routing_override`` so benchmarks can reproduce the load-balanced
(round-robin, Eq. 25) and load-skewed patterns exactly.

Expert share (``FFNSpec.held`` / ``first_held``): a chip of an
expert-parallel deployment holds only some experts' weights.  The
router still spans all ``n_experts`` and the top-k weights are
normalized over all k picks; the layer then computes its held experts'
part alone — token-expert pairs routed elsewhere are sorted past the
held groups and carry zero weight in the combine.  Nothing stands in
for the absent experts: summing every share's output gives the uncut
layer.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.arch import FFNSpec
from repro.models.layers import _init

Array = jax.Array


def init_moe(key, d_model: int, f: FFNSpec, dtype=jnp.bfloat16) -> Dict:
    ks = jax.random.split(key, 5)
    e, dff = f.n_held, f.d_ff
    p = {
        "router": _init(ks[0], (d_model, f.n_experts), scale=0.02,
                        dtype=jnp.float32),
        "w_up": _init(ks[1], (e, d_model, dff), dtype=dtype),
        "w_down": _init(ks[2], (e, dff, d_model), dtype=dtype),
    }
    if f.activation == "swiglu":
        p["w_gate"] = _init(ks[3], (e, d_model, dff), dtype=dtype)
    if f.n_shared_experts:
        p["shared_up"] = _init(ks[4], (d_model, f.n_shared_experts * dff),
                               dtype=dtype)
        p["shared_down"] = _init(
            jax.random.fold_in(ks[4], 1), (f.n_shared_experts * dff, d_model),
            dtype=dtype)
    return p


def route_topk(router_w: Array, x: Array, k: int) -> Tuple[Array, Array, Array]:
    """Returns (weights (T,k) f32, idx (T,k) i32, router_probs (T,E) f32)."""
    logits = (x.astype(jnp.float32) @ router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(logits, k)
    weights = jax.nn.softmax(top_vals, axis=-1)     # renormalize over top-k
    return weights, top_idx, probs


def balanced_routing(n_tokens: int, k: int, n_experts: int) -> Array:
    """Paper Eq. 25: round-robin {(i*k + j) mod E} — the load-balanced
    (upper-bound) controlled pattern."""
    i = jnp.arange(n_tokens, dtype=jnp.int32)[:, None]
    j = jnp.arange(k, dtype=jnp.int32)[None, :]
    return (i * k + j) % n_experts


def skewed_routing(n_tokens: int, k: int, n_experts: int) -> Array:
    """All tokens on the same k experts — the load-skewed (lower-bound)
    pattern."""
    del n_experts
    j = jnp.arange(k, dtype=jnp.int32)[None, :]
    return jnp.broadcast_to(j, (n_tokens, k))


def moe_ffn(params, f: FFNSpec, x: Array,
            routing_override: Optional[Tuple[Array, Array]] = None,
            use_kernel: bool = False,
            ) -> Tuple[Array, Array]:
    """x: (..., d) -> (out (..., d), aux_loss scalar).

    routing_override: (idx (T,k), weights (T,k)) for controlled patterns.
    """
    orig_shape = x.shape
    d = orig_shape[-1]
    xt = x.reshape(-1, d)
    t = xt.shape[0]
    e, k = f.n_experts, f.top_k

    if routing_override is not None:
        top_idx, weights = routing_override
        weights = weights.astype(jnp.float32)
        aux = jnp.zeros((), jnp.float32)
    else:
        weights, top_idx, probs = route_topk(params["router"], xt, k)
        # switch-style load-balance aux loss
        frac = jnp.mean(jax.nn.one_hot(top_idx, e, dtype=jnp.float32),
                        axis=(0, 1))
        imp = jnp.mean(probs, axis=0)
        aux = e * jnp.sum(frac * imp)

    # the held experts' part of the layer: dispatch, grouped GEMM over the
    # held groups, weighted combine
    with jax.named_scope("experts"):
        # --- dispatch: sort token-expert pairs by held expert -------------
        # pairs routed to experts held elsewhere take key ``held``: they
        # sort past every held group, and no group covers them
        held = f.n_held
        local = top_idx.reshape(-1) - f.first_held    # (T*k,)
        is_held = (local >= 0) & (local < held)
        key = jnp.where(is_held, local, held)
        flat_w = weights.reshape(-1)
        order = jnp.argsort(key)                      # stable
        token_of_pair = order // k
        x_sorted = xt[token_of_pair]                  # (T*k, d)
        group_sizes = jnp.bincount(key, length=held + 1)[:held].astype(
            jnp.int32)

        # --- expert FFN: grouped GEMM -------------------------------------
        if use_kernel:
            if held < e:
                # the kernel gives every sorted row an expert: the pairs
                # past the held groups would land in the last group's
                raise ValueError("the grouped moe_ffn kernel serves layers "
                                 "that hold every expert")
            from repro.kernels.moe_ffn.ops import grouped_ffn
            h_out = grouped_ffn(x_sorted, params, group_sizes, f.activation,
                                n_tokens=t)
        else:
            up = jax.lax.ragged_dot(x_sorted, params["w_up"], group_sizes)
            if f.activation == "swiglu":
                gate = jax.lax.ragged_dot(x_sorted, params["w_gate"],
                                          group_sizes)
                h = (jax.nn.silu(gate.astype(jnp.float32))
                     * up.astype(jnp.float32)).astype(x.dtype)
            else:
                h = jax.nn.gelu(up.astype(jnp.float32)).astype(x.dtype)
            h_out = jax.lax.ragged_dot(h, params["w_down"], group_sizes)

        # --- combine: weighted scatter-add (eta = 2 accesses, Eq. 17) -----
        # rows past the held groups hold whatever the GEMM left there:
        # select, never multiply, them away
        contrib = jnp.where(
            is_held[order][:, None],
            h_out.astype(jnp.float32) * flat_w[order][:, None], 0.0)
        out = jnp.zeros((t, d), jnp.float32).at[token_of_pair].add(contrib)
    out = out.astype(x.dtype)

    if f.n_shared_experts:
        sh = jax.nn.gelu((xt @ params["shared_up"]).astype(jnp.float32))
        out = out + (sh.astype(x.dtype) @ params["shared_down"])

    return out.reshape(orig_shape), aux

