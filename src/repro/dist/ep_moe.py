"""Expert-parallel MoE FFN: ``shard_map`` over an expert-sharded mesh.

The single-device reference (``repro.models.moe.moe_ffn``) sorts
token-expert pairs and runs one grouped GEMM.  At scale the expert
tables live sharded over the ``model`` mesh axis, and each decode step
runs the paper's dispatch -> expert FFN -> combine pipeline (Sec. 3.3)
across chips:

  1. every shard routes its LOCAL tokens (router weights replicated),
  2. token activations are packed into per-expert capacity buffers and
     exchanged with one ``all_to_all`` (dispatch),
  3. each shard runs its resident experts' FFN as one batched GEMM over
     the received buffers,
  4. a second ``all_to_all`` returns expert outputs to the token's home
     shard, where the weighted combine (eta = 2 accesses, Eq. 17) runs.

Capacity semantics match production EP stacks: each (source shard,
expert) pair owns ``capacity`` token slots; overflow tokens are dropped
from that expert's contribution (their routing weight is simply lost),
which keeps the exchange statically shaped.  ``capacity_factor`` large
enough (>= E/k) guarantees zero drops and bit-compatible-modulo-
summation-order agreement with the reference.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.arch import FFNSpec
from repro.core.granularity import round_up
from repro.models.moe import route_topk

Array = jax.Array


def _pad_experts(w: Array, e_pad: int) -> Array:
    e = w.shape[0]
    if e_pad == e:
        return w
    pad = jnp.zeros((e_pad - e, *w.shape[1:]), w.dtype)
    return jnp.concatenate([w, pad], axis=0)


def ep_moe_ffn(params: Dict, f: FFNSpec, x: Array, mesh: Mesh, *,
               axis: str = "model", capacity_factor: float = 1.0) -> Array:
    """Expert-parallel ``moe_ffn`` forward.

    x: (T, d) global token activations, sharded ``P(axis, None)``;
    returns (T, d) with the same sharding.  Numerically matches
    ``moe_ffn(params, f, x)[0]`` when no capacity drops occur.
    """
    n_ep = mesh.shape[axis]
    e, k = f.n_experts, f.top_k
    d = x.shape[-1]
    if x.ndim != 2:
        raise ValueError(f"ep_moe_ffn expects (T, d) tokens, got {x.shape}")
    if x.shape[0] % n_ep:
        raise ValueError(f"T={x.shape[0]} not divisible by EP size {n_ep}")
    t_loc = x.shape[0] // n_ep
    # experts padded so every shard holds the same number of tables;
    # the router never selects a padded expert, so its zero weights are dead
    e_pad = round_up(e, n_ep)
    e_loc = e_pad // n_ep
    # per-(source shard, expert) slot count; t_loc always suffices because
    # top-k indices are distinct per token
    cap = int(math.ceil(capacity_factor * t_loc * k / e))
    cap = max(1, min(cap, t_loc))
    swiglu = f.activation == "swiglu"

    w_up = _pad_experts(params["w_up"], e_pad)
    w_down = _pad_experts(params["w_down"], e_pad)
    w_gate = _pad_experts(params["w_gate"], e_pad) if swiglu else None
    router = params["router"]

    def local(xs, router, w_up, w_gate, w_down):
        # xs: (t_loc, d) — this shard's resident tokens
        weights, top_idx, _ = route_topk(router, xs, k)
        tk = t_loc * k
        flat_e = top_idx.reshape(-1)                       # (tk,)
        flat_w = weights.reshape(-1)                       # (tk,) f32
        tok_of_pair = jnp.arange(tk, dtype=jnp.int32) // k
        # rank of each pair within its expert's buffer (pair order)
        onehot = (flat_e[:, None] == jnp.arange(e_pad, dtype=jnp.int32)[None]
                  ).astype(jnp.int32)
        rank = (jnp.cumsum(onehot, axis=0) - 1)[jnp.arange(tk), flat_e]
        keep = rank < cap                                   # capacity drop
        # --- dispatch: pack (e_pad, cap, d) buffers, one all_to_all -------
        buf = jnp.zeros((e_pad, cap, d), xs.dtype)
        buf = buf.at[flat_e, rank].set(
            jnp.where(keep[:, None], xs[tok_of_pair], 0), mode="drop")
        buf = buf.reshape(n_ep, e_loc, cap, d)
        recv = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                                  tiled=True)               # (n_ep src, ...)
        # --- expert FFN: batched GEMM over this shard's experts -----------
        xr = recv.transpose(1, 0, 2, 3).reshape(e_loc, n_ep * cap, d)
        up = jnp.einsum("ecd,edf->ecf", xr, w_up)
        if swiglu:
            gate = jnp.einsum("ecd,edf->ecf", xr, w_gate)
            h = (jax.nn.silu(gate.astype(jnp.float32))
                 * up.astype(jnp.float32)).astype(xs.dtype)
        else:
            h = jax.nn.gelu(up.astype(jnp.float32)).astype(xs.dtype)
        out_e = jnp.einsum("ecf,efd->ecd", h, w_down)
        # --- return trip + weighted combine at the token's home shard -----
        back = out_e.reshape(e_loc, n_ep, cap, d).transpose(1, 0, 2, 3)
        ret = jax.lax.all_to_all(back, axis, split_axis=0, concat_axis=0,
                                 tiled=True)
        ret = ret.reshape(e_pad, cap, d)
        pair_out = ret[flat_e, jnp.clip(rank, 0, cap - 1)]
        contrib = (pair_out.astype(jnp.float32)
                   * jnp.where(keep, flat_w, 0.0)[:, None])
        out = jnp.zeros((t_loc, d), jnp.float32).at[tok_of_pair].add(contrib)
        return out.astype(xs.dtype)

    mapped = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None), P(None, None), P(axis, None, None),
                  (P(axis, None, None) if swiglu else P()),
                  P(axis, None, None)),
        out_specs=P(axis, None),
        check_vma=False)
    out = mapped(x, router,
                 w_up, w_gate if swiglu else jnp.zeros(()), w_down)

    if f.n_shared_experts:
        sh = jax.nn.gelu((x @ params["shared_up"]).astype(jnp.float32))
        out = out + (sh.astype(x.dtype) @ params["shared_down"])
    return out
