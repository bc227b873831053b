"""jit'd wrapper: moe_align_block_size (TPU edition) + fused grouped FFN.

``align_block_size`` is the faithful port of the mechanism in paper
Tables 3-7: routed token counts per expert are padded up to
``token_block`` (BLOCK_SIZE_M analogue), slots are laid out contiguously
per expert, and per-block expert ids + validity flags are produced for
the kernel's scalar-prefetch metadata.  The static allocation bound is
vLLM's own ``numel + E*(block-1)`` (Table 5), rounded to a block multiple.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.granularity import round_up, select_token_block
from repro.kernels.moe_ffn.kernel import moe_ffn_pallas


def align_block_size(expert_of_sorted: jnp.ndarray, group_sizes: jnp.ndarray,
                     n_experts: int, token_block: int,
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, int]:
    """Returns (slot_of_sorted (M,), block_expert (n_blocks,),
    block_valid (n_blocks,), m_pad_max).

    slot_of_sorted maps each sorted token row to its padded slot.
    """
    m = expert_of_sorted.shape[0]
    m_pad_max = round_up(m + n_experts * (token_block - 1), token_block)
    n_blocks = m_pad_max // token_block

    padded_counts = ((group_sizes + token_block - 1) // token_block
                     ) * token_block
    pad_off = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(padded_counts)[:-1].astype(jnp.int32)])
    grp_off = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(group_sizes)[:-1].astype(jnp.int32)])
    rank = jnp.arange(m, dtype=jnp.int32) - grp_off[expert_of_sorted]
    slot = pad_off[expert_of_sorted] + rank

    total_pad = jnp.sum(padded_counts).astype(jnp.int32)
    block_start = jnp.arange(n_blocks, dtype=jnp.int32) * token_block
    block_valid = (block_start < total_pad).astype(jnp.int32)
    # expert whose padded range contains this block's start
    cum = jnp.cumsum(padded_counts).astype(jnp.int32)
    block_expert = jnp.searchsorted(cum, block_start, side="right"
                                    ).astype(jnp.int32)
    block_expert = jnp.clip(block_expert, 0, n_experts - 1)
    return slot, block_expert, block_valid, m_pad_max


@functools.partial(jax.jit, static_argnames=("activation", "interpret",
                                             "token_block_override",
                                             "n_tokens"))
def grouped_ffn(x_sorted, params: Dict, group_sizes, activation: str = "swiglu",
                interpret: Optional[bool] = None, token_block_override=None,
                n_tokens: int = 0):
    """x_sorted: (M = T*k, d) token rows grouped by expert; group_sizes: (E,).

    Returns (M, d) expert-FFN outputs in the same order.  Physical work is
    quantized to token_block rows per expert (the M_moe staircase); the
    block-size branch keys on the TOKEN count T (vLLM Table 8), passed as
    n_tokens (defaults to M when unknown).

    interpret=None compiles the kernel on TPU and runs the Pallas
    interpreter elsewhere; pass True/False to force either.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m, d = x_sorted.shape
    e = group_sizes.shape[0]
    f = params["w_up"].shape[-1]
    token_block = token_block_override or select_token_block(
        n_tokens or m, e)
    f_tile = min(f, 512)

    expert_of_sorted = jnp.repeat(jnp.arange(e, dtype=jnp.int32), 1)[
        jnp.searchsorted(jnp.cumsum(group_sizes), jnp.arange(m), side="right")]
    slot, block_expert, block_valid, m_pad_max = align_block_size(
        expert_of_sorted, group_sizes, e, token_block)

    x_padded = jnp.zeros((m_pad_max, d), x_sorted.dtype).at[slot].set(x_sorted)
    # non-gated activations carry no gate weights — the kernel drops the
    # operand entirely rather than streaming a placeholder
    w_gate = params["w_gate"] if activation == "swiglu" else None
    out_padded = moe_ffn_pallas(
        x_padded, w_gate, params["w_up"], params["w_down"],
        block_expert, block_valid, token_block=token_block, f_tile=f_tile,
        activation=activation, interpret=interpret)
    return out_padded[slot]
