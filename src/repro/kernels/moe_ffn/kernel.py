"""Pallas TPU fused grouped-GEMM MoE FFN (expert-token block-aligned).

This is the TPU re-derivation of the vLLM/SGLang fused-MoE mechanism the
paper inspects (App. E): tokens sorted by expert are padded to
``token_block`` rows (BLOCK_SIZE_M analogue = M_moe), and each grid step
runs one (token_block x d_model) tile through its expert's gate/up/down
weights — the expert id per block comes from scalar-prefetched metadata,
so the weight BlockSpec index_map is data-dependent exactly like the GPU
kernels' expert_ids lookup.

Grid: (n_blocks, n_f_tiles).  f (expert d_ff) is tiled so one weight tile
fits VMEM even for mixtral-sized experts; the fp32 output accumulates
across f tiles in scratch.  Blocks beyond the dynamic padded token count
are skipped with @pl.when — the TPU analogue of the GPU's dynamic grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _moe_kernel(block_expert_ref, block_valid_ref,
                x_ref, *refs,
                activation: str, n_f_tiles: int):
    # refs is (wg, wu, wd, o, acc) for swiglu and (wu, wd, o, acc) for
    # gelu — a gated activation is the ONLY reason to stream a gate
    # tile; the gelu grid must not pay a second up-projection's DMA.
    if activation == "swiglu":
        wg_ref, wu_ref, wd_ref, o_ref, acc_ref = refs
    else:
        wu_ref, wd_ref, o_ref, acc_ref = refs
    jf = pl.program_id(1)

    @pl.when(jf == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    i = pl.program_id(0)
    valid = block_valid_ref[i] > 0

    @pl.when(valid)
    def _compute():
        x = x_ref[...].astype(jnp.float32)                    # (tb, d)
        wu = wu_ref[0].astype(jnp.float32)                    # (d, ft)
        up = jax.lax.dot_general(x, wu, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if activation == "swiglu":
            wg = wg_ref[0].astype(jnp.float32)
            gate = jax.lax.dot_general(x, wg, (((1,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
            h = jax.nn.silu(gate) * up
        else:
            h = jax.nn.gelu(up)
        wd = wd_ref[0].astype(jnp.float32)                    # (ft, d)
        acc_ref[...] += jax.lax.dot_general(h, wd, (((1,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32)

    @pl.when(jf == n_f_tiles - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def moe_ffn_pallas(x_padded, w_gate, w_up, w_down, block_expert, block_valid,
                   *, token_block: int, f_tile: int, activation: str,
                   interpret: bool = False):
    """x_padded: (m_pad, d); w_*: (E, d, f) / (E, f, d);
    block_expert/block_valid: (n_blocks,) i32 scalar-prefetch.
    ``w_gate`` may be None for non-gated activations — the gate operand
    is then dropped from the spec list entirely, so the grid streams one
    up-projection tile per step instead of two."""
    m_pad, d = x_padded.shape
    e, _, f = w_up.shape
    n_blocks = m_pad // token_block
    n_f_tiles = f // f_tile
    grid = (n_blocks, n_f_tiles)

    kernel = functools.partial(_moe_kernel, activation=activation,
                               n_f_tiles=n_f_tiles)
    expert_spec = pl.BlockSpec(
        (1, d, f_tile), lambda i, j, be, bv: (be[i], 0, j))
    in_specs = [pl.BlockSpec((token_block, d), lambda i, j, be, bv: (i, 0))]
    operands = [x_padded]
    if activation == "swiglu":
        in_specs.append(expert_spec)
        operands.append(w_gate)
    in_specs += [
        expert_spec,
        pl.BlockSpec((1, f_tile, d), lambda i, j, be, bv: (be[i], j, 0)),
    ]
    operands += [w_up, w_down]

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((token_block, d),
                                   lambda i, j, be, bv: (i, 0)),
            scratch_shapes=[pltpu.VMEM((token_block, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m_pad, d), x_padded.dtype),
        interpret=interpret,
        name="moe_ffn",
    )(block_expert, block_valid, *operands)
