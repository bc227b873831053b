"""Pallas TPU chunked selective scan (Mamba1 recurrence).

The SSM analogue of the paper's granularity mechanism: decode positions
are processed in SSM_CHUNK-position blocks (DESIGN.md §6) — physical work
is quantized to whole chunks, giving the scan-chunk term of the NFP
principle for SSM/hybrid architectures.

Recurrence (per chunk, sequential in time inside the chunk, f32 state):
    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) B_t
    y_t = <h_t, C_t>

Layout: x/dt (b, s_pad, di); B/C (b, s_pad, ds); A (di, ds); h0 (b, di, ds).
Grid: (b, n_di_tiles, n_chunks) — chunks innermost; the running state
lives in VMEM scratch and persists across grid steps (TPU grid iterations
execute sequentially), re-initialized from h0 at chunk 0 of each
(batch row, channel tile).  The recurrence is independent per channel,
so d_inner is tiled: a whole-d_inner block (8192 channels at
falcon-mamba-7b width) overflows the 16 MiB scoped-VMEM limit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DI_TILE = 512          # channels per grid step (a multiple of the 128 lanes)


def _di_tile(di: int) -> int:
    """Largest lane-aligned divisor of ``di`` up to DI_TILE; ``di`` itself
    when none exists (small widths take the whole axis in one block)."""
    for t in range(min(DI_TILE, di) // 128 * 128, 0, -128):
        if di % t == 0:
            return t
    return di


def _scan_kernel(x_ref, dt_ref, b_in_ref, c_in_ref, a_ref, h0_ref,
                 y_ref, hout_ref, state_ref, *, chunk: int, n_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = h0_ref[0]

    a = a_ref[...]                                            # (tile, ds)

    def step(t, h):
        x_t = x_ref[0, t, :]                                  # (tile,)
        dt_t = dt_ref[0, t, :]
        b_t = b_in_ref[0, t, :]                               # (ds,)
        c_t = c_in_ref[0, t, :]
        da = jnp.exp(dt_t[:, None] * a)                       # (tile, ds)
        dbx = (dt_t * x_t)[:, None] * b_t[None, :]
        h = da * h + dbx
        y_ref[0, t, :] = jnp.sum(h * c_t[None, :], axis=1)
        return h

    state_ref[...] = jax.lax.fori_loop(0, chunk, step, state_ref[...])

    @pl.when(ic == n_chunks - 1)
    def _finish():
        hout_ref[0] = state_ref[...]


def mamba_scan_pallas(x, dt, b_in, c_in, a, h0, *, chunk: int,
                      interpret: bool = False):
    """x/dt: (b, s_pad, di) f32; b_in/c_in: (b, s_pad, ds) f32;
    a: (di, ds) f32; h0: (b, di, ds) f32.  Returns (y, h_final)."""
    bsz, s_pad, di = x.shape
    ds = b_in.shape[-1]
    n_chunks = s_pad // chunk
    dtile = _di_tile(di)
    grid = (bsz, di // dtile, n_chunks)

    kernel = functools.partial(_scan_kernel, chunk=chunk, n_chunks=n_chunks)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, dtile), lambda ib, jd, ic: (ib, ic, jd)),
            pl.BlockSpec((1, chunk, dtile), lambda ib, jd, ic: (ib, ic, jd)),
            pl.BlockSpec((1, chunk, ds), lambda ib, jd, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, ds), lambda ib, jd, ic: (ib, ic, 0)),
            pl.BlockSpec((dtile, ds), lambda ib, jd, ic: (jd, 0)),
            pl.BlockSpec((1, dtile, ds), lambda ib, jd, ic: (ib, jd, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dtile), lambda ib, jd, ic: (ib, ic, jd)),
            pl.BlockSpec((1, dtile, ds), lambda ib, jd, ic: (ib, jd, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s_pad, di), jnp.float32),
            jax.ShapeDtypeStruct((bsz, di, ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dtile, ds), jnp.float32)],
        interpret=interpret,
        name="mamba_scan",
    )(x, dt, b_in, c_in, a, h0)
