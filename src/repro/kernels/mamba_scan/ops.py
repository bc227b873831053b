"""jit'd wrapper: chunk padding (the scan-chunk granularity) + kernel call."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.granularity import round_up, select_scan_chunk
from repro.kernels.mamba_scan.kernel import mamba_scan_pallas


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan(x, dt, b_in, c_in, a, h0,
                   interpret: Optional[bool] = None):
    """x/dt: (b, s, di) f32; b_in/c_in: (b, s, ds) f32; a: (di, ds);
    h0: (b, di, ds).  Positions are padded to SSM_CHUNK — the scan-chunk
    granularity of the NFP principle for SSM architectures.

    interpret=None compiles the kernel on TPU and runs the Pallas
    interpreter elsewhere; pass True/False to force either.

    Returns (y (b, s, di), h_final) — h_final is the state after the s
    REAL positions (padding uses dt=0 => identity state update).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bsz, s, di = x.shape
    chunk = select_scan_chunk(s)
    s_pad = round_up(s, chunk)
    pad = s_pad - s

    def padf(t):
        return jnp.pad(t, ((0, 0), (0, pad), (0, 0)))

    # dt=0 makes padded steps identity: h = exp(0)*h + 0
    y, h = mamba_scan_pallas(padf(x), padf(dt), padf(b_in), padf(c_in),
                             a, h0, chunk=chunk, interpret=interpret)
    return y[:, :s], h
