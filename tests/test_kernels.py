"""Per-kernel validation: shape/dtype sweeps, Pallas interpret mode vs
pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.mamba_scan.ops import selective_scan
from repro.kernels.mamba_scan.ref import selective_scan_ref
from repro.kernels.moe_ffn.ops import align_block_size, grouped_ffn
from repro.kernels.moe_ffn.ref import grouped_ffn_ref
from repro.models.layers import _init


KEY = jax.random.PRNGKey(0)


# ===========================================================================
# decode attention
# ===========================================================================

ATTN_CASES = [
    # (b, n, h, kv, dh, s_max, cache_len, window)
    (2, 1, 8, 2, 64, 256, 200, None),        # N=1 AR baseline, GQA
    (1, 7, 4, 4, 128, 300, 100, None),       # odd N, MHA
    (2, 17, 8, 1, 64, 512, 400, 128),        # MQA + sliding window
    (1, 64, 16, 8, 128, 1024, 900, None),    # exactly one q tile
    (1, 65, 16, 8, 128, 1024, 900, None),    # crosses the q-tile boundary
    (2, 3, 6, 3, 32, 128, 60, None),         # odd head dim count
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_vs_ref(case, dtype):
    b, n, h, kv, dh, s, cl, win = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, n, h, dh)).astype(dtype)
    filled = cl + n
    kc = jnp.zeros((b, s, kv, dh), dtype).at[:, :filled].set(
        jax.random.normal(ks[1], (b, filled, kv, dh)).astype(dtype))
    vc = jnp.zeros((b, s, kv, dh), dtype).at[:, :filled].set(
        jax.random.normal(ks[2], (b, filled, kv, dh)).astype(dtype))
    out = decode_attention(q, kc, vc, cl + n, window=win, interpret=True)
    ref = decode_attention_ref(q, kc, vc, cl, window=win)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_decode_attention_padded_rows_do_not_leak():
    """Rows beyond N are padding; output must only contain the N real rows
    and they must be unaffected by the pad (compare n=1 vs n=1-in-tile-64)."""
    b, h, kv, dh, s, cl = 1, 4, 2, 64, 256, 100
    ks = jax.random.split(KEY, 3)
    kc = jax.random.normal(ks[1], (b, s, kv, dh))
    vc = jax.random.normal(ks[2], (b, s, kv, dh))
    q1 = jax.random.normal(ks[0], (b, 1, h, dh))
    out1 = decode_attention(q1, kc, vc, cl + 1, interpret=True)
    assert out1.shape == (b, 1, h, dh)
    assert not bool(jnp.any(jnp.isnan(out1)))


# ===========================================================================
# MoE grouped FFN
# ===========================================================================

MOE_CASES = [
    (8, 64, 32, 4, "swiglu"),
    (33, 128, 256, 8, "swiglu"),
    (64, 64, 512, 4, "gelu"),
    (100, 256, 1024, 16, "swiglu"),
    (1, 32, 64, 8, "swiglu"),          # single token (decode N=1)
]


@pytest.mark.parametrize("case", MOE_CASES)
def test_grouped_ffn_vs_ref(case):
    m, d, f, e, act = case
    ks = jax.random.split(KEY, 4)
    params = {"w_up": _init(ks[0], (e, d, f), dtype=jnp.float32),
              "w_gate": _init(ks[1], (e, d, f), dtype=jnp.float32),
              "w_down": _init(ks[2], (e, f, d), dtype=jnp.float32)}
    gs = np.random.default_rng(m).multinomial(m, np.ones(e) / e)
    gs = jnp.asarray(gs, jnp.int32)
    x = jax.random.normal(ks[3], (m, d), jnp.float32)
    out = grouped_ffn(x, params, gs, act, interpret=True)
    ref = grouped_ffn_ref(x, params, gs, act)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-4, rtol=5e-4)


def test_align_block_size_staircase():
    """The padded layout implements Eq. 28: per-expert ceil to token_block."""
    e, tb = 8, 16
    gs = jnp.asarray([1, 0, 17, 16, 3, 0, 0, 31], jnp.int32)
    m = int(gs.sum())
    expert_of = jnp.repeat(jnp.arange(e), gs, total_repeat_length=m)
    slot, block_expert, block_valid, m_pad_max = align_block_size(
        expert_of, gs, e, tb)
    # slots unique & within bounds
    assert len(set(np.asarray(slot).tolist())) == m
    assert int(slot.max()) < m_pad_max
    # executed blocks = sum ceil(counts/tb)
    expect_blocks = sum(int(np.ceil(c / tb)) for c in np.asarray(gs) if c)
    assert int(block_valid.sum()) == expect_blocks
    # vLLM bound: numel + E*(block-1), rounded up
    assert m_pad_max <= ((m + e * (tb - 1) + tb - 1) // tb) * tb


def test_grouped_ffn_skewed_routing():
    """All tokens on the same experts (paper's lower-bound case)."""
    m, d, f, e = 48, 64, 128, 16
    ks = jax.random.split(KEY, 4)
    params = {"w_up": _init(ks[0], (e, d, f), dtype=jnp.float32),
              "w_gate": _init(ks[1], (e, d, f), dtype=jnp.float32),
              "w_down": _init(ks[2], (e, f, d), dtype=jnp.float32)}
    gs = jnp.zeros((e,), jnp.int32).at[0].set(24).at[1].set(24)
    x = jax.random.normal(ks[3], (m, d), jnp.float32)
    out = grouped_ffn(x, params, gs, "swiglu", interpret=True)
    ref = grouped_ffn_ref(x, params, gs, "swiglu")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-4, rtol=5e-4)


# ===========================================================================
# mamba selective scan
# ===========================================================================

# the last two span several d_inner tiles (512 and 128 channels)
SCAN_CASES = [(2, 16, 64, 16), (1, 7, 32, 8), (2, 33, 128, 16), (1, 1, 64, 16),
              (2, 20, 1536, 16), (1, 5, 640, 8)]


@pytest.mark.parametrize("case", SCAN_CASES)
def test_selective_scan_vs_ref(case):
    b, s, di, ds = case
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (b, s, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, di)))
    b_in = jax.random.normal(ks[2], (b, s, ds))
    c_in = jax.random.normal(ks[3], (b, s, ds))
    a = -jnp.exp(jax.random.normal(ks[4], (di, ds)) * 0.5)
    h0 = jax.random.normal(ks[5], (b, di, ds))
    y, h = selective_scan(x, dt, b_in, c_in, a, h0, interpret=True)
    yr, hr = selective_scan_ref(x, dt, b_in, c_in, a, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-4)


def test_scan_chunk_padding_is_identity():
    """Padded steps (dt=0) must not change the final state."""
    b, s, di, ds = 1, 5, 16, 8       # 5 pads to 16
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (b, s, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, di)))
    b_in = jax.random.normal(ks[2], (b, s, ds))
    c_in = jax.random.normal(ks[3], (b, s, ds))
    a = -jnp.exp(jax.random.normal(ks[4], (di, ds)) * 0.5)
    h0 = jnp.zeros((b, di, ds))
    _, h = selective_scan(x, dt, b_in, c_in, a, h0, interpret=True)
    _, hr = selective_scan_ref(x, dt, b_in, c_in, a, h0)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-5)
