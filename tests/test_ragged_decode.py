"""Ragged per-slot decode attention: lossless-verification harness.

Three layers of proof that the scheduler's kernel fast path is lossless:
  1. kernel parity — the ragged Pallas kernel (interpret mode on CPU)
     vs the pure-jnp oracle across GQA group sizes, SWA windows,
     q_block/k_block choices, and adversarial row-length mixes,
  2. layer parity — ``gqa_decode(use_kernel=True)`` vs the XLA reference
     with per-row cache lengths (rope + ragged cache writes included),
  3. golden equivalence — ``ServingLoop`` over the kernel path emits
     byte-identical token streams to solo ``DecodeEngine.greedy_generate``
     in greedy and speculative modes, with slack telemetry present and
     no fallback warning.

Plus the ``gqa_decode_ring`` SWA ring buffer (wraparound commits and
window masks across the seam) and ``slack_report`` invariants.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.arch import AttentionSpec
from repro.core.granularity import round_up, select_q_block
from repro.kernels.decode_attention.ops import (decode_attention,
                                                decode_attention_ragged,
                                                slack_report)
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.models.attention import gqa_decode, gqa_decode_ring, init_attention

KEY = jax.random.PRNGKey(0)


def _rand_qkv(b, n, h, kv, dh, s, dtype=jnp.float32):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, n, h, dh)).astype(dtype)
    kc = jax.random.normal(ks[1], (b, s, kv, dh)).astype(dtype)
    vc = jax.random.normal(ks[2], (b, s, kv, dh)).astype(dtype)
    return q, kc, vc


# ===========================================================================
# 1. kernel parity vs oracle
# ===========================================================================

RAGGED_CASES = [
    # (b, n, h, kv, dh, s_max, lens, window, q_block, k_block)
    (4, 1, 8, 2, 64, 256, [0, 37, 200, 100], None, None, 128),    # N=1 mixed
    (4, 5, 8, 2, 64, 256, [0, 37, 200, 100], None, None, 128),    # len-0 row
    (3, 7, 4, 4, 32, 384, [60, 60, 60], None, None, 128),         # all-equal
    (2, 4, 8, 1, 64, 256, [252, 10], None, None, 128),            # max_len row
    (4, 3, 6, 3, 32, 256, [5, 100, 200, 253], None, 16, 64),      # qb16/kb64
    (4, 17, 8, 2, 64, 512, [0, 130, 255, 300], 128, None, 128),   # SWA mixed
    (2, 2, 4, 2, 32, 256, [128, 64], 32, 16, 128),                # tiny window
    (2, 65, 16, 8, 64, 256, [100, 5], None, None, 128),           # 2 q tiles
]


@pytest.mark.parametrize("case", RAGGED_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ragged_kernel_vs_ref(case, dtype):
    b, n, h, kv, dh, s, lens, win, qb, kb = case
    q, kc, vc = _rand_qkv(b, n, h, kv, dh, s, dtype)
    lens = jnp.asarray(lens, jnp.int32)
    out = decode_attention_ragged(q, kc, vc, lens, window=win,
                                  q_block_override=qb, k_block=kb,
                                  interpret=True)
    ref = decode_attention_ref(q, kc, vc, lens, window=win)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_ragged_equals_rowwise_scalar_kernel():
    """The ragged launch must agree with running each row alone through the
    aligned (scalar total_len) kernel — raggedness cannot couple rows."""
    b, n, h, kv, dh, s = 4, 5, 8, 2, 64, 256
    lens = [0, 37, 200, 100]
    q, kc, vc = _rand_qkv(b, n, h, kv, dh, s)
    out = decode_attention_ragged(q, kc, vc, jnp.asarray(lens, jnp.int32),
                                  interpret=True)
    for bi, ln in enumerate(lens):
        solo = decode_attention(q[bi:bi + 1], kc[bi:bi + 1], vc[bi:bi + 1],
                                ln + n, interpret=True)
        np.testing.assert_allclose(np.asarray(out[bi:bi + 1]),
                                   np.asarray(solo), atol=2e-6, rtol=2e-6)


def test_scalar_broadcast_matches_aligned_entry():
    """decode_attention(total_len) is the ragged kernel with aligned rows."""
    b, n, h, kv, dh, s, cl = 2, 3, 4, 2, 32, 128, 60
    q, kc, vc = _rand_qkv(b, n, h, kv, dh, s)
    aligned = decode_attention(q, kc, vc, cl + n, interpret=True)
    ragged = decode_attention_ragged(
        q, kc, vc, jnp.full((b,), cl, jnp.int32), interpret=True)
    np.testing.assert_allclose(np.asarray(aligned), np.asarray(ragged),
                               atol=0, rtol=0)


# ===========================================================================
# 2. layer parity: gqa_decode kernel vs XLA reference, per-row lengths
# ===========================================================================

@pytest.mark.parametrize("kind,window", [("gqa", None), ("swa", 48)])
def test_gqa_decode_kernel_path_per_row(kind, window):
    a = AttentionSpec(kind=kind, n_heads=4, n_kv_heads=2, head_dim=32,
                      window=window)
    d = 64
    params = init_attention(jax.random.PRNGKey(1), d, a, dtype=jnp.float32)
    b, n, s = 3, 4, 128
    lens = jnp.asarray([0, 17, 90], jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(2), (b, n, d), jnp.float32)
    cache = {"k": jax.random.normal(jax.random.PRNGKey(3), (b, s, 2, 32)),
             "v": jax.random.normal(jax.random.PRNGKey(4), (b, s, 2, 32))}
    out_k, cache_k = gqa_decode(params, a, x, cache, lens, 10000.0,
                                use_kernel=True)
    out_r, cache_r = gqa_decode(params, a, x, cache, lens, 10000.0,
                                use_kernel=False)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=1e-4, rtol=1e-4)
    # the cache write path is shared — must be identical
    np.testing.assert_array_equal(np.asarray(cache_k["k"]),
                                  np.asarray(cache_r["k"]))


# ===========================================================================
# 3. golden equivalence: ServingLoop kernel path vs solo greedy decode
# ===========================================================================

@pytest.fixture(scope="module")
def serving_setup():
    from repro.configs import get_config
    from repro.models import init_model
    from repro.serving import DecodeEngine
    cfg = get_config("stablelm_3b", reduced=True)
    params = init_model(KEY, cfg)
    prompts = [np.asarray(jax.random.randint(
        jax.random.PRNGKey(i + 1), (6 + i,), 0, cfg.vocab_size))
        for i in range(3)]
    refs = []
    for p in prompts:
        eng = DecodeEngine(cfg, params, batch=1, max_len=256)
        refs.append(np.asarray(
            eng.greedy_generate(jnp.asarray(p)[None], 12)[0]))
    return cfg, params, prompts, refs


@pytest.mark.parametrize("mode", ["greedy", "speculative"])
def test_serving_kernel_path_golden(serving_setup, mode):
    """ServingLoop(use_kernel=True): byte-identical to solo greedy decode,
    no fallback warning, slack telemetry in every step entry."""
    from repro.serving import DecodeEngine, ServingLoop
    cfg, params, prompts, refs = serving_setup
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        eng = DecodeEngine(cfg, params, batch=3, max_len=256,
                           use_kernel=True)
        loop = ServingLoop(eng, mode=mode, max_width=6)
        for p in prompts:
            loop.submit(p, 12)
        out = loop.run()
    for i in range(len(prompts)):
        assert np.array_equal(refs[i], out[i]), i
    for e in loop.step_log:
        for k in ("attn_row_util", "kv_tiles_executed", "kv_tiles_grid",
                  "kv_tiles_skipped", "kv_tile_util", "attn_heads_per_step",
                  "attn_grid_steps"):
            assert k in e
        assert 0 < e["kv_tiles_executed"] <= e["kv_tiles_grid"]
    assert "mean_kv_tile_util" in loop.stats()


def test_solo_kernel_engine_matches_reference_engine(serving_setup):
    """Single-request greedy decode through the kernel path is also
    byte-identical to the XLA reference engine."""
    from repro.serving import DecodeEngine
    cfg, params, prompts, refs = serving_setup
    eng = DecodeEngine(cfg, params, batch=1, max_len=256, use_kernel=True)
    toks = np.asarray(
        eng.greedy_generate(jnp.asarray(prompts[0])[None], 12)[0])
    assert np.array_equal(refs[0], toks)


# ===========================================================================
# gqa_decode_ring: SWA ring buffer across the wraparound seam
# ===========================================================================

def test_ring_decode_matches_full_cache_across_seam():
    """Drive ring (O(window) buffer) and full-cache SWA decode in lockstep
    past the wraparound: outputs must agree at every step, including the
    steps whose window spans the ring seam."""
    a = AttentionSpec(kind="swa", n_heads=4, n_kv_heads=2, head_dim=32,
                      window=32)
    d, b, n, w_buf, s_full = 64, 2, 4, 48, 192
    params = init_attention(jax.random.PRNGKey(5), d, a, dtype=jnp.float32)
    ring = {"k": jnp.zeros((b, w_buf, 2, 32)), "v": jnp.zeros((b, w_buf, 2, 32))}
    full = {"k": jnp.zeros((b, s_full, 2, 32)), "v": jnp.zeros((b, s_full, 2, 32))}
    steps = (s_full - n) // n                     # 47 commits -> 3+ wraps
    wrapped = False
    for step in range(steps):
        cl = step * n
        x = jax.random.normal(jax.random.fold_in(KEY, step), (b, n, d),
                              jnp.float32)
        out_r, ring = gqa_decode_ring(params, a, x, ring, cl, 10000.0)
        out_f, full = gqa_decode(params, a, x, full, cl, 10000.0)
        np.testing.assert_allclose(np.asarray(out_r), np.asarray(out_f),
                                   atol=1e-4, rtol=1e-4, err_msg=f"step {step}")
        wrapped |= cl + n > w_buf
    assert wrapped                                # the seam was crossed


def test_ring_wraparound_slot_contents():
    """After wrapping, each ring slot must hold the LARGEST position
    congruent to it — verified by committing recognizable values."""
    a = AttentionSpec(kind="swa", n_heads=2, n_kv_heads=1, head_dim=8,
                      window=8)
    d, b, n, w_buf = 16, 1, 2, 16
    params = init_attention(jax.random.PRNGKey(6), d, a, dtype=jnp.float32)
    ring = {"k": jnp.zeros((b, w_buf, 1, 8)), "v": jnp.zeros((b, w_buf, 1, 8))}
    total = 3 * w_buf + n                        # several full wraps
    for cl in range(0, total, n):
        x = jnp.full((b, n, d), 0.0).at[:, :, 0].set(
            cl + jnp.arange(n, dtype=jnp.float32))   # position tag
        _, ring = gqa_decode_ring(params, a, x, ring, cl, 10000.0)
    # position p lives in slot p % w_buf; last writes win
    k = np.asarray(ring["k"])                    # (b, w_buf, 1, 8)
    assert k.shape[1] == w_buf
    # every slot was overwritten at least twice (no stale zeros)
    assert np.all(np.abs(k).sum(axis=(2, 3)) > 0)


# ===========================================================================
# slack_report invariants
# ===========================================================================

def test_slack_report_bounds_and_monotonicity():
    lens = np.asarray([0, 37, 200, 100])
    rep = slack_report(5, lens, 256, head_dim=64)
    assert rep["kv_tiles_useful"] <= rep["kv_tiles_executed"] <= rep["kv_tiles_grid"]
    assert rep["kv_tiles_skipped"] == rep["kv_tiles_grid"] - rep["kv_tiles_executed"]
    assert 0 < rep["row_utilization"] <= 1
    # longer slots -> at least as many executed tiles
    rep2 = slack_report(5, lens + 40, 256, head_dim=64)
    assert rep2["kv_tiles_executed"] >= rep["kv_tiles_executed"]
    # inactive rows move tiles from useful to pure slack
    rep3 = slack_report(5, lens, 256, head_dim=64,
                        active=[True, True, False, False])
    assert rep3["kv_tiles_useful"] < rep3["kv_tiles_executed"]
    assert rep3["kv_tiles_executed"] == rep["kv_tiles_executed"]


def test_slack_report_matches_kernel_tiling():
    """The report's q_block/physical-rows model must equal the launch math
    in ops.decode_attention_ragged."""
    for n in (1, 5, 64, 65):
        rep = slack_report(n, np.zeros(2, np.int64), 256, head_dim=64)
        qb = select_q_block(n, 64)
        assert rep["q_block"] == qb
        assert rep["rows_physical"] == 2 * round_up(n, qb)
        # one KV head a step by default: a step per (row, q tile, kv tile)
        assert rep["heads_per_step"] == 1
        assert rep["grid_steps"] == rep["kv_tiles_grid"]
        assert rep["grid_steps"] == 2 * round_up(n, qb) // qb * 2
