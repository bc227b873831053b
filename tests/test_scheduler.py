"""Budget-aware multi-request scheduler: losslessness vs solo greedy
decoding, NFP position-budget enforcement, continuous batching, and the
unified SlotAdapter protocol behind every serving mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_model
from repro.serving import (DecodeEngine, ServingLoop, SlotAdapter,
                           init_mtp_heads)

KEY = jax.random.PRNGKey(0)
TOKENS = 16

# full serving loops (solo references + batched runs) — nightly lane;
# the tier-1 lane keeps the kernel-path golden tests in test_ragged_decode
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("stablelm_3b", reduced=True)
    params = init_model(KEY, cfg)
    prompts = [np.asarray(jax.random.randint(
        jax.random.PRNGKey(i + 1), (6 + i,), 0, cfg.vocab_size))
        for i in range(5)]
    refs = []
    for p in prompts:
        eng = DecodeEngine(cfg, params, batch=1, max_len=256)
        refs.append(np.asarray(
            eng.greedy_generate(jnp.asarray(p)[None], TOKENS)[0]))
    return cfg, params, prompts, refs


def _run_loop(cfg, params, prompts, mode, slots=4, max_width=8):
    eng = DecodeEngine(cfg, params, batch=slots, max_len=256)
    loop = ServingLoop(eng, mode=mode, max_width=max_width)
    for p in prompts:
        loop.submit(p, TOKENS)
    return loop, loop.run()


def test_greedy_serving_matches_solo_greedy(setup):
    """>= 4 concurrent requests through ONE engine: every stream is
    byte-identical to running the request alone."""
    cfg, params, prompts, refs = setup
    loop, out = _run_loop(cfg, params, prompts[:4], "greedy")
    assert max(e["active"] for e in loop.step_log) == 4
    for i in range(4):
        assert np.array_equal(refs[i], out[i]), i


def test_speculative_serving_lossless(setup):
    """Budget-split n-gram verification windows stay lossless."""
    cfg, params, prompts, refs = setup
    loop, out = _run_loop(cfg, params, prompts[:4], "speculative")
    for i in range(4):
        assert np.array_equal(refs[i], out[i]), i
    # parallelism realized: some forwards carried > active positions
    assert loop.stats()["max_positions_per_forward"] > 4


def test_positions_per_forward_within_budget(setup):
    """Total positions per forward never exceed the NFP budget (with a
    floor of one position per active request)."""
    cfg, params, prompts, refs = setup
    for mode in ("greedy", "speculative"):
        loop, _ = _run_loop(cfg, params, prompts[:4], mode)
        assert loop.step_log
        for e in loop.step_log:
            assert e["positions"] <= max(e["budget"], e["active"]), (mode, e)
        assert loop.stats()["max_positions_per_forward"] > 0


def test_continuous_batching_queues_beyond_slots(setup):
    """More requests than slots: the queue drains through freed slots
    and every stream still matches its solo reference."""
    cfg, params, prompts, refs = setup
    loop, out = _run_loop(cfg, params, prompts, "greedy", slots=2)
    assert len(out) == len(prompts)
    assert max(e["active"] for e in loop.step_log) <= 2
    for i in range(len(prompts)):
        assert np.array_equal(refs[i], out[i]), i


def test_slot_isolation_prefill_does_not_clobber(setup):
    """Admitting a new request must not disturb resident slots' caches:
    interleaved admission (slots=2, staggered lengths) already exercises
    this, but check the cache lengths directly too."""
    cfg, params, prompts, _ = setup
    eng = DecodeEngine(cfg, params, batch=3, max_len=256)
    loop = ServingLoop(eng, mode="greedy")
    loop.submit(prompts[0], TOKENS)
    loop.submit(prompts[1], TOKENS)
    loop.admit()
    loop.step()
    lens_before = np.asarray(eng.slot_lens).copy()
    loop.submit(prompts[2], TOKENS)
    loop.admit()
    loop.step()
    lens_after = np.asarray(eng.slot_lens)
    # resident slots advanced by exactly their commit, newcomer prefilled
    assert lens_after[0] == lens_before[0] + 1
    assert lens_after[1] == lens_before[1] + 1
    assert lens_after[2] == len(prompts[2]) + 1


@pytest.mark.parametrize("mode", ServingLoop.MODES)
def test_all_drivers_implement_protocol(setup, mode):
    cfg, params, prompts, _ = setup
    eng = DecodeEngine(cfg, params, batch=1, max_len=256)
    heads = init_mtp_heads(jax.random.PRNGKey(5), cfg.d_model,
                           cfg.vocab_size, n_heads=4)
    loop = ServingLoop(eng, mode=mode, mtp_heads=heads)
    assert isinstance(loop.adapter, SlotAdapter)
    assert loop.adapter.mode == mode
    budget = eng.nfp_budget(loop.eps, ell=len(prompts[0]))
    assert loop.adapter.width(1, budget) >= 1
