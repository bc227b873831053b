"""Batched diffusion + MTP serving through the scheduler, the diffusion
KV-commit regression, and the bucketed-prefill compile discipline.

Fast lane: tiny reduced configs, short streams — these are the
scheduler-mode goldens the tier-1 suite must keep honest."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_model
from repro.models.transformer import init_cache
from repro.serving import (DecodeEngine, DiffusionSlotAdapter, PagedKVConfig,
                           ServingLoop, init_mtp_heads)
from repro.serving.diffusion import refine_block, token_choice
from repro.serving.engine import _prefill_fn

KEY = jax.random.PRNGKey(0)
TOKENS = 10


@pytest.fixture(scope="module")
def dense_setup():
    cfg = get_config("stablelm_3b", reduced=True)
    params = init_model(KEY, cfg)
    prompts = [np.asarray(jax.random.randint(
        jax.random.PRNGKey(i + 1), (5 + i,), 0, cfg.vocab_size))
        for i in range(4)]
    return cfg, params, prompts


@pytest.fixture(scope="module")
def moe_setup():
    cfg = get_config("llada_mini_like", reduced=True)
    params = init_model(KEY, cfg)
    prompts = [np.asarray(jax.random.randint(
        jax.random.PRNGKey(i + 1), (5 + i,), 0, cfg.vocab_size))
        for i in range(3)]
    return cfg, params, prompts


def _cache_kv(cache, length):
    """Every attention-segment cache leaf, truncated to ``length``."""
    out = []
    for seg in cache["segments"]:
        for key in sorted(seg):
            out.append(np.asarray(seg[key][:, :, :length]
                                  .astype(jnp.float32)))
    return out


def _prefilled(cfg, params, stream, max_len=96):
    """Dense one-row cache holding the KV of prefilling ``stream``."""
    _, cache, _ = _prefill_fn(params, cfg, jnp.asarray(stream[None],
                                                       jnp.int32),
                              init_cache(cfg, 1, max_len), None)
    return cache


def _solo(cfg, params, prompt, tokens, max_len=96, use_kernel=False,
          **loop_kw):
    """``prompt`` served alone through a loop with one slot.  Returns the
    stream, the engine, and the slot's committed length when it was
    released."""
    eng = DecodeEngine(cfg, params, batch=1, max_len=max_len,
                       use_kernel=use_kernel)
    released = {}
    release = eng.release_slot

    def spy(slot):
        released[slot] = int(eng.slot_lens_host[slot])
        release(slot)
    eng.release_slot = spy
    loop = ServingLoop(eng, **loop_kw)
    loop.submit(prompt, tokens)
    return loop.run()[0], eng, released.get(0)


# ===========================================================================
# Headline bugfix: diffusion must not commit KV computed from MASK inputs
# ===========================================================================

def test_diffusion_committed_kv_matches_prefill(dense_setup):
    """After a one-slot diffusion generation, the slot's committed cache
    must be byte-identical to PREFILLING the resolved stream — the final
    refinement iteration's cache saw mask-token inputs and must never
    have been committed."""
    cfg, params, prompts = dense_setup
    toks, eng, committed = _solo(cfg, params, prompts[2], TOKENS,
                                 mode="diffusion", block_size=5,
                                 refine_steps=2)
    stream = np.concatenate([prompts[2], toks[:-1]])
    assert committed == len(stream)
    for got, want in zip(_cache_kv(eng.cache, len(stream)),
                         _cache_kv(_prefilled(cfg, params, stream),
                                   len(stream))):
        assert np.array_equal(got, want)


class _PoisonedCommit(DiffusionSlotAdapter):
    """The pre-fix commit, for one slot: the LAST REFINEMENT forward's
    cache, in which positions resolved during/after the final iteration
    were still mask_id inputs."""

    def run_step(self, slots, width, budget):
        loop, eng = self.loop, self.loop.engine
        (s,) = slots
        req = loop.active[s]
        n = max(1, min(width, req.max_tokens - len(req.generated)))
        block = np.full((n,), self._mask_id(), np.int64)
        resolved = np.zeros((n,), bool)
        per_iter = max(1, int(np.ceil(n / self.refine_steps)))
        tokens = np.zeros((eng.batch, n + 1), np.int64)
        for _ in range(self.refine_steps):
            if resolved.all():
                break
            tokens[s, 0] = req.pending
            tokens[s, 1:] = block
            logits, new_cache, _ = loop.shared_forward(tokens, budget)
            preds, conf = self.readback(*token_choice(logits))
            refine_block(block, resolved, preds[s], conf[s], per_iter)
        block[~resolved] = preds[s, :n][~resolved]
        req.generated.extend(int(t) for t in block)
        req.pending = int(block[-1])
        advances = np.zeros((eng.batch,), np.int32)
        advances[s] = n
        eng.commit_slots(new_cache, advances)


def test_diffusion_kv_regression_has_teeth(dense_setup):
    """Negative control: replaying the pre-fix commit (the cache of a
    forward that still saw MASK inputs) must FAIL the byte comparison —
    i.e. the regression test above genuinely catches the bug."""
    cfg, params, prompts = dense_setup
    toks, eng, _ = _solo(cfg, params, prompts[2], TOKENS, adapter=
                         _PoisonedCommit(None, block_size=5, refine_steps=2))
    stream = np.concatenate([prompts[2], toks[:-1]])
    assert any(not np.array_equal(got, want)
               for got, want in zip(_cache_kv(eng.cache, len(stream)),
                                    _cache_kv(_prefilled(cfg, params, stream),
                                              len(stream))))


# ===========================================================================
# Golden byte-equivalence: batched scheduler modes vs one-slot runs
# ===========================================================================

def test_serving_diffusion_matches_solo(dense_setup):
    """ServingLoop(mode='diffusion') over a mixed-length batch: every
    request's token stream is byte-identical to the same prompt alone in
    a one-slot loop at the same block size, including through a queue
    deeper than the slot pool."""
    cfg, params, prompts = dense_setup
    solo = [_solo(cfg, params, p, TOKENS, mode="diffusion", block_size=4,
                  refine_steps=2)[0] for p in prompts]
    eng = DecodeEngine(cfg, params, batch=3, max_len=96)
    loop = ServingLoop(eng, mode="diffusion", block_size=4, refine_steps=2)
    for p in prompts:
        loop.submit(p, TOKENS)
    out = loop.run()
    assert len(out) == len(prompts)
    for i in range(len(prompts)):
        assert np.array_equal(solo[i], out[i]), i
    # block parallelism realized through the shared forwards
    assert loop.stats()["tokens_per_forward"] > 1.0


def test_serving_mtp_matches_solo(dense_setup):
    """ServingLoop(mode='mtp') is lossless: byte-identical to solo AR
    greedy (greedy acceptance)."""
    cfg, params, prompts = dense_setup
    heads = init_mtp_heads(jax.random.PRNGKey(5), cfg.d_model,
                           cfg.vocab_size, n_heads=4)
    refs = []
    for p in prompts:
        eng = DecodeEngine(cfg, params, batch=1, max_len=96)
        refs.append(np.asarray(
            eng.greedy_generate(jnp.asarray(p)[None], TOKENS)[0]))
    eng = DecodeEngine(cfg, params, batch=3, max_len=96)
    loop = ServingLoop(eng, mode="mtp", mtp_heads=heads, max_width=5)
    for p in prompts:
        loop.submit(p, TOKENS)
    out = loop.run()
    for i in range(len(prompts)):
        assert np.array_equal(refs[i], out[i]), i


def test_serving_modes_moe_kernel_golden(moe_setup):
    """MoE config through the Pallas ragged decode-attention path
    (use_kernel=True, interpret on CPU): batched diffusion + mtp streams
    stay byte-identical to their one-slot and AR greedy references."""
    cfg, params, prompts = moe_setup
    t = 6
    heads = init_mtp_heads(jax.random.PRNGKey(5), cfg.d_model,
                           cfg.vocab_size, n_heads=3)
    solo_diff, refs = [], []
    for p in prompts:
        solo_diff.append(_solo(cfg, params, p, t, max_len=64,
                               use_kernel=True, mode="diffusion",
                               block_size=3, refine_steps=2)[0])
        eng = DecodeEngine(cfg, params, batch=1, max_len=64,
                           use_kernel=True)
        refs.append(np.asarray(
            eng.greedy_generate(jnp.asarray(p)[None], t)[0]))
    eng = DecodeEngine(cfg, params, batch=3, max_len=64, use_kernel=True)
    loop = ServingLoop(eng, mode="diffusion", block_size=3, refine_steps=2)
    for p in prompts:
        loop.submit(p, t)
    out = loop.run()
    for i in range(len(prompts)):
        assert np.array_equal(solo_diff[i], out[i]), i
    eng = DecodeEngine(cfg, params, batch=3, max_len=64, use_kernel=True)
    loop = ServingLoop(eng, mode="mtp", mtp_heads=heads, max_width=4)
    for p in prompts:
        loop.submit(p, t)
    out = loop.run()
    for i in range(len(prompts)):
        assert np.array_equal(refs[i], out[i]), i


# ===========================================================================
# Bucketed batched prefill: compile discipline + one forward per group
# ===========================================================================

def test_bucketed_prefill_one_forward_per_admission_group(dense_setup):
    """8 admissions with 8 distinct prompt lengths and 8 free slots:
    ONE prefill forward (not one full-batch forward per request)."""
    cfg, params, _ = dense_setup
    prompts = [np.asarray(jax.random.randint(
        jax.random.PRNGKey(30 + i), (5 + i,), 0, cfg.vocab_size))
        for i in range(8)]
    eng = DecodeEngine(cfg, params, batch=8, max_len=96)
    loop = ServingLoop(eng, mode="greedy")
    for p in prompts:
        loop.submit(p, 4)
    loop.run()
    assert len(eng.prefill_log) == 1
    assert eng.prefill_log[0]["slots"] == list(range(8))
    assert eng.prefill_log[0]["bucket"] == 16     # next pow2 >= 12


def test_bucketed_prefill_compiles_at_most_n_buckets(dense_setup):
    """M admissions at M distinct prompt lengths trigger at most
    n_buckets prefill compiles — staggered admission through a small
    slot pool included."""
    cfg, params, _ = dense_setup
    lengths = list(range(5, 13))                  # buckets: 8 and 16
    prompts = [np.asarray(jax.random.randint(
        jax.random.PRNGKey(40 + i), (n,), 0, cfg.vocab_size))
        for i, n in enumerate(lengths)]
    eng = DecodeEngine(cfg, params, batch=2, max_len=96)
    n_buckets = len({eng.prefill_bucket(n) for n in lengths})
    assert n_buckets == 2
    before = _prefill_fn._cache_size()
    loop = ServingLoop(eng, mode="greedy")
    for p in prompts:
        loop.submit(p, 4)
    loop.run()
    compiled = _prefill_fn._cache_size() - before
    assert 0 < compiled <= n_buckets
    used = {e["bucket"] for e in eng.prefill_log}
    assert used <= {8, 16}


def test_commit_slots_row_mask_on_device(dense_setup):
    """commit_slots must leave advance-0 rows untouched and accept the
    advances without a host round-trip (device array in, no np
    materialization required)."""
    cfg, params, prompts = dense_setup
    eng = DecodeEngine(cfg, params, batch=2, max_len=96)
    eng.prefill_slots({0: prompts[0], 1: prompts[1]})
    before = _cache_kv(eng.cache, 32)
    toks = jnp.asarray(np.zeros((2, 2), np.int64), jnp.int32)
    _, new_cache, _ = eng.decode_slots(toks)
    eng.commit_slots(new_cache, jnp.asarray([2, 0], jnp.int32))
    after = _cache_kv(eng.cache, 32)
    lens = np.asarray(eng.slot_lens)
    assert lens[0] == len(prompts[0]) + 2 and lens[1] == len(prompts[1])
    for b, a in zip(before, after):
        # row 1 untouched everywhere; row 0 advanced
        assert np.array_equal(b[:, 1], a[:, 1])


def test_paged_engine_never_reads_a_donated_pool(dense_setup):
    """Every program that updates a paged pool takes it donated, so a
    caller that kept the pool it passed would read a deleted buffer.
    The engine adopts each returned pool at once: two decode forwards
    without a commit, a prefix-hit admission with a copy-on-write, and
    a full admission all run on one engine, and the forwards agree."""
    cfg, params, _ = dense_setup
    eng = DecodeEngine(cfg, params, batch=2, max_len=96,
                       paged=PagedKVConfig(block_size=16))
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, cfg.vocab_size, size=32)
    eng.prefill_slots({0: prompt})                     # _scatter_prefill
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 3)), jnp.int32)
    before = eng.cache
    first, new_cache, _ = eng.decode_slots(toks)
    assert new_cache is eng.cache
    assert all(x.is_deleted() for x in jax.tree.leaves(before))
    # again, uncommitted: the same positions are written, then read
    second, new_cache, _ = eng.decode_slots(toks)
    np.testing.assert_array_equal(np.asarray(first[0]),
                                  np.asarray(second[0]))
    eng.commit_slots(new_cache, np.zeros(2, np.int64))
    # the same 32-token prompt: 31 positions hit, the divergence lies
    # inside a shared block and is copied on write before the suffix
    # forward (_copy_pool_blocks, then _decode_paged_fn)
    eng.prefill_slots({1: prompt})
    assert eng.prefill_log[-1]["cached_tokens"] == 31
    assert eng.manager.cow_copies == 1
    eng.release_slot(0)
    eng.prefill_slots({0: rng.integers(0, cfg.vocab_size, size=20)})
    logits, new_cache, _ = eng.decode_slots(toks)
    eng.commit_slots(new_cache, np.full(2, 3, np.int64))
    assert np.isfinite(np.asarray(logits.astype(jnp.float32))).all()
    assert not any(x.is_deleted() for x in jax.tree.leaves(eng.cache))
    eng.manager.check_invariants()
