"""Paged KV cache: byte-equality vs dense serving, allocator/COW
invariants, prefix-cache hits, adversarial block-table layouts.

The byte-equality tests run the FULL serving stack (ServingLoop over a
DecodeEngine) twice — dense per-slot cache vs paged pool — and require
identical token streams.  On the kernel path the paged launch's kv tile
is the page size, so the tests pin ``block_size = K_BLOCK`` (128) where
bitwise equality against the dense kernel launch is structural; the
small-page configurations run the XLA reference path, where masked
positions contribute exact zeros and equality is again structural.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config
from repro.core.arch import AttentionSpec
from repro.core.granularity import round_up, select_q_block
from repro.kernels.decode_attention.kernel import (
    decode_attention_paged_pallas)
from repro.kernels.decode_attention.ops import (decode_attention_paged,
                                                heads_per_step)
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.models import init_model
from repro.models.attention import gqa_decode_paged, init_attention
from repro.serving import (BlockManager, DecodeEngine, PagedKVConfig,
                           ServingLoop, init_mtp_heads)

MAX_LEN = 256


@pytest.fixture(scope="module")
def model():
    cfg = get_config("stablelm_3b", reduced=True)
    params = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _serve(cfg, params, mode, prompts, *, paged=None, use_kernel=False,
           tokens=8, slots=2, max_len=MAX_LEN):
    eng = DecodeEngine(cfg, params, batch=slots, max_len=max_len,
                       use_kernel=use_kernel, paged=paged)
    kwargs = {}
    if mode == "mtp":
        kwargs["mtp_heads"] = init_mtp_heads(
            jax.random.PRNGKey(5), cfg.d_model, cfg.vocab_size, n_heads=4)
    if mode == "diffusion":
        kwargs["refine_steps"] = 2
    loop = ServingLoop(eng, mode=mode, **kwargs)
    for p in prompts:
        loop.submit(p, tokens)
    return loop.run(), loop


def _prompts(cfg, n, seed=3, lo=4, hi=14):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=int(rng.integers(lo, hi)))
            for _ in range(n)]


# ===========================================================================
# Byte-equality: paged serving == dense serving, all four modes
# ===========================================================================


@pytest.mark.parametrize("mode", ["greedy", "speculative", "mtp",
                                  "diffusion"])
def test_paged_matches_dense_kernel_path(model, mode):
    """Acceptance: paged is byte-identical to dense for every serve mode
    on the Pallas kernel path.  block_size == K_BLOCK makes the paged
    launch's kv tiling identical to the dense launch's, so equality is
    bitwise, not approximate."""
    cfg, params = model
    prompts = _prompts(cfg, 4)
    dense, _ = _serve(cfg, params, mode, prompts, use_kernel=True)
    paged, loop = _serve(cfg, params, mode, prompts, use_kernel=True,
                         paged=PagedKVConfig(block_size=128))
    assert dense.keys() == paged.keys()
    for rid in dense:
        assert np.array_equal(dense[rid], paged[rid]), f"req {rid} diverged"
    # the kernel slack telemetry stays on under paging: a step carries
    # every KV head, so a launch runs a step per (row, q tile, page)
    logged = [e for e in loop.step_log if "kv_tile_util" in e]
    assert logged
    kv_tiles = MAX_LEN // 128
    for e in logged:
        assert e["attn_heads_per_step"] == cfg.attention.n_kv_heads
        assert e["attn_grid_steps"] == 2 * -(-e["width"] // 64) * kv_tiles


@pytest.mark.parametrize("mode", ["greedy", "speculative", "mtp",
                                  "diffusion"])
def test_paged_matches_dense_xla_small_pages(model, mode):
    """XLA reference path with small (16-position) pages and fragmented
    allocation: still byte-identical to dense serving."""
    cfg, params = model
    prompts = _prompts(cfg, 5, seed=11)
    dense, _ = _serve(cfg, params, mode, prompts, slots=3)
    paged, _ = _serve(cfg, params, mode, prompts, slots=3,
                      paged=PagedKVConfig(block_size=16))
    for rid in dense:
        assert np.array_equal(dense[rid], paged[rid]), f"req {rid} diverged"


def test_paged_matches_dense_mla(model):
    """MLA's latent cache pages too (XLA path; the kernel serves GQA)."""
    cfg = get_config("minicpm3_4b", reduced=True)
    params = init_model(jax.random.PRNGKey(0), cfg)
    prompts = _prompts(cfg, 3, seed=5)
    dense, _ = _serve(cfg, params, "greedy", prompts, tokens=6)
    paged, _ = _serve(cfg, params, "greedy", prompts, tokens=6,
                      paged=PagedKVConfig(block_size=16))
    for rid in dense:
        assert np.array_equal(dense[rid], paged[rid])


def _live_kv(eng, slot, length):
    """Slot ``slot``'s first ``length`` committed K and V positions, per
    layer, as (layers, length, kv, dh): read from the dense cache, or
    gathered from the paged pool (layers, kv, n_phys, dh, block) through
    the slot's block table."""
    seg = eng.cache["segments"][0]
    if eng.manager is None:
        return [np.asarray(seg[k][:, slot, :length]) for k in ("k", "v")]
    bs = eng.manager.block_size
    pages = eng.manager.tables[slot, :-(-length // bs)]
    out = []
    for k in ("k", "v"):
        pool = np.asarray(seg[k])[:, :, pages]        # (L, kv, nb, dh, bs)
        virt = pool.transpose(0, 2, 4, 1, 3)          # (L, nb, bs, kv, dh)
        out.append(virt.reshape((pool.shape[0], -1) + virt.shape[3:])
                   [:, :length])
    return out


@pytest.mark.parametrize("use_kernel,block", [(False, 16), (True, 128)])
def test_in_place_paged_forward_matches_dense(model, use_kernel, block):
    """The paged forward writes its pool in place and hands it back
    (the engine adopts it before any commit): over two forwards with a
    commit between them that advances some rows by 0, its logits and the
    pool's committed positions equal the dense slotted engine's."""
    cfg, params = model
    rng = np.random.default_rng(17)
    prompts = {s: p for s, p in enumerate(_prompts(cfg, 3, seed=17,
                                                   lo=12, hi=40))}
    engines = [DecodeEngine(cfg, params, batch=3, max_len=MAX_LEN,
                            use_kernel=use_kernel, paged=paged)
               for paged in (None, PagedKVConfig(block_size=block))]
    for eng in engines:
        eng.prefill_slots(prompts)
    for advances in ([3, 0, 2], [1, 3, 0]):
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (3, 3)),
                           jnp.int32)
        logits = []
        for eng in engines:
            lg, new_cache, _ = eng.decode_slots(toks)
            eng.commit_slots(new_cache, np.asarray(advances))
            logits.append(np.asarray(lg.astype(jnp.float32)))
        np.testing.assert_array_equal(logits[0], logits[1])
    dense, paged = engines
    assert np.array_equal(dense.slot_lens_host, paged.slot_lens_host)
    for s, length in enumerate(paged.slot_lens_host):
        for want, got in zip(_live_kv(dense, s, int(length)),
                             _live_kv(paged, s, int(length))):
            np.testing.assert_array_equal(want, got)


def test_paged_small_pool_backpressure(model):
    """A pool too small for all requests at once stalls admission (free
    BLOCKS gate, not free slots) but still serves every stream
    correctly."""
    cfg, params = model
    prompts = _prompts(cfg, 5, seed=13)
    dense, _ = _serve(cfg, params, "greedy", prompts, slots=3)
    # each request reserves cdiv(p + tokens, 16) <= 2 blocks; 3 blocks
    # force (mostly) serial admission despite 3 free slots
    paged, loop = _serve(cfg, params, "greedy", prompts, slots=3,
                         paged=PagedKVConfig(block_size=16, n_blocks=3))
    for rid in dense:
        assert np.array_equal(dense[rid], paged[rid])
    s = loop.stats()
    assert s["kv_blocks_peak"] <= 3
    assert max(e["active"] for e in loop.step_log) <= 2
    loop.engine.manager.check_invariants()


def test_paged_rejects_unsupported_arch():
    cfg = get_config("falcon_mamba_7b", reduced=True)
    params = init_model(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="attention-only"):
        DecodeEngine(cfg, params, batch=2, max_len=64,
                     paged=PagedKVConfig(block_size=16))


def test_paged_block_size_must_divide_max_len(model):
    cfg, params = model
    with pytest.raises(ValueError, match="multiple"):
        DecodeEngine(cfg, params, batch=2, max_len=100,
                     paged=PagedKVConfig(block_size=16))


# ===========================================================================
# Prefix caching
# ===========================================================================


def test_prefix_hit_skips_prefill(model):
    """The second admission of an identical prompt reuses the resident
    blocks: its prefill computes only the divergent suffix (forward
    counters + bucket width shrink), and the output stream is identical
    to dense serving."""
    cfg, params = model
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, cfg.vocab_size, size=37)
    dense, _ = _serve(cfg, params, "greedy", [prompt, prompt], slots=1)
    paged, loop = _serve(cfg, params, "greedy", [prompt, prompt], slots=1,
                         paged=PagedKVConfig(block_size=16))
    for rid in dense:
        assert np.array_equal(dense[rid], paged[rid])
    s = loop.stats()
    assert s["prefix_hits"] == 1
    assert s["prefix_hit_tokens"] == 32          # 2 full 16-token blocks
    assert s["prefill_positions_saved"] == 32
    assert s["prefill_positions_computed"] == 37 + 5
    log = loop.engine.prefill_log
    assert log[0]["cached_tokens"] == 0 and log[0]["computed_tokens"] == 37
    assert log[1]["cached_tokens"] == 32 and log[1]["computed_tokens"] == 5
    # the hit admission ran in a (much) narrower bucket than a full
    # prefill would have — the compile/positions win of skipping
    assert log[1]["bucket"] < log[0]["bucket"]
    loop.engine.manager.check_invariants()


def test_prefix_cache_off_never_hits(model):
    cfg, params = model
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, cfg.vocab_size, size=37)
    _, loop = _serve(cfg, params, "greedy", [prompt, prompt], slots=1,
                     paged=PagedKVConfig(block_size=16, prefix_cache=False))
    s = loop.stats()
    assert s["prefix_hits"] == 0
    assert s["prefill_positions_saved"] == 0


def test_prefix_hit_with_cow_divergence(model):
    """Prompt length an exact multiple of the block size: the whole
    prompt is cache-resident, the recomputed last position diverges
    INSIDE a shared block, and admission copy-on-writes it."""
    cfg, params = model
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, cfg.vocab_size, size=32)
    dense, _ = _serve(cfg, params, "greedy", [prompt, prompt], slots=1)
    paged, loop = _serve(cfg, params, "greedy", [prompt, prompt], slots=1,
                         paged=PagedKVConfig(block_size=16))
    for rid in dense:
        assert np.array_equal(dense[rid], paged[rid])
    s = loop.stats()
    assert s["prefix_hits"] == 1
    assert s["prefix_hit_tokens"] == 31          # p - 1
    assert s["cow_copies"] == 1
    loop.engine.manager.check_invariants()


def test_prefix_hit_kernel_path(model):
    """Prefix reuse through the Pallas path: hits still fire and streams
    match the no-cache paged serve (identical page-tiled numerics)."""
    cfg, params = model
    rng = np.random.default_rng(21)
    head = rng.integers(0, cfg.vocab_size, size=32)
    prompts = [np.concatenate([head, rng.integers(0, cfg.vocab_size,
                                                  size=4)])
               for _ in range(3)]
    nocache, _ = _serve(cfg, params, "greedy", prompts, slots=1,
                        use_kernel=True,
                        paged=PagedKVConfig(block_size=16,
                                            prefix_cache=False))
    cached, loop = _serve(cfg, params, "greedy", prompts, slots=1,
                          use_kernel=True,
                          paged=PagedKVConfig(block_size=16))
    for rid in nocache:
        assert np.array_equal(nocache[rid], cached[rid])
    assert loop.stats()["prefix_hits"] == 2


# ===========================================================================
# Allocator / refcount / COW invariants (hypothesis)
# ===========================================================================


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000),
       n_blocks=st.integers(min_value=4, max_value=24),
       block_size=st.sampled_from([4, 8, 16]))
def test_block_manager_invariants(seed, n_blocks, block_size):
    """Random admit/release traffic: refcounts always equal the sum of
    slot + cache holds, free blocks are never referenced, COW only
    fires when the divergence is inside a shared block, and the manager
    refuses (rather than corrupts) when the pool is truly full."""
    rng = np.random.default_rng(seed)
    batch, max_len = 4, 16 * block_size
    mgr = BlockManager(batch, max_len, block_size, n_blocks)
    vocab = 11
    shared = rng.integers(0, vocab, size=int(rng.integers(1, 3 * block_size)))
    live: dict = {}
    for _ in range(30):
        mgr.check_invariants()
        op = rng.random()
        free_slots = [s for s in range(batch) if s not in live]
        if op < 0.55 and free_slots:
            s = int(rng.choice(free_slots))
            if rng.random() < 0.5:
                tail = rng.integers(0, vocab,
                                    size=int(rng.integers(0, block_size)))
                prompt = np.concatenate([shared, tail]).astype(np.int64)
            else:
                prompt = rng.integers(0, vocab,
                                      size=int(rng.integers(1, 2 * block_size)))
            reserve = int(min(len(prompt) + int(rng.integers(0, 16)),
                              max_len))
            reserve = max(reserve, len(prompt))
            cow_before = mgr.cow_copies
            if not mgr.can_admit(prompt.tolist(), reserve):
                with pytest.raises(RuntimeError):
                    mgr.admit(s, prompt.tolist(), reserve)
                # a failed admit may leave a partial table; reset it
                mgr.release(s)
                continue
            res = mgr.admit(s, prompt.tolist(), reserve)
            assert 0 <= res.cached_len <= len(prompt) - 1
            if res.cow_copies:
                # COW only when the divergence sits inside a shared block
                assert res.cached_len % block_size != 0
                assert mgr.cow_copies == cow_before + len(res.cow_copies)
            mgr.register_prompt(s, prompt.tolist())
            live[s] = prompt
        elif live:
            s = int(rng.choice(sorted(live)))
            mgr.release(s)
            del live[s]
    mgr.check_invariants()
    for s in sorted(live):
        mgr.release(s)
    mgr.check_invariants()
    # only the prefix cache may still hold blocks
    held = mgr.allocator.n_used
    assert held == (len(mgr.prefix) if mgr.prefix is not None else 0)


def test_cow_admission_not_gated_on_tight_pool(model):
    """Regression: admission_cost must not double-count the COW source
    (it is decref'd back to evictable before the copy allocates).  On a
    pool exactly the size of one reservation, the second serve of a
    fully cached prompt must still admit — the old accounting gated it
    forever and run() span without serving."""
    bs = 16
    mgr = BlockManager(batch=1, max_len=4 * bs, block_size=bs, n_blocks=4)
    prompt = list(range(2 * bs))                     # fully block-aligned
    mgr.admit(0, prompt, reserve_len=4 * bs)
    mgr.register_prompt(0, prompt)
    mgr.release(0)
    assert mgr.can_admit(prompt, 4 * bs)             # was False (bug)
    res = mgr.admit(0, prompt, reserve_len=4 * bs)
    assert res.cached_len == 2 * bs - 1 and len(res.cow_copies) == 1
    mgr.check_invariants()
    # end-to-end: 1 slot, pool == one reservation, same prompt twice
    cfg, params = model
    rng = np.random.default_rng(17)
    p = rng.integers(0, cfg.vocab_size, size=32)
    eng = DecodeEngine(cfg, params, batch=1, max_len=256,
                       paged=PagedKVConfig(block_size=16, n_blocks=3))
    loop = ServingLoop(eng, mode="greedy")
    loop.submit(p, 8)
    loop.submit(p, 8)
    results = loop.run()
    assert len(results) == 2
    assert np.array_equal(results[0], results[1])
    assert loop.stats()["prefix_hits"] == 1


def test_refcount_sharing_and_eviction():
    """Two slots sharing a cached prefix: the shared blocks carry one
    hold per slot + one for the cache; eviction only recycles blocks
    whose sole hold is the cache's."""
    bs = 8
    mgr = BlockManager(batch=2, max_len=8 * bs, block_size=bs, n_blocks=6)
    prompt = list(range(2 * bs + 3))                   # 2 full blocks
    r0 = mgr.admit(0, prompt, reserve_len=3 * bs)
    assert r0.cached_len == 0 and r0.new_blocks == 3
    mgr.register_prompt(0, prompt)
    r1 = mgr.admit(1, prompt, reserve_len=3 * bs)
    assert r1.cached_len == 2 * bs
    shared = [int(mgr.tables[1, i]) for i in range(2)]
    assert shared == [int(mgr.tables[0, i]) for i in range(2)]
    for b in shared:
        assert mgr.allocator.refcount[b] == 3          # slot0 + slot1 + cache
    mgr.check_invariants()
    mgr.release(0)
    for b in shared:
        assert mgr.allocator.refcount[b] == 2
    mgr.release(1)
    for b in shared:
        assert mgr.allocator.refcount[b] == 1          # cache-only
    assert mgr.n_evictable() == 2
    # exhaust the pool: allocation must evict the cache-only blocks
    free_before = mgr.allocator.n_free
    grabbed = [mgr._alloc_or_evict() for _ in range(free_before + 2)]
    assert mgr.evictions == 2
    assert len(set(grabbed)) == len(grabbed)
    with pytest.raises(RuntimeError):
        mgr._alloc_or_evict()


# ===========================================================================
# Adversarial block-table layouts on the kernel path
# ===========================================================================


def _pool_from_dense(k_dense, v_dense, lens, n, bs, layout, layers=3,
                     layer=1, seed=0):
    """Pack a dense (b, s, kv, dh) cache into layer ``layer`` of a
    stacked (layers, kv, n_phys, dh, bs) pool under ``layout``:
    'fragmented' (random pages), 'reversed' (descending pages),
    'identity' (pages in order).  Every other layer holds noise."""
    b, s, kv, dh = k_dense.shape
    max_blocks = s // bs
    rng = np.random.default_rng(seed)
    need = []
    for bi in range(b):
        need.append(-(-int(lens[bi] + n) // bs))
    n_phys = sum(max(c, 1) for c in need) + 2          # + slack + trash
    order = np.arange(n_phys - 1)
    if layout == "fragmented":
        rng.shuffle(order)
    elif layout == "reversed":
        order = order[::-1]
    tables = np.full((b, max_blocks), n_phys - 1, np.int32)
    k_pool = np.asarray(
        rng.standard_normal((layers, kv, n_phys, dh, bs)), np.float32)
    v_pool = np.asarray(
        rng.standard_normal((layers, kv, n_phys, dh, bs)), np.float32)
    pi = 0
    for bi in range(b):
        for j in range(need[bi]):
            p = int(order[pi]); pi += 1
            tables[bi, j] = p
            for pool, dense in ((k_pool, k_dense), (v_pool, v_dense)):
                page = np.asarray(dense[bi, j * bs:(j + 1) * bs])
                pool[layer, :, p] = page.transpose(1, 2, 0)
    return jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(tables)


@functools.partial(jax.jit, static_argnames=("hps", "window", "block"))
def _paged_launch(q, k_pool, v_pool, lens, tables, *, hps, window=None,
                  block=None):
    """The paged launch over layer 1 of the pool with ``hps`` KV heads a
    grid step, behind ``ops.decode_attention_paged``'s layout prep:
    q (b, n, h, dh) in, (b, n, h, dh) out."""
    b, n, h, dh = q.shape
    kv = k_pool.shape[1]
    qb = select_q_block(n, dh)
    n_pad = round_up(n, qb)
    qk = q.reshape(b, n, kv, h // kv, dh).transpose(0, 2, 3, 1, 4)
    qk = jnp.pad(qk, ((0, 0), (0, 0), (0, 0), (0, n_pad - n), (0, 0)))
    o = decode_attention_paged_pallas(
        qk, k_pool, v_pool, lens, tables, jnp.asarray([1], jnp.int32),
        q_block=qb, scale=1.0 / dh ** 0.5, heads_per_step=hps,
        window=window, n_logical=n, block=block, interpret=True)
    return o[:, :, :, :n].transpose(0, 3, 1, 2, 4).reshape(b, n, h, dh)


@pytest.mark.parametrize("hps", [1, 2, 4])
@pytest.mark.parametrize("layout", ["fragmented", "reversed", "identity"])
@pytest.mark.parametrize("window", [None, 24])
def test_paged_kernel_adversarial_layouts(layout, window, hps):
    """Kernel-vs-oracle parity under hostile tables: scattered and
    reversed physical pages, a len-0 row, a single-block slot, and a
    full-cache row — junk in unattached pages, and in the other layers
    of the stacked pool, must never leak through, whether a grid step
    carries one of the 4 KV heads, two, or all (equal to one a step
    bit for bit; the ops entry launches the ``heads_per_step`` its
    shapes give)."""
    rng = np.random.default_rng(1)
    b, n, h, kv, dh = 4, 4, 8, 4, 64
    bs, s = 16, 96
    lens = np.array([0, 5, 16 - n, s - n], np.int32)   # len-0 / single-block
    q = jnp.asarray(rng.standard_normal((b, n, h, dh)), jnp.float32)
    k_dense = jnp.asarray(rng.standard_normal((b, s, kv, dh)), jnp.float32)
    v_dense = jnp.asarray(rng.standard_normal((b, s, kv, dh)), jnp.float32)
    k_pool, v_pool, tables = _pool_from_dense(k_dense, v_dense, lens, n,
                                              bs, layout)
    args = (q, k_pool, v_pool, jnp.asarray(lens), tables)
    out = _paged_launch(*args, hps=hps, window=window)
    ref = decode_attention_ref(q, k_dense, v_dense, jnp.asarray(lens),
                               window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    if hps > 1:
        np.testing.assert_array_equal(
            out, _paged_launch(*args, hps=1, window=window))
    if hps == heads_per_step(kv, h // kv, select_q_block(n, dh), dh, bs, 4):
        np.testing.assert_array_equal(
            out, decode_attention_paged(*args, 1, window=window))


# (n_heads, kv_heads, window, block): MHA, GQA with 3 and 8 query heads a
# KV head, 8 under a block-causal mask of blocks of 4, a sliding window
HEAD_LAYOUTS = {"mha": (4, 4, None, None), "gqa3": (12, 4, None, None),
                "gqa8_block4": (32, 4, None, 4), "window": (8, 4, 24, None)}


@functools.lru_cache(maxsize=None)
def _grouping_case(layout: str, n: int):
    """Inputs of one head-grouping case, the one-head-a-step launch's
    output and the reference's, all as numpy arrays."""
    h, kv, window, block = HEAD_LAYOUTS[layout]
    rng = np.random.default_rng(n)
    b, dh, bs, s = 3, 16, 16, 96
    lens = np.array([0, 8 + n % 4, s - n], np.int32)
    q = rng.standard_normal((b, n, h, dh)).astype(np.float32)
    k_dense = rng.standard_normal((b, s, kv, dh)).astype(np.float32)
    v_dense = rng.standard_normal((b, s, kv, dh)).astype(np.float32)
    k_pool, v_pool, tables = _pool_from_dense(k_dense, v_dense, lens, n,
                                              bs, "fragmented", seed=n)
    args = (jnp.asarray(q), k_pool, v_pool, jnp.asarray(lens), tables)
    one = _paged_launch(*args, hps=1, window=window, block=block)
    ref = decode_attention_ref(jnp.asarray(q), jnp.asarray(k_dense),
                               jnp.asarray(v_dense), jnp.asarray(lens),
                               window=window, block=block)
    return args, np.asarray(one), np.asarray(ref)


@pytest.mark.parametrize("hps", [1, 2, 4])
@pytest.mark.parametrize("n", [1, 4, 16])
@pytest.mark.parametrize("layout", sorted(HEAD_LAYOUTS))
def test_paged_kernel_head_groupings(layout, n, hps):
    """A grid step carrying ``hps`` of the 4 KV heads (one, a proper
    divisor, all) computes what one head a step does, bit for bit, and
    the reference's attention within float32 rounding: the heads are a
    batch axis of the step's matmuls, and the mask and the tile-skip
    rule are the row's alone."""
    _, _, window, block = HEAD_LAYOUTS[layout]
    args, one, ref = _grouping_case(layout, n)
    out = np.asarray(_paged_launch(*args, hps=hps, window=window,
                                   block=block))
    np.testing.assert_array_equal(out, one)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("n", [1, 3, 20])
def test_paged_kv_write_kernel_matches_xla_write(n):
    """The kernel path's write (``paged_kv_write``, a page at a time)
    leaves the stacked pool exactly as the XLA path's indexed write: in
    layer 1 of 3, for rows whose new positions start a page, straddle
    pages (n = 20 spans three), run past the block table into the trash
    page, or sit in a row whose table is all trash."""
    rng = np.random.default_rng(n)
    a = AttentionSpec(kind="gqa", n_heads=4, n_kv_heads=2, head_dim=16)
    b, bs, max_blocks, d = 4, 16, 4, 32
    n_phys = b * max_blocks + 1
    params = init_attention(jax.random.PRNGKey(n), d, a, jnp.float32)
    pool = {k: jnp.asarray(rng.standard_normal(
        (3, a.n_kv_heads, n_phys, a.head_dim, bs)), jnp.float32)
        for k in ("k", "v")}
    tables = rng.permutation(n_phys - 1)[:b * max_blocks].reshape(
        b, max_blocks).astype(np.int32)
    tables[3] = n_phys - 1                       # an inactive row
    offsets = jnp.asarray([0, 14, 60, 5], jnp.int32)
    x = jnp.asarray(rng.standard_normal((b, n, d)), jnp.float32)
    outs = [gqa_decode_paged(params, a, x, dict(pool), offsets,
                             jnp.asarray(tables), 1, 10000.0,
                             use_kernel=use_kernel)
            for use_kernel in (True, False)]
    for k in ("k", "v"):
        got, want = (np.asarray(o[1][k])[:, :, :-1] for o in outs)
        np.testing.assert_array_equal(got, want)    # trash page aside
    np.testing.assert_allclose(np.asarray(outs[0][0][:3]),
                               np.asarray(outs[1][0][:3]),
                               atol=2e-4, rtol=2e-4)


# ===========================================================================
# Admission rejection (the prefill_bucket clamp bugfix)
# ===========================================================================


@pytest.mark.parametrize("paged", [None, PagedKVConfig(block_size=16)])
def test_submit_rejects_oversized_prompt(model, paged):
    """A prompt longer than max_len is rejected at submit with a clear
    error instead of failing deep inside the clamped prefill bucket."""
    cfg, params = model
    eng = DecodeEngine(cfg, params, batch=2, max_len=64, paged=paged)
    loop = ServingLoop(eng, mode="greedy")
    with pytest.raises(ValueError, match="exceeds the engine's max_len"):
        loop.submit(np.arange(65) % cfg.vocab_size, max_tokens=1)
    with pytest.raises(ValueError, match="cannot fit"):
        loop.submit(np.arange(60) % cfg.vocab_size, max_tokens=16)
    with pytest.raises(ValueError, match="empty"):
        loop.submit(np.zeros((0,), np.int64), max_tokens=4)


def test_prefill_slots_rejects_oversized_prompt(model):
    """The engine-level API guards too (callers that bypass the loop)."""
    cfg, params = model
    eng = DecodeEngine(cfg, params, batch=2, max_len=64)
    with pytest.raises(ValueError, match="exceeds the engine's max_len"):
        eng.prefill_slots({0: jnp.zeros((70,), jnp.int32)})


def test_submit_rejects_request_larger_than_pool(model):
    cfg, params = model
    eng = DecodeEngine(cfg, params, batch=2, max_len=256,
                       paged=PagedKVConfig(block_size=16, n_blocks=4))
    loop = ServingLoop(eng, mode="greedy")
    with pytest.raises(ValueError, match="KV blocks"):
        loop.submit(np.arange(100) % cfg.vocab_size, max_tokens=50)
