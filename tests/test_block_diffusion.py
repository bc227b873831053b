"""Block diffusion (SDAR) and the held-expert share on the CPU, at toy
widths with seeded random weights.

- the paged Pallas kernel's block-causal mask (interpret mode) against
  the XLA path's;
- prefill of the prompt's whole blocks, then refinement and commit
  through the paged pool, against the float32 reference's full
  block-causal forward (``chipbench/configs/sdar_30b_a3b.py``), on
  logits;
- the serving loop's block-diffusion sampler: one block per step, each
  position's refinement step recorded, masked by flag;
- the expert share: the shares' outputs sum to the uncut layer, and a
  layer that holds every expert computes what it computed before the
  share existed.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.arch import FFNSpec
from repro.kernels.decode_attention.ops import decode_attention_paged
from repro.models import init_model
from repro.models.attention import _causal_mask, _gqa_core, _paged_gather
from repro.models.moe import init_moe, moe_ffn, route_topk
from repro.serving import DecodeEngine, PagedKVConfig, ServingLoop

BENCH = Path(__file__).resolve().parents[1] / "chipbench"
SEED = 2**33 + 21
L = 4


@pytest.fixture(scope="module")
def sdar():
    """The configuration module (its float32 reference), the toy
    configuration's architecture, the program's config and its served
    bf16 weights drawn by path from SEED."""
    sys.path.insert(0, str(BENCH))
    try:
        spec = importlib.util.spec_from_file_location(
            "sdar_30b_a3b_module", BENCH / "configs" / "sdar_30b_a3b.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        import weights
    finally:
        sys.path.remove(str(BENCH))
    config = json.loads((BENCH / "testdata" / "tiny_sdar.json").read_text())
    cfg = mod.program_config(config)
    shapes = jax.eval_shape(lambda k: init_model(k, cfg),
                            jax.random.PRNGKey(0))
    return mod, mod.arch_of(config), cfg, weights.build(shapes, SEED)


# ---------------------------------------------------------------------------
# the kernel's mask
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,lens", [(4, (0, 8, 20)), (8, (4, 12, 0)),
                                    (3, (8, 16, 4))],
                         ids=["one_block", "two_blocks", "part_block"])
def test_paged_kernel_block_mask_matches_xla(n, lens):
    """Block-causal paged attention: the interpreted Pallas kernel and the
    XLA path (gathered pages, ``_causal_mask`` with the block and the
    written range) agree."""
    b, h, kv, dh, bs, n_phys, layers = 3, 4, 2, 16, 8, 20, 2
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (b, n, h, dh), jnp.float32)
    k_pool = jax.random.normal(ks[1], (layers, kv, n_phys, dh, bs))
    v_pool = jax.random.normal(ks[2], (layers, kv, n_phys, dh, bs))
    rng = np.random.default_rng(0)
    tables = jnp.asarray(rng.permutation(n_phys - 1)[:b * 4].reshape(b, 4),
                         jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    got = decode_attention_paged(q, k_pool, v_pool, lens, tables, 1,
                                 block=L, interpret=True)
    kk, vv = _paged_gather(k_pool, 1, tables), _paged_gather(v_pool, 1,
                                                             tables)
    q_pos = lens[:, None] + jnp.arange(n)[None]
    kv_pos = jnp.broadcast_to(jnp.arange(kk.shape[1])[None], (b, kk.shape[1]))
    mask = _causal_mask(q_pos, kv_pos, kv_valid=kv_pos < (lens + n)[:, None],
                        block=L)
    want = _gqa_core(q, kk, vv, mask, 1.0 / dh ** 0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# ---------------------------------------------------------------------------
# the serving path against the float32 reference
# ---------------------------------------------------------------------------
def test_full_forward_matches_reference_in_float32(sdar):
    """The program's own full forward (block-causal mask, per-head q/k
    norm, held experts) with the weights widened to float32 gives the
    reference's logits to float32 rounding."""
    from repro.models import forward
    mod, arch, cfg, params = sdar
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    rng = np.random.default_rng(1)
    seqs = [rng.integers(0, cfg.vocab_size, n) for n in (13, 22)]
    for s, ref in zip(seqs, mod.logits_of(arch, SEED, seqs)):
        with jax.default_matmul_precision("highest"):
            logits = forward(params, cfg, {"tokens": jnp.asarray(s)[None]},
                             mode="train")[0][0]
        np.testing.assert_allclose(np.asarray(logits), ref, rtol=0,
                                   atol=2e-4 * np.abs(ref).max())


@pytest.mark.parametrize("tail", [0, 1, 2, 3])
def test_block_prefill_and_refinement_match_reference(sdar, tail):
    """A prompt of 8 + ``tail`` tokens keeps its 2 whole blocks; the first
    block holds the tail, one refined token and MASK.  Its refinement
    forward, then, after its commit, the next block's first refinement
    agree with the reference's full forward over the same sequence.
    The tolerance is bf16's: the program computes in bf16, the reference
    in float32 from the same weights.  3e-2 of the largest logit is about
    four times the widest difference seen (7.9e-3) and a tenth of the
    least that a causal kernel mask in place of the block-causal one
    gives (0.33)."""
    mod, arch, cfg, params = sdar
    mask = arch["mask_id"]
    rng = np.random.default_rng(tail)
    prompt = rng.integers(0, mask, 8 + tail)
    eng = DecodeEngine(cfg, params, batch=2, max_len=64, use_kernel=True,
                       paged=PagedKVConfig(block_size=16))
    eng.prefill_slots({1: prompt})
    assert eng.slot_lens_host[1] == 8
    first = np.concatenate([prompt[8:], rng.integers(0, mask, 1),
                            np.full(L - tail - 1, mask)])[:L]
    second = np.concatenate([rng.integers(0, mask, 1), np.full(3, mask)])
    toks = np.zeros((2, L), np.int64)
    toks[1] = first
    got = [np.asarray(eng.decode_slots(jnp.asarray(toks, jnp.int32))[0][1],
                      np.float32)]
    # commit the block resolved (its masks fixed to tokens)
    resolved = np.where(first == mask, rng.integers(0, mask, L), first)
    toks[1] = resolved
    _, cache, _ = eng.decode_slots(jnp.asarray(toks, jnp.int32))
    eng.commit_slots(cache, np.asarray([0, L]))
    toks[1] = second
    got.append(np.asarray(eng.decode_slots(jnp.asarray(toks, jnp.int32))[0][1],
                          np.float32))
    ref = mod.logits_of(arch, SEED, [
        np.concatenate([prompt[:8], first]),
        np.concatenate([prompt[:8], resolved, second])])
    for g, r in zip(got, ref):
        np.testing.assert_allclose(g, r[-L:], rtol=0,
                                   atol=3e-2 * np.abs(r).max())


def test_block_diffusion_serving_records_each_fix(sdar):
    """Through ``ServingLoop(mode="diffusion")``: every step commits one
    aligned block per row; each generated position records the step that
    fixed it; a prompt ending in the mask id keeps it as a token (masked
    by flag); no prefill token is emitted; the row's forward counter
    holds its refinements and commits."""
    _, arch, cfg, params = sdar
    mask = arch["mask_id"]
    eng = DecodeEngine(cfg, params, batch=3, max_len=64, use_kernel=True,
                       paged=PagedKVConfig(block_size=16))
    loop = ServingLoop(eng, mode="diffusion")
    assert loop.adapter.width(3, 1) == L
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, mask, p) for p in (8, 9, 10, 11)]
    prompts[1][-1] = mask
    reqs = [loop.submit(p, t) for p, t in zip(prompts, (5, 7, 6, 9))]
    loop.run()
    for req in reqs:
        p = len(req.prompt)
        end = p + len(req.generated)
        assert end % L == 0 and end - L < p + req.max_tokens <= end
        assert len(req.fixed_at) == len(req.generated)
        steps = np.asarray(req.fixed_at)
        # a block's masked positions are fixed one a step (ceil(masked /
        # 4), at most 4 masked), in steps 0, 1, ...
        for b in range(p // L, end // L):
            g = np.arange(max(p, b * L), (b + 1) * L) - p
            assert sorted(steps[g]) == list(range(len(g)))
        assert req.forwards > 0
        assert len(req.tokens()) == req.max_tokens
    # the prompt's mask id was a fixed token: its block refined 3 masks
    assert sorted(reqs[1].fixed_at[:3]) == [0, 1, 2]
    assert loop.stats()["tokens_per_forward"] > 1.0


# ---------------------------------------------------------------------------
# the expert share
# ---------------------------------------------------------------------------
def _share(params, f: FFNSpec, first: int, held: int):
    """One chip's slice of a whole layer's weights: the router whole."""
    sub = {k: (v if k == "router" else v[first:first + held])
           for k, v in params.items()}
    return sub, dataclasses.replace(f, held=held, first_held=first)


def test_expert_shares_sum_to_the_uncut_layer():
    """16 experts top-4 over 8 chips of 2 experts each: the shares'
    partial outputs add up to the layer computed with every expert."""
    f = FFNSpec(kind="moe", d_ff=32, activation="swiglu", n_experts=16,
                top_k=4)
    params = init_moe(jax.random.PRNGKey(2), 64, f, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (3, 5, 64), jnp.float32)
    whole, _ = moe_ffn(params, f, x)
    parts = []
    for first in range(0, 16, 2):
        sub, share = _share(params, f, first, 2)
        parts.append(moe_ffn(sub, share, x)[0])
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=0, atol=1e-5 * float(jnp.abs(whole).max()))
    # each share is a real part: no share alone is the layer
    assert all(float(jnp.abs(p - whole).max()) > 1e-3 for p in parts)


def _layer_before_the_share(params, f: FFNSpec, x):
    """The MoE FFN as it was computed before layers knew which experts
    they hold: every expert's group in the grouped GEMM."""
    d = x.shape[-1]
    xt = x.reshape(-1, d)
    weights, top_idx, _ = route_topk(params["router"], xt, f.top_k)
    flat_idx = top_idx.reshape(-1)
    flat_w = weights.reshape(-1)
    order = jnp.argsort(flat_idx)
    token_of_pair = order // f.top_k
    x_sorted = xt[token_of_pair]
    group_sizes = jnp.bincount(flat_idx, length=f.n_experts).astype(
        jnp.int32)
    up = jax.lax.ragged_dot(x_sorted, params["w_up"], group_sizes)
    gate = jax.lax.ragged_dot(x_sorted, params["w_gate"], group_sizes)
    h = (jax.nn.silu(gate.astype(jnp.float32))
         * up.astype(jnp.float32)).astype(x.dtype)
    h_out = jax.lax.ragged_dot(h, params["w_down"], group_sizes)
    contrib = h_out.astype(jnp.float32) * flat_w[order][:, None]
    out = jnp.zeros((xt.shape[0], d), jnp.float32).at[token_of_pair].add(
        contrib)
    return out.astype(x.dtype).reshape(x.shape)


def test_all_experts_held_computes_the_layer_as_before():
    """Granite's layer (every expert held): bit for bit what the layer
    computed before the share, in its served bf16."""
    cfg = get_config("granite_moe_3b_a800m", reduced=True)
    params = init_moe(jax.random.PRNGKey(6), cfg.d_model, cfg.ffn)
    x = jax.random.normal(jax.random.PRNGKey(7), (4, 6, cfg.d_model),
                          jnp.bfloat16)
    got = jax.jit(lambda p, x: moe_ffn(p, cfg.ffn, x)[0])(params, x)
    want = jax.jit(lambda p, x: _layer_before_the_share(p, cfg.ffn, x))(
        params, x)
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(want, np.float32))
