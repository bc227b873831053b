#!/usr/bin/env python3
"""Bring-up smoke test of the serving path on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the sharded train step on four chips

One chip, in order, one info line per check:

  device   platform, device_kind and count; the HardwareSpec of that kind
  kernels  ragged and paged decode attention (stablelm_3b widths), the
           grouped MoE FFN (granite_moe_3b_a800m widths) and the selective
           scan (falcon_mamba_7b widths), compiled, each against its plain
           jax.numpy reference on the same chip
  serve    stablelm_3b at published widths with random bf16 weights: eight
           requests through ServingLoop on a paged DecodeEngine with the
           kernels on, greedy then speculative, then an identical repeat
           pass that must compile nothing
  xla      one decode forward of a filled engine through XLA attention,
           against the kernel path's logits

``--four-chips`` runs only launch/train's sharded train step on a 4-chip
mesh (stablelm_3b widths, 4 layers) against the same steps on one chip.

The script fails unless JAX's first device is a TPU: it never falls back
to the CPU.  The last line of standard output is one JSON verdict, printed
only when every check passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import itertools
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
if not (SRC / "repro").is_dir():
    sys.exit(f"chip_smoke: no repro package under {SRC}; run this script "
             "from a checkout of the repository")
sys.path.insert(0, str(SRC))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.hardware import spec_for_device_kind  # noqa: E402
from repro.data import DataConfig, make_pipeline  # noqa: E402
from repro.dist.elastic import elastic_mesh  # noqa: E402
from repro.kernels.decode_attention.ops import (  # noqa: E402
    decode_attention_paged, decode_attention_ragged)
from repro.kernels.decode_attention.ref import decode_attention_ref  # noqa: E402
from repro.kernels.mamba_scan.ops import selective_scan  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.train import sharded_train  # noqa: E402
from repro.models import init_model  # noqa: E402
from repro.models.mamba import _mamba1_scan  # noqa: E402
from repro.models.moe import init_moe, moe_ffn  # noqa: E402
from repro.serving import DecodeEngine, PagedKVConfig, ServingLoop  # noqa: E402
from repro.serving.engine import (_copy_pool_blocks, _decode_paged_fn,  # noqa: E402
                                  _prefill_fn, _scatter_prefill,
                                  greedy_tokens)
from repro.training import AdamWConfig  # noqa: E402

SEED = 0

# Allowed max-abs error, as a multiple of the reference's largest
# magnitude.  bf16 keeps 8 significant bits (one ulp is 2^-8 of the
# value's power of two), so a kernel that computes in f32 and rounds its
# output to bf16 differs from an f32 reference by about one ulp; four
# ulps leave room for the reference's own rounding of bf16 inputs.
RTOL_BF16 = 2.0 ** -6
# f32 scan, elementwise in both: the only differences are exp/sum
# rounding in a different order.
RTOL_F32 = 1e-4
# bf16 model, 32 layers: XLA and the kernel round the attention output
# differently in every layer, and the residual stream carries it on.
RTOL_LOGITS = 5e-2
# Training loss of the same seeded steps on one and on four chips: the
# 4-way tensor-parallel matmuls sum their partials in another order.
ATOL_LOSS = 1e-2


class SmokeFailure(RuntimeError):
    pass


@dataclasses.dataclass(frozen=True)
class Size:
    """Batch and sequence sizes around the configs' own widths."""
    batch: int            # decode rows / cache slots
    seq: int              # kernel-check cache length
    block: int            # paged KV block (positions)
    max_len: int          # serving cache length per slot
    requests: int
    prompt: tuple         # (shortest, longest) prompt
    tokens: int           # new tokens per request
    scan_seq: int         # selective-scan positions


FULL = Size(batch=4, seq=4096, block=128, max_len=2048, requests=8,
            prompt=(128, 512), tokens=32, scan_seq=128)
REDUCED = Size(batch=2, seq=256, block=16, max_len=128, requests=3,
               prompt=(8, 40), tokens=6, scan_seq=40)


def _size(reduced: bool) -> Size:
    return REDUCED if reduced else FULL


def check_device(count: int) -> tuple:
    """JAX's devices must be ``count`` TPUs of a kind with known peaks."""
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SmokeFailure(
            f"no TPU: JAX's first device is {dev.platform!r} "
            f"({dev.device_kind}); this smoke test runs only on a TPU")
    if len(devs) != count:
        raise SmokeFailure(f"{len(devs)} TPU devices, this run needs {count}")
    hw = spec_for_device_kind(dev.device_kind)
    print(f"device: platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devs)} spec={hw.name} "
          f"(phi={hw.phi:.4g} FLOP/s, beta={hw.beta:.4g} B/s, "
          f"hbm={hw.hbm_bytes:.4g} B)")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}, hw


def _compare(name: str, got, ref, rtol: float) -> None:
    got = np.asarray(jnp.asarray(got, jnp.float32))
    ref = np.asarray(jnp.asarray(ref, jnp.float32))
    if got.shape != ref.shape or not np.all(np.isfinite(got)):
        raise SmokeFailure(f"{name}: shape {got.shape} vs {ref.shape}, "
                           f"finite={bool(np.all(np.isfinite(got)))}")
    err = float(np.max(np.abs(got - ref)))
    scale = float(np.max(np.abs(ref)))
    tol = rtol * scale
    line = (f"{name}: max_abs_err={err!r} tol={tol!r} "
            f"(rtol={rtol!r} x max|ref|={scale!r})")
    print(line)
    if not err <= tol:
        raise SmokeFailure(line)


def check_kernels(reduced: bool = False) -> None:
    """Each main-path kernel against its plain jnp reference."""
    size = _size(reduced)
    key = jax.random.PRNGKey(SEED)
    ks = iter(jax.random.split(key, 16))
    rng = np.random.default_rng(SEED)

    # --- decode attention at stablelm_3b widths --------------------------
    a = get_config("stablelm_3b", reduced).attention
    b, s, bs = size.batch, size.seq, size.block
    for n in (1, 8):
        lens = jnp.asarray(rng.integers(0, s - n, b), jnp.int32)
        q = jax.random.normal(next(ks), (b, n, a.n_heads, a.head_dim),
                              jnp.bfloat16)
        kc = jax.random.normal(next(ks), (b, s, a.n_kv_heads, a.head_dim),
                               jnp.bfloat16)
        vc = jax.random.normal(next(ks), (b, s, a.n_kv_heads, a.head_dim),
                               jnp.bfloat16)
        with jax.default_matmul_precision("highest"):
            ref = decode_attention_ref(q.astype(jnp.float32),
                                       kc.astype(jnp.float32),
                                       vc.astype(jnp.float32), lens)
        _compare(f"kernels ragged_attention n={n} b={b} s={s} "
                 f"h={a.n_heads} kv={a.n_kv_heads} dh={a.head_dim}",
                 decode_attention_ragged(q, kc, vc, lens), ref, RTOL_BF16)
        # the same cache scattered over a pool in shuffled pages, plus
        # the trailing trash page
        n_blk = s // bs
        perm = rng.permutation(b * n_blk).astype(np.int32)
        tables = jnp.asarray(perm.reshape(b, n_blk))
        pool_shape = (1, a.n_kv_heads, b * n_blk + 1, a.head_dim, bs)

        def pages(c):
            return c.reshape(b * n_blk, bs, a.n_kv_heads,
                             a.head_dim).transpose(0, 2, 3, 1)
        kp = jnp.zeros(pool_shape, jnp.bfloat16).at[
            0, :, tables.reshape(-1)].set(pages(kc))
        vp = jnp.zeros(pool_shape, jnp.bfloat16).at[
            0, :, tables.reshape(-1)].set(pages(vc))
        _compare(f"kernels paged_attention n={n} b={b} s={s} block={bs}",
                 decode_attention_paged(q, kp, vp, lens, tables, 0), ref,
                 RTOL_BF16)

    # --- grouped MoE FFN at granite_moe_3b_a800m widths ------------------
    g = get_config("granite_moe_3b_a800m", reduced)
    params = init_moe(next(ks), g.d_model, g.ffn)
    x = jax.random.normal(next(ks), (b * 8, g.d_model), jnp.bfloat16)
    got, _ = moe_ffn(params, g.ffn, x, use_kernel=True)
    ref, _ = moe_ffn(params, g.ffn, x, use_kernel=False)   # ragged_dot
    _compare(f"kernels grouped_ffn tokens={x.shape[0]} d={g.d_model} "
             f"f={g.ffn.d_ff} E={g.ffn.n_experts} k={g.ffn.top_k}",
             got, ref, RTOL_BF16)

    # --- selective scan at falcon_mamba_7b widths ------------------------
    m = get_config("falcon_mamba_7b", reduced)
    di, ds, t = m.ssm.d_inner(m.d_model), m.ssm.d_state, size.scan_seq
    xs = jax.random.normal(next(ks), (b, t, di), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(next(ks), (b, t, di)) - 1.0)
    b_in = jax.random.normal(next(ks), (b, t, ds), jnp.float32)
    c_in = jax.random.normal(next(ks), (b, t, ds), jnp.float32)
    a_mat = -jnp.broadcast_to(jnp.arange(1, ds + 1, dtype=jnp.float32),
                              (di, ds))
    h0 = jax.random.normal(next(ks), (b, di, ds), jnp.float32)
    y, h = selective_scan(xs, dt, b_in, c_in, a_mat, h0)
    with jax.default_matmul_precision("highest"):
        y_ref, h_ref = _mamba1_scan(xs, dt, b_in, c_in, a_mat, h0)
    _compare(f"kernels selective_scan y b={b} s={t} d_inner={di} "
             f"d_state={ds}", y, y_ref, RTOL_F32)
    _compare("kernels selective_scan h_final", h, h_ref, RTOL_F32)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, and the number
    of executables it built, from its monitoring events."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event in (self.TRACE, self.LOWER, self.BACKEND):
            self.seconds += duration
        if event == self.BACKEND:
            self.compiles += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self)


STEP_FNS = (_decode_paged_fn, _prefill_fn, _scatter_prefill, greedy_tokens,
            _copy_pool_blocks)


def _step_cache_entries() -> dict:
    return {f.__name__: f._cache_size() for f in STEP_FNS}


def _prompts(vocab: int, size: Size) -> list:
    rng = np.random.default_rng(SEED)
    lo, hi = size.prompt
    return [rng.integers(0, vocab, int(rng.integers(lo, hi + 1)))
            for _ in range(size.requests)]


def _engine(cfg, params, size: Size) -> DecodeEngine:
    # a ServingLoop and its adapter reference each other, so an earlier
    # engine's KV pool (2.5 GiB at full width) lives until the cycle
    # collector runs: collect before allocating the next one
    gc.collect()
    return DecodeEngine(cfg, params, batch=size.batch, max_len=size.max_len,
                        use_kernel=True,
                        paged=PagedKVConfig(block_size=size.block))


def serve(reduced: bool = False) -> dict:
    """Serve the requests in greedy and speculative mode, then repeat the
    speculative pass; returns the counts the verdict checks."""
    size = _size(reduced)
    cfg = get_config("stablelm_3b", reduced)
    params = init_model(jax.random.PRNGKey(SEED), cfg)
    prompts = _prompts(cfg.vocab_size, size)

    def one_pass(mode: str) -> int:
        eng = _engine(cfg, params, size)
        loop = ServingLoop(eng, mode=mode)
        for p in prompts:
            loop.submit(p, size.tokens)
        t0 = time.perf_counter()
        results = loop.run()
        wall = time.perf_counter() - t0
        ok = sum(len(t) == size.tokens and bool(np.all((t >= 0)
                                                       & (t < cfg.vocab_size)))
                 for t in results.values())
        st = loop.stats()
        print(f"serve {cfg.name} mode={mode}: {ok}/{len(prompts)} requests "
              f"complete, {st['tokens']} tokens, {st['forwards']} forwards, "
              f"{st['prefill_forwards']} prefills, wall={wall!r}s (info)")
        if ok != len(prompts):
            raise SmokeFailure(f"serve {mode}: {ok}/{len(prompts)} requests "
                               f"returned {size.tokens} tokens in [0, vocab)")
        return st["tokens"]

    with CompileClock() as clock:
        served = one_pass("greedy") + one_pass("speculative")
    warm = _step_cache_entries()
    with CompileClock() as again:
        served += one_pass("speculative")
    new = {k: v - warm[k] for k, v in _step_cache_entries().items()}
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"serve: compile_s={clock.seconds!r} executables={clock.compiles} "
          f"tokens_served={served} repeat_new_step_cache_entries="
          f"{sum(new.values())} repeat_executables={again.compiles} "
          f"peak_bytes_in_use={peak}")
    if any(new.values()) or again.compiles:
        raise SmokeFailure(f"the repeat pass compiled: {new}, "
                           f"{again.compiles} executables")
    return {"tokens": served, "peak_bytes_in_use": peak}


def kernel_vs_xla(reduced: bool = False) -> None:
    """One decode forward of a filled engine, kernel vs XLA attention."""
    size = _size(reduced)
    cfg = get_config("stablelm_3b", reduced)
    gc.collect()                      # the serve check's engines and params
    params = init_model(jax.random.PRNGKey(SEED), cfg)
    eng = _engine(cfg, params, size)
    loop = ServingLoop(eng, mode="greedy")
    for p in _prompts(cfg.vocab_size, size)[:size.batch]:
        loop.submit(p, size.tokens)
    loop.admit()
    tokens = jax.random.randint(jax.random.PRNGKey(SEED + 1),
                                (size.batch, 8), 0, cfg.vocab_size)
    tables = jnp.asarray(eng.manager.device_tables())
    # each forward donates the pool it is given: hand the next one the
    # pool the last returned.  Both write the same positions before they
    # read them, past every row's committed length.
    got, eng.cache, _ = _decode_paged_fn(params, cfg, tokens, eng.cache,
                                         eng.slot_lens, tables,
                                         use_kernel=True)
    ref, eng.cache, _ = _decode_paged_fn(params, cfg, tokens, eng.cache,
                                         eng.slot_lens, tables,
                                         use_kernel=False)
    _compare(f"xla decode logits {cfg.name} slots={size.batch} n=8 "
             f"lens={eng.slot_lens_host.tolist()}", got, ref, RTOL_LOGITS)


def four_chips(reduced: bool = False) -> None:
    """launch/train's sharded step on a 4-chip mesh vs one chip."""
    cfg = dataclasses.replace(get_config("stablelm_3b", reduced), n_layers=4)
    steps, global_batch, seq = 3, 8, 128
    opt_cfg = AdamWConfig(warmup_steps=0, total_steps=steps)
    data = make_pipeline(DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                                    global_batch=global_batch, seed=SEED))
    batches = [{"tokens": jnp.asarray(d["tokens"])}
               for d in itertools.islice(data, steps)]
    shape, axes = elastic_mesh(4)

    def run(mesh) -> list:
        init, step = sharded_train(cfg, mesh, opt_cfg,
                                   global_batch=global_batch, n_micro=2)
        state = jax.block_until_ready(init(jax.random.PRNGKey(SEED)))
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in jax.devices()]
        losses = []
        for batch in batches:
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        print(f"four_chips mesh={dict(zip(axes, mesh.devices.shape))} "
              f"layers={cfg.n_layers} d_model={cfg.d_model}: "
              f"losses={losses} bytes_in_use_per_chip_after_init={in_use}")
        return losses

    mesh4 = make_mesh(shape, axes)
    one = make_mesh((1,) * len(axes), axes, devices=jax.devices()[:1])
    l4, l1 = run(mesh4), run(one)
    diff = max(abs(x - y) for x, y in zip(l4, l1))
    line = (f"four_chips: max |loss_4 - loss_1| = {diff!r} "
            f"tol={ATOL_LOSS!r}")
    print(line)
    if not diff <= ATOL_LOSS or not all(np.isfinite(l4 + l1)):
        raise SmokeFailure(line)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train step on four chips "
                         "against one chip")
    args = ap.parse_args(argv)
    enable_compile_cache()
    try:
        device, hw = check_device(4 if args.four_chips else 1)
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    if args.four_chips:
        four_chips()
    else:
        check_kernels()
        out = serve()
        peak = out["peak_bytes_in_use"]
        if peak is None or not peak < hw.hbm_bytes:
            raise SmokeFailure(f"peak_bytes_in_use={peak} against "
                               f"{hw.hbm_bytes:.0f} B of HBM")
        kernel_vs_xla()
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
