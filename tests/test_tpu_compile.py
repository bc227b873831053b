"""The main-path kernels and the serving step compile for a TPU v5e.

Nothing runs: each case lowers and compiles for a described ``v5e:2x2``
chip, which refuses what interpret mode cannot show (scoped-VMEM
overflow, unaligned blocks, a program larger than the chip's memory).
The topology is described inside a fixture, so importing this file loads
no TPU library, and every case skips together where none can be
described.  The persistent compile cache is off around the compiles: an
entry compiled for a described chip cannot be read back without one.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.arch import AttentionSpec
from repro.kernels.decode_attention.ops import (decode_attention_paged,
                                                decode_attention_ragged,
                                                heads_per_step)
from repro.kernels.mamba_scan.ops import selective_scan
from repro.kernels.moe_ffn.ops import grouped_ffn
from repro.models import init_model
from repro.models.transformer import init_paged_cache
from repro.serving.engine import _decode_paged_fn

HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot describe a v5e here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


ATTN = {
    "stablelm_3b": get_config("stablelm_3b").attention,
    "granite_moe_3b_a800m": get_config("granite_moe_3b_a800m").attention,
    "sdar_30b_a3b": get_config("sdar_30b_a3b").attention,
}


@pytest.mark.parametrize("arch", sorted(ATTN))
@pytest.mark.parametrize("n", [1, 8])
def test_ragged_attention_compiles(one_chip, arch, n):
    a = ATTN[arch]
    b, s = 4, 4096
    args = (_spec(one_chip, (b, n, a.n_heads, a.head_dim), jnp.bfloat16),
            _spec(one_chip, (b, s, a.n_kv_heads, a.head_dim), jnp.bfloat16),
            _spec(one_chip, (b, s, a.n_kv_heads, a.head_dim), jnp.bfloat16),
            _spec(one_chip, (b,), jnp.int32))
    jax.jit(lambda *x: decode_attention_ragged(
        *x, block=a.diffusion_block, interpret=False)).lower(*args).compile()


def _compile_paged(one_chip, a, n, block):
    b, s = 4, 4096
    n_phys = b * s // block + 1
    args = (_spec(one_chip, (b, n, a.n_heads, a.head_dim), jnp.bfloat16),
            _spec(one_chip, (2, a.n_kv_heads, n_phys, a.head_dim, block),
                  jnp.bfloat16),
            _spec(one_chip, (2, a.n_kv_heads, n_phys, a.head_dim, block),
                  jnp.bfloat16),
            _spec(one_chip, (b,), jnp.int32),
            _spec(one_chip, (b, s // block), jnp.int32),
            _spec(one_chip, (), jnp.int32))
    jax.jit(lambda *x: decode_attention_paged(
        *x, block=a.diffusion_block, interpret=False)).lower(*args).compile()


@pytest.mark.parametrize("arch", sorted(ATTN))
@pytest.mark.parametrize("block", [16, 128])
@pytest.mark.parametrize("n", [1, 16, 65])
def test_paged_attention_compiles(one_chip, arch, block, n):
    """Every KV head of a page in one grid step fits scoped VMEM at the
    three configurations' layouts, up to two query tiles a row."""
    _compile_paged(one_chip, ATTN[arch], n, block)


def test_paged_attention_compiles_below_all_heads(one_chip):
    """64 query heads over 8 KV heads of 128: all 8 in one step would
    overflow scoped VMEM, so the shapes give fewer heads a step, and
    that program compiles."""
    a = AttentionSpec(kind="gqa", n_heads=64, n_kv_heads=8, head_dim=128)
    assert heads_per_step(8, 8, 64, 128, 128, 2) < 8
    _compile_paged(one_chip, a, 65, 128)


@pytest.mark.parametrize("arch", sorted(ATTN))
def test_heads_per_step_takes_every_kv_head(arch):
    """At the three configurations' layouts (bf16, query tile 64, pages
    of 128) one grid step carries every KV head."""
    a = ATTN[arch]
    assert heads_per_step(a.n_kv_heads, a.n_heads // a.n_kv_heads, 64,
                          a.head_dim, 128, 2) == a.n_kv_heads


@pytest.mark.parametrize("tokens", [4, 32])
def test_grouped_ffn_compiles(one_chip, tokens):
    f = get_config("granite_moe_3b_a800m").ffn
    d = get_config("granite_moe_3b_a800m").d_model
    e, m = f.n_experts, tokens * f.top_k
    params = {"w_gate": _spec(one_chip, (e, d, f.d_ff), jnp.bfloat16),
              "w_up": _spec(one_chip, (e, d, f.d_ff), jnp.bfloat16),
              "w_down": _spec(one_chip, (e, f.d_ff, d), jnp.bfloat16)}
    jax.jit(lambda x, p, g: grouped_ffn(x, p, g, f.activation,
                                        interpret=False, n_tokens=tokens)
            ).lower(_spec(one_chip, (m, d), jnp.bfloat16), params,
                    _spec(one_chip, (e,), jnp.int32)).compile()


@pytest.mark.parametrize("seq", [1, 16, 128])
def test_selective_scan_compiles(one_chip, seq):
    """falcon_mamba_7b: a whole-d_inner block (8192 channels) overflows
    the 16 MiB scoped-VMEM limit; the kernel tiles channels."""
    cfg = get_config("falcon_mamba_7b")
    b, di, ds = 4, cfg.ssm.d_inner(cfg.d_model), cfg.ssm.d_state
    f32 = jnp.float32
    args = (_spec(one_chip, (b, seq, di), f32),
            _spec(one_chip, (b, seq, di), f32),
            _spec(one_chip, (b, seq, ds), f32),
            _spec(one_chip, (b, seq, ds), f32),
            _spec(one_chip, (di, ds), f32),
            _spec(one_chip, (b, di, ds), f32))
    jax.jit(lambda *x: selective_scan(*x, interpret=False)
            ).lower(*args).compile()


def test_decode_step_fits_one_chip(one_chip, monkeypatch):
    """stablelm_3b at published widths, the paged engine chip_smoke.py
    serves (4 slots x 2048 positions, block 128), widest decode forward:
    arguments + output + temporaries fit one chip's 16 GiB."""
    # the kernel ops pick the compiled kernel from the backend; this
    # process's backend is the CPU, the program is for the TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("stablelm_3b")
    slots, max_len, block, width = 4, 2048, 128, 16
    n_phys = slots * max_len // block + 1

    def place(tree):
        return jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype), tree)

    params = place(jax.eval_shape(functools.partial(init_model, cfg=cfg),
                                  jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(
        lambda: init_paged_cache(cfg, n_phys, block)))
    compiled = _decode_paged_fn.lower(
        params, cfg, _spec(one_chip, (slots, width), jnp.int32), cache,
        _spec(one_chip, (slots,), jnp.int32),
        _spec(one_chip, (slots, max_len // block), jnp.int32),
        use_kernel=True).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, (mem.argument_size_in_bytes,
                               mem.output_size_in_bytes,
                               mem.temp_size_in_bytes)


# the benchmark's paged engines: slots, decode width; 1024 positions in
# pages of 128
BENCH_ENGINES = {"stablelm_3b": (8, 3), "granite_moe_3b_a800m": (16, 2),
                 "sdar_30b_a3b": (32, 4)}


def _bench_config(arch):
    """The benchmark's program config: SDAR at its cut, 24 of 48 layers
    and one chip's 16 of 128 experts."""
    cfg = get_config(arch)
    if arch == "sdar_30b_a3b":
        cfg = cfg.replace(n_layers=24,
                          ffn=dataclasses.replace(cfg.ffn, held=16))
    return cfg


def _instructions(hlo: str):
    """name -> (opcode, dims, operand names) of every array-valued
    instruction in a compiled module, fused computations included."""
    out = {}
    for m in re.finditer(r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* "
                         r"([\w\-]+)\(([^)]*)", hlo, re.M):
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        out[m.group(1)] = (m.group(3), dims,
                           re.findall(r"%([\w.\-]+)", m.group(4)))
    return out


@pytest.mark.parametrize("arch", sorted(BENCH_ENGINES))
def test_paged_decode_keeps_pool_in_place(one_chip, monkeypatch, arch):
    """The benchmark's decode forward at published widths takes its pool
    in place: the output aliases the donated pool, and no copy,
    dynamic-slice or dynamic-update-slice moves a whole layer's pages
    (an in-place dynamic-update-slice moves only its update)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _bench_config(arch)
    (slots, width), max_len, block = BENCH_ENGINES[arch], 1024, 128
    n_phys = slots * max_len // block + 1
    params = jax.tree.map(
        lambda x: _spec(one_chip, x.shape, x.dtype),
        jax.eval_shape(functools.partial(init_model, cfg=cfg),
                       jax.random.PRNGKey(0)))
    cache = jax.tree.map(
        lambda x: _spec(one_chip, x.shape, x.dtype),
        jax.eval_shape(lambda: init_paged_cache(cfg, n_phys, block)))
    compiled = _decode_paged_fn.lower(
        params, cfg, _spec(one_chip, (slots, width), jnp.int32), cache,
        _spec(one_chip, (slots,), jnp.int32),
        _spec(one_chip, (slots, max_len // block), jnp.int32),
        use_kernel=True).compile()
    leaves = jax.tree.leaves(cache)
    pool_bytes = sum(x.size * x.dtype.itemsize for x in leaves)
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
    layer = leaves[0].size // leaves[0].shape[0]

    def pages(dims):
        return (math.prod(dims) >= layer
                and bool({n_phys, n_phys * block} & set(dims)))

    insts = _instructions(compiled.as_text())
    moved = [name for name, (op, dims, args) in insts.items()
             if (op in ("copy", "dynamic-slice") and pages(dims))
             or (op == "dynamic-update-slice"
                 and pages(insts.get(args[1], ("", ()))[1]))]
    assert moved == [], moved
