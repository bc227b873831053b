"""Plain float32 forward of the served architectures, one layer at a time.

Decoder-only transformer: token embedding; per layer RMSNorm ->
grouped-query attention (rotary on the whole head, causal softmax) ->
residual -> RMSNorm -> SwiGLU FFN, dense or top-k mixture of experts ->
residual; final RMSNorm; output head (tied to the embedding where the
configuration says so).  This is the block the program serves; where it
departs from a published model, the configuration file lists it.

Weights come from ``weights`` by path and layer, drawn in the dtype the
model is served in and then widened to float32, so the reference sees
the same numbers the program holds without taking any array from it.
Every product runs at ``Precision.HIGHEST``.  ``precision="fp8"`` rounds
each operand of each product to float8 e4m3 first: the control that a
lower precision than the configuration's has to fail.

``quantize``, ``mm``, ``rms``, ``rope``, ``swiglu`` and ``head_stats``
are the building blocks a configuration's own module
(``chipbench/configs/<config>.py``) may build another block from, with
its weights drawn by path through ``weights``.

Nothing here imports the program.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import weights

HI = jax.lax.Precision.HIGHEST
BF16 = jnp.bfloat16


def quantize(x, precision: str):
    """``x`` as an operand at ``precision``: unchanged for "f32",
    rounded to float8 e4m3 (and widened back) for "fp8"."""
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if precision != "f32":
        raise ValueError(f"unknown precision {precision!r}")
    return x


def mm(a, b, precision: str):
    """A product whose operands are first taken to ``precision``."""
    return jnp.matmul(quantize(a, precision), quantize(b, precision),
                      precision=HI)


def layer_shapes(arch: Dict) -> Dict[str, tuple]:
    """path (under ``segments/0/``) -> (per-layer shape, served dtype)."""
    d, h, kv, dh = (arch["d_model"], arch["n_heads"], arch["n_kv_heads"],
                    arch["head_dim"])
    out = {
        "ln1/scale": ((d,), BF16), "ln2/scale": ((d,), BF16),
        "attn/wq": ((d, h * dh), BF16), "attn/wk": ((d, kv * dh), BF16),
        "attn/wv": ((d, kv * dh), BF16), "attn/wo": ((h * dh, d), BF16),
    }
    f = arch["ffn"]
    if f["kind"] == "moe":
        e, ff = f["n_experts"], f["d_ff"]
        out.update({"ffn/router": ((d, e), jnp.float32),
                    "ffn/w_up": ((e, d, ff), BF16),
                    "ffn/w_gate": ((e, d, ff), BF16),
                    "ffn/w_down": ((e, ff, d), BF16)})
    else:
        ff = f["d_ff"]
        out.update({"ffn/up": ((d, ff), BF16), "ffn/gate": ((d, ff), BF16),
                    "ffn/down": ((ff, d), BF16)})
    return out


@functools.partial(jax.jit, static_argnames=("arch_key",))
def _layer_weights(key, layer, arch_key):
    arch = dict(arch_key)
    arch["ffn"] = dict(arch["ffn"])
    return {p: weights.layer_leaf(key, "segments/0/" + p, layer, s,
                                  dt).astype(jnp.float32)
            for p, (s, dt) in layer_shapes(arch).items()}


def _freeze(arch: Dict):
    return tuple(sorted((k, tuple(sorted(v.items())) if isinstance(v, dict)
                         else v) for k, v in arch.items()))


def rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, theta):
    """x: (n, L, heads, dh) at positions 0..L-1; rotates halves."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(w, x, arch, precision):
    n, length, _ = x.shape
    h, kv, dh = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    q = mm(x, w["attn/wq"], precision).reshape(n, length, h, dh)
    k = mm(x, w["attn/wk"], precision).reshape(n, length, kv, dh)
    v = mm(x, w["attn/wv"], precision).reshape(n, length, kv, dh)
    q, k = rope(q, arch["rope_theta"]), rope(k, arch["rope_theta"])
    q = q.reshape(n, length, kv, h // kv, dh)
    s = jnp.einsum("nqkgd,nskd->nkgqs", quantize(q, precision),
                   quantize(k, precision), precision=HI) / np.sqrt(dh)
    causal = jnp.tril(jnp.ones((length, length), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    ctx = jnp.einsum("nkgqs,nskd->nqkgd", quantize(p, precision),
                     quantize(v, precision),
                     precision=HI).reshape(n, length, h * dh)
    return mm(ctx, w["attn/wo"], precision)


def swiglu(x, gate, up, down, precision):
    """down(silu(x @ gate) * (x @ up))."""
    return mm(jax.nn.silu(mm(x, gate, precision)) * mm(x, up, precision),
               down, precision)


def _ffn(w, x, arch, precision):
    f = arch["ffn"]
    if f["kind"] != "moe":
        return swiglu(x, w["ffn/gate"], w["ffn/up"], w["ffn/down"],
                       precision)
    shape = x.shape
    t = x.reshape(-1, shape[-1])
    logits = mm(t, w["ffn/router"], precision)
    top, idx = jax.lax.top_k(logits, f["top_k"])
    gates = jax.nn.softmax(top, axis=-1)
    combine = jnp.zeros(logits.shape).at[
        jnp.arange(t.shape[0])[:, None], idx].set(gates)

    def expert(acc, inp):
        gate, up, down, c = inp
        return acc + c[:, None] * swiglu(t, gate, up, down, precision), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(t),
                          (w["ffn/w_gate"], w["ffn/w_up"], w["ffn/w_down"],
                           combine.T))
    return out.reshape(shape)


@functools.partial(jax.jit, static_argnames=("arch_key", "precision"))
def _layer(w, x, arch_key, precision):
    arch = dict(arch_key)
    arch["ffn"] = dict(arch["ffn"])
    eps = arch["norm_eps"]
    x = x + _attention(w, rms(x, w["ln1/scale"], eps), arch, precision)
    return x + _ffn(w, rms(x, w["ln2/scale"], eps), arch, precision)


def head_weight(arch: Dict, seed: int):
    """(d, vocab) float32 output head."""
    key = weights.base_key(seed)
    d, v = arch["d_model"], arch["vocab_size"]
    if arch["tie_embeddings"]:
        return weights.leaf(key, "embed/table", (v, d), BF16).astype(
            jnp.float32).T
    return weights.leaf(key, "lm_head/w", (d, v), BF16).astype(jnp.float32)


def final_hidden(arch: Dict, seed: int, seqs: Sequence[np.ndarray],
                 precision: str = "f32", rows: int = 4,
                 pad_to: int = 256) -> List[np.ndarray]:
    """Final-norm hidden states (L_i, d) of each token sequence."""
    key = weights.base_key(seed)
    ak = _freeze(arch)
    d = arch["d_model"]
    longest = max(len(s) for s in seqs)
    length = -(-longest // pad_to) * pad_to
    table = weights.leaf(key, "embed/table", (arch["vocab_size"], d), BF16)
    final = weights.leaf(key, "final_norm/scale", (d,), BF16).astype(
        jnp.float32)
    groups = []
    for start in range(0, len(seqs), rows):
        group = seqs[start:start + rows]
        toks = np.zeros((rows, length), np.int32)
        for i, s in enumerate(group):
            toks[i, :len(s)] = s
        groups.append(table[jnp.asarray(toks)].astype(jnp.float32))
    del table
    with jax.default_matmul_precision("highest"):
        for layer in range(arch["n_layers"]):
            w = _layer_weights(key, layer, ak)
            groups = [_layer(w, x, ak, precision) for x in groups]
            del w
    out = []
    for start, x in zip(range(0, len(seqs), rows), groups):
        x = np.asarray(rms(x, final, arch["norm_eps"]))
        for i, s in enumerate(seqs[start:start + rows]):
            out.append(x[i, :len(s)])
    return out


@functools.partial(jax.jit, static_argnames=("precision",))
def _head_stats(hidden, w, tokens, precision):
    """Per position: the best logit, the logit of ``tokens``, the argmax."""
    logits = mm(hidden, w, precision)
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return best, got, jnp.argmax(logits, axis=-1).astype(jnp.int32)


def head_stats(hidden: np.ndarray, w, tokens: np.ndarray,
               precision: str = "f32", chunk: int = 512):
    """(best, logit of tokens, argmax) at each row of ``hidden``."""
    outs = []
    with jax.default_matmul_precision("highest"):
        for s in range(0, len(hidden), chunk):
            h = np.zeros((chunk, hidden.shape[1]), np.float32)
            t = np.zeros((chunk,), np.int32)
            k = min(chunk, len(hidden) - s)
            h[:k], t[:k] = hidden[s:s + k], tokens[s:s + k]
            outs.append([np.asarray(a)[:k] for a in _head_stats(
                jnp.asarray(h), w, jnp.asarray(t), precision)])
    return tuple(np.concatenate([o[i] for o in outs]) for i in range(3))
