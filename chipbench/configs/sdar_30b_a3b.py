"""SDAR-30B-A3B-Chat: how the harness reads, builds, checks and counts it.

The block is Qwen3-MoE's, from which SDAR is converted: token embedding;
per layer RMSNorm -> grouped-query attention with a per-head RMSNorm of
q and k over ``head_dim`` before rotary -> residual -> RMSNorm -> top-k
mixture of SwiGLU experts -> residual; final RMSNorm; untied output
head.  Attention is block-causal: a position sees every position below
the end of its own block of ``block_length`` (blocks aligned to absolute
positions).  The experts are one chip's share of an expert-parallel
deployment (the file's ``deployment``): the router spans every expert,
its top-k weights are normalized over the k picks, and only the held
experts' part of the layer is computed, here as in the program.

``gaps`` scores each served token at the refinement step that fixed it:
the gap between the reference's best logit and the logit of the served
token at that position, given its block as it stood then (positions
fixed at earlier steps carry their tokens, the rest MASK, the prompt's
tail fixed) and every block before it clean.  One reference forward per
request covers all of them: the clean blocks, then each block's step
states, each state seeing itself and the clean blocks before it.

Weights are drawn by path through ``weights``, layer by layer, in the
dtype the program holds them, widened to float32; every product runs at
``Precision.HIGHEST`` (``reference.mm``), and ``precision="fp8"`` is the
float8 control.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import counts
import reference
import weights

BF16 = jnp.bfloat16
PAD_TO = 128


# ---------------------------------------------------------------------------
# reading and building
# ---------------------------------------------------------------------------
def arch_of(config: Dict) -> Dict:
    """The architecture numbers of the file: ``ffn.n_experts`` is the
    router's published width, ``ffn.held`` the experts held here."""
    d = config["hidden_size"]
    return {
        "n_layers": config["num_hidden_layers"], "d_model": d,
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"], "vocab_size": config["vocab_size"],
        "norm_eps": config["rms_norm_eps"],
        "rope_theta": float(config["rope_theta"]),
        "tie_embeddings": bool(config["tie_word_embeddings"]),
        "block": config["diffusion"]["block_length"],
        "mask_id": config["vocab_size"] - 1,
        "ffn": {"kind": "moe", "d_ff": config["moe_intermediate_size"],
                "n_experts": config["published"]["num_experts"],
                "held": config["num_experts"],
                "first_held": config["deployment"]["first_held_expert"],
                "top_k": config["num_experts_per_tok"]},
    }


def program_config(config: Dict):
    """The program's ArchConfig: the preset with every number from the
    file."""
    from repro.configs import get_config
    a = arch_of(config)
    f = a["ffn"]
    base = get_config(config["arch_id"])
    return base.replace(
        n_layers=a["n_layers"], d_model=a["d_model"],
        vocab_size=a["vocab_size"], norm_eps=a["norm_eps"],
        rope_theta=a["rope_theta"], tie_embeddings=a["tie_embeddings"],
        attention=dataclasses.replace(
            base.attention, n_heads=a["n_heads"], n_kv_heads=a["n_kv_heads"],
            head_dim=a["head_dim"], qk_norm_eps=a["norm_eps"],
            diffusion_block=a["block"]),
        ffn=dataclasses.replace(
            base.ffn, d_ff=f["d_ff"], n_experts=f["n_experts"],
            top_k=f["top_k"], held=f["held"], first_held=f["first_held"]))


def model_flops(arch: Dict, contexts: Sequence[int]) -> float:
    """FLOPs of tokens at these context lengths on this chip: a token's
    top-k experts fall on the held ones in proportion (top_k x held /
    n_experts), the router is the full width."""
    f = arch["ffn"]
    share = dict(f, top_k=f["top_k"] * f["held"] / f["n_experts"])
    return counts.model_flops(dict(arch, ffn=share), contexts)


# ---------------------------------------------------------------------------
# the float32 reference
# ---------------------------------------------------------------------------
def layer_shapes(arch: Dict) -> Dict[str, tuple]:
    """path (under ``segments/0/``) -> (per-layer shape, served dtype)."""
    d, h, kv, dh = (arch["d_model"], arch["n_heads"], arch["n_kv_heads"],
                    arch["head_dim"])
    f = arch["ffn"]
    e, ff = f["held"], f["d_ff"]
    return {
        "ln1/scale": ((d,), BF16), "ln2/scale": ((d,), BF16),
        "attn/wq": ((d, h * dh), BF16), "attn/wk": ((d, kv * dh), BF16),
        "attn/wv": ((d, kv * dh), BF16), "attn/wo": ((h * dh, d), BF16),
        "attn/q_norm/scale": ((dh,), BF16),
        "attn/k_norm/scale": ((dh,), BF16),
        "ffn/router": ((d, f["n_experts"]), jnp.float32),
        "ffn/w_up": ((e, d, ff), BF16), "ffn/w_gate": ((e, d, ff), BF16),
        "ffn/w_down": ((e, ff, d), BF16),
    }


def _freeze(arch: Dict):
    return tuple(sorted((k, tuple(sorted(v.items())) if isinstance(v, dict)
                         else v) for k, v in arch.items()))


def _thaw(arch_key) -> Dict:
    arch = dict(arch_key)
    arch["ffn"] = dict(arch["ffn"])
    return arch


@functools.partial(jax.jit, static_argnames=("arch_key",))
def _layer_weights(key, layer, arch_key):
    return {p: weights.layer_leaf(key, "segments/0/" + p, layer, s,
                                  dt).astype(jnp.float32)
            for p, (s, dt) in layer_shapes(_thaw(arch_key)).items()}


def _rope(x, pos, theta):
    """x: (n, S, heads, dh) at positions ``pos`` (n, S); rotates halves."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(w, x, pos, visible, arch, precision):
    n, length, _ = x.shape
    h, kv, dh = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    eps = arch["norm_eps"]
    q = reference.mm(x, w["attn/wq"], precision).reshape(n, length, h, dh)
    k = reference.mm(x, w["attn/wk"], precision).reshape(n, length, kv, dh)
    v = reference.mm(x, w["attn/wv"], precision).reshape(n, length, kv, dh)
    q = _rope(reference.rms(q, w["attn/q_norm/scale"], eps), pos,
              arch["rope_theta"])
    k = _rope(reference.rms(k, w["attn/k_norm/scale"], eps), pos,
              arch["rope_theta"])
    q = q.reshape(n, length, kv, h // kv, dh)
    s = jnp.einsum("nqkgd,nskd->nkgqs", reference.quantize(q, precision),
                   reference.quantize(k, precision),
                   precision=reference.HI) / np.sqrt(dh)
    p = jax.nn.softmax(jnp.where(visible[:, None, None], s, -jnp.inf),
                       axis=-1)
    ctx = jnp.einsum("nkgqs,nskd->nqkgd", reference.quantize(p, precision),
                     reference.quantize(v, precision),
                     precision=reference.HI).reshape(n, length, h * dh)
    return reference.mm(ctx, w["attn/wo"], precision)


def _experts(w, x, arch, precision):
    """The held experts' part of the layer: top-k over the router's full
    width, weights normalized over the k, absent experts left out."""
    f = arch["ffn"]
    shape = x.shape
    t = x.reshape(-1, shape[-1])
    logits = reference.mm(t, w["ffn/router"], precision)
    top, idx = jax.lax.top_k(logits, f["top_k"])
    gates = jax.nn.softmax(top, axis=-1)
    local = idx - f["first_held"]
    here = (local >= 0) & (local < f["held"])
    combine = jnp.zeros((t.shape[0], f["held"])).at[
        jnp.arange(t.shape[0])[:, None], jnp.clip(local, 0, f["held"] - 1)
    ].add(jnp.where(here, gates, 0.0))

    def expert(acc, inp):
        gate, up, down, c = inp
        return acc + c[:, None] * reference.swiglu(t, gate, up, down,
                                                    precision), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(t),
                          (w["ffn/w_gate"], w["ffn/w_up"], w["ffn/w_down"],
                           combine.T))
    return out.reshape(shape)


@functools.partial(jax.jit, static_argnames=("arch_key", "precision"))
def _layer(w, x, pos, visible, arch_key, precision):
    arch = _thaw(arch_key)
    eps = arch["norm_eps"]
    x = x + _attention(w, reference.rms(x, w["ln1/scale"], eps), pos,
                       visible, arch, precision)
    return x + _experts(w, reference.rms(x, w["ln2/scale"], eps), arch,
                        precision)


def final_hidden(arch: Dict, seed: int, tokens: np.ndarray, pos: np.ndarray,
                 visible: np.ndarray, precision: str = "f32",
                 rows: int = 1) -> np.ndarray:
    """Final-norm hidden states (n, S, d) of token rows ``tokens`` (n, S)
    at positions ``pos`` (n, S), position q attending to k where
    ``visible[n, q, k]``."""
    key = weights.base_key(seed)
    ak = _freeze(arch)
    d = arch["d_model"]
    table = weights.leaf(key, "embed/table", (arch["vocab_size"], d), BF16)
    final = weights.leaf(key, "final_norm/scale", (d,), BF16).astype(
        jnp.float32)
    groups = [(table[jnp.asarray(tokens[i:i + rows], jnp.int32)].astype(
        jnp.float32), jnp.asarray(pos[i:i + rows], jnp.int32),
        jnp.asarray(visible[i:i + rows]))
        for i in range(0, len(tokens), rows)]
    del table
    with jax.default_matmul_precision("highest"):
        for layer in range(arch["n_layers"]):
            w = _layer_weights(key, layer, ak)
            groups = [(_layer(w, x, p, v, ak, precision), p, v)
                      for x, p, v in groups]
            del w
    return np.concatenate([np.asarray(reference.rms(x, final,
                                                    arch["norm_eps"]))
                           for x, _, _ in groups])


def block_causal(length: int, block: int) -> np.ndarray:
    """(length, length) visibility of a plain sequence under block-causal
    attention."""
    blk = np.arange(length) // block
    return blk[None, :] <= blk[:, None]


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------
def unroll(arch: Dict, prompt: np.ndarray, n_served: int, generated,
           fixed_at):
    """One request's reference input: the clean blocks before its last
    served block, then each block's state at each refinement step.

    Returns (tokens, pos, visible, rows), ``rows[j]`` the unrolled row
    whose logits scored served token j."""
    L, mask = arch["block"], arch["mask_id"]
    p = len(prompt)
    seq = np.concatenate([prompt, np.asarray(generated, np.int64)])
    first, last = p // L, (p + n_served - 1) // L
    clean = last * L
    tokens, pos = [seq[:clean]], [np.arange(clean)]
    owner = [np.arange(clean) // L]          # block each row's mask keys on
    state = [np.full(clean, -1)]             # -1: clean
    rows = np.zeros(n_served, np.int64)
    at = clean
    fixed_at = np.asarray(fixed_at, np.int64)
    for b in range(first, last + 1):
        span = np.arange(b * L, (b + 1) * L)
        gen = span - p                       # < 0: the prompt's tail
        steps = fixed_at[gen[gen >= 0]]
        for t in range(int(steps.max()) + 1):
            toks = np.where(gen < 0, seq[np.minimum(span, len(seq) - 1)],
                            mask)
            done = (gen >= 0) & (fixed_at[np.maximum(gen, 0)] < t)
            toks[done] = seq[span[done]]
            tokens.append(toks)
            pos.append(span)
            owner.append(np.full(L, b))
            state.append(np.full(L, at))
            mine = (gen >= 0) & (gen < n_served) & (
                fixed_at[np.maximum(gen, 0)] == t)
            rows[gen[mine]] = at + np.flatnonzero(mine)
            at += L
    tokens, pos = np.concatenate(tokens), np.concatenate(pos)
    owner, state = np.concatenate(owner), np.concatenate(state)
    clean_key = state == -1
    # a clean row sees the clean rows of its block and before; a state
    # row sees the clean rows of the blocks before its own, and itself
    visible = np.where(
        (state == -1)[:, None],
        clean_key[None, :] & (owner[None, :] <= owner[:, None]),
        (clean_key[None, :] & (owner[None, :] < owner[:, None]))
        | (state[None, :] == state[:, None]))
    return tokens, pos, visible, rows


def _stack(unrolled):
    """Pad each request's (tokens, pos, visible) to one length: a padding
    row sees only itself."""
    length = -(-max(len(u[0]) for u in unrolled) // PAD_TO) * PAD_TO
    n = len(unrolled)
    tokens = np.zeros((n, length), np.int64)
    pos = np.zeros((n, length), np.int64)
    visible = np.broadcast_to(np.eye(length, dtype=bool),
                              (n, length, length)).copy()
    for i, (t, p, v, _) in enumerate(unrolled):
        tokens[i, :len(t)], pos[i, :len(p)] = t, p
        visible[i, :len(t), :len(t)] = v
    return tokens, pos, visible


def head_weight(arch: Dict, seed: int):
    return reference.head_weight(arch, seed)


def gaps(arch: Dict, seed: int, finished, control: bool = False) -> Dict:
    """Widest and mean gap of the served tokens, each at the state it was
    fixed in; with ``control`` also of the float8 control's own first
    choices at the same positions."""
    unrolled, targets = [], []
    for prompt, tokens, req in finished:
        unrolled.append(unroll(arch, np.asarray(prompt, np.int64),
                               len(tokens), req.generated, req.fixed_at))
        targets.append(np.asarray(tokens, np.int32))
    tokens, pos, visible = _stack(unrolled)
    targets = np.concatenate(targets)

    def scored(precision):
        hidden = final_hidden(arch, seed, tokens, pos, visible, precision)
        return np.concatenate([hidden[i, u[3]]
                               for i, u in enumerate(unrolled)])

    w = head_weight(arch, seed)
    ref = scored("f32")
    best, got, _ = reference.head_stats(ref, w, targets)
    out = {"max_logit_gap": float(np.max(best - got)),
           "mean_logit_gap": float(np.mean(best - got)),
           "positions": int(len(targets))}
    if control:
        _, _, pick = reference.head_stats(scored("fp8"), w, targets, "fp8")
        _, got_c, _ = reference.head_stats(ref, w, pick)
        out["control"] = {"max_logit_gap": float(np.max(best - got_c)),
                          "mean_logit_gap": float(np.mean(best - got_c))}
    return out


def logits_of(arch: Dict, seed: int, seqs: List[np.ndarray]) -> List:
    """Float32 logits of plain sequences under the full block-causal
    forward (the reference the program's serving path is held to)."""
    unrolled = [(s, np.arange(len(s)), block_causal(len(s), arch["block"]),
                 None) for s in seqs]
    tokens, pos, visible = _stack(unrolled)
    hidden = final_hidden(arch, seed, tokens, pos, visible)
    w = np.asarray(head_weight(arch, seed))
    with jax.default_matmul_precision("highest"):
        return [np.asarray(jnp.matmul(hidden[i, :len(s)], w,
                                      precision=reference.HI))
                for i, s in enumerate(seqs)]
