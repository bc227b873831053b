"""Operations and bytes, computed from shapes: the paged decode-attention
kernel's work under its tile-skip rule, and a model's FLOPs per token.

The skip rule is the one ``repro.kernels.decode_attention`` documents
for its paged launch (grid ``b x kv_heads x q_tiles x kv_tiles``, one
kv tile per pool page): for row ``b`` and query tile ``iq`` the kv tiles
``0 .. min(n_kv_tiles, ceil((len_b + min(n, (iq+1)*q_block)) / page))-1``
execute, and the rest revisit the last one, whose copy the pipeline
elides.  Every executed tile reads one K and one V page; each
``(b, kv_head, iq)`` reads its query tile once and writes its output
tile once.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Sequence

BF16 = 2
Q_BLOCK = 64          # the kernel's query tile (fixed policy)


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


@dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def seconds(self, peak_flops: float, peak_bytes_per_s: float) -> float:
        """The least time the chip could take: the larger bound."""
        return max(self.flops / peak_flops, self.bytes / peak_bytes_per_s)


def executed_kv_tiles(n: int, lens: Sequence[int], page: int,
                      n_kv_tiles: int, q_block: int = Q_BLOCK) -> int:
    """kv tiles one kv head executes over all rows and query tiles."""
    n_q_tiles = _cdiv(n, q_block)
    total = 0
    for length in lens:
        for iq in range(n_q_tiles):
            hi = int(length) + min(n, (iq + 1) * q_block)
            total += max(1, min(n_kv_tiles, _cdiv(hi, page)))
    return total


def paged_attention(n: int, lens: Sequence[int], page: int, n_kv_tiles: int,
                    n_heads: int, n_kv_heads: int, head_dim: int,
                    q_block: int = Q_BLOCK) -> Work:
    """One launch (one layer) of the paged decode-attention kernel at
    query width ``n`` over rows at committed lengths ``lens``."""
    g = n_heads // n_kv_heads
    rows = g * q_block
    tiles = n_kv_heads * executed_kv_tiles(n, lens, page, n_kv_tiles, q_block)
    flops = tiles * 4.0 * rows * page * head_dim      # QK^T and PV
    kv_bytes = tiles * 2.0 * page * head_dim * BF16   # one K and one V page
    q_tiles = len(lens) * n_kv_heads * _cdiv(n, q_block)
    qo_bytes = q_tiles * 2.0 * rows * head_dim * BF16
    return Work(flops, kv_bytes + qo_bytes)


def matmul_params_per_token(arch: Dict) -> float:
    """Weights one token multiplies through: attention projections, the
    active experts (or the dense FFN), the router and the output head.
    The embedding lookup is a gather, not a product."""
    d = arch["d_model"]
    h, kv, dh = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    attn = d * h * dh * 2 + d * kv * dh * 2          # q, o and k, v
    ffn = arch["ffn"]
    if ffn["kind"] == "moe":
        mlp = ffn["top_k"] * 3 * d * ffn["d_ff"] + d * ffn["n_experts"]
    else:
        mlp = 3 * d * ffn["d_ff"]
    return arch["n_layers"] * (attn + mlp) + d * arch["vocab_size"]


def model_flops(arch: Dict, contexts: Sequence[int]) -> float:
    """FLOPs a model needs for tokens at the given context lengths: two
    per matmul weight, plus QK^T and PV over each token's context."""
    per_token = 2.0 * matmul_params_per_token(arch)
    attn = 4.0 * arch["n_layers"] * arch["n_heads"] * arch["head_dim"]
    return per_token * len(contexts) + attn * float(sum(contexts))
