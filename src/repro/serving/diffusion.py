"""Diffusion-style block decoding (WeDLM-like: causal attention + masked
iterative refinement) — the DLLM side of the paper's validation.

A block of N positions (N = the NFP budget) starts as [MASK] tokens and
is refined over ``refine_steps`` decode forwards; each iteration commits
the most confident still-masked positions.  Every refinement forward is
a multi-position decode forward of exactly N+1 positions, so the block
size is the parallelism knob the NFP budget governs (paper Sec. 6:
"diffusion-style block size").

KV-commit discipline: refinement forwards see MASK tokens at unresolved
positions, so their cache is POISON — a position resolved during (or
after) the final iteration would commit KV computed from a mask-token
input.  Both drivers therefore run one extra forward over the fully
resolved block and commit THAT cache, making the committed KV
byte-identical to prefilling the resolved tokens
(``tests/test_serving_modes.py::test_diffusion_committed_kv_matches_prefill``).

Under the common protocol: ``propose`` emits the mask block and
``resolve`` replaces the single-forward greedy verification with the
iterative refinement loop; ``DiffusionSlotAdapter`` runs the same
refinement over MANY requests at once — each refinement iteration is
ONE shared multi-position forward whose width still fits the NFP budget
split across the active rows.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.serving.algorithm import ParallelDecodeAlgorithm, SlotAdapter
from repro.serving.engine import DecodeEngine

Array = jax.Array


def refine_block(block: np.ndarray, resolved: np.ndarray, lg: np.ndarray,
                 per_iter: int) -> None:
    """One refinement update in place: freeze the ``per_iter`` most
    confident still-masked positions of ``block`` given the float32
    logits ``lg`` ((>=n+1, vocab); row i predicts block position i)."""
    n = len(block)
    probs = np.exp(lg - lg.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    conf = probs.max(-1)[:n]
    preds = probs.argmax(-1)[:n]
    cand = np.where(~resolved)[0]
    order = cand[np.argsort(-conf[cand])]
    pick = order[:per_iter]
    block[pick] = preds[pick]
    resolved[pick] = True


@dataclass
class DiffusionBlockDecoder(ParallelDecodeAlgorithm):
    engine: DecodeEngine
    block_size: Optional[int] = None     # None -> NFP budget
    refine_steps: int = 4
    mask_id: Optional[int] = None        # None -> vocab_size - 1

    def __post_init__(self):
        super().__post_init__()
        if self.refine_steps < 1:
            raise ValueError(f"refine_steps must be >= 1, "
                             f"got {self.refine_steps}")

    def _block(self) -> int:
        if self.block_size is not None:
            return self.block_size
        return max(1, self.engine.nfp_budget() - 1)

    parallel_width = _block

    def _mask_id(self) -> int:
        if self.mask_id is not None:
            return self.mask_id
        return self.engine.cfg.vocab_size - 1

    def propose(self, context: np.ndarray, pending: int,
                n: int) -> np.ndarray:
        return np.full((n,), self._mask_id(), np.int64)

    def resolve(self, pending: int, drafts: np.ndarray
                ) -> Tuple[List[int], int]:
        """Iterative refinement: each forward re-predicts the block and
        the most confident still-masked positions freeze.  A FINAL
        forward over the fully-resolved block produces the cache that
        commits — the refinement forwards' caches hold KV computed from
        mask-token inputs and must never reach the engine."""
        n = len(drafts)
        block = np.asarray(drafts, np.int64).copy()
        resolved = np.zeros((n,), bool)
        per_iter = max(1, int(np.ceil(n / self.refine_steps)))
        step_logits = None
        for _ in range(self.refine_steps):
            if resolved.all():
                break
            step_logits, _, _ = self.forward_block(
                np.concatenate([[pending], block]))
            refine_block(block, resolved,
                         np.asarray(step_logits[0].astype(jnp.float32)),
                         per_iter)
        if not resolved.all():
            block[~resolved] = np.asarray(
                jnp.argmax(step_logits[0], axis=-1))[:n][~resolved]
        # commit forward: KV for [pending] + block[:-1] computed from the
        # RESOLVED tokens (byte-identical to prefilling them)
        _, new_cache, _ = self.forward_block(
            np.concatenate([[pending], block]))
        self.engine.commit(new_cache, n)
        return list(block[:-1]), int(block[-1])


class DiffusionSlotAdapter(SlotAdapter):
    """Scheduler-side diffusion block refinement: every active request
    refines its own block, but each refinement iteration is ONE shared
    multi-position forward over all rows — the scheduler's NFP budget
    split covers ``n_active * (block + 1)`` positions per forward, so
    the block size shrinks as concurrency grows (the DLLM counterpart of
    the speculative width split).  Rows that resolve early simply ride
    along untouched until the slowest row finishes, and the final commit
    forward (fully-resolved blocks, see module docstring) is shared too.
    """

    mode = "diffusion"

    def __init__(self, loop, block_size: Optional[int] = None,
                 refine_steps: int = 4, mask_id: Optional[int] = None):
        super().__init__(loop)
        if refine_steps < 1:
            raise ValueError(f"refine_steps must be >= 1, "
                             f"got {refine_steps}")
        self.block_size = block_size
        self.refine_steps = refine_steps
        self.mask_id = mask_id

    def _mask_id(self) -> int:
        if self.mask_id is not None:
            return self.mask_id
        return self.loop.engine.cfg.vocab_size - 1

    def width(self, n_active: int, budget: int) -> int:
        if self.block_size is not None:
            n = self.block_size
        else:
            # each refinement forward carries (block + 1) positions/row
            n = max(1, budget // max(n_active, 1) - 1)
        return min(n, self.loop.max_width)

    def headroom(self) -> int:
        return self.loop.max_width

    def run_step(self, slots: List[int], width: int, budget: int) -> None:
        loop = self.loop
        eng = loop.engine
        mask_id = self._mask_id()
        # per-row block sizes, clipped to each request's remaining tokens
        n: Dict[int, int] = {}
        blocks: Dict[int, np.ndarray] = {}
        resolved: Dict[int, np.ndarray] = {}
        for s in slots:
            req = loop.active[s]
            n[s] = max(1, min(width, req.max_tokens - len(req.generated)))
            blocks[s] = np.full((n[s],), mask_id, np.int64)
            resolved[s] = np.zeros((n[s],), bool)
        w = max(n.values())

        def block_tokens() -> np.ndarray:
            tokens = np.zeros((eng.batch, w + 1), np.int64)
            for s in slots:
                tokens[s, 0] = loop.active[s].pending
                tokens[s, 1:1 + n[s]] = blocks[s]
            return tokens

        last_lg: Dict[int, np.ndarray] = {}
        for _ in range(self.refine_steps):
            if all(resolved[s].all() for s in slots):
                break
            logits, _, _ = loop.shared_forward(block_tokens(), budget)
            todo = [s for s in slots if not resolved[s].all()]
            with TraceAnnotation("repro.adapter.readback"):
                for s in todo:
                    last_lg[s] = np.asarray(logits[s].astype(jnp.float32))
            with TraceAnnotation("repro.adapter.accept"):
                for s in todo:
                    refine_block(blocks[s], resolved[s], last_lg[s], max(
                        1, int(np.ceil(n[s] / self.refine_steps))))
        for s in slots:
            if not resolved[s].all():
                blocks[s][~resolved[s]] = (
                    last_lg[s].argmax(-1)[:n[s]][~resolved[s]])
        # shared commit forward over the fully-resolved blocks — the only
        # cache that reaches the engine
        _, new_cache, _ = loop.shared_forward(block_tokens(), budget)
        advances = np.zeros((eng.batch,), np.int32)
        for s in slots:
            req = loop.active[s]
            req.generated.extend(int(t) for t in blocks[s])
            advances[s] = n[s]                   # pending + block[:-1]
            req.pending = int(blocks[s][-1])
        eng.commit_slots(new_cache, advances)
