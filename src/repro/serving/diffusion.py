"""Diffusion-style block decoding — the DLLM side of the paper's
validation.  Two samplers: WeDLM-like (causal attention + masked
iterative refinement, any model) and SDAR's block diffusion (a model
whose attention is block-causal, ``AttentionSpec.diffusion_block``).

A block of N positions (N = the NFP budget) starts as [MASK] tokens and
is refined over ``refine_steps`` decode forwards; each iteration commits
the most confident still-masked positions.  Every refinement forward is
a multi-position decode forward of exactly N+1 positions, so the block
size is the parallelism knob the NFP budget governs (paper Sec. 6:
"diffusion-style block size").

KV-commit discipline: refinement forwards see MASK tokens at unresolved
positions, so their cache is POISON — a position resolved during (or
after) the final iteration would commit KV computed from a mask-token
input.  Both adapters therefore run one extra forward over the fully
resolved blocks and commit THAT cache, making the committed KV
byte-identical to prefilling the resolved tokens
(``tests/test_serving_modes.py::test_diffusion_committed_kv_matches_prefill``).

Under the common protocol, ``DiffusionSlotAdapter`` replaces the
single-forward greedy verification (``run_step``) with the iterative
refinement loop over every active request at once — each refinement
iteration is ONE shared multi-position forward whose width still fits
the NFP budget split across the active rows.

Block diffusion (``BlockDiffusionSlotAdapter``, SDAR): blocks of the
model's own length L are aligned to absolute positions; a masked
position's own logits predict it, with no pending token.  Prefill keeps
the prompt's whole blocks (``DecodeEngine.committed_len``); its last
``p mod L`` tokens are fixed positions of the first block.  Each block
is refined by shared width-L forwards under a static low-confidence
schedule, then one forward over the resolved block commits its KV.

Every refinement reads only each position's argmax and its probability,
computed on device (``token_choice``): the logits never leave it.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.serving.algorithm import SlotAdapter


@jax.jit
def token_choice(logits):
    """Each position's argmax (int32) and its softmax probability
    (float32), ON DEVICE: a refinement transfers these two small arrays,
    never the (..., vocab) logits."""
    lg = logits.astype(jnp.float32)
    top = jnp.max(lg, axis=-1, keepdims=True)
    conf = 1.0 / jnp.sum(jnp.exp(lg - top), axis=-1)
    return jnp.argmax(lg, axis=-1).astype(jnp.int32), conf


def refine_block(block: np.ndarray, resolved: np.ndarray, preds: np.ndarray,
                 conf: np.ndarray, per_iter: int) -> None:
    """One refinement update in place: freeze the ``per_iter`` most
    confident still-masked positions of ``block`` to their predictions
    (``preds``/``conf`` from ``token_choice``, entry i for block
    position i)."""
    n = len(block)
    cand = np.where(~resolved)[0]
    order = cand[np.argsort(-conf[:n][cand])]
    pick = order[:per_iter]
    block[pick] = preds[:n][pick]
    resolved[pick] = True


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

        preds = None
        for _ in range(self.refine_steps):
            if all(resolved[s].all() for s in slots):
                break
            logits, _, _ = loop.shared_forward(block_tokens(), budget)
            todo = [s for s in slots if not resolved[s].all()]
            with TraceAnnotation("repro.adapter.readback"):
                preds, conf = self.readback(*token_choice(logits))
            with TraceAnnotation("repro.adapter.accept"):
                for s in todo:
                    refine_block(blocks[s], resolved[s], preds[s], conf[s],
                                 max(1, int(np.ceil(n[s] / self.refine_steps))))
        for s in slots:
            if not resolved[s].all():
                blocks[s][~resolved[s]] = preds[s, :n[s]][~resolved[s]]
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


class BlockDiffusionSlotAdapter(SlotAdapter):
    """SDAR's block-diffusion sampler over the slotted paged path, for a
    model whose attention is block-causal with block length L
    (``AttentionSpec.diffusion_block``).

    A step generates one block per active row, in lock step: the block
    starts at the row's committed length (a multiple of L), its first
    positions are the tokens already known past it (the prompt's last
    ``p mod L`` on a row's first block), the rest MASK.  Every
    refinement is one shared width-L forward; each row then fixes
    ``ceil(masked / refine_steps)`` of its most confident still-masked
    positions, counted when its block starts (the static low-confidence
    schedule, temperature 0).  Rows done early ride along.  A last
    shared forward over the resolved blocks writes their clean KV and
    advances each row by L.  A position is masked by flag, never by
    comparing token ids: a prompt may hold the mask id.

    Each ``Request`` records, per generated position, the refinement
    step that fixed it (``fixed_at``).  The final block is refined
    whole, past ``max_tokens``; ``Request.tokens`` cuts it there.
    """

    mode = "diffusion"
    first_token = False

    def __init__(self, loop, refine_steps: int = 4,
                 mask_id: Optional[int] = None):
        super().__init__(loop)
        if refine_steps < 1:
            raise ValueError(f"refine_steps must be >= 1, "
                             f"got {refine_steps}")
        cfg = loop.engine.cfg
        self.block = cfg.attention.diffusion_block
        self.refine_steps = refine_steps
        self.mask_id = cfg.vocab_size - 1 if mask_id is None else mask_id

    def width(self, n_active: int, budget: int) -> int:
        # the trained block length, whatever the budget: not a knob
        return self.block

    def headroom(self) -> int:
        return self.block

    def run_step(self, slots: List[int], width: int, budget: int) -> None:
        loop, eng, L = self.loop, self.loop.engine, self.block
        block = np.zeros((eng.batch, L), np.int64)
        masked = np.zeros((eng.batch, L), bool)
        step_of = np.zeros((eng.batch, L), np.int64)
        known: Dict[int, int] = {}
        for s in slots:
            req = loop.active[s]
            tail = np.concatenate(
                [req.prompt, np.asarray(req.generated, np.int64)]
            )[int(eng.slot_lens_host[s]):]
            known[s] = len(tail)
            block[s, :known[s]] = tail
            block[s, known[s]:] = self.mask_id
            masked[s, known[s]:] = True
        quota = -(-masked.sum(1) // self.refine_steps)       # (batch,)
        step = 0
        while masked.any():
            with TraceAnnotation("repro.adapter.refine",
                                 rows=int(masked.any(1).sum()),
                                 masked=int(masked.sum())):
                logits, _, _ = loop.shared_forward(block, budget)
                preds, conf = self.readback(*token_choice(logits))
                order = np.argsort(np.where(masked, -conf, np.inf), axis=1,
                                   kind="stable")
                rank = np.argsort(order, axis=1)
                fix = masked & (rank < quota[:, None])
                block[fix] = preds[fix]
                step_of[fix] = step
                masked &= ~fix
            step += 1
        with TraceAnnotation("repro.adapter.commit_block"):
            _, cache, _ = loop.shared_forward(block, budget)
            advances = np.zeros((eng.batch,), np.int64)
            for s in slots:
                req = loop.active[s]
                req.generated.extend(int(t) for t in block[s, known[s]:])
                req.fixed_at.extend(int(t) for t in step_of[s, known[s]:])
                advances[s] = L
            eng.commit_slots(cache, advances)
