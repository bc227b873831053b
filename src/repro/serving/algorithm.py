"""Common protocol for parallel-decoding algorithms.

Every family the paper abstracts — speculative verification, MTP head
verification, diffusion block refinement — is the same system-level
loop: PROPOSE a block of candidate positions, VERIFY it with one (or a
few) multi-position decode forwards (Eq. 2), COMMIT the accepted prefix
to the KV cache.  The NFP budget caps the block width in every case
(paper Sec. 6), so the driver machinery is algorithm-independent and
lives here once, at BOTH serving granularities:

  ``ParallelDecodeAlgorithm``  the batch=1 driver: one request owns the
                               whole engine (and the whole budget).
  ``SlotAdapter``              the scheduler-side adapter: the same
                               propose → verify → commit protocol driven
                               ROW-WISE by ``ServingLoop`` — every active
                               request fills its slot's row of ONE shared
                               multi-position forward per step, and the
                               NFP budget is split across the rows.

A new algorithm implements ``propose`` (and optionally ``resolve`` /
``run_step`` when verification is not single-forward greedy acceptance,
e.g. diffusion refinement) and inherits the rest; see
``speculative.py`` / ``mtp.py`` / ``diffusion.py`` for the paired
instantiations.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.serving.engine import DecodeEngine, greedy_tokens

__all__ = ["DecodeStats", "ParallelDecodeAlgorithm", "SlotAdapter"]


@dataclass
class DecodeStats:
    """Position/forward accounting — the quantities NFP normalizes
    (paper Sec. J.2.3)."""

    tokens: int = 0
    forwards: int = 0
    positions: int = 0

    @property
    def tokens_per_forward(self) -> float:
        return self.tokens / max(self.forwards, 1)

    @property
    def position_utilization(self) -> float:
        return self.tokens / max(self.positions, 1)

    def as_dict(self) -> Dict:
        return {
            "tokens": self.tokens,
            "forwards": self.forwards,
            "positions": self.positions,
            "tokens_per_forward": self.tokens_per_forward,
            "position_utilization": self.position_utilization,
        }


@dataclass
class ParallelDecodeAlgorithm:
    """Propose -> verify -> commit driver over one DecodeEngine.

    Subclass protocol:
      parallel_width()        block width for the next step; the default
                              spends the engine's NFP budget (reserving
                              one position for the pending token).
      propose(ctx, pending, n) length-n candidate block (np.int64).
      resolve(pending, drafts) verify + commit; returns (committed
                              tokens — now in the cache after
                              ``pending`` — and the next pending token).
                              Default: one multi-position forward with
                              greedy prefix acceptance, which keeps the
                              output stream identical to AR greedy.
      begin(prompt, pending)  optional hook after target prefill
                              (draft-model setup and the like).
      observe(hidden, k)      optional hook: the verify forward's
                              final-norm hidden states (1, n, d) plus
                              the accepted index k whose logits produced
                              the next pending token (MTP proposes from
                              hidden[0, k]).
    """

    engine: DecodeEngine

    def __post_init__(self):
        self.stats = DecodeStats()

    # ------------------------------------------------------------------
    # protocol (overridable)
    # ------------------------------------------------------------------
    def parallel_width(self) -> int:
        return max(1, self.engine.nfp_budget() - 1)

    def begin(self, prompt: np.ndarray, pending: int) -> None:
        pass

    def observe(self, hidden, k: int) -> None:
        pass

    def propose(self, context: np.ndarray, pending: int,
                n: int) -> np.ndarray:
        raise NotImplementedError

    def resolve(self, pending: int, drafts: np.ndarray
                ) -> Tuple[List[int], int]:
        """Greedy verification: accept the longest draft prefix the
        target model reproduces, plus the model's own next token."""
        block = np.concatenate([[pending], drafts]).astype(np.int64)
        logits, new_cache, hidden = self.forward_block(block)
        # argmax runs jitted on device; only the (n,) i32 winners cross
        # to the host (the accept loop below is inherently host-side)
        preds = np.asarray(greedy_tokens(logits[0]))  # analysis: allow-host-sync
        k = 0
        while k < len(drafts) and preds[k] == drafts[k]:
            k += 1
        self.engine.commit(new_cache, 1 + k)
        self.observe(hidden, k)
        return list(drafts[:k]), int(preds[k])

    # ------------------------------------------------------------------
    # shared machinery
    # ------------------------------------------------------------------
    def forward_block(self, block: np.ndarray):
        """One multi-position decode forward over ``block`` WITHOUT
        committing; tracks forward/position stats.  Returns
        (logits, new_cache, hidden)."""
        eng = self.engine
        toks = jnp.broadcast_to(jnp.asarray(block[None], jnp.int32),
                                (eng.batch, len(block)))
        logits, new_cache, hidden = eng.peek_step(toks)
        self.stats.forwards += 1
        self.stats.positions += len(block)
        return logits, new_cache, hidden

    def generate(self, prompt, max_tokens: int
                 ) -> Tuple[np.ndarray, Dict]:
        """Greedy generation (batch=1 driver).  Returns (tokens, stats)."""
        eng = self.engine
        self.stats = DecodeStats()
        logits = eng.prefill(prompt)
        pending = int(jnp.argmax(logits[0]))
        context = np.asarray(prompt[0]).astype(np.int64)
        generated: List[int] = [pending]
        self.begin(np.asarray(prompt), pending)
        while len(generated) < max_tokens:
            n = min(self.parallel_width(), max_tokens - len(generated))
            drafts = self.propose(context, pending, n)
            committed, next_pending = self.resolve(pending, drafts)
            context = np.concatenate(
                [context, [pending], committed]).astype(np.int64)
            generated.extend(list(committed) + [next_pending])
            pending = next_pending
        self.stats.tokens = len(generated)
        return np.asarray(generated[:max_tokens]), self.stats.as_dict()


class SlotAdapter:
    """Scheduler-side propose → verify → commit adapter.

    ``ServingLoop`` owns admission, slots, telemetry, and retirement;
    the adapter owns what happens INSIDE one scheduler step.  The base
    class is the greedy/speculative shape — every active request's
    pending token (plus optional per-row drafts from ``propose``) rides
    ONE shared multi-position forward, and each row greedily accepts its
    longest reproduced draft prefix, which keeps every stream
    byte-identical to solo greedy decoding.

    Subclass protocol:
      width(n_active, budget)  per-request block width for this step —
                               how the adapter splits the NFP budget
                               across rows (ASPD-style).
      headroom()               cache positions a slot needs beyond
                               prompt + max_tokens (admission check).
      begin(req, hidden)       after the request's slot is prefilled;
                               ``hidden`` is the (d,) final-norm state
                               of its last prompt position.
      propose(req, n)          length-<=n draft block for one row.
      observe(req, k, hidden)  after acceptance: k = accepted index,
                               ``hidden`` the row's (n, d) verify-forward
                               hidden states.
      run_step(slots, width, budget)
                               the whole verify/commit drive; override
                               when verification needs several shared
                               forwards (diffusion refinement).
    """

    mode = "greedy"

    def __init__(self, loop):
        self.loop = loop

    # -- protocol ------------------------------------------------------
    def width(self, n_active: int, budget: int) -> int:
        return 1

    def headroom(self) -> int:
        return 0

    def begin(self, req, hidden) -> None:
        pass

    def propose(self, req, n: int) -> np.ndarray:
        return np.zeros((0,), np.int64)

    def propose_rows(self, want: Dict[int, int]) -> Dict[int, np.ndarray]:
        """Draft blocks for many rows at once: {slot: n} -> {slot:
        drafts}.  Default defers to per-row ``propose``; adapters whose
        proposal is itself a device computation (the MTP head bank)
        override this to run ONE batched dispatch for all rows instead
        of one dispatch + host sync per row per step."""
        return {s: self.propose(self.loop.active[s], n)
                for s, n in want.items()}

    def observe(self, req, k: int, hidden) -> None:
        pass

    # -- default drive: propose / ONE shared forward / greedy accept ---
    def run_step(self, slots: List[int], width: int, budget: int) -> None:
        loop = self.loop
        eng = loop.engine
        tokens = np.zeros((eng.batch, width), np.int64)
        want: Dict[int, int] = {}
        for s in slots:
            req = loop.active[s]
            tokens[s, 0] = req.pending
            # clip each row's drafts to its remaining tokens — budget
            # positions past a request's max_tokens would be discarded
            n_draft = min(width - 1,
                          req.max_tokens - len(req.generated) - 1)
            if n_draft > 0:
                want[s] = n_draft
        drafts: Dict[int, np.ndarray] = {}
        with TraceAnnotation("repro.adapter.draft"):
            for s, d in (self.propose_rows(want) if want else {}).items():
                d = np.asarray(d, np.int64)[:want[s]]
                if len(d):
                    drafts[s] = d
                    tokens[s, 1:1 + len(d)] = d
        logits, new_cache, hidden = loop.shared_forward(tokens, budget)
        # greedy winners computed ON DEVICE (jitted); the only per-step
        # device->host transfer is this (batch, width) i32 block — the
        # token stream emission every serving loop fundamentally needs
        with TraceAnnotation("repro.adapter.readback"):
            preds = np.asarray(greedy_tokens(logits))  # analysis: allow-host-sync
        advances = np.zeros((eng.batch,), np.int32)
        with TraceAnnotation("repro.adapter.accept"):
            for s in slots:
                req = loop.active[s]
                k = 0
                d = drafts.get(s)
                if d is not None:
                    while k < len(d) and preds[s, k] == d[k]:
                        k += 1
                    req.generated.extend(int(t) for t in d[:k])
                bonus = int(preds[s, k])
                req.generated.append(bonus)
                advances[s] = 1 + k              # pending + accepted drafts
                req.pending = bonus
                self.observe(req, k, hidden[s])
        eng.commit_slots(new_cache, advances)
