"""Multi-token prediction (MTP) driver — the third parallel-decoding
family the paper abstracts (Sec. 7.1; Gloeckle et al. 2024, DeepSeek-V3).

A bank of ``n_heads`` lightweight prediction heads (one linear head per
future offset, trained against shifted targets) proposes the next
``n_heads`` tokens from the LAST hidden state; the base model then
verifies them with ONE multi-position decode forward — identical system
structure to speculative decoding (the inherited propose -> verify ->
commit drive of ``SlotAdapter``), but the draft is a model component
rather than a separate model, so the NFP budget directly caps the useful
number of MTP heads (paper Sec. 6: "MTP prediction length").

The proposal input is the REAL final-norm hidden state threaded out of
``models.transformer.forward``: a slot's prefill hands over its last
prompt position's state, and every verify forward hands over the state
at the accepted index whose logits produced the new pending token —
exactly the state the heads were trained against (``mtp_loss``).

Greedy acceptance keeps output identical to AR greedy decoding.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import _init
from repro.serving.algorithm import SlotAdapter

Array = jax.Array


def init_mtp_heads(key, d_model: int, vocab: int, n_heads: int,
                   dtype=jnp.bfloat16) -> Dict:
    ks = jax.random.split(key, n_heads)
    return {"heads": jnp.stack([_init(k, (d_model, vocab), scale=0.02,
                                      dtype=dtype) for k in ks])}


@jax.jit
def mtp_propose(heads: Dict, hidden: Array) -> Array:
    """hidden: (b, d) last-position hidden state -> (b, n_heads) greedy
    proposals for offsets +2..+n_heads+1.

    Jitted: the head-bank einsum AND its argmax run as one device
    dispatch, so callers transfer only the (b, n_heads) i32 proposals —
    never the (b, n_heads, vocab) head logits."""
    logits = jnp.einsum("bd,hdv->bhv", hidden.astype(jnp.float32),
                        heads["heads"].astype(jnp.float32))
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def mtp_loss(heads: Dict, hidden: Array, tokens: Array) -> Array:
    """Train the head bank: head h predicts token at offset h+2.
    hidden: (b, s, d); tokens: (b, s)."""
    n_heads = heads["heads"].shape[0]
    total = jnp.zeros((), jnp.float32)
    for h in range(n_heads):
        off = h + 2
        if tokens.shape[1] <= off:
            break
        hs = hidden[:, :-off]
        tgt = tokens[:, off:]
        logits = (hs.astype(jnp.float32)
                  @ heads["heads"][h].astype(jnp.float32))
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
        total = total + jnp.mean(lse - gold)
    return total / n_heads


class MTPSlotAdapter(SlotAdapter):
    """Scheduler-side MTP: each request proposes from ITS row's last
    verify-forward hidden state (tracked on the Request across steps),
    the head bank caps the useful width, and the remaining NFP budget is
    split evenly across rows.  Greedy acceptance per row keeps every
    stream lossless."""

    mode = "mtp"

    def __init__(self, loop, heads: Dict):
        super().__init__(loop)
        if heads is None:
            raise ValueError("mtp serving mode needs an mtp_heads bank")
        self.heads = heads

    def width(self, n_active: int, budget: int) -> int:
        bank = self.heads["heads"].shape[0]
        w = max(1, budget // max(n_active, 1))
        return min(w, self.loop.max_width, bank + 1)

    def headroom(self) -> int:
        return self.loop.max_width

    def begin(self, req, hidden) -> None:
        req.hidden = hidden

    def propose(self, req, n: int) -> np.ndarray:
        return np.asarray(  # analysis: allow-host-sync — (1, heads) i32
            mtp_propose(self.heads, req.hidden[None]))[0][:n].astype(np.int64)

    def propose_rows(self, want):
        # ONE head-bank dispatch over every row's hidden state — the
        # per-row default would pay n_active device round-trips per step.
        # The transfer is the (rows, heads) i32 proposal block only.
        rows = sorted(want)
        hid = jnp.stack([self.loop.active[s].hidden for s in rows])
        props = np.asarray(  # analysis: allow-host-sync
            mtp_propose(self.heads, hid)).astype(np.int64)
        return {s: props[i][:want[s]] for i, s in enumerate(rows)}

    def observe(self, req, k: int, hidden) -> None:
        req.hidden = hidden[k]
