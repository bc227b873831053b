"""Multi-position decode engine.

The engine executes the paper's abstraction directly: a decode forward
that processes N positions (Eq. 2) over a pre-allocated cache.  One
compiled executable serves every step at a given N (cache_len is a traced
scalar), matching the bucketed-compile discipline of TPU serving stacks.

The NFP budget (core.parallelism_budget) tells algorithm drivers
(speculative verification, diffusion block decode) how many positions are
near-free for the current arch x hardware x batch x context.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.arch import LAYER_ATTN, ArchConfig
from repro.core.granularity import GranularitySpec
from repro.core.hardware import HardwareSpec, get_hardware
from repro.core.nfp import parallelism_budget
from repro.models.transformer import (forward, init_cache, init_paged_cache,
                                      make_segments)
from repro.serving.paged import BlockManager, PagedKVConfig

Array = jax.Array


@functools.partial(jax.jit, static_argnames=("cfg", "use_kernel"))
def _prefill_fn(params, cfg: ArchConfig, tokens, cache, use_kernel=False):
    logits, cache, _, hidden = forward(params, cfg, {"tokens": tokens},
                                       mode="prefill", cache=cache,
                                       cache_len=0, use_kernel=use_kernel)
    return logits, cache, hidden


@functools.partial(jax.jit, static_argnames=("cfg", "use_kernel"))
def _decode_fn(params, cfg: ArchConfig, tokens, cache, cache_len,
               use_kernel=False):
    logits, cache, _, hidden = forward(params, cfg, {"tokens": tokens},
                                       mode="decode", cache=cache,
                                       cache_len=cache_len,
                                       use_kernel=use_kernel)
    return logits, cache, hidden


@functools.partial(jax.jit, static_argnames=("cfg", "use_kernel"))
def _decode_paged_fn(params, cfg: ArchConfig, tokens, cache, slot_lens,
                     block_tables, use_kernel=False):
    logits, cache, _, hidden = forward(params, cfg, {"tokens": tokens},
                                       mode="decode", cache=cache,
                                       cache_len=slot_lens,
                                       use_kernel=use_kernel,
                                       block_tables=block_tables)
    return logits, cache, hidden


@jax.jit
def greedy_tokens(logits):
    """Greedy token selection ON DEVICE.  Verify loops call this and
    transfer only the small (b, n) int32 result to the host — pulling
    the raw (b, n, vocab) logits across per step is the kind of
    hot-path transfer ``repro.analysis`` exists to flag."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


@jax.jit
def _copy_pool_blocks(cache, src, dst):
    """Copy pool blocks src -> dst across every layer (the COW device
    op).  Pool leaves are (layers, n_phys, block, ...): index axis 1."""
    return jax.tree.map(lambda pool: pool.at[:, dst].set(pool[:, src]), cache)


@jax.jit
def _scatter_prefill(cache, scratch, flat_idx, rows, cols):
    """Move freshly prefilled KV from the dense scratch cache into pool
    pages: scratch[(row, col)] -> pool_flat[flat_idx], per layer.
    Padding entries target the trash page (duplicate-index writes there
    are harmless)."""
    def one(pool, scr):
        n_phys, bs = pool.shape[1], pool.shape[2]
        flat = pool.reshape((pool.shape[0], n_phys * bs) + pool.shape[3:])
        flat = flat.at[:, flat_idx].set(scr[:, rows, cols])
        return flat.reshape(pool.shape)
    return jax.tree.map(one, cache, scratch)


def _prefill_span(entry: Dict) -> TraceAnnotation:
    """Host span around one admission forward, carrying the counts its
    ``prefill_log`` entry keeps: prompt tokens computed, and the tokens
    the forward runs (every row at the bucket width)."""
    return TraceAnnotation("repro.engine.prefill",
                           computed_tokens=entry["computed_tokens"],
                           padded_tokens=entry["padded_tokens"],
                           bucket=entry["bucket"])


@dataclass
class DecodeEngine:
    """``paged=PagedKVConfig(...)`` switches the slotted serving mode
    onto the paged KV cache: ``cache`` becomes a global refcounted block
    pool (``init_paged_cache``) shared by all slots through the
    ``BlockManager``'s per-slot block tables, and admissions whose
    prompt prefix is already resident skip prefill for the shared
    blocks.  Paged mode serves attention-only archs via the slotted API
    (``prefill_slots``/``decode_slots``/``commit_slots``); the
    single-request scalar-``cache_len`` drivers stay dense."""

    cfg: ArchConfig
    params: Dict
    batch: int
    max_len: int
    # None: the attached chip's spec on a TPU, the v5e target elsewhere
    hardware: Optional[HardwareSpec] = None
    use_kernel: bool = False
    cache: Optional[Dict] = None
    # committed positions of the single-request drivers.  A HOST int on
    # purpose: every step's budget/width decision reads it, and a device
    # scalar here cost one blocking device->host sync per decode step
    # (it is re-uploaded as a traced scalar by the jitted forwards, which
    # is cheap and non-blocking in the other direction).
    cache_len: int = 0
    paged: Optional[PagedKVConfig] = None

    def __post_init__(self):
        if self.hardware is None:
            self.hardware = get_hardware()
        self.manager: Optional[BlockManager] = None
        if self.paged is not None:
            if self.cfg.encoder is not None or any(
                    kind != LAYER_ATTN for kind, _ in make_segments(self.cfg)):
                raise ValueError(
                    "paged KV cache requires an attention-only decoder "
                    f"(no SSM/hybrid segments, no encoder); got {self.cfg.name}")
            bs = self.paged.block_size
            n_blocks = (self.paged.n_blocks if self.paged.n_blocks
                        else self.batch * (self.max_len // max(bs, 1)))
            self.manager = BlockManager(self.batch, self.max_len, bs,
                                        n_blocks, self.paged.prefix_cache)
            if self.cache is None:
                self.cache = init_paged_cache(self.cfg, self.manager.n_phys,
                                              bs)
        elif self.cache is None:
            self.cache = init_cache(self.cfg, self.batch, self.max_len)
        self.gran = GranularitySpec.for_backend(
            self.cfg.ffn.n_experts,
            head_dim=(self.cfg.attention.head_dim if self.cfg.attention
                      else 128),
            kv_page=(self.paged.block_size if self.paged else 0))
        # per-slot cache lengths for the scheduler's slotted mode; the
        # single-request drivers keep using the scalar ``cache_len``.
        # ``slot_lens`` rides the jitted decode forwards (per-row ragged
        # lengths), ``slot_lens_host`` is its host-side mirror: every
        # update comes from host values (prompt lengths, accepted
        # counts), so the scheduler's budget/admission math never has to
        # block on a device read mid-decode.
        self.slot_lens = jnp.zeros((self.batch,), jnp.int32)
        self.slot_lens_host = np.zeros((self.batch,), np.int64)
        self._bt_device: Optional[Array] = None
        # (b, d) final-norm hidden of the last prefilled position (MTP
        # proposals read it); one entry per bucketed prefill forward
        self.last_hidden: Optional[Array] = None
        self.prefill_log: List[Dict] = []
        self.preempted_slots = 0               # preempt_slot() evictions

    def _require_dense(self, what: str) -> None:
        if self.manager is not None:
            raise RuntimeError(
                f"{what} drives the aligned dense cache; a paged engine "
                "serves through prefill_slots/decode_slots/commit_slots")

    def _device_tables(self) -> Array:
        """Device copy of the block tables, cached between admissions —
        tables only change at admit/COW/release, so re-uploading every
        decode step would be pure repeated host->device traffic."""
        if self._bt_device is None:
            self._bt_device = jnp.asarray(self.manager.device_tables())
        return self._bt_device

    # ------------------------------------------------------------------
    def nfp_budget(self, eps: float = 0.2, routing: str = "balanced",
                   ell: Optional[int] = None) -> int:
        """Near-free position budget for the CURRENT state (Sec. 6).

        Pure host math: ``cache_len`` is the host-side committed length,
        so a per-step budget query costs no device synchronization."""
        if ell is None:
            ell = self.cache_len
        ell = max(int(ell), 1)
        return parallelism_budget(self.cfg, self.hardware, self.gran,
                                  self.batch, ell, eps, routing)

    # ------------------------------------------------------------------
    def prefill(self, tokens: Array) -> Array:
        """tokens: (b, prompt_len).  Returns last-position logits.

        ``self.last_hidden`` holds the (b, d) final-norm hidden state of
        the last prompt position — the state auxiliary head banks (MTP)
        propose from."""
        self._require_dense("prefill")
        logits, self.cache, hidden = _prefill_fn(self.params, self.cfg,
                                                 tokens, self.cache,
                                                 self.use_kernel)
        self.cache_len = int(tokens.shape[1])
        self.last_hidden = hidden[:, -1]
        return logits[:, -1]

    def decode_step(self, tokens: Array, advance: Optional[int] = None
                    ) -> Array:
        """One multi-position decode forward over N = tokens.shape[1]
        positions.  ``advance`` = how many of the N positions to commit to
        the cache (speculative decoding commits only accepted tokens);
        default commits all N."""
        self._require_dense("decode_step")
        logits, new_cache, _ = _decode_fn(self.params, self.cfg, tokens,
                                          self.cache, self.cache_len,
                                          self.use_kernel)
        n = tokens.shape[1]
        adv = n if advance is None else advance
        if adv > 0:
            self.cache = new_cache
            self.cache_len = self.cache_len + int(adv)
        return logits

    def peek_step(self, tokens: Array) -> Tuple[Array, Dict, Array]:
        """Decode forward WITHOUT committing (verification forwards).
        Returns (logits, new_cache, hidden)."""
        self._require_dense("peek_step")
        return _decode_fn(self.params, self.cfg, tokens, self.cache,
                          self.cache_len, self.use_kernel)

    def commit(self, new_cache: Dict, n_accepted) -> None:
        self._require_dense("commit")
        self.cache = new_cache
        self.cache_len = self.cache_len + int(n_accepted)

    # ------------------------------------------------------------------
    # Slotted multi-request mode (repro.serving.scheduler).  Each batch
    # row is an independent cache slot at its own sequence length; the
    # scheduler multiplexes requests over slots and the NFP budget over
    # the per-forward positions.
    # ------------------------------------------------------------------
    def _row_mask(self, rows, like: Array) -> Array:
        m = jnp.zeros((self.batch,), bool).at[jnp.asarray(rows)].set(True)
        return m.reshape((1, self.batch) + (1,) * (like.ndim - 2))

    def _set_slot_len(self, slot: int, value: int) -> None:
        """Update one slot's committed length on device AND in the host
        mirror — ``value`` is always host-known (a prompt length or a
        cached-prefix length), so the mirror costs nothing."""
        self.slot_lens = self.slot_lens.at[slot].set(value)
        self.slot_lens_host[slot] = int(value)

    def prefill_bucket(self, p: int) -> int:
        """Power-of-two prompt-length bucket (floor 8, ceiling max_len):
        bucketed prefill compiles once per BUCKET, not once per distinct
        prompt length."""
        b = 8
        while b < p:
            b *= 2
        return min(b, self.max_len)

    def _needs_exact_prefill(self) -> bool:
        """SSM / hybrid segments carry a recurrent state that would
        absorb the bucket's tail padding — those archs prefill at exact
        prompt lengths (still batched across equal-length prompts)."""
        return any(kind != LAYER_ATTN for kind, _ in make_segments(self.cfg))

    def prefill_slots(self, prompts: Dict[int, Array],
                      reserve: Optional[Dict[int, int]] = None
                      ) -> Dict[int, Tuple[Array, Array]]:
        """Bucketed multi-slot batched prefill: fill MANY cache slots in
        one forward.  ``prompts``: {slot: (p,) tokens}.

        Prompts are right-padded to a shared power-of-two length bucket
        (masked by causality: pad positions sit AFTER each prompt, so no
        prompt position attends to them; their junk KV lands beyond
        ``slot_lens`` where the decode mask never reads it before the
        next forward overwrites it).  One compile per bucket replaces the
        per-admission recompile storm of prefilling each distinct prompt
        length separately — and one forward admits the whole group.

        On a PAGED engine, ``reserve`` caps each slot's block-table
        reservation to {slot: prompt + max_tokens + headroom} positions
        (default: the full ``max_len``), and admissions whose prompt
        prefix is prefix-cache resident skip the prefill compute for the
        shared blocks — only the divergent suffix runs, as a per-row
        offset decode-shape forward (see ``_prefill_slots_paged``).

        Returns {slot: (last-prompt-position logits, hidden)}.
        """
        lens = {s: int(jnp.shape(p)[0]) for s, p in prompts.items()}
        for s, p in lens.items():
            if p < 1:
                raise ValueError(f"slot {s}: empty prompt")
            if p > self.max_len:
                raise ValueError(
                    f"slot {s}: prompt of {p} tokens exceeds the engine's "
                    f"max_len={self.max_len}; it cannot be prefilled "
                    "(admission should have rejected it)")
        if self.manager is not None:
            return self._prefill_slots_paged(prompts, lens, reserve or {})
        groups: List[Tuple[int, List[int]]]
        if self._needs_exact_prefill():
            by_len: Dict[int, List[int]] = {}
            for s, p in lens.items():
                by_len.setdefault(p, []).append(s)
            groups = [(p, rows) for p, rows in sorted(by_len.items())]
        else:
            groups = [(self.prefill_bucket(max(lens.values())),
                       list(prompts))]
        out: Dict[int, Tuple[Array, Array]] = {}
        for width, rows in groups:
            entry = {"slots": sorted(rows), "bucket": width,
                     "computed_tokens": sum(lens[s] for s in rows),
                     "padded_tokens": self.batch * width}
            with _prefill_span(entry):
                toks = np.zeros((self.batch, width), np.int32)
                for s in rows:
                    toks[s, :lens[s]] = np.asarray(prompts[s], np.int64)
                logits, new_cache, hidden = _prefill_fn(
                    self.params, self.cfg, jnp.asarray(toks), self.cache,
                    self.use_kernel)
                self.cache = jax.tree.map(
                    lambda old, new: jnp.where(self._row_mask(rows, old),
                                               new, old),
                    self.cache, new_cache)
                for s in rows:
                    self._set_slot_len(s, lens[s])
                    out[s] = (logits[s, lens[s] - 1], hidden[s, lens[s] - 1])
            self.prefill_log.append(entry)
        return out

    def _prefill_slots_paged(self, prompts: Dict[int, Array],
                             lens: Dict[int, int],
                             reserve: Dict[int, int]
                             ) -> Dict[int, Tuple[Array, Array]]:
        """Paged admission + prefill.

        Per slot: the BlockManager attaches prefix-cache-resident blocks
        (read-only, refcounted), performs the divergence-block
        copy-on-write when the reuse boundary falls inside a shared
        block, and eagerly allocates the rest of the reservation.  Then:

          - NO-HIT slots run the normal bucketed prefill against a dense
            SCRATCH cache sized to the bucket, and the fresh KV is
            scattered into their pool pages — the forward itself is
            byte-identical to the dense engine's.
          - HIT slots skip the shared prefix entirely: only the
            divergent suffix runs, as ONE shared decode-shape forward at
            per-row offsets (= each slot's cached length), writing
            straight into the pool.  This is where prefix caching turns
            into saved prefill compute.

        Full prompt blocks register in the prefix cache AFTERWARD (their
        KV is resident by then), so later admissions can hit them.
        """
        mgr = self.manager
        tok_host = {s: np.asarray(prompts[s], np.int64).ravel()
                    for s in prompts}
        plans = {}
        for s in sorted(prompts):
            r = min(int(reserve.get(s, self.max_len)), self.max_len)
            plans[s] = mgr.admit(s, tok_host[s].tolist(),
                                 max(r, lens[s]))
        self._bt_device = None                 # tables changed
        cows = [c for s in sorted(prompts) for c in plans[s].cow_copies]
        if cows:
            self.cache = _copy_pool_blocks(
                self.cache, jnp.asarray([c[0] for c in cows], jnp.int32),
                jnp.asarray([c[1] for c in cows], jnp.int32))
        full = sorted(s for s in prompts if plans[s].cached_len == 0)
        hits = sorted(s for s in prompts if plans[s].cached_len > 0)
        out: Dict[int, Tuple[Array, Array]] = {}
        bs = mgr.block_size
        if full:
            width = self.prefill_bucket(max(lens[s] for s in full))
            entry = {"slots": full, "bucket": width, "cached_tokens": 0,
                     "computed_tokens": sum(lens[s] for s in full),
                     "padded_tokens": self.batch * width}
            with _prefill_span(entry):
                toks = np.zeros((self.batch, width), np.int32)
                for s in full:
                    toks[s, :lens[s]] = tok_host[s]
                scratch = init_cache(self.cfg, self.batch, width)
                logits, scratch, hidden = _prefill_fn(
                    self.params, self.cfg, jnp.asarray(toks), scratch,
                    self.use_kernel)
                rows, cols, flats = [], [], []
                for s in full:
                    pos = np.arange(lens[s])
                    page = mgr.tables[s, pos // bs].astype(np.int64)
                    rows.append(np.full(lens[s], s, np.int64))
                    cols.append(pos)
                    flats.append(page * bs + pos % bs)
                rows = np.concatenate(rows)
                cols = np.concatenate(cols)
                flats = np.concatenate(flats)
                # pad the scatter to a power-of-two bucket (compile reuse);
                # pad entries dump into the trash page
                m = 8
                while m < len(rows):
                    m *= 2
                pad = m - len(rows)
                rows = np.pad(rows, (0, pad))
                cols = np.pad(cols, (0, pad))
                flats = np.pad(flats, (0, pad),
                               constant_values=mgr.trash * bs)
                self.cache = _scatter_prefill(
                    self.cache, scratch, jnp.asarray(flats, jnp.int32),
                    jnp.asarray(rows, jnp.int32),
                    jnp.asarray(cols, jnp.int32))
                for s in full:
                    self._set_slot_len(s, lens[s])
                    out[s] = (logits[s, lens[s] - 1], hidden[s, lens[s] - 1])
            self.prefill_log.append(entry)
        if hits:
            suf = {s: lens[s] - plans[s].cached_len for s in hits}
            width = self.prefill_bucket(max(suf.values()))
            entry = {"slots": hits, "bucket": width,
                     "cached_tokens": sum(plans[s].cached_len for s in hits),
                     "computed_tokens": sum(suf.values()),
                     "padded_tokens": self.batch * width}
            with _prefill_span(entry):
                for s in hits:
                    self._set_slot_len(s, plans[s].cached_len)
                toks = np.zeros((self.batch, width), np.int32)
                for s in hits:
                    toks[s, :suf[s]] = tok_host[s][plans[s].cached_len:]
                logits, new_cache, hidden = _decode_paged_fn(
                    self.params, self.cfg, jnp.asarray(toks), self.cache,
                    self.slot_lens, self._device_tables(), self.use_kernel)
                # suffix KV is committed; rows outside the hit group wrote
                # past their own committed length (or into the trash
                # page), which no mask ever reads back
                self.cache = new_cache
                for s in hits:
                    self._set_slot_len(s, lens[s])
                    out[s] = (logits[s, suf[s] - 1], hidden[s, suf[s] - 1])
            self.prefill_log.append(entry)
        for s in sorted(prompts):
            mgr.register_prompt(s, tok_host[s].tolist())
        return out

    def prefill_slot(self, slot: int, prompt: Array) -> Array:
        """Prefill ONE cache slot; thin wrapper over ``prefill_slots``."""
        (logits, _hidden) = self.prefill_slots({slot: prompt})[slot]
        return logits

    def decode_slots(self, tokens: Array) -> Tuple[Array, Dict, Array]:
        """Multi-position decode forward over ALL slots at their own
        cache lengths, WITHOUT committing.  tokens: (batch, n).
        Returns (logits, new_cache, hidden).

        With ``use_kernel=True`` the per-slot lengths ride the ragged
        Pallas decode-attention kernel's scalar-prefetch lane — one
        quantized launch for the whole mixed-length batch (on a paged
        engine, with the block tables as a second prefetch operand)."""
        with TraceAnnotation("repro.engine.decode"):
            if self.manager is not None:
                return _decode_paged_fn(self.params, self.cfg, tokens,
                                        self.cache, self.slot_lens,
                                        self._device_tables(),
                                        self.use_kernel)
            return _decode_fn(self.params, self.cfg, tokens, self.cache,
                              self.slot_lens, self.use_kernel)

    def commit_slots(self, new_cache: Dict, advances) -> None:
        """Commit per-slot: rows with advance > 0 take the new cache and
        bump their length; rows with 0 are untouched (inactive slots or
        fully-rejected blocks).  The row mask is built from the advances
        ON DEVICE — materializing it on the host would force a device
        sync every scheduler step.  ``advances`` must be HOST values
        (the adapters' accept counts always are): they also feed the
        ``slot_lens_host`` mirror the scheduler budgets against.

        A paged engine adopts the new pool wholesale: the forward's
        writes only ever touch pages the writing slot exclusively owns
        (COW guarantees refcount-1 at write time) or the trash page, and
        rows that advanced 0 only wrote past their committed length —
        positions every mask skips until a later forward overwrites
        them.  Per-row selection would therefore change nothing."""
        with TraceAnnotation("repro.engine.commit"):
            adv_host = np.asarray(advances, np.int64)
            adv = jnp.asarray(adv_host, jnp.int32)
            self.slot_lens_host = self.slot_lens_host + adv_host
            if self.manager is not None:
                self.cache = new_cache
                self.slot_lens = self.slot_lens + adv
                return
            keep = adv > 0                           # (batch,) on device
            self.cache = jax.tree.map(
                lambda old, new: jnp.where(
                    keep.reshape((1, self.batch) + (1,) * (old.ndim - 2)),
                    new, old),
                self.cache, new_cache)
            self.slot_lens = self.slot_lens + adv

    def release_slot(self, slot: int) -> None:
        if self.manager is not None:
            self.manager.release(slot)
            self._bt_device = None             # tables changed
        self._set_slot_len(slot, 0)

    def preempt_slot(self, slot: int) -> None:
        """Evict a slot mid-stream (scheduler preemption): its paged
        blocks return to the pool — except prefix-cache-resident ones,
        which stay hit-able so the recompute-on-resume prefill can skip
        them — and the row's committed length zeroes.  The evicted KV is
        recomputed at re-admission from the request's host-side context,
        so no device state needs saving."""
        if self.manager is not None:
            self.manager.preempt(slot)
            self._bt_device = None             # tables changed
        self.preempted_slots += 1
        self._set_slot_len(slot, 0)

    # ------------------------------------------------------------------
    def greedy_generate(self, prompt: Array, steps: int) -> Array:
        """Plain autoregressive baseline (N=1 per forward)."""
        logits = self.prefill(prompt)
        last = jnp.argmax(logits, axis=-1)[:, None]
        out = [last]
        for _ in range(steps - 1):
            logits = self.decode_step(last)
            last = jnp.argmax(logits[:, -1], axis=-1)[:, None]
            out.append(last)
        return jnp.concatenate(out, axis=1)
