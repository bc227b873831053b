"""Budget-aware continuous-batching scheduler over one DecodeEngine.

The paper's Sec. 6 reads N_max(eps) as a deployment knob: how many
decode positions one forward can carry near-free.  A single-request
driver spends that budget on ONE request's verification length / block
size; the scheduler spends it across MANY concurrent requests — the
"system-side parallelism selection" the NFP principle enables:

  - each request owns a SLOT (one batch row) of the engine's
    pre-allocated cache, at its own sequence length (per-slot
    ``cache_len`` threading through the decode forward),
  - admission keeps the active set small enough that every request gets
    at least one position inside the budget; the rest queue.  Newly
    admitted requests are prefilled TOGETHER: prompts are padded to a
    power-of-two length bucket and all new slots fill in one forward
    (one XLA compile per bucket instead of one per distinct prompt
    length — see ``DecodeEngine.prefill_slots``),
  - every scheduler step the ALGORITHM ADAPTER drives one (or, for
    diffusion refinement, a few) batched multi-position forwards whose
    total positions (active slots x per-request width) never exceed
    N_max(eps).

All four parallel-decoding families run through the same ``SlotAdapter``
propose → verify → commit protocol (``serving.algorithm``):

  greedy       1 position per request per forward (lossless, minimal
               latency variance),
  speculative  per-request n-gram verification windows sized so the
               whole forward stays inside the budget (ASPD-style
               adaptive splitting; lossless),
  mtp          per-request head-bank proposals from each row's real
               last hidden state, one shared verify forward (lossless),
  diffusion    per-request mask-block refinement where every refinement
               iteration is one shared forward and a final shared
               forward commits clean KV (matches the solo driver's
               token stream per request).

Greedy/speculative/mtp streams are identical to running each request
alone through ``DecodeEngine.greedy_generate``; diffusion streams are
identical to the solo ``DiffusionBlockDecoder`` at the same block size.

Load-pressure policies (``repro.loadgen`` drives them under traced
traffic):

  admission control   ``submit`` applies per-loop backpressure (bounded
                      waiting queue -> ``AdmissionRejected``), and
                      ``admit`` drains the queue in SLO-class priority
                      order rather than raw FIFO (FIFO within a class).
  preemption          ``preempt(slot)`` evicts an active request's KV
                      (paged blocks return to the pool) and requeues it;
                      re-admission RECOMPUTES the evicted KV by
                      prefilling ``req.context`` — the already-emitted
                      stream and the pending token are host state, so a
                      preempted request resumes byte-identically to a
                      never-preempted run (tests/test_loadgen.py
                      goldens).  With ``AdmissionConfig.preemption`` a
                      higher-priority arrival preempts the
                      lowest-priority active victim when the pool or
                      slot supply blocks its admission.

Admission is ARRIVAL-driven, not step-driven: ``step`` only decodes
(the hot path ``repro.analysis`` walks), while ``run`` and the trace
harness call ``admit`` at the arrival boundary — where prompt upload
and first-token readback are inherent, one batched transfer per
admission group.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.kernels.decode_attention.ops import slack_report
from repro.serving.algorithm import SlotAdapter
from repro.serving.diffusion import (BlockDiffusionSlotAdapter,
                                     DiffusionSlotAdapter)
from repro.serving.engine import DecodeEngine, greedy_tokens
from repro.serving.mtp import MTPSlotAdapter
from repro.serving.speculative import SpeculativeSlotAdapter

__all__ = ["AdmissionConfig", "AdmissionRejected", "Request", "SLOClass",
           "ServingLoop", "DEFAULT_SLO_CLASSES"]

Array = jax.Array


@dataclass(frozen=True)
class SLOClass:
    """One multi-tenant service class: admission priority plus the
    latency targets ``repro.loadgen.stats`` scores goodput against."""

    name: str
    priority: int = 0                  # higher admits first, preempts lower
    ttft_target_s: float = float("inf")
    itl_target_s: float = float("inf")


#: interactive beats default beats batch; targets are TPU-scale virtual
#: seconds (the load harness measures against the simulated clock)
DEFAULT_SLO_CLASSES: Dict[str, SLOClass] = {
    "interactive": SLOClass("interactive", priority=10,
                            ttft_target_s=0.5, itl_target_s=0.05),
    "default": SLOClass("default", priority=0,
                        ttft_target_s=2.0, itl_target_s=0.2),
    "batch": SLOClass("batch", priority=-10),
}


class AdmissionRejected(RuntimeError):
    """Backpressure: the waiting queue is at ``max_waiting`` capacity."""


@dataclass(frozen=True)
class AdmissionConfig:
    """Admission-control policy knobs (all default to the legacy
    behavior: unbounded FIFO queue, no preemption, one class).

    ``max_waiting``  bounds the waiting queue; ``submit`` beyond it
                     raises ``AdmissionRejected`` (backpressure —
                     callers shed load instead of growing an unbounded
                     queue whose tail can never meet its SLO).
    ``preemption``   lets ``admit`` evict the lowest-priority active
                     request when a STRICTLY higher-priority arrival
                     cannot get a slot or enough KV blocks.  A victim
                     re-enters the queue at its own (lower) priority,
                     so it can never preempt back: no thrash cycles.
    ``classes``      the SLO-class registry ``submit`` validates
                     against (None -> ``DEFAULT_SLO_CLASSES``).
    """

    max_waiting: Optional[int] = None
    preemption: bool = False
    classes: Optional[Dict[str, SLOClass]] = None

    def slo(self, name: str) -> SLOClass:
        table = self.classes if self.classes is not None \
            else DEFAULT_SLO_CLASSES
        return table[name]


@dataclass
class Request:
    """One generation request and its runtime state."""

    rid: int
    prompt: np.ndarray                     # (p,) int64
    max_tokens: int
    generated: List[int] = field(default_factory=list)
    pending: Optional[int] = None          # next token to feed (emitted,
    slot: Optional[int] = None             #   not yet in the cache)
    hidden: Optional[Array] = None         # (d,) state MTP proposes from
    done: bool = False
    slo_class: str = "default"
    preemptions: int = 0                   # times evicted + requeued
    t_submit: float = 0.0                  # time.perf_counter() at submit
    t_admit: Optional[float] = None        #   and at the first admission
    forwards: int = 0                      # decode forwards its row was in
    # block diffusion: per generated position, the refinement step of
    # its block that fixed it
    fixed_at: List[int] = field(default_factory=list)

    @property
    def context(self) -> np.ndarray:
        """Tokens whose KV is committed in the request's cache slot: all
        but the pending token (block diffusion has none pending)."""
        n_cached = len(self.generated) - (self.pending is not None)
        return np.concatenate(
            [self.prompt, self.generated[:n_cached]]).astype(np.int64)

    def tokens(self) -> np.ndarray:
        return np.asarray(self.generated[:self.max_tokens], np.int64)


class ServingLoop:
    """Multiplex concurrent requests through one shared DecodeEngine.

    ``mode`` selects the per-slot algorithm adapter (see module
    docstring); a custom ``SlotAdapter`` subclass instance can be
    plugged in directly via ``adapter=`` (it receives this loop).
    ``mtp_heads`` feeds the mtp adapter; ``block_size`` /
    ``refine_steps`` / ``mask_id`` feed the diffusion adapter, which is
    SDAR's block-diffusion sampler for a model with block-causal
    attention (``AttentionSpec.diffusion_block``, then the only block
    size) and the WeDLM-like one otherwise.

    ``controller`` (an ``autotune.BudgetController``) replaces the raw
    analytic ``engine.nfp_budget`` as the per-step position budget: the
    analytic value stays the hard cap, but the controller shrinks and
    probes inside it against the step latency the loop actually
    observes (admission keeps the analytic gate — concurrency is a
    throughput decision, the controller governs per-forward width).
    ``step_clock(width, ell) -> seconds`` substitutes a latency model
    for the wall clock (one call per forward of that step) — the
    calibration benchmark injects the roofline simulator here, since a
    CPU host cannot time the TPU-target forward it is scheduling.
    """

    MODES = ("greedy", "speculative", "diffusion", "mtp")

    def __init__(self, engine: DecodeEngine, mode: str = "greedy",
                 eps: float = 0.2, max_width: int = 16,
                 adapter: Optional[SlotAdapter] = None,
                 mtp_heads: Optional[Dict] = None,
                 block_size: Optional[int] = None, refine_steps: int = 4,
                 mask_id: Optional[int] = None,
                 controller=None,
                 step_clock: Optional[Callable[[int, int], float]] = None,
                 admission: Optional[AdmissionConfig] = None):
        self.engine = engine
        self.eps = eps
        self.max_width = max_width
        self.controller = controller
        self.step_clock = step_clock
        self.admission = admission if admission is not None \
            else AdmissionConfig()
        if adapter is None:
            if mode not in self.MODES:
                raise ValueError(f"unknown serving mode {mode!r}")
            if mode == "greedy":
                adapter = SlotAdapter(self)
            elif mode == "speculative":
                adapter = SpeculativeSlotAdapter(self)
            elif mode == "mtp":
                adapter = MTPSlotAdapter(self, mtp_heads)
            else:
                a = engine.cfg.attention
                trained = a.diffusion_block if a is not None else None
                if trained is None:
                    adapter = DiffusionSlotAdapter(
                        self, block_size=block_size,
                        refine_steps=refine_steps, mask_id=mask_id)
                elif block_size in (None, trained):
                    adapter = BlockDiffusionSlotAdapter(
                        self, refine_steps=refine_steps, mask_id=mask_id)
                else:
                    raise ValueError(f"{engine.cfg.name} generates blocks "
                                     f"of {trained}, not {block_size}")
        self.adapter = adapter
        self.mode = adapter.mode
        if controller is not None:
            controller.bind(self.mode, engine.use_kernel,
                            clocked=step_clock is not None)
        # budget provenance of the CURRENT step (set by ``budget()``,
        # read by ``shared_forward`` telemetry and ``step`` timing)
        self._budget_info: Dict = {}
        self.waiting: Deque[Request] = deque()
        self.active: Dict[int, Request] = {}            # slot -> request
        self.free_slots: List[int] = list(range(engine.batch))
        self.finished: Dict[int, Request] = {}
        self._next_rid = 0
        # load-pressure telemetry (preemption / backpressure policies)
        self.preempted_total = 0
        self.resumed_total = 0
        self.rejected_total = 0
        # engine.prefill_log outlives this loop — remember where ours starts
        self._prefill_log_start = len(engine.prefill_log)
        # per-forward telemetry: active/width/positions/budget plus, when
        # serving through the kernel path, its measured granularity slack
        # (attn_row_util, kv_tiles_executed/grid/skipped, kv_tile_util,
        # attn_heads_per_step, attn_grid_steps of one layer's launch) —
        # the measured counterpart of the core.nfp M_attn prediction.
        # Diffusion logs one entry per refinement/commit forward, so
        # ``len(step_log)`` counts FORWARDS in every mode.
        self.step_log: List[Dict] = []
        # bytes of the widest element the attention launch reads, which
        # sizes its grid step: the activations' (the embedding's) or the
        # K cache's
        self._attn_itemsize = max(jax.tree.leaves((
            jax.tree.map(lambda x: x.dtype.itemsize, engine.params["embed"]),
            jax.tree_util.tree_map_with_path(
                lambda path, x: (x.dtype.itemsize
                                 if getattr(path[-1], "key", None) == "k"
                                 else 0), engine.cache))))

    # ------------------------------------------------------------------
    def submit(self, prompt, max_tokens: int,
               slo_class: str = "default") -> Request:
        try:
            self.admission.slo(slo_class)
        except KeyError:
            raise ValueError(f"unknown SLO class {slo_class!r}") from None
        cap = self.admission.max_waiting
        if cap is not None and len(self.waiting) >= cap:
            self.rejected_total += 1
            raise AdmissionRejected(
                f"waiting queue at capacity ({cap}); shed load or retry")
        prompt = np.asarray(prompt, np.int64).ravel()
        # reject here, where the caller can handle it per-request — an
        # admission-time failure would abort every in-flight request.
        # The prompt-alone check matters: ``prefill_bucket`` clamps its
        # bucket to max_len, so an oversized prompt used to fail deep in
        # the prefill machinery (or silently truncate on some paths)
        # instead of at the API surface.
        headroom = self.adapter.headroom()
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if len(prompt) > self.engine.max_len:
            raise ValueError(
                f"prompt of {len(prompt)} tokens exceeds the engine's "
                f"max_len={self.engine.max_len}; it can never be admitted")
        if len(prompt) + int(max_tokens) + headroom > self.engine.max_len:
            raise ValueError(
                f"request of {len(prompt)} prompt + {max_tokens} tokens "
                f"(+{headroom} draft headroom) cannot fit "
                f"max_len={self.engine.max_len}")
        mgr = self.engine.manager
        if mgr is not None:
            worst = -(-min(len(prompt) + int(max_tokens) + headroom,
                           self.engine.max_len) // mgr.block_size)
            if worst > mgr.n_blocks:
                raise ValueError(
                    f"request needs up to {worst} KV blocks but the pool "
                    f"only has {mgr.n_blocks}; it can never be admitted")
        req = Request(self._next_rid, prompt, int(max_tokens),
                      slo_class=slo_class, t_submit=time.perf_counter())
        self._next_rid += 1
        self.waiting.append(req)
        return req

    # ------------------------------------------------------------------
    def budget(self) -> int:
        """Position budget at the CURRENT longest active context:
        the analytic NFP budget, refined by the ``BudgetController``
        when one is attached (predicted / calibrated / applied
        provenance lands in each forward's ``step_log`` entry).

        Reads the engine's HOST mirror of the slot lengths — budgeting
        must never block on a device read in the decode hot path."""
        lens = self.engine.slot_lens_host
        ell = max(int(lens.max()) if lens.size else 1, 1)
        analytic = self.engine.nfp_budget(self.eps, ell=ell)
        info = {"ell": ell, "analytic": analytic, "applied": analytic}
        if self.controller is not None:
            info["applied"] = self.controller.budget(
                ell, len(self.active), analytic)
            calibrated = self.controller.table_budget(
                ell, len(self.active), analytic)
            if calibrated is not None:
                info["calibrated"] = calibrated
        self._budget_info = info
        return info["applied"]

    def _reserve_len(self, req: Request) -> int:
        """Cache positions a request can touch over its lifetime."""
        return min(len(req.prompt) + req.max_tokens
                   + self.adapter.headroom(), self.engine.max_len)

    @staticmethod
    def _admit_tokens(req: Request) -> np.ndarray:
        """Positions a (re-)admission must have committed KV for.  Fresh
        requests prefill their prompt; a preempted request RECOMPUTES
        its evicted cache by prefilling ``context`` (prompt + generated
        minus the pending token) — the stream itself is host state, so
        nothing re-emits and the resumed request is indistinguishable
        from one that was never evicted."""
        return req.context if req.generated else req.prompt

    def _priority(self, req: Request) -> int:
        return self.admission.slo(req.slo_class).priority

    def _pop_candidate(self) -> Optional[Request]:
        """Highest-priority waiting request (FIFO within a class: rid
        order — a preempted request keeps its original rid, so it
        resumes ahead of later arrivals of its own class)."""
        if not self.waiting:
            return None
        best = min(self.waiting, key=lambda r: (-self._priority(r), r.rid))
        self.waiting.remove(best)
        return best

    def _block_cost(self, req: Request) -> int:
        """Pool blocks this admission consumes: fresh allocations PLUS
        the evictable cached blocks it would pin (they stop being
        reclaimable), per ``BlockManager.admission_cost``."""
        mgr = self.engine.manager
        if mgr is None:
            return 0
        need, pinned = mgr.admission_cost(
            self._admit_tokens(req).tolist(), self._reserve_len(req))
        return need + pinned

    def _blocks_left(self, promised: int) -> int:
        """Free + evictable blocks minus what THIS admission group has
        already promised to candidates not yet prefilled."""
        mgr = self.engine.manager
        return (mgr.available_blocks() - promised) if mgr is not None else 0

    def _fits(self, req: Request, promised: int) -> bool:
        if not self.free_slots:
            return False
        if self.engine.manager is None:
            return True
        return self._block_cost(req) <= self._blocks_left(promised)

    def preempt(self, slot: int) -> Request:
        """Evict the request in ``slot`` mid-stream: its paged blocks
        return to the pool (dense: the row's length zeroes) and it
        re-enters the waiting queue for recompute-on-resume.  MTP
        proposal state is rebuilt from the resume prefill's last hidden,
        so no device state survives the eviction."""
        req = self.active.pop(slot)
        self.engine.preempt_slot(slot)
        self.free_slots.append(slot)
        req.slot = None
        req.hidden = None
        req.preemptions += 1
        self.preempted_total += 1
        self.waiting.appendleft(req)
        return req

    def _preempt_for(self, cand: Request, promised: int) -> None:
        """Priority preemption: while ``cand`` (already popped from the
        queue) cannot get a slot or enough KV blocks, evict the
        lowest-priority active request — only ever one with priority
        STRICTLY below the candidate's, so victims (which requeue at
        their own priority) can never preempt back."""
        while not self._fits(cand, promised):
            victims = [s for s, r in self.active.items()
                       if self._priority(r) < self._priority(cand)]
            if not victims:
                return
            # lowest priority first; latest-admitted (largest rid) tie-
            # break wastes the least completed work
            victim = max(victims, key=lambda s: (
                -self._priority(self.active[s]), self.active[s].rid))
            self.preempt(victim)

    def admit(self) -> int:
        """Admission: fill free slots in SLO-priority order while every
        active request still fits >= 1 position inside the budget, then
        prefill ALL newly admitted slots in one bucketed batched
        forward.  Returns the number of requests admitted.

        On a paged engine the gate is FREE BLOCKS, not free slots alone:
        a candidate only admits if the pool can cover its whole
        reservation (prompt + max_tokens + headroom, minus whatever its
        prefix-cache hit reuses) — evictable cache-only blocks count as
        available.  Requests that don't fit yet simply wait; retirement
        and LRU eviction free blocks over time (and, under
        ``AdmissionConfig.preemption``, a higher-priority candidate
        evicts the lowest-priority active request instead of waiting).

        Called from ``run`` and the trace harness at the ARRIVAL
        boundary, never from ``step``: admission is where prompts enter
        and first tokens leave, so its device<->host traffic is
        inherent — and batched: one ``greedy_tokens`` readback covers
        every freshly admitted slot (resumed requests need none, their
        pending token is host state already)."""
        t0 = time.perf_counter()
        with TraceAnnotation("repro.scheduler.admit") as span:
            admitted = self._select()
            if not admitted:
                return 0
            span.set_metadata(rids=" ".join(
                str(r.rid) for r in admitted.values()))
            outs = self.engine.prefill_slots(
                {s: self._admit_tokens(r) for s, r in admitted.items()},
                reserve={s: self._reserve_len(r)
                         for s, r in admitted.items()})
            fresh = sorted(s for s, r in admitted.items() if not r.generated)
            if fresh and self.adapter.first_token:
                # first token of every fresh request in ONE device argmax +
                # one small (k,) readback — the admission-boundary transfer
                with TraceAnnotation("repro.scheduler.first_token"):
                    first = np.asarray(greedy_tokens(
                        jnp.stack([outs[s][0] for s in fresh])))
                for i, s in enumerate(fresh):
                    req = admitted[s]
                    req.pending = int(first[i])
                    req.generated = [req.pending]
            for slot, req in admitted.items():
                if req.preemptions and slot not in fresh:
                    self.resumed_total += 1
                if req.t_admit is None:
                    req.t_admit = t0
                self.active[slot] = req
                self.adapter.begin(req, outs[slot][1])
            return len(admitted)

    def _select(self) -> Dict[int, Request]:
        """The admission group: {slot: request} popped from the queue in
        priority order while the budget and the pool allow."""
        admitted: Dict[int, Request] = {}
        promised = 0                      # blocks owed to this group
        ell = int(self.engine.slot_lens_host.max())
        while self.free_slots or self.admission.preemption:
            cand = self._pop_candidate()
            if cand is None:
                break
            if self.admission.preemption:
                self._preempt_for(cand, promised)
            # prospective budget once the candidate's context lands
            ell_next = max(ell, len(self._admit_tokens(cand)), 1)
            budget = self.engine.nfp_budget(self.eps, ell=ell_next)
            over_budget = (len(self.active) + len(admitted)
                           >= max(1, budget))
            if over_budget or not self._fits(cand, promised):
                # head-of-line within priority order: don't skip ahead,
                # retirement/eviction frees blocks over time
                self.waiting.appendleft(cand)
                break
            promised += self._block_cost(cand)
            slot = self.free_slots.pop(0)
            cand.slot = slot
            admitted[slot] = cand
            ell = ell_next
        return admitted

    # ------------------------------------------------------------------
    def _attn_slack(self, width: int) -> Optional[Dict]:
        """Model this forward's kernel-granularity slack: the ragged decode
        kernel's physical query rows / kv tiles vs the useful work of the
        active slots (``ops.slack_report`` mirrors the kernel's per-row
        tile-skip rule exactly).  None when the engine runs the XLA
        reference path (nothing is tiled, so reporting tile slack would
        fabricate a measurement) or for archs the kernel doesn't serve
        (MLA / attention-free)."""
        a = self.engine.cfg.attention
        if not self.engine.use_kernel or a is None or a.kind == "mla":
            return None
        active = np.zeros(self.engine.batch, bool)
        active[list(self.active)] = True
        extra = {}
        if self.engine.manager is not None:
            # the paged launch tiles kv by PAGE: its k_block is the kv
            # block size, so executed/grid tiles stay honest under paging
            extra["k_block"] = self.engine.manager.block_size
        return slack_report(
            width, self.engine.slot_lens_host, self.engine.max_len,
            head_dim=a.head_dim,
            window=a.window if a.kind == "swa" else None,
            block=a.diffusion_block, active=active, n_heads=a.n_heads,
            kv_heads=a.n_kv_heads, itemsize=self._attn_itemsize, **extra)

    def shared_forward(self, tokens: np.ndarray, budget: int
                       ) -> Tuple[Array, Dict, Array]:
        """ONE batched multi-position decode forward over all slots,
        WITHOUT committing; appends this forward's telemetry entry.
        Returns (logits, new_cache, hidden)."""
        width = tokens.shape[1]
        with TraceAnnotation("repro.scheduler.log_forward"):
            entry = {
                "active": len(self.active), "width": width,
                "positions": len(self.active) * width, "budget": budget,
                "budget_analytic": self._budget_info.get("analytic", budget),
                "ell": self._budget_info.get("ell", 1),
            }
            if "calibrated" in self._budget_info:
                entry["budget_calibrated"] = self._budget_info["calibrated"]
            for req in self.active.values():
                req.forwards += 1
            if self.engine.manager is not None:
                entry["kv_blocks_used"] = self.engine.manager.blocks_used()
            slack = self._attn_slack(width)
            if slack is not None:
                entry.update({
                    "attn_rows_physical": slack["rows_physical"],
                    "attn_row_util": slack["row_utilization"],
                    "kv_tiles_executed": slack["kv_tiles_executed"],
                    "kv_tiles_grid": slack["kv_tiles_grid"],
                    "kv_tiles_skipped": slack["kv_tiles_skipped"],
                    "kv_tile_util": slack["kv_tile_utilization"],
                    "attn_heads_per_step": slack["heads_per_step"],
                    "attn_grid_steps": slack["grid_steps"],
                })
            self.step_log.append(entry)
        return self.engine.decode_slots(jnp.asarray(tokens, jnp.int32))

    # ------------------------------------------------------------------
    def step(self) -> bool:
        """One DECODE iteration: let the adapter drive its shared
        forward(s) + per-slot commit, retire finished requests.  Returns
        False when no work remains.  Admission is the caller's move
        (``run`` / the trace harness invoke ``admit`` at the arrival
        boundary) — this keeps the steady-state decode path free of the
        prompt-upload/first-token transfers admission inherently makes
        (``repro.analysis`` walks exactly this function)."""
        if not self.active:
            return bool(self.waiting)
        with TraceAnnotation("repro.scheduler.step") as span:
            budget = self.budget()
            slots = sorted(self.active)
            width = self.adapter.width(len(slots), budget)
            span.set_metadata(width=width, active=len(slots))
            mark = len(self.step_log)
            t0 = time.perf_counter()
            self.adapter.run_step(slots, width, budget)
            # --- step latency + controller feedback --------------------
            # run_step host-syncs on its accept loop, so the wall clock is
            # a faithful per-step latency on a real accelerator;
            # step_clock substitutes a latency model per forward
            # (benchmarks on CPU).
            dt = time.perf_counter() - t0
            new = self.step_log[mark:]
            if new:
                if self.step_clock is not None:
                    ell = self._budget_info.get("ell", 1)
                    dt = sum(self.step_clock(e["width"], ell) for e in new)
                new[-1]["step_latency_s"] = dt
                if self.controller is not None:
                    ratio = self.controller.observe(
                        self._budget_info.get("ell", 1),
                        max(e["width"] for e in new), dt / len(new))
                    if ratio is not None:
                        new[-1]["latency_ratio"] = ratio
            # --- retire ------------------------------------------------
            with TraceAnnotation("repro.scheduler.retire") as retire:
                done = []
                for s in slots:
                    req = self.active[s]
                    if len(req.generated) >= req.max_tokens:
                        req.done = True
                        self.finished[req.rid] = req
                        del self.active[s]
                        self.engine.release_slot(s)
                        self.free_slots.append(s)
                        done.append(str(req.rid))
                retire.set_metadata(rids=" ".join(done))
        return bool(self.active or self.waiting)

    # ------------------------------------------------------------------
    def run(self) -> Dict[int, np.ndarray]:
        """Serve until the queue drains; returns {rid: tokens}."""
        while True:
            self.admit()
            if not self.active and self.waiting:
                raise RuntimeError(
                    "admission stalled with an empty active set — the "
                    "pool cannot cover the head-of-queue reservation "
                    "(submit() should have rejected it)")
            if not self.step():
                break
        return {rid: req.tokens() for rid, req in
                sorted(self.finished.items())}

    # ------------------------------------------------------------------
    def stats(self) -> Dict:
        total_tokens = sum(len(r.tokens()) for r in self.finished.values())
        total_positions = sum(e["positions"] for e in self.step_log)
        forwards = len(self.step_log)
        out = {
            "requests": len(self.finished),
            "tokens": total_tokens,
            "forwards": forwards,
            "preemptions": self.preempted_total,
            "resumes": self.resumed_total,
            "rejections": self.rejected_total,
            "positions": total_positions,
            "tokens_per_forward": total_tokens / max(forwards, 1),
            "position_utilization": total_tokens / max(total_positions, 1),
            "max_positions_per_forward": max(
                (e["positions"] for e in self.step_log), default=0),
        }
        prefills = self.engine.prefill_log[self._prefill_log_start:]
        out["prefill_forwards"] = len(prefills)
        out["prefill_buckets"] = sorted({e["bucket"] for e in prefills})
        out["prefill_positions_computed"] = sum(
            e.get("computed_tokens", 0) for e in prefills)
        if self.engine.manager is not None:
            # paged-cache accounting: pool occupancy plus the prefix-hit
            # counters — ``prefill_positions_saved`` is the prompt
            # positions admissions did NOT have to prefill
            out.update(self.engine.manager.stats())
            out["prefill_positions_saved"] = sum(
                e.get("cached_tokens", 0) for e in prefills)
        # budget provenance: what the analytic predictor said, what the
        # calibration table said, what was actually spent — plus the
        # controller's observed-latency accounting when one is attached
        if self.step_log:
            out["mean_budget"] = (sum(e["budget"] for e in self.step_log)
                                  / len(self.step_log))
            out["mean_budget_analytic"] = (
                sum(e.get("budget_analytic", e["budget"])
                    for e in self.step_log) / len(self.step_log))
            calibrated = [e["budget_calibrated"] for e in self.step_log
                          if "budget_calibrated" in e]
            if calibrated:
                out["mean_budget_calibrated"] = (sum(calibrated)
                                                 / len(calibrated))
        latencies = [e["step_latency_s"] for e in self.step_log
                     if "step_latency_s" in e]
        if latencies:
            out["step_latency_total_s"] = sum(latencies)
        ratios = [e["latency_ratio"] for e in self.step_log
                  if "latency_ratio" in e]
        if ratios:
            out["mean_latency_ratio"] = sum(ratios) / len(ratios)
            out["max_latency_ratio"] = max(ratios)
        if self.controller is not None:
            out["controller"] = self.controller.stats()
        slacked = [e for e in self.step_log if "kv_tile_util" in e]
        if slacked:
            out["mean_attn_row_util"] = (
                sum(e["attn_row_util"] for e in slacked) / len(slacked))
            out["mean_kv_tile_util"] = (
                sum(e["kv_tile_util"] for e in slacked) / len(slacked))
            out["kv_tiles_skipped"] = sum(
                e["kv_tiles_skipped"] for e in slacked)
            out["kv_tiles_executed"] = sum(
                e["kv_tiles_executed"] for e in slacked)
        return out
