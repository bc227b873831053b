"""NFP measurement protocol (paper App. C.1.2-C.1.3).

Works on any latency source: wall-clock timing of a callable (CPU sanity
sweeps), the roofline simulator (TPU-target curves), or recorded curves.
"""
from __future__ import annotations

import math
import statistics
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Sequence, Tuple

import jax

DEFAULT_EPS = 0.2


@dataclass
class LatencyCurve:
    ns: List[int]
    times: List[float]
    baseline_n: int = 1
    # optional per-sample relative spread (max-min over round medians,
    # normalized by the median) from ``time_callable`` — the noise floor
    # the autotune controller's variance gate reuses.  Empty for
    # simulated / recorded curves (deterministic sources).
    spreads: List[float] = field(default_factory=list)

    @property
    def baseline_time(self) -> float:
        if self.baseline_n not in self.ns:
            raise ValueError(
                f"baseline_n={self.baseline_n} was not sampled: the curve "
                f"covers N in {sorted(self.ns)}.  Add the baseline to the "
                "sweep (for balanced MoE use balanced_moe_baseline_n).")
        return self.times[self.ns.index(self.baseline_n)]

    @property
    def max_spread(self) -> float:
        """Largest relative per-round spread across the sweep — a single
        scalar noise floor for tolerance gating (0 when unknown)."""
        return max(self.spreads, default=0.0)

    def as_dict(self) -> Dict[int, float]:
        return dict(zip(self.ns, self.times))


def extract_nmax(curve: LatencyCurve, eps: float = DEFAULT_EPS,
                 contiguous: bool = False) -> int:
    """Eq. 4 / Eq. 24: largest sampled N with T(N) <= (1+eps)*T(baseline).

    For the load-balanced MoE case the baseline is the smallest N that
    activates all experts (Eq. 26) — pass it via ``curve.baseline_n``.

    ``contiguous=True`` stops at the FIRST above-tolerance N past the
    baseline instead of taking the global max: on a noisy wall-clock
    curve a single rebound sample beyond the knee (a lucky fast round at
    large N) would otherwise inflate N_max past the real boundary.  The
    calibrator uses this mode; the default keeps the paper's protocol.
    """
    t0 = curve.baseline_time
    best = curve.baseline_n
    for n, t in sorted(zip(curve.ns, curve.times)):
        if n < curve.baseline_n:
            continue
        if t <= (1.0 + eps) * t0:
            best = max(best, n)
        elif contiguous:
            break
    return best


def balanced_moe_baseline_n(n_experts: int, b: int, k: int) -> int:
    """Eq. 26: N_bal0 = ceil(E / (b*k)) — smallest N activating all experts."""
    return math.ceil(n_experts / (b * k))


def sensitivity_sweep(curve: LatencyCurve,
                      eps_values: Sequence[float] = (0.05, 0.10, 0.15, 0.20, 0.30),
                      ) -> Dict[float, int]:
    """App. I tolerance sweep."""
    return {eps: extract_nmax(curve, eps) for eps in eps_values}


# ---------------------------------------------------------------------------
# Wall-clock timing (CPU sanity layer).  Scaled-down version of the paper's
# protocol: warmup then R rounds x I iterations, median of per-round medians.
# ---------------------------------------------------------------------------

def time_callable(fn: Callable[[], object], warmup: int = 3, rounds: int = 5,
                  iters: int = 10) -> Tuple[float, float]:
    """Returns ``(median, spread)``: the median of per-round medians and
    the relative per-round spread ``(max - min) / median`` — the
    measured noise floor.  The autotune controller's variance gate
    consumes the spread directly instead of re-deriving noise from live
    serving steps."""
    for _ in range(warmup):
        r = fn()
        _block(r)
    round_medians = []
    for _ in range(rounds):
        samples = []
        for _ in range(iters):
            t0 = time.perf_counter()
            r = fn()
            _block(r)
            samples.append(time.perf_counter() - t0)
        round_medians.append(statistics.median(samples))
    med = statistics.median(round_medians)
    spread = ((max(round_medians) - min(round_medians)) / med
              if med > 0 else 0.0)
    return med, spread


def _block(result) -> None:
    """block_until_ready for jax outputs (other leaves pass through).  A
    failed sync raises: swallowing it would time the enqueue."""
    jax.block_until_ready(result)


def sweep_callable(make_fn: Callable[[int], Callable[[], object]],
                   n_values: Sequence[int], baseline_n: int = 1,
                   warmup: int = 3, rounds: int = 5, iters: int = 10,
                   ) -> LatencyCurve:
    """Measure T(N) over a sweep.  ``make_fn(n)`` returns a zero-arg callable
    executing one decode forward with n positions (pre-compiled outside the
    timed region, matching App. C.1.3's pre-allocation discipline)."""
    ns, times, spreads = [], [], []
    for n in n_values:
        fn = make_fn(int(n))
        med, spread = time_callable(fn, warmup, rounds, iters)
        times.append(med)
        spreads.append(spread)
        ns.append(int(n))
    return LatencyCurve(ns, times, baseline_n, spreads)


def staircase_boundaries(ns: Sequence[int], values: Sequence[float],
                         rel_jump: float = 0.05) -> List[int]:
    """Detect discrete staircase steps in a metric (runtime FLOPs / AI):
    the paper's RQ3 signature of granularity-governed execution."""
    steps = []
    for i in range(1, len(ns)):
        if values[i - 1] > 0 and (values[i] - values[i - 1]) / values[i - 1] > rel_jump:
            steps.append(int(ns[i]))
    return steps
