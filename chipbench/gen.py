"""Seeded traffic from a mix file: arrivals, prompt and output lengths.

Adapted from the program's ``repro.loadgen.trace`` (Poisson and 2-state
MMPP arrivals, bounded-Pareto and clamped-lognormal lengths), kept here
so that no later change to the program can change the traffic.

Every draw is an inverse CDF of a uniform ``u``.  The uniforms come from
a stratified grid: within each block of ``block`` consecutive requests
every stratum ``(j + 0.5) / block`` is used once, in an order drawn from
the seed.  So every seed sends the same multiset of lengths and gaps in
another order, and two seeds differ in order and in token ids, not in
how much work they carry.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List

import numpy as np


@dataclass(frozen=True)
class Request:
    """One request of a run: when it is due (seconds from the start of
    the lead-in; 0 for a closed loop, whose clients send on completion),
    its prompt and how many tokens it asks for."""

    rid: int
    due_s: float
    prompt: np.ndarray
    max_tokens: int


def _norm_ppf(u: float) -> float:
    """Standard normal quantile (Acklam's rational approximation,
    relative error below 1.2e-9)."""
    a = (-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00)
    b = (-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01)
    c = (-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00)
    d = (7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00)
    lo = 0.02425
    if u < lo:
        q = math.sqrt(-2 * math.log(u))
        return (((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
                + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    if u > 1 - lo:
        return -_norm_ppf(1 - u)
    q = u - 0.5
    r = q * q
    return ((((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r
             + a[5]) * q / (((((b[0] * r + b[1]) * r + b[2]) * r + b[3]) * r
                             + b[4]) * r + 1))


def length(spec: Dict, u: float) -> int:
    """A token length for uniform ``u`` under ``spec``: ``pareto``
    (bounded on [lo, hi], tail index ``alpha``), ``lognormal`` (median
    ``median``, log-space ``sigma``), clamped to [lo, hi], or ``fixed``."""
    lo, hi = int(spec["lo"]), int(spec["hi"])
    if lo > hi:
        raise ValueError(f"length lo={lo} > hi={hi}")
    dist = spec["dist"]
    if dist == "fixed" or lo == hi:
        return lo
    if dist == "pareto":
        a = float(spec["alpha"])
        ratio = (lo / hi) ** a
        x = lo / (1.0 - u * (1.0 - ratio)) ** (1.0 / a)
    elif dist == "lognormal":
        x = float(spec["median"]) * math.exp(float(spec["sigma"])
                                             * _norm_ppf(u))
    else:
        raise ValueError(f"unknown length dist {dist!r}")
    return int(min(hi, max(lo, round(x))))


def gap(spec: Dict, u: float, burst: List[bool], u_switch: float) -> float:
    """One inter-arrival gap: ``poisson`` at ``rate_rps``, or ``mmpp``
    (calm ``rate_rps`` / ``burst_rate_rps``, switching with
    ``p_enter_burst`` / ``p_exit_burst`` after each arrival; ``burst``
    is the boxed state)."""
    kind = spec["kind"]
    if kind == "poisson":
        return -math.log(1.0 - u) / float(spec["rate_rps"])
    if kind != "mmpp":
        raise ValueError(f"unknown arrival kind {kind!r}")
    rate = float(spec["burst_rate_rps"] if burst[0] else spec["rate_rps"])
    g = -math.log(1.0 - u) / rate
    if burst[0]:
        burst[0] = u_switch >= float(spec["p_exit_burst"])
    else:
        burst[0] = u_switch < float(spec["p_enter_burst"])
    return g


def stratified(rng: np.random.Generator, n: int, block: int) -> np.ndarray:
    """``n`` uniforms, each block of ``block`` a permutation of the
    stratum midpoints."""
    out = np.empty(n)
    for start in range(0, n, block):
        k = min(block, n - start)
        out[start:start + k] = (rng.permutation(block)[:k] + 0.5) / block
    return out


def generate(mix: Dict, seed: int, n: int, vocab: int) -> List[Request]:
    """The first ``n`` requests of ``mix`` under ``seed``.  A closed
    loop's requests are all due at 0: its clients take them in order."""
    rng = np.random.default_rng(seed)
    block = int(mix.get("strata", 32))
    u_prompt = stratified(rng, n, block)
    u_out = stratified(rng, n, block)
    open_loop = mix["loop"] == "open"
    if open_loop:
        u_gap = stratified(rng, n, block)
        u_switch = rng.random(n)
    burst = [False]
    now = 0.0
    out = []
    for i in range(n):
        if open_loop:
            now += gap(mix["arrivals"], float(u_gap[i]), burst,
                       float(u_switch[i]))
        p = length(mix["prompt"], float(u_prompt[i]))
        m = length(mix["output"], float(u_out[i]))
        prompt = rng.integers(0, vocab, size=p).astype(np.int64)
        out.append(Request(i, now, prompt, m))
    return out
