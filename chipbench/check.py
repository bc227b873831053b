"""Whether what the timed path served is correct: the default check.

A configuration whose module (``chipbench/configs/<config>.py``, see
``run.Model``) defines ``gaps`` is checked by that instead, on the same
sample.  Each entry of the sample is ``(prompt, served tokens, the
program's Request)``; the default reads the first two.

A sample of the requests the window finished, drawn from the seed with
the longest among them, is run through the float32 reference over each
prompt followed by its served tokens.  At every served position the
gap ``best reference logit - reference logit of the served token`` is
read; the widest gap over the sample (``max_logit_gap``) and the mean
gap over its positions (``mean_logit_gap``) are the numbers a
configuration file can set a limit on.  Greedy serving of a sound
program puts a gap at rounding: a token served where the reference has
a near-tie (or, in a mixture of experts, where a router near-tie sends
a token to another expert).

With ``control`` the same positions are read with the reference rounded
to float8 in the program's place: the gaps of the tokens that the lower
precision puts first.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

import reference


def sample(finished: Sequence[tuple], seed: int, n: int) -> List[int]:
    """Indices of ``n`` finished requests (prompt, tokens, ...): the one
    with the most served tokens, and the rest drawn from the seed."""
    if not finished:
        return []
    longest = max(range(len(finished)), key=lambda i: len(finished[i][1]))
    rng = np.random.default_rng([int(seed), 7])
    rest = [i for i in rng.permutation(len(finished)) if i != longest]
    return [longest] + [int(i) for i in rest[:max(0, n - 1)]]


def _inputs(finished):
    """Reference inputs and targets: prompt + tokens[:-1] predicts
    tokens[j] at position len(prompt) - 1 + j."""
    seqs, spans = [], []
    for prompt, tokens, *_ in finished:
        seqs.append(np.concatenate([prompt, tokens[:-1]]).astype(np.int32))
        spans.append((len(prompt) - 1, len(tokens)))
    return seqs, spans


def gaps(arch: Dict, seed: int, finished, control: bool = False) -> Dict:
    """Widest and mean gap of the served tokens, and with ``control``
    also of the float8 control's own first choices at the same
    positions."""
    seqs, spans = _inputs(finished)
    hidden = reference.final_hidden(arch, seed, seqs, "f32")
    w = reference.head_weight(arch, seed)
    ref_hidden, targets = [], []
    for h, (start, n), (_, tokens, *_) in zip(hidden, spans, finished):
        ref_hidden.append(h[start:start + n])
        targets.append(np.asarray(tokens, np.int32))
    ref_hidden = np.concatenate(ref_hidden)
    targets = np.concatenate(targets)
    best, got, _ = reference.head_stats(ref_hidden, w, targets)
    out = {"max_logit_gap": float(np.max(best - got)),
           "mean_logit_gap": float(np.mean(best - got)),
           "positions": int(len(targets))}
    if control:
        low = reference.final_hidden(arch, seed, seqs, "fp8")
        low_hidden = np.concatenate([h[s:s + n] for h, (s, n)
                                     in zip(low, spans)])
        _, _, pick = reference.head_stats(low_hidden, w, targets, "fp8")
        _, got_c, _ = reference.head_stats(ref_hidden, w, pick)
        out["control"] = {"max_logit_gap": float(np.max(best - got_c)),
                          "mean_logit_gap": float(np.mean(best - got_c))}
    return out
