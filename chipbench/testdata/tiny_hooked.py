"""Test configuration module: the check rebuilt from the reference's
blocks, as a configuration whose block the default does not serve would
build its own.  It defines ``gaps`` alone; ``arch_of``,
``program_config`` and ``model_flops`` stay the defaults."""
import numpy as np

import reference


def head_weight(arch, seed):
    return reference.head_weight(arch, seed)


def gaps(arch, seed, finished, control=False):
    """Widest and mean gap of the served tokens below the reference's
    best logit, over every served position."""
    seqs, spans, targets = [], [], []
    for prompt, tokens, _req in finished:
        seqs.append(np.concatenate([prompt, tokens[:-1]]).astype(np.int32))
        spans.append((len(prompt) - 1, len(tokens)))
        targets.append(np.asarray(tokens, np.int32))
    hidden = reference.final_hidden(arch, seed, seqs, "f32")
    rows = np.concatenate([h[s:s + n] for h, (s, n) in zip(hidden, spans)])
    best, got, _ = reference.head_stats(rows, head_weight(arch, seed),
                                        np.concatenate(targets))
    return {"max_logit_gap": float(np.max(best - got)),
            "mean_logit_gap": float(np.mean(best - got)),
            "positions": int(len(rows))}
