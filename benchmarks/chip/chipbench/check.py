"""Whether what the timed path served is right.

After the window, a sample of the finished requests, drawn from the seed,
is run through the plain reference once each: prompt and served tokens,
teacher-forced.  At the position before each served token the reference
gives its logits; the token's gap is how far its logit lies below the
reference's best, in units of that position's logit standard deviation.
``logit_gap_max`` is the widest gap over every token of the sample.  A
greedy server that computes the model right serves the reference's best
token or one within rounding of it; a wrong cache entry, position, mask
or token lands far below.
"""
from __future__ import annotations

import numpy as np

from chipbench import reference, weights
from chipbench.spec import Model

BLOCK = 4  # sequences per reference call
WRONG = 1e9  # the gap of a token outside the vocabulary


def control_quant(m: Model) -> str:
    """The precision step below the one the configuration states."""
    return {"bfloat16": "fp8", "float32": "bf16"}[m.dtype]


def sample(answered, seed: int, n_remote: int, n_hedge: int):
    """Indices of the requests to compare: ``n_remote`` answered by the
    remote tier and ``n_hedge`` by the hedge, drawn from the seed.

    ``answered`` is a list of ``(index, used_remote)`` pairs."""
    rng = np.random.default_rng([seed, 7])
    remote = [i for i, r in answered if r]
    hedge = [i for i, r in answered if not r]
    pick = []
    for group, k in ((remote, n_remote), (hedge, n_hedge)):
        if group:
            pick += list(rng.choice(group, size=min(k, len(group)), replace=False))
    return sorted(int(i) for i in pick)


def _positions(prompt_len: int, n_tokens: int, rows: int):
    r = np.repeat(np.arange(rows), n_tokens)
    c = np.tile(prompt_len - 1 + np.arange(n_tokens), rows)
    return r, c


def gaps(m: Model, w: dict, prompts: np.ndarray, served: np.ndarray,
         quant: str = "none", control: bool = False) -> np.ndarray:
    """Per-token gaps of ``served`` (R, n) after ``prompts`` (R, P).

    With ``control`` the tokens are not ``served`` but those that the
    ``quant`` pass ranks first, and the gaps are read from the float32
    reference at the same positions."""
    R, P = prompts.shape
    n = served.shape[1]
    out = []
    for b in range(0, R, BLOCK):
        pr, sv = prompts[b:b + BLOCK], served[b:b + BLOCK]
        bad = (sv < 0) | (sv >= m.vocab_size)
        seq = np.concatenate([pr, np.clip(sv[:, :-1], 0, m.vocab_size - 1)], 1)
        rows, cols = _positions(P, n, len(pr))
        ref = np.asarray(reference.logits(m, w, seq, rows, cols))
        if control:
            low = np.asarray(reference.logits(m, w, seq, rows, cols, quant))
            tok = low.argmax(-1)
        else:
            tok = np.clip(sv, 0, m.vocab_size - 1).reshape(-1)
        best = ref.max(-1)
        g = (best - ref[np.arange(len(tok)), tok]) / ref.std(-1)
        if not control:
            g = np.where(bad.reshape(-1), WRONG, g)
        out.append(g.reshape(len(pr), n))
    return np.concatenate(out, 0)


def widest_gap(models: dict, seed: int, groups: dict, control: bool = False) -> dict:
    """``logit_gap_max`` over the tiers' samples.

    ``groups`` maps a tier (``remote``/``hedge``) to ``(prompts, served)``;
    ``models`` maps it to its :class:`Model`.  Weights are regenerated
    from the seed, one tier at a time.  With ``control`` the reading is
    the control's: the reference one precision step down in the program's
    place."""
    widest = {}
    for tier, (prompts, served) in groups.items():
        if len(prompts) == 0:
            continue
        m = models[tier]
        w = weights.make(m, seed)
        widest[tier] = float(np.max(gaps(m, w, prompts, served,
                                         quant=control_quant(m), control=control)))
        del w
    return widest
