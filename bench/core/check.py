"""What decides ``correct``: the served tokens against the plain
reference.

Once the window has closed and the engine is gone, a sample of the
finished requests, drawn from the seed and always holding the longest,
is run through the float32 reference of the configuration's architecture
module (its ``logits_at``, bench/core/arch.py) over its prompt and its
served tokens.  At each served position the number compared is
the gap by which the served token's reference logit lies below the
reference's best; the cell's reading is the widest gap in the sample.
Greedy decoding in bfloat16 serves the reference's best token or one
within rounding of it, so the gap stays small; a wrong token, a wrong
cache page or a missing collective leaves it at the logits' own scale.

The control (``control_gaps``) reads the same gap for the token that the
module's control, the reference one precision step lower (float8 for
bfloat16), puts first.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from bench.core.traffic import seed_list


def sample(records, seed: int, min_tokens: int, max_seqs: int) -> List:
    """Finished requests: the one with the most served tokens, then
    others in an order drawn from the seed, until ``min_tokens`` served
    tokens or ``max_seqs`` requests."""
    done = [r for r in records if r.done and r.tokens]
    if not done:
        return []
    done.sort(key=lambda r: (-len(r.tokens), r.rid))
    rng = np.random.default_rng(seed_list(seed) + [0x5A4D])
    picked = [done[0]]
    for i in rng.permutation(len(done) - 1) + 1:
        if (sum(len(r.tokens) for r in picked) >= min_tokens
                or len(picked) >= max_seqs):
            break
        picked.append(done[i])
    return picked


def _inputs(picked):
    seqs, pos, tgt = [], [], []
    for r in picked:
        P = len(r.prompt)
        seqs.append(np.concatenate([r.prompt, np.asarray(r.tokens[:-1],
                                                         np.int32)]))
        pos.append(np.arange(P - 1, P - 1 + len(r.tokens)))
        tgt.append(np.asarray(r.tokens, np.int32))
    return seqs, pos, tgt


def served_gap(arch, cfg: Dict, seed: int, picked: Sequence) -> Dict:
    """Widest gap of a served token below the reference's best."""
    seqs, pos, tgt = _inputs(picked)
    worst, n = 0.0, 0
    for lg, t in zip(arch.logits_at(cfg, seed, seqs, pos), tgt):
        gap = lg.max(-1) - np.take_along_axis(lg, t[:, None], -1)[:, 0]
        worst = max(worst, float(gap.max()))
        n += len(t)
    return {"max_logit_gap": worst, "tokens": n}


def control_gaps(arch, cfg: Dict, seed: int, picked: Sequence) -> Dict:
    """The control: at the same positions, the gap of the token the
    module's control puts first."""
    seqs, pos, _ = _inputs(picked)
    ctl = arch.logits_at(cfg, seed, seqs, pos, quant=True)
    ref = arch.logits_at(cfg, seed, seqs, pos)
    worst, n = 0.0, 0
    for r, c in zip(ref, ctl):
        pick = c.argmax(-1)
        gap = r.max(-1) - np.take_along_axis(r, pick[:, None], -1)[:, 0]
        worst = max(worst, float(gap.max()))
        n += r.shape[0]
    return {"max_logit_gap": worst, "tokens": n}


def budgets_met(picked: Sequence) -> bool:
    """Each finished request served exactly its token budget (greedy,
    no end-of-sequence token)."""
    return all(len(r.tokens) == r.max_new for r in picked)
