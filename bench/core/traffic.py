"""The one traffic generator: it reads a mix file (bench/traffic/*.json)
and turns it, with a run's seed, into requests.

Every seed gets the same work.  In a chat mix the sizes, the turn
counts, the think times, the arrival times and which sessions share a
system prompt are drawn once from the mix's own ``mix_seed``; the
run's seed draws the token ids.  Permuting sessions over the start
times would change which turns fall inside the window, and with them
how much there is to do (by up to a tenth of the window's output
tokens at 1 request/s), so the schedule stays fixed.  The offline pool
has fixed sizes too; the run's seed orders it.

Two arrival kinds:

* ``open_sessions`` -- multi-turn chat sessions start as a Poisson
  process; turn k+1 is due a think time after turn k was due, and its
  prompt is turn k's prompt, then fresh tokens of turn k's output
  length, then the new message.  System prompts are shared across
  sessions (Zipf choice) unless ``share_prefixes`` is false, in which
  case every token of every session is fresh and only the lengths and
  the schedule stay.
* ``closed`` -- offline generation: ``concurrency`` requests in flight
  at all times, drawn in order from a fixed pool.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

import numpy as np


@dataclass
class Req:
    rid: int
    due_s: float              # offset from the start of the traffic
    prompt: np.ndarray        # int32 token ids
    max_new_tokens: int
    session: int = -1
    turn: int = 0


def _lognormal(rng, spec: Dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def seed_list(seed: int) -> List[int]:
    """A seed of any size as 32-bit words for numpy's SeedSequence."""
    if seed < 0:
        raise ValueError("seeds are non-negative")
    out = []
    while True:
        out.append(seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return out


def sessions(mix: Dict, seed: int, horizon_s: float, vocab: int) -> List[Req]:
    """Chat requests due before ``horizon_s`` (lead-in included),
    sorted by due time."""
    base = np.random.default_rng(mix["mix_seed"])
    run = np.random.default_rng(seed_list(seed))
    n_turn_mean = mix["turns"]["mean"]
    session_rate = mix["rate_rps"] / n_turn_mean
    # enough sessions for the longest window a run can ask for
    n = int(np.ceil(session_rate * mix["max_horizon_s"] * 1.5)) + 8
    gaps = base.exponential(1.0 / session_rate, n)
    p = 1.0 / n_turn_mean
    turns = np.minimum(base.geometric(p, n), mix["turns"]["max"])
    sys_spec = mix["system_prompts"]
    n_sys = sys_spec["count"]
    sys_len = base.integers(sys_spec["min"], sys_spec["max"] + 1, n_sys)
    zipf = 1.0 / np.arange(1, n_sys + 1) ** sys_spec["zipf_s"]
    sys_pick = base.choice(n_sys, n, p=zipf / zipf.sum())
    tmax = mix["turns"]["max"]
    msg = _lognormal(base, mix["message"], n * tmax).reshape(n, tmax)
    out = _lognormal(base, mix["output"], n * tmax).reshape(n, tmax)
    think = base.uniform(mix["think_s"]["min"], mix["think_s"]["max"],
                         (n, tmax))
    # the run's seed draws only token ids: every seed serves the same
    # sessions at the same times
    starts = np.cumsum(gaps)
    used = int(np.searchsorted(starts, horizon_s))
    sys_tokens = [run.integers(0, vocab, L).astype(np.int32)
                  for L in sys_len]
    share = mix.get("share_prefixes", True)
    ctx = mix["context_tokens"]
    reqs: List[Req] = []
    for j in range(used):
        k_sys = sys_pick[j]
        prompt = (sys_tokens[k_sys] if share else
                  run.integers(0, vocab, sys_len[k_sys]).astype(np.int32))
        due = starts[j]
        for t in range(turns[j]):
            if t:
                fresh = run.integers(0, vocab, out[j, t - 1] + msg[j, t])
                prompt = np.concatenate([prompt, fresh.astype(np.int32)])
                due += think[j, t - 1]
            else:
                first = run.integers(0, vocab, msg[j, 0]).astype(np.int32)
                prompt = np.concatenate([prompt, first])
            if due >= horizon_s or len(prompt) + out[j, t] > ctx:
                break
            reqs.append(Req(-1, float(due), prompt, int(out[j, t]), j, t))
    reqs.sort(key=lambda r: r.due_s)
    for i, r in enumerate(reqs):
        r.rid = i
    return reqs


class ClosedPool:
    """Offline requests in a fixed order: the pool's sizes come from the
    mix seed, their order and tokens from the run seed."""

    def __init__(self, mix: Dict, seed: int, vocab: int):
        base = np.random.default_rng(mix["mix_seed"])
        n = mix["pool"]
        self.prompt_len = _lognormal(base, mix["prompt"], n)
        self.out_len = _lognormal(base, mix["output"], n)
        self.run = np.random.default_rng(seed_list(seed))
        self.order = self.run.permutation(n)
        self.vocab = vocab
        self.next = 0

    def take(self) -> Req:
        j = self.order[self.next % len(self.order)]
        rid = self.next
        self.next += 1
        toks = self.run.integers(0, self.vocab, self.prompt_len[j])
        return Req(rid, 0.0, toks.astype(np.int32), int(self.out_len[j]))
