import json
from pathlib import Path

import numpy as np
import pytest

from bench.core import traffic

ROOT = Path(__file__).resolve().parents[2]
CHAT = json.loads((ROOT / "bench/traffic/chat-prefix.json").read_text())
OFFLINE = json.loads((ROOT / "bench/traffic/tp4-offline.json").read_text())
VOCAB = 49155


def _sessions(seed, mix=CHAT, horizon=50.0):
    return traffic.sessions(mix, seed, horizon, VOCAB)


def test_chat_lengths_and_schedule():
    reqs = _sessions(3_000_000_007)
    # the arrival rate ramps up over the first turns of the sessions
    assert 0.5 < len(reqs) / (CHAT["rate_rps"] * 50.0) < 2.0
    dues = [r.due_s for r in reqs]
    assert dues == sorted(dues) and dues[-1] < 50.0
    b = CHAT["bounds"]
    for r in reqs:
        assert b["min_seq_tokens"] <= len(r.prompt)
        assert len(r.prompt) + r.max_new_tokens <= CHAT["context_tokens"]
        assert CHAT["output"]["min"] <= r.max_new_tokens <= \
            CHAT["output"]["max"]
        assert r.prompt.dtype == np.int32 and r.prompt.max() < VOCAB


def test_chat_same_work_for_every_seed():
    a, b = _sessions(11), _sessions(2 ** 33 + 11)
    # the same requests at the same times, with other token ids
    assert [(r.due_s, len(r.prompt), r.max_new_tokens, r.session, r.turn)
            for r in a] == [(r.due_s, len(r.prompt), r.max_new_tokens,
                             r.session, r.turn) for r in b]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in
               zip(_sessions(11), a))


def test_chat_turns_extend_their_history_and_share_system_prompts():
    reqs = _sessions(5)
    by_session = {}
    for r in reqs:
        by_session.setdefault(r.session, []).append(r)
    multi = [s for s in by_session.values() if len(s) > 1]
    assert multi
    for turns in multi:
        for prev, nxt in zip(turns, turns[1:]):
            assert np.array_equal(nxt.prompt[:len(prev.prompt)], prev.prompt)
            assert len(nxt.prompt) > len(prev.prompt) + prev.max_new_tokens
            assert 4.0 <= nxt.due_s - prev.due_s <= 12.0
    firsts = [t[0].prompt[:1024] for t in by_session.values()]
    distinct = {f.tobytes() for f in firsts}
    assert len(distinct) <= CHAT["system_prompts"]["count"] < len(firsts)


def test_unshared_mix_keeps_lengths_but_shares_nothing():
    mix = dict(CHAT, share_prefixes=False)
    a, b = _sessions(9), _sessions(9, mix)
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    heads = {r.prompt[:1024].tobytes() for r in b if r.turn == 0}
    assert len(heads) == sum(r.turn == 0 for r in b)


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 9])
def test_offline_pool(seed):
    pool = traffic.ClosedPool(OFFLINE, seed, 256000)
    reqs = [pool.take() for _ in range(OFFLINE["pool"])]
    b = OFFLINE["bounds"]
    for r in reqs:
        assert b["min_seq_tokens"] <= len(r.prompt) <= max(b["prompt_buckets"])
        assert len(r.prompt) + r.max_new_tokens <= b["max_seq_tokens"]
    # prompts fall in the two prefill length buckets the warm-up covers
    assert {1 << (len(r.prompt) - 1).bit_length() for r in reqs} <= \
        set(b["prompt_buckets"])
    other = traffic.ClosedPool(OFFLINE, seed + 1, 256000)
    assert sorted(len(r.prompt) for r in reqs) == sorted(
        len(other.take().prompt) for _ in range(OFFLINE["pool"]))
