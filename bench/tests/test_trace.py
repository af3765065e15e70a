import json
from pathlib import Path

import pytest

from bench.core import trace

DATA = Path(__file__).parent / "data"


def _tr():
    ms = 1e6
    # one chip, window 0..100 ms; ops busy 10-30 and 50-60 ms (an
    # all-reduce 55-60 overlaps nothing; another at 20-25 overlaps a
    # fusion); a decode round 5-35, a prefill round 45-65, an admit span
    # 35-45 covering the idle gap 30-50
    dev = {"ops": [("fusion.1", 10 * ms, 30 * ms),
                   ("all-reduce.3", 20 * ms, 25 * ms),
                   ("fusion.2", 50 * ms, 55 * ms),
                   ("all-reduce.4", 55 * ms, 60 * ms)],
           "modules": [("jit_step(12)", 10 * ms, 30 * ms),
                       ("jit_pim_page_init_batched(3)", 50 * ms, 60 * ms)]}
    host = [("bench.mark", 0, 0.01 * ms), ("bench.mark", 100 * ms,
                                            100.01 * ms),
            ("round.decode", 5 * ms, 35 * ms),
            ("admit", 35 * ms, 45 * ms),
            ("round.prefill", 45 * ms, 65 * ms)]
    return {"devices": {"/device:TPU:0": dev}, "host": host}


def test_summary_on_a_hand_made_trace():
    s = trace.summarise(_tr())
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx(0.030)
    assert s.rounds == [("round.decode", pytest.approx(0.030),
                         pytest.approx(0.020)),
                        ("round.prefill", pytest.approx(0.020),
                         pytest.approx(0.010))]
    assert s.collective_s == pytest.approx(0.010)
    assert s.collective_exposed_s == pytest.approx(0.005)
    assert s.module_s["jit_pim_page_init_batched"] == pytest.approx(0.010)
    gaps = dict((round(t, 6), n) for n, t in s.idle_gaps)
    assert gaps[0.02] == "admit"          # 30-50 ms
    assert gaps[0.04] == "other"          # 60-100 ms
    assert gaps[0.01] == "round.decode"   # 0-10 ms, inside the round span


def test_no_marks_or_no_ops_reads_nothing():
    tr = _tr()
    tr["host"] = [h for h in tr["host"] if h[0] != "bench.mark"]
    assert trace.summarise(tr) is None


def test_union_and_intersection():
    u = trace.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0, 3), (5, 8)]
    assert trace.intersect(u, [(2, 6)]) == [(2, 3), (5, 6)]


def test_itl_gaps_count_the_gap_open_at_the_close():
    from types import SimpleNamespace as NS
    from bench.core.readers import window_gaps
    win = NS(start_ms=100.0, end_ms=200.0)
    recs = [
        # finished in the window: gaps ending in it (the 90->110 one too)
        NS(token_ms=[90.0, 110.0, 130.0], done=True),
        # stalled at the close: its last gap is still open, 150 -> 200
        NS(token_ms=[120.0, 150.0], done=False),
        # its next token came after the close: open gap 180 -> 200
        NS(token_ms=[170.0, 180.0, 260.0], done=True),
        # no token yet: time to first token's business, not a gap
        NS(token_ms=[], done=False),
    ]
    gaps = window_gaps(NS(win=win, records=recs))
    assert sorted(gaps) == [10.0, 20.0, 20.0, 20.0, 30.0, 50.0]
