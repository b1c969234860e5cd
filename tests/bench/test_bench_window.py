"""The window's end-to-end metrics, from client records: a tail is taken
over every request due in the window, those still waiting included."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402


def rec(due, events):
    return {"due": due, "sent": due, "events": events, "tokens": [],
            "done": None, "error": None, "max_new": 10}


def test_ttft_counts_requests_still_waiting():
    t0, t1 = 100.0, 110.0
    records = [rec(101.0 + i, [(101.2 + i, 1)]) for i in range(8)]
    records.append(rec(102.0, []))            # no token by the window's end
    records.append(rec(95.0, [(96.0, 1)]))     # due before the window
    records.append(rec(111.0, [(111.5, 1)]))   # due after it
    samples = harness.ttft_samples(records, t0, t1)
    assert len(samples) == 9
    assert max(samples) == pytest.approx(8.0)  # waited from 102 to 110
    assert sorted(samples)[:8] == pytest.approx([0.2] * 8)


def test_ttft_tail_rises_when_one_request_starves():
    t0, t1 = 0.0, 10.0
    fast = [rec(0.1 * i, [(0.1 * i + 0.05, 1)]) for i in range(9)]
    m_ok = harness.end_to_end(fast, {"t0": t0, "t1": t1}, 1.0)
    m_bad = harness.end_to_end(fast + [rec(1.0, [])], {"t0": t0, "t1": t1},
                               1.0)
    assert m_bad["ttft_p90_s"] > m_ok["ttft_p90_s"] + 0.5


def test_tpot_and_tokens_count_only_the_window():
    t0, t1 = 10.0, 20.0
    r = rec(5.0, [(9.0, 4), (11.0, 3), (12.0, 3), (13.0, 5), (21.0, 2)])
    # in window: 11, 12, 13 s with 3 + 3 + 5 tokens
    assert harness.tpot_samples([r], t0, t1) == [pytest.approx(2.0 / 10)]
    assert harness.window_tokens([r], t0, t1) == 11
    one = rec(5.0, [(11.0, 3)])
    assert harness.tpot_samples([one], t0, t1) == []
