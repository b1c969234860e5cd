"""Traffic: every run of a mix sends the same corpus, so every seed gets the
same work; the seed draws only which finished requests the check compares."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import check, traffic  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
# every mix of the benchmark, and an open-loop one at a cell's sizes
MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(ROOT, "bench",
                                                        "traffic")))
MIXES.append("open-loop")
SEEDS = (3_000_000_001, 3_000_000_002)   # beyond 32 signed bits


def mix(name):
    if name == "open-loop":
        m = mix("decode-backlog")
        m.update(loop="open", rate_per_s=6.0)
        return m
    with open(os.path.join(ROOT, "bench", "traffic", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = traffic.generate(mix(name), 30, 49155)
    b = traffic.generate(mix(name), 30, 49155)
    assert a == b


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_other_order_same_work(name):
    """Runs with other seeds send the same requests in the same order, and
    compare another sample of them, the longest always in it."""
    reqs = traffic.generate(mix(name), 30, 49155)   # takes no seed
    records = [{"prompt": r.prompt, "tokens": [0] * r.max_new,
                "done": {}, "error": None} for r in reqs[:64]]
    picked = [[id(r) for r in check.sample(records, s, min_tokens=10**9,
                                           max_requests=8)]
              for s in SEEDS]
    longest = id(max(records, key=lambda r: len(r["tokens"])))
    assert picked[0] != picked[1]
    assert picked[0][0] == picked[1][0] == longest
    assert len(set(picked[0])) == len(picked[0]) == 8


@pytest.mark.parametrize("name", MIXES)
def test_lengths_follow_the_mix(name):
    m = mix(name)
    reqs = traffic.generate(m, 30, 49155)
    plens = np.array([len(r.prompt) for r in reqs])
    olens = np.array([r.max_new for r in reqs])
    for arr, dist in ((plens, m["prompt_len"]), (olens, m["output_len"])):
        assert arr.min() >= dist["min"] and arr.max() <= dist["max"]
        assert abs(np.median(arr) - dist["median"]) <= 0.05 * dist["median"]
    assert plens.max() <= m["engine"]["max_prompt_len"]
    assert olens.max() <= m["engine"]["max_new_cap"]
    if m["loop"] == "open":
        span = reqs[-1].offset_s
        want = traffic.num_requests(m, 30) / m["rate_per_s"]
        assert span == pytest.approx(want, rel=0.01)


def test_first_budgets_spread_the_closed_loop():
    m = mix("decode-backlog")
    firsts = traffic.first_budgets([1000] * m["clients"])
    assert len(set(firsts)) > m["clients"] // 2
    assert all(1 <= f <= 1000 for f in firsts)

