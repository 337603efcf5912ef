"""The traffic generator repeats per seed and follows its mix."""
from __future__ import annotations

import json
import statistics

import numpy as np
import pytest

from chipbench.traffic import generator as gen

MIXES = ("chat",)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    m = gen.load_mix(mix)
    a = gen.make_requests(m, 2 ** 33 + 7, 1000)
    b = gen.make_requests(m, 2 ** 33 + 7, 1000)
    c = gen.make_requests(m, 2 ** 33 + 8, 1000)
    assert [(r.due_s, r.prompt, r.max_tokens) for r in a] == \
        [(r.due_s, r.prompt, r.max_tokens) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in c]
    # the schedule is the same for every seed; only the ids differ
    assert [(r.due_s, len(r.prompt), r.max_tokens) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_tokens) for r in c]


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_offers_the_same_sizes(mix):
    """Each block of ``block`` requests holds every quantile once, so the
    sizes of whole blocks are one multiset whatever the seed."""
    m = gen.load_mix(mix)
    k = m["block"]
    sizes = []
    for seed in (1, 99, 2 ** 40 + 3):
        reqs = gen.make_requests(m, seed, 1000)[:k]
        sizes.append((sorted(len(r.prompt) for r in reqs),
                      sorted(r.max_tokens for r in reqs)))
    assert sizes[0] == sizes[1] == sizes[2]


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_follow_the_clipped_lognormal(mix):
    m = gen.load_mix(mix)
    reqs = gen.make_requests(m, 5, 1000)
    for key, lens in (("prompt", [len(r.prompt) for r in reqs]),
                      ("output", [r.max_tokens for r in reqs])):
        d = m[key]
        assert d["lo"] <= min(lens) and max(lens) <= d["hi"]
        med = statistics.median(lens)
        assert abs(np.log(med / d["median"])) < 0.15
        # a heavy tail: the top quantile sits well above the median
        assert max(lens) > 2.0 * med


def test_open_loop_rate_and_ids():
    m = gen.load_mix("chat")
    reqs = gen.make_requests(m, 3, 1000)
    due = np.array([r.due_s for r in reqs])
    assert due[0] == 0.0 and np.all(np.diff(due) > 0)
    k = m["block"]
    # whole blocks arrive at the mix's mean rate exactly
    span = due[k] - due[0]
    assert span == pytest.approx(k / m["rate_per_s"], rel=0.25)
    ids = np.concatenate([r.prompt for r in reqs])
    assert ids.min() >= 0 and ids.max() < 1000


def test_unknown_loop_is_refused():
    m = dict(gen.load_mix("chat"), loop="closed")
    with pytest.raises(ValueError, match="unknown loop"):
        gen.make_requests(m, 3, 1000)


@pytest.mark.parametrize("mix", MIXES)
def test_a_window_takes_whole_blocks(mix):
    """Every request of a block is due inside one measured window, so a
    window samples each quantile of the mix, not the few that a fixed
    order happens to put first."""
    bench = json.loads((gen.MIX_DIR.parents[1] / "BENCHMARK.json")
                       .read_text())
    m = gen.load_mix(mix)
    reqs = gen.make_requests(m, 3, 1000)
    k = m["block"]
    assert reqs[k - 1].due_s < bench["run_seconds"] <= reqs[k].due_s


def test_exponential_quantiles_mean_one():
    q = gen.exponential_quantiles(16)
    assert q.mean() == pytest.approx(1.0)
    assert np.all(np.diff(q) > 0)
