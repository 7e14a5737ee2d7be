"""The fixed-work generator: every seed offers the same work."""

import collections

import pytest

from benchmark.manifest import HERE, load_json
from benchmark.traffic_gen import closed_pool, open_schedule, totals

MIX = load_json(HERE, "traffic", "chat-open.json")
SEEDS = [0, 1, 7, 2**31 + 11, 3_000_000_019]


def multiset(reqs):
    return collections.Counter((len(r.prompt), r.out_len) for r in reqs)


@pytest.mark.parametrize("seconds", [10.0, 51.0])
def test_same_work_for_every_seed(seconds):
    runs = [open_schedule(MIX, seconds, s, 1000) for s in SEEDS]
    ramp0, win0 = runs[0]
    n = round(MIX["rate_per_s"] * seconds)
    assert len(win0) == n
    for ramp, win in runs[1:]:
        assert totals(win) == totals(win0) and totals(ramp) == totals(ramp0)
        assert multiset(win) == multiset(win0)
        meas = [r for r in win if r.measured]
        assert multiset(meas) == multiset([r for r in win0 if r.measured])
    # the seed rotates the measured trace and draws the tokens
    lens = [[len(r.prompt) for r in win if r.measured] for _r, win in runs]
    assert lens[1] != lens[0]
    k = lens[0].index(lens[1][0])
    assert any(lens[1] == lens[0][j:] + lens[0][:j]
               for j in range(len(lens[0])) if lens[0][j] == lens[1][0])
    assert runs[1][1][0].prompt != win0[0].prompt
    # ramp and tail keep the mix's own order
    assert [len(r.prompt) for r in runs[1][0]] == [len(r.prompt)
                                                   for r in ramp0]


def test_due_times_inside_the_window_and_measured_before_the_tail():
    seconds = 51.0
    for s in SEEDS:
        _ramp, win = open_schedule(MIX, seconds, s, 1000)
        due = [r.due for r in win]
        assert due == sorted(due)
        assert 0.0 <= due[0] and due[-1] < seconds
        cut = seconds - MIX["tail_seconds"]
        slot = 1.0 / MIX["rate_per_s"]
        assert all(r.due < cut + slot for r in win if r.measured)
        assert all(r.due >= cut - slot
                   for r in win if not r.measured)
        # request i is due in slot i of width 1/rate: exactly N arrivals
        assert all(int(d * MIX["rate_per_s"] + 1e-9) == i
                   for i, d in enumerate(due))


def test_lengths_span_the_distribution():
    _ramp, win = open_schedule(MIX, 51.0, 3, 1000)
    p = sorted(len(r.prompt) for r in win if r.measured)
    o = sorted(r.out_len for r in win if r.measured)
    assert MIX["prompt_len"]["lo"] <= p[0] <= 40 and 480 <= p[-1] <= 512
    assert 16 <= o[0] <= 18 and 94 <= o[-1] <= 96


@pytest.mark.parametrize("base", ["traffic", "rehearsal/traffic"])
def test_closed_pool_is_a_rotation_of_one_multiset(base):
    m = load_json(HERE, base, "grpo-rollout-sat.json")
    pools = [closed_pool(m, s, 1000) for s in SEEDS]
    for p in pools[1:]:
        assert multiset(p) == multiset(pools[0])
    assert all(r.group_size == m.get("group_size", 1) for r in pools[0])
    orders = {tuple(len(r.prompt) for r in p) for p in pools}
    assert len(orders) > 1
    assert pools[0][0].prompt != pools[1][0].prompt
