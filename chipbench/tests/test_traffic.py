"""Seeded traffic: the same seed gives the same requests, and every seed
serves the same work in another order."""
import numpy as np
import pytest

from chipbench import traffic
from chipbench.tests.smoke import MIX

BIG = 2 ** 33 + 17          # more than 32 bits, as seeds may be


def _mix(**kw):
    return dict(MIX, **kw)


def test_same_seed_same_requests():
    a = traffic.build_requests(MIX, BIG, 2.0, 256)
    b = traffic.build_requests(MIX, BIG, 2.0, 256)
    assert a == b


@pytest.mark.parametrize("phase", ["warm", "window", "tail"])
def test_seeds_share_the_schedule(phase):
    a = [r for r in traffic.build_requests(MIX, BIG, 2.0, 256)
         if r.phase == phase]
    b = [r for r in traffic.build_requests(MIX, BIG + 1, 2.0, 256)
         if r.phase == phase]
    assert len(a) == len(b) == round(MIX["rate"] * {
        "warm": MIX["warm_s"], "window": 2.0, "tail": MIX["grace_s"]}[phase])
    # the same lengths at the same due times; only the token ids differ
    assert [(r.due, len(r.prompt), r.out_len) for r in a] == \
        [(r.due, len(r.prompt), r.out_len) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    # the schedule is shuffled, not sorted by length
    lens = [len(r.prompt) for r in a]
    assert len(lens) < 3 or lens != sorted(lens)


def test_window_spans_its_seconds_in_due_order():
    reqs = traffic.build_requests(MIX, 7, 3.0, 256)
    dues = [r.due for r in reqs]
    assert dues == sorted(dues)
    win = [r for r in reqs if r.phase == "window"]
    assert MIX["warm_s"] <= win[0].due and win[-1].due < MIX["warm_s"] + 3.0
    assert [r.idx for r in reqs] == list(range(len(reqs)))


def test_lengths_follow_the_mix():
    lo, hi = MIX["prompt"]["clip"]
    reqs = traffic.build_requests(MIX, 3, 20.0, 256)
    assert all(lo <= len(r.prompt) <= hi for r in reqs)
    assert all(0 < t < 256 for r in reqs for t in r.prompt)
    q = traffic.lognormal_quantiles(7.5, 0.5, (512, 3584), 1001)
    assert q[500] == int(np.exp(7.5))            # the median is e^mu
    assert q.min() == 512 and q.max() == 3584    # clipped as the mix says


def test_gaps_sum_to_the_span():
    g = traffic.exponential_gaps(50, 10.0)
    assert g.sum() == pytest.approx(10.0)
    assert np.all(np.diff(g) > 0)                # increasing quantiles
