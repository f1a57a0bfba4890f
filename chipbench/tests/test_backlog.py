"""The sweep's backlog counts requests due that have not reached decode,
so a queue before decode shows as well as one before prefill."""
from types import SimpleNamespace

from chipbench import harness, traffic


def test_backlog_counts_requests_not_yet_decoding():
    reqs = [traffic.Req(i, due, (1, 2, 3), out, "window")
            for i, (due, out) in enumerate([(0.0, 8), (1.0, 8), (2.0, 8),
                                            (3.0, 1), (9.0, 8)])]
    cluster = SimpleNamespace(prefill=[None], decode=[None])
    c = harness.Client(cluster, reqs, clock=None, annotate=None)
    c.origin = 100.0
    c.stamps[0] = [100.5, 100.6, 100.7]   # decoding since 100.6
    c.stamps[1] = [101.5]                 # prefilled, waiting for decode
    c.stamps[3] = [103.2]                 # one token: done at its first
    # request 2 has no token; request 4 is not due by 105
    assert c.backlog(100.55) == 1         # 0 has only its first token
    assert c.backlog(102.0) == 2          # 1 waits for decode, 2 is due
    assert c.backlog(105.0) == 2
    assert c.backlog(110.0) == 3          # 4 is due too
