"""Device time of the KV splice per hand-off, in ms. Each `serve.kv_handoff`
host span (one per request whose KV reaches the decode engine) is paired,
in order, with the first run of the splice program (`jit__ins`,
`trace.PROGRAMS["insert"]`) that starts after the span starts and before
the next decode step's program; the splices of the pairs are summed over
their number. A splice whose hand-off lies before the trace, or a hand-off
whose splice lies after it, is left out. None when no pair is found.
Moves `ttft_p90_ms`."""
import bisect

from chipbench import trace

HANDOFF = "serve.kv_handoff"


def _runs(tr, kind):
    """(start, device time) of each run of one program kind, in order: its
    module events, or, where the trace has no module line (the CPU), each
    stretch of its operations that no other operation breaks."""
    pat = trace.PROGRAMS[kind]
    if tr.modules:
        return sorted((e.start, e.end - e.start) for e in tr.modules
                      if pat.search(e.name))
    out, cur = [], []
    for e in sorted(tr.ops, key=lambda e: e.start) + [None]:
        if e is not None and pat.search(e.meta):
            cur.append((e.start, e.end))
        elif cur:
            out.append((cur[0][0], trace._union(cur)))
            cur = []
    return out


def read(run):
    if run.trace is None:
        return None
    handoffs = sorted(e.start for e in run.trace.host if e.name == HANDOFF)
    splices = _runs(run.trace, "insert")
    decodes = [s for s, _ in _runs(run.trace, "decode")]
    n, t, j = 0, 0.0, 0
    for a in handoffs:
        k = bisect.bisect_right(decodes, a)
        b = decodes[k] if k < len(decodes) else float("inf")
        while j < len(splices) and splices[j][0] < a:
            j += 1
        if j < len(splices) and splices[j][0] < b:
            n += 1
            t += splices[j][1]
            j += 1
    return t / n * 1e3 if n else None
