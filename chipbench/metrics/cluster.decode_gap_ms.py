"""Mean stall between decode steps that continuing sequences sit through,
in ms: the decode engine's `held_gap_s` (host clock, from the end of one
decode step to the start of the next while it holds a sequence that is
not done: prefills, KV splices and host work on the shared chip) over its
step counter. Read from traced runs, so the two gaps between step records
that hold the profiler's start and stop (`trace.host_span`) are taken
out, and the result is clipped at 0. Moves `tpot_p90_ms`.

The traced reading is not the untraced per-step stall. The profiler's
start and stop hold the client for seconds, in which arrivals pile up:
the window loses decode steps but none of its prefills, so each step
left carries more prefill. On one TPU v5e in `yi6b.longdoc` this reads
26.8-27.8 ms traced, where `held_gap_s / steps` of untraced runs is
16.3-16.5 ms; the reading also moves with the profiler's stop time."""


def _gap_around(steps, t):
    """The gap between consecutive step records that holds time `t`."""
    for a, b in zip(steps, steps[1:]):
        if a.t1 <= t < b.t0:
            return b.t0 - a.t1
    return 0.0


def read(run):
    steps = run.counters.get("decode.steps", 0)
    held = run.counters.get("decode.held_gap_s")
    if run.trace is None or held is None or steps <= 0:
        return None
    stall = sum(_gap_around(run.steps, t) for t in run.trace.host_span)
    return max(held - stall, 0.0) / steps * 1e3
