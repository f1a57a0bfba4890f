"""Mean decode step time of the decode engine over the window: its own
step times (host clock around each decode call, up to
`block_until_ready`) over its step counter. Moves `tpot_p90_ms`."""


def read(run):
    steps = run.counters.get("decode.steps", 0)
    if steps <= 0:
        return None
    return run.counters["decode.clock_s"] / steps * 1e3
