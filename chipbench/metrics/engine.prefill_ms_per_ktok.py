"""Prefill engine time per 1000 prompt tokens computed, over the window:
the engine's own step times (host clock around each prefill call, up to
`block_until_ready`) over its prompt-token counter. Moves `ttft_p90_ms`."""


def read(run):
    tokens = run.counters.get("prefill.prefill_tokens", 0)
    if tokens <= 0:
        return None
    return run.counters["prefill.clock_s"] / tokens * 1e6
