"""Sequences per decode step over the window, from the decode engine's
token and step counters. Moves `output_tok_per_s`."""


def read(run):
    steps = run.counters.get("decode.steps", 0)
    if steps <= 0:
        return None
    return run.counters["decode.decode_tokens"] / steps
