"""Host time of one decode step outside the device wait, in ms: the decode
engine's `dispatch_s` (building the token array and enqueueing the step)
plus `pull_s` (argmax, the copy of the tokens to the host, sampling),
host clock, over its step counter. Moves `output_tok_per_s`."""


def read(run):
    steps = run.counters.get("decode.steps", 0)
    parts = [run.counters.get(f"decode.{k}") for k in ("dispatch_s",
                                                       "pull_s")]
    if steps <= 0 or None in parts:
        return None
    return sum(parts) / steps * 1e3
