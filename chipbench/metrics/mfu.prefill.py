"""Model FLOP utilisation of the prefill program, in percent: the FLOPs
that the traced prompts need (`costs.prefill_flops` at their real
lengths; padding to a bucket counts as waste) over the prefill program's
device time times the chip's peak. Moves `ttft_p90_ms`."""
from chipbench import costs


def read(run):
    if run.trace is None:
        return None
    flops = t = 0.0
    for rec, ann in run.trace.pairs(run.steps):
        if rec.prefill_tokens:
            dt = run.trace.program_time("prefill", ann)
            if dt > 0:
                flops += costs.prefill_flops(run.dims, rec.prefill_tokens)
                t += dt
    return 100.0 * flops / (t * run.peak["flops"]) if t > 0 else None
