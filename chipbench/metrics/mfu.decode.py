"""The decode step's share of the chip's peak, in percent: for each traced
decode step, the least time its work needs (the larger of its FLOPs over
peak FLOP/s and its bytes, weights plus live KV, over peak bandwidth:
`costs.roofline_s`), summed, over the decode program's device time. Decode
is bound by bandwidth, so this is the bytes bound. Moves
`output_tok_per_s`."""
from chipbench import costs


def read(run):
    if run.trace is None:
        return None
    need = t = 0.0
    for rec, ann in run.trace.pairs(run.steps):
        if rec.decode_ctx:
            dt = run.trace.program_time("decode", ann)
            if dt > 0:
                need += costs.roofline_s(
                    costs.decode_step_flops(run.dims, rec.decode_ctx),
                    costs.decode_step_bytes(run.dims, rec.decode_ctx),
                    run.peak)
                t += dt
    return 100.0 * need / t if t > 0 else None
