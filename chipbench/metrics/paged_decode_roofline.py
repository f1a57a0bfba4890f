"""`paged_decode`'s share of its roofline, in percent: for each traced
decode step, the least time the kernel's work needs (QK and PV over each
sequence's live positions, and reading their keys and values once:
`costs.attention_decode_*`, bound by bandwidth), summed, over the summed
device time of the kernel's operations. Moves `tpot_p90_ms`."""
from chipbench import costs


def read(run):
    if run.trace is None:
        return None
    need = t = 0.0
    for rec, ann in run.trace.pairs(run.steps):
        if rec.decode_ctx:
            dt = run.trace.kernel_time("paged_decode", ann)
            if dt > 0:
                need += costs.roofline_s(
                    costs.attention_decode_flops(run.dims, rec.decode_ctx),
                    costs.attention_decode_bytes(run.dims, rec.decode_ctx),
                    run.peak)
                t += dt
    return 100.0 * need / t if t > 0 else None
