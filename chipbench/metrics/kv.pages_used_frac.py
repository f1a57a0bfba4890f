"""Share of the decode engine's page pool in use, as a fraction, averaged
over the window's decode steps (`kv.used_pages / kv.num_pages` read after
each step). Moves `output_tok_per_s`."""


def read(run):
    fr = [s.kv_used_frac for s in run.steps if s.kv_used_frac is not None]
    return sum(fr) / len(fr) if fr else None
