"""Share of the traced window in which no operation ran on the device, as
a fraction: 1 - busy / window, busy being the union of the device's
operation intervals. Moves `output_tok_per_s`."""


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    return 1.0 - run.trace.busy_s / run.trace.window_s
