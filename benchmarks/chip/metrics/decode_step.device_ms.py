"""Busy device time per decode step, in ms: the device work inside the
harness's ``bench.step`` annotations of the traced window."""


def read(ctx):
    steps = ctx["trace"].steps
    return 1e-6 * sum(s.busy_ns for s in steps) / len(steps) if steps \
        else None
