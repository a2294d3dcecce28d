"""Busy device time inside admissions (``bench.admit``: batch-1 prefill and
slot write) per prompt token admitted, in µs, over the traced window."""


def read(ctx):
    admits = ctx["trace"].admits
    tokens = sum(a.plen for a in admits)
    return 1e-3 * sum(a.busy_ns for a in admits) / tokens if tokens else None
