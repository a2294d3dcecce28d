"""The decode step's share of its roofline, %: the least time the traced
steps could take over their busy device time.  Each step must read the
served weights it touches once (``ctx["weight_bytes"]``) and the KV its
tokens attend to (``ctx["decode_kv_bytes"]``), and do the FLOPs its tokens
require (``ctx["decode_flops"]``), each as the configuration's
architecture counts it from the served arrays' shapes and dtypes; its
least time is the larger of bytes over HBM bandwidth and FLOPs over the
bf16 peak."""

import counts


def read(ctx):
    tr, rec = ctx["trace"], ctx["rec"]
    busy_s = 1e-9 * sum(s.busy_ns for s in tr.steps)
    if not tr.steps or not rec.ctx or not busy_s:
        return None
    n = len(tr.steps)
    # the window's tokens spread over its steps, scaled to the traced ones
    share = n / sum(1 for _, b, _ in rec.steps if rec.t0 <= b <= rec.t_end)
    flops = ctx["decode_flops"] * share
    nbytes = n * ctx["weight_bytes"] + ctx["decode_kv_bytes"] * share
    return 100.0 * counts.least_time_s(flops, nbytes, ctx["peak"]) / busy_s
