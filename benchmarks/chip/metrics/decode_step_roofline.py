"""The decode step's share of its roofline, %: the least time the traced
steps could take over their busy device time.  Each step must read every
served weight once (the non-zero payload and metadata of kernel-served
roles, dense roles whole, the tied head) and the live KV context of each
slot, and do the FLOPs its tokens require (``counts.decode_token_flops``);
its least time is the larger of bytes over HBM bandwidth and FLOPs over
the bf16 peak, all counted from the served arrays' shapes and dtypes."""

import counts


def read(ctx):
    tr, rec = ctx["trace"], ctx["rec"]
    busy_s = 1e-9 * sum(s.busy_ns for s in tr.steps)
    if not tr.steps or not rec.ctx or not busy_s:
        return None
    n = len(tr.steps)
    # the window's tokens spread over its steps, scaled to the traced ones
    share = n / sum(1 for _, b, _ in rec.steps if rec.t0 <= b <= rec.t_end)
    flops = counts.decode_token_flops(ctx["dims"], ctx["nnz_layer"],
                                      rec.ctx) * share
    nbytes = n * ctx["weight_bytes"] \
        + ctx["kv_bytes_per_position"] * sum(rec.ctx) * share
    return 100.0 * counts.least_time_s(flops, nbytes, ctx["peak"]) / busy_s
