"""Share of the chip's bf16 peak that the window's output tokens required,
%: the FLOPs each token needed (``counts.decode_token_flops``: 2 × the
non-zero projection weights, the tied head, attention over its live
context) summed over the tokens decoded in the window, over the window's
host-clock length and the peak."""

import counts


def read(ctx):
    rec = ctx["rec"]
    if not rec.ctx:
        return None
    flops = counts.decode_token_flops(ctx["dims"], ctx["nnz_layer"], rec.ctx)
    return 100.0 * flops / (rec.t_end - rec.t0) / ctx["peak"]["flops_bf16"]
